"""The load generator and the closed-loop runner, on the CPU."""
import math
import time

from bench.harness import serve, traffic as T
from bench.tests import tiny

PAGE = 16


def test_same_seed_same_schedule():
    a = T.schedule(tiny.OPEN, 2 ** 31 + 5, 4.0, 512, PAGE)
    b = T.schedule(tiny.OPEN, 2 ** 31 + 5, 4.0, 512, PAGE)
    assert [(r.prompt, r.max_new, r.due) for r in a] == \
        [(r.prompt, r.max_new, r.due) for r in b]


def test_seeds_permute_one_multiset_of_sizes_and_gaps():
    a = T.schedule(tiny.OPEN, 1, 4.0, 512, PAGE)
    b = T.schedule(tiny.OPEN, 2, 4.0, 512, PAGE)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    gaps = lambda s: sorted(round(y.due - x.due, 9)
                            for x, y in zip([T.Req(-1, [], 0)] + s, s))
    assert gaps(a) == gaps(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_fixed_order_is_the_same_for_every_seed():
    a = T.schedule(tiny.CLOSED, 1, 4.0, 512, PAGE)
    b = T.schedule(tiny.CLOSED, 99, 4.0, 512, PAGE)
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]


def test_prompts_are_whole_pages_and_share_the_system_prompt():
    reqs = T.schedule(tiny.OPEN, 3, 4.0, 512, PAGE)
    shared = tiny.OPEN["shared_prefix"]
    assert all(len(r.prompt) % PAGE == 0 for r in reqs)
    assert len({tuple(r.prompt[:shared]) for r in reqs}) == 1
    assert all(tiny.OPEN["output"]["min"] <= r.max_new
               <= tiny.OPEN["output"]["max"] for r in reqs)


def test_stratified_lognormal_quantiles():
    q = T.lognormal_quantiles(5, 100, 0.5, 60, 150)
    assert q == sorted(q) and q[2] == 100 and q[0] == 60 and q[-1] == 150
    assert T.lognormal_quantiles(3, 100, 0.1, 10, 1000, 16)[1] == 112
    e = T.exponential_quantiles(1000, 2.0)
    assert math.isclose(sum(e) / len(e), 2.0, rel_tol=0.01)


def test_open_loop_count_covers_preroll_and_window():
    assert T.n_requests(tiny.OPEN, 4.0) == math.ceil(4.0 * (1 + 4.0))


class FakeEngine:
    """Admits everything at once and emits one token per tick."""

    def __init__(self):
        self.queue, self.active = [], []
        self.stats = {"shared_tokens": 0, "backpressure": 0}

    @property
    def n_active(self):
        return len(self.active)

    def submit(self, req):
        self.queue.append(req)

    def tick(self):
        time.sleep(0.001)
        for r in self.queue:
            r.pending = 0
        self.active += self.queue
        self.queue = []
        for r in self.active:
            r.generated.append(r.pending)
            r.done = len(r.generated) >= r.max_new
        self.active = [r for r in self.active if not r.done]


def test_closed_loop_sends_each_clients_next_request_on_completion():
    mix = dict(tiny.CLOSED, clients=2, requests_per_client=40)
    reqs = T.schedule(mix, 7, 0.2, 512, PAGE)
    win = serve.Runner(FakeEngine(), reqs, mix, 0.2).run()
    sent = [r for r in reqs if r.submitted_at is not None]
    assert len(sent) > 2 and win.ticks
    for r in sent:
        assert r.client == r.idx % 2
        if r.idx >= 2:
            prev = reqs[r.idx - 2]
            assert len(prev.token_times) == prev.max_new
            assert r.due_at == prev.token_times[-1]
    # every tick's decode positions are the emitting requests' positions
    t = win.ticks[0]
    assert len(t.decode_pos) == sum(
        1 for r in sent for x in r.token_times if x == t.end)
