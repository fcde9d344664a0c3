"""The readers of the engine's own request stamps, on hand-made requests:
queue wait (submitted -> admitted) over the requests due in the window,
prefill (admitted -> first token on the host) over those admitted in it,
and nothing read where the engine stamps nothing."""
import time
from types import SimpleNamespace

import pytest

from bench.harness import traffic as T
from bench.harness.metrics import Context, reader
from bench.harness.serve import Window
from repro.serve.engine import Request

WIN = Window(open=100.0, close=110.0, end=110.5)


def req(idx, due, submitted, admitted=None, prefilled=None):
    r = T.Req(idx, [1, 2, 3], 4, due_at=due)
    r.engine_req = Request(idx, r.prompt, r.max_new, submitted_at=submitted,
                           admitted_at=admitted, prefilled_at=prefilled)
    return r


def ctx(reqs):
    return Context({}, 16, 128, WIN, reqs, {}, None, None)


def stamped():
    return [req(0, 101.0, 101.0, 101.2, 101.5),     # due in the window
            req(1, 102.0, 102.0, 103.0, 103.1),     # due in the window
            req(2, 99.0, 99.0, 100.5, 100.9),       # admitted in it
            req(3, 111.0, 111.0)]                   # due after it


def test_admit_wait_over_requests_due_in_the_window():
    # waits 0.2 and 1.0 s: p90 = 0.2 + 0.9 * 0.8
    assert reader("engine.admit_wait_p90_ms")(ctx(stamped())) == \
        pytest.approx(920.0)


def test_prefill_over_requests_admitted_in_the_window():
    # 0.3, 0.1 and 0.4 s: p90 = 0.3 + 0.8 * 0.1
    assert reader("engine.prefill_p90_ms")(ctx(stamped())) == \
        pytest.approx(380.0)


def test_a_request_never_admitted_waits_until_the_end():
    reqs = stamped()[:2] + [req(4, 105.0, time.perf_counter() - 20.0)]
    # waits 0.2, 1.0 and at least 20 s: p90 >= 1.0 + 0.8 * 19.0
    assert reader("engine.admit_wait_p90_ms")(ctx(reqs)) >= 16_200.0
    # it has no prefill to count: 0.1 + 0.9 * 0.2 over the other two
    assert reader("engine.prefill_p90_ms")(ctx(reqs)) == \
        pytest.approx(280.0)


def test_nothing_to_read_without_stamps():
    assert reader("engine.admit_wait_p90_ms")(ctx([])) is None
    assert reader("engine.prefill_p90_ms")(ctx([])) is None
    # an engine whose requests carry no stamps
    bare = T.Req(0, [1], 1, due_at=101.0)
    bare.engine_req = SimpleNamespace(generated=[], pending=-1, done=False)
    assert reader("engine.admit_wait_p90_ms")(ctx([bare])) is None
    assert reader("engine.prefill_p90_ms")(ctx([bare])) is None
