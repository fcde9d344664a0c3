"""The serving loop's profiler spans and host-clock stamps, read back from
a real ``jax.profiler`` trace of a tiny paged engine on the CPU: every
``engine.*`` span is recorded, the decode step and its readback nest in
the tick, each request's stamps are ordered, and each step program and
width counts one compile."""
import dataclasses
import glob
import os

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models.transformer import init_params
from repro.serve import engine as E
from repro.serve.engine import Request, ServingEngine

SPANS = (E.SPAN_TICK, E.SPAN_ADMIT, E.SPAN_PREFILL, E.SPAN_DECODE,
         E.SPAN_READBACK, E.SPAN_RELEASE, E.SPAN_COMPILE)
# prompt lengths over chunk 8: last chunks of 3, 5 and 4 tokens
PROMPTS = (11, 5, 20)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Serve three requests through two slots (one waits for a slot)
    under the profiler, with the host tracer at the benchmark's level;
    record every step dispatch's program and width."""
    from jax.profiler import ProfileData
    cfg = dataclasses.replace(get_smoke_config("gpt3-24l"), vocab_size=128,
                              d_model=128, d_ff=256, n_heads=4,
                              n_kv_heads=4, head_dim=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, chunk=8,
                        paged=True, page_size=8, use_kernel=True)
    dispatched = []

    def recording(kind, fn):
        def f(params, caches, seen, toks, *rest):
            dispatched.append((kind, toks.shape))
            return fn(params, caches, seen, toks, *rest)
        return f
    eng._prefill_fn = recording("prefill", eng._prefill_fn)
    eng._decode_fn = recording("decode", eng._decode_fn)
    reqs = [Request(i, list(range(1, S + 1)), max_new=3)
            for i, S in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    eng.run()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events]
    return eng, reqs, dispatched, events


def test_every_engine_span_is_recorded(served):
    _, _, _, events = served
    names = {n for n, _, _ in events}
    assert set(SPANS) <= names
    assert {n for n in names if n.startswith("engine.")} == set(SPANS)


def test_decode_and_readback_lie_inside_a_tick(served):
    _, _, _, events = served
    ticks = [(s, t) for n, s, t in events if n == E.SPAN_TICK]
    inner = [(s, t) for n, s, t in events
             if n in (E.SPAN_DECODE, E.SPAN_READBACK)]
    assert inner
    for s, t in inner:
        assert any(a <= s and t <= b for a, b in ticks), (s, t)


def test_request_stamps_are_ordered(served):
    _, reqs, _, _ = served
    for r in reqs:
        assert r.done
        assert r.submitted_at <= r.admitted_at <= r.prefilled_at


def test_one_compile_per_step_program_and_width(served):
    eng, _, dispatched, _ = served
    assert eng.stats["step_compiles"] == len(set(dispatched))
    # prefill at last-chunk widths 3, 5, 4 and the full 8; decode at 1
    assert {(k, s[1]) for k, s in dispatched} == {
        ("prefill", 8), ("prefill", 3), ("prefill", 5), ("prefill", 4),
        ("decode", 1)}
    _, _, _, events = served
    assert sum(n == E.SPAN_COMPILE for n, _, _ in events) == \
        eng.stats["step_compiles"]


def test_programs_are_named_by_phase(served):
    _, _, _, events = served
    names = {n for n, _, _ in events}
    assert any("engine_step_prefill" in n for n in names)
    assert any("engine_step_decode" in n for n in names)
