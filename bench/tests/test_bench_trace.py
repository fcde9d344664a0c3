"""The trace -> metrics reduction on a small synthetic trace."""
import pytest

from bench.harness import trace as TR
from bench.harness.metrics import KERNELS, Context

KPAT = KERNELS["paged_decode"]
MS = 1_000_000      # ns


def ev(name, s, t):
    return TR.Event(name, s * MS, t * MS)


def synthetic():
    """A 100 ms window: a prefill program (0-30 ms, two ops), a decode
    program (40-70 ms) whose loop holds the kernel, and one op that
    starts before the window opens.  The host waits 70-100 ms."""
    dev = TR.Device(
        ops=[ev("fusion.1", 0, 10), ev("fusion.2", 10, 30),
             ev("while.6", 40, 70), ev("fusion.3", 40, 50),
             ev(f"{KPAT}.9", 50, 65), ev("fusion.4", 65, 70),
             ev("early", -20, 2)],
        modules=[ev("jit_engine_step(1)", 0, 30),
                 ev("jit_engine_step(2)", 40, 70)])
    host = [ev(TR.WINDOW_SPAN, 0, 100), ev("bench.tick", 0, 35),
            ev("bench.record", 35, 40), ev("bench.wait", 70, 100)]
    return TR.Trace((0, 100 * MS), host, {"/device:TPU:0": dev})


def test_busy_union_idle_share_and_programs():
    s = TR.reduce(synthetic(), KERNELS)
    assert s.window_s == pytest.approx(0.1)
    # ops cover 0-30 and 40-70: the loop, its body and the early op merge
    assert s.busy_s == pytest.approx(0.06)
    assert "while.6" not in s.ops           # it holds other operations
    assert s.idle_share == pytest.approx(0.4)
    assert s.programs == pytest.approx({
        "jit_engine_step(1)": 0.03,
        "jit_engine_step(2) [paged_decode]": 0.03})
    assert s.kernel_s == pytest.approx({"paged_decode": 0.015})
    # each gap goes to the innermost host span open at its middle
    assert s.idle == pytest.approx({"bench.record": 0.01,
                                    "bench.wait": 0.03})
    assert s.ops["early"] == pytest.approx(0.002)       # clipped


def test_program_time_by_kind_and_breakdown():
    s = TR.reduce(synthetic(), KERNELS)
    ctx = Context({}, 16, 128, None, [], {}, None, s)
    assert ctx.program_s("decode") == pytest.approx(0.03)
    assert ctx.program_s("prefill") == pytest.approx(0.03)
    b = TR.breakdown(s, top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.2", f"{KPAT}.9"]
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(0.03)]


def test_op_names_from_hlo_text():
    assert TR.op_name("%paged_attention.9 = bf16[32,8,4,128] custom-call("
                      "s32[32,288] %copy-done.3)") == "paged_attention.9"
    assert TR.op_name("fusion.2") == "fusion.2"


def test_union_and_gaps():
    assert TR.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert TR.gaps([(0, 3), (5, 7)], 0, 10) == [(3, 5), (7, 10)]
    assert TR.gaps([], 0, 4) == [(0, 4)]


def test_metric_readers_on_the_synthetic_trace():
    from bench.harness.metrics import reader
    s = TR.reduce(synthetic(), KERNELS)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e10}
    work = {"decode_flops": 3e9, "prefill_flops": 6e9, "kernel_flops": 1e6,
            "kernel_bytes": 7.5e7, "admitted_prompt_tokens": 0,
            "shared_tokens": 0}
    ctx = Context({}, 16, 128, None, [], work, peaks, s)
    assert reader("step.prefill_mfu")(ctx) == pytest.approx(20.0)
    assert reader("step.decode_mfu")(ctx) == pytest.approx(10.0)
    # 7.5e7 B at 1e10 B/s = 7.5 ms of a 15 ms kernel; bytes bound it
    assert reader("kernel.paged_decode_roofline")(ctx) == pytest.approx(50.0)
    assert reader("device.idle_share")(ctx) == pytest.approx(40.0)
    # nothing to read: no prompt admitted, no trace
    assert reader("engine.prefix_hit_share")(ctx) is None
    assert reader("step.decode_mfu")(Context({}, 16, 128, None, [], work,
                                             peaks, None)) is None
