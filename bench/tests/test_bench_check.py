"""A whole run of a cell at a tiny size on the CPU, the look for a chip
skipped: the sound program is correct, and the control (the reference in
fp8, put in the program's place) is not."""
import json
import time

import jax
import pytest

from bench.harness.cell import run_cell
from bench.tests import tiny

CASES = {"closed-qwen3": (tiny.QWEN3, tiny.CLOSED),
         "open-qwen1.5-shared-prefix": (tiny.QWEN15, tiny.OPEN)}


def run(cfg, mix, seed=2 ** 31 + 99, control=False):
    b = tiny.bench("tiny-cell")
    return run_cell(b, b["workloads"][0], cfg, mix, seed=seed, seconds=1.5,
                    trace=False, devs=jax.devices(),
                    t_start=time.perf_counter(), control=control)


@pytest.mark.parametrize("case", list(CASES))
def test_sound_run_is_correct_and_the_control_is_not(case):
    cfg, mix = CASES[case]
    limit = cfg["check"]["served_logit_gap"]
    out = run(cfg, mix)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["served_logit_gap"]["value"] <= limit
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "out_tok_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    json.dumps(out, allow_nan=False)
    ctl = run(cfg, mix, control=True)
    assert ctl["correct"] is False
    assert ctl["checks"]["served_logit_gap"]["value"] > limit
    assert list(ctl)[-1] == "checks"
