"""One run of one cell, from set-up to the result line."""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import time

from bench.harness import check, device, serve, spec, trace as TR
from bench.harness import metrics as M
from bench.harness import traffic as T

OUT = spec.CHECKOUT / ".bench_out"


def weight_seed(seed: int) -> int:
    return seed % (2 ** 31 - 1)


class Tracer:
    """The profiler over the measured window: device operations and the
    harness's ``bench.*`` host spans; no Python function tracing, which
    would slow the host and swell the trace."""

    def __init__(self, path: str):
        self.path = path

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.path, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, devs, t_start: float,
             control: bool = False) -> dict:
    """Serve the cell's schedule for ``seconds`` and return the result
    line's object.  ``devs``: the devices the run holds."""
    import jax
    counter = serve.CompileCounter()
    fam = spec.family(cfg)
    model_cfg = fam.model_config(cfg)
    m = fam.widths(cfg)
    wseed = weight_seed(seed)
    argv = spec.launcher_argv(cfg, mix, wseed)

    t = time.perf_counter()
    engine = serve.build_engine(argv, model_cfg, fam, m, wseed)
    log(f"engine: {cfg['name']} {fam.describe(m)}; slots={engine.slots} "
        f"cache_len={engine.cache_len} chunk={engine.chunk} pool="
        f"{engine.num_blocks}x{engine.page_size} kernel={engine.use_kernel} "
        f"(built in {time.perf_counter() - t:.1f}s)")
    t = time.perf_counter()
    widths = serve.warm_up(engine, m["vocab_size"], seed)
    log(f"warm-up: {widths} prompt widths served in "
        f"{time.perf_counter() - t:.1f}s")

    reqs = T.schedule(mix, seed, seconds, m["vocab_size"], engine.page_size)
    trace_dir = str(OUT / "trace" / cell["name"])
    tracer = Tracer(trace_dir) if trace else None
    if trace:
        _clean(trace_dir)
    runner = serve.Runner(engine, reqs, mix, seconds, tracer, counter)
    win = runner.run()
    setup_s = win.open - t_start
    log(f"setup: {setup_s:.3f}s to the window's start")
    peak = device.memory_peak(devs)
    log(f"memory: peak_bytes_in_use {peak}, bytes_limit "
        f"{device.memory_limit(devs)}")
    stats = dict(engine.stats)

    # open loop: every request due in the window; closed loop: every
    # request the engine took into service before the window closed (the
    # rest wait in its queue, which is the closed loop's own pacing)
    if mix["loop"] == "open":
        attempted = M.due_in_window(reqs, win)
    else:
        attempted = [r for r in reqs if r.admitted_tick_start is not None
                     and r.admitted_tick_start < win.close]
    failed = sum(1 for r in attempted if not r.token_times)
    host = M.host_metrics(reqs, win)
    late = sorted(win.late_s)
    log(f"window: {win.seconds:.3f}s, {len(win.ticks)} ticks, "
        f"{M.tokens_in_window(reqs, win)} tokens, {len(attempted)} requests "
        f"attempted, {failed} without a first token; backpressure "
        f"{win.backpressure}, clients held back at open {win.held_at_open}")
    if late:
        log(f"generator lateness: p50 {M.percentile(late, 50) * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} submissions")
    log(f"compilations inside the window: {len(win.compiles)} "
        f"{sorted(set(win.compiles))}")
    log(f"engine stats: {stats}")

    page, chunk = engine.page_size, engine.chunk
    work = M.live_work(win, reqs, fam, m, page, chunk)
    summary = None
    if trace:
        path = TR.latest_xplane(trace_dir)
        summary = TR.reduce(TR.load(path), M.KERNELS) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference runs on a chip the system under test no longer holds
    sampled = check.sample(reqs, mix["check_requests"], seed)
    del engine, runner
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())
    t = time.perf_counter()
    cmp = check.gaps(sampled, fam, m, wseed, control=control)
    limit = cfg["check"]["served_logit_gap"]
    log(f"check: {cmp['requests']} requests, {cmp['positions']} served "
        f"tokens compared with the float32 reference in "
        f"{time.perf_counter() - t:.1f}s ({live} bytes live before it)")
    correct = bool(cmp["positions"] > 0 and cmp["served_logit_gap"] <= limit)

    cell_name = cell["name"]
    metrics = {}
    if not trace:
        vals = dict(host, setup_s=setup_s)
        for e in bench["end_to_end"]:
            if cell_name in e.get("workloads", [cell_name]):
                metrics[e["name"]] = {"value": _num(vals[e["name"]]),
                                      "unit": e["unit"]}
    else:
        pk = device.peaks(devs[0].device_kind)
        ctx = M.Context(m, page, chunk, win, reqs, work, pk, summary, fam)
        for p in bench["per_layer"]:
            if cell_name not in p.get("workloads", [cell_name]):
                continue
            v = M.reader(p["name"])(ctx)
            if v is not None:
                metrics[p["name"]] = {"value": v, "unit": p["unit"]}
    dev = dict(device.describe(devs), memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": len(attempted), "failed": failed,
           "metrics": metrics, "device": dev}
    if trace and summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = TR.breakdown(summary)
        top = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:40]
        log(f"trace: top device ops {top}")
        log(f"trace: programs {summary.programs}; kernels "
            f"{summary.kernel_s}; busy {summary.busy_s:.4f}s of "
            f"{summary.window_s:.4f}s")
    log(f"work: {work}")
    out["checks"] = {"served_logit_gap": {
        "value": _num(cmp["served_logit_gap"]), "limit": limit}}
    if control:
        log(f"control: the fp8 reference's first choices stand in for the "
            f"served tokens, whose own gap is {cmp['program_logit_gap']!r}")
    log(f"check served_logit_gap {cmp['served_logit_gap']!r} limit {limit!r}")
    log(f"correct {correct}")
    return out


def _num(v: float) -> float:
    """A metric that a missing request pushed to infinity reads as the
    largest finite float, above every limit."""
    return v if math.isfinite(v) else sys.float_info.max


def _clean(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
