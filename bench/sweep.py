#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the highest arrival rate the
system sustains (the knee).  One process builds the cell's engine once,
warms it up, and serves the cell's mix at each rate in turn:

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 30 \
        --rates 0.5,1,1.5,2

For each rate it prints one JSON line: tokens per second, time to first
token, and the queue wait of the window's first and last thirds.  A rate
is sustained where the last third waits no longer than the first.  The
cell's traffic file then takes about four fifths of the highest
sustained rate.  Needs a TPU, like the benchmark itself.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
from bench import run as R  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    from bench.harness import device, serve, spec
    from bench.harness.cell import weight_seed
    from bench.harness import metrics as M
    from bench.harness import traffic as T
    bench = spec.benchmark()
    cell = spec.cell(bench, a.workload)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    import jax
    jax.config.update("jax_compilation_cache_dir", str(R.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device.require_tpu(cell["chips"])
    fam = spec.family(cfg)
    m = fam.widths(cfg)
    wseed = weight_seed(a.seed)
    engine = serve.build_engine(spec.launcher_argv(cfg, mix, wseed),
                                fam.model_config(cfg), fam, m, wseed)
    serve.warm_up(engine, m["vocab_size"], a.seed)
    for rate in [float(x) for x in a.rates.split(",")]:
        rmix = dict(mix, rate_per_s=rate)
        reqs = T.schedule(rmix, a.seed, a.seconds, m["vocab_size"],
                          engine.page_size)
        win = serve.Runner(engine, reqs, rmix, a.seconds).run()
        while engine.queue or engine.n_active:
            engine.tick()
        due = M.due_in_window(reqs, win)
        third = win.seconds / 3
        waits = [((r.admitted_tick_start or time.perf_counter()) - r.due_at,
                  r.due_at) for r in due]
        first = [w for w, t in waits if t < win.open + third]
        last = [w for w, t in waits if t >= win.open + 2 * third]
        tt = M.ttfts(reqs, win)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "out_tok_s": M.tokens_in_window(reqs, win) / win.seconds,
            "ttft_p50_ms": M.percentile(tt, 50) * 1e3,
            "ttft_p90_ms": M.percentile(tt, 90) * 1e3,
            "itl_p95_ms": M.percentile(M.itls(reqs, win), 95) * 1e3,
            "wait_first_third_p50_ms": M.percentile(first, 50) * 1e3,
            "wait_last_third_p50_ms": M.percentile(last, 50) * 1e3,
            "without_first_token": sum(1 for r in due if not r.token_times),
        }), flush=True)


if __name__ == "__main__":
    main()
