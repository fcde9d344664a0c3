#!/usr/bin/env python3
"""On-chip benchmark of the serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and, by name, its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); builds the engine through the serving
launcher; warms up every shape the traffic uses; serves the seeded
schedule for ``--seconds``; checks what was served against the float32
reference; prints the result as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (``bench/metrics/<name>.py``) from a profiler trace.

Refuses to run, with a non-zero exit and no result, where JAX finds no
TPU or fewer chips than the cell asks for.  ``--control 1`` runs the
check's control: the reference in fp8 takes the served tokens' place in
the comparison, so the run has to read ``correct`` false; the
benchmark's own runs leave it off.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
# the compile cache lives at one fixed path inside the checkout
CACHE_DIR = CHECKOUT / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    a = parse(argv)
    from bench.harness import spec
    bench = spec.benchmark()
    cell = spec.cell(bench, a.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import device
    devs = device.require_tpu(cell["chips"])
    devs = devs[:cell["chips"]]
    device.peaks(devs[0].device_kind)
    from bench.harness.cell import run_cell
    out = run_cell(bench, cell, cfg, mix, seed=a.seed, seconds=a.seconds,
                   trace=bool(a.trace), devs=devs, t_start=T_START,
                   control=bool(a.control))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
