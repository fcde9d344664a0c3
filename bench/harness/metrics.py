"""Host-clock end-to-end metrics, live work counts (by the configuration's
family module), and the loader of the per-layer metric readers
(``bench/metrics/<name>.py``, each a ``read(ctx)`` that returns a number,
or None where it finds nothing to read)."""
from __future__ import annotations

import importlib.util
import math
from dataclasses import dataclass
from typing import Dict, List, Optional

from bench.harness.spec import BENCH

INF = math.inf


def percentile(vals: List[float], q: float) -> float:
    """Linear-interpolated percentile; a missing value is +inf, and a
    percentile that reaches one is +inf."""
    if not vals:
        return INF
    v = sorted(vals)
    rank = q / 100.0 * (len(v) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    if math.isinf(v[hi]):
        return INF
    return v[lo] + (v[hi] - v[lo]) * (rank - lo)


def due_in_window(reqs, win) -> list:
    return [r for r in reqs if r.due_at is not None
            and win.open <= r.due_at < win.close]


def ttfts(reqs, win) -> List[float]:
    """Seconds from due time to first token, of every request due in the
    window; a request with no first token is +inf."""
    return [(r.token_times[0] - r.due_at) if r.token_times else INF
            for r in due_in_window(reqs, win)]


def itls(reqs, win) -> List[float]:
    """Every gap between consecutive tokens of a request, whose later
    token came in the window."""
    out = []
    for r in reqs:
        t = r.token_times
        out += [t[i] - t[i - 1] for i in range(1, len(t))
                if win.open <= t[i] <= win.end]
    return out


def tokens_in_window(reqs, win) -> int:
    return sum(1 for r in reqs for t in r.token_times
               if win.open <= t <= win.end)


def host_metrics(reqs, win) -> Dict[str, float]:
    """Every end-to-end metric the host clock gives (seconds -> ms)."""
    tt = ttfts(reqs, win)
    it = itls(reqs, win)
    return {"ttft_p90_ms": percentile(tt, 90) * 1e3,
            "itl_p95_ms": percentile(it, 95) * 1e3,
            "out_tok_s": tokens_in_window(reqs, win) / win.seconds}


def prefill_spans(win, by_idx, chunk: int):
    """(start, stop) prompt positions prefilled for each request admitted
    in the window.  A tick's attached tokens (the engine's
    ``shared_tokens`` counter) are exact per request where the tick
    admitted one; where it admitted several, they are split evenly,
    capped at each prompt's length - 1."""
    out = []
    for t in win.ticks:
        if not t.admitted:
            continue
        left, k = t.shared_tokens, len(t.admitted)
        for i, idx in enumerate(t.admitted):
            S = len(by_idx[idx].prompt)
            share = min(S - 1, left // (k - i))
            left -= share
            out.append((share, S))
    return out


def live_work(win, reqs, fam, m: dict, page: int, chunk: int) -> dict:
    """FLOPs and bytes the algorithm needs over the window's ticks, as
    the family module ``fam`` counts them."""
    by_idx = {r.idx: r for r in reqs}
    decode = sum(fam.decode_step_flops(m, t.decode_pos) for t in win.ticks)
    kf = kb = 0
    for t in win.ticks:
        f, b = fam.decode_attention_need(m, t.decode_pos, page)
        kf += f
        kb += b
    spans = prefill_spans(win, by_idx, chunk)
    prefill = sum(fam.prefill_flops(m, a, b, chunk) for a, b in spans)
    return {"decode_flops": decode, "kernel_flops": kf, "kernel_bytes": kb,
            "prefill_flops": prefill, "prefill_tokens":
            sum(b - a for a, b in spans),
            "admitted_prompt_tokens": sum(b for _, b in spans),
            "shared_tokens": sum(t.shared_tokens for t in win.ticks),
            "decode_steps": sum(1 for t in win.ticks if t.decode_pos)}


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    m: dict
    page: int
    chunk: int
    win: object
    reqs: list
    work: dict
    peaks: Optional[dict]
    trace: object          # trace.Summary, or None without a trace
    family: object = None  # the configuration's bench/reference module

    def program_s(self, kind: str) -> Optional[float]:
        """Device seconds of the engine step programs: ``decode`` (those
        that ran the paged-decode kernel) or ``prefill`` (the others)."""
        if self.trace is None:
            return None
        tot = 0.0
        for name, s in self.trace.programs.items():
            if "engine_step" not in name:
                continue
            if ("[paged_decode]" in name) == (kind == "decode"):
                tot += s
        return tot or None


# kernel label -> the text that names its operations in the trace: the
# Pallas call's custom-call is named after its jitted wrapper
KERNELS = {"paged_decode": "paged_attention"}


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

