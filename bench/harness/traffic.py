"""Load generator: turns a traffic file's parameters and a seed into a
schedule of requests.  One generator serves every traffic mix; a mix is
data only (``bench/traffic/<name>.json``).

Lengths come from stratified quantiles of a clipped lognormal: for n
requests, the (i + 1/2)/n quantiles; inter-arrival gaps are stratified
exponential quantiles.  Their order comes from the mix's own
``layout_seed``, so every run serves the same lengths and gaps in the
same order, and the run's seed draws only the token ids.  Prompt
lengths are rounded up to whole pages.

Loops:
* ``closed``: ``clients`` callers, each sending its next request when
  its previous one completes; request j of client c is
  ``requests[j * clients + c]``.
* ``open``: Poisson arrivals at ``rate_per_s``, due times relative to the
  schedule's start; the measured window opens ``preroll_s`` later.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_N = NormalDist()


@dataclass
class Req:
    """One scheduled request and what the run records about it."""
    idx: int
    prompt: List[int]
    max_new: int
    due: float = 0.0              # seconds from the schedule's start
    client: int = -1              # closed loop: the caller that sends it
    # filled in by the run, host clock (seconds, perf_counter)
    due_at: Optional[float] = None
    submitted_at: Optional[float] = None
    admitted_tick_start: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    engine_req: object = None


def lognormal_quantiles(n: int, median: float, sigma: float, lo: float,
                        hi: float, round_to: int = 1) -> List[int]:
    """n stratified quantiles of a lognormal clipped to [lo, hi], each
    rounded up to a multiple of ``round_to``; ascending."""
    out = []
    for i in range(n):
        v = median * math.exp(sigma * _N.inv_cdf((i + 0.5) / n))
        v = min(max(v, lo), hi)
        out.append(int(math.ceil(v / round_to) * round_to))
    return out


def exponential_quantiles(n: int, mean: float) -> List[float]:
    """n stratified quantiles of an exponential of the given mean."""
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def _lengths(spec: dict, n: int, page: int, prompt: bool) -> List[int]:
    return lognormal_quantiles(n, spec["median"], spec["sigma"], spec["min"],
                               spec["max"], page if prompt else 1)


def n_requests(mix: dict, seconds: float) -> int:
    """How many requests a schedule holds for a window of ``seconds``."""
    if mix["loop"] == "closed":
        return mix["clients"] * mix["requests_per_client"]
    horizon = mix["preroll_s"] + seconds
    return max(1, int(math.ceil(mix["rate_per_s"] * horizon)))


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             page: int) -> List[Req]:
    """The requests of one run, in submission order (closed loop: in
    client-major waves; open loop: by due time)."""
    n = n_requests(mix, seconds)
    order_rng = np.random.default_rng(mix["layout_seed"])
    tok_rng = np.random.default_rng([seed, 2])
    plen = _lengths(mix["prompt"], n, page, True)
    olen = _lengths(mix["output"], n, page, False)
    plen = [plen[i] for i in order_rng.permutation(n)]
    olen = [olen[i] for i in order_rng.permutation(n)]
    shared = mix.get("shared_prefix", 0)
    system = tok_rng.integers(0, vocab, shared).tolist()
    reqs = [Req(i, system + tok_rng.integers(0, vocab, plen[i]).tolist(),
                olen[i]) for i in range(n)]
    if mix["loop"] == "closed":
        for r in reqs:
            r.client = r.idx % mix["clients"]
    else:
        gaps = exponential_quantiles(n, 1.0 / mix["rate_per_s"])
        gaps = [gaps[i] for i in order_rng.permutation(n)]
        t = 0.0
        for r, g in zip(reqs, gaps):
            t += g
            r.due = t
    return reqs


def prompt_widths(chunk: int, page: int) -> List[int]:
    """Every last-chunk width a page-rounded prompt can leave: one
    compiled prefill program each."""
    return list(range(page, chunk + 1, page))
