"""Whether what the timed path served is correct.

Once the window has closed and the system's state is freed, a sample of
the requests it served, drawn from the seed and always holding the one
with the most served tokens, is run through the float32 reference of
the configuration's family (``bench/reference/<reference>.py``),
teacher-forced on the prompt and the served tokens.  At every served
position the gap by which the served token's reference logit lies below
the reference's best is read; the number compared is the widest gap.
Greedy decoding in bfloat16 may pick a near-tie; it may not pick a token
the reference ranks far below its best.

The control (``quant="fp8"``) puts the reference, computed with fp8
projections, in the system's place: at each of the same positions the
token it ranks first is taken for the served one, and its gap in the
float32 reference is the number compared, against the same limit.  A
control run has to come out as not correct.
"""
from __future__ import annotations

import math
import numpy as np


def sample(reqs, n: int, seed: int) -> list:
    """Up to ``n`` served requests: the one with the most served tokens,
    and the rest drawn from the seed."""
    served = [r for r in reqs if r.engine_req is not None
              and r.engine_req.generated]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.engine_req.generated), -r.idx))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 4])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(reqs, ref, m: dict, weight_seed: int,
         control: bool = False) -> dict:
    """Widest gap of the compared tokens over the sampled requests, with
    the count of positions compared, by the family module ``ref``.  The
    compared tokens are the served ones, or with ``control`` the fp8
    reference's first choices; the served tokens' own gap is kept as
    ``program_logit_gap`` either way."""
    import jax
    import jax.numpy as jnp

    w = ref.draw_weights(weight_seed, m)
    worst, ctl, n_pos, bad_ids = 0.0, 0.0, 0, 0
    for r in reqs:
        served = list(r.engine_req.generated)
        if any(t < 0 or t >= m["vocab_size"] for t in served):
            bad_ids += 1
            continue
        n, S = len(served), len(r.prompt)
        seq = r.prompt + served[:-1]
        rows = list(range(S - 1, S - 1 + n))
        lg = ref.logits_at(w, m, seq, rows)
        toks = jnp.asarray(ref._pad(served, lg.shape[0]))
        worst = max(worst, float(np.max(np.asarray(ref.gap_of(lg, toks))[:n])))
        n_pos += n
        if control:
            pick = ref.first_choice(ref.logits_at(w, m, seq, rows, quant="fp8"))
            ctl = max(ctl, float(np.max(np.asarray(ref.gap_of(lg, pick))[:n])))
        del lg
    program = worst if not bad_ids else math.inf
    del w
    jax.clear_caches()
    return {"served_logit_gap": ctl if control else program,
            "program_logit_gap": program, "positions": n_pos,
            "requests": len(reqs), "out_of_vocab": bad_ids}
