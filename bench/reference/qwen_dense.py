"""The dense Qwen family (Qwen3 with per-head qk-norm, Qwen1.5 with QKV
bias): everything the benchmark knows of it.  A configuration file names
this module under ``"reference": "qwen_dense"``, and the harness reaches
the family only through the functions below (``bench/harness/spec.py``
``family``).

* ``model_config(cfg)`` and ``widths(cfg)`` read the configuration file:
  the system's ModelConfig, and the dict ``m`` of widths that everything
  else here takes.  ``describe(m)`` is the widths part of the engine log
  line.
* ``prepare_params(params, m, seed)`` sets in the system's weights what
  the benchmark draws and the initialiser does not: the QKV biases.

The plain float32 reference, written from the published model
description and independent of the system under test:

* ``draw_weights(seed, m)`` draws the seeded random weights in bfloat16
  by the same recipe as the serving system's weight initialiser (a
  threefry key split into six, one matrix per normal draw scaled by
  1/sqrt(fan_in), output projections further scaled by
  1/sqrt(2 * layers), norms stored as scale - 1 = 0).  The recipe is
  restated here, not imported, so that a change to the system cannot
  move the reference with it.
* ``draw_biases(seed, m)`` draws the QKV biases (where ``attn_bias``):
  the initialiser leaves them zero, which would hide a fault in the bias
  path, so the benchmark draws them from the seed and sets them in the
  system's weights, and the reference draws the same.
* ``logits_at(...)`` runs one token sequence through the model, one layer
  at a time, every activation and matmul in float32 at ``highest``
  precision, and returns the logits at the positions asked for.
* ``quant="fp8"`` is the control: every projection's operands rounded to
  float8_e4m3 with a per-row (activations) or per-column (weights)
  scale, as an fp8 serving path would compute.  It must fail the
  comparison.

The work counts: ``decode_step_flops``, ``prefill_flops`` and
``decode_attention_need`` give the operations and bytes the algorithm
needs, from the widths and the live lengths the run recorded.  Work that
the program walks but the algorithm does not need (masked batch rows,
unallocated block-table columns, pages past a row's length) is never
counted.  They hold for grouped-query attention with K and V cached per
kv head; a family that caches otherwise (a shared latent) counts its own.

``m`` is a plain dict of widths: d_model, n_layers, n_heads,
n_kv_heads, head_dim, d_ff, vocab_size, rope_theta, norm_eps,
qk_norm, attn_bias.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn
SEQ_BUCKET = 512         # sequences are padded to a multiple of this
# std of the drawn QKV biases, against unit-scale q, k, v.  Much larger
# (0.5), the v bias adds one constant vector per layer that swamps the
# residual at random weights, the logits turn peaked and no near-tie is
# left for the fp8 control to flip.
BIAS_STD = 0.1
BF16_BYTES = 2

# config-file key -> (ModelConfig field, key of ``m``)
WIDTHS = {
    "hidden_size": ("d_model", "d_model"),
    "num_hidden_layers": ("n_layers", "n_layers"),
    "num_attention_heads": ("n_heads", "n_heads"),
    "num_key_value_heads": ("n_kv_heads", "n_kv_heads"),
    "head_dim": ("head_dim", "head_dim"),
    "intermediate_size": ("d_ff", "d_ff"),
    "vocab_size": ("vocab_size", "vocab_size"),
    "rope_theta": ("rope_theta", "rope_theta"),
    "rms_norm_eps": ("norm_eps", "norm_eps"),
    "qk_norm": ("qk_norm", "qk_norm"),
    "attention_bias": ("attn_bias", "attn_bias"),
}


# ---------------------------------------------------------------------------
# The configuration file
# ---------------------------------------------------------------------------

def widths(cfg: dict) -> dict:
    """The dict of widths the reference and the work counters read."""
    m = {w: cfg[k] for k, (_, w) in WIDTHS.items()}
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not handled by the reference")
    return m


def model_config(cfg: dict):
    """The system's ModelConfig for this configuration file: the
    architecture's own config with every width set from the file."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    over = {f: cfg[k] for k, (f, _) in WIDTHS.items()}
    return dataclasses.replace(base, name=cfg["name"],
                               tie_embeddings=False, **over)


def describe(m: dict) -> str:
    return (f"{m['n_layers']}L d={m['d_model']} heads={m['n_heads']}/"
            f"{m['n_kv_heads']}x{m['head_dim']} d_ff={m['d_ff']} "
            f"vocab={m['vocab_size']}")


def prepare_params(params, m: dict, seed: int):
    """The system's weights with, where the model has QKV biases, the
    seeded biases in place of the initialiser's zeros."""
    if not m["attn_bias"]:
        return params
    return with_biases(params, draw_biases(seed, m))


def with_biases(params, biases: dict):
    """``params`` with every leaf named after a key of ``biases`` replaced
    by that array, which must have the leaf's shape and type."""
    hit = []

    def put(path, leaf):
        name = getattr(path[-1], "key", None)
        if name not in biases:
            return leaf
        new = biases[name]
        if new.shape != leaf.shape or new.dtype != leaf.dtype:
            raise ValueError(f"bias {name}: {new.shape} {new.dtype} for "
                             f"{leaf.shape} {leaf.dtype}")
        hit.append(name)
        return new

    out = jax.tree_util.tree_map_with_path(put, params)
    if sorted(hit) != sorted(biases):
        raise ValueError(f"biases set {sorted(hit)}, drawn {sorted(biases)}")
    return out


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _normal(key, shape, std):
    return (jax.random.normal(key, shape, F32) * std).astype(BF16)


def _layer(key, m):
    d, hq, hkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    out_scale = 1.0 / math.sqrt(2 * m["n_layers"])
    k_mix, k_ffn = jax.random.split(key)
    ka = jax.random.split(k_mix, 5)
    kf = jax.random.split(k_ffn, 3)
    w = {"norm1": jnp.zeros((d,), F32), "norm2": jnp.zeros((d,), F32),
         "wq": _normal(ka[0], (d, hq * hd), 1 / math.sqrt(d)),
         "wk": _normal(ka[1], (d, hkv * hd), 1 / math.sqrt(d)),
         "wv": _normal(ka[2], (d, hkv * hd), 1 / math.sqrt(d)),
         "wo": _normal(ka[3], (hq * hd, d), out_scale / math.sqrt(hq * hd)),
         "w_gate": _normal(kf[0], (d, f), 1 / math.sqrt(d)),
         "w_up": _normal(kf[1], (d, f), 1 / math.sqrt(d)),
         "w_down": _normal(kf[2], (f, d), out_scale / math.sqrt(f))}
    if m["qk_norm"]:
        w.update(q_norm=jnp.zeros((hd,), F32), k_norm=jnp.zeros((hd,), F32))
    return w


def _biases(key, m):
    L, hd = m["n_layers"], m["head_dim"]
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 1), 3)
    # bfloat16 values held in float32: the served model adds them in bf16
    return {n: _normal(k, (L, h * hd), BIAS_STD).astype(F32)
            for n, k, h in (("bq", kq, m["n_heads"]),
                            ("bk", kk, m["n_kv_heads"]),
                            ("bv", kv, m["n_kv_heads"]))}


def draw_biases(seed: int, m: dict) -> dict:
    """The seeded QKV biases ``bq``, ``bk``, ``bv``, each (n_layers,
    heads * head_dim) float32, in one jitted call on the device."""
    return jax.jit(lambda k: _biases(k, m))(jax.random.PRNGKey(seed))


def draw_weights(seed: int, m: dict) -> dict:
    """The seeded bf16 weights: embed (V, d), lm_head (d, V), final_norm,
    and ``layers``: every per-layer leaf stacked on a leading layer axis."""
    def draw(key):
        keys = jax.random.split(key, 6)
        d, V = m["d_model"], m["vocab_size"]
        w = {"embed": _normal(keys[0], (V, d), 0.02),
             "lm_head": _normal(keys[1], (d, V), 1 / math.sqrt(d)),
             "final_norm": jnp.zeros((d,), F32)}
        # one repeating period of one layer: each layer's key is the
        # first of a one-way split of its period key
        per = jax.random.split(keys[4], m["n_layers"])
        w["layers"] = jax.vmap(
            lambda k: _layer(jax.random.split(k, 1)[0], m))(per)
        if m["attn_bias"]:
            w["layers"].update(_biases(key, m))
        return w
    return jax.jit(draw)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Forward, float32
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x: (S, H, D); pos: (S,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "quant"))
def _layer_fwd(x, layers, i, n_valid, *, m, quant):
    """Layer ``i`` of the stacked ``layers`` on x (S, d), float32."""
    m = dict(m)
    w = jax.tree.map(lambda a: a[i], layers)
    S = x.shape[0]
    hq, hkv, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _rmsnorm(x, w["norm1"], eps)
    q, k, v = _mm(h, w["wq"], quant), _mm(h, w["wk"], quant), _mm(h, w["wv"], quant)
    if m["attn_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = q.reshape(S, hq, hd), k.reshape(S, hkv, hd), v.reshape(S, hkv, hd)
    if m["qk_norm"]:
        q, k = _rmsnorm(q, w["q_norm"], eps), _rmsnorm(k, w["k_norm"], eps)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    # grouped-query attention: query head j reads kv head j // (hq / hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / math.sqrt(hd)
    ok = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
    x = x + _mm(a.reshape(S, hq * hd), w["wo"], quant)
    h = _rmsnorm(x, w["norm2"], eps)
    g = _mm(h, w["w_gate"], quant)
    u = _mm(h, w["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], quant)


@jax.jit
def _embed(embed, toks):
    return embed[toks].astype(F32)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, final_norm, lm_head, rows, *, m, quant):
    h = _rmsnorm(x[rows], final_norm, dict(m)["norm_eps"])
    return _mm(h, lm_head, quant)


def _pad(a, n, fill=0):
    out = np.full((n,), fill, np.int32)
    out[:len(a)] = a
    return out


def logits_at(w: dict, m: dict, tokens, rows, *, quant: str = "none"):
    """float32 logits at sequence positions ``rows`` of the token
    sequence ``tokens`` (causal: position i sees tokens 0..i).  Shapes
    are padded to multiples of SEQ_BUCKET, so few programs compile: the
    result has ``bucket(len(rows))`` rows, of which the first
    ``len(rows)`` are asked for."""
    n = len(tokens)
    S = -(-n // SEQ_BUCKET) * SEQ_BUCKET
    R = -(-len(rows) // SEQ_BUCKET) * SEQ_BUCKET
    mk = tuple(sorted(m.items()))
    x = _embed(w["embed"], jnp.asarray(_pad(tokens, S)))
    for i in range(m["n_layers"]):
        x = _layer_fwd(x, w["layers"], i, n, m=mk, quant=quant)
    return _head(x, w["final_norm"], w["lm_head"], jnp.asarray(_pad(rows, R)),
                 m=mk, quant=quant)


@jax.jit
def gap_of(ref_logits, toks):
    """Per row: the reference's best logit minus its logit of ``toks``."""
    best = ref_logits.max(axis=-1)
    return best - jnp.take_along_axis(ref_logits, toks[:, None], axis=-1)[:, 0]


@jax.jit
def first_choice(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Work the algorithm needs
# ---------------------------------------------------------------------------

def matmul_flops_per_token(m: dict) -> int:
    """Projection FLOPs of one token through every layer (no unembed)."""
    d, hq, hkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    per_layer = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d + 3 * 2 * d * f
    return per_layer * m["n_layers"]


def attention_flops(m: dict, pos: int) -> int:
    """Score and value FLOPs of one query at position ``pos`` (it sees
    pos + 1 keys), over every layer."""
    return 4 * m["n_heads"] * m["head_dim"] * (pos + 1) * m["n_layers"]


def unembed_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def decode_step_flops(m: dict, positions) -> int:
    """One decode step of the live slots at query ``positions``: each
    slot's token through every layer, its attention, and its unembed."""
    return sum(matmul_flops_per_token(m) + attention_flops(m, p)
               + unembed_flops(m) for p in positions)


def prefill_flops(m: dict, start: int, stop: int, chunk: int) -> int:
    """Prompt positions [start, stop) of one request, prefilled in chunks
    of ``chunk``: every live token's projections and causal attention,
    and one unembed per chunk (only a chunk's last position is read)."""
    n = stop - start
    attn = sum(attention_flops(m, p) for p in range(start, stop))
    chunks = -(-n // chunk)
    return n * matmul_flops_per_token(m) + attn + chunks * unembed_flops(m)


def decode_attention_need(m: dict, positions, page: int) -> tuple:
    """(FLOPs, bytes) the paged decode attention needs in one step, over
    every layer: each live slot's K and V pages up to its position, its
    query and its output; scores and weighted values over pos + 1 keys."""
    hq, hkv, hd, L = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["n_layers"]
    flops = 0
    nbytes = 0
    for p in positions:
        pages = -(-(p + 1) // page)
        kv = 2 * pages * page * hkv * hd * BF16_BYTES
        qo = 2 * hq * hd * BF16_BYTES
        nbytes += (kv + qo) * L
        flops += 4 * hq * hd * (p + 1) * L
    return flops, nbytes
