"""Operations and bytes the algorithm needs, from the configuration's
widths and the live lengths the run recorded.  Work that the program
walks but the algorithm does not need (masked batch rows, unallocated
block-table columns, pages past a row's length) is never counted.

``m`` is the dict of widths of ``reference.qwen_dense``.
"""
from __future__ import annotations

BF16_BYTES = 2


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
