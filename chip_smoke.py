#!/usr/bin/env python3
"""Bring-up check of the serving path on TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the SPMD stage pipeline

One chip: builds qwen3-8b's chip-share preset (``configs/qwen3_8b.py``
``CHIP``: every published width, 18 of 36 layers) with seeded random
weights through ``repro.launch.serve``'s own engine construction — paged
KV, prefix sharing on, decode through the compiled Pallas paged-decode
kernel — serves 8 requests that share a system prefix through
``ServingEngine.run()``, checks every request completes with in-vocab
tokens, then compares the compiled kernel with the scan path at layer
level on one pool and block table of the engine's geometry.

Four chips: runs only ``core.executor.spmd_pipeline`` over a 4-device
``stage`` mesh (one 4096x4096 bf16 stage per chip) and compares it with
the same stages applied in sequence on one chip.

Everything runs in this one process, which holds the chip(s) from start
to end.  With no TPU the script exits non-zero and prints no result.
The last line of stdout is the JSON result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the one-chip deployment: launcher flags of repro.launch.serve
CHIP_SERVE_ARGS = ["--chip", "--arch", "qwen3-8b", "--kernel",
                   "--slots", "8", "--cache-len", "2048", "--chunk", "128",
                   "--page-size", "16", "--seed", "0"]
N_REQUESTS = 8
MAX_NEW = 32
PIPE_STAGES = 4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(count: int):
    """The TPU devices this run holds; exits when JAX finds fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} TPU device(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s). Not running on "
            f"anything else.")
    return devs


def make_prompts(chunk: int, vocab: int, seed: int):
    """8 prompts: a 2-chunk system prefix shared by all, then a 1- or
    2-chunk user part; prompts 2 and 3 repeat 0 and 1 exactly (a client
    retry) while those are still resident, so their last page
    copy-on-writes.  Every length is a chunk multiple, so every prefill
    chunk has the one compiled width."""
    import numpy as np
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, 2 * chunk).tolist()
    users = [rng.integers(0, vocab, chunk * (1 + i % 2)).tolist()
             for i in range(N_REQUESTS - 2)]
    prompts = [system + u for u in users]
    return prompts[:2] * 2 + prompts[2:]


def serve_phase(launch_argv, max_new: int):
    """Build the engine through the launcher and serve the prompts."""
    import jax
    from repro.launch import serve as launcher
    from repro.serve.engine import Request

    args = launcher.build_parser().parse_args(launch_argv)
    cfg = launcher.model_config(args)
    t0 = time.perf_counter()
    engine = launcher.engine_builder(args, cfg)()
    jax.block_until_ready(engine.params)
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    print(f"model: {cfg.name} {cfg.n_layers}L d={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}, {n_params} params, "
          f"paged {engine.num_blocks}x{engine.page_size}, slots="
          f"{engine.slots}, chunk={engine.chunk}, kernel="
          f"{engine.use_kernel} (init {time.perf_counter() - t0:.1f}s)")
    t0 = time.perf_counter()
    engine.warmup()
    print(f"compile: {time.perf_counter() - t0:.1f}s (prefill "
          f"({engine.slots},{engine.chunk}) + decode ({engine.slots},1))")

    prompts = make_prompts(engine.chunk, cfg.vocab_size, args.seed)
    for i, p in enumerate(prompts):
        engine.submit(Request(i, p, max_new=max_new))
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    st = engine.stats
    toks = sum(len(r.generated) for r in done)
    print(f"served: {len(done)} requests, {toks} tokens, wall {wall:.2f}s, "
          f"{st['prefill_calls']} prefill + {st['decode_calls']} decode "
          f"calls, {st['shared_pages']} shared pages, {st['cow_copies']} "
          f"COW copies")

    if sorted(r.req_id for r in done) != list(range(len(prompts))):
        fail(f"served {sorted(r.req_id for r in done)} of "
             f"{len(prompts)} requests")
    for r in done:
        if len(r.generated) != max_new or not all(
                0 <= t < cfg.vocab_size for t in r.generated):
            fail(f"request {r.req_id}: {len(r.generated)} tokens, "
                 f"want {max_new} in [0, {cfg.vocab_size})")
    if not st["shared_pages"] or not st["cow_copies"]:
        fail("prefix attach and copy-on-write did not both run")
    by_id = {r.req_id: r.generated for r in done}
    same = [by_id[i] == by_id[i - 2] for i in (2, 3)]
    print(f"repeated prompts decode as the originals: {same} "
          f"(greedy; not checked: random weights leave near-ties)")
    return cfg, engine


def kernel_phase(cfg, engine, seed: int):
    """Compiled paged-decode kernel vs the scan path, one layer's decode
    attention at the engine's widths and pool geometry.

    Both read the same bf16 pools and block table and accumulate in
    float32.  The scan path runs at float32 matmul precision ("highest")
    as the reference.  Tolerance, elementwise: 2^-7 * (|ref| + ref|v|),
    where ref|v| is the same attention over |v| (the softmax-weighted
    magnitude of the values).  The first term is one bf16 rounding step
    of the output, which both paths cast to bf16; the second is the
    error of rounding every softmax weight once to bf16 before the
    weighted sum, as an MXU pass does.  Accumulating in bf16 across the
    page walk would exceed it.  Idle slots must be exactly 0 in both."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models.layers import attention

    B, N, page, C = (engine.slots, engine.num_blocks, engine.page_size,
                     engine.n_cols)
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    # live slots hold 1 position up to the full table; the last idles
    lens = np.linspace(1, C * page, B - 1).astype(np.int64)
    table = np.full((B, C), -1, np.int32)
    pos = np.full((N, page), -1, np.int32)
    q_pos = np.full((B, 1), -1, np.int32)
    free = rng.permutation(N)
    used = 0
    for b in range(B - 1):
        n = -(-int(lens[b]) // page)
        blocks = free[used:used + n]
        used += n
        table[b, :n] = blocks
        for c, blk in enumerate(blocks):
            hi = min(page, int(lens[b]) - c * page)
            pos[blk, :hi] = np.arange(c * page, c * page + hi)
        q_pos[b, 0] = lens[b] - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (N, page, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (N, page, Hkv, D), jnp.bfloat16)
    table, pos, q_pos = (jnp.asarray(table), jnp.asarray(pos),
                         jnp.asarray(q_pos))

    args = (q, k, v, pos, table, q_pos)
    program = ops.paged_attention.lower(*args).compile().as_text()
    compiled_kernel = "tpu_custom_call" in program
    out_k = np.asarray(ops.paged_attention(*args), np.float32)
    scan = jax.jit(partial(attention, use_kernel=False))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(scan(q, k, v, q_pos, pos, table=table), np.float32)
        ref_abs = np.asarray(scan(q, k, jnp.abs(v), q_pos, pos,
                                  table=table), np.float32)
    bound = 2.0 ** -7 * (np.abs(ref) + ref_abs)
    err = np.abs(out_k - ref)
    worst = float(np.max(err / np.where(bound > 0, bound, 1.0)))
    print(f"kernel vs scan: B={B} Hq={Hq} Hkv={Hkv} D={D} pool={N}x{page} "
          f"cols={C}: max |err| {float(err.max()):.3e}, max |ref| "
          f"{float(np.abs(ref).max()):.3e}, worst err/tolerance "
          f"{worst:.3f}, tpu_custom_call in program: {compiled_kernel}")
    if not np.all(np.isfinite(out_k)) or not np.all(err <= bound):
        fail(f"kernel differs from the scan path beyond tolerance "
             f"(worst err/tolerance {worst:.3f})")
    if np.any(out_k[B - 1] != 0) or np.any(ref[B - 1] != 0):
        fail("idle slot attention output is not exactly 0")
    return compiled_kernel


def pipeline_phase(devices, d: int = 4096, batch: int = 256,
                   n_micro: int = 8):
    """``spmd_pipeline`` over a 4-device stage mesh vs the same stages in
    sequence on one device.  Each stage: tanh(x @ W_i), bf16 in and out
    with float32 accumulation.  Both paths compute the same operations,
    so they may differ only by how a stage's output rounds to bf16:
    tolerance 2^-7, two bf16 steps at the tanh range's top (|y| < 1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.executor import spmd_pipeline
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(PIPE_STAGES)
    kw, kx = jax.random.split(jax.random.PRNGKey(0))
    w = (jax.random.normal(kw, (PIPE_STAGES, d, d), jnp.float32)
         * d ** -0.5).astype(jnp.bfloat16)
    xs = jax.random.normal(kx, (n_micro, batch, d), jnp.bfloat16)

    def stage(wi, x):
        return jnp.tanh(jnp.dot(x, wi, preferred_element_type=jnp.float32)
                        ).astype(jnp.bfloat16)

    w_staged = jax.device_put(w, NamedSharding(mesh, P("stage")))
    owner = {}
    for sh in w_staged.addressable_shards:
        owner[sh.index[0].start] = sh.device
    print("stage params: " + ", ".join(
        f"stage {i} on {owner[i]}" for i in sorted(owner)))
    if sorted(owner) != list(range(PIPE_STAGES)) or len(
            {dv.id for dv in owner.values()}) != PIPE_STAGES:
        fail(f"stage params are not one stage per device: {owner}")

    t0 = time.perf_counter()
    out = jax.jit(lambda w_, x_: spmd_pipeline(stage, w_, x_, mesh,
                                               axis="stage"))(w_staged, xs)
    out = np.asarray(out, np.float32)
    t_pipe = time.perf_counter() - t0

    one = devices[0]
    w1, x1 = jax.device_put(w, one), jax.device_put(xs, one)

    @jax.jit
    def sequential(w_, x_):
        for i in range(PIPE_STAGES):
            x_ = stage(w_[i], x_)
        return x_

    ref = np.asarray(sequential(w1, x1), np.float32)
    err = float(np.max(np.abs(out - ref)))
    print(f"spmd_pipeline: {PIPE_STAGES} stages x ({d}x{d}) bf16, "
          f"microbatches {xs.shape}, first call incl. compile "
          f"{t_pipe:.1f}s; vs sequential on {one}: max |err| {err:.3e}, "
          f"bitwise equal {bool(np.array_equal(out, ref))}")
    if out.shape != ref.shape or not np.all(np.isfinite(out)) \
            or err > 2.0 ** -7:
        fail(f"pipeline differs from sequential: max |err| {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip SPMD stage pipeline phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro sources under {ROOT}/src")
    sys.path.insert(0, str(ROOT / "src"))
    devs = require_tpu(PIPE_STAGES if args.four_chips else 1)

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    if args.four_chips:
        pipeline_phase(devs)
    else:
        cfg, engine = serve_phase(CHIP_SERVE_ARGS, MAX_NEW)
        if not kernel_phase(cfg, engine, seed=1):
            fail("paged decode kernel was not compiled through Mosaic")
        stats = devs[0].memory_stats() or {}
        print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
