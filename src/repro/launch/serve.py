"""Serving launcher: chunked-prefill continuous-batching engine.

``--smoke`` (the default) serves the reduced same-family config, which
runs anywhere; ``--chip`` serves the architecture's chip-share preset
(``configs.<arch>.CHIP``: published widths, depth cut to one chip's
share of a stated deployment), which needs a TPU.  The production-mesh
serve path is proven by dryrun.py.

The engine runs exactly two steady-state jitted shapes: the chunked-
prefill step ``(slots, chunk)`` and the decode tick ``(slots, 1)``;
``--warmup`` compiles both ahead of traffic and reports the compile time
separately from serving throughput.

``--paged`` (default on) stores KV through a block table: per-request
cache memory is ceil((prompt + max_new) / page_size) pages from a shared
``--num-blocks`` pool instead of one worst-case ``cache_len`` per slot,
and the queue backpressures when the pool is exhausted.  ``--no-paged``
selects the dense per-slot ring caches (bitwise reference semantics).
Paged mode shares identical prompt-prefix pages content-addressed
(stored once, refcounted, copy-on-write on divergence — skipped pages
skip their prefill compute too); ``--no-prefix-share`` disables it and
``--prefix-tokens N`` prepends a common system prompt so the fast path
has traffic to hit.
``--temperature``/``--top-p``/``--top-k``/``--rep-penalty`` sample
in-jit with per-slot PRNG streams (temperature 0 = greedy,
bitwise-stable; the repetition penalty reads an in-jit per-slot
seen-token mask).  ``--kernel`` decodes through the fused Pallas
paged-attention kernel (block-table-driven page DMA) instead of the
chunked-gather scan path.

``--replicas N`` serves the same workload through a **fleet**: N engine
replicas on heterogeneous simulated devices (``--devices``, cycled from
``perfmodel.DEVICE_CATALOG``) behind one FIFO queue, placed by Eq. 2
estimated completion time (fast devices take proportionally more
requests), with ``--standby`` spare replicas registered in the broker's
backup pool and ``--heartbeat-every`` ticks between failure-detection
rounds (``--reliability`` < 1 makes seeded mid-decode failures happen:
in-flight requests re-prefill on the drafted replacement).
``--chaos-rate`` > 0 additionally injects a seeded ``FaultPlan`` (crash,
straggle, partition, pool_pressure, corrupt) over the first
``--chaos-ticks`` ticks; requests carry a ``--max-retries`` budget and
the run reports structured per-request outcomes instead of raising away
partial results (``--strict`` restores the raise).

Stateful failover (fleet mode): ``--migration auto|always|never``
controls whether soft-drain and rebalance victims move by verified
KV-page migration (checksum-chained export/import, dedup against the
destination's content registry) instead of re-prefilling — ``auto``
decides per request with the bytes-over-bandwidth vs recompute cost
model; ``--snapshot-every N`` records decode snapshots so crash victims
resume from their last snapshot; ``--rebalance-every N`` adds the load
trigger; ``--hold-pages N`` keeps refcount-zero registered pages LRU-
held so imports and re-admissions dedup against them.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
        --requests 8 --max-new 16 --slots 4 --chunk 16 --page-size 16
    PYTHONPATH=src python -m repro.launch.serve --replicas 3 \
        --devices rtx4090,rtx3080 --standby 1 --requests 12
    PYTHONPATH=src python -m repro.launch.serve --chip --kernel \
        --slots 8 --cache-len 2048 --chunk 128 --prefix-tokens 256
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ALL_ARCHS, get_chip_config, get_smoke_config
from repro.core.perfmodel import DEVICE_CATALOG
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServingEngine
from repro.serve.router import FleetRouter, sim_node


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS), default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced same-family config (default)")
    ap.add_argument("--chip", dest="smoke", action="store_false",
                    help="chip-share preset at published widths "
                         "(configs.<arch>.CHIP; needs a TPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk: admission costs ceil(S/chunk) "
                         "jitted steps instead of S")
    ap.add_argument("--paged", dest="paged", action="store_true",
                    default=True,
                    help="block-table KV cache: per-request pages from a "
                         "shared pool (default)")
    ap.add_argument("--no-paged", dest="paged", action="store_false",
                    help="dense per-slot ring caches (reference semantics)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="positions per cache page (paged mode)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in pages; 0 = same memory as the dense "
                         "cache (slots * cache_len / page_size)")
    ap.add_argument("--no-prefix-share", dest="prefix_share",
                    action="store_false", default=True,
                    help="disable content-addressed prefix sharing "
                         "(paged mode: identical prompt-prefix pages are "
                         "stored once, attached by refcount, and "
                         "copy-on-write on divergence)")
    ap.add_argument("--prefix-tokens", type=int, default=0,
                    help="prepend this many shared system-prompt tokens "
                         "to every request (exercises prefix sharing)")
    ap.add_argument("--kernel", action="store_true",
                    help="decode attention through the fused Pallas "
                         "paged-decode kernel (paged mode only; compiled "
                         "through Mosaic on a TPU, interpreted on the CPU)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample only among the k highest-logit tokens "
                         "(0 = no top-k cut; with --temperature > 0)")
    ap.add_argument("--rep-penalty", type=float, default=1.0,
                    help="CTRL-style repetition penalty on already-emitted "
                         "tokens (1.0 = off; applies to greedy slots too)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a FleetRouter with this many "
                         "engine replicas (1 = single engine, no router)")
    ap.add_argument("--devices", default="rtx4090,rtx3080",
                    help="comma-separated DEVICE_CATALOG names cycled "
                         "across replicas (fleet mode placement speeds)")
    ap.add_argument("--standby", type=int, default=0,
                    help="spare replicas registered in the broker backup "
                         "pool, drafted by speed match on failure")
    ap.add_argument("--heartbeat-every", type=int, default=0,
                    help="fleet mode: broker heartbeat round every N "
                         "engine ticks (0 = no failure detection)")
    ap.add_argument("--reliability", type=float, default=1.0,
                    help="per-heartbeat replica survival probability "
                         "(< 1 exercises seeded mid-decode failover)")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="fleet mode: per-(tick, replica) probability of "
                         "a seeded injected fault (crash / straggle / "
                         "partition / pool_pressure); 0 = no fault plan")
    ap.add_argument("--chaos-ticks", type=int, default=64,
                    help="inject faults over the first N ticks of the "
                         "chaos plan (with --chaos-rate > 0)")
    ap.add_argument("--chaos-seed", type=int, default=-1,
                    help="fault-plan RNG seed (-1 = reuse --seed)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="per-request retry budget: a request requeued "
                         "by failures more than this many times ends "
                         "with outcome failed_retries instead of "
                         "retrying forever")
    ap.add_argument("--strict", action="store_true",
                    help="fleet mode: raise on any failed request "
                         "instead of returning partial results with "
                         "structured outcomes")
    ap.add_argument("--migration", choices=["auto", "always", "never"],
                    default="auto",
                    help="fleet mode: soft-drain/rebalance victims move "
                         "via verified KV-page migration instead of "
                         "re-prefilling; 'auto' runs the bytes-over-"
                         "bandwidth vs recompute cost model, 'always' "
                         "skips it, 'never' restores drain-and-requeue")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="fleet mode: record (prefix digests, generated "
                         "tokens) for every admitted request every N "
                         "ticks so crash victims resume decoding instead "
                         "of starting over (0 = off)")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="fleet mode: every N ticks, migrate the newest "
                         "request off a replica whose pending tokens "
                         "exceed rebalance_factor x the least-loaded "
                         "peer (0 = off)")
    ap.add_argument("--hold-pages", type=int, default=0,
                    help="per-engine LRU hold: keep up to N refcount-"
                         "zero registered pages resident instead of "
                         "scrubbing, so re-admissions and migration "
                         "imports dedup against them (paged sharing "
                         "mode only)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip ahead-of-traffic compilation of the two "
                         "engine shapes")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def model_config(args):
    return (get_smoke_config(args.arch) if args.smoke
            else get_chip_config(args.arch))


def engine_builder(args, cfg):
    """Seeded random weights for ``cfg`` and a zero-argument builder of
    engines over them (fleet replicas share the one parameter pytree).
    The init is jitted so each weight is drawn straight into bf16,
    without a float32 copy of the largest matrices on the device."""
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)

    def build_engine():
        return ServingEngine(params, cfg, slots=args.slots,
                             cache_len=args.cache_len, chunk=args.chunk,
                             paged=args.paged, page_size=args.page_size,
                             num_blocks=args.num_blocks or None,
                             use_kernel=args.kernel, seed=args.seed,
                             share_prefix=args.prefix_share,
                             hold_pages=args.hold_pages)
    return build_engine


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()
    cfg = model_config(args)
    build_engine = engine_builder(args, cfg)

    if args.replicas > 1:
        serve_fleet(args, cfg, build_engine)
        return

    engine = build_engine()
    if not args.no_warmup:
        t0 = time.time()
        engine.warmup()
        print(f"warmup: compiled prefill ({args.slots},{engine.chunk}) + "
              f"decode ({args.slots},1) in {time.time() - t0:.2f}s")
    key = jax.random.PRNGKey(args.seed + 1)
    system = _system_prefix(args, cfg)
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        prompt = system + jax.random.randint(sub, (4 + i % 4,), 0,
                                             cfg.vocab_size).tolist()
        engine.submit(Request(i, prompt, max_new=args.max_new,
                              temperature=args.temperature,
                              top_p=args.top_p, top_k=args.top_k,
                              rep_penalty=args.rep_penalty))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    st = engine.stats
    mode = (f"paged:{engine.num_blocks}x{engine.page_size}"
            + ("+kernel" if engine.use_kernel else "")
            if engine.paged else "dense")
    print(f"{cfg.name}: served {len(done)} requests, {toks} tokens in "
          f"{dt:.2f}s ({toks/dt:.1f} tok/s, slots={args.slots}, {mode})")
    print(f"  engine calls: {st['prefill_calls']} prefill (chunk="
          f"{engine.chunk}) + {st['decode_calls']} decode ticks, "
          f"{st['admitted']} admissions, {st['backpressure']} backpressure, "
          f"{st['step_compiles']} step programs compiled or loaded")
    if engine._can_share:
        print(f"  prefix sharing: {st['shared_pages']} pages attached "
              f"({st['shared_tokens']} prompt tokens skipped prefill), "
              f"{st['cow_copies']} copy-on-write")
    for r in sorted(done, key=lambda r: r.req_id)[:4]:
        print(f"  req{r.req_id}: prompt={r.prompt} -> {r.generated}")


def _system_prefix(args, cfg):
    """--prefix-tokens: a deterministic shared system prompt prepended to
    every request so the prefix-sharing fast path has something to hit."""
    if not args.prefix_tokens:
        return []
    key = jax.random.PRNGKey(args.seed + 7)
    return jax.random.randint(key, (args.prefix_tokens,), 0,
                              cfg.vocab_size).tolist()


def serve_fleet(args, cfg, build_engine):
    """--replicas > 1: broker-routed fleet over heterogeneous simulated
    devices, one shared FIFO queue, ECT placement, seeded failover."""
    devices = [d.strip() for d in args.devices.split(",") if d.strip()]
    for d in devices:
        if d not in DEVICE_CATALOG:
            raise SystemExit(f"--devices: unknown device {d!r} "
                             f"(catalog: {', '.join(DEVICE_CATALOG)})")
    def node(i):
        return sim_node(devices[i % len(devices)],
                        reliability=args.reliability)
    plan = None
    if args.chaos_rate > 0:
        from repro.serve.faults import FaultPlan
        chaos_seed = args.seed if args.chaos_seed < 0 else args.chaos_seed
        plan = FaultPlan.seeded(
            chaos_seed, ticks=args.chaos_ticks,
            replica_ids=list(range(args.replicas + args.standby)),
            rate=args.chaos_rate)
        print(f"chaos: {len(plan)} seeded faults over {args.chaos_ticks} "
              f"ticks (seed={chaos_seed}, rate={args.chaos_rate})")
    router = FleetRouter(
        [(build_engine(), node(i)) for i in range(args.replicas)],
        [(build_engine(), node(args.replicas + i))
         for i in range(args.standby)],
        seed=args.seed, fault_plan=plan, migration=args.migration,
        snapshot_every=args.snapshot_every,
        rebalance_every=args.rebalance_every)
    if not args.no_warmup:
        t0 = time.time()
        for rep in router.replicas:
            rep.engine.warmup()
        print(f"warmup: compiled {len(router.replicas)} replicas in "
              f"{time.time() - t0:.2f}s (standby replicas compile when "
              f"drafted)")
    key = jax.random.PRNGKey(args.seed + 1)
    system = _system_prefix(args, cfg)
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        prompt = system + jax.random.randint(sub, (4 + i % 4,), 0,
                                             cfg.vocab_size).tolist()
        router.submit(Request(i, prompt, max_new=args.max_new,
                              temperature=args.temperature,
                              top_p=args.top_p, top_k=args.top_k,
                              rep_penalty=args.rep_penalty,
                              max_retries=args.max_retries))
    t0 = time.time()
    res = router.run(heartbeat_every=args.heartbeat_every,
                     strict=args.strict)
    dt = time.time() - t0
    done = res.completed
    toks = sum(len(r.generated) for r in done)
    st = router.stats
    print(f"{cfg.name} fleet: {len(router.live_replicas())} live replicas "
          f"served {len(done)}/{len(done) + len(res.failed)} requests, "
          f"{toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s, "
          f"{res.ticks} ticks)")
    print(f"  outcomes: " + ", ".join(
        f"{k}={v}" for k, v in sorted(res.outcomes().items())))
    print(f"  router: {st['placed']} placements, {st['held']} held ticks, "
          f"{st['failures']} failures, {st['requeued']} requeued, "
          f"{st['replacements']} drafted from backup")
    degraded = {k: st[k] for k in ("soft_drains", "preempted", "straggles",
                                   "partitions", "partition_heals",
                                   "partition_escalations", "pool_pressure",
                                   "injected_crashes", "corrupt_faults")
                if st.get(k)}
    if degraded:
        print("  degraded mode: " + ", ".join(
            f"{k}={v}" for k, v in sorted(degraded.items())))
    failover = {k: st[k] for k in ("migrations", "migration_fallbacks",
                                   "rebalances", "rebalance_holds",
                                   "snapshot_restores") if st.get(k)}
    reps = list(router.replicas) + list(router._standby.values())
    deduped = sum(r.engine.stats.get("deduped_pages", 0) for r in reps)
    resumed = sum(r.engine.stats.get("resumed_tokens", 0) for r in reps)
    rejects = sum(r.engine.stats.get("import_rejects", 0) for r in reps)
    if failover or deduped or resumed or rejects:
        print("  stateful failover: " + ", ".join(
            f"{k}={v}" for k, v in sorted(failover.items()))
            + (f", deduped_pages={deduped}" if deduped else "")
            + (f", resumed_tokens={resumed}" if resumed else "")
            + (f", import_rejects={rejects}" if rejects else ""))
    for r in sorted(res.failed, key=lambda r: r.req_id)[:6]:
        tr = res.traces.get(r.req_id, {})
        print(f"  FAILED req{r.req_id}: outcome={r.outcome} "
              f"retries={r.retries}/{r.max_retries} "
              f"placements={tr.get('placements')}")
    shared = sum(r.engine.stats.get("shared_pages", 0)
                 for r in router.replicas)
    cow = sum(r.engine.stats.get("cow_copies", 0) for r in router.replicas)
    if any(r.engine._can_share for r in router.replicas):
        print(f"  prefix sharing: {shared} pages attached fleet-wide, "
              f"{cow} copy-on-write")
    for rep in sorted(router.replicas, key=lambda r: r.replica_id):
        state = "live" if rep.alive else "DEAD"
        print(f"  replica {rep.replica_id} [{rep.node.device.name}, "
              f"{state}]: served {len(rep.served)} requests "
              f"{sorted(rep.served)}")


if __name__ == "__main__":
    main()
