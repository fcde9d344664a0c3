"""Serving engine: prefill + single-token decode over the model zoo's
cache pytrees (KV / MLA-latent / SSM-state / SWA-ring), greedy or
per-slot temperature/top-p sampling, and a slot-based continuous batcher
with **chunked prefill** (admission costs ceil(S/chunk) jitted steps, the
decode tick is one jitted step over all slots) and a **paged slot cache**
(vLLM-style block table: per-request cache memory is ceil((prompt +
max_new) / page_size) pages from a shared pool instead of one
engine-wide worst-case ``cache_len`` per slot).

``make_prefill_step`` / ``make_decode_step`` are the functions the
multi-pod dry-run lowers for the ``prefill_32k`` / ``decode_32k`` /
``long_500k`` input shapes; ``make_engine_step`` is the single
masked-slot step function behind ``ServingEngine`` (chunked prefill and
decode tick are the same function at two shapes, jitted under two names:
``engine_step_prefill`` and ``engine_step_decode``).
"""
from __future__ import annotations

import time
import zlib
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.paged_attention import live_columns
from repro.models.config import ATTN, MAMBA, RWKV, SWA, ModelConfig
from repro.models.layers import NEG_INF, swa_ring_blocks
from repro.models.transformer import forward, init_cache, unembed

Array = jax.Array

# pool leaves of paged attention-family caches (block-indexed, shared
# across slots); everything else in a cache pytree is per-slot state
POOL_LEAVES = ("k", "v", "pos", "ckv", "krope")

# host spans of the serving loop (``jax.profiler.TraceAnnotation``): they
# record only while a profiler trace runs, on the device trace's clock,
# and cost one inactive TraceMe each otherwise
SPAN_TICK = "engine.tick"           # all of ServingEngine.tick()
SPAN_ADMIT = "engine.admit"         # one request's admission
SPAN_PREFILL = "engine.prefill"     # one chunk: inputs + step dispatch
SPAN_DECODE = "engine.decode"       # the decode step: inputs + dispatch
SPAN_READBACK = "engine.readback"   # waiting for and copying step tokens
SPAN_RELEASE = "engine.release"     # freeing and scrubbing a slot's pages
SPAN_COMPILE = "engine.compile"     # first dispatch of a step shape


def make_prefill_step(cfg: ModelConfig, *, kv_chunk: int = 1024) -> Callable:
    """(params, caches, batch, positions) -> (last-token logits, caches).
    batch carries (B, S_prompt) tokens (and/or stub embeddings)."""
    def prefill_step(params, caches, batch, positions):
        logits, _, caches = forward(params, cfg, batch, caches=caches,
                                    positions=positions, kv_chunk=kv_chunk)
        return logits[:, -1:, :], caches
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, kv_chunk: int = 1024) -> Callable:
    """(params, caches, tokens (B,1) | embeds, positions (B,1)) ->
    (logits (B,1,V), caches).  One new token against the running cache.
    (Continuous batching goes through ``make_engine_step`` instead, whose
    masked-slot semantics are the tested path.)"""
    def decode_step(params, caches, batch, positions):
        logits, _, caches = forward(params, cfg, batch, caches=caches,
                                    positions=positions, decode=True,
                                    kv_chunk=kv_chunk)
        return logits, caches
    return decode_step


def topp_sample(keys: Array, logits: Array, temperature: Array,
                top_p: Array, top_k: Optional[Array] = None) -> Array:
    """Per-row temperature + nucleus (+ optional top-k) sampling, fully
    in-jit.

    keys: (B, 2) uint32 raw threefry key data; logits: (B, V) float32;
    temperature / top_p: (B,) float32; top_k: (B,) int32, 0 = no top-k
    limit.  Rows are sampled independently (vmapped categorical) from
    the smallest prefix of the sorted distribution whose mass reaches
    top_p, intersected with the top_k highest-logit tokens (the top
    token always stays, so top_p -> 0 or top_k == 1 degenerates to
    greedy).  Returns (B, 1) int32.
    """
    V = logits.shape[-1]
    lg = logits / jnp.maximum(temperature, 1e-6)[:, None]
    order = jnp.argsort(-lg, axis=-1)
    slg = jnp.take_along_axis(lg, order, axis=-1)
    probs = jax.nn.softmax(slg, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]          # top-1 always kept
    if top_k is not None:
        k_eff = jnp.where(top_k > 0, top_k, V)     # 0 disables the cut
        keep &= jnp.arange(V, dtype=jnp.int32)[None, :] < k_eff[:, None]
    slg = jnp.where(keep, slg, NEG_INF)
    idx = jax.vmap(jax.random.categorical)(keys, slg)            # (B,)
    return jnp.take_along_axis(order, idx[:, None], axis=-1).astype(jnp.int32)


def apply_repetition_penalty(logits: Array, seen: Array,
                             rep_penalty: Array) -> Array:
    """CTRL-style repetition penalty, per row: logits of tokens the row
    has already seen (prompt + generated so far) are divided by the
    penalty when positive and multiplied when negative, discouraging
    re-emission.  logits: (B, V) f32; seen: (B, V) bool;
    rep_penalty: (B,) f32, 1.0 = off.  Rows with penalty 1.0 are
    returned bitwise-untouched (the ``where`` keeps the original
    values), so default slots never drift."""
    pen = rep_penalty[:, None]
    scaled = jnp.where(logits > 0, logits / pen, logits * pen)
    return jnp.where(seen & (pen != 1.0), scaled, logits)


def make_engine_step(cfg: ModelConfig, *, kv_chunk: int = 1024,
                     paged: bool = False,
                     use_kernel: bool = False) -> Callable:
    """(params, caches, seen (B,V) bool, tokens (B,S), positions (B,S),
    table (B,n_cols), rng_keys (B,2) uint32, temperature (B,), top_p
    (B,), top_k (B,) int32, rep_penalty (B,)) ->
    (next-token ids (B,1) int32, caches, seen).

    The one step function behind the continuous batcher: the SAME
    function serves chunked prefill (S = chunk) and the batched decode
    tick (S = 1, which statically selects the single-token cache paths —
    absorbed MLA etc.).  Rows/entries with position -1 are cache/state
    no-ops, so idle slots ride along for free.  Only the LAST position is
    unembedded (the engine never consumes mid-chunk logits) and token
    selection happens inside the jit — greedy argmax for slots with
    temperature 0 (bitwise-identical to the greedy-only engine),
    per-slot temperature/top-p/top-k via a (B, 2) PRNG-key array
    otherwise — so one (slots, vocab) matmul and (B, 1) token ids are
    all that leave the step, never (B, S, V) logits.

    ``seen`` is the per-slot already-emitted-token mask, maintained
    in-jit: the step scatters its valid input tokens (prompt chunks and
    fed-back decode tokens alike) before selection, so repetition
    penalty (``rep_penalty`` != 1, CTRL-style) sees prompt + generation
    so far without any (B, V) traffic leaving the device.  The scatter,
    the penalty and the sampling branch are all ``lax.cond``-gated on
    the same predicates, so the all-default steady state pays for none
    of them.  (Gating the scatter is sound: a slot's mask is only ever
    read while its penalty != 1, a request's penalty is fixed for its
    lifetime — so every step of a penalized request runs with the cond
    on — and the mask is cleared host-side at admission.)

    ``paged=True`` routes every attention-family cache access through the
    block ``table`` (dense engines pass a dummy, which the forward
    ignores); ``use_kernel=True`` additionally dispatches paged S=1
    decode attention to the fused Pallas paged-decode kernel (the block
    table drives the page DMA — no gathered K/V copy in HBM).
    """
    def engine_step(params, caches, seen, tokens, positions, table,
                    rng_keys, temperature, top_p, top_k, rep_penalty):
        B = tokens.shape[0]
        rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                                tokens.shape)
        seen = jax.lax.cond(
            jnp.any(rep_penalty != 1.0),
            lambda sn: sn.at[rows, tokens].max(positions >= 0),
            lambda sn: sn, seen)
        h, _, caches = forward(params, cfg, {"tokens": tokens},
                               caches=caches, positions=positions,
                               decode=tokens.shape[1] == 1,
                               kv_chunk=kv_chunk, compute_logits=False,
                               masked_slots=True,
                               block_table=table if paged else None,
                               use_kernel=use_kernel)
        logits = unembed(params, cfg, h[:, -1:, :])              # (B,1,V)
        lg = logits[:, 0, :]
        # both conds keep the all-default steady state on the cheap path:
        # no (B, V) where-rewrite, no vocab sort/softmax at runtime
        lg = jax.lax.cond(
            jnp.any(rep_penalty != 1.0),
            lambda l: apply_repetition_penalty(l, seen, rep_penalty),
            lambda l: l, lg)
        greedy = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
        tok = jax.lax.cond(
            jnp.any(temperature > 0.0),
            lambda: jnp.where(temperature[:, None] > 0.0,
                              topp_sample(rng_keys, lg, temperature,
                                          top_p, top_k), greedy),
            lambda: greedy)
        return tok, caches, seen
    return engine_step


def _stamp() -> float:
    """The host clock of the ``Request`` stamps.  Replay stays bitwise:
    the stamps are recorded for measurement and no decision reads them."""
    return time.perf_counter()  # repro-lint: disable=DET001 (stamps only)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, which a jit of it gives its compiled program
    (``jit_<name>`` in HLO dumps and profiler traces)."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


def sample(logits: Array, key, temperature: float = 0.0) -> Array:
    """logits (B,1,V) -> tokens (B,1)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1) \
        .astype(jnp.int32)


def generate(params, cfg: ModelConfig, prompts: Array, *, max_new: int,
             cache_len: Optional[int] = None, temperature: float = 0.0,
             seed: int = 0, jit: bool = True) -> Array:
    """Batched generation.  prompts: (B, S_prompt) int32.
    Returns (B, S_prompt + max_new)."""
    B, S0 = prompts.shape
    cache_len = cache_len or (S0 + max_new)
    caches = init_cache(cfg, B, cache_len)
    prefill = make_prefill_step(cfg)
    decode = make_decode_step(cfg)
    if jit:
        prefill, decode = jax.jit(prefill), jax.jit(decode)
    pos = jnp.broadcast_to(jnp.arange(S0, dtype=jnp.int32)[None], (B, S0))
    logits, caches = prefill(params, caches, {"tokens": prompts}, pos)
    key = jax.random.PRNGKey(seed)
    out = [prompts]
    tok = sample(logits, key, temperature)
    for t in range(max_new):
        out.append(tok)
        if t == max_new - 1:
            break
        key, sub = jax.random.split(key)
        # np, not jnp: a device op here would dispatch once per decoded
        # token (HOT001); decode() converts the operand batch once
        posd = np.full((B, 1), S0 + t, np.int32)
        logits, caches = decode(params, caches, {"tokens": tok}, posd)
        tok = sample(logits, sub, temperature)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Slot-based continuous batcher (production-style serving loop)
# ---------------------------------------------------------------------------

@dataclass
class Request:
    req_id: int
    prompt: List[int]
    max_new: int
    temperature: float = 0.0     # 0 -> greedy (bitwise-stable default)
    top_p: float = 1.0
    top_k: int = 0               # 0 -> no top-k cut
    rep_penalty: float = 1.0     # 1.0 -> no repetition penalty
    generated: List[int] = field(default_factory=list)
    pending: int = -1            # next token to feed/emit
    done: bool = False
    # chain digests of the prompt's full prefix pages, stamped by
    # drain_requests() so a failover requeue keeps its prefix identity
    # for the router's affinity tie-break (None until drained)
    prefix_digests: Optional[List[int]] = None
    # degraded-mode lifecycle (managed by serve.router.FleetRouter):
    # retries counts requeue-from-prompt events caused by faults (crash,
    # soft-drain, partition timeout — preemption is free); past
    # max_retries the request stops consuming the fleet and fails
    # terminally.  outcome is stamped exactly once when the request
    # leaves the system: "ok" | "failed_retries" | "failed_unservable"
    # | "deadline_exceeded" (None while still in flight).
    retries: int = 0
    max_retries: int = 3
    outcome: Optional[str] = None
    # snapshot restore (stateful failover): tokens this request had
    # already decoded at the router's last periodic snapshot.  Stamped
    # by the crash path before requeue; admission re-prefills prompt +
    # resume as ONE extended prompt (chunked prefill is bitwise-equal to
    # the decode that first produced that KV) and restores ``generated``
    # from it, so only tokens decoded since the snapshot are re-decoded.
    resume_tokens: Optional[List[int]] = None
    # host-clock stamps (``_stamp``, None until set): submit() stamps
    # submitted_at; admission stamps admitted_at when it pops the
    # request off the queue and prefilled_at once its first token is on
    # the host.  A requeue (drain, preemption) clears the last two.
    # Observations only: no decision reads them, and equality ignores them
    submitted_at: Optional[float] = field(default=None, compare=False)
    admitted_at: Optional[float] = field(default=None, compare=False)
    prefilled_at: Optional[float] = field(default=None, compare=False)


class BlockAllocator:
    """Host-side free-list over the paged cache pool, **reference-counted
    and content-addressed**.

    Admission is **reservation-based**: a request reserves its worst case
    (``ceil((prompt + max_new) / page_size)`` pages) up front, takes pages
    lazily (prompt pages at admit, one page per crossed boundary during
    decode), and releases everything on finish.  Because reserved pages
    are guaranteed allocatable, decode-time extends can never fail —
    pool exhaustion surfaces only as admission backpressure (the queue
    waits) instead of a mid-decode crash.

    **Refcounts**: ``alloc_one`` hands out a page at refcount 1;
    ``share`` bumps it (a second slot's table row now points at the same
    physical page); ``free`` decrements and only returns a page to the
    free list — and reports it in its return value, so the engine scrubs
    it — when the count reaches zero.  Freeing an unheld page asserts
    (double-free protection).

    **Content addressing**: ``register`` maps a prefix-page digest to a
    resident block; ``lookup`` resolves a digest back to the block.  A
    ``check`` value (parent block id + the page's exact tokens) rides
    along with every registration: lookup verifies it, so a digest
    collision falls back to a miss (the caller allocates a private page)
    instead of silently attaching wrong content.  Because the check
    chains through parent *block ids*, matching check values imply
    byte-identical token prefixes by induction.  Registrations hold no
    refcount of their own and are dropped when the block is physically
    freed.

    **LRU hold** (``hold_limit`` > 0): up to that many refcount-zero
    registered pages are HELD instead of freed — registration and
    content intact — so a popular prefix readmitted after a brief idle
    gap attaches its pages instead of re-prefilling.  Held pages count
    as available capacity (``can_reserve``); a reservation that needs
    them evicts the oldest first, and evicted pages land on
    ``take_scrub()`` so the engine zeroes their stale content before
    reuse.  ``hold_limit == 0`` (the default) keeps the exact
    free-at-refcount-zero semantics."""

    def __init__(self, num_blocks: int, hold_limit: int = 0):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self.reserved = 0
        # pages withheld from NEW reservations by fault injection
        # (pool_pressure): existing reservations are untouched, so
        # decode-time extends stay infallible — pressure only
        # backpressures admission.  May transiently exceed n_free.
        self.withheld = 0
        self.hold_limit = hold_limit
        self._held: List[int] = []                 # LRU, oldest first
        # held pages evicted back to the free list: content is stale,
        # the engine drains this and scrubs them before any reuse
        self._pending_scrub: List[int] = []
        self.refcount: Dict[int, int] = {}
        self._by_digest: Dict[int, int] = {}       # digest -> block
        self._entries: Dict[int, tuple] = {}       # block -> (digest, check)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_held(self) -> int:
        return len(self._held)

    def can_reserve(self, n: int) -> bool:
        return self.n_free + self.n_held - self.reserved - self.withheld >= n

    def reserve(self, n: int) -> bool:
        """Set aside ``n`` future pages; False = backpressure."""
        if not self.can_reserve(n):
            return False
        # reservations must be backed by truly-free pages (alloc_one
        # pops the free list): evict exactly the held pages this one
        # needs, oldest first
        short = n - (self.n_free - self.reserved - self.withheld)
        if short > 0:
            self.evict_held(short)
        self.reserved += n
        return True

    def evict_held(self, k: int) -> List[int]:
        """Evict up to ``k`` oldest held pages back to the free list
        (deregistered; queued on ``take_scrub`` — their content is stale
        from the pool's point of view)."""
        out: List[int] = []
        for _ in range(max(0, min(k, len(self._held)))):
            b = self._held.pop(0)
            self.deregister(b)
            self._free.append(b)
            self._pending_scrub.append(b)
            out.append(b)
        return out

    def take_scrub(self) -> List[int]:
        """Blocks evicted from the hold since the last call — free-listed
        but carrying stale content; the caller owns scrubbing them."""
        out, self._pending_scrub = self._pending_scrub, []
        return out

    def alloc_one(self) -> int:
        """Take one page against an existing reservation (refcount 1)."""
        assert self._free, "BlockAllocator: reservation invariant violated"
        self.reserved -= 1
        assert self.reserved >= 0, "alloc_one without a reservation"
        b = self._free.pop()
        self.refcount[b] = 1
        return b

    def share(self, block: int) -> None:
        """Another table row now references ``block``.  Reviving a HELD
        page (refcount zero, kept resident by the LRU hold) takes it
        back out of the hold at refcount 1 — the hold paying off."""
        if not self.refcount.get(block, 0) and block in self._held:
            self._held.remove(block)
            self.refcount[block] = 1
            return
        assert self.refcount.get(block, 0) > 0, \
            f"BlockAllocator: share of unheld block {block}"
        self.refcount[block] += 1

    def lookup(self, digest: int, check: tuple) -> Optional[int]:
        """Resolve a prefix-page digest to its resident block, or None on
        a miss or a verified hash collision (check mismatch)."""
        b = self._by_digest.get(digest)
        if b is None or self._entries[b][1] != check:
            return None
        return b

    def register(self, digest: int, check: tuple, block: int) -> bool:
        """Advertise ``block`` as holding the prefix page ``digest``.
        First registration wins (an existing entry — including a
        colliding one — is kept); a block advertises one digest."""
        if digest in self._by_digest or block in self._entries:
            return False
        self._by_digest[digest] = block
        self._entries[block] = (digest, check)
        return True

    def deregister(self, block: int) -> None:
        """Drop the block's digest advertisement (content is about to
        diverge, or the block is being physically freed)."""
        ent = self._entries.pop(block, None)
        if ent is not None:
            self._by_digest.pop(ent[0], None)

    def is_registered(self, block: int) -> bool:
        return block in self._entries

    def free(self, blocks: List[int], unreserve: int = 0) -> List[int]:
        """Drop one reference per listed block.  Returns the blocks whose
        refcount reached zero — ONLY those went back to the free list and
        only those may (and must) be scrubbed; pages still shared by
        another slot stay live and untouched."""
        freed: List[int] = []
        for b in blocks:
            rc = self.refcount.get(b, 0)
            assert rc > 0, f"BlockAllocator: double free of [{b}]"
            if rc == 1:
                del self.refcount[b]
                if self.hold_limit > 0 and self.is_registered(b):
                    # LRU hold: keep the page resident — registration
                    # and content intact — so a readmitted prefix can
                    # attach it; NOT reported freed (must not be
                    # scrubbed while held)
                    self._held.append(b)
                    self.evict_held(len(self._held) - self.hold_limit)
                else:
                    self.deregister(b)
                    self._free.append(b)
                    freed.append(b)
            else:
                self.refcount[b] = rc - 1
        self.reserved -= unreserve
        assert self.reserved >= 0 and self.n_free <= self.num_blocks
        return freed


def _clear_slot(caches, s, skip_pools: bool = False):
    """Zero one slot's cache/state across every cache kind (KV /
    MLA-latent / SSM-state / SWA-ring) and invalidate its positions.

    Slot is ALWAYS the first axis after the structural prefix: prefix
    caches are (slots, ...); stack caches carry one leading ``n_periods``
    axis, i.e. (periods, slots, ...).  Deciding on the pytree path (not
    on shape coincidences like ``shape[0] != slots``) keeps the reset
    correct when n_periods happens to equal the slot count.

    ``skip_pools=True`` (paged engines) leaves block-pool leaves alone —
    pools are indexed by block id, not slot, and recycled blocks are
    scrubbed by ``_clear_blocks`` when they return to the free list."""
    def clear(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if skip_pools and name in POOL_LEAVES:
            return leaf
        top = str(getattr(path[0], "key", path[0]))
        bdim = 1 if top == "stack" else 0
        if leaf.ndim <= bdim:            # defensive: scalar/period-only leaf
            return leaf
        idx = (slice(None),) * bdim + (s,)
        fill = -1 if name == "pos" else 0
        return leaf.at[idx].set(jnp.asarray(fill, leaf.dtype))
    return jax.tree_util.tree_map_with_path(clear, caches)


def _pool_mixer(cfg: ModelConfig, path) -> str:
    """Mixer kind ("attn" / "swa" / ...) of the layer owning a pool leaf,
    from the leaf's pytree path.  Per-cache-kind pools give SWA layers
    their own (smaller) block-id space, so scrubs must route each block
    vector to the right pools — decided on the structural path (prefix
    index / period index), never on shape coincidences."""
    top = str(getattr(path[0], "key", path[0]))
    idx = getattr(path[1], "idx", None)
    specs = cfg.prefix_layers if top == "prefix" else cfg.period
    return specs[idx].mixer


def make_clear_blocks(cfg: ModelConfig) -> Callable:
    """(caches, blocks, blocks_swa) -> caches.  Scrub the given pool
    blocks in every paged cache leaf: keys/values to 0 and positions to
    -1, so a recycled block can never leak a stale key into its next
    owner (old positions could pass the causal mask).  Full-attention
    pools take ids from ``blocks``, sliding-window pools from
    ``blocks_swa`` — the two block-id spaces are disjoint per-kind pools.
    Each vector is fixed-width int32 padded with an out-of-pool id
    (scatter mode='drop' ignores the padding), so the jit compiles once
    regardless of how many blocks a request held."""
    def clear_blocks(caches, blocks, blocks_swa):
        def clear(path, leaf):
            name = str(getattr(path[-1], "key", path[-1]))
            if name not in POOL_LEAVES:
                return leaf
            top = str(getattr(path[0], "key", path[0]))
            bdim = 1 if top == "stack" else 0
            ids = blocks_swa if _pool_mixer(cfg, path) == SWA else blocks
            idx = (slice(None),) * bdim + (ids,)
            fill = -1 if name == "pos" else 0
            return leaf.at[idx].set(jnp.asarray(fill, leaf.dtype),
                                    mode="drop")
        return jax.tree_util.tree_map_with_path(clear, caches)
    return clear_blocks


def _path_key(path) -> str:
    """Stable string key of a cache pytree path ("prefix/3/k",
    "stack/0/v", ...) — the host-side index of migration payloads."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


@dataclass
class RequestState:
    """A live request's complete decode state, serialized by
    ``ServingEngine.export_state`` for verified migration to another
    replica (``import_state``).

    ``pool`` / ``slot_state`` map pytree-path keys to host numpy arrays:
    each pool entry holds the request's pages for one paged cache leaf
    (page rows ordered like ``cols`` / ``cols_swa``), each slot_state
    entry one per-slot recurrent carry.  ``digests`` is the prompt's
    chain-digest trail — the content address the importer dedups against
    its own registry, so pages already resident at the destination never
    cross the wire twice.  ``checksum`` chains crc32 over the tokens,
    the position, and every payload array in deterministic order: the
    importer recomputes the chain and rejects the WHOLE transfer on any
    mismatch, so corrupted bytes are never attached to a pool."""
    req: Request
    position: int
    fingerprint: tuple
    cols: List[int]                    # attn table columns, export order
    cols_swa: List[int]                # swa ring columns, export order
    pool: Dict[str, np.ndarray]
    slot_state: Dict[str, np.ndarray]
    digests: List[int]
    checksum: int
    payload_bytes: int


def state_checksum(state: "RequestState") -> int:
    """Chained crc32 over a migration payload — tokens, position, then
    every payload array in sorted-key order.  One flipped byte anywhere
    breaks the chain, so import verification is all-or-nothing."""
    req = state.req
    c = zlib.crc32(np.asarray(req.prompt, np.int64).tobytes())
    c = zlib.crc32(np.asarray(req.generated + [req.pending],
                              np.int64).tobytes(), c)
    c = zlib.crc32(np.int64(state.position).tobytes(), c)
    for key in sorted(state.pool):
        c = zlib.crc32(np.ascontiguousarray(state.pool[key]).tobytes(), c)
    for key in sorted(state.slot_state):
        c = zlib.crc32(np.ascontiguousarray(state.slot_state[key]).tobytes(),
                       c)
    return c


def make_gather_blocks(cfg: ModelConfig) -> Callable:
    """(caches, blocks, blocks_swa, s) -> payload pytree with the SAME
    treedef as ``caches``: pool leaves become their pages at the given
    fixed-width padded block ids (full-attention pools gather ``blocks``,
    sliding-window pools ``blocks_swa``), per-slot leaves become slot
    ``s``'s row — one jitted call lifts a request's entire cache state
    (KV pages + recurrent carries) off the device.  Out-of-pool padding
    ids clamp in-bounds; the engine slices the garbage rows away."""
    def gather_blocks(caches, blocks, blocks_swa, s):
        def take(path, leaf):
            name = str(getattr(path[-1], "key", path[-1]))
            top = str(getattr(path[0], "key", path[0]))
            bdim = 1 if top == "stack" else 0
            if name in POOL_LEAVES:
                ids = blocks_swa if _pool_mixer(cfg, path) == SWA else blocks
                return leaf[(slice(None),) * bdim + (ids,)]
            if leaf.ndim <= bdim:
                return leaf
            return leaf[(slice(None),) * bdim + (s,)]
        return jax.tree_util.tree_map_with_path(take, caches)
    return gather_blocks


def make_scatter_blocks(cfg: ModelConfig) -> Callable:
    """Inverse of ``make_gather_blocks``: write a payload pytree back
    into the pools at the given block ids and into slot ``s``'s per-slot
    rows.  Scatter mode='drop' skips out-of-pool padding ids, which is
    how deduplicated pages (already resident at the destination) keep
    their payload rows from landing."""
    def scatter_blocks(caches, payload, blocks, blocks_swa, s):
        def put(path, leaf, pay):
            name = str(getattr(path[-1], "key", path[-1]))
            top = str(getattr(path[0], "key", path[0]))
            bdim = 1 if top == "stack" else 0
            if name in POOL_LEAVES:
                ids = blocks_swa if _pool_mixer(cfg, path) == SWA else blocks
                idx = (slice(None),) * bdim + (ids,)
                return leaf.at[idx].set(pay.astype(leaf.dtype), mode="drop")
            if leaf.ndim <= bdim:
                return leaf
            idx = (slice(None),) * bdim + (s,)
            return leaf.at[idx].set(pay.astype(leaf.dtype))
        return jax.tree_util.tree_map_with_path(put, caches, payload)
    return scatter_blocks


def _copy_block(caches, src, dst):
    """Copy-on-write: duplicate pool page ``src`` into ``dst`` across
    every paged cache leaf (keys, values, positions).  Used when a slot
    holding a shared prefix page is about to write into it — the write
    lands in the private copy, so shared pages are never mutated.
    ``src``/``dst`` are traced scalars: one compile covers every pair.
    (Prefix sharing is gated to models whose paged pools are all
    full-attention kind, so no per-kind routing is needed here.)"""
    def cp(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name not in POOL_LEAVES:
            return leaf
        top = str(getattr(path[0], "key", path[0]))
        bdim = 1 if top == "stack" else 0
        src_idx = (slice(None),) * bdim + (src,)
        dst_idx = (slice(None),) * bdim + (dst,)
        return leaf.at[dst_idx].set(leaf[src_idx])
    return jax.tree_util.tree_map_with_path(cp, caches)


class ServingEngine:
    """Fixed-slot continuous batching with **chunked prefill** and an
    optional **paged slot cache** (``paged=True`` — the default in the
    serving launchers/example; the class itself defaults to the dense
    rings, which are the bitwise reference semantics).

    Requests occupy slots; admission runs the new request's prompt through
    the shared slot cache in ``ceil(S_prompt / chunk)`` batched forward
    steps (other slots masked with position -1) instead of S single-token
    decode calls; every engine tick then decodes one token for all active
    slots in a single jitted step over the stacked slot state.

    **Paged mode** (``paged=True``): attention-family caches live in
    per-layer pools of ``num_blocks`` pages of ``page_size`` positions
    (default pool size = the dense cache's memory,
    ``slots * cache_len / page_size`` pages), addressed through a
    host-side ``(slots, ceil(cache_len / page_size))`` block table.  A
    request reserves ``ceil((prompt + max_new) / page_size)`` pages at
    admission — its OWN worst case, not the engine-wide ``cache_len`` —
    takes prompt pages immediately and one more page whenever decode
    crosses a page boundary, and frees everything when it finishes
    (freed blocks are scrubbed before recycling so no stale keys leak).
    When the pool cannot cover a reservation the queue backpressures
    (``stats["backpressure"]``) until a running request finishes; decode
    of admitted requests NEVER stalls on allocation (reservations make
    extends infallible).  Sliding-window layers draw ring pages from a
    separate exact-fit per-kind pool of ``slots * ceil(window /
    page_size)`` pages with its own block table (hybrids pass a
    ``{"attn", "swa"}`` table dict into the step); SSM/RWKV state stays
    per-slot (a recurrent carry has no sequence axis).  ``paged=False``
    selects the dense per-slot ring caches, which remain the bitwise
    reference semantics.

    **Prefix sharing** (``share_prefix=True``, the default, paged
    full-attention/MLA models): prompt-prefix pages are
    content-addressed in the allocator (chain digests + collision-proof
    check values); admission ATTACHES resident pages — table points at
    the existing block, refcount++, prefill chunks skipped, reservation
    reduced — and the first write into a shared page copies-on-write,
    so shared pages are never mutated and greedy decode stays
    bitwise-identical to the non-shared engine.  Pages physically free
    (and scrub) only at refcount zero.  See serve/README.md for the
    full page lifecycle; ``stats`` tracks ``shared_pages`` /
    ``shared_tokens`` / ``cow_copies``.  ``hold_pages`` > 0 (sharing
    engines only) additionally keeps up to that many refcount-zero
    registered pages resident in an LRU hold, so a popular prefix
    readmitted after a brief idle gap still attaches its pages — held
    pages are evicted first under ``pool_pressure`` and whenever a
    reservation needs the capacity.

    **Stateful failover** (paged engines): ``export_state(req)`` lifts a
    live request's complete decode state — generated tokens, pool page
    contents per cache kind, recurrent carries, the prompt's
    chain-digest trail, and a chained crc32 over the whole payload —
    and ``import_state`` attaches it mid-decode on another engine of the
    same model: verification first (a corrupted payload is rejected
    outright; wrong content never reaches a pool), then registry dedup
    (resident prefix pages attach by reference instead of crossing the
    wire), then one jitted scatter for the rest.  Greedy decode of a
    migrated request is bitwise-identical to never having moved.
    Crash recovery composes with it: a request carrying
    ``resume_tokens`` (the router's periodic snapshot) re-prefills
    prompt + resume as one extended prompt and resumes decode after the
    snapshot point instead of regenerating from scratch.

    **Kernel mode** (``use_kernel=True``, paged engines only): the S=1
    decode tick dispatches attention to the fused Pallas paged-decode
    kernel (``repro.kernels.paged_attention``) — the block table is
    scalar-prefetched and drives the page DMA, so the per-chunk
    gathered K/V copy of the scan path never lands in HBM.  Chunked
    prefill keeps the scan path (reference semantics) either way.

    Sampling is per-slot and in-jit: requests carry ``temperature`` /
    ``top_p`` / ``top_k`` / ``rep_penalty``; greedy (temperature 0,
    penalty 1) slots take the argmax path, bitwise-identical to the
    greedy-only engine, and sampled slots use a counter-based per-slot
    PRNG key threaded through the step as a ``(slots, 2)`` uint32 array
    — full logits never leave the device.  Repetition penalty reads a
    per-slot ``(slots, vocab)`` seen-token mask maintained in-jit from
    the step's own input tokens (prompt chunks and fed-back decode
    tokens), cleared host-side on admission.

    Per-slot positions keep the shared batched cache consistent; idle
    slots step with position -1, which every cache kind treats as a
    write/state no-op.  Cache buffers are donated to the jitted step on
    accelerator backends so the slot cache is updated in place.  Step
    inputs are assembled in numpy and shipped as one array per operand —
    never through O(slots) per-slot device ``.at[].set()`` dispatches.

    ``stats`` counts jitted forward calls (``prefill_calls`` /
    ``decode_calls``) — the admission cost of an S-token prompt is
    ``ceil(S/chunk)`` calls, which tests and benchmarks rely on — plus
    ``admitted`` and paged-pool ``backpressure`` events, and
    ``step_compiles``: the first dispatch of each step program and shape
    (``engine_step_prefill`` at every chunk width, ``engine_step_decode``),
    where JIT compiles or loads from the persistent cache.  Kernel
    engines count ``decode_pages_walked``, the table columns each decode
    tick's paged-decode kernel walks (every slot's live columns, the
    kernel's own ``live_columns``), against ``decode_pages_tabled``,
    slots x table width a tick.  Each
    ``Request`` carries host-clock stamps ``submitted_at`` /
    ``admitted_at`` / ``prefilled_at`` (queue wait and prefill apart).
    The serving loop runs inside ``engine.*`` profiler spans (``SPAN_*``
    above), so a trace splits the host's share of a tick by phase.
    """

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 cache_len: int = 512, chunk: int = 32, paged: bool = False,
                 page_size: int = 16, num_blocks: Optional[int] = None,
                 use_kernel: bool = False, share_prefix: bool = True,
                 hold_pages: int = 0, seed: int = 0):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        self.chunk = max(1, min(chunk, cache_len))
        self.paged = paged
        self.page_size = page_size
        if use_kernel and not paged:
            raise ValueError(
                "ServingEngine: use_kernel=True requires paged=True — the "
                "fused paged-decode kernel reads the block-table pool "
                "(dense rings keep the scan path)")
        self.use_kernel = use_kernel
        # full (non-windowed) attention layers must never wrap the ring:
        # every position of prompt + generation needs a live cache entry.
        # SWA rings may wrap freely — chunked prefill attends over
        # [pre-write ring ∥ chunk], so eviction never loses in-window keys.
        specs = tuple(cfg.prefix_layers) + tuple(cfg.period)
        self._has_attn = any(s.mixer == ATTN for s in specs)
        self._has_swa = any(s.mixer == SWA for s in specs)
        self._has_recurrent = any(s.mixer in (MAMBA, RWKV) for s in specs)
        self._bounded_ctx = self._has_attn
        # prefix sharing holds only where skipping prefill compute for a
        # page leaves NO other state stale: recurrent carries would still
        # need the skipped tokens, SWA ring pages get overwritten in
        # place, and MoE capacity truncation depends on the chunk shape
        # (so a shorter tail would not be bitwise-reproducing).
        self._can_share = (paged and share_prefix and self._has_attn
                           and not self._has_swa and not self._has_recurrent
                           and not cfg.n_experts)
        if paged:
            self.n_cols = max(1, -(-cache_len // page_size))
            self.num_blocks = (num_blocks if num_blocks is not None
                               else max(1, -(-slots * cache_len // page_size)))
            # the LRU hold only pays off where pages are content-
            # addressed (sharing engines); elsewhere it would just
            # delay scrubs
            self._alloc = BlockAllocator(
                self.num_blocks,
                hold_limit=hold_pages if self._can_share else 0)
            self._ring_blocks = (swa_ring_blocks(cfg.sliding_window,
                                                 page_size, self.n_cols)
                                 if self._has_swa else 0)
            self._table = np.full((slots, self.n_cols), -1, np.int32)
            self._slot_reserved = [0] * slots
            # per-cache-kind pools: SWA layers cycle over at most
            # ring_blocks pages per slot, so their pools get their own
            # exact-fit block-id space (slots * ring_blocks pages) instead
            # of full-attention-sized ones — an exact fit can never
            # backpressure, and hybrid models stop paying full-length
            # pool memory for windowed layers.
            self.num_blocks_swa = slots * self._ring_blocks
            if self._has_swa:
                self._alloc_swa = BlockAllocator(self.num_blocks_swa)
                self._table_swa = np.full((slots, self._ring_blocks), -1,
                                          np.int32)
                self._slot_reserved_swa = [0] * slots
            self.caches = init_cache(cfg, slots, cache_len, paged=True,
                                     page_size=page_size,
                                     num_blocks=self.num_blocks,
                                     num_blocks_swa=self.num_blocks_swa)
        else:
            self.num_blocks = 0
            self.num_blocks_swa = 0
            self._table = np.zeros((slots, 1), np.int32)   # dummy, unread
            self.caches = init_cache(cfg, slots, cache_len)
        # buffer donation is a no-op on CPU and would only warn
        donate = jax.default_backend() != "cpu"
        dn = dict(donate_argnums=(1, 2)) if donate else {}
        d0 = dict(donate_argnums=(0,)) if donate else {}
        # one step function, jitted under two names so a trace tells
        # chunked prefill and the decode tick apart
        step = make_engine_step(cfg, paged=paged, use_kernel=self.use_kernel)
        self._prefill_fn = jax.jit(_named(step, "engine_step_prefill"), **dn)
        self._decode_fn = jax.jit(_named(step, "engine_step_decode"), **dn)
        self._step_shapes: set = set()     # (decode?, width) dispatched
        self._reset_fn = jax.jit(partial(_clear_slot, skip_pools=paged), **d0)
        self._clear_blocks_fn = jax.jit(make_clear_blocks(cfg), **d0)
        self._copy_block_fn = jax.jit(_copy_block, **d0)
        # gather must NOT donate: the caches stay live after an export
        self._gather_blocks_fn = jax.jit(make_gather_blocks(cfg))
        self._scatter_blocks_fn = jax.jit(make_scatter_blocks(cfg), **d0)
        self._page_bytes_cache: Dict[str, int] = {}
        self._clear_seen_fn = jax.jit(
            lambda seen, s: seen.at[s].set(False), **d0)
        self._seen = jnp.zeros((slots, cfg.vocab_size), jnp.bool_)
        self.active: List[Optional[Request]] = [None] * slots
        self.positions = [0] * slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        # table columns of slot s currently mapped to a SHARED page (a
        # write into one copies first — see _ensure_blocks)
        self._slot_shared: List[set] = [set() for _ in range(slots)]
        self.stats = {"prefill_calls": 0, "decode_calls": 0, "admitted": 0,
                      "backpressure": 0, "shared_pages": 0,
                      "shared_tokens": 0, "cow_copies": 0, "preempted": 0,
                      "exported": 0, "imported": 0, "import_rejects": 0,
                      "imported_pages": 0, "deduped_pages": 0,
                      "resumed_tokens": 0, "step_compiles": 0,
                      "decode_pages_walked": 0, "decode_pages_tabled": 0}
        self._seed = seed
        self._step_seq = 0
        self._admit_seq = 0
        self._admitted_at = [0] * slots
        self._temp = np.zeros((slots,), np.float32)
        self._topp = np.ones((slots,), np.float32)
        self._topk = np.zeros((slots,), np.int32)
        self._reppen = np.ones((slots,), np.float32)

    # -- paged-pool bookkeeping (host side) -----------------------------

    def _blocks_for(self, logical_len: int) -> int:
        """Full-attention pool pages a request of total logical length
        ``logical_len`` can ever touch (its own ceil(len/page), bounded
        by the table width).  Zero for models without full-attention
        layers: SWA rings live in their own exact-fit pool that can never
        backpressure, recurrent state is per-slot."""
        if not self.paged or not self._has_attn:
            return 0
        return min(-(-logical_len // self.page_size), self.n_cols)

    def _blocks_for_swa(self, logical_len: int) -> int:
        """SWA ring pages the request will occupy (bounded by the ring)."""
        if not self.paged or not self._has_swa:
            return 0
        return min(-(-logical_len // self.page_size), self._ring_blocks)

    # -- content-addressed prefix pages ---------------------------------

    @staticmethod
    def _digest(payload) -> int:
        """Digest of one prefix page: payload chains the parent page's
        digest with this page's tokens, so equal digests (plus the
        allocator's check verification) mean equal FULL token prefixes —
        a page's KV content depends on everything before it, not just its
        own tokens.  Static so tests can monkeypatch it (e.g. to a
        constant, forcing collisions) per engine instance."""
        return hash(payload)

    def prefix_digests(self, prompt: List[int]) -> List[int]:
        """Chain digests of every FULL page of ``prompt`` — the
        content-address trail the fleet router uses for prefix-affinity
        placement (and that ``drain_requests`` pins to failover
        requeues)."""
        P = self.page_size
        out: List[int] = []
        prev = 0
        for i in range(len(prompt) // P):
            prev = self._digest((prev, tuple(prompt[i * P:(i + 1) * P])))
            out.append(prev)
        return out

    def _match_prefix(self, prompt: List[int]):
        """Resolve the longest resident shared prefix of ``prompt``.
        Returns (shared_tokens, full_hits, partial_hit):

        * full_hits — [(col, block)] for each leading FULL page resident
          in the pool (contiguous: a registrant registered all its full
          pages, so the first miss ends the chain);
        * partial_hit — (col, block, covered) when a registered
          PARTIAL page (another request's trailing prompt page) extends
          the match past the last full hit — attaching it shares the page
          first and copy-on-writes when the divergent tail is appended.
        """
        P = self.page_size
        S = len(prompt)
        hits: List[Tuple[int, int]] = []
        prev_d, prev_b = 0, -1
        m = 0
        for i in range(S // P):
            page = tuple(prompt[i * P:(i + 1) * P])
            d = self._digest((prev_d, page))
            b = self._alloc.lookup(d, (prev_b, page))
            if b is None:
                break
            hits.append((i, b))
            prev_d, prev_b = d, b
            m += 1
        partial = None
        best = 0
        for j in range(m * P + 1, min(S, (m + 1) * P) + 1):
            tail = tuple(prompt[m * P:j])
            d = self._digest((prev_d, tail, "partial"))
            b = self._alloc.lookup(d, (prev_b, tail, "partial"))
            if b is not None and j > best:
                partial, best = (m, b, j), j
        return (best if partial else m * P), hits, partial

    def shared_prefix_pages(self, prompt: List[int]) -> int:
        """How many of the request's prefix pages are resident RIGHT NOW
        (full-page hits + a trailing partial hit) — the router's
        prefix-affinity signal.  0 for engines that cannot share."""
        if not self._can_share:
            return 0
        _, hits, partial = self._match_prefix(prompt)
        return len(hits) + (1 if partial else 0)

    def prefill_calls_for(self, prompt: List[int]) -> int:
        """Jitted chunked-prefill calls admitting ``prompt`` would cost
        NOW: shared resident prefix pages are skipped, only the unshared
        tail (at least one token — the last prompt token must produce
        logits) runs through the step function."""
        S = len(prompt)
        if self._can_share:
            shared, _, _ = self._match_prefix(prompt)
            S -= min(shared, S - 1)
        return -(-S // self.chunk)

    def _register_prefix(self, s: int, prompt: List[int],
                         include_partial: bool = True) -> None:
        """Advertise slot ``s``'s freshly admitted prompt pages in the
        allocator's content registry: every FULL page under its chain
        digest, plus the trailing partial page (if any) so an
        exact-or-longer prompt can attach it and CoW on divergence.
        First registration wins; a collision (digest taken by different
        content) simply leaves our private page unadvertised.
        ``include_partial=False`` (migration import) skips the trailing
        page: a migrated slot's last prompt page already carries decode
        tokens past the prompt tail, so advertising it as exactly the
        tail would lie about its content."""
        P = self.page_size
        S = len(prompt)
        prev_d, prev_b = 0, -1
        for i in range(S // P):
            page = tuple(prompt[i * P:(i + 1) * P])
            d = self._digest((prev_d, page))
            b = int(self._table[s, i])
            self._alloc.register(d, (prev_b, page), b)
            canon = self._alloc.lookup(d, (prev_b, page))
            prev_d, prev_b = d, (canon if canon is not None else b)
        if S % P and include_partial:
            tail = tuple(prompt[(S // P) * P:])
            d = self._digest((prev_d, tail, "partial"))
            self._alloc.register(d, (prev_b, tail, "partial"),
                                 int(self._table[s, S // P]))

    def _cow(self, s: int, c: int) -> None:
        """Copy-on-write table column ``c`` of slot ``s``: take a private
        page against the slot's reservation, duplicate the shared page's
        contents on device, repoint the table, release the shared
        reference.  The shared page itself is never mutated."""
        old = int(self._table[s, c])
        assert self._alloc.refcount.get(old, 0) > 1, \
            "ServingEngine: CoW of an unshared page"
        new = self._alloc.alloc_one()
        self._slot_reserved[s] -= 1
        self.caches = self._copy_block_fn(
            self.caches, jnp.asarray(old, jnp.int32),
            jnp.asarray(new, jnp.int32))
        self._table[s, c] = new
        freed = self._alloc.free([old])
        assert not freed        # still referenced by the other holder(s)
        self._slot_shared[s].discard(c)
        self.stats["cow_copies"] += 1

    def _ensure_blocks(self, s: int, p_lo: int, p_hi: int) -> None:
        """Make the table columns that writes at positions [p_lo, p_hi]
        will touch safely writable: allocate unmapped columns; columns
        mapped to a SHARED page copy-on-write first (a divergent append
        must never mutate a page another slot still reads); an owned page
        still advertised in the content registry is deregistered before
        the append changes its content."""
        if not self.paged:
            return
        P = self.page_size
        if self._has_attn:
            for c in range(p_lo // P, p_hi // P + 1):
                b = int(self._table[s, c])
                if b < 0:
                    self._table[s, c] = self._alloc.alloc_one()
                    self._slot_reserved[s] -= 1
                elif self._can_share:
                    if self._alloc.refcount.get(b, 0) > 1:
                        self._cow(s, c)
                    else:
                        # sole holder: the append may proceed in place,
                        # but the page's advertised content is about to
                        # change — stop matching it
                        self._alloc.deregister(b)
                        self._slot_shared[s].discard(c)
        if self._has_swa:
            ring_p = self._ring_blocks * P
            if p_hi - p_lo + 1 >= ring_p:
                cols = range(self._ring_blocks)
            else:
                c0, c1 = (p_lo % ring_p) // P, (p_hi % ring_p) // P
                cols = (range(c0, c1 + 1) if c0 <= c1 else
                        list(range(c0, self._ring_blocks))
                        + list(range(c1 + 1)))
            for c in cols:
                if self._table_swa[s, c] < 0:
                    self._table_swa[s, c] = self._alloc_swa.alloc_one()
                    self._slot_reserved_swa[s] -= 1

    def _free_slot_blocks(self, s: int) -> None:
        """Drop a finished slot's page references and release unused
        reservations.  Only pages whose refcount reached ZERO return to
        the free list and get scrubbed (keys zeroed, positions -1);
        pages still shared by another slot stay live — scrubbing them
        would corrupt the other slot's cache."""
        if not self.paged:
            return
        blocks = [int(b) for b in self._table[s] if b >= 0]
        scrub: List[int] = []
        if blocks or self._slot_reserved[s]:
            scrub = self._alloc.free(blocks,
                                     unreserve=self._slot_reserved[s])
            self._slot_reserved[s] = 0
        scrub_swa: List[int] = []
        if self._has_swa:
            sblocks = [int(b) for b in self._table_swa[s] if b >= 0]
            if sblocks or self._slot_reserved_swa[s]:
                scrub_swa = self._alloc_swa.free(
                    sblocks, unreserve=self._slot_reserved_swa[s])
                self._slot_reserved_swa[s] = 0
            self._table_swa[s] = -1
        # pages the allocator evicted from its LRU hold (overflow) are
        # free-listed with stale content: scrub them with this batch
        self._scrub_blocks(scrub + self._alloc.take_scrub(), scrub_swa)
        self._table[s] = -1
        self._slot_shared[s].clear()

    def _scrub_blocks(self, scrub: List[int],
                      scrub_swa: List[int]) -> None:
        """Zero recycled pool pages (keys 0, positions -1) through the
        fixed-width jitted scrub, chunking longer lists so the jit still
        compiles once per engine."""
        wid = max(1, self._ring_blocks)
        while scrub or scrub_swa:
            part, scrub = scrub[:self.n_cols], scrub[self.n_cols:]
            part_swa, scrub_swa = scrub_swa[:wid], scrub_swa[wid:]
            pad = np.full((self.n_cols,), self.num_blocks, np.int32)
            pad[:len(part)] = part
            pad_swa = np.full((wid,), max(1, self.num_blocks_swa), np.int32)
            pad_swa[:len(part_swa)] = part_swa
            # numpy operands: jit converts once per call, nothing jnp
            # dispatches host-side in this loop
            self.caches = self._clear_blocks_fn(self.caches, pad, pad_swa)

    def _drain_scrub(self) -> None:
        """Scrub pages evicted from the allocator's LRU hold by a
        reservation or pool pressure (free-listed, stale content)."""
        if self.paged:
            self._scrub_blocks(self._alloc.take_scrub(), [])

    def _release_slot(self, s: int) -> None:
        """The ONE place a slot is vacated — shared by the finish path,
        ``drain_requests``, ``preempt_newest``, and ``export_state``:
        clear the slot, free + scrub its pages, and restore the greedy
        sampling defaults so an idle slot can't keep the
        all-greedy/no-penalty fast paths (lax.cond) switched off."""
        with TraceAnnotation(SPAN_RELEASE):
            self.active[s] = None
            self._free_slot_blocks(s)
            self._temp[s] = 0.0
            self._topp[s] = 1.0
            self._topk[s] = 0
            self._reppen[s] = 1.0

    def _table_arg(self):
        """The block-table step operand: one array for single-kind
        engines, per-cache-kind {"attn", "swa"} tables when the pools
        have split block-id spaces.  Copied (``jnp.array``): the host
        mutates these tables in place while a dispatched step may still
        be reading them, and ``jnp.asarray`` can alias host memory."""
        if self.paged and self._has_swa:
            return {"attn": jnp.array(self._table),
                    "swa": jnp.array(self._table_swa)}
        return jnp.array(self._table)

    # -- occupancy / fleet hooks (read by serve.router.FleetRouter) ------

    @property
    def n_active(self) -> int:
        return sum(1 for r in self.active if r is not None)

    def admitted_requests(self) -> List[Request]:
        """Requests currently holding a slot, in ADMISSION order (slot
        index lies once slots recycle) — the order the router walks for
        migration, snapshots, and rebalance victim choice."""
        live = [s for s in range(self.slots) if self.active[s] is not None]
        live.sort(key=lambda s: self._admitted_at[s])
        return [self.active[s] for s in live]

    @property
    def pending_tokens(self) -> int:
        """Tokens this engine still has to process: queued requests cost
        their full prompt + max_new (prefill AND decode are ahead of
        them); admitted requests have paid prefill, so only their
        remaining decode tokens count.  This is the engine's current load
        term in the router's Eq. 2-style completion-time estimate."""
        tok = sum(len(r.prompt) + r.max_new for r in self.queue)
        tok += sum(r.max_new - len(r.generated)
                   for r in self.active if r is not None)
        return tok

    @property
    def pending_prefill_calls(self) -> int:
        """Jitted chunked-prefill calls still ahead of this engine (its
        own queue, shared-prefix discounts applied) — the per-call
        dispatch overhead term of the router's admission-aware ECT."""
        return sum(self.prefill_calls_for(r.prompt) for r in self.queue)

    @property
    def free_pages(self) -> int:
        """Pool pages not yet committed: free minus reservations minus
        the worst-case demand of requests already sitting in this
        engine's own queue (they WILL reserve at admission).  Dense
        engines are page-unconstrained and report a sentinel large
        enough that page checks never bind."""
        if not self.paged:
            return 1 << 30
        queued = sum(self._blocks_for(len(r.prompt) + r.max_new)
                     for r in self.queue)
        return (self._alloc.n_free + self._alloc.n_held
                - self._alloc.reserved - self._alloc.withheld - queued)

    @property
    def occupancy(self) -> dict:
        """One host-side snapshot of engine load for placement decisions
        and monitoring — no device sync."""
        return {
            "active": self.n_active,
            "queued": len(self.queue),
            "free_slots": self.slots - self.n_active,
            "pending_tokens": self.pending_tokens,
            "free_pages": self.free_pages if self.paged else None,
        }

    def can_serve(self, prompt: List[int], max_new: int) -> bool:
        """Could this engine EVER run such a request (regardless of its
        current load)?  Mirrors ``submit``'s validation without raising,
        plus a vocab bound so a heterogeneous fleet never routes token
        ids a replica's model cannot embed."""
        if not prompt or max(prompt) >= self.cfg.vocab_size:
            return False
        if self._bounded_ctx and len(prompt) + max_new > self.cache_len:
            return False
        if self.paged and self._blocks_for(len(prompt) + max_new) \
                > self.num_blocks:
            return False
        return True

    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pool pages a request would reserve at admission."""
        return self._blocks_for(prompt_len + max_new)

    # -- stateful failover: verified page migration ----------------------

    def _kind_page_bytes(self, swa: bool) -> int:
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.caches)[0]:
            name = str(getattr(path[-1], "key", path[-1]))
            if name not in POOL_LEAVES:
                continue
            if (_pool_mixer(self.cfg, path) == SWA) != swa:
                continue
            top = str(getattr(path[0], "key", path[0]))
            bdim = 1 if top == "stack" else 0
            total += leaf.nbytes // leaf.shape[bdim]
        return total

    @property
    def page_bytes(self) -> int:
        """Bytes one full-attention pool page occupies across every
        paged cache leaf — the unit of the router's migrate-vs-reprefill
        byte estimate."""
        if not self.paged or not self._has_attn:
            return 0
        if "attn" not in self._page_bytes_cache:
            self._page_bytes_cache["attn"] = self._kind_page_bytes(False)
        return self._page_bytes_cache["attn"]

    @property
    def page_bytes_swa(self) -> int:
        """Bytes one sliding-window ring page occupies across leaves."""
        if not self.paged or not self._has_swa:
            return 0
        if "swa" not in self._page_bytes_cache:
            self._page_bytes_cache["swa"] = self._kind_page_bytes(True)
        return self._page_bytes_cache["swa"]

    def registry_digests(self) -> frozenset:
        """Digests currently resident in the content registry (LRU-held
        pages included) — the per-replica view the router gossips on
        heartbeats, so placement affinity and migrate-dedup byte
        estimates see pages registered AFTER placement decisions."""
        if not self._can_share:
            return frozenset()
        return frozenset(self._alloc._by_digest)

    def migration_fingerprint(self) -> tuple:
        """Compatibility key for stateful migration.  Page payloads are
        raw device floats, so source and destination must run the SAME
        weights (object identity — fleet replicas share one param
        pytree), the same architecture, and the same page geometry;
        anything else falls back to re-prefill."""
        return (id(self.params), self.cfg, self.paged, self.page_size,
                self.cache_len)

    def export_state(self, req: Request) -> Optional[RequestState]:
        """Serialize a LIVE request's complete decode state for verified
        migration: generated + pending tokens (they ride on the Request),
        every pool page its slot maps (per cache kind), per-slot
        recurrent carries, the prompt's chain-digest trail (the importer
        dedups against its own content registry), and a chained crc32
        over the whole payload.  The slot is released — after a
        successful ``import_state`` elsewhere the request continues
        mid-decode; if the import fails the caller falls back to
        requeue-from-prompt (the state object holds everything needed
        either way).  Returns None for dense engines or a request not
        currently admitted here."""
        if not self.paged:
            return None
        s = next((i for i in range(self.slots) if self.active[i] is req),
                 None)
        if s is None:
            return None
        cols = [c for c in range(self.n_cols) if self._table[s, c] >= 0]
        blocks = [int(self._table[s, c]) for c in cols]
        cols_swa, blocks_swa = [], []
        if self._has_swa:
            cols_swa = [c for c in range(self._ring_blocks)
                        if self._table_swa[s, c] >= 0]
            blocks_swa = [int(self._table_swa[s, c]) for c in cols_swa]
        pad = np.full((self.n_cols,), self.num_blocks, np.int32)
        pad[:len(blocks)] = blocks
        wid = max(1, self._ring_blocks)
        pad_swa = np.full((wid,), max(1, self.num_blocks_swa), np.int32)
        pad_swa[:len(blocks_swa)] = blocks_swa
        payload = self._gather_blocks_fn(self.caches, jnp.asarray(pad),
                                         jnp.asarray(pad_swa),
                                         jnp.asarray(s, jnp.int32))
        pool: Dict[str, np.ndarray] = {}
        slot_state: Dict[str, np.ndarray] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(payload)[0]:
            key = _path_key(path)
            name = str(getattr(path[-1], "key", path[-1]))
            bdim = 1 if key.startswith("stack") else 0
            arr = np.asarray(leaf)
            if name in POOL_LEAVES:
                k = (len(cols_swa)
                     if _pool_mixer(self.cfg, path) == SWA else len(cols))
                pool[key] = arr[(slice(None),) * bdim + (slice(0, k),)]
            else:
                slot_state[key] = arr
        state = RequestState(
            req=req, position=self.positions[s],
            fingerprint=self.migration_fingerprint(),
            cols=cols, cols_swa=cols_swa, pool=pool, slot_state=slot_state,
            digests=self.prefix_digests(req.prompt), checksum=0,
            payload_bytes=(sum(a.nbytes for a in pool.values())
                           + sum(a.nbytes for a in slot_state.values())))
        state.checksum = state_checksum(state)
        req.prefix_digests = state.digests
        self._release_slot(s)
        self.stats["exported"] += 1
        return state

    def import_state(self, state: RequestState) -> bool:
        """Attach a migrated request mid-decode.  Verification comes
        FIRST: the payload's checksum chain is recomputed and any
        mismatch rejects the whole transfer before a byte reaches the
        pool — unverified content is never served.  Then the prompt's
        full prefix pages are deduplicated against the local content
        registry (resident pages attach by reference; their bitwise
        equality follows from the chain-digest + check-value induction),
        the rest land in freshly allocated pages via one jitted scatter,
        and the slot's positions / sampling params / seen mask are
        restored.  Returns False — engine state untouched — when no slot
        or pages are free, the fingerprint mismatches, or verification
        fails."""
        req = state.req
        if not self.paged \
                or state.fingerprint != self.migration_fingerprint():
            return False
        s = next((i for i in range(self.slots) if self.active[i] is None),
                 None)
        if s is None:
            return False
        if state_checksum(state) != state.checksum:
            self.stats["import_rejects"] += 1
            return False
        S = len(req.prompt)
        hits: List[Tuple[int, int]] = []
        if self._can_share:
            _, hits, _ = self._match_prefix(req.prompt)
        resident = {c: b for (c, b) in hits if c in set(state.cols)}
        need = self._blocks_for(S + req.max_new) - len(resident)
        if not self._alloc.reserve(need):
            return False
        self._slot_reserved[s] = need
        need_swa = self._blocks_for_swa(S + req.max_new)
        if need_swa:
            ok = self._alloc_swa.reserve(need_swa)
            assert ok   # exact-fit pool: slots * ring_blocks
            self._slot_reserved_swa[s] = need_swa
        self._drain_scrub()
        self.caches = self._reset_fn(self.caches, s)
        self._seen = self._clear_seen_fn(self._seen, s)
        # map table columns: attach deduped pages by reference; fresh
        # pages for the rest.  ``ids[j]`` pairs with payload row j —
        # deduped columns keep the out-of-pool padding id so the
        # scatter (mode='drop') skips their rows entirely.
        ids = np.full((self.n_cols,), self.num_blocks, np.int32)
        written = 0
        for j, c in enumerate(state.cols):
            if c in resident:
                b = resident[c]
                self._alloc.share(b)
                self._table[s, c] = b
                self._slot_shared[s].add(c)
            else:
                b = self._alloc.alloc_one()
                self._slot_reserved[s] -= 1
                self._table[s, c] = b
                ids[j] = b
                written += 1
        wid = max(1, self._ring_blocks)
        ids_swa = np.full((wid,), max(1, self.num_blocks_swa), np.int32)
        for j, c in enumerate(state.cols_swa):
            b = self._alloc_swa.alloc_one()
            self._slot_reserved_swa[s] -= 1
            self._table_swa[s, c] = b
            ids_swa[j] = b

        def build(path, leaf):
            key = _path_key(path)
            name = str(getattr(path[-1], "key", path[-1]))
            top = str(getattr(path[0], "key", path[0]))
            bdim = 1 if top == "stack" else 0
            if name in POOL_LEAVES:
                swa = _pool_mixer(self.cfg, path) == SWA
                rows = wid if swa else self.n_cols
                src = state.pool[key]
                shape = list(leaf.shape)
                shape[bdim] = rows
                out = np.zeros(shape, src.dtype)
                k = src.shape[bdim]
                out[(slice(None),) * bdim + (slice(0, k),)] = src
                return jnp.asarray(out)
            if leaf.ndim <= bdim:
                return leaf
            return jnp.asarray(state.slot_state[key])

        payload = jax.tree_util.tree_map_with_path(build, self.caches)
        self.caches = self._scatter_blocks_fn(
            self.caches, payload, jnp.asarray(ids), jnp.asarray(ids_swa),
            jnp.asarray(s, jnp.int32))
        self.active[s] = req
        self._admit_seq += 1
        self._admitted_at[s] = self._admit_seq
        self.positions[s] = state.position
        self._temp[s] = req.temperature
        self._topp[s] = req.top_p
        self._topk[s] = req.top_k
        self._reppen[s] = req.rep_penalty
        if req.rep_penalty != 1.0:
            # the in-jit seen mask is maintained from step inputs, which
            # this engine never saw: rebuild it from prompt + generated
            row = np.zeros((self.cfg.vocab_size,), bool)
            row[np.asarray(req.prompt + req.generated, np.int64)] = True
            self._seen = self._seen.at[s].set(jnp.asarray(row))
        if self._can_share:
            self._register_prefix(s, req.prompt, include_partial=False)
        self.stats["imported"] += 1
        self.stats["imported_pages"] += written + len(state.cols_swa)
        self.stats["deduped_pages"] += len(resident)
        return True

    def drain_requests(self) -> List[Request]:
        """Harvest every live request in SUBMISSION order — admitted
        slots by admission sequence (slot index lies once slots have
        been recycled), then the engine queue, which is FIFO and
        strictly younger than anything admitted — for re-queueing on
        another replica.  Cache/pages are assumed lost with the replica,
        so each request is reset to re-prefill from its prompt:
        generated tokens are discarded, never silently kept or dropped.
        The engine itself is left empty (slots idle, pages freed,
        sampling params back to greedy defaults).  Each request keeps its
        prefix-page digest trail (``prefix_digests``) so the router's
        failover requeue can still steer it toward a replica already
        holding (or about to admit) the same shared prefix."""
        out: List[Request] = []
        admitted = sorted((s for s in range(self.slots)
                           if self.active[s] is not None),
                          key=lambda s: self._admitted_at[s])
        for s in admitted:
            req = self.active[s]
            self._release_slot(s)
            out.append(req)
        out.extend(self.queue)
        self.queue = []
        for req in out:
            req.generated = []
            req.pending = -1
            req.done = False
            req.admitted_at = req.prefilled_at = None
            req.prefix_digests = self.prefix_digests(req.prompt)
        return out

    def preempt_newest(self) -> Optional[Request]:
        """Evict the YOUNGEST live request — the engine queue's tail if
        any (it holds no pages yet), else the most recently admitted
        slot — resetting it to re-prefill from its prompt exactly like
        ``drain_requests`` (generated tokens discarded, pages freed and
        scrubbed, prefix digests stamped so the victim re-shares its
        prefix wherever it lands).  Returns the victim, or None when the
        engine is idle.  The router uses this to satisfy a held
        head-of-line request's worst-case reservation: preempting newest
        keeps the loss (tokens already decoded) minimal and FIFO fairness
        intact — the head is by construction older than anything
        admitted after it."""
        if self.queue:
            req = self.queue.pop()
        else:
            live = [s for s in range(self.slots) if self.active[s] is not None]
            if not live:
                return None
            s = max(live, key=lambda s: self._admitted_at[s])
            req = self.active[s]
            self._release_slot(s)
        req.generated = []
        req.pending = -1
        req.done = False
        req.admitted_at = req.prefilled_at = None
        req.prefix_digests = self.prefix_digests(req.prompt)
        self.stats["preempted"] += 1
        return req

    def set_pool_pressure(self, pages: int) -> None:
        """Fault injection (``faults.pool_pressure``): withhold ``pages``
        full-attention pool pages from NEW admissions, as if a co-tenant
        grabbed the memory.  Reservation-backed decode of admitted
        requests is untouched — pressure can only backpressure the
        queue, never crash in-flight work.  ``0`` restores the full
        pool.  No-op for dense engines and for models without
        full-attention paged pools (the SWA ring pool is exact-fit by
        construction and must never be squeezed).  Pages idling in the
        LRU hold are surrendered first — the hold is a cache, not a
        commitment."""
        if not self.paged or not self._has_attn:
            return
        pages = max(0, int(pages))
        if pages:
            self._alloc.evict_held(pages)
            self._drain_scrub()
        self._alloc.withheld = pages

    # -- request intake --------------------------------------------------

    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(
                f"ServingEngine: request {req.req_id} has an empty prompt — "
                f"at least one prompt token is required to seed decoding")
        if self._bounded_ctx and len(req.prompt) + req.max_new > self.cache_len:
            # only full-attention caches bound the context: SWA rings wrap
            # exactly under the window mask, recurrent state has no length
            raise ValueError(
                f"ServingEngine: request {req.req_id} needs "
                f"{len(req.prompt)} prompt + {req.max_new} new tokens but "
                f"cache_len={self.cache_len}; full-attention caches must "
                f"not wrap (raise cache_len or lower max_new)")
        if self.paged:
            need = self._blocks_for(len(req.prompt) + req.max_new)
            if need > self.num_blocks:
                raise ValueError(
                    f"ServingEngine: request {req.req_id} needs {need} cache "
                    f"pages but the pool has only {self.num_blocks} — it "
                    f"could never be admitted (raise num_blocks)")
        req.submitted_at = _stamp()
        self.queue.append(req)

    def warmup(self) -> None:
        """Compile the two engine shapes ahead of serving: the chunked-
        prefill step (slots, chunk) and the decode tick (slots, 1).  Runs
        them with every position masked (-1), which is a cache no-op, so
        warmup never perturbs engine state."""
        for C, decode in ((self.chunk, False), (1, True)):
            toks = np.zeros((self.slots, C), np.int32)
            pos = np.full((self.slots, C), -1, np.int32)
            _, self.caches = self._call_step(toks, pos, decode)
        # compile the reset against a FREE slot only (resetting it is
        # harmless — admission resets again); never touch a live one
        free = [s for s in range(self.slots) if self.active[s] is None]
        if free:
            self.caches = self._reset_fn(self.caches, free[-1])
        if self.paged:
            # all-padding block vectors: scrub is a compiled no-op
            pad = np.full((self.n_cols,), self.num_blocks, np.int32)
            pad_swa = np.full((max(1, self._ring_blocks),),
                              max(1, self.num_blocks_swa), np.int32)
            self.caches = self._clear_blocks_fn(self.caches,
                                                jnp.asarray(pad),
                                                jnp.asarray(pad_swa))
        jax.block_until_ready(self.caches)

    # -- the serving loop ------------------------------------------------

    def _call_step(self, toks: np.ndarray, pos: np.ndarray, decode: bool):
        """One jitted engine step (``engine_step_decode`` for the decode
        tick, ``engine_step_prefill`` for a prompt chunk); host-side
        operands (numpy) convert to device arrays ONCE here, as copies —
        the per-slot sampling vectors are mutated in place by admission
        and release while the step runs asynchronously.  The per-slot
        PRNG keys are counter-based (slot seed, step counter), so
        sampling streams are deterministic and never leave host control.
        The first dispatch of each program and width runs inside an
        ``engine.compile`` span and counts in ``stats["step_compiles"]``."""
        keys = np.empty((self.slots, 2), np.uint32)
        keys[:, 0] = np.arange(self._seed, self._seed + self.slots,
                               dtype=np.uint32)
        keys[:, 1] = np.uint32(self._step_seq)
        self._step_seq += 1
        args = (jnp.array(toks), jnp.array(pos), self._table_arg(),
                jnp.array(keys), jnp.array(self._temp), jnp.array(self._topp),
                jnp.array(self._topk), jnp.array(self._reppen))
        shape = (decode, toks.shape[1])
        first = shape not in self._step_shapes
        if first:
            self._step_shapes.add(shape)
            self.stats["step_compiles"] += 1
        with TraceAnnotation(SPAN_COMPILE) if first else nullcontext():
            if decode:
                nxt, self.caches, self._seen = self._decode_fn(
                    self.params, self.caches, self._seen, *args)
            else:
                nxt, self.caches, self._seen = self._prefill_fn(
                    self.params, self.caches, self._seen, *args)
        return nxt, self.caches

    def _admit(self) -> None:
        """Chunked-prefill admission: reserve the request's worst-case
        pages (paged mode; insufficient pool = backpressure, the queue
        stays FIFO), reset the slot's per-slot state, then walk the
        prompt through the cache ``chunk`` tokens per jitted step (other
        slots masked with position -1).  The final chunk may be shorter —
        it compiles once per distinct remainder length.

        **Prefix-sharing fast path** (content-addressed pools): prompt
        pages already resident — registered by an earlier admission whose
        prompt shares this one's prefix — are ATTACHED (table points at
        the existing page, refcount++) instead of reserved and
        re-prefilled; only the unshared tail runs through the jitted
        steps.  At least the LAST prompt token always recomputes (its
        logits seed decoding), so a fully resident prompt still costs one
        short chunk — and copy-on-writes the page it lands in.  The
        skipped pages are also excluded from the up-front reservation,
        which is what raises peak concurrency at equal pool memory."""
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                with TraceAnnotation(SPAN_ADMIT):
                    if not self._admit_into(s):
                        break          # FIFO: later requests wait too

    def _admit_into(self, s: int) -> bool:
        """Admit the queue's head into the free slot ``s``, through to its
        first token on the host; False where the pool backpressures."""
        req = self.queue[0]
        # snapshot restore: prefill prompt + resume tokens as ONE
        # extended prompt (chunked prefill writes KV bitwise-equal
        # to the decode that first produced it), then restore
        # ``generated`` below — only tokens decoded after the
        # router's snapshot are re-decoded
        resume = list(req.resume_tokens or ())
        full = req.prompt + resume
        S = len(full)
        # total logical length stays prompt + max_new: resumed
        # tokens count against the generation budget
        total = S + req.max_new - len(resume)
        shared_tok, hits, partial = (
            self._match_prefix(full) if self._can_share
            else (0, [], None))
        start = min(shared_tok, S - 1)
        if self.paged:
            # reserve only unshared pages — but a shared page the
            # tail will write into (the partial hit, or the last
            # full hit when the whole prompt matched) still needs
            # a private page for its copy-on-write
            untouched = sum(1 for (i, _) in hits
                            if (i + 1) * self.page_size <= start)
            need = self._blocks_for(total) - untouched
            if not self._alloc.reserve(need):
                self.stats["backpressure"] += 1
                return False
            self._slot_reserved[s] = need
            self._drain_scrub()
            need_swa = self._blocks_for_swa(total)
            if need_swa:
                ok = self._alloc_swa.reserve(need_swa)
                assert ok   # exact-fit pool: slots * ring_blocks
                self._slot_reserved_swa[s] = need_swa
        self.queue.pop(0)
        req.admitted_at = _stamp()
        self.active[s] = req
        self._admit_seq += 1
        self._admitted_at[s] = self._admit_seq
        self.caches = self._reset_fn(self.caches, s)
        self._seen = self._clear_seen_fn(self._seen, s)
        self._temp[s] = req.temperature
        self._topp[s] = req.top_p
        self._topk[s] = req.top_k
        self._reppen[s] = req.rep_penalty
        for (c, b) in hits:
            self._table[s, c] = b
            self._alloc.share(b)
            self._slot_shared[s].add(c)
        if partial is not None:
            c, b, _ = partial
            self._table[s, c] = b
            self._alloc.share(b)
            self._slot_shared[s].add(c)
        self.stats["shared_pages"] += \
            len(hits) + (1 if partial else 0)
        self.stats["shared_tokens"] += start
        prompt = np.asarray(full, np.int32)
        nxt = None
        for c0 in range(start, S, self.chunk):
            with TraceAnnotation(SPAN_PREFILL):
                piece = prompt[c0:c0 + self.chunk]
                C = len(piece)
                self._ensure_blocks(s, c0, c0 + C - 1)
                toks = np.zeros((self.slots, C), np.int32)
                toks[s] = piece
                pos = np.full((self.slots, C), -1, np.int32)
                pos[s] = np.arange(c0, c0 + C, dtype=np.int32)
                nxt, self.caches = self._call_step(toks, pos, False)
                self.stats["prefill_calls"] += 1
        if self._can_share:
            self._register_prefix(s, full)
        self.positions[s] = S
        # the extended prompt's last logits ARE the decode logits
        # at that position (chunked-prefill parity), so greedy
        # resume continues exactly where the snapshot left off
        with TraceAnnotation(SPAN_READBACK):
            req.pending = int(nxt[s, -1])
        req.prefilled_at = _stamp()
        if resume:
            req.generated = resume
            req.resume_tokens = None
            self.stats["resumed_tokens"] += len(resume)
        self.stats["admitted"] += 1
        return True

    def tick(self) -> int:
        """One engine iteration: feed each active slot's pending token,
        emit it, and compute the next — a single jitted decode step over
        all slots.  Returns #active slots."""
        with TraceAnnotation(SPAN_TICK):
            self._admit()
            act = [s for s in range(self.slots)
                   if self.active[s] is not None]
            if not act:
                return 0
            with TraceAnnotation(SPAN_DECODE):
                toks = np.zeros((self.slots, 1), np.int32)
                pos = np.full((self.slots, 1), -1, np.int32)
                for s in act:
                    self._ensure_blocks(s, self.positions[s],
                                        self.positions[s])
                    toks[s, 0] = self.active[s].pending
                    pos[s, 0] = self.positions[s]
                nxt, self.caches = self._call_step(toks, pos, True)
                self.stats["decode_calls"] += 1
                if self.use_kernel:
                    self.stats["decode_pages_walked"] += int(live_columns(
                        pos, self.page_size, self.n_cols).sum())
                    self.stats["decode_pages_tabled"] += \
                        self.slots * self.n_cols
            # every token first, then the releases: the readback span
            # holds only the host's wait for the step and its copies
            with TraceAnnotation(SPAN_READBACK):
                for s in act:
                    req = self.active[s]
                    req.generated.append(req.pending)
                    req.pending = int(nxt[s, 0])
                    self.positions[s] += 1
            for s in act:
                req = self.active[s]
                if len(req.generated) >= req.max_new:
                    req.done = True
                    self.finished.append(req)
                    self._release_slot(s)
            return len(act)

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.tick() and not self.queue:
                break
        return self.finished
