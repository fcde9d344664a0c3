"""Execution plane (paper §3.1 P3–P6, §3.6): interprets IR-plane sub-DAGs
with JAX as the ML engine.

* ``OP_IMPLS``: the op vocabulary — each op id maps to (init, apply).
  New ops plug in through ``register_op`` (P5/P6 task universality).
* ``SubDagExecutor``: one compnode's runtime.  FP runs the sub-DAG and
  captures a ``jax.vjp`` pullback; BP consumes cotangents arriving from
  user compnodes and emits cotangents to producer compnodes (the paper's
  BP-task message passing, reversed FP edges); Update applies a local
  optimizer to the parametric ops it hosts.
* ``LocalCluster``: wires executors together through a ``Bus`` that
  accounts every transferred byte (validating the DAG cut-size model).
* ``spmd_pipeline``: the TPU-native production mapping — the same staged
  execution as a ``shard_map`` collective_permute pipeline.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.dag import DAG, LOSS, PARAMETRIC, PLACEHOLDER
from repro.models import ssm
from repro.models.layers import (attn_apply, attn_init, embed_init, ffn_apply,
                                 ffn_init, moe_apply, moe_init, rmsnorm,
                                 rmsnorm_init)

Array = jax.Array


# ---------------------------------------------------------------------------
# Op vocabulary (the IR-plane/execution-plane contract)
# ---------------------------------------------------------------------------

def _res_block(mixer_apply):
    def apply(params, cfg, x, positions):
        h = rmsnorm(x, params["norm"], cfg.norm_eps)
        h = mixer_apply(params, cfg, h, positions)
        return x + h
    return apply


def _attn(params, cfg, h, positions, window=0):
    out, _ = attn_apply(params["op"], h, cfg, positions=positions, window=window)
    return out


def _swa(params, cfg, h, positions):
    return _attn(params, cfg, h, positions, window=cfg.sliding_window)


def _mamba(params, cfg, h, positions):
    out, _ = ssm.mamba_apply(params["op"], h, cfg)
    return out


def _rwkv(params, cfg, h, positions):
    out, _ = ssm.rwkv_apply(params["op"], h, cfg)
    return out


def _dense_ffn(params, cfg, h, positions):
    return ffn_apply(params["op"], h)


def _moe_ffn(params, cfg, h, positions):
    out, _aux = moe_apply(params["op"], h, cfg)   # aux folded by the driver
    return out


_MIXER_INITS = {
    "attn_block": attn_init, "swa_block": attn_init,
    "mamba_block": ssm.mamba_init, "rwkv_block": ssm.rwkv_init,
    "dense_ffn": ffn_init, "moe_ffn": moe_init,
}

OP_IMPLS: Dict[str, dict] = {}


def register_op(op_id: str, init: Callable, apply: Callable) -> None:
    OP_IMPLS[op_id] = {"init": init, "apply": apply}


def _block_init(mixer_init):
    def init(key, cfg):
        return {"norm": rmsnorm_init(cfg.d_model), "op": mixer_init(key, cfg)}
    return init


for _op, _fn in [("attn_block", _attn), ("swa_block", _swa),
                 ("mamba_block", _mamba), ("rwkv_block", _rwkv),
                 ("dense_ffn", _dense_ffn), ("moe_ffn", _moe_ffn)]:
    register_op(_op, _block_init(_MIXER_INITS[_op]), _res_block(_fn))

register_op(
    "embedding",
    lambda key, cfg: {"embed": embed_init(key, cfg.vocab_size, cfg.d_model)},
    lambda p, cfg, tokens, positions: jnp.take(p["embed"], tokens, axis=0))

register_op(
    "unembed",
    lambda key, cfg: {"norm": rmsnorm_init(cfg.d_model),
                      "head": embed_init(key, cfg.d_model, cfg.vocab_size)},
    lambda p, cfg, x, positions: (rmsnorm(x, p["norm"], cfg.norm_eps)
                                  @ p["head"].astype(x.dtype)).astype(jnp.float32))


def _xent(p, cfg, logits, labels, positions):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


register_op("cross_entropy", lambda key, cfg: {}, _xent)


# ---------------------------------------------------------------------------
# Message bus with byte accounting (the decentralized communicator stand-in)
# ---------------------------------------------------------------------------

class Bus:
    def __init__(self):
        self.mailboxes: Dict[int, Dict[str, Array]] = {}
        self.bytes_sent: Dict[Tuple[int, int], float] = {}

    def send(self, src: int, dst: int, key: str, value: Array) -> None:
        self.mailboxes.setdefault(dst, {})[key] = value
        nbytes = math.prod(value.shape) * value.dtype.itemsize
        self.bytes_sent[(src, dst)] = self.bytes_sent.get((src, dst), 0.0) + nbytes

    def recv(self, dst: int, key: str) -> Array:
        box = self.mailboxes.get(dst)
        if not box or key not in box:
            raise KeyError(
                f"Bus.recv: no message {key!r} in mailbox of dst={dst} "
                f"(available keys: {sorted(box) if box else []}) — "
                f"a DAG cut is mis-scheduled or the producer never sent")
        return box.pop(key)

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_sent.values())


# ---------------------------------------------------------------------------
# Sub-DAG executor: FP / BP / Update tasks (paper §3.6)
# ---------------------------------------------------------------------------

class SubDagExecutor:
    def __init__(self, compnode_id: int, dag: DAG, op_names: Sequence[str],
                 cfg, key):
        self.compnode_id = compnode_id
        self.dag = dag
        self.op_names = list(op_names)
        self.cfg = cfg
        self.params: Dict[str, dict] = {}
        keys = jax.random.split(key, max(1, len(self.op_names)))
        for k, name in zip(keys, self.op_names):
            node = dag[name]
            if node.op_type in (PARAMETRIC, LOSS) or node.op in OP_IMPLS:
                if node.op in OP_IMPLS:
                    self.params[name] = OP_IMPLS[node.op]["init"](k, cfg)
        self._pullback = None
        self._out_keys: List[str] = []

    # -- the pure function of (params, external inputs) -> sent outputs ----
    def _fp_fn(self, params, ext_inputs: Dict[str, Array],
               placeholders: Dict[str, Array], positions,
               want: Optional[str] = None):
        values: Dict[str, Array] = dict(ext_inputs)
        values.update(placeholders)
        loss = None
        for name in self.op_names:
            node = self.dag[name]
            if node.op_type == PLACEHOLDER:
                continue
            args = [values[a] for a in node.args]
            out = OP_IMPLS[node.op]["apply"](params.get(name, {}), self.cfg,
                                             *args, positions)
            values[name] = out
            if node.op_type == LOSS:
                loss = out
        outs = {k: values[k] for k in self._out_keys}
        wanted = values.get(want) if want else None
        return outs, (loss, wanted)

    def fp(self, bus: Bus, assignment: Dict[str, int],
           placeholders: Dict[str, Array], positions,
           want: Optional[str] = None) -> Tuple[Optional[Array], Optional[Array]]:
        """FP task: pull outer-required data from the bus, execute, push
        outwards data.  Captures the vjp pullback for the BP task.
        Returns (loss, wanted-op value)."""
        my = self.compnode_id
        mine = set(self.op_names)
        ext_needed = sorted({a for n in self.op_names for a in self.dag[n].args
                             if a not in mine})
        ext_inputs = {a: bus.recv(my, f"fp/{a}") for a in ext_needed}
        self._out_keys = sorted({
            n for n in self.op_names
            if any(assignment[u] != my for u in self.dag.users(n))})

        fn = lambda p, e: self._fp_fn(p, e, placeholders, positions, want)
        (outs, (loss, wanted)), self._pullback = jax.vjp(
            fn, self.params, ext_inputs, has_aux=False)
        self._ext_keys = ext_needed
        for name, val in outs.items():
            for u in self.dag.users(name):
                dst = assignment[u]
                if dst != my:
                    bus.send(my, dst, f"fp/{name}", val)
        return loss, wanted

    def bp(self, bus: Bus, assignment: Dict[str, int],
           loss_cotangent: float = 1.0) -> Dict[str, dict]:
        """BP task: assemble cotangents for every sent output (from user
        compnodes), pull back, send cotangents for external inputs to their
        producers.  Returns parameter gradients for hosted ops."""
        my = self.compnode_id
        out_ct = {}
        for name in self._out_keys:
            ct = None
            remote_peers = {assignment[u] for u in self.dag.users(name)} - {my}
            for peer in sorted(remote_peers):
                piece = bus.recv(my, f"bp/{name}/{peer}")
                ct = piece if ct is None else ct + piece
            out_ct[name] = ct
        has_loss = any(self.dag[n].op_type == LOSS for n in self.op_names)
        loss_ct = jnp.asarray(loss_cotangent, jnp.float32) if has_loss else None
        param_grads, ext_ct = self._pullback((out_ct, (loss_ct, None)))
        for name, ct in ext_ct.items():
            src = assignment[name]
            bus.send(my, src, f"bp/{name}/{self.compnode_id}", ct)
        # route by (producer, this-consumer) key so multiple consumers sum
        return param_grads

    def update(self, grads: Dict[str, dict], lr: float = 1e-3) -> None:
        """Update task: plain SGD on hosted parametric ops (per-op
        optimizers configurable by the job file; SGD keeps the cluster
        test exact)."""
        self.params = jax.tree.map(
            lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype)
            if g is not None else p,
            self.params, grads)


# Wait-free ordering note: fp/bp must run in stage order in this
# single-process simulation; the cluster drives that.
class LocalCluster:
    """All compnode executors in one process, wired via a byte-accounting
    bus — the decentralized system in miniature."""

    def __init__(self, dag: DAG, parts: Sequence[Sequence[str]], cfg, key,
                 peer_ids: Optional[Sequence[int]] = None):
        self.dag = dag
        self.cfg = cfg
        self.parts = [list(p) for p in parts]
        self.peer_ids = list(peer_ids) if peer_ids else list(range(len(parts)))
        self.assignment = {n: self.peer_ids[i]
                           for i, part in enumerate(parts) for n in part}
        keys = jax.random.split(key, len(parts))
        self.executors = [SubDagExecutor(self.peer_ids[i], dag, part, cfg, keys[i])
                          for i, part in enumerate(self.parts)]
        self.bus = Bus()

    def train_step(self, tokens: Array, labels: Array, lr: float = 1e-3
                   ) -> float:
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        placeholders_all = {"input": tokens, "label": labels}
        loss = None
        for ex in self.executors:                      # FP in stage order
            ph = {n: placeholders_all[n] for n in ex.op_names
                  if self.dag[n].op_type == PLACEHOLDER}
            l, _ = ex.fp(self.bus, self.assignment, ph, positions)
            loss = l if l is not None else loss
        grads = {}
        for ex in reversed(self.executors):            # BP in reverse order
            grads[ex.compnode_id] = ex.bp(self.bus, self.assignment)
        for ex in self.executors:                      # Update
            ex.update(grads[ex.compnode_id], lr)
        return float(loss)

    def forward(self, tokens: Array, want: str = "head") -> Array:
        """Inference FP through the pipeline; returns ``want``'s output
        (logits for an unembed-terminated DAG)."""
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        out = None
        for ex in self.executors:
            ph = {n: tokens for n in ex.op_names
                  if self.dag[n].op_type == PLACEHOLDER}
            _, wanted = ex.fp(self.bus, self.assignment, ph, positions,
                              want=want)
            out = wanted if wanted is not None else out
        return out


# ---------------------------------------------------------------------------
# SPMD pipeline (shard_map + collective_permute): production mapping
# ---------------------------------------------------------------------------

def spmd_pipeline(stage_fn: Callable, stacked_params, x_microbatches: Array,
                  mesh, axis: str = "stage"):
    """Run a GPipe-style pipeline over the mesh axis ``axis``.

    stage_fn(params_i, x) -> x; ``stacked_params`` has a leading axis of
    size n_stages sharded over ``axis``; ``x_microbatches``: (n_micro, ...)
    microbatch inputs (resident on stage 0's shard conceptually; every
    stage receives its predecessor's output via collective_permute).

    Returns (n_micro, ...) outputs produced by the last stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    total = n_micro + n_stages - 1                     # fill + drain ticks

    from jax.sharding import PartitionSpec as P

    def per_stage(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # my stage's params
        xs = xs[0]                                     # (n_micro, ...) local
        idx = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            state, outs = carry                        # state: current x
            mb = jnp.clip(t, 0, n_micro - 1)
            inject = xs[mb]
            # stage 0 takes fresh microbatches; others take permuted input
            x_in = jnp.where(idx == 0, inject, state)
            y = stage_fn(params, x_in)
            # pass activations forward along the chain
            state_next = jax.lax.ppermute(y, axis, perm)
            # last stage records its output at the right slot
            out_t = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (out_t >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: o.at[jnp.clip(out_t, 0, n_micro - 1)].set(y),
                lambda o: o,
                outs)
            return (state_next, outs), None

        y0 = jax.eval_shape(stage_fn, params, xs[0])
        outs0 = jnp.zeros((n_micro,) + y0.shape, y0.dtype)
        state0 = jnp.zeros(y0.shape, y0.dtype)
        (_, outs), _ = jax.lax.scan(tick, (state0, outs0),
                                    jnp.arange(total))
        return outs[None]

    spec_params = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P(axis)),
                       out_specs=P(axis), check_vma=False)
    # replicate microbatches across stages (each stage uses only what it needs)
    xs_tiled = jnp.broadcast_to(x_microbatches[None],
                                (n_stages,) + x_microbatches.shape)
    outs = fn(stacked_params, xs_tiled)
    return outs[-1]
