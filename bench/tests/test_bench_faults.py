"""A whole run at a tiny size with the timed path broken underneath:
``correct`` has to come out false for each fault a served cell can have
on one chip (a step that returns its state unchanged, half of the batch
left out, a token altered where it is produced), and a step that leaves
out the QKV biases that the qwen1.5 cell's configuration names."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny
from bench.tests.test_bench_check import run


def _stale_state(step):
    def f(params, caches, *rest):
        tok, _, seen = step(params, caches, *rest)
        return tok, caches, seen
    return f


def _half_batch(step):
    def f(*args):
        tok, caches, seen = step(*args)
        h = tok.shape[0] // 2
        return jnp.concatenate([tok[:h], tok[:tok.shape[0] - h]]), caches, seen
    return f


def _token_altered(step):
    def f(params, *rest):
        tok, caches, seen = step(params, *rest)
        return (tok + 1) % params["embed"].shape[0], caches, seen
    return f


def _bias_dropped(step):
    def f(params, *rest):
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: (jnp.zeros_like(a) if getattr(p[-1], "key", None)
                          in ("bq", "bk", "bv") else a), params)
        return step(params, *rest)
    return f


FAULTS = {"state_unchanged": (_stale_state, tiny.QWEN3, tiny.CLOSED),
          "half_batch": (_half_batch, tiny.QWEN3, tiny.CLOSED),
          "token_altered": (_token_altered, tiny.QWEN3, tiny.CLOSED),
          "qkv_bias_dropped": (_bias_dropped, tiny.QWEN15, tiny.OPEN)}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_step_is_not_correct(fault, monkeypatch):
    import repro.serve.engine as E
    wrap, cfg, mix = FAULTS[fault]
    real = E.make_engine_step
    monkeypatch.setattr(E, "make_engine_step",
                        lambda *a, **k: wrap(real(*a, **k)))
    out = run(cfg, mix)
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > \
        cfg["check"]["served_logit_gap"]
