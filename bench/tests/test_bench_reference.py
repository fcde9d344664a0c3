"""The float32 reference against the system's own model code, at a
small size, for both dense variants: the same weights from the same
seed (with the benchmark's nonzero QKV biases), and the same logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import spec
from bench.tests import tiny


@pytest.mark.parametrize("cfg", [tiny.QWEN3, tiny.QWEN15],
                         ids=["qwen3-qk-norm", "qwen1.5-qkv-bias"])
def test_reference_matches_the_model(cfg):
    from repro.models.transformer import forward, init_params
    ref = spec.family(cfg)
    mc, m = ref.model_config(cfg), ref.widths(cfg)
    seed = 2 ** 31 + 11
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), mc)
    w = ref.draw_weights(seed, m)
    # the benchmark's seeded biases, set as the harness sets them
    params = ref.prepare_params(params, m, seed)
    st = params["stack"][0]
    if m["attn_bias"]:
        biases = ref.draw_biases(seed, m)
        assert all(float(jnp.abs(b).mean()) > ref.BIAS_STD / 2
                   for b in biases.values())
        for name, b in biases.items():
            assert bool(jnp.array_equal(st["mixer"][name], b))
    for ours, theirs in [(w["embed"], params["embed"]),
                         (w["lm_head"], params["lm_head"]),
                         (w["layers"]["wq"], st["mixer"]["wq"]),
                         (w["layers"]["wo"], st["mixer"]["wo"]),
                         (w["layers"]["w_up"], st["ffn"]["w_up"]),
                         (w["layers"]["w_down"], st["ffn"]["w_down"])]:
        assert ours.dtype == jnp.bfloat16
        assert bool(jnp.array_equal(ours, theirs))
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 40)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, _, _ = forward(p32, mc, {"tokens": jnp.asarray(toks)[None]})
    got = ref.logits_at(w, m, toks, np.arange(40))[:40]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=1e-4, rtol=1e-4)


def test_fp8_control_departs_from_the_reference():
    ref = spec.family(tiny.QWEN3)
    m = ref.widths(tiny.QWEN3)
    w = ref.draw_weights(5, m)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], 24)
    a = ref.logits_at(w, m, toks, np.arange(24))[:24]
    b = ref.logits_at(w, m, toks, np.arange(24), quant="fp8")[:24]
    err = float(jnp.abs(a - b).max())
    assert 1e-3 < err < 1.0
