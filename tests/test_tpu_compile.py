"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs: each test compiles ``paged_attention_fwd(...,
interpret=False)`` for a *described* v5e (no chip attached) with the
TPU compiler that ships with jaxlib, so a block layout or VMEM budget
Mosaic refuses fails here instead of on the chip.  Interpret-mode
parity tests cannot see such refusals.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_fwd

BF16 = jnp.bfloat16
# decode geometry of the served qwen3-8b preset: 8 slots x 2048 positions
# in 16-position pages
B, PAGE, N_BLOCKS, N_COLS = 8, 16, 1024, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, Hq, Hkv, D, De=0, *, b=B, n_blocks=N_BLOCKS,
             n_cols=N_COLS):
    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((b, 1, Hq, D)), sds((n_blocks, PAGE, Hkv, D)),
            sds((n_blocks, PAGE, Hkv, D)),
            sds((n_blocks, PAGE), jnp.int32), sds((b, n_cols), jnp.int32),
            sds((b, 1), jnp.int32)]
    if De:
        args += [sds((b, 1, Hq, De)), sds((n_blocks, PAGE, Hkv, De))]

    def fwd(q, k, v, pos, table, q_pos, *extra):
        kw = dict(q_extra=extra[0], k_extra=extra[1]) if extra else {}
        return paged_attention_fwd(q, k, v, pos, table, q_pos,
                                   interpret=False, **kw)

    compiled = jax.jit(fwd).lower(*args).compile()
    pool_bytes = n_blocks * PAGE * Hkv * D * 2
    return compiled, pool_bytes


def test_paged_decode_kernel_compiles_qwen3_8b_gqa(one_chip):
    """qwen3-8b decode: 32 query heads over 8 kv heads of 128."""
    compiled, pool_bytes = _compile(one_chip, Hq=32, Hkv=8, D=128)
    assert "tpu_custom_call" in compiled.as_text()
    # the pools enter the kernel as free views, never as copies
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_paged_decode_kernel_compiles_deepseek_v3_mla(one_chip):
    """deepseek-v3 absorbed MLA decode: 128 heads over one 512-wide
    latent pool (K = V) plus the 64-wide rope pool as a second score
    contraction."""
    compiled, pool_bytes = _compile(one_chip, Hq=128, Hkv=1, D=512, De=64)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("Hq,b,n_blocks,n_cols", [
    (32, 32, 2048, 288),    # qwen3-8b-reason: 32 slots x 4608 positions
    (40, 8, 1152, 144),     # qwen1.5-32b-chat: groups of 5, 8 x 2304
], ids=["qwen3-8b-reason", "qwen1.5-32b-chat"])
def test_paged_decode_kernel_compiles_bench_geometry(one_chip, Hq, b,
                                                     n_blocks, n_cols):
    """The benchmark cells' decode geometries: 8 kv heads of 128, the
    cells' slot counts, table widths and pool sizes.  A view of a pool
    that is not a bitcast of its layout would show here as a relayout
    copy of a whole 64 MiB pool in temp."""
    compiled, pool_bytes = _compile(one_chip, Hq=Hq, Hkv=8, D=128, b=b,
                                    n_blocks=n_blocks, n_cols=n_cols)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
