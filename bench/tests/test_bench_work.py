"""FLOP and needed-byte counters of the dense family against
hand-computed values, reached through the configuration's family."""
import pytest

from bench.harness import spec


def _fam(name):
    cfg = spec.config(name)
    fam = spec.family(cfg)
    return fam, fam.widths(cfg)


def test_qwen3_8b_chip_counts():
    W, m = _fam("qwen3-8b-chip")
    # per layer: qkv 2*4096*(32+16)*128, o 2*4096*4096, mlp 6*4096*12288
    per_layer = 2 * 4096 * 6144 + 2 * 4096 * 4096 + 6 * 4096 * 12288
    assert W.matmul_flops_per_token(m) == 18 * per_layer
    assert W.unembed_flops(m) == 2 * 4096 * 151936
    # a query at position 99 sees 100 keys: 4 * 32 heads * 128 * 100
    assert W.attention_flops(m, 99) == 18 * 4 * 32 * 128 * 100
    # needed K/V: position 16 reads 17 keys -> two 16-position pages
    f, b = W.decode_attention_need(m, [16], 16)
    assert b == 18 * (2 * 2 * 16 * 8 * 128 * 2 + 2 * 32 * 128 * 2)
    assert f == 18 * 4 * 32 * 128 * 17


def test_qwen15_32b_chip_counts():
    W, m = _fam("qwen1.5-32b-chip")
    per_layer = (2 * 5120 * (40 + 16) * 128 + 2 * 5120 * 5120
                 + 6 * 5120 * 27392)
    assert W.matmul_flops_per_token(m) == 8 * per_layer
    # 8 kv heads x 128 x 2 (k, v) x 2 bytes x 8 layers = 32 KiB per token
    _, b = W.decode_attention_need(m, [15], 16)
    assert b - 8 * 2 * 40 * 128 * 2 == 16 * 32 * 1024


def test_only_live_work_is_counted():
    W, m = _fam("qwen3-8b-chip")
    # idle slots add nothing, whatever the batch or table width
    assert W.decode_step_flops(m, []) == 0
    assert W.decode_attention_need(m, [], 16) == (0, 0)
    one = W.decode_step_flops(m, [40])
    assert W.decode_step_flops(m, [40, 40]) == 2 * one
    # a 300-token prompt tail from position 768, chunk 128: 3 chunks,
    # one unembed each, attention over 769..1068 keys
    f = W.prefill_flops(m, 768, 1068, 128)
    attn = sum(W.attention_flops(m, p) for p in range(768, 1068))
    assert f == 300 * W.matmul_flops_per_token(m) + attn + 3 * W.unembed_flops(m)


# the counts as the harness computed them before the family modules:
# what the benchmark reads may not move with the code that reads it
PINNED = {
    "qwen3-8b-chip": (16_758_341_632, 6_813_931_012_096,
                      (377_487_360, 94_961_664)),
    "qwen1.5-32b-chip": (18_800_967_680, 7_516_984_442_880,
                         (209_715_200, 42_270_720)),
}


@pytest.mark.parametrize("name", list(PINNED))
def test_counts_are_pinned(name):
    W, m = _fam(name)
    decode, prefill, need = PINNED[name]
    assert W.decode_step_flops(m, [255, 1023]) == decode
    assert W.prefill_flops(m, 0, 960, 128) == prefill
    assert W.decode_attention_need(m, [255, 1023], 16) == need
