"""Work counting, against values worked by hand for qwen1.5-0.5b
(d 1024, 16 heads of 64, d_ff 2816, 24 layers, vocab 151936, bf16 KV)."""
import pytest

import spec
import work

QWEN = work.Dims.of(spec.config(spec.load_benchmark(), "qwen1.5-0.5b"))
V5E = work.peaks("TPU v5 lite")


def test_dims():
    assert QWEN == work.Dims(1024, 16, 16, 64, 2816, 24, 151936, 2)


def test_layer_flops():
    # q, k, v: 1024 x 3072; o: 1024 x 1024; gate, up, down: 3 x 1024 x 2816
    assert work.layer_matmul_flops(QWEN) == 2 * (3145728 + 1048576
                                                 + 8650752)


def test_one_decode_row():
    # one token at position 511 attends to 512 keys
    attn = 4 * 16 * 64 * 512 * 24
    assert work.attention_flops(QWEN, 511, 1) == attn == 50331648
    assert work.token_flops(QWEN, 511, 1, 1) == (24 * 25690112 + attn
                                                 + 2 * 1024 * 151936)
    b, f = work.attention_need(QWEN, 511, 1)
    # K and V of 512 tokens x 16 heads x 64 x 2 B, plus q and out rows
    assert b == (2 * 16 * 64 * 512 * 2 + 2 * 16 * 64 * 2) * 24 == 50429952
    assert f == attn


def test_one_mixed_step():
    # eight decode lanes at context 512 and one 256-token chunk from 0
    rows = [(511, 1)] * 8 + [(0, 256)]
    chunk_keys = 256 * 257 // 2                     # causal: 1 + ... + 256
    assert work.attention_flops(QWEN, 0, 256) == 4 * 1024 * chunk_keys * 24
    flops = 8 * 50331648 + 4 * 1024 * chunk_keys * 24
    chunk_bytes = (2 * 16 * 64 * 256 * 2 + 2 * 256 * 16 * 64 * 2) * 24
    bytes_ = 8 * 50429952 + chunk_bytes
    want = max(flops / 197e12, bytes_ / 819e9)
    assert work.roofline_seconds([rows], QWEN, V5E) == pytest.approx(want)
    # a step is one call per layer: bounded as a whole, not row by row
    rowwise = sum(max(work.attention_need(QWEN, q, n)[1] / 197e12,
                      work.attention_need(QWEN, q, n)[0] / 819e9)
                  for q, n in rows)
    assert work.roofline_seconds([rows], QWEN, V5E) <= rowwise


def test_padding_is_not_work():
    assert work.roofline_seconds([[]], QWEN, V5E) == 0.0
    assert work.token_flops(QWEN, 0, 0, 0) == 0


def test_unknown_device_kind_has_no_default():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
