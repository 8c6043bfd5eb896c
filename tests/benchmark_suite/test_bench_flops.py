"""flops.py and peaks.json against hand-worked counts."""

import pytest

from benchmark import flops


def test_ernie_large_train_flops_per_token_by_hand():
    # per layer and token: QKV+O 4 x 2 x 1024^2 = 8,388,608; FFN 2 x 2 x
    # 1024 x 4096 = 16,777,216; attention 2 x 2 x 512 x 1024 = 2,097,152
    per_layer = 8_388_608 + 16_777_216 + 2_097_152
    assert per_layer == 27_262_976
    # MLM head on 80 of 512 positions: (2 x 1024^2 + 2 x 1024 x 18000) x 80/512
    mlm = (2_097_152 + 36_864_000) * 80 / 512
    nsp = (2_097_152 + 4096) / 512
    forward = 24 * per_layer + mlm + nsp
    got = flops.bert_forward_flops_per_token(
        hidden=1024, layers=24, ffn=4096, vocab=18000, seq=512, max_preds=80)
    assert got == pytest.approx(forward, rel=1e-12)
    assert 6.5e8 < got < 6.7e8
    assert flops.bert_train_flops_per_token(
        hidden=1024, layers=24, ffn=4096, vocab=18000, seq=512,
        max_preds=80) == pytest.approx(3 * forward, rel=1e-12)


def test_xglm_sized_bytes_per_decode_step_by_hand():
    # per layer: 4 x 2048^2 + 2 x 2048 x 8192 = 50,331,648 matrix entries,
    # biases 4 x 2048 + 8192 + 2048 = 18,432, norms 4 x 2048 = 8,192
    per_layer = 50_331_648 + 18_432 + 8_192
    weights = 4 * (24 * per_layer + 256008 * 2048)
    assert flops.decoder_weight_bytes(2048, 24, 8192, 256008) == weights
    assert 6.9e9 < weights < 7.0e9              # 1.73 B parameters, fp32
    kv = 2 * 2048 * 24 * 4
    assert kv == 393_216                         # 393 KB a cached token
    assert flops.decoder_kv_bytes_per_token(2048, 24) == kv
    assert flops.decoder_step_bytes(2048, 24, 8192, 256008, 2500) \
        == weights + 2500 * kv


def test_decoder_flops_per_token_by_hand():
    per_layer = 8 * 2048 * 2048 + 4 * 2048 * 8192 + 4 * 300 * 2048
    assert flops.decoder_forward_flops_per_token(2048, 24, 8192, 256008, 300) \
        == 24 * per_layer + 2 * 2048 * 256008


def test_peaks_come_from_the_table_and_an_unknown_kind_raises():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="cpu"):
        flops.peaks("cpu")
