"""The peak table and the work functions, on shapes worked by hand."""

import json

import pytest

from bench import peaks
from bench.harness import ROOT

TINY = {"hidden_size": 4, "intermediate_size": 8, "vocab_size": 10,
        "num_hidden_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 1, "tie_word_embeddings": False}
V5E = peaks.peaks_for("TPU v5 lite")


def test_v5e_peaks_are_the_published_ones():
    assert V5E.flops_bf16 == 197e12
    assert V5E.hbm_bytes_per_s == 819e9
    assert V5E.hbm_bytes == 16e9
    assert V5E.ici_bytes_per_s == 200e9          # 1,600 Gbit/s
    assert "TPU v5e" in V5E.source


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_mfu_train_by_hand():
    # hd = 2; attention weights 4*2*(2*2 + 2*1) = 48, MLP 3*4*8 = 96,
    # head 10*4 = 40: 2 * (48 + 96 + 40) = 368 FLOPs of matmuls per token;
    # causal attention over (1 + 2) / 2 keys: 2*2 * 1 layer * 2 heads *
    # hd 2 * 1.5 = 24; forward 392, training 3x = 1176.
    assert peaks.decoder_matmul_flops_per_token(TINY) == 368.0
    assert peaks.train_flops_per_token(TINY, 2) == 1176.0
    assert peaks.mfu(1e9, 1176.0, 1, V5E) == pytest.approx(
        100 * 1176e9 / 197e12)
    assert peaks.mfu(1e9, 1176.0, 4, V5E) == pytest.approx(
        100 * 1176e9 / (4 * 197e12))


def test_danube_parameters_by_hand():
    c = json.loads((ROOT / "bench/configs/danube.json").read_text())
    # per layer: attention 2560*80*(64+16) = 16,384,000, MLP 3*2560*6912 =
    # 53,084,160, two norms 5,120; 24 layers; embedding and head
    # 32000*2560 each; the final norm 2560
    assert peaks.decoder_weight_params(c) == \
        24 * (16_384_000 + 53_084_160 + 5_120) + 2 * 81_920_000 + 2560
    assert peaks.kv_bytes_per_token(c) == 2 * 24 * 8 * 80 * 2


def test_decode_tick_is_bound_by_bytes_at_small_batch():
    c = json.loads((ROOT / "bench/configs/danube.json").read_text())
    least = peaks.decode_tick_least_s(c, [100, 200], V5E)
    weights = peaks.decoder_weight_params(c) * 2
    kv = peaks.kv_bytes_per_token(c) * 300
    assert least == pytest.approx((weights + kv) / 819e9)
    assert peaks.decode_tick_least_s(c, [], V5E) == 0.0


def test_exchange_least_time():
    block = 4 * 640 * 4096 * 2                  # (4, 640, 4096) bf16
    assert block == 20 * 2 ** 20
    assert peaks.exchange_bytes_per_chip(4, block) == 60 * 2 ** 20
    assert peaks.exchange_least_s(4, block, V5E) == pytest.approx(
        60 * 2 ** 20 / 200e9)


def test_moe_capacity_of_the_dispatch_cell():
    assert peaks.moe_capacity(1.25, 2, 4096, 16) == 640
    assert peaks.moe_capacity(1.25, 2, 64, 4) == 40
