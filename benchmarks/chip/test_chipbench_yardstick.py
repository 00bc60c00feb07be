"""The benchmark's FLOPs function and peaks table."""

import json
import pathlib

import pytest

import flops
import peaks

HERE = pathlib.Path(__file__).resolve().parent


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_olmo_flops_hand_count():
    cfg = _config("olmo-1b-fp32")
    assert cfg["num_hidden_layers"] == 8
    # per layer: q, k, v, o 2048x2048 each; SwiGLU 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 67_108_864
    weights = 8 * layer + 50304 * 2048          # + the tied head
    assert flops.matmul_params(cfg) == weights == 639_893_504
    # causal attention: QK^T and PV over (S+1)/2 keys a token, x3 for
    # forward + backward: 6 * L * H * dh * (S + 1)
    attn = 6 * 8 * 16 * 128 * 2049
    assert flops.train_flops_per_token(cfg, 2048) == 6 * weights + attn
    assert flops.train_flops_per_token(cfg, 2048) == 4_040_785_920


def test_qwen_flops_hand_count():
    cfg = _config("qwen2.5-3b-fp32")
    assert cfg["num_hidden_layers"] == 3
    # q and o 2048x2048, k and v 2048x(2 heads x 128), SwiGLU 3 x 2048 x 11008
    layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert layer == 77_070_336
    weights = 3 * layer + 151936 * 2048
    assert flops.matmul_params(cfg) == weights == 542_375_936
    attn = 6 * 3 * 16 * 128 * 4097
    assert flops.train_flops_per_token(cfg, 4096) == 6 * weights + attn
    assert flops.train_flops_per_token(cfg, 4096) == 3_405_287_424


def test_peaks_known_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v6 lite", ""])
def test_peaks_unknown_device_raises(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)
