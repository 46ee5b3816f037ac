"""The operation and byte counts, against hand counts at one shape."""
import pytest

from bench import costs
from bench.reference import dense_lm

CFG = {  # smollm-360m's shape
    "num_hidden_layers": 32, "hidden_size": 960, "num_attention_heads": 15,
    "num_key_value_heads": 5, "head_dim": 64, "intermediate_size": 2560,
    "vocab_size": 49152, "norm": "rmsnorm", "reference": "dense_lm",
}
CODEC = {"group_size": 10}
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_params_by_hand():
    per_layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    want = 32 * per_layer + 49152 * 960 + 65 * 960
    assert dense_lm.n_params(CFG) == want == 361_821_120


def test_kv_bytes_per_token():
    assert dense_lm.kv_bytes_per_token(CFG) == 32 * 2 * 320 * 2 == 40_960


def test_decode_step_by_hand():
    w = costs.decode_step(CFG, [100, 200])
    matmul = 361_821_120 - 65 * 960
    attn = 4 * 15 * 64 * (101 + 201) * 32
    assert w["flops"] == 2 * matmul * 2 + attn
    assert w["bytes"] == 361_821_120 * 2 + 40_960 * 300 + 40_960 * 2


def test_token_kernels_by_hand():
    # one lossless and one lossy 256-token chunk: 64 (layer, K/V) rows,
    # 26 groups of 10, 320 channels
    w = costs.token_kernels(CFG, CODEC, 256, 1, 1)
    out = 64 * 26 * 10 * 320 * 2
    deltas = 64 * 26 * 9 * 320
    lossless = deltas * 2 + 64 * 26 * 320 * 2 + 64 * 26 * 4 + out
    lossy = deltas * 2 + 64 * 26 * 320 * 4 + 64 * 4 + out
    assert w["bytes"] == lossless + lossy
    assert w["flops"] == 2 * 64 * 26 * 10 * 320 + 3 * deltas


def test_least_seconds_takes_the_larger_bound():
    assert costs.least_seconds({"flops": 197e12, "bytes": 0.0}, PEAK) == pytest.approx(1.0)
    assert costs.least_seconds({"flops": 0.0, "bytes": 819e9}, PEAK) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    from bench.peaks import peaks

    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
