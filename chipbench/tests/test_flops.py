"""FLOP and byte counts against the hand arithmetic of the cells.

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import flops  # noqa: E402
from chipbench.arch import qwen2  # noqa: E402


def shape(name: str) -> dict:
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_train_config_parameter_counts():
    s = shape("qwen2.5-3b-train-3L")
    # per layer: q, o 2048x2048 each; k, v 2048x256 each; MLP 3x2048x11008
    layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert qwen2.layer_matmul_params(s) == layer == 77_070_336
    assert qwen2.head_params(s) == 151_936 * 2048 == 311_164_928
    assert qwen2.matmul_params_per_token(s) == 3 * layer + 311_164_928
    # plus q/k/v biases (2048 + 2 x 256) and two norm scales per layer,
    # and the final norm: the 542,397,952 parameters the program holds
    assert qwen2.param_count(s) == 542_397_952


def test_train_flops_per_token():
    s = shape("qwen2.5-3b-train-3L")
    want = 6 * 542_375_936 + 12 * 3 * 2048 * 2048
    assert flops.train_flops_per_token(s, 2048) == want
    assert abs(want / 1e9 - 3.405) < 1e-3


def test_update_bytes_are_five_f32_passes():
    s = shape("qwen2.5-3b-train-3L")
    assert flops.update_bytes(s) == 20 * 542_397_952   # 10.85 GB


def test_serve_config_counts():
    s = shape("qwen2.5-3b-serve")
    assert qwen2.param_count(s) == 3_085_938_688
    # 36 layers x 2 KV heads x 128 x (K, V) x 2 bytes
    assert qwen2.kv_bytes_per_token(s, 2) == 36_864
    # the pool: 32 slots x 2048 tokens
    assert qwen2.kv_bytes_per_token(s, 2) * 32 * 2048 == 2_415_919_104


def test_decode_attention_bytes():
    s = shape("qwen2.5-3b-serve")
    # two slots at 100 and 300 positions: their K/V, plus q and out of
    # 16 heads x 128 per layer
    want = 400 * 36_864 + 2 * 36 * 2 * 16 * 128 * 2
    assert flops.decode_attention_bytes(s, [100, 300]) == want


def test_decode_and_prefill_flops():
    s = shape("qwen2.5-3b-serve")
    n_mat = 36 * 77_070_336 + 311_164_928
    assert flops.decode_flops(s, 10) == 2 * n_mat + 4 * 36 * 2048 * 10
    body = 2 * 36 * 77_070_336 * 4
    attn = 4 * 36 * 2048 * (1 + 2 + 3 + 4)
    assert flops.prefill_flops(s, 4) == body + attn + 2 * 311_164_928
