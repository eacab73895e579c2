import json
import os

import pytest

from benchmark import flops, loader


def model(name):
    """A cell's configuration, or (xglm-1.7b: the four-chip cell's, which no
    cell of ``BENCHMARK.json`` uses yet) one kept beside the tests."""
    for d in ("benchmark/configs", "benchmark/tests/data/configs"):
        path = os.path.join(loader.ROOT, d, name + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)["model"]
    raise FileNotFoundError(name)


@pytest.mark.parametrize("name,params,matmul", [
    # vocab x d + layers x 12 d^2 (+ biases and LayerNorm vectors)
    ("xglm-564m", 564.5e6, 256008 * 1024 + 24 * 12 * 1024 ** 2),
    ("xglm-1.7b", 1.733e9, 256008 * 2048 + 24 * 12 * 2048 ** 2),
    # qkv 3072 x (24 + 4) x 128, out 3072^2, mlp 2 x 3072 x 12288
    ("starcoder2-3b", 3.03e9, 49152 * 3072 + 30 * (3072 * 3584 + 3072 ** 2 + 2 * 3072 * 12288)),
])
def test_parameter_counts_against_hand_counts(name, params, matmul):
    m = model(name)
    assert flops.matmul_params(m) == matmul
    assert flops.param_count(m) == pytest.approx(params, rel=2e-3)


@pytest.mark.parametrize("name,gflop", [("xglm-564m", 3.69), ("xglm-1.7b", 11.0)])
def test_training_flops_a_token(name, gflop):
    m = model(name)
    # 6 a matmul parameter + 3 x (4 x (L+1)/2 x d) a layer of causal attention
    by_hand = 6 * flops.matmul_params(m) + 3 * m["depth"] * 4 * (2049 / 2) * m["dim"]
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(by_hand, rel=1e-9)
    assert flops.train_flops_per_token(m, 2048) / 1e9 == pytest.approx(gflop, abs=0.05)


def test_serving_counts():
    m = model("starcoder2-3b")
    assert flops.weight_bytes(m) == pytest.approx(6.06e9, rel=5e-3)
    assert flops.kv_bytes_per_position(m) == 30720
    one = flops.serve_flops(m, 1, 1)        # one position, one token out
    assert one == 2 * flops.matmul_params(m) + 4 * 30 * 24 * 128
    # the window clips what a position attends to
    short = dict(m, attn_window=16)
    assert flops.attention_flops_forward(short, 64) < flops.attention_flops_forward(m, 64)


def test_flash_call_counts():
    assert flops.flash_call_flops("fwd", 1, 1, 128, 64) == 2 * 2 * (128 * 129 / 2) * 64
    assert flops.flash_call_flops("dkv", 2, 3, 128, 64) == 2 * 3 * 4 * 2 * (128 * 129 / 2) * 64
    assert flops.flash_call_bytes("fwd", 1, 4, 2, 128, 64) == (2 * 4 + 2 * 2) * 128 * 64 * 2
