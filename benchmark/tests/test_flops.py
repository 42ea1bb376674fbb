"""The FLOPs and bytes functions against shapes enumerated by hand."""

import json
import os

import pytest

from benchmark.families import gpt2_lm, resnet_v15

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as fh:
        return json.load(fh)


def test_lm_formula_equals_the_sum_over_its_matmuls():
    cfg = config("cerebras-gpt-1.3b")
    d, L, T, V = 2048, 7, 2048, 50257
    assert (cfg["n_embd"], cfg["n_layer"], cfg["n_positions"],
            cfg["vocab_size"], cfg["n_inner"]) == (d, L, T, V, 8192)
    # One token, forward: every weight matmul is 2*k*n; attention per
    # layer is QK^T and PV, 2*T*d each, halved by the causal mask.
    fwd = 0
    for _ in range(L):
        fwd += 2 * d * 3 * d            # qkv
        fwd += (2 * T * d + 2 * T * d) / 2  # scores and weighted sum, causal
        fwd += 2 * d * d                # proj
        fwd += 2 * d * 8192             # fc1
        fwd += 2 * 8192 * d             # fc2
    fwd += 2 * d * V                    # head
    # The backward pass needs two products for each of the forward's.
    assert gpt2_lm.flops_per_unit(cfg) == pytest.approx(3 * fwd, rel=1e-12)
    assert gpt2_lm.flops_per_unit(cfg) == pytest.approx(2.9076e9, rel=1e-4)
    # bench.py's convention counts full attention: 12*L*T*d.
    old = gpt2_lm.flops_per_unit(cfg) + 6 * L * T * d
    assert old / gpt2_lm.flops_per_unit(cfg) == pytest.approx(1.06, abs=0.005)


def test_lm_parameter_count_from_the_same_shapes():
    cfg = config("cerebras-gpt-1.3b")
    d, L, V, T = 2048, 7, 50257, 2048
    weights = sum(k * n * c for _, k, n, c in gpt2_lm.matmuls(cfg))
    others = (V * d + T * d                   # embeddings
              + L * (2 * 2 * d + 8192 + d)    # two LayerNorms, fc biases
              + 2 * d)                        # final LayerNorm
    assert weights + others == 562_501_632


def test_resnet50_forward_is_4_1_gmac_an_image():
    cfg = config("resnet50-v1.5")
    macs = resnet_v15.forward_macs(cfg)
    assert macs == pytest.approx(4.1e9, rel=0.01)
    rows = resnet_v15.layers(cfg)
    assert len(rows) == 1 + 16 * 3 + 4 + 1     # stem, 16 blocks, 4 proj, head
    by_name = {r[0]: r for r in rows}
    assert by_name["conv_init"] == ("conv_init", 112, 112, 7, 7, 3, 64)
    # v1.5: the stride sits on the 3x3, so the 1x1 before it still sees
    # the larger map.
    assert by_name["BottleneckBlock_3.Conv_0"][1:3] == (56, 56)
    assert by_name["BottleneckBlock_3.Conv_1"][1:3] == (28, 28)
    assert by_name["BottleneckBlock_15.Conv_2"] == (
        "BottleneckBlock_15.Conv_2", 7, 7, 1, 1, 512, 2048)
    assert by_name["head"][5:] == (2048, 1000)
    stem = 112 * 112 * 7 * 7 * 3 * 64
    assert resnet_v15.flops_per_unit(cfg) == 2.0 * (3 * macs - stem)
    assert resnet_v15.flops_per_unit(cfg) == pytest.approx(24.3e9, rel=0.005)


def test_flash_cost_at_the_benchmark_shape():
    cfg = config("cerebras-gpt-1.3b")
    cost = gpt2_lm.flash_cost(cfg, 8)
    B, T, H, D, L = 8, 2048, 16, 128, 7
    assert cost["shape"] == [B, T, H, D]
    product = 2 * B * H * T * T * D / 2
    assert product == pytest.approx(68.72e9, rel=1e-3)
    assert cost["flops"] == L * 7 * product
    tensor = B * T * H * D * 2
    assert tensor == 67_108_864
    stats = B * H * T * 4
    assert cost["bytes"] == L * (15 * tensor + 5 * stats)
    # Compute-bound on a v5e: 2.44 ms a layer by FLOPs, 1.23 by bytes.
    assert cost["flops"] / L / 197e12 == pytest.approx(2.44e-3, rel=0.01)
    assert cost["bytes"] / L / 819e9 == pytest.approx(1.23e-3, rel=0.01)
