"""OLMoE's FLOPs and bytes functions, and the expert layer's reader,
against shapes enumerated by hand (in ``test_flops.py``'s manner)."""

import json
import os

import pytest

from benchmark.families import gpt2_lm, olmoe_lm
from benchmark.metrics import moe_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as fh:
        return json.load(fh)


def test_olmoe_formula_equals_the_sum_over_its_matmuls():
    cfg = config("olmoe-1b-7b")
    d, L, T, V, E, k, h = 2048, 1, 4096, 50304, 64, 8, 1024
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["max_position_embeddings"], cfg["vocab_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["intermediate_size"]) == (d, L, T, V, E, k, h)
    # One token, forward: every weight matmul is 2*k*n; a token runs 8 of
    # the 64 experts, three matrices each; attention is QK^T and PV,
    # 2*T*d each, halved by the causal mask.
    fwd = 0
    for _ in range(L):
        fwd += 2 * d * 3 * d                     # qkv
        fwd += (2 * T * d + 2 * T * d) / 2       # scores, weighted sum
        fwd += 2 * d * d                         # proj
        fwd += 2 * d * E                         # router
        fwd += k * (2 * d * h + 2 * d * h + 2 * h * d)   # gate, up, down
    fwd += 2 * d * V                             # head
    assert olmoe_lm.flops_per_unit(cfg) == pytest.approx(3 * fwd, rel=1e-12)
    # ISSUE 25's shares a token: experts 302 M, projections 101 M, scores
    # 50 M, head 618 M.
    assert 6 * k * 3 * d * h == pytest.approx(302e6, rel=1e-3)
    assert olmoe_lm.flops_per_unit(cfg) == pytest.approx(1.0719e9, rel=1e-4)
    # Running all 64 experts on every token would be 2.97 times the FLOPs.
    dense = olmoe_lm.flops_per_unit(cfg) + 6 * (E - k) * 3 * d * h
    assert dense / olmoe_lm.flops_per_unit(cfg) == pytest.approx(2.97, abs=0.01)


def test_olmoe_parameter_count_from_the_same_shapes():
    cfg = config("olmoe-1b-7b")
    d, V, E, h = 2048, 50304, 64, 1024
    per_token = {n: (k, m) for n, k, m, _ in olmoe_lm.matmuls(cfg)}
    layer = (per_token["qkv"][0] * per_token["qkv"][1] + d * d + d * E
             + E * 3 * d * h          # every expert is held, 8 are run
             + 2 * d + 2 * d)         # ln1, ln2; q_norm, k_norm
    assert layer + 2 * V * d + d == 625_616_896


def test_moe_cost_at_the_benchmark_shape():
    cfg = config("olmoe-1b-7b")
    cost = olmoe_lm.moe_cost(cfg, 4)
    tokens, d, E, k, h = 4 * 4096, 2048, 64, 8, 1024
    A = tokens * k
    assert cost["assignments"] == A == 131_072
    assert cost["expert_parameters"] == 3 * E * d * h == 402_653_184
    # Three grouped matmuls of A rows, and the router: 6 FLOPs a weight.
    assert cost["flops"] == 6 * (3 * A * d * h + tokens * d * E)
    # Each grouped matmul, three times (forward, input gradient, weight
    # gradient): A rows in and A rows out in bf16; the bf16 weights read
    # twice and the float32 weight gradient written once.
    one = 3 * A * (d + h) * 2 + 2 * E * d * h * 2 + E * d * h * 4
    assert cost["bytes"] == 3 * one
    # Compute-bound on a v5e: 25.2 ms a step by FLOPs, 12.7 by bytes.
    assert cost["flops"] / 197e12 == pytest.approx(25.2e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(12.7e-3, rel=0.01)


def test_moe_ms_takes_the_expert_layer_s_ops_and_no_other():
    for label in (
            "jvp(TransformerLM)/block_*/moe/route/dot_general [convolution]",
            "transpose(jvp(TransformerLM))/block_*/moe/combine/mul [loop]",
            "moe/dispatch/sort [sort]", "ragged-dot-none [custom-call]",
            "params['block_*']['moe']['w_up'] [data formatting]"):
        assert moe_ms.in_expert_layer(label), label
    for label in (
            "jvp(TransformerLM)/block_*/attn/qkv/dot_general [convolution]",
            "jvp(TransformerLM)/block_*/ln2/mul [loop]", "fusion",
            "jvp(TransformerLM)/block_*/fc1/dot_general [moe]"):
        assert not moe_ms.in_expert_layer(label), label

    class Family:
        moe_cost = staticmethod(lambda cfg, b: {})

    trace = {"devices": [{"steps": 5, "op_self_s": {
        "jvp(M)/block_*/moe/experts/silu [loop]": 0.010,
        "ragged-dot-none [custom-call]": 0.090, "jvp(M)/head/dot_general [c]": 0.3}}]}
    assert moe_ms.read({"family": Family}, trace) == pytest.approx(20.0)
    assert moe_ms.read({"family": Family}, None) is None
    assert moe_ms.read({"family": gpt2_lm}, trace) is None
    # A program without the layer or its scopes (this metric's parent).
    bare = {"devices": [{"steps": 5, "op_self_s": {"fusion": 0.3}}]}
    assert moe_ms.read({"family": Family}, bare) is None
