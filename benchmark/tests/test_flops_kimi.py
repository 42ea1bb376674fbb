"""The Kimi-Linear family's FLOPs and bytes functions against shapes
enumerated by hand, and the readers of the two metrics the configuration
brings (``kda_decay_ms``, ``kda_intra_ms``) with the accepted ones that read
the cell, on a hand-made trace."""

import json
import os

import numpy as np
import pytest

from benchmark.families import kimi_linear_lm as family
from benchmark.metrics import (delta_ms, delta_roofline, gqa_flash_ms,
                               kda_decay_ms, kda_intra_ms, linattn_ms,
                               mla_attn_ms, mla_attn_roofline, mla_ms, moe_ms,
                               mtp_ms, route_ms)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "kimi-linear-48b-a3b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut: the dense layer and one period (k K K x
# K), 8 of 256 experts, an eighth of the vocabulary.
d, T, V, H = 2304, 8192, 20480, 32
DK = DV = 128
RANK, C = 128, 64
KVL, NOPE, ROPE, VD = 512, 128, 64, 128
DENSE, EH, E, HELD, TOP = 9216, 1024, 256, 8, 8
KDA_LAYERS, ATTN_LAYERS, EXPERT_LAYERS = 4, 1, 4
KDA = (3 * d * H * DK + d * H + 2 * (d * RANK + RANK * H * DK)
       + H * DV * d)
LATENT = d * H * 192 + d * (KVL + ROPE) + KVL * H * 256 + H * VD * d


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    assert family.pattern(cfg) == "kKKxK"
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            cfg["linear_chunk_size"], cfg["linear_low_rank"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["mla_use_nope"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["experts_routed_over"], cfg["num_experts"],
            cfg["num_experts_per_token"], cfg["routed_scaling_factor"],
            cfg["rms_norm_eps"]) == (
                d, T, V, H, H, H, DK, 4, C, RANK, None, KVL, NOPE, ROPE, VD,
                True, DENSE, EH, E, HELD, TOP, 2.446, 1e-5)
    assert [(k, cfg["reduced"][k]["published"], cfg["reduced"][k]["run"])
            for k in cfg["reduced"]] == [
        ("num_hidden_layers", 27, 5), ("num_experts", 256, HELD),
        ("vocab_size", 163840, V)]
    assert all(cfg[k] == cfg["reduced"][k]["run"] for k in cfg["reduced"])
    # Every number of the catalog's copy but the three cuts, the nested
    # group whole.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == set(
            cfg["reduced"])
    leaves = family.grad_leaves(cfg)
    for must in (("layer_0", "lin", "f_a", "kernel"),
                 ("layer_0", "lin", "g_b", "kernel"),
                 ("layer_0", "lin", "q", "kernel"),
                 ("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "moe", "w_gate"),
                 ("layer_3", "attn", "q_b", "kernel"),
                 ("layer_4", "lin", "f_b", "kernel")):
        assert must in leaves
    assert not any(path[-2:] == ("moe", "w_gate") and path[0] != "layer_1"
                   for path in leaves)
    assert family.host_batch(cfg, np.random.default_rng(0),
                             2).shape == (2, T + 1)
    assert not hasattr(family, "mtp_pattern")
    assert not hasattr(family, "FLASH_KERNELS")


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    """Forward FLOPs a token, by hand (ISSUE 62's table): the KDA
    projections 316 M, the latent layer's projections 58 M and its causal
    scores 84 M, the dense SwiGLU 127 M, the head 94 M, the shared experts
    57 M, the routers 4.7 M, the held experts 14 M at the uniform share (a
    quarter of an assignment a token), the chunked rule 18 M (the issue
    guessed 30), the convolutions 0.4 M."""
    assert (KDA, LATENT) == (39_460_864, 29_114_368)
    assert sum(k * n for _, k, n in family.kda_matmuls(cfg)) == KDA
    assert sum(k * n for _, k, n in family.latent_matmuls(cfg)) == LATENT
    assert family.held_share(cfg) == 0.25
    delta = H * (3 * C * DK + 2 * C * DV + C * C / 3 + 6 * DK * DV)
    assert family.delta_flops_per_token(cfg) == delta
    parts = {
        "kda": 2 * KDA_LAYERS * KDA, "latent": 2 * LATENT,
        "scores": 2 * T * H * (192 + VD) / 2,
        "dense": 2 * 3 * d * DENSE, "head": 2 * d * V,
        "shared": 2 * EXPERT_LAYERS * 3 * d * EH,
        "router": 2 * EXPERT_LAYERS * d * E,
        "held": 2 * EXPERT_LAYERS * 0.25 * 3 * d * EH,
        "delta": KDA_LAYERS * delta,
        "conv": KDA_LAYERS * 2 * 4 * H * 3 * DK}
    forward = sum(parts.values())
    assert family.flops_per_unit(cfg) == pytest.approx(3 * forward, rel=1e-12)
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "kda": 315.7, "latent": 58.2, "scores": 83.9, "dense": 127.4,
        "head": 94.4, "shared": 56.6, "router": 4.7, "held": 14.2,
        "delta": 18.0, "conv": 0.4}
    assert forward == pytest.approx(0.773e9, rel=0.01)
    # Four of five layers are KDA: their projections, rule and convolution
    # are 43% of the model's FLOPs; one step of the cell is 19.0 TFLOP.
    assert (parts["kda"] + parts["delta"] + parts["conv"]) / forward == (
        pytest.approx(0.43, abs=0.01))
    assert T * 3 * forward == pytest.approx(19.0e12, rel=0.01)


def test_delta_cost_prices_a_decay_a_channel(cfg):
    cost = family.delta_cost(cfg, 1)
    assert cost["flops"] == 3 * KDA_LAYERS * T * family.delta_flops_per_token(
        cfg)
    # q, k, v in bf16; g a CHANNEL and beta a head in f32.
    inputs = H * ((DK + DK + DV) * 2 + DK * 4 + 4)
    o = H * DV * 2
    assert cost["bytes"] == KDA_LAYERS * T * ((inputs + o)
                                              + (inputs + o + inputs))
    assert cost["decay_bytes"] == KDA_LAYERS * T * H * DK * 4
    assert cost["chunks"] == KDA_LAYERS * T // C
    assert cost["sub_chunks"] == KDA_LAYERS * (T // C) * (C - 1)
    assert cost["state_bytes"] == KDA_LAYERS * (T // C) * H * DV * DK * 4
    # The float32 log-decays, read three times and written once, are 35% of
    # the compulsory traffic: what a decay a head does not move.
    assert 3 * cost["decay_bytes"] / cost["bytes"] == pytest.approx(0.35,
                                                                    abs=0.01)
    # Byte-bound on a v5e: 5.6 ms a step, against 2.2 by FLOPs.
    assert cost["bytes"] / 819e9 == pytest.approx(5.59e-3, rel=0.02)
    assert cost["flops"] / 197e12 == pytest.approx(2.25e-3, rel=0.02)
    twice = family.delta_cost(cfg, 2)
    assert (twice["flops"], twice["bytes"]) == (2 * cost["flops"],
                                                2 * cost["bytes"])


def test_flash_mla_and_moe_cost_at_the_benchmark_shape(cfg):
    cost = family.flash_cost(cfg, 1)
    pair = 2 * H * T * T / 2
    assert cost["flops"] == ATTN_LAYERS * pair * (320 + 832)
    qk, v, stat = T * H * 192 * 2, T * H * VD * 2, H * T * 4
    assert cost["bytes"] == (2 * qk + 2 * v + stat) + (4 * qk + 4 * v
                                                       + 2 * stat)
    assert cost["shape"] == [1, T, H, 192, VD] and cost["calls_per_step"] == 1
    # FLOP-bound on a v5e: 12.6 ms a step.
    assert cost["flops"] / 197e12 == pytest.approx(12.56e-3, rel=0.01)
    mla = family.mla_cost(cfg, 1)
    assert mla["flops"] == 6.0 * T * LATENT
    assert mla["recomputed_flops"] == 2.0 * T * (d * H * 192 + KVL * H * 256)
    assert mla["pass_bytes"] == T * 5 * 2 * KVL
    moe = family.moe_cost(cfg, 1)
    assert moe["router_flops"] == EXPERT_LAYERS * 6.0 * T * d * E
    A = T * TOP * HELD / E
    assert (moe["assignments"], moe["held_assignments"]) == (T * TOP, A)
    assert moe["flops"] == moe["router_flops"] + EXPERT_LAYERS * 6.0 * 3 * d * (
        A * EH + T * EH)
    assert moe["expert_parameters"] == EXPERT_LAYERS * 3 * d * (HELD + 1) * EH


# ------------------------------------------------------------ the readers

STACK = "transpose(jvp(TransformerLM))/TransformerLM._pattern_stack/layer_*/"


def record_of(fam, cfg_):
    return {"family": fam, "cfg": cfg_, "job": {"batch_per_chip": 1},
            "peaks": PEAKS}


def test_the_readers_take_their_ops_and_no_other(cfg):
    ops = {
        STACK + "lin/decay/f_a/dot_general [convolution fusion]": 0.010,
        STACK + "lin/checkpoint/decay/neg [loop fusion]": 0.020,
        STACK + "lin/checkpoint/delta/decay/exp [loop fusion]": 0.030,
        STACK + "lin/checkpoint/delta/solve/dot_general [convolution]": 0.200,
        STACK + "lin/checkpoint/delta/intra/dot_general [convolution]": 0.050,
        STACK + "lin/checkpoint/delta/states/while [while]": 0.100,
        STACK + "lin/checkpoint/delta/inter/dot_general [convolution]": 0.040,
        STACK + "lin/in_proj/q/dot_general [convolution fusion]": 0.060,
        "params['layer_*']['lin']['f_b']['kernel'] [data formatting]": 0.005,
        STACK + "attn/mla/attend/flash_group_bwd/pallas_call [custom-call]":
            0.070,
        STACK + "attn/mla/lanes/concatenate [loop fusion]": 0.008,
        STACK + "attn/mla/q_up/dot_general [convolution fusion]": 0.012,
        STACK + "moe/route/dot_general [convolution fusion]": 0.004,
        STACK + "moe/moe_gmm/pallas_call [custom-call]": 0.016,
        STACK + "mlp/up/dot_general [convolution fusion]": 0.090,
        "add [loop fusion]": 0.001}
    trace = {"devices": [{"steps": 5, "op_self_s": ops}]}
    record = record_of(family, cfg)
    ms = lambda s: pytest.approx(1e3 * s / 5)
    assert kda_decay_ms.read(record, trace) == ms(0.010 + 0.020 + 0.030)
    assert kda_intra_ms.read(record, trace) == ms(0.200 + 0.050)
    assert delta_ms.read(record, trace) == ms(0.030 + 0.200 + 0.050 + 0.100
                                              + 0.040)
    assert linattn_ms.read(record, trace) == ms(0.515)
    took = 1e-3 * delta_ms.read(record, trace)
    assert delta_roofline.read(record, trace) == pytest.approx(
        100 * family.delta_cost(cfg, 1)["bytes"] / 819e9 / took)
    assert mla_attn_ms.read(record, trace) == ms(0.070)
    assert gqa_flash_ms.read(record, trace) == ms(0.070)
    assert mla_ms.read(record, trace) == ms(0.008 + 0.012)
    assert mla_attn_roofline.read(record, trace) == pytest.approx(
        100 * family.flash_cost(cfg, 1)["flops"] / 197e12 / 0.014)
    assert moe_ms.read(record, trace) == ms(0.004 + 0.016)
    assert route_ms.read(record, trace) == ms(0.004)
    assert mtp_ms.read(record, trace) is None
    for mod in (kda_decay_ms, kda_intra_ms):
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
            "ms", "linear-attention mixers", "step_ms")


def test_a_decay_a_head_and_a_parent_s_trace_read_nothing(cfg):
    """``olmohybrid_1chip`` prices no per-channel decays, so the two new
    readers stay silent there though its trace holds ``lin/delta/solve``; a
    trace without the scopes (a parent's program) reads nothing in the new
    cell either, and no trace at all nothing anywhere."""
    from benchmark.families import gpt2_lm, olmo_hybrid_lm
    with open(os.path.join(HERE, "configs", "olmo-hybrid-7b.json")) as fh:
        olmo = json.load(fh)
    trace = {"devices": [{"steps": 5, "op_self_s": {
        STACK + "lin/checkpoint/delta/solve/dot_general [convolution]": 0.2,
        STACK + "lin/checkpoint/delta/decay/exp [loop fusion]": 0.1}}]}
    for mod in (kda_decay_ms, kda_intra_ms):
        assert mod.read(record_of(olmo_hybrid_lm, olmo), trace) is None
        assert mod.read(record_of(gpt2_lm, {}), trace) is None
        assert mod.read(record_of(family, cfg), None) is None
        assert mod.read(record_of(family, cfg), {"devices": [
            {"steps": 5, "op_self_s": {"add [loop fusion]": 1.0}}]}) is None
