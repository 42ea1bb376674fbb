"""The Nemotron-3-Super family's FLOPs and bytes functions, and the readers
of the two metrics it brings, against shapes enumerated by hand (in
``test_flops_nemotron.py``'s manner)."""

import json
import os

import pytest

from benchmark.families import nemotron3_super_lm as family
from benchmark.metrics import (latent_ms, moe_ms, moe_roofline, mtp_ms,
                               route_ms)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "nemotron-3-super-120b-a12b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut: one rank's group of a mixer and its
# heads of attention, 8 of 512 experts, an eighth of the vocabulary.
d, T, V = 4096, 8192, 16384
H, P, G, N, Q, K = 16, 64, 1, 128, 128, 4           # the mixer's share
HQ, HKV, D = 4, 1, 128                              # attention's share
E, HELD, TOP, EH, SH, LAT = 512, 8, 22, 2688, 5376, 1024
LM, LA, LE = 5, 1 + 1, 5 + 1       # layers a step: the stack's + the module's


def test_the_configuration_is_the_published_one_but_for_the_seven_cuts(cfg):
    assert family.pattern(cfg) == "MEMEMEM*EME"
    assert family.mtp_pattern(cfg) == "*E"
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["chunk_size"], cfg["conv_kernel"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["experts_routed_over"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["moe_latent_size"]) == (
                d, T, V, H, P, G, N, Q, K, HQ, HKV, D, E, HELD, TOP, EH, SH,
                LAT)
    assert [(k, cfg["reduced"][k]["published"], cfg["reduced"][k]["run"])
            for k in cfg["reduced"]] == [
        ("num_hidden_layers", 88, 11), ("mamba_num_heads", 128, 16),
        ("n_groups", 8, 1), ("num_attention_heads", 32, 4),
        ("num_key_value_heads", 2, 1), ("n_routed_experts", 512, 8),
        ("vocab_size", 131072, 16384)]
    assert all(cfg[k] == cfg["reduced"][k]["run"] for k in cfg["reduced"])
    leaves = family.grad_leaves(cfg)
    assert leaves[0] == ("layer_0", "ssm", "in_proj", "kernel")
    assert ("layer_7", "attn", "kv", "kernel") in leaves
    # The routed leaves are the first expert layer's.
    assert ("layer_1", "moe", "w_up") in leaves
    assert not any(path[-2:] == ("moe", "w_up") and path[0] != "layer_1"
                   for path in leaves)
    assert ("mtp", "layer_1", "moe", "shared", "w_down") in leaves
    assert ("mtp", "layer_0", "attn", "q", "kernel") in leaves
    assert family.host_batch(cfg, __import__("numpy").random.default_rng(0),
                             1).shape == (1, T + 2)


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    inner = H * P
    scan = ((G * 2 * Q * N + H * 2 * Q * P) / 2      # C B^T, (L o CB^T) x
            + 2 * H * 2 * P * N                      # states in, states out
            + 2 * K * (inner + 2 * G * N))           # the convolution
    assert family.scan_flops_per_token(cfg) == scan
    mixer = 2 * d * (2 * inner + 2 * G * N + H) + 2 * inner * d + scan
    attention = (2 * d * HQ * D + 2 * d * 2 * HKV * D + 2 * HQ * D * d
                 + (2 * T * HQ * D + 2 * T * HQ * D) / 2)
    held = TOP * HELD / E                            # 0.34375 of a token
    router, latent = 2 * d * E, 2 * 2 * d * LAT
    shared, routed = 2 * 2 * d * SH, held * 2 * 2 * LAT * EH
    experts = router + latent + shared + routed
    head, eh_proj = 2 * d * V, 2 * 2 * d * d
    module = eh_proj + attention + experts + head
    fwd = 5 * mixer + attention + 5 * experts + head + module
    assert family.flops_per_unit(cfg) == pytest.approx(3 * fwd, rel=1e-12)
    # The shares a token, forward, in MFLOP (ISSUE 46: 4.2, 16.8, 88.1,
    # 3.8; 564, 333, 141, 134, 19 of 1,191).
    assert [round(x / 1e6, 1) for x in (router, latent, shared, routed)] == [
        4.2, 16.8, 88.1, 3.8]
    assert [round(x / 1e6) for x in (5 * experts, module, 5 * mixer, head,
                                     attention, fwd)] == [
        564, 333, 140, 134, 19, 1191]          # the mixers: 140.5
    assert round(eh_proj / 1e6) == 67
    # The layers that hold the two mechanisms, and the new arithmetic
    # itself: latent projections, routing, held latent experts, the module.
    assert (5 * experts + module) / fwd == pytest.approx(0.753, abs=0.002)
    assert (5 * (router + latent + routed) + module) / fwd == pytest.approx(
        0.384, abs=0.002)
    # A step of 8,192 positions.
    assert T * family.flops_per_unit(cfg) == pytest.approx(29.26e12,
                                                           rel=1e-3)


def test_ssd_and_pass_cost_at_the_benchmark_shape(cfg):
    cost = family.ssd_cost(cfg, 1)
    per_token = (G * 2 * Q * N + H * 2 * Q * P) / 2 + 2 * H * 2 * P * N
    assert cost["flops"] == 3 * LM * T * per_token
    inputs = (H * P + 2 * G * N) * 2 + H * 4
    assert cost["bytes"] == LM * T * (
        (inputs + H * P * 2) + (inputs + H * P * 2 + inputs))
    assert cost["chunks"] == LM * (T // Q) == 320
    assert cost["state_bytes"] == 320 * H * P * N * 4
    # Byte-bound on a v5e: 0.60 ms a step by bytes, 0.42 by FLOPs.
    assert cost["bytes"] / 819e9 == pytest.approx(0.60e-3, rel=0.02)
    assert cost["flops"] / 197e12 == pytest.approx(0.42e-3, rel=0.03)
    passes = family.pass_cost(cfg, 1)
    conv_dim, inner = H * P + 2 * G * N, H * P
    assert passes["bytes_per_token"] == (5 * conv_dim + 8 * inner) * 2 == 29_184
    assert passes["bytes"] == LM * T * 29_184
    assert passes["bytes"] / 819e9 == pytest.approx(1.46e-3, rel=0.01)


def test_moe_cost_at_the_benchmark_shape(cfg):
    cost = family.moe_cost(cfg, 1)
    A = T * TOP * HELD / E
    assert cost["assignments"] == T * TOP == 180_224
    assert cost["held_assignments"] == A == 2_816      # 352 an expert
    assert cost["router_flops"] == LE * 6 * T * d * E
    assert cost["latent_flops"] == LE * 6 * 2 * T * d * LAT
    assert cost["flops"] == LE * 6 * (T * d * E + 2 * T * d * LAT
                                      + 2 * A * LAT * EH + 2 * T * d * SH)

    def matmul(rows, k, n, weights):
        return 3 * rows * (k + n) * 2 + 2 * weights * 2 + weights * 4

    assert cost["bytes"] == LE * (2 * matmul(T, d, LAT, d * LAT)
                                  + 2 * matmul(A, LAT, EH, HELD * LAT * EH)
                                  + 2 * matmul(T, d, SH, d * SH))
    assert cost["expert_parameters"] == LE * (
        2 * HELD * LAT * EH + 2 * d * LAT + 2 * d * SH)
    # FLOP-bound: 84.5 ms a step in six layers, the shared expert 65.9 of
    # them, the latent projections 12.6, the router 3.1, the held experts
    # 2.8 (at the uniform load; a layer runs ceil(landed / W) windows of
    # W = 5,632 rows, twice that load: moe._window_plan).
    assert cost["flops"] / 197e12 == pytest.approx(84.5e-3, rel=0.01)
    assert cost["bytes"] / 819e9 < 0.5 * cost["flops"] / 197e12
    assert cost["latent_flops"] / 197e12 == pytest.approx(12.6e-3, rel=0.01)
    assert cost["router_flops"] / 197e12 == pytest.approx(3.1e-3, rel=0.02)


def test_flash_cost_at_the_benchmark_shape(cfg):
    cost = family.flash_cost(cfg, 1)
    product = 2 * HQ * T * T * D / 2
    assert cost["flops"] == LA * 7 * product
    q, kv, stat = T * HQ * D * 2, T * HKV * D * 2, HQ * T * 4
    assert cost["bytes"] == LA * ((2 * q + 2 * kv + stat)
                                  + (3 * q + 2 * kv + 2 * stat)
                                  + (2 * q + 4 * kv + 2 * stat))
    assert cost["calls_per_step"] == 2 and cost["shape"] == [1, T, HQ, HKV, D]
    # FLOP-bound on a v5e: 2.44 ms a step in the two layers.
    assert cost["flops"] / 197e12 == pytest.approx(2.44e-3, rel=0.01)


STACK = "TransformerLM._pattern_stack"
OPS = {
    f"jvp(TransformerLM)/{STACK}/layer_*/moe/route/router/dot_general "
    "[convolution fusion]": 0.010,
    f"jvp(TransformerLM)/{STACK}/layer_*/moe/latent_down/dot_general "
    "[convolution fusion]": 0.020,
    f"transpose(jvp(TransformerLM))/{STACK}/layer_*/moe/latent_up/"
    "dot_general [convolution fusion]": 0.030,
    "params['layer_*']['moe']['latent_up']['kernel'] [data formatting]":
        0.002,
    f"jvp(TransformerLM)/{STACK}/layer_*/moe/shared/dot_general "
    "[convolution fusion]": 0.200,
    f"jvp(TransformerLM)/{STACK}/mtp/layer_*/moe/latent_down/dot_general "
    "[convolution fusion]": 0.004,
    f"jvp(TransformerLM)/{STACK}/mtp/eh_proj/dot_general "
    "[convolution fusion]": 0.040,
    f"transpose(jvp(TransformerLM))/{STACK}/mtp/layer_*/attn/pallas_call "
    "[custom-call]": 0.008,
    "params['mtp']['eh_proj']['kernel'] [data formatting]": 0.001,
    "jvp(mtp)/xent/loss/dot_general [convolution fusion]": 0.030,
    "transpose(jvp(mtp))/xent/grad/dot_general [convolution fusion]": 0.060,
    "xent/loss/dot_general [convolution fusion]": 0.030,
    "transpose(jvp())/xent/grad/dot_general [convolution fusion]": 0.060,
    f"jvp(TransformerLM)/{STACK}/layer_*/ssm/in_proj/dot_general "
    "[convolution fusion]": 0.050,
}


def test_latent_ms_and_mtp_ms_take_their_ops_and_no_other(cfg):
    trace = {"devices": [{"steps": 2, "op_self_s": OPS}]}
    record = {"family": family, "cfg": cfg, "job": {"batch_per_chip": 1},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    assert latent_ms.read(record, trace) == pytest.approx(
        1e3 * (0.020 + 0.030 + 0.002 + 0.004) / 2)
    assert mtp_ms.read(record, trace) == pytest.approx(
        1e3 * (0.004 + 0.040 + 0.008 + 0.001 + 0.030 + 0.060) / 2)
    assert route_ms.read(record, trace) == pytest.approx(5.0)
    assert moe_ms.read(record, trace) == pytest.approx(
        1e3 * (0.010 + 0.020 + 0.030 + 0.002 + 0.200 + 0.004) / 2)
    # 84.5 ms of roofline over 133 ms read.
    assert moe_roofline.read(record, trace) == pytest.approx(
        100 * 84.5 / 133.0, rel=0.01)
    assert latent_ms.read(record, None) is None
    assert mtp_ms.read(record, None) is None
    nothing = {"devices": [{"steps": 2, "op_self_s": {
        "xent/loss/dot_general [convolution fusion]": 1.0}}]}
    assert latent_ms.read(record, nothing) is None
    assert mtp_ms.read(record, nothing) is None


def test_families_without_the_mechanisms_read_nothing(cfg):
    """A family that prices no latent (``moe_cost`` without
    ``latent_flops``) or has no prediction module reads nothing, whatever
    the trace holds: the other cells' lines do not grow."""
    from benchmark.families import gpt2_lm, nemotron_h_lm
    trace = {"devices": [{"steps": 2, "op_self_s": OPS}]}
    with open(os.path.join(HERE, "configs",
                           "nemotron-twotower-30b-a3b.json")) as fh:
        twotower = json.load(fh)
    record = {"family": nemotron_h_lm, "cfg": twotower,
              "job": {"batch_per_chip": 2}, "peaks": None}
    assert latent_ms.read(record, trace) is None
    assert mtp_ms.read(record, trace) is None
    assert route_ms.read(record, trace) is None
    record = {"family": gpt2_lm, "cfg": {}, "job": {}, "peaks": None}
    assert latent_ms.read(record, trace) is None
    assert mtp_ms.read(record, trace) is None
