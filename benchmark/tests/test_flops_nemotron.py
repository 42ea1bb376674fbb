"""The Nemotron-H family's FLOPs and bytes functions, and the mixers'
readers, against shapes enumerated by hand (in ``test_flops_olmoe.py``'s
manner)."""

import json
import os

import pytest

from benchmark.families import nemotron_h_lm
from benchmark.metrics import ssm_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "nemotron-twotower-30b-a3b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V = 2688, 8192, 16384
H, P, G, N, Q, K = 64, 64, 8, 128, 128, 4           # the mixer
HQ, HKV, D = 32, 2, 128                             # attention
E, HELD, TOP, EH, SH = 128, 8, 6, 1856, 3712        # the experts


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    assert nemotron_h_lm.pattern(cfg) == "MEMEM*EME"
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["chunk_size"], cfg["conv_kernel"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["experts_routed_over"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"]) == (
                d, T, V, H, P, G, N, Q, K, HQ, HKV, D, E, HELD, TOP, EH, SH)
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "n_routed_experts", "vocab_size")] == [
            52, 128, 131072]
    assert nemotron_h_lm.grad_leaves(cfg)[0] == (
        "layer_0", "ssm", "in_proj", "kernel")
    assert ("layer_5", "attn", "kv", "kernel") in nemotron_h_lm.grad_leaves(
        cfg)


def test_parameter_count_from_the_same_shapes(cfg):
    inner, conv_dim = H * P, H * P + 2 * G * N
    mixer = (d * (2 * inner + 2 * G * N + H) + K * conv_dim + conv_dim
             + 3 * H + inner + inner * d + d)
    attention = d * HQ * D + d * 2 * HKV * D + HQ * D * d + d
    experts_outside = d * E + 2 * d * SH + d
    one_expert = 2 * d * EH
    assert (mixer, attention, experts_outside, one_expert) == (
        38_744_896, 23_399_040, 20_302_464, 9_977_856)
    total = (4 * mixer + attention + 4 * (experts_outside + HELD * one_expert)
             + 2 * V * d)
    assert total == 666_960_256                  # ISSUE 30's count
    assert total + d == 666_962_944              # with the final norm
    per_token = sum(k * n * count
                    for _, k, n, count in nemotron_h_lm.matmuls(cfg))
    # Weights a token multiplies: everything but the embedding, the norms,
    # the convolution and the mixers' vectors; of the held experts the
    # share 6 * 8 / 128 of one.
    assert per_token == pytest.approx(
        total - V * d - 9 * d - 4 * (K * conv_dim + conv_dim + 3 * H + inner)
        - 4 * HELD * one_expert + 4 * (TOP * HELD / E) * one_expert)


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    inner = H * P
    scan = ((G * 2 * Q * N + H * 2 * Q * P) / 2      # C B^T, (L o CB^T) x
            + 2 * H * 2 * P * N                      # states in, states out
            + 2 * K * (inner + 2 * G * N))           # the convolution
    assert nemotron_h_lm.scan_flops_per_token(cfg) == scan
    mixer = 2 * d * (2 * inner + 2 * G * N + H) + 2 * inner * d + scan
    attention = (2 * d * HQ * D + 2 * d * 2 * HKV * D + 2 * HQ * D * d
                 + (2 * T * HQ * D + 2 * T * HQ * D) / 2)
    experts = (2 * d * E + 2 * 2 * d * SH
               + (TOP * HELD / E) * 2 * 2 * d * EH)
    head = 2 * d * V
    fwd = 4 * mixer + attention + 4 * experts + head
    assert nemotron_h_lm.flops_per_unit(cfg) == pytest.approx(3 * fwd,
                                                              rel=1e-12)
    # The shares a token, forward (ISSUE 30 gives 82.6, 114, 48 and 88 M;
    # its mixer counts the scan at 5.1 M where the chunked form needs 2.8).
    assert mixer == pytest.approx(80.2e6, rel=2e-3)
    assert attention == pytest.approx(113.9e6, rel=1e-3)
    assert experts == pytest.approx(48.1e6, rel=1e-3)
    assert head == pytest.approx(88.1e6, rel=1e-3)
    assert nemotron_h_lm.flops_per_unit(cfg) == pytest.approx(2.1455e9,
                                                              rel=1e-4)
    assert 4 * mixer / fwd == pytest.approx(0.449, abs=0.002)
    # A step of 2 x 8192 tokens.
    assert 16_384 * nemotron_h_lm.flops_per_unit(cfg) == pytest.approx(
        35.15e12, rel=1e-3)


def test_ssd_cost_at_the_benchmark_shape(cfg):
    cost = nemotron_h_lm.ssd_cost(cfg, 2)
    tokens = 2 * T
    per_token = (G * 2 * Q * N + H * 2 * Q * P) / 2 + 2 * H * 2 * P * N
    assert cost["flops"] == 3 * 4 * tokens * per_token
    # Forward: x (H P), B, C (G N each) in bf16 and dt (H) in f32 in, y
    # out; backward: the same and dy in, four gradients out.
    inputs = (H * P + 2 * G * N) * 2 + H * 4
    assert cost["bytes"] == 4 * tokens * (
        (inputs + H * P * 2) + (inputs + H * P * 2 + inputs))
    assert cost["chunks"] == 4 * 2 * (T // Q) == 512
    assert cost["state_bytes"] == 512 * H * P * N * 4
    # Byte-bound on a v5e: 4.3 ms a step by bytes, 2.7 by FLOPs.
    assert cost["bytes"] / 819e9 == pytest.approx(4.3e-3, rel=0.02)
    assert cost["flops"] / 197e12 == pytest.approx(2.7e-3, rel=0.03)


def test_moe_cost_at_the_benchmark_shape(cfg):
    cost = nemotron_h_lm.moe_cost(cfg, 2)
    tokens = 2 * T
    A = tokens * TOP * HELD / E
    assert cost["assignments"] == tokens * TOP == 98_304
    assert cost["held_assignments"] == A == 6_144     # 768 an expert
    assert cost["expert_parameters"] == 4 * (2 * HELD * d * EH + 2 * d * SH)
    assert cost["flops"] == 4 * 6 * (tokens * d * E + 2 * A * d * EH
                                     + 2 * tokens * d * SH)

    def matmul(rows, k, n, weights):
        return 3 * rows * (k + n) * 2 + 2 * weights * 2 + weights * 4

    assert cost["bytes"] == 4 * (2 * matmul(A, d, EH, HELD * d * EH)
                                 + 2 * matmul(tokens, d, SH, d * SH))
    # FLOP-bound: 48.0 ms a step, the shared expert 39.8 of them.
    assert cost["flops"] / 197e12 == pytest.approx(48.0e-3, rel=0.01)
    assert cost["bytes"] / 819e9 < 0.5 * cost["flops"] / 197e12
    assert 4 * 6 * 2 * tokens * d * SH / 197e12 == pytest.approx(39.8e-3,
                                                                 rel=0.01)


def test_flash_cost_at_the_benchmark_shape(cfg):
    """Grouped KV heads save bytes and no FLOP: every query head has its
    own (T, T) scores; k, v, dk and dv move at their two heads."""
    cost = nemotron_h_lm.flash_cost(cfg, 2)
    product = 2 * 2 * HQ * T * T * D / 2             # one causal product
    assert cost["flops"] == 7 * product
    q, kv, stat = 2 * T * HQ * D * 2, 2 * T * HKV * D * 2, 2 * HQ * T * 4
    assert cost["bytes"] == ((2 * q + 2 * kv + stat)
                             + (3 * q + 2 * kv + 2 * stat)
                             + (2 * q + 4 * kv + 2 * stat))
    assert cost["calls_per_step"] == 1 and cost["shape"] == [2, T, HQ, HKV, D]
    # FLOP-bound on a v5e: 19.5 ms a step, against 1.2 by bytes.
    assert cost["flops"] / 197e12 == pytest.approx(19.5e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(1.2e-3, rel=0.05)
    # The cell's readers find the kernels by their label, and leave the
    # grouped matmuls' custom calls and the gpt cells (``flash_ms``'s) alone.
    from benchmark.families import gpt2_lm
    from benchmark.metrics import flash_ms, gqa_flash_ms, gqa_flash_roofline
    stack = "TransformerLM._pattern_stack/layer_*/attn/pallas_call"
    trace = {"devices": [{"steps": 4, "pallas_s": {"fwd": 1.0, "bwd": 1.0},
                          "op_self_s": {
        f"jvp(TransformerLM)/{stack} [custom-call]": 0.04,
        f"transpose(jvp(TransformerLM))/{stack} [custom-call]": 0.12,
        "ragged-dot-none [custom-call]": 0.4,
        "jvp(TransformerLM)/layer_*/attn/q/dot_general [convolution]": 0.02,
    }}]}
    record = {"family": nemotron_h_lm, "cfg": cfg,
              "job": {"batch_per_chip": 2},
              "program": {"kernels": ["_dkdv_kernel", "_dq_kernel",
                                      "_fwd_kernel"]},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    assert gqa_flash_ms.read(record, trace) == pytest.approx(40.0)
    assert gqa_flash_roofline.read(record, trace) == pytest.approx(48.8,
                                                                   rel=0.01)
    assert flash_ms.read(record, trace) is None
    assert gqa_flash_ms.read({**record, "family": gpt2_lm}, trace) is None
    assert gqa_flash_ms.read(record, None) is None
    assert gqa_flash_roofline.read({**record, "peaks": None}, trace) is None


def test_ssm_ms_and_ssd_ms_take_the_mixer_s_ops_and_no_other():
    scan = ("jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/ssm/"
            "checkpoint/scan/intra/mul [loop fusion]")
    for label in (
            scan,
            "transpose(jvp(TransformerLM))/TransformerLM._pattern_stack/"
            "layer_*/ssm/in_proj/dot_general [convolution fusion]",
            "params['layer_*']['ssm']['in_proj']['kernel'] [data formatting]",
            "layer_*/ssm/rematted_computation/conv/mul [loop fusion]"):
        assert ssm_ms.in_mixer(label), label
    assert ssm_ms.in_scan(scan)
    for label in (
            "jvp(TransformerLM)/layer_*/ssm/in_proj/dot_general [convolution]",
            "jvp(TransformerLM)/layer_*/ssm/conv/mul [loop fusion]",
            "jvp(TransformerLM)/layer_*/moe/scan/while [while]"):
        assert not ssm_ms.in_scan(label), label
    for label in (
            "jvp(TransformerLM)/layer_*/moe/shared/dot_general [convolution]",
            "jvp(TransformerLM)/layer_*/attn/pallas_call [custom-call]",
            "transpose(jvp(xent/grad))/dot_general [convolution fusion]",
            "add [loop fusion]"):
        assert not ssm_ms.in_mixer(label), label


def test_a_record_without_the_layer_reads_nothing():
    from benchmark.families import gpt2_lm
    from benchmark.metrics import ssd_ms, ssd_roofline
    trace = {"devices": [{"steps": 5, "op_self_s": {
        "jvp(TransformerLM)/layer_*/ssm/scan/intra/mul [loop fusion]": 0.5}}]}
    record = {"family": gpt2_lm, "peaks": None, "cfg": {}, "job": {}}
    assert ssm_ms.read(record, trace) is None
    assert ssd_ms.read(record, None) is None
    assert ssd_roofline.read(record, trace) is None
    record = {"family": nemotron_h_lm, "peaks": None}
    assert ssm_ms.read(record, trace) == ssd_ms.read(record, trace) == 100.0
    assert ssd_roofline.read(record, trace) is None       # no peaks: the CPU
    assert ssm_ms.read(record, {"devices": [
        {"steps": 5, "op_self_s": {"add [loop fusion]": 1.0}}]}) is None
