"""The Olmo-Hybrid family's FLOPs and bytes functions, and the linear
mixers' readers, against shapes enumerated by hand (in
``test_flops_nemotron.py``'s manner)."""

import json
import os

import pytest

from benchmark.families import olmo_hybrid_lm
from benchmark.metrics import delta_ms, delta_roofline, linattn_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCHITECTURES = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "olmo-hybrid-7b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V, FF = 3840, 8192, 12544, 11008
H, DK, DV, K, C = 30, 96, 192, 4, 64                 # the linear mixer
HA, D = 30, 128                                      # attention
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_configuration_is_the_published_one_but_for_the_two_cuts(cfg):
    assert olmo_hybrid_lm.pattern(cfg) == "LLLF"
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["intermediate_size"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["linear_chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (
                d, T, V, FF, H, H, DK, DV, K, C, HA, HA)
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert [(cfg["reduced"][k]["published"], cfg["reduced"][k]["run"])
            for k in ("num_hidden_layers", "vocab_size")] == [
                (32, 4), (100352, 12544)]
    assert len(cfg["layer_types"]) == 32 and cfg["rms_norm_eps"] == 1e-6
    assert olmo_hybrid_lm.grad_leaves(cfg)[0] == (
        "layer_0", "lin", "q", "kernel")
    assert ("layer_3", "attn", "qkv", "kernel") in olmo_hybrid_lm.grad_leaves(
        cfg)
    if os.path.isfile(ARCHITECTURES):
        with open(ARCHITECTURES) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert cfg["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items() if cfg[k] != v)
        assert differs == sorted(cfg["reduced"])


def test_parameter_count_from_the_same_shapes(cfg):
    conv_dim = H * (2 * DK + DV)
    mixer = (2 * d * H * DK + 3 * d * H * DV + 2 * d * H + K * conv_dim
             + 2 * H + DV)
    mlp = 3 * d * FF
    attention = 4 * d * d + 2 * d
    assert (mixer, mlp, attention) == (88_750_332, 126_812_160, 58_990_080)
    linear_layer, full_layer = mixer + mlp + 2 * d, attention + mlp + 2 * d
    assert (linear_layer, full_layer) == (215_570_172, 185_809_920)
    total = 3 * linear_layer + full_layer + 2 * V * d + d
    assert total == 928_862_196                      # ISSUE 32's count
    per_token = sum(k * n * count
                    for _, k, n, count in olmo_hybrid_lm.matmuls(cfg))
    # Weights a token multiplies: everything but the embedding, the norms,
    # the convolution and the mixers' vectors.
    assert per_token == total - V * d - (9 + 2) * d - 3 * (
        K * conv_dim + 2 * H + DV)


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    delta = H * (C * DK / 2 * 2          # K K^T over the causal half
                 + C * DK / 2 * 2        # Q K^T
                 + C * DK / 2 * 2        # T (exp(gamma) K), T triangular
                 + C * DV / 2 * 2        # T V
                 + C * DV / 2 * 2        # tril(Gamma Q K^T) V'
                 + C * C / 3             # T itself, by substitution
                 + 2 * DK * DV * 3)      # W S^T, Q S^T, the state's update
    assert olmo_hybrid_lm.delta_flops_per_token(cfg) == delta
    assert delta == pytest.approx(4.65e6, rel=0.01)
    projections = 2 * d * (2 * H * DK + 3 * H * DV + 2 * H)
    mlp = 2 * 3 * d * FF
    attention = 2 * 4 * d * d
    scores = 2 * 2 * T * d / 2
    head = 2 * d * V
    conv = 2 * K * H * (2 * DK + DV)
    forward = (3 * (projections + delta + conv) + 4 * mlp + attention
               + scores + head)
    assert olmo_hybrid_lm.flops_per_unit(cfg) == pytest.approx(3 * forward)
    # ISSUE 32's shares of the forward pass: 1.84 GFLOP a token; the three
    # linear layers 71% of it, their mixers 30%, the MLPs 55%, full
    # attention 10%, the head 5%.
    assert forward == pytest.approx(1.84e9, rel=0.01)
    assert 3 * (projections + delta + conv) / forward == pytest.approx(
        0.30, abs=0.01)
    assert 3 * (projections + delta + conv + mlp) / forward == pytest.approx(
        0.71, abs=0.01)
    assert 4 * mlp / forward == pytest.approx(0.55, abs=0.01)
    assert (attention + scores) / forward == pytest.approx(0.10, abs=0.01)
    assert head / forward == pytest.approx(0.05, abs=0.01)
    # One step of the cell: 45 TFLOP.
    assert T * 3 * forward == pytest.approx(45.2e12, rel=0.01)


def test_delta_cost_at_the_benchmark_shape(cfg):
    cost = olmo_hybrid_lm.delta_cost(cfg, 1)
    assert cost["flops"] == 3 * 3 * T * olmo_hybrid_lm.delta_flops_per_token(
        cfg)
    inputs = H * ((DK + DK + DV) * 2 + 2 * 4)        # q, k, v; g, beta
    o = H * DV * 2
    assert cost["bytes"] == 3 * T * ((inputs + o) + (inputs + o + inputs))
    assert cost["chunks"] == 3 * T // C
    assert cost["state_bytes"] == 3 * (T // C) * H * DV * DK * 4
    # Byte-bound on a v5e: 2.8 ms a step, against 1.7 by FLOPs.
    assert cost["bytes"] / 819e9 == pytest.approx(2.8e-3, rel=0.02)
    assert cost["flops"] / 197e12 == pytest.approx(1.74e-3, rel=0.02)
    # Twice the batch, twice the cost.
    twice = olmo_hybrid_lm.delta_cost(cfg, 2)
    assert twice["flops"] == 2 * cost["flops"]
    assert twice["bytes"] == 2 * cost["bytes"]


def test_flash_cost_at_the_benchmark_shape(cfg):
    cost = olmo_hybrid_lm.flash_cost(cfg, 1)
    product = 2 * HA * T * T * D / 2                 # one causal product
    assert cost["flops"] == 7 * product
    tensor, stat = T * HA * D * 2, HA * T * 4
    assert cost["bytes"] == 15 * tensor + 5 * stat
    assert cost["calls_per_step"] == 1 and cost["shape"] == [1, T, HA, D]
    # FLOP-bound on a v5e: 9.2 ms a step.
    assert cost["flops"] / 197e12 == pytest.approx(9.16e-3, rel=0.01)
    # The step's only Pallas kernels are flash's, so the cell is read by
    # ``flash_ms`` and not by label.
    from benchmark.metrics import flash_ms, flash_roofline, gqa_flash_ms
    trace = {"devices": [{"steps": 5, "pallas_s": {"fwd": 0.02, "bwd": 0.06},
                          "op_self_s": {}}]}
    record = {"family": olmo_hybrid_lm, "cfg": cfg,
              "job": {"batch_per_chip": 1}, "peaks": PEAKS,
              "program": {"kernels": ["_dkdv_kernel_grouped",
                                      "_dq_kernel_grouped", "_fwd_kernel"]}}
    assert flash_ms.read(record, trace) == pytest.approx(16.0)
    assert flash_roofline.read(record, trace) == pytest.approx(57.2, rel=0.01)
    assert gqa_flash_ms.read(record, trace) is None


def test_linattn_ms_and_delta_ms_take_the_mixer_s_ops_and_no_other():
    solve = ("jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/lin/"
             "checkpoint/delta/solve/dot_general [convolution fusion]")
    for label in (
            solve,
            "transpose(jvp(TransformerLM))/TransformerLM._pattern_stack/"
            "layer_*/lin/in_proj/q/dot_general [convolution fusion]",
            "params['layer_*']['lin']['v']['kernel'] [data formatting]",
            "layer_*/lin/rematted_computation/conv/mul [loop fusion]"):
        assert linattn_ms.in_mixer(label), label
    assert linattn_ms.in_delta(solve)
    assert linattn_ms.in_delta(
        "transpose(jvp(TransformerLM))/layer_*/lin/delta/states/while [while]")
    for label in (
            "jvp(TransformerLM)/layer_*/lin/in_proj/q/dot_general [conv]",
            "jvp(TransformerLM)/layer_*/lin/conv/mul [loop fusion]",
            "jvp(TransformerLM)/layer_*/mlp/delta/mul [loop fusion]"):
        assert not linattn_ms.in_delta(label), label
    for label in (
            "jvp(TransformerLM)/layer_*/mlp/up/dot_general [convolution]",
            "jvp(TransformerLM)/layer_*/attn/pallas_call [custom-call]",
            "jvp(TransformerLM)/layer_*/ssm/scan/intra/mul [loop fusion]",
            "transpose(jvp(xent/grad))/dot_general [convolution fusion]",
            "add [loop fusion]"):
        assert not linattn_ms.in_mixer(label), label


def test_a_record_without_the_layer_reads_nothing(cfg):
    from benchmark.families import gpt2_lm, nemotron_h_lm
    trace = {"devices": [{"steps": 5, "op_self_s": {
        "jvp(TransformerLM)/layer_*/lin/delta/intra/mul [loop fusion]": 0.5,
        "jvp(TransformerLM)/layer_*/lin/conv/mul [loop fusion]": 0.25}}]}
    for family in (gpt2_lm, nemotron_h_lm):
        record = {"family": family, "peaks": None, "cfg": {}, "job": {}}
        assert linattn_ms.read(record, trace) is None
        assert delta_ms.read(record, None) is None
        assert delta_roofline.read(record, trace) is None
    record = {"family": olmo_hybrid_lm, "peaks": None}
    assert linattn_ms.read(record, trace) == 150.0
    assert delta_ms.read(record, trace) == 100.0
    assert delta_roofline.read(record, trace) is None     # no peaks: the CPU
    record = {"family": olmo_hybrid_lm, "peaks": PEAKS, "cfg": cfg,
              "job": {"batch_per_chip": 1}}
    assert delta_roofline.read(record, trace) == pytest.approx(2.8, rel=0.02)
    assert linattn_ms.read(record, {"devices": [
        {"steps": 5, "op_self_s": {"add [loop fusion]": 1.0}}]}) is None


def test_the_three_readers_name_one_layer_and_the_metric_they_move():
    for mod in (linattn_ms, delta_ms, delta_roofline):
        assert (mod.LAYER, mod.MOVES) == ("linear-attention mixers",
                                          "step_ms")
    assert (linattn_ms.UNIT, delta_ms.UNIT, delta_roofline.UNIT) == (
        "ms", "ms", "%")
