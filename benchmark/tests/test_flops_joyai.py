"""The JoyAI-LLM-Flash family's FLOPs and bytes functions against shapes
enumerated by hand and against the ``dot_general``s of the plain reference's
own jaxpr, and the readers of the three metrics the configuration brings on
a hand-made trace."""

import json
import os

import numpy as np
import pytest

from benchmark.families import joyai_flash_lm as family
from benchmark.metrics import (gqa_flash_ms, mla_attn_ms, mla_attn_roofline,
                               mla_ms, moe_ms, mtp_ms, route_ms)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "joyai-llm-flash.json")) as fh:
        return json.load(fh)


# The published widths, and the cut: the dense layer and four expert layers,
# 16 of 256 experts, an eighth of the vocabulary.
d, T, V, H = 2048, 8192, 16160, 32
QL, KVL, NOPE, ROPE, VD = 1536, 512, 128, 64, 128
DENSE, EH, E, HELD, TOP = 7168, 768, 256, 16, 8
LA, LX = 5 + 1, 4 + 1          # layers a step: the stack's + the module's
LATENT = (d * QL + QL * H * 192 + d * (KVL + ROPE) + KVL * H * 256
          + H * VD * d)


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    assert family.pattern(cfg) == "dxxxx" and family.mtp_pattern(cfg) == "x"
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["experts_routed_over"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
                d, T, V, H, H, QL, KVL, NOPE, ROPE, VD, DENSE, EH, E, HELD,
                TOP, 2.5, 32_000_000, 1e-6)
    assert [(k, cfg["reduced"][k]["published"], cfg["reduced"][k]["run"])
            for k in cfg["reduced"]] == [
        ("num_hidden_layers", 40, 5), ("n_routed_experts", 256, 16),
        ("vocab_size", 129280, 16160)]
    assert all(cfg[k] == cfg["reduced"][k]["run"] for k in cfg["reduced"])
    # Every number of the catalog's copy but the three cuts.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == set(
            cfg["reduced"])
    leaves = family.grad_leaves(cfg)
    assert ("layer_0", "attn", "kv_a", "kernel") in leaves
    assert ("layer_4", "attn", "q_b", "kernel") in leaves
    assert ("layer_1", "moe", "w_gate") in leaves
    assert not any(path[-2:] == ("moe", "w_gate") and path[0] != "layer_1"
                   for path in leaves)
    assert family.host_batch(cfg, np.random.default_rng(0),
                             2).shape == (2, T + 2)


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    assert LATENT == 26_345_472        # a layer's five projections
    attention = 2 * LATENT + (2 * T * H * 192 + 2 * T * H * VD) / 2
    dense = 2 * 3 * d * DENSE
    held = TOP * HELD / E                            # 0.5 of a token
    router, shared, routed = 2 * d * E, 2 * 3 * d * EH, held * 2 * 3 * d * EH
    experts = router + shared + routed
    head, eh_proj = 2 * d * V, 2 * 2 * d * d
    fwd = LA * attention + dense + LX * experts + eh_proj + 2 * head
    assert family.flops_per_unit(cfg) == pytest.approx(3 * fwd, rel=1e-12)
    # A step of 16,384 positions, TFLOP, forward and backward: the kernels'
    # two products forward and four backward (the kernels' own second pass
    # over the scores is recomputation: ISSUE 50 counted it, 29.7), the
    # latent projections 15.5, the two head passes 6.5, the dense SwiGLU
    # 4.3, router + shared + held experts 3.7, eh_proj 0.8.
    step = 2 * T * 3 / 1e12
    parts = [LA * (2 * T * H * 320) / 2, LA * 2 * LATENT, 2 * head, dense,
             LX * experts, eh_proj]
    assert [round(step * x, 1) for x in parts] == [24.7, 15.5, 6.5, 4.3, 3.7,
                                                   0.8]
    assert step * fwd == pytest.approx(55.68, abs=0.01)
    # Latent attention is 72% of the model's FLOPs: kernels 44, projections
    # 28.
    assert (parts[0] + parts[1]) / fwd == pytest.approx(0.724, abs=0.002)


def test_flash_and_mla_cost_at_the_benchmark_shape(cfg):
    cost = family.flash_cost(cfg, 2)
    assert cost["flops"] == LA * 2 * T * T * H * (320 + 832)
    assert cost["flops"] == pytest.approx(29.7e12, rel=2e-3)
    qk, v, stat = 2 * T * H * 192 * 2, 2 * T * H * VD * 2, 2 * H * T * 4
    assert cost["bytes"] == LA * ((2 * qk + 2 * v + stat)
                                  + (4 * qk + 4 * v + 2 * stat))
    assert cost["shape"] == [2, T, H, 192, VD] and cost["calls_per_step"] == 6
    # FLOP-bound on a v5e: 150.6 ms a step in six layers.
    assert cost["flops"] / 197e12 == pytest.approx(150.6e-3, rel=2e-3)
    assert cost["bytes"] / 819e9 < 0.1 * cost["flops"] / 197e12
    mla = family.mla_cost(cfg, 2)
    assert mla["flops"] == LA * 6 * 2 * T * LATENT
    assert mla["flops"] == pytest.approx(15.5e12, rel=5e-3)
    assert mla["recomputed_flops"] == LA * 2 * 2 * T * (QL * H * 192
                                                        + KVL * H * 256)
    assert mla["pass_bytes"] == LA * 2 * T * 5 * 2 * (QL + KVL + 33 * ROPE)
    assert mla["bytes"] > mla["pass_bytes"]


def test_moe_cost_at_the_benchmark_shape(cfg):
    cost = family.moe_cost(cfg, 2)
    A = 2 * T * TOP * HELD / E
    assert cost["assignments"] == 2 * T * TOP == 131_072
    assert cost["held_assignments"] == A == 8_192       # 512 an expert
    assert cost["router_flops"] == LX * 6 * 2 * T * d * E
    assert cost["flops"] == LX * 6 * (2 * T * d * E + 3 * A * d * EH
                                      + 3 * 2 * T * d * EH)

    def matmul(rows, k, n, weights):
        return 3 * rows * (k + n) * 2 + 2 * weights * 2 + weights * 4

    assert cost["bytes"] == LX * 3 * (matmul(A, d, EH, HELD * d * EH)
                                      + matmul(2 * T, d, EH, d * EH))
    assert cost["expert_parameters"] == LX * 3 * d * EH * (HELD + 1)
    # FLOP-bound: 18.9 ms a step in five layers at the uniform load.
    assert cost["flops"] / 197e12 == pytest.approx(18.9e-3, rel=0.01)


# ------------------------------ against the plain reference's own jaxpr


def dot_flops(jaxpr, times=1):
    """FLOPs of every ``dot_general`` of a jaxpr, through its sub-jaxprs,
    a scan's body counted once a trip."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            contracted = int(np.prod([lhs[i] for i in lc]))
            total += times * 2 * contracted * int(np.prod(
                [n for i, n in enumerate(lhs) if i not in lc])) * int(
                np.prod([n for i, n in enumerate(rhs)
                         if i not in rc and i not in eqn.params[
                             "dimension_numbers"][1][1]]))
        trips = eqn.params.get("length", 1) if (
            eqn.primitive.name == "scan") else 1
        for sub in eqn.params.values():
            for inner in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += dot_flops(inner, times * trips)
    return total


def test_the_cost_functions_count_the_reference_s_own_products():
    """The forward pass of the plain reference at the tiny preset: every
    ``dot_general`` of its jaxpr, summed, is what :func:`family.matmuls`
    and the attention term of ``flops_per_unit`` give once they count as
    the reference computes — every held expert on EVERY token (the model
    counts the uniform share) and the whole (T, T) square (the model counts
    the causal half)."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(HERE, "configs", "joyai-llm-flash.json")) as fh:
        tiny = {**json.load(fh), **family.TINY}
    B, Tt = 2, tiny["sequence_length"]
    params, aux = jax.eval_shape(lambda k: family.init(tiny, k),
                                 jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, Tt + 2), jnp.int32)
    theirs = jnp.zeros((B, 3, Tt, tiny["num_experts_per_tok"]), jnp.int32)
    jaxpr = jax.make_jaxpr(family.reference_given_choices(tiny))(
        params, aux, tokens, theirs, 0.0)
    counted = dot_flops(jaxpr.jaxpr)
    s = family._sizes(tiny)
    held_everywhere = tiny["n_routed_experts"] / family.held_share(tiny)
    per_token = sum(2 * k * n * count * (held_everywhere if name == "held"
                                         else 1)
                    for name, k, n, count in family.matmuls(tiny))
    square = s["layers"]["attn"] * 2 * Tt * s["H"] * (s["qk"] + s["v"])
    assert counted == B * Tt * (per_token + square)
    # ... and flops_per_unit is that at the share and the half, x 3.
    model = sum(2 * k * n * count
                for _, k, n, count in family.matmuls(tiny)) + square / 2
    assert family.flops_per_unit(tiny) == pytest.approx(3 * model)


# ------------------------------------------- the three metrics' readers

STACK = "TransformerLM._pattern_stack"
BWD = f"transpose(jvp(TransformerLM))/{STACK}/layer_*/attn/jvp(TransformerLM)"
OPS = {
    f"jvp(TransformerLM)/{STACK}/layer_*/attn/mla/q_down/q_a/dot_general "
    "[convolution fusion]": 0.010,
    f"jvp(TransformerLM)/{STACK}/layer_*/attn/mla/norm/q_norm/mul "
    "[loop fusion]": 0.002,
    f"jvp(TransformerLM)/{STACK}/layer_*/attn/mla/q_up/dot_general "
    "[convolution fusion]": 0.020,
    f"{BWD}/{STACK}/layer_*/attn/checkpoint/mla/kv_up/dot_general "
    "[convolution fusion]": 0.030,
    f"{BWD}/{STACK}/layer_*/attn/checkpoint/mla/rope/concatenate "
    "[loop fusion]": 0.004,
    f"jvp(TransformerLM)/{STACK}/layer_*/attn/mla/attend/pallas_call "
    "[custom-call]": 0.100,
    f"{BWD}/{STACK}/layer_*/attn/checkpoint/mla/attend/pallas_call "
    "[custom-call]": 0.200,
    f"jvp(TransformerLM)/{STACK}/layer_*/attn/mla/attend/reduce_precision "
    "[data formatting]": 0.001,
    f"transpose(jvp(TransformerLM))/{STACK}/layer_*/attn/mla/out/proj/"
    "dot_general [convolution fusion]": 0.040,
    "params['layer_*']['attn']['q_b']['kernel'] [data formatting]": 0.003,
    f"jvp(TransformerLM)/{STACK}/mtp/layer_*/attn/mla/attend/pallas_call "
    "[custom-call]": 0.050,
    f"jvp(TransformerLM)/{STACK}/mtp/layer_*/attn/mla/kv_down/kv_a/"
    "dot_general [convolution fusion]": 0.006,
    f"jvp(TransformerLM)/{STACK}/layer_*/moe/route/router/dot_general "
    "[convolution fusion]": 0.010,
    f"jvp(TransformerLM)/{STACK}/layer_*/moe/route/bias_update/sign "
    "[loop fusion]": 0.002,
    f"jvp(TransformerLM)/{STACK}/layer_*/mlp/gate/dot_general "
    "[convolution fusion]": 0.070,
    "params['layer_*']['mlp']['gate']['kernel'] [data formatting]": 0.005,
    "xent/loss/dot_general [convolution fusion]": 0.030,
}


def test_the_three_readers_take_their_ops_and_no_other(cfg):
    trace = {"devices": [{"steps": 2, "op_self_s": OPS}]}
    record = {"family": family, "cfg": cfg, "job": {"batch_per_chip": 2},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    assert mla_ms.read(record, trace) == pytest.approx(
        1e3 * (0.010 + 0.002 + 0.020 + 0.030 + 0.004 + 0.040 + 0.003 + 0.006)
        / 2)
    assert mla_attn_ms.read(record, trace) == pytest.approx(
        1e3 * (0.100 + 0.200 + 0.050) / 2)
    # The family has a flash_cost and names no FLASH_KERNELS: the kernels
    # read under the accepted name too, the same number.
    assert gqa_flash_ms.read(record, trace) == mla_attn_ms.read(record, trace)
    # 150.6 ms of roofline over 175 ms read.
    assert mla_attn_roofline.read(record, trace) == pytest.approx(
        100 * 150.6 / 175.0, rel=3e-3)
    assert route_ms.read(record, trace) == pytest.approx(6.0)
    assert moe_ms.read(record, trace) == pytest.approx(6.0)
    assert mtp_ms.read(record, trace) == pytest.approx(
        1e3 * (0.050 + 0.006) / 2)
    assert [mla_ms.latent_part(label) for label in list(OPS)[:3]] == [
        "q_down", "norm", "q_up"]
    for reader in (mla_ms, mla_attn_ms, mla_attn_roofline):
        assert reader.read(record, None) is None
        nothing = {"devices": [{"steps": 2, "op_self_s": {
            "xent/loss/dot_general [convolution fusion]": 1.0}}]}
        assert reader.read(record, nothing) is None
    assert mla_attn_roofline.read({**record, "peaks": None}, trace) is None


def test_families_without_latent_attention_read_nothing(cfg):
    """A family that prices no latent attention (no ``mla_cost``) reads
    nothing, whatever the trace holds: the other cells' lines do not grow,
    and the parent's program gives no number and raises nothing."""
    from benchmark.families import gpt2_lm, nemotron3_super_lm
    trace = {"devices": [{"steps": 2, "op_self_s": OPS}]}
    for other in (gpt2_lm, nemotron3_super_lm):
        record = {"family": other, "cfg": {}, "job": {}, "peaks": None}
        for reader in (mla_ms, mla_attn_ms, mla_attn_roofline):
            assert reader.read(record, trace) is None
