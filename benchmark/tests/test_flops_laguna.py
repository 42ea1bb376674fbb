"""The Laguna family's FLOPs and bytes functions against a hand count (ISSUE
58's numbers), the live-pair formula against a brute-force count of the
boolean masks, the catalog's published keys, and the new readers' labels."""

import json
import os

import numpy as np
import pytest

from benchmark.families import laguna_lm
from benchmark.metrics import (attn_gate_ms, gqa_flash_ms, moe_ms, route_ms,
                               win_flash_ms, win_flash_roofline)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "laguna-xs.2.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V, L = 2048, 8192, 12544, 5
HG, HW, HKV, D, W = 48, 64, 8, 128, 512
E, HELD, TOP, EH, SH, DENSE = 256, 32, 8, 512, 512, 8192
CAUSAL = T * (T + 1) // 2
WINDOW = W * (W + 1) // 2 + (T - W) * W
MEGA = 1e6


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    assert laguna_lm.pattern(cfg) == "SDWEWEWESE"
    assert laguna_lm.heads(cfg) == {"S": HG, "W": HW}
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["experts_routed_over"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["intermediate_size"],
            cfg["sliding_window"], cfg["moe_routed_scaling_factor"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"]) == (
                d, T, V, L, HG, HKV, D, E, HELD, TOP, EH, SH, DENSE, W, 2.5,
                1e-6, 262144)
    assert cfg["gating"] is True and "gate_form" not in cfg
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            40, 256, 100352]
    assert all(cfg["reduced"][k]["run"] == cfg[k] for k in cfg["reduced"])
    # Never under the guide's floors: 4 layers behind the dense one, a whole
    # period, 8 experts, an eighth of the vocabulary.
    assert L >= 5 and HELD >= 8 and V * 8 == 100352
    for key in ("assumed", "departures"):
        assert cfg[key] and all("TBD" not in line for line in cfg[key])
    assert "TBD" not in json.dumps(cfg)
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Laguna-XS.2")
        assert row["source_url"] == cfg["source"]
        differs = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differs == set(cfg["reduced"])


def test_the_parameters_are_the_published_count(cfg):
    """The whole model by the family's own matmuls is the published
    "33.4B" with the gate a value a head (the sibling Laguna-S-2.1's
    ``gating: "per-head"``); a gate a value a channel (``W_g`` 2048 -> ``H_l``
    x 128) would be 629 M in the 4.9 M's place and read 34.1 B.  The cut is
    691.6 M."""
    whole = {**cfg, "num_hidden_layers": 40, "num_experts": 256,
             "vocab_size": 100352}

    def parameters(c):
        held = c["num_experts"] / laguna_lm.held_share(c)     # matmuls count
        total = sum(k * n * count * (held if name == "experts" else 1)
                    for name, k, n, count in laguna_lm.matmuls(c))
        return total + c["vocab_size"] * c["hidden_size"]     # the embedding

    assert round(parameters(whole) / 1e9, 2) == 33.44
    a_channel = d * (D - 1) * (10 * HG + 30 * HW)
    assert round(a_channel / 1e6) == 624 and round(
        (parameters(whole) + a_channel) / 1e9, 1) == 34.1
    norms = (2 * L + 1) * d
    assert parameters(cfg) + norms == 691_623_936


@pytest.mark.parametrize("t,w", [(8, 4), (16, 16), (24, 1), (64, 21)])
def test_live_pairs_against_the_boolean_mask(t, w):
    """``j <= i`` and ``0 <= i - j < W``, row by row, counted."""
    apart = np.arange(t)[:, None] - np.arange(t)[None, :]
    assert laguna_lm.live_pairs(t) == int((apart >= 0).sum())
    assert laguna_lm.live_pairs(t, w) == int(
        ((apart >= 0) & (apart < w)).sum())


def test_model_flops_by_hand(cfg):
    """Forward FLOPs a token over the five layers (ISSUE 58): projections at
    the per-layer widths 345 M (ISSUE 58's 344 M and the gates' 1.2 M), global
    attention over the causal pairs 201 M
    (100.7 M a layer), windowed attention over its LIVE pairs 49 M (496 keys
    a query), dense 101 M, shared 25 M, held experts 25 M at uniform routing,
    routers 4 M, head 51 M: 801 M a token, 19.7 TFLOP a step."""
    def proj(H):
        return 2 * d * (H * D + 2 * HKV * D + H) + 2 * H * D * d

    projections = 2 * proj(HG) + 3 * proj(HW)
    global_attn = 2 * 4 * HG * D * CAUSAL / T
    window_attn = 3 * 4 * HW * D * WINDOW / T
    dense = 2 * d * 3 * DENSE
    shared = 4 * 2 * d * 3 * SH
    held = 4 * TOP * HELD / E * 2 * d * 3 * EH
    routers = 4 * 2 * d * E
    head = 2 * d * V
    assert [round(x / MEGA) for x in (
        projections, global_attn, window_attn, dense, shared, held, routers,
        head)] == [345, 201, 49, 101, 25, 25, 4, 51]
    assert round(global_attn / 2 / MEGA, 1) == 100.7
    assert round(WINDOW / T) == 496
    token = (projections + global_attn + window_attn + dense + shared + held
             + routers + head)
    assert round(token / MEGA) == 802            # 801.8
    assert laguna_lm.flops_per_unit(cfg) == pytest.approx(3 * token,
                                                          rel=1e-12)
    assert round(3 * token * T / 1e12, 1) == 19.7
    # The two kinds of attention layer are 74% of the step, the kernels 31%.
    assert round((projections + global_attn + window_attn) / token, 2) == 0.74
    assert round((global_attn + window_attn) / token, 2) == 0.31
    assert laguna_lm.units_per_sample(cfg) == T
    assert TOP * T * HELD / E / HELD == 256       # rows an expert, uniform


def test_kernel_costs_by_hand(cfg):
    flash = laguna_lm.flash_cost(cfg, 1)
    window = laguna_lm.window_flash_cost(cfg, 1)
    assert flash["live_pairs"] == 2 * CAUSAL + 3 * WINDOW
    assert window["live_pairs"] == 3 * WINDOW
    assert (flash["calls_per_step"], window["calls_per_step"]) == (5, 3)
    assert flash["flops"] == 14 * D * (2 * HG * CAUSAL + 3 * HW * WINDOW)
    assert window["flops"] == 14 * D * 3 * HW * WINDOW

    def nbytes(H):
        q, kv, stat = T * H * D * 2, T * HKV * D * 2, H * T * 4
        return 6 * q + 6 * kv + 3 * stat

    assert flash["bytes"] == 2 * nbytes(HG) + 3 * nbytes(HW)
    assert window["bytes"] == 3 * nbytes(HW)
    # FLOPs bound both: the window's 1.4 TFLOP against 2.8 GB a step.
    assert window["flops"] / 197e12 > 2 * window["bytes"] / 819e9
    moe = laguna_lm.moe_cost(cfg, 1)
    assert moe["assignments"] == T * TOP == 65_536
    assert moe["held_assignments"] == 8_192
    assert moe["router_flops"] == 4 * 6 * T * d * E
    assert moe["flops"] == 4 * 6 * (T * d * E + 3 * d * (8_192 * EH + T * SH))
    assert moe["expert_parameters"] == 4 * 3 * d * (HELD * EH + SH)
    assert not hasattr(laguna_lm, "FLASH_KERNELS")


def test_the_readers_labels():
    """``win_flash_ms`` takes the kernels under ``swa/attend`` and no other
    attention kernel; ``attn_gate_ms`` the ops under ``attn/gate`` and
    ``rope/yarn`` and the non-kernel ops under ``swa/attend``."""
    fwd = "jvp(TransformerLM)/layer_*/attn/swa/attend/pallas_call [custom]"
    bwd = ("transpose(jvp(TransformerLM))/layer_*/attn/swa/attend/"
           "pallas_call [custom]")
    global_kernel = "jvp(TransformerLM)/layer_*/attn/pallas_call [custom]"
    glue = ("transpose(jvp(TransformerLM))/layer_*/attn/swa/attend/"
            "reduce_sum [fusion]")
    gate = "jvp(TransformerLM)/layer_*/attn/attn/gate/gate/dot_general [dot]"
    gate_bwd = ("transpose(jvp(TransformerLM))/layer_*/attn/attn/gate/mul "
                "[fusion]")
    yarn = "jvp(TransformerLM)/layer_*/attn/rope/yarn/mul [fusion]"
    expert = "jvp(TransformerLM)/layer_*/moe/route/dot_general [dot]"
    assert win_flash_ms.is_window_kernel(fwd)
    assert win_flash_ms.is_window_kernel(bwd)
    assert not win_flash_ms.is_window_kernel(global_kernel)
    assert not win_flash_ms.is_window_kernel(glue)
    for kernel in (fwd, bwd, global_kernel):
        assert gqa_flash_ms.is_attention_kernel(kernel)
        assert attn_gate_ms.gate_part(kernel) is None
    assert attn_gate_ms.gate_part(glue) == "attend"
    assert attn_gate_ms.gate_part(gate) == "gate"
    assert attn_gate_ms.gate_part(gate_bwd) == "gate"
    assert attn_gate_ms.gate_part(yarn) == "yarn"
    assert attn_gate_ms.gate_part(expert) is None
    assert attn_gate_ms.gate_part("fusion") is None
    assert route_ms.in_router(expert) and moe_ms.in_expert_layer(expert)
    assert not moe_ms.in_expert_layer(gate)


def test_the_readers_read_nothing_where_nothing_is(cfg):
    record = {"family": laguna_lm, "cfg": cfg, "job": {"batch_per_chip": 1},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    fwd = "jvp(TransformerLM)/layer_*/attn/swa/attend/pallas_call [custom]"
    gate = "jvp(TransformerLM)/layer_*/attn/attn/gate/mul [fusion]"
    for reader in (win_flash_ms, win_flash_roofline, attn_gate_ms):
        assert reader.read(record, None) is None
        assert reader.read(record, {"devices": [{
            "steps": 2, "op_self_s": {"fusion": 0.5}}]}) is None
    trace = {"devices": [{"steps": 2, "op_self_s": {
        fwd: 0.05, gate: 0.004, "fusion": 0.5,
        "jvp(TransformerLM)/layer_*/attn/pallas_call [custom]": 1.0}}]}
    assert win_flash_ms.read(record, trace) == pytest.approx(25.0)
    assert attn_gate_ms.read(record, trace) == pytest.approx(2.0)
    least_s = laguna_lm.window_flash_cost(cfg, 1)["flops"] / 197e12
    assert win_flash_roofline.read(record, trace) == pytest.approx(
        100 * least_s / 25e-3)
    # A family that prices no window: the share is not read.
    from benchmark.families import sdar_moe_lm
    assert win_flash_roofline.read({**record, "family": sdar_moe_lm},
                                   trace) is None
