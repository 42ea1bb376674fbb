"""The SDAR family's FLOPs and bytes functions against a hand count (ISSUE
52's numbers), the live-pair formula against a brute-force count of the
boolean mask, the catalog's published keys, and the new reader's labels."""

import json
import os

import numpy as np
import pytest

from benchmark.families import sdar_moe_lm
from benchmark.metrics import bd_stream_ms, gqa_flash_ms, moe_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "sdar-30b-a3b-chat.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V, L = 2048, 8192, 18992, 5
HQ, HKV, D, BLOCK = 32, 4, 128, 4
E, HELD, TOP, EH = 128, 16, 8, 768
ROWS = 2 * T
LIVE = T * T + T * BLOCK                       # of 4 T^2 pairs a sequence
TERA = 1e12


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    assert sdar_moe_lm.pattern(cfg) == "SE" * L
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["experts_routed_over"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["max_position_embeddings"]) == (
                d, T, V, L, HQ, HKV, D, E, HELD, TOP, EH, 1_000_000, 1e-6,
                32768)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            48, 128, 151936]
    assert all(cfg["reduced"][k]["run"] == cfg[k] for k in cfg["reduced"])
    # Never under the guide's floors: 4 layers, 8 experts, an eighth.
    assert L >= 5 and HELD >= 8 and V * 8 == 151936
    bd = cfg["block_diffusion"]
    assert bd["block_length"] == BLOCK and bd["mask_token_id"] == V - 1
    assert 0 < bd["t_low"] < bd["t_high"] <= 1
    for key in ("assumed", "departures"):
        assert cfg[key] and all("TO FILL" not in line for line in cfg[key])
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == cfg["source"]
        differs = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differs == set(cfg["reduced"])


@pytest.mark.parametrize("t,block", [(8, 4), (16, 4), (64, 32), (24, 1)])
def test_live_pairs_against_the_boolean_mask(t, block):
    """The four rules, row by row, counted."""
    blk = np.arange(t) // block
    clean_clean = blk[None] <= blk[:, None]
    noised_clean = blk[None] < blk[:, None]
    noised_noised = blk[None] == blk[:, None]
    brute = int(clean_clean.sum() + noised_clean.sum() + noised_noised.sum())
    assert sdar_moe_lm.live_pairs(t, block) == brute == t * t + t * block


def test_model_flops_by_hand(cfg):
    """A layer forward at one sequence (16,384 rows; ISSUE 52): projections
    0.619 T, router 0.009 T, held experts at the uniform 16,384 assignments
    0.155 T, attention over the 67.14 M live pairs 1.100 T — 58% of the
    layer's 1.882 T; the head 0.637 T over the noised rows.  Of the LAST
    layer the loss depends on 0.975 T (kv on both streams, the rest on the
    noised rows, half the live pairs): 0.907 T is not the model's."""
    assert LIVE == 67_141_632
    proj = ROWS * 2 * d * (HQ * D + 2 * HKV * D + HQ * D)
    router = ROWS * 2 * d * E
    experts = ROWS * TOP * HELD / E * 3 * 2 * d * EH
    attention = 4 * D * HQ * LIVE
    layer = proj + router + experts + attention
    head = T * 2 * d * V
    assert [round(x / TERA, 3) for x in (proj, router, experts, attention,
                                         layer, head)] == [
        0.618, 0.009, 0.155, 1.1, 1.882, 0.637]
    assert round(attention / layer, 2) == 0.58
    last = (ROWS * 2 * d * 2 * HKV * D + T * 2 * d * 2 * HQ * D
            + (router + experts + attention) / 2)
    assert round(last / TERA, 3) == 0.975
    assert round((layer - last) / TERA, 3) == 0.907
    step = 3 * ((L - 1) * layer + last + head)
    assert sdar_moe_lm.flops_per_unit(cfg) * T == pytest.approx(step,
                                                                rel=1e-12)
    assert round(step / TERA, 1) == 27.4
    names = [m[0] for m in sdar_moe_lm.matmuls(cfg)]
    assert names == ["attn_q", "attn_kv", "attn_proj", "router", "w_gate",
                     "w_up", "w_down", "head"]
    assert sdar_moe_lm.units_per_sample(cfg) == T          # not the rows


def test_kernel_costs_by_hand(cfg):
    flash = sdar_moe_lm.flash_cost(cfg, 1)
    assert flash["live_pairs"] == LIVE and flash["all_pairs"] == 4 * T * T
    assert flash["flops"] == L * 14 * D * HQ * LIVE
    q, kv, stat = ROWS * HQ * D * 2, ROWS * HKV * D * 2, HQ * ROWS * 4
    assert flash["bytes"] == L * (6 * q + 6 * kv + 3 * stat)
    assert flash["shape"] == [1, ROWS, HQ, HKV, D]
    # FLOPs bound it: 3.85 TFLOP against 1.0 GB a step.
    assert flash["flops"] / 197e12 > 10 * flash["bytes"] / 819e9
    moe = sdar_moe_lm.moe_cost(cfg, 1)
    assert moe["assignments"] == ROWS * TOP == 131_072       # doubled rows
    assert moe["held_assignments"] == 16_384
    assert moe["flops"] == L * 6 * (ROWS * d * E + 3 * 16_384 * d * EH)
    assert moe["expert_parameters"] == L * 3 * HELD * d * EH
    assert not hasattr(sdar_moe_lm, "FLASH_KERNELS")
    assert not hasattr(sdar_moe_lm, "bd_stream_cost")


def test_the_reader_s_labels():
    """``bd_stream_ms`` takes the ops of the four scopes but the kernels;
    the kernels under ``bd/attend`` stay ``gqa_flash_ms``'s."""
    part = bd_stream_ms.stream_part
    assert part("jvp(TransformerLM)/bd/assemble/tok_emb/take [gather]") == (
        "assemble")
    assert part("transpose(jvp(TransformerLM))/bd/assemble/tok_emb/"
                "scatter-add [scatter]") == "assemble"
    assert part("jvp(TransformerLM)/bd/split/slice [data formatting]") == (
        "split")
    assert part("jvp(bd)/loss/mul [fusion]") == "loss"
    kernel = "jvp(TransformerLM)/layer_*/attn/bd/attend/pallas_call [custom]"
    glue = ("transpose(jvp(TransformerLM))/layer_*/attn/bd/attend/"
            "reduce_sum [fusion]")
    assert part(kernel) is None and part(glue) == "attend"
    assert gqa_flash_ms.is_attention_kernel(kernel)
    assert not gqa_flash_ms.is_attention_kernel(glue)
    assert part("jvp(TransformerLM)/layer_*/moe/sort [sort]") is None
    assert part("fusion") is None
    assert not moe_ms.in_expert_layer(kernel)
    record = {"family": sdar_moe_lm}
    assert bd_stream_ms.read(record, None) is None
    trace = {"devices": [{"steps": 2, "op_self_s": {
        kernel: 1.0, glue: 0.004, "fusion": 0.5,
        "jvp(TransformerLM)/bd/split/slice [data formatting]": 0.002}}]}
    assert bd_stream_ms.read(record, trace) == pytest.approx(3.0)
    assert bd_stream_ms.read(record, {"devices": [{
        "steps": 2, "op_self_s": {"fusion": 0.5}}]}) is None
