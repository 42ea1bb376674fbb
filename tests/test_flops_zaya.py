"""The ZAYA1 family's FLOPs and bytes functions, and the readers of the
three metrics that came with it, against shapes enumerated by hand (in
``benchmark/tests/test_flops_keye.py``'s manner), the configuration file
against the catalog's row, and the benchmark's own files against each
other."""

import json
import os

import pytest

from benchmark.families import zaya1_lm
from benchmark.metrics import (
    cca_mix_ms, cca_mix_roofline, gqa_flash_ms, moe_ms, route_ms)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut (depth from the file: the chip chose it).
d, T, V = 2048, 16384, 32784
H, G, D = 8, 2, 128                                 # attention
LATENT = (H + G) * D                                # the q | k channels
R, ROUTED, HELD, EH = 256, 17, 8, 2048              # router and experts


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    L = cfg["num_hidden_layers"]
    assert zaya1_lm.pattern(cfg) == "Z" * L
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["cca_time0"], cfg["cca_time1"],
            cfg["partial_rotary_factor"], zaya1_lm.rope_theta(cfg),
            cfg["router_hidden_size"], cfg["experts_routed_over"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["rms_norm_eps"],
            cfg["max_position_embeddings"], cfg["tie_word_embeddings"]) == (
                d, T, V, H, G, D, 2, 2, 0.5, 5e6, R, ROUTED - 1, HELD, 1,
                EH, 1e-5, 131072, True)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            40, 16, 262272]
    assert all(cfg["reduced"][k]["run"] == cfg[k] for k in cfg["reduced"])
    # Never under the guide's floors: 4 layers, 8 experts, an eighth.
    assert L >= 4 and HELD >= 8 and V * 8 == 262272
    for key in ("assumed", "departures"):
        assert cfg[key] and all("TODO" not in line for line in cfg[key])
    assert "TODO" not in cfg["deployment"]
    for key in ("loss_rel", "grad_rel", "tie_margin"):
        assert cfg["tolerances"][key] > 0 and cfg["tolerances"][key + "_why"]
    leaves = zaya1_lm.grad_leaves(cfg)
    assert len(leaves) == len(set(leaves)) == 2 * 4 + 6 + 1
    assert ("layer_1", "moe", "router_state_scale") in leaves
    assert (f"layer_{L - 1}", "attn", "conv1_kernel") in leaves
    # No leaf of few numbers: the temperatures are seen through the others.
    assert not any(path[-1] == "temp" for path in leaves)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no model catalog")
def test_every_published_key_is_the_catalog_s(cfg):
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but for the three in ``reduced``; no
    width among them."""
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "ZAYA1-8B")
    assert cfg["source"] == row["source_url"]
    differing = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differing == sorted(cfg["reduced"])
    assert all(row["config"][k] == cfg["reduced"][k]["published"]
               for k in differing)


def test_matmuls_and_flops_per_token_by_hand(cfg):
    L = cfg["num_hidden_layers"]
    outside = (d * H * D + d * G * D + 2 * d * G * D // 2      # q, k, v1, v2
               + (H + G) * 2 * D * D                           # conv1
               + H * D * d                                     # proj
               + d * R + 2 * R * R + R * ROUTED)               # the router
    assert outside == 6_230_272
    held = 3 * d * EH * HELD / ROUTED        # of a token's one choice in 17
    assert round(held) == 5_921_370
    head = d * V
    assert head == 67_141_632
    weights = L * (outside + held) + head
    got = sum(k * n * count for _, k, n, count in zaya1_lm.matmuls(cfg))
    assert abs(got - weights) <= 1e-6 * weights
    attention = 4 * H * D * (T + 1) / 2                 # forward, a token
    assert attention == 33_556_480
    want = 6 * weights + 3 * L * attention
    assert abs(zaya1_lm.flops_per_unit(cfg) - want) <= 1e-9 * want
    if L == 5:
        assert round(want) == 1_270_746_263
    # Attention's products are more than half of a layer's arithmetic as
    # the model counts it, and the head about a third of the cell's.
    layer = 6 * (outside + held) + 3 * attention
    assert 0.55 < 3 * attention / layer < 0.6
    assert 0.25 < 6 * head / want < 0.4


def test_flash_cost_by_hand(cfg):
    L = cfg["num_hidden_layers"]
    cost = zaya1_lm.flash_cost(cfg, 1)
    product = 2 * H * T * T * D / 2
    assert cost["flops"] == L * 7 * product
    q, kv, stat = T * H * D * 2, T * G * D * 2, H * T * 4
    assert cost["bytes"] == L * (7 * q + 8 * kv + 5 * stat)
    assert cost["shape"] == [1, T, H, G, D] and cost["calls_per_step"] == L
    # FLOPs bound the kernels at this shape.
    assert cost["flops"] / 197e12 > 20 * cost["bytes"] / 819e9


def test_cca_mix_cost_by_hand(cfg):
    L = cfg["num_hidden_layers"]
    cost = zaya1_lm.cca_mix_cost(cfg, 1)
    assert (cost["latent_channels"], cost["shifted_channels"]) == (1280, 128)
    moved = T * 2 * ((2 + 3) * LATENT + (2 + 2) * 128)       # bf16, a layer
    weights = LATENT * 3 + (H + G) * (2 * D * D + D)
    assert weights == 332_800                   # the convolutions' numbers
    assert cost["bytes"] == L * (moved + 2 * 4 * weights)
    assert cost["flops"] == L * 6 * T * (H + G) * 2 * D * D
    # Bytes bound the passes: 1.4 ms of HBM time against 0.8 ms of MXU time
    # at five layers.
    assert cost["bytes"] / 819e9 > 1.5 * cost["flops"] / 197e12


def test_moe_cost_by_hand(cfg):
    L = cfg["num_hidden_layers"]
    cost = zaya1_lm.moe_cost(cfg, 1)
    A = T * HELD / ROUTED                   # 7,710 of 16,384 assignments
    assert round(A) == 7710 and cost["held_assignments"] == A
    router = T * (d * R + 2 * R * R + R * ROUTED)
    assert cost["router_flops"] == L * 6 * router
    assert cost["flops"] == L * 6 * (router + 3 * A * d * EH)
    rows, weights = A * (d + EH) * 2, HELD * d * EH
    assert cost["bytes"] == L * 3 * (3 * rows + 8 * weights)
    assert cost["assignments"] == T
    assert cost["expert_parameters"] == L * 3 * weights == L * 100_663_296
    # FLOPs bound the layer at half a deployment chip's load too.
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9


# ------------------------------------------------------------ the readers


def label(stack, op="fusion.1"):
    return f"jit(step)/jvp(Zaya1LM)/{stack} [{op}]"


def test_the_readers_find_their_scopes_and_no_other():
    attn, moe = "layer_2/attn", "layer_2/moe"
    for scope in ("conv", "qk_mean", "norm_rope", "shift"):
        assert cca_mix_ms.in_passes(label(f"{attn}/cca/{scope}/mul"))
        assert cca_mix_ms.in_passes(
            label(f"transpose(jvp(Zaya1LM))/{attn}/cca/{scope}/dot_general"))
    assert not cca_mix_ms.in_passes(label(f"{attn}/cca/project/q/dot_general"))
    assert not cca_mix_ms.in_passes(label(f"{attn}/pallas_call"))
    assert not cca_mix_ms.in_passes(label(f"{moe}/route/conv/mul"))
    assert not cca_mix_ms.in_passes(label("layer_2/ssm/conv/mul"))
    for scope in ("down", "eda", "mlp"):
        assert route_ms.in_router(label(f"{moe}/route/{scope}/dot_general"))
        assert moe_ms.in_expert_layer(label(f"{moe}/route/{scope}/mul"))
    assert route_ms.in_router(label(f"{moe}/route/softmax"))
    assert not route_ms.in_router(label(f"{moe}/experts/pallas_call"))
    assert not route_ms.in_router(label(f"{attn}/route/mul"))
    assert gqa_flash_ms.is_attention_kernel(
        f"jit(step)/jvp(Zaya1LM)/{attn}/pallas_call [_fwd_kernel]")
    assert not gqa_flash_ms.is_attention_kernel(
        label(f"{moe}/experts/pallas_call", "moe_gmm"))


def test_the_readers_read_a_trace_and_nothing_without_the_layer(cfg):
    L = cfg["num_hidden_layers"]
    ops = {label("layer_0/attn/cca/conv/mul"): 0.004,
           label("layer_0/attn/cca/norm_rope/rsqrt"): 0.002,
           label("layer_0/attn/cca/project/q/dot_general"): 0.010,
           label("layer_0/moe/route/down/dot_general"): 0.003,
           label("layer_0/moe/route/mlp/erf"): 0.001,
           label("layer_0/moe/experts/pallas_call", "moe_gmm"): 0.050}
    trace = {"devices": [{"steps": 2, "op_self_s": ops}]}
    record = {"family": zaya1_lm, "cfg": cfg, "job": {"batch_per_chip": 1},
              "peaks": {"bf16_flops_per_s": 197e12,
                        "hbm_bytes_per_s": 819e9}}
    assert cca_mix_ms.read(record, trace) == pytest.approx(3.0)
    assert route_ms.read(record, trace) == pytest.approx(2.0)
    share = cca_mix_roofline.read(record, trace)
    least_ms = 1e3 * zaya1_lm.cca_mix_cost(cfg, 1)["bytes"] / 819e9
    assert share == pytest.approx(100 * least_ms / 3.0)
    assert 0.25 * L < least_ms < 0.3 * L
    # No trace, no peaks, a family that prices neither, a program that has
    # no such scope (the parent): nothing, and no error.
    from benchmark.families import keye_vl2_lm, olmoe_lm
    assert cca_mix_ms.read(record, None) is None
    assert cca_mix_roofline.read({**record, "peaks": None}, trace) is None
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as fh:
        keye = json.load(fh)
    for family in (keye_vl2_lm, olmoe_lm):
        other = {**record, "family": family, "cfg": keye}
        assert cca_mix_ms.read(other, trace) is None
        assert cca_mix_roofline.read(other, trace) is None
    assert route_ms.read({**record, "family": keye_vl2_lm, "cfg": keye},
                         trace) is None
    bare = {"devices": [{"steps": 2, "op_self_s": {
        label("layer_0/attn/pallas_call", "_fwd_kernel"): 0.02}}]}
    assert cca_mix_ms.read(record, bare) is None
    assert cca_mix_roofline.read(record, bare) is None
    assert route_ms.read(record, bare) is None


def test_benchmark_json_names_the_cell_its_config_and_its_metrics():
    """The cell's entries as PR 43 appended them; later PRs append behind
    them (PR 46: a ninth configuration, a tenth cell, its name behind this
    one's in the lists both report)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    config = {c["name"]: c for c in spec["configs"]}["zaya1-8b"]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    cell = {w["name"]: w for w in spec["workloads"]}["zaya1_1chip"]
    assert cell == {**cell, "config": "zaya1-8b", "traffic": "dp1_b1",
                    "chips": 1}
    assert len(spec["configs"]) >= 8 and len(spec["workloads"]) >= 9
    metrics = {m["name"]: m for m in spec["per_layer"]}
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("cca_mix_ms")
    assert names[at:at + 3] == ["cca_mix_ms", "cca_mix_roofline", "route_ms"]
    for name in ("cca_mix_ms", "cca_mix_roofline"):
        assert metrics[name]["workloads"] == ["zaya1_1chip"]
    assert metrics["route_ms"]["workloads"][0] == "zaya1_1chip"
    for name in ("cca_mix_ms", "cca_mix_roofline", "route_ms"):
        assert metrics[name]["moves"] == "step_ms"
    for name in ("gqa_flash_ms", "gqa_flash_roofline", "moe_ms",
                 "moe_roofline", "route_ms"):
        assert "zaya1_1chip" in metrics[name]["workloads"]
    throughput = {m["name"]: m for m in spec["end_to_end"]}[
        "tokens_per_s_chip"]
    assert "zaya1_1chip" in throughput["workloads"]
