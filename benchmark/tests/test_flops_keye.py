"""The Keye-VL-2.0 family's FLOPs and bytes functions, and the sparse
attention's readers, against shapes enumerated by hand (in
``test_flops_nemotron.py``'s manner) and against what the traced program
notes of itself at the ``TINY`` sizes."""

import json
import os

import pytest

from benchmark.families import keye_vl2_lm
from benchmark.metrics import (
    _sparse, index_kl_roofline, index_ms, index_scores_roofline,
    sel_flash_ms, sel_flash_roofline, sparse_attn_ms)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V, L = 2048, 16384, 18992, 5
HQ, HKV, D = 32, 4, 128                             # attention
HI, DI, TOPK = 16, 64, 2048                         # the indexer
E, HELD, TOP, EH = 128, 16, 8, 768                  # the experts
CAUSAL = T * (T + 1) // 2
SELECTED = TOPK * (TOPK + 1) // 2 + (T - TOPK) * TOPK


def test_the_configuration_is_the_published_one_but_for_the_three_cuts(cfg):
    sa = cfg["sa_config"]
    assert keye_vl2_lm.pattern(cfg) == "SE" * L
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["indexer_num_kv_heads"], sa["topk"], sa["q_chunk_size"],
            sa["kv_chunk_size"], cfg["experts_routed_over"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"]) == (
                d, T, V, L, HQ, HKV, D, HI, DI, 1, TOPK, 512, 512, E, HELD,
                TOP, EH, 10_000_000, 1e-6, 262_144)
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            48, 128, 151936]
    assert all(cfg["reduced"][k]["run"] == cfg[k] for k in cfg["reduced"])
    # Never under the guide's floors: 4 layers, 8 experts, an eighth.
    assert L >= 4 and HELD >= 8 and V * 8 == 151936
    for key in ("assumed", "departures"):
        assert cfg[key] and all("TO FILL" not in line for line in cfg[key])
    assert any("vision tower" in line for line in cfg["departures"])
    leaves = keye_vl2_lm.grad_leaves(cfg)
    assert leaves[0] == ("layer_0", "attn", "q", "kernel")
    for path in (("layer_0", "attn", "index_w", "kernel"),
                 ("layer_8", "attn", "index_q", "kernel"),
                 ("layer_8", "attn", "q_norm", "scale"),
                 ("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "moe", "w_up"), ("tok_emb", "embedding")):
        assert path in leaves, path
    # The deep layers' expert leaves are no check on seeded random weights
    # (their routers collapse: the family's comment); none is compared.
    assert not any(p[1] == "moe" and p[0] != "layer_1" for p in leaves
                   if len(p) > 1)


def test_parameter_count_from_the_same_shapes(cfg):
    attention = d * HQ * D + d * 2 * HKV * D + HQ * D * d + 2 * D
    indexer = d * (HI * DI + DI + HI)
    router, one_expert = d * E, 3 * d * EH
    assert (attention, indexer, router, one_expert) == (
        18_874_624, 2_260_992, 262_144, 4_718_592)
    layer = attention + indexer + router + HELD * one_expert + 2 * d
    assert layer == 96_899_328
    outside = 2 * V * d + d
    assert outside == 77_793_280
    assert 6 * layer + outside == 659_189_248             # ISSUE 36's count
    assert L * layer + outside == 562_289_920             # the depth run
    assert 4 * layer + outside == 465_390_592
    per_token = sum(k * n * count
                    for _, k, n, count in keye_vl2_lm.matmuls(cfg))
    # Weights a token multiplies: everything but the embedding and the
    # norms' scales; of the held experts the share 8 * 16 / 128 = 1.
    assert per_token == pytest.approx(
        L * (layer - 2 * d - 2 * D - HELD * one_expert + one_expert)
        + V * d)


def test_flops_formula_equals_the_sum_over_its_parts(cfg):
    assert (CAUSAL, SELECTED) == (134_225_920, 31_458_304)
    assert SELECTED / CAUSAL == pytest.approx(0.2344, abs=1e-4)
    projections = 2 * d * (HQ * D + 2 * HKV * D) + 2 * HQ * D * d
    index_proj = 2 * d * (HI * DI + DI + HI)
    experts = 2 * d * E + (TOP * HELD / E) * 3 * 2 * d * EH
    attention = 4 * HQ * D * SELECTED / T        # the model's: |S_t| a query
    scores = 2 * HI * DI * CAUSAL / T
    head = 2 * d * V
    fwd = L * (projections + index_proj + experts + attention + scores) + head
    assert keye_vl2_lm.flops_per_unit(cfg) == pytest.approx(3 * fwd,
                                                            rel=1e-12)
    assert keye_vl2_lm.flops_per_unit(cfg) == 1_740_404_736.0
    assert keye_vl2_lm.flops_per_unit({**cfg, "num_hidden_layers": 6}) == (
        2_041_810_944.0)
    # A layer and sequence, forward (ISSUE 36: 0.856, 0.515, 0.275 T).
    assert T * (projections + index_proj + experts) == pytest.approx(
        0.856e12, rel=2e-3)
    assert T * attention == pytest.approx(0.515e12, rel=2e-3)
    assert T * scores == pytest.approx(0.275e12, rel=2e-3)
    assert (attention + scores) / (
        projections + index_proj + experts + attention + scores
    ) == pytest.approx(0.48, abs=0.005)
    # A step of 1 x 16,384 tokens.
    assert T * keye_vl2_lm.flops_per_unit(cfg) == pytest.approx(28.51e12,
                                                                rel=1e-3)


def test_sel_flash_cost_at_the_benchmark_shape(cfg):
    """The selected pairs' work, whatever implements it: a kernel that
    multiplies every causal tile does 4.27 times the products."""
    cost = keye_vl2_lm.sel_flash_cost(cfg, 1)
    product = 2 * HQ * D * SELECTED
    assert cost["flops"] == L * 7 * product
    q, kv, stat = T * HQ * D * 2, T * HKV * D * 2, HQ * T * 4
    assert cost["bytes"] == L * ((2 * q + 2 * kv + stat)
                                 + (3 * q + 2 * kv + 2 * stat)
                                 + (2 * q + 4 * kv + 2 * stat) + T * T)
    assert cost["calls_per_step"] == L
    assert cost["shape"] == [1, T, HQ, HKV, D]
    assert (cost["selected_pairs"], cost["causal_pairs"]) == (SELECTED,
                                                              CAUSAL)
    assert CAUSAL / SELECTED == pytest.approx(4.267, abs=1e-3)
    # FLOP-bound on a v5e: 45.8 ms a step against 8.3 by bytes.
    assert cost["flops"] / 197e12 == pytest.approx(45.8e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(8.26e-3, rel=0.02)


def test_indexer_costs_at_the_benchmark_shape(cfg):
    scores = keye_vl2_lm.index_scores_cost(cfg, 1)
    assert scores["flops"] == L * 2 * HI * DI * CAUSAL
    assert scores["bytes"] == L * (T * (HI * DI + DI) * 2 + T * HI * 4
                                   + CAUSAL * 4)
    # 7.0 ms a step by FLOPs, 3.5 by the float32 scores it writes.
    assert scores["flops"] / 197e12 == pytest.approx(7.0e-3, rel=0.01)
    assert scores["bytes"] / 819e9 == pytest.approx(3.5e-3, rel=0.05)
    kl = keye_vl2_lm.index_kl_cost(cfg, 1)
    assert kl["flops"] == L * SELECTED * (2 * HQ * D + 6 * HI * DI)
    reads = (T * ((HQ + HKV) * D + HI * DI + DI) * 2
             + T * (HQ + 1 + HI) * 4 + T * T)
    writes = T * (HI * DI + DI + HI + 1) * 4
    assert kl["bytes"] == L * (reads + writes)
    assert kl["flops"] / 197e12 == pytest.approx(11.4e-3, rel=0.01)


def test_moe_cost_at_the_benchmark_shape(cfg):
    cost = keye_vl2_lm.moe_cost(cfg, 1)
    A = T * TOP * HELD / E
    assert cost["assignments"] == T * TOP == 131_072
    assert cost["held_assignments"] == A == 16_384        # 1,024 an expert
    assert cost["expert_parameters"] == L * 3 * HELD * d * EH
    assert cost["flops"] == L * 6 * (T * d * E + 3 * A * d * EH)

    def matmul(rows, k, n, weights):
        return 3 * rows * (k + n) * 2 + 2 * weights * 2 + weights * 4

    assert cost["bytes"] == L * 3 * matmul(A, d, EH, HELD * d * EH)
    assert cost["flops"] / 197e12 == pytest.approx(12.5e-3, rel=0.01)
    assert cost["bytes"] / 819e9 < cost["flops"] / 197e12


def test_the_traced_program_notes_the_counts_the_cost_functions_use(cfg):
    """At the ``TINY`` sizes: what the attention layers tell
    ``make_train_step`` of one step (``note_layer``) and what they sow are
    the pairs, FLOPs and bytes the family's functions reckon with."""
    import jax
    import numpy as np
    from horovod_tpu.parallel.moe import noting_expert_layers

    tiny = {**cfg, **keye_vl2_lm.TINY}
    B = keye_vl2_lm.TINY_BATCH_PER_CHIP
    sizes = keye_vl2_lm._sizes(tiny)
    t, k = tiny["sequence_length"], tiny["sa_config"]["topk"]
    assert sizes["selected_pairs"] == sum(min(i + 1, k) for i in range(t))
    assert sizes["causal_pairs"] == t * (t + 1) // 2
    params, aux = keye_vl2_lm.init(tiny, jax.random.PRNGKey(0))
    tokens = keye_vl2_lm.host_batch(tiny, np.random.default_rng(0), B)
    noted = {}
    model = keye_vl2_lm._model(tiny)
    _, state = noting_expert_layers(model.apply, noted)(
        {"params": params}, tokens[:, :-1], return_hidden=True,
        mutable=["intermediates"])
    layers = [v for path, v in noted.items() if "attn.causal_pairs" in v]
    assert len(layers) == tiny["num_hidden_layers"]
    n = len(layers)
    total = {key: sum(v[key] for v in layers) for key in layers[0]}
    sel = keye_vl2_lm.sel_flash_cost(tiny, B)
    scores = keye_vl2_lm.index_scores_cost(tiny, B)
    assert total["attn.selected_pairs"] == n * sel["selected_pairs"]
    assert total["attn.causal_pairs"] == n * sel["causal_pairs"]
    assert total["attn.index_flops"] == scores["flops"]
    assert total["attn.select_bytes"] == n * B * t * t
    heads, width = tiny["num_attention_heads"], tiny["head_dim"]
    assert sel["flops"] == 7 * 2 * heads * width * total[
        "attn.selected_pairs"]
    for i in range(n):
        sown = state["intermediates"][f"layer_{2 * i}"]["attn"]
        assert float(sown["selected_per_query"][0]) * B * t == (
            sel["selected_pairs"])
        assert int(np.asarray(sown["select"][0], np.int64).sum()) == (
            sel["selected_pairs"])


# ------------------------------------------------------------ the readers

STACK = "TransformerLM._pattern_stack/layer_*/attn/attn._selected"
OPS = {
    f"jvp(TransformerLM)/{STACK}/flash_select/flash_select_fwd/pallas_call "
    "[custom-call]": 0.30,
    f"transpose(jvp(TransformerLM))/{STACK}/flash_select/"
    "flash_select_dq/pallas_call [custom-call]": 0.90,
    f"jvp(TransformerLM)/{STACK}/index/scores/index_scores/pallas_call "
    "[custom-call]": 0.08,
    f"jvp(TransformerLM)/{STACK}/index/kl/index_kl/pallas_call "
    "[custom-call]": 0.20,
    f"jvp(TransformerLM)/{STACK}/index/while/body/topk/reduce_sum "
    "[loop fusion]": 0.40,
    f"jvp(TransformerLM)/{STACK}/index/select/concatenate [data formatting]":
        0.02,
    f"jvp(TransformerLM)/{STACK}/index/project/index_q/dot_general "
    "[convolution fusion]": 0.04,
    f"transpose(jvp(TransformerLM))/{STACK}/index/project/index_q/"
    "dot_general [convolution fusion]": 0.04,
    "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/attn/q/"
    "dot_general [convolution fusion]": 0.10,
    "params['layer_*']['attn']['q']['kernel'] [data formatting]": 0.01,
    "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/moe/"
    "pallas_call [custom-call]": 0.50,
    "add [loop fusion]": 0.10,
}


def record(cfg, **more):
    return {"family": keye_vl2_lm, "cfg": cfg, "job": {"batch_per_chip": 1},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            **more}


def test_the_readers_take_the_attention_s_ops_and_no_other(cfg):
    trace = {"devices": [{"steps": 4, "op_self_s": OPS}]}
    rec = record(cfg)
    assert sel_flash_ms.read(rec, trace) == pytest.approx(300.0)
    assert index_ms.read(rec, trace) == pytest.approx(
        1e3 * (0.08 + 0.20 + 0.40 + 0.02 + 0.04 + 0.04) / 4)
    assert sparse_attn_ms.read(rec, trace) == pytest.approx(
        1e3 * (sum(OPS.values()) - 0.50 - 0.10) / 4)
    sel = keye_vl2_lm.sel_flash_cost(cfg, 1)["flops"] / 197e12
    assert sel_flash_roofline.read(rec, trace) == pytest.approx(
        100 * sel / 0.300)
    assert index_scores_roofline.read(rec, trace) == pytest.approx(
        100 * keye_vl2_lm.index_scores_cost(cfg, 1)["flops"] / 197e12 / 0.020)
    assert index_kl_roofline.read(rec, trace) == pytest.approx(
        100 * keye_vl2_lm.index_kl_cost(cfg, 1)["flops"] / 197e12 / 0.050)
    # The other attention readers leave the cell alone, and these every
    # other family.
    from benchmark.families import nemotron_h_lm
    from benchmark.metrics import flash_ms, gqa_flash_ms
    rec = record(cfg, program={"kernels": ["flash_select_fwd"]})
    assert gqa_flash_ms.read(rec, trace) is None
    assert flash_ms.read(rec, trace) is None
    other = {**rec, "family": nemotron_h_lm}
    for reader in (sparse_attn_ms, index_ms, sel_flash_ms, sel_flash_roofline,
                   index_scores_roofline, index_kl_roofline):
        assert reader.read(other, trace) is None, reader.__name__


@pytest.mark.parametrize("reader", [
    sparse_attn_ms, index_ms, sel_flash_ms, sel_flash_roofline,
    index_scores_roofline, index_kl_roofline],
    ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_a_trace_without_the_layer_reads_nothing(cfg, reader):
    """A program that lacks the scopes, as these metrics' parent does, and
    a run with no trace or no peaks: nothing, and no exception."""
    bare = {"devices": [{"steps": 5, "op_self_s": {
        "jvp(TransformerLM)/layer_*/attn_q/dot_general [convolution]": 1.0,
        "add [loop fusion]": 1.0}}]}
    assert reader.read(record(cfg), None) is None
    assert reader.read(record(cfg), bare) is None
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
        "%" if reader.__name__.endswith("roofline") else "ms",
        "sparse attention", "step_ms")
    if reader.UNIT == "%":
        trace = {"devices": [{"steps": 4, "op_self_s": OPS}]}
        assert reader.read({**record(cfg), "peaks": None}, trace) is None


def test_labels_by_part():
    scores = (f"jvp(TransformerLM)/{STACK}/index/scores/index_scores/"
              "pallas_call [custom-call]")
    assert _sparse.is_scores_kernel(scores) and _sparse.in_indexer(scores)
    assert not _sparse.is_kl_kernel(scores)
    assert not _sparse.is_selected_flash(scores)
    assert not _sparse.in_indexer(
        "jvp(TransformerLM)/layer_*/index/attn_norm/mul [loop fusion]")
    assert not _sparse.in_attention(
        "jvp(TransformerLM)/layer_*/moe/router/dot_general [convolution]")
