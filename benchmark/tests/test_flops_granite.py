"""The Granite 4.0-H family's FLOPs and bytes functions, and the two
readers this configuration brings, against shapes enumerated by hand (in
``test_flops_nemotron.py``'s manner)."""

import json
import os

import pytest

from benchmark.families import granite_hybrid_lm as family
from benchmark.metrics import (
    gqa_flash_ms, mixer_pass_ms, mixer_pass_roofline, ssd_roofline, ssm_ms)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs",
                           "granite-4.0-h-micro.json")) as fh:
        return json.load(fh)


# The published widths, and the cut.
d, T, V, MLP = 2048, 8192, 12544, 8192
H, P, G, N, Q, K = 64, 64, 1, 128, 256, 4           # the mixer
HQ, HKV, D = 32, 8, 64                              # attention
M_LAYERS, A_LAYERS = 9, 1
INNER, CONV = H * P, H * P + 2 * G * N              # 4096, 4352


def test_the_configuration_is_the_published_one_but_for_the_two_cuts(cfg):
    assert family.pattern(cfg) == "mmmmmammmm"
    assert (cfg["hidden_size"], cfg["sequence_length"], cfg["vocab_size"],
            cfg["shared_intermediate_size"], cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"],
            cfg["mamba_chunk_size"], cfg["mamba_d_conv"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            family.head_dim(cfg)) == (d, T, V, MLP, H, P, G, N, Q, K, HQ,
                                      HKV, D)
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["logits_scaling"], cfg["attention_multiplier"],
            cfg["tie_word_embeddings"], cfg["rms_norm_eps"]) == (
                12, 0.22, 8, 0.015625, True, 1e-5)
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert [cfg["reduced"][k]["published"] for k in (
        "num_hidden_layers", "vocab_size")] == [40, 100352]
    assert family.grad_leaves(cfg)[0] == ("layer_0", "ssm", "in_proj",
                                          "kernel")
    assert ("layer_5", "attn", "kv", "kernel") in family.grad_leaves(cfg)
    assert ("layer_9", "mlp", "down", "kernel") in family.grad_leaves(cfg)
    assert not hasattr(family, "FLASH_KERNELS")     # gqa_flash_ms reads


def test_every_published_key_is_in_the_file_under_its_name(cfg):
    """The catalog's row: every key of its ``config`` is in the file with
    the same value but the two in ``reduced``."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"])


def test_parameter_count_from_the_same_shapes(cfg):
    mixer = (d * (2 * INNER + 2 * G * N + H) + K * CONV + CONV + 3 * H
             + INNER + INNER * d)
    attention = d * HQ * D + d * 2 * HKV * D + HQ * D * d
    swiglu = 3 * d * MLP
    assert (mixer, attention, swiglu) == (25_847_232, 10_485_760, 50_331_648)
    total = (M_LAYERS * (mixer + swiglu + 2 * d)
             + A_LAYERS * (attention + swiglu + 2 * d) + V * d + d)
    assert total == 772_160_448
    # The weights the matmuls multiply by are all of it but the vectors.
    weights = sum(k * n * count for _, k, n, count in family.matmuls(cfg))
    vectors = (M_LAYERS * (K * CONV + CONV + 3 * H + INNER)
               + (M_LAYERS + A_LAYERS) * 2 * d + d)
    assert weights == total - vectors == 771_883_008


def test_flops_per_unit_by_hand(cfg):
    weights = 771_883_008
    attention = A_LAYERS * T * HQ * D                       # 16,777,216
    intra = (G * 2 * Q * N + H * 2 * Q * P) / 2             # 1,081,344
    scan = intra + 2 * H * 2 * P * N + 2 * K * CONV         # 3,213,312
    assert family.scan_flops_per_token(cfg) == scan == 3_213_312
    want = 6 * weights + 6 * attention + 3 * M_LAYERS * scan
    assert family.flops_per_unit(cfg) == want == 4_818_720_768
    # A step of 8,192 tokens: 39.5 TFLOP, 0.2 s at the v5e's peak.
    assert round(want * T / 1e12, 2) == 39.47


def test_ssd_cost_by_hand(cfg):
    cost = family.ssd_cost(cfg, 1)
    conv = 2 * K * CONV
    assert cost["flops"] == 3 * M_LAYERS * T * (3_213_312 - conv)
    inputs = (INNER + 2 * G * N) * 2 + H * 4                # 8,960 B a token
    y = INNER * 2
    assert cost["bytes"] == M_LAYERS * T * ((inputs + y)
                                            + (inputs + y + inputs))
    assert cost["bytes"] == 3_189_768_192
    assert cost["chunks"] == M_LAYERS * T // Q == 288
    assert cost["state_bytes"] == 288 * H * P * N * 4
    # Bytes bound it on the v5e: 3.89 ms against 3.57 by FLOPs.
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert round(1e3 * cost["bytes"] / 819e9, 2) == 3.89


def test_pass_cost_by_hand(cfg):
    cost = family.pass_cost(cfg, 1)
    conv = (2 + 3) * CONV * 2          # read + write; read, read, write
    gate = (3 + 5) * INNER * 2         # y, z -> out; y, z, do -> dy, dz
    assert cost["bytes_per_token"] == conv + gate == 109_056
    assert cost["bytes"] == M_LAYERS * T * 109_056 == 8_040_480_768
    assert family.pass_cost(cfg, 2)["bytes"] == 2 * cost["bytes"]
    assert round(1e3 * cost["bytes"] / 819e9, 2) == 9.82


def test_flash_cost_by_hand_with_k_and_v_at_their_eight_heads(cfg):
    cost = family.flash_cost(cfg, 1)
    assert cost["shape"] == [1, T, HQ, HKV, D] and cost["calls_per_step"] == 1
    product = 2 * HQ * T * T * D / 2
    assert cost["flops"] == 7 * product == 962_072_674_304
    q, kv, stat = T * HQ * D * 2, T * HKV * D * 2, HQ * T * 4
    assert cost["bytes"] == ((2 * q + 2 * kv + stat)
                             + (3 * q + 2 * kv + 2 * stat)
                             + (2 * q + 4 * kv + 2 * stat))
    # K and V priced at 8 heads, not the 32 the merged-heads path repeats
    # them to: the repeat counts against the share.
    repeated = cost["bytes"] + 8 * (T * HQ * D * 2 - kv)
    assert repeated > 1.5 * cost["bytes"]
    # FLOPs bound it: 4.88 ms at the peak.
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    assert round(1e3 * cost["flops"] / 197e12, 2) == 4.88


STACK = ("transpose(jvp(TransformerLM))/TransformerLM._pattern_stack/"
         "layer_*/ssm/")
LABELS = {
    STACK + "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/ssm/"
            "checkpoint/scan/ssd_bwd/pallas_call [custom-call]": "scan",
    STACK + "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/ssm/"
            "checkpoint/conv/ssm_conv_bwd/pallas_call [custom-call]": "pass",
    "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/ssm/checkpoint/"
    "conv/ssm_conv_fwd/pallas_call [custom-call]": "pass",
    "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/ssm/gate_norm/"
    "ssm_gate_fwd/pallas_call [custom-call]": "pass",
    STACK + "gate_norm/ssm_gate_bwd/pallas_call [custom-call]": "pass",
    STACK + "in_proj/transpose(jvp())/dot_general [convolution fusion]":
        "mixer",
    "jvp(TransformerLM)/TransformerLM._pattern_stack/layer_*/mlp/gate/"
    "dot_general [convolution fusion]": None,
    "transpose(jvp(TransformerLM))/TransformerLM._pattern_stack/layer_*/attn/"
    "pallas_call [custom-call]": "attention",
    # The convolution's PARAMETERS live in a module named conv; their
    # casts are named after the parameter and belong to no pass.
    "params['layer_*']['ssm']['conv']['kernel'] [data formatting]": "mixer",
}


def test_the_pass_reader_takes_the_two_scopes_and_nothing_else():
    for label, kind in LABELS.items():
        assert mixer_pass_ms.in_passes(label) == (kind == "pass"), label
        assert ssm_ms.in_scan(label) == (kind == "scan"), label
        assert ssm_ms.in_mixer(label) == (kind in ("pass", "scan", "mixer")
                                          ), label
        assert gqa_flash_ms.is_attention_kernel(label) == (
            kind == "attention"), label


def test_readers_on_a_hand_made_trace(cfg):
    """Five steps: the passes' ops 0.1 s together, the scan's 0.15.  The
    shares cannot pass 100: the least times are 9.82 and 3.89 ms."""
    ops = {label: {"pass": 0.025, "scan": 0.15}.get(kind, 0.5)
           for label, kind in LABELS.items()}
    trace = {"devices": [{"steps": 5, "op_self_s": ops}]}
    record = {"family": family, "cfg": cfg, "job": {"batch_per_chip": 1},
              "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert mixer_pass_ms.read(record, trace) == pytest.approx(20.0)
    assert mixer_pass_roofline.read(record, trace) == pytest.approx(
        100 * 9.8174 / 20.0, rel=1e-4)
    assert ssd_roofline.read(record, trace) == pytest.approx(
        100 * 3.8947 / 30.0, rel=1e-4)
    # Off the chip, without a trace, or for a family that prices no scan
    # or no passes: nothing, and no exception.
    assert mixer_pass_ms.read(record, None) is None
    assert mixer_pass_roofline.read({**record, "peaks": None}, trace) is None

    class NoPasses:
        ssd_cost = staticmethod(family.ssd_cost)

    class NoScan:
        pass

    assert mixer_pass_ms.read({**record, "family": NoPasses}, trace) == (
        pytest.approx(20.0))
    assert mixer_pass_roofline.read({**record, "family": NoPasses},
                                    trace) is None
    assert mixer_pass_ms.read({**record, "family": NoScan}, trace) is None
    empty = {"devices": [{"steps": 5, "op_self_s": {}}]}
    assert mixer_pass_ms.read(record, empty) is None
    assert mixer_pass_roofline.read(record, empty) is None
