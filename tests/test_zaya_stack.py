"""The ZAYA1 stack (``Zaya1LM``; pattern letter ``Z``): compressed
convolutional attention — attention inside a latent whose q and k pass two
causal convolutions, with a QK-mean, a value shift, an L2 norm under a
learned temperature and rotary positions on half of each head — and then a
top-1 expert layer whose router is a small network with a state handed from
layer to layer and one choice that computes nothing, both joined to the
residual under learned scales and biases; the held share of such a layer;
and the benchmark family's plain float32 reference against the program —
loss and named gradient leaves on seeded weights.  Here: the tree by count
and the reference; the layer's parts by hand, the shares, the state and the
other routers' pinned programs are ``test_zaya_layers.py``'s (one file until
PR 56; the last file by name is the run's last job under ``--dist
loadfile``, and it was 200 s long).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import zaya1_lm as family
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import CompressedConvAttention, Zaya1LM

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b.json")) as fh:
        return json.load(fh)


# ------------------------------------------------- the tree, by count


def count(model):
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)), params


def test_parameter_counts_the_cut_and_the_published_model():
    """By hand (ISSUE 43): attention 5,242,880 in its five projections and
    332,802 in the two convolutions and the temperatures; the router
    661,009; two norms 4,096; the merges' 8 vectors of 2,048; an expert
    12,582,912.  The model's first layer has no scale and bias on its
    residual (4,096 fewer) and no scale on a router state (256 fewer)."""
    attention = 2048 * 1024 * 2 + 2048 * 256 + 2 * 2048 * 128
    convs = 1280 * 3 + (10 * 2 * 128 * 128 + 1280) + 2
    router = (2048 * 256 + 256) + 256 + 256 + 2 * (256 * 256 + 256) \
        + 256 * 17 + 17
    outside = attention + convs + router + 4096 + 8 * 2048
    assert (attention, convs, router, outside) == (
        5_242_880, 332_802, 661_009, 6_257_171)
    expert = 3 * 2048 * 2048
    cfg = published()
    n, params = count(family._model(cfg))
    layers = cfg["num_hidden_layers"]
    assert n == (layers * (outside + 8 * expert) - 4096 - 256
                 + 32_784 * 2048 + 2048)
    assert "head" not in params                       # the table is the head
    assert sorted(params["layer_1"]) == [
        "attn", "merge_attn", "merge_moe", "moe", "moe_norm", "norm"]
    assert sorted(params["layer_0"]["merge_attn"]) == ["bias_y", "scale_y"]
    assert "router_state_scale" not in params["layer_0"]["moe"]
    assert params["layer_1"]["moe"]["router_state_scale"].shape == (256,)
    assert params["layer_1"]["moe"]["w_gate"].shape == (8, 2048, 2048)
    assert params["layer_1"]["moe"]["router_out"]["kernel"].shape == (256, 17)
    assert params["layer_1"]["attn"]["conv1_kernel"].shape == (10, 2, 128,
                                                                128)
    n40, _ = count(Zaya1LM())
    assert n40 == (40 * (outside + 16 * expert) - 4096 - 256
                   + 262_272 * 2048 + 2048)
    assert n40 == 8_840_481_272
    assert family.pattern(cfg) == "Z" * layers and Zaya1LM().pattern == (
        "Z" * len(cfg["layer_types"]))


# ------------------------- program against the family's plain reference


def family_cfg(compute="float32", **over):
    """Two layers (so that a state is handed on) at sizes the flash kernels
    take: 4 query heads over 2 KV heads of 128, 4 of 8 experts held."""
    cfg = published()
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=128, num_experts=4,
               experts_routed_over=8, moe_intermediate_size=32,
               router_hidden_size=16, sequence_length=64, vocab_size=256,
               training={**cfg["training"], "compute_dtype": compute},
               tolerances={**cfg["tolerances"], "tie_margin": 1e-6})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def compared():
    """Loss and every gradient leaf of the program in float32, of the
    program in bfloat16 and of the reference, on one seeded batch; the
    kernels interpreted."""
    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(
        jax.random.PRNGKey(11))
    # Biases, scales and temperatures off their initial 0 and 1, so that a
    # part that ignored one would show.
    params = jax.tree.map(
        lambda a: a + (0.1 * jax.random.normal(jax.random.PRNGKey(12),
                                               a.shape) if a.ndim == 1
                       else 0.0), params)
    for i in range(2):
        params[f"layer_{i}"]["moe"]["choice_bias"] = jnp.zeros(9)
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(5), 1))
    noted = {}
    out = {"cfg": cfg, "params": params, "tokens": tokens}
    with jax.default_matmul_precision("highest"):
        for name, fn in (
                ("float32", noting_layers(family.loss_fn(cfg), noted)),
                ("reference", lambda p, a, t: (
                    family.reference_loss(cfg)(p, a, t), a))):
            (loss, _), grads = jax.jit(jax.value_and_grad(
                fn, has_aux=True))(params, aux, tokens)
            out[name] = (float(loss), grads)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        family.loss_fn(family_cfg("bfloat16")), has_aux=True))(
            params, aux, tokens)
    out["bfloat16"] = (float(loss), grads)
    out["noted"] = noted
    return out


# Why these bounds.  The program in float32 differs from the reference in
# the order of its sums (flash against a held softmax, the grouped
# matmuls' rows against a masked matmul over every token, a product of
# depth 256 against two of 128): observed 4e-8 on the loss and up to 4e-5
# on a leaf.  The same program computing in bfloat16 — the nearest
# precision below the one this test's configuration states — reads 2e-3 and
# up on every leaf but one.
LOSS_TOL, LEAF_TOL = 2e-6, 3e-4


def test_loss_and_every_leaf_against_the_plain_reference(compared):
    want_loss, want = compared["reference"]
    got_loss, got = compared["float32"]
    assert abs(got_loss - want_loss) <= LOSS_TOL * abs(want_loss)
    # choice_bias has no gradient on either side: 0 against 0.
    errors = jax.tree.map(
        lambda g, w: 0.0 if not float(jnp.abs(w).max()) and not float(
            jnp.abs(g).max()) else rel(g, w), got, want)
    worst = max(jax.tree.leaves(errors))
    assert worst <= LEAF_TOL, errors
    named = family.grad_leaves(compared["cfg"])
    for must in (("layer_0", "attn", "q", "kernel"),
                 ("layer_0", "attn", "conv1_kernel"),
                 ("layer_0", "attn", "v2", "kernel"),
                 ("layer_0", "attn", "proj", "kernel"),
                 ("layer_0", "moe", "router_down", "kernel"),
                 ("layer_1", "moe", "router_state_scale"),
                 ("layer_0", "moe", "router_fc1", "kernel"),
                 ("layer_0", "moe", "w_gate"), ("layer_0", "moe", "w_down"),
                 ("layer_0", "merge_moe", "scale_y"),
                 ("tok_emb", "embedding")):
        assert must in named
    for path in named:
        leaf, reference = errors, want
        for key in path:
            leaf, reference = leaf[key], reference[key]
        assert leaf <= LEAF_TOL, path
        assert float(jnp.abs(reference).max()) > 0.0, path


def test_bfloat16_where_the_configuration_says_float32_fails(compared):
    _, want = compared["reference"]
    _, got = compared["bfloat16"]
    named = family.grad_leaves(compared["cfg"])

    def at(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    errors = [rel(at(got, path), at(want, path)) for path in named]
    assert max(errors) > 10 * LEAF_TOL and sorted(errors)[1] > 4 * LEAF_TOL, (
        errors)


def test_the_drawn_temperatures_are_seen_through_the_query_projection(
        compared):
    """``family.init`` draws the temperatures from [0.5, 1.5], a KV head and
    layer its own; the reference with every temperature at the model's
    initial 1 — what a program that ignored them would compute — is told
    apart by the first layer's ``q`` and ``conv1_kernel`` leaves by more
    than the chip's ``grad_rel``, with no two-number leaf compared."""
    cfg = compared["cfg"]
    drawn, _ = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(11))
    temps = jnp.stack([drawn[f"layer_{i}"]["attn"]["temp"]
                       for i in range(2)])
    assert temps.shape == (2, 2) and len(set(np.asarray(temps).ravel())) == 4
    assert float(temps.min()) >= 0.5 and float(temps.max()) <= 1.5
    assert float(jnp.abs(temps - 1.0).min()) > 0.02
    other, _ = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(12))
    assert not jnp.array_equal(other["layer_0"]["attn"]["temp"], temps[0])

    params = jax.tree.map(lambda a: a, compared["params"])
    for i in range(2):
        params[f"layer_{i}"]["attn"]["temp"] = jnp.ones(2)
    with jax.default_matmul_precision("highest"):
        ignored = jax.jit(jax.grad(
            lambda p: family.reference_loss(cfg)(p, {}, compared["tokens"])))(
                params)
    _, want = compared["reference"]
    limit = published()["tolerances"]["grad_rel"]
    for leaf in ("q", "conv1_kernel"):
        got = ignored["layer_0"]["attn"][leaf]
        ref = want["layer_0"]["attn"][leaf]
        got, ref = (got["kernel"], ref["kernel"]) if leaf == "q" else (got,
                                                                       ref)
        assert rel(got, ref) > limit, leaf


def test_the_layers_note_their_sizes(compared):
    noted = compared["noted"]
    attn = [n for n in noted.values() if "attn.latent_channels" in n]
    # Float32 is the module's own form: no row's passes ran as kernels.
    assert attn == 2 * [{"attn.latent_channels": 64 * (4 + 2 * 2) * 128,
                         "attn.conv_taps": 64 * 6 * 128 * 4,
                         "attn.cca_kernel_rows": 0}]
    moe = [n for n in noted.values() if "moe.router_hidden" in n]
    assert len(moe) == 2 and all(
        n["moe.router_hidden"] == 16 and n["moe.skip_choice"] == 1
        and n["moe.assignments"] == 64
        # 3 · top-1 · 4 held ≥ 9 outputs: the window is every assignment.
        and n["moe.permuted_assignments"] == 64
        and n["moe.held_assignments"] == 64 * 4 // 9 for n in moe)
    assert [n for n in noted.values() if "lm.tied_head" in n] == [
        {"lm.tied_head": 1}]


@pytest.mark.parametrize("seq,dtype,attn,rows", [
    (128, jnp.bfloat16, "flash", 2 * 128), (128, jnp.bfloat16, "full", 0),
    (128, F32, "flash", 0), (72, jnp.bfloat16, "flash", 0)],
    ids=["kernels", "the_dense_oracle", "float32", "no_whole_strip"])
def test_the_layer_counts_the_rows_its_passes_ran_as_kernels(seq, dtype,
                                                             attn, rows):
    """``attn.cca_kernel_rows`` is ``B · T`` a layer where
    ``cca_passes._plan`` takes the kernels (lane-aligned heads, two-byte
    activations, a sequence in whole strips of rows, ``attn="flash"``) and
    0 where the module's ``jax.numpy`` runs."""
    layer = CompressedConvAttention(num_heads=4, kv_heads=2, head_dim=128,
                                    attn=attn, dtype=dtype)
    u = jax.ShapeDtypeStruct((2, seq, 32), dtype)
    noted = {}
    jax.eval_shape(noting_layers(
        lambda u: layer.init(jax.random.PRNGKey(0), u), noted), u)
    (counters,) = noted.values()
    assert counters["attn.cca_kernel_rows"] == rows
    assert counters["attn.latent_channels"] == 2 * seq * 8 * 128


def test_the_reference_takes_the_program_s_choice_inside_the_margin_only():
    """With a margin of nothing the reference keeps its own argmax whatever
    it is given; with a margin of 1 it takes whatever it is given: a held
    expert for every token is another loss, and the choice that computes
    nothing is worth what an expert held elsewhere is — nothing."""
    cfg = family_cfg(num_hidden_layers=1, sequence_length=32)
    params, _ = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(3))
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(1), 1))
    given = family.reference_given_choices(cfg)
    own = family.program_choices(cfg, params, tokens)
    held, elsewhere, skip = (jnp.full_like(own, e) for e in (0, 6, 8))
    with jax.default_matmul_precision("highest"):
        base = float(given(params, tokens, own, 0.0))
        assert float(given(params, tokens, held, 0.0)) == base
        forced = float(given(params, tokens, held, 1.0))
        assert abs(forced - base) > 1e-4
        nothing = float(given(params, tokens, skip, 1.0))
        assert nothing == float(given(params, tokens, elsewhere, 1.0))
        assert abs(forced - nothing) > 1e-4
