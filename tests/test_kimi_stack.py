"""The Kimi-Linear stack (``KimiLinearLM``): Kimi Delta Attention in three
layers of four beside latent attention without positions and without a
query latent, a leading dense layer, expert layers whose sigmoid router
chooses by ``s + b`` — the benchmark family's plain float32 reference
against the program (loss and every gradient leaf on seeded weights, the
named leaves of each kind of layer among them), the same program in
bfloat16 failing the same bounds, and the parameter counts of the published
model and of the cell's cut.  The shares, the normal path and the span ring
are ``tests/test_kimi_train.py``'s; the mixer's chunked rule alone
``tests/test_gated_delta.py``'s; scopes, counters and the programs this
leaves unmoved ``tests/test_kimi_program.py``'s.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import kimi_linear_lm as family
from horovod_tpu.models import KimiLinearLM

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as fh:
        return json.load(fh)


def at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# ------------------------------------------------- the tree, by count

D = 2304
KDA = (3 * D * 4096 + D * 32 + 2 * (D * 128 + 128 * 4096) + 4096 * D
       + 4 * 3 * 4096 + 32 + 4096 + 128)
LATENT = D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + 32 * 128 * D
EXPERT = 3 * D * 1024


def expert_part(held):
    """Router, shared expert, ``held`` experts; the 256 biases are state."""
    return D * 256 + (held + 1) * EXPERT


def count(model):
    made = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32)),
        jax.random.PRNGKey(0))
    n = {name: sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree))
         for name, tree in made.items()}
    return n["params"], n.get("balance", 0), made["params"]


def test_parameter_counts_the_published_model_and_the_cut():
    """By hand from the catalog's row (ISSUE 62): a KDA mixer 39,514,272, a
    latent mixer 29,114,880, the dense SwiGLU 63,700,992, an expert
    7,077,888, a router 589,824 (the issue's 590,080 counts the layer's 256
    balancing biases, which this tree holds as STATE).  The published stack
    — 20 KDA and 7 latent layers, one dense and 26 of 256 experts —
    is 49.1 B, the model's "48B"; the cell's cut holds 8 experts a layer."""
    assert (KDA, LATENT, 3 * D * 9216, EXPERT, D * 256) == (
        39_514_272, 29_114_880, 63_700_992, 7_077_888, 589_824)
    whole = (20 * KDA + 7 * LATENT + 3 * D * 9216 + 26 * expert_part(256)
             + 27 * 2 * D + 2 * 163840 * D + D)
    n, state, params = count(KimiLinearLM())
    assert (n, state) == (whole, 26 * 256) and 48e9 < n < 50e9
    assert KimiLinearLM().pattern == "kKKx" + "KKKx" * 5 + "KKx"
    assert [i + 1 for i, c in enumerate(KimiLinearLM().pattern)
            if c in "dx"] == published()["linear_attn_config"][
                "full_attn_layers"]
    assert params["layer_0"]["mlp"]["gate"]["kernel"].shape == (D, 9216)
    assert params["layer_1"]["moe"]["w_gate"].shape == (256, D, 1024)
    assert params["layer_3"]["attn"]["q_b"]["kernel"].shape == (D, 6144)
    assert params["layer_26"]["attn"]["kv_a"]["kernel"].shape == (D, 576)
    assert params["layer_25"]["lin"]["f_b"]["kernel"].shape == (128, 4096)
    assert params["layer_25"]["lin"]["dt_bias"].shape == (4096,)

    cfg = published()
    assert family.pattern(cfg) == "kKKxK"
    cut, state, _ = count(family._model(cfg))
    held = cfg["num_experts"]
    assert cut == (4 * KDA + LATENT + 3 * D * 9216 + 4 * expert_part(held)
                   + 5 * 2 * D + 2 * cfg["vocab_size"] * D + D)
    assert state == 4 * 256
    # 602.4 M held here (6.73 GiB at 12 bytes); 16 held would be 828.9 M.
    assert (held, cut) == (8, 602_433_408)
    assert cut + 4 * 8 * EXPERT == 828_925_824


# ------------------------- program against the family's plain reference


def family_cfg(compute="float32", **over):
    """The tiny preset cut to a layer of each kind (k x K: a KDA layer with
    the dense SwiGLU, a latent layer with experts, a KDA layer with
    experts), two heads, the latent layer's at the published head widths, 4
    of 16 experts held, top-3."""
    cfg = published()
    cfg.update({k: v for k, v in family.TINY.items() if k != "tolerances"})
    cfg.update(num_hidden_layers=3, linear_attn_config={
        **cfg["linear_attn_config"], "kda_layers": [1, 3],
        "full_attn_layers": [2]})
    cfg.update(training={**cfg["training"], "compute_dtype": compute},
               tolerances={**cfg["tolerances"], "tie_margin": 1e-6})
    cfg.update(over)
    return cfg


def seeded(cfg):
    params, aux = family.init(cfg, jax.random.PRNGKey(1))
    # Vectors off their initial 0 or 1 and the bias off zero, so that a side
    # that ignored one would show.
    params = jax.tree.map(
        lambda a: a * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape)) if a.ndim == 1 else a,
        params)
    aux = jax.tree.map(lambda a: 0.02 * jax.random.normal(
        jax.random.PRNGKey(5), a.shape), aux)
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(0), 2))
    return params, aux, tokens


@pytest.fixture(scope="module")
def compared():
    """One compile a side: the program's choices are read once and handed
    to the reference (``reference_loss`` does both in one program — the
    train test's, and the benchmark's)."""
    cfg = family_cfg()
    params, aux, tokens = seeded(cfg)
    theirs = jax.jit(lambda p, a: family.program_expert_choices(
        cfg, p, a, tokens))(params, aux)
    given = family.reference_given_choices(cfg)
    reference = jax.jit(jax.value_and_grad(
        lambda p, a: given(p, a, tokens, theirs, 1e-6)))
    out = {"cfg": cfg, "reference": reference(params, aux)}
    for compute in ("float32", "bfloat16"):
        loss_fn = family.loss_fn(family_cfg(compute))
        out[compute] = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0]))(params)
    out["reference_unbiased"] = float(reference(
        params, jax.tree.map(jnp.zeros_like, aux))[0])
    return out


# The program in float32 differs from the reference in the order of its sums
# (the chunked rule against the recurrence, flash against a held softmax, the
# window's grouped rows against a masked matmul over every token, the fused
# head against held logits); the same program in bfloat16 — the nearest
# precision below the one this test's configuration states — must fail the
# same bounds on every named leaf.
LOSS_TOL, LEAF_TOL = 2e-6, 2e-4


def test_loss_and_every_leaf_against_the_plain_reference(compared):
    want_loss, want = compared["reference"]
    got_loss, got = compared["float32"]
    assert abs(got_loss - want_loss) <= LOSS_TOL * abs(want_loss)
    errors = jax.tree.map(rel, got, want)
    assert max(jax.tree.leaves(errors)) <= LEAF_TOL, errors
    named = family.grad_leaves(compared["cfg"])
    assert family.pattern(compared["cfg"]) == "kxK"
    for must in (("layer_0", "lin", "f_a", "kernel"),
                 ("layer_0", "lin", "f_b", "kernel"),
                 ("layer_0", "lin", "g_b", "kernel"),
                 ("layer_0", "lin", "A_log"),
                 ("layer_0", "lin", "q", "kernel"),
                 ("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "moe", "w_gate"),
                 ("layer_1", "attn", "q_b", "kernel"),
                 ("layer_1", "attn", "kv_b", "kernel"),
                 ("layer_2", "lin", "f_b", "kernel"), ("head", "kernel")):
        assert must in named
    for path in named:
        assert at(errors, path) <= LEAF_TOL, path
        assert float(jnp.abs(at(want, path)).max()) > 0.0, path


def test_bfloat16_where_the_configuration_says_float32_fails(compared):
    _, want = compared["reference"]
    _, got = compared["bfloat16"]
    errors = [rel(at(got, path), at(want, path))
              for path in family.grad_leaves(compared["cfg"])]
    assert min(errors) > 25 * LEAF_TOL, errors


def test_the_bias_chooses_and_the_reference_reads_the_same_one(compared):
    want = compared["reference"][0]
    assert abs(compared["reference_unbiased"] - want) > 10 * LOSS_TOL * abs(
        want)


# ------------------------------------- what the reference says of choices


def test_routing_numbers_and_a_choice_beyond_the_margin_breaks_no_tie():
    """:func:`reference_routing` gives what the accepted families print from
    a debug callback, as values.  The float32 program's own choices are the
    reference's (none disagrees).  Choices moved to the next expert disagree,
    lie beyond a margin of 1e-6 where they span a larger gap — and there the
    reference keeps its own choice: its loss is the loss under the
    program's."""
    cfg = family_cfg()
    params, aux, tokens = seeded(cfg)
    theirs = jax.jit(lambda p, a: family.program_expert_choices(
        cfg, p, a, tokens))(params, aux)
    moved = (theirs + 1) % cfg["experts_routed_over"]
    given = jax.jit(family.reference_given_choices(cfg))
    routing = jax.jit(family.reference_routing(cfg))
    own = routing(params, aux, tokens, theirs, 1e-6)
    assert own["assignments"] == 2 * theirs.shape[2] * 2 * 3
    assert float(own["disagreeing_share"]) == 0.0
    assert float(own["largest_gap"]) == 0.0
    other = routing(params, aux, tokens, moved, 1e-6)
    assert float(other["disagreeing_share"]) > 0.2
    assert 0.0 < float(other["beyond_margin_share"]) <= float(
        other["disagreeing_share"])
    assert float(other["largest_gap"]) > 1e-6
    at_theirs = float(given(params, aux, tokens, theirs, 1e-6))
    at_moved = float(given(params, aux, tokens, moved, 1e-6))
    assert abs(at_moved - at_theirs) <= LOSS_TOL * abs(at_theirs)
    # Inside a margin that spans every gap the moved choices are taken.
    assert abs(float(given(params, aux, tokens, moved, 2.0)) - at_theirs) > (
        100 * LOSS_TOL * abs(at_theirs))


def test_the_harness_s_reference_holds_no_host_callback():
    """jax writes no program that holds a host callback to its persistent
    compile cache, and the harness's two programs of ``reference_loss``
    compile for minutes at the cell's sizes: with one, every run of the
    cell paid them (the set-up passed the driver's clock: PR 62)."""
    cfg = family_cfg()
    params, aux, tokens = seeded(cfg)
    reference = family.reference_loss(cfg)
    for f in (reference, jax.grad(reference)):
        assert "callback" not in str(jax.make_jaxpr(f)(params, aux, tokens))
