"""The JoyAI-LLM-Flash stack (``JoyAIFlashLM``): latent attention in every
layer, a leading dense layer, expert layers whose sigmoid router chooses by
``s + b`` with ``b`` a state no gradient touches, and the prediction module
of one such layer — the benchmark family's plain float32 reference against
the program (loss and every gradient leaf on seeded weights), the tiny
preset through ``make_train_step`` with the bias in ``aux_state`` moving by
exactly ±γ a step (``tests/test_joyai_train.py``), and the parameter counts
of the published model and of the cell's cut.  The attention module alone
is ``tests/test_latent_attention.py``'s, its kernels
``tests/test_flash_split_widths.py``'s.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import joyai_flash_lm as family
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import JoyAIFlashLM

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GAMMA = 1e-3


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as fh:
        return json.load(fh)


def at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# ------------------------------------------------- the tree, by count


def count(model):
    made = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 65), jnp.int32)),
        jax.random.PRNGKey(0))
    n = {name: sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree))
         for name, tree in made.items()}
    return n["params"], n.get("balance", 0), made["params"]


# By hand (ISSUE 50): a layer's attention with its two latents' norms, and a
# layer with its two pre-norms of 2,048.
ATTENTION = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
             + 512 * 32 * 256 + 32 * 128 * 2048)
DENSE_LAYER = ATTENTION + 3 * 2048 * 7168 + 2 * 2048
EXPERT = 3 * 2048 * 768


def expert_layer(held):
    """Router, shared expert, ``held`` experts; the 256 biases are state."""
    return ATTENTION + 2048 * 256 + (held + 1) * EXPERT + 2 * 2048


def test_parameter_counts_the_published_model_and_the_cut():
    """The published constructor without its prediction module: the dense
    layer, 39 expert layers of 256 experts, embedding, final norm and head —
    48.94 B, the catalog's 48B — and the module's own expert layer, three
    norms and ``eh_proj`` behind them.  The issue's 48,942,542,592 counts
    256 balancing biases in each of 39 layers; this tree holds them as
    STATE (the collection ``"balance"``: 9,984 and the module's 256), so
    its parameters are that many fewer.  The cell's cut likewise: the
    issue's 680,441,088 less 256 in each of five expert layers."""
    assert (ATTENTION, DENSE_LAYER) == (26_347_520, 70_391_808)
    assert expert_layer(256) == 1_239_554_304 - 256
    assert expert_layer(16) == 107_092_224 - 256
    stack = (DENSE_LAYER + 39 * expert_layer(256) + 2 * 129280 * 2048 + 2048)
    assert stack == 48_942_542_592 - 39 * 256
    n, state, params = count(JoyAIFlashLM(mtp=None))
    assert (n, state) == (stack, 39 * 256)
    assert JoyAIFlashLM().pattern == "d" + "x" * 39
    module = 3 * 2048 + 2 * 2048 * 2048 + expert_layer(256)
    n, state, params = count(JoyAIFlashLM())
    assert (n, state) == (stack + module, 40 * 256)
    assert params["layer_0"]["mlp"]["gate"]["kernel"].shape == (2048, 7168)
    assert params["layer_1"]["moe"]["w_gate"].shape == (256, 2048, 768)
    assert params["layer_1"]["moe"]["shared"]["w_gate"].shape == (2048, 768)
    assert params["layer_39"]["attn"]["q_b"]["kernel"].shape == (1536, 6144)
    assert set(params["mtp"]) == {"n_e", "n_h", "eh_proj", "layer_0", "n_m"}

    cfg = published()
    cut, state, _ = count(family._model(cfg))
    module = 3 * 2048 + 2 * 2048 * 2048 + expert_layer(16)
    assert module == 115_486_976 - 256
    assert cut == (DENSE_LAYER + 4 * expert_layer(16) + 2 * 16160 * 2048
                   + 2048 + module)
    assert (cut, state) == (680_441_088 - 5 * 256, 5 * 256)
    assert cut == 680_439_808


# ------------------------- program against the family's plain reference


def family_cfg(compute="float32", **over):
    """The dense layer, ONE expert layer and the module at the published
    head widths: 2 heads, 4 of 16 experts held, top-3."""
    cfg = published()
    cfg.update({k: v for k, v in family.TINY.items() if k != "tolerances"})
    cfg["num_hidden_layers"] = 2
    cfg.update(training={**cfg["training"], "compute_dtype": compute,
                         "bias_update_speed": GAMMA},
               tolerances={**cfg["tolerances"], "tie_margin": 1e-6})
    cfg.update(over)
    return cfg


def biased(aux, seed=9):
    """``aux`` with every bias off zero, so that its place shows."""
    leaves, tree = jax.tree.flatten(aux)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        0.05 * jax.random.normal(k, a.shape) for k, a in zip(keys, leaves)])


@pytest.fixture(scope="module")
def compared():
    """Loss and every gradient leaf of the program in float32, of the
    program in bfloat16 and of the reference, on one seeded batch under a
    bias that is not zero; the kernels interpreted."""
    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(11))
    aux = biased(aux)
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(5), 1))
    noted = {}
    out = {"cfg": cfg, "params": params, "aux": aux, "tokens": tokens}
    zero = jax.tree.map(jnp.zeros_like, aux)
    with jax.default_matmul_precision("highest"):
        for name, fn in (
                ("float32", noting_layers(family.loss_fn(cfg), noted)),
                ("reference", lambda p, a, t: (
                    family.reference_loss(cfg)(p, a, t), a))):
            both = jax.jit(jax.value_and_grad(fn, has_aux=True))
            (loss, _), grads = both(params, aux, tokens)
            out[name] = (float(loss), grads)
            # The same two programs under a bias of nothing.
            out[name + "_unbiased"] = float(both(params, zero, tokens)[0][0])
    (loss, _), grads = jax.jit(jax.value_and_grad(
        family.loss_fn(family_cfg("bfloat16")), has_aux=True))(
            params, aux, tokens)
    out["bfloat16"] = (float(loss), grads)
    out["noted"] = noted
    return out


# The program in float32 differs from the reference in the order of its sums
# (flash against a held softmax, rotate-half on permuted columns against
# adjacent pairs, the window's grouped rows against a masked matmul over
# every token, the fused head against held logits); the same program in
# bfloat16 — the nearest precision below the one this test's configuration
# states — must fail the same bounds on every named leaf.
LOSS_TOL, LEAF_TOL = 2e-6, 2e-4


def test_loss_and_every_leaf_against_the_plain_reference(compared):
    want_loss, want = compared["reference"]
    got_loss, got = compared["float32"]
    assert abs(got_loss - want_loss) <= LOSS_TOL * abs(want_loss)
    errors = jax.tree.map(rel, got, want)
    assert max(jax.tree.leaves(errors)) <= LEAF_TOL, errors
    named = family.grad_leaves(compared["cfg"])
    for must in (("layer_0", "attn", "kv_a", "kernel"),
                 ("layer_0", "attn", "q_b", "kernel"),
                 ("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "attn", "proj", "kernel"),
                 ("mtp", "eh_proj", "kernel"), ("head", "kernel")):
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
    """Under another bias the reference gives another loss: ``b`` enters the
    choice, on both sides from ``aux``."""
    got, want = compared["float32_unbiased"], compared["reference_unbiased"]
    assert abs(got - want) <= LOSS_TOL * abs(want)
    assert abs(want - compared["reference"][0]) > 10 * LOSS_TOL * abs(want)


def test_the_layers_note_their_sizes(compared):
    noted = compared["noted"]
    moe = [n for n in noted.values() if "moe.assignments" in n]
    assert len(moe) == 2 and all(
        n["moe.bias_updates"] == 1 and n["moe.assignments"] == 64 * 3
        and n["moe.held_assignments"] == 64 * 3 * 4 // 16 for n in moe)
    attn = [n for n in noted.values() if "attn.q_latent" in n]
    assert len(attn) == 3 and all(
        (n["attn.qk_head_dim"], n["attn.v_head_dim"],
         n["attn.padded_lanes"]) == (192, 128, 64) for n in attn)
    assert ("mtp", "layer_0", "moe") in noted and ("mtp",) in noted
