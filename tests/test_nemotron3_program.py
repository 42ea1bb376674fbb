"""What ``latent`` and ``mtp`` add to the program and what they leave
alone: a ``TransformerLM(mtp=...)`` takes one more id a sequence and gives a
pair; ``multi_token_xent`` is the weighted sum of two plain cross-entropies;
the refusals name the new fields; a latent layer is the same in every
form a held share's window takes; and without a latent and a prediction
module tiny stacks of every other family the benchmark runs lower to the
text the parent commit lowered them to.  The reference comparisons are
``tests/test_nemotron3_stack.py``'s, the shares ``test_nemotron3_shares.py``'s.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    GraniteHybridLM, KeyeLM, Nemotron3SuperLM, NemotronHLM, OLMoELM,
    OlmoHybridLM, TransformerLM, Zaya1LM)
from horovod_tpu.ops.losses import multi_token_xent
from horovod_tpu.parallel.moe import DroplessMoE

F32 = jnp.float32


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


# ------------------------------- the other families' programs are unmoved

SSM = dict(num_heads=4, head_dim=8, n_groups=2, state_size=8, conv_kernel=4,
           chunk=8)
OTHERS = {
    "nemotron_h": (lambda: NemotronHLM(
        vocab=64, dim=32, pattern="M*E", num_heads=2, kv_heads=1,
        head_dim=16, attn="full", ssm=SSM, moe_experts=8, moe_top_k=3,
        moe_hidden=16, dtype=F32,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                 activation="relu2", shared_hidden=32, held=(2, 2))),
        "d0d8eb827e799a93"),
    "granite_hybrid": (lambda: GraniteHybridLM(
        vocab=64, dim=32, pattern="ma", num_heads=2, kv_heads=1, head_dim=16,
        attn="full", mlp_hidden=48, ssm={**SSM, "n_groups": 1}, dtype=F32),
        "18b1b81f01a61398"),
    "keye": (lambda: KeyeLM(
        vocab=64, dim=32, pattern="SE", num_heads=2, kv_heads=1, head_dim=16,
        attn="full", indexer=dict(num_heads=2, head_dim=8, topk=4),
        moe_experts=8, moe_top_k=2, moe_hidden=16, dtype=F32,
        moe=dict(router="softmax", renormalize=True, activation="swiglu",
                 held=(0, 2))), "c0f6f52a244180f3"),
    "zaya1": (lambda: Zaya1LM(
        vocab=64, dim=32, pattern="ZZ", num_heads=2, kv_heads=1, head_dim=16,
        attn="full", moe_experts=4, moe_top_k=1, moe_hidden=16, dtype=F32,
        moe=dict(router="mlp", router_hidden=8, skip_choice=True,
                 activation="swiglu", held=(0, 2))), "5fc191a9efbe46a3"),
    "olmo_hybrid": (lambda: OlmoHybridLM(
        vocab=64, dim=32, pattern="LF", num_heads=2, attn="full",
        mlp_hidden=48, dtype=F32,
        lin=dict(num_heads=2, key_dim=8, value_dim=16, conv_kernel=4,
                 chunk=8, allow_neg_eigval=True)), "016a39b5859ce892"),
    "olmoe": (lambda: OLMoELM(
        vocab=64, dim=32, depth=1, num_heads=2, attn="full", moe_experts=8,
        moe_top_k=2, moe_hidden=16, dtype=F32), "ea43dcfe9dba1e8a"),
}


@pytest.mark.parametrize("name", OTHERS)
def test_without_a_latent_and_a_prediction_module_the_stacks_lower_as_they_did(
        name):
    """A tiny stack of each family the benchmark's other cells run, loss
    and gradients, lowers to the text — to the letter — that the commit
    before ``latent`` and ``mtp`` lowered it to (SHA-256 taken there,
    43bb0d2, PR 46): with ``latent=0`` and ``mtp=None`` neither field
    leaves a trace.  The three stacks that hold a share of their experts
    (``nemotron_h``, ``keye``, ``zaya1``) lower to what PR 53 made of the
    held share — windows that follow the load, nothing levelled — and
    their digests were taken at its tree, still with neither field."""
    build, digest = OTHERS[name]
    model = build()
    assert model.mtp is None and not dict(model.moe or {}).get("latent")
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 64
    # A lowering reads the parameters' shapes alone: none is drawn (an eager
    # ``init`` runs the stack op by op, 20 to 39 s a case; PR 56).
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens)["params"])

    def loss(p):
        return model.apply({"params": p}, tokens,
                           return_hidden=True).astype(F32).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest, name


# ------------------------------------------------ the call and its refusals


def tiny(**over):
    fields = dict(vocab=64, dim=32, pattern="ME", num_heads=2, kv_heads=1,
                  head_dim=16, attn="full", ssm=SSM, moe_experts=8,
                  moe_top_k=3, moe_hidden=16, dtype=F32,
                  moe=dict(router="sigmoid", renormalize=True,
                           gate_scale=5.0, activation="relu2",
                           shared_hidden=32, latent=8))
    fields.update(over)
    return Nemotron3SuperLM(**fields)


def test_the_call_takes_one_more_id_and_gives_a_pair():
    model = tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 19), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
    h, h2 = model.apply({"params": params}, tokens[:, :-1],
                        return_hidden=True)
    assert h.shape == h2.shape == (2, 17, 32)
    logits, logits2 = model.apply({"params": params}, tokens[:, :-1])
    assert logits.shape == logits2.shape == (2, 17, 64)
    np.testing.assert_allclose(logits2, h2 @ params["head"]["kernel"],
                               rtol=2e-5, atol=2e-5)
    # The stack's half is the model without the module on the same ids.
    plain = tiny(mtp=None)
    stack_only = {k: v for k, v in params.items() if k != "mtp"}
    np.testing.assert_array_equal(
        h, plain.apply({"params": stack_only}, tokens[:, :-2],
                       return_hidden=True))
    # The loss of both: the plain means, weighted.
    loss = multi_token_xent((h, h2), params["head"]["kernel"], tokens,
                            (1.0, 0.1))

    def ce(lg, labels):
        lg = lg.reshape(-1, 64)
        return (jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, labels.reshape(-1, 1), -1)[:, 0]).mean()

    want = ce(logits, tokens[:, 1:-1]) + 0.1 * ce(logits2, tokens[:, 2:])
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    with pytest.raises(ValueError, match="scored on"):
        multi_token_xent((h, h2), params["head"]["kernel"], tokens[:, :-1],
                         (1.0, 0.1))
    with pytest.raises(ValueError, match="scored on"):
        multi_token_xent((h, h2), params["head"]["kernel"], tokens, (1.0,))


def test_the_refusals_name_the_new_fields():
    tokens = jnp.zeros((1, 9), jnp.int32)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="multi-token prediction"):
        TransformerLM(vocab=64, dim=32, depth=1, num_heads=2, tp_axis="tp",
                      mtp=dict(pattern="*")).init(key, tokens)
    with pytest.raises(ValueError, match="mtp=.*belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, depth=1, num_heads=2,
                      mtp=dict(pattern="*")).init(key, tokens)
    with pytest.raises(ValueError, match="ONE prediction module"):
        tiny(mtp=dict(pattern="Z")).init(key, tokens)
    with pytest.raises(ValueError, match="ONE prediction module"):
        tiny(mtp=dict(pattern="*E", depth=2)).init(key, tokens)
    with pytest.raises(ValueError, match="untied head"):
        tiny(tie_head=True).init(key, tokens)


LATENT_LAYER = dict(num_experts=8, hidden=16, top_k=3, router="sigmoid",
                    renormalize=True, gate_scale=5.0, activation="relu2",
                    shared_hidden=32, latent=8, held=(2, 2), dtype=F32)


@functools.cache
def latent_layer_at_its_own_window():
    """The layer, its parameters, its input, and its value and gradients
    under the library's own window — what every case below compares with,
    made once (the same arrays a case: 18 s each before PR 56)."""
    from horovod_tpu.parallel import moe

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 12), F32)
    layer = DroplessMoE(**LATENT_LAYER)
    params = layer.init(jax.random.PRNGKey(1), x)
    # 48 tokens x top-3 x 2 of 8 held = 36 rows a uniform load.
    plan = moe._window_plan(assignments=144, held=2, routed=8, row_bytes=32,
                            expert_bytes=2 * 2 * 8 * 16 * 4)
    assert plan == moe.WindowPlan(40, 4, 36)
    want = jax.value_and_grad(
        lambda p: (layer.apply(p, x)[0] ** 2).sum())(params)
    return layer, params, x, want


@pytest.mark.parametrize("rows", [16, 24, 144])
def test_a_latent_layer_is_the_same_at_every_window_of_the_held_share(
        rows, monkeypatch):
    """The held share's rows move in two forms — windows of ``W`` sorted
    rows, as many as the landed assignments fill, and, where a window is
    every assignment, gathers through the sort's permutation.  With the
    experts in a latent each gives the output and gradients of the
    library's own window (``_window_plan``: 40 rows, the uniform load of
    36 in whole sublanes): at 16 and 24 more windows run, at 144 the
    window is every assignment; the lowered program holds the window's
    rows at the LATENT's width."""
    from horovod_tpu.parallel import moe

    layer, params, x, want = latent_layer_at_its_own_window()

    def value_and_grads():
        return jax.value_and_grad(
            lambda p: (layer.apply(p, x)[0] ** 2).sum())(params)

    monkeypatch.setattr(
        moe, "_window_plan",
        lambda **shapes: moe.WindowPlan(rows, -(-144 // rows), 36))
    got = value_and_grads()
    assert max(jax.tree.leaves(jax.tree.map(rel, got, want))) < 1e-5
    (_, sown) = layer.apply(params, x, mutable=["intermediates"])
    sown = sown["intermediates"]
    assert int(sown["held_windows"][0]) == -(
        -int(sown["held_assignments"][0]) // rows)
    text = jax.jit(lambda p: layer.apply(p, x)[0]).lower(params).as_text()
    assert f"tensor<{rows}x8xf32>" in text
