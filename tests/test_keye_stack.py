"""The Keye-VL-2.0 language tower (``models.transformer.KeyeLM``: pattern
``SE``) against the benchmark family's plain reference
(``benchmark/families/keye_vl2_lm.py``), at small sizes on the CPU:

* ``GroupedQueryAttention``'s per-head QK-norm and rotary positions against
  ``Attention``'s where the two are the same layer;
* the model against the reference — loss, named gradient leaves, ``S_t``
  itself; the gradient split (the indexer moves under ``L_I`` alone, the
  rest under the cross-entropy alone); what no output comparison sees, in
  the traced program: no approximate top-k, the indexer's input behind a
  ``stop_gradient``;
(the shares of an expert layer, the normal path through
``make_train_step``, scopes and counters: ``tests/test_keye_train.py``).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import keye_vl2_lm
from horovod_tpu.models import GroupedQueryAttention, index_losses
from horovod_tpu.models.transformer import Attention

from test_gated_delta import _equations
from test_hybrid_stack import rel


def family_cfg(compute_dtype="float32", **override):
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as fh:
        cfg = {**json.load(fh), **keye_vl2_lm.TINY, **override}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute_dtype}
    return cfg


def model_inputs(cfg, n=2, seed=5):
    params, aux = jax.jit(lambda k: keye_vl2_lm.init(cfg, k))(
        jax.random.PRNGKey(seed))
    # Norm scales off 1, so that a scale left out shows.
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: (a + 0.2 * jax.random.normal(next(keys), a.shape)
                         if path[-1].key == "scale" else a), params)
    tokens = keye_vl2_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


# ---------------------------------------- QK-norm and rotary on the layer


@pytest.mark.parametrize("heads,qk_norm", [(1, True), (2, False)],
                         ids=["one_head_normed", "two_heads_rotary_alone"])
def test_qk_norm_and_rotary_against_attention_s(heads, qk_norm):
    """``Attention`` norms the whole q and k vectors and
    ``GroupedQueryAttention`` each head's: with one head they are one
    layer; rotary positions are the same function on both.  Same weights
    (``qkv`` split into ``q`` and ``kv``), same output."""
    B, T, D, theta = 2, 48, 128, 1e4
    C = heads * D
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, C))
    theirs = Attention(heads, "full", dtype=jnp.float32, qk_norm=qk_norm,
                       norm_eps=1e-6, rope_theta=theta)
    p = theirs.init(jax.random.PRNGKey(1), x)["params"]
    ours = GroupedQueryAttention(heads, heads, D, attn="full",
                                 dtype=jnp.float32, qk_norm=qk_norm,
                                 norm_eps=1e-6, rope_theta=theta)
    mine = {"q": {"kernel": p["qkv"]["kernel"][:, :C]},
            "kv": {"kernel": p["qkv"]["kernel"][:, C:]},
            "proj": p["proj"]}
    if qk_norm:
        scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (D,))
        p = {**p, "q_norm": {"scale": scale}, "k_norm": {"scale": 2 * scale}}
        mine.update(q_norm=p["q_norm"], k_norm=p["k_norm"])
    with jax.default_matmul_precision("highest"):
        want = theirs.apply({"params": p}, x)
        got = ours.apply({"params": mine}, x)
        flash = ours.clone(attn="flash").apply({"params": mine}, x)
    assert rel(got, want) <= 1e-6 and rel(flash, want) <= 2e-6
    # The layer without the fields is the layer it was: same tree.
    plain = GroupedQueryAttention(heads, heads, D, attn="full",
                                  dtype=jnp.float32)
    assert set(plain.init(jax.random.PRNGKey(1), x)["params"]) == {
        "q", "kv", "proj"}


# ------------------------------------------- the model and the reference


@pytest.mark.parametrize("compute_dtype,loss_tol,grad_tol", [
    ("float32", 2e-6, 2e-4), ("bfloat16", 5e-3, 1.5e-1)])
def test_model_against_reference_loss(compute_dtype, loss_tol, grad_tol,
                                      capsys):
    """The program (kernels interpreted) against the plain reference on
    seeded weights: the loss, every named gradient leaf, and the selection
    itself — in float32 the program's ``S_t`` IS the reference's own top-k
    (no key differs); in bfloat16 the differing keys lie inside the
    margin."""
    cfg = family_cfg(compute_dtype)
    params, aux, tokens = model_inputs(cfg)
    loss_fn, ref_fn = keye_vl2_lm.loss_fn(cfg), keye_vl2_lm.reference_loss(
        cfg)
    got, g = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, aux, tokens)[0]))(params)
    want, w = jax.jit(jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens)))(params)
    assert abs(float(got) - float(want)) <= loss_tol * float(want)
    leaves = keye_vl2_lm.grad_leaves(cfg)
    assert ("layer_0", "attn", "index_w", "kernel") in leaves
    assert ("layer_2", "attn", "q_norm", "scale") in leaves
    errors = {"/".join(path): rel(
        jax.tree_util.tree_reduce(lambda t, k: t[k], path, g),
        jax.tree_util.tree_reduce(lambda t, k: t[k], path, w))
        for path in leaves}
    assert max(errors.values()) <= grad_tol, errors
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"bench": "selection"')]
    pairs = 2 * 2 * sum(min(t + 1, 16) for t in range(64))
    assert lines and all(l["chosen"] == pairs for l in lines)
    assert all(l["beyond_margin_share"] == 0.0 for l in lines)
    if compute_dtype == "float32":
        assert all(l["disagreeing_share"] == 0.0 for l in lines)


def test_reference_takes_the_program_s_selection_only_where_it_is_a_tie():
    """The reference given OTHER sets than its own: inside the margin it
    follows them; beyond it, or where a set is not ``min(t + 1, topk)``
    causal keys, it keeps its own (one layer, so that a query's own set
    does not depend on what the other queries were given).  And the
    precision control: the same mathematics in bfloat16 is another
    number."""
    cfg = family_cfg("float32", num_hidden_layers=1)
    params, aux, tokens = model_inputs(cfg)
    given = jax.jit(keye_vl2_lm.reference_given_choices(cfg))
    select, experts = keye_vl2_lm.program_choices(cfg, params, tokens)
    want = float(given(params, tokens, select, experts, 0.0, 0.0))
    # The most recent 16 keys in place of the indexer's choice.
    T = select.shape[-1]
    recent = jnp.tril(jnp.ones((T, T), jnp.int8)) - jnp.tril(
        jnp.ones((T, T), jnp.int8), -16)
    recent = jnp.broadcast_to(recent, select.shape)
    assert float(given(params, tokens, recent, experts, 0.0, 0.0)) == want
    assert float(given(params, tokens, recent, experts, 1e3, 0.0)) != want
    # A set of fewer keys is no tie at any margin; nor one with a key of
    # the future.
    fewer = select.at[..., 0].set(0)
    assert float(given(params, tokens, fewer, experts, 1e3, 0.0)) == want
    future = recent.at[..., 20, 40].set(1).at[..., 20, 20].set(0)
    assert float(given(params, tokens, future, experts, 1e3, 0.0)) == float(
        given(params, tokens, recent.at[..., 20, :].set(select[..., 20, :]),
              experts, 1e3, 0.0))
    low = keye_vl2_lm.reference_given_choices(cfg, dtype="bfloat16")
    assert abs(float(low(params, tokens, select, experts, 0.0, 0.0))
               - want) > 1e-4 * want


def test_the_indexer_moves_under_its_kl_alone_and_the_rest_under_ce_alone():
    """The gradient split: the cross-entropy's gradient is zero on the
    indexer's three matrices (the selection is discrete and the indexer's
    input detached), and ``L_I``'s is zero on everything else (``p``
    detached, and nothing upstream of the normed input reached)."""
    cfg = family_cfg("float32")
    params, aux, tokens = model_inputs(cfg)
    model = keye_vl2_lm._model(cfg)

    def parts(p):
        from horovod_tpu.ops.losses import fused_softmax_xent
        h, state = model.apply({"params": p}, tokens[:, :-1],
                               return_hidden=True, mutable=["intermediates"])
        ce = fused_softmax_xent(h.reshape(-1, cfg["hidden_size"]),
                                p["head"]["kernel"],
                                tokens[:, 1:].reshape(-1)).mean()
        return ce, index_losses(state["intermediates"])

    g_ce = jax.jit(jax.grad(lambda p: parts(p)[0]))(params)
    g_kl = jax.jit(jax.grad(lambda p: parts(p)[1]))(params)

    def is_indexer(path):
        return any(getattr(k, "key", "").startswith("index_") for k in path)

    for (path, ce), kl in zip(jax.tree_util.tree_leaves_with_path(g_ce),
                              jax.tree.leaves(g_kl)):
        where = jax.tree_util.keystr(path)
        if is_indexer(path):
            assert float(jnp.abs(ce).max()) == 0.0, where
            assert float(jnp.abs(kl).max()) > 0.0, where
        else:
            assert float(jnp.abs(kl).max()) == 0.0, where
            assert float(jnp.abs(ce).max()) > 0.0, where
    got = jax.jit(jax.grad(
        lambda p: keye_vl2_lm.loss_fn(cfg)(p, aux, tokens)[0]))(params)
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (got, g_ce, g_kl))):
        np.testing.assert_allclose(a, b + c, rtol=1e-5, atol=1e-8)


def test_the_selection_is_exact_and_the_indexer_s_input_detached():
    """In the traced program (what the comparison with the reference
    cannot see): no approximate top-k anywhere, forward or backward; each
    of the indexer's three projections reads a ``stop_gradient`` of the
    layer's normed input; and the selection map the kernels read is int8,
    (B, T, T)."""
    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    loss_fn = keye_vl2_lm.loss_fn(cfg)
    for fn in (lambda p: loss_fn(p, aux, tokens)[0],
               jax.grad(lambda p: loss_fn(p, aux, tokens)[0])):
        names = {e.primitive.name for e in _equations(
            jax.make_jaxpr(fn)(params).jaxpr)}
        assert not any("approx" in n for n in names), names
    eqns = list(_equations(jax.make_jaxpr(
        lambda p: loss_fn(p, aux, tokens)[0])(params).jaxpr))
    made_by = {v: e for e in eqns for v in e.outvars}
    projections = [e for e in eqns if e.primitive.name == "dot_general"
                   and any(f"/index_{m}" in str(e.source_info.name_stack)
                           for m in "qkw")]
    assert len(projections) == 6          # 2 layers x 3 projections
    for e in projections:
        source = made_by[e.invars[0]]
        while source.primitive.name == "convert_element_type":
            source = made_by[source.invars[0]]
        assert source.primitive.name == "stop_gradient"
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    maps = [v.aval for e in kernels for v in e.invars
            if v.aval.dtype == jnp.int8]
    T = cfg["sequence_length"]
    assert maps and all(a.shape == (2, T, T) for a in maps)


