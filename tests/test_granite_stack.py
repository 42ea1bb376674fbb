"""The Granite 4.0-H stack (``GraniteHybridLM``; pattern letters ``m`` and
``a``): two PRE-norm sub-layers a layer with a residual multiplier, the
embedding multiplier, the logits' scaling, the tied head; the mixer with
ONE group of B and C over every head and one gated norm over all its
channels, through the kernels (interpreted) where the plans take them; and
the benchmark family's plain float32 reference against the program — loss
and named gradient leaves on seeded weights.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import granite_hybrid_lm as family
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.metrics import registry
from horovod_tpu.models import GraniteHybridLM, TransformerLM
from horovod_tpu.models.ssm import Mamba2Mixer
from horovod_tpu.models.transformer import (
    GroupedQueryAttention, PatternLayer, SwiGLU)
from horovod_tpu.ops import _pallas, ssd
from horovod_tpu.parallel.mesh import RANKS_AXIS

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as fh:
        return json.load(fh)


# ------------------------------------------------- the tree, by count


def count(model):
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)), params


def test_parameter_counts_the_cut_and_the_published_model():
    """By hand (ISSUE 38): a mixer 25,847,232 (in_proj 2048 x 8512, conv
    4 x 4352 + 4352, dt_bias, A_log, D 3 x 64, gate norm 4096, out_proj
    4096 x 2048), a SwiGLU 50,331,648, two norms 4,096 -> a mixer layer
    76,182,976; attention 10,485,760 -> 60,821,504; the tied table
    vocab x 2048, the final norm 2,048.  The family builds the cut's tree
    from the configuration file, ``GraniteHybridLM()`` the published one."""
    mixer_layer = 25_847_232 + 50_331_648 + 4_096
    attn_layer = 10_485_760 + 50_331_648 + 4_096
    assert (mixer_layer, attn_layer) == (76_182_976, 60_821_504)
    cfg = published()
    n, params = count(family._model(cfg))
    assert n == 9 * mixer_layer + attn_layer + 12_544 * 2048 + 2048
    assert n == 772_160_448
    assert "head" not in params                       # the table is the head
    assert params["layer_0"]["ssm"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert params["layer_5"]["attn"]["kv"]["kernel"].shape == (2048, 1024)
    assert [k for k in params["layer_5"]] == ["attn", "mlp", "mlp_norm",
                                              "norm"]
    n40, _ = count(GraniteHybridLM())
    assert n40 == 36 * mixer_layer + 4 * attn_layer + 100_352 * 2048 + 2048
    assert n40 == 3_191_396_096
    assert family.pattern(cfg) == "mmmmmammmm"
    assert GraniteHybridLM().pattern == "".join(
        family.LETTER[kind] for kind in cfg["layer_types"])


# ------------------------------------------------ the layer form, by hand


def test_layer_form_two_pre_norm_sub_layers_with_a_residual_multiplier():
    """``h = x + r f(norm(x))``, ``y = h + r mlp(mlp_norm(h))`` for both
    letters, from the sub-modules applied by hand on the layer's own
    parameters; ``r`` = 1 is another function."""
    r, eps = 0.22, 1e-5
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    subs = {"m": dict(num_heads=4, head_dim=16, n_groups=1, state_size=16,
                      chunk=16),
            "a": dict(num_heads=4, kv_heads=2, head_dim=16, attn="full",
                      scale=0.125)}
    for kind, sub in subs.items():
        layer = PatternLayer(kind, sub, dtype=F32, mlp_hidden=96,
                             norm_eps=eps, residual_multiplier=r)
        params = layer.init(jax.random.PRNGKey(1), x)["params"]
        params = jax.tree.map(
            lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                  a.shape), params)

        def norm(y, scale):
            return y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                                     + eps) * scale

        if kind == "m":
            f = Mamba2Mixer(**sub, norm_eps=eps, dtype=F32).apply(
                {"params": params["ssm"]}, norm(x, params["norm"]["scale"]))
        else:
            f = GroupedQueryAttention(**sub, dtype=F32).apply(
                {"params": params["attn"]}, norm(x, params["norm"]["scale"]))
        h = x + r * f
        want = h + r * SwiGLU(96, F32).apply(
            {"params": params["mlp"]}, norm(h, params["mlp_norm"]["scale"]))
        got = layer.apply({"params": params}, x)
        assert rel(got, want) <= 1e-6, kind
        plain = PatternLayer(kind, sub, dtype=F32, mlp_hidden=96,
                             norm_eps=eps).apply({"params": params}, x)
        assert rel(plain, want) > 0.1, kind


def test_attention_scale_is_the_configured_one_not_the_root():
    q = dict(num_heads=4, kv_heads=2, head_dim=16, attn="full", dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32)) * 3.0
    params = GroupedQueryAttention(**q).init(jax.random.PRNGKey(1), x)
    default = GroupedQueryAttention(**q).apply(params, x)
    root = GroupedQueryAttention(**q, scale=16 ** -0.5).apply(params, x)
    granite = GroupedQueryAttention(**q, scale=1 / 16).apply(params, x)
    assert rel(default, root) == 0.0 and rel(granite, root) > 1e-2


def tiny(**over):
    fields = dict(vocab=96, dim=32, pattern="ma", num_heads=2, kv_heads=1,
                  head_dim=16, attn="full", attn_scale=1 / 16, mlp_hidden=48,
                  ssm=dict(num_heads=4, head_dim=16, n_groups=1,
                           state_size=16, chunk=16),
                  dtype=F32, head_dtype=F32)
    fields.update(over)
    return GraniteHybridLM(**fields)


def test_multipliers_and_the_tied_head_by_hand():
    """The embedded tokens times 12, the logits the normed hidden states
    times the table transposed over 8; each multiplier left out is another
    function; the block stack refuses them."""
    model = tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 96)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    assert sorted(params) == ["layer_0", "layer_1", "ln_f", "tok_emb"]
    table = params["tok_emb"]["embedding"]
    logits = model.apply({"params": params}, tokens)
    hidden = model.apply({"params": params}, tokens, return_hidden=True)
    assert model.head_kernel(params).shape == (32, 96)
    assert rel(logits, hidden @ model.head_kernel(params)) <= 1e-6

    x = 12.0 * table[tokens]
    for i, kind in enumerate("ma"):
        x = PatternLayer(kind, dict(model.ssm) if kind == "m" else dict(
            num_heads=2, kv_heads=1, head_dim=16, attn="full",
            scale=1 / 16), dtype=F32, mlp_hidden=48, norm_eps=1e-5,
            residual_multiplier=0.22).apply(
                {"params": params[f"layer_{i}"]}, x)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-5) * params[
        "ln_f"]["scale"]
    assert rel(logits, (x @ table.T) / 8.0) <= 1e-5
    for dropped in (dict(embedding_multiplier=1.0),
                    dict(residual_multiplier=1.0), dict(logits_scaling=1.0),
                    dict(attn_scale=None)):
        other = tiny(**dropped).apply({"params": params}, tokens)
        assert rel(other, logits) > 1e-2, dropped
    with pytest.raises(ValueError, match="pattern stack"):
        TransformerLM(vocab=96, dim=32, depth=1, num_heads=2,
                      tie_head=True).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="residual_multiplier"):
        tiny(pattern="M").init(jax.random.PRNGKey(0), tokens)


def test_the_tied_table_s_gradient_is_the_gather_s_plus_the_head_s():
    """One parameter, two uses.  With the two uses given a copy each, the
    gradient of the tied loss on the table is the sum of the copies'
    gradients, and neither part is nothing."""
    from horovod_tpu.ops.losses import fused_softmax_xent

    model = tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 96)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]

    def two_uses(gathered, head, params):
        p = {**params, "tok_emb": {"embedding": gathered}}
        h = model.apply({"params": p}, tokens[:, :-1], return_hidden=True)
        return fused_softmax_xent(h.reshape(-1, 32), head.T,
                                  tokens[:, 1:].reshape(-1)).mean()

    def tied(params):
        h = model.apply({"params": params}, tokens[:, :-1],
                        return_hidden=True)
        return fused_softmax_xent(h.reshape(-1, 32),
                                  model.head_kernel(params),
                                  tokens[:, 1:].reshape(-1)).mean()

    table = params["tok_emb"]["embedding"]
    d_gather, d_head = jax.jit(jax.grad(two_uses, argnums=(0, 1)))(
        table, table, params)
    d_tied = jax.jit(jax.grad(tied))(params)["tok_emb"]["embedding"]
    assert rel(d_tied, d_gather + d_head) <= 1e-6
    assert rel(d_gather, d_tied) > 0.1 and rel(d_head, d_tied) > 0.1


# ------------------------- program against the family's plain reference


def family_cfg(compute="float32", **over):
    """One mixer and one attention layer at sizes the kernels take: ONE
    group of B and C over 16 heads of 64 (a norm group of 1,024 channels:
    the gate's pieces; with no VMEM head-room the scan's 4 head tiles at
    float32, 2 at bfloat16), chunks of 256, attention of 2 query heads
    over 1 KV head of 64 (the merged-heads path), two chunks a sequence."""
    cfg = published()
    cfg.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
               hidden_size=128, shared_intermediate_size=256,
               intermediate_size=256, mamba_n_heads=16, mamba_d_head=64,
               num_attention_heads=2, num_key_value_heads=1,
               sequence_length=512, vocab_size=512,
               training={**cfg["training"], "compute_dtype": compute})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def compared():
    """Loss and every gradient leaf of the program in float32, of the
    program in bfloat16 and of the reference, on one seeded batch; the
    kernels interpreted, the scan's group split as on a device without
    VMEM head-room.  The probe is every family's, so the attention
    layer's plan sees no head-room either (the tiny preset's flash forms
    ask for none)."""
    original = _pallas.vmem_headroom_ok
    _pallas.vmem_headroom_ok = lambda: False
    jax.clear_caches()
    try:
        cfg = family_cfg()
        params, aux = jax.jit(lambda k: family.init(cfg, k))(
            jax.random.PRNGKey(11))
        tokens = jnp.asarray(family.host_batch(
            cfg, np.random.default_rng(5), 1))
        noted = {}
        out = {"cfg": cfg, "params": params, "tokens": tokens}
        for name, fn in (
                ("float32", noting_layers(family.loss_fn(cfg), noted)),
                ("bfloat16", family.loss_fn(family_cfg("bfloat16"))),
                ("reference", lambda p, a, t: (
                    family.reference_loss(cfg)(p, a, t), a))):
            # ONE program each: differentiated eagerly, op by op, the
            # three cost this fixture 94 s (PR 56).
            (loss, _), grads = jax.jit(jax.value_and_grad(
                fn, has_aux=True))(params, aux, tokens)
            out[name] = (float(loss), grads)
        out["noted"] = noted
        return out
    finally:
        _pallas.vmem_headroom_ok = original
        jax.clear_caches()


# Why these bounds.  The program in float32 differs from the reference in
# the order of its sums alone (chunked scan against the (T, T) dual form,
# flash against a held softmax, the kernels' float32 arithmetic): observed
# 0 on the loss (to float32's last digit) and 3e-7 to 6.1e-5 on the leaves
# (the largest A_log, a sum of terms of both signs over every position).
# The same program computing in bfloat16 — the nearest precision below the
# one this test's configuration states — reads 6.3e-6 on the loss and
# 5.1e-3 to 1.6e-2 on the leaves: ten times the leaves' bound on its best
# leaf, so a float32 part that went bfloat16 fails it.  A multiplier a
# little off (below) reads 5e-6 to 7e-4 on the loss and 0.1 to 9 on its
# worst leaf.
LOSS_TOL, LEAF_TOL = 2e-6, 5e-4


def test_the_plans_take_the_kernels_at_the_compared_size(compared):
    noted = compared["noted"]
    (mixer,) = [n for n in noted.values() if "ssm.fused_scans" in n]
    assert mixer["ssm.fused_scans"] == 1 and mixer["ssm.fused_passes"] == 2
    assert mixer["ssm.head_tiles"] == 4            # 16 heads > a block's 4
    assert mixer["ssm.group_channels"] == 1024     # > a block's 512 columns
    (attn,) = [n for n in noted.values() if "attn.merged_heads" in n]
    assert attn["attn.merged_heads"] == 2
    assert [n for n in noted.values() if "lm.tied_head" in n] == [
        {"lm.tied_head": 1}]


def test_loss_and_every_leaf_against_the_plain_reference(compared):
    want_loss, want = compared["reference"]
    got_loss, got = compared["float32"]
    assert abs(got_loss - want_loss) <= LOSS_TOL * abs(want_loss)
    errors = jax.tree.map(rel, got, want)
    worst = max(jax.tree.leaves(errors))
    assert worst <= LEAF_TOL, errors
    named = family.grad_leaves(compared["cfg"])
    assert ("tok_emb", "embedding") in named
    for path in named:
        leaf = errors
        for key in path:
            leaf = leaf[key]
        assert leaf <= LEAF_TOL, path


def test_bfloat16_where_the_configuration_says_float32_fails(compared):
    _, want = compared["reference"]
    _, got = compared["bfloat16"]
    errors = jax.tree.leaves(jax.tree.map(rel, got, want))
    assert min(errors) > 4 * LEAF_TOL, errors


@pytest.mark.parametrize("key,value", [
    ("residual_multiplier", 0.25), ("embedding_multiplier", 11),
    ("logits_scaling", 7), ("attention_multiplier", 0.125)])
def test_a_changed_multiplier_fails_the_comparison(compared, key, value):
    """The reference with one multiplier a little off (0.22 -> 0.25,
    12 -> 11, 8 -> 7, 1/64 -> 1/8) reads outside both bounds: the program
    has the multipliers where the reference has them."""
    cfg = family_cfg(**{key: value})
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: family.reference_loss(cfg)(p, {}, compared["tokens"])))(
            compared["params"])
    want_loss, want = compared["reference"]
    assert abs(float(loss) - want_loss) > LOSS_TOL * abs(want_loss)
    assert max(jax.tree.leaves(jax.tree.map(rel, grads, want))) > LEAF_TOL


def test_reference_dual_form_equals_its_recurrence():
    """The reference's mixer two ways, in float32: the (T, T) dual form
    the comparison uses and the recurrence written as the recurrence."""
    cfg = family_cfg(sequence_length=64)
    params, _ = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(2))
    u = jax.random.normal(jax.random.PRNGKey(3), (64, 128))
    p = params["layer_0"]["ssm"]
    with jax.default_matmul_precision("highest"):
        dual = family.reference_mixer(cfg, "dual")(p, u)
        recurrence = family.reference_mixer(cfg, "recurrence")(p, u)
    assert rel(dual, recurrence) <= 1e-5


def test_counters_reach_the_registry_through_make_train_step():
    """``ssm.head_tiles``, ``ssm.group_channels``, ``attn.merged_heads``
    and ``lm.tied_head`` are bumped at every dispatch from what the layers
    noted while traced, as the ``ssm.*`` counters before them."""
    import optax

    cfg = {**published(), **family.TINY}
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(0), 2))
    tx = optax.sgd(0.1)
    step = make_train_step(family.loss_fn(cfg), tx, Mesh(
        np.asarray(jax.devices()[:1]), (RANKS_AXIS,)))
    names = ("ssm.head_tiles", "ssm.group_channels", "attn.merged_heads",
             "lm.tied_head", "ssm.fused_scans")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    opt_state, losses = tx.init(params), []
    for _ in range(3):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    after = {n: registry.snapshot()["counters"].get(n, 0) - before[n]
             for n in names}
    # The tiny preset: one mixer whose group is a grid step's (the XLA
    # form: 1 tile, 64 channels), 2 sequences of 2 query heads of 32.
    assert after == {"ssm.head_tiles": 3, "ssm.group_channels": 3 * 64,
                     "attn.merged_heads": 3 * 4, "lm.tied_head": 3,
                     "ssm.fused_scans": 0}


def test_at_the_cell_s_shape_every_mixer_takes_the_kernels(monkeypatch):
    """``granitehmicro_1chip``'s step traced from shapes as on the chip
    (``jax.default_backend`` answers "tpu" for the trace; nothing runs):
    the nine mixers note a fused scan and two fused passes each, eight
    head tiles over a group of 4,096 channels; the attention layer sends
    its 32 query heads through the merged-heads path; the head is tied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = published()
    params, aux = jax.eval_shape(lambda k: family.init(cfg, k),
                                 jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, cfg["sequence_length"] + 1), jnp.int32)
    noted = {}
    jax.eval_shape(noting_layers(family.loss_fn(cfg), noted),
                   params, aux, tokens)
    totals = {}
    for counters in noted.values():
        for name, n in counters.items():
            totals[name] = totals.get(name, 0) + n
    assert totals == {
        "ssm.fused_scans": 9, "ssm.fused_passes": 18,
        "ssm.head_tiles": 9 * 8, "ssm.group_channels": 9 * 4096,
        "ssm.scan_chunks": 9 * 32,
        "ssm.state_bytes": 9 * 32 * 64 * 64 * 128 * 4,
        "attn.merged_heads": 32, "lm.tied_head": 1}
