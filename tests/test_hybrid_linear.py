"""The layers of two sub-layers of the hybrid (pattern) stack (``L``: a
Gated DeltaNet mixer, ``F``: full attention with QK-norm; a SwiGLU MLP after
each, the norm on every sub-layer's output) against the ``olmo_hybrid_lm``
family's plain reference, whose delta rule is the token-by-token recurrence.
(One of the four files ``test_hybrid_stack.py`` was until PR 50.)
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.families import nemotron_h_lm, olmo_hybrid_lm
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.models import (
    NemotronHLM, OlmoHybridLM, SwiGLU, TransformerLM)
from horovod_tpu.models.linear_attention import GatedDeltaNet
from horovod_tpu.models.ssm import Mamba2Mixer
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops import ssd
from horovod_tpu.ops.ssd import (
    scan_sizes, ssd_recurrence, ssd_scan, ssd_scan_packed)
from horovod_tpu.parallel.moe import DroplessMoE, _SharedExpert
from horovod_tpu.parallel.ring_attention import full_attention

from _once import out_and_grads
from test_gated_delta import _equations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))



# ------------------------------------ linear attention, two sub-layers


def hybrid_cfg(compute_dtype="float32", **override):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as fh:
        cfg = {**json.load(fh), **olmo_hybrid_lm.TINY, **override}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute_dtype}
    return cfg


ONE_PERIOD = dict(num_hidden_layers=4, layer_types=[
    "linear_attention"] * 3 + ["full_attention"])


def hybrid_inputs(cfg, n=2, seed=5):
    params, aux = jax.jit(lambda k: olmo_hybrid_lm.init(cfg, k))(
        jax.random.PRNGKey(seed))
    tokens = olmo_hybrid_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


@pytest.mark.parametrize("T,chunk,neg", [(40, 16, True), (64, 64, False)],
                         ids=["T_not_a_multiple_beta_to_2",
                              "one_chunk_beta_to_1"])
def test_delta_mixer_module_equals_the_reference_recurrence(T, chunk, neg):
    """``GatedDeltaNet`` (float32) against the family's plain mixer, whose
    delta rule steps token by token, same parameter tree: output to 1e-5
    of its largest, every parameter's gradient and the input's to 2e-4."""
    cfg = hybrid_cfg(linear_chunk_size=chunk, linear_allow_neg_eigval=neg)
    mixer = GatedDeltaNet(
        num_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"], chunk=chunk,
        allow_neg_eigval=neg, norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, cfg["hidden_size"]))
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    # Move the one-initialised leaf off one, so a wrong use shows.
    params = {**params, "gate_norm": 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), params["gate_norm"].shape)}
    assert set(params) == {"q", "k", "v", "g", "a", "b", "out", "conv",
                           "A_log", "dt_bias", "gate_norm"}
    assert set(params["conv"]) == {"kernel"}
    reference = olmo_hybrid_lm.reference_mixer(cfg)

    def ours(p, x):
        return mixer.apply({"params": p}, x)

    def theirs(p, x):
        return jax.vmap(lambda s: reference(p, s))(x)

    weight = jnp.sin(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    with jax.default_matmul_precision("highest"):
        (got, g), (want, w) = (
            out_and_grads(f, lambda y: (y * weight).sum(), params, x,
                          jit=True) for f in (ours, theirs))
    assert got.shape == want.shape == x.shape
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    errors = {jax.tree_util.keystr(path): rel(a, b) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(w))}
    assert len(errors) == 12 and max(errors.values()) <= 2e-4, errors


def test_swiglu_is_the_gated_mlp():
    mlp = SwiGLU(24, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    p = mlp.init(jax.random.PRNGKey(1), h)["params"]
    assert {k: v["kernel"].shape for k, v in p.items()} == {
        "gate": (16, 24), "up": (16, 24), "down": (24, 16)}
    want = (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]
    assert rel(mlp.apply({"params": p}, h), want) <= 1e-6


# float32 compute: every leaf of the gradient is the reference's to
# summation order, through one whole period (LLLF) and through the
# rehearsal's preset (LF).  bfloat16 compute on 128 tokens of a 256-wide
# model: the preset's own, looser tolerances, on the family's named leaves.
@pytest.mark.parametrize("compute_dtype,layers,loss_tol,grad_tol", [
    ("float32", ONE_PERIOD, 1e-5, 3e-4), ("float32", {}, 1e-5, 3e-4),
    ("bfloat16", {}, 5e-3, 0.2)],
    ids=["float32_one_period", "float32_preset", "bfloat16_preset"])
def test_hybrid_model_against_reference_loss(compute_dtype, layers,
                                             loss_tol, grad_tol):
    cfg = hybrid_cfg(compute_dtype, **layers)
    assert olmo_hybrid_lm.pattern(cfg) == ("LLLF" if layers else "LF")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    ref_fn = olmo_hybrid_lm.reference_loss(cfg)
    # Each side ONE program, not differentiated eagerly op by op (PR 56).
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0]))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens)))(params)
    assert abs(float(got) - float(want)) / float(want) <= loss_tol
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    named = [tuple(jax.tree_util.DictKey(k) for k in path)
             for path in olmo_hybrid_lm.grad_leaves(cfg)]
    assert len(named) == 8 and set(named) <= set(flat_got)
    errors = {jax.tree_util.keystr(path): rel(flat_got[path],
                                              flat_want[path])
              for path in (flat_got if compute_dtype == "float32"
                           else named)}
    assert max(errors.values()) <= grad_tol, errors


def test_hybrid_reference_in_bfloat16_is_another_number():
    """The comparison's precision control: the reference's own mathematics
    in bfloat16 is not the reference."""
    cfg = hybrid_cfg()
    params, aux, tokens = hybrid_inputs(cfg)
    want = float(jax.jit(olmo_hybrid_lm.reference_loss(cfg))(
        params, aux, tokens))
    low = float(olmo_hybrid_lm.reference_loss(cfg, dtype="bfloat16")(
        params, aux, tokens))
    assert abs(low - want) > 1e-4 * want


def test_hybrid_stack_s_tree_and_the_published_count():
    """The parameter tree of one period, and — from shapes alone — the
    published model's size: one period of 832,520,436 parameters eight
    times over, embedding, head and final norm."""
    cfg = hybrid_cfg(**ONE_PERIOD)
    params, _, _ = hybrid_inputs(cfg)
    assert set(params) == {"tok_emb", "layer_0", "layer_1", "layer_2",
                           "layer_3", "ln_f", "head"}
    assert set(params["layer_0"]) == {"lin", "mixer_norm", "mlp",
                                      "mlp_norm"}
    assert set(params["layer_3"]) == {"attn", "mixer_norm", "mlp",
                                      "mlp_norm"}
    assert set(params["layer_3"]["attn"]) == {"qkv", "q_norm", "k_norm",
                                              "proj"}
    shapes = jax.eval_shape(
        lambda: OlmoHybridLM(pattern="LLLF", attn="full").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count["layer_0"] == 215_570_172
    assert count["layer_3"] == 185_809_920
    period = sum(count[f"layer_{i}"] for i in range(4))
    assert period == 832_520_436
    assert count["tok_emb"] == count["head"] == 100_352 * 3840
    assert 8 * period + 2 * count["tok_emb"] + 3840 == 7_430_870_688


def test_the_hybrid_s_float32_parts_are_float32_in_the_traced_program():
    """Under bfloat16 compute: the delta rule's carried state is float32
    (``ops/gated_delta.py``'s own test holds the solve); the L2 norms'
    sums of squares, ``beta``'s sigmoid and the decay's softplus and
    exponentials are float32."""
    cfg = hybrid_cfg("bfloat16")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    eqns = list(_equations(jax.make_jaxpr(
        lambda p: loss_fn(p, aux, tokens)[0])(params).jaxpr))
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    B, T = 2, cfg["sequence_length"]
    carried = [v.aval for e in eqns if e.primitive.name == "scan"
               for v in e.outvars[:e.params["num_carry"]]
               if v.aval.shape == (B, H, dv, dk)]
    assert len(carried) == 1 and carried[0].dtype == jnp.float32
    per_head = [e for e in eqns if e.outvars
                and e.outvars[0].aval.shape == (B, T, H)
                and e.primitive.name in ("logistic", "exp", "log1p",
                                         "reduce_sum")]
    assert {e.primitive.name for e in per_head} >= {"logistic", "exp",
                                                    "reduce_sum"}
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in per_head)


def test_tiny_hybrid_trains_through_make_train_step(hvd):
    """The preset through the normal path on the 8-device mesh: the first
    step's loss is the reference's on the global batch, the loss falls,
    the state stays float32, and each dispatch bumps the mixer's counters
    from the shapes it noted."""
    cfg = hybrid_cfg("bfloat16")
    params, aux, _ = hybrid_inputs(cfg)
    tokens = olmo_hybrid_lm.host_batch(cfg, np.random.default_rng(7), 8)
    tx = olmo_hybrid_lm.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(jax.jit(olmo_hybrid_lm.reference_loss(cfg))(
        params, aux, tokens))
    step = make_train_step(olmo_hybrid_lm.loss_fn(cfg), tx, hvd.ranks_mesh())
    names = ("lin.delta_chunks", "lin.state_bytes", "ssm.scan_chunks",
             "moe.assignments")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    losses = []
    for _ in range(4):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) / want <= 5e-3
    assert losses[-1] < losses[0]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    after = registry.snapshot()["counters"]
    # A shard's step, four dispatches: one sequence of 64 tokens through
    # one mixer (4 chunks of 16; 2 heads x 32 x 16 float32 a state).
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "lin.delta_chunks": 4 * 4, "lin.state_bytes": 4 * 4 * 2 * 32 * 16 * 4,
        "ssm.scan_chunks": 0, "moe.assignments": 0}


def test_trace_scopes_name_the_linear_mixer_s_parts():
    cfg = hybrid_cfg("bfloat16")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, aux, tokens)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("layer_0/lin/in_proj/q", "lin/in_proj/b", "lin/conv",
                  "lin/delta", "lin/delta/solve", "lin/delta/states",
                  "lin/delta/inter", "lin/delta/intra", "lin/gate_norm",
                  "lin/out_proj/out", "layer_0/mlp/up", "layer_1/attn/qkv",
                  "layer_1/mlp/down", "layer_1/mixer_norm"):
        assert scope in text, scope
    # The cell's own readers find them under those names.
    from benchmark.metrics import linattn_ms, ssm_ms
    assert linattn_ms.in_delta(
        "transpose(jvp(TransformerLM))/layer_*/lin/delta/solve/dot_general")
    assert linattn_ms.in_mixer("params['layer_*']['lin']['q']['kernel']")
    assert not linattn_ms.in_delta("jvp(TransformerLM)/layer_*/lin/conv/mul")
    assert not linattn_ms.in_mixer("jvp(TransformerLM)/layer_*/mlp/up/dot")
    assert not ssm_ms.in_mixer("jvp(TransformerLM)/layer_*/lin/conv/mul")


def test_two_sub_layer_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    lin = dict(num_heads=2, key_dim=8, value_dim=16, chunk=8)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, lin=lin).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, mlp_hidden=48).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="pattern stack"):
        OlmoHybridLM(vocab=64, dim=32, num_heads=2, lin=lin, mlp_hidden=48,
                     pattern="L", tp_axis="tp", attn="full").init(
                         jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="runs the stack as published"):
        olmo_hybrid_lm.init(hybrid_cfg(attention_bias=True),
                            jax.random.PRNGKey(0))
