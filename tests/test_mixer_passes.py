"""The state-space mixer's two elementwise passes as Pallas kernels
(``ops/mixer_passes.py``), interpreted on the CPU: against the XLA forms
the mixer keeps for shapes that do not tile (``causal_conv`` with its
activation, ``gated_group_norm``) and against a float32 definition, values
and every gradient; the one function that chooses a form, as a table; the
float32 parts, held in the kernels' jaxprs; and the ``ssm.fused_passes``
counter with the names the kernels take in a lowered step.
"""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.metrics import registry
from horovod_tpu.models import NemotronHLM
from horovod_tpu.models.ssm import Mamba2Mixer, causal_conv, gated_group_norm
from horovod_tpu.ops import mixer_passes
from horovod_tpu.ops.mixer_passes import (
    PassPlan, conv_silu, gated_norm, passes_plan)

from test_gated_delta import _equations

F32 = jnp.float32
K, EPS = 4, 1e-5

# name -> batch, T, inner, norm groups, B | C columns (2 G N), heads.  Rows
# come 1024 a block (512 in float32) and 32 a strip; the packed array is
# [z | xBC | dt] padded to whole 128-lane tiles, as the mixer's.
SHAPES = {
    "one_block_of_64_rows": (1, 64, 256, 2, 128, 4),
    "two_blocks_batch_2": (2, 2048, 256, 2, 128, 4),
    "a_block_and_a_tail_batch_2": (2, 1056, 256, 1, 128, 4),
    "one_group_of_512_tiles_of_256": (1, 96, 512, 1, 256, 8),
    # Norm groups wider than a block's most columns (one group over every
    # head): a block holds as many fewer rows (64 and a tail of 32 here),
    # and a strip's sums are gathered a piece of 512 channels at a time.
    "one_group_of_1024_in_two_pieces_batch_2": (2, 160, 1024, 1, 256, 16),
    "two_groups_of_1024_in_two_pieces": (1, 64, 2048, 2, 256, 32),
}


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def inputs(case, dtype, seed=0):
    b, T, inner, groups, bc, heads = SHAPES[case]
    conv_dim = inner + bc
    width = inner + conv_dim + heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    packed = jax.random.normal(ks[0], (b, T, width + -width % 128), dtype)
    w = jax.random.normal(ks[1], (K, conv_dim)) * 0.5
    bias = jax.random.normal(ks[2], (conv_dim,)) * 0.5
    y = jax.random.normal(ks[3], (b, T, inner), dtype)
    scale = 1.0 + 0.2 * jax.random.normal(ks[4], (inner,))
    plan = passes_plan(packed, inner=inner, conv_dim=conv_dim, groups=groups,
                       kernel=K, interpret=True)
    assert plan.form == "kernels", plan
    weights = (jax.random.normal(ks[5], (b, T, conv_dim)),
               jax.random.normal(ks[6], (b, T, inner)))
    return dict(packed=packed, w=w, bias=bias, y=y, scale=scale, plan=plan,
                inner=inner, conv_dim=conv_dim, groups=groups,
                weights=weights)


def split(packed, inner, conv_dim):
    return packed[..., :inner], packed[..., inner:inner + conv_dim]


def value_and_grads(fn, weight, *args):
    """``fn(*args)`` and the gradients of its weighted sum."""
    def loss(*a):
        out = fn(*a)
        return (out.astype(F32) * weight).sum(), out
    (_, out), grads = jax.value_and_grad(loss, argnums=tuple(
        range(len(args))), has_aux=True)(*args)
    return out, grads


def conv_definition(x, w, b):
    """``silu(b + sum_j w_j x_{t-(K-1)+j})`` in float32, token by token
    from an explicit zero history: no padding, no shifted slices."""
    x = x.astype(F32)

    def step(history, x_t):                       # history (b, K - 1, c)
        window = jnp.concatenate([history, x_t[:, None]], axis=1)
        pre = b + jnp.einsum("bkc,kc->bc", window, w)
        return window[:, 1:], pre * jax.nn.sigmoid(pre)

    zeros = jnp.zeros((x.shape[0], w.shape[0] - 1, x.shape[-1]), F32)
    _, out = jax.lax.scan(step, zeros, jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(out, 0, 1)


def gate_definition(y, z, scale, groups):
    y, z = y.astype(F32), z.astype(F32)
    g = y * z * jax.nn.sigmoid(z)
    grouped = g.reshape(*g.shape[:-1], groups, -1)
    rms = jnp.sqrt((grouped ** 2).mean(-1, keepdims=True) + EPS)
    return (grouped / rms).reshape(g.shape) * scale


# (dtype, bound against the float32 definition, bound against the XLA
# form).  float32: the same sums in another order.  bfloat16: operands
# rounded alike on every side; the kernels round once, at the store
# (2^-9 of a value, 2e-3 of a norm), where ``causal_conv`` rounds every
# multiply-add, so the kernels are the closer to the definition.
PRECISIONS = {"float32": (F32, 2e-6, 2e-6),
              "bfloat16": (jnp.bfloat16, 3e-3, 8e-3)}


@pytest.mark.parametrize("dtype", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_conv_kernels_equal_the_xla_form_and_the_definition(case, dtype):
    """``ssm_conv_fwd`` / ``ssm_conv_bwd`` read ``xBC`` out of the packed
    array: the value and the gradients of ``x`` (zeros outside its
    columns), ``w`` and ``b`` against ``silu(causal_conv(...))`` on the
    split array and against the token-by-token definition."""
    dtype, to_definition, to_xla = PRECISIONS[dtype]
    a = inputs(case, dtype)
    inner, conv_dim = a["inner"], a["conv_dim"]
    weight = a["weights"][0]

    got, (dp, dw, db) = value_and_grads(
        lambda p, w, b: conv_silu(p, w, b, first=inner, plan=a["plan"],
                                  interpret=True),
        weight, a["packed"], a["w"], a["bias"])
    assert got.dtype == dtype and dp.dtype == dtype
    assert dw.dtype == db.dtype == F32
    _, dx = split(dp, inner, conv_dim)
    assert not np.asarray(dp[..., :inner], F32).any()
    assert not np.asarray(dp[..., inner + conv_dim:], F32).any()

    _, x = split(a["packed"], inner, conv_dim)
    for form, bound in (
            (lambda x, w, b: jax.nn.silu(causal_conv(x, w, b)), to_xla),
            (conv_definition, to_definition)):
        want, (wx, ww, wb) = value_and_grads(form, weight, x, a["w"],
                                             a["bias"])
        assert rel(got, want) <= bound
        assert rel(dx, wx) <= 2 * bound
        assert rel(dw, ww) <= 2 * bound and rel(db, wb) <= 2 * bound


@pytest.mark.parametrize("dtype", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_gate_kernels_equal_the_xla_form_and_the_definition(case, dtype):
    """``ssm_gate_fwd`` / ``ssm_gate_bwd`` read ``z`` out of the packed
    array: the value and the gradients of ``y``, ``z`` (zeros outside its
    columns) and ``scale`` against ``gated_group_norm`` and against the
    definition."""
    dtype, to_definition, _ = PRECISIONS[dtype]
    a = inputs(case, dtype)
    inner, groups = a["inner"], a["groups"]
    weight = a["weights"][1]

    got, (dy, dp, dscale) = value_and_grads(
        lambda y, p, s: gated_norm(y, p, s, groups=groups, eps=EPS,
                                   plan=a["plan"], interpret=True),
        weight, a["y"], a["packed"], a["scale"])
    assert got.dtype == dy.dtype == dp.dtype == dtype
    assert dscale.dtype == F32
    assert not np.asarray(dp[..., inner:], F32).any()
    dz = dp[..., :inner]

    z, _ = split(a["packed"], inner, a["conv_dim"])
    # Both forms hold the gate in float32, so one bound serves.
    for form in (lambda y, z, s: gated_group_norm(y, z, s, groups=groups,
                                                  eps=EPS),
                 lambda y, z, s: gate_definition(y, z, s, groups)):
        want, (wy, wz, ws) = value_and_grads(form, weight, a["y"], z,
                                             a["scale"])
        assert rel(got, want) <= to_definition
        assert rel(dy, wy) <= 2 * to_definition
        assert rel(dz, wz) <= 2 * to_definition
        assert rel(dscale, ws) <= 2 * to_definition


def test_no_row_of_one_sequence_reaches_the_next():
    """Batch 2 over two time blocks: sequence 1's convolution, alone in a
    batch of one, gives bit for bit what it gives behind sequence 0 — the
    rows carried into a sequence's first block are zeros, forward (the
    halo) and backward (the rows of ``dpre`` after a block)."""
    a = inputs("two_blocks_batch_2", jnp.bfloat16)

    def run(packed, weight):
        return value_and_grads(
            lambda p, w, b: conv_silu(p, w, b, first=a["inner"],
                                      plan=a["plan"], interpret=True),
            weight, packed, a["w"], a["bias"])

    weight = a["weights"][0]
    both, (dboth, _, _) = run(a["packed"], weight)
    alone, (dalone, _, _) = run(a["packed"][1:], weight[1:])
    assert (np.asarray(both[1], F32) == np.asarray(alone[0], F32)).all()
    assert (np.asarray(dboth[1], F32) == np.asarray(dalone[0], F32)).all()
    # And it does reach across the block boundary inside a sequence.
    rows = a["plan"].rows
    moved = a["packed"].at[0, rows - 1].add(1.0)
    out, _ = run(moved, weight)
    changed = np.flatnonzero(np.asarray(
        (out != both).any(-1)[0]))
    assert changed.tolist() == list(range(rows - 1, rows - 1 + K))


# ------------------------------------------------------------- the plan


def seen(T=8192, inner=4096, conv_dim=6144, groups=8, kernel=4, itemsize=2,
         interpret=False, manual_axes=False):
    return dict(T=T, inner=inner, conv_dim=conv_dim, groups=groups,
                kernel=kernel, itemsize=itemsize, interpret=interpret,
                manual_axes=manual_axes)


KERNELS, XLA = "kernels", ("xla", 0, 0, 0, 0)
# What ``mixer_passes._plan`` observes -> (form, rows a block, rows a
# strip, the convolution's channels a block, the gate's).
PLAN_TABLE = {
    # twotower_1chip: 64 heads of 64, 8 groups of B and C with 128 columns.
    "cell": (seen(), (KERNELS, 1024, 32, 512, 512)),
    "cell_float32": (seen(itemsize=4), (KERNELS, 512, 32, 512, 512)),
    "cell_T_not_in_whole_blocks": (seen(T=8224),
                                   (KERNELS, 1024, 32, 512, 512)),
    "cell_T_not_in_whole_strips": (seen(T=8200), XLA),
    "cell_compiled_under_shard_map": (seen(manual_axes=True),
                                      (KERNELS, 1024, 32, 512, 512)),
    # Interpreted Pallas cannot run under manual mesh axes (jax 0.9.0).
    "interpreted_under_shard_map": (seen(interpret=True, manual_axes=True),
                                    XLA),
    "interpreted_short": (seen(T=64, inner=256, conv_dim=384, groups=2,
                               itemsize=4, interpret=True),
                          (KERNELS, 64, 32, 128, 256)),
    "groups_of_128": (seen(groups=32), (KERNELS, 1024, 32, 512, 512)),
    "groups_of_256_in_an_odd_count": (seen(inner=768, conv_dim=1024,
                                           groups=3),
                                      (KERNELS, 1024, 32, 256, 256)),
    "two_taps": (seen(kernel=2), (KERNELS, 1024, 32, 512, 512)),
    # The tiny preset of the CPU tests, and every way of not tiling.
    "tiny_preset": (seen(T=64, inner=64, conv_dim=128, groups=2, itemsize=4,
                         interpret=True), XLA),
    "group_of_96": (seen(inner=768, conv_dim=1024, groups=8), XLA),
    # One norm group over all 4,096 channels (one B/C group over 64
    # heads): a gate block of 128 rows, the convolution's as before.
    "one_group_of_4096": (seen(groups=1), (KERNELS, 1024, 32, 512, 4096)),
    "one_group_of_4096_conv_of_4352": (seen(groups=1, conv_dim=4352),
                                       (KERNELS, 1024, 32, 256, 4096)),
    "one_group_of_4096_float32": (seen(groups=1, itemsize=4),
                                  (KERNELS, 512, 32, 512, 4096)),
    "one_group_wider_than_the_gate_takes": (seen(inner=16384,
                                                 conv_dim=16640, groups=1),
                                            XLA),
    "one_group_of_768_not_in_whole_pieces": (seen(inner=768, conv_dim=1024,
                                                  groups=1), XLA),
    "groups_do_not_divide": (seen(groups=7), XLA),
    "conv_channels_off_the_tile": (seen(conv_dim=6208), XLA),
    "nine_taps": (seen(kernel=9), XLA),
    "one_byte_activations": (seen(itemsize=1), XLA),
}


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_passes_plan_table(case):
    """The one function that chooses kernels or the XLA forms: a pure
    table, no kernel, no device."""
    observed, want = PLAN_TABLE[case]
    assert mixer_passes._plan(**observed) == PassPlan(*want)


def test_the_passes_have_no_knob():
    source = inspect.getsource(mixer_passes)
    assert "environ" not in source and "getenv" not in source
    assert list(inspect.signature(conv_silu).parameters) == [
        "packed", "w", "b", "first", "plan", "interpret"]
    assert list(inspect.signature(gated_norm).parameters) == [
        "y", "packed", "scale", "groups", "eps", "plan", "interpret"]
    assert [f.name for f in inspect.signature(Mamba2Mixer).parameters.values()
            if "pass" in f.name or "fused" in f.name] == []
    # A plan that is not the kernels' is refused, not run some other way.
    a = inputs("one_block_of_64_rows", F32)
    with pytest.raises(ValueError, match="XLA forms"):
        conv_silu(a["packed"], a["w"], a["bias"], first=a["inner"],
                  plan=PassPlan(*XLA), interpret=True)
    with pytest.raises(ValueError, match="XLA forms"):
        gated_norm(a["y"], a["packed"], a["scale"], groups=a["groups"],
                   eps=EPS, plan=PassPlan(*XLA), interpret=True)


# ------------------------------------------------------ float32 inside


ARITHMETIC = {"add", "sub", "mul", "div", "neg", "exp", "rsqrt", "logistic",
              "reduce_sum", "integer_pow", "max", "select_n", "tanh",
              "add_any", "square"}


def kernel_calls(dtype=jnp.bfloat16):
    a = inputs("a_block_and_a_tail_batch_2", dtype)

    def loss(p, w, b, y, s):
        x = conv_silu(p, w, b, first=a["inner"], plan=a["plan"],
                      interpret=True)
        o = gated_norm(y, p, s, groups=a["groups"], eps=EPS, plan=a["plan"],
                       interpret=True)
        return x.astype(F32).sum() + o.astype(F32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        a["packed"], a["w"], a["bias"], a["y"], a["scale"])
    return {e.params["jaxpr"].debug_info.func_name: e
            for e in _equations(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"}, a


def test_the_float32_parts_are_float32_in_the_kernels():
    """Under bfloat16 activations every multiply-add, activation, mean
    square and partial sum inside the four kernels is float32: the only
    bfloat16 values are those just loaded (converted at once) and those
    about to be stored (converted once); the sums of ``dw``, ``db`` and
    ``dscale`` leave the kernels in float32, as does the backward's
    carried ``dpre``."""
    calls, a = kernel_calls()
    assert set(calls) == {"ssm_conv_fwd", "ssm_conv_bwd",
                          "ssm_gate_fwd", "ssm_gate_bwd"}
    for name, call in calls.items():
        eqns = list(_equations(call.params["jaxpr"]))
        narrow = [e for e in eqns if e.primitive.name in ARITHMETIC and any(
            getattr(v.aval, "dtype", None) == jnp.bfloat16
            for v in (*e.invars, *e.outvars))]
        assert not narrow, (name, narrow[:3])
        assert sum(e.primitive.name in ARITHMETIC for e in eqns) >= 10
        # bfloat16 appears only at the ends: out of a load, into a store.
        for e in eqns:
            if e.primitive.name != "convert_element_type":
                continue
            src, dst = e.invars[0].aval.dtype, e.outvars[0].aval.dtype
            if jnp.bfloat16 in (src, dst):
                assert {src, dst} == {jnp.dtype(jnp.bfloat16),
                                      jnp.dtype(F32)}, (name, e)
    b = a["packed"].shape[0]
    sums = calls["ssm_conv_bwd"].outvars[1].aval
    assert (sums.shape, sums.dtype) == ((b, K + 1, 8, a["conv_dim"]), F32)
    carried = calls["ssm_conv_bwd"].params["jaxpr"].invars[-1].aval
    assert (carried.shape[0], carried.dtype) == (8, F32)
    sums = calls["ssm_gate_bwd"].outvars[2].aval
    assert (sums.shape, sums.dtype) == ((b, 8, a["inner"]), F32)


def test_the_kernels_keep_their_inputs_and_nothing_else():
    """What the backward pass reads of the forward is what the XLA forms
    kept: the packed array, ``y`` and the parameters — no pre-activation,
    no gate, no norm."""
    a = inputs("one_block_of_64_rows", jnp.bfloat16)
    _, conv_vjp = jax.vjp(
        lambda p, w, b: conv_silu(p, w, b, first=a["inner"], plan=a["plan"],
                                  interpret=True),
        a["packed"], a["w"], a["bias"])
    _, gate_vjp = jax.vjp(
        lambda y, p, s: gated_norm(y, p, s, groups=a["groups"], eps=EPS,
                                   plan=a["plan"], interpret=True),
        a["y"], a["packed"], a["scale"])

    def kinds(arrays):
        return sorted((x.shape, str(x.dtype)) for x in arrays)

    assert kinds(jax.tree.leaves(conv_vjp)) == kinds(
        (a["packed"], a["w"], a["bias"]))
    assert kinds(jax.tree.leaves(gate_vjp)) == kinds(
        (a["y"], a["packed"], a["scale"]))


# ----------------------------------------------------- mixer and counter


def one_mixer_stack(**ssm):
    model = NemotronHLM(vocab=64, dim=32, pattern="M", max_len=128,
                        dtype=F32, ssm=ssm)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]

    def loss_fn(p, aux, tokens):
        import optax
        logits = model.apply({"params": p}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), aux

    return loss_fn, params, tokens


TILING = dict(num_heads=2, head_dim=64, n_groups=1, state_size=128,
              chunk=128)
NOT_TILING = dict(num_heads=2, head_dim=16, n_groups=1, state_size=16,
                  chunk=16)


def test_mixer_with_the_kernels_equals_the_mixer_without():
    """One module, one parameter tree, two forms: at a tiling shape the
    mixer takes the kernels (and pads its projection to whole tiles); its
    output and every parameter's gradient are the XLA forms' on the same
    parameters, which a plan that refuses everything stands in for."""
    mixer = Mamba2Mixer(**TILING, dtype=F32)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 32))
    params = mixer.init(jax.random.PRNGKey(1), u)["params"]
    assert params["in_proj"]["kernel"].shape == (32, 2 * 128 + 2 * 128 + 2)

    def value_grads():
        return jax.value_and_grad(lambda p, u: (mixer.apply(
            {"params": p}, u) ** 2).sum(), argnums=(0, 1))(params, u)

    noted = {}
    got, got_grads = noting_layers(value_grads, noted)()
    assert [n["ssm.fused_passes"] for n in noted.values()] == [2]
    import horovod_tpu.models.ssm as ssm_module
    refuse = lambda *a, **k: PassPlan(*XLA)                    # noqa: E731
    original, ssm_module.passes_plan = ssm_module.passes_plan, refuse
    try:
        noted = {}
        want, want_grads = noting_layers(value_grads, noted)()
    finally:
        ssm_module.passes_plan = original
    assert [n["ssm.fused_passes"] for n in noted.values()] == [0]
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and rel(g, w) <= 2e-5


def test_tiny_stacks_count_no_fused_pass(hvd):
    """The nine-layer preset's mixers (16-channel heads) note 0 passes
    while traced, and a one-mixer stack that does not tile counts none in
    its dispatches through ``make_train_step`` on the 8-device mesh."""
    import optax

    from benchmark.families import nemotron_h_lm
    from test_hybrid_stack import family_cfg, model_inputs

    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    noted = {}
    jax.eval_shape(noting_layers(nemotron_h_lm.loss_fn(cfg), noted),
                   params, aux, tokens)
    passes = [n["ssm.fused_passes"] for n in noted.values()
              if "ssm.fused_passes" in n]
    assert passes == [0, 0, 0, 0]

    loss_fn, params, tokens = one_mixer_stack(**NOT_TILING)
    tx = optax.sgd(0.5)
    step = make_train_step(loss_fn, tx, hvd.ranks_mesh())
    tokens = jnp.tile(tokens, (8, 1))
    before = registry.snapshot()["counters"].get("ssm.fused_passes", 0)
    aux, opt_state = {}, tx.init(params)
    for _ in range(2):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
    assert np.isfinite(float(loss))
    assert registry.snapshot()["counters"].get(
        "ssm.fused_passes", 0) == before


def test_one_mixer_at_a_tiling_shape_counts_two_fused_passes():
    """A one-mixer stack whose passes tile (2 heads of 64 in one norm
    group of 128, 384 convolved channels, 128 rows) through
    ``make_train_step`` on one device: it trains, each dispatch counts two
    fused passes beside its fused scan, and the lowered step names the
    four kernels under ``ssm/conv`` and ``ssm/gate_norm``, inside the
    mixer where the cell's reader looks and outside its scan."""
    import optax

    from benchmark.metrics import ssm_ms
    from horovod_tpu.parallel.mesh import RANKS_AXIS

    loss_fn, params, tokens = one_mixer_stack(**TILING)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, tokens)[0])).lower(
        params).as_text(debug_info=True)
    stacks = set(re.findall(r'"([^"]*/ssm_(?:conv|gate)_(?:fwd|bwd))[/"]',
                            text))
    assert {s.rsplit("/", 1)[1] for s in stacks} == {
        "ssm_conv_fwd", "ssm_conv_bwd", "ssm_gate_fwd", "ssm_gate_bwd"}
    for s in stacks:
        parts = s.split("/")
        under_ssm = parts[len(parts) - parts[::-1].index("ssm"):]
        assert ("conv" if "_conv_" in s else "gate_norm") in under_ssm, s
        assert ssm_ms.in_mixer(s) and not ssm_ms.in_scan(s), s
    assert "ssm/split" not in text

    tx = optax.sgd(0.5)
    step = make_train_step(loss_fn, tx, Mesh(np.asarray(jax.devices()[:1]),
                                             (RANKS_AXIS,)))
    names = ("ssm.fused_passes", "ssm.fused_scans")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    aux, opt_state, losses = {}, tx.init(params), []
    for _ in range(3):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    after = registry.snapshot()["counters"]
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "ssm.fused_passes": 6, "ssm.fused_scans": 3}
