"""The latent's passes of compressed convolutional attention as Pallas
kernels (``ops/cca_passes.py``), interpreted on the CPU: against the
module's own ``jax.numpy`` form (``attn="full"`` keeps it), values and every
gradient, over three time blocks of two sequences; the kernels' rotation
against ``apply_rotary``; the one function that chooses a form, as a
table; the float32 parts, held in the kernels' jaxprs; and what the
backward keeps of the forward.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (
    CompressedConvAttention, _cca_mix_xla, apply_rotary)
from horovod_tpu.ops import cca_passes
from horovod_tpu.ops.cca_passes import CcaPlan, cca_mix

from test_gated_delta import _equations

F32, BF16 = jnp.float32, jnp.bfloat16
# Two KV groups of two query heads, lane-aligned heads, half of each
# rotated; three blocks of 128 rows a sequence (``_ROWS`` is held to 128 in
# these tests), so the halo, a sequence's start and the backward's carried
# rows are all crossed.
B, T, H, G, D, MODEL = 2, 384, 4, 2, 128, 64
CCA = dict(num_heads=H, kv_heads=G, head_dim=D, rope_theta=5e6, dtype=BF16)
LATENT = ((B, T, H, D), (B, T, G, D), (B, T, G, D))


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


@pytest.fixture()
def three_blocks(monkeypatch):
    monkeypatch.setattr(cca_passes, "_ROWS", 128)


def layer_and_inputs():
    """A module's parameters with biases and temperatures off their initial
    0 and 1 (``test_zaya_stack.cca_with_biases``' recipe), an input, and
    the weights of a scalar of the latent."""
    u = jax.random.normal(jax.random.PRNGKey(0), (B, T, MODEL)).astype(BF16)
    params = CompressedConvAttention(**CCA, attn="full").init(
        jax.random.PRNGKey(1), u)["params"]
    params = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                              a.shape), params)
    weights = [jax.random.normal(jax.random.PRNGKey(5 + i), shape)
               for i, shape in enumerate(LATENT)]
    return params, u, weights


def latent_and_grads(attn, params, u, weights, **fields):
    """``(q", k", v)`` as the module sows them and the gradients of a
    weighted sum of them for every parameter and the input."""
    layer = CompressedConvAttention(**{**CCA, **fields}, attn=attn)

    def scalar(p, u):
        _, state = layer.apply({"params": p}, u, mutable=["intermediates"])
        latent = state["intermediates"]["latent"][0]
        return sum((a.astype(F32) * w).sum()
                   for a, w in zip(latent, weights)), latent

    (_, latent), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1), has_aux=True))(params, u)
    return latent, grads


def test_kernels_equal_the_module_s_form(three_blocks):
    """q", k", v and the gradients of the input and of every parameter —
    the projections', ``conv0_kernel``, ``conv0_bias``, ``conv1_kernel``,
    ``conv1_bias``, ``temp`` — from the kernels (``attn="flash"``: the plan
    takes them at these shapes) against the module's ``jax.numpy``
    (``attn="full"``), both in bfloat16: apart by roundings to bfloat16
    only (the kernels keep the grouped convolution's sums and the
    parameters' gradients in float32 where XLA's einsum rounds them)."""
    params, u, weights = layer_and_inputs()
    assert cca_passes.cca_plan(
        jax.ShapeDtypeStruct(LATENT[0], BF16), kv_heads=G, taps=(2, 2),
        interpret=True) == CcaPlan("kernels", 128, 128)
    got, got_grads = latent_and_grads("flash", params, u, weights)
    want, want_grads = latent_and_grads("full", params, u, weights)
    _, state = CompressedConvAttention(**{**CCA, "dtype": F32}).apply(
        {"params": params}, u, mutable=["intermediates"])
    exact = state["intermediates"]["latent"][0]
    for name, g, w, e in zip("qkv", got, want, exact):
        assert g.dtype == w.dtype == BF16 and g.shape == w.shape
        assert rel(g, w) <= 6e-3, (name, rel(g, w))
        # No further from the float32 module than the module's own
        # bfloat16 form is.
        assert rel(g, e) <= 1.1 * rel(w, e) + 1e-6, (name, rel(g, e))
    assert rel(got[2], want[2]) == 0.0
    (gp, gu), (wp, wu) = got_grads, want_grads
    assert gu.dtype == BF16 and rel(gu, wu) <= 1e-2, rel(gu, wu)
    for name in ("conv0_kernel", "conv0_bias", "conv1_kernel", "conv1_bias",
                 "temp"):
        assert gp[name].dtype == F32 and gp[name].shape == wp[name].shape
        assert rel(gp[name], wp[name]) <= 1e-2, (name,
                                                 rel(gp[name], wp[name]))
    for name in ("q", "k", "v1", "v2"):
        err = rel(gp[name]["kernel"], wp[name]["kernel"])
        assert err <= 1.5e-2, (name, err)


def operands(seed=0, taps=(2, 2), b=B, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    t0, t1 = taps
    return (jax.random.normal(ks[0], (b, t, H, D), BF16),
            jax.random.normal(ks[1], (b, t, G, D), BF16),
            0.7 * jax.random.normal(ks[2], ((H + G) * D, t0)),
            0.3 * jax.random.normal(ks[3], ((H + G) * D,)),
            jax.random.normal(ks[4], (H + G, t1, D, D)) / D ** 0.5,
            0.3 * jax.random.normal(ks[5], (H + G, D)),
            1.0 + 0.3 * jax.random.normal(ks[6], (G,)))


@pytest.mark.parametrize("taps", [(3, 2), (1, 3), (1, 1)],
                         ids=["taps_3_2", "taps_1_3", "taps_1_1"])
def test_other_taps_reach_as_far_back_as_the_module_s(taps):
    """Taps other than the cell's two and two, over two blocks of four
    strips: the same kernels against the module's form (``_cca_mix_xla``),
    values and the gradients of every operand."""
    args = operands(1, taps, b=1, t=256)
    plan = CcaPlan("kernels", 128, 32)
    weights = [jax.random.normal(jax.random.PRNGKey(9 + i), a.shape)
               for i, a in enumerate(args[:2])]

    def scalar(fn):
        def f(*a):
            out = fn(*a)
            return sum((o.astype(F32) * w).sum()
                       for o, w in zip(out, weights)), out
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(7)),
                                          has_aux=True))

    got = scalar(lambda *a: cca_mix(*a, rope_theta=1e4, rotary_width=D,
                                    plan=plan, interpret=True))(*args)
    want = scalar(lambda *a: _cca_mix_xla(
        *a, taps=taps, dtype=BF16, rope_theta=1e4, width=D))(*args)
    for g, w in zip(got[0][1], want[0][1]):
        assert g.dtype == w.dtype == BF16 and rel(g, w) <= 6e-3, rel(g, w)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype and rel(g, w) <= 1.5e-2, rel(g, w)


def test_no_row_of_one_sequence_reaches_the_next(three_blocks):
    """The halo of a sequence's first block is zeros, forward and in the
    backward's carried rows: the second sequence alone gives what it gives
    behind the first."""
    args = operands(2)
    plan = CcaPlan("kernels", 128, 128)

    def run(q0, k0):
        out, pull = jax.vjp(lambda q, k: cca_mix(
            q, k, *args[2:], rope_theta=5e6, rotary_width=64, plan=plan,
            interpret=True), q0, k0)
        return out, pull(out)

    both, alone = run(*args[:2]), run(args[0][1:], args[1][1:])
    for a, b in zip(jax.tree.leaves(both), jax.tree.leaves(alone)):
        assert rel(a[1:], b) == 0.0


def test_the_kernels_rotation_is_apply_rotary_s():
    """Half of a head of 128 rotated: the table and the two lane rolls
    against ``apply_rotary(width=64)``, and turned back by its transpose."""
    t, width, theta = 200, 64, 5e6
    y = jax.random.normal(jax.random.PRNGKey(0), (t, D))
    positions = jnp.arange(t)

    def turn(width, sign=1.0):
        table = cca_passes.rotary_table(t, D, width, theta)
        low = cca_passes._low_lanes((t, D), width // 2)
        return jax.jit(lambda y: cca_passes.rotate(
            y, table[:, :D], table[:, D:], low, width // 2, sign))

    def rotary(y, width):
        return apply_rotary(y[None, :, None], positions, theta,
                            width)[0, :, 0]

    got, want = turn(width)(y), rotary(y, width)
    assert float(jnp.abs(got - want).max()) <= 1e-6
    assert (got[:, width:] == y[:, width:]).all()
    _, pull = jax.vjp(lambda y: rotary(y, width), y)
    assert float(jnp.abs(turn(width, -1.0)(y) - pull(y)[0]).max()) <= 1e-6
    assert float(jnp.abs(turn(width, -1.0)(got) - y).max()) <= 1e-5
    # The whole head rotated: no lane passes.
    assert float(jnp.abs(turn(D)(y) - rotary(y, D)).max()) <= 1e-6


# ------------------------------------------------------------- the plan


def seen(T=16_384, num_heads=8, kv_heads=2, head_dim=128, taps=(2, 2),
         itemsize=2, interpret=False, manual_axes=False):
    return dict(T=T, num_heads=num_heads, kv_heads=kv_heads,
                head_dim=head_dim, taps=taps, itemsize=itemsize,
                interpret=interpret, manual_axes=manual_axes)


XLA = ("xla", 0, 0)
# What ``cca_passes._plan`` observes -> (form, rows a block, rows a strip).
PLAN_TABLE = {
    # zaya1_1chip: 8 query over 2 KV heads of 128 at T 16,384, bfloat16.
    "cell": (seen(), ("kernels", 1024, 512)),
    "cell_interpreted": (seen(interpret=True), ("kernels", 1024, 512)),
    "cell_compiled_under_shard_map": (seen(manual_axes=True),
                                      ("kernels", 1024, 512)),
    # Interpreted Pallas cannot run under manual mesh axes (jax 0.9.0).
    "interpreted_under_shard_map": (seen(interpret=True, manual_axes=True),
                                    XLA),
    "three_strips_of_128": (seen(T=384), ("kernels", 384, 128)),
    "five_strips_of_128": (seen(T=640), ("kernels", 640, 128)),
    "blocks_of_one_strip": (seen(T=2560), ("kernels", 512, 512)),
    "blocks_of_three_strips_of_256": (seen(T=2304), ("kernels", 768, 256)),
    "strips_of_16": (seen(T=2064), ("kernels", 688, 16)),
    "one_strip_of_16": (seen(T=16), ("kernels", 16, 16)),
    # A group of more or wider heads than the cell's holds fewer rows.
    "heads_of_256": (seen(head_dim=256), ("kernels", 512, 512)),
    "one_group_of_eight": (seen(kv_heads=1), ("kernels", 512, 512)),
    "one_group_of_sixteen_heads_of_256": (
        seen(num_heads=16, kv_heads=1, head_dim=256), ("kernels", 128, 128)),
    "taps_as_far_as_the_halo": (seen(taps=(5, 5)), ("kernels", 1024, 512)),
    # The tiny shapes of the CPU tests, and every way of not tiling.
    "tiny_preset": (seen(T=64, num_heads=4, head_dim=16, itemsize=4,
                         interpret=True), XLA),
    "cell_float32": (seen(itemsize=4), XLA),
    "one_byte_activations": (seen(itemsize=1), XLA),
    "T_not_in_whole_strips": (seen(T=16_390), XLA),
    "heads_of_64": (seen(head_dim=64), XLA),
    "heads_do_not_group": (seen(kv_heads=3), XLA),
    "taps_past_the_halo": (seen(taps=(6, 5)), XLA),
    "no_tap": (seen(taps=(0, 2)), XLA),
}


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_cca_plan_table(case):
    """The one function that chooses the kernels or the module's XLA form:
    a pure table, no kernel, no device."""
    observed, want = PLAN_TABLE[case]
    assert cca_passes._plan(**observed) == CcaPlan(*want)


def test_the_passes_have_no_knob():
    source = inspect.getsource(cca_passes)
    assert "environ" not in source and "getenv" not in source
    assert list(inspect.signature(cca_mix).parameters) == [
        "q0", "k0", "w0", "b0", "w1", "b1", "temp", "rope_theta",
        "rotary_width", "plan", "interpret"]
    assert [f for f in CompressedConvAttention.__dataclass_fields__
            if "kernel" in f or "pass" in f or "fused" in f] == []
    # A plan that is not the kernels' is refused, not run some other way.
    with pytest.raises(ValueError, match="XLA form"):
        cca_mix(*operands(), rope_theta=5e6, rotary_width=64,
                plan=CcaPlan(*XLA), interpret=True)


# ------------------------------------------------------ float32 inside


ARITHMETIC = {"add", "sub", "mul", "div", "neg", "exp", "rsqrt", "logistic",
              "reduce_sum", "integer_pow", "max", "select_n", "tanh",
              "add_any", "square", "roll"}


def kernel_calls():
    args = operands()
    plan = CcaPlan("kernels", 128, 64)

    def loss(*a):
        q, k = cca_mix(*a, rope_theta=5e6, rotary_width=64, plan=plan,
                       interpret=True)
        return q.astype(F32).sum() + k.astype(F32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(7))))(*args)
    return {e.params["jaxpr"].debug_info.func_name: e
            for e in _equations(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"}


def test_the_float32_parts_are_float32_in_the_kernels():
    """Under bfloat16 activations every tap, mean, sum, norm and rotation
    inside the two kernels is float32: bfloat16 values are those just
    loaded (converted at once), those about to be stored (converted once)
    and the MXU's operands — ``z1``, the module's own rounding, its
    matrices, and ``dz2`` — whose products accumulate in float32; the
    parameters' sums leave the backward in float32, as do its carried
    rows."""
    calls = kernel_calls()
    assert set(calls) == {"cca_mix_fwd", "cca_mix_bwd"}
    for name, call in calls.items():
        eqns = list(_equations(call.params["jaxpr"]))
        narrow = [e for e in eqns if e.primitive.name in ARITHMETIC and any(
            getattr(v.aval, "dtype", None) == BF16
            for v in (*e.invars, *e.outvars))]
        assert not narrow, (name, narrow[:3])
        assert sum(e.primitive.name in ARITHMETIC for e in eqns) >= 50
        products = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(products) == (H // G + 1) * (1 if "fwd" in name else 3)
        for e in products:
            assert [v.aval.dtype for v in e.invars] == [BF16, BF16]
            assert e.outvars[0].aval.dtype == F32
        for e in eqns:
            if e.primitive.name != "convert_element_type":
                continue
            src, dst = e.invars[0].aval.dtype, e.outvars[0].aval.dtype
            if BF16 in (src, dst):
                assert {src, dst} == {jnp.dtype(BF16), jnp.dtype(F32)}, (
                    name, e)
    outs = [v.aval for v in calls["cca_mix_bwd"].outvars]
    assert [(o.shape, o.dtype) for o in outs[2:]] == [
        ((B, 4, 8, H * D), F32), ((B, 5, 8, G * D), F32),
        ((B, H, 2 * D, D), F32), ((B, G, 2 * D, D), F32)]
    carried = calls["cca_mix_bwd"].params["jaxpr"].invars[-1].aval
    assert (carried.shape, carried.dtype) == ((H // G + 1, 2, 8, D), F32)


def test_the_kernels_keep_their_inputs_and_nothing_else():
    """What the backward reads of the forward is the two projections'
    outputs and the parameters: no mean, no convolved or normed latent."""
    args = operands()
    _, pull = jax.vjp(lambda *a: cca_mix(
        *a, rope_theta=5e6, rotary_width=64,
        plan=CcaPlan("kernels", 128, 128), interpret=True), *args)

    def kinds(arrays):
        return sorted((x.shape, str(x.dtype)) for x in arrays)

    assert kinds(jax.tree.leaves(pull)) == kinds(args)
