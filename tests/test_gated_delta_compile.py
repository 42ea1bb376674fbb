"""The tile kernels of the gated delta rule with a decay a key channel
(``ops/gated_delta.py``: ``kda_tiles_fwd``, ``kda_tiles_bwd``) compiled for a
described TPU v5e (see ``tests/_v5e.py``) at ``kimilinear_1chip``'s layer —
one sequence of 8,192, 32 heads of 128 | 128 in chunks of 64 — and at every
tiling ``delta_plan`` admits, and what their bodies keep in float32, read
from the jaxpr.  The interpreted tests of the same kernels are
``test_gated_delta.py``'s.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _v5e import custom_calls, pallas_calls, v5e  # noqa: F401
from horovod_tpu.ops import gated_delta as gd

B, T, H, DK, DV, C = 1, 8192, 32, 128, 128, 64


def shapes(one, t=T, dk=DK, dtype=jnp.bfloat16, heads=H):
    def s(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    return (s(B, t, heads, dk), s(B, t, heads, dk), s(B, t, heads, DV),
            s(B, t, heads, dk, dt=jnp.float32),
            s(B, t, heads, dt=jnp.float32))


def test_the_rule_at_the_cell_s_layer(v5e):
    """Value and five gradients of the rule as the mixer calls it, under a
    ``jax.checkpoint``, with the kernels asked for compiled: three kernels —
    the forward, the forward again in the checkpoint's replay, one backward
    whose residuals are the rule's inputs — at 8 chunks a grid step, no
    second walk over groups of chunks (one ``while``: the sequential pass,
    forward and transposed), and a plan under the XLA form's."""
    args = shapes(SingleDeviceSharding(v5e[0]))
    assert gd.rule_plan(args[0], args[3], C, False) == gd.DeltaPlan(
        "tile_kernels", C, 8)

    def loss(*a):
        return gd.gated_delta_rule(*a, chunk=C, interpret=False).astype(
            jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=range(5))).lower(*args)
    assert [name for name, _ in custom_calls(lowered.as_text())] == [
        "kda_tiles_bwd", "kda_tiles_fwd", "kda_tiles_fwd"]
    compiled = lowered.compile()
    kernels = [line.split(" = ")[0] for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3, kernels
    _, grads = compiled.out_info
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 2.5 * 2 ** 30, plan / 2 ** 30


# (tokens, chunk, key width, operands): every tiling the plan admits — 8, 4,
# 2 and 1 chunks of 64 a grid step, chunks of 128, keys two lane tiles wide,
# float32 operands.
@pytest.mark.parametrize("t,chunk,dk,dtype,per", [
    (8192, 64, 128, "bfloat16", 8), (64 * 12, 64, 128, "bfloat16", 4),
    (64 * 6, 64, 128, "bfloat16", 2), (64 * 7, 64, 128, "bfloat16", 1),
    (8192, 128, 128, "bfloat16", 8),
    (8192, 64, 256, "bfloat16", 8), (8192, 64, 128, "float32", 8)])
def test_every_tiling_the_plan_admits_compiles(v5e, t, chunk, dk, dtype, per):
    """The kernel pair alone — ``_tiles`` and its transpose — at the plan's
    own tiling for the shape."""
    heads = 4 if t == T else 2
    q, k, _, g, _ = shapes(SingleDeviceSharding(v5e[0]), t, dk,
                           jnp.dtype(dtype), heads)
    plan = gd.rule_plan(q, g, chunk, False)
    assert plan == gd.DeltaPlan("tile_kernels", chunk, per)

    def pair(q, k, g):
        out, back = jax.vjp(
            lambda *a: gd._tiles(*a, chunk, plan.chunks_a_step, False),
            q, k, g)
        return out, back(out)

    lowered = jax.jit(pair).lower(q, k, g)
    assert [name for name, _ in custom_calls(lowered.as_text())] == [
        "kda_tiles_bwd", "kda_tiles_fwd"]
    lowered.compile()
    grids = {name: grid for name, grid, _ in pallas_calls(
        jax.make_jaxpr(pair)(q, k, g).jaxpr)}
    assert grids == {"kda_tiles_fwd": (B, t // chunk // per, heads),
                     "kda_tiles_bwd": (B, t // chunk // per, heads)}


def _body(name, traced):
    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call" and e.params["name"] == name:
                yield e.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    def equations(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from equations(sub)

    (body,) = walk(traced.jaxpr)
    return list(equations(body))


@pytest.mark.parametrize("name", ["kda_tiles_fwd", "kda_tiles_bwd"])
def test_the_kernel_bodies_float32_parts(name):
    """What no comparison of outputs sees, read from the kernels' bodies
    with bfloat16 operands: every exponent is made of ``g`` by float32 adds
    — no product and NO subtraction anywhere in a body, so no difference of
    two running sums —; every ``exp`` reads a ``min(., 0)`` of such a sum,
    in float32, one a level and one each for ``into`` and ``out_of``; every
    product takes bfloat16 operands and gives float32; no level's pairs are
    skipped (one product a level, masked); nothing narrower than float32 is
    summed; and a body holds its levels once — the chunks of a step are a
    loop."""
    levels = C.bit_length() - 1
    per = 2

    def s(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt)

    q, g = s(1, per * C, 2, DK), s(1, per * C, 2, DK, dt=jnp.float32)

    def pair(q, k, g):
        out, back = jax.vjp(lambda *a: gd._tiles(*a, C, per, True), q, k, g)
        return out, back(out)

    eqns = _body(name, jax.make_jaxpr(pair)(q, q, g))
    made_by = {v: e for e in eqns for v in e.outvars}
    assert not [e for e in eqns if e.primitive.name in ("sub", "neg", "div")]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == levels       # a level's products as one
    for e in dots:
        assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars)
        assert e.outvars[0].aval.dtype == jnp.float32
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) == levels + 2
    for e in exps:
        assert e.invars[0].aval.dtype == jnp.float32
        assert e.invars[0].aval.shape == (C, DK)
        source = made_by[e.invars[0]]
        assert source.primitive.name == "min"
        # What the guard reads is a sum: an add, or a select between two.
        assert made_by[source.invars[0]].primitive.name in ("add", "select_n")
    loops = [e for e in eqns if e.primitive.name in ("scan", "while")]
    assert len(loops) == 1
    for e in eqns:
        if e.primitive.name in ("add", "reduce_sum", "mul"):
            assert all(v.aval.dtype in (jnp.float32, jnp.int32)
                       for v in e.invars if hasattr(v, "aval")), e


def test_each_kernel_body_is_traced_once_a_step_not_once_a_layer(monkeypatch):
    """Three mixers at the kernels' widths, no chip and no compile: the
    drivers ``_tiles_fwd`` / ``_tiles_bwd`` are ``jax.jit(inline=True)``,
    so tracing the stack's gradient runs the backward body once and the
    forward body twice — as the forward that runs and as the mixers'
    ``jax.checkpoint``'s replay (a rule traced while the checkpoint's jaxpr
    is evaluated sees another trace context) — where a trace a layer would
    be three and six; every layer leaves its three kernels."""
    import collections
    import functools

    import flax.linen as nn

    from horovod_tpu.models.linear_attention import KimiDeltaAttention

    calls = collections.Counter()

    def counted(name):
        body = getattr(gd, name)

        @functools.wraps(body)
        def call(*args, **kwargs):
            calls[name] += 1
            return body(*args, **kwargs)
        return call

    for name in ("_tiles_fwd_kernel", "_tiles_bwd_kernel"):
        monkeypatch.setattr(gd, name, counted(name))

    class Three(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(3):
                x = x + KimiDeltaAttention(num_heads=2, key_dim=128,
                                           value_dim=128, chunk=64,
                                           low_rank=16)(x)
            return x

    # No other test's: a trace made earlier would be shared.
    x = jax.ShapeDtypeStruct((1, 64 * 5, 48), jnp.bfloat16)
    params = jax.eval_shape(
        lambda x_: Three().init(jax.random.PRNGKey(0), x_)["params"], x)
    jax.clear_caches()
    calls.clear()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x_: Three().apply({"params": p}, x_).astype(
            jnp.float32).sum()))(params, x)
    assert dict(calls) == {"_tiles_fwd_kernel": 2, "_tiles_bwd_kernel": 1}
    assert collections.Counter(
        name for name, _, _ in pallas_calls(jaxpr.jaxpr)) == {
            "kda_tiles_fwd": 6, "kda_tiles_bwd": 3}
