"""``ops/gated_delta.py``: the chunked gated delta rule against the
recurrence it computes, at small sizes on the CPU; its triangular solve
against a general inverse; what it keeps in float32, in the traced
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.gated_delta import (
    delta_plan, delta_sizes, gated_delta_recurrence, gated_delta_rule,
    unit_lower_inverse)

NAMES = ("q", "k", "v", "g", "beta")


def rel(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def delta_inputs(T, b=2, H=3, dk=8, dv=16, seed=0, beta_range=(0.05, 1.95)):
    """Unit keys, queries scaled by ``d_k^-1/2``, decays of a few percent a
    token, and ``beta`` on both sides of 1 unless told otherwise."""
    r = np.random.default_rng(seed)
    q = r.standard_normal((b, T, H, dk))
    k = r.standard_normal((b, T, H, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((b, T, H, dv))
    g = -r.uniform(0.001, 0.5, (b, T, H))
    beta = r.uniform(*beta_range, (b, T, H))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def weighted(f, weight):
    return lambda *a: (f(*a).astype(jnp.float32) * weight).sum()


@pytest.mark.parametrize("T,chunk,beta_range", [
    (37, 8, (0.05, 1.95)), (64, 16, (0.05, 1.95)), (64, 64, (1.0, 1.95)),
    (100, 64, (0.05, 1.0)), (5, 8, (0.05, 1.95))],
    ids=["T_not_a_multiple", "four_chunks", "one_chunk_beta_over_1",
         "a_chunk_and_a_tail_beta_under_1", "shorter_than_a_chunk"])
def test_chunked_rule_equals_the_recurrence(T, chunk, beta_range):
    """Values to 2e-6 and the gradients of all five inputs to 5e-6 of the
    recurrence's, in float32."""
    x = delta_inputs(T, beta_range=beta_range)
    want = gated_delta_recurrence(*x)
    got = gated_delta_rule(*x, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert rel(got, want) <= 2e-6
    weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
        want.shape)
    ours = jax.grad(weighted(
        lambda *a: gated_delta_rule(*a, chunk=chunk), weight),
        argnums=range(5))(*x)
    theirs = jax.grad(weighted(gated_delta_recurrence, weight),
                      argnums=range(5))(*x)
    errors = {n: rel(a, b) for n, a, b in zip(NAMES, ours, theirs)}
    assert max(errors.values()) <= 5e-6, errors


def test_two_chunk_lengths_give_one_answer():
    """The mathematics does not depend on the chunk: 8 against 32, values
    and gradients."""
    x = delta_inputs(96, seed=4)
    a, b = (gated_delta_rule(*x, chunk=c) for c in (8, 32))
    assert rel(a, b) <= 2e-6
    ga, gb = (jax.grad(lambda *i: (gated_delta_rule(*i, chunk=c) ** 2).sum(),
                       argnums=range(5))(*x) for c in (8, 32))
    assert max(rel(u, w) for u, w in zip(ga, gb)) <= 5e-6


def test_the_rule_is_causal_and_sequences_start_from_nothing():
    """A token's output does not read a later token, a later chunk or
    another sequence."""
    x = delta_inputs(48, seed=2)
    whole = gated_delta_rule(*x, chunk=16)
    head = gated_delta_rule(*(a[:, :20] for a in x), chunk=16)
    assert rel(whole[:, :20], head) <= 2e-6
    alone = gated_delta_rule(*(a[1:] for a in x), chunk=16)
    assert rel(whole[1:], alone) <= 2e-6


@pytest.mark.parametrize("beta,g,what", [
    (0.0, -0.1, "nothing_written"), (1.0, 0.0, "plain_delta_rule")])
def test_limits_of_the_rule(beta, g, what):
    """``beta`` 0 writes nothing, so the output is zero; ``beta`` 1 without
    decay is the plain delta rule: after a token is written, reading with
    its own key returns its value exactly (``S_t k_t = v_t``)."""
    q, k, v, _, _ = delta_inputs(24, b=1, H=2, seed=6)
    shape = q.shape[:3]
    got = gated_delta_rule(k if beta else q, k, v, jnp.full(shape, g),
                           jnp.full(shape, beta), chunk=8)
    want = v if beta else jnp.zeros_like(v)
    assert float(jnp.abs(got - want).max()) <= 2e-5


@pytest.mark.parametrize("C", [1, 5, 16, 64])
def test_unit_lower_inverse_and_its_rule(C):
    """The finite product against a general inverse of ``I + A``, and its
    own backward rule against autodiff through the general one."""
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(C), (3, C, C))
                 * 1.2 / max(C, 4) ** 0.5, -1)
    eye = jnp.eye(C)
    with jax.default_matmul_precision("highest"):
        got, want = unit_lower_inverse(A), jnp.linalg.inv(eye + A)
        assert rel(got, want) <= 1e-5
        weight = jnp.sin(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        g = jax.grad(lambda a: (unit_lower_inverse(a) * weight).sum())(A)
        w = jax.grad(lambda a: (jnp.linalg.inv(eye + a) * weight).sum())(A)
    assert rel(jnp.tril(g, -1), jnp.tril(w, -1)) <= 1e-5


def test_bfloat16_operands_stay_close_to_float32():
    """The training recipe: operands in bfloat16, sums in float32; ``o`` in
    the operands' dtype, within 2% of the recurrence, gradients too."""
    x = delta_inputs(128, b=1, H=2, dk=32, dv=64, seed=9)
    low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    want = gated_delta_recurrence(*x)
    got = gated_delta_rule(*low, chunk=32)
    assert got.dtype == jnp.bfloat16 and rel(got, want) <= 2e-2
    weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
        want.shape)
    ours = jax.grad(weighted(lambda *a: gated_delta_rule(*a, chunk=32),
                             weight), argnums=range(5))(*low)
    theirs = jax.grad(weighted(gated_delta_recurrence, weight),
                      argnums=range(5))(*x)
    assert max(rel(a, b) for a, b in zip(ours, theirs)) <= 3e-2


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["forward", "forward_and_backward"])
def test_the_float32_parts_are_float32_in_the_traced_program(differentiated):
    """What no comparison of outputs sees is held in the jaxpr: with
    bfloat16 operands the state carried from chunk to chunk (and its
    gradient, carried back) is float32; every (C, C) x (C, C) product —
    the solve's, and its rule's — takes float32 operands at HIGHEST, and so
    does the running sum; no other product takes an operand narrower than
    the inputs' dtype, and the three whose results are summed with
    something (``V'``, the state, ``o``) give float32."""
    b, T, H, dk, dv, C = 1, 64, 2, 32, 48, 16
    x = delta_inputs(T, b=b, H=H, dk=dk, dv=dv)
    low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]

    def f(*a):
        return gated_delta_rule(*a, chunk=C).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(f, argnums=range(5)) if differentiated
                            else f)(*low)
    eqns = list(_equations(traced.jaxpr))
    carried = [v.aval for e in eqns if e.primitive.name == "scan"
               for v in e.outvars[:e.params["num_carry"]]
               if v.aval.shape == (b, H, dv, dk)]
    assert len(carried) == (2 if differentiated else 1)
    assert all(a.dtype == jnp.float32 for a in carried)
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    float32 = [e for e in dots
               if all(v.aval.dtype == jnp.float32 for v in e.invars)]
    # The running sum (and its transpose), three squarings and three
    # products of the doubling at C 16, two products of its rule.
    assert len(float32) == (1 + 6 + 3 if differentiated else 1 + 6)
    for e in float32:
        assert all(v.aval.shape[-1] == C for v in e.invars)
        assert e.params["precision"] == highest
    rest = [e for e in dots if e not in float32]
    assert len(rest) >= 7
    for e in rest:
        assert all(v.aval.dtype in (jnp.bfloat16, jnp.float32)
                   for v in e.invars)
    summed = [e for e in rest if e.outvars[0].aval.shape[-2:] in (
        (C, dv), (dv, dk)) and all(v.aval.dtype == jnp.bfloat16
                                   for v in e.invars)]
    assert sum(e.outvars[0].aval.dtype == jnp.float32 for e in summed) >= 4


def test_sizes_and_plan():
    """What a call passes between chunks, and the one form there is."""
    assert delta_sizes(1, 8192, 30, 96, 192, 64) == {
        "chunks": 128, "state_bytes": 128 * 30 * 192 * 96 * 4}
    assert delta_sizes(2, 100, 3, 8, 16, 64)["chunks"] == 4
    plan = delta_plan(64)
    assert plan.form == "xla_chunked" and plan.chunk == 64
    assert delta_plan.__code__.co_varnames == ("chunk",)
