"""``ops/gated_delta.py``: the chunked gated delta rule against the
recurrence it computes, at small sizes on the CPU; its triangular solve
against a general inverse; what it keeps in float32, in the traced
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import (
    DeltaPlan, delta_plan, delta_sizes, gated_delta_recurrence,
    gated_delta_rule, rule_plan, unit_lower_inverse)

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
    """What a call passes between chunks, and the form the rank of ``g``
    gives it: nothing else picks one."""
    assert delta_sizes(1, 8192, 30, 96, 192, 64) == {
        "chunks": 128, "state_bytes": 128 * 30 * 192 * 96 * 4,
        "decay_bytes": 0, "sub_chunks": 0}
    assert delta_sizes(2, 100, 3, 8, 16, 64)["chunks"] == 4
    # A decay a key channel: the log-decays of every (padded) token in
    # float32, and 63 pairs of sub-chunks a chunk of 64 (1 + 2 + ... + 32).
    assert delta_sizes(1, 8192, 32, 128, 128, 64, g_rank=4) == {
        "chunks": 128, "state_bytes": 128 * 32 * 128 * 128 * 4,
        "decay_bytes": 8192 * 32 * 128 * 4, "sub_chunks": 128 * 63}
    plan = delta_plan(64)
    assert plan.form == "xla_chunked" and plan.chunk == 64
    assert delta_plan(64, g_rank=4).form == "xla_chunked_halved"
    # What the plan reads, and nothing that an option could set: the chunk,
    # the rank of g, the call's length, the keys' width, the operands'
    # bytes, whether Pallas is interpreted and whether axes are manual.
    assert delta_plan.__code__.co_varnames[
        :delta_plan.__code__.co_argcount
        + delta_plan.__code__.co_kwonlyargcount] == (
            "chunk", "g_rank", "seq_len", "key_dim", "itemsize",
            "interpret", "manual_axes")


CELL = dict(seq_len=8192, key_dim=128)


@pytest.mark.parametrize("chunk,g_rank,seen,want", [
    # kimilinear_1chip's layer: 128 chunks of 64, eight a grid step; in
    # float32 and interpreted the same.
    (64, 4, CELL, ("tile_kernels", 64, 8)),
    (64, 4, {**CELL, "itemsize": 4}, ("tile_kernels", 64, 8)),
    (64, 4, {**CELL, "interpret": True}, ("tile_kernels", 64, 8)),
    # Chunks a step: the largest power of two up to 8 that divides them.
    (64, 4, {**CELL, "seq_len": 200}, ("tile_kernels", 64, 4)),
    (64, 4, {**CELL, "seq_len": 64 * 6}, ("tile_kernels", 64, 2)),
    (64, 4, {**CELL, "seq_len": 64 * 7}, ("tile_kernels", 64, 1)),
    (128, 4, CELL, ("tile_kernels", 128, 8)),
    (64, 4, {**CELL, "key_dim": 256}, ("tile_kernels", 64, 8)),
    # What stands down: the rehearsal's heads of 16 and any key width off
    # the lanes, chunks the kernels do not tile, interpreted Pallas under
    # manual axes (compiled Mosaic is unaffected); a decay a head never
    # asks.
    (64, 4, {**CELL, "key_dim": 16}, ("xla_chunked_halved", 64, 0)),
    (64, 4, {**CELL, "key_dim": 96}, ("xla_chunked_halved", 64, 0)),
    (16, 4, CELL, ("xla_chunked_halved", 16, 0)),
    (32, 4, CELL, ("xla_chunked_halved", 32, 0)),
    (256, 4, CELL, ("xla_chunked_halved", 256, 0)),
    (64, 4, {**CELL, "interpret": True, "manual_axes": True},
     ("xla_chunked_halved", 64, 0)),
    (64, 4, {**CELL, "manual_axes": True}, ("tile_kernels", 64, 8)),
    (64, 4, {}, ("xla_chunked_halved", 64, 0)),
    (64, 3, CELL, ("xla_chunked", 64, 0)),
])
def test_the_plan_of_a_decay_a_key_channel(chunk, g_rank, seen, want):
    """``delta_plan`` is a pure function of what the call shows: the tile
    kernels where the shape tiles, the XLA halved form where it does not."""
    assert delta_plan(chunk, g_rank, **seen) == DeltaPlan(*want)


def test_rule_plan_reads_the_call():
    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    wide, narrow = s(1, 8192, 32, 128), s(2, 64, 2, 16)
    assert rule_plan(wide, s(1, 8192, 32, 128, dtype=jnp.float32), 64,
                     False) == DeltaPlan("tile_kernels", 64, 8)
    assert rule_plan(narrow, narrow, 16, True).form == "xla_chunked_halved"
    assert rule_plan(wide, s(1, 8192, 32), 64, False).form == "xla_chunked"


# ------------------------------------------------- a decay a key channel


def channel_inputs(T, b=2, H=3, dk=8, dv=16, seed=0, fast=0.0):
    """:func:`delta_inputs` with ``g`` (b, T, H, d_k) and ``beta`` in (0,
    1).  ``fast``: three channels in ten decay by ``g = -fast`` a token —
    ``exp(-20)`` a token passes float32's range inside a chunk of 64 — and
    the others by under half a percent, so that a tile holds entries of
    every size."""
    q, k, v, _, _ = delta_inputs(T, b, H, dk, dv, seed)
    r = np.random.default_rng(seed + 100)
    g = -r.uniform(0.001, 0.5, (b, T, H, dk))
    if fast:
        g = np.where(r.uniform(size=g.shape) < 0.3, -fast, g * 0.01)
    beta = r.uniform(0.05, 0.95, (b, T, H))
    return q, k, v, jnp.asarray(g, jnp.float32), jnp.asarray(beta,
                                                             jnp.float32)


# The two forms of a rank-4 call, by the sizes that give them: the XLA
# halved form at keys of 8 (any chunk), the tile kernels — interpreted
# here — at keys of 128 in chunks of 64, two heads of one sequence.
FORMS = {"xla_chunked_halved": dict(b=2, H=3, dk=8, dv=16),
         "tile_kernels": dict(b=1, H=2, dk=128, dv=16)}


def xla_halved(chunk):
    """The rank-4 rule in its XLA form whatever the shapes would take."""
    return lambda *a: gated_delta._per_channel_rule(
        *a, DeltaPlan("xla_chunked_halved", chunk), True)


@pytest.mark.parametrize("form,T,chunk,fast", [
    ("xla_chunked_halved", 37, 16, 0.0), ("xla_chunked_halved", 96, 32, 0.0),
    ("xla_chunked_halved", 128, 64, 0.0),
    ("xla_chunked_halved", 96, 16, 20.0),
    ("xla_chunked_halved", 96, 32, 20.0),
    ("xla_chunked_halved", 96, 64, 20.0),
    ("tile_kernels", 200, 64, 0.0), ("tile_kernels", 128, 64, 20.0),
    ("tile_kernels", 256, 128, 20.0)],
    ids=["c16_T_not_a_multiple", "c32", "c64", "c16_g_to_minus_20",
         "c32_g_to_minus_20", "c64_g_to_minus_20",
         "kernels_c64_T_not_a_multiple", "kernels_c64_g_to_minus_20",
         "kernels_c128_g_to_minus_20"])
def test_per_channel_chunked_rule_equals_the_recurrence(form, T, chunk, fast):
    """``g`` of rank 4 in both forms, with decays of a few percent a token
    and with channels at ``g = -20`` a token: values to 2e-6 and the
    gradients of all five inputs to 5e-6 of the recurrence's in float32,
    as the rank-3 form is held, and finite throughout — every exponent is
    a sum of ``g`` itself, so nothing cancels where the running sums reach
    -1,280 a chunk.  The kernels' gradient of ``g`` is the XLA form's to
    1e-5 besides."""
    x = channel_inputs(T, fast=fast, **FORMS[form])
    assert rule_plan(x[0], x[3], chunk, True).form == form

    def value_and_grads(rule):       # one compile a side, not one an op
        def f(*a):
            o = rule(*a)
            weight = jnp.cos(jnp.arange(o.size, dtype=jnp.float32))
            return (o.astype(jnp.float32) * weight.reshape(o.shape)).sum(), o
        return jax.jit(jax.value_and_grad(f, argnums=range(5),
                                          has_aux=True))(*x)

    (_, want), theirs = value_and_grads(gated_delta_recurrence)
    (_, got), ours = value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk))
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all()) and rel(got, want) <= 2e-6
    assert all(bool(jnp.isfinite(a).all()) for a in ours)
    errors = {n: rel(a, b) for n, a, b in zip(NAMES, ours, theirs)}
    assert max(errors.values()) <= 5e-6, errors
    if form == "tile_kernels":
        (_, other), halved = value_and_grads(xla_halved(chunk))
        assert rel(got, other) <= 2e-6
        assert rel(ours[3], halved[3]) <= 1e-5


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["forward", "forward_and_backward"])
@pytest.mark.parametrize("form,T,C,dk", [
    ("xla_chunked_halved", 64, 16, 32), ("tile_kernels", 128, 64, 128)])
def test_the_float32_parts_of_a_decay_a_key_channel(form, T, C, dk,
                                                    differentiated):
    """The rank-3 jaxpr test's reading of a rank-4 call in both forms, the
    kernels' bodies included, with bfloat16 operands: the state carried from
    chunk to chunk (and its gradient, carried back) is float32; every
    product that takes float32 operands — the solve's, its rule's, the XLA
    form's sums of ``g`` — takes them at HIGHEST and the kernels' bodies
    hold none (their sums are adds); no product takes an operand narrower
    than bfloat16; and every ``exp`` reads float32."""
    b, H, dv = 1, 2, 48
    x = channel_inputs(T, b=b, H=H, dk=dk, dv=dv)
    low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    assert rule_plan(low[0], low[3], C, True).form == form

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
    assert len(float32) >= 2 * (C.bit_length() - 2)     # the doubling
    assert all(e.params["precision"] == highest for e in float32)
    for e in dots:
        assert all(v.aval.dtype in (jnp.bfloat16, jnp.float32)
                   for v in e.invars)
    assert all(e.invars[0].aval.dtype == jnp.float32
               for e in eqns if e.primitive.name == "exp")
    bodies = [e.params["jaxpr"] for e in eqns
              if e.primitive.name == "pallas_call"]
    assert len(bodies) == {("tile_kernels", False): 1,
                           ("tile_kernels", True): 2}.get(
                               (form, differentiated), 0)
    for body in bodies:
        for e in _equations(body):
            if e.primitive.name == "dot_general":
                assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars)
                assert e.outvars[0].aval.dtype == jnp.float32


def test_groups_of_chunks_change_nothing(monkeypatch):
    """The tiles of a long call are made a group of chunks at a time
    (``_TILE_GROUP_ELEMENTS``) and again in the backward pass: walked in
    four groups or at once, values and gradients are the same numbers."""
    from horovod_tpu.ops import gated_delta

    x = channel_inputs(128, b=1, H=2, fast=20.0, seed=5)

    def value_and_grads():
        f = lambda *a: (gated_delta_rule(*a, chunk=16) ** 2).sum()
        return jax.jit(jax.value_and_grad(f, argnums=range(5)))(*x)

    at_once = value_and_grads()
    monkeypatch.setattr(gated_delta, "_TILE_GROUP_ELEMENTS",
                        2 * 1 * 2 * 16 * 8)         # two chunks a group
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=16))(*x)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [4, 8]
    grouped = value_and_grads()
    assert max(rel(a, b) for a, b in zip(jax.tree.leaves(grouped),
                                         jax.tree.leaves(at_once))) <= 1e-6


@pytest.mark.parametrize("form,chunk", [("xla_chunked_halved", 16),
                                        ("tile_kernels", 64)])
def test_one_decay_for_every_channel_is_the_rank_3_rule(form, chunk):
    """``g`` broadcast over the key channels is the decay a head: the two
    ranks give one answer in either form of the second, and a chunk that is
    no power of two is refused by the second rank alone."""
    q, k, v, g, beta = delta_inputs(80, seed=3, beta_range=(0.05, 0.95),
                                    **FORMS[form])
    wide = jnp.broadcast_to(g[..., None], q.shape)
    assert rule_plan(q, wide, chunk, True).form == form
    a, b = jax.jit(lambda g_, w_: tuple(
        gated_delta_rule(q, k, v, u, beta, chunk=chunk) for u in (g_, w_)))(
            g, wide)
    assert rel(b, a) <= 2e-6
    assert jax.eval_shape(lambda: gated_delta_rule(
        q, k, v, g, beta, chunk=24)).shape == a.shape
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_rule(q, k, v, wide, beta, chunk=24)


@pytest.mark.parametrize("form,T,C,dk", [
    ("xla_chunked_halved", 128, 32, 8), ("tile_kernels", 192, 64, 128)])
def test_the_halved_form_holds_no_tile_a_channel_and_no_positive_exponent(
        form, T, C, dk):
    """In the traced program of a rank-4 call, the kernels' bodies
    included: no array with two chunk axes AND the key axis ((C, C, d_k) in
    any order), no (T, T) array, and every ``exp`` reads either a ``min(.,
    0)`` or the carried total of a chunk (a sum of ``g``); the running
    sums, the solve and the carried state are float32 with bfloat16
    operands."""
    dv = 16
    x = channel_inputs(T, b=1, H=2, dk=dk, dv=dv)
    x = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    assert rule_plan(x[0], x[3], C, True).form == form
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=C))(*x)

    def walk(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    eqns = list(walk(jaxpr.jaxpr))
    for e in eqns:
        for v_ in e.outvars:
            shape = v_.aval.shape
            assert shape.count(T) < 2, (e.primitive, shape)
            assert not (shape.count(C) >= 2 and dk in shape
                        and len(shape) >= 3 and shape[-1] == dk), (
                            e.primitive, shape)
    made_by = {v_: e for e in eqns for v_ in e.outvars}
    exps = [e for e in eqns if e.primitive.name == "exp"]
    # The kernel makes every level's factor by one ``exp``; the pass its own.
    assert len(exps) >= (2 if form == "tile_kernels" else 3)
    for e in exps:
        source = made_by.get(e.invars[0])
        assert source is None or source.primitive.name == "min" or (
            e.invars[0].aval.shape[-1] == dk
            and C not in e.invars[0].aval.shape), source
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels == (["kda_tiles_fwd"] if form == "tile_kernels" else [])
    # The one pass over the chunks (the kernel's walk over a step's chunks
    # carries nothing).
    scans = [e for e in eqns if e.primitive.name == "scan"
             and e.params["num_carry"]
             and e.params["jaxpr"].jaxpr.invars[0].aval.ndim == 4]
    assert len(scans) == 1
    carry = scans[0].params["jaxpr"].jaxpr.invars[0].aval
    assert carry.shape[-2:] == (dv, dk) and carry.dtype == jnp.float32
