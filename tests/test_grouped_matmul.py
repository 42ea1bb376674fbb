"""The experts' grouped matmuls as Pallas kernels
(``ops/grouped_matmul.py``), interpreted on the CPU: the three products and
the custom VJP against a float32 loop over the groups on seeded rows, for
group sizes that meet every edge of the tiling; bfloat16 against the same
loop beside what ``lax.ragged_dot`` reads there; the float32 accumulators,
held in the kernels' jaxprs; the weight gradient handed a float32 block to
add to, and the same walk landing rows on their tokens, against the
scatter-add; the one function that chooses a form, as a table; and the
``moe.fused_matmuls`` counter.
"""

import inspect
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.metrics import registry
from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops.grouped_matmul import (
    GroupedPlan, grouped_matmul, grouped_plan)
from horovod_tpu.parallel.moe import DroplessMoE

from test_gated_delta import _equations

F32 = jnp.float32

# Tiles of 64 rows worked through in strips of 32, blocks of 128 columns:
# the cell's plan at an eighth of its rows, so that every edge is met in
# 256 rows.
SMALL = GroupedPlan("kernels", 64, 32, 128, 0, 128)
M, K, N = 256, 128, 256

# name -> group sizes over 256 rows (tiles at 64, 128, 192; strips at 32).
SIZES = {
    "even_on_the_tiles": [64, 64, 64, 64],
    "uneven": [37, 91, 50, 78],
    "a_zero_group_first_inside_and_last": [0, 100, 0, 0, 156, 0],
    "a_group_shorter_than_a_strip": [60, 5, 3, 188],
    "a_boundary_inside_a_tile_and_on_a_strip": [32, 40, 120, 64],
    "trailing_empty_rows": [10, 20, 30],
    "trailing_empty_rows_past_whole_tiles": [64, 0, 30],
    "one_group_takes_all": [256, 0, 0],
    "no_group_has_a_row": [0, 0, 0],
    "the_last_takes_the_rest": [17, 5, 0, 9, 225],
    "more_groups_than_tiles": [3] * 40 + [136],
}


def operands(sizes, dtype, seed=0):
    G = len(sizes)
    ks = jax.random.split(jax.random.PRNGKey(seed + sum(sizes)), 3)
    x = jax.random.normal(ks[0], (M, K), F32).astype(dtype)
    w = (jax.random.normal(ks[1], (G, K, N), F32) / np.sqrt(K)).astype(dtype)
    dy = jax.random.normal(ks[2], (M, N), F32).astype(dtype)
    return x, w, dy, jnp.asarray(sizes, jnp.int32)


def loop(x, w, dy, sizes):
    """The definition: one float32 product a group at HIGHEST, its input
    gradient and its weight gradient; rows past the last group are
    zeros."""
    x, w, dy = (np.asarray(a.astype(F32), np.float64) for a in (x, w, dy))
    y, dx, dw = np.zeros((M, N)), np.zeros((M, K)), np.zeros(w.shape)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = slice(start, start + size)
        y[rows] = x[rows] @ w[g]
        dx[rows] = dy[rows] @ w[g].T
        dw[g] = x[rows].T @ dy[rows]
        start += size
    return y, dx, dw


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def three_products(x, w, dy, sizes, plan=SMALL):
    return (gm._gmm(x, w, sizes, transposed=False, plan=plan,
                    interpret=True),
            gm._gmm(dy, w, sizes, transposed=True, plan=plan,
                    interpret=True),
            gm._tgmm(x, dy, sizes, plan=plan,
                     interpret=True))


@pytest.mark.parametrize("case", SIZES)
def test_the_three_products_equal_the_loop_over_groups(case):
    """Float32 operands: each kernel against the per-group loop, to
    rounding; rows no group holds are zeros, an empty group's weight
    gradient is zeros."""
    x, w, dy, sizes = operands(SIZES[case], F32)
    want = loop(x, w, dy, sizes)
    for name, got, ref in zip(("y", "dx", "dw"),
                              three_products(x, w, dy, sizes), want):
        assert got.dtype == F32
        assert rel(got, ref) < 2e-6, (case, name)
    landed = sum(SIZES[case])
    y, dx, dw = three_products(x, w, dy, sizes)
    assert not np.asarray(y[landed:]).any()
    assert not np.asarray(dx[landed:]).any()
    for g, size in enumerate(SIZES[case]):
        if size == 0:
            assert not np.asarray(dw[g]).any(), (case, g)


@pytest.mark.parametrize("case", ["uneven", "trailing_empty_rows",
                                  "a_zero_group_first_inside_and_last",
                                  "the_last_takes_the_rest"])
def test_the_custom_vjp_equals_the_loop_s_gradients(case):
    """``jax.vjp`` of the op the layer calls: value, input gradient and
    weight gradient against the loop; the group sizes take no gradient."""
    x, w, dy, sizes = operands(SIZES[case], F32)
    y, pull = jax.vjp(lambda x, w: gm._fused(x, w, sizes, SMALL, True), x, w)
    dx, dw = pull(dy)
    for name, got, ref in zip(("y", "dx", "dw"), (y, dx, dw),
                              loop(x, w, dy, sizes)):
        assert rel(got, ref) < 2e-6, (case, name)


TILINGS = [(64, 64, 128), (128, 32, 256), (256, 128, 128), (32, 16, 128)]


@pytest.mark.parametrize("rows,strip,cols", TILINGS)
def test_every_tiling_gives_the_same_products(rows, strip, cols):
    """Rows a tile, rows a strip and columns a block change the walk, not
    the answer."""
    x, w, dy, sizes = operands(SIZES["a_group_shorter_than_a_strip"], F32)
    plan = GroupedPlan("kernels", rows, strip, cols, 0, 128)
    for got, ref in zip(three_products(x, w, dy, sizes, plan),
                        loop(x, w, dy, sizes)):
        assert rel(got, ref) < 2e-6


def handed_block(sizes, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (len(sizes), K, N), F32)


@pytest.mark.parametrize("case", [
    "uneven", "even_on_the_tiles", "a_zero_group_first_inside_and_last",
    "trailing_empty_rows", "one_group_takes_all", "no_group_has_a_row",
    "more_groups_than_tiles"])
def test_a_handed_block_is_added_to(case):
    """``_tgmm`` handed a float32 block gives the block plus what it gives
    with none — ragged, empty and whole groups; an empty group's block
    comes back as it went in — in float32 whatever the operands' dtype,
    where the block-less call rounds to theirs."""
    for dtype in (F32, jnp.bfloat16):
        x, _, dy, sizes = operands(SIZES[case], dtype)
        block = handed_block(SIZES[case])
        got = gm._tgmm(x, dy, sizes, block, plan=SMALL, interpret=True)
        bare = gm._tgmm(x, dy, sizes, plan=SMALL, interpret=True)
        assert (got.dtype, bare.dtype) == (F32, dtype)
        x64, dy64 = (np.asarray(a.astype(F32), np.float64) for a in (x, dy))
        want, start = np.asarray(block, np.float64).copy(), 0
        for g, size in enumerate(SIZES[case]):
            want[g] += x64[start:start + size].T @ dy64[start:start + size]
            start += size
        assert rel(got, want) < 2e-6, case
        if dtype == F32:
            assert rel(got, block + bare) < 2e-6, case
        for g, size in enumerate(SIZES[case]):
            if size == 0:
                np.testing.assert_array_equal(got[g], block[g])


@pytest.mark.parametrize("rows,strip,cols", TILINGS)
def test_every_tiling_adds_to_the_handed_block_alike(rows, strip, cols):
    x, _, dy, sizes = operands(SIZES["a_group_shorter_than_a_strip"], F32)
    plan = GroupedPlan("kernels", rows, strip, cols, 0, 128)
    block = handed_block(sizes)
    assert rel(gm._tgmm(x, dy, sizes, block, plan=plan, interpret=True),
               block + gm._tgmm(x, dy, sizes, plan=plan, interpret=True)
               ) < 2e-6


# Tokens of the 256 rows of a window over 512 tokens in tiles of 128; a
# token past the last (512) is a row that landed nowhere.
def landing_tokens(case):
    key = jax.random.PRNGKey(11)
    if case == "tokens_repeat_inside_the_window":
        token = jax.random.randint(key, (M,), 0, 40)
    elif case == "token_tiles_with_no_row":
        token = jnp.where(jax.random.bernoulli(key, 0.5, (M,)),
                          jax.random.randint(key, (M,), 0, 128),
                          jax.random.randint(key, (M,), 384, 512))
    elif case == "rows_past_landed":
        token = jax.random.randint(key, (M,), 0, 512).at[150:].set(512)
    elif case == "no_row_landed":
        token = jnp.full((M,), 512)
    else:
        assert case == "one_token_takes_every_row"
        token = jnp.full((M,), 129)
    return jnp.sort(token)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "rows"])
@pytest.mark.parametrize("case", [
    "tokens_repeat_inside_the_window", "token_tiles_with_no_row",
    "rows_past_landed", "no_row_landed", "one_token_takes_every_row"])
def test_the_landing_equals_the_scatter_add(case, gated):
    """The walk with the selection as its left operand, over token tiles:
    a window's bfloat16 rows, sorted by token, land on the float32 block
    as ``block.at[token].add(rows.astype(float32) * gate)`` lands them, to
    float32 rounding (the order of a sum apart) — the gate never rounded
    below float32."""
    n = 512
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(ks[0], (M, N), F32).astype(jnp.bfloat16)
    # Gates with all 24 bits in use.
    gate = jax.random.uniform(ks[1], (M,), F32, 0.01, 1.0) if gated else None
    block = jax.random.normal(ks[2], (n, N), F32)
    token = landing_tokens(case)
    scaled = np.asarray(rows.astype(F32), np.float64) * (
        np.asarray(gate, np.float64)[:, None] if gated else 1.0)
    want = np.asarray(block, np.float64).copy()
    np.add.at(want, np.asarray(token)[np.asarray(token) < n],
              scaled[np.asarray(token) < n])
    got = gm.landed_rows(block, rows, token, gate, plan=SMALL, interpret=True)
    assert got.dtype == F32 and got.shape == (n, N)
    assert rel(got, want) < 2e-6, case
    theirs = block.at[token].add(
        rows.astype(F32) * (gate[:, None] if gated else 1.0), mode="drop")
    assert rel(got, theirs) < 2e-6, case
    untouched = np.setdiff1d(np.arange(n), np.asarray(token))
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(block)[untouched])


def test_the_gradients_called_directly_are_the_op_s_own():
    """``grouped_gradients`` is the op's backward rule: the same two
    transposes, the weight gradient's handed its block where one is
    given."""
    x, w, dy, sizes = operands(SIZES["uneven"], jnp.bfloat16)
    block = handed_block(sizes)
    dx, dw = gm.grouped_gradients(x, w, dy, sizes, SMALL, interpret=True,
                                  block=block)
    _, theirs = jax.vjp(lambda x, w: gm._fused(x, w, sizes, SMALL, True),
                        x, w)
    want_dx, want_dw = theirs(dy)
    np.testing.assert_array_equal(dx, want_dx)
    assert dw.dtype == F32 and rel(dw, block + want_dw.astype(F32)) < 2.0 ** -8
    bare = gm.grouped_gradients(x, w, dy, sizes, SMALL, interpret=True)[1]
    np.testing.assert_array_equal(bare, want_dw)


@pytest.mark.parametrize("case", ["uneven", "more_groups_than_tiles",
                                  "trailing_empty_rows"])
def test_bfloat16_reads_what_ragged_dot_reads(case):
    """bfloat16 operands against the float32 loop on the same (rounded)
    operands: the kernels round once, at the store, so they read what
    ``lax.ragged_dot`` and its transposes read there — within a bfloat16
    step of the largest value, and no further off than ``ragged_dot``
    by more than one more."""
    x, w, dy, sizes = operands(SIZES[case], jnp.bfloat16)
    want = loop(x, w, dy, sizes)
    y, pull = jax.vjp(lambda x, w: lax.ragged_dot(x, w, sizes), x, w)
    ragged = (y, *pull(dy))
    step = 2.0 ** -8
    for name, got, theirs, ref in zip(("y", "dx", "dw"),
                                      three_products(x, w, dy, sizes),
                                      ragged, want):
        assert got.dtype == jnp.bfloat16
        assert rel(got, ref) <= step, (case, name)
        assert rel(got, ref) <= rel(theirs, ref) + step, (case, name)


def kernel_calls():
    x, w, dy, sizes = operands(SIZES["uneven"], jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x, w: jax.vjp(
            lambda x, w: gm._fused(x, w, sizes, SMALL, True), x, w)[1](dy))(
                x, w)
    fwd = jax.make_jaxpr(lambda x, w: gm._fused(x, w, sizes, SMALL, True))(
        x, w)
    calls = {}
    for j in (fwd, jaxpr):
        for e in _equations(j.jaxpr):
            if e.primitive.name == "pallas_call":
                calls[e.params["name"]] = e
    return calls


def test_the_accumulators_are_float32_and_the_operands_stay_narrow():
    """Under bfloat16 rows and weights every product inside the three
    kernels takes its operands as they were loaded — bfloat16, never
    widened on the way into the MXU — and gives float32; what is summed
    (the weight gradient's block over a group's visits) is summed in a
    float32 scratch; bfloat16 is written only by the one conversion before
    a store."""
    calls = kernel_calls()
    assert set(calls) == {"moe_gmm", "moe_gmm_nt", "moe_tgmm"}
    for name, call in calls.items():
        eqns = list(_equations(call.params["jaxpr"]))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert dots, name
        for e in dots:
            assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars), name
            assert e.outvars[0].aval.dtype == F32, name
        for e in eqns:
            if e.primitive.name in ("add", "add_any", "mul"):
                assert all(getattr(v.aval, "dtype", F32) != jnp.bfloat16
                           for v in (*e.invars, *e.outvars)), (name, e)
    scratch = calls["moe_tgmm"].params["jaxpr"].invars[-1].aval
    assert (scratch.shape, scratch.dtype) == ((128, 128), F32)
    # The weight gradient leaves in the operands' dtype: one rounding.
    assert calls["moe_tgmm"].outvars[0].aval.dtype == jnp.bfloat16


def landing_calls():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    rows = jax.random.normal(ks[0], (M, N), jnp.bfloat16)
    gate = jax.random.uniform(ks[1], (M,), F32)
    token = jnp.sort(jax.random.randint(ks[2], (M,), 0, 512))
    calls = {}
    for name, g in (("gated", gate), ("rows", None)):
        jaxpr = jax.make_jaxpr(lambda block: gm.landed_rows(
            block, rows, token, g, plan=SMALL, interpret=True))(
                jnp.zeros((512, N), F32))
        calls[name], = (e for e in _equations(jaxpr.jaxpr)
                        if e.primitive.name == "pallas_call")
    return calls


def test_the_landing_s_gate_and_accumulator_are_float32():
    """Twin of the test above for the landing and for the weight gradient
    handed its block: the gate enters the kernel in float32 and is taken
    apart there into three bfloat16 parts that sum to it exactly (no
    ``mul`` rounds a gated row: a part is SELECTED into the left operand,
    and a bfloat16 part times a bfloat16 row is exact in float32); every
    product takes bfloat16 operands and gives float32; the handed block,
    the scratch and what is written are float32, the block the output's own
    buffer."""
    calls = landing_calls()
    x, _, dy, sizes = operands(SIZES["uneven"], jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda block: gm._tgmm(
        x, dy, sizes, block, plan=SMALL, interpret=True))(
            jnp.zeros((4, K, N), F32))
    calls["handed"], = (e for e in _equations(jaxpr.jaxpr)
                        if e.primitive.name == "pallas_call")
    assert [c.params["name"] for c in calls.values()] == [
        "moe_land", "moe_land", "moe_tgmm"]
    for name, call in calls.items():
        eqns = list(_equations(call.params["jaxpr"]))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(dots) == {"gated": 3, "rows": 1, "handed": 2}[name]
        for e in dots:
            assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars), name
            assert e.outvars[0].aval.dtype == F32, name
        for e in eqns:
            if e.primitive.name in ("add", "add_any", "sub", "mul"):
                assert all(getattr(v.aval, "dtype", F32) != jnp.bfloat16
                           for v in (*e.invars, *e.outvars)), (name, e)
        # No floating product but the MXU's (the walk multiplies indices).
        assert not any(e.primitive.name == "mul" and jnp.issubdtype(
            e.outvars[0].aval.dtype, jnp.floating) for e in eqns), name
        # The block is the operand before the output; they share a buffer.
        operands_, out = call.invars, call.outvars[0].aval
        assert call.params["input_output_aliases"] == (
            (len(operands_) - 1, 0),)
        assert operands_[-1].aval.dtype == out.dtype == F32
        assert operands_[-1].aval.shape == out.shape
        scratch = call.params["jaxpr"].invars[-1].aval
        assert scratch.dtype == F32 and scratch.shape == (
            (128, 128) if name == "handed" else out.shape[1:]), name
    gate = calls["gated"].invars[4].aval
    assert (gate.shape, gate.dtype) == ((1, M), F32)


def test_three_bfloat16_parts_hold_every_bit_of_a_gate():
    gate = jnp.concatenate([
        jax.random.uniform(jax.random.PRNGKey(0), (4096,), F32),
        jnp.asarray([0.0, 1.0, 2.0 ** -20, 1 - 2.0 ** -24, 5.0, 1e-30])])
    parts = gm._gate_parts(gate)
    assert all((p.astype(jnp.bfloat16).astype(F32) == p).all()
               for p in parts)
    np.testing.assert_array_equal(
        np.sum([np.asarray(p, np.float64) for p in parts], axis=0),
        np.asarray(gate, np.float64))


def test_the_walk_lists_every_pair_once_and_every_group():
    """``_visits`` by hand at tiles of 64: pairs in row order, a pair
    listed twice standing twice in a row, an empty group listed with a
    tile it has no row in."""
    tiles, groups, offsets = gm._visits(
        jnp.asarray([64, 0, 30, 100], jnp.int32), 256, 64)
    assert offsets.tolist() == [0, 64, 64, 94, 194]
    pairs = list(zip(tiles.tolist(), groups.tolist()))
    assert pairs == [(0, 0), (0, 0), (1, 1), (1, 2), (1, 2), (1, 3),
                     (2, 3), (3, 3)]
    for sizes in SIZES.values():
        tiles, groups, offsets = gm._visits(jnp.asarray(sizes, jnp.int32),
                                            M, 64)
        tiles, groups = tiles.tolist(), groups.tolist()
        assert len(tiles) == M // 64 + len(sizes)
        assert tiles == sorted(tiles) and groups == sorted(groups)
        assert set(groups) == set(range(len(sizes)))
        assert set(tiles) == set(range(M // 64))
        met = {(t, g) for g, size in enumerate(sizes) for t in range(M // 64)
               if max(offsets[g], t * 64) < min(offsets[g] + size,
                                                (t + 1) * 64)}
        assert met <= set(zip(tiles, groups))


PLAN_TABLE = {
    # name: (rows, groups, k, n, itemsize, interpret, manual_axes,
    #        vmem_headroom) -> form
    "twotower_window_up": ((18_432, 8, 2688, 1920, 2, False, False, True),
                           "kernels"),
    "twotower_window_down": ((18_432, 8, 1920, 2688, 2, False, False, True),
                             "kernels"),
    "olmoe_up": ((131_072, 64, 2048, 1024, 2, False, False, True),
                 "kernels"),
    "olmoe_down": ((131_072, 64, 1024, 2048, 2, False, False, True),
                   "kernels"),
    # A window of 33 strips, not whole tiles: tiles of one strip.
    "latent_window_up": ((8_448, 8, 1024, 2688, 2, False, False, True),
                         "kernels"),
    "latent_window_down": ((8_448, 8, 2688, 1024, 2, False, False, True),
                           "kernels"),
    "interpreted_off_the_mesh": ((512, 4, 128, 128, 2, True, False, True),
                                 "kernels"),
    "interpreted_under_manual_axes": (
        (512, 4, 128, 128, 2, True, True, True), "ragged_dot"),
    "compiled_under_manual_axes": (
        (18_432, 8, 2688, 1920, 2, False, True, True), "kernels"),
    "hidden_off_the_lanes": ((18_432, 8, 2688, 1856, 2, False, False, True),
                             "ragged_dot"),
    "tiny_preset_olmoe": ((256, 8, 64, 32, 2, True, False, True),
                          "ragged_dot"),
    "tiny_preset_held": ((56, 2, 16, 128, 4, True, False, True),
                         "ragged_dot"),
    "rows_off_the_tile": ((18_440, 8, 2688, 1920, 2, False, False, True),
                          "ragged_dot"),
    "float32_operands": ((18_432, 8, 2688, 1920, 4, False, False, True),
                         "ragged_dot"),
    "a_device_of_16_mb_vmem": (
        (18_432, 8, 2688, 1920, 2, False, False, False), "ragged_dot"),
}


@pytest.mark.parametrize("case", PLAN_TABLE)
def test_plan_table(case):
    """``_plan`` is a pure function of what the op observes: the two
    cells' shapes take the kernels, with the hidden width padded to whole
    128-lane tiles; the tiny presets, interpreted Pallas under manual
    axes and whatever does not tile take ``lax.ragged_dot``, padded to
    256."""
    args, form = PLAN_TABLE[case]
    names = ("rows", "groups", "k", "n", "itemsize", "interpret",
             "manual_axes", "vmem_headroom")
    plan = gm._plan(**dict(zip(names, args)))
    assert plan.form == form
    if form == "kernels":
        tile = gm._STRIP if args[0] % gm._ROWS else gm._ROWS
        assert plan == GroupedPlan("kernels", tile, gm._STRIP,
                                   gm._MOST_COLS, gm._VMEM_MB, 128)
        assert args[0] % plan.rows == 0 and plan.rows % plan.strip == 0
    else:
        assert plan == GroupedPlan("ragged_dot", 0, 0, 0, 0, 256)


def test_the_plan_has_no_knob():
    """No option, environment variable or model's name picks a form."""
    assert list(inspect.signature(gm._plan).parameters) == [
        "rows", "groups", "k", "n", "itemsize", "interpret", "manual_axes",
        "vmem_headroom"]
    source = inspect.getsource(gm)
    assert "environ" not in source and "getenv" not in source
    assert list(inspect.signature(grouped_matmul).parameters) == [
        "x", "w", "group_sizes", "plan", "interpret"]


def test_the_blocks_cut_the_cells_widths_evenly():
    assert [gm._block(w, 1024) for w in (2688, 1920, 2048, 1024, 128)] == [
        896, 640, 1024, 1024, 128]
    assert gm._block(2688, 512) == 384


def test_the_op_falls_back_to_ragged_dot_where_the_plan_says():
    x, w, dy, sizes = operands(SIZES["uneven"], F32)
    plan = grouped_plan(x, w.shape[0], N, interpret=True)
    assert plan.form == "ragged_dot"       # float32 operands
    np.testing.assert_array_equal(
        np.asarray(grouped_matmul(x, w, sizes, plan, interpret=True)),
        np.asarray(lax.ragged_dot(x, w, sizes)))


# ------------------------------------------------- the layer and its counter


class FourHeldLayers(nn.Module):
    """The expert layers of the ``twotower_1chip`` cell's stack: four, each
    holding 8 of 128 relu² experts."""
    hidden: int

    @nn.compact
    def __call__(self, x):
        for i in range(4):
            x = x + DroplessMoE(num_experts=128, hidden=self.hidden, top_k=6,
                                router="sigmoid", renormalize=True,
                                activation="relu2", held=(0, 8),
                                name=f"moe_{i}")(x)[0]
        return x


def fused_matmuls(module, x):
    noted = {}
    jax.eval_shape(noting_layers(
        lambda x: module.init(jax.random.PRNGKey(0), x), noted), x)
    return sum(c["moe.fused_matmuls"] for c in noted.values())


def test_fused_matmuls_counts_eight_three_and_none():
    """``moe.fused_matmuls`` — forward grouped matmuls a step that took the
    kernels — by tracing the layers at the cells' shapes (no product is
    computed): 8 in ``twotower_1chip`` (four layers' up and down), 3 in
    ``olmoe_1chip`` (gate, up, down), 0 at the tiny presets' shapes."""
    tokens = jax.ShapeDtypeStruct((16_384, 2688), jnp.bfloat16)
    assert fused_matmuls(FourHeldLayers(hidden=1856), tokens) == 8
    olmoe = DroplessMoE(num_experts=64, hidden=1024, top_k=8)
    assert fused_matmuls(
        olmoe, jax.ShapeDtypeStruct((16_384, 2048), jnp.bfloat16)) == 3
    tiny = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
    assert fused_matmuls(FourHeldLayers(hidden=48), tiny) == 0
    assert fused_matmuls(DroplessMoE(num_experts=8, hidden=16, top_k=2),
                         tiny) == 0


def tileable_layer(held):
    """A layer whose sorted rows and widths tile — 256 tokens of width 128,
    top-2 of 4 experts 128 wide: 512 rows — so its plan takes the
    kernels."""
    layer = DroplessMoE(num_experts=4, hidden=128, top_k=2,
                        **({"held": (0, 4), "activation": "relu2"}
                           if held else {}))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    return layer, params, x


@pytest.mark.parametrize("held", [False, True], ids=["all_experts", "held"])
def test_the_layer_s_answers_do_not_follow_the_form(held, monkeypatch):
    """The same layer through the kernels and through ``lax.ragged_dot``
    (its plan patched): output and every gradient agree to bfloat16
    rounding."""
    layer, params, x = tileable_layer(held)
    assert grouped_plan(jax.ShapeDtypeStruct((512, 128), jnp.bfloat16), 4,
                        128, interpret=True).form == "kernels"

    def value_and_grads():
        def loss(p, x):
            out = layer.apply({"params": p}, x)[0]
            return (out.astype(F32) ** 2).sum(), out
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    (_, out), (dp, dx) = value_and_grads()
    monkeypatch.setattr(
        "horovod_tpu.parallel.moe.grouped_plan",
        lambda *a, **k: GroupedPlan("ragged_dot", 0, 0, 0, 0, 256))
    (_, want), (want_dp, want_dx) = value_and_grads()
    assert rel(out, want) < 2e-2
    assert rel(dx, want_dx) < 2e-2
    for name in want_dp:
        if name != "router":
            assert rel(dp[name], want_dp[name]) < 2e-2, name


class OneLayer(nn.Module):
    @nn.compact
    def __call__(self, x):
        return DroplessMoE(num_experts=4, hidden=128, top_k=2,
                           name="moe")(x)[0]


def test_a_tiling_layer_counts_its_fused_matmuls_at_dispatch(hvd):
    """Through ``make_train_step`` on one device: the layer trains and
    every dispatch bumps ``moe.fused_matmuls`` by the three forward
    products that took the kernels; the lowered step names the three
    kernels under the layer's ``experts`` scope, where the cell's reader
    (``moe_ms``) looks."""
    import optax

    from benchmark.metrics import moe_ms
    from horovod_tpu.parallel.mesh import RANKS_AXIS

    _, _, x = tileable_layer(held=False)
    net = OneLayer()
    params = net.init(jax.random.PRNGKey(2), x)["params"]
    batch = x[None]

    def loss_fn(p, aux, batch):
        out = net.apply({"params": p}, batch[0])
        return ((out.astype(F32) - 1.0) ** 2).mean(), aux

    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, batch)[0])).lower(
        params).as_text(debug_info=True)
    stacks = set(re.findall(r'"([^"]*/moe_(?:gmm_nt|gmm|tgmm))[/"]', text))
    assert {s.rsplit("/", 1)[1] for s in stacks} == {
        "moe_gmm", "moe_gmm_nt", "moe_tgmm"}
    for s in stacks:
        assert "moe" in s.split("/") and "experts" in s.split("/"), s
        assert moe_ms.in_expert_layer(s), s

    tx = optax.sgd(0.1)
    step = make_train_step(loss_fn, tx, Mesh(np.asarray(jax.devices()[:1]),
                                             (RANKS_AXIS,)))
    before = registry.snapshot()["counters"].get("moe.fused_matmuls", 0)
    aux, opt_state, losses = {}, tx.init(params), []
    for _ in range(3):
        params, aux, opt_state, loss = step(params, aux, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert registry.snapshot()["counters"].get(
        "moe.fused_matmuls", 0) - before == 9
