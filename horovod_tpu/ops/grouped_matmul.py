"""Grouped matmuls over rows sorted by group: an expert layer's products.

``rows`` (M, K) hold G groups of consecutive rows, ``group_sizes`` (G,)
long, and every group has a matrix of its own, ``w`` (G, K, N).  Three
products make an expert layer's forward and backward pass:

* ``rows_g @ w_g`` for every group, (M, N) (:func:`grouped_matmul`);
* its input gradient, ``dy_g @ w_g.T``, (M, K) — the same kernel, the
  matrix read transposed;
* its weight gradient, ``rows_g.T @ dy_g``, one (K, N) block a group.

Operands are in the activations' dtype (bfloat16 in training); every
product is accumulated in float32 and rounded once, at the store.  Group
sizes arrive as data.  Rows past the last group belong to none: they read
as zeros in the weight gradient and are written as zeros by the other two.

Two forms, and one place that chooses (:func:`_plan`, a pure function of
the shapes, the dtype's width, ``interpret``, manual mesh axes and whether
the device backs a scoped-VMEM budget above Mosaic's default; no option
picks a form):

* **Pallas TPU kernels** where the widths are whole 128-lane tiles and the
  rows whole tiles (``olmoe_1chip``, ``twotower_1chip``,
  ``nemo3super_1chip``).  The rows are cut
  into tiles of ``plan.rows``; a *visit* is one (row tile, group) pair
  whose rows meet, listed in row order by :func:`_visits` from the group
  sizes and read by the kernels' index maps (scalar prefetch): a tile that
  holds a boundary is visited once for each of its groups, the other
  group's rows masked.  ``moe_gmm`` / ``moe_gmm_nt`` hold a visit's row
  tile and the group's (K, column block) in VMEM, the block staying put
  while the visits are the same group's, and work through the tile a strip
  of rows at a time — a strip the group has no row in is skipped, so a
  boundary costs one strip twice, not a tile.  ``moe_tgmm`` keeps a group's
  (K block, N block) of the weight gradient in float32 in VMEM over the
  group's visits and writes it once, zeros for an empty group — or, handed
  a float32 block, starts from it and writes float32 back into its buffer
  (``input_output_aliases``): a sum over calls with no pass of its own
  (:func:`grouped_gradients`).  The same walk LANDS sorted rows on their
  tokens (``moe_land``, :func:`landed_rows`): the groups are tiles of
  tokens, the handed block the (tokens, N) float32 accumulator, the left
  operand the selection the kernel forms from the rows' token ids —
  ``S.T @ rows`` is a scatter-add done by the MXU, a float32 gate taken
  apart into three bfloat16 parts whose products are exact.  The drivers
  are ``jax.jit(inline=True)``: the layers of a stack share one trace of
  each kernel body.
* **``lax.ragged_dot``** otherwise (the tiny shapes of the CPU tests,
  interpreted Pallas under ``shard_map``'s manual axes): the caller's own
  form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

_F32 = jnp.float32


class GroupedPlan(NamedTuple):
    """What :func:`_plan` decides for one grouped matmul and its two
    transposes."""
    form: str       # "kernels" | "ragged_dot"
    rows: int       # rows a tile; 0 in the ragged_dot form
    strip: int      # rows of a tile multiplied at a time
    cols: int       # most columns a block of an output or a weight gradient
    vmem_mb: int    # scoped-VMEM budget asked, MB
    lanes: int      # what a width is padded to a multiple of, by zeros


# Rows a tile, rows a strip and the most columns a block: the best of a
# sweep on the chip at the two cells' shapes (PERF.md section 6, PR 35).
# Rows that are whole strips and not whole tiles (a window of 8,448 = 33 x
# 256) take tiles of one strip (PERF.md section 6, PR 46).
_ROWS = 512
_STRIP = 256
_MOST_COLS = 1024
# The scoped-VMEM budget the kernels ask, above Mosaic's default of 16 MB;
# shapes whose blocks would take more than three quarters of it are
# ``lax.ragged_dot``'s.  The cells' largest kernels take 15.7 MB by shapes
# (OLMoE's forward: a (512, 2048) and a (2048, 1024) block twice over, the
# pipeline's two buffers, beside the output's) and 28 (a weight gradient's
# (1024, 1024) block in float32: handed in and written back, two buffers
# each, its scratch and its product; 16.8 with no block handed).
_VMEM_MB = 48
# On the v5e ``lax.ragged_dot`` ran at 33 TFLOP/s at a width of 1856 or
# 1920 and at 63-93 at 2048 (PERF.md section 6, PR 30).
_RAGGED_LANES = 256


def _block(width: int, most: int) -> int:
    """The widest whole-128-lane block no wider than ``most`` that cuts
    ``width`` evenly."""
    tiles = width // 128
    return 128 * max(b for b in range(1, tiles + 1)
                     if tiles % b == 0 and 128 * b <= max(most, 128))


def _vmem_bytes(rows, strip, cols, k, n, itemsize) -> int:
    """What the largest kernel's blocks, scratch and temporaries take, by
    shapes, for a layer's products both ways (K to N and N to K)."""
    kc, nc = _block(k, cols), _block(n, cols)
    gmm = max(2 * (rows * whole + whole * block + rows * block) * itemsize
              + strip * block * 4
              for whole, block in ((k, nc), (n, kc)))
    # The weight gradient handed a float32 block: the block coming in and
    # going out, two buffers each, the scratch and a product.
    tgmm = 2 * rows * (kc + nc) * itemsize + 6 * kc * nc * 4
    return max(gmm, tgmm)


def _plan(*, rows, groups, k, n, itemsize, interpret, manual_axes,
          vmem_headroom) -> GroupedPlan:
    """Kernels or ``lax.ragged_dot`` — the one place that chooses, a pure
    function of what the op observes at trace time.

    The kernels take widths in whole 128-lane tiles and rows in whole
    tiles of ``_ROWS``, or of ``_STRIP`` where only that cuts them evenly
    (two-byte operands: a float32 tile of as many rows
    holds twice the bytes), the contraction whole in VMEM; they ask a
    scoped-VMEM budget above Mosaic's default, which ``vmem_headroom``
    says the device backs, and leave what would not fit it.  Interpreted
    Pallas under ``shard_map``'s manual axes takes ``ragged_dot``
    (:func:`_pallas.xla_form`)."""
    ragged = GroupedPlan("ragged_dot", 0, 0, 0, 0, _RAGGED_LANES)
    tile = _STRIP if rows % _ROWS else _ROWS
    if (k % 128 or n % 128 or rows % tile or groups < 1 or itemsize != 2
            or not vmem_headroom
            or _pallas.xla_form(interpret, manual_axes)):
        return ragged
    if (_vmem_bytes(tile, _STRIP, _MOST_COLS, k, n, itemsize)
            > _VMEM_MB * 2 ** 20 * 3 // 4):
        return ragged
    return GroupedPlan("kernels", tile, _STRIP, _MOST_COLS, _VMEM_MB, 128)


def grouped_plan(rows_like, groups: int, n: int, *,
                 interpret: bool) -> GroupedPlan:
    """:func:`_plan` for sorted rows that are, or are shaped like,
    ``rows_like`` (M, K) against ``groups`` matrices (K, n): what the
    expert layer, ``chip_smoke.py`` and the tests ask."""
    vma = (rows_like.vma if isinstance(rows_like, jax.ShapeDtypeStruct)
           else jax.typeof(rows_like).vma)
    return _plan(rows=rows_like.shape[0], groups=groups,
                 k=rows_like.shape[1], n=n,
                 itemsize=jnp.dtype(rows_like.dtype).itemsize,
                 interpret=interpret, manual_axes=bool(vma),
                 vmem_headroom=_pallas.vmem_headroom_ok())


# ------------------------------------------------------------- the visits


def _visits(group_sizes, rows: int, tile: int):
    """``(tiles, groups, offsets)``: the (row tile, group) pairs the
    kernels walk, in row order, ``rows // tile + G`` of them, and the
    groups' first rows (G + 1,).

    A pair starts where a group or a tile starts, so every pair whose rows
    meet is listed, every group is (an empty one with a tile it has no
    row in: its weight gradient is written all the same), and a pair
    listed twice — a group that starts where a tile does — stands twice
    in a row, where the kernels pass over the second.  Tiles and groups
    both never fall along the list."""
    G = group_sizes.shape[0]
    n_tiles = rows // tile
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # A group's start sorts before the tile's that starts on the same row.
    keys = jnp.concatenate([offsets[:-1] * 2,
                            jnp.arange(n_tiles, dtype=jnp.int32) * tile * 2
                            + 1])
    by_row = jnp.argsort(keys, stable=True).astype(jnp.int32)
    first_row = keys[by_row] // 2
    holding = jnp.searchsorted(ends, first_row, side="right").astype(
        jnp.int32)
    groups = jnp.minimum(jnp.where(by_row < G, by_row, holding), G - 1)
    tiles = jnp.minimum(first_row // tile, n_tiles - 1)
    return tiles, groups, offsets


def _visit(tiles_ref, groups_ref, offsets_ref, v, tile: int):
    """Visit ``v``: its group, whether the visit before was the same
    tile's and whether it was this very pair, and the rows ``[lo, hi)``
    of the group inside the tile, counted from the tile's first."""
    t, g = tiles_ref[v], groups_ref[v]
    before = jnp.maximum(v - 1, 0)
    same_tile = (v > 0) & (tiles_ref[before] == t)
    again = same_tile & (groups_ref[before] == g)
    lo = jnp.maximum(offsets_ref[g] - t * tile, 0)
    hi = jnp.minimum(offsets_ref[g + 1] - t * tile, tile)
    return g, same_tile, again, lo, hi


# ------------------------------------------------------------ the kernels


def _gmm_kernel(tiles_ref, groups_ref, offsets_ref, x_ref, w_ref, o_ref, *,
                tile: int, strip: int, transposed: bool):
    """One visit of ``rows_g @ w_g`` (``w_g.T`` if ``transposed``): the
    strips of the tile that the group has rows in, the other groups' rows
    of a strip left as they are."""
    _, same_tile, again, lo, hi = _visit(tiles_ref, groups_ref, offsets_ref,
                                         pl.program_id(1), tile)

    @pl.when(jnp.logical_not(same_tile))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    contract = (((1,), (1 if transposed else 0,)), ((), ()))
    for first in range(0, tile, strip):
        rows = slice(first, first + strip)

        # One store serves whole and cut strips alike: a branch for the
        # whole ones, without the select, was no faster on the chip
        # (PERF.md section 6, PR 35).
        @pl.when(jnp.logical_not(again) & (lo < first + strip)
                 & (hi > first))
        def _(rows=rows, first=first):
            row = first + lax.broadcasted_iota(jnp.int32, (strip, 1), 0)
            o_ref[rows, :] = jnp.where(
                (row >= lo) & (row < hi),
                lax.dot_general(x_ref[rows, :], w_ref[...], contract,
                                preferred_element_type=_F32),
                o_ref[rows, :].astype(_F32)).astype(o_ref.dtype)


def _gate_parts(gate):
    """A float32 gate as three bfloat16 values that sum to it exactly (its
    24 bits, eight a part), each kept in float32: a part times a bfloat16
    row is then exact in a float32 product."""
    parts = []
    for _ in range(3):
        part = gate.astype(jnp.bfloat16).astype(_F32)
        parts.append(part)
        gate = gate - part
    return parts


def _tgmm_kernel(tiles_ref, groups_ref, offsets_ref, *refs, tile: int,
                 landing: str, handed: bool):
    """One visit of ``left_g.T @ dy_g``: the tile's share of the group's
    block, summed in float32 over the group's visits — onto the block the
    call was ``handed``, or onto zeros.

    The left operand is the tile's rows of ``x`` or, ``landing``, the
    SELECTION of the rows' tokens: the groups are tiles of as many tokens
    as the block has rows, ``S[r, c] = 1`` where row ``r`` belongs to the
    tile's token ``c``, so ``S.T @ dy`` lands every row on its token —
    ``"gated"``: times its float32 gate, as three exact bfloat16
    products."""
    left_ref, (dy_ref, *block, o_ref, acc_ref) = refs[0], refs[-3 - handed:]
    v = pl.program_id(2)
    g, _, again, lo, hi = _visit(tiles_ref, groups_ref, offsets_ref, v, tile)
    last = pl.num_programs(2) - 1

    @pl.when((v == 0) | (groups_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = block[0][...] if handed else jnp.zeros_like(acc_ref)

    def add(dy, selection=None):
        """``x.T @ dy``, or ``selection @ dy``, onto the scratch."""
        acc_ref[...] += lax.dot_general(
            left_ref[...] if selection is None else selection, dy,
            (((0 if selection is None else 1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    if landing:
        # A row matches no token of another tile, and one that landed
        # nowhere (its token past the last) none at all: nothing to mask.
        @pl.when(jnp.logical_not(again) & (hi > lo))
        def _():
            tokens = acc_ref.shape[0]
            match = (left_ref[...] - g * tokens
                     == lax.broadcasted_iota(jnp.int32, (tokens, tile), 0))
            for part in (_gate_parts(refs[1][...]) if landing == "gated"
                         else [1.0]):
                add(dy_ref[...],
                    jnp.where(match, part, 0.0).astype(dy_ref.dtype))
    else:
        whole = (lo == 0) & (hi == tile)

        @pl.when(jnp.logical_not(again) & whole)
        def _():
            add(dy_ref[...])

        @pl.when(jnp.logical_not(again | whole) & (hi > lo))
        def _():
            row = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            add(jnp.where((row >= lo) & (row < hi), dy_ref[...].astype(_F32),
                          0.0).astype(dy_ref.dtype))

    @pl.when((v == last) | (groups_ref[jnp.minimum(v + 1, last)] != g))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("transposed", "plan", "interpret"))
def _gmm(x, w, group_sizes, *, transposed: bool, plan, interpret):
    """``x_g @ w_g`` (M, N) for ``w`` (G, K, N) or, ``transposed``,
    ``x_g @ w_g.T`` for ``w`` (G, N, K)."""
    M, K = x.shape
    N = w.shape[1] if transposed else w.shape[2]
    cols = _block(N, plan.cols)
    tiles, groups, offsets = _visits(group_sizes, M, plan.rows)
    if transposed:
        w_spec = pl.BlockSpec((None, cols, K),
                              lambda j, v, t, g, o: (g[v], j, 0))
    else:
        w_spec = pl.BlockSpec((None, K, cols),
                              lambda j, v, t, g, o: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=plan.rows, strip=plan.strip,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // cols, tiles.shape[0]),
            in_specs=[
                pl.BlockSpec((plan.rows, K),
                             lambda j, v, t, g, o: (t[v], 0)),
                w_spec],
            out_specs=pl.BlockSpec((plan.rows, cols),
                                   lambda j, v, t, g, o: (t[v], j))),
        out_shape=_pallas.struct((M, N), x.dtype, x, w),
        interpret=interpret, name="moe_gmm_nt" if transposed else "moe_gmm",
        **_pallas.compiler_params(interpret, ("parallel", "arbitrary"),
                                  plan.vmem_mb),
    )(tiles, groups, offsets, x, w)


def _tgmm_call(left, left_specs, dy, group_sizes, block, *, k: int,
               k_cols: int, n_cols: int, tile: int, landing: str, plan,
               interpret):
    """The walk of (row tile, group) visits that ``_tgmm`` and ``landed_rows``
    share: ``left`` (arrays and their block specs) against ``dy`` (M, N),
    one (k, N) block a group, float32 and summed onto ``block`` in place
    where one is handed, in ``dy``'s dtype onto zeros otherwise."""
    M, N = dy.shape
    G = group_sizes.shape[0]
    tiles, group_of, offsets = _visits(group_sizes, M, tile)
    out_spec = pl.BlockSpec((None, k_cols, n_cols),
                            lambda i, j, v, t, g, o: (g[v], i, j))
    handed = block is not None
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile=tile, landing=landing,
                          handed=handed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(k // k_cols, N // n_cols, tiles.shape[0]),
            in_specs=[
                *left_specs,
                pl.BlockSpec((tile, n_cols),
                             lambda i, j, v, t, g, o: (t[v], j)),
                *[out_spec] * handed],
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((k_cols, n_cols), _F32)]),
        out_shape=_pallas.struct((G, k, N), _F32 if handed else dy.dtype,
                                 *left, dy, *[block] * handed),
        # The handed block is the output: the three prefetched operands,
        # the left ones and ``dy`` stand before it.
        input_output_aliases={4 + len(left): 0} if handed else {},
        interpret=interpret, name="moe_land" if landing else "moe_tgmm",
        **_pallas.compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), plan.vmem_mb),
    )(tiles, group_of, offsets, *left, dy, *[block] * handed)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("plan", "interpret"))
def _tgmm(x, dy, group_sizes, block=None, *, plan, interpret):
    """``x_g.T @ dy_g`` for every group: (G, K, N) — added to the float32
    ``block`` (G, K, N) where one is handed, in place."""
    K = x.shape[1]
    k_cols, n_cols = _block(K, plan.cols), _block(dy.shape[1], plan.cols)
    return _tgmm_call(
        [x], [pl.BlockSpec((plan.rows, k_cols),
                           lambda i, j, v, t, g, o: (t[v], i))],
        dy, group_sizes, block, k=K, k_cols=k_cols, n_cols=n_cols,
        tile=plan.rows, landing="", plan=plan, interpret=interpret)


# Tokens a tile of the accumulator that a window's rows land on, which is
# also the rows a tile of the landing's walk: a row tile meets every token
# tile it has a row of at a whole tile's cost, three times over under a
# gate, so both are small.  On a v5e 128 beat 256 by 2-4% over a window's
# two landings at the four cells' shapes, 17% at the narrowest (PERF.md
# section 6, PR 57).
LANDING_TOKENS = 128
_LANDING_MOST_COLS = 4096


@functools.partial(jax.jit, inline=True,
                   static_argnames=("plan", "interpret"))
def landed_rows(block, dy, token, gate=None, *, plan, interpret=False):
    """``block`` (n, N) float32 with every row of ``dy`` (M, N) added to
    the row of its ``token`` (M,) — times its float32 ``gate`` (M,) where
    one is given, the product in float32 —, in place and with no scatter.
    ``dy`` is sorted by token; a row whose token is ``n`` or more lands
    nowhere.

    The same walk as the weight gradient's under the kernels' ``plan``,
    with the groups tiles of ``LANDING_TOKENS`` tokens (``n`` a multiple),
    the block seen as (n / tokens, tokens, N) and the left operand the
    selection the kernel forms from the token ids (:func:`_tgmm_kernel`)."""
    n, N = block.shape
    tokens = LANDING_TOKENS
    tile = min(tokens, plan.rows)
    token = token.astype(jnp.int32)
    ends = jnp.searchsorted(token, jnp.arange(1, n // tokens + 1) * tokens)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    left = [token[None]] + ([] if gate is None else [gate.astype(_F32)[None]])
    return _tgmm_call(
        left, [pl.BlockSpec((1, tile), lambda i, j, v, t, g, o: (0, t[v]))
               ] * len(left),
        dy, sizes, block.reshape(n // tokens, tokens, N), k=tokens,
        k_cols=tokens, n_cols=_block(N, _LANDING_MOST_COLS), tile=tile,
        landing="rows" if gate is None else "gated", plan=plan,
        interpret=interpret).reshape(n, N)


# ---------------------------------------------------------------- the op


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused(x, w, group_sizes, plan, interpret):
    return _gmm(x, w, group_sizes, transposed=False, plan=plan,
                interpret=interpret)


def _fused_fwd_rule(x, w, group_sizes, plan, interpret):
    return _fused(x, w, group_sizes, plan, interpret), (x, w, group_sizes)


def _fused_bwd_rule(plan, interpret, res, dy):
    x, w, group_sizes = res
    return (*grouped_gradients(x, w, dy, group_sizes, plan,
                               interpret=interpret), None)


_fused.defvjp(_fused_fwd_rule, _fused_bwd_rule)


def grouped_matmul(x, w, group_sizes, plan: GroupedPlan, *,
                   interpret: bool = False):
    """``x[rows of group g] @ w[g]`` for every group ``g``: ``x`` (M, K)
    sorted by group, ``w`` (G, K, N), ``group_sizes`` (G,) integers whose
    sum is at most M; rows past it come out as zeros.  Differentiable in
    ``x`` and ``w``.  ``plan`` (:func:`grouped_plan`; the same for K to N
    and N to K, so one serves a layer's products) says whether the
    kernels or ``lax.ragged_dot`` run; ``interpret=True`` runs the kernels
    off-TPU (tests)."""
    if plan.form != "kernels":
        return lax.ragged_dot(x, w, group_sizes)
    return _fused(x, w.astype(x.dtype), group_sizes.astype(jnp.int32), plan,
                  interpret)


def grouped_gradients(x, w, dy, group_sizes, plan: GroupedPlan, *,
                      interpret: bool = False, block=None):
    """The two transposes of :func:`grouped_matmul` under the kernels'
    ``plan``, called directly: ``(dy_g @ w_g.T, x_g.T @ dy_g)`` for the
    cotangent ``dy`` (M, N) of ``x_g @ w_g``.  With a float32 ``block``
    (G, K, N) the weight gradient is added to it, in place and in float32,
    by the kernel that forms it; with none it is what the op's own
    backward rule gives, in the operands' dtype."""
    w, dy = w.astype(x.dtype), dy.astype(x.dtype)
    group_sizes = group_sizes.astype(jnp.int32)
    return (_gmm(dy, w, group_sizes, transposed=True, plan=plan,
                 interpret=interpret),
            _tgmm(x, dy, group_sizes, block, plan=plan, interpret=interpret))
