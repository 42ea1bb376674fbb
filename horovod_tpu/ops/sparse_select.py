"""Learned sparse attention's selection: an indexer scores every causal key
of a query, the ``topk`` best are the only keys its attention heads read,
and a KL term trains the indexer towards the heads' own probabilities
(DeepSeek Sparse Attention, DeepSeek-V3.2-Exp report, 2025).

With ``qI`` (B, T, H_I, D_I), ``kI`` (B, T, D_I) — one key head — and
``w`` (B, T, H_I) the indexer's projections of a token::

    I[t, s] = (H_I · D_I)^-1/2 · Σ_j w[t, j] · relu(qI[t, j] · kI[s])   s <= t
    S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s]
              (a tie goes to the lower index, as ``lax.top_k`` breaks it)
    L_I     = mean_t KL(p[t, ·] ‖ softmax_{s ∈ S_t} I[t, ·]),
              p[t, s] = (1 / H) Σ_h P[t, h, s]  for s ∈ S_t

with ``P`` the selected attention's probabilities, detached.

Three steps, none of which ever holds a (T, T) float32 array of all heads:

* :func:`index_select` — the scores a band of query rows at a time
  (:func:`index_scores`, a Pallas kernel: the H_I products of a tile are
  summed in VMEM and one float32 tile leaves it), the exact top ``topk`` of
  each row (the k-th value and the last tie taken, by bisection — no sort,
  no approximation), and the selection as the kernels read it: an **int8
  (B, T, T) map**, 1 where ``s ∈ S_t`` (``I > τ``, or ``I == τ`` up to the
  last tie: no scatter).  The top-k is :func:`index_threshold`, ONE
  Pallas kernel over the strips of every band: a strip of a band's scores
  comes into VMEM once, both bisections run on it there — the one on the
  key index only where ties at the k-th outnumber their room — and the
  strip's rows of the map leave once, with the log-sum-exp of the chosen
  scores (``_threshold_plan`` picks the strip from shapes and the device's
  VMEM).  Where no strip fits, :func:`select_rows` — the same algorithm as
  XLA loops over tiles of ``tile`` rows, and the tests' oracle — and a pad
  and a concatenate make the map.  It is **kept for the backward pass**
  (T² bytes a layer: 256 MiB at T 16,384), because making it again costs
  the scores and the top-k a second time.  Nothing here is differentiated.
* the selected attention itself is ``flash_attention(..., select=map)``.
* :func:`index_kl` — ``L_I`` and, in the same pass, its gradient on the
  indexer's three projections (:func:`_kl_kernel`): a tile's ``p`` is
  summed over the heads in VMEM (recomputed: the forward's softmax is
  online, so the heads' sum cannot leave it), the tile's scores are made
  again — each head's product ONCE, its sign kept in VMEM for the
  gradient — and ``g = softmax_S(I) - p`` goes straight into ``dqI``,
  ``dkI`` and ``dw``: ``H + 3 H_I`` products a tile, ``w`` and ``dw`` as
  row operations once a query block (``dw_h`` is the row product of
  ``qI_h`` with ``dqI_h`` before ``w_h`` scales it: no division by ``w``).
  ``L_I`` moves nothing else: ``q``, ``k`` and the statistics arrive
  detached.

``sparse_attention_reference`` is the same mathematics in plain
``jax.numpy`` with dense (T, T) arrays, for the models' ``attn="full"``
path and the tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

# Tiles of the two kernels (queries x keys).  The KL kernel holds a tile's
# query rows of every attention head, the gradient of every indexer head
# and the heads' (tile, tile) masks beside its float32 score tiles: at the
# first tiling below and Keye's widths the compiler counts 58 MB of
# scoped VMEM, which v4 and later back; ``_kl_plan`` takes the first tiling
# that fits the device's budget by ``_kl_vmem_bytes`` (alone on a v5e, ms a
# layer at T 16,384: 512 x 512 18.1, 256 x 512 18.6, 512 x 1024 21.9;
# PERF.md section 6, PR 41).
_BLOCK = 512
_KL_VMEM_MB = 96
_KL_TILINGS = ((512, 512), (256, 512), (256, 256), (128, 256), (128, 128))
_KL_LIVE_TILES = 8
_MOSAIC_DEFAULT_VMEM_MB = 16


def _block(T: int, cap: int = _BLOCK) -> int:
    """The largest divisor of ``T`` up to ``cap`` that Mosaic can tile (a
    multiple of 128, or ``T`` itself when one block covers it)."""
    if T <= cap:
        return T
    for b in range(cap, 127, -128):
        if T % b == 0:
            return b
    raise ValueError(f"the indexer's kernels need a sequence length with a "
                     f"divisor that is a multiple of 128 (up to {cap}); "
                     f"got T={T}")


# ----------------------------------------------------------- the scores


def _scores_kernel(qi_ref, ki_ref, w_ref, out_ref, *, heads, scale, row0,
                   block_q, block_k):
    """One (block_q, block_k) tile of ``I``: the heads' products summed
    here, -inf above the diagonal.  Grid (B, rows / block_q, keys /
    block_k); ``row0``: the band's first query row."""
    i, j = pl.program_id(1), pl.program_id(2)
    first = row0 + i * block_q
    live = j * block_k <= first + block_q - 1

    @pl.when(live)
    def _tile():
        ki, w = ki_ref[0], w_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            s = lax.dot_general(qi_ref[0, h], ki, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc += w[:, h:h + 1] * jnp.maximum(s, 0.0)
        rows = first + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        cols = j * block_k + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        out_ref[0] = jnp.where(cols <= rows, acc * scale, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _future():
        out_ref[0] = jnp.full((block_q, block_k), -jnp.inf, jnp.float32)


def index_scores(qi, ki, w, *, row0: int = 0, rows: Optional[int] = None,
                 interpret: bool = False):
    """``I`` for the query rows ``row0 .. row0 + rows - 1`` against the
    keys ``0 .. row0 + rows - 1`` (no later key is causal for them):
    (B, rows, row0 + rows) float32, -inf where ``s > t``.  ``qi``
    (B, H_I, T, D_I) — heads before rows, as the kernel reads them —,
    ``ki`` (B, T, D_I), ``w`` (B, T, H_I) float32."""
    B, HI, T, DI = qi.shape
    rows = T - row0 if rows is None else rows
    width = row0 + rows
    bq, bk = _block(rows), _block(width)
    if row0 % bq:
        raise ValueError(f"a band starts on a tile: row0={row0}, tile {bq}")
    r0 = row0 // bq
    with jax.named_scope("scores"):
        return pl.pallas_call(
            functools.partial(_scores_kernel, heads=HI,
                              scale=1.0 / math.sqrt(HI * DI), row0=row0,
                              block_q=bq, block_k=bk),
            grid=(B, rows // bq, width // bk),
            in_specs=[
                pl.BlockSpec((1, HI, bq, DI), lambda b, i, j: (b, 0, i + r0, 0)),
                pl.BlockSpec((1, bk, DI), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, bq, HI), lambda b, i, j: (b, i + r0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j)),
            out_shape=_pallas.struct((B, rows, width), jnp.float32, qi, ki, w),
            interpret=interpret,
            name="index_scores",
            **_pallas.compiler_params(
                interpret, ("parallel", "parallel", "parallel")),
        )(qi, ki, w)


# -------------------------------------------------------- the selection


def select_rows(scores, topk: int):
    """The selection of some query rows from their scores (..., rows, W),
    -inf where a key is not causal: ``(chosen, lse)`` with ``chosen`` bool,
    True on the ``min(topk, causal keys)`` largest of a row — exactly
    ``lax.top_k``'s set, a tie to the lower index — and ``lse`` the
    log-sum-exp of the chosen scores.

    Exact, and no sort: the k-th largest score of a row is found by
    bisection on the scores' bit patterns (32 counts of ``score >= v``),
    and the ties at it that still have room by bisection on the key index
    (``log2 W`` counts).  On a v5e a tile of 512 rows of 16,384 takes
    0.5 ms for the first where ``lax.top_k`` takes 5.0 (PERF.md section 6,
    PR 36)."""
    W = scores.shape[-1]
    k = min(topk, W)
    with jax.named_scope("topk"):
        # Bit patterns that order as the floats do (-0 under +0, as XLA's
        # sort and top-k have them).
        bits = lax.bitcast_convert_type(scores, jnp.uint32)
        key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

        def count(mask):
            return mask.sum(axis=-1, dtype=jnp.int32)[..., None]

        def value_bit(i, v):
            higher = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            return jnp.where(count(key >= higher) >= k, higher, v)

        # The largest v that k keys reach: the k-th largest key.  (The
        # loops start from zeros made of their data, so that under
        # shard_map they vary as it does.)
        kth = lax.fori_loop(0, 32, value_bit, key[..., :1] & jnp.uint32(0))
        above, tied = key > kth, key == kth
        room = k - count(above)
        cols = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
        index_bits = max(1, (W - 1).bit_length())

        def index_bit(i, c):
            later = c | (jnp.int32(1) << (index_bits - 1 - i))
            return jnp.where(count(tied & (cols < later)) < room, later, c)

        # The largest c with fewer than ``room`` ties before it: where the
        # last tie that is taken lies.
        last_tie = lax.fori_loop(0, index_bits, index_bit, room * 0)
    with jax.named_scope("select"):
        chosen = (above | (tied & (cols <= last_tie))) & (scores > -jnp.inf)
        lse = jax.scipy.special.logsumexp(
            jnp.where(chosen, scores, -jnp.inf), axis=-1)
    return chosen, lse


def _bands(T: int, tile: int, topk: int) -> int:
    """How many bands of query rows the scores are made in: a band's rows
    are scored against the keys up to its last row only, so four bands do
    10/16 of the square's work and two 3/4.  A band is whole tiles."""
    for n in (4, 2):
        if T % (n * tile) == 0 and T // n >= max(topk, tile):
            return n
    return 1


# The selection's kernel.  A grid step owns a strip of ``block_rows`` whole
# rows of one band's scores and bisects them ``_THRESHOLD_GROUP`` rows at a
# time, a lane tile of columns a step of its loops (``_THRESHOLD_UNROLL`` of
# them unrolled): a strip of EVERY band and the strip's rows of the map are
# in VMEM twice (Mosaic's pipeline), a group's keys once.
# ``_threshold_plan`` takes the first of ``_THRESHOLD_ROWS`` that fits the
# device's budget by ``_threshold_vmem_bytes``.  (Alone on a v5e, ms a layer
# at T 16,384 where ``select_rows`` takes 14.7: groups of 64 rows 4.7, of
# 32 5.5, of 128 4.7, of 256 7.2; 32 lane tiles a loop step 4.7, 16 4.8,
# and at groups of 32 rows 32, 16, 4 tiles 5.7, 6.0, 7.7; the same at
# strips of 256, 128 and 64 rows: PERF.md section 6, PR 47.)
_THRESHOLD_ROWS = (256, 128, 64)
_THRESHOLD_GROUP = 64
_THRESHOLD_UNROLL = 32
_THRESHOLD_VMEM_MB = 64
_LANES = 128
_INT_MIN = -2 ** 31
# -inf's bit pattern in the keys' order: what lies above it is a score.
_NO_SCORE = _INT_MIN + 0x7FFFFF


def _threshold_strip(s_ref, map_ref, lse_ref, tie_ref, key_scr, *, k):
    """A strip's rows of the selection: its scores ``s_ref`` (1,
    block_rows, width) are in VMEM once and its rows of the map ``map_ref``
    (1, block_rows, T) leave once.  A group of rows at a time:

    * the keys: the scores' bit patterns as int32 that order as the floats
      do, ``bits ^ (bits >> 31 & 0x7fffffff)`` — ``select_rows``' unsigned
      keys with the top bit flipped, so that every compare is a signed
      one — into ``key_scr`` (T / 128, group, 128), and the row's maximum;
    * the k-th largest key by 32 counts of ``key >= v``, a count summed
      lane-wise over the columns and across the lanes once;
    * the last tie taken by ``log2 width`` counts of ``tied & col < c``,
      ONLY where some row of the group has more keys at its k-th than room
      for them (a row whose k-th is not a score takes every score);
    * ``chosen`` as int8 and the log-sum-exp of the chosen scores in one
      pass over scores and keys; zeros past ``width``.

    ``tie_ref``: whether a group of the strip ran the tie bisection."""
    block_rows, width = s_ref.shape[1:]
    T = map_ref.shape[2]
    group, tiles = key_scr.shape[1], width // _LANES
    index_bits = max(1, (width - 1).bit_length())
    unroll = max(u for u in range(1, _THRESHOLD_UNROLL + 1) if tiles % u == 0)
    i32 = jnp.int32
    lane = lax.broadcasted_iota(i32, (group, _LANES), 1)

    # The steps of the three sweeps are unrolled ``unroll`` times a trace,
    # and written with ``lax`` calls: every ``jax.numpy`` operator on a
    # tracer is a jitted function of its own, whose trace jax reports (the
    # span ring would hold 5,600 ``jax/trace`` spans a trace of this
    # kernel, of the 16,384 it keeps).
    def sweep(step, carry):
        """``step(lane tile's index, carry)`` over the group's columns,
        ``unroll`` tiles a loop step (Mosaic unrolls a loop whole or not at
        all)."""
        def steps(c, carry):
            for u in range(unroll):
                carry = step(lax.add(lax.mul(c, unroll), u), carry)
            return carry
        return lax.fori_loop(0, tiles // unroll, steps, carry)

    def lanes(x):
        return jnp.broadcast_to(x, (group, _LANES))

    def count(hit):
        """Rows' counts (group, 1) of ``hit(keys, lane tile's index)``."""
        return sweep(
            lambda c, n: lax.add(
                n, lax.convert_element_type(hit(key_scr[c], c), i32)),
            jnp.zeros((group, _LANES), i32)).sum(axis=1, keepdims=True)

    def columns(c):
        return pl.ds(pl.multiple_of(lax.mul(c, _LANES), _LANES), _LANES)

    def column_index(c):
        return lax.add(lane, lax.broadcast(lax.mul(c, _LANES), lane.shape))

    def rows_of(g):
        rows = pl.ds(pl.multiple_of(g * group, group), group)

        def form(c, top):
            s = s_ref[0, rows, columns(c)]
            bits = lax.bitcast_convert_type(s, i32)
            key_scr[c] = lax.bitwise_xor(bits, lax.bitwise_and(
                lax.shift_right_arithmetic(bits, i32(31)), i32(0x7FFFFFFF)))
            return lax.max(top, s)

        top = sweep(form, jnp.full((group, _LANES), -jnp.inf, jnp.float32)
                    ).max(axis=1, keepdims=True)

        def value_bit(i, carry):
            # ``v``: the bits of select_rows' unsigned key found so far;
            # ``reach``: how many keys reach it.
            v, reach = carry
            higher = v | (i32(1) << (31 - i))
            least = lanes(higher ^ i32(_INT_MIN))
            n = count(lambda key, _: lax.ge(key, least))
            return (jnp.where(n >= k, higher, v), jnp.where(n >= k, n, reach))

        v, reach = lax.fori_loop(
            0, 32, value_bit,
            (jnp.zeros((group, 1), i32), jnp.full((group, 1), width, i32)))
        kth = lanes(v ^ i32(_INT_MIN))
        room = k - count(lambda key, _: lax.gt(key, kth))
        tied = reach - (k - room)
        crowded = jnp.max(
            ((kth[:, :1] > _NO_SCORE) & (tied > room)).astype(i32))

        def index_bit(i, c):
            later = c | (i32(1) << (index_bits - 1 - i))
            before = lanes(later)
            n = count(lambda key, tile: lax.bitwise_and(
                lax.eq(key, kth), lax.lt(column_index(tile), before)))
            return jnp.where(n < room, later, c)

        # The largest c with fewer than ``room`` ties before it; every tie
        # is taken where none is short of room.
        last_tie = lanes(lax.cond(
            crowded > 0,
            lambda: lax.fori_loop(0, index_bits, index_bit,
                                  jnp.zeros((group, 1), i32)),
            lambda: jnp.full((group, 1), width, i32)))
        shift = lanes(jnp.where(top > -jnp.inf, top, 0.0))
        nothing = jnp.zeros((group, _LANES), jnp.float32)

        def emit(c, total):
            s, key = s_ref[0, rows, columns(c)], key_scr[c]
            tie = lax.bitwise_and(lax.eq(key, kth),
                                  lax.le(column_index(c), last_tie))
            chosen = lax.bitwise_and(lax.bitwise_or(lax.gt(key, kth), tie),
                                     lax.gt(s, -jnp.inf))
            map_ref[0, rows, columns(c)] = lax.convert_element_type(
                lax.convert_element_type(chosen, i32), jnp.int8)
            return lax.add(total, lax.select(
                chosen, lax.exp(lax.sub(s, shift)), nothing))

        total = sweep(emit, nothing)
        lse_ref[0, rows, :] = shift[:, :1] + jnp.log(
            total.sum(axis=1, keepdims=True))
        if T > width:
            map_ref[0, rows, width:] = jnp.zeros((group, T - width), jnp.int8)
        return crowded

    ran = lax.fori_loop(0, block_rows // group,
                        lambda g, ran: ran | rows_of(g), i32(0))
    tie_ref[...] = jnp.full(tie_ref.shape, ran, i32)


def _threshold_kernel(*refs, topk):
    """Grid (B, bands, rows / block_rows), a band's strips one after the
    other: the step's strip is the band's, ``_threshold_strip`` at that
    band's static width."""
    *s_refs, map_ref, lse_ref, tie_ref, key_scr = refs
    for b, s_ref in enumerate(s_refs):
        @pl.when(pl.program_id(1) == b)
        def _band(s_ref=s_ref):
            _threshold_strip(s_ref, map_ref, lse_ref, tie_ref, key_scr,
                             k=min(topk, s_ref.shape[2]))


def _threshold_vmem_bytes(block_rows, rows, bands):
    """What the selection's kernel holds in VMEM at a strip, from shapes: a
    strip of every band's scores (the bands' widths are ``rows`` to ``bands
    · rows`` = T), the strip's rows of the map, its log-sum-exps (a lane
    tile a row) and its flag twice (Mosaic's pipeline), a group's keys, and
    2 MB of the compiler's own."""
    T = bands * rows
    blocks = (block_rows * rows * (bands * (bands + 1) // 2) * 4
              + block_rows * T + block_rows * _LANES * 4 + 8 * _LANES * 4)
    return 2 * blocks + _THRESHOLD_GROUP * T * 4 + 2 * 2 ** 20


def _threshold_plan(rows, bands, tile, vmem_headroom):
    """``(block_rows, vmem_mb)`` of the selection's kernel for ``bands``
    bands of ``rows`` query rows, band ``b`` against ``(b + 1) · rows``
    keys: the first strip of ``_THRESHOLD_ROWS``, at most the caller's
    ``tile``, that divides a band and fits the budget —
    ``_THRESHOLD_VMEM_MB`` where the device backs it, Mosaic's default
    (``vmem_mb`` 0) where it does not.  ``block_rows`` 0: no strip does, or
    the widths are not whole lane tiles, and the selection keeps its XLA
    form (:func:`select_rows`)."""
    mb = _THRESHOLD_VMEM_MB if vmem_headroom else 0
    budget = (mb or _MOSAIC_DEFAULT_VMEM_MB) * 2 ** 20
    if rows % _LANES:
        return 0, mb
    for block_rows in _THRESHOLD_ROWS:
        if (block_rows <= tile and rows % block_rows == 0
                and _threshold_vmem_bytes(block_rows, rows, bands) <= budget):
            return block_rows, mb
    return 0, mb


@functools.partial(jax.jit, inline=True, static_argnames=(
    "topk", "block_rows", "vmem_mb", "interpret"))
def index_threshold(*bands, topk: int, block_rows: int, vmem_mb: int = 0,
                    interpret: bool = False):
    """The selection from the bands' scores — band ``b`` (B, rows, (b + 1)
    · rows), -inf where a key is not causal, the last band's width T —:
    ``(select, lse, ties)`` with ``select`` the int8 (B, T, T) map and
    ``lse`` (B, T) :func:`select_rows`' set and log-sum-exp, and ``ties``
    (B, T / block_rows) 1 where a strip's tie bisection ran.  ONE kernel
    (:func:`_threshold_kernel`) over every band's strips: a strip's scores
    are read once and its rows of the map written once, zeros past the
    band's width included — no pad, no concatenate, no second buffer.  (A
    band's block index holds still while the other bands' strips run, so
    nothing is fetched twice.)  The body is traced once a shape, not once
    a layer."""
    B, rows, _ = bands[0].shape
    if rows % block_rows or block_rows % _THRESHOLD_GROUP or rows % _LANES:
        raise ValueError(
            f"index_threshold: bands of {rows} rows in strips of {block_rows} "
            f"(whole strips of whole groups of {_THRESHOLD_GROUP} rows, and "
            f"widths of whole lane tiles)")
    n, strips = len(bands), rows // block_rows
    T = n * rows

    def strip_of(b):
        # The band's own strip while it runs; its first before, its last
        # after: an index that holds still is not fetched again.
        return lambda bt, band, i: (
            bt, jnp.clip(i + (band - b) * strips, 0, strips - 1), 0)

    def row_block(bt, band, i):
        return bt, band * strips + i, 0

    with jax.named_scope("topk"):
        select, lse, ties = pl.pallas_call(
            functools.partial(_threshold_kernel, topk=topk),
            grid=(B, n, strips),
            in_specs=[pl.BlockSpec((1, block_rows, (b + 1) * rows),
                                   strip_of(b)) for b in range(n)],
            out_specs=[
                pl.BlockSpec((1, block_rows, T), row_block),
                pl.BlockSpec((1, block_rows, 1), row_block),
                pl.BlockSpec((1, 1, 8, _LANES),
                             lambda bt, band, i: (*row_block(bt, band, i), 0)),
            ],
            out_shape=[
                _pallas.struct((B, T, T), jnp.int8, *bands),
                _pallas.struct((B, T, 1), jnp.float32, *bands),
                _pallas.struct((B, n * strips, 8, _LANES), jnp.int32, *bands),
            ],
            scratch_shapes=[pltpu.VMEM(
                (T // _LANES, _THRESHOLD_GROUP, _LANES), jnp.int32)],
            interpret=interpret,
            name="index_threshold",
            **_pallas.compiler_params(
                interpret, ("parallel", "arbitrary", "arbitrary"), vmem_mb),
        )(*bands)
    return select, lse[..., 0], ties[..., 0, 0]


def _select_band_rows(band, topk: int, tile: int, T: int):
    """The XLA form of a band's selection: :func:`select_rows` on tiles of
    ``tile`` rows, ``(int8 (B, rows, T) rows of the map, lse (B, rows))``."""
    B, rows, width = band.shape
    tiles = band.reshape(B, rows // tile, tile, width).swapaxes(0, 1)
    chosen, lse = lax.map(lambda s: select_rows(s, topk), tiles)
    chosen = chosen.swapaxes(0, 1).reshape(B, rows, width)
    with jax.named_scope("select"):
        padded = jnp.pad(chosen.astype(jnp.int8),
                         [(0, 0), (0, 0), (0, T - width)])
    return padded, lse.swapaxes(0, 1).reshape(B, rows)


def select_bands(bands, topk: int, T: int, plan, *, tile: int = _BLOCK,
                 interpret: bool = False):
    """``(select, lse, tie_tiles)`` from the bands' scores, first band
    first, under ``plan``, a ``_threshold_plan``: :func:`index_threshold`
    where it names a strip, else :func:`select_rows` a band, padded and
    concatenated.  ``tie_tiles``: the share of strips whose tie bisection
    ran (every one in the XLA form, which runs it always).  ``bands`` may
    be a generator: the XLA form then makes a band's scores when it
    selects from them, as it always did."""
    block_rows, vmem_mb = plan
    if block_rows:
        select, lse, ties = index_threshold(
            *bands, topk=topk, block_rows=block_rows, vmem_mb=vmem_mb,
            interpret=interpret)
        return select, lse, ties.mean(dtype=jnp.float32)
    maps, lses = zip(*(_select_band_rows(band, topk, tile, T)
                       for band in bands))
    with jax.named_scope("select"):
        return (jnp.concatenate(maps, axis=1), jnp.concatenate(lses, axis=1),
                jnp.float32(1.0))


def index_select_counted(qi, ki, w, topk: int, *, tile: int = _BLOCK,
                         interpret: bool = False):
    """:func:`index_select`'s ``(select, lse)`` and ``tie_tiles``
    (:func:`select_bands`)."""
    qi, ki, w = (lax.stop_gradient(a) for a in (qi, ki, w))
    B, T, HI, DI = qi.shape
    tile = min(tile, T)
    if T % tile:
        raise ValueError(f"index_select: tile {tile} must divide T={T}")
    qi = qi.transpose(0, 2, 1, 3)
    w = w.astype(jnp.float32)
    bands = _bands(T, tile, topk)
    rows = T // bands
    plan = ((0, 0) if _pallas.xla_form(interpret, bool(jax.typeof(qi).vma))
            else _threshold_plan(rows, bands, tile,
                                 _pallas.vmem_headroom_ok()))
    scores = (index_scores(qi, ki, w, row0=b * rows, rows=rows,
                           interpret=interpret)             # (B, rows, width)
              for b in range(bands))
    return select_bands(scores, topk, T, plan, tile=tile,
                        interpret=interpret)


def index_select(qi, ki, w, topk: int, *, tile: int = _BLOCK,
                 interpret: bool = False):
    """``(select, lse)``: the int8 (B, T, T) map of ``S_t`` (1 where query
    ``t`` reads key ``s``) and the log-sum-exp (B, T) of each query's
    selected scores.  ``qi`` (B, T, H_I, D_I), ``ki`` (B, T, D_I), ``w``
    (B, T, H_I).  Scores and top-k run a band of query rows at a time, the
    top-k in strips of at most ``tile`` rows (``tile`` leaves ``S_t`` as it
    is): :func:`index_threshold` where ``_threshold_plan`` finds a strip,
    else :func:`select_rows`.  Nothing is differentiated."""
    return index_select_counted(qi, ki, w, topk, tile=tile,
                                interpret=interpret)[:2]


def selection_counters(select, block: int):
    """What a selection map says of itself: ``(selected_per_query,
    live_tiles)`` — the mean ``|S_t|``, and the share of the causal
    (block, block) tiles that hold a selected pair (what a table of live
    tiles could skip is the rest)."""
    B, T, _ = select.shape
    block = min(block, T)
    per_query = select.astype(jnp.int32).sum(dtype=jnp.int32) / (B * T)
    if T % block:
        return per_query, jnp.float32(1.0)
    n = T // block
    any_ = select.reshape(B, n, block, n, block).max(axis=(2, 4)) > 0
    return per_query, any_.sum() / (B * n * (n + 1) // 2)


# ----------------------------------------------------- the indexer's loss


def _kl_kernel(q_ref, k_ref, lse_ref, sel_ref, qi_ref, ki_ref, w_ref,
               lsei_ref, kl_ref, dqi_ref, dw_ref, dki_ref,
               kl_scr, dqi_scr, pos_scr, wqi_scr, *, heads, kv_heads,
               head_dim, index_heads, scale, index_scale, block_q, block_k):
    """One (block_q, block_k) tile of the KL pass.  Grid (B, T / block_q,
    T / block_k), the key tiles innermost: a query tile's ``KL`` rows and
    ``dqI`` form in scratch across them; a key tile's ``dkI`` leaves as one
    partial a query tile (summed outside).

    Each product ``s_h = qI_h kIᵀ`` is formed once: what the gradient needs
    of it is ``1[s_h > 0]``, kept in ``pos_scr`` as all-ones / zero words
    as wide as an operand, so that ``e_h = 1[s_h > 0] ⊙ g`` is one AND on
    ``g``'s bits.  ``w`` stays off the (block_q, block_k) elements::

        dqI'_h = e_h kI                      summed in ``dqi_scr``
        dkI    = Σ_h e_hᵀ (w_h ⊙ qI_h)       ``wqi_scr``, made once a query tile
        dqI_h  = w_h ⊙ dqI'_h                at the query tile's last step
        dw_h   = Σ_s g relu(s_h) = <qI_h, dqI'_h>   a row product, there too
    """
    i, j, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    D = head_dim
    dtype = ki_ref.dtype

    @pl.when(j == 0)
    def _init():
        kl_scr[...] = jnp.zeros_like(kl_scr)
        dqi_scr[...] = jnp.zeros_like(dqi_scr)
        w = w_ref[0]
        for h in range(index_heads):
            wqi_scr[h] = (w[:, h:h + 1]
                          * qi_ref[0, h].astype(jnp.float32)).astype(dtype)

    live = j * block_k <= (i + 1) * block_q - 1

    @pl.when(live)
    def _tile():
        ok = sel_ref[0].astype(jnp.int32) != 0
        # p: the heads' probabilities of this tile, averaged.
        k = k_ref[0]
        lse = lse_ref[0]
        p = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            g = h // (heads // kv_heads)
            s = lax.dot_general(
                q_ref[0, :, h * D:(h + 1) * D], k[:, g * D:(g + 1) * D],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p += jnp.exp(s - lse[:, h:h + 1])
        p = jnp.where(ok, p * (1.0 / heads), 0.0)
        # The indexer's own distribution over the selected keys.
        ki, w = ki_ref[0], w_ref[0]
        scores = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(index_heads):
            s = lax.dot_general(qi_ref[0, h], ki, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            pos_scr[h] = jnp.where(s > 0.0, -1, 0).astype(pos_scr.dtype)
            scores += w[:, h:h + 1] * jnp.maximum(s, 0.0)
        log_pi = scores * index_scale - lsei_ref[0]
        pi = jnp.where(ok, jnp.exp(log_pi), 0.0)
        kl_scr[...] += jnp.sum(
            jnp.where(p > 0.0,
                      p * (jnp.log(jnp.maximum(p, 1e-37)) - log_pi), 0.0),
            axis=1, keepdims=True)
        # d KL / d I = softmax_S(I) - p, straight into the projections.
        g = lax.bitcast_convert_type(
            ((pi - p) * index_scale).astype(dtype), pos_scr.dtype)
        dki = jnp.zeros((block_k, ki.shape[1]), jnp.float32)
        for h in range(index_heads):
            e = lax.bitcast_convert_type(g & pos_scr[h], dtype)
            dqi_scr[h] += lax.dot_general(
                e, ki, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dki += lax.dot_general(
                e, wqi_scr[h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dki_ref[0, 0] = dki

    @pl.when(jnp.logical_not(live))
    def _future():
        dki_ref[0, 0] = jnp.zeros(dki_ref.shape[2:], jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        kl_ref[0] = kl_scr[...]
        w = w_ref[0]
        for h in range(index_heads):
            d = dqi_scr[h]
            dw_ref[0, h] = jnp.sum(qi_ref[0, h].astype(jnp.float32) * d,
                                   axis=1, keepdims=True)
            dqi_ref[0, h] = w[:, h:h + 1] * d


def _kl_vmem_bytes(bq, bk, H, Hkv, D, HI, DI, itemsize):
    """What the KL kernel holds in VMEM at a tiling, from shapes: every
    operand and result block twice (Mosaic's pipeline), the scratch, the
    float32 (bq, bk) tiles live at once and 2 MB of the compiler's own.
    At Keye's widths the compiler counts (MB, bisection on the limit for
    the described v5e, PR 41) 12 / 13 / 23 / 28 / 58 from the smallest
    tiling to the largest where this says 12.5 / 14.0 / 25.1 / 30.1 / 56.5."""
    def block(rows, cols, size):
        # A last axis fills whole 128-lane tiles: (.., 1) weighs as (.., 128).
        return rows * -(-cols // 128) * 128 * size

    operands = (block(bq, H * D, itemsize) + block(bk, Hkv * D, itemsize)
                + block(bq, H, 4) + block(bq, bk, 1)
                + block(HI * bq, DI, itemsize) + block(bk, DI, itemsize)
                + block(bq, HI, 4) + block(bq, 1, 4))
    results = (block(bq, 1, 4) + block(HI * bq, DI, 4)
               + block(HI * bq, 1, 4) + block(bk, DI, 4))
    scratch = (block(bq, 1, 4) + block(HI * bq, DI, 4)
               + block(HI * bq, bk, itemsize) + block(HI * bq, DI, itemsize))
    return (2 * (operands + results) + scratch
            + _KL_LIVE_TILES * bq * bk * 4 + 2 * 2 ** 20)


def _kl_plan(T, H, Hkv, D, HI, DI, itemsize, vmem_headroom):
    """``(block_q, block_k, vmem_mb)`` of the KL pass: the first of
    ``_KL_TILINGS`` (cut to ``T``; the last if none) whose VMEM by shapes
    fits the budget — ``_KL_VMEM_MB`` where the device backs it, Mosaic's
    default (``vmem_mb`` 0) where it does not.  One algorithm at every
    tiling."""
    mb = _KL_VMEM_MB if vmem_headroom else 0
    budget = (mb or _MOSAIC_DEFAULT_VMEM_MB) * 2 ** 20
    for bq, bk in _KL_TILINGS:
        bq, bk = _block(T, bq), _block(T, bk)
        if _kl_vmem_bytes(bq, bk, H, Hkv, D, HI, DI, itemsize) <= budget:
            break
    return bq, bk, mb


def _kl_pass(qi, ki, w, q, k, lse, select, lse_i, *, scale, interpret):
    """``(kl rows (B, T), dqI, dkI, dw)`` for a unit cotangent on the SUM
    of the rows."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    HI, DI = qi.shape[2], qi.shape[3]
    bq, bk, vmem_mb = _kl_plan(T, H, Hkv, D, HI, DI, qi.dtype.itemsize,
                               _pallas.vmem_headroom_ok())
    nq = T // bq
    qi_t = qi.transpose(0, 2, 1, 3)                          # (B, HI, T, DI)
    kl, dqi, dw, dki = pl.pallas_call(
        functools.partial(
            _kl_kernel, heads=H, kv_heads=Hkv, head_dim=D, index_heads=HI,
            scale=scale, index_scale=1.0 / math.sqrt(HI * DI), block_q=bq,
            block_k=bk),
        grid=(B, nq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, H * D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, HI, bq, DI), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, bk, DI), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, HI), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, HI, bq, DI), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, HI, bq, 1), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, DI), lambda b, i, j: (b, i, j, 0)),
        ],
        out_shape=[
            _pallas.struct((B, T, 1), jnp.float32, q, qi),
            _pallas.struct((B, HI, T, DI), jnp.float32, q, qi),
            _pallas.struct((B, HI, T, 1), jnp.float32, q, qi),
            _pallas.struct((B, nq, T, DI), jnp.float32, q, qi),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((HI, bq, DI), jnp.float32),
            pltpu.VMEM((HI, bq, bk), jnp.dtype(f"int{8 * qi.dtype.itemsize}")),
            pltpu.VMEM((HI, bq, DI), qi.dtype)],
        interpret=interpret,
        name="index_kl",
        **_pallas.compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), vmem_mb),
    )(q.reshape(B, T, H * D), k.reshape(B, T, Hkv * D),
      lse.transpose(0, 2, 1), select, qi_t, ki, w.astype(jnp.float32),
      lse_i[..., None])
    return (kl[..., 0], dqi.transpose(0, 2, 1, 3), dki.sum(axis=1),
            dw[..., 0].transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _index_kl(qi, ki, w, q, k, lse, select, lse_i, scale, interpret):
    return _index_kl_fwd(qi, ki, w, q, k, lse, select, lse_i, scale,
                         interpret)[0]


def _index_kl_fwd(qi, ki, w, q, k, lse, select, lse_i, scale, interpret):
    kl, dqi, dki, dw = _kl_pass(qi, ki, w, q, k, lse, select, lse_i,
                                scale=scale, interpret=interpret)
    n = kl.size
    return kl.mean(), (dqi.astype(qi.dtype) / n, dki.astype(ki.dtype) / n,
                       dw.astype(w.dtype) / n)


def _index_kl_bwd(scale, interpret, grads, g):
    # Nothing reaches q, k or the statistics: L_I moves the indexer alone.
    return (*((g * d).astype(d.dtype) for d in grads),
            None, None, None, None, None)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kl(qi, ki, w, q, k, lse, select, lse_i, *,
             scale: Optional[float] = None, interpret: bool = False):
    """``L_I``, the mean over the B·T queries of ``KL(p ‖ softmax_S(I))``.
    ``qi``, ``ki``, ``w``: the indexer's projections, the only arguments a
    gradient reaches (made in the same pass, kept for the backward one);
    ``q`` (B, T, H, D), ``k`` (B, T, H_kv, D) and ``lse`` (B, H, T): the
    selected attention's rotated queries, keys and log-sum-exps, read
    detached; ``select``, ``lse_i``: :func:`index_select`'s."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    q, k, lse, lse_i = (lax.stop_gradient(a) for a in (q, k, lse, lse_i))
    with jax.named_scope("kl"):
        return _index_kl(qi, ki, w, q, k, lse, select, lse_i, float(scale),
                         bool(interpret))


# ------------------------------------------------------- plain reference


def sparse_attention_reference(q, k, v, qi, ki, w, topk: int):
    """``(out, L_I, select)`` with dense (T, T) arrays in plain
    ``jax.numpy``: the selected attention of ``q`` (B, T, H, D) over ``k``,
    ``v`` (B, T, H_kv, D) and the indexer's loss.  The indexer's inputs are
    used as they come (the caller detaches what it must)."""
    B, T, H, D = q.shape
    rep = H // k.shape[2]
    HI, DI = qi.shape[2], qi.shape[3]
    causal = jnp.tril(jnp.ones((T, T), bool))
    products = jnp.einsum("bthd,bsd->bhts", qi.astype(jnp.float32),
                          ki.astype(jnp.float32))
    scores = jnp.einsum("bth,bhts->bts", w.astype(jnp.float32),
                        jax.nn.relu(products)) / math.sqrt(HI * DI)
    scores = jnp.where(causal, scores, -jnp.inf)
    chosen, lse_i = select_rows(lax.stop_gradient(scores), topk)
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, rep, axis=2),
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    probs = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype),
                     jnp.repeat(v, rep, axis=2))
    p = lax.stop_gradient(probs.mean(axis=1))
    log_pi = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    kl = jnp.where(p > 0, p * (jnp.log(jnp.maximum(p, 1e-37))
                               - jnp.where(chosen, log_pi, 0.0)), 0.0)
    return out, kl.sum(axis=-1).mean(), chosen.astype(jnp.int8)
