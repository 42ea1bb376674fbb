"""State-space duality: Mamba-2's selective scan, computed in chunks.

Per head ``h`` (``P`` channels, state ``N`` wide) the recurrence is

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t        a_t = exp(dt_t A_h), A_h < 0
    y_t = S_t C_t + D_h x_t

with ``B_t``, ``C_t`` shared by the heads of a group.  :func:`ssd_scan`
never steps token by token and never forms a (T, T) matrix: the sequence
is cut into chunks of ``chunk`` tokens (Dao & Gu, arXiv:2405.21060, the
"SSD" algorithm),

* inside a chunk the output is a masked product: ``(L o C B^T) (dt x)``
  with ``L[t, s] = a_{s+1} ... a_t`` for ``s <= t``, three matmuls on
  ``chunk``-square tiles;
* each chunk's own contribution to the state, ``sum_s (a_{s+1} ... a_last)
  dt_s x_s (x) B_s``, is one matmul;
* the states are passed from chunk to chunk in float32 (the one
  sequential part: T/chunk steps of an elementwise multiply-add on
  (H, P, N));
* the state entering a chunk reaches its tokens through ``C`` and the
  decay from the chunk's start, one more matmul.

Matmul operands are in ``x.dtype`` (bfloat16 in training), every decay, the
running sum, the accumulation and the carried state in float32.

Two forms of that one algorithm, and one place that chooses
(:func:`_plan`, a pure function of the shapes, the dtype, ``interpret``,
manual mesh axes and the device kind; no option picks a form):

* **Pallas TPU kernels** where the shapes tile (chunks and state in
  multiples of 128, a group's channels in whole 128-lane tiles; the
  ``twotower_1chip`` and ``granitehmicro_1chip`` cells).  ``ssd_fwd`` walks a sequence's chunks in
  order — grid ``(batch, group, chunk)``, the chunk axis sequential — with
  the group's running state in VMEM: no chunk-square tile and no chunk
  state goes to HBM.  It reads ``x``, ``B``, ``C`` as column ranges of
  the one ``(b, T, H P + 2 G N)`` array the mixer's convolution leaves
  (:func:`ssd_scan_packed`), so nothing is split or transposed on the way
  in but ``dt`` (4 MB, turned time-minor).  The backward is a
  ``jax.custom_vjp`` whose residuals are the inputs only: ``ssd_states``
  recomputes the state entering every chunk and writes it out in float32
  (a transient of the layer's backward), ``ssd_bwd`` sweeps the chunks
  from the last to the first with the state's gradient carried in VMEM
  and writes ``dx``, ``dB``, ``dC`` (summed over a group's heads in the
  kernel), ``ddt``, and what ``A`` and ``D`` get per position (XLA
  finishes those sums).  A group whose blocks would not fit a grid step
  (one group of B and C over 64 heads of 64 in chunks of 256: the
  ``granitehmicro_1chip`` cell) is split into head tiles, the fewest whose
  blocks fit Mosaic's default budget: the grid's second axis then counts
  tiles, a tile reads its group's ``B`` and ``C`` and writes its part of
  ``dB`` and ``dC`` in float32, and XLA sums a group's tiles.  Under the
  mixer's ``jax.checkpoint`` the
  replayed forward leaves no kernel: nothing reads its ``y``.  The
  drivers are ``jax.jit(inline=True)``: the mixers of a stack share one
  trace of each kernel body.
* **plain XLA** (:func:`_ssd_chunked`) otherwise — the tiny shapes of
  the CPU tests, interpreted Pallas under ``shard_map``'s manual axes —
  and as the kernels' second oracle; the scopes ``intra``, ``states``,
  ``pass`` and ``inter`` name its four parts in a trace.  Differentiated
  as it stands it keeps the chunk-square tiles, ``T * chunk * H`` floats
  several times over: a caller at training sizes wraps the scan in a
  ``jax.checkpoint`` (the mixer does, together with its convolution).

:func:`ssd_recurrence` is the definition, token by token in float32, for
tests at small sizes.
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


def scan_sizes(batch: int, seq_len: int, heads: int, head_dim: int,
               state: int, chunk: int) -> dict:
    """What one call of :func:`ssd_scan` passes between chunks, from
    shapes: the chunks scanned and the bytes of float32 chunk states."""
    chunks = batch * -(-seq_len // chunk)
    return {"chunks": chunks,
            "state_bytes": chunks * heads * head_dim * state * 4}


def _ssd_chunked(x, dt, A, B, C, D, chunk):
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G                                   # heads a group
    Q = chunk
    nc = T // Q
    dtype = x.dtype

    xc = x.reshape(b, nc, Q, G, R, P)
    Bc = B.reshape(b, nc, Q, G, N)
    Cc = C.reshape(b, nc, Q, G, N)
    # (b, nc, G, R, Q): the chunk's positions last, so that the
    # chunk-square tiles below have them as their two minor dimensions.
    dtc = dt.astype(_F32).reshape(b, nc, Q, G, R).transpose(0, 1, 3, 4, 2)
    dA = dtc * A.astype(_F32).reshape(G, R)[None, None, :, :, None]
    # log of a_1 ... a_t: the running sum over a chunk as one product with
    # a triangle of ones, at full float32 precision (a cumsum lowers to a
    # reduce-window that took 9.6 ms a step on the chip, PERF.md, PR 30).
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    cum = jnp.einsum("bcgrs,ts->bcgrt", dA, causal.astype(_F32),
                     precision=lax.Precision.HIGHEST)
    total = cum[..., -1]                         # (b, nc, G, R)

    with jax.named_scope("intra"):
        scores = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc,
                            preferred_element_type=_F32)
        seg = cum[..., :, None] - cum[..., None, :]          # [t, s]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        m = (scores[:, :, :, None] * decay
             * dtc[..., None, :]).astype(dtype)  # (b, nc, G, R, Q, S)
        y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m, xc,
                       preferred_element_type=_F32)

    with jax.named_scope("states"):
        # What each token leaves in the state at its chunk's end.
        to_end = (jnp.exp(total[..., None] - cum) * dtc).transpose(
            0, 1, 4, 2, 3)                       # (b, nc, Q, G, R)
        xw = (xc.astype(_F32) * to_end[..., None]).astype(dtype)
        own = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, Bc,
                         preferred_element_type=_F32)

    with jax.named_scope("pass"):
        def step(state, chunk_in):
            own_c, total_c = chunk_in
            new = jnp.exp(total_c)[..., None, None] * state + own_c
            return new, state                    # emit the state ENTERING

        _, entering = lax.scan(
            step, jnp.zeros_like(own[:, 0]),     # varies as the operands
            (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)  # (b, nc, G, R, P, N)

    with jax.named_scope("inter"):
        from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)   # (b,nc,Q,G,R)
        y = y + from_start[..., None] * jnp.einsum(
            "bcqgn,bcgrpn->bcqgrp", Cc, entering.astype(dtype),
            preferred_element_type=_F32)

    y = y.reshape(b, T, H, P) + D.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(dtype)


# ------------------------------------------------------------ the kernels
#
# One grid step of each kernel holds one chunk of one group of one
# sequence: ``x`` (Q, R P) — the group's R heads side by side —, ``B`` and
# ``C`` (Q, N), ``dt`` (R, Q) with the chunk's positions on the lanes, and
# the group's state transposed, (N, R P) float32, so that the read-out
# ``C S^T`` and the update ``B^T xw`` of all R heads are one product each
# and only ``B`` (or ``C``) is ever transposed.  The chunk axis is the
# grid's last and sequential; the state lives in scratch across it.
#
# A group too wide for one block (one group over 64 heads: 4,096 channels
# a row) is split into ``tiles`` head tiles (``_plan``), each a grid step
# chain of its own along the grid's second axis: the kernels then see R
# heads of ONE TILE where the text says "group", ``g`` counts tiles, and
# the tile reads its group's ``B`` and ``C`` (block ``g // tiles``).  What
# a tile adds to ``dB`` and ``dC`` it writes out in float32, and XLA sums
# a group's tiles.
#
# What a head needs along the sublanes (its running sum as a column, for
# the decay tile's rows) comes from one small transpose a grid step; what
# it needs per channel (a column repeated over the head's P lanes) is
# spread a *tile* at a time, a tile being 128 lanes: two heads of 64, or
# a head of 128 or more.


class _Dims(NamedTuple):
    """The static sizes of a fused scan: heads, channels a head, groups,
    state width, chunk, and the tiles a group's heads are split in (a
    grid step holds ONE tile of one group: :func:`_plan`)."""
    H: int
    P: int
    G: int
    N: int
    Q: int
    tiles: int = 1

    @property
    def V(self):                 # head tiles a sequence: the grid's axis 1
        return self.G * self.tiles

    @property
    def R(self):                 # heads a tile (a whole group's where 1)
        return self.H // self.V

    @property
    def RP(self):                # a tile's channels
        return self.R * self.P

    @property
    def tile(self):              # lanes worked on at a time
        return max(self.P, 128)

    @property
    def hp(self):                # heads a tile
        return self.tile // self.P


def _nt(a, b):
    """``a b^T`` with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _tn(a, b):
    """``a^T b`` with float32 accumulation."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _triangle(Q):
    """``[t, s] = t >= s``."""
    return (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _running_sums(dt_ref, a_ref, g, d: _Dims):
    """A chunk's ``dt`` and the running sum of ``dt A`` — the product with
    a triangle of ones ``_ssd_chunked`` uses, float32 at full precision —
    as rows (R, Q) and, transposed once, as columns (Q, R)."""
    dt = dt_ref[...]
    dA = jnp.concatenate([dt[r:r + 1] * a_ref[g * d.R + r]
                          for r in range(d.R)], axis=0)
    tri = _triangle(d.Q)
    cum = lax.dot_general(dA, tri.astype(_F32), (((1,), (1,)), ((), ())),
                          preferred_element_type=_F32,
                          precision=lax.Precision.HIGHEST)
    cols = jnp.concatenate([cum, dt], axis=0).T              # (Q, 2 R)
    return dt, cum, cols[:, :d.R], cols[:, d.R:], tri


def _over_lanes(cols, j, d: _Dims):
    """Tile ``j``'s heads' columns of ``cols`` (Q, R), each repeated over
    its head's P lanes: (Q, tile)."""
    shape = (cols.shape[0], d.tile)
    first = j * d.hp
    out = jnp.broadcast_to(cols[:, first:first + 1], shape)
    if d.hp > 1:
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        for k in range(1, d.hp):
            out = jnp.where(lane >= k * d.P, jnp.broadcast_to(
                cols[:, first + k:first + k + 1], shape), out)
    return out


def _a_head_each(values, d: _Dims):
    """``values[r]`` (a scalar or a (1, 1) array) over head ``r``'s P
    lanes: a row (1, R P)."""
    shape = (1, d.RP)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    # Every head through a select, the first too: a bare (1, 1) -> (1, R P)
    # broadcast that meets a (N, R P) operand is folded into one broadcast
    # over sublanes and lanes, which Mosaic (jax 0.9.0) refuses.
    out = jnp.zeros(shape, _F32)
    for r in range(d.R):
        out = jnp.where(lane >= r * d.P,
                        jnp.broadcast_to(values[r], shape), out)
    return out


def _head_lanes(k, d: _Dims):
    """Which lanes of a tile are its ``k``-th head's: (1, tile), or None
    where the tile is one head."""
    if d.hp == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, d.tile), 1)
    return (lane >= k * d.P) & (lane < (k + 1) * d.P)


def _decay_tile(scores, cum, cum_c, dt, tri, r, dtype):
    """Head ``r``'s masked decay ``L`` (float32) and the tile ``scores o L
    o dt`` cast to the operands' dtype, as ``_ssd_chunked`` casts it."""
    seg = cum_c[:, r:r + 1] - cum[r:r + 1, :]                # [t, s]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    return decay, (scores * decay * dt[r:r + 1, :]).astype(dtype)


def _to_end(cum_c, d: _Dims):
    """The decay from each position to its chunk's end, (Q, R)."""
    return jnp.exp(cum_c[d.Q - 1:d.Q, :] - cum_c)


def _whole_chunk(cum, d: _Dims):
    """The decay over the whole chunk, a head's over its lanes: (1, R P)."""
    return _a_head_each([jnp.exp(cum[r:r + 1, d.Q - 1:d.Q])
                         for r in range(d.R)], d)


def _state_update(st, Bm, xw, cum, d: _Dims):
    """``exp(total) S + B^T xw``: the state leaving the chunk."""
    return _whole_chunk(cum, d) * st + _tn(Bm, jnp.concatenate(xw, axis=1))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, st_ref,
                *, d: _Dims):
    g, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = x_ref.dtype
    dt, cum, cum_c, dt_c, tri = _running_sums(dt_ref, a_ref, g, d)
    Bm, Cm = b_ref[...], c_ref[...]
    scores = _nt(Cm, Bm)                                     # (Q, Q)
    to_end = _to_end(cum_c, d) * dt_c                        # (Q, R)
    from_start = jnp.exp(cum_c)
    st = st_ref[...]
    read = _nn(Cm, st.astype(dtype))                         # (Q, R P)
    skip = _a_head_each([d_ref[g * d.R + r] for r in range(d.R)], d)
    xw = []
    for j in range(d.RP // d.tile):
        sl = slice(j * d.tile, (j + 1) * d.tile)
        xt = x_ref[:, sl]
        xf = xt.astype(_F32)
        y = None
        for k in range(d.hp):
            _, m = _decay_tile(scores, cum, cum_c, dt, tri, j * d.hp + k,
                               dtype)
            yk = _nn(m, xt)
            y = yk if k == 0 else jnp.where(_head_lanes(k, d), yk, y)
        y = (y + _over_lanes(from_start, j, d) * read[:, sl]
             + skip[:, sl] * xf)
        y_ref[:, sl] = y.astype(y_ref.dtype)
        xw.append((xf * _over_lanes(to_end, j, d)).astype(dtype))
    st_ref[...] = _state_update(st, Bm, xw, cum, d)


def _states_kernel(x_ref, b_ref, dt_ref, a_ref, entering_ref, st_ref, *,
                   d: _Dims):
    """The state entering each chunk, written out: the backward's first
    pass (``states`` and ``pass`` of the XLA form, nothing of ``y``)."""
    g, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = x_ref.dtype
    _, cum, cum_c, dt_c, _ = _running_sums(dt_ref, a_ref, g, d)
    to_end = _to_end(cum_c, d) * dt_c
    st = st_ref[...]
    entering_ref[...] = st
    xw = [(x_ref[:, j * d.tile:(j + 1) * d.tile].astype(_F32)
           * _over_lanes(to_end, j, d)).astype(dtype)
          for j in range(d.RP // d.tile)]
    st_ref[...] = _state_update(st, b_ref[...], xw, cum, d)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, dy_ref, entering_ref, a_ref,
                d_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                dst_ref, *, d: _Dims):
    """One chunk of the sweep from the last chunk to the first.
    ``dst_ref`` carries the gradient of the state leaving the chunk and is
    left holding that of the state entering it; ``da_ref`` and ``dd_ref``
    gather what ``A`` and ``D`` get, per position, over a sequence's
    chunks (XLA finishes the sums)."""
    g, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    R, Q = d.R, d.Q
    dt, cum, cum_c, dt_c, tri = _running_sums(dt_ref, a_ref, g, d)
    Bm, Cm = b_ref[...], c_ref[...]
    scores = _nt(Cm, Bm)
    decay_to_end = _to_end(cum_c, d)                         # (Q, R)
    to_end = decay_to_end * dt_c
    from_start = jnp.exp(cum_c)
    st = entering_ref[...]                                   # (N, R P) f32
    sb = st.astype(dtype)
    down = dst_ref[...]
    down_b = down.astype(dtype)
    read = _nn(Cm, sb)                                       # (Q, R P)
    dxw = _nn(Bm, down_b)
    skip = _a_head_each([d_ref[g * R + r] for r in range(R)], d)

    dscores = jnp.zeros((Q, Q), _F32)
    dz, xw = [], []
    col_v = []                  # per head (1, Q): what dt gets through m
    dcum_c, ddt_c, dtotal = [], [], []       # per head (Q, 1), (1, 1)
    for j in range(d.RP // d.tile):
        sl = slice(j * d.tile, (j + 1) * d.tile)
        xt, gt = x_ref[:, sl], dy_ref[:, sl]
        xf, gf = xt.astype(_F32), gt.astype(_F32)
        e_t, te_t = _over_lanes(from_start, j, d), _over_lanes(to_end, j, d)
        dz.append((e_t * gf).astype(dtype))
        xw.append((xf * te_t).astype(dtype))
        dxw_t = dxw[:, sl]
        dx = skip[:, sl] * gf + dxw_t * te_t
        through_read = gf * e_t * read[:, sl]
        through_xw = dxw_t * xf
        for k in range(d.hp):
            r = j * d.hp + k
            mine = _head_lanes(k, d)
            decay, m = _decay_tile(scores, cum, cum_c, dt, tri, r, dtype)
            gk = gt if mine is None else jnp.where(mine, gt, 0)
            dm = _nt(gk, xt) * decay                         # (Q, Q)
            dscores = dscores + dm * dt[r:r + 1, :]
            dx = dx + _tn(m, gk)
            # What the decay tile's exponent gets, [t, s]: the running sum
            # takes its row sums at t and, negated, its column sums at s —
            # from the one float32 tile, so that the two cancel as they
            # must.
            dm = dm * scores
            col_v.append(jnp.sum(dm, axis=0, keepdims=True))
            rs_read, rs_xw = (jnp.sum(
                q if mine is None else jnp.where(mine, q, 0.0), axis=1,
                keepdims=True) for q in (through_read, through_xw))
            u = rs_xw * to_end[:, r:r + 1]
            ddt_c.append(rs_xw * decay_to_end[:, r:r + 1])
            dcum_c.append(jnp.sum(dm * dt[r:r + 1, :], axis=1,
                                  keepdims=True) + rs_read - u)
            dtotal.append(jnp.sum(u, axis=0, keepdims=True))
        dx_ref[:, sl] = dx.astype(dx_ref.dtype)
        dd_ref[:, sl] += gf * xf
    dz, xw = jnp.concatenate(dz, axis=1), jnp.concatenate(xw, axis=1)
    ds = dscores.astype(dtype)
    dc_ref[...] = (_nn(ds, Bm) + _nt(dz, sb)).astype(dc_ref.dtype)
    db_ref[...] = (_tn(ds, Cm) + _nt(xw, down_b)).astype(db_ref.dtype)

    dec = _whole_chunk(cum, d)
    kept = jnp.sum(down * st, axis=0, keepdims=True) * dec   # (1, R P)
    lane = lax.broadcasted_iota(jnp.int32, (1, d.RP), 1)
    for r in range(R):
        dtotal[r] = dtotal[r] + jnp.sum(
            jnp.where((lane >= r * d.P) & (lane < (r + 1) * d.P), kept,
                      0.0), axis=1, keepdims=True)
    dst_ref[...] = dec * down + _tn(Cm, dz)

    # Back through the running sum: the same triangle, transposed.
    rows = jnp.concatenate(dcum_c + ddt_c, axis=1).T         # (2 R, Q)
    col_v = jnp.concatenate(col_v, axis=0)                   # (R, Q)
    last = lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    dcum = rows[:R] - col_v * dt + jnp.concatenate(
        [jnp.where(last, jnp.broadcast_to(dtotal[r], (1, Q)), 0.0)
         for r in range(R)], axis=0)
    ddA = jnp.dot(dcum, tri.astype(_F32), preferred_element_type=_F32,
                  precision=lax.Precision.HIGHEST)           # (R, Q)
    ddt_ref[...] = col_v + rows[R:] + jnp.concatenate(
        [ddA[r:r + 1] * a_ref[g * R + r] for r in range(R)], axis=0)
    da_ref[...] += ddA * dt


def _specs(d: _Dims, nc, backward=False):
    """Block specs over ``(b, T, ...)`` arrays: ``x`` and a same-shaped
    ``y`` / ``dy`` / ``dx``, ``B`` / ``C``: ``cols(width, first)``, tile
    0's block of ``width`` columns being the ``first``-th (``shared``: a
    group's block, the same for each of its tiles); ``dt``-shaped
    (b V, R, T); the states entering the chunks.  ``backward``: chunks
    from the last to the first."""
    def chunk(c):
        return nc - 1 - c if backward else c

    def cols(width, first, shared=False):
        if shared and d.tiles > 1:
            return pl.BlockSpec(
                (None, d.Q, width),
                lambda i, g, c: (i, chunk(c), first + g // d.tiles))
        return pl.BlockSpec((None, d.Q, width),
                            lambda i, g, c: (i, chunk(c), first + g))

    rows = pl.BlockSpec((None, d.R, d.Q),
                        lambda i, g, c: (i * d.V + g, 0, chunk(c)))
    entering = pl.BlockSpec((None, None, None, d.N, d.RP),
                            lambda i, g, c: (i, g, chunk(c), 0, 0))
    return cols, rows, entering


_SMEM = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _time_minor(dt, d: _Dims):
    """``dt`` (b, T, H) as (b V, R, T) float32: a chunk's positions on
    the lanes."""
    b, T, _ = dt.shape
    return dt.astype(_F32).reshape(b, T, d.V, d.R).transpose(
        0, 2, 3, 1).reshape(b * d.V, d.R, T)


def _bases(d: _Dims):
    """Column-block offsets of x | B | C in one (b, T, H P + 2 G N) row:
    x's in blocks of a tile's channels, B's and C's in blocks of N."""
    inner = d.H * d.P
    return 0, inner // d.N, (inner + d.G * d.N) // d.N


@functools.partial(jax.jit, inline=True,
                   static_argnames=("d", "plan", "interpret"))
def _fused_fwd(xbc, dt, A, D, *, d: _Dims, plan, interpret):
    """``y`` (b, T, H P) from the convolution's output as it stands:
    x | B | C are read as column ranges of ``xbc``."""
    b, T, _ = xbc.shape
    nc = T // d.Q
    cols, rows, _ = _specs(d, nc)
    x0, b0, c0 = _bases(d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d),
        grid=(b, d.V, nc),
        in_specs=[cols(d.RP, x0), cols(d.N, b0, True), cols(d.N, c0, True),
                  rows, _SMEM(), _SMEM()],
        out_specs=cols(d.RP, 0),
        out_shape=_pallas.struct((b, T, d.H * d.P), xbc.dtype, xbc, dt),
        scratch_shapes=[pltpu.VMEM((d.N, d.RP), _F32)],
        interpret=interpret, name="ssd_fwd",
        **_pallas.compiler_params(interpret, _SEMANTICS, plan.vmem_mb),
    )(xbc, xbc, xbc, _time_minor(dt, d), A.astype(_F32), D.astype(_F32))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("d", "plan", "interpret"))
def _fused_bwd(xbc, dt, A, D, dy, *, d: _Dims, plan, interpret):
    """The cotangents of :func:`_fused_fwd`'s four operands.  Two
    kernels: the states entering every chunk, recomputed and written out
    in float32 (a transient of this call), then the sweep from the last
    chunk to the first with the state's gradient carried in VMEM."""
    b, T, _ = xbc.shape
    nc = T // d.Q
    x0, b0, c0 = _bases(d)
    dt_rows = _time_minor(dt, d)
    A32, D32 = A.astype(_F32), D.astype(_F32)
    cols, rows, entering = _specs(d, nc)
    states = pl.pallas_call(
        functools.partial(_states_kernel, d=d),
        grid=(b, d.V, nc),
        in_specs=[cols(d.RP, x0), cols(d.N, b0, True), rows, _SMEM()],
        out_specs=entering,
        out_shape=_pallas.struct((b, d.V, nc, d.N, d.RP), _F32, xbc, dt),
        scratch_shapes=[pltpu.VMEM((d.N, d.RP), _F32)],
        interpret=interpret, name="ssd_states",
        **_pallas.compiler_params(interpret, _SEMANTICS, plan.vmem_mb),
    )(xbc, xbc, dt_rows, A32)

    cols, rows, entering = _specs(d, nc, backward=True)
    per_group = pl.BlockSpec((None, d.R, d.Q),
                             lambda i, g, c: (i * d.V + g, 0, 0))
    per_channel = pl.BlockSpec((None, d.Q, d.RP),
                               lambda i, g, c: (i * d.V + g, 0, 0))
    like = (xbc, dt, dy)
    # A group's tiles each write their part of dB and dC: float32 where
    # there is more than one, so that their sum rounds once.
    part = xbc.dtype if d.tiles == 1 else _F32
    dx, dB, dC, ddt, dA, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d),
        grid=(b, d.V, nc),
        in_specs=[cols(d.RP, x0), cols(d.N, b0, True), cols(d.N, c0, True),
                  rows, cols(d.RP, 0), entering, _SMEM(), _SMEM()],
        out_specs=[cols(d.RP, 0), cols(d.N, 0), cols(d.N, 0), rows,
                   per_group, per_channel],
        out_shape=[_pallas.struct((b, T, d.H * d.P), xbc.dtype, *like),
                   _pallas.struct((b, T, d.V * d.N), part, *like),
                   _pallas.struct((b, T, d.V * d.N), part, *like),
                   _pallas.struct((b * d.V, d.R, T), _F32, *like),
                   _pallas.struct((b * d.V, d.R, d.Q), _F32, *like),
                   _pallas.struct((b * d.V, d.Q, d.RP), _F32, *like)],
        scratch_shapes=[pltpu.VMEM((d.N, d.RP), _F32)],
        interpret=interpret, name="ssd_bwd",
        **_pallas.compiler_params(interpret, _SEMANTICS, plan.vmem_mb),
    )(xbc, xbc, xbc, dt_rows, dy, states, A32, D32)
    if d.tiles > 1:
        dB, dC = (a.reshape(b, T, d.G, d.tiles, d.N).sum(3).reshape(
            b, T, d.G * d.N).astype(xbc.dtype) for a in (dB, dC))
    ddt = ddt.reshape(b, d.V, d.R, T).transpose(0, 3, 1, 2).reshape(
        b, T, d.H)
    dA = dA.reshape(b, d.V, d.R, d.Q).sum((0, 3)).reshape(d.H)
    dD = dD.reshape(b, d.V, d.Q, d.R, d.P).sum((0, 2, 4)).reshape(d.H)
    return (jnp.concatenate([dx, dB, dC], axis=-1), ddt.astype(dt.dtype),
            dA.astype(A.dtype), dD.astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(xbc, dt, A, D, d, plan, interpret):
    return _fused_fwd(xbc, dt, A, D, d=d, plan=plan, interpret=interpret)


def _fused_fwd_rule(xbc, dt, A, D, d, plan, interpret):
    # The inputs alone: the states of every chunk would be 268 MB a layer
    # at the cell's shape, and under the mixer's jax.checkpoint nothing
    # reads the replayed y, so the replayed forward leaves no kernel.
    return (_fused_fwd(xbc, dt, A, D, d=d, plan=plan, interpret=interpret),
            (xbc, dt, A, D))


def _fused_bwd_rule(d, plan, interpret, res, dy):
    return _fused_bwd(*res, dy, d=d, plan=plan, interpret=interpret)


_fused.defvjp(_fused_fwd_rule, _fused_bwd_rule)


class ScanPlan(NamedTuple):
    """What :func:`_plan` decides for one call of the scan."""
    form: str           # "kernels" | "xla"
    grid: tuple         # (head tiles, chunks) a sequence; () in the XLA form
    vmem_bytes: int     # what the largest kernel's blocks, scratch and
    #                     temporaries take, by shapes; 0 in the XLA form
    vmem_mb: int        # scoped-VMEM budget asked, MB; 0 = Mosaic's default
    tiles: int = 1      # head tiles a group is split in: 1 = a group a step


# Mosaic's default scoped-VMEM budget, and the most the kernels ask a
# device with head-room for.
_DEFAULT_VMEM = 16 * 2 ** 20
_MOST_VMEM = 64 * 2 ** 20


def _plan(*, T, H, P, G, N, chunk, itemsize, interpret, manual_axes,
          vmem_headroom) -> ScanPlan:
    """Kernels or the XLA form — the one place that chooses, a pure
    function of what the op observes at trace time.

    The kernels take a shape that tiles: chunks of a multiple of 128
    positions (they are the lanes of ``dt`` and of the decay tiles), a
    state a multiple of 128 wide, a group's channels ``P H / G`` in whole
    tiles of 128 lanes, ``H P`` a multiple of ``N`` (B and C are addressed
    in blocks of N columns behind x).  Interpreted Pallas under
    ``shard_map``'s manual axes takes the XLA form
    (:func:`_pallas.xla_form`).  ``vmem_headroom``: whether the device
    backs a scoped budget above Mosaic's default, asked only where the
    backward kernel's blocks need it.

    A grid step holds a whole group where its blocks fit (within
    ``_MOST_VMEM`` on a device with head-room, within Mosaic's default on
    one without).  A group wider than that (one group over 64 heads: the
    blocks would ask 92 MB) is split into the fewest head tiles whose
    blocks fit Mosaic's default budget, each of whole 128-lane tiles."""
    xla = ScanPlan("xla", (), 0, 0)
    if H % G:
        return xla
    d = _Dims(H, P, G, N, chunk)
    tiles = (chunk % 128 == 0 and N % 128 == 0 and d.RP % 128 == 0
             and (128 % P == 0 or P % 128 == 0) and d.R % d.hp == 0
             and (H * P) % N == 0)
    if not tiles or _pallas.xla_form(interpret, manual_axes):
        return xla
    Q = chunk

    def blocks(RP):
        # The backward kernel: x, dy, dx blocks and B, C, dB, dC, each
        # twice (the pipeline's two buffers); the entering state and dD's
        # gatherer in float32, twice; the carried gradient; some ten
        # (Q, R P) float32 temporaries and a handful of (Q, Q).
        return (2 * (3 * Q * RP + 4 * Q * N) * itemsize
                + 2 * (N * RP + Q * RP) * 4 + N * RP * 4
                + 10 * Q * RP * 4 + 8 * Q * Q * 4)

    chunks, fits = -(-T // chunk), _DEFAULT_VMEM * 3 // 4
    asked = blocks(d.RP)
    if asked <= fits:
        return ScanPlan("kernels", (G, chunks), asked, 0)
    vmem_mb = -(-asked * 4 // 3 // 2 ** 20)
    if vmem_headroom and vmem_mb * 2 ** 20 <= _MOST_VMEM:
        return ScanPlan("kernels", (G, chunks), asked, vmem_mb)
    for split in range(2, d.R // d.hp + 1):
        heads = d.R // split
        if d.R % split or heads % d.hp or heads * P % 128:
            continue
        asked = blocks(heads * P)
        if asked <= fits:
            return ScanPlan("kernels", (G * split, chunks), asked, 0, split)
    return xla


def scan_plan(x_like, dt_like, *, heads, head_dim, groups, state, chunk,
              interpret) -> ScanPlan:
    """:func:`_plan` for a call whose ``x`` (alone or packed with B and C)
    and ``dt`` are, or are shaped like, ``x_like`` and ``dt_like``
    (b, T, ...): what the mixer, ``chip_smoke.py`` and the tests ask."""
    vma = jax.typeof(x_like).vma | jax.typeof(dt_like).vma
    return _plan(T=x_like.shape[1], H=heads, P=head_dim, G=groups, N=state,
                 chunk=chunk, itemsize=x_like.dtype.itemsize,
                 interpret=interpret, manual_axes=bool(vma),
                 vmem_headroom=_pallas.vmem_headroom_ok())


def _padded(arrays, T, chunk):
    pad = -T % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                 for a in arrays)


def ssd_scan_packed(xBC, dt, A, D, *, heads: int, groups: int, state: int,
                    chunk: int = 128, interpret: bool = False):
    """:func:`ssd_scan` on ``x | B | C`` as one array (b, T, H P + 2 G N),
    the layout the mixer's convolution leaves them in; ``y`` (b, T, H P).
    The kernels read the three as column ranges of ``xBC``, so nothing is
    split or transposed on the way in; the XLA form splits it."""
    b, T, width = xBC.shape
    inner = width - 2 * groups * state
    if heads % groups or inner % heads:
        raise ValueError(f"{groups} groups do not divide {heads} heads, or "
                         f"{heads} heads not {inner} channels")
    plan = scan_plan(xBC, dt, heads=heads, head_dim=inner // heads,
                     groups=groups, state=state, chunk=chunk,
                     interpret=interpret)
    xBC, dt = _padded((xBC, dt), T, chunk)
    if plan.form == "kernels":
        y = _fused(xBC, dt, A, D, _Dims(heads, inner // heads, groups,
                                        state, chunk, plan.tiles), plan,
                   interpret)
    else:
        x, B, C = jnp.split(xBC, [inner, inner + groups * state], axis=-1)
        lead = xBC.shape[:2]
        y = _ssd_chunked(x.reshape(*lead, heads, inner // heads), dt, A,
                         B.reshape(*lead, groups, state),
                         C.reshape(*lead, groups, state), D, chunk)
        y = y.reshape(*lead, inner)
    return y[:, :T]


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128,
             interpret: bool = False):
    """``y`` (b, T, H, P) of the recurrence in the module docstring.

    ``x`` (b, T, H, P); ``dt`` (b, T, H), positive (after its softplus);
    ``A`` (H,), negative; ``B``, ``C`` (b, T, G, N) with ``G`` dividing
    ``H`` (head ``h`` reads group ``h // (H / G)``); ``D`` (H,).  ``T``
    need not be a multiple of ``chunk``: the tail is padded with steps of
    ``dt = 0``, which neither decay the state nor add to it.

    Which form runs follows the shapes (:func:`_plan`) and is not an
    option.  ``interpret=True`` runs the kernels off-TPU (tests)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    if H % G:
        raise ValueError(f"{G} groups do not divide {H} heads")
    plan = scan_plan(x, dt, heads=H, head_dim=P, groups=G, state=N,
                     chunk=chunk, interpret=interpret)
    if plan.form == "kernels":
        packed = jnp.concatenate([x.reshape(b, T, H * P),
                                  B.reshape(b, T, G * N),
                                  C.reshape(b, T, G * N)], axis=-1)
        return ssd_scan_packed(packed, dt, A, D, heads=H, groups=G, state=N,
                               chunk=chunk, interpret=interpret
                               ).reshape(b, T, H, P)
    x, dt, B, C = _padded((x, dt, B, C), T, chunk)
    return _ssd_chunked(x, dt, A, B, C, D, chunk)[:, :T]


def ssd_recurrence(x, dt, A, B, C, D):
    """The same ``y`` by the recurrence itself, one token a step, in
    float32 at full matmul precision: the definition :func:`ssd_scan` is
    tested against (its backward keeps every state: small sizes only)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    x, dt, A, B, C, D = (a.astype(_F32) for a in (x, dt, A, B, C, D))
    B = jnp.repeat(B, H // G, axis=2)            # (b, T, H, N)
    C = jnp.repeat(C, H // G, axis=2)

    def step(state, t):
        x_t, dt_t, B_t, C_t = t                  # (b,H,P) (b,H) (b,H,N) x2
        a = jnp.exp(dt_t * A)[..., None, None]
        state = a * state + (dt_t[..., None] * x_t)[..., None] * B_t[
            :, :, None, :]
        return state, (state * C_t[:, :, None, :]).sum(-1)

    _, y = lax.scan(step, jnp.zeros((b, H, P, N), _F32),
                    tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x
