"""The state-space mixer's two elementwise passes, each one read and one
write of its activation.

Around its scan (:mod:`horovod_tpu.ops.ssd`) Mamba-2's mixer makes two
passes over (b, T, channels) activations that hold no matmul:

* the depthwise causal convolution and its activation,
  ``silu(b + sum_j w_j x_{t - (K - 1) + j})`` (:func:`conv_silu`);
* the gate and the grouped RMSNorm, ``rmsnorm_group(y * silu(z)) * scale``
  over groups of ``inner / groups`` channels (:func:`gated_norm`).

Both read their operand straight out of the input projection's one array
``[z | xBC | dt]`` (b, T, 2 inner + 2 G N + H and the zero columns that
fill its last 128-lane tile: ``models/ssm.py`` ``PaddedDense``) as a column range — as the
scan reads x, B, C out of the convolution's one array — so nothing is
split on the way in.  Operands are in the activations' dtype (bfloat16 in
training) in HBM; every product, sum and norm inside is float32, with one
rounding at the store; the parameters' gradients (``w``, ``b``, ``scale``)
are float32.

Two forms, and one place that chooses (:func:`_plan`, a pure function of
the shapes, the dtype's width, ``interpret`` and manual mesh axes; no
option picks a form):

* **Pallas TPU kernels** where the rows and the channels tile (a time
  length in whole strips of rows, a norm group's channels and the
  convolution's column offset in whole 128-lane tiles; the
  ``twotower_1chip`` cell).  Forward and backward are one kernel each, a
  ``jax.custom_vjp`` whose residuals are the inputs only.  A grid step
  holds a block of rows of a block of channels in VMEM and works through
  it a strip of rows at a time, in registers.  ``ssm_conv_fwd`` takes the
  ``K - 1`` rows before a block from a 16-row halo block of the same
  array (zeros at a sequence's start: no row of one sequence reaches the
  next); ``ssm_conv_bwd`` recomputes the pre-activation, walks the time
  blocks from the last to the first with the first rows of the later
  block's ``dpre`` carried in VMEM, and gathers ``dw``, ``db`` as
  8-row partial sums a sequence that XLA finishes.  ``ssm_gate_fwd``
  and ``ssm_gate_bwd`` hold whole norm groups; the backward recomputes
  ``g = y silu(z)`` and its norm, writes ``dy`` and ``dz`` in one pass
  and gathers ``dscale`` the same way.  A norm group wider than a
  block's 512 columns (one group over all 4,096 channels: the
  ``granitehmicro_1chip`` cell) is a block by itself, of as many fewer
  rows, and a strip's sums over it (of ``g^2``; in the backward also of
  ``dn g``) are gathered 512 columns at a time before each piece is
  worked through again: the block is in VMEM, so nothing is read twice
  from HBM, and ``silu(z)`` is computed twice.  The drivers are
  ``jax.jit(inline=True)``: the mixers of a stack share one trace of
  each kernel body.
* **plain XLA** otherwise (the tiny shapes of the CPU tests, interpreted
  Pallas under ``shard_map``'s manual axes): the caller's own forms
  (``models/ssm.py``: ``causal_conv``, ``gated_group_norm``), on the split
  arrays.
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

# Rows of the block before a time block that the convolution reads for its
# K - 1: a whole bfloat16 tile.  In float32 the last _TAIL of them are
# kept, a whole float32 tile.
_HALO = 16
_TAIL = 8


class PassPlan(NamedTuple):
    """What :func:`_plan` decides for one mixer's two passes."""
    form: str            # "kernels" | "xla"
    rows: int            # time rows a block; 0 in the XLA form
    strip: int           # rows worked on at a time, in registers
    conv_cols: int       # the convolution's channels a block
    gate_cols: int       # the gate's channels a block: whole norm groups
    #                      (one wider than _MOST_COLS holds as many fewer
    #                      rows: _gate_rows)


# Rows a block (of two-byte activations; half as many of four-byte ones),
# rows a strip, and the most channels a block: the best of a sweep on the
# chip at the cell's shape (PERF.md section 6, PR 33).  The largest kernel,
# ``ssm_conv_bwd``, then holds three blocks of 1 MiB twice (the pipeline's
# two buffers): inside Mosaic's default scoped-VMEM budget on every TPU, so
# the plan observes no device kind.
_ROWS = 1024
_STRIP = 32
_MOST_COLS = 512
# The widest norm group the gate takes: a block of it still holds a strip
# of rows within a block's bytes (one norm over all 4,096 channels: 128
# rows of two-byte activations).
_MOST_GROUP = 16 * _MOST_COLS


def _plan(*, T, inner, conv_dim, groups, kernel, itemsize, interpret,
          manual_axes) -> PassPlan:
    """Kernels or the XLA forms — the one place that chooses, a pure
    function of what the passes observe at trace time.

    The kernels take a time length in whole strips of rows; norm groups
    of ``inner / groups`` channels in whole 128-lane tiles, no more than
    a block's most — or a multiple of that, up to ``_MOST_GROUP``: such a
    group is a block by itself, of as many fewer rows, and a strip's sums
    over it are gathered a piece of ``_MOST_COLS`` channels at a time —;
    a convolution whose channels and whose column offset
    in the packed array (``inner``) are whole 128-lane tiles, with no more
    taps than the rows carried between blocks.  Interpreted Pallas under
    ``shard_map``'s manual axes takes the XLA forms
    (:func:`_pallas.xla_form`)."""
    xla = PassPlan("xla", 0, 0, 0, 0)
    if inner % groups or _pallas.xla_form(interpret, manual_axes):
        return xla
    group = inner // groups
    wide = group > _MOST_COLS
    if (T % _STRIP or group % 128 or conv_dim % 128
            or (wide and (group % _MOST_COLS or group > _MOST_GROUP))
            or not 1 <= kernel <= _TAIL or itemsize not in (2, 4)):
        return xla
    conv_cols = 128
    while (conv_cols * 2 <= _MOST_COLS and inner % (conv_cols * 2) == 0
           and conv_dim % (conv_cols * 2) == 0):
        conv_cols *= 2
    gate_cols = group
    while gate_cols * 2 <= _MOST_COLS and inner % (gate_cols * 2) == 0:
        gate_cols *= 2
    return PassPlan("kernels", min(_ROWS * 2 // itemsize, T), _STRIP,
                    conv_cols, gate_cols)


def passes_plan(like, *, inner, conv_dim, groups, kernel,
                interpret) -> PassPlan:
    """:func:`_plan` for a mixer whose activations are, or are shaped
    like, ``like`` (b, T, ...): what the mixer, ``chip_smoke.py`` and the
    tests ask."""
    return _plan(T=like.shape[1], inner=inner, conv_dim=conv_dim,
                 groups=groups, kernel=kernel, itemsize=like.dtype.itemsize,
                 interpret=interpret,
                 manual_axes=bool(jax.typeof(like).vma))


# ------------------------------------------------------------ the kernels


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _rows8(x):
    """The sum of ``x`` (rows, c) over its groups of 8 rows, (8, c): vreg
    adds only; the last 8 -> 1 is XLA's."""
    out = x[0:8]
    for r in range(8, x.shape[0], 8):
        out = out + x[r:r + 8]
    return out


def _valid_rows(first, strip, T):
    """Which of a strip's rows, the first of them row ``first`` of its
    sequence, lie inside it: (strip, 1)."""
    return first + lax.broadcasted_iota(jnp.int32, (strip, 1), 0) < T


def _window(x_ref, halo, i, r0, strip):
    """Strip ``i``'s rows of ``x_ref`` with the _TAIL rows before them on
    top, float32: (_TAIL + strip, c).  Before a block's first strip come
    the rows of ``halo``."""
    start = pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO)
    before = x_ref[pl.ds(start, _HALO), :]
    before = jnp.where(i == 0, halo, before.astype(_F32)[_HALO - _TAIL:])
    return jnp.concatenate(
        [before, x_ref[pl.ds(r0, strip), :].astype(_F32)], axis=0)


def _taps(w_ref, b_ref, K):
    w = w_ref[...]
    return [w[j:j + 1, :] for j in range(K)], b_ref[...]


def _pre_activation(window, taps, bias, K, strip):
    """``b + sum_s w_{K-1-s} x_{t-s}`` for a strip's rows and the shifted
    rows themselves."""
    shifted = [window[_TAIL - s:_TAIL - s + strip] for s in range(K)]
    pre = bias + taps[K - 1] * shifted[0]
    for s in range(1, K):
        pre = pre + taps[K - 1 - s] * shifted[s]
    return pre, shifted


def _halo_rows(halo_ref, at_start):
    """The _TAIL rows before a block, zeros at a sequence's start."""
    halo = halo_ref[...].astype(_F32)[_HALO - _TAIL:]
    return jnp.where(at_start, 0.0, halo)


def _conv_fwd_kernel(halo_ref, x_ref, w_ref, b_ref, y_ref, *, K, strip):
    taps, bias = _taps(w_ref, b_ref, K)
    halo = _halo_rows(halo_ref, pl.program_id(2) == 0)

    def body(i, _):
        r0 = pl.multiple_of(i * strip, strip)
        pre, _ = _pre_activation(_window(x_ref, halo, i, r0, strip), taps,
                                 bias, K, strip)
        y_ref[pl.ds(r0, strip), :] = (pre * _sigmoid(pre)).astype(
            y_ref.dtype)
        return 0

    lax.fori_loop(0, x_ref.shape[0] // strip, body, 0)


def _conv_bwd_kernel(halo_ref, x_ref, dy_ref, w_ref, b_ref, dx_ref, dwb_ref,
                     after_ref, *, K, strip, T):
    """One time block of the walk from the last block to the first.
    ``after_ref`` carries the first _TAIL rows of the later block's
    ``dpre``; ``dwb_ref`` gathers ``dw`` (its first K planes) and ``db``
    over a sequence's blocks, as sums over groups of 8 rows."""
    t, nt = pl.program_id(2), pl.num_programs(2)
    block = nt - 1 - t
    rows, cols = x_ref.shape

    @pl.when(t == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    taps, bias = _taps(w_ref, b_ref, K)
    halo = _halo_rows(halo_ref, block == 0)
    strips = rows // strip

    def body(j, carry):
        after, sums = carry
        i = strips - 1 - j
        r0 = pl.multiple_of(i * strip, strip)
        window = _window(x_ref, halo, i, r0, strip)
        pre, shifted = _pre_activation(window, taps, bias, K, strip)
        sig = _sigmoid(pre)
        dpre = (dy_ref[pl.ds(r0, strip), :].astype(_F32)
                * sig * (1.0 + pre * (1.0 - sig)))
        if T % rows:
            # A block past the sequence's end holds whatever was there.
            valid = _valid_rows(block * rows + r0, strip, T)
            dpre = jnp.where(valid, dpre, 0.0)
            shifted = [jnp.where(valid, x, 0.0) for x in shifted]
        ahead = jnp.concatenate([dpre, after], axis=0)
        dx = taps[K - 1] * dpre
        for s in range(1, K):
            dx = dx + taps[K - 1 - s] * ahead[s:s + strip]
        dx_ref[pl.ds(r0, strip), :] = dx.astype(dx_ref.dtype)
        sums = tuple(
            acc + _rows8(dpre * shifted[K - 1 - k] if k < K else dpre)
            for k, acc in enumerate(sums))
        return dpre[:_TAIL], sums

    zero = jnp.zeros((8, cols), _F32)
    after, sums = lax.fori_loop(0, strips, body,
                                (after_ref[...], (zero,) * (K + 1)))
    after_ref[...] = after
    for k, acc in enumerate(sums):
        dwb_ref[k] += acc


def _gated(y_ref, z_ref, r0, strip, sl):
    """A strip of the columns ``sl``: ``y``, ``z``, ``sigmoid(z)`` and the
    gated ``g = y z sigmoid(z)``, float32."""
    y = y_ref[pl.ds(r0, strip), sl].astype(_F32)
    z = z_ref[pl.ds(r0, strip), sl].astype(_F32)
    sig = _sigmoid(z)
    return y, z, sig, y * z * sig


def _gate_parts(y_ref, z_ref, r0, strip, sl, eps):
    """A strip of one norm group: :func:`_gated` and ``1 / rms(g)``."""
    y, z, sig, g = _gated(y_ref, z_ref, r0, strip, sl)
    r = lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return y, z, sig, g, r


def _pieces(c, group):
    """The columns of the norm group that begins at ``c``, a piece of at
    most ``_MOST_COLS`` at a time: what a strip holds in registers."""
    piece = min(group, _MOST_COLS)
    return [slice(p, p + piece) for p in range(c, c + group, piece)]


def _row_sum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _gate_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, group, strip, eps):
    rows, cols = y_ref.shape

    def body(i, _):
        r0 = pl.multiple_of(i * strip, strip)
        for c in range(0, cols, group):
            pieces = _pieces(c, group)
            if len(pieces) == 1:
                _, _, _, g, r = _gate_parts(y_ref, z_ref, r0, strip,
                                            pieces[0], eps)
                out_ref[pl.ds(r0, strip), pieces[0]] = (
                    g * r * scale_ref[:, pieces[0]]).astype(out_ref.dtype)
                continue
            # A group wider than a piece: its rows' sums of squares first,
            # then each piece again (the block is in VMEM: no second read).
            ss = 0.0
            for sl in pieces:
                g = _gated(y_ref, z_ref, r0, strip, sl)[3]
                ss = ss + _row_sum(g * g)
            r = lax.rsqrt(ss * (1.0 / group) + eps)
            for sl in pieces:
                g = _gated(y_ref, z_ref, r0, strip, sl)[3]
                out_ref[pl.ds(r0, strip), sl] = (
                    g * r * scale_ref[:, sl]).astype(out_ref.dtype)
        return 0

    lax.fori_loop(0, rows // strip, body, 0)


def _gate_bwd_kernel(y_ref, z_ref, do_ref, scale_ref, dy_ref, dz_ref,
                     dscale_ref, *, group, strip, eps, T):
    """``dscale_ref`` gathers ``dscale`` over a sequence's blocks, as sums
    over groups of 8 rows."""
    t = pl.program_id(2)
    rows, cols = y_ref.shape

    @pl.when(t == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    piece = min(group, _MOST_COLS)

    def body(i, sums):
        r0 = pl.multiple_of(i * strip, strip)

        def cotangent(sl):
            return do_ref[pl.ds(r0, strip), sl].astype(_F32)

        def finish(sl, y, z, sig, n, r, do, dn, mean_dn_n):
            """``dy``, ``dz`` of the columns ``sl`` written, and their part
            of ``dscale`` added to its running sums."""
            dg = r * (dn - n * mean_dn_n)
            dy_ref[pl.ds(r0, strip), sl] = (dg * z * sig).astype(
                dy_ref.dtype)
            dz_ref[pl.ds(r0, strip), sl] = (
                dg * y * sig * (1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)
            ds = do * n
            if T % rows:
                ds = jnp.where(_valid_rows(t * rows + r0, strip, T), ds,
                               0.0)
            return sums[sl.start // piece] + _rows8(ds)

        out = []
        for c in range(0, cols, group):
            pieces = _pieces(c, group)
            if len(pieces) == 1:
                sl = pieces[0]
                y, z, sig, g, r = _gate_parts(y_ref, z_ref, r0, strip, sl,
                                              eps)
                do = cotangent(sl)
                n = g * r
                dn = do * scale_ref[:, sl]
                out.append(finish(
                    sl, y, z, sig, n, r, do, dn,
                    jnp.mean(dn * n, axis=-1, keepdims=True)))
                continue
            # A group wider than a piece: its rows' two sums first (of g^2
            # for the norm, of dn g for the norm's transpose), then each
            # piece again.
            ss = sd = 0.0
            for sl in pieces:
                g = _gated(y_ref, z_ref, r0, strip, sl)[3]
                ss = ss + _row_sum(g * g)
                sd = sd + _row_sum(cotangent(sl) * scale_ref[:, sl] * g)
            r = lax.rsqrt(ss * (1.0 / group) + eps)
            mean_dn_n = r * sd * (1.0 / group)
            for sl in pieces:
                y, z, sig, g = _gated(y_ref, z_ref, r0, strip, sl)
                do = cotangent(sl)
                out.append(finish(sl, y, z, sig, g * r, r, do,
                                  do * scale_ref[:, sl], mean_dn_n))
        return tuple(out)

    zero = jnp.zeros((8, piece), _F32)
    sums = lax.fori_loop(0, rows // strip, body, (zero,) * (cols // piece))
    for k, acc in enumerate(sums):
        dscale_ref[:, k * piece:(k + 1) * piece] += acc


# ------------------------------------------------------------- the drivers


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _conv_specs(plan: PassPlan, K, first, nt, backward=False):
    """Block specs of the convolution over (b, T, ...) arrays, grid
    (batch, channel block, time block): the _HALO rows before the packed
    array's block of ``xBC`` (its channels begin at column ``first``) and
    that block; a (b, T, channels) array's block; the taps' and the
    bias's.  ``backward``: time blocks from the last to the first."""
    rows, cols = plan.rows, plan.conv_cols
    first //= cols

    def block(t):
        return nt - 1 - t if backward else t

    halo = pl.BlockSpec(
        (None, _HALO, cols), lambda i, c, t: (
            i, jnp.maximum(block(t) * (rows // _HALO) - 1, 0), first + c))
    packed = pl.BlockSpec((None, rows, cols),
                          lambda i, c, t: (i, block(t), first + c))
    own = pl.BlockSpec((None, rows, cols),
                       lambda i, c, t: (i, block(t), c))
    taps = pl.BlockSpec((K, cols), lambda i, c, t: (0, c))
    bias = pl.BlockSpec((1, cols), lambda i, c, t: (0, c))
    return halo, packed, own, taps, bias


@functools.partial(jax.jit, inline=True,
                   static_argnames=("first", "plan", "interpret"))
def _conv_fwd(packed, w, b, *, first, plan: PassPlan, interpret):
    """``silu(conv(xBC) + b)`` (b, T, c) from columns ``[first, first +
    c)`` of ``packed``."""
    bsz, T, _ = packed.shape
    K, C = w.shape
    nt = -(-T // plan.rows)
    halo, x_spec, own, taps, bias = _conv_specs(plan, K, first, nt)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, K=K, strip=plan.strip),
        grid=(bsz, C // plan.conv_cols, nt),
        in_specs=[halo, x_spec, taps, bias],
        out_specs=own,
        out_shape=_pallas.struct((bsz, T, C), packed.dtype, packed),
        interpret=interpret, name="ssm_conv_fwd",
        **_pallas.compiler_params(interpret, _SEMANTICS),
    )(packed, packed, w.astype(_F32), b.astype(_F32).reshape(1, C))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("first", "plan", "interpret"))
def _conv_bwd(packed, w, b, dy, *, first, plan: PassPlan, interpret):
    """The cotangents of ``xBC`` (b, T, c), ``w`` and ``b``."""
    bsz, T, _ = packed.shape
    K, C = w.shape
    cols = plan.conv_cols
    nt = -(-T // plan.rows)
    halo, x_spec, own, taps, bias = _conv_specs(plan, K, first, nt,
                                                backward=True)
    sums = pl.BlockSpec((None, K + 1, 8, cols), lambda i, c, t: (i, 0, 0, c))
    dx, dwb = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, K=K, strip=plan.strip, T=T),
        grid=(bsz, C // cols, nt),
        in_specs=[halo, x_spec, own, taps, bias],
        out_specs=[own, sums],
        out_shape=[_pallas.struct((bsz, T, C), packed.dtype, packed, dy),
                   _pallas.struct((bsz, K + 1, 8, C), _F32, packed, dy)],
        scratch_shapes=[pltpu.VMEM((_TAIL, cols), _F32)],
        interpret=interpret, name="ssm_conv_bwd",
        **_pallas.compiler_params(interpret, _SEMANTICS),
    )(packed, packed, dy, w.astype(_F32), b.astype(_F32).reshape(1, C))
    dwb = dwb.sum((0, 2))
    return dx, dwb[:K].astype(w.dtype), dwb[K].astype(b.dtype)


def _gate_specs(plan: PassPlan):
    """Block specs of the gate over (b, T, ...) arrays — ``z`` is the
    packed array's first ``inner`` columns, so one spec serves both —
    and the scale's, grid (batch, channel block, time block)."""
    rows, cols = _gate_rows(plan), plan.gate_cols
    return (pl.BlockSpec((None, rows, cols), lambda i, c, t: (i, t, c)),
            pl.BlockSpec((1, cols), lambda i, c, t: (0, c)))


def _gate_rows(plan: PassPlan) -> int:
    """Time rows a block of the gate: the plan's, or as many fewer as its
    norm group is wider than ``_MOST_COLS`` (whole strips, one at least):
    a block's bytes stay what the sweep chose."""
    if plan.gate_cols <= _MOST_COLS:
        return plan.rows
    rows = plan.rows * _MOST_COLS // plan.gate_cols
    return max(rows // plan.strip, 1) * plan.strip


@functools.partial(jax.jit, inline=True,
                   static_argnames=("groups", "eps", "plan", "interpret"))
def _gate_fwd(y, packed, scale, *, groups, eps, plan: PassPlan, interpret):
    bsz, T, inner = y.shape
    own, per_channel = _gate_specs(plan)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, group=inner // groups,
                          strip=plan.strip, eps=eps),
        grid=(bsz, inner // plan.gate_cols, -(-T // _gate_rows(plan))),
        in_specs=[own, own, per_channel],
        out_specs=own,
        out_shape=_pallas.struct((bsz, T, inner), y.dtype, y, packed),
        interpret=interpret, name="ssm_gate_fwd",
        **_pallas.compiler_params(interpret, _SEMANTICS),
    )(y, packed, scale.astype(_F32).reshape(1, inner))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("groups", "eps", "plan", "interpret"))
def _gate_bwd(y, packed, scale, do, *, groups, eps, plan: PassPlan,
              interpret):
    """The cotangents of ``y``, ``z`` (b, T, inner) and ``scale``."""
    bsz, T, inner = y.shape
    own, per_channel = _gate_specs(plan)
    sums = pl.BlockSpec((None, 8, plan.gate_cols),
                        lambda i, c, t: (i, 0, c))
    like = (y, packed, do)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, group=inner // groups,
                          strip=plan.strip, eps=eps, T=T),
        grid=(bsz, inner // plan.gate_cols, -(-T // _gate_rows(plan))),
        in_specs=[own, own, own, per_channel],
        out_specs=[own, own, sums],
        out_shape=[_pallas.struct((bsz, T, inner), y.dtype, *like),
                   _pallas.struct((bsz, T, inner), packed.dtype, *like),
                   _pallas.struct((bsz, 8, inner), _F32, *like)],
        interpret=interpret, name="ssm_gate_bwd",
        **_pallas.compiler_params(interpret, _SEMANTICS),
    )(y, packed, do, scale.astype(_F32).reshape(1, inner))
    return dy, dz, dscale.sum((0, 1)).astype(scale.dtype)


def _into_columns(d, first, width):
    """``d`` (b, T, c) as columns ``[first, first + c)`` of an array
    ``width`` wide, zeros elsewhere: a cotangent of the packed array,
    which XLA sums with the other readers' as it is written."""
    return jnp.pad(d, ((0, 0), (0, 0), (first, width - first - d.shape[-1])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(packed, w, b, first, plan, interpret):
    return _conv_fwd(packed, w, b, first=first, plan=plan,
                     interpret=interpret)


def _conv_fwd_rule(packed, w, b, first, plan, interpret):
    return _conv(packed, w, b, first, plan, interpret), (packed, w, b)


def _conv_bwd_rule(first, plan, interpret, res, dy):
    packed, w, b = res
    dx, dw, db = _conv_bwd(packed, w, b, dy, first=first, plan=plan,
                           interpret=interpret)
    return _into_columns(dx, first, packed.shape[-1]), dw, db


_conv.defvjp(_conv_fwd_rule, _conv_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gate(y, packed, scale, groups, eps, plan, interpret):
    return _gate_fwd(y, packed, scale, groups=groups, eps=eps, plan=plan,
                     interpret=interpret)


def _gate_fwd_rule(y, packed, scale, groups, eps, plan, interpret):
    return (_gate(y, packed, scale, groups, eps, plan, interpret),
            (y, packed, scale))


def _gate_bwd_rule(groups, eps, plan, interpret, res, do):
    y, packed, scale = res
    dy, dz, dscale = _gate_bwd(y, packed, scale, do, groups=groups, eps=eps,
                               plan=plan, interpret=interpret)
    return dy, _into_columns(dz, 0, packed.shape[-1]), dscale


_gate.defvjp(_gate_fwd_rule, _gate_bwd_rule)


def _kernels_only(plan: PassPlan) -> None:
    if plan.form != "kernels":
        raise ValueError(f"{plan} is not the kernels': these shapes take "
                         "the caller's XLA forms")


def conv_silu(packed, w, b, *, first: int, plan: PassPlan,
              interpret: bool = False):
    """``silu(b + sum_j w_j x_{t - (K - 1) + j})`` (b, T, c) where ``x``
    is columns ``[first, first + c)`` of ``packed`` (b, T, width), ``w``
    (K, c) and ``b`` (c,): the kernels of a ``plan`` that takes them
    (:func:`passes_plan`).  Multiply-adds and the activation in float32,
    one rounding to ``packed.dtype``."""
    _kernels_only(plan)
    return _conv(packed, w, b, first, plan, interpret)


def gated_norm(y, packed, scale, *, groups: int, eps: float, plan: PassPlan,
               interpret: bool = False):
    """``rmsnorm_group(y * silu(z)) * scale`` (b, T, inner) where ``z`` is
    the first ``inner`` columns of ``packed`` (b, T, width) and a group is
    ``inner / groups`` channels: the kernels of a ``plan`` that takes
    them.  Everything inside in float32, one rounding to ``y.dtype``."""
    _kernels_only(plan)
    return _gate(y, packed, scale, groups, eps, plan, interpret)
