"""The latent's passes of compressed convolutional attention, one read and
one write of the latent each way.

Between its projections and the flash kernels, compressed convolutional
attention (``models/transformer.py`` ``CompressedConvAttention``) works on
the latent ``[q~ | k~]`` — ``H`` query heads and ``G`` key heads of ``D``
channels — with nothing but short causal row windows and one small product
a head:

* the QK-mean ``m_q[h] = (q~[h] + k~[h // g]) / 2``, ``m_k[j]`` the mean of
  its group's ``m_q`` (``g = H / G``);
* a depth-wise causal convolution of ``t0`` taps with bias (``conv0``),
  rounded to the activations' dtype (``z1``), then a causal convolution of
  ``t1`` taps that mixes the ``D`` channels of each head with a ``(D, D)``
  matrix a tap (``conv1``): one product of depth ``t1 · D`` a head;
* ``q' = z_q + m_q``, ``k' = z_k + m_k``, the L2 norm a head, ``√D`` and
  the key heads' temperature, and rotate-half positions on the first
  ``width`` channels of each head.

:func:`cca_mix` is all of that as **one Pallas kernel forward and one
backward**, a ``jax.custom_vjp`` whose residuals are its inputs only.
Operands are in the activations' dtype (bfloat16 in training) in HBM and
on the MXU; every sum, mean, norm and rotation inside is float32, with the
module's two roundings (``z1``, and the store); the parameters' gradients
are float32.

``cca_mix_fwd``, grid (sequence, time block, KV group): a grid step holds
one group's ``g`` query heads of ``q~`` and its key head of ``k~``, read
from the two projections' own arrays (no concatenate, no pad); the rows
before a block come from a 16-row halo block of the same arrays, zeros at
a sequence's start.  It works through the block a strip of rows at a
time.  ``cca_mix_bwd``, grid (sequence, KV group, time block): recomputes
``q'``, ``k'`` from ``q~``, ``k~``, goes back through the rotation and the
norm, forms the taps' cotangents ``dz2 · W1ᵀ`` on the MXU, walks the time
blocks from the last to the first with the first rows of the later
strip's tap cotangents carried in VMEM, writes ``dq~``, ``dk~`` once — the
mean's path included — and gathers ``dW1`` (float32, resident over a
sequence's walk) and ``dw0``, ``db0``, ``db1``, ``dtemp`` as 8-row partial
sums a sequence that XLA finishes.  The drivers are ``jax.jit(inline=
True)``: the layers of a stack share one trace of each kernel body.

Two forms, and one place that chooses (:func:`_plan`, a pure function of
the shapes, the dtype's width, ``interpret`` and manual mesh axes; no
option picks a form): these kernels where the rows and the heads tile,
else the module's own ``jax.numpy`` (the tiny float32 shapes of the CPU
tests, interpreted Pallas under ``shard_map``'s manual axes).
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

# Rows of the block before a time block that the convolutions read for
# their t0 + t1 - 2: a whole bfloat16 tile, of which the last _TAIL are
# kept in float32, a whole float32 tile.
_HALO = 16
_TAIL = 8

# The most rows a block and the rows a strip (the largest of these that
# divides the sequence): the best of a sweep on the chip at the cell's shape
# (PERF.md section 6, PR 49: 0.27 + 0.50 ms a layer at 1,024 x 512, 0.32 +
# 0.59 at 512 x 128, 0.46 + 1.67 at 512 x 32).  The vector unit binds the
# kernels, not bytes, and a strip's fixed work — its halo, a push of each
# head's matrices to the MXU, the carried rows and the partial sums — is
# the same at any height, so a strip is tall: 64 float32 registers an
# array, worked through VMEM.  The backward then holds 11 MB of blocks,
# inside Mosaic's default scoped-VMEM budget on every TPU, so the plan
# observes no device kind.  A group of more or wider heads than the cell's
# five of 128 holds as many fewer rows.
_ROWS = 1024
_BLOCK = _ROWS * 5 * 128
_STRIPS = (512, 256, 128, 64, 32, 16)

# What the L2 norm's sum of squares is held above (the module's).
_TINY = 1e-24


class CcaPlan(NamedTuple):
    """What :func:`_plan` decides for one layer's passes."""
    form: str            # "kernels" | "xla"
    rows: int            # time rows a block; 0 in the XLA form
    strip: int           # rows worked on at a time


def _plan(*, T, num_heads, kv_heads, head_dim, taps, itemsize, interpret,
          manual_axes) -> CcaPlan:
    """Kernels or the module's XLA form — the one place that chooses, a
    pure function of what the passes observe at trace time.

    The kernels take a time length in whole strips of rows (a strip is the
    tallest of ``_STRIPS`` that divides it), heads of whole 128-lane tiles,
    query heads in whole KV groups, both convolutions' reach (``t0 + t1 -
    2`` rows) inside the rows kept of the halo, and two-byte activations
    (the float32 of the CPU tests keeps the module's form, which is then
    exact).  A block is the most rows up to ``_ROWS``, and to ``_BLOCK``
    elements of a group's heads, that divide the sequence in whole strips.
    Interpreted Pallas under ``shard_map``'s manual axes takes the XLA form
    (:func:`_pallas.xla_form`)."""
    xla = CcaPlan("xla", 0, 0)
    t0, t1 = taps
    if (_pallas.xla_form(interpret, manual_axes) or itemsize != 2
            or T % _STRIPS[-1] or head_dim % 128 or num_heads % kv_heads
            or min(t0, t1) < 1 or t0 + t1 - 2 > _TAIL):
        return xla
    most = min(_ROWS, T, _BLOCK // ((num_heads // kv_heads + 1) * head_dim))
    strip = next(s for s in _STRIPS if T % s == 0 and s <= most)
    rows = max(r for r in range(strip, most + 1, strip) if T % r == 0)
    return CcaPlan("kernels", rows, strip)


def cca_plan(like, *, kv_heads, taps, interpret) -> CcaPlan:
    """:func:`_plan` for a layer whose query latent is, or is shaped like,
    ``like`` (B, T, H, D): what the module, ``chip_smoke.py`` and the tests
    ask."""
    _, T, H, D = like.shape
    return _plan(T=T, num_heads=H, kv_heads=kv_heads, head_dim=D,
                 taps=tuple(taps), itemsize=like.dtype.itemsize,
                 interpret=interpret,
                 manual_axes=bool(jax.typeof(like).vma))


def rotary_table(T, D, width, theta):
    """``[cos | sin]`` (T, 2 D) float32 for rotate-half positions on the
    first ``width`` channels of a head of ``D``, as ``apply_rotary``
    computes its angles: the pair ``(x[i], x[i + width/2])`` turns by ``t ·
    theta^(-2i/width)``.  Beyond ``width`` the cosine is 1 and the sine 0;
    the sine carries the sign of its half (``-sin`` on the first)."""
    half = width // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=_F32) / half)
    angle = jnp.arange(T).astype(_F32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    rest = jnp.zeros((T, D - width), _F32)
    return jnp.concatenate([cos, cos, rest + 1.0, -sin, sin, rest], axis=-1)


# ------------------------------------------------------------ the kernels


def _rows8(x):
    """The sum of ``x`` (rows, c) over its groups of 8 rows, (8, c): vreg
    adds only, as one reduction (a strip is tall: a sum written out an add
    a group made three quarters of the backward's equations); the last
    8 -> 1 is XLA's."""
    return x.reshape(x.shape[0] // 8, 8, x.shape[1]).sum(axis=0)


def _low_lanes(shape, half):
    """Which lanes hold the first element of a rotated pair."""
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) < half


def rotate(y, cos, sin, low, half, sign=1.0):
    """``y`` turned by the table's angles (``sign`` -1: turned back, the
    rotation's transpose), float32.  Lane ``i`` meets ``y[i + half]`` where
    ``low`` (``i < half``) and ``y[i - half]`` above: the other element of
    its pair (two lane rolls and a select; beyond the rotated width whatever
    the second roll brings, which the table's zero sine drops)."""
    D, lanes = y.shape[-1], y.ndim - 1
    partner = jnp.where(low, pltpu.roll(y, D - half, lanes),
                        pltpu.roll(y, half, lanes))
    return y * cos + partner * sin if sign > 0 else y * cos - partner * sin


def _halo_rows(halo_ref, cols, at_start):
    """The _TAIL rows before a block of the columns ``cols``, zeros at a
    sequence's start."""
    halo = halo_ref[:, cols].astype(_F32)[_HALO - _TAIL:]
    return jnp.where(at_start, 0.0, halo)


def _window(x_ref, cols, halo, i, r0, strip):
    """Strip ``i``'s rows of the columns ``cols`` of ``x_ref`` with the
    _TAIL rows before them on top, float32: (_TAIL + strip, D)."""
    start = pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO)
    before = x_ref[pl.ds(start, _HALO), cols]
    before = jnp.where(i == 0, halo, before.astype(_F32)[_HALO - _TAIL:])
    return jnp.concatenate(
        [before, x_ref[pl.ds(r0, strip), cols].astype(_F32)], axis=0)


def _shifted(window, reach, strip):
    """``x_s[t] = x[t - s]`` for ``s`` in ``0 .. reach``."""
    return [window[_TAIL - s:_TAIL - s + strip] for s in range(reach + 1)]


def _vectors(vec_ref, cols, t0):
    """A head's depth-wise taps (t0 of (1, D)), its two biases, and — a key
    head's only — its scale ``√D τ``, from the packed rows of ``vec_ref``."""
    v = vec_ref[:, cols]
    rows = [v[r:r + 1] for r in range(v.shape[0])]
    return rows[:t0], rows[t0], rows[t0 + 1], rows[t0 + 2:]


def _taps(xs, w0, b0, t0, t1, dtype):
    """``z1`` of a strip as the grouped convolution reads it: tap ``j``
    side by side, (strip, t1 · D) in ``dtype`` — ``c[t - (t1 - 1 - j)]``
    with ``c[t] = b0 + Σ_i w0_i x[t - (t0 - 1 - i)]`` in float32."""
    out = []
    for j in range(t1):
        back = t1 - 1 - j
        acc = w0[0] * xs[back + t0 - 1]
        for i in range(1, t0):
            acc = acc + w0[i] * xs[back + t0 - 1 - i]
        out.append((b0 + acc).astype(dtype))
    return jnp.concatenate(out, axis=-1)


def _mxu(a, b, contract=((1,), (0,))):
    """A product of operands in the activations' dtype, summed in float32."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=_F32)


def _each_strip(strips, strip, body, last_first=False):
    """``body(i, r0)`` for every strip of a block; ``last_first``: from
    the last to the first."""
    def step(j, _):
        i = strips - 1 - j if last_first else j
        body(i, pl.multiple_of(i * strip, strip))
        return 0

    lax.fori_loop(0, strips, step, 0)


def _inverse_norm(p):
    """A row's sum of squares and ``1 / ‖p‖₂``."""
    ss = jnp.sum(p * p, axis=-1, keepdims=True)
    return ss, lax.rsqrt(jnp.maximum(ss, _TINY))


def _group(qh_ref, kh_ref, vq_ref, vk_ref, at_start, g, D, t0):
    """What a grid step's strips share: the columns of the group's ``g``
    query heads (the key head's are ``one``), the halos' kept rows and the
    packed vectors of each, the key head's last."""
    heads = [slice(h * D, (h + 1) * D) for h in range(g)]
    one = slice(0, D)
    return (heads, one,
            [_halo_rows(qh_ref, c, at_start) for c in heads],
            _halo_rows(kh_ref, one, at_start),
            [_vectors(vq_ref, cols, t0) for cols in heads],
            _vectors(vk_ref, one, t0))


def _fwd_kernel(qh_ref, q_ref, kh_ref, k_ref, vq_ref, vk_ref, w1q_ref,
                w1k_ref, rot_ref, qo_ref, ko_ref, *, g, D, t0, t1, half,
                strip):
    reach = t0 + t1 - 2
    heads, one, halos, halo_k, vec_q, vec_k = _group(
        qh_ref, kh_ref, vq_ref, vk_ref, pl.program_id(1) == 0, g, D, t0)
    w0k, b0k, b1k, (scale_k,) = vec_k
    scale_q = float(D) ** 0.5
    low = _low_lanes((strip, D), half)

    def body(i, r0):
        rows = pl.ds(r0, strip)
        cos, sin = rot_ref[rows, :D], rot_ref[rows, D:]
        xk = _shifted(_window(k_ref, one, halo_k, i, r0, strip), reach,
                      strip)
        mean_k = 0.0
        for h, cols in enumerate(heads):
            xs = _shifted(_window(q_ref, cols, halos[h], i, r0, strip),
                          reach, strip)
            w0, b0, b1, _ = vec_q[h]
            mean = 0.5 * (xs[0] + xk[0])
            mean_k = mean_k + mean
            p = _mxu(_taps(xs, w0, b0, t0, t1, q_ref.dtype),
                     w1q_ref[h]) + b1 + mean
            _, r = _inverse_norm(p)
            qo_ref[rows, cols] = rotate(p * (r * scale_q), cos, sin, low,
                                        half).astype(qo_ref.dtype)
        p = _mxu(_taps(xk, w0k, b0k, t0, t1, k_ref.dtype),
                 w1k_ref[...]) + b1k + mean_k * (1.0 / g)
        _, r = _inverse_norm(p)
        ko_ref[rows, :] = rotate(p * (r * scale_k), cos, sin, low,
                                 half).astype(ko_ref.dtype)

    _each_strip(q_ref.shape[0] // strip, strip, body)


def _bwd_kernel(qh_ref, q_ref, kh_ref, k_ref, dqo_ref, dko_ref, vq_ref,
                vk_ref, w1q_ref, w1k_ref, w1qT_ref, w1kT_ref, rot_ref,
                dq_ref, dk_ref, sq_ref, sk_ref, dw1q_ref, dw1k_ref,
                after_ref, *, g, D, t0, t1, half, strip):
    """One time block of the walk from the last block to the first.
    ``after_ref`` (g + 1, reach, _TAIL, D) carries the first rows of the
    later strip's ``e_s`` (below) a head; ``sq_ref`` / ``sk_ref`` gather
    ``dw0`` (their first t0 planes), ``db0``, ``db1`` and — the key head's
    — ``dtemp / √D τ`` over a sequence's blocks as sums over groups of 8
    rows; ``dw1q_ref`` / ``dw1k_ref`` gather ``dW1`` whole."""
    t, nt = pl.program_id(2), pl.num_programs(2)
    reach = t0 + t1 - 2

    @pl.when(t == 0)
    def _():
        for ref in (after_ref, sq_ref, sk_ref, dw1q_ref, dw1k_ref):
            ref[...] = jnp.zeros_like(ref)

    heads, one, halos, halo_k, vec_q, vec_k = _group(
        qh_ref, kh_ref, vq_ref, vk_ref, t == nt - 1, g, D, t0)
    scale_q = float(D) ** 0.5
    dtype = q_ref.dtype
    low = _low_lanes((strip, D), half)

    def to_operand(xs, vec, w1, mean, do, cos, sin, scale):
        """A head's ``z1`` taps again, and the cotangent of the norm's
        operand ``p`` from the output's: back through the rotation, the
        scale and ``p / ‖p‖₂`` — ``dp = c1 dy - c2 p`` with ``c1 = scale /
        ‖p‖`` and ``c2 = c1 Σ(dy p) / ‖p‖²`` a row (0 where the norm was
        held above nothing).  Also ``dy p / ‖p‖``, which sums to the
        cotangent of the scale."""
        w0, b0, b1, _ = vec
        taps = _taps(xs, w0, b0, t0, t1, dtype)
        p = _mxu(taps, w1) + b1 + mean
        ss, r = _inverse_norm(p)
        dy = rotate(do, cos, sin, low, half, sign=-1.0)
        dyp = dy * p
        c1 = r * scale
        c2 = jnp.where(ss > _TINY, c1 * (r * r), 0.0) * jnp.sum(
            dyp, axis=-1, keepdims=True)
        return taps, c1 * dy - c2 * p, dyp * r

    def through_convs(slot, xs, vec, taps, w1T, dp, sums_ref, cols,
                      dw1_ref, dw1_at):
        """``dx`` of the convolutions' path for a strip, with the strip's
        part of the parameters' gradients added to their running sums and
        the rows the earlier strip needs left in ``after_ref[slot]``.
        e_s[t] = Σ w0_i dtap_j[t] over the (i, j) whose taps reach s rows
        back: dx[u] = Σ_s e_s[u + s]."""
        w0 = vec[0]
        dz2 = dp.astype(dtype)
        dw1_ref[dw1_at] += _mxu(taps, dz2, ((0,), (0,)))
        dtaps = _mxu(dz2, w1T)
        dtap = [dtaps[:, j * D:(j + 1) * D] for j in range(t1)]
        e = [0.0] * (reach + 1)
        dw0 = [0.0] * t0
        for j in range(t1):
            for i in range(t0):
                s = (t1 - 1 - j) + (t0 - 1 - i)
                e[s] = e[s] + w0[i] * dtap[j]
                dw0[i] = dw0[i] + dtap[j] * xs[s]
        dx = e[0]
        for s in range(1, reach + 1):
            ahead = jnp.concatenate([e[s], after_ref[slot, s - 1]], axis=0)
            dx = dx + ahead[s:s + strip]
            after_ref[slot, s - 1] = e[s][:_TAIL]
        for k, part in enumerate(dw0 + [sum(dtap[1:], dtap[0]), dp]):
            sums_ref[k, :, cols] += _rows8(part)
        return dx

    def body(i, r0):
        rows = pl.ds(r0, strip)
        cos, sin = rot_ref[rows, :D], rot_ref[rows, D:]
        xk = _shifted(_window(k_ref, one, halo_k, i, r0, strip), reach,
                      strip)
        means = [0.5 * (q_ref[rows, cols].astype(_F32) + xk[0])
                 for cols in heads]
        mean_k = sum(means[1:], means[0]) * (1.0 / g)
        # The key head as far as its norm's operand: the query heads'
        # inputs read its cotangent through the mean.
        taps_k, dp_k, dscale = to_operand(
            xk, vec_k, w1k_ref[...], mean_k, dko_ref[rows, :].astype(_F32),
            cos, sin, vec_k[3][0])
        sk_ref[t0 + 2, :, :] += _rows8(dscale)
        dp_sum = dp_k
        for h, cols in enumerate(heads):
            xs = _shifted(_window(q_ref, cols, halos[h], i, r0, strip),
                          reach, strip)
            taps, dp, _ = to_operand(
                xs, vec_q[h], w1q_ref[h], means[h],
                dqo_ref[rows, cols].astype(_F32), cos, sin, scale_q)
            dp_sum = dp_sum + dp
            dx = through_convs(h, xs, vec_q[h], taps, w1qT_ref[h], dp,
                               sq_ref, cols, dw1q_ref, h)
            # The mean's path: m_q[h] reads q~[h] by half, m_k by 1 / 2g.
            dq_ref[rows, cols] = (dx + 0.5 * dp + (0.5 / g) * dp_k).astype(
                dq_ref.dtype)
        dx = through_convs(g, xk, vec_k, taps_k, w1kT_ref[...], dp_k, sk_ref,
                           one, dw1k_ref, slice(None))
        dk_ref[rows, :] = (dx + 0.5 * dp_sum).astype(dk_ref.dtype)

    _each_strip(q_ref.shape[0] // strip, strip, body, last_first=True)


# ------------------------------------------------------------- the drivers


def _operands(q0, k0, w0, b0, w1, b1, temp):
    """The arrays as the kernels read them: the latents with their heads
    merged; a side's per-channel vectors packed by rows (the t0 depth-wise
    taps, ``b0``, ``b1`` and — keys — ``√D τ`` a head), float32; the grouped
    convolution's matrices a head at depth ``t1 · D``, in the latents'
    dtype."""
    B, T, H, D = q0.shape
    G = k0.shape[2]
    t1 = w1.shape[1]
    vec = jnp.concatenate([w0.astype(_F32).T, b0.astype(_F32)[None],
                           b1.astype(_F32).reshape(1, -1)], axis=0)
    scale = jnp.broadcast_to(
        (float(D) ** 0.5 * temp.astype(_F32))[:, None], (G, D))
    vec_k = jnp.concatenate([vec[:, H * D:], scale.reshape(1, G * D)],
                            axis=0)
    w1 = w1.reshape(H + G, t1 * D, D).astype(q0.dtype)
    return (q0.reshape(B, T, H * D), k0.reshape(B, T, G * D),
            vec[:, :H * D], vec_k, w1[:H], w1[H:])


def _latent_specs(plan: CcaPlan, g, D, block):
    """Block specs of a (B, T, heads · D) array's halo and block — a
    group's ``g`` query heads, or its key head — for a grid whose ids
    ``block`` turns into (sequence, time block, group)."""
    rows = plan.rows

    def spec(height, width, at):
        return pl.BlockSpec((None, height, width), lambda *ids: at(
            *block(*ids)))

    def halo(b, t, j):
        return b, jnp.maximum(t * (rows // _HALO) - 1, 0), j

    return (spec(_HALO, g * D, halo), spec(rows, g * D, lambda *at: at),
            spec(_HALO, D, halo), spec(rows, D, lambda *at: at))


def _shared_specs(vq, vk, g, D, t1, block):
    """Block specs of a group's packed vectors, of its convolution
    matrices (t1 · D, D), and of those turned (D, t1 · D)."""
    def group(*ids):
        return block(*ids)[2]

    def matrices(*shape):
        return [pl.BlockSpec((g,) + shape, lambda *ids: (group(*ids), 0, 0)),
                pl.BlockSpec((None,) + shape,
                             lambda *ids: (group(*ids), 0, 0))]

    return ([pl.BlockSpec((vq.shape[0], g * D),
                          lambda *ids: (0, group(*ids))),
             pl.BlockSpec((vk.shape[0], D), lambda *ids: (0, group(*ids)))]
            + matrices(t1 * D, D), matrices(D, t1 * D))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("rope", "plan", "interpret"))
def _mix_fwd(q0, k0, w0, b0, w1, b1, temp, *, rope, plan: CcaPlan,
             interpret):
    B, T, H, D = q0.shape
    G, t0, t1 = k0.shape[2], w0.shape[1], w1.shape[1]
    g, theta, width = H // G, *rope
    q, k, vq, vk, w1q, w1k = _operands(q0, k0, w0, b0, w1, b1, temp)

    def block(b, t, j):
        return b, t, j

    qh, qb, kh, kb = _latent_specs(plan, g, D, block)
    rot = pl.BlockSpec((plan.rows, 2 * D), lambda b, t, j: (t, 0))
    qo, ko = pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, D=D, t0=t0, t1=t1,
                          half=width // 2, strip=plan.strip),
        grid=(B, T // plan.rows, G),
        in_specs=[qh, qb, kh, kb,
                  *_shared_specs(vq, vk, g, D, t1, block)[0], rot],
        out_specs=[qb, kb],
        out_shape=[_pallas.struct(q.shape, q.dtype, q0, k0),
                   _pallas.struct(k.shape, k.dtype, q0, k0)],
        interpret=interpret, name="cca_mix_fwd",
        **_pallas.compiler_params(interpret, ("parallel",) * 3),
    )(q, q, k, k, vq, vk, w1q, w1k, rotary_table(T, D, width, theta))
    return qo.reshape(q0.shape), ko.reshape(k0.shape)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("rope", "plan", "interpret"))
def _mix_bwd(q0, k0, w0, b0, w1, b1, temp, dqo, dko, *, rope,
             plan: CcaPlan, interpret):
    """The cotangents of ``q0``, ``k0``, ``w0``, ``b0``, ``w1``, ``b1`` and
    ``temp``."""
    B, T, H, D = q0.shape
    G, t0, t1 = k0.shape[2], w0.shape[1], w1.shape[1]
    g, theta, width = H // G, *rope
    nt = T // plan.rows
    q, k, vq, vk, w1q, w1k = _operands(q0, k0, w0, b0, w1, b1, temp)

    def block(b, j, t):
        return b, nt - 1 - t, j

    qh, qb, kh, kb = _latent_specs(plan, g, D, block)
    rot = pl.BlockSpec((plan.rows, 2 * D), lambda b, j, t: (nt - 1 - t, 0))
    shared, turned = _shared_specs(vq, vk, g, D, t1, block)
    nq, nk = t0 + 2, t0 + 3
    sums = [pl.BlockSpec((None, nq, 8, g * D), lambda b, j, t: (b, 0, 0, j)),
            pl.BlockSpec((None, nk, 8, D), lambda b, j, t: (b, 0, 0, j)),
            pl.BlockSpec((None, g, t1 * D, D), lambda b, j, t: (b, j, 0, 0)),
            pl.BlockSpec((None, None, t1 * D, D),
                         lambda b, j, t: (b, j, 0, 0))]
    like = (q0, k0, dqo, dko)
    dq, dk, sq, sk, dw1q, dw1k = pl.pallas_call(
        functools.partial(_bwd_kernel, g=g, D=D, t0=t0, t1=t1,
                          half=width // 2, strip=plan.strip),
        grid=(B, G, nt),
        in_specs=[qh, qb, kh, kb, qb, kb, *shared, *turned, rot],
        out_specs=[qb, kb, *sums],
        out_shape=[_pallas.struct(q.shape, q.dtype, *like),
                   _pallas.struct(k.shape, k.dtype, *like),
                   _pallas.struct((B, nq, 8, H * D), _F32, *like),
                   _pallas.struct((B, nk, 8, G * D), _F32, *like),
                   _pallas.struct((B, H, t1 * D, D), _F32, *like),
                   _pallas.struct((B, G, t1 * D, D), _F32, *like)],
        scratch_shapes=[pltpu.VMEM(
            (g + 1, max(t0 + t1 - 2, 1), _TAIL, D), _F32)],
        interpret=interpret, name="cca_mix_bwd",
        **_pallas.compiler_params(interpret,
                                  ("parallel", "parallel", "arbitrary")),
    )(q, q, k, k, dqo.reshape(q.shape), dko.reshape(k.shape), vq, vk,
      w1q, w1k, w1q.swapaxes(1, 2), w1k.swapaxes(1, 2),
      rotary_table(T, D, width, theta))
    sq, sk = sq.sum((0, 2)), sk.sum((0, 2))
    per_channel = jnp.concatenate([sq, sk[:nq]], axis=1)
    dw1 = jnp.concatenate([dw1q.sum(0), dw1k.sum(0)], axis=0)
    dtemp = float(D) ** 0.5 * sk[nq].reshape(G, D).sum(-1)
    return (dq.reshape(q0.shape), dk.reshape(k0.shape),
            per_channel[:t0].T.astype(w0.dtype),
            per_channel[t0].astype(b0.dtype),
            dw1.reshape(w1.shape).astype(w1.dtype),
            per_channel[t0 + 1].reshape(b1.shape).astype(b1.dtype),
            dtemp.astype(temp.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _mix(q0, k0, w0, b0, w1, b1, temp, rope, plan, interpret):
    return _mix_fwd(q0, k0, w0, b0, w1, b1, temp, rope=rope, plan=plan,
                    interpret=interpret)


def _mix_fwd_rule(q0, k0, w0, b0, w1, b1, temp, rope, plan, interpret):
    return (_mix(q0, k0, w0, b0, w1, b1, temp, rope, plan, interpret),
            (q0, k0, w0, b0, w1, b1, temp))


def _mix_bwd_rule(rope, plan, interpret, res, cotangents):
    return _mix_bwd(*res, *cotangents, rope=rope, plan=plan,
                    interpret=interpret)


_mix.defvjp(_mix_fwd_rule, _mix_bwd_rule)


def cca_mix(q0, k0, w0, b0, w1, b1, temp, *, rope_theta: float,
            rotary_width: int, plan: CcaPlan, interpret: bool = False):
    """``q"`` (B, T, H, D) and ``k"`` (B, T, G, D) as the flash kernels read
    them, from the projections' ``q~`` (B, T, H, D) and ``k~`` (B, T, G,
    D): the QK-mean, the depth-wise convolution (``w0`` ((H + G) D, t0),
    ``b0``), the grouped one (``w1`` (H + G, t1, D, D), ``b1`` (H + G, D)),
    the L2 norm with ``√D`` and the key heads' ``temp`` (G,), and
    rotate-half positions (base ``rope_theta``) on the first
    ``rotary_width`` channels of each head — the kernels of a ``plan``
    that takes them (:func:`cca_plan`)."""
    if plan.form != "kernels":
        raise ValueError(f"{plan} is not the kernels': these shapes take "
                         "the module's XLA form")
    return _mix(q0, k0, w0, b0, w1, b1, temp,
                (float(rope_theta), int(rotary_width)), plan, interpret)
