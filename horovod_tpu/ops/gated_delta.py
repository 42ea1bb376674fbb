"""The gated delta rule of linear attention, computed in chunks.

Per head, with keys and queries ``d_k`` wide (the caller normalises
them), values ``d_v`` wide, a decay ``alpha_t = exp(g_t)`` (``g_t <= 0``)
and a step ``beta_t``, the state ``S`` (d_v, d_k) starts at zero and

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

(Yang et al., Gated Delta Networks, arXiv:2412.06464; ``beta`` may pass 1,
up to 2, so that the transition has negative eigenvalues: Grazzi et al.,
arXiv:2411.12537).  The transition is a matrix, not Mamba-2's scalar
decay (``ops/ssd.py``), so a chunk is no running sum of products: the
tokens of a chunk see each other's corrections, and the chunk's rows
have to be solved together before any product with the state is made.

:func:`gated_delta_rule` never steps token by token and never forms a
(T, T) matrix.  In chunks of ``C`` tokens, with ``gamma_i`` the running
sum of ``g`` inside the chunk and ``Gamma_ij = exp(gamma_i - gamma_j)``
for ``i >= j`` (formed from the difference: ``exp(gamma_i) exp(-gamma_j)``
overflows),

* scope ``solve``: ``A = tril(diag(beta) (Gamma o K K^T), -1)`` and
  ``T = (I + A)^-1 diag(beta)`` — the WY form; ``I + A`` is unit lower
  triangular and ``A`` nilpotent, so the inverse is the finite product
  ``(I - A)(I + A^2)(I + A^4)...`` (:func:`unit_lower_inverse`), in
  float32 at full precision — then ``W = T (exp(gamma) o K)``, ``U = T V``;
* scope ``states``: the one sequential part, T/C steps: from the state
  ``S`` entering a chunk, ``V' = U - W S^T`` and the state leaving it
  ``S <- exp(gamma_C) S + V'^T (exp(gamma_C - gamma) o K)``, carried in
  float32;
* scope ``inter``: what the entering state gives the chunk's tokens,
  ``(exp(gamma) o Q) S^T``;
* scope ``intra``: what the chunk's own tokens give, ``tril(Gamma o Q
  K^T) V'``.

Matmul operands are in ``v.dtype`` (bfloat16 in training); what is summed
after a product (``V'``, the state, ``o``) is summed in float32, and
``g``, ``gamma``, ``Gamma``, ``beta``, the solve and the carried state
are float32.  The answer does not depend on ``C``.

**A decay a key channel** (Kimi Delta Attention, Kimi Linear technical
report, arXiv:2510.26692): ``g`` of rank 4, (b, T, H, d_k), makes the
decay a diagonal matrix on the key side,

    S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T

and the same WY form holds with ``gamma`` (C, d_k) a running sum a
CHANNEL: ``W = T (K o exp(gamma))``, ``V' = U - W S^T``, ``S <- S
Diag(exp(gamma_C)) + V'^T (K o exp(gamma_C - gamma))``, the read-out
``(Q o exp(gamma)) S^T`` — every exponent a sum of ``g`` over later or
earlier tokens, none positive.  What changes is the two (C, C) tiles: the
decay sits INSIDE the contraction, ``A_ij = beta_i sum_d k_id exp(gamma_id
- gamma_jd) k_jd``, and neither ``(K o exp(gamma)) (K o exp(-gamma))^T``
(overflows: a channel at ``g = -20`` a token passes float32's range
inside a chunk) nor a (C, C, d_k) array (``d_k`` times the bytes) will
do.  :func:`_halved_tiles` forms them from sub-chunks: the chunk is halved
``log2 C`` times, and at the level whose halves are ``s`` tokens long the
pairs ``(i, j)`` with ``i`` in the SECOND half of a block of ``2 s`` and
``j`` in its FIRST half — each pair ``i > j`` belongs to exactly one level —
take both factors against the reference row ``r``, the last row of the
first half, which lies between them (``j <= r < i``):

    exp(gamma_i - gamma_j) = exp(gamma_i - gamma_r) exp(gamma_r - gamma_j)

with both exponents sums of ``g`` over ``(r, i]`` and ``(j, r]`` — summed
from ``g`` itself, not taken as differences of running sums, so never
positive and with nothing to cancel, in the gradient of ``g`` either: a
factor underflows only where the product does.  A level is one product of
``K o E_s`` (or ``Q o E_s``) with ``(K o E_s)^T``, ``E_s = exp(-|gamma -
gamma_r|)`` a row, masked to its pairs; the diagonal sub-blocks that are
left are single tokens, ``q_i . k_i`` with no decay, formed directly.  No
(T, T) and no (C, C, d_k) array is formed, ``exp`` is never raised to a
positive power, and the chunk (a power of two) changes nothing.  Float32:
``g``, its sums, every ``E_s`` before it multiplies an operand, the tiles'
sums, the solve, the carried state; matmul operands ``v.dtype``.

Two forms of the rank-4 tiles, and one place that chooses
(:func:`delta_plan`, a pure function of the chunk, the keys' width, the
operands' bytes, ``interpret`` and manual mesh axes; no option picks one):

* **Pallas TPU kernels** (``kda_tiles_fwd``, ``kda_tiles_bwd``; form
  ``"tile_kernels"``) where the shapes tile — keys in whole 128-lane
  tiles, chunks of 64 or 128: the ``kimilinear_1chip`` cell.  A grid step
  holds a few chunks of one head's ``q``, ``k`` and ``g`` in VMEM, read as
  column ranges of the (b, T, H d_k) arrays as they stand; a chunk at a
  time it makes every level's exponent from ``g`` by float32 ADDS (halves'
  totals exchanged between siblings, level by level: sums only, never a
  difference), ``exp(min(., 0))`` of them, a level's two products as one
  with float32 accumulation, masks and adds them in VMEM, and writes what
  the solve, the pass and the read-outs are handed and nothing a level
  made: the two (C, C) tiles, ``K o exp(gamma)``, ``K o exp(gamma_C -
  gamma)``, ``Q o exp(gamma)`` and ``gamma_C``.  The backward kernel reads
  the same three inputs and those six cotangents, makes the levels again
  and writes ``dq``, ``dk``, ``dg``: the pair is a ``jax.custom_vjp`` whose
  residuals are its inputs, so every chunk's tiles are made at once, with
  no groups.  The inverse, ``W``, ``U``, the sequential pass and the
  read-outs are the XLA text below, under the same scopes (the kernels
  under ``solve``).
* **plain XLA** (:func:`_halved_tiles`; form ``"xla_chunked_halved"``)
  otherwise — heads narrower than a lane tile (the CPU rehearsal's 16),
  interpreted Pallas under ``shard_map``'s manual axes — and as the
  kernels' oracle.  Differentiated as it stands a level keeps a float32
  factor, two scaled operands and two products a chunk, so what comes
  before the sequential pass is made for ``_TILE_GROUP_ELEMENTS`` of a
  call's chunks at a time and again in the backward pass, which keeps the
  chunked ``q``, ``k``, ``v``, ``g``, ``beta`` and what the pass and the
  read-outs are handed (``W``, ``U``, the decayed ``k`` and ``q``, the
  ``Q K^T`` tile).

The rank-3 rule is plain XLA, differentiated as it stands; in every form
the solve has its own rule, ``-T^T dT T^T``, so that the powers of ``A``
are not kept, and a caller at training sizes wraps the rule in a
``jax.checkpoint``, as
:class:`~horovod_tpu.models.linear_attention.GatedDeltaNet` and
:class:`~horovod_tpu.models.linear_attention.KimiDeltaAttention` do.  The
rank of ``g`` picks the body and nothing else does; a rank-3 call lowers
to the text it lowered to before the second body was written
(``tests/test_kimi_program.py``).

:func:`gated_delta_recurrence` is the definition, token by token in
float32, for tests at small sizes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


class DeltaPlan(NamedTuple):
    """How :func:`gated_delta_rule` runs: ``form`` — ``"xla_chunked"``
    for a decay a head (``g`` of rank 3); for a decay a key channel (rank
    4) ``"tile_kernels"`` (the tiles from halved sub-chunks made in VMEM,
    ``chunks_a_step`` chunks of one head a grid step) or
    ``"xla_chunked_halved"`` (the same tiles by plain XLA) — and the chunk
    length."""
    form: str
    chunk: int
    chunks_a_step: int = 0


# The most chunks of one head that a grid step of the tile kernels holds
# (timed alone at 1, 2, 4, 8 and 16 chunks of 64 on a v5e, PERF.md §6
# "PR 63").
_CHUNKS_A_STEP = 8


def delta_plan(chunk: int = 64, g_rank: int = 3, *, seq_len: int = 0,
               key_dim: int = 0, itemsize: int = 2, interpret: bool = False,
               manual_axes: bool = False) -> DeltaPlan:
    """The form a call takes, a pure function of what the op observes at
    trace time; no option picks another.  The rank of ``g`` chooses the
    body.  Inside rank 4 the tile kernels take a shape that tiles: a chunk
    of 64 or 128 tokens (a chunk's two cotangent tiles side by side fill
    whole 128-lane tiles; at 256 the levels' masks alone pass Mosaic's
    default scoped VMEM) and keys in whole 128-lane tiles; anything else — the CPU rehearsal's heads of 16, interpreted
    Pallas under ``shard_map``'s manual axes (:func:`_pallas.xla_form`) —
    stands down to the XLA form.  ``chunks_a_step``: the largest power of
    two up to ``_CHUNKS_A_STEP`` that divides the call's chunks."""
    if g_rank == 3:
        return DeltaPlan("xla_chunked", chunk)
    tiles = (chunk in (64, 128) and key_dim > 0 and key_dim % 128 == 0
             and itemsize in (2, 4))
    if not tiles or _pallas.xla_form(interpret, manual_axes):
        return DeltaPlan("xla_chunked_halved", chunk)
    chunks = max(1, -(-seq_len // chunk))
    per = _CHUNKS_A_STEP
    while chunks % per:
        per //= 2
    return DeltaPlan("tile_kernels", chunk, per)


def delta_sizes(batch: int, seq_len: int, heads: int, key_dim: int,
                value_dim: int, chunk: int, g_rank: int = 3) -> dict:
    """What one call passes between chunks, from shapes: the chunks a
    head walks, the bytes of float32 states entering them, the float32
    bytes of per-channel log-decays the call keeps (``g`` of rank 4, its
    padded tokens counted; 0 for a decay a head) and the pairs of
    sub-chunks whose products make a head's tiles (``chunk - 1`` a chunk:
    1 + 2 + ... + chunk / 2; 0 for a decay a head)."""
    chunks = batch * -(-seq_len // chunk)
    per_channel = g_rank == 4
    return {"chunks": chunks,
            "state_bytes": chunks * heads * value_dim * key_dim * 4,
            "decay_bytes": (chunks * chunk * heads * key_dim * 4
                            if per_channel else 0),
            "sub_chunks": chunks * (chunk - 1) if per_channel else 0}


def _finite_neumann(A):
    C = A.shape[-1]
    power = -A
    inverse = jnp.eye(C, dtype=A.dtype) + power
    for _ in range(max(C - 1, 1).bit_length() - 1):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    return inverse


@jax.custom_vjp
def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` (..., C, C),
    float32: ``A^C = 0``, so the Neumann series ends and factors into
    ``(I - A)(I + A^2)(I + A^4)...``, ``ceil(log2 C)`` factors.  Its
    backward rule is the inverse's own, ``-T^T dT T^T``: the powers of
    ``A`` are not kept."""
    return _finite_neumann(A)


def _unit_lower_inverse_fwd(A):
    inverse = _finite_neumann(A)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, g, precision=_HIGHEST), t,
                        precision=_HIGHEST),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, interpret=None):
    """``o`` (b, T, H, d_v) of the module docstring's recurrence for ``q``,
    ``k`` (b, T, H, d_k), ``v`` (b, T, H, d_v), ``beta`` (b, T, H) and
    ``g`` (b, T, H) — a decay a head — or (b, T, H, d_k) — a decay a key
    channel, in chunks that are a power of two —, each sequence from a
    zero state.  A ``T`` that is no multiple of ``chunk`` is padded with
    tokens that change nothing (``beta`` 0, ``g`` 0).  Which form a rank-4
    call takes follows its shapes (:func:`delta_plan`) and is not an
    option; ``interpret`` (None: off the TPU) runs its kernels
    interpreted."""
    if g.ndim == 4:
        if interpret is None:
            interpret = _pallas.interpret()
        return _per_channel_rule(q, k, v, g, beta,
                                 rule_plan(q, g, chunk, interpret), interpret)
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    nc = (T + pad) // C
    dtype = v.dtype

    def chunked(a):                  # (b, T, H, ...) -> (b, nc, H, C, ...)
        a = a.reshape(b, nc, C, *a.shape[2:])
        return jnp.moveaxis(a, 2, 3)

    qc, kc, vc = chunked(q), chunked(k), chunked(v)
    gc, bc = chunked(g.astype(_F32)), chunked(beta.astype(_F32))
    causal = jnp.tril(jnp.ones((C, C), bool))
    # The running sum over a chunk as a product with a triangle of ones at
    # full precision, as ops/ssd.py has it (a cumsum lowers to a
    # reduce-window on the chip).
    gamma = jnp.einsum("bnhs,ts->bnht", gc, causal.astype(_F32),
                       precision=_HIGHEST)
    total = gamma[..., -1]                                   # (b, nc, H)
    Gamma = jnp.exp(jnp.where(
        causal, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))

    with jax.named_scope("solve"):
        kk = jnp.einsum("bnhid,bnhjd->bnhij", kc, kc,
                        preferred_element_type=_F32)
        A = jnp.where(jnp.tril(causal, -1),
                      bc[..., :, None] * Gamma * kk, 0.0)
        Tm = (unit_lower_inverse(A) * bc[..., None, :]).astype(dtype)
        k_in = (kc.astype(_F32) * jnp.exp(gamma)[..., None]).astype(dtype)
        W = jnp.einsum("bnhij,bnhjd->bnhid", Tm, k_in)
        U = jnp.einsum("bnhij,bnhjv->bnhiv", Tm, vc)

    with jax.named_scope("states"):
        k_out = (kc.astype(_F32)
                 * jnp.exp(total[..., None] - gamma)[..., None]).astype(dtype)

        def step(S, chunk_in):       # S (b, H, d_v, d_k), float32
            W_c, U_c, k_c, total_c = chunk_in
            entering = S.astype(dtype)
            v_new = (U_c.astype(_F32) - jnp.einsum(
                "bhid,bhvd->bhiv", W_c, entering,
                preferred_element_type=_F32)).astype(dtype)
            S = jnp.exp(total_c)[..., None, None] * S + jnp.einsum(
                "bhiv,bhid->bhvd", v_new, k_c, preferred_element_type=_F32)
            return S, (entering, v_new)

        start = jnp.broadcast_to(          # varies as the operands do
            jnp.zeros_like(total[:, 0])[..., None, None], (b, H, dv, dk))
        _, (entering, v_new) = lax.scan(
            step, start,
            tuple(jnp.moveaxis(a, 1, 0) for a in (W, U, k_out, total)))
        entering = jnp.moveaxis(entering, 0, 1)     # (b, nc, H, d_v, d_k)
        v_new = jnp.moveaxis(v_new, 0, 1)           # (b, nc, H, C, d_v)

    with jax.named_scope("inter"):
        q_in = (qc.astype(_F32) * jnp.exp(gamma)[..., None]).astype(dtype)
        o = jnp.einsum("bnhid,bnhvd->bnhiv", q_in, entering,
                       preferred_element_type=_F32)

    with jax.named_scope("intra"):
        qk = jnp.einsum("bnhid,bnhjd->bnhij", qc, kc,
                        preferred_element_type=_F32)
        o = o + jnp.einsum("bnhij,bnhjv->bnhiv", (Gamma * qk).astype(dtype),
                           v_new, preferred_element_type=_F32)

    o = jnp.moveaxis(o, 3, 2).reshape(b, T + pad, H, dv)
    return o[:, :T].astype(dtype)


def _halving_masks(C: int):
    """``[(s, mask)]`` for the half lengths ``s = C/2, C/4, ..., 1``:
    ``mask`` (C, C) holds the pairs ``(i, j)`` with ``i`` in the second
    half and ``j`` in the first half of one block of ``2 s`` rows.  The
    masks part the strict lower triangle."""
    i = np.arange(C)
    out, s = [], C // 2
    while s:
        second = (i // s) % 2 == 1
        out.append((s, (i[:, None] // (2 * s) == i[None, :] // (2 * s))
                    & second[:, None] & ~second[None, :]))
        s //= 2
    return out


def _sums(g, ones):
    """``ones`` (t, s) of 0 and 1 applied to the token axis of ``g`` (...,
    s, d_k): sums of log-decays, as a product at full precision (a cumsum
    lowers to a reduce-window on the chip)."""
    return jnp.einsum("ts,...sd->...td", jnp.asarray(ones, _F32), g,
                      precision=_HIGHEST)


def _halved_tiles(qc, kc, gc, dtype):
    """``sum_d k_id exp(gamma_id - gamma_jd) k_jd`` for ``i > j`` and
    ``sum_d q_id exp(gamma_id - gamma_jd) k_jd`` for ``i >= j``, (..., C,
    C) float32 and zero elsewhere, for ``gc`` (..., C, d_k) the log-decays
    of a chunk: the module docstring's halving.  At the level of half
    length ``s`` a row's exponent ``-|gamma - gamma_r|`` is summed from
    ``g`` itself — over the rows behind it in a first half, over the rows
    up to it in a second half — and never taken as a difference of two
    running sums, forward or backward."""
    C, dk = gc.shape[-2:]
    lead = gc.shape[:-2]
    qk = jnp.eye(C, dtype=_F32) * jnp.einsum(
        "...id,...id->...i", qc, kc, preferred_element_type=_F32)[..., None]
    kk = jnp.zeros_like(qk)
    for s, mask in _halving_masks(C):
        halves = gc.reshape(*lead, C // (2 * s), 2, s, dk)
        E = jnp.exp(jnp.minimum(jnp.stack(
            [_sums(halves[..., 0, :, :], np.triu(np.ones((s, s)), 1)),
             _sums(halves[..., 1, :, :], np.tril(np.ones((s, s))))],
            axis=-3).reshape(gc.shape), 0.0))
        ks = (kc.astype(_F32) * E).astype(dtype)
        qs = (qc.astype(_F32) * E).astype(dtype)
        kk = kk + jnp.where(mask, jnp.einsum(
            "...id,...jd->...ij", ks, ks, preferred_element_type=_F32), 0.0)
        qk = qk + jnp.where(mask, jnp.einsum(
            "...id,...jd->...ij", qs, ks, preferred_element_type=_F32), 0.0)
    return kk, qk


# Elements of one (chunks, heads, C, d_k) operand that the halved form's
# tiles are made for at a time: the chunks of a call are walked in groups
# of this size (16 chunks of 64 tokens at 32 heads of 128), each group's
# tiles made again in the backward pass, so that what a level keeps — a
# float32 factor and two scaled operands a level, two (C, C) float32 tiles
# — is a group's and not the sequence's (3 GiB at 8,192 tokens).
_TILE_GROUP_ELEMENTS = 1 << 22


# ------------------------------------------------------- the tile kernels
#
# One grid step of either kernel holds ``per`` chunks of one head of one
# sequence: ``q``, ``k`` (per C, d_k) in the operands' dtype and ``g``
# float32, read as a column range of the (b, T, H d_k) arrays the mixer
# leaves, so that nothing is chunked or transposed on the way in.  A chunk
# at a time (a ``fori_loop``: the body is lowered once) it makes the
# exponents of all ``log2 C`` levels, of ``into`` and of ``out_of`` from
# ``g`` by float32 adds (:func:`_exponents`: each a sum of ``g`` itself
# over the rows between a token and its level's reference row, as
# ``_halved_tiles`` has it), ``exp(min(., 0))`` of them, and a level's two
# products as one, ``[Q o E; K o E] (K o E)^T``, masked and added in
# float32 in VMEM.  What leaves the core is what the solve, the sequential
# pass and the read-outs are handed: ``kk`` (C, C) float32, ``qk`` (C, C)
# and the decayed ``k_in``, ``k_out``, ``q_in`` in the operands' dtype,
# ``total`` (d_k) float32, in the chunked layout (b, chunks, H, C, .).  The
# backward kernel reads the same three inputs and those six cotangents,
# makes the levels again, and writes ``dq``, ``dk``, ``dg``: a level's four
# transposed products are one (2 C, 2 C) x (2 C, d_k) product with the two
# cotangent tiles and their transposes laid in one square (``G = H + H^T``,
# ``H = [[tril(dkk, -1), 0], [dqk, 0]]``), and the exponents' cotangents go
# back through the same adds (:func:`_exponents_transposed`).


def _level_masks(C: int) -> np.ndarray:
    """(levels, C, C) float32 of 0 and 1: :func:`_halving_masks`."""
    return np.stack([m for _, m in _halving_masks(C)]).astype(np.float32)


def _cotangent_masks(C: int) -> np.ndarray:
    """(levels, 2 C, 2 C): a level's pairs in the square ``[[dkk + dkk^T,
    dqk^T], [dqk, 0]]``."""
    m = _level_masks(C)
    t = np.swapaxes(m, 1, 2)
    return np.concatenate([np.concatenate([m + t, t], axis=2),
                           np.concatenate([m, np.zeros_like(m)], axis=2)],
                          axis=1)


def _nt(a, b):
    """``a b^T`` with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _row_col(C: int):
    """Row and column numbers of a (C, C) tile."""
    return (lax.broadcasted_iota(jnp.int32, (C, C), 0),
            lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _second_half(C: int, s: int, width: int):
    """Which rows of a chunk lie in the second half of their block of
    ``2 s`` rows: (C, width) bool."""
    row = lax.broadcasted_iota(jnp.int32, (C, width), 0)
    return lax.bitwise_and(row, s) != 0


def _siblings(x, s: int):
    """``x`` (C, width) with the two halves (``s`` rows each) of every
    block of ``2 s`` rows exchanged: whole sublane tiles renamed where
    ``s`` is a multiple of 8, two rotations and a select below."""
    C = x.shape[0]
    if s % 8 == 0:
        return lax.concatenate(
            [x[first + half:first + half + s]
             for first in range(0, C, 2 * s) for half in (s, 0)], 0)
    return lax.select(_second_half(C, s, x.shape[1]),
                      pltpu.roll(x, s, 0), pltpu.roll(x, C - s, 0))


def _exponents(g):
    """Every level's exponent of a chunk ``g`` (C, d_k) float32, then
    ``into``'s and ``out_of``'s: ``[e_{C/2}, ..., e_1, into, out_of]``,
    each (C, d_k) and a sum of ``g`` itself by float32 adds.  With ``a_s``
    the sum over the rows of a token's half of ``s`` rows up to it and
    ``b_s`` over those behind it, ``a_2s = a_s + [second half] t_s'`` and
    ``b_2s = b_s + [first half] t_s'`` for ``t_s'`` the other half's total
    (``t_2s = t_s + t_s'``): a level's exponent is ``a_s`` in a second
    half and ``b_s`` in a first, ``into = a_C``, ``out_of = b_C`` — no
    difference of two sums anywhere."""
    C, dk = g.shape
    zero = jnp.zeros_like(g)
    a, b, t = g, zero, g
    levels, s = [], 1
    while s < C:
        second = _second_half(C, s, dk)
        levels.append(lax.select(second, a, b))
        other = _siblings(t, s)
        a = lax.add(a, lax.select(second, other, zero))
        b = lax.add(b, lax.select(second, zero, other))
        t = lax.add(t, other)
        s *= 2
    return levels[::-1] + [a, b]


def _exponents_transposed(de):
    """The cotangent of ``g`` from those of :func:`_exponents`' blocks
    (the same list): the same adds walked back, float32."""
    C, dk = de[0].shape
    count = len(de) - 2
    zero = jnp.zeros_like(de[0])
    da, db, dt = de[-2], de[-1], None
    for level in range(count):                  # s = C/2, ..., 1
        s = C >> (level + 1)
        second = _second_half(C, s, dk)
        dother = lax.select(second, da, db)
        if dt is not None:
            dother = lax.add(dother, dt)
        back = _siblings(dother, s)
        dt = back if dt is None else lax.add(dt, back)
        da = lax.add(da, lax.select(second, de[level], zero))
        db = lax.add(db, lax.select(second, zero, de[level]))
    return lax.add(da, dt)


def _decays(e):
    """``exp(min(., 0))`` of an exponent: the guard changes nothing."""
    return lax.exp(lax.min(e, jnp.zeros_like(e)))


def _tiles_fwd_kernel(q_ref, k_ref, g_ref, mask_ref, kk_ref, qk_ref,
                      kin_ref, kout_ref, qin_ref, total_ref, *, C: int,
                      per: int):
    """``per`` chunks of one head: the six outputs of each (the comment
    above).  ``mask_ref`` (levels, C, C): a level's pairs, 0 and 1."""
    levels = C.bit_length() - 1
    dtype = q_ref.dtype
    row, col = _row_col(C)
    eye = row == col

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * C, C), C)
        qf = q_ref[rows, :].astype(_F32)
        kf = k_ref[rows, :].astype(_F32)
        e = _exponents(g_ref[rows, :])
        qk = jnp.where(eye, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
        kk = jnp.zeros((C, C), _F32)
        for l in range(levels):
            El = _decays(e[l])
            ks = lax.convert_element_type(lax.mul(kf, El), dtype)
            qs = lax.convert_element_type(lax.mul(qf, El), dtype)
            both = _nt(lax.concatenate([qs, ks], 0), ks)     # (2 C, C)
            mask = mask_ref[l]
            qk = lax.add(qk, lax.mul(both[:C], mask))
            kk = lax.add(kk, lax.mul(both[C:], mask))
        into, out_of = _decays(e[levels]), _decays(e[levels + 1])
        kk_ref[j] = kk
        qk_ref[j] = qk.astype(dtype)
        kin_ref[j] = (kf * into).astype(dtype)
        kout_ref[j] = (kf * out_of).astype(dtype)
        qin_ref[j] = (qf * into).astype(dtype)
        total_ref[j] = e[levels][C - 1:]
        return carry

    lax.fori_loop(0, per, chunk, 0)


def _tiles_bwd_kernel(q_ref, k_ref, g_ref, dkk_ref, dqk_ref, dkin_ref,
                      dkout_ref, dqin_ref, dtotal_ref, mask_ref, dq_ref,
                      dk_ref, dg_ref, h_ref, *, C: int, per: int):
    """``per`` chunks of one head, transposed.  ``mask_ref`` (levels, 2 C,
    2 C): a level's pairs in the square ``G``; ``h_ref`` (2 C, 2 C)
    float32: the two cotangent tiles of a chunk beside zeros."""
    levels = C.bit_length() - 1
    dtype = q_ref.dtype
    row, col = _row_col(C)
    eye, below = row == col, row > col
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    h_ref[:, C:] = jnp.zeros((2 * C, C), _F32)

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * C, C), C)
        qf = q_ref[rows, :].astype(_F32)
        kf = k_ref[rows, :].astype(_F32)
        e = _exponents(g_ref[rows, :])
        dqk = dqk_ref[j].astype(_F32)
        # kk is zero on and above its diagonal whatever its cotangent holds.
        h_ref[:C, :C] = jnp.where(below, dkk_ref[j], 0.0)
        h_ref[C:, :C] = dqk
        H = h_ref[...]
        G = H + H.T
        own = jnp.sum(jnp.where(eye, dqk, 0.0), axis=1, keepdims=True)
        into, out_of = _decays(e[levels]), _decays(e[levels + 1])
        dkin = dkin_ref[j].astype(_F32)
        dkout = dkout_ref[j].astype(_F32)
        dqin = dqin_ref[j].astype(_F32)
        dk = dkin * into + dkout * out_of + own * qf
        dq = dqin * into + own * kf
        de = [None] * levels + [
            (dkin * kf + dqin * qf) * into
            + jnp.where(last, dtotal_ref[j], 0.0), dkout * kf * out_of]
        for l in range(levels):
            El = _decays(e[l])
            ks = lax.convert_element_type(lax.mul(kf, El), dtype)
            qs = lax.convert_element_type(lax.mul(qf, El), dtype)
            pairs = lax.convert_element_type(lax.mul(G, mask_ref[l]), dtype)
            d = lax.dot_general(pairs, lax.concatenate([ks, qs], 0),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=_F32)  # (2 C, d_k)
            dks, dqs = d[:C], d[C:]
            dk = lax.add(dk, lax.mul(dks, El))
            dq = lax.add(dq, lax.mul(dqs, El))
            de[l] = lax.mul(
                lax.add(lax.mul(dks, kf), lax.mul(dqs, qf)), El)
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dg_ref[rows, :] = _exponents_transposed(de)
        return carry

    lax.fori_loop(0, per, chunk, 0)


_PARALLEL = ("parallel", "parallel", "parallel")


def _tile_specs(C: int, per: int, dk: int):
    """Block specs of a grid ``(sequence, chunks / per, head)``: a head's
    columns of ``per`` chunks of a (b, T, H d_k) array; ``per`` chunks of
    one head of a chunked (b, chunks, H, rows, cols) array; a whole
    constant."""
    flat = pl.BlockSpec((None, per * C, dk), lambda i, c, h: (i, c, h))

    def chunked(rows, cols):
        return pl.BlockSpec((None, per, None, rows, cols),
                            lambda i, c, h: (i, c, h, 0, 0))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i, c, h: (0,) * a.ndim)

    return flat, chunked, whole


@functools.partial(jax.jit, inline=True,
                   static_argnames=("C", "per", "interpret"))
def _tiles_fwd(q, k, g, *, C, per, interpret):
    """``kk``, ``qk``, ``k_in``, ``k_out``, ``q_in``, ``total`` of every
    chunk, (b, chunks, H, ...), from ``q``, ``k``, ``g`` (b, T, H, d_k)."""
    b, T, H, dk = q.shape
    nc = T // C
    flat, chunked, whole = _tile_specs(C, per, dk)
    masks = jnp.asarray(_level_masks(C))

    def out(rows, cols, dtype):
        return _pallas.struct((b, nc, H, rows, cols), dtype, q, k, g)

    kk, qk, k_in, k_out, q_in, total = pl.pallas_call(
        functools.partial(_tiles_fwd_kernel, C=C, per=per),
        grid=(b, nc // per, H),
        in_specs=[flat, flat, flat, whole(masks)],
        out_specs=[chunked(C, C), chunked(C, C), chunked(C, dk),
                   chunked(C, dk), chunked(C, dk), chunked(1, dk)],
        out_shape=[out(C, C, _F32), out(C, C, q.dtype), out(C, dk, q.dtype),
                   out(C, dk, q.dtype), out(C, dk, q.dtype),
                   out(1, dk, _F32)],
        interpret=interpret, name="kda_tiles_fwd",
        **_pallas.compiler_params(interpret, _PARALLEL),
    )(*(a.reshape(b, T, H * dk) for a in (q, k, g)), masks)
    return kk, qk, k_in, k_out, q_in, total.reshape(b, nc, H, dk)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("C", "per", "interpret"))
def _tiles_bwd(q, k, g, cotangents, *, C, per, interpret):
    """``dq``, ``dk``, ``dg`` (b, T, H, d_k) from the inputs and the
    cotangents of :func:`_tiles_fwd`'s six outputs."""
    b, T, H, dk = q.shape
    nc = T // C
    flat, chunked, whole = _tile_specs(C, per, dk)
    masks = jnp.asarray(_cotangent_masks(C))
    dkk, dqk, dkin, dkout, dqin, dtotal = cotangents
    like = (q, k, g, *cotangents)
    dq, dk_, dg = pl.pallas_call(
        functools.partial(_tiles_bwd_kernel, C=C, per=per),
        grid=(b, nc // per, H),
        in_specs=[flat, flat, flat, chunked(C, C), chunked(C, C),
                  chunked(C, dk), chunked(C, dk), chunked(C, dk),
                  chunked(1, dk), whole(masks)],
        out_specs=[flat, flat, flat],
        out_shape=[_pallas.struct((b, T, H * dk), q.dtype, *like),
                   _pallas.struct((b, T, H * dk), k.dtype, *like),
                   _pallas.struct((b, T, H * dk), _F32, *like)],
        scratch_shapes=[pltpu.VMEM((2 * C, 2 * C), _F32)],
        interpret=interpret, name="kda_tiles_bwd",
        **_pallas.compiler_params(interpret, _PARALLEL),
    )(*(a.reshape(b, T, H * dk) for a in (q, k, g)), dkk.astype(_F32), dqk,
      dkin, dkout, dqin, dtotal.astype(_F32).reshape(b, nc, H, 1, dk), masks)
    return tuple(a.reshape(b, T, H, dk) for a in (dq, dk_, dg))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _tiles(q, k, g, C, per, interpret):
    return _tiles_fwd(q, k, g, C=C, per=per, interpret=interpret)


def _tiles_fwd_rule(q, k, g, C, per, interpret):
    # The inputs alone: nothing a level made is kept, so the tiles need no
    # groups of chunks under a second jax.checkpoint.
    return (_tiles_fwd(q, k, g, C=C, per=per, interpret=interpret),
            (q, k, g))


def _tiles_bwd_rule(C, per, interpret, res, cotangents):
    return _tiles_bwd(*res, cotangents, C=C, per=per, interpret=interpret)


_tiles.defvjp(_tiles_fwd_rule, _tiles_bwd_rule)


def rule_plan(q_like, g_like, chunk: int, interpret: bool) -> DeltaPlan:
    """:func:`delta_plan` for a call whose ``q`` and ``g`` are, or are
    shaped like, ``q_like`` (b, T, H, d_k) and ``g_like``: what the rule,
    ``chip_smoke.py`` and the tests ask (the mixer, whose ``q`` is not yet
    cut into heads, asks :func:`delta_plan` itself)."""
    vma = jax.typeof(q_like).vma | jax.typeof(g_like).vma
    return delta_plan(chunk, len(g_like.shape), seq_len=q_like.shape[1],
                      key_dim=q_like.shape[-1],
                      itemsize=q_like.dtype.itemsize, interpret=interpret,
                      manual_axes=bool(vma))


def _per_channel_rule(q, k, v, g, beta, plan: DeltaPlan, interpret):
    """:func:`gated_delta_rule` for ``g`` (b, T, H, d_k): the module
    docstring's second form, its tiles made as ``plan`` says."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = plan.chunk
    if C & (C - 1):
        raise ValueError("a decay a key channel halves its chunks: chunk "
                         f"has to be a power of two, not {C}")
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)]
                                    + [(0, 0)] * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    nc = (T + pad) // C
    dtype = v.dtype

    def chunked(a):                  # (b, T, H, ...) -> (b, nc, H, C, ...)
        a = a.reshape(b, nc, C, *a.shape[2:])
        return jnp.moveaxis(a, 2, 3)

    def solved(kk, k_in, vc, bc):
        """``W`` and ``U`` of the WY form from the ``K K^T`` tile."""
        Tm = (unit_lower_inverse(bc[..., :, None] * kk)
              * bc[..., None, :]).astype(dtype)
        return (jnp.einsum("bnhij,bnhjd->bnhid", Tm, k_in),
                jnp.einsum("bnhij,bnhjv->bnhiv", Tm, vc))

    def before_the_states(qc, kc, vc, gc, bc):
        """What a group of chunks hands the sequential pass and the
        read-outs: every factor is ``exp`` of a sum of ``g`` (the guard
        ``min(., 0)`` changes nothing)."""
        with jax.named_scope("decay"):
            ones = np.ones((C, C))
            into = jnp.exp(jnp.minimum(_sums(gc, np.tril(ones)), 0.0))
            out_of = jnp.exp(jnp.minimum(_sums(gc, np.triu(ones, 1)), 0.0))
            total = gc.sum(axis=-2)                        # (b, n, H, d_k)
        with jax.named_scope("solve"):
            kk, qk = _halved_tiles(qc, kc, gc, dtype)
            k_in = (kc.astype(_F32) * into).astype(dtype)
            W, U = solved(kk, k_in, vc, bc)
        k_out = (kc.astype(_F32) * out_of).astype(dtype)
        q_in = (qc.astype(_F32) * into).astype(dtype)
        return W, U, k_out, total, q_in, qk.astype(dtype)

    def after_the_kernels(q, k, v, g, beta):
        """The same six from the tile kernels: every chunk at once, the
        kernels' residuals being their inputs."""
        vc, bc = chunked(v), chunked(beta.astype(_F32))
        with jax.named_scope("solve"):
            kk, qk, k_in, k_out, q_in, total = _tiles(
                q, k, g.astype(_F32), C, plan.chunks_a_step, interpret)
            W, U = solved(kk, k_in, vc, bc)
        return W, U, k_out, total, q_in, qk

    def in_groups(operands):
        """:func:`before_the_states` for ``_TILE_GROUP_ELEMENTS`` of the
        chunks at a time, each group's made again in the backward pass."""
        per = max(1, _TILE_GROUP_ELEMENTS // (b * H * C * dk))
        per = max(n for n in range(1, min(per, nc) + 1) if nc % n == 0)
        if per == nc:
            return before_the_states(*operands)

        def grouped(a):              # (b, nc, ...) -> (groups, b, per, ...)
            return jnp.moveaxis(
                a.reshape(b, nc // per, per, *a.shape[2:]), 1, 0)

        return (jnp.moveaxis(a, 0, 1).reshape(b, nc, *a.shape[3:])
                for a in lax.map(
                    jax.checkpoint(lambda group: before_the_states(*group)),
                    tuple(grouped(a) for a in operands)))

    if plan.form == "tile_kernels":
        W, U, k_out, total, q_in, qk = after_the_kernels(q, k, v, g, beta)
    else:
        W, U, k_out, total, q_in, qk = in_groups(
            (chunked(q), chunked(k), chunked(v), chunked(g.astype(_F32)),
             chunked(beta.astype(_F32))))

    with jax.named_scope("states"):
        def step(S, chunk_in):       # S (b, H, d_v, d_k), float32
            W_c, U_c, k_c, total_c = chunk_in
            entering = S.astype(dtype)
            v_new = (U_c.astype(_F32) - jnp.einsum(
                "bhid,bhvd->bhiv", W_c, entering,
                preferred_element_type=_F32)).astype(dtype)
            S = jnp.exp(total_c)[..., None, :] * S + jnp.einsum(
                "bhiv,bhid->bhvd", v_new, k_c, preferred_element_type=_F32)
            return S, (entering, v_new)

        start = jnp.broadcast_to(          # varies as the operands do
            jnp.zeros_like(total[:, 0])[..., None, :], (b, H, dv, dk))
        _, (entering, v_new) = lax.scan(
            step, start,
            tuple(jnp.moveaxis(a, 1, 0) for a in (W, U, k_out, total)))
        entering = jnp.moveaxis(entering, 0, 1)     # (b, nc, H, d_v, d_k)
        v_new = jnp.moveaxis(v_new, 0, 1)           # (b, nc, H, C, d_v)

    with jax.named_scope("inter"):
        o = jnp.einsum("bnhid,bnhvd->bnhiv", q_in, entering,
                       preferred_element_type=_F32)

    with jax.named_scope("intra"):
        o = o + jnp.einsum("bnhij,bnhjv->bnhiv", qk, v_new,
                           preferred_element_type=_F32)

    o = jnp.moveaxis(o, 3, 2).reshape(b, T + pad, H, dv)
    return o[:, :T].astype(dtype)


def gated_delta_recurrence(q, k, v, g, beta):
    """The definition, one token a step in float32: ``S_t = S_{t-1}
    Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``, ``o_t = S_t
    q_t``, with ``alpha_t = exp(g_t)`` a value a head (``g`` of rank 3) or
    a key channel (rank 4).  Keeps every state for the backward pass: small
    sizes only."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, t):                  # S (b, H, d_v, d_k)
        q_t, k_t, v_t, g_t, beta_t = t
        decay = jnp.exp(g_t)         # a head, or a key channel of it
        S = (decay[..., None, None] if g.ndim == 3
             else decay[..., None, :]) * S
        seen = jnp.einsum("bhvd,bhd->bhv", S, k_t)
        S = S + jnp.einsum("bhv,bhd->bhvd",
                           beta_t[..., None] * (v_t - seen), k_t)
        return S, jnp.einsum("bhvd,bhd->bhv", S, q_t)

    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((b, H, dv, dk), _F32), tuple(
            jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)
