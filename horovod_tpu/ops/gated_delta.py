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
sums, the solve, the carried state; matmul operands ``v.dtype``.  What
comes before the sequential pass is made for ``_TILE_GROUP_ELEMENTS`` of a
call's chunks at a time and again in the backward pass, which keeps the
chunked ``q``, ``k``, ``v``, ``g``, ``beta`` and what the pass and the
read-outs are handed (``W``, ``U``, the decayed ``k`` and ``q``, the
``Q K^T`` tile).

Plain XLA in both ranks, differentiated as it stands (the solve alone has
its own rule, ``-T^T dT T^T``, so that the powers of ``A`` are not
kept): a caller at training sizes wraps it in a ``jax.checkpoint``, as
:class:`~horovod_tpu.models.linear_attention.GatedDeltaNet` and
:class:`~horovod_tpu.models.linear_attention.KimiDeltaAttention` do.
:func:`delta_plan` names the form, as ``flash_attention._plan`` and
``ssd._plan`` name theirs; a fused kernel would be chosen there.  The
rank of ``g`` picks it and nothing else does; a rank-3 call lowers to the
text it lowered to before the second form was written
(``tests/test_kimi_program.py``).

:func:`gated_delta_recurrence` is the definition, token by token in
float32, for tests at small sizes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


class DeltaPlan(NamedTuple):
    """How :func:`gated_delta_rule` runs: ``form`` — ``"xla_chunked"``
    for a decay a head (``g`` of rank 3), ``"xla_chunked_halved"`` for a
    decay a key channel (rank 4: the tiles from halved sub-chunks) — and
    the chunk length."""
    form: str
    chunk: int


def delta_plan(chunk: int = 64, g_rank: int = 3) -> DeltaPlan:
    """The form a call takes: the rank of its ``g`` decides, and no option
    picks another."""
    return DeltaPlan("xla_chunked" if g_rank == 3 else "xla_chunked_halved",
                     chunk)


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


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """``o`` (b, T, H, d_v) of the module docstring's recurrence for ``q``,
    ``k`` (b, T, H, d_k), ``v`` (b, T, H, d_v), ``beta`` (b, T, H) and
    ``g`` (b, T, H) — a decay a head — or (b, T, H, d_k) — a decay a key
    channel, in chunks that are a power of two —, each sequence from a
    zero state.  A ``T`` that is no multiple of ``chunk`` is padded with
    tokens that change nothing (``beta`` 0, ``g`` 0)."""
    if g.ndim == 4:
        return _per_channel_rule(q, k, v, g, beta, chunk)
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


def _per_channel_rule(q, k, v, g, beta, chunk):
    """:func:`gated_delta_rule` for ``g`` (b, T, H, d_k): the module
    docstring's second form."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
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
            Tm = (unit_lower_inverse(bc[..., :, None] * kk)
                  * bc[..., None, :]).astype(dtype)
            k_in = (kc.astype(_F32) * into).astype(dtype)
            W = jnp.einsum("bnhij,bnhjd->bnhid", Tm, k_in)
            U = jnp.einsum("bnhij,bnhjv->bnhiv", Tm, vc)
        k_out = (kc.astype(_F32) * out_of).astype(dtype)
        q_in = (qc.astype(_F32) * into).astype(dtype)
        return W, U, k_out, total, q_in, qk.astype(dtype)

    operands = (chunked(q), chunked(k), chunked(v),
                chunked(g.astype(_F32)), chunked(beta.astype(_F32)))
    per = max(1, _TILE_GROUP_ELEMENTS // (b * H * C * dk))
    per = max(n for n in range(1, min(per, nc) + 1) if nc % n == 0)
    if per == nc:
        W, U, k_out, total, q_in, qk = before_the_states(*operands)
    else:
        def grouped(a):              # (b, nc, ...) -> (groups, b, per, ...)
            return jnp.moveaxis(
                a.reshape(b, nc // per, per, *a.shape[2:]), 1, 0)

        W, U, k_out, total, q_in, qk = (
            jnp.moveaxis(a, 0, 1).reshape(b, nc, *a.shape[3:])
            for a in lax.map(
                jax.checkpoint(lambda group: before_the_states(*group)),
                tuple(grouped(a) for a in operands)))

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
