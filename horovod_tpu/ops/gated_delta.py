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

One form, plain XLA, differentiated as it stands (the solve alone has
its own rule, ``-T^T dT T^T``, so that the powers of ``A`` are not
kept): a caller at training sizes wraps it in a ``jax.checkpoint``, as
:class:`~horovod_tpu.models.linear_attention.GatedDeltaNet` does.
:func:`delta_plan` says so, as ``flash_attention._plan`` and
``ssd._plan`` say theirs; a fused kernel would be chosen there.

:func:`gated_delta_recurrence` is the definition, token by token in
float32, for tests at small sizes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


class DeltaPlan(NamedTuple):
    """How :func:`gated_delta_rule` runs: ``form`` (``"xla_chunked"``, the
    only one) and the chunk length."""
    form: str
    chunk: int


def delta_plan(chunk: int = 64) -> DeltaPlan:
    """The form a call takes.  There is one; no option picks another."""
    return DeltaPlan("xla_chunked", chunk)


def delta_sizes(batch: int, seq_len: int, heads: int, key_dim: int,
                value_dim: int, chunk: int) -> dict:
    """What one call passes between chunks, from shapes: the chunks a
    head walks and the bytes of float32 states entering them."""
    chunks = batch * -(-seq_len // chunk)
    return {"chunks": chunks,
            "state_bytes": chunks * heads * value_dim * key_dim * 4}


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
    ``k`` (b, T, H, d_k), ``v`` (b, T, H, d_v), ``g`` and ``beta``
    (b, T, H), each sequence from a zero state.  A ``T`` that is no
    multiple of ``chunk`` is padded with tokens that change nothing
    (``beta`` 0, ``g`` 0)."""
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


def gated_delta_recurrence(q, k, v, g, beta):
    """The definition, one token a step in float32: ``S_t = alpha_t
    S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``, ``o_t = S_t q_t``.
    Keeps every state for the backward pass: small sizes only."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, t):                  # S (b, H, d_v, d_k)
        q_t, k_t, v_t, g_t, beta_t = t
        S = jnp.exp(g_t)[..., None, None] * S
        seen = jnp.einsum("bhvd,bhd->bhv", S, k_t)
        S = S + jnp.einsum("bhv,bhd->bhvd",
                           beta_t[..., None] * (v_t - seen), k_t)
        return S, jnp.einsum("bhvd,bhd->bhv", S, q_t)

    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((b, H, dv, dk), _F32), tuple(
            jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)
