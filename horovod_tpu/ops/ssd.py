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
* the states are passed from chunk to chunk by a ``lax.scan`` over the
  ``T / chunk`` chunk states, in float32 (the one sequential part: T/chunk
  steps of an elementwise multiply-add on (H, P, N));
* the state entering a chunk reaches its tokens through ``C`` and the
  decay from the chunk's start, one more matmul.

Matmul operands are in ``x.dtype`` (bfloat16 in training), every decay, the
accumulation and the carried state in float32.  Plain XLA; the scopes
``intra``, ``states``, ``pass`` and ``inter`` name the four parts in a trace.
Differentiated as it stands it keeps the chunk-square tiles, ``T * chunk *
H`` floats several times over: a caller at training sizes wraps it in a
``jax.checkpoint`` (the mixer does, together with its convolution), so
that the backward pass recomputes them.

:func:`ssd_recurrence` is the definition, token by token in float32, for
tests at small sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    """``y`` (b, T, H, P) of the recurrence in the module docstring.

    ``x`` (b, T, H, P); ``dt`` (b, T, H), positive (after its softplus);
    ``A`` (H,), negative; ``B``, ``C`` (b, T, G, N) with ``G`` dividing
    ``H`` (head ``h`` reads group ``h // (H / G)``); ``D`` (H,).  ``T``
    need not be a multiple of ``chunk``: the tail is padded with steps of
    ``dt = 0``, which neither decay the state nor add to it."""
    b, T, H, P = x.shape
    if H % B.shape[2]:
        raise ValueError(f"{B.shape[2]} groups do not divide {H} heads")
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    y = _ssd_chunked(x, dt, A, B, C, D, chunk)
    return y[:, :T] if pad else y


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
