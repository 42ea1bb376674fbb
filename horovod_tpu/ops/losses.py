"""Memory-efficient fused softmax cross-entropy for large-vocab LM heads.

The straightforward ``logits = hidden @ W; optax.softmax_cross_entropy``
materializes an ``(N, vocab)`` f32 logits tensor *and* keeps it (plus
softmax intermediates) alive as autodiff residuals — at the benchmark
shape (N = 16384, vocab = 32768) that is ~2 GB of f32 logits and enough
peak-HBM pressure that XLA auto-rematerializes convolution fusions.

This op computes the same loss with the streamed-head schedule (public
pattern in every large-LM codebase):

* forward: split the rows into chunks (python-unrolled, 2-way by
  default); each chunk computes its logits tile, reduces it to ``lse``
  and the label logit, and DISCARDS the tile — residuals are just
  ``(hidden, W, labels, lse)``;
* ``lse`` takes no second read of the tile.  ``sum exp(logits - max)``
  has to wait for the row maximum; ``sum exp(logits - shift)``, with a
  shift known before the tile exists (the row's maximum over a few
  columns, one small matmul), is reduced in the epilogue of the matmul
  that makes the tile and is as exact, unless a logit lies so far above
  the shift that the sum overflows.  The rule sees that on the device
  (``lax.cond``): such a chunk gets its ``lse`` from the usual shifted
  sum over tiles made again a few hundred rows at a time
  (:func:`_tile_lse`);
* backward: revisit the chunks, form ``softmax - onehot`` from each
  logits tile and contract it immediately into ``d hidden`` and ``dW``.

The backward is WRITTEN as a recompute of each tile, one extra head
matmul, in exchange for never holding O(N x vocab) residuals.  Where the
backward directly follows the forward (``jax.grad`` of ``.mean()``, as
every step in this repo is) XLA merges that matmul with the forward's
identical one and the tile lives from one to the other: three head-sized
matmuls a chunk run, not four (PERF.md section 6, PR 26): a form that
saved the tile as a bf16 residual saved what XLA already keeps, and left
at PR 27.  The schedule follows ``n`` and ``chunk`` (:func:`_schedule`)
and is not an option.  All matmuls run in the input dtype (bf16 on TPU) with
f32 accumulation, so precision matches the f32-logits reference within
bf16 rounding.  The ops carry the trace scopes ``xent/loss`` and
``xent/grad``; device time under ``xent/loss/overflowed`` counts the
chunks whose sum overflowed (docs/observability.md).  Under ``vmap`` the
conditional becomes a select that runs both branches: as exact, slower.

No reference analogue (the reference's models predate large-vocab LM
heads); cited by SURVEY §5.7's long-context mandate.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

# Beyond this many python-unrolled chunks the HLO growth (each body ~3
# large matmuls in the backward) outweighs the unrolled form's advantages
# and the constant-size lax.scan schedule takes over.
_MAX_UNROLL_CHUNKS = 8


def _schedule(n: int, chunk: int):
    """(rows a chunk, python-unrolled?) for ``n`` rows under the caller's
    transient bound of ``chunk`` rows (chunk x V f32).

    Two chunks where the bound allows: the halved logits transient (1 GB
    instead of 2 GB at the bench shape) drops peak HBM below the point
    where XLA auto-rematerializes one convolution fusion per layer, with
    none of the while-loop and ``dh``-stacking overhead that made a
    scanned loop slower than one tile.  A smaller ``chunk`` is honoured: it
    RAISES the chunk count to the smallest that tiles ``n`` exactly
    within the bound, unrolled up to ``_MAX_UNROLL_CHUNKS`` bodies and
    scanned past that."""
    k = 2 if n % 2 == 0 else 1
    if n // k > chunk:
        k = n // _pick_chunk(n, chunk)
    return n // k, k <= _MAX_UNROLL_CHUNKS


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= target (so the scan tiles
    exactly; callers flatten (B, T) so n is composite in practice)."""
    if n <= target:
        return n
    chunk = max(d for d in range(1, target + 1) if n % d == 0)
    if chunk < max(1, target // 8):
        import warnings
        warnings.warn(
            f"fused cross-entropy: token count {n} has no divisor near the "
            f"target chunk {target} (best is {chunk}); the scan degenerates "
            f"to {n // chunk} tiny (chunk={chunk}, vocab) tiles. Pad or "
            f"flatten the batch to a composite token count.", stacklevel=3)
    return chunk


# The columns whose row maximum is the shift of ``sum exp`` (one lane
# tile of the head weight), and the rows of the tiles that a chunk whose
# sum overflowed is made of again (103 MB of f32 at a vocabulary of
# 50,257).
_SHIFT_COLUMNS = 128
_OVERFLOWED_ROWS = 512


def _logits_tile(h_c, w):
    return jax.lax.dot_general(
        h_c, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (C, V) f32


def _max_shifted_lse(logits):
    m = jnp.max(logits, axis=-1)
    return m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))


def _tile_lse(logits, h_c, w):
    """``log sum exp`` of each row of the tile ``h_c @ w``, in one pass.

    ``sum exp(logits - max)`` has to wait for the row maximum, so it is a
    second read of the whole f32 tile: 2.2 ms of 8,192 x 50,257 on the
    v5e, a chunk (PERF.md section 5).  A shift that is known before the
    tile lets XLA reduce the sum in the epilogue of the matmul that makes
    the tile.  The shift is the row's maximum over the first
    ``_SHIFT_COLUMNS`` columns, which is never above the row's maximum:
    the sum is at least 1, so nothing that matters is flushed, and it is
    as exact as the usual sum unless a logit lies some 80 above the
    shift and the sum overflows.  Softmax does not see an offset common
    to a row, and neither does this: what counts is the spread inside a
    row (a few units at initialisation, 10-20 in a trained GPT-2, whose
    logits sit near -100), not where the logits lie.  Whether a sum
    overflowed the rule sees on the device (``lax.cond``); the chunk's
    ``lse`` then comes from the other branch, which makes the tile again
    ``_OVERFLOWED_ROWS`` rows at a time: the tile in hand stays out of the
    conditional, whose branch would want it in a layout of its own
    (1.5 GiB more in the compiled plan of the head alone).  Device time
    under ``xent/loss/overflowed`` is the count of chunks that took it."""
    shift = jnp.max(_logits_tile(h_c, w[:, :_SHIFT_COLUMNS]), axis=-1)
    total = jnp.sum(jnp.exp(logits - shift[:, None]), axis=-1)

    def overflowed():
        rows = _pick_chunk(h_c.shape[0], _OVERFLOWED_ROWS)
        with jax.named_scope("overflowed"):
            return lax.map(
                lambda h_r: _max_shifted_lse(_logits_tile(h_r, w)),
                h_c.reshape(-1, rows, h_c.shape[1])).reshape(-1)

    return lax.cond(jnp.all(jnp.isfinite(total)),
                    lambda: shift + jnp.log(total), overflowed)


def _chunk_fwd(h_c, w, labels_c):
    """One chunk's (loss, lse) from its logits tile; the tile dies here."""
    with jax.named_scope("xent/loss"):
        logits = _logits_tile(h_c, w)
        lse = _tile_lse(logits, h_c, w)
        correct = jnp.take_along_axis(
            logits, labels_c[:, None], axis=-1)[:, 0]
        # Held where they are, for what the compiler otherwise does
        # around a conditional (sandbox compiles of whole steps, PR 26):
        # it moves the first use of ``lse``, ``logits - lse``, into both
        # branches, so that the conditional takes the tile in and hands a
        # second one out (the plan of ``gpt13b_1chip`` 13.87 -> 15.58 GiB).
        lse, correct = lax.optimization_barrier((lse, correct))
    return lse - correct, lse


def _chunk_bwd(h_c, w, labels_c, lse_c, g_c):
    """Contract one chunk's ``softmax - onehot`` straight into
    (dh_c, dw_c) from its logits tile, made again."""
    with jax.named_scope("xent/grad"):
        logits = _logits_tile(h_c, w)
        p = jnp.exp(logits - lse_c[:, None])
        cols = lax.broadcasted_iota(jnp.int32, p.shape, 1)
        dlogits = ((p - (cols == labels_c[:, None]))
                   * g_c[:, None]).astype(h_c.dtype)     # (C, V)
        dh_c = jax.lax.dot_general(
            dlogits, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (C, d)
        dw_c = jax.lax.dot_general(
            h_c, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (d, V)
    return dh_c, dw_c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_softmax_xent(hidden, w, labels, chunk=16384):
    """Per-token softmax cross-entropy of a linear head, never holding the
    full logits as a residual.

    Args:
      hidden: (N, d) activations (any float dtype; matmuls run in this
        dtype with f32 accumulation).
      w: (d, V) head weight (cast to ``hidden.dtype`` for the matmuls).
      labels: (N,) int32 target ids in [0, V).
      chunk: most rows a logits tile may hold (the transient is chunk x
        V f32).  The rows are split in two where that allows, else into
        the fewest equal chunks within it (a divisor of N); see
        :func:`_schedule`.

    Returns: (N,) f32 per-token losses (``lse - logit[label]``) — take
    ``.mean()`` for the usual reduction.
    """
    return _xent_fwd(hidden, w, labels, chunk)[0]


def _xent_fwd(hidden, w, labels, chunk):
    n = hidden.shape[0]
    c, unrolled = _schedule(n, chunk)
    wc = w.astype(hidden.dtype)
    if unrolled:
        parts = [_chunk_fwd(hidden[i:i + c], wc, labels[i:i + c])
                 for i in range(0, n, c)]
        loss = jnp.concatenate([p[0] for p in parts])
        lse = jnp.concatenate([p[1] for p in parts])
    else:
        def body(_, hl):
            h_c, l_c = hl
            return None, _chunk_fwd(h_c, wc, l_c)

        _, (loss, lse) = lax.scan(
            body, None, (hidden.reshape(n // c, c, -1),
                         labels.reshape(n // c, c)))
        loss, lse = loss.reshape(n), lse.reshape(n)
    return loss, (hidden, w, labels, lse)


def _xent_bwd(chunk, res, g):
    hidden, w, labels, lse = res
    n, d = hidden.shape
    c, unrolled = _schedule(n, chunk)
    wc = w.astype(hidden.dtype)
    g = g.astype(jnp.float32)
    if unrolled:
        dhs, dw = [], jnp.zeros_like(w, jnp.float32)
        for i in range(0, n, c):
            s = slice(i, i + c)
            dh_c, dw_c = _chunk_bwd(hidden[s], wc, labels[s], lse[s], g[s])
            dhs.append(dh_c)
            dw = dw + dw_c
        dh = jnp.concatenate(dhs)
    else:
        def body(dw_acc, args):
            h_c, l_c, lse_c, g_c = args
            dh_c, dw_c = _chunk_bwd(h_c, wc, l_c, lse_c, g_c)
            return dw_acc + dw_c, dh_c

        dw, dhs = lax.scan(
            body, jnp.zeros_like(w, jnp.float32),
            (hidden.reshape(n // c, c, d), labels.reshape(n // c, c),
             lse.reshape(n // c, c), g.reshape(n // c, c)))
        dh = dhs.reshape(n, d)
    return dh.astype(hidden.dtype), dw.astype(w.dtype), None


fused_softmax_xent.defvjp(_xent_fwd, _xent_bwd)


def multi_token_xent(hiddens, w, tokens, weights):
    """The loss of a model with multi-token prediction: ``Σ_k weights[k] ·
    mean CE(hiddens[k] w, tokens[:, 1 + k : 1 + k + T])``.

    ``hiddens``: the (B, T, d) hidden states a ``TransformerLM(mtp=...)``
    returns with ``return_hidden=True`` — the stack's, whose position ``t``
    predicts token ``t + 1``, then each prediction module's, one token
    further each; ``tokens`` (B, T + len(hiddens)) the ids they were made
    from and are scored on; ``w`` the one (d, V) head all share, whose
    gradient is then the sum of every term's.  Each term is a
    :func:`fused_softmax_xent` pass; those past the first run under the
    trace scope ``mtp``."""
    T, d = hiddens[0].shape[1], hiddens[0].shape[-1]
    if tokens.shape[1] != T + len(hiddens) or len(weights) != len(hiddens):
        raise ValueError(
            f"{len(hiddens)} hidden states of {T} positions are scored on "
            f"sequences of {T + len(hiddens)} ids with a weight each; got "
            f"{tokens.shape[1]} ids and {len(weights)} weights")
    total = 0.0
    for k, (h, weight) in enumerate(zip(hiddens, weights)):
        labels = tokens[:, 1 + k:1 + k + T].reshape(-1)
        with jax.named_scope("mtp") if k else contextlib.nullcontext():
            term = fused_softmax_xent(h.reshape(-1, d), w, labels).mean()
        total = total + weight * term
    return total
