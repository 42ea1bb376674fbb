"""Memory-efficient fused softmax cross-entropy for large-vocab LM heads.

The straightforward ``logits = hidden @ W; optax.softmax_cross_entropy``
materializes an ``(N, vocab)`` f32 logits tensor *and* keeps it (plus
softmax intermediates) alive as autodiff residuals — at the benchmark
shape (N = 16384, vocab = 32768) that is ~2 GB of f32 logits and enough
peak-HBM pressure that XLA auto-rematerializes convolution fusions
(measured ~26 ms/step of recompute on v5e, docs/benchmarks.md).

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
matmuls a chunk run, not four (PERF.md section 6, PR 26).
``HOROVOD_TPU_XENT_MODE`` selects alternative schedules (see
:func:`_xent_mode`), including a save-the-logits form with a compact
bf16 residual.  All matmuls run in the input dtype (bf16 on TPU) with
f32 accumulation, so precision matches the f32-logits reference within
bf16 rounding.  The ops carry the trace scopes ``xent/loss`` and
``xent/grad``; device time under ``xent/loss/overflowed`` counts the
chunks whose sum overflowed (docs/observability.md).  Under ``vmap`` the
conditional becomes a select that runs both branches: as exact, slower.

No reference analogue (the reference's models predate large-vocab LM
heads); cited by SURVEY §5.7's long-context mandate.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
from jax import lax

_DEFAULT_MODE = "unroll2"
# Beyond this many python-unrolled chunks the HLO growth outweighs the
# unrolled form's advantages and the lax.scan schedule takes over.
_MAX_UNROLL_CHUNKS = 8


def _xent_mode() -> str:
    """CE schedule variant from ``HOROVOD_TPU_XENT_MODE`` (trace time):

    * ``unroll2`` (default) — python-unrolled 2-way row chunking of the
      streamed-head schedule: the logits transient halves, with none of
      the ``lax.scan`` while-loop/stacking overhead that made the
      scanned form slower.  At the bench shape the halved transient
      (1 GB instead of 2 GB) drops peak HBM below the point where XLA
      auto-rematerializes one convolution fusion per layer — measured
      547 → 518 ms/step, MFU 0.704 → 0.744 on v5e
      (docs/benchmarks.md).  ``unrollK`` generalizes (K clamped to a
      divisor of N; K=1 == one tile).
    * ``recompute`` — the single-tile streamed-head schedule (or a
      ``lax.scan`` when the ``chunk`` argument is below N): no logits
      residual, one extra head matmul in the backward.
    * ``save`` / ``saveK`` — keep the logits as a compact bf16 residual
      (N × vocab × 2 bytes, K-way chunked) and skip the backward
      recompute matmul; ``save2`` measured ~0.5 ms ≤ ``unroll2`` at the
      bench shape but holds a 1 GB residual, so it stays opt-in.

    An unrecognized value warns and falls back to the default rather
    than raising mid-trace.
    """
    raw = os.environ.get("HOROVOD_TPU_XENT_MODE", _DEFAULT_MODE)
    if not re.fullmatch(r"recompute|save\d*|unroll\d+", raw):
        import warnings
        warnings.warn(
            f"HOROVOD_TPU_XENT_MODE={raw!r} is not one of 'recompute', "
            f"'saveK', 'unrollK'; using the default {_DEFAULT_MODE!r}",
            RuntimeWarning, stacklevel=3)
        return _DEFAULT_MODE
    return raw


def _mode_layout(mode: str, n: int, chunk: int):
    """(save_logits, n_chunks, scan_chunk) for a validated mode string.

    ``n_chunks`` is ``None`` when the schedule should be the
    ``lax.scan``/single-tile ``recompute`` form, tiled by ``scan_chunk``
    rows; otherwise it is the python-unroll count, clamped to a divisor
    of ``n``.  An explicitly small ``chunk`` is honored in every mode —
    the caller's transient bound (chunk × V f32) RAISES the chunk count
    past the mode's minimum when n/k would exceed it — but once that
    would unroll more than ``_MAX_UNROLL_CHUNKS`` bodies into the HLO
    (each ~3 large matmuls in the backward), the constant-size scan
    schedule takes over at the same transient bound (losing a
    save-mode's residual is fine — at that many chunks the transient is
    tiny anyway)."""
    if mode == "recompute":
        return False, None, chunk
    save = mode.startswith("save")
    k = int((mode[len("save"):] if save else mode[len("unroll"):]) or 1)
    k = max(1, k)
    while n % k:
        k -= 1
    if n // k > chunk:
        k = n // _pick_chunk(n, chunk)
    if k > _MAX_UNROLL_CHUNKS:
        if save:
            import warnings
            warnings.warn(
                f"HOROVOD_TPU_XENT_MODE={mode!r}: the chunk bound "
                f"({chunk} rows over n={n} tokens) needs {k} unrolled "
                f"bodies, past the limit of {_MAX_UNROLL_CHUNKS}; "
                "falling back to the scan recompute schedule — the "
                "save-logits residual is dropped and the backward "
                "recomputes the head matmul. Raise the chunk bound or "
                "use fewer chunks to keep the residual.",
                RuntimeWarning, stacklevel=3)
        return False, None, min(chunk, n // k)
    return save, k, chunk


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


def _chunk_fwd(h_c, w, labels_c, want_logits=False):
    """One chunk's (loss, lse) from its logits tile; the tile dies here —
    unless ``want_logits`` asks for it back as a compact bf16 residual
    (the save schedule)."""
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
    if want_logits:
        return lse - correct, lse, logits.astype(jnp.bfloat16)
    return lse - correct, lse


def _chunk_bwd(h_c, w, labels_c, lse_c, g_c, logits_c=None):
    """Contract one chunk's ``softmax - onehot`` straight into
    (dh_c, dw_c); the logits tile is recomputed unless a saved bf16 tile
    (``logits_c``) is supplied."""
    with jax.named_scope("xent/grad"):
        logits = (_logits_tile(h_c, w) if logits_c is None
                  else logits_c.astype(jnp.float32))
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
      chunk: target rows per logits tile for the ``lax.scan`` fallback
        schedule (``HOROVOD_TPU_XENT_MODE=recompute`` with chunk < N);
        clamped to the largest divisor of N.  The DEFAULT schedule is
        ``unroll2`` (see :func:`_xent_mode`): python-unrolled 2-way
        chunking, which halves the logits transient with no loop
        overhead — at the bench shape that freed enough peak HBM to stop
        XLA auto-rematerializing a convolution per layer (−29 ms/step on
        v5e).  A *scanned* loop measured slower than one tile
        (while-loop + dh stacking, docs/benchmarks.md); the unrolled
        form is how to shrink the transient.

    Returns: (N,) f32 per-token losses (``lse - logit[label]``) — take
    ``.mean()`` for the usual reduction.
    """
    # Primal-only call (no VJP): a save-mode residual would be computed
    # and thrown away — suppress it.
    loss, _ = _xent_fwd(hidden, w, labels, chunk, _save_ok=False)
    return loss


def _xent_fwd_impl(hidden, w, labels, chunk):
    n = hidden.shape[0]
    c = _pick_chunk(n, chunk)
    wc = w.astype(hidden.dtype)
    if c == n:
        loss, lse = _chunk_fwd(hidden, wc, labels)
        return loss, lse
    hs = hidden.reshape(n // c, c, -1)
    ls = labels.reshape(n // c, c)

    def body(_, hl):
        h_c, l_c = hl
        return None, _chunk_fwd(h_c, wc, l_c)

    _, (loss, lse) = lax.scan(body, None, (hs, ls))
    return loss.reshape(n), lse.reshape(n)


def _xent_fwd(hidden, w, labels, chunk, _save_ok=True):
    save, k, scan_chunk = _mode_layout(_xent_mode(), hidden.shape[0], chunk)
    save = save and _save_ok
    if k is None:
        loss, lse = _xent_fwd_impl(hidden, w, labels, scan_chunk)
        return loss, (hidden, w, labels, lse, None)
    wc = w.astype(hidden.dtype)
    n = hidden.shape[0]
    c = n // k
    parts = [_chunk_fwd(hidden[i * c:(i + 1) * c], wc,
                        labels[i * c:(i + 1) * c], want_logits=save)
             for i in range(k)]
    loss = jnp.concatenate([p[0] for p in parts])
    lse = jnp.concatenate([p[1] for p in parts])
    logits_bf16 = (jnp.concatenate([p[2] for p in parts]) if save else None)
    return loss, (hidden, w, labels, lse, logits_bf16)


def _xent_bwd(chunk, res, g):
    # Whether logits were saved is read off the residual itself (not the
    # env), so a mode change between the forward and backward trace
    # cannot desynchronize the schedule from the saved state.
    hidden, w, labels, lse, logits_bf16 = res
    n, d = hidden.shape
    wc = w.astype(hidden.dtype)
    g = g.astype(jnp.float32)
    _, k, scan_chunk = _mode_layout(_xent_mode(), n, chunk)
    if k is not None or logits_bf16 is not None:
        k = k or 1
        c = n // k
        dhs, dw = [], jnp.zeros_like(w, jnp.float32)
        for i in range(k):
            s = slice(i * c, (i + 1) * c)
            dh_c, dw_c = _chunk_bwd(
                hidden[s], wc, labels[s], lse[s], g[s],
                None if logits_bf16 is None else logits_bf16[s])
            dhs.append(dh_c)
            dw = dw + dw_c
        return (jnp.concatenate(dhs).astype(hidden.dtype),
                dw.astype(w.dtype), None)
    c = _pick_chunk(n, scan_chunk)
    if c == n:
        dh, dw = _chunk_bwd(hidden, wc, labels, lse, g)
    else:
        hs = hidden.reshape(n // c, c, d)
        ls = labels.reshape(n // c, c)
        lses = lse.reshape(n // c, c)
        gs = g.reshape(n // c, c)

        def body(dw_acc, args):
            h_c, l_c, lse_c, g_c = args
            dh_c, dw_c = _chunk_bwd(h_c, wc, l_c, lse_c, g_c)
            return dw_acc + dw_c, dh_c

        dw, dhs = lax.scan(body, jnp.zeros_like(w, jnp.float32),
                           (hs, ls, lses, gs))
        dh = dhs.reshape(n, d)
    return dh.astype(hidden.dtype), dw.astype(w.dtype), None


fused_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
