"""Ring attention — sequence parallelism for long contexts.

Beyond the reference's scope (it is data-parallel only, SURVEY §5.7) but
first-class here: the sequence dimension is sharded across the rank mesh and
attention runs blockwise while K/V shards rotate around the ICI ring via
``lax.ppermute`` (Liu et al., "Ring Attention with Blockwise Transformers";
the public pattern — this is an independent implementation).

TPU mapping:

* each hop moves one K/V block to the ICI neighbour — bandwidth-optimal on
  the torus, and XLA overlaps the ``ppermute`` with the current block's
  attention math (communication hides behind the MXU);
* the online-softmax accumulators keep everything in f32 while Q/K/V stay
  bf16 — the numerics of flash attention, streamed over ranks instead of
  SRAM tiles;
* memory per chip is O(T_local²·…/T) — context length scales linearly with
  the number of chips.

Two shard layouts:

* ``layout="contiguous"`` (default) — rank r holds positions
  ``[r*T_local, (r+1)*T_local)``.  Matches the framework's plain data
  layout contract, but with ``causal=True`` the work per hop is imbalanced
  (low ranks are fully masked on late hops while high ranks attend, and
  the per-hop ``ppermute`` barrier makes everyone wait).
* ``layout="zigzag"`` — the global sequence is split into ``2n`` chunks
  and rank r holds chunks ``(r, 2n-1-r)``.  Every non-diagonal hop is then
  exactly half-causal-visible *for every rank*: a ``lax.switch`` computes
  only the visible half (all queries × early K chunk when the incoming
  shard is from the causal past, late queries × both K chunks when it is
  from the causal future), so per-hop compute is both halved and balanced.
  Use :func:`zigzag_indices` / :func:`inverse_zigzag_indices` to permute
  the host-side sequence into/out of this layout before sharding.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.mesh import RANKS_AXIS

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_attend(q, k, v, pos_q, pos_k, causal, scale):
    """One (Q-local × K-block) attention contribution with explicit
    allowed-mask (never relies on exp(-inf))."""
    # q: (B, Tq, H, D), k/v: (B, Tk, H, D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        allowed = pos_k[None, :] <= pos_q[:, None]        # (Tq, Tk)
        logits = jnp.where(allowed[None, None, :, :], logits, _NEG_BIG)
        p_mask = allowed[None, None, :, :]
    else:
        p_mask = None
    block_max = jnp.max(logits, axis=-1)                  # (B, H, Tq)
    p = jnp.exp(logits - block_max[..., None])
    if p_mask is not None:
        p = jnp.where(p_mask, p, 0.0)
    block_sum = jnp.sum(p, axis=-1)                       # (B, H, Tq)
    block_out = jnp.einsum("bhqk,bkhd->bqhd", p,
                           v.astype(jnp.float32))
    return block_max, block_sum, block_out


def zigzag_indices(n: int, seq_len: int):
    """Permutation taking a contiguous global sequence to zigzag layout.

    After ``x = x[:, zigzag_indices(n, T)]`` a plain contiguous shard over
    ``n`` ranks gives rank r the chunk pair ``(r, 2n-1-r)``.
    """
    import numpy as np
    if seq_len % (2 * n):
        raise ValueError(
            f"zigzag layout needs seq_len % (2*ranks) == 0, got "
            f"{seq_len} % {2 * n}")
    c = seq_len // (2 * n)
    idx = []
    for r in range(n):
        idx.extend(range(r * c, (r + 1) * c))
        idx.extend(range((2 * n - 1 - r) * c, (2 * n - r) * c))
    return np.asarray(idx)


def inverse_zigzag_indices(n: int, seq_len: int):
    """Permutation taking zigzag layout back to the contiguous sequence."""
    import numpy as np
    return np.argsort(zigzag_indices(n, seq_len))


def zigzag_shard_positions(rank, n, local_len):
    """Global positions of rank ``rank``'s zigzag shard of ``local_len``
    tokens (chunks ``rank`` and ``2n-1-rank``, each ``local_len // 2``).
    Usable with traced ``rank`` (e.g. ``lax.axis_index``) — models use it
    for position embeddings under the zigzag layout."""
    c = local_len // 2
    return jnp.concatenate([rank * c + jnp.arange(c),
                            (2 * n - 1 - rank) * c + jnp.arange(c)])


def _zigzag_pos(rank, n, c):
    return zigzag_shard_positions(rank, n, 2 * c)


def ring_attention(q, k, v, *, axis_name=RANKS_AXIS, causal: bool = True,
                   scale: Optional[float] = None,
                   layout: str = "contiguous"):
    """Blockwise self-attention over a sequence sharded on ``axis_name``.

    ``q``/``k``/``v``: (batch, seq_local, heads, head_dim) — this rank's
    sequence shard, in ``layout`` ("contiguous" rank-major or "zigzag";
    see module docstring).  Returns the attention output in the same
    layout.  Must run under shard_map/pmap with ``axis_name`` bound.
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring-attention layout {layout!r}")
    if layout == "zigzag":
        if not causal:
            # Without a causal mask every hop is fully visible — zigzag
            # has nothing to balance; contiguous is identical and simpler.
            layout = "contiguous"
        else:
            return _ring_attention_zigzag(q, k, v, axis_name=axis_name,
                                          scale=scale)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    T = q.shape[1]
    if scale is None:
        scale = 1.0 / (q.shape[3] ** 0.5)
    pos_q = my * T + jnp.arange(T)

    def hop(s, k_blk, v_blk):
        src = (my - s) % n
        pos_k = src * T + jnp.arange(T)
        return _block_attend(q, k_blk, v_blk, pos_q, pos_k, causal, scale)

    return _ring_scan(q, k, v, axis_name, hop)


def _ring_scan(q, k, v, axis_name, hop):
    """The n-hop K/V ring with the online-softmax merge, shared by both
    layouts.  ``hop(s, k_blk, v_blk) -> (block_max, block_sum, block_out)``
    computes hop ``s``'s contribution for all local query rows (identity
    elements — -big/0/0 — for rows the hop doesn't touch)."""
    n = lax.axis_size(axis_name)
    B, T, H, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(s, carry):
        o, m, l, kv = carry
        bm, bs, bo = hop(s, *kv)
        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)            # rescale old accumulators
        beta = jnp.exp(bm - new_m)            # rescale this block
        l = l * alpha + bs * beta
        o = o * alpha.transpose(0, 2, 1)[..., None] + \
            bo * beta.transpose(0, 2, 1)[..., None]
        # Rotate K/V to the next ring position; overlaps with next block's
        # math under XLA's async collective scheduling.
        kv = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm=perm), kv)
        return o, new_m, l, kv

    # Constant inits carry no data dependence on the shard index, so VMA
    # tracking (check_vma=True) classifies them invariant while the loop
    # body produces varying values — the carry types would mismatch.  Cast
    # them to the axes the inputs actually vary over (no-op when unchecked).
    vma = jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    m0 = jnp.full((B, H, T), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    if vma:
        from horovod_tpu.parallel._vma import ensure_varying
        o0, m0, l0 = (ensure_varying(a, tuple(vma)) for a in (o0, m0, l0))
    o, m, l, _ = lax.fori_loop(0, n, body, (o0, m0, l0, (k, v)))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_attention_zigzag(q, k, v, *, axis_name, scale):
    """Causal ring attention over zigzag-laid-out shards.

    Rank r holds chunks (r, 2n-1-r) of the 2n-chunk global sequence.  On
    each hop the causal structure is known per rank pair, so instead of a
    dense masked block we compute only the visible region:

    * ``src == my`` — the local diagonal: dense with the causal mask;
    * ``src < my`` (causal past): its early chunk is fully visible to every
      local query, its late chunk fully masked → all queries × half K;
    * ``src > my`` (causal future): both its chunks are fully visible to the
      local *late* chunk only → half queries × all K.

    Every rank lands in the same-cost branch on every non-diagonal hop —
    the load imbalance of the contiguous layout disappears and per-hop
    FLOPs are halved.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    if T % 2:
        raise ValueError(f"zigzag layout needs an even local length, got {T}")
    C = T // 2
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    pos_q = _zigzag_pos(my, n, C)

    def hop(s, k_blk, v_blk):
        src = (my - s) % n
        pos_k = _zigzag_pos(src, n, C)

        def diag(_):
            return _block_attend(q, k_blk, v_blk, pos_q, pos_k, True, scale)

        def past(_):
            return _block_attend(q, k_blk[:, :C], v_blk[:, :C],
                                 pos_q, pos_k[:C], False, scale)

        def future(_):
            bm, bs, bo = _block_attend(q[:, C:], k_blk, v_blk,
                                       pos_q[C:], pos_k, False, scale)
            # Early local queries see nothing from this shard: identity
            # elements for the online-softmax merge.
            pad_m = jnp.full((B, H, C), _NEG_BIG, jnp.float32)
            pad_s = jnp.zeros((B, H, C), jnp.float32)
            pad_o = jnp.zeros((B, C, H, D), jnp.float32)
            return (jnp.concatenate([pad_m, bm], axis=2),
                    jnp.concatenate([pad_s, bs], axis=2),
                    jnp.concatenate([pad_o, bo], axis=1))

        branch = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
        return lax.switch(branch, (diag, past, future), None)

    return _ring_scan(q, k, v, axis_name, hop)


def block_diffusion_allowed(rows: int, block: int):
    """The block-diffusion mask as a plain (rows, rows) boolean matrix,
    ``[query, key]``: the rows are a clean copy of a sequence of ``rows /
    2`` tokens and then a noised copy, in blocks of ``block`` tokens.  A
    clean query reads the clean keys of its own and earlier blocks; a
    noised query the clean keys of earlier blocks and the noised keys of
    its own block."""
    half = rows // 2
    noised = jnp.arange(rows) >= half
    blk = (jnp.arange(rows) % half) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return jnp.where(
        q_noised,
        jnp.where(k_noised, k_blk == q_blk, k_blk < q_blk),
        ~k_noised & (k_blk <= q_blk))


def window_allowed(rows: int, window: int):
    """The causal window as a plain (rows, rows) boolean matrix, ``[query,
    key]``: query ``i`` reads key ``j`` iff ``0 <= i - j < window`` — itself
    and the ``window - 1`` keys before it."""
    apart = jnp.arange(rows)[:, None] - jnp.arange(rows)[None, :]
    return (apart >= 0) & (apart < window)


def full_attention(q, k, v, *, causal: bool = True,
                   scale: Optional[float] = None,
                   q_offset: int = 0, k_offset: int = 0, mask=None):
    """Single-device reference attention (same math, no ring) — used by the
    tests as the oracle and by the transformer when sequence parallelism is
    off.  ``mask=("block_diffusion", L)`` or ``("window", W)``: the flash
    family's positional mask in the causal mask's place, as a plain boolean
    matrix (:func:`block_diffusion_allowed`, :func:`window_allowed`)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    pos_q = q_offset + jnp.arange(Tq)
    pos_k = k_offset + jnp.arange(Tk)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        allowed = (window_allowed if mask[0] == "window"
                   else block_diffusion_allowed)(Tq, mask[1])
        logits = jnp.where(allowed[None, None], logits, _NEG_BIG)
    elif causal:
        allowed = pos_k[None, :] <= pos_q[:, None]
        logits = jnp.where(allowed[None, None, :, :], logits, _NEG_BIG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
