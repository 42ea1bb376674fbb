"""Decoder-only transformer LM — the long-context model family.

Not in the reference (it predates transformers' dominance and is DP-only);
included because long-context sequence parallelism is first-class in this
framework.  TPU-first choices: bf16 compute / f32 params, static shapes,
pre-norm blocks, and a pluggable attention implementation:

* ``attn="full"``        — single-shard full attention (no SP),
* ``attn="flash"``       — single-shard Pallas flash attention
  (:mod:`horovod_tpu.ops.flash_attention`): same math, O(T·d) HBM traffic
  instead of the dense (T, T) buffer — 4-29x faster than the XLA dense
  path on v5e (docs/long-context.md),
* ``attn="ring"``        — :func:`horovod_tpu.parallel.ring_attention` (K/V
  ring over the mesh axis; sequence length scales with chips),
* ``attn="ring_zigzag"`` — ring attention with the load-balanced zigzag
  shard layout (tokens pre-permuted with
  :func:`~horovod_tpu.parallel.ring_attention.zigzag_indices`; ~2x faster
  causal hops),
* ``attn="ulysses"``     — :func:`horovod_tpu.parallel.ulysses` (all-to-all
  head/sequence re-shard),
* ``attn="ulysses_flash"`` — Ulysses with the Pallas flash kernel as the
  local attention (linear memory for the full-sequence local compute).

With ``attn != "full"`` the module must run inside shard_map with the
sequence dimension sharded on ``sp_axis``; position embeddings are computed
from the global position of each shard (rank offset, or the zigzag chunk
positions under ``ring_zigzag``).

The defaults are the GPT-2 block (LayerNorm, learned positions, a 4x GELU
MLP).  The block of today's sparse-expert LMs is options on the same
modules: ``norm="rms"``, ``pos="rotary"``, ``qk_norm=True`` (RMSNorm over
the whole q and k vectors before the split into heads) and
``moe_experts > 0`` (the MLP becomes
:class:`~horovod_tpu.parallel.moe.DroplessMoE`: SwiGLU experts, top-k, no
dropped token).  :func:`OLMoELM` is OLMoE-1B-7B's setting of them.

``pattern`` replaces the stack of blocks by a hybrid one, a letter a
layer.  ``M``, ``*``, ``S`` and ``E`` are ONE sub-layer behind a pre-norm
residual (``x + f(norm(x))``): ``M`` a Mamba-2 mixer
(:class:`~horovod_tpu.models.ssm.Mamba2Mixer`), ``*`` grouped-query
attention (:class:`GroupedQueryAttention`) with no positions and no
norm, ``S`` grouped-query attention with per-head QK-norm, rotary
positions and the keys a learned indexer selects
(:mod:`horovod_tpu.ops.sparse_select`), ``E`` a ``DroplessMoE``.
:func:`NemotronHLM` is the Nemotron-H setting, :func:`KeyeLM` the
Keye-VL-2.0 language tower's.  Those are one causal tower under
next-token cross-entropy.  ``diffusion=dict(block=L, mask_id=...)`` trains
a stack of ``S`` and ``E`` layers under the block-diffusion objective
instead: a clean and a noised copy of every sequence in one pass of ``2 T``
rows, positions repeated, the flash family's positional block mask in
every ``S`` layer, the noised half returned (:func:`SDARLM`); there is no
conditioning between towers and no sampler.  ``L`` and ``F`` are TWO sub-layers, a
mixer and then a dense SwiGLU MLP (:class:`SwiGLU`), each with the norm
on its OUTPUT (OLMo 2's residual form: ``h = x + norm(mixer(x))``,
``y = h + norm(mlp(h))``): ``L`` a Gated DeltaNet linear-attention mixer
(:class:`~horovod_tpu.models.linear_attention.GatedDeltaNet`), ``F`` full
multi-head attention (:class:`Attention`, with the model's ``qk_norm``,
no positions).  :func:`OlmoHybridLM` is the Olmo-Hybrid setting.  ``m``
and ``a`` are TWO PRE-norm sub-layers with the model's
``residual_multiplier`` ``r`` on each one's output (``h = x + r
mixer(norm(x))``, ``y = h + r mlp(norm(h))``): ``m`` a Mamba-2 mixer,
``a`` grouped-query attention without positions whose softmax is scaled
by ``attn_scale``, each followed by a dense :class:`SwiGLU`.  With them
go ``embedding_multiplier`` (on the embedded tokens),
``logits_scaling`` (the final hidden states are divided by it) and
``tie_head`` (no ``head`` parameter: the logits are the hidden states
times the embedding table transposed, :meth:`TransformerLM.head_kernel`,
and the table's gradient is the gather's plus the head's).
:func:`GraniteHybridLM` is the Granite 4.0-H setting.  ``Z`` is TWO
pre-norm sub-layers joined to the residual by a learned scale and bias on
each side (:class:`ResidualMerge`): compressed convolutional attention
(:class:`CompressedConvAttention`, attention inside a latent whose q and k
pass through two causal convolutions) and then a ``DroplessMoE`` whose
router is a small network with a state that the stack hands from one ``Z``
layer to the next.  :func:`Zaya1LM` is the ZAYA1 setting.  ``d`` and ``x``
are TWO pre-norm sub-layers (``h = x + f(norm(x))``, ``y = h +
g(norm(h))``) whose first is multi-head latent attention
(:class:`LatentAttention`: queries, keys and values projected UP from
low-rank latents, one rotary key all heads share, heads wider than their
values; ``mla=`` holds its widths) and whose second is a dense
:class:`SwiGLU` ``mlp_hidden`` wide (``d``) or a ``DroplessMoE`` (``x``).
:func:`JoyAIFlashLM` is the JoyAI-LLM-Flash setting.  ``W`` and ``D`` are
ONE pre-norm sub-layer like ``S`` and ``E``: ``W`` the stack's WINDOWED
attention — ``S``'s :class:`GroupedQueryAttention` under a causal window,
with the head count and rotary table ``window=`` gives it in place of the
stack's — and ``D`` a dense :class:`SwiGLU` ``mlp_hidden`` wide; with them
``attn_gate`` (a sigmoid gate on the heads' output), ``rope_width`` (a
partial rotary factor) and ``rope_scaling`` (YaRN).  :func:`LagunaLM` is the
Laguna-XS.2 setting: global and windowed attention layers with their own
head counts in one pattern.

``k`` and ``K`` are TWO pre-norm sub-layers like ``d`` and ``x`` whose first
is a linear-attention mixer with a decay a key channel
(:class:`~horovod_tpu.models.linear_attention.KimiDeltaAttention`, ``lin=``
its fields) and whose second is a dense :class:`SwiGLU` (``k``) or a
``DroplessMoE`` (``K``).  With ``pos="none"`` the ``d`` and ``x`` layers'
latent attention runs without positions, and ``mla=dict(q_latent=None,
...)`` without a query latent (:class:`LatentAttention`).
:func:`KimiLinearLM` is the Kimi-Linear setting: three ``K`` layers to one
``x``.

``mtp`` adds a multi-token-prediction module behind a pattern stack
(:class:`MultiTokenPrediction`): from the stack's final hidden states and
the NEXT token's embedding, through the shared table, it makes a second
hidden state that the shared head turns into a prediction of the token
after the next; :func:`horovod_tpu.ops.losses.multi_token_xent` is the
loss of both.  ``moe=dict(latent=...)`` puts an ``E`` layer's routed
experts in a latent between a shared down- and up-projection
(``DroplessMoE``).  :func:`Nemotron3SuperLM` sets both.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.layer_notes import note_layer
from horovod_tpu.ops import _pallas, cca_passes
from horovod_tpu.ops.flash_attention import (
    auto_block, flash_attention_auto, flash_qkv_proj, kv_resident_bytes,
    mask_tile_counts, select_tile_fetches, window_pairs)
from horovod_tpu.parallel.mesh import RANKS_AXIS
from horovod_tpu.parallel.moe import DroplessMoE
from horovod_tpu.parallel.ring_attention import (
    full_attention, ring_attention, zigzag_shard_positions)
from horovod_tpu.parallel.ulysses import ulysses_attention


def _norm(kind: str, eps: float, dtype, name: str):
    """The block's normalisation: ``"layer"`` (LayerNorm, GPT-2) or
    ``"rms"`` (RMSNorm, no mean and no bias)."""
    if kind == "layer":
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == "rms":
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError(f"unknown norm: {kind!r}")


def yarn_frequencies(width: int, theta: float, *, factor: float,
                     original_max_len: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """YaRN's rotary frequencies (Peng et al., arXiv:2309.00071, the
    "NTK-by-parts" table) for a rotated width ``R = width``: ``(R/2,)``
    float32.  With ``f_m = theta^(-2m/R)`` the plain table and ``c(r) = R
    ln(original_max_len / (2 pi r)) / (2 ln theta)`` the channel whose
    wavelength turns ``r`` times over the original context, ``low =
    floor(c(beta_fast))`` and ``high = ceil(c(beta_slow))`` clamped to ``[0,
    R - 1]``, ``ramp_m = clip((m - low) / (high - low), 0, 1)``, the table is
    ``f_m (1 - ramp_m) + (f_m / factor) ramp_m``: the fast channels as they
    were, the slow ones stretched ``factor`` times, a linear blend between.
    Made at trace time in float64, so the table is a constant."""
    m = np.arange(width // 2, dtype=np.float64)
    plain = float(theta) ** (-2.0 * m / width)

    def channel(turns):
        return (width * math.log(original_max_len / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), width - 1)
    ramp = np.clip((m - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def apply_rotary(x, pos, theta: float = 10000.0,
                 width: Optional[int] = None, scaling=None):
    """Rotary position embedding, rotate-half form, on ``x`` (B, T, H, D)
    at positions ``pos`` (T,).  ``width`` (even, default ``D``) is the
    rotated width ``R``: the pair ``(x[i], x[i + R/2])``, ``i < R/2``, is
    turned by ``pos · theta^(-2i/R)`` and the channels ``R .. D - 1`` pass
    unchanged (a partial rotary factor of ``R / D``).  Angles and the
    rotation in float32.  ``scaling`` (``dict(factor=, original_max_len=,
    beta_fast=, beta_slow=, attention_factor=)``): YaRN — the frequencies
    are :func:`yarn_frequencies`' and cos and sin are multiplied by
    ``attention_factor`` (default ``0.1 ln(factor) + 1``), so that a rotated
    ``q · k`` carries its square; under the trace scope ``rope/yarn``."""
    D = x.shape[-1]
    width = D if width is None else width
    if width % 2 or not 0 < width <= D:
        raise ValueError(f"rotated width {width} of a head of {D}")
    half = width // 2
    if scaling is None:
        freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
        scope, factor = contextlib.nullcontext(), None
    else:
        table = dict(scaling)
        factor = jnp.float32(table.pop(
            "attention_factor", 0.1 * math.log(table["factor"]) + 1.0))
        freq = yarn_frequencies(width, theta, **table)
        scope = jax.named_scope("rope/yarn")
    with scope:
        angle = pos.astype(jnp.float32)[:, None] * freq[None]   # (T, R/2)
        def over_heads(wave):
            wave = wave if factor is None else wave * factor
            return wave[None, :, None, :]

        cos = over_heads(jnp.cos(angle))
        sin = over_heads(jnp.sin(angle))
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:width].astype(jnp.float32)
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if width < D:
            turned.append(x[..., width:].astype(jnp.float32))
        return jnp.concatenate(turned, axis=-1).astype(x.dtype)


class _QKVKernel(nn.Module):
    """Declares the same ``kernel`` param an ``nn.Dense(features,
    use_bias=False)`` would (name, shape, lecun-normal init) and returns
    it raw — used when the matmul itself lives inside a fused op, so the
    param tree stays interchangeable with the plain-Dense path."""
    features: int

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (in_features, self.features), jnp.float32)


class Attention(nn.Module):
    num_heads: int
    attn: str = "full"
    sp_axis: Any = RANKS_AXIS
    dtype: Any = jnp.bfloat16
    # RMSNorm over the whole (C-wide) q and k vectors, before the heads
    # are split (OLMoE's QK-norm), with this epsilon.
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # Rotary positions on q and k with this base; None: none here (the
    # model adds learned position embeddings).
    rope_theta: Optional[float] = None

    @nn.compact
    def __call__(self, x, pos=None):
        B, T, C = x.shape
        D = C // self.num_heads
        blk = auto_block(T)
        if (self.attn == "flash" and D % 128 == 0
                and (blk == T or blk >= 64)
                and not self.qk_norm and self.rope_theta is None):
            # Fused-projection fast path: one op computes qkv and runs
            # the kernels straight off it through head-offset BlockSpecs
            # — no split slice, no (B, T, H, D) transpose, and the
            # (B, T, 3C) projection is recomputed in the backward rather
            # than held as a residual.
            w = _QKVKernel(3 * C, name="qkv")(C)
            out = flash_qkv_proj(
                x.astype(self.dtype), w, self.num_heads, causal=True,
                interpret=_pallas.interpret())
            return nn.Dense(C, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, name="proj")(out)
        qkv = nn.Dense(3 * C, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if self.qk_norm:
            q = _norm("rms", self.norm_eps, self.dtype, "q_norm")(q)
            k = _norm("rms", self.norm_eps, self.dtype, "k_norm")(k)
        q = q.reshape(B, T, self.num_heads, D)
        k = k.reshape(B, T, self.num_heads, D)
        v = v.reshape(B, T, self.num_heads, D)
        if self.rope_theta is not None:
            pos = jnp.arange(T) if pos is None else pos
            q = apply_rotary(q, pos, self.rope_theta)
            k = apply_rotary(k, pos, self.rope_theta)
        if self.attn == "ring":
            out = ring_attention(q, k, v, axis_name=self.sp_axis,
                                 causal=True)
        elif self.attn == "ring_zigzag":
            out = ring_attention(q, k, v, axis_name=self.sp_axis,
                                 causal=True, layout="zigzag")
        elif self.attn == "ulysses":
            out = ulysses_attention(q, k, v, axis_name=self.sp_axis,
                                    causal=True)
        elif self.attn == "full":
            out = full_attention(q, k, v, causal=True)
        elif self.attn == "flash":
            out = flash_attention_auto(q, k, v, causal=True)
        elif self.attn == "ulysses_flash":
            # Ulysses re-shard with the Pallas kernel as the local
            # attention — linear memory for the full-sequence local
            # compute instead of the dense (T, T) logits.
            out = ulysses_attention(q, k, v, axis_name=self.sp_axis,
                                    causal=True,
                                    attn_fn=flash_attention_auto)
        else:
            raise ValueError(f"unknown attention impl: {self.attn!r}")
        out = out.reshape(B, T, C)
        return nn.Dense(C, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, name="proj")(out)


class GroupedQueryAttention(nn.Module):
    """Attention of ``num_heads`` query heads over ``kv_heads``
    key-value heads of ``head_dim`` (query head ``h`` reads KV head
    ``h // (num_heads / kv_heads)``) under the call's mask — the causal one
    unless the call names another —, no bias; the heads' total width need
    not be the model's.  Keys and values are of one width here (the flash
    family takes values of another: :class:`LatentAttention`).  Parameters ``q``, ``kv`` (keys | values) and
    ``proj``.  ``attn="flash"`` reads the grouped keys and values in place
    (:func:`~horovod_tpu.ops.flash_attention.flash_attention`); ``"full"``
    repeats them for the dense oracle.

    As it stands: no position encoding, no norm.  ``qk_norm``: RMSNorm
    over each head's ``head_dim`` channels of q and of k (learned scales
    ``q_norm``, ``k_norm``, epsilon ``norm_eps``), before the positions.
    ``rope_theta``: rotary positions on q and k with this base, at the
    call's ``pos`` ((T,), the rows' positions in their sequence; default
    ``arange(T)``, a row's index).  The call's ``mask``: the flash family's
    positional block mask in the causal mask's place (``("block_diffusion",
    L)``: the rows are a clean and a noised copy of one sequence, ``pos``
    then ``[0 .. T/2 - 1]`` twice; :func:`~horovod_tpu.ops.flash_attention.
    flash_attention`), the kernels under the trace scope ``bd/attend`` and
    the counters ``attn.bd_block``, ``attn.bd_live_pairs``,
    ``attn.bd_live_tiles``, ``attn.bd_visited_tiles`` (the forward's tiles,
    all query heads; in the resident form the area of its sub-tiles),
    ``attn.bd_grid_steps``, ``attn.bd_live_steps`` (the forward's grid and
    the steps of it that compute a tile) and ``attn.bd_visited_pairs`` (the
    pairs a head's forward computes scores of: :func:`~horovod_tpu.ops.
    flash_attention.mask_tile_counts`); not with an ``indexer``.
    ``scale``: the factor on ``q k^T`` (default ``head_dim ** -0.5``).
    ``rope_width`` and ``rope_scaling``: :func:`apply_rotary`'s rotated
    width (a partial rotary factor) and YaRN table, on q and k alike.
    ``window``: a causal window in the causal mask's place — a query reads
    itself and the ``window - 1`` keys before it (the flash family's
    ``("window", W)`` mask; not with a call's ``mask`` nor an ``indexer``) —,
    the kernels under the trace scope ``swa/attend`` and the counters
    ``attn.window``, ``attn.win_live_pairs``, ``attn.win_live_tiles``,
    ``attn.win_visited_tiles`` (the forward's tiles, all query heads),
    ``attn.win_grid_steps``, ``attn.win_live_steps`` (the forward's grid and
    the steps of it that compute a tile, all query heads) and
    ``attn.win_visited_pairs`` (the pairs a head's forward computes scores
    of, against ``attn.win_live_pairs``: :func:`~horovod_tpu.ops.
    flash_attention.mask_tile_counts`).
    ``out_gate``: a sigmoid gate on the heads' output before ``proj``, one
    value a head from the layer's input through the parameter ``gate``
    (``dim -> num_heads``), under the trace scope ``attn/gate``.
    ``heads_kind``: the label under which the layer counts
    its query heads, ``attn.heads#kind=<label>`` (a stack whose attention
    layers differ in their heads sets it).
    ``make_train_step`` counts ``attn.merged_heads``: the query heads a
    step sends through the flash family's path for heads off the 128-lane
    width, which repeats the grouped keys and values and merges the heads
    into the batch with a transpose each way (0 at a lane-aligned
    ``head_dim``).

    ``indexer`` (``dict(num_heads=, head_dim=, topk=)``, optionally
    ``tile=``): learned sparse attention (:mod:`horovod_tpu.ops.
    sparse_select`).  From the layer's input, DETACHED, an indexer of that
    many heads over one key head (parameters ``index_q``, ``index_k``,
    ``index_w``; rotary positions on its q and k where the layer has them)
    scores every causal key of a query; the ``topk`` best are the only keys
    the query's heads read; and ``L_I``, the KL term that is the indexer's
    only gradient, is sown as the intermediate ``index_kl``
    (:func:`index_losses` sums a model's), beside ``selected_per_query``,
    ``live_tiles`` and ``tie_tiles`` (the share of the top-k's strips whose
    tie bisection ran: the kernel ``index_threshold`` runs it only where a
    row has more keys at its k-th score than room for them).  Trace scopes
    ``index/project``, ``index/scores``, ``index/topk`` (the kernel
    ``index_threshold`` writes the map there), ``index/select``,
    ``index/kl`` and ``flash_select``."""
    num_heads: int
    kv_heads: int
    head_dim: int
    attn: str = "flash"
    dtype: Any = jnp.bfloat16
    qk_norm: bool = False
    norm_eps: float = 1e-6
    rope_theta: Optional[float] = None
    indexer: Any = None
    scale: Optional[float] = None
    rope_width: Optional[int] = None
    rope_scaling: Any = None
    window: Optional[int] = None
    out_gate: bool = False
    heads_kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, pos=None, mask=None):
        B, T, C = x.shape
        H, Hkv, D = self.num_heads, self.kv_heads, self.head_dim
        if self.window is not None and (mask is not None
                                        or self.indexer is not None):
            raise ValueError(
                f"a layer with window={self.window} runs under its own "
                f"mask: no call's mask={mask!r}, no indexer")

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, name=name)

        q = dense(H * D, "q")(x).reshape(B, T, H, D)
        k, v = jnp.split(dense(2 * Hkv * D, "kv")(x), 2, axis=-1)
        k, v = k.reshape(B, T, Hkv, D), v.reshape(B, T, Hkv, D)
        if self.qk_norm:
            q = _norm("rms", self.norm_eps, self.dtype, "q_norm")(q)
            k = _norm("rms", self.norm_eps, self.dtype, "k_norm")(k)
        if self.rope_theta is not None:
            table = {name: value for name, value in (
                ("width", self.rope_width), ("scaling", self.rope_scaling))
                if value is not None}
            q, k = (apply_rotary(a, jnp.arange(T) if pos is None else pos,
                                 self.rope_theta, **table) for a in (q, k))
        if self.indexer is not None:
            if mask is not None:
                raise ValueError("the indexer selects among causal keys: "
                                 f"no mask={mask!r} with it")
            out = self._selected(x, q, k, v, dense)
            return dense(C, "proj")(out.reshape(B, T, H * D))
        if self.attn not in ("flash", "full"):
            raise ValueError("grouped-query attention runs attn='flash' or "
                             f"'full', not {self.attn!r}")
        counters = {"attn.merged_heads": (
            B * H if self.attn == "flash" and D % 128 else 0)}
        if self.heads_kind is not None:
            counters[f"attn.heads#kind={self.heads_kind}"] = H
        if self.window is not None:
            mask = ("window", self.window)
            with jax.named_scope("swa/attend"):
                out = self._attend(q, k, v, mask)
            tiles = (mask_tile_counts(q, k, mask) if self.attn == "flash"
                     else {"live_pairs": B * window_pairs(T, self.window)})
            counters.update({"attn.window": self.window, **{
                f"attn.win_{name}": count for name, count in tiles.items()}})
        elif mask is not None:
            with jax.named_scope("bd/attend"):
                out = self._attend(q, k, v, mask)
            tiles = (mask_tile_counts(q, k, mask) if self.attn == "flash"
                     else {"live_pairs": B * (T // 2) * (T // 2 + mask[1])})
            counters.update({"attn.bd_block": mask[1], **{
                f"attn.bd_{name}": count for name, count in tiles.items()}})
        else:
            out = self._attend(q, k, v, None)
        if self.out_gate:
            with jax.named_scope("attn/gate"):
                gate = nn.sigmoid(dense(H, "gate")(x))
                out = out * gate[..., None]
        note_layer(self.path, counters)
        return dense(C, "proj")(out.reshape(B, T, H * D))

    def _attend(self, q, k, v, mask):
        """The heads' output under the causal mask, or ``mask``."""
        masked = {} if mask is None else {"mask": mask}
        if self.attn == "flash":
            return flash_attention_auto(q, k, v, causal=True,
                                        scale=self.scale, **masked)
        rep = self.num_heads // self.kv_heads
        return full_attention(q, jnp.repeat(k, rep, axis=2),
                              jnp.repeat(v, rep, axis=2), causal=True,
                              scale=self.scale, **masked)

    def _selected(self, x, q, k, v, dense):
        """The heads' output over the keys the indexer selects, with
        ``L_I`` and the selection's counters sown."""
        from horovod_tpu.ops import sparse_select

        if self.attn not in ("flash", "full"):
            raise ValueError("grouped-query attention runs attn='flash' or "
                             f"'full', not {self.attn!r}")
        B, T, _ = x.shape
        HI, DI, topk = (self.indexer[key] for key in
                        ("num_heads", "head_dim", "topk"))
        with jax.named_scope("index/project"):
            xi = lax.stop_gradient(x)
            qi = dense(HI * DI, "index_q")(xi).reshape(B, T, HI, DI)
            ki = dense(DI, "index_k")(xi).reshape(B, T, 1, DI)
            w = dense(HI, "index_w")(xi)
            if self.rope_theta is not None:
                qi = apply_rotary(qi, jnp.arange(T), self.rope_theta)
                ki = apply_rotary(ki, jnp.arange(T), self.rope_theta)
            ki = ki[:, :, 0]
        if self.attn == "full":
            out, kl, select = sparse_select.sparse_attention_reference(
                q, k, v, qi, ki, w, topk)
            ties = jnp.float32(1.0)
        else:
            interpret = _pallas.interpret()
            tile = ({"tile": self.indexer["tile"]}
                    if "tile" in self.indexer else {})
            with jax.named_scope("index"):
                select, lse_i, ties = sparse_select.index_select_counted(
                    qi, ki, w, topk, interpret=interpret, **tile)
            out, lse = flash_attention_auto(q, k, v, causal=True,
                                            select=select)
            with jax.named_scope("index"):
                kl = sparse_select.index_kl(qi, ki, w, q, k, lse, select,
                                            lse_i, interpret=interpret)
        with jax.named_scope("index/counters"):
            per_query, live = sparse_select.selection_counters(
                select, auto_block(T) or T)
        self.sow("intermediates", "index_kl", kl)
        self.sow("intermediates", "selected_per_query", per_query)
        self.sow("intermediates", "live_tiles", live)
        self.sow("intermediates", "tie_tiles", ties)
        self.sow("intermediates", "select", select)
        pairs = sum(min(t + 1, topk) for t in range(T))
        note_layer(self.path, {
            "attn.causal_pairs": B * T * (T + 1) // 2,
            "attn.selected_pairs": B * pairs,
            "attn.index_flops": B * 2 * HI * DI * T * (T + 1) // 2,
            "attn.select_bytes": B * T * T,
            "attn.select_tile_fetches": (
                0 if self.attn == "full" else select_tile_fetches(q, k))})
        return out


def _cca_mix_xla(q0, k0, w0, b0, w1, b1, temp, *, taps, dtype, rope_theta,
                 width):
    """``q"`` and ``k"`` from ``q~`` and ``k~`` in ``jax.numpy``: the
    form :func:`~horovod_tpu.ops.cca_passes.cca_mix`'s kernels are held
    to."""
    B, T, H, D = q0.shape
    G = k0.shape[2]
    t0, t1 = taps
    f32 = jnp.float32
    with jax.named_scope("cca/qk_mean"):
        grouped = q0.astype(f32).reshape(B, T, G, H // G, D)
        m_q = 0.5 * (grouped + k0.astype(f32)[:, :, :, None])
        m_k = m_q.mean(axis=3)
        m_q = m_q.reshape(B, T, H, D)

    with jax.named_scope("cca/conv"):
        z = jnp.concatenate([q0, k0], axis=2).reshape(B, T, (H + G) * D)
        z = jnp.pad(z, ((0, 0), (t0 + t1 - 2, 0), (0, 0))).astype(f32)
        rows = T + t1 - 1
        z1 = b0 + sum(w0[:, i] * z[:, i:i + rows] for i in range(t0))
        z1 = z1.astype(dtype).reshape(B, rows, H + G, D)
        # The taps of a head side by side: one product of depth t1 · D.
        taps = jnp.concatenate([z1[:, i:i + T] for i in range(t1)],
                               axis=-1)
        z2 = jnp.einsum(
            "bthc,hcd->bthd", taps,
            w1.reshape(H + G, t1 * D, D).astype(dtype)).astype(
                f32) + b1
        q1, k1 = z2[:, :, :H] + m_q, z2[:, :, H:] + m_k

    with jax.named_scope("cca/norm_rope"):
        def unit(x):
            return x * lax.rsqrt(jnp.maximum(
                jnp.sum(x * x, axis=-1, keepdims=True), 1e-24))

        pos = jnp.arange(T)
        q = apply_rotary(unit(q1) * D ** 0.5, pos, rope_theta,
                         width).astype(dtype)
        k = apply_rotary(unit(k1) * (D ** 0.5 * temp[:, None]), pos,
                         rope_theta, width).astype(dtype)
    return q, k


class CompressedConvAttention(nn.Module):
    """Compressed convolutional attention (CCA; Zyphra, arXiv:2510.04476,
    as ZAYA1 runs it): causal attention of ``num_heads`` query heads over
    ``kv_heads`` key-value heads of ``head_dim`` INSIDE a latent narrower
    than the model — nothing is projected up again before the heads; the
    output projection ``proj`` takes the heads' ``num_heads · head_dim``
    channels back to the model's width.  On the layer's normed input ``u``
    (B, T, C), with ``g = num_heads / kv_heads``:

    * ``cca/project``: ``q~ = u W_q`` (H heads), ``k~ = u W_k`` (G heads),
      ``u W_v1``, ``u W_v2`` (``G · D / 2`` channels each), no bias.
    * ``cca/qk_mean``: ``m_q[h] = (q~[h] + k~[h // g]) / 2`` and ``m_k[j]``
      the mean of its group's ``m_q``, from q~ and k~ BEFORE the
      convolutions.
    * ``cca/conv``: over the ``(H + G) · D`` channels ``z = [q~ | k~]``
      with ``taps[0] - 1 + taps[1] - 1`` zero rows in front (causal), a
      depth-wise convolution of ``taps[0]`` taps with bias (``conv0``) and
      then, nothing between, a convolution of ``taps[1]`` taps that mixes
      the ``D`` channels of each head with a ``(D, D)`` matrix a tap and
      head, with bias (``conv1``); both valid, so ``T`` rows come out.
      ``q' = z_q + m_q``, ``k' = z_k + m_k``.
    * ``cca/shift``: the values ``[u_t W_v1 | u_{t-1} W_v2]`` (``u_{-1} =
      0``) as ``G`` heads of ``D``: the second half of the value channels
      reads the previous token.
    * ``cca/norm_rope``: ``q" = √D q' / ‖q'‖₂`` and ``k" = τ_j √D k' /
      ‖k'‖₂`` a head (``temp`` ``τ`` (G,), learned, init 1), then rotary
      positions (``rope_theta``, rotate-half) on the first
      ``rotary_fraction · D`` channels of each head.
    * softmax-causal attention at the scale ``D^-1/2``, query head ``h``
      over KV head ``h // g`` (``attn="flash"``: :func:`flash_attention_auto`
      reading the grouped keys and values in place; ``"full"``: the dense
      oracle), then ``proj``.

    Between ``cca/project`` and the flash kernels, everything but the
    value shift — the QK-mean, both convolutions, the norm with its
    temperature and the rotation — is one Pallas kernel forward and one
    backward (:func:`horovod_tpu.ops.cca_passes.cca_mix`, under the scope
    ``cca/conv``: ``q~`` and ``k~`` read once, ``q"`` and ``k"`` written
    once, float32 inside with the two roundings ``z1`` and the store)
    where ``cca_passes._plan`` takes the shapes: ``attn="flash"``,
    ``head_dim`` in whole 128-lane tiles, two-byte activations, a sequence
    in whole strips of rows.  Otherwise — the tiny float32 shapes of the
    CPU tests, ``attn="full"``, the oracle — it is ``jax.numpy``
    (:func:`_cca_mix_xla`), float32 inside the fusions and ``dtype`` between
    them; the value shift always is.  No option picks a form.  Sown as the
    intermediate ``latent``: ``(q", k", v)`` as the kernels read them.
    ``make_train_step`` counts ``attn.latent_channels`` (q, k and v
    channels a step), ``attn.conv_taps`` (channels times taps a step) and
    ``attn.cca_kernel_rows`` (token rows a step whose passes ran as the
    kernels: ``B · T`` a layer, 0 in the ``jax.numpy`` form)."""
    num_heads: int
    kv_heads: int
    head_dim: int
    attn: str = "flash"
    dtype: Any = jnp.bfloat16
    taps: Any = (2, 2)
    rope_theta: float = 10000.0
    rotary_fraction: float = 0.5

    @nn.compact
    def __call__(self, u):
        B, T, C = u.shape
        H, G, D = self.num_heads, self.kv_heads, self.head_dim
        t0, t1 = self.taps
        if H % G or D % 2 or (G * D) % 2 or min(t0, t1) < 1:
            raise ValueError(f"compressed attention of {H} heads over {G} "
                             f"of {D} with taps {self.taps}")
        f32 = jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=f32, name=name)

        with jax.named_scope("cca/project"):
            q0 = dense(H * D, "q")(u).reshape(B, T, H, D)
            k0 = dense(G * D, "k")(u).reshape(B, T, G, D)
            v_now = dense(G * D // 2, "v1")(u)
            v_prev = dense(G * D // 2, "v2")(u)

        width = int(round(self.rotary_fraction * D / 2)) * 2
        w0 = self.param("conv0_kernel", nn.initializers.lecun_normal(
            in_axis=1, out_axis=0), ((H + G) * D, t0), f32)
        b0 = self.param("conv0_bias", nn.initializers.zeros,
                        ((H + G) * D,), f32)
        w1 = self.param("conv1_kernel", nn.initializers.lecun_normal(
            in_axis=(1, 2), out_axis=3, batch_axis=(0,)),
            (H + G, t1, D, D), f32)
        b1 = self.param("conv1_bias", nn.initializers.zeros, (H + G, D), f32)
        temp = self.param("temp", nn.initializers.ones, (G,), f32)

        interpret = _pallas.interpret()
        plan = cca_passes.cca_plan(q0, kv_heads=G, taps=self.taps,
                                   interpret=interpret)
        if self.attn == "flash" and plan.form == "kernels":
            # One kernel each way for everything between the projections
            # and the flash kernels but the value shift.
            with jax.named_scope("cca/conv"):
                q, k = cca_passes.cca_mix(
                    q0, k0, w0, b0, w1, b1, temp,
                    rope_theta=self.rope_theta, rotary_width=width,
                    plan=plan, interpret=interpret)
            kernel_rows = B * T
        else:
            q, k = _cca_mix_xla(q0, k0, w0, b0, w1, b1, temp, taps=self.taps,
                                dtype=self.dtype,
                                rope_theta=self.rope_theta, width=width)
            kernel_rows = 0

        with jax.named_scope("cca/shift"):
            v_prev = jnp.pad(v_prev, ((0, 0), (1, 0), (0, 0)))[:, :T]
            v = jnp.concatenate([v_now, v_prev], axis=-1).reshape(B, T, G, D)
        self.sow("intermediates", "latent", (q, k, v))

        if self.attn == "flash":
            out = flash_attention_auto(q, k, v, causal=True)
        elif self.attn == "full":
            out = full_attention(q, jnp.repeat(k, H // G, axis=2),
                                 jnp.repeat(v, H // G, axis=2), causal=True)
        else:
            raise ValueError("compressed attention runs attn='flash' or "
                             f"'full', not {self.attn!r}")
        note_layer(self.path, {
            "attn.latent_channels": B * T * (H + 2 * G) * D,
            "attn.conv_taps": B * T * (H + G) * D * (t0 + t1),
            "attn.cca_kernel_rows": kernel_rows})
        return dense(C, "proj")(out.reshape(B, T, H * D))


def _kernel_outputs_saveable(prim, *_, **__) -> bool:
    """The ``jax.checkpoint`` policy of :class:`LatentAttention`: what a
    Pallas kernel wrote is kept (the attention's output and row
    statistics), everything else between the latents and the output
    projection is computed again in the backward pass."""
    return prim.name == "pallas_call"


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2 report, arXiv:2405.04434,
    section 2.1; V3 report, arXiv:2412.19437, section 2.1.1) of
    ``num_heads`` heads, causal, no bias.  On the layer's normed input
    ``x`` (B, T, C), with ``n`` an RMSNorm (``norm_eps``, learned scale):

    * ``mla/q_down``: ``c_q = x W_qa`` (``q_latent`` wide, ``q_a``);
      ``mla/kv_down``: ``[c_kv | k_r] = x W_kva`` (``kv_latent`` |
      ``rope_dim``, ``kv_a``); ``mla/norm``: ``c_q ← n(c_q)``
      (``q_norm``), ``c_kv ← n(c_kv)`` (``kv_norm``) — a norm BETWEEN the
      two projections of each side.
    * ``mla/q_up``: ``[q_nope,h | q_rope,h] = c_q W_qb`` (``nope_dim`` |
      ``rope_dim`` a head, ``q_b``); ``mla/kv_up``: ``[k_nope,h | v_h] =
      c_kv W_kvb`` (``nope_dim`` | ``v_dim`` a head, ``kv_b``).
    * ``mla/rope``: rotary positions (``rope_theta``, all ``rope_dim``
      channels) on every ``q_rope,h`` and on the ONE ``k_r`` a token, which
      all heads read; a head's key is ``[k_nope,h | k_r]``.
    * ``mla/attend``: softmax-causal attention at the scale ``(nope_dim +
      rope_dim)^-1/2`` — scores ``q_nope,h · k_nope,h + q_rope,h · k_r`` —
      over values ``v_dim`` wide; ``mla/out``: ``[o_1 … o_H] W_o``
      (``proj``, ``num_heads · v_dim`` to the model's width).

    The rotation runs in the rotate-half form (:func:`apply_rotary`: the
    pair ``(i, i + rope_dim / 2)``).  A model published with adjacent pairs
    (``rope_interleave``: ``(2i, 2i + 1)``) gives the same scores under a
    fixed permutation of the ``rope_dim`` rotary columns — of each head of
    ``q_b`` and of the last ``rope_dim`` columns of ``kv_a`` — which a
    loader of its weights applies: published column ``2i`` to column ``i``,
    ``2i + 1`` to ``i + rope_dim / 2``.

    ``attn="flash"``: the flash family's kernels
    (:func:`~horovod_tpu.ops.flash_attention.flash_attention`, which takes
    values narrower than keys), q and k built in whole 128-lane tiles (192
    → 256: zeros behind the rotary part) under ``mla/rope`` so that nothing
    but the kernels runs under ``mla/attend``; ``"full"``: the dense oracle
    at the published widths.  What lies between the latents and the output
    projection is a ``jax.checkpoint``: the backward pass keeps ``c_q``,
    ``c_kv``, ``k_r`` and what the forward kernel wrote (``o`` and the row
    statistics) and projects up, rotates and pads again — q, k, v and o of
    32 heads are 40 KiB a token a layer in bfloat16 where the latents are
    4.1.  ``make_train_step`` counts ``attn.q_latent``, ``attn.kv_latent``,
    ``attn.qk_head_dim``, ``attn.v_head_dim``, ``attn.padded_lanes`` (lanes
    a head the kernels multiply beyond the published widths, q·k side + v
    side: 64 at 192 | 128, one pass of a 128-wide MXU more either way; 0
    for ``"full"``), ``attn.kv_resident_bytes`` (bytes of a head's K and V
    that a step of the forward kernel holds in VMEM: the whole rows, 6 MiB
    at T 8,192, where the flash family's plan takes its resident form; 0
    where it streams them) and ``attn.latent_residual_bytes`` (bytes a
    token the layer keeps for the backward pass between its latents and
    ``proj``)."""
    num_heads: int
    q_latent: Optional[int]
    kv_latent: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    attn: str = "flash"
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    rope_theta: Optional[float] = 10000.0

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        H, N, R, V = self.num_heads, self.nope_dim, self.rope_dim, self.v_dim
        if self.attn not in ("flash", "full"):
            raise ValueError("latent attention runs attn='flash' or 'full', "
                             f"not {self.attn!r}")
        flash = self.attn == "flash"
        # The kernels' q·k width: whole 128-lane tiles.
        lanes = -(N + R) % 128 if flash else 0

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, name=name)

        def normed(y, name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                              name=name)(y)

        c_q = x
        if self.q_latent:
            with jax.named_scope("mla/q_down"):
                c_q = dense(self.q_latent, "q_a")(x)
        with jax.named_scope("mla/kv_down"):
            c_kv, k_r = jnp.split(dense(self.kv_latent + R, "kv_a")(x),
                                  [self.kv_latent], axis=-1)
        with jax.named_scope("mla/norm"):
            if self.q_latent:
                c_q = normed(c_q, "q_norm")
            c_kv = normed(c_kv, "kv_norm")
        w_qb = _QKVKernel(H * (N + R), name="q_b")(c_q.shape[-1])
        w_kvb = _QKVKernel(H * (N + V), name="kv_b")(self.kv_latent)

        @functools.partial(jax.checkpoint, policy=_kernel_outputs_saveable)
        def attend(c_q, c_kv, k_r, w_qb, w_kvb):
            with jax.named_scope("mla/q_up"):
                q = (c_q @ w_qb.astype(self.dtype)).reshape(B, T, H, N + R)
            with jax.named_scope("mla/kv_up"):
                kv = (c_kv @ w_kvb.astype(self.dtype)).reshape(
                    B, T, H, N + V)
            rotated = self.rope_theta is not None
            with jax.named_scope("mla/rope" if rotated else "mla/lanes"):
                if rotated:
                    pos = jnp.arange(T)
                    q_r = apply_rotary(q[..., N:], pos, self.rope_theta)
                    k_r = apply_rotary(k_r[:, :, None], pos, self.rope_theta)
                else:
                    q_r, k_r = q[..., N:], k_r[:, :, None]
                zeros = jnp.zeros((B, T, H, lanes), self.dtype)
                q = jnp.concatenate([q[..., :N], q_r, zeros], axis=-1)
                k = jnp.concatenate(
                    [kv[..., :N], jnp.broadcast_to(k_r, (B, T, H, R)),
                     zeros], axis=-1)
                v = kv[..., N:]
            with jax.named_scope("mla/attend"):
                scale = (N + R) ** -0.5
                if flash:
                    # What the kernels' plan keeps of a head's K and V in
                    # VMEM, asked of the operands the kernels see.
                    resident[0] = kv_resident_bytes(q, k, v)
                    return flash_attention_auto(q, k, v, causal=True,
                                                scale=scale)
                return full_attention(q, k, v, causal=True, scale=scale)

        resident = [0]
        out = attend(c_q, c_kv, k_r, w_qb, w_kvb)
        itemsize = jnp.dtype(self.dtype).itemsize
        note_layer(self.path, {
            "attn.q_latent": self.q_latent or 0,
            "attn.kv_latent": self.kv_latent,
            "attn.qk_head_dim": N + R, "attn.v_head_dim": V,
            "attn.padded_lanes": lanes + (-V % 128 if flash else 0),
            "attn.kv_resident_bytes": resident[0],
            # c_q, c_kv and k_r; o and the (8-wide, float32) row statistics
            # where a kernel wrote them.
            "attn.latent_residual_bytes": (
                itemsize * (c_q.shape[-1] + self.kv_latent + R)
                + (itemsize * H * V + 4 * 8 * H if flash else 0))})
        with jax.named_scope("mla/out"):
            return dense(C, "proj")(out.reshape(B, T, H * V))


class ResidualMerge(nn.Module):
    """``s_x ⊙ (x + b_x) + s_y ⊙ (y + b_y)``: the residual ``x`` and a
    sub-layer's output ``y`` each under a learned bias and scale a channel
    (``scale_*`` init 1, ``bias_*`` init 0; float32 arithmetic, the
    stream's dtype out).  ``residual=False``: ``x`` passes as it is (the
    first sub-layer of a model, whose residual is the embedding)."""
    residual: bool = True

    @nn.compact
    def __call__(self, x, y):
        def vector(name, init):
            return self.param(name, init, (x.shape[-1],), jnp.float32)

        kept = x.astype(jnp.float32)
        if self.residual:
            kept = vector("scale_x", nn.initializers.ones) * (
                kept + vector("bias_x", nn.initializers.zeros))
        return (kept + vector("scale_y", nn.initializers.ones) * (
            y.astype(jnp.float32) + vector("bias_y", nn.initializers.zeros))
        ).astype(x.dtype)


def index_losses(intermediates):
    """``L_I`` summed over every sparse-attention layer that sowed it into
    ``intermediates`` (what ``model.apply(..., mutable=["intermediates"])``
    returns under that key)."""
    return sum(value for path, value
               in jax.tree_util.tree_leaves_with_path(intermediates)
               if any(getattr(key, "key", None) == "index_kl"
                      for key in path))


class SwiGLU(nn.Module):
    """The dense gated MLP: ``W_down(silu(W_gate h) * W_up h)``, ``hidden``
    wide, no bias.  Parameters ``gate``, ``up``, ``down``."""
    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32, name=name)

        return dense(h.shape[-1], "down")(
            nn.silu(dense(self.hidden, "gate")(h))
            * dense(self.hidden, "up")(h))


class PatternLayer(nn.Module):
    """One layer of a pattern stack.  ``"M"`` (submodule ``ssm``), ``"*"``,
    ``"S"`` and ``"W"`` (``attn``; ``"W"`` is ``"S"`` with a ``sub`` of its
    own — the stack's windowed attention layers, their own heads and rotary
    table), ``"E"`` (``moe``) and ``"D"`` (``mlp``, a dense :class:`SwiGLU`
    ``mlp_hidden`` wide): ``x + f(norm(x))`` with
    ``f`` the one sub-layer ``kind`` names.  ``"L"`` (``lin``) and ``"F"`` (``attn``):
    ``h = x + mixer_norm(f(x))``, then ``h + mlp_norm(mlp(h))`` with
    ``mlp`` a :class:`SwiGLU` ``mlp_hidden`` wide.  ``"m"`` (``ssm``) and
    ``"a"`` (``attn``): ``h = x + r f(norm(x))``, then ``h + r
    mlp(mlp_norm(h))`` with ``r`` the ``residual_multiplier``.  ``sub``
    holds the fields of ``f``.  ``"Z"`` (``attn``, then ``moe``): ``h =
    merge(x, cca(norm(x)))``, then ``merge(h, moe(moe_norm(h),
    router_state))`` with ``cca`` a :class:`CompressedConvAttention`,
    ``moe`` a ``DroplessMoE`` whose router carries a state and ``merge`` a
    :class:`ResidualMerge` (``merge_attn``, ``merge_moe``; ``first``: the
    model's first layer, whose ``merge_attn`` leaves the residual alone);
    ``sub`` holds the two modules' fields under ``"attn"`` and ``"moe"``,
    the layer is called with the previous ``"Z"`` layer's router state
    (None for the first) and returns ``(y, router_state)``.  ``"d"`` and
    ``"x"`` (``attn``, then ``mlp`` or ``moe``): ``h = x + mla(norm(x))``
    with ``mla`` a :class:`LatentAttention`, then ``h + mlp(mlp_norm(h))``
    with ``mlp`` a :class:`SwiGLU` ``mlp_hidden`` wide (``"d"``) or ``h +
    moe(moe_norm(h))`` with ``moe`` a ``DroplessMoE`` (``"x"``); ``sub``
    holds the two modules' fields under ``"attn"`` and ``"moe"``.
    ``"k"`` and ``"K"`` (``lin``, then ``mlp`` or ``moe``): the same two
    pre-norm sub-layers with a
    :class:`~horovod_tpu.models.linear_attention.KimiDeltaAttention` first,
    ``h = x + kda(norm(x))``; ``sub`` holds the modules' fields under
    ``"lin"`` and ``"moe"``.
    The call's ``pos`` and ``mask`` are an ``"S"`` layer's
    (:class:`GroupedQueryAttention`'s call); with a mask every other kind
    but ``"E"`` is refused."""
    kind: str
    sub: Any
    dtype: Any = jnp.bfloat16
    ln_dtype: Any = jnp.float32
    norm: str = "rms"
    norm_eps: float = 1e-5
    mlp_hidden: int = 0
    residual_multiplier: float = 1.0
    first: bool = False

    @nn.compact
    def __call__(self, x, router_state=None, pos=None, mask=None):
        def normed(y, name):
            return _norm(self.norm, self.norm_eps, self.ln_dtype, name)(y)

        if mask is not None and self.kind not in ("S", "E"):
            raise ValueError("positions handed in and a mask in the causal "
                             "one's place reach 'S' layers ('E' layers read "
                             f"neither); the layer is {self.kind!r}")

        if self.kind == "Z":
            y = CompressedConvAttention(**self.sub["attn"], dtype=self.dtype,
                                        name="attn")(normed(x, "norm"))
            h = ResidualMerge(residual=not self.first,
                              name="merge_attn")(x, y)
            y, _, _, router_state = DroplessMoE(
                **self.sub["moe"], dtype=self.dtype, norm_eps=self.norm_eps,
                name="moe")(normed(h, "moe_norm"), router_state)
            return ResidualMerge(name="merge_moe")(h, y), router_state
        if self.kind in ("d", "x", "k", "K"):
            if self.kind in ("d", "x"):
                mixer = LatentAttention(**self.sub["attn"], dtype=self.dtype,
                                        norm_eps=self.norm_eps, name="attn")
            else:
                from horovod_tpu.models.linear_attention import (
                    KimiDeltaAttention)
                mixer = KimiDeltaAttention(**self.sub["lin"],
                                           norm_eps=self.norm_eps,
                                           dtype=self.dtype, name="lin")
            h = x + mixer(normed(x, "norm"))
            if self.kind in ("d", "k"):
                return h + SwiGLU(self.mlp_hidden, self.dtype, name="mlp")(
                    normed(h, "mlp_norm"))
            return h + DroplessMoE(**self.sub["moe"], dtype=self.dtype,
                                   name="moe")(normed(h, "moe_norm"))[0]
        if self.kind in ("L", "F"):
            if self.kind == "L":
                from horovod_tpu.models.linear_attention import GatedDeltaNet
                y = GatedDeltaNet(**self.sub, norm_eps=self.norm_eps,
                                  dtype=self.dtype, name="lin")(x)
            else:
                y = Attention(**self.sub, dtype=self.dtype,
                              norm_eps=self.norm_eps, name="attn")(x)
            h = x + normed(y, "mixer_norm")
            return h + normed(SwiGLU(self.mlp_hidden, self.dtype,
                                     name="mlp")(h), "mlp_norm")
        h = normed(x, "norm")
        if self.kind in ("M", "m"):
            from horovod_tpu.models.ssm import Mamba2Mixer
            y = Mamba2Mixer(**self.sub, norm_eps=self.norm_eps,
                            dtype=self.dtype, name="ssm")(h)
        elif self.kind in ("*", "a"):
            y = GroupedQueryAttention(**self.sub, dtype=self.dtype,
                                      name="attn")(h)
        elif self.kind in ("S", "W"):
            y = GroupedQueryAttention(**self.sub, dtype=self.dtype,
                                      norm_eps=self.norm_eps,
                                      name="attn")(h, pos, mask)
        elif self.kind == "E":
            y = DroplessMoE(**self.sub, dtype=self.dtype, name="moe")(h)[0]
        elif self.kind == "D":
            y = SwiGLU(self.mlp_hidden, self.dtype, name="mlp")(h)
        else:
            raise ValueError(f"unknown layer {self.kind!r} in a pattern: "
                             "'M', '*', 'S', 'W', 'E', 'D', 'L', 'F', 'm', "
                             "'a' or 'Z', or 'd', 'x', 'k' or 'K'")
        if self.kind in ("m", "a"):
            r = self.residual_multiplier
            h = x + r * y
            return h + r * SwiGLU(self.mlp_hidden, self.dtype, name="mlp")(
                normed(h, "mlp_norm"))
        return x + y


class MultiTokenPrediction(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3 report, section 2.2,
    in Megatron-core's ``MultiTokenPredictionLayer`` form): from the
    stack's final hidden states ``h`` (B, T, d) — position ``t`` predicts
    token ``t + 1`` — and the embeddings ``e`` (B, T, d) of those very
    tokens ``t + 1``, through the model's own table::

        h' = [ n_e(e) | n_h(h) ] W_eh          W_eh (2 d, d), no bias
        h' = layer(h')  for each letter of ``pattern``
        out = n_m(h')

    whose logits through the model's own head predict token ``t + 2``.
    ``n_e``, ``n_h``, ``n_m`` are the model's norm; the layers are
    :class:`PatternLayer` ``layer_{i}`` with the stack's own ``subs`` (the
    module's parameters are its own, the table and the head are shared).
    ``make_train_step`` counts ``mtp.depth`` (prediction modules a step:
    1) and ``mtp.positions`` (positions a step the module runs)."""
    pattern: str
    subs: Any
    layer_fields: Any

    @nn.compact
    def __call__(self, h, e):
        f = self.layer_fields

        def normed(y, name):
            return _norm(f["norm"], f["norm_eps"], f["ln_dtype"], name)(y)

        x = nn.Dense(h.shape[-1], use_bias=False, dtype=f["dtype"],
                     param_dtype=jnp.float32, name="eh_proj")(
            jnp.concatenate([normed(e, "n_e"), normed(h, "n_h")], axis=-1))
        for i, kind in enumerate(self.pattern):
            x = PatternLayer(kind, self.subs.get(kind), name=f"layer_{i}",
                             **f)(x)
        note_layer(self.path, {"mtp.depth": 1,
                               "mtp.positions": h.shape[0] * h.shape[1]})
        return normed(x, "n_m")


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attn: str = "full"
    sp_axis: Any = RANKS_AXIS
    tp_axis: Any = None
    dtype: Any = jnp.bfloat16
    # LayerNorm compute dtype: f32 is the safe default; bf16 keeps the
    # residual stream out of f32 round-trips (~2x LN HBM traffic) at the
    # usual bf16-training precision trade (stats over d_model elements).
    ln_dtype: Any = jnp.float32
    norm: str = "layer"
    norm_eps: float = 1e-6
    qk_norm: bool = False
    rope_theta: Optional[float] = None
    # moe_experts > 0: the MLP is a DroplessMoE named "moe" (moe_top_k of
    # moe_experts SwiGLU experts, each moe_hidden wide); its router
    # losses are sown as intermediates (parallel.moe.router_losses).
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_hidden: int = 0

    @nn.compact
    def __call__(self, x, pos=None):
        """``pos`` (T,): the tokens' global positions, for rotary
        attention on a sequence shard; default ``arange(T)``."""
        C = x.shape[-1]
        h = _norm(self.norm, self.norm_eps, self.ln_dtype, "ln1")(x)
        if self.tp_axis:
            # Megatron layout: heads and MLP hidden sharded over tp_axis,
            # one psum per sub-block (see parallel/tensor_parallel.py).
            from horovod_tpu.parallel.tensor_parallel import (
                TPMlp, TPSelfAttention)
            x = x + TPSelfAttention(self.num_heads, axis=self.tp_axis,
                                    dtype=self.dtype, name="attn")(h)
            h = _norm(self.norm, self.norm_eps, self.ln_dtype, "ln2")(x)
            return x + TPMlp(self.mlp_ratio * C, C, axis=self.tp_axis,
                             dtype=self.dtype, name="mlp")(h)
        x = x + Attention(self.num_heads, self.attn, self.sp_axis,
                          self.dtype, qk_norm=self.qk_norm,
                          norm_eps=self.norm_eps,
                          rope_theta=self.rope_theta, name="attn")(h, pos)
        h = _norm(self.norm, self.norm_eps, self.ln_dtype, "ln2")(x)
        if self.moe_experts:
            y, _, _ = DroplessMoE(self.moe_experts, self.moe_hidden,
                                  self.moe_top_k, dtype=self.dtype,
                                  name="moe")(h)
            return x + y
        h = nn.Dense(self.mlp_ratio * C, dtype=self.dtype,
                     param_dtype=jnp.float32, name="fc1")(h)
        h = nn.gelu(h)
        h = nn.Dense(C, dtype=self.dtype, param_dtype=jnp.float32,
                     name="fc2")(h)
        return x + h


def _apply_block_stack(x, *, num_heads, depth, mlp_ratio, attn, sp_axis,
                       tp_axis, dtype, ln_dtype=jnp.float32, pos=None,
                       **block_options):
    """Run ``depth`` Blocks named ``block_{i}`` in the caller's flax scope
    (shared by TransformerLM and BlockStack so their param trees agree)."""
    for i in range(depth):
        x = Block(num_heads, mlp_ratio=mlp_ratio, attn=attn,
                  sp_axis=sp_axis, tp_axis=tp_axis, dtype=dtype,
                  ln_dtype=ln_dtype, name=f"block_{i}",
                  **block_options)(x, pos)
    return x


class BlockStack(nn.Module):
    """``depth`` consecutive transformer blocks — ONE pipeline stage.

    Activation-shape preserving, so it slots into
    :func:`horovod_tpu.parallel.pipeline.pipeline_apply` as ``stage_fn``:
    initialize per-stage params with ``stage_params_init``, keep the token
    embedding and LM head outside the pipeline (replicated), and each
    chip along ``pp`` runs its ``depth`` blocks.  See
    ``examples/jax_pipeline_transformer.py`` for the full wiring.
    """

    num_heads: int
    depth: int
    mlp_ratio: int = 4
    attn: str = "full"
    sp_axis: Any = RANKS_AXIS
    tp_axis: Any = None
    dtype: Any = jnp.bfloat16
    ln_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        return _apply_block_stack(
            x, num_heads=self.num_heads, depth=self.depth,
            mlp_ratio=self.mlp_ratio, attn=self.attn,
            sp_axis=self.sp_axis, tp_axis=self.tp_axis, dtype=self.dtype,
            ln_dtype=self.ln_dtype)


class TransformerLM(nn.Module):
    """Causal LM over token ids.

    Input: (B, T_local) int32 token ids — the full sequence when
    ``attn="full"``, this rank's shard otherwise.
    """
    vocab: int
    dim: int = 256
    depth: int = 4
    num_heads: int = 8
    max_len: int = 2048
    attn: str = "full"
    sp_axis: Any = RANKS_AXIS
    # Tensor parallelism: shard heads + MLP hidden over this mesh axis
    # (Megatron layout); embeddings/head replicated.  Requires running
    # inside shard_map with check_vma=True and attn="full".
    tp_axis: Any = None
    dtype: Any = jnp.bfloat16
    # LM-head matmul compute dtype.  f32 is the safe default; bf16 runs
    # the (T, d) @ (d, vocab) projection at full MXU rate (the head is
    # about a fifth of a gpt cell's step: PERF.md §5, "The head, op by
    # op") — cast the logits back to f32 for the softmax in the loss.
    head_dtype: Any = jnp.float32
    # LayerNorm compute dtype (see Block.ln_dtype); bf16 for max MFU.
    ln_dtype: Any = jnp.float32
    # The block's other choices (module docstring; Block's fields).
    norm: str = "layer"              # "layer" | "rms"
    norm_eps: float = 1e-6
    pos: str = "learned"             # "learned" | "rotary"
    rope_theta: float = 10000.0
    qk_norm: bool = False
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_hidden: int = 0
    # A hybrid stack (module docstring): one letter a layer, ``depth`` is
    # then the pattern's length whatever it says, and ``pos`` must be
    # "none" (the mixers carry the order) or "rotary" (the ``S`` layers
    # turn their q and k by rope_theta; no other layer has positions).
    # ``ssm``: the fields of Mamba2Mixer; ``*`` and ``S`` layers have
    # num_heads query heads over kv_heads KV heads of head_dim, ``S`` with
    # the model's qk_norm per head and the ``indexer``
    # (GroupedQueryAttention's field); ``moe``: DroplessMoE's further fields (router,
    # renormalize, gate_scale, activation, shared_hidden, held); ``lin``:
    # the fields of GatedDeltaNet; ``F`` layers have num_heads heads of
    # dim / num_heads with the model's qk_norm; ``L``, ``F``, ``m`` and
    # ``a`` layers end in a SwiGLU mlp_hidden wide; ``a`` layers scale
    # their scores by attn_scale (None: head_dim ** -0.5), and ``m`` and
    # ``a`` layers every sub-layer's output by residual_multiplier.
    pattern: Optional[str] = None
    ssm: Any = None
    kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    moe: Any = None
    lin: Any = None
    mlp_hidden: int = 0
    indexer: Any = None
    attn_scale: Optional[float] = None
    residual_multiplier: float = 1.0
    cca: Any = None
    # ``d`` and ``x`` layers: LatentAttention's widths (q_latent — None:
    # no query latent —, kv_latent, nope_dim, rope_dim, v_dim) for
    # num_heads heads, rotary positions of rope_theta (pos="rotary") or
    # none at all (pos="none").  ``k`` and ``K`` layers: ``lin`` holds the
    # fields of KimiDeltaAttention.
    mla: Any = None
    # ``mtp=dict(pattern="*E")``: a multi-token-prediction module named
    # ``mtp`` behind the stack (MultiTokenPrediction), its layers the
    # letters of its own pattern with this stack's ssm=, moe=, heads.  The
    # call then takes ONE MORE id a sequence than the stack runs, (B, T +
    # 1): the stack reads ``tokens[:, :-1]``, the module the embeddings of
    # ``tokens[:, 1:]``, and the call returns a pair — the stack's hidden
    # states (or logits) and the module's (__call__).
    mtp: Any = None
    # Of a pattern stack too: the embedded tokens are multiplied by
    # embedding_multiplier, the final hidden states divided by
    # logits_scaling, and with tie_head the head is the embedding table
    # transposed (no ``head`` parameter; head_kernel()).
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_head: bool = False
    # ``diffusion=dict(block=L, mask_id=id)``: a pattern stack of ``S`` and
    # ``E`` layers trained under the block-diffusion objective.  The call
    # takes ``masked`` (B, T) bool beside the ids: the stack runs the 2 T
    # rows ``[tokens ; where(masked, mask_id, tokens)]`` — a clean and a
    # noised copy, both at positions 0 .. T - 1 — with the flash family's
    # ``("block_diffusion", L)`` mask in every ``S`` layer, and the call
    # returns the NOISED half's hidden states (or logits), (B, T, ...).
    diffusion: Any = None
    # Of a pattern stack's ``S`` layers, and of its ``W`` layers unless
    # ``window`` says otherwise: a sigmoid gate on the heads' output, one
    # value a head (attn_gate), the rotated width of a head and YaRN's table
    # (GroupedQueryAttention's out_gate, rope_width, rope_scaling).
    attn_gate: bool = False
    rope_width: Optional[int] = None
    rope_scaling: Any = None
    # ``W`` layers: ``window=dict(window=W, ...)`` — ``S``'s attention under
    # a causal window of W keys, with whichever of num_heads, rope_theta,
    # rope_width and rope_scaling the dict gives in place of the stack's
    # (a stack of windowed and global layers with their own head counts and
    # rotary tables).  ``D`` layers are a dense SwiGLU mlp_hidden wide.
    window: Any = None

    @nn.compact
    def __call__(self, tokens, return_hidden=False, masked=None):
        """``return_hidden=True`` skips the LM-head matmul and returns the
        final-LN hidden states — pair it with
        :func:`horovod_tpu.ops.losses.fused_softmax_xent` on
        ``params["head"]["kernel"]`` so the (T, vocab) logits are never
        materialized as autodiff residuals (init still uses the default
        call so the param tree always contains the head; with ``tie_head``
        there is none, and :meth:`head_kernel` gives the matrix).  The
        hidden states are already divided by ``logits_scaling``.  With
        ``mtp`` the ids are (B, T + 1) and the result is a pair, the
        stack's over ``tokens[:, :-1]`` and the prediction module's (the
        field's comment): :func:`horovod_tpu.ops.losses.multi_token_xent`
        takes the pair of hidden states.  With ``diffusion`` the call takes
        ``masked`` (B, T) bool — the positions the noised copy replaces by
        the mask token; None: none — and returns the noised half's (the
        field's comment); position ``t`` of it predicts token ``t`` itself,
        so the labels are not shifted."""
        if masked is not None and not self.diffusion:
            raise ValueError("masked= is the block-diffusion call's "
                             "(diffusion=dict(block=, mask_id=))")
        if self.tp_axis and self.attn != "full":
            raise ValueError(
                "tp_axis composes with attn='full' only (TP attention "
                f"computes the full sequence locally); got {self.attn!r}")
        if self.tp_axis and (self.moe_experts or self.qk_norm
                             or self.pos != "learned" or self.pattern
                             or self.moe or self.indexer or self.cca
                             or self.mla or self.mtp or self.diffusion
                             or self.window or self.attn_gate):
            raise ValueError("tp_axis runs the GPT-2 block only: no "
                             "experts (whole, a held share or in a latent), "
                             "QK-norm, rotary positions, pattern stack or "
                             "multi-token prediction (mtp=)")
        if self.pos not in ("learned", "rotary", "none"):
            raise ValueError(f"unknown pos: {self.pos!r}")
        if self.pattern is not None:
            return self._pattern_stack(tokens, return_hidden, masked)
        if (self.pos == "none" or self.moe or self.ssm or self.lin
                or self.mlp_hidden or self.indexer or self.cca or self.mla
                or self.tie_head or self.mtp or self.diffusion
                or self.window or self.attn_gate or self.rope_scaling
                or self.rope_width is not None
                or self.attn_scale is not None
                or (self.residual_multiplier, self.embedding_multiplier,
                    self.logits_scaling) != (1.0, 1.0, 1.0)):
            raise ValueError("pos='none', ssm=, moe= (its latent= too), lin=, "
                             "indexer=, cca=, mla=, mtp=, diffusion=, "
                             "window=, attn_gate=, rope_width=, "
                             "rope_scaling=, "
                             "mlp_hidden=, attn_scale=, tie_head= and the "
                             "three multipliers belong to a pattern stack; "
                             "the block stack "
                             "takes learned or rotary positions and "
                             "moe_experts")
        rotary = self.pos == "rotary"
        B, T = tokens.shape
        if self.attn in ("full", "flash"):
            pos = jnp.arange(T)
        elif self.attn == "ring_zigzag":
            pos = zigzag_shard_positions(
                lax.axis_index(self.sp_axis), lax.axis_size(self.sp_axis), T)
        else:
            pos = lax.axis_index(self.sp_axis) * T + jnp.arange(T)
        x = nn.Embed(self.vocab, self.dim, param_dtype=jnp.float32,
                     dtype=self.dtype, name="tok_emb")(tokens)
        if not rotary:
            pos_emb = nn.Embed(self.max_len, self.dim,
                               param_dtype=jnp.float32, dtype=self.dtype,
                               name="pos_emb")(pos)
            x = x + pos_emb[None]
        x = _apply_block_stack(
            x, num_heads=self.num_heads, depth=self.depth, mlp_ratio=4,
            attn=self.attn, sp_axis=self.sp_axis, tp_axis=self.tp_axis,
            dtype=self.dtype, ln_dtype=self.ln_dtype,
            pos=pos if rotary else None, norm=self.norm,
            norm_eps=self.norm_eps, qk_norm=self.qk_norm,
            rope_theta=self.rope_theta if rotary else None,
            moe_experts=self.moe_experts, moe_top_k=self.moe_top_k,
            moe_hidden=self.moe_hidden)
        x = _norm(self.norm, self.norm_eps, self.ln_dtype, "ln_f")(x)
        if return_hidden:
            return x
        return nn.Dense(self.vocab, use_bias=False, dtype=self.head_dtype,
                        param_dtype=jnp.float32, name="head")(x)

    def _pattern_stack(self, tokens, return_hidden, masked=None):
        rotary = self.rope_theta if self.pos == "rotary" else None
        bd = dict(self.diffusion) if self.diffusion else None
        if bd is not None and (set(bd) != {"block", "mask_id"}
                               or set(self.pattern) - {"S", "E"}
                               or self.indexer or self.mtp
                               or tokens.shape[1] % bd["block"]):
            raise ValueError(
                "diffusion= is dict(block=L, mask_id=id) on a pattern of 'S' "
                "and 'E' layers without indexer= or mtp=, called with whole "
                f"blocks of tokens; got {self.diffusion!r}, pattern "
                f"{self.pattern!r}, {tokens.shape[1]} tokens")
        if self.attn not in ("full", "flash") or self.pos not in (
                ("none", "rotary") if set("SWZdx") & set(self.pattern)
                else ("none",)) or ("Z" in self.pattern and rotary is None):
            raise ValueError("a pattern stack runs whole sequences "
                             "(attn='full' or 'flash') with pos='none', or "
                             "'rotary' for its 'S' layers, 'W' layers, 'Z' "
                             "layers ('Z' layers have no other) and 'd' and "
                             "'x' layers; got "
                             f"attn={self.attn!r}, pos={self.pos!r}")
        experts = dict(num_experts=self.moe_experts, hidden=self.moe_hidden,
                       top_k=self.moe_top_k, **dict(self.moe or {}))
        subs = {
            "M": dict(self.ssm or {}),
            "*": dict(num_heads=self.num_heads, kv_heads=self.kv_heads,
                      head_dim=self.head_dim, attn=self.attn),
            "S": dict(num_heads=self.num_heads, kv_heads=self.kv_heads,
                      head_dim=self.head_dim, attn=self.attn,
                      qk_norm=self.qk_norm, indexer=self.indexer,
                      rope_theta=rotary),
            "E": experts,
            "L": dict(self.lin or {}),
            "F": dict(num_heads=self.num_heads, attn=self.attn,
                      qk_norm=self.qk_norm),
            "a": dict(num_heads=self.num_heads, kv_heads=self.kv_heads,
                      head_dim=self.head_dim, attn=self.attn,
                      scale=self.attn_scale),
            "Z": dict(attn=dict(num_heads=self.num_heads,
                                kv_heads=self.kv_heads,
                                head_dim=self.head_dim, attn=self.attn,
                                rope_theta=rotary, **dict(self.cca or {})),
                      moe=experts),
            "d": dict(attn=dict(num_heads=self.num_heads, attn=self.attn,
                                rope_theta=rotary, **dict(self.mla or {})),
                      moe=experts),
        }
        subs["m"], subs["x"] = subs["M"], subs["d"]
        subs["k"] = subs["K"] = dict(lin=dict(self.lin or {}), moe=experts)
        if self.attn_gate:
            subs["S"]["out_gate"] = True
        for name, value in (("rope_width", self.rope_width),
                            ("rope_scaling", self.rope_scaling)):
            if value is not None:
                subs["S"][name] = value
        if "W" in self.pattern:
            own = dict(self.window or {})
            if "window" not in own or set(own) - {
                    "window", "num_heads", "rope_theta", "rope_width",
                    "rope_scaling"} or self.indexer or rotary is None:
                raise ValueError(
                    "'W' layers take window=dict(window=W) and, of their "
                    "own, num_heads, rope_theta, rope_width and "
                    "rope_scaling, in a stack with pos='rotary' and no "
                    f"indexer=; got {self.window!r}")
            # Two kinds of attention layer in one stack: each counts its
            # query heads under its kind.
            subs["S"]["heads_kind"] = "global"
            subs["W"] = {**subs["S"], **own, "heads_kind": "window"}
        elif self.window:
            raise ValueError("window= is the 'W' layers'; the pattern "
                             f"{self.pattern!r} holds none")
        if self.residual_multiplier != 1.0 and set(self.pattern) - {"m", "a"}:
            raise ValueError("residual_multiplier scales the sub-layers of "
                             "'m' and 'a' layers only; the pattern "
                             f"{self.pattern!r} holds others")
        layer_fields = dict(dtype=self.dtype, ln_dtype=self.ln_dtype,
                            norm=self.norm, norm_eps=self.norm_eps,
                            mlp_hidden=self.mlp_hidden,
                            residual_multiplier=self.residual_multiplier)
        mtp = None
        if self.mtp:
            letters = dict(self.mtp).get("pattern")
            if set(self.mtp) != {"pattern"} or not letters or (
                    "Z" in letters) or self.tie_head:
                raise ValueError(
                    "mtp= is dict(pattern=<letters>): ONE prediction module "
                    "of those layers (no 'Z': nothing hands it a router "
                    "state) behind a stack with an untied head; got "
                    f"{self.mtp!r}, tie_head={self.tie_head}")
            mtp = MultiTokenPrediction(letters, subs, layer_fields,
                                       name="mtp")
        embed = nn.Embed(self.vocab, self.dim, param_dtype=jnp.float32,
                         dtype=self.dtype, name="tok_emb")
        where = {}                # an 'S' layer's positions and mask
        if bd is None:
            x = embed(tokens)
        else:
            B, T = tokens.shape
            with jax.named_scope("bd/assemble"):
                if masked is None:
                    masked = jnp.zeros(tokens.shape, jnp.bool_)
                x = embed(jnp.concatenate(
                    [tokens, jnp.where(masked, bd["mask_id"], tokens)],
                    axis=1))
                where = dict(pos=jnp.tile(jnp.arange(T), 2),
                             mask=("block_diffusion", bd["block"]))
            self.sow("intermediates", "masked_tokens", masked.sum())
        if self.embedding_multiplier != 1.0:
            x = x * self.embedding_multiplier
        if mtp is not None:
            # One table serves both: the stack reads all ids but the last,
            # the module all but the first.
            x, next_emb = x[:, :-1], x[:, 1:]
        router_state = None       # of the last 'Z' layer, for the next one
        for i, kind in enumerate(self.pattern):
            layer = PatternLayer(kind, subs.get(kind), first=i == 0,
                                 name=f"layer_{i}", **layer_fields)
            if kind == "Z":
                x, router_state = layer(x, router_state)
            else:
                x = layer(x, **where)
        if bd is not None:
            with jax.named_scope("bd/split"):
                x = x[:, T:]
        x = _norm(self.norm, self.norm_eps, self.ln_dtype, "ln_f")(x)
        if self.logits_scaling != 1.0:
            x = x / self.logits_scaling
        counters = {"lm.tied_head": 1} if self.tie_head else {}
        if bd is not None:
            counters["lm.bd_rows"] = 2 * B * T
        if counters:
            note_layer(self.path, counters)
        if mtp is not None:
            x = (x, mtp(x, next_emb))
        if return_hidden:
            return x
        if self.tie_head:
            return jnp.dot(x.astype(self.head_dtype),
                           embed.embedding.astype(self.head_dtype).T)
        head = nn.Dense(self.vocab, use_bias=False, dtype=self.head_dtype,
                        param_dtype=jnp.float32, name="head")
        return jax.tree.map(head, x)

    def head_kernel(self, params):
        """The (dim, vocab) matrix that turns ``return_hidden=True``'s
        hidden states into logits: ``params["head"]["kernel"]``, or with
        ``tie_head`` the embedding table transposed — one parameter, whose
        gradient is then the gather's plus the head's."""
        if self.tie_head:
            return params["tok_emb"]["embedding"].T
        return params["head"]["kernel"]


def NemotronHLM(**overrides) -> TransformerLM:
    """The Nemotron-H stack that ``nvidia/Nemotron-Labs-TwoTower-30B-A3B-
    Base-BF16``'s config.json describes, as a :class:`TransformerLM` with
    a ``pattern``: 52 layers ``MEMEM*E...`` at d 2688, RMSNorm eps 1e-5;
    ``M`` Mamba-2 mixers of 64 heads of 64, 8 groups, state 128, conv 4,
    chunks of 128; ``*`` attention of 32 query heads over 2 KV heads of
    128 without rotary positions; ``E`` 128 relu² experts 1856 wide,
    top-6 by sigmoid scores renormalised and scaled by 2.5, and one shared
    expert 3712 wide; vocab 131072, untied head.  ``overrides`` replace
    any field: a cut takes the first letters of the pattern, and
    ``moe={..., "held": (first, count)}`` keeps one chip's share of every
    layer's experts (``parallel.moe.DroplessMoE``).  Two further fields
    this model leaves off and :func:`Nemotron3SuperLM` sets: ``moe={...,
    "latent": w}`` (the routed experts work ``w`` wide between a shared
    down- and up-projection) and ``mtp=dict(pattern=...)`` (a
    multi-token-prediction module behind the stack); a third it leaves
    off too: ``moe={..., "choice_bias": γ}``, the sigmoid router's
    balancing bias with its update (:func:`JoyAIFlashLM` sets it; this
    config has no speed for it).  A tensor-parallel
    rank's share of a mixer is ``Mamba2Mixer`` at ONE group: ``ssm=dict(
    num_heads=H / n_groups, n_groups=1, ...)``.

    What is NOT here: the model card's second, denoising tower, its
    conditioning on this one, in-block bidirectional attention and the
    block-diffusion objective (config.json holds no key of theirs).  This
    is one causal tower under next-token cross-entropy, trained like
    :func:`OLMoELM` through ``make_train_step`` and ``fused_softmax_xent``.
    """
    fields = dict(
        vocab=131072, dim=2688, num_heads=32, kv_heads=2, head_dim=128,
        max_len=262144, norm="rms", norm_eps=1e-5, pos="none",
        pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        ssm=dict(num_heads=64, head_dim=64, n_groups=8, state_size=128,
                 conv_kernel=4, chunk=128),
        moe_experts=128, moe_top_k=6, moe_hidden=1856,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                 activation="relu2", shared_hidden=3712))
    fields.update(overrides)
    return TransformerLM(**fields)


def Nemotron3SuperLM(**overrides) -> TransformerLM:
    """The stack that ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``'s
    config.json describes (``model_type`` ``nemotron_h``), as a
    :class:`TransformerLM` with a ``pattern``: 88 layers (40 ``M``, 40
    ``E``, 8 ``*``) at d 4096, RMSNorm eps 1e-5, no positions; ``M``
    Mamba-2 mixers of 128 heads of 64 in 8 groups, state 128, conv 4,
    chunks of 128; ``*`` attention of 32 query heads over 2 KV heads of
    128; ``E`` LatentMoE — 512 relu² experts 2688 wide that work in a
    latent of 1024 between a down- and an up-projection all of them share
    (``DroplessMoE(latent=1024)``), top-22 by sigmoid scores renormalised
    and scaled by 5, and one shared expert 5376 wide on the layer's input
    itself; a multi-token-prediction module of one ``*`` and one ``E``
    layer behind the stack (``mtp=dict(pattern="*E")``,
    :class:`MultiTokenPrediction`); vocab 131072, untied head.

    What config.json does not say is the family's published form
    (Megatron-core's latent projections and ``MultiTokenPredictionLayer``):
    ``benchmark/configs/nemotron-3-super-120b-a12b.json`` lists each under
    ``assumed``.  Not built: a prediction depth past 1 with shared weights,
    any exchange between chips.  Left off: the router's correction bias
    (``moe["choice_bias"]``, an option of ``DroplessMoE`` since
    :func:`JoyAIFlashLM`; its update's speed is not in this config).

    ``overrides`` replace any field.  A cut takes the first letters of the
    pattern.  One chip's share of a layer is overrides alone, no other
    code: a tensor-parallel rank's share of a mixer is the mixer of its ONE
    group (``n_groups`` 8 is the degree the mixer is built for: B, C and
    the gated norm are a group's own, so ``ssm=dict(num_heads=16,
    n_groups=1, ...)`` is rank ``g``'s heads and the eight shares add up);
    of attention ``num_heads=4, kv_heads=1``; of the experts ``moe={...,
    "held": (first, count)}``.  Trained through ``make_train_step`` with
    the loss :func:`horovod_tpu.ops.losses.multi_token_xent` of the two
    hidden states ``model.apply(..., tokens[:, :-1], return_hidden=True)``
    gives for sequences of T + 2 ids."""
    fields = dict(
        vocab=131072, dim=4096, num_heads=32, kv_heads=2, head_dim=128,
        max_len=262144, norm="rms", norm_eps=1e-5, pos="none",
        pattern=("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                 "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        ssm=dict(num_heads=128, head_dim=64, n_groups=8, state_size=128,
                 conv_kernel=4, chunk=128),
        moe_experts=512, moe_top_k=22, moe_hidden=2688,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=5.0,
                 activation="relu2", shared_hidden=5376, latent=1024),
        mtp=dict(pattern="*E"))
    fields.update(overrides)
    return TransformerLM(**fields)


def GraniteHybridLM(**overrides) -> TransformerLM:
    """The stack that ``ibm-granite/granite-4.0-h-micro``'s config.json
    describes (``model_type`` ``granitemoehybrid`` with no experts:
    ``num_local_experts`` 0, the dense ``shared_intermediate_size`` MLP
    alone), as a :class:`TransformerLM` with a ``pattern``: 40 layers,
    ``mmmmmammmm`` four times (``layer_types``), at d 2048, pre-norm
    RMSNorm eps 1e-5, every sub-layer's output times
    ``residual_multiplier`` 0.22; ``m`` Mamba-2 mixers of 64 heads of 64
    whose B and C are ONE group over all heads (state 128, conv 4 with
    bias, chunks of 256, one gated norm over all 4,096 channels); ``a``
    attention of 32 query heads over 8 KV heads of 64 with no positions
    (``position_embedding_type`` "nope") and scores scaled by
    ``attention_multiplier`` 1/64; a SwiGLU 8192 wide after each; the
    embedded tokens times ``embedding_multiplier`` 12, the logits the
    normed hidden states times the embedding table transposed
    (``tie_word_embeddings``) over ``logits_scaling`` 8; vocab 100352.
    ``overrides`` replace any field: a cut takes the first letters of the
    pattern.  Trained like :func:`OLMoELM` through ``make_train_step``
    and ``fused_softmax_xent`` on ``model.head_kernel(params)``."""
    fields = dict(
        vocab=100352, dim=2048, num_heads=32, kv_heads=8, head_dim=64,
        max_len=131072, norm="rms", norm_eps=1e-5, pos="none",
        pattern="mmmmmammmm" * 4, attn_scale=0.015625, mlp_hidden=8192,
        ssm=dict(num_heads=64, head_dim=64, n_groups=1, state_size=128,
                 conv_kernel=4, chunk=256),
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, tie_head=True)
    fields.update(overrides)
    return TransformerLM(**fields)


def KeyeLM(**overrides) -> TransformerLM:
    """The language tower that ``Kwai-Keye/Keye-VL-2.0-30B-A3B``'s
    config.json describes (``model_type`` ``KeyeVL2``), as a
    :class:`TransformerLM` with a ``pattern``: 48 layers ``SE`` at d 2048,
    pre-norm RMSNorm eps 1e-6; ``S`` attention of 32 query heads over 4 KV
    heads of 128, RMSNorm over each head of q and of k, rotary positions
    (theta 1e7) and ``sa_config``'s indexer — 16 heads of 64 over one key
    head choose the 2,048 causal keys a query's heads read
    (DeepSeek Sparse Attention; :mod:`horovod_tpu.ops.sparse_select`);
    ``E`` 128 SwiGLU experts 768 wide, top-8 by softmax renormalised, no
    shared expert; vocab 151936, untied head.

    What config.json does not say is the family's convention: per-head
    QK-norm (the key set is the Qwen3-MoE decoder's, whose layer has it),
    rotary positions on the indexer's q and k over all 64 channels with
    the layer's theta, the score's factor ``(16 · 64)^-1/2``,
    ``q_chunk_size`` 512 as the tile scores and top-k are computed in.
    Text tokens only: the three sections of ``mrope_section`` carry one
    position, so multi-axis rotary IS one-axis rotary here.  What is NOT
    here: the vision tower (the config's language settings are all the
    catalog holds) and with it any image token.

    Trained like :func:`OLMoELM` through ``make_train_step`` and
    ``fused_softmax_xent``, the loss ``CE + index_losses(intermediates)``:
    the indexer's parameters move under its KL term alone and everything
    else under the cross-entropy alone.  ``overrides`` replace any field:
    a cut takes the first letters of the pattern, and ``moe={..., "held":
    (first, count)}`` keeps one chip's share of every layer's experts."""
    fields = dict(
        vocab=151936, dim=2048, num_heads=32, kv_heads=4, head_dim=128,
        max_len=262144, norm="rms", norm_eps=1e-6, pos="rotary",
        rope_theta=1e7, qk_norm=True, pattern="SE" * 48,
        indexer=dict(num_heads=16, head_dim=64, topk=2048, tile=512),
        moe_experts=128, moe_top_k=8, moe_hidden=768,
        moe=dict(router="softmax", renormalize=True, activation="swiglu"))
    fields.update(overrides)
    return TransformerLM(**fields)


def LagunaLM(**overrides) -> TransformerLM:
    """The stack that ``poolside/Laguna-XS.2``'s config.json describes
    (``model_type`` ``laguna``), as a :class:`TransformerLM` with a
    ``pattern``: 40 layers at d 2048, pre-norm RMSNorm eps 1e-6, two
    sub-layers each.  The attention sub-layer is GLOBAL in every fourth
    layer (``layer_types`` ``full_attention``, letter ``S``: 48 query heads
    over 8 KV heads of 128 under the causal mask; rotary positions on the
    first 64 channels of a head — ``partial_rotary_factor`` 0.5 — from
    YaRN's table, theta 500,000, factor 64 over 4,096 original positions,
    ``beta_fast`` 64, ``beta_slow`` 1, cos and sin times the published
    ``attention_factor``) and WINDOWED in the three after it
    (``sliding_attention``, letter ``W``: 64 query heads over the 8 KV
    heads, a query reads itself and the 511 keys before it —
    ``sliding_window`` 512 —, plain rotary positions of theta 10,000 over
    the whole head); ``num_attention_heads_per_layer`` is the two head
    counts.  ``gating: true``: a sigmoid gate on the heads' output before
    the output projection, ONE value a head (the sibling ``Laguna-S-2.1``'s
    config.json says ``gating: "per-head"``; the benchmark's configuration
    has the count of parameters that agrees).  The
    second sub-layer is a dense SwiGLU 8,192 wide in layer 0
    (``mlp_layer_types`` ``dense``, letter ``D``) and, in the 39 after it,
    256 SwiGLU experts 512 wide (letter ``E``), top-8 by sigmoid scores,
    gates renormalised over the chosen and scaled by
    ``moe_routed_scaling_factor`` 2.5, one shared expert 512 wide; vocab
    100,352, untied head.  No bias anywhere, no QK-norm, no auxiliary loss.

    ``overrides`` replace any field: a cut takes the first letters of the
    pattern (``"SD" + "WE" * 3 + "SE"``: a whole period behind the dense
    layer), ``moe={..., "held": (first, count)}`` keeps one chip's share of
    every layer's experts, a smaller ``vocab`` its share of the
    vocabulary."""
    period = "WE" * 3 + "SE"
    fields = dict(
        vocab=100352, dim=2048, num_heads=48, kv_heads=8, head_dim=128,
        max_len=262144, norm="rms", norm_eps=1e-6, pos="rotary",
        rope_theta=500000.0, rope_width=64,
        rope_scaling=dict(factor=64.0, original_max_len=4096,
                          beta_fast=64.0, beta_slow=1.0,
                          attention_factor=1.4158883083359672),
        attn_gate=True,
        window=dict(window=512, num_heads=64, rope_theta=10000.0,
                    rope_width=None, rope_scaling=None),
        pattern=("SD" + period * 10)[:80], mlp_hidden=8192,
        moe_experts=256, moe_top_k=8, moe_hidden=512,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                 activation="swiglu", shared_hidden=512))
    fields.update(overrides)
    return TransformerLM(**fields)


def SDARLM(**overrides) -> TransformerLM:
    """The stack that ``JetLM/SDAR-30B-A3B-Chat``'s config.json describes
    (``model_type`` ``sdar_moe``; the key set is the Qwen3-MoE decoder's),
    as a :class:`TransformerLM` with a ``pattern``: 48 layers ``SE`` at d
    2048, pre-norm RMSNorm eps 1e-6; ``S`` attention of 32 query heads over
    4 KV heads of 128, RMSNorm over each head of q and of k, rotary
    positions (theta 1e6); ``E`` 128 SwiGLU experts 768 wide, top-8 by
    softmax renormalised, no shared expert; vocab 151936, untied head —
    :func:`KeyeLM`'s layer without its indexer.  What is its own is the
    OBJECTIVE, block diffusion (``diffusion=dict(block=4, mask_id=...)``;
    :class:`TransformerLM`'s field): the model is called with the ids and
    ``masked``, runs a clean and a noised copy of every sequence in one
    pass of 2 T rows — positions 0 .. T - 1 twice, the flash family's
    ``("block_diffusion", 4)`` mask — and returns the noised half's hidden
    states, whose cross-entropy against the SAME positions' clean ids,
    weighted ``1 / t_b`` on the masked positions of block ``b`` (mask rate
    ``t_b``) and 0 elsewhere, is the loss (the noise is the batch's, the
    weighting the caller's, around ONE ``fused_softmax_xent`` pass).

    config.json gives neither the block length nor the schedule: 4 is the
    released Chat models' block length, ``mask_id`` defaults to the
    vocabulary's last row (a cut sets its own slice's), and
    ``benchmark/configs/sdar-30b-a3b-chat.json`` lists both under
    ``assumed`` beside per-head QK-norm.  Not here: a sampler that
    generates by denoising blocks (there is no serving path).

    ``overrides`` replace any field: a cut takes the first letters of the
    pattern, and ``moe={..., "held": (first, count)}`` keeps one chip's
    share of every layer's experts."""
    fields = dict(
        vocab=151936, dim=2048, num_heads=32, kv_heads=4, head_dim=128,
        max_len=32768, norm="rms", norm_eps=1e-6, pos="rotary",
        rope_theta=1e6, qk_norm=True, pattern="SE" * 48,
        moe_experts=128, moe_top_k=8, moe_hidden=768,
        moe=dict(router="softmax", renormalize=True, activation="swiglu"))
    fields.update(overrides)
    fields.setdefault("diffusion",
                      dict(block=4, mask_id=fields["vocab"] - 1))
    return TransformerLM(**fields)


def JoyAIFlashLM(**overrides) -> TransformerLM:
    """The stack that ``jdopensource/JoyAI-LLM-Flash``'s config.json
    describes (``model_type`` ``joyai_llm_flash``; the key set is
    DeepSeek-V3's), as a :class:`TransformerLM` with a ``pattern``: 40
    layers at d 2048, pre-norm RMSNorm eps 1e-6, every layer multi-head
    latent attention (:class:`LatentAttention`: 32 heads, a q latent of
    1,536 and a k | v latent of 512, each normed before it is projected up;
    keys of 128 + 64 rotary channels — ONE rotary key a token for all heads,
    theta 3.2e7 — against values of 128) and then, in layer 0
    (``first_k_dense_replace`` 1, letter ``d``), a dense SwiGLU 7,168 wide
    and, in the 39 after it (letter ``x``), 256 SwiGLU experts 768 wide,
    top-8 by sigmoid scores plus a balancing bias that chooses and never
    gates (``topk_method`` ``noaux_tc``: ``DroplessMoE(choice_bias=γ)``,
    state in the collection ``"balance"``, moved by the layer itself after
    each step's counts and by no gradient), gates renormalised over the
    chosen and scaled by 2.5, and one shared SwiGLU expert 768 wide; a
    multi-token-prediction module of one ``x`` layer behind the stack
    (``num_nextn_predict_layers`` 1); vocab 129,280, untied head.

    What config.json has no key for is the DeepSeek-V3 report's: the bias
    update's speed γ 1e-3 (``moe["choice_bias"]``), the prediction loss's
    weight (the caller's).  The rotary pairing run is rotate-half; the
    published ``rope_interleave`` pairs adjacent channels, and a loader of
    published weights permutes the rotary columns of ``q_b`` and ``kv_a``
    (:class:`LatentAttention`).  No exchange between chips is built.

    ``overrides`` replace any field.  A cut takes the first letters of the
    pattern (the dense layer and then expert layers: ``pattern="dxxxx"``);
    one chip's share of the experts is ``moe={..., "held": (first,
    count)}`` and of the vocabulary a smaller ``vocab``; the heads are not
    divided (the latents' down-projections and norms would be replicated on
    every rank that holds some).  Trained through ``make_train_step`` with
    the bias in its ``aux_state``::

        def loss_fn(params, aux, tokens):          # tokens (B, T + 2)
            hiddens, moved = model.apply(
                {"params": params, **aux}, tokens[:, :-1],
                return_hidden=True, mutable=["balance"])
            return multi_token_xent(hiddens, model.head_kernel(params),
                                    tokens, (1.0, 0.3)), moved
    """
    fields = dict(
        vocab=129280, dim=2048, num_heads=32, max_len=131072, norm="rms",
        norm_eps=1e-6, pos="rotary", rope_theta=3.2e7,
        pattern="d" + "x" * 39, mlp_hidden=7168,
        mla=dict(q_latent=1536, kv_latent=512, nope_dim=128, rope_dim=64,
                 v_dim=128),
        moe_experts=256, moe_top_k=8, moe_hidden=768,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                 activation="swiglu", shared_hidden=768, choice_bias=1e-3),
        mtp=dict(pattern="x"))
    fields.update(overrides)
    return TransformerLM(**fields)


def KimiLinearLM(**overrides) -> TransformerLM:
    """The stack that ``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s
    config.json describes (``model_type`` ``kimi_linear``), as a
    :class:`TransformerLM` with a ``pattern``: 27 layers at d 2304, pre-norm
    RMSNorm eps 1e-5, two sub-layers each.  The first is Kimi Delta
    Attention in 20 layers (``linear_attn_config.kda_layers``; letters
    ``k`` and ``K``:
    :class:`~horovod_tpu.models.linear_attention.KimiDeltaAttention`, 32
    heads with keys and values of 128, conv 4, a decay a key channel from a
    low-rank gate, beta in (0, 1), a sigmoid-gated norm) and multi-head
    latent attention in the 7 others (``full_attn_layers``, 1-based 4, 8,
    ..., 24 and 27; letter ``x``: :class:`LatentAttention`, 32 heads, NO
    query latent — ``q_lora_rank`` null —, a k | v latent of 512 and 64
    shared key channels that are NOT rotated — ``mla_use_nope``; keys of
    192 against values of 128).  The second is a dense SwiGLU 9,216 wide in
    layer 1 (``first_k_dense_replace`` 1, letter ``k``) and, in the 26
    after it, 256 SwiGLU experts 1,024 wide, top-8 by sigmoid scores plus a
    balancing bias that chooses and never gates (``use_grouped_topk`` with
    ONE group is plain top-8; ``DroplessMoE(choice_bias=...)``, state in
    the collection ``"balance"``), gates renormalised over the chosen and
    scaled by 2.446, and one shared SwiGLU expert 1,024 wide; vocab
    163,840, untied head.  No layer has positions: the KDA layers carry
    the order.

    What config.json has no key for is the report's (arXiv:2510.26692) and
    the public ``flash-linear-attention`` KDA layer's: the low-rank pairs'
    inner width (the head's 128), ``A_log`` a head and ``dt_bias`` a
    channel with Mamba's initialisers, the gate's sigmoid, no bias on the
    gate's up-projection, chunks of 64; the bias update's speed 1e-3 is the
    DeepSeek-V3 report's.  ``linear_attn_config.head_dim`` 128 is ``d_k``
    and ``d_v``; the top-level ``head_dim`` 72 is d / 32 and nothing reads
    it.  No exchange between chips is built.

    ``overrides`` replace any field.  A cut takes the first letters of the
    pattern (``pattern="kKKxK"``: the dense layer and one period); one
    chip's share of the experts is ``moe={..., "held": (first, count)}``
    and of the vocabulary a smaller ``vocab``; the heads are not divided.
    Trained through ``make_train_step`` with the bias in its
    ``aux_state``::

        def loss_fn(params, aux, tokens):          # tokens (B, T + 1)
            h, moved = model.apply(
                {"params": params, **aux}, tokens[:, :-1],
                return_hidden=True, mutable=["balance"])
            ce = fused_softmax_xent(h.reshape(-1, model.dim),
                                    params["head"]["kernel"],
                                    tokens[:, 1:].reshape(-1)).mean()
            return ce, moved
    """
    fields = dict(
        vocab=163840, dim=2304, num_heads=32, max_len=1048576, norm="rms",
        norm_eps=1e-5, pos="none",
        pattern="kKKx" + "KKKx" * 5 + "KKx", mlp_hidden=9216,
        lin=dict(num_heads=32, key_dim=128, value_dim=128, conv_kernel=4,
                 chunk=64),
        mla=dict(q_latent=None, kv_latent=512, nope_dim=128, rope_dim=64,
                 v_dim=128),
        moe_experts=256, moe_top_k=8, moe_hidden=1024,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.446,
                 activation="swiglu", shared_hidden=1024, choice_bias=1e-3))
    fields.update(overrides)
    return TransformerLM(**fields)


def Zaya1LM(**overrides) -> TransformerLM:
    """The stack that ``Zyphra/ZAYA1-8B``'s config.json describes
    (``model_type`` ``zaya``), as a :class:`TransformerLM` with a
    ``pattern``: 40 layers ``Z`` (``layer_types`` all ``hybrid``) at d 2048,
    pre-norm RMSNorm eps 1e-5.  A layer is compressed convolutional
    attention (:class:`CompressedConvAttention`: 8 query heads over 2 KV
    heads of 128 in a latent, two causal convolutions of ``cca_time0`` 2
    and ``cca_time1`` 2 taps over q and k, QK-mean, value shift, L2 norm
    with a learned temperature a KV head, rotary positions of theta 5e6 on
    half of each head) and then a top-1 layer of 16 SwiGLU experts 2048
    wide chosen by a router network 256 wide that carries its state from
    layer to layer and has a seventeenth choice that computes nothing
    (``DroplessMoE(router="mlp", skip_choice=True)``); residual and
    sub-layer output are merged under learned scales and biases
    (:class:`ResidualMerge`); vocab 262272, the head tied to the embedding.

    What config.json does not say is the family's published form (Zyphra,
    arXiv:2510.04476 and the ZAYA1 report, arXiv:2511.17127; the switches
    ``zaya_use_eda``, ``zaya_use_mod``, ``scale_residual_merge`` are on in
    both sibling configurations): the router's depth and activation, the
    state's learned scale, the order of the latent's passes.
    ``benchmark/configs/zaya1-8b.json`` lists each under ``assumed``.  No
    auxiliary loss: the correction bias the router chooses by is held at
    zero (nothing here updates it outside the gradient).

    Trained like :func:`GraniteHybridLM` through ``make_train_step`` and
    ``fused_softmax_xent`` on ``model.head_kernel(params)``.  ``overrides``
    replace any field: a cut takes the first letters of the pattern, and
    ``moe={..., "held": (first, count)}`` keeps one chip's share of every
    layer's experts."""
    fields = dict(
        vocab=262272, dim=2048, num_heads=8, kv_heads=2, head_dim=128,
        max_len=131072, norm="rms", norm_eps=1e-5, pos="rotary",
        rope_theta=5e6, pattern="Z" * 40,
        cca=dict(taps=(2, 2), rotary_fraction=0.5),
        moe_experts=16, moe_top_k=1, moe_hidden=2048,
        moe=dict(router="mlp", router_hidden=256, skip_choice=True,
                 activation="swiglu"),
        tie_head=True)
    fields.update(overrides)
    return TransformerLM(**fields)


def OlmoHybridLM(**overrides) -> TransformerLM:
    """The stack that ``allenai/Olmo-Hybrid-7B``'s config.json describes
    (``model_type`` ``olmo_hybrid``), as a :class:`TransformerLM` with a
    ``pattern``: 32 layers ``LLLF`` eight times at d 3840, RMSNorm eps
    1e-6 on every sub-layer's output; ``L`` Gated DeltaNet mixers of 30
    heads with keys 96 and values 192 wide, conv 4, beta in (0, 2); ``F``
    attention of 30 heads of 128 with QK-norm over the whole q and k; a
    SwiGLU MLP 11008 wide after each; vocab 100352, untied head.  What
    config.json does not give is set by the family's convention: the
    norms' place (OLMo 2's), chunks of 64 for the delta rule, no rotary
    embedding (``rope_theta`` is null: the linear layers carry the
    order).  ``overrides`` replace any field: a cut takes the first
    letters of the pattern.  Trained like :func:`OLMoELM` through
    ``make_train_step`` and ``fused_softmax_xent``."""
    fields = dict(
        vocab=100352, dim=3840, num_heads=30, max_len=65536, norm="rms",
        norm_eps=1e-6, pos="none", qk_norm=True, pattern="LLLF" * 8,
        lin=dict(num_heads=30, key_dim=96, value_dim=192, conv_kernel=4,
                 chunk=64, allow_neg_eigval=True),
        mlp_hidden=11008)
    fields.update(overrides)
    return TransformerLM(**fields)


def OLMoELM(**overrides) -> TransformerLM:
    """OLMoE-1B-7B (Muennighoff et al., arXiv:2409.02060; the widths of
    ``allenai/OLMoE-1B-7B-0125-Instruct``'s config.json) as a
    :class:`TransformerLM`: 16 pre-norm blocks of RMSNorm (eps 1e-5),
    d 2048, 16 heads of 128 with QK-norm and rotary positions (theta
    10000), 64 SwiGLU experts 1024 wide with top-8 routing (gates not
    renormalised, no dropped token), vocab 50304, untied head.
    ``overrides`` replace any field (``depth=1``, ``attn="flash"``, the
    dtypes...).

    Trained through :func:`horovod_tpu.jax.spmd.make_train_step` with the
    router's two auxiliary terms (0.01 and 0.001 are OLMoE's)::

        model = OLMoELM(depth=1, attn="flash")

        def loss_fn(params, aux, tokens):
            h, state = model.apply({"params": params}, tokens[:, :-1],
                                   return_hidden=True,
                                   mutable=["intermediates"])
            ce = fused_softmax_xent(h.reshape(-1, model.dim),
                                    params["head"]["kernel"],
                                    tokens[:, 1:].reshape(-1)).mean()
            balance, z = router_losses(state["intermediates"])
            return ce + 0.01 * balance + 0.001 * z, aux

        step = make_train_step(loss_fn, optax.adamw(4e-4), hvd.ranks_mesh())
        previous = None
        for batch in loader:
            params, aux, opt_state, loss = step(params, aux, opt_state, batch)
            if previous is not None:
                previous.block_until_ready()    # the loss of the step before
            previous = loss

    Reading the previous step's loss keeps the host one step ahead of the
    device and no more (an unread loop deadlocks the 8-device CPU mesh,
    PERF.md section 7).
    """
    fields = dict(vocab=50304, dim=2048, depth=16, num_heads=16,
                  max_len=4096, norm="rms", norm_eps=1e-5, pos="rotary",
                  rope_theta=10000.0, qk_norm=True, moe_experts=64,
                  moe_top_k=8, moe_hidden=1024)
    fields.update(overrides)
    return TransformerLM(**fields)
