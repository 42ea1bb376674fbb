"""Linear-attention mixers as flax modules, the sub-layers of a hybrid
stack (:class:`~horovod_tpu.models.transformer.TransformerLM` with a
``pattern``): :class:`GatedDeltaNet` (letter ``L``), whose decay is one
value a head, and :class:`KimiDeltaAttention` (letters ``k`` and ``K``),
whose decay is a value a key channel.

**Gated DeltaNet.**

``x`` (B, T, d) in, (B, T, d) out, no bias anywhere, ``H`` heads with keys
and queries ``d_k`` wide and values ``d_v`` wide::

    q~ = W_q x,  k~ = W_k x  (H d_k each),  v~ = W_v x  (H d_v)
    [q~ | k~ | v~] = silu(causal depthwise conv_K([q~ | k~ | v~]))
    q = q~ / |q~|_2 / sqrt(d_k),  k = k~ / |k~|_2          per head
    beta_t = 2 sigmoid(W_b x_t)        (allow_neg_eigval; else sigmoid)
    g_t = -exp(A_log_h) softplus(W_a x_t + dt_bias_h),  alpha_t = exp(g_t)
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                                 S (d_v, d_k), zero at t = 0
    y = W_o [RMSNorm_head(o) * scale * silu(W_g x)]

(Yang et al., arXiv:2412.06464; the factor 2 on beta is Grazzi et al.'s,
arXiv:2411.12537.)  The norm is over each head's ``d_v`` channels with
one learned ``d_v``-vector shared by the heads.  The recurrence is
:func:`horovod_tpu.ops.gated_delta.gated_delta_rule` (chunks of ``chunk``
tokens, a triangular solve a chunk, float32 states passed between
them).  The L2 norms, ``beta``, ``g`` and everything the chunked form
keeps in float32 are float32 whatever ``dtype`` is.

In a trace the module's scopes are ``in_proj`` (the six projections),
``conv``, ``delta`` (with the chunked form's ``solve``, ``states``,
``inter``, ``intra``), ``gate_norm`` and ``out_proj``;
``make_train_step`` counts ``lin.delta_chunks`` and ``lin.state_bytes``
(the float32 states passed between chunks) from what the module notes of
its shapes while traced, as it does the ``ssm.*`` counters.

**Kimi Delta Attention** (Kimi Linear technical report, arXiv:2510.26692;
the public ``flash-linear-attention`` KDA layer): the same rule with the
decay a diagonal matrix on the key side, two low-rank gates and a
sigmoid-gated norm.  ``H`` heads, ``d_k`` = ``d_v`` in the published
model, ``r`` the low-rank width, no bias but ``dt_bias``::

    q~ = W_q x,  k~ = W_k x,  v~ = W_v x          (H d_k, H d_k, H d_v)
    q = L2norm_head(silu(conv_K(q~))) / sqrt(d_k)
    k = L2norm_head(silu(conv_K(k~))),  v = silu(conv_K(v~))
    g_t = -exp(A_log_h) softplus(W_f_up W_f_down x_t + dt_bias)   (H, d_k)
    alpha_t = exp(g_t)                    A_log a head, dt_bias a channel
    beta_t = sigmoid(W_b x_t)                                  in (0, 1)^H
    S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                             S (d_v, d_k), zero at t = 0
    y = W_o [RMSNorm_head(o_t) * scale * sigmoid(W_g_up W_g_down x_t)]

(``S`` in this file's orientation, the transpose of the report's: the
decay scales the state's KEY side.)  ``beta`` never passes 1, so there
is no ``allow_neg_eigval``.  The recurrence is
:func:`~horovod_tpu.ops.gated_delta.gated_delta_rule` handed ``g`` of
rank 4: the chunked form whose (C, C) tiles are made from halved
sub-chunks, every factor against a reference row between the two tokens
so that no exponent is positive (that module's docstring has the
algebra).  Float32 whatever ``dtype`` is: the L2 norms, ``beta``, ``g``
from the up-projection's output on (``softplus``, ``A_log``, ``dt_bias``),
its running sums, the solve, the carried state, the gate's sigmoid and
the norm; matmul operands ``dtype``.  Kept for the backward pass, as
:class:`GatedDeltaNet` keeps its own: the projections' outputs ``q~``,
``k~``, ``v~``, ``W_b x`` and the two low-rank activations ``W_f_down x``,
``W_g_down x`` (``r`` wide: the up-projections to ``H d_k`` and ``H d_v``
run again), the rule's ``o`` and the normed, gated ``o``; the convolution,
the decays, the rule and the gate run again — where the rule's tile
kernels take the shape (``delta_plan``: keys in whole 128-lane tiles,
chunks of 64 or more) their residuals are their inputs and every chunk's
tiles are made at once; in the XLA form a group of chunks at a time.

Its scopes are ``in_proj`` (q, k, v, b), ``conv``, ``decay`` (the
low-rank decay projection, ``softplus`` and the log-decays; the rule's
running sums and the factors made of them are ``delta/decay``), ``delta``
(``solve``, ``states``, ``inter``, ``intra``), ``gate_norm`` (the
low-rank gate and the gated norm) and ``out_proj``; beside
``lin.delta_chunks`` and ``lin.state_bytes`` it notes ``lin.decay_bytes``
(float32 bytes of per-channel log-decays a call keeps),
``lin.sub_chunks`` (pairs of sub-chunks whose products make the tiles) and
``lin.tile_kernel_chunks`` (the chunks whose tiles the kernels made in
VMEM: ``lin.delta_chunks`` where they engage, 0 where the plan stood down).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.layer_notes import note_layer
from horovod_tpu.models.ssm import (
    CausalConv, a_log_init, causal_conv, dt_bias_init)
from horovod_tpu.ops import _pallas
from horovod_tpu.ops.gated_delta import (
    delta_plan, delta_sizes, gated_delta_rule)


# ``dt_bias`` starts as the inverse softplus of a log-uniform step in
# [1e-3, 1e-1] floored at 1e-4, as Mamba's does (``models/ssm.py``).
_DT_MIN_MAX_FLOOR = (1e-3, 1e-1, 1e-4)


def l2_normalise(u, eps: float):
    """``u / |u|_2`` over the last axis, in float32, ``eps`` under the
    root."""
    u = u.astype(jnp.float32)
    return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + eps)


def _conv_silu(q, k, v, conv_w, qk_channels: int):
    """``silu(causal_conv(.))`` of q, k and v, each under its columns of
    the one kernel over q | k | v (``qk_channels`` for q and for k)."""
    w_q, w_k, w_v = jnp.split(conv_w, [qk_channels, 2 * qk_channels], axis=1)
    return (nn.silu(causal_conv(q, w_q)), nn.silu(causal_conv(k, w_k)),
            nn.silu(causal_conv(v, w_v)))


def _unit_heads(q, k, shape, eps: float, dtype):
    """q and k as heads ``shape`` (B, T, H, d_k), each L2-normalised in
    float32, q over ``sqrt(d_k)``, in ``dtype``."""
    q, k = (l2_normalise(u.reshape(shape), eps) for u in (q, k))
    return (q * shape[-1] ** -0.5).astype(dtype), k.astype(dtype)


class GatedDeltaNet(nn.Module):
    """Module docstring.  Parameters ``q``, ``k``, ``v``, ``g``, ``a``,
    ``b``, ``out`` (kernels), ``conv`` (kernel over q | k | v), ``A_log``,
    ``dt_bias``, ``gate_norm``.

    Kept for the backward pass: the projections' outputs, the delta
    rule's ``o`` and the normalised, gated ``o``; the convolution, its
    activation, the L2 norms, the chunked form and the gate are
    recomputed."""
    num_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        Bsz, T, d = x.shape
        H, dk, dv = self.num_heads, self.key_dim, self.value_dim
        f32 = jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        with jax.named_scope("in_proj"):
            q, k, v, gate = (dense(H * w, name)(x) for name, w in (
                ("q", dk), ("k", dk), ("v", dv), ("g", dv)))
            a, b = dense(H, "a")(x), dense(H, "b")(x)
        conv_w, _ = CausalConv(self.conv_kernel, self.param_dtype,
                               use_bias=False, name="conv")(H * (2 * dk + dv))
        dt_bias = self.param("dt_bias", dt_bias_init(*_DT_MIN_MAX_FLOOR),
                             (H,), self.param_dtype)
        A_log = self.param("A_log", a_log_init, (H,), self.param_dtype)
        scale = self.param("gate_norm", nn.initializers.ones, (dv,),
                           self.param_dtype)

        @jax.checkpoint
        def conv_and_delta(q, k, v, a, b, conv_w, dt_bias, A_log):
            with jax.named_scope("conv"):
                q, k, v = _conv_silu(q, k, v, conv_w, H * dk)
            with jax.named_scope("delta"):
                q, k = _unit_heads(q, k, (Bsz, T, H, dk), self.norm_eps,
                                   self.dtype)
                beta = jax.nn.sigmoid(b.astype(f32))
                if self.allow_neg_eigval:
                    beta = 2.0 * beta
                g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
                    a.astype(f32) + dt_bias)
                return gated_delta_rule(q, k, v.reshape(Bsz, T, H, dv), g,
                                        beta, chunk=self.chunk)

        @jax.checkpoint
        def gate_norm(o, gate, scale):
            with jax.named_scope("gate_norm"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps)
                gate = nn.silu(gate.astype(f32)).reshape(Bsz, T, H, dv)
                return (o * scale * gate).astype(self.dtype).reshape(
                    Bsz, T, H * dv)

        y = gate_norm(conv_and_delta(q, k, v, a, b, conv_w, dt_bias, A_log),
                      gate, scale)
        sizes = delta_sizes(Bsz, T, H, dk, dv, self.chunk)
        note_layer(self.path, {"lin.delta_chunks": sizes["chunks"],
                               "lin.state_bytes": sizes["state_bytes"]})
        with jax.named_scope("out_proj"):
            return dense(d, "out")(y)


class _Kernel(nn.Module):
    """The ``kernel`` an ``nn.Dense(features, use_bias=False)`` would
    declare, returned raw: the product runs inside a block that is
    computed again in the backward pass."""
    features: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (in_features, self.features), self.param_dtype)


class KimiDeltaAttention(nn.Module):
    """Module docstring, second part.  Parameters ``q``, ``k``, ``v``,
    ``b``, ``f_a``, ``f_b`` (the decay's low-rank pair, down and up),
    ``g_a``, ``g_b`` (the gate's), ``out`` (kernels), ``conv`` (kernel
    over q | k | v), ``A_log`` (H), ``dt_bias`` (H d_k), ``gate_norm``
    (d_v).  ``low_rank``: the pairs' inner width (None: ``value_dim``, the
    head's)."""
    num_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    low_rank: Any = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        Bsz, T, d = x.shape
        H, dk, dv = self.num_heads, self.key_dim, self.value_dim
        rank = self.low_rank or dv
        f32 = jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        with jax.named_scope("in_proj"):
            q, k, v = (dense(H * w, name)(x) for name, w in (
                ("q", dk), ("k", dk), ("v", dv)))
            b = dense(H, "b")(x)
        with jax.named_scope("decay"):
            f = dense(rank, "f_a")(x)
        with jax.named_scope("gate_norm"):
            gate = dense(rank, "g_a")(x)
        w_f = _Kernel(H * dk, self.param_dtype, name="f_b")(rank)
        w_g = _Kernel(H * dv, self.param_dtype, name="g_b")(rank)
        conv_w, _ = CausalConv(self.conv_kernel, self.param_dtype,
                               use_bias=False, name="conv")(H * (2 * dk + dv))
        dt_bias = self.param("dt_bias", dt_bias_init(*_DT_MIN_MAX_FLOOR),
                             (H * dk,), self.param_dtype)
        A_log = self.param("A_log", a_log_init, (H,), self.param_dtype)
        scale = self.param("gate_norm", nn.initializers.ones, (dv,),
                           self.param_dtype)
        interpret = _pallas.interpret()

        @jax.checkpoint
        def conv_and_delta(q, k, v, f, b, conv_w, w_f, dt_bias, A_log):
            with jax.named_scope("conv"):
                q, k, v = _conv_silu(q, k, v, conv_w, H * dk)
            with jax.named_scope("decay"):
                g = (f @ w_f.astype(self.dtype)).astype(f32) + dt_bias
                g = -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
                    g.reshape(Bsz, T, H, dk))
            with jax.named_scope("delta"):
                q, k = _unit_heads(q, k, (Bsz, T, H, dk), self.norm_eps,
                                   self.dtype)
                beta = jax.nn.sigmoid(b.astype(f32))
                return gated_delta_rule(q, k, v.reshape(Bsz, T, H, dv), g,
                                        beta, chunk=self.chunk,
                                        interpret=interpret)

        @jax.checkpoint
        def gate_norm(o, gate, w_g, scale):
            with jax.named_scope("gate_norm"):
                o = o.astype(f32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps)
                gate = jax.nn.sigmoid(
                    (gate @ w_g.astype(self.dtype)).astype(f32)).reshape(
                        Bsz, T, H, dv)
                return (o * scale * gate).astype(self.dtype).reshape(
                    Bsz, T, H * dv)

        y = gate_norm(
            conv_and_delta(q, k, v, f, b, conv_w, w_f, dt_bias, A_log),
            gate, w_g, scale)
        sizes = delta_sizes(Bsz, T, H, dk, dv, self.chunk, g_rank=4)
        plan = delta_plan(self.chunk, 4, seq_len=T, key_dim=dk,
                          itemsize=q.dtype.itemsize, interpret=interpret,
                          manual_axes=bool(jax.typeof(q).vma))
        note_layer(self.path, {
            "lin.delta_chunks": sizes["chunks"],
            "lin.state_bytes": sizes["state_bytes"],
            "lin.decay_bytes": sizes["decay_bytes"],
            "lin.sub_chunks": sizes["sub_chunks"],
            "lin.tile_kernel_chunks": (
                sizes["chunks"] if plan.form == "tile_kernels" else 0)})
        with jax.named_scope("out_proj"):
            return dense(d, "out")(y)
