"""Gated DeltaNet's mixer as a flax module: the linear-attention sub-layer
of a hybrid stack (:class:`~horovod_tpu.models.transformer.TransformerLM`
with a ``pattern``, letter ``L``).

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
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.layer_notes import note_layer
from horovod_tpu.models.ssm import (
    CausalConv, a_log_init, causal_conv, dt_bias_init)
from horovod_tpu.ops.gated_delta import delta_sizes, gated_delta_rule


# ``dt_bias`` starts as the inverse softplus of a log-uniform step in
# [1e-3, 1e-1] floored at 1e-4, as Mamba's does (``models/ssm.py``).
_DT_MIN_MAX_FLOOR = (1e-3, 1e-1, 1e-4)


def l2_normalise(u, eps: float):
    """``u / |u|_2`` over the last axis, in float32, ``eps`` under the
    root."""
    u = u.astype(jnp.float32)
    return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + eps)


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
                w_q, w_k, w_v = jnp.split(conv_w, [H * dk, 2 * H * dk],
                                          axis=1)
                q = nn.silu(causal_conv(q, w_q))
                k = nn.silu(causal_conv(k, w_k))
                v = nn.silu(causal_conv(v, w_v))
            with jax.named_scope("delta"):
                q, k = (l2_normalise(u.reshape(Bsz, T, H, dk), self.norm_eps)
                        for u in (q, k))
                q, k = (q * dk ** -0.5).astype(self.dtype), k.astype(
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
