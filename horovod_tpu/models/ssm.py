"""Mamba-2's mixer as a flax module: the state-space sub-layer of a hybrid
stack (:class:`~horovod_tpu.models.transformer.TransformerLM` with a
``pattern``).

``u`` (B, T, d) in, (B, T, d) out, no bias but the convolution's::

    [z | xBC | dt] = W_in u          inner | inner + 2 G N | H   (inner = H P)
    xBC = silu(causal depthwise conv_k(xBC) + b)   -> x (H, P), B, C (G, N)
    dt  = softplus(dt + dt_bias),  a_t = exp(-dt_t exp(A_log_h))
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D_h x_t
    y   = RMSNorm_grouped(y * silu(z)) * scale     (groups of inner / G)
    out = W_out y

The recurrence is :func:`horovod_tpu.ops.ssd.ssd_scan_packed` (chunks of
``chunk`` tokens, float32 states passed between them; it reads x, B, C
out of the convolution's one array, and runs as Pallas kernels where the
shapes tile, as XLA otherwise: ``ops/ssd.py`` chooses).  In a trace the
module's scopes are ``in_proj``, ``conv``, ``scan``, ``gate_norm`` and
``out_proj``; ``make_train_step`` counts ``ssm.scan_chunks``,
``ssm.state_bytes`` (the float32 states passed between chunks, in VMEM
where the kernels run) and ``ssm.fused_scans`` (scans that took the
kernels) from what the module notes of its shapes while traced.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.ssd import scan_plan, scan_sizes, ssd_scan_packed
from horovod_tpu.parallel.moe import note_layer


def dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """Mamba's: ``dt`` log-uniform in [dt_min, dt_max], floored, and the
    bias its inverse softplus."""
    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class CausalConv(nn.Module):
    """The parameters of a depthwise causal convolution over time,
    ``kernel`` taps a channel and, unless ``use_bias`` is off, a bias
    (``None`` then); :func:`causal_conv` applies them (the mixer does,
    inside the block it recomputes)."""
    kernel: int = 4
    param_dtype: Any = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, channels: int):
        bound = 1.0 / math.sqrt(self.kernel)      # torch's Conv1d default

        def uniform(key, shape, dtype):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        return (self.param("kernel", uniform, (self.kernel, channels),
                           self.param_dtype),
                self.param("bias", uniform, (channels,), self.param_dtype)
                if self.use_bias else None)


def causal_conv(x, w, b=None):
    """``y_t = b + sum_j w_j x_{t - (K - 1) + j}`` on ``x`` (..., T, c) with
    ``w`` (K, c): K shifted multiply-adds, in ``x.dtype``; no ``b``, no
    bias."""
    K, T = w.shape[0], x.shape[-2]
    padded = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(K - 1, 0), (0, 0)])
    w = w.astype(x.dtype)
    y = 0 if b is None else b.astype(x.dtype)
    for j in range(K):
        y = y + w[j] * padded[..., j:j + T, :]
    return y


class Mamba2Mixer(nn.Module):
    """Module docstring.  ``num_heads`` heads of ``head_dim`` channels,
    ``n_groups`` groups of B and C with ``state_size`` columns each.

    Kept for the backward pass: the input projection's output, the scan's
    ``y`` and the normalised ``y``; the convolution, its activation, the
    scan's tiles and the gate are recomputed."""
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        Bsz, T, d = u.shape
        H, P, G, N = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, gn = H * P, G * N
        if H % G or inner % G:
            raise ValueError(f"{G} groups divide neither {H} heads nor "
                             f"their {inner} channels")

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)

        zxbcdt = dense(2 * inner + 2 * gn + H, "in_proj")(u)
        z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
        conv_w, conv_b = CausalConv(self.conv_kernel, self.param_dtype,
                                    name="conv")(inner + 2 * gn)
        dt_bias = self.param("dt_bias", dt_bias_init(
            self.dt_min, self.dt_max, self.dt_floor), (H,), self.param_dtype)
        A_log = self.param("A_log", a_log_init, (H,), self.param_dtype)
        D = self.param("D", nn.initializers.ones, (H,), self.param_dtype)
        scale = self.param("gate_norm", nn.initializers.ones, (inner,),
                           self.param_dtype)

        interpret = jax.default_backend() != "tpu"

        @jax.checkpoint
        def conv_and_scan(xBC, dt, conv_w, conv_b, dt_bias, A_log, D):
            with jax.named_scope("conv"):
                xBC = nn.silu(causal_conv(xBC, conv_w, conv_b))
            with jax.named_scope("scan"):
                dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                return ssd_scan_packed(
                    xBC, dt, -jnp.exp(A_log.astype(jnp.float32)), D,
                    heads=H, groups=G, state=N, chunk=self.chunk,
                    interpret=interpret)

        @jax.checkpoint
        def gate_norm(y, z, scale):
            with jax.named_scope("gate_norm"):
                y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
                y = y.reshape(Bsz, T, G, inner // G)
                y = y * jax.lax.rsqrt(
                    jnp.mean(y * y, axis=-1, keepdims=True) + self.norm_eps)
                return (y.reshape(Bsz, T, inner) * scale).astype(self.dtype)

        y = gate_norm(conv_and_scan(xBC, dt, conv_w, conv_b, dt_bias, A_log,
                                    D), z, scale)
        sizes = scan_sizes(Bsz, T, H, P, N, self.chunk)
        fused = scan_plan(xBC, dt, heads=H, head_dim=P, groups=G, state=N,
                          chunk=self.chunk, interpret=interpret
                          ).form == "kernels"
        note_layer(self.path, {"ssm.scan_chunks": sizes["chunks"],
                               "ssm.state_bytes": sizes["state_bytes"],
                               "ssm.fused_scans": int(fused)})
        return dense(d, "out_proj")(y)
