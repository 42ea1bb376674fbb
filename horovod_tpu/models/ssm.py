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
shapes tile, as XLA otherwise: ``ops/ssd.py`` chooses).  The convolution
with its activation and the gated norm are one pass each over their
activation: Pallas kernels that read ``xBC`` and ``z`` as column ranges of
the input projection's one array where rows and channels tile
(:mod:`horovod_tpu.ops.mixer_passes` chooses; float32 inside, one
rounding at the store), as XLA on the split arrays otherwise
(:func:`causal_conv`, in the activations' dtype, and the gate in
float32).  What the plans take: groups of B and C from many (8 groups
of 8 heads, a group a grid step of the scan) down to ONE over every head
(64 heads of 64: the scan splits the group's heads into tiles of 8, a
tile a grid step, and sums the tiles' ``dB`` and ``dC``; the gated norm
holds all 4,096 channels of a row in a block of 128 rows and gathers a
row's sums 512 channels at a time), chunks of 128 or 256.  In a trace
the module's scopes are ``in_proj``, ``conv``,
``scan``, ``gate_norm`` and ``out_proj``; ``make_train_step`` counts
``ssm.scan_chunks``, ``ssm.state_bytes`` (the float32 states passed
between chunks, in VMEM where the kernels run), ``ssm.fused_scans``
(scans that took the kernels), ``ssm.fused_passes`` (of the two
passes, those that took theirs), ``ssm.head_tiles`` (the tiles a group's
heads are split in for the scan: 1 where a group is a grid step) and
``ssm.group_channels`` (the channels of a group, ``inner / G``) from
what the module notes of its shapes while traced.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.layer_notes import note_layer
from horovod_tpu.ops import _pallas
from horovod_tpu.ops.mixer_passes import conv_silu, gated_norm, passes_plan
from horovod_tpu.ops.ssd import scan_plan, scan_sizes, ssd_scan_packed


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


def gated_group_norm(y, z, scale, *, groups: int, eps: float):
    """``rmsnorm_group(y * silu(z)) * scale`` on (..., inner) arrays, a
    group ``inner / groups`` channels: the gate, the mean square and the
    scale in float32, the result in ``y.dtype``."""
    inner = y.shape[-1]
    g = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    g = g.reshape(*y.shape[:-1], groups, inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(y.shape) * scale).astype(y.dtype)


def _padded_product(x, kernel, pad, dtype):
    x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=dtype)
    kernel = jnp.pad(kernel, ((0, 0), (0, pad)))
    return jax.lax.dot_general(x, kernel, (((x.ndim - 1,), (0,)), ((), ())))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def padded_product(x, kernel, pad, dtype):
    """``x @ kernel`` in ``dtype`` with ``pad`` zero columns after the
    kernel's own, and autodiff's gradients.  The backward pass casts and
    pads the kernel again, behind a barrier that keeps the compiler from
    sharing the forward's copy: a cast alone fuses into both products,
    but the padded bfloat16 copy would be kept from the forward to the
    backward pass, 55 MB a mixer at the cell's widths."""
    return _padded_product(x, kernel, pad, dtype)


def _padded_product_fwd(x, kernel, pad, dtype):
    return _padded_product(x, kernel, pad, dtype), (x, kernel)


def _padded_product_bwd(pad, dtype, res, dout):
    x, kernel = res
    kernel, dout = jax.lax.optimization_barrier((kernel, dout))
    return jax.vjp(lambda x, k: _padded_product(x, k, pad, dtype), x,
                   kernel)[1](dout)


padded_product.defvjp(_padded_product_fwd, _padded_product_bwd)


class PaddedDense(nn.Module):
    """``nn.Dense`` without a bias whose output is ``pad`` zero columns
    wider than its ``features``: the kernel (in, features) is the
    parameter, padded as it is cast.  Why: the TPU compiler lays a
    product's output out with its *rows* minor where the columns do not
    fill whole 128-lane tiles and no XLA op reads it (2 x 8,192 x 10,304
    in the cell: 0.8 ms a copy, three a layer, before a kernel could read
    it)."""
    features: int
    pad: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), self.param_dtype)
        return padded_product(x, kernel, self.pad, self.dtype)


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

        interpret = _pallas.interpret()
        passes = passes_plan(u, inner=inner, conv_dim=inner + 2 * gn,
                             groups=G, kernel=self.conv_kernel,
                             interpret=interpret)
        fused_passes = passes.form == "kernels"
        width = 2 * inner + 2 * gn + H
        if fused_passes:
            # The kernels read xBC and z out of the projection's one array,
            # and keep their inputs alone for the backward pass.
            zxbcdt = PaddedDense(width, -width % 128, self.dtype,
                                 self.param_dtype, name="in_proj")(u)
            z = xBC = zxbcdt
            dt = zxbcdt[..., width - H:width]

            def conv(xBC, w, b):
                return conv_silu(xBC, w, b, first=inner, plan=passes,
                                 interpret=interpret)

            def gate(y, z, scale):
                with jax.named_scope("gate_norm"):
                    return gated_norm(y, z, scale, groups=G,
                                      eps=self.norm_eps, plan=passes,
                                      interpret=interpret)
        else:
            zxbcdt = dense(width, "in_proj")(u)
            z, xBC, dt = jnp.split(zxbcdt, [inner, width - H], axis=-1)

            def conv(xBC, w, b):
                return nn.silu(causal_conv(xBC, w, b))

            @jax.checkpoint
            def gate(y, z, scale):
                with jax.named_scope("gate_norm"):
                    return gated_group_norm(y, z, scale, groups=G,
                                            eps=self.norm_eps)

        conv_w, conv_b = CausalConv(self.conv_kernel, self.param_dtype,
                                    name="conv")(inner + 2 * gn)
        dt_bias = self.param("dt_bias", dt_bias_init(
            self.dt_min, self.dt_max, self.dt_floor), (H,), self.param_dtype)
        A_log = self.param("A_log", a_log_init, (H,), self.param_dtype)
        D = self.param("D", nn.initializers.ones, (H,), self.param_dtype)
        scale = self.param("gate_norm", nn.initializers.ones, (inner,),
                           self.param_dtype)

        @jax.checkpoint
        def conv_and_scan(xBC, dt, conv_w, conv_b, dt_bias, A_log, D):
            with jax.named_scope("conv"):
                xBC = conv(xBC, conv_w, conv_b)
            with jax.named_scope("scan"):
                dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
                return ssd_scan_packed(
                    xBC, dt, -jnp.exp(A_log.astype(jnp.float32)), D,
                    heads=H, groups=G, state=N, chunk=self.chunk,
                    interpret=interpret)

        y = gate(conv_and_scan(xBC, dt, conv_w, conv_b, dt_bias, A_log, D),
                 z, scale)
        sizes = scan_sizes(Bsz, T, H, P, N, self.chunk)
        scan = scan_plan(xBC, dt, heads=H, head_dim=P, groups=G, state=N,
                         chunk=self.chunk, interpret=interpret)
        note_layer(self.path, {"ssm.scan_chunks": sizes["chunks"],
                               "ssm.state_bytes": sizes["state_bytes"],
                               "ssm.fused_scans": int(scan.form == "kernels"),
                               "ssm.fused_passes": 2 * int(fused_passes),
                               "ssm.head_tiles": scan.tiles,
                               "ssm.group_channels": inner // G})
        return dense(d, "out_proj")(y)
