"""In-jit quantized collectives — the compiled-path analogue of the eager
ring's int8 wire (EQuARX-style, arXiv 2506.17615).

The eager cross-process plane already narrows fp32 payloads to per-block
absmax int8 on the socket (``cpp/htpu/quantize.cc``); this module ports
that codec into the XLA data plane so gradients inside ``shard_map`` move
as int8 too.  Layout parity is bit-exact with the C++ codec: one fp32
scale per :data:`BLOCK_ELEMS`-element block, ``scale = max(absmax/127,
FLT_MIN)`` (1.0 for all-zero blocks), ``q = round(clip(x * (1/scale),
-127, 127))`` with ties-to-even — so a chunk quantized here can be
decoded by the C++ plane and vice versa (see
:func:`host_wire_encode` / ``tests/test_quantized_collectives.py``).

Quantized values cannot ride ``lax.psum``/``lax.psum_scatter`` directly —
int8 sums overflow, and per-block scales don't commute with the
reduction — so :func:`quantized_ring_allreduce` schedules the ring
explicitly with ``lax.ppermute``: quantize → ring reduce-scatter over
int8 shards (dequantize-sum-requantize at every accumulate hop, each hop
re-deriving block scales from the fp32 partial sum) → allgather of the
owned shard → dequantize.  XLA overlaps the per-hop codec work with the
permute DMAs; accumulation stays fp32 throughout.

The quantize/dequantize kernels are Pallas (``pltpu``) so on TPU the
codec fuses into VMEM-resident blocks next to the DMA; under
``JAX_PLATFORMS=cpu`` the same kernels run in interpret mode, and
``HOROVOD_TPU_INJIT_PALLAS=0`` selects a pure-``jnp`` reference codec
(bit-identical output, used by the parity tests as a cross-check).

Policy: only bulk gradients quantize.  1-D leaves (norms, biases) and
anything under the size floor (``HOROVOD_TPU_INJIT_INT8_FLOOR`` fp32
bytes, default 64 KiB) stay on the raw psum path — their bytes don't pay
for the codec and their precision matters most.  The knob surface
mirrors the eager plane: the ``compression=`` argument selects the wire,
``HOROVOD_TPU_INJIT_WIRE_DTYPE`` overrides the default process-wide.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import _pallas
from horovod_tpu.parallel.hierarchical import all_gather_invariant

# Block geometry — MUST match cpp/htpu/quantize.h (kInt8BlockElems,
# kSubChunkElems): one fp32 absmax scale per 1024 elements, wire images
# framed in self-contained 64K-element sub-chunks.
BLOCK_ELEMS = 1024
SUB_CHUNK_ELEMS = 64 * 1024

# Smallest normal fp32 (FLT_MIN).  Scales are clamped here so a block
# whose absmax is subnormal still gets a finite 1/scale: without the
# clamp, absmax/127 can underflow to 0 and exact-zero elements quantize
# to 0 * inf = NaN (the Int8Compressor edge case this PR fixes — the C++
# BlockScale carries the same clamp).
MIN_SCALE = 1.17549435e-38

# f32(1/127), the exact constant the C++ BlockScale multiplies by.
INV_127 = float(__import__("numpy").float32(1.0) / __import__("numpy").float32(127.0))

_ENV_WIRE = "HOROVOD_TPU_INJIT_WIRE_DTYPE"
_ENV_FLOOR = "HOROVOD_TPU_INJIT_INT8_FLOOR"
_ENV_PALLAS = "HOROVOD_TPU_INJIT_PALLAS"

DEFAULT_INT8_FLOOR_BYTES = 64 << 10

# Grid rows per Pallas program instance: 8 sublanes of fp32 input, each
# row one quantization block laid across the 1024-lane minor dim.
_ROWS = 8


# --------------------------------------------------------------- policy


def resolve_injit_compression(compression):
    """Apply the ``HOROVOD_TPU_INJIT_WIRE_DTYPE`` override.

    Mirrors the eager plane's ``HOROVOD_TPU_WIRE_DTYPE``: the env knob
    fills in the wire dtype only where the call site left the default
    ``NoneCompressor`` — an explicit ``compression=`` argument wins.
    Accepts the same wire-dtype *names* the eager ``hvd.allreduce``
    does (``"none"``/``"bf16"``/``"fp16"``/``"int8"``); an explicit
    ``"none"`` string pins the raw wire regardless of the env.
    """
    from horovod_tpu.compression import (
        NoneCompressor, canonical_wire_dtype, compressor_for_wire)
    if is_auto(compression):
        # Adaptive-precision autopilot (HOROVOD_TPU_PRECISION=auto): not a
        # static compressor — callers resolve per bucket at trace/submit
        # time through horovod_tpu.precision.  Passed through unchanged.
        return compression
    if isinstance(compression, str):
        # Explicit string wins outright — including "none", which pins the
        # raw wire regardless of the env knob.
        return compressor_for_wire(canonical_wire_dtype(
            compression.strip().lower(), source="compression"))
    is_default = (compression is NoneCompressor
                  or isinstance(compression, NoneCompressor))
    if not is_default:
        return compression
    name = os.environ.get(_ENV_WIRE, "").strip().lower()
    wire = canonical_wire_dtype(name, source=_ENV_WIRE)
    if wire == "":
        return compression
    return compressor_for_wire(wire)


def is_auto(compression) -> bool:
    """True for the ``compression="auto"`` marker — wire dtype chosen per
    bucket by the adaptive-precision autopilot rather than statically."""
    return (isinstance(compression, str)
            and compression.strip().lower() == "auto")


def is_int8(compression) -> bool:
    from horovod_tpu.compression import Int8Compressor
    return (compression is Int8Compressor
            or isinstance(compression, Int8Compressor)
            or (isinstance(compression, type)
                and issubclass(compression, Int8Compressor)))


def int8_floor_bytes() -> int:
    return int(os.environ.get(_ENV_FLOOR, str(DEFAULT_INT8_FLOOR_BYTES)))


def int8_eligible(shape, dtype, *, floor_bytes: int | None = None) -> bool:
    """Whether a gradient leaf goes over the int8 wire.

    Bulk matmul gradients (>= 2-D, at or above the size floor) quantize;
    1-D leaves (layernorm gains, biases) and small tensors stay raw —
    the policy table in docs/concepts.md.
    """
    if floor_bytes is None:
        floor_bytes = int8_floor_bytes()
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return False
    if len(shape) < 2:
        return False
    return math.prod(shape) * 4 >= floor_bytes


# ---------------------------------------------------------------- codec


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas(*operands) -> bool:
    """Whether the codec runs as the Pallas kernels.  ``_ENV_PALLAS=0``
    selects the jnp codec outright.  So does an *interpreted* kernel on
    operands that vary over a ``shard_map`` axis: the generic interpreter
    evaluates the kernel body op by op under the vma check, which refuses
    a varying block meeting a constant.  Compiled Mosaic never evaluates
    the body, so on TPU the ring always runs the kernels; the two codecs
    are bit-identical (tests/test_quantized_collectives.py)."""
    if os.environ.get(_ENV_PALLAS, "1") == "0":
        return False
    return not _pallas.xla_form(
        _interpret(), any(jax.typeof(o).vma for o in operands))


def _block_scale(absmax):
    # Multiply by the f32 reciprocal of 127 (not divide): XLA rewrites a
    # divide-by-constant into a reciprocal multiply, so bit-parity with
    # the C++ BlockScale holds only with both planes multiplying.
    scale = jnp.maximum(absmax * INV_127, MIN_SCALE)
    return jnp.where(absmax > 0, scale, jnp.float32(1.0))


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = _block_scale(absmax)
    inv = 1.0 / scale
    q = jnp.round(jnp.clip(x * inv, -127.0, 127.0))
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _deq_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _jnp_quantize(grid):
    absmax = jnp.max(jnp.abs(grid), axis=1, keepdims=True)
    scale = _block_scale(absmax)
    inv = 1.0 / scale
    q = jnp.round(jnp.clip(grid * inv, -127.0, 127.0)).astype(jnp.int8)
    return q, scale


def _pallas_quantize(grid):
    from jax.experimental import pallas as pl
    blocks = grid.shape[0]
    return pl.pallas_call(
        _quant_kernel,
        grid=(blocks // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, BLOCK_ELEMS), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((_ROWS, BLOCK_ELEMS), lambda i: (i, 0)),
                   pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))),
        out_shape=(_pallas.struct((blocks, BLOCK_ELEMS), jnp.int8, grid),
                   _pallas.struct((blocks, 1), jnp.float32, grid)),
        interpret=_interpret(),
    )(grid)


def _pallas_dequantize(q, scales):
    from jax.experimental import pallas as pl
    blocks = q.shape[0]
    return pl.pallas_call(
        _deq_kernel,
        grid=(blocks // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, BLOCK_ELEMS), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, BLOCK_ELEMS), lambda i: (i, 0)),
        out_shape=_pallas.struct((blocks, BLOCK_ELEMS), jnp.float32, q,
                                 scales),
        interpret=_interpret(),
    )(q, scales)


def quantize_blocks(flat):
    """Quantize a flat fp32 vector (size a multiple of BLOCK_ELEMS) to
    ``(q int8 [blocks, 1024], scales fp32 [blocks, 1])`` — the same
    block grid and scale rule as ``EncodeWireChunk``."""
    size = flat.shape[0]
    assert size % BLOCK_ELEMS == 0, size
    blocks = size // BLOCK_ELEMS
    grid = flat.reshape(blocks, BLOCK_ELEMS).astype(jnp.float32)
    if not _use_pallas(grid):
        return _jnp_quantize(grid)
    rows = -(-blocks // _ROWS) * _ROWS
    if rows != blocks:
        # Zero rows quantize to (q=0, scale=1) and are sliced back off.
        grid = jnp.pad(grid, ((0, rows - blocks), (0, 0)))
    q, scales = _pallas_quantize(grid)
    return q[:blocks], scales[:blocks]


def dequantize_blocks(q, scales):
    """Inverse of :func:`quantize_blocks`: flat fp32 of size
    ``blocks * BLOCK_ELEMS`` (``float(q) * scale``, as DecodeWireChunk)."""
    blocks = q.shape[0]
    if not _use_pallas(q, scales):
        return (q.astype(jnp.float32) * scales).reshape(-1)
    rows = -(-blocks // _ROWS) * _ROWS
    if rows != blocks:
        q = jnp.pad(q, ((0, rows - blocks), (0, 0)))
        scales = jnp.pad(scales, ((0, rows - blocks), (0, 0)),
                         constant_values=1.0)
    out = _pallas_dequantize(q, scales)
    return out[:blocks].reshape(-1)


def snap_to_grid(x):
    """Quantize + dequantize ``x`` onto its int8 block grid (fp32 out,
    same shape).  The local quantization operator ``Q`` used both by
    ``Int8Compressor`` and by error-feedback residuals
    (``residual = g - Q(g)``)."""
    n = x.size
    blocks = -(-n // BLOCK_ELEMS)
    flat = jnp.ravel(x).astype(jnp.float32)
    padded = blocks * BLOCK_ELEMS
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    q, scales = quantize_blocks(flat)
    return dequantize_blocks(q, scales)[:n].reshape(x.shape)


# ------------------------------------------------------- ring allreduce


def _allgather(v, axis_name):
    # Varying -> Invariant gather where jax tracks VMA (same trick as
    # hierarchical_allreduce); plain all_gather otherwise.
    if jax.typeof(v).vma:
        return all_gather_invariant(v, axis_name, axis=0, tiled=False)
    return lax.all_gather(v, axis_name, axis=0, tiled=False)


def quantized_ring_allreduce(x, axis_name: str, *, average: bool = False):
    """Allreduce ``x`` over ``axis_name`` with int8 on every hop.

    Must run inside ``shard_map``/``pmap`` with ``axis_name`` in scope.
    Ring reduce-scatter: at step ``s`` each rank quantizes its fp32
    partial sum of chunk ``(rank - s) mod n``, ppermutes the int8
    payload + block scales to ``rank + 1``, and dequantize-adds the
    received chunk into its accumulator — per-block rescale at every
    accumulate hop, so the wire never carries more than 8 bits/element
    plus 4 scale bytes per 1024.  After ``n - 1`` steps rank ``r`` owns
    the fully reduced chunk ``(r + 1) mod n``; one final quantized
    allgather + dequantize materializes the full result.

    ``lax.psum_scatter`` cannot express the per-hop rescale (it reduces
    in the wire dtype), hence the explicit ``lax.ppermute`` schedule.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_dtype, orig_shape = x.dtype, x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    size = flat.shape[0]
    chunk = -(-(-(-size // n)) // BLOCK_ELEMS) * BLOCK_ELEMS
    padded = chunk * n
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    acc = flat.reshape(n, chunk)
    idx = lax.axis_index(axis_name)
    perm = [(r, (r + 1) % n) for r in range(n)]
    for s in range(n - 1):
        send_i = jnp.mod(idx - s, n)
        cur = lax.dynamic_slice_in_dim(acc, send_i, 1, axis=0)[0]
        q, scales = quantize_blocks(cur)
        q = lax.ppermute(q, axis_name, perm)
        scales = lax.ppermute(scales, axis_name, perm)
        recv_i = jnp.mod(idx - s - 1, n)
        prev = lax.dynamic_slice_in_dim(acc, recv_i, 1, axis=0)
        upd = prev + dequantize_blocks(q, scales).reshape(1, chunk)
        acc = lax.dynamic_update_slice_in_dim(acc, upd, recv_i, axis=0)
    own_i = jnp.mod(idx + 1, n)
    own = lax.dynamic_slice_in_dim(acc, own_i, 1, axis=0)[0]
    q, scales = quantize_blocks(own)
    gq = _allgather(q, axis_name)              # (n, blocks, 1024)
    gs = _allgather(scales, axis_name)         # (n, blocks, 1)
    blocks = chunk // BLOCK_ELEMS
    deq = dequantize_blocks(gq.reshape(n * blocks, BLOCK_ELEMS),
                            gs.reshape(n * blocks, 1))
    # Gathered row r holds chunk (r + 1) mod n; rotate back into order.
    full = jnp.roll(deq.reshape(n, chunk), 1, axis=0).reshape(-1)[:size]
    if average:
        full = full / n
    return full.reshape(orig_shape).astype(orig_dtype)


# ----------------------------------------------- bytes-on-wire estimate


def ring_wire_bytes(size: int, n: int) -> int:
    """Estimated per-rank bytes a :func:`quantized_ring_allreduce` of
    ``size`` elements sends over ``n`` ranks: 2(n-1) hops of one int8
    chunk + its fp32 block-scale header."""
    if n <= 1:
        return 0
    chunk = -(-(-(-size // n)) // BLOCK_ELEMS) * BLOCK_ELEMS
    hop = chunk + (chunk // BLOCK_ELEMS) * 4
    return 2 * (n - 1) * hop


def _dtype_key(dtype) -> str:
    return {"float32": "fp32", "bfloat16": "bf16",
            "float16": "fp16"}.get(jnp.dtype(dtype).name,
                                   jnp.dtype(dtype).name)


def estimate_wire_plan(tree, n: int, compression,
                       hierarchical: bool = False) -> Dict[str, int]:
    """Per-step, per-rank bytes-on-wire estimate for a gradient tree,
    keyed by wire dtype — the numbers behind the
    ``injit.bytes#wire_dtype=*`` counters.

    Raw psum legs are modeled as a bandwidth-optimal ring
    (``2(n-1)/n * payload``), the int8 leg with its exact chunk + scale
    framing.  Estimates, not reconciled counts: XLA owns the actual
    collective schedule inside the compiled program.
    """
    compression = resolve_injit_compression(compression)
    plan: Dict[str, int] = {}
    if n <= 1:
        return plan
    int8 = is_int8(compression)
    for leaf in jax.tree.leaves(tree):
        shape = tuple(leaf.shape)
        dtype = jnp.dtype(leaf.dtype)
        size = math.prod(shape) if shape else 1
        if (int8 and not hierarchical
                and int8_eligible(shape, dtype)):
            key, nbytes = "int8", ring_wire_bytes(size, n)
        else:
            if not jnp.issubdtype(dtype, jnp.floating):
                wire = dtype
            elif int8:
                # Hierarchical falls back to snap-to-grid over a bf16
                # wire; ineligible leaves stay raw.
                wire = (jnp.dtype(jnp.bfloat16)
                        if hierarchical and int8_eligible(
                            shape, dtype, floor_bytes=0) else dtype)
            else:
                wire = jnp.dtype(getattr(compression, "wire_dtype", None)
                                 or dtype)
            key = _dtype_key(wire)
            nbytes = 2 * (n - 1) * size * wire.itemsize // n
        if nbytes:
            plan[key] = plan.get(key, 0) + nbytes
    return plan


def record_wire_plan(plan: Dict[str, int], steps: int = 1) -> None:
    """Fold a wire plan into the process metrics registry (one call per
    dispatched step batch).  The steps themselves, ``injit.steps``, are
    counted where the step is dispatched, on every mesh size
    (``spmd._StepInstruments``)."""
    from horovod_tpu.metrics import registry
    for key, nbytes in plan.items():
        registry.inc(f"injit.bytes#wire_dtype={key}", nbytes * steps)


# -------------------------------------------- host wire image (parity)


def host_wire_encode(values) -> bytes:
    """Encode a host fp32 array into the C++ int8 wire image
    (``EncodeWireChunk`` framing: per 64K-element sub-chunk, an fp32
    scale header then the int8 payload) using THIS module's codec —
    the cross-plane parity hook."""
    import numpy as np
    arr = np.ascontiguousarray(values, dtype=np.float32).reshape(-1)
    out = bytearray()
    for lo in range(0, arr.size, SUB_CHUNK_ELEMS):
        seg = arr[lo:lo + SUB_CHUNK_ELEMS]
        blocks = -(-seg.size // BLOCK_ELEMS)
        pad = blocks * BLOCK_ELEMS - seg.size
        flat = np.pad(seg, (0, pad)) if pad else seg
        q, scales = quantize_blocks(jnp.asarray(flat))
        out += np.asarray(scales).reshape(-1).astype("<f4").tobytes()
        out += np.asarray(q).reshape(-1)[:seg.size].tobytes()
    return bytes(out)


def host_wire_decode(buf: bytes, n_elems: int):
    """Decode a C++ int8 wire image with THIS module's codec; inverse
    framing of :func:`host_wire_encode`."""
    import numpy as np
    out = np.empty(n_elems, dtype=np.float32)
    pos = 0
    for lo in range(0, n_elems, SUB_CHUNK_ELEMS):
        length = min(SUB_CHUNK_ELEMS, n_elems - lo)
        blocks = -(-length // BLOCK_ELEMS)
        scales = np.frombuffer(buf, dtype="<f4", count=blocks,
                               offset=pos).copy()
        pos += blocks * 4
        q = np.frombuffer(buf, dtype=np.int8, count=length,
                          offset=pos).copy()
        pos += length
        pad = blocks * BLOCK_ELEMS - length
        if pad:
            q = np.pad(q, (0, pad))
        deq = dequantize_blocks(
            jnp.asarray(q).reshape(blocks, BLOCK_ELEMS),
            jnp.asarray(scales).reshape(blocks, 1))
        out[lo:lo + length] = np.asarray(deq)[:length]
    return out
