"""Collectives for use *inside* ``jit``/``shard_map`` — the static SPMD path.

The reference executes every collective through a dynamic negotiation
(enqueue → coordinator → MPI/NCCL call, ``horovod/common/operations.cc``).
Inside an XLA program none of that is needed: program order is identical on
every rank by construction, so a collective is just an op.  These wrappers
lower straight to XLA's AllReduce / AllGather / CollectivePermute over the
ICI mesh and exist to give the reference's op surface (names, averaging,
gradient semantics) a TPU-native home:

* ``allreduce``  ↔ ``MPI_Allreduce``/``ncclAllReduce`` paths
  (``operations.cc:1268-1281, 1179-1187``); gradient of allreduce is
  allreduce (reference ``horovod/tensorflow/mpi_ops.py:93-124``) — linearity
  gives JAX that for free.
* ``allgather``  ↔ ``MPI_Allgatherv`` (``operations.cc:796-856``); gradient
  is reduce-scatter = "allreduce then slice by rank offset"
  (``mpi_ops.py:126-164``), which is exactly the transpose XLA derives.
* ``broadcast``  ↔ ``MPI_Bcast`` (``operations.cc:1333-1353``); a real
  broadcast forward (binomial tree of CollectivePermutes — no AllReduce in
  the compiled program) whose ``custom_vjp`` backward is "psum the upstream
  grad, zeroed on non-root ranks" — the registered gradient at
  ``mpi_ops.py:167-182``.  ``mode="psum"`` selects the masked-psum
  formulation instead when a VMA-*invariant* (provably replicated) output
  is required.

All take ``axis_name`` (default ``'ranks'``, the world mesh axis) and work
under ``shard_map``/``pmap`` with that axis in scope.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.mesh import RANKS_AXIS

AxisName = Union[str, Sequence[str]]

# Reduction op names, mirroring hvd's average flag plus MPI-style ops.
SUM = "sum"
AVERAGE = "average"
MIN = "min"
MAX = "max"


def num_ranks(axis_name: AxisName = RANKS_AXIS):
    return lax.axis_size(axis_name)


def rank_index(axis_name: AxisName = RANKS_AXIS):
    return lax.axis_index(axis_name)


def allreduce(x, *, average: bool = True, op: Optional[str] = None,
              axis_name: AxisName = RANKS_AXIS):
    """Sum (or average/min/max) ``x`` across ranks; every rank gets the result.

    ``average=True`` matches the reference default where gradients are
    averaged rather than summed (``horovod/tensorflow/__init__.py:45-66``).
    """
    if op is None:
        op = AVERAGE if average else SUM
    if op == AVERAGE:
        return lax.pmean(x, axis_name)
    if op == SUM:
        return lax.psum(x, axis_name)
    if op == MIN:
        return lax.pmin(x, axis_name)
    if op == MAX:
        return lax.pmax(x, axis_name)
    raise ValueError(f"unknown reduction op: {op!r}")


def allgather(x, *, axis_name: AxisName = RANKS_AXIS, axis: int = 0):
    """Concatenate ``x`` from all ranks along ``axis`` (default 0), like the
    reference's allgather contract: same shape on all ranks except possibly
    dim0 (ragged dim0 is an eager-path feature; inside jit shapes are static
    and uniform)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def _tree_broadcast(x, root_rank: int, axis_name: str):
    """Binomial-tree broadcast: ceil(log2 n) CollectivePermute rounds, the
    set of ranks holding root's value doubling each round.  No AllReduce
    appears in the program."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    rel = (idx - root_rank) % n
    cur = x
    step = 1
    while step < n:
        perm = [((root_rank + s) % n, (root_rank + s + step) % n)
                for s in range(step) if s + step < n]
        recv = lax.ppermute(cur, axis_name, perm)
        got = (rel >= step) & (rel < 2 * step)
        cur = jnp.where(got, recv, cur)
        step *= 2
    return cur


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _broadcast_permute(x, root_rank: int, axis_name: str):
    return _tree_broadcast(x, root_rank, axis_name)


def _broadcast_permute_fwd(x, root_rank, axis_name):
    return _tree_broadcast(x, root_rank, axis_name), None


def _broadcast_permute_bwd(root_rank, axis_name, _res, g):
    # The reference's registered gradient (mpi_ops.py:167-182): allreduce
    # the upstream grad; non-root ranks contribute zeros downstream.
    idx = lax.axis_index(axis_name)
    total = lax.psum(g, axis_name)
    return (jnp.where(idx == root_rank, total,
                      jnp.zeros_like(total)),)


_broadcast_permute.defvjp(_broadcast_permute_fwd, _broadcast_permute_bwd)


def broadcast(x, root_rank: int, *, axis_name: AxisName = RANKS_AXIS,
              mode: str = "permute"):
    """Every rank receives rank ``root_rank``'s value of ``x``.

    ``mode="permute"`` (default): a real broadcast — binomial tree of
    CollectivePermutes, no AllReduce in the forward program — with a
    ``custom_vjp`` reproducing the reference's registered gradient (psum
    of the cotangent, zeroed off-root, ``mpi_ops.py:167-182``).  Its
    output is VMA-**varying** (equal on every rank in fact, but the
    checker cannot see through a permute), so under
    ``shard_map(check_vma=True)`` return it through a per-rank
    ``out_spec`` (e.g. ``P('ranks')``) or keep consuming it in-scope.
    Code that returned the old masked-psum result through a REPLICATED
    ``out_spec`` (``P()``) will now fail at trace time with shard_map's
    varying-over-mesh-axes error — pass ``mode="psum"`` there to keep
    the provably-invariant formulation.

    ``mode="psum"``: the masked-psum formulation — ~2× the bytes on the
    forward but VMA-*invariant* output (usable with replicated
    ``out_specs``) and the same gradient via the autodiff transpose.
    Composite ``axis_name`` tuples always take this path (a tree over a
    product of axes would need a linearized permute).
    """
    if mode not in ("permute", "psum"):
        raise ValueError(f"broadcast mode must be 'permute' or 'psum', "
                         f"got {mode!r}")
    if mode == "psum" or not isinstance(axis_name, str):
        idx = lax.axis_index(axis_name)
        mask = (idx == root_rank).astype(x.dtype)
        return lax.psum(x * mask, axis_name)
    return _broadcast_permute(x, root_rank, axis_name)


def reducescatter(x, *, average: bool = False,
                  axis_name: AxisName = RANKS_AXIS, axis: int = 0):
    """Reduce across ranks and scatter equal chunks of ``axis`` to each rank.

    Not in the reference's public op set but it is the building block of its
    hierarchical allreduce (``ncclReduceScatter``, ``operations.cc:1090``);
    exposed because it is also the ZeRO-style primitive users expect.
    """
    out = lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)
    if average:
        out = out / lax.axis_size(axis_name)
    return out


def alltoall(x, *, axis_name: AxisName = RANKS_AXIS,
             split_axis: int = 0, concat_axis: int = 0):
    """All-to-all over the mesh axis (sequence/expert parallel building
    block; beyond the reference's three ops but first-class here)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def staged_bucket_allreduce(leaves, reduce_flat, *, bucket_bytes=None,
                            overlap: bool = False):
    """Bucketed, staged collective over a list of flat (1-D) arrays.

    The in-jit half of the plane-agnostic scheduler: leaves are packed
    into byte-bounded buckets by :func:`horovod_tpu.scheduler
    .pack_buckets` (same packer as the eager overlap path — oversized
    leaves ride alone) and ``reduce_flat`` runs once per bucket on the
    concatenated payload, staged in the scheduler's issue order.  Under
    ``overlap`` that order is reversed registration order: backward
    materializes the LAST layer's gradients first, so the tail bucket's
    collective is emitted first, with its inputs ready while earlier
    layers are still differentiating.  That orders the collectives and
    no more: XLA's scheduler does not run one under the remaining
    backward pass by itself (on the v5e every all-reduce of a step
    compiled with default options was synchronous, ``PERF.md`` section 6);
    what does is the compile options ``make_train_step`` hands its
    multi-device TPU program (``spmd._step_compiler_options``).  Bucket
    contents do not depend on the issue order, so overlap changes
    scheduling, never math.

    Returns the reduced payload re-split per leaf (flat; caller
    reshapes).  ``reduce_flat`` must be shape-polymorphic over 1-D
    arrays (e.g. a quantized ring or a hierarchical allreduce).
    """
    from horovod_tpu import scheduler as _sched
    if bucket_bytes is None:
        bucket_bytes = _sched.bucket_bytes_from_env()
    sizes = [int(l.size) * int(l.dtype.itemsize) for l in leaves]
    dtypes = [str(l.dtype) for l in leaves]
    buckets = _sched.pack_buckets(sizes, dtypes, bucket_bytes)
    out = [None] * len(leaves)
    for b in _sched.issue_order(len(buckets), overlap):
        idxs = buckets[b]
        flat = (leaves[idxs[0]].ravel() if len(idxs) == 1
                else jnp.concatenate([leaves[i].ravel() for i in idxs]))
        red = reduce_flat(flat)
        offset = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = red[offset:offset + n]
            offset += n
    return out
