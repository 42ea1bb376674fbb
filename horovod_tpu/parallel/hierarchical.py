"""Hierarchical (two-tier) allreduce over the ('dcn', 'ici') mesh.

TPU-native re-design of the reference's hierarchical allreduce
(``horovod/common/operations.cc:1025-1177``): there, NCCL reduce-scatters
within a node, each local rank does a cross-node ``MPI_Allreduce`` on its
shard in parallel, and NCCL allgathers the result — so the slow inter-node
links carry only ``1/local_size`` of the bytes.

On TPU the two tiers are the ICI mesh (intra-slice, fast) and DCN
(inter-slice).  The same algebra in XLA collectives:

    reduce_scatter(ici) → allreduce(dcn) on the shard → all_gather(ici)

Unlike the reference there is no pinned-host staging buffer and no explicit
remainder pass (``operations.cc:1040-1177``): the tensor is flattened and
zero-padded up to a multiple of the ICI group size — the same divisibility
trick as the reference's fusion-buffer padding (``:1031-1039``) — and XLA
schedules the DCN transfer off the scattered shard directly in HBM.

Inside one physical slice this still helps nothing — XLA's flat ``psum``
is already optimal on a uniform ICI torus — so the flat path is the default
and this is opt-in for multi-slice meshes, exactly as
``HOROVOD_HIERARCHICAL_ALLREDUCE`` is opt-in in the reference
(``operations.cc:1575-1592``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.mesh import DCN_AXIS, ICI_AXIS

# Varying → Invariant all_gather (transpose: dynamic_slice): exactly the
# op tier 3 wants under check_vma, and what the int8 ring's final gather
# uses.  jax 0.9.0 keeps it private.  If an upgrade moves it this import
# fails loudly: the psum stand-in that would keep things running moves
# about twice the ICI bytes.
from jax._src.lax.parallel import all_gather_invariant


def hierarchical_allreduce(x, *, average: bool = False,
                           ici_axis: str = ICI_AXIS,
                           dcn_axis: str = DCN_AXIS):
    """Allreduce ``x`` across both mesh tiers, minimising DCN traffic.

    Must run under ``shard_map``/``pmap`` with both axes in scope.  Result is
    identical (up to float reassociation) to ``psum(x, (dcn, ici))``.
    """
    n_ici = lax.axis_size(ici_axis)
    flat = x.reshape(-1)
    size = flat.shape[0]
    padded = -(-size // n_ici) * n_ici
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    # Tier 1: reduce-scatter across the fast ICI links.
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    # Tier 2: each ICI position reduces its shard across slices in parallel —
    # DCN carries 1/ici_size of the payload, the reference's key trick.
    shard = lax.psum(shard, dcn_axis)
    # Tier 3: gather the reduced shards back across ICI.  Under
    # check_vma=True a plain all_gather output is tracked as varying over
    # the gathered axis, which would poison every downstream out_spec;
    # ``all_gather_invariant`` is the sound Varying→Invariant gather —
    # same ICI bytes as all_gather, provably-replicated type.
    if jax.typeof(shard).vma:
        full = all_gather_invariant(shard, ici_axis, axis=0, tiled=True)
    else:
        full = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    out = full[:size].reshape(x.shape)
    if average:
        out = out / (lax.axis_size(ici_axis) * lax.axis_size(dcn_axis))
    return out
