"""horovod_tpu — a TPU-native distributed training framework.

Brand-new implementation of the capabilities of Horovod v0.15.1
(reference: steve-engineml/horovod, surveyed in ``SURVEY.md``), designed for
TPU hardware: topology from the pod runtime instead of ``mpirun``, XLA
collectives over the ICI mesh instead of MPI/NCCL, trace-time gradient
fusion instead of runtime fusion-buffer memcpys, and a jit/shard_map-first
SPMD API with an eager negotiated path for dynamic use.

Quick start (mirrors the reference's 4-step usage, ``README.md``)::

    import horovod_tpu as hvd
    hvd.init()                                # 1. topology from the pod
    mesh = hvd.ranks_mesh()                   # 2. the world mesh
    # 3. wrap your optimizer  (see horovod_tpu.jax.DistributedOptimizer)
    # 4. broadcast initial parameters from rank 0
"""

from horovod_tpu.basics import (           # noqa: F401
    init, shutdown, is_initialized, size, local_size, rank, local_rank,
    process_index, process_count, devices, local_devices, ranks_mesh,
    hierarchical_mesh, get_topology, mpi_threads_supported, wire_dtype,
    NotInitializedError,
)
# Callable module: ``hvd.metrics()`` returns the merged snapshot while
# ``hvd.metrics.registry`` / ``.prometheus_text()`` expose the machinery.
from horovod_tpu import metrics        # noqa: F401, E402
# Callable module: ``hvd.observe()`` returns the merged local+fleet
# observatory view; ``hvd.observe.note_step`` feeds the decomposition.
from horovod_tpu import observe        # noqa: F401, E402
from horovod_tpu.ops.eager import (        # noqa: F401
    allreduce, allreduce_async, allgather, allgather_async, broadcast,
    broadcast_async, poll, synchronize, PerRank, scatter_ranks,
    CollectiveError, HorovodAbortedError, HorovodRetryableError,
)
from horovod_tpu.process_set import (      # noqa: F401, E402
    ProcessSet, add_process_set, remove_process_set, process_set_by_name,
    reconfigure_process_set,
)
from horovod_tpu.publish import ParameterPublisher   # noqa: F401, E402
from horovod_tpu import elastic            # noqa: F401, E402
from horovod_tpu.ops import injit          # noqa: F401
from horovod_tpu.ops.injit import (        # noqa: F401
    SUM, AVERAGE, MIN, MAX,
)
from horovod_tpu.compression import Compression   # noqa: F401
# Submodule surfaces (imported last — they depend on the names above):
from horovod_tpu import jax                # noqa: F401, E402
from horovod_tpu import callbacks          # noqa: F401, E402
from horovod_tpu import sparse             # noqa: F401, E402

__version__ = "0.1.0"
