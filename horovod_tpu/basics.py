"""Process-global framework state: init / shutdown / rank queries.

API parity with the reference's ``HorovodBasics`` ctypes bridge
(``horovod/common/__init__.py:51-154``): every query raises if called before
``init()``, ``shutdown()`` is registered with ``atexit``, and ``init()`` may
restrict the job to a subset of ranks.

Unlike the reference there is no ``mpirun``: topology comes from the TPU pod
runtime via JAX (see :mod:`horovod_tpu.topology`).  The background controller
(C++ core, :mod:`horovod_tpu.core`) is started here, mirroring
``InitializeHorovodOnce`` (``horovod/common/operations.cc:1907-1925``).
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, NamedTuple, Optional, Sequence

from horovod_tpu import topology as _topology_mod
from horovod_tpu.timeline import ring


class NotInitializedError(RuntimeError):
    """Raised when a query runs before ``init()``.

    Mirrors ``'Horovod has not been initialized; use hvd.init().'``
    (reference ``horovod/common/__init__.py:92-96``).
    """

    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; use hvd.init().")


class _GlobalState:
    """Singleton framework state (mirrors ``HorovodGlobalState``,
    reference ``horovod/common/operations.cc:112-247``)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.shut_down = False
        self.topology: Optional[_topology_mod.Topology] = None
        self.controller = None          # horovod_tpu.core.Controller
        self.mesh = None                # default 1-D 'ranks' mesh
        self.atexit_registered = False


_state = _GlobalState()


class _Live(NamedTuple):
    """What an initialized framework holds, read at one moment."""
    topology: _topology_mod.Topology
    controller: Any                     # horovod_tpu.core.Controller
    mesh: Any


def _require_init() -> _Live:
    """The initialized state, or :class:`NotInitializedError`.  The fields
    are read BEFORE the flag and ``shutdown()`` lowers the flag before it
    clears them, so a thread that races a shutdown gets whole fields or
    the documented error, never a ``None``."""
    live = _Live(_state.topology, _state.controller, _state.mesh)
    if not _state.initialized:
        raise NotInitializedError()
    return live


def init(ranks: Optional[Sequence[int]] = None) -> None:
    """Initialize the framework.

    ``ranks``: optional subset of global device ranks to participate,
    mirroring ``hvd.init(comm=[...])`` (reference
    ``horovod/common/__init__.py:58-68``).  Safe to call more than once
    (subsequent calls are no-ops, as in ``InitializeHorovodOnce``).
    """
    with _state.lock:
        if _state.initialized:
            return
        # In the span ring: ``init`` over ``init/topology``, ``mesh``,
        # ``init/controller`` and, below the controller or whoever asks
        # for it first, ``init/native_core`` (``cpp_core.load``).
        with ring.span("init"):
            _init_locked(ranks)


def _init_locked(ranks) -> None:
    with ring.span("init/topology"):
        _state.topology = _topology_mod.resolve(ranks)
    # Multi-controller pod without a TCP control plane: the in-jit SPMD
    # path (make_train_step, injit ops, the global mesh) needs no
    # negotiation at all — XLA's runtime carries the collectives — so
    # init() succeeds and only the *eager* (negotiated) API is gated:
    # its first call fails fast with a clear error instead of the
    # silent 60 s stall-deadlock it would otherwise hit (each process
    # would submit only its local ranks' requests while size() spans
    # the whole pod).  The reference initializes unconditionally under
    # its launcher (``operations.cc:1435-1532``); the control plane is
    # likewise never optional-but-blocking here.
    with ring.span("mesh"):
        from horovod_tpu.parallel import mesh as _mesh_mod
        _state.mesh = _mesh_mod.build_ranks_mesh(_state.topology)
    with ring.span("init/controller"):
        from horovod_tpu import core as _core_mod
        _state.controller = _core_mod.Controller(_state.topology, _state.mesh)
        # Elastic standby: the controller adopted the identity the
        # coordinator assigned at admission (process index, rank, world
        # size) — the env-derived snapshot above is a placeholder.
        _state.topology = _state.controller.topology
        # Multi-process: the controller's layout exchange discovered which
        # processes share this host (reference: shared-memory comm split,
        # operations.cc:1499-1509); fold that into the topology so
        # local_rank() reports the discovered index.
        if _state.controller.host_local_rank is not None:
            import dataclasses
            _state.topology = dataclasses.replace(
                _state.topology,
                local_rank_override=_state.controller.host_local_rank)
        _state.controller.start()
    from horovod_tpu import metrics as _metrics_mod
    _metrics_mod.start_exporters(_state.topology.rank)
    if not _state.atexit_registered:
        atexit.register(shutdown)
        _state.atexit_registered = True
    _state.shut_down = False
    _state.initialized = True


def shutdown() -> None:
    """Shut the framework down (idempotent; registered with atexit, mirroring
    reference ``horovod/common/__init__.py:69``).  The span ring stays as
    it is: what a run recorded is read after it."""
    with _state.lock:
        if not _state.initialized:
            return
        try:
            if _state.controller is not None:
                _state.controller.stop()
        finally:
            from horovod_tpu import metrics as _metrics_mod
            _metrics_mod.stop_exporters()
            # Registered process sets die with the job — the next init
            # re-seeds the registry from HOROVOD_TPU_PROCESS_SETS.
            from horovod_tpu import process_set as _process_set_mod
            _process_set_mod.reset()
            _state.initialized = False      # before the fields: _require_init
            _state.controller = None
            _state.topology = None
            _state.mesh = None
            _state.shut_down = True


def is_initialized() -> bool:
    return _state.initialized


def size() -> int:
    """Total number of ranks (= participating TPU chips)."""
    return _require_init().topology.size


def local_size() -> int:
    """Number of ranks (chips) owned by this process."""
    return _require_init().topology.local_size


def rank() -> int:
    """Global rank of this process's first chip; rank 0 is the coordinator."""
    return _require_init().topology.rank


def local_rank() -> int:
    """Index of this process among processes on the same host."""
    return _require_init().topology.local_rank


def process_index() -> int:
    return _require_init().topology.process_index


def process_count() -> int:
    return _require_init().topology.process_count


def local_devices():
    return _require_init().topology.local_devices


def devices():
    return _require_init().topology.devices


def ranks_mesh():
    """The default 1-D ``('ranks',)`` mesh over all participating chips."""
    return _require_init().mesh


def hierarchical_mesh(ici_size=None):
    """Two-tier ``('dcn', 'ici')`` mesh whose ``ici`` groups are the
    devices' PHYSICAL slice membership (host locality as fallback; an
    explicit ``ici_size`` forces a fixed split) — the device-level
    analogue of the reference's local/cross communicator pair
    (``operations.cc:1499-1532``).  Pair with
    :func:`horovod_tpu.parallel.hierarchical.hierarchical_allreduce`."""
    from horovod_tpu.parallel import mesh as _mesh_mod
    return _mesh_mod.build_hierarchical_mesh(_require_init().topology,
                                             ici_size)


def get_topology():
    """The resolved job topology snapshot — pass it to
    :func:`horovod_tpu.parallel.mesh.build_mesh` to lay custom mesh shapes
    (dp/tp/pp/sp/ep axes) over the participating chips."""
    return _require_init().topology


def controller():
    return _require_init().controller


def metrics() -> dict:
    """One merged metrics snapshot: the native core's registry (ring bytes
    per wire dtype, tick/gather/negotiation latency, aborts, stalls) plus
    the controller-side series (enqueues/ops by type, handle wait time,
    fusion-buffer utilization), as ``{"counters", "gauges", "histograms",
    "ts", "rank"}``.  Works before init too (native counters may already
    exist); see docs/observability.md."""
    from horovod_tpu import metrics as _metrics_mod
    return _metrics_mod.snapshot()


def wire_dtype() -> str:
    """Effective process-wide default for the cross-process ring's wire
    compression (``HOROVOD_TPU_WIRE_DTYPE``): "" = raw fp32, or
    "bf16"/"fp16"/"int8".  Per-call ``allreduce(..., compression=...)``
    overrides it; all ranks must agree per tensor or negotiation raises a
    coordinated error."""
    from horovod_tpu.core import default_wire_dtype
    return default_wire_dtype()


def mpi_threads_supported() -> bool:
    """Parity shim for ``hvd.mpi_threads_supported()``
    (reference ``horovod/common/__init__.py:140-154``).

    There is no MPI on the TPU path; the control plane (gRPC/TCP) is always
    thread-safe, so this reports True once initialized.
    """
    _require_init()
    return True


def check_mesh_async_ordering(what: str) -> None:
    """Raise when launching a jitted collective program would race
    outstanding async eager collectives on a SHARED multi-controller
    runtime.

    On such a runtime every process must launch mesh programs in the
    same order; an ``*_async`` op whose program is still executing in
    the background can interleave differently per process with a newly
    dispatched jitted step — the cross-process deadlock/corruption the
    reference's coordinator exists to prevent
    (``operations.cc:1414-1433``).  No-op before init, on disjoint
    runtimes (TCP data plane), and single-process jobs.
    """
    c = _state.controller
    if c is None:
        return
    n = c.mesh_async_hazard()
    if n:
        raise RuntimeError(
            f"{what} would dispatch a jitted collective program while "
            f"{n} async eager collective(s) are still outstanding on a "
            f"shared multi-controller runtime.  Call synchronize() (or "
            f"poll() until done) on every *_async handle before "
            f"dispatching jitted steps, so all processes launch mesh "
            f"programs in the same order (see docs/running.md).")
