"""Input pipeline utilities — the framework side of the data contract.

The reference delegates data loading to its host frameworks but its
examples all repeat the same moves: shard the dataset per rank
(``DistributedSampler`` / ``dataset.shard``, e.g.
``examples/pytorch_mnist.py:98-103``), feed each step, keep per-rank
batch counts equal so no rank stalls the collectives.  On TPU the same
contract plus two TPU-specific needs:

* on a multi-controller pod each process must contribute ONLY its local
  rows of the global batch (``jax.make_array_from_process_local_data``);
* the host work of producing batch k+1 (generation, augmentation,
  ``device_put`` staging) should overlap the device running step k —
  and with ``make_train_step(steps_per_call=k)`` batches must arrive
  stacked k-deep.

:class:`ShardedLoader` packages all of it: wrap any iterable of host
batches (pytrees with a common leading batch dim), get back an iterator
of mesh-sharded device arrays, prefetched ``prefetch`` batches ahead on
a background thread, optionally stacked for the multi-step scan.

The loader traces itself into the span ring (:mod:`horovod_tpu.timeline`),
every span keyed by the ordinal of the batch it serves.  On the producer
thread: ``loader/source`` (``next(source)``), ``loader/stage`` (stack and
``device_put``), ``loader/put_wait`` (blocked on a full queue: the thread
is ahead).  On the consumer: ``loader/get_wait`` (blocked on an empty
one).  The registry carries the same for a job too long for a ring:
counters ``loader.batches``, ``loader.bytes`` (host bytes staged) and
``loader.starved`` (gets that waited over a millisecond), gauge
``loader.queue_depth`` (batches ready at each get).
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Any, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.metrics import registry
from horovod_tpu.timeline import ring

STARVED_NS = 1_000_000   # a get that waited longer counts in loader.starved


def shard_for_process(batch, mesh: Mesh, spec=None):
    """Turn this PROCESS's local rows into a global mesh-sharded array.

    Single-controller: a plain sharded ``device_put`` (the batch is the
    global batch).  Multi-controller: the batch is only this process's
    shard of the global batch (the pod input contract —
    ``docs/running.md``), assembled with
    ``jax.make_array_from_process_local_data``.

    Contract warning: on a pod every process must pass its OWN rows; if
    every process holds the identical GLOBAL batch instead, use
    :func:`horovod_tpu.jax.spmd.shard_batch` — mixing the two contracts
    silently duplicates rows into an inflated global batch.
    """
    if spec is None:
        spec = P(tuple(mesh.axis_names))
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda a: jax.make_array_from_process_local_data(
                sharding, np.asarray(a)), batch)
    return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)


class ShardedLoader:
    """Prefetching, mesh-sharding batch iterator.

    ``it`` yields host batches (pytrees; every leaf shares the leading
    batch dimension of this process's shard).  Iterating the loader
    yields device-resident, mesh-sharded batches; staging runs on a
    daemon thread ``prefetch`` batches ahead so host-side batch prep
    overlaps device compute.

    ``steps_per_call=k`` groups k consecutive batches and stacks them on
    a new leading axis — the layout :func:`make_train_step` expects for
    its multi-step scan; a trailing group smaller than k is dropped
    (like the reference's equal-batch-count contract, a partial scan
    call would desynchronize ranks).
    """

    def __init__(self, it, mesh: Mesh, *, spec=None,
                 steps_per_call: int = 1, prefetch: int = 2):
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got "
                             f"{steps_per_call}")
        if prefetch < 1:
            raise ValueError(f"prefetch must be >= 1, got {prefetch}")
        # A zero-arg factory supports multi-epoch re-iteration; a plain
        # iterable/generator is single-use (a silently-empty second epoch
        # would be a training bug, so it raises instead).
        self._factory = it if callable(it) else None
        self._it = None if callable(it) else it
        self._consumed = False
        self._mesh = mesh
        base = spec if spec is not None else P(tuple(mesh.axis_names))
        # The scan axis leads every leaf when stacking: shard the dims
        # after it (mirrors make_train_step's batch_spec transform).
        self._spec = P(*([None] + list(base))) if steps_per_call > 1 \
            else base
        self._k = steps_per_call
        self._prefetch = prefetch

    def _stage(self, batch):
        if self._k > 1:
            batch = jax.tree.map(
                lambda *xs: np.stack(xs), *batch)
        return shard_for_process(batch, self._mesh, self._spec)

    def __iter__(self) -> Iterator[Any]:
        if self._factory is not None:
            source = self._factory()
        else:
            if self._consumed:
                raise RuntimeError(
                    "ShardedLoader built from a plain iterable is "
                    "single-use (a generator would silently yield an "
                    "empty second epoch); pass a zero-arg factory for "
                    "multi-epoch iteration")
            self._consumed = True
            source = self._it
        q: "queue.Queue" = queue.Queue(maxsize=self._prefetch)
        stop = threading.Event()
        _END = object()

        def put(item, ordinal=None) -> bool:
            # Bounded put that gives up when the consumer went away, so
            # an abandoned iteration can't wedge the producer thread
            # holding device-resident batches forever.  One span over the
            # whole wait, not one a poll.
            with ring.span("loader/put_wait", key=ordinal):
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return True
                    except queue.Full:
                        continue
            return False

        def stage_and_put(host, ordinal) -> bool:
            with ring.span("loader/stage", key=ordinal):
                staged = self._stage(host)
            registry.inc("loader.batches")
            registry.inc("loader.bytes", sum(
                a.nbytes if hasattr(a, "nbytes") else np.asarray(a).nbytes
                for a in jax.tree.leaves(host)))
            return put(staged, ordinal)

        def produce():
            try:
                batches = iter(source)
                for ordinal in itertools.count():
                    group = []
                    while len(group) < self._k:
                        with ring.span("loader/source", key=ordinal):
                            host_batch = next(batches, _END)
                        if host_batch is _END:
                            # trailing partial group dropped (see class
                            # docstring)
                            put(_END)
                            return
                        if stop.is_set():
                            return
                        group.append(host_batch)
                    host = group[0] if self._k == 1 else tuple(group)
                    if not stage_and_put(host, ordinal):
                        return
            except BaseException as exc:   # noqa: BLE001 — re-raised below
                put(exc)

        thread = threading.Thread(target=produce, daemon=True,
                                  name="horovod_tpu-data-prefetch")
        thread.start()
        try:
            for ordinal in itertools.count():
                registry.set_gauge("loader.queue_depth", q.qsize())
                with ring.span("loader/get_wait", key=ordinal) as waited:
                    item = q.get()
                if waited.end_ns - waited.start_ns > STARVED_NS:
                    registry.inc("loader.starved")
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()


def epoch_batches(x, y, batch_size: int, *, rank: int, size: int,
                  seed: Optional[int] = None):
    """Per-rank epoch iterator over in-memory arrays — the
    ``DistributedSampler`` pattern (reference
    ``examples/pytorch_mnist.py:98-103``): optional epoch shuffle
    (identical permutation on every rank via ``seed``), rank-strided
    rows, equal batch counts everywhere (tail dropped).
    """
    n = x.shape[0]
    order = np.arange(n)
    if seed is not None:
        np.random.RandomState(seed).shuffle(order)
    mine = order[rank::size]
    # Batch count derived from the GLOBAL minimum (n // size), not this
    # rank's local row count: with n % size != 0 some ranks hold one row
    # more, and a locally-derived count would let them dispatch an extra
    # collective step nobody else joins (pod deadlock).
    per_rank = (n // size) // batch_size
    for b in range(per_rank):
        idx = mine[b * batch_size:(b + 1) * batch_size]
        yield x[idx], y[idx]
