"""Chrome-tracing timeline — the reference's Horovod Timeline on TPU.

Mirrors ``horovod/common/timeline.{h,cc}``: each named tensor is modelled as
a trace "process" (metadata event naming it); spans cover the negotiation
phase (NEGOTIATE_ALLREDUCE etc. with per-rank instant events), a QUEUE span
(response constructed → executor start, the reference's time-in-queue
bracket, ``operations.h:35``), the top-level operation, and nested
activities (MEMCPY_IN_FUSION_BUFFER, XLA_ALLREDUCE, ...).  Opened on EVERY
rank when ``HOROVOD_TPU_TIMELINE`` is set: the value is a path template
(a literal ``{rank}`` placeholder, or ``.rank<R>`` inserted before the
extension in multi-rank jobs — ``per_rank_trace_path``), each trace opens
with a ``trace_t0`` wall-clock anchor, and the coordinator records
``clock_offset`` estimates so ``tools/trace_merge.py`` can merge the
per-rank files onto one timebase.  Output loads in ``chrome://tracing`` /
Perfetto.

This complements (does not replace) the XLA profiler: it shows the
control-plane life cycle of every named tensor, which device-side profiles
cannot see.

A C++ implementation with identical output lives in ``cpp/timeline.{h,cc}``
and is used when the native core is loaded; this module is the fallback and
the format specification.

The jitted hot path and the host layers around it (``hvd.init()``,
``ShardedLoader``, ``make_train_step``) trace themselves into :data:`ring`,
a bounded in-memory :class:`SpanRing` that is always recording — the
sibling, on this side, of the native flight recorder on the eager plane.
The ``Timeline`` file is one of its readers: its ``train_step/dispatch``
lane is fed from the ring's ``step/dispatch`` span
(:meth:`Timeline.activity_span`).  ``docs/observability.md`` has the table
of spans.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional


def per_rank_trace_path(template: str, rank: int, size: int = None) -> str:
    """Resolve the ``HOROVOD_TPU_TIMELINE`` path template for one rank.

    A literal ``{rank}`` placeholder is always substituted.  Without a
    placeholder, multi-rank jobs (``size`` > 1 or unknown) get ``.rank<R>``
    inserted before the extension — ``/tmp/t.json`` → ``/tmp/t.rank1.json``
    — while single-rank jobs keep the literal path (back-compat with the
    rank-0-only tracing of earlier rounds).  Idempotent: a path already
    carrying this rank's suffix passes through unchanged (run.py fills the
    template per child AND the controller resolves it again locally).
    """
    if "{rank}" in template:
        return template.replace("{rank}", str(rank))
    if size is not None and size <= 1:
        return template
    root, ext = os.path.splitext(template)
    if root.endswith(f".rank{rank}"):
        return template
    return f"{root}.rank{rank}{ext}"


def wire_activity(base: str, wire_dtype: str) -> str:
    """Activity name for a data-plane transfer, tagged with the negotiated
    ring wire compression — ``TCP_ALLREDUCE[int8]`` — so traces show what
    actually rode the wire.  Raw fp32 transfers keep the bare name (no
    ``[fp32]`` suffix: pre-compression traces stay comparable)."""
    return f"{base}[{wire_dtype}]" if wire_dtype else base


class Timeline:
    FLUSH_EVERY_S = 1.0   # reference timeline.h:32

    def __init__(self, path: str, rank: int = 0):
        self._file = open(path, "w")
        self._file.write("[")
        self._lock = threading.Lock()
        self._first_event = True
        self._t0 = time.monotonic()
        self._t0_ns = time.perf_counter_ns()    # the span ring's clock
        t0_wall_us = int(time.time() * 1e6)
        self._tensor_pids: Dict[str, int] = {}
        self._next_pid = 1
        self._last_flush = time.monotonic()
        self._closed = False
        self.rank = rank
        # Absolute anchor: ts 0 of this trace is t0_wall_us on this
        # process's wall clock.  trace_merge.py keys per-rank alignment
        # off this event.
        self._emit({"name": "trace_t0", "ph": "i", "s": "g", "pid": 0,
                    "ts": 0, "args": {"rank": rank,
                                      "t0_wall_us": t0_wall_us}})

    # ----------------------------------------------------------- primitives

    def _ts_us(self) -> int:
        return int((time.monotonic() - self._t0) * 1e6)

    def _emit(self, ev: dict):
        with self._lock:
            if self._closed:
                return
            # Comma BEFORE each event after the first: a process killed
            # mid-run leaves a file missing only the closing "]", which
            # trace_merge.py repairs trivially, while close() produces
            # strictly valid JSON (Perfetto's trace_processor rejects the
            # old trailing-comma form).
            self._file.write("\n" if self._first_event else ",\n")
            self._first_event = False
            self._file.write(json.dumps(ev))
            now = time.monotonic()
            if now - self._last_flush > self.FLUSH_EVERY_S:
                self._file.flush()
                self._last_flush = now

    def _pid(self, tensor_name: str) -> int:
        with self._lock:
            pid = self._tensor_pids.get(tensor_name)
            created = pid is None
            if created:
                pid = self._next_pid
                self._next_pid += 1
                self._tensor_pids[tensor_name] = pid
        if created:
            # Metadata event registering the tensor as a trace process
            # (reference timeline.cc:51-68); emitted exactly once per tensor.
            self._emit({"name": "process_name", "ph": "M", "pid": pid,
                        "args": {"name": tensor_name}})
            self._emit({"name": "process_sort_index", "ph": "M", "pid": pid,
                        "args": {"sort_index": pid}})
        return pid

    # ---------------------------------------------------------- negotiation

    def negotiate_start(self, tensor_name: str, request_type) -> None:
        from horovod_tpu.core import request_type_name
        self._emit({"ph": "B", "pid": self._pid(tensor_name),
                    "ts": self._ts_us(),
                    "name": f"NEGOTIATE_{request_type_name(request_type)}"})

    def negotiate_rank_ready(self, tensor_name: str, rank: int) -> None:
        self._emit({"ph": "i", "pid": self._pid(tensor_name),
                    "ts": self._ts_us(), "s": "p", "name": str(rank)})

    def negotiate_end(self, tensor_name: str) -> None:
        self._emit({"ph": "E", "pid": self._pid(tensor_name),
                    "ts": self._ts_us()})

    # ------------------------------------------------------------ operation

    def start(self, tensor_name: str, response_type) -> None:
        name = {0: "ALLREDUCE", 1: "ALLGATHER", 2: "BROADCAST",
                3: "ERROR"}.get(int(response_type), "UNKNOWN")
        self._emit({"ph": "B", "pid": self._pid(tensor_name),
                    "ts": self._ts_us(), "name": name})

    def end(self, tensor_name: str) -> None:
        self._emit({"ph": "E", "pid": self._pid(tensor_name),
                    "ts": self._ts_us()})

    def activity_start_all(self, entries, activity: str) -> None:
        for e in entries:
            self._emit({"ph": "B", "pid": self._pid(e.name),
                        "ts": self._ts_us(), "name": activity})

    def activity_end_all(self, entries) -> None:
        for e in entries:
            self._emit({"ph": "E", "pid": self._pid(e.name),
                        "ts": self._ts_us()})

    def activity_span(self, tensor_name: str, activity: str,
                      start_ns: int, end_ns: int) -> None:
        """A whole activity on ``tensor_name``'s lane as one complete
        event, from a span the caller timed itself on
        ``time.perf_counter_ns()`` (the span ring's ``step/dispatch``)."""
        self._emit({"ph": "X", "pid": self._pid(tensor_name),
                    "ts": (start_ns - self._t0_ns) // 1000,
                    "dur": (end_ns - start_ns) // 1000, "name": activity})

    def cache_hit_tick(self, dur_us: int) -> None:
        """Complete-event span (``"ph": "X"``) marking a negotiation tick
        served entirely from the response cache — visually distinct from
        NEGOTIATE_* spans; ``dur`` is the full tick latency."""
        self._emit({"ph": "X", "pid": 0, "ts": self._ts_us() - int(dur_us),
                    "dur": int(dur_us), "name": "CACHED_TICK"})

    def tick_span(self, tick: int, dur_us: int) -> None:
        """Complete-event span covering one negotiation tick, tagged with
        the tick id in ``args`` — the cross-rank alignment anchor
        ``trace_merge.py`` lines per-rank traces up by."""
        dur_us = max(0, int(dur_us))
        self._emit({"ph": "X", "pid": 0, "ts": self._ts_us() - dur_us,
                    "dur": dur_us, "name": "TICK",
                    "args": {"tick": int(tick)}})

    def instant(self, name: str, args: dict = None) -> None:
        """Global instant event on the control track (``clock_offset``
        metadata, markers)."""
        self._emit({"name": name, "ph": "i", "s": "g", "pid": 0,
                    "ts": self._ts_us(), "args": args or {}})

    # ------------------------------------------------------------- counters

    def counter(self, name: str, value: int) -> None:
        """Chrome-trace counter sample (``"ph": "C"``): Perfetto renders
        each named series as a rate track alongside the spans (queue
        depth, bytes in flight).  Counters live on pid 0 — they are
        job-level series, not per-tensor ones."""
        self._emit({"ph": "C", "pid": 0, "ts": self._ts_us(),
                    "name": name, "args": {"value": int(value)}})

    def flush(self) -> None:
        """Force buffered events to disk — abort paths call this so a
        trace survives even when the process dies mid-run."""
        with self._lock:
            if not self._closed:
                self._file.flush()

    def close(self):
        with self._lock:
            if not self._closed:
                self._file.write("\n]\n")
                self._file.close()
                self._closed = True


# ---------------------------------------------------------- the span ring


class Span(NamedTuple):
    """One closed span, as :meth:`SpanRing.snapshot` gives it."""
    id: int
    parent: int             # the span open on this thread at its start; 0: none
    name: str
    thread: int             # threading.get_ident() of the thread it ran on
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    key: Optional[int]      # what spans of one unit of work share (below)


class _ThreadSpans:
    """One thread's open spans, innermost last, and the spans it added
    after the fact most recently (:meth:`SpanRing.add`)."""
    __slots__ = ("ident", "open", "late")

    def __init__(self):
        self.ident = threading.get_ident()
        self.open: list = []
        self.late: collections.deque = collections.deque(maxlen=64)


class _OpenSpan:
    """A span from ``with ring.span(name)`` on; ``start_ns`` and
    ``end_ns`` are stamped whether or not the ring keeps it."""
    __slots__ = ("_ring", "_thread", "id", "parent", "name", "key",
                 "start_ns", "end_ns")

    def __init__(self, ring, name, key):
        self._ring = ring
        self._thread = None
        self.id = self.parent = 0
        self.name = name
        self.key = key

    def __enter__(self):
        ring = self._ring
        if ring.enabled:
            thread = self._thread = ring._thread()
            if thread.open:
                outer = thread.open[-1]
                self.parent = outer.id
                if self.key is None:
                    self.key = outer.key
            self.id = next(ring._ids)
            thread.open.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        thread = self._thread
        if thread is not None:
            if thread.open and thread.open[-1] is self:
                thread.open.pop()
            elif self in thread.open:       # closed out of order
                thread.open.remove(self)
            self._ring._keep([self.id, self.parent, self.name, thread.ident,
                              self.start_ns, self.end_ns, self.key])
        return False


class SpanRing:
    """The last ``maxlen`` spans of this process, in memory.

    ``with ring.span(name, key=k):`` times a piece of host work on
    ``time.perf_counter_ns()``.  Its ``parent`` is the span open on the
    same thread when it opened; ``key`` is what the spans of one unit of
    work share — the call ordinal for everything a dispatch of the train
    step causes, the batch ordinal for everything the loader does to one
    batch — and a span given none takes its parent's.  A span is kept
    when it closes, so children stand before their parents.

    Always recording and bounded: the oldest span makes room for the
    newest and ``dropped`` counts those.  No file, no thread, no
    environment variable, no lock beyond the deque's own.  Nothing leaves
    memory until a reader asks: :meth:`snapshot` (tuples), :meth:`events`
    (Chrome-trace ``X`` events for Perfetto).  ``enabled`` is an
    attribute for an A/B of the ring's own cost and for tests, not a
    knob: switched off, ``span()`` still stamps its two clock reads (the
    timeline lane and the observatory read them) and keeps nothing.

    The spans are not mirrored into ``jax.profiler``'s trace as
    annotations: the host tracer's lowest level that keeps annotations
    also keeps the runtime's per-tile ``Transpose`` spans, a million of
    them in five steps of a 38.5 MB batch (``PERF.md`` section 6, PR 34).
    :meth:`events` takes the shift between this clock and another
    trace's instead, and :func:`horovod_tpu.profiling.capture`, which
    finds that shift from the trace it records, writes the device's ops
    and these spans into one file on one clock.
    """

    PROCESS_NAME = "host (horovod_tpu ring)"
    SLACK_NS = 1_000_000    # between jax's clocks and ours, for add()

    def __init__(self, maxlen: int = 16384):
        self.enabled = True
        self.dropped = 0
        self._spans: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._thread_names: Dict[int, str] = {}

    def _thread(self) -> _ThreadSpans:
        try:
            return self._local.spans
        except AttributeError:
            thread = self._local.spans = _ThreadSpans()
            self._thread_names[thread.ident] = threading.current_thread().name
            return thread

    def _keep(self, record: list) -> None:
        spans = self._spans
        if len(spans) == spans.maxlen:
            self.dropped += 1
        spans.append(record)

    def span(self, name: str, key: Optional[int] = None) -> _OpenSpan:
        return _OpenSpan(self, name, key)

    def open_names(self) -> List[str]:
        """Names of the calling thread's open spans, outermost first."""
        return [s.name for s in self._thread().open]

    def add(self, name: str, start_ns: int, end_ns: int) -> Optional[int]:
        """Keep a span known only once it has ended (a duration jax
        reports, a collection).  Its parent is the innermost span open on
        this thread that had opened by ``start_ns``; spans added this way
        before it that lie inside it become its children (jax reports an
        inner ``jit``'s trace before the outer one's, and a cache read
        before the compile it is part of).  Returns its id."""
        if not self.enabled:
            return None
        thread = self._thread()
        parent, key = 0, None
        for outer in reversed(thread.open):
            if outer.start_ns <= start_ns + self.SLACK_NS:
                parent, key = outer.id, outer.key
                break
        span_id = next(self._ids)
        for earlier in reversed(thread.late):
            if earlier[5] <= start_ns:
                break
            if (earlier[1] == parent
                    and earlier[4] >= start_ns - self.SLACK_NS):
                earlier[1] = span_id
        record = [span_id, parent, name, thread.ident, start_ns, end_ns, key]
        thread.late.append(record)
        self._keep(record)
        return span_id

    def snapshot(self) -> List[Span]:
        """The kept spans, oldest first."""
        return [Span(*record) for record in self._spans.copy()]

    def events(self, shift_ns: int = 0,
               pid: Optional[int] = None) -> List[dict]:
        """The kept spans as Chrome-trace complete events of one process,
        :attr:`PROCESS_NAME`, with their threads' names: ``json.dump``
        them for Perfetto.  ``ts`` and ``dur`` are microseconds of
        ``time.perf_counter_ns()`` less ``shift_ns``: the shift is what
        sets the spans on another trace's clock, and ``pid`` what keeps
        them clear of its processes (this process's id by default)."""
        pid = os.getpid() if pid is None else pid
        spans = self.snapshot()
        out = [{"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": self.PROCESS_NAME}}]
        out.extend({"ph": "M", "pid": pid, "tid": ident,
                    "name": "thread_name", "args": {
                        "name": self._thread_names.get(ident, str(ident))}}
                   for ident in sorted({s.thread for s in spans}))
        out.extend({"ph": "X", "pid": pid, "tid": s.thread, "name": s.name,
                    "ts": (s.start_ns - shift_ns) / 1e3,
                    "dur": (s.end_ns - s.start_ns) / 1e3,
                    "args": {"id": s.id, "parent": s.parent, "key": s.key}}
                   for s in spans)
        return out

    def clear(self) -> None:
        self._spans.clear()
        self.dropped = 0


def self_ns(spans: List[Span]) -> Dict[int, int]:
    """Each span's self time: its length less the part of it its
    children cover."""
    children: Dict[int, list] = collections.defaultdict(list)
    for s in spans:
        children[s.parent].append(s)
    out = {}
    for s in spans:
        covered, reached = 0, s.start_ns
        for c in sorted(children.get(s.id, ()), key=lambda c: c.start_ns):
            start = max(c.start_ns, reached)
            end = min(c.end_ns, s.end_ns)
            if end > start:
                covered += end - start
                reached = end
        out[s.id] = s.end_ns - s.start_ns - covered
    return out


#: The process's ring.  ``hvd.shutdown()`` leaves it as it is.
ring = SpanRing()

# jax's own phases of building a program, as spans: each is a duration
# jax reports when the phase ends.
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    # compile_or_get_cached as a whole: on a hit of the persistent cache
    # the read-back lies inside it, and is its child here.
    "/jax/core/compile/backend_compile_duration": "jax/compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax/cache_read",
}
_listening_to_jax = False


def listen_to_jax() -> None:
    """Register, once, the listener that keeps jax's phases in the ring
    and counts ``step.compiles``: backend compilations (or cache reads)
    under a ``step/*`` span.  One after set-up; more is a recompilation,
    and its ``jax/compile`` span carries the call ordinal."""
    global _listening_to_jax
    if _listening_to_jax:
        return
    _listening_to_jax = True
    import jax.monitoring
    from horovod_tpu.metrics import registry

    def on_duration(event, seconds, **_):
        name = JAX_PHASES.get(event)
        if name is None:
            return
        end_ns = time.perf_counter_ns()
        kept = ring.add(name, end_ns - int(seconds * 1e9), end_ns)
        if (kept and name == "jax/compile"
                and any(n.startswith("step/") for n in ring.open_names())):
            registry.inc("step.compiles")

    jax.monitoring.register_event_duration_secs_listener(on_duration)


_full_gc_started = [0]


def _on_gc(phase, info):
    # Called for every collection: returns at once for all but the full
    # ones, whose pauses nothing else in a trace accounts for.
    if info["generation"] != 2:
        return
    if phase == "start":
        _full_gc_started[0] = time.perf_counter_ns()
    elif _full_gc_started[0]:
        ring.add("host/gc", _full_gc_started[0], time.perf_counter_ns())
        _full_gc_started[0] = 0


gc.callbacks.append(_on_gc)
