"""Reduction of a jax profiler Chrome trace (``*.trace.json.gz``) to the
numbers the per-layer metrics read.  The benchmark's own copy: a PR that
claims a gain cannot change how its gain is computed.

What the trace of a TPU v5e under jax 0.9.0 looks like (looked at by
hand, PR 22; ``benchmark/data/`` keeps a cut of one):

* one process per chip, ``/device:TPU:<n>``, with the threads
  ``XLA Modules`` (one span per program execution, named
  ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one span per HLO op that ran
  on the core; nested spans for ops that hold others, such as ``while``)
  and ``Async XLA Ops`` (empty in every trace of PR 22: the gradient
  all-reduces of ``gpt13b_dp4`` are synchronous spans on ``XLA Ops``);
* one ``/host:CPU`` process with the runtime's spans, which the benchmark
  turns off (they flood the trace and slow the loader's staging): the
  host's side is the benchmark's own spans on the host clock, moved onto
  the trace's clock by the ends of the steps (:func:`host_offset`);
* op spans carry ``args.hlo_category``, ``args.long_name`` (the HLO text
  of the op) and ``args.tf_op`` (jax's name stack: the flax module path
  and the primitive), so a collective is found by its HLO opcode and a
  Pallas kernel by its custom-call target, never by a fusion number.  A
  Pallas kernel's span is named after the module scope it was called in
  (``attn.21``); its ``kernel_name`` is NOT in the trace.

Times here are seconds unless a name says otherwise; the trace's own
unit is microseconds.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")
OPS_THREAD = "XLA Ops"
ASYNC_THREAD = "Async XLA Ops"
MODULES_THREAD = "XLA Modules"
US = 1e-6


# ------------------------------------------------------------ loading


def newest_trace_file(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_events(path: str) -> List[dict]:
    with gzip.open(path) as fh:
        return json.load(fh).get("traceEvents", [])


def device_pids(events: Iterable[dict]) -> Dict[int, str]:
    """``{pid: '/device:TPU:<n>'}``.  The CPU platform writes no device
    process at all."""
    names = {e["pid"]: e["args"].get("name", "") for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    return {p: n for p, n in names.items()
            if n.startswith("/device:") and "CPU" not in n}


def thread_names(events: Iterable[dict]) -> Dict[Tuple[int, int], str]:
    return {(e["pid"], e["tid"]): e["args"].get("name", "")
            for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"}


def spans(events: Iterable[dict], pid: int, thread: str,
          threads: Dict[Tuple[int, int], str]) -> List[dict]:
    """Complete (``ph == 'X'``) events of one named thread of one
    process, by start time."""
    out = [e for e in events
           if e.get("ph") == "X" and e.get("pid") == pid
           and threads.get((pid, e.get("tid"))) == thread]
    out.sort(key=lambda e: (e["ts"], -e.get("dur", 0.0)))
    return out


def interval(e: dict) -> Interval:
    return (e["ts"] * US, (e["ts"] + e.get("dur", 0.0)) * US)


# ---------------------------------------------------- interval algebra


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def total(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval of ``merged`` covers."""
    out, at = [], window[0]
    for a, b in clip(merged, window):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Seconds covered by both of two merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ------------------------------------------------------- device side


def opcode(e: dict) -> str:
    """The HLO opcode of an op span: ``hlo_category`` is a class
    ('convolution', 'data formatting', ...), not the opcode, so it is read
    from the HLO text in ``long_name`` (``%name = shape opcode(...)``) and,
    failing that, from the name with its number stripped."""
    long_name = e.get("args", {}).get("long_name", "")
    m = re.search(r"=\s+(?:\([^=]*?\)|\S+)\s+([a-z][a-z0-9-]*)\(", long_name)
    if m:
        return m.group(1)
    return re.sub(r"[.\d]+$", "", e.get("name", ""))


def collective_of(code: str) -> Optional[str]:
    """The collective an opcode belongs to (``all-reduce`` for
    ``all-reduce-start``), or None."""
    base = re.sub(r"-(start|done)$", "", code)
    return base if base in COLLECTIVE_OPCODES else None


def is_collective(e: dict) -> bool:
    return collective_of(opcode(e)) is not None


def self_times(op_spans: Sequence[dict]) -> Dict[str, float]:
    """Seconds by :func:`label` with nested spans subtracted from the
    span that holds them (a ``while`` holds its body's ops)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []          # (end, name)
    for e in op_spans:
        a, b = interval(e)
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(b, stack[-1][0]) - a
        name = label(e)
        out[name] += b - a
        stack.append((b, name))
    return dict(out)


PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def is_pallas(e: dict) -> bool:
    return PALLAS_TARGET in e.get("args", {}).get("long_name", "")


def pallas_seconds(op_spans: Sequence[dict], window: Interval
                   ) -> Dict[str, float]:
    """Device seconds inside ``window`` of the Pallas TPU kernels — the
    op spans whose HLO is a custom call to ``tpu_custom_call`` — split
    into the forward pass's and the backward pass's by jax's name stack
    (``transpose(...)`` marks the backward)."""
    out = {"fwd": 0.0, "bwd": 0.0, "calls": 0}
    for e in op_spans:
        if not is_pallas(e):
            continue
        sec = total(clip([interval(e)], window))
        side = "bwd" if "transpose(" in e["args"].get("tf_op", "") else "fwd"
        out[side] += sec
        out["calls"] += 1
    return out


def label(e: dict) -> str:
    """A readable, stable name for an op span: jax's name stack (module
    path and primitive) with the jit wrapper dropped and block numbers
    folded (``block_3`` -> ``block_*``), and the HLO category; for an op
    with no name stack, its name without its number."""
    args = e.get("args", {})
    stack = args.get("tf_op", "").rstrip(":")
    if not stack:
        return re.sub(r"[.\d]+$", "", e.get("name", ""))
    stack = re.sub(r"^jit\(\w+\)/", "", stack)
    stack = re.sub(r"_\d+(?=/|$)", "_*", stack)
    return f"{stack} [{args.get('hlo_category', '?')}]"


def collective_intervals(op_spans: Sequence[dict],
                         async_spans: Sequence[dict]) -> List[Interval]:
    """When a collective was running or in flight on one device: the
    spans of synchronous collective ops, the spans the runtime draws on
    the async thread, and for a ``-start``/``-done`` pair of op spans the
    whole stretch from the start's beginning to the done's end."""
    out = [interval(e) for e in async_spans if is_collective(e)]
    open_starts: Dict[str, List[float]] = defaultdict(list)
    for e in op_spans:
        code = opcode(e)
        base = collective_of(code)
        if base is None:
            continue
        a, b = interval(e)
        if code.endswith("-start"):
            open_starts[base].append(a)
        elif code.endswith("-done") and open_starts[base]:
            a = open_starts[base].pop(0)
        out.append((a, b))
    return union(out)


def compute_intervals(op_spans: Sequence[dict]) -> List[Interval]:
    """When the core ran an op that is no collective.  Spans that hold
    others (``while``, ``conditional``, ``call``) are left out: what runs
    inside them has spans of its own."""
    skip = {"while", "conditional", "call"}
    codes = ((e, opcode(e)) for e in op_spans)
    return union(interval(e) for e, code in codes
                 if code not in skip and collective_of(code) is None)


def step_modules(events: Sequence[dict], pid: int,
                 threads: Dict[Tuple[int, int], str]) -> List[dict]:
    """The executions of the step program on one device: of the module
    names on the ``XLA Modules`` thread, the one with the most device
    time."""
    mods = spans(events, pid, MODULES_THREAD, threads)
    by_name: Dict[str, float] = defaultdict(float)
    for e in mods:
        by_name[e["name"]] += e.get("dur", 0.0)
    if not by_name:
        return []
    top = max(by_name, key=by_name.get)
    return [e for e in mods if e["name"] == top]


# --------------------------------------------------------- host side


def host_offset(step_spans: Sequence[dict],
                host_step_ends: Sequence[float]) -> Optional[float]:
    """Seconds to take from a host-clock time to get trace time.  The
    host loop sees step ``k`` complete (its loss read returns) a moment
    after the device ends the ``k``-th step span of the trace, never
    before: the smallest difference over the steps is the offset, good to
    the latency of one read (about 0.1 ms)."""
    pairs = list(zip(host_step_ends, (interval(e)[1] for e in step_spans)))
    if not pairs:
        return None
    return min(h - d for h, d in pairs)


def attribute_gaps(idle: Sequence[Interval],
                   host: Dict[str, List[Interval]], other: str = "other"
                   ) -> List[Tuple[str, Interval]]:
    """Each idle gap of the device with the host span that covers most
    of it (``other`` where none does)."""
    out = []
    for gap in idle:
        best, best_s = other, 0.0
        for name, ivals in host.items():
            s = overlap([gap], ivals)
            if s > best_s:
                best, best_s = name, s
        out.append((best, gap))
    return out


# ------------------------------------------------------------ reduce


def reduce(events: Sequence[dict], *,
           host_spans: Optional[Dict[str, List[Interval]]] = None,
           host_step_ends: Sequence[float] = ()) -> Optional[dict]:
    """Everything the metric readers need from one trace, or None where
    the trace has no device process (the CPU platform).

    ``host_spans`` are the host loop's own spans by name and
    ``host_step_ends`` the times it saw each step complete, both on the
    host clock; they are moved onto the trace's clock by
    :func:`host_offset` and each idle gap of the device is named after
    the span that covers it.

    The window is device time from the start of the first whole step in
    the trace to the start of the last one, on each device by its own
    step spans: whole steps, each with the gap that follows it."""
    pids = device_pids(events)
    if not pids:
        return None
    threads = thread_names(events)
    devices = []
    for pid in sorted(pids, key=lambda p: pids[p]):
        steps = step_modules(events, pid, threads)
        if len(steps) < 2:
            continue
        window = (interval(steps[0])[0], interval(steps[-1])[0])
        ops = [e for e in spans(events, pid, OPS_THREAD, threads)
               if window[0] <= e["ts"] * US < window[1]]
        asyncs = [e for e in spans(events, pid, ASYNC_THREAD, threads)
                  if window[0] <= e["ts"] * US < window[1]]
        coll = clip(collective_intervals(ops, asyncs), window)
        comp = clip(compute_intervals(ops), window)
        busy = union(list(coll) + list(comp))
        offset = host_offset(steps, host_step_ends)
        host = {} if offset is None else {
            name: union((a - offset, b - offset) for a, b in ivals)
            for name, ivals in (host_spans or {}).items()}
        devices.append({
            "name": pids[pid],
            "steps": len(steps) - 1,
            "window_s": window[1] - window[0],
            "busy_s": total(busy),
            "compute_s": total(comp),
            "collective_s": total(coll),
            "collective_exposed_s": total(coll) - overlap(coll, comp),
            "pallas_s": pallas_seconds(ops, window),
            "op_self_s": self_times(ops),
            "idle_gaps": attribute_gaps(gaps(busy, window), host),
            "module": steps[0]["name"],
        })
    if not devices:
        return None
    return {"devices": devices}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: device 0's ops by self time and its
    idle time by what the host was doing, largest first."""
    dev = reduced["devices"][0]
    ops = sorted(dev["op_self_s"].items(), key=lambda kv: -kv[1])[:top]
    by_host: Dict[str, float] = defaultdict(float)
    for name, (a, b) in dev["idle_gaps"]:
        by_host[name] += b - a
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
