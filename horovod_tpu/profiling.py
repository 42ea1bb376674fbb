"""Device-side profiling utilities: honest kernel timing and per-op
roofline attribution on TPU.

Two measurement traps motivated this module:

* **wall clock includes the host** — dispatch latency dominates small
  programs; the device-side trace span is the kernel's own time
  (:func:`device_time_ms`);
* **aggregate counters hide the roofline** — XLA's per-op trace spans
  carry ``model_flops`` and ``bytes_accessed``, which places every
  fusion against the MXU and HBM peaks (:func:`per_op_rooflines`).

The reader takes the Chrome trace (``*.trace.json.gz``) the installed
profiler writes next to its ``.xplane.pb``: on a TPU v5e under jax 0.9.0
it carries a ``/device:TPU:N`` process with "XLA Modules" and "XLA Ops"
threads, and per-op ``model_flops`` / ``bytes_accessed`` that
``jax.profiler.ProfileData`` does not expose
(``tests/data/tpu_v5e_matmul.trace.json.gz`` is one such recording).

One trap is left to the reader of two traces: two clocks.
:func:`capture` therefore writes, beside the profiler's file, ONE Chrome
trace (:data:`ONE_FILE`) with the device's processes as they are and the
span ring's spans of the capture (:mod:`horovod_tpu.timeline`:
``step/dispatch``, ``step/enqueue``, ``loader/*``, ``profile/run``) as one
more process, moved onto the device's clock (:func:`host_shift`).

No reference analogue (its profiling story is the Horovod timeline,
which this framework also implements in :mod:`horovod_tpu.timeline`);
this module covers the *device* side that SURVEY §5.5 leaves to
external tooling.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics
import tempfile
import time
from collections import defaultdict
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

from horovod_tpu import timeline as _timeline


class DevicePeaks(NamedTuple):
    """Published per-chip peaks of one accelerator generation."""
    bf16_flops: float            # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float       # bytes/s


# The one table of peaks in the tree, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, system-architecture pages
# "TPU v4", "TPU v5e", "TPU v5p" and "TPU v6e" (per-chip figures).  Only
# "TPU v5 lite" has been read off a chip by this repo; the other keys are
# jax's names for those generations.  A device that is not listed is an
# error, never a default: a utilization computed against another chip's
# peak is wrong without looking wrong.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1228e9),
    "TPU v5 lite": DevicePeaks(197e12, 819e9),      # v5e
    "TPU v5": DevicePeaks(459e12, 2765e9),          # v5p
    "TPU v6 lite": DevicePeaks(918e12, 1640e9),     # v6e
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks for ``device_kind``; raises for a kind not in the table."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}.  Add the chip to "
            "horovod_tpu.profiling.DEVICE_PEAKS with its source.") from None


def _newest(log_dir: str, name: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "plugins/profile/*", name))
    return max(paths, key=os.path.getmtime) if paths else None


def _latest_trace_file(log_dir: str) -> Optional[str]:
    return _newest(log_dir, "*.trace.json.gz")


def _load_trace(path: str) -> dict:
    with gzip.open(path) as fh:
        return json.load(fh)


def load_trace_events(log_dir: str) -> List[dict]:
    """Raw Chrome-trace events from the newest trace under ``log_dir``
    (as written by ``jax.profiler.trace``)."""
    path = _latest_trace_file(log_dir)
    return _load_trace(path).get("traceEvents", []) if path else []


def _device_pids(events) -> set:
    pids = {e["pid"]: e["args"].get("name", "") for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    # '/device:TPU:0' etc.; the python host shows as '/host:CPU'.  The
    # CPU platform emits NO device process at all (host-only trace) -- or,
    # once libtpu is loaded in the process (a compile for a described TPU),
    # an empty device plane: a device process is one that ran something.
    ran = {e["pid"] for e in events if e.get("ph") == "X"}
    return {p for p, n in pids.items()
            if n.startswith("/device:") and "CPU" not in n and p in ran}


def _thread_names(events) -> Dict[tuple, str]:
    return {(e["pid"], e["tid"]): e["args"].get("name", "")
            for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"}


#: The file :func:`capture` writes beside the profiler's ``*.trace.json.gz``:
#: load it in Perfetto (``ui.perfetto.dev``) or ``chrome://tracing``.
ONE_FILE = "device_and_host.json.gz"


def one_file(log_dir: str) -> Optional[str]:
    """The newest :data:`ONE_FILE` under ``log_dir``, or None."""
    return _newest(log_dir, ONE_FILE)


def _device_ends_us(events, iters: int) -> Optional[List[float]]:
    """When the device ended each of ``iters`` traced calls, on the
    trace's clock: the programs a device ran (its ``XLA Modules`` spans)
    fall, in order, into as many equal groups as there were calls, and a
    call's end is its group's latest end over all devices.  None where
    some device's programs do not divide among the calls (a ``run`` that
    launches now one program, now two), or where no device ran any."""
    devices, threads = _device_pids(events), _thread_names(events)
    modules = defaultdict(list)
    for e in events:
        pid = e.get("pid")
        if (e.get("ph") == "X" and pid in devices
                and threads.get((pid, e.get("tid"))) == "XLA Modules"):
            modules[pid].append((e["ts"], e["ts"] + e.get("dur", 0.0)))
    ends: List[float] = []
    for spans in modules.values():
        each, left = divmod(len(spans), iters)
        if not each or left:
            return None
        spans.sort()
        last = [max(end for _, end in spans[i * each:(i + 1) * each])
                for i in range(iters)]
        ends = [max(pair) for pair in zip(ends, last)] if ends else last
    return ends or None


def host_shift(host_ends_ns: Sequence[int],
               device_ends_us: Sequence[float]) -> Tuple[int, List[float]]:
    """``(shift_ns, waits_us)``: what to take from a
    ``time.perf_counter_ns()`` reading to get the trace's time, from the
    moments the host saw each traced call complete and the moments the
    device ended it on the trace's clock.  The host cannot have seen a
    call complete before the device ended it, so the smallest
    ``host end - device end`` over the calls is the shift: late by the
    latency of the quickest read (about 0.1 ms), never early.
    ``waits_us`` is how much longer than that each call's read took."""
    gaps = [h - round(d * 1e3) for h, d in zip(host_ends_ns, device_ends_us)]
    shift = min(gaps)
    return shift, [(g - shift) / 1e3 for g in gaps]


def _write_one_file(trace_path: str, calls, opened_ns: int) -> dict:
    """Write :data:`ONE_FILE` beside ``trace_path`` and return what it
    records of itself (``metadata["horovod_tpu"]``).  ``calls`` are the
    ``profile/run`` spans and ``opened_ns`` the ring's clock just before
    the profiler's session opened, which is the trace's zero to within
    the session's start: the shift where there is no device to go by."""
    trace = _load_trace(trace_path)
    events = trace.get("traceEvents", [])
    device_ends = _device_ends_us(events, len(calls))
    note = {"device_processes": len(_device_pids(events)),
            "session_opened_shift_ns": opened_ns}
    if device_ends is None:
        why = ("a device's programs do not divide among the traced calls"
               if note["device_processes"] else
               "no device process in the trace (the CPU platform): host "
               "spans alone")
        note.update(shift_ns=opened_ns, shift_from="session_opened", why=why)
    else:
        shift, waits = host_shift([c.end_ns for c in calls], device_ends)
        note.update(shift_ns=shift, shift_from="device_ends",
                    residual_us=statistics.median(waits),
                    host_after_device_us=waits)
    pid = 1 + max((e.get("pid", 0) for e in events), default=0)
    host = [e for e in _timeline.ring.events(note["shift_ns"], pid)
            if e["ph"] != "X" or e["ts"] + e["dur"] >= 0.0]
    trace["traceEvents"] = events + host
    trace.setdefault("metadata", {})["horovod_tpu"] = note
    with gzip.open(os.path.join(os.path.dirname(trace_path), ONE_FILE),
                   "wt") as fh:
        json.dump(trace, fh)
    return note


def capture(run: Callable[[], None], *, warmup: int = 1,
            iters: int = 2, log_dir: Optional[str] = None) -> str:
    """Run ``run()`` under ``jax.profiler.trace`` (after ``warmup``
    untraced calls) and return the trace directory.  ``run`` must end in
    ``block_until_ready``: the trace closes when it returns, and the end
    of each call is what sets the host's spans on the device's clock.
    Each traced call is a ``profile/run`` span of the ring (key = the
    iteration); when the trace closes, :data:`ONE_FILE` is written beside
    the profiler's file (:func:`one_file` finds it): every process of the
    profiler's trace as it is, and ``host (horovod_tpu ring)`` with the
    ring's spans since the session opened, shifted by
    :func:`host_shift`.  Its ``metadata["horovod_tpu"]`` records the
    shift, where it came from, and ``residual_us``: the median over the
    calls of how much longer than the quickest the host's read took;
    reading the profiler's file, merging and writing is the ring's span
    ``profile/one_file``.
    (The profiler's own ``/host:CPU`` process is on the host's clock as
    the profiler read it, which the device's processes are not: 1.4 ms
    apart in ``tests/data/tpu_v5e_matmul.trace.json.gz``.)

    On an accelerator the trace holds device spans or this raises; the CPU
    platform has no device process, and there the one file holds the host
    process alone, on the session's clock, and says so."""
    import jax

    for _ in range(warmup):
        run()
    log_dir = log_dir or tempfile.mkdtemp(prefix="htpu_profile")
    opened_ns = time.perf_counter_ns()
    calls = []
    with jax.profiler.trace(log_dir):
        for i in range(iters):
            with _timeline.ring.span("profile/run", key=i) as call:
                run()
            calls.append(call)
    path = _latest_trace_file(log_dir)
    note = None
    if path:
        with _timeline.ring.span("profile/one_file"):
            note = _write_one_file(path, calls, opened_ns)
    if jax.default_backend() != "cpu" and not (
            note and note["device_processes"]):
        raise RuntimeError(
            f"profiler trace under {log_dir} holds no device process on "
            f"platform {jax.default_backend()!r}: "
            f"{path or 'no *.trace.json.gz written'}")
    return log_dir


def device_time_ms(log_dir: str, *, per: int = 1) -> Optional[float]:
    """Longest device-side XLA-module span in the trace, in ms / ``per``
    — the execution time of the dominant program without the host's
    dispatch.  None when the trace has no device spans (the CPU
    platform)."""
    events = load_trace_events(log_dir)
    dev = _device_pids(events)
    if not dev:
        return None
    best = 0.0
    for e in events:
        if (e.get("ph") == "X" and e.get("pid") in dev
                and e.get("name", "").startswith("jit_")):
            best = max(best, e.get("dur", 0.0))
    return best / 1e3 / per if best else None


def per_op_rooflines(log_dir: str, peaks: DevicePeaks) -> List[dict]:
    """Per-op roofline table from a captured trace: ops on the device's
    'XLA Ops' thread aggregated by (name stem, source line), each with
    total ms, achieved FLOP/s and bytes/s, and their fractions of
    ``peaks`` (from :func:`device_peaks`).  Sorted by time, descending."""
    peak_flops, peak_bytes = peaks.bf16_flops, peaks.hbm_bytes_per_s
    events = load_trace_events(log_dir)
    dev = _device_pids(events)
    tids = _thread_names(events)
    agg = defaultdict(lambda: [0.0, 0.0, 0.0, 0])
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev:
            continue
        if tids.get((e["pid"], e["tid"])) != "XLA Ops":
            continue
        a = e.get("args", {})
        stem = re.sub(r"\.\d+(\.remat)?$", r"\1", e.get("name", ""))
        src = re.sub(r".*/(site-packages|repo)/", "",
                     a.get("source", "?"))
        key = (stem, src)
        agg[key][0] += e.get("dur", 0.0)           # us
        agg[key][1] += float(a.get("model_flops", 0) or 0)
        agg[key][2] += float(a.get("bytes_accessed", 0) or 0)
        agg[key][3] += 1
    rows = []
    for (stem, src), (dur, fl, by, n) in sorted(
            agg.items(), key=lambda kv: -kv[1][0]):
        sec = dur * 1e-6
        rows.append({
            "op": stem, "source": src, "count": n,
            "ms": round(dur / 1e3, 3),
            "tflops_per_sec": round(fl / sec / 1e12, 2) if sec else 0.0,
            "pct_of_peak_flops": round(100 * fl / sec / peak_flops, 1)
            if sec else 0.0,
            "gbytes_per_sec": round(by / sec / 1e9, 1) if sec else 0.0,
            "pct_of_peak_bw": round(100 * by / sec / peak_bytes, 1)
            if sec else 0.0,
        })
    return rows


def print_rooflines(rows: List[dict], top: int = 30) -> None:
    print(f"{'ms':>9} {'n':>5} {'TF/s':>7} {'%MXU':>5} {'GB/s':>7} "
          f"{'%HBM':>5}  op @ source")
    for r in rows[:top]:
        print(f"{r['ms']:9.3f} {r['count']:5d} "
              f"{r['tflops_per_sec']:7.1f} {r['pct_of_peak_flops']:5.1f} "
              f"{r['gbytes_per_sec']:7.1f} {r['pct_of_peak_bw']:5.1f}  "
              f"{r['op']} @ {r['source']}")
