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
import tempfile
from collections import defaultdict
from typing import Callable, Dict, List, NamedTuple, Optional


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


def _latest_trace_file(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(
        log_dir, "plugins/profile/*/*.trace.json.gz"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_trace_events(log_dir: str) -> List[dict]:
    """Raw Chrome-trace events from the newest trace under ``log_dir``
    (as written by ``jax.profiler.trace``)."""
    path = _latest_trace_file(log_dir)
    if path is None:
        return []
    with gzip.open(path) as fh:
        return json.load(fh).get("traceEvents", [])


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


def capture(run: Callable[[], None], *, warmup: int = 1,
            iters: int = 2, log_dir: Optional[str] = None) -> str:
    """Run ``run()`` under ``jax.profiler.trace`` (after ``warmup``
    untraced calls) and return the trace directory.  ``run`` must end in
    ``block_until_ready``: the trace closes when it returns.  On an
    accelerator the trace holds device spans or this raises; the CPU
    platform has no device process, and there the directory comes back
    with host spans only."""
    import jax

    for _ in range(warmup):
        run()
    log_dir = log_dir or tempfile.mkdtemp(prefix="htpu_profile")
    with jax.profiler.trace(log_dir):
        for _ in range(iters):
            run()
    if (jax.default_backend() != "cpu"
            and not _device_pids(load_trace_events(log_dir))):
        raise RuntimeError(
            f"profiler trace under {log_dir} holds no device process on "
            f"platform {jax.default_backend()!r}: "
            f"{_latest_trace_file(log_dir) or 'no *.trace.json.gz written'}")
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
