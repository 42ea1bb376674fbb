"""Trace-parsing tests for horovod_tpu.profiling: against a fabricated
Chrome trace, and against one recorded on a TPU v5e (the CPU platform
emits no device spans)."""

import gzip
import json
import os
import shutil

import pytest

from horovod_tpu import profiling

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_matmul.trace.json.gz")


def write_trace(tmp_path, events):
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "vm.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    return str(tmp_path)


def make_events():
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
    ]
    spans = [
        # Module span: 10 ms over 2 reps.
        {"ph": "X", "pid": 3, "tid": 2, "name": "jit_step(123)",
         "dur": 10_000.0, "ts": 0},
        # Two instances of one fusion: 1e9 flops, 1e6 bytes in 1 ms each.
        {"ph": "X", "pid": 3, "tid": 3, "name": "multiply_add_fusion.7",
         "dur": 1_000.0, "ts": 0,
         "args": {"model_flops": "1000000000", "bytes_accessed": "1000000",
                  "source": "/x/site-packages/flax/linear.py:1"}},
        {"ph": "X", "pid": 3, "tid": 3, "name": "multiply_add_fusion.9",
         "dur": 1_000.0, "ts": 2,
         "args": {"model_flops": "1000000000", "bytes_accessed": "1000000",
                  "source": "/x/site-packages/flax/linear.py:1"}},
        # A host span that must be ignored.
        {"ph": "X", "pid": 701, "tid": 1, "name": "jit_step(123)",
         "dur": 99_000.0, "ts": 0},
    ]
    return meta + spans


def test_device_time_ms(tmp_path):
    d = write_trace(tmp_path, make_events())
    assert profiling.device_time_ms(d, per=2) == 5.0


def test_device_time_none_without_device(tmp_path):
    evts = [e for e in make_events() if e.get("pid") != 3]
    d = write_trace(tmp_path, evts)
    assert profiling.device_time_ms(d) is None


def test_per_op_rooflines(tmp_path):
    d = write_trace(tmp_path, make_events())
    rows = profiling.per_op_rooflines(d, profiling.DevicePeaks(2e12, 1e9))
    assert len(rows) == 1
    r = rows[0]
    # .N suffix stripped, both instances aggregated.
    assert r["op"] == "multiply_add_fusion"
    assert r["count"] == 2
    assert r["ms"] == 2.0
    # 2e9 flops / 2e-3 s = 1e12 FLOP/s = 50% of the 2e12 peak.
    assert r["tflops_per_sec"] == 1.0
    assert r["pct_of_peak_flops"] == 50.0
    # 2e6 bytes / 2e-3 s = 1e9 B/s = 100% of peak bw.
    assert r["pct_of_peak_bw"] == 100.0
    assert r["source"] == "flax/linear.py:1"


def test_capture_returns_dir(tmp_path, monkeypatch):
    import tempfile

    import jax.numpy as jnp

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    log_dir = profiling.capture(
        lambda: jnp.ones((8,)).sum().block_until_ready(), iters=1)
    assert os.path.isdir(log_dir)
    # CPU platform: no device process, so no rows and no device time.
    assert profiling.per_op_rooflines(
        log_dir, profiling.DevicePeaks(1.0, 1.0)) == []
    assert profiling.device_time_ms(log_dir) is None


def test_capture_raises_without_device_spans_off_cpu(monkeypatch, tmp_path):
    """On an accelerator a trace with no device process is a failure of
    the measurement, not an empty result."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="no device process"):
        profiling.capture(
            lambda: jnp.ones((8,)).sum().block_until_ready(),
            warmup=0, iters=1, log_dir=str(tmp_path))


def test_empty_device_plane_is_no_device_process():
    """A process with libtpu loaded (it compiled for a described TPU) names
    a device plane in its CPU traces and runs nothing on it: only a plane
    with a complete event counts as a device process."""
    def named(pid, name):
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}

    events = [named(1, "/host:CPU"), named(2, "/device:TPU:0"),
              named(3, "/device:TPU:1"),
              {"ph": "X", "pid": 1, "tid": 1, "name": "host", "dur": 1.0},
              {"ph": "X", "pid": 3, "tid": 1, "name": "jit_f", "dur": 1.0}]
    assert profiling._device_pids(events) == {3}
    assert profiling._device_pids(events[:4]) == set()


def test_recorded_v5e_trace(tmp_path):
    """A trace recorded on the chip (jax 0.9.0, one TPU v5e, three calls
    of a jitted 4096^3 bf16 matmul-and-sum): the module span is 0.7045 ms
    and the matmul fusion runs at 99% of the published bf16 peak."""
    d = tmp_path / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d)
    assert profiling.device_time_ms(str(tmp_path)) == pytest.approx(
        0.70454, abs=1e-4)
    rows = profiling.per_op_rooflines(
        str(tmp_path), profiling.device_peaks("TPU v5 lite"))
    top = rows[0]
    assert top["op"] == "convolution_reduce_fusion"
    assert top["count"] == 3
    assert top["ms"] == pytest.approx(2.114, abs=1e-3)
    assert top["pct_of_peak_flops"] == 99.0


def test_device_peaks_table():
    v5e = profiling.device_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(LookupError, match="no published peaks"):
        profiling.device_peaks("TPU v9 imaginary")


# ------------------------------------------- one file, one clock


def test_host_shift_is_the_smallest_host_end_less_device_end():
    """The host cannot see a call complete before the device ended it:
    the quickest read sets the shift, the others read as waits."""
    shift_ns = 7_000_000_000
    device_ends_us = [1_000.0, 2_500.0, 4_000.0]
    read_latency_us = [180.0, 95.0, 240.0]
    host_ends_ns = [shift_ns + round((d + lat) * 1e3)
                    for d, lat in zip(device_ends_us, read_latency_us)]
    got, waits = profiling.host_shift(host_ends_ns, device_ends_us)
    assert got == shift_ns + 95_000
    assert waits == pytest.approx([85.0, 0.0, 145.0])
    # On the shifted clock no call ends before its device end.
    assert all((h - got) / 1e3 >= d
               for h, d in zip(host_ends_ns, device_ends_us))


def recorded_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d)
    return str(d / os.path.basename(RECORDED))


# The recorded trace's three executions of the matmul, us on its clock.
MODULES_US = [(43517.32425, 704.53625), (45092.001672, 704.5375),
              (46549.726672, 704.53875)]


def synthetic_ring(monkeypatch, shift_ns, read_latency_us):
    """A ring of its own with what three traced calls of a step would
    leave, on a host clock ``shift_ns`` ahead of the trace's: a
    ``profile/run`` a call that ends ``read_latency_us`` after the device
    did, a dispatch inside it, and a loader span from before the session."""
    from horovod_tpu import timeline

    ring = timeline.SpanRing()
    monkeypatch.setattr(timeline, "ring", ring)

    def at(us):
        return shift_ns + round(us * 1e3)

    ring.add("loader/stage", at(-900.0), at(-100.0))
    calls = []
    for i, ((start, dur), lat) in enumerate(zip(MODULES_US,
                                                read_latency_us)):
        with ring.span("profile/run", key=i) as call:
            with ring.span("step/dispatch"):
                with ring.span("step/enqueue"):
                    pass
        call.start_ns, call.end_ns = at(start - 150.0), at(start + dur + lat)
        calls.append(call)
    # The kept records carry the clock reads of the with blocks: set the
    # calls' on the synthetic clock, and the dispatches' inside them.
    for record in ring._spans:
        if record[2] == "profile/run":
            record[4], record[5] = (calls[record[6]].start_ns,
                                    calls[record[6]].end_ns)
        elif record[2].startswith("step/"):
            record[4] = calls[record[6]].start_ns + 10_000
            record[5] = record[4] + 60_000
    return calls


def read_one_file(trace_path):
    path = os.path.join(os.path.dirname(trace_path), profiling.ONE_FILE)
    with gzip.open(path) as fh:
        return json.load(fh)


def test_one_file_lays_the_ring_on_the_recorded_trace_s_clock(
        tmp_path, monkeypatch):
    shift_ns = 5_000_000_000_123
    calls = synthetic_ring(monkeypatch, shift_ns, [180.0, 95.0, 240.0])
    trace_path = recorded_dir(tmp_path)
    note = profiling._write_one_file(trace_path, calls,
                                     opened_ns=shift_ns - 40_000)
    one = read_one_file(trace_path)
    assert profiling.one_file(str(tmp_path)) == os.path.join(
        os.path.dirname(trace_path), profiling.ONE_FILE)
    # The shift and the residual are on the file.
    assert one["metadata"]["horovod_tpu"] == note
    assert note["shift_from"] == "device_ends"
    assert note["shift_ns"] == pytest.approx(shift_ns + 95_000, abs=2)
    assert note["host_after_device_us"] == pytest.approx(
        [85.0, 0.0, 145.0], abs=1e-2)
    assert note["residual_us"] == pytest.approx(85.0, abs=1e-2)
    assert note["device_processes"] == 1
    # The profiler's events stand as they stood.
    with gzip.open(trace_path) as fh:
        recorded = json.load(fh)["traceEvents"]
    events = one["traceEvents"]
    assert events[:len(recorded)] == recorded
    # One more process, clear of the profiler's, with the ring's name.
    added = events[len(recorded):]
    (pid,) = {e["pid"] for e in added}
    assert pid not in {e.get("pid") for e in recorded}
    assert [e["args"]["name"] for e in added
            if e["name"] == "process_name"] == ["host (horovod_tpu ring)"]
    spans = [e for e in added if e["ph"] == "X"]
    # No call ends before the device program it waited on; the quickest
    # read ends with it.
    runs = sorted((e for e in spans if e["name"] == "profile/run"),
                  key=lambda e: e["args"]["key"])
    lead = [e["ts"] + e["dur"] - (start + dur)
            for e, (start, dur) in zip(runs, MODULES_US)]
    assert all(x >= -1e-3 for x in lead) and min(lead) == pytest.approx(
        0.0, abs=1e-2)
    # Each dispatch lies before its device program on the one axis.
    for e in spans:
        if e["name"] == "step/enqueue":
            start, _ = MODULES_US[e["args"]["key"]]
            assert e["ts"] + e["dur"] < start
    # What ended before the session opened is not of the capture.
    assert {e["name"] for e in spans} == {"profile/run", "step/dispatch",
                                          "step/enqueue"}


def test_programs_that_do_not_divide_among_the_calls_leave_the_session_s_shift(
        tmp_path, monkeypatch):
    calls = synthetic_ring(monkeypatch, 1_000_000, [100.0, 100.0, 100.0])
    note = profiling._write_one_file(recorded_dir(tmp_path), calls[:2],
                                     opened_ns=1_000_000 - 40_000)
    assert note["shift_from"] == "session_opened"
    assert note["shift_ns"] == 1_000_000 - 40_000
    assert "do not divide" in note["why"] and "residual_us" not in note


def test_a_call_s_device_end_is_the_latest_over_the_devices():
    def process(pid, n):
        return [{"ph": "M", "pid": pid, "name": "process_name",
                 "args": {"name": f"/device:TPU:{n}"}},
                {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
                 "args": {"name": "XLA Modules"}},
                {"ph": "M", "pid": pid, "tid": 3, "name": "thread_name",
                 "args": {"name": "XLA Ops"}}]

    def module(pid, ts, dur, tid=2):
        return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
                "name": "jit_step(1)"}

    events = (process(3, 0) + process(4, 1)
              # Two programs a call on each device; an op span is none.
              + [module(3, 0.0, 10.0), module(3, 10.0, 5.0),
                 module(3, 100.0, 10.0), module(3, 110.0, 5.0),
                 module(3, 0.0, 500.0, tid=3)]
              + [module(4, 0.0, 10.0), module(4, 10.0, 9.0),
                 module(4, 100.0, 10.0), module(4, 110.0, 2.0)])
    assert profiling._device_ends_us(events, 2) == [19.0, 115.0]
    assert profiling._device_ends_us(events, 3) is None
    assert profiling._device_ends_us(events[:3], 2) is None


def test_capture_on_the_cpu_writes_the_host_process_alone_and_says_so(
        tmp_path):
    import jax.numpy as jnp

    from horovod_tpu import timeline

    log_dir = profiling.capture(
        lambda: jnp.ones((8,)).sum().block_until_ready(),
        iters=2, log_dir=str(tmp_path))
    with gzip.open(profiling.one_file(log_dir)) as fh:
        one = json.load(fh)
    note = one["metadata"]["horovod_tpu"]
    assert note["device_processes"] == 0
    assert note["shift_from"] == "session_opened"
    assert "host spans alone" in note["why"]
    assert note["shift_ns"] == note["session_opened_shift_ns"]
    host = [e for e in one["traceEvents"]
            if e.get("name") == "process_name"
            and e["args"]["name"] == timeline.SpanRing.PROCESS_NAME]
    assert len(host) == 1
    runs = [e for e in one["traceEvents"] if e.get("ph") == "X"
            and e["pid"] == host[0]["pid"] and e["name"] == "profile/run"]
    assert sorted(e["args"]["key"] for e in runs) == [0, 1]
    # On the session's clock: after its opening, in the order they ran.
    assert 0.0 <= runs[0]["ts"] < runs[1]["ts"]
    # The ring keeps them too, as any span.
    kept = [s for s in timeline.ring.snapshot() if s.name == "profile/run"]
    assert [s.key for s in kept[-2:]] == [0, 1]
