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
