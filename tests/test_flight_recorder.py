"""Abort-time flight recorder (PR: observability).

Fast tests drive the native ring through the ctypes bindings: wrap /
eviction accounting, snapshot JSON shape, detail sanitizing, and dump
files.  The slow test launches a real 2-process group with
``HOROVOD_TPU_FAULT=hang`` and asserts EVERY rank — including the hung
one, poked with SIGUSR2 — leaves a parseable dump naming the stalled
tensor and tick, and that the survivor's abort error names its dump path.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import pytest

from horovod_tpu import cpp_core

pytestmark = pytest.mark.skipif(
    not cpp_core.available(), reason="native core not built")


def snapshot(why="test"):
    text = cpp_core.flight_snapshot(why)
    assert text, "flight snapshot unavailable"
    return json.loads(text)


class TestRing:
    def test_record_and_snapshot_shape(self):
        cpp_core.flight_set_capacity(64)
        cpp_core.flight_set_rank(5)
        cpp_core.flight_record("unit.shape", "hello", 123, 4, 7)
        snap = snapshot("shape")
        assert snap["rank"] == 5
        assert snap["why"] == "shape"
        assert snap["capacity"] == 64
        ev = snap["events"][-1]
        assert ev["kind"] == "unit.shape"
        assert ev["detail"] == "hello"
        assert (ev["bytes"], ev["a"], ev["b"]) == (123, 4, 7)
        assert ev["ts_us"] > 0

    def test_wrap_evicts_oldest(self):
        # SetCapacity clears the ring, so counts below are exact.
        cpp_core.flight_set_capacity(8)
        for i in range(20):
            cpp_core.flight_record("unit.wrap", f"ev{i}", i)
        snap = snapshot("wrap")
        assert snap["capacity"] == 8
        assert snap["recorded"] == 20
        assert snap["dropped"] == 12
        assert len(snap["events"]) == 8
        # Oldest-first, and exactly the last 8 survive.
        assert [e["detail"] for e in snap["events"]] == \
            [f"ev{i}" for i in range(12, 20)]

    def test_detail_sanitized_for_json(self):
        # Quotes, backslashes, control bytes, non-ASCII: all must be
        # defanged at record time so even the lock-free signal dump can
        # quote fields verbatim.
        cpp_core.flight_set_capacity(8)
        cpp_core.flight_record("unit.dirty", 'a"b\\c\nd\x01é')
        snap = snapshot("dirty")   # json.loads above IS the assertion
        detail = snap["events"][-1]["detail"]
        assert detail.startswith("a.b.c.d.")

    def test_long_fields_truncated_not_overflowed(self):
        cpp_core.flight_set_capacity(8)
        cpp_core.flight_record("k" * 300, "d" * 500)
        ev = snapshot("long")["events"][-1]
        assert len(ev["kind"]) <= 15      # char kind[16], NUL-terminated
        assert len(ev["detail"]) <= 95    # char detail[96]

    def test_dump_writes_parseable_file(self):
        cpp_core.flight_set_capacity(8)
        cpp_core.flight_set_rank(0)
        cpp_core.flight_record("unit.dump", "to disk")
        path = cpp_core.flight_dump("unit")
        assert path and os.path.exists(path)
        with open(path) as f:
            dump = json.load(f)
        # The recorder fixed its directory ($TMPDIR) when this process
        # first used it, so the dump cannot go under tmp_path: take it away.
        os.remove(path)
        assert dump["why"] == "unit"
        assert any(e["kind"] == "unit.dump" for e in dump["events"])


# ------------------------------------------------------- slow multi-process

HANG_WORKER = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    t0 = time.monotonic()
    i = 0
    try:
        while time.monotonic() - t0 < 90:
            hvd.allreduce(np.ones(8, np.float32), name=f"fl.{i}")
            i += 1
        print(f"NO_ABORT rank={rank}", flush=True)
        sys.exit(5)
    except hvd.HorovodAbortedError as e:
        print(f"ABORTED rank={rank} msg={e}", flush=True)
        sys.exit(3)
""")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_hang_fault_dumps_on_every_rank(tmp_path):
    """2-proc job, rank 1 hangs at tick 5: the surviving rank's abort
    must carry its flight dump; the HUNG rank must still produce one via
    SIGUSR2 (the path run.py pokes before terminating survivors).  Both
    dumps must parse and name the stalled tensor and the tick."""
    port = free_port()
    procs = []
    for i in range(2):
        env = dict(os.environ)
        env.update({
            "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_TPU_PROCESS_INDEX": str(i),
            "HOROVOD_TPU_PROCESS_COUNT": "2",
            "HOROVOD_TPU_SIZE": "2",
            "HOROVOD_TPU_RANK": str(i),
            "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
            "HOROVOD_TPU_CYCLE_TIME_MS": "2",
            "HOROVOD_TPU_HEARTBEAT_S": "2",
            "HOROVOD_TPU_FAULT": "hang:rank=1:tick=5",
            "HOROVOD_TPU_FLIGHT_RECORDER_DIR": str(tmp_path),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        env.pop("HOROVOD_TPU_TIMELINE", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", HANG_WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    # Rank 0 (the coordinator) detects the missed heartbeat and aborts.
    out0, _ = procs[0].communicate(timeout=120)
    assert procs[0].returncode == 3, out0
    assert "ABORTED" in out0 and "rank 1" in out0, out0
    assert "flight recorder:" in out0, out0

    # Rank 1 is wedged inside the injected hang: only the async-signal
    # dump can save its ring.  Poke it the way run.py's _reap does.
    procs[1].send_signal(signal.SIGUSR2)
    rank1_dump = tmp_path / "htpu_flight.rank1.json"
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not rank1_dump.exists():
        time.sleep(0.1)
    procs[1].kill()
    procs[1].communicate()

    for rank in (0, 1):
        path = tmp_path / f"htpu_flight.rank{rank}.json"
        assert path.exists(), f"no dump for rank {rank}"
        with open(path) as f:
            dump = json.load(f)
        assert dump["rank"] == rank
        assert dump["events"], dump
        details = " ".join(e["kind"] + " " + e["detail"]
                           for e in dump["events"])
        # Names the in-flight tensors ("fl.<i>" via negotiate.pending on
        # the worker / response.ready on the coordinator)...
        assert "fl." in details, details
        # ...and the tick: the header tick is the last one entered, and
        # every event is tick-stamped.
        assert dump["tick"] >= 1
        assert any(e["tick"] >= 1 for e in dump["events"])
    # The hung rank's dump came from the signal path and shows the
    # injected fault itself.
    with open(rank1_dump) as f:
        d1 = json.load(f)
    assert d1["why"] == "sigusr2"
    assert any(e["kind"] == "fault.hang" for e in d1["events"]), d1

    # The survivor's abort message points at a dump that really exists.
    dump_path = out0.split("flight recorder: ")[1].split("]")[0]
    assert os.path.exists(dump_path), dump_path
