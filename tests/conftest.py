"""Test fixture: 8 virtual CPU devices stand in for an 8-chip TPU slice.

The reference runs every test both single-process and under ``mpirun -np 2``
(SURVEY §4).  The TPU-native equivalent: force the host platform to expose 8
XLA CPU devices so the rank mesh, shardings, and collectives execute exactly
as they would across chips; separate multi-process tests (test_multiprocess*)
launch real extra processes over the distributed control plane.

Must run before jax is imported anywhere.
"""

import os
import sys

# Escape hatch for hardware tests: with HOROVOD_TPU_TEST_REAL_TPU=1 AND an
# explicit test_flash_tpu.py target on the command line, the run uses
# whatever platform JAX resolves (a real TPU chip) instead of the virtual
# CPU mesh.  The argv guard keeps an exported var from silently changing
# the device topology of the full suite, whose tests assume the 8-device
# virtual slice.
_REAL_TPU = (os.environ.get("HOROVOD_TPU_TEST_REAL_TPU") == "1"
             and any("test_flash_tpu" in a for a in sys.argv))

if not _REAL_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import faulthandler  # noqa: E402
import hashlib  # noqa: E402
import signal  # noqa: E402

import pytest  # noqa: E402

# The one time limit of every test (set-up, call and tear-down together).
# Under the driver's command (six workers on the sandbox's 8 cores) every
# tier-1 test but three ends within 40 s; those three, and any test that
# needs more, say so with @pytest.mark.time_limit(seconds, "why").
TEST_LIMIT_S = 300
# How long after the limit a main thread stuck in native code, where no
# Python signal handler can run, is given before its process is ended.
NATIVE_GRACE_S = 30


@pytest.fixture(autouse=True)
def _time_limit(request, tmp_path_factory):
    """Fail a test that passes its limit, with every thread's stack.

    Two stages.  SIGALRM in the main thread raises the failure wherever
    Python code runs or waits.  For a main thread that never comes back from
    native code, ``faulthandler`` writes the stacks and ends the process
    ``NATIVE_GRACE_S`` later.  A process that ends under a test (this, or
    XLA aborting on a collective that lost a participant) makes xdist hand
    the test to the next worker, again and again: the note each test keeps
    in the run's directory while it runs turns the second try into a
    failure that carries what the first left, so a crash costs one test.
    """
    nodeid = request.node.nodeid
    marker = request.node.get_closest_marker("time_limit")
    limit = marker.args[0] if marker else TEST_LIMIT_S
    # The directory this run's processes share: the controller's base temp,
    # which holds each xdist worker's own.
    base = tmp_path_factory.getbasetemp()
    shared = base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base
    started = shared / "started"
    started.mkdir(exist_ok=True)
    note = started / hashlib.sha1(nodeid.encode()).hexdigest()
    if note.exists():
        pytest.fail(
            f"{nodeid} did not come to an end when this run first tried it: "
            "its process died under it. Not tried again. It left:\n"
            + note.read_text(errors="replace"), pytrace=False)

    with open(note, "w") as f:
        f.write(f"{nodeid}\n")
        f.flush()

        def on_alarm(signum, frame):
            faulthandler.dump_traceback(file=f, all_threads=True)
            pytest.fail(f"{nodeid} passed its time limit of {limit} s. "
                        f"Threads:\n{note.read_text(errors='replace')}",
                        pytrace=False)

        before = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
        faulthandler.dump_traceback_later(
            limit + NATIVE_GRACE_S, exit=True, file=f)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            faulthandler.cancel_dump_traceback_later()
            signal.signal(signal.SIGALRM, before)
    note.unlink()


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    # State is process-global; leave initialized across tests for speed.
