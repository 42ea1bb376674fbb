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
import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def pytest_configure(config):
    """One persistent compile cache a run, shared by its workers.

    The suite pays for traces, lowerings and compiles, not for arithmetic
    (``tools/tier1_times.py``), and it compiles the same programs over and
    over: every worker process the same small eager ops, file after file
    the same tiny presets, and everything again after a
    ``jax.clear_caches()``.  With jax's own cache in a directory of the
    run's (under ``TMPDIR``, gone with the run) the second and later
    compiles of a program are a read: 155 s -> 137 s for
    ``test_zaya_stack.py`` alone and cold, 59 s warm (sandbox, PR 56).
    Where the variable is set already, an operator's choice stands; the
    ``v5e`` fixture (``tests/_v5e.py``) still turns the cache off for its
    modules.  The workers and every process a test starts inherit it."""
    if (hasattr(config, "workerinput") or _REAL_TPU
            or os.environ.get(_CACHE_ENV)):
        return
    config._jax_cache_dir = tempfile.mkdtemp(prefix="jax_cache_")
    os.environ[_CACHE_ENV] = config._jax_cache_dir
    # Every program, the small ones too: they are most of the count.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def pytest_unconfigure(config):
    made = getattr(config, "_jax_cache_dir", None)
    if made:
        os.environ.pop(_CACHE_ENV, None)
        shutil.rmtree(made, ignore_errors=True)

# The one time limit of every test (set-up, call and tear-down together).
# Under the driver's command (six workers on the sandbox's 8 cores; PR 56,
# by tools/tier1_times.py on the run's junit file) every tier-1 test but
# eleven ends within 40 s and all but four within 60: the ResNet-50 example
# run twice (138 s), the four-device step compiled twice for the described
# v5e (99), InceptionV3's step (71) and one ZAYA1 layer compiled for the
# v5e (62); three of them, and any test that needs more than the limit, say
# so with @pytest.mark.time_limit(seconds, "why").
# The suite's budget, which the limit does not hold and a PR has to: no
# file over 300 s of summed case time (a file is one worker's job under
# --dist loadfile; the longest is 259 s), the whole within 1,000 s of the
# driver's 1,470 (970 s).  A PR whose new tests cost more than 60 s says in
# CHANGES.md which fixture or trace it could not share (tests/_once.py, a
# module-scoped fixture, jax.eval_shape for a lowering's parameters).
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
