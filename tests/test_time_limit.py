"""The per-test time limit of ``conftest.py``, tried on a child pytest run.

Each case writes a two-test file, runs it under this directory's conftest
and reads the child's report: the test that blocks is failed with every
thread's stack, and the test behind it still runs and passes.
"""

import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# Blocks where Python handles signals: the alarm's handler fails the test.
IN_PYTHON = """
    import time
    import pytest

    @pytest.mark.time_limit(1, "the limit's own test")
    def test_blocks():
        time.sleep(60)

    def test_behind_it():
        pass
"""

# Blocks in native code that no signal interrupts (a second lock of a plain
# mutex, the GIL released): only the second stage ends it, and the process
# with it.
IN_NATIVE = """
    import ctypes
    import conftest
    import pytest

    conftest.NATIVE_GRACE_S = 1

    @pytest.mark.time_limit(1, "the limit's own test")
    def test_blocks():
        libc = ctypes.CDLL(None)
        mutex = ctypes.create_string_buffer(128)
        libc.pthread_mutex_init(mutex, None)
        libc.pthread_mutex_lock(mutex)
        libc.pthread_mutex_lock(mutex)

    def test_behind_it():
        pass
"""


def run_child(tmp_path, source, *options):
    test_file = tmp_path / "test_child.py"
    test_file.write_text(textwrap.dedent(source))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["PYTHONPATH"] = os.pathsep.join([HERE, REPO])
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(test_file), "-p", "conftest",
         "-c", os.path.join(REPO, "pyproject.toml"), "-p", "no:cacheprovider",
         "--basetemp", str(tmp_path / "base"), "-q", *options],
        capture_output=True, text=True, timeout=120, env=env, cwd=HERE)


def test_a_test_blocked_in_python_fails_with_the_stacks(tmp_path):
    out = run_child(tmp_path, IN_PYTHON, "-p", "no:xdist")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "1 failed, 1 passed" in out.stdout, out.stdout
    assert "test_blocks passed its time limit of 1 s" in out.stdout
    # The dump of the stacks: the sleeping line of the main thread.
    assert "Threads:" in out.stdout
    assert 'test_child.py", line 7 in test_blocks' in out.stdout, out.stdout


def test_a_test_blocked_in_native_code_costs_one_test(tmp_path):
    """The process dies; xdist hands the test to the worker that replaces
    it, where it is failed with what the first try left and not run, and
    the run goes on to its end."""
    out = run_child(tmp_path, IN_NATIVE, "-p", "xdist", "-n", "1",
                    "--dist", "loadfile")
    assert out.returncode == 1, out.stdout + out.stderr
    # The failure is xdist's report of the crash, the error the refusal.
    assert "1 failed, 1 passed, 1 error" in out.stdout, out.stdout
    assert "node down" in out.stdout
    assert "test_blocks did not come to an end" in out.stdout
    assert "Timeout (0:00:02)!" in out.stdout, out.stdout
    # The main thread's stack, at the second lock.
    assert 'test_child.py", line 14 in test_blocks' in out.stdout, out.stdout
