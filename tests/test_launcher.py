"""End-to-end test of the mpirun replacement (``python -m
horovod_tpu.run``): the reference's launch story is ``mpirun -np N
python train.py`` (``docs/running.md:1-46``); ours must spawn N wired
processes whose collectives agree, with zero manual env."""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu import cpp_core

pytestmark = pytest.mark.skipif(
    not cpp_core.available(), reason="native core not built")

_PAYLOAD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    n, r = hvd.size(), hvd.rank()
    out = np.asarray(hvd.allreduce(np.full((4,), float(r + 1), np.float32),
                                   average=False, name="launch.sum"))
    np.testing.assert_allclose(out, np.full((4,), n * (n + 1) / 2.0))
    print(f"LAUNCH_OK rank={r} size={n}", flush=True)
""")


def test_run_np2_allreduce(tmp_path):
    script = tmp_path / "payload.py"
    script.write_text(_PAYLOAD)
    env = dict(os.environ)
    env.pop("HOROVOD_TPU_COORD_ADDR", None)
    # One virtual device per spawned process (the suite's conftest sets 8,
    # which would give each 1-rank worker a gapped rank space).
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # Own session so a hang kills the whole tree (launcher + payload
    # grandchildren), not just the launcher.
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--",
         sys.executable, str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, start_new_session=True)
    try:
        combined, _ = proc.communicate(timeout=180)
    except subprocess.TimeoutExpired:
        import signal
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        combined, _ = proc.communicate()
        pytest.fail(f"launcher timed out; output:\n{combined}")
    assert proc.returncode == 0, combined
    assert "LAUNCH_OK rank=0 size=2" in combined, combined
    assert "LAUNCH_OK rank=1 size=2" in combined, combined


class TestOneProcessPerChip:
    """Where the children run on the host's TPU chips, child i is confined
    to the chips [i*rpp, (i+1)*rpp) through the runtime's own visibility
    settings; where that cannot be done the launcher refuses, once.  The
    launcher's parent never asks jax (it would take the chips itself)."""

    @pytest.fixture()
    def spawned(self, monkeypatch):
        """Run run.main with Popen replaced: the env of each child."""
        from horovod_tpu import run as run_mod

        envs = []

        class Child:
            def __init__(self, cmd, env=None):
                envs.append(env)

            def poll(self):
                return None        # still running while the rest spawn

        monkeypatch.setattr(run_mod.subprocess, "Popen", Child)
        monkeypatch.setattr(run_mod, "_supervise", lambda procs, grace: 0)
        monkeypatch.delenv("HOROVOD_TPU_TIMELINE", raising=False)
        return run_mod, envs

    def test_children_get_disjoint_chips(self, spawned, monkeypatch):
        run_mod, envs = spawned
        monkeypatch.setattr(run_mod, "tpu_chips_on_host", lambda: 4)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert run_mod.main(["-np", "4", "--", "true"]) == 0
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert [e["HOROVOD_TPU_RANK"] for e in envs] == ["0", "1", "2", "3"]

    def test_two_chips_per_process(self, spawned, monkeypatch):
        run_mod, envs = spawned
        monkeypatch.setattr(run_mod, "tpu_chips_on_host", lambda: 4)
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert run_mod.main(["-np", "2", "--ranks-per-process", "2",
                             "--", "true"]) == 0
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0,1", "2,3"]
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,2,1"}

    def test_too_few_chips_refuses_before_spawning(self, spawned,
                                                   monkeypatch, capsys):
        run_mod, envs = spawned
        monkeypatch.setattr(run_mod, "tpu_chips_on_host", lambda: 1)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        with pytest.raises(SystemExit) as exc:
            run_mod.main(["-np", "4", "--", "true"])
        assert exc.value.code != 0
        assert "cannot be given chips of their own" in capsys.readouterr().err
        assert envs == []

    def test_cpu_children_are_left_alone(self, spawned, monkeypatch):
        """bench.py's workers and the tests pin JAX_PLATFORMS=cpu: no
        chip is assigned, and the host's chips are not even counted."""
        run_mod, envs = spawned
        monkeypatch.setattr(run_mod, "tpu_chips_on_host",
                            lambda: pytest.fail("counted the chips"))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert run_mod.main(["-np", "2", "--", "true"]) == 0
        assert all("TPU_VISIBLE_CHIPS" not in e for e in envs)

    def test_launcher_import_starts_no_backend(self):
        """`python -m horovod_tpu.run` and `import horovod_tpu` leave
        every jax backend uninitialised."""
        out = subprocess.run(
            [sys.executable, "-c",
             "import horovod_tpu, horovod_tpu.run; "
             "import jax._src.xla_bridge as xb; print(len(xb._backends))"],
            capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "0"
