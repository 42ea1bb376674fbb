"""Async incremental checkpointing (PR: sub-second recovery).

Fast tests cover the delta-chain format (base + N deltas == full state,
torn tips, staging/orphan debris skipped by ``latest_epoch``), the
``crash_in_save`` fault-spec parse, the :class:`AsyncCheckpointer`
pipeline (double-buffered coalescing, non-blocking snapshots, periodic
full bases, attributed write-error propagation, kill-mid-delta fallback),
the ``run_elastic`` integration, and the world-size sidecar through the
chain format.  Slow tests run the scripted chaos drills from bench.py:
kill one of two ranks under load and compare sync-checkpoint recovery
against the async stream (the ISSUE's <= 25% bar), and plant a
``crash_in_save`` fault under a 3-process job to prove the committed
chain survives a writer killed mid-commit.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu import checkpoint, ckpt_stream, cpp_core, elastic
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.core import parse_fault_spec, parse_fault_specs
from horovod_tpu.ops.eager import HorovodRetryableError

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _flat(state):
    return checkpoint.flatten_state(state)


def _state(step, n=16):
    return {"w": np.full(n, float(step), np.float32),
            "b": np.arange(3, dtype=np.float64),
            "step": np.asarray(step, np.int64)}


# ------------------------------------------------------------------ fast unit


class TestCrashInSaveFaultSpec:
    def test_parse(self):
        (fs,) = parse_fault_specs("crash_in_save:rank=1:epoch=30")
        assert (fs.mode, fs.rank, fs.epoch) == ("crash_in_save", 1, 30)

    def test_epoch_zero_is_legal(self):
        fs = parse_fault_spec("crash_in_save:rank=0:epoch=0")
        assert fs.epoch == 0

    def test_mixed_with_tick_modes(self):
        specs = parse_fault_specs(
            "crash:rank=1:tick=40;crash_in_save:rank=0:epoch=8")
        assert [(s.mode, s.rank) for s in specs] == [
            ("crash", 1), ("crash_in_save", 0)]

    def test_tick_key_rejected(self):
        with pytest.raises(ValueError, match="epoch"):
            parse_fault_spec("crash_in_save:rank=0:tick=3")

    def test_epoch_key_rejected_for_tick_modes(self):
        with pytest.raises(ValueError, match="tick"):
            parse_fault_spec("crash:rank=0:epoch=3")

    def test_negative_epoch_rejected(self):
        with pytest.raises(ValueError, match="epoch must be >= 0"):
            parse_fault_spec("crash_in_save:rank=0:epoch=-1")


class TestChainFormat:
    def _chain(self, d, epochs):
        """Commit the law state at each epoch; returns the flats."""
        prev, prev_e = None, -1
        flats = {}
        for e in epochs:
            fl = _flat(_state(e))
            checkpoint.save_chain(d, fl, e, prev_epoch=prev_e,
                                  prev_flat=prev)
            flats[e] = fl
            prev, prev_e = fl, e
        return flats

    def test_base_plus_deltas_equals_full_state(self, tmp_path):
        d = str(tmp_path)
        self._chain(d, [0, 2, 4, 6])
        assert checkpoint.chain_links(d, 6) == [0, 2, 4, 6]
        out = checkpoint.restore(d, 6, _state(0))
        for k, v in _flat(_state(6)).items():
            np.testing.assert_array_equal(
                checkpoint.flatten_state(out)[k], v)

    def test_delta_stores_only_changed_leaves(self, tmp_path):
        d = str(tmp_path)
        s0, s1 = _state(0), _state(1)
        stats0 = checkpoint.save_chain(d, _flat(s0), 0)
        stats1 = checkpoint.save_chain(d, _flat(s1), 1, prev_epoch=0,
                                       prev_flat=_flat(s0))
        assert stats0 == {"kind": "base", "epoch": 0, "shards": 3,
                          "total": 3, "nbytes": stats0["nbytes"]}
        # "b" is identical in both states — the delta must not carry it.
        assert stats1["kind"] == "delta" and stats1["shards"] == 2
        assert stats1["nbytes"] < stats0["nbytes"]

    def test_unchanged_state_commits_empty_delta(self, tmp_path):
        d = str(tmp_path)
        fl = _flat(_state(3))
        checkpoint.save_chain(d, fl, 0)
        stats = checkpoint.save_chain(d, fl, 1, prev_epoch=0, prev_flat=fl)
        assert stats["shards"] == 0 and stats["nbytes"] == 0
        out = checkpoint.restore(d, 1, _state(0))
        np.testing.assert_array_equal(out["w"], _state(3)["w"])

    def test_leaf_set_change_forces_base(self, tmp_path):
        d = str(tmp_path)
        fl = _flat(_state(0))
        checkpoint.save_chain(d, fl, 0)
        wider = dict(fl)
        wider["['extra']"] = np.ones(4, np.float32)
        stats = checkpoint.save_chain(d, wider, 1, prev_epoch=0,
                                      prev_flat=fl)
        assert stats["kind"] == "base" and stats["shards"] == 4

    def test_torn_tip_skipped_by_latest_epoch(self, tmp_path):
        """Satellite: a resume racing a crashed writer must fall back
        past the torn tip, not pick it."""
        d = str(tmp_path)
        self._chain(d, [0, 2, 4])
        shutil.rmtree(str(tmp_path / "checkpoint-2"))   # tear the chain
        assert checkpoint.chain_links(d, 4) is None
        assert checkpoint.latest_epoch(d) == 0
        assert checkpoint.resolve_committed_epoch(d, 4) == 0
        with pytest.raises(checkpoint.TornChainError, match="torn"):
            checkpoint.restore(d, 4, _state(0))

    def test_latest_epoch_skips_staging_and_orphans(self, tmp_path):
        """Satellite: dot-prefixed staging dirs, orphaned sidecars, and
        stray files from a crash-in-save must never look like a
        checkpoint to a racing restore."""
        d = str(tmp_path)
        self._chain(d, [3])
        os.makedirs(str(tmp_path / ".tmp-checkpoint-9-4242"))
        (tmp_path / "checkpoint-9.world.json").write_text("{}")
        (tmp_path / "checkpoint-11").write_text("")   # stray FILE
        assert checkpoint.latest_epoch(d) == 3

    def test_mixed_legacy_and_chain_epochs(self, hvd, tmp_path):
        d = str(tmp_path)
        checkpoint.save(d, _state(0), 0)               # legacy orbax
        fl = _flat(_state(5))
        checkpoint.save_chain(d, fl, 5)
        checkpoint.save_chain(d, _flat(_state(7)), 7, prev_epoch=5,
                              prev_flat=fl)
        assert checkpoint.latest_epoch(d) == 7
        out = checkpoint.restore(d, 7, _state(0))
        np.testing.assert_array_equal(np.asarray(out["w"]), _state(7)["w"])
        legacy = checkpoint.restore(d, 0, _state(0))
        np.testing.assert_array_equal(np.asarray(legacy["w"]),
                                      _state(0)["w"])

    def test_clean_stale_spares_active_staging(self, hvd, tmp_path):
        """A synchronous save() must not reap the async writer's
        in-flight staging dir or its pre-commit sidecar."""
        d = str(tmp_path)
        staging = str(tmp_path / ".tmp-checkpoint-8-1")
        os.makedirs(staging)
        (tmp_path / "checkpoint-8.world.json").write_text("{}")
        checkpoint._ACTIVE_STAGING[8] = staging
        try:
            checkpoint.save(d, _state(1), 0)
        finally:
            del checkpoint._ACTIVE_STAGING[8]
        assert os.path.isdir(staging)
        assert (tmp_path / "checkpoint-8.world.json").exists()
        # Unregistered debris with the same shape IS reaped.
        checkpoint.save(d, _state(1), 1)
        assert not os.path.isdir(staging)
        assert not (tmp_path / "checkpoint-8.world.json").exists()


class TestAsyncCheckpointer:
    def test_commits_base_then_deltas(self, tmp_path):
        d = str(tmp_path)
        ac = ckpt_stream.AsyncCheckpointer(d, snapshot_every_steps=1)
        try:
            ac.seed(_state(0), -1)
            ac.snapshot(_state(1), 1)
            ac.flush()
            ac.snapshot(_state(2), 2)
            ac.flush()
        finally:
            ac.close()
        assert checkpoint.latest_epoch(d) == 2
        assert ac.last_committed_epoch == 2
        m = checkpoint._chain_manifest(d, 2)
        assert m["kind"] == "delta" and m["prev"] == 1
        out = checkpoint.restore(d, 2, _state(0))
        np.testing.assert_array_equal(out["w"], _state(2)["w"])

    def test_snapshot_does_not_block_on_slow_writer(self, tmp_path,
                                                    monkeypatch):
        """Satellite overlap assertion: the step path pays only the
        device→host copy — a writer stuck in a slow commit must not
        stall snapshot()."""
        gate = threading.Event()
        orig = checkpoint.save_chain

        def slow_save(*args, **kwargs):
            gate.wait(timeout=30)
            return orig(*args, **kwargs)
        monkeypatch.setattr(checkpoint, "save_chain", slow_save)
        ac = ckpt_stream.AsyncCheckpointer(str(tmp_path),
                                           snapshot_every_steps=1)
        try:
            ac.snapshot(_state(1), 1)        # writer enters slow_save
            time.sleep(0.05)
            t0 = time.perf_counter()
            ac.snapshot(_state(2), 2)
            dt = time.perf_counter() - t0
            assert dt < 1.0, f"snapshot blocked {dt:.2f}s on the writer"
            gate.set()
            ac.flush()
        finally:
            gate.set()
            ac.close()
        assert checkpoint.latest_epoch(str(tmp_path)) == 2

    def test_double_buffer_coalesces_to_latest(self, tmp_path,
                                               monkeypatch):
        gate = threading.Event()
        orig = checkpoint.save_chain

        def slow_save(*args, **kwargs):
            gate.wait(timeout=30)
            return orig(*args, **kwargs)
        monkeypatch.setattr(checkpoint, "save_chain", slow_save)
        before = hvd_metrics.registry.snapshot()["counters"].get(
            "ckpt.coalesced", 0)
        ac = ckpt_stream.AsyncCheckpointer(str(tmp_path),
                                           snapshot_every_steps=1)
        try:
            ac.snapshot(_state(1), 1)
            time.sleep(0.05)                 # writer holds epoch 1
            assert ac.snapshot(_state(2), 2) is True    # fills the buffer
            assert ac.snapshot(_state(3), 3) is False   # replaces epoch 2
            gate.set()
            ac.flush()
        finally:
            gate.set()
            ac.close()
        d = str(tmp_path)
        assert checkpoint.latest_epoch(d) == 3
        assert not os.path.isdir(os.path.join(d, "checkpoint-2"))
        after = hvd_metrics.registry.snapshot()["counters"].get(
            "ckpt.coalesced", 0)
        assert after == before + 1

    def test_periodic_full_base(self, tmp_path):
        d = str(tmp_path)
        ac = ckpt_stream.AsyncCheckpointer(d, snapshot_every_steps=1,
                                           full_every=2)
        try:
            for e in range(1, 6):
                ac.snapshot(_state(e), e)
                ac.flush()
        finally:
            ac.close()
        kinds = [checkpoint._chain_manifest(d, e)["kind"]
                 for e in range(1, 6)]
        assert kinds == ["base", "delta", "delta", "base", "delta"]
        # Restoring the tip replays only from the latest base.
        assert checkpoint.chain_links(d, 5) == [4, 5]

    def test_write_error_raises_attributed_retryable(self, tmp_path,
                                                     monkeypatch):
        """Satellite: a disk-full inside the writer thread surfaces as an
        attributed HorovodRetryableError on the owning rank's step path,
        plus a ckpt.write_errors counter and a flight event."""
        events = []
        monkeypatch.setattr(
            cpp_core, "flight_record",
            lambda kind, detail="", nbytes=0, a=0, b=0:
                events.append((kind, detail)))
        monkeypatch.setattr(
            checkpoint, "save_chain",
            lambda *a, **k: (_ for _ in ()).throw(
                OSError(28, "No space left on device")))
        before = hvd_metrics.registry.snapshot()["counters"].get(
            "ckpt.write_errors", 0)
        ac = ckpt_stream.AsyncCheckpointer(str(tmp_path),
                                           snapshot_every_steps=1)
        try:
            ac.snapshot(_state(1), 1)
            with pytest.raises(HorovodRetryableError) as ei:
                ac.flush()
        finally:
            ac.close(flush=False)
        msg = str(ei.value)
        assert "rank 0" in msg and "epoch 1" in msg
        assert "No space left" in msg
        after = hvd_metrics.registry.snapshot()["counters"].get(
            "ckpt.write_errors", 0)
        assert after == before + 1
        assert any(k == "CKPT_WRITE_ERROR" for k, _ in events)

    def test_kill_mid_delta_recovers_previous_chain(self, hvd, tmp_path,
                                                    monkeypatch):
        """Satellite drill (fast half): a writer killed between staging
        its shards and committing leaves debris; the previous committed
        chain stays the resume point and restore_and_broadcast picks it."""
        d = str(tmp_path)

        class Died(Exception):
            pass

        def fake_die(code, msg):
            raise Died(f"exit {code}: {msg}")
        monkeypatch.setattr(ckpt_stream, "_die", fake_die)
        monkeypatch.setenv("HOROVOD_TPU_FAULT",
                           "crash_in_save:rank=0:epoch=4")
        monkeypatch.setenv("HOROVOD_TPU_RANK", "0")
        ac = ckpt_stream.AsyncCheckpointer(d, snapshot_every_steps=1)
        try:
            ac.snapshot(_state(2), 2)
            ac.flush()                       # epoch 2 commits (< fault)
            ac.snapshot(_state(4), 4)        # fault fires mid-commit
            with pytest.raises(HorovodRetryableError, match="epoch 4"):
                ac.flush()
        finally:
            ac.close(flush=False)
        assert any(e.startswith(".tmp-checkpoint-4")
                   for e in os.listdir(d)), os.listdir(d)
        assert checkpoint.latest_epoch(d) == 2
        state, epoch = checkpoint.restore_and_broadcast(d, _state(0))
        assert epoch == 2
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      _state(2)["w"])

    def test_seed_after_legacy_save_forces_base(self, hvd, tmp_path):
        """A delta cannot chain to an orbax dir: after restoring a
        legacy checkpoint the next commit must be a fresh base."""
        d = str(tmp_path)
        checkpoint.save(d, _state(3), 3)
        ac = ckpt_stream.AsyncCheckpointer(d, snapshot_every_steps=1)
        try:
            ac.seed(_state(3), 3)
            ac.snapshot(_state(4), 4)
            ac.flush()
        finally:
            ac.close()
        assert checkpoint._chain_manifest(d, 4)["kind"] == "base"

    def test_seed_on_chain_tip_continues_delta(self, tmp_path):
        d = str(tmp_path)
        fl = _flat(_state(3))
        checkpoint.save_chain(d, fl, 3)
        ac = ckpt_stream.AsyncCheckpointer(d, snapshot_every_steps=1)
        try:
            ac.seed(_state(3), 3)
            ac.snapshot(_state(4), 4)
            ac.flush()
        finally:
            ac.close()
        m = checkpoint._chain_manifest(d, 4)
        assert m["kind"] == "delta" and m["prev"] == 3


class TestRestoreAndBroadcastChain:
    def test_torn_explicit_epoch_falls_back_committed(self, hvd, tmp_path,
                                                      capfd):
        """Every rank pivots to the fallback BEFORE the value broadcast —
        the agreed epoch must be restorable, not just present."""
        d = str(tmp_path)
        fl = _flat(_state(2))
        checkpoint.save_chain(d, fl, 2)
        checkpoint.save_chain(d, _flat(_state(6)), 6, prev_epoch=5,
                              prev_flat=None)
        checkpoint.save_chain(d, _flat(_state(8)), 8, prev_epoch=6,
                              prev_flat=_flat(_state(6)))
        shutil.rmtree(str(tmp_path / "checkpoint-6"))   # tear 8's base
        state, epoch = checkpoint.restore_and_broadcast(d, _state(0),
                                                        epoch=8)
        assert epoch == 2
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      _state(2)["w"])
        assert "torn or missing" in capfd.readouterr().err

    def test_world_size_mismatch_through_chain(self, hvd, tmp_path,
                                               capfd):
        """Satellite: the sidecar world-size check holds for chain
        epochs — replicated state re-broadcasts with a note, sharded
        state fails naming the leaf."""
        d = str(tmp_path)
        checkpoint.save_chain(d, _flat(_state(1)), 0)
        assert checkpoint.saved_world_size(d, 0) == hvd.size()
        with open(checkpoint._world_meta_path(d, 0), "w") as f:
            json.dump({"world_size": hvd.size() + 1}, f)
        state, epoch = checkpoint.restore_and_broadcast(d, _state(0))
        assert epoch == 0
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      _state(1)["w"])
        assert "world size" in capfd.readouterr().err

    def test_world_size_mismatch_sharded_leaf_fails(self, hvd, tmp_path,
                                                    monkeypatch):
        d = str(tmp_path)
        checkpoint.save_chain(d, _flat(_state(1)), 0)
        with open(checkpoint._world_meta_path(d, 0), "w") as f:
            json.dump({"world_size": hvd.size() + 1}, f)
        monkeypatch.setattr(checkpoint, "_sharded_leaf_path",
                            lambda tree: "['w']")
        with pytest.raises(ValueError, match=r"\['w'\].*sharded"):
            checkpoint.restore_and_broadcast(d, _state(0))


class TestRunElasticStream:
    def test_stream_lifecycle_and_knob(self, hvd, tmp_path, monkeypatch):
        """run_elastic(snapshot_every_steps=N) arms the stream on the
        root rank, elastic.snapshot() feeds it at the cadence, and a
        clean exit flushes the final snapshot committed."""
        d = str(tmp_path)
        seen = {}

        def train(state, epoch):
            seen["stream"] = elastic.active_stream()
            assert seen["stream"] is not None
            for step in range(1, 7):
                elastic.snapshot(_state(step), step)
            return "done"
        out = elastic.run_elastic(train, directory=d, like=_state(0),
                                  snapshot_every_steps=2)
        assert out == "done"
        assert elastic.active_stream() is None      # closed on exit
        assert checkpoint.latest_epoch(d) == 6      # flushed tip
        assert checkpoint.is_chain(d, 6)

    def test_env_cadence_default(self, hvd, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_CKPT_EVERY_STEPS", "3")
        d = str(tmp_path)

        taken = []

        def train(state, epoch):
            for step in range(1, 7):
                if elastic.snapshot(_state(step), step):
                    taken.append(step)
                    # Latest wins: a snapshot still unwritten when the next
                    # one comes is replaced by it.  Wait for the writer, so
                    # that step 3 is a link of the chain and not a race.
                    elastic.active_stream().flush()
            return None
        elastic.run_elastic(train, directory=d, like=_state(0))
        assert taken == [3, 6]
        assert checkpoint.latest_epoch(d) == 6
        assert checkpoint._chain_manifest(d, 6)["prev"] == 3

    def test_off_by_default(self, hvd, tmp_path, monkeypatch):
        monkeypatch.delenv("HOROVOD_TPU_CKPT_EVERY_STEPS", raising=False)
        monkeypatch.delenv("HOROVOD_TPU_CKPT_ASYNC", raising=False)

        def train(state, epoch):
            assert elastic.active_stream() is None
            assert elastic.snapshot(_state(1), 1) is False
            return None
        elastic.run_elastic(train, directory=str(tmp_path),
                            like=_state(0))
        assert checkpoint.latest_epoch(str(tmp_path)) == -1

    def test_knob_defaults(self, monkeypatch):
        for var in ("HOROVOD_TPU_CKPT_ASYNC", "HOROVOD_TPU_CKPT_EVERY_STEPS",
                    "HOROVOD_TPU_CKPT_FULL_EVERY"):
            monkeypatch.delenv(var, raising=False)
        assert not ckpt_stream.async_enabled()
        assert ckpt_stream.snapshot_every_steps_default() == 0
        assert ckpt_stream.full_every_default() == 16
        monkeypatch.setenv("HOROVOD_TPU_CKPT_ASYNC", "1")
        monkeypatch.setenv("HOROVOD_TPU_CKPT_EVERY_STEPS", "5")
        monkeypatch.setenv("HOROVOD_TPU_CKPT_FULL_EVERY", "4")
        assert ckpt_stream.async_enabled()
        assert ckpt_stream.snapshot_every_steps_default() == 5
        assert ckpt_stream.full_every_default() == 4

    def test_launcher_propagates_ckpt_knobs(self):
        """--snapshot-every-steps sets both checkpoint env knobs in
        every child (and implies async); --ckpt-async alone sets only
        the mode flag."""
        probe = ("import os; print('KNOBS',"
                 " os.environ.get('HOROVOD_TPU_CKPT_ASYNC', '-'),"
                 " os.environ.get('HOROVOD_TPU_CKPT_EVERY_STEPS', '-'))")
        p = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
             "--snapshot-every-steps", "4", "--",
             sys.executable, "-c", probe],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr
        assert "KNOBS 1 4" in p.stdout, p.stdout
        p = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
             "--ckpt-async", "--", sys.executable, "-c", probe],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr
        assert "KNOBS 1 -" in p.stdout, p.stdout


# ------------------------------------------------------- slow chaos drills

pytestmark_native = pytest.mark.skipif(
    not cpp_core.available(), reason="native core not built")


@pytest.mark.slow
@pytestmark_native
class TestChaosDrills:
    def test_async_recovery_beats_sync_baseline(self):
        """ISSUE acceptance: the scripted kill-one-rank drill — async
        incremental recovery must take <= 25% of the synchronous
        full-checkpoint baseline recorded in the same run, with
        bit-identical resumed parameters in both legs."""
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        import bench
        r = bench._recovery_drill()
        assert r["sync"]["state_ok"] and r["async"]["state_ok"], r
        assert r["sync"]["replayed_steps"] > r["async"]["replayed_steps"], r
        assert r["async"]["resume_epoch"] > r["sync"]["resume_epoch"], r
        assert r["recovery_ratio_async_vs_sync"] <= 0.25, r
        # Downtime was recorded natively on both legs.
        assert r["sync"]["native_downtime_s"] >= 0, r
        assert r["async"]["native_downtime_s"] >= 0, r
        # The async leg actually wrote a delta chain.
        assert r["async"]["commits"]["delta"] > 0, r
        assert r["async"]["ckpt_bytes"]["delta"] > 0, r

    def test_crash_in_save_chain_survives(self, tmp_path):
        """ISSUE acceptance: plant crash_in_save on the writing rank —
        the writer dies between staging and commit, the survivors fail
        over, and the job resumes from the last COMMITTED chain epoch
        (< the fault epoch), torn debris notwithstanding."""
        if REPO_ROOT not in sys.path:
            sys.path.insert(0, REPO_ROOT)
        port = None
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        procs = []
        for i in range(3):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
                "HOROVOD_TPU_PROCESS_INDEX": str(i),
                "HOROVOD_TPU_PROCESS_COUNT": "3",
                "HOROVOD_TPU_SIZE": "3",
                "HOROVOD_TPU_RANK": str(i),
                "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
                "HOROVOD_TPU_CYCLE_TIME_MS": "2",
                "HOROVOD_TPU_RENDEZVOUS_S": "20",
                "HOROVOD_TPU_ELASTIC": "1",
                "HOROVOD_TPU_FAULT": "crash_in_save:rank=0:epoch=30",
                "BENCH_RECOVERY_MODE": "async",
                "BENCH_RECOVERY_DIE_RANK": "-1",
                "BENCH_RECOVERY_DIR": str(tmp_path),
            })
            env.pop("HOROVOD_TPU_TIMELINE", None)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
                 "--recovery-worker"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
                outs.append((p.returncode, out))
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                outs.append((None, out))
        rc0, out0 = outs[0]
        assert rc0 == 43, out0      # _die(43) from the planted fault
        assert "crashing rank 0 mid-save" in out0, out0
        survivors = [o for rc, o in outs[1:] if rc == 0]
        assert survivors, outs
        recleg = None
        for out in survivors:
            for line in out.splitlines():
                if line.startswith("RECLEG "):
                    recleg = json.loads(line[len("RECLEG "):])
        assert recleg is not None, survivors
        assert recleg["state_ok"], recleg
        # Resumed from a COMMITTED chain epoch below the fault epoch.
        assert 0 <= recleg["resume_epoch"] < 30, recleg
        # The committed chain survived the torn commit: the survivor's
        # resume point was restorable and the drill replayed forward.
        assert recleg["replayed_steps"] >= 1, recleg
