"""Backward-overlap under the plane-agnostic scheduler: overlap on must
be bit-identical to overlap off on both planes, cached ticks must replay
the scheduler-issued order, and the fused matmul+reduce-scatter must
match its unfused twin (PR: one scheduler, two planes)."""

import os
import socket
import subprocess
import sys
import textwrap

import horovod_tpu  # noqa: F401  — installs the jax.shard_map shim
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.compression import Compression
from horovod_tpu.metrics import registry as metrics_registry


def _grad_tree(n_leading=1, seed=0):
    """Mixed-dtype tree whose float32 leaves straddle a small bucket
    bound: with HOROVOD_TPU_BUCKET_BYTES=1024 the 300-elem leaf is
    oversized (rides alone), the rest pack in declaration order."""
    rng = np.random.RandomState(seed)

    def r(*shape, dtype=np.float32):
        return rng.randn(*((n_leading,) + shape if n_leading > 1
                           else shape)).astype(dtype)

    return {
        "a": r(60),
        "big": r(300),                     # > 1 KiB: oversized, alone
        "b": {"c": r(7, 5), "d": r(33)},
        "half": r(16, dtype=np.float16),   # non-f32: per-leaf path
    }


class TestEagerBitIdentity:
    def test_overlap_matches_per_leaf_bitwise(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "1024")
        import horovod_tpu.jax as hvd_jax
        grads = _grad_tree()
        off = hvd_jax.allreduce_gradients(grads, overlap=False,
                                          name_prefix="olid.off")
        on = hvd_jax.allreduce_gradients(grads, overlap=True,
                                         name_prefix="olid.on")
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), off, on)

    def test_overlap_sum_and_int8_wire_config(self, hvd, monkeypatch):
        # average=False and the int8 wire config (int8-aligned
        # 1024-multiple leaves); on this plane wire compression engages
        # only across processes, so on == off must still be exact.
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "8192")
        import horovod_tpu.jax as hvd_jax
        rng = np.random.RandomState(7)
        grads = {"a": rng.randn(1024).astype(np.float32),
                 "b": rng.randn(1024).astype(np.float32),
                 "c": rng.randn(2048).astype(np.float32)}
        off = hvd_jax.allreduce_gradients(
            grads, overlap=False, average=False,
            compression=Compression.int8, name_prefix="olq.off")
        on = hvd_jax.allreduce_gradients(
            grads, overlap=True, average=False,
            compression=Compression.int8, name_prefix="olq.on")
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), off, on)

    def test_env_knob_routes_to_overlap(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_OVERLAP", "1")
        import horovod_tpu.jax as hvd_jax
        before = metrics_registry.snapshot()["counters"].get(
            "overlap.steps", 0)
        out = hvd_jax.allreduce_gradients(
            {"w": np.ones(8, np.float32)}, name_prefix="olenv")
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
        after = metrics_registry.snapshot()["counters"].get(
            "overlap.steps", 0)
        assert after == before + 1

    def test_overlap_emits_hidden_exposed_metrics(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "1024")
        import horovod_tpu.jax as hvd_jax
        snap0 = metrics_registry.snapshot()
        hvd_jax.allreduce_gradients(_grad_tree(seed=3), overlap=True,
                                    name_prefix="olm")
        snap1 = metrics_registry.snapshot()

        def count(snap, name):
            return (snap["histograms"].get(name) or {}).get("count", 0)

        for name in ("overlap.hidden_seconds", "overlap.exposed_seconds",
                     "overlap.hidden_fraction"):
            assert count(snap1, name) == count(snap0, name) + 1, name
    def test_overlap_counts_buckets(self, hvd, monkeypatch):
        # The planner may be native or Python, and each counts in its own
        # registry.  The merged snapshot shows the Python count wherever a
        # test run earlier in this process left one, so read both.
        from horovod_tpu import metrics as hvd_metrics
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "1024")
        import horovod_tpu.jax as hvd_jax

        def buckets():
            return sum(
                snap.get("counters", {}).get("overlap.buckets", 0)
                for snap in (hvd_metrics.native_snapshot(),
                             hvd_metrics.registry.snapshot()))

        before = buckets()
        hvd_jax.allreduce_gradients(_grad_tree(seed=4), overlap=True,
                                    name_prefix="olb")
        assert buckets() - before >= 2   # the tree spans several buckets


class TestCachedTickReplay:
    def test_cached_tick_replays_issued_order(self):
        """The negotiated ResponseList IS the serialized issue schedule
        (readiness order in, fusion's stable merge preserves it) and the
        response cache replays it verbatim — a cached tick re-issues the
        SAME schedule the scheduler chose when the tick first ran."""
        from horovod_tpu import scheduler
        from horovod_tpu.core import (Request, RequestType, Response,
                                      ResponseType, _LocalResponseCache)

        def req(name):
            return Request(request_rank=0,
                           request_type=RequestType.ALLREDUCE,
                           tensor_name=name, tensor_type="float32",
                           tensor_shape=(8,), root_rank=-1, device=0)

        # Readiness order from backward: the tail tensor arrives first.
        pending = [req("t2"), req("t0"), req("t1")]
        responses = [Response(ResponseType.ALLREDUCE, [r.tensor_name],
                              devices=[0], tensor_sizes=[8])
                     for r in pending]
        planned = scheduler.plan_tick(responses, lambda n: 32,
                                      lambda n: "float32", 1 << 20)
        assert [r.tensor_names for r in planned] == [["t2", "t0", "t1"]]
        cache = _LocalResponseCache(capacity=8)
        assert cache.lookup(pending, table_empty=True) is None
        cache.store(pending, planned)
        replay = cache.lookup(pending, table_empty=True)
        assert replay is not None
        assert [r.tensor_names for r in replay] == [["t2", "t0", "t1"]]


def _flat_body(mesh, **kw):
    from horovod_tpu.jax.spmd import reduce_gradients

    def f(g):
        return reduce_gradients(g, ("ranks",), **kw)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P("ranks"),
                             out_specs=P("ranks")))


class TestInjitBitIdentity:
    def test_staged_buckets_match_single_collective(self, hvd):
        from horovod_tpu.ops.injit import staged_bucket_allreduce
        mesh = hvd.ranks_mesh()
        n = hvd.size()
        rng = np.random.RandomState(11)
        leaves = [rng.randn(n, k).astype(np.float32)
                  for k in (100, 28, 300, 57)]

        def run(overlap):
            def f(*ls):
                out = staged_bucket_allreduce(
                    list(ls), lambda flat: lax.psum(flat, "ranks"),
                    bucket_bytes=512, overlap=overlap)
                return tuple(out)
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P("ranks"),
                out_specs=P("ranks")))(*leaves)

        on, off = run(True), run(False)
        for x, y in zip(on, off):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # The reduction really happened (flat per-leaf outputs come back
        # rank-concatenated; every rank row holds the sum).
        np.testing.assert_allclose(
            np.asarray(off[0]).reshape(n, -1)[0], leaves[0].sum(0),
            rtol=1e-5)

    def test_reduce_gradients_overlap_bit_identical(self, hvd,
                                                    monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "2048")
        mesh = hvd.ranks_mesh()
        n = hvd.size()
        rng = np.random.RandomState(12)
        grads = {"a": rng.randn(n, 300).astype(np.float32),
                 "b": {"c": rng.randn(n, 40).astype(np.float32)},
                 "h": rng.randn(n, 16).astype(np.float16)}
        on = _flat_body(mesh, overlap=True)(grads)
        off = _flat_body(mesh, overlap=False)(grads)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), on, off)

    def test_reduce_gradients_overlap_int8_bit_identical(self, hvd,
                                                         monkeypatch):
        # int8-eligible leaves (1024-multiples): the quantized ring rides
        # per-bucket; overlap may only change the issue order, never the
        # block boundaries, so results stay bitwise equal.
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "8192")
        mesh = hvd.ranks_mesh()
        n = hvd.size()
        rng = np.random.RandomState(13)
        grads = {"a": rng.randn(n, 1024).astype(np.float32),
                 "b": rng.randn(n, 2048).astype(np.float32)}
        on = _flat_body(mesh, compression=Compression.int8,
                        overlap=True)(grads)
        off = _flat_body(mesh, compression=Compression.int8,
                         overlap=False)(grads)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), on, off)

    def test_hierarchical_overlap_bit_identical(self, hvd, monkeypatch):
        from horovod_tpu.parallel.mesh import DCN_AXIS, ICI_AXIS
        if hvd.size() < 4:
            pytest.skip("needs 4 devices")
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "1024")
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    (DCN_AXIS, ICI_AXIS))
        from horovod_tpu.jax.spmd import reduce_gradients
        rng = np.random.RandomState(14)
        grads = {"a": rng.randn(2, 200).astype(np.float32),
                 "b": rng.randn(2, 77).astype(np.float32)}

        def body(overlap):
            def f(g):
                return reduce_gradients(g, (DCN_AXIS, ICI_AXIS),
                                        overlap=overlap)
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=P(DCN_AXIS),
                out_specs=P(DCN_AXIS)))

        on = body(True)(grads)
        off = body(False)(grads)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), on, off)

    def test_make_train_step_overlap_trajectory_exact(self, hvd,
                                                      monkeypatch):
        monkeypatch.setenv("HOROVOD_TPU_BUCKET_BYTES", "512")
        import optax
        from horovod_tpu.jax.spmd import make_train_step
        mesh = hvd.ranks_mesh()
        rng = np.random.RandomState(15)
        T, d = 32, 8
        x = rng.randn(T, d).astype(np.float32)
        y = (x @ rng.randn(d, 1)).astype(np.float32)
        params = {"w": jnp.zeros((d, 1)), "b": jnp.zeros((1,))}

        def loss_fn(p, aux, batch):
            bx, by = batch
            return jnp.mean((bx @ p["w"] + p["b"] - by) ** 2), aux

        def train(overlap):
            tx = optax.sgd(0.1)
            step = make_train_step(loss_fn, tx, mesh,
                                   sync_aux_state=False, donate=False,
                                   overlap=overlap)
            p, o, losses = params, tx.init(params), []
            for _ in range(5):
                p, _, o, loss = step(p, {}, o, (x, y))
                losses.append(np.asarray(loss))
            return p, losses

        p_on, l_on = train(True)
        p_off, l_off = train(False)
        np.testing.assert_array_equal(l_on, l_off)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), p_on, p_off)


class TestMatmulReduceScatter:
    def _mesh(self, n=4):
        if len(jax.devices()) < n:
            pytest.skip(f"needs {n} devices")
        return Mesh(np.asarray(jax.devices()[:n]), ("tp",))

    def test_forward_matches_psum_reference(self, hvd):
        from horovod_tpu.parallel.tensor_parallel import (
            matmul_reducescatter)
        n = 4
        mesh = self._mesh(n)
        rng = np.random.RandomState(0)
        x = rng.randn(n * 16, 8).astype(np.float32)   # (rows, k_local)
        w = rng.randn(n * 8, 12).astype(np.float32)

        def fused(xl, wl):
            return matmul_reducescatter(xl, wl, "tp")

        def ref(xl, wl):
            full = lax.psum(jnp.dot(xl, wl), "tp")
            idx = lax.axis_index("tp")
            return lax.dynamic_slice_in_dim(full, idx * 4, 4, axis=-2)

        def run(f):
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P("tp"), P("tp")),
                out_specs=P("tp")))(x, w)

        np.testing.assert_allclose(np.asarray(run(fused)),
                                   np.asarray(run(ref)),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_reference(self, hvd):
        from horovod_tpu.parallel.tensor_parallel import (
            matmul_reducescatter)
        n = 4
        mesh = self._mesh(n)
        rng = np.random.RandomState(1)
        x = rng.randn(n * 8, 4).astype(np.float32)
        w = rng.randn(n * 4, 6).astype(np.float32)

        def loss_of(f):
            def L(xl, wl):
                return (f(xl, wl) ** 2).sum()
            return L

        def fused(xl, wl):
            return matmul_reducescatter(xl, wl, "tp")

        def ref(xl, wl):
            full = lax.psum(jnp.dot(xl, wl), "tp")
            idx = lax.axis_index("tp")
            return lax.dynamic_slice_in_dim(full, idx * 2, 2, axis=-2)

        def grads(f):
            return jax.jit(shard_map(
                lambda xl, wl: jax.grad(loss_of(f), argnums=(0, 1))(
                    xl, wl),
                mesh=mesh, in_specs=(P("tp"), P("tp")),
                out_specs=P("tp")))(x, w)

        for a, b in zip(grads(fused), grads(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_indivisible_rows_raise(self, hvd):
        from horovod_tpu.parallel.tensor_parallel import (
            matmul_reducescatter)
        mesh = self._mesh(4)

        def f(xl, wl):
            return matmul_reducescatter(xl, wl, "tp")

        with pytest.raises(ValueError, match="divisible"):
            jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P("tp"), P("tp")),
                out_specs=P("tp")))(
                np.ones((4 * 3, 4), np.float32),   # 3 rows/shard, n=4
                np.ones((4 * 4, 6), np.float32))

    def test_row_parallel_scatter_output_matches(self, hvd):
        from horovod_tpu.parallel.tensor_parallel import RowParallelDense
        n = 4
        mesh = self._mesh(n)
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                         (8, 6 * n)), np.float32)
        dense = RowParallelDense(5, dtype=jnp.float32)
        scat = RowParallelDense(5, dtype=jnp.float32, scatter_output=True)

        def body(x_local):
            params = dense.init(jax.random.PRNGKey(3), x_local)["params"]
            y_full = dense.apply({"params": params}, x_local)
            y_scat = scat.apply({"params": params}, x_local)
            return y_full, y_scat

        y_full, y_scat = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, "tp"),),
            out_specs=(P(), P("tp")), check_vma=False))(x)
        # Concatenating the scattered row blocks rebuilds the replicated
        # output (to ring-accumulation float tolerance).
        np.testing.assert_allclose(np.asarray(y_scat),
                                   np.asarray(y_full),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- slow legs


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


OVERLAP_2PROC_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax

    hvd.init()
    rank, n = hvd.rank(), hvd.size()
    rng = np.random.RandomState(100 + rank)
    grads = {"a": rng.randn(60).astype(np.float32),
             "big": rng.randn(300).astype(np.float32),
             "b": {"c": rng.randn(7, 5).astype(np.float32)},
             "h": rng.randn(16).astype(np.float16)}
    off = hvd_jax.allreduce_gradients(grads, overlap=False,
                                      name_prefix="ol2.off")
    on = hvd_jax.allreduce_gradients(grads, overlap=True,
                                     name_prefix="ol2.on")
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), off, on)
    # A second overlapped step with the same names rides the response
    # cache; the replayed schedule must produce the same bits again.
    again = hvd_jax.allreduce_gradients(grads, overlap=True,
                                        name_prefix="ol2.on")
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), on, again)
    snap = hvd.metrics()
    assert snap["counters"].get("overlap.steps", 0) >= 2, snap["counters"]
    print(f"WORKER_OK rank={rank}")
    hvd.shutdown()
""")


OVERLAP_ELASTIC_WORKER = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import elastic

    elastic.init()
    rank = hvd.rank()
    grads = {"a": np.full(60, float(rank + 1), np.float32),
             "big": np.full(300, 2.0, np.float32)}
    # One healthy overlapped step at generation 0.
    out = hvd_jax.allreduce_gradients(grads, overlap=True, average=False,
                                      name_prefix="olel.warm")
    assert np.allclose(np.asarray(out["a"]), 3.0), np.asarray(out["a"])[:3]
    if rank == 1:
        os._exit(42)      # dies without the shutdown handshake

    # Survivor: the next overlapped step is mid-flight when the peer
    # loss lands.  The in-flight buckets must complete RETRYABLE (never
    # ABORTED, never a hang), and after the elastic reconfigure the
    # retried step succeeds in the single-rank world.
    attempt = 0
    while True:
        try:
            out = hvd_jax.allreduce_gradients(
                grads, overlap=True, average=False,
                name_prefix=f"olel.step{attempt}")
            break
        except hvd.HorovodRetryableError as e:
            print(f"RETRYABLE_SURFACED attempt={attempt}: "
                  f"{str(e)[:80]}", flush=True)
            gen = elastic.generation()
            t0 = time.monotonic()
            while elastic.generation() == gen and \
                    time.monotonic() - t0 < 60:
                time.sleep(0.05)
            attempt += 1
            assert attempt < 10
    assert hvd.size() == 1, hvd.size()
    assert elastic.generation() >= 1
    assert np.allclose(np.asarray(out["a"]), 1.0)   # own contribution
    print(f"WORKER_OK rank={rank} size={hvd.size()} "
          f"gen={elastic.generation()} retries={attempt}", flush=True)
    hvd.shutdown()
""")


def _launch(script, nprocs=2, timeout=180, extra_env=None):
    port = free_port()
    procs = []
    for i in range(nprocs):
        env = dict(os.environ)
        env.update({
            "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_TPU_PROCESS_INDEX": str(i),
            "HOROVOD_TPU_PROCESS_COUNT": str(nprocs),
            "HOROVOD_TPU_SIZE": str(nprocs),
            "HOROVOD_TPU_RANK": str(i),
            "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
            "HOROVOD_TPU_CYCLE_TIME_MS": "2",
            "HOROVOD_TPU_BUCKET_BYTES": "1024",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        env.update(extra_env or {})
        env.pop("HOROVOD_TPU_TIMELINE", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    return outs


@pytest.mark.slow
class TestOverlapMultiprocess:
    def test_two_process_bit_identity(self):
        """Across a real TCP ring with per-rank-distinct gradients,
        overlap on == off bit-for-bit (2-rank ring sums are order-safe
        by IEEE commutativity; bucket payloads are identical either
        way)."""
        from horovod_tpu import cpp_core
        if not cpp_core.available():
            pytest.skip("native core not built")
        outs = _launch(OVERLAP_2PROC_WORKER)
        for rc, out in outs:
            assert rc == 0, out
            assert "WORKER_OK" in out, out

    def test_elastic_reconfigure_mid_overlapped_step(self, tmp_path):
        """A rank dying while the survivor's overlapped step is in
        flight: the issued buckets complete RETRYABLE, the membership
        reconfigures, and the retried overlapped step succeeds in the
        shrunken world — never an abort, never a hang."""
        from horovod_tpu import cpp_core
        if not cpp_core.available():
            pytest.skip("native core not built")
        outs = _launch(OVERLAP_ELASTIC_WORKER, timeout=240,
                       extra_env={"HOROVOD_TPU_ELASTIC": "1",
                                  "HOROVOD_TPU_CONTROL_TIMEOUT_S": "10"})
        rc1, out1 = outs[1]
        assert rc1 == 42, out1
        rc0, out0 = outs[0]
        assert rc0 == 0, out0
        assert "RETRYABLE_SURFACED" in out0, out0
        assert "ABORTED" not in out0, out0
        assert "WORKER_OK rank=0 size=1" in out0, out0
