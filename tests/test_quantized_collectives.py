"""In-jit quantized collectives (ops/quantized_collectives.py): codec
round-trip and edge cases, Pallas-vs-jnp bit parity, cross-plane wire
parity against the C++ ring codec, the quantized ring allreduce inside
shard_map, the bucket policy knobs, the bytes-on-wire metrics, and the
``compression=none`` no-op guard.

Runs entirely on the 8-virtual-CPU mesh: the Pallas kernels execute in
interpret mode (the same code path a TPU-less CI exercises).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu  # noqa: F401  (jax compat shim: jax.shard_map)
from horovod_tpu import cpp_core
from horovod_tpu.compression import Compression, NoneCompressor
from horovod_tpu.ops import quantized_collectives as qc


def _rand(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale
            ).astype(np.float32)


# ---------------------------------------------------------------- codec


@pytest.mark.parametrize("n", [1024, 4096, 65536])
def test_codec_roundtrip_error_bound(n):
    x = _rand((n,), seed=n)
    q, scales = qc.quantize_blocks(jnp.asarray(x))
    assert q.dtype == jnp.int8 and scales.dtype == jnp.float32
    deq = np.asarray(qc.dequantize_blocks(q, scales))
    # Per-block absolute error is at most half a quantization step.
    err = np.abs(deq - x).reshape(-1, qc.BLOCK_ELEMS).max(axis=1)
    step = np.asarray(scales).reshape(-1)
    assert np.all(err <= 0.5 * step + 1e-7)


@pytest.mark.parametrize("shape", [(1,), (5,), (1000,), (3, 341),
                                   (1025,), (33, 31), (2047,)])
def test_snap_to_grid_tails_and_shapes(shape):
    """Non-multiple-of-1024 tails round-trip without NaN/inf and keep
    their shape (the Int8Compressor edge case this PR fixes)."""
    x = _rand(shape, seed=sum(shape))
    out = np.asarray(qc.snap_to_grid(jnp.asarray(x)))
    assert out.shape == x.shape
    assert np.all(np.isfinite(out))
    absmax = np.abs(x).max()
    assert np.abs(out - x).max() <= 0.5 * absmax * (1 / 127) + 1e-7


def test_all_zero_and_tiny_blocks_are_nan_free():
    # All-zero block: scale 1, exact zeros back.
    z = np.zeros(2048, np.float32)
    q, s = qc.quantize_blocks(jnp.asarray(z))
    assert np.all(np.asarray(s) == 1.0)
    assert np.all(np.asarray(qc.dequantize_blocks(q, s)) == 0.0)
    # Tiny-but-normal absmax: without the FLT_MIN clamp 1/scale would be
    # inf and the block's exact zeros would decode as NaN.
    t = np.zeros(1024, np.float32)
    t[7] = 2e-38
    out = np.asarray(qc.snap_to_grid(jnp.asarray(t)))
    assert np.all(np.isfinite(out))
    assert out[0] == 0.0


def test_pallas_and_jnp_codecs_bit_identical(monkeypatch):
    x = jnp.asarray(_rand((8 * 1024 + 1024,), seed=11, scale=3.0))
    monkeypatch.setenv("HOROVOD_TPU_INJIT_PALLAS", "1")
    qp, sp = qc.quantize_blocks(x)
    dp = qc.dequantize_blocks(qp, sp)
    monkeypatch.setenv("HOROVOD_TPU_INJIT_PALLAS", "0")
    qj, sj = qc.quantize_blocks(x)
    dj = qc.dequantize_blocks(qj, sj)
    assert np.array_equal(np.asarray(qp), np.asarray(qj))
    assert np.array_equal(np.asarray(sp).view(np.uint32),
                          np.asarray(sj).view(np.uint32))
    assert np.array_equal(np.asarray(dp).view(np.uint32),
                          np.asarray(dj).view(np.uint32))


# ------------------------------------------------- cross-plane parity


@pytest.mark.skipif(not cpp_core.available(),
                    reason="native core not built")
@pytest.mark.parametrize("n", [100, 1024, 1025, 65536, 70001])
def test_wire_image_parity_with_cpp_codec(n):
    """The in-jit codec and the C++ ring codec produce byte-identical
    int8 wire images, and each decodes the other's bit-exactly."""
    rng = np.random.RandomState(n)
    x = (rng.randn(n) * np.exp(rng.uniform(-6, 6, n))).astype(np.float32)
    cpp_img = cpp_core.wire_encode("int8", x)
    jit_img = qc.host_wire_encode(x)
    assert cpp_img == jit_img
    cpp_dec = cpp_core.wire_decode("int8", jit_img, n)
    jit_dec = qc.host_wire_decode(cpp_img, n)
    assert np.array_equal(cpp_dec.view(np.uint32),
                          jit_dec.view(np.uint32))


@pytest.mark.skipif(not cpp_core.available(),
                    reason="native core not built")
def test_wire_image_parity_zero_and_tiny_blocks():
    x = np.zeros(3 * 1024 + 100, np.float32)
    x[1024] = 2e-38          # tiny-but-normal absmax block
    x[2048:2060] = 5.0       # a normal block amid zeros
    assert cpp_core.wire_encode("int8", x) == qc.host_wire_encode(x)
    dec = qc.host_wire_decode(qc.host_wire_encode(x), x.size)
    assert np.all(np.isfinite(dec))


# ------------------------------------------------ Int8Compressor (API)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("shape", [(7,), (33, 31), (5, 7, 13), (2050,)])
def test_int8_compressor_property(shape, dtype):
    """Odd shapes and dtypes: compress/decompress keeps shape + dtype,
    stays finite, and the error respects the block quantization step."""
    x = jnp.asarray(_rand(shape, seed=len(shape)), dtype=dtype)
    c, ctx = Compression.int8.compress(x)
    out = Compression.int8.decompress(c, ctx)
    assert out.shape == x.shape and out.dtype == x.dtype
    xf = np.asarray(x, np.float32)
    of = np.asarray(out, np.float32)
    assert np.all(np.isfinite(of))
    # int8 grid error + one bf16 wire cast (~2^-8 relative).
    absmax = np.abs(xf).max()
    assert np.abs(of - xf).max() <= absmax * (0.5 / 127 + 2 ** -8) + 1e-6


def test_int8_compressor_all_zero_and_int_passthrough():
    z = jnp.zeros((3, 400), jnp.float32)
    c, ctx = Compression.int8.compress(z)
    assert np.all(np.asarray(Compression.int8.decompress(c, ctx)) == 0.0)
    ints = jnp.arange(12, dtype=jnp.int32)
    c, ctx = Compression.int8.compress(ints)
    assert ctx is None and c is ints


# ------------------------------------------------------ ring allreduce


def test_quantized_ring_matches_pmean(hvd):
    mesh = hvd.ranks_mesh()
    n = mesh.size
    x = _rand((n, 48, 128), seed=5)        # per-rank (48, 128), 3 tail
                                           # blocks per 8-rank chunk

    def body(xs):
        xs = xs[0]
        ring = qc.quantized_ring_allreduce(xs, "ranks", average=True)
        ref = lax.pmean(xs, "ranks")
        return ring, ref

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P("ranks"), out_specs=P()))
    ring, ref = f(x)
    # Per-hop requantization error grows ~linearly in hops; 5% covers
    # n=8 with margin (measured ~1.4%).
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                               rtol=0.05, atol=0.05 * np.abs(x).mean())
    assert not np.array_equal(np.asarray(ring), np.asarray(ref))


def test_reduce_gradients_int8_routes_by_policy(hvd, monkeypatch):
    """Under compression=int8 the bulk 2-D leaf rides the quantized ring
    (lossy) while the 1-D bias leaf stays on the raw pmean path
    (bit-identical to the uncompressed reduce)."""
    from horovod_tpu.jax.spmd import reduce_gradients
    monkeypatch.setenv("HOROVOD_TPU_INJIT_INT8_FLOOR", "0")
    mesh = hvd.ranks_mesh()
    n = mesh.size
    grads = {"w": _rand((n, 32, 64), seed=1), "b": _rand((n, 64), seed=2)}

    def body(g):
        g = jax.tree.map(lambda a: a[0], g)
        red = reduce_gradients(g, ("ranks",), average=True,
                               compression=Compression.int8)
        raw = reduce_gradients(g, ("ranks",), average=True)
        return red, raw

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P("ranks"), out_specs=P()))
    red, raw = f(grads)
    # 1-D leaf: ineligible -> bit-identical to the raw path.
    assert np.array_equal(np.asarray(red["b"]), np.asarray(raw["b"]))
    # 2-D leaf: quantized -> close but not bit-identical.  atol tracks
    # the quantization step, which scales with the block absmax of the
    # summed gradient (~n^0.5), not the element magnitude.
    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(raw["w"]),
                               rtol=0.05, atol=0.05)
    assert not np.array_equal(np.asarray(red["w"]), np.asarray(raw["w"]))


def test_compression_none_reduce_is_bit_identical(hvd, monkeypatch):
    """Guard: the int8 machinery must not perturb the default path —
    reduce_gradients(compression=none) == plain pmean, bitwise."""
    monkeypatch.delenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", raising=False)
    from horovod_tpu.jax.spmd import reduce_gradients
    mesh = hvd.ranks_mesh()
    n = mesh.size
    grads = {"w": _rand((n, 16, 80), seed=3), "b": _rand((n, 80), seed=4)}

    def body(g):
        g = jax.tree.map(lambda a: a[0], g)
        red = reduce_gradients(g, ("ranks",), average=True,
                               compression=NoneCompressor)
        ref = jax.tree.map(lambda a: lax.pmean(a, "ranks"), g)
        return red, ref

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P("ranks"), out_specs=P()))
    red, ref = f(grads)
    for a, b in zip(jax.tree.leaves(red), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------- policy knobs


def test_int8_eligibility_policy(monkeypatch):
    monkeypatch.delenv("HOROVOD_TPU_INJIT_INT8_FLOOR", raising=False)
    floor = qc.DEFAULT_INT8_FLOOR_BYTES
    assert qc.int8_eligible((256, 64), jnp.float32)          # 64 KiB
    assert not qc.int8_eligible((256, 63), jnp.float32)      # under floor
    assert not qc.int8_eligible((1 << 20,), jnp.float32)     # 1-D
    assert not qc.int8_eligible((256, 64), jnp.int32)        # not float
    monkeypatch.setenv("HOROVOD_TPU_INJIT_INT8_FLOOR", "0")
    assert qc.int8_floor_bytes() == 0
    assert qc.int8_eligible((2, 2), jnp.float32)
    assert qc.int8_eligible((4, 4), jnp.float32,
                            floor_bytes=floor) is False


def test_wire_dtype_env_fills_default_only(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", "int8")
    assert qc.resolve_injit_compression(NoneCompressor) is Compression.int8
    # Explicit argument wins over the env knob.
    assert qc.resolve_injit_compression(
        Compression.bf16) is Compression.bf16
    monkeypatch.setenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", "bf16")
    assert qc.resolve_injit_compression(NoneCompressor) is Compression.bf16
    monkeypatch.setenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", "none")
    assert qc.resolve_injit_compression(NoneCompressor) is NoneCompressor
    monkeypatch.setenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", "int4")
    with pytest.raises(ValueError, match="INJIT_WIRE_DTYPE"):
        qc.resolve_injit_compression(NoneCompressor)


def test_compression_accepts_wire_dtype_names(monkeypatch):
    """The in-jit surface takes the same string names as the eager
    ``hvd.allreduce(compression=...)``; an explicit ``"none"`` pins the
    raw wire even when the env asks for int8."""
    monkeypatch.delenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", raising=False)
    assert qc.resolve_injit_compression("int8") is Compression.int8
    assert qc.resolve_injit_compression("bf16") is Compression.bf16
    assert qc.resolve_injit_compression("fp16") is Compression.fp16
    assert qc.resolve_injit_compression("none") is NoneCompressor
    monkeypatch.setenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", "int8")
    assert qc.resolve_injit_compression("none") is NoneCompressor
    with pytest.raises(ValueError, match="int4"):
        qc.resolve_injit_compression("int4")


# -------------------------------------------------------- wire metrics


def test_estimate_wire_plan_and_counters(monkeypatch):
    monkeypatch.delenv("HOROVOD_TPU_INJIT_WIRE_DTYPE", raising=False)
    monkeypatch.delenv("HOROVOD_TPU_INJIT_INT8_FLOOR", raising=False)
    n = 8
    tree = {"w": jnp.zeros((512, 128), jnp.float32),   # 256 KiB: int8
            "b": jnp.zeros((128,), jnp.float32)}       # 1-D: raw
    plan = qc.estimate_wire_plan(tree, n, Compression.int8)
    chunk = -(-(-(-(512 * 128) // n)) // qc.BLOCK_ELEMS) * qc.BLOCK_ELEMS
    assert plan["int8"] == 2 * (n - 1) * (chunk + chunk // 1024 * 4)
    assert plan["fp32"] == 2 * (n - 1) * 128 * 4 // n
    # bf16 wire: everything floating casts down, no int8 key.
    plan = qc.estimate_wire_plan(tree, n, Compression.bf16)
    assert set(plan) == {"bf16"}
    assert plan["bf16"] == 2 * (n - 1) * (512 * 128 + 128) * 2 // n
    # n=1: nothing moves.
    assert qc.estimate_wire_plan(tree, 1, Compression.int8) == {}

    from horovod_tpu.metrics import registry
    before = registry.snapshot()["counters"]
    qc.record_wire_plan({"int8": 1000, "fp32": 64}, steps=3)
    after = registry.snapshot()["counters"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("injit.bytes#wire_dtype=int8") == 3000
    assert delta("injit.bytes#wire_dtype=fp32") == 192
    # The steps are counted where the step is dispatched, on every mesh
    # size (the next test; tests/test_spans.py on one device).
    assert delta("injit.steps") == 0


def test_make_train_step_records_injit_bytes(hvd, monkeypatch):
    """The compiled train step folds its wire plan into the metrics
    registry at dispatch time (Pallas interpret-mode end to end)."""
    import optax
    from horovod_tpu.jax.spmd import make_train_step
    monkeypatch.setenv("HOROVOD_TPU_INJIT_INT8_FLOOR", "0")
    mesh = hvd.ranks_mesh()
    n = mesh.size

    def loss_fn(params, aux, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2), aux

    params = {"w": jnp.asarray(_rand((16, 8), seed=9))}
    opt = optax.sgd(0.01)
    step = make_train_step(loss_fn, opt, mesh,
                           compression=Compression.int8)
    x = _rand((n * 4, 16), seed=10)
    y = _rand((n * 4, 8), seed=11)

    from horovod_tpu.metrics import registry
    before = registry.snapshot()["counters"]
    params, aux, opt_state, loss = step(params, {}, opt.init(params),
                                        (x, y))
    assert np.isfinite(float(loss))
    after = registry.snapshot()["counters"]
    key = "injit.bytes#wire_dtype=int8"
    assert after.get(key, 0) > before.get(key, 0)
    assert after.get("injit.steps", 0) == before.get("injit.steps", 0) + 1
