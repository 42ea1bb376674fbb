"""Hardware compile coverage for the flash kernels (real TPU only).

The rest of the suite runs the kernels in interpret mode on the CPU mesh;
Mosaic's tiling constraints (narrow (block_q, 8) lse blocks, padded
ragged lengths, the mask-elision dual paths) are only truly exercised by
a hardware compile.  Run with::

    HOROVOD_TPU_TEST_REAL_TPU=1 python -m pytest tests/test_flash_tpu.py

The env var only takes effect when this file is named explicitly on the
command line (the rest of the suite assumes the 8-device virtual CPU
mesh).  Skipped automatically when no TPU backend is available.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a real TPU (set HOROVOD_TPU_TEST_REAL_TPU=1)")


def make_qkv(rng, B, T, H, D, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)


def _check_fwd_bwd(key, B, T, H, D, expect_fwd_kernel=None):
    """Shared compile-and-match body: flash forward vs the dense oracle,
    grad finiteness, and (optionally) WHICH forward kernel form the
    lowering selected — a fallback silently passing as the guarded form
    is exactly what a regression test must not do."""
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import full_attention

    q, k, v = make_qkv(jax.random.PRNGKey(key), B, T, H, D)
    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    if expect_fwd_kernel is not None:
        assert expect_fwd_kernel in fwd.lower(q, k, v).as_text(), (
            f"expected the {expect_fwd_kernel} forward form at "
            f"T={T}, D={D}; the gate stood it down")
    out = fwd(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)

    def loss(q):
        return (flash_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    g = jax.jit(jax.grad(loss))(q)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_fwd_bwd_compile_and_match_dense():
    _check_fwd_bwd(0, 1, 2048, 4, 64)


def test_fullunroll_t4096_grad_compiles():
    """T=4096, D=128: the fully-unrolled forward's Mosaic stack is
    ~44 MB here — over the 16 MB default scoped-VMEM budget — and only
    compiles through the raised per-kernel budget (round-5 regression:
    the sweep's 4096 row failed allocation until the budget landed).
    Asserts the fullunroll form is actually selected (the unrolled-KV
    fallback must not let a gate regression pass silently), checks the
    forward against the dense oracle, and runs the backward through the
    packed split pair at these blocks."""
    _check_fwd_bwd(5, 1, 4096, 2, 128,
                   expect_fwd_kernel="_fwd_kernel_fullunroll")


def test_auto_pad_prime_length_compiles():
    """T=4099 (prime): the auto-pad path must compile on Mosaic and match
    the dense oracle — including the ragged seq_len masking."""
    from horovod_tpu.ops.flash_attention import flash_attention_auto
    from horovod_tpu.parallel.ring_attention import full_attention

    q, k, v = make_qkv(jax.random.PRNGKey(1), 1, 4099, 2, 64)
    out = jax.jit(
        lambda q, k, v: flash_attention_auto(q, k, v, causal=True))(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_single_ragged_block_small_T():
    """A lone multiple-of-8 block (T=120 < 128) and the narrow lse output
    tile must compile on hardware (advisor r2 finding)."""
    from horovod_tpu.ops.flash_attention import flash_attention

    q, k, v = make_qkv(jax.random.PRNGKey(2), 2, 120, 2, 64)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=120, block_k=120))(q, k, v)
    assert out.shape == q.shape
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_packed_layout_compiles_and_matches():
    """D=128 routes through the head-packed kernels (head-offset
    BlockSpecs + unrolled-KV forward) — hardware Mosaic compile of the
    round-4 layout, checked against the dense oracle."""
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import full_attention

    q, k, v = make_qkv(jax.random.PRNGKey(4), 1, 1024, 2, 128)

    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_qkv_proj_fused_compiles_and_trains():
    """flash_qkv_proj (projection recomputed in backward) on hardware:
    value matches projecting then attending; gradient is finite."""
    from horovod_tpu.ops.flash_attention import flash_qkv_proj
    from horovod_tpu.parallel.ring_attention import full_attention

    B, T, H, D = 1, 512, 2, 128
    C = H * D
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, C), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(6), (C, 3 * C), jnp.float32)
         * 0.05)

    out = jax.jit(lambda x, w: flash_qkv_proj(x, w, H))(x, w)
    qkv = (x @ w.astype(x.dtype))
    q, k, v = (t.reshape(B, T, H, D) for t in jnp.split(qkv, 3, axis=-1))
    want = full_attention(q, k, v, causal=True).reshape(B, T, C)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)

    def loss(x, w):
        return (flash_qkv_proj(x, w, H).astype(jnp.float32) ** 2).sum()

    dx, dw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
    assert np.isfinite(np.asarray(dx, np.float32)).all()
    assert np.isfinite(np.asarray(dw)).all()


def test_unaligned_lane_block_T1000():
    """T=1000 runs as ONE 1000-wide (8-aligned, non-128-aligned) block —
    the configuration the round-3 advisor flagged as CI-only; compile
    and match the oracle on real Mosaic."""
    from horovod_tpu.ops.flash_attention import auto_block, flash_attention
    from horovod_tpu.parallel.ring_attention import full_attention

    assert auto_block(1000) == 1000
    q, k, v = make_qkv(jax.random.PRNGKey(7), 1, 1000, 2, 64)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("B,T", [(2, 2048), (1, 4096)],
                         ids=["T2048", "T4096"])
def test_sub_tile_backward_matches_dense(B, T):
    """The grouped pair with its diagonal blocks cut into sub-tiles (what
    ``_plan`` selects at 1024² blocks, D 128, causal) against
    ``full_attention``: forward value and dq, dk, dv, each relative to the
    reference's largest magnitude (``chip_smoke.py``'s bounds)."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.parallel.ring_attention import full_attention

    H, D = 4, 128
    q, k, v = make_qkv(jax.random.PRNGKey(8), B, T, H, D)
    plan = fa._plan_for(q.reshape(B, T, H * D), H, D, (0, 0, 0), True,
                        1024, 1024, 1024, 1024, False)
    assert (plan.bwd, plan.bwd_sub) == ("grouped", fa._DIAG_SUB)

    def loss(attn):
        def f(q, k, v):
            out = attn(q, k, v, causal=True)
            return (out.astype(jnp.float32) ** 2).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = loss(fa.flash_attention)
    text = flash.lower(q, k, v).as_text()
    assert "_dq_kernel_grouped" in text and "_dkdv_kernel_grouped" in text
    (_, out), grads = flash(q, k, v)
    (_, want_out), want_grads = loss(full_attention)(q, k, v)

    def rel_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        return np.abs(got - want).max() / np.abs(want).max()

    assert rel_err(out, want_out) <= 2e-2
    for g, w in zip(grads, want_grads):
        assert rel_err(g, w) <= 4e-2
