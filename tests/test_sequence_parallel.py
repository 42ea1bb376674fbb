"""Sequence-parallel attention tests: ring attention and Ulysses all-to-all
must match single-device full attention exactly (same math, different
communication schedule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.ring_attention import (
    full_attention, inverse_zigzag_indices, ring_attention, zigzag_indices)
from horovod_tpu.parallel.ulysses import ulysses_attention


def make_qkv(rng, B, T, H, D, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def run_sharded(hvd, fn, q, k, v):
    mesh = hvd.ranks_mesh()
    body = shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "ranks"), P(None, "ranks"), P(None, "ranks")),
        out_specs=P(None, "ranks"), check_vma=False)
    return np.asarray(jax.jit(body)(q, k, v))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, hvd, causal):
        n = hvd.size()
        B, T, H, D = 2, 4 * n, 2, 8
        q, k, v = make_qkv(jax.random.PRNGKey(0), B, T, H, D)
        want = np.asarray(full_attention(q, k, v, causal=causal))
        got = run_sharded(
            hvd, lambda q, k, v: ring_attention(q, k, v, causal=causal),
            q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_single_block_per_rank(self, hvd):
        n = hvd.size()
        B, T, H, D = 1, n, 1, 4   # one position per rank
        q, k, v = make_qkv(jax.random.PRNGKey(1), B, T, H, D)
        want = np.asarray(full_attention(q, k, v, causal=True))
        got = run_sharded(
            hvd, lambda q, k, v: ring_attention(q, k, v, causal=True),
            q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_bf16_inputs_f32_accumulation(self, hvd):
        n = hvd.size()
        B, T, H, D = 1, 2 * n, 2, 8
        q, k, v = make_qkv(jax.random.PRNGKey(2), B, T, H, D, jnp.bfloat16)
        want = np.asarray(full_attention(q, k, v, causal=True),
                          dtype=np.float32)
        got = run_sharded(
            hvd, lambda q, k, v: ring_attention(q, k, v, causal=True),
            q, k, v).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)

    def test_grad_flows(self, hvd):
        """Ring attention must be differentiable (it sits inside training
        steps); gradient equals full attention's gradient."""
        n = hvd.size()
        B, T, H, D = 1, 2 * n, 1, 4
        q, k, v = make_qkv(jax.random.PRNGKey(3), B, T, H, D)
        mesh = hvd.ranks_mesh()

        def ring_loss(q, k, v):
            return (ring_attention(q, k, v, causal=True) ** 2).sum()

        body = shard_map(
            lambda q, k, v: jax.tree.map(
                lambda g: jax.lax.psum(g, "ranks") * 0 + g,   # keep sharded
                jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)),
            mesh=mesh,
            in_specs=(P(None, "ranks"),) * 3,
            out_specs=(P(None, "ranks"),) * 3, check_vma=False)
        gq, gk, gv = jax.jit(body)(q, k, v)

        def full_loss(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()
        wq, wk, wv = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(wq),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(wk),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(wv),
                                   rtol=1e-4, atol=1e-4)


class TestZigzagRingAttention:
    def zigzag(self, hvd, x):
        return x[:, zigzag_indices(hvd.size(), x.shape[1])]

    def unzigzag(self, hvd, x):
        return x[:, inverse_zigzag_indices(hvd.size(), x.shape[1])]

    def test_matches_full_attention(self, hvd):
        n = hvd.size()
        B, T, H, D = 2, 4 * n, 2, 8
        q, k, v = make_qkv(jax.random.PRNGKey(6), B, T, H, D)
        want = np.asarray(full_attention(q, k, v, causal=True))
        got = run_sharded(
            hvd,
            lambda q, k, v: ring_attention(q, k, v, causal=True,
                                           layout="zigzag"),
            self.zigzag(hvd, q), self.zigzag(hvd, k), self.zigzag(hvd, v))
        np.testing.assert_allclose(self.unzigzag(hvd, got), want,
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches_full_attention(self, hvd):
        n = hvd.size()
        B, T, H, D = 1, 2 * n, 1, 4
        q, k, v = make_qkv(jax.random.PRNGKey(7), B, T, H, D)
        mesh = hvd.ranks_mesh()

        def zz_loss(q, k, v):
            return (ring_attention(q, k, v, causal=True,
                                   layout="zigzag") ** 2).sum()

        body = shard_map(
            lambda q, k, v: jax.grad(zz_loss, argnums=(0, 1, 2))(q, k, v),
            mesh=mesh, in_specs=(P(None, "ranks"),) * 3,
            out_specs=(P(None, "ranks"),) * 3, check_vma=False)
        grads = jax.jit(body)(*(self.zigzag(hvd, t) for t in (q, k, v)))

        def full_loss(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()
        wants = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(grads, wants):
            np.testing.assert_allclose(
                self.unzigzag(hvd, np.asarray(got)), np.asarray(want),
                rtol=1e-4, atol=1e-4)

    def test_wall_clock_ab(self, hvd):
        """The A/B that motivates the layout: at compute-dominated sizes the
        balanced half-work schedule beats the dense-masked contiguous one
        (observed ~1.5x on the 8-device host platform; asserted loosely to
        tolerate timer noise)."""
        import os
        import time

        if (os.cpu_count() or 0) < 8:
            pytest.skip("8 virtual devices need >= 8 cores for timing to "
                        "mean anything")

        n = hvd.size()
        B, T, H, D = 1, 128 * n, 8, 64
        q, k, v = make_qkv(jax.random.PRNGKey(8), B, T, H, D)
        mesh = hvd.ranks_mesh()

        def build(layout):
            body = shard_map(
                lambda q, k, v: ring_attention(q, k, v, causal=True,
                                               layout=layout),
                mesh=mesh, in_specs=(P(None, "ranks"),) * 3,
                out_specs=P(None, "ranks"), check_vma=False)
            return jax.jit(body).lower(q, k, v).compile()

        clock = {}
        for layout in ("contiguous", "zigzag"):
            compiled = build(layout)
            compiled(q, k, v)[0].block_until_ready()   # warm
            samples = []
            for _ in range(4):
                t0 = time.perf_counter()
                compiled(q, k, v)[0].block_until_ready()
                samples.append(time.perf_counter() - t0)
            # Best-of-N: the min is robust to scheduler noise.
            clock[layout] = min(samples)
        ratio = clock["zigzag"] / clock["contiguous"]
        print(f"ring-attention A/B: {clock} (zigzag/contiguous = "
              f"{ratio:.2f})")
        # Report-only (advisor r2): wall-clock ratios on a shared CI host
        # flake under concurrent load no matter how loose the bound — the
        # correctness of both layouts is asserted by the parity tests
        # above; the ratio is printed for humans, and no cell times the
        # two layouts on hardware yet (ROADMAP.md R3).


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, hvd, causal):
        n = hvd.size()
        B, T, H, D = 2, 2 * n, n, 4   # heads == ranks
        q, k, v = make_qkv(jax.random.PRNGKey(4), B, T, H, D)
        want = np.asarray(full_attention(q, k, v, causal=causal))
        got = run_sharded(
            hvd, lambda q, k, v: ulysses_attention(q, k, v, causal=causal),
            q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_multiple_heads_per_rank(self, hvd):
        n = hvd.size()
        B, T, H, D = 1, 2 * n, 2 * n, 4
        q, k, v = make_qkv(jax.random.PRNGKey(5), B, T, H, D)
        want = np.asarray(full_attention(q, k, v, causal=True))
        got = run_sharded(
            hvd, lambda q, k, v: ulysses_attention(q, k, v, causal=True),
            q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
