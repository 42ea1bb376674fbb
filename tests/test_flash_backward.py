"""Flash attention's backward kernels, interpreted on the CPU: the per-head
pair at heads off the lane width (the merged layout), and the ONE fused
kernel a KV group of a call with grouped KV heads and no map, against
``full_attention``'s gradients and against each other.  The pair grouped
over heads and its diagonal sub-tiles are ``test_flash_sub_tiles.py``'s; the
forward, the layouts and the plan ``test_flash_attention.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import full_attention

from test_flash_attention import make_qkv


class TestPallasBackward:
    """D off the lane width: the merged layout's grid forward and
    per-head pair."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense_oracle(self, hvd, causal):
        q, k, v = make_qkv(jax.random.PRNGKey(11), 2, 64, 2, 16)

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=16,
                                  block_k=16, interpret=True)
            return (out ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=causal) ** 2).sum()

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)

    def test_bf16_grads(self, hvd):
        q, k, v = make_qkv(jax.random.PRNGKey(12), 1, 64, 2, 16,
                           jnp.bfloat16)

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=32,
                                  block_k=32, interpret=True)
            return (out.astype(jnp.float32) ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=True)
                    .astype(jnp.float32) ** 2).sum()

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=1e-2, atol=1e-2)

    def test_uneven_blocks_pallas_bwd(self, hvd):
        q, k, v = make_qkv(jax.random.PRNGKey(13), 1, 48, 2, 8)

        def loss(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=16,
                                    block_k=8, interpret=True) ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)

    def test_padded_seq_len_grads(self, hvd):
        """Zero-padded inputs with seq_len masking: the backward pair
        must mask the padding tail."""
        T, T_pad = 40, 64
        q, k, v = make_qkv(jax.random.PRNGKey(14), 1, T, 2, 8)
        pad = [(0, 0), (0, T_pad - T), (0, 0), (0, 0)]

        def loss(q, k, v):
            out = flash_attention(
                jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                causal=True, block_q=16, block_k=16, interpret=True,
                seq_len=T)
            return (out[:, :T] ** 2).sum()

        def loss_full(q, k, v):
            return (full_attention(q, k, v, causal=True) ** 2).sum()

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)


class TestGroupedKvFusedBackward:
    """A call with grouped KV heads at lane-aligned heads and no map: the
    backward is ONE kernel a KV group (``flash_group_bwd``: the selected
    attention's fused kernel without its map) wherever ``_plan`` can see
    that a KV head's ``dK`` and ``dV`` fit VMEM; the per-head pair
    elsewhere.  Both against ``full_attention``'s gradients, and against
    each other."""

    B, T, D, BLOCK = 1, 64, 128, 16

    def _problem(self, kv_rep, hkv=2):
        ks = jax.random.split(jax.random.PRNGKey(61 + kv_rep), 3)
        return tuple(
            jax.random.normal(key, (self.B, self.T, h, self.D))
            for key, h in zip(ks, (hkv * kv_rep, hkv, hkv)))

    def _grads(self, q, k, v, causal, seq_len):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=self.BLOCK,
                                  block_k=self.BLOCK, interpret=True,
                                  seq_len=seq_len)
            return (out[:, :seq_len] ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @staticmethod
    def _equations(jaxpr):
        """Every equation of a jaxpr, nested jaxprs included."""
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for v in value if isinstance(value, (list, tuple)) else [
                        value]:
                    v = getattr(v, "jaxpr", v)
                    if hasattr(v, "eqns"):
                        yield from TestGroupedKvFusedBackward._equations(v)

    def _kernels(self, jaxpr):
        """``{name: kernel jaxpr}`` of every ``pallas_call`` of a jaxpr."""
        return {eqn.params["name"]
                or eqn.params["jaxpr"].debug_info.func_name:
                eqn.params["jaxpr"] for eqn in self._equations(jaxpr)
                if eqn.primitive.name == "pallas_call"}

    def _backward_kernels(self, q, k, v, block=16):
        """The names of the backward's kernels (the forward's left out)."""
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=True).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
        return {name: body for name, body in self._kernels(
            jaxpr.jaxpr).items() if "fwd" not in name}

    @pytest.mark.parametrize("seq_len", [None, 40], ids=["whole", "padded"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "non_causal"])
    @pytest.mark.parametrize("kv_rep,hkv", [(2, 2), (4, 2), (16, 1)],
                             ids=["2Q_per_KV", "4Q_per_KV", "16Q_per_KV"])
    def test_fused_matches_oracle(self, hvd, kv_rep, hkv, causal, seq_len):
        q, k, v = self._problem(kv_rep, hkv)
        n = seq_len or self.T

        def loss_full(q, k, v):
            k, v = (jnp.repeat(a[:, :n], kv_rep, axis=2) for a in (k, v))
            return (full_attention(q[:, :n], k, v, causal=causal) ** 2).sum()

        assert set(self._backward_kernels(q, k, v)) == {"flash_group_bwd"}
        got = self._grads(q, k, v, causal, seq_len)
        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        assert got[1].shape == got[2].shape == (self.B, self.T, hkv, self.D)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("seq_len", [None, 40], ids=["whole", "padded"])
    @pytest.mark.parametrize("kv_rep,hkv", [(2, 2), (4, 2), (16, 1)],
                             ids=["2Q_per_KV", "4Q_per_KV", "16Q_per_KV"])
    def test_per_head_pair_agrees(self, hvd, monkeypatch, kv_rep, hkv,
                                  seq_len):
        """The same call where the device backs no budget above Mosaic's
        default: the per-head pair, to float32 reassociation."""
        from horovod_tpu.ops import _pallas

        q, k, v = self._problem(kv_rep, hkv)
        fused = self._grads(q, k, v, True, seq_len)
        # (Every family's probe at once; the plan is asked outside any
        # shared trace, so no cache holds the fused form.)
        monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: False)
        assert set(self._backward_kernels(q, k, v)) == {"_dq_kernel",
                                                        "_dkdv_kernel"}
        pair = self._grads(q, k, v, True, seq_len)
        for g, w in zip(fused, pair):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-5)

    def test_bf16_operands_f32_sums(self, hvd):
        """The pair's precision: bfloat16 operands into every product,
        float32 results, ``p`` and ``dS`` cast once a head."""
        q, k, v = (a.astype(jnp.bfloat16) for a in self._problem(4))
        kernel = self._backward_kernels(q, k, v, block=64)["flash_group_bwd"]
        products = [eqn for eqn in self._equations(kernel)
                    if eqn.primitive.name == "dot_general"]
        # A masked and an unmasked body, each five products for each of the
        # four heads of a group (the pair's two kernels form seven).
        assert len(products) == 2 * 5 * 4
        for eqn in products:
            assert all(v_.aval.dtype == jnp.bfloat16 for v_ in eqn.invars)
            assert eqn.outvars[0].aval.dtype == jnp.float32
