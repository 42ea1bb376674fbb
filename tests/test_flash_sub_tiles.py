"""Flash attention's backward pair blocked over adjacent heads, and its
diagonal block pairs cut into sub-tiles, interpreted on the CPU: the
drivers called directly at sizes the interpreter can afford, against the
per-head pair (exactly) and against ``full_attention``'s gradients.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel.ring_attention import full_attention

from test_flash_attention import make_qkv


def packed_problem(seed, B, T, H, D, qkv, seq_len=None, block=8,
                   causal=True):
    """Operands of the packed backward drivers as the custom-VJP rules
    hand them over: (q, k, v, o, lse, do), head bases."""
    from horovod_tpu.ops import flash_attention as fa

    scale = 1.0 / D ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    if qkv:
        base = (0, H, 2 * H)
        q = k = v = jax.random.normal(ks[0], (B, T, 3 * H * D))
    else:
        base = (0, 0, 0)
        q, k, v = (x.reshape(B, T, H * D)
                   for x in make_qkv(ks[0], B, T, H, D))
    plan = fa._Plan("grid", 0, 0, "per_head", 0, 0, 0.0)
    o, lse = fa._fwd_packed(q, k, v, H, D, plan, scale=scale, causal=causal,
                            block_q=block, block_k=block, interpret=True,
                            seq_len=seq_len, head_base=base)
    do = jax.random.normal(ks[1], o.shape)
    return (q, k, v, o, lse, do), base, plan, scale


class TestHeadGroupBwd:
    """The pair blocked over adjacent heads (contiguous group*D-wide
    tiles) against the per-head pair, which the classes above hold to the
    dense oracle: per-head math is identical, so the gradients must match
    EXACTLY.  _plan selects the grouped pair only at 1024² blocks, which
    no interpreted test can afford, so the driver is called directly at
    the small shapes."""

    @pytest.mark.parametrize("qkv,seq_len,H,group", [
        (False, None, 4, 2), (False, 24, 2, 2), (True, None, 4, 2),
        (True, 24, 4, 2), (True, None, 4, 4)],
        ids=["qkv_apart", "qkv_apart-padded", "fused_qkv",
             "fused_qkv-padded", "fused_qkv-group4"])
    def test_grouped_matches_per_head_exactly(self, hvd, qkv, seq_len, H,
                                              group):
        from horovod_tpu.ops import flash_attention as fa

        ops, base, plan, scale = packed_problem(41, 2, 32, H, 128, qkv,
                                                seq_len)
        kw = dict(scale=scale, causal=True, block_q=8, block_k=8,
                  interpret=True, seq_len=seq_len, head_base=base)
        want = fa._bwd_pallas_packed(*ops, H, 128, plan, **kw)
        got = fa._bwd_pallas_packed_grouped(*ops, H, 128, group, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("seq_len", [None, 24], ids=["whole", "padded"])
    def test_grouped_matches_oracle(self, hvd, seq_len):
        """And against ``full_attention`` itself."""
        from horovod_tpu.ops import flash_attention as fa

        B, T, H, D = 1, 32, 2, 128
        (q, k, v, o, lse, _), base, _, scale = packed_problem(
            45, B, T, H, D, False, seq_len)
        n = seq_len or T

        def loss_full(q, k, v):
            return (full_attention(*(x.reshape(B, T, H, D)[:, :n]
                                     for x in (q, k, v)),
                                   causal=True) ** 2).sum()

        want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        valid = (jnp.arange(T) < n)[None, :, None]
        do = jnp.where(valid, 2 * o, 0.0)      # d(sum o^2) on real rows
        got = fa._bwd_pallas_packed_grouped(
            q, k, v, o, lse, do, H, D, 2, scale=scale, causal=True,
            block_q=8, block_k=8, interpret=True, seq_len=seq_len,
            head_base=base)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)


# The grouped pair with its diagonal block pairs cut into sub-tiles
# (block, requested sub-tile, T, seq_len): 2, 4 and 8 sub-tiles a block
# side, one and several blocks a row, and the padding's end inside a
# sub-tile on the diagonal, inside an interior block, and on a block edge.
SUB_TILE_CASES = {
    "2_a_side-one_block": (32, 16, 32, None),
    "4_a_side-one_block": (32, 8, 32, None),
    "4_a_side-three_blocks": (32, 8, 96, None),
    "8_a_side-two_blocks": (64, 8, 128, None),
    "ends_in_diagonal_sub_tile": (32, 8, 96, 90),
    "ends_in_interior_block": (32, 8, 96, 50),
    "ends_on_block_edge": (32, 8, 96, 64),
    # 16 does not divide 24: no sub-tile, the whole-block bodies.
    "sub_tile_does_not_divide": (24, 16, 72, None),
}


@functools.cache
def whole_block_pair(qkv, block, T, seq_len):
    """The operands at one shape of ``SUB_TILE_CASES`` and the grouped
    pair's gradients with WHOLE blocks — which are the per-head pair's bit
    for bit, asserted here: what every case of that shape reads, made once a
    shape (the drivers are lowered anew at every eager call; two sub-tile
    widths of one block share a shape)."""
    from horovod_tpu.ops import flash_attention as fa

    H, D = 2, 128
    ops, base, plan, scale = packed_problem(51, 1, T, H, D, qkv, seq_len,
                                            block=block)
    kw = dict(scale=scale, causal=True, block_q=block, block_k=block,
              interpret=True, seq_len=seq_len, head_base=base)
    per_head = fa._bwd_pallas_packed(*ops, H, D, plan, **kw)
    whole = fa._bwd_pallas_packed_grouped(*ops, H, D, 2, **kw)
    for w, p in zip(whole, per_head):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(p))
    return ops, kw, whole


class TestDiagonalSubTiles:
    """Only products whose every element the causal mask sets to zero are
    left out, so the gradients are those of the per-head pair and of
    ``full_attention`` up to the order of the float32 sums."""

    @pytest.mark.parametrize("case", sorted(SUB_TILE_CASES))
    @pytest.mark.parametrize("qkv", [False, True],
                             ids=["qkv_apart", "fused_qkv"])
    def test_matches_per_head_and_oracle(self, hvd, case, qkv):
        from horovod_tpu.ops import flash_attention as fa

        block, want_sub, T, seq_len = SUB_TILE_CASES[case]
        sub = fa._diag_sub(True, block, block, want_sub)
        assert sub == (0 if "not_divide" in case else want_sub)
        B, H, D = 1, 2, 128
        ops, kw, whole = whole_block_pair(qkv, block, T, seq_len)

        def sub_tile_pair(*ops):
            return fa._bwd_pallas_packed_grouped(*ops, H, D, 2, sub=sub,
                                                 **kw)

        if sub:
            # One program for both cotangents below: one lowering of the
            # two kernels, not two.
            sub_tile_pair = jax.jit(sub_tile_pair)
        got = sub_tile_pair(*ops)
        for g, w in zip(got, whole):
            if sub:
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           rtol=2e-5, atol=2e-5)
            else:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if qkv:
            return
        # Against the oracle: forward value, then dq, dk, dv of sum(o^2).
        q, k, v, o, lse, _ = ops
        n = seq_len or T

        def heads(x):
            return x.reshape(B, T, H, D)[:, :n]

        def oracle(q, k, v):
            dense = full_attention(heads(q), heads(k), heads(v), causal=True)
            return (dense ** 2).sum(), dense

        (_, dense), want = jax.jit(jax.value_and_grad(
            oracle, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(heads(o)), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5)
        valid = (jnp.arange(T) < n)[None, :, None]
        got = sub_tile_pair(q, k, v, o, lse, jnp.where(valid, 2 * o, 0.0))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-4)

    def test_non_causal_is_unchanged(self, hvd):
        """No mask, no diagonal: ``_diag_sub`` answers 0 and the pair is
        the per-head pair's, bit for bit."""
        from horovod_tpu.ops import flash_attention as fa

        assert fa._diag_sub(False, 32, 32, 8) == 0
        ops, base, plan, scale = packed_problem(52, 1, 64, 2, 128, True,
                                                block=32, causal=False)
        kw = dict(scale=scale, causal=False, block_q=32, block_k=32,
                  interpret=True, seq_len=None, head_base=base)
        want = fa._bwd_pallas_packed(*ops, 2, 128, plan, **kw)
        got = fa._bwd_pallas_packed_grouped(*ops, 2, 128, 2, sub=0, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("causal,block_q,block_k,sub,want", [
        (True, 1024, 1024, 256, 256), (True, 1024, 1024, 512, 512),
        (False, 1024, 1024, 256, 0),      # nothing is masked
        (True, 1024, 512, 256, 0),        # the diagonal is not qi == kj
        (True, 768, 768, 512, 0),         # 512 does not divide the block
        (True, 256, 256, 256, 0)],        # one sub-tile is the block
        ids=["cell", "sub_512", "non_causal", "oblong_blocks",
             "does_not_divide", "one_sub_tile"])
    def test_diag_sub_rule(self, causal, block_q, block_k, sub, want):
        from horovod_tpu.ops import flash_attention as fa

        assert fa._diag_sub(causal, block_q, block_k, sub) == want

    def test_whole_model_through_the_sub_tile_pair(self, hvd, monkeypatch):
        """The rules hand the plan's sub-tile to the pair: with the plan
        steered to the grouped pair at a size the interpreter can afford,
        ``jax.grad`` of ``flash_attention_qkv`` is the oracle's."""
        from horovod_tpu.ops import flash_attention as fa

        plan = fa._plan
        monkeypatch.setattr(fa, "_plan", lambda **seen: plan(**seen)._replace(
            bwd="grouped", bwd_sub=8))
        B, T, H, D = 1, 64, 2, 128
        qkv = jax.random.normal(jax.random.PRNGKey(53), (B, T, 3 * H * D))

        def loss(qkv):
            return (fa.flash_attention_qkv(qkv, H, causal=True, block_q=32,
                                           block_k=32, interpret=True)
                    ** 2).sum()

        def loss_full(qkv):
            q, k, v = (x.reshape(B, T, H, D)
                       for x in jnp.split(qkv, 3, axis=-1))
            return (full_attention(q, k, v, causal=True) ** 2).sum()

        jax.clear_caches()        # the steered plan must be asked
        np.testing.assert_allclose(np.asarray(jax.grad(loss)(qkv)),
                                   np.asarray(jax.grad(loss_full)(qkv)),
                                   rtol=2e-4, atol=2e-4)
