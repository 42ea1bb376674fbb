"""The flash family at values narrower than keys (latent attention's heads
of 192 = 128 | 64 against values of 128): ``flash_attention`` against the
dense oracle — output, dq, dk, dv — in both backward forms ``_plan`` can
take, the resident forward (PR 51) against the grid form and the oracle —
at two widths and, under grouped KV heads, at one (PR 60) —, the plan's
rows for such a call, and every row of the table the other
calls read unchanged.  Interpreted kernels at the smallest T that tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.ring_attention import full_attention

from test_flash_attention import PLAN_TABLE, observed


def operands(B, T, H, Hkv, D, Dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H, D)),
            jax.random.normal(ks[1], (B, T, Hkv, D)),
            jax.random.normal(ks[2], (B, T, Hkv, Dv)),
            jax.random.normal(ks[3], (B, T, H, Dv)))


# (B, T, H, Hkv, D, Dv, block, headroom): the cell's head at the smallest
# size, in the fused backward (a device that backs the budget) and in the
# per-head pair (one that does not); two query heads a KV head; values
# WIDER than keys; widths off the lane on both sides (24 and 16 are both
# padded to 128: one width by then, and the plan every other call has).
@pytest.mark.parametrize("B,T,H,Hkv,D,Dv,block,headroom", [
    (1, 64, 2, 2, 192, 128, 32, True), (1, 64, 2, 2, 192, 128, 32, False),
    (2, 64, 4, 2, 192, 128, 32, True), (1, 64, 2, 1, 192, 128, 32, False),
    (1, 64, 2, 2, 128, 256, 32, True), (1, 64, 2, 2, 24, 16, 32, True)],
    ids=["cell_fused", "cell_pair", "grouped_kv_fused", "grouped_kv_pair",
         "values_wider", "off_the_lanes"])
def test_values_narrower_than_keys_equal_full_attention(
        monkeypatch, B, T, H, Hkv, D, Dv, block, headroom):
    monkeypatch.setattr(fa._pallas, "vmem_headroom_ok", lambda: headroom)
    q, k, v, w = operands(B, T, H, Hkv, D, Dv)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def flash(q, k, v):
        out = fa.flash_attention(q, k, v, block_q=block, block_k=block,
                                 interpret=True)
        return (out * w).sum(), out

    def dense(q, k, v):
        out = full_attention(q, jnp.repeat(k, H // Hkv, axis=2),
                             jnp.repeat(v, H // Hkv, axis=2), causal=True)
        return (out * w).sum(), out

    (_, out), got = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want_out), want = jax.value_and_grad(dense, (0, 1, 2),
                                             has_aux=True)(q, k, v)
    assert out.shape == (B, T, H, Dv)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4)
    if D + -D % 128 != Dv + -Dv % 128:
        assert {(p.fwd, p.bwd) for p in plans} == {
            ("grid", "group_fused" if headroom else "per_head")}


# (T, block, causal, seq_len, chain rows): the resident forward — the head's
# K and V rows in VMEM, a rolled loop over the live tiles, the Q block in
# chains — at lane-wide tiles: the causal triangle in four chains, in two
# and in one; a padded tail that leaves the last Q block partly dead (the
# masked loop, and a tile wholly in the padding); no mask at all; padding
# alone.
@pytest.mark.parametrize("T,block,causal,seq_len,rows", [
    (256, 128, True, None, 32), (256, 128, True, None, 64),
    (256, 128, True, None, 128), (384, 128, True, 300, 32),
    (256, 128, False, None, 32), (256, 128, False, 200, 64),
    (256, 256, True, None, 64)],
    ids=["causal_4_chains", "causal_2_chains", "causal_1_chain",
         "causal_padded_tail", "no_mask", "padding_alone", "one_q_block"])
def test_resident_forward_equals_the_grid_form_and_full_attention(
        monkeypatch, T, block, causal, seq_len, rows):
    monkeypatch.setattr(fa, "_RESIDENT_CHAIN_ROWS", rows)
    q, k, v, _ = operands(1, T, 2, 2, 192, 128)
    n = seq_len or T
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])
    out = fa.flash_attention(q, k, v, causal=causal, block_q=block,
                             block_k=block, seq_len=seq_len, interpret=True)
    assert {(p.fwd, p.fwd_tile, p.fwd_vmem_mb) for p in plans} == {
        ("resident", rows, 64)}
    want = full_attention(q[:, :n], k[:, :n], v[:, :n], causal=causal)
    np.testing.assert_allclose(out[:, :n], want, rtol=2e-5, atol=2e-5)

    # o AND lse against the grid form on the same packed, padded operands.
    packed = [fa._pad_lanes(a).reshape(1, T, -1) for a in (q, k, v)]
    forms = {
        fwd: fa._fwd_packed(
            *packed, 2, 256, plans[0]._replace(fwd=fwd), scale=192 ** -0.5,
            causal=causal, block_q=block, block_k=block, interpret=True,
            seq_len=seq_len, Dv=128)
        for fwd in ("resident", "grid", "grid_live")}
    o, lse = forms["resident"]
    assert o.shape == (1, T, 2 * 128) and lse.shape == (1, 2, T)
    for other in ("grid", "grid_live"):
        np.testing.assert_allclose(o[:, :n], forms[other][0][:, :n],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(lse[..., :n], forms[other][1][..., :n],
                                   rtol=1e-6, atol=1e-5)
    # The control shares the grid form's arithmetic to the bit.
    assert (forms["grid"][0] == forms["grid_live"][0]).all()


def test_resident_forward_at_grouped_kv_heads_and_its_gradients(monkeypatch):
    """Two query heads a KV head: the rows of a KV head are fetched for
    both, and the backward reads the ``o`` and ``lse`` the form wrote."""
    monkeypatch.setattr(fa, "_RESIDENT_CHAIN_ROWS", 64)
    q, k, v, w = operands(1, 256, 4, 2, 192, 128)

    def flash(q, k, v):
        return (fa.flash_attention(q, k, v, block_q=128, block_k=128,
                                   interpret=True) * w).sum()

    def dense(q, k, v):
        return (full_attention(q, jnp.repeat(k, 2, axis=2),
                               jnp.repeat(v, 2, axis=2), causal=True)
                * w).sum()

    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4)


# (T, block, causal, seq_len, chain rows, H, Hkv): the resident forward at ONE
# width under grouped KV heads (PR 60), past the fully-unrolled form's reach
# (brought down to 0 here): the causal triangles in four chains and in one
# at four and eight query heads a KV head, a padded tail, no mask.
@pytest.mark.parametrize("T,block,causal,seq_len,rows,H,Hkv", [
    (256, 128, True, None, 32, 4, 1), (256, 128, True, None, 128, 8, 1),
    (384, 128, True, 300, 32, 4, 2), (256, 128, False, None, 64, 2, 1)],
    ids=["causal_4_chains_kv4", "causal_1_chain_kv8", "padded_tail_kv2",
         "no_mask_kv2"])
def test_resident_forward_at_one_width_and_its_gradients(
        monkeypatch, T, block, causal, seq_len, rows, H, Hkv):
    """The plan gives the call the resident forward; its output equals full
    attention's, its ``o`` and ``lse`` the grid form's on the same operands,
    and its gradients — through ``flash_group_bwd``, which reads the ``o``
    and ``lse`` this form wrote — the grid call's and the oracle's."""
    monkeypatch.setattr(fa, "_FULL_UNROLL_MAX_T", 0)
    monkeypatch.setattr(fa, "_RESIDENT_CHAIN_ROWS", rows)
    q, k, v, w = operands(1, T, H, Hkv, 128, 128)
    n, rep = seq_len or T, H // Hkv
    plans = []
    planned = fa._plan
    monkeypatch.setattr(fa, "_plan", lambda **seen: plans.append(
        planned(**seen)) or plans[-1])

    def flash(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal, block_q=block,
                                 block_k=block, seq_len=seq_len,
                                 interpret=True)
        return (out[:, :n] * w[:, :n]).sum(), out

    def dense(q, k, v):
        out = full_attention(q[:, :n], jnp.repeat(k[:, :n], rep, axis=2),
                             jnp.repeat(v[:, :n], rep, axis=2), causal=causal)
        return (out * w[:, :n]).sum(), out

    grad = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)
    (_, out), got = grad(q, k, v)
    assert {(p.fwd, p.fwd_tile, p.fwd_vmem_mb, p.bwd) for p in plans} == {
        ("resident", rows, 64, "group_fused")}
    (_, want_out), want = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(
        q, k, v)
    np.testing.assert_allclose(out[:, :n], want_out, rtol=2e-5, atol=2e-5)
    packed = [a.reshape(1, T, -1) for a in (q, k, v)]
    (o, lse), (o_grid, lse_grid) = (
        fa._fwd_packed(*packed, H, 128, plans[0]._replace(fwd=fwd),
                       scale=128 ** -0.5, causal=causal, block_q=block,
                       block_k=block, interpret=True, seq_len=seq_len,
                       kv_rep=rep) for fwd in ("resident", "grid"))
    np.testing.assert_allclose(o[:, :n], o_grid[:, :n], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse[..., :n], lse_grid[..., :n], rtol=1e-6,
                               atol=1e-5)
    monkeypatch.setattr(fa, "_plan", lambda **seen: planned(**seen)._replace(
        fwd="grid", fwd_tile=0, fwd_vmem_mb=0))
    jax.clear_caches()
    _, grid = grad(q, k, v)
    jax.clear_caches()          # the traces do not key on the plan
    for g, g_grid, w_ in zip(got, grid, want):
        np.testing.assert_allclose(g, g_grid, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g[:, :n], w_[:, :n], rtol=2e-4, atol=2e-4)


def test_what_the_widths_must_agree_in():
    q, k, v, _ = operands(1, 64, 2, 2, 192, 128)
    with pytest.raises(ValueError, match="head width 192"):
        fa.flash_attention(q, k[..., :128], v, interpret=True)
    with pytest.raises(ValueError, match="batch, length and heads"):
        fa.flash_attention(q, k, v[:, :, :1], interpret=True)
    with pytest.raises(ValueError, match="one width"):
        fa.flash_attention(q, k, v, interpret=True,
                           select=jnp.ones((1, 64, 64), jnp.int8))


# What _plan answers a call whose values are not as wide as its keys (both
# in whole 128-lane tiles by then).  Forward: the head's K and V rows
# resident (PR 51) on a device that backs the budget where T (D + Dv) 2
# bytes fit 8 MiB — joyaiflash_1chip's call: 6 MiB, 1024 x 1024
# tiles in chains of 256 rows — at tiles of whole lanes to 1024, compiled
# Mosaic or interpreted off a mesh's manual axes; else the grid form.
# Backward: the one kernel a KV group where dK (T, D) and dV (T, Dv) float32
# fit 16 MiB on such a device — 12 MiB there — else the per-head pair.
RESIDENT = ("resident", 256, 64)
SPLIT_ROWS = {
    "cell_T8192_256_128": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128),
        (*RESIDENT, "group_fused", 64, 0, 0.889, (1024,) * 4)),
    "cell_no_headroom": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128,
                 vmem_headroom=False),
        ("grid", 0, 0, "per_head", 0, 0, 0.889, (1024,) * 4)),
    # 16,384 x (256 + 128) x 2 = 12 MiB of rows, x 4 = 24 MiB of gradients:
    # the grid form and the pair.
    "T16384_256_128": (
        observed(16384, D=256, H=32, base=(0, 0, 0), Dv=128),
        ("grid", 0, 0, "per_head", 0, 0, 0.941, (1024,) * 4)),
    "T2048_256_128": (
        observed(2048, D=256, H=32, base=(0, 0, 0), Dv=128),
        (*RESIDENT, "group_fused", 64, 0, 0.667, (1024,) * 4)),
    "T8192_4Q_per_KV_256_128": (
        observed(8192, D=256, H=8, base=(0, 0, 0), kv_rep=4, Dv=128),
        (*RESIDENT, "group_fused", 64, 0, 0.889, (1024, 1024, 512, 1024))),
    "values_wider_128_256": (
        observed(8192, D=128, H=32, base=(0, 0, 0), Dv=256),
        (*RESIDENT, "group_fused", 64, 0, 0.889, (1024,) * 4)),
    # Float32 operands: the rows are 12 MiB.
    "cell_float32": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128, itemsize=4),
        ("grid", 0, 0, "group_fused", 64, 0, 0.889, (1024,) * 4)),
    # One chain where 256 rows do not divide the Q block; tiles off the
    # lanes, and interpreted Pallas under shard_map, stay on the grid.
    "blocks_of_128": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128, blocks=128),
        ("resident", 128, 64, "group_fused", 64, 0, 0.985, (128,) * 4)),
    "blocks_of_384": (
        observed(1536, D=256, H=32, base=(0, 0, 0), Dv=128, blocks=384),
        ("resident", 384, 64, "group_fused", 64, 0, 0.801, (384,) * 4)),
    "blocks_off_the_lanes": (
        observed(64, D=256, H=2, base=(0, 0, 0), Dv=128, blocks=32),
        ("grid", 0, 0, "group_fused", 64, 0, 0.677, (32,) * 4)),
    "interpreted_under_shard_map": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128, interpret=True,
                 manual_axes=True),
        ("grid", 0, 0, "group_fused", 64, 0, 0.889, (1024,) * 4)),
}


@pytest.mark.parametrize("case", sorted(SPLIT_ROWS))
def test_plan_rows_of_a_call_with_two_widths(case):
    seen, want = SPLIT_ROWS[case]
    assert fa._plan(**seen) == fa._Plan(*want)


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_one_width_named_twice_is_the_row_it_was(case):
    """``Dv`` equal to ``D`` is every other call's plan, to the field."""
    seen, want = PLAN_TABLE[case]
    assert fa._plan(**seen, Dv=seen["D"]) == fa._Plan(*want)
