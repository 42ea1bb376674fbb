"""The flash family under a causal window (``mask=("window", W)``): the
kernels, interpreted, against the dense oracle under the window as a plain
boolean matrix — output, log-sum-exp and the three gradients, at one, six
and eight query heads a KV head, at windows smaller than, equal to and larger
than the tile, in the three forward forms and the two backward forms a call
can reach —, what ``_plan`` gives the benchmark's two attention shapes, the
block the shapes choose, the tiles visited against the tiles live and the
dead steps' index maps against the matrix, what is refused, and that a causal
call at six and eight query heads a KV head still lowers to the parent's text.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import _pallas, flash_attention as fa
from horovod_tpu.parallel.ring_attention import (
    _NEG_BIG, full_attention, window_allowed)

F32 = jnp.float32
GRID = {"_FULL_UNROLL_MAX_T": 0, "_UNROLL_KV_MAX_NK": 0}


def operands(T, H, Hkv, D=128, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((1, T, H, D), (1, T, Hkv, D), (1, T, Hkv, D), (1, T, H, D))
    return [jax.random.normal(k, s, F32) for k, s in zip(keys, shapes)]


def test_the_mask_by_its_sentence():
    """Query ``i`` reads key ``j`` iff ``0 <= i - j < W``: itself and the
    ``W - 1`` keys before it; ``W >= T`` is the causal mask."""
    m = np.asarray(window_allowed(12, 4))
    for i in range(12):
        assert np.flatnonzero(m[i]).tolist() == list(
            range(max(0, i - 3), i + 1))
    assert m.sum() == 4 * 5 // 2 + 8 * 4 == fa.window_pairs(12, 4)
    assert (np.asarray(window_allowed(12, 12)) == np.tri(12, dtype=bool)).all()


@pytest.mark.parametrize("name,T,W,H,Hkv,blk,limits,headroom,fwd,bwd", [
    ("fullunroll_mha_w_eq_tile", 64, 16, 2, 2, 16, {}, True, "fullunroll",
     "per_head"),
    ("grid_fused_kv8_w_lt_tile", 64, 8, 8, 1, 16, GRID, True, "grid",
     "group_fused"),
    ("grid_fused_kv6_w_eq_tile", 64, 16, 6, 1, 16, GRID, True, "grid",
     "group_fused"),
    ("unrollkv_kv2_w_gt_tile", 64, 24, 2, 1, 16,
     {"_FULL_UNROLL_MAX_T": 0}, True, "unrollkv", "group_fused"),
    ("grid_per_head_kv8_w_odd", 64, 21, 8, 1, 16, GRID, False, "grid",
     "per_head"),
    ("grid_per_head_mha_rect", 96, 20, 2, 2, (32, 16), GRID, True, "grid",
     "per_head"),
])
def test_kernels_against_the_dense_oracle(monkeypatch, name, T, W, H, Hkv,
                                          blk, limits, headroom, fwd, bwd):
    for limit, value in limits.items():
        monkeypatch.setattr(fa, limit, value)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: headroom)
    bq, bk = blk if isinstance(blk, tuple) else (blk, blk)
    q, k, v, do = operands(T, H, Hkv)
    mask, D = ("window", W), q.shape[-1]
    plan = fa._plan_for(q.reshape(1, T, -1), H, D, (0, 0, 0), fa.Window(W),
                        bq, bk, bq, bk, True, kv_rep=H // Hkv)
    assert (plan.fwd, plan.bwd) == (fwd, bwd), plan

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=bq,
                                  block_k=bk, interpret=True)

    def dense(q, k, v):
        rep = H // Hkv
        return full_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                              mask=mask)

    (out, grads), (want, want_grads) = (
        jax.jit(lambda *a, f=f: (f(*a), jax.grad(
            lambda *a: (f(*a) * do).sum(), (0, 1, 2))(*a)))(q, k, v)
        for f in (flash, dense))
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=5e-5)
    # The saved log-sum-exp, from the rule's forward half.
    _, (_, _, _, _, lse) = fa._flash_packed_fwd(
        q.reshape(1, T, -1), k.reshape(1, T, -1), v.reshape(1, T, -1), H,
        D ** -0.5, fa.Window(W), bq, bk, bq, bk, True, None)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, 2))
    logits = jnp.where(window_allowed(T, W), logits * D ** -0.5, _NEG_BIG)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1), atol=2e-5)


def test_causal_at_six_query_heads_a_kv_head(monkeypatch):
    """The global layers' call — the causal mask at ``kv_rep`` 6, which no
    other cell runs — through the grid forward and the one fused backward
    kernel a KV group, against the dense oracle."""
    for limit, value in GRID.items():
        monkeypatch.setattr(fa, limit, value)
    q, k, v, do = operands(64, 6, 1)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)

    def dense(q, k, v):
        return full_attention(q, jnp.repeat(k, 6, 2), jnp.repeat(v, 6, 2))

    got, want = (jax.jit(lambda *a, f=f: (f(*a), *jax.grad(
        lambda *a: (f(*a) * do).sum(), (0, 1, 2))(*a)))(q, k, v)
        for f in (flash, dense))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_plan_at_the_benchmark_s_shapes():
    """lagunaxs2_1chip's two calls: one sequence of 8,192, 8 KV heads of 128
    in bfloat16.  WINDOWED, 64 query heads under 512 keys a query: the block
    the shapes choose is ``auto_block``'s 1,024, as for the causal call;
    forward the grid form (a KV head's rows, 2 MiB, are past the resident
    forms' 1 MiB), backward the one kernel a KV group (dK and dV of 8,192
    rows are 8 MiB) at the 512 x 512 tiles eight heads a step allow; 31 of
    a KV head's 256 such tiles hold a live pair.  GLOBAL, 48 query heads
    under the causal mask at 1024²: the same two forms, the backward at 512
    x 512 (six heads a step).  Without
    the budget, and at one query head a KV head, the per-head pair — never
    the pair blocked over two heads under a window."""
    def plan(**over):
        fields = dict(T=8192, D=128, H=64, head_base=(0, 0, 0), itemsize=2,
                      causal=fa.Window(512), block_q=1024, block_k=1024,
                      bwd_block_q=1024, bwd_block_k=1024, interpret=False,
                      manual_axes=False, vmem_headroom=True, kv_rep=8)
        return fa._plan(**{**fields, **over})

    assert fa._mask_auto_block(8192, ("window", 512)) == 1024
    p = plan()
    assert (p.fwd, p.bwd, p.bwd_vmem_mb) == ("grid", "group_fused", 64)
    assert p.blocks == (1024, 1024, 512, 512)
    assert fa._bd_tiles(fa.Window(512), 8192, 512, 512) == 31
    assert p.bwd_live_share == round(
        fa.window_pairs(8192, 512) / (31 * 512 * 512), 3) == 0.5
    g = plan(H=48, kv_rep=6, causal=True)
    assert (g.fwd, g.bwd, g.blocks) == ("grid", "group_fused",
                                        (1024, 1024, 512, 512))
    assert plan(vmem_headroom=False).bwd == "per_head"
    assert plan(kv_rep=1).bwd == "per_head"
    assert plan(kv_rep=1, causal=True).bwd == "grouped"
    assert plan(T=4096).fwd == "fullunroll"


@pytest.mark.parametrize("rows,W,blk", [
    (8192, 512, 1024), (8192, 4096, 1024), (8192, 100, 1024), (8192, 64, 1024),
    (64, 16, 64), (2304, 512, 768), (4096, 1024, 1024)])
def test_the_block_is_chosen_from_shapes(rows, W, blk):
    """``auto_block``'s of the rows, whatever the window: the causal
    call's."""
    assert fa._mask_auto_block(rows, ("window", W)) == blk == fa.auto_block(
        rows)


@pytest.mark.parametrize("T,W,bq,bk", [
    (64, 16, 16, 16), (64, 8, 16, 16), (64, 24, 16, 16), (64, 21, 16, 16),
    (96, 20, 32, 16), (96, 40, 16, 32), (64, 1, 16, 16), (64, 64, 16, 16)])
def test_tiles_and_dead_steps_against_the_matrix(T, W, bq, bk):
    """The tiles the kernels' dead test lets through are the tiles that hold
    a live pair of the boolean matrix — no more —, ``interior`` says every
    pair of the tile is live, and a dead step's index maps (the K/V block a
    forward or dq step holds, the Q block a dk/dv step holds) name a live
    block of the same row or column."""
    win, nq, nk = fa.Window(W), T // bq, T // bk
    matrix = np.asarray(window_allowed(T, W))
    tiles = matrix.reshape(nq, bq, nk, bk)
    assert fa._bd_tiles(win, T, bq, bk) == tiles.any(axis=(1, 3)).sum()
    for i in range(nq):
        for j in range(nk):
            live, interior = fa._win_live_interior(win, i, j, bq, bk)
            assert live == tiles[i, :, j].any()
            assert interior == tiles[i, :, j].all()
            held = int(fa._win_live_k(win, bq, bk, i, j))
            assert tiles[i, :, held].any() and (held == j or not live)
            held = int(fa._win_live_q(win, bq, bk, nq, j, i))
            assert tiles[held, :, j].any() and (held == i or not live)
    assert fa.window_pairs(T, W) == matrix.sum()


def test_tile_counts_of_the_benchmark_s_call():
    """What the windowed layers count: 496 keys a query on average, 15 of
    64 tiles a head live and visited at the forward's block of 1,024."""
    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    counts = fa.mask_tile_counts(q, k, ("window", 512))
    assert counts == {"live_pairs": 512 * 513 // 2 + 7680 * 512,
                      "live_tiles": 64 * 15, "visited_tiles": 64 * 15}
    assert counts["live_pairs"] / 8192 == pytest.approx(496.03, abs=0.01)


def test_refusals():
    q, k, v, _ = operands(64, 2, 1)
    mask = ("window", 16)
    with pytest.raises(ValueError, match="seq_len=50"):
        fa.flash_attention(q, k, v, mask=mask, block_q=16, block_k=16,
                           interpret=True, seq_len=50)   # no padded tail
    with pytest.raises(ValueError, match="must divide T=64"):
        fa.flash_attention(q, k, v, mask=mask, block_q=24, block_k=24,
                           interpret=True)
    with pytest.raises(ValueError, match="no padding under"):
        fa.flash_attention_auto(q[:, :63], k[:, :63], v[:, :63], mask=mask)
    with pytest.raises(ValueError, match='"window", W'):
        fa.flash_attention_auto(q, k, v, mask=("window", 0))
    with pytest.raises(ValueError, match='"window", W'):
        fa.flash_attention_auto(q, k, v, mask=("segment", 4))
    with pytest.raises(ValueError, match="one width"):
        fa.flash_attention(q, k, v[..., :64], mask=mask, interpret=True)
    with pytest.raises(ValueError, match="without a selection"):
        fa.flash_attention(q, k, v, mask=mask, interpret=True,
                           select=jnp.ones((1, 64, 64), jnp.int8))


@pytest.mark.parametrize("name,H,Hkv,more,digest", [
    ("gqa6_group_fused", 6, 1, {}, "a7cb87e2a2c56be0"),
    ("gqa8_group_fused", 8, 1, {}, "2efe0216b80b5d7b"),
    ("gqa6_padded_tail", 6, 1, {"seq_len": 50}, "0b5e63ac6a1d9fe9"),
])
def test_a_causal_call_lowers_to_the_parent_s_text(name, H, Hkv, more,
                                                   digest):
    """Loss and gradients of a causal call at the cell's two ratios,
    interpreted (the kernels' bodies are then in the text, the five mask
    helpers' arithmetic and the per-head pair's index maps with them), lower
    to the text — to the letter — that the commit before the window lowered
    them to (SHA-256 taken there, 4c2e3bb, PR 58);
    ``test_flash_block_mask.py`` holds the digests of one and four."""
    q = jnp.zeros((1, 64, H, 128), F32)
    k = v = jnp.zeros((1, 64, Hkv, 128), F32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=32,
                                  block_k=32, interpret=True, **more).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(q, k, v)
    assert hashlib.sha256(text.as_text().encode()).hexdigest()[:16] == digest
