"""The flash family under a positional block mask (``mask=("block_diffusion",
L)``): the kernels, interpreted, against the dense oracle under the mask as a
plain boolean matrix — output, log-sum-exp and the three gradients, at one
query head a KV head and at eight, in the three forward forms and the two
backward forms a call can reach —, what ``_plan`` gives the benchmark's
shape, that no tile the mask leaves nothing of is visited, what is refused,
and that a causal call still lowers to the parent's text.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.ring_attention import (
    _NEG_BIG, block_diffusion_allowed, full_attention)

F32 = jnp.float32


def operands(T, H, Hkv, D=128, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((1, 2 * T, H, D), (1, 2 * T, Hkv, D), (1, 2 * T, Hkv, D),
              (1, 2 * T, H, D))
    return [jax.random.normal(k, s, F32) for k, s in zip(keys, shapes)]


def test_the_mask_by_its_four_rules():
    """The boolean matrix, quadrant by quadrant, at T 8 in blocks of 4."""
    m = np.asarray(block_diffusion_allowed(16, 4))
    blk = np.arange(8) // 4
    assert (m[:8, :8] == (blk[None] <= blk[:, None])).all()     # clean, clean
    assert not m[:8, 8:].any()                                 # clean, noised
    assert (m[8:, :8] == (blk[None] < blk[:, None])).all()      # noised, clean
    assert (m[8:, 8:] == (blk[None] == blk[:, None])).all()    # noised, noised
    assert m.sum() == 8 * 8 + 8 * 4          # T^2 + T L live of 4 T^2 pairs


@pytest.mark.parametrize("name,T,L,H,Hkv,blk,limits,fwd,bwd", [
    ("fullunroll_per_head", 32, 4, 2, 2, 16, {}, "fullunroll", "per_head"),
    ("grid_group_fused_kv8", 32, 4, 8, 1, 16,
     {"_FULL_UNROLL_MAX_T": 0, "_UNROLL_KV_MAX_NK": 0}, "grid",
     "group_fused"),
    ("unrollkv_tile_is_a_block", 64, 32, 2, 1, 32,
     {"_FULL_UNROLL_MAX_T": 0}, "unrollkv", "group_fused"),
])
def test_kernels_against_the_dense_oracle(monkeypatch, name, T, L, H, Hkv,
                                          blk, limits, fwd, bwd):
    for limit, value in limits.items():
        monkeypatch.setattr(fa, limit, value)
    q, k, v, do = operands(T, H, Hkv)
    mask, D = ("block_diffusion", L), q.shape[-1]
    bd = fa.BlockDiffusion(L, T)
    plan = fa._plan_for(q.reshape(1, 2 * T, -1), H, D, (0, 0, 0), bd, blk,
                        blk, blk, blk, True, kv_rep=H // Hkv)
    assert (plan.fwd, plan.bwd) == (fwd, bwd), plan

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=blk,
                                  block_k=blk, interpret=True)

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
        q.reshape(1, 2 * T, -1), k.reshape(1, 2 * T, -1),
        v.reshape(1, 2 * T, -1), H, D ** -0.5, bd, blk, blk, blk, blk, True,
        None)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, 2))
    logits = jnp.where(block_diffusion_allowed(2 * T, L), logits * D ** -0.5,
                       _NEG_BIG)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1), atol=2e-5)


def test_plan_at_the_benchmark_s_shape():
    """sdar_1chip's call: 2 x 8,192 rows, 32 query heads over 4 KV heads of
    128 in bfloat16.  Forward the resident form since PR 60 (a KV head's K
    and V rows, 8 MiB, are the rule's limit to the byte; 1024 x 1024 tiles
    in chains of 256 rows under 64 MB), and the grid form where the device
    backs no budget, at one query head a KV head, at oblong tiles or where
    a chain would split a block of the mask; backward the one kernel a KV
    group —
    dK and dV of 16,384 rows are 16 MiB, the rule's limit to the byte — at
    the 512 x 512 tiles eight heads a step allow; without the budget, and at
    one query head a KV head, the per-head pair (never the pair blocked over
    two heads: its dead steps and sub-tiles are the causal mask's)."""
    def plan(**over):
        fields = dict(T=16384, D=128, H=32, head_base=(0, 0, 0), itemsize=2,
                      causal=fa.BlockDiffusion(4, 8192), block_q=1024,
                      block_k=1024, bwd_block_q=1024, bwd_block_k=1024,
                      interpret=False, manual_axes=False, vmem_headroom=True,
                      kv_rep=8)
        return fa._plan(**{**fields, **over})

    p = plan()
    assert (p.fwd, p.fwd_tile, p.fwd_vmem_mb) == ("resident", 256, 64)
    assert (p.bwd, p.bwd_vmem_mb) == ("group_fused", 64)
    for stands_down in (dict(vmem_headroom=False), dict(kv_rep=1),
                        dict(block_q=512), dict(T=32768),
                        dict(causal=fa.BlockDiffusion(512, 8192))):
        assert plan(**stands_down).fwd == "grid", stands_down
    assert p.blocks == (1024, 1024, 512, 512)
    # 288 of the backward's 1,024 tiles a KV head hold a live pair.
    assert p.bwd_live_share == round(8192 * 8196 / (288 * 512 * 512), 3)
    assert plan(vmem_headroom=False).bwd == "per_head"
    assert plan(kv_rep=1).bwd == "per_head"
    assert plan(kv_rep=1, causal=True).bwd == "grouped"
    assert plan(T=4096, causal=fa.BlockDiffusion(4, 2048)).fwd == "fullunroll"


@pytest.mark.parametrize("T,L,blk", [(8192, 4, 1024), (64, 4, 16),
                                     (64, 32, 32), (96, 4, 32)])
def test_no_dead_tile_is_visited(T, L, blk):
    """The tiles the kernels' dead test lets through are the tiles that hold
    a live pair, counted from the boolean matrix itself where it is small
    and from the closed form (two causal sweeps and the noised diagonal)
    where it is not; and a dead step's index map names a live block."""
    bd = fa.BlockDiffusion(L, T)
    n = T // blk
    visited = fa._bd_tiles(bd, 2 * T, blk, blk)
    assert visited == n * n + n + (n if blk > L else 0)
    if T == 8192:
        assert visited == 80 and 4 * n * n == 256
        return      # the call's counts: test_the_counts_say_which_form_ran
    tiles = np.asarray(block_diffusion_allowed(2 * T, L)).reshape(
        2 * n, blk, 2 * n, blk).any(axis=(1, 3))
    assert tiles.sum() == visited
    for i in range(2 * n):
        for j in range(2 * n):
            live, interior = fa._bd_live_interior(bd, i, j, blk, blk)
            assert live == tiles[i, j]
            held = int(fa._bd_live_k(bd, blk, blk, i, j))
            assert tiles[i, held] and (held == j or not live)
            if interior:
                assert np.asarray(block_diffusion_allowed(2 * T, L))[
                    i * blk:(i + 1) * blk, j * blk:(j + 1) * blk].all()


# (T a stream, L, block, rows a chain, H, Hkv): the resident forward (PR 60)
# under the mask at lane-wide tiles — clean and noised Q blocks, two of each —
# in chains of four and of one, at one, four and eight query heads a KV head,
# and at a mask block as wide as a chain.
@pytest.mark.parametrize("T,L,blk,rows,H,Hkv", [
    (256, 4, 128, 32, 4, 1), (256, 4, 128, 128, 4, 1), (256, 4, 128, 32, 2, 2),
    (256, 4, 128, 64, 8, 1), (256, 32, 128, 32, 2, 1)],
    ids=["4_chains_kv4", "1_chain_kv4", "4_chains_kv1", "2_chains_kv8",
         "mask_block_is_a_chain"])
def test_resident_forward_equals_the_grid_form_and_the_dense_oracle(
        monkeypatch, T, L, blk, rows, H, Hkv):
    """``o`` and ``lse`` of the resident forward under the mask against the
    grid form's on the same operands and against full float32 attention
    under the boolean matrix, and the call's three gradients — through
    ``flash_group_bwd`` where the KV heads are grouped, reading the ``o`` and
    ``lse`` this form wrote — against the grid call's and the oracle's."""
    monkeypatch.setattr(fa, "_FULL_UNROLL_MAX_T", 0)
    monkeypatch.setattr(fa, "_RESIDENT_CHAIN_ROWS", rows)
    q, k, v, do = operands(T, H, Hkv)
    mask, D, rep = ("block_diffusion", L), q.shape[-1], H // Hkv
    bd = fa.BlockDiffusion(L, T)
    plan = fa._plan_for(q.reshape(1, 2 * T, -1), H, D, (0, 0, 0), bd, blk,
                        blk, blk, blk, True, kv_rep=rep)
    # One query head a KV head keeps the grid form by the rule; the body
    # is run under it here all the same.
    assert (plan.fwd, plan.fwd_tile) == (
        ("resident", rows) if rep > 1 else ("unrollkv", 0))
    packed = [a.reshape(1, 2 * T, -1) for a in (q, k, v)]
    (o, lse), (o_grid, lse_grid) = (
        fa._fwd_packed(*packed, H, D, plan._replace(
            fwd=fwd, fwd_tile=tile, fwd_vmem_mb=0), scale=D ** -0.5,
            causal=bd, block_q=blk, block_k=blk, interpret=True, kv_rep=rep)
        for fwd, tile in (("resident", rows), ("grid", 0)))
    np.testing.assert_allclose(o, o_grid, atol=2e-5)
    np.testing.assert_allclose(lse, lse_grid, atol=1e-5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2))
    logits = jnp.where(block_diffusion_allowed(2 * T, L), logits * D ** -0.5,
                       _NEG_BIG)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1), atol=2e-5)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=blk,
                                  block_k=blk, interpret=True)

    def dense(q, k, v):
        return full_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                              mask=mask)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: (f(*a) * do).sum(), (0, 1, 2))(*a)))(q, k, v)

    plans = []
    planned = fa._plan
    monkeypatch.setattr(fa, "_plan", lambda **seen: plans.append(
        planned(**seen)) or plans[-1])
    out, grads = both(flash)
    assert {p.fwd for p in plans} == {plan.fwd}
    monkeypatch.setattr(fa, "_plan", lambda **seen: planned(**seen)._replace(
        fwd="grid", fwd_tile=0, fwd_vmem_mb=0))
    jax.clear_caches()
    out_grid, grads_grid = both(flash)
    jax.clear_caches()          # the traces do not key on the plan
    want, want_grads = both(dense)
    np.testing.assert_allclose(out.reshape(o.shape), o, atol=2e-5)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, grid, ref in zip(grads, grads_grid, want_grads):
        np.testing.assert_allclose(got, grid, atol=2e-5)
        np.testing.assert_allclose(got, ref, atol=5e-5)


@pytest.mark.parametrize("half,L,blk,rows", [
    (64, 4, 32, 8), (64, 4, 16, 16), (96, 8, 32, 16), (64, 32, 32, 32),
    (128, 4, 64, 16)])
def test_the_resident_run_covers_the_live_pairs_and_nothing_else(half, L, blk,
                                                                 rows):
    """:func:`_resident_run`'s tiles and sub-tiles, Q block by Q block and
    chain by chain, by enumeration: the whole tiles hold live pairs alone,
    a sub-tile's mask is the boolean matrix's own, no live pair lies outside
    what a chain folds, and the area folded is what
    :func:`_fwd_visited_pairs` counts."""
    bd = fa.BlockDiffusion(L, half)
    allowed = np.asarray(block_diffusion_allowed(2 * half, L))
    covered = np.zeros_like(allowed)
    chains, area = blk // rows, 0
    for qi in range(2 * half // blk):
        first, n_int, n_live, edges = fa._resident_run(
            bd, qi, blk, blk, 2 * half // blk, None, rows)
        assert first == 0 and n_live is None
        q0 = qi * blk
        assert allowed[q0:q0 + blk, :n_int * blk].all()
        covered[q0:q0 + blk, :n_int * blk] = True
        area += n_int * blk * blk
        for at, left, ok, when in edges:
            if when is not None and not when:
                continue
            for c in range(chains):
                r0, c0 = q0 + c * rows, at * blk + c * rows
                if left:
                    assert allowed[r0:r0 + rows, at * blk:c0].all()
                    covered[r0:r0 + rows, at * blk:c0] = True
                    area += rows * c * rows
                sub = np.asarray(ok())
                assert (sub == allowed[r0:r0 + rows, c0:c0 + rows]).all()
                assert not covered[r0:r0 + rows, c0:c0 + rows].any()
                covered[r0:r0 + rows, c0:c0 + rows] = sub
                area += rows * rows
    assert (covered == allowed).all()
    plan = fa._Plan("resident", rows, 0, "per_head", 0, 0, 1.0)
    assert area == fa._fwd_visited_pairs(plan, bd, 2 * half, blk)
    # Four chains: 0.625 of a tile on each clean-key diagonal, 0.25 of the
    # noised one's, where the grid form runs three whole.
    if chains == 4:
        n = half // blk
        assert area == blk * blk * (n * (n - 1) + n * (0.625 * 2 + 0.25))


@pytest.mark.parametrize("headroom", [True, False])
def test_the_counts_say_which_form_ran(monkeypatch, headroom):
    """``mask_tile_counts`` at ``sdar_1chip``'s call.  On a device that backs
    the resident forward's budget: a step a Q block, all of them live, and
    68 tile-areas a head visited — the chains' sub-tiles on the three
    diagonals — where 80 tiles hold a live pair: 94% of what it computes is
    live.  On one that does not, the grid form: 256 steps a head of which 80
    compute a tile, whole: 80%."""
    monkeypatch.setattr(fa._pallas, "vmem_headroom_ok", lambda: headroom)
    monkeypatch.setattr(fa._pallas, "interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 16_384, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16_384, 4, 128), jnp.bfloat16)
    counts = fa.mask_tile_counts(q, k, ("block_diffusion", 4))
    tiles, steps, live_steps = (68, 16, 16) if headroom else (80, 256, 80)
    assert counts == {"live_pairs": 8192 * 8196, "live_tiles": 32 * 80,
                      "visited_tiles": 32 * tiles, "grid_steps": 32 * steps,
                      "live_steps": 32 * live_steps,
                      "visited_pairs": tiles * 1024 * 1024}
    assert round(counts["live_pairs"] / counts["visited_pairs"], 3) == (
        0.942 if headroom else 0.8)


def test_refusals():
    q, k, v, _ = operands(32, 2, 1)
    mask = ("block_diffusion", 4)
    with pytest.raises(ValueError, match="whole blocks of the mask"):
        fa.flash_attention(q[:, :48], k[:, :48], v[:, :48], mask=mask,
                           block_q=16, block_k=16,
                           interpret=True)      # 16 divides 48 and not 24
    with pytest.raises(ValueError, match="whole blocks of the mask"):
        fa.flash_attention(q, k, v, mask=("block_diffusion", 5),
                           block_q=16, block_k=16, interpret=True)
    with pytest.raises(ValueError, match="seq_len=50"):
        fa.flash_attention(q, k, v, mask=mask, block_q=16, block_k=16,
                           interpret=True, seq_len=50)   # no padded tail
    with pytest.raises(ValueError, match="no padding under"):
        fa.flash_attention_auto(q[:, :63], k[:, :63], v[:, :63], mask=mask)
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention_auto(q, k, v, mask=("segment", 4))
    with pytest.raises(ValueError, match="one width"):
        fa.flash_attention(q, k, v[..., :64], mask=mask, interpret=True)
    with pytest.raises(ValueError, match="without a selection"):
        fa.flash_attention(q, k, v, mask=mask, interpret=True,
                           select=jnp.ones((1, 64, 64), jnp.int8))


@pytest.mark.parametrize("name,H,Hkv,more,digest", [
    ("mha_fullunroll", 2, 2, {}, "9ffbe3bd490b6a29"),
    ("gqa_group_fused", 4, 1, {}, "b38cd894668deb5d"),
    ("padded_tail", 2, 1, {"seq_len": 50}, "3d40d335093b6e02"),
])
def test_a_causal_call_lowers_to_the_parent_s_text(name, H, Hkv, more,
                                                   digest):
    """Loss and gradients of a causal call, interpreted (the kernels' bodies
    are then in the text, the five mask helpers' arithmetic with them), lower
    to the text — to the letter — that the commit before the positional mask
    lowered them to (SHA-256 taken there, 1bf3e8b, PR 52)."""
    q = jnp.zeros((1, 64, H, 128), F32)
    k = v = jnp.zeros((1, 64, Hkv, 128), F32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=32,
                                  block_k=32, interpret=True, **more).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(q, k, v)
    assert hashlib.sha256(text.as_text().encode()).hexdigest()[:16] == digest
