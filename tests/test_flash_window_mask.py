"""The flash family under a causal window (``mask=("window", W)``): the
kernels, interpreted, against the dense oracle under the window as a plain
boolean matrix — output, log-sum-exp and the three gradients, at one, six
and eight query heads a KV head, at windows smaller than, equal to and larger
than the tile, in the three forward forms and the two backward forms a call
can reach, at the shapes the band's schedule branches on (PR 59: a KV axis as
long as a Q block's live run, the block pairs on the window's two edges cut
into sub-tiles), each with the oracles of a window one key wider and one
narrower MISSED —, what ``_plan`` gives the benchmark's two attention shapes,
the block the shapes choose, the grid's steps, the tiles visited and the pairs
computed against the 0/1 matrix, the dead steps' index maps against it, that
the float32 parts stay float32, what is refused, and that a causal call at six
and eight query heads a KV head still lowers to the parent's text.
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


def cut_at(monkeypatch, sub):
    """``_plan`` with the sub-tile the chip's 256 is to its blocks: the one
    backward kernel a KV group at the interpreted tests' blocks of 16 and 32
    cuts its pairs on the window's edges into sub-tiles of ``sub``, under
    ``_diag_sub``'s own rules (0: never)."""
    plan = fa._plan

    def planned(**seen):
        p = plan(**seen)
        if not (isinstance(seen["causal"], fa.Window)
                and p.bwd == "group_fused"):
            return p
        return p._replace(bwd_sub=sub and fa._diag_sub(
            seen["causal"], *p.blocks[2:], sub))

    monkeypatch.setattr(fa, "_plan", planned)
    jax.clear_caches()      # the traces do not key on the plan


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


# ``cut``: the backward's sub-tile the plan carries, of ``sub`` asked.
@pytest.mark.parametrize(
    "name,T,W,H,Hkv,blk,limits,headroom,fwd,bwd,sub,cut", [
        ("fullunroll_mha_w_eq_tile", 64, 16, 2, 2, 16, {}, True,
         "fullunroll", "per_head", 0, 0),
        ("grid_fused_kv8_w_lt_tile", 64, 8, 8, 1, 16, GRID, True, "grid",
         "group_fused", 0, 0),
        ("grid_fused_kv6_w_eq_tile", 64, 16, 6, 1, 16, GRID, True, "grid",
         "group_fused", 0, 0),
        ("unrollkv_kv2_w_gt_tile", 64, 24, 2, 1, 16,
         {"_FULL_UNROLL_MAX_T": 0}, True, "unrollkv", "group_fused", 0, 0),
        ("grid_per_head_kv8_w_odd", 64, 21, 8, 1, 16, GRID, False, "grid",
         "per_head", 0, 0),
        ("grid_per_head_mha_rect", 96, 20, 2, 2, (32, 16), GRID, True, "grid",
         "per_head", 0, 0),
        # The band's schedule (PR 59), cut into sub-tiles of 8.
        ("cut_kv8_w_eq_block", 64, 16, 8, 1, 16, GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_w_block_plus_1", 64, 17, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 0),
        ("cut_kv2_w_block_minus_1", 64, 15, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 0),
        ("cut_kv2_w_two_blocks", 64, 32, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_w_eq_sub", 64, 8, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_w_lt_sub", 64, 4, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 0),
        ("cut_kv2_w_eq_T", 64, 64, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_w_gt_T", 64, 80, 2, 1, 16, GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_one_q_block", 32, 16, 2, 1, (32, 16), GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_tall_blocks", 96, 16, 2, 1, (32, 16), GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_kv2_wide_blocks", 96, 32, 2, 1, (16, 32), GRID, True, "grid",
         "group_fused", 8, 8),
        ("cut_mha_per_head_pair", 64, 16, 2, 2, 16, GRID, True, "grid",
         "per_head", 8, 0),
    ])
def test_kernels_against_the_dense_oracle(monkeypatch, name, T, W, H, Hkv,
                                          blk, limits, headroom, fwd, bwd,
                                          sub, cut):
    for limit, value in limits.items():
        monkeypatch.setattr(fa, limit, value)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: headroom)
    cut_at(monkeypatch, sub)
    bq, bk = blk if isinstance(blk, tuple) else (blk, blk)
    q, k, v, do = operands(T, H, Hkv)
    mask, D = ("window", W), q.shape[-1]
    plan = fa._plan_for(q.reshape(1, T, -1), H, D, (0, 0, 0), fa.Window(W),
                        bq, bk, bq, bk, True, kv_rep=H // Hkv)
    assert (plan.fwd, plan.bwd) == (fwd, bwd), plan
    assert plan.bwd_sub == cut, plan

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=bq,
                                  block_k=bk, interpret=True)

    def dense(w):
        rep = H // Hkv
        return lambda q, k, v: full_attention(
            q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
            mask=("window", w))

    def both(f):
        return jax.jit(lambda *a: (f(*a), *jax.grad(
            lambda *a: (f(*a) * do).sum(), (0, 1, 2))(*a)))(q, k, v)

    got = both(flash)
    for g, ref, atol in zip(got, both(dense(W)), (2e-5, 5e-5, 5e-5, 5e-5)):
        np.testing.assert_allclose(g, ref, atol=atol)
    # A window one key wider or narrower is another attention, and seen.
    for w in (W + 1, W - 1):
        if w and min(w, T) != min(W, T):
            assert max(float(jnp.abs(g - ref).max())
                       for g, ref in zip(got, both(dense(w)))) > 0.2, w
    # The saved log-sum-exp, from the rule's forward half.
    _, (_, _, _, _, lse) = fa._flash_packed_fwd(
        q.reshape(1, T, -1), k.reshape(1, T, -1), v.reshape(1, T, -1), H,
        D ** -0.5, fa.Window(W), bq, bk, bq, bk, True, None)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, 2))
    logits = jnp.where(window_allowed(T, W), logits * D ** -0.5, _NEG_BIG)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(logits, -1), atol=2e-5)
    jax.clear_caches()


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
    the shapes choose is the window's 512 (PR 59; ``auto_block``'s 1,024
    while the grid had a step a block pair);
    forward the grid form (a KV head's rows, 2 MiB, are past the resident
    forms' 1 MiB), backward the one kernel a KV group (dK and dV of 8,192
    rows are 8 MiB) at the 512 x 512 tiles eight heads a step allow; both
    on a KV axis of the live run's 2 steps, the backward's block pairs on
    the window's edges in sub-tiles of 256: 31 of a KV head's 256 backward
    tiles hold a live pair, and two thirds of the scores it computes are
    live where half were.  GLOBAL, 48 query heads
    under the causal mask at 1024²: the resident forward since PR 60 (a KV
    head's rows fetched once for its six query heads, chains of 256 rows;
    never under the window) and the same backward at 512
    x 512 (six heads a step), nothing cut.  Without
    the budget, and at one query head a KV head, the per-head pair, whole
    tiles — never the pair blocked over two heads under a window."""
    def plan(**over):
        fields = dict(T=8192, D=128, H=64, head_base=(0, 0, 0), itemsize=2,
                      causal=fa.Window(512), block_q=512, block_k=512,
                      bwd_block_q=512, bwd_block_k=512, interpret=False,
                      manual_axes=False, vmem_headroom=True, kv_rep=8)
        return fa._plan(**{**fields, **over})

    assert fa._mask_auto_block(8192, ("window", 512)) == 512
    p = plan()
    assert (p.fwd, p.fwd_tile, p.bwd, p.bwd_vmem_mb) == (
        "grid", 0, "group_fused", 64)
    assert p.blocks == (512, 512, 512, 512) and p.bwd_sub == 256
    assert fa._win_steps(fa.Window(512), 8192, 512, 512) == 2
    assert fa._win_steps(fa.Window(512), 8192, 1024, 1024) == 2
    assert fa._win_steps(fa.Window(512), 8192, 256, 256) == 3
    assert fa._bd_tiles(fa.Window(512), 8192, 512, 512) == 31
    assert fa._win_visited(fa.Window(512), 8192, 512, 512, 0) == 31 * 512 ** 2
    # 15 pairs on each edge, three of a pair's four sub-tiles, and the first.
    assert fa._win_visited(fa.Window(512), 8192, 512, 512, 256) == (
        (15 * 3 * 2 + 3) * 256 ** 2)
    assert p.bwd_live_share == round(
        fa.window_pairs(8192, 512) / (93 * 256 * 256), 3) == 0.667
    g = plan(H=48, kv_rep=6, causal=True, block_q=1024, block_k=1024,
             bwd_block_q=1024, bwd_block_k=1024)
    assert (g.fwd, g.fwd_tile, g.bwd, g.blocks, g.bwd_sub) == (
        "resident", 256, "group_fused", (1024, 1024, 512, 512), 0)
    for whole in (plan(vmem_headroom=False), plan(kv_rep=1)):
        assert (whole.bwd, whole.bwd_sub, whole.bwd_live_share) == (
            "per_head", 0, 0.5)
    assert plan(kv_rep=1, causal=True, bwd_block_q=1024,
                bwd_block_k=1024).bwd == "grouped"
    assert plan(T=4096).fwd == "fullunroll"
    # A window no multiple of the sub-tile, a pair of one sub-tile or of too
    # many: whole masked pairs, on the short axis all the same.
    assert plan(causal=fa.Window(500)).bwd_sub == 0
    assert fa._diag_sub(fa.Window(512), 1024, 1024) == 256
    assert fa._diag_sub(fa.Window(512), 256, 256) == 0
    assert fa._diag_sub(fa.Window(512), 2048, 1024) == 0
    assert fa._diag_sub(fa.BlockDiffusion(4, 4096), 1024, 1024) == 0


@pytest.mark.parametrize("rows,W,blk", [
    (8192, 512, 512), (8192, 4096, 1024), (8192, 100, 512), (8192, 64, 512),
    (8192, 768, 512), (8192, 1024, 1024), (64, 16, 64), (2304, 512, 384),
    (4096, 1024, 1024), (1024, 512, 512), (520, 16, 520)])
def test_the_block_is_chosen_from_shapes(rows, W, blk):
    """``auto_block``'s of the rows, the causal call's, but no wider than
    the window, or than 512 under a narrower one, where a lane-aligned
    divisor of the rows is."""
    assert fa._mask_auto_block(rows, ("window", W)) == blk
    assert blk <= fa.auto_block(rows) and rows % blk == 0
    assert blk == fa.auto_block(rows) or blk <= max(W, 512)


@pytest.mark.parametrize("T,W,bq,bk", [
    (64, 16, 16, 16), (64, 8, 16, 16), (64, 24, 16, 16), (64, 21, 16, 16),
    (96, 20, 32, 16), (96, 40, 16, 32), (64, 1, 16, 16), (64, 64, 16, 16),
    (64, 32, 16, 16), (64, 80, 16, 16), (96, 16, 32, 16), (96, 32, 16, 32),
    (32, 16, 32, 16), (128, 32, 32, 32)])
def test_tiles_and_dead_steps_against_the_matrix(T, W, bq, bk):
    """The tiles the kernels' dead test lets through are the tiles that hold
    a live pair of the boolean matrix — no more —, ``interior`` says every
    pair of the tile is live, and a dead step's index maps (the K/V block a
    forward or dq step holds, the Q block a dk/dv step holds) name a live
    block of the same row or column.  The short KV axis (PR 59): a Q
    block's row of the grid has as many steps as the longest run of live
    tiles any Q block has, its steps stand on the run's tiles in order, each
    once, and a step past the run's end is dead and holds the run's last
    tile.  The sub-tiles (of 8): the products of a tile on an edge cover its
    sub-tiles that hold a live pair — those and no other, each once —, a
    product with no edge holds no dead pair, one on the causal edge is live
    at and under its diagonal, one on the far edge above it; the pairs
    computed are those sub-tiles' area."""
    win, nq, nk = fa.Window(W), T // bq, T // bk
    matrix = np.asarray(window_allowed(T, W))
    tiles = matrix.reshape(nq, bq, nk, bk)
    assert fa._bd_tiles(win, T, bq, bk) == tiles.any(axis=(1, 3)).sum()
    steps = fa._win_steps(win, T, bq, bk)
    assert steps == tiles.any(axis=(1, 3)).sum(axis=1).max()
    for i in range(nq):
        for j in range(nk):
            live, interior = fa._win_live_interior(win, i, j, bq, bk)
            assert live == tiles[i, :, j].any()
            assert interior == tiles[i, :, j].all()
            held = int(fa._win_live_k(win, bq, bk, i, j))
            assert tiles[i, :, held].any() and (held == j or not live)
            held = int(fa._win_live_q(win, bq, bk, nq, j, i))
            assert tiles[held, :, j].any() and (held == i or not live)
        run = np.flatnonzero(tiles[i].any(axis=(0, 2)))
        stood = [int(fa._kv_step(win, bq, bk, i, step))
                 for step in range(steps)]
        assert stood[:len(run)] == run.tolist()
        for step, kj in enumerate(stood):
            live = bool(fa._win_live_interior(win, i, kj, bq, bk)[0])
            assert live == (step < len(run))
            held = int(fa._win_run_k(win, bq, bk, i, step))
            assert held == (kj if live else run[-1])
    assert fa.window_pairs(T, W) == matrix.sum()
    assert fa._win_visited(win, T, bq, bk, 0) == (
        tiles.any(axis=(1, 3)).sum() * bq * bk)

    sub = fa._diag_sub(win, bq, bk, 8)
    assert sub == (0 if W % 8 else 8)
    if not sub:
        return
    cells = matrix.reshape(T // sub, sub, T // sub, sub)
    assert fa._win_visited(win, T, bq, bk, sub) == (
        cells.any(axis=(1, 3)).sum() * sub * sub)
    on_edge = {(i, j) for i in range(nq) for j in range(nk)
               if tiles[i, :, j].any() and not tiles[i, :, j].all()}
    assert {i * bq - j * bk for i, j in on_edge} == set(
        fa._win_edge_tiles(W, T, bq, bk))
    lower = np.tri(sub, dtype=bool)
    for i, j in on_edge:
        tile = tiles[i, :, j]
        seen = np.zeros((bq // sub, bk // sub), int)
        for r0, r1, col, edge in fa._win_regions(i * bq - j * bk, bq, bk, W,
                                                 sub):
            seen[r0:r1, col] += 1
            part = tile[r0 * sub:r1 * sub, col * sub:(col + 1) * sub]
            if edge is None:
                assert part.all()
            else:
                assert r1 - r0 == 1
                assert (part == (lower if edge == "near" else ~lower)).all()
        assert (seen == tile.reshape(bq // sub, sub, bk // sub, sub
                                     ).any(axis=(1, 3))).all()


@pytest.mark.parametrize("W,tile", [
    (512, 512), (256, 512), (100, 512), (1024, 1024), (4096, 1024)])
def test_the_counter_against_the_matrix(monkeypatch, W, tile):
    """``mask_tile_counts`` of a grid-form call, two sequences of 2,048 over
    four query heads: the grid's steps, the steps and the tiles that hold a
    live pair, and the pairs whose scores a head's forward computes — its
    visited tiles whole — counted on the 0/1 matrix."""
    for limit, value in GRID.items():
        monkeypatch.setattr(fa, limit, value)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: True)
    B, T, H = 2, 2048, 4
    q = jax.ShapeDtypeStruct((B, T, H, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, T, 1, 128), jnp.bfloat16)
    assert fa._mask_auto_block(T, ("window", W)) == tile
    matrix = np.asarray(window_allowed(T, W))
    tiles = matrix.reshape(T // tile, tile, T // tile, tile).any(axis=(1, 3))
    assert fa.mask_tile_counts(q, k, ("window", W)) == {
        "live_pairs": B * matrix.sum(),
        "live_tiles": B * H * tiles.sum(), "visited_tiles": B * H * tiles.sum(),
        "grid_steps": B * H * (T // tile) * tiles.sum(axis=1).max(),
        "live_steps": B * H * tiles.sum(),
        "visited_pairs": B * tiles.sum() * tile * tile}


def test_tile_counts_of_the_benchmark_s_call(monkeypatch):
    """What the windowed layers count: 496 keys a query on average, 31 of
    256 tiles a head live and visited at the forward's block of 512, on a
    grid of 32 steps a head for them (PR 59; at the parent 15 of 64 tiles of
    1,024 on 64 steps a head), and twice the live pairs computed where 3.9
    times were."""
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: True)
    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    counts = fa.mask_tile_counts(q, k, ("window", 512))
    live = 512 * 513 // 2 + 7680 * 512
    assert counts == {"live_pairs": live,
                      "live_tiles": 64 * 31, "visited_tiles": 64 * 31,
                      "grid_steps": 64 * 32, "live_steps": 64 * 31,
                      "visited_pairs": 31 * 512 ** 2}
    assert counts["live_pairs"] / 8192 == pytest.approx(496.03, abs=0.01)
    assert counts["visited_pairs"] / live == pytest.approx(2.0, abs=1e-3)
    assert counts["grid_steps"] - counts["live_steps"] <= 16 * 64    # nq * H


def equations(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for v in value if isinstance(value, (list, tuple)) else [value]:
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    yield from equations(v)


def test_the_cut_kernels_keep_float32_sums_and_multiply_in_bfloat16(
        monkeypatch):
    """The cell's ``correct`` does not see a rounded accumulator, so the
    traced kernels of a windowed call are read: the forward's masked and
    unmasked body; in the cut backward a body a product — the pair inside
    the window whole, and three sub-tile products for each of the two
    distances at which an edge crosses a 16 x 16 pair under 16 keys —,
    every product on the configuration's bfloat16 operands into float32,
    every exponential in float32, the running statistics, the accumulator
    and ``dq``, ``dK``, ``dV`` in float32 scratch, and no cast to bfloat16
    but a product's left operand and the results' one rounding."""
    for limit, value in GRID.items():
        monkeypatch.setattr(fa, limit, value)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: True)
    cut_at(monkeypatch, 8)
    T, H, D, blk = 64, 2, 128, 16
    q, k, v, _ = (a.astype(jnp.bfloat16) for a in operands(T, H, 1))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=("window", 16), block_q=blk, block_k=blk,
            interpret=True).astype(F32).sum(), (0, 1, 2)))(q, k, v)
    calls = {(eqn.params["name"]
              or eqn.params["jaxpr"].debug_info.func_name): eqn
             for eqn in equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert sorted(calls) == ["_fwd_kernel", "flash_group_bwd"]
    cut = 1 + 2 * 3          # whole, and three products at each distance
    for name, grid, scratch, bodies, products, casts in (
            ("_fwd_kernel", (1, H, 4, 2),
             [((blk, 128), "float32")] * 2 + [((blk, D), "float32")],
             2, 2, 2 + 1),
            ("flash_group_bwd", (1, 1, 4, 2),
             [((H, blk, D), "float32")] + [((T, D), "float32")] * 2,
             cut, 5 * H, 2 * H * cut + H + 2)):
        call = calls[name]
        assert tuple(call.params["grid_mapping"].grid) == grid
        body = call.params["jaxpr"]
        refs = [(v.aval.shape, str(v.aval.dtype)) for v in body.invars]
        assert refs[-len(scratch):] == scratch
        eqns = list(equations(body))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(dots) == products * bodies
        for e in dots:
            assert {str(v.aval.dtype) for v in e.invars} == {"bfloat16"}
            assert str(e.outvars[0].aval.dtype) == "float32"
        exps = [e for e in eqns if e.primitive.name == "exp"]
        assert exps and all(str(e.outvars[0].aval.dtype) == "float32"
                            for e in exps)
        to_bf16 = [e for e in eqns
                   if e.primitive.name == "convert_element_type"
                   and str(e.outvars[0].aval.dtype) == "bfloat16"]
        assert len(to_bf16) == casts
    jax.clear_caches()


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


@pytest.mark.parametrize("name,H,Hkv,limits,how,digest", [
    ("bd_gqa4_fullunroll", 4, 1, {}, {"mask": ("block_diffusion", 4)},
     "ce34aeab94470372"),
    ("bd_gqa4_grid_fused", 4, 1, GRID, {"mask": ("block_diffusion", 4)},
     "644c4f99ea938203"),
    ("bd_mha_grid_per_head", 2, 2, GRID, {"mask": ("block_diffusion", 4)},
     "1f36d7151676172b"),
    ("causal_gqa8_grid_fused", 8, 1, GRID, {"causal": True},
     "027f9cbef3ed6435"),
])
def test_the_other_masks_lower_to_the_parent_s_text(monkeypatch, name, H,
                                                    Hkv, limits, how, digest):
    """``sdar_1chip``'s guard: the block-diffusion mask shares the grid
    forward, the one backward kernel a KV group, ``_select_live_k``,
    ``_masked_dispatch`` and ``_live_block`` with the window, and the band's
    schedule (PR 59) touches none of their other branches — loss and
    gradients of a ``("block_diffusion", L)`` call, and of a causal one, in
    the grid forms, interpreted, lower to the text — to the letter — that the
    commit before the band's schedule lowered them to (SHA-256 taken there,
    4cf8ec2, PR 58)."""
    for limit, value in limits.items():
        monkeypatch.setattr(fa, limit, value)
    jax.clear_caches()
    q = jnp.zeros((1, 64, H, 128), F32)
    k = v = jnp.zeros((1, 64, Hkv, 128), F32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_q=16, block_k=16,
                                  interpret=True, **how).sum()

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(q, k, v)
    assert hashlib.sha256(text.as_text().encode()).hexdigest()[:16] == digest
    jax.clear_caches()
