"""The flash-attention family compiled for a described TPU v5e (see
``tests/_v5e.py``): dense, grouped-KV, resident, block-mask, window and
split-width calls at the cells' shapes and at every tiling ``_plan`` admits.  The
interpreted tests of the same kernels are ``test_flash_attention.py``,
``test_flash_backward.py``, ``test_flash_block_mask.py``,
``test_flash_split_widths.py`` and ``test_latent_attention.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _v5e import (compile_text, custom_calls, pallas_calls,  # noqa: F401
                  scoped_vmem_mb, v5e)

# The benchmark TransformerLM: d=2048, 16 heads of 128, T=2048, batch 8.
B, T, H, D = 8, 2048, 16, 128


# The benchmark cells' shapes (fully-unrolled forward, the pair grouped
# over two heads; T 4096 is OLMoE's and needs the raised VMEM budgets),
# then what else _plan can choose: the unrolled-KV forward with the
# per-head pair, the grid forward past a 1 MB K/V row, and heads off the
# lane width (GPT-2 small's 12 of 64), merged into the batch.
# Since PR 29 the grouped pair cuts its diagonal blocks into 256-wide
# sub-tiles at the two cell shapes and at T 8192; without the causal mask
# it stands down to whole blocks ("T1024_non_causal": at T 2048 the
# fully-unrolled forward, all 16 of its tiles live, wants 20.4 MB of scoped
# VMEM against the default 16 — at the parent of PR 29 too; no cell runs
# attention without the mask).
@pytest.mark.parametrize("b,t,h,d,blocks,causal,sub", [
    (B, T, H, D, None, True, 256), (4, 4096, H, D, None, True, 256),
    (B, 1024, H, D, None, False, 0), (2, 2304, H, D, None, True, 0),
    (1, 8192, H, D, None, True, 256), (8, 1024, 12, 64, 512, True, 0)],
    ids=["cell_T2048", "cell_T4096", "T1024_non_causal", "unrollkv",
         "grid", "D64"])
def test_flash_attention_fwd_bwd(v5e, monkeypatch, b, t, h, d, blocks,
                                 causal, sub):
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=blocks,
                                  block_k=blocks).astype(jnp.float32).sum()

    text = compile_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        q, q, q)
    assert text.count("tpu_custom_call") >= 3    # forward, dq, dk/dv
    assert {p.bwd_sub for p in plans} == {sub}


def test_flash_qkv_proj_fwd_bwd(v5e):
    """The fused projection + attention op as models/transformer.py calls
    it: (8, 2048, 2048) activations by the (2048, 6144) qkv kernel."""
    from horovod_tpu.ops.flash_attention import flash_qkv_proj

    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((H * D, 3 * H * D), jnp.float32, sharding=one)

    def loss(x, w):
        return flash_qkv_proj(x, w, H, causal=True).astype(
            jnp.float32).sum()

    text = compile_text(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    assert text.count("tpu_custom_call") >= 3


# ------------------------------------------- the hybrid stack's own parts
# (the twotower_1chip cell: 2 sequences of 8,192, Nemotron-H's widths)


# The scoped VMEM the compiler counts for the fused backward without a map
# at the two cells' shapes, under the blocks the plan gives them (MB, found
# by bisection on the limit in the sandbox, PR 44): zaya1_1chip's 4 heads a
# group at 512 x 1024 and T 16,384 between 40 and 44 (48–50 at the 1024 x
# 1024 the map's form would take), twotower_1chip's 16 at 256 x 512 and
# T 8,192 between 24 and 28.
GROUP_BWD_COUNTED_MB = 44
# ... and for the resident forward at one width (PR 60, the compiler's own
# count in its refusal of a smaller limit): 26.62 at T 16,384 / D 128 — 8 MiB
# of K and V rows, twice — under the block-diffusion mask at 1024 x 1024
# tiles in chains of 256 rows (``sdar_1chip``), 26.27 under the causal mask
# (``zaya1_1chip``), 17.0 at 512 x 512, 18.27 at T 8,192 (``twotower_1chip``,
# ``lagunaxs2_1chip``'s global call).
RESIDENT_FWD_COUNTED_MB = 28


@pytest.mark.parametrize("b,t,h,blocks", [
    (2, 8192, 32, (256, 512)), (1, 16_384, 8, (512, 1024))],
    ids=["twotower_1chip", "zaya1_1chip"])
def test_grouped_kv_flash_fwd_bwd_at_nemotron_widths(v5e, monkeypatch, b, t,
                                                     h, blocks):
    """32 query heads over 2 KV heads of 128 at T 8192 (``twotower_1chip``)
    and 8 over 2 at T 16,384 (``zaya1_1chip``): a K/V row is 2 MB or more,
    past the fully-unrolled form, so since PR 60 the resident forward — a
    KV head's K and V rows, 4 and 8 MiB, in VMEM for the query heads that
    share them, 1024 x 1024 tiles in chains of 256 rows, under the 28 MB the
    compiler counts for it (the grid forward before); and since PR 44 the
    backward as ONE kernel a KV group (``flash_group_bwd``; the per-head
    pair before, whose dk/dv kernel ran the query heads of a KV head one
    after another), under the plan's blocks and 64 MB of scoped VMEM — of
    which the compiler counts at most 44, so it compiles under that.  dk
    and dv come back at the KV heads' width."""
    from horovod_tpu.ops import flash_attention as fa

    assert fa._SELECT_FUSED_VMEM_MB >= GROUP_BWD_COUNTED_MB + 8
    assert fa._RESIDENT_VMEM_MB >= RESIDENT_FWD_COUNTED_MB + 8
    monkeypatch.setattr(fa, "_SELECT_FUSED_VMEM_MB", GROUP_BWD_COUNTED_MB)
    monkeypatch.setattr(fa, "_RESIDENT_VMEM_MB", RESIDENT_FWD_COUNTED_MB)
    jax.clear_caches()
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((b, t, h, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, t, 2, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv)
    assert custom_calls(lowered.as_text()) == [
        ("flash_group_bwd", 6), ("flash_resident_fwd", 3)]
    assert scoped_vmem_mb(lowered.as_text()) == {
        "flash_resident_fwd": RESIDENT_FWD_COUNTED_MB,
        "flash_group_bwd": GROUP_BWD_COUNTED_MB}
    assert {(p.fwd, p.fwd_tile, p.bwd, p.bwd_sub, p.blocks[2:])
            for p in plans} == {("resident", 256, "group_fused", 0, blocks)}
    compiled = lowered.compile()
    _, (dq, dk, dv) = compiled.out_info
    assert dq.shape == (b, t, h, 128)
    assert dk.shape == dv.shape == (b, t, 2, 128)
    jax.clear_caches()      # the traces do not key on the budget


def test_block_mask_flash_fwd_bwd_at_the_sdar_cell_s_shape(v5e, monkeypatch):
    """``sdar_1chip``'s call: a clean and a noised copy of 8,192 tokens,
    16,384 rows, 32 query heads over 4 KV heads of 128 under the
    block-diffusion mask in blocks of 4.  The resident forward since PR 60 —
    a KV head's K and V rows of 16,384, 8 MiB (the rule's limit), in VMEM, a
    step a Q block of 1,024 rows in four chains, the tiles on the mask's
    three diagonals as the chains' sub-tiles — under the 28 MB the compiler
    counts, and the one backward kernel a KV group at 512 x 512 — eight
    heads a step, dK and dV of 16,384 rows resident (16 MiB: the rule's
    limit) — under the budget the compiler counts for the causal call plus
    the masked body's tile; no map is an operand."""
    from horovod_tpu.ops import flash_attention as fa

    counted = GROUP_BWD_COUNTED_MB + 8
    assert fa._SELECT_FUSED_VMEM_MB >= counted + 8
    assert fa._RESIDENT_VMEM_MB >= RESIDENT_FWD_COUNTED_MB + 8
    assert fa._RESIDENT_KV_BYTES == 16_384 * 2 * 128 * 2
    monkeypatch.setattr(fa, "_SELECT_FUSED_VMEM_MB", counted)
    monkeypatch.setattr(fa, "_RESIDENT_VMEM_MB", RESIDENT_FWD_COUNTED_MB)
    jax.clear_caches()
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 16_384, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16_384, 4, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, mask=("block_diffusion", 4)
                                  ).astype(jnp.float32).sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    lowered = jax.jit(grad).lower(q, kv, kv)
    assert custom_calls(lowered.as_text()) == [
        ("flash_group_bwd", 6), ("flash_resident_fwd", 3)]
    assert scoped_vmem_mb(lowered.as_text()) == {
        "flash_resident_fwd": RESIDENT_FWD_COUNTED_MB,
        "flash_group_bwd": counted}
    assert [grid for _, grid, _ in pallas_calls(
        jax.make_jaxpr(grad)(q, kv, kv).jaxpr)] == [
        (1, 32, 16), (1, 4, 32, 32)]
    _, (dq, dk, dv) = lowered.compile().out_info
    assert dq.shape == (1, 16_384, 32, 128)
    assert dk.shape == dv.shape == (1, 16_384, 4, 128)
    jax.clear_caches()      # the traces do not key on the budget


@pytest.mark.parametrize("kind,heads,how,grids", [
    ("windowed", 64, {"mask": ("window", 512)},
     [(1, 64, 16, 2), (1, 8, 16, 2)]),
    ("global", 48, {"causal": True}, [(1, 48, 8), (1, 8, 16, 16)]),
])
def test_the_laguna_cell_s_two_attention_calls_fwd_bwd(v5e, monkeypatch,
                                                       kind, heads, how,
                                                       grids):
    """``lagunaxs2_1chip``'s calls: one sequence of 8,192 over 8 KV heads of
    128 — the windowed layers' 64 query heads under 512 keys a query
    (tiles of 512, the block the shapes choose) and the global
    layers' 48 under the causal mask (six query heads a KV head, which no
    other cell runs).  The forward — the grid form under the window, the
    resident form under the causal mask (PR 60: a step a Q block) — and the
    one backward kernel a KV group, dK and dV of 8,192 rows resident,
    compile for the v5e under the budgets the plan gives; no map is an
    operand.  Under the window both walk the band (PR 59): a KV axis of the
    live run's 2 steps where the causal call's backward has 16, and the
    backward's block pairs on the window's edges in sub-tiles of 256."""
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, **how).astype(jnp.float32).sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    lowered = jax.jit(grad).lower(q, kv, kv)
    forward, budget = (("_fwd_kernel", 0) if kind == "windowed" else
                       ("flash_resident_fwd", fa._RESIDENT_VMEM_MB))
    assert custom_calls(lowered.as_text()) == sorted([
        (forward, 3), ("flash_group_bwd", 6)])
    assert scoped_vmem_mb(lowered.as_text()) == {
        forward: budget, "flash_group_bwd": fa._SELECT_FUSED_VMEM_MB}
    assert [grid for _, grid, _ in pallas_calls(
        jax.make_jaxpr(grad)(q, kv, kv).jaxpr)] == grids
    assert {(p.fwd, p.bwd, p.bwd_sub, p.blocks) for p in plans} == {
        ("grid", "group_fused", 256, (512,) * 4) if kind == "windowed" else
        ("resident", "group_fused", 0, (1024, 1024, 512, 512))}
    _, (dq, dk, dv) = lowered.compile().out_info
    assert dq.shape == (1, 8192, heads, 128)
    assert dk.shape == dv.shape == (1, 8192, 8, 128)


# (window, block_q, block_k, KV steps, sub-tile): the tilings ``_plan``
# admits for a windowed call beside the cell's own, at two query heads a KV
# head to keep the compiles short — a pair of one sub-tile is never cut;
# oblong blocks, three distances on an edge; a window of three sub-tiles; one
# no multiple of the sub-tile keeps whole masked pairs on its short axis.
@pytest.mark.parametrize("window,block_q,block_k,steps,sub", [
    (512, 256, 256, 3, 0), (512, 512, 512, 2, 256), (512, 1024, 512, 3, 256),
    (768, 512, 512, 3, 256), (500, 512, 512, 2, 0)])
def test_the_window_s_band_compiles_at_every_tiling(v5e, monkeypatch, window,
                                                    block_q, block_k, steps,
                                                    sub):
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, mask=("window", window), block_q=block_q,
            block_k=block_k).astype(jnp.float32).sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    lowered = jax.jit(grad).lower(q, kv, kv)
    assert custom_calls(lowered.as_text()) == [
        ("_fwd_kernel", 3), ("flash_group_bwd", 6)]
    assert [grid for _, grid, _ in pallas_calls(
        jax.make_jaxpr(grad)(q, kv, kv).jaxpr)] == [
        (1, 16, 8192 // block_q, steps), (1, 8, 8192 // block_q, steps)]
    assert {(p.fwd, p.bwd, p.bwd_sub, p.blocks[2:]) for p in plans} == {
        ("grid", "group_fused", sub, (block_q, block_k))}
    lowered.compile()


def test_latent_attention_s_kernels_fwd_bwd_at_the_joyai_cell_s_shape(
        v5e, monkeypatch):
    """``joyaiflash_1chip``'s call (PR 50): 32 heads, keys of 192 (128 | 64)
    against values of 128, two sequences of 8,192.  ``flash_attention``
    pads q and k to 256 lanes and leaves v, o and dv at 128.  Forward (PR
    51): a head's K and V rows resident — 6 MiB, twice for the pipeline —,
    the KV loop inside the grid step, 1024 x 1024 tiles in four chains of
    256 rows under 64 MB of scoped VMEM, of which the compiler counts at
    most 24 (it refuses 20).  Backward: ONE kernel a head
    (``flash_group_bwd`` at a group of one: ``dK`` (T, 256) and ``dV``
    (T, 128) float32 resident, 12 MiB) under the plan's 1024 x 1024 tiles
    and 64 MB — of which the compiler counts at most 40.  Both compile
    under what it counts.  The gradients come back at the published
    widths."""
    from horovod_tpu.ops import flash_attention as fa

    counted_mb, counted_fwd_mb = 40, 24
    assert fa._SELECT_FUSED_VMEM_MB >= counted_mb + 8
    assert fa._RESIDENT_VMEM_MB >= counted_fwd_mb + 8
    monkeypatch.setattr(fa, "_SELECT_FUSED_VMEM_MB", counted_mb)
    monkeypatch.setattr(fa, "_RESIDENT_VMEM_MB", counted_fwd_mb)
    jax.clear_caches()
    one = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v)
    assert custom_calls(lowered.as_text()) == [
        ("flash_group_bwd", 6), ("flash_resident_fwd", 3)]
    assert scoped_vmem_mb(lowered.as_text()) == {
        "flash_resident_fwd": counted_fwd_mb, "flash_group_bwd": counted_mb}
    assert {(p.fwd, p.fwd_tile, p.bwd, p.blocks) for p in plans} == {
        ("resident", 256, "group_fused", (1024,) * 4)}
    _, (dq, dk, dv) = lowered.compile().out_info
    assert dq.shape == dk.shape == (2, 8192, 32, 192)
    assert dv.shape == (2, 8192, 32, 128)
    jax.clear_caches()      # the traces do not key on the budget


# (T, block_q, block_k, causal, seq_len, chain rows): every kind of tiling
# the two-width branch of _plan admits for the resident forward — whole
# lanes to 1024 a side, the rows to 8 MiB — compiles under the stated 64
# MB: the cell's own; square tiles of 512 and of 128 (one chain); Q blocks
# narrower and wider than the K tile (the masked loop in place of the
# triangles); a block 256 does not divide; a padded tail; no mask; and a
# shorter sequence.
@pytest.mark.parametrize("t,block_q,block_k,causal,seq_len,rows", [
    (8192, 1024, 1024, True, None, 256), (8192, 512, 512, True, None, 256),
    (8192, 128, 128, True, None, 128), (8192, 512, 1024, True, None, 256),
    (8192, 1024, 128, True, None, 256), (1536, 384, 384, True, None, 384),
    (8192, 1024, 1024, True, 8000, 256),
    (8192, 1024, 1024, False, None, 256),
    (2048, 1024, 1024, True, None, 256)],
    ids=["cell", "square_512", "square_128", "q_narrower", "q_wider",
         "block_of_384", "padded_tail", "no_mask", "T2048"])
def test_resident_forward_compiles_at_every_tiling_the_plan_admits(
        v5e, monkeypatch, t, block_q, block_k, causal, seq_len, rows):
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((1, t, 2, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        seq_len=seq_len)).lower(qk, qk, v).compile()
    assert {(p.fwd, p.fwd_tile, p.fwd_vmem_mb) for p in plans} == {
        ("resident", rows, 64)}
    assert "flash_resident_fwd" in compiled.as_text()
    assert compiled.out_info.shape == (1, t, 2, 128)


# (T, query heads, KV heads, block_q, block_k, how, chain rows): the tilings
# the ONE-width branch of _plan admits for the resident forward (PR 60) beside
# the cells' own — grouped KV heads past the fully-unrolled form, the rows to
# 8 MiB: the block mask at square tiles of 512; heads of 256 at T 8,192 (8
# MiB again); oblong causal tiles and a padded tail (the masked loop); no
# mask.
@pytest.mark.parametrize("t,h,hkv,d,block_q,block_k,how,rows", [
    (16_384, 8, 2, 128, 512, 512, {"mask": ("block_diffusion", 4)}, 256),
    (8192, 4, 1, 256, 1024, 1024, {"causal": True}, 256),
    (8192, 4, 2, 128, 512, 1024, {"causal": True}, 256),
    (8192, 4, 2, 128, 1024, 1024, {"causal": True, "seq_len": 8000}, 256),
    (8192, 4, 2, 128, 1024, 1024, {"causal": False}, 256),
    (16_384, 4, 2, 128, 128, 128, {"mask": ("block_diffusion", 32)}, 128)],
    ids=["block_mask_512", "heads_of_256", "q_narrower", "padded_tail",
         "no_mask", "block_mask_one_chain"])
def test_resident_forward_at_one_width_compiles_at_every_tiling(
        v5e, monkeypatch, t, h, hkv, d, block_q, block_k, how, rows):
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, t, hkv, d), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, **how)).lower(
            q, kv, kv).compile()
    assert {(p.fwd, p.fwd_tile, p.fwd_vmem_mb) for p in plans} == {
        ("resident", rows, 64)}
    assert "flash_resident_fwd" in compiled.as_text()
    assert compiled.out_info.shape == (1, t, h, d)


# (T, query heads, KV heads, widths of k and v, block, how): where the
# resident forward stands down.  At two widths (PR 51): a device that backs
# no budget above Mosaic's default, K and V rows past 8 MiB (T 16,384 at 256
# + 128 lanes: 12) and tiles off the lanes.  At one width (PR 60): the same
# three (rows past 8 MiB: T 32,768 at D 128), one query head a KV head
# (``olmohybrid_1chip``'s call, whose family admits the flash three by
# kernel name), a causal window (the band's grid form, PR 59), and under the
# block mask oblong tiles.
@pytest.mark.parametrize("why,t,h,hkv,d,dv,block,how", [
    ("no_headroom", 8192, 2, 2, 192, 128, 1024, {}),
    ("rows_over_the_bound", 16_384, 2, 2, 192, 128, 1024, {}),
    ("tiles_off_the_lanes", 8192, 2, 2, 192, 128, 64, {}),
    ("one_width_no_headroom", 8192, 4, 2, 128, 128, 1024, {}),
    ("one_width_rows_over_the_bound", 32_768, 4, 2, 128, 128, 1024, {}),
    ("one_width_tiles_off_the_lanes", 8192, 4, 2, 128, 128, 64, {}),
    ("one_query_head_a_kv_head", 8192, 2, 2, 128, 128, 1024, {}),
    ("a_causal_window", 8192, 4, 2, 128, 128, 512,
     {"mask": ("window", 512)}),
    ("block_mask_oblong_tiles", 16_384, 4, 2, 128, 128, 1024,
     {"mask": ("block_diffusion", 4), "block_q": 512})])
def test_where_the_resident_forward_stands_down_the_grid_form_lowers(
        v5e, monkeypatch, why, t, h, hkv, d, dv, block, how):
    """Each lowers to the grid forward as it was, under Mosaic's default
    budget."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa._pallas, "vmem_headroom_ok",
                        lambda: "no_headroom" not in why)
    jax.clear_caches()
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, t, hkv, d), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, t, hkv, dv), jnp.bfloat16, sharding=one)
    how = {"block_q": block, "block_k": block, **how}
    text = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, **how)).lower(
        q, k, v).as_text()
    assert custom_calls(text) == [("_fwd_kernel", 3)]
    assert scoped_vmem_mb(text) == {"_fwd_kernel": 0}
    jax.clear_caches()      # the traces do not key on the device


# ------------------------------------- the linear-attention hybrid's parts
# (the olmohybrid_1chip cell: 1 sequence of 8,192, Olmo-Hybrid's widths)


def test_flash_fwd_bwd_at_thirty_heads_of_olmo_hybrid(v5e, monkeypatch):
    """30 heads of 128 — no power of two — at T 8192 through the split q,
    k, v entry, as ``Attention`` with QK-norm calls it: a K/V row is 2 MB,
    so the grid forward; 30 is even, so the pair grouped over two heads,
    its diagonal blocks cut into 256-wide sub-tiles."""
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, 8192, 30, 128), jnp.bfloat16, sharding=one)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert {(p.fwd, p.bwd, p.bwd_sub) for p in plans} == {
        ("grid", "grouped", 256)}
    _, grads = compiled.out_info
    assert all(g.shape == (1, 8192, 30, 128) for g in grads)


@pytest.mark.parametrize("entry,b,t,h,hkv,kernels", [
    ("proj", 8, 2048, 16, 16, ["_dkdv_kernel_grouped", "_dq_kernel_grouped",
                               "_fwd_kernel_fullunroll"]),
    ("split", 4, 4096, 16, 16, ["_dkdv_kernel_grouped", "_dq_kernel_grouped",
                                "_fwd_kernel_fullunroll"]),
    ("split", 1, 8192, 30, 30, ["_dkdv_kernel_grouped", "_dq_kernel_grouped",
                                "_fwd_kernel"]),
    ("split", 2, 8192, 32, 2, ["flash_group_bwd", "flash_resident_fwd"])],
    ids=["gpt", "olmoe", "olmo_hybrid", "nemotron_grouped_kv"])
def test_a_call_without_a_selection_lowers_as_it_did(v5e, entry, b, t, h,
                                                     hkv, kernels):
    """The four cells' calls without a map lower to the kernels, and each
    kernel to the operands, that the parent of PR 37 lowered them to (the
    literals are its): q, k, v forward; q, k, v, dO and the two row
    statistics backward.  A plain kernel that still carried a map would
    read one more.  The three with one query head a KV head stay byte for
    byte; the grouped-KV call's backward is one kernel since PR 44
    (``flash_group_bwd``: the pair's six operands, once) and its forward the
    resident form since PR 60 (``flash_resident_fwd``: q, k, v)."""
    from horovod_tpu.ops import flash_attention as fa

    one = SingleDeviceSharding(v5e[0])

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if entry == "proj":
        def loss(x, w):
            return fa.flash_qkv_proj(x, w, h, causal=True).astype(
                jnp.float32).sum()
        shapes = (s(b, t, h * D), s(h * D, 3 * h * D, dtype=jnp.float32))
    else:
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        shapes = (s(b, t, h, D), s(b, t, hkv, D), s(b, t, hkv, D))
    lowered = jax.jit(jax.grad(loss, argnums=range(len(shapes)))).lower(
        *shapes)
    operands = (6, 6, 3) if len(kernels) == 3 else (6, 3)
    assert custom_calls(lowered.as_text()) == list(zip(kernels, operands))
