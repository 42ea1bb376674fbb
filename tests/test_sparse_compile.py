"""Learned sparse attention compiled for a described TPU v5e (see
``tests/_v5e.py``): ``flash_attention(select=map)``, the KL pass and the
selection's threshold kernel at ``keye_1chip``'s widths (1 sequence of
16,384, Keye-VL-2.0's) and at every tiling their plans admit.  The
interpreted tests of the same kernels are ``test_sparse_attention.py`` and
``test_sparse_kl.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _v5e import (  # noqa: F401
    custom_calls, pallas_calls, scoped_vmem_mb, v5e)


@pytest.mark.parametrize("headroom,T", [(True, 16_384), (False, 16_384),
                                        (True, 32_768)],
                         ids=["512x1024_fused_64MB", "256x1024_pair_default",
                              "512x1024_pair_32MB_T32768"])
def test_selected_attention_fwd_bwd_at_keye_widths(v5e, monkeypatch,
                                                   headroom, T):
    """One layer's sparse attention as ``GroupedQueryAttention(indexer=…)``
    calls it — 32 query over 4 KV heads of 128 at T 16,384, an indexer of
    16 heads of 64 that keeps 2,048 keys a query — compiles for the chip:
    the scores in four bands (``index_scores``), the exact top-k ONE kernel
    over the four bands' strips (``index_threshold``: strips of 128 rows
    under its stated 64 MB, each writing its rows of the one int8 map, so
    the compiled step holds no pad, concatenate or copy of it), with no
    sort and no approximate top-k, the selected attention a KV
    group a grid step (``flash_select_*``: the int8 (1, T, T) map an
    operand of each, grids over the 4 KV heads, the eight heads of a group
    one (block, 1024) block), and the KL pass (``index_kl``).  At every
    tiling ``_plan`` admits: Q blocks of 512 with the backward ONE kernel
    under its 64 MB budget (``flash_select_fwd`` and ``flash_select_bwd``:
    two calls, the map read twice); Q blocks of 256 and the dq / dk-dv pair
    under Mosaic's default where the device backs no more; and the pair at
    Q blocks of 512 under 32 MB where a KV head's dK and dV no longer fit
    their 16 MiB (T 32,768; the last two the kernels alone).  The map is
    256 MiB; a band's float32 scores are at most 1 GiB and no (T, T)
    float32 array of all heads is ever made."""
    import re

    from horovod_tpu.ops import (
        _pallas, flash_attention as fa, sparse_select)

    # Every family's probe: the KL pass is lowered with head-room only
    # (the fused case), where it saw the CPU's "True" before as well.
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: headroom)
    jax.clear_caches()       # the drivers' traces do not key on the device
    one = SingleDeviceSharding(v5e[0])
    B, H, Hkv, D, HI, DI, topk = 1, 32, 4, 128, 16, 64, 2048
    block_q = 512 if headroom else 256
    fused = headroom and T == 16_384

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def selected(q, k, v, select):
        out, lse = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                      block_k=1024, select=select)
        return out.astype(jnp.float32).sum(), lse

    def loss(q, k, v, qi, ki, w):
        select, lse_i = sparse_select.index_select(qi, ki, w, topk, tile=512)
        out, lse = selected(q, k, v, select)
        return out + sparse_select.index_kl(qi, ki, w, q, k, lse, select,
                                            lse_i)

    qkv = (s(B, T, H, D), s(B, T, Hkv, D), s(B, T, Hkv, D))
    alone = jax.value_and_grad(lambda *a: selected(*a)[0], argnums=(0, 1, 2))
    calls = {name: (grid, avals) for name, grid, avals in pallas_calls(
        jax.make_jaxpr(alone)(*qkv, s(B, T, T, dtype=jnp.int8)).jaxpr)}
    n, nk = T // block_q, T // 1024
    backward = ({"flash_select_bwd": (B, Hkv, n, nk)} if fused else
                {"flash_select_dq": (B, Hkv, n, nk),
                 "flash_select_dkdv": (B, Hkv, nk, n)})
    assert {name: grid for name, (grid, _) in calls.items()} == {
        "flash_select_fwd": (B, Hkv, n, nk), **backward}
    for grid, avals in calls.values():
        assert [(a.shape, str(a.dtype)) for a in avals][-1] == (
            (B, T, T), "int8")
    if not fused:
        lowered = jax.jit(alone).lower(*qkv, s(B, T, T, dtype=jnp.int8))
        assert custom_calls(lowered.as_text()) == [
            ("flash_select_dkdv", 7), ("flash_select_dq", 7),
            ("flash_select_fwd", 4)]
        assert set(scoped_vmem_mb(lowered.as_text()).values()) == {
            32 if headroom else 0}
        lowered.compile()
        return

    lowered = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(
        *qkv, s(B, T, HI, DI), s(B, T, DI), s(B, T, HI))
    lowered_text = lowered.as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', lowered_text)
    assert sorted(set(names)) == [
        "flash_select_bwd", "flash_select_fwd", "index_kl", "index_scores",
        "index_threshold"]
    assert names.count("index_scores") == 4                # the bands
    assert names.count("index_threshold") == 1
    assert not re.search(r"stablehlo\.(pad|concatenate)[^\n]*x16384xi8>",
                         lowered_text)
    # The map is an operand of the two kernels, whose row statistics come
    # a KV head (4), not a query head (32); the backward runs under the
    # plan's own budget.
    selected_calls = [line for line in lowered_text.splitlines()
                      if 'kernel_name = "flash_select_' in line]
    assert len(selected_calls) == 2
    for line in selected_calls:
        operands = line[line.rindex(" : ("):]
        assert operands.count("tensor<1x16384x16384xi8>") == 1
        assert "tensor<1x4x16384x8xf32>" in operands
        assert "x32x16384" not in operands
    assert custom_calls(lowered_text)[:2] == [
        ("flash_select_bwd", 7), ("flash_select_fwd", 4)]
    limits = scoped_vmem_mb(lowered_text)
    assert (limits["flash_select_fwd"], limits["flash_select_bwd"]) == (
        32, fa._SELECT_FUSED_VMEM_MB) == (32, 64)
    assert limits["index_kl"] == sparse_select._KL_VMEM_MB == 96
    assert sparse_select._threshold_plan(T // 4, 4, 512, True) == (
        128, limits["index_threshold"])
    assert limits["index_threshold"] == sparse_select._THRESHOLD_VMEM_MB == 64
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "approx" not in text.lower() and " sort(" not in text
    assert "s8[1,16384,16384]" in text
    assert not re.search(
        r"= s8\[1,16384,16384\]\S* (copy|pad|concatenate|fusion)\(", text)
    assert "f32[1,16,16384,16384]" not in text
    assert "f32[1,16384,16,16384]" not in text
    _, grads = compiled.out_info
    assert [g.shape for g in grads] == [
        (B, T, H, D), (B, T, Hkv, D), (B, T, Hkv, D), (B, T, HI, DI),
        (B, T, DI), (B, T, HI)]
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 3.0 * 2 ** 30, plan / 2 ** 30


# The scoped VMEM the compiler counts for the fused backward alone at the
# cell's widths and T 16,384, at the Q block `_group_block_q` gives each group
# size (MB, found by bisection on the limit in the sandbox, PR 39): G 1 at
# 1024 rows 46.9, G 2 50.3, G 4 55.3, G 8 at 512 46.8, G 16 at 256 43.9.
FUSED_BWD_COUNTED_MB = 56


@pytest.mark.parametrize("G", [1, 2, 4, 8, 16])
def test_the_fused_selected_backward_compiles_at_every_group_size(
        v5e, monkeypatch, G):
    """``flash_select_fwd`` and ``flash_select_bwd`` at 4 KV heads of 128
    and T 16,384 with 1 to 16 query heads a KV head, each at the Q block
    the plan gives it — and the backward under 56 MB, the most the compiler
    counts at any of them, so that the plan's 64 leaves 8 over."""
    from horovod_tpu.ops import _pallas, flash_attention as fa

    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: True)
    assert fa._SELECT_FUSED_VMEM_MB >= FUSED_BWD_COUNTED_MB + 8
    monkeypatch.setattr(fa, "_SELECT_FUSED_VMEM_MB", FUSED_BWD_COUNTED_MB)
    jax.clear_caches()
    one = SingleDeviceSharding(v5e[0])
    B, T, Hkv, D = 1, 16_384, 4, 128

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(q, k, v, select):
        return fa.flash_attention(q, k, v, causal=True, select=select)[
            0].astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        s(B, T, Hkv * G, D), s(B, T, Hkv, D), s(B, T, Hkv, D),
        s(B, T, T, dtype=jnp.int8))
    assert custom_calls(lowered.as_text()) == [
        ("flash_select_bwd", 7), ("flash_select_fwd", 4)]
    assert scoped_vmem_mb(lowered.as_text()) == {
        "flash_select_fwd": 32, "flash_select_bwd": FUSED_BWD_COUNTED_MB}
    lowered.compile()
    jax.clear_caches()


# The scoped VMEM the compiler counts for the KL pass alone at the cell's
# widths and T 16,384, a tiling of ``sparse_select._KL_TILINGS`` each (MB,
# found by bisection on the limit in the sandbox, PR 41; the parent's
# kernel at 512 x 512: 51).
KL_COUNTED_MB = {(512, 512): 58, (256, 512): 28, (256, 256): 23,
                 (128, 256): 13, (128, 128): 12}


@pytest.mark.parametrize("tiling,headroom", [
    *((tiling, True) for tiling in KL_COUNTED_MB),
    ((128, 256), False), ((128, 128), False)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else (
        "raised" if v else "default"))
def test_the_kl_pass_compiles_at_every_tiling(v5e, monkeypatch, tiling,
                                              headroom):
    """``index_kl`` at 32 query over 4 KV heads of 128, 16 indexer heads of
    64 and T 16,384 at every tiling ``_kl_plan`` can choose: under the
    raised limit each compiles within what the compiler counted for it —
    the most, 58 MB, leaves ``_KL_VMEM_MB`` 38 over —, and the two that
    ``_kl_vmem_bytes`` admits under Mosaic's default 16 MB compile there,
    the first of them being the plan's choice without head-room.  One
    algorithm throughout: ``H + 3 H_I`` products a tile
    (``tests/test_sparse_attention.py``)."""
    from horovod_tpu.ops import _pallas, sparse_select as ss

    assert tuple(KL_COUNTED_MB) == ss._KL_TILINGS
    B, T, H, Hkv, D, HI, DI = 1, 16_384, 32, 4, 128, 16, 64
    shape = (T, H, Hkv, D, HI, DI, 2)
    assert ss._kl_plan(*shape, True) == (512, 512, ss._KL_VMEM_MB)
    assert ss._kl_plan(*shape, False) == (128, 256, 0)
    assert max(KL_COUNTED_MB.values()) + 8 <= ss._KL_VMEM_MB
    limit = KL_COUNTED_MB[tiling] if headroom else 0
    assert headroom or ss._kl_vmem_bytes(*tiling, *shape[1:]) <= (
        ss._MOSAIC_DEFAULT_VMEM_MB * 2 ** 20)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: headroom)
    monkeypatch.setattr(ss, "_KL_TILINGS", (tiling,))
    monkeypatch.setattr(ss, "_KL_VMEM_MB", limit)
    one = SingleDeviceSharding(v5e[0])

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    lowered = jax.jit(lambda *a: ss._kl_pass(
        *a, scale=D ** -0.5, interpret=False)).lower(
        s(B, T, HI, DI), s(B, T, DI), s(B, T, HI, dtype=jnp.float32),
        s(B, T, H, D), s(B, T, Hkv, D), s(B, H, T, dtype=jnp.float32),
        s(B, T, T, dtype=jnp.int8), s(B, T, dtype=jnp.float32))
    assert custom_calls(lowered.as_text()) == [("index_kl", 8)]
    assert scoped_vmem_mb(lowered.as_text()) == {"index_kl": limit}
    kl, dqi, dki, dw = lowered.compile().out_info
    assert [a.shape for a in (kl, dqi, dki, dw)] == [
        (B, T), (B, T, HI, DI), (B, T, DI), (B, T, HI)]


# The scoped VMEM the compiler counts for the selection's kernel alone:
# {(rows a band, bands): {strip rows: MB}} (found by bisection on the limit
# in the sandbox, PR 47).  The first is the cell's.
THRESHOLD_COUNTED_MB = {
    (4096, 4): {256: 93, 128: 49, 64: 27},
    (2048, 4): {256: 47, 128: 25, 64: 14},
    (4096, 2): {256: 31, 128: 17, 64: 10},
    (4096, 1): {256: 12, 128: 7, 64: 5}}


@pytest.mark.parametrize("rows,bands", THRESHOLD_COUNTED_MB,
                         ids=lambda v: str(v))
def test_the_selection_compiles_at_every_strip(v5e, rows, bands):
    """``index_threshold`` over one, two and four bands at T 4,096 to
    16,384, at every strip ``_threshold_plan`` can take (256 to 64 rows; a
    strip of every band in VMEM, the keys of one group of 64 rows in
    scratch): each compiles within what the compiler counted for it,
    ``_threshold_vmem_bytes`` says no less and at most 2 MB more, and
    whatever the formula admits under the stated 64 MB or under Mosaic's
    default 16 MB compiles there — at the cell's shape strips of 128 rows
    with 14 MB to spare, and nothing under the default."""
    from horovod_tpu.ops import sparse_select as ss

    counted_mb = THRESHOLD_COUNTED_MB[rows, bands]
    assert tuple(counted_mb) == ss._THRESHOLD_ROWS
    one = SingleDeviceSharding(v5e[0])
    operands = [jax.ShapeDtypeStruct((1, rows, (b + 1) * rows), jnp.float32,
                                     sharding=one) for b in range(bands)]
    T = rows * bands

    def first_under(mb):
        return next((r for r in ss._THRESHOLD_ROWS
                     if ss._threshold_vmem_bytes(r, rows, bands)
                     <= mb * 2 ** 20), 0)

    assert ss._threshold_plan(rows, bands, 512, True) == (
        first_under(ss._THRESHOLD_VMEM_MB), ss._THRESHOLD_VMEM_MB)
    assert ss._threshold_plan(rows, bands, 512, False) == (
        first_under(ss._MOSAIC_DEFAULT_VMEM_MB), 0)
    if (rows, bands) == (4096, 4):
        assert (first_under(64), first_under(16)) == (128, 0)
    for block_rows, counted in counted_mb.items():
        said = ss._threshold_vmem_bytes(block_rows, rows, bands) / 2 ** 20
        assert counted - 1 <= said <= counted + 2, (block_rows, said)
        limits = {counted}
        if said <= ss._MOSAIC_DEFAULT_VMEM_MB:
            limits.add(0)
        if said <= ss._THRESHOLD_VMEM_MB:
            limits.add(ss._THRESHOLD_VMEM_MB)
        for limit in limits:
            lowered = jax.jit(lambda *a, strip=block_rows, mb=limit:
                              ss.index_threshold(*a, topk=2048,
                                                 block_rows=strip, vmem_mb=mb)
                              ).lower(*operands)
            text = lowered.as_text()
            assert custom_calls(text) == [("index_threshold", bands)]
            assert scoped_vmem_mb(text) == {"index_threshold": limit}
            assert "output_operand_alias" not in text
            select, lse, ties = lowered.compile().out_info
            assert (select.shape, lse.shape, ties.shape) == (
                (1, T, T), (1, T), (1, T // block_rows))
            assert str(select.dtype) == "int8"
    jax.clear_caches()


def test_the_selection_keeps_its_xla_form_where_no_strip_fits(v5e,
                                                              monkeypatch):
    """Without head-room the indexer's selection still lowers: at the
    cell's T 16,384 as ``select_rows`` — the parent's pads and concatenate,
    no ``index_threshold``: a strip of 64 rows of every band is 28 MB by
    ``_threshold_vmem_bytes``, over Mosaic's default 16 —, at half that
    length with strips of 64 rows under the default (``vmem`` 0 on
    ``index_threshold``); at a T of 65,536 no strip fits the stated budget
    either."""
    import re

    from horovod_tpu.ops import _pallas, sparse_select as ss

    one = SingleDeviceSharding(v5e[0])

    def lowered(T, headroom):
        monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: headroom)
        jax.clear_caches()
        shapes = ((1, T, 16, 64), (1, T, 64), (1, T, 16))
        text = jax.jit(lambda *a: ss.index_select(*a, 2048)).lower(*(
            jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
            for shape in shapes)).as_text()
        return (re.findall(r'kernel_name = "([^"]+)"', text),
                scoped_vmem_mb(text),
                bool(re.search(r"stablehlo\.concatenate[^\n]*xi8>", text)))

    names, _, concatenated = lowered(16_384, False)
    assert names == ["index_scores"] * 4 and concatenated
    assert ss._threshold_plan(4096, 4, 512, False) == (0, 0)
    names, limits, concatenated = lowered(8192, False)
    assert names.count("index_threshold") == 1 and not concatenated
    assert limits["index_threshold"] == 0
    assert ss._threshold_plan(2048, 4, 512, False) == (64, 0)
    names, _, concatenated = lowered(65_536, True)
    assert names == ["index_scores"] * 4 and concatenated
    jax.clear_caches()
