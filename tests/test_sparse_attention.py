"""Learned sparse attention's pieces (``ops/sparse_select.py`` and the
selected path of ``ops/flash_attention.py``, a KV group a grid step),
interpreted on the CPU:

* the flash kernels under a selection map against a dense masked softmax,
  forward, log-sum-exp and gradients — 1 to 16 query heads a KV head,
  several blocks, a length padded to its blocks, rows whose first key
  tiles are empty — and, under a map of ones, the causal kernels' without
  one; the backward in both forms ``_plan`` knows, the dq / dk-dv pair and
  the one fused kernel (the ``backward`` fixture), and the fused kernel's
  float32 parts in its jaxpr;
* the exact top-k against a sort, ties included, and the same ``S_t``
  whatever the tile;
The indexer's loss — ``L_I`` and its gradient against the dense reference,
at every tiling of the KL pass — is ``test_sparse_kl.py``'s (one file until
PR 56; a file is one worker's job under ``--dist loadfile``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import sparse_select as ss
from horovod_tpu.ops.flash_attention import (
    flash_attention, flash_attention_auto)

from _once import out_and_grads


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def qkv(B, T, H, Hkv, D, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (B, T, h, D))
                 for key, h in zip(keys, (H, Hkv, Hkv)))


def random_selection(B, T, share=0.3, seed=3):
    """A causal map in which every query reads itself and ``share`` of the
    earlier keys."""
    picked = jax.random.uniform(jax.random.PRNGKey(seed), (B, T, T)) < share
    picked |= jnp.eye(T, dtype=bool)
    return (picked & jnp.tril(jnp.ones((T, T), bool))).astype(jnp.int8)


@pytest.fixture(params=["pair", "fused"])
def backward(request, monkeypatch):
    """The backward form under a selection, as ``_plan`` picks it: the one
    fused kernel wherever a KV head's two float32 gradients fit their
    budget (every size here), the dq / dk-dv pair where they do not — the
    budget is 0 for ``pair``.  ``seen`` collects the plans of the calls."""
    if request.param == "pair":
        monkeypatch.setattr(fa, "_FUSED_RESIDENT_BYTES", 0)
    seen = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **kw: seen.append(plan(**kw)) or seen[-1])
    jax.clear_caches()          # the drivers' traces do not key on the budget
    yield seen
    assert {p.bwd for p in seen if p.blocks} == {
        {"pair": "group", "fused": "group_fused"}[request.param]}
    if request.param == "pair":     # "fused" left what any other test leaves
        jax.clear_caches()


def equations(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (kernel bodies,
    branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def dense_oracle(q, k, v, select):
    H, Hkv, D = q.shape[2], k.shape[2], q.shape[3]
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, H // Hkv, axis=2))
    s = jnp.where(select[:, None] != 0, s / np.sqrt(D), -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1),
                      jnp.repeat(v, H // Hkv, axis=2))


@pytest.mark.parametrize("B,T,H,Hkv,D,block", [
    (1, 256, 8, 1, 128, 64), (2, 128, 4, 2, 128, 128),
    (1, 192, 2, 2, 256, 64)],
    ids=["8Q_1KV_16_tiles", "one_tile", "heads_of_256"])
def test_selected_flash_equals_the_dense_masked_oracle(B, T, H, Hkv, D,
                                                       block):
    q, k, v = qkv(B, T, H, Hkv, D)
    select = random_selection(B, T)
    weight = jax.random.normal(jax.random.PRNGKey(5), (B, T, H, D))

    def ours(q, k, v):
        out, lse = flash_attention(q, k, v, block_q=block, block_k=block,
                                   interpret=True, select=select)
        assert lse.shape == (B, H, T)
        return out

    with jax.default_matmul_precision("highest"):
        (out, got), (want, ref) = (
            out_and_grads(f, lambda o: (o * weight).sum(), q, k, v)
            for f in (ours, lambda *a: dense_oracle(*a, select)))
    assert rel(out, want) <= 2e-6
    assert max(rel(a, b) for a, b in zip(got, ref)) <= 5e-6


@pytest.mark.parametrize("T", [100, 200], ids=["padded_by_auto", "by_hand"])
def test_a_length_that_is_no_multiple_of_the_block(T, backward):
    """T 100 through ``flash_attention_auto`` (padded to 104, one block)
    and T 200 padded by hand to four blocks of 64: the map is padded with
    zeros, the padding's rows and columns are masked, and the result and
    the gradients are the unpadded oracle's — the fused backward's last
    key tile lies wholly in the padding and stays the zeros it began as."""
    q, k, v = qkv(1, T, 8, 1, 128, seed=2)
    select = random_selection(1, T, seed=4)

    def ours(q, k, v):
        if T == 100:
            return flash_attention_auto(q, k, v, select=select)[0]
        pad = [(0, 0), (0, 256 - T), (0, 0), (0, 0)]
        out, _ = flash_attention(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), block_q=64,
            block_k=64, interpret=True, seq_len=T,
            select=jnp.pad(select, [(0, 0), (0, 256 - T), (0, 256 - T)]))
        return out[:, :T]

    with jax.default_matmul_precision("highest"):
        (out, got), (want, ref) = (
            out_and_grads(f, lambda o: (o ** 2).sum(), q, k, v)
            for f in (ours, lambda *a: dense_oracle(*a, select)))
    assert rel(out, want) <= 2e-6
    assert max(rel(a, b) for a, b in zip(got, ref)) <= 5e-6


def test_a_map_of_ones_is_causal_flash():
    """``topk >= T`` selects every causal key.  17 key blocks, so the path
    without a selection runs the same grid forward and per-head pair: under
    a map of ones the kernels do its arithmetic, forward and backward —
    to the last bit but one (interpreted, the two bodies are compiled
    apart and the CPU contracts them differently: 1.8e-7 of 1)."""
    B, T, H, Hkv, D, block = 1, 136, 2, 1, 128, 8
    q, k, v = qkv(B, T, H, Hkv, D, seed=7)
    ones = jnp.ones((B, T, T), jnp.int8)
    assert fa._plan_for(q.reshape(B, T, H * D), H, D, (0, 0, 0), True, block,
                        block, block, block, True, kv_rep=2)[:1] == ("grid",)

    def loss(select):
        def f(q, k, v):
            out = flash_attention(q, k, v, block_q=block, block_k=block,
                                  interpret=True, select=select)
            out = out if select is None else out[0]
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)

    (_, out), grads = loss(ones)(q, k, v)
    (_, out0), grads0 = loss(None)(q, k, v)
    np.testing.assert_allclose(out, out0, rtol=0, atol=5e-7)
    for a, b in zip(grads, grads0):
        assert rel(a, b) <= 5e-7


def test_a_selection_is_an_int8_map_over_lane_aligned_heads():
    q, k, v = qkv(1, 64, 2, 1, 128)
    with pytest.raises(ValueError, match="int8"):
        flash_attention(q, k, v, interpret=True,
                        select=jnp.ones((1, 64, 64), jnp.int32))
    q, k, v = qkv(1, 64, 2, 1, 64)
    with pytest.raises(ValueError, match="lane-aligned"):
        flash_attention(q, k, v, interpret=True,
                        select=jnp.ones((1, 64, 64), jnp.int8))


def selected_lse(q, k, select):
    """The log-sum-exp of each head's selected scores, (B, H, T)."""
    H, Hkv, D = q.shape[2], k.shape[2], q.shape[3]
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, H // Hkv, axis=2))
    return jax.scipy.special.logsumexp(
        jnp.where(select[:, None] != 0, s / np.sqrt(D), -jnp.inf), axis=-1)


def against_the_oracle(q, k, v, select, block, out_tol=2e-6, grad_tol=5e-6):
    """Output, log-sum-exp and the three gradients of the selected flash
    against the dense masked softmax."""
    weight = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def ours(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_k=block,
                               interpret=True, select=select)

    with jax.default_matmul_precision("highest"):
        (out, lse), got = out_and_grads(
            ours, lambda o: (o[0] * weight).sum(), q, k, v)
        want, ref = out_and_grads(lambda *a: dense_oracle(*a, select),
                                  lambda o: (o * weight).sum(), q, k, v)
        assert rel(out, want) <= out_tol
        np.testing.assert_allclose(lse, selected_lse(q, k, select),
                                   rtol=2e-6, atol=2e-6)
    assert max(rel(a, b) for a, b in zip(got, ref)) <= grad_tol


@pytest.mark.parametrize("G", [1, 2, 4, 8, 16])
def test_the_group_form_at_every_group_size(G, backward):
    """``G`` query heads a KV head are ``G`` lane slices of one grid step,
    served from one decoded tile: two KV heads of 1 to 16 query heads,
    four tiles of 64, with the log-sum-exp of the selected scores as the
    second output; backward, the pair's two sweeps or the fused kernel's
    one, a KV head's dK and dV summed over its ``G`` heads either way."""
    B, T, Hkv, D = 1, 128, 2, 128
    q, k, v = qkv(B, T, G * Hkv, Hkv, D, seed=G)
    against_the_oracle(q, k, v, random_selection(B, T, seed=10 + G), 64)


@pytest.mark.parametrize("which", ["first_tile_empty", "itself_alone"])
def test_a_row_whose_selected_keys_all_lie_in_later_tiles(which, backward):
    """"Every query must select a key" holds over the row, not over a
    tile.  ``first_tile_empty``: the rows of the last two Q blocks read
    nothing of the first key tile (nor, every other one, of the second),
    so the running maximum is still ``_NEG_BIG`` when those tiles add
    their ones — and the first key the row does read wipes them.
    ``itself_alone``: every third row reads its own position and nothing
    else, the last key of the last live tile."""
    B, T, H, Hkv, D, block = 1, 256, 8, 1, 128, 64
    q, k, v = qkv(B, T, H, Hkv, D, seed=11)
    select = np.array(random_selection(B, T, share=0.4, seed=12))
    if which == "first_tile_empty":
        select[:, 128:, :64] = 0
        select[:, 128::2, 64:128] = 0
    else:
        select[:, ::3, :] = 0
        select[:, np.arange(0, T, 3), np.arange(0, T, 3)] = 1
    assert (select.sum(-1) > 0).all()
    against_the_oracle(q, k, v, jnp.asarray(select), block)


def test_a_row_that_selects_nothing_leaves_zeros_and_moves_nothing(backward):
    """A row without a key (the padding's are such) comes out 0 with a
    log-sum-exp of 0, and whatever cotangent it is handed reaches no
    gradient."""
    B, T, H, Hkv, D = 1, 128, 2, 1, 128
    q, k, v = qkv(B, T, H, Hkv, D, seed=13)
    select = np.array(random_selection(B, T, seed=14))
    select[:, 70] = 0

    def ours(q, k, v):
        return flash_attention(q, k, v, block_q=64, block_k=64,
                               interpret=True, select=jnp.asarray(select))

    out, lse = ours(q, k, v)
    assert not np.asarray(out[:, 70]).any() and not np.asarray(
        lse[:, :, 70]).any()
    grads = jax.grad(lambda *a: ours(*a)[0][:, 70].sum(), (0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() and not np.asarray(g).any()
               for g in grads)


def test_a_selection_takes_a_kv_group_a_grid_step():
    """Every plan without a selection is the one it was (the plan table of
    ``test_flash_attention.py``); with one, the group form each way,
    whatever the length, with the Q block halved until the group's heads
    hold at most 4,096 query rows a step under a 32 MB budget — 2,048, and
    512 a head, under Mosaic's default where the device backs no more —,
    and the backward fused into one kernel where its resident gradients
    fit.  The map's tiles a call fetches follow the plan."""
    seen = dict(T=2048, D=128, H=16, head_base=(0, 0, 0), itemsize=2,
                causal=True, block_q=1024, block_k=1024, bwd_block_q=1024,
                bwd_block_k=1024, interpret=False, manual_axes=False,
                vmem_headroom=True)
    assert fa._plan(**seen) == ("fullunroll", 512, 0, "grouped", 32, 256,
                                0.889, ())
    # (Grouped KV heads without a map: since PR 44 the fused kernel too,
    # in its own tiling — a step's heads hold 2 Mi score elements, not 4.)
    assert fa._plan(**seen, kv_rep=8) == (
        "fullunroll", 512, 0, "group_fused", 64, 0, 0.8,
        (1024, 1024, 512, 512))
    assert fa._plan(**seen, select=True) == (
        "group", 0, 32, "group_fused", 64, 0, 0.667, (1024,) * 4)
    keye = dict(seen, T=16_384, H=32, kv_rep=8)
    # (... and since PR 60 the resident forward, past T 4,096.)
    assert fa._plan(**keye) == ("resident", 256, 64, "group_fused", 64, 0,
                                0.97, (1024, 1024, 512, 512))
    assert fa._plan(**dict(keye, vmem_headroom=False))[:6] == (
        "grid", 0, 0, "per_head", 0, 0)
    # The backward is ONE kernel under 64 MB where a KV head's dK and dV,
    # 2 x T x D float32, fit 16 MiB and the device backs the budget — the
    # cell's T 16,384 at heads of 128 is the longest that does —, and the
    # dq / dk-dv pair otherwise: a longer sequence, wider heads at that
    # length, a device at Mosaic's default.
    assert fa._plan(**keye, select=True) == (
        "group", 0, 32, "group_fused", 64, 0, 0.941, (512, 1024, 512, 1024))
    assert fa._plan(**dict(keye, T=32_768), select=True)[3:5] == ("group", 32)
    assert fa._plan(**dict(keye, D=256), select=True)[3:5] == ("group", 32)
    assert fa._plan(**dict(keye, T=8192, D=256), select=True)[3:5] == (
        "group_fused", 64)
    assert fa._plan(**dict(keye, vmem_headroom=False), select=True) == (
        "group", 0, 0, "group", 0, 0, 0.941, (256, 1024, 256, 1024))
    for group in (1, 2, 4, 16):
        assert fa._plan(**dict(keye, kv_rep=group), select=True)[3:5] == (
            "group_fused", 64)
    for group, block_q in ((1, 1024), (4, 1024), (16, 256)):
        assert fa._plan(**dict(keye, kv_rep=group), select=True).blocks[0] \
            == block_q
    assert fa._plan(**dict(keye, kv_rep=1, vmem_headroom=False),
                    select=True).blocks == (512, 1024, 512, 1024)
    # The interpreted tests' blocks are run as they are given.
    assert fa._plan(**dict(keye, T=256, block_q=64, block_k=64,
                           bwd_block_q=64, bwd_block_k=64),
                    select=True).blocks == (64,) * 4


@pytest.mark.parametrize("form,sweeps", [("fused", 1), ("pair", 2)])
def test_the_tiles_of_the_map_a_call_fetches_follow_the_plan(monkeypatch,
                                                             form, sweeps):
    """``attn.select_tile_fetches`` at the cell's shape: 272 causal tiles
    of 512 x 1024 a KV head forward, and as many again for each sweep the
    backward makes — one fused, two as the pair (3,264 a layer until PR
    39, 2,176 since, on a device that backs the fused kernel's budget)."""
    if form == "pair":
        monkeypatch.setattr(fa, "_FUSED_RESIDENT_BYTES", 0)
    q = jax.ShapeDtypeStruct((1, 16_384, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16_384, 4, 128), jnp.bfloat16)
    assert fa.select_tile_fetches(q, k) == 4 * 272 * (1 + sweeps)
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: False)
    # Q blocks of 256 under Mosaic's default, and the pair whatever fits.
    assert fa.select_tile_fetches(q, k) == 4 * 544 * 3


def test_the_fused_backward_keeps_float32_sums_and_multiplies_in_bfloat16():
    """The cell's ``correct`` does not see a rounded accumulator, so the
    traced kernel is read: ``p`` and ``dS`` are float32, every one of a
    head's five products takes the configuration's bfloat16 operands and
    leaves float32, and ``dq``, ``dK``, ``dV`` are summed in float32
    scratch — the two resident ones (T, D) — before their one rounding."""
    B, T, H, Hkv, D, block = 1, 256, 2, 1, 128, 128
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv(B, T, H, Hkv, D))
    select = random_selection(B, T)
    jax.clear_caches()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=True,
            select=select)[0].astype(jnp.float32).sum(), (0, 1, 2)))(q, k, v)

    calls = {eqn.params["name"]: eqn for eqn in equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert sorted(calls) == ["flash_select_bwd", "flash_select_fwd"]
    call = calls["flash_select_bwd"]
    assert [(v.aval.shape, str(v.aval.dtype)) for v in call.outvars] == [
        ((B, T, H * D), "bfloat16"), ((B, T, D), "bfloat16"),
        ((B, T, D), "bfloat16")]
    body = call.params["jaxpr"]
    refs = [(v.aval.shape, str(v.aval.dtype)) for v in body.invars]
    # The scratch follows 7 operands and 3 results: the decoded tile, dq of
    # the group's heads, and the KV head's whole dK and dV.
    assert refs[10:] == [((block, block), "float32"),
                         ((H // Hkv, block, D), "float32"),
                         ((T, D), "float32"), ((T, D), "float32")]
    eqns = list(equations(body))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    # Two heads, a masked and an unmasked tile body decoded apart from the
    # heads' loop: five products a head, once.
    assert len(dots) == 5 * (H // Hkv)
    for e in dots:
        assert {str(v.aval.dtype) for v in e.invars} == {"bfloat16"}
        assert str(e.outvars[0].aval.dtype) == "float32"
    exps = [e for e in eqns if e.primitive.name == "exp"]
    assert len(exps) == H // Hkv
    assert all(str(e.outvars[0].aval.dtype) == "float32" for e in exps)
    # What is added into the scratch is float32: no accumulator is rounded
    # on its way.  The only casts to bfloat16 are a head's ``p`` and ``dS``
    # as operands, its dq's store, and the stores of dK and dV.
    to_bf16 = [e for e in eqns if e.primitive.name == "convert_element_type"
               and str(e.outvars[0].aval.dtype) == "bfloat16"]
    assert len(to_bf16) == 3 * (H // Hkv) + 2
    jax.clear_caches()


def test_a_halved_q_block_is_the_same_attention(monkeypatch, backward):
    """The plan's own Q block is a schedule detail: with the rows a step
    cut to 1,024, eight heads run blocks of 128 against key tiles of 256
    where the caller said 256 — the same output, statistics and gradients
    as at the caller's blocks, forward and backward (the fused kernel then
    adds two Q blocks' worth into each slice of dK and dV for one)."""
    B, T, H, Hkv, D = 1, 512, 8, 1, 128
    q, k, v = qkv(B, T, H, Hkv, D, seed=15)
    select = random_selection(B, T, seed=16)

    def run():
        def f(q, k, v):
            out, lse = flash_attention(q, k, v, block_q=256, block_k=256,
                                       interpret=True, select=select)
            return (out ** 2).sum(), (out, lse)
        jax.clear_caches()
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)

    (_, (out, lse)), grads = run()
    monkeypatch.setattr(fa, "_GROUP_ROWS", 1024)
    (_, (out2, lse2)), grads2 = run()
    assert {p.blocks for p in backward} == {(256,) * 4, (128, 256, 128, 256)}
    np.testing.assert_allclose(out2, out, rtol=0, atol=2e-6)
    np.testing.assert_allclose(lse2, lse, rtol=0, atol=2e-6)
    for a, b in zip(grads2, grads):
        assert rel(a, b) <= 2e-6
    with jax.default_matmul_precision("highest"):
        assert rel(out2, dense_oracle(q, k, v, select)) <= 2e-3


# ------------------------------------------------------------ the top-k


def tied_scores(rng, rows, W):
    """Scores rounded to halves tie by the dozen; a row's second half, and
    a whole row but three keys, are -inf (not causal)."""
    s = np.round(rng.normal(size=(3, rows, W)).astype(np.float32) * 2) / 2
    s += 0.0        # no -0: XLA's order has it under +0, numpy's sort beside
    s[1, :, W // 2:] = -np.inf
    s[2, 5, 3:] = -np.inf
    return s


@pytest.mark.parametrize("W,k,bands", [
    (64, 16, None), (100, 100, None), (37, 5, None), (256, 300, None),
    (512, 100, None),
    # The kernel (interpreted) on whole lane tiles, the last of ``bands``
    # bands ``W`` wide: ``k >= W``, one band, two, and four — three of them
    # a band's width and not ``T``.
    (256, 300, 1), (512, 100, 1), (1024, 64, 2), (512, 48, 4)],
    ids=lambda v: "rows" if v is None else str(v))
def test_the_exact_top_k_against_a_sort_ties_included(W, k, bands):
    """The set is the first ``k`` of a stable descending sort — a tie to
    the lower index — without what is not causal, and ``lax.top_k``'s.
    ``bands``: through ``index_threshold``, which writes every band's rows
    of the (T, T) map — zeros past the band's width — and must give
    ``select_rows``' set bit for bit."""
    rng = np.random.default_rng(W)
    if bands is None:
        scores, select, lses = [tied_scores(rng, 40, W)], None, None
    else:
        rows = W // bands
        scores = [tied_scores(rng, rows, (b + 1) * rows)
                  for b in range(bands)]
        select, lses, _ = ss.index_threshold(
            *map(jnp.asarray, scores), topk=k, block_rows=64, interpret=True)
        assert select.dtype == jnp.int8 and select.shape == (3, W, W)
    for b, s in enumerate(scores):
        rows, width = s.shape[1:]
        chosen, lse = jax.jit(lambda x: ss.select_rows(x, k))(s)
        if select is not None:
            band = np.asarray(select)[:, b * rows:(b + 1) * rows]
            assert (band[..., :width] == np.asarray(chosen)).all()
            assert not band[..., width:].any()
            chosen, lse = band[..., :width] != 0, lses[:, b * rows:width]
        order = np.argsort(-s, axis=-1, kind="stable")[..., :k]
        want = np.zeros(s.shape, bool)
        np.put_along_axis(want, order, True, -1)
        want &= np.isfinite(s)
        assert (np.asarray(chosen) == want).all()
        top = np.zeros(s.shape, bool)
        np.put_along_axis(
            top, np.asarray(jax.lax.top_k(s, min(k, width))[1]), True, -1)
        assert (np.asarray(chosen) == (top & np.isfinite(s))).all()
        np.testing.assert_allclose(
            lse, jax.scipy.special.logsumexp(jnp.where(want, s, -jnp.inf),
                                             -1), rtol=1e-6)


def test_the_tie_bisection_runs_only_where_ties_outnumber_their_room():
    """A strip's flag, and ``tie_tiles`` from it: 0 on scores that do not
    tie, 0 where the keys at the k-th score all have room (each is taken),
    1 where they outnumber it — the lower indices are taken, as
    ``lax.top_k`` breaks a tie — and ``select_rows``' set every time.  A
    row with no more scores than ``k`` takes them all and asks for no tie
    bisection whatever ties at -inf."""
    W, k = 128, 8
    rng = np.random.default_rng(3)
    free = rng.permutation(W * W).reshape(1, W, W).astype(np.float32)
    room = free.copy()
    room[0, 7] = -9.0
    room[0, 7, :4] = 1e6 + np.arange(4)   # four above the k-th ...
    room[0, 7, 100:104] = -5.0            # ... and four AT it: 8 = k
    crowded = free.copy()
    crowded[0, 100, 10::16] = 1e6         # eight at the top ...
    crowded[0, 100, 3] = 1e6              # ... and a ninth, for k = 8
    short = free.copy()
    short[0, :, 5:] = -np.inf             # five scores a row, k = 8
    for scores, flags in ((free, [0, 0]), (room, [0, 0]),
                          (crowded, [0, 1]), (short, [0, 0])):
        select, lse, ties = ss.index_threshold(
            jnp.asarray(scores), topk=k, block_rows=64, interpret=True)
        assert np.asarray(ties).tolist() == [flags]
        chosen, want_lse = ss.select_rows(jnp.asarray(scores), k)
        assert (np.asarray(select) == np.asarray(chosen)).all()
        np.testing.assert_allclose(lse, want_lse, rtol=1e-6)
    assert np.flatnonzero(np.asarray(select)[0, 3]).tolist() == [0, 1, 2, 3, 4]
    taken = np.asarray(ss.index_threshold(
        jnp.asarray(room), topk=k, block_rows=64, interpret=True)[0])[0, 7]
    assert np.flatnonzero(taken).tolist() == [0, 1, 2, 3, 100, 101, 102, 103]
    taken = np.asarray(ss.index_threshold(
        jnp.asarray(crowded), topk=k, block_rows=64,
        interpret=True)[0])[0, 100]
    assert np.flatnonzero(taken).tolist() == [3, *range(10, 10 + 16 * 7, 16)]


def test_the_selection_s_plan_follows_the_strip_s_bytes():
    """``_threshold_plan``, of shapes and the device's budget only: a strip
    of EVERY band is in VMEM, so the cell's four bands of 4,096 rows take
    strips of 128 rows under the stated 64 MB (256 would ask 94) and none
    under Mosaic's default; fewer or narrower bands take more rows; a
    caller's smaller ``tile`` caps the strip; rows that are not whole lane
    tiles, and a band that is not whole strips, keep the XLA form."""
    assert ss._threshold_plan(4096, 4, 512, True) == (
        128, ss._THRESHOLD_VMEM_MB)
    assert ss._threshold_plan(4096, 4, 512, False) == (0, 0)
    assert ss._threshold_plan(2048, 4, 512, True)[0] == 256
    assert ss._threshold_plan(2048, 4, 512, False) == (64, 0)
    assert ss._threshold_plan(4096, 1, 512, False) == (256, 0)
    assert ss._threshold_plan(2048, 4, 64, True)[0] == 64
    assert ss._threshold_plan(128, 4, 128, True)[0] == 128
    assert ss._threshold_plan(64, 4, 64, True)[0] == 0
    assert ss._threshold_plan(384, 1, 256, True)[0] == 128
    assert ss._threshold_plan(16384, 4, 512, True)[0] == 0
    for rows in ss._THRESHOLD_ROWS:
        assert rows % ss._THRESHOLD_GROUP == 0 == rows % 32   # int8 tiles


def indexer_inputs(B, T, HI, DI, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (B, T, HI, DI)),
            jax.random.normal(keys[1], (B, T, DI)),
            jax.random.normal(keys[2], (B, T, HI)))


def test_tiles_of_64_and_128_give_the_same_selection():
    """The tile is where scores and top-k are computed, not a unit of
    selection: ``S_t`` is the dense reference's whatever it is, each query
    keeps ``min(t + 1, topk)`` keys, all of them causal."""
    B, T, topk = 2, 256, 48
    qi, ki, w = indexer_inputs(B, T, 4, 64)
    q, k, v = qkv(B, T, 2, 1, 128)
    maps = [ss.index_select(qi, ki, w, topk, tile=tile, interpret=True)[0]
            for tile in (64, 128, 256)]
    want = ss.sparse_attention_reference(q, k, v, qi, ki, w, topk)[2]
    for got in maps:
        assert got.dtype == jnp.int8 and (got == want).all()
    per_query = np.asarray(want.sum(-1))
    assert (per_query == np.minimum(np.arange(T) + 1, topk)).all()
    assert not np.triu(np.asarray(want[0]), 1).any()
    selected, live = ss.selection_counters(want, 64)
    assert float(selected) == np.minimum(np.arange(T) + 1, topk).mean()
    assert 0 < float(live) <= 1


def test_the_scores_are_made_in_bands_of_the_causal_width():
    """Four bands at T 16,384 (a band's rows against the keys up to its
    last row), one where the sequence is no longer than ``topk`` tiles."""
    assert ss._bands(16384, 512, 2048) == 4
    assert ss._bands(4096, 512, 2048) == 2
    assert ss._bands(2048, 512, 2048) == 1
    B, T = 1, 512
    qi, ki, w = indexer_inputs(B, T, 2, 64, seed=8)
    whole = ss.index_scores(qi.transpose(0, 2, 1, 3), ki, w, interpret=True)
    band = ss.index_scores(qi.transpose(0, 2, 1, 3), ki, w, row0=256,
                           rows=128, interpret=True)
    assert band.shape == (B, 128, 384)
    np.testing.assert_array_equal(band, whole[:, 256:384, :384])
    products = jnp.einsum("bthd,bsd->bhts", qi, ki)
    want = jnp.einsum("bth,bhts->bts", w, jax.nn.relu(products)) / np.sqrt(
        2 * 64)
    causal = np.tril(np.ones((T, T), bool))
    np.testing.assert_allclose(np.where(causal, whole[0], 0),
                               np.where(causal, want[0], 0), atol=1e-5)
    assert np.isneginf(np.asarray(whole[0])[~causal]).all()


def test_the_bands_write_one_map_no_pad_no_concatenate(monkeypatch):
    """``index_select`` lowered for the TPU where the plan finds a strip:
    a scores kernel a band, then ONE ``index_threshold`` whose operands are
    the four bands' scores and whose first result is the whole int8 map,
    and no ``pad`` or ``concatenate`` with an int8 array of ``T`` columns
    on either side — which the XLA form, taken where there is no strip,
    does hold.  The share of strips whose tie bisection ran comes with
    both forms."""
    import re

    B, T, topk = 1, 512, 48
    shapes = (jax.ShapeDtypeStruct((B, T, 2, 64), jnp.bfloat16),
              jax.ShapeDtypeStruct((B, T, 64), jnp.bfloat16),
              jax.ShapeDtypeStruct((B, T, 2), jnp.float32))

    def lowered():
        jax.clear_caches()
        return jax.jit(lambda *a: ss.index_select_counted(
            *a, topk, tile=128)).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()

    def map_moves(text):
        return [line for line in text.splitlines()
                if re.search(r"stablehlo\.(pad|concatenate)", line)
                and f"x{T}xi8>" in line]

    text = lowered()
    assert re.findall(r'kernel_name = "([^"]+)"', text) == [
        *["index_scores"] * 4, "index_threshold"]
    call, = [line for line in text.splitlines()
             if 'kernel_name = "index_threshold"' in line]
    assert call[call.rindex(" : ("):].startswith(
        " : (tensor<1x128x128xf32>, tensor<1x128x256xf32>, "
        "tensor<1x128x384xf32>, tensor<1x128x512xf32>) -> "
        "(tensor<1x512x512xi8>, ")
    assert "output_operand_alias" not in call and not map_moves(text)
    monkeypatch.setattr(ss, "_threshold_plan", lambda *a: (0, 0))
    text = lowered()
    assert re.findall(r'kernel_name = "([^"]+)"', text) == ["index_scores"] * 4
    assert len(map_moves(text)) >= 2          # the pads, the concatenate
    jax.clear_caches()

    # (Positive products: no relu makes a score exactly zero, so none tie.)
    qi, ki, w = (jnp.abs(a) for a in indexer_inputs(B, T, 2, 64, seed=9))
    want = ss.index_select_counted(qi, ki, w, topk, tile=128, interpret=True)
    assert float(want[2]) == 1.0
    monkeypatch.undo()
    got = ss.index_select_counted(qi, ki, w, topk, tile=128, interpret=True)
    assert (got[0] == want[0]).all() and float(got[2]) == 0.0
    np.testing.assert_allclose(got[1], want[1], rtol=1e-6)
    # Halves tie by the dozen: every strip past the first rows' runs the
    # bisection on the key index.
    tied = ss.index_select_counted(jnp.round(qi), jnp.round(ki),
                                   jnp.round(w), topk, tile=128,
                                   interpret=True)
    assert float(tied[2]) == 1.0
