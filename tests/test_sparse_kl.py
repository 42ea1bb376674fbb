"""The indexer's loss (``sparse_select.index_kl``, the KL pass), interpreted
on the CPU: ``L_I`` and its gradient against the dense reference at every
tiling ``_kl_plan`` can choose, that it moves the indexer's projections
alone, and the pass's products and float32 sums in its jaxpr.  The selection
and the selected flash kernels around it are ``test_sparse_attention.py``'s,
whose helpers these tests share.
"""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import sparse_select as ss
from horovod_tpu.ops.flash_attention import flash_attention

from _once import out_and_grads
from test_sparse_attention import (
    equations, indexer_inputs, qkv, random_selection, rel)


@pytest.fixture(params=[
    "512x512_96MB", "512x512_default_vmem", "256x512", "256x256", "128x256",
    "128x128"])
def kl_tiling(request, monkeypatch):
    """The KL pass at every tiling ``_kl_plan`` can choose, a case a
    tiling: the first of ``_KL_TILINGS`` under the raised budget and under
    Mosaic's default, then each later one alone — T is 512 in these tests:
    one tile, query blocks half the key tile, two to four square tiles a
    side.  ``seen`` collects the plans of the calls."""
    name = request.param
    faked = name == "512x512_default_vmem"
    if faked:
        # Every family's probe: the selected attention around the pass
        # is planned without head-room too, and its drivers' traces do
        # not key on the device.
        monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: False)
        jax.clear_caches()
    bq, bk = (int(n) for n in name.split("_")[0].split("x"))
    assert (bq, bk) in ss._KL_TILINGS
    monkeypatch.setattr(ss, "_KL_TILINGS", ((bq, bk),))
    seen = []
    plan = ss._kl_plan
    monkeypatch.setattr(
        ss, "_kl_plan", lambda *a: seen.append(plan(*a)) or seen[-1])
    yield seen
    assert set(seen) == {(bq, bk, 0 if faked else ss._KL_VMEM_MB)}
    if faked:
        jax.clear_caches()


@pytest.mark.parametrize("w_rows", ["normal", "zeros_and_negatives"])
def test_index_kl_and_its_gradient_against_the_reference(kl_tiling, w_rows):
    """The whole path — scores, selection, selected flash, KL pass — against
    the dense reference: the output, ``L_I``, and the gradients of ``out ·
    weight + 3 L_I`` on all six inputs.  ``zeros_and_negatives``: queries
    whose ``w`` is zero in every head (their scores tie at 0), heads whose
    ``w`` is zero everywhere and negative entries — ``dw`` there is
    ``Σ g relu(s)`` whatever ``w`` holds: the kernel's row product, which
    never divides by it."""
    B, T, H, Hkv, D, HI, DI, topk = 1, 512, 8, 1, 128, 4, 64, 48
    q, k, v = qkv(B, T, H, Hkv, D)
    qi, ki, w = indexer_inputs(B, T, HI, DI)
    if w_rows == "zeros_and_negatives":
        w = w.at[:, ::5].set(0.0).at[:, :, 1].set(0.0)
        w = w.at[:, 1::5, 2].set(-jnp.abs(w[:, 1::5, 2]))
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, D))

    def ours(q, k, v, qi, ki, w):
        select, lse_i = ss.index_select(qi, ki, w, topk, tile=128,
                                        interpret=True)
        out, lse = flash_attention(q, k, v, block_q=128, block_k=128,
                                   interpret=True, select=select)
        return out, ss.index_kl(qi, ki, w, q, k, lse, select, lse_i,
                                interpret=True)

    def theirs(*args):
        return ss.sparse_attention_reference(*args, topk)[:2]

    def total(out_and_kl):
        out, kl = out_and_kl
        return (out * weight).sum() + 3.0 * kl

    with jax.default_matmul_precision("highest"):
        ((out, kl), got), ((out_ref, kl_ref), ref) = (
            out_and_grads(f, total, q, k, v, qi, ki, w)
            for f in (ours, theirs))
        assert rel(out, out_ref) <= 2e-6
        assert abs(float(kl) - float(kl_ref)) <= 1e-6 * float(kl_ref) > 0
    errors = {n: rel(a, b) for n, a, b in zip(
        ("q", "k", "v", "qi", "ki", "w"), got, ref)}
    assert max(errors.values()) <= 1e-5, errors
    if w_rows == "zeros_and_negatives":
        # A gradient reaches w where w itself is zero.
        assert float(jnp.abs(got[5][:, ::5]).min(axis=-1).max()) > 0
        assert float(jnp.abs(got[5][:, :, 1]).max()) > 0


def test_index_kl_moves_the_indexer_alone(kl_tiling):
    """``L_I``'s gradient reaches ``qI``, ``kI`` and ``w`` and is zero on
    the attention's own q and k (``p`` is detached)."""
    B, T, topk = 1, 512, 32
    q, k, v = qkv(B, T, 2, 1, 128)
    qi, ki, w = indexer_inputs(B, T, 4, 64)
    select, lse_i = ss.index_select(qi, ki, w, topk, interpret=True)
    _, lse = flash_attention(q, k, v, interpret=True, select=select)
    grads = jax.grad(lambda *a: ss.index_kl(*a, lse, select, lse_i,
                                            interpret=True),
                     range(5))(qi, ki, w, q, k)
    assert all(float(jnp.abs(g).max()) > 0 for g in grads[:3])
    assert all(float(jnp.abs(g).max()) == 0 for g in grads[3:])


def test_the_kl_pass_forms_each_product_once_and_sums_in_float32(
        monkeypatch):
    """The cell's ``correct`` sees neither a product formed twice nor a
    rounded accumulator, so the traced kernel is read, at 2 query heads
    over 1 KV head and 4 indexer heads in (128, 128) tiles: ``H + 3 H_I``
    products a tile (``q_h kᵀ``; ``qI_h kIᵀ`` ONCE, ``e_h kI``, ``e_hᵀ (w_h
    ⊙ qI_h)``: ``H + 4 H_I`` until PR 41), each on the configuration's
    bfloat16 operands and leaving float32; ``p``, ``log_pi`` and the sums
    of ``KL``, ``dqI'`` and ``dkI`` float32; what the gradient keeps of a
    score its sign, as all-ones words as wide as an operand; and the only
    roundings ``g`` once a tile and ``w ⊙ qI`` once a query block."""
    B, T, H, Hkv, D, HI, DI, block = 1, 256, 2, 1, 128, 4, 64, 128
    monkeypatch.setattr(ss, "_KL_TILINGS", ((block, block),))
    q, k, _ = (a.astype(jnp.bfloat16) for a in qkv(B, T, H, Hkv, D))
    qi, ki, w = indexer_inputs(B, T, HI, DI)
    qi, ki = qi.astype(jnp.bfloat16), ki.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: ss._kl_pass(
        *a, scale=D ** -0.5, interpret=True))(
        qi, ki, w, q, k, jnp.zeros((B, H, T)), random_selection(B, T),
        jnp.zeros((B, T)))
    (call,) = [eqn for eqn in equations(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert call.params["name"] == "index_kl"
    assert [(v.aval.shape, str(v.aval.dtype)) for v in call.outvars] == [
        ((B, T, 1), "float32"), ((B, HI, T, DI), "float32"),
        ((B, HI, T, 1), "float32"), ((B, T // block, T, DI), "float32")]
    body = call.params["jaxpr"]
    refs = [(v.aval.shape, str(v.aval.dtype)) for v in body.invars]
    # The scratch follows 8 operands and 4 results: the KL rows, dqI' of
    # every indexer head, the heads' masks, and w ⊙ qI.
    assert refs[12:] == [((block, 1), "float32"),
                         ((HI, block, DI), "float32"),
                         ((HI, block, block), "int16"),
                         ((HI, block, DI), "bfloat16")]
    eqns = list(equations(body))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == H + 3 * HI
    for e in dots:
        assert {str(v.aval.dtype) for v in e.invars} == {"bfloat16"}
        assert str(e.outvars[0].aval.dtype) == "float32"
    # p's heads and pi; log p: all float32 (block, block) tiles.
    for name, count in (("exp", H + 1), ("log", 1)):
        found = [e for e in eqns if e.primitive.name == name]
        assert len(found) == count
        assert all((e.outvars[0].aval.shape, str(e.outvars[0].aval.dtype))
                   == ((block, block), "float32") for e in found)
    # A head's e is one AND of g's bits with its mask.
    ands = [e for e in eqns if e.primitive.name == "and"
            and str(e.outvars[0].aval.dtype) == "int16"]
    assert len(ands) == HI
    # Nothing is added into a rounded accumulator: every add that is not
    # index arithmetic is float32, and the only casts to bfloat16 are g's
    # (once a tile) and w ⊙ qI's (a head, once a query block).
    adds = [e for e in eqns if e.primitive.name == "add"
            and e.outvars[0].aval.shape]
    assert adds and {str(e.outvars[0].aval.dtype) for e in adds} == {
        "float32"}
    to_bf16 = [e for e in eqns if e.primitive.name == "convert_element_type"
               and str(e.outvars[0].aval.dtype) == "bfloat16"]
    assert len(to_bf16) == HI + 1
