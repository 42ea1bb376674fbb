"""SDAR's stack under its block-diffusion objective: what may reach what in
the two-stream pass (by ``jax.grad`` to the embedded rows), positions
repeated and not counted on, the program against the benchmark family's
plain float32 reference — loss and named gradient leaves on seeded weights
—, a batch with nothing masked, the counters, and the call's refusals.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import sdar_moe_lm as family
from benchmark.run import leaf
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import SDARLM, KeyeLM, TransformerLM
from horovod_tpu.models.transformer import PatternLayer

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, L, DIM = 16, 4, 32


def family_cfg(compute="float32", **over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as fh:
        cfg = {**json.load(fh), **family.TINY, **over}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute}
    return cfg


# ------------------------------------------- the two streams, layer level


def stack(attn="full", pos=None):
    """``f(params, x)`` on embedded rows ``x`` (1, 2 T, d): two ``S`` and
    two ``E`` layers as the model builds them, under the block mask."""
    import flax.linen as nn

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x):
            s = dict(num_heads=2, kv_heads=1, head_dim=16, attn=attn,
                     qk_norm=True, indexer=None, rope_theta=1e4)
            e = dict(num_experts=4, hidden=16, top_k=2, router="softmax",
                     renormalize=True, activation="swiglu")
            where = dict(pos=jnp.tile(jnp.arange(T), 2) if pos is None
                         else pos, mask=("block_diffusion", L))
            for i, kind in enumerate("SESE"):
                x = PatternLayer(kind, s if kind == "S" else e, dtype=F32,
                                 name=f"layer_{i}")(x, **where)
            return x

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * T, DIM), F32)
    model = Stack()
    return model, model.init(jax.random.PRNGKey(1), x), x


def test_nothing_leaks_between_the_streams_and_the_blocks():
    """Output row against input row through two attention and two expert
    layers: a noised block reads its own noised rows and the clean rows of
    EARLIER blocks — not the clean rows of its own block (the leak that
    makes the loss trivial) or of later ones, and no other block's noised
    rows —; a clean row reads the clean rows of its own and earlier blocks
    and no noised row, so the clean half does not move with the noise."""
    model, params, x = stack()
    r = jax.random.normal(jax.random.PRNGKey(2), (DIM,), F32)
    # One VJP an output row (the experts' ragged_dot has no vmap rule).
    grads = jax.lax.map(lambda i: jax.grad(
        lambda x: (model.apply(params, x)[0, i] * r).sum())(x),
        jnp.arange(2 * T))
    reach = np.abs(np.asarray(grads)[:, 0]).max(-1) > 0     # [out row, in row]
    blk = np.arange(T) // L
    assert (reach[:T, :T] == (blk[None] <= blk[:, None])).all()
    assert not reach[:T, T:].any()
    assert (reach[T:, :T] == (blk[None] < blk[:, None])).all()
    assert (reach[T:, T:] == (blk[None] == blk[:, None])).all()


def test_positions_are_the_tokens_and_not_the_rows():
    """With nothing masked the noised copy IS the clean copy, and a noised
    row then reads the same keys and values as its clean twin — its own
    block's from the noised copy, the earlier ones' from the clean — if and
    only if both stand at the same rotary position: the halves come out
    equal under repeated positions and differ under ``0 .. 2T - 1``."""
    model, params, x = stack()
    twice = jnp.concatenate([x[:, :T], x[:, :T]], axis=1)
    out = model.apply(params, twice)
    np.testing.assert_allclose(out[:, :T], out[:, T:], atol=1e-5)
    counted, _, _ = stack(pos=jnp.arange(2 * T))
    out = counted.apply(params, twice)
    assert float(jnp.abs(out[:, :T] - out[:, T:]).max()) > 1e-2


def test_flash_and_full_agree_in_the_stack():
    full, params, x = stack()
    flash, _, _ = stack(attn="flash")
    np.testing.assert_allclose(flash.apply(params, x), full.apply(params, x),
                               atol=2e-5)


# ------------------------- program against the family's plain reference


def test_model_against_reference_loss(loss_tol=1e-5, grad_tol=2e-3):
    """The program (interpreted kernels under the block mask, held experts,
    the fused head over the noised half, the weighted loss) in float32
    against the family's reference, whose 2T x 2T mask is built from the
    four rules (in bfloat16 the rehearsal compares them:
    ``benchmark/tests/test_rehearse.py``)."""
    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    batch = family.host_batch(cfg, np.random.default_rng(1), 2)
    assert batch["masked"].any() and not batch["masked"].all()
    loss_fn, ref_fn = family.loss_fn(cfg), family.reference_loss(cfg)
    paths = family.grad_leaves(cfg)

    def both(f):
        value, g = jax.jit(jax.value_and_grad(f))(params, aux, batch)
        return float(value), [leaf(g, path) for path in paths]

    got, got_g = both(lambda p, a, b: loss_fn(p, a, b)[0])
    want, want_g = both(ref_fn)
    assert abs(got - want) <= loss_tol * abs(want)
    for path, g, w in zip(paths, got_g, want_g):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= grad_tol, (path, err)
    # The mask token's row is read (the noised copy) and so has a gradient.
    emb = got_g[paths.index(("tok_emb", "embedding"))]
    assert float(jnp.abs(emb[cfg["block_diffusion"]["mask_token_id"]]).max()
                 ) > 0


def test_nothing_masked_is_no_loss_and_no_nan():
    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    batch = family.host_batch(cfg, np.random.default_rng(1), 2)
    batch = {"tokens": batch["tokens"],
             "masked": np.zeros_like(batch["masked"]),
             "weight": np.zeros_like(batch["weight"])}
    value, g = jax.jit(jax.value_and_grad(
        lambda p: family.loss_fn(cfg)(p, aux, batch)[0]))(params)
    assert float(value) == 0.0
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


def test_host_batch_is_the_objective_s_noise():
    """Ids leave the mask token's row alone; one rate a block, spread evenly
    over the interval (low discrepancy: every 1/16 of it holds one of a
    sequence's 16 blocks); the weight is 1/t on masked positions, else 0;
    the same seed gives the same batch."""
    cfg = family_cfg()
    bd = cfg["block_diffusion"]
    a = family.host_batch(cfg, np.random.default_rng(7), 3)
    b = family.host_batch(cfg, np.random.default_rng(7), 3)
    assert all((a[k] == b[k]).all() for k in a)
    assert a["tokens"].max() < bd["mask_token_id"] and a["tokens"].min() >= 0
    rates = family.mask_rates(cfg, np.random.default_rng(7), 3)
    assert rates.shape == (3, 16)
    unit = (rates - bd["t_low"]) / (bd["t_high"] - bd["t_low"])
    assert (np.sort((unit * 16).astype(int), axis=1) == np.arange(16)).all()
    w = a["weight"].reshape(3, 16, 4)
    assert ((w == 0) == ~a["masked"].reshape(3, 16, 4)).all()
    per_block = np.where(w > 0, w, np.nan)
    assert np.nanmin(per_block) >= 1 / bd["t_high"] - 1e-6
    assert np.nanmax(per_block) <= 1 / bd["t_low"] + 1e-6
    some = per_block[~np.isnan(per_block).all(-1)]      # one rate a block
    assert (np.nanmax(some, -1) - np.nanmin(some, -1)).max() < 1e-6


# ----------------------------------------- the model, counters, refusals


def test_sdarlm_is_the_published_stack_and_counts_its_rows():
    m = SDARLM()
    k = KeyeLM()
    assert (m.vocab, m.dim, m.num_heads, m.kv_heads, m.head_dim) == (
        151936, 2048, 32, 4, 128)
    assert m.pattern == "SE" * 48 and m.rope_theta == 1e6 and m.qk_norm
    assert (m.moe_experts, m.moe_top_k, m.moe_hidden, m.moe) == (
        k.moe_experts, k.moe_top_k, k.moe_hidden, k.moe)
    assert m.diffusion == dict(block=4, mask_id=151935) and m.indexer is None
    assert m.max_len == 32768 and m.norm_eps == 1e-6

    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    batch = family.host_batch(cfg, np.random.default_rng(1), 2)
    notes = {}
    jax.eval_shape(noting_layers(family.loss_fn(cfg), notes), params, aux,
                   batch)
    rows, T_ = 2 * 2 * 64, 64
    assert notes[()]["lm.bd_rows"] == rows
    attn = notes[("layer_0", "attn")]
    assert attn["attn.bd_block"] == 4
    assert attn["attn.bd_live_pairs"] == 2 * (T_ * T_ + T_ * 4)
    assert attn["attn.bd_live_tiles"] == attn["attn.bd_visited_tiles"] == (
        2 * 2 * 3)                 # one 64-row tile a stream: 3 of 4 tiles
    # The expert layer sees twice the rows a token: 2 T k assignments.
    assert notes[("layer_1", "moe")]["moe.assignments"] == rows * 3
    cost = family.moe_cost(cfg, 2)
    assert cost["assignments"] == rows * 3


def test_the_call_s_refusals():
    tokens = jnp.zeros((1, 16), jnp.int32)
    tiny = dict(vocab=64, dim=32, num_heads=2, kv_heads=1, head_dim=16,
                attn="full", moe_experts=4, moe_top_k=2, moe_hidden=16,
                dtype=F32)
    with pytest.raises(ValueError, match="block-diffusion call"):
        KeyeLM(**tiny, pattern="SE", indexer=None).init(
            jax.random.PRNGKey(0), tokens, masked=tokens > 0)
    with pytest.raises(ValueError, match="whole\\s+blocks of tokens"):
        SDARLM(**tiny, pattern="SE").init(jax.random.PRNGKey(0),
                                          tokens[:, :14])
    with pytest.raises(ValueError, match="'S'\\s+and 'E' layers"):
        SDARLM(**tiny, pattern="S*").init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="pattern stack"):
        TransformerLM(vocab=64, dim=32, depth=1, num_heads=2,
                      diffusion=dict(block=4, mask_id=63)).init(
                          jax.random.PRNGKey(0), tokens)
    # The default call (init) is the call with nothing masked.
    model = SDARLM(**tiny, pattern="SE")
    params = model.init(jax.random.PRNGKey(0), tokens)
    out = model.apply(params, tokens, return_hidden=True)
    assert out.shape == (1, 16, 32)
    np.testing.assert_allclose(
        out, model.apply(params, tokens, return_hidden=True,
                         masked=jnp.zeros((1, 16), bool)))
