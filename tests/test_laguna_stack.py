"""Laguna's stack — windowed and global attention layers with their own head
counts and rotary tables, an output gate, a dense layer and then a held share
of sigmoid-routed experts: what a windowed layer may read (by ``jax.grad`` to
its input rows), YaRN's table against its formula, the gate's shape, the
program against the benchmark family's plain float32 reference — loss and
named gradient leaves on seeded weights, and every deliberate fault patched
into the program told apart —, the eight shares of a sparse layer against the uncut
layer, the counters, and the refusals.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import laguna_lm as family
from benchmark.run import leaf
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import GroupedQueryAttention, LagunaLM, TransformerLM
from horovod_tpu.models.transformer import (
    PatternLayer, apply_rotary, yarn_frequencies)
from horovod_tpu.parallel.moe import DroplessMoE

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def family_cfg(compute="float32", **over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2.json")) as fh:
        cfg = {**json.load(fh), **family.TINY, **over}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute}
    return cfg


# -------------------------------------------------- the windowed layer


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_a_windowed_layer_reads_its_window_and_nothing_else(attn):
    """Output row ``i`` of one ``W`` layer has a gradient to the input rows
    ``i - W + 1 .. i`` and to no other: none to a row at or before ``i - W``
    (behind the window) and none to a row after ``i`` (the future)."""
    T, W, dim = 32, 8, 32
    sub = dict(num_heads=4, kv_heads=2, head_dim=128 if attn == "flash"
               else 16, attn=attn, rope_theta=1e4, window=W,
               out_gate=True)
    layer = PatternLayer("W", sub, dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, dim), F32)
    params = layer.init(jax.random.PRNGKey(1), x)
    r = jax.random.normal(jax.random.PRNGKey(2), (dim,), F32)
    grads = jax.vmap(lambda i: jax.grad(
        lambda x: (layer.apply(params, x)[0, i] * r).sum())(x))(
            jnp.arange(T))
    reach = np.abs(np.asarray(grads)[:, 0]).max(-1) > 0     # [out row, in row]
    apart = np.arange(T)[:, None] - np.arange(T)[None, :]
    assert (reach == ((apart >= 0) & (apart < W))).all()


def test_flash_and_full_agree_in_the_stack():
    kw = dict(vocab=64, dim=32, num_heads=6, kv_heads=1, head_dim=128,
              pattern="SDWESE", mlp_hidden=48, moe_experts=8, moe_top_k=3,
              moe_hidden=16,
              moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                       activation="swiglu", shared_hidden=16, held=(0, 4)),
              window=dict(window=8, num_heads=8, rope_theta=1e4,
                          rope_width=None, rope_scaling=None),
              dtype=F32, head_dtype=F32, ln_dtype=F32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    full = LagunaLM(attn="full", **kw)
    params = full.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(LagunaLM(attn="flash", **kw).apply(params,
                                                                  tokens),
                               full.apply(params, tokens), atol=5e-5)
    # Each kind has its own heads: the q kernels differ in width.
    shapes = jax.tree.map(lambda a: a.shape, params["params"])
    assert shapes["layer_0"]["attn"]["q"]["kernel"] == (32, 6 * 128)
    assert shapes["layer_2"]["attn"]["q"]["kernel"] == (32, 8 * 128)
    assert shapes["layer_1"]["mlp"]["down"]["kernel"] == (48, 32)


# ------------------------------------------------------ rotary tables


def test_yarn_s_table_against_its_formula():
    """Laguna-XS.2's global table — 64 rotated channels, theta 500,000,
    factor 64 over 4,096 original positions, beta 64 and 1 — at three ``m``:
    a fast channel left as it was, a slow one stretched 64 times, one on the
    ramp between; and the family's reference makes the same table."""
    theta, R = 500000.0, 64
    table = yarn_frequencies(R, theta, factor=64.0, original_max_len=4096,
                             beta_fast=64.0, beta_slow=1.0)

    def c(r):
        return R * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(theta))

    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16) and table.shape == (32,)
    for m in (2, 10, 30):
        f = theta ** (-m / 32)
        ramp = min(max((m - low) / (high - low), 0.0), 1.0)
        assert table[m] == pytest.approx(f * (1 - ramp) + f / 64 * ramp,
                                         rel=1e-6)
    assert table[2] == pytest.approx(theta ** (-2 / 32), rel=1e-6)
    assert table[30] == pytest.approx(theta ** (-30 / 32) / 64, rel=1e-6)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2.json")) as fh:
        ref = family.reference_tables(json.load(fh))
    np.testing.assert_array_equal(ref["S"][0], table)
    assert ref["S"][1] == 1.4158883083359672 == pytest.approx(
        0.1 * math.log(64) + 1)
    assert ref["W"][0].shape == (64,) and ref["W"][1] == 1.0
    np.testing.assert_allclose(ref["W"][0], 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-6)


def test_yarn_rotates_half_a_head_and_scales_cos_and_sin():
    """Only the rotated half of a head's ``q . k`` carries the square of
    ``attention_factor``; the other channels pass unchanged."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128), F32)
    pos = jnp.arange(8)
    scaling = dict(factor=64.0, original_max_len=4096, beta_fast=64.0,
                   beta_slow=1.0, attention_factor=1.5)
    out = apply_rotary(x, pos, 500000.0, width=64, scaling=scaling)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    np.testing.assert_allclose(
        jnp.linalg.norm(out[..., :64], axis=-1),
        1.5 * jnp.linalg.norm(x[..., :64], axis=-1), rtol=1e-5)
    np.testing.assert_allclose(out[:, 0, :, :64], 1.5 * x[:, 0, :, :64],
                               rtol=1e-6)           # position 0 turns nothing
    default = apply_rotary(x, pos, 500000.0, width=64, scaling={
        k: v for k, v in scaling.items() if k != "attention_factor"})
    np.testing.assert_allclose(
        default[..., :64] * 1.5 / (0.1 * math.log(64) + 1), out[..., :64],
        rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------ the gate


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (6, 1)])
def test_the_gate_is_a_value_a_head(heads, kv_heads):
    """``W_g`` is ``dim -> heads``, whatever the heads' grouping; with its
    kernel at zero the gate is one half everywhere and the layer's output
    half the ungated layer's."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32), F32)
    fields = dict(num_heads=heads, kv_heads=kv_heads, head_dim=16,
                  attn="full", dtype=F32)
    gated = GroupedQueryAttention(**fields, out_gate=True)
    params = gated.init(jax.random.PRNGKey(1), x)["params"]
    assert params["gate"]["kernel"].shape == (32, heads)
    plain = {k: v for k, v in params.items() if k != "gate"}
    params["gate"]["kernel"] = jnp.zeros_like(params["gate"]["kernel"])
    np.testing.assert_allclose(
        gated.apply({"params": params}, x),
        0.5 * GroupedQueryAttention(**fields).apply({"params": plain}, x),
        atol=1e-6)


# ------------------------- program against the family's plain reference


def both(f, params, aux, batch, paths):
    value, g = jax.jit(jax.value_and_grad(f))(params, aux, batch)
    return float(value), [leaf(g, path) for path in paths]


@pytest.fixture(scope="module")
def tiny():
    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    batch = family.host_batch(cfg, np.random.default_rng(1), 2)
    paths = family.grad_leaves(cfg)
    loss_fn = family.loss_fn(cfg)
    got = both(lambda p, a, b: loss_fn(p, a, b)[0], params, aux, batch, paths)
    want = both(family.reference_loss(cfg), params, aux, batch, paths)
    return cfg, params, aux, batch, paths, got, want


def test_model_against_reference_loss(tiny, loss_tol=1e-5, grad_tol=2e-3):
    """The program (interpreted kernels under the causal mask at six query
    heads a KV head and under the window at eight, YaRN's and the plain
    table, the gate, the dense layer, held sigmoid-routed experts with a
    shared one, the fused head) in float32 against the family's reference
    (in bfloat16 the rehearsal compares them:
    ``benchmark/tests/test_rehearse.py``)."""
    cfg, params, aux, batch, paths, (got, got_g), (want, want_g) = tiny
    assert family.pattern(cfg) == "SDWESE"
    assert {p[:3] for p in paths} >= {("layer_0", "attn", "gate"),
                                      ("layer_2", "attn", "gate"),
                                      ("layer_3", "moe", "router")}
    assert abs(got - want) <= loss_tol * abs(want)
    for path, g, w in zip(paths, got_g, want_g):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= grad_tol, (path, err)


def regrouped(params, cfg):
    """``params`` with every attention layer's query heads put in the order
    ``h mod H_kv``: under the program's grouping head ``h`` then reads KV head
    ``h mod H_kv`` where the model's reads ``h // (H / H_kv)`` — the heads'
    q columns, gate columns and proj rows moved together, nothing else."""
    Hkv, D = cfg["num_key_value_heads"], cfg["head_dim"]
    out = jax.tree.map(lambda a: a, params)
    for i, kind in enumerate(family.pattern(cfg)):
        if kind not in "SW":
            continue
        H = family.heads(cfg)[kind]
        order = jnp.asarray(sorted(range(H), key=lambda h: (h % Hkv, h)))
        attn = dict(out[f"layer_{i}"]["attn"])
        dim = attn["q"]["kernel"].shape[0]
        attn["q"] = {"kernel": attn["q"]["kernel"].reshape(dim, H, D)[
            :, order].reshape(dim, H * D)}
        attn["gate"] = {"kernel": attn["gate"]["kernel"][:, order]}
        attn["proj"] = {"kernel": attn["proj"]["kernel"].reshape(H, D, dim)[
            order].reshape(H * D, dim)}
        out[f"layer_{i}"] = {**out[f"layer_{i}"], "attn": attn}
    return out


# One deliberate error of the PROGRAM each: a field of the stack
# (``LagunaLM``'s, as ``laguna_lm._model`` builds it) set wrongly.
FAULTS = {
    "window_one_more": lambda m, T: dict(
        window={**m.window, "window": m.window["window"] + 1}),
    "window_is_causal": lambda m, T: dict(window={**m.window, "window": T}),
    "heads_grouped_wrongly": None,                  # ``regrouped``
    "no_gate": lambda m, T: dict(attn_gate=False),
    "plain_rope": lambda m, T: dict(
        rope_scaling={**m.rope_scaling, "factor": 1.0}),
    "no_attention_factor": lambda m, T: dict(
        rope_scaling={**m.rope_scaling, "attention_factor": 1.0}),
    "global_rotated_whole": lambda m, T: dict(rope_width=None,
                                              rope_scaling=None),
    "no_scale": lambda m, T: dict(moe={**m.moe, "gate_scale": 1.0}),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_reference_tells_each_fault(tiny, fault, monkeypatch,
                                        grad_tol=2e-3):
    """Each deliberate error, patched into the PROGRAM — the window one key
    too wide, the windowed layers run causal, the query heads grouped over
    the KV heads wrongly, the gate left out, YaRN's table replaced by the
    plain one, ``attention_factor`` left out, the global layers rotated
    whole, the factor 2.5 left out —, moves a named gradient leaf at least
    ten times further from the reference than the right program stands."""
    cfg, params, aux, batch, paths, _, (_, want_g) = tiny
    right, patch = family._model, FAULTS[fault]
    if patch is not None:
        monkeypatch.setattr(family, "_model", lambda c: right(c).clone(
            **patch(right(c), c["sequence_length"])))
    loss_fn = family.loss_fn(cfg)
    _, got_g = both(
        lambda p, a, b: loss_fn(p if patch else regrouped(p, cfg), a, b)[0],
        params, aux, batch, paths)
    worst = max(float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                for g, w in zip(got_g, want_g))
    assert worst >= 10 * grad_tol, (fault, worst)


# ----------------------------------------------------- the eight shares


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A sparse layer cut over eight chips — experts 0-3, 4-7, ... of 32 —,
    the router whole on each: the shares' routed parts add up to the uncut
    layer's, with the shared expert counted once."""
    fields = dict(num_experts=32, hidden=16, top_k=8, router="sigmoid",
                  renormalize=True, gate_scale=2.5, activation="swiglu",
                  shared_hidden=16, dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), F32)
    whole = DroplessMoE(**fields)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    want = whole.apply({"params": params}, x)[0]
    shared_alone = want - DroplessMoE(**{**fields, "shared_hidden": 0}).apply(
        {"params": {k: v for k, v in params.items() if k != "shared"}}, x)[0]
    total = jnp.zeros_like(want)
    for share in range(8):
        own = dict(params, **{name: params[name][4 * share:4 * share + 4]
                              for name in ("w_gate", "w_up", "w_down")})
        total += DroplessMoE(**fields, held=(4 * share, 4)).apply(
            {"params": own}, x)[0] - shared_alone
    np.testing.assert_allclose(total + shared_alone, want, atol=2e-5)
    assert float(jnp.abs(shared_alone).max()) > 0


# -------------------------------------------------- builder and counters


def test_lagunalm_is_the_published_stack_and_counts_its_kinds():
    m = LagunaLM()
    assert (m.vocab, m.dim, m.num_heads, m.kv_heads, m.head_dim) == (
        100352, 2048, 48, 8, 128)
    assert len(m.pattern) == 80 and m.pattern.startswith("SDWEWEWESEWE")
    assert (m.pattern[::2].count("S"), m.pattern[::2].count("W")) == (10, 30)
    assert (m.pattern[1::2].count("D"), m.pattern[1::2].count("E")) == (1, 39)
    assert m.window == dict(window=512, num_heads=64, rope_theta=10000.0,
                            rope_width=None, rope_scaling=None)
    assert (m.rope_theta, m.rope_width, m.attn_gate) == (500000.0, 64, True)
    assert m.rope_scaling["attention_factor"] == 1.4158883083359672
    assert (m.mlp_hidden, m.moe_experts, m.moe_top_k, m.moe_hidden) == (
        8192, 256, 8, 512)
    assert m.moe == dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                         activation="swiglu", shared_hidden=512)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2.json")) as fh:
        published = json.load(fh)
    letters = "".join(
        {"full_attention": "S", "sliding_attention": "W"}[a]
        + {"dense": "D", "sparse": "E"}[f]
        for a, f in zip(published["layer_types"],
                        published["mlp_layer_types"]))
    assert letters == m.pattern
    assert published["num_attention_heads_per_layer"] == [
        {"S": 48, "W": 64}[k] for k in m.pattern[::2]]

    cfg = family_cfg()
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(0))
    batch = family.host_batch(cfg, np.random.default_rng(1), 2)
    notes = {}
    jax.eval_shape(noting_layers(family.loss_fn(cfg), notes), params, aux,
                   batch)
    T, W = 64, 16
    glob, win = notes[("layer_0", "attn")], notes[("layer_2", "attn")]
    assert glob == {"attn.merged_heads": 0, "attn.heads#kind=global": 12}
    assert win["attn.heads#kind=window"] == 16 and win["attn.window"] == W
    pairs = W * (W + 1) // 2 + (T - W) * W
    assert win["attn.win_live_pairs"] == 2 * pairs == 2 * family.live_pairs(
        T, W)
    # One tile of 64 rows a head: the fully unrolled form's.
    assert win["attn.win_live_tiles"] == win["attn.win_visited_tiles"] == (
        2 * 16)
    # A step a head, each computing its one tile whole.
    assert win["attn.win_grid_steps"] == win["attn.win_live_steps"] == 2 * 16
    assert win["attn.win_visited_pairs"] == 2 * T * T
    assert notes[("layer_3", "moe")]["moe.assignments"] == 2 * T * 3


def test_the_stack_s_refusals():
    tokens = jnp.zeros((1, 16), jnp.int32)
    tiny_ = dict(vocab=64, dim=32, num_heads=2, kv_heads=1, head_dim=16,
                 attn="full", moe_experts=4, moe_top_k=2, moe_hidden=16,
                 mlp_hidden=16, dtype=F32)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="'W' layers take window="):
        LagunaLM(**tiny_, pattern="WE", window=None).init(key, tokens)
    with pytest.raises(ValueError, match="'W' layers take window="):
        LagunaLM(**tiny_, pattern="WE",
                 window=dict(window=4, kv_heads=2)).init(key, tokens)
    with pytest.raises(ValueError, match="holds none"):
        LagunaLM(**tiny_, pattern="SE").init(key, tokens)
    with pytest.raises(ValueError, match="pattern stack"):
        TransformerLM(vocab=64, dim=32, depth=1, num_heads=2,
                      attn_gate=True).init(key, tokens)
    with pytest.raises(ValueError, match="runs under its own\\s+mask"):
        GroupedQueryAttention(num_heads=2, kv_heads=1, head_dim=16,
                              attn="full", window=4).init(
            key, jnp.zeros((1, 16, 32)), None, ("block_diffusion", 4))
    with pytest.raises(ValueError, match="reach 'S' layers"):
        PatternLayer("W", dict(num_heads=2, kv_heads=1, head_dim=16,
                               attn="full", window=4)).init(
            key, jnp.zeros((1, 16, 32)), mask=("block_diffusion", 4))
