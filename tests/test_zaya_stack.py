"""The ZAYA1 stack (``Zaya1LM``; pattern letter ``Z``): compressed
convolutional attention — attention inside a latent whose q and k pass two
causal convolutions, with a QK-mean, a value shift, an L2 norm under a
learned temperature and rotary positions on half of each head — and then a
top-1 expert layer whose router is a small network with a state handed from
layer to layer and one choice that computes nothing, both joined to the
residual under learned scales and biases; the held share of such a layer;
and the benchmark family's plain float32 reference against the program —
loss and named gradient leaves on seeded weights.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import zaya1_lm as family
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import (
    CompressedConvAttention, ResidualMerge, TransformerLM, Zaya1LM,
    apply_rotary)
from horovod_tpu.models.transformer import PatternLayer
from horovod_tpu.parallel.moe import DroplessMoE

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b.json")) as fh:
        return json.load(fh)


# ------------------------------------------------- the tree, by count


def count(model):
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)), params


def test_parameter_counts_the_cut_and_the_published_model():
    """By hand (ISSUE 43): attention 5,242,880 in its five projections and
    332,802 in the two convolutions and the temperatures; the router
    661,009; two norms 4,096; the merges' 8 vectors of 2,048; an expert
    12,582,912.  The model's first layer has no scale and bias on its
    residual (4,096 fewer) and no scale on a router state (256 fewer)."""
    attention = 2048 * 1024 * 2 + 2048 * 256 + 2 * 2048 * 128
    convs = 1280 * 3 + (10 * 2 * 128 * 128 + 1280) + 2
    router = (2048 * 256 + 256) + 256 + 256 + 2 * (256 * 256 + 256) \
        + 256 * 17 + 17
    outside = attention + convs + router + 4096 + 8 * 2048
    assert (attention, convs, router, outside) == (
        5_242_880, 332_802, 661_009, 6_257_171)
    expert = 3 * 2048 * 2048
    cfg = published()
    n, params = count(family._model(cfg))
    layers = cfg["num_hidden_layers"]
    assert n == (layers * (outside + 8 * expert) - 4096 - 256
                 + 32_784 * 2048 + 2048)
    assert "head" not in params                       # the table is the head
    assert sorted(params["layer_1"]) == [
        "attn", "merge_attn", "merge_moe", "moe", "moe_norm", "norm"]
    assert sorted(params["layer_0"]["merge_attn"]) == ["bias_y", "scale_y"]
    assert "router_state_scale" not in params["layer_0"]["moe"]
    assert params["layer_1"]["moe"]["router_state_scale"].shape == (256,)
    assert params["layer_1"]["moe"]["w_gate"].shape == (8, 2048, 2048)
    assert params["layer_1"]["moe"]["router_out"]["kernel"].shape == (256, 17)
    assert params["layer_1"]["attn"]["conv1_kernel"].shape == (10, 2, 128,
                                                                128)
    n40, _ = count(Zaya1LM())
    assert n40 == (40 * (outside + 16 * expert) - 4096 - 256
                   + 262_272 * 2048 + 2048)
    assert n40 == 8_840_481_272
    assert family.pattern(cfg) == "Z" * layers and Zaya1LM().pattern == (
        "Z" * len(cfg["layer_types"]))


# ----------------------------------------------- rotary on half a head


def test_half_rotary_leaves_the_upper_channels_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 128))
    pos = jnp.arange(12) + 5
    half = apply_rotary(x, pos, 5e6, 64)
    assert jnp.array_equal(half[..., 64:], x[..., 64:])
    assert rel(half[..., :64], apply_rotary(x[..., :64], pos, 5e6)) == 0.0
    assert rel(half[..., :64], x[..., :64]) > 0.1
    whole = apply_rotary(x, pos, 5e6)
    assert rel(whole, apply_rotary(x, pos, 5e6, 128)) == 0.0
    assert rel(whole[..., 64:], x[..., 64:]) > 0.1
    with pytest.raises(ValueError, match="rotated width"):
        apply_rotary(x, pos, 5e6, 130)


# ------------------------------------- the attention sub-layer, by hand


CCA = dict(num_heads=4, kv_heads=2, head_dim=16, attn="full", dtype=F32,
           rope_theta=5e6)


def cca_with_biases():
    """A module and parameters whose biases and temperatures are not their
    initial 0 and 1, and an input."""
    layer = CompressedConvAttention(**CCA)
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 24))
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    params = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                              a.shape), params)
    return layer, params, u


def test_attention_sub_layer_is_causal_and_reaches_exactly_two_back():
    """A change at token t moves no output before t.  Inside the latent, q"
    and k" of token t read tokens t, t - 1 and t - 2 and no other (two
    convolutions of two taps); the first half of the value channels reads
    token t alone, the second half token t - 1 alone."""
    layer, params, u = cca_with_biases()
    T = u.shape[1]

    def run(u):
        out, state = layer.apply({"params": params}, u,
                                 mutable=["intermediates"])
        q, k, v = state["intermediates"]["latent"][0]
        return out[0], q[0], k[0], v[0].reshape(T, -1)

    def reach(jac):
        """(T out, T in) bools: whether output row t reads input row s."""
        jac = np.asarray(jac)
        return np.abs(jac.reshape(T, -1, 1, T, jac.shape[-1])).max(
            axis=(1, 2, 4)) > 0

    d_out, d_q, d_k, d_v = jax.jacobian(run)(u)
    t, s = np.indices((T, T))
    assert (reach(d_out) == (s <= t)).all()
    window = (s <= t) & (s >= t - 2)
    assert (reach(d_q) == window).all() and (reach(d_k) == window).all()
    half = d_v.shape[1] // 2          # of the G · D value channels in order
    assert (reach(d_v[:, :half]) == (s == t)).all()
    assert (reach(d_v[:, half:]) == (s == t - 1)).all()


def test_attention_sub_layer_by_hand():
    """The module against the equations written out for one head pair at a
    time with loops, on its own parameters."""
    layer, p, u = cca_with_biases()
    u0 = np.asarray(u[0], np.float64)
    T, H, G, D = u0.shape[0], 4, 2, 16
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    q0 = (u0 @ w["q"]["kernel"]).reshape(T, H, D)
    k0 = (u0 @ w["k"]["kernel"]).reshape(T, G, D)
    m_q = np.stack([(q0[:, h] + k0[:, h // 2]) / 2 for h in range(H)], 1)
    m_k = np.stack([(m_q[:, 2 * j] + m_q[:, 2 * j + 1]) / 2
                    for j in range(G)], 1)
    z = np.concatenate([np.zeros((2, (H + G) * D)),
                        np.concatenate([q0.reshape(T, -1),
                                        k0.reshape(T, -1)], 1)])
    z1 = np.stack([w["conv0_bias"] + w["conv0_kernel"][:, 0] * z[t]
                   + w["conv0_kernel"][:, 1] * z[t + 1]
                   for t in range(T + 1)]).reshape(T + 1, H + G, D)
    z2 = np.stack([np.stack([
        w["conv1_bias"][h] + z1[t, h] @ w["conv1_kernel"][h, 0]
        + z1[t + 1, h] @ w["conv1_kernel"][h, 1] for h in range(H + G)])
        for t in range(T)])
    q, k = z2[:, :H] + m_q, z2[:, H:] + m_k
    q = 4.0 * q / np.linalg.norm(q, axis=-1, keepdims=True)
    k = (w["temp"][:, None] * 4.0 * k
         / np.linalg.norm(k, axis=-1, keepdims=True))

    def rot(x):
        freq = 5e6 ** (-np.arange(4) / 4.0)
        angle = np.arange(T)[:, None, None] * freq
        a, b = x[..., :4], x[..., 4:8]
        return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               b * np.cos(angle) + a * np.sin(angle),
                               x[..., 8:]], -1)

    q, k = rot(q), rot(k)
    u_prev = np.concatenate([np.zeros((1, u0.shape[1])), u0[:-1]])
    v = np.concatenate([u0 @ w["v1"]["kernel"], u_prev @ w["v2"]["kernel"]],
                       1).reshape(T, G, D)
    out = np.zeros((T, H, D))
    for h in range(H):
        logits = q[:, h] @ k[:, h // 2].T / 4.0
        logits[np.triu_indices(T, 1)] = -np.inf
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        out[:, h] = probs / probs.sum(-1, keepdims=True) @ v[:, h // 2]
    want = out.reshape(T, H * D) @ w["proj"]["kernel"]
    got = layer.apply({"params": p}, u)[0]
    assert rel(got, want) <= 2e-5


@pytest.mark.parametrize("fault", [
    "no_shift", "no_qk_mean", "whole_head_rotary", "no_temperature",
    "previous_tap_zeroed"])
def test_each_part_of_the_latent_moves_the_output(fault):
    """The module with one part of the mathematics taken out is another
    function: what the chip's comparison catches by a tolerance is no
    rounding here either."""
    layer, p, u = cca_with_biases()
    want = layer.apply({"params": p}, u)
    if fault == "whole_head_rotary":
        got = CompressedConvAttention(**CCA, rotary_fraction=1.0).apply(
            {"params": p}, u)
    elif fault == "no_temperature":
        got = layer.apply({"params": {**p, "temp": jnp.ones(2)}}, u)
    elif fault == "previous_tap_zeroed":
        got = layer.apply({"params": {**p, "conv1_kernel": p[
            "conv1_kernel"].at[:, 0].set(0.0)}}, u)
    elif fault == "no_shift":
        _, state = layer.apply({"params": p}, u, mutable=["intermediates"])
        v = state["intermediates"]["latent"][0][2].reshape(1, 10, -1)
        same_token = u @ p["v2"]["kernel"]
        assert rel(v[:, 1:, 16:], same_token[:, :-1]) <= 1e-6
        assert float(jnp.abs(v[:, 0, 16:]).max()) == 0.0
        assert rel(v[:, :, 16:], same_token) > 0.1
        assert rel(v[:, :, :16], u @ p["v1"]["kernel"]) <= 1e-6
        return
    else:
        # Without the QK-mean, k~ reaches q" only through the means: a
        # query then does not move with its KV head's key projection.
        def q_latent(k_kernel):
            _, s = layer.apply({"params": {**p, "k": {"kernel": k_kernel}}},
                               u, mutable=["intermediates"])
            return s["intermediates"]["latent"][0][0]
        d = jax.jacobian(q_latent)(p["k"]["kernel"])
        assert float(jnp.abs(d).max()) > 1e-3
        return
    assert rel(got, want) > 1e-2, fault


def test_merge_by_hand_and_the_first_sub_layer_s():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
    merge = ResidualMerge()
    p = merge.init(jax.random.PRNGKey(2), x, y)["params"]
    assert rel(merge.apply({"params": p}, x, y), x + y) == 0.0
    p = {k: v + i + 1.0 for i, (k, v) in enumerate(sorted(p.items()))}
    want = p["scale_x"] * (x + p["bias_x"]) + p["scale_y"] * (y + p["bias_y"])
    assert rel(merge.apply({"params": p}, x, y), want) <= 1e-6
    first = ResidualMerge(residual=False)
    assert sorted(first.init(jax.random.PRNGKey(2), x, y)["params"]) == [
        "bias_y", "scale_y"]
    assert rel(first.apply({"params": p}, x, y),
               x + p["scale_y"] * (y + p["bias_y"])) <= 1e-6


# --------------------------------------- the expert sub-layer's shares


MOE = dict(num_experts=16, hidden=24, top_k=1, router="mlp",
           router_hidden=8, skip_choice=True, dtype=F32)


def plain_expert_layer(p, u, r_prev=None):
    """The uncut layer from the equations, every expert a plain matmul:
    ``(y, r, chosen)``."""
    r = u @ p["router_down"]["kernel"] + p["router_down"]["bias"]
    if r_prev is not None:
        r = r + p["router_state_scale"] * r_prev
    h = r * jax.lax.rsqrt((r * r).mean(-1, keepdims=True) + 1e-5) * p[
        "router_norm"]["scale"]
    for name in ("router_fc1", "router_fc2"):
        h = jax.nn.gelu(h @ p[name]["kernel"] + p[name]["bias"],
                        approximate=False)
    probs = jax.nn.softmax(h @ p["router_out"]["kernel"], axis=-1)
    chosen = jnp.argmax(probs + p["choice_bias"], axis=-1)
    gate = jnp.take_along_axis(probs, chosen[:, None], axis=-1)
    y = jnp.zeros_like(u)
    for e in range(p["w_gate"].shape[0]):
        one = (jax.nn.silu(u @ p["w_gate"][e]) * (u @ p["w_up"][e])) @ p[
            "w_down"][e]
        y = y + jnp.where(chosen[:, None] == e, gate * one, 0.0)
    return y, r, chosen


@pytest.fixture(scope="module")
def expert_layer():
    u = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    whole = DroplessMoE(**MOE)
    with jax.default_matmul_precision("highest"):
        params = whole.init(jax.random.PRNGKey(1), u,
                            jnp.zeros((96, 8)))["params"]
    params = jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(jax.random.PRNGKey(3),
                                              a.shape), params)
    params["choice_bias"] = jnp.zeros(17)
    return whole, params, u


def test_the_two_shares_add_up_to_the_uncut_layer(expert_layer):
    """``held=(0, 8)`` and ``held=(8, 8)`` of one expert sub-layer — the two
    chips of the deployment's pair — add up to the uncut reference's layer,
    the choice that computes nothing adding nothing in either; the layer
    with every expert here gives the same."""
    whole, params, u = expert_layer
    r_prev = jax.random.normal(jax.random.PRNGKey(4), (96, 8))
    with jax.default_matmul_precision("highest"):
        want, want_r, chosen = plain_expert_layer(params, u, r_prev)
        parts, sown = [], []
        for first in (0, 8):
            share = {k: (v[first:first + 8] if k.startswith("w_") else v)
                     for k, v in params.items()}
            (y, _, _, r), state = DroplessMoE(**MOE, held=(first, 8)).apply(
                {"params": share}, u, r_prev, mutable=["intermediates"])
            parts.append(y)
            sown.append(state["intermediates"])
            assert rel(r, want_r) <= 1e-6
        (y_whole, _, _, _), state = whole.apply(
            {"params": params}, u, r_prev, mutable=["intermediates"])
    counts = np.bincount(np.asarray(chosen), minlength=17)
    assert counts[16] > 0 and counts[:8].sum() > 0 and counts[8:16].sum() > 0
    assert rel(parts[0] + parts[1], want) <= 1e-5
    assert rel(y_whole, want) <= 1e-5
    for part, here in zip(parts, (slice(0, 8), slice(8, 16))):
        mine = (chosen >= here.start) & (chosen < here.stop)
        assert float(jnp.abs(part[~mine]).max()) == 0.0     # also the skips
        assert rel(part[mine], want[mine]) <= 1e-5
    for s, here in zip(sown, (slice(0, 8), slice(8, 16))):
        assert (np.asarray(s["tokens_per_expert"][0]) == counts).all()
        assert int(s["held_assignments"][0]) == counts[here].sum()
        assert int(s["skipped_assignments"][0]) == counts[16]
        assert (np.asarray(s["expert_index"][0])[:, 0] == chosen).all()
    assert int(state["intermediates"]["held_assignments"][0]) == (
        counts[:16].sum())


def test_the_choice_bias_moves_the_choice_and_not_the_gate(expert_layer):
    """``choice_bias`` is added for the choice alone and lies outside the
    gradient: a bias on the skip choice sends every token there (the layer
    adds nothing), and its gradient is exactly zero."""
    whole, params, u = expert_layer
    biased = {**params, "choice_bias": jnp.zeros(17).at[16].set(2.0)}
    (y, _, _, _), state = whole.apply({"params": biased}, u,
                                      mutable=["intermediates"])
    assert float(jnp.abs(y).max()) == 0.0
    assert int(state["intermediates"]["skipped_assignments"][0]) == 96
    g = jax.grad(lambda p: (whole.apply({"params": p}, u)[0] ** 2).sum())(
        params)
    assert float(jnp.abs(g["choice_bias"]).max()) == 0.0
    assert float(jnp.abs(g["router_out"]["kernel"]).max()) > 0.0


def test_the_router_s_state_reaches_the_next_layer(expert_layer):
    """A gradient on layer 1's ``W_d`` from layer 2's choice weights alone:
    layer 2 reads an input of its own, so layer 1's parameters reach its
    output through the handed-on state and nothing else."""
    whole, params, u = expert_layer
    other = jax.random.normal(jax.random.PRNGKey(7), u.shape)

    def second_layer_output(first_params, hand_on: bool):
        _, _, _, r = whole.apply({"params": first_params}, u)
        y, _, _, _ = whole.apply({"params": params}, other,
                                 r if hand_on else None)
        return (y ** 2).sum()

    g = jax.grad(second_layer_output)(params, True)
    assert float(jnp.abs(g["router_down"]["kernel"]).max()) > 0.0
    assert float(jnp.abs(g["w_gate"]).max()) == 0.0   # layer 1's experts: no
    g = jax.grad(second_layer_output)(params, False)
    assert float(jnp.abs(g["router_down"]["kernel"]).max()) == 0.0
    with pytest.raises(ValueError, match="carries a state"):
        DroplessMoE(num_experts=4, hidden=8, top_k=1).init(
            jax.random.PRNGKey(0), u, jnp.zeros((96, 8)))


def test_the_stack_hands_the_state_from_z_layer_to_z_layer():
    """``_pattern_stack`` threads the state through the ``Z`` layers and
    past a layer that has none; the model's second ``Z`` layer scales what
    it is handed, and that scale has a gradient."""
    model = Zaya1LM(vocab=64, dim=32, num_heads=2, kv_heads=1, head_dim=16,
                    pattern="Z*Z", moe_experts=4, moe_hidden=16,
                    moe=dict(router="mlp", router_hidden=8,
                             skip_choice=True),
                    attn="full", dtype=F32, head_dtype=F32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    assert "router_state_scale" not in params["layer_0"]["moe"]
    assert "router_state_scale" in params["layer_2"]["moe"]
    g = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum()))(params)
    assert float(jnp.abs(g["layer_2"]["moe"]["router_state_scale"]).max()) > 0
    with pytest.raises(ValueError, match="'Z' layers have no other"):
        Zaya1LM(vocab=64, dim=32, pattern="Z", pos="none").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="'m', 'a' or 'Z'"):
        PatternLayer("Q", None).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 4, 8)))
    with pytest.raises(ValueError, match="pattern stack"):
        TransformerLM(vocab=64, dim=32, depth=1, num_heads=2,
                      cca=dict(taps=(2, 2))).init(jax.random.PRNGKey(0),
                                                  tokens)


# --------------- the three routers the benchmark's other cells run


@pytest.mark.parametrize("name,settings,digest", [
    ("olmoe", dict(num_experts=8, hidden=32, top_k=2), "320956d894be7186"),
    ("nemotron", dict(num_experts=8, hidden=32, top_k=3, router="sigmoid",
                      renormalize=True, gate_scale=2.5, activation="relu2",
                      shared_hidden=64, held=(2, 2)), "549bb5fe39a76bc8"),
    ("keye", dict(num_experts=8, hidden=32, top_k=2, router="softmax",
                  renormalize=True, held=(0, 2)), "3b1ff08a4f14498a"),
])
def test_the_existing_routers_programs_are_the_parent_s(name, settings,
                                                        digest):
    """The layer's lowered program, loss and gradients, under the three
    settings the benchmark's other expert cells run is, to the letter, the
    one the commit before the ``mlp`` router lowered (``olmoe``'s SHA-256
    taken there, PR 43: every expert here, which no later PR has moved)
    and, for the two held ones, the one PR 53 lowers, whose windows follow
    the load (taken at its tree; the digests of PR 45, acb1388, pinned the
    levelled window and its ``overflowed`` branch before): same program,
    same bits out.  The held shares are 2 of 8 — windows of 40 of 144 and
    40 of 96 assignments, smaller than ``n · k`` as ``twotower_1chip``'s
    and ``keye_1chip``'s are —, so what is pinned is the window form those
    cells run; at 4 of 8 the window is every assignment, whose rows do not
    scatter-add (``tests/test_dropless_moe.py``)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 16), F32)
    layer = DroplessMoE(**settings, dtype=F32)
    params = layer.init(jax.random.PRNGKey(1), x)

    def g(params, x):
        def loss(p):
            out, b, z = layer.apply(p, x)
            return (out ** 2).sum() + b + z
        return jax.value_and_grad(loss)(params)

    text = jax.jit(g).lower(params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest, name


# ------------------------- program against the family's plain reference


def family_cfg(compute="float32", **over):
    """Two layers (so that a state is handed on) at sizes the flash kernels
    take: 4 query heads over 2 KV heads of 128, 4 of 8 experts held."""
    cfg = published()
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=128, num_experts=4,
               experts_routed_over=8, moe_intermediate_size=32,
               router_hidden_size=16, sequence_length=64, vocab_size=256,
               training={**cfg["training"], "compute_dtype": compute},
               tolerances={**cfg["tolerances"], "tie_margin": 1e-6})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def compared():
    """Loss and every gradient leaf of the program in float32, of the
    program in bfloat16 and of the reference, on one seeded batch; the
    kernels interpreted."""
    cfg = family_cfg()
    params, aux = family.init(cfg, jax.random.PRNGKey(11))
    # Biases, scales and temperatures off their initial 0 and 1, so that a
    # part that ignored one would show.
    params = jax.tree.map(
        lambda a: a + (0.1 * jax.random.normal(jax.random.PRNGKey(12),
                                               a.shape) if a.ndim == 1
                       else 0.0), params)
    for i in range(2):
        params[f"layer_{i}"]["moe"]["choice_bias"] = jnp.zeros(9)
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(5), 1))
    noted = {}
    out = {"cfg": cfg, "params": params, "tokens": tokens}
    with jax.default_matmul_precision("highest"):
        for name, fn in (
                ("float32", noting_layers(family.loss_fn(cfg), noted)),
                ("reference", lambda p, a, t: (
                    family.reference_loss(cfg)(p, a, t), a))):
            (loss, _), grads = jax.jit(jax.value_and_grad(
                fn, has_aux=True))(params, aux, tokens)
            out[name] = (float(loss), grads)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        family.loss_fn(family_cfg("bfloat16")), has_aux=True))(
            params, aux, tokens)
    out["bfloat16"] = (float(loss), grads)
    out["noted"] = noted
    return out


# Why these bounds.  The program in float32 differs from the reference in
# the order of its sums (flash against a held softmax, the grouped
# matmuls' rows against a masked matmul over every token, a product of
# depth 256 against two of 128): observed 4e-8 on the loss and up to 4e-5
# on a leaf.  The same program computing in bfloat16 — the nearest
# precision below the one this test's configuration states — reads 2e-3 and
# up on every leaf but one.
LOSS_TOL, LEAF_TOL = 2e-6, 3e-4


def test_loss_and_every_leaf_against_the_plain_reference(compared):
    want_loss, want = compared["reference"]
    got_loss, got = compared["float32"]
    assert abs(got_loss - want_loss) <= LOSS_TOL * abs(want_loss)
    # choice_bias has no gradient on either side: 0 against 0.
    errors = jax.tree.map(
        lambda g, w: 0.0 if not float(jnp.abs(w).max()) and not float(
            jnp.abs(g).max()) else rel(g, w), got, want)
    worst = max(jax.tree.leaves(errors))
    assert worst <= LEAF_TOL, errors
    named = family.grad_leaves(compared["cfg"])
    for must in (("layer_0", "attn", "q", "kernel"),
                 ("layer_0", "attn", "conv1_kernel"),
                 ("layer_0", "attn", "v2", "kernel"),
                 ("layer_0", "attn", "proj", "kernel"),
                 ("layer_0", "moe", "router_down", "kernel"),
                 ("layer_1", "moe", "router_state_scale"),
                 ("layer_0", "moe", "router_fc1", "kernel"),
                 ("layer_0", "moe", "w_gate"), ("layer_0", "moe", "w_down"),
                 ("layer_0", "merge_moe", "scale_y"),
                 ("tok_emb", "embedding")):
        assert must in named
    for path in named:
        leaf, reference = errors, want
        for key in path:
            leaf, reference = leaf[key], reference[key]
        assert leaf <= LEAF_TOL, path
        assert float(jnp.abs(reference).max()) > 0.0, path


def test_bfloat16_where_the_configuration_says_float32_fails(compared):
    _, want = compared["reference"]
    _, got = compared["bfloat16"]
    named = family.grad_leaves(compared["cfg"])

    def at(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    errors = [rel(at(got, path), at(want, path)) for path in named]
    assert max(errors) > 10 * LEAF_TOL and sorted(errors)[1] > 4 * LEAF_TOL, (
        errors)


def test_the_drawn_temperatures_are_seen_through_the_query_projection(
        compared):
    """``family.init`` draws the temperatures from [0.5, 1.5], a KV head and
    layer its own; the reference with every temperature at the model's
    initial 1 — what a program that ignored them would compute — is told
    apart by the first layer's ``q`` and ``conv1_kernel`` leaves by more
    than the chip's ``grad_rel``, with no two-number leaf compared."""
    cfg = compared["cfg"]
    drawn, _ = family.init(cfg, jax.random.PRNGKey(11))
    temps = jnp.stack([drawn[f"layer_{i}"]["attn"]["temp"]
                       for i in range(2)])
    assert temps.shape == (2, 2) and len(set(np.asarray(temps).ravel())) == 4
    assert float(temps.min()) >= 0.5 and float(temps.max()) <= 1.5
    assert float(jnp.abs(temps - 1.0).min()) > 0.02
    other, _ = family.init(cfg, jax.random.PRNGKey(12))
    assert not jnp.array_equal(other["layer_0"]["attn"]["temp"], temps[0])

    params = jax.tree.map(lambda a: a, compared["params"])
    for i in range(2):
        params[f"layer_{i}"]["attn"]["temp"] = jnp.ones(2)
    with jax.default_matmul_precision("highest"):
        ignored = jax.jit(jax.grad(
            lambda p: family.reference_loss(cfg)(p, {}, compared["tokens"])))(
                params)
    _, want = compared["reference"]
    limit = published()["tolerances"]["grad_rel"]
    for leaf in ("q", "conv1_kernel"):
        got = ignored["layer_0"]["attn"][leaf]
        ref = want["layer_0"]["attn"][leaf]
        got, ref = (got["kernel"], ref["kernel"]) if leaf == "q" else (got,
                                                                       ref)
        assert rel(got, ref) > limit, leaf


def test_the_layers_note_their_sizes(compared):
    noted = compared["noted"]
    attn = [n for n in noted.values() if "attn.latent_channels" in n]
    # Float32 is the module's own form: no row's passes ran as kernels.
    assert attn == 2 * [{"attn.latent_channels": 64 * (4 + 2 * 2) * 128,
                         "attn.conv_taps": 64 * 6 * 128 * 4,
                         "attn.cca_kernel_rows": 0}]
    moe = [n for n in noted.values() if "moe.router_hidden" in n]
    assert len(moe) == 2 and all(
        n["moe.router_hidden"] == 16 and n["moe.skip_choice"] == 1
        and n["moe.assignments"] == 64
        # 3 · top-1 · 4 held ≥ 9 outputs: the window is every assignment.
        and n["moe.permuted_assignments"] == 64
        and n["moe.held_assignments"] == 64 * 4 // 9 for n in moe)
    assert [n for n in noted.values() if "lm.tied_head" in n] == [
        {"lm.tied_head": 1}]


@pytest.mark.parametrize("seq,dtype,attn,rows", [
    (128, jnp.bfloat16, "flash", 2 * 128), (128, jnp.bfloat16, "full", 0),
    (128, F32, "flash", 0), (72, jnp.bfloat16, "flash", 0)],
    ids=["kernels", "the_dense_oracle", "float32", "no_whole_strip"])
def test_the_layer_counts_the_rows_its_passes_ran_as_kernels(seq, dtype,
                                                             attn, rows):
    """``attn.cca_kernel_rows`` is ``B · T`` a layer where
    ``cca_passes._plan`` takes the kernels (lane-aligned heads, two-byte
    activations, a sequence in whole strips of rows, ``attn="flash"``) and
    0 where the module's ``jax.numpy`` runs."""
    layer = CompressedConvAttention(num_heads=4, kv_heads=2, head_dim=128,
                                    attn=attn, dtype=dtype)
    u = jax.ShapeDtypeStruct((2, seq, 32), dtype)
    noted = {}
    jax.eval_shape(noting_layers(
        lambda u: layer.init(jax.random.PRNGKey(0), u), noted), u)
    (counters,) = noted.values()
    assert counters["attn.cca_kernel_rows"] == rows
    assert counters["attn.latent_channels"] == 2 * seq * 8 * 128


def test_the_reference_takes_the_program_s_choice_inside_the_margin_only():
    """With a margin of nothing the reference keeps its own argmax whatever
    it is given; with a margin of 1 it takes whatever it is given: a held
    expert for every token is another loss, and the choice that computes
    nothing is worth what an expert held elsewhere is — nothing."""
    cfg = family_cfg(num_hidden_layers=1, sequence_length=32)
    params, _ = family.init(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(1), 1))
    given = family.reference_given_choices(cfg)
    own = family.program_choices(cfg, params, tokens)
    held, elsewhere, skip = (jnp.full_like(own, e) for e in (0, 6, 8))
    with jax.default_matmul_precision("highest"):
        base = float(given(params, tokens, own, 0.0))
        assert float(given(params, tokens, held, 0.0)) == base
        forced = float(given(params, tokens, held, 1.0))
        assert abs(forced - base) > 1e-4
        nothing = float(given(params, tokens, skip, 1.0))
        assert nothing == float(given(params, tokens, elsewhere, 1.0))
        assert abs(forced - nothing) > 1e-4
