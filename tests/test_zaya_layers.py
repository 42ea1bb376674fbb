"""The ZAYA1 layer's parts (``tests/test_zaya_stack.py`` has the tree and
the reference): rotary positions on half a head; compressed convolutional
attention by hand, its reach and what each part of the latent does; the
residual merge; the expert sub-layer's two shares, its choice bias and the
router's state handed from layer to layer; and the lowered programs of the
three routers the benchmark's other cells run, pinned.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    CompressedConvAttention, ResidualMerge, TransformerLM, Zaya1LM,
    apply_rotary)
from horovod_tpu.models.transformer import PatternLayer
from horovod_tpu.parallel.moe import DroplessMoE

from test_zaya_stack import F32, rel


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
    # A lowering reads the parameters' shapes alone: none is drawn.
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(1), x))

    def g(params, x):
        def loss(p):
            out, b, z = layer.apply(p, x)
            return (out ** 2).sum() + b + z
        return jax.value_and_grad(loss)(params)

    text = jax.jit(g).lower(params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest, name
