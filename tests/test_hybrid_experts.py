"""Attention and the experts of the hybrid (pattern) stack against plain
references, at small sizes on the CPU: the flash kernels with grouped KV
heads against the dense oracle with the keys and values repeated, and
``DroplessMoE`` told which experts it holds — the shares add up to the uncut
layer, and a skewed router loses no assignment.  (One of the four files
``test_hybrid_stack.py`` was until PR 50.)
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.families import nemotron_h_lm, olmo_hybrid_lm
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.models import (
    NemotronHLM, OlmoHybridLM, SwiGLU, TransformerLM)
from horovod_tpu.models.linear_attention import GatedDeltaNet
from horovod_tpu.models.ssm import Mamba2Mixer
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops import ssd
from horovod_tpu.ops.ssd import (
    scan_sizes, ssd_recurrence, ssd_scan, ssd_scan_packed)
from horovod_tpu.parallel.moe import (
    DroplessMoE, _SharedExpert, _window_plan)
from horovod_tpu.parallel.ring_attention import full_attention

from test_dropless_moe import equations
from test_gated_delta import _equations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))



# ------------------------------------------------ grouped-query attention


# (B, T, H, Hkv, D, block): the fully-unrolled forward; 17 KV blocks, so
# the grid forward; a head off the lane width (repeated, then merged into
# the batch); and multi-head attention through the same entry.
@pytest.mark.parametrize("B,T,H,Hkv,D,block", [
    (1, 256, 4, 2, 128, 128), (2, 256, 4, 1, 128, 64),
    (1, 1088, 2, 1, 128, 64), (1, 64, 4, 2, 32, 32),
    (1, 256, 2, 2, 128, 128)],
    ids=["16Q_per_KV_shape_small", "one_KV_head", "grid_forward", "D32",
         "multi_head"])
def test_grouped_kv_flash_equals_full_attention(B, T, H, Hkv, D, block):
    """Forward, dQ, and dK / dV summed over the query heads of a group,
    against ``full_attention`` on keys and values repeated H / Hkv times."""
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    weight = jax.random.normal(ks[3], (B, T, H, D))

    def ours(q, k, v):
        return (flash_attention(q, k, v, block_q=block, block_k=block,
                                interpret=True) * weight).sum()

    def oracle(q, k, v):
        rep = H // Hkv
        return (full_attention(q, jnp.repeat(k, rep, 2),
                               jnp.repeat(v, rep, 2), causal=True)
                * weight).sum()

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(ours, (0, 1, 2))(q, k, v)
        want, want_g = jax.value_and_grad(oracle, (0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) <= 1e-3
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)


def test_grouped_kv_heads_must_divide():
    q = jnp.zeros((1, 64, 3, 128))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], interpret=True)


# ---------------------------------------------------------- held experts

N, D_, HID, E, K = 96, 16, 24, 16, 3
NEMOTRON = dict(num_experts=E, hidden=HID, top_k=K, dtype=jnp.float32,
                router="sigmoid", renormalize=True, gate_scale=2.5,
                activation="relu2", shared_hidden=40)


# JoyAI-LLM-Flash's layer at the same sizes: SwiGLU experts and shared
# expert, and the k chosen by ``s + b`` with ``b`` a state, here off zero.
JOYAI = {**NEMOTRON, "activation": "swiglu", "choice_bias": 1e-3}
BIAS = 0.2 * jax.random.normal(jax.random.PRNGKey(7), (32,))


def uncut_layer(skewed: bool, experts: int = E, settings=NEMOTRON):
    layer = DroplessMoE(**{**settings, "num_experts": experts})
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D_))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    if skewed:
        # Experts 0 and 1 are in every token's top-3, whatever the token.
        x = x.at[:, 0].set(3.0)
        kernel = params["router"]["kernel"].at[0, :2].set(10.0)
        params = {**params, "router": {"kernel": kernel}}
    return layer, params, x


def share_of(params, first, count):
    return {**params, **{name: params[name][first:first + count]
                         for name in ("w_gate", "w_up", "w_down")
                         if name in params}}


def nemotron_oracle(params, x, bias=0.0, top_k=K):
    """Every expert on every token, weighted by the top-k mask: the k
    largest of ``s + bias`` chosen, gated by ``s``; relu² experts, or
    SwiGLU ones where the parameters hold a ``w_gate``."""
    E = params["router"]["kernel"].shape[1]

    def expert(p, e=...):
        up = x @ p["w_up"][e]
        if "w_gate" in p:
            return (jax.nn.silu(x @ p["w_gate"][e]) * up) @ p["w_down"][e]
        return jnp.square(jax.nn.relu(up)) @ p["w_down"][e]

    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ params["router"]["kernel"])
        kth = jnp.sort(s + bias, axis=-1)[:, -top_k]
        gates = jnp.where(s + bias >= kth[:, None], s, 0.0)
        gates = 2.5 * gates / gates.sum(-1, keepdims=True)
        out = expert(params["shared"])
        for e in range(E):
            out = out + gates[:, e:e + 1] * expert(params, e)
    return out


@pytest.mark.parametrize("skewed,experts,settings", [
    (False, 16, NEMOTRON), (True, 16, NEMOTRON), (True, 32, NEMOTRON),
    (False, 32, JOYAI)],
    ids=["balanced", "two_experts_take_every_token_five_windows",
         "two_of_32_take_every_token_eight_windows",
         "sixteen_shares_under_a_bias_that_is_not_zero"])
def test_the_shares_add_up_to_the_uncut_layer(skewed, experts, settings):
    """Shares of two experts each: what they give, with the shared expert
    (every share computes it alike) counted once, is the uncut layer's
    output, which is the loop over all experts.  The counts of
    assignments that landed on the shares add up to k N — none is lost.
    Every share runs ``ceil(landed / W)`` windows of the ``W`` rows that
    ``_window_plan`` gives it.  In the skewed cases share 0 takes 2 N = 192
    of them: of 16 experts that is five windows of 40 rows (the uniform
    load is 36), of 32 eight of 24 (18: a window over the uniform load,
    where a window's fixed cost outweighs its rows'), and the shares no
    token chose run none.  The last case is JoyAI-LLM-Flash's layer (PR 50): the sixteen shares of 32
    SwiGLU experts, chosen by ``s + b`` under a bias ``b`` that is not zero
    and is every share's alike, gated by ``s`` alone."""
    whole, params, x = uncut_layer(skewed, experts, settings)
    state = ({"balance": {"choice_bias": BIAS[:experts]}}
             if settings.get("choice_bias") else {})
    bias = BIAS[:experts] if state else 0.0
    shares = experts // 2
    with jax.default_matmul_precision("highest"):
        want, _, _ = whole.apply({"params": params, **state}, x)
        np.testing.assert_allclose(want, nemotron_oracle(params, x, bias),
                                   rtol=1e-5, atol=1e-5)
        if state:       # the bias moved choices: without it, another output
            assert float(jnp.abs(want - nemotron_oracle(params, x)).max()
                         ) > 1e-3
        parts, landed, windows = [], [], []
        for i in range(shares):
            layer = DroplessMoE(**{**settings, "num_experts": experts},
                                held=(2 * i, 2))
            (out, _, _), sown = layer.apply(
                {"params": share_of(params, 2 * i, 2), **state}, x,
                mutable=["intermediates"])
            parts.append(out)
            landed.append(int(sown["intermediates"]["held_assignments"][0]))
            windows.append(int(sown["intermediates"]["held_windows"][0]))
        shared = _SharedExpert(40, jnp.float32,
                               activation=settings["activation"]).apply(
            {"params": params["shared"]}, x)
    # The shared expert's output is in the sum once a share and is taken
    # out again all but once: with 16 shares that costs float32 a digit.
    np.testing.assert_allclose(sum(parts) - (shares - 1) * shared, want,
                               rtol=5e-5, atol=5e-5)
    assert sum(landed) == N * K
    rows = window_rows(N * K, 2, experts, 3 if "w_gate" in params else 2)
    assert rows == {16: 40, 32: 32 if "w_gate" in params else 24}[experts]
    assert windows == [-(-n // rows) for n in landed]
    if skewed:
        assert landed[0] == 2 * N and windows[0] == {16: 5, 32: 8}[experts]
        assert 0 in windows


@pytest.mark.parametrize("experts", [16, 32],
                         ids=["five_windows", "eight_windows"])
def test_a_share_s_gradients_equal_the_masked_loop_s(experts):
    """One share under the skewed router (192 assignments: five windows of
    40 rows, eight of 24): gradients of its own experts, the router, the
    shared expert and the input against the oracle restricted to the held
    experts."""
    E = experts
    _, params, x = uncut_layer(True, experts)
    layer = DroplessMoE(**{**NEMOTRON, "num_experts": experts}, held=(0, 2))
    mine = share_of(params, 0, 2)

    def ours(p, x):
        out = layer.apply({"params": p}, x)[0]
        return (out * jnp.cos(out)).sum()

    def oracle(p, x):
        zeros = jnp.zeros((E - 2,) + p["w_up"].shape[1:])
        full = {**p, "w_up": jnp.concatenate([p["w_up"], zeros]),
                "w_down": jnp.concatenate(
                    [p["w_down"], zeros.transpose(0, 2, 1)])}
        out = nemotron_oracle(full, x)
        return (out * jnp.cos(out)).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, (0, 1))(mine, x)
        want = jax.grad(oracle, (0, 1))(mine, x)
    # Leaves of up to 100 in size, summed in another order: 2e-4 of that.
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_a_share_traces_under_shard_map_with_vma_checks():
    """Tokens split over the data-parallel axis, the share replicated: the
    loop's carry varies as the tokens do, and each shard runs the windows
    of its own count."""
    _, params, x = uncut_layer(True)
    layer = DroplessMoE(**NEMOTRON, held=(0, 2))
    mine = share_of(params, 0, 2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("ranks",))
    out = jax.jit(shard_map(
        lambda p, x: layer.apply({"params": p}, x)[0], mesh=mesh,
        in_specs=(P(), P("ranks")), out_specs=P("ranks"),
        check_vma=True))(mine, x)
    halves = [layer.apply({"params": mine}, h)[0]
              for h in (x[:N // 2], x[N // 2:])]
    np.testing.assert_allclose(out, jnp.concatenate(halves), rtol=1e-5,
                               atol=1e-5)


def window_rows(assignments, held, routed, matrices=2):
    """``W`` of a float32 share of ``held`` experts of ``matrices`` each."""
    return _window_plan(assignments=assignments, held=held, routed=routed,
                        row_bytes=4 * D_,
                        expert_bytes=4 * matrices * held * D_ * HID).rows


# (assignments, held, routed, bytes a row, parameters a held expert) of a
# layer of the six cells that hold a share, and what the plan gives each:
# rows a window, the most windows, the uniform load.
CELLS = {
    "keye_and_sdar_1chip": ((131_072, 16, 128, 4096, 3 * 2048 * 768),
                            (16_384, 8, 16_384)),
    "joyaiflash_1chip": ((131_072, 16, 256, 4096, 3 * 2048 * 768),
                         (10_752, 13, 8_192)),
    "twotower_1chip": ((98_304, 8, 128, 5376, 2 * 2688 * 1856),
                       (7_680, 13, 6_144)),
    "nemo3super_1chip": ((180_224, 8, 512, 2048, 2 * 1024 * 2688),
                         (5_632, 32, 2_816)),
    "zaya1_1chip": ((16_384, 8, 17, 4096, 3 * 2048 * 2048),
                    (16_384, 1, 7_710)),
    "a_tenth_of_a_row_tile": ((1_024, 1, 16, 256, 2 * 128 * 128),
                              (96, 11, 64))}


@pytest.mark.parametrize("cell", CELLS)
def test_the_window_plan_at_the_cells_shapes(cell):
    """``_window_plan`` is a function of shapes alone: never under the
    uniform load, over it where a window's fixed cost outweighs its rows'
    (the more the narrower a row and the fewer land), in whole row tiles
    of the kernels where the load fills one (sublanes below), and every
    assignment where the share is a third of the layer."""
    (assignments, held, routed, row_bytes, parameters), want = CELLS[cell]
    plan = _window_plan(assignments=assignments, held=held, routed=routed,
                        row_bytes=row_bytes,
                        expert_bytes=4 * held * parameters)
    assert tuple(plan) == want
    assert plan.rows >= min(plan.uniform, assignments)
    assert plan.windows == -(-assignments // plan.rows)
    assert plan.rows % (512 if 512 <= plan.uniform < plan.rows else 8) == 0


# Two held of 16 at top-2: 192 assignments, 24 a uniform load, 32 a window.
LOADS = {"none": 0, "a_row_short_of_a_window": 31, "a_window": 32,
         "a_row_over": 33, "a_row_over_three": 97, "every_assignment": 192}


def layer_at_load(landed: int):
    """The uncut layer at top-2, its router's first two input rows set so
    that exactly ``landed`` assignments go to experts 0 and 1: a token
    whose feature ``e`` is +3 has expert ``e`` among its two, one at -3
    never has."""
    settings = {**NEMOTRON, "top_k": 2}
    layer = DroplessMoE(**settings)
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D_))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    kernel = params["router"]["kernel"].at[:2].set(0.0)
    kernel = kernel.at[0, 0].set(10.0).at[1, 1].set(10.0)
    to_first = -(-landed // 2)
    x = x.at[:, 0].set(jnp.where(jnp.arange(N) < to_first, 3.0, -3.0))
    x = x.at[:, 1].set(jnp.where(jnp.arange(N) < landed - to_first, 3.0,
                                 -3.0))
    return settings, {**params, "router": {"kernel": kernel}}, x


@pytest.mark.parametrize("landed", LOADS.values(), ids=LOADS.keys())
def test_the_windows_follow_the_load(landed):
    """At every load from none to every assignment the share runs
    ``ceil(landed / W)`` windows, drops nothing (the eight shares' counts
    add up to k N) and gives the output and gradients of the oracle
    restricted to the held experts."""
    settings, params, x = layer_at_load(landed)
    rows = window_rows(2 * N, 2, E)
    assert rows == 32
    mine = share_of(params, 0, 2)

    def share(first):
        return DroplessMoE(**settings, held=(first, 2))

    def ours(p, x):
        out = share(0).apply({"params": p}, x)[0]
        return (out * jnp.cos(out)).sum()

    def oracle(p, x):
        zeros = jnp.zeros((E - 2,) + p["w_up"].shape[1:])
        full = {**p, "w_up": jnp.concatenate([p["w_up"], zeros]),
                "w_down": jnp.concatenate(
                    [p["w_down"], zeros.transpose(0, 2, 1)])}
        out = nemotron_oracle(full, x, top_k=2)
        return (out * jnp.cos(out)).sum()

    counts = []
    with jax.default_matmul_precision("highest"):
        for first in range(0, E, 2):
            (out, _, _), sown = share(first).apply(
                {"params": share_of(params, first, 2)}, x,
                mutable=["intermediates"])
            sown = sown["intermediates"]
            counts.append(int(sown["held_assignments"][0]))
            assert int(sown["held_windows"][0]) == -(-counts[-1] // rows)
        got = jax.value_and_grad(ours, (0, 1))(mine, x)
        want = jax.value_and_grad(oracle, (0, 1))(mine, x)
    assert counts[0] == landed and sum(counts) == 2 * N
    # Sums of up to 192 rows in another order: 1e-5 of a leaf's largest.
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-4 + 1e-5 * float(jnp.abs(w).max()))


def loops_of(jaxpr):
    """``(primitive name, equation)`` of every loop and branch in
    ``jaxpr`` and below it."""
    return [(eqn.primitive.name, eqn) for eqn in equations(jaxpr)
            if eqn.primitive.name in ("while", "scan", "cond")]


def test_a_share_is_one_loop_each_way_under_a_bound_read_on_the_device():
    """The traced program of a share whose window is smaller than its
    assignments: ONE ``while`` forward and one more backward, no ``cond``
    and no ``scan``; the bound is a value of the program (the loop's
    condition compares the counter with an operand, not a literal); the
    forward carries the float32 combine, the backward ``dx``, ``dgate``
    and every ``dW`` in float32 whatever the activations' dtype."""
    settings, params, x = layer_at_load(73)
    settings = {**settings, "dtype": jnp.bfloat16}
    layer = DroplessMoE(**settings, held=(0, 2))
    mine = share_of(params, 0, 2)
    x = x.astype(jnp.bfloat16)

    def loss(p, x):
        return layer.apply({"params": p}, x)[0].astype(jnp.float32).sum()

    forward = loops_of(jax.make_jaxpr(loss)(mine, x).jaxpr)
    both = loops_of(jax.make_jaxpr(jax.grad(loss, (0, 1)))(mine, x).jaxpr)
    assert [name for name, _ in forward] == ["while"]
    assert [name for name, _ in both] == ["while", "while"]
    for _, eqn in both:
        cond = eqn.params["cond_jaxpr"].jaxpr
        compare, = cond.eqns
        assert compare.primitive.name == "lt"
        assert all(v in cond.invars for v in compare.invars)
    carried = [sorted((v.aval.shape, str(v.aval.dtype))
                      for v in eqn.outvars if v.aval.ndim)
               for _, eqn in both]
    hidden = HID + -HID % 256          # ``lax.ragged_dot``'s lanes
    assert carried[0] == [((N, D_), "float32")]
    assert carried[1] == sorted([
        ((N, D_), "float32"), ((2 * N,), "float32"),
        ((2, D_, hidden), "float32"), ((2, hidden, D_), "float32")])


def test_held_must_be_a_range_of_the_experts():
    _, params, x = uncut_layer(False)
    with pytest.raises(ValueError, match="held"):
        DroplessMoE(**NEMOTRON, held=(14, 4)).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="router"):
        DroplessMoE(**{**NEMOTRON, "router": "tanh"}).init(
            jax.random.PRNGKey(0), x)
