"""The cut of ``nemo3super_1chip`` is one chip's share of every layer, and
the shares add up: at a small size, the outputs of the eight
tensor-parallel ranks' mixers (``Mamba2Mixer`` at the rank's ONE group) and
attention shares (the rank's query heads over the KV head they read), and
of all the expert shares of a LatentMoE layer (``DroplessMoE(latent=...,
held=...)``) with what every chip computes alike — router, latent
projections, shared expert — counted once, are what the benchmark
family's plain reference gives for the uncut layer.  Program modules in
float32 on slices of ONE uncut parameter tree; no code stands in for the
absent chips: the sum is taken here, in the test.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import nemotron3_super_lm as family
from horovod_tpu.models import GroupedQueryAttention
from horovod_tpu.models.ssm import Mamba2Mixer
from horovod_tpu.parallel.moe import DroplessMoE, _SharedExpert

F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANKS = 8

# The uncut layer at a small size, in the configuration's own keys: 16
# mixer heads of 8 in 8 groups, 8 query heads over 2 KV heads, 32 experts
# top-5 in a latent of 12.
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron-3-super-120b-a12b.json")) as _fh:
    UNCUT = {**json.load(_fh), "hidden_size": 24, "mamba_num_heads": 16,
             "mamba_head_dim": 8, "n_groups": 8, "ssm_state_size": 8,
             "chunk_size": 8, "num_attention_heads": 8,
             "num_key_value_heads": 2, "head_dim": 16,
             "n_routed_experts": 32, "experts_routed_over": 32,
             "num_experts_per_tok": 5, "moe_intermediate_size": 20,
             "moe_latent_size": 12,
             "moe_shared_expert_intermediate_size": 28,
             "sequence_length": 32}
T, D = UNCUT["sequence_length"], UNCUT["hidden_size"]


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def seeded(module, *args, seed):
    """The module's parameters with every vector off its initial 0 or 1,
    so that a share that ignored one would show."""
    params = module.init(jax.random.PRNGKey(seed), *args)["params"]
    return jax.tree.map(
        lambda a: a + (0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               a.shape) if a.ndim == 1
                       else 0.0), params)


def u_of(seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, D), F32)


def test_the_eight_ranks_mixers_add_up_to_the_uncut_mixer():
    """Rank ``g`` of 8 holds heads ``2g, 2g + 1`` and group ``g``: the
    columns ``[z_g | x_g B_g C_g | dt_g]`` of the input projection, its
    channels of the convolution, its entries of ``dt_bias``, ``A_log``,
    ``D``, its channels of the gated norm's scale (one norm group IS one
    B/C group) and its rows of the output projection.  Nothing in a mixer
    crosses groups before ``W_out``, so the outputs add up."""
    H, P, G, N = (UNCUT[k] for k in ("mamba_num_heads", "mamba_head_dim",
                                     "n_groups", "ssm_state_size"))
    inner, h, c = H * P, H // G, H * P // G    # a rank's heads and channels
    fields = dict(head_dim=P, state_size=N, conv_kernel=4, chunk=8, dtype=F32)
    u = u_of(0)
    whole = seeded(Mamba2Mixer(num_heads=H, n_groups=G, **fields), u, seed=1)
    with jax.default_matmul_precision("highest"):
        want = family.reference_mixer(UNCUT)(whole, u[0])

        def columns(g):
            """Rank g's columns of [z | x B C | dt], and of [x | B | C]."""
            x = np.arange(g * c, (g + 1) * c)
            B = inner + np.arange(g * N, (g + 1) * N)
            xBC = np.concatenate([x, B, G * N + B])
            return (np.concatenate([x, inner + xBC, 2 * inner + 2 * G * N
                                    + np.arange(g * h, (g + 1) * h)]), xBC)

        total = 0.0
        for g in range(G):
            cols, xBC = columns(g)
            heads = slice(g * h, (g + 1) * h)
            share = {
                "in_proj": {"kernel": whole["in_proj"]["kernel"][:, cols]},
                "conv": {"kernel": whole["conv"]["kernel"][:, xBC],
                         "bias": whole["conv"]["bias"][xBC]},
                "dt_bias": whole["dt_bias"][heads],
                "A_log": whole["A_log"][heads], "D": whole["D"][heads],
                "gate_norm": whole["gate_norm"][g * c:(g + 1) * c],
                "out_proj": {"kernel": whole["out_proj"]["kernel"][
                    g * c:(g + 1) * c]}}
            total = total + Mamba2Mixer(num_heads=h, n_groups=1, **fields
                                        ).apply({"params": share}, u)[0]
    assert rel(total, want) < 1e-5
    assert rel(total - Mamba2Mixer(num_heads=h, n_groups=1, **fields).apply(
        {"params": share}, u)[0], want) > 1e-2      # every rank is needed


def test_the_eight_ranks_attention_shares_add_up_to_the_uncut_layer():
    """Rank ``g`` of 8 holds query head ``g`` (the cell's rank four of 32)
    over KV head ``g // 4``, which the 4 ranks that read it each hold: its
    columns of ``q``, that KV head's of ``k | v``, its rows of the output
    projection."""
    H, Hkv, Dh = (UNCUT[k] for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    u = u_of(2)
    whole = seeded(GroupedQueryAttention(H, Hkv, Dh, attn="full", dtype=F32),
                   u, seed=3)
    with jax.default_matmul_precision("highest"):
        want = family.reference_attention(UNCUT)(whole, u[0])
        total = 0.0
        for g in range(RANKS):
            q = slice(g * Dh, (g + 1) * Dh)
            kv = g // (H // Hkv)
            k = np.arange(kv * Dh, (kv + 1) * Dh)
            share = {
                "q": {"kernel": whole["q"]["kernel"][:, q]},
                "kv": {"kernel": whole["kv"]["kernel"][
                    :, np.concatenate([k, Hkv * Dh + k])]},
                "proj": {"kernel": whole["proj"]["kernel"][q]}}
            total = total + GroupedQueryAttention(
                1, 1, Dh, attn="full", dtype=F32).apply({"params": share},
                                                        u)[0]
    assert rel(total, want) < 1e-5


def test_the_expert_shares_add_up_to_the_uncut_latent_layer():
    """Four chips hold 8 of the 32 experts each.  Every share routes over
    all 32, chooses the top 5 and normalises the gates over them, projects
    every token into the latent, runs ITS experts on the rows routed to
    them, and projects its partial sum up: ``W_up`` is linear, so the
    shares' ``y_share W_up`` add up to ``y W_up``.  The shared expert,
    which every chip computes alike, is counted once."""
    E, K = UNCUT["experts_routed_over"], UNCUT["num_experts_per_tok"]
    fields = dict(num_experts=E, hidden=UNCUT["moe_intermediate_size"],
                  top_k=K, router="sigmoid", renormalize=True,
                  gate_scale=float(UNCUT["routed_scaling_factor"]),
                  activation="relu2", latent=UNCUT["moe_latent_size"],
                  dtype=F32)
    sh = UNCUT["moe_shared_expert_intermediate_size"]
    u = u_of(4)
    whole = DroplessMoE(shared_hidden=sh, **fields).init(
        jax.random.PRNGKey(5), u)["params"]
    with jax.default_matmul_precision("highest"):
        uncut, state = DroplessMoE(shared_hidden=sh, **fields).apply(
            {"params": whole}, u, mutable=["intermediates"])
        chosen = state["intermediates"]["expert_index"][0]      # (T, K)
        want, routing = family.reference_experts(UNCUT)(whole, u[0], chosen,
                                                        0.0)
        assert not float(routing[0])      # the choices are the reference's
        total = _SharedExpert(sh, F32).apply({"params": whole["shared"]}, u)
        landed = 0
        for first in range(0, E, 8):
            share = {k: (v[first:first + 8] if k.startswith("w_") else v)
                     for k, v in whole.items() if k != "shared"}
            out, state = DroplessMoE(held=(first, 8), **fields).apply(
                {"params": share}, u, mutable=["intermediates"])
            total = total + out[0]
            landed += int(state["intermediates"]["held_assignments"][0])
    assert landed == T * K
    assert rel(total[0], want) < 1e-5
    assert rel(uncut[0][0], want) < 1e-5
