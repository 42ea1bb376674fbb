"""The Kimi-Linear family on the normal path: the shares of the experts add
up to the uncut layer; the tiny preset trains through ``make_train_step``
with the routers' bias in ``aux_state`` and bumps the mixers' and the latent
layer's counters; and the traces of the family at the CELL's shapes stay
inside the program's span ring.  The reference comparisons are
``tests/test_kimi_stack.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import kimi_linear_lm as family
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.parallel.moe import DroplessMoE, _SharedExpert
from test_kimi_stack import F32, family_cfg, published, rel


# ------------------------------------------------------------ the shares


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 of 32 experts each (the cell's 8 of 256, at a small
    size).  Every share routes over all 32 by sigmoid scores, chooses the
    top 5, normalises the gates over them and scales by 2.446, and runs ITS
    experts on the rows routed to them; the shared expert, which every chip
    computes alike, is counted once.  The sum is the family's plain
    reference on the uncut layer, and the uncut program's."""
    uncut = {**published(), "hidden_size": 24, "num_experts": 32,
             "experts_routed_over": 32, "num_experts_per_token": 5,
             "moe_intermediate_size": 20}
    E, K, T = 32, 5, 32
    fields = dict(num_experts=E, hidden=20, top_k=K, router="sigmoid",
                  renormalize=True,
                  gate_scale=float(uncut["routed_scaling_factor"]),
                  activation="swiglu", dtype=F32)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, T, 24), F32)
    whole = DroplessMoE(shared_hidden=20, **fields).init(
        jax.random.PRNGKey(5), u)["params"]
    with jax.default_matmul_precision("highest"):
        out, state = DroplessMoE(shared_hidden=20, **fields).apply(
            {"params": whole}, u, mutable=["intermediates"])
        chosen = state["intermediates"]["expert_index"][0]      # (T, K)
        want, routing = family.reference_experts(uncut)(
            whole, jnp.zeros((E,), F32), u[0], chosen, 0.0)
        assert not float(routing[0])      # the choices are the reference's
        total = _SharedExpert(20, F32, activation="swiglu").apply(
            {"params": whole["shared"]}, u)
        landed = 0
        for first in range(0, E, 8):
            share = {k: (v[first:first + 8] if k.startswith("w_") else v)
                     for k, v in whole.items() if k != "shared"}
            part, state = DroplessMoE(held=(first, 8), **fields).apply(
                {"params": share}, u, mutable=["intermediates"])
            total = total + part[0]
            landed += int(state["intermediates"]["held_assignments"][0])
    assert landed == T * K
    assert rel(total[0], want) < 1e-5
    assert rel(out[0][0], want) < 1e-5


# ----------------------------------------------------- the normal path


def test_tiny_stack_trains_through_make_train_step(hvd):
    """The preset through the normal path on the 8-device mesh: the first
    step's loss is the reference's on the global batch, the loss falls, the
    state stays float32, the bias moves, and each dispatch bumps the
    mixers' and the latent layer's counters from the shapes they noted."""
    cfg = family_cfg("bfloat16")
    params, aux = family.init(cfg, jax.random.PRNGKey(1))
    tokens = family.host_batch(cfg, np.random.default_rng(7), 8)
    tx = family.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(jax.jit(family.reference_loss(cfg))(params, aux, tokens))
    step = make_train_step(family.loss_fn(cfg), tx, hvd.ranks_mesh(),
                           sync_aux_state=family.SYNC_AUX_STATE)
    names = ("lin.delta_chunks", "lin.state_bytes", "lin.decay_bytes",
             "lin.sub_chunks", "lin.tile_kernel_chunks", "attn.q_latent",
             "attn.kv_latent")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    losses = []
    for _ in range(4):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) / want <= 5e-3
    assert losses[-1] < losses[0]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    assert max(float(jnp.abs(a).max()) for a in jax.tree.leaves(aux)) > 0
    after = registry.snapshot()["counters"]
    # A shard's step, four dispatches: one sequence of 64 tokens through
    # two mixers (4 chunks of 16; 2 heads of 16 x 16 float32 a state, 2 x
    # 16 log-decays a token, 15 pairs of sub-chunks a chunk; heads of 16
    # under manual axes: no tile kernel) and one latent layer with no query
    # latent.
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "lin.delta_chunks": 4 * 2 * 4,
        "lin.state_bytes": 4 * 2 * 4 * 2 * 16 * 16 * 4,
        "lin.decay_bytes": 4 * 2 * 64 * 2 * 16 * 4,
        "lin.sub_chunks": 4 * 2 * 4 * 15, "lin.tile_kernel_chunks": 0,
        "attn.q_latent": 0, "attn.kv_latent": 4 * 32}


def test_the_step_counts_the_chunks_whose_tiles_the_kernels_made(hvd):
    """Two mixers at a shape the tile kernels take (keys of 128 in chunks of
    64, one device, so no manual axes) through ``make_train_step``: the
    step's ``lin.tile_kernel_chunks`` is its ``lin.delta_chunks`` — every
    chunk's tiles were made in VMEM (interpreted here) — and the loss falls."""
    import flax.linen as nn
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.models.linear_attention import KimiDeltaAttention

    class Two(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(2):
                x = x + KimiDeltaAttention(
                    num_heads=1, key_dim=128, value_dim=16, chunk=64,
                    low_rank=8, dtype=F32)(x)
            return x

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 16), F32)
    params = Two().init(jax.random.PRNGKey(3), x)["params"]
    tx = optax.sgd(0.05)

    def loss_fn(p, aux, batch):
        return (Two().apply({"params": p}, batch) ** 2).mean(), aux

    step = make_train_step(loss_fn, tx,
                           Mesh(np.asarray(jax.devices()[:1]), ("ranks",)))
    names = ("lin.delta_chunks", "lin.tile_kernel_chunks")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    opt_state, aux, losses = tx.init(params), {}, []
    for _ in range(2):
        params, aux, opt_state, loss = step(params, aux, opt_state, x)
        losses.append(float(loss))
    assert losses[1] < losses[0]
    after = registry.snapshot()["counters"]
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "lin.delta_chunks": 2 * 2 * 2, "lin.tile_kernel_chunks": 2 * 2 * 2}


# ------------------------------------------------- the ring, off the chip


def test_the_family_s_traces_at_the_cell_s_shapes_stay_inside_the_ring():
    """PR 61's failure, guarded off the chip: every trace of a jitted
    function is a ``jax/trace`` span in the program's ring, which holds
    16,384; a reference or a model that unrolls Python loops at the
    published sizes fills it, and the readers of ``trace_s``, ``lower_s``
    and ``xla_s`` then read nothing.  Traced with ``jax.eval_shape`` at the
    CELL's shapes (no compile, no arrays): the reference's loss and
    gradients and the program's — what ``benchmark/run.py``'s reference
    check traces — keep the ring under half its room and drop nothing."""
    from horovod_tpu import timeline

    cfg = published()
    params, aux = jax.eval_shape(lambda k: family.init(cfg, k),
                                 jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, cfg["sequence_length"] + 1), jnp.int32)
    reference, loss_fn = family.reference_loss(cfg), family.loss_fn(cfg)
    timeline.listen_to_jax()
    timeline.ring.clear()
    dropped = timeline.ring.dropped
    jax.eval_shape(reference, params, aux, tokens)
    jax.eval_shape(jax.grad(reference), params, aux, tokens)
    jax.eval_shape(jax.grad(lambda p, a, t: loss_fn(p, a, t)[0]), params,
                   aux, tokens)
    kept = timeline.ring.snapshot()
    assert timeline.ring.dropped == dropped
    assert 0 < len(kept) < 8192, len(kept)
