"""The JoyAI-LLM-Flash preset through ``make_train_step`` with the routers'
balancing bias in ``aux_state``: every entry moves by exactly ±γ a step
against its load and carries no gradient; what does not compose is refused;
``BENCHMARK.json`` names the two cells this configuration's PR added.  The
program against the reference, leaf by leaf, is ``tests/test_joyai_stack.py``'s.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import joyai_flash_lm as family
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.models import JoyAIFlashLM, TransformerLM

from test_joyai_stack import GAMMA, ROOT, at, family_cfg, published


def test_tiny_stack_trains_with_the_bias_in_aux_state(hvd):
    """Two steps of the preset through the normal path on the 8-device mesh:
    the first step's loss is the reference's on the global batch, the state
    stays float32, and every entry of every layer's bias moves by exactly
    ±γ a step (or stays: a load that IS the mean) — against the load, the
    same on every shard — while its gradient is nothing."""
    cfg = family_cfg("bfloat16", sequence_length=32)
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(11))
    tokens = family.host_batch(cfg, np.random.default_rng(7), 8)
    tx = family.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(jax.jit(family.reference_loss(cfg))(params, aux, tokens))
    step = make_train_step(family.loss_fn(cfg), tx, hvd.ranks_mesh(),
                           sync_aux_state=family.SYNC_AUX_STATE)
    assert {float(jnp.abs(b).max()) for b in jax.tree.leaves(aux)} == {0.0}
    history = [jax.tree.map(np.asarray, aux)]       # the step donates it
    losses = []
    for _ in range(2):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
        history.append(jax.tree.map(np.asarray, aux))
    assert abs(losses[0] - want) / want <= 5e-3
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves((params, aux)))
    assert set(aux["balance"]) == {"layer_1", "mtp"}
    for before, after in zip(history, history[1:]):
        for b0, b1 in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            # The mean over 8 shards of their ±γ: a multiple of γ / 8.
            moved = np.asarray(b1 - b0, np.float64) / (GAMMA / 8)
            assert np.abs(moved - np.round(moved)).max() < 1e-3
            assert np.abs(moved).max() <= 8 + 1e-3 and np.abs(moved).max() > 0


def test_one_shard_s_bias_moves_by_exactly_gamma_and_has_no_gradient():
    cfg = family_cfg(sequence_length=32)
    params, aux = jax.jit(lambda k: family.init(cfg, k))(jax.random.PRNGKey(11))
    tokens = jnp.asarray(family.host_batch(cfg, np.random.default_rng(5), 2))
    loss = family.loss_fn(cfg)
    (_, moved), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(params, aux, tokens)
    assert not any(float(jnp.abs(g).max())
                   for g in jax.tree.leaves(grads[1]))
    hiddens, state = jax.jit(lambda p, a, t: family._model(cfg).apply(
        {"params": p, **a}, t[:, :-1], return_hidden=True,
        mutable=["intermediates"]))(params, aux, tokens)
    sown = state["intermediates"]
    for path in (("layer_1",), ("mtp", "layer_0")):
        load = np.asarray(at(sown, path)["moe"]["tokens_per_expert"][0])
        assert float(at(sown, path)["moe"]["choice_bias_absmax"][0]) == 0.0
        step = np.asarray(at(moved["balance"], path)["moe"]["choice_bias"])
        np.testing.assert_allclose(
            step, GAMMA * np.sign(load.mean() - load), rtol=1e-6)
    # Without a mutable "balance" the call is a pair of hidden states and
    # nothing moves.
    assert len(hiddens) == 2 and "balance" not in state


def test_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 9), jnp.int32)
    small = dict(vocab=32, dim=16, num_heads=1, mlp_hidden=8, moe_experts=4,
                 moe_top_k=1, moe_hidden=8,
                 mla=dict(q_latent=8, kv_latent=8, nope_dim=8, rope_dim=4,
                          v_dim=8))
    with pytest.raises(ValueError, match="'d' and 'x' layers"):
        JoyAIFlashLM(**small, pattern="dx", pos="learned").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="mla="):
        TransformerLM(vocab=32, dim=16, depth=1, num_heads=1,
                      mla=small["mla"]).init(jax.random.PRNGKey(0), tokens)
    from horovod_tpu.parallel.moe import DroplessMoE
    with pytest.raises(ValueError, match="choice_bias"):
        DroplessMoE(4, 8, 1, router="softmax", choice_bias=1e-3).init(
            jax.random.PRNGKey(0), jnp.zeros((3, 8)))


def test_benchmark_json_names_the_cells_the_config_and_the_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    config = {c["name"]: c for c in spec["configs"]}["joyai-llm-flash"]
    assert config == {
        **config, "file": "benchmark/configs/joyai-llm-flash.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]}
    cfg = published()
    assert list(cfg["reduced"]) == config["reduced"]
    assert [(cfg["reduced"][k]["published"], cfg["reduced"][k]["run"])
            for k in config["reduced"]] == [(40, 5), (256, 16),
                                            (129280, 16160)]
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells["joyaiflash_1chip"] == {
        **cells["joyaiflash_1chip"], "config": "joyai-llm-flash",
        "traffic": "dp1_b2", "chips": 1}
    assert cells["resnet50_dp4"] == {
        **cells["resnet50_dp4"], "config": "resnet50-v1.5",
        "traffic": "dp4_b256", "chips": 4}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) >= 2
    assert len(spec["workloads"]) >= 12 and len(spec["configs"]) >= 10
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name, unit, better in (("mla_ms", "ms", "lower"),
                               ("mla_attn_ms", "ms", "lower"),
                               ("mla_attn_roofline", "%", "higher")):
        # The cell that brought them first; a later cell with a latent
        # layer joins the list behind it (kimilinear_1chip, PR 62).
        assert metrics[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "latent attention",
            "moves": "step_ms", "workloads": metrics[name]["workloads"]}
        assert metrics[name]["workloads"][0] == "joyaiflash_1chip"
    for name in ("moe_ms", "moe_roofline", "route_ms", "mtp_ms",
                 "gqa_flash_ms", "gqa_flash_roofline"):
        assert "joyaiflash_1chip" in metrics[name]["workloads"], name
    for name in ("comm_ms", "comm_exposed_pct", "grad_reduce_ms"):
        assert "resnet50_dp4" in metrics[name]["workloads"], name
