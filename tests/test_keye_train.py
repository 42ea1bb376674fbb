"""The Keye-VL-2.0 language tower on the normal path, at small sizes on the
CPU (``tests/test_keye_stack.py`` holds it to the reference):

* the published parameter count and the benchmark's cut;
* the eight chips' held shares of one expert layer add up to the uncut
  layer;
* the tiny preset through ``make_train_step`` on the 8-device mesh, with
  the counters its attention layers note;
* what the layers sow, and the scopes the per-layer metrics read;
* options that do not compose are refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import keye_vl2_lm
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.models import (
    GroupedQueryAttention, KeyeLM, TransformerLM, index_losses)
from horovod_tpu.ops import flash_attention
from horovod_tpu.parallel.moe import DroplessMoE

from test_hybrid_experts import share_of
from test_keye_stack import family_cfg, model_inputs


def test_keye_stack_s_tree_and_the_published_count():
    """``KeyeLM()`` as published has 30.64 B parameters (the language
    tower alone); the benchmark's cut — 5 layers, 16 held experts, an
    eighth of the vocabulary — 562,289,920 (659,189,248 at the 6 layers
    ISSUE 36 counted)."""
    def count(model):
        shapes = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))["params"]
        return shapes, sum(int(np.prod(a.shape))
                           for a in jax.tree.leaves(shapes))

    shapes, n = count(KeyeLM())
    assert len([k for k in shapes if k.startswith("layer_")]) == 96
    layer = (2048 * 4096 + 2048 * 1024 + 4096 * 2048 + 256       # attention
             + 2048 * (1024 + 64 + 16)                           # indexer
             + 2048 * 128 + 128 * 3 * 2048 * 768 + 2 * 2048)     # experts
    assert n == 48 * layer + 2 * 151936 * 2048 + 2048 == 30_640_650_240
    cuts = [count(KeyeLM(pattern="SE" * layers, vocab=18992,
                         moe=dict(router="softmax", renormalize=True,
                                  activation="swiglu", held=(0, 16))))[1]
            for layers in (5, 6)]
    assert cuts == [562_289_920, 659_189_248]
    assert sorted(shapes["layer_0"]["attn"]) == [
        "index_k", "index_q", "index_w", "k_norm", "kv", "proj", "q",
        "q_norm"]


# ------------------------------------------------------- the held share


@pytest.mark.parametrize("lead", [(96,), (1, 2 * 96)],
                         ids=["a_row_a_token", "two_rows_a_token"])
def test_the_eight_shares_add_up_to_the_uncut_layer(lead):
    """Keye's expert layer (softmax router over 16, top-3 renormalised,
    SwiGLU, no shared expert) cut as the configuration cuts it: eight
    chips hold two experts each, every one routes over all 16, and their
    partial outputs add up to the uncut layer's, which is the loop over
    all experts.  No assignment is lost: the counts that landed add up
    to k N.  SDAR's layer is the same one at the ``2 T`` rows a sequence
    of its two-stream pass (``sdar_1chip``, PR 52): one sequence's clean and
    noised rows, twice the assignments a token."""
    D_, E, K = 16, 16, 3
    N = int(np.prod(lead))
    fields = dict(num_experts=E, hidden=24, top_k=K, dtype=jnp.float32,
                  router="softmax", renormalize=True, activation="swiglu")
    whole = DroplessMoE(**fields)
    x = jax.random.normal(jax.random.PRNGKey(0), (*lead, D_))
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    with jax.default_matmul_precision("highest"):
        want, _, _ = whole.apply({"params": params}, x)
        s = jax.nn.softmax(x @ params["router"]["kernel"], axis=-1)
        gates = jnp.where(s >= jnp.sort(s, axis=-1)[..., -K, None], s, 0.0)
        gates = gates / gates.sum(-1, keepdims=True)
        oracle = sum(gates[..., e:e + 1] * (
            (jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e]))
            @ params["w_down"][e]) for e in range(E))
        np.testing.assert_allclose(want, oracle, rtol=1e-5, atol=1e-5)
        parts, landed = [], []
        for chip in range(8):
            share = {**share_of(params, 2 * chip, 2),
                     "w_gate": params["w_gate"][2 * chip:2 * chip + 2]}
            (out, _, _), state = DroplessMoE(
                **fields, held=(2 * chip, 2)).apply(
                    {"params": share}, x, mutable=["intermediates"])
            parts.append(out)
            landed.append(int(state["intermediates"]["held_assignments"][0]))
    np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-5)
    assert sum(landed) == N * K


# ------------------------------------------------------ the normal path


def test_tiny_keye_trains_through_make_train_step(hvd, monkeypatch):
    """The preset through the normal path on the 8-device mesh, the loss
    read every step: the first is the reference's on the global batch, it
    falls, the state stays float32, and each dispatch bumps the attention
    layers' counters from the shapes they noted."""
    cfg = family_cfg("bfloat16")
    params, aux, _ = model_inputs(cfg)
    tokens = keye_vl2_lm.host_batch(cfg, np.random.default_rng(7), 8)
    tx = keye_vl2_lm.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(jax.jit(keye_vl2_lm.reference_loss(cfg))(
        params, aux, tokens))
    step = make_train_step(keye_vl2_lm.loss_fn(cfg), tx, hvd.ranks_mesh())
    names = ("attn.causal_pairs", "attn.selected_pairs", "attn.index_flops",
             "attn.select_bytes", "attn.select_tile_fetches",
             "moe.assignments", "moe.held_assignments")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    losses = []
    for _ in range(4):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) / want <= 5e-3
    assert losses[-1] < losses[0]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    after = registry.snapshot()["counters"]
    got = {n: after.get(n, 0) - before[n] for n in names}
    # A shard's step, four dispatches: one sequence of 64 tokens through 2
    # attention layers (16 of up to 64 keys a query; an indexer of 4 heads
    # of 64) and 2 expert layers (3 of 8 experts a token, 4 held).
    selected = sum(min(t + 1, 16) for t in range(64))
    assert got == {"attn.causal_pairs": 4 * 2 * 64 * 65 // 2,
                   "attn.selected_pairs": 4 * 2 * selected,
                   "attn.index_flops": 4 * 2 * 2 * 4 * 64 * 64 * 65 // 2,
                   "attn.select_bytes": 4 * 2 * 64 * 64,
                   # One tile of 64, one KV head, two kernels: the
                   # forward and the fused backward, which the plan takes
                   # here (the dq / dk-dv pair would read it a third time).
                   "attn.select_tile_fetches": 4 * 2 * 2,
                   "moe.assignments": 4 * 2 * 64 * 3,
                   "moe.held_assignments": 4 * 2 * 64 * 3 // 2}
    shard = (jax.ShapeDtypeStruct((1, 64, 2, 128), jnp.bfloat16),
             jax.ShapeDtypeStruct((1, 64, 1, 128), jnp.bfloat16))
    assert flash_attention.select_tile_fetches(*shard) == 2
    monkeypatch.setattr(flash_attention, "_FUSED_RESIDENT_BYTES", 0)
    assert flash_attention.select_tile_fetches(*shard) == 3


def test_what_the_layers_sow_and_the_scopes_they_trace_under():
    """Beside the router's: ``index_kl`` (a layer's ``L_I``),
    ``selected_per_query`` (the mean ``|S_t|``: the static count a query)
    and ``live_tiles``; and the scopes the per-layer metrics read."""
    cfg = family_cfg("float32")
    params, aux, tokens = model_inputs(cfg)
    model = keye_vl2_lm._model(cfg)
    _, state = jax.jit(lambda p, ids: model.apply(
        {"params": p}, ids, return_hidden=True, mutable=["intermediates"]))(
            params, tokens[:, :-1])
    sown = state["intermediates"]["layer_2"]["attn"]
    assert float(sown["selected_per_query"][0]) == sum(
        min(t + 1, 16) for t in range(64)) / 64
    assert float(sown["live_tiles"][0]) == 1.0
    assert float(sown["index_kl"][0]) > 0
    assert float(index_losses(state["intermediates"])) == pytest.approx(
        float(sown["index_kl"][0]
              + state["intermediates"]["layer_0"]["attn"]["index_kl"][0]))
    # (flax names a method's scope after it: ``attn/attn._selected/...``.)
    stacks = {stack.replace("attn._selected/", "") for stack in _name_stacks(
        jax.make_jaxpr(lambda p: keye_vl2_lm.loss_fn(cfg)(
            p, aux, tokens)[0])(params).jaxpr)}
    for scope in ("attn/index/project", "attn/index/scores",
                  "attn/index/topk", "attn/index/select", "attn/index/kl",
                  "attn/flash_select"):
        assert any(scope in stack for stack in stacks), scope
    # What the per-layer metrics read (benchmark/metrics/_sparse.py): the
    # three kernels' labels, each under its scope.
    from benchmark.metrics import _sparse
    kernels = {stack for stack in stacks if stack.endswith("pallas_call")}
    for found in (_sparse.is_selected_flash, _sparse.is_scores_kernel,
                  _sparse.is_kl_kernel):
        assert any(found(stack + " [custom-call]") for stack in kernels), (
            found.__name__, kernels)
    assert all(_sparse.in_attention(stack) for stack in kernels
               if "/attn/" in stack)


def _name_stacks(jaxpr, prefix=""):
    """Every equation's name stack with its primitive, as the profiler's
    op names have them (``tracered.label``), nested jaxprs under their
    equation's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(part for part in (
            prefix, str(eqn.source_info.name_stack)) if part)
        yield f"{stack}/{eqn.primitive.name}"
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _name_stacks(inner, stack)


def test_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    indexer = dict(num_heads=2, head_dim=64, topk=4)
    with pytest.raises(ValueError, match="pattern stack"):
        TransformerLM(vocab=32, dim=32, num_heads=2, indexer=indexer).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="'rotary' for its 'S' layers"):
        KeyeLM(vocab=32, pattern="SE", pos="learned").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="attn='flash' or 'full'"):
        GroupedQueryAttention(2, 1, 128, attn="ring", indexer=indexer).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16, 32)))
