"""The dropless expert layer and the OLMoE block against plain references.

``DroplessMoE`` (parallel/moe.py) sorts the token-to-expert assignments
and runs grouped matmuls over them; the oracle here loops over the experts,
applies each to ALL tokens and weights by the top-k mask of the router's
probabilities.  The whole model (``TransformerLM`` with RMSNorm, rotary,
QK-norm and experts) is held against the benchmark family's
``reference_loss``, which is written the same way.
"""

import collections
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.families import olmoe_lm
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.metrics import registry
from horovod_tpu.ops.grouped_matmul import GroupedPlan, grouped_plan
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import (
    DroplessMoE, _pad_hidden, router_losses)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, D, HID, E, K = 96, 16, 24, 6, 2


def oracle(params, x, k=K):
    """(output, load_balance, router_z): every expert on every token."""
    with jax.default_matmul_precision("highest"):
        logits = x @ params["router"]["kernel"]
        probs = jax.nn.softmax(logits, axis=-1)
        kth = jnp.sort(probs, axis=-1)[:, -k]
        chosen = probs >= kth[:, None]
        out = jnp.zeros_like(x)
        for e in range(probs.shape[1]):
            h = (jax.nn.silu(x @ params["w_gate"][e])
                 * (x @ params["w_up"][e])) @ params["w_down"][e]
            out = out + jnp.where(chosen[:, e], probs[:, e], 0.0)[:, None] * h
        f = chosen.sum(0) / x.shape[0]
        balance = probs.shape[1] * (f * probs.mean(0)).sum()
        z = (jax.nn.logsumexp(logits, axis=-1) ** 2).mean()
    return out, balance, z


def layer_and_params(skewed: bool):
    layer = DroplessMoE(num_experts=E, hidden=HID, top_k=K,
                        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    if skewed:
        # Every token's largest logit is expert 0's, whatever the token.
        kernel = params["router"]["kernel"]
        x = x.at[:, 0].set(3.0)
        params = {**params, "router": {
            "kernel": kernel.at[0, 0].set(10.0)}}
    return layer, params, x


@pytest.mark.parametrize("skewed", [False, True],
                         ids=["balanced", "one_expert_takes_every_token"])
def test_layer_equals_the_loop_over_experts(skewed):
    """float32 on both sides, so both routers choose the same experts and
    what is left is summation order: 1e-5 (observed 1e-6).  In the skewed
    case expert 0 is in every token's top-2 — three times its balanced
    share, over twice a capacity factor of 1.25 — and the output still
    equals the oracle's:
    nothing is dropped."""
    layer, params, x = layer_and_params(skewed)

    def run(p, x):
        with jax.default_matmul_precision("highest"):
            (out, balance, z), state = layer.apply(
                {"params": p}, x, mutable=["intermediates"])
        return out, balance, z, state["intermediates"]

    out, balance, z, sown = run(params, x)
    want_out, want_balance, want_z = oracle(params, x)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(balance, want_balance, rtol=1e-5)
    np.testing.assert_allclose(z, want_z, rtol=1e-5)
    counts, = sown["tokens_per_expert"]
    assert int(counts.sum()) == N * K
    if skewed:
        capacity = 1.25 * N * K / E          # what MoELayer would allow it
        assert int(counts[0]) == N > 2 * capacity

    def scalar(fn):
        def f(p, x):
            out, balance, z = fn(p, x)[:3]
            return (out * jnp.cos(out)).sum() + 0.3 * balance + 0.1 * z
        return f

    got = jax.grad(scalar(run), argnums=(0, 1))(params, x)
    want = jax.grad(scalar(oracle), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_sown_values_and_leading_dimensions():
    layer, params, x = layer_and_params(False)
    (out, balance, z), state = layer.apply(
        {"params": params}, x.reshape(4, N // 4, D),
        mutable=["intermediates"])
    assert out.shape == (4, N // 4, D)
    sown = state["intermediates"]
    assert set(sown) == {"aux_load_balance", "aux_router_z",
                         "tokens_per_expert", "expert_index"}
    assert sown["expert_index"][0].shape == (N, K)
    assert sown["tokens_per_expert"][0].shape == (E,)
    got_balance, got_z = router_losses({"a": {"moe": sown},
                                        "b": {"moe": sown}})
    np.testing.assert_allclose(got_balance, 2 * balance, rtol=1e-6)
    np.testing.assert_allclose(got_z, 2 * z, rtol=1e-6)
    with pytest.raises(ValueError, match="top_k"):
        DroplessMoE(num_experts=2, hidden=4, top_k=3).init(
            jax.random.PRNGKey(0), x)


def test_layer_traces_under_shard_map_with_vma_checks():
    """Plain data parallelism: tokens split over the axis, experts
    replicated; each shard's output is the layer on its own tokens."""
    layer, params, x = layer_and_params(False)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("ranks",))

    def body(p, x):
        out, balance, z = layer.apply({"params": p}, x)
        return out, jax.lax.pmean(balance, "ranks")

    out, balance = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P("ranks")),
        out_specs=(P("ranks"), P()), check_vma=True))(params, x)
    halves = [layer.apply({"params": params}, h)
              for h in (x[:N // 2], x[N // 2:])]
    np.testing.assert_allclose(
        out, jnp.concatenate([h[0] for h in halves]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        balance, (halves[0][1] + halves[1][1]) / 2, rtol=1e-6)


# ------------- a held share whose one window is every assignment


def held_layer(top_k: int, skip: bool, held=(1, 2)):
    """4 experts (and the choice that computes nothing), ``held`` of them
    here.  With 2 held the share is a third of the outputs or more and
    the one window is every assignment (``3 · top_k · 2 ≥ 4 + skip``);
    with 1 held and top-1 the windows are ``_window_plan``'s rows of the
    288, as many as the routing fills."""
    layer = DroplessMoE(num_experts=4, hidden=HID, top_k=top_k,
                        skip_choice=skip, held=held, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3 * N, D))
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    return layer, params, x


def window_form(layer, p, x):
    """The layer's output as the parent formed it when the window held
    every assignment: rows gathered by ``x[token]`` (autodiff scatter-adds
    the cotangent home), the weighted results scatter-added onto their
    tokens.  Same sort, masks, sizes and grouped products."""
    (first, held), k = layer.held, layer.top_k
    logits = jnp.dot(x, p["router"]["kernel"],
                     precision=jax.lax.Precision.HIGHEST)
    gate, expert = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    flat = expert.reshape(-1)
    local = jnp.where((flat >= first) & (flat < first + held), flat - first,
                      held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    token = order // k
    here = (jnp.arange(flat.size) < sizes.sum())[:, None]
    rows = jnp.where(here, x[token], 0)
    g = jnp.where(here[:, 0], gate.reshape(-1)[order], 0.0)
    # What the layer pads the hidden width to where ``lax.ragged_dot`` runs.
    w = {name: _pad_hidden(p[name], 1 if name == "w_down" else 2, 256)
         for name in ("w_gate", "w_up", "w_down")}
    h = jnp.where(here, jax.nn.silu(
        jax.lax.ragged_dot(rows, w["w_gate"], sizes))
        * jax.lax.ragged_dot(rows, w["w_up"], sizes), 0)
    y = jnp.where(here, jax.lax.ragged_dot(h, w["w_down"], sizes), 0)
    return jnp.zeros_like(x).at[token].add(y * g[:, None])


def weighed(out):
    return (out * jnp.cos(out)).sum()


@pytest.mark.parametrize("skip", [False, True], ids=["", "skip_choice"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_the_permuted_rows_are_the_window_s(top_k, skip):
    """Where the window is every assignment the rows go to expert order and
    come back as gathers through the sort's permutation.  Output, loss and
    every gradient leaf are the scatter-add form's: at top-1 BIT FOR BIT —
    a token's one product ``y · g`` lands on a zero and its cotangent is
    summed over one row —; at top-2 to the order of a two-term sum."""
    layer, params, x = held_layer(top_k, skip)

    def got_fn(p, x):
        out = layer.apply({"params": p}, x)[0]
        return weighed(out), out

    def want_fn(p, x):
        out = window_form(layer, p, x)
        return weighed(out), out

    (got_loss, got), got_g = jax.jit(jax.value_and_grad(
        got_fn, argnums=(0, 1), has_aux=True))(params, x)
    (want_loss, want), want_g = jax.jit(jax.value_and_grad(
        want_fn, argnums=(0, 1), has_aux=True))(params, x)
    assert float(jnp.abs(want).max()) > 0 and not bool(
        jnp.abs(want).sum(axis=1).all())      # some token's rows are elsewhere
    leaves = jax.tree_util.tree_leaves_with_path
    assert [path for path, _ in leaves(got_g)] == [
        path for path, _ in leaves(want_g)]
    for (path, g), w in zip(leaves(((got_loss, got), got_g)),
                            jax.tree.leaves(((want_loss, want), want_g))):
        assert float(jnp.abs(w).max()) > 0, path
        if top_k == 1:
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        else:
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6,
                                       err_msg=str(path))


def equations(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (the checkpointed
    block, its replay, branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def row_scatter_adds(jaxpr, width: int) -> int:
    """``scatter-add``s into a floating array of rows ``(·, width)``."""
    operands = [eqn.invars[0].aval for eqn in equations(jaxpr)
                if eqn.primitive.name == "scatter-add"]
    return sum(a.ndim == 2 and a.shape[1] == width
               and jnp.issubdtype(a.dtype, jnp.floating) for a in operands)


def tiling_held_layer():
    """A held layer whose windows tile — 2,048 tokens of width 128, top-2
    of 16 experts 128 wide, 2 of them held: windows of 512 sorted rows of
    the 4,096, bfloat16 — so the grouped matmuls' plan takes the
    (interpreted) kernels."""
    layer = DroplessMoE(num_experts=16, hidden=128, top_k=2, held=(0, 2))
    x = jax.random.normal(jax.random.PRNGKey(2), (2048, 128), jnp.bfloat16)
    return layer, layer.init(jax.random.PRNGKey(3), x)["params"], x


def ragged_dot_plan(*args, **kwargs):
    return GroupedPlan("ragged_dot", 0, 0, 0, 0, 256)


@pytest.mark.parametrize("case", ["every_assignment",
                                  "a_smaller_window_under_the_kernels",
                                  "a_smaller_window_under_ragged_dot"])
def test_only_a_smaller_window_scatter_adds_rows(case, monkeypatch):
    """Forward, replay and backward of a layer whose window is every
    assignment hold no scatter-add of rows (``bincount``'s integer one
    stays).  Nor do windows smaller than ``n · k`` where the grouped
    matmuls' plan takes the kernels: their rows land on their tokens, both
    ways, through a grouped transposed product (``moe_land``, twice), and
    every weight gradient is summed onto its carry by ``moe_tgmm`` handed
    it.  Under ``lax.ragged_dot`` such windows keep their scatter-adds: the
    combine's, and in the backward loop the one that lands the gathered
    rows' cotangent.  The layers' notes say which form ran."""
    if case == "every_assignment":
        layer, params, x = held_layer(1, True, (1, 2))
    else:
        layer, params, x = tiling_held_layer()
        if case.endswith("ragged_dot"):
            monkeypatch.setattr("horovod_tpu.parallel.moe.grouped_plan",
                                ragged_dot_plan)
    noted = {}

    def loss(p, x):
        return weighed(layer.apply({"params": p}, x)[0].astype(jnp.float32))

    jaxpr = jax.make_jaxpr(noting_layers(
        jax.value_and_grad(loss, argnums=(0, 1)), noted))(params, x)
    found = row_scatter_adds(jaxpr.jaxpr, x.shape[1])
    kernels = collections.Counter(
        eqn.params["name"] for eqn in equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")
    counters, = noted.values()
    n, k = x.shape[0], layer.top_k
    assert counters["moe.assignments"] == n * k
    if case == "every_assignment":
        assert found == 0 and not kernels
        assert counters["moe.permuted_assignments"] == n * k
        assert counters["moe.landed_by_product"] == 0
        return
    assert counters["moe.permuted_assignments"] == 0
    assert counters["moe.window_rows"] == 512
    if case.endswith("kernels"):
        assert found == 0
        # Gate, up and down forward and in the backward loop again, their
        # input gradients, their weight gradients handed their carries, and
        # the landing of ``out`` and of ``dx``.
        assert kernels == {"moe_gmm": 6, "moe_gmm_nt": 3, "moe_tgmm": 3,
                           "moe_land": 2}
        handed = [eqn.params["input_output_aliases"]
                  for eqn in equations(jaxpr.jaxpr)
                  if eqn.primitive.name == "pallas_call"
                  and eqn.params["name"] in ("moe_tgmm", "moe_land")]
        assert all(len(aliases) == 1 for aliases in handed), handed
        assert counters["moe.landed_by_product"] == 512
    else:
        assert found == 2 and not kernels
        assert counters["moe.landed_by_product"] == 0


def window_operands(landed: int, activation: str, products: bool):
    """``_held_windows``' operands at the tiling layer's shapes with
    ``landed`` of the 2,048 assignments on the 2 held experts, and what is
    static of it: windows of 512 rows under the interpreted kernels."""
    n, d, hid, held, k, W = 1024, 128, 128, 2, 2, 512
    ks = jax.random.split(jax.random.PRNGKey(landed), 6)
    flat = jnp.full((n * k,), held).at[
        jax.random.permutation(ks[0], n * k)[:landed]].set(
            jax.random.randint(ks[1], (landed,), 0, held))
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    x = jax.random.normal(ks[2], (n, d), jnp.bfloat16)
    gate = jax.random.uniform(ks[3], (n, k), jnp.float32)
    names = ("w_gate", "w_up", "w_down") if activation == "swiglu" else (
        "w_up", "w_down")
    w = {name: jax.random.normal(
        key, (held, hid, d) if name == "w_down" else (held, d, hid),
        jnp.float32) / 12 for name, key in zip(names, jax.random.split(ks[4], 3))}
    plan = grouped_plan(jax.ShapeDtypeStruct((W, d), jnp.bfloat16), held,
                        hid, interpret=True)
    assert plan.form == "kernels" and moe._lands_by_product(plan.form, n)
    static = moe._Held(k, W, activation, jnp.bfloat16, plan, True, products)
    ct = jax.random.normal(ks[5], (n, d), jnp.float32)
    return static, (x, gate, w, order, ends, group_sizes, ends[-1]), ct


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("landed", [0, 300, 512, 1300],
                         ids=["nothing_landed", "under_a_window",
                              "exactly_a_window", "three_windows"])
def test_the_products_give_what_the_scatter_adds_give(landed, activation):
    """``_held_windows`` both ways on the same operands, the same kernels
    under both: ``out`` (float32) and ``dgate`` to the order of a float32
    sum; ``dx`` to a bfloat16 step of its largest value (it leaves in the
    tokens' dtype); every ``dW`` to a bfloat16 step — the scatter form
    rounds a window's share to bfloat16 before its float32 pass, the
    product form never does."""
    results = []
    for products in (True, False):
        static, operands, ct = window_operands(landed, activation, products)
        out, pull = jax.vjp(
            lambda x, gate, w: moe._held_windows(static, x, gate, w,
                                                 *operands[3:]),
            *operands[:3])
        results.append((out, *pull(ct)))
    (out, dx, dgate, dw), (want_out, want_dx, want_dgate, want_dw) = results
    assert out.dtype == jnp.float32 and dx.dtype == jnp.bfloat16
    if landed == 0:
        for leaf in jax.tree.leaves(results):
            assert not np.asarray(leaf).any()
        return
    assert float(jnp.abs(want_out).max()) > 0

    def rel(got, want):
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        return np.abs(got - want).max() / np.abs(want).max()

    assert rel(out, want_out) < 2e-6
    assert rel(dgate, want_dgate) < 2e-6
    assert rel(dx, want_dx) <= 2.0 ** -8
    assert set(dw) == set(want_dw)
    for name in dw:
        assert dw[name].dtype == jnp.float32
        assert rel(dw[name], want_dw[name]) <= 2.0 ** -8, name


def test_which_windows_land_by_product_is_a_function_of_shapes():
    """``_lands_by_product``: the kernels' form and tokens in whole tiles
    of the landing; no option, environment variable or model's name."""
    from horovod_tpu.ops.grouped_matmul import LANDING_TOKENS

    assert list(inspect.signature(moe._lands_by_product).parameters) == [
        "form", "tokens"]
    assert moe._lands_by_product("kernels", 16_384)
    assert moe._lands_by_product("kernels", 8_192)
    assert moe._lands_by_product("kernels", LANDING_TOKENS)
    assert not moe._lands_by_product("kernels", 16_384 + 8)
    assert not moe._lands_by_product("ragged_dot", 16_384)
    assert "environ" not in inspect.getsource(moe)


# ------------------------------------------------------- the whole model


def tiny_cfg(compute_dtype="bfloat16"):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as fh:
        cfg = {**json.load(fh), **olmoe_lm.TINY}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute_dtype}
    return cfg


def model_inputs(cfg, n=4, seed=5):
    params, aux = jax.jit(lambda k: olmoe_lm.init(cfg, k))(
        jax.random.PRNGKey(seed))
    tokens = olmoe_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


# float32 compute: the routers agree exactly and every leaf of the
# gradient is the reference's to summation order (observed 2e-6).
# bfloat16 compute (8 mantissa bits) on 512 tokens of a 128-wide model:
# the loss to 5e-3 (observed 1.5e-3); the gradient leaves to 0.4, because
# a tiny router's k-th and (k+1)-th probabilities are close and a flipped
# assignment moves that token's whole contribution (observed: router
# 0.10-0.21, expert and qkv leaves 0.07-0.12, head 0.05-0.07).  At the
# published widths on the chip the tolerances are the configuration
# file's, far tighter; there the test is benchmark/run.py's.
@pytest.mark.parametrize("compute_dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 1e-4), ("bfloat16", 5e-3, 0.4)])
def test_model_against_reference_loss(compute_dtype, loss_tol, grad_tol,
                                      capsys):
    cfg = tiny_cfg(compute_dtype)
    params, aux, tokens = model_inputs(cfg)
    loss_fn, ref_fn = olmoe_lm.loss_fn(cfg), olmoe_lm.reference_loss(cfg)
    # Each side ONE program, not differentiated eagerly op by op (PR 56).
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0]))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens)))(params)
    assert abs(float(got) - float(want)) / float(want) <= loss_tol
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    named = [tuple(jax.tree_util.DictKey(k) for k in path)
             for path in olmoe_lm.grad_leaves(cfg)]
    assert set(named) <= set(flat_got)
    # Every leaf where the comparison is exact, the family's named ones
    # where it is statistical.
    errors = {jax.tree_util.keystr(path): float(
        jnp.linalg.norm(flat_got[path] - flat_want[path])
        / jnp.linalg.norm(flat_want[path]))
        for path in (flat_got if compute_dtype == "float32" else named)}
    assert max(errors.values()) <= grad_tol, errors
    # The reference prints the share of assignments the two routers
    # disagree on: none in float32.
    jax.effects_barrier()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"bench": "routing"')]
    assert lines and all(l["assignments"] == 2 * 4 * 128 * 2 for l in lines)
    if compute_dtype == "float32":
        assert all(l["disagreeing_share"] == 0.0 for l in lines)
    else:
        assert all(l["disagreeing_share"] < 0.05 for l in lines)


def test_two_device_step_is_the_mean_of_the_one_device_steps():
    """Data parallelism over two devices, experts replicated.  Each shard
    routes its own tokens and the load-balancing term is bilinear in a
    shard's statistics, so the two-device step equals the mean of the
    one-device steps on each half (SGD is linear in the gradient), not
    the one-device step on the whole batch."""
    cfg = tiny_cfg("float32")
    params, aux, tokens = model_inputs(cfg)
    tx = optax.sgd(0.1)
    devices = jax.devices()

    def step_on(devs, batch):
        mesh = Mesh(np.asarray(devs), ("ranks",))
        step = make_train_step(olmoe_lm.loss_fn(cfg), tx, mesh,
                               sync_aux_state=False, donate=False)
        new, _, _, loss = step(params, aux, tx.init(params), batch)
        return new, float(loss)

    both, loss = step_on(devices[:2], tokens)
    first, loss_a = step_on(devices[:1], tokens[:2])
    second, loss_b = step_on(devices[:1], tokens[2:])
    assert loss == pytest.approx((loss_a + loss_b) / 2, rel=1e-6)
    for got, a, b in zip(*(jax.tree.leaves(t)
                           for t in (both, first, second))):
        np.testing.assert_allclose(got, (a + b) / 2, rtol=1e-5, atol=1e-7)


def moe_counters():
    counters = registry.snapshot()["counters"]
    return {name: counters.get(name, 0)
            for name in ("moe.assignments", "moe.expert_bytes")}


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_step_counts_assignments_and_expert_bytes(steps_per_call):
    cfg = tiny_cfg()
    params, aux, tokens = model_inputs(cfg, n=2)
    tx = optax.sgd(0.1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("ranks",))
    step = make_train_step(olmoe_lm.loss_fn(cfg), tx, mesh,
                           sync_aux_state=False, donate=False,
                           steps_per_call=steps_per_call)
    batch = tokens if steps_per_call == 1 else np.stack([tokens] * 2)
    before = moe_counters()
    for _ in range(2):
        out = step(params, aux, tx.init(params), batch)
    jax.block_until_ready(out)
    after = moe_counters()
    layers, n, k = 2, 2 * 128, 2
    cost = olmoe_lm.moe_cost(cfg, 2)
    assert (after["moe.assignments"] - before["moe.assignments"]
            == 2 * steps_per_call * layers * n * k)
    assert cost["assignments"] == n * k
    # float32 parameters: 4 bytes each, every expert, every layer.
    assert (after["moe.expert_bytes"] - before["moe.expert_bytes"]
            == 2 * steps_per_call * cost["expert_parameters"] * 4)


def test_a_model_without_experts_counts_nothing():
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab=64, dim=32, depth=1, num_heads=2, max_len=16)
    tokens = jnp.zeros((2, 17), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    assert "pos_emb" in params and "bias" in params["block_0"]["ln1"]

    def loss_fn(p, aux, t):
        logits = model.apply({"params": p}, t[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, t[:, 1:]).mean(), aux

    tx = optax.sgd(0.1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("ranks",))
    before = moe_counters()
    step = make_train_step(loss_fn, tx, mesh, donate=False)
    jax.block_until_ready(step(params, {}, tx.init(params), tokens))
    assert moe_counters() == before


def test_rotary_is_a_rotation_by_relative_position():
    """Rotate-half form: norms are kept and q·k depends on the distance
    between the two positions only."""
    from horovod_tpu.models import apply_rotary

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 8))
    x = jnp.broadcast_to(x, (1, 12, 1, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 8))
    y = jnp.broadcast_to(y, (1, 12, 1, 8))
    pos = jnp.arange(12)
    rx, ry = apply_rotary(x, pos), apply_rotary(y, pos)
    np.testing.assert_allclose(jnp.linalg.norm(rx, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(rx[0, 0], x[0, 0], rtol=1e-6)
    dots = jnp.einsum("qd,kd->qk", rx[0, :, 0], ry[0, :, 0])
    np.testing.assert_allclose(dots[3, 1], dots[9, 7], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dots[5, 5], (x[0, 0, 0] * y[0, 0, 0]).sum(),
                               rtol=1e-4, atol=1e-5)
