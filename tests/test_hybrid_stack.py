"""The hybrid (pattern) stack whole: the nine-layer tiny preset of the
``nemotron_h_lm`` family against its ``reference_loss`` and through
``make_train_step``, with its trace scopes, counters and the options that do
not compose.  Its layers' own tests are ``test_hybrid_scan.py`` (the mixer),
``test_hybrid_experts.py`` (grouped-KV attention, held experts) and
``test_hybrid_linear.py`` (linear attention, two sub-layers): one file until
PR 50, four since, because a file is one worker's job under ``--dist
loadfile``.
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
from horovod_tpu.parallel.moe import DroplessMoE, _SharedExpert
from horovod_tpu.parallel.ring_attention import full_attention

from test_gated_delta import _equations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


from test_hybrid_scan import family_cfg, kernel_inputs


# ------------------------------------------------------- the whole model


def model_inputs(cfg, n=2, seed=5):
    params, aux = jax.jit(lambda k: nemotron_h_lm.init(cfg, k))(
        jax.random.PRNGKey(seed))
    tokens = nemotron_h_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


# float32 compute: the routers agree exactly and every leaf of the
# gradient is the reference's to summation order.  bfloat16 compute on 128
# tokens of a 64-wide model: the tiny preset's own, looser tolerances
# (the reference breaks near-ties as the program did, so the routers'
# differing choices no longer set the floor: 0.1-0.4 a leaf without).
@pytest.mark.parametrize("compute_dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 2e-4), ("bfloat16", 5e-3, 0.2)])
def test_model_against_reference_loss(compute_dtype, loss_tol, grad_tol,
                                      capsys):
    cfg = family_cfg(compute_dtype)
    assert nemotron_h_lm.pattern(cfg) == "MEMEM*EME"
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    ref_fn = nemotron_h_lm.reference_loss(cfg)
    # Each side ONE program: differentiated eagerly, op by op, the two
    # cost 130 s and 79 s a case (PR 56).
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0]))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens)))(params)
    assert abs(float(got) - float(want)) / float(want) <= loss_tol
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    named = [tuple(jax.tree_util.DictKey(k) for k in path)
             for path in nemotron_h_lm.grad_leaves(cfg)]
    assert set(named) <= set(flat_got)
    errors = {jax.tree_util.keystr(path): rel(flat_got[path],
                                              flat_want[path])
              for path in (flat_got if compute_dtype == "float32"
                           else named)}
    assert max(errors.values()) <= grad_tol, errors
    jax.effects_barrier()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"bench": "routing"')]
    assert lines and all(l["assignments"] == 4 * 2 * 64 * 3 for l in lines)
    assert all(l["beyond_margin_share"] == 0.0 for l in lines)
    if compute_dtype == "float32":
        assert all(l["disagreeing_share"] == 0.0 for l in lines)
    else:
        assert all(0 < l["largest_gap"] <= cfg["tolerances"]["tie_margin"]
                   for l in lines)


def test_reference_takes_the_program_s_choice_only_where_it_is_a_tie():
    """The reference given OTHER choices than its own: inside the margin it
    follows them (another loss than its own choice gives), beyond the
    margin, or where they are not k distinct experts, it keeps its own."""
    cfg = family_cfg("float32")
    params, aux, tokens = model_inputs(cfg)
    given = jax.jit(nemotron_h_lm.reference_given_choices(cfg))
    own = nemotron_h_lm.program_expert_choices(cfg, params, tokens)
    want = float(given(params, tokens, own, 0.0))
    # The sixth-and-lower choice of every token pushed one expert on.
    E = cfg["experts_routed_over"]
    other = own.at[..., -1].set((own[..., -1] + 1) % E)
    assert float(given(params, tokens, other, 0.0)) == want
    assert float(given(params, tokens, other, 1.0)) != want
    # One expert chosen twice is k - 1 experts: no tie at any margin.
    fewer = own.at[..., -1].set(own[..., 0])
    assert float(given(params, tokens, fewer, 1.0)) == want
    # The comparison's precision control: the same mathematics in bfloat16
    # is another number (on the chip, at T 8192, not even a finite one).
    low = nemotron_h_lm.reference_given_choices(cfg, dtype="bfloat16")
    assert abs(float(low(params, tokens, own, 0.0)) - want) > 1e-4 * want


def test_the_float32_parts_are_float32_in_the_traced_program():
    """What the comparison with the reference cannot see (on the chip a
    chunk state carried in bfloat16 and a router at the default matmul
    precision read each seed's own floor: they perturb less than the
    recipe's bfloat16 arithmetic does) is held here, in the program's
    jaxpr: under bfloat16 compute the state passed from chunk to chunk is
    float32, and the router's matmul takes float32 operands at HIGHEST."""
    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    eqns = list(_equations(jax.make_jaxpr(
        lambda p: loss_fn(p, aux, tokens)[0])(params).jaxpr))
    H, P_, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["ssm_state_size"])
    carried = [v.aval for e in eqns if e.primitive.name == "scan"
               for v in e.outvars[:e.params["num_carry"]]
               if v.aval.shape[-2:] == (P_, N) and v.aval.size % (H * P_ * N)
               == 0]
    assert len(carried) == 4 and all(a.dtype == jnp.float32 for a in carried)
    routers = [e for e in eqns if e.primitive.name == "dot_general"
               and e.outvars[0].aval.shape[-1] == cfg["experts_routed_over"]
               and e.invars[1].aval.shape == (cfg["hidden_size"],
                                              cfg["experts_routed_over"])]
    assert len(routers) == 4
    for e in routers:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,
                                         jax.lax.Precision.HIGHEST)


def test_the_float32_parts_are_float32_in_the_kernels_too():
    """The same parts where the scan runs as kernels (bfloat16 operands at
    a tiling shape): the state scratch of the forward and of the states
    pass, the transient of entering states between the backward's two
    kernels and the carried gradient of the state are float32; the
    running-sum product (and its transpose in the sweep) takes float32
    operands at HIGHEST; every other product takes its operands in
    ``x.dtype`` — not narrower — and accumulates in float32."""
    x, dt, A, B, C, D = kernel_inputs("three_chunks_batch_2_G_lt_H",
                                      "bfloat16")
    b, T, H, P_ = x.shape
    G, N_ = B.shape[2:]
    RP = H // G * P_
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: ssd_scan(*a, chunk=128, interpret=True).astype(
            jnp.float32).sum(), argnums=tuple(range(6))))(x, dt, A, B, C, D)
    calls = {e.params["jaxpr"].debug_info.func_name: e
             for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"ssd_fwd", "ssd_states", "ssd_bwd"}
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    for name, call in calls.items():
        kernel = call.params["jaxpr"]
        carried = kernel.invars[-1].aval         # the one scratch buffer
        assert (carried.shape, carried.dtype) == ((N_, RP), jnp.float32)
        dots = [e for e in _equations(kernel)
                if e.primitive.name == "dot_general"]
        sums = [e for e in dots if e.params["precision"] in (
            highest, jax.lax.Precision.HIGHEST)]
        assert len(sums) == {"ssd_fwd": 1, "ssd_states": 1,
                             "ssd_bwd": 2}[name]
        for e in sums:
            assert all(v.aval.dtype == jnp.float32 for v in e.invars)
            assert 128 in e.invars[1].aval.shape     # the triangle
        products = [e for e in dots if e not in sums]
        assert len(products) >= {"ssd_fwd": 4, "ssd_states": 1,
                                 "ssd_bwd": 12}[name]
        for e in products:
            assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars)
            assert e.params["preferred_element_type"] == jnp.float32
    entering = calls["ssd_states"].outvars[0].aval
    assert (entering.shape, entering.dtype) == ((b, G, 3, N_, RP),
                                                jnp.float32)
    assert any(v.aval.shape == entering.shape and v.aval.dtype
               == jnp.float32 for v in calls["ssd_bwd"].invars)


def test_tiny_stack_trains_through_make_train_step(hvd):
    """The nine-layer preset through the normal path on the 8-device mesh:
    the first step's loss is the reference's on the global batch, the loss
    falls, the state stays float32, and each dispatch bumps the mixers'
    and the expert layers' counters from the shapes they noted."""
    cfg = family_cfg("bfloat16")
    params, aux, _ = model_inputs(cfg)
    tokens = nemotron_h_lm.host_batch(cfg, np.random.default_rng(7), 8)
    tx = nemotron_h_lm.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(jax.jit(nemotron_h_lm.reference_loss(cfg))(
        params, aux, tokens))
    step = make_train_step(nemotron_h_lm.loss_fn(cfg), tx, hvd.ranks_mesh())
    names = ("ssm.scan_chunks", "ssm.state_bytes", "ssm.fused_scans",
             "moe.assignments", "moe.held_assignments", "moe.expert_bytes")
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
    # A shard's step, four dispatches: one sequence of 64 tokens through 4
    # mixers (4 chunks of 16; 4 heads x 16 x 16 float32 a state) and 4
    # expert layers (3 of 8 experts a token, 4 held, 2 matrices of 64x32).
    # The preset's scans do not tile (chunks of 16): the XLA form, no
    # fused scan.
    assert got == {"ssm.scan_chunks": 4 * 4 * 4,
                   "ssm.state_bytes": 4 * 4 * 4 * 4 * 16 * 16 * 4,
                   "ssm.fused_scans": 0,
                   "moe.assignments": 4 * 4 * 64 * 3,
                   "moe.held_assignments": 4 * 4 * 64 * 3 // 2,
                   "moe.expert_bytes": 4 * 4 * 2 * 4 * 64 * 32 * 4}


def test_one_mixer_at_a_tiling_shape_counts_a_fused_scan(hvd):
    """A one-mixer stack whose scan tiles (2 heads of 64 in one group,
    state 128, one chunk of 128) through ``make_train_step`` on one device
    — the plain program, so no manual mesh axis stands the interpreted
    kernels down: it trains, each dispatch counts one fused scan, and the
    lowered step names the three kernels under ``ssm/scan``, where the
    cell's reader looks and nowhere else."""
    import re

    import optax

    from benchmark.metrics import ssm_ms
    from horovod_tpu.parallel.mesh import RANKS_AXIS

    model = NemotronHLM(vocab=64, dim=32, pattern="M", max_len=128,
                        dtype=jnp.float32,
                        ssm=dict(num_heads=2, head_dim=64, n_groups=1,
                                 state_size=128, chunk=128))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]

    def loss_fn(p, aux, tokens):
        logits = model.apply({"params": p}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), aux

    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, tokens)[0])).lower(
        params).as_text(debug_info=True)
    stacks = set(re.findall(r'"([^"]*/ssd_(?:fwd|states|bwd))[/"]', text))
    assert {s.rsplit("/", 1)[1] for s in stacks} == {
        "ssd_fwd", "ssd_states", "ssd_bwd"}
    assert all(ssm_ms.in_scan(s) for s in stacks), stacks
    for part in ("intra", "states", "pass", "inter"):
        assert f"ssm/scan/{part}" not in text

    tx = optax.sgd(0.5)
    step = make_train_step(loss_fn, tx, Mesh(np.asarray(jax.devices()[:1]),
                                             (RANKS_AXIS,)))
    names = ("ssm.fused_scans", "ssm.scan_chunks", "ssm.state_bytes")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    aux, opt_state, losses = {}, tx.init(params), []
    for _ in range(3):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    after = registry.snapshot()["counters"]
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "ssm.fused_scans": 3, "ssm.scan_chunks": 3,
        "ssm.state_bytes": 3 * 2 * 64 * 128 * 4}


def test_trace_scopes_name_the_mixer_s_parts_and_the_shared_expert():
    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, aux, tokens)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/scan/intra",
                  "ssm/scan/states", "ssm/scan/pass", "ssm/scan/inter",
                  "ssm/gate_norm", "ssm/out_proj", "moe/route",
                  "moe/shared", "layer_5/attn", "layer_8/moe"):
        assert scope in text, scope
    # The cell's own readers find them under those names.
    from benchmark.metrics import moe_ms, ssm_ms
    assert ssm_ms.in_scan("jvp(TransformerLM)/layer_*/ssm/scan/intra/mul")
    assert ssm_ms.in_mixer("params['layer_*']['ssm']['in_proj']['kernel']")
    assert not ssm_ms.in_scan("jvp(TransformerLM)/layer_*/ssm/conv/mul")
    assert moe_ms.in_expert_layer(
        "transpose(jvp(TransformerLM))/layer_*/moe/shared/dot_general")


def test_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    tiny = dict(vocab=64, dim=32, num_heads=2, kv_heads=1, head_dim=16,
                ssm=dict(num_heads=2, head_dim=8, n_groups=1, state_size=8,
                         chunk=8),
                moe_experts=4, moe_top_k=2, moe_hidden=16, attn="full")
    model = NemotronHLM(**tiny, pattern="M*E")
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params) == {"tok_emb", "layer_0", "layer_1", "layer_2",
                           "ln_f", "head"}
    assert set(params["layer_1"]["attn"]) == {"q", "kv", "proj"}
    with pytest.raises(ValueError, match="pattern stack"):
        NemotronHLM(**tiny, pattern="M", tp_axis="tp").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="held share"):
        TransformerLM(vocab=64, dim=32, num_heads=2, tp_axis="tp",
                      moe={"held": (0, 2)}).init(jax.random.PRNGKey(0),
                                                 tokens)
    with pytest.raises(ValueError, match="pos='none'"):
        NemotronHLM(**{**tiny, "pos": "rotary"}, pattern="M").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, pos="none").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="unknown layer"):
        NemotronHLM(**tiny, pattern="MX").init(jax.random.PRNGKey(0), tokens)
