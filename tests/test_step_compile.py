"""Whole steps compiled or lowered for a described TPU v5e (see
``tests/_v5e.py``): ``make_train_step`` on the four described devices, the
``nemo3super_1chip`` cell's step with every kernel family on its path, how
often a step traces each kernel, and the int8 codec and ring.  The options
the step hands the compiler are ``test_step_compile_options.py``'s.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from _v5e import NEMO3_WINDOW, compile_text, v5e  # noqa: F401

# The benchmark TransformerLM: d=2048, 16 heads of 128, T=2048.
T, H, D = 2048, 16, 128


# Equations in the two grouped backward kernels' jaxprs at the parent of
# PR 29 (T 2048, two heads a tile, whole blocks, a masked and an unmasked
# body each).
HEAD_KERNEL_EQUATIONS = {"_dkdv_kernel_grouped": 176,
                         "_dq_kernel_grouped": 149}


@pytest.mark.parametrize("family", ["flash", "scan", "passes", "experts",
                                    "held_windows", "selected",
                                    "selected_pair", "threshold",
                                    "grouped_kv", "latent"])
def test_each_kernel_is_traced_once_a_step_not_once_a_layer(monkeypatch,
                                                            family):
    """The set-up guard, no chip and no compile: tracing ``jax.grad`` of a
    stack at the cell's widths runs each kernel body once.

    ``flash``: a three-layer ``TransformerLM``; the layers' calls share the
    traces of ``_qkv_fwd`` / ``_qkv_bwd`` (three times each, the forward
    six, before PR 29: a kernel body's cost was paid 14 times a set-up on
    one chip).  And the bodies
    stay of a size: the pair's jaxprs hold at most three times the
    equations they held with whole blocks only — a whole-block body and
    seven products of the diagonal's triangle for a masked and an unmasked
    whole-block body; the form that emits one sub-tile body, a rolled
    loop, lost 9 ms a step on the chip (PERF.md, PR 29).

    ``scan``: the pattern stack's four mixers at Nemotron-H's widths (a
    sequence of 256); each mixer calls the forward kernel, its
    ``jax.checkpoint`` replays it, its backward rule calls the states pass
    and the sweep.  The four mixers share the traces of ``_fused_fwd`` /
    ``_fused_bwd`` (``ops/ssd.py``): the states pass and the sweep are
    traced once, the forward body twice — once as the forward that runs,
    once as the checkpoint's replay (a rule traced while the checkpoint's
    jaxpr is evaluated sees another trace context than the step's own, so
    the two do not share; 0.2 s, and the replay leaves no kernel).

    ``passes``: the same four mixers' convolution and gated norm
    (``ops/mixer_passes.py``).  The drivers ``_conv_fwd`` / ``_conv_bwd``
    / ``_gate_fwd`` / ``_gate_bwd`` are shared likewise: each backward
    body and the gate's forward are traced once, the convolution's forward
    twice (the checkpoint's replay again) — and there the replay stays a
    kernel, eight in all, because the scan's backward reads its output.

    ``experts``: four expert layers, each holding 8 of 16 relu² experts
    (512 tokens, top-2: a window of 1,024 sorted rows; widths 256 and 128,
    so ``grouped_matmul._plan`` takes the kernels).  Up and down are two
    shapes of each product, and the drivers ``_gmm`` / ``_tgmm`` are
    shared by the layers: the weight gradient's body is traced twice, the
    other's six times — up and down as the forward that runs, as the
    checkpoint's replay, and read transposed for the input gradients —
    where a trace a layer would be eight and twenty-four.
    ``held_windows``: two layers that each hold 2 of 16 (2,048 tokens,
    top-2: windows of 512 sorted rows, as many as the landed assignments
    fill).  The loop's body is traced once each way: up and down forward,
    and in the backward loop again, read transposed for the input
    gradients; the weight gradient's body four times — up and down handed
    their float32 carries, and as the landing of a window's rows on their
    tokens, the output's under its gate and ``dx``'s bare (PR 57: the
    same walk, the selection of the rows' tokens its left operand) — and
    every layer leaves those ten kernels (``moe_gmm`` 4, ``moe_gmm_nt``
    2, ``moe_tgmm`` 2, ``moe_land`` 2), whatever the windows a step runs.

    ``selected``: two sparse-attention layers of a ``KeyeLM`` (8 query
    heads over one KV head of 128, 32 of up to 256 keys a query).  The
    drivers ``_select_fwd_call`` / ``_select_bwd_call`` are shared by the
    layers: the forward's and the fused backward's body — eight unrolled
    heads each — is traced once, and every layer leaves its two kernels;
    ``selected_pair``: the same where the plan takes the dq / dk-dv pair
    (a budget of 0 for the resident gradients): three bodies, once each,
    three kernels a layer.  ``threshold``: the same two layers at a
    sequence of 512 and tiles of 128, where ``_threshold_plan`` takes the
    selection's kernel: ``index_threshold``'s driver is shared by the
    layers, so the kernel is traced once — its strip body once a band's
    width, four — and every layer leaves its one kernel.

    ``grouped_kv``: three attention layers of 4 query heads over 2 KV
    heads of 128 and no map (``zaya1_1chip`` has six such, PR 44).  The
    backward goes through ``_select_bwd_call`` with no map: the fused
    body — two unrolled heads, masked and unmasked — is traced once and
    every layer leaves its one kernel; the forward rule calls its driver
    bare, so its body is traced once a layer."""
    import collections
    import functools

    from horovod_tpu.models import (
        KeyeLM, NemotronHLM, TransformerLM, Zaya1LM)
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.ops import (
        cca_passes, grouped_matmul, mixer_passes, sparse_select, ssd)

    calls = collections.Counter()

    def counted(name, body):
        @functools.wraps(body)
        def call(*args, **kwargs):
            calls[name] += 1
            return body(*args, **kwargs)
        return call

    for name in ("_fwd_kernel", "_fwd_kernel_unrollkv",
                 "_fwd_kernel_fullunroll", "_dq_kernel", "_dkdv_kernel",
                 "_dq_kernel_grouped", "_dkdv_kernel_grouped",
                 "_select_fwd_kernel", "_select_dq_kernel",
                 "_select_dkdv_kernel", "_select_bwd_kernel"):
        monkeypatch.setattr(fa, name, counted(name, getattr(fa, name)))
    if family == "threshold":
        for name in ("_threshold_kernel", "_threshold_strip"):
            monkeypatch.setattr(sparse_select, name,
                                counted(name, getattr(sparse_select, name)))
    if family == "scan":
        for name in ("_fwd_kernel", "_states_kernel", "_bwd_kernel"):
            monkeypatch.setattr(ssd, name,
                                counted("ssd." + name, getattr(ssd, name)))
    if family == "passes":
        for name in ("_conv_fwd_kernel", "_conv_bwd_kernel",
                     "_gate_fwd_kernel", "_gate_bwd_kernel"):
            monkeypatch.setattr(mixer_passes, name,
                                counted(name, getattr(mixer_passes, name)))

    if family == "latent":
        for name in ("_fwd_kernel", "_bwd_kernel"):
            monkeypatch.setattr(cca_passes, name, counted(
                "cca." + name, getattr(cca_passes, name)))
    if family in ("experts", "held_windows"):
        for name in ("_gmm_kernel", "_tgmm_kernel"):
            monkeypatch.setattr(grouped_matmul, name,
                                counted(name, getattr(grouped_matmul, name)))

    # No other test's, nor another case's: a trace made earlier would be
    # shared.
    batch = {"flash": 3, "scan": 3, "passes": 5, "experts": 2,
             "held_windows": 2, "selected": 1, "selected_pair": 1, "threshold": 1,
             "grouped_kv": 1, "latent": 7}[family]
    if family.startswith("selected") or family == "threshold":
        if family == "selected_pair":
            monkeypatch.setattr(fa, "_FUSED_RESIDENT_BYTES", 0)
        seq, tile = (512, 128) if family == "threshold" else (256, 64)
        model = KeyeLM(vocab=512, dim=256, num_heads=8, kv_heads=1,
                       pattern="SS", max_len=seq, attn="flash",
                       dtype=jnp.bfloat16,
                       indexer=dict(num_heads=2, head_dim=64, topk=32,
                                    tile=tile))
        want = {"selected": {"_select_fwd_kernel": 1,
                             "_select_bwd_kernel": 1},
                "threshold": {"_select_fwd_kernel": 1,
                              "_select_bwd_kernel": 1,
                              "_threshold_kernel": 1,
                              "_threshold_strip": 4},
                "selected_pair": {"_select_fwd_kernel": 1,
                                  "_select_dq_kernel": 1,
                                  "_select_dkdv_kernel": 1}}[family]
    elif family == "grouped_kv":
        seq = 256
        model = NemotronHLM(vocab=512, dim=256, num_heads=4, kv_heads=2,
                            pattern="***", max_len=seq, attn="flash",
                            dtype=jnp.bfloat16)
        want = {"_fwd_kernel_fullunroll": 3, "_select_bwd_kernel": 1}
    elif family == "latent":
        seq = 128
        model = Zaya1LM(vocab=512, dim=256, num_heads=4, kv_heads=2,
                        head_dim=128, pattern="ZZZ", max_len=seq,
                        attn="flash", dtype=jnp.bfloat16, moe_experts=4,
                        moe_hidden=128,
                        moe=dict(router="mlp", router_hidden=16,
                                 skip_choice=True, activation="swiglu"))
        want = {"_fwd_kernel_fullunroll": 3, "_select_bwd_kernel": 1,
                "cca._fwd_kernel": 1, "cca._bwd_kernel": 1}
    elif family == "flash":
        seq = T
        model = TransformerLM(vocab=512, dim=H * D, depth=3, num_heads=H,
                              max_len=T, attn="flash", dtype=jnp.bfloat16)
        want = {"_fwd_kernel_fullunroll": 1, "_dq_kernel_grouped": 1,
                "_dkdv_kernel_grouped": 1}
    elif family == "experts":
        seq = 256
        model = NemotronHLM(
            vocab=512, dim=256, pattern="EEEE", max_len=seq,
            dtype=jnp.bfloat16, moe_experts=16, moe_top_k=2, moe_hidden=128,
            moe=dict(router="sigmoid", renormalize=True, activation="relu2",
                     held=(0, 8)))
        want = {"_gmm_kernel": 6, "_tgmm_kernel": 2}
    elif family == "held_windows":
        seq = 1024
        model = NemotronHLM(
            vocab=512, dim=256, pattern="EE", max_len=seq,
            dtype=jnp.bfloat16, moe_experts=16, moe_top_k=2, moe_hidden=128,
            moe=dict(router="sigmoid", renormalize=True, activation="relu2",
                     held=(0, 2)))
        # The weight gradient's body besides as the landing: the output's
        # (gated) and ``dx``'s.
        want = {"_gmm_kernel": 6, "_tgmm_kernel": 4}
    else:
        seq = 256
        model = NemotronHLM(vocab=512, dim=256, pattern="MMMM", max_len=seq,
                            dtype=jnp.bfloat16)
        want = {"scan": {"ssd._fwd_kernel": 2, "ssd._states_kernel": 1,
                         "ssd._bwd_kernel": 1},
                "passes": {"_conv_fwd_kernel": 2, "_conv_bwd_kernel": 1,
                           "_gate_fwd_kernel": 1, "_gate_bwd_kernel": 1}
                }[family]
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    if family.startswith("selected") or family in ("threshold",
                                                   "grouped_kv", "latent"):
        # ``init`` ran the forward with the step's own shapes, and the
        # forward rule would share that trace.
        jax.clear_caches()
    calls.clear()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, tokens: model.apply({"params": p}, tokens).astype(
            jnp.float32).sum()))(
        params, jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    assert dict(calls) == want

    def sub_jaxprs(eqn):
        for value in eqn.params.values():
            for v in value if isinstance(value, (list, tuple)) else [value]:
                v = getattr(v, "jaxpr", v)
                if hasattr(v, "eqns"):
                    yield v

    def equations(jaxpr):
        return sum(1 + sum(equations(j) for j in sub_jaxprs(e))
                   for e in jaxpr.eqns)

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield (eqn.params["jaxpr"].debug_info.func_name,
                       equations(eqn.params["jaxpr"]))
            else:
                for j in sub_jaxprs(eqn):
                    yield from kernels(j)

    found = list(kernels(jaxpr.jaxpr))
    sizes = dict(found)
    if family == "grouped_kv":
        assert collections.Counter(name for name, _ in found) == {
            "_fwd_kernel_fullunroll": 3, "flash_group_bwd": 3}
        return
    if family == "latent":
        names = collections.Counter(name for name, _ in found)
        assert (names["cca_mix_fwd"], names["cca_mix_bwd"]) == (3, 3), names
        return
    if family == "threshold":
        names = collections.Counter(name for name, _ in found)
        assert (names["index_threshold"], names["index_scores"]) == (2, 8)
        jax.clear_caches()
        return
    if family.startswith("selected"):
        names = collections.Counter(name for name, _ in found)
        assert {n: c for n, c in names.items() if "select" in n} == {
            "selected": {"flash_select_fwd": 2, "flash_select_bwd": 2},
            "selected_pair": {"flash_select_fwd": 2, "flash_select_dq": 2,
                              "flash_select_dkdv": 2}}[family]
        jax.clear_caches()      # the traces do not key on the budget
        return
    if family == "experts":
        # Four layers: up and down forward and replayed, their two input
        # gradients, their two weight gradients.
        assert collections.Counter(name for name, _ in found) == {
            "moe_gmm": 16, "moe_gmm_nt": 8, "moe_tgmm": 8}
        return
    if family == "held_windows":
        assert collections.Counter(name for name, _ in found) == {
            "moe_gmm": 8, "moe_gmm_nt": 4, "moe_tgmm": 4, "moe_land": 4}
        return
    if family != "flash":
        # Four mixers: the scan's forward, and in the backward its states
        # pass and its sweep, the replayed forwards gone with their ``y``;
        # the convolution twice forward (the replay feeds the scan's
        # backward) and once backward, the gate once each way.
        names = collections.Counter(name for name, _ in found)
        assert names == {"ssd_fwd": 4, "ssd_states": 4, "ssd_bwd": 4,
                         "ssm_conv_fwd": 8, "ssm_conv_bwd": 4,
                         "ssm_gate_fwd": 4, "ssm_gate_bwd": 4}
        return
    assert set(HEAD_KERNEL_EQUATIONS) < set(sizes)
    for name, at_head in HEAD_KERNEL_EQUATIONS.items():
        assert sizes[name] <= 3 * at_head, (name, sizes[name], at_head)


def test_int8_codec_1mi(v5e, monkeypatch):
    """quantize + dequantize at 1 Mi elements (1024 blocks of 1024)."""
    from horovod_tpu.ops import quantized_collectives as qc

    monkeypatch.setattr(qc, "_interpret", lambda: False)
    one = SingleDeviceSharding(v5e[0])
    flat = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one)

    def roundtrip(x):
        return qc.dequantize_blocks(*qc.quantize_blocks(x))

    assert compile_text(roundtrip, flat).count("tpu_custom_call") == 2


@pytest.mark.parametrize("size", [1 << 20, 33 * 31 + 5])
def test_quantized_ring_allreduce_four_devices(v5e, monkeypatch, size):
    """The in-jit int8 ring under ``shard_map(check_vma=True)`` on a mesh
    of the four described devices — at a block-aligned size and at one
    that pads (a gradient leaf's size is what it is): codec kernels
    compiled, hops as collective-permutes."""
    from horovod_tpu.ops import quantized_collectives as qc

    monkeypatch.setattr(qc, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(v5e), ("ranks",))
    x = jax.ShapeDtypeStruct((4, size), jnp.float32,
                             sharding=NamedSharding(mesh, P("ranks")))

    def ring(x):
        return qc.quantized_ring_allreduce(x[0], "ranks", average=True)

    text = compile_text(jax.shard_map(ring, mesh=mesh, in_specs=P("ranks"),
                                      out_specs=P(), check_vma=True), x)
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_train_step_all_reduces_fused_with_backward(v5e, monkeypatch):
    """``make_train_step`` on the four described devices, a two-layer LM
    (flash kernels compiled): under the options it hands the program, at
    least half of the all-reduced bytes sit inside async collective
    fusions and no Pallas kernel is lost or repeated; with none, every
    all-reduce is synchronous."""
    import optax

    from horovod_tpu.jax import spmd
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.ops.losses import fused_softmax_xent

    vocab, dim, depth, heads, seq, batch = 8192, 1024, 2, 8, 1024, 8
    mesh = Mesh(np.asarray(v5e), ("ranks",))
    model = TransformerLM(vocab=vocab, dim=dim, depth=depth, num_heads=heads,
                          max_len=seq, attn="flash", dtype=jnp.bfloat16)

    def loss_fn(params, aux, tokens):
        h = model.apply({"params": params}, tokens[:, :-1],
                        return_hidden=True)
        return fused_softmax_xent(h.reshape(-1, dim),
                                  params["head"]["kernel"],
                                  tokens[:, 1:].reshape(-1)).mean(), aux

    tx = optax.adamw(1e-4)
    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, seq), jnp.int32))[
            "params"], jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(tx.init, params)

    def shaped(tree, spec):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

    args = (shaped(params, P()), {}, shaped(opt_state, P()),
            shaped(jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32),
                   P("ranks")))
    options = spmd._step_compiler_options(mesh, params)
    assert options, "a four-device mesh of TPU devices gets no options"
    # The kernels ask jax.default_backend() whether to lower interpreted.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def compiled_text():
        step = spmd.make_train_step(loss_fn, tx, mesh, sync_aux_state=False)
        return step.lower(*args).compile().as_text()

    fused = compiled_text()
    monkeypatch.setattr(spmd, "_step_compiler_options",
                        lambda mesh, params: {})
    plain = compiled_text()
    kernels = 'custom_call_target="tpu_custom_call"'
    assert plain.count(kernels) >= 3 * depth
    assert fused.count(kernels) == plain.count(kernels)
    assert spmd.fused_all_reduce_share(plain) == 0.0
    assert spmd.fused_all_reduce_share(fused) >= 0.5


# ------------------------------------- the Nemotron-3-Super cell's parts
# (the nemo3super_1chip cell: 1 sequence of 8,192 (+2), one chip's share)


def test_the_nemo3super_cell_s_step_lowers_with_every_kernel_family(
        v5e, monkeypatch):
    """The cell's whole step, built as ``benchmark/run.py`` builds it (the
    family's ``loss_fn`` and optimizer through ``make_train_step``) from
    shapes alone, lowers for the described chip with every kernel family
    on its path: the scan's three at 16 heads in ONE group, the mixer's
    two passes at an input projection padded from 2,320 to 2,432 columns,
    the grouped-KV flash forward and its one-kernel backward at 4 query
    heads over 1 KV head, and the grouped matmuls at the latent's window.
    A lowering, not a compile (``benchmark/compile_check.py`` compiles it:
    13.645 GiB planned, PR 46)."""
    import importlib
    import json

    from horovod_tpu.jax.spmd import make_train_step
    from horovod_tpu.parallel.mesh import RANKS_AXIS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as fh:
        cfg = json.load(fh)
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    mesh = Mesh(np.asarray(v5e[:1]), (RANKS_AXIS,))
    replicated = NamedSharding(mesh, P())

    def shaped(tree, sharding=replicated):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    tx = family.optimizer(cfg)
    params, aux = jax.eval_shape(lambda k: family.init(cfg, k),
                                 jax.random.PRNGKey(0))
    assert sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(params)) == 838_246_896
    batch = family.host_batch(cfg, np.random.default_rng(0), 1)
    assert batch.shape == (1, 8194)
    step = make_train_step(family.loss_fn(cfg), tx, mesh,
                           sync_aux_state=family.SYNC_AUX_STATE)
    lowered = step.lower(shaped(params), shaped(aux),
                         shaped(jax.eval_shape(tx.init, params)),
                         shaped(batch, NamedSharding(mesh, P(RANKS_AXIS))))
    text = lowered.as_text()
    import re
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == {
        "flash_resident_fwd", "flash_group_bwd", "moe_gmm", "moe_gmm_nt",
        "moe_land", "moe_tgmm", "ssd_bwd", "ssd_fwd", "ssd_states",
        "ssm_conv_bwd", "ssm_conv_fwd", "ssm_gate_bwd", "ssm_gate_fwd"}
    assert "stablehlo.all_reduce" not in text
    assert "8192x2432xbf16" in text            # the padded input projection
    assert f"{NEMO3_WINDOW}x1024xbf16" in text  # a window, in the latent
