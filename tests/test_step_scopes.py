"""The step's trace scopes (``spmd.STEP_SCOPES``): where ``make_train_step``
puts ``grad_reduce``, ``optimizer`` and ``aux_sync``, that they are
metadata and nothing else, and that the model's own names stand as they
stood.  Read from the lowered step's text on the CPU, on the 8-device
mesh (the ``shard_map`` program) and on a 1-device mesh (the plain one)."""

import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax import spmd
from horovod_tpu.parallel.mesh import RANKS_AXIS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def loss_fn(params, aux, batch):
    x, y = batch
    loss = jnp.mean((x @ params["w"] + params["b"] - y) ** 2)
    return loss, {"seen": aux["seen"] + x.shape[0], "mean_y": jnp.mean(y)}


def lowered(n_devices, tx=None, **options):
    mesh = Mesh(np.asarray(jax.devices()[:n_devices]), (RANKS_AXIS,))
    tx = tx or optax.adamw(1e-3)
    params = {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}
    aux = {"seen": jnp.zeros((), jnp.int32), "mean_y": jnp.zeros(())}
    lead = (32,)
    if options.get("steps_per_call", 1) > 1:
        lead = (options["steps_per_call"],) + lead
    batch = (jnp.zeros(lead + (8,)), jnp.zeros(lead + (1,)))
    step = spmd.make_train_step(loss_fn, tx, mesh, donate=False, **options)
    return step.lower(params, aux, tx.init(params), batch)


def name_stacks(text):
    """The name stacks of a lowered text with its debug info."""
    return set(re.findall(r'loc\("([^"]+)"', text))


def scoped(stacks, scope):
    """Those that stand under ``scope``: a token that is not the last."""
    return {s for s in stacks if scope in s.split("/")[:-1]}


def test_the_names_are_held_once_and_no_other_is_a_scope():
    assert spmd.STEP_SCOPES == ("grad_reduce", "optimizer", "aux_sync")
    with pytest.raises(ValueError, match="none of"):
        spmd.step_scope("reduce")


def test_shard_map_step_names_all_three():
    stacks = name_stacks(lowered(8).as_text(debug_info=True))
    reduce_ops = {s.rsplit("/", 1)[1] for s in scoped(stacks, "grad_reduce")}
    # The collective and what stands around it: the division by the mesh.
    assert {"psum_invariant", "div"} <= reduce_ops, reduce_ops
    update_ops = {s.rsplit("/", 1)[1] for s in scoped(stacks, "optimizer")}
    assert {"add", "mul", "sqrt"} <= update_ops, update_ops
    # The aux state's float leaf is averaged, its counter takes the
    # maximum, and the loss's pmean stands beside them.
    sync_ops = {s.rsplit("/", 1)[1] for s in scoped(stacks, "aux_sync")}
    assert {"psum_invariant", "pmax"} <= sync_ops, sync_ops
    # The forward and backward pass stand under none of them.
    for s in stacks:
        if "jvp(" in s:
            assert not set(s.split("/")) & set(spmd.STEP_SCOPES), s


def test_plain_step_names_the_optimizer_alone():
    stacks = name_stacks(lowered(1).as_text(debug_info=True))
    assert scoped(stacks, "optimizer")
    assert not scoped(stacks, "grad_reduce")
    assert not scoped(stacks, "aux_sync")


@pytest.mark.parametrize("n_devices", [8, 1])
def test_the_default_text_holds_no_scope_and_is_the_text_without_them(
        n_devices, monkeypatch):
    with_scopes = lowered(n_devices).as_text()
    for word in spmd.STEP_SCOPES:
        assert word not in with_scopes, word
    monkeypatch.setattr(spmd, "step_scope",
                        lambda name: contextlib.nullcontext())
    assert not scoped(name_stacks(
        lowered(n_devices).as_text(debug_info=True)), "optimizer")
    assert lowered(n_devices).as_text() == with_scopes


@pytest.mark.parametrize("n_devices", [8, 1])
def test_the_compiled_program_differs_by_metadata_alone(n_devices,
                                                        monkeypatch):
    def stripped(low):
        # Without each instruction's ``metadata={op_name=… stack_frame_id=…}``
        # and the module's tables of files, functions, locations and
        # frames that ``stack_frame_id`` points into (a ``with`` more on
        # the Python stack moves those).
        text = low.compile().as_text()
        assert "metadata={" in text and "\nStackFrames\n" in text
        text = re.sub(r",? ?metadata=\{[^{}]*\}", "", text)
        return re.sub(r"\n(FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(?:\d+ [^\n]*\n)*", "\n", text)

    with_scopes = stripped(lowered(n_devices))
    monkeypatch.setattr(spmd, "step_scope",
                        lambda name: contextlib.nullcontext())
    assert stripped(lowered(n_devices)) == with_scopes


@pytest.mark.parametrize("n_devices", [8, 1])
def test_two_steps_a_call_keep_the_scopes_inside_the_scan(n_devices):
    text = lowered(n_devices, steps_per_call=2).as_text(debug_info=True)
    assert "stablehlo.while" in text
    stacks = name_stacks(text)
    assert scoped(stacks, "optimizer")
    if n_devices > 1:
        assert scoped(stacks, "grad_reduce") and scoped(stacks, "aux_sync")
    # Inside the loop's body and not around the loop: no scope names it.
    for s in stacks:
        tokens = s.split("/")
        if "while" in tokens:
            before = tokens[:tokens.index("while")]
            assert not set(before) & set(spmd.STEP_SCOPES), s


@pytest.mark.parametrize("n_devices", [8, 1])
def test_an_optimizer_that_reduces_for_itself_reads_as_reduction(n_devices):
    tx = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    stacks = name_stacks(lowered(n_devices, tx=tx).as_text(debug_info=True))
    inner = {s for s in stacks if "optimizer/grad_reduce/" in s}
    assert inner, sorted(scoped(stacks, "optimizer"))
    # sgd's own scaling stays the optimizer's.
    assert scoped(stacks, "optimizer") - inner


def test_reduce_gradients_carries_its_scope_for_every_caller():
    mesh = Mesh(np.asarray(jax.devices()), (RANKS_AXIS,))
    from jax.sharding import PartitionSpec as P

    def body(g):
        return spmd.reduce_gradients({"g": g}, (RANKS_AXIS,))["g"]

    text = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(RANKS_AXIS),
                                 out_specs=P())).lower(
        jnp.zeros((8, 4))).as_text(debug_info=True)
    stacks = name_stacks(text)
    reduce_ops = {s.rsplit("/", 1)[1] for s in scoped(stacks, "grad_reduce")}
    assert {"psum_invariant", "div"} <= reduce_ops, stacks
    assert spmd.reduce_gradients.__name__ == "reduce_gradients"
    assert "Cross-rank gradient reduction" in spmd.reduce_gradients.__doc__


def test_the_model_s_own_labels_read_as_before():
    """The tiny preset of ``twotower_1chip``'s family, cut to one mixer
    and one expert layer, through ``make_train_step``: the loss head's,
    the experts' and the mixers' scopes stand where the benchmark's
    readers look, with nothing of the step's put before them."""
    from benchmark.families import nemotron_h_lm
    from benchmark.metrics import moe_ms, ssm_ms

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-twotower-30b-a3b.json")) as fh:
        cfg = {**json.load(fh), **nemotron_h_lm.TINY, "num_hidden_layers": 2}
    assert nemotron_h_lm.pattern(cfg) == "ME"
    # Shapes alone: the step is lowered, never run.
    params, aux = jax.eval_shape(
        lambda: nemotron_h_lm.init(cfg, jax.random.PRNGKey(5)))
    tokens = nemotron_h_lm.host_batch(cfg, np.random.default_rng(5), 2)
    tx = nemotron_h_lm.optimizer(cfg)
    step = spmd.make_train_step(
        nemotron_h_lm.loss_fn(cfg), tx,
        Mesh(np.asarray(jax.devices()[:1]), (RANKS_AXIS,)),
        sync_aux_state=nemotron_h_lm.SYNC_AUX_STATE, donate=False)
    stacks = name_stacks(step.lower(
        params, aux, jax.eval_shape(tx.init, params),
        tokens).as_text(debug_info=True))
    model = {s for s in stacks if "jvp(" in s}
    for s in model:
        assert not set(s.split("/")) & set(spmd.STEP_SCOPES), s
    heads = {s.split("/")[0] for s in model}
    assert heads <= {"jit(plain_one)"}, heads
    after_jit = {s.split("/", 1)[1] for s in model}
    assert any(s.startswith("transpose(jvp(xent/grad))/") for s in after_jit)
    assert any(s.startswith("jvp(xent/loss)/") for s in after_jit)
    assert any(s.startswith("jvp(") and re.search(
        r"/layer_\d+/moe/route/", s) for s in after_jit)
    assert any(re.search(r"/layer_\d+/ssm/scan/", s) for s in after_jit)
    assert any(moe_ms.in_expert_layer(s) for s in after_jit)
    assert any(ssm_ms.in_scan(s) for s in after_jit)
    # And the update of those layers' weights has its own name now.
    assert scoped(stacks, "optimizer")
    assert not any(moe_ms.in_expert_layer(s) or ssm_ms.in_mixer(s)
                   for s in scoped(stacks, "optimizer"))
