"""The three readers of ``make_train_step``'s scopes (``optimizer_ms``,
``grad_reduce_ms``, ``unowned_pct``) on hand-made ``op_self_s`` tables
with ``tracered.label``'s labels, and ``unowned_pct`` on the cut of a chip
trace: which op has an owner, which scope an op counts under, and that a
program without the scopes reads nothing where one with them reads 0."""

import os

import pytest

from benchmark import tracered
from benchmark.metrics import grad_reduce_ms, optimizer_ms, unowned_pct
from horovod_tpu.jax import spmd

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "data", "gpt13b_1chip_v5e.trace.json.gz")
ONE_CHIP, FOUR_CHIPS = {"chips": 1}, {"chips": 4}


def trace_of(op_self_s, steps=2):
    return {"devices": [{"op_self_s": op_self_s, "steps": steps}]}


@pytest.mark.parametrize("label,own", [
    # No name stack: tracered.label gives the op's name without its number.
    ("fusion", []),
    ("copy-done", []),
    # A bare primitive, alone or behind the wrappers.
    ("add [loop fusion]", []),
    ("shard_map/add [loop fusion]", []),
    ("jit(inner)/mul [loop fusion]", []),
    # One token that is no primitive: the compiler's name for what the op
    # serves (a parameter's cast, a jitted function's transform).
    ("params['head']['kernel'] [data formatting]",
     ["params['head']['kernel']"]),
    ("jvp(jit(take_along_axis)) [iota]", ["jvp(jit(take_along_axis))"]),
    # Owned: by a module, a transform of one, a scope.
    ("jvp(TransformerLM)/block_*/fc1/dot_general [convolution fusion]",
     ["jvp(TransformerLM)", "block_*", "fc1"]),
    ("transpose(jvp())/dot_general [convolution fusion]",
     ["transpose(jvp())"]),
    ("optimizer/add [loop fusion]", ["optimizer"]),
    ("shard_map/grad_reduce/psum [all-reduce]", ["grad_reduce"]),
    ("shard_map/while/body/optimizer/jit(_where)/select_n [loop fusion]",
     ["while", "body", "optimizer", "jit(_where)"]),
    # A scope's word as the LAST token is the primitive, not a scope.
    ("optimizer [loop fusion]", []),
    ("jvp(Model)/grad_reduce [loop fusion]", ["jvp(Model)"]),
])
def test_owners_of_a_label(label, own):
    assert unowned_pct.owners(label) == own


OPS = {
    "jvp(TransformerLM)/block_*/fc1/dot_general [convolution fusion]": 0.400,
    "optimizer/add [loop fusion]": 0.030,
    "optimizer/jit(_where)/select_n [loop fusion]": 0.002,
    "shard_map/optimizer/mul [loop fusion]": 0.008,
    "shard_map/grad_reduce/psum [all-reduce]": 0.010,
    "shard_map/grad_reduce/div [loop fusion]": 0.006,
    "shard_map/optimizer/grad_reduce/psum [all-reduce]": 0.004,
    "shard_map/aux_sync/pmax [all-reduce]": 0.001,
    "jvp(Model)/optimizer [loop fusion]": 0.100,     # a primitive so named
    "add [loop fusion]": 0.020,
    "fusion": 0.015,
    "copy-done": 0.004,
}


def test_optimizer_ms_is_what_runs_under_the_scope_and_not_under_reduction():
    got = optimizer_ms.read(ONE_CHIP, trace_of(OPS))
    assert got == pytest.approx(1e3 * (0.030 + 0.002 + 0.008) / 2)


def test_a_reduction_inside_the_optimizer_counts_once_as_reduction():
    got = grad_reduce_ms.read(FOUR_CHIPS, trace_of(OPS))
    assert got == pytest.approx(1e3 * (0.010 + 0.006 + 0.004) / 2)
    both = (optimizer_ms.read(FOUR_CHIPS, trace_of(OPS))
            + grad_reduce_ms.read(FOUR_CHIPS, trace_of(OPS)))
    under_either = sum(s for label, s in OPS.items() if {
        "optimizer", "grad_reduce"} & set(unowned_pct.owners(label)))
    assert both == pytest.approx(1e3 * under_either / 2)


def test_grad_reduce_ms_has_nothing_to_read_on_one_chip():
    assert grad_reduce_ms.read(ONE_CHIP, trace_of(OPS)) is None


def test_unowned_pct_counts_bare_primitives_and_unnamed_ops():
    got = unowned_pct.read(ONE_CHIP, trace_of(OPS))
    assert got == pytest.approx(
        100.0 * (0.020 + 0.015 + 0.004) / sum(OPS.values()))


@pytest.mark.parametrize("reader", [optimizer_ms, grad_reduce_ms,
                                    unowned_pct])
def test_no_trace_reads_nothing(reader):
    assert reader.read(FOUR_CHIPS, None) is None


@pytest.mark.parametrize("reader", [optimizer_ms, grad_reduce_ms,
                                    unowned_pct])
def test_a_program_without_the_scopes_reads_nothing(reader, monkeypatch):
    """A parent commit: ``spmd`` has no ``STEP_SCOPES``."""
    monkeypatch.delattr(spmd, "STEP_SCOPES")
    assert reader.read(FOUR_CHIPS, trace_of(OPS)) is None


def test_a_program_without_that_scope_reads_nothing(monkeypatch):
    monkeypatch.setattr(spmd, "STEP_SCOPES", ("grad_reduce",))
    assert optimizer_ms.read(FOUR_CHIPS, trace_of(OPS)) is None
    assert grad_reduce_ms.read(FOUR_CHIPS, trace_of(OPS)) is not None


@pytest.mark.parametrize("reader", [optimizer_ms, grad_reduce_ms])
def test_scopes_and_no_op_alone_under_them_read_zero_not_nothing(reader):
    """The gpt cells: the update is fused into the weight-gradient
    fusions, which keep the gradient's name."""
    fused = {k: v for k, v in OPS.items()
             if not {"optimizer", "grad_reduce"} & set(unowned_pct.owners(k))}
    got = reader.read(FOUR_CHIPS, trace_of(fused))
    assert got == 0.0 and got is not None


def test_unowned_pct_of_a_chip_trace():
    """The cut of ``gpt13b_1chip``'s trace (PR 22, before the scopes): the
    bare ``add`` loop fusions (AdamW on what no matmul hosts), ``copy``
    and the async copies' and slices' start and done ops."""
    reduced = tracered.reduce(tracered.load_events(DATA))
    ops = reduced["devices"][0]["op_self_s"]
    unowned = {k: v for k, v in ops.items() if not unowned_pct.owners(k)}
    assert set(unowned) == {
        "add [loop fusion]", "copy", "copy-start", "copy-done",
        "slice-start", "slice-done", "slice_bitcast_fusion", "fusion",
        "custom-call"}
    assert max(unowned, key=unowned.get) == "add [loop fusion]"
    got = unowned_pct.read(ONE_CHIP, reduced)
    assert got == pytest.approx(100.0 * sum(unowned.values())
                                / sum(ops.values()))
    assert got == pytest.approx(2.193, abs=5e-3)
    # The trace has no op under a scope: a program with them reads 0.
    assert optimizer_ms.read(ONE_CHIP, reduced) == 0.0
