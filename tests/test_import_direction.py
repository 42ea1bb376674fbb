"""Which module may know what, read from the files' ASTs (no jax): the
kernel families of ``horovod_tpu/ops`` share one module, ``_pallas``, and
no family reaches into another's private names; the layers' notes live
in ``horovod_tpu/layer_notes.py``, not in the expert module."""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOTES = {"note_layer", "noting_layers", "noting_expert_layers", "_NOTING"}


def imported(path):
    """``(module, name)`` of every ``from module import name`` of a file
    of the package, wherever in it the statement stands."""
    with open(os.path.join(ROOT, "horovod_tpu", path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            for alias in node.names:
                yield node.module, alias.name


@pytest.mark.parametrize("module", [
    "ssd", "mixer_passes", "grouped_matmul", "sparse_select",
    "flash_attention", "gated_delta", "losses", "quantized_collectives"])
def test_a_kernel_family_takes_no_private_name_of_another(module):
    private = [(source, name) for source, name in imported(f"ops/{module}.py")
               if source.startswith("horovod_tpu.ops")
               and source != "horovod_tpu.ops._pallas"
               and name.startswith("_") and name != "_pallas"]
    assert not private, private


@pytest.mark.parametrize("path", [
    "models/ssm.py", "models/linear_attention.py", "models/transformer.py",
    "jax/spmd.py"])
def test_the_layers_notes_do_not_come_from_the_expert_module(path):
    from_moe = {name for source, name in imported(path)
                if source == "horovod_tpu.parallel.moe"}
    assert not from_moe & NOTES, from_moe & NOTES
    if path == "jax/spmd.py":
        assert not from_moe, from_moe
