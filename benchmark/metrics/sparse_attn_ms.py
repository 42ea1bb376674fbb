"""Device milliseconds a step in the sparse-attention sub-layers, device 0,
forward and backward: the self time of every op whose name stack lies
under a flax module named ``attn`` (projections, per-head norms, rotary
positions, the indexer's scores, selection and KL pass, the selected-
attention kernels) and of the casts of its parameters.  Read only for a
family that prices the selected attention (``sel_flash_cost``)."""

from benchmark.metrics import _sparse

UNIT = "ms"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.milliseconds(record, trace, _sparse.in_attention)
