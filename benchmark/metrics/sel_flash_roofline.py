"""The selected-attention kernels' share of their roofline: the least time
the chip could take for the SELECTED pairs' operations and bytes (the
family's ``sel_flash_cost``, from shapes: the same work whatever
implements it) over ``sel_flash_ms``.  A version that multiplies every
causal tile does 4.3 times the selected pairs' products at T 16,384, so it
reads no more than 23%."""

from benchmark.metrics import _sparse

UNIT = "%"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.roofline(
        record, _sparse.milliseconds(record, trace,
                                     _sparse.is_selected_flash),
        "sel_flash_cost")
