"""Device milliseconds a step in the selected-attention Pallas kernels,
device 0, forward, dq and dk/dv, found by their label: the
``pallas_call`` ops under the attention modules' ``flash_select`` scope."""

from benchmark.metrics import _sparse

UNIT = "ms"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.milliseconds(record, trace, _sparse.is_selected_flash)
