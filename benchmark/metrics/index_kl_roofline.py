"""The KL pass's kernel's share of its roofline: the least time the chip
could take for the selected pairs' operations and bytes (the family's
``index_kl_cost``) over the self time of the ``pallas_call`` ops under
``index/kl``."""

from benchmark.metrics import _sparse

UNIT = "%"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.roofline(
        record, _sparse.milliseconds(record, trace, _sparse.is_kl_kernel),
        "index_kl_cost")
