"""The indexer's scores kernel's share of its roofline: the least time the
chip could take for the causal pairs' operations and bytes (the family's
``index_scores_cost``) over the self time of the ``pallas_call`` ops
under ``index/scores``."""

from benchmark.metrics import _sparse

UNIT = "%"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.roofline(
        record, _sparse.milliseconds(record, trace,
                                     _sparse.is_scores_kernel),
        "index_scores_cost")
