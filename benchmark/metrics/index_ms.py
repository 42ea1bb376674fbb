"""Device milliseconds a step in the indexer, device 0: the self time of
every op under the attention modules' ``index`` scope — its projections
(``index/project``), the scores kernel (``index/scores``), the exact
top-k (``index/topk``), the map's making (``index/select``), the KL
pass (``index/kl``) and the counters — with the transposes of the
projections."""

from benchmark.metrics import _sparse

UNIT = "ms"
LAYER = "sparse attention"
MOVES = "step_ms"


def read(record, trace):
    return _sparse.milliseconds(record, trace, _sparse.in_indexer)
