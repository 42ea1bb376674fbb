"""Device milliseconds a step where the decay a key channel changes the
chunked rule's arithmetic, device 0, forward, recomputed and transposed:
the self time of every op under ``lin/delta/solve`` (the halving levels'
scaled operands and masked products that make the ``K K^T`` and ``Q K^T``
tiles, the triangular solve, ``W`` and ``U``) and ``lin/delta/intra`` (the
``Q K^T`` tile applied to ``V'``) — what a fused kernel would take first.
A part of ``delta_ms``.  Read only for a family whose ``delta_cost`` prices
per-channel decays (``decay_bytes``); a program without the scopes, as this
metric's parent has, reads nothing."""

from benchmark.metrics import kda_decay_ms, linattn_ms

UNIT = "ms"
LAYER = "linear-attention mixers"
MOVES = "step_ms"


def in_tiles(label: str) -> bool:
    """Whether an op label of ``tracered.label`` lies under a mixer's
    ``delta/solve`` or ``delta/intra``."""
    parts = label.split(" [")[0].split("/")
    if "lin" not in parts:
        return False
    rest = parts[parts.index("lin"):]
    return "delta" in rest and any(
        scope in rest[rest.index("delta"):] for scope in ("solve", "intra"))


def read(record, trace):
    if trace is None or not kda_decay_ms.prices_channels(record):
        return None
    return linattn_ms.milliseconds(record, trace, in_tiles)
