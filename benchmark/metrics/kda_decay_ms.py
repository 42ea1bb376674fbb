"""Device milliseconds a step in what a decay a key CHANNEL costs a
linear-attention mixer before its tiles, device 0, forward, recomputed and
transposed: the self time of every op under a scope ``decay`` of a flax
module named ``lin`` — ``lin/decay`` (the low-rank decay projection, the
``softplus`` and the log-decays ``g``, float32 (T, H, d_k)) and
``lin/delta/decay`` (the chunked rule's sums of ``g`` a channel and the
factors ``exp`` makes of them; a part of ``delta_ms`` too).  Read only for
a family whose ``delta_cost`` prices per-channel decays (``decay_bytes``);
a program without the scope, as this metric's parent has, reads nothing."""

from benchmark.metrics import linattn_ms

UNIT = "ms"
LAYER = "linear-attention mixers"
MOVES = "step_ms"


def in_decay(label: str) -> bool:
    """Whether an op label of ``tracered.label`` lies under a ``decay``
    scope of a mixer."""
    parts = label.split(" [")[0].split("/")
    return "lin" in parts and "decay" in parts[parts.index("lin"):]


def prices_channels(record) -> bool:
    family = record["family"]
    return hasattr(family, "delta_cost") and "decay_bytes" in (
        family.delta_cost(record["cfg"], record["job"]["batch_per_chip"]))


def read(record, trace):
    if trace is None or not prices_channels(record):
        return None
    return linattn_ms.milliseconds(record, trace, in_decay)
