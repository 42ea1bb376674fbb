"""Device milliseconds a step in the expert layers' router, device 0,
forward and backward: the self time of every op under the ``route`` scope
of a flax module named ``moe`` — the down-projection (``route/down``), the
state handed on from the layer before (``route/eda``), the router network
(``route/mlp``), the softmax and the choice —, float32 at full matmul
precision.  A part of ``moe_ms``.  Read only for a family that prices a
router network (``moe_cost`` gives ``router_flops``); a program without
the scopes, as this metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "experts"
MOVES = "step_ms"


def in_router(label: str) -> bool:
    """Whether an op label of ``tracered.label`` lies under an expert
    layer's ``route`` scope."""
    parts = label.split(" [")[0].split("/")
    return "moe" in parts and "route" in parts[parts.index("moe"):]


def read(record, trace):
    family = record["family"]
    if trace is None or not hasattr(family, "moe_cost") or (
            "router_flops" not in family.moe_cost(
                record["cfg"], record["job"]["batch_per_chip"])):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if in_router(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
