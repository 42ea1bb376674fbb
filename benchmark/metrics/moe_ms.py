"""Device milliseconds a step in the expert layers, device 0, forward and
backward: the self time of every op whose name stack lies under a flax
module named ``moe`` (the router, sort, gathers, activation, combine and
router losses of ``DroplessMoE``, with their transposes), of the casts
of its parameters, which the compiler names after the parameter
(``params['block_0']['moe']['w_gate']``), and of the grouped matmuls,
which the TPU compiler turns into custom calls named ``ragged-dot...``
that carry no name stack.  Read only for a family that
prices the layer (``moe_cost``); a program without the layer or its
scopes, as this metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "experts"
MOVES = "step_ms"


def in_expert_layer(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to the expert
    layer."""
    stack = label.split(" [")[0]
    return ("moe" in stack.split("/") or "['moe']" in stack
            or label.startswith(("ragged-dot", "ragged_dot")))


def read(record, trace):
    if trace is None or not hasattr(record["family"], "moe_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if in_expert_layer(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
