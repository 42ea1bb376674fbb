"""Device milliseconds a step in the expert layers' two latent projections,
device 0, forward and backward: the self time of every op under the
submodules ``latent_down`` (``z = u W_down``, before the rows are gathered)
and ``latent_up`` (``y W_up``, after the combine) of a flax module named
``moe``, and of the casts of their parameters, which the compiler names
after the parameter (``params['layer_1']['moe']['latent_down']['kernel']``).
A part of ``moe_ms``.  Read only for a family that prices the projections
(``moe_cost`` gives ``latent_flops``); a program without the layer or its
scopes, as this metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "experts"
MOVES = "step_ms"

PROJECTIONS = ("latent_down", "latent_up")


def in_latent_projection(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to one of an
    expert layer's latent projections."""
    stack = label.split(" [")[0]
    parts = stack.split("/")
    if "moe" in parts and any(p in parts[parts.index("moe"):]
                              for p in PROJECTIONS):
        return True
    return any(f"['moe']['{p}']" in stack for p in PROJECTIONS)


def read(record, trace):
    family = record["family"]
    if trace is None or not hasattr(family, "moe_cost") or (
            "latent_flops" not in family.moe_cost(
                record["cfg"], record["job"]["batch_per_chip"])):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if in_latent_projection(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
