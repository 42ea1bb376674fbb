"""Device milliseconds a step in the linear-attention mixers, device 0,
forward and backward: the self time of every op whose name stack lies
under a flax module named ``lin`` (``GatedDeltaNet``'s projections,
convolution, chunked delta rule and gated norm — scopes ``in_proj``,
``conv``, ``delta``, ``gate_norm``, ``out_proj`` — with their
recomputations and transposes) and of the casts of its parameters, which
the compiler names after the parameter
(``params['layer_0']['lin']['q']['kernel']``).  Read only for a family
that prices the delta rule (``delta_cost``); a program without the layer
or its scopes, as this metric's parent has, reads nothing."""

UNIT = "ms"
LAYER = "linear-attention mixers"
MOVES = "step_ms"


def in_mixer(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to a mixer."""
    stack = label.split(" [")[0]
    return "lin" in stack.split("/") or "['lin']" in stack


def in_delta(label: str) -> bool:
    """Whether it belongs to the mixer's ``delta`` scope: the chunked
    delta rule alone (L2 norms, decays, solve, states, read-outs), without
    projections, convolution and gate."""
    parts = label.split(" [")[0].split("/")
    return "lin" in parts and "delta" in parts[parts.index("lin"):]


def milliseconds(record, trace, belongs):
    if trace is None or not hasattr(record["family"], "delta_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items() if belongs(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None


def read(record, trace):
    return milliseconds(record, trace, in_mixer)
