"""Device milliseconds a step in the state-space mixers, device 0,
forward and backward: the self time of every op whose name stack lies
under a flax module named ``ssm`` (``Mamba2Mixer``'s projections,
convolution, chunked scan and gated norm — scopes ``in_proj``, ``conv``,
``scan``, ``gate_norm``, ``out_proj`` — with their recomputations and
transposes) and of the casts of its parameters, which the compiler names
after the parameter (``params['layer_0']['ssm']['in_proj']['kernel']``).
Read only for a family that prices the scan (``ssd_cost``); a program
without the layer or its scopes, as this metric's parent has, reads
nothing."""

UNIT = "ms"
LAYER = "state-space mixers"
MOVES = "step_ms"


def in_mixer(label: str) -> bool:
    """Whether an op label of ``tracered.label`` belongs to a mixer."""
    stack = label.split(" [")[0]
    return "ssm" in stack.split("/") or "['ssm']" in stack


def in_scan(label: str) -> bool:
    """Whether it belongs to the mixer's ``scan`` scope: the chunked
    state-space scan alone, without projections, convolution and gate."""
    parts = label.split(" [")[0].split("/")
    return "ssm" in parts and "scan" in parts[parts.index("ssm"):]


def milliseconds(record, trace, belongs):
    if trace is None or not hasattr(record["family"], "ssd_cost"):
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items() if belongs(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None


def read(record, trace):
    return milliseconds(record, trace, in_mixer)
