"""Device milliseconds a step in what a gated, windowed, YaRN-rotated
attention layer adds AROUND its kernels, device 0, forward and backward:
the self time of every op under the trace scopes ``attn/gate`` (the gate's
projection, its sigmoid and the product with the heads' output) and
``rope/yarn`` (the global layers' rotation from YaRN's table, its factor on
cos and sin), and of every op under ``swa/attend`` that is NOT a Pallas
kernel — the layout copies and row statistics the call makes around the
windowed kernels (their own time is ``win_flash_ms``).  Three sources in
one number: the gate runs in both kinds of attention layer, YaRN's rotation
in the global ones alone, the copies in the windowed ones alone
(:func:`gate_part` tells an op's).  A program without the scopes, as this
metric's parent has, reads nothing."""

from benchmark.metrics.win_flash_ms import under

UNIT = "ms"
LAYER = "attention glue"
MOVES = "step_ms"


def gate_part(label: str):
    """``"gate"``, ``"yarn"`` or ``"attend"`` for an op label under one of
    the three scopes (a Pallas kernel under ``swa/attend`` left out), else
    None."""
    for part, scope in (("gate", ("attn", "gate")), ("yarn", ("rope", "yarn")),
                        ("attend", ("swa", "attend"))):
        tokens = under(label, *scope)
        if tokens is not None and not (part == "attend"
                                       and tokens[-1] == "pallas_call"):
            return part
    return None


def read(record, trace):
    if trace is None:
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if gate_part(label) is not None)
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
