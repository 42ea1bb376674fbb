"""Device milliseconds a step in the WINDOWED attention layers' flash
kernels, device 0, forward and backward: the self time of the
``pallas_call`` ops under the trace scope ``swa/attend`` that
``GroupedQueryAttention(window=W)`` puts around its kernels.  A part of
``gqa_flash_ms``, which reads every attention kernel of the cell; the
global layers' kernels are the rest.  A program without the scope, as this
metric's parent has, reads nothing."""

import re

UNIT = "ms"
LAYER = "kernels"
MOVES = "step_ms"

_WRAPPER = re.compile(r"\w+\(|\)")


def under(label: str, *scope: str):
    """The tokens of an op label of ``tracered.label`` from the trace scope
    ``scope`` (its parts in a row) on, the wrappers of the transposes taken
    off, or None for an op of no such scope."""
    tokens = _WRAPPER.sub("", label.split(" [")[0]).split("/")
    for at in range(len(tokens) - len(scope) + 1):
        if tuple(tokens[at:at + len(scope)]) == scope:
            return tokens[at:]
    return None


def is_window_kernel(label: str) -> bool:
    """Whether an op label is a Pallas kernel of a windowed attention
    layer."""
    tokens = under(label, "swa", "attend")
    return tokens is not None and tokens[-1] == "pallas_call"


def read(record, trace):
    if trace is None:
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if is_window_kernel(label))
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
