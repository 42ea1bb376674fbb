"""Device milliseconds a step in the two-stream form's glue, device 0,
forward and backward: the self time of every op under the block-diffusion
call's trace scopes ``bd/assemble`` (the noised copy's ids, the gather of
both streams' rows, the positions), ``bd/split`` (the noised half cut out
for the head) and ``bd/loss`` (the weighting of the per-row cross-entropy),
and of every op under ``bd/attend`` that is NOT a Pallas kernel — the pads,
concatenates, layout copies and row statistics that the call makes around
the flash kernels (their own time is ``gqa_flash_ms``).  What the objective
costs beside the kernels and the doubled rows: copies of this kind are how
``joyaiflash_1chip`` lost 18.7 ms a step unowned.  A program without the
scopes, as this metric's parent has, reads nothing."""

import re

UNIT = "ms"
LAYER = "block diffusion"
MOVES = "step_ms"

PARTS = ("assemble", "attend", "split", "loss")
_WRAPPER = re.compile(r"\w+\(|\)")


def stream_part(label: str):
    """The part of the block-diffusion call an op label of
    ``tracered.label`` belongs to — the scope under ``bd`` — or None for an
    op of no such scope and for a Pallas kernel under ``bd/attend``."""
    tokens = _WRAPPER.sub("", label.split(" [")[0]).split("/")
    for at, token in enumerate(tokens[:-1]):
        if token == "bd" and tokens[at + 1] in PARTS:
            if tokens[at + 1] == "attend" and tokens[-1] == "pallas_call":
                return None
            return tokens[at + 1]
    return None


def read(record, trace):
    if trace is None:
        return None
    d = trace["devices"][0]
    seconds = sum(s for label, s in d["op_self_s"].items()
                  if stream_part(label) is not None)
    return 1e3 * seconds / d["steps"] if seconds > 0 else None
