"""Device milliseconds a step in the mixers' chunked gated delta rules
alone, device 0: the self time of the ops under the scope ``delta`` of a
module named ``lin`` — the L2 norms, the decays, the triangular solve,
the states' pass from chunk to chunk and the two read-outs, forward,
recomputed and transposed.  What ``delta_roofline`` divides by."""

from benchmark.metrics import linattn_ms

UNIT = "ms"
LAYER = "linear-attention mixers"
MOVES = "step_ms"


def read(record, trace):
    return linattn_ms.milliseconds(record, trace, linattn_ms.in_delta)
