"""Device milliseconds a step in the mixers' chunked state-space scans
alone, device 0: the self time of the ops under the scope ``scan`` of a
module named ``ssm`` — the products inside the chunks, the chunk states,
their pass from chunk to chunk and their read-out, forward, recomputed
and transposed.  What ``ssd_roofline`` divides by."""

from benchmark.metrics import ssm_ms

UNIT = "ms"
LAYER = "state-space mixers"
MOVES = "step_ms"


def read(record, trace):
    return ssm_ms.milliseconds(record, trace, ssm_ms.in_scan)
