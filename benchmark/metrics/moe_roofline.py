"""The expert layers' share of their roofline: the least time the chip
could take for their operations and bytes (the family's ``moe_cost``,
from shapes; the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s) over ``moe_ms``.  At the benchmark's shape FLOPs bound it
(``tests/test_flops_olmoe.py``)."""

from benchmark.metrics import moe_ms

UNIT = "%"
LAYER = "experts"
MOVES = "step_ms"


def read(record, trace):
    took_ms = moe_ms.read(record, trace)
    if took_ms is None or record["peaks"] is None:
        return None
    cost = record["family"].moe_cost(record["cfg"],
                                     record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
