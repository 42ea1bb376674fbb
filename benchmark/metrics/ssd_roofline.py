"""The chunked scans' share of their roofline: the least time the chip
could take for their operations and bytes (the family's ``ssd_cost``,
from shapes, forward and backward; the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s) over ``ssd_ms``.  At the benchmark's shape
bytes bound it (``tests/test_flops_nemotron.py``)."""

from benchmark.metrics import ssd_ms

UNIT = "%"
LAYER = "state-space mixers"
MOVES = "step_ms"


def read(record, trace):
    took_ms = ssd_ms.read(record, trace)
    if took_ms is None or record["peaks"] is None:
        return None
    cost = record["family"].ssd_cost(record["cfg"],
                                     record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
