"""The grouped-query flash kernels' share of their roofline: the least
time the chip could take for their operations and bytes (the family's
``flash_cost``, from shapes; the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s) over ``gqa_flash_ms``.  At the benchmark's shape
FLOPs bound it (``tests/test_flops_nemotron.py``)."""

from benchmark.metrics import gqa_flash_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "step_ms"


def read(record, trace):
    took_ms = gqa_flash_ms.read(record, trace)
    if took_ms is None or record["peaks"] is None:
        return None
    cost = record["family"].flash_cost(record["cfg"],
                                       record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
