"""The chunked delta rules' share of their roofline: the least time the
chip could take for their operations and bytes (the family's
``delta_cost``, from shapes, forward and backward; the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s) over ``delta_ms``.  At the
benchmark's shape bytes bound it (``tests/test_flops_olmo_hybrid.py``)."""

from benchmark.metrics import delta_ms

UNIT = "%"
LAYER = "linear-attention mixers"
MOVES = "step_ms"


def read(record, trace):
    took_ms = delta_ms.read(record, trace)
    if took_ms is None or record["peaks"] is None:
        return None
    cost = record["family"].delta_cost(record["cfg"],
                                       record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
