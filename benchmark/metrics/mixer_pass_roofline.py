"""The mixers' two elementwise passes' share of their roofline: the least
time the chip could take for their bytes (the family's ``pass_cost``, from
shapes: one read of each pass's operands and one write of its result,
forward and backward, over peak bytes/s — the passes hold no matmul, so
bytes bound them) over ``mixer_pass_ms``.  A family that does not price
the passes reads nothing."""

from benchmark.metrics import mixer_pass_ms

UNIT = "%"
LAYER = "state-space mixers"
MOVES = "step_ms"


def read(record, trace):
    family = record["family"]
    took_ms = mixer_pass_ms.read(record, trace)
    if (took_ms is None or record["peaks"] is None
            or not hasattr(family, "pass_cost")):
        return None
    cost = family.pass_cost(record["cfg"], record["job"]["batch_per_chip"])
    least_s = cost["bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (took_ms * 1e-3)
