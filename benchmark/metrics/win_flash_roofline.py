"""The windowed attention layers' flash kernels' share of their roofline:
the least time the chip could take for the operations and bytes of the
pairs the window LEAVES (the family's ``window_flash_cost``, from shapes:
the same work whatever implements it, never the tiles a kernel visits; the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over
``win_flash_ms``.  A tile a window crosses is computed whole and credited
its live pairs, and a grid step that computes nothing is credited nothing:
both count against the share."""

from benchmark.metrics import win_flash_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "step_ms"


def read(record, trace):
    took_ms = win_flash_ms.read(record, trace)
    family = record["family"]
    if (took_ms is None or record["peaks"] is None
            or not hasattr(family, "window_flash_cost")):
        return None
    cost = family.window_flash_cost(record["cfg"],
                                    record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
