"""Latent attention's kernels' share of their roofline: the least time the
chip could take for their operations and bytes at the PUBLISHED widths (the
family's ``flash_cost``, from shapes: keys of 192, values of 128, the causal
half; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s)
over ``mla_attn_ms``.  FLOPs bound it.  The lanes the kernels pad, the
backward's second pass over the scores and any recomputation count against
the share: on a 128-wide MXU a contraction of 192 takes the passes of 256,
so about 82 is the ceiling."""

from benchmark.metrics import mla_attn_ms

UNIT = "%"
LAYER = "latent attention"
MOVES = "step_ms"


def read(record, trace):
    took_ms = mla_attn_ms.read(record, trace)
    if took_ms is None or record["peaks"] is None:
        return None
    cost = record["family"].flash_cost(record["cfg"],
                                       record["job"]["batch_per_chip"])
    least_s = max(cost["flops"] / record["peaks"]["bf16_flops_per_s"],
                  cost["bytes"] / record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (took_ms * 1e-3)
