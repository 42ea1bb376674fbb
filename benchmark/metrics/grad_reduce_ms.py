"""Device milliseconds a step in the gradient reduction, device 0: the
self time of every op under the ``grad_reduce`` scope of
``spmd.reduce_gradients`` (and of ``allreduce_gradients`` inside a
``DistributedOptimizer``) — the collectives AND what stands around them:
wire casts, bucket staging, the division by the mesh size, and a fusion
that hosts an all-reduce where the compiler gave it the all-reduce's
name.  Beside ``comm_ms``, which sees collective opcodes only.  Nothing
to read on one chip, or for a program without the scope."""

from benchmark.metrics import unowned_pct

UNIT = "ms"
LAYER = "gradient reduction"
MOVES = "step_ms"


def read(record, trace):
    if record["chips"] < 2:
        return None
    return unowned_pct.scope_ms(trace, "grad_reduce")
