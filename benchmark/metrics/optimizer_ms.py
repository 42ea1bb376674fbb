"""Device milliseconds a step in the optimizer's update, device 0: the
self time of every op under ``make_train_step``'s ``optimizer`` scope
(``optimizer.update`` and ``optax.apply_updates``: AdamW's moments, the
weight decay, the step, the add into the parameters) and not under
``grad_reduce`` (an optimizer that reduces for itself).  XLA names a
fusion after its root, so where the compiler fuses a leaf's update into
the fusion that makes its gradient (the gpt cells' weight-gradient
matmuls, every cell's head) that time stays with the gradient's module
and this reads what ran ALONE: near 0 there, the whole update where
nothing hosts it (the expert cells' 17-23 ms).  0.0, not nothing, for a
program that has the scope and no op alone under it; nothing for a
program without the scope, as this metric's parent is."""

from benchmark.metrics import unowned_pct

UNIT = "ms"
LAYER = "optimizer"
MOVES = "step_ms"


def read(record, trace):
    return unowned_pct.scope_ms(trace, "optimizer")
