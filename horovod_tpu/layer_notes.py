"""What the layers of a model tell the step builder about one step, from
shapes alone: the hook between :func:`horovod_tpu.jax.spmd.make_train_step`
and every layer that has a static count to report (experts' ``moe.*``,
state-space mixers' ``ssm.*``, linear attention's ``lin.*``, attention's
``attn.*``, ``lm.tied_head``).  It imports nothing of the package.
"""

from __future__ import annotations

import functools
from typing import Callable

# What make_train_step wants to know of the layers its loss_fn holds:
# dicts that a layer traced meanwhile writes its static sizes into, keyed
# by its module path (so a second trace of the same layer changes
# nothing).
_NOTING: list = []


def noting_layers(fn: Callable, into: dict) -> Callable:
    """``fn``, with every layer that calls :func:`note_layer` while traced
    inside a call of it written into ``into`` as ``{module path: {counter
    name: one step's count}}`` — per shard, from shapes alone
    (``moe.assignments``, ``ssm.scan_chunks``, ...)."""

    @functools.wraps(fn)
    def noting(*args, **kwargs):
        _NOTING.append(into)
        try:
            return fn(*args, **kwargs)
        finally:
            _NOTING.pop()

    return noting


def note_layer(path, counters: dict) -> None:
    """What a layer being traced tells :func:`noting_layers`'s callers of
    one step's static counts (``{counter name: count}``)."""
    for noted in _NOTING:
        noted[path] = counters
