"""Median host milliseconds for ``step(...)`` to return in the measured
window: the five wrappers and jax's dispatch — enqueue, not completion."""

import statistics

UNIT = "ms"
LAYER = "step builder"
MOVES = "step_ms"


def read(record, trace):
    d = record["window"]["dispatches"]
    return statistics.median(d) * 1e3 if d else None
