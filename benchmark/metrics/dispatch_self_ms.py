"""Median host milliseconds a dispatch of the measured window spent in
the program's own wrappers: ``step/dispatch`` less ``step/enqueue`` (the
call into the jitted function), joined on the call ordinal.  The part of
``dispatch_ms`` that is ours; the rest is jax's.  The last dispatch, of
the step in flight when the window closed, is left out, as
``dispatch_ms`` leaves it out."""

import statistics

from benchmark.metrics import _spans

UNIT = "ms"
LAYER = "step builder"
MOVES = "step_ms"


def read(record, trace):
    calls = _spans.in_window(record, "step/dispatch")
    inner = _spans.in_window(record, "step/enqueue")
    if not calls or inner is None:
        return None
    enqueue_ns = {s.key: s.end_ns - s.start_ns for s in inner}
    own = [(s.end_ns - s.start_ns - enqueue_ns[s.key]) / 1e6
           for s in calls[:-1] if s.key in enqueue_ns]
    return statistics.median(own) if own else None
