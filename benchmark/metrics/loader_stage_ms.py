"""Median host milliseconds ShardedLoader's thread took to stage one
batch in the measured window (``loader/stage``: stack where
``steps_per_call`` > 1, then ``device_put`` to the mesh)."""

import statistics

from benchmark.metrics import _spans

UNIT = "ms"
LAYER = "input pipeline"
MOVES = "step_ms"


def read(record, trace):
    stages = _spans.in_window(record, "loader/stage")
    if not stages:
        return None
    return statistics.median(s.end_ns - s.start_ns for s in stages) / 1e6
