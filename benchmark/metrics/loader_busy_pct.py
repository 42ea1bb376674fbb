"""Share of the measured window ShardedLoader's thread was working:
``loader/source`` + ``loader/stage`` as far as they lie inside the window
(a span across one of its ends is cut there) over the window.
The rest of its time it is ahead, blocked on a full queue
(``loader/put_wait``).  Near 100 the input binds the step whatever
``input_wait_pct`` reads."""

from benchmark.metrics import _spans

UNIT = "%"
LAYER = "input pipeline"
MOVES = "step_ms"


def read(record, trace):
    working = _spans.clipped_to_window(
        record, ("loader/source", "loader/stage"))
    if not working:
        return None
    t_open, t_close = _spans.window_ns(record)
    return 100.0 * _spans.covered_s(working) / ((t_close - t_open) / 1e9)
