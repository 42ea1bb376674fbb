"""Share of the measured window the loop spent blocked in
``next(loader)``: what ShardedLoader's thread did not hide."""

UNIT = "%"
LAYER = "input pipeline"
MOVES = "step_ms"


def read(record, trace):
    waits = record["window"]["waits"]
    if not waits:
        return None
    return 100.0 * sum(waits) / record["window_s"]
