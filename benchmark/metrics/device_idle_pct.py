"""Share of the traced slice in which no op, compute or collective, ran
on device 0 (the ``trace`` line has every device)."""

UNIT = "%"
LAYER = "device"
MOVES = "step_ms"


def read(record, trace):
    if trace is None:
        return None
    d = trace["devices"][0]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
