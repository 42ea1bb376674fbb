"""Share of the traced slice during which a collective ran and no
compute op ran on that device, mean over the devices: the most a faster
or better hidden reduction can give back."""

import statistics

UNIT = "%"
LAYER = "gradient reduction"
MOVES = "step_ms"


def read(record, trace):
    if trace is None or record["chips"] < 2:
        return None
    return 100.0 * statistics.fmean(
        d["collective_exposed_s"] / d["window_s"] for d in trace["devices"])
