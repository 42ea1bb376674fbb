"""Device milliseconds a step during which a collective (by HLO opcode)
was running or in flight, mean over the devices.  Nothing to read on one
chip."""

import statistics

UNIT = "ms"
LAYER = "gradient reduction"
MOVES = "step_ms"


def read(record, trace):
    if trace is None or record["chips"] < 2:
        return None
    return 1e3 * statistics.fmean(
        d["collective_s"] / d["steps"] for d in trace["devices"])
