"""Device memory one step needs by the compiler's plan for the step
program: arguments + outputs + temporaries - aliased (donated) bytes.
The runtime's ``peak_bytes_in_use`` does not hold a program's
temporaries; the ``memory`` line prints both beside this."""

UNIT = "GiB"
LAYER = "device"
MOVES = "mfu_pct"


def read(record, trace):
    m = record.get("memory_plan")
    if m is None:
        return None
    return (m["argument"] + m["output"] + m["temp"] - m["alias"]) / 2 ** 30
