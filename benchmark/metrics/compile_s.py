"""Host seconds to the step's first result: make_train_step's traces
(``step.lower``), then the first call ended by reading its loss — the
compile, or the persistent cache's read-back (the ``setup`` line says
which), and one step.  Rendering the lowered text, which only the
benchmark's checks need, is left out."""

UNIT = "s"
LAYER = "step builder"
MOVES = "setup_s"


def read(record, trace):
    p = record["phases"]
    if "step_trace_s" not in p:
        return None
    return p["step_trace_s"] + p["step_first_call_s"]
