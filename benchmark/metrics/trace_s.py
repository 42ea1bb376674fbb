"""Host seconds of set-up the program spent tracing the step in Python,
all passes: ``make_train_step``'s two ``eval_shape`` traces on one chip
(spans ``step/trace_spmd``, ``step/trace_plain``) and what jax reports
as ``jax/trace`` under ``step/lower`` / ``step/first_call`` — as the
union of the intervals, since a jitted function inside the step reports
its own trace inside the outer one's."""

from benchmark.metrics import _spans

UNIT = "s"
LAYER = "step builder"
MOVES = "setup_s"


def read(record, trace):
    spans = _spans.during_setup(record)
    if spans is None:
        return None
    passes = [s for s in spans
              if s.name in ("step/trace_spmd", "step/trace_plain")]
    passes += [s for s in _spans.under(spans, _spans.STEP_BUILD)
               if s.name == "jax/trace"]
    return _spans.covered_s(passes) if passes else None
