"""Host seconds of set-up jax spent turning the step's jaxpr into an
MLIR module: the union of the ``jax/lower`` spans under ``step/lower`` /
``step/first_call``."""

from benchmark.metrics import _spans

UNIT = "s"
LAYER = "step builder"
MOVES = "setup_s"


def read(record, trace):
    spans = _spans.during_setup(record)
    if spans is None:
        return None
    lowers = [s for s in _spans.under(spans, _spans.STEP_BUILD)
              if s.name == "jax/lower"]
    return _spans.covered_s(lowers) if lowers else None
