"""Host seconds of set-up in the backend for the step program: the sum
of the ``jax/compile`` spans under ``step/lower`` / ``step/first_call``
— the compile in a new checkout, the persistent cache's read-back
otherwise.  jax times ``compile_or_get_cached`` as a whole, so on a hit
``jax/cache_read`` is a child of ``jax/compile`` and is not added."""

from benchmark.metrics import _spans

UNIT = "s"
LAYER = "step builder"
MOVES = "setup_s"


def read(record, trace):
    spans = _spans.during_setup(record)
    if spans is None:
        return None
    compiles = [s for s in _spans.under(spans, _spans.STEP_BUILD)
                if s.name == "jax/compile"]
    if not compiles:
        return None
    return sum(s.end_ns - s.start_ns for s in compiles) / 1e9
