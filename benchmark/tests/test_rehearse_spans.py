"""The traced rehearsal of every cell lists the six metrics that read the
program's span ring — a value off the chip is null, as every value is —
and the ring of a whole run has dropped nothing."""

import pytest

from benchmark.tests.test_rehearse import CELLS, ROOT, last_line, run
from benchmark.tests.test_span_metrics import NAMES, READERS


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_lists_the_six_span_metrics(cell):
    proc = run(ROOT, "--workload", cell, "--seed", "34", "--seconds", "1",
               "--trace", "1", "--rehearse")
    metrics = last_line(proc)["metrics"]
    assert set(NAMES) <= set(metrics)
    assert all(metrics[n]["value"] is None for n in NAMES)
    assert all(metrics[n]["unit"] == READERS[n].UNIT for n in NAMES)
