"""tools/tier1_times.py on a hand-written junit file: seconds and cases a
file, the long cases, the summed time, the six-worker bound and the cap's
share."""

import importlib.util
import io
import os

_SPEC = importlib.util.spec_from_file_location(
    "tier1_times",
    os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                 "tier1_times.py"))
tier1_times = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(tier1_times)

JUNIT = """<?xml version="1.0" encoding="utf-8"?>
<testsuites name="pytest tests"><testsuite name="pytest" errors="0"
 failures="1" skipped="1" tests="5" time="735.0">
<testcase classname="tests.test_a.TestX" name="test_one[big-1]" time="30.5" />
<testcase classname="tests.test_a.TestX.TestInner" name="test_two" time="1.5" />
<testcase classname="tests.test_b" name="test_three" time="25.0">
<failure message="no">no</failure></testcase>
<testcase classname="tests.test_b" name="test_four" time="0.0">
<skipped message="no chip" /></testcase>
<testcase classname="tests.test_b" name="test_five" time="3.0" />
</testsuite></testsuites>
"""


def test_report_on_a_hand_written_junit_file(tmp_path):
    path = tmp_path / "t1.xml"
    path.write_text(JUNIT)
    wall, cases = tier1_times.read(path)
    assert wall == 735.0
    assert [(c[0], c[3]) for c in cases] == [
        ("tests/test_a.py", True), ("tests/test_a.py", True),
        ("tests/test_b.py", False), ("tests/test_b.py", False),
        ("tests/test_b.py", True)]
    out = io.StringIO()
    tier1_times.report(path, over=20.0, files=1, out=out)
    lines = out.getvalue().splitlines()
    assert lines[1].split() == ["32.0", "2", "tests/test_a.py"]
    assert not any("tests/test_b.py" in l for l in lines[:3])   # --files 1
    assert "2 cases of 20 s or more, 56 s together:" in lines
    assert lines[4].split() == ["30.5", "tests/test_a.py::test_one[big-1]"]
    assert lines[5].split() == ["25.0", "tests/test_b.py::test_three"]
    assert lines[-1] == (
        "5 cases in 2 files, 3 passed; summed case time 60 s; longest file "
        "32 s; 6-worker lower bound 10 s; wall 735 s = 50% of the 1470 s cap")
