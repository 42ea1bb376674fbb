"""Timeline format guarantees: the trace parses as JSON, per-tensor pid
metadata is emitted exactly once, counter tracks use the Chrome-trace
counter phase, wire-tagged activity names, and both implementations
(Python fallback and the native writer) agree.
"""

import json

import pytest

from horovod_tpu import cpp_core
from horovod_tpu.core import RequestType, ResponseType
from horovod_tpu.timeline import Timeline, per_rank_trace_path, wire_activity


class _Entry:
    def __init__(self, name):
        self.name = name


def load_trace(path):
    with open(path) as f:
        return json.load(f)


class TestWireActivity:
    def test_compressed_wire_is_tagged(self):
        assert wire_activity("TCP_ALLREDUCE", "int8") == "TCP_ALLREDUCE[int8]"
        assert wire_activity("TCP_ALLREDUCE", "bf16") == "TCP_ALLREDUCE[bf16]"

    def test_raw_fp32_stays_bare(self):
        # Pre-compression traces must stay comparable: no [fp32] suffix.
        assert wire_activity("TCP_ALLREDUCE", "") == "TCP_ALLREDUCE"


class TestPerRankTracePath:
    def test_placeholder_substituted(self):
        assert per_rank_trace_path("/tmp/t.{rank}.json", 3) == \
            "/tmp/t.3.json"

    def test_suffix_inserted_before_extension(self):
        assert per_rank_trace_path("/tmp/t.json", 1, size=4) == \
            "/tmp/t.rank1.json"

    def test_single_rank_keeps_literal_path(self):
        # Back-compat: 1-process jobs trace to exactly the configured file.
        assert per_rank_trace_path("/tmp/t.json", 0, size=1) == "/tmp/t.json"

    def test_idempotent_over_filled_path(self):
        # run.py fills the template per child AND the controller resolves
        # it again locally; the second pass must be a no-op.
        once = per_rank_trace_path("/tmp/t.json", 2, size=4)
        assert per_rank_trace_path(once, 2, size=4) == once


class TestPythonTimeline:
    def test_trace_t0_anchor_and_strict_json(self, tmp_path):
        path = tmp_path / "t.json"
        tl = Timeline(str(path), rank=2)
        tl.counter("queue_depth", 1)
        tl.close()
        with open(path) as f:
            text = f.read()
        assert text.endswith("\n]\n")          # strictly valid, no {} pad
        events = json.loads(text)
        assert events[0]["name"] == "trace_t0"
        assert events[0]["args"]["rank"] == 2
        assert events[0]["ts"] == 0
        assert events[0]["args"]["t0_wall_us"] > 0

    def test_truncated_trace_is_repairable(self, tmp_path):
        # A killed rank leaves a file missing only the closing "]"; the
        # comma-before-event format keeps every complete line valid.
        path = tmp_path / "t.json"
        tl = Timeline(str(path))
        tl.counter("queue_depth", 1)
        tl.counter("queue_depth", 2)
        tl.flush()
        with open(path) as f:
            text = f.read()          # no close(): simulate SIGKILL
        events = json.loads(text + "\n]")
        assert [e for e in events if e.get("ph") == "C"]
        tl.close()

    def test_tick_span_and_instant(self, tmp_path):
        path = tmp_path / "t.json"
        tl = Timeline(str(path))
        tl.tick_span(7, 1500)
        tl.instant("clock_offset", {"rank": 1, "offset_us": 42.0})
        tl.close()
        events = load_trace(path)
        ticks = [e for e in events if e.get("name") == "TICK"]
        assert len(ticks) == 1
        assert ticks[0]["ph"] == "X" and ticks[0]["pid"] == 0
        assert ticks[0]["dur"] == 1500 and ticks[0]["args"]["tick"] == 7
        offs = [e for e in events if e.get("name") == "clock_offset"]
        assert offs and offs[0]["args"]["offset_us"] == 42.0
    def test_trace_parses_and_pid_metadata_once(self, tmp_path):
        path = tmp_path / "t.json"
        tl = Timeline(str(path))
        for _ in range(3):   # repeated spans must not repeat the metadata
            tl.negotiate_start("grad.0", RequestType.ALLREDUCE)
            tl.negotiate_rank_ready("grad.0", 0)
            tl.negotiate_end("grad.0")
            tl.start("grad.0", ResponseType.ALLREDUCE)
            tl.activity_start_all([_Entry("grad.0")], "XLA_ALLREDUCE")
            tl.activity_end_all([_Entry("grad.0")])
            tl.end("grad.0")
        tl.start("grad.1", ResponseType.ALLGATHER)
        tl.end("grad.1")
        tl.close()

        events = load_trace(path)
        assert isinstance(events, list) and events
        names = [e for e in events if e.get("name") == "process_name"]
        assert len(names) == 2   # exactly once per tensor
        by_pid = {e["pid"]: e["args"]["name"] for e in names}
        assert sorted(by_pid.values()) == ["grad.0", "grad.1"]
        sorts = [e for e in events if e.get("name") == "process_sort_index"]
        assert len(sorts) == 2

    def test_counter_events(self, tmp_path):
        path = tmp_path / "t.json"
        tl = Timeline(str(path))
        tl.counter("queue_depth", 3)
        tl.counter("bytes_in_flight", 4096)
        tl.flush()
        tl.close()
        counters = [e for e in load_trace(path) if e.get("ph") == "C"]
        assert len(counters) == 2
        for e in counters:
            assert e["pid"] == 0          # job-level track, not per-tensor
            assert isinstance(e["args"]["value"], int)
        assert {e["name"] for e in counters} == {"queue_depth",
                                                 "bytes_in_flight"}

    def test_close_is_idempotent(self, tmp_path):
        path = tmp_path / "t.json"
        tl = Timeline(str(path))
        tl.counter("queue_depth", 1)
        tl.close()
        tl.close()            # atexit guard may close after stop()
        tl.counter("queue_depth", 2)   # late event must be a no-op
        events = load_trace(path)
        assert len([e for e in events if e.get("ph") == "C"]) == 1


@pytest.mark.skipif(not cpp_core.available(), reason="native core not built")
class TestNativeTimeline:
    def test_same_format_as_python(self, tmp_path):
        path = tmp_path / "native.json"
        tl = cpp_core.CppTimeline(str(path))
        tl.negotiate_start("grad.0", int(RequestType.ALLREDUCE))
        tl.negotiate_rank_ready("grad.0", 0)
        tl.negotiate_end("grad.0")
        tl.start("grad.0", int(ResponseType.ALLREDUCE))
        tl.end("grad.0")
        tl.counter("queue_depth", 2)
        tl.flush()
        tl.close()
        events = load_trace(path)
        names = [e for e in events if e.get("name") == "process_name"]
        assert len(names) == 1
        assert names[0]["args"]["name"] == "grad.0"
        counters = [e for e in events if e.get("ph") == "C"]
        assert len(counters) == 1
        assert counters[0]["name"] == "queue_depth"
        assert counters[0]["args"]["value"] == 2
        assert counters[0]["pid"] == 0

    def test_rank_anchor_tick_span_strict_json(self, tmp_path):
        path = tmp_path / "native.json"
        tl = cpp_core.CppTimeline(str(path), rank=1)
        tl.tick_span(3, 250)
        tl.instant("clock_offset", {"rank": 1, "offset_us": -7.5,
                                    "uncertainty_us": 2.0})
        tl.close()
        with open(path) as f:
            text = f.read()
        assert text.endswith("\n]\n")
        events = json.loads(text)
        assert events[0]["name"] == "trace_t0"
        assert events[0]["args"]["rank"] == 1
        assert events[0]["args"]["t0_wall_us"] > 0
        ticks = [e for e in events if e.get("name") == "TICK"]
        assert ticks and ticks[0]["args"]["tick"] == 3
        assert ticks[0]["dur"] == 250
        offs = [e for e in events if e.get("name") == "clock_offset"]
        assert offs and offs[0]["args"]["offset_us"] == -7.5


@pytest.mark.parametrize("backend", ["python", "cpp"])
def test_activity_span_is_a_complete_event_on_the_lane(tmp_path, backend):
    """A span the caller timed itself (the ring's ``step/dispatch``) lands
    on its lane as one ``X`` event of that length, ending when it ended,
    in both writers."""
    import time
    path = tmp_path / "t.json"
    if backend == "cpp":
        if not cpp_core.available():
            pytest.skip("native core not built")
        tl = cpp_core.CppTimeline(str(path))
    else:
        tl = Timeline(str(path))
    tl.activity_start_all([_Entry("train_step/execute")], "EXECUTE")
    start_ns = time.perf_counter_ns()
    time.sleep(0.02)
    end_ns = time.perf_counter_ns()
    time.sleep(0.01)
    tl.activity_span("train_step/dispatch", "DISPATCH", start_ns, end_ns)
    tl.activity_end_all([_Entry("train_step/execute")])
    tl.close()
    events = load_trace(path)
    lanes = {e["args"]["name"]: e["pid"] for e in events
             if e.get("name") == "process_name"}
    (span,) = [e for e in events if e.get("name") == "DISPATCH"]
    assert span["ph"] == "X" and span["pid"] == lanes["train_step/dispatch"]
    assert span["dur"] == (end_ns - start_ns) // 1000
    (begin,) = [e for e in events if e.get("name") == "EXECUTE"]
    (end,) = [e for e in events if e.get("ph") == "E"
              and e["pid"] == lanes["train_step/execute"]]
    # On the writer's own clock: it began after EXECUTE began and ended
    # 10 ms or more before EXECUTE ended.
    assert begin["ts"] <= span["ts"] + 500
    assert span["ts"] + span["dur"] <= end["ts"] - 9000
