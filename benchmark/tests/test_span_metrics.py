"""The six readers of the program's span ring (``trace_s``, ``lower_s``,
``xla_s``, ``dispatch_self_ms``, ``loader_busy_pct``, ``loader_stage_ms``)
on a hand-made ring: what they select by the window's ``t_open`` and
``boundaries``, what they add up, and that they give None — never a
number, never an exception — where the ring dropped what they need or the
program has no ring at all."""

import importlib
import types

import pytest

from horovod_tpu import timeline
from horovod_tpu.timeline import Span

NAMES = ("trace_s", "lower_s", "xla_s", "dispatch_self_ms",
         "loader_busy_pct", "loader_stage_ms")
READERS = {n: importlib.import_module(f"benchmark.metrics.{n}") for n in NAMES}
MS = 1_000_000
S = 1_000_000_000
MAIN, LOADER = 1, 2

# The window opens at 100 s; steps are seen complete at 101, 102, 103 s.
RECORD = {"window": {"t_open": 100.0, "boundaries": [101.0, 102.0, 103.0]}}


def hand_made():
    spans, ids = [], iter(range(1, 10_000))

    def span(name, start_ns, end_ns, parent=0, thread=MAIN, key=None):
        s = Span(next(ids), parent, name, thread, int(start_ns), int(end_ns),
                 key)
        spans.append(s)
        return s.id

    # The reference check's own jit: no step/* ancestor, in no metric.
    span("jax/trace", 10 * S, 14 * S)
    span("jax/compile", 14 * S, 30 * S)
    # make_train_step's set-up as run.py drives it: lower, then first call.
    resolve = span("step/resolve", 40 * S, 46 * S)
    spmd = span("step/trace_spmd", 40 * S, 43 * S, resolve)
    outer = span("jax/trace", 40 * S + MS, 43 * S - MS, spmd)
    span("jax/trace", 41 * S, 42 * S, outer)            # an inner jit's
    span("step/trace_plain", 43 * S, 46 * S, resolve)
    lower = span("step/lower", 46 * S, 49 * S)
    third = span("jax/trace", 46 * S, 46.5 * S, lower)
    span("jax/trace", 46.1 * S, 46.2 * S, third)        # inside: adds nothing
    span("jax/lower", 46.5 * S, 49 * S, lower)
    first = span("step/first_call", 50 * S, 52 * S, key=0)
    enqueue = span("step/enqueue", 50 * S, 52 * S, first, key=0)
    compiled = span("jax/compile", 50 * S, 51.5 * S, enqueue, key=0)
    span("jax/cache_read", 50.2 * S, 51.4 * S, compiled, key=0)
    # Warm-up, before the window: a dispatch that is in no window metric.
    d = span("step/dispatch", 60 * S, 60 * S + 9 * MS, key=1)
    span("step/enqueue", 60 * S, 60 * S + 1 * MS, d, key=1)
    span("loader/stage", 60 * S, 61 * S, thread=LOADER, key=1)
    # The window: three dispatches, 1 / 2 / 4 ms of which 0.5 / 1 / 1 ms
    # enqueue; the fourth is of the step in flight at the window's end.
    for k, (at, total, inner) in enumerate(
            [(100.1, 1.0, 0.5), (101.1, 2.0, 1.0), (102.1, 4.0, 1.0),
             (102.9, 8.0, 1.0)], start=2):
        d = span("step/dispatch", at * S, at * S + total * MS, key=k)
        span("step/enqueue", at * S, at * S + inner * MS, d, key=k)
    # The loader's thread: 10 + 50 ms a batch, three batches in the window
    # and one whose staging lies across its end (103 s).
    for k, at in enumerate([100.2, 101.2, 102.2, 102.97], start=2):
        span("loader/source", at * S, at * S + 10 * MS, thread=LOADER, key=k)
        span("loader/stage", at * S + 10 * MS, at * S + 60 * MS,
             thread=LOADER, key=k)
        span("loader/put_wait", at * S + 60 * MS, at * S + 900 * MS,
             thread=LOADER, key=k)
    # After the window (the traced slice, compiled_plan): in no metric.
    again = span("step/lower", 110 * S, 111 * S)
    span("jax/lower", 110 * S, 111 * S, again)
    span("step/dispatch", 112 * S, 112 * S + 50 * MS, key=9)
    spans.sort(key=lambda s: s.end_ns)                  # kept when closed
    return spans


@pytest.fixture()
def ring(monkeypatch):
    fake = types.SimpleNamespace(dropped=0, spans=hand_made())
    fake.snapshot = lambda: list(fake.spans)
    monkeypatch.setattr(timeline, "ring", fake)
    return fake


def read(name, record=RECORD):
    return READERS[name].read(record, None)


def test_set_up_readers_add_the_step_s_spans_and_nothing_else(ring):
    # trace_spmd 3 s + trace_plain 3 s + the union under step/lower 0.5 s;
    # the inner jit's span and the reference check's add nothing.
    assert read("trace_s") == pytest.approx(6.5)
    assert read("lower_s") == pytest.approx(2.5)        # not the later one
    # The compile span alone: the cache read is its child, never added.
    assert read("xla_s") == pytest.approx(1.5)


def test_window_readers_select_by_t_open_and_the_last_boundary(ring):
    # Self times 0.5, 1.0, 3.0 ms; the in-flight fourth (7.0) is left out,
    # as are warm-up's 8.0 and the traced slice's.
    assert read("dispatch_self_ms") == pytest.approx(1.0)
    # 3 x (10 + 50) ms, and 10 + 20 of the batch cut at the end, of 3 s.
    assert read("loader_busy_pct") == pytest.approx(100 * 0.21 / 3.0)
    assert read("loader_stage_ms") == pytest.approx(50.0)   # whole ones
    # A later window takes other spans: from 102 s on, one batch and a cut.
    later = {"window": {"t_open": 102.0, "boundaries": [102.5, 103.0]}}
    assert read("loader_busy_pct", later) == pytest.approx(100 * 0.09 / 1.0)
    assert read("dispatch_self_ms", later) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NAMES)
def test_none_where_the_ring_dropped_a_span_the_reader_needs(ring, name):
    # The oldest spans went: set-up is no longer whole.
    ring.dropped = 5
    ring.spans = [s for s in ring.spans if s.end_ns > 47 * S]
    if name in ("trace_s", "lower_s", "xla_s"):
        assert read(name) is None
    else:
        # Spans are kept in the order they ended, so everything since the
        # window opened is still there: the window's readers still read.
        assert read(name) is not None
    # What is left begins inside the window: nobody reads.
    ring.spans = [s for s in ring.spans if s.end_ns > 101 * S]
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_where_the_program_has_no_ring_or_no_such_span(
        monkeypatch, name):
    monkeypatch.delattr(timeline, "ring")               # a parent commit
    assert read(name) is None
    empty = types.SimpleNamespace(dropped=0, snapshot=lambda: [])
    monkeypatch.setattr(timeline, "ring", empty, raising=False)
    assert read(name) is None


def test_the_union_counts_an_interval_once():
    from benchmark.metrics import _spans
    a = Span(1, 0, "x", 1, 0, 10 * S, None)
    b = Span(2, 1, "x", 1, 2 * S, 4 * S, None)          # inside a
    c = Span(3, 0, "x", 1, 8 * S, 12 * S, None)         # overlaps a's end
    d = Span(4, 0, "x", 1, 20 * S, 21 * S, None)        # apart
    assert _spans.covered_s([d, c, b, a]) == pytest.approx(13.0)
    assert _spans.under([a, b, c, d], ("x",)) == [b]
