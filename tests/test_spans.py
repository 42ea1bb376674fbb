"""The span ring of ``horovod_tpu.timeline`` and the host layers that
trace themselves into it: ``hvd.init()``, ``ShardedLoader``,
``make_train_step`` (its traces, lowering, compile, every dispatch).

The ring is the process's; a test reads the spans made after a mark it
takes (``since``), so tests that share a worker do not see each other's.
"""

import gc
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu import timeline
from horovod_tpu.data import ShardedLoader
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.timeline import SpanRing, ring, self_ns


def since(mark):
    """The process ring's spans that began after ``mark``."""
    return [s for s in ring.snapshot() if s.start_ns >= mark]


def named(spans, name):
    return [s for s in spans if s.name == name]


def ancestors(span, spans):
    by_id = {s.id: s for s in spans}
    out = []
    while span.parent in by_id:
        span = by_id[span.parent]
        out.append(span.name)
    return out


def counter(name):
    return registry.snapshot()["counters"].get(name, 0)


def seconds(spans):
    return sum(s.end_ns - s.start_ns for s in spans) / 1e9


# ------------------------------------------------------------ the ring


def test_parent_and_key_of_nested_spans_across_two_threads():
    r = SpanRing()
    started = threading.Event()
    release = threading.Event()

    def other():
        with r.span("other/outer", key=7):
            started.set()
            release.wait(5)
            with r.span("other/inner"):
                pass

    t = threading.Thread(target=other, name="the-other-thread")
    with r.span("main/outer", key=1) as outer:
        t.start()
        started.wait(5)
        with r.span("main/inner") as inner:
            with r.span("main/own_key", key=2) as own:
                pass
        release.set()
        t.join()
    assert (inner.parent, inner.key) == (outer.id, 1)     # key inherited
    assert (own.parent, own.key) == (inner.id, 2)
    spans = {s.name: s for s in r.snapshot()}
    # A span open on another thread is nobody's parent here.
    assert spans["other/outer"].parent == 0
    assert spans["other/inner"].parent == spans["other/outer"].id
    assert spans["other/inner"].key == 7
    assert spans["other/outer"].thread != spans["main/outer"].thread
    # Kept when closed: children stand before their parents.
    names = [s.name for s in r.snapshot() if s.name.startswith("main/")]
    assert names == ["main/own_key", "main/inner", "main/outer"]
    assert all(s.end_ns >= s.start_ns for s in r.snapshot())


def test_the_ring_is_bounded_and_counts_what_it_dropped():
    r = SpanRing(maxlen=8)
    for i in range(11):
        with r.span("s", key=i):
            pass
    kept = r.snapshot()
    assert len(kept) == 8 and r.dropped == 3
    assert [s.key for s in kept] == list(range(3, 11))    # oldest went
    r.clear()
    assert r.snapshot() == [] and r.dropped == 0
    assert ring._spans.maxlen == 16384


def test_self_time_is_the_span_less_what_its_children_cover():
    r = SpanRing()
    with r.span("outer") as outer:
        time.sleep(0.01)
        with r.span("child"):
            time.sleep(0.02)
        with r.span("child"):
            time.sleep(0.02)
    spans = r.snapshot()
    own = self_ns(spans)
    children = named(spans, "child")
    assert own[outer.id] == (outer.end_ns - outer.start_ns) - sum(
        c.end_ns - c.start_ns for c in children)
    assert 0.008 < own[outer.id] / 1e9 < 0.03
    assert all(own[c.id] == c.end_ns - c.start_ns for c in children)
    # Overlapping children are not counted twice.
    r2 = SpanRing()
    with r2.span("outer") as o:
        pass
    o_start = o.start_ns
    overlapping = [timeline.Span(1, 0, "outer", 1, o_start, o_start + 100, None),
                   timeline.Span(2, 1, "a", 1, o_start + 10, o_start + 60, None),
                   timeline.Span(3, 1, "b", 1, o_start + 40, o_start + 80, None)]
    assert self_ns(overlapping)[1] == 30


def test_a_span_added_after_the_fact_finds_its_parent_and_its_children():
    r = SpanRing()
    with r.span("step/lower", key=4) as lower:
        time.sleep(0.002)
        now = time.perf_counter_ns()
        inner = r.add("jax/trace", now - 500_000, now)           # reported first
        outer = r.add("jax/trace", now - 1_500_000, now + 1000)  # holds it
        began_before = r.add("jax/compile", lower.start_ns - 10_000_000, now)
    alone = r.add("jax/compile", lower.end_ns + 10, lower.end_ns + 20)
    spans = {s.id: s for s in r.snapshot()}
    assert spans[outer].parent == lower.id and spans[outer].key == 4
    assert spans[inner].parent == outer
    assert spans[began_before].parent == 0      # began before the span opened
    assert spans[alone].parent == 0 and spans[alone].key is None


def test_switched_off_the_ring_keeps_nothing_and_still_times_the_span():
    r = SpanRing()
    r.enabled = False
    with r.span("x") as x:
        time.sleep(0.001)
    assert r.add("y", 1, 2) is None
    assert r.snapshot() == [] and r.dropped == 0
    assert x.end_ns - x.start_ns >= 1_000_000
    r.enabled = True
    with r.span("x"):
        pass
    assert len(r.snapshot()) == 1


def test_events_are_chrome_trace_complete_events_with_thread_names():
    r = SpanRing()

    def work():
        with r.span("loader/stage", key=3):
            pass

    t = threading.Thread(target=work, name="horovod_tpu-data-prefetch")
    t.start()
    t.join()
    events = json.loads(json.dumps(r.events()))
    (x,) = [e for e in events if e["ph"] == "X"]
    assert x["name"] == "loader/stage" and x["args"]["key"] == 3
    assert x["dur"] >= 0 and x["ts"] > 0
    (m,) = [e for e in events if e.get("name") == "thread_name"]
    assert m["args"]["name"] == "horovod_tpu-data-prefetch"
    assert m["tid"] == x["tid"]
    # One process, named, under this process's id unless told another.
    (proc,) = [e for e in events if e.get("name") == "process_name"]
    assert proc["args"]["name"] == "host (horovod_tpu ring)"
    assert proc["pid"] == x["pid"] == os.getpid()
    # The shift sets the spans on another trace's clock: that much earlier,
    # the same length; the pid keeps them clear of its processes.
    (moved,) = [e for e in r.events(shift_ns=2_000_000, pid=7)
                if e["ph"] == "X"]
    assert moved["ts"] == pytest.approx(x["ts"] - 2_000.0)
    assert moved["dur"] == x["dur"] and moved["pid"] == 7


def test_a_full_collection_is_a_span_and_a_young_one_is_not():
    # A full collection first: the counts that earlier tests on this worker
    # left cannot then trip an automatic one inside the marked interval.
    gc.collect()
    mark = time.perf_counter_ns()
    gc.collect(0)
    assert named(since(mark), "host/gc") == []
    with ring.span("test/holds_the_collection") as holder:
        gc.collect()
    (full,) = named(since(mark), "host/gc")
    assert full.parent == holder.id


# ---------------------------------------------------------- the loader


def _batches(n, delay_s=0.0):
    for i in range(n):
        if delay_s:
            time.sleep(delay_s)
        yield (np.full((8, 4), float(i), np.float32),
               np.full((8,), i, np.int32))


def _loader_shares(spans):
    """The formula of the benchmark's ``loader_busy_pct`` over the whole
    life of one loader, and the share its thread spent ahead."""
    source, stage = named(spans, "loader/source"), named(spans, "loader/stage")
    put_wait = named(spans, "loader/put_wait")
    producer = source + stage + put_wait
    life = (max(s.end_ns for s in producer)
            - min(s.start_ns for s in producer)) / 1e9
    return (seconds(source + stage) / life, seconds(put_wait) / life)


def test_a_slow_source_reads_the_loader_busy_and_the_consumer_starved(hvd):
    mesh = hvd.ranks_mesh()
    before = {n: counter(n) for n in ("loader.batches", "loader.bytes",
                                      "loader.starved")}
    mark = time.perf_counter_ns()
    out = list(ShardedLoader(_batches(12, delay_s=0.02), mesh))
    assert len(out) == 12
    spans = since(mark)
    busy, ahead = _loader_shares(spans)
    assert busy > 0.9 and ahead < 0.1
    # Every span of one batch carries its ordinal, on both threads.
    for name in ("loader/source", "loader/stage", "loader/put_wait"):
        assert [s.key for s in named(spans, name)][:12] == list(range(12))
    waits = named(spans, "loader/get_wait")
    assert [s.key for s in waits] == list(range(13))     # 12 + the end
    assert {s.thread for s in waits}.isdisjoint(
        {s.thread for s in named(spans, "loader/stage")})
    assert seconds(waits) > 0.2                          # it waited for all
    assert counter("loader.batches") - before["loader.batches"] == 12
    assert counter("loader.bytes") - before["loader.bytes"] == 12 * (
        8 * 4 * 4 + 8 * 4)
    assert counter("loader.starved") - before["loader.starved"] >= 10


def test_a_slow_consumer_reads_the_loader_idle_and_put_wait_the_rest(hvd):
    mesh = hvd.ranks_mesh()
    starved = counter("loader.starved")
    mark = time.perf_counter_ns()
    n = 0
    for _ in ShardedLoader(_batches(10), mesh, prefetch=2):
        time.sleep(0.03)
        n += 1
    assert n == 10
    spans = since(mark)
    busy, ahead = _loader_shares(spans)
    assert busy < 0.2 and ahead > 0.8
    # One span over a whole wait, not one a 0.1 s poll of the queue.
    assert len(named(spans, "loader/put_wait")) == 11    # 10 + the end
    assert counter("loader.starved") - starved <= 2      # the first get(s)
    assert registry.snapshot()["gauges"]["loader.queue_depth"] >= 1


def test_stacked_batches_share_the_ordinal_of_the_call_they_feed(hvd):
    mesh = hvd.ranks_mesh()
    mark = time.perf_counter_ns()
    out = list(ShardedLoader(_batches(7), mesh, steps_per_call=2))
    assert len(out) == 3                                 # 1 dropped
    spans = since(mark)
    assert [s.key for s in named(spans, "loader/stage")] == [0, 1, 2]
    assert [s.key for s in named(spans, "loader/source")] == [
        0, 0, 1, 1, 2, 2, 3, 3]                          # the last: the end


# ------------------------------------------------------ the step builder


def _problem(rows=8):
    rng = np.random.RandomState(0)
    x = rng.randn(rows, 16).astype(np.float32)
    return ({"w": jnp.zeros((16, 4))},
            (jnp.asarray(x), jnp.asarray(x[:, :4])))


def _loss_fn(params, aux, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), aux


@pytest.fixture()
def one_device_mesh(hvd):
    return Mesh(np.asarray(jax.devices()[:1]), ("ranks",))


def test_set_up_on_one_device_is_three_traces_a_lowering_and_a_compile(
        one_device_mesh):
    params, batch = _problem()
    tx = optax.sgd(0.05)
    opt_state = tx.init(params)
    compiles = counter("step.compiles")
    steps = counter("injit.steps")
    mark = time.perf_counter_ns()
    step = make_train_step(_loss_fn, tx, one_device_mesh, donate=False)
    step.lower(params, {}, opt_state, batch)
    for _ in range(3):
        params, _, opt_state, loss = step(params, {}, opt_state, batch)
    jax.block_until_ready(loss)
    spans = since(mark)

    (resolve,) = named(spans, "step/resolve")
    (spmd,) = named(spans, "step/trace_spmd")
    (plain,) = named(spans, "step/trace_plain")
    assert spmd.parent == plain.parent == resolve.id
    assert spmd.end_ns <= plain.start_ns
    (lower,) = named(spans, "step/lower")
    (first,) = named(spans, "step/first_call")
    assert first.key == 0 and lower.end_ns <= first.start_ns

    def under(name, holders):
        return [s for s in named(spans, name)
                if set(ancestors(s, spans)) & set(holders)]

    assert under("jax/trace", ["step/trace_spmd"])
    assert under("jax/trace", ["step/trace_plain"])
    assert under("jax/trace", ["step/lower", "step/first_call"])
    assert len(under("jax/lower", ["step/lower", "step/first_call"])) == 1
    (compiled,) = under("jax/compile", ["step/lower", "step/first_call"])
    assert compiled.key == 0
    assert "step/enqueue" in ancestors(compiled, spans)
    # Every call: step/dispatch over step/enqueue, keyed by its ordinal.
    calls = named(spans, "step/dispatch")
    assert [s.key for s in calls] == [1, 2]
    for call in calls:
        (enqueue,) = [s for s in named(spans, "step/enqueue")
                      if s.parent == call.id]
        assert enqueue.key == call.key
        assert self_ns(spans)[call.id] == (
            call.end_ns - call.start_ns) - (enqueue.end_ns - enqueue.start_ns)
    assert counter("step.compiles") - compiles == 1
    assert counter("injit.steps") - steps == 3           # on one device too


def test_a_jit_compiled_outside_any_program_span_has_no_step_ancestor(hvd):
    timeline.listen_to_jax()
    compiles = counter("step.compiles")
    x = jnp.ones((5,))
    mark = time.perf_counter_ns()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    spans = since(mark)
    (compiled,) = named(spans, "jax/compile")
    assert compiled.parent == 0 and compiled.key is None
    assert all(not any(a.startswith("step/") for a in ancestors(s, spans))
               for s in spans if s.name.startswith("jax/"))
    assert counter("step.compiles") == compiles


def test_a_second_batch_shape_is_a_recompilation_with_its_call_ordinal(
        one_device_mesh):
    params, batch = _problem()
    _, wider = _problem(rows=12)
    tx = optax.sgd(0.05)
    opt_state = tx.init(params)
    compiles = counter("step.compiles")
    mark = time.perf_counter_ns()
    step = make_train_step(_loss_fn, tx, one_device_mesh, donate=False)
    for b in (batch, batch, wider, wider):
        params, _, opt_state, loss = step(params, {}, opt_state, b)
    spans = since(mark)
    assert counter("step.compiles") - compiles == 2
    keys = sorted(s.key for s in named(spans, "jax/compile")
                  if any(a.startswith("step/") for a in ancestors(s, spans)))
    assert keys == [0, 2]
    (again,) = [s for s in named(spans, "jax/compile") if s.key == 2]
    assert "step/dispatch" in ancestors(again, spans)


def test_a_cache_read_is_the_child_of_the_compile_it_is_part_of(
        tmp_path, one_device_mesh):
    """jax times ``compile_or_get_cached`` as a whole under the compile
    event (``jax/_src/compiler.py``), so with a warm persistent cache the
    ring holds ``jax/cache_read`` inside ``jax/compile``, never beside
    it: ``xla_s`` adds the compile spans alone."""
    from jax.experimental.compilation_cache import compilation_cache

    def build_and_call():
        # A new function object each time: jax's in-memory caches miss,
        # the persistent one is asked.
        params, batch = _problem()
        tx = optax.sgd(0.05)
        step = make_train_step(lambda p, a, b: _loss_fn(p, a, b), tx,
                               one_device_mesh, donate=False)
        mark = time.perf_counter_ns()
        step(params, {}, tx.init(params), batch)[-1].block_until_ready()
        return since(mark)

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        cold = build_and_call()
        assert not [s for s in named(cold, "jax/cache_read")
                    if "step/first_call" in ancestors(s, cold)]
        warm = build_and_call()
        reads = [s for s in named(warm, "jax/cache_read")
                 if "step/first_call" in ancestors(s, warm)]
        assert reads
        by_id = {s.id: s for s in warm}
        for read in reads:
            holder = by_id[read.parent]
            assert holder.name == "jax/compile"
            assert holder.start_ns <= read.start_ns + ring.SLACK_NS
            assert read.end_ns <= holder.end_ns
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_on_a_mesh_of_devices_there_is_no_resolve_and_steps_are_counted(hvd):
    mesh = hvd.ranks_mesh()
    params, batch = _problem(rows=2 * mesh.size)
    tx = optax.sgd(0.05)
    opt_state = tx.init(params)
    steps = counter("injit.steps")
    mark = time.perf_counter_ns()
    step = make_train_step(_loss_fn, tx, mesh, donate=False)
    compiled = step.lower(params, {}, opt_state, batch).compile()
    params, _, opt_state, loss = compiled(params, {}, opt_state, batch)
    params, _, opt_state, loss = step(params, {}, opt_state, batch)
    jax.block_until_ready(loss)
    spans = since(mark)
    assert not named(spans, "step/resolve")
    assert len(named(spans, "step/lower")) == 1
    # The compiled executable keeps the instrumented dispatch: the same
    # ordinals, the same enqueue span below.
    assert [s.key for s in named(spans, "step/first_call")] == [0]
    assert [s.key for s in named(spans, "step/dispatch")] == [1]
    assert len(named(spans, "step/enqueue")) == 2
    assert counter("injit.steps") - steps == 2


def test_timeline_lane_and_observatory_read_the_one_dispatch_span(
        hvd, tmp_path, one_device_mesh):
    """One pair of clock reads a dispatch: the ``DISPATCH`` event of the
    timeline and the observatory's stall are the ring's span."""
    from horovod_tpu import basics, observe
    from horovod_tpu.timeline import Timeline

    path = tmp_path / "timeline.json"
    controller = basics._state.controller
    assert controller.timeline is None
    timeline_file = controller.timeline = Timeline(str(path))
    was_enabled = observe.enabled()
    observe.set_enabled(True)
    before = registry.snapshot()["histograms"].get(
        "step.stall_seconds", {"count": 0, "sum": 0.0})
    try:
        params, batch = _problem()
        tx = optax.sgd(0.05)
        opt_state = tx.init(params)
        mark = time.perf_counter_ns()
        step = make_train_step(_loss_fn, tx, one_device_mesh, donate=False)
        for _ in range(4):
            params, _, opt_state, loss = step(params, {}, opt_state, batch)
        jax.block_until_ready(loss)
        time.sleep(0.3)         # the watcher stamps the last EXECUTE end
    finally:
        observe.set_enabled(was_enabled)
        controller.timeline = None
        timeline_file.close()
    spans = since(mark)
    calls = named(spans, "step/first_call") + named(spans, "step/dispatch")
    events = json.loads(path.read_text())
    dispatched = [e for e in events if e.get("name") == "DISPATCH"]
    assert len(dispatched) == 4 and all(e["ph"] == "X" for e in dispatched)
    assert [e["dur"] for e in dispatched] == [
        (s.end_ns - s.start_ns) // 1000 for s in calls]
    assert [e["ts"] for e in dispatched] == [
        (s.start_ns - timeline_file._t0_ns) // 1000 for s in calls]
    # The observatory: a step from the second call on, its stall the span.
    after = registry.snapshot()["histograms"]["step.stall_seconds"]
    assert after["count"] - before["count"] == 3
    assert after["sum"] - before["sum"] == pytest.approx(
        seconds(calls[1:]), rel=1e-6)
