"""The trace reduction: on a cut of a trace recorded on the chip, and on
hand-made events where the chip has given none yet."""

import os

import pytest

from benchmark import tracered as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "data", "gpt13b_1chip_v5e.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return tr.load_events(DATA)


def test_recorded_trace_devices_and_steps(recorded):
    assert tr.device_pids(recorded) == {3: "/device:TPU:0"}
    steps = tr.step_modules(recorded, 3, tr.thread_names(recorded))
    assert len(steps) == 3
    assert all(s["name"].startswith("jit_plain_one(") for s in steps)


def test_recorded_trace_busy_union_and_kernels(recorded):
    d = tr.reduce(recorded)["devices"][0]
    assert d["steps"] == 2
    assert d["window_s"] == pytest.approx(0.70263, rel=1e-4)   # 2 x 351.3 ms
    assert 0.9999 < d["busy_s"] / d["window_s"] <= 1.0
    assert d["collective_s"] == 0
    # 21 Pallas calls a step (7 layers x forward, dq, dk/dv), found by
    # their custom-call target; 1.08 ms a forward, 4.87 ms a backward.
    assert d["pallas_s"]["calls"] == 42
    assert d["pallas_s"]["fwd"] / 14 == pytest.approx(1.077e-3, rel=0.01)
    assert d["pallas_s"]["bwd"] / 14 == pytest.approx(4.872e-3, rel=0.01)
    # Self times add up to the busy time: nothing is counted twice.
    assert sum(d["op_self_s"].values()) == pytest.approx(d["busy_s"],
                                                         rel=1e-3)
    top = tr.breakdown({"devices": [d]})["device_ops"]
    assert len(top) == 10
    assert top[0][0] == "transpose(jvp())/dot_general [convolution fusion]"
    assert not any("fusion." in name for name, _ in top)


def test_recorded_trace_idle_gaps_by_host_span(recorded):
    steps = tr.step_modules(recorded, 3, tr.thread_names(recorded))
    ends = [tr.interval(s)[1] for s in steps]
    # A host clock 1000 s ahead of the trace's, reads 0.1-0.3 ms late.
    host_ends = [e + 1000.0 + late for e, late in zip(ends, (3e-4, 1e-4, 2e-4))]
    assert tr.host_offset(steps, host_ends) == pytest.approx(1000.0001)
    host = {"loss_read": [(tr.interval(s)[0] + 1000.0, e)
                          for s, e in zip(steps, host_ends)],
            "next_batch": [(0.0, 1.0)]}
    d = tr.reduce(recorded, host_spans=host,
                  host_step_ends=host_ends)["devices"][0]
    gaps = tr.breakdown({"devices": [d]})["idle_gaps"]
    assert gaps[0][0] == "loss_read"
    assert sum(s for _, s in gaps) == pytest.approx(
        d["window_s"] - d["busy_s"], rel=1e-6)


# --------------------------------------------------- hand-made events


def meta(pid, name, threads):
    out = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}]
    for tid, tname in threads.items():
        out.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                    "args": {"name": tname}})
    return out


def op(ts, dur, name, hlo, tid=3, pid=3, category="x"):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"long_name": f"%{name} = f32[4]{{0}} {hlo}",
                                   "hlo_category": category}}


def module(ts, dur, pid=3):
    return {"ph": "X", "pid": pid, "tid": 2, "ts": ts, "dur": dur,
            "name": "jit_step(1)", "args": {}}


THREADS = {2: "XLA Modules", 3: "XLA Ops", 4: "Async XLA Ops"}


def reduce_one(ops):
    events = meta(3, "/device:TPU:0", THREADS) + [
        module(0, 1000), module(1000, 1000)] + ops
    return tr.reduce(events)["devices"][0]


def test_opcode_from_hlo_text():
    assert tr.opcode(op(0, 1, "all-reduce.3", "all-reduce(f32[4]{0} %x)")
                     ) == "all-reduce"
    tuple_shape = {"name": "attn.2", "args": {"long_name":
                   "%attn.2 = (bf16[8,2]{1,0:T(8,128)(2,1)}, f32[8]{0}) "
                   'custom-call(bf16[8,2]{1,0} %f), custom_call_target='
                   '"tpu_custom_call"'}}
    assert tr.opcode(tuple_shape) == "custom-call"
    assert tr.is_pallas(tuple_shape)
    assert tr.opcode({"name": "fusion.12", "args": {}}) == "fusion"
    assert tr.is_collective(op(0, 1, "ar", "all-reduce-start(f32[4]{0} %x)"))
    assert not tr.is_collective(op(0, 1, "f", "fusion(f32[4]{0} %x)"))


def test_exposed_collective_no_compute_beside_it():
    d = reduce_one([op(0, 600, "fusion.1", "fusion(f32[4]{0} %x)"),
                    op(600, 300, "all-reduce.1", "all-reduce(f32[4]{0} %g)")])
    assert d["collective_s"] == pytest.approx(300e-6)
    assert d["collective_exposed_s"] == pytest.approx(300e-6)
    assert d["compute_s"] == pytest.approx(600e-6)
    assert d["busy_s"] == pytest.approx(900e-6)      # collective counts busy
    assert d["window_s"] == pytest.approx(1000e-6)


def test_overlapped_collective_on_the_async_thread():
    # In flight from 200 to 700 us on the async thread while a fusion runs
    # to 600: 100 us of it are exposed.
    d = reduce_one([op(0, 600, "fusion.1", "fusion(f32[4]{0} %x)"),
                    op(200, 500, "all-reduce.1", "all-reduce(f32[4]{0} %g)",
                       tid=4)])
    assert d["collective_s"] == pytest.approx(500e-6)
    assert d["collective_exposed_s"] == pytest.approx(100e-6)
    assert d["busy_s"] == pytest.approx(700e-6)


def test_start_done_pair_spans_the_stretch_between():
    # start at 100 (5 us), compute 105..400, done at 500..520: in flight
    # 100..520, of which 105..400 hidden.
    d = reduce_one([
        op(100, 5, "all-reduce-start.1", "all-reduce-start(f32[4]{0} %g)"),
        op(105, 295, "fusion.2", "fusion(f32[4]{0} %x)"),
        op(500, 20, "all-reduce-done.1", "all-reduce-done(f32[4]{0} %s)")])
    assert d["collective_s"] == pytest.approx(420e-6)
    assert d["collective_exposed_s"] == pytest.approx(125e-6)


def test_self_time_of_a_span_that_holds_others():
    d = reduce_one([op(0, 500, "while.1", "while(f32[4]{0} %c)"),
                    op(10, 100, "fusion.1", "fusion(f32[4]{0} %x)"),
                    op(200, 100, "fusion.2", "fusion(f32[4]{0} %x)")])
    assert d["op_self_s"]["while"] == pytest.approx(300e-6)
    assert d["op_self_s"]["fusion"] == pytest.approx(200e-6)
    assert d["compute_s"] == pytest.approx(200e-6)   # the holder is no work


def test_no_device_process_gives_nothing():
    assert tr.reduce(meta(701, "/host:CPU", {1: "main"})) is None


def test_interval_algebra():
    merged = tr.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert tr.total(merged) == 4
    assert tr.gaps(merged, (0, 10)) == [(3, 5), (6, 10)]
    assert tr.overlap(merged, [(2, 5.5)]) == pytest.approx(1.5)
    assert tr.clip(merged, (1, 5.5)) == [(1, 3), (5, 5.5)]
