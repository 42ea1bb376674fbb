"""The pinned scaling sub-leg's CPU split must keep SMT siblings
together: Linux enumerates one hyperthread per physical core first and
the siblings after, so a positional half-split would give both processes
one thread of EVERY physical core — measuring exactly the contention the
pinned leg exists to remove.  This path only executes on multi-core
hosts (the CI container allows one CPU), so it is covered by simulating
the sysfs topology."""

import builtins
import io
import os
import sys

import pytest


@pytest.fixture
def bench_mod():
    # bench.py lives at the repo root, which plain `pytest` does not put
    # on sys.path (tests/ has no __init__.py, so rootdir insertion
    # inserts tests/, not the root).
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    added = root not in sys.path
    if added:
        sys.path.insert(0, root)
    saved = sys.argv
    sys.argv = ["bench.py"]
    try:
        import bench
        yield bench
    finally:
        sys.argv = saved
        if added:
            sys.path.remove(root)


def _fake_topology(monkeypatch, bench, cpus, pkg_core_by_cpu):
    monkeypatch.setattr(bench.os, "sched_getaffinity",
                        lambda pid: set(cpus))
    pinned = {}
    monkeypatch.setattr(bench.os, "sched_setaffinity",
                        lambda pid, mask: pinned.update(mask={int(c) for c in mask}))

    real_open = builtins.open

    def fake_open(path, *a, **kw):
        p = str(path)
        if p.startswith("/sys/devices/system/cpu/cpu"):
            cpu = int(p.split("cpu")[2].split("/")[0])
            if cpu not in pkg_core_by_cpu:
                raise OSError(p)
            pkg, core = pkg_core_by_cpu[cpu]
            val = pkg if p.endswith("physical_package_id") else core
            return io.StringIO(str(val))
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", fake_open)
    return pinned


class TestPinCpuHalf:
    def test_smt_siblings_stay_together(self, monkeypatch, bench_mod):
        """4 physical cores x 2 threads, sibling-after enumeration
        (0-3 = thread 0 of cores 0-3, 4-7 = thread 1): each half must
        own 2 WHOLE cores (both threads), not one thread of all four."""
        topo = {c: (0, c % 4) for c in range(8)}
        pinned = _fake_topology(monkeypatch, bench_mod, range(8), topo)
        assert bench_mod._pin_cpu_half(0)
        h0 = pinned["mask"]
        assert bench_mod._pin_cpu_half(1)
        h1 = pinned["mask"]
        # Disjoint, exhaustive, equal budgets.
        assert h0 | h1 == set(range(8)) and not (h0 & h1)
        assert len(h0) == len(h1) == 4
        # Whole cores: a CPU and its sibling (c, c+4) always land together.
        for c in range(4):
            assert ({c, c + 4} <= h0) or ({c, c + 4} <= h1)

    def test_hybrid_topology_balances_cpu_counts(self, monkeypatch,
                                                 bench_mod):
        """2-thread P-cores + 1-thread E-cores (6 CPUs on 4 cores): the
        halves must get 3 CPUs each — a contiguous or group-count split
        would give 4/2 and the lockstep allreduce would report the
        starved half as data-plane cost."""
        topo = {0: (0, 0), 4: (0, 0), 1: (0, 1), 5: (0, 1),
                2: (0, 2), 3: (0, 3)}
        pinned = _fake_topology(monkeypatch, bench_mod,
                                [0, 1, 2, 3, 4, 5], topo)
        assert bench_mod._pin_cpu_half(0)
        h0 = pinned["mask"]
        assert bench_mod._pin_cpu_half(1)
        h1 = pinned["mask"]
        assert h0 | h1 == {0, 1, 2, 3, 4, 5} and not (h0 & h1)
        assert len(h0) == len(h1) == 3
        assert ({0, 4} <= h0) or ({0, 4} <= h1)   # siblings together
        assert ({1, 5} <= h0) or ({1, 5} <= h1)

    def test_odd_core_count_gives_process0_the_smaller_half(
            self, monkeypatch, bench_mod):
        """5 cores x 2 threads: whole cores cannot split 5/5 — the pinned
        1-process baseline (process 0) must get the SMALLER half, the
        same budget that paces the lockstep 2-process leg, so the
        efficiency ratio stays apples-to-apples."""
        topo = {c: (0, c % 5) for c in range(10)}
        pinned = _fake_topology(monkeypatch, bench_mod, range(10), topo)
        assert bench_mod._pin_cpu_half(0)
        h0 = pinned["mask"]
        assert bench_mod._pin_cpu_half(1)
        h1 = pinned["mask"]
        assert h0 | h1 == set(range(10)) and not (h0 & h1)
        assert len(h0) == 4 and len(h1) == 6
        for c in range(5):
            assert ({c, c + 5} <= h0) or ({c, c + 5} <= h1)

    def test_single_physical_core_refuses(self, monkeypatch, bench_mod):
        """2 CPUs that are SMT siblings of ONE core: no disjoint halves
        exist, the helper must refuse rather than split the core."""
        pinned = _fake_topology(monkeypatch, bench_mod, [0, 1],
                                {0: (0, 0), 1: (0, 0)})
        assert not bench_mod._pin_cpu_half(0)
        assert "mask" not in pinned

    def test_unreadable_topology_falls_back_positional(self, monkeypatch,
                                                       bench_mod):
        pinned = _fake_topology(monkeypatch, bench_mod, [0, 1, 2, 3], {})
        assert bench_mod._pin_cpu_half(0)
        h0 = pinned["mask"]
        assert bench_mod._pin_cpu_half(1)
        h1 = pinned["mask"]
        assert h0 | h1 == {0, 1, 2, 3} and not (h0 & h1)
        assert len(h0) == len(h1) == 2

    def test_one_cpu_noop(self, monkeypatch, bench_mod):
        pinned = _fake_topology(monkeypatch, bench_mod, [0], {0: (0, 0)})
        assert not bench_mod._pin_cpu_half(0)
        assert "mask" not in pinned


def test_the_device_legs_and_their_knobs_are_gone(bench_mod):
    """``bench.py`` is the host planes' drills: how fast the device path
    trains is ``benchmark/run.py``'s to say, so no knob of the legs that
    said it before the chip, and no virtual-device mode, is left."""
    import re
    source = open(bench_mod.__file__).read()
    assert not re.findall(
        r"BENCH_TLM_\w*|BENCH_SCALE_\w*|BENCH_SUMMARY_FILE", source)
    flags = {flag for action in bench_mod._parser()._actions
             for flag in action.option_strings}
    assert "--n-virtual" not in flags and "--no-transformer" not in flags
    assert {"--tcp-worker", "--recovery-worker", "--policy-worker",
            "--publish-worker", "--ctrl-worker", "--solo-worker",
            "--xport-worker"} <= flags
