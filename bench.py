"""Host-plane drills — prints ONE JSON line.

``python bench.py`` runs the drills of the planes that live on the host
and touches no accelerator: every process it starts pins jax to the CPU
platform.

* ``scaling_tcp_2proc`` (``BENCH_SCALING=0`` skips it): the same worker
  loop at 1 process and at 2 processes under the ``horovod_tpu.run``
  launcher — the real cross-process eager data plane (negotiation +
  payload over the native ring), with its wire-compression, overlap and
  observatory A/Bs, the allreduce-algorithm and transport sweeps, the
  response cache's counters, and, each behind its own switch, the
  recovery (``BENCH_RECOVERY``), fleet-policy (``BENCH_POLICY``) and
  publish-while-training (``BENCH_PUBLISH``) drills;
* ``ctrl_sweep`` (``BENCH_CTRL=0`` skips it): the flat-vs-hier
  negotiation tick at 8/32/128 loopback processes.

These are host speeds and stand beside no device metric.  How fast the
device path trains is ``benchmark/run.py``'s to say (``BENCHMARK.json``;
numbers in ``PERF_LEDGER.jsonl`` and ``PERF.md``); docs/benchmarks.md
describes both.  The ``--*-worker`` flags are the drills' own
subprocesses (and what ``tests/`` and the chaos drills start).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _cpu_jax():
    """jax pinned to the CPU platform, persistent compile cache on — how
    every worker subprocess of this file starts.  The parent may hold the
    chip, and a chip belongs to one process."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu import compile_cache
    compile_cache.enable()
    return jax


def _pin_cpu_half(half: int) -> bool:
    """Pin this process to one half of the allowed CPUs (BENCH_TCP_PIN
    legs).  Must run BEFORE jax initializes its thread pools.  Returns
    False (no-op) when affinity is unsupported or <2 CPUs.

    The split keeps SMT siblings TOGETHER: Linux typically enumerates
    one hyperthread per physical core first and the siblings after, so
    a naive first-half/second-half cut would hand both processes the
    same physical cores (each owning one thread of every core) — the
    exact contention the pinned leg exists to remove.  CPUs are grouped
    by (package, core) id from sysfs and whole cores are dealt greedily
    (largest group to the lighter half) so the halves get CPU counts as
    equal as whole cores allow — a group-count or contiguous split
    would starve one half on a hybrid host (2-thread P-cores + 1-thread
    E-cores) and the lockstep allreduce would report the asymmetry as
    data-plane cost.  Unreadable topology degrades to single-CPU groups
    (positional dealing)."""
    try:
        cpus = sorted(os.sched_getaffinity(0))
    except AttributeError:          # non-Linux
        return False
    groups = _cpu_core_groups(cpus)
    if len(groups) < 2:
        return False   # a single physical core cannot give disjoint halves
    bins, counts = ([], []), [0, 0]
    for g in sorted(groups, key=len, reverse=True):
        i = 0 if counts[0] <= counts[1] else 1
        bins[i].append(g)
        counts[i] += len(g)
    # When whole cores cannot split evenly (odd core count), hand the
    # SMALLER half to process 0: the pinned 1-process baseline runs as
    # process 0, and the lockstep 2-process leg is paced by its slowest
    # rank — giving both the same (bottleneck) budget keeps the
    # efficiency ratio an apples-to-apples data-plane measurement
    # instead of blaming the core asymmetry on the wire.
    if counts[1] < counts[0]:
        bins = (bins[1], bins[0])
    chosen = bins[half % 2]
    os.sched_setaffinity(0, {c for g in chosen for c in g})
    return True


def _cpu_core_groups(cpus):
    """Allowed CPUs grouped by physical core ((package, core) id from
    sysfs), sorted; single-CPU groups positionally when the topology is
    unreadable.  Shared by the pin helper and the parent's can-we-pin
    gate so they can never disagree."""
    if len(cpus) < 2:
        return [[c] for c in cpus]

    def core_key(c):
        base = f"/sys/devices/system/cpu/cpu{c}/topology"
        try:
            with open(f"{base}/physical_package_id") as f:
                pkg = int(f.read())
            with open(f"{base}/core_id") as f:
                core = int(f.read())
            return (pkg, core)
        except (OSError, ValueError):
            return None

    keys = {c: core_key(c) for c in cpus}
    if any(k is None for k in keys.values()):
        return [[c] for c in cpus]                   # positional fallback
    by_core = {}
    for c in cpus:
        by_core.setdefault(keys[c], []).append(c)
    return [by_core[k] for k in sorted(by_core)]


def tcp_worker():
    """2-process disjoint-runtime worker (spawned by ``horovod_tpu.run``
    under :func:`bench_scaling_tcp`): a small conv training loop whose
    gradient sync takes the EAGER path — negotiation + payload over the
    native TCP ring, the configuration a real multi-host eager job uses.
    Prints one JSON line on rank 0 with per-process throughput and the
    directly measured communication fraction (wall time inside
    ``allreduce_gradients`` over wall time of the whole step — the
    profiler cannot provide this on the CPU backend, which exposes no
    device-side spans).

    With ``BENCH_TCP_PIN=1`` each process pins itself to a disjoint CPU
    half before JAX spins up (the pinned leg: contention replaced by a
    fixed half-machine budget); the TCPLEG line reports whether the pin
    actually took, so the parent never mistakes an unpinnable host's
    numbers for pinned ones."""
    pinned = False
    if os.environ.get("BENCH_TCP_PIN") == "1":
        pinned = _pin_cpu_half(
            int(os.environ.get("HOROVOD_TPU_PROCESS_INDEX", "0")))
    jax = _cpu_jax()
    import numpy as np

    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax

    # Pin the headline phases to the flat ring so their numbers keep the
    # same meaning across runs regardless of the auto-selection default
    # (small payloads would otherwise route to the latency path).  The
    # algo sweep below flips this deliberately, one phase at a time.
    os.environ["HOROVOD_TPU_ALLREDUCE_ALGO"] = "ring"

    hvd.init()
    n = hvd.process_count()
    batch, iters, params, tx, grads_fn, apply_fn = _conv_leg_setup(
        seed=hvd.rank())
    params = hvd_jax.broadcast_parameters(params)
    opt_state = tx.init(params)

    # warmup/compile
    for _ in range(2):
        loss, grads = grads_fn(params)
        grads = hvd_jax.allreduce_gradients(grads)
        params, opt_state = apply_fn(params, opt_state, grads)
    np.asarray(loss)

    from horovod_tpu import basics
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.compression import Compression
    control = getattr(basics.controller(), "_control", None)

    def _wire_bytes(wire):
        """Per-dtype bytes-on-wire from the unified metrics registry —
        the same counters the JSONL/Prometheus exporters publish, so the
        bench numbers and the live telemetry can never disagree.
        ``wire=None`` sums every wire (the autopilot leg's traffic moves
        between dtypes as the ladder climbs)."""
        c = hvd_metrics.snapshot().get("counters", {})
        if wire is None:
            return (sum(v for k, v in c.items()
                        if k.startswith("ring.allreduce.bytes_sent#wire=")),
                    sum(v for k, v in c.items()
                        if k.startswith("ring.allreduce.bytes_recv#wire=")))
        return (c.get(f"ring.allreduce.bytes_sent#wire={wire}", 0),
                c.get(f"ring.allreduce.bytes_recv#wire={wire}", 0))

    def measured_loop(params, opt_state, compression, wire,
                      name_prefix="DistributedOptimizer.grads"):
        """One timed window of the training loop; returns throughput,
        comm fraction, and the data-plane bytes that actually rode the
        ring wire (compressed bytes when a wire dtype is active)."""
        s0, r0 = _wire_bytes(wire)
        t_comm = 0.0
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, grads = grads_fn(params)
            jax.block_until_ready(grads)
            c0 = time.perf_counter()
            grads = hvd_jax.allreduce_gradients(grads,
                                                compression=compression,
                                                name_prefix=name_prefix)
            jax.block_until_ready(grads)
            t_comm += time.perf_counter() - c0
            params, opt_state = apply_fn(params, opt_state, grads)
        np.asarray(loss)
        dt = time.perf_counter() - t0
        s1, r1 = _wire_bytes(wire)
        return params, opt_state, dt, t_comm, s1 - s0, r1 - r0

    # fp32 ring leg first (the headline numbers keep their meaning), then
    # the same loop per compressed wire: bytes-on-wire from the data-plane
    # counters, comm_fraction, and the allreduce's max error vs the fp32
    # ring on a fixed gradient tree.
    wire_stats = {}
    raw_sent = None
    for wire, comp in (("fp32", Compression.none),
                       ("bf16", Compression.bf16),
                       ("int8", Compression.int8)):
        params, opt_state, dt, t_comm, sent, recvd = measured_loop(
            params, opt_state, comp, wire)
        stats = {
            "images_per_sec_per_proc": round(batch * iters / dt, 2),
            "step_time_ms": round(dt / iters * 1e3, 2),
            "comm_fraction": round(t_comm / dt, 4),
            "bytes_on_wire_sent": sent,
            "bytes_on_wire_recvd": recvd,
        }
        if wire == "fp32":
            raw_sent, dt_raw, t_comm_raw = sent, dt, t_comm
        elif raw_sent:
            stats["bytes_ratio_vs_fp32"] = round(sent / raw_sent, 4)
            stats["faster_than_fp32"] = dt < dt_raw
        wire_stats[wire] = stats

    # Autopilot leg (compression="auto", HOROVOD_TPU_PRECISION=auto):
    # requests go out RAW with measured residual reports riding the
    # request wire's precision ext; the coordinator climbs the ladder per
    # bucket and stamps the negotiated dtype.  Runs LAST and under its
    # own tensor names so a promoted auto bucket can never collide with
    # the static legs' raw fp32 requests.  Headline: step time within 5%
    # of the best static wire above.
    from horovod_tpu import precision as _hvd_precision
    if _hvd_precision.get_autopilot().enabled:
        for _ in range(3):   # warmup: let the ladder climb pre-window
            loss, grads = grads_fn(params)
            grads = hvd_jax.allreduce_gradients(
                grads, compression="auto", name_prefix="auto.grads")
            params, opt_state = apply_fn(params, opt_state, grads)
        np.asarray(loss)
        params, opt_state, dt, t_comm, sent, recvd = measured_loop(
            params, opt_state, "auto", None, name_prefix="auto.grads")
        auto_stats = {
            "images_per_sec_per_proc": round(batch * iters / dt, 2),
            "step_time_ms": round(dt / iters * 1e3, 2),
            "comm_fraction": round(t_comm / dt, 4),
            "bytes_on_wire_sent": sent,
            "bytes_on_wire_recvd": recvd,
        }
        best_static = min((w["step_time_ms"] for w in wire_stats.values()
                           if "step_time_ms" in w), default=None)
        if best_static:
            auto_stats["vs_best_static"] = round(
                auto_stats["step_time_ms"] / best_static, 4)
        wire_stats["auto"] = auto_stats

    # Overlap A/B: the same loop with the bucketed-overlap scheduler off
    # (per-leaf allreduce after backward fully materializes) and on
    # (bucketed allreduces issued the moment each bucket's last gradient
    # lands, docs/concepts.md "Scheduler and overlap").  The ON leg's
    # comm_fraction counts only *exposed* communication — comm hidden
    # under backward is not time the step waited for — with the
    # hidden/exposed split read off the overlap.* histograms so the
    # bench and the live telemetry can never disagree.
    def _overlap_ab(p, s):
        results = {}
        for mode, ov in (("off", False), ("on", True)):
            # Warm outside the window: bucket planning + first-use
            # negotiation of the leg's tensor names.
            loss, grads = grads_fn(p)
            grads = hvd_jax.allreduce_gradients(
                grads, overlap=ov, name_prefix=f"olab.{mode}")
            p, s = apply_fn(p, s, grads)
            h0 = hvd_metrics.snapshot().get("histograms", {})
            t_comm = 0.0
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, grads = grads_fn(p)
                if not ov:
                    jax.block_until_ready(grads)
                c0 = time.perf_counter()
                grads = hvd_jax.allreduce_gradients(
                    grads, overlap=ov, name_prefix=f"olab.{mode}")
                jax.block_until_ready(grads)
                t_comm += time.perf_counter() - c0
                p, s = apply_fn(p, s, grads)
            np.asarray(loss)
            dt = time.perf_counter() - t0
            h1 = hvd_metrics.snapshot().get("histograms", {})

            def _dsum(nm):
                return ((h1.get(nm) or {}).get("sum", 0.0)
                        - (h0.get(nm) or {}).get("sum", 0.0))

            exposed = _dsum("overlap.exposed_seconds")
            results[mode] = {
                "step_time_ms": round(dt / iters * 1e3, 2),
                "comm_fraction": round((exposed if ov else t_comm) / dt, 4),
                "hidden_comm_seconds": round(
                    _dsum("overlap.hidden_seconds"), 6),
                "exposed_comm_seconds": round(exposed, 6),
            }
        return results

    overlap_ab = _overlap_ab(params, opt_state)

    # Observatory A/B: the identical fp32 ring loop with the per-hop
    # transfer telemetry (XferScope at every SendFrame/RecvFrame/
    # DuplexTransfer on this leg) off and on, flipped at runtime through
    # the native toggle.  The ON/OFF step-time ratio is the observatory's
    # whole hot-path cost — the acceptance budget is ≤2%
    # (docs/observability.md "Observatory").
    def _observe_ab(p, s):
        from horovod_tpu import observe as hvd_observe
        was = hvd_observe.enabled()
        results = {}
        for mode in ("off", "on"):
            hvd_observe.set_enabled(mode == "on")
            # Warm outside the window (compile + negotiation are shared
            # with earlier phases, but keep the twin legs symmetric).
            loss, grads = grads_fn(p)
            grads = hvd_jax.allreduce_gradients(grads)
            p, s = apply_fn(p, s, grads)
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, grads = grads_fn(p)
                jax.block_until_ready(grads)
                grads = hvd_jax.allreduce_gradients(grads)
                jax.block_until_ready(grads)
                p, s = apply_fn(p, s, grads)
            np.asarray(loss)
            dt = time.perf_counter() - t0
            results[mode] = {"step_time_ms": round(dt / iters * 1e3, 2)}
        hvd_observe.set_enabled(was)
        off = results["off"]["step_time_ms"]
        on = results["on"]["step_time_ms"]
        results["overhead_fraction"] = (round((on - off) / off, 4)
                                        if off else None)
        return results

    observe_ab = _observe_ab(params, opt_state)

    # Accuracy: one fixed per-process payload through each wire vs the
    # fp32 ring (max abs error over the payload scale — the ring-level
    # analogue of the codec unit tests).  A synthetic normal vector, not
    # the live gradients: the toy loss converges within the measured
    # windows and its gradients underflow to zero, which would make every
    # wire look exact.
    nelems = sum(int(np.size(g)) for g in jax.tree.leaves(params))
    flat = np.random.default_rng(1000 + hvd.process_index()).standard_normal(
        nelems).astype(np.float32)
    ref = np.asarray(hvd.allreduce(flat, average=False, name="wire.ref",
                                   compression="none"))
    scale = float(np.max(np.abs(ref))) or 1.0
    for wire in ("bf16", "int8"):
        out = np.asarray(hvd.allreduce(flat, average=False,
                                       name=f"wire.{wire}",
                                       compression=wire))
        wire_stats[wire]["allreduce_max_err_vs_fp32"] = float(
            f"{np.max(np.abs(out - ref)) / scale:.3e}")

    # Algorithm sweep: per-size p50 allreduce latency for each data-plane
    # algorithm.  The algorithm preference is read from the environment
    # per enqueue and rides the negotiated request, so flipping the env at
    # the same phase point on every process keeps the preference uniform.
    # On this 2-process single-host leg "hier" degenerates to the
    # intra-host fan-in/fan-out legs (one leader, no inter-host ring) —
    # still a distinct data path from the flat ring.  The reported
    # crossover is the largest payload where the latency path still beats
    # the ring; compare it against the configured
    # HOROVOD_TPU_ALLREDUCE_CROSSOVER (docs/benchmarks.md).
    def _algo_probe(reps=7):
        from horovod_tpu.core import algo_crossover_bytes
        sizes = [256, 1024, 4096, 16384, 65536, 262144, 1048576]  # elems
        sweep = {"sizes_bytes": [s * 4 for s in sizes], "algos": {}}
        def _plane_bytes():
            c = hvd_metrics.snapshot().get("counters", {})
            return (sum(v for k, v in c.items()
                        if k.startswith("ring.allreduce.bytes_sent#wire=")),
                    c.get("ring.hier_local.bytes_sent", 0))

        for algo in ("ring", "small", "hier"):
            os.environ["HOROVOD_TPU_ALLREDUCE_ALGO"] = algo
            w0, l0 = _plane_bytes()
            medians = []
            for n_el in sizes:
                payload = np.ones(n_el, np.float32)
                # warm: first hier/small call bootstraps the host-group
                # sockets; a reused name lets later reps ride the
                # response cache so negotiation noise stays off the
                # data-plane timing.
                hvd.allreduce(payload, average=False,
                              name=f"algoprobe.{algo}.{n_el}")
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    hvd.allreduce(payload, average=False,
                                  name=f"algoprobe.{algo}.{n_el}")
                    ts.append(time.perf_counter() - t0)
                medians.append(round(sorted(ts)[len(ts) // 2] * 1e6, 1))
            w1, l1 = _plane_bytes()
            # Ring-wire vs intra-host bytes during this algo's phase:
            # hier routes member traffic off the (inter-host) ring wire
            # onto the raw local legs — by ~local_size on a real pod.
            sweep["algos"][algo] = {"p50_us": medians,
                                    "ring_wire_bytes": w1 - w0,
                                    "hier_local_bytes": l1 - l0}
        os.environ["HOROVOD_TPU_ALLREDUCE_ALGO"] = "ring"
        crossover = 0
        for sz, s_us, r_us in zip(sweep["sizes_bytes"],
                                  sweep["algos"]["small"]["p50_us"],
                                  sweep["algos"]["ring"]["p50_us"]):
            if s_us <= r_us:
                crossover = sz
        sweep["measured_crossover_bytes"] = crossover
        sweep["configured_crossover_bytes"] = algo_crossover_bytes()
        return sweep

    algo_sweep = _algo_probe()

    # Response-cache probe: repeated negotiation of a fixed set of small
    # named tensors.  The first burst pays full negotiation (every name
    # rides the wire as a serialized Request; the fused responses are
    # built and broadcast); once every rank's slot bits agree, the
    # coordinator replays the stored response set and each burst moves a
    # fixed-size bitvector + mini-frame instead.  Per-burst deltas come
    # off the coordinator's registry (rank 0 is process 0 here), so the
    # bench numbers and the live telemetry can never disagree.
    # Burst sizing: the whole set must enqueue within one controller
    # cycle (1 ms) on both processes, or the ramp's slot assignment —
    # which requires every process to contribute a name in the SAME
    # tick — straggles across ticks and never completes.  64 tiny
    # enqueues fit comfortably; the burst count covers the full ramp
    # (full negotiation → bits + store → served) with steady-state room.
    def _cache_probe(n_names=64, bursts=32):
        def counters():
            return hvd_metrics.snapshot().get("counters", {})

        def tick_hists():
            h = hvd_metrics.snapshot().get("histograms", {})
            return (h.get("control.tick_seconds#cached=0"),
                    h.get("control.tick_seconds#cached=1"))

        def hist_delta(h1, h0):
            """Probe-window view of a cumulative histogram: subtract the
            pre-probe snapshot so earlier phases' ticks don't drown the
            burst latencies."""
            if not h1:
                return None
            if not h0:
                return h1
            return {"bounds": h1["bounds"],
                    "counts": [a - b
                               for a, b in zip(h1["counts"], h0["counts"])],
                    "sum": h1["sum"] - h0["sum"],
                    "count": h1["count"] - h0["count"]}

        h_uncached0, h_cached0 = tick_hists()
        payload = np.ones(8, np.float32)
        per_burst = []
        for _ in range(bursts):
            c0 = counters()
            handles = [hvd.allreduce_async(payload, average=False,
                                           name=f"cacheprobe.{j}")
                       for j in range(n_names)]
            for h in handles:
                hvd.synchronize(h)
            c1 = counters()
            per_burst.append({
                k: c1.get(f"control.{k}", 0) - c0.get(f"control.{k}", 0)
                for k in ("negotiation_bytes", "ticks", "cache_hits",
                          "cache_misses")})

        def hist_stats(h):
            """Approximate median (upper bound of the bucket holding the
            midpoint) + mean from a fixed-bucket histogram snapshot."""
            if not h or not h.get("count"):
                return None
            bounds, counts = h["bounds"], h["counts"]
            half, acc, median = h["count"] / 2.0, 0, bounds[-1]
            for k, cnt in enumerate(counts):
                acc += cnt
                if acc >= half:
                    median = bounds[min(k, len(bounds) - 1)]
                    break
            return {"count": h["count"], "median_le_s": median,
                    "mean_s": round(h["sum"] / h["count"], 9)}

        h_uncached1, h_cached1 = tick_hists()
        uncached_b = per_burst[0]["negotiation_bytes"]
        # Best burst past the two ramp bursts (assign, then store): a
        # tick-aligned steady-state burst is pure bitvector + mini-frame.
        # Bursts whose two processes straddle a tick boundary fall back
        # to compressed-request negotiation (correct, just not served) —
        # min() reports the fast path the aligned bursts actually rode,
        # with the full per-burst list alongside for the distribution.
        cached_b = min(b["negotiation_bytes"] for b in per_burst[2:])
        return {
            "names_per_burst": n_names,
            "bursts": per_burst,
            "uncached_burst_negotiation_bytes": uncached_b,
            "cached_burst_negotiation_bytes": cached_b,
            "negotiation_bytes_ratio": (round(uncached_b / cached_b, 2)
                                        if cached_b else None),
            "tick_seconds_uncached": hist_stats(
                hist_delta(h_uncached1, h_uncached0)),
            "tick_seconds_cached": hist_stats(
                hist_delta(h_cached1, h_cached0)),
        }

    from horovod_tpu.core import cache_capacity_from_env
    cache_stats = None
    if control is not None:
        probe = _cache_probe()
        if hvd.rank() == 0:
            cache_stats = probe
            cache_stats["capacity"] = cache_capacity_from_env()

    if hvd.rank() == 0:
        transport = (control.ring_transport()
                     if control is not None
                     and hasattr(control, "ring_transport") else "none")
        snap = hvd.metrics()

        def _straggler_skew():
            # Per-rank gather-arrival skew from the coordinator's
            # control.gather_skew_seconds#rank= histograms: who arrived
            # late at the negotiation barrier during this leg, and by how
            # much on average.  The live counterpart of the post-hoc
            # tools/trace_merge.py report.
            prefix = "control.gather_skew_seconds#rank="
            per_rank = {}
            for name, h in snap.get("histograms", {}).items():
                if not name.startswith(prefix) or not h.get("count"):
                    continue
                rank = name[len(prefix):]
                per_rank[rank] = {
                    "count": h["count"],
                    "mean_s": round(h["sum"] / h["count"], 9)}
            if not per_rank:
                return None
            slowest = max(per_rank, key=lambda r: per_rank[r]["mean_s"])
            return {"per_rank": per_rank, "slowest_rank": slowest}

        print("TCPLEG " + json.dumps({
            "n_proc": n,
            "images_per_sec_per_proc": round(batch * iters / dt_raw, 2),
            "comm_fraction": round(t_comm_raw / dt_raw, 4),
            "ring_transport": transport,
            "pinned": pinned,
            "wire_compression": wire_stats,
            # Bucketed-overlap A/B on this leg: step time, comm fraction
            # (exposed-only when overlap is on), hidden/exposed comm
            # seconds from the overlap.* histograms.
            "overlap_ab": overlap_ab,
            # Observatory A/B: step time with the per-hop telemetry off
            # vs on, and the measured overhead fraction (budget ≤2%).
            "observe_ab": observe_ab,
            # Per-size p50 latency for ring/small/hier plus the measured
            # small↔ring crossover (docs/benchmarks.md).
            "algo_sweep": algo_sweep,
            # Cached-vs-uncached negotiation: per-burst wire bytes and the
            # labeled tick-latency histograms of the response cache.
            "response_cache": cache_stats,
            # Per-rank negotiation-barrier lateness (None when the
            # coordinator recorded no skew samples, e.g. 1-proc runs).
            "straggler_skew": _straggler_skew(),
            # Full counter/gauge state at the end of the run, straight
            # from the unified registry (histograms are left to the
            # JSONL/Prometheus exporters to keep this line readable).
            "metrics": {"counters": snap.get("counters", {}),
                        "gauges": snap.get("gauges", {})},
        }), flush=True)
    hvd.shutdown()


def _conv_leg_setup(seed=0):
    """Shared workload of the 2-process leg and its contention probes:
    identical model/data/optimizer so the probes measure scheduling, not
    a different program."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import ConvNet

    batch = int(os.environ.get("BENCH_TCP_BATCH", "8"))
    iters = int(os.environ.get("BENCH_TCP_ITERS", "12"))
    model = ConvNet(num_classes=10)
    images = jax.random.normal(jax.random.PRNGKey(seed),
                               (batch, 32, 32, 3), jnp.float32)
    labels = jnp.zeros((batch,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), images[:1])["params"]
    tx = optax.sgd(0.01, momentum=0.9)

    @jax.jit
    def grads_fn(params):
        def loss(p):
            logits = model.apply({"params": p}, images)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        return jax.value_and_grad(loss)(params)

    @jax.jit
    def apply_fn(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return batch, iters, params, tx, grads_fn, apply_fn


def solo_worker():
    """The tcp_worker loop minus framework and communication — the same
    split grads/apply dispatch and per-iter grads sync, so one copy is
    the comm-free baseline and two concurrent copies measure the host's
    pure compute-contention ceiling for the 2-process leg."""
    jax = _cpu_jax()
    import numpy as np

    batch, iters, params, tx, grads_fn, apply_fn = _conv_leg_setup()
    opt_state = tx.init(params)
    for _ in range(2):
        loss, grads = grads_fn(params)
        jax.block_until_ready(grads)
        params, opt_state = apply_fn(params, opt_state, grads)
    np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, grads = grads_fn(params)
        jax.block_until_ready(grads)
        params, opt_state = apply_fn(params, opt_state, grads)
    np.asarray(loss)
    dt = time.perf_counter() - t0
    print("SOLOLEG " + json.dumps(
        {"images_per_sec": round(batch * iters / dt, 2)}), flush=True)


def xport_worker():
    """One rank of the per-hop transport microbench (spawned under
    ``horovod_tpu.run`` by the xport_sweep leg): eager allreduces of bare
    numpy payloads across a sweep of sizes, each timed per call, so every
    configured leg — shm fan-in, io_uring ring, classic TCP ring, UDS —
    yields a latency/bandwidth curve with no model in the way.  Rank 0
    prints one ``XPORTLEG`` JSON line with the curve and the transports
    the native plane actually selected (a leg that silently fell back
    must be visible in the artifact, not mislabeled)."""
    jax = _cpu_jax()
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics

    hvd.init()
    iters = int(os.environ.get("BENCH_XPORT_ITERS", "30"))
    sizes = [int(s) for s in os.environ.get(
        "BENCH_XPORT_SIZES",
        "4096,65536,262144,1048576,4194304").split(",")]
    curve = []
    for nbytes in sizes:
        buf = np.ones(nbytes // 4, np.float32)
        for _ in range(3):   # negotiation + response-cache ramp
            hvd.allreduce(buf, average=False, name=f"xp.{nbytes}")
        lat = []
        for _ in range(iters):
            t0 = time.perf_counter()
            hvd.allreduce(buf, average=False, name=f"xp.{nbytes}")
            lat.append(time.perf_counter() - t0)
        lat.sort()
        p50 = lat[len(lat) // 2]
        curve.append({"bytes": nbytes,
                      "p50_us": round(p50 * 1e6, 1),
                      "mbps": round(nbytes / p50 / 1e6, 1)})
    if hvd.rank() == 0:
        control = getattr(basics.controller(), "_control", None)
        print("XPORTLEG " + json.dumps({
            "data_transport": (control.data_transport()
                               if control is not None
                               and hasattr(control, "data_transport")
                               else "none"),
            "ring_transport": (control.ring_transport()
                               if control is not None
                               and hasattr(control, "ring_transport")
                               else "none"),
            "sizes": curve}), flush=True)
    hvd.shutdown()


def recovery_worker():
    """One rank of the chaos recovery drill (BENCH_RECOVERY_* env).

    Trains a deterministic law (``w = full(step)``; each step sleeps
    BENCH_RECOVERY_STEP_MS to stand in for compute) under
    ``run_elastic``; rank BENCH_RECOVERY_DIE_RANK SIGKILLs itself at
    BENCH_RECOVERY_DIE_STEP.  Checkpoint mode is BENCH_RECOVERY_MODE:
    ``sync`` saves a full checkpoint every BENCH_RECOVERY_SYNC_EVERY
    steps on the step path; ``async`` snapshots every
    BENCH_RECOVERY_CADENCE steps into the delta stream.  The survivor
    replays to the pre-crash frontier and prints one ``RECLEG`` JSON
    line: recovery wall-clock (last pre-crash step -> caught back up),
    the native downtime gauge, replayed steps, checkpoint byte
    counters, and whether the restored state matched the law
    bit-exactly."""
    import signal

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    jax = _cpu_jax()
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint, elastic
    from horovod_tpu import metrics as hvd_metrics

    mode = os.environ.get("BENCH_RECOVERY_MODE", "async")
    die_rank = int(os.environ.get("BENCH_RECOVERY_DIE_RANK", "1"))
    die_step = int(os.environ.get("BENCH_RECOVERY_DIE_STEP", "99"))
    sync_every = int(os.environ.get("BENCH_RECOVERY_SYNC_EVERY", "50"))
    cadence = int(os.environ.get("BENCH_RECOVERY_CADENCE", "2"))
    step_s = float(os.environ.get("BENCH_RECOVERY_STEP_MS", "40")) / 1e3
    ckpt_dir = os.environ["BENCH_RECOVERY_DIR"]
    n_elem = int(os.environ.get("BENCH_RECOVERY_STATE_ELEMS", "65536"))

    elastic.init()
    like = {"w": np.zeros(n_elem, np.float32),
            "step": np.zeros((), np.int64)}
    progress = {"step": 0, "t": 0.0}

    def law(step):
        return {"w": np.full(n_elem, float(step), np.float32),
                "step": np.asarray(step, np.int64)}

    def train(state, resume_epoch):
        gen = elastic.generation()
        step = int(state["step"])
        if gen == 0:
            if mode == "sync":
                checkpoint.save(ckpt_dir, dict(state), step)
            t0 = time.monotonic()
            while step < die_step + 10 and time.monotonic() - t0 < 120:
                if elastic.generation() != gen:
                    raise hvd.HorovodRetryableError(
                        "membership changed between steps")
                if hvd.rank() == die_rank and step == die_step:
                    os.kill(os.getpid(), signal.SIGKILL)
                hvd.allreduce(np.ones(256, np.float32),
                              name=f"rec.{gen}.{step}")
                time.sleep(step_s)
                step += 1
                state = law(step)
                progress["step"], progress["t"] = step, time.monotonic()
                if mode == "sync":
                    if step % sync_every == 0:
                        checkpoint.save(ckpt_dir, state, step)
                else:
                    elastic.snapshot(state, step)
            print(f"NO_RECONFIG rank={hvd.rank()}", flush=True)
            sys.exit(5)
        # Survivor after the reconfiguration: verify bit-identity of the
        # restored state against the law, replay to the frontier, report.
        ok = bool(np.array_equal(np.asarray(state["w"]), law(step)["w"]))
        replayed = progress["step"] - step
        while step < progress["step"]:
            hvd.allreduce(np.ones(256, np.float32),
                          name=f"rec.{gen}.{step}")
            time.sleep(step_s)
            step += 1
            state = law(step)
            if mode == "sync":
                if step % sync_every == 0:
                    checkpoint.save(ckpt_dir, state, step)
            else:
                elastic.snapshot(state, step)
        recovery_s = time.monotonic() - progress["t"]
        snap = hvd_metrics.snapshot()
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        dir_bytes = 0
        for root, _dirs, files in os.walk(ckpt_dir):
            dir_bytes += sum(
                os.path.getsize(os.path.join(root, f)) for f in files)
        if hvd.rank() == 0:
            print("RECLEG " + json.dumps({
                "mode": mode,
                "resume_epoch": int(resume_epoch),
                "replayed_steps": int(replayed),
                "recovery_seconds": round(recovery_s, 4),
                "native_downtime_s": round(
                    gauges.get("elastic.last_downtime_s", -1.0), 4),
                "state_ok": ok,
                "step_seconds": step_s,
                "ckpt_bytes": {
                    "base": int(counters.get(
                        "ckpt.bytes_written#kind=base", 0)),
                    "delta": int(counters.get(
                        "ckpt.bytes_written#kind=delta", 0)),
                    "dir": int(dir_bytes),
                },
                "commits": {
                    "base": int(counters.get("ckpt.commits#kind=base", 0)),
                    "delta": int(counters.get(
                        "ckpt.commits#kind=delta", 0)),
                    "snapshots": int(counters.get("ckpt.snapshots", 0)),
                },
            }), flush=True)
        return state

    elastic.run_elastic(
        train, directory=ckpt_dir, like=like,
        snapshot_every_steps=cadence if mode == "async" else 0)
    print("RECDONE", flush=True)


def policy_worker():
    """One rank of the straggler-eviction policy drill (BENCH_POLICY_*
    env).

    Three ranks train a fixed allreduce loop under ``run_elastic`` with
    the fleet policy armed; the drill plants ``slow:rank=1:ms=M`` on
    exactly one process's environment.  The coordinator's policy demotes
    the straggler at a planned tick boundary and admits the parked spare
    in the same reconfigure (``HOROVOD_TPU_ELASTIC_MIN_RANKS`` pins the
    floor so the swap is world-neutral).  Rank 0 then prints one
    ``POLLEG`` JSON line: wall time from the start of delayed ticking to
    the resumed step, the native ``policy.*`` counters, the downtime
    gauge, and whether the restored state matched bit-exactly."""
    import sys

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    jax = _cpu_jax()
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint, elastic
    from horovod_tpu import metrics as hvd_metrics

    slow_ms = int(os.environ.get("BENCH_POLICY_SLOW_MS", "30"))
    ckpt_dir = os.environ["BENCH_POLICY_DIR"]
    elastic.init()
    w0 = np.arange(4096, dtype=np.float32)
    t_start = {"t": 0.0}

    def train(state, resume_epoch):
        gen = elastic.generation()
        if gen == 0:
            checkpoint.save(ckpt_dir, dict(state), 0)
            t_start["t"] = time.monotonic()
            t0 = time.monotonic()
            i = 0
            while time.monotonic() - t0 < 120:
                if elastic.generation() != gen:
                    raise hvd.HorovodRetryableError(
                        "membership changed between steps")
                hvd.allreduce(np.ones(256, np.float32),
                              name=f"pol.{gen}.{i}")
                i += 1
            print(f"NO_EVICTION rank={hvd.rank()}", flush=True)
            sys.exit(5)
        evict_s = time.monotonic() - t_start["t"]
        ok = bool(np.array_equal(np.asarray(state["w"]), w0))
        snap = hvd_metrics.snapshot()
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        if hvd.rank() == 0:
            print("POLLEG " + json.dumps({
                "slow_ms": slow_ms,
                "evict_seconds": round(evict_s, 4),
                "native_downtime_s": round(
                    gauges.get("elastic.last_downtime_s", -1.0), 4),
                "evictions": int(counters.get("policy.evictions", 0)),
                "evictions_suppressed": int(
                    counters.get("policy.evictions_suppressed", 0)),
                "generation": int(gen),
                "size": int(hvd.size()),
                "state_ok": ok,
            }), flush=True)
        return state

    try:
        elastic.run_elastic(train, directory=ckpt_dir, like={"w": w0})
    except hvd.HorovodAbortedError:
        # The evicted straggler itself: demoted out of the membership.
        print("POLABORT", flush=True)
        sys.exit(3)
    print("POLDONE", flush=True)


def publish_worker():
    """One process of the publish-while-training drill (BENCH_PUBLISH_*
    env; two processes, four ranks, ``HOROVOD_TPU_PROCESS_SETS``
    registers the subscriber set ``serve:2,3`` on process 1).

    Both processes run the same world-allreduce training loop twice: a
    baseline leg, then a leg where process 0 commits a checkpoint-chain
    epoch every K steps and process 1's :class:`ParameterPublisher`
    polls the directory between steps, streaming each committed tip to
    the ``serve`` set on the set-scoped host plane.  Training never
    stops; process 1 prints one ``PUBLEG`` JSON line with the measured
    step-time delta, publish latency and commit-to-serve staleness."""
    import sys

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=2")
    jax = _cpu_jax()
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.publish import ParameterPublisher

    ckpt_dir = os.environ["BENCH_PUBLISH_DIR"]
    steps = int(os.environ.get("BENCH_PUBLISH_STEPS", "40"))
    ckpt_every = int(os.environ.get("BENCH_PUBLISH_CKPT_EVERY", "10"))
    hvd.init()
    assert hvd.size() == 4 and hvd.process_count() == 2
    pidx = hvd.process_index()
    payload = np.ones(1 << 14, np.float32)
    base_flat = {f"['w{i}']": np.arange(4096, dtype=np.float32)
                 for i in range(4)}

    def leg(publishing, tag):
        pub = (ParameterPublisher(ckpt_dir, "serve")
               if publishing and pidx == 1 else None)
        prev, prev_flat = -1, None
        times = []
        for i in range(steps):
            s0 = time.monotonic()
            hvd.allreduce(payload, average=False, name=f"{tag}.{i}")
            times.append(time.monotonic() - s0)
            if publishing and pidx == 0 and i % ckpt_every == ckpt_every - 1:
                epoch = i // ckpt_every
                flat = {k: v + float(epoch) for k, v in base_flat.items()}
                checkpoint.save_chain(ckpt_dir, flat, epoch,
                                      prev_epoch=prev, prev_flat=prev_flat)
                prev, prev_flat = epoch, flat
            if pub is not None:
                out = pub.poll()
                if out is not None:
                    # Published state is the committed chain tip, not a
                    # torn or in-flight write.
                    epoch = pub.last_published_epoch
                    want = base_flat["['w0']"] + float(epoch)
                    assert np.array_equal(np.asarray(out["['w0']"]), want)
        return sum(times) / len(times)

    base_s = leg(False, "base")
    hvd.allreduce(np.ones(4, np.float32), name="phase.barrier")
    pub_s = leg(True, "pub")
    # Keep the coordinator alive through process 1's final publish: its
    # last poll() may still be negotiating on the serve set when process
    # 0 falls out of the loop.
    hvd.allreduce(np.ones(4, np.float32), name="end.barrier")
    if pidx == 1:
        snap = hvd_metrics.snapshot()
        hists = snap.get("histograms", {})
        lat = hists.get("publish.latency_seconds", {})
        stale = hists.get("publish.staleness_seconds#process_set=serve", {})
        nlat = lat.get("count", 0)
        nstale = stale.get("count", 0)
        print("PUBLEG " + json.dumps({
            "publishes": int(snap.get("counters", {}).get(
                "publish.count", 0)),
            "publish_bytes": int(snap.get("counters", {}).get(
                "publish.bytes", 0)),
            "publish_latency_s": round(
                lat.get("sum", 0.0) / nlat, 5) if nlat else None,
            "staleness_s": round(
                stale.get("sum", 0.0) / nstale, 5) if nstale else None,
            "publish_epoch": int(snap.get("gauges", {}).get(
                "publish.epoch#process_set=serve", -1)),
            "step_seconds_baseline": round(base_s, 5),
            "step_seconds_publishing": round(pub_s, 5),
            "step_time_delta_pct": round(
                (pub_s - base_s) / base_s * 100.0, 2),
        }), flush=True)
    print("PUBDONE", flush=True)
    sys.exit(0)


def _publish_drill():
    """Publish-while-training drill: two processes over the TCP control
    plane, training on the world set while process 1 streams committed
    checkpoint-chain tips to the ``serve`` process set.  Returns the
    PUBLEG block — publish latency, commit-to-serve staleness, and the
    training step-time delta the serving plane imposed."""
    import socket
    import subprocess
    import sys
    import tempfile

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmpdir = tempfile.mkdtemp(prefix="bench-publish-")
    port = free_port()
    procs = []
    for i in range(2):
        env = dict(os.environ)
        env.pop("HOROVOD_TPU_FAULT", None)
        env.pop("HOROVOD_TPU_TIMELINE", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_TPU_PROCESS_INDEX": str(i),
            "HOROVOD_TPU_PROCESS_COUNT": "2",
            "HOROVOD_TPU_SIZE": "4",
            "HOROVOD_TPU_RANK": str(i * 2),
            "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
            "HOROVOD_TPU_CYCLE_TIME_MS": "2",
            "HOROVOD_TPU_PROCESS_SETS": "serve:2,3",
            "BENCH_PUBLISH_DIR": tmpdir,
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--publish-worker"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__))))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append((p.returncode, out))
    for rc, out in outs:
        # The acceptance bar: publishing never aborts training.
        if rc != 0 or "PUBDONE" not in out:
            raise RuntimeError(
                f"publish drill: worker exited {rc} without finishing "
                f"training:\n{out[-2000:]}")
    for line in outs[1][1].splitlines():
        if line.startswith("PUBLEG "):
            result = json.loads(line[len("PUBLEG "):])
            result["note"] = (
                "both processes train on the world set while process 0 "
                "commits a chain epoch every 10 steps and process 1 "
                "streams each committed tip to the serve set between its "
                "own steps; staleness_s = commit-to-served lag, "
                "step_time_delta_pct = training cost of the serving plane "
                "(same host, so it includes CPU contention)")
            return result
    raise RuntimeError(
        f"publish drill produced no PUBLEG line:\n{outs[1][1][-2000:]}")


def _recovery_drill():
    """Kill-one-rank recovery drill, sync full checkpoints vs the async
    delta stream, in the same run on the same machine.  Returns the
    artifact block with both legs and the headline ratio
    (``recovery_ratio_async_vs_sync`` — the acceptance bar is <= 0.25:
    async recovery replays a snapshot interval, sync replays a full
    checkpoint interval)."""
    import signal
    import socket
    import subprocess
    import sys
    import tempfile

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def leg(mode):
        tmpdir = tempfile.mkdtemp(prefix=f"bench-recovery-{mode}-")
        port = free_port()
        procs = []
        for i in range(2):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
                "HOROVOD_TPU_PROCESS_INDEX": str(i),
                "HOROVOD_TPU_PROCESS_COUNT": "2",
                "HOROVOD_TPU_SIZE": "2",
                "HOROVOD_TPU_RANK": str(i),
                "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
                "HOROVOD_TPU_CYCLE_TIME_MS": "2",
                "HOROVOD_TPU_ELASTIC": "1",
                "BENCH_RECOVERY_MODE": mode,
                "BENCH_RECOVERY_DIR": tmpdir,
            })
            env.pop("HOROVOD_TPU_FAULT", None)
            env.pop("HOROVOD_TPU_TIMELINE", None)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--recovery-worker"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append((p.returncode, out))
        rc1, _out1 = outs[1]
        if rc1 != -signal.SIGKILL:
            raise RuntimeError(
                f"{mode} leg: victim exited {rc1}, expected SIGKILL:\n"
                f"{outs[1][1][-2000:]}")
        rc0, out0 = outs[0]
        for line in out0.splitlines():
            if line.startswith("RECLEG "):
                result = json.loads(line[len("RECLEG "):])
                if rc0 != 0:
                    result["survivor_exit"] = rc0
                return result
        raise RuntimeError(
            f"{mode} leg produced no RECLEG line (survivor exit {rc0}):\n"
            f"{out0[-2000:]}")

    sync = leg("sync")
    async_ = leg("async")
    ratio = (round(async_["recovery_seconds"] / sync["recovery_seconds"], 4)
             if sync.get("recovery_seconds") else None)
    return {
        "sync": sync,
        "async": async_,
        "recovery_ratio_async_vs_sync": ratio,
        "note": ("one of two ranks SIGKILLed under load; recovery = wall "
                 "time from the survivor's last pre-crash step until it "
                 "replayed back to that step.  sync saves a full "
                 "checkpoint every 50 steps on the step path; async "
                 "snapshots every 2 steps into the base+delta stream"),
    }


def _policy_drill():
    """Planted-straggler eviction drill: three ranks plus a parked spare,
    ``slow:rank=1:ms=M`` on exactly one process, the fleet policy armed.
    Returns the POLLEG block from the coordinator — time-to-evict, the
    ``policy.*`` counters, and bit-identity of the resumed state."""
    import signal
    import socket
    import subprocess
    import sys
    import tempfile

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmpdir = tempfile.mkdtemp(prefix="bench-policy-")
    port = free_port()
    slow_ms = int(os.environ.get("BENCH_POLICY_SLOW_MS", "30"))
    procs = []
    for i in range(4):
        standby = i >= 3
        env = dict(os.environ)
        env.pop("HOROVOD_TPU_FAULT", None)
        env.pop("HOROVOD_TPU_TIMELINE", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "HOROVOD_TPU_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_TPU_PROCESS_INDEX": str(i),
            "HOROVOD_TPU_PROCESS_COUNT": "3",
            "HOROVOD_TPU_SIZE": "3",
            "HOROVOD_TPU_RANK": str(i),
            "HOROVOD_TPU_CONTROL_TIMEOUT_S": "60",
            "HOROVOD_TPU_CYCLE_TIME_MS": "2",
            "HOROVOD_TPU_ELASTIC": "1",
            "HOROVOD_TPU_EVICT_THRESHOLD": "0.01",
            "HOROVOD_TPU_EVICT_TICKS": "5",
            "HOROVOD_TPU_EVICT_MAX": "1",
            # Floor at the full world: the eviction waits for the spare
            # to park, making the demotion a world-neutral 3->3 swap.
            "HOROVOD_TPU_ELASTIC_MIN_RANKS": "3",
            "BENCH_POLICY_DIR": tmpdir,
            "BENCH_POLICY_SLOW_MS": str(slow_ms),
        })
        if i == 1:
            # Fault targeting is by CURRENT first rank: only the victim
            # may carry the spec, or a re-ranked survivor (or the spare
            # adopting the seat) would inherit the delay.
            env["HOROVOD_TPU_FAULT"] = f"slow:rank=1:ms={slow_ms}"
        if standby:
            env["HOROVOD_TPU_STANDBY"] = "1"
            env["HOROVOD_TPU_STANDBY_WAIT_S"] = "60"
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--policy-worker"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__))))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append((p.returncode, out))
    rc1, out1 = outs[1]
    if rc1 != 3 or "POLABORT" not in out1:
        raise RuntimeError(
            f"policy drill: victim exited {rc1}, expected the eviction "
            f"abort:\n{out1[-2000:]}")
    rc0, out0 = outs[0]
    for line in out0.splitlines():
        if line.startswith("POLLEG "):
            result = json.loads(line[len("POLLEG "):])
            if rc0 != 0:
                result["coordinator_exit"] = rc0
            result["note"] = (
                "one of three ranks slowed by slow_ms per tick; the fleet "
                "policy demoted it after 5 consecutive over-threshold "
                "gathers and admitted the parked spare in the same planned "
                "reconfigure; evict_seconds = wall time from the "
                "coordinator's first training step to its resumed step "
                "(the straggler delays ticks from init onward, so the "
                "hysteresis window may already be partly filled)")
            return result
    raise RuntimeError(
        f"policy drill produced no POLLEG line (coordinator exit {rc0}):\n"
        f"{out0[-2000:]}")


def ctrl_worker():
    """One process of the control-plane tick sweep (``ctrl_sweep`` leg):
    no data plane, no model — just the native negotiation tick in
    lockstep with every peer, driven straight through ctypes.  Every
    tick sends the canonical EMPTY RequestList (a heartbeat — the frame
    a response-cache-served steady-state tick degenerates to), so the
    sweep isolates pure control fan-in/fan-out cost; under
    ``HOROVOD_TPU_CONTROL_TOPO=hier`` the byte-identical member frames
    also exercise the aggregation container's template/roster
    compression, which is what keeps root ingress bytes ~flat however
    many processes each host runs.  Process 0 prints one ``CTRLLEG``
    JSON line with the per-tick wall time and the root-side counters."""
    from horovod_tpu import cpp_core, wire

    pidx = int(os.environ["BENCH_CTRL_PIDX"])
    pcount = int(os.environ["BENCH_CTRL_PCOUNT"])
    port = int(os.environ["BENCH_CTRL_PORT"])
    ticks = int(os.environ.get("BENCH_CTRL_TICKS", "30"))
    warm = int(os.environ.get("BENCH_CTRL_WARM", "5"))
    # Generous rendezvous budget: every loopback process pays the Python
    # import serially when cores are scarce, and Create blocks until the
    # whole job is connected.
    timeout_ms = int(os.environ.get("BENCH_CTRL_TIMEOUT_MS", "240000"))
    ctl = cpp_core.CppControlPlane(pidx, pcount, "127.0.0.1", port,
                                   pidx, pcount, timeout_ms=timeout_ms)
    blob = wire.serialize_request_list([])
    for _ in range(warm):
        ctl.tick(blob, 1 << 20)
    t0 = time.perf_counter()
    for _ in range(ticks):
        ctl.tick(blob, 1 << 20)
    dt = time.perf_counter() - t0
    if pidx == 0:
        snap = cpp_core.metrics_snapshot()
        counters = snap.get("counters", {})
        gauges = snap.get("gauges", {})
        print("CTRLLEG " + json.dumps({
            "tick_us": dt / ticks * 1e6,
            # Counters cover warm + timed ticks; the parent divides by
            # total_ticks for per-tick rates.
            "total_ticks": warm + ticks,
            "root_gather_bytes": counters.get(
                "control.root_gather_bytes", 0),
            "merged_frames": counters.get("control.merged_frames", 0),
            "agg_depth": gauges.get("control.agg_depth", 0),
        }), flush=True)
    ctl.close()


def _ctrl_sweep():
    """Flat-vs-hier control tick latency at 8/32/128 loopback processes
    (``BENCH_CTRL_PROCS``), the world spread over four fake member hosts
    plus a root-only host (fingerprints, not real machines — every
    socket is loopback, what differs is the gather topology: the root
    reads O(procs) sockets flat, O(hosts) hier).

    Reuses the transport microbench's interleaved-window trick: each
    timing window runs the flat leg and the hier leg back to back, so
    both topologies sample the same wall clock and machine noise cancels
    out of the ratio; the per-topology estimate is the best window.
    Headline: ``hier_tick_speedup_128p`` (flat tick / hier tick at the
    largest world)."""
    import socket
    import subprocess
    import sys

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    procs_list = [int(s) for s in os.environ.get(
        "BENCH_CTRL_PROCS", "8,32,128").split(",")]
    windows = int(os.environ.get("BENCH_CTRL_WINDOWS", "2"))
    ticks = int(os.environ.get("BENCH_CTRL_TICKS", "30"))
    n_hosts = int(os.environ.get("BENCH_CTRL_HOSTS", "4"))

    def leg(nproc, topo):
        port = free_port()
        # Contiguous pidx blocks per fake host: matches a real
        # one-launcher-per-host layout and lets the container's roster
        # runs stay O(1) per host.
        chunk = max(1, -(-(nproc - 1) // n_hosts))
        children = []
        for p in range(nproc):
            fp = ("ctrl-root-host" if p == 0
                  else f"ctrl-member-host-{(p - 1) // chunk}")
            env = dict(os.environ)
            # A clean control-plane environment: inherited knobs (cache
            # capacity, elastic, integrity...) must not skew the A/B.
            for k in list(env):
                if k.startswith("HOROVOD_TPU_"):
                    del env[k]
            env.update({
                "JAX_PLATFORMS": "cpu",
                "HOROVOD_TPU_CONTROL_TOPO": topo,
                "HOROVOD_TPU_HOST_FINGERPRINT": fp,
                "BENCH_CTRL_PIDX": str(p),
                "BENCH_CTRL_PCOUNT": str(nproc),
                "BENCH_CTRL_PORT": str(port),
                "BENCH_CTRL_TICKS": str(ticks),
            })
            env.pop("XLA_FLAGS", None)
            children.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--ctrl-worker"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env))
        line = None
        try:
            for p, child in enumerate(children):
                out, _ = child.communicate(timeout=600)
                if child.returncode != 0:
                    raise RuntimeError(
                        f"ctrl leg {nproc}p/{topo}: process {p} exited "
                        f"{child.returncode}:\n{out[-1500:]}")
                if p == 0:
                    for ln in out.splitlines():
                        if ln.startswith("CTRLLEG "):
                            line = json.loads(ln[len("CTRLLEG "):])
        finally:
            for child in children:
                if child.poll() is None:
                    child.kill()
        if line is None:
            raise RuntimeError(
                f"ctrl leg {nproc}p/{topo} produced no CTRLLEG line")
        return line

    legs = {}
    speedup_by_n = {}
    for nproc in procs_list:
        best = {}
        for _ in range(windows):
            for topo in ("flat", "hier"):   # interleaved within the window
                res = leg(nproc, topo)
                cur = best.get(topo)
                if cur is None or res["tick_us"] < cur["tick_us"]:
                    best[topo] = res
        flat, hier = best["flat"], best["hier"]
        speedup = (flat["tick_us"] / hier["tick_us"]
                   if hier["tick_us"] > 0 else None)
        speedup_by_n[nproc] = speedup
        legs[f"{nproc}p"] = {
            "flat_tick_us": round(flat["tick_us"], 1),
            "hier_tick_us": round(hier["tick_us"], 1),
            "hier_tick_speedup": round(speedup, 3) if speedup else None,
            "flat_root_gather_bytes_per_tick": round(
                flat["root_gather_bytes"] / flat["total_ticks"], 1),
            "hier_root_gather_bytes_per_tick": round(
                hier["root_gather_bytes"] / hier["total_ticks"], 1),
            "hier_merged_frames_per_tick": round(
                hier["merged_frames"] / hier["total_ticks"], 1),
            "flat_agg_depth": flat["agg_depth"],
            "hier_agg_depth": hier["agg_depth"],
        }
    top = max(procs_list)
    return {
        "legs": legs,
        "windows": windows,
        "ticks_per_window": ticks,
        "fake_member_hosts": n_hosts,
        "hier_tick_speedup_128p": (
            round(speedup_by_n[top], 3)
            if top == 128 and speedup_by_n.get(top) else None),
        "note": ("empty-frame lockstep ticks over loopback; hosts are "
                 "fingerprints, so the hier win measured here is the "
                 "root's O(hosts)-vs-O(procs) fan-in, not network "
                 "locality"),
    }


def bench_scaling_tcp():
    """Disjoint-runtime scaling leg on localhost: the same worker loop at
    1 process (no communication) and at 2 processes under the
    ``horovod_tpu.run`` launcher (negotiation + payload over the native
    TCP ring).  Efficiency = 2-process per-process throughput over the
    1-process number.  This exercises the REAL cross-process eager data
    plane under load; both processes share one host's cores, so the
    ceiling is contention-bound."""
    import subprocess
    import sys

    def run_leg(nproc, pin=False):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        # The worker sweeps wire dtypes itself; an exported process-wide
        # default would silently turn the "fp32" leg into a compressed one.
        env.pop("HOROVOD_TPU_WIRE_DTYPE", None)
        # Adaptive-precision autopilot, armed for the whole worker run:
        # the static legs pass explicit wire dtypes (their requests carry
        # them, so the coordinator never stamps those), and the auto leg
        # runs last under its own tensor names.  TICKS=2 lets the ladder
        # climb within the short warmup window; the lowered int8 floor
        # lets the small conv leg's buckets report residuals at all.
        env["HOROVOD_TPU_PRECISION"] = "auto"
        env["HOROVOD_TPU_PRECISION_TICKS"] = "2"
        env.setdefault("HOROVOD_TPU_INJIT_INT8_FLOOR", "4096")
        if pin:
            env["BENCH_TCP_PIN"] = "1"
        else:
            # An exported BENCH_TCP_PIN must not leak into the nominally
            # unpinned legs — the artifact would silently mix pinned and
            # unpinned measurements.
            env.pop("BENCH_TCP_PIN", None)
        # Own session so a timeout can kill the WHOLE process group:
        # subprocess.run's timeout only kills the launcher, leaving its
        # worker grandchildren burning cores under the retried window.
        proc = subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run", "-np", str(nproc),
             "--", sys.executable, os.path.abspath(__file__),
             "--tcp-worker"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            start_new_session=True)
        try:
            stdout, stderr = proc.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            import signal
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
            proc.wait()
            raise
        for line in stdout.splitlines():
            if line.startswith("TCPLEG "):
                return json.loads(line[len("TCPLEG "):])
        raise RuntimeError(
            f"tcp leg ({nproc}p) produced no TCPLEG line:\n"
            f"{stdout[-2000:]}\n{stderr[-2000:]}")

    def run_solo(nproc):
        """N INDEPENDENT comm-free workers at once (the tcp loop minus
        the framework); at N=1 the comm-free baseline, at N=2 the pure
        core-contention measurement.  None on any child failure — a
        half-failed pair would report a contention-free 'ceiling'."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--solo-worker"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
            for _ in range(nproc)]
        rates = []
        try:
            for p in procs:
                try:
                    out, _ = p.communicate(timeout=600)
                except subprocess.TimeoutExpired:
                    return None
                if p.returncode != 0:
                    return None
                for line in out.splitlines():
                    if line.startswith("SOLOLEG "):
                        rates.append(json.loads(
                            line[len("SOLOLEG "):])["images_per_sec"])
            if len(rates) != nproc:
                return None
            return sum(rates) / len(rates)
        finally:
            # Any early exit must not leave a sibling worker burning the
            # cores under the NEXT bench leg.
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()

    # Single-shot numbers on a contended host swing run-to-run (±30%
    # observed on the 1-CPU bench container); take the best of N windows
    # per leg, so the artifact reports capability, not scheduler luck.
    windows = max(1, int(os.environ.get("BENCH_TCP_WINDOWS", "3")))

    def best_leg(nproc, pin=False):
        """Best window by throughput; a transient launch/negotiation
        failure only costs that window — the leg fails when ALL windows
        do.  A TIMEOUT is not retried: a hang is not transient, each
        repeat would cost another 600 s, and the group-kill above has
        already reaped the stuck workers."""
        runs, last_err = [], None
        for _ in range(windows):
            try:
                runs.append(run_leg(nproc, pin=pin))
            except subprocess.TimeoutExpired as e:
                # A hang is not transient and each repeat costs another
                # 600 s — stop launching windows, but keep any already
                # collected (the group-kill has reaped the stuck
                # workers, so they are untainted).
                last_err = e
                break
            except Exception as e:   # noqa: BLE001 — launcher transients
                last_err = e
        if not runs:
            raise RuntimeError(
                f"all windows of the {nproc}-process leg failed; last "
                f"error: {last_err}") from last_err
        return max(runs, key=lambda r: r["images_per_sec_per_proc"])

    def best_solo(nproc):
        runs = [run_solo(nproc) for _ in range(windows)]
        runs = [r for r in runs if r]
        return max(runs) if runs else None

    one = best_leg(1)
    two = best_leg(2)
    single_solo = best_solo(1)
    dual_solo = best_solo(2) if single_solo else None
    # Pinned legs: each process confined to a disjoint CPU half, and the
    # 1-process baseline confined to a half as well — so numerator and
    # denominator run on the SAME compute budget and the efficiency
    # isolates the data plane instead of scheduler contention (the
    # multi-host analogue, where peers never share cores).  Requires at
    # least 2 allowed CPUs; on a 1-CPU host the legs would silently
    # measure the unpinned configuration, so they are skipped instead.
    try:
        allowed = sorted(os.sched_getaffinity(0))
    except AttributeError:
        allowed = [0]
    # Same grouping the worker's pin helper uses: a host whose allowed
    # CPUs are SMT siblings of one physical core is just as unsplittable
    # as a 1-CPU host, and must be reported as a deliberate skip, not as
    # an affinity "error" after burning every pinned window.
    n_splittable = len(_cpu_core_groups(allowed))
    if n_splittable < 2:
        pinned = {"skipped": f"host allows {len(allowed)} CPU(s) on "
                             f"{n_splittable} physical core(s); disjoint "
                             "halves are impossible, the 2-process leg "
                             "shares that budget entirely (see "
                             "contention_ceiling)"}
    else:
        try:
            one_pin = best_leg(1, pin=True)
            two_pin = best_leg(2, pin=True)
            if not (one_pin.get("pinned") and two_pin.get("pinned")):
                raise RuntimeError("worker could not apply CPU affinity")
            pinned_eff = round(two_pin["images_per_sec_per_proc"]
                               / one_pin["images_per_sec_per_proc"], 4)
            pinned = {
                "images_per_sec_per_proc_1_halfcores":
                    one_pin["images_per_sec_per_proc"],
                "images_per_sec_per_proc_2":
                    two_pin["images_per_sec_per_proc"],
                "scaling_efficiency": pinned_eff,
                "comm_fraction": two_pin["comm_fraction"],
                "note": ("both measurements on a fixed half-machine CPU "
                         "budget (sched_setaffinity): the efficiency "
                         "loss here is the eager data plane's own cost, "
                         "not core-scheduler contention"),
            }
        except Exception as e:   # noqa: BLE001 — affinity-less platforms
            pinned = {"error": f"{type(e).__name__}: {e}"}
    if os.environ.get("BENCH_RECOVERY", "1") == "1":
        try:
            recovery = _recovery_drill()
        except Exception as e:   # noqa: BLE001 — the drill must not sink
            recovery = {"error": f"{type(e).__name__}: {e}"}  # the leg
    else:
        recovery = {"skipped": "BENCH_RECOVERY=0"}
    if os.environ.get("BENCH_POLICY", "1") == "1":
        try:
            policy = _policy_drill()
        except Exception as e:   # noqa: BLE001 — the drill must not sink
            policy = {"error": f"{type(e).__name__}: {e}"}  # the leg
    else:
        policy = {"skipped": "BENCH_POLICY=0"}
    if os.environ.get("BENCH_PUBLISH", "1") == "1":
        try:
            publish = _publish_drill()
        except Exception as e:   # noqa: BLE001 — the drill must not sink
            publish = {"error": f"{type(e).__name__}: {e}"}  # the leg
    else:
        publish = {"skipped": "BENCH_PUBLISH=0"}

    def run_xport_leg(extra_env):
        """One 2-process microbench leg (bare-payload allreduce sweep)
        under a forced transport configuration; returns the XPORTLEG
        curve printed by rank 0 of the child job."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("HOROVOD_TPU_WIRE_DTYPE", None)
        env.pop("BENCH_TCP_PIN", None)
        env.pop("HOROVOD_TPU_INTEGRITY", None)
        env.update(extra_env)
        proc = subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
             "--", sys.executable, os.path.abspath(__file__),
             "--xport-worker"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            start_new_session=True)
        try:
            stdout, stderr = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            import signal
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass
            proc.wait()
            raise
        for line in stdout.splitlines():
            if line.startswith("XPORTLEG "):
                return json.loads(line[len("XPORTLEG "):])
        raise RuntimeError(
            f"xport leg produced no XPORTLEG line:\n"
            f"{stdout[-2000:]}\n{stderr[-2000:]}")

    # Per-hop transport microbench: the same bare-payload sweep under
    # each data-plane configuration.  Both processes share this host, so
    # `hier` forms one 2-process group — its intra-host leg IS the hop
    # under test (UDS sockets vs the shm segment), while the `ring` legs
    # compare the leader-ring hop (classic TCP vs io_uring).  Same
    # windows policy as the throughput legs: best per size across
    # BENCH_XPORT_WINDOWS runs, so the curves report transport
    # capability, not scheduler luck on a shared host.
    if os.environ.get("BENCH_XPORT", "1") == "1":
        xwindows = max(1, int(os.environ.get("BENCH_XPORT_WINDOWS", "3")))
        xlegs = (
            ("uds", {"HOROVOD_TPU_ALLREDUCE_ALGO": "hier",
                     "HOROVOD_TPU_TRANSPORT": "classic"}),
            ("shm", {"HOROVOD_TPU_ALLREDUCE_ALGO": "hier",
                     "HOROVOD_TPU_TRANSPORT": "shm"}),
            ("classic", {"HOROVOD_TPU_ALLREDUCE_ALGO": "ring",
                         "HOROVOD_TPU_TRANSPORT": "classic",
                         "HOROVOD_TPU_UDS": "0"}),
            ("uring", {"HOROVOD_TPU_ALLREDUCE_ALGO": "ring",
                       "HOROVOD_TPU_TRANSPORT": "uring",
                       "HOROVOD_TPU_UDS": "0"}),
            # CRC A/B twins: the same three data-plane legs with the
            # end-to-end integrity trailer on — the off/on ratio is the
            # measured cost of checksumming every frame/chunk.
            ("classic+crc", {"HOROVOD_TPU_ALLREDUCE_ALGO": "ring",
                             "HOROVOD_TPU_TRANSPORT": "classic",
                             "HOROVOD_TPU_UDS": "0",
                             "HOROVOD_TPU_INTEGRITY": "1"}),
            ("shm+crc", {"HOROVOD_TPU_ALLREDUCE_ALGO": "hier",
                         "HOROVOD_TPU_TRANSPORT": "shm",
                         "HOROVOD_TPU_INTEGRITY": "1"}),
            ("uring+crc", {"HOROVOD_TPU_ALLREDUCE_ALGO": "ring",
                           "HOROVOD_TPU_TRANSPORT": "uring",
                           "HOROVOD_TPU_UDS": "0",
                           "HOROVOD_TPU_INTEGRITY": "1"}))
        # Interleave the windows across legs (uds shm classic uring, then
        # again) rather than exhausting one leg's windows before the next:
        # the legs being ratioed below then sample the SAME stretch of
        # wall clock, so a transient stall on a shared host taxes them
        # about equally instead of skewing whichever leg it landed on.
        xruns = {label: [] for label, _ in xlegs}
        xerrs = {}
        for _ in range(xwindows):
            for label, lenv in xlegs:
                if label in xerrs and isinstance(
                        xerrs[label], subprocess.TimeoutExpired):
                    continue   # a wedged leg won't unwedge; save the budget
                try:
                    xruns[label].append(run_xport_leg(lenv))
                except Exception as e:   # noqa: BLE001 — per-leg, not fatal
                    xerrs[label] = e
        xport = {}
        for label, _ in xlegs:
            runs = xruns[label]
            if not runs:
                e = xerrs[label]
                xport[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
                continue
            merged = dict(runs[0])
            merged["sizes"] = [
                min((r["sizes"][i] for r in runs),
                    key=lambda c: c["p50_us"])
                for i in range(len(runs[0]["sizes"]))]
            xport[label] = merged
        # Headline ratio: shm fan-in bandwidth over the UDS fan-in
        # baseline, worst case across the >= 256 KiB payloads (the
        # zero-copy win must hold where it matters, not just at the top).
        try:
            shm_b = {c["bytes"]: c["mbps"]
                     for c in xport["shm"]["sizes"] if c["bytes"] >= 1 << 18}
            uds_b = {c["bytes"]: c["mbps"]
                     for c in xport["uds"]["sizes"] if c["bytes"] >= 1 << 18}
            xport["shm_vs_uds_speedup_256k_plus"] = round(
                min(shm_b[b] / uds_b[b] for b in shm_b), 3)
        except Exception:   # noqa: BLE001 — a failed leg has no curve
            xport["shm_vs_uds_speedup_256k_plus"] = None
        # Headline CRC cost: per-leg worst-case p50 inflation with the
        # integrity trailer on, across the >= 256 KiB payloads (small
        # payloads are latency-dominated; the acceptance bound — checksum
        # overhead under 5% — is a bandwidth-regime claim).
        crc_over = {}
        for label in ("classic", "shm", "uring"):
            try:
                off = {c["bytes"]: c["p50_us"]
                       for c in xport[label]["sizes"]
                       if c["bytes"] >= 1 << 18}
                on = {c["bytes"]: c["p50_us"]
                      for c in xport[label + "+crc"]["sizes"]
                      if c["bytes"] >= 1 << 18}
                crc_over[label] = round(
                    max(on[b] / off[b] - 1.0 for b in off), 4)
            except Exception:   # noqa: BLE001 — a failed leg has no curve
                crc_over[label] = None
        measured = [v for v in crc_over.values() if v is not None]
        crc_over["max"] = round(max(measured), 4) if measured else None
        xport["crc_overhead_256k_plus"] = crc_over
    else:
        xport = {"skipped": "BENCH_XPORT=0"}
    transport = two.get("ring_transport", "tcp")
    eff = round(two["images_per_sec_per_proc"]
                / one["images_per_sec_per_proc"], 4)
    ceiling = (round(dual_solo / single_solo, 4)
               if dual_solo and single_solo else None)
    return {
        "n_proc": 2,
        "transport": ("native ring over Unix domain sockets (co-located "
                      "on-host fast path)" if transport == "uds"
                      else "native TCP ring (disjoint runtimes)"),
        "ring_transport": transport,
        "images_per_sec_per_proc_1": one["images_per_sec_per_proc"],
        "images_per_sec_per_proc_2": two["images_per_sec_per_proc"],
        "scaling_efficiency": eff,
        # Two processes share one host's cores: two INDEPENDENT
        # comm-free copies measure the efficiency ceiling contention
        # alone imposes; efficiency_vs_ceiling is the data plane's own
        # share of it (a multi-host pod has no such ceiling — peers
        # don't steal each other's compute).
        "contention_ceiling": ceiling,
        "efficiency_vs_ceiling": (round(eff / ceiling, 4)
                                  if ceiling else None),
        "pinned": pinned,
        "comm_fraction": two["comm_fraction"],
        "comm_fraction_note": "wall time inside the eager allreduce over "
                              "wall time of the step, measured on rank 0 "
                              "of the 2-process run",
        # Per-wire-dtype sweep (fp32 / bf16 / int8 ring wires): throughput,
        # comm_fraction, compressed bytes-on-wire (bf16 ~0.5x, int8 ~0.25x
        # of the fp32 ring), and allreduce max error vs the fp32 ring.
        "wire_compression": two.get("wire_compression"),
        # Backward-overlap A/B on the real wire: step time and
        # comm_fraction with the bucketed scheduler off vs on (the ON
        # fraction counts only exposed communication, with the
        # hidden/exposed split read off the overlap.* histograms).
        "overlap_ab": two.get("overlap_ab"),
        # Observatory A/B on the real wire: step time with the per-hop
        # transfer telemetry off vs on plus the overhead fraction — the
        # acceptance budget is <= 2% (docs/observability.md).
        "observe_ab": two.get("observe_ab"),
        # Response-cache effect on the control plane: per-burst
        # negotiation bytes (uncached vs cached) and cached/uncached tick
        # latency, measured by the worker's probe on the coordinator.
        "response_cache": two.get("response_cache"),
        # Kill-one-rank recovery drill (sync full checkpoints vs the
        # async delta stream) — the trajectory tracks recovery, not just
        # throughput.  BENCH_RECOVERY=0 skips it.
        "recovery": recovery,
        # Planted-straggler eviction drill: time from the first delayed
        # tick to the policy's planned demotion + spare admission, with
        # the policy.* counters.  BENCH_POLICY=0 skips it.
        "policy": policy,
        # Publish-while-training drill: committed chain tips streamed to
        # a subscriber process set mid-training, with publish latency,
        # commit-to-serve staleness, and the training step-time delta.
        # BENCH_PUBLISH=0 skips it.
        "publish": publish,
        # Per-hop transport curves (latency p50 + bandwidth per payload
        # size) for the UDS fan-in, shm fan-in, classic TCP ring, and
        # io_uring ring, plus the worst-case shm-over-UDS speedup at
        # >= 256 KiB.  BENCH_XPORT=0 skips it.
        "xport_sweep": xport,
    }


def _scaling_legs():
    """The scaling leg (its worker processes pin themselves to the CPU
    platform).  Always returns a dict — a failed leg records its error
    and main() then exits non-zero."""
    try:
        return {"scaling_tcp_2proc": bench_scaling_tcp()}
    except Exception as exc:   # noqa: BLE001 — recorded, not fatal
        return {"scaling_tcp_2proc": {
            "error": f"{type(exc).__name__}: {exc}"[:300]}}


# The drills' own subprocesses, a hidden flag each: ``--tcp-worker`` ...
WORKERS = (ctrl_worker, tcp_worker, solo_worker, xport_worker,
           recovery_worker, policy_worker, publish_worker)


def _parser():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for worker in WORKERS:
        ap.add_argument(f"--{worker.__name__.replace('_', '-')}",
                        action="store_true", help=argparse.SUPPRESS)
    return ap


def main():
    args = _parser().parse_args()
    for worker in WORKERS:
        if getattr(args, worker.__name__):
            worker()
            return

    report = {}
    if os.environ.get("BENCH_SCALING", "1") == "1":
        report.update(_scaling_legs())
    # Control-plane tick sweep: flat-vs-hier negotiation round-trip at
    # 8/32/128 loopback processes (no data plane — the leg needs only
    # subprocesses and sockets).  BENCH_CTRL=0 skips it.
    if os.environ.get("BENCH_CTRL", "1") == "1":
        try:
            report["ctrl_sweep"] = _ctrl_sweep()
        except Exception as exc:   # noqa: BLE001 — recorded, not fatal
            report["ctrl_sweep"] = {
                "error": f"{type(exc).__name__}: {exc}"[:1000]}
    print(json.dumps(report))
    return _failed_legs(report)


def _failed_legs(report, path=""):
    """Exit status for main(): 1 when any leg that ran recorded an
    ``error`` (the report keeps the message), else 0."""
    if isinstance(report, dict):
        if "error" in report:
            print(f"bench.py: leg {path or '<top>'} failed: "
                  f"{report['error']}", file=sys.stderr)
            return 1
        return max((_failed_legs(v, f"{path}.{k}" if path else k)
                    for k, v in report.items()), default=0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
