"""Synthetic benchmark harness — prints ONE JSON line for the driver.

TPU-native counterpart of the reference's benchmark harness
(``examples/pytorch_synthetic_benchmark.py:93-110``): synthetic data, full
training step (forward + backward + gradient allreduce + SGD update),
throughput measured over timed iterations after warmup.

Two legs in the default run, merged into the one JSON line:

* ResNet-50 (the judged metric, images/sec/chip) — HBM-bandwidth-bound
  on v5e, so its MFU ceiling is ~32% regardless of skill;
* TransformerLM + Pallas flash attention at a compute-bound shape — the
  leg where MFU is the telling number.

``python bench.py --n-virtual 8`` instead runs the scaling mode on a
virtual 8-device CPU mesh: per-chip throughput at N devices over the
1-device number = scaling efficiency (the reference's published metric,
``docs/benchmarks.md:3-6`` — 90% at 512 GPUs), plus a comm/compute split
from the profiler where the backend exposes device-side collective spans.

Baseline anchor: the reference publishes 1656.82 images/sec total for
ResNet-101 on 16 Pascal GPUs = 103.55 img/sec/device
(``docs/benchmarks.md:22-39``); per BASELINE.json the judged metric is
images/sec/chip on ResNet-50, so ``vs_baseline`` is img/sec/chip divided
by that per-device anchor.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

BASELINE_PER_DEVICE = 1656.82 / 16.0   # reference docs/benchmarks.md:22-39

def _cpu_jax():
    """jax pinned to the CPU platform, persistent compile cache on — how
    every worker subprocess of this file starts.  The parent may hold the
    chip, and a chip belongs to one process."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu import compile_cache
    compile_cache.enable()
    return jax


def aot_compile(step, args):
    """Compile ONCE ahead-of-time and reuse the executable for both the
    timed run and the cost analysis (lowering again after calling would
    compile a second identical program).  Returns (callable, flops,
    bytes_accessed) from XLA's cost model; a compile error is the
    caller's error.  NOTE: XLA counts a scan body ONCE regardless of
    trip count — callers scale by steps-per-call.
    """
    compiled = step.lower(*args).compile()
    analysis = compiled.cost_analysis()
    return (compiled, float(analysis["flops"]),
            float(analysis["bytes accessed"]))


def synth_variables(jax, init_fn, rng):
    """Benchmark-grade parameter synthesis: flax's ``init`` traces and
    compiles the model's whole forward pass just to produce parameters.
    Timing is initializer-independent, so instead compile one trivial
    RNG program over the ``eval_shape`` tree:
    scale/var-style leaves get ones, bias/mean get zeros, weights get
    N(0, 0.02) — values sane enough that the loss is finite and falls.
    """
    import jax.numpy as jnp
    import jax.tree_util as jtu

    shapes = jax.eval_shape(init_fn, rng)
    leaves, treedef = jtu.tree_flatten_with_path(shapes)
    paths = [jtu.keystr(p).lower() for p, _ in leaves]
    leaves = [l for _, l in leaves]

    @jax.jit
    def make(rng):
        keys = jax.random.split(rng, len(leaves))
        out = []
        for key, path, leaf in zip(keys, paths, leaves):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            elif "scale" in path or "var" in path:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif "bias" in path or "mean" in path:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                out.append(jax.random.normal(key, leaf.shape, leaf.dtype)
                           * 0.02)
        return jax.tree.unflatten(treedef, out)

    return make(rng)


def _timed(step_fn, state, data, iters, windows):
    """Best-of-N timing windows, each ended by ``block_until_ready`` on
    the loss.  Returns (state, best seconds per window)."""
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            state = step_fn(state, data)
        state[-1].block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return state, best


def bench_resnet(jax, hvd, mesh, nchips):
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import profiling
    from horovod_tpu.jax.spmd import make_train_step
    from horovod_tpu.models import ResNet50

    # BENCH_MODEL swaps the convnet under test: the reference's scaling
    # anchors are Inception V3 / ResNet / VGG-16 (docs/benchmarks.md:3-6);
    # the judged default stays resnet50.
    model_name = os.environ.get("BENCH_MODEL", "resnet50")
    default_size = {"inception_v3": 299}.get(model_name, 224)
    # Model-aware default batch: 128 @299 through V3 would OOM a 16 GB
    # chip (the documented working config is 32, docs/benchmarks.md);
    # VGG's fc activations similarly cap lower than ResNet's.
    default_batch = {"inception_v3": 32, "vgg16": 64}.get(model_name, 128)
    batch_per_chip = int(os.environ.get("BENCH_BATCH_PER_CHIP",
                                        str(default_batch)))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", str(default_size)))
    warmup_iters = int(os.environ.get("BENCH_WARMUP", "5"))
    timed_batches = int(os.environ.get("BENCH_ITERS", "30"))
    windows = int(os.environ.get("BENCH_WINDOWS", "4"))
    batch = batch_per_chip * nchips

    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    if remat and model_name != "resnet50":
        raise SystemExit(
            f"BENCH_REMAT=1 is only plumbed for resnet50, not "
            f"{model_name!r} — running without remat would report memory "
            "numbers for a configuration you didn't ask for")
    if model_name == "resnet50":
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, remat=remat)
    elif model_name == "inception_v3":
        from horovod_tpu.models import InceptionV3
        model = InceptionV3(num_classes=1000, dtype=jnp.bfloat16)
    elif model_name == "vgg16":
        from horovod_tpu.models import VGG16
        model = VGG16(num_classes=1000, dtype=jnp.bfloat16)
    else:
        raise SystemExit(f"unknown BENCH_MODEL {model_name!r}")
    rng = jax.random.PRNGKey(42)
    # Generate the global batch already sharded over the mesh so no single
    # chip ever holds it (the reference generates per-rank data locally,
    # examples/pytorch_synthetic_benchmark.py:60-63).
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch_sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))

    @functools.partial(jax.jit, out_shardings=(batch_sharding, batch_sharding))
    def make_batch(rng):
        images = jax.random.normal(
            rng, (batch, image_size, image_size, 3), jnp.bfloat16)
        labels = jnp.zeros((batch,), jnp.int32)
        return images, labels

    images, labels = make_batch(rng)
    variables = synth_variables(
        jax, lambda r: model.init(r, images[:1], train=True), rng)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = bool(batch_stats)   # VGG-16 is BN-free

    def loss_fn(params, batch_stats, batch):
        imgs, lbls = batch
        if has_bn:
            logits, mut = model.apply(
                {"params": params, "batch_stats": batch_stats}, imgs,
                train=True, mutable=["batch_stats"])
            batch_stats = mut["batch_stats"]
        else:
            logits = model.apply({"params": params}, imgs, train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, batch_stats

    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    # batch_stats are computed per-shard from the micro-batch, so they must
    # be synced (on one chip the pmean over a size-1 axis is free in XLA).
    sync_aux = (os.environ.get("BENCH_SYNC_AUX", "1") == "1") and has_bn
    # steps_per_call > 1 scans several optimizer steps inside one XLA
    # program, amortizing the host's dispatch latency.
    spc = int(os.environ.get("BENCH_STEPS_PER_CALL", "5"))
    step = make_train_step(loss_fn, tx, mesh, sync_aux_state=sync_aux,
                           steps_per_call=spc)
    if spc > 1:
        images = jnp.broadcast_to(images[None], (spc,) + images.shape)
        labels = jnp.broadcast_to(labels[None], (spc,) + labels.shape)

    data = (images, labels)   # already mesh-sharded
    step, flops, nbytes = aot_compile(
        step, (params, batch_stats, opt_state, data))
    # max(1, ...): one untimed call is always needed to bind `loss` (and
    # to finish compilation) even when BENCH_WARMUP=0.
    for _ in range(max(1, warmup_iters)):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, data)
    np.asarray(loss)

    def one(state, data):
        params, batch_stats, opt_state, _ = state
        return step(params, batch_stats, opt_state, data)

    state = (params, batch_stats, opt_state, loss)
    state, dt = _timed(one, state, data, timed_batches, windows)
    params, batch_stats, opt_state, loss = state

    img_per_sec = batch * spc * timed_batches / dt
    per_chip = img_per_sec / nchips
    step_ms = dt / (timed_batches * spc) * 1e3

    # MFU: achieved FLOP/s over the chip's peak bf16 FLOP/s.  FLOPs per
    # call come from XLA's cost model (scan body scaled by trip count).
    # All roofline numbers are PER CHIP: XLA's cost analysis describes the
    # per-device SPMD module.
    kind = jax.devices()[0].device_kind
    peaks = profiling.device_peaks(kind)
    peak = peaks.bf16_flops
    achieved = flops * spc / (dt / timed_batches)
    mfu = achieved / peak
    hbm_util = (nbytes * spc / (dt / timed_batches)) / peaks.hbm_bytes_per_s

    # The Pascal anchor is ResNet-101 throughput; a cross-model ratio
    # would be meaningless, so only the (comparable) resnet leg reports it.
    is_resnet = model_name == "resnet50"
    return {
        "metric": f"{model_name}_synthetic_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": (round(per_chip / BASELINE_PER_DEVICE, 3)
                        if is_resnet else None),
        "step_time_ms": round(step_ms, 2),
        "batch_per_chip": batch_per_chip,
        "device_kind": kind,
        "peak_bf16_tflops_per_chip": peak / 1e12,
        "achieved_tflops_per_chip": round(achieved / 1e12, 2),
        "mfu": round(mfu, 4),
        # XLA cost-model bytes over HBM peak: a roofline proxy, not a
        # measurement — values near/over 1.0 mean the step is bandwidth-
        # dominated (some of those accesses are served from VMEM).
        "xla_bytes_over_hbm_peak": round(hbm_util, 4),
        "baseline": ("resnet101 103.55 img/s/device (16x Pascal, "
                     "docs/benchmarks.md:22-39 — the reference's only "
                     "published absolute throughput; no resnet50 number "
                     "exists)") if is_resnet else None,
    }


def bench_transformer(jax, hvd, mesh, nchips):
    """Compute-bound leg: TransformerLM + Pallas flash attention.

    ResNet-50 is HBM-bound (MFU capped ~32% on v5e); this shape is where
    the MXU can actually be fed — d_model 2048, 12 layers, seq 2048,
    causal flash attention, bf16 — so its MFU is judged against the 0.40
    bar, not the bandwidth roofline.
    """
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import profiling
    from horovod_tpu.jax.spmd import make_train_step
    from horovod_tpu.models import TransformerLM

    dim = int(os.environ.get("BENCH_TLM_DIM", "2048"))
    depth = int(os.environ.get("BENCH_TLM_DEPTH", "12"))
    heads = int(os.environ.get("BENCH_TLM_HEADS", "16"))
    vocab = int(os.environ.get("BENCH_TLM_VOCAB", "32768"))
    seq = int(os.environ.get("BENCH_TLM_SEQ", "2048"))
    batch_per_chip = int(os.environ.get("BENCH_TLM_BATCH_PER_CHIP", "8"))
    warmup_iters = int(os.environ.get("BENCH_TLM_WARMUP", "2"))
    timed_batches = int(os.environ.get("BENCH_TLM_ITERS", "8"))
    # Best-of-3 like the resnet leg's best-of-4.
    windows = int(os.environ.get("BENCH_TLM_WINDOWS", "3"))
    attn = os.environ.get("BENCH_TLM_ATTN", "flash")
    batch = batch_per_chip * nchips

    # f32 vs bf16 LayerNorm: the per-op device profile attributes ~50
    # ms/step to the f32 LN converts+stats at this shape
    # (convert_reduce_fusion, docs/benchmarks.md) — bf16 LN is the bench
    # default; set BENCH_TLM_LN_DTYPE=f32 for the conservative config.
    ln_dtype = (jnp.float32
                if os.environ.get("BENCH_TLM_LN_DTYPE", "bf16") == "f32"
                else jnp.bfloat16)
    model = TransformerLM(vocab=vocab, dim=dim, depth=depth,
                          num_heads=heads, max_len=seq, attn=attn,
                          dtype=jnp.bfloat16, head_dtype=jnp.bfloat16,
                          ln_dtype=ln_dtype)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))

    @functools.partial(jax.jit, out_shardings=sharding)
    def make_tokens(rng):
        return jax.random.randint(rng, (batch, seq + 1), 0, vocab,
                                  dtype=jnp.int32)

    tokens = make_tokens(jax.random.PRNGKey(0))
    params = synth_variables(
        jax, lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32)),
        jax.random.PRNGKey(1))["params"]

    # Memory-efficient fused CE head (default): never holds the (N, vocab)
    # f32 logits as residuals, which otherwise pushes peak HBM past the
    # chip and makes XLA auto-rematerialize one convolution per layer
    # (~40 ms/step measured; docs/benchmarks.md).
    fused_head = os.environ.get("BENCH_TLM_FUSED_XENT", "1") == "1"

    def loss_fn(params, aux, batch):
        if fused_head:
            from horovod_tpu.ops.losses import fused_softmax_xent
            h = model.apply({"params": params}, batch[:, :-1],
                            return_hidden=True)
            loss = fused_softmax_xent(
                h.reshape(-1, dim), params["head"]["kernel"],
                batch[:, 1:].reshape(-1)).mean()
        else:
            # bf16 head matmul (full MXU rate), f32 softmax for stability.
            logits = model.apply({"params": params}, batch[:, :-1])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), batch[:, 1:]).mean()
        return loss, aux

    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    # steps_per_call scans k optimizer steps inside one XLA program,
    # amortizing the ~2.4 ms host-dispatch gap (same knob as the resnet
    # leg; ~7 ms/step of wall-vs-device gap measured at spc=1).
    spc = int(os.environ.get("BENCH_TLM_STEPS_PER_CALL", "4"))
    step = make_train_step(loss_fn, tx, mesh, sync_aux_state=False,
                           steps_per_call=spc)
    if spc > 1:
        tokens = jnp.broadcast_to(tokens[None], (spc,) + tokens.shape)
    step, flops, _ = aot_compile(step, (params, {}, opt_state, tokens))

    for _ in range(max(1, warmup_iters)):   # >=1 binds `loss`
        params, aux, opt_state, loss = step(params, {}, opt_state, tokens)
    np.asarray(loss)

    def one(state, data):
        params, opt_state, _ = state
        params, _, opt_state, loss = step(params, {}, opt_state, data)
        return params, opt_state, loss

    state = (params, opt_state, loss)
    state, dt = _timed(one, state, tokens, timed_batches, windows)

    tok_per_sec = batch * seq * spc * timed_batches / dt
    step_ms = dt / (timed_batches * spc) * 1e3
    peak = profiling.device_peaks(jax.devices()[0].device_kind).bf16_flops
    # MFU by the standard model-FLOPs convention (PaLM appendix B /
    # Megatron): 6 FLOPs per matmul param per token (fwd+bwd) plus
    # attention's 12*T*d per token per layer — no credit for recompute,
    # no causal discount.  XLA's cost model is reported alongside as the
    # executed-FLOPs view (it counts rematerialization and the fused-CE
    # backward recompute, but not the Pallas kernels' matmuls, so the
    # two can land on either side of each other).
    n_matmul = 12 * depth * dim * dim + vocab * dim
    model_flops = (6 * n_matmul + 12 * depth * seq * dim) * (
        batch_per_chip * seq)
    # dt/timed_batches is seconds per CALL (= spc optimizer steps); the
    # XLA cost model counts a scan body once, so both scale by spc.
    achieved = model_flops * spc / (dt / timed_batches)
    mfu = achieved / peak
    mfu_xla = flops * spc / (dt / timed_batches) / peak
    mfu_xla_note = None
    if mfu_xla > 1.0 and spc > 1:
        # Guard against a jax/XLA change that starts multiplying the
        # scan-body cost by trip count: >1.0 MFU is physically
        # impossible, so drop our own spc scaling and say so.
        mfu_xla = flops / (dt / timed_batches) / peak
        mfu_xla_note = ("cost model appears to include the scan trip "
                        "count; spc scaling removed")
    # In-jit wire A/B (fp32 vs bf16 vs int8 gradient wire): identical
    # program except for the reduce_gradients compression, so step-time
    # deltas are the wire's own cost/benefit.  The fp32 row reuses the
    # main leg above (compression=none IS the fp32 wire).
    # The A/B legs must not touch `params`: the donating main leg above
    # consumed that buffer.  state[0] is the last step call's output and
    # stays live (nothing donates it after the timed windows).
    ab_params = state[0]
    wire_ab = None
    if (os.environ.get("BENCH_TLM_AB", "1") == "1" and nchips > 1):
        wire_ab = _injit_wire_ab(
            jax, np, build_step=lambda comp: make_train_step(
                loss_fn, tx, mesh, sync_aux_state=False,
                steps_per_call=spc, compression=comp, donate=False),
            init_state=lambda: (ab_params, {}, tx.init(ab_params)),
            data=tokens, nchips=nchips,
            iters=max(2, timed_batches // 2), spc=spc,
            fp32_sec_per_step=dt / (timed_batches * spc),
            mfu_of=lambda sec: round(model_flops / sec / peak, 4))
    elif os.environ.get("BENCH_TLM_AB", "1") == "1":
        wire_ab = {"note": "single chip: every collective is the "
                           "identity, so the gradient wire never "
                           "engages — run the multi-chip leg for the "
                           "fp32/bf16/int8 comparison"}
    # In-jit overlap A/B: identical program except reduce_gradients
    # emits per-bucket collectives in the scheduler's overlap order
    # (tail bucket first — ready while earlier layers still
    # differentiate) instead of one fused tail collective.  Bucket
    # contents are issue-order independent, so any step-time delta is
    # XLA's latency hiding, not different math.
    overlap_ab = None
    if os.environ.get("BENCH_TLM_OVERLAP_AB", "1") == "1" and nchips > 1:
        ol_iters = max(2, timed_batches // 2)

        def _overlap_leg(ov):
            ostep = make_train_step(loss_fn, tx, mesh,
                                    sync_aux_state=False,
                                    steps_per_call=spc, donate=False,
                                    overlap=ov)
            st = (ab_params, {}, tx.init(ab_params))
            ostep, _, _ = aot_compile(ostep, (*st, tokens))
            p, aux, o, loss = ostep(*st, tokens)   # warmup binds loss
            np.asarray(loss)

            def one(s, data):
                p, aux, o, _ = s
                return ostep(p, aux, o, data)

            state = (p, aux, o, loss)
            _, d = _timed(one, state, tokens, ol_iters, 2)

            def target():
                np.asarray(one(state, tokens)[-1])

            return d / (ol_iters * spc), target

        overlap_ab = {}
        for mode, ov in (("off", False), ("on", True)):
            try:
                sec, target = _overlap_leg(ov)
            except Exception as exc:   # noqa: BLE001 — per-leg, not fatal
                overlap_ab[mode] = {"error": f"{type(exc).__name__}: "
                                             f"{exc}"[:300]}
                continue
            overlap_ab[mode] = {
                "step_time_ms": round(sec * 1e3, 2),
                "comm_fraction": _comm_fraction(jax, target),
            }
        if ("step_time_ms" in overlap_ab.get("on", {})
                and "step_time_ms" in overlap_ab.get("off", {})):
            overlap_ab["on_faster_than_off"] = (
                overlap_ab["on"]["step_time_ms"]
                < overlap_ab["off"]["step_time_ms"])
            if overlap_ab["on"]["comm_fraction"] is None:
                overlap_ab["note"] = (
                    "hidden/exposed comm seconds live inside XLA's "
                    "schedule on the in-jit plane (no host-side "
                    "measurement point); the eager counterpart in "
                    "scaling_tcp_2proc.overlap_ab reports the measured "
                    "hidden/exposed split")
    elif os.environ.get("BENCH_TLM_OVERLAP_AB", "1") == "1":
        overlap_ab = {"note": "single chip: no collectives to "
                              "overlap — run the multi-chip leg"}
    return {
        "transformer_lm": {
            "tokens_per_sec_per_chip": round(tok_per_sec / nchips, 1),
            "step_time_ms": round(step_ms, 2),
            "mfu": round(mfu, 4),
            "mfu_xla_cost_model": round(mfu_xla, 4),
            **({"mfu_xla_note": mfu_xla_note} if mfu_xla_note else {}),
            "achieved_model_tflops_per_chip": round(achieved / 1e12, 2),
            "dim": dim, "depth": depth, "seq_len": seq,
            "batch_per_chip": batch_per_chip, "attn": attn,
            **({"injit_wire_ab": wire_ab} if wire_ab else {}),
            **({"overlap_ab": overlap_ab} if overlap_ab else {}),
        }
    }


def _injit_wire_ab(jax, np, *, build_step, init_state, data, nchips,
                   iters, spc, fp32_sec_per_step, mfu_of):
    """Shared fp32/bf16/int8 in-jit wire A/B: per-wire step time, MFU
    (when the caller can compute one), and the estimated bytes each wire
    dtype moves per rank per step (the same plan behind the
    ``injit.bytes#wire_dtype=*`` counters).  Every wire runs the codec
    the library selects; a leg that fails is recorded as an error and
    fails the run."""
    from horovod_tpu.compression import Compression
    from horovod_tpu.ops import quantized_collectives as qc

    params = init_state()[0]

    def leg_sec(comp):
        step = build_step(comp)
        state = init_state()
        step, _, _ = aot_compile(step, (*state, data))
        p, aux, o = state
        p, aux, o, loss = step(p, aux, o, data)   # warmup binds loss
        np.asarray(loss)

        def one(st, data):
            p, aux, o, _ = st
            return step(p, aux, o, data)

        _, d = _timed(one, (p, aux, o, loss), data, iters, 2)
        return d / (iters * spc)

    out = {}
    for wire, comp in (("fp32", Compression.none),
                       ("bf16", Compression.bf16),
                       ("int8", Compression.int8)):
        plan = qc.estimate_wire_plan(params, nchips, comp)
        if wire == "fp32" and fp32_sec_per_step is not None:
            sec = fp32_sec_per_step
        else:
            try:
                sec = leg_sec(comp)
            except Exception as exc:   # noqa: BLE001 — main() exits 1
                out[wire] = {"error": f"{type(exc).__name__}: "
                                      f"{exc}"[:300]}
                continue
        out[wire] = {
            "step_time_ms": round(sec * 1e3, 2),
            "mfu": mfu_of(sec),
            "est_wire_bytes_per_step_per_rank": plan or None,
        }
    if ("step_time_ms" in out.get("int8", {})
            and "step_time_ms" in out.get("fp32", {})):
        out["int8_faster_than_fp32"] = (out["int8"]["step_time_ms"]
                                        < out["fp32"]["step_time_ms"])
    # Autopilot leg (HOROVOD_TPU_PRECISION=auto + compression="auto"):
    # warm the per-process ladder with the measured int8-grid residual of
    # each param leaf (the stand-in for its gradient bucket at this
    # shape), then time the step with the plan the ladder actually chose.
    # The acceptance bar: within 5% of the best static wire above.
    if os.environ.get("BENCH_TLM_AUTO", "1") == "1":
        out["auto"] = _injit_auto_leg(np, params, leg_sec)
        best = min((leg["step_time_ms"]
                    for leg in (out.get(w) or {}
                                for w in ("fp32", "bf16", "int8"))
                    if "step_time_ms" in leg), default=None)
        if best and "step_time_ms" in out["auto"]:
            out["auto_vs_best_static"] = round(
                out["auto"]["step_time_ms"] / best, 4)
    return out


def _injit_auto_leg(np, params, leg_sec):
    """One ``compression="auto"`` timing leg for the in-jit wire A/B."""
    import jax.tree_util as jtu
    from horovod_tpu import precision as _precision
    from horovod_tpu.ops import quantized_collectives as qc
    saved = {k: os.environ.get(k) for k in
             ("HOROVOD_TPU_PRECISION", "HOROVOD_TPU_PRECISION_TICKS")}
    os.environ["HOROVOD_TPU_PRECISION"] = "auto"
    os.environ["HOROVOD_TPU_PRECISION_TICKS"] = "2"
    _precision.reset_autopilot()
    try:
        pilot = _precision.get_autopilot()
        rng = np.random.RandomState(0)
        for path, leaf in jtu.tree_flatten_with_path(params)[0]:
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", None)
            if (dtype is None or np.dtype(dtype) != np.float32
                    or not qc.int8_eligible(shape, np.float32)):
                continue
            try:
                g = np.asarray(leaf, dtype=np.float32)
            except RuntimeError:
                # The fp32 leg donated this buffer; a synthetic gradient
                # at the same shape stands in — the int8-grid residual
                # of gaussian data is representative for the codec.
                g = rng.standard_normal(shape).astype(np.float32)
            denom = float(np.linalg.norm(g.ravel()))
            rel = (float(np.linalg.norm(
                g - np.asarray(qc.snap_to_grid(g), dtype=np.float32)))
                / denom) if denom > 0 else 0.0
            name = f"grads{jtu.keystr(path)}"
            for _ in range(4):   # enough healthy ticks to reach int8
                pilot.note_residual(name, rel)
        levels = {}
        for path, leaf in jtu.tree_flatten_with_path(params)[0]:
            lv = pilot.level_for(f"grads{jtu.keystr(path)}")
            key = ("fp32", "bf16", "int8")[lv]
            levels[key] = levels.get(key, 0) + 1
        try:
            sec = leg_sec("auto")
        except Exception as exc:   # noqa: BLE001 — per-leg, not fatal
            return {"error": f"{type(exc).__name__}: {exc}"[:300]}
        return {
            "step_time_ms": round(sec * 1e3, 2),
            "buckets_by_wire": levels,
            "promotions": pilot.promotions,
            "demotions": pilot.demotions,
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _precision.reset_autopilot()


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
    ceiling is contention-bound like the virtual-mesh mode."""
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
    # per leg — the same policy as the chip legs' BENCH_WINDOWS — so the
    # artifact reports capability, not scheduler luck.
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


def bench_scaling(n_virtual: int):
    """Scaling mode: per-chip throughput at N virtual CPU devices vs 1,
    plus a comm/compute split from the profiler when device-side spans
    are exposed.  Plumbs the judged multi-chip metric (reference anchor:
    90% efficiency at 512 GPUs, docs/benchmarks.md:3-6) so a pod run is
    `python bench.py` away when hardware arrives."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_virtual} "
        + os.environ.get("XLA_FLAGS", ""))
    jax = _cpu_jax()
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.jax.spmd import make_train_step
    from horovod_tpu.models import ConvNet

    batch_per_chip = int(os.environ.get("BENCH_SCALE_BATCH_PER_CHIP", "8"))
    iters = int(os.environ.get("BENCH_SCALE_ITERS", "10"))
    windows = int(os.environ.get("BENCH_SCALE_WINDOWS", "3"))
    model = ConvNet(num_classes=10)
    tx = optax.sgd(0.01, momentum=0.9)

    from horovod_tpu.compression import Compression

    def run(devices, compression=Compression.none):
        n = len(devices)
        mesh = Mesh(np.asarray(devices), ("ranks",))
        batch = batch_per_chip * n
        rng = jax.random.PRNGKey(0)
        images = jax.device_put(
            jax.random.normal(rng, (batch, 32, 32, 3), jnp.float32),
            NamedSharding(mesh, P("ranks")))
        labels = jax.device_put(
            jnp.zeros((batch,), jnp.int32),
            NamedSharding(mesh, P("ranks")))
        params = model.init(rng, images[:1])["params"]

        def loss_fn(params, aux, batch):
            imgs, lbls = batch
            logits = model.apply({"params": params}, imgs)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, lbls).mean(), aux

        step = make_train_step(loss_fn, tx, mesh, sync_aux_state=False,
                               donate=False, compression=compression)
        opt_state = tx.init(params)
        data = (images, labels)
        for _ in range(3):   # warmup/compile
            *_, loss = step(params, {}, opt_state, data)
        np.asarray(loss)

        def one(state, data):
            p, o, _ = state
            p, _, o, loss = step(p, {}, o, data)
            return p, o, loss

        (_, _, loss), dt = _timed(one, (params, opt_state, loss), data,
                                  iters, windows)

        def profile_target():
            np.asarray(one((params, opt_state, loss), data)[-1])

        return batch * iters / dt / n, profile_target, params

    per_chip_1, _, _ = run(jax.devices()[:1])
    per_chip_n, profile_target, params = run(jax.devices())

    # In-jit wire A/B at N devices: same ConvNet step, only the gradient
    # wire changes (the 8 MB dense kernel is int8-eligible under the
    # default floor).  On a shared-core virtual mesh the psum is a
    # memcpy while the int8 ring does real codec work, so int8 "losing"
    # here measures codec compute, not wire savings — the note says so.
    wire_ab = None
    if os.environ.get("BENCH_SCALE_AB", "1") == "1":
        from horovod_tpu.ops import quantized_collectives as qc
        wire_ab = {}
        for wire, comp in (("fp32", Compression.none),
                           ("bf16", Compression.bf16),
                           ("int8", Compression.int8)):
            if wire == "fp32":
                per_chip_c = per_chip_n
            else:
                try:
                    per_chip_c, _, _ = run(jax.devices(), compression=comp)
                except Exception as exc:   # noqa: BLE001 — per-leg
                    wire_ab[wire] = {"error": f"{type(exc).__name__}: "
                                              f"{exc}"[:300]}
                    continue
            plan = qc.estimate_wire_plan(params, n_virtual, comp)
            wire_ab[wire] = {
                "step_time_ms": round(batch_per_chip / per_chip_c * 1e3,
                                      2),
                "images_per_sec_per_chip": round(per_chip_c, 2),
                "est_wire_bytes_per_step_per_rank": plan or None,
            }
        if ("step_time_ms" in wire_ab.get("int8", {})
                and "step_time_ms" in wire_ab.get("fp32", {})):
            wire_ab["int8_faster_than_fp32"] = (
                wire_ab["int8"]["step_time_ms"]
                < wire_ab["fp32"]["step_time_ms"])
            wire_ab["note"] = (
                "virtual CPU mesh: collectives are intra-process "
                "memcpys, so the int8 leg pays the codec FLOPs without "
                "any wire to save — see scaling_tcp_2proc."
                "wire_compression for the cross-process wire where the "
                "byte savings are real")

    # Comm/compute split measured on the ACTUAL benchmark step (not a
    # probe), where the backend exposes device-side spans.
    comm_frac = _comm_fraction(jax, profile_target)
    out = {
        "metric": "scaling_efficiency",
        "n_devices": n_virtual,
        "images_per_sec_per_chip_1": round(per_chip_1, 2),
        "images_per_sec_per_chip_n": round(per_chip_n, 2),
        "scaling_efficiency": round(per_chip_n / per_chip_1, 4),
        **({"injit_wire_ab": wire_ab} if wire_ab else {}),
        "comm_fraction": comm_frac,
        "note": "virtual CPU mesh: the N-device run shares the same host "
                "cores as the 1-device run, so efficiency ~1/N is the "
                "expected ceiling here — this mode validates the metric "
                "plumbing and collective layout; hardware efficiency "
                "needs a pod slice",
    }
    if comm_frac is None:
        out["comm_fraction_note"] = (
            "null by backend limitation: the CPU platform's profiler "
            "emits no device-side spans (verified: trace contains only "
            "the /host:CPU process), so a trace-based comm/compute "
            "split cannot exist here — see scaling_tcp_2proc."
            "comm_fraction for the directly measured value on the "
            "cross-process data plane")
    return out


def _comm_fraction(jax, run_step):
    """Fraction of device-side per-op span time in collectives while
    ``run_step()`` (the actual benchmark step) executes under the
    profiler; None on the CPU platform, whose profiler writes no device
    spans.  Capture + parsing come from :mod:`horovod_tpu.profiling` so
    there is exactly one trace-format implementation in the tree."""
    if jax.default_backend() == "cpu":
        return None
    from horovod_tpu import profiling

    tmp = profiling.capture(run_step, warmup=0, iters=3)
    rows = profiling.per_op_rooflines(
        tmp, profiling.device_peaks(jax.devices()[0].device_kind))
    total = sum(r["ms"] for r in rows)
    comm = sum(r["ms"] for r in rows
               if any(k in r["op"].lower() for k in (
                   "all-reduce", "all_reduce", "allreduce",
                   "all-gather", "collective", "psum")))
    return round(comm / total, 4)


def _scaling_legs():
    """Both scaling legs, each in its own subprocess (the parent holds
    the chip; the legs pin themselves to the CPU platform, which a child
    can do while its parent holds the chip).  Always returns a dict — a
    failed leg records its error and main() then exits non-zero."""
    import subprocess
    import sys

    legs = {}
    n_virtual = int(os.environ.get("BENCH_SCALE_VIRTUAL_DEVICES", "8"))
    try:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--n-virtual", str(n_virtual)],
            capture_output=True, text=True, timeout=900, env=env)
        lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
        if out.returncode != 0 or not lines:
            raise RuntimeError(
                f"virtual leg exited {out.returncode}; "
                f"stdout: {out.stdout[-800:]!r} "
                f"stderr: {out.stderr[-800:]!r}")
        legs[f"scaling_virtual_{n_virtual}dev"] = json.loads(lines[-1])
    except Exception as exc:   # noqa: BLE001 — recorded, not fatal
        legs[f"scaling_virtual_{n_virtual}dev"] = {
            "error": f"{type(exc).__name__}: {exc}"[:1000]}
    try:
        legs["scaling_tcp_2proc"] = bench_scaling_tcp()
    except Exception as exc:   # noqa: BLE001
        legs["scaling_tcp_2proc"] = {
            "error": f"{type(exc).__name__}: {exc}"[:300]}
    return legs


def write_bench_summary(report: dict,
                        path: str = None) -> str | None:
    """Consolidated headline artifact next to the raw report stream.

    The raw ``BENCH_rNN`` files the growth driver captures are stdout
    tails — truncated, unparsed, and useless for trend lines.  This
    writes ``BENCH_r08.json`` (override with ``BENCH_SUMMARY_FILE``; set
    it empty to skip) holding just the judged numbers: single/virtual
    step times and MFU, TCP scaling efficiency, the zero-copy transport
    speedup, the CRC integrity overhead, the observatory's on/off
    step-time overhead, the adaptive-precision autopilot's A/B against
    the best static wire on both planes, and the hierarchical control
    topology's tick speedup at the 128-process sweep point — each pulled
    from the full report when the producing leg ran, ``None`` when it
    was skipped or failed."""
    if path is None:
        path = os.environ.get("BENCH_SUMMARY_FILE", "BENCH_r08.json")
    if not path:
        return None

    def get(*keys):
        node = report
        for k in keys:
            if not isinstance(node, dict) or k not in node:
                return None
            node = node[k]
        return node

    tcp = report.get("scaling_tcp_2proc") or {}
    summary = {
        "resnet_step_time_ms": get("step_time_ms"),
        "resnet_mfu": get("mfu"),
        "transformer_step_time_ms": get("transformer_lm", "step_time_ms"),
        "transformer_mfu": get("transformer_lm", "mfu"),
        "virtual_scaling_efficiency": get(
            "scaling_virtual_8dev", "scaling_efficiency"),
        "tcp_scaling_efficiency": tcp.get("scaling_efficiency"),
        "tcp_step_time_ms": get(
            "scaling_tcp_2proc", "wire_compression", "fp32",
            "step_time_ms"),
        "tcp_comm_fraction": tcp.get("comm_fraction"),
        "overlap_ab": tcp.get("overlap_ab"),
        "shm_vs_uds_speedup_256k_plus": get(
            "scaling_tcp_2proc", "xport_sweep",
            "shm_vs_uds_speedup_256k_plus"),
        "crc_overhead_256k_plus": get(
            "scaling_tcp_2proc", "xport_sweep", "crc_overhead_256k_plus",
            "max"),
        # Observatory hot-path cost: off/on step time + overhead fraction
        # from the TCP leg's A/B (acceptance budget <= 2%).
        "observe_ab": tcp.get("observe_ab"),
        # Adaptive-precision autopilot vs the best static wire, both
        # planes (acceptance bar: ratio <= 1.05).
        "precision_auto_tcp_vs_best_static": get(
            "scaling_tcp_2proc", "wire_compression", "auto",
            "vs_best_static"),
        "precision_auto_injit_vs_best_static": get(
            "transformer_lm", "injit_wire_ab", "auto_vs_best_static"),
        "precision_auto_injit": get(
            "transformer_lm", "injit_wire_ab", "auto"),
        # Hierarchical control plane: flat-vs-hier negotiation tick at
        # the sweep's 128-process point (acceptance bar: > 1, i.e. the
        # per-host aggregation tier beats the flat O(procs) root gather).
        "hier_tick_speedup_128p": get(
            "ctrl_sweep", "hier_tick_speedup_128p"),
    }
    try:
        with open(path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        return None
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-virtual", type=int, default=0,
                    help="run the scaling mode on N virtual CPU devices")
    ap.add_argument("--no-transformer", action="store_true",
                    help="skip the transformer MFU leg")
    ap.add_argument("--tcp-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--solo-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--xport-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--recovery-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--policy-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--publish-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ctrl-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.ctrl_worker:
        ctrl_worker()
        return

    if args.tcp_worker:
        tcp_worker()
        return
    if args.solo_worker:
        solo_worker()
        return
    if args.xport_worker:
        xport_worker()
        return
    if args.recovery_worker:
        recovery_worker()
        return
    if args.policy_worker:
        policy_worker()
        return
    if args.publish_worker:
        publish_worker()
        return
    if args.n_virtual:
        print(json.dumps(bench_scaling(args.n_virtual)))
        return

    import jax
    import horovod_tpu as hvd
    from horovod_tpu import compile_cache

    compile_cache.enable()
    hvd.init()
    mesh = hvd.ranks_mesh()
    nchips = hvd.size()

    if os.environ.get("BENCH_ONLY") == "transformer":
        report = bench_transformer(jax, hvd, mesh, nchips)
        print(json.dumps(report))
        return _failed_legs(report)
    report = bench_resnet(jax, hvd, mesh, nchips)
    if not args.no_transformer and os.environ.get(
            "BENCH_TRANSFORMER", "1") == "1":
        report.update(bench_transformer(jax, hvd, mesh, nchips))
    # The reference's headline metric is scaling efficiency
    # (docs/benchmarks.md:3-6); the default artifact carries both
    # localhost approximations of it (virtual mesh + 2-process TCP).
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
    write_bench_summary(report)
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
