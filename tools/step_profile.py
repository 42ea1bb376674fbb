"""Profile the exact bench transformer (or resnet) train step on the
chip and aggregate device-side per-op spans against the chip's peaks.
Usage: python tools/step_profile.py [resnet]"""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import functools
import os
import sys

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu import compile_cache, profiling
from horovod_tpu.jax.spmd import make_train_step
from bench import synth_variables


def profile_and_dump(run, label, topn=40):
    """``run`` must end in block_until_ready."""
    log_dir = profiling.capture(run, warmup=2, iters=2)
    rows = profiling.per_op_rooflines(
        log_dir, profiling.device_peaks(jax.devices()[0].device_kind))
    print(f"== {label}: module {profiling.device_time_ms(log_dir):.2f} ms, "
          f"XLA-ops total {sum(r['ms'] for r in rows):.2f} ms over 2 steps "
          f"(trace: {log_dir}) ==")
    profiling.print_rooflines(rows, top=topn)


def transformer():
    from horovod_tpu.models import TransformerLM
    dim, depth, heads, vocab, seq, bpc = 2048, 12, 16, 32768, 2048, 8
    attn = os.environ.get("BENCH_TLM_ATTN", "flash")
    ln_dtype = (jnp.float32
                if os.environ.get("BENCH_TLM_LN_DTYPE", "bf16") == "f32"
                else jnp.bfloat16)
    model = TransformerLM(vocab=vocab, dim=dim, depth=depth,
                          num_heads=heads, max_len=seq, attn=attn,
                          dtype=jnp.bfloat16, head_dtype=jnp.bfloat16,
                          ln_dtype=ln_dtype)
    mesh = hvd.ranks_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))

    @functools.partial(jax.jit, out_shardings=sharding)
    def make_tokens(rng):
        return jax.random.randint(rng, (bpc, seq + 1), 0, vocab,
                                  dtype=jnp.int32)

    tokens = make_tokens(jax.random.PRNGKey(0))
    params = synth_variables(
        jax, lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32)),
        jax.random.PRNGKey(1))["params"]

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
            logits = model.apply({"params": params}, batch[:, :-1])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), batch[:, 1:]).mean()
        return loss, aux

    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    step = make_train_step(loss_fn, tx, mesh, sync_aux_state=False)
    state = {}

    def run():
        nonlocal params, opt_state
        params, _, opt_state, loss = step(params, {}, opt_state, tokens)
        jax.block_until_ready(loss)

    profile_and_dump(run, f"transformer step attn={attn}")


def resnet():
    from horovod_tpu.models import ResNet50
    bpc, size = 128, 224
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    mesh = hvd.ranks_mesh()
    rng = jax.random.PRNGKey(42)
    images = jax.random.normal(rng, (bpc, size, size, 3), jnp.bfloat16)
    labels = jnp.zeros((bpc,), jnp.int32)
    variables = synth_variables(
        jax, lambda r: model.init(r, images[:1], train=True), rng)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch_stats, batch):
        imgs, lbls = batch
        logits, mut = model.apply(
            {"params": params, "batch_stats": batch_stats}, imgs,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, mut["batch_stats"]

    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    step = make_train_step(loss_fn, tx, mesh, sync_aux_state=True,
                           steps_per_call=1)
    data = (images, labels)

    def run():
        nonlocal params, batch_stats, opt_state
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, data)
        jax.block_until_ready(loss)

    profile_and_dump(run, "resnet50 step bpc=128")


if __name__ == "__main__":
    compile_cache.enable()
    hvd.init()
    if "resnet" in sys.argv:
        resnet()
    else:
        transformer()
