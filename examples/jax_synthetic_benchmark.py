"""Synthetic benchmark — TPU-native counterpart of the reference's
``examples/pytorch_synthetic_benchmark.py``: synthetic images, full training
step, img/sec mean ± 1.96σ per device and aggregate (reference ``:93-110``).

Fusion on/off comparison (the reference's Tensor Fusion on/off): pass
``--no-fusion`` to disable trace-time gradient fusion — gradients are then
allreduced one XLA collective per tensor instead of letting XLA bucket them,
mirroring ``HOROVOD_FUSION_THRESHOLD=0``.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.jax.spmd import make_train_step, shard_batch
from horovod_tpu.models import ResNet50, ResNet101, ResNet152


MODELS = {"resnet50": ResNet50, "resnet101": ResNet101,
          "resnet152": ResNet152}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50", choices=sorted(MODELS))
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-rank batch size (reference default 32)")
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--no-fusion", action="store_true",
                   help="one collective per gradient tensor (fusion off)")
    args = p.parse_args()

    hvd.init()
    mesh = hvd.ranks_mesh()
    n = hvd.size()
    batch = args.batch_size * n

    model = MODELS[args.model](num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(
        rng, (batch, args.image_size, args.image_size, 3), jnp.bfloat16)
    labels = jnp.zeros((batch,), jnp.int32)
    variables = model.init(rng, images[:1], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch_stats, data):
        imgs, lbls = data
        logits, mut = model.apply(
            {"params": params, "batch_stats": batch_stats}, imgs,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, mut["batch_stats"]

    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    if args.no_fusion:
        # Per-tensor collectives: an optimization barrier between gradient
        # allreduces stops XLA from bucketing them (the runtime analogue of
        # HOROVOD_FUSION_THRESHOLD=0).
        from jax import shard_map

        def step_body(params, batch_stats, opt_state, data):
            # Varying view of the params so the cotangents are raw
            # per-shard gradients (see make_train_step); the explicit
            # per-tensor pmean below is then the mean, not a double-sum.
            from horovod_tpu.parallel._vma import ensure_varying_tree
            params_v = ensure_varying_tree(params, ("ranks",))
            (loss, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_v, batch_stats, data)
            leaves, treedef = jax.tree.flatten(grads)
            reduced = []
            for leaf in leaves:
                leaf = lax.pmean(leaf, "ranks")
                leaf = lax.optimization_barrier(leaf)
                reduced.append(leaf)
            grads = jax.tree.unflatten(treedef, reduced)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            new_bs = jax.tree.map(lambda a: lax.pmean(a, "ranks"), new_bs)
            return params, new_bs, opt_state, lax.pmean(loss, "ranks")

        step = jax.jit(shard_map(
            step_body, mesh=mesh,
            in_specs=(P(), P(), P(), P("ranks")),
            out_specs=(P(), P(), P(), P()), check_vma=True),
            donate_argnums=(0, 1, 2))
    else:
        step = make_train_step(loss_fn, tx, mesh, sync_aux_state=True)

    data = shard_batch((images, labels), mesh)

    def run_once():
        nonlocal params, batch_stats, opt_state
        for _ in range(args.num_batches_per_iter):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, data)
        np.asarray(loss)   # host read = hard sync

    print(f"Model: {args.model}, batch size (per rank): {args.batch_size}, "
          f"ranks: {n}, fusion: {not args.no_fusion}")
    for _ in range(max(1, args.num_warmup_batches //
                       args.num_batches_per_iter)):
        run_once()

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_once()
        dt = time.perf_counter() - t0
        ips = batch * args.num_batches_per_iter / dt
        print(f"Iter #{i}: {ips:.1f} img/sec total")
        img_secs.append(ips / n)

    # Reporting format parity: mean ± 1.96σ per device and aggregate
    # (reference pytorch_synthetic_benchmark.py:93-110).
    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    print(f"Img/sec per rank: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    print(f"Total img/sec on {n} rank(s): {n * img_sec_mean:.1f} "
          f"+-{n * img_sec_conf:.1f}")


if __name__ == "__main__":
    main()
