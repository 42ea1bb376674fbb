"""MNIST training — TPU-native counterpart of the reference's MNIST
examples (``examples/tensorflow_mnist.py``, ``examples/pytorch_mnist.py``):
same 4-step recipe (init → shard data by rank → wrap optimizer →
broadcast initial state), ConvNet model, per-epoch metric averaging.

Runs on real MNIST if an ``mnist.npz`` is available locally (set
``--data``), else on a deterministic synthetic stand-in so the example is
runnable in hermetic environments (no download at import time, unlike the
reference which fetches the dataset).

Usage:  python examples/jax_mnist.py --epochs 2
        (multi-chip: runs data-parallel over every visible TPU chip)
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import callbacks as hvd_callbacks
from horovod_tpu.data import ShardedLoader, epoch_batches
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.models import ConvNet


def load_data(path):
    """(train_x, train_y, test_x, test_y) in [0,1] NHWC."""
    if path and os.path.exists(path):
        with np.load(path) as d:
            return (d["x_train"].astype(np.float32) / 255.0, d["y_train"],
                    d["x_test"].astype(np.float32) / 255.0, d["y_test"])
    # Synthetic stand-in: class-dependent blobs, learnable to high accuracy.
    rng = np.random.RandomState(0)
    n_train, n_test = 8192, 1024
    y = rng.randint(0, 10, n_train + n_test)
    x = rng.randn(n_train + n_test, 28, 28).astype(np.float32) * 0.1
    for c in range(10):
        mask = y == c
        x[mask, c * 2:(c * 2) + 4, c * 2:(c * 2) + 4] += 1.0
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-rank batch size")
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.5)
    p.add_argument("--data", type=str, default="",
                   help="path to an mnist.npz; synthetic data if absent")
    args = p.parse_args()

    # Step 1: initialize from the pod topology (no mpirun).
    hvd.init()
    mesh = hvd.ranks_mesh()
    n = hvd.size()
    global_batch = args.batch_size * n

    train_x, train_y, test_x, test_y = load_data(args.data)

    model = ConvNet()
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]

    # Step 3: wrap the optimizer; LR scaled by size per the reference recipe
    # (README step 3), warmup ramps into it.  inject_hyperparams exposes
    # lr/momentum to the callbacks.
    tx = hvd.jax.DistributedOptimizer(
        optax.inject_hyperparams(optax.sgd)(
            learning_rate=args.lr * n, momentum=args.momentum))
    opt_state = tx.init(params)

    def loss_fn(params, aux, batch):
        imgs, lbls = batch
        logits = model.apply({"params": params}, imgs[..., None])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean(), aux

    train_step = make_train_step(loss_fn, tx, mesh)

    state = hvd_callbacks.TrainingState(params=params, opt_state=opt_state)
    # Derive steps_per_epoch exactly as the loader batches: per-process
    # rows (n // P) over per-process batch (global_batch // P).  The
    # naive len(train_x) // global_batch drifts from the real step count
    # whenever P does not divide global_batch or n, and the warmup
    # schedule would follow the wrong clock.
    per_proc_batch = global_batch // hvd.process_count()
    steps_per_epoch = (len(train_x) // hvd.process_count()) // per_proc_batch
    cbs = hvd_callbacks.CallbackList(
        [
            # Step 4: broadcast initial state from rank 0.
            hvd_callbacks.BroadcastGlobalVariablesCallback(0),
            hvd_callbacks.MetricAverageCallback(),
            hvd_callbacks.LearningRateWarmupCallback(
                warmup_epochs=1, steps_per_epoch=steps_per_epoch, verbose=1),
        ],
        state, params={"steps": steps_per_epoch})

    cbs.on_train_begin()
    train_y32 = train_y.astype(np.int32)
    for epoch in range(args.epochs):
        cbs.on_epoch_begin(epoch)
        # Step 2 of the recipe: DistributedSampler-style epoch shard —
        # identical shuffle everywhere, process-strided rows, equal batch
        # counts (horovod_tpu.data; reference pytorch_mnist.py:98-103).
        # Each process stages its share of the global batch;
        # shard_for_process (inside ShardedLoader) assembles the global
        # sharded array, and the prefetch thread stays a step ahead.
        loader = ShardedLoader(
            lambda e=epoch: epoch_batches(
                train_x, train_y32,
                global_batch // hvd.process_count(),
                rank=hvd.process_index(), size=hvd.process_count(),
                seed=1234 + e),
            mesh)
        losses = []
        for b, batch in enumerate(loader):
            cbs.on_batch_begin(b)
            state.params, _, state.opt_state, loss = train_step(
                state.params, {}, state.opt_state, batch)
            if losses:
                # Lagged read (see jax_mnist_advanced.py): at most one step
                # queued behind the one that runs.
                losses[-1].block_until_ready()
            losses.append(loss)
            cbs.on_batch_end(b)
        logs = {"loss": float(np.mean([np.asarray(l) for l in losses]))}
        cbs.on_epoch_end(epoch, logs=logs)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={logs['loss']:.4f} "
                  f"lr={logs.get('lr', float('nan')):.4f}")

    # Eval (rank-replicated; metric averaged across ranks for parity with
    # pytorch_mnist.py's metric_average, :44-125).
    logits = model.apply({"params": state.params},
                         jnp.asarray(test_x)[..., None])
    acc = float(np.mean(np.argmax(np.asarray(logits), -1) == test_y))
    acc = float(np.asarray(hvd.allreduce(np.float32(acc), average=True,
                                         name="test.accuracy")))
    if hvd.rank() == 0:
        print(f"test accuracy: {acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
