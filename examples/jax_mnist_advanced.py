"""Advanced MNIST — TPU-native counterpart of the reference's
``examples/keras_mnist_advanced.py``: data augmentation with **per-rank
random streams**, the full callback stack (broadcast, metric averaging,
LR warmup), and rank-0-only checkpointing.

Where the reference seeds a separate host-side ``ImageDataGenerator`` per
worker (``keras_mnist_advanced.py:105-121``), the TPU-native version
compiles augmentation *into the training step*: each shard derives its
stream by folding ``lax.axis_index`` (its rank) and the step counter into
the replicated PRNG key, so every rank sees distinct augmentations with no
host-side pipeline at all — the random shifts/scales fuse into the same
XLA program as the forward pass.

Usage:  python examples/jax_mnist_advanced.py --epochs 4
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

import horovod_tpu as hvd
from horovod_tpu import callbacks as hvd_callbacks
from horovod_tpu import checkpoint as hvd_checkpoint
from horovod_tpu.jax.spmd import make_eval_step, make_train_step, shard_batch
from horovod_tpu.models import ConvNet

MAX_SHIFT = 3        # random translation, pixels (reference uses ~8% ≈ 2.2)
SCALE_RANGE = 0.08   # random brightness/zoom-like multiplicative jitter


def augment(key, images):
    """Random shift + multiplicative jitter, static shapes throughout.

    Per-image keys via vmap; shift implemented as pad + dynamic_slice so
    XLA lowers it to cheap HBM addressing rather than a gather.
    """
    n, h, w = images.shape[:3]
    keys = jax.random.split(key, n)

    def one(k, img):
        k_shift, k_scale = jax.random.split(k)
        dy, dx = jax.random.randint(k_shift, (2,), 0, 2 * MAX_SHIFT + 1)
        padded = jnp.pad(img, ((MAX_SHIFT, MAX_SHIFT),
                               (MAX_SHIFT, MAX_SHIFT)))
        img = lax.dynamic_slice(padded, (dy, dx), (h, w))
        scale = 1.0 + jax.random.uniform(
            k_scale, (), minval=-SCALE_RANGE, maxval=SCALE_RANGE)
        return img * scale

    return jax.vmap(one)(keys, images)


def load_data():
    """Deterministic synthetic MNIST stand-in (hermetic; no downloads).

    The class signal is blob *size* (shift-invariant), so random-shift
    augmentation makes the task harder without making it ambiguous.
    """
    rng = np.random.RandomState(0)
    n_train, n_test = 8192, 1024
    y = rng.randint(0, 10, n_train + n_test)
    x = rng.randn(n_train + n_test, 28, 28).astype(np.float32) * 0.1
    for c in range(10):
        mask = y == c
        sz = 2 * c + 2
        x[mask, 4:4 + sz, 4:4 + sz] += 1.0
    return (x[:n_train], y[:n_train].astype(np.int32),
            x[n_train:], y[n_train:].astype(np.int32))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-rank batch size")
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--warmup-epochs", type=int, default=2)
    p.add_argument("--checkpoint-dir", type=str, default="")
    args = p.parse_args()

    hvd.init()
    mesh = hvd.ranks_mesh()
    n = hvd.size()
    global_batch = args.batch_size * n

    train_x, train_y, test_x, test_y = load_data()
    model = ConvNet()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]

    tx = hvd.jax.DistributedOptimizer(
        optax.inject_hyperparams(optax.sgd)(
            learning_rate=args.lr * n, momentum=0.9))
    opt_state = tx.init(params)

    axis = tuple(mesh.axis_names)

    def loss_fn(params, aux, batch):
        imgs, lbls = batch
        # Per-rank stream: fold (rank, step) into the replicated key.  The
        # TPU-native analogue of the reference's per-worker generator seed.
        key = jax.random.fold_in(
            jax.random.fold_in(aux["key"], lax.axis_index(axis)),
            aux["step"])
        imgs = augment(key, imgs)
        logits = model.apply({"params": params}, imgs[..., None])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, {"key": aux["key"], "step": aux["step"] + 1}

    train_step = make_train_step(loss_fn, tx, mesh)

    def eval_metrics(params, aux, batch):
        imgs, lbls = batch
        logits = model.apply({"params": params}, imgs[..., None])
        return {"accuracy": jnp.mean(jnp.argmax(logits, -1) == lbls)}

    eval_step = make_eval_step(eval_metrics, mesh)

    state = hvd_callbacks.TrainingState(params=params, opt_state=opt_state)
    steps_per_epoch = len(train_x) // global_batch
    cbs = hvd_callbacks.CallbackList(
        [
            hvd_callbacks.BroadcastGlobalVariablesCallback(0),
            hvd_callbacks.MetricAverageCallback(),
            hvd_callbacks.LearningRateWarmupCallback(
                warmup_epochs=args.warmup_epochs,
                steps_per_epoch=steps_per_epoch, verbose=1),
        ],
        state, params={"steps": steps_per_epoch})

    aux = {"key": jax.random.PRNGKey(42), "step": jnp.int32(0)}
    rng_np = np.random.RandomState(1234)
    cbs.on_train_begin()
    for epoch in range(args.epochs):
        cbs.on_epoch_begin(epoch)
        perm = rng_np.permutation(len(train_x))
        losses = []
        for b in range(steps_per_epoch):
            cbs.on_batch_begin(b)
            idx = perm[b * global_batch:(b + 1) * global_batch]
            batch = shard_batch((train_x[idx], train_y[idx]), mesh)
            state.params, aux, state.opt_state, loss = train_step(
                state.params, aux, state.opt_state, batch)
            if losses:
                # Lagged read: with step b queued, wait for step b-1.  The
                # device never idles and the host never runs an epoch ahead.
                # The CPU client (the 8-virtual-device mesh) deadlocks once
                # 32 steps are in flight: launches that wait for a slot take
                # the pool threads its all-reduce participants need.
                losses[-1].block_until_ready()
            losses.append(loss)
            cbs.on_batch_end(b)
        logs = {"loss": float(np.mean([np.asarray(l) for l in losses]))}
        cbs.on_epoch_end(epoch, logs=logs)
        # Rank-0-only checkpointing (reference convention, README step 6);
        # other ranks no-op inside save().
        if args.checkpoint_dir:
            hvd_checkpoint.save(
                args.checkpoint_dir,
                {"params": state.params, "opt_state": state.opt_state},
                epoch)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={logs['loss']:.4f} "
                  f"lr={logs.get('lr', float('nan')):.4f}")

    n_eval = (len(test_x) // global_batch) * global_batch
    accs = []
    for b in range(n_eval // global_batch):
        sl = slice(b * global_batch, (b + 1) * global_batch)
        m = eval_step(state.params, {},
                      shard_batch((test_x[sl], test_y[sl]), mesh))
        accs.append(float(np.asarray(m["accuracy"])))
    acc = float(np.mean(accs))
    if hvd.rank() == 0:
        print(f"test accuracy: {acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
