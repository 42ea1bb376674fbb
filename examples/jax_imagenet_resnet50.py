"""ImageNet ResNet-50 — TPU-native counterpart of the reference's
``examples/keras_imagenet_resnet50.py``: LR warmup + staircase schedule
callbacks, rank-0 checkpointing, restore-and-broadcast resume
(reference ``:64-103, 132-151``).

Data: an ImageNet-format numpy shard directory via ``--data``; without it a
synthetic generator keeps the example hermetic (the reference requires the
real dataset on disk).
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import callbacks as hvd_callbacks
from horovod_tpu import checkpoint as hvd_checkpoint
from horovod_tpu.jax.spmd import make_train_step, shard_batch
from horovod_tpu.models import ResNet50


def synthetic_batches(global_batch, image_size, steps, seed):
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        x = rng.randn(global_batch, image_size, image_size, 3).astype(
            np.float32)
        y = rng.randint(0, 1000, global_batch).astype(np.int32)
        yield x, y


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-rank batch size")
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="per-rank base LR (scaled by size, reference :107)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=5e-5)
    p.add_argument("--warmup-epochs", type=int, default=5)
    p.add_argument("--checkpoint-dir", type=str, default="./checkpoints")
    p.add_argument("--steps-per-epoch", type=int, default=100,
                   help="synthetic-data steps per epoch")
    p.add_argument("--image-size", type=int, default=224)
    args = p.parse_args()

    hvd.init()
    mesh = hvd.ranks_mesh()
    n = hvd.size()
    global_batch = args.batch_size * n

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, args.image_size, args.image_size, 3))
    variables = model.init(rng, sample, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # Optimizer: SGD + momentum + weight decay, LR scaled by size
    # (reference keras_imagenet_resnet50.py:105-112), hyperparams exposed
    # for the callbacks.
    tx = hvd.jax.DistributedOptimizer(
        optax.inject_hyperparams(
            lambda learning_rate, momentum: optax.chain(
                optax.add_decayed_weights(args.wd),
                optax.sgd(learning_rate, momentum=momentum)),
        )(learning_rate=args.base_lr * n, momentum=args.momentum),
        compression=hvd.Compression.bf16)
    opt_state = tx.init(params)

    def loss_fn(params, batch_stats, batch):
        imgs, lbls = batch
        logits, mut = model.apply(
            {"params": params, "batch_stats": batch_stats}, imgs,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, mut["batch_stats"]

    train_step = make_train_step(loss_fn, tx, mesh)

    state = hvd_callbacks.TrainingState(
        params=params, opt_state=opt_state, aux_state=batch_stats)

    # Resume: agree on the epoch, restore on rank 0, broadcast everywhere
    # (reference keras_imagenet_resnet50.py:64-103 — there via
    # hvd.load_model + broadcast; here the state pytree broadcast does
    # both).  The optimizer state resumes too, so SGD momentum survives
    # a restart exactly as the reference's loaded optimizer does;
    # params-and-optimizer-only jobs can use checkpoint.save_model /
    # load_model(directory) instead, which also rebuilds the optimizer
    # from its persisted spec.
    ckpt_state = {"params": state.params, "batch_stats": state.aux_state,
                  "opt_state": state.opt_state}
    # optional_keys: checkpoints written before opt_state was added
    # still resume (momentum restarts fresh in that case).
    restored, resume_epoch = hvd_checkpoint.restore_and_broadcast(
        args.checkpoint_dir, ckpt_state, optional_keys=("opt_state",))
    state.params = restored["params"]
    state.aux_state = restored["batch_stats"]
    state.opt_state = restored["opt_state"]
    # The restored hyperparams carry the checkpoint's DECAYED lr; the
    # schedule callbacks below capture initial_lr at on_train_begin and
    # re-apply their multipliers per epoch, so the live hyperparams must
    # be reset to the configured base values — otherwise a resume past a
    # decay boundary double-applies the decay.  (Momentum buffers — the
    # actual optimizer STATE — stay restored.)
    hvd_callbacks.find_hyperparams(state.opt_state).update(
        hvd_callbacks.find_hyperparams(opt_state))

    cbs = hvd_callbacks.CallbackList(
        [
            hvd_callbacks.BroadcastGlobalVariablesCallback(0),
            hvd_callbacks.MetricAverageCallback(),
            # Warmup then staircase decay — the reference's exact schedule
            # (keras_imagenet_resnet50.py:114-121).
            hvd_callbacks.LearningRateWarmupCallback(
                warmup_epochs=args.warmup_epochs,
                steps_per_epoch=args.steps_per_epoch, verbose=1),
            hvd_callbacks.LearningRateScheduleCallback(
                multiplier=1.0, start_epoch=args.warmup_epochs,
                end_epoch=30),
            hvd_callbacks.LearningRateScheduleCallback(
                multiplier=1e-1, start_epoch=30, end_epoch=60),
            hvd_callbacks.LearningRateScheduleCallback(
                multiplier=1e-2, start_epoch=60, end_epoch=80),
            hvd_callbacks.LearningRateScheduleCallback(
                multiplier=1e-3, start_epoch=80),
        ],
        state, params={"steps": args.steps_per_epoch})

    cbs.on_train_begin()
    for epoch in range(resume_epoch + 1, args.epochs):
        cbs.on_epoch_begin(epoch)
        losses = []
        for b, (x, y) in enumerate(synthetic_batches(
                global_batch, args.image_size, args.steps_per_epoch,
                seed=epoch)):
            cbs.on_batch_begin(b)
            batch = shard_batch((x, y), mesh)
            state.params, state.aux_state, state.opt_state, loss = \
                train_step(state.params, state.aux_state, state.opt_state,
                           batch)
            if losses:
                # Lagged read (see jax_mnist_advanced.py): at most one step
                # queued behind the one that runs.
                losses[-1].block_until_ready()
            losses.append(loss)
            cbs.on_batch_end(b)
        logs = {"loss": float(np.mean([np.asarray(l) for l in losses]))}
        cbs.on_epoch_end(epoch, logs=logs)
        # Rank-0-only checkpoint (reference convention, README step 6).
        hvd_checkpoint.save(
            args.checkpoint_dir,
            {"params": state.params, "batch_stats": state.aux_state,
             "opt_state": state.opt_state},
            epoch=epoch)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={logs['loss']:.4f} "
                  f"lr={logs.get('lr', float('nan')):.5f}")


if __name__ == "__main__":
    main()
