"""Estimator-style MNIST — TPU-native counterpart of the reference's
``examples/tensorflow_mnist_estimator.py``: a structured train/evaluate
loop driven by a model_fn, with the rank-0-only ``model_dir`` checkpoint
convention (``tensorflow_mnist_estimator.py:147`` — "save checkpoints only
on worker 0 to prevent other workers from corrupting them") and total
steps divided by world size (``:178``).

The Estimator here owns: auto-resume from the newest checkpoint in
``model_dir``, the broadcast-after-init/restore hook, periodic rank-0
checkpointing, and sharded evaluation — so the user script is just a
model_fn and two input_fns.

Usage:  python examples/jax_mnist_estimator.py --steps 200
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import checkpoint as hvd_checkpoint
from horovod_tpu.jax.spmd import make_eval_step, make_train_step, shard_batch
from horovod_tpu.models import ConvNet


class Estimator:
    """Structured training loop over the framework's SPMD step.

    ``model_fn(params, batch) -> (loss, predictions)``; ``params`` created
    by ``init_fn(rng)``.  ``model_dir`` follows the reference's estimator
    convention: pass a path on every rank — only rank 0 writes, every rank
    restores via rank-0-read + broadcast.
    """

    def __init__(self, init_fn, model_fn, optimizer, model_dir=None,
                 checkpoint_every=0):
        hvd.init()
        self.mesh = hvd.ranks_mesh()
        self.model_fn = model_fn
        self.model_dir = model_dir
        self.checkpoint_every = checkpoint_every
        self.tx = hvd.jax.DistributedOptimizer(optimizer)
        self.params = init_fn(jax.random.PRNGKey(0))
        self.opt_state = self.tx.init(self.params)
        self.global_step = 0

        def loss_fn(params, aux, batch):
            loss, _ = model_fn(params, batch)
            return loss, aux

        self._train_step = make_train_step(loss_fn, self.tx, self.mesh)

        def metrics_fn(params, aux, batch):
            loss, preds = model_fn(params, batch)
            _, labels = batch
            return {"loss": loss,
                    "accuracy": jnp.mean(preds == labels)}

        self._eval_step = make_eval_step(metrics_fn, self.mesh)

        # Auto-resume: rank 0 scans/restores, state broadcast to all ranks
        # (restore_and_broadcast broadcasts even when nothing was found, so
        # a fresh init is also rank-consistent).
        if model_dir:
            restored, resume = hvd_checkpoint.restore_and_broadcast(
                model_dir, {"params": self.params,
                            "opt_state": self.opt_state,
                            "global_step": np.asarray(0, np.int64)})
            self.params = restored["params"]
            self.opt_state = restored["opt_state"]
            if resume >= 0:
                self.global_step = int(np.asarray(restored["global_step"]))
        else:
            self.params = hvd.jax.broadcast_parameters(
                self.params, root_rank=0)

    def _save(self):
        if self.model_dir:
            hvd_checkpoint.save(
                self.model_dir,
                {"params": self.params, "opt_state": self.opt_state,
                 "global_step": self.global_step},
                self.global_step)

    def train(self, input_fn, steps):
        """Run ``steps // size`` optimizer steps (reference ``:178`` scales
        total work by world size); ``input_fn(step) -> global batch``."""
        local_steps = max(1, steps // hvd.size())
        loss = None
        for _ in range(local_steps):
            batch = shard_batch(input_fn(self.global_step), self.mesh)
            prev = loss
            self.params, _, self.opt_state, loss = self._train_step(
                self.params, {}, self.opt_state, batch)
            if prev is not None:
                # Lagged read (see jax_mnist_advanced.py): at most one step
                # queued behind the one that runs.
                prev.block_until_ready()
            self.global_step += 1
            if (self.checkpoint_every
                    and self.global_step % self.checkpoint_every == 0):
                self._save()
        self._save()
        return {"loss": float(np.asarray(loss)),
                "global_step": self.global_step}

    def evaluate(self, input_fn, steps):
        totals = {}
        for step in range(steps):
            batch = shard_batch(input_fn(step), self.mesh)
            m = self._eval_step(self.params, {}, batch)
            for k, v in m.items():
                totals.setdefault(k, []).append(float(np.asarray(v)))
        return {k: float(np.mean(v)) for k, v in totals.items()}


def load_data():
    rng = np.random.RandomState(0)
    n_train, n_test = 8192, 1024
    y = rng.randint(0, 10, n_train + n_test)
    x = rng.randn(n_train + n_test, 28, 28).astype(np.float32) * 0.1
    for c in range(10):
        mask = y == c
        x[mask, c * 2:(c * 2) + 4, c * 2:(c * 2) + 4] += 1.0
    return (x[:n_train], y[:n_train].astype(np.int32),
            x[n_train:], y[n_train:].astype(np.int32))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200,
                   help="total train steps across all ranks")
    p.add_argument("--batch-size", type=int, default=64,
                   help="per-rank batch size")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--model-dir", type=str, default="")
    p.add_argument("--checkpoint-every", type=int, default=50)
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    global_batch = args.batch_size * n
    train_x, train_y, test_x, test_y = load_data()

    model = ConvNet()

    def init_fn(rng):
        return model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]

    def model_fn(params, batch):
        imgs, lbls = batch
        logits = model.apply({"params": params}, imgs[..., None])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, jnp.argmax(logits, -1)

    est = Estimator(init_fn, model_fn,
                    optax.sgd(args.lr * n, momentum=0.9),
                    model_dir=args.model_dir or None,
                    checkpoint_every=args.checkpoint_every)

    rng = np.random.RandomState(est.global_step + 1)

    def train_input_fn(step):
        idx = rng.randint(0, len(train_x), global_batch)
        return train_x[idx], train_y[idx]

    def eval_input_fn(step):
        sl = slice(step * global_batch, (step + 1) * global_batch)
        return test_x[sl], test_y[sl]

    result = est.train(train_input_fn, steps=args.steps)
    metrics = est.evaluate(eval_input_fn, steps=len(test_x) // global_batch)
    if hvd.rank() == 0:
        print(f"global_step={result['global_step']} "
              f"eval_loss={metrics['loss']:.4f} "
              f"eval_accuracy={metrics['accuracy']:.4f}")
    return metrics["accuracy"]


if __name__ == "__main__":
    main()
