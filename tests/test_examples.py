"""Example smoke tests — the reference CI runs shortened versions of its
examples as integration tests (.travis.yml:112-130, e.g. tensorflow_mnist
with steps 20000→100); same idea here with tiny configs."""

import runpy
import sys
import types

import numpy as np
import pytest


def run_example(monkeypatch, path, argv):
    monkeypatch.setattr(sys, "argv", ["x"] + argv)
    return runpy.run_path(path, run_name="__main__")


@pytest.fixture()
def run_ahead(monkeypatch):
    """Counts how far an example's loop runs ahead of the device: the most
    steps dispatched whose loss the host had not yet waited for.

    The examples are what users copy onto a CPU host, and there the PjRt
    client deadlocks once 32 steps are in flight on the 8-device mesh (the
    launches waiting for a slot hold the pool threads that the all-reduce's
    participants need), so their loops read the previous step's loss."""
    from horovod_tpu.jax import spmd
    seen = types.SimpleNamespace(dispatched=0, waited_for=0, most=0)

    class Loss:
        def __init__(self, value):
            seen.dispatched += 1
            self.value, self.number = value, seen.dispatched
            seen.most = max(seen.most, seen.dispatched - seen.waited_for)

        def block_until_ready(self):
            seen.waited_for = max(seen.waited_for, self.number)
            return self.value.block_until_ready()

        def __array__(self, *args, **kwargs):
            return np.asarray(self.block_until_ready(), *args, **kwargs)

    make = spmd.make_train_step

    def make_train_step(*args, **kwargs):
        step = make(*args, **kwargs)

        def counted(*a, **k):
            *state, loss = step(*a, **k)
            return (*state, Loss(loss))
        return counted

    monkeypatch.setattr(spmd, "make_train_step", make_train_step)
    return seen


def test_mnist_example(hvd, monkeypatch, run_ahead):
    monkeypatch.setattr(sys, "argv", ["x", "--epochs", "1",
                                      "--batch-size", "16"])
    ns = runpy.run_path("examples/jax_mnist.py")
    acc = ns["main"]()
    assert acc > 0.9, f"synthetic MNIST should be learnable, got acc={acc}"
    assert run_ahead.dispatched == 64 and run_ahead.most <= 2, run_ahead


def test_mnist_advanced_example(hvd, monkeypatch, tmp_path, capsys,
                                run_ahead):
    monkeypatch.setattr(sys, "argv", [
        "x", "--epochs", "2", "--batch-size", "16", "--warmup-epochs", "1",
        "--checkpoint-dir", str(tmp_path)])
    ns = runpy.run_path("examples/jax_mnist_advanced.py")
    acc = ns["main"]()
    assert acc > 0.9, f"augmented synthetic MNIST should learn, got {acc}"
    # Rank-0 checkpoint convention: one checkpoint per epoch was written.
    assert (tmp_path / "checkpoint-1").exists()
    # The second epoch is past the warm-up, whose callbacks read the
    # optimizer state every step: nothing but the loop's own read holds
    # the host back there.
    assert run_ahead.dispatched == 128 and run_ahead.most <= 2, run_ahead


def test_mnist_estimator_example(hvd, monkeypatch, tmp_path, capsys,
                                 run_ahead):
    # Total steps are divided by world size (reference estimator :178).
    first = 40 // hvd.size()
    args = ["--batch-size", "16", "--model-dir", str(tmp_path),
            "--checkpoint-every", "3"]
    monkeypatch.setattr(sys, "argv", ["x", "--steps", "40"] + args)
    ns = runpy.run_path("examples/jax_mnist_estimator.py")
    ns["main"]()
    out = capsys.readouterr().out
    assert f"global_step={first}" in out
    # Second run auto-resumes from the saved global step.
    monkeypatch.setattr(sys, "argv", ["x", "--steps", "16"] + args)
    ns = runpy.run_path("examples/jax_mnist_estimator.py")
    ns["main"]()
    out = capsys.readouterr().out
    assert f"global_step={first + 16 // hvd.size()}" in out
    assert run_ahead.dispatched == first + 16 // hvd.size()
    assert run_ahead.most <= 2, run_ahead


def test_model_parallel_example(hvd, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [
        "x", "--steps", "30", "--batch-size", "8", "--dim", "16",
        "--hidden-per-chip", "8"])
    ns = runpy.run_path("examples/jax_model_parallel.py")
    losses = ns["main"]()
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    out = capsys.readouterr().out
    assert "sharded PartitionSpec(None, 'tp')" in out


def test_pipeline_transformer_example(hvd, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["x", "--steps", "25", "--dim", "16",
                                      "--heads", "2", "--seq-len", "8"])
    ns = runpy.run_path("examples/jax_pipeline_transformer.py")
    losses = ns["main"]()
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    out = capsys.readouterr().out
    assert f"pipeline stages={hvd.size()}" in out


def test_pod_training_example(hvd, monkeypatch, capsys):
    """The zero-config multi-controller recipe, degraded to one process
    over the 8 virtual chips (the real 2-process run lives in
    tests/test_multicontroller.py)."""
    monkeypatch.setattr(sys, "argv", ["x", "--steps", "60"])
    ns = runpy.run_path("examples/jax_pod_training.py")
    loss0, final = ns["main"]()
    assert final < 0.05 * loss0, (loss0, final)


def test_word2vec_example(hvd, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [
        "x", "--steps", "30", "--vocab", "300", "--dim", "16",
        "--batch-size", "16"])
    runpy.run_path("examples/jax_word2vec.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "pairs/sec" in out


@pytest.mark.time_limit(
    600, "runs the ResNet-50 example twice on 8 virtual devices: 138 s "
         "beside the five other workers of the driver's command on the "
         "sandbox")
def test_imagenet_example_resume(hvd, monkeypatch, tmp_path, capsys,
                                 run_ahead):
    args = ["--batch-size", "2", "--steps-per-epoch", "2",
            "--image-size", "32", "--warmup-epochs", "1",
            "--checkpoint-dir", str(tmp_path)]
    monkeypatch.setattr(sys, "argv", ["x", "--epochs", "1"] + args)
    runpy.run_path("examples/jax_imagenet_resnet50.py", run_name="__main__")
    monkeypatch.setattr(sys, "argv", ["x", "--epochs", "2"] + args)
    runpy.run_path("examples/jax_imagenet_resnet50.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "epoch 0" in out and "epoch 1" in out
    # The resume run must not retrain epoch 0.
    assert out.count("epoch 0:") == 1
    assert run_ahead.dispatched == 4 and run_ahead.most <= 2, run_ahead
