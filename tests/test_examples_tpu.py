"""Real-chip example drives (opt-in, like test_flash_tpu).

The example suite runs on the 8-virtual-device CPU mesh; the round-3
regression (single-chip fast path breaking every DistributedOptimizer
example on the real TPU while CI stayed green) showed the deployment
topology needs its own automated leg.  Run with::

    HOROVOD_TPU_TEST_REAL_TPU=1 python -m pytest tests/test_examples_tpu.py

Examples run as SUBPROCESSES with a clean environment, so the parent
suite's CPU-platform conftest does not apply; each subprocess resolves
whatever accelerator JAX finds, and needs it to itself: run this file
alone, from a parent that stays off jax.  Skipped unless explicitly
opted in.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("HOROVOD_TPU_TEST_REAL_TPU") != "1",
    reason="opt-in hardware leg (set HOROVOD_TPU_TEST_REAL_TPU=1)")

_EXAMPLES = [
    ("examples/jax_mnist.py", ["--epochs", "1", "--batch-size", "64"]),
    ("examples/jax_mnist_advanced.py",
     ["--epochs", "1", "--batch-size", "64", "--warmup-epochs", "1",
      "--checkpoint-dir", "{tmp}"]),
    # The sparse allgather path through the stock DistributedOptimizer
    # (round-5 rework) — single-chip collectives degenerate but the
    # IndexedSlices routing and scatter-to-dense update still execute.
    ("examples/jax_word2vec.py",
     ["--steps", "30", "--vocab", "500", "--batch-size", "16"]),
]


@pytest.mark.parametrize("path,argv", _EXAMPLES,
                         ids=[p.split("/")[-1] for p, _ in _EXAMPLES])
def test_example_on_real_chip(path, argv, tmp_path):
    argv = [a.format(tmp=tmp_path) if "{tmp}" in a else a for a in argv]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # Let the subprocess resolve the real accelerator platform.
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_TPU_TIMELINE", None)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, path] + argv,
                         capture_output=True, text=True, timeout=900,
                         env=env, cwd=repo)
    assert out.returncode == 0, f"{out.stdout[-3000:]}\n{out.stderr[-3000:]}"
