"""Model-zoo coverage for the reference's benchmark models beyond ResNet:
Inception V3 (the 90%-scaling anchor) and VGG-16 (the 68% one), reference
``docs/benchmarks.md:3-6``.  Full-resolution shapes are checked abstractly
(eval_shape — no CPU convolutions at 299x299); training is exercised for
real at a reduced resolution through make_train_step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.models import InceptionV3, VGG16


def test_inception_v3_canonical_shape():
    model = InceptionV3(num_classes=1000)
    out = jax.eval_shape(
        lambda r, x: model.init_with_output(r, x, train=False)[0],
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 299, 299, 3), jnp.float32))
    assert out.shape == (2, 1000) and out.dtype == jnp.float32
    # Param budget sanity: V3 is ~23.8M params (torchvision, no aux head).
    variables = jax.eval_shape(
        lambda r, x: model.init(r, x, train=False),
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 299, 299, 3), jnp.float32))
    n = sum(int(np.prod(v.shape))
            for v in jax.tree.leaves(variables["params"]))
    assert 20e6 < n < 28e6, n


def test_vgg16_canonical_shape():
    model = VGG16(num_classes=1000)
    out = jax.eval_shape(
        lambda r, x: model.init_with_output(r, x)[0],
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert out.shape == (2, 1000) and out.dtype == jnp.float32
    variables = jax.eval_shape(
        lambda r, x: model.init(r, x),
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
    n = sum(int(np.prod(v.shape))
            for v in jax.tree.leaves(variables["params"]))
    assert 130e6 < n < 145e6, n   # canonical VGG-16: ~138M


@pytest.mark.parametrize("model_cls,size", [
    pytest.param(InceptionV3, 75, marks=pytest.mark.time_limit(
        600, "compiles InceptionV3 for 8 virtual devices: 71 s beside the "
             "five other workers of the driver's command on the sandbox "
             "(117 s until PR 56 made its init one program)")),
    (VGG16, 32)])
def test_benchmark_models_train_data_parallel(hvd, model_cls, size):
    """One real DP train step at reduced resolution: finite falling loss,
    synced batch stats where the model has them."""
    n = hvd.size()
    mesh = hvd.ranks_mesh()
    model = model_cls(num_classes=10, dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(rng, (2 * n, size, size, 3), jnp.float32)
    labels = jnp.tile(jnp.arange(2), (n,)).astype(jnp.int32)
    # (ONE program: an eager ``init`` compiles every layer's ops one by one.)
    variables = jax.jit(lambda k, x: model.init(k, x, train=True))(
        rng, images[:1])
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    has_bn = bool(batch_stats)

    def loss_fn(params, aux, batch):
        imgs, lbls = batch
        if has_bn:
            logits, mut = model.apply(
                {"params": params, "batch_stats": aux}, imgs, train=True,
                mutable=["batch_stats"])
            aux = mut["batch_stats"]
        else:
            logits = model.apply({"params": params}, imgs, train=True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, lbls).mean()
        return loss, aux

    tx = optax.sgd(0.01)
    step = make_train_step(loss_fn, tx, mesh, sync_aux_state=has_bn,
                           donate=False)
    sh = NamedSharding(mesh, P("ranks"))
    batch = (jax.device_put(images, sh), jax.device_put(labels, sh))
    opt_state = tx.init(params)
    losses = []
    for _ in range(3):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
