"""Family ``resnet_v15``: bottleneck ResNet v1.5 (He et al. Table 1, the
stride of each down-sampling bottleneck on its 3x3) on NHWC images.

The system under test is the repo's ``models.resnet.ResNet`` in bfloat16
with batch statistics synced by ``make_train_step``; the rest of this
file is the benchmark's own yardstick: uint8 host batches as a decoder
hands them over, convolution and dense FLOPs from shapes, and a plain
float32 reference reading the same parameter tree.
"""

from __future__ import annotations

import numpy as np

THROUGHPUT = ("images_per_s_chip", "images/s/chip")
SYNC_AUX_STATE = True

# The CPU rehearsal's sizes, with tolerances for four 32x32 images.
TINY = {"stage_sizes": [1, 1], "num_filters": 8, "num_classes": 10,
        "image_size": 32,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 5e-1}}
TINY_BATCH_PER_CHIP = 4

# The scale of each block's last batch norm starts at zero, so at
# initialisation every other leaf of a residual branch has a gradient of
# exactly zero: that scale is the branch's one leaf worth comparing.
GRAD_LEAVES = (("conv_init", "kernel"),
               ("BottleneckBlock_0", "conv_proj", "kernel"),
               ("BottleneckBlock_{last}", "BatchNorm_2", "scale"),
               ("head", "kernel"))
# float32 residuals of a whole 256-image batch do not fit beside the
# parameters; batch statistics tie the images of a batch together, so
# both sides take the same first 32 images.
GRAD_SAMPLES = 32

# ImageNet channel statistics on the 0..255 scale (torchvision's
# 0.485/0.456/0.406 and 0.229/0.224/0.225 times 255).
MEAN = (123.675, 116.28, 103.53)
STD = (58.395, 57.12, 57.375)


def grad_leaves(cfg):
    last = sum(cfg["stage_sizes"]) - 1
    return [tuple(p.format(last=last) for p in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models.resnet import ResNet
    return ResNet(stage_sizes=list(cfg["stage_sizes"]),
                  num_filters=cfg["num_filters"],
                  num_classes=cfg["num_classes"], dtype=jnp.bfloat16)


def init(cfg, key):
    import jax.numpy as jnp
    s = cfg["image_size"]
    v = _model(cfg).init(key, jnp.zeros((1, s, s, 3), jnp.bfloat16),
                         train=True)
    return v["params"], v["batch_stats"]


def _normalise(images, dtype):
    import jax.numpy as jnp
    x = images.astype(jnp.float32)
    return ((x - jnp.asarray(MEAN)) / jnp.asarray(STD)).astype(dtype)


def loss_fn(cfg):
    import jax.numpy as jnp
    import optax

    model = _model(cfg)

    def loss(params, batch_stats, batch):
        images, labels = batch
        logits, mut = model.apply(
            {"params": params, "batch_stats": batch_stats},
            _normalise(images, jnp.bfloat16), train=True,
            mutable=["batch_stats"])
        xent = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return xent, mut["batch_stats"]

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "sgd":
        raise ValueError(f"resnet_v15 trains with sgd, not {o['name']!r}")
    return optax.chain(optax.add_decayed_weights(o["weight_decay"]),
                       optax.sgd(o["learning_rate"], momentum=o["momentum"]))


def host_batch(cfg, rng: np.random.Generator, n: int):
    s = cfg["image_size"]
    return (rng.integers(0, 256, (n, s, s, 3), dtype=np.uint8),
            rng.integers(0, cfg["num_classes"], (n,), dtype=np.int32))


def units_per_sample(cfg) -> int:
    return 1


# --------------------------------------------------- FLOPs, from shapes


def _out(size: int, stride: int) -> int:
    return -(-size // stride)          # SAME padding: ceil(size / stride)


def layers(cfg):
    """Every convolution and the dense head of one forward pass as
    ``(name, out_h, out_w, kh, kw, cin, cout)``, from the architecture's
    table and the input size."""
    rows = []
    s = _out(cfg["image_size"], 2)
    f = cfg["num_filters"]
    rows.append(("conv_init", s, s, 7, 7, 3, f))
    s = _out(s, 2)                     # 3x3 max-pool, stride 2
    cin, block = f, 0
    for stage, count in enumerate(cfg["stage_sizes"]):
        width = f * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            so = _out(s, stride)
            name = f"BottleneckBlock_{block}"
            rows.append((f"{name}.Conv_0", s, s, 1, 1, cin, width))
            rows.append((f"{name}.Conv_1", so, so, 3, 3, width, width))
            rows.append((f"{name}.Conv_2", so, so, 1, 1, width, 4 * width))
            if stride != 1 or cin != 4 * width:
                rows.append((f"{name}.conv_proj", so, so, 1, 1, cin,
                             4 * width))
            cin, s, block = 4 * width, so, block + 1
    rows.append(("head", 1, 1, 1, 1, cin, cfg["num_classes"]))
    return rows


def forward_macs(cfg) -> int:
    return sum(h * w * kh * kw * cin * cout
               for _, h, w, kh, kw, cin, cout in layers(cfg))


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained image requires: every convolution and the
    head forward (2 FLOPs a multiply-add), its weight gradient and its
    input gradient — except the first convolution's input gradient,
    which nothing needs (images are not trained).  Batch-norm, ReLU and
    pooling are not matrix work and are not counted; nor is anything the
    compiler recomputes."""
    rows = layers(cfg)
    first = rows[0]
    first_macs = first[1] * first[2] * first[3] * first[4] * first[5] * first[6]
    return 2.0 * (3 * forward_macs(cfg) - first_macs)


# ------------------------------------------------------ plain reference


def reference_loss(cfg):
    """``f(params, batch_stats, (images, labels)) -> loss`` in plain
    ``jax.numpy``/``lax`` float32: the whole batch at once (batch
    statistics tie the images together), training-mode batch norm with
    the biased variance of the batch, eps 1e-5.  Convolutions pad as the
    program's do ('SAME'; (3, 3) on the 7x7 stem): with stride 2 on an
    even size that pads one pixel on the far side only, where
    torchvision pads one on each side — listed under ``departures``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, p, stride=1, padding="SAME"):
        return lax.conv_general_dilated(
            x, p["kernel"], (stride, stride), padding,
            dimension_numbers=dn)

    def batch_norm(x, p, eps=1e-5):
        mu = x.mean((0, 1, 2))
        var = ((x - mu) ** 2).mean((0, 1, 2))
        return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]

    def bottleneck(x, p, stride):
        y = jax.nn.relu(batch_norm(conv(x, p["Conv_0"]), p["BatchNorm_0"]))
        y = jax.nn.relu(batch_norm(conv(y, p["Conv_1"], stride),
                                   p["BatchNorm_1"]))
        y = batch_norm(conv(y, p["Conv_2"]), p["BatchNorm_2"])
        if "conv_proj" in p:
            x = batch_norm(conv(x, p["conv_proj"], stride), p["norm_proj"])
        return jax.nn.relu(x + y)

    def loss(params, batch_stats, batch):
        images, labels = batch
        with jax.default_matmul_precision("highest"):
            params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            x = _normalise(images, jnp.float32)
            x = conv(x, params["conv_init"], 2, [(3, 3), (3, 3)])
            x = jax.nn.relu(batch_norm(x, params["bn_init"]))
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                [(0, 0), (1, 1), (1, 1), (0, 0)])
            block = 0
            for stage, count in enumerate(cfg["stage_sizes"]):
                for j in range(count):
                    stride = 2 if stage > 0 and j == 0 else 1
                    x = bottleneck(x, params[f"BottleneckBlock_{block}"],
                                   stride)
                    block += 1
            x = x.mean((1, 2))
            logits = x @ params["head"]["kernel"] + params["head"]["bias"]
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            return (lse - picked).mean()

    return loss
