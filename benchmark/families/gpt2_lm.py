"""Family ``gpt2_lm``: a dense GPT-2-shaped causal LM, keyed like Hugging
Face's ``GPT2LMHeadModel`` config (``n_embd``, ``n_layer``, ``n_head``,
``n_inner``, ``n_positions``, ``vocab_size``).

The system under test is the repo's ``TransformerLM`` with the flash
kernels and the fused cross-entropy head; everything else in this file is
the benchmark's own yardstick for it: the host-batch maker, the model
FLOPs, the flash kernels' operations and bytes, and a plain float32
reference of the same mathematics that reads the same parameter tree.

Departures of the program's model from GPT-2 as published are not
papered over here: the reference computes what the program's model
defines (tanh GELU, LayerNorm eps 1e-6, no attention biases, untied
head); the configuration file lists each under ``departures``.
"""

from __future__ import annotations

import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: head 128 keeps the fused flash_qkv_proj path.
# A few hundred tokens average bfloat16's rounding out far less than a
# real batch does, so the preset brings its own, looser tolerances.
TINY = {"n_embd": 128, "n_layer": 2, "n_head": 1, "n_inner": 512,
        "n_positions": 128, "vocab_size": 512,
        "tolerances": {"loss_rel": 2e-3, "grad_rel": 5e-2}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's: one at each
# end of the network and one inside the first and the last block.
GRAD_LEAVES = (("tok_emb", "embedding"),
               ("block_0", "attn", "qkv", "kernel"),
               ("block_{last}", "fc2", "kernel"),
               ("head", "kernel"))
GRAD_SAMPLES = 1          # one sequence on both sides

# What the flash kernels are called in the lowered step (``kernel_name``).
FLASH_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkdv_kernel")


def grad_leaves(cfg):
    last = cfg["n_layer"] - 1
    return [tuple(p.format(last=last) for p in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import TransformerLM

    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise ValueError("TransformerLM's MLP is 4x wide; n_inner must be "
                         f"4*n_embd, got {cfg['n_inner']}")
    return TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["n_embd"], depth=cfg["n_layer"],
        num_heads=cfg["n_head"], max_len=cfg["n_positions"], attn="flash",
        dtype=jnp.bfloat16, head_dtype=jnp.bfloat16, ln_dtype=jnp.bfloat16)


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, cfg["n_positions"]), jnp.int32))["params"]
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["n_embd"]

    def loss(params, aux, tokens):
        h = model.apply({"params": params}, tokens[:, :-1],
                        return_hidden=True)
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"],
            tokens[:, 1:].reshape(-1))
        return per_token.mean(), aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"gpt2_lm trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences of ``n_positions`` tokens plus the label of the
    last one, int32, as a tokenizer's packer hands them over."""
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["n_positions"] + 1), dtype=np.int32)


def units_per_sample(cfg) -> int:
    """Tokens a sequence contributes to ``tokens_per_s_chip``."""
    return cfg["n_positions"]


# --------------------------------------------------- FLOPs, from shapes


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight."""
    d, inner, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    return [("qkv", d, 3 * d, L), ("proj", d, d, L),
            ("fc1", d, inner, L), ("fc2", inner, d, L),
            ("head", d, cfg["vocab_size"], 1)]


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward of
    every weight matmul (2 + 4 FLOPs per weight) and of attention's two
    products over the causal half of the (T, T) square.  Recomputation
    is not counted; the embedding lookup is no matmul.

    ``6 * n_matmul + 6 * L * T * d``, where the repo's ``bench.py`` has
    ``12 * L * T * d`` (full attention): at equal speed this reads about
    6% (relative) lower."""
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = cfg["n_layer"] * cfg["n_positions"] * cfg["n_embd"]
    return 6.0 * n_matmul + 6.0 * attn


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the flash kernels of one step need on one
    chip, from their shapes ``(B, T, H, D)``, causal.

    FLOPs: the forward's two products (QK^T, PV) and the backward's five
    (S again, dV, dP, dQ, dK: the score recompute is part of the flash
    algorithm, which never stores S), each ``2*B*H*T*T*D`` halved by the
    causal mask.  Bytes: each kernel's compulsory traffic in bf16 — the
    forward reads q, k, v and writes o; the dq kernel reads q, k, v, do
    and writes dq; the dk/dv kernel reads the same four and writes dk,
    dv — plus the float32 row statistics (lse, and delta in the
    backward)."""
    B, T, H = batch_per_chip, cfg["n_positions"], cfg["n_head"]
    D = cfg["n_embd"] // H
    product = 2.0 * B * H * T * T * D / 2          # one causal product
    tensor = B * T * H * D * 2                     # one bf16 (B,T,H,D)
    stat = B * H * T * 4                           # one f32 (B,H,T)
    # The split backward recomputes S and dP in both of its kernels; the
    # algorithm needs each once, so the roofline charges five products.
    flops = cfg["n_layer"] * (2 + 5) * product
    nbytes = cfg["n_layer"] * ((4 * tensor + stat)            # forward
                               + (5 * tensor + 2 * stat)      # dq
                               + (6 * tensor + 2 * stat))     # dk/dv
    return {"flops": flops, "bytes": nbytes, "shape": [B, T, H, D],
            "calls_per_step": cfg["n_layer"]}


# ------------------------------------------------------ plain reference


def reference_loss(cfg):
    """``f(params, aux, tokens) -> loss`` in plain ``jax.numpy`` float32:
    GPT-2's decoder as published (pre-LN blocks, learned positions,
    multi-head causal softmax attention scaled by 1/sqrt(head), 4x MLP,
    final LayerNorm, linear head, mean token cross-entropy), with the
    program model's own choices where it departs (see module docstring).
    One sequence at a time through ``lax.map``; no kernels, no fusion
    tricks, the (T, T) scores held in full."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, L = cfg["n_head"], cfg["n_layer"]

    def layer_norm(x, p, eps=1e-6):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]

    def gelu_tanh(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    def one_sequence(params, seq):
        inp, labels = seq[:-1], seq[1:]
        T = inp.shape[0]
        x = (params["tok_emb"]["embedding"][inp]
             + params["pos_emb"]["embedding"][:T])
        C = x.shape[-1]
        D = C // H
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(L):
            p = params[f"block_{i}"]
            h = layer_norm(x, p["ln1"])
            q, k, v = jnp.split(h @ p["attn"]["qkv"]["kernel"], 3, axis=-1)
            q, k, v = (t.reshape(T, H, D) for t in (q, k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(T, C) @ p["attn"]["proj"]["kernel"]
            h = layer_norm(x, p["ln2"])
            h = gelu_tanh(h @ p["fc1"]["kernel"] + p["fc1"]["bias"])
            x = x + h @ p["fc2"]["kernel"] + p["fc2"]["bias"]
        x = layer_norm(x, params["ln_f"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def loss(params, aux, tokens):
        with jax.default_matmul_precision("highest"):
            params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            return lax.map(lambda s: one_sequence(params, s), tokens).mean()

    return loss
