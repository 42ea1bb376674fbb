"""Family ``olmo_hybrid_lm``: the dense hybrid stack of linear and full
attention, keyed like Hugging Face's ``olmo_hybrid`` config
(``layer_types``, ``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, ``rms_norm_eps``, ``vocab_size``).

The first ``num_hidden_layers`` entries of ``layer_types`` are run;
``vocab_size`` is this chip's slice of the vocabulary; ``sequence_length``
is the training sequence (``max_position_embeddings`` stays the model's
declared 65,536); ``linear_chunk_size`` is the delta rule's chunk (not a
key of config.json: ``assumed``).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.OlmoHybridLM``): ``ops/gated_delta.py``'s chunked
gated delta rule in ``models/linear_attention.py``'s mixer, the flash
kernels through the split q, k, v path with QK-norm, the dense SwiGLU
MLP, the fused cross-entropy head.  Everything else in this file is the
benchmark's own yardstick: the host-batch maker, the model FLOPs, the
delta rule's and the flash kernels' operations and bytes, and a plain
float32 reference of the same mathematics that reads the same parameter
tree.  The loss is the mean next-token cross-entropy and nothing else.

A checkout whose program has no gated delta rule cannot run this family:
the import fails, at once and before jax is loaded.
"""

from __future__ import annotations

import math
import os

import numpy as np

if not os.path.isfile(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
        "horovod_tpu", "ops", "gated_delta.py")):
    raise ImportError("olmo_hybrid_lm needs horovod_tpu/ops/gated_delta.py: "
                      "this checkout's program has no gated delta rule")

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# What the flash kernels are called in the lowered step (``kernel_name``);
# the delta rule is plain XLA, so they are the step's only Pallas kernels.
FLASH_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkdv_kernel")

# The CPU rehearsal's sizes: one layer of each kind, LF (every mixer
# multiplies the relative rounding error of its input some 2.7 times — it
# is trilinear in q, k, v and gated —, and behind three of them at these
# widths bfloat16 leaves the first layer's leaves 20-100% off), attention
# of two heads of 128 (the lane-aligned kernels, interpreted), four chunks
# a sequence.  The preset brings its own, looser tolerances.
TINY = {"num_hidden_layers": 2,
        "layer_types": ["linear_attention", "full_attention"],
        "hidden_size": 256, "intermediate_size": 384,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 16, "linear_value_head_dim": 32,
        "linear_chunk_size": 16, "sequence_length": 64, "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1}}
TINY_BATCH_PER_CHIP = 2

LETTER = {"linear_attention": "L", "full_attention": "F"}

# Leaves whose gradients are compared with the reference's, with {l} and
# {f} the first linear-attention and the first full-attention layer.  The
# decay's path is held by ``lin/a`` (d x H numbers), not by ``lin/A_log``
# and ``lin/dt_bias``: those are H = 30 numbers each, sums over every token
# of ``dg_t g_t`` (the two read alike to three digits, softplus being exp
# at these biases) whose sign is a matter of chance, so their relative
# error is the large leaves' times a draw between 0.5 and 2.1 (chip, PR 32,
# 70 seeds: configs/olmo-hybrid-7b.json, ``grad_rel_why``) and no bound that
# the precision control fails holds them.  In float32 the CPU tests compare
# every leaf, these two among them (tests/test_hybrid_stack.py).
GRAD_LEAVES = (("layer_{l}", "lin", "q", "kernel"),
               ("layer_{l}", "lin", "conv", "kernel"),
               ("layer_{l}", "lin", "a", "kernel"),
               ("layer_{l}", "lin", "b", "kernel"),
               ("layer_{l}", "lin", "out", "kernel"),
               ("layer_{f}", "attn", "qkv", "kernel"),
               ("layer_{f}", "mlp", "up", "kernel"),
               ("head", "kernel"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    return "".join(LETTER[kind]
                   for kind in cfg["layer_types"][:cfg["num_hidden_layers"]])


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"l": p.index("L"), "f": p.index("F")}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import OlmoHybridLM

    as_published = {
        "model_type": "olmo_hybrid", "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if (cfg["num_key_value_heads"] != cfg["num_attention_heads"]
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]):
        differs["heads"] = "key and value heads as many as query heads"
    if differs:
        raise ValueError(f"olmo_hybrid_lm runs the stack as published; "
                         f"got {differs}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return OlmoHybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"],
        num_heads=cfg["num_attention_heads"],
        lin=dict(num_heads=cfg["linear_num_value_heads"],
                 key_dim=cfg["linear_key_head_dim"],
                 value_dim=cfg["linear_value_head_dim"],
                 conv_kernel=cfg["linear_conv_kernel_dim"],
                 chunk=cfg["linear_chunk_size"],
                 allow_neg_eigval=cfg["linear_allow_neg_eigval"]),
        mlp_hidden=cfg["intermediate_size"])


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``.  No parameter's
    shape depends on the sequence length, so a short one is traced."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256)),
                       jnp.int32))["params"]
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]

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
        raise ValueError(f"olmo_hybrid_lm trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences of ``sequence_length`` tokens plus the label of the
    last one, int32, ids drawn from this chip's slice of the vocabulary."""
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


def units_per_sample(cfg) -> int:
    """Tokens a sequence contributes to ``tokens_per_s_chip``."""
    return cfg["sequence_length"]


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return {"d": cfg["hidden_size"], "H": H, "dk": dk, "dv": dv,
            "conv_dim": H * (2 * dk + dv), "C": cfg["linear_chunk_size"],
            "T": cfg["sequence_length"],
            "layers": {k: pattern(cfg).count(k) for k in "LF"}}


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a token."""
    s = _sizes(cfg)
    d, L, F = s["d"], s["layers"]["L"], s["layers"]["F"]
    qk, vw = s["H"] * s["dk"], s["H"] * s["dv"]
    ff = cfg["intermediate_size"]
    return [("lin_q", d, qk, L), ("lin_k", d, qk, L), ("lin_v", d, vw, L),
            ("lin_g", d, vw, L), ("lin_a", d, s["H"], L),
            ("lin_b", d, s["H"], L), ("lin_out", vw, d, L),
            ("attn_qkv", d, 3 * d, F), ("attn_proj", d, d, F),
            ("mlp_gate", d, ff, L + F), ("mlp_up", d, ff, L + F),
            ("mlp_down", ff, d, L + F),
            ("head", d, cfg["vocab_size"], 1)]


def delta_flops_per_token(cfg) -> float:
    """Forward FLOPs a token of ONE mixer's chunked delta rule, all
    heads, a chunk of ``C`` tokens divided by ``C``.  Over the causal
    half of a (C, C) tile: ``K K^T`` and ``Q K^T`` (C d_k each), the
    triangular ``T`` applied to the decayed keys (C d_k) and to the values
    (C d_v), the masked scores applied to ``V'`` (C d_v); ``T`` itself as
    the substitution it needs, C^2 / 3 (the doubling product the program
    runs does more, and is not the algorithm's); three whole products with
    the (d_v, d_k) state, 2 d_k d_v each: ``W S^T``, ``Q S^T`` and the
    state's update."""
    s = _sizes(cfg)
    C, dk, dv = s["C"], s["dk"], s["dv"]
    return s["H"] * (3 * C * dk + 2 * C * dv + C * C / 3
                     + 3 * 2 * dk * dv)


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward (2 +
    4 FLOPs per weight) of every weight matmul, of attention's two
    products over the causal half of the (T, T) square, of the mixers'
    chunked delta rule (:func:`delta_flops_per_token`) and of their
    convolution's ``linear_conv_kernel_dim`` multiply-adds a channel.
    Recomputation is not counted; the embedding lookup is no matmul."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = s["layers"]["F"] * s["T"] * s["d"]
    conv = 2 * cfg["linear_conv_kernel_dim"] * s["conv_dim"]
    return (6.0 * n_matmul + 6.0 * attn
            + 3.0 * s["layers"]["L"] * (delta_flops_per_token(cfg) + conv))


def delta_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the mixers' delta rules of one step need on
    one chip (the chunked form, forward and backward, without convolution
    and norms), from shapes.

    FLOPs: :func:`delta_flops_per_token`, twice again for the backward.
    Bytes: the compulsory traffic of a form that keeps its chunk states
    and its (C, C) tiles on the chip — forward it reads ``q``, ``k``
    (d_k), ``v`` (d_v) in bf16 and ``g``, ``beta`` in f32 a head and writes
    ``o`` (d_v); the backward reads those and ``do`` and writes the five
    gradients.  What the XLA form moves beyond that (the float32 tiles,
    ``W``, ``U``, ``V'``, the states entering every chunk written and read
    back) counts against its share."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    layers = s["layers"]["L"]
    flops = 3.0 * layers * tokens * delta_flops_per_token(cfg)
    inputs = s["H"] * ((2 * s["dk"] + s["dv"]) * 2 + 2 * 4)
    o = s["H"] * s["dv"] * 2
    nbytes = layers * tokens * ((inputs + o) + (inputs + o + inputs))
    chunks = batch_per_chip * -(-s["T"] // s["C"])
    return {"flops": flops, "bytes": nbytes, "chunks": layers * chunks,
            "state_bytes": layers * chunks * s["H"] * s["dv"] * s["dk"] * 4}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the flash kernels of one step need on one
    chip, from their shapes ``(B, T, H, D)``, causal: ``gpt2_lm.flash_cost``'s
    count (the forward's two products and the backward's five, each
    ``2 B H T T D`` over the causal half; each kernel's compulsory traffic
    in bf16 plus the float32 row statistics) at this family's keys, a call
    a full-attention layer."""
    from benchmark.families import gpt2_lm
    return gpt2_lm.flash_cost(
        {"n_positions": cfg["sequence_length"],
         "n_head": cfg["num_attention_heads"],
         "n_embd": cfg["hidden_size"], "n_layer": pattern(cfg).count("F")},
        batch_per_chip)


# ------------------------------------------------------ plain reference

# Tokens a block of the reference's recurrence; a block's states are
# recomputed in the backward pass, so that T 8192 fits.
_REFERENCE_BLOCK = 128


def reference_mixer(cfg):
    """``f(p, x) -> y`` for ONE sequence ``x`` (T, d) and a mixer's
    parameters ``p``: Gated DeltaNet's mixer in plain float32, the delta
    rule as the recurrence itself, one token a step under ``lax.scan``, all
    heads at once — no chunk, no triangular solve.  Memory only: the scan
    runs in blocks of 128 tokens whose states are recomputed in the
    backward pass (a ``T`` that is no multiple of 128 runs as one block)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    H, dk, dv = s["H"], s["dk"], s["dv"]
    K, eps = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"]
    neg = cfg["linear_allow_neg_eigval"]

    def step(S, t):                          # S (H, d_v, d_k)
        q_t, k_t, v_t, alpha_t, beta_t = t
        S = alpha_t[:, None, None] * S
        S = S - beta_t[:, None, None] * jnp.einsum(
            "hvd,hd,he->hve", S, k_t, k_t)
        S = S + beta_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvd,hd->hv", S, q_t)

    @jax.checkpoint
    def block(S, ts):
        return lax.scan(step, S, ts)

    def conv(u, w):
        T = u.shape[0]
        padded = jnp.pad(u, [(K - 1, 0), (0, 0)])
        return sum(w[j] * padded[j:j + T] for j in range(K))

    def mixer(p, x):
        T = x.shape[0]
        w_q, w_k, w_v = jnp.split(p["conv"]["kernel"], [H * dk, 2 * H * dk],
                                  axis=1)
        q = jax.nn.silu(conv(x @ p["q"]["kernel"], w_q)).reshape(T, H, dk)
        k = jax.nn.silu(conv(x @ p["k"]["kernel"], w_k)).reshape(T, H, dk)
        v = jax.nn.silu(conv(x @ p["v"]["kernel"], w_v)).reshape(T, H, dv)
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + eps) / math.sqrt(dk)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + eps)
        beta = jax.nn.sigmoid(x @ p["b"]["kernel"]) * (2.0 if neg else 1.0)
        alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
            x @ p["a"]["kernel"] + p["dt_bias"]))
        n = T // _REFERENCE_BLOCK if T % _REFERENCE_BLOCK == 0 else 1
        ts = tuple(a.reshape(n, T // n, *a.shape[1:])
                   for a in (q, k, v, alpha, beta))
        _, o = lax.scan(block, jnp.zeros((H, dv, dk), x.dtype), ts)
        o = o.reshape(T, H, dv)
        o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        gate = jax.nn.silu(x @ p["g"]["kernel"]).reshape(T, H, dv)
        return (o * p["gate_norm"] * gate).reshape(T, H * dv) @ p["out"][
            "kernel"]

    return mixer


def reference_loss(cfg, dtype: str = "float32"):
    """``f(params, aux, tokens) -> loss`` in plain ``jax.numpy`` float32 at
    ``highest`` matmul precision: the stack as config.json and the
    family's convention describe it (module docstring of
    ``horovod_tpu.models.linear_attention`` for the mixer, by
    :func:`reference_mixer`; every sub-layer's output RMS-normalised
    before it joins the residual stream, ``h = x + norm(mixer(x))``,
    ``y = h + norm(mlp(h))``; full attention of ``num_attention_heads``
    heads, RMSNorm over the whole q and k before the heads are split,
    causal softmax scaled by 1/sqrt(head), no positions, no bias; SwiGLU
    MLP; final RMSNorm, untied head, mean token cross-entropy).  One
    sequence at a time through ``lax.map``; no kernels, no chunks.

    Departures from the published description, all for memory at T 8192
    and none in the mathematics: attention runs one head at a time with
    its (T, T) scores held in full and recomputed in the backward pass;
    each layer is recomputed in the backward pass from its input; the
    recurrence's states are recomputed a block of 128 tokens at a time.

    ``dtype="bfloat16"`` is the precision control of the comparison and
    no reference: the same plain mathematics with every float32 part
    (weights, statistics, norms, decays, states, scores) in bfloat16 at
    the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H = cfg["num_attention_heads"]
    D = cfg["hidden_size"] // H
    eps = cfg["rms_norm_eps"]
    layers = pattern(cfg)
    mixer = reference_mixer(cfg)
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def attention(a, x):
        T = x.shape[0]
        q, k, v = jnp.split(x @ a["qkv"]["kernel"], 3, axis=-1)
        q = rms_norm(q, a["q_norm"]["scale"]).reshape(T, H, D)
        k = rms_norm(k, a["k_norm"]["scale"]).reshape(T, H, D)
        v = v.reshape(T, H, D)
        causal = jnp.tril(jnp.ones((T, T), bool))

        @jax.checkpoint
        def one_head(args):
            q_h, k_h, v_h = args
            s = jnp.where(causal, (q_h @ k_h.T) / math.sqrt(D), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v_h

        o = lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
        return o.transpose(1, 0, 2).reshape(T, H * D) @ a["proj"]["kernel"]

    def mlp(m, h):
        return (jax.nn.silu(h @ m["gate"]["kernel"])
                * (h @ m["up"]["kernel"])) @ m["down"]["kernel"]

    def layer(kind):
        @jax.checkpoint
        def f(p, x):
            y = mixer(p["lin"], x) if kind == "L" else attention(p["attn"], x)
            h = x + rms_norm(y, p["mixer_norm"]["scale"])
            return h + rms_norm(mlp(p["mlp"], h), p["mlp_norm"]["scale"])
        return f

    def one_sequence(params, seq):
        inp, labels = seq[:-1], seq[1:]
        x = params["tok_emb"]["embedding"][inp]
        for i, kind in enumerate(layers):
            x = layer(kind)(params[f"layer_{i}"], x)
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def loss(params, aux, tokens):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            ce = lax.map(lambda s: one_sequence(cast, s), tokens)
        return ce.mean().astype(jnp.float32)

    return loss
