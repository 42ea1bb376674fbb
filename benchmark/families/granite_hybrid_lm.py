"""Family ``granite_hybrid_lm``: the Granite 4.0-H dense hybrid stack
(Mamba-2 mixers whose B and C are one group over every head, grouped-query
attention without positions, a dense SwiGLU after each, muP-style
multipliers, a tied head), keyed like Hugging Face's ``granitemoehybrid``
config (``layer_types``, ``hidden_size``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_n_groups``, ``mamba_d_state``, ``mamba_d_conv``,
``mamba_chunk_size``, ``num_attention_heads``, ``num_key_value_heads``,
``attention_multiplier``, ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``shared_intermediate_size``,
``rms_norm_eps``, ``tie_word_embeddings``, ``vocab_size``).

The first ``num_hidden_layers`` entries of ``layer_types`` are run;
``vocab_size`` is this chip's slice of the vocabulary; ``sequence_length``
is the training sequence (``max_position_embeddings`` stays the model's
declared 131,072).  No expert is here because the model has none
(``num_local_experts`` 0: the block's MLP is the dense
``shared_intermediate_size`` SwiGLU alone).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.GraniteHybridLM``): ``ops/ssd.py``'s chunked scan
with the one group's heads split into tiles, ``ops/mixer_passes.py``'s
convolution and gated norm over all 4,096 channels, the flash kernels
through the path for heads off the lane width, the fused cross-entropy
head on the embedding table.  Everything else in this file is the
benchmark's own yardstick: the host-batch maker, the model FLOPs, the
scan's, the passes' and the flash kernels' operations and bytes, and a
plain float32 reference of the same mathematics that reads the same
parameter tree.  The loss is the mean next-token cross-entropy and
nothing else.

A checkout whose program has no such stack cannot run this family: the
import fails, at once and before jax is loaded.
"""

from __future__ import annotations

import os

import numpy as np

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       os.pardir, "horovod_tpu", "models",
                       "transformer.py")) as _fh:
    if "def GraniteHybridLM" not in _fh.read():
        raise ImportError(
            "granite_hybrid_lm needs models.transformer.GraniteHybridLM: "
            "this checkout's program has no pre-norm two-sub-layer pattern "
            "layers, multipliers or tied head")

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: both kinds of layer, one group of B and C over
# four heads, attention of two query heads over one KV head of 32 (off the
# lane width: the merged-heads path, interpreted).  A few hundred tokens
# average bfloat16's rounding out far less than a real batch does, so the
# preset brings its own, looser tolerances.
TINY = {"num_hidden_layers": 2, "layer_types": ["mamba", "attention"],
        "hidden_size": 64, "shared_intermediate_size": 128,
        "intermediate_size": 128, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_chunk_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "attention_multiplier": 0.03125, "sequence_length": 64,
        "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1}}
TINY_BATCH_PER_CHIP = 2

LETTER = {"mamba": "m", "attention": "a"}

# Leaves whose gradients are compared with the reference's, with {m} and
# {a} the first mixer and the first attention layer and {z} the last layer:
# the mixer's two projections, its convolution and its decay, the grouped
# keys and values, the SwiGLU nearest the loss, and the embedding table,
# whose gradient is the gather's plus the tied head's.
GRAD_LEAVES = (("layer_{m}", "ssm", "in_proj", "kernel"),
               ("layer_{m}", "ssm", "conv", "kernel"),
               ("layer_{m}", "ssm", "A_log"),
               ("layer_{m}", "ssm", "out_proj", "kernel"),
               ("layer_{m}", "mlp", "up", "kernel"),
               ("layer_{a}", "attn", "kv", "kernel"),
               ("layer_{z}", "mlp", "down", "kernel"),
               ("tok_emb", "embedding"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    return "".join(LETTER[kind] for kind in
                   cfg["layer_types"][:cfg["num_hidden_layers"]])


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"m": p.index("m"), "a": p.index("a"), "z": len(p) - 1}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


def head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import GraniteHybridLM

    as_published = {
        "model_type": "granitemoehybrid", "hidden_act": "silu",
        "attention_bias": False, "mamba_proj_bias": False,
        "mamba_conv_bias": True, "mamba_n_groups": 1, "mamba_expand": 2,
        "position_embedding_type": "nope", "normalization_function": "rmsnorm",
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "tie_word_embeddings": True}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if cfg["shared_intermediate_size"] != cfg["intermediate_size"]:
        differs["intermediate_size"] = cfg["intermediate_size"]
    if differs:
        raise ValueError(f"granite_hybrid_lm runs the dense stack as "
                         f"published; got {differs}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return GraniteHybridLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        attn_scale=float(cfg["attention_multiplier"]),
        mlp_hidden=cfg["shared_intermediate_size"],
        ssm=dict(num_heads=cfg["mamba_n_heads"],
                 head_dim=cfg["mamba_d_head"],
                 n_groups=cfg["mamba_n_groups"],
                 state_size=cfg["mamba_d_state"],
                 conv_kernel=cfg["mamba_d_conv"],
                 chunk=cfg["mamba_chunk_size"],
                 dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
                 dt_floor=cfg["time_step_floor"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]), tie_head=True)


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
            h.reshape(-1, dim), model.head_kernel(params),
            tokens[:, 1:].reshape(-1))
        return per_token.mean(), aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"granite_hybrid_lm trains with adamw, not "
                         f"{o['name']!r}")
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
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {"d": cfg["hidden_size"], "H": H, "P": P, "G": G, "N": N,
            "inner": H * P, "conv_dim": H * P + 2 * G * N,
            "Q": cfg["mamba_chunk_size"], "T": cfg["sequence_length"],
            "layers": {k: pattern(cfg).count(k) for k in "ma"}}


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a token.  The head is the embedding table transposed: one
    parameter, two uses, and only the head's is a matmul."""
    s = _sizes(cfg)
    d, L = s["d"], s["layers"]
    qw = cfg["num_attention_heads"] * head_dim(cfg)
    kvw = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    mlp = cfg["shared_intermediate_size"]
    every = L["m"] + L["a"]
    return [("ssm_in_proj", d, 2 * s["inner"] + 2 * s["G"] * s["N"] + s["H"],
             L["m"]),
            ("ssm_out_proj", s["inner"], d, L["m"]),
            ("attn_q", d, qw, L["a"]), ("attn_kv", d, kvw, L["a"]),
            ("attn_proj", qw, d, L["a"]),
            ("mlp_gate", d, mlp, every), ("mlp_up", d, mlp, every),
            ("mlp_down", mlp, d, every),
            ("head", d, cfg["vocab_size"], 1)]


def scan_flops_per_token(cfg) -> float:
    """Forward FLOPs a token of ONE mixer's chunked scan: within a chunk
    ``C B^T`` a group (2 Q N) and ``(L o C B^T) x`` a head (2 Q P), both over
    the causal half; the chunk's state ``x (x) B`` and the entering
    state's read-out through ``C``, 2 P N a head each; and the
    convolution's ``mamba_d_conv`` multiply-adds a channel.  ``C B^T`` is
    counted once a GROUP: what a scan that splits the group's heads into
    tiles recomputes a tile counts against its share."""
    s = _sizes(cfg)
    intra = (s["G"] * 2 * s["Q"] * s["N"] + s["H"] * 2 * s["Q"] * s["P"]) / 2
    return (intra + 2 * s["H"] * 2 * s["P"] * s["N"]
            + 2 * cfg["mamba_d_conv"] * s["conv_dim"])


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward (2 +
    4 FLOPs per weight) of every weight matmul it runs, of attention's two
    products over the causal half of the (T, T) square, and of the mixers'
    chunked scans (:func:`scan_flops_per_token`).  Recomputation is not
    counted; the embedding lookup is no matmul."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = (s["layers"]["a"] * s["T"] * cfg["num_attention_heads"]
            * head_dim(cfg))
    return (6.0 * n_matmul + 6.0 * attn
            + 3.0 * s["layers"]["m"] * scan_flops_per_token(cfg))


def ssd_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the mixers' scans of one step need on one chip
    (the chunked form, forward and backward, without the convolution),
    from shapes, as ``nemotron_h_lm.ssd_cost`` counts them.

    FLOPs: :func:`scan_flops_per_token`'s four products, twice again for
    the backward.  Bytes: the compulsory traffic of a scan that keeps its
    chunk states on the chip — forward it reads ``x`` (H P), ``B`` and ``C``
    (G N each, ONCE a group) in bf16 and ``dt`` (H) in f32 and writes ``y``
    (H P); the backward reads those and ``dy`` and writes the four
    gradients.  What the kernels move beyond that (B and C fetched again
    by every head tile, float32 parts of ``dB`` and ``dC`` a tile, the
    float32 states of every chunk written and read back by the backward)
    counts against their share."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    layers = s["layers"]["m"]
    conv = 2 * cfg["mamba_d_conv"] * s["conv_dim"]
    flops = 3.0 * layers * tokens * (scan_flops_per_token(cfg) - conv)
    inputs = (s["inner"] + 2 * s["G"] * s["N"]) * 2 + s["H"] * 4
    y = s["inner"] * 2
    nbytes = layers * tokens * ((inputs + y) + (inputs + y + inputs))
    chunks = batch_per_chip * -(-s["T"] // s["Q"])
    return {"flops": flops, "bytes": nbytes, "chunks": layers * chunks,
            "state_bytes": (layers * chunks * s["H"] * s["P"] * s["N"] * 4)}


def pass_cost(cfg, batch_per_chip: int) -> dict:
    """Bytes the mixers' two elementwise passes of one step need on one
    chip, forward and backward, from shapes: each pass one read of its
    operands and one write of its result, in bf16.  The convolution reads
    ``xBC`` and writes it activated (2 conv_dim values a token); its
    backward reads ``xBC`` and ``dy`` and writes ``dx`` (3).  The gated
    norm reads ``y`` and ``z`` and writes the normed product (3 inner);
    its backward reads ``y``, ``z``, ``do`` and writes ``dy``, ``dz`` (5).
    The convolution replayed inside the mixer's ``jax.checkpoint`` is a
    recomputation and counts against the share; the parameters' gradients
    are a few KB.  The passes hold no matmul and some dozen float32
    operations a value: bytes bound them, and no FLOPs are counted."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    per_token = (2 + 3) * s["conv_dim"] * 2 + (3 + 5) * s["inner"] * 2
    return {"bytes": s["layers"]["m"] * tokens * per_token,
            "bytes_per_token": per_token}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the flash kernels of one step need on one
    chip, from their shapes — queries ``(B, T, H, D)``, keys and values
    ``(B, T, H_kv, D)``, causal —, as ``nemotron_h_lm.flash_cost`` counts
    them: 2 + 5 products of ``2 B H T T D`` over the causal half; each
    kernel's compulsory traffic in bf16 with k, v, dk, dv at their
    ``H_kv`` heads, plus the float32 row statistics.  At heads of 64 the
    program repeats k and v to ``H`` heads in HBM and transposes every
    operand: that traffic is not in this count and so counts against the
    share."""
    B, T = batch_per_chip, cfg["sequence_length"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    layers = pattern(cfg).count("a")
    product = 2.0 * B * H * T * T * D / 2
    q, kv = B * T * H * D * 2, B * T * Hkv * D * 2     # one bf16 tensor
    stat = B * H * T * 4
    nbytes = layers * ((2 * q + 2 * kv + stat)              # forward
                       + (3 * q + 2 * kv + 2 * stat)        # dq
                       + (2 * q + 4 * kv + 2 * stat))       # dk/dv
    return {"flops": layers * (2 + 5) * product, "bytes": nbytes,
            "shape": [B, T, H, Hkv, D], "calls_per_step": layers}


# ------------------------------------------------------ plain reference


def reference_mixer(cfg, form: str = "dual"):
    """``f(p, u) -> y`` for ONE sequence ``u`` (T, d) and a mixer's
    parameters ``p``: Mamba-2's mixer in plain float32, B and C shared by
    the heads of a group (all of them at ``mamba_n_groups`` 1) and the
    gated RMSNorm over a group's ``inner / G`` channels.  ``form="dual"``:
    per head, the (T, T) matrix ``L o C B^T`` with ``L[t, s] = a_{s+1} ...
    a_t`` for ``s <= t`` applied to ``dt x`` — no chunk, no state; heads
    one after another, each recomputed in the backward pass, so that
    T 8192 fits.  ``form="recurrence"``: the recurrence itself, one token
    a step (keeps every state for the backward: small sizes only)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    H, P, G, N, inner = s["H"], s["P"], s["G"], s["N"], s["inner"]
    K, eps = cfg["mamba_d_conv"], cfg["rms_norm_eps"]

    @jax.checkpoint
    def one_head(args):
        x, dt, a_log, B, C = args        # (T,P) (T,) () (T,N) (T,N)
        T = x.shape[0]
        cum = jnp.cumsum(-dt * jnp.exp(a_log))
        mask = jnp.tril(jnp.ones((T, T), bool))
        L = jnp.exp(jnp.where(mask, cum[:, None] - cum[None, :], -jnp.inf))
        return (L * (C @ B.T)) @ (dt[:, None] * x)

    def recurrence(x, dt, a_log, B, C):  # (T,H,P) (T,H) (H,) (T,H,N) x2
        def step(state, t):
            x_t, dt_t, B_t, C_t = t
            a = jnp.exp(-dt_t * jnp.exp(a_log))[:, None, None]
            state = a * state + (dt_t[:, None] * x_t)[:, :, None] * B_t[
                :, None, :]
            return state, (state * C_t[:, None, :]).sum(-1)

        return lax.scan(step, jnp.zeros((H, P, N), x.dtype),
                        (x, dt, B, C))[1]

    def mixer(p, u):
        T = u.shape[0]
        z, xBC, dt = jnp.split(u @ p["in_proj"]["kernel"],
                               [inner, 2 * inner + 2 * G * N], axis=-1)
        padded = jnp.pad(xBC, [(K - 1, 0), (0, 0)])
        xBC = p["conv"]["bias"] + sum(
            p["conv"]["kernel"][j] * padded[j:j + T] for j in range(K))
        x, B, C = jnp.split(jax.nn.silu(xBC), [inner, inner + G * N], axis=-1)
        x = x.reshape(T, H, P)
        B = jnp.repeat(B.reshape(T, G, N), H // G, axis=1)      # (T, H, N)
        C = jnp.repeat(C.reshape(T, G, N), H // G, axis=1)
        dt = jax.nn.softplus(dt + p["dt_bias"])                  # (T, H)
        if form == "dual":
            y = lax.map(one_head, (x.transpose(1, 0, 2), dt.T, p["A_log"],
                                   B.transpose(1, 0, 2),
                                   C.transpose(1, 0, 2))).transpose(1, 0, 2)
        else:
            y = recurrence(x, dt, p["A_log"], B, C)
        y = (y + p["D"][:, None] * x).reshape(T, inner) * jax.nn.silu(z)
        y = y.reshape(T, G, inner // G)
        y = y * lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
        return (y.reshape(T, inner) * p["gate_norm"]) @ p["out_proj"][
            "kernel"]

    return mixer


def reference_loss(cfg, mixer_form: str = "dual", dtype: str = "float32"):
    """``f(params, aux, tokens) -> loss`` in plain ``jax.numpy`` float32 at
    full matmul precision: the stack as config.json describes it.

        x0 = embedding_multiplier * E[tokens]
        h  = x + r * f(RMSNorm(x));   y = h + r * SwiGLU(RMSNorm(h))
        logits = RMSNorm(x_L) E^T / logits_scaling

    with ``r`` = ``residual_multiplier``, ``f`` the mixer
    (:func:`reference_mixer`) or attention of ``num_attention_heads``
    query heads over ``num_key_value_heads`` KV heads of ``hidden_size /
    num_attention_heads``, causal softmax of the scores times
    ``attention_multiplier``, no positions, no bias; mean token
    cross-entropy.  One sequence at a time through ``lax.map``, each layer
    recomputed in the backward pass (``jax.checkpoint``: ten layers of
    float32 activations at T 8192 would not fit beside the weights); no
    kernels, no chunks; attention one query head at a time with its
    (T, T) scores held in full.

    Departures from the published model: none in the mathematics; the
    weights are seeded random ones and the vocabulary is the chip's slice
    (the configuration's ``departures``).

    ``dtype="bfloat16"`` is the precision control of the comparison and
    no reference: the same plain mathematics with every float32 part
    (weights, statistics, running sums, softmax) in bfloat16 at the
    default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    eps = cfg["rms_norm_eps"]
    r = float(cfg["residual_multiplier"])
    layers = pattern(cfg)
    mixer = reference_mixer(cfg, mixer_form)
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def attention(a, h):
        T = h.shape[0]
        q = (h @ a["q"]["kernel"]).reshape(T, H, D)
        k, v = jnp.split(h @ a["kv"]["kernel"], 2, axis=-1)
        k, v = k.reshape(T, Hkv, D), v.reshape(T, Hkv, D)
        causal = jnp.tril(jnp.ones((T, T), bool))

        @jax.checkpoint
        def one_head(args):
            q_h, kv_head = args
            s = (q_h @ k[:, kv_head].T) * float(cfg["attention_multiplier"])
            s = jnp.where(causal, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, kv_head]

        o = lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(H) // (H // Hkv)))
        return o.transpose(1, 0, 2).reshape(T, H * D) @ a["proj"]["kernel"]

    def swiglu(m, h):
        return (jax.nn.silu(h @ m["gate"]["kernel"])
                * (h @ m["up"]["kernel"])) @ m["down"]["kernel"]

    def layer(kind):
        @jax.checkpoint
        def apply(p, x):
            h = rms_norm(x, p["norm"]["scale"])
            f = mixer(p["ssm"], h) if kind == "m" else attention(p["attn"], h)
            x = x + r * f
            return x + r * swiglu(p["mlp"],
                                  rms_norm(x, p["mlp_norm"]["scale"]))
        return apply

    def one_sequence(params, seq):
        inp, labels = seq[:-1], seq[1:]
        table = params["tok_emb"]["embedding"]
        x = jnp.asarray(cfg["embedding_multiplier"], dtype) * table[inp]
        for i, kind in enumerate(layers):
            x = layer(kind)(params[f"layer_{i}"], x)
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = (x @ table.T) / jnp.asarray(cfg["logits_scaling"], dtype)
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
