"""Family ``kimi_linear_lm``: the Kimi-Linear stack — Kimi Delta Attention
in three layers of four (a gated delta rule whose decay is a value a key
CHANNEL, from a low-rank gate; a sigmoid-gated norm) beside multi-head
latent attention with no positions and no query latent, a leading dense
SwiGLU layer, expert layers whose sigmoid router chooses by ``s + b`` with a
balancing bias ``b`` that no gradient touches —, keyed like the published
config.json (``model_type`` ``kimi_linear``: ``hidden_size``,
``num_attention_heads``, ``linear_attn_config`` — ``kda_layers``,
``full_attn_layers``, ``num_heads``, ``head_dim``,
``short_conv_kernel_size`` —, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``mla_use_nope``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts``, ``num_shared_experts``, ``num_experts_per_token``,
``routed_scaling_factor``, ``rms_norm_eps``, ``vocab_size``).

The first ``num_hidden_layers`` layers are run: layer ``i`` (1-based) is a
KDA layer or a latent-attention layer as ``linear_attn_config`` lists it,
with a dense SwiGLU in the first ``first_k_dense_replace`` and experts
behind them.  The configuration is ONE CHIP'S SHARE of a deployment that
divides the experts and the vocabulary: ``num_experts`` counts the experts
held here (the first ones) of the ``experts_routed_over`` the router chooses
``num_experts_per_token`` of, and ``vocab_size`` this chip's slice of the
vocabulary.  The heads are not divided.  ``sequence_length`` is the training
sequence; ``linear_chunk_size`` the delta rule's chunk and
``linear_low_rank`` the gates' inner width (neither a key of config.json:
``assumed``).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.KimiLinearLM``): ``ops/gated_delta.py``'s chunked rule
handed a decay of rank 4 in ``models/linear_attention.py``'s
``KimiDeltaAttention``, ``LatentAttention`` without ``q_a`` and without a
rotation over the flash family's kernels at 192 | 128, ``DroplessMoE`` with
held experts and ``choice_bias`` (its state is ``make_train_step``'s
``aux_state``), the fused cross-entropy head.  Everything else in this file
is the benchmark's own yardstick: the host-batch maker, the model FLOPs, the
delta rule's, the kernels', the projections' and the expert layers'
operations and bytes, and a plain float32 reference of the same mathematics
— the recurrence token by token, attention as a dense causal softmax — that
reads the same parameter tree and the same ``b``.  The loss is the mean
next-token cross-entropy and nothing else.

A checkout whose program has no ``KimiLinearLM`` cannot run this family: the
import fails, at once and before jax is loaded.
"""

from __future__ import annotations

import math
import os

import numpy as np

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       os.pardir, "horovod_tpu", "models",
                       "transformer.py")) as _fh:
    if "def KimiLinearLM" not in _fh.read():
        raise ImportError("kimi_linear_lm needs models.transformer."
                          "KimiLinearLM: this checkout's program has no "
                          "delta rule with a decay a key channel")

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
# The routers' balancing bias is aux state that every step moves by its own
# tokens' counts, so make_train_step may not take it for a pass-through
# (joyai_flash_lm.SYNC_AUX_STATE has the rest of the argument).
SYNC_AUX_STATE = True

# The CPU rehearsal's sizes: the dense layer and one period (k K K x K), 4 of
# 16 experts held, top-3; two heads, the latent layer's at the PUBLISHED head
# widths (128 | 64 against 128) so that the kernels that take values narrower
# than keys run, interpreted; four chunks a sequence.  A few hundred tokens
# average bfloat16's rounding out far less than a real batch does, and every
# mixer multiplies its input's relative error, so the preset brings its own,
# looser tolerances.
TINY = {"hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 2,
        "num_key_value_heads": 2, "kv_lora_rank": 32,
        "linear_attn_config": {
            "full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
            "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
        "linear_chunk_size": 16, "linear_low_rank": 8,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": 4, "experts_routed_over": 16,
        "num_experts_per_token": 3, "sequence_length": 64, "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 3e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's, with {k} the first
# KDA layer, {e} the first expert layer, {x} the first latent-attention layer
# and {z} the last layer of the stack.  The first KDA layer holds every part
# of the mechanism: a projection in front of the convolution, both low-rank
# pairs (the decay's ``f`` and the gate's ``g``), ``A_log`` (a number a head
# that scales every channel's log-decay), ``b`` (the step).  The latent
# layer's are its four projections and its one norm.  The routed leaves are
# the FIRST expert layer's (joyai_flash_lm.GRAD_LEAVES has the reason).  The
# last layer's decay pair stands behind every other layer.
GRAD_LEAVES = (("layer_{k}", "lin", "q", "kernel"),
               ("layer_{k}", "lin", "conv", "kernel"),
               ("layer_{k}", "lin", "f_a", "kernel"),
               ("layer_{k}", "lin", "f_b", "kernel"),
               ("layer_{k}", "lin", "A_log"),
               ("layer_{k}", "lin", "b", "kernel"),
               ("layer_{k}", "lin", "g_a", "kernel"),
               ("layer_{k}", "lin", "g_b", "kernel"),
               ("layer_{k}", "lin", "out", "kernel"),
               ("layer_{e}", "moe", "router", "kernel"),
               ("layer_{e}", "moe", "w_gate"),
               ("layer_{e}", "moe", "shared", "w_up"),
               ("layer_{x}", "attn", "q_b", "kernel"),
               ("layer_{x}", "attn", "kv_a", "kernel"),
               ("layer_{x}", "attn", "kv_norm", "scale"),
               ("layer_{x}", "attn", "kv_b", "kernel"),
               ("layer_{x}", "attn", "proj", "kernel"),
               ("layer_{z}", "lin", "f_b", "kernel"),
               ("head", "kernel"),
               ("tok_emb", "embedding"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    """A letter a layer run: ``k`` / ``K`` a KDA layer with a dense SwiGLU /
    with experts, ``d`` / ``x`` a latent-attention layer with the same."""
    lin = cfg["linear_attn_config"]
    letters = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        dense = i <= cfg["first_k_dense_replace"]
        if i in lin["kda_layers"]:
            letters.append("k" if dense else "K")
        elif i in lin["full_attn_layers"]:
            letters.append("d" if dense else "x")
        else:
            raise ValueError(f"layer {i} is in neither list of "
                             "linear_attn_config")
    return "".join(letters)


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"k": min(p.index(c) for c in "kK" if c in p),
          "e": min(p.index(c) for c in "Kx" if c in p),
          "x": min(p.index(c) for c in "dx" if c in p),
          "z": len(p) - 1}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import KimiLinearLM

    as_published = {
        "model_type": "kimi_linear", "hidden_act": "silu",
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
        "num_shared_experts": 1, "moe_layer_freq": 1, "mla_use_nope": True,
        "q_lora_rank": None, "rope_scaling": None,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "first_k_dense_replace": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    lin = cfg["linear_attn_config"]
    if differs or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or lin["num_heads"] != cfg["num_attention_heads"]:
        raise ValueError(f"kimi_linear_lm runs the stack as published; got "
                         f"{differs or 'another head count in some layer'}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return KimiLinearLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"],
        num_heads=cfg["num_attention_heads"],
        mlp_hidden=cfg["intermediate_size"],
        lin=dict(num_heads=lin["num_heads"], key_dim=lin["head_dim"],
                 value_dim=lin["head_dim"],
                 conv_kernel=lin["short_conv_kernel_size"],
                 chunk=cfg["linear_chunk_size"],
                 low_rank=cfg["linear_low_rank"]),
        mla=dict(q_latent=cfg["q_lora_rank"], kv_latent=cfg["kv_lora_rank"],
                 nope_dim=cfg["qk_nope_head_dim"],
                 rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"]),
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_token"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="sigmoid", renormalize=True,
                 gate_scale=float(cfg["routed_scaling_factor"]),
                 activation="swiglu",
                 shared_hidden=(cfg["num_shared_experts"]
                                * cfg["moe_intermediate_size"]),
                 choice_bias=float(cfg["training"]["bias_update_speed"]),
                 held=(0, cfg["num_experts"])))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``: ``aux`` is the
    routers' balancing bias (the collection ``"balance"``, zeros).  No
    parameter's shape depends on the sequence length, so a short one is
    traced."""
    import jax.numpy as jnp
    made = dict(_model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256)), jnp.int32)))
    return made.pop("params"), made


def loss_fn(cfg):
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]

    def loss(params, aux, tokens):
        h, moved = model.apply({"params": params, **aux}, tokens[:, :-1],
                               return_hidden=True, mutable=["balance"])
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"],
            tokens[:, 1:].reshape(-1))
        return per_token.mean(), moved

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"kimi_linear_lm trains with adamw, not {o['name']!r}")
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


def _expert_layers(cfg):
    return [i for i, kind in enumerate(pattern(cfg)) if kind in "Kx"]


def program_expert_choices(cfg, params, aux, tokens):
    """The experts the PROGRAM's routers chose for ``tokens`` (B, T + 1)
    under the bias ``aux`` holds: (B, expert layers, T,
    num_experts_per_token) indices read from what its expert layers sow.
    :func:`reference_loss` breaks its near-ties with them.  They are read
    beside the gradients of :func:`grad_leaves`, from one
    forward-and-backward pass of the program's own loss, as
    ``nemotron3_super_lm.program_expert_choices`` reads them and for its
    reason; where those gradients are not finite the choices break no tie
    (-1)."""
    import functools

    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]
    paths = grad_leaves(cfg)

    def loss(p):
        h, state = model.apply({"params": p, **aux}, tokens[:, :-1],
                               return_hidden=True, mutable=["intermediates"])
        return (fused_softmax_xent(h.reshape(-1, dim), p["head"]["kernel"],
                                   tokens[:, 1:].reshape(-1)).mean(),
                state["intermediates"])

    (_, sown), grads = jax.value_and_grad(loss, has_aux=True)(
        jax.lax.stop_gradient(params))
    finite = jnp.stack([
        jnp.isfinite(functools.reduce(lambda t, k: t[k], path, grads)).all()
        for path in paths]).all()
    B, T = tokens.shape[0], tokens.shape[1] - 1
    chosen = jnp.stack([
        sown[f"layer_{i}"]["moe"]["expert_index"][0].reshape(B, T, -1)
        for i in _expert_layers(cfg)], axis=1)
    return jnp.where(finite, chosen, -1)


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    lin = cfg["linear_attn_config"]
    H, dk = lin["num_heads"], lin["head_dim"]
    letters = pattern(cfg)
    return {"d": cfg["hidden_size"], "H": H, "dk": dk, "dv": dk,
            "rank": cfg["linear_low_rank"],
            "conv_dim": H * 3 * dk, "C": cfg["linear_chunk_size"],
            "T": cfg["sequence_length"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"],
            "layers": {"kda": sum(letters.count(c) for c in "kK"),
                       "attn": sum(letters.count(c) for c in "dx"),
                       "dense": sum(letters.count(c) for c in "kd"),
                       "experts": sum(letters.count(c) for c in "Kx")}}


def held_share(cfg) -> float:
    """Of a token's ``num_experts_per_token`` assignments, those that uniform
    routing sends to the experts held here."""
    return (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["experts_routed_over"])


def kda_matmuls(cfg):
    """The nine projections of ONE KDA mixer, per token, as ``(name, k,
    n)``."""
    s = _sizes(cfg)
    d, r, qk, vw = s["d"], s["rank"], s["H"] * s["dk"], s["H"] * s["dv"]
    return [("q", d, qk), ("k", d, qk), ("v", d, vw), ("b", d, s["H"]),
            ("f_a", d, r), ("f_b", r, qk), ("g_a", d, r), ("g_b", r, vw),
            ("out", vw, d)]


def latent_matmuls(cfg):
    """The four projections of ONE latent-attention layer (no query latent:
    ``q_b`` reads the layer's input), per token, as ``(name, k, n)``."""
    s = _sizes(cfg)
    d, H = s["d"], s["H"]
    kv, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return [("q_b", d, H * s["qk"]), ("kv_a", d, kv + rope),
            ("kv_b", kv, H * (cfg["qk_nope_head_dim"] + s["v"])),
            ("proj", H * s["v"], d)]


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as ``(name, k, n,
    count)``: a (1, k) row times a (k, n) weight, ``count`` of them a token
    (a fraction for the routed experts: :func:`held_share`)."""
    s = _sizes(cfg)
    d, L = s["d"], s["layers"]
    eh = cfg["moe_intermediate_size"]
    sh = cfg["num_shared_experts"] * eh
    return ([("kda_" + name, k, n, L["kda"]) for name, k, n in kda_matmuls(cfg)]
            + [(name, k, n, L["attn"]) for name, k, n in latent_matmuls(cfg)]
            + [("dense_mlp", d, 3 * cfg["intermediate_size"], L["dense"]),
               ("router", d, cfg["experts_routed_over"], L["experts"]),
               ("shared", d, 3 * sh, L["experts"]),
               ("held", d, 3 * eh, held_share(cfg) * L["experts"]),
               ("head", d, cfg["vocab_size"], 1)])


def delta_flops_per_token(cfg) -> float:
    """Forward FLOPs a token of ONE mixer's chunked delta rule, all heads, a
    chunk of ``C`` tokens divided by ``C`` — ``olmo_hybrid_lm``'s count,
    which a decay a channel does not change: over the causal half of a (C,
    C) tile ``K K^T`` and ``Q K^T`` (C d_k each, the decay inside the
    contraction), the triangular ``T`` applied to the decayed keys (C d_k)
    and to the values (C d_v), the masked scores applied to ``V'`` (C d_v);
    ``T`` itself as the substitution it needs, C^2 / 3; three whole products
    with the (d_v, d_k) state, 2 d_k d_v each.  The ``log2 C`` whole
    products the halved form runs for each tile, and the factors it scales
    the operands by, are the program's and count against its share."""
    s = _sizes(cfg)
    C, dk, dv = s["C"], s["dk"], s["dv"]
    return s["H"] * (3 * C * dk + 2 * C * dv + C * C / 3 + 3 * 2 * dk * dv)


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token of THIS CHIP'S SHARE requires: forward
    plus backward (2 + 4 FLOPs per weight) of every weight matmul it runs
    (:func:`matmuls`) — the routed experts at the share of a token uniform
    routing sends here —, of latent attention's two products at the
    PUBLISHED widths (scores 192 wide, values 128) over the causal half of
    the (T, T) square, of the KDA mixers' chunked delta rule
    (:func:`delta_flops_per_token`) and of their convolution's
    ``short_conv_kernel_size`` multiply-adds a channel.  Recomputation, the
    lanes the kernels pad, the rows that round a load up to whole windows,
    the embedding lookup, the top-k, the sort and the combine are not
    counted."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = s["layers"]["attn"] * s["T"] * s["H"] * (s["qk"] + s["v"]) / 2
    conv = (2 * cfg["linear_attn_config"]["short_conv_kernel_size"]
            * s["conv_dim"])
    return (6.0 * n_matmul + 6.0 * attn
            + 3.0 * s["layers"]["kda"] * (delta_flops_per_token(cfg) + conv))


def delta_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the KDA mixers' delta rules of one step need on
    one chip (the chunked form, forward and backward, without convolution,
    gates and norms), from shapes.

    FLOPs: :func:`delta_flops_per_token`, twice again for the backward.
    Bytes: the compulsory traffic of a form that keeps its chunk states and
    its (C, C) tiles on the chip — forward it reads ``q``, ``k`` (d_k), ``v``
    (d_v) in bf16, the log-decays ``g`` in f32 a key CHANNEL (``decay_bytes``
    a pass: what a decay a head does not read) and ``beta`` in f32 a head and
    writes ``o`` (d_v); the backward reads those and ``do`` and writes the
    five gradients, ``dg`` a channel.  What the XLA form moves beyond that
    (each halving level's scaled operands and float32 tiles, ``W``, ``U``,
    ``V'``, the states entering every chunk written and read back) counts
    against its share."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    layers = s["layers"]["kda"]
    flops = 3.0 * layers * tokens * delta_flops_per_token(cfg)
    decay = s["H"] * s["dk"] * 4
    inputs = s["H"] * ((2 * s["dk"] + s["dv"]) * 2 + 4) + decay
    o = s["H"] * s["dv"] * 2
    nbytes = layers * tokens * ((inputs + o) + (inputs + o + inputs))
    chunks = batch_per_chip * -(-s["T"] // s["C"])
    return {"flops": flops, "bytes": nbytes, "chunks": layers * chunks,
            "state_bytes": layers * chunks * s["H"] * s["dv"] * s["dk"] * 4,
            "decay_bytes": layers * tokens * decay,
            "sub_chunks": layers * chunks * (s["C"] - 1)}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the attention kernels of one step need on one
    chip, from their shapes at the PUBLISHED widths — queries and keys ``(B,
    T, H, 192)``, values ``(B, T, H, 128)``, causal —, a call a latent
    layer: ``joyai_flash_lm.flash_cost``'s count.  FLOPs, over the causal
    half: the forward's two products (``T² H · 320`` a sequence) and the
    backward's five (``· 832``).  Bytes: each direction's compulsory traffic
    in bf16 plus the float32 row statistics.  The lanes the kernels pad (192
    to 256) and the shared key written out a head count against the share."""
    s = _sizes(cfg)
    B, T, H = batch_per_chip, s["T"], s["H"]
    layers = s["layers"]["attn"]
    pair = 2.0 * B * H * T * T / 2                   # per unit of depth
    qk, v = B * T * H * s["qk"] * 2, B * T * H * s["v"] * 2   # bf16 tensors
    stat = B * H * T * 4
    nbytes = layers * ((2 * qk + 2 * v + stat)                 # forward
                       + (4 * qk + 4 * v + 2 * stat))          # backward
    return {"flops": layers * pair * ((s["qk"] + s["v"])
                                      + (3 * s["qk"] + 2 * s["v"])),
            "bytes": nbytes, "shape": [B, T, H, s["qk"], s["v"]],
            "calls_per_step": layers}


def mla_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the rest of latent attention needs a step on one
    chip — everything of a layer but the kernels —, forward and backward,
    from shapes.  FLOPs: the four projections (:func:`latent_matmuls`), 2 a
    weight forward and 4 backward, the up-projections ONCE
    (``recomputed_flops`` is what projecting up again in the backward pass
    adds — ``q_b`` from the layer's input, ``kv_b`` from the latent).
    ``pass_bytes``: the one norm's compulsory traffic in bf16 (2 + 3 values
    a channel of the 512-wide latent); there is no rotation.  ``bytes`` adds
    the projections' rows in and out and weights."""
    s = _sizes(cfg)
    tokens, L = batch_per_chip * s["T"], s["layers"]["attn"]
    flops = L * 6.0 * tokens * sum(k * n for _, k, n in latent_matmuls(cfg))
    up = sum(k * n for name, k, n in latent_matmuls(cfg)
             if name in ("q_b", "kv_b"))
    pass_bytes = L * tokens * 5 * 2 * cfg["kv_lora_rank"]

    def matmul_bytes(rows, k_, n_):
        return 3 * rows * (k_ + n_) * 2 + 2 * k_ * n_ * 2 + k_ * n_ * 4

    return {"flops": flops, "recomputed_flops": L * 2.0 * tokens * up,
            "pass_bytes": pass_bytes,
            "bytes": pass_bytes + L * sum(
                matmul_bytes(tokens, k, n)
                for _, k, n in latent_matmuls(cfg))}


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one chip,
    forward and backward, from shapes — ``joyai_flash_lm.moe_cost``'s count
    at this family's keys: the router over all ``experts_routed_over``
    (``router_flops``: what ``route_ms`` is read for), the held experts'
    three grouped matmuls at the load uniform routing sends here and the
    shared expert's three matmuls over every token; 2 FLOPs a weight forward
    and 4 backward; bytes per matmul in bf16.  The top-k, the sort, the
    gathers, the combine, the activation, the bias update and the rows that
    round the load up to whole windows are left out."""
    d, eh = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sh = cfg["num_shared_experts"] * eh
    E, held = cfg["experts_routed_over"], cfg["num_experts"]
    L = _sizes(cfg)["layers"]["experts"]
    tokens = batch_per_chip * cfg["sequence_length"]
    A = tokens * held_share(cfg)
    router = L * 6.0 * tokens * d * E
    flops = router + L * 6.0 * 3 * d * (A * eh + tokens * sh)

    def matmul_bytes(rows, k_, n_, weights):
        moved = rows * (k_ + n_) * 2
        return 3 * moved + 2 * weights * 2 + weights * 4

    nbytes = L * 3 * (matmul_bytes(A, d, eh, held * d * eh)
                      + matmul_bytes(tokens, d, sh, d * sh))
    return {"flops": flops, "bytes": nbytes,
            "assignments": tokens * cfg["num_experts_per_token"],
            "held_assignments": A, "router_flops": router,
            "expert_parameters": L * 3 * d * (held * eh + sh)}


# ------------------------------------------------------ plain reference

# Tokens a block of the reference's recurrence; a block's states are
# recomputed in the backward pass, so that T 8192 fits.
_REFERENCE_BLOCK = 128


def reference_mixer(cfg):
    """``f(p, x) -> y`` for ONE sequence ``x`` (T, d) and a KDA mixer's
    parameters ``p``, the equations written out: ``q``, ``k``, ``v`` through
    a causal depthwise convolution and ``silu``, ``q`` and ``k`` L2-normed a
    head (``q`` over ``sqrt(d_k)``); ``alpha_t = exp(-exp(A_log_h)
    softplus(x_t W_fa W_fb + dt_bias))`` a key channel; ``beta_t =
    sigmoid(x_t W_b)``; the delta rule as the recurrence itself, one token a
    step under ``lax.scan``, all heads at once — ``S <- S Diag(alpha_t)``,
    ``S <- S + beta_t (v_t - S k_t) k_t^T``, ``o_t = S q_t`` —, no chunk, no
    triangular solve; ``y = [n(o) * sigmoid(x W_ga W_gb)] W_o`` with ``n``
    an RMSNorm over a head's values.  Memory only: the scan runs in blocks of
    128 tokens whose states are recomputed in the backward pass (a ``T`` that
    is no multiple of 128 runs as one block)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    H, dk, dv = s["H"], s["dk"], s["dv"]
    K = cfg["linear_attn_config"]["short_conv_kernel_size"]
    eps = cfg["rms_norm_eps"]

    def step(S, t):                          # S (H, d_v, d_k)
        q_t, k_t, v_t, alpha_t, beta_t = t
        S = S * alpha_t[:, None, :]
        seen = jnp.einsum("hvd,hd->hv", S, k_t)
        S = S + (beta_t[:, None] * (v_t - seen))[:, :, None] * k_t[:, None, :]
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
        beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
        f = (x @ p["f_a"]["kernel"]) @ p["f_b"]["kernel"] + p["dt_bias"]
        alpha = jnp.exp(-jnp.exp(p["A_log"])[:, None]
                        * jax.nn.softplus(f.reshape(T, H, dk)))
        n = T // _REFERENCE_BLOCK if T % _REFERENCE_BLOCK == 0 else 1
        ts = tuple(a.reshape(n, T // n, *a.shape[1:])
                   for a in (q, k, v, alpha, beta))
        _, o = lax.scan(block, jnp.zeros((H, dv, dk), x.dtype), ts)
        o = o.reshape(T, H, dv)
        o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        gate = jax.nn.sigmoid((x @ p["g_a"]["kernel"]) @ p["g_b"]["kernel"])
        return ((o * p["gate_norm"] * gate.reshape(T, H, dv)).reshape(
            T, H * dv) @ p["out"]["kernel"])

    return mixer


def reference_attention(cfg):
    """``f(a, h) -> y`` for ONE sequence ``h`` (T, d) and a latent-attention
    layer's parameters ``a``, the equations written out: ``[q_nope | q_r] =
    h W_q`` a head (no query latent); ``[c_kv | k_r] = h W_kva``, ``c_kv <-
    n(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head; NO rotation: scores
    ``(q_nope · k_nope + q_r · k_r) / sqrt(192)`` with the ONE ``k_r`` a
    token that all heads read, under the causal mask, softmax, ``o = P v``
    (128 wide), ``[o_1 … o_H] W_o``.  One head at a time with its (T, T)
    scores held in full, each recomputed in the backward pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H = cfg["num_attention_heads"]
    N, R, V = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    kv_rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]

    def attention(a, h):
        T = h.shape[0]
        q = (h @ a["q_b"]["kernel"]).reshape(T, H, N + R)
        down = h @ a["kv_a"]["kernel"]
        c_kv, k_r = down[:, :kv_rank], down[:, kv_rank:]
        c_kv = c_kv * lax.rsqrt((c_kv * c_kv).mean(-1, keepdims=True)
                                + eps) * a["kv_norm"]["scale"]
        kv = (c_kv @ a["kv_b"]["kernel"]).reshape(T, H, N + V)
        causal = jnp.tril(jnp.ones((T, T), bool))

        @jax.checkpoint
        def one_head(args):
            q_nope, q_r, k_nope, v = args
            s = (q_nope @ k_nope.T + q_r @ k_r.T) / math.sqrt(N + R)
            return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v

        o = lax.map(one_head, tuple(x.transpose(1, 0, 2) for x in (
            q[..., :N], q[..., N:], kv[..., :N], kv[..., N:])))
        return o.transpose(1, 0, 2).reshape(T, H * V) @ a["proj"]["kernel"]

    return attention


def reference_experts(cfg):
    """``f(m, b, h, theirs, margin) -> (y, routing)`` for ONE sequence ``h``
    (T, d), an expert layer's parameters ``m`` and its balancing bias ``b``:
    scores ``s = sigmoid(h W_r)`` over all ``experts_routed_over``; the
    ``num_experts_per_token`` largest of ``s + b`` chosen (the program's
    ``theirs`` (T, k) where they are a tie within ``margin``); gates ``s`` —
    never ``s + b`` — renormalised over the chosen and scaled by
    ``routed_scaling_factor``; a ``lax.scan`` over the ``num_experts`` HELD
    experts, each a SwiGLU applied to all tokens and weighted by its gate;
    the shared SwiGLU expert.  ``routing``: assignments of ``theirs`` that
    are not the reference's own, those of them beyond the margin, the
    largest gap one spans."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_token"],
                  cfg["num_experts"])
    scale = float(cfg["routed_scaling_factor"])

    def swiglu(x, w_gate, w_up, w_down):
        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

    def experts(m, b, h, theirs, margin):
        s = jax.nn.sigmoid(h @ m["router"]["kernel"])              # (T, E)
        biased = s + b
        own = biased >= jnp.sort(biased, axis=-1)[:, E - K, None]
        theirs = jax.nn.one_hot(theirs, E, dtype=jnp.bool_).any(axis=1)
        gap = (jnp.where(theirs, -jnp.inf, biased).max(-1)
               - jnp.where(theirs, biased, jnp.inf).min(-1)).astype(
                   jnp.float32)
        tie = (theirs.sum(-1) == K) & (gap <= margin)
        chosen = jnp.where(tie[:, None], theirs, own)
        gates = jnp.where(chosen, s, 0.0)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + 1e-20)

        def one_expert(y, w):
            w_gate, w_up, w_down, gate = w
            return y + gate[:, None] * swiglu(h, w_gate, w_up, w_down), None

        y, _ = lax.scan(one_expert, jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"],
                         gates[:, :held].T))
        shared = m["shared"]
        y = y + swiglu(h, shared["w_gate"], shared["w_up"], shared["w_down"])
        differing = theirs & ~own
        return y, jnp.stack([
            differing.sum().astype(jnp.float32),
            (differing & ~tie[:, None]).sum().astype(jnp.float32),
            jnp.where(differing.any(-1), gap, 0.0).max()])

    return experts


def reference_loss(cfg, dtype: str = "float32"):
    """``f(params, aux, tokens) -> loss``: :func:`reference_given_choices`
    with the bias ``aux`` holds, the program's expert choices under it for
    the same weights and tokens, and the configuration's
    ``tolerances.tie_margin``.  The program that gives the choices is ONE
    jitted function of this call: the harness traces the reference twice
    (its loss, its gradients), and where jax finds the first trace again the
    second keeps 14 ``jax/trace`` spans of the ring's 16,384 for that
    program and not 2,000 (the sandbox; on the chip the ring read the same
    8,835 with and without: ``PERF.md`` section 7, PR 62).

    No host callback, so no ``{"bench": "routing"}`` line: jax keeps a
    program that holds one out of its persistent compile cache, and the
    harness's two programs of this function compile for 75 s and 126 s on
    the chip's host (my chip run, PR 62) — every run, where a run from the
    cache reads them back.  :func:`reference_routing` gives the line's
    numbers to whoever asks."""
    import jax

    given = reference_given_choices(cfg, dtype)
    margin = cfg["tolerances"]["tie_margin"]
    choices = jax.jit(lambda params, aux, tokens: program_expert_choices(
        cfg, params, aux, tokens))

    def loss(params, aux, tokens):
        return given(params, aux, tokens,
                     choices(jax.lax.stop_gradient(params), aux, tokens),
                     margin)

    return loss


def reference_given_choices(cfg, dtype: str = "float32"):
    """``f(params, aux, tokens, theirs, margin) -> loss`` in plain
    ``jax.numpy`` float32 at full matmul precision, ``tokens`` (B, T + 1):
    the stack as config.json, the Kimi Linear report and the DeepSeek-V2
    report describe it — pre-norm residuals of two sub-layers a layer,
    RMSNorm; a KDA mixer (:func:`reference_mixer`) or latent attention
    (:func:`reference_attention`) as ``linear_attn_config`` lists the layer;
    a dense SwiGLU in the first ``first_k_dense_replace`` layers and the
    expert layer of :func:`reference_experts` behind them, choosing by ``s +
    b`` with ``b`` read from ``aux["balance"]`` as the program reads it;
    final RMSNorm, untied head, mean next-token cross-entropy.

    One sequence at a time through ``lax.map``; no kernels, no chunks, no
    padding, no sort, no grouped matmul, no window.  Everything sequential or
    blocked is a ``lax.scan`` or a ``lax.map`` — the recurrence over tokens,
    attention over heads, the experts over the held ones — so that a trace of
    it at the published sizes is a few hundred jitted calls, not tens of
    thousands (the program's span ring holds 16,384).  Every sub-layer is a
    ``jax.checkpoint``, so the backward pass holds one sub-layer's float32
    intermediates at a time.  It shares no code with ``horovod_tpu/ops``,
    ``models`` or ``parallel``.

    **Near-ties are broken as the program broke them**, as
    ``nemotron3_super_lm.reference_given_choices`` does and for its reason;
    the margin is on ``s + b``, what the choice reads.

    ``dtype="bfloat16"`` is the precision control of the comparison and no
    reference: the same plain mathematics with every float32 part (weights,
    statistics, decays, states, softmax, router, scores, the combine) in
    bfloat16 at the default matmul precision."""
    both = _reference(cfg, dtype)
    return lambda *args: both(*args)[0]


def reference_routing(cfg):
    """``f(params, aux, tokens, theirs, margin) -> dict``: how the choices
    ``theirs`` sat with the reference's own over the expert layers of one
    call — ``assignments``, the shares of them that ``disagree`` with the
    reference's choice and that lie ``beyond_margin`` (those the reference
    did not take over), and the ``largest_gap`` in ``s + b`` one spans.
    What the accepted families print from a debug callback, as values."""
    both = _reference(cfg, "float32")
    K = cfg["num_experts_per_token"]

    def routing(params, aux, tokens, theirs, margin):
        said = both(params, aux, tokens, theirs, margin)[1]
        n = said.shape[1] * tokens.shape[0] * cfg["sequence_length"] * K
        return {"assignments": n,
                "disagreeing_share": said[..., 0].sum() / n,
                "beyond_margin_share": said[..., 1].sum() / n,
                "largest_gap": said[..., 2].max()}

    return routing


def _reference(cfg, dtype):
    """``f(params, aux, tokens, theirs, margin) -> (loss, routing)`` of
    :func:`reference_given_choices`, with ``routing`` (B, expert layers, 3)
    what :func:`reference_experts` says of each layer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = cfg["rms_norm_eps"]
    T = cfg["sequence_length"]
    letters = pattern(cfg)
    mixer = reference_mixer(cfg)
    attention = reference_attention(cfg)
    experts = reference_experts(cfg)
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def swiglu(p, x):
        return (jax.nn.silu(x @ p["gate"]["kernel"])
                * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]

    @jax.checkpoint
    def mixer_sublayer(p, x):
        return x + mixer(p["lin"], rms_norm(x, p["norm"]["scale"]))

    @jax.checkpoint
    def attention_sublayer(p, x):
        return x + attention(p["attn"], rms_norm(x, p["norm"]["scale"]))

    @jax.checkpoint
    def dense_sublayer(p, x):
        return x + swiglu(p["mlp"], rms_norm(x, p["mlp_norm"]["scale"]))

    @jax.checkpoint
    def expert_sublayer(p, b, x, theirs, margin):
        y, said = experts(p["moe"], b, rms_norm(x, p["moe_norm"]["scale"]),
                          theirs, margin)
        return x + y, said

    @jax.checkpoint
    def cross_entropy(h, head, labels):
        logits = h @ head
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def one_sequence(params, bias, seq, theirs, margin):
        x = params["tok_emb"]["embedding"][seq[:T]]
        routing = []
        for i, kind in enumerate(letters):
            p = params[f"layer_{i}"]
            x = (mixer_sublayer if kind in "kK" else attention_sublayer)(p, x)
            if kind in "kd":
                x = dense_sublayer(p, x)
            else:
                b = bias[f"layer_{i}"]["moe"]["choice_bias"]
                x, said = expert_sublayer(p, b, x, theirs[len(routing)],
                                          margin)
                routing.append(said)
        h = rms_norm(x, params["ln_f"]["scale"])
        return (cross_entropy(h, params["head"]["kernel"], seq[1:T + 1]),
                jnp.stack(routing))

    def loss(params, aux, tokens, theirs, margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast, bias = jax.tree.map(lambda a: a.astype(dtype),
                                      (params, aux["balance"]))
            ce, routing = lax.map(
                lambda s: one_sequence(cast, bias, *s, margin),
                (tokens, theirs))
        return ce.mean().astype(jnp.float32), routing

    return loss
