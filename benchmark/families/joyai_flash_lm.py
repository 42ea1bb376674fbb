"""Family ``joyai_flash_lm``: the JoyAI-LLM-Flash stack — multi-head latent
attention in every layer (queries, keys and values projected UP from
low-rank latents that are normed first, one rotary key a token that all
heads read, keys of 128 + 64 against values of 128), a leading dense SwiGLU
layer, expert layers whose sigmoid router chooses by ``s + b`` with a
balancing bias ``b`` that no gradient touches, and a multi-token-prediction
module of one expert layer behind the stack —, keyed like the published
config.json (``model_type`` ``joyai_llm_flash``, DeepSeek-V3's key set:
``hidden_size``, ``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``rope_theta``, ``rms_norm_eps``,
``num_nextn_predict_layers``, ``vocab_size``).

The first ``num_hidden_layers`` layers are run — ``first_k_dense_replace``
dense ones, expert layers behind them — and the prediction module's one
expert layer behind those.  The configuration is ONE CHIP'S SHARE of a
deployment that divides the experts and the vocabulary: ``n_routed_experts``
counts the experts held here (the first ones) of the ``experts_routed_over``
the router chooses ``num_experts_per_tok`` of, and ``vocab_size`` this
chip's slice of the vocabulary.  The heads are not divided.
``sequence_length`` is the training sequence; a sequence of the batch is two
ids longer (the labels of the last position's two predictions).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
and ``mtp`` (``models.transformer.JoyAIFlashLM``): ``LatentAttention`` over
the flash family's kernels at values narrower than keys, ``DroplessMoE``
with held experts and ``choice_bias`` (its state is ``make_train_step``'s
``aux_state``), the fused cross-entropy head twice a step.  Everything else
in this file is the benchmark's own yardstick: the host-batch maker, the
model FLOPs, the kernels', the projections' and the expert layers'
operations and bytes, and a plain float32 reference of the same mathematics
— as published, rotary positions on adjacent pairs — that reads the same
parameter tree and the same ``b``.  The loss is ``CE(next token) +
mtp_loss_scaling_factor · CE(the token after)`` and nothing else.
"""

from __future__ import annotations

import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
# The routers' balancing bias is aux state that every step moves by its own
# tokens' counts, so make_train_step may not take it for a pass-through.
# One chip runs the plain program, where this setting adds nothing; across
# data-parallel chips it would average the shards' biases (the mean of
# their signs), which is NOT the published rule — that all-reduces the
# counts first, inside the layer, and nothing here stands in for it.
SYNC_AUX_STATE = True

# The CPU rehearsal's sizes: the dense layer, two expert layers and the
# prediction module, 4 of 16 experts held, top-3; two heads at the PUBLISHED
# head widths (128 | 64 against 128), so that the kernels that take values
# narrower than keys run, interpreted.  A few hundred tokens average
# bfloat16's rounding out far less than a real batch does, so the preset
# brings its own, looser tolerances.
TINY = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": 48, "kv_lora_rank": 32,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "experts_routed_over": 16,
        "num_experts_per_tok": 3, "sequence_length": 64, "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's, with {e} the
# first expert layer and {z} the last layer of the stack.  The first layer's
# attention holds every part of the mechanism (both latents' projections
# down and up, a latent's norm; ``kv_a``'s last 64 columns are the shared
# rotary key's), the last layer's the same behind every expert layer.  The
# routed leaves are the FIRST expert layer's: a routed leaf reads every
# assignment the reference was handed that the compared program did not
# make, and behind more layers the two compiles of the program differ in
# more of them (nemotron-3-super-120b-a12b.json, ``grad_rel_why``).  The
# head's carries both terms of the loss, the table's the stack's gather and
# the prediction module's.
GRAD_LEAVES = (("layer_0", "attn", "q_a", "kernel"),
               ("layer_0", "attn", "q_b", "kernel"),
               ("layer_0", "attn", "kv_a", "kernel"),
               ("layer_0", "attn", "kv_norm", "scale"),
               ("layer_0", "attn", "kv_b", "kernel"),
               ("layer_0", "mlp", "gate", "kernel"),
               ("layer_{e}", "moe", "router", "kernel"),
               ("layer_{e}", "moe", "w_gate"),
               ("layer_{e}", "moe", "shared", "w_up"),
               ("layer_{z}", "attn", "q_b", "kernel"),
               ("layer_{z}", "attn", "proj", "kernel"),
               ("mtp", "eh_proj", "kernel"),
               ("mtp", "layer_0", "attn", "kv_b", "kernel"),
               ("mtp", "layer_0", "moe", "shared", "w_down"),
               ("head", "kernel"),
               ("tok_emb", "embedding"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    dense = cfg["first_k_dense_replace"]
    return "d" * dense + "x" * (cfg["num_hidden_layers"] - dense)


def mtp_pattern(cfg) -> str:
    return "x" * cfg["num_nextn_predict_layers"]


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"e": p.index("x"), "z": len(p) - 1}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import JoyAIFlashLM

    as_published = {
        "model_type": "joyai_llm_flash", "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "n_shared_experts": 1, "moe_layer_freq": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "rope_scaling": None, "rope_interleave": True,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
        "first_k_dense_replace": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if differs or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError(f"joyai_flash_lm runs the stack as published; got "
                         f"{differs or 'grouped KV heads'}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return JoyAIFlashLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        num_heads=cfg["num_attention_heads"],
        mlp_hidden=cfg["intermediate_size"],
        mla=dict(q_latent=cfg["q_lora_rank"], kv_latent=cfg["kv_lora_rank"],
                 nope_dim=cfg["qk_nope_head_dim"],
                 rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"]),
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="sigmoid", renormalize=True,
                 gate_scale=float(cfg["routed_scaling_factor"]),
                 activation="swiglu",
                 shared_hidden=(cfg["n_shared_experts"]
                                * cfg["moe_intermediate_size"]),
                 choice_bias=float(cfg["training"]["bias_update_speed"]),
                 held=(0, cfg["n_routed_experts"])),
        mtp=dict(pattern=mtp_pattern(cfg)))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``: ``aux`` is the
    routers' balancing bias (the collection ``"balance"``, zeros).  No
    parameter's shape depends on the sequence length, so a short one is
    traced."""
    import jax.numpy as jnp
    made = dict(_model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256) + 1),
                       jnp.int32)))
    return made.pop("params"), made


def loss_fn(cfg):
    from horovod_tpu.ops.losses import multi_token_xent

    model = _model(cfg)
    weights = (1.0, float(cfg["training"]["mtp_loss_scaling_factor"]))

    def loss(params, aux, tokens):
        hiddens, moved = model.apply(
            {"params": params, **aux}, tokens[:, :-1], return_hidden=True,
            mutable=["balance"])
        return multi_token_xent(hiddens, model.head_kernel(params), tokens,
                                weights), moved

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError("joyai_flash_lm trains with adamw, not "
                         f"{o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences of ``sequence_length`` tokens plus the labels of the
    last position's two predictions, int32, ids drawn from this chip's
    slice of the vocabulary."""
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 2), dtype=np.int32)


def units_per_sample(cfg) -> int:
    """Tokens a sequence contributes to ``tokens_per_s_chip``: the
    positions trained, each under both terms of the loss."""
    return cfg["sequence_length"]


def _expert_layers(cfg):
    """Paths of the expert layers in the order the reference meets them:
    the stack's, then the prediction module's."""
    return ([(f"layer_{i}",) for i, kind in enumerate(pattern(cfg))
             if kind == "x"]
            + [("mtp", f"layer_{i}") for i in range(len(mtp_pattern(cfg)))])


def program_expert_choices(cfg, params, aux, tokens):
    """The experts the PROGRAM's routers chose for ``tokens`` (B, T + 2)
    under the bias ``aux`` holds: (B, expert layers, T, num_experts_per_tok)
    indices, the stack's layers and then the prediction module's, read from
    what its expert layers sow.  :func:`reference_loss` breaks its
    near-ties with them.  They are read beside the gradients of
    :func:`grad_leaves`, from one forward-and-backward pass of the
    program's own loss, as ``nemotron3_super_lm.program_expert_choices``
    reads them and for its reason; where those gradients are not finite the
    choices break no tie (-1)."""
    import functools

    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.losses import multi_token_xent

    model = _model(cfg)
    weights = (1.0, float(cfg["training"]["mtp_loss_scaling_factor"]))
    paths = grad_leaves(cfg)

    def loss(p):
        hiddens, state = model.apply(
            {"params": p, **aux}, tokens[:, :-1], return_hidden=True,
            mutable=["intermediates"])
        return (multi_token_xent(hiddens, model.head_kernel(p), tokens,
                                 weights), state["intermediates"])

    (_, sown), grads = jax.value_and_grad(loss, has_aux=True)(
        jax.lax.stop_gradient(params))
    finite = jnp.stack([
        jnp.isfinite(functools.reduce(lambda t, k: t[k], path, grads)).all()
        for path in paths]).all()
    B, T = tokens.shape[0], tokens.shape[1] - 2
    chosen = jnp.stack([
        functools.reduce(lambda t, k: t[k], path, sown)["moe"][
            "expert_index"][0].reshape(B, T, -1)
        for path in _expert_layers(cfg)], axis=1)
    return jnp.where(finite, chosen, -1)


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    letters = pattern(cfg) + mtp_pattern(cfg)
    H = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "H": H, "T": cfg["sequence_length"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"],
            # Layers of each kind a step runs: the stack's and the
            # prediction module's; every one of them holds attention.
            "layers": {"d": letters.count("d"), "x": letters.count("x"),
                       "attn": len(letters)}}


def held_share(cfg) -> float:
    """Of a token's ``num_experts_per_tok`` assignments, those that uniform
    routing sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["experts_routed_over"])


def latent_matmuls(cfg):
    """The five projections of ONE latent-attention layer, per position, as
    ``(name, k, n)``."""
    s = _sizes(cfg)
    d, H = s["d"], s["H"]
    q, kv, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], (
        cfg["qk_rope_head_dim"])
    return [("q_a", d, q), ("q_b", q, H * s["qk"]), ("kv_a", d, kv + rope),
            ("kv_b", kv, H * (cfg["qk_nope_head_dim"] + s["v"])),
            ("proj", H * s["v"], d)]


def matmuls(cfg):
    """Every weight matmul of one forward pass, per trained position, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a position (a fraction for the routed experts:
    :func:`held_share`).  The head is read twice, once a term of the loss;
    the prediction module's layer counts with the stack's."""
    s = _sizes(cfg)
    d, L = s["d"], s["layers"]
    eh = cfg["moe_intermediate_size"]
    sh = cfg["n_shared_experts"] * eh
    return ([(name, k, n, L["attn"]) for name, k, n in latent_matmuls(cfg)]
            + [("dense_mlp", d, 3 * cfg["intermediate_size"], L["d"]),
               ("router", d, cfg["experts_routed_over"], L["x"]),
               ("shared", d, 3 * sh, L["x"]),
               ("held", d, 3 * eh, held_share(cfg) * L["x"]),
               ("mtp_eh_proj", 2 * d, d, cfg["num_nextn_predict_layers"]),
               ("head", d, cfg["vocab_size"],
                1 + cfg["num_nextn_predict_layers"])])


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained position of THIS CHIP'S SHARE requires:
    forward plus backward (2 + 4 FLOPs per weight) of every weight matmul it
    runs (:func:`matmuls`) — the routed experts at the share of a token
    uniform routing sends here, the up-projections ONCE —, and of
    attention's two products at the PUBLISHED widths (scores 192 wide,
    values 128) over the causal half of the (T, T) square, forward and twice
    again backward, in every layer and the module's.  Recomputation (the
    up-projections' in the backward pass, the kernels' second pass over the
    scores), the lanes the kernels pad, the rows that round a load up to
    whole windows (``ceil(landed / W)``, ``moe._window_plan``), the embedding
    lookups, the top-k, the sort and the combine are not counted."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = s["layers"]["attn"] * s["T"] * s["H"] * (s["qk"] + s["v"]) / 2
    return 6.0 * n_matmul + 6.0 * attn


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the attention kernels of one step need on one
    chip, from their shapes at the PUBLISHED widths — queries and keys
    ``(B, T, H, 192)``, values ``(B, T, H, 128)``, causal —, over every
    layer and the prediction module's.

    FLOPs, over the causal half: the forward's two products (scores 192
    deep, ``P V`` 128 wide: ``T² H · 320`` a sequence) and the backward's
    five (the scores again, ``dP`` and ``dV`` at 128, ``dQ`` and ``dK`` at
    192: ``· 832``).  Bytes: each direction's compulsory traffic in bf16 —
    forward q, k, v in and o out; backward those and ``dO`` in and dq, dk,
    dv out — plus the float32 row statistics.  The lanes the kernels pad
    (192 to 256) and the shared rotary key written out a head count
    against the share."""
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
    from shapes, over every layer and the prediction module's.

    FLOPs: the five projections (:func:`latent_matmuls`), 2 a weight forward
    and 4 backward, the up-projections ONCE (``recomputed_flops`` is what
    projecting up again in the backward pass adds: it counts against
    ``mla_ms``).  ``pass_bytes``: the byte-bound passes' compulsory traffic
    in bf16 — each latent's norm reads and writes it forward and reads it
    and the cotangent and writes one backward (2 + 3 values a channel), and
    the rotation the same over the ``H · 64`` rotary channels of q and the
    64 of the shared key.  ``bytes`` adds the projections' rows in and out
    and weights, as ``moe_cost`` counts a matmul."""
    s = _sizes(cfg)
    tokens, L = batch_per_chip * s["T"], s["layers"]["attn"]
    flops = L * 6.0 * tokens * sum(k * n for _, k, n in latent_matmuls(cfg))
    up = sum(k * n for name, k, n in latent_matmuls(cfg)
             if name in ("q_b", "kv_b"))
    rope = (s["H"] + 1) * cfg["qk_rope_head_dim"]
    pass_bytes = L * tokens * 5 * 2 * (
        cfg["q_lora_rank"] + cfg["kv_lora_rank"] + rope)

    def matmul_bytes(rows, k_, n_):
        return 3 * rows * (k_ + n_) * 2 + 2 * k_ * n_ * 2 + k_ * n_ * 4

    return {"flops": flops, "recomputed_flops": L * 2.0 * tokens * up,
            "pass_bytes": pass_bytes,
            "bytes": pass_bytes + L * sum(
                matmul_bytes(tokens, k, n)
                for _, k, n in latent_matmuls(cfg))}


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one chip,
    forward and backward, from shapes, over the stack's expert layers and
    the prediction module's: the router over all ``experts_routed_over``
    (``router_flops``: what ``route_ms`` is read for), the held experts'
    three grouped matmuls at the load uniform routing sends here (``A =
    tokens · num_experts_per_tok · held / routed over`` rows) and the shared
    expert's three matmuls over every token.

    FLOPs: 2 a weight forward and 4 backward, the router's float32 product
    at one pass of the bf16 peak (it runs several: that counts against the
    share).  Bytes, per matmul, in bf16 as in ``nemotron3_super_lm.moe_cost``.
    The top-k, the sort, the gathers, the scatter of the combine, the
    activation, the bias update and the rows that round the load up to whole
    windows (a layer runs ``ceil(landed / W)`` of them, ``W`` from
    ``moe._window_plan``) are left out: what the layer takes for them
    counts against its roofline share."""
    d, eh = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sh = cfg["n_shared_experts"] * eh
    E, held = cfg["experts_routed_over"], cfg["n_routed_experts"]
    L = _sizes(cfg)["layers"]["x"]
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
            "assignments": tokens * cfg["num_experts_per_tok"],
            "held_assignments": A, "router_flops": router,
            "expert_parameters": L * 3 * d * (held * eh + sh)}


# ------------------------------------------------------ plain reference


def _say_routing(assignments, differing, beyond, largest_gap):
    print(json.dumps({"bench": "routing", "assignments": int(assignments),
                      "disagreeing_share": float(differing / assignments),
                      "beyond_margin_share": float(beyond / assignments),
                      "largest_gap": float(largest_gap)}), flush=True)


def rotate_adjacent_pairs(x, theta: float):
    """Rotary positions as published (``rope_interleave``): ``x`` (T, ...,
    R), the pair ``(x[2i], x[2i + 1])`` turned by ``t · theta^(-2i/R)``."""
    import jax.numpy as jnp

    T, R = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq      # (T, R/2)
    angle = angle.reshape((T,) + (1,) * (x.ndim - 2) + (R // 2,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def as_published(rotary):
    """The ``R`` rotary channels of the program's layout in the published
    order: the program runs the rotate-half form, so what it holds in
    columns ``i`` and ``i + R/2`` is the published pair ``(2i, 2i + 1)``
    (``models.transformer.LatentAttention``: the permutation a loader of
    published weights applies, undone)."""
    import jax.numpy as jnp

    half = rotary.shape[-1] // 2
    return jnp.stack([rotary[..., :half], rotary[..., half:]],
                     axis=-1).reshape(rotary.shape)


def reference_attention(cfg, leave_out: str = ""):
    """``f(a, h) -> y`` for ONE sequence ``h`` (T, d) and a latent-attention
    layer's parameters ``a``, the equations written out: ``c_q = n(h W_qa)``,
    ``[q_nope | q_rope] = c_q W_qb`` a head; ``[c_kv | k_r] = h W_kva``,
    ``c_kv <- n(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head; rotary
    positions on adjacent pairs of every ``q_rope`` and of the one ``k_r``;
    scores ``(q_nope · k_nope + q_rope · k_r) / sqrt(192)`` under the
    causal mask, softmax, ``o = P v`` (128 wide), ``[o_1 … o_H] W_o``.  One
    head at a time with its (T, T) scores held in full, each recomputed in
    the backward pass.  ``leave_out`` names one part to drop — ``"rope"``
    (the ``q_rope · k_r`` term), ``"norms"`` (the latents' two norms) or
    ``"widths"`` (values as wide as keys: ``v`` read from ``kv_b``'s first
    192 columns a head) — for the tests that show each matters."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H = cfg["num_attention_heads"]
    N, R, V = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    kv_rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])

    def norm(x, scale_):
        if leave_out == "norms":
            return x
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def attention(a, h):
        T = h.shape[0]
        c_q = norm(h @ a["q_a"]["kernel"], a["q_norm"]["scale"])
        q = (c_q @ a["q_b"]["kernel"]).reshape(T, H, N + R)
        down = h @ a["kv_a"]["kernel"]
        c_kv = norm(down[:, :kv_rank], a["kv_norm"]["scale"])
        kv = (c_kv @ a["kv_b"]["kernel"]).reshape(T, H, N + V)
        q_rope = rotate_adjacent_pairs(as_published(q[..., N:]), theta)
        k_r = rotate_adjacent_pairs(as_published(down[:, kv_rank:]), theta)
        causal = jnp.tril(jnp.ones((T, T), bool))
        values = kv[..., N:]
        if leave_out == "widths":
            values = jnp.pad(kv, ((0, 0), (0, 0), (0, R)))[..., :N + R]

        @jax.checkpoint
        def one_head(args):
            q_nope, q_r, k_nope, v = args
            s = q_nope @ k_nope.T
            if leave_out != "rope":
                s = s + q_r @ k_r.T
            s = jnp.where(causal, s / math.sqrt(N + R), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = lax.map(one_head, tuple(x.transpose(1, 0, 2) for x in (
            q[..., :N], q_rope, kv[..., :N], values)))
        o = o[..., :V]
        return o.transpose(1, 0, 2).reshape(T, H * V) @ a["proj"]["kernel"]

    return attention


def reference_experts(cfg):
    """``f(m, b, h, theirs, margin) -> (y, routing)`` for ONE sequence ``h``
    (T, d), an expert layer's parameters ``m`` and its balancing bias ``b``:
    scores ``s = sigmoid(h W_r)`` over all ``experts_routed_over``; the
    ``num_experts_per_tok`` largest of ``s + b`` chosen (the program's
    ``theirs`` (T, k) where they are a tie within ``margin``:
    :func:`reference_given_choices`); gates ``s`` — never ``s + b`` —
    renormalised over the chosen and scaled by ``routed_scaling_factor``; a
    loop over the ``n_routed_experts`` HELD experts, each a SwiGLU applied
    to all tokens and weighted by its gate; the shared SwiGLU expert.
    ``routing``: assignments of ``theirs`` that are not the reference's
    own, those of them beyond the margin, the largest gap one spans."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["n_routed_experts"])
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
    ``tolerances.tie_margin``."""
    given = reference_given_choices(cfg, dtype)
    margin = cfg["tolerances"]["tie_margin"]

    def loss(params, aux, tokens):
        return given(params, aux, tokens,
                     program_expert_choices(cfg, params, aux, tokens), margin)

    return loss


def reference_given_choices(cfg, dtype: str = "float32", leave_out: str = ""):
    """``f(params, aux, tokens, theirs, margin) -> loss`` in plain
    ``jax.numpy`` float32 at full matmul precision, ``tokens`` (B, T + 2):
    the stack as config.json and the DeepSeek-V3 report describe it —
    pre-norm residuals of two sub-layers a layer, RMSNorm; latent attention
    by :func:`reference_attention`; a dense SwiGLU in the first
    ``first_k_dense_replace`` layers and the expert layer of
    :func:`reference_experts` behind them, choosing by ``s + b`` with ``b``
    read from ``aux["balance"]`` as the program reads it; final RMSNorm,
    untied head — and the multi-token-prediction module: ``h' =
    [n_e(Emb(x_{t+1})) | n_h(h_t)] W_eh`` through its own layer and ``n_m``
    to the same head, predicting ``x_{t+2}``.  The loss is the mean
    cross-entropy of the first prediction plus
    ``training.mtp_loss_scaling_factor`` times the second's.

    One sequence at a time through ``lax.map``; no kernels, no padding, no
    sort, no grouped matmul, no window, no recomputed projection: attention
    one head at a time with its (T, T) scores held in full; the experts a
    loop over the ``n_routed_experts`` HELD ones, each applied to ALL tokens
    and weighted by the top-k mask of the scores — what the experts held
    elsewhere would add is left out, as in the program.  Every layer and
    each head pass is a ``jax.checkpoint``, so the backward pass holds one
    layer's float32 intermediates at a time.  It shares no code with
    ``models/transformer.py`` or ``parallel/moe.py``.

    **Near-ties are broken as the program broke them**, as
    ``nemotron3_super_lm.reference_given_choices`` does and for its reason;
    the margin is on ``s + b``, what the choice reads.  Beside its result
    the function prints one ``{"bench": "routing"}`` line a call.

    ``dtype="bfloat16"`` is the precision control of the comparison and no
    reference: the same plain mathematics with every float32 part (weights,
    statistics, softmax, router, scores, the combine) in bfloat16 at the
    default matmul precision.  ``leave_out``:
    :func:`reference_attention`'s."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    K, eps = cfg["num_experts_per_tok"], cfg["rms_norm_eps"]
    lam = float(cfg["training"]["mtp_loss_scaling_factor"])
    T = cfg["sequence_length"]
    attention = reference_attention(cfg, leave_out)
    experts = reference_experts(cfg)
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def swiglu(p, x):
        return (jax.nn.silu(x @ p["gate"]["kernel"])
                * (x @ p["up"]["kernel"])) @ p["down"]["kernel"]

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

    def layers(params, bias, letters, x, theirs, margin, routing):
        for i, kind in enumerate(letters):
            p = params[f"layer_{i}"]
            x = attention_sublayer(p, x)
            if kind == "d":
                x = dense_sublayer(p, x)
            else:
                b = bias[f"layer_{i}"]["moe"]["choice_bias"]
                x, said = expert_sublayer(p, b, x, theirs[len(routing)],
                                          margin)
                routing.append(said)
        return x

    @jax.checkpoint
    def cross_entropy(h, head, labels):
        logits = h @ head
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def one_sequence(params, bias, seq, theirs, margin):
        table, head = params["tok_emb"]["embedding"], params["head"]["kernel"]
        routing = []
        x = layers(params, bias, pattern(cfg), table[seq[:T]], theirs, margin,
                   routing)
        h = rms_norm(x, params["ln_f"]["scale"])
        m = params["mtp"]
        x = jnp.concatenate(
            [rms_norm(table[seq[1:T + 1]], m["n_e"]["scale"]),
             rms_norm(h, m["n_h"]["scale"])], axis=-1) @ m["eh_proj"]["kernel"]
        x = layers(m, bias["mtp"], mtp_pattern(cfg), x, theirs, margin,
                   routing)
        h2 = rms_norm(x, m["n_m"]["scale"])
        return (cross_entropy(h, head, seq[1:T + 1])
                + lam * cross_entropy(h2, head, seq[2:T + 2]),
                jnp.stack(routing))

    def loss(params, aux, tokens, theirs, margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast, bias = jax.tree.map(lambda a: a.astype(dtype),
                                      (params, aux["balance"]))
            ce, routing = lax.map(
                lambda s: one_sequence(cast, bias, *s, margin),
                (tokens, theirs))
        n = tokens.shape[0] * T
        jax.debug.callback(
            _say_routing, routing.shape[1] * n * K, routing[..., 0].sum(),
            routing[..., 1].sum(), routing[..., 2].max())
        return ce.mean().astype(jnp.float32)

    return loss
