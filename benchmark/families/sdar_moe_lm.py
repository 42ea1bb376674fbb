"""Family ``sdar_moe_lm``: SDAR's decoder — grouped-query attention with
per-head QK-norm and rotary positions over softmax-routed SwiGLU experts,
the Qwen3-MoE layer — trained under its BLOCK-DIFFUSION objective, keyed
like the model's own config.json (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``,
``rms_norm_eps``, ``rope_theta``, ``vocab_size``).

The objective (Arriola et al., "Block Diffusion", ICLR 2025, which SDAR's
report, arXiv:2510.06303, adopts): a sequence of ``T`` tokens is cut into
blocks of ``L``; block ``b`` draws a mask rate ``t_b`` and each of its
tokens becomes the mask token with that probability; the loss is ``(1/T)
Σ_b (1/t_b) Σ_{i in b, masked} -log p(x_i | noised block b, clean blocks
before b)``.  One pass gives every block's term: the stack runs the ``2 T``
rows ``[clean ; noised]``, both halves at positions ``0 .. T - 1``, under a
mask in which a clean query reads the clean keys of its own and earlier
blocks and a noised query the clean keys of EARLIER blocks and the noised
keys of its OWN block; only the noised half reaches the head, and a masked
position predicts its own token (no shift).  ``block_diffusion`` in the
configuration holds what config.json does not (``block_length``, the
interval the rates are drawn from, the mask token's id).

``num_hidden_layers`` layers are run, each the two sub-layers ``S`` and
``E`` of the pattern stack.  The configuration is ONE CHIP'S SHARE of an
expert-parallel deployment: ``num_experts`` counts the experts held here
(the first ones), the router is ``experts_routed_over`` wide and chooses
``num_experts_per_tok`` of all of them, and ``vocab_size`` is this chip's
slice of the vocabulary, whose LAST row is the mask token.

The system under test is the repo's ``TransformerLM`` with a ``pattern``
and ``diffusion`` (``models.transformer.SDARLM``): the flash kernels under
the positional block mask, ``DroplessMoE`` with held experts at twice the
rows a token, the fused cross-entropy head over the noised half.  The
noise is DATA: :func:`host_batch` makes ids, masks and weights from the
seed.  Everything else in this file is the benchmark's own yardstick: the
host-batch maker, the model FLOPs, the kernels' operations and bytes, and a
plain float32 reference of the same mathematics that reads the same
parameter tree and shares no code with the program's mask.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: two layers, two query heads over one KV head
# of 128 (the lane-aligned kernels, interpreted), 4 of 8 experts held,
# top-3, 16 blocks of 4 tokens.  Sixty-four tokens average bfloat16's
# rounding out far less than a real batch does, so the preset brings its
# own, looser tolerances.
TINY = {"hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
        "num_experts": 4, "experts_routed_over": 8,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "sequence_length": 64, "vocab_size": 256,
        "block_diffusion": {"block_length": 4, "t_low": 0.45, "t_high": 0.95,
                            "mask_token_id": 255},
        # 180 K parameters and 128 tokens a step: the rate at which one
        # second of steps shows a fall beside the batches' own spread.
        "training": {"optimizer": {"name": "adamw", "learning_rate": 3e-3,
                                   "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                                   "weight_decay": 0.1},
                     "param_dtype": "float32", "compute_dtype": "bfloat16"},
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's: the attention's
# of the first and the last layer ({l}: pattern index 2 l), the first
# layer's router and one expert matrix, the embedding (the gather of both
# streams' rows, the mask token's among them) and the head.  The LAST
# layers' expert leaves are left out as in ``keye_vl2_lm``: on seeded
# random weights and random tokens the deep routers collapse and what is
# left of their gradient is a sum of near-identical rows that cancel.
GRAD_LEAVES = (("layer_{l}", "attn", "q", "kernel"),
               ("layer_{l}", "attn", "kv", "kernel"),
               ("layer_{l}", "attn", "proj", "kernel"),
               ("layer_{l}", "attn", "q_norm", "scale"))
EXPERT_LEAVES = (("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "moe", "w_up"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    return "SE" * cfg["num_hidden_layers"]


def grad_leaves(cfg):
    last = cfg["num_hidden_layers"] - 1
    out = [tuple(part.format(l=2 * layer) for part in path)
           for layer in sorted({0, last}) for path in GRAD_LEAVES]
    return out + list(EXPERT_LEAVES) + [("tok_emb", "embedding"),
                                        ("head", "kernel")]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import SDARLM

    as_published = {
        "model_type": "sdar_moe", "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "tie_word_embeddings": False, "use_sliding_window": False,
        "rope_scaling": None}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    bd = cfg["block_diffusion"]
    if differs or bd["mask_token_id"] != cfg["vocab_size"] - 1:
        raise ValueError(f"sdar_moe_lm runs the stack as published, the mask "
                         f"token the slice's last row; got {differs}, {bd}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return SDARLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="softmax", renormalize=True, activation="swiglu",
                 held=(0, cfg["num_experts"])),
        diffusion=dict(block=bd["block_length"],
                       mask_id=bd["mask_token_id"]))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``.  No parameter's
    shape depends on the sequence length, so a short one is traced.

    The embedding table is drawn at UNIT root-mean-square a row (the
    module's default, ``1 / sqrt(hidden_size)``, times ``sqrt(hidden_size)``;
    the configuration's ``assumed`` says why): at the default the residual
    stream of seeded random weights is the attention's average over hundreds
    of random values, nearly the same for every row — and a third of this
    objective's rows are one mask token besides —, every router sees one
    vector, and where three of its eight choices happen to be held here the
    layer's load sat at the levelled window of three times the uniform load
    that the library ran until PR 53, which overflowed on some of the pool's
    batches and not on others: +55 ms on those steps.  A layer now runs
    ``ceil(landed / W)`` windows, ``W`` from ``moe._window_plan``; the scale
    stays, as part of the weights every reading was taken on."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256)),
                       jnp.int32))["params"]
    table = params["tok_emb"]["embedding"]
    params["tok_emb"]["embedding"] = table * math.sqrt(cfg["hidden_size"])
    return params, {}


def loss_fn(cfg):
    import jax
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]

    def loss(params, aux, batch):
        tokens = batch["tokens"]
        h = model.apply({"params": params}, tokens, return_hidden=True,
                        masked=batch["masked"])
        # ONE pass of the head, over the noised half's T rows a sequence;
        # a position's label is its own clean token.
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"], tokens.reshape(-1))
        with jax.named_scope("bd/loss"):
            weighted = (per_token * batch["weight"].reshape(-1)).sum()
            return weighted / tokens.size, aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"sdar_moe_lm trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def mask_rates(cfg, rng: np.random.Generator, n: int):
    """``(n, blocks)`` mask rates: the low-discrepancy sampler of the MDLM
    and Block-Diffusion code — one uniform offset a sequence, the blocks'
    rates spread evenly over the unit interval from it, their order
    shuffled — mapped onto ``[t_low, t_high]``."""
    bd = cfg["block_diffusion"]
    blocks = cfg["sequence_length"] // bd["block_length"]
    unit = (rng.random((n, 1)) + np.arange(blocks) / blocks) % 1.0
    unit = rng.permuted(unit, axis=1)
    return bd["t_low"] + (bd["t_high"] - bd["t_low"]) * unit


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences with their noise: ``tokens`` (n, T) int32, ids
    uniform over the slice's data rows (every row but the mask token's);
    ``masked`` (n, T) bool, each token of block ``b`` with probability
    ``t_b`` (:func:`mask_rates`); ``weight`` (n, T) float32, ``1 / t_b`` on
    the masked positions and 0 elsewhere."""
    bd = cfg["block_diffusion"]
    T = cfg["sequence_length"]
    tokens = rng.integers(0, bd["mask_token_id"], (n, T), dtype=np.int32)
    rate = np.repeat(mask_rates(cfg, rng, n), bd["block_length"], axis=1)
    masked = rng.random((n, T)) < rate
    return {"tokens": tokens, "masked": masked,
            "weight": np.where(masked, 1.0 / rate, 0.0).astype(np.float32)}


def units_per_sample(cfg) -> int:
    """DATA tokens a sequence contributes to ``tokens_per_s_chip`` (the
    stack runs twice as many rows)."""
    return cfg["sequence_length"]


def program_choices(cfg, params, batch):
    """The experts the PROGRAM's routers chose for ``batch``, read from
    what its layers sow: (B, layers, 2 T, num_experts_per_tok) — the clean
    rows, then the noised.  :func:`reference_loss` breaks its near-ties
    with them."""
    import jax
    import jax.numpy as jnp

    tokens = batch["tokens"]
    _, state = _model(cfg).apply(
        {"params": jax.lax.stop_gradient(params)}, tokens,
        return_hidden=True, masked=batch["masked"],
        mutable=["intermediates"])
    B, T = tokens.shape
    sown = state["intermediates"]
    return jnp.stack([
        sown[f"layer_{2 * i + 1}"]["moe"]["expert_index"][0]
        .reshape(B, 2 * T, -1) for i in range(cfg["num_hidden_layers"])],
        axis=1)


# --------------------------------------------------- FLOPs, from shapes


def live_pairs(T: int, L: int) -> int:
    """(query, key) pairs the block-diffusion mask leaves of a sequence's
    ``4 T^2``: a clean query of block ``b`` reads ``(b + 1) L`` clean keys,
    a noised one ``b L`` clean keys and ``L`` noised — ``T (T + L) / 2``
    each, ``T^2 + T L`` together."""
    blocks = T // L
    clean = sum(L * (b + 1) * L for b in range(blocks))
    noised = sum(L * (b * L + L) for b in range(blocks))
    assert clean == noised == T * (T + L) // 2
    return clean + noised


def _sizes(cfg):
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "T": cfg["sequence_length"], "L": cfg["num_hidden_layers"],
            "block": cfg["block_diffusion"]["block_length"]}


def matmuls(cfg):
    """Every weight matmul of one forward pass that the loss depends on,
    per DATA token, as ``(name, k, n, count)``: a (1, k) row times a (k, n)
    weight, ``count`` of them a token.  A token is two rows of the stack,
    so a layer's matmuls count twice — but in the LAST layer, whose clean
    rows feed nothing but that layer's keys and values: there only ``kv``
    runs on both.  The routed experts at the held share of the
    ``num_experts_per_tok`` a row is routed to; the head on the noised
    rows alone."""
    s = _sizes(cfg)
    d, L = s["d"], s["L"]
    rows = 2 * (L - 1) + 1
    qw, kvw = s["H"] * s["D"], 2 * s["Hkv"] * s["D"]
    held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["experts_routed_over"])
    eh = cfg["moe_intermediate_size"]
    return [("attn_q", d, qw, rows), ("attn_kv", d, kvw, 2 * L),
            ("attn_proj", qw, d, rows),
            ("router", d, cfg["experts_routed_over"], rows),
            ("w_gate", d, eh, held * rows), ("w_up", d, eh, held * rows),
            ("w_down", eh, d, held * rows),
            ("head", d, cfg["vocab_size"], 1)]


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained DATA token requires: forward plus backward
    (2 + 4 FLOPs per weight) of every weight matmul the loss depends on
    (:func:`matmuls`) and of attention's two products over the pairs the
    mask leaves (``4 H D`` a pair forward) — all ``T^2 + T L`` of a layer,
    but the last layer's clean queries, which nothing reads: the noised
    queries' half there.  The model's work, not the implementation's: the
    program runs the last layer's clean stream whole (PERF.md section 7)
    and is credited nothing for it; a tile computed whole under a mask is
    credited its live pairs."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    pairs = live_pairs(s["T"], s["block"]) * (s["L"] - 0.5) / s["T"]
    return 6.0 * n_matmul + 3.0 * 4.0 * s["H"] * s["D"] * pairs


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the attention kernels of one step need on one
    chip, whatever implements them, from the mask's LIVE pairs: ``4 D H`` a
    pair forward (two products) and ``10 D H`` backward (five: the score
    recompute belongs to the algorithm, once) in every layer's call, over
    all ``2 T`` query rows — the kernels are called so.  Bytes: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o, do
    and writes dq, dk, dv — in bf16, k, v, dk, dv at their ``H_kv`` heads —
    plus the float32 row statistics."""
    s = _sizes(cfg)
    B, rows, H, Hkv, D, L = (batch_per_chip, 2 * s["T"], s["H"], s["Hkv"],
                             s["D"], s["L"])
    pairs = B * live_pairs(s["T"], s["block"])
    q, kv = B * rows * H * D * 2, B * rows * Hkv * D * 2   # one bf16 tensor
    stat = B * H * rows * 4
    nbytes = L * ((2 * q + 2 * kv + stat)                  # forward
                  + (4 * q + 4 * kv + 2 * stat))           # backward
    return {"flops": L * (4.0 + 10.0) * D * H * pairs, "bytes": nbytes,
            "shape": [B, rows, H, Hkv, D], "calls_per_step": L,
            "live_pairs": pairs, "all_pairs": B * rows * rows}


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one
    chip, forward and backward, from shapes, as ``keye_vl2_lm.moe_cost``
    counts them — at the ``2 T`` rows a sequence that the stack runs: the
    router over all ``experts_routed_over`` and the held SwiGLU experts'
    three grouped matmuls at the load uniform routing sends here (``A =
    rows * num_experts_per_tok * held / routed over``)."""
    d, eh = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, held, k = (cfg["experts_routed_over"], cfg["num_experts"],
                  cfg["num_experts_per_tok"])
    L = cfg["num_hidden_layers"]
    rows = 2 * batch_per_chip * cfg["sequence_length"]
    A = rows * k * held / E
    flops = L * 6.0 * (rows * d * E + 3 * A * d * eh)
    moved = A * (d + eh) * 2            # one grouped matmul's rows, in + out
    weights = held * d * eh             # one projection's, every held expert
    nbytes = L * 3 * (3 * moved + 2 * weights * 2 + weights * 4)
    return {"flops": flops, "bytes": nbytes, "assignments": rows * k,
            "held_assignments": A, "expert_parameters": L * 3 * weights}


# ------------------------------------------------------ plain reference


def _say_choices(what, total, differing, beyond, largest_gap):
    print(json.dumps({"bench": what, "chosen": int(total),
                      "disagreeing_share": float(differing / total),
                      "beyond_margin_share": float(beyond / total),
                      "largest_gap": float(largest_gap)}), flush=True)


def reference_loss(cfg, dtype: str = "float32"):
    """``f(params, aux, batch) -> loss``: :func:`reference_given_choices`
    with the program's expert choices for the same weights and batch and
    the configuration's margin."""
    given = reference_given_choices(cfg, dtype)
    margin = cfg["tolerances"]["tie_margin"]

    def loss(params, aux, batch):
        return given(params, batch, program_choices(cfg, params, batch),
                     margin)

    return loss


def reference_given_choices(cfg, dtype: str = "float32"):
    """``f(params, batch, experts, tie_margin) -> loss`` in plain
    ``jax.numpy`` float32: the stack and the objective as config.json and
    the configuration's ``assumed`` describe them.

    A sequence's ``2 T`` rows are ``[x ; where(masked, mask id, x)]`` at
    positions ``[0 .. T - 1, 0 .. T - 1]``.  A layer is ``h = x +
    Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.  *Attention*: ``q =
    W_q x``, ``k, v = W_kv x`` (H query heads over H_kv KV heads of D),
    RMSNorm over each head's channels of q and of k, rotary positions
    (rotate-half, theta ``rope_theta``) at the rows' POSITIONS, a dense
    softmax over the keys the ``2 T x 2 T`` mask leaves.  The mask is
    built here from the objective's four rules, literally, a block of query
    rows at a time (recomputed in the backward pass, so that T 8,192 fits)
    — clean query, clean key: ``blk(key) <= blk(query)``; clean query,
    noised key: never; noised query, clean key: ``blk(key) < blk(query)``;
    noised query, noised key: ``blk(key) == blk(query)`` — with no tile, no
    online softmax and none of the program's mask code.  *Experts*: as in
    ``keye_vl2_lm`` — softmax scores over all ``experts_routed_over``, the
    ``num_experts_per_tok`` largest, gates renormalised over the chosen; a
    loop over the ``num_experts`` HELD ones, each applied to ALL rows and
    weighted by the top-k mask; what the experts held elsewhere would add
    is left out, as in the program.  The loss is ``Σ weight · CE(noised
    row, the position's clean token) / T`` over the vocabulary slice.

    **Near-ties of the routers are broken as the program broke them**
    (``experts`` (B, layers, 2 T, k)), inside ``tie_margin``, as in
    ``nemotron_h_lm``; beside its result the function prints a
    ``{"bench": "routing"}`` line.

    ``dtype="bfloat16"`` is the precision control of the comparison and no
    reference: the same plain mathematics with every float32 part in
    bfloat16 at the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    H, Hkv, D, T, blk = s["H"], s["Hkv"], s["D"], s["T"], s["block"]
    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["num_experts"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    mask_id = cfg["block_diffusion"]["mask_token_id"]
    n_layers = cfg["num_hidden_layers"]
    dtype = jnp.dtype(dtype)
    # Of each of the 2 T rows: its position in the sequence, whether it is
    # of the noised copy, and its block.
    position = jnp.concatenate([jnp.arange(T), jnp.arange(T)])
    is_noised = jnp.concatenate([jnp.zeros(T, bool), jnp.ones(T, bool)])
    block_of = position // blk

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def rotary(x):                                   # (2 T, heads, width)
        half = x.shape[-1] // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = (position.astype(jnp.float32)[:, None] * freq)[:, None]
        cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
            x.dtype)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(a, h):
        rows = 2 * T
        q = (h @ a["q"]["kernel"]).reshape(rows, H, D)
        k, v = jnp.split(h @ a["kv"]["kernel"], 2, axis=-1)
        k, v = k.reshape(rows, Hkv, D), v.reshape(rows, Hkv, D)
        q = rotary(rms_norm(q, a["q_norm"]["scale"]))
        k = rotary(rms_norm(k, a["k_norm"]["scale"]))

        @jax.checkpoint
        def queries(args):
            q_b, q_noised, q_block = args
            qn, qb = q_noised[:, None], q_block[:, None]
            kn, kb = is_noised[None, :], block_of[None, :]
            visible = ((~qn & ~kn & (kb <= qb))      # clean reads clean
                       | (qn & ~kn & (kb < qb))      # noised reads clean
                       | (qn & kn & (kb == qb)))     # noised reads its own
            logits = jnp.einsum("tgrd,sgd->grts",
                                q_b.reshape(-1, Hkv, H // Hkv, D), k)
            logits = jnp.where(visible, logits / math.sqrt(D), -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("grts,sgd->tgrd", probs, v).reshape(-1, H * D)

        step = min(rows, 128)
        out = lax.map(queries, tuple(
            x.reshape(rows // step, step, *x.shape[1:])
            for x in (q, is_noised, block_of)))
        return out.reshape(rows, H * D) @ a["proj"]["kernel"]

    def experts(m, h, theirs, margin):
        sc = jax.nn.softmax(h @ m["router"]["kernel"], axis=-1)   # (2T, E)
        own = sc >= jnp.sort(sc, axis=-1)[:, E - K, None]
        theirs = jax.nn.one_hot(theirs, E, dtype=jnp.bool_).any(axis=1)
        gap = (jnp.where(theirs, -jnp.inf, sc).max(-1)
               - jnp.where(theirs, sc, jnp.inf).min(-1)).astype(jnp.float32)
        tie = (theirs.sum(-1) == K) & (gap <= margin)
        chosen = jnp.where(tie[:, None], theirs, own)
        gates = jnp.where(chosen, sc, 0.0)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)

        @jax.checkpoint
        def one_expert(w_gate, w_up, w_down, gate):
            return gate[:, None] * (
                (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

        y, _ = lax.scan(lambda y, w: (y + one_expert(*w), None),
                        jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"],
                         gates[:, :held].T))
        differing = theirs & ~own
        return y, jnp.stack([
            differing.sum().astype(jnp.float32),
            (differing & ~tie[:, None]).sum().astype(jnp.float32),
            jnp.where(differing.any(-1), gap, 0.0).max()])

    def one_sequence(params, tokens, masked, weight, chosen_experts, margin):
        ids = jnp.concatenate([tokens, jnp.where(masked, mask_id, tokens)])
        x = params["tok_emb"]["embedding"][ids]
        routing = []
        # A sub-layer's intermediates are made again in the backward
        # pass: what is kept between them is the residual stream.
        attend = jax.checkpoint(lambda p, x: attention(
            p["attn"], rms_norm(x, p["norm"]["scale"])))
        route = jax.checkpoint(lambda p, x, theirs: experts(
            p["moe"], rms_norm(x, p["norm"]["scale"]), theirs, margin))
        for i in range(n_layers):
            x = x + attend(params[f"layer_{2 * i}"], x)
            y, said = route(params[f"layer_{2 * i + 1}"], x,
                            chosen_experts[i])
            x = x + y
            routing.append(said)
        x = rms_norm(x[T:], params["ln_f"]["scale"])       # the noised half
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        return (weight.astype(x.dtype) * (lse - picked)).sum() / T, jnp.stack(
            routing)

    def loss(params, batch, chosen_experts, tie_margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            total, routing = lax.map(
                lambda a: one_sequence(cast, *a, tie_margin),
                (batch["tokens"], batch["masked"], batch["weight"],
                 chosen_experts))
        jax.debug.callback(
            functools.partial(_say_choices, "routing"),
            n_layers * batch["tokens"].size * 2 * K, routing[..., 0].sum(),
            routing[..., 1].sum(), routing[..., 2].max())
        return total.mean().astype(jnp.float32)

    return loss
