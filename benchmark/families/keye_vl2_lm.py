"""Family ``keye_vl2_lm``: the language tower of Keye-VL-2.0 — grouped-query
attention with per-head QK-norm and rotary positions whose keys a learned
indexer selects (``sa_config``: DeepSeek Sparse Attention), over softmax-
routed SwiGLU experts —, keyed like the model's own config.json
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``num_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``, ``rms_norm_eps``,
``rope_theta``, ``sa_config``, ``vocab_size``).

``num_hidden_layers`` layers are run, each the two sub-layers ``S`` and
``E`` of the pattern stack.  The configuration is ONE CHIP'S SHARE of an
expert-parallel deployment: ``num_experts`` counts the experts held here
(the first ones), the router is ``experts_routed_over`` wide and chooses
``num_experts_per_tok`` of all of them, and ``vocab_size`` is this chip's
slice of the vocabulary.  ``sequence_length`` is the training sequence
(``max_position_embeddings`` stays the model's declared 262,144).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.KeyeLM``): ``ops/sparse_select.py``'s scores,
selection and KL pass, the flash kernels under a selection map,
``DroplessMoE`` with held experts, the fused cross-entropy head.  The loss
is the mean next-token cross-entropy plus every layer's ``L_I`` at weight
1.  Everything else in this file is the benchmark's own yardstick: the
host-batch maker, the model FLOPs, the new kernels' operations and bytes,
and a plain float32 reference of the same mathematics that reads the same
parameter tree.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: two layers, two query heads over one KV head
# of 128 (the lane-aligned kernels, interpreted), an indexer of 4 heads
# that keeps 16 of up to 64 keys, 4 of 8 experts held, top-3.  A hundred
# tokens average bfloat16's rounding out far less than a real batch does,
# so the preset brings its own, looser tolerances.
TINY = {"hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                      "q_chunk_size": 32, "topk": 16},
        "num_experts": 4, "experts_routed_over": 8,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "sequence_length": 64, "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5, "select_margin": 2.0 ** -4}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's: the attention's
# and the indexer's of the first and the last layer ({l}: pattern index
# 2 l), the first layer's router and one expert matrix, the embedding and
# the head.  The LAST layers' expert leaves are left out: on seeded random
# weights and random tokens the deep routers collapse (every query's
# attention averages ~2,048 random values, so the stream's token-independent
# part grows with depth: in the 4th of 4 layers 8 of the 16 held experts
# received no token and one 96% of the gradient's norm; PERF.md section 6,
# PR 36), and what is left of their gradient is a sum of near-identical rows
# that cancel — the shipped program read 0.02 to 0.33 there over three
# seeds, against 0.04 to 0.08 in the first layer.  The experts' backward
# pass of every layer still lies under the first layer's and the embedding's
# leaves.
GRAD_LEAVES = (("layer_{l}", "attn", "q", "kernel"),
               ("layer_{l}", "attn", "kv", "kernel"),
               ("layer_{l}", "attn", "proj", "kernel"),
               ("layer_{l}", "attn", "q_norm", "scale"),
               ("layer_{l}", "attn", "index_q", "kernel"),
               ("layer_{l}", "attn", "index_k", "kernel"),
               ("layer_{l}", "attn", "index_w", "kernel"))
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
    from horovod_tpu.models import KeyeLM

    as_published = {
        "model_type": "KeyeVL2", "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "tie_word_embeddings": False, "use_sliding_window": False}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if differs or cfg["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError(f"keye_vl2_lm runs the stack as published, the "
                         f"indexer over one key head; got {differs}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    sa = cfg["sa_config"]
    return KeyeLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        indexer=dict(num_heads=sa["indexer_num_heads"],
                     head_dim=sa["indexer_head_dim"], topk=sa["topk"],
                     tile=sa["q_chunk_size"]),
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="softmax", renormalize=True, activation="swiglu",
                 held=(0, cfg["num_experts"])))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``.  No parameter's
    shape depends on the sequence length, so a short one is traced."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256)),
                       jnp.int32))["params"]
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.models import index_losses
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]

    def loss(params, aux, tokens):
        h, state = model.apply({"params": params}, tokens[:, :-1],
                               return_hidden=True, mutable=["intermediates"])
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"],
            tokens[:, 1:].reshape(-1))
        return per_token.mean() + index_losses(state["intermediates"]), aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"keye_vl2_lm trains with adamw, not {o['name']!r}")
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


def program_choices(cfg, params, tokens):
    """What the PROGRAM chose for ``tokens`` (B, T + 1), read from what its
    layers sow: ``(select, experts)`` — the selection maps (B, layers, T,
    T) int8 and the routers' experts (B, layers, T, num_experts_per_tok).
    :func:`reference_loss` breaks its near-ties with them."""
    import jax
    import jax.numpy as jnp

    _, state = _model(cfg).apply(
        {"params": jax.lax.stop_gradient(params)}, tokens[:, :-1],
        return_hidden=True, mutable=["intermediates"])
    B, T = tokens.shape[0], tokens.shape[1] - 1
    sown = state["intermediates"]
    layers = range(cfg["num_hidden_layers"])
    select = [sown[f"layer_{2 * i}"]["attn"]["select"][0] for i in layers]
    experts = [sown[f"layer_{2 * i + 1}"]["moe"]["expert_index"][0]
               .reshape(B, T, -1) for i in layers]
    return jnp.stack(select, axis=1), jnp.stack(experts, axis=1)


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    sa = cfg["sa_config"]
    T, topk = cfg["sequence_length"], sa["topk"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "HI": sa["indexer_num_heads"], "DI": sa["indexer_head_dim"],
            "topk": topk, "T": T, "L": cfg["num_hidden_layers"],
            # Pairs (query, key) a sequence: the causal ones, and the
            # selected ones, min(t + 1, topk) a query.
            "causal_pairs": T * (T + 1) // 2,
            "selected_pairs": (min(T, topk) * (min(T, topk) + 1) // 2
                               + max(T - topk, 0) * topk)}


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a token (a fraction for the routed experts: of the
    ``num_experts_per_tok`` a token is routed to, the held share)."""
    s = _sizes(cfg)
    d, L = s["d"], s["L"]
    qw, kvw = s["H"] * s["D"], 2 * s["Hkv"] * s["D"]
    held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["experts_routed_over"])
    eh = cfg["moe_intermediate_size"]
    return [("attn_q", d, qw, L), ("attn_kv", d, kvw, L),
            ("attn_proj", qw, d, L),
            ("index_q", d, s["HI"] * s["DI"], L), ("index_k", d, s["DI"], L),
            ("index_w", d, s["HI"], L),
            ("router", d, cfg["experts_routed_over"], L),
            ("w_gate", d, eh, held * L), ("w_up", d, eh, held * L),
            ("w_down", eh, d, held * L),
            ("head", d, cfg["vocab_size"], 1)]


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward (2 +
    4 FLOPs per weight) of every weight matmul it runs — the routed
    experts at the held share —, of the selected attention's two products
    over the pairs the indexer SELECTS (``4 H D |S_t|`` a query forward),
    and of the indexer's scores over the causal pairs (``2 H_I D_I (t +
    1)`` a query forward).  The model's work, not the implementation's: a
    step that multiplies every causal tile of the attention is credited
    with the selected pairs alone.  Recomputation (the KL pass's second
    look at the probabilities) is not counted; the embedding lookup, the
    selection, the sort and the combine are no matmuls."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = 4.0 * s["H"] * s["D"] * s["selected_pairs"] / s["T"]
    index = 2.0 * s["HI"] * s["DI"] * s["causal_pairs"] / s["T"]
    return 6.0 * n_matmul + 3.0 * s["L"] * (attn + index)


def sel_flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the selected attention of one step needs on
    one chip, whatever implements it: the SELECTED pairs' two products
    forward and five backward (``gpt2_lm.flash_cost``'s count: the score
    recompute belongs to the algorithm, once), ``2 H D`` FLOPs a pair and
    product.  Bytes: each kernel's compulsory traffic in bf16, as
    ``nemotron_h_lm.flash_cost`` counts it (k, v, dk, dv at their ``H_kv``
    heads), the float32 row statistics, and the int8 selection map once.
    A version that visits every causal tile does 4.3 times the products
    at T 16,384 and reads a share no higher than 23%."""
    s = _sizes(cfg)
    B, T, H, Hkv, D, L = (batch_per_chip, s["T"], s["H"], s["Hkv"], s["D"],
                          s["L"])
    product = 2.0 * B * H * D * s["selected_pairs"]
    q, kv = B * T * H * D * 2, B * T * Hkv * D * 2     # one bf16 tensor
    stat = B * H * T * 4
    nbytes = L * ((2 * q + 2 * kv + stat)              # forward
                  + (3 * q + 2 * kv + 2 * stat)        # dq
                  + (2 * q + 4 * kv + 2 * stat)        # dk/dv
                  + B * T * T)                         # the map, once
    return {"flops": L * (2 + 5) * product, "bytes": nbytes,
            "shape": [B, T, H, Hkv, D], "calls_per_step": L,
            "selected_pairs": B * s["selected_pairs"],
            "causal_pairs": B * s["causal_pairs"]}


def index_scores_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the indexer's scores of one step need (the
    ``index_scores`` kernel; forward only, nothing of it is
    differentiated): ``2 H_I D_I`` FLOPs a causal pair; it reads ``qI``,
    ``kI`` in bf16 and ``w`` in float32 and writes one float32 score a
    causal pair."""
    s = _sizes(cfg)
    B, T, HI, DI, L = batch_per_chip, s["T"], s["HI"], s["DI"], s["L"]
    pairs = B * s["causal_pairs"]
    return {"flops": L * 2.0 * HI * DI * pairs,
            "bytes": L * (B * T * (HI * DI + DI) * 2 + B * T * HI * 4
                          + pairs * 4)}


def index_kl_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the KL pass of one step needs (the ``index_kl``
    kernel: ``L_I`` and its gradient in one pass), over the SELECTED pairs
    alone: a pair's probability from each of the H heads (``2 H D``), its
    score again (``2 H_I D_I``) and the two gradient products into ``dqI``
    and ``dkI`` (``4 H_I D_I``).  Bytes: q, k, qI, kI in bf16, the row
    statistics and ``w`` in float32, the map once, and the three gradients
    in float32."""
    s = _sizes(cfg)
    B, T, H, Hkv, D, HI, DI, L = (batch_per_chip, s["T"], s["H"], s["Hkv"],
                                  s["D"], s["HI"], s["DI"], s["L"])
    pairs = B * s["selected_pairs"]
    reads = (B * T * ((H + Hkv) * D + HI * DI + DI) * 2
             + B * T * (H + 1 + HI) * 4 + B * T * T)
    writes = B * T * (HI * DI + DI + HI + 1) * 4
    return {"flops": L * pairs * (2.0 * H * D + 6.0 * HI * DI),
            "bytes": L * (reads + writes)}


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one
    chip, forward and backward, from shapes: the router over all
    ``experts_routed_over`` and the held SwiGLU experts' three grouped
    matmuls at the load uniform routing sends here (``A = tokens *
    num_experts_per_tok * held / routed over`` rows).

    FLOPs: 2 a weight forward and 4 backward.  Bytes, per matmul, in
    bf16 as in ``olmoe_lm.moe_cost``: forward its rows in and out and the
    weights; the input-gradient product the same again; the
    weight-gradient product both sets of rows and the gradient in float32.
    The sort, the gathers, the scatter of the combine and the activation
    are left out: what the layer takes for them counts against its
    roofline share."""
    d, eh = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, held, k = (cfg["experts_routed_over"], cfg["num_experts"],
                  cfg["num_experts_per_tok"])
    L = cfg["num_hidden_layers"]
    tokens = batch_per_chip * cfg["sequence_length"]
    A = tokens * k * held / E
    flops = L * 6.0 * (tokens * d * E + 3 * A * d * eh)
    rows = A * (d + eh) * 2             # one grouped matmul's rows, in + out
    weights = held * d * eh             # one projection's, every held expert
    nbytes = L * 3 * (3 * rows + 2 * weights * 2 + weights * 4)
    return {"flops": flops, "bytes": nbytes, "assignments": tokens * k,
            "held_assignments": A, "expert_parameters": L * 3 * weights}


# ------------------------------------------------------ plain reference


def _say_choices(what, total, differing, beyond, largest_gap):
    print(json.dumps({"bench": what, "chosen": int(total),
                      "disagreeing_share": float(differing / total),
                      "beyond_margin_share": float(beyond / total),
                      "largest_gap": float(largest_gap)}), flush=True)


def reference_loss(cfg, dtype: str = "float32"):
    """``f(params, aux, tokens) -> loss``: :func:`reference_given_choices`
    with the program's selections and expert choices for the same weights
    and tokens and the configuration's two margins."""
    given = reference_given_choices(cfg, dtype)
    tol = cfg["tolerances"]

    def loss(params, aux, tokens):
        select, experts = program_choices(cfg, params, tokens)
        return given(params, tokens, select, experts, tol["select_margin"],
                     tol["tie_margin"])

    return loss


def reference_given_choices(cfg, dtype: str = "float32"):
    """``f(params, tokens, select, experts, select_margin, tie_margin) ->
    loss`` in plain ``jax.numpy`` float32: the stack as config.json and the
    configuration's ``assumed`` describe it.  A layer is ``h = x +
    Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.

    *Attention*: ``q = W_q x``, ``k, v = W_kv x`` (H query heads over H_kv
    KV heads of D), RMSNorm over each head's channels of q and of k,
    rotary positions (rotate-half, theta ``rope_theta``).  *Indexer*, on
    the normed input DETACHED: ``qI = rot(W_qI x)`` (H_I heads of D_I),
    ``kI = rot(W_kI x)``, ``w = W_w x``; ``I[t, s] = (H_I D_I)^-1/2 Σ_j
    w[t, j] relu(qI[t, j] · kI[s])`` for ``s <= t``; ``S_t`` the ``min(t +
    1, topk)`` largest by ``lax.top_k`` (a tie to the lower index).  The
    heads attend over ``S_t`` alone by a dense masked softmax.  ``L_I =
    mean_t KL(p_t ‖ softmax_{S_t} I_t)`` with ``p`` the heads' mean
    probability, detached.  Scores, top-k, attention and KL run a block of
    queries at a time (each recomputed in the backward pass) so that
    T 16,384 fits; no kernel, no map of tiles, no online softmax.

    *Experts*: softmax scores over all ``experts_routed_over``, the
    ``num_experts_per_tok`` largest chosen, gates renormalised over the
    chosen; SwiGLU experts; a loop over the ``num_experts`` HELD ones, each
    applied to ALL tokens and weighted by the top-k mask — what the
    experts held elsewhere would add is left out, as in the program.

    The loss is the mean token cross-entropy over the vocabulary slice
    plus every layer's ``L_I``.

    **Near-ties are broken as the program broke them**, for the routers as
    in ``nemotron_h_lm`` and for the selection by the same rule.  The
    choice of a query's ``topk`` keys is discrete; the program's scores
    come from a bfloat16 residual stream and bfloat16 products, so keys
    whose scores lie within its rounding of the ``topk``-th may fall
    either side, and either choice is as right.  The reference computes
    its own float32 scores and its own top k, and takes the program's set
    for a query (``select`` (B, layers, T, T), nonzero where chosen) where
    it is ``min(t + 1, topk)`` causal keys of which none scores more than
    ``select_margin`` below one left out; everywhere else it keeps its
    own.  Scores, probabilities, ``L_I`` and everything after are the
    reference's own either way.  A program that selects fewer keys, the
    most recent ones, or by another score is compared with the
    reference's own choice and fails the gradient check.  Beside its
    result the function prints (``{"bench": "selection"}`` and
    ``{"bench": "routing"}`` lines, from debug callbacks) the share of the
    program's choices that are not the reference's, the share of them
    beyond the margin, and the largest gap a differing choice spans.

    ``dtype="bfloat16"`` is the precision control of the comparison and
    no reference: the same plain mathematics with every float32 part in
    bfloat16 at the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    H, Hkv, D, HI, DI, topk = (s["H"], s["Hkv"], s["D"], s["HI"], s["DI"],
                               s["topk"])
    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["num_experts"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    n_layers = cfg["num_hidden_layers"]
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def rotary(x):                                   # (T, heads, width)
        T, half = x.shape[0], x.shape[-1] // 2
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = (jnp.arange(T, dtype=jnp.float32)[:, None] * freq)[:, None]
        cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(
            x.dtype)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(a, h, theirs, margin):
        T = h.shape[0]
        block = min(T, 128)
        q = (h @ a["q"]["kernel"]).reshape(T, H, D)
        k, v = jnp.split(h @ a["kv"]["kernel"], 2, axis=-1)
        k, v = k.reshape(T, Hkv, D), v.reshape(T, Hkv, D)
        q = rotary(rms_norm(q, a["q_norm"]["scale"]))
        k = rotary(rms_norm(k, a["k_norm"]["scale"]))
        hi = lax.stop_gradient(h)
        qi = rotary((hi @ a["index_q"]["kernel"]).reshape(T, HI, DI))
        ki = rotary((hi @ a["index_k"]["kernel"]).reshape(T, 1, DI))[:, 0]
        w = hi @ a["index_w"]["kernel"]
        cols = jnp.arange(T)

        @jax.checkpoint
        def queries(args):
            rows, q_b, qi_b, w_b, theirs_b = args
            causal = cols[None, :] <= rows[:, None]
            products = jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi_b, ki))
            scores = jnp.einsum("tj,tjs->ts", w_b, products) / math.sqrt(
                HI * DI)
            scores = jnp.where(causal, scores, -jnp.inf)
            values, index = lax.top_k(lax.stop_gradient(scores),
                                      min(topk, T))
            tau = values[:, -1:]
            last_tie = jnp.where(values == tau, index, -1).max(-1)[:, None]
            own = causal & ((scores > tau)
                            | ((scores == tau) & (cols[None] <= last_tie)))
            # The program's set for each query, and how far below a causal
            # key it left out its lowest choice scores.
            theirs_b = theirs_b != 0
            f32 = scores.astype(jnp.float32)
            gap = (jnp.where(causal & ~theirs_b, f32, -jnp.inf).max(-1)
                   - jnp.where(theirs_b, f32, jnp.inf).min(-1))
            tie = ((theirs_b.sum(-1) == jnp.minimum(rows + 1, topk))
                   & ~(theirs_b & ~causal).any(-1) & (gap <= margin))
            chosen = jnp.where(tie[:, None], theirs_b, own)
            logits = jnp.einsum("tgrd,sgd->grts",
                                q_b.reshape(-1, Hkv, H // Hkv, D), k)
            logits = jnp.where(chosen, logits / math.sqrt(D), -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("grts,sgd->tgrd", probs, v).reshape(-1, H * D)
            p = lax.stop_gradient(probs.mean(axis=(0, 1)))
            log_pi = jax.nn.log_softmax(
                jnp.where(chosen, scores, -jnp.inf), axis=-1)
            kl = jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                       - jnp.where(chosen, log_pi, 0.0)),
                           0.0).sum(-1)
            differing = theirs_b & ~own
            said = jnp.stack([
                differing.sum().astype(jnp.float32),
                (differing & ~tie[:, None]).sum().astype(jnp.float32),
                jnp.where(differing.any(-1), gap, 0.0).max()])
            return out, kl, said

        def blocks(x):
            return x.reshape(T // block, block, *x.shape[1:])

        out, kl, said = lax.map(queries, (blocks(cols), blocks(q), blocks(qi),
                                          blocks(w), blocks(theirs)))
        said = jnp.stack([said[:, 0].sum(), said[:, 1].sum(),
                          said[:, 2].max()])
        return (out.reshape(T, H * D) @ a["proj"]["kernel"], kl.mean(), said)

    def experts(m, h, theirs, margin):
        sc = jax.nn.softmax(h @ m["router"]["kernel"], axis=-1)   # (T, E)
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

    def one_sequence(params, seq, select, chosen_experts, margins):
        inp, labels = seq[:-1], seq[1:]
        x = params["tok_emb"]["embedding"][inp]
        index_loss, selection, routing = 0.0, [], []
        # A sub-layer's intermediates are made again in the backward
        # pass: what is kept between them is the residual stream.
        attend = jax.checkpoint(lambda p, x, theirs: attention(
            p["attn"], rms_norm(x, p["norm"]["scale"]), theirs, margins[0]))
        route = jax.checkpoint(lambda p, x, theirs: experts(
            p["moe"], rms_norm(x, p["norm"]["scale"]), theirs, margins[1]))
        for i in range(n_layers):
            y, kl, said = attend(params[f"layer_{2 * i}"], x, select[i])
            x, index_loss = x + y, index_loss + kl
            selection.append(said)
            y, said = route(params[f"layer_{2 * i + 1}"], x,
                            chosen_experts[i])
            x = x + y
            routing.append(said)
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return ((lse - picked).mean() + index_loss, jnp.stack(selection),
                jnp.stack(routing))

    def loss(params, tokens, select, chosen_experts, select_margin,
             tie_margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            total, selection, routing = lax.map(
                lambda a: one_sequence(cast, *a, (select_margin, tie_margin)),
                (tokens, select, chosen_experts))
        B, T = tokens.shape[0], tokens.shape[1] - 1
        for what, said, n in (
                ("selection", selection, n_layers * B * s["selected_pairs"]),
                ("routing", routing, n_layers * B * T * K)):
            jax.debug.callback(
                functools.partial(_say_choices, what), n, said[..., 0].sum(),
                said[..., 1].sum(), said[..., 2].max())
        return total.mean().astype(jnp.float32)

    return loss

