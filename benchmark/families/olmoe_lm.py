"""Family ``olmoe_lm``: OLMoE's sparse-expert causal LM, keyed like Hugging
Face's ``OlmoeForCausalLM`` config (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` = one expert's width, ``rms_norm_eps``,
``rope_theta``, ``vocab_size``, ``max_position_embeddings``).

The system under test is the repo's ``TransformerLM`` with its RMSNorm,
rotary, QK-norm and ``DroplessMoE`` options, the flash kernels and the
fused cross-entropy head; everything else in this file is the benchmark's
own yardstick for it: the host-batch maker, the model FLOPs, the expert
layer's operations and bytes, and a plain float32 reference of the same
mathematics that reads the same parameter tree.

The training loss is the token cross-entropy plus the router's two
auxiliary terms, each summed over the layers:
``CE + load_balance_coef * E * sum_e f_e P_e + router_z_coef *
mean(logsumexp(router logits)^2)``.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The program has to hold the dropless expert layer.  A tree without it
# (this PR's parent) cannot run this family: say so now, before jax, the
# native core and the weights are started.
_MOE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "horovod_tpu", "parallel", "moe.py")
if os.path.isfile(_MOE):
    with open(_MOE) as _fh:
        if "class DroplessMoE" not in _fh.read():
            raise ImportError(
                "family olmoe_lm needs horovod_tpu.parallel.moe.DroplessMoE, "
                "which this tree's program does not have")

# The CPU rehearsal's sizes: 4 experts, top-2, one head of 128.  A few
# hundred tokens average bfloat16's rounding out far less than a real
# batch does, and a tiny router flips more choices, so the preset brings
# its own, looser tolerances.
TINY = {"hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 1,
        "num_key_value_heads": 1, "intermediate_size": 64, "num_experts": 4,
        "num_experts_per_tok": 2, "max_position_embeddings": 128,
        "vocab_size": 512,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 4e-1}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's: the router,
# one expert matrix, the attention projection and the head.
GRAD_LEAVES = (("block_0", "moe", "router", "kernel"),
               ("block_{last}", "moe", "w_gate"),
               ("block_0", "attn", "qkv", "kernel"),
               ("head", "kernel"))
GRAD_SAMPLES = 1          # one sequence on both sides


def grad_leaves(cfg):
    last = cfg["num_hidden_layers"] - 1
    return [tuple(p.format(last=last) for p in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import TransformerLM

    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("TransformerLM's attention is multi-head: "
                         "num_key_value_heads must equal num_attention_heads")
    if (cfg["hidden_act"], cfg["norm_topk_prob"], cfg["attention_bias"],
            cfg["clip_qkv"], cfg["tie_word_embeddings"]) != (
                "silu", False, False, None, False):
        raise ValueError("olmoe_lm runs OLMoE's block as published: silu "
                         "experts, gates not renormalised, no attention "
                         "bias, no qkv clipping, untied head")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return TransformerLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        max_len=cfg["max_position_embeddings"], attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm="rms", norm_eps=cfg["rms_norm_eps"], pos="rotary",
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        moe_experts=cfg["num_experts"], moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["intermediate_size"])


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, cfg["max_position_embeddings"]),
                       jnp.int32))["params"]
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.ops.losses import fused_softmax_xent
    from horovod_tpu.parallel.moe import router_losses

    model, dim = _model(cfg), cfg["hidden_size"]
    coef = cfg["training"]["loss"]

    def loss(params, aux, tokens):
        h, state = model.apply({"params": params}, tokens[:, :-1],
                               return_hidden=True, mutable=["intermediates"])
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"],
            tokens[:, 1:].reshape(-1))
        balance, z = router_losses(state["intermediates"])
        return (per_token.mean() + coef["load_balance_coef"] * balance
                + coef["router_z_coef"] * z), aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"olmoe_lm trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences of ``max_position_embeddings`` tokens plus the
    label of the last one, int32, as a tokenizer's packer hands them
    over."""
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["max_position_embeddings"] + 1),
                        dtype=np.int32)


def units_per_sample(cfg) -> int:
    """Tokens a sequence contributes to ``tokens_per_s_chip``."""
    return cfg["max_position_embeddings"]


def program_expert_choices(cfg, params, tokens):
    """The experts the PROGRAM's router chose, per layer, as (L, N, E)
    booleans, for :func:`reference_loss`'s printed share of assignments
    on which the two routers disagree."""
    import jax
    import jax.numpy as jnp

    _, state = _model(cfg).apply(
        {"params": jax.lax.stop_gradient(params)}, tokens[:, :-1],
        return_hidden=True, mutable=["intermediates"])
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        index, = state["intermediates"][f"block_{i}"]["moe"]["expert_index"]
        chosen.append(jax.nn.one_hot(index, cfg["num_experts"],
                                     dtype=jnp.bool_).any(axis=1))
    return jnp.stack(chosen)


# --------------------------------------------------- FLOPs, from shapes


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight.  Of the
    ``num_experts`` experts a token runs ``num_experts_per_tok``."""
    d, h, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    active = cfg["num_experts_per_tok"] * L
    return [("qkv", d, 3 * d, L), ("proj", d, d, L),
            ("router", d, cfg["num_experts"], L),
            ("w_gate", d, h, active), ("w_up", d, h, active),
            ("w_down", h, d, active),
            ("head", d, cfg["vocab_size"], 1)]


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward of
    every weight matmul it runs (2 + 4 FLOPs per weight; the experts it
    is routed to, not all of them) and of attention's two products over
    the causal half of the (T, T) square.  Recomputation is not counted;
    the embedding lookup, the sort and the combine are no matmuls."""
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = (cfg["num_hidden_layers"] * cfg["max_position_embeddings"]
            * cfg["hidden_size"])
    return 6.0 * n_matmul + 6.0 * attn


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one
    chip, forward and backward, from shapes.

    FLOPs: the router and the three grouped matmuls over the
    ``A = tokens * num_experts_per_tok`` assignment rows, 2 FLOPs a
    weight forward and 4 backward.  Bytes: each grouped matmul's
    compulsory traffic in bf16 — forward it reads its A input rows and
    every expert's weights and writes its A output rows; the backward's
    input-gradient product reads the A output-gradient rows and the
    weights again and writes A rows; its weight-gradient product reads
    both sets of A rows and writes the gradient in float32, as the
    optimizer takes it.  The sort, the gathers into and out of expert
    order, the activation and the combine are left out: what the layer
    takes for them counts against its roofline share."""
    d, h, E = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    tokens = batch_per_chip * cfg["max_position_embeddings"]
    A = tokens * k
    flops = L * 6.0 * (tokens * d * E + 3 * A * d * h)
    rows = A * (d + h) * 2              # one grouped matmul's rows, in + out
    weights = E * d * h                 # one projection's, every expert
    nbytes = L * 3 * (3 * rows + 2 * weights * 2 + weights * 4)
    return {"flops": flops, "bytes": nbytes, "assignments": A,
            "expert_parameters": L * 3 * weights}


# ------------------------------------------------------ plain reference


def _say_routing(share, assignments):
    print(json.dumps({"bench": "routing",
                      "assignments": int(assignments),
                      "disagreeing_share": float(share)}), flush=True)


def reference_loss(cfg):
    """``f(params, aux, tokens) -> loss`` in plain ``jax.numpy`` float32:
    OLMoE's decoder as published (pre-norm blocks of RMSNorm, q/k/v
    without bias, RMSNorm over the whole q and k vectors, rotate-half
    rotary positions, multi-head causal softmax attention scaled by
    1/sqrt(head), a top-k softmax router whose gates are not
    renormalised, SwiGLU experts, final RMSNorm, untied linear head) and
    its training loss (module docstring).  One sequence at a time through
    ``lax.map``; no kernels, no sort, no grouped matmul: a loop over the
    experts, each applied to ALL tokens and weighted by the top-k mask of
    the router's probabilities; the (T, T) scores held in full.  The
    load-balancing term is bilinear in two batch statistics, so the
    per-sequence counts and probability sums are added up first.

    Routing is discrete: where a token's k-th and (k+1)-th probabilities
    are closer than bfloat16's rounding of the router's input, program
    and reference choose differently.  Beside its result the function
    prints (one ``{"bench": "routing"}`` line per call, from a debug
    callback) the share of the program's assignments that are not the
    reference's; nothing of that enters the returned loss."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    coef = cfg["training"]["loss"]

    def rms_norm(x, scale):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale

    def rotary(x):                                  # (T, H, D)
        T, _, D = x.shape
        inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
        rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
        return x * cos + rotated * sin

    def experts(h, m):
        logits = h @ m["router"]["kernel"]                        # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        kth = jnp.sort(probs, axis=-1)[:, E - K]
        chosen = probs >= kth[:, None]
        gates = jnp.where(chosen, probs, 0.0)

        def one_expert(y, w):
            w_gate, w_up, w_down, gate = w
            return y + gate[:, None] * (
                (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down), None

        y, _ = lax.scan(one_expert, jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"], gates.T))
        z = jax.nn.logsumexp(logits, axis=-1) ** 2
        return y, chosen, probs.sum(0), z.mean()

    def one_sequence(params, seq):
        inp, labels = seq[:-1], seq[1:]
        T = inp.shape[0]
        x = params["tok_emb"]["embedding"][inp]
        C = x.shape[-1]
        D = C // H
        causal = jnp.tril(jnp.ones((T, T), bool))
        chosen, prob_sums, zs = [], [], []
        for i in range(L):
            p = params[f"block_{i}"]
            a = p["attn"]
            h = rms_norm(x, p["ln1"]["scale"])
            q, k, v = jnp.split(h @ a["qkv"]["kernel"], 3, axis=-1)
            q = rotary(rms_norm(q, a["q_norm"]["scale"]).reshape(T, H, D))
            k = rotary(rms_norm(k, a["k_norm"]["scale"]).reshape(T, H, D))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                           v.reshape(T, H, D))
            x = x + o.reshape(T, C) @ a["proj"]["kernel"]
            y, c, ps, z = experts(rms_norm(x, p["ln2"]["scale"]), p["moe"])
            x = x + y
            chosen.append(c)
            prob_sums.append(ps)
            zs.append(z)
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return ((lse - picked).mean(), jnp.stack(chosen),
                jnp.stack(prob_sums), jnp.stack(zs))

    def loss(params, aux, tokens):
        with jax.default_matmul_precision("highest"):
            params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            ce, chosen, prob_sums, z = lax.map(
                lambda s: one_sequence(params32, s), tokens)
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(L, n, E)
        f = chosen.sum(axis=1) / n                                # (L, E)
        balance = (E * (f * prob_sums.sum(axis=0) / n).sum(-1)).sum()
        # Not part of the reference: what the program's router chose.
        theirs = program_expert_choices(cfg, params, tokens)
        jax.debug.callback(
            _say_routing, (theirs & ~chosen).sum() / (L * n * K), L * n * K)
        return (ce.mean() + coef["load_balance_coef"] * balance
                + coef["router_z_coef"] * z.mean(axis=0).sum())

    return loss
