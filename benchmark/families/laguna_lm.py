"""Family ``laguna_lm``: Laguna's decoder — a stack whose attention layers
come in two kinds with their own head counts and rotary tables, global ones
under the causal mask and windowed ones under a causal window, each with a
sigmoid gate on its heads' output, over a dense SwiGLU layer and then
sigmoid-routed SwiGLU experts with a shared expert — keyed like the model's
own config.json (``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_attention_heads_per_layer``,
``num_key_value_heads``, ``head_dim``, ``layer_types``, ``mlp_layer_types``,
``sliding_window``, ``rope_parameters``, ``gating``, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``moe_routed_scaling_factor``,
``rms_norm_eps``, ``vocab_size``).

``num_hidden_layers`` layers are run, the FIRST entries of the three
published per-layer lists: layer ``l`` is the two sub-layers of the pattern
stack ``S`` (``full_attention``) or ``W`` (``sliding_attention``) and ``D``
(``dense``) or ``E`` (``sparse``).  The configuration is ONE CHIP'S SHARE of
an expert-parallel deployment: ``num_experts`` counts the experts held here
(the first ones), the router is ``experts_routed_over`` wide and chooses
``num_experts_per_tok`` of all of them, and ``vocab_size`` is this chip's
slice of the vocabulary.  What config.json leaves open — the gate is a
value a head, the routers' scores are sigmoids renormalised over the chosen —
is the configuration's ``assumed``.

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.LagunaLM``): the flash kernels under the causal mask
at 6 query heads a KV head and under the positional window mask at 8,
``DroplessMoE`` with held experts, the fused cross-entropy head.  Everything
else in this file is the benchmark's own yardstick: the host-batch maker,
the model FLOPs, the kernels' operations and bytes, and a plain float32
reference of the same mathematics that reads the same parameter tree and
shares no code with the program's mask, rotary or routing helpers.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: a whole period behind the dense layer at three
# layers (global + dense, windowed + sparse, global + sparse), 12 | 16 query
# heads over two KV heads of 128 (the lane-aligned kernels, interpreted, at
# both of the model's ratios, 6 and 8), a window of 16 in a sequence of 64,
# YaRN over 16 original positions, 4 of 8 experts held, top-3.
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
        "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 128,
        "num_attention_heads_per_layer": [12, 16, 12],
        "layer_types": ["full_attention", "sliding_attention",
                        "full_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse"],
        "sliding_window": 16,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 16},
        "num_experts": 4, "experts_routed_over": 8,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32,
        "sequence_length": 64, "vocab_size": 256,
        # 250 K parameters and 128 tokens a step: the rate at which one
        # second of steps shows a fall.
        "training": {"optimizer": {"name": "adamw", "learning_rate": 3e-3,
                                   "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                                   "weight_decay": 0.1},
                     "embedding_rms": 1.0,
                     "param_dtype": "float32", "compute_dtype": "bfloat16"},
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's: all four
# matrices of the first global and of the first windowed attention layer
# (the gate's among them), the dense layer's down-projection, the first
# sparse layer's router, one held expert matrix and a shared one, the
# embedding and the head.
ATTN_LEAVES = (("attn", "q", "kernel"), ("attn", "kv", "kernel"),
               ("attn", "gate", "kernel"), ("attn", "proj", "kernel"))
EXPERT_LEAVES = (("moe", "router", "kernel"), ("moe", "w_up"),
                 ("moe", "shared", "w_down"))
GRAD_SAMPLES = 1          # one sequence on both sides


def layer_letters(cfg):
    """``[(attention letter, FFN letter)]`` of the layers that are run: the
    first ``num_hidden_layers`` entries of the published lists."""
    kinds = {"full_attention": "S", "sliding_attention": "W"}
    ffns = {"dense": "D", "sparse": "E"}
    L = cfg["num_hidden_layers"]
    return [(kinds[a], ffns[f]) for a, f in zip(cfg["layer_types"][:L],
                                                cfg["mlp_layer_types"][:L])]


def pattern(cfg) -> str:
    return "".join(a + f for a, f in layer_letters(cfg))


def heads(cfg):
    """``{"S": query heads of a global layer, "W": of a windowed one}``,
    from ``num_attention_heads_per_layer``; each kind has ONE count."""
    L = cfg["num_hidden_layers"]
    out = {}
    for (kind, _), n in zip(layer_letters(cfg),
                            cfg["num_attention_heads_per_layer"][:L]):
        if out.setdefault(kind, n) != n:
            raise ValueError(f"laguna_lm: {kind!r} layers of {out[kind]} and "
                             f"of {n} query heads")
    return out


def grad_leaves(cfg):
    letters = pattern(cfg)
    out = [(f"layer_{letters.index(kind)}", *path)
           for kind in "SW" if kind in letters for path in ATTN_LEAVES]
    out.append((f"layer_{letters.index('D')}", "mlp", "down", "kernel"))
    out += [(f"layer_{letters.index('E')}", *path) for path in EXPERT_LEAVES]
    return out + [("tok_emb", "embedding"), ("head", "kernel")]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import LagunaLM

    as_published = {"model_type": "laguna", "attention_bias": False,
                    "tie_word_embeddings": False, "gating": True,
                    "moe_apply_router_weight_on_input": False,
                    "partial_rotary_factor": 0.5}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    ropes = cfg["rope_parameters"]
    full, near = ropes["full_attention"], ropes["sliding_attention"]
    H = heads(cfg)
    if (differs or full["rope_type"] != "yarn" or near["rope_type"] != "default"
            or H["S"] != cfg["num_attention_heads"]):
        raise ValueError(f"laguna_lm runs the stack as published; got "
                         f"{differs}, {ropes}, heads {H}")
    D = cfg["head_dim"]
    compute = jnp.dtype(cfg["training"]["compute_dtype"])

    def width(rope):
        rotated = int(round(D * rope["partial_rotary_factor"]))
        return None if rotated == D else rotated

    return LagunaLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["rms_norm_eps"],
        num_heads=H["S"], kv_heads=cfg["num_key_value_heads"], head_dim=D,
        rope_theta=float(full["rope_theta"]), rope_width=width(full),
        rope_scaling=dict(
            factor=float(full["factor"]),
            original_max_len=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        attn_gate=cfg["gating"],
        window=dict(window=cfg["sliding_window"],
                    num_heads=H.get("W", H["S"]),
                    rope_theta=float(near["rope_theta"]),
                    rope_width=width(near), rope_scaling=None),
        mlp_hidden=cfg["intermediate_size"],
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="sigmoid", renormalize=True, activation="swiglu",
                 gate_scale=float(cfg["moe_routed_scaling_factor"]),
                 shared_hidden=cfg["shared_expert_intermediate_size"],
                 held=(0, cfg["num_experts"])))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``.  No parameter's
    shape depends on the sequence length, so a short one is traced.  The
    embedding table is drawn at ``training.embedding_rms`` root-mean-square
    a row (the module's default is ``1 / sqrt(hidden_size)``; the
    configuration's ``assumed`` says why, ``sdar-30b-a3b-chat.json``'s
    reading is the precedent)."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256)),
                       jnp.int32))["params"]
    table = params["tok_emb"]["embedding"]
    params["tok_emb"]["embedding"] = table * (
        cfg["training"]["embedding_rms"] * math.sqrt(cfg["hidden_size"]))
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]

    def loss(params, aux, tokens):                 # tokens (B, T + 1)
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
        raise ValueError(f"laguna_lm trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def host_batch(cfg, rng: np.random.Generator, n: int):
    """``n`` sequences of ``sequence_length`` tokens plus the label of the
    last one, int32, ids uniform over this chip's slice of the vocabulary."""
    return rng.integers(0, cfg["vocab_size"],
                        (n, cfg["sequence_length"] + 1), dtype=np.int32)


def units_per_sample(cfg) -> int:
    """Tokens a sequence contributes to ``tokens_per_s_chip``."""
    return cfg["sequence_length"]


def _sparse_layers(cfg):
    """Pattern indices of the ``E`` sub-layers."""
    return [i for i, letter in enumerate(pattern(cfg)) if letter == "E"]


def program_choices(cfg, params, tokens):
    """The experts the PROGRAM's routers chose for ``tokens`` (B, T + 1) IN
    ITS GRADIENT STEP, read from what its layers sow: (B, sparse layers, T,
    num_experts_per_tok).  :func:`reference_loss` breaks its near-ties with
    them.

    They are taken from the program as ``benchmark/run.py`` differentiates
    it — :func:`loss_fn` under ``jax.grad``, the :func:`grad_leaves` kept —
    and not from a forward pass alone: XLA keeps excess precision where it
    fuses, a forward-alone compilation rounds the routers' inputs otherwise
    than the gradient step's forward does, and on a v5e the two then choose
    differently in 3 to 10% of a sparse layer's rows (PR 58; each such row's
    experts, gates and all that follows differ by their own size, and every
    named leaf read 0.03 to 0.15 from the reference for it).  No two
    compilations round alike to the last row: what is left of it is in the
    configuration's ``grad_rel_why``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.losses import fused_softmax_xent

    model, dim = _model(cfg), cfg["hidden_size"]
    B, T = tokens.shape[0], tokens.shape[1] - 1

    def loss(p):
        h, state = model.apply({"params": p}, tokens[:, :-1],
                               return_hidden=True, mutable=["intermediates"])
        per_token = fused_softmax_xent(
            h.reshape(-1, dim), p["head"]["kernel"], tokens[:, 1:].reshape(-1))
        sown = state["intermediates"]
        return per_token.mean(), jnp.stack([
            sown[f"layer_{i}"]["moe"]["expert_index"][0].reshape(B, T, -1)
            for i in _sparse_layers(cfg)], axis=1)

    g, chosen = jax.grad(loss, has_aux=True)(jax.lax.stop_gradient(params))
    # The backward pass stays in the program, as far as the named leaves
    # need it — what the forward keeps for it decides how the forward fuses
    # —, by an addend that is 0 and that the compiler may not drop (a
    # float's product with 0 is no constant).
    kept = sum(functools.reduce(lambda tree, key: tree[key], path, g)
               .astype(jnp.float32).sum() for path in grad_leaves(cfg))
    return chosen + (0.0 * kept).astype(chosen.dtype)


# --------------------------------------------------- FLOPs, from shapes


def live_pairs(T: int, window=None) -> int:
    """(query, key) pairs a sequence of ``T`` leaves standing: under the
    causal mask row ``i`` reads ``i + 1`` keys, under a window ``min(i + 1,
    window)`` — itself and the ``window - 1`` before it."""
    return sum(min(i + 1, window or T) for i in range(T))


def _sizes(cfg):
    letters = layer_letters(cfg)
    return {"d": cfg["hidden_size"], "Hkv": cfg["num_key_value_heads"],
            "D": cfg["head_dim"], "T": cfg["sequence_length"],
            "W": cfg["sliding_window"], "H": heads(cfg),
            "attn": [a for a, _ in letters],
            "dense": sum(f == "D" for _, f in letters),
            "sparse": sum(f == "E" for _, f in letters)}


def held_share(cfg) -> float:
    """Assignments a token sends to the experts held here under uniform
    routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["experts_routed_over"])


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as ``(name, k, n,
    count)``: a (1, k) row times a (k, n) weight, ``count`` of them a token.
    The projections at each attention layer's own head count (the gate's
    width is the heads'); the routed experts at the held share of the
    ``num_experts_per_tok`` a token is routed to."""
    s = _sizes(cfg)
    d, D = s["d"], s["D"]
    out = []
    for kind in "SW":
        n, H = s["attn"].count(kind), s["H"].get(kind, 0)
        out += [(f"attn_q.{kind}", d, H * D, n),
                (f"attn_kv.{kind}", d, 2 * s["Hkv"] * D, n),
                (f"attn_gate.{kind}", d, H, n),
                (f"attn_proj.{kind}", H * D, d, n)]
    eh, sh = (cfg["moe_intermediate_size"],
              cfg["shared_expert_intermediate_size"])
    return out + [
        ("dense", d, 3 * cfg["intermediate_size"], s["dense"]),
        ("router", d, cfg["experts_routed_over"], s["sparse"]),
        ("shared", d, 3 * sh, s["sparse"]),
        ("experts", d, 3 * eh, held_share(cfg) * s["sparse"]),
        ("head", d, cfg["vocab_size"], 1)]


def attention_pairs(cfg) -> dict:
    """``{"S": pairs, "W": pairs}`` one sequence leaves standing in ONE
    layer of the kind."""
    s = _sizes(cfg)
    return {"S": live_pairs(s["T"]), "W": live_pairs(s["T"], s["W"])}


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward (2 + 4
    FLOPs per weight) of every weight matmul (:func:`matmuls`) and of
    attention's two products over the pairs each layer's mask leaves (``4 H
    D`` a pair forward, at the layer's own ``H``) — the window's LIVE pairs,
    never the tiles a kernel visits."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    pairs = attention_pairs(cfg)
    attend = sum(4.0 * s["H"][kind] * s["D"] * pairs[kind] / s["T"]
                 for kind in s["attn"])
    return 6.0 * n_matmul + 3.0 * attend


def _flash_cost(cfg, batch_per_chip: int, kinds: str) -> dict:
    s = _sizes(cfg)
    B, T, Hkv, D = batch_per_chip, s["T"], s["Hkv"], s["D"]
    pairs = attention_pairs(cfg)
    flops = nbytes = live = calls = 0
    for kind in s["attn"]:
        if kind not in kinds:
            continue
        H = s["H"][kind]
        q, kv = B * T * H * D * 2, B * T * Hkv * D * 2     # one bf16 tensor
        stat = B * H * T * 4
        flops += (4.0 + 10.0) * D * H * B * pairs[kind]
        nbytes += (2 * q + 2 * kv + stat) + (4 * q + 4 * kv + 2 * stat)
        live += B * pairs[kind]
        calls += 1
    return {"flops": flops, "bytes": nbytes, "calls_per_step": calls,
            "live_pairs": live, "all_pairs": calls * B * T * T,
            "shape": [B, T, [s["H"][k] for k in kinds if k in s["H"]], Hkv,
                      D]}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the attention kernels of one step need on one
    chip, whatever implements them, from each layer's LIVE pairs — BOTH
    kinds' (``gqa_flash_ms`` reads every attention kernel of the cell):
    ``4 D H`` a pair forward (two products) and ``10 D H`` backward (five:
    the score recompute belongs to the algorithm, once) at the layer's own
    ``H``.  Bytes: the forward reads q, k, v and writes o; the backward
    reads q, k, v, o, do and writes dq, dk, dv — in bf16, k, v, dk, dv at
    their ``H_kv`` heads — plus the float32 row statistics."""
    return _flash_cost(cfg, batch_per_chip, "SW")


def window_flash_cost(cfg, batch_per_chip: int) -> dict:
    """:func:`flash_cost` of the WINDOWED layers alone: what
    ``win_flash_roofline`` prices ``win_flash_ms`` with."""
    return _flash_cost(cfg, batch_per_chip, "W")


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the sparse layers of one step need on one chip,
    forward and backward, from shapes, as ``joyai_flash_lm.moe_cost`` counts
    them: the router over all ``experts_routed_over`` (``router_flops``:
    what ``route_ms`` is read for), the held experts' three grouped matmuls
    at the load uniform routing sends here (``A = tokens ·
    num_experts_per_tok · held / routed over`` rows) and the shared expert's
    three matmuls over every token.  The top-k, the sort, the gathers, the
    combine, the activation and the rows that round the load up to whole
    windows are left out: what the layer takes for them counts against its
    roofline share."""
    d, eh = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sh = cfg["shared_expert_intermediate_size"]
    E, held = cfg["experts_routed_over"], cfg["num_experts"]
    L = _sizes(cfg)["sparse"]
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


def _say_choices(what, total, differing, beyond, largest_gap):
    print(json.dumps({"bench": what, "chosen": int(total),
                      "disagreeing_share": float(differing / total),
                      "beyond_margin_share": float(beyond / total),
                      "largest_gap": float(largest_gap)}), flush=True)


def reference_loss(cfg, dtype: str = "float32"):
    """``f(params, aux, tokens) -> loss``: :func:`reference_given_choices`
    with the program's expert choices for the same weights and batch and
    the configuration's margin."""
    given = reference_given_choices(cfg, dtype)
    margin = cfg["tolerances"]["tie_margin"]

    def loss(params, aux, tokens):
        return given(params, tokens, program_choices(cfg, params, tokens),
                     margin)

    return loss


def reference_tables(cfg):
    """The two rotary tables, from the published ``rope_parameters`` and the
    equations alone: ``{"S": (theta (R/2,) float32, factor on cos and sin),
    "W": ...}``.  Windowed layers: ``theta_m = base^(-m / (R/2))`` over the
    whole head.  Global layers: the first ``R = partial_rotary_factor · D``
    channels under YaRN — ``f_m`` the plain table, ``c(r) = R ln(original /
    (2 pi r)) / (2 ln base)``, ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))`` clamped to ``[0, R - 1]``, a linear ramp between,
    ``theta_m = f_m (1 - ramp) + f_m / factor · ramp`` — with cos and sin
    times ``attention_factor``."""
    D = cfg["head_dim"]
    out = {}
    for kind, name in (("S", "full_attention"), ("W", "sliding_attention")):
        rope = cfg["rope_parameters"][name]
        R = int(round(D * rope["partial_rotary_factor"]))
        base = float(rope["rope_theta"])
        m = np.arange(R // 2, dtype=np.float64)
        theta = base ** (-m / (R // 2))
        factor = 1.0
        if rope["rope_type"] == "yarn":
            original = rope["original_max_position_embeddings"]

            def c(turns):
                return R * math.log(original / (2 * math.pi * turns)) / (
                    2 * math.log(base))

            low = max(math.floor(c(rope["beta_fast"])), 0)
            high = min(math.ceil(c(rope["beta_slow"])), R - 1)
            ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
            theta = theta * (1 - ramp) + theta / rope["factor"] * ramp
            factor = float(rope["attention_factor"])
        out[kind] = (theta.astype(np.float32), factor)
    return out


def reference_given_choices(cfg, dtype: str = "float32"):
    """``f(params, tokens, experts, tie_margin) -> loss`` in plain
    ``jax.numpy`` float32 at the highest matmul precision: the stack as
    config.json and the configuration's ``assumed`` describe it.

    A layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.
    *Attention* of layer ``l``: ``q = u W_q`` (``H_l`` heads of D), ``k, v =
    u W_kv`` (H_kv heads), rotary positions in the rotate-half form from
    the layer kind's table (:func:`reference_tables`: the first R channels
    of a head, cos and sin times the kind's factor), scores ``q k^T /
    sqrt(D)``, query head ``h`` reading KV head ``h // (H_l / H_kv)``; query
    ``i`` reads key ``j`` iff ``j <= i`` (global) or ``0 <= i - j <
    sliding_window`` (windowed) — the mask written from that sentence, a
    block of query rows at a time (recomputed in the backward pass), with no
    tile, no online softmax and none of the program's mask code; a dense
    softmax; the heads' output times ``sigmoid(u W_g)``, a value a head;
    ``W_o``.  *FFN*: the dense
    SwiGLU, or ``s = sigmoid(u W_r)`` over all ``experts_routed_over``, the
    ``num_experts_per_tok`` largest, ``w = moe_routed_scaling_factor · s /
    sum of the chosen s``, a loop over the ``num_experts`` HELD experts, each
    applied to ALL rows and weighted by the top-k mask, plus the shared
    SwiGLU expert; what the experts held elsewhere would add is left out, as
    in the program.  The loss is the mean next-token cross-entropy over the
    vocabulary slice.

    **Near-ties of the routers are broken as the program broke them**
    (``experts`` (B, sparse layers, T, k)), inside ``tie_margin``, as in
    ``nemotron_h_lm``; beside its result the function prints a
    ``{"bench": "routing"}`` line.

    ``dtype="bfloat16"`` is the precision control of the comparison and no
    reference: the same plain mathematics with every float32 part in
    bfloat16 at the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    s = _sizes(cfg)
    Hkv, D, T, W = s["Hkv"], s["D"], s["T"], s["W"]
    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["num_experts"])
    eps = cfg["rms_norm_eps"]
    scale = float(cfg["moe_routed_scaling_factor"])
    letters = pattern(cfg)
    sparse = _sparse_layers(cfg)
    dtype = jnp.dtype(dtype)
    tables = reference_tables(cfg)
    position = jnp.arange(T)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    def swiglu(x, w_gate, w_up, w_down):
        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

    def rotary(x, kind):                              # (T, heads, D)
        theta, factor = tables[kind]
        half = theta.size
        angle = (position.astype(jnp.float32)[:, None] * theta)[:, None]
        cos = (jnp.cos(angle) * factor).astype(x.dtype)
        sin = (jnp.sin(angle) * factor).astype(x.dtype)
        a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                               -1)

    def attention(a, u, kind):
        H = s["H"][kind]
        q = rotary((u @ a["q"]["kernel"]).reshape(T, H, D), kind)
        k, v = jnp.split(u @ a["kv"]["kernel"], 2, axis=-1)
        k, v = rotary(k.reshape(T, Hkv, D), kind), v.reshape(T, Hkv, D)
        # Query head h reads KV head h // (H / H_kv).
        kv_of = jnp.arange(H) // (H // Hkv)
        k, v = k[:, kv_of], v[:, kv_of]                      # (T, H, D)

        @jax.checkpoint
        def queries(args):
            q_b, i = args
            apart = i[:, None] - position[None, :]        # query - key
            visible = apart >= 0
            if kind == "W":
                visible = visible & (apart < W)
            logits = jnp.einsum("thd,shd->hts", q_b, k)
            logits = jnp.where(visible, logits / math.sqrt(D), -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("hts,shd->thd", probs, v)

        step = min(T, 128)
        out = lax.map(queries, (q.reshape(T // step, step, H, D),
                                position.reshape(T // step, step)))
        out = out.reshape(T, H, D)
        gate = jax.nn.sigmoid(u @ a["gate"]["kernel"])
        out = out * gate[:, :, None]
        return out.reshape(T, H * D) @ a["proj"]["kernel"]

    def experts(m, h, theirs, margin):
        sc = jax.nn.sigmoid(h @ m["router"]["kernel"])             # (T, E)
        own = sc >= jnp.sort(sc, axis=-1)[:, E - K, None]
        theirs = jax.nn.one_hot(theirs, E, dtype=jnp.bool_).any(axis=1)
        gap = (jnp.where(theirs, -jnp.inf, sc).max(-1)
               - jnp.where(theirs, sc, jnp.inf).min(-1)).astype(jnp.float32)
        tie = (theirs.sum(-1) == K) & (gap <= margin)
        chosen = jnp.where(tie[:, None], theirs, own)
        gates = jnp.where(chosen, sc, 0.0)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + 1e-20)

        @jax.checkpoint
        def one_expert(w_gate, w_up, w_down, gate):
            return gate[:, None] * swiglu(h, w_gate, w_up, w_down)

        y, _ = lax.scan(lambda y, w: (y + one_expert(*w), None),
                        jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"],
                         gates[:, :held].T))
        shared = m["shared"]
        y = y + swiglu(h, shared["w_gate"], shared["w_up"], shared["w_down"])
        differing = theirs & ~own
        return y, jnp.stack([
            differing.sum().astype(jnp.float32),
            (differing & ~tie[:, None]).sum().astype(jnp.float32),
            jnp.where(differing.any(-1), gap, 0.0).max()])

    def one_sequence(params, seq, chosen_experts, margin):
        x = params["tok_emb"]["embedding"][seq[:-1]]
        routing = []
        for i, letter in enumerate(letters):
            p = params[f"layer_{i}"]
            # A sub-layer's intermediates are made again in the backward
            # pass: what is kept between them is the residual stream.
            if letter in "SW":
                y = jax.checkpoint(lambda p, x, kind=letter: attention(
                    p["attn"], rms_norm(x, p["norm"]["scale"]), kind))(p, x)
            elif letter == "D":
                y = jax.checkpoint(lambda p, x: swiglu(
                    rms_norm(x, p["norm"]["scale"]),
                    *(p["mlp"][n]["kernel"] for n in ("gate", "up", "down"))
                ))(p, x)
            else:
                y, said = jax.checkpoint(lambda p, x, theirs: experts(
                    p["moe"], rms_norm(x, p["norm"]["scale"]), theirs,
                    margin))(p, x, chosen_experts[sparse.index(i)])
                routing.append(said)
            x = x + y
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return (lse - picked).mean(), jnp.stack(routing)

    def loss(params, tokens, chosen_experts, tie_margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            total, routing = lax.map(
                lambda a: one_sequence(cast, *a, tie_margin),
                (tokens, chosen_experts))
        jax.debug.callback(
            functools.partial(_say_choices, "routing"),
            len(sparse) * tokens.shape[0] * T * K, routing[..., 0].sum(),
            routing[..., 1].sum(), routing[..., 2].max())
        return total.mean().astype(jnp.float32)

    return loss
