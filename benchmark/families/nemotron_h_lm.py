"""Family ``nemotron_h_lm``: the Nemotron-H hybrid stack (Mamba-2 mixers,
grouped-query attention, sigmoid-routed relu² experts with a shared
one), keyed like Hugging Face's ``nemotron_h`` config
(``hybrid_override_pattern``, ``hidden_size``, ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``chunk_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``n_routed_experts``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``,
``routed_scaling_factor``, ``norm_topk_prob``, ``vocab_size``).

The first ``num_hidden_layers`` letters of the pattern are run.  The
configuration is ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` counts the experts held here (the first ones), the
router is ``experts_routed_over`` wide and chooses ``num_experts_per_tok``
of all of them, and ``vocab_size`` is this chip's slice of the vocabulary.
``sequence_length`` is the training sequence (``max_position_embeddings``
stays the model's declared 262,144).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
(``models.transformer.NemotronHLM``): ``ops/ssd.py``'s chunked scan, the
flash kernels with grouped KV heads, ``DroplessMoE`` with held experts,
the fused cross-entropy head.  Everything else in this file is the
benchmark's own yardstick: the host-batch maker, the model FLOPs, the
scan's and the expert layers' operations and bytes, and a plain float32
reference of the same mathematics that reads the same parameter tree.
The loss is the mean next-token cross-entropy and nothing else.
"""

from __future__ import annotations

import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: all three kinds of layer in the published
# order, 4 of 8 experts held, top-3, attention of two query heads over one
# KV head of 128 (the lane-aligned kernels, interpreted).  A few hundred
# tokens average bfloat16's rounding out far less than a real batch does,
# so the preset brings its own, looser tolerances.
TINY = {"hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16,
        "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
        "n_routed_experts": 4, "experts_routed_over": 8,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 64, "sequence_length": 64,
        "vocab_size": 256,
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's, with {m}, {a},
# {e} the first mixer, attention and expert layer of the pattern.
GRAD_LEAVES = (("layer_{m}", "ssm", "in_proj", "kernel"),
               ("layer_{m}", "ssm", "A_log"),
               ("layer_{m}", "ssm", "dt_bias"),
               ("layer_{m}", "ssm", "conv", "kernel"),
               ("layer_{a}", "attn", "kv", "kernel"),
               ("layer_{e}", "moe", "router", "kernel"),
               ("layer_{e}", "moe", "w_up"),
               ("layer_{e}", "moe", "shared", "w_up"),
               ("head", "kernel"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"m": p.index("M"), "a": p.index("*"), "e": p.index("E")}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import NemotronHLM

    as_published = {
        "model_type": "nemotron_h", "mamba_hidden_act": "silu",
        "mlp_hidden_act": "relu2", "attention_bias": False,
        "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
        "use_conv_bias": True, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "n_shared_experts": 1,
        "tie_word_embeddings": False, "time_step_limit": [0, None]}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if differs:
        raise ValueError(f"nemotron_h_lm runs the stack as published; "
                         f"got {differs}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return NemotronHLM(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), attn="flash",
        dtype=compute, head_dtype=compute, ln_dtype=compute,
        norm_eps=cfg["layer_norm_epsilon"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm=dict(num_heads=cfg["mamba_num_heads"],
                 head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
                 state_size=cfg["ssm_state_size"],
                 conv_kernel=cfg["conv_kernel"], chunk=cfg["chunk_size"],
                 dt_min=cfg["time_step_min"], dt_max=cfg["time_step_max"],
                 dt_floor=cfg["time_step_floor"]),
        moe_experts=cfg["experts_routed_over"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_hidden=cfg["moe_intermediate_size"],
        moe=dict(router="sigmoid", renormalize=True,
                 gate_scale=float(cfg["routed_scaling_factor"]),
                 activation="relu2",
                 shared_hidden=cfg["moe_shared_expert_intermediate_size"],
                 held=(0, cfg["n_routed_experts"])))


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
        raise ValueError(f"nemotron_h_lm trains with adamw, not {o['name']!r}")
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


def program_expert_choices(cfg, params, tokens):
    """The experts the PROGRAM's routers chose for ``tokens`` (B, T + 1):
    (B, expert layers, T, num_experts_per_tok) indices, read from what its
    expert layers sow.  :func:`reference_loss` breaks its near-ties with
    them."""
    import jax
    import jax.numpy as jnp

    _, state = _model(cfg).apply(
        {"params": jax.lax.stop_gradient(params)}, tokens[:, :-1],
        return_hidden=True, mutable=["intermediates"])
    B, T = tokens.shape[0], tokens.shape[1] - 1
    chosen = [state["intermediates"][f"layer_{i}"]["moe"]["expert_index"][0]
              .reshape(B, T, -1)
              for i, kind in enumerate(pattern(cfg)) if kind == "E"]
    return jnp.stack(chosen, axis=1)


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "H": H, "P": P, "G": G, "N": N,
            "inner": H * P, "conv_dim": H * P + 2 * G * N,
            "Q": cfg["chunk_size"], "T": cfg["sequence_length"],
            "layers": {k: pattern(cfg).count(k) for k in "M*E"}}


def matmuls(cfg):
    """Every weight matmul of one forward pass, per token, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a token (a fraction for the routed experts: of the
    ``num_experts_per_tok`` a token is routed to, the held share)."""
    s = _sizes(cfg)
    d, L = s["d"], s["layers"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kvw = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["experts_routed_over"])
    eh, sh = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    return [("ssm_in_proj", d, 2 * s["inner"] + 2 * s["G"] * s["N"] + s["H"],
             L["M"]),
            ("ssm_out_proj", s["inner"], d, L["M"]),
            ("attn_q", d, qw, L["*"]), ("attn_kv", d, kvw, L["*"]),
            ("attn_proj", qw, d, L["*"]),
            ("router", d, cfg["experts_routed_over"], L["E"]),
            ("w_up", d, eh, held * L["E"]), ("w_down", eh, d, held * L["E"]),
            ("shared_w_up", d, sh, L["E"]), ("shared_w_down", sh, d, L["E"]),
            ("head", d, cfg["vocab_size"], 1)]


def scan_flops_per_token(cfg) -> float:
    """Forward FLOPs a token of ONE mixer's chunked scan: within a chunk
    ``C B^T`` a group (2 Q N) and ``(L o C B^T) x`` a head (2 Q P), both over
    the causal half; the chunk's state ``x (x) B`` and the entering
    state's read-out through ``C``, 2 P N a head each; and the
    convolution's ``conv_kernel`` multiply-adds a channel."""
    s = _sizes(cfg)
    intra = (s["G"] * 2 * s["Q"] * s["N"] + s["H"] * 2 * s["Q"] * s["P"]) / 2
    return (intra + 2 * s["H"] * 2 * s["P"] * s["N"]
            + 2 * cfg["conv_kernel"] * s["conv_dim"])


def flops_per_unit(cfg) -> float:
    """Model FLOPs one trained token requires: forward plus backward (2 +
    4 FLOPs per weight) of every weight matmul it runs — the routed
    experts at the held share ``num_experts_per_tok * n_routed_experts /
    experts_routed_over`` of a token —, of attention's two products over
    the causal half of the (T, T) square, and of the mixers' chunked
    scans (:func:`scan_flops_per_token`).  Recomputation is not counted;
    the embedding lookup, the sort and the combine are no matmuls."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = (s["layers"]["*"] * s["T"] * cfg["num_attention_heads"]
            * cfg["head_dim"])
    return (6.0 * n_matmul + 6.0 * attn
            + 3.0 * s["layers"]["M"] * scan_flops_per_token(cfg))


def ssd_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the mixers' scans of one step need on one chip
    (the chunked form, forward and backward, without the convolution),
    from shapes.

    FLOPs: :func:`scan_flops_per_token`'s four products, twice again for
    the backward.  Bytes: the compulsory traffic of a scan that keeps its
    chunk states on the chip — forward it reads ``x`` (H P), ``B`` and ``C``
    (G N each) in bf16 and ``dt`` (H) in f32 and writes ``y`` (H P); the
    backward reads those and ``dy`` and writes the four gradients.  What
    the XLA form moves beyond that (chunk-square tiles, the float32 states
    of every chunk written and read back) counts against its share."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    layers = s["layers"]["M"]
    conv = 2 * cfg["conv_kernel"] * s["conv_dim"]
    flops = 3.0 * layers * tokens * (scan_flops_per_token(cfg) - conv)
    inputs = (s["inner"] + 2 * s["G"] * s["N"]) * 2 + s["H"] * 4
    y = s["inner"] * 2
    nbytes = layers * tokens * ((inputs + y) + (inputs + y + inputs))
    chunks = batch_per_chip * -(-s["T"] // s["Q"])
    return {"flops": flops, "bytes": nbytes, "chunks": layers * chunks,
            "state_bytes": (layers * chunks * s["H"] * s["P"] * s["N"] * 4)}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the flash kernels of one step need on one
    chip, from their shapes — queries ``(B, T, H, D)``, keys and values
    ``(B, T, H_kv, D)``, causal —, as ``gpt2_lm.flash_cost`` counts them.

    FLOPs: the forward's two products and the backward's five (the score
    recompute belongs to the algorithm, once), each ``2 B H T T D`` over
    the causal half: every query head has its own scores, so grouping
    the keys saves none.  Bytes: each kernel's compulsory traffic in
    bf16 — the forward reads q, k, v and writes o; the dq kernel reads
    q, k, v, do and writes dq; the dk/dv kernel reads the same four and
    writes dk, dv — with k, v, dk, dv at their ``H_kv`` heads (they are
    not repeated in HBM), plus the float32 row statistics."""
    B, T = batch_per_chip, cfg["sequence_length"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = pattern(cfg).count("*")
    product = 2.0 * B * H * T * T * D / 2
    q, kv = B * T * H * D * 2, B * T * Hkv * D * 2     # one bf16 tensor
    stat = B * H * T * 4
    nbytes = layers * ((2 * q + 2 * kv + stat)              # forward
                       + (3 * q + 2 * kv + 2 * stat)        # dq
                       + (2 * q + 4 * kv + 2 * stat))       # dk/dv
    return {"flops": layers * (2 + 5) * product, "bytes": nbytes,
            "shape": [B, T, H, Hkv, D], "calls_per_step": layers}


def moe_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the expert layers of one step need on one
    chip, forward and backward, from shapes: the router over all
    ``experts_routed_over``, the held experts' two grouped matmuls at the
    load uniform routing sends here (``A = tokens * num_experts_per_tok *
    held / routed over`` rows), and the shared expert's two matmuls over
    every token.

    FLOPs: 2 a weight forward and 4 backward.  Bytes, per matmul, in
    bf16 as in ``olmoe_lm.moe_cost``: forward its rows in and out and the
    weights; the input-gradient product the same again; the
    weight-gradient product both sets of rows and the gradient in float32.
    The sort, the gathers, the scatter of the combine and the activation
    are left out: what the layer takes for them counts against its
    roofline share."""
    d = cfg["hidden_size"]
    eh, sh = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    E, held, k = (cfg["experts_routed_over"], cfg["n_routed_experts"],
                  cfg["num_experts_per_tok"])
    L = pattern(cfg).count("E")
    tokens = batch_per_chip * cfg["sequence_length"]
    A = tokens * k * held / E
    flops = L * 6.0 * (tokens * d * E + 2 * A * d * eh + 2 * tokens * d * sh)

    def matmul_bytes(rows, k_, n_, weights):
        moved = rows * (k_ + n_) * 2
        return 3 * moved + 2 * weights * 2 + weights * 4

    nbytes = L * (2 * matmul_bytes(A, d, eh, held * d * eh)
                  + 2 * matmul_bytes(tokens, d, sh, d * sh))
    return {"flops": flops, "bytes": nbytes, "assignments": tokens * k,
            "held_assignments": A,
            "expert_parameters": L * (2 * held * d * eh + 2 * d * sh)}


# ------------------------------------------------------ plain reference


def _say_routing(assignments, differing, beyond, largest_gap):
    print(json.dumps({"bench": "routing", "assignments": int(assignments),
                      "disagreeing_share": float(differing / assignments),
                      "beyond_margin_share": float(beyond / assignments),
                      "largest_gap": float(largest_gap)}), flush=True)


def reference_mixer(cfg, form: str = "dual"):
    """``f(p, u) -> y`` for ONE sequence ``u`` (T, d) and a mixer's
    parameters ``p``: Mamba-2's mixer in plain float32.  ``form="dual"``:
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
    K, eps = cfg["conv_kernel"], cfg["layer_norm_epsilon"]

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

        return lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
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
    """``f(params, aux, tokens) -> loss``: :func:`reference_given_choices`
    with the program's expert choices for the same weights and tokens and
    the configuration's ``tolerances.tie_margin``."""
    given = reference_given_choices(cfg, mixer_form, dtype)
    margin = cfg["tolerances"]["tie_margin"]

    def loss(params, aux, tokens):
        return given(params, tokens,
                     program_expert_choices(cfg, params, tokens), margin)

    return loss


def reference_given_choices(cfg, mixer_form: str = "dual",
                            dtype: str = "float32"):
    """``f(params, tokens, theirs, margin) -> loss`` in plain ``jax.numpy``
    float32: the stack as config.json describes it (module docstring of
    ``horovod_tpu.models.ssm`` for the mixer; pre-norm residuals of one
    sub-layer a layer, RMSNorm; attention of ``num_attention_heads`` query
    heads over ``num_key_value_heads`` KV heads, causal softmax scaled by
    1/sqrt(head_dim), no positions, no bias; experts by sigmoid scores,
    the ``num_experts_per_tok`` largest of all ``experts_routed_over``
    chosen, gates the scores renormalised over the chosen and scaled by
    ``routed_scaling_factor``, relu² experts, one shared expert; final
    RMSNorm, untied head, mean token cross-entropy).  One sequence at a
    time through ``lax.map``; no kernels, no chunks, no sort, no grouped
    matmul: the mixer by :func:`reference_mixer`; attention one query head
    at a time with its (T, T) scores held in full; the experts a loop over
    the ``n_routed_experts`` HELD ones, each applied to ALL tokens and
    weighted by the top-k mask of the scores — what the experts held
    elsewhere would add is left out, as in the program.

    **Near-ties are broken as the program broke them.**  The choice of
    the k experts is discrete, and where a token's k-th and (k+1)-th
    scores lie closer than the program's bfloat16 arithmetic resolves,
    the program's choice is as right as the reference's; left alone,
    those tokens (one in fifteen, in each of four layers) move every
    gradient leaf by 5-20% and hide everything smaller.  So the
    reference computes its own float32 scores and its own top k, and
    takes the program's k experts for a token (``theirs``, (B, expert
    layers, T, k) indices: :func:`program_expert_choices`) where they are
    k distinct experts of which none scores more than ``margin`` below
    one left out; everywhere else it keeps its own.  The scores, the gates and everything after are the
    reference's own either way.  A program that chooses fewer experts,
    or others than a tie allows, is compared with the reference's choice
    and fails the gradient check.  Beside its result the function prints
    (one ``{"bench": "routing"}`` line a call, from a debug callback) the
    share of the program's assignments that are not the reference's, the
    share of them beyond the margin, and the largest gap a differing
    choice spans.

    ``dtype="bfloat16"`` is the precision control of the comparison and
    no reference: the same plain mathematics with every float32 part
    (weights, statistics, running sums, states, scores) in bfloat16 at
    the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["n_routed_experts"])
    eps, scale = cfg["layer_norm_epsilon"], float(cfg["routed_scaling_factor"])
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
            s = (q_h @ k[:, kv_head].T) / math.sqrt(D)
            s = jnp.where(causal, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, kv_head]

        o = lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(H) // (H // Hkv)))
        return o.transpose(1, 0, 2).reshape(T, H * D) @ a["proj"]["kernel"]

    def experts(m, h, theirs, margin):
        s = jax.nn.sigmoid(h @ m["router"]["kernel"])              # (T, E)
        own = s >= jnp.sort(s, axis=-1)[:, E - K, None]
        # The program's k for each token, and how far below an expert it
        # left out its lowest choice scores (negative where it left out
        # none that scores higher: the reference's own choice).
        theirs = jax.nn.one_hot(theirs, E, dtype=jnp.bool_).any(axis=1)
        gap = (jnp.where(theirs, -jnp.inf, s).max(-1)
               - jnp.where(theirs, s, jnp.inf).min(-1)).astype(jnp.float32)
        tie = (theirs.sum(-1) == K) & (gap <= margin)
        chosen = jnp.where(tie[:, None], theirs, own)
        gates = jnp.where(chosen, s, 0.0)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + 1e-20)

        def one_expert(y, w):
            w_up, w_down, gate = w
            return y + gate[:, None] * (
                jnp.square(jax.nn.relu(h @ w_up)) @ w_down), None

        y, _ = lax.scan(one_expert, jnp.zeros_like(h),
                        (m["w_up"], m["w_down"], gates[:, :held].T))
        shared = m["shared"]
        y = y + jnp.square(jax.nn.relu(h @ shared["w_up"])) @ shared["w_down"]
        differing = theirs & ~own
        return y, jnp.stack([
            differing.sum().astype(jnp.float32),
            (differing & ~tie[:, None]).sum().astype(jnp.float32),
            jnp.where(differing.any(-1), gap, 0.0).max()])

    def one_sequence(params, seq, theirs, margin):
        inp, labels = seq[:-1], seq[1:]
        x = params["tok_emb"]["embedding"][inp]
        routing = []
        for i, kind in enumerate(layers):
            p = params[f"layer_{i}"]
            h = rms_norm(x, p["norm"]["scale"])
            if kind == "M":
                x = x + mixer(p["ssm"], h)
            elif kind == "*":
                x = x + attention(p["attn"], h)
            else:
                y, said = experts(p["moe"], h, theirs[len(routing)],
                                  margin)
                x = x + y
                routing.append(said)
        x = rms_norm(x, params["ln_f"]["scale"])
        logits = x @ params["head"]["kernel"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean(), jnp.stack(routing)

    def loss(params, tokens, theirs, margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            ce, routing = lax.map(lambda s: one_sequence(cast, *s, margin),
                                  (tokens, theirs))
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        jax.debug.callback(
            _say_routing, routing.shape[1] * n * K, routing[..., 0].sum(),
            routing[..., 1].sum(), routing[..., 2].max())
        return ce.mean().astype(jnp.float32)

    return loss
