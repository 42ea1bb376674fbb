"""Family ``nemotron3_super_lm``: the Nemotron-3-Super hybrid stack — Mamba-2
mixers, grouped-query attention, LatentMoE expert layers (sigmoid-routed
relu² experts that work in a latent between a down- and an up-projection
all of them share, beside a shared expert on the layer's input) and a
multi-token-prediction module behind the stack —, keyed like Hugging
Face's ``nemotron_h`` config (``hybrid_override_pattern``, ``hidden_size``,
``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
``conv_kernel``, ``chunk_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``n_routed_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``moe_latent_size``,
``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``,
``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``,
``vocab_size``).

The first ``num_hidden_layers`` letters of the pattern are run, and the
prediction module's ``mtp_hybrid_override_pattern`` behind them.  The
configuration is ONE CHIP'S SHARE of a deployment that divides every layer:
``mamba_num_heads`` / ``n_groups`` and ``num_attention_heads`` /
``num_key_value_heads`` count the heads of one tensor-parallel rank (a
rank's share of a mixer is the mixer of its one B/C group),
``n_routed_experts`` the experts held here (the first ones) of the
``experts_routed_over`` the router chooses ``num_experts_per_tok`` of, and
``vocab_size`` this chip's slice of the vocabulary.  ``sequence_length`` is
the training sequence; a sequence of the batch is two ids longer (the
labels of the last position's two predictions).

The system under test is the repo's ``TransformerLM`` with a ``pattern``
and ``mtp`` (``models.transformer.Nemotron3SuperLM``): ``ops/ssd.py``'s
chunked scan, the flash kernels with grouped KV heads, ``DroplessMoE``
with held experts in a latent, the fused cross-entropy head twice a step
(``ops.losses.multi_token_xent``).  Everything else in this file is the
benchmark's own yardstick: the host-batch maker, the model FLOPs, the
scan's, the passes', the flash kernels' and the expert layers' operations
and bytes, and a plain float32 reference of the same mathematics that
reads the same parameter tree.  The loss is ``CE(next token) +
mtp_loss_scaling_factor · CE(the token after)`` and nothing else.
"""

from __future__ import annotations

import json
import math

import numpy as np

THROUGHPUT = ("tokens_per_s_chip", "tokens/s/chip")
SYNC_AUX_STATE = False

# The CPU rehearsal's sizes: every kind of layer and the prediction module,
# 4 of 16 experts held in a latent of 16, top-3, attention of two query
# heads over one KV head of 128 (the lane-aligned kernels, interpreted), a
# mixer of one group.  A few hundred tokens average bfloat16's rounding
# out far less than a real batch does, so the preset brings its own,
# looser tolerances.
TINY = {"hidden_size": 64, "num_hidden_layers": 5, "mamba_num_heads": 4,
        "mamba_head_dim": 16, "n_groups": 1, "ssm_state_size": 16,
        "chunk_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 128, "n_routed_experts": 4,
        "experts_routed_over": 16, "num_experts_per_tok": 3,
        "moe_intermediate_size": 32, "moe_latent_size": 16,
        "moe_shared_expert_intermediate_size": 64, "sequence_length": 64,
        "vocab_size": 256,
        "hybrid_override_pattern": "ME*EM",
        "tolerances": {"loss_rel": 5e-3, "grad_rel": 2e-1,
                       "tie_margin": 2.0 ** -5}}
TINY_BATCH_PER_CHIP = 2

# Leaves whose gradients are compared with the reference's, with {m}, {a},
# {e} the first mixer, attention and expert layer of the pattern and {x},
# {y} the prediction module's expert and attention layer.  The head's
# carries both terms of the loss, the table's the stack's gather and the
# prediction module's.  A routed leaf (router, latent projection, an
# expert's matrix) reads every assignment the reference was handed that
# the compared program did not make, so the choices come from a
# forward-and-backward compile of the program, as the compared gradients
# do (:func:`program_expert_choices`).  That is still not the compared
# compile: behind the attention layer the routed leaves read 0.07-0.12 of
# the one bound of 0.15 on the chip (the configuration's ``grad_rel_why``)
# and are not named; the routed leaves are the FIRST expert layer's, and
# the deep layers and the module are held by leaves no choice enters.
GRAD_LEAVES = (("layer_{m}", "ssm", "in_proj", "kernel"),
               ("layer_{m}", "ssm", "A_log"),
               ("layer_{a}", "attn", "kv", "kernel"),
               ("layer_{e}", "moe", "router", "kernel"),
               ("layer_{e}", "moe", "latent_down", "kernel"),
               ("layer_{e}", "moe", "w_up"),
               ("layer_{e}", "moe", "latent_up", "kernel"),
               ("layer_{e}", "moe", "shared", "w_up"),
               ("mtp", "eh_proj", "kernel"),
               ("mtp", "layer_{y}", "attn", "q", "kernel"),
               ("mtp", "layer_{x}", "moe", "shared", "w_down"),
               ("head", "kernel"),
               ("tok_emb", "embedding"))
GRAD_SAMPLES = 1          # one sequence on both sides


def pattern(cfg) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def mtp_pattern(cfg) -> str:
    return cfg["mtp_hybrid_override_pattern"]


def grad_leaves(cfg):
    p = pattern(cfg)
    at = {"m": p.index("M"), "a": p.index("*"), "e": p.index("E"),
          "x": mtp_pattern(cfg).index("E"), "y": mtp_pattern(cfg).index("*")}
    return [tuple(part.format(**at) for part in path) for path in GRAD_LEAVES]


# ------------------------------------------------------ system under test


def _model(cfg):
    import jax.numpy as jnp
    from horovod_tpu.models import Nemotron3SuperLM

    as_published = {
        "model_type": "nemotron_h", "mamba_hidden_act": "silu",
        "mlp_hidden_act": "relu2", "attention_bias": False,
        "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
        "use_conv_bias": True, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "n_shared_experts": 1,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 1}
    differs = {k: cfg[k] for k, v in as_published.items() if cfg[k] != v}
    if differs:
        raise ValueError(f"nemotron3_super_lm runs the stack as published; "
                         f"got {differs}")
    compute = jnp.dtype(cfg["training"]["compute_dtype"])
    return Nemotron3SuperLM(
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
                 activation="relu2", latent=cfg["moe_latent_size"],
                 shared_hidden=cfg["moe_shared_expert_intermediate_size"],
                 held=(0, cfg["n_routed_experts"])),
        mtp=dict(pattern=mtp_pattern(cfg)))


def init(cfg, key):
    """(params, aux) on the device, float32, from ``key``.  No parameter's
    shape depends on the sequence length, so a short one is traced."""
    import jax.numpy as jnp
    params = _model(cfg).init(
        key, jnp.zeros((1, min(cfg["sequence_length"], 256) + 1),
                       jnp.int32))["params"]
    return params, {}


def loss_fn(cfg):
    from horovod_tpu.ops.losses import multi_token_xent

    model = _model(cfg)
    weights = (1.0, float(cfg["training"]["mtp_loss_scaling_factor"]))

    def loss(params, aux, tokens):
        hiddens = model.apply({"params": params}, tokens[:, :-1],
                              return_hidden=True)
        return multi_token_xent(hiddens, model.head_kernel(params), tokens,
                                weights), aux

    return loss


def optimizer(cfg):
    import optax
    o = cfg["training"]["optimizer"]
    if o["name"] != "adamw":
        raise ValueError("nemotron3_super_lm trains with adamw, not "
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


def program_expert_choices(cfg, params, tokens):
    """The experts the PROGRAM's routers chose for ``tokens`` (B, T + 2):
    (B, expert layers, T, num_experts_per_tok) indices, the stack's layers
    and then the prediction module's, read from what its expert layers
    sow.  :func:`reference_loss` breaks its near-ties with them.

    They are read beside the gradients of :func:`grad_leaves`, from one
    forward-and-backward pass of the program's own loss: a forward-only
    compile rounds bfloat16 in other places than the compile whose
    gradients are compared, and by the third expert layer some 2% of the
    held assignments differ between the two (PERF.md section 6, PR 46).
    Where those gradients are not finite the choices break no tie (-1)."""
    import functools

    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.losses import multi_token_xent

    model = _model(cfg)
    weights = (1.0, float(cfg["training"]["mtp_loss_scaling_factor"]))
    paths = grad_leaves(cfg)

    def loss(p):
        hiddens, state = model.apply(
            {"params": p}, tokens[:, :-1], return_hidden=True,
            mutable=["intermediates"])
        return (multi_token_xent(hiddens, model.head_kernel(p), tokens,
                                 weights), state["intermediates"])

    (_, sown), grads = jax.value_and_grad(loss, has_aux=True)(
        jax.lax.stop_gradient(params))
    finite = jnp.stack([
        jnp.isfinite(functools.reduce(lambda t, k: t[k], path, grads)).all()
        for path in paths]).all()
    B, T = tokens.shape[0], tokens.shape[1] - 2
    layers = [sown[f"layer_{i}"] for i, kind in enumerate(pattern(cfg))
              if kind == "E"]
    layers += [sown["mtp"][f"layer_{i}"]
               for i, kind in enumerate(mtp_pattern(cfg)) if kind == "E"]
    chosen = jnp.stack([layer["moe"]["expert_index"][0].reshape(B, T, -1)
                        for layer in layers], axis=1)
    return jnp.where(finite, chosen, -1)


# --------------------------------------------------- FLOPs, from shapes


def _sizes(cfg):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    letters = pattern(cfg) + mtp_pattern(cfg)
    return {"d": cfg["hidden_size"], "H": H, "P": P, "G": G, "N": N,
            "inner": H * P, "conv_dim": H * P + 2 * G * N,
            "Q": cfg["chunk_size"], "T": cfg["sequence_length"],
            # Layers of each kind a step runs: the stack's and the
            # prediction module's.
            "layers": {k: letters.count(k) for k in "M*E"}}


def held_share(cfg) -> float:
    """Of a token's ``num_experts_per_tok`` assignments, those that uniform
    routing sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["experts_routed_over"])


def matmuls(cfg):
    """Every weight matmul of one forward pass, per trained position, as
    ``(name, k, n, count)``: a (1, k) row times a (k, n) weight, ``count``
    of them a position (a fraction for the routed experts:
    :func:`held_share`).  The head is read twice, once a term of the loss;
    the prediction module's layers count with the stack's."""
    s = _sizes(cfg)
    d, L = s["d"], s["layers"]
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kvw = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    held = held_share(cfg)
    eh, sh, lat = (cfg["moe_intermediate_size"],
                   cfg["moe_shared_expert_intermediate_size"],
                   cfg["moe_latent_size"])
    return [("ssm_in_proj", d, 2 * s["inner"] + 2 * s["G"] * s["N"] + s["H"],
             L["M"]),
            ("ssm_out_proj", s["inner"], d, L["M"]),
            ("attn_q", d, qw, L["*"]), ("attn_kv", d, kvw, L["*"]),
            ("attn_proj", qw, d, L["*"]),
            ("router", d, cfg["experts_routed_over"], L["E"]),
            ("latent_down", d, lat, L["E"]), ("latent_up", lat, d, L["E"]),
            ("w_up", lat, eh, held * L["E"]),
            ("w_down", eh, lat, held * L["E"]),
            ("shared_w_up", d, sh, L["E"]), ("shared_w_down", sh, d, L["E"]),
            ("mtp_eh_proj", 2 * d, d, cfg["num_nextn_predict_layers"]),
            ("head", d, cfg["vocab_size"],
             1 + cfg["num_nextn_predict_layers"])]


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
    """Model FLOPs one trained position of THIS CHIP'S SHARE requires:
    forward plus backward (2 + 4 FLOPs per weight) of every weight matmul
    it runs (:func:`matmuls`) — the routed experts at the share of a token
    uniform routing sends here —, of attention's two products over the
    causal half of the (T, T) square in the stack's and the module's
    attention layer, and of the mixers' chunked scans
    (:func:`scan_flops_per_token`).  Recomputation, the rows that round a load up
    to whole windows (``ceil(landed / W)``, ``moe._window_plan``),
    the embedding lookups, the top-k, the sort and the combine are not
    counted."""
    s = _sizes(cfg)
    n_matmul = sum(k * n * count for _, k, n, count in matmuls(cfg))
    attn = (s["layers"]["*"] * s["T"] * cfg["num_attention_heads"]
            * cfg["head_dim"])
    return (6.0 * n_matmul + 6.0 * attn
            + 3.0 * s["layers"]["M"] * scan_flops_per_token(cfg))


def ssd_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the mixers' scans of one step need on one chip
    (the chunked form, forward and backward, without the convolution),
    from shapes, as ``nemotron_h_lm.ssd_cost`` counts them.

    FLOPs: :func:`scan_flops_per_token`'s four products, twice again for
    the backward.  Bytes: the compulsory traffic of a scan that keeps its
    chunk states on the chip — forward it reads ``x`` (H P), ``B`` and ``C``
    (G N each) in bf16 and ``dt`` (H) in f32 and writes ``y`` (H P); the
    backward reads those and ``dy`` and writes the four gradients."""
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


def pass_cost(cfg, batch_per_chip: int) -> dict:
    """Bytes the mixers' two elementwise passes of one step need on one
    chip, forward and backward, from shapes, as
    ``granite_hybrid_lm.pass_cost`` counts them: each pass one read of its
    operands and one write of its result, in bf16.  The convolution reads
    ``xBC`` and writes it activated (2 conv_dim values a token); its
    backward reads ``xBC`` and ``dy`` and writes ``dx`` (3).  The gated
    norm reads ``y`` and ``z`` and writes the normed product (3 inner);
    its backward reads ``y``, ``z``, ``do`` and writes ``dy``, ``dz`` (5).
    The convolution replayed inside the mixer's ``jax.checkpoint`` counts
    against the share.  The passes hold no matmul: bytes bound them."""
    s = _sizes(cfg)
    tokens = batch_per_chip * s["T"]
    per_token = (2 + 3) * s["conv_dim"] * 2 + (3 + 5) * s["inner"] * 2
    return {"bytes": s["layers"]["M"] * tokens * per_token,
            "bytes_per_token": per_token}


def flash_cost(cfg, batch_per_chip: int) -> dict:
    """Operations and bytes the flash kernels of one step need on one
    chip, from their shapes — queries ``(B, T, H, D)``, keys and values
    ``(B, T, H_kv, D)``, causal —, as ``nemotron_h_lm.flash_cost`` counts
    them, over the stack's attention layers and the prediction module's.

    FLOPs: the forward's two products and the backward's five, each
    ``2 B H T T D`` over the causal half.  Bytes: each kernel's compulsory
    traffic in bf16 with k, v, dk, dv at their ``H_kv`` heads, plus the
    float32 row statistics."""
    B, T = batch_per_chip, cfg["sequence_length"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = _sizes(cfg)["layers"]["*"]
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
    chip, forward and backward, from shapes, over the stack's expert
    layers and the prediction module's (``moe_ms`` reads both): the router
    over all ``experts_routed_over`` (``router_flops``: what ``route_ms``
    is read for), the two latent projections over every token
    (``latent_flops``: ``latent_ms``), the held experts' two grouped
    matmuls in the latent at the load uniform routing sends here (``A =
    tokens * num_experts_per_tok * held / routed over`` rows), and the
    shared expert's two matmuls over every token.

    FLOPs: 2 a weight forward and 4 backward, the router's float32 product
    at one pass of the bf16 peak (it runs several: that counts against the
    share).  Bytes, per matmul, in bf16 as in ``nemotron_h_lm.moe_cost``:
    forward its rows in and out and the weights; the input-gradient
    product the same again; the weight-gradient product both sets of rows
    and the gradient in float32.  The top-k, the sort, the gathers, the
    scatter of the combine, the activation and the rows that round the load
    up to whole windows (a layer runs ``ceil(landed / W)`` of them, ``W``
    from ``moe._window_plan``) are left out: what the layer takes for them
    counts against its roofline share."""
    d, lat = cfg["hidden_size"], cfg["moe_latent_size"]
    eh, sh = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    E, held = cfg["experts_routed_over"], cfg["n_routed_experts"]
    L = _sizes(cfg)["layers"]["E"]
    tokens = batch_per_chip * cfg["sequence_length"]
    A = tokens * held_share(cfg)
    router = L * 6.0 * tokens * d * E
    latent = L * 6.0 * 2 * tokens * d * lat
    flops = (router + latent
             + L * 6.0 * (2 * A * lat * eh + 2 * tokens * d * sh))

    def matmul_bytes(rows, k_, n_, weights):
        moved = rows * (k_ + n_) * 2
        return 3 * moved + 2 * weights * 2 + weights * 4

    nbytes = L * (2 * matmul_bytes(tokens, d, lat, d * lat)
                  + 2 * matmul_bytes(A, lat, eh, held * lat * eh)
                  + 2 * matmul_bytes(tokens, d, sh, d * sh))
    return {"flops": flops, "bytes": nbytes,
            "assignments": tokens * cfg["num_experts_per_tok"],
            "held_assignments": A, "router_flops": router,
            "latent_flops": latent,
            "expert_parameters": L * (2 * held * lat * eh + 2 * d * lat
                                      + 2 * d * sh)}


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


def reference_attention(cfg):
    """``f(a, h) -> y`` for ONE sequence ``h`` (T, d) and an attention
    layer's parameters ``a``: ``num_attention_heads`` query heads over
    ``num_key_value_heads`` KV heads of ``head_dim`` by a masked softmax
    scaled by 1/sqrt(head_dim), no positions, no bias; one query head at a
    time with its (T, T) scores held in full, each recomputed in the
    backward pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])

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

    return attention


def reference_experts(cfg):
    """``f(m, h, theirs, margin) -> (y, routing)`` for ONE sequence ``h``
    (T, d) and a LatentMoE layer's parameters ``m``: sigmoid scores over
    all ``experts_routed_over``, the ``num_experts_per_tok`` largest
    chosen (the program's ``theirs`` (T, k) where they are a tie within
    ``margin``: :func:`reference_given_choices`), gates the scores
    renormalised over the chosen and scaled by ``routed_scaling_factor``;
    ``z = h W_down``; a loop over the ``n_routed_experts`` HELD experts,
    each applied to all tokens' latents and weighted by its gate; the sum
    through ``W_up``; the shared relu² expert on ``h`` itself.
    ``routing``: assignments of ``theirs`` that are not the reference's
    own, those of them beyond the margin, the largest gap one spans."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    E, K, held = (cfg["experts_routed_over"], cfg["num_experts_per_tok"],
                  cfg["n_routed_experts"])
    scale = float(cfg["routed_scaling_factor"])

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
        z = h @ m["latent_down"]["kernel"]                     # (T, latent)

        def one_expert(y, w):
            w_up, w_down, gate = w
            return y + gate[:, None] * (
                jnp.square(jax.nn.relu(z @ w_up)) @ w_down), None

        y, _ = lax.scan(one_expert, jnp.zeros_like(z),
                        (m["w_up"], m["w_down"], gates[:, :held].T))
        shared = m["shared"]
        y = (y @ m["latent_up"]["kernel"]
             + jnp.square(jax.nn.relu(h @ shared["w_up"])) @ shared["w_down"])
        differing = theirs & ~own
        return y, jnp.stack([
            differing.sum().astype(jnp.float32),
            (differing & ~tie[:, None]).sum().astype(jnp.float32),
            jnp.where(differing.any(-1), gap, 0.0).max()])

    return experts


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
    float32 at full matmul precision, ``tokens`` (B, T + 2): the stack as
    config.json describes it — pre-norm residuals of one sub-layer a layer,
    RMSNorm; the mixer by :func:`reference_mixer`; attention of
    ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
    heads by a masked softmax scaled by 1/sqrt(head_dim), no positions, no
    bias; LatentMoE: sigmoid scores over all ``experts_routed_over``, the
    ``num_experts_per_tok`` largest chosen, gates the scores renormalised
    over the chosen and scaled by ``routed_scaling_factor``, ``z = u
    W_down`` (``moe_latent_size`` wide), relu² experts on ``z``, their
    gated sum through ``W_up``, one shared relu² expert on ``u`` itself;
    final RMSNorm, untied head — and the multi-token-prediction module:
    ``h' = [n_e(Emb(x_{t+1})) | n_h(h_t)] W_eh`` through its own layers
    and ``n_m`` to the same head, predicting ``x_{t+2}``.  The loss is the
    mean cross-entropy of the first prediction plus
    ``training.mtp_loss_scaling_factor`` times the second's.

    One sequence at a time through ``lax.map``; no kernels, no chunks, no
    sort, no grouped matmul, no window: attention one query head at a time
    with its (T, T) scores held in full; the experts a loop over the
    ``n_routed_experts`` HELD ones, each applied to ALL tokens' latents and
    weighted by the top-k mask of the scores — what the experts held
    elsewhere would add is left out, as in the program.  Computed in
    blocks: every layer and each head pass is a ``jax.checkpoint``, so the
    backward pass holds one layer's float32 intermediates at a time.

    **Near-ties are broken as the program broke them**, as
    ``nemotron_h_lm.reference_given_choices`` does and for its reason: the
    reference computes its own float32 scores and its own top k, and takes
    the program's k experts for a token (``theirs``, (B, expert layers, T,
    k): :func:`program_expert_choices`) where they are k distinct experts
    of which none scores more than ``margin`` below one left out;
    everywhere else it keeps its own.  Scores, gates and everything after
    are the reference's own either way.  Beside its result the function
    prints one ``{"bench": "routing"}`` line a call.

    ``dtype="bfloat16"`` is the precision control of the comparison and
    no reference: the same plain mathematics with every float32 part
    (weights, statistics, running sums, states, scores, the combine) in
    bfloat16 at the default matmul precision."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    K, eps = cfg["num_experts_per_tok"], cfg["layer_norm_epsilon"]
    lam = float(cfg["training"]["mtp_loss_scaling_factor"])
    T = cfg["sequence_length"]
    mixer = reference_mixer(cfg, mixer_form)
    attention, experts = reference_attention(cfg), reference_experts(cfg)
    dtype = jnp.dtype(dtype)

    def rms_norm(x, scale_):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale_

    @jax.checkpoint
    def mixer_layer(p, x):
        return x + mixer(p["ssm"], rms_norm(x, p["norm"]["scale"]))

    @jax.checkpoint
    def attention_layer(p, x):
        return x + attention(p["attn"], rms_norm(x, p["norm"]["scale"]))

    @jax.checkpoint
    def expert_layer(p, x, theirs, margin):
        y, said = experts(p["moe"], rms_norm(x, p["norm"]["scale"]), theirs,
                          margin)
        return x + y, said

    def layers(params, letters, x, theirs, margin, routing):
        for i, kind in enumerate(letters):
            p = params[f"layer_{i}"]
            if kind == "M":
                x = mixer_layer(p, x)
            elif kind == "*":
                x = attention_layer(p, x)
            else:
                x, said = expert_layer(p, x, theirs[len(routing)], margin)
                routing.append(said)
        return x

    @jax.checkpoint
    def cross_entropy(h, head, labels):
        logits = h @ head
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def one_sequence(params, seq, theirs, margin):
        table, head = params["tok_emb"]["embedding"], params["head"]["kernel"]
        routing = []
        x = layers(params, pattern(cfg), table[seq[:T]], theirs, margin,
                   routing)
        h = rms_norm(x, params["ln_f"]["scale"])
        m = params["mtp"]
        x = jnp.concatenate(
            [rms_norm(table[seq[1:T + 1]], m["n_e"]["scale"]),
             rms_norm(h, m["n_h"]["scale"])], axis=-1) @ m["eh_proj"]["kernel"]
        x = layers(m, mtp_pattern(cfg), x, theirs, margin, routing)
        h2 = rms_norm(x, m["n_m"]["scale"])
        return (cross_entropy(h, head, seq[1:T + 1])
                + lam * cross_entropy(h2, head, seq[2:T + 2]),
                jnp.stack(routing))

    def loss(params, tokens, theirs, margin):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            ce, routing = lax.map(lambda s: one_sequence(cast, *s, margin),
                                  (tokens, theirs))
        n = tokens.shape[0] * T
        jax.debug.callback(
            _say_routing, routing.shape[1] * n * K, routing[..., 0].sum(),
            routing[..., 1].sum(), routing[..., 2].max())
        return ce.mean().astype(jnp.float32)

    return loss
