"""What a decay a key channel adds to the program and what it leaves alone:
a rank-3 ``g`` through ``gated_delta_rule``, ``GatedDeltaNet``,
``LatentAttention`` with its query latent and its rotation, and tiny stacks
of the two families that share the changed modules lower to the text — to
the letter — that the parent commit lowered them to; ``KimiDeltaAttention``
names its parts and notes its sizes; ``LatentAttention`` without a query
latent and without a rotation is the family's dense oracle in every
parameter; and the new pattern letters' refusals name them.  The stack
against the family's reference is ``tests/test_kimi_stack.py``'s, the
chunked form against the recurrence ``tests/test_gated_delta.py``'s.
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from benchmark.families import kimi_linear_lm as family
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import (
    JoyAIFlashLM, KimiLinearLM, LatentAttention, OlmoHybridLM)
from horovod_tpu.models.linear_attention import (
    GatedDeltaNet, KimiDeltaAttention)
from horovod_tpu.ops.gated_delta import gated_delta_rule

F32, BF16 = jnp.float32, jnp.bfloat16


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ----------------------------- the other cells' programs are unmoved


def _rule(dtype, T, chunk):
    b, H, dk, dv = 2, 3, 8, 16

    def s(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    args = (s(b, T, H, dk), s(b, T, H, dk), s(b, T, H, dv),
            s(b, T, H, dt=F32), s(b, T, H, dt=F32))
    return jax.jit(jax.value_and_grad(
        lambda *a: gated_delta_rule(*a, chunk=chunk).astype(F32).sum(),
        argnums=range(5))).lower(*args).as_text()


def _module(model, x):
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x)["params"])
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, x).astype(F32).sum())).lower(
            params).as_text()


def _latent(attn):
    wide = attn == "flash"      # the kernels' own head widths, interpreted
    return _module(
        LatentAttention(num_heads=2, q_latent=24, kv_latent=16,
                        nope_dim=128 if wide else 16,
                        rope_dim=64 if wide else 8,
                        v_dim=128 if wide else 16, attn=attn),
        jnp.ones((1, 128 if wide else 32, 24), BF16))


def _stack(model, extra):
    tokens = jnp.arange(2 * (16 + extra), dtype=jnp.int32).reshape(
        2, 16 + extra) % 64
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    aux = {k: v for k, v in made.items() if k != "params"}

    def loss(p, a):
        out = model.apply({"params": p, **a}, tokens, return_hidden=True,
                          mutable=list(a) or False)
        return sum(u.astype(F32).sum()
                   for u in jax.tree.leaves(out[0] if a else out))

    return jax.jit(jax.value_and_grad(loss)).lower(made["params"],
                                                   aux).as_text()


UNMOVED = {
    "rule_float32_a_tail": (lambda: _rule(F32, 40, 16), "12253569a5cd9348"),
    "rule_bfloat16_one_chunk": (lambda: _rule(BF16, 64, 64),
                                "12485ba3047ed66a"),
    "gated_delta_net": (lambda: _module(
        GatedDeltaNet(num_heads=2, key_dim=8, value_dim=16, chunk=16),
        jnp.ones((2, 32, 24), BF16)), "0d67c12391a65422"),
    "latent_full": (lambda: _latent("full"), "55135c12e2518e22"),
    "latent_flash": (lambda: _latent("flash"), "b6ed124a9f7b37cc"),
    "joyai_stack": (lambda: _stack(JoyAIFlashLM(
        vocab=64, dim=32, pattern="dx", num_heads=2, attn="full",
        mlp_hidden=48, dtype=F32,
        mla=dict(q_latent=24, kv_latent=16, nope_dim=16, rope_dim=8,
                 v_dim=16),
        moe_experts=8, moe_top_k=3, moe_hidden=16,
        moe=dict(router="sigmoid", renormalize=True, gate_scale=2.5,
                 activation="swiglu", shared_hidden=16, choice_bias=1e-3,
                 held=(0, 2))), 1), "8236e8ebc52a81ba"),
    "olmo_hybrid_stack": (lambda: _stack(OlmoHybridLM(
        vocab=64, dim=32, pattern="LF", num_heads=2, attn="full",
        mlp_hidden=48, dtype=F32,
        lin=dict(num_heads=2, key_dim=8, value_dim=16, conv_kernel=4,
                 chunk=8, allow_neg_eigval=True)), 0), "cf7cfa3af0f2cdc3"),
}


@pytest.mark.parametrize("name", UNMOVED)
def test_what_olmohybrid_and_joyaiflash_run_lowers_as_it_did(name):
    """Loss and gradients of the delta rule handed a decay a head (float32
    with a padded tail, bfloat16 in one chunk), of ``GatedDeltaNet``, of
    ``LatentAttention`` with a query latent and a rotation (dense, and over
    the interpreted kernels at 192 | 128) and of a tiny stack of each of
    the two families lower to the text the commit before the rank-4 form
    lowered them to (SHA-256 taken there, 8f72306, PR 60's tree): neither
    the second form nor ``q_latent=None`` nor ``rope_theta=None`` leaves a
    trace in a program that does not ask for it."""
    lower, want = UNMOVED[name]
    assert digest(lower()) == want, name


# ------------------------------------------- the mixer: scopes and counters

LIN = dict(num_heads=2, key_dim=16, value_dim=16, chunk=16, low_rank=8)


def test_the_mixer_names_its_parts_and_notes_its_sizes():
    x = jnp.ones((2, 64, 24), BF16)
    mixer = KimiDeltaAttention(**LIN)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), x)["params"])
    assert {k: (v["kernel"].shape if "kernel" in v else None)
            if isinstance(v, dict) else v.shape
            for k, v in params.items()} == {
        "q": (24, 32), "k": (24, 32), "v": (24, 32), "b": (24, 2),
        "f_a": (24, 8), "f_b": (8, 32), "g_a": (24, 8), "g_b": (8, 32),
        "out": (32, 24), "conv": (4, 96), "A_log": (2,), "dt_bias": (32,),
        "gate_norm": (16,)}
    noted = {}
    text = jax.jit(jax.grad(noting_layers(
        lambda p: mixer.apply({"params": p}, x).astype(F32).sum(),
        noted))).lower(params).as_text(debug_info=True)
    for scope in ("in_proj/q", "conv", "decay/f_a", "decay/neg",
                  "delta/decay", "delta/solve", "delta/states",
                  "delta/inter", "delta/intra", "gate_norm/g_a",
                  "gate_norm/logistic", "out_proj/out"):
        assert scope in text, scope
    # Two sequences of 64 tokens in chunks of 16: 8 chunks, a (16, 16)
    # float32 state a head entering each, the log-decays of 2 heads of 16
    # channels a token, 15 pairs of sub-chunks a chunk; heads of 16 are no
    # shape the tile kernels take, so the plan stood down.
    assert list(noted.values()) == [{
        "lin.delta_chunks": 8, "lin.state_bytes": 8 * 2 * 16 * 16 * 4,
        "lin.decay_bytes": 2 * 64 * 2 * 16 * 4, "lin.sub_chunks": 8 * 15,
        "lin.tile_kernel_chunks": 0}]
    assert "kda_tiles" not in text
    # The cell's readers find them under those names.
    from benchmark.metrics import (kda_decay_ms, kda_intra_ms, linattn_ms)
    stack = "transpose(jvp(TransformerLM))/layer_*/lin/"
    assert kda_decay_ms.in_decay(stack + "decay/softplus")
    assert kda_decay_ms.in_decay(stack + "checkpoint/delta/decay/exp")
    assert not kda_decay_ms.in_decay(stack + "delta/solve/dot_general")
    assert kda_intra_ms.in_tiles(stack + "delta/solve/dot_general")
    assert kda_intra_ms.in_tiles(stack + "delta/intra/dot_general")
    assert not kda_intra_ms.in_tiles(stack + "delta/states/while")
    assert linattn_ms.in_delta(stack + "delta/decay/exp")


def test_the_mixer_at_the_kernels_widths_notes_their_chunks():
    """Keys 128 wide in chunks of 64: the tile kernels (interpreted here)
    make every chunk's tiles, under ``delta/solve`` with the inverse, and
    the XLA form's ``delta/decay`` is gone — the decayed operands leave the
    same kernel."""
    x = jnp.ones((1, 128, 24), BF16)
    mixer = KimiDeltaAttention(num_heads=2, key_dim=128, value_dim=16,
                               chunk=64, low_rank=8)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), x)["params"])
    noted = {}
    jaxpr = jax.make_jaxpr(jax.grad(noting_layers(
        lambda p: mixer.apply({"params": p}, x).astype(F32).sum(),
        noted)))(params)
    (counters,) = noted.values()
    assert counters["lin.tile_kernel_chunks"] == counters[
        "lin.delta_chunks"] == 2

    def walk(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    kernels = [(e.params["name"], str(e.source_info.name_stack))
               for e in walk(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    # Forward, the forward again under the mixer's checkpoint, backward.
    assert sorted(n for n, _ in kernels) == [
        "kda_tiles_bwd", "kda_tiles_fwd", "kda_tiles_fwd"]
    assert all("delta/solve" in scope for _, scope in kernels), kernels
    assert not any("delta/decay" in str(e.source_info.name_stack)
                   for e in walk(jaxpr.jaxpr))


def test_the_mixer_s_float32_parts_are_float32_in_the_traced_program():
    """With bfloat16 operands: ``softplus`` and every ``exp`` and
    ``logistic`` (the decays, ``beta``, the gate), the L2 norms' and the
    gated norm's ``rsqrt`` read float32."""
    x = jnp.ones((1, 32, 24), BF16)
    mixer = KimiDeltaAttention(**LIN)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), x)["params"])
    jaxpr = jax.make_jaxpr(lambda p: mixer.apply({"params": p}, x))(params)

    def walk(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    kinds = {}
    for e in walk(jaxpr.jaxpr):
        if e.primitive.name in ("exp", "logistic", "rsqrt", "log1p"):
            kinds.setdefault(e.primitive.name, set()).add(
                e.invars[0].aval.dtype)
    # silu's logistic runs in the convolution's dtype; every other one here
    # is the step's or the gate's.
    assert kinds["exp"] == {jnp.dtype(F32)}
    assert kinds["rsqrt"] == {jnp.dtype(F32)}
    assert jnp.dtype(F32) in kinds["logistic"]


# --------------------- latent attention without a query latent or rotation

CFG = {"num_attention_heads": 2, "qk_nope_head_dim": 128,
       "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 32,
       "rms_norm_eps": 1e-5}
D_MODEL, T = 64, 64


def latent(attn, **fields):
    return LatentAttention(**{**dict(
        num_heads=2, q_latent=None, kv_latent=32, nope_dim=128, rope_dim=64,
        v_dim=128, attn=attn, dtype=F32, norm_eps=1e-5, rope_theta=None),
        **fields})


@pytest.fixture(scope="module")
def latent_problem():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, T, D_MODEL))
    w = jax.random.normal(ks[1], (2, T, D_MODEL))
    params = latent("full").init(ks[2], x)["params"]
    params = jax.tree.map(
        lambda a: a * (1.0 + 0.3 * jax.random.normal(ks[3], a.shape))
        if a.ndim == 1 else a, params)
    attention = family.reference_attention(CFG)

    def loss(p):
        with jax.default_matmul_precision("highest"):
            return (jax.vmap(lambda h: attention(p, h))(x) * w).sum()

    return params, x, w, jax.jit(jax.value_and_grad(loss))(params)


def worst(got, want):
    return max(float(jnp.linalg.norm(g - w)
                     / jnp.maximum(jnp.linalg.norm(w), 1e-30))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_unrotated_latent_attention_equals_its_dense_oracle(attn,
                                                            latent_problem):
    """No ``q_a``, no ``q_norm``: ``q_b`` reads the layer's input; the 64
    shared key channels unrotated.  A loss and EVERY parameter's gradient
    against the family's plain reference, dense and over the interpreted
    kernels, to 2e-4; with a rotation where there is none the same
    comparison fails."""
    params, x, w, wanted = latent_problem
    assert set(params) == {"q_b", "kv_a", "kv_norm", "kv_b", "proj"}
    assert params["q_b"]["kernel"].shape == (D_MODEL, 2 * 192)

    def run(module):
        def loss(p):
            with jax.default_matmul_precision("highest"):
                return (module.apply({"params": p}, x) * w).sum()
        return jax.jit(jax.value_and_grad(loss))(params)

    assert worst(run(latent(attn)), wanted) <= 2e-4
    if attn == "full":
        assert worst(run(latent(attn, rope_theta=1e4)), wanted) > 1e-2


def test_unrotated_latent_attention_s_scopes_and_counters():
    x = jnp.ones((1, T, D_MODEL), BF16)
    module = latent("flash", dtype=BF16)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x)["params"])
    noted = {}
    text = jax.jit(jax.grad(noting_layers(
        lambda p: module.apply({"params": p}, x).astype(F32).sum(),
        noted))).lower(params).as_text(debug_info=True)
    for scope in ("mla/kv_down", "mla/norm", "mla/q_up", "mla/kv_up",
                  "mla/lanes", "mla/attend", "mla/out"):
        assert scope in text, scope
    assert "mla/q_down" not in text and "mla/rope" not in text
    (counters,) = noted.values()
    assert counters["attn.q_latent"] == 0
    assert counters["attn.kv_latent"] == 32
    assert counters["attn.padded_lanes"] == 64
    # The layer's input in c_q's place, the k | v latent and the shared
    # key; o and the row statistics that the kernel wrote.
    assert counters["attn.latent_residual_bytes"] == (
        2 * (D_MODEL + 32 + 64) + 2 * 2 * 128 + 4 * 8 * 2)


# ------------------------------------------------------------- refusals


def test_the_new_letters_and_what_they_refuse():
    tokens = jnp.zeros((1, 16), jnp.int32)
    small = dict(vocab=32, dim=16, num_heads=1, mlp_hidden=8, moe_experts=4,
                 moe_top_k=1, moe_hidden=8, attn="full",
                 lin=dict(num_heads=1, key_dim=8, value_dim=8, chunk=8),
                 mla=dict(q_latent=None, kv_latent=8, nope_dim=8, rope_dim=4,
                          v_dim=8))
    made = jax.eval_shape(lambda: KimiLinearLM(
        **small, pattern="kKx").init(jax.random.PRNGKey(0), tokens))
    assert set(made["params"]["layer_0"]) == {"norm", "lin", "mlp_norm",
                                              "mlp"}
    assert set(made["params"]["layer_1"]) == {"norm", "lin", "moe_norm",
                                              "moe"}
    assert set(made["params"]["layer_2"]["attn"]) == {
        "q_b", "kv_a", "kv_norm", "kv_b", "proj"}
    assert set(made["balance"]) == {"layer_1", "layer_2"}
    with pytest.raises(ValueError, match="pos='none'"):
        KimiLinearLM(**small, pattern="kK", pos="rotary").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="power of two"):
        KimiLinearLM(**{**small, "lin": {**small["lin"], "chunk": 12}},
                     pattern="k").init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        KimiLinearLM(**small, pattern=None).init(jax.random.PRNGKey(0),
                                                 tokens)
