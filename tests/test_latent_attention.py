"""``LatentAttention`` against the ``joyai_flash_lm`` family's plain
reference, at a small size on the CPU in float32: a loss and EVERY
parameter's gradient, in the dense form and over the interpreted kernels —
and the reference with the rope term, the latents' norms or the two widths
left out, each of which must FAIL the same tolerance (so the test sees each
part of the mechanism).  The reference rotates adjacent pairs, as published;
the module runs the rotate-half form on permuted columns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import joyai_flash_lm as family
from horovod_tpu.layer_notes import noting_layers
from horovod_tpu.models import LatentAttention

# Two heads at the published head widths, small latents, T 64.
CFG = {"num_attention_heads": 2, "qk_nope_head_dim": 128,
       "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": 48,
       "kv_lora_rank": 32, "rms_norm_eps": 1e-6, "rope_theta": 3.2e7}
D_MODEL, T = 64, 64
TOL = 2e-4


def module(attn):
    return LatentAttention(
        num_heads=2, q_latent=48, kv_latent=32, nope_dim=128, rope_dim=64,
        v_dim=128, attn=attn, dtype=jnp.float32, norm_eps=1e-6,
        rope_theta=3.2e7)


def problem(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, T, D_MODEL))
    w = jax.random.normal(ks[1], (2, T, D_MODEL))
    params = module("full").init(ks[2], x)["params"]
    # Norm scales off 1, so that their gradients and their place show.
    params = jax.tree.map(
        lambda a: a * (1.0 + 0.3 * jax.random.normal(ks[3], a.shape))
        if a.ndim == 1 else a, params)
    return params, x, w


def reference(leave_out=""):
    attention = family.reference_attention(CFG, leave_out)

    def loss(p, x, w):
        with jax.default_matmul_precision("highest"):
            return (jax.vmap(lambda h: attention(p, h))(x) * w).sum()

    return jax.jit(jax.value_and_grad(loss))


def program(attn):
    def loss(p, x, w):
        with jax.default_matmul_precision("highest"):
            return (module(attn).apply({"params": p}, x) * w).sum()

    return jax.jit(jax.value_and_grad(loss))


def worst(got, want):
    """The largest relative error over the loss and every leaf."""
    def rel(g, w):
        return float(jnp.linalg.norm(g - w)
                     / jnp.maximum(jnp.linalg.norm(w), 1e-30))
    return max(rel(g, w) for g, w in zip(jax.tree.leaves(got),
                                         jax.tree.leaves(want)))


@pytest.fixture(scope="module")
def wanted():
    params, x, w = problem()
    return reference()(params, x, w)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_module_equals_the_reference_in_every_parameter(attn, wanted):
    params, x, w = problem()
    got = program(attn)(params, x, w)
    assert set(got[1]) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm",
                           "kv_b", "proj"}
    assert worst(got, wanted) <= TOL


@pytest.mark.parametrize("leave_out", ["rope", "norms", "widths"])
def test_a_reference_with_a_part_left_out_fails(leave_out, wanted):
    params, x, w = problem()
    assert worst(reference(leave_out)(params, x, w), wanted) > 100 * TOL


def test_shapes_counters_and_what_is_kept():
    params, x, _ = problem()
    assert params["q_b"]["kernel"].shape == (48, 2 * 192)
    assert params["kv_a"]["kernel"].shape == (D_MODEL, 32 + 64)
    assert params["kv_b"]["kernel"].shape == (32, 2 * 256)
    assert params["proj"]["kernel"].shape == (2 * 128, D_MODEL)
    for attn, padded, kept in (("flash", 64, 4 * (48 + 32 + 64 + 2 * 128)
                                + 4 * 8 * 2),
                               ("full", 0, 4 * (48 + 32 + 64))):
        notes = {}
        jax.eval_shape(noting_layers(
            lambda p, x: module(attn).apply({"params": p}, x), notes),
            params, x)
        (noted,) = notes.values()
        assert noted == {
            "attn.q_latent": 48, "attn.kv_latent": 32,
            "attn.qk_head_dim": 192, "attn.v_head_dim": 128,
            "attn.padded_lanes": padded,
            # T 64 tiles in blocks of 64, off the lanes: the grid form,
            # which streams K and V.
            "attn.kv_resident_bytes": 0,
            "attn.latent_residual_bytes": kept}


@pytest.mark.parametrize("headroom,kept", [
    (True, 256 * (256 + 128) * 4), (False, 0)],
    ids=["resident_rows", "no_headroom_streams"])
def test_kv_resident_bytes_are_the_kernels_plan_s(monkeypatch, headroom,
                                                  kept):
    """At a T that tiles in whole lanes the counter is what the flash
    family's ``_plan`` holds of a head's K and V in VMEM — the rows at 256
    and 128 lanes (float32 here) in the resident form — and 0 where the
    device backs no budget for it and the grid form streams them."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa._pallas, "vmem_headroom_ok", lambda: headroom)
    x = jax.ShapeDtypeStruct((1, 256, D_MODEL), jnp.float32)
    params = jax.eval_shape(
        lambda x: module("full").init(jax.random.PRNGKey(0), x)["params"], x)
    notes = {}
    jax.eval_shape(noting_layers(
        lambda p, x: module("flash").apply({"params": p}, x), notes),
        params, x)
    (noted,) = notes.values()
    assert noted["attn.kv_resident_bytes"] == kept


def test_the_backward_pass_keeps_the_latents_and_the_kernel_s_outputs():
    """Between the latents and ``proj`` only what a kernel wrote is a
    residual: no (B, T, H, 192) or (B, T, H, 256) tensor is saved."""
    params, x, w = problem()
    from jax._src.ad_checkpoint import saved_residuals
    saved = saved_residuals(
        lambda p: (module("flash").apply({"params": p}, x) * w).sum(), params)
    wide = [a.shape for a, _ in saved if a.ndim == 4 and a.shape[-1] in (
        192, 256)]
    assert not wide
    shapes = {a.shape for a, _ in saved}
    assert (2, T, 2 * 128) in shapes or (2, T, 2, 128) in shapes   # o
    assert (2, T, 48) in shapes and (2, T, 32) in shapes      # the latents


def test_scopes_name_the_parts():
    params, x, w = problem()
    text = jax.jit(jax.grad(
        lambda p: (module("flash").apply({"params": p}, x) * w).sum())
    ).lower(params).as_text(debug_info=True)
    for scope in ("mla/q_down", "mla/kv_down", "mla/norm", "mla/q_up",
                  "mla/kv_up", "mla/rope", "mla/attend", "mla/out"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="'flash' or 'full'"):
        module("ring").init(jax.random.PRNGKey(0), x)
