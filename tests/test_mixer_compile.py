"""The mixers' kernels compiled for a described TPU v5e (see
``tests/_v5e.py``): the chunked scan, the convolution and gated norm, the
latent passes of compressed convolutional attention and the chunked delta
rule, at the cells' shapes and at every tiling their plans take.  The
interpreted tests of the same kernels are ``test_hybrid_scan.py``,
``test_ssd_wide_group.py``, ``test_mixer_passes.py``, ``test_cca_passes.py``
and ``test_gated_delta.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _v5e import compile_text, v5e  # noqa: F401


def test_chunked_scan_fwd_bwd_at_nemotron_widths(v5e):
    """``ssd_scan_packed`` as the mixer calls it — 2 sequences of 8,192, 64
    heads of 64, 8 groups, state 128, chunks of 128, x | B | C as the
    convolution's one array, under a ``jax.checkpoint`` — with the kernels
    asked for compiled.  The value and its gradients are three kernels
    (``ssd_fwd``; ``ssd_states`` and ``ssd_bwd``: the forward replayed by
    the checkpoint leaves none, nothing reads its ``y``); nothing copies
    or transposes a (2, 8192, ...) bfloat16 array on its way in or out —
    only ``dt`` (4 MB, float32) is turned time-minor —; and the plan is
    0.76 GiB where the XLA form's, with its chunk-square tiles and its
    chunk states in HBM, is 1.73."""
    import re

    from horovod_tpu.ops import ssd

    one = SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    b, t, h, p, g, n = 2, 8192, 64, 64, 8, 128
    args = (s((b, t, h * p + 2 * g * n)), s((b, t, h), jnp.float32),
            s((h,), jnp.float32), s((h,), jnp.float32))
    assert ssd.scan_plan(*args[:2], heads=h, head_dim=p, groups=g, state=n,
                         chunk=128, interpret=False) == ssd.ScanPlan(
                             "kernels", (8, 64), 5505024, 0)

    @jax.checkpoint
    def loss(*a):
        return ssd.ssd_scan_packed(*a, heads=h, groups=g, state=n,
                                   chunk=128).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
                       ).lower(*args).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3
    for name in ("ssd_fwd", "ssd_states", "ssd_bwd"):
        assert sum(name in line.split(" = ")[0] for line in kernels) == 1
    moved = [line for line in text.splitlines()
             if re.search(r"= bf16\[2,8192,\d+\]\S* (copy|transpose)\(", line)]
    assert not moved, moved
    _, (dxbc, ddt, dA, dD) = compiled.out_info
    assert dxbc.shape == args[0].shape and dxbc.dtype == jnp.bfloat16
    assert (ddt.shape, dA.shape, dD.shape) == ((b, t, h), (h,), (h,))
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 1.0 * 2 ** 30, plan / 2 ** 30


# (b, T, H, P, G, N, chunk, dtype): what else ``ssd._plan`` hands to the
# kernels, one case a way of tiling — a head of 128 alone in its group,
# two heads of 64 to a tile, a head wider than a tile, float32 operands at
# the cell's shape, a wider state, a longer chunk; and one group over 64
# heads in chunks of 256 (``granitehmicro_1chip``: the group's heads in 8
# tiles a grid step each; 16 tiles of float32 operands).
@pytest.mark.parametrize("b,t,h,p,g,n,chunk,dtype", [
    (2, 1024, 2, 128, 2, 128, 128, "bfloat16"),
    (2, 1024, 4, 64, 2, 128, 128, "bfloat16"),
    (1, 1024, 4, 256, 2, 128, 128, "bfloat16"),
    (2, 8192, 64, 64, 8, 128, 128, "float32"),
    (1, 1024, 16, 64, 2, 256, 128, "bfloat16"),
    (1, 1024, 16, 64, 2, 128, 256, "bfloat16"),
    (1, 8192, 64, 64, 1, 128, 256, "bfloat16"),
    (1, 1024, 64, 64, 1, 128, 256, "float32")],
    ids=["one_head_of_128_a_group", "two_heads_of_64_a_group",
         "heads_of_256", "cell_float32", "state_256", "chunk_256",
         "one_group_of_64_heads_in_8_tiles",
         "one_group_of_64_heads_float32_in_16_tiles"])
def test_chunked_scan_compiles_wherever_the_plan_takes_the_kernels(
        v5e, b, t, h, p, g, n, chunk, dtype):
    """A shape ``_plan`` gives the kernels has to compile: interpret mode
    refuses nothing of what Mosaic refuses (a (1, 1) value broadcast over
    a tile was refused at one head a group)."""
    from horovod_tpu.ops import ssd

    one = SingleDeviceSharding(v5e[0])
    args = tuple(jax.ShapeDtypeStruct(shape, kind, sharding=one)
                 for shape, kind in (((b, t, h * p + 2 * g * n), dtype),
                                     ((b, t, h), "float32"),
                                     ((h,), "float32"), ((h,), "float32")))
    assert ssd.scan_plan(*args[:2], heads=h, head_dim=p, groups=g, state=n,
                         chunk=chunk, interpret=False).form == "kernels"

    @jax.checkpoint
    def loss(*a):
        return ssd.ssd_scan_packed(*a, heads=h, groups=g, state=n,
                                   chunk=chunk).astype(jnp.float32).sum()

    text = compile_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                        *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def passes_value_and_grads(args, *, inner, groups, plan):
    """The two passes as the mixer holds them — the convolution under a
    ``jax.checkpoint``, the gate on its own — compiled."""
    from horovod_tpu.ops import mixer_passes

    @jax.checkpoint
    def conv(packed, w, b):
        return mixer_passes.conv_silu(packed, w, b, first=inner, plan=plan)

    def loss(packed, w, b, y, scale):
        gated = mixer_passes.gated_norm(y, packed, scale, groups=groups,
                                        eps=1e-5, plan=plan)
        return (conv(packed, w, b).astype(jnp.float32).sum()
                + gated.astype(jnp.float32).sum())

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()


def passes_shapes(one, b, t, inner, bc, heads, dtype, taps=4):
    conv_dim = inner + bc
    width = inner + conv_dim + heads

    def s(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=one)

    return (s((b, t, width + -width % 128), dtype),
            s((taps, conv_dim), "float32"), s((conv_dim,), "float32"),
            s((b, t, inner), dtype), s((inner,), "float32"))


def test_mixer_passes_fwd_bwd_at_nemotron_widths(v5e):
    """The mixer's convolution and gated norm at the cell's shape — 2
    sequences of 8,192, 4,096 channels in 8 norm groups, 6,144 convolved
    by 4 taps, both read out of the projection's [z | xBC | dt] padded to
    10,368 columns — with the kernels asked for compiled.  Four kernels by
    name (no scan reads the checkpoint's replay here, so it leaves none;
    the backward kernels recompute from the inputs); nothing copies or
    transposes a
    (2, 8192, ...) bfloat16 array around them — the cotangents reach the
    packed array's columns through two ``pad``s that XLA sums as it writes
    them —; the parameters' gradients are float32."""
    import re

    from horovod_tpu.ops import mixer_passes

    one = SingleDeviceSharding(v5e[0])
    args = passes_shapes(one, 2, 8192, 4096, 2048, 64, "bfloat16")
    assert args[0].shape == (2, 8192, 10368)
    plan = mixer_passes.passes_plan(args[0], inner=4096, conv_dim=6144,
                                    groups=8, kernel=4, interpret=False)
    assert plan == mixer_passes.PassPlan("kernels", 1024, 32, 512, 512)
    compiled = passes_value_and_grads(args, inner=4096, groups=8, plan=plan)
    text = compiled.as_text()
    kernels = [line.split(" = ")[0] for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 4, kernels
    for name in ("ssm_conv_fwd", "ssm_conv_bwd", "ssm_gate_fwd",
                 "ssm_gate_bwd"):
        assert sum(name in k for k in kernels) == 1, (name, kernels)
    moved = [line for line in text.splitlines()
             if re.search(r"= bf16\[2,8192,\d+\]\S* (copy|transpose)\(", line)]
    assert not moved, moved
    _, (dpacked, dw, db, dy, dscale) = compiled.out_info
    assert (dpacked.shape, dpacked.dtype) == (args[0].shape, jnp.bfloat16)
    assert (dy.shape, dy.dtype) == (args[3].shape, jnp.bfloat16)
    assert dw.dtype == db.dtype == dscale.dtype == jnp.float32


# (b, T, inner, B | C columns, heads, norm groups, taps, dtype): what else
# ``mixer_passes._plan`` hands to the kernels, one case a way of tiling —
# float32 activations at the cell's shape (blocks of 512 rows), four norm
# groups of 128 to a block, an odd count of groups of 256, channels that
# only tile by 128, a sequence shorter than a block, one that ends inside
# a block, two taps; and ONE norm group over all 4,096 channels
# (``granitehmicro_1chip``: gate blocks of 128 rows, the row's sums
# gathered 512 channels at a time), in float32, and ending inside a block.
@pytest.mark.parametrize("b,t,inner,bc,heads,groups,taps,dtype", [
    (2, 8192, 4096, 2048, 64, 8, 4, "float32"),
    (1, 2048, 4096, 2048, 64, 32, 4, "bfloat16"),
    (1, 2048, 768, 256, 12, 3, 4, "bfloat16"),
    (1, 2048, 384, 256, 6, 3, 4, "bfloat16"),
    (2, 64, 256, 128, 4, 2, 4, "bfloat16"),
    (2, 1056, 1024, 256, 16, 2, 4, "bfloat16"),
    (1, 2048, 1024, 256, 16, 2, 2, "bfloat16"),
    (1, 8192, 4096, 256, 64, 1, 4, "bfloat16"),
    (1, 1024, 4096, 256, 64, 1, 4, "float32"),
    (2, 1056, 1024, 256, 16, 1, 4, "bfloat16")],
    ids=["cell_float32", "groups_of_128", "three_groups_of_256",
         "channels_in_tiles_of_128", "shorter_than_a_block",
         "ends_inside_a_block", "two_taps", "one_group_of_4096",
         "one_group_of_4096_float32",
         "one_group_of_1024_ends_inside_a_block"])
def test_mixer_passes_compile_wherever_the_plan_takes_the_kernels(
        v5e, b, t, inner, bc, heads, groups, taps, dtype):
    """A shape ``_plan`` gives the kernels has to compile: interpret mode
    refuses nothing of what Mosaic refuses."""
    from horovod_tpu.ops import mixer_passes

    one = SingleDeviceSharding(v5e[0])
    args = passes_shapes(one, b, t, inner, bc, heads, dtype, taps)
    plan = mixer_passes.passes_plan(args[0], inner=inner,
                                    conv_dim=inner + bc, groups=groups,
                                    kernel=taps, interpret=False)
    assert plan.form == "kernels", plan
    text = passes_value_and_grads(args, inner=inner, groups=groups,
                                  plan=plan).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4


def test_chunked_delta_rule_fwd_bwd_at_olmo_hybrid_widths(v5e):
    """``gated_delta_rule`` as the mixer calls it — 1 sequence of 8,192, 30
    heads, keys 96 and values 192 wide, chunks of 64, under a
    ``jax.checkpoint`` — compiles for the chip as plain XLA (no custom
    call), the one sequential part a ``while`` of 128 steps each way.
    Alone, with nothing else wanting the memory, it plans 2.94 GiB —
    float32 (64, 64) tiles of 63 MB each, 360 MB of padded float32 states
    entering the chunks — of the 5 the cell's step has for temporaries: a
    fused kernel's second measure, beside ``delta_roofline``."""
    from horovod_tpu.ops.gated_delta import gated_delta_rule

    one = SingleDeviceSharding(v5e[0])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    b, t, h, dk, dv = 1, 8192, 30, 96, 192
    args = (s((b, t, h, dk)), s((b, t, h, dk)), s((b, t, h, dv)),
            s((b, t, h), jnp.float32), s((b, t, h), jnp.float32))

    @jax.checkpoint
    def loss(*a):
        return gated_delta_rule(*a, chunk=64).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert text.count(" while(") == 3          # forward, replayed, backward
    _, grads = compiled.out_info
    assert [g.shape for g in grads] == [a.shape for a in args]
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 3.25 * 2 ** 30, plan / 2 ** 30


# (b, T, query heads, KV heads, head_dim, taps) -> rows a block, rows a
# strip: what ``cca_passes._plan`` hands to the kernels, one case a way of
# tiling — the cell's shape, two sequences, a sequence of three strips of
# 128 in one block, blocks of three strips of 256, 43 strips of 16 a block,
# one KV group of eight query heads (nine heads a step: half the rows),
# heads of two lane tiles, and taps that reach as far as the halo's kept
# rows.
@pytest.mark.parametrize("b,t,h,g,d,taps,rows,strip", [
    (1, 16_384, 8, 2, 128, (2, 2), 1024, 512),
    (2, 2048, 8, 2, 128, (2, 2), 1024, 512),
    (1, 384, 8, 2, 128, (2, 2), 384, 128),
    (1, 2304, 8, 2, 128, (2, 2), 768, 256),
    (1, 2064, 8, 2, 128, (2, 2), 688, 16),
    (1, 2048, 8, 1, 128, (2, 2), 512, 512),
    (1, 2048, 4, 2, 256, (2, 2), 512, 512),
    (1, 2048, 4, 2, 128, (5, 5), 1024, 512)],
    ids=["zaya1_1chip", "two_sequences", "three_strips_of_128_one_block",
         "blocks_of_three_strips_of_256", "strips_of_16",
         "one_group_of_eight", "heads_of_256", "taps_as_far_as_the_halo"])
def test_cca_passes_compile_wherever_the_plan_takes_the_kernels(
        v5e, b, t, h, g, d, taps, rows, strip):
    """A shape ``cca_passes._plan`` gives the kernels has to compile,
    forward and backward: interpret mode refuses nothing of what Mosaic
    refuses.  Two kernels by name; the parameters' gradients float32, the
    latents' in their dtype."""
    from horovod_tpu.ops import cca_passes

    one = SingleDeviceSharding(v5e[0])

    def s(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (s((b, t, h, d), "bfloat16"), s((b, t, g, d), "bfloat16"),
            s(((h + g) * d, taps[0])), s(((h + g) * d,)),
            s((h + g, taps[1], d, d)), s((h + g, d)), s((g,)))
    plan = cca_passes.cca_plan(args[0], kv_heads=g, taps=taps,
                               interpret=False)
    assert plan == cca_passes.CcaPlan("kernels", rows, strip)

    def loss(*a):
        q, k = cca_passes.cca_mix(*a, rope_theta=5e6, rotary_width=d // 2,
                                  plan=plan)
        return q.astype(jnp.float32).sum() + k.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(7)))).lower(*args).compile()
    kernels = [line.split(" = ")[0] for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2, kernels
    assert sum("cca_mix_fwd" in k for k in kernels) == 1, kernels
    assert sum("cca_mix_bwd" in k for k in kernels) == 1, kernels
    _, grads = compiled.out_info
    assert [(x.shape, x.dtype) for x in grads] == [
        (a.shape, a.dtype) for a in args]
