"""The flash family at values narrower than keys (latent attention's heads
of 192 = 128 | 64 against values of 128): ``flash_attention`` against the
dense oracle — output, dq, dk, dv — in both backward forms ``_plan`` can
take, the plan's rows for such a call, and every row of the table the other
calls read unchanged.  Interpreted kernels at the smallest T that tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel.ring_attention import full_attention

from test_flash_attention import PLAN_TABLE, observed


def operands(B, T, H, Hkv, D, Dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H, D)),
            jax.random.normal(ks[1], (B, T, Hkv, D)),
            jax.random.normal(ks[2], (B, T, Hkv, Dv)),
            jax.random.normal(ks[3], (B, T, H, Dv)))


# (B, T, H, Hkv, D, Dv, block, headroom): the cell's head at the smallest
# size, in the fused backward (a device that backs the budget) and in the
# per-head pair (one that does not); two query heads a KV head; values
# WIDER than keys; widths off the lane on both sides (24 and 16 are both
# padded to 128: one width by then, and the plan every other call has).
@pytest.mark.parametrize("B,T,H,Hkv,D,Dv,block,headroom", [
    (1, 64, 2, 2, 192, 128, 32, True), (1, 64, 2, 2, 192, 128, 32, False),
    (2, 64, 4, 2, 192, 128, 32, True), (1, 64, 2, 1, 192, 128, 32, False),
    (1, 64, 2, 2, 128, 256, 32, True), (1, 64, 2, 2, 24, 16, 32, True)],
    ids=["cell_fused", "cell_pair", "grouped_kv_fused", "grouped_kv_pair",
         "values_wider", "off_the_lanes"])
def test_values_narrower_than_keys_equal_full_attention(
        monkeypatch, B, T, H, Hkv, D, Dv, block, headroom):
    monkeypatch.setattr(fa._pallas, "vmem_headroom_ok", lambda: headroom)
    q, k, v, w = operands(B, T, H, Hkv, D, Dv)
    plans = []
    plan = fa._plan
    monkeypatch.setattr(
        fa, "_plan", lambda **seen: plans.append(plan(**seen)) or plans[-1])

    def flash(q, k, v):
        out = fa.flash_attention(q, k, v, block_q=block, block_k=block,
                                 interpret=True)
        return (out * w).sum(), out

    def dense(q, k, v):
        out = full_attention(q, jnp.repeat(k, H // Hkv, axis=2),
                             jnp.repeat(v, H // Hkv, axis=2), causal=True)
        return (out * w).sum(), out

    (_, out), got = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want_out), want = jax.value_and_grad(dense, (0, 1, 2),
                                             has_aux=True)(q, k, v)
    assert out.shape == (B, T, H, Dv)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=2e-4, atol=2e-4)
    if D + -D % 128 != Dv + -Dv % 128:
        assert {(p.fwd, p.bwd) for p in plans} == {
            ("grid", "group_fused" if headroom else "per_head")}


def test_what_the_widths_must_agree_in():
    q, k, v, _ = operands(1, 64, 2, 2, 192, 128)
    with pytest.raises(ValueError, match="head width 192"):
        fa.flash_attention(q, k[..., :128], v, interpret=True)
    with pytest.raises(ValueError, match="batch, length and heads"):
        fa.flash_attention(q, k, v[:, :, :1], interpret=True)
    with pytest.raises(ValueError, match="one width"):
        fa.flash_attention(q, k, v, interpret=True,
                           select=jnp.ones((1, 64, 64), jnp.int8))


# What _plan answers a call whose values are not as wide as its keys (both
# in whole 128-lane tiles by then): the grid forward, and the one kernel a
# KV group where dK (T, D) and dV (T, Dv) float32 fit 16 MiB on a device
# that backs the budget — joyaiflash_1chip's call: 12 MiB, 1024 x 1024
# tiles — else the per-head pair.
SPLIT_ROWS = {
    "cell_T8192_256_128": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128),
        ("grid", 0, 0, "group_fused", 64, 0, 0.889, (1024,) * 4)),
    "cell_no_headroom": (
        observed(8192, D=256, H=32, base=(0, 0, 0), Dv=128,
                 vmem_headroom=False),
        ("grid", 0, 0, "per_head", 0, 0, 0.889, (1024,) * 4)),
    # 16,384 x (256 + 128) x 4 = 24 MiB: the pair.
    "T16384_256_128": (
        observed(16384, D=256, H=32, base=(0, 0, 0), Dv=128),
        ("grid", 0, 0, "per_head", 0, 0, 0.941, (1024,) * 4)),
    # Short sequences too: no other forward has run at two widths.
    "T2048_256_128": (
        observed(2048, D=256, H=32, base=(0, 0, 0), Dv=128),
        ("grid", 0, 0, "group_fused", 64, 0, 0.667, (1024,) * 4)),
    "T8192_4Q_per_KV_256_128": (
        observed(8192, D=256, H=8, base=(0, 0, 0), kv_rep=4, Dv=128),
        ("grid", 0, 0, "group_fused", 64, 0, 0.889, (1024, 1024, 512, 1024))),
    "values_wider_128_256": (
        observed(8192, D=128, H=32, base=(0, 0, 0), Dv=256),
        ("grid", 0, 0, "group_fused", 64, 0, 0.889, (1024,) * 4)),
}


@pytest.mark.parametrize("case", sorted(SPLIT_ROWS))
def test_plan_rows_of_a_call_with_two_widths(case):
    seen, want = SPLIT_ROWS[case]
    assert fa._plan(**seen) == fa._Plan(*want)


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_one_width_named_twice_is_the_row_it_was(case):
    """``Dv`` equal to ``D`` is every other call's plan, to the field."""
    seen, want = PLAN_TABLE[case]
    assert fa._plan(**seen, Dv=seen["D"]) == fa._Plan(*want)
