"""The chunked state-space scan where ONE group of B and C spans more
heads than a grid step's blocks hold (``ops/ssd.py``; granite-4.0-h-micro:
one group over 64 heads of 64 in chunks of 256): ``_plan`` splits the
group's heads into tiles, each a grid step chain of its own that reads
the group's B and C and writes its part of ``dB`` and ``dC`` in float32,
and XLA sums a group's tiles.  The kernels run interpreted here, against
the XLA form (``_ssd_chunked``) and the recurrence; what Mosaic makes of
the same shapes is ``tests/test_mixer_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import _pallas, ssd
from horovod_tpu.ops.ssd import ssd_recurrence, ssd_scan, ssd_scan_packed


def rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def inputs(b, T, H, P, G, N, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, T, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    B = jax.random.normal(ks[3], (b, T, G, N)) / np.sqrt(N)
    C = jax.random.normal(ks[4], (b, T, G, N)) / np.sqrt(N)
    D = jax.random.normal(ks[5], (H,))
    return x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D


@pytest.fixture
def default_budget_only(monkeypatch):
    """The device backs no scoped VMEM above Mosaic's default, so that a
    group of a CPU test's size is already wider than a block."""
    monkeypatch.setattr(_pallas, "vmem_headroom_ok", lambda: False)
    # The drivers are jitted on their static arguments alone.
    jax.clear_caches()
    yield
    jax.clear_caches()


# name -> (b, T, H, P, G, N, chunk, dtype, tiles the plan must choose).
WIDE = {
    "one_group_16_heads_chunk_256_float32_4_tiles":
        (1, 512, 16, 64, 1, 128, 256, "float32", 4),
    "one_group_16_heads_chunk_256_bfloat16_2_tiles":
        (1, 512, 16, 64, 1, 128, 256, "bfloat16", 2),
    "two_groups_of_32_heads_batch_2_chunk_128_T_off_the_chunk":
        (2, 200, 64, 64, 2, 128, 128, "float32", 2),
}


@pytest.mark.parametrize("case", sorted(WIDE))
def test_a_group_in_head_tiles_equals_the_xla_form(case, default_budget_only):
    """Values and the gradients of all six inputs, ``dB`` and ``dC``
    summed over a group's head tiles.  float32 against the recurrence and
    the XLA form: float32 rounding (observed 6e-6 of the norm forward, at
    most 3e-7 on ``dx``, ``dB``, ``dC``, 1.2e-6 on ``dt`` and ``D``, 1.5e-5
    on ``A``, a sum of terms of both signs over every position).
    bfloat16 against the XLA form at the same precisions, as
    ``test_hybrid_scan.py`` holds the one-tile kernels: the backward
    rounds its cotangent operands to bfloat16 where autodiff on the CPU
    keeps them float32."""
    b, T, H, P, G, N, chunk, dtype, tiles = WIDE[case]
    args = inputs(b, T, H, P, G, N, dtype, seed=len(case))
    plan = ssd.scan_plan(args[0], args[1], heads=H, head_dim=P, groups=G,
                         state=N, chunk=chunk, interpret=True)
    assert (plan.form, plan.tiles) == ("kernels", tiles), plan
    assert plan.grid == (G * tiles, -(-T // chunk)) and plan.vmem_mb == 0

    def xla_form(x, dt, A, B, C, D):
        x, dt, B, C = ssd._padded((x, dt, B, C), T, chunk)
        return ssd._ssd_chunked(x, dt, A, B, C, D, chunk)[:, :T]

    def kernels(*a):
        return ssd_scan(*a, chunk=chunk, interpret=True)

    value_tol, grad_tol, a_tol = ((1e-5, 1e-4, 2e-4) if dtype == "float32"
                                  else (2e-3, 1e-2, 2e-2))
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)

    def loss(f):
        return lambda *a: (f(*a).astype(jnp.float32) * weight).sum()

    with jax.default_matmul_precision("highest"):
        got = kernels(*args)
        want = xla_form(*args)
        assert got.shape == want.shape and got.dtype == args[0].dtype
        assert rel(got, want) <= value_tol
        if dtype == "float32":
            assert rel(got, ssd_recurrence(*args)) <= value_tol
        grads = [jax.grad(loss(f), argnums=tuple(range(6)))(*args)
                 for f in (kernels, xla_form)]
    for i, (g, w) in enumerate(zip(*grads)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert rel(g, w) <= (a_tol if i == 2 else grad_tol), (
            "x dt A B C D".split()[i])


def test_tiles_read_their_group_s_B_and_C_not_their_own(default_budget_only):
    """Two groups of two tiles each: tile ``v`` reads B and C of group
    ``v // tiles``.  With B zero in the SECOND group only, the first
    group's heads keep their scan and the second group's heads reduce to
    the skip ``D x`` — an index map that sent tile 1 to group 1 would zero
    the wrong heads."""
    b, T, H, P, G, N = 1, 128, 64, 64, 2, 128
    x, dt, A, B, C, D = inputs(b, T, H, P, G, N, "float32", seed=7)
    assert ssd.scan_plan(x, dt, heads=H, head_dim=P, groups=G, state=N,
                         chunk=128, interpret=True).tiles == 2
    B = B.at[:, :, 1].set(0.0)
    y = ssd_scan(x, dt, A, B, C, D, chunk=128, interpret=True)
    half = H // 2
    assert rel(y[:, :, half:], D[half:, None] * x[:, :, half:]) <= 1e-6
    want = ssd_recurrence(x, dt, A, B, C, D)
    assert rel(y[:, :, :half], want[:, :, :half]) <= 1e-5
    assert float(jnp.abs(y[:, :, :half] - D[:half, None]
                         * x[:, :, :half]).max()) > 1e-2


def test_packed_entry_with_head_tiles(default_budget_only):
    """The mixer's entry at one group: ``x | B | C`` as one array, the
    gradient of the one array included (``dB``, ``dC`` behind ``dx``)."""
    b, T, H, P, G, N, chunk = 1, 256, 16, 64, 1, 128, 256
    x, dt, A, B, C, D = inputs(b, T, H, P, G, N, "float32", seed=3)
    packed = jnp.concatenate([x.reshape(b, T, -1), B.reshape(b, T, -1),
                              C.reshape(b, T, -1)], axis=-1)
    kw = dict(heads=H, groups=G, state=N, chunk=chunk, interpret=True)
    assert ssd.scan_plan(packed, dt, head_dim=P, **kw).tiles == 4

    def ours(p):
        return ssd_scan_packed(p, dt, A, D, **kw)

    def split(p):
        x, B, C = jnp.split(p, [H * P, H * P + G * N], axis=-1)
        return ssd_recurrence(x.reshape(b, T, H, P), dt, A,
                              B.reshape(b, T, G, N), C.reshape(b, T, G, N),
                              D).reshape(b, T, -1)

    with jax.default_matmul_precision("highest"):
        assert rel(ours(packed), split(packed)) <= 1e-5
        got = jax.grad(lambda p: (ours(p) ** 2).sum())(packed)
        want = jax.grad(lambda p: (split(p) ** 2).sum())(packed)
    assert got.shape == packed.shape
    for name, cols in (("dx", slice(0, H * P)),
                       ("dB", slice(H * P, H * P + N)),
                       ("dC", slice(H * P + N, None))):
        assert rel(got[..., cols], want[..., cols]) <= 1e-4, name


def test_the_tiles_parts_of_dB_and_dC_are_float32_in_the_traced_program(
        default_budget_only):
    """A group's tiles each write their part of ``dB`` and ``dC``; the
    parts are float32 whatever the operands' dtype, so that their sum
    rounds once (a group a grid step sums its heads in the kernel and
    writes the operands' dtype)."""
    b, T, H, P, G, N, chunk = 1, 256, 16, 64, 1, 128, 256
    args = inputs(b, T, H, P, G, N, "bfloat16")

    def loss(*a):
        return ssd_scan(*a, chunk=chunk, interpret=True).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(3, 4)))(*args))
    tiles = ssd.scan_plan(args[0], args[1], heads=H, head_dim=P, groups=G,
                          state=N, chunk=chunk, interpret=True).tiles
    assert tiles == 2
    assert text.count(f"f32[{b},{T},{tiles * N}]") >= 2
    assert f"bf16[{b},{T},{tiles * N}]" not in text


def seen(T=8192, H=64, P=64, G=1, N=128, chunk=256, itemsize=2,
         interpret=False, manual_axes=False, vmem_headroom=True):
    return dict(T=T, H=H, P=P, G=G, N=N, chunk=chunk, itemsize=itemsize,
                interpret=interpret, manual_axes=manual_axes,
                vmem_headroom=vmem_headroom)


KERNELS, XLA = "kernels", ("xla", (), 0, 0)
# What ``ssd._plan`` observes -> (form, (head tiles, chunks) a sequence,
# VMEM bytes by shapes, scoped-VMEM MB asked, tiles a group).
PLAN_TABLE = {
    # twotower_1chip: the plan it had at the parent, field for field.
    "twotower_cell": (seen(G=8, chunk=128), (KERNELS, (8, 64), 5505024, 0)),
    # granitehmicro_1chip: T 8192, 64 heads of 64, ONE group, state 128,
    # chunks of 256, bfloat16.  A group a step would ask 92 MB.
    "granite_cell": (seen(), (KERNELS, (8, 32), 11272192, 0, 8)),
    "granite_cell_no_headroom": (seen(vmem_headroom=False),
                                 (KERNELS, (8, 32), 11272192, 0, 8)),
    "granite_cell_float32": (seen(itemsize=4),
                             (KERNELS, (16, 32), 8257536, 0, 16)),
    "granite_cell_T_not_a_multiple": (seen(T=8200),
                                      (KERNELS, (8, 33), 11272192, 0, 8)),
    "granite_cell_compiled_under_shard_map": (
        seen(manual_axes=True), (KERNELS, (8, 32), 11272192, 0, 8)),
    "granite_cell_interpreted_under_shard_map": (
        seen(interpret=True, manual_axes=True), XLA),
    "granite_cell_state_256": (seen(N=256),
                               (KERNELS, (8, 32), 12582912, 0, 8)),
    # A group whose blocks fit a device's head-room stays a grid step.
    "two_groups_of_32_heads_chunk_256": (seen(G=2),
                                         (KERNELS, (2, 32), 37224448, 48)),
    "one_group_chunk_128": (seen(chunk=128),
                            (KERNELS, (1, 64), 38535168, 49)),
    # ... and is split where the device has none, or the group is wider.
    "one_group_chunk_128_no_headroom": (seen(chunk=128, vmem_headroom=False),
                                        (KERNELS, (4, 64), 10223616, 0, 4)),
    "one_group_of_128_heads_chunk_128": (seen(H=128, chunk=128),
                                         (KERNELS, (8, 64), 10223616, 0, 8)),
    "one_group_of_66_heads_in_11_tiles": (seen(H=66),
                                          (KERNELS, (11, 32), 9109504, 0, 11)),
    # Two heads of 2,048 channels: one head alone is past the budget and a
    # head is not split.
    "heads_wider_than_a_block": (seen(H=2, P=2048), XLA),
    # The interpreted test shape above.
    "interpreted_16_heads_float32": (
        seen(T=512, H=16, itemsize=4, interpret=True, vmem_headroom=False),
        (KERNELS, (4, 2), 8257536, 0, 4)),
}


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_scan_plan_table_with_head_tiles(case):
    """The one function that chooses: a pure table, no kernel, no device.
    The published Granite shape gets kernels within ``_MOST_VMEM`` (within
    Mosaic's default: ``vmem_mb`` 0), twotower's the plan it had."""
    observed, want = PLAN_TABLE[case]
    plan = ssd._plan(**observed)
    assert plan == ssd.ScanPlan(*want)
    assert plan.vmem_mb * 2 ** 20 <= ssd._MOST_VMEM
    if plan.form == "kernels":
        assert plan.grid[0] == observed["G"] * plan.tiles
        heads = observed["H"] // plan.grid[0]
        assert heads * observed["P"] % 128 == 0
