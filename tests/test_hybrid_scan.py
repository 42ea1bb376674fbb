"""The state-space mixer of the hybrid (pattern) stack against plain
references, at small sizes on the CPU: ``ops/ssd.py``'s chunked scan against
the recurrence it computes, its kernels against their oracles, its plan's
table, and the ``Mamba2Mixer`` module against the benchmark family's plain
mixer.  (One of the four files ``test_hybrid_stack.py`` was until PR 50: a
file is one worker's job under ``--dist loadfile``.)
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.families import nemotron_h_lm, olmo_hybrid_lm
from horovod_tpu.jax.spmd import make_train_step
from horovod_tpu.metrics import registry
from horovod_tpu.models import (
    NemotronHLM, OlmoHybridLM, SwiGLU, TransformerLM)
from horovod_tpu.models.linear_attention import GatedDeltaNet
from horovod_tpu.models.ssm import Mamba2Mixer
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops import ssd
from horovod_tpu.ops.ssd import (
    scan_sizes, ssd_recurrence, ssd_scan, ssd_scan_packed)
from horovod_tpu.parallel.moe import DroplessMoE, _SharedExpert
from horovod_tpu.parallel.ring_attention import full_attention

from _once import out_and_grads
from test_gated_delta import _equations

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))



# ------------------------------------------------------------ the scan


def scan_inputs(T, b=2, H=4, P_=8, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, T, H, P_)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (b, T, G, N)),
            jax.random.normal(ks[4], (b, T, G, N)),
            jax.random.normal(ks[5], (H,)))


@pytest.mark.parametrize("T,chunk", [(37, 8), (128, 128), (200, 128)],
                         ids=["T_not_a_multiple", "one_chunk_of_128",
                              "a_chunk_and_a_tail"])
def test_chunked_scan_equals_the_recurrence(T, chunk):
    """float32 on both sides: forward to 1e-5 of the largest output and
    every gradient (x, dt, A, B, C, D) to 1e-4 of its norm (observed
    6e-5 / 14 and 8e-6)."""
    args = scan_inputs(T)
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)
    with jax.default_matmul_precision("highest"):
        (got, grads), (want, ref) = (
            out_and_grads(f, lambda y: (y * weight).sum(), *args)
            for f in (lambda *a: ssd_scan(*a, chunk=chunk), ssd_recurrence))
    assert got.shape == want.shape == args[0].shape
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    for g, w in zip(grads, ref):
        assert rel(g, w) <= 1e-4


def test_scan_sizes_and_groups():
    assert scan_sizes(2, 8192, 64, 64, 128, 128) == {
        "chunks": 128, "state_bytes": 128 * 64 * 64 * 128 * 4}
    assert scan_sizes(1, 130, 2, 4, 8, 128)["chunks"] == 2
    x, dt, A, B, C, D = scan_inputs(16, H=3, G=2)
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(x, dt, A, B, C, D, chunk=8)


# ------------------------------------------------- the scan's kernels
# (interpreted: the forward kernel, and the backward's two — the states
# entering every chunk, then the sweep from the last chunk to the first)

# (T, b, H, P, G, N), all in chunks of 128: what tiles.
KERNEL_SHAPES = {
    "one_chunk": (128, 1, 2, 64, 1, 128),
    "three_chunks_batch_2_G_lt_H": (384, 2, 4, 64, 2, 128),
    "T_not_a_multiple": (300, 1, 2, 64, 1, 128),
    "G_equals_H": (256, 1, 2, 128, 2, 128),
}


def kernel_inputs(case, dtype):
    T, b, H, P_, G, N_ = KERNEL_SHAPES[case]
    x, dt, A, B, C, D = scan_inputs(T, b=b, H=H, P_=P_, G=G, N=N_,
                                    seed=len(case))
    return (x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D)


def xla_form(x, dt, A, B, C, D):
    """``_ssd_chunked`` as ``ssd_scan`` calls it where the shape does not
    tile: the kernels' second oracle."""
    T = x.shape[1]
    x, dt, B, C = ssd._padded((x, dt, B, C), T, 128)
    return ssd._ssd_chunked(x, dt, A, B, C, D, 128)[:, :T]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_scan_kernels_equal_their_oracles(case, dtype):
    """Values and the gradients of all six inputs.  float32 against the
    recurrence: float32 rounding (observed 2.4e-6 of the norm forward,
    7e-6 the gradients, ``A``'s — a sum of terms of both signs over every
    position — 5.7e-5, the XLA form's 1.5e-4).  bfloat16 against the XLA
    form at the same precisions: the forward rounds the same tiles
    (observed 2.1e-5: a rounding flipped here and there), the backward
    rounds its cotangent operands to bfloat16 where autodiff on the CPU
    keeps them float32 (observed 3.6e-3, ``A``'s 7.5e-3; against the
    recurrence the kernels' ``dt`` and ``A`` read 1.7e-3 and 7.8e-3 where
    the XLA form's read 2.4e-3 and 7.8e-3 — the row and the column sums of
    a decay tile's cotangent cancel in the running sum, and have to be
    taken from one float32 tile: taken from a product with the rounded
    tile, ``A``'s read 0.69)."""
    args = kernel_inputs(case, dtype)
    assert ssd.scan_plan(args[0], args[1], heads=args[0].shape[2],
                         head_dim=args[0].shape[3],
                         groups=args[3].shape[2], state=128, chunk=128,
                         interpret=True).form == "kernels"
    oracle, value_tol, grad_tol, a_tol = (
        (ssd_recurrence, 1e-5, 1e-4, 2e-4) if dtype == "float32"
        else (xla_form, 2e-3, 1e-2, 2e-2))
    weight = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)

    def weighted(y):
        return (y.astype(jnp.float32) * weight).sum()

    with jax.default_matmul_precision("highest"):
        (got, grads), (want, ref) = (
            out_and_grads(f, weighted, *args)
            for f in (lambda *a: ssd_scan(*a, chunk=128, interpret=True),
                      oracle))
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert rel(got.astype(jnp.float32),
               want.astype(jnp.float32)) <= value_tol
    for i, (g, w) in enumerate(zip(grads, ref)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert rel(g.astype(jnp.float32), w.astype(jnp.float32)) <= (
            a_tol if i == 2 else grad_tol), "x dt A B C D".split()[i]


def test_packed_entry_reads_x_B_C_out_of_one_array():
    """The mixer's entry: ``x | B | C`` as the convolution leaves them,
    kernels and XLA form alike, gradient of the one array included."""
    for case, chunk, form in (("three_chunks_batch_2_G_lt_H", 128,
                               "kernels"),
                              ("T_not_a_multiple", 64, "xla")):
        x, dt, A, B, C, D = kernel_inputs(case, "float32")
        b, T, H, P_ = x.shape
        G, N_ = B.shape[2:]
        packed = jnp.concatenate([x.reshape(b, T, -1), B.reshape(b, T, -1),
                                  C.reshape(b, T, -1)], axis=-1)
        kw = dict(heads=H, groups=G, state=N_, chunk=chunk, interpret=True)
        assert ssd.scan_plan(packed, dt, head_dim=P_, **kw).form == form

        def ours(p):
            return ssd_scan_packed(p, dt, A, D, **kw)

        def split(p):
            x, B, C = jnp.split(p, [H * P_, H * P_ + G * N_], axis=-1)
            return ssd_recurrence(x.reshape(b, T, H, P_), dt, A,
                                  B.reshape(b, T, G, N_),
                                  C.reshape(b, T, G, N_), D).reshape(b, T, -1)

        with jax.default_matmul_precision("highest"):
            (out, (got,)), (ref, (want,)) = (
                out_and_grads(f, lambda y: (y ** 2).sum(), packed)
                for f in (ours, split))
        assert rel(out, ref) <= 1e-5
        assert rel(got, want) <= 1e-4
    with pytest.raises(ValueError, match="groups"):
        ssd_scan_packed(packed, dt, A, D, heads=H, groups=3, state=N_)


def seen(T=8192, H=64, P=64, G=8, N=128, chunk=128, itemsize=2,
         interpret=False, manual_axes=False, vmem_headroom=True):
    return dict(T=T, H=H, P=P, G=G, N=N, chunk=chunk, itemsize=itemsize,
                interpret=interpret, manual_axes=manual_axes,
                vmem_headroom=vmem_headroom)


KERNELS, XLA = "kernels", ("xla", (), 0, 0)
# What ``ssd._plan`` observes -> (form, (groups, chunks) a sequence, VMEM
# bytes by shapes, scoped-VMEM MB asked: 0 is Mosaic's default).
PLAN_TABLE = {
    # twotower_1chip: 8 groups of 8 heads of 64, 64 chunks a sequence.
    "cell": (seen(), (KERNELS, (8, 64), 5505024, 0)),
    "cell_float32": (seen(itemsize=4), (KERNELS, (8, 64), 6553600, 0)),
    "cell_T_not_a_multiple": (seen(T=8200), (KERNELS, (8, 65), 5505024, 0)),
    "cell_compiled_under_shard_map": (seen(manual_axes=True),
                                      (KERNELS, (8, 64), 5505024, 0)),
    "cell_no_headroom": (seen(vmem_headroom=False),
                         (KERNELS, (8, 64), 5505024, 0)),
    # Interpreted Pallas cannot run under manual mesh axes (jax 0.9.0).
    "interpreted_under_shard_map": (seen(interpret=True, manual_axes=True),
                                    XLA),
    "interpreted": (seen(T=384, H=4, G=2, itemsize=4, interpret=True),
                    (KERNELS, (2, 3), 2424832, 0)),
    "one_head_of_128_a_group": (seen(T=256, H=2, P=128, G=2),
                                (KERNELS, (2, 2), 1966080, 0)),
    # The tiny preset of the CPU tests, and every way of not tiling.
    "tiny_preset": (seen(T=64, H=4, P=16, G=2, N=16, chunk=16, itemsize=4,
                         interpret=True), XLA),
    "chunk_16": (seen(chunk=16), XLA),
    "chunk_64": (seen(chunk=64), XLA),
    "state_64": (seen(N=64), XLA),
    "head_of_96": (seen(P=96), XLA),
    "a_group_of_one_head_of_64": (seen(G=64), XLA),
    "three_heads_of_64_a_group": (seen(H=48, G=16), XLA),
    "channels_not_in_blocks_of_state": (seen(H=6, G=3, N=256), XLA),
    "groups_do_not_divide": (seen(H=64, G=7), XLA),
    # 64 heads of 64 in one group: blocks past the default budget.
    "one_group_of_4096": (seen(G=1), (KERNELS, (1, 64), 38535168, 49)),
    # ... and split into head tiles within the default budget where the
    # device has no more, or the group is wider (tests/test_ssd_wide_group.py).
    "one_group_of_4096_no_headroom": (seen(G=1, vmem_headroom=False),
                                      (KERNELS, (4, 64), 10223616, 0, 4)),
    "one_group_of_8192": (seen(H=128, G=1),
                          (KERNELS, (8, 64), 10223616, 0, 8)),
}


@pytest.mark.parametrize("case", sorted(PLAN_TABLE))
def test_scan_plan_table(case):
    """The one function that chooses kernels or the XLA form: a pure
    table, no kernel, no device."""
    observed, want = PLAN_TABLE[case]
    assert ssd._plan(**observed) == ssd.ScanPlan(*want)


def test_the_scan_has_no_knob():
    import inspect

    source = inspect.getsource(ssd)
    assert "environ" not in source and "getenv" not in source
    assert list(inspect.signature(ssd_scan).parameters) == [
        "x", "dt", "A", "B", "C", "D", "chunk", "interpret"]


def family_cfg(compute_dtype="float32", **override):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-twotower-30b-a3b.json")) as fh:
        cfg = {**json.load(fh), **nemotron_h_lm.TINY, **override}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute_dtype}
    return cfg


def mixer_and_params(cfg, T, seed=0):
    mixer = Mamba2Mixer(
        num_heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        norm_eps=cfg["layer_norm_epsilon"], dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(seed),
                          (2, T, cfg["hidden_size"]))
    params = mixer.init(jax.random.PRNGKey(seed + 1), u)["params"]
    # Move the one-initialised leaves off one, so a wrong use shows.
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 2)
    params = {**params,
              "D": 1.0 + 0.5 * jax.random.normal(keys[0], params["D"].shape),
              "gate_norm": 1.0 + 0.2 * jax.random.normal(
                  keys[1], params["gate_norm"].shape)}
    return mixer, params, u


@pytest.mark.parametrize("T,chunk", [(40, 16), (128, 128)],
                         ids=["T_not_a_multiple", "chunk_128_exactly"])
def test_mixer_module_equals_the_reference_recurrence(T, chunk):
    """``Mamba2Mixer`` (float32) against the family's plain mixer in its
    recurrence form, same parameter tree: output to 1e-5 of its largest,
    every parameter's gradient and the input's to 2e-4."""
    cfg = family_cfg(chunk_size=chunk)
    mixer, params, u = mixer_and_params(cfg, T)
    reference = nemotron_h_lm.reference_mixer(cfg, "recurrence")

    def ours(p, u):
        return mixer.apply({"params": p}, u)

    def theirs(p, u):
        return jax.vmap(lambda s: reference(p, s))(u)

    weight = jnp.sin(jnp.arange(u.size, dtype=jnp.float32)).reshape(u.shape)
    with jax.default_matmul_precision("highest"):
        (got, g), (want, w) = (
            out_and_grads(f, lambda y: (y * weight).sum(), params, u,
                          jit=True) for f in (ours, theirs))
    assert got.shape == want.shape == u.shape
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    errors = {jax.tree_util.keystr(path): rel(a, b) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(w))}
    assert max(errors.values()) <= 2e-4, errors
    assert {"['A_log']", "['dt_bias']", "['D']", "['conv']['kernel']",
            "['conv']['bias']", "['gate_norm']"} <= {
                k[3:] if k.startswith("[0]") else k for k in errors}


def test_the_two_reference_forms_agree():
    """The quadratic dual ``(L o C B^T) (dt x)``, head by head, against the
    recurrence: two independent readings of the same equations."""
    cfg = family_cfg()
    _, params, u = mixer_and_params(cfg, 48, seed=3)
    dual = nemotron_h_lm.reference_mixer(cfg, "dual")
    step = nemotron_h_lm.reference_mixer(cfg, "recurrence")
    with jax.default_matmul_precision("highest"):
        (a, (ga,)), (b, (gb,)) = (
            out_and_grads(lambda p: f(p, u[0]), lambda y: (y ** 2).sum(),
                          params, jit=True) for f in (dual, step))
    assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert rel(x, y) <= 1e-4
