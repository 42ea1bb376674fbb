"""The hybrid (pattern) stack and its three kinds of layer against plain
references, at small sizes on the CPU.

* ``ops/ssd.py``'s chunked scan against the recurrence it computes, and
  the ``Mamba2Mixer`` module against the benchmark family's plain mixer;
* the flash kernels with grouped KV heads against the dense oracle with
  the keys and values repeated;
* ``DroplessMoE`` told which experts it holds: the shares add up to the
  uncut layer, and a skewed router loses no assignment;
* the nine-layer tiny preset through ``make_train_step`` against the
  family's ``reference_loss``, with its trace scopes and counters;
* the layers of two sub-layers (``L``: a Gated DeltaNet mixer, ``F``: full
  attention with QK-norm; a SwiGLU MLP after each, the norm on every
  sub-layer's output) against the ``olmo_hybrid_lm`` family's plain
  reference, whose delta rule is the token-by-token recurrence.
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
from horovod_tpu.parallel.moe import (
    _HELD_WINDOW, DroplessMoE, _SharedExpert)
from horovod_tpu.parallel.ring_attention import full_attention

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
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk=chunk)
        want = ssd_recurrence(*args)
        assert got.shape == want.shape == args[0].shape
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())
        weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        grads = [jax.grad(lambda *a: (f(*a) * weight).sum(),
                          argnums=tuple(range(6)))(*args)
                 for f in (lambda *a: ssd_scan(*a, chunk=chunk),
                           ssd_recurrence)]
    for g, w in zip(*grads):
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

    def loss(f):
        return lambda *a: (f(*a).astype(jnp.float32) * weight).sum()

    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk=128, interpret=True)
        want = oracle(*args)
        assert got.shape == want.shape and got.dtype == args[0].dtype
        assert rel(got.astype(jnp.float32),
                   want.astype(jnp.float32)) <= value_tol
        grads = [jax.grad(loss(f), argnums=tuple(range(6)))(*args)
                 for f in (lambda *a: ssd_scan(*a, chunk=128,
                                               interpret=True), oracle)]
    for i, (g, w) in enumerate(zip(*grads)):
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
            assert rel(ours(packed), split(packed)) <= 1e-5
            got = jax.grad(lambda p: (ours(p) ** 2).sum())(packed)
            want = jax.grad(lambda p: (split(p) ** 2).sum())(packed)
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

    with jax.default_matmul_precision("highest"):
        got, want = ours(params, u), theirs(params, u)
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())
        weight = jnp.sin(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        g = jax.grad(lambda p, u: (ours(p, u) * weight).sum(), (0, 1))(
            params, u)
        w = jax.grad(lambda p, u: (theirs(p, u) * weight).sum(), (0, 1))(
            params, u)
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
        a, b = dual(params, u[0]), step(params, u[0])
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max())
        ga = jax.grad(lambda p: (dual(p, u[0]) ** 2).sum())(params)
        gb = jax.grad(lambda p: (step(p, u[0]) ** 2).sum())(params)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert rel(x, y) <= 1e-4


# ------------------------------------------------ grouped-query attention


# (B, T, H, Hkv, D, block): the fully-unrolled forward; 17 KV blocks, so
# the grid forward; a head off the lane width (repeated, then merged into
# the batch); and multi-head attention through the same entry.
@pytest.mark.parametrize("B,T,H,Hkv,D,block", [
    (1, 256, 4, 2, 128, 128), (2, 256, 4, 1, 128, 64),
    (1, 1088, 2, 1, 128, 64), (1, 64, 4, 2, 32, 32),
    (1, 256, 2, 2, 128, 128)],
    ids=["16Q_per_KV_shape_small", "one_KV_head", "grid_forward", "D32",
         "multi_head"])
def test_grouped_kv_flash_equals_full_attention(B, T, H, Hkv, D, block):
    """Forward, dQ, and dK / dV summed over the query heads of a group,
    against ``full_attention`` on keys and values repeated H / Hkv times."""
    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    weight = jax.random.normal(ks[3], (B, T, H, D))

    def ours(q, k, v):
        return (flash_attention(q, k, v, block_q=block, block_k=block,
                                interpret=True) * weight).sum()

    def oracle(q, k, v):
        rep = H // Hkv
        return (full_attention(q, jnp.repeat(k, rep, 2),
                               jnp.repeat(v, rep, 2), causal=True)
                * weight).sum()

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(ours, (0, 1, 2))(q, k, v)
        want, want_g = jax.value_and_grad(oracle, (0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) <= 1e-3
    for g, w in zip(got_g, want_g):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5)


def test_grouped_kv_heads_must_divide():
    q = jnp.zeros((1, 64, 3, 128))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], interpret=True)


# ---------------------------------------------------------- held experts

N, D_, HID, E, K = 96, 16, 24, 16, 3
NEMOTRON = dict(num_experts=E, hidden=HID, top_k=K, dtype=jnp.float32,
                router="sigmoid", renormalize=True, gate_scale=2.5,
                activation="relu2", shared_hidden=40)


def uncut_layer(skewed: bool, experts: int = E):
    layer = DroplessMoE(**{**NEMOTRON, "num_experts": experts})
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D_))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    if skewed:
        # Experts 0 and 1 are in every token's top-3, whatever the token.
        x = x.at[:, 0].set(3.0)
        kernel = params["router"]["kernel"].at[0, :2].set(10.0)
        params = {**params, "router": {"kernel": kernel}}
    return layer, params, x


def share_of(params, first, count):
    return {**params, "w_up": params["w_up"][first:first + count],
            "w_down": params["w_down"][first:first + count]}


def nemotron_oracle(params, x):
    """Every expert on every token, weighted by the top-k mask."""
    E = params["router"]["kernel"].shape[1]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ params["router"]["kernel"])
        kth = jnp.sort(s, axis=-1)[:, -K]
        gates = jnp.where(s >= kth[:, None], s, 0.0)
        gates = 2.5 * gates / gates.sum(-1, keepdims=True)
        out = jnp.square(jax.nn.relu(x @ params["shared"]["w_up"])) @ params[
            "shared"]["w_down"]
        for e in range(E):
            out = out + gates[:, e:e + 1] * (jnp.square(jax.nn.relu(
                x @ params["w_up"][e])) @ params["w_down"][e])
    return out


@pytest.mark.parametrize("skewed,experts", [
    (False, 16), (True, 16), (True, 32)],
    ids=["balanced", "two_experts_take_every_token_two_windows",
         "two_of_32_take_every_token_every_window"])
def test_the_shares_add_up_to_the_uncut_layer(skewed, experts):
    """Shares of two experts each: what they give, with the shared expert
    (every share computes it alike) counted once, is the uncut layer's
    output, which is the loop over all experts.  The counts of
    assignments that landed on the shares add up to k N — none is lost.
    In the skewed cases share 0 takes 2 N = 192 of them: of 16 experts
    that is over its window of ``_HELD_WINDOW`` times the uniform load
    (108 rows) and inside two, of 32 (54 rows) over two windows, so the
    ``overflowed`` loop runs: one further window filled, then three."""
    whole, params, x = uncut_layer(skewed, experts)
    shares = experts // 2
    with jax.default_matmul_precision("highest"):
        want, _, _ = whole.apply({"params": params}, x)
        np.testing.assert_allclose(want, nemotron_oracle(params, x),
                                   rtol=1e-5, atol=1e-5)
        parts, landed = [], []
        for i in range(shares):
            layer = DroplessMoE(**{**NEMOTRON, "num_experts": experts},
                                held=(2 * i, 2))
            (out, _, _), state = layer.apply(
                {"params": share_of(params, 2 * i, 2)}, x,
                mutable=["intermediates"])
            parts.append(out)
            landed.append(int(state["intermediates"]["held_assignments"][0]))
        shared = _SharedExpert(40, jnp.float32).apply(
            {"params": params["shared"]}, x)
    # The shared expert's output is in the sum once a share and is taken
    # out again all but once: with 16 shares that costs float32 a digit.
    np.testing.assert_allclose(sum(parts) - (shares - 1) * shared, want,
                               rtol=5e-5, atol=5e-5)
    assert sum(landed) == N * K
    if skewed:
        window = _HELD_WINDOW * N * K * 2 // experts
        assert landed[0] == 2 * N > window
        assert (2 * N > 2 * window) == (experts == 32)


@pytest.mark.parametrize("experts", [16, 32],
                         ids=["second_window", "every_window"])
def test_a_share_s_gradients_equal_the_masked_loop_s(experts):
    """One share under the skewed router (the overflow loop): gradients
    of its own experts, the router, the shared expert and the input
    against the oracle restricted to the held experts."""
    E = experts
    _, params, x = uncut_layer(True, experts)
    layer = DroplessMoE(**{**NEMOTRON, "num_experts": experts}, held=(0, 2))
    mine = share_of(params, 0, 2)

    def ours(p, x):
        out = layer.apply({"params": p}, x)[0]
        return (out * jnp.cos(out)).sum()

    def oracle(p, x):
        zeros = jnp.zeros((E - 2,) + p["w_up"].shape[1:])
        full = {**p, "w_up": jnp.concatenate([p["w_up"], zeros]),
                "w_down": jnp.concatenate(
                    [p["w_down"], zeros.transpose(0, 2, 1)])}
        out = nemotron_oracle(full, x)
        return (out * jnp.cos(out)).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, (0, 1))(mine, x)
        want = jax.grad(oracle, (0, 1))(mine, x)
    # Leaves of up to 100 in size, summed in another order: 2e-4 of that.
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_a_share_traces_under_shard_map_with_vma_checks():
    """Tokens split over the data-parallel axis, the share replicated: the
    windows' zeros and the scan's carry vary as the tokens do."""
    _, params, x = uncut_layer(True)
    layer = DroplessMoE(**NEMOTRON, held=(0, 2))
    mine = share_of(params, 0, 2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("ranks",))
    out = jax.jit(shard_map(
        lambda p, x: layer.apply({"params": p}, x)[0], mesh=mesh,
        in_specs=(P(), P("ranks")), out_specs=P("ranks"),
        check_vma=True))(mine, x)
    halves = [layer.apply({"params": mine}, h)[0]
              for h in (x[:N // 2], x[N // 2:])]
    np.testing.assert_allclose(out, jnp.concatenate(halves), rtol=1e-5,
                               atol=1e-5)


def test_held_must_be_a_range_of_the_experts():
    _, params, x = uncut_layer(False)
    with pytest.raises(ValueError, match="held"):
        DroplessMoE(**NEMOTRON, held=(14, 4)).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="router"):
        DroplessMoE(**{**NEMOTRON, "router": "tanh"}).init(
            jax.random.PRNGKey(0), x)


# ------------------------------------------------------- the whole model


def model_inputs(cfg, n=2, seed=5):
    params, aux = nemotron_h_lm.init(cfg, jax.random.PRNGKey(seed))
    tokens = nemotron_h_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


# float32 compute: the routers agree exactly and every leaf of the
# gradient is the reference's to summation order.  bfloat16 compute on 128
# tokens of a 64-wide model: the tiny preset's own, looser tolerances
# (the reference breaks near-ties as the program did, so the routers'
# differing choices no longer set the floor: 0.1-0.4 a leaf without).
@pytest.mark.parametrize("compute_dtype,loss_tol,grad_tol", [
    ("float32", 1e-5, 2e-4), ("bfloat16", 5e-3, 0.2)])
def test_model_against_reference_loss(compute_dtype, loss_tol, grad_tol,
                                      capsys):
    cfg = family_cfg(compute_dtype)
    assert nemotron_h_lm.pattern(cfg) == "MEMEM*EME"
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    ref_fn = nemotron_h_lm.reference_loss(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0])(params)
    want, want_g = jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens))(params)
    assert abs(float(got) - float(want)) / float(want) <= loss_tol
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    named = [tuple(jax.tree_util.DictKey(k) for k in path)
             for path in nemotron_h_lm.grad_leaves(cfg)]
    assert set(named) <= set(flat_got)
    errors = {jax.tree_util.keystr(path): rel(flat_got[path],
                                              flat_want[path])
              for path in (flat_got if compute_dtype == "float32"
                           else named)}
    assert max(errors.values()) <= grad_tol, errors
    jax.effects_barrier()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"bench": "routing"')]
    assert lines and all(l["assignments"] == 4 * 2 * 64 * 3 for l in lines)
    assert all(l["beyond_margin_share"] == 0.0 for l in lines)
    if compute_dtype == "float32":
        assert all(l["disagreeing_share"] == 0.0 for l in lines)
    else:
        assert all(0 < l["largest_gap"] <= cfg["tolerances"]["tie_margin"]
                   for l in lines)


def test_reference_takes_the_program_s_choice_only_where_it_is_a_tie():
    """The reference given OTHER choices than its own: inside the margin it
    follows them (another loss than its own choice gives), beyond the
    margin, or where they are not k distinct experts, it keeps its own."""
    cfg = family_cfg("float32")
    params, aux, tokens = model_inputs(cfg)
    given = jax.jit(nemotron_h_lm.reference_given_choices(cfg))
    own = nemotron_h_lm.program_expert_choices(cfg, params, tokens)
    want = float(given(params, tokens, own, 0.0))
    # The sixth-and-lower choice of every token pushed one expert on.
    E = cfg["experts_routed_over"]
    other = own.at[..., -1].set((own[..., -1] + 1) % E)
    assert float(given(params, tokens, other, 0.0)) == want
    assert float(given(params, tokens, other, 1.0)) != want
    # One expert chosen twice is k - 1 experts: no tie at any margin.
    fewer = own.at[..., -1].set(own[..., 0])
    assert float(given(params, tokens, fewer, 1.0)) == want
    # The comparison's precision control: the same mathematics in bfloat16
    # is another number (on the chip, at T 8192, not even a finite one).
    low = nemotron_h_lm.reference_given_choices(cfg, dtype="bfloat16")
    assert abs(float(low(params, tokens, own, 0.0)) - want) > 1e-4 * want


def test_the_float32_parts_are_float32_in_the_traced_program():
    """What the comparison with the reference cannot see (on the chip a
    chunk state carried in bfloat16 and a router at the default matmul
    precision read each seed's own floor: they perturb less than the
    recipe's bfloat16 arithmetic does) is held here, in the program's
    jaxpr: under bfloat16 compute the state passed from chunk to chunk is
    float32, and the router's matmul takes float32 operands at HIGHEST."""
    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    eqns = list(_equations(jax.make_jaxpr(
        lambda p: loss_fn(p, aux, tokens)[0])(params).jaxpr))
    H, P_, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["ssm_state_size"])
    carried = [v.aval for e in eqns if e.primitive.name == "scan"
               for v in e.outvars[:e.params["num_carry"]]
               if v.aval.shape[-2:] == (P_, N) and v.aval.size % (H * P_ * N)
               == 0]
    assert len(carried) == 4 and all(a.dtype == jnp.float32 for a in carried)
    routers = [e for e in eqns if e.primitive.name == "dot_general"
               and e.outvars[0].aval.shape[-1] == cfg["experts_routed_over"]
               and e.invars[1].aval.shape == (cfg["hidden_size"],
                                              cfg["experts_routed_over"])]
    assert len(routers) == 4
    for e in routers:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,
                                         jax.lax.Precision.HIGHEST)


def test_the_float32_parts_are_float32_in_the_kernels_too():
    """The same parts where the scan runs as kernels (bfloat16 operands at
    a tiling shape): the state scratch of the forward and of the states
    pass, the transient of entering states between the backward's two
    kernels and the carried gradient of the state are float32; the
    running-sum product (and its transpose in the sweep) takes float32
    operands at HIGHEST; every other product takes its operands in
    ``x.dtype`` — not narrower — and accumulates in float32."""
    x, dt, A, B, C, D = kernel_inputs("three_chunks_batch_2_G_lt_H",
                                      "bfloat16")
    b, T, H, P_ = x.shape
    G, N_ = B.shape[2:]
    RP = H // G * P_
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: ssd_scan(*a, chunk=128, interpret=True).astype(
            jnp.float32).sum(), argnums=tuple(range(6))))(x, dt, A, B, C, D)
    calls = {e.params["jaxpr"].debug_info.func_name: e
             for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert set(calls) == {"ssd_fwd", "ssd_states", "ssd_bwd"}
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    for name, call in calls.items():
        kernel = call.params["jaxpr"]
        carried = kernel.invars[-1].aval         # the one scratch buffer
        assert (carried.shape, carried.dtype) == ((N_, RP), jnp.float32)
        dots = [e for e in _equations(kernel)
                if e.primitive.name == "dot_general"]
        sums = [e for e in dots if e.params["precision"] in (
            highest, jax.lax.Precision.HIGHEST)]
        assert len(sums) == {"ssd_fwd": 1, "ssd_states": 1,
                             "ssd_bwd": 2}[name]
        for e in sums:
            assert all(v.aval.dtype == jnp.float32 for v in e.invars)
            assert 128 in e.invars[1].aval.shape     # the triangle
        products = [e for e in dots if e not in sums]
        assert len(products) >= {"ssd_fwd": 4, "ssd_states": 1,
                                 "ssd_bwd": 12}[name]
        for e in products:
            assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars)
            assert e.params["preferred_element_type"] == jnp.float32
    entering = calls["ssd_states"].outvars[0].aval
    assert (entering.shape, entering.dtype) == ((b, G, 3, N_, RP),
                                                jnp.float32)
    assert any(v.aval.shape == entering.shape and v.aval.dtype
               == jnp.float32 for v in calls["ssd_bwd"].invars)


def test_tiny_stack_trains_through_make_train_step(hvd):
    """The nine-layer preset through the normal path on the 8-device mesh:
    the first step's loss is the reference's on the global batch, the loss
    falls, the state stays float32, and each dispatch bumps the mixers'
    and the expert layers' counters from the shapes they noted."""
    cfg = family_cfg("bfloat16")
    params, aux, _ = model_inputs(cfg)
    tokens = nemotron_h_lm.host_batch(cfg, np.random.default_rng(7), 8)
    tx = nemotron_h_lm.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(nemotron_h_lm.reference_loss(cfg)(params, aux, tokens))
    step = make_train_step(nemotron_h_lm.loss_fn(cfg), tx, hvd.ranks_mesh())
    names = ("ssm.scan_chunks", "ssm.state_bytes", "ssm.fused_scans",
             "moe.assignments", "moe.held_assignments", "moe.expert_bytes")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    losses = []
    for _ in range(4):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) / want <= 5e-3
    assert losses[-1] < losses[0]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    after = registry.snapshot()["counters"]
    got = {n: after.get(n, 0) - before[n] for n in names}
    # A shard's step, four dispatches: one sequence of 64 tokens through 4
    # mixers (4 chunks of 16; 4 heads x 16 x 16 float32 a state) and 4
    # expert layers (3 of 8 experts a token, 4 held, 2 matrices of 64x32).
    # The preset's scans do not tile (chunks of 16): the XLA form, no
    # fused scan.
    assert got == {"ssm.scan_chunks": 4 * 4 * 4,
                   "ssm.state_bytes": 4 * 4 * 4 * 4 * 16 * 16 * 4,
                   "ssm.fused_scans": 0,
                   "moe.assignments": 4 * 4 * 64 * 3,
                   "moe.held_assignments": 4 * 4 * 64 * 3 // 2,
                   "moe.expert_bytes": 4 * 4 * 2 * 4 * 64 * 32 * 4}


def test_one_mixer_at_a_tiling_shape_counts_a_fused_scan(hvd):
    """A one-mixer stack whose scan tiles (2 heads of 64 in one group,
    state 128, one chunk of 128) through ``make_train_step`` on one device
    — the plain program, so no manual mesh axis stands the interpreted
    kernels down: it trains, each dispatch counts one fused scan, and the
    lowered step names the three kernels under ``ssm/scan``, where the
    cell's reader looks and nowhere else."""
    import re

    import optax

    from benchmark.metrics import ssm_ms
    from horovod_tpu.parallel.mesh import RANKS_AXIS

    model = NemotronHLM(vocab=64, dim=32, pattern="M", max_len=128,
                        dtype=jnp.float32,
                        ssm=dict(num_heads=2, head_dim=64, n_groups=1,
                                 state_size=128, chunk=128))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 129), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]

    def loss_fn(p, aux, tokens):
        logits = model.apply({"params": p}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), aux

    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, tokens)[0])).lower(
        params).as_text(debug_info=True)
    stacks = set(re.findall(r'"([^"]*/ssd_(?:fwd|states|bwd))[/"]', text))
    assert {s.rsplit("/", 1)[1] for s in stacks} == {
        "ssd_fwd", "ssd_states", "ssd_bwd"}
    assert all(ssm_ms.in_scan(s) for s in stacks), stacks
    for part in ("intra", "states", "pass", "inter"):
        assert f"ssm/scan/{part}" not in text

    tx = optax.sgd(0.5)
    step = make_train_step(loss_fn, tx, Mesh(np.asarray(jax.devices()[:1]),
                                             (RANKS_AXIS,)))
    names = ("ssm.fused_scans", "ssm.scan_chunks", "ssm.state_bytes")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    aux, opt_state, losses = {}, tx.init(params), []
    for _ in range(3):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    after = registry.snapshot()["counters"]
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "ssm.fused_scans": 3, "ssm.scan_chunks": 3,
        "ssm.state_bytes": 3 * 2 * 64 * 128 * 4}


def test_trace_scopes_name_the_mixer_s_parts_and_the_shared_expert():
    cfg = family_cfg("bfloat16")
    params, aux, tokens = model_inputs(cfg)
    loss_fn = nemotron_h_lm.loss_fn(cfg)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, aux, tokens)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/scan/intra",
                  "ssm/scan/states", "ssm/scan/pass", "ssm/scan/inter",
                  "ssm/gate_norm", "ssm/out_proj", "moe/route",
                  "moe/shared", "layer_5/attn", "layer_8/moe"):
        assert scope in text, scope
    # The cell's own readers find them under those names.
    from benchmark.metrics import moe_ms, ssm_ms
    assert ssm_ms.in_scan("jvp(TransformerLM)/layer_*/ssm/scan/intra/mul")
    assert ssm_ms.in_mixer("params['layer_*']['ssm']['in_proj']['kernel']")
    assert not ssm_ms.in_scan("jvp(TransformerLM)/layer_*/ssm/conv/mul")
    assert moe_ms.in_expert_layer(
        "transpose(jvp(TransformerLM))/layer_*/moe/shared/dot_general")


def test_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    tiny = dict(vocab=64, dim=32, num_heads=2, kv_heads=1, head_dim=16,
                ssm=dict(num_heads=2, head_dim=8, n_groups=1, state_size=8,
                         chunk=8),
                moe_experts=4, moe_top_k=2, moe_hidden=16, attn="full")
    model = NemotronHLM(**tiny, pattern="M*E")
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params) == {"tok_emb", "layer_0", "layer_1", "layer_2",
                           "ln_f", "head"}
    assert set(params["layer_1"]["attn"]) == {"q", "kv", "proj"}
    with pytest.raises(ValueError, match="pattern stack"):
        NemotronHLM(**tiny, pattern="M", tp_axis="tp").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="held share"):
        TransformerLM(vocab=64, dim=32, num_heads=2, tp_axis="tp",
                      moe={"held": (0, 2)}).init(jax.random.PRNGKey(0),
                                                 tokens)
    with pytest.raises(ValueError, match="pos='none'"):
        NemotronHLM(**{**tiny, "pos": "rotary"}, pattern="M").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, pos="none").init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="unknown layer"):
        NemotronHLM(**tiny, pattern="MX").init(jax.random.PRNGKey(0), tokens)


# ------------------------------------ linear attention, two sub-layers


def hybrid_cfg(compute_dtype="float32", **override):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as fh:
        cfg = {**json.load(fh), **olmo_hybrid_lm.TINY, **override}
    cfg["training"] = {**cfg["training"], "compute_dtype": compute_dtype}
    return cfg


ONE_PERIOD = dict(num_hidden_layers=4, layer_types=[
    "linear_attention"] * 3 + ["full_attention"])


def hybrid_inputs(cfg, n=2, seed=5):
    params, aux = olmo_hybrid_lm.init(cfg, jax.random.PRNGKey(seed))
    tokens = olmo_hybrid_lm.host_batch(cfg, np.random.default_rng(seed), n)
    return params, aux, tokens


@pytest.mark.parametrize("T,chunk,neg", [(40, 16, True), (64, 64, False)],
                         ids=["T_not_a_multiple_beta_to_2",
                              "one_chunk_beta_to_1"])
def test_delta_mixer_module_equals_the_reference_recurrence(T, chunk, neg):
    """``GatedDeltaNet`` (float32) against the family's plain mixer, whose
    delta rule steps token by token, same parameter tree: output to 1e-5
    of its largest, every parameter's gradient and the input's to 2e-4."""
    cfg = hybrid_cfg(linear_chunk_size=chunk, linear_allow_neg_eigval=neg)
    mixer = GatedDeltaNet(
        num_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"], chunk=chunk,
        allow_neg_eigval=neg, norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, cfg["hidden_size"]))
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    # Move the one-initialised leaf off one, so a wrong use shows.
    params = {**params, "gate_norm": 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(2), params["gate_norm"].shape)}
    assert set(params) == {"q", "k", "v", "g", "a", "b", "out", "conv",
                           "A_log", "dt_bias", "gate_norm"}
    assert set(params["conv"]) == {"kernel"}
    reference = olmo_hybrid_lm.reference_mixer(cfg)

    def ours(p, x):
        return mixer.apply({"params": p}, x)

    def theirs(p, x):
        return jax.vmap(lambda s: reference(p, s))(x)

    with jax.default_matmul_precision("highest"):
        got, want = ours(params, x), theirs(params, x)
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())
        weight = jnp.sin(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        g = jax.grad(lambda p, x: (ours(p, x) * weight).sum(), (0, 1))(
            params, x)
        w = jax.grad(lambda p, x: (theirs(p, x) * weight).sum(), (0, 1))(
            params, x)
    errors = {jax.tree_util.keystr(path): rel(a, b) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(w))}
    assert len(errors) == 12 and max(errors.values()) <= 2e-4, errors


def test_swiglu_is_the_gated_mlp():
    mlp = SwiGLU(24, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    p = mlp.init(jax.random.PRNGKey(1), h)["params"]
    assert {k: v["kernel"].shape for k, v in p.items()} == {
        "gate": (16, 24), "up": (16, 24), "down": (24, 16)}
    want = (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]
    assert rel(mlp.apply({"params": p}, h), want) <= 1e-6


# float32 compute: every leaf of the gradient is the reference's to
# summation order, through one whole period (LLLF) and through the
# rehearsal's preset (LF).  bfloat16 compute on 128 tokens of a 256-wide
# model: the preset's own, looser tolerances, on the family's named leaves.
@pytest.mark.parametrize("compute_dtype,layers,loss_tol,grad_tol", [
    ("float32", ONE_PERIOD, 1e-5, 3e-4), ("float32", {}, 1e-5, 3e-4),
    ("bfloat16", {}, 5e-3, 0.2)],
    ids=["float32_one_period", "float32_preset", "bfloat16_preset"])
def test_hybrid_model_against_reference_loss(compute_dtype, layers,
                                             loss_tol, grad_tol):
    cfg = hybrid_cfg(compute_dtype, **layers)
    assert olmo_hybrid_lm.pattern(cfg) == ("LLLF" if layers else "LF")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    ref_fn = olmo_hybrid_lm.reference_loss(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda p: loss_fn(p, aux, tokens)[0])(params)
    want, want_g = jax.value_and_grad(
        lambda p: ref_fn(p, aux, tokens))(params)
    assert abs(float(got) - float(want)) / float(want) <= loss_tol
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    named = [tuple(jax.tree_util.DictKey(k) for k in path)
             for path in olmo_hybrid_lm.grad_leaves(cfg)]
    assert len(named) == 8 and set(named) <= set(flat_got)
    errors = {jax.tree_util.keystr(path): rel(flat_got[path],
                                              flat_want[path])
              for path in (flat_got if compute_dtype == "float32"
                           else named)}
    assert max(errors.values()) <= grad_tol, errors


def test_hybrid_reference_in_bfloat16_is_another_number():
    """The comparison's precision control: the reference's own mathematics
    in bfloat16 is not the reference."""
    cfg = hybrid_cfg()
    params, aux, tokens = hybrid_inputs(cfg)
    want = float(olmo_hybrid_lm.reference_loss(cfg)(params, aux, tokens))
    low = float(olmo_hybrid_lm.reference_loss(cfg, dtype="bfloat16")(
        params, aux, tokens))
    assert abs(low - want) > 1e-4 * want


def test_hybrid_stack_s_tree_and_the_published_count():
    """The parameter tree of one period, and — from shapes alone — the
    published model's size: one period of 832,520,436 parameters eight
    times over, embedding, head and final norm."""
    cfg = hybrid_cfg(**ONE_PERIOD)
    params, _, _ = hybrid_inputs(cfg)
    assert set(params) == {"tok_emb", "layer_0", "layer_1", "layer_2",
                           "layer_3", "ln_f", "head"}
    assert set(params["layer_0"]) == {"lin", "mixer_norm", "mlp",
                                      "mlp_norm"}
    assert set(params["layer_3"]) == {"attn", "mixer_norm", "mlp",
                                      "mlp_norm"}
    assert set(params["layer_3"]["attn"]) == {"qkv", "q_norm", "k_norm",
                                              "proj"}
    shapes = jax.eval_shape(
        lambda: OlmoHybridLM(pattern="LLLF", attn="full").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count["layer_0"] == 215_570_172
    assert count["layer_3"] == 185_809_920
    period = sum(count[f"layer_{i}"] for i in range(4))
    assert period == 832_520_436
    assert count["tok_emb"] == count["head"] == 100_352 * 3840
    assert 8 * period + 2 * count["tok_emb"] + 3840 == 7_430_870_688


def test_the_hybrid_s_float32_parts_are_float32_in_the_traced_program():
    """Under bfloat16 compute: the delta rule's carried state is float32
    (``ops/gated_delta.py``'s own test holds the solve); the L2 norms'
    sums of squares, ``beta``'s sigmoid and the decay's softplus and
    exponentials are float32."""
    cfg = hybrid_cfg("bfloat16")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    eqns = list(_equations(jax.make_jaxpr(
        lambda p: loss_fn(p, aux, tokens)[0])(params).jaxpr))
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    B, T = 2, cfg["sequence_length"]
    carried = [v.aval for e in eqns if e.primitive.name == "scan"
               for v in e.outvars[:e.params["num_carry"]]
               if v.aval.shape == (B, H, dv, dk)]
    assert len(carried) == 1 and carried[0].dtype == jnp.float32
    per_head = [e for e in eqns if e.outvars
                and e.outvars[0].aval.shape == (B, T, H)
                and e.primitive.name in ("logistic", "exp", "log1p",
                                         "reduce_sum")]
    assert {e.primitive.name for e in per_head} >= {"logistic", "exp",
                                                    "reduce_sum"}
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in per_head)


def test_tiny_hybrid_trains_through_make_train_step(hvd):
    """The preset through the normal path on the 8-device mesh: the first
    step's loss is the reference's on the global batch, the loss falls,
    the state stays float32, and each dispatch bumps the mixer's counters
    from the shapes it noted."""
    cfg = hybrid_cfg("bfloat16")
    params, aux, _ = hybrid_inputs(cfg)
    tokens = olmo_hybrid_lm.host_batch(cfg, np.random.default_rng(7), 8)
    tx = olmo_hybrid_lm.optimizer(cfg)
    opt_state = tx.init(params)
    want = float(olmo_hybrid_lm.reference_loss(cfg)(params, aux, tokens))
    step = make_train_step(olmo_hybrid_lm.loss_fn(cfg), tx, hvd.ranks_mesh())
    names = ("lin.delta_chunks", "lin.state_bytes", "ssm.scan_chunks",
             "moe.assignments")
    before = {n: registry.snapshot()["counters"].get(n, 0) for n in names}
    losses = []
    for _ in range(4):
        params, aux, opt_state, loss = step(params, aux, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) / want <= 5e-3
    assert losses[-1] < losses[0]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    after = registry.snapshot()["counters"]
    # A shard's step, four dispatches: one sequence of 64 tokens through
    # one mixer (4 chunks of 16; 2 heads x 32 x 16 float32 a state).
    assert {n: after.get(n, 0) - before[n] for n in names} == {
        "lin.delta_chunks": 4 * 4, "lin.state_bytes": 4 * 4 * 2 * 32 * 16 * 4,
        "ssm.scan_chunks": 0, "moe.assignments": 0}


def test_trace_scopes_name_the_linear_mixer_s_parts():
    cfg = hybrid_cfg("bfloat16")
    params, aux, tokens = hybrid_inputs(cfg)
    loss_fn = olmo_hybrid_lm.loss_fn(cfg)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, aux, tokens)[0])).lower(
        params).as_text(debug_info=True)
    for scope in ("layer_0/lin/in_proj/q", "lin/in_proj/b", "lin/conv",
                  "lin/delta", "lin/delta/solve", "lin/delta/states",
                  "lin/delta/inter", "lin/delta/intra", "lin/gate_norm",
                  "lin/out_proj/out", "layer_0/mlp/up", "layer_1/attn/qkv",
                  "layer_1/mlp/down", "layer_1/mixer_norm"):
        assert scope in text, scope
    # The cell's own readers find them under those names.
    from benchmark.metrics import linattn_ms, ssm_ms
    assert linattn_ms.in_delta(
        "transpose(jvp(TransformerLM))/layer_*/lin/delta/solve/dot_general")
    assert linattn_ms.in_mixer("params['layer_*']['lin']['q']['kernel']")
    assert not linattn_ms.in_delta("jvp(TransformerLM)/layer_*/lin/conv/mul")
    assert not linattn_ms.in_mixer("jvp(TransformerLM)/layer_*/mlp/up/dot")
    assert not ssm_ms.in_mixer("jvp(TransformerLM)/layer_*/lin/conv/mul")


def test_two_sub_layer_options_that_do_not_compose_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    lin = dict(num_heads=2, key_dim=8, value_dim=16, chunk=8)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, lin=lin).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="belong to a pattern stack"):
        TransformerLM(vocab=64, dim=32, num_heads=2, mlp_hidden=48).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="pattern stack"):
        OlmoHybridLM(vocab=64, dim=32, num_heads=2, lin=lin, mlp_hidden=48,
                     pattern="L", tp_axis="tp", attn="full").init(
                         jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="runs the stack as published"):
        olmo_hybrid_lm.init(hybrid_cfg(attention_bias=True),
                            jax.random.PRNGKey(0))
