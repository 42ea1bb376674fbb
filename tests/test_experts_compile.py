"""The experts' kernels and layers compiled for a described TPU v5e (see
``tests/_v5e.py``): the grouped matmuls at every tiling
``grouped_matmul._plan`` takes, and the all-experts, held and latent layers
at the cells' widths.  The interpreted tests of the same code are
``test_grouped_matmul.py``, ``test_dropless_moe.py`` and
``test_hybrid_experts.py``.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _v5e import (  # noqa: F401
    NEMO3_WINDOW, custom_calls, kernels_by_name, v5e)


def body_computations(compiled_text):
    """The text of every ``while`` body of a compiled program."""
    names = set(re.findall(r"body=%?([\w.\-]+)", compiled_text))
    for block in re.split(r"\n\n", compiled_text):
        head = re.match(r"\s*%?([\w.\-]+) ", block)
        if head and head.group(1) in names:
            yield block


def test_dropless_expert_layer_fwd_bwd_at_olmoe_widths(v5e, monkeypatch):
    """``DroplessMoE`` as the ``olmoe_1chip`` cell calls it: 16,384 tokens
    of width 2048, 64 experts of 1024, top-8 — forward and backward on one
    described chip.  ``grouped_matmul._plan`` takes the kernels there: the
    three grouped matmuls and their six transposes compile to the family's
    three kernels by name (no ``ragged-dot`` is left), nothing is a dense
    tokens x experts product, and the layer with its gradients fits the
    chip several times over."""
    from horovod_tpu.parallel.moe import DroplessMoE

    # The layer asks jax.default_backend() whether to lower interpreted.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    tokens, d, hidden, experts, top_k = 16_384, 2048, 1024, 64, 8
    one = SingleDeviceSharding(v5e[0])
    layer = DroplessMoE(num_experts=experts, hidden=hidden, top_k=top_k)
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda key: layer.init(
            key, jnp.zeros((8, d), jnp.bfloat16))["params"],
            jax.random.PRNGKey(0)))
    assert params["w_gate"].shape == (experts, d, hidden)

    def loss(p, x):
        out, balance, z = layer.apply({"params": p}, x)
        return out.astype(jnp.float32).sum() + balance + z

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x)
    assert kernels_by_name(lowered) == {"moe_gmm": 3, "moe_gmm_nt": 3,
                                        "moe_tgmm": 3, "moe_land": 0}
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert "ragged-dot" not in text
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 1.6 GB of float32 expert weights and as much of gradients, and
    # under 3 GB of bf16 rows: a dense (tokens, experts, capacity)
    # dispatch would be 10.7 GB a tensor.
    assert plan < 8 * 2 ** 30, plan / 2 ** 30


# (rows, groups, K, N): what ``grouped_matmul._plan`` hands to the kernels,
# one case a way of tiling — the two cells' products both ways, widths that
# cut into blocks of 384 and 640 only, one group, more groups than row
# tiles, the widest contraction the plan still holds whole in VMEM, and
# (PR 46) a contraction of 1,024 against 21 lane tiles: a held window of a
# 1,024-wide latent, up at its 5,632 rows and down at 8,448 (33 tiles of
# 256: rows in whole strips only).  The held cells' rows are their windows'
# (``moe._window_plan``, PR 53): 7,680, 5,632 and, at ``keye_1chip``'s
# widths, ``joyaiflash_1chip``'s 10,752 (21 tiles of 512).
@pytest.mark.parametrize("rows,groups,k,n", [
    (7_680, 8, 2688, 1920), (7_680, 8, 1920, 2688),
    (131_072, 64, 2048, 1024), (131_072, 64, 1024, 2048),
    (1024, 4, 1152, 640), (512, 1, 128, 128), (512, 64, 256, 384),
    (1024, 2, 4096, 1024), (5_632, 8, 1024, 2688), (8_448, 8, 2688, 1024),
    (10_752, 16, 2048, 768)],
    ids=["twotower_up", "twotower_down", "olmoe_up", "olmoe_down",
         "blocks_of_384_and_640", "one_group", "more_groups_than_tiles",
         "widest_contraction", "latent_window_up",
         "latent_rows_in_whole_strips_only", "joyaiflash_window"])
def test_grouped_matmuls_compile_wherever_the_plan_takes_the_kernels(
        v5e, rows, groups, k, n):
    """A shape ``_plan`` gives the kernels has to compile, the product and
    both of its gradients: interpret mode refuses nothing of what Mosaic
    refuses (a block off the tiling, more scoped VMEM than was asked)."""
    from horovod_tpu.ops import grouped_matmul as gm

    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    x, w, dy = shape(rows, k), shape(groups, k, n), shape(rows, n)
    plan = gm.grouped_plan(x, groups, n, interpret=False)
    assert plan.form == "kernels", plan

    def product_and_gradients(x, w, dy, sizes):
        y, pull = jax.vjp(
            lambda x, w: gm.grouped_matmul(x, w, sizes, plan), x, w)
        return y, pull(dy)

    lowered = jax.jit(product_and_gradients).lower(
        x, w, dy, shape(groups, dtype=jnp.int32))
    assert kernels_by_name(lowered) == {
        "moe_gmm": 1, "moe_gmm_nt": 1, "moe_tgmm": 1, "moe_land": 0}
    compiled = lowered.compile()
    y, (dx, dw) = compiled.out_info
    assert (y.shape, dx.shape, dw.shape) == ((rows, n), (rows, k),
                                             (groups, k, n))
    assert y.dtype == dx.dtype == dw.dtype == jnp.bfloat16


# (W rows a window, n tokens, d width, padded hidden width, held experts):
# the windows of the five cells whose rows land by product.
LANDINGS = {"keye_and_sdar_1chip": (16_384, 16_384, 2048, 768, 16),
            "joyaiflash_1chip": (10_752, 16_384, 2048, 768, 16),
            "twotower_1chip": (7_680, 16_384, 2688, 1920, 8),
            "nemo3super_1chip": (5_632, 8_192, 1024, 2688, 8)}


@pytest.mark.parametrize("cell", LANDINGS)
def test_the_landing_and_the_handed_block_compile_at_the_cells_windows(
        v5e, cell):
    """What a held window adds to the family at the four shapes the cells
    run: the landing of a window's rows on (n, d) float32 — gated, as the
    output's, and bare, as ``dx``'s — and ``moe_tgmm`` handed a float32
    carry, up and down.  Each writes the buffer it was handed (the
    program aliases every donated block and plans no temporary of a
    block's size)."""
    from horovod_tpu.ops import grouped_matmul as gm

    W, n, d, hidden, held = LANDINGS[cell]
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    plan = gm.grouped_plan(shape(W, d), held, hidden, interpret=False)
    assert plan.form == "kernels" and n % gm.LANDING_TOKENS == 0

    def sums(out, dx, up, down, rows, token, gate, x, h, sizes):
        return (gm.landed_rows(out, rows, token, gate, plan=plan),
                gm.landed_rows(dx, rows, token, plan=plan),
                gm._tgmm(x, h, sizes, up, plan=plan, interpret=False),
                gm._tgmm(h, x, sizes, down, plan=plan, interpret=False))

    f32 = jnp.float32
    blocks = (shape(n, d, dtype=f32), shape(n, d, dtype=f32),
              shape(held, d, hidden, dtype=f32),
              shape(held, hidden, d, dtype=f32))
    lowered = jax.jit(sums, donate_argnums=(0, 1, 2, 3)).lower(
        *blocks, shape(W, d), shape(W, dtype=jnp.int32),
        shape(W, dtype=f32), shape(W, d), shape(W, hidden),
        shape(held, dtype=jnp.int32))
    assert kernels_by_name(lowered) == {
        "moe_gmm": 0, "moe_gmm_nt": 0, "moe_tgmm": 2, "moe_land": 2}
    compiled = lowered.compile()
    assert [(o.shape, o.dtype) for o in compiled.out_info] == [
        (b.shape, f32) for b in blocks]
    m = compiled.memory_analysis()
    handed = sum(4 * b.size for b in blocks)
    assert m.alias_size_in_bytes == handed, (m.alias_size_in_bytes, handed)
    assert m.temp_size_in_bytes < min(4 * b.size for b in blocks)


# A held layer of three cells as its family calls it: (tokens, width, the
# layer's fields, GiB the plan stays under).  ``zaya1_1chip``'s and
# ``nemo3super_1chip``'s stand further down, inside their own layers;
# ``joyaiflash_1chip``'s is ``keye_1chip``'s at windows of 10,752 rows,
# whose kernels compile above (``joyaiflash_window``).
HELD_LAYERS = {
    "twotower_1chip": (16_384, 2688, dict(
        num_experts=128, hidden=1856, top_k=6, router="sigmoid",
        renormalize=True, gate_scale=2.5, activation="relu2",
        shared_hidden=3712, held=(0, 8)), 4.5),
    "keye_and_sdar_1chip": (16_384, 2048, dict(
        num_experts=128, hidden=768, top_k=8, renormalize=True,
        held=(0, 16)), 3.0)}


@pytest.mark.parametrize("cell", HELD_LAYERS)
def test_held_expert_layer_fwd_bwd_at_the_cells_widths(v5e, monkeypatch,
                                                       cell):
    """``DroplessMoE(held=...)`` as the cells call it (``twotower_1chip``:
    16,384 tokens of width 2688 routed over 128 experts, top-6, 8 of them
    held, a shared expert 3712 wide).  The grouped matmuls run over
    windows of the ``W`` sorted rows that ``_window_plan`` gives the
    shapes, inside ONE loop each way whose trip count the device reads,
    not over the layer's assignments (98,304 there: 0.5 GiB a tensor of
    their rows), as the family's kernels (``grouped_matmul._plan`` takes
    them at every such ``W``: each projection forward, again in the
    backward loop, an input and a weight gradient each), with the
    experts' hidden width padded to the kernels' whole lane tiles (1856
    to 1920), not to ``ragged_dot``'s 2048; the weight gradients are
    carried in float32; the plan, with the float32 weights and gradients,
    stays under its bound."""
    from horovod_tpu.parallel.moe import DroplessMoE, _window_plan

    # The layer asks jax.default_backend() whether to lower interpreted.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    tokens, d, fields, gib = HELD_LAYERS[cell]
    one = SingleDeviceSharding(v5e[0])
    layer = DroplessMoE(**fields)
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda key: layer.init(
            key, jnp.zeros((8, d), jnp.bfloat16))["params"],
            jax.random.PRNGKey(0)))
    held, hidden = fields["held"][1], fields["hidden"]
    matrices = 2 if fields.get("activation") == "relu2" else 3
    assert params["w_up"].shape == (held, d, hidden)
    assert params["router"]["kernel"].shape == (d, fields["num_experts"])
    assignments = tokens * fields["top_k"]
    window = _window_plan(
        assignments=assignments, held=held, routed=fields["num_experts"],
        row_bytes=2 * d, expert_bytes=4 * matrices * held * d * hidden)
    assert 1 < window.windows and window.rows % 256 == 0, window

    def loss(p, x):
        return layer.apply({"params": p}, x)[0].astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x)
    # The landing of ``out`` in the forward loop and of ``dx`` in the
    # backward one; each weight gradient's kernel is handed its carry.
    assert kernels_by_name(lowered) == {
        "moe_gmm": 2 * matrices, "moe_gmm_nt": matrices,
        "moe_tgmm": matrices, "moe_land": 2}
    assert "stablehlo.case" not in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    W, padded = window.rows, hidden + -hidden % 128
    assert "ragged-dot" not in text
    # No scatter-add of rows is left, and the loops update their float32
    # carries in place: no copy of the (n, d) accumulator nor of a weight
    # gradient's carry inside a loop's body.
    assert not re.search(rf"f32\[{tokens},{d}\]\S* scatter\(", text)
    bodies = "\n".join(body_computations(text))
    assert "tpu_custom_call" in bodies
    for carry in (f"f32[{tokens},{d}]", f"f32[{held},{d},{padded}]",
                  f"f32[{held},{padded},{d}]"):
        assert not re.search(re.escape(carry) + r"\S* copy\(", bodies), carry
    assert f"{W},{d}" in text and f"{assignments},{d}" not in text
    assert f"bf16[{W},{padded}]" in text
    assert f"f32[{held},{d},{padded}]" in text
    if padded != hidden:
        assert f"{W},{hidden}]" not in text
        assert f"{W},{hidden + -hidden % 256}]" not in text
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < gib * 2 ** 30, plan / 2 ** 30


# ---------------------------------------------- the ZAYA1 layer's parts
# (the zaya1_1chip cell: 1 sequence of 16,384, ZAYA1-8B's widths)


def test_a_zaya_layer_fwd_bwd_at_zaya_widths(v5e, monkeypatch):
    """One ``Z`` layer as the ``zaya1_1chip`` cell calls it, forward and
    backward on one chip: compressed convolutional attention's latent as
    the two kernels of ``ops/cca_passes.py`` (PR 49; plain XLA before)
    around the grouped-KV flash kernels at 8 query over 2 KV
    heads of 128 and T 16,384, then the router network and 8 held of 16
    top-1 experts 2,048 wide.  With 3 x 8 held >= the 17 outputs the held
    window is EVERY assignment: the grouped matmuls run over 16,384 rows,
    as the family's kernels, and no second window exists."""
    from horovod_tpu.models.transformer import PatternLayer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, d = 16_384, 2048
    one = SingleDeviceSharding(v5e[0])
    layer = PatternLayer("Z", dict(
        attn=dict(num_heads=8, kv_heads=2, head_dim=128, attn="flash",
                  rope_theta=5e6, taps=(2, 2), rotary_fraction=0.5),
        moe=dict(num_experts=16, hidden=2048, top_k=1, router="mlp",
                 router_hidden=256, skip_choice=True, held=(0, 8))))
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16, sharding=one)
    state = jax.ShapeDtypeStruct((1, tokens, 256), jnp.float32, sharding=one)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda key: layer.init(
            key, jnp.zeros((1, 256, d), jnp.bfloat16),
            jnp.zeros((1, 256, 256), jnp.float32))["params"],
            jax.random.PRNGKey(0)))
    assert params["moe"]["w_gate"].shape == (8, d, 2048)
    assert params["moe"]["router_out"]["kernel"].shape == (256, 17)
    assert params["attn"]["conv1_kernel"].shape == (10, 2, 128, 128)

    def loss(p, x, state):
        y, r = layer.apply({"params": p}, x, state)
        return y.astype(jnp.float32).sum() + r.sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        params, x, state)
    found = kernels_by_name(lowered)
    # Up, gate and down forward, their replay in the checkpoint, and the
    # input and weight gradients: one window, so each exactly once.
    assert found == {"moe_gmm": 6, "moe_gmm_nt": 3, "moe_tgmm": 3,
                     "moe_land": 0}, found
    # The resident forward since PR 60 (the grid forward before) and,
    # since PR 44, the backward as one kernel a KV group (the per-head pair
    # ``_dkdv_kernel``, ``_dq_kernel`` before).
    # The latent's passes: the forward reads the two projections' arrays
    # (each also as its halo), two packed vectors, two sets of matrices and
    # the rotation's table; the backward the two cotangents and the
    # matrices turned besides.
    assert custom_calls(lowered.as_text())[:4] == [
        ("cca_mix_bwd", 13), ("cca_mix_fwd", 9), ("flash_group_bwd", 6),
        ("flash_resident_fwd", 3)]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "16384,2048" in text
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 3.0 * 2 ** 30, plan / 2 ** 30


# ------------------------------------- the Nemotron-3-Super cell's parts
# (the nemo3super_1chip cell: 1 sequence of 8,192 (+2), one chip's share)


def test_a_latent_expert_layer_fwd_bwd_at_nemotron3_widths(v5e, monkeypatch):
    """One ``E`` layer as the ``nemo3super_1chip`` cell calls it, forward
    and backward on one chip: 8,192 tokens of width 4,096 routed over 512
    experts, top-22, 8 of them held, in a latent of 1,024 between the two
    projections every expert shares, beside a shared expert 5,376 wide.
    The grouped matmuls run over windows of ``W`` sorted rows OF THE
    LATENT (``_window_plan``'s, for the 2,816 that uniform routing sends
    here), as the family's kernels, not over the 180,224 assignments and
    never at the model's width."""
    from horovod_tpu.models.transformer import PatternLayer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tokens, d = 8_192, 4096
    one = SingleDeviceSharding(v5e[0])
    layer = PatternLayer("E", dict(
        num_experts=512, hidden=2688, top_k=22, router="sigmoid",
        renormalize=True, gate_scale=5.0, activation="relu2",
        shared_hidden=5376, latent=1024, held=(0, 8)))
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda key: layer.init(
            key, jnp.zeros((1, 256, d), jnp.bfloat16))["params"],
            jax.random.PRNGKey(0)))
    assert params["moe"]["w_up"].shape == (8, 1024, 2688)
    assert params["moe"]["w_down"].shape == (8, 2688, 1024)
    assert params["moe"]["router"]["kernel"].shape == (d, 512)
    assert params["moe"]["latent_down"]["kernel"].shape == (d, 1024)

    def loss(p, x):
        return layer.apply({"params": p}, x).astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x)
    # Up and down in the forward loop and again in the backward one, two
    # input and two weight gradients.
    assert kernels_by_name(lowered) == {
        "moe_gmm": 4, "moe_gmm_nt": 2, "moe_tgmm": 2, "moe_land": 2}
    W = NEMO3_WINDOW
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert f"{W},1024" in text and f"{W},2688" in text
    assert f"{W},4096" not in text and "180224,1024" not in text
    m = compiled.memory_analysis()
    plan = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert plan < 3.0 * 2 ** 30, plan / 2 ** 30
