"""fused_softmax_xent must match the materialized-logits reference in
value and gradients (it is the bench transformer's loss head)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops.losses import fused_softmax_xent


def naive_loss(h, w, labels):
    logits = (h @ w.astype(h.dtype)).astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


class TestFusedXent:
    # 2, 5 and 8 unrolled chunks of the 40 rows, then 10 scanned.
    @pytest.mark.parametrize("chunk", [4096, 8, 5, 4])
    def test_matches_reference(self, chunk):
        rng = np.random.RandomState(0)
        n, d, v = 40, 16, 97
        h = jnp.asarray(rng.randn(n, d), jnp.float32)
        w = jnp.asarray(rng.randn(d, v) * 0.1, jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        got = fused_softmax_xent(h, w, labels, chunk)
        want = naive_loss(h, w, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    # 2 and 3 unrolled chunks of the 30 rows, then 15 scanned.
    @pytest.mark.parametrize("chunk", [4096, 10, 2])
    def test_grads_match_reference(self, chunk):
        rng = np.random.RandomState(1)
        n, d, v = 30, 8, 64
        h = jnp.asarray(rng.randn(n, d), jnp.float32)
        w = jnp.asarray(rng.randn(d, v) * 0.1, jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)

        def loss_fused(h, w):
            return fused_softmax_xent(h, w, labels, chunk).mean()

        def loss_naive(h, w):
            return naive_loss(h, w, labels).mean()

        got = jax.grad(loss_fused, argnums=(0, 1))(h, w)
        want = jax.grad(loss_naive, argnums=(0, 1))(h, w)
        for g, wv in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                       rtol=1e-5, atol=1e-6)

    def test_bf16_activations(self):
        """bf16 h / f32 w — the bench configuration; the fused op's f32
        accumulation must stay within bf16 rounding of the f32 path."""
        rng = np.random.RandomState(2)
        n, d, v = 32, 16, 50
        h = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
        w = jnp.asarray(rng.randn(d, v) * 0.1, jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        got = fused_softmax_xent(h, w, labels, 8)
        want = naive_loss(h.astype(jnp.float32), w, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("n,chunk,chunks", [
        (15, 16384, 1), (30, 16384, 2), (30, 10, 3), (32, 8, 4),
        (32, 4, 8), (32, 2, 16)])
    def test_every_chunk_count_matches_reference(self, n, chunk, chunks):
        """One tile (an odd row count), the default two, more because
        ``chunk`` asks for them, the last unrolled count and the scan:
        the same loss and gradients."""
        from horovod_tpu.ops import losses

        rows, unrolled = losses._schedule(n, chunk)
        assert n // rows == chunks
        assert unrolled == (chunks <= losses._MAX_UNROLL_CHUNKS)
        rng = np.random.RandomState(5)
        d, v = 8, 64
        h = jnp.asarray(rng.randn(n, d), jnp.float32)
        w = jnp.asarray(rng.randn(d, v) * 0.1, jnp.float32)
        labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)

        def loss_fused(h, w):
            return fused_softmax_xent(h, w, labels, chunk).mean()

        def loss_naive(h, w):
            return naive_loss(h, w, labels).mean()

        got_l, got_g = jax.value_and_grad(loss_fused, argnums=(0, 1))(h, w)
        want_l, want_g = jax.value_and_grad(loss_naive, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                                   rtol=1e-4, atol=1e-5)
        for g, wv in zip(got_g, want_g):
            np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                       rtol=1e-5, atol=1e-6)

    def test_model_hidden_path_matches_full_apply(self):
        """TransformerLM(return_hidden=True) + fused head == the model's
        own logits + optax CE (f32 head)."""
        from horovod_tpu.models import TransformerLM

        vocab, dim = 64, 32
        model = TransformerLM(vocab=vocab, dim=dim, depth=1, num_heads=4,
                              attn="full", dtype=jnp.float32,
                              head_dtype=jnp.float32)
        toks = jnp.asarray(
            np.random.RandomState(3).randint(0, vocab, (2, 17)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        labels = jnp.asarray(
            np.random.RandomState(4).randint(0, vocab, (2, 17)), jnp.int32)

        logits = model.apply({"params": params}, toks)
        want = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()

        h = model.apply({"params": params}, toks, return_hidden=True)
        got = fused_softmax_xent(
            h.reshape(-1, dim), params["head"]["kernel"],
            labels.reshape(-1)).mean()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestSchedule:
    @pytest.mark.parametrize("n,chunk,rows,unrolled", [
        # Unrolled under the limit: the two chunks of every LM cell.
        (16384, 16384, 8192, True),
        (4096, 2048, 2048, True),
        # Scanned above it, at the same transient bound.
        (4096, 64, 64, False),
        # An explicitly small chunk is honoured: four bodies, not two.
        (4096, 1024, 1024, True),
        # The last unrolled count, and the first scanned.
        (4096, 512, 512, True),
        (4608, 512, 512, False),
        # An odd row count is one tile while the bound allows.
        (4097, 16384, 4097, True),
        # The largest divisor within the bound, not the bound itself.
        (30, 8, 6, True),
    ])
    def test_schedule_follows_n_and_chunk(self, n, chunk, rows, unrolled):
        """Which schedule for which ``(n, chunk)``: nothing else decides
        (no mode, no environment), and no warning comes with either."""
        import warnings

        from horovod_tpu.ops import losses

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert losses._schedule(n, chunk) == (rows, unrolled)

    def test_module_reads_no_environment(self):
        import inspect

        from horovod_tpu.ops import losses

        source = inspect.getsource(losses)
        assert "environ" not in source and "getenv" not in source
        assert "XENT_MODE" not in source


# ------------------------------------------- the default schedule's rules


def head_problem(n=24, d=8, v=50, dtype=jnp.float32, seed=7):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, d), dtype)
    w = jnp.asarray(rng.randn(d, v) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    mask = jnp.asarray(rng.rand(n) > 0.3, jnp.float32)
    return h, w, labels, mask


# How a caller reduces the per-token losses: the cotangent that the
# backward rule gets is the same on every row under the first two, and a
# row mask under the third.
REDUCTIONS = {
    "mean": lambda x, m: x.mean(),
    "sum": lambda x, m: x.sum(),
    "masked": lambda x, m: (x * m).sum() / m.sum(),
}


def fused_and_naive(reduction, labels, chunk=16384):
    reduce = REDUCTIONS[reduction]

    def fused(h, w, m):
        return reduce(fused_softmax_xent(h, w, labels, chunk), m)

    def naive(h, w, m):
        return reduce(naive_loss(h, w, labels), m)

    return fused, naive


def assert_trees_close(got, want, **tol):
    for g, wv in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(wv, np.float32), **tol)


class TestGradsUnderEveryReduction:
    @pytest.mark.parametrize("reduction", sorted(REDUCTIONS))
    # The default two chunks, three, one tile (odd rows), twelve scanned.
    @pytest.mark.parametrize("n,chunk", [(24, 16384), (24, 8), (25, 16384),
                                         (24, 2)])
    def test_grads_match_reference(self, n, chunk, reduction):
        h, w, labels, mask = head_problem(n=n)
        fused, naive = fused_and_naive(reduction, labels, chunk)
        got = jax.jit(jax.value_and_grad(fused, argnums=(0, 1, 2)))(
            h, w, mask)
        want = jax.value_and_grad(naive, argnums=(0, 1, 2))(h, w, mask)
        assert_trees_close(got, want, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("reduction", sorted(REDUCTIONS))
    def test_bf16_activations_grads(self, reduction):
        h, w, labels, mask = head_problem(n=32, d=16, dtype=jnp.bfloat16)
        fused, naive = fused_and_naive(reduction, labels)
        got = jax.value_and_grad(fused, argnums=(0, 1))(h, w, mask)
        want = jax.value_and_grad(naive, argnums=(0, 1))(
            h.astype(jnp.float32), w, mask)
        assert got[1][0].dtype == jnp.bfloat16
        assert got[1][1].dtype == jnp.float32
        assert_trees_close(got, want, rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("wrap", ["checkpoint", "vmap"])
    @pytest.mark.parametrize("reduction", ["mean", "masked"])
    def test_under_checkpoint_and_vmap(self, wrap, reduction):
        """``vmap`` turns the ``lse`` conditional into a select of both
        branches: slower, and as exact."""
        h, w, labels, mask = head_problem()
        fused, naive = fused_and_naive(reduction, labels)
        if wrap == "checkpoint":
            got = jax.grad(jax.checkpoint(fused), argnums=(0, 1))(h, w, mask)
            want = jax.grad(naive, argnums=(0, 1))(h, w, mask)
        else:
            hs = jnp.stack([h, 2 * h])
            got = jax.vmap(jax.grad(fused, argnums=(0, 1)),
                           in_axes=(0, None, None))(hs, w, mask)
            want = jax.vmap(jax.grad(naive, argnums=(0, 1)),
                            in_axes=(0, None, None))(hs, w, mask)
        assert_trees_close(got, want, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n,chunk,bodies,differentiated", [
        # As written, a chunk's backward makes the tile again: logits in
        # the forward rule, then logits, dh, dW.  (Compiled where the
        # backward follows the forward, XLA merges the two logits
        # matmuls: PERF.md section 6, PR 26.)
        (32, 16384, 2, 8),
        (32, 8, 4, 16),
        (33, 16384, 1, 4),
        # Sixteen chunks are scanned: one body in the text of each rule.
        (32, 2, 1, 4),
    ])
    def test_head_matmuls_in_the_jaxpr(self, n, chunk, bodies,
                                       differentiated):
        v = 200
        h, w, labels, _ = head_problem(n=n, v=v)

        def loss(h, w):
            return fused_softmax_xent(h, w, labels, chunk).mean()

        # Every chunk's ``lse`` conditional holds one more, in the branch
        # of a sum that overflowed: the tile again, a few rows at a time.
        lse_conds = [[1, 0]] * bodies
        assert head_dots(jax.make_jaxpr(loss)(h, w).jaxpr, v) == (
            bodies, lse_conds)
        assert head_dots(
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w).jaxpr,
            v) == (differentiated, lse_conds)


# What is added to every logit of a row, and to those beyond the columns
# that the shift looks at; with them, whether ``sum exp(logits - shift)``
# stays finite.  An offset common to a row changes nothing (a trained
# GPT-2's logits sit near -100); a logit far above the shift overflows.
LOGIT_CASES = {"plain": (0.0, 0.0, True), "all_down": (-100.0, 0.0, True),
               "all_up": (100.0, 0.0, True), "spike": (0.0, 150.0, False),
               "spike_down": (-100.0, 150.0, False)}


class TestOnePassLse:
    """``lse`` comes from ``sum exp(logits - shift)``, the shift being the
    row's maximum over the first columns, while that sum is finite in
    every row of a chunk, and from the sum shifted by the row maximum
    over tiles made again where it is not."""

    @staticmethod
    def problem(offset, spike, spiked_rows=32, dtype=jnp.float32):
        from horovod_tpu.ops import losses

        n, v = 32, 300
        h, w, labels, mask = head_problem(n=n, d=8, v=v, dtype=dtype)
        # Two more columns of ``hidden`` against two more rows of ``w``:
        # ones against ``offset`` everywhere, and the spiked rows' ones
        # against ``spike`` beyond the shift's columns.
        spiked = (jnp.arange(n) < spiked_rows).astype(dtype)
        h = jnp.concatenate([h, jnp.ones((n, 1), dtype), spiked[:, None]],
                            axis=1)
        beyond = jnp.arange(v) >= losses._SHIFT_COLUMNS
        w = jnp.concatenate([w, jnp.full((1, v), offset),
                             spike * beyond[None, :]], axis=0)
        return h, w, labels, mask

    @pytest.fixture
    def nan_where_overflowed(self, monkeypatch):
        from horovod_tpu.ops import losses

        monkeypatch.setattr(
            losses, "_max_shifted_lse",
            lambda logits: jnp.full(logits.shape[:1], jnp.nan))

    # Of the 32 rows: the default two chunks and sixteen scanned in every
    # case, four unrolled in the plain one.
    @pytest.mark.parametrize("case,chunk", [
        (case, chunk) for case in sorted(LOGIT_CASES)
        for chunk in (16384, 2, 8) if case == "plain" or chunk != 8])
    def test_value_and_grads_match_reference(self, case, chunk):
        offset, spike, _ = LOGIT_CASES[case]
        h, w, labels, mask = self.problem(offset, spike)
        fused, naive = fused_and_naive("mean", labels, chunk)
        got = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(h, w, mask)
        want = jax.value_and_grad(naive, argnums=(0, 1))(h, w, mask)
        # Logits of a hundred or so cost float32 four decimal places of
        # ``x - lse``, in the reference as here.
        tol = (dict(rtol=1e-5, atol=1e-6) if case == "plain"
               else dict(rtol=3e-4, atol=1e-4))
        assert_trees_close(got, want, **tol)

    @pytest.mark.parametrize("case", sorted(LOGIT_CASES))
    def test_branch_follows_the_sum(self, case, nan_where_overflowed):
        """With the other branch made to return NaN, exactly the chunks
        whose sum overflowed lose their losses."""
        offset, spike, finite = LOGIT_CASES[case]
        h, w, labels, _ = self.problem(offset, spike)
        got = np.asarray(jax.jit(fused_softmax_xent)(h, w, labels))
        if finite:
            np.testing.assert_allclose(
                got, np.asarray(naive_loss(h, w, labels)), rtol=1e-5,
                atol=1e-5)
        else:
            assert np.isnan(got).all()

    def test_one_chunk_that_overflowed_falls_back_alone(
            self, nan_where_overflowed):
        # One row of the first chunk (of 16 rows) holds the spike.
        h, w, labels, _ = self.problem(0.0, 150.0, spiked_rows=1)
        got = np.asarray(jax.jit(fused_softmax_xent)(h, w, labels))
        assert np.isnan(got[:16]).all()
        np.testing.assert_allclose(
            got[16:], np.asarray(naive_loss(h, w, labels))[16:], rtol=1e-5,
            atol=1e-5)

    @pytest.mark.parametrize("rows,tiles", [(1024, 2), (30, 1), (1026, 3)])
    def test_overflowed_tiles_divide_the_chunk(self, rows, tiles):
        """The other branch tiles a chunk by the largest divisor of its
        rows up to ``_OVERFLOWED_ROWS``: its transient stays bounded
        whatever the chunk."""
        from horovod_tpu.ops import losses

        h = jnp.ones((rows, 4), jnp.float32)
        w = jnp.ones((4, 7), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda h, w: losses._tile_lse(losses._logits_tile(h, w), h, w))(
                h, w)
        cond, = (e for e in jaxpr.eqns if e.primitive.name == "cond")
        scans = [e for branch in cond.params["branches"]
                 for e in branch.jaxpr.eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [tiles]
        assert rows // tiles <= losses._OVERFLOWED_ROWS


def head_dots(jaxpr, v):
    """(``dot_general``s with a dimension of ``v`` outside any
    conditional, [their counts in the branches of each ``cond``])."""
    from jax.extend import core as jcore

    top, conds = 0, []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            conds.append([head_dots(branch.jaxpr, v)[0]
                          for branch in eqn.params["branches"]])
            continue
        if eqn.primitive.name == "dot_general" and any(
                v in var.aval.shape for var in (*eqn.invars, *eqn.outvars)):
            top += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jcore.Jaxpr):
                    sub_top, sub_conds = head_dots(sub, v)
                    top += sub_top
                    conds += sub_conds
    return top, conds


class TestThroughTrainStep:
    def test_shard_map_step_matches_reference(self):
        """One SGD step through ``make_train_step`` on the 8-device CPU
        mesh (the ``shard_map`` program) moves the parameters as the
        plain reference's gradients of the global batch would."""
        from jax.sharding import Mesh

        from horovod_tpu.jax.spmd import make_train_step

        devices = jax.devices()
        assert len(devices) == 8
        mesh = Mesh(np.asarray(devices), ("ranks",))
        rng = np.random.RandomState(11)
        vocab, d, n = 40, 8, 8 * 6
        params = {"emb": jnp.asarray(rng.randn(vocab, d), jnp.float32),
                  "head": jnp.asarray(rng.randn(d, vocab) * 0.1,
                                      jnp.float32)}
        batch = {"x": jnp.asarray(rng.randint(0, vocab, n), jnp.int32),
                 "y": jnp.asarray(rng.randint(0, vocab, n), jnp.int32)}

        def loss_fn(params, aux, batch):
            h = params["emb"][batch["x"]]
            return fused_softmax_xent(h, params["head"],
                                      batch["y"]).mean(), aux

        def reference(params):
            return naive_loss(params["emb"][batch["x"]], params["head"],
                              batch["y"]).mean()

        tx = optax.sgd(1.0)
        step = make_train_step(loss_fn, tx, mesh, sync_aux_state=False,
                               donate=False)
        new, _, _, loss = step(params, {}, tx.init(params), batch)
        jax.block_until_ready(new)
        want_loss, want = jax.value_and_grad(reference)(params)
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
        assert_trees_close(jax.tree.map(lambda a, b: a - b, params, new),
                           want, rtol=1e-5, atol=1e-6)
