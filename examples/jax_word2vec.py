"""word2vec skip-gram — TPU-native counterpart of the reference's
``examples/tensorflow_word2vec.py``: the embedding gradient takes the
**sparse allgather path** (reference ``horovod/tensorflow/__init__.py:67-78``)
instead of a dense allreduce over the whole vocabulary.

Design: the forward gathers only the touched embedding rows; the backward
produces gradients for those rows, which are handed to the stock
``DistributedOptimizer`` as ``IndexedSlices`` — the wrapper routes them
through the sparse allgather automatically (rows+indices over the rank
mesh, comm cost ∝ batch size, not vocab size) and scatters to dense only
locally for the optax update.  ``sparse_as_dense=True`` would densify
before a regular allreduce instead, like the reference's escape hatch.

Corpus: synthetic Zipf-distributed token stream (the reference downloads
text8; this stays hermetic).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import sparse
from horovod_tpu.jax.spmd import shard_batch


def make_corpus(vocab, n_tokens, seed=0):
    rng = np.random.RandomState(seed)
    # Zipf-ish unigram distribution like natural text.
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    return rng.choice(vocab, size=n_tokens, p=p).astype(np.int32)


def skipgram_pairs(corpus, window, batch, rng):
    centers = rng.randint(window, len(corpus) - window, batch)
    offs = rng.randint(1, window + 1, batch) * rng.choice([-1, 1], batch)
    return corpus[centers], corpus[centers + offs]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=5000)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=128,
                   help="per-rank batch size")
    p.add_argument("--neg", type=int, default=8,
                   help="negative samples per pair (NCE-style)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--window", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.5)
    args = p.parse_args()

    hvd.init()
    mesh = hvd.ranks_mesh()
    n = hvd.size()
    global_batch = args.batch_size * n

    rng = np.random.RandomState(hash("w2v") % (2 ** 31))
    corpus = make_corpus(args.vocab, 200_000)

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    emb_in = jax.random.uniform(k1, (args.vocab, args.dim),
                                minval=-0.5 / args.dim,
                                maxval=0.5 / args.dim)
    emb_out = jax.random.uniform(k2, (args.vocab, args.dim),
                                 minval=-0.5 / args.dim,
                                 maxval=0.5 / args.dim)
    # Step 4 of the recipe: all ranks start from identical tables.
    emb_in, emb_out = hvd.jax.broadcast_parameters((emb_in, emb_out))
    params = {"emb_in": emb_in, "emb_out": emb_out}

    # The stock wrapper: IndexedSlices gradient leaves take the sparse
    # allgather path inside its update — no manual sparse.allreduce.
    tx = hvd.jax.DistributedOptimizer(optax.sgd(args.lr))
    opt_state = tx.init(params)

    def step_body(params, opt_state, centers, contexts, negs):
        """One sparse SGD step under shard_map (centers/contexts/negs are
        this rank's shard)."""
        emb_in, emb_out = params["emb_in"], params["emb_out"]
        c_rows = emb_in[centers]               # (B, D) touched rows only
        ctx_rows = emb_out[contexts]           # (B, D)
        neg_rows = emb_out[negs]               # (B, K, D)

        def loss_of(rows):
            c, ctx, neg = rows
            pos_logit = jnp.sum(c * ctx, axis=-1)
            neg_logit = jnp.einsum("bd,bkd->bk", c, neg)
            pos_loss = jax.nn.softplus(-pos_logit)
            neg_loss = jax.nn.softplus(neg_logit).sum(-1)
            return (pos_loss + neg_loss).mean()

        loss, (g_c, g_ctx, g_neg) = jax.value_and_grad(loss_of)(
            (c_rows, ctx_rows, neg_rows))

        # Row-gradients as IndexedSlices; both emb_out contributions
        # (context + negatives) concatenate into one slice-set —
        # duplicate indices sum, the IndexedSlices contract.
        grads = {
            "emb_in": sparse.IndexedSlices(g_c, centers, emb_in.shape),
            "emb_out": sparse.IndexedSlices(
                jnp.concatenate([g_ctx, g_neg.reshape(-1, g_neg.shape[-1])]),
                jnp.concatenate([contexts, negs.reshape(-1)]),
                emb_out.shape),
        }
        updates, opt_state2 = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state2, lax.pmean(loss, "ranks")

    # check_vma=False is deliberate here: the sparse path allgathers
    # (rows, indices) and scatter-adds the identical gathered data on every
    # rank, so the embedding update is invariant by construction — but an
    # all_gather output is *tracked* varying, which the checker cannot see
    # past.  The dense training paths all run checked (make_train_step).
    step = jax.jit(shard_map(
        step_body, mesh=mesh,
        in_specs=(P(), P(), P("ranks"), P("ranks"), P("ranks")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    t0 = time.perf_counter()
    loss = None
    for i in range(args.steps):
        centers, contexts = skipgram_pairs(corpus, args.window, global_batch,
                                           rng)
        negs = rng.randint(0, args.vocab,
                           (global_batch, args.neg)).astype(np.int32)
        centers, contexts, negs = shard_batch(
            (centers, contexts, negs), mesh)
        prev = loss
        params, opt_state, loss = step(params, opt_state, centers, contexts,
                                       negs)
        if prev is not None:
            # Lagged read (see jax_mnist_advanced.py): at most one step
            # queued behind the one that runs.
            prev.block_until_ready()
        if i % 50 == 0 and hvd.rank() == 0:
            print(f"step {i}: loss={float(np.asarray(loss)):.4f}")
    if hvd.rank() == 0:
        dt = time.perf_counter() - t0
        print(f"{args.steps} steps in {dt:.2f}s "
              f"({args.steps * global_batch / dt:.0f} pairs/sec); "
              f"final loss {float(np.asarray(loss)):.4f}")


if __name__ == "__main__":
    main()
