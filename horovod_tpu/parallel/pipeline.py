"""Pipeline parallelism — GPipe-style microbatch schedule on a mesh axis.

Beyond the reference's scope (data-parallel only, SURVEY §2.3): layers are
partitioned into S stages, one per chip along the ``pp`` mesh axis, and a
batch is split into M microbatches that stream through the stages.  The
TPU-first realization runs *inside* ``shard_map``:

* every stage executes the SAME per-tick program (SPMD) — what differs is
  the pp-varying stage params and the tick's microbatch index;
* activations move stage→stage with ``lax.ppermute`` — one ICI neighbour
  hop, the cheapest possible transfer on the torus;
* the schedule is a ``lax.scan`` over ``M + S - 1`` ticks (the GPipe
  pipeline depth): static trip count, no data-dependent control flow, one
  compiled program.

Bubble fraction is ``(S-1)/(M+S-1)`` — pick ``M >= 4*S`` in practice.

Training runs under ``shard_map(..., check_vma=True)`` like tensor
parallelism: stage params are VMA-varying over ``pp`` (use
:func:`stage_params_init`), activations crossing ``ppermute`` and the
masked collection transpose correctly, so `jax.grad` through the whole
schedule gives exact per-stage gradients (asserted against a sequential
oracle in ``tests/test_pipeline.py``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

PP_AXIS = "pp"


def stage_params_init(init_fn: Callable[[jax.Array], Any], key,
                      axis: str = PP_AXIS):
    """Initialize per-stage params inside shard_map: folds the stage index
    into ``key`` so each stage draws distinct params, and marks every leaf
    VMA-varying over ``axis`` (constant initializers would otherwise be
    treated as one shared array; see tensor_parallel._per_shard_init)."""
    from horovod_tpu.parallel._vma import ensure_varying_tree
    stage_key = jax.random.fold_in(key, lax.axis_index(axis))
    return ensure_varying_tree(init_fn(stage_key), axis)


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   *, axis: str = PP_AXIS):
    """Run ``x`` through ``S`` pipelined stages; call inside shard_map.

    ``stage_fn(stage_params, activation) -> activation`` is ONE stage's
    computation (all stages must share in/out activation shape).
    ``stage_params`` is this shard's stage slice (pp-varying).
    ``x_microbatches``: ``(M, microbatch, ...)``, replicated across the
    ``pp`` axis.  Returns ``(M, microbatch, ...)`` outputs, replicated.

    Tick ``t``: stage ``s`` processes microbatch ``t - s`` (garbage outside
    ``[0, M)``, masked out at collection), then its output hops to stage
    ``s+1`` via ppermute.  After ``M + S - 1`` ticks the last stage has
    produced every microbatch; a masked psum replicates the result.
    """
    S = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    M = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]
    perm = [(i, i + 1) for i in range(S - 1)]   # forward chain, no wrap

    from horovod_tpu.parallel._vma import ensure_varying
    # The scan carry's variance must match the body's output: varying over
    # pp (per-stage state) and over every axis the input varies on (e.g.
    # dp when the batch is data-sharded on an outer mesh axis).
    carry_axes = set(jax.typeof(x_microbatches).vma) | {axis}
    state0 = jnp.zeros(mb_shape, x_microbatches.dtype)
    out0 = jnp.zeros((M,) + mb_shape, x_microbatches.dtype)
    for ax in sorted(carry_axes):
        state0 = ensure_varying(state0, ax)
        out0 = ensure_varying(out0, ax)

    def tick(carry, t):
        state, outputs = carry
        # Stage 0 feeds from the input queue; later stages from the wire.
        feed = x_microbatches[jnp.clip(t, 0, M - 1)]
        inp = jnp.where(stage == 0, feed, state)
        out = stage_fn(stage_params, inp)
        # The last stage finished microbatch t-(S-1) this tick.
        widx = t - (S - 1)
        widx_c = jnp.clip(widx, 0, M - 1)
        valid = jnp.logical_and(stage == S - 1, widx >= 0)
        outputs = outputs.at[widx_c].set(
            jnp.where(valid, out, outputs[widx_c]))
        state = lax.ppermute(out, axis, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state0, out0),
                               jnp.arange(M + S - 1))
    # Replicate the last stage's collected outputs to every stage.
    outputs = lax.psum(
        jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)), axis)
    return outputs


def microbatch(x, num_microbatches: int):
    """(B, ...) → (M, B/M, ...) for :func:`pipeline_apply`."""
    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"batch {B} not divisible by num_microbatches={num_microbatches}")
    return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])


def unmicrobatch(x):
    """Inverse of :func:`microbatch`."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
