"""VMA (varying-manual-axes) helpers shared by the model-parallel modules.

Under ``shard_map(..., check_vma=True)`` JAX tracks whether each value is
invariant or varying across every manual mesh axis; the psum/pvary
transpose pairing that makes model-parallel gradients exact depends on
per-shard parameters actually being *varying*.  A constant initializer
(``zeros``) produces a value with no data dependence on the shard index,
which the tracker would classify invariant — i.e. one shared array whose
gradient gets cross-shard summed.  ``ensure_varying`` closes that hole.
"""

from __future__ import annotations

import jax
from jax import lax


def ensure_varying(v, axis):
    """Mark ``v`` varying over manual ``axis`` (a name or tuple of names)
    if it isn't already."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    missing = tuple(a for a in axes if a not in jax.typeof(v).vma)
    if missing:
        v = lax.pcast(v, missing, to="varying")
    return v


def ensure_varying_tree(tree, axis):
    """:func:`ensure_varying` over every leaf of a pytree."""
    return jax.tree.map(lambda v: ensure_varying(v, axis), tree)


def per_shard_init(init, axis: str):
    """Wrap a flax initializer so each shard along ``axis`` draws a
    distinct, VMA-varying slice: folds the shard index into the RNG key
    and marks the result varying (constant initializers like ``zeros``
    ignore the key and would otherwise be classified invariant — i.e. one
    shared array whose gradient gets cross-shard summed)."""
    from jax import lax

    def wrapped(key, shape, dtype):
        return ensure_varying(
            init(jax.random.fold_in(key, lax.axis_index(axis)),
                 shape, dtype), axis)
    return wrapped
