"""A value and its gradients from ONE pass.

The suite pays for traces, lowerings and compiles, not for arithmetic
(``tools/tier1_times.py``; PR 56): a comparison that calls ``f`` for its
value and then ``jax.grad`` of a scalar of ``f`` lowers every kernel behind
``f`` twice, and one that differentiates a whole module eagerly compiles it
op by op.  The tests' comparisons go through here instead.
"""

import jax


def out_and_grads(f, scalar, *args, jit=False):
    """``(f(*args), gradients of scalar(f(*args)) on every argument)``.

    The forward's value is the differentiated pass's own (``has_aux``), so
    what a test asserts of the output and of the gradients is asserted of
    one run.  ``jit``: the pass as ONE program — for modules and models,
    whose eager differentiation compiles hundreds of small programs."""
    def both(*a):
        out = f(*a)
        return scalar(out), out

    run = jax.value_and_grad(both, tuple(range(len(args))), has_aux=True)
    (_, out), grads = (jax.jit(run) if jit else run)(*args)
    return out, grads
