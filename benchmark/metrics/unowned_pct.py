"""Share of device 0's op time a step that no part of the program owns:
100 x the self time of the ops with no owner / the self time of all ops.
An op has no owner where its label carries no name stack at all (the
compiler's own ``fusion``, ``copy-done``, ...) or where the stack, with
the ``jit(...)/`` and ``shard_map/`` wrappers taken off, is one token
that is a primitive's name (``add``): it says what ran and not whose it
was.  (A one-token stack that is no primitive is a name the compiler
gave for what the op serves — the cast of a parameter is called
``params['head']['kernel']`` — and owns itself.)  The forward
and backward pass are owned by the flax modules and the layers' scopes;
the rest of the step by ``make_train_step``'s ``STEP_SCOPES``
(``grad_reduce``, ``optimizer``, ``aux_sync``), and this is the measure of
those: read only for a program that has them (a parent commit's reading
belongs in its PR's account, not on the ledger).  XLA names a fusion after
its root, so an update fused into a weight-gradient fusion is owned by the
gradient's module; what is left here is what the compiler made with no
name to inherit.

This file also holds what the three readers of the step's scopes share
(``optimizer_ms``, ``grad_reduce_ms``)."""

import re

UNIT = "%"
LAYER = "device"
MOVES = "step_ms"

_WRAPPER = re.compile(r"jit\(\w*\)|shard_map")
_PRIMITIVE = re.compile(r"[A-Za-z_][\w\-]*")


def step_scopes():
    """The program's ``STEP_SCOPES``; None where it has none."""
    try:
        from horovod_tpu.jax import spmd
    except ImportError:
        return None
    return getattr(spmd, "STEP_SCOPES", None)


def owners(label: str) -> list:
    """The tokens of an op label of ``tracered.label`` that say whose the
    op is: its name stack without the wrappers before it and without the
    primitive, its last token.  Empty for an op with no owner."""
    stack, bracket, _ = label.partition(" [")
    if not bracket:
        return []       # no name stack: the label is the op's own name
    tokens = stack.split("/")
    while tokens and _WRAPPER.fullmatch(tokens[0]):
        tokens.pop(0)
    if tokens and _PRIMITIVE.fullmatch(tokens[-1]):
        tokens.pop()
    return tokens


def under_scope(label: str, scope: str) -> bool:
    """Whether the op stands under ``scope``; under ``grad_reduce``
    nothing counts as any other scope's, so a reduction inside the
    optimizer (``optimizer/grad_reduce/...``) is counted once, as
    reduction."""
    own = owners(label)
    if "grad_reduce" in own:
        return scope == "grad_reduce"
    return scope in own


def scope_ms(trace, scope: str):
    """Device 0's self time a step under ``scope``, ms: None without a
    trace or for a program without that scope, 0.0 where it has the scope
    and no op ran alone under it (the compiler fused them all away)."""
    if trace is None or scope not in (step_scopes() or ()):
        return None
    d = trace["devices"][0]
    return 1e3 * sum(s for label, s in d["op_self_s"].items()
                     if under_scope(label, scope)) / d["steps"]


def read(record, trace):
    if trace is None or not step_scopes():
        return None
    ops = trace["devices"][0]["op_self_s"]
    total = sum(ops.values())
    if total <= 0:
        return None
    return 100.0 * sum(s for label, s in ops.items()
                       if not owners(label)) / total
