"""Expert parallelism — Switch-style mixture-of-experts over a mesh axis.

Beyond the reference's scope (data-parallel only, SURVEY §2.3): the MLP is
replaced by E experts, one per chip along the ``ep`` mesh axis, and each
token is routed to one expert.  The TPU-first realization runs inside
``shard_map`` with tokens sharded over ``ep`` (data parallel within the
expert group):

* the router is a small replicated dense — top-1 (Switch) or top-k
  (GShard-style, renormalized combined gates) expert choice per token,
  with an optional ST-MoE router z-loss;
* dispatch is pure matmul: a ``(tokens, E, capacity)`` one-hot dispatch
  tensor built from a cumulative-sum position assignment — einsums instead
  of scatters, so everything lands on the MXU with static shapes;
* one ``lax.all_to_all`` ships each shard's per-expert buffers to the
  owning chips, the local expert FFN runs on its ``(E*capacity, d)``
  tokens, and a second all_to_all ships results home, where the same
  dispatch tensor combines them (weighted by the gate).

Tokens over capacity are dropped (pass through the residual only) — the
Switch behaviour, with first choices claiming slots before second
choices; size capacity with ``capacity_factor``.  The router's
load-balancing auxiliary loss (Switch eq. 4: ``E * Σ_e f_e · p_e``) plus
the weighted z-loss is returned alongside the output; add
``aux_weight * aux`` to the loss.

Training runs under ``shard_map(..., check_vma=True)`` like the other
model-parallel modules; expert params are VMA-varying over ``ep``.

:class:`DroplessMoE` is the other expert layer: many experts on ONE
shard, top-k of them per token, no capacity and no dropped token —
assignments sorted by expert and a grouped matmul over the sorted rows.
It is what a model with more experts than chips runs data-parallel today
(experts replicated), and the local half an expert-parallel layer will
wrap between two all-to-alls.  Until the two are folded into one
(ROADMAP R2): :class:`MoELayer` where each chip of an ``ep`` axis holds
one GELU expert and a capacity is acceptable, :class:`DroplessMoE`
everywhere else.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel._vma import per_shard_init as _expert_init

EP_AXIS = "ep"


class MoELayer(nn.Module):
    """Top-k MoE feed-forward, one expert per ``axis`` shard.

    ``top_k=1`` is Switch routing (raw gate probability weighting);
    ``top_k>=2`` is GShard-style: each token goes to its k best experts
    with the combined gates renormalized over the chosen k.  Capacity is
    assigned with choice priority — every first choice claims its slot
    before any second choice — so under pressure second choices drop
    first.

    Input ``(tokens_local, d)`` — this shard's tokens, sharded over
    ``axis``.  Returns ``(output, aux_loss)``: output ``(tokens_local,
    d)`` (zero rows for fully-dropped tokens — callers keep the residual
    connection), aux_loss the scalar per-shard auxiliary loss: the Switch
    load-balancing term plus ``router_z_weight`` times the router z-loss
    ``mean(logsumexp(logits)^2)`` (ST-MoE, keeps router logits from
    drifting into bf16-unfriendly magnitudes).  The components are also
    ``sow``n as intermediates ``aux_load_balance`` / ``aux_router_z``.
    """

    hidden: int
    capacity_factor: float = 1.25
    axis: str = EP_AXIS
    top_k: int = 1
    router_z_weight: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        E = lax.axis_size(self.axis)
        T, d = x.shape
        if not 1 <= self.top_k <= E:
            raise ValueError(f"top_k={self.top_k} out of range for {E} "
                             "experts")
        # GShard convention: capacity scales with top_k, so k*T assignments
        # fit at capacity_factor >= 1 under balanced routing.
        C = max(1, int(self.capacity_factor * self.top_k * T / E))

        # Router (replicated params): per-token expert scores.
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          param_dtype=self.param_dtype,
                          name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)           # (T, E)

        # Iterated argmax instead of a sort: k one-hot choice masks and
        # their gate probabilities, all static shapes for the MXU.
        remaining = probs
        onehots, gates = [], []
        for _ in range(self.top_k):
            expert = remaining.argmax(axis=-1)                    # (T,)
            oh = jax.nn.one_hot(expert, E, dtype=jnp.float32)     # (T, E)
            onehots.append(oh)
            gates.append((remaining * oh).sum(axis=-1))           # (T,)
            remaining = remaining * (1.0 - oh)
        if self.top_k == 1:
            weights = gates                  # Switch: raw gate probability
        else:
            denom = jnp.maximum(sum(gates), 1e-9)
            weights = [g / denom for g in gates]   # GShard: renormalized

        # Capacity slots with choice priority: each choice's tokens are
        # placed after every earlier choice's claims on that expert.
        claimed = jnp.zeros((E,), jnp.float32)
        disp = jnp.zeros((T, E, C), jnp.float32)
        comb = jnp.zeros((T, E, C), jnp.float32)
        for oh, w in zip(onehots, weights):
            pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh             # (T, E)
            pos_t = (pos.sum(-1) + (oh * claimed).sum(-1)).astype(
                jnp.int32)                                        # (T,)
            keep = (pos_t < C).astype(jnp.float32)
            slot = (oh[:, :, None]
                    * jax.nn.one_hot(pos_t, C, dtype=jnp.float32)[:, None, :]
                    * keep[:, None, None])                        # (T, E, C)
            disp = disp + slot
            comb = comb + w[:, None, None] * slot
            claimed = claimed + oh.sum(axis=0)

        # Local buffers -> owning experts -> FFN -> back home.
        buffers = jnp.einsum("td,tec->ecd", x.astype(self.dtype),
                             disp.astype(self.dtype))             # (E, C, d)
        recv = lax.all_to_all(buffers, self.axis, split_axis=0,
                              concat_axis=0)                      # (E, C, d)
        h = recv.reshape(E * C, d)
        w1 = self.param("w1", _expert_init(nn.initializers.lecun_normal(),
                                           self.axis),
                        (d, self.hidden), self.param_dtype)
        w2 = self.param("w2", _expert_init(nn.initializers.lecun_normal(),
                                           self.axis),
                        (self.hidden, d), self.param_dtype)
        h = jnp.dot(h.astype(self.dtype), w1.astype(self.dtype))
        h = nn.gelu(h)
        h = jnp.dot(h, w2.astype(self.dtype))
        sent = lax.all_to_all(h.reshape(E, C, d), self.axis,
                              split_axis=0, concat_axis=0)        # (E, C, d)
        # Dropped slots are exactly zero in comb, and the gate weighting
        # is already folded into it.
        out = jnp.einsum("ecd,tec->td", sent.astype(jnp.float32),
                         comb)                                    # (T, d)

        # Switch load-balancing aux loss on first choices: E * sum f_e p_e
        # where f_e is the fraction of tokens whose best expert is e, p_e
        # the mean router prob.
        f = onehots[0].mean(axis=0)
        p = probs.mean(axis=0)
        balance = E * jnp.sum(f * p)
        z = jax.scipy.special.logsumexp(logits, axis=-1)          # (T,)
        z_loss = jnp.mean(z ** 2)
        self.sow("intermediates", "aux_load_balance", balance)
        self.sow("intermediates", "aux_router_z", z_loss)
        aux = balance + self.router_z_weight * z_loss
        return out.astype(x.dtype), aux


# ------------------------------------------------------------ dropless


# What make_train_step wants to know of the expert layers its loss_fn
# holds: dicts that a DroplessMoE traced meanwhile writes its static sizes
# into, keyed by its module path (so a second trace of the same layer
# changes nothing).
_NOTING: list = []


def noting_expert_layers(fn: Callable, into: dict) -> Callable:
    """``fn``, with every :class:`DroplessMoE` traced inside a call of it
    written into ``into`` as ``{module path: (assignments, expert
    parameter bytes)}`` — one step's, per shard, from shapes alone."""

    @functools.wraps(fn)
    def noting(*args, **kwargs):
        _NOTING.append(into)
        try:
            return fn(*args, **kwargs)
        finally:
            _NOTING.pop()

    return noting


@jax.custom_vjp
def _to_expert_order(x, order, inverse):
    """Rows of ``x`` (N, d) as the k·N assignments sorted by expert:
    assignment ``a`` belongs to token ``a // k``.  A gather both ways: the
    cotangent comes home through ``inverse`` and is summed over a token's
    k assignments, where autodiff's transpose would scatter-add."""
    return x[order // (order.shape[0] // x.shape[0])]


def _to_expert_order_fwd(x, order, inverse):
    return _to_expert_order(x, order, inverse), (inverse, x.shape[0])


def _to_expert_order_bwd(res, g):
    inverse, n = res
    g = g[inverse].reshape(n, -1, g.shape[-1])
    return g.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@jax.custom_vjp
def _to_token_order(y, order, inverse):
    """Sorted assignment rows ``y`` (k·N, d) back in assignment order
    (token-major); the transpose is the gather through ``order``."""
    return y[inverse]


def _to_token_order_fwd(y, order, inverse):
    return y[inverse], order


def _to_token_order_bwd(order, g):
    return g[order], None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


class DroplessMoE(nn.Module):
    """Top-k of ``num_experts`` SwiGLU experts, all on this shard; no
    capacity, so no token is dropped whatever the imbalance.

    ``y = Σ_{e ∈ topk} p_e · W_down,e (silu(W_gate,e x) ⊙ W_up,e x)`` with
    ``p`` the router's softmax over all experts, not renormalised over
    the chosen k.  The router and its softmax run in float32 at full
    matmul precision (a TPU's default float32 matmul rounds its operands
    to bfloat16, which moves the k-th choice of many tokens); the experts
    run in ``dtype``.  The k·N assignments are sorted by expert and each
    projection is one grouped matmul over the sorted rows
    (``lax.ragged_dot``: FLOPs follow the assignments, N·k, not N·E), all
    shapes static.

    Input ``(..., d)``.  Returns ``(output, load_balance, router_z)``:
    the load-balancing term ``E · Σ_e f_e · P_e`` (``f_e`` = assignments
    to ``e`` ÷ tokens, ``P_e`` = mean router probability; ``top_k`` when
    routing is uniform) and the router z-loss ``mean(logsumexp(logits)²)``
    of this shard's tokens, unweighted.  Both are also sown as
    intermediates ``aux_load_balance`` / ``aux_router_z``
    (:func:`router_losses` sums them over a model's layers), beside
    ``tokens_per_expert`` (E,) and ``expert_index`` (N, k).
    """

    num_experts: int
    hidden: int
    top_k: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        E, k = self.num_experts, self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"top_k={k} out of range for {E} experts")
        lead, d = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, d)
        n = x.shape[0]

        with jax.named_scope("route"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              param_dtype=self.param_dtype,
                              precision=lax.Precision.HIGHEST,
                              name="router")(x.astype(jnp.float32))
            probs = jax.nn.softmax(logits, axis=-1)               # (N, E)
            gate, expert = lax.top_k(probs, k)                    # (N, k)

        with jax.named_scope("dispatch"):
            flat = expert.reshape(-1)              # assignment a: token a // k
            order = jnp.argsort(flat, stable=True)
            inverse = jnp.argsort(order)
            tokens_per_expert = jnp.bincount(flat, length=E).astype(
                jnp.int32)
            rows = _to_expert_order(x.astype(self.dtype), order, inverse)

        with jax.named_scope("experts"):
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            w_gate, w_up, w_down = (
                self.param(name, init, shape, self.param_dtype).astype(
                    self.dtype)
                for name, shape in (("w_gate", (E, d, self.hidden)),
                                    ("w_up", (E, d, self.hidden)),
                                    ("w_down", (E, self.hidden, d))))
            h = (nn.silu(lax.ragged_dot(rows, w_gate, tokens_per_expert))
                 * lax.ragged_dot(rows, w_up, tokens_per_expert))
            y = lax.ragged_dot(h, w_down, tokens_per_expert)      # (k·N, d)

        with jax.named_scope("combine"):
            y = _to_token_order(y, order, inverse).reshape(n, k, d)
            out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), gate)

        with jax.named_scope("router_losses"):
            f = lax.stop_gradient(tokens_per_expert / n)
            balance = E * jnp.sum(f * probs.mean(axis=0))
            z_loss = jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        self.sow("intermediates", "aux_load_balance", balance)
        self.sow("intermediates", "aux_router_z", z_loss)
        self.sow("intermediates", "tokens_per_expert", tokens_per_expert)
        self.sow("intermediates", "expert_index", expert)
        for noted in _NOTING:
            noted[self.path] = (n * k, 3 * E * d * self.hidden
                                * jnp.dtype(self.param_dtype).itemsize)
        return out.astype(x.dtype).reshape(*lead, d), balance, z_loss


def router_losses(intermediates) -> tuple:
    """``(load_balance, router_z)`` summed over every expert layer that
    sowed them into ``intermediates`` (what ``model.apply(...,
    mutable=["intermediates"])`` returns under that key)."""
    found = {"aux_load_balance": [], "aux_router_z": []}
    for path, value in jax.tree_util.tree_leaves_with_path(intermediates):
        for name, values in found.items():
            if any(getattr(key, "key", None) == name for key in path):
                values.append(value)
    return sum(found["aux_load_balance"]), sum(found["aux_router_z"])
