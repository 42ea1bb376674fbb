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
(experts replicated) and, told which experts it ``held``, the local half
an expert-parallel layer will wrap between two all-to-alls (one chip's
share of a layer, without its exchange; ROADMAP R1).  Until the two are
folded into one (ROADMAP D11): :class:`MoELayer` where each chip of an
``ep`` axis holds one GELU expert and a capacity is acceptable,
:class:`DroplessMoE` everywhere else.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.layer_notes import note_layer, noting_layers
from horovod_tpu.ops import _pallas
from horovod_tpu.ops.grouped_matmul import (
    LANDING_TOKENS, grouped_gradients, grouped_matmul, grouped_plan,
    landed_rows)
from horovod_tpu.parallel._vma import ensure_varying_tree
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


class WindowPlan(NamedTuple):
    """What :func:`_window_plan` decides for a layer that holds a share of
    its experts."""
    rows: int       # W: sorted rows a window
    windows: int    # the most a step can run, ceil(n k / W); 1: permuted
    uniform: int    # U: what uniform routing sends to the held experts


# A held share that takes this part or more of a layer's assignments under
# uniform routing sorts them ALL as one window: its rows then move as
# gathers through the sort's permutation, which cost less than a window's
# scatter-add of a third of them (PERF.md section 6, PR 45).
_PERMUTED_SHARE = 3
# Rows of a window the kernels cut evenly (``grouped_matmul._ROWS``), and
# the sublanes of a row tile below that.
_WINDOW_TILE = 512
# What a window of a held share costs on a v5e, fitted to ``chip_smoke.py``'s
# ``held_windows`` table at five cells' layers (PERF.md section 6, PR 53).
# A part that does not follow its rows, by the byte of the experts' float32
# weight gradient (its accumulate over the windows, each kernel's pass over
# every expert's matrix): 1.4 to 4.5 ms.  And a part by the row: its moves
# by the byte (gather, masks, casts, the float32 sums both ways) and
# its products by the FLOP, forward, again in the backward pass and both
# gradients: 0.38 to 0.77 us.  Since the sums are products that accumulate
# in place the table reads 1.0 to 2.8 ms and 0.34 to 0.65 us (PERF.md
# section 6, PR 57); the constants stand, because ``W`` comes out the same
# or within a row tile or two, and moves with the margin over ``U`` that is
# the rule's next change (ROADMAP S2 (e)).
_WINDOW_S_PER_EXPERT_BYTE = 11e-12
_ROW_S_PER_BYTE = 75e-12
_ROW_S_PER_FLOP = 1 / 197e12


def _window_plan(*, assignments: int, held: int, routed: int,
                 row_bytes: int, expert_bytes: int) -> WindowPlan:
    """The rows ``W`` of a window of a layer that holds ``held`` of the
    ``routed`` outputs its ``assignments`` (n k) are routed over — the one
    place that chooses, a pure function of the layer's shapes
    (``row_bytes`` a gathered row, ``expert_bytes`` the held experts'
    weight gradient in float32).

    A step runs ``ceil(landed / W)`` windows for the ``landed`` assignments
    its routing sent here (the device reads the count), so a window is the
    unit its work is rounded up to: half a window's rows are gathered,
    masked and landed for nothing a layer on average, and every
    window that runs pays once for what does not follow its rows.  The two
    balance at ``W = sqrt(2 U fixed / row)`` for a load near the uniform
    ``U`` — and ``W`` is never under ``U``: a router that balances sends
    ``U``, and a window a little smaller would run two for it."""
    uniform = assignments * held // routed
    if _PERMUTED_SHARE * uniform >= assignments:
        return WindowPlan(assignments, 1, uniform)
    fixed = _WINDOW_S_PER_EXPERT_BYTE * expert_bytes
    # A row meets one expert's matrices four times, 2 FLOPs a parameter
    # (4 bytes of ``expert_bytes``) each.
    row = (_ROW_S_PER_BYTE * row_bytes
           + _ROW_S_PER_FLOP * 2 * expert_bytes / held)
    tile = _WINDOW_TILE if uniform >= _WINDOW_TILE else 8
    rows = max(uniform, math.sqrt(2 * uniform * fixed / row))
    rows = min(assignments, -(-int(rows) // tile) * tile)
    return WindowPlan(rows, -(-assignments // rows), uniform)


def _lands_by_product(form: str, tokens: int) -> bool:
    """Whether a window's rows land on their tokens, and its weight
    gradients on their carry, as grouped transposed products that
    accumulate in place (``grouped_matmul.landed_rows``, ``grouped_gradients``
    handed its block) — the one place that chooses, by shapes alone: where
    the grouped matmuls' plan takes the kernels and the tokens cut into the
    landing's tiles.  ``lax.ragged_dot``'s form (the CPU under
    ``shard_map``, odd widths) scatter-adds the rows and adds the weight
    gradients in a pass of their own."""
    return form == "kernels" and tokens % LANDING_TOKENS == 0


def _pad_hidden(a, axis: int, lanes: int):
    """``a`` with zeros behind its ``axis`` up to a multiple of ``lanes``:
    the held experts' hidden width inside the grouped matmuls (the
    parameters keep theirs; a width that is a multiple already is left
    alone).  ``lanes`` is what the grouped matmuls' plan says they want:
    whole 128-lane tiles for the kernels (1856 -> 1920), 256 for
    ``lax.ragged_dot`` (2048)."""
    pad = -a.shape[axis] % lanes
    if not pad:
        return a
    return jnp.pad(a, [(0, pad if i == axis else 0) for i in range(a.ndim)])


# benchmark/tests/test_flops_keye.py imports the notes' wrapper under the
# name it had while it lived here; the benchmark's files are not every
# PR's to edit (ROADMAP.md D12 takes this alias away with that import).
noting_expert_layers = noting_layers


# The row moves of a layer whose sorted rows are ALL k·N assignments —
# every expert here, or a held share whose window is every assignment —:
# a gather each way, forward and backward, through the sort's ``order`` and
# its ``inverse``.  A held share with a smaller window gathers each
# window's rows and lands them home (``_held_windows``: by a grouped
# transposed product where the kernels run, ``_land``; by scatter-add under
# ``lax.ragged_dot``): a gather through ``inverse`` would move k·N rows to
# bring W of them home.
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


# ------------------------------------------------- a held share's windows


class _Held(NamedTuple):
    """What is static of a held share's windows."""
    top_k: int
    rows: int           # W
    activation: str
    dtype: Any
    plan: Any           # the grouped matmuls' plan over W rows
    interpret: bool
    products: bool      # ``_lands_by_product``: no scatter-add of rows


def _activate(activation, up, gate=None):
    """The experts' hidden activations of their projections."""
    if activation == "swiglu":
        return nn.silu(gate) * up
    return jnp.square(nn.relu(up))


def _projections(activation):
    """The matrices whose products ``_activate`` reads, in its order."""
    return ("w_up", "w_gate") if activation == "swiglu" else ("w_up",)


def _hidden(rows, w, group_sizes, activation, plan, interpret):
    """The experts' hidden activations on sorted ``rows``."""
    return _activate(activation, *(
        grouped_matmul(rows, w[name], group_sizes, plan, interpret=interpret)
        for name in _projections(activation)))


def _held_rows(rows, w, here, sizes, held: _Held):
    """The grouped matmuls over sorted ``rows``, ``sizes`` of them an
    expert.  A row where ``here`` is false belongs to no expert here and
    is masked to nothing on its way in and out."""
    with jax.named_scope("experts"):
        rows = jnp.where(here, rows.astype(held.dtype), 0)
        h = jnp.where(here, _hidden(rows, w, sizes, held.activation,
                                    held.plan, held.interpret), 0)
        return jnp.where(here, grouped_matmul(
            h, w["w_down"], sizes, held.plan, interpret=held.interpret), 0)


def _padded(w, held: _Held):
    """The held experts' matrices as the grouped matmuls read them: in the
    activations' dtype, the hidden width padded as their plan says."""
    with jax.named_scope("experts"):
        return {name: _pad_hidden(a.astype(held.dtype),
                                  1 if name == "w_down" else 2,
                                  held.plan.lanes)
                for name, a in w.items()}


def _window(i, x, gate, order, ends, group_sizes, landed, held: _Held):
    """Window ``i`` of the sorted assignments, ``[i W, (i + 1) W)``: the
    assignments, their tokens, which of the rows landed here, each expert's
    group cut to the window, and the rows and gates gathered."""
    W = held.rows
    lo = i * W
    with jax.named_scope("dispatch"):
        a = lax.dynamic_slice_in_dim(order, lo, W)
        token = a // held.top_k
        here = ((lo + jnp.arange(W)) < landed)[:, None]
        sizes = jnp.clip(jnp.minimum(ends, lo + W)
                         - jnp.maximum(ends - group_sizes, lo), 0, W)
        return a, token, here, sizes, x[token], gate.reshape(-1)[a]


def _gated(y, g, here):
    """What a window's results ``y`` add to their tokens: float32, each
    row times its gate ``g``."""
    return y.astype(jnp.float32) * jnp.where(here, g[:, None], 0.0)


def _weighted(rows, g, w, here, sizes, held: _Held):
    """What a window's gathered ``rows`` add to their tokens: the experts'
    results in float32, each times its gate ``g``."""
    y = _held_rows(rows, w, here, sizes, held)
    with jax.named_scope("combine"):
        return _gated(y, g, here)


def _land(block, rows, token, here, gate, held: _Held):
    """``block`` (n, d) float32 with a window's ``rows`` — each times its
    float32 ``gate`` where one is given — added to their tokens, in place
    and with no scatter: the rows are put in token order (one sort of W
    tokens, one gather of W rows in the activations' dtype) and landed by
    ``grouped_matmul.landed_rows``.  A row that is not ``here`` takes a
    token past the last and lands nowhere."""
    token = jnp.where(here[:, 0], token, block.shape[0])
    by_token = jnp.argsort(token)
    return landed_rows(
        block, rows[by_token], token[by_token],
        None if gate is None else gate[by_token], plan=held.plan,
        interpret=held.interpret)


def _window_gradients(rows, g, w, here, sizes, d_out, dw, held: _Held):
    """A window's backward pass written out: ``(d_rows, d_g, dw)`` for the
    cotangent ``d_out`` of what ``_weighted`` gives — its products again,
    then each product's two transposes called directly
    (``grouped_matmul.grouped_gradients``), so that every weight gradient
    is summed onto its float32 carry ``dw[name]`` by the kernel that forms
    it.  The activation's and the gate's derivatives are ``jax.vjp``'s."""
    names = _projections(held.activation)

    def gradients(x, name, dy):
        d_x, dw[name] = grouped_gradients(
            x, w[name], dy, sizes, held.plan, interpret=held.interpret,
            block=dw[name])
        return d_x

    with jax.named_scope("experts"):
        rows = jnp.where(here, rows.astype(held.dtype), 0)
        h, pull_h = jax.vjp(
            functools.partial(_activate, held.activation),
            *(grouped_matmul(rows, w[name], sizes, held.plan,
                             interpret=held.interpret) for name in names))
        h = jnp.where(here, h, 0)
        y = jnp.where(here, grouped_matmul(
            h, w["w_down"], sizes, held.plan, interpret=held.interpret), 0)
    with jax.named_scope("combine"):
        d_y, d_g = jax.vjp(lambda y, g: _gated(y, g, here), y, g)[1](d_out)
    with jax.named_scope("experts"):
        dw = dict(dw)
        d_h = gradients(h, "w_down", jnp.where(here, d_y, 0))
        d_rows = sum(gradients(rows, name, d) for name, d in zip(
            names, pull_h(jnp.where(here, d_h, 0))))
        return jnp.where(here, d_rows, 0), d_g, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_windows(held: _Held, x, gate, w, order, ends, group_sizes,
                  landed):
    """The held experts' share of the output, (n, d) float32: the sorted
    assignments a window of ``W`` rows at a time, ``ceil(landed / W)`` of
    them — a trip count the device reads, so the work follows the load.
    Each window gathers its rows by ``x[token]``, runs the grouped matmuls
    with each expert's group cut to the window, and adds the weighted
    results to their tokens in float32: landed by a grouped transposed
    product that accumulates in place where ``held.products``
    (:func:`_land`), scatter-added otherwise.

    A loop of a data-dependent length has no reverse-mode rule, so this
    carries its own: the backward pass keeps ``(x, gate, w)`` and the
    integer operands, no window's rows, and runs the same windows again
    with ``dx``, ``dgate`` and the experts' ``dW`` summed over them in
    float32 — ``dx`` landed as the output is and each ``dW`` summed onto
    its carry by the kernel that forms it (:func:`_window_gradients`)
    where ``held.products``; each window through ``jax.vjp``, ``dx``
    scatter-added and ``dW`` added in a pass of its own, otherwise."""
    padded = _padded(w, held)

    def body(i, out):
        _, token, here, sizes, rows, g = _window(
            i, x, gate, order, ends, group_sizes, landed, held)
        if held.products:
            y = _held_rows(rows, padded, here, sizes, held)
            with jax.named_scope("combine"):
                return _land(out, y, token, here, g, held)
        y = _weighted(rows, g, padded, here, sizes, held)
        with jax.named_scope("combine"):
            return out.at[token].add(y)

    return lax.fori_loop(0, -(-landed // held.rows), body,
                         jnp.zeros_like(x, dtype=jnp.float32))


def _held_windows_fwd(held, x, gate, w, order, ends, group_sizes, landed):
    return (_held_windows(held, x, gate, w, order, ends, group_sizes, landed),
            (x, gate, w, order, ends, group_sizes, landed))


def _held_windows_bwd(held, res, ct):
    x, gate, w, order, ends, group_sizes, landed = res
    padded = _padded(w, held)

    def body(i, carry):
        dx, dgate, dw = carry
        a, token, here, sizes, rows, g = _window(
            i, x, gate, order, ends, group_sizes, landed, held)
        if held.products:
            with jax.named_scope("combine"):
                d_out = ct[token]
            d_rows, d_g, dw = _window_gradients(
                rows, g, padded, here, sizes, d_out, dw, held)
            with jax.named_scope("dispatch"):
                return (_land(dx, d_rows, token, here, None, held),
                        dgate.at[a].add(d_g), dw)
        _, pull = jax.vjp(
            lambda rows, g, w: _weighted(rows, g, w, here, sizes, held),
            rows, g, padded)
        with jax.named_scope("combine"):
            d_out = ct[token]
        d_rows, d_g, d_w = pull(d_out)
        with jax.named_scope("dispatch"):
            dx = dx.at[token].add(d_rows.astype(jnp.float32))
            dgate = dgate.at[a].add(d_g)
        with jax.named_scope("experts"):
            dw = {name: dw[name] + d_w[name].astype(jnp.float32)
                  for name in dw}
        return dx, dgate, dw

    def zeros(like):
        # Zeros that vary over the mesh axes the operand varies over.
        return jnp.zeros_like(like, dtype=jnp.float32)

    dx, dgate, dw = lax.fori_loop(
        0, -(-landed // held.rows), body,
        (zeros(x), zeros(gate.reshape(-1)), jax.tree.map(zeros, padded)))
    with jax.named_scope("experts"):
        dw = {name: lax.slice(a, (0, 0, 0), w[name].shape).astype(
            w[name].dtype) for name, a in dw.items()}
    return (dx.astype(x.dtype), dgate.reshape(gate.shape).astype(gate.dtype),
            dw, None, None, None, None)


_held_windows.defvjp(_held_windows_fwd, _held_windows_bwd)


class _SharedExpert(nn.Module):
    """One expert that every token runs, of the layer's own activation:
    ``W_down relu(W_up x)²``, or ``W_down (silu(W_gate x) ⊙ W_up x)``."""
    hidden: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    activation: str = "relu2"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.lecun_normal()
        if self.activation == "swiglu":
            w_gate = self.param("w_gate", init, (d, self.hidden),
                                self.param_dtype)
        w_up = self.param("w_up", init, (d, self.hidden), self.param_dtype)
        w_down = self.param("w_down", init, (self.hidden, d),
                            self.param_dtype)
        up = x @ w_up.astype(self.dtype)
        if self.activation == "swiglu":
            h = nn.silu(x @ w_gate.astype(self.dtype)) * up
        else:
            h = jnp.square(nn.relu(up))
        return h @ w_down.astype(self.dtype)


class DroplessMoE(nn.Module):
    """Top-k of ``num_experts`` experts on this shard; no capacity, so no
    token is dropped whatever the imbalance.

    The defaults are OLMoE's layer: ``y = Σ_{e ∈ topk} p_e · W_down,e
    (silu(W_gate,e x) ⊙ W_up,e x)`` with ``p`` the router's softmax over all
    experts, not renormalised over the chosen k.  The router and its
    scores run in float32 at full matmul precision (a TPU's default
    float32 matmul rounds its operands to bfloat16, which moves the k-th
    choice of many tokens); the experts run in ``dtype``.  The k·N
    assignments are sorted by expert and each projection is one grouped
    matmul over the sorted rows (``ops/grouped_matmul.py``: Pallas kernels
    where its plan takes them, ``lax.ragged_dot`` otherwise; FLOPs follow
    the assignments, N·k, not N·E), all shapes static.

    The other settings (Nemotron-H's layer sets all of them):

    * ``router="sigmoid"``: scores ``s = sigmoid(W_r x)``, the k largest
      chosen; ``renormalize``: gates ``s_e / Σ_chosen s``; ``gate_scale``
      multiplies them.
    * ``choice_bias=γ > 0`` (``router="sigmoid"``): the k are the largest
      of ``s + b`` — the bias chooses and never gates — with ``b`` (one
      entry a router output, float32, zeros at first) NO parameter: it is
      the variable ``choice_bias`` of the collection ``"balance"``, so it
      carries no gradient, and where the caller makes that collection
      mutable the layer moves it itself, under the trace scope
      ``route/bias_update``: ``b_e ← b_e + γ · sign(mean load − load_e)``
      with ``load`` the ``tokens_per_expert`` this call counted (DeepSeek-V3's
      auxiliary-loss-free balancing; this shard's tokens over all the
      router's outputs — a deployment would all-reduce the counts first).
      ``make_train_step``'s ``aux_state`` is where it lives: ``loss_fn``
      applies the model with ``{"params": params, **aux_state}`` and
      ``mutable=["balance"]`` and returns what comes back.  Sown beside
      ``tokens_per_expert``: ``choice_bias_absmax`` (of the bias the choice
      read); noted: ``moe.bias_updates`` (1 a layer and step).  0: no
      variable, and the layer of the commits before.
    * ``activation="relu2"``: experts of two matrices,
      ``W_down,e relu(W_up,e x)²``.
    * ``shared_hidden > 0``: one more expert that wide and of the same
      ``activation``, run by every token with gate 1 (submodule and trace
      scope ``shared``).
    * ``held=(first, count)``: this shard HOLDS experts ``first ..
      first + count - 1`` of the ``num_experts`` it routes over — one
      chip's share of an expert-parallel layer, without its exchange.
      The k are chosen and the gates normalised over all experts;
      assignments to experts held elsewhere add nothing here, and the
      parameters are the held experts' alone.  Still no capacity, and
      the work follows the load: the held experts' assignments are sorted
      to the front and the grouped matmuls run over them a window of ``W``
      sorted rows at a time, ``ceil(landed / W)`` windows for the
      ``landed`` assignments this step's routing sent here — a trip count
      the device reads, so a step costs what its routing asks and none is
      lost at any load.  ``W`` is :func:`_window_plan`'s, a function of the
      layer's shapes (what uniform routing sends here or somewhat more,
      in whole row tiles of the kernels).  A window gathers its rows by
      ``x[token]`` and adds the weighted results to their tokens in float32
      (:func:`_held_windows`, a ``jax.custom_vjp``: the backward pass
      keeps the layer's inputs, no window's rows, and runs the same
      windows again with ``dx``, ``dgate`` and the experts' ``dW`` summed
      over them in float32).  Where the grouped matmuls' plan takes the
      kernels and the tokens cut into the landing's tiles
      (:func:`_lands_by_product`, shapes alone) none of the three float32
      sums is a scatter or a pass of its own: the window's rows are put in
      token order and landed — ``out`` under the float32 gate, ``dx`` bare —
      by the weight gradient's own walk over tiles of tokens, its left
      operand the selection of each row's token, on the loop's carry in
      place (``grouped_matmul.landed_rows``), and every ``dW`` is summed
      onto its float32 carry by the kernel that forms it
      (``grouped_gradients`` handed its block; the window's backward is
      written out, :func:`_window_gradients`).  Under ``lax.ragged_dot``
      the rows are scatter-added and ``dW`` is added in a pass.  Sown
      beside the rest: ``held_assignments``,
      the number that landed here, and ``held_windows``, the windows that
      ran; noted: ``moe.held_assignments`` (what uniform routing sends)
      and ``moe.window_rows`` (``W``).  Where the held experts take a third
      or more of the outputs routed over (top-1 with 8 of 17 held) the one
      window is EVERY assignment.  It sorts a whole permutation, so its
      rows go to expert order and come back as gathers through it
      (``_to_expert_order`` / ``_to_token_order``, as where every expert is
      here) and nothing is scatter-added: the same rows, masks and
      products, the grouped matmuls skipping the strips past ``landed``,
      the expert block recomputed in the backward pass.  Noted beside the
      rest: ``moe.permuted_assignments``, ``n · k`` where the rows moved
      through the permutation, 0 where windows of them were gathered and
      landed, and ``moe.landed_by_product``, ``W`` where a window's rows
      land through the product, 0 where they are scatter-added or the one
      window is every assignment.
    * ``router="mlp"``: the router is a small network that carries a state
      from one expert layer to the next, and the layer is called as
      ``layer(x, router_state)``.  ``r = W_d x + b_d`` (``router_hidden``
      wide), ``r ← r + γ ⊙ router_state`` where a state is given (``γ``
      learned, init 1), logits ``W_3 gelu(W_2 gelu(W_1 norm(r) + b_1) +
      b_2)`` with ``norm`` an RMSNorm (``norm_eps``) and the exact GELU,
      scores their softmax; the k chosen by ``scores + choice_bias`` (a
      parameter held at zero and outside the gradient: nothing here updates
      it), the lower index on a tie, and gated by the scores alone.
      Parameters ``router_down``, ``router_state_scale``, ``router_norm``,
      ``router_fc1``, ``router_fc2``, ``router_out``, ``choice_bias``; trace
      scopes ``route/down``, ``route/eda``, ``route/mlp``.  ``r``, float32,
      is returned as a fourth result for the next layer.
    * ``skip_choice``: the router has ONE MORE output than there are
      experts, a choice that computes nothing.  It is routed over like an
      expert (``tokens_per_expert`` has ``num_experts + 1`` entries, the
      gates are normalised with it), belongs to no shard's ``held`` range
      and is sent to no rows; sown: ``skipped_assignments``.
    * ``latent > 0``: the routed experts work in a latent that wide
      (LatentMoE): ``z = W_down x`` once for every token (submodule and
      trace scope ``latent_down``; no bias, norm or activation), the
      experts' matrices are ``(latent, hidden)`` and ``(hidden, latent)``,
      the sorted rows, the grouped matmuls' plan, the held share's window
      and the float32 combine are all ``latent`` wide, and ``W_up`` takes
      the combined rows back to the model's width (``latent_up``) —
      linear, so the shares of a layer's experts still add up.  The router
      and the shared expert read ``x`` itself.  Noted beside the rest:
      ``moe.latent`` (the width) and ``moe.row_bytes`` (bytes a gathered
      row: what an expert-parallel exchange would move an assignment).

    Input ``(..., d)``.  Returns ``(output, load_balance, router_z)``:
    the load-balancing term ``E · Σ_e f_e · P_e`` (``f_e`` = assignments
    to ``e`` ÷ tokens, ``P_e`` = mean router score; ``top_k`` when
    routing is uniform under a softmax) and the router z-loss
    ``mean(logsumexp(logits)²)`` of this shard's tokens, unweighted.
    Both are also sown as intermediates ``aux_load_balance`` /
    ``aux_router_z`` (:func:`router_losses` sums them over a model's
    layers), beside ``tokens_per_expert`` (E,) and ``expert_index``
    (N, k).
    """

    num_experts: int
    hidden: int
    top_k: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router: str = "softmax"              # "softmax" | "sigmoid" | "mlp"
    renormalize: bool = False
    gate_scale: float = 1.0
    activation: str = "swiglu"           # "swiglu" | "relu2"
    shared_hidden: int = 0
    held: Optional[Tuple[int, int]] = None
    router_hidden: int = 0               # router="mlp": the state's width
    skip_choice: bool = False
    norm_eps: float = 1e-5               # of the mlp router's norm
    latent: int = 0                      # the routed experts' width; 0: d
    choice_bias: float = 0.0             # router="sigmoid": the update's γ

    @property
    def routed_over(self) -> int:
        """Outputs of the router: the experts and the skip choice."""
        return self.num_experts + bool(self.skip_choice)

    @nn.compact
    def __call__(self, x, router_state=None):
        E, k = self.num_experts, self.top_k
        if not 1 <= k <= E:
            raise ValueError(f"top_k={k} out of range for {E} experts")
        if self.router not in ("softmax", "sigmoid", "mlp") or (
                self.activation not in ("swiglu", "relu2")):
            raise ValueError(f"unknown router {self.router!r} or "
                             f"activation {self.activation!r}")
        if router_state is not None and self.router != "mlp":
            raise ValueError("only router='mlp' carries a state; got "
                             f"router={self.router!r}")
        if self.choice_bias and (self.router != "sigmoid"
                                 or self.choice_bias < 0):
            raise ValueError("choice_bias is the step γ > 0 of the sigmoid "
                             "router's balancing bias (router='mlp' holds "
                             "its own, untrained); got "
                             f"{self.choice_bias} for {self.router!r}")
        lead, d = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, d)
        n = x.shape[0]
        routed = self.routed_over

        with jax.named_scope("route"):
            if self.router == "mlp":
                logits, router_state = self._mlp_router(
                    x.astype(jnp.float32), router_state)
                probs = jax.nn.softmax(logits, axis=-1)      # (N, routed)
                bias = self.param("choice_bias", nn.initializers.zeros,
                                  (routed,), self.param_dtype)
                _, expert = lax.top_k(probs + lax.stop_gradient(bias), k)
                gate = jnp.take_along_axis(probs, expert, axis=-1)
            else:
                logits = nn.Dense(routed, use_bias=False, dtype=jnp.float32,
                                  param_dtype=self.param_dtype,
                                  precision=lax.Precision.HIGHEST,
                                  name="router")(x.astype(jnp.float32))
                if self.router == "softmax":
                    probs = jax.nn.softmax(logits, axis=-1)       # (N, E)
                else:
                    probs = jax.nn.sigmoid(logits)
                if self.choice_bias:
                    bias = self.variable("balance", "choice_bias", jnp.zeros,
                                         (routed,), jnp.float32)
                    _, expert = lax.top_k(
                        probs + lax.stop_gradient(bias.value), k)
                    gate = jnp.take_along_axis(probs, expert, axis=-1)
                else:
                    gate, expert = lax.top_k(probs, k)            # (N, k)
            if self.renormalize:
                gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-20)
            if self.gate_scale != 1.0:
                gate = gate * self.gate_scale

        names = (("w_gate", "w_up", "w_down") if self.activation == "swiglu"
                 else ("w_up", "w_down"))
        # What the routed experts read: the tokens, or their latent.
        rows, width = x, self.latent or d
        if self.latent:
            rows = self._latent(width, "latent_down")(x.astype(self.dtype))
        if self.held is None and not self.skip_choice:
            out, tokens_per_expert, fused = self._all_experts(
                rows, gate, expert, names)
            n_held, window, products = E, None, False
        else:
            # With a skip choice and no share named, every expert is held:
            # the skip choice is then the one output held nowhere.
            first, n_held = self.held or (0, E)
            if not (0 <= first and n_held >= 1 and first + n_held <= E):
                raise ValueError(f"held={self.held} is not a range of the "
                                 f"{E} experts")
            (out, tokens_per_expert, held_assignments, held_windows, fused,
             window, products) = self._held_experts(
                 rows, gate, expert, names, first, n_held)
            self.sow("intermediates", "held_assignments", held_assignments)
            self.sow("intermediates", "held_windows", held_windows)
        if self.skip_choice:
            self.sow("intermediates", "skipped_assignments",
                     tokens_per_expert[E])
        if self.latent:
            out = self._latent(d, "latent_up")(
                out.astype(self.dtype)).astype(jnp.float32)

        if self.shared_hidden:
            out = out + _SharedExpert(
                self.shared_hidden, self.dtype, self.param_dtype,
                self.activation,
                name="shared")(x.astype(self.dtype)).astype(jnp.float32)

        with jax.named_scope("router_losses"):
            f = lax.stop_gradient(tokens_per_expert / n)
            balance = routed * jnp.sum(f * probs.mean(axis=0))
            z_loss = jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        self.sow("intermediates", "aux_load_balance", balance)
        self.sow("intermediates", "aux_router_z", z_loss)
        self.sow("intermediates", "tokens_per_expert", tokens_per_expert)
        self.sow("intermediates", "expert_index", expert)
        if self.choice_bias:
            self.sow("intermediates", "choice_bias_absmax",
                     jnp.abs(bias.value).max())
            if not self.is_initializing() and self.is_mutable_collection(
                    "balance"):
                with jax.named_scope("route/bias_update"):
                    load = tokens_per_expert.astype(jnp.float32)
                    bias.value = bias.value + self.choice_bias * jnp.sign(
                        load.mean() - load)
        counters = {
            "moe.assignments": n * k,
            "moe.fused_matmuls": fused * len(names),
            # Assignments whose rows moved as gathers through the sort's
            # permutation; 0 where a window of them is gathered and landed.
            "moe.permuted_assignments": n * k * (
                window is None or window.windows == 1),
            # Rows of a window that land on their tokens through a grouped
            # transposed product; 0 where they are scatter-added or the one
            # window is every assignment.
            "moe.landed_by_product": window.rows if products else 0,
            "moe.expert_bytes": (len(names) * n_held * width * self.hidden
                                 * jnp.dtype(self.param_dtype).itemsize),
            "moe.row_bytes": width * jnp.dtype(self.dtype).itemsize}
        if self.latent:
            counters["moe.latent"] = self.latent
        if self.held is not None:
            # What uniform routing sends to the held experts, and the rows
            # of a window; the number a step's routing did send and the
            # windows it ran are on the device (sown as ``held_assignments``
            # and ``held_windows``).
            counters["moe.held_assignments"] = window.uniform
            counters["moe.window_rows"] = window.rows
        if self.router == "mlp":
            counters["moe.router_hidden"] = self.router_hidden
        if self.skip_choice:
            counters["moe.skip_choice"] = 1
        if self.choice_bias:
            counters["moe.bias_updates"] = 1
        note_layer(self.path, counters)
        out = out.astype(x.dtype).reshape(*lead, d)
        if self.router == "mlp":
            return (out, balance, z_loss,
                    router_state.reshape(*lead, self.router_hidden))
        return out, balance, z_loss

    def _mlp_router(self, x, state):
        """``(logits, r)`` of the router network on float32 ``x`` (N, d)
        and the previous expert layer's ``r`` (class docstring): float32
        at full matmul precision throughout, as the one-matrix routers
        are."""
        R = self.router_hidden
        if R < 1:
            raise ValueError("router='mlp' needs router_hidden >= 1")

        def dense(features, name, use_bias=True):
            return nn.Dense(features, use_bias=use_bias, dtype=jnp.float32,
                            param_dtype=self.param_dtype,
                            precision=lax.Precision.HIGHEST, name=name)

        with jax.named_scope("down"):
            r = dense(R, "router_down")(x)
        if state is not None:
            with jax.named_scope("eda"):
                scale = self.param("router_state_scale",
                                   nn.initializers.ones, (R,),
                                   self.param_dtype)
                r = r + scale * state.reshape(-1, R).astype(jnp.float32)
        with jax.named_scope("mlp"):
            h = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32,
                           param_dtype=self.param_dtype,
                           name="router_norm")(r)
            h = nn.gelu(dense(R, "router_fc1")(h), approximate=False)
            h = nn.gelu(dense(R, "router_fc2")(h), approximate=False)
            logits = dense(self.routed_over, "router_out", use_bias=False)(h)
        return logits, r

    def _latent(self, features, name):
        """One of the two projections every routed expert shares."""
        return nn.Dense(features, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name=name)

    def _weights(self, names, n_experts, d):
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        return {
            name: self.param(
                name, init, (n_experts, self.hidden, d) if name == "w_down"
                else (n_experts, d, self.hidden), self.param_dtype)
            for name in names}

    def _all_experts(self, x, gate, expert, names):
        """Every expert is here: all k·N assignments, in expert order."""
        E, k = self.num_experts, self.top_k
        n, d = x.shape
        with jax.named_scope("dispatch"):
            flat = expert.reshape(-1)              # assignment a: token a // k
            order = jnp.argsort(flat, stable=True)
            inverse = jnp.argsort(order)
            tokens_per_expert = jnp.bincount(flat, length=E).astype(
                jnp.int32)
            rows = _to_expert_order(x.astype(self.dtype), order, inverse)

        with jax.named_scope("experts"):
            plan = grouped_plan(rows, E, self.hidden,
                                interpret=_pallas.interpret())
            w = {name: a.astype(self.dtype)
                 for name, a in self._weights(names, E, d).items()}
            h = _hidden(rows, w, tokens_per_expert, self.activation, plan,
                        _pallas.interpret())
            y = grouped_matmul(h, w["w_down"], tokens_per_expert, plan,
                               interpret=_pallas.interpret())     # (k·N, d)

        with jax.named_scope("combine"):
            y = _to_token_order(y, order, inverse).reshape(n, k, d)
            out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), gate)
        return out, tokens_per_expert, plan.form == "kernels"

    def _held_experts(self, x, gate, expert, names, first, n_held):
        """``n_held`` of the experts are here (class docstring)."""
        E, k = self.routed_over, self.top_k
        n, d = x.shape
        with jax.named_scope("dispatch"):
            flat = expert.reshape(-1)
            tokens_per_expert = jnp.bincount(flat, length=E).astype(
                jnp.int32)
            # Held experts 0 .. n_held - 1; everything else sorts last.
            local = jnp.where((flat >= first) & (flat < first + n_held),
                              flat - first, n_held)
            order = jnp.argsort(local, stable=True)
            group_sizes = lax.dynamic_slice_in_dim(
                tokens_per_expert, first, n_held)
            ends = jnp.cumsum(group_sizes)
            landed = ends[-1]
        w = self._weights(names, n_held, d)
        window = _window_plan(
            assignments=n * k, held=n_held, routed=E,
            row_bytes=d * jnp.dtype(self.dtype).itemsize,
            expert_bytes=4 * sum(a.size for a in w.values()))
        W = window.rows
        # The grouped matmuls' plan says what the hidden width is padded to.
        plan = grouped_plan(
            jax.ShapeDtypeStruct((W, d), self.dtype, vma=jax.typeof(x).vma),
            n_held, self.hidden + -self.hidden % 128,
            interpret=_pallas.interpret())
        held = _Held(k, W, self.activation, self.dtype, plan,
                     _pallas.interpret(), _lands_by_product(plan.form, n))
        fused = plan.form == "kernels"
        held_windows = -(-landed // W)
        # One window is every assignment (``W == n k``): ``order`` is then a
        # whole permutation, and rows move both ways as gathers through it.
        if window.windows > 1:
            # What the experts' matrices' cotangent varies over, the tokens
            # do: the sum over the mesh is their ``pcast``'s transpose.
            w = ensure_varying_tree(w, tuple(jax.typeof(x).vma))
            order = jnp.pad(order, (0, window.windows * W - n * k))
            return (_held_windows(held, x, gate, w, order, ends, group_sizes,
                                  landed),
                    tokens_per_expert, landed, held_windows, fused, window,
                    held.products)

        with jax.named_scope("dispatch"):
            inverse = jnp.argsort(order)

        @jax.checkpoint
        def every_assignment(x, gate, w):
            """The one window over all ``n k`` sorted rows: a window's
            rows, masks and products, moved by ``_to_expert_order`` /
            ``_to_token_order`` where a window gathers ``x[token]`` and
            scatter-adds the weighted rows home.  The grouped matmuls skip
            the strips past ``landed``."""
            with jax.named_scope("dispatch"):
                here = (jnp.arange(W) < landed)[:, None]
                rows = _to_expert_order(x.astype(self.dtype), order, inverse)
            y = _held_rows(rows, _padded(w, held), here, group_sizes, held)
            with jax.named_scope("combine"):
                y = _to_token_order(y, order, inverse).reshape(n, k, d)
                g = jnp.where(inverse.reshape(n, k) < landed, gate, 0.0)
                # The float32 product the scatter-add lands on its token.
                return (y.astype(jnp.float32) * g[..., None]).sum(axis=1)

        return (every_assignment(x, gate, w), tokens_per_expert, landed,
                held_windows, fused, window, False)


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
