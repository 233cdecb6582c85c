"""Mixture-of-Experts FFN with expert parallelism over the 'ep' mesh axis.

ABSENT in the reference (SURVEY §2.3: "Expert parallelism / MoE — none");
first-class here.  Tokens live on (dp, ep, sp)-sharded batches; experts are
sharded over 'ep'.  Dispatch is top-1 with a fixed capacity (static shapes —
XLA-friendly: routing is one-hot einsums, never dynamic gather/scatter), and
tokens travel to their expert's shard and back via ``lax.all_to_all`` over
the ICI ring.

All functions are per-shard bodies for use inside ``shard_map``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import pallas_kernels

__all__ = ["init_moe_params", "moe_ffn"]


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> Dict:
    """Global (unsharded) MoE parameter pytree; shard 'wi'/'wo' over
    ('ep', -, 'tp') / ('ep', 'tp', -) and replicate 'gate'."""
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "gate": (jax.random.normal(k1, (d_model, n_experts), jnp.float32)
                 * s_in).astype(dtype),
        "wi": (jax.random.normal(k2, (n_experts, d_model, d_ff), jnp.float32)
               * s_in).astype(dtype),
        "wo": (jax.random.normal(k3, (n_experts, d_ff, d_model), jnp.float32)
               * s_out).astype(dtype),
    }


def moe_ffn(x, params, n_experts: int, axis_name: str = "ep",
            capacity_factor: float = 2.0, tp_axis: str = None):
    """Top-1 routed expert FFN.  x: per-shard (S, D) tokens; params per-shard
    with wi (E_local, D, F_local), wo (E_local, F_local, D), gate (D, E).

    With ``tp_axis`` the expert hidden dim F is additionally tensor-parallel:
    expert outputs are psum'ed over tp before the combine (row-parallel
    reduce); cotangent reduction over tp is handled by shard_map's
    varying-manual-axes AD (check_vma=True).

    Returns (S, D) combined expert outputs plus the load-balancing auxiliary
    loss (Shazeer et al. style: E * mean(gates_e) * mean(dispatch_e))."""
    S, D = x.shape
    E = n_experts
    ep = lax.psum(1, axis_name) if axis_name is not None else 1
    cap = max(1, int(capacity_factor * S / E))

    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32),
                        params["gate"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_val = probs.max(axis=-1)                       # (S,)
    expert = probs.argmax(axis=-1)                      # (S,)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)   # (S, E)

    # position of each token within its expert's capacity buffer
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot       # (S, E)
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                            dtype=jnp.float32) * keep[..., None]
    dispatch = pos_oh                                    # (S, E, C) 0/1
    combine = dispatch * gate_val[:, None, None]         # (S, E, C)

    # aux load-balancing loss
    density = onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux_loss = E * jnp.sum(density * density_proxy)

    buf = jnp.einsum("sec,sd->ecd", dispatch, x.astype(jnp.float32))  # (E,C,D)
    if axis_name is not None and ep > 1:
        e_loc = E // ep
        buf = buf.reshape(ep, e_loc, cap, D)
        # send chunk j (experts owned by ep-rank j) to rank j; receive one
        # chunk per source rank → (ep, e_loc, C, D) indexed by source rank
        buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
        buf = buf.reshape(ep, e_loc, cap, D)
        tokens = buf.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, D)
    else:
        tokens = buf                                     # (E, C, D)

    dt = params["wi"].dtype
    h = jnp.einsum("ekd,edf->ekf", tokens.astype(dt), params["wi"],
                   preferred_element_type=jnp.float32)
    h = jax.nn.gelu(h)
    out = jnp.einsum("ekf,efd->ekd", h.astype(dt), params["wo"],
                     preferred_element_type=jnp.float32)   # (E_loc, K, D)
    if tp_axis is not None:
        # row-parallel reduce BEFORE the combine so downstream (combine,
        # gate grads) sees complete, tp-replicated values
        out = lax.psum(out, tp_axis)

    if axis_name is not None and ep > 1:
        e_loc = E // ep
        out = out.reshape(e_loc, ep, cap, D).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
        out = out.reshape(E, cap, D)
    y = jnp.einsum("sec,ecd->sd", combine, out.astype(jnp.float32))
    return y.astype(x.dtype), aux_loss.astype(jnp.float32)


# --------------------------------------------------- dropless top-k, a share
# rows of a held expert's slot, in even shares (S * top_k / E): room for the
# fullest expert of an untrained router that follows four batches (1 742 of
# 8 192 tokens at 6 of 128, PERF.md section 6), so that the step's time does
# not follow the routing
SLOT_SHARES = 6


def moe_topk_held(x, router_w, bias, up, down, held, top_k, scaling=1.0,
                  norm_topk=True, slot_rows=None, act=None):
    """Routed experts of one expert-parallel share: the layer is *told which
    experts it holds* (``held = (first, count)``), routes over all of them,
    and returns the part of the result its own experts give.

    ``x`` (S, D) tokens; ``router_w`` (E, D) and ``bias`` (E,) over all E
    experts; ``up`` (count, D, F) and ``down`` (count, F, D) of the experts
    held.  Scores are ``sigmoid(x router_w^T)`` in float32 at the highest
    matmul precision (a near-tie must not be decided by rounding); the
    ``top_k`` of score + ``bias`` are chosen; their scores, divided by their
    sum when ``norm_topk``, times ``scaling`` weigh the experts' outputs
    ``down_e(act(up_e x))``.  Terms of experts not held are left out: with
    every share's result added, the whole layer results.

    No token is dropped under any imbalance and no one-hot dispatch tensor
    is built: the (token, expert) pairs are sorted by expert, and every held
    expert is given its first ``slot_rows`` rows in one product a projection
    (the empty rows zero), then ``slot_rows`` more while it has any.  So the
    work is count x slot_rows rows -- the same whatever the routing -- until
    an expert is sent more than ``slot_rows`` tokens; each further slot
    costs that one expert's, and S tokens to one expert are served in
    ceil(S / slot_rows) slots.  ``slot_rows`` defaults to ``SLOT_SHARES``
    times an expert's even share S * top_k / E, in whole tiles of 256 rows.

    The loop over the held experts is differentiated by hand
    (``_held_experts``): the backward pass walks the same sorted slots, so
    nothing is kept per expert or per slot, and a row is moved once, in
    place, forward and backward.  The router's gradient flows through the
    sorted pairs' weights; the routing itself is outside the loop.

    Returns ``(y, load)``: (S, D) and the tokens routed to each of the E
    experts (int32), held or not."""
    from .. import telemetry
    telemetry.counter_add("dispatch.moe.sorted_slots")
    act = act or jax.nn.relu
    S, D = x.shape
    E = router_w.shape[0]
    first, count = held
    if slot_rows is None:
        slot_rows = -(-SLOT_SHARES * S * top_k // (E * 256)) * 256
    rows = min(S, slot_rows)
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)   # (S, k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
        weight = (chosen * scaling).astype(x.dtype)
        # compared and summed, not scattered: a scatter's time follows its
        # collisions, and the step's time must not follow the routing
        load = (idx.reshape(-1, 1) == jnp.arange(E, dtype=idx.dtype)).sum(
            axis=0, dtype=jnp.int32)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(-1) - first
        local = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(local, stable=True)     # held pairs first
        sizes = lax.dynamic_slice(load, (first,), (count,))
        starts = jnp.cumsum(sizes) - sizes
        # a slot reads ``rows`` sorted pairs in one slice: room for the last
        # expert's, which a slice that ran past the end would shift
        token = jnp.pad((order // top_k).astype(jnp.int32), (0, rows))
        w_sorted = jnp.pad(weight.reshape(-1)[order], (0, rows))
    kernel = pallas_kernels.rows_use_pallas(rows, D, x.dtype)
    tiles = pallas_kernels.live_use_pallas(rows, D, down.shape[1],
                                           up.shape[2], x.dtype)
    return _held_experts(rows, act, (kernel, tiles), x, up, down, w_sorted,
                         token, sizes, starts), load


# Every row a held expert reads or writes is moved once, in place, by an
# operation told that no two rows of a slot collide: within an expert the
# sorted pairs' tokens ascend (``order`` is a stable sort by expert, and
# ``top_k`` picks no expert twice for a token), and dead row j is given the
# index S + j -- past the end, so it reads the fill and is written nowhere,
# and sorted and unique with the live ones.  The gathers are told both.  The
# scatter-adds are told ``unique_indices`` alone: told its indices are
# sorted, XLA's TPU scatter takes another path that is slower (0.73 -> 0.90
# ms a slot at Nemotron's shape, 0.46 -> 0.96 at Solar's) and rounds (the
# gradients came out 1e-3 off; PERF.md section 6, PR 35).  On one TPU the
# adds are ``pallas_kernels.rows_scatter_add``'s instead, and the products
# ``pallas_kernels.slot_products``' (and its vjp's), which stop at the last
# 128-row tile that holds a live row: a slot's rows past it are read and
# written by nothing.


def _slot_pairs(token, w_sorted, size, start, r, rows, S):
    """Rows [r * rows, (r + 1) * rows) of one expert's sorted pairs: their
    tokens (S + j for a row past ``size``), their weights (0 there), where
    they lie among the sorted pairs, which are live (they lead), and how
    many."""
    j = r * rows + jnp.arange(rows, dtype=jnp.int32)
    live = j < size
    at = start + r * rows
    tok = jnp.where(live, lax.dynamic_slice(token, (at,), (rows,)), S + j)
    w = jnp.where(live, lax.dynamic_slice(w_sorted, (at,), (rows,)), 0)
    return tok, w, at, live, jnp.clip(size - r * rows, 0, rows)


def _slot_rows(a, tok, rounded=False):
    """Rows ``tok`` of ``a``; ``rounded``: as bfloat16, for the kernels,
    whose products round every operand so (the cast is the gather's)."""
    rows = a.at[tok].get(mode="fill", fill_value=0, indices_are_sorted=True,
                         unique_indices=True)
    return rows.astype(jnp.bfloat16) if rounded else rows


def _no_rows(x, kernel):
    """Zeros for the slots' rows to be added into: as the kernel holds them
    (``pallas_kernels.rows_scatter_add``) or as ``x``."""
    S, D = x.shape
    return jnp.zeros((S, D // 128, 128) if kernel else (S, D), x.dtype)


def _as_rows_of(x, y, kernel):
    """The sum of the slots' rows as an array like ``x``.  From the kernel's
    form that is one relayout; behind a barrier, so that it is one copy here
    and not a strided read inside whatever consumes the result."""
    return lax.optimization_barrier(y.reshape(x.shape)) if kernel else y


def _add_rows(y, tok, n, upd, kernel):
    if kernel:
        return pallas_kernels.rows_scatter_add(y, tok, n, upd.astype(y.dtype))
    return y.at[tok].add(upd.astype(y.dtype), mode="drop",
                         unique_indices=True)


def _slot_products(act, xs, e_up, e_down, w):
    h = act(jnp.matmul(xs, e_up))
    return jnp.matmul(h.astype(xs.dtype), e_down) * w[:, None]


def _experts_as_read(up, down, tiles):
    """What the scan over the held experts hands each, and the stacks a
    slot's products read in place: for the kernels, its index and the
    stacks rounded to bfloat16 (as the TPU's default precision rounds
    them); for XLA, its weights and none."""
    if tiles:
        return jnp.arange(up.shape[0], dtype=jnp.int32), (
            up.astype(jnp.bfloat16), down.astype(jnp.bfloat16))
    return (up, down), ()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_experts(rows, act, kernels, x, up, down, w_sorted, token, sizes,
                  starts):
    """What the held experts add, (S, D): a scan over them with the result
    as its carry; each is given its first slot always and a further one
    while it has rows (a loop of as many trips, not differentiated).
    ``kernels = (rows, tiles)``: the rows are added by
    ``mx_rows_scatter_add``; the products run on the live row tiles."""
    S = x.shape[0]
    kernel, tiles = kernels
    each, stacks = _experts_as_read(up, down, tiles)

    def slot(r, y, e, size, start):
        tok, w, _, _, n = _slot_pairs(token, w_sorted, size, start, r, rows,
                                      S)
        with jax.named_scope("moe.dispatch"):
            xs = _slot_rows(x, tok, tiles)
        with jax.named_scope("moe.experts"):
            out = pallas_kernels.slot_products(
                act, e, n, xs, *stacks, w[:, None]) if tiles else \
                _slot_products(act, xs, *e, w)
        with jax.named_scope("moe.combine"):
            return _add_rows(y, tok, n, out, kernel)

    def expert(y, held_e):
        slots = -(-held_e[1] // rows)       # the first always, more if sent
        return lax.fori_loop(1, slots, lambda r, y: slot(r, y, *held_e),
                             slot(0, y, *held_e)), None

    with jax.named_scope("moe.combine"):
        y = _no_rows(x, kernel)
    y, _ = lax.scan(expert, y, (each, sizes, starts))
    with jax.named_scope("moe.combine"):
        return _as_rows_of(x, y, kernel)


def _held_experts_fwd(rows, act, kernels, *args):
    # nothing per expert or per slot is kept: the backward pass gathers a
    # slot's rows again
    return _held_experts(rows, act, kernels, *args), args


def _held_experts_bwd(rows, act, kernels, res, g):
    """By hand over the sorted slots: a scan over the held experts carrying
    x's cotangent (S, D) and the sorted weights'; a slot gathers its rows of
    ``g`` and of ``x``, takes the cotangents of its products -- on the live
    row tiles (``pallas_kernels.slot_products_vjp``), or by ``jax.vjp``
    (``act`` is any callable) -- and adds its rows' cotangent in place on
    the carry.  The weights' cotangents are the kernels' stacks in the
    carry, each expert's written in place; for XLA the scan's results."""
    x, up, down, w_sorted, token, sizes, starts = res
    S = x.shape[0]
    kernel, tiles = kernels
    each, stacks = _experts_as_read(up, down, tiles)

    def slot(r, first, dx, dws, s_up, s_down, e, size, start):
        tok, w, at, live, n = _slot_pairs(token, w_sorted, size, start, r,
                                          rows, S)
        with jax.named_scope("moe.combine"):
            dout = _slot_rows(g, tok)
        with jax.named_scope("moe.dispatch"):
            xs = _slot_rows(x, tok, tiles)
        with jax.named_scope("moe.experts"):
            if tiles:
                dxs, d_up, d_down, dw = pallas_kernels.slot_products_vjp(
                    act, e, n, xs, *stacks, w[:, None], dout,
                    (s_up, s_down), not first)
                dw = dw[:, 0]
            else:
                dxs, d_up, d_down, dw = jax.vjp(
                    functools.partial(_slot_products, act), xs, *e,
                    w)[1](dout)
                if not first:
                    d_up, d_down = s_up + d_up, s_down + d_down
        with jax.named_scope("moe.dispatch"):
            dx = _add_rows(dx, tok, n, dxs, kernel)
            # the slot's pairs are contiguous among the sorted ones
            dws = lax.dynamic_update_slice(dws, jnp.where(
                live, dw, lax.dynamic_slice(dws, (at,), (rows,))), (at,))
        return dx, dws, d_up, d_down

    def expert(carry, held_e):
        # a further slot adds its weights' cotangents onto the first's
        dx, dws, d_up, d_down = lax.fori_loop(
            1, -(-held_e[1] // rows),
            lambda r, c: slot(r, False, *c, *held_e),
            slot(0, True, *carry, *held_e))
        if tiles:
            return (dx, dws, d_up, d_down), None
        return (dx, dws, None, None), (d_up, d_down)

    with jax.named_scope("moe.dispatch"):
        none = (_no_rows(x, kernel), jnp.zeros_like(w_sorted)) + (
            pallas_kernels.weight_sums(up, down) if tiles else (None, None))
    (dx, dws, *sums), d_ws = lax.scan(expert, none, (each, sizes, starts))
    d_up, d_down = pallas_kernels.weights_of(sums, up) if tiles else d_ws
    with jax.named_scope("moe.dispatch"):
        return (_as_rows_of(x, dx, kernel), d_up, d_down, dws, None, None,
                None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)
