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

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

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
        token = (order // top_k).astype(jnp.int32)
        w_sorted = weight.reshape(-1)[order]
        sizes = lax.dynamic_slice(load, (first,), (count,))
        starts = jnp.cumsum(sizes) - sizes

    def slot(y, e_up, e_down, size, start, r):
        """``y`` and what rows [r * rows, (r + 1) * rows) of one expert
        add: a row past ``size`` is token S, which is none -- it reads
        zeros and is written nowhere."""
        with jax.named_scope("moe.dispatch"):
            j = r * rows + jnp.arange(rows, dtype=jnp.int32)
            live = j < size
            pos = jnp.where(live, start + j, 0)
            tok = jnp.where(live, token[pos], S)
            w = jnp.where(live, w_sorted[pos], 0)
            xs = x.at[tok].get(mode="fill", fill_value=0)
        with jax.named_scope("moe.experts"):
            h = act(jnp.matmul(xs, e_up))
            out = jnp.matmul(h.astype(x.dtype), e_down) * w[:, None]
        with jax.named_scope("moe.combine"):
            return y.at[tok].add(out.astype(y.dtype), mode="drop")

    def expert(y, e_up, e_down, size, start):
        """``y`` and what one expert adds: its first slot always, a further
        one while it has rows."""
        def more(y, r):
            return lax.cond(r * rows < size, slot, lambda y, *_: y,
                            y, e_up, e_down, size, start, r)

        def further(y):
            return lax.scan(lambda y, r: (jax.checkpoint(more)(y, r), None),
                            y, jnp.arange(1, -(-S // rows), dtype=jnp.int32))[0]

        return lax.cond(rows < size, further, lambda y: y,
                        slot(y, e_up, e_down, size, start, 0))

    # Recomputed in the backward pass an expert at a time, and inside it a
    # further slot at a time: one slot's rows live at once, and what a
    # ``cond`` keeps for its branch (x, the expert's weights) is kept for
    # one expert, not stacked by the scan around it.
    y, _ = lax.scan(lambda y, held_e: (jax.checkpoint(expert)(y, *held_e),
                                       None),
                    jnp.zeros_like(x), (up, down, sizes, starts))
    return y, load
