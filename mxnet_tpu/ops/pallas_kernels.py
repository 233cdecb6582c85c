"""Fused Pallas TPU kernels for the memory-bound hot ops.

≙ the reference's hand-fused CUDA kernels (src/operator/nn/softmax.cc
fused softmax, layer_norm.cc fused LayerNorm+stats, and the NVRTC
pointwise fusion N11): on TPU these ops are HBM-bandwidth-bound, so each
kernel streams a row-block from HBM into VMEM once and finishes all math
there (one read + one write per element instead of XLA's worst-case
multi-pass).

Dispatch contract: `*_fused` entry points run the Pallas kernel where
this process drives exactly one TPU (a Mosaic kernel cannot be
partitioned by GSPMD, see `pallas_block.one_tpu`) for tile-friendly
shapes, and the jnp reference elsewhere (CPU tests force
`interpret=True` through the `_FORCE_INTERPRET` switch).
Backward passes are custom_vjp closed forms — Pallas kernels are not
auto-differentiable.

Every routed call counts once, like ``pallas_block.decide``, when it is
decided (trace time): ``dispatch.pallas.hits.<kernel>.<last_dim>`` where
the forward kernel is emitted, ``dispatch.pallas.fallbacks.<kernel>.
<last_dim>`` where the answer is XLA (off the tile, not one TPU, or
LayerNorm's training forward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import pallas_block as _pb

_FORCE_INTERPRET = False     # tests flip this to exercise kernels on CPU


def _count(outcome, kernel, last_dim):
    _pb._tele().counter_add(
        f"dispatch.pallas.{outcome}.{kernel}.{last_dim}", 1)


def _use_pallas(last_dim, kernel=None):
    """The routing decision.  With ``kernel`` a "no" counts one fallback;
    the "yes" is counted where the kernel is emitted, so a caller may ask
    first (``ops/nn.py``) and the ``*_fused`` entry point ask again."""
    if _FORCE_INTERPRET or (_pb.one_tpu() and last_dim % 128 == 0):
        return True
    if kernel:
        _count("fallbacks", kernel, last_dim)
    return False


def _interpret():
    return _FORCE_INTERPRET or _pb.interpret()


def _fit_block(n, block):
    """Largest divisor of n that is <= block (tile-size fitting)."""
    block = max(1, min(n, block))
    while n % block:
        block -= 1
    return block


# ------------------------------------------------------------ softmax

def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = e / jnp.sum(e, axis=-1, keepdims=True)


def _softmax_pallas(x2d):
    rows, cols = x2d.shape
    _count("hits", "softmax", cols)
    block_rows = _fit_block(rows, 512 * 128 // max(cols, 1))
    return pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        interpret=_interpret(),
        name="mx_softmax_fwd",
    )(x2d)


@jax.custom_vjp
def softmax_fused(x):
    """Row softmax over the last axis, one HBM pass."""
    if not _use_pallas(x.shape[-1], "softmax"):
        return jax.nn.softmax(x, axis=-1)
    x2d = x.reshape(-1, x.shape[-1])
    return _softmax_pallas(x2d).reshape(x.shape)


def _softmax_fwd(x):
    y = softmax_fused(x)
    return y, y


def _softmax_bwd(y, g):
    return ((g - jnp.sum(g * y, axis=-1, keepdims=True)) * y,)


softmax_fused.defvjp(_softmax_fwd, _softmax_bwd)


# ---------------------------------------------------------- layer norm

def _layernorm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    o_ref[:] = xc * jax.lax.rsqrt(var + eps) * g_ref[:] + b_ref[:]


def _layernorm_pallas(x2d, gamma, beta, eps):
    rows, cols = x2d.shape
    _count("hits", "layernorm", cols)
    block_rows = _fit_block(rows, 512 * 128 // max(cols, 1))
    return pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
                  pl.BlockSpec((cols,), lambda i: (0,)),
                  pl.BlockSpec((cols,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        interpret=_interpret(),
        name="mx_layernorm_fwd",
    )(x2d, gamma, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layernorm_fused(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last axis: stats + scale/shift in one pass."""
    if not _use_pallas(x.shape[-1], "layernorm"):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + eps) * gamma + beta
    x2d = x.reshape(-1, x.shape[-1])
    return _layernorm_pallas(x2d, gamma, beta, eps).reshape(x.shape)


def _ln_fwd(x, gamma, beta, eps):
    # training forward: compute output straight from the residuals so the
    # stats pass runs once (the fused kernel stays the inference path)
    _count("fallbacks", "layernorm", x.shape[-1])
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    out = xc * rstd * gamma + beta
    return out, (xc, rstd, gamma)


def _ln_bwd(eps, res, g):
    xc, rstd, gamma = res
    n = xc.shape[-1]
    xhat = xc * rstd
    gg = g * gamma
    dx = rstd * (gg - jnp.mean(gg, axis=-1, keepdims=True) -
                 xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True))
    dgamma = jnp.sum(g * xhat, axis=tuple(range(g.ndim - 1)))
    dbeta = jnp.sum(g, axis=tuple(range(g.ndim - 1)))
    return dx, dgamma, dbeta


layernorm_fused.defvjp(_ln_fwd, _ln_bwd)


# ------------------------------------------------- attention (flash-style)

def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, kv_len,
                 block_k):
    """One (block_q, d) query tile vs the full K/V, online softmax —
    the FlashAttention recurrence; K/V stream through VMEM block_k rows
    at a time so the (block_q, kv_len) score matrix never materializes
    in HBM.  Emits the row logsumexp too — the backward's only extra
    residual (O(L) next to q/k/v)."""
    q = q_ref[0] * scale
    block_q, d = q.shape
    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        # pl.ds ref indexing (not lax.dynamic_slice on a value): the form
        # the Pallas TPU lowering supports for a moving VMEM window
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, kv_len // block_k, body, (m, l, acc))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l))[:, 0]


def _attention_pallas(q, k, v, scale, block_q=128, block_k=128):
    """→ (out, lse): lse is the backward residual; inference drops it
    (XLA DCEs the unused output)."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    _count("hits", "attention", D)
    block_q = _fit_block(Lq, block_q)
    block_k = _fit_block(Lk, block_k)
    q3 = q.reshape(B * H, Lq, D)
    k3 = k.reshape(B * H, Lk, D)
    v3 = v.reshape(B * H, Lk, D)
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, kv_len=Lk,
                          block_k=block_k),
        out_shape=(jax.ShapeDtypeStruct(q3.shape, q.dtype),
                   jax.ShapeDtypeStruct((B * H, Lq), jnp.float32)),
        grid=(B * H, Lq // block_q),
        in_specs=[pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0))],
        out_specs=(pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, block_q), lambda b, i: (b, i))),
        interpret=_interpret(),
        name="mx_attn_fwd",
    )(q3, k3, v3)
    return out.reshape(B, H, Lq, D), lse.reshape(B, H, Lq)


def _attention_ref(q, k, v, scale):
    # f32 logits/softmax accumulation regardless of input dtype (bf16
    # inputs keep MXU speed; statistics stay full precision)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attn_use_pallas(q, k):
    """ONE forward/backward eligibility predicate — the two passes must
    always take matching code paths for a given shape."""
    ok = _use_pallas(q.shape[-1]) and q.shape[-1] % 128 == 0 and \
        not any(sz % 8 for sz in (q.shape[2], k.shape[2]))
    if not ok:
        _count("fallbacks", "attention", q.shape[-1])
    return ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention_fused(q, k, v, scale=None):
    """Softmax(QKᵀ·scale)V for (B, H, L, D) tensors — flash-style fused on
    TPU (jnp reference elsewhere). Differentiable: the custom VJP
    recomputes attention weights in the backward (FlashAttention's
    recompute strategy) so the fused forward never materialises the
    (L, L) score matrix in HBM."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if not _attn_use_pallas(q, k):
        return _attention_ref(q, k, v, scale)
    return _attention_pallas(q, k, v, scale)[0]


def _attn_fwd(q, k, v, scale):
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if not _attn_use_pallas(q, k):
        return _attention_ref(q, k, v, s), (q, k, v, None, None)
    # save o + lse (O(L·D) + O(L), tiny next to q/k/v): the backward then
    # needs exactly two streamed passes (dq, dkv) — no o/lse recompute
    o, lse = _attention_pallas(q, k, v, s)
    return o, (q, k, v, o, lse)


def _attn_bwd_ref(s, q, k, v, g):
    # recompute p = softmax(qk·s); closed-form VJP (materialises (L, L))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    p = jax.nn.softmax(logits, axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g, v)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k) * s
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q) * s
    return dq, dk, dv


# ---- flash-style backward: stream K/V (resp. Q) blocks, never hold the
# (L, L) score matrix in HBM (FlashAttention backward, recompute from the
# row statistics lse = m + log l saved by a stats forward pass).

def _attn_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dq_ref, *, scale, kv_len, block_k):
    """dq tile: loop K/V blocks; p = exp(s·scale − lse);
    ds = p·(g·vᵀ − Δ); dq += ds·k·scale."""
    q = q_ref[0]
    g = g_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, None]
    delta = delta_ref[0][:, None]
    block_q, d = q.shape
    acc = jnp.zeros((block_q, d), jnp.float32)

    def body(i, acc):
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        dp = jnp.dot(g, v.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32) * scale

    acc = jax.lax.fori_loop(0, kv_len // block_k, body, acc)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, *, scale, q_len, block_q):
    """dk/dv tile: loop Q blocks; pᵀ accumulations."""
    k = k_ref[0]
    v = v_ref[0]
    block_k, d = k.shape
    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        g = g_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q)][:, None]
        delta = delta_ref[0, pl.ds(i * block_q, block_q)][:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                          # (bq, bk)
        dv = dv + jnp.dot(p.T.astype(g.dtype), g,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jnp.dot(ds.T.astype(q.dtype), q,
                          preferred_element_type=jnp.float32) * scale
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, q_len // block_q, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _attn_bwd_pallas(s, q, k, v, g, o, lse, block_q=128, block_k=128):
    """Two streamed passes (dq tiles; dk/dv tiles) from the saved o/lse
    residuals — the (L, L) score matrix never exists in HBM."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _fit_block(Lq, block_q)
    block_k = _fit_block(Lk, block_k)
    q3 = q.reshape(B * H, Lq, D)
    k3 = k.reshape(B * H, Lk, D)
    v3 = v.reshape(B * H, Lk, D)
    g3 = g.reshape(B * H, Lq, D)
    lse = lse.reshape(B * H, Lq)
    # Δ = rowsum(g ⊙ o) from the SAVED forward output (O(L·D) residual —
    # what FlashAttention keeps; only p is ever recomputed)
    delta = jnp.sum(g3.astype(jnp.float32) *
                    o.reshape(B * H, Lq, D).astype(jnp.float32), axis=-1)
    dq = pl.pallas_call(
        functools.partial(_attn_dq_kernel, scale=s, kv_len=Lk,
                          block_k=block_k),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
        grid=(B * H, Lq // block_q),
        in_specs=[pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, block_q), lambda b, i: (b, i)),
                  pl.BlockSpec((1, block_q), lambda b, i: (b, i))],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        interpret=_interpret(),
        name="mx_attn_dq",
    )(q3, k3, v3, g3, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_dkv_kernel, scale=s, q_len=Lq,
                          block_q=block_q),
        out_shape=(jax.ShapeDtypeStruct(k3.shape, k.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v.dtype)),
        grid=(B * H, Lk // block_k),
        in_specs=[pl.BlockSpec((1, Lq, D), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, Lq, D), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, Lq), lambda b, j: (b, 0)),
                  pl.BlockSpec((1, Lq), lambda b, j: (b, 0))],
        out_specs=(pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0))),
        interpret=_interpret(),
        name="mx_attn_dkv",
    )(q3, k3, v3, g3, lse, delta)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


def _attn_bwd(scale, res, g):
    q, k, v, o, lse = res
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if o is None:                # fwd took the jnp reference path
        return _attn_bwd_ref(s, q, k, v, g)
    return _attn_bwd_pallas(s, q, k, v, g, o, lse)


attention_fused.defvjp(_attn_fwd, _attn_bwd)
