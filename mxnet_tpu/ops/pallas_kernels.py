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
#
# One program holds every row of one batch element for one lane block of
# heads, so neither pass has anything to stream or to carry from tile to
# tile: the forward is a row softmax per query tile, the backward makes
# its own statistics from the recomputed scores.  An (L, L) matrix lives
# in VMEM a query tile at a time and never in HBM, and the backward's
# only residuals are q, k and v themselves.
#
# Arrays are (G, L, C) with heads side by side on the last axis.  A block
# is (1, L, W) lanes wide and holds W // head_dim heads: two for the
# head_dim 64 of a packed (B, T, 3·H·hd) projection, whose q, k and v are
# then three windows of ONE array (`cols`), one otherwise.  Heads that
# share a block are told apart by a lane mask on one operand of each
# product, never by a slice: a 128-deep contraction over 64 zeros costs
# the MXU what a 64-deep one does, and nothing has to move across lanes.
#
# Precision: HBM arrays, scores, statistics and accumulators are float32;
# the MXU operands are rounded to bfloat16, which is what XLA's DEFAULT
# precision does to the float32 `dot_general`s of the composition.

_ATTN_TILE = 512 * 512       # scores a step of the in-kernel loop (1 MiB
#                              of f32; 512 x 512 measured fastest, PERF.md)
_ATTN_MAX_LEN = 1024         # whole-L blocks: the backward's 14 (L, W)
#                              f32 buffers and 6 score tiles, under 16 MiB
_NT = (((1,), (1,)), ((), ()))    # a · bᵀ
_TN = (((0,), (0,)), ((), ()))    # aᵀ · b


def _attn_block_q(lq, lk):
    """Query rows a step: the most 128-row groups that divide lq and keep
    the score tile within `_ATTN_TILE`."""
    groups = lq // 128
    return 128 * max(n for n in range(1, groups + 1)
                     if groups % n == 0 and (n == 1 or
                                             128 * n * lk <= _ATTN_TILE))


def _head_masks(width, heads):
    """→ per head a (1, width) lane mask, or [None] for one head."""
    if heads == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    hd = width // heads
    return [(lane >= h * hd) & (lane < (h + 1) * hd) for h in range(heads)]


def _mxu(x, keep=None, scale=None):
    """An MXU operand: one head's lanes, scaled, rounded to bfloat16."""
    if scale is not None:
        x = x * scale
    if keep is not None:
        x = jnp.where(keep, x, 0.0)
    return x.astype(jnp.bfloat16)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, heads):
    """o = softmax(q·kᵀ·scale)·v, a (block_q, Lk) score tile at a time."""
    lq, width = q_ref.shape[1:]
    block_q = _attn_block_q(lq, k_ref.shape[1])
    masks = _head_masks(width, heads)
    k = _mxu(k_ref[0])
    v = _mxu(v_ref[0])

    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, rows, :]
        out = 0.0
        for keep in masks:
            s = jax.lax.dot_general(_mxu(q, keep, scale), k, _NT,
                                    preferred_element_type=jnp.float32)
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            o = jnp.dot(e.astype(jnp.bfloat16), v,
                        preferred_element_type=jnp.float32) \
                / jnp.sum(e, axis=-1, keepdims=True)
            out += o if keep is None else jnp.where(keep, o, 0.0)
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lq // block_q, body, 0)


def _attn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, dq_ref, dk_ref, dv_ref, *,
                     scale, heads):
    """dq, dk, dv from q, k, v and the cotangent g alone.  Scores are
    recomputed TRANSPOSED, (Lk, block_q), so that the softmax statistics
    and Δ = Σₖ p·dp are sums over sublanes (rows of a lane vector, no
    relayout) and four of the five products need no transpose:
    sᵀ = k·qᵀ, dpᵀ = v·gᵀ, dv += pᵀ·g, dk += dsᵀ·q; dq = (dsᵀ)ᵀ·k is the
    one the MXU transposes.  Δ from p·dp is the softmax VJP's own
    Σ g·y, term for term."""
    lq, width = q_ref.shape[1:]
    block_q = _attn_block_q(lq, k_ref.shape[1])
    masks = _head_masks(width, heads)
    k_all = k_ref[0]
    k = _mxu(k_all)
    v = _mxu(v_ref[0])
    k_heads = [_mxu(k_all, keep, scale) for keep in masks]
    dk_ref[0] = jnp.zeros(dk_ref.shape[1:], dk_ref.dtype)
    dv_ref[0] = jnp.zeros(dv_ref.shape[1:], dv_ref.dtype)

    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q_all = q_ref[0, rows, :]
        g_all = g_ref[0, rows, :].astype(jnp.float32)
        dq = dk = dv = 0.0
        for keep, k_h in zip(masks, k_heads):
            q = _mxu(q_all, keep, scale)
            g = _mxu(g_all, keep)
            s = jax.lax.dot_general(k, q, _NT,
                                    preferred_element_type=jnp.float32)
            e = jnp.exp(s - jnp.max(s, axis=0, keepdims=True))
            p = e * (1.0 / jnp.sum(e, axis=0, keepdims=True))
            dp = jax.lax.dot_general(v, g, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - jnp.sum(p * dp, axis=0, keepdims=True))
            p = p.astype(jnp.bfloat16)
            ds = ds.astype(jnp.bfloat16)
            dv += jnp.dot(p, g, preferred_element_type=jnp.float32)
            dk += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq += jax.lax.dot_general(ds, k_h, _TN,
                                      preferred_element_type=jnp.float32)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0] += dk.astype(dk_ref.dtype)
        dv_ref[0] += dv.astype(dv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lq // block_q, body, 0)


def _attn_call(kernel, name, ins, outs_like, scale, *, d, width=None,
               cols=(0, 0, 0), n_blocks=1):
    """One kernel over a (G, n_blocks) grid of (1, L, width) blocks of
    (G, L, C) arrays.  `width` lanes (default: one head) hold heads of
    head_dim `d`; q, k and v start `cols` blocks into their last axis,
    every other array at 0; outputs are (G, L, n_blocks · width), shaped
    after `outs_like`."""
    width = width or d

    def block(a, first=0):
        return pl.BlockSpec((1, a.shape[1], width),
                            lambda g, j: (g, 0, first + j))

    return pl.pallas_call(
        functools.partial(kernel, scale=scale, heads=width // d),
        out_shape=[jax.ShapeDtypeStruct(a.shape[:2] + (n_blocks * width,),
                                        a.dtype) for a in outs_like],
        grid=(ins[0].shape[0], n_blocks),
        in_specs=[block(a, c) for a, c in zip(ins, cols + (0,))],
        out_specs=[block(a) for a in outs_like],
        interpret=_interpret(),
        name=name,
    )(*ins)


def _attention_pallas(q, k, v, scale, *, d, **geometry):
    """Forward → o, (G, Lq, n_blocks · width)."""
    _count("hits", "attention", d)
    return _attn_call(_attn_fwd_kernel, "mx_attn_fwd", (q, k, v), (q,),
                      scale, d=d, **geometry)[0]


def _attn_bwd_pallas(q, k, v, g, scale, **geometry):
    """→ [dq, dk, dv]."""
    return _attn_call(_attn_bwd_kernel, "mx_attn_bwd", (q, k, v, g),
                      (q, k, v), scale, **geometry)


def _attention_ref(q, k, v, scale):
    # f32 logits/softmax accumulation regardless of input dtype (bf16
    # inputs keep MXU speed; statistics stay full precision)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attn_route(lq, lk, d):
    """Does the kernel take these shapes here?  Asks, counts nothing."""
    return (_FORCE_INTERPRET or _pb.one_tpu()) and d % 64 == 0 and \
        lq % 128 == 0 and lk % 128 == 0 and \
        max(lq, lk) <= _ATTN_MAX_LEN


def _attn_use_pallas(lq, lk, d, plain=True):
    """The routing decision of both entry points.  `plain` is the
    caller's own condition (no mask, no dropout on the weights); a "no"
    of either kind counts one fallback, a "yes" is counted where the
    forward kernel is emitted."""
    ok = plain and _attn_route(lq, lk, d)
    if not ok:
        _count("fallbacks", "attention", d)
    return ok


def _heads_to_rows(x):
    return x.reshape((-1,) + x.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention_fused(q, k, v, scale=None):
    """Softmax(QKᵀ·scale)V for (B, H, L, D) tensors — flash-style fused on
    TPU (jnp reference elsewhere). Differentiable: the custom VJP
    recomputes attention weights in the backward (FlashAttention's
    recompute strategy) so neither pass materialises the (L, L) score
    matrix in HBM."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if not _attn_use_pallas(q.shape[2], k.shape[2], q.shape[3]):
        return _attention_ref(q, k, v, scale)
    return _attention_pallas(_heads_to_rows(q), _heads_to_rows(k),
                             _heads_to_rows(v), scale,
                             d=q.shape[3]).reshape(q.shape)


def _attn_fwd(q, k, v, scale):
    return attention_fused(q, k, v, scale), (q, k, v)


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


def _attn_bwd(scale, res, g):
    q, k, v = res
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    # the forward's question again, uncounted: same shapes, same answer
    if not _attn_route(q.shape[2], k.shape[2], q.shape[3]):
        return _attn_bwd_ref(s, q, k, v, g)
    dq, dk, dv = _attn_bwd_pallas(
        _heads_to_rows(q), _heads_to_rows(k), _heads_to_rows(v),
        _heads_to_rows(g), s, d=q.shape[3])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


attention_fused.defvjp(_attn_fwd, _attn_bwd)


# ---- the same kernels over a packed projection: q, k and v of head h are
# columns [h·hd, (h+1)·hd) of thirds 0, 1, 2 of a (B, T, 3·H·hd) array,
# the output is (B, T, H·hd) — what a fused QKV Dense writes and what the
# output Dense reads, so no head is ever transposed in HBM.

def _split_heads(qkv, heads):
    """(B, T, 3·H·hd) → q, k, v, each (B, H, T, hd)."""
    b, t, c = qkv.shape
    x = qkv.reshape(b, t, 3, heads, c // (3 * heads)).transpose(2, 0, 3, 1, 4)
    return x[0], x[1], x[2]


def _packed_width(heads, d):
    """Lanes of one block: a whole 128-lane row of heads where they tile
    it, one head otherwise."""
    return 128 if 128 % d == 0 and (heads * d) % 128 == 0 else d


def _packed(qkv, heads):
    """→ the geometry keywords of the two `pallas_call` wrappers."""
    d = qkv.shape[2] // (3 * heads)
    width = _packed_width(heads, d)
    n = heads * d // width
    return dict(d=d, width=width, cols=(0, n, 2 * n), n_blocks=n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _self_attention_pallas(qkv, heads):
    geo = _packed(qkv, heads)
    return _attention_pallas(qkv, qkv, qkv, geo["d"] ** -0.5, **geo)


def _self_attn_fwd(qkv, heads):
    return _self_attention_pallas(qkv, heads), qkv


def _self_attn_bwd(heads, qkv, g):
    geo = _packed(qkv, heads)
    return (jnp.concatenate(_attn_bwd_pallas(
        qkv, qkv, qkv, g, geo["d"] ** -0.5, **geo), axis=-1),)


_self_attention_pallas.defvjp(_self_attn_fwd, _self_attn_bwd)


def self_attention_use_pallas(length, head_dim, plain=True):
    """A block that keeps its own composition for masks and dropout asks
    here first (a "no" counts the fallback)."""
    return _attn_use_pallas(length, length, head_dim, plain)


def self_attention_fused(qkv, heads):
    """softmax(q·kᵀ/√hd)·v of every head of a packed (B, T, 3·H·hd)
    projection → (B, T, H·hd): the fused kernels where they apply, the
    composition they replace elsewhere."""
    b, t, c = qkv.shape
    d = c // (3 * heads)
    if self_attention_use_pallas(t, d):
        return _self_attention_pallas(qkv, heads)
    q, k, v = _split_heads(qkv, heads)
    return _attention_ref(q, k, v, d ** -0.5) \
        .transpose(0, 2, 1, 3).reshape(b, t, heads * d)


# ----------------------------------- causal grouped-query attention (trainable)
#
# softmax(q·kᵀ/√hd + causal mask)·v where Hq / Hkv query heads share a
# key-value head, for a sequence too long to hold a row of scores: the
# online softmax over key blocks at or below the diagonal forward, a saved
# row log-sum-exp, and ONE backward kernel that recomputes the scores tile
# by tile.  No (block_q, block_k) tile of scores, probabilities or masks
# reaches HBM in either pass.
#
# Arrays stay as the projections wrote them: q, o and the cotangent
# (B, T, Hq·hd), k and v (B, T, Hkv·hd); a head is a column block.  The
# grid is (B, Hkv, Hq/Hkv, T/block_q): through all the inner steps of one
# (batch, key-value head) the K and V blocks keep their index — one whole
# head each, fetched once, rounded to bfloat16 into scratch once — and the
# key blocks above the diagonal are never visited (a dynamic trip count).
# The backward's dk and dv blocks keep their index over the same steps and
# accumulate the group's query heads and query blocks in VMEM.
#
# Precision as above: HBM arrays, scores, statistics and accumulators
# float32, MXU operands rounded to bfloat16, q scaled in float32 before it
# is rounded — what the two-scan composition in `ops/nn.py` computes at
# XLA's DEFAULT precision.
#
# With a static `window` W (a row sees its own key and the W − 1 before
# it) the same two bodies are the kernels `mx_window_attn_fwd` / `_bwd`:
# the key loop of the query block starting at row `first` begins at block
# max(0, first − W + 1) // block_k, the leading blocks that hold a key too
# far back for the block's last row are masked (row − key < W), the middle
# ones are not, the diagonal ones are.  Work, and the blocks of dk and dv
# touched, follow the band.  Without a window the bodies trace to what
# they were.

_CAUSAL_BLOCK = 512          # query rows a grid step, keys a loop step
_CAUSAL_MAX_HEAD = 8192 * 128    # T·hd of a K or V head whole in VMEM: the
#                              backward holds 8 of them in float32 (k, v, dk,
#                              dv, double-buffered) and 2 in bfloat16, 36 MiB
_CAUSAL_LOW = -1e30          # a masked score (the composition's)


def _causal_blocks(t, most=_CAUSAL_BLOCK):
    """→ (block_q, block_k): the most 128-row groups up to `most` that
    divide t."""
    return (128 * max(n for n in range(1, most // 128 + 1)
                      if (t // 128) % n == 0),) * 2


def _causal_span(first, block_q, block_k):
    """Key blocks a query block starting at row `first` needs: those below
    index `full` lie wholly at or below its first row (no mask), those
    from `full` to `need` cross the diagonal."""
    return (first + 1) // block_k, (first + block_q + block_k - 1) // block_k


_WINDOW_BLOCK = 256          # a windowed call's rows and keys a step: a query
#                              block needs window + block_q - 1 keys, so at
#                              512 the band of 2048 in 8192 is 51 % of the
#                              triangle's tiles, at 256 it is 48 %


def _window_span(first, block_q, block_k, window):
    """→ (lo, lead, full, need): of `_causal_span`'s blocks a query block
    that sees the last `window` keys (row − key < window) visits those from
    `lo`; those below `lead` hold a key too far back for its last row and
    are masked, those from `lead` to `full` are not, those from `full` to
    `need` cross the diagonal (and the window's edge, where it is that
    near)."""
    full, need = _causal_span(first, block_q, block_k)
    lo = jnp.maximum(first - window + 1, 0) // block_k
    edge = jnp.maximum(first + block_q - 1 - window + block_k, 0) // block_k
    return lo, jnp.clip(edge, lo, full), full, need


def window_tiles(t, window, blocks=None):
    """→ (visited, causal): the (block_q, block_k) tiles the windowed
    kernels' key loops run for one head of `t` rows, and those of the whole
    causal triangle at the same blocks.  Counted from the shapes."""
    block_q, block_k = blocks or _causal_blocks(t, _WINDOW_BLOCK)
    visited = causal = 0
    for first in range(0, t, block_q):
        need = _causal_span(first, block_q, block_k)[1]
        visited += need - max(first - window + 1, 0) // block_k
        causal += need
    return visited, causal


def _causal_round_kv(k_ref, v_ref, kb_ref, vb_ref, block_k):
    """A new key-value head: its MXU operands, rounded once."""
    def rows(j, carry):
        r = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        kb_ref[r, :] = k_ref[0, r, :].astype(jnp.bfloat16)
        vb_ref[r, :] = v_ref[0, r, :].astype(jnp.bfloat16)
        return carry

    jax.lax.fori_loop(0, k_ref.shape[1] // block_k, rows, 0)


def _causal_keep(row, key, window):
    """The mask of a tile that crosses an edge."""
    keep = row >= key
    return keep if window is None else keep & (row - key < window)


def _causal_loops(tile, carry, first, block_q, block_k, window):
    """`tile(j, carry, masked, wide=1)` over the key blocks a query block
    needs, in order: the window's masked leading edge (none without a
    window), the blocks no mask touches, the masked diagonal.  A windowed
    call takes the unmasked blocks two at a time (`wide=2`: one tile of
    2 x block_k keys; what a tile costs beside its products - the row
    maxima and sums, the rescaled accumulator - it then costs half as
    often), and a last one alone where their number is odd."""
    if window is None:
        lead, (full, need) = 0, _causal_span(first, block_q, block_k)
    else:
        lo, lead, full, need = _window_span(first, block_q, block_k, window)
        carry = jax.lax.fori_loop(
            lo, lead, functools.partial(tile, masked=True), carry)
        pairs = (full - lead) // 2
        carry = jax.lax.fori_loop(
            0, pairs, lambda p, c: tile(lead + 2 * p, c, masked=False,
                                        wide=2), carry)
        lead = lead + 2 * pairs
    carry = jax.lax.fori_loop(lead, full,
                              functools.partial(tile, masked=False), carry)
    return jax.lax.fori_loop(full, need, functools.partial(tile, masked=True),
                             carry)


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, kb_ref, vb_ref,
                       *, scale, block_k, window=None):
    """One query block of one query head: o and the rows' log-sum-exp.
    A row of a windowed call whose keys in a leading block are all too far
    back reads that block as if it saw all of it; its next real score
    scales that to nothing (exp(LOW − m) = 0), and every row has one: its
    own key."""
    h, i = pl.program_id(2), pl.program_id(3)
    block_q, d = q_ref.shape[1:]

    @pl.when((h == 0) & (i == 0))
    def _():
        _causal_round_kv(k_ref, v_ref, kb_ref, vb_ref, block_k)

    q = _mxu(q_ref[0].astype(jnp.float32), scale=scale)
    first = i * block_q

    def tile(j, carry, masked, wide=1):
        m, l, acc = carry
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), wide * block_k)
        s = jax.lax.dot_general(q, kb_ref[keys, :], _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            shape = (block_q, block_k)
            row = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            key = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(_causal_keep(row, key, window), s, _CAUSAL_LOW)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        old = jnp.exp(m - m_new)
        pv = jnp.dot(p.astype(jnp.bfloat16), vb_ref[keys, :],
                     preferred_element_type=jnp.float32)
        return (m_new, l * old + jnp.sum(p, axis=-1, keepdims=True),
                acc * old + pv)

    m, l, acc = _causal_loops(
        tile, (jnp.full((block_q, 1), _CAUSAL_LOW, jnp.float32),
               jnp.zeros((block_q, 1), jnp.float32),
               jnp.zeros((block_q, d), jnp.float32)),
        first, block_q, block_k, window)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # the statistics are a column (block_q, 1); the backward wants a row
    lse = jnp.broadcast_to(m + jnp.log(l), (block_q, 128))
    lse_ref[0, 0] = lse.T[:1]


def _causal_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, kb_ref, vb_ref,
                       *, scale, block_k, window=None):
    """One query block of one query head: its dq, and its part of the
    key-value head's dk and dv.  Scores are recomputed TRANSPOSED,
    (block_k, block_q), as in `_attn_bwd_kernel`: lse and Δ = Σ g·o are
    rows that broadcast over sublanes, and of the five products only
    dq = (dsᵀ)ᵀ·k is one the MXU transposes."""
    h, i = pl.program_id(2), pl.program_id(3)
    block_q, d = q_ref.shape[1:]

    @pl.when((h == 0) & (i == 0))
    def _():
        _causal_round_kv(k_ref, v_ref, kb_ref, vb_ref, block_k)
        dk_ref[0] = jnp.zeros(dk_ref.shape[1:], dk_ref.dtype)
        dv_ref[0] = jnp.zeros(dv_ref.shape[1:], dv_ref.dtype)

    q = _mxu(q_ref[0].astype(jnp.float32), scale=scale)
    g = _mxu(g_ref[0])
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    first = i * block_q

    def tile(j, dq, masked, wide=1):
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), wide * block_k)
        k = kb_ref[keys, :]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            shape = (block_k, block_q)
            key = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            row = first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(_causal_keep(row, key, window), s, _CAUSAL_LOW)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(vb_ref[keys, :], g, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(jnp.bfloat16)
        dv_ref[0, keys, :] += jnp.dot(p.astype(jnp.bfloat16), g,
                                      preferred_element_type=jnp.float32)
        dk_ref[0, keys, :] += jnp.dot(ds, q,
                                      preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(ds, k, _TN,
                                        preferred_element_type=jnp.float32)

    dq = _causal_loops(tile, jnp.zeros((block_q, d), jnp.float32), first,
                       block_q, block_k, window)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _causal_call(kernel, name, ins, outs, heads, kv, blocks, vmem,
                 window=None):
    """One kernel over the (B, Hkv, Hq/Hkv, T/block_q) grid.  `ins` and
    `outs` are (array or shape, kind) pairs: "q" a query head's
    (block_q, hd) block, "kv" a key-value head whole, "row" a query head's
    (1, block_q) block of a (B, Hq, 1, T) array of row statistics.
    `blocks` = (block_q, block_k) is the tests'; None: `_causal_blocks`,
    up to `_WINDOW_BLOCK` with a `window`, which also names the kernel
    `mx_window_attn_*`."""
    from jax.experimental.pallas import tpu as pltpu
    b, t, width = ins[0][0].shape
    d, r = width // heads, heads // kv
    block_q, block_k = blocks or _causal_blocks(
        t, _CAUSAL_BLOCK if window is None else _WINDOW_BLOCK)
    if window is not None:
        name = name.replace("causal", "window")
    specs = {
        "q": pl.BlockSpec((1, block_q, d),
                          lambda b, g, h, i: (b, i, g * r + h)),
        "kv": pl.BlockSpec((1, t, d), lambda b, g, h, i: (b, 0, g)),
        "row": pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, g, h, i: (b, g * r + h, 0, i)),
    }
    return pl.pallas_call(
        functools.partial(kernel, scale=d ** -0.5, block_k=block_k,
                          window=window),
        out_shape=[a for a, _ in outs],
        grid=(b, kv, r, t // block_q),
        in_specs=[specs[kind] for _, kind in ins],
        out_specs=[specs[kind] for _, kind in outs],
        scratch_shapes=[pltpu.VMEM((t, d), jnp.bfloat16)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name=name,
    )(*(a for a, _ in ins))


def _causal_vmem(t, d, whole_f32):
    """The scoped VMEM a call may use: `whole_f32` double-buffered float32
    (T, hd) blocks, the two bfloat16 heads, and 24 MiB for the per-step
    blocks and a tile's temporaries."""
    return t * d * (8 * whole_f32 + 4) + (24 << 20)


def _causal_counts_as(window):
    """The `<kernel>` of a call's `dispatch.pallas.{hits,fallbacks}.*`."""
    return "causal_attention" if window is None else "window_attention"


def _causal_fwd_pallas(q, k, v, heads, kv, blocks, window=None):
    """→ o (B, T, Hq·hd), lse (B, Hq, 1, T) float32."""
    b, t, width = q.shape
    d = width // heads
    _count("hits", _causal_counts_as(window), d)
    if window is not None:
        tele = _pb._tele()
        for what, n in zip(("visited", "causal"),
                           window_tiles(t, window, blocks)):
            tele.counter_add(f"attn.window.tiles_{what}", b * heads * n)
    return _causal_call(
        _causal_fwd_kernel, "mx_causal_attn_fwd",
        ((q, "q"), (k, "kv"), (v, "kv")),
        ((jax.ShapeDtypeStruct(q.shape, q.dtype), "q"),
         (jax.ShapeDtypeStruct((b, heads, 1, t), jnp.float32), "row")),
        heads, kv, blocks, _causal_vmem(t, d, 2), window)


def _causal_bwd_pallas(q, k, v, o, lse, g, heads, kv, blocks, window=None):
    """→ dq, dk, dv (dk and dv float32: sums over a group's heads)."""
    b, t, width = q.shape
    d = width // heads
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, t, heads, d), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b, heads, 1, t)
    kv_sum = jax.ShapeDtypeStruct(k.shape, jnp.float32)
    return _causal_call(
        _causal_bwd_kernel, "mx_causal_attn_bwd",
        ((q, "q"), (k, "kv"), (v, "kv"), (g, "q"), (lse, "row"),
         (delta, "row")),
        ((jax.ShapeDtypeStruct(q.shape, q.dtype), "q"), (kv_sum, "kv"),
         (kv_sum, "kv")),
        heads, kv, blocks, _causal_vmem(t, d, 4), window)


def causal_attention_use_pallas(t, heads, kv, d, window=None):
    """The routing decision of `nn.causal_gqa_attention`: one TPU (or the
    tests' interpret switch), head_dim in lane tiles, whole groups, a
    length in 128s whose K/V head VMEM holds whole.  A "no" counts one
    fallback (`window_attention`'s for a windowed call); the "yes" is
    counted where the forward kernel is emitted."""
    ok = (_FORCE_INTERPRET or _pb.one_tpu()) and d % 128 == 0 and \
        heads % kv == 0 and t % 128 == 0 and t * d <= _CAUSAL_MAX_HEAD
    if not ok:
        _count("fallbacks", _causal_counts_as(window), d)
    return ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def causal_gqa_attention_fused(q, k, v, heads, kv, blocks=None, window=None):
    """Causal softmax(q·kᵀ/√hd)·v of `heads` query heads over `kv`
    key-value heads, read in place from the projections: q (B, T, Hq·hd),
    k and v (B, T, Hkv·hd) → (B, T, Hq·hd).  `blocks` = (block_q,
    block_k) is the tests' (default: `_causal_blocks`).  With `window` a
    row sees its own key and the `window` − 1 before it, and the kernels
    (`mx_window_attn_fwd` / `_bwd`) visit the band's key blocks only."""
    return _causal_fwd_pallas(q, k, v, heads, kv, blocks, window)[0]


def _causal_vjp_fwd(q, k, v, heads, kv, blocks, window):
    o, lse = _causal_fwd_pallas(q, k, v, heads, kv, blocks, window)
    return o, (q, k, v, o, lse)


def _causal_vjp_bwd(heads, kv, blocks, window, res, g):
    q, k, v, o, lse = res
    dq, dk, dv = _causal_bwd_pallas(q, k, v, o, lse, g, heads, kv, blocks,
                                    window)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


causal_gqa_attention_fused.defvjp(_causal_vjp_fwd, _causal_vjp_bwd)


# ------------------------------------ Mamba-2's chunked scan (SSD, trainable)
#
# Per head S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_tᵀ, y_t = S_t c_t + d x_t,
# by chunks of `chunk` steps: inside a chunk y is a product with the
# (chunk, chunk) tile m[t, s] = (c_t·b_s) exp(cum_t − cum_s) dt_s, s ≤ t;
# across chunks the state is carried.  The grid is (B, G, T/chunk), chunks
# sequential: the state entering a chunk is a VMEM scratch, so neither a
# decay tile nor a chunk's end state nor the chunk-by-chunk carry product
# of the composition in `ops/nn.py::ssd_chunked` reaches HBM.  The backward
# walks the chunks in reverse with the state's cotangent as its scratch and
# recomputes the tiles; the one residual the forward emits for it is the
# state entering every chunk, rounded as the MXU reads it.
#
# Arrays stay as the mixer holds them: x, y and their cotangents
# (B, T, H·P), b and c (B, T, G·N); a group's H/G heads are adjacent lanes
# and a grid step takes all of them (c·bᵀ is made once for the group, db
# and dc are summed over its heads in the step).  Heads narrower than a
# 128-lane block share one and are told apart by lane masks, as in the
# attention kernels above; the state is held transposed, (N, H/G·P), so
# every product of a block is 128 lanes wide.  The per-step scalars — the
# inclusive cumulative sum of dt·a inside each chunk and dt, a few MB that
# XLA makes exactly in float32 — come packed twice: `col` (B, G, T, 2·H/G)
# with time on sublanes and `row` (B, G, 2·H/G, T) with time on lanes, so
# no tile needs a relayout.
#
# Precision: HBM arrays, the carried state, cum, every exponential and m
# before it enters the MXU are float32; MXU operands are rounded to
# bfloat16 — what XLA's DEFAULT precision does to the composition's
# einsums.  Every decay is the exponential of a difference that is not
# positive (above the diagonal: of `_SSD_LOW`, which is exactly 0).

_SSD_CHUNK = 128             # the chunk the route takes (the (chunk, chunk)
#                              tile is one MXU pass deep)
_SSD_MAX_LANES = 1024        # H/G·P of a group: its blocks and the
#                              (N, H/G·P) state stay a few MiB of VMEM
_SSD_LOW = -1e30             # a masked exponent


def _ssd_lane_block(p):
    """→ (lanes of a block, heads sharing it)."""
    return (128, 128 // p) if p < 128 else (p, 1)


def _per_head(masks, vals):
    """One array whose lanes hold, for every head of a block, that head's
    value (a (rows, 1) column or a full tile)."""
    out = vals[-1]
    for keep, v in zip(masks[-2::-1], vals[-2::-1]):
        out = jnp.where(keep, v, out)
    return out


def _head_sums(masks, tile):
    """→ per head the (rows, 1) sums over that head's lanes."""
    return [jnp.sum(tile if keep is None else jnp.where(keep, tile, 0.0),
                    axis=1, keepdims=True) for keep in masks]


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, y_ref,
                    *rest, p):
    """One chunk of one group: y, the state carried on, and (where the
    call has an output for it) the state that entered, for the backward."""
    *s_ref, st_ref = rest
    f32 = jnp.float32
    q, lanes = x_ref.shape[1:]
    width, per = _ssd_lane_block(p)
    r = lanes // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    b = b_ref[0].astype(f32)
    cq = _mxu(c_ref[0])
    cb = jax.lax.dot_general(cq, _mxu(b), _NT, preferred_element_type=f32)
    bt = _mxu(b.T)
    col, row = col_ref[0, 0], row_ref[0, 0]
    below = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)          # s <= t
    masks = _head_masks(width, per)
    for blk in range(lanes // width):
        at = slice(blk * width, (blk + 1) * width)
        x = x_ref[0, :, at].astype(f32)
        xq = _mxu(x)
        st = st_ref[:, at]
        sq = _mxu(st)
        if s_ref:
            s_ref[0][0, 0, :, at] = sq
        inside, grown, to_end, kept = [], [], [], []
        for h in range(blk * per, (blk + 1) * per):
            a_t, dt_t = col[:, h:h + 1], col[:, r + h:r + h + 1]
            a_s, dt_s = row[h:h + 1, :], row[r + h:r + h + 1, :]
            last = a_t[q - 1:q]
            m = cb * jnp.exp(jnp.where(below, a_t - a_s, _SSD_LOW)) * dt_s
            inside.append(jnp.dot(_mxu(m), xq, preferred_element_type=f32))
            grown.append(jnp.exp(a_t))
            to_end.append(jnp.exp(last - a_t) * dt_t)
            kept.append(jnp.exp(last))
        y = _per_head(masks, inside) + \
            jnp.dot(cq, sq, preferred_element_type=f32) * \
            _per_head(masks, grown) + d_ref[:, at] * x
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        st_ref[:, at] = _per_head(masks, kept) * st + jnp.dot(
            bt, _mxu(x * _per_head(masks, to_end)),
            preferred_element_type=f32)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, g_ref, s_ref,
                    d_ref, dx_ref, db_ref, dc_ref, gcol_ref, grow_ref, dd_ref,
                    dst_ref, *, p):
    """One chunk of one group, chunks in reverse: dx, the group's db and dc,
    and per step and head the cotangents of cum and of dt — `gcol` holds
    (d cum | d dt) with time on sublanes, `grow` the part of d cum that a
    sum over sublanes leaves with time on lanes; XLA adds the two and takes
    the reverse cumulative sum; `dd` sums g·x over the chunks, a lane a
    column.  Tiles are recomputed TRANSPOSED, [s, t], so that dx = mᵀ·g and
    db = dcbᵀ·c are plain products and the sums over t are sums over lanes;
    dc = (dcbᵀ)ᵀ·b is the one the MXU transposes."""
    f32 = jnp.float32
    q, lanes = x_ref.shape[1:]
    width, per = _ssd_lane_block(p)
    r = lanes // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, f32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, f32)

    b, c = b_ref[0].astype(f32), c_ref[0].astype(f32)
    bq, cq = _mxu(b), _mxu(c)
    ct = _mxu(c.T)
    cbt = jax.lax.dot_general(bq, cq, _NT, preferred_element_type=f32)
    col, row = col_ref[0, 0], row_ref[0, 0]
    above = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)          # t >= s
    ends = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    masks = _head_masks(width, per)
    dcbt = jnp.zeros((q, q), f32)
    db = jnp.zeros(b.shape, f32)
    dc = jnp.zeros(c.shape, f32)
    d_cum, d_dt, d_cum_row = [], [], []
    for blk in range(lanes // width):
        at = slice(blk * width, (blk + 1) * width)
        x = x_ref[0, :, at].astype(f32)
        g = g_ref[0, :, at].astype(f32)
        gq = _mxu(g)
        sq = s_ref[0, 0, :, at]
        ds = dst_ref[:, at]
        dsq = _mxu(ds)
        inside, direct, dts, grown, to_end, kept = [], [], [], [], [], []
        for h, keep in zip(range(blk * per, (blk + 1) * per), masks):
            a_s, dt_s = col[:, h:h + 1], col[:, r + h:r + h + 1]
            a_t = row[h:h + 1, :]
            last = a_s[q - 1:q]
            decay = jnp.exp(jnp.where(above, a_t - a_s, _SSD_LOW))
            inside.append(jnp.dot(_mxu(cbt * decay * dt_s), gq,
                                  preferred_element_type=f32))
            dm = jax.lax.dot_general(_mxu(x, keep), gq, _NT,
                                     preferred_element_type=f32) * decay
            dcbt += dm * dt_s
            z = dm * cbt
            direct.append(jnp.sum(z, axis=1, keepdims=True))
            d_cum_row.append(jnp.sum(z * dt_s, axis=0, keepdims=True))
            dts.append(dt_s)
            grown.append(jnp.exp(a_s))
            to_end.append(jnp.exp(last - a_s))
            kept.append(jnp.exp(last))
        grown, to_end = _per_head(masks, grown), _per_head(masks, to_end)
        kept, dt = _per_head(masks, kept), _per_head(masks, dts)
        # the state leaving the chunk: S' = kept·S + Σ_s to_end_s dt_s x_s b_sᵀ
        reach = jnp.dot(bq, dsq, preferred_element_type=f32) * to_end
        left = _head_sums(masks, x * reach)         # d dt_s through S'
        db += jax.lax.dot_general(_mxu(x * to_end * dt), dsq, _NT,
                                  preferred_element_type=f32)
        # the state entering it: y_t += exp(cum_t) S c_t
        g_grown = g * grown
        gq_grown = _mxu(g_grown)
        entered = _head_sums(masks, g_grown * jnp.dot(
            cq, sq, preferred_element_type=f32))    # d cum_t through exp(cum_t)
        dc += jax.lax.dot_general(gq_grown, sq, _NT,
                                  preferred_element_type=f32)
        held = [jnp.sum(v, axis=0, keepdims=True) for v in
                _head_sums(masks, ds * sq.astype(f32) * kept)]
        dst_ref[:, at] = kept * ds + jnp.dot(ct, gq_grown,
                                             preferred_element_type=f32)
        dx = _per_head(masks, inside) + reach * dt + d_ref[:, at] * g
        dx_ref[0, :, at] = dx.astype(dx_ref.dtype)
        dd_ref[0, :, at] += jnp.sum(g * x, axis=0, keepdims=True)
        for j in range(per):
            through = (direct[j] + left[j]) * dts[j]
            d_cum.append(
                entered[j] - through + jnp.where(
                    ends, held[j] + jnp.sum(left[j] * dts[j], axis=0,
                                            keepdims=True), 0.0))
            d_dt.append(direct[j] + left[j])
    db_ref[0] = db + jnp.dot(_mxu(dcbt), cq, preferred_element_type=f32)
    dc_ref[0] = dc + jax.lax.dot_general(_mxu(dcbt), bq, _TN,
                                         preferred_element_type=f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * r), 1)
    gcol = jnp.zeros((q, 2 * r), f32)
    for i, v in enumerate(d_cum + d_dt):
        gcol = jnp.where(lane == i, v, gcol)
    gcol_ref[0, 0] = gcol
    sub = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    grow = jnp.zeros((r, q), f32)
    for i, v in enumerate(d_cum_row):
        grow = jnp.where(sub == i, v, grow)
    grow_ref[0, 0] = grow


def _ssd_call(kernel, name, ins, outs, heads, groups, chunk, reverse):
    """One kernel over the (B, G, T/chunk) grid, chunk k (or, `reverse`,
    the k-th from the end) of group g a step.  `ins` and `outs` are
    (array or shape, kind) pairs: "x" a group's heads (chunk, H/G·P) of a
    (B, T, H·P) array, "n" its (chunk, N) of a (B, T, G·N) array, "col" /
    "row" the packed scalars ("grow": the cum half of a "row"), "d" the
    group's lanes of a (1, H·P) array,
    "s" the (N, H/G·P) state of a (B, T/chunk, N, H·P) array, "dd" the
    group's lanes of a (B, 1, H·P) sum over the chunks."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, hp = ins[0][0].shape
    n = ins[1][0].shape[2] // groups
    lanes, r = hp // groups, heads // groups
    nc = t // chunk

    def at(k):
        return nc - 1 - k if reverse else k

    specs = {
        "x": pl.BlockSpec((1, chunk, lanes), lambda b, g, k: (b, at(k), g)),
        "n": pl.BlockSpec((1, chunk, n), lambda b, g, k: (b, at(k), g)),
        "col": pl.BlockSpec((1, 1, chunk, 2 * r),
                            lambda b, g, k: (b, g, at(k), 0)),
        "row": pl.BlockSpec((1, 1, 2 * r, chunk),
                            lambda b, g, k: (b, g, 0, at(k))),
        "grow": pl.BlockSpec((1, 1, r, chunk),
                             lambda b, g, k: (b, g, 0, at(k))),
        "d": pl.BlockSpec((1, lanes), lambda b, g, k: (0, g)),
        "s": pl.BlockSpec((1, 1, n, lanes), lambda b, g, k: (b, at(k), 0, g)),
        "dd": pl.BlockSpec((1, 1, lanes), lambda b, g, k: (b, 0, g)),
    }
    return pl.pallas_call(
        kernel,
        out_shape=[a for a, _ in outs],
        grid=(bsz, groups, nc),
        in_specs=[specs[kind] for _, kind in ins],
        out_specs=[specs[kind] for _, kind in outs],
        scratch_shapes=[pltpu.VMEM((n, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(*(a for a, _ in ins))


def _ssd_scalars(dt, a, groups, chunk):
    """→ col (B, G, T, 2·H/G) = (cum | dt) and row, its transpose: cum the
    inclusive cumulative sum of dt·a inside each chunk, float32."""
    bsz, t, h = dt.shape
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a.astype(jnp.float32))
                     .reshape(bsz, t // chunk, chunk, h), axis=2)
    col = jnp.concatenate([cum.reshape(bsz, t, groups, h // groups),
                           dt.reshape(bsz, t, groups, h // groups)], axis=-1)
    col = col.transpose(0, 2, 1, 3)
    return col, col.transpose(0, 1, 3, 2)


def _ssd_d_lanes(d, heads, p):
    """d (H,) → (1, H·P), a head's value on each of its lanes (no d: 0)."""
    d = jnp.zeros((heads,), jnp.float32) if d is None else d
    return jnp.repeat(d.astype(jnp.float32), p)[None]


def _ssd_fwd_pallas(x, dt, a, b, c, d, heads, groups, chunk, emit=False):
    """→ [y (B, T, H·P)], with `emit` also the bfloat16 states entering
    the chunks (B, T/chunk, N, H·P), and the packed scalars."""
    bsz, t, hp = x.shape
    n = b.shape[2] // groups
    _count("hits", "ssd", n)
    col, row = _ssd_scalars(dt, a, groups, chunk)
    ins = [(x, "x"), (b, "n"), (c, "n"), (col, "col"), (row, "row"),
           (_ssd_d_lanes(d, heads, hp // heads), "d")]
    outs = [(jax.ShapeDtypeStruct(x.shape, x.dtype), "x")]
    if emit:
        outs.append((jax.ShapeDtypeStruct((bsz, t // chunk, n, hp),
                                          jnp.bfloat16), "s"))
    return _ssd_call(
        functools.partial(_ssd_fwd_kernel, p=hp // heads), "mx_ssd_fwd", ins, outs, heads, groups, chunk, False), col, row


def _ssd_bwd_pallas(x, b, c, d, col, row, states, g, heads, groups, chunk):
    """→ dx, db, dc (float32: sums over a group's heads), gcol, grow and
    the per-lane partial sums (B, 1, H·P) of d's cotangent."""
    bsz, t, hp = x.shape
    f32 = jnp.float32
    ins = [(x, "x"), (b, "n"), (c, "n"), (col, "col"), (row, "row"),
           (g, "x"), (states, "s"),
           (_ssd_d_lanes(d, heads, hp // heads), "d")]
    outs = [(jax.ShapeDtypeStruct(x.shape, x.dtype), "x"),
            (jax.ShapeDtypeStruct(b.shape, f32), "n"),
            (jax.ShapeDtypeStruct(c.shape, f32), "n"),
            (jax.ShapeDtypeStruct(col.shape, f32), "col"),
            (jax.ShapeDtypeStruct(row.shape[:2] + (heads // groups, t), f32),
             "grow"),
            (jax.ShapeDtypeStruct((bsz, 1, hp), f32), "dd")]
    return _ssd_call(
        functools.partial(_ssd_bwd_kernel, p=hp // heads), "mx_ssd_bwd", ins, outs, heads, groups, chunk, True)


def ssd_use_pallas(t, heads, groups, p, n, chunk):
    """The routing decision of `nn.ssd_chunked`: one TPU (or the tests'
    interpret switch), chunks of `_SSD_CHUNK` steps dividing T, a state
    size in lane tiles, whole groups whose heads fill 128-lane blocks (a
    head one or more blocks, or a whole share of one) and fit
    `_SSD_MAX_LANES`.  A "no" counts one fallback; the "yes" is counted
    where the forward kernel is emitted."""
    lanes = heads // groups * p
    ok = (_FORCE_INTERPRET or _pb.one_tpu()) and chunk == _SSD_CHUNK and \
        t % chunk == 0 and n % 128 == 0 and heads % groups == 0 and \
        lanes % 128 == 0 and lanes <= _SSD_MAX_LANES and \
        (p % 128 == 0 or 128 % p == 0)
    if not ok:
        _count("fallbacks", "ssd", n)
    return ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def ssd_fused(x, dt, a, b, c, d, heads, groups, chunk=_SSD_CHUNK):
    """Mamba-2's scan of `heads` heads in `groups` groups, read in place
    from the mixer's arrays: x (B, T, H·P), dt (B, T, H), a (H,), b and c
    (B, T, G·N), d (H,) or None → y (B, T, H·P).  `chunk` other than
    `_SSD_CHUNK` is the tests'."""
    return _ssd_fwd_pallas(x, dt, a, b, c, d, heads, groups, chunk)[0][0]


def _ssd_vjp_fwd(x, dt, a, b, c, d, heads, groups, chunk):
    (y, states), col, row = _ssd_fwd_pallas(x, dt, a, b, c, d, heads, groups,
                                            chunk, emit=True)
    return y, (x, dt, a, b, c, d, col, row, states)


def _ssd_vjp_bwd(heads, groups, chunk, res, g):
    x, dt, a, b, c, d, col, row, states = res
    dx, db, dc, gcol, grow, dd = _ssd_bwd_pallas(
        x, b, c, d, col, row, states, g, heads, groups, chunk)
    bsz, t, h = dt.shape
    r = h // groups
    # cum is an inclusive sum of dt·a inside a chunk: its cotangent reaches
    # dt_t·a from every later step of the chunk
    d_cum = (gcol[..., :r] + grow.transpose(0, 1, 3, 2)) \
        .transpose(0, 2, 1, 3).reshape(bsz, t // chunk, chunk, h)
    d_dta = jnp.flip(jnp.cumsum(jnp.flip(d_cum, 2), axis=2), 2) \
        .reshape(bsz, t, h)
    d_dt = gcol[..., r:].transpose(0, 2, 1, 3).reshape(bsz, t, h) + \
        d_dta * a.astype(jnp.float32)
    d_a = jnp.sum(d_dta * dt.astype(jnp.float32), axis=(0, 1))
    d_d = None if d is None else \
        dd.reshape(bsz, h, -1).sum(axis=(0, 2)).astype(d.dtype)
    return (dx, d_dt.astype(dt.dtype), d_a.astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype), d_d)


ssd_fused.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


# ----------------------------------------- rows of a sorted slot, in place
# `parallel/moe.py::moe_topk_held` adds the rows a held expert's slot made
# into the tokens they came from.  Within a slot the live rows' tokens are
# sorted and unique, so no two of them meet: each target row is read, added
# to and written back by its own DMA, the (S, D) array staying in HBM.  XLA's
# scatter-add walks the rows one after another (0.69 ms for 2 304 rows of
# 2 688 float32 of which 535 are live, flags or not: PERF.md section 6,
# PR 35); gathering a slot's rows it does at the HBM rate, so there is no
# gather kernel.
#
# A DMA moves whole (8, 128) tiles, so a row must lie on a dimension that is
# not tiled: the target is held as (S, D / 128, 128) -- a row is D / 128
# sublanes of one tile column -- while the loop runs, and reshaped once
# after it.
_ROWS_VMEM = 10 * 2 ** 20    # the slot rows' tile twice and the target rows'


def rows_use_pallas(rows, d, dtype):
    """The routing decision of `moe_topk_held`'s row moves: one TPU (or the
    tests' interpret switch), float32 rows in whole 128-lane blocks, a slot
    in whole tiles of 128 rows.  Counted either way, once a traced layer:
    ``dispatch.pallas.hits.moe_rows.<D>`` / ``...fallbacks.moe_rows.<D>``."""
    ok = (_FORCE_INTERPRET or _pb.one_tpu()) and d % 128 == 0 and \
        rows % 128 == 0 and jnp.dtype(dtype) == jnp.float32
    _count("hits" if ok else "fallbacks", "moe_rows", d)
    return ok


def _rows_scatter_add_kernel(tok_ref, live_ref, upd_ref, _, y_ref, buf, sem,
                             *, tile):
    from jax.experimental.pallas import tpu as pltpu
    base = pl.program_id(0) * tile
    n = jnp.clip(live_ref[0] - base, 0, tile)   # live rows lead the slot

    def each(read, wait):
        def body(j, carry):
            there, here = y_ref.at[tok_ref[base + j]], buf.at[j]
            copy = pltpu.make_async_copy(there, here, sem) if read else \
                pltpu.make_async_copy(here, there, sem)
            copy.wait() if wait else copy.start()
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    @pl.when(n > 0)                  # a tile wholly past `live` moves nothing
    def _():
        each(True, False)            # every target row in flight at once,
        each(True, True)
        buf[...] += upd_ref[...].reshape(buf.shape)
        each(False, False)           # and back: no two are the same row
        each(False, True)


def rows_scatter_add(y3, tok, live, upd):
    """``y3`` (S, D/128, 128) with ``upd``'s (rows, D) first ``live`` rows
    added at rows ``tok[:live]`` -- which must be unique (sorted besides,
    they are read in order) -- in place: ``y3`` is aliased to the result.
    ``tok`` (rows,) and ``live`` () int32; rows past ``live`` are not
    read."""
    from jax.experimental.pallas import tpu as pltpu
    s, c, lanes = y3.shape
    rows, d = upd.shape
    padded = -(-c // 8) * 8 * lanes * y3.dtype.itemsize
    tile = 256 if rows % 256 == 0 and 3 * 256 * padded <= _ROWS_VMEM else 128
    return pl.pallas_call(
        functools.partial(_rows_scatter_add_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile, c, lanes), y3.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(y3.shape, y3.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="mx_rows_scatter_add",
    )(tok, jnp.reshape(live, (1,)).astype(jnp.int32), upd, y3)


# ------------------------- the gated delta rule's chunk states (KDA and GDN)
#
# `ops/nn.py::_delta_rule_chunked` ends in a recurrence over the chunks of a
# head: with the state S (dk, dv) entering a chunk, u = u0 − w S,
# o = qg S + a_qk u, S ← g_end ⊙ S + k_endᵀ u.  The grid is (B, H / heads a
# step, T / chunk), chunks sequential: the state is a VMEM scratch, the
# chunk's tiles are read in place from the (B, H, nc, chunk, ·) arrays the
# composition holds, and a step takes several heads so that their chains of
# dependent products overlap.  u0 and w are read out of the triangular
# solve's one result `wu` = [w | u0] (no slice of it is made in HBM; Mosaic
# takes the lane offset dk as it is).  The backward walks the
# chunks in reverse with the state's cotangent as its scratch and makes u
# again; the one residual the forward emits for it is the state entering
# every chunk.
#
# The state is held transposed, (dv, dk): the decay of a chunk is then a row
# (1, dk) — or (1, 1) where it is one number a head — that broadcasts over
# it, and its cotangent is a sum over sublanes.  dk and dv are what the
# model has (Mosaic takes 96 and 192 as they are: a block's last axis is the
# array's).
#
# Precision: HBM arrays, the state, its cotangent, u and every sum are
# float32; MXU operands are rounded to bfloat16, as for the kernels above.

_DELTA_CHUNK = 64            # the chunk the route takes (both models')
_DELTA_MAX_STATE = 256 * 256  # dk·dv of a head, each in whole lane tiles
_DELTA_VMEM = 6 * 2 ** 20    # a step's blocks, once (the pipeline holds two)


def _lanes(d):
    """`d` lanes in whole 128-lane tiles."""
    return -(-d // 128) * 128


def _delta_fwd_kernel(wu_ref, qg_ref, a_ref, ke_ref, g_ref, o_ref, *rest):
    """One chunk of a few heads: o, the state carried on, and (where the
    call has an output for it) the state that entered, for the backward."""
    *s_ref, st_ref = rest
    f32 = jnp.float32
    t, dv = o_ref.shape[3:]
    dk = qg_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    for h in range(o_ref.shape[1]):
        st = st_ref[h]                                       # (dv, dk)
        if s_ref:
            s_ref[0][0, h, 0] = st
        sq = _mxu(st)
        both = jax.lax.dot_general(
            _mxu(jnp.concatenate([wu_ref[0, h, 0, :, :dk],
                                  qg_ref[0, h, 0]], axis=0)),
            sq, _NT, preferred_element_type=f32)             # (2t, dv)
        uq = _mxu(wu_ref[0, h, 0, :, dk:] - both[:t])
        o_ref[0, h, 0] = both[t:] + jnp.dot(_mxu(a_ref[0, h, 0]), uq,
                                            preferred_element_type=f32)
        st_ref[h] = g_ref[0, h, 0] * st + jax.lax.dot_general(
            uq, _mxu(ke_ref[0, h, 0]), _TN, preferred_element_type=f32)


def _delta_bwd_kernel(wu_ref, qg_ref, a_ref, ke_ref, g_ref, s_ref, do_ref,
                      dwu_ref, dqg_ref, da_ref, dke_ref, dg_ref, dst_ref):
    """One chunk of a few heads, chunks in reverse: the cotangents of the
    five arrays (`dwu` = [dw | du0]) and the cotangent of the state that
    entered, carried on.  `dst` holds the cotangent of the state the chunk
    leaves, transposed as the state is."""
    f32 = jnp.float32
    dk = qg_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, f32)

    for h in range(do_ref.shape[1]):
        st, dst = s_ref[0, h, 0], dst_ref[h]                 # (dv, dk)
        sq, dsq = _mxu(st), _mxu(dst)
        wq, qgq = _mxu(wu_ref[0, h, 0, :, :dk]), _mxu(qg_ref[0, h, 0])
        keq, aq = _mxu(ke_ref[0, h, 0]), _mxu(a_ref[0, h, 0])
        doq = _mxu(do_ref[0, h, 0])
        uq = _mxu(wu_ref[0, h, 0, :, dk:] - jax.lax.dot_general(
            wq, sq, _NT, preferred_element_type=f32))
        du = jax.lax.dot_general(aq, doq, _TN, preferred_element_type=f32) + \
            jax.lax.dot_general(keq, dsq, _NT, preferred_element_type=f32)
        duq = _mxu(du)
        dwu_ref[0, h, 0, :, dk:] = du
        dwu_ref[0, h, 0, :, :dk] = -jnp.dot(duq, sq,
                                            preferred_element_type=f32)
        da_ref[0, h, 0] = jax.lax.dot_general(doq, uq, _NT,
                                              preferred_element_type=f32)
        dqg_ref[0, h, 0] = jnp.dot(doq, sq, preferred_element_type=f32)
        dke_ref[0, h, 0] = jnp.dot(uq, dsq, preferred_element_type=f32)
        g = g_ref[0, h, 0]
        dg = jnp.sum(dst * st, axis=0, keepdims=True)        # (1, dk)
        dg_ref[0, h, 0] = dg if g.shape == dg.shape else \
            jnp.sum(dg, axis=1, keepdims=True)
        # dSᵀ = doᵀ qg + g ⊙ dS'ᵀ − duᵀ w, the two products as one
        dst_ref[h] = g * dst + jax.lax.dot_general(
            jnp.concatenate([doq, duq], axis=0),
            jnp.concatenate([qgq, -wq], axis=0), _TN,
            preferred_element_type=f32)


def _delta_heads_a_step(heads, arrays):
    """The most heads (a divisor of `heads`) whose blocks of a step — one
    head's (rows, lanes) of every array of `arrays`, float32, lanes in
    whole tiles as VMEM holds them — stay within `_DELTA_VMEM`."""
    one = 4 * sum(a.shape[-2] * _lanes(a.shape[-1]) for a in arrays)
    return _fit_block(heads, max(1, _DELTA_VMEM // one))


def _delta_call(kernel, name, ins, outs, reverse):
    """One kernel over the (B, H / per, nc) grid, chunk k (or, `reverse`,
    the k-th from the end) of `per` heads a step; every array is
    (B, H, nc, rows, lanes), `ins` starts with wu and qg, and a block is
    `per` heads' (rows, lanes) of one chunk.  The scratch is `per` states
    (dv, dk)."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, heads, nc = ins[0].shape[:3]
    dk = ins[1].shape[-1]
    dv = ins[0].shape[-1] - dk
    per = _delta_heads_a_step(heads, list(ins) + list(outs))

    def spec(a):
        return pl.BlockSpec(
            (1, per, 1) + a.shape[3:],
            (lambda b, h, k: (b, h, nc - 1 - k, 0, 0)) if reverse else
            (lambda b, h, k: (b, h, k, 0, 0)))

    return pl.pallas_call(
        kernel, out_shape=outs, grid=(bsz, heads // per, nc),
        in_specs=[spec(a) for a in ins], out_specs=[spec(a) for a in outs],
        scratch_shapes=[pltpu.VMEM((per, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(), name=name)(*ins)


def _delta_fwd_pallas(counted, wu, qg, a_qk, k_end, g_end, emit=False):
    """→ [o (B, H, nc, chunk, dv)], with `emit` also the states entering
    the chunks, transposed: (B, H, nc, dv, dk) float32.  `counted` is the
    (kernel, last_dim) of the hit."""
    f32 = jnp.float32
    bsz, heads, nc, chunk, dk = qg.shape
    dv = wu.shape[-1] - dk
    _count("hits", *counted)
    outs = [jax.ShapeDtypeStruct((bsz, heads, nc, chunk, dv), f32)]
    if emit:
        outs.append(jax.ShapeDtypeStruct((bsz, heads, nc, dv, dk), f32))
    return _delta_call(_delta_fwd_kernel, "mx_delta_rule_fwd",
                       (wu, qg, a_qk, k_end, g_end), outs, False)


def delta_rule_use_pallas(t, heads, dk, dv, chunk, kind="kda"):
    """The routing decision of `nn._delta_rule_chunked`'s recurrence: one
    TPU (or the tests' interpret switch), chunks of `_DELTA_CHUNK` steps
    (T is padded to whole chunks before it), dk and dv in whole sublane
    tiles and a state of at most `_DELTA_MAX_STATE` numbers in VMEM.  A
    "no" counts one fallback under `kind` ("kda" or "gdn"); the "yes" is
    counted where the forward kernel is emitted."""
    del t, heads          # any: T is in whole chunks, heads are grid steps
    ok = (_FORCE_INTERPRET or _pb.one_tpu()) and chunk == _DELTA_CHUNK and \
        dk % 8 == 0 and dv % 8 == 0 and \
        _lanes(dk) * _lanes(dv) <= _DELTA_MAX_STATE
    if not ok:
        _count("fallbacks", kind, dk)
    return ok


def delta_rule_fused(kind, wu, qg, a_qk, k_end, g_end):
    """The recurrence over the chunk states of `nn._delta_rule_chunked`,
    read in place from its float32 arrays: wu (B, H, nc, chunk, dk + dv) =
    [w | u0]; qg and k_end (B, H, nc, chunk, dk); a_qk (B, H, nc, chunk,
    chunk); g_end (B, H, nc, dk) or (B, H, nc, 1) → o (B, H, nc, chunk,
    dv).  `kind` ("kda" or "gdn") names the counter."""
    return _delta_rule_tiles((kind, qg.shape[-1]), wu, qg, a_qk, k_end,
                             g_end[..., None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _delta_rule_tiles(counted, *tiles):
    return _delta_fwd_pallas(counted, *tiles)[0]


def _delta_vjp_fwd(counted, *tiles):
    o, states = _delta_fwd_pallas(counted, *tiles, emit=True)
    return o, (tiles, states)


def _delta_vjp_bwd(counted, res, do):
    tiles, states = res
    return tuple(_delta_call(
        _delta_bwd_kernel, "mx_delta_rule_bwd", tiles + (states, do),
        [jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in tiles], True))


_delta_rule_tiles.defvjp(_delta_vjp_fwd, _delta_vjp_bwd)


# ---------------------------------- a held expert's products, its live rows
# `parallel/moe.py::moe_topk_held` gives every held expert a slot of `rows`
# sorted rows: the first `live` are its tokens, the rest the gather's zeros.
# These kernels run a slot's products on the row tiles that hold a live row
# and on no other: the grid's length is that number of tiles (one at
# least), so a tile wholly past `live` is neither read nor written.  Its
# rows of a result keep whatever HBM held, and nothing reads them (the row
# adds stop at `live`, the sorted weights' cotangent is masked by it).
# Inside a computed tile the rows past `live` come out zero: their inputs
# are zero, and so is `act(0)`.
#
# Rows on the output (`mx_moe_live_fwd`, `mx_moe_live_bwd`): a (tile, D)
# tile of rows through both of the expert's weights, each held once in VMEM
# as bfloat16 -- read in place from the stack of the held experts' weights
# at the prefetched expert, one buffer (its block never moves) -- with the
# activation, its derivative and the sorted weights applied to the float32
# tiles between the products.  Rows on the reduction (`mx_moe_live_*_grad`):
# aᵀ b over the live tiles, summed into a float32 block of the weight's
# cotangent that stays in VMEM while they pass; the cotangent is cut along
# the model width D so that a block fits.
#
# Precision is the TPU's default for the float32 products they replace:
# MXU operands rounded to bfloat16, float32 sums.  The hidden rows and
# their cotangent, which are only ever MXU operands, are stored rounded.
_LIVE_TILE = 128             # rows a grid step
_LIVE_WEIGHTS = 40 * 2 ** 20  # an expert's two weights whole in VMEM, bf16
_LIVE_BLOCK = 4 * 2 ** 20    # a block of a weight's float32 cotangent


def live_use_pallas(rows, d, f, fu, dtype):
    """The routing decision of `moe_topk_held`'s products: one TPU (or the
    tests' interpret switch), float32 rows, D in whole 128-lane blocks, a
    slot in whole tiles of 128 rows, an expert's (D, fu) and (F, D)
    weights whole in VMEM as bfloat16.  The expert width is taken whole,
    so F need not be in lane blocks -- but for an activation that splits a
    fused (fu = 2F) product in two, each half in whole lane blocks.
    Counted either way, once a traced layer:
    ``dispatch.pallas.hits.moe_live.<D>`` / ``...fallbacks.moe_live.<D>``."""
    ok = (_FORCE_INTERPRET or _pb.one_tpu()) and d % 128 == 0 and \
        rows % _LIVE_TILE == 0 and (fu == f or f % 128 == 0) and \
        2 * d * (f + fu) <= _LIVE_WEIGHTS and jnp.dtype(dtype) == jnp.float32
    _count("hits" if ok else "fallbacks", "moe_live", d)
    return ok


def _live_tiles(live):
    """The grid's length: the tiles that hold a live row, one at least (a
    weight's cotangent is written even where the slot is empty)."""
    return jnp.maximum((live + _LIVE_TILE - 1) // _LIVE_TILE, 1)


def _live_fwd_kernel(_, x_ref, w_ref, up_ref, down_ref, o_ref, *, act):
    f32 = jnp.float32
    h = act(jnp.dot(_mxu(x_ref[...]), up_ref[...],
                    preferred_element_type=f32))
    o_ref[...] = jnp.dot(_mxu(h), down_ref[...],
                         preferred_element_type=f32) * w_ref[...]


def _live_bwd_kernel(_, x_ref, g_ref, w_ref, up_ref, down_ref, h_ref, dh_ref,
                     dx_ref, dw_ref, *, act):
    """The hidden rows' cotangent from ``g downᵀ``, which also gives the
    sorted weights': Σ act(x up) · (g downᵀ) over F -- no second product by
    down is needed."""
    f32 = jnp.float32
    gh = jax.lax.dot_general(_mxu(g_ref[...]), down_ref[...], _NT,
                             preferred_element_type=f32)
    h, back = jax.vjp(act, jnp.dot(_mxu(x_ref[...]), up_ref[...],
                                   preferred_element_type=f32))
    dh = back(gh * w_ref[...])[0]
    h_ref[...] = h.astype(h_ref.dtype)
    dh_ref[...] = dh.astype(dh_ref.dtype)
    dw_ref[...] = jnp.sum(h * gh, axis=1, keepdims=True)
    dx_ref[...] = jax.lax.dot_general(_mxu(dh), up_ref[...], _NT,
                                      preferred_element_type=f32)


def _live_rows(kernel, name, e, live, rows_in, stacks, outs):
    """`kernel` on the live row tiles: `rows_in` (rows, c) arrays in
    (tile, c) blocks, expert `e`'s weights of `stacks` (count, ·, ·) whole
    and once; `outs` the (rows, c) results' shapes."""
    from jax.experimental.pallas import tpu as pltpu
    tm = _LIVE_TILE

    def tile(a):
        return pl.BlockSpec((tm, a.shape[1]), lambda i, e: (i, 0))

    def whole(w):
        return pl.BlockSpec((None,) + w.shape[1:], lambda i, e: (e[0], 0, 0),
                            pipeline_mode=pl.Buffered(1))

    widths = [a.shape[1] for a in list(rows_in) + list(outs)] + \
        [w.shape[2] for w in stacks]
    vmem = sum(w.size // w.shape[0] * w.dtype.itemsize for w in stacks) + \
        4 * tm * (2 * sum(_lanes(c) for c in widths) +
                  6 * max(_lanes(c) for c in widths)) + (4 << 20)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_live_tiles(live),),
            in_specs=[tile(a) for a in rows_in] + [whole(w) for w in stacks],
            out_specs=[tile(a) for a in outs]),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=_interpret(), name=name,
    )(jnp.reshape(e, (1,)).astype(jnp.int32), *rows_in, *stacks)


def _live_sum_kernel(_, a_ref, b_ref, *refs, scaled, add):
    w_ref = refs[0] if scaled else None
    stack_ref, o_ref = refs[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = stack_ref[...] if add else \
            jnp.zeros(o_ref.shape, o_ref.dtype)

    b = b_ref[...]
    if scaled:
        b = b * w_ref[...]
    o_ref[0] += jax.lax.dot_general(_mxu(a_ref[...]), _mxu(b), _TN,
                                    preferred_element_type=jnp.float32)


def _live_sum(name, e, live, a, b, w, stack, add, cut_a=True):
    """aᵀ (b w) over the live row tiles of a (rows, P) and b (rows, Q) (w
    (rows, 1), or none) → `stack` (count, P, Q) float32 with expert `e`'s
    (P, Q) replaced by it (`add`: by it added to what was there), in place;
    made in blocks of a's columns (`cut_a`) or b's, in whole lane tiles."""
    from jax.experimental.pallas import tpu as pltpu
    rows, p = a.shape
    q = b.shape[1]
    tm = _LIVE_TILE
    cut, whole = (p, q) if cut_a else (q, p)
    blk = 128 * _fit_block(cut // 128,
                           max(1, _LIVE_BLOCK // (128 * 4 * _lanes(whole))))
    specs = [pl.BlockSpec((tm, blk if cut_a else p),
                          lambda j, i, e: (i, j if cut_a else 0)),
             pl.BlockSpec((tm, q if cut_a else blk),
                          lambda j, i, e: (i, 0 if cut_a else j))]
    ins = [a, b]
    if w is not None:
        ins.append(w)
        specs.append(pl.BlockSpec((tm, 1), lambda j, i, e: (i, 0)))
    out = pl.BlockSpec((1,) + ((blk, q) if cut_a else (p, blk)),
                       lambda j, i, e: (e[0], j, 0) if cut_a else
                       (e[0], 0, j))
    # the block in, out and summed (twice each), its product's temporaries
    vmem = 4 * (8 * blk * _lanes(whole) +
                tm * 3 * (_lanes(specs[0].block_shape[1]) +
                          _lanes(specs[1].block_shape[1]))) + (4 << 20)
    return pl.pallas_call(
        functools.partial(_live_sum_kernel, scaled=w is not None, add=add),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cut // blk, _live_tiles(live)),
            # what was there is read only where it is added onto
            in_specs=specs + [out if add else pl.BlockSpec(
                memory_space=pl.ANY)],
            out_specs=out),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        input_output_aliases={len(ins) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_interpret(), name=name,
    )(jnp.reshape(e, (1,)).astype(jnp.int32), *ins, stack)


def slot_products(act, e, live, xs, up, down, w):
    """What one slot of held expert `e` gives, ``act(xs up_e) down_e * w``,
    on its live row tiles: xs (rows, D), its first `live` rows live, float32
    or already rounded to bfloat16; up (count, D, fu) and down (count, F,
    D), the held experts' stacks, bfloat16; w (rows, 1) float32 → (rows,
    D) float32."""
    rows, d = xs.shape
    return _live_rows(
        functools.partial(_live_fwd_kernel, act=act), "mx_moe_live_fwd", e,
        live, (xs, w), (up, down),
        [jax.ShapeDtypeStruct((rows, d), jnp.float32)])[0]


def slot_products_vjp(act, e, live, xs, up, down, w, dout, sums, add):
    """The cotangents of `slot_products` for the rows' ``dout`` (rows, D):
    → (d xs, d up, d down, d w (rows, 1)), float32, where the weights' are
    `sums` (as `weight_sums` makes them) with expert `e`'s written in place
    (`add`: added onto what was there)."""
    rows, d = xs.shape
    f, fu = down.shape[1], up.shape[2]
    bf16, f32 = jnp.bfloat16, jnp.float32
    h, dh, dxs, dw = _live_rows(
        functools.partial(_live_bwd_kernel, act=act), "mx_moe_live_bwd", e,
        live, (xs, dout, w), (up, down),
        [jax.ShapeDtypeStruct((rows, f), bf16),
         jax.ShapeDtypeStruct((rows, fu), bf16),
         jax.ShapeDtypeStruct((rows, d), f32),
         jax.ShapeDtypeStruct((rows, 1), f32)])
    up_t = sums[0].shape != up.shape
    d_up = _live_sum("mx_moe_live_up_grad", e, live, *(
        (dh, xs) if up_t else (xs, dh)), None, sums[0], add, cut_a=not up_t)
    d_down = _live_sum("mx_moe_live_down_grad", e, live, h, dout, w, sums[1],
                       add, cut_a=False)
    return dxs, d_up, d_down, dw


def weight_sums(up, down):
    """Zeros for the held experts' weight cotangents as `slot_products_vjp`
    writes them.  Up's is made as (count, fu, D) where fu is no whole lane
    tiles: the TPU lays such a (count, D, fu) array out with D minor, so
    that `weights_of` then moves nothing."""
    if up.shape[2] % 128:
        return (jnp.zeros((up.shape[0], up.shape[2], up.shape[1]),
                          jnp.float32), jnp.zeros(down.shape, jnp.float32))
    return jnp.zeros(up.shape, jnp.float32), jnp.zeros(down.shape, jnp.float32)


def weights_of(sums, up):
    """The weights' cotangents from `weight_sums`' form: up's, down's."""
    d_up, d_down = sums
    return (d_up if d_up.shape == up.shape else jnp.swapaxes(d_up, 1, 2),
            d_down)
