"""Fused residual-block Pallas pipeline: conv + BN (+ add) (+ ReLU).

ROADMAP item 2 (VERDICT r05 #2): the lone 3×3/s1 implicit-GEMM conv
(``conv3x3_s1`` below) covered one conv; the ResNet hot loop spends its
HBM bandwidth on the *epilogue* — every conv output made four HBM round
trips (conv write, BN read+write, add/ReLU read+write) before the next
layer read it.  This module fuses the whole block tail into the conv
kernel:

- **frozen stats** (inference / use_global_stats): BN folds to a
  per-channel affine ``y = conv(x,w)·scale + shift`` with
  ``scale = γ·rsqrt(σ²+ε)``, ``shift = β − μ·scale`` — one kernel, one
  HBM round trip, residual add and ReLU applied in-register.
- **training**: batch stats need the full conv output, so the pipeline
  is two fused passes — pass 1 computes the conv AND accumulates the
  per-channel Σz/Σz² into a revisited f32 accumulator block (the stats
  ride along for free on the f32 MXU accumulator before the bf16
  down-cast); pass 2 is a fused elementwise affine+add+ReLU kernel.
  Two round trips instead of four.

All kernels are **row-blocked**: the grid is ``(N, H // bh)`` with the
padded image fetched once per batch index while ``bh``-row output
blocks stream through VMEM — Pallas's automatic pipelining then
double-buffers the NEXT image's HBM→VMEM DMA against the current
image's row-block compute.  ``bh`` comes from the per-stage tiling
table (``_TILES``), which is how dgrad/wgrad stay competitive on the
stage-2/3 shapes whose whole-image blocks blew the VMEM budget.

Routing is one rule, in code: a call takes these kernels when its
shapes and dtype pass ``eligible_block``, its ``HxWxC`` stage is one
``_DEFAULT_TABLE`` routes, and this process drives exactly one TPU
(``one_tpu()``).  ``decide()`` counts the answer
(``dispatch.pallas.{hits,fallbacks}.<stage>``); nothing else can change
it, so no cache keys on it.  ``MXNET_TPU_PALLAS_INTERPRET`` chooses no
path: it runs the same kernels interpreted on a TPU, to debug a Mosaic
fault.

Interpret mode (automatic off the TPU: the CPU's tests) runs the same
kernels unmodified.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["interpret", "one_tpu", "stage_key", "decide", "conv_wins",
           "eligible_block", "conv3x3", "conv3x3_dgrad", "conv3x3_wgrad",
           "conv3x3_s1", "residual_block_fused", "block_active"]


def _tele():
    from .. import telemetry
    return telemetry


def one_tpu() -> bool:
    """Default-on condition shared by every Pallas route: this process
    drives exactly one TPU.  A Mosaic kernel cannot be partitioned by
    GSPMD (the lowering refuses: "wrap the call in a shard_map"), and a
    route decided at trace time cannot see whether the program it is
    traced into will be partitioned — with more than one device any
    program may be.  So on a multi-chip host every default route answers
    XLA until the kernels are wrapped (ROADMAP D2)."""
    devs = jax.devices()
    return devs[0].platform == "tpu" and len(devs) == 1


def interpret() -> bool:
    """Pallas interpret mode: forced off-TPU, or via env for on-TPU
    debugging."""
    return jax.devices()[0].platform != "tpu" or \
        os.environ.get("MXNET_TPU_PALLAS_INTERPRET", "") == "1"


# ------------------------------------------------------------ tiling table
# Per-stage row-block heights.  ``fwd`` rows ride the forward / dgrad / the
# train-mode affine pass; ``wgrad`` rows block the cotangent stream of
# the weight-grad accumulation.  Anything not listed falls back to the
# largest divisor of H whose patch block fits the budget.
_TILES = {
    "56x56x64": {"fwd": 14, "wgrad": 14},
    "28x28x128": {"fwd": 14, "wgrad": 14},
    "14x14x256": {"fwd": 7, "wgrad": 7},
}

# Patch-matrix block budget: (bh·W, 9C) is the VMEM resident the MXU
# streams from; 2 MiB keeps double-buffered fwd+wgrad under the 12 MiB
# bound the lone-conv kernel measured against the 16 MiB scoped-vmem limit.
_PATCH_BLOCK_BYTES = 2 * 1024 * 1024


def stage_key(H: int, W: int, C: int) -> str:
    return f"{H}x{W}x{C}"


def _pick_bh(H, W, C, itemsize, kind="fwd") -> int:
    t = _TILES.get(stage_key(H, W, C))
    if t and H % t.get(kind, 0) == 0:
        return t[kind]
    for bh in range(min(H, 16), 0, -1):
        if H % bh == 0 and bh * W * 9 * C * itemsize <= _PATCH_BLOCK_BYTES:
            return bh
    return 1


# --------------------------------------------------------- dispatch table
# Which stages route, forward and backward: the one constant ``decide``
# and ``conv_wins`` read.  An A/B of a stage is one entry flipped here
# and the ResNet cell read, parent against change.
_DEFAULT_TABLE = {
    "56x56x64": {"fwd": "pallas", "bwd": "pallas"},
    "28x28x128": {"fwd": "xla", "bwd": "xla"},
    "14x14x256": {"fwd": "xla", "bwd": "xla"},
}


def block_active() -> bool:
    """True when at least one stage would route to Pallas — the gluon
    layer's cue to take the fused forward at all."""
    return one_tpu() and any(e["fwd"] == "pallas"
                             for e in _DEFAULT_TABLE.values())


def eligible_block(x_shape, w_shape, dtype, has_residual=False) -> bool:
    """Shape/VMEM gate for the row-blocked kernels: 3×3 filters on a
    4-D NHWC activation, padded image + one row block (patches, out,
    residual, z) double-buffered under the 12 MiB budget."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if tuple(w_shape[:2]) != (3, 3) or w_shape[2] != x_shape[-1]:
        return False
    _, H, W, C = x_shape
    cout = w_shape[-1]
    if H < 1 or W < 1:
        return False
    isz = jnp.dtype(dtype).itemsize
    bh = _pick_bh(H, W, C, isz)
    blk = bh * W * (9 * C * isz            # patch matrix
                    + cout * 4             # f32 accumulator
                    + cout * isz * (2 + (1 if has_residual else 0)))  # z/out/res
    bytes_needed = 2 * ((H + 2) * (W + 2) * C * isz    # image, double-buffered
                        + blk
                        + 9 * C * cout * 4)            # weights + wgrad acc
    return bytes_needed < 12 * 1024 * 1024


Route = collections.namedtuple("Route", "fwd bwd stage")


def _routed(x_shape, w_shape, dtype, has_residual=False):
    """The ``_DEFAULT_TABLE`` entry of a call the kernels can take
    (``eligible_block``) on a stage whose forward routes, else None."""
    if not eligible_block(x_shape, w_shape, dtype, has_residual):
        return None
    ent = _DEFAULT_TABLE.get(stage_key(*x_shape[1:]))
    return ent if ent and ent["fwd"] == "pallas" else None


def decide(x_shape, w_shape, dtype, has_residual=False) -> Route:
    """Per-stage routing decision for a 3×3/s1 residual block.  Emits
    the ``dispatch.pallas.{hits,fallbacks}.<stage>`` counters — these
    count routing *decisions* (trace/dispatch time): a steady-state
    fused step re-decides nothing, by design."""
    _, H, W, C = x_shape if len(x_shape) == 4 else (0, 0, 0, 0)
    stage = stage_key(H, W, C)
    if not one_tpu():
        return Route("xla", "xla", stage)
    ent = _routed(x_shape, w_shape, dtype, has_residual)
    if ent is None:
        _tele().counter_add(f"dispatch.pallas.fallbacks.{stage}", 1)
        return Route("xla", "xla", stage)
    _tele().counter_add(f"dispatch.pallas.hits.{stage}", 1)
    return Route("pallas", ent["bwd"], stage)


def conv_wins(x_shape, w_shape, stride, pad, dilate, groups, dtype) -> bool:
    """The same rule for the STANDALONE conv of ops/nn.py: a 3×3,
    stride-1, SAME, undilated, ungrouped conv on a stage whose forward
    routes.  Silent — the block counters belong to ``decide``."""
    if not one_tpu():
        return False
    st = stride if isinstance(stride, (tuple, list)) else (stride, stride)
    pd = pad if isinstance(pad, (tuple, list)) else (pad, pad)
    dl = dilate if isinstance(dilate, (tuple, list)) else (dilate, dilate)
    if groups != 1 or tuple(st) != (1, 1) or tuple(pd) != (1, 1) \
            or tuple(dl) != (1, 1):
        return False
    return _routed(x_shape, w_shape, dtype) is not None


# ---------------------------------------------------------------- kernels
def _patches(xp_ref, r0, bh, W, C):
    """(bh·W, 9C) patch matrix for output rows [r0, r0+bh): nine shifted
    row-block loads from the padded-image ref (the TPU lowering slices
    refs, not loaded values), tap-major columns (matches the
    (3,3,C,Cout) → (9C,Cout) weight reshape)."""
    cols = [xp_ref[0, pl.ds(r0 + dh, bh), pl.ds(dw, W), :]
            .reshape(bh * W, C)
            for dh in range(3) for dw in range(3)]
    return jnp.concatenate(cols, axis=1)


def _conv_kernel(xp_ref, w_ref, out_ref, *, bh, W, C, Cout):
    i = pl.program_id(1)
    acc = jnp.dot(_patches(xp_ref, i * bh, bh, W, C), w_ref[:],
                  preferred_element_type=jnp.float32)
    out_ref[0] = acc.reshape(bh, W, Cout).astype(out_ref.dtype)


def _conv_affine_kernel(*refs, bh, W, C, Cout, add, relu):
    """Frozen-stats fused forward: conv + per-channel affine (folded BN)
    + residual add + ReLU, all on the f32 accumulator in VMEM."""
    if add:
        xp_ref, w_ref, sc_ref, sh_ref, res_ref, out_ref = refs
    else:
        xp_ref, w_ref, sc_ref, sh_ref, out_ref = refs
    i = pl.program_id(1)
    acc = jnp.dot(_patches(xp_ref, i * bh, bh, W, C), w_ref[:],
                  preferred_element_type=jnp.float32)
    acc = acc * sc_ref[0] + sh_ref[0]
    if add:
        acc += res_ref[0].reshape(bh * W, Cout).astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    out_ref[0] = acc.reshape(bh, W, Cout).astype(out_ref.dtype)


def _conv_stats_kernel(xp_ref, w_ref, z_ref, s1_ref, s2_ref,
                       *, bh, W, C, Cout):
    """Training pass 1: conv + per-channel Σz / Σz² accumulated into a
    revisited (1, Cout) f32 block across the whole grid (sequential TPU
    grid → revisiting is safe), read straight off the f32 accumulator."""
    n, i = pl.program_id(0), pl.program_id(1)
    acc = jnp.dot(_patches(xp_ref, i * bh, bh, W, C), w_ref[:],
                  preferred_element_type=jnp.float32)
    z_ref[0] = acc.reshape(bh, W, Cout).astype(z_ref.dtype)
    s1 = jnp.sum(acc, axis=0, keepdims=True)
    s2 = jnp.sum(acc * acc, axis=0, keepdims=True)
    first = (n == 0) & (i == 0)

    @pl.when(first)
    def _init():
        s1_ref[:] = s1
        s2_ref[:] = s2

    @pl.when(jnp.logical_not(first))
    def _acc():
        s1_ref[:] += s1
        s2_ref[:] += s2


def _affine_kernel(*refs, Cout, add, relu):
    """Training pass 2: fused elementwise normalize (+ add) (+ ReLU)."""
    if add:
        z_ref, sc_ref, sh_ref, res_ref, out_ref = refs
    else:
        z_ref, sc_ref, sh_ref, out_ref = refs
    y = z_ref[0].astype(jnp.float32) * sc_ref[0] + sh_ref[0]
    if add:
        y += res_ref[0].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[0] = y.astype(out_ref.dtype)


def _wgrad_kernel(xp_ref, dy_ref, out_ref, *, bh, W, C, Cout):
    """dW (9C, Cout) accumulated over the (batch × row-block) grid."""
    n, i = pl.program_id(0), pl.program_id(1)
    patches = _patches(xp_ref, i * bh, bh, W, C)
    dy = dy_ref[0].reshape(bh * W, Cout)
    contrib = lax.dot_general(patches, dy, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    first = (n == 0) & (i == 0)

    @pl.when(first)
    def _init():
        out_ref[:] = contrib

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[:] += contrib


# ----------------------------------------------------------- kernel drivers
def _specs(N, H, W, C, Cout, bh, *, affine=False, add=False):
    """in_specs for the conv-family kernels: padded image fetched once
    per batch index (the index map ignores the row-block coordinate, so
    the pipeline double-buffers image n+1's DMA behind image n's row
    blocks), weights/affine pinned, residual streamed per row block."""
    sp = [pl.BlockSpec((1, H + 2, W + 2, C), lambda n, i: (n, 0, 0, 0)),
          pl.BlockSpec((9 * C, Cout), lambda n, i: (0, 0))]
    if affine:
        sp += [pl.BlockSpec((1, Cout), lambda n, i: (0, 0)),
               pl.BlockSpec((1, Cout), lambda n, i: (0, 0))]
    if add:
        sp += [pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0))]
    return sp


def _out_spec(bh, W, Cout):
    return pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0))


def conv3x3(x, w, out_dtype=None, name="mx_block_conv"):
    """Row-blocked 3×3/s1 SAME conv (no epilogue) — the plain forward
    and, with rotated weights, the dgrad (``name``: the kernel's
    instruction name in a device trace, docs/tracing.md)."""
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    bh = _pick_bh(H, W, C, jnp.dtype(x.dtype).itemsize)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wf = w.reshape(9 * C, Cout)
    kern = functools.partial(_conv_kernel, bh=bh, W=W, C=C, Cout=Cout)
    return pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=_specs(N, H, W, C, Cout, bh),
        out_specs=_out_spec(bh, W, Cout),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), out_dtype or x.dtype),
        interpret=interpret(),
        name=name,
    )(xp, wf)


def conv3x3_dgrad(w, dy):
    """dx = conv3x3(dy, w rotated 180° and IO-transposed)."""
    w_rot = jnp.flip(jnp.flip(w, 0), 1).transpose(0, 1, 3, 2)
    return conv3x3(dy, w_rot.astype(dy.dtype), name="mx_block_dx")


def conv3x3_wgrad(x, dy):
    """dw (3,3,C,Cout) f32, accumulated over the row-blocked grid."""
    N, H, W, C = x.shape
    Cout = dy.shape[-1]
    bh = _pick_bh(H, W, C, jnp.dtype(x.dtype).itemsize, "wgrad")
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    kern = functools.partial(_wgrad_kernel, bh=bh, W=W, C=C, Cout=Cout)
    dw = pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=[
            pl.BlockSpec((1, H + 2, W + 2, C), lambda n, i: (n, 0, 0, 0)),
            pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((9 * C, Cout), lambda n, i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((9 * C, Cout), jnp.float32),
        interpret=interpret(),
        name="mx_block_dw",
    )(xp, dy)
    return dw.reshape(3, 3, C, Cout)


@jax.custom_vjp
def conv3x3_s1(x, w):
    """3×3 stride-1 SAME NHWC convolution (x (N, H, W, C), w HWIO) as
    the row-blocked implicit GEMM, with its dgrad and wgrad kernels: the
    lone conv ``ops/nn.py::convolution`` takes where ``conv_wins``."""
    return conv3x3(x, w)


def _conv3x3_s1_fwd(x, w):
    return conv3x3(x, w), (x, w)


def _conv3x3_s1_bwd(saved, dy):
    x, w = saved
    return (conv3x3_dgrad(w, dy).astype(x.dtype),
            conv3x3_wgrad(x, dy).astype(w.dtype))


conv3x3_s1.defvjp(_conv3x3_s1_fwd, _conv3x3_s1_bwd)


def _conv_affine(x, w, scale, shift, res, relu):
    """Frozen-stats fused block: one kernel, one HBM round trip."""
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    bh = _pick_bh(H, W, C, jnp.dtype(x.dtype).itemsize)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wf = w.reshape(9 * C, Cout)
    add = res is not None
    kern = functools.partial(_conv_affine_kernel, bh=bh, W=W, C=C,
                             Cout=Cout, add=add, relu=relu)
    args = [xp, wf, scale.reshape(1, Cout), shift.reshape(1, Cout)]
    if add:
        args.append(res)
    return pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=_specs(N, H, W, C, Cout, bh, affine=True, add=add),
        out_specs=_out_spec(bh, W, Cout),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
        interpret=interpret(),
        name="mx_block_fwd",
    )(*args)


def _conv_stats(x, w):
    """Training pass 1: (z, Σz, Σz²) in one sweep."""
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    bh = _pick_bh(H, W, C, jnp.dtype(x.dtype).itemsize)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wf = w.reshape(9 * C, Cout)
    kern = functools.partial(_conv_stats_kernel, bh=bh, W=W, C=C, Cout=Cout)
    z, s1, s2 = pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=_specs(N, H, W, C, Cout, bh),
        out_specs=[_out_spec(bh, W, Cout),
                   pl.BlockSpec((1, Cout), lambda n, i: (0, 0)),
                   pl.BlockSpec((1, Cout), lambda n, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
                   jax.ShapeDtypeStruct((1, Cout), jnp.float32),
                   jax.ShapeDtypeStruct((1, Cout), jnp.float32)],
        interpret=interpret(),
        name="mx_block_stats",
    )(xp, wf)
    return z, s1[0], s2[0]


def _affine(z, scale, shift, res, relu):
    """Training pass 2: fused normalize (+ add) (+ ReLU)."""
    N, H, W, Cout = z.shape
    bh = _pick_bh(H, W, Cout, jnp.dtype(z.dtype).itemsize)
    add = res is not None
    kern = functools.partial(_affine_kernel, Cout=Cout, add=add, relu=relu)
    sp = [pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0)),
          pl.BlockSpec((1, Cout), lambda n, i: (0, 0)),
          pl.BlockSpec((1, Cout), lambda n, i: (0, 0))]
    args = [z, scale.reshape(1, Cout), shift.reshape(1, Cout)]
    if add:
        sp.append(pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0)))
        args.append(res)
    return pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=sp,
        out_specs=_out_spec(bh, W, Cout),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        interpret=interpret(),
        name="mx_block_affine",
    )(*args)


# ------------------------------------------------------------- custom vjp
# cfg is a hashable static: (eps, frozen, relu, has_res, bwd_route).
Cfg = collections.namedtuple("Cfg", "eps frozen relu has_res bwd")


def _fold(gamma, beta, mean, inv):
    """BN → per-channel affine in f32: scale = γ·inv, shift = β − μ·scale."""
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale, shift


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused(cfg, x, w, gamma, beta, mean, var, res):
    return _fused_fwd(cfg, x, w, gamma, beta, mean, var, res)[0]


def _fused_fwd(cfg, x, w, gamma, beta, mean, var, res):
    if cfg.frozen:
        inv = lax.rsqrt(var.astype(jnp.float32) + cfg.eps)
        scale, shift = _fold(gamma, beta, mean, inv)
        out = _conv_affine(x, w, scale, shift, res, cfg.relu)
        return (out, mean, var), (x, w, gamma, mean, inv, out)
    z, s1, s2 = _conv_stats(x, w)
    npix = x.shape[0] * x.shape[1] * x.shape[2]
    bmean = s1 / npix
    bvar = jnp.maximum(s2 / npix - bmean * bmean, 0.0)
    inv = lax.rsqrt(bvar + cfg.eps)
    scale, shift = _fold(gamma, beta, bmean, inv)
    out = _affine(z, scale, shift, res, cfg.relu)
    return (out, bmean, bvar), (x, w, gamma, z, bmean, inv, out)


def _conv_bwd(cfg, x, w, dz):
    """dgrad + wgrad, routed per the stage's bwd entry."""
    if cfg.bwd == "pallas":
        dx = conv3x3_dgrad(w, dz).astype(x.dtype)
        dw = conv3x3_wgrad(x, dz).astype(w.dtype)
        return dx, dw
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    _, vjp = jax.vjp(
        lambda a, b: lax.conv_general_dilated(
            a, b, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn), x, w)
    return vjp(dz)


def _sums(dy, xhat):
    """(Σdy, Σdy·x̂) per channel in ONE variadic f32 sweep (the same
    one-pass reduce as ops/nn.py:_bn_train_bwd)."""
    rax = (0, 1, 2)
    dyf = dy.astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return lax.reduce((dyf, dyf * xhat.astype(jnp.float32)), (zero, zero),
                      lambda a, b: (a[0] + b[0], a[1] + b[1]), rax)


def _fused_bwd(cfg, saved, cts):
    dout = cts[0]                    # stat cotangents ignored (EMA aux state)
    if cfg.frozen:
        x, w, gamma, mean, inv, out = saved
        dz_post = jnp.where(out > 0, dout, 0) if cfg.relu else dout
        dres = dz_post if cfg.has_res else None
        # z is recomputed (Pallas conv) rather than saved: frozen-mode
        # grads are the rare path, HBM residency the common cost
        z = conv3x3(x, w) if cfg.bwd == "pallas" else None
        if z is None:
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
            z = lax.conv_general_dilated(x, w, (1, 1), [(1, 1), (1, 1)],
                                         dimension_numbers=dn)
        xhat = (z.astype(jnp.float32) - mean.astype(jnp.float32)) * inv
        sum_dy, sum_dy_xhat = _sums(dz_post, xhat)
        dgamma = sum_dy_xhat.astype(gamma.dtype)
        dbeta = sum_dy.astype(gamma.dtype)
        scale = (gamma.astype(jnp.float32) * inv).astype(dz_post.dtype)
        dz = (dz_post * scale).astype(x.dtype)
        dx, dw = _conv_bwd(cfg, x, w, dz)
        zeros = jnp.zeros_like(mean)
        return (dx.astype(x.dtype), dw.astype(w.dtype), dgamma, dbeta,
                zeros, zeros, dres)
    x, w, gamma, z, bmean, inv, out = saved
    dz_post = jnp.where(out > 0, dout, 0) if cfg.relu else dout
    dres = dz_post if cfg.has_res else None
    shape = (1, 1, 1, z.shape[-1])
    xhat = ((z - bmean.reshape(shape).astype(z.dtype))
            * inv.reshape(shape).astype(z.dtype))
    sum_dy, sum_dy_xhat = _sums(dz_post, xhat)
    dgamma = sum_dy_xhat.astype(gamma.dtype)
    dbeta = sum_dy.astype(gamma.dtype)
    n = x.shape[0] * x.shape[1] * x.shape[2]
    scale = gamma.astype(jnp.float32) * inv               # [C] f32
    dz = (scale.reshape(shape).astype(dz_post.dtype)
          * (dz_post - (sum_dy / n).reshape(shape).astype(dz_post.dtype)
             - xhat * (sum_dy_xhat / n).reshape(shape).astype(dz_post.dtype)))
    dx, dw = _conv_bwd(cfg, x, w, dz.astype(x.dtype))
    zeros = jnp.zeros_like(bmean)
    return (dx.astype(x.dtype), dw.astype(w.dtype), dgamma, dbeta,
            zeros.astype(jnp.float32), zeros.astype(jnp.float32), dres)


_fused.defvjp(_fused_fwd, _fused_bwd)


def residual_block_fused(x, w, gamma, beta, mean, var, residual=None, *,
                         eps=1e-5, frozen=False, relu=True, bwd="xla"):
    """Fused 3×3/s1 conv + BN (+ residual add) (+ ReLU), custom-vjp.

    Returns ``(out, batch_mean, batch_var)`` in training mode and
    ``(out, mean, var)`` (the running stats, unchanged) when frozen.
    ``bwd`` routes dgrad/wgrad (the stage's ``_DEFAULT_TABLE`` entry).
    """
    cfg = Cfg(float(eps), bool(frozen), bool(relu),
              residual is not None, str(bwd))
    return _fused(cfg, x, w, gamma, beta, mean, var, residual)
