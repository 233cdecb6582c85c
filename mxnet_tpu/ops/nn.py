"""Neural-net ops over jax.lax — the FCompute layer of the TPU build.

Equivalent of the reference's src/operator/nn/ (convolution.cc, pooling.cc,
batch_norm.cc, softmax.cc, fully_connected.cc:255, layer_norm.cc,
dropout.cc, activation.cc) re-designed for TPU:

- **Layout is NHWC** (channels-last): XLA:TPU tiles the last dim onto the
  128-lane registers, so channels-last keeps convs/matmuls on the MXU without
  relayout. The reference defaults to NCHW for cuDNN; layout is a parameter
  here with NHWC the default and fast path.
- All functions are pure (raw jax arrays in/out) so they compose with jit /
  grad / shard_map; NDArray-level wrappers route through the autograd tape.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# ----------------------------------------------------------------- helpers


def _pallas_block():
    from . import pallas_block
    return pallas_block


def _pair(x, n=2):
    if isinstance(x, int):
        return (x,) * n
    return tuple(x)


# ------------------------------------------------------------- activations
relu = jax.nn.relu
sigmoid = jax.nn.sigmoid
tanh = jnp.tanh
softrelu = jax.nn.softplus
softplus = jax.nn.softplus
softsign = jax.nn.soft_sign
silu = jax.nn.silu
swish = jax.nn.silu
mish = lambda x: x * jnp.tanh(jax.nn.softplus(x))  # noqa: E731


def gelu(x, approximate: bool = True):
    return jax.nn.gelu(x, approximate=approximate)


def leaky_relu(x, slope=0.01):
    return jnp.where(x >= 0, x, slope * x)


def elu(x, alpha=1.0):
    return jnp.where(x > 0, x, alpha * jnp.expm1(x))


def selu(x):
    return jax.nn.selu(x)


def prelu(x, alpha):
    return jnp.where(x >= 0, x, alpha * x)


def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


_ACTIVATIONS = {
    "relu": relu, "sigmoid": sigmoid, "tanh": tanh, "softrelu": softrelu,
    "softsign": softsign, "gelu": gelu, "silu": silu, "swish": swish,
    "mish": mish, "elu": elu, "selu": selu, "leaky": leaky_relu,
    "log_sigmoid": jax.nn.log_sigmoid,
}


def activation(x, act_type: str):
    """≙ npx.activation (src/operator/nn/activation.cc)."""
    return _ACTIVATIONS[act_type](x)


# ---------------------------------------------------------------- softmax
def softmax(x, axis=-1, temperature: Optional[float] = None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if axis in (-1, x.ndim - 1):
        from . import pallas_kernels as _pk
        if _pk._use_pallas(x.shape[-1], "softmax"):
            return _pk.softmax_fused(x)   # single-HBM-pass Pallas kernel
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def masked_softmax(x, mask, axis=-1, temperature=1.0):
    """≙ src/operator/nn/masked_softmax; mask True = keep."""
    if temperature != 1.0:
        x = x / temperature
    neg = jnp.finfo(x.dtype).min
    x = jnp.where(mask, x, neg)
    out = jax.nn.softmax(x, axis=axis)
    return jnp.where(mask, out, 0.0)


def masked_log_softmax(x, mask, axis=-1):
    neg = jnp.finfo(x.dtype).min
    x = jnp.where(mask, x, neg)
    return jax.nn.log_softmax(x, axis=axis)


# --------------------------------------------------------- fully connected
def fully_connected(x, weight, bias=None, flatten=True):
    """≙ FullyConnected (src/operator/nn/fully_connected.cc:255).

    weight is (out_units, in_units) as in the reference; lowers to a single
    MXU matmul with fp32 accumulation.
    """
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    out = jnp.matmul(x, weight.T, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def dense(x, weight, bias=None):
    return fully_connected(x, weight, bias, flatten=False)


def _conv_pet(x):
    """Accumulation dtype for conv: request f32 output for f32 inputs; for
    low-precision (bf16/fp16) inputs return None so the output keeps the
    input dtype — the MXU still accumulates in f32 internally, and a
    low-precision output keeps the conv transpose (weight-grad) rule on
    uniform dtypes (lax rejects bf16 operands with an f32 cotangent)."""
    return jnp.float32 if x.dtype in (jnp.float32, jnp.float64) else None


# ------------------------------------------------------------- convolution
def _s2d_plan(k, p, size):
    """Per-dim plan for the space-to-depth stem rewrite of a stride-2 conv.

    The odd k×k kernel is zero-padded to even k+1 (front row if that keeps
    the padded-input origin block-aligned, else back row), then both input
    and kernel are space-to-depth'd by 2 and the conv runs stride-1 VALID.
    Returns (pad_lo, pad_hi, kernel_pad, n_out); exact — every output
    window sums the same products as the original conv.
    """
    out = (size + 2 * p - k) // 2 + 1
    if (p + 1) % 2 == 0:
        lo, kpad = p + 1, (1, 0)     # kernel element d ↦ original d-1
    else:
        lo, kpad = p, (0, 1)         # kernel element d ↦ original d
    hi = 2 * (out - 1) + (k + 1) - size - lo   # exact cover; lo+hi+size even
    return lo, hi, kpad, out


def _s2d_conv2d(x, weight, pad, pet):
    """Space-to-depth rewrite for MXU-hostile stems (e.g. ResNet 7×7/s2 on
    3 channels): 4× fewer spatial positions, 4× the input features —
    ≥8× better MXU utilisation on the stem and its wgrad/dgrad."""
    N, H, W, C = x.shape
    kh, kw, _, O = weight.shape
    lo_h, hi_h, kp_h, _ = _s2d_plan(kh, pad[0], H)
    lo_w, hi_w, kp_w, _ = _s2d_plan(kw, pad[1], W)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    wp = jnp.pad(weight, (kp_h, kp_w, (0, 0), (0, 0)))
    Hp, Wp = H + lo_h + hi_h, W + lo_w + hi_w
    x2 = xp.reshape(N, Hp // 2, 2, Wp // 2, 2, C)
    x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(N, Hp // 2, Wp // 2, 4 * C)
    w2 = wp.reshape((kh + 1) // 2, 2, (kw + 1) // 2, 2, C, O)
    w2 = w2.transpose(0, 2, 1, 3, 4, 5).reshape(
        (kh + 1) // 2, (kw + 1) // 2, 4 * C, O)
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x2, w2, window_strides=(1, 1), padding="VALID",
        dimension_numbers=dn, preferred_element_type=pet)


def convolution(x, weight, bias=None, stride=1, pad=0, dilate=1, groups=1,
                layout: str = "NHWC"):
    """2-D convolution ≙ Convolution (src/operator/nn/convolution.cc).

    weight layout HWIO (kh, kw, in/groups, out) — the XLA-native filter
    layout. Accumulates in fp32 on the MXU (preferred_element_type).
    Small-channel stride-2 stems (ResNet's 7×7/s2 on RGB) are rewritten
    space-to-depth so the MXU sees 4·C input features instead of 3.
    """
    stride, pad, dilate = _pair(stride), _pair(pad), _pair(dilate)
    if layout == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    if (stride == (2, 2) and dilate == (1, 1) and groups == 1
            and x.shape[-1] <= 4 and weight.shape[0] % 2 == 1
            and weight.shape[1] % 2 == 1 and max(weight.shape[:2]) >= 5
            and min(x.shape[1], x.shape[2]) >= max(weight.shape[:2])):
        out = _s2d_conv2d(x, weight, pad, _conv_pet(x))
    elif _pallas_block().conv_wins(x.shape, weight.shape, stride, pad,
                                   dilate, groups, x.dtype):
        # hand-tiled implicit GEMM on the stages whose forward routes
        # (ops/pallas_block.py)
        out = _pallas_block().conv3x3_s1(x, weight)
    else:
        dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        out = lax.conv_general_dilated(
            x, weight, window_strides=stride,
            padding=[(pad[0], pad[0]), (pad[1], pad[1])],
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=groups,
            preferred_element_type=_conv_pet(x))
    out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    if layout == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


def conv_transpose(x, weight, bias=None, stride=1, pad=0, dilate=1,
                   output_padding=0, groups=1, layout: str = "NHWC"):
    """2-D transposed conv ≙ Deconvolution (src/operator/nn/deconvolution.cc)."""
    stride, pad, dilate = _pair(stride), _pair(pad), _pair(dilate)
    opad = _pair(output_padding)
    if layout == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    kh, kw = weight.shape[0], weight.shape[1]
    pad_h = (dilate[0] * (kh - 1) - pad[0], dilate[0] * (kh - 1) - pad[0] + opad[0])
    pad_w = (dilate[1] * (kw - 1) - pad[1], dilate[1] * (kw - 1) - pad[1] + opad[1])
    # weight storage is (kh, kw, in, out) for the DEconv mapping, which is
    # exactly the HWIO filter of the equivalent lhs-dilated direct conv —
    # only a spatial flip is needed (an in/out swap here would transpose
    # the channel mixing and produce wrong numerics).
    w = jnp.flip(weight, (0, 1))
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    out = lax.conv_general_dilated(
        x, w,
        window_strides=(1, 1), padding=[pad_h, pad_w],
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups, preferred_element_type=_conv_pet(x))
    out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    if layout == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


# ---------------------------------------------------------------- pooling
def pooling(x, kernel=2, stride=None, pad=0, pool_type="max",
            global_pool=False, count_include_pad=True, layout="NHWC"):
    """≙ Pooling (src/operator/nn/pooling.cc) via lax.reduce_window."""
    if layout == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    if global_pool:
        kernel = (x.shape[1], x.shape[2])
        stride = (1, 1)
        pad = (0, 0)
    kernel = _pair(kernel)
    stride = _pair(stride if stride is not None else kernel)
    pad = _pair(pad)
    window = (1,) + kernel + (1,)
    strides = (1,) + stride + (1,)
    pads = ((0, 0), (pad[0], pad[0]), (pad[1], pad[1]), (0, 0))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides, pads)
    elif pool_type == "avg":
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        if count_include_pad:
            out = s / (kernel[0] * kernel[1])
        else:
            ones = jnp.ones(x.shape[:3] + (1,), x.dtype)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
            out = s / cnt
    elif pool_type == "sum":
        out = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    elif pool_type == "lp":
        s = lax.reduce_window(x * x, 0.0, lax.add, window, strides, pads)
        out = jnp.sqrt(s)
    else:
        raise ValueError(f"unknown pool_type {pool_type}")
    if layout == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


# ------------------------------------------------------------ normalization
def _bn_stats(x, ch):
    """Per-channel (mean, E[x²]) with f32 accumulation, reading x ONCE.

    A single variadic lax.reduce keeps both sums in one sweep; the f32
    converts happen inside the fused reduce so no full-size f32 copy of the
    activation is ever materialised in HBM (that copy — an extra f32 write
    + read per conv output — was 2× the conv HBM traffic on the profile).
    """
    rax = tuple(i for i in range(x.ndim) if i != ch)
    n = 1
    for i in rax:
        n *= x.shape[i]
    xf = x.astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    s1, s2 = lax.reduce((xf, xf * xf), (zero, zero),
                        lambda a, b: (a[0] + b[0], a[1] + b[1]), rax)
    return s1 / n, s2 / n, n


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, gamma, beta, eps, ch):
    """Training-mode batch norm with the canonical fused backward.

    custom_vjp so the saved residuals are (x, mean, inv, gamma) — x stays
    in its compute dtype (bf16 under AMP). Default AD instead saves the
    full-size f32 shifted activation from the variance term, which forces
    every conv output to materialise in f32 (≈3× the HBM bytes/step).
    Gradients for the returned batch stats are treated as stop_gradient
    (they feed the running-stat EMA only — the reference likewise never
    differentiates running stats, batch_norm.cc backward).
    """
    return _bn_train_fwd(x, gamma, beta, eps, ch)[0]


def _bn_train_fwd(x, gamma, beta, eps, ch):
    mean, m2, _ = _bn_stats(x, ch)
    var = jnp.maximum(m2 - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    out = ((x - mean.reshape(shape).astype(x.dtype))
           * inv.reshape(shape).astype(x.dtype)
           * gamma.reshape(shape) + beta.reshape(shape))
    return (out, mean, var), (x, gamma, mean, inv)


def _bn_train_bwd(eps, ch, res, cts):
    x, gamma, mean, inv = res
    dy = cts[0]                      # stat cotangents ignored (EMA aux state)
    rax = tuple(i for i in range(x.ndim) if i != ch)
    n = 1
    for i in rax:
        n *= x.shape[i]
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    xhat = ((x - mean.reshape(shape).astype(x.dtype))
            * inv.reshape(shape).astype(x.dtype))
    dyf = dy.astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    sum_dy, sum_dy_xhat = lax.reduce(
        (dyf, dyf * xhat.astype(jnp.float32)), (zero, zero),
        lambda a, b: (a[0] + b[0], a[1] + b[1]), rax)
    dgamma = sum_dy_xhat.astype(gamma.dtype)
    dbeta = sum_dy.astype(dy.dtype)
    scale = gamma.astype(jnp.float32) * inv            # [C] f32
    dx = (scale.reshape(shape).astype(dy.dtype)
          * (dy - (sum_dy / n).reshape(shape).astype(dy.dtype)
             - xhat * (sum_dy_xhat / n).reshape(shape).astype(dy.dtype)))
    return dx.astype(x.dtype), dgamma, dbeta


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def batch_norm(x, gamma, beta, running_mean, running_var, momentum=0.9,
               eps=1e-5, use_global_stats=False, training=True, axis=-1):
    """≙ BatchNorm (src/operator/nn/batch_norm.cc).

    Returns (out, new_mean, new_var). In training mode computes batch stats
    (f32 accumulation over the compute-dtype activation) through a
    custom-vjp kernel whose backward is the fused cuDNN-style formula —
    residuals stay in the compute dtype, stats/EMA math stays f32.
    """
    ch = axis % x.ndim
    if training and not use_global_stats:
        if x.dtype in (jnp.float32, jnp.float64):
            # full precision: default AD fuses the backward best (the
            # custom kernel's explicit reduce passes measured ~8% slower
            # on the f32 ResNet-50 step); residual dtype is a non-issue.
            # One-pass shifted stats (shift s kills the E[x²]−E[x]²
            # cancellation when |mean| ≫ std); jnp reductions only — the
            # variadic lax.reduce has no efficient AD transpose.
            reduce_axes = tuple(i for i in range(x.ndim) if i != ch)
            s = lax.stop_gradient(
                jnp.moveaxis(x, ch, -1).reshape(-1, x.shape[ch])[0])
            shape = [1] * x.ndim
            shape[ch] = x.shape[ch]
            xs = x - s.reshape(shape)
            m1 = jnp.mean(xs, axis=reduce_axes)
            m2 = jnp.mean(xs * xs, axis=reduce_axes)
            mean = m1 + s
            var = jnp.maximum(m2 - m1 * m1, 0.0)
            out = ((x - mean.reshape(shape))
                   * lax.rsqrt(var.reshape(shape) + eps)
                   * gamma.reshape(shape) + beta.reshape(shape))
        else:
            # low precision (AMP): custom vjp keeps every saved residual
            # in the compute dtype — default AD would re-derive the stats
            # path and pin a full-size f32 copy of each conv output in HBM
            out, mean, var = _bn_train(x, gamma, beta, eps, ch)
        new_mean = momentum * running_mean + (1 - momentum) * mean
        new_var = momentum * running_var + (1 - momentum) * var
        return out, new_mean, new_var
    mean, var = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    mean_b = mean.reshape(shape).astype(x.dtype)
    inv = lax.rsqrt(var.reshape(shape) + eps).astype(x.dtype)
    out = (x - mean_b) * inv * gamma.reshape(shape) + beta.reshape(shape)
    return out, running_mean, running_var


def residual_block(x, weight, gamma, beta, running_mean, running_var,
                   residual=None, momentum=0.9, eps=1e-5,
                   use_global_stats=False, training=True, relu=True):
    """Fused residual-block tail: 3×3/s1 SAME conv + BatchNorm
    (+ residual add) (+ ReLU), NHWC/HWIO — the block the XLA emitter
    won't fuse (see ops/pallas_block.py).

    Returns ``(out, new_mean, new_var)`` with the same running-stat EMA
    contract as ``batch_norm``.  Routing is per-stage
    (``pallas_block.decide``): a routed HxWxC stage on one TPU takes the
    Pallas pipeline, everything else the reference
    composition (conv → batch_norm → add → relu), which is numerically
    identical to the unfused layer path.
    """
    pb = _pallas_block()
    frozen = (not training) or use_global_stats
    route = pb.decide(x.shape, weight.shape, x.dtype,
                      has_residual=residual is not None)
    if route.fwd == "pallas":
        out, bmean, bvar = pb.residual_block_fused(
            x, weight, gamma, beta, running_mean, running_var, residual,
            eps=eps, frozen=frozen, relu=relu, bwd=route.bwd)
        if frozen:
            return out, running_mean, running_var
        new_mean = momentum * running_mean + \
            (1 - momentum) * bmean.astype(running_mean.dtype)
        new_var = momentum * running_var + \
            (1 - momentum) * bvar.astype(running_var.dtype)
        return out, new_mean, new_var
    z = convolution(x, weight, None, stride=1, pad=1)
    out, new_mean, new_var = batch_norm(
        z, gamma, beta, running_mean, running_var, momentum=momentum,
        eps=eps, use_global_stats=use_global_stats, training=training)
    if residual is not None:
        out = out + residual
    if relu:
        out = jax.nn.relu(out)
    return out, new_mean, new_var


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """≙ LayerNorm (src/operator/nn/layer_norm.cc); fp32 stats."""
    if axis in (-1, x.ndim - 1) and x.dtype == jnp.float32:
        from . import pallas_kernels as _pk
        if _pk._use_pallas(x.shape[-1], "layernorm"):
            return _pk.layernorm_fused(x, gamma, beta, eps)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    out = out.astype(x.dtype)
    return out * gamma + beta


def rms_norm(x, gamma, axis=-1, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=axis, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)).astype(x.dtype) * gamma


def instance_norm(x, gamma, beta, eps=1e-5, axis=-1):
    """≙ InstanceNorm: normalize over spatial dims per sample+channel."""
    ch = axis % x.ndim
    reduce_axes = tuple(i for i in range(1, x.ndim) if i != ch)
    mean = jnp.mean(x, axis=reduce_axes, keepdims=True)
    var = jnp.var(x, axis=reduce_axes, keepdims=True)
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    return (x - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


def group_norm(x, gamma, beta, num_groups, eps=1e-5):
    """≙ GroupNorm (channels-last): groups over the last axis."""
    orig = x.shape
    c = orig[-1]
    xg = x.reshape(orig[:-1] + (num_groups, c // num_groups))
    axes = tuple(range(1, x.ndim - 1)) + (x.ndim,)
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    return xg.reshape(orig) * gamma + beta


def l2_normalize(x, axis=-1, eps=1e-10):
    return x * lax.rsqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)


# ---------------------------------------------------------------- dropout
def dropout(x, rate, key, training=True):
    """Functional dropout ≙ src/operator/nn/dropout.cc; key-explicit."""
    if not training or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


# --------------------------------------------------------------- embedding
def embedding(indices, weight):
    """≙ Embedding op (src/operator/tensor/indexing_op.cc) — gather rows."""
    return jnp.take(weight, indices.astype(jnp.int32), axis=0)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype=jnp.float32):
    oh = jax.nn.one_hot(indices, depth, dtype=dtype)
    if on_value != 1.0 or off_value != 0.0:
        oh = oh * (on_value - off_value) + off_value
    return oh


def pick(x, index, axis=-1, keepdims=False):
    """≙ pick op: select one element along axis per position of index."""
    idx = jnp.expand_dims(index.astype(jnp.int32), axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


def topk(x, k=1, axis=-1, ret_typ="indices", is_ascend=False):
    """≙ topk (src/operator/tensor/ordering_op.cc)."""
    xm = jnp.moveaxis(x, axis, -1)
    if is_ascend:
        vals, idx = lax.top_k(-xm, k)
        vals = -vals
    else:
        vals, idx = lax.top_k(xm, k)
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)
    if ret_typ == "indices":
        return idx
    if ret_typ == "value":
        return vals
    return vals, idx


# ------------------------------------------------------------- sequence ops
def sequence_mask(x, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    """≙ SequenceMask (src/operator/sequence_mask.cc); time axis = `axis`."""
    if not use_sequence_length or sequence_length is None:
        return x
    seq_len = x.shape[axis]
    pos = jnp.arange(seq_len)
    shape = [1] * x.ndim
    shape[axis] = seq_len
    pos = pos.reshape(shape)
    lens_shape = [1] * x.ndim
    batch_axis = 1 if axis == 0 else 0
    lens_shape[batch_axis] = x.shape[batch_axis]
    lens = sequence_length.reshape(lens_shape)
    mask = pos < lens
    return jnp.where(mask, x, value)


def sequence_last(x, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return lax.index_in_dim(x, x.shape[axis] - 1, axis, keepdims=False)
    idx = (sequence_length.astype(jnp.int32) - 1)
    xm = jnp.moveaxis(x, axis, 0)  # (T, B, ...)
    return jnp.take_along_axis(
        xm, idx.reshape((1, -1) + (1,) * (xm.ndim - 2)), axis=0)[0]


def sequence_reverse(x, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(x, axis=axis)
    xm = jnp.moveaxis(x, axis, 0)
    T = xm.shape[0]
    pos = jnp.arange(T)[:, None]
    lens = sequence_length.astype(jnp.int32)[None, :]
    src = jnp.where(pos < lens, lens - 1 - pos, pos)
    out = jnp.take_along_axis(xm, src.reshape(src.shape + (1,) * (xm.ndim - 2)), axis=0)
    return jnp.moveaxis(out, 0, axis)


# ----------------------------------------------------------------- losses
def softmax_cross_entropy(logits, labels, sparse=True, axis=-1):
    """Fused log_softmax + NLL ≙ SoftmaxCrossEntropy / SoftmaxOutput."""
    logp = jax.nn.log_softmax(logits, axis=axis)
    if sparse:
        return -pick(logp, labels, axis=axis)
    return -jnp.sum(labels * logp, axis=axis)


def sigmoid_binary_cross_entropy(logits, labels, from_sigmoid=False):
    if from_sigmoid:
        eps = 1e-12
        return -(labels * jnp.log(logits + eps) + (1 - labels) * jnp.log(1 - logits + eps))
    # numerically-stable: max(x,0) - x*z + log(1+exp(-|x|))
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))


# ----------------------------------------------------------------- casting
def amp_cast(x, dtype):
    """≙ amp_cast (src/operator/tensor/amp_cast.cc)."""
    return x.astype(dtype)


def amp_multicast(*xs, cast_narrowest=False):
    dtypes = [x.dtype for x in xs]
    target = jnp.result_type(*dtypes) if not cast_narrowest else min(
        dtypes, key=lambda d: jnp.finfo(d).bits if jnp.issubdtype(d, jnp.floating) else 64)
    return tuple(x.astype(target) for x in xs)


def all_finite(*arrays):
    """≙ all_finite op (src/operator/all_finite.cc) — AMP skip-update check."""
    ok = jnp.array(True)
    for a in arrays:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(a)))
    return ok


def clip_global_norm(arrays, max_norm):
    """Global-norm gradient clipping (gluon.utils.clip_global_norm parity)."""
    total = jnp.sqrt(sum(jnp.sum(a.astype(jnp.float32) ** 2) for a in arrays))
    scale = jnp.minimum(1.0, max_norm / (total + 1e-12))
    return [a * scale.astype(a.dtype) for a in arrays], total


def sync_batch_norm(x, gamma, beta, running_mean, running_var,
                    momentum=0.9, eps=1e-5, training=True, axis=-1,
                    axis_name=None):
    """≙ contrib SyncBatchNorm (src/operator/contrib/sync_batch_norm.cc).

    TPU-native: batch statistics are pmean'd over the named mesh axis
    (data-parallel shards inside shard_map/pmap) instead of the
    reference's cross-GPU key-value reduce. Outside a named-axis context
    it degrades to plain batch_norm.
    """
    if not training or axis_name is None:
        return batch_norm(x, gamma, beta, running_mean, running_var,
                          momentum, eps, False, training, axis)
    reduce_axes = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
    mean = jnp.mean(x.astype(jnp.float32), axis=reduce_axes)
    sq = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=reduce_axes)
    mean = lax.pmean(mean, axis_name)
    sq = lax.pmean(sq, axis_name)
    var = sq - mean * mean
    new_mean = momentum * running_mean + (1 - momentum) * mean
    new_var = momentum * running_var + (1 - momentum) * var
    shape = [1] * x.ndim
    shape[axis % x.ndim] = x.shape[axis % x.ndim]
    inv = lax.rsqrt(var.reshape(shape) + eps).astype(x.dtype)
    out = (x - mean.reshape(shape).astype(x.dtype)) * inv \
        * gamma.reshape(shape) + beta.reshape(shape)
    return out, new_mean, new_var


def convolution_nd(x, weight, bias=None, stride=1, pad=0, dilate=1,
                   groups=1, ndims=3):
    """N-D convolution (channels-last N...C, filter ...IO) — the 3-D case
    of src/operator/nn/convolution.cc."""
    stride = _pair(stride, ndims)
    pad = _pair(pad, ndims)
    dilate = _pair(dilate, ndims)
    spatial = "".join("DHW"[-ndims + i] for i in range(ndims))
    lhs_spec = "N" + spatial + "C"
    rhs_spec = spatial + "IO"
    dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=_conv_pet(x)).astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def pooling_nd(x, kernel, stride=None, pad=0, pool_type="max",
               global_pool=False, count_include_pad=True, ndims=3):
    """N-D pooling (channels-last) via reduce_window — 1-D/3-D twins of
    pooling()."""
    if global_pool:
        kernel = x.shape[1:1 + ndims]
        stride = (1,) * ndims
        pad = (0,) * ndims
    kernel = _pair(kernel, ndims)
    stride = _pair(stride if stride is not None else kernel, ndims)
    pad = _pair(pad, ndims)
    window = (1,) + kernel + (1,)
    strides = (1,) + stride + (1,)
    pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    if pool_type == "sum":
        return s
    if count_include_pad:
        denom = 1
        for k in kernel:
            denom *= k
        return s / denom
    ones = jnp.ones(x.shape[:-1] + (1,), x.dtype)
    cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
    return s / cnt


def reflection_pad2d(x, pad):
    """≙ ReflectionPad2D (pad_width on H and W, NHWC)."""
    p = _pair(pad) if not isinstance(pad, int) else (pad, pad)
    return jnp.pad(x, ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0)),
                   mode="reflect")


# ------------------------------------------------------------ int8 kernels
def _pallas_int8():
    from . import pallas_int8
    return pallas_int8


def _quantize_sym(x, in_t):
    """Symmetric per-tensor int8 quantization of an activation against a
    calibrated threshold: scale 127/T, round-to-nearest, clip ±127."""
    s_in = 127.0 / max(float(in_t), 1e-12)
    qx = jnp.clip(jnp.round(x.astype(jnp.float32) * s_in),
                  -127, 127).astype(jnp.int8)
    return qx, s_in


def quantized_dense(x, qw, w_scale, bias=None, *, in_t, flatten=True,
                    act=None):
    """int8 fully-connected (≙ the reference's quantized_fully_connected):
    activation quantized on the fly against the calibrated threshold
    ``in_t``, pre-quantized int8 weights ``qw`` shaped (in, units), MXU
    int8×int8→int32, per-output-channel dequant ``1/(s_in·w_scale[c])``
    + bias + optional activation fused into the epilogue."""
    qx, s_in = _quantize_sym(x, in_t)
    if flatten and qx.ndim > 2:
        qx = qx.reshape(qx.shape[0], -1)
    acc = lax.dot_general(qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) / (s_in * w_scale.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act is not None:
        out = _ACTIVATIONS[act](out)
    return out


def quantized_conv(x, qw, w_scale, bias=None, residual=None, *, in_t,
                   stride=(1, 1), pad=(1, 1), dilate=(1, 1), groups=1,
                   relu=False, act=None):
    """int8 conv (NHWC activation, pre-quantized HWIO int8 weights) with
    the dequant + bias (+ residual) (+ ReLU) epilogue.  3×3/s1/SAME
    single-group convs route through the Pallas int8 implicit-GEMM
    (ops/pallas_int8.py) where ``decide_int8`` says so — the epilogue
    rides the int32 accumulator in VMEM, one HBM pass.  Everything else
    (and stage/eligibility fallbacks) composes the XLA int8 conv with
    ``preferred_element_type=int32`` and the identical epilogue math.

    ``bias`` is the per-channel shift — after BN folding this IS the
    folded-BN affine, so the quantized fused residual-block route needs
    no separate scale/shift pass."""
    pi = _pallas_int8()
    qx, s_in = _quantize_sym(x, in_t)
    cout = qw.shape[-1]
    dq = 1.0 / (s_in * w_scale.astype(jnp.float32))        # per-Cout
    shift = bias.astype(jnp.float32) if bias is not None \
        else jnp.zeros((cout,), jnp.float32)
    fuse_relu = bool(relu) or act == "relu"
    stride, pad, dilate = _pair(stride), _pair(pad), _pair(dilate)
    conv3x3 = (stride == (1, 1) and pad == (1, 1) and dilate == (1, 1)
               and groups == 1 and tuple(qw.shape[:2]) == (3, 3))
    route = pi.decide_int8(x.shape, qw.shape, residual is not None) \
        if conv3x3 else "xla"
    if route == "pallas":
        out = pi.qconv3x3_affine(qx, qw, dq, shift, res=residual,
                                 relu=fuse_relu)
    elif conv3x3:
        out = pi.qconv3x3_xla(qx, qw, dq, shift, res=residual,
                              relu=fuse_relu)
    else:
        dn = lax.conv_dimension_numbers(qx.shape, qw.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        acc = lax.conv_general_dilated(
            qx, qw, window_strides=stride,
            padding=[(pad[0], pad[0]), (pad[1], pad[1])],
            rhs_dilation=dilate, dimension_numbers=dn,
            feature_group_count=groups,
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * dq + shift
        if residual is not None:
            out = out + residual.astype(jnp.float32)
        if fuse_relu:
            out = jnp.maximum(out, 0.0)
    if act is not None and act != "relu":
        out = _ACTIVATIONS[act](out)
    return out


# ----------------------------------------------------- dispatch fast path
# Eager calls on concrete arrays route through the executable cache
# (dispatch_cache.cached_call): array args are dynamic, everything else
# keys the jitted kernel.  Tracer inputs (vjp backward, hybridize traces,
# user jit) pass through untouched, so autograd and deferred compute see
# the original functions.  No op needs an extra key: a routing decision
# reads shapes and `one_tpu()`, which cannot change inside a process.
# Applied AFTER every definition so internal callers (`dense` →
# `fully_connected`) trace the plain bodies, and numpy_extension's
# import-time `_wrap1(...)` captures the cached versions.
from ..dispatch_cache import cached_call as _cached_call

gelu = _cached_call(gelu)
leaky_relu = _cached_call(leaky_relu)
elu = _cached_call(elu)
selu = _cached_call(selu)
prelu = _cached_call(prelu)
hard_sigmoid = _cached_call(hard_sigmoid)
activation = _cached_call(activation)
softmax = _cached_call(softmax)
log_softmax = _cached_call(log_softmax)
masked_softmax = _cached_call(masked_softmax)
masked_log_softmax = _cached_call(masked_log_softmax)
fully_connected = _cached_call(fully_connected)
dense = _cached_call(dense)
convolution = _cached_call(convolution)
quantized_conv = _cached_call(quantized_conv)
quantized_dense = _cached_call(quantized_dense)
conv_transpose = _cached_call(conv_transpose)
pooling = _cached_call(pooling)
batch_norm = _cached_call(batch_norm)
residual_block = _cached_call(residual_block)
layer_norm = _cached_call(layer_norm)
rms_norm = _cached_call(rms_norm)
instance_norm = _cached_call(instance_norm)
group_norm = _cached_call(group_norm)
l2_normalize = _cached_call(l2_normalize)
dropout = _cached_call(dropout)          # PRNG key is a dynamic array arg
embedding = _cached_call(embedding)
one_hot = _cached_call(one_hot)
pick = _cached_call(pick)
topk = _cached_call(topk)
sequence_mask = _cached_call(sequence_mask)
sequence_last = _cached_call(sequence_last)
sequence_reverse = _cached_call(sequence_reverse)
softmax_cross_entropy = _cached_call(softmax_cross_entropy)
sigmoid_binary_cross_entropy = _cached_call(sigmoid_binary_cross_entropy)
amp_cast = _cached_call(amp_cast)
convolution_nd = _cached_call(convolution_nd)
pooling_nd = _cached_call(pooling_nd)
reflection_pad2d = _cached_call(reflection_pad2d)


# ------------------------------------------------------------- attention
def multihead_self_attention(qkv, heads):
    """softmax(q·kᵀ/√hd)·v of every head of a fused QKV projection
    ``(B, T, 3·heads·hd)`` (thirds q | k | v, heads side by side in each)
    → ``(B, T, heads·hd)``.  One pair of Pallas kernels where they apply,
    the matmul/softmax/matmul composition elsewhere."""
    from . import pallas_kernels as _pk
    return _pk.self_attention_fused(qkv, heads)


# ------------------------------------------- state-space, causal GQA, LM loss
# The mixers of a hybrid Mamba-2 / expert / attention language model
# (models/nemotron_h.py) as compositions XLA compiles: none holds a (T, T)
# matrix or a (T, V) float32 matrix twice in HBM.  Each counts the route it
# took at trace time (``dispatch.ssm.*``, ``dispatch.attention.causal.*``,
# ``dispatch.loss.*``) so that a later kernel shows as another name.
def relu2(x):
    """``relu(x)**2`` (``mlp_hidden_act = "relu2"``)."""
    return jnp.square(jnp.maximum(x, 0))


_ACTIVATIONS["relu2"] = relu2


def _count_route(name):
    from .. import telemetry
    telemetry.counter_add("dispatch." + name)


def gated_group_rms_norm(y, z, gamma, groups, eps=1e-5):
    """``groupRMSNorm(y * silu(z)) * gamma``: the RMS is taken over each of
    ``groups`` equal runs of the last axis (Mamba-2's gated norm)."""
    g = y * jax.nn.silu(z)
    grouped = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    return rms_norm(grouped, 1.0, eps=eps).reshape(g.shape) * gamma


def causal_conv1d(x, weight, bias=None):
    """Causal depthwise convolution over time: ``y[b, t, c] = bias[c] +
    sum_j weight[c, j] * x[b, t - (K-1) + j, c]``, zeros before the
    sequence.  ``x`` (B, T, C), ``weight`` (C, K).  K shifted multiplies,
    which XLA fuses into one pass."""
    k = weight.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + t] * weight[:, j] for j in range(k))
    return y if bias is None else y + bias


def ssd_chunked(x, dt, a, b, c, d=None, chunk=128):
    """Mamba-2's selective state-space layer by chunks (SSD, Dao & Gu 2024):
    per head ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T``, ``y_t = S_t c_t
    + d x_t``, computed as products inside chunks of ``chunk`` steps plus a
    recurrence over the chunks' end states.

    ``x`` (B, T, H, P), ``dt`` (B, T, H) positive, ``a`` (H,) negative, ``b``
    and ``c`` (B, T, G, N) with H / G heads sharing a group, ``d`` (H,).
    Heads are batch dimensions and time is minor throughout, so the largest
    temporaries are (B, T/chunk, H, chunk, chunk) and (B, T/chunk, H, P, N):
    linear in T.  Decays are exponentials of differences of an inclusive
    cumulative sum taken where the difference is not positive, so none
    exceeds 1.

    Where `pallas_kernels.ssd_use_pallas` says so (one TPU, ``chunk`` 128
    dividing T, N in 128s, whole groups whose heads fill 128-lane blocks)
    the same quantity is one forward and one backward Pallas kernel that
    read x, b and c in place, carry the state from chunk to chunk in VMEM
    and keep every (chunk, chunk) tile there."""
    from . import pallas_kernels as _pk
    B, T, H, P = x.shape
    G, N = b.shape[2:]
    if _pk.ssd_use_pallas(T, H, G, P, N, chunk):
        return _pk.ssd_fused(
            x.reshape(B, T, H * P), dt, a, b.reshape(B, T, G * N),
            c.reshape(B, T, G * N), d, H, G).reshape(x.shape)
    _count_route("ssm.xla_chunked")
    q = chunk if T % chunk == 0 else T
    nc, r = T // q, H // G
    f32 = jnp.float32
    xc = x.reshape(B, nc, q, G, r, P).transpose(0, 1, 3, 4, 2, 5)
    dtc = dt.astype(f32).reshape(B, nc, q, G, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(B, nc, q, G, N).transpose(0, 1, 3, 2, 4)
    cc = c.reshape(B, nc, q, G, N).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(G, r, 1), axis=-1)
    # inside a chunk: y[t] = sum_{s<=t} exp(cum_t - cum_s) dt_s (c_t.b_s) x_s
    cb = jnp.einsum("bcgtn,bcgsn->bcgts", cc, bc, preferred_element_type=f32)
    tri = jnp.tril(jnp.ones((q, q), bool))
    seg = jnp.where(tri, cum[..., :, None] - cum[..., None, :], 0.0)
    decay = jnp.where(tri, jnp.exp(seg), 0.0)            # (B,nc,G,r,t,s)
    m = (cb[:, :, :, None] * decay * dtc[..., None, :]).astype(x.dtype)
    y = jnp.einsum("bcgrts,bcgrsp->bcgrtp", m, xc, preferred_element_type=f32)
    # each chunk's own end state, then the state entering every chunk
    last = cum[..., -1]                                   # (B,nc,G,r)
    to_end = (jnp.exp(last[..., None] - cum) * dtc).astype(x.dtype)
    own = jnp.einsum("bcgrsp,bcgsn->bcgrpn", xc * to_end[..., None], bc,
                     preferred_element_type=f32)
    total = jnp.cumsum(last, axis=1)
    # entering[c] = sum_{j<c} exp(total[c-1] - total[j]) own[j]
    before = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
    span = jnp.where(before, (total - last)[:, :, None] - total[:, None], 0.0)
    carry = jnp.where(before, jnp.exp(span), 0.0)         # (B,c,j,G,r)
    entering = jnp.einsum("bcjgr,bjgrpn->bcgrpn", carry, own,
                          preferred_element_type=f32)
    y = y + jnp.einsum("bcgtn,bcgrpn->bcgrtp", cc, entering.astype(x.dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(B, T, H, P).astype(x.dtype)
    return y if d is None else y + x * d[:, None]


def causal_gqa_attention(q, k, v, q_block=512, k_block=1024, window=None):
    """Causal ``softmax(q k^T / sqrt(hd)) v`` with grouped keys and values:
    ``q`` (B, T, Hq, hd), ``k`` and ``v`` (B, T, Hkv, hd), each key-value
    head serving Hq / Hkv query heads -> (B, T, Hq, hd).  With ``window``
    (static) query i sees key j iff ``0 <= i - j < window``: itself and the
    ``window - 1`` before it; key blocks wholly behind the window are
    skipped like those above the diagonal.

    A loop over blocks of ``q_block`` query rows, and inside it a loop over
    blocks of ``k_block`` keys with the running maximum, sum and weighted
    values of an online softmax; key blocks wholly above the diagonal are
    skipped (``lax.cond``), so only the causal half is computed.  A query
    block is recomputed in the backward pass.  Both loops are ``lax.scan``s:
    one (B, Hq, q_block, k_block) score tile a step forward, one query
    block's tiles backward, and no (T, T) matrix in HBM.  (Unrolled blocks
    chained by ``optimization_barrier`` are scheduled all at once by the
    TPU compiler: compile-only rehearsal, PR 29.)

    Where `pallas_kernels.causal_attention_use_pallas` says so (one TPU,
    head_dim in 128s, whole groups, T in 128s, a K/V head of at most
    8192 x 128) the same quantity is one forward and one backward Pallas
    kernel that read the heads in place and keep every tile in VMEM
    (``mx_causal_attn_*``; ``mx_window_attn_*`` with a window)."""
    from . import pallas_kernels as _pk
    B, T, Hq, hd = q.shape
    G = k.shape[2]
    if _pk.causal_attention_use_pallas(T, Hq, G, hd, window):
        return _pk.causal_gqa_attention_fused(
            q.reshape(B, T, Hq * hd), k.reshape(B, T, G * hd),
            v.reshape(B, T, G * hd), Hq, G, None, window).reshape(q.shape)
    _count_route("attention.causal.xla_blocked" if window is None
                 else "attention.window.xla_blocked")
    r = Hq // G
    qb = q_block if T % q_block == 0 else T
    kb = k_block if T % k_block == 0 else T
    f32, low = jnp.float32, -1e30
    qs = (q * hd ** -0.5).reshape(B, T // qb, qb, G, r, hd) \
        .transpose(1, 0, 3, 4, 2, 5)                     # (nq,B,G,r,qb,hd)
    ks = k.reshape(B, T // kb, kb, G, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, T // kb, kb, G, hd).transpose(1, 0, 3, 2, 4)

    @jax.checkpoint
    def rows(args):
        i, q_i = args
        row = i * qb + jnp.arange(qb)

        def keys(carry, inp):
            j, k_j, v_j = inp

            def attend(carry):
                m, l, acc = carry
                s = jnp.einsum("bgrqd,bgkd->bgrqk", q_i, k_j,
                               preferred_element_type=f32)
                key = j * kb + jnp.arange(kb)[None]
                keep = row[:, None] >= key
                if window is not None:
                    keep &= row[:, None] - key < window
                s = jnp.where(keep, s, low)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                old = jnp.exp(m - m_new)
                pv = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v_j.dtype),
                                v_j, preferred_element_type=f32)
                return (m_new, l * old + jnp.sum(p, axis=-1),
                        acc * old[..., None] + pv)
            needed = j * kb <= i * qb + qb - 1
            if window is not None:      # its last key within the first row's
                needed &= j * kb + kb - 1 > i * qb - window
            return lax.cond(needed, attend, lambda c: c, carry), None

        init = (jnp.full((B, G, r, qb), low, f32),
                jnp.zeros((B, G, r, qb), f32),
                jnp.zeros((B, G, r, qb, hd), f32))
        (_, l, acc), _ = lax.scan(keys, init,
                                  (jnp.arange(T // kb), ks, vs))
        return acc / l[..., None]

    o = lax.map(rows, (jnp.arange(T // qb), qs))          # (nq,B,G,r,qb,hd)
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(B, T, Hq, hd).astype(q.dtype)


def rope_tables(positions, dim, theta=10000.0):
    """Rotary embedding's tables for ``rotate_half`` pairing (channel c
    with c + dim / 2): ``positions`` (T,) -> ``(cos, sin)``, each (T, dim)
    float32, the angle of channel c and of c + dim / 2 being ``position *
    theta ** (-2 c / dim)``; ``sin`` carries the minus sign of the first
    half, so that ``rope`` is two multiplies and an add."""
    c = jnp.arange(dim)
    freq = theta ** (-(c % (dim // 2)).astype(jnp.float32) * 2.0 / dim)
    angle = positions.astype(jnp.float32)[:, None] * freq[None]
    return jnp.cos(angle), jnp.where(c < dim // 2, -1.0, 1.0) * jnp.sin(angle)


@jax.jit
def rope(x, cos, sin):
    """``x cos + rotate_half(x) sin`` over the last axis of ``x``
    (B, T, H, dim) with ``rope_tables``' (T, dim) tables:
    ``(x1 cos - x2 sin, x2 cos + x1 sin)`` for the halves x1, x2.  A
    program of its own wherever it is not traced into a larger one (the
    tape's backward pass runs a recomputed block operation by operation):
    the TPU compiler fails on the half-tile rotation compiled alone
    (``IsFusibleUnalignedDUS``, libtpu 0.0.34) and takes it beside the
    multiplies."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    out = xf * cos[:, None] + jnp.roll(xf, half, axis=-1) * sin[:, None]
    return out.astype(x.dtype)


def linear_cross_entropy(h, weight, labels, block=1024):
    """``-log softmax(h @ weight.T)[labels]`` per row of ``h`` without the
    whole product: rows are taken ``block`` at a time and each block's
    logits are made again in the backward pass, so HBM never holds more
    than (block, V) of them.  The backward pass keeps each row's
    log-sum-exp from the forward one (so the forward loop runs first and
    nothing but the weight as it was is read by either).  ``h`` (..., D),
    ``weight`` (V, D), ``labels`` (...,) -> (...,) float32."""
    _count_route("loss.linear_blocked")
    lead = labels.shape
    h2 = h.reshape(-1, h.shape[-1])
    y2 = labels.reshape(-1).astype(jnp.int32)
    n = h2.shape[0]
    blk = block if n % block == 0 else n

    def logits(h_b, w):
        return jnp.matmul(h_b, w.T, preferred_element_type=jnp.float32)

    def forward(h_b, w, y_b):
        z = logits(h_b, w)
        m = jnp.max(z, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
        return lse - jnp.take_along_axis(z, y_b[:, None], axis=-1)[:, 0], lse

    def backward(res, g):
        h_b, w, y_b, lse = res
        p = jnp.exp(logits(h_b, w) - lse[:, None])
        hot = y_b[:, None] == jnp.arange(p.shape[-1], dtype=jnp.int32)
        dz = (g[:, None] * (p - hot)).astype(h_b.dtype)
        return (jnp.matmul(dz, w).astype(h_b.dtype),
                jnp.matmul(dz.T, h_b).astype(w.dtype), None)

    @jax.custom_vjp
    def rows(h_b, w, y_b):
        return forward(h_b, w, y_b)[0]

    def rows_fwd(h_b, w, y_b):
        out, lse = forward(h_b, w, y_b)
        return out, (h_b, w, y_b, lse)

    rows.defvjp(rows_fwd, backward)

    out = lax.map(lambda b: rows(b[0], weight, b[1]),
                  (h2.reshape(n // blk, blk, -1), y2.reshape(n // blk, blk)))
    return out.reshape(lead)


# A block whose output is a product ``h @ weight.T`` that its consumer may
# not need whole (a language model's head in training) *offers* the factors
# while its forward is traced; ``SoftmaxCrossEntropyLoss`` *claims* them when
# the very array it is given is that product, and takes the loss in blocks.
# One slot, identity of the traced value, emptied by the claim: an unclaimed
# offer changes nothing (the product is then computed as written).
_offered = [None]


def offer_product(product, h, weight):
    _offered[0] = (product, h, weight)


def claim_product(product):
    """``(h, weight)`` if ``product`` is the array last offered, else None."""
    held, _offered[0] = _offered[0], None
    if held is not None and held[0] is product:
        return held[1], held[2]
    return None


# ------------------------------------------ gated delta rule (KDA), SwiGLU
# Kimi Linear's gated delta-rule linear attention (arXiv:2510.26692) and the
# gated expert activation of models/solar_open2.py, appended after every
# line a kernel of another model is traced through.
def swiglu(h):
    """``silu(gate) * up`` of a fused product ``h = x [W_gate | W_up]``:
    the last axis holds the gate half, then the up half."""
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


_ACTIVATIONS["swiglu"] = swiglu

KDA_SUB = 16      # rows of a chunk whose pairwise decays are made explicitly


def kda_chunked(q, k, v, g, beta, chunk=64):
    """Kimi delta attention by chunks: per head, with a state ``S`` (dk, dv)
    that starts at zero, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T`` and ``o_t = S_t^T q_t``.

    ``q`` and ``k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g`` (B, T, H, dk)
    the log-decay of every channel (not positive), ``beta`` (B, T, H) ->
    (B, T, H, dv).  The caller normalises and scales ``q`` and ``k``.

    With ``u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t)`` the update
    is ``S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T``, so inside a chunk of
    ``chunk`` steps (``G`` the inclusive running sum of ``g``, ``S_0`` the
    entering state) the ``u`` solve the unit lower triangular system
    ``(I + A) U = beta (V - (K exp G) S_0)`` with ``A[t, s] = beta_t sum_c
    k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for s < t (the WY / UT form), and
    ``O = (Q exp G) S_0 + A_qk U`` with the same pairwise decays between
    ``q_t`` and ``k_s``, s <= t.  ``(I + A)^{-1}`` is applied to ``beta V``
    and ``beta K exp G`` for all chunks at once (one triangular solve);
    only ``U = U~ - W S_0``, the output and the state's update run chunk
    after chunk (`_delta_rule_chunked`, which `gdn_chunked` shares): as the
    kernel pair ``mx_delta_rule_fwd`` / ``mx_delta_rule_bwd`` with the state
    in VMEM where `pallas_kernels.delta_rule_use_pallas` says so (one TPU,
    ``chunk`` 64, dk and dv in 8s), as a ``lax.scan`` elsewhere (the CPU, a
    mesh, other chunks).  The pairwise products and the solve are XLA's on
    both routes.

    The decay is per channel, so ``A`` is no product of two factors that
    stay finite (``exp(-G)`` overflows under a strong decay).  As
    ``fla``'s ``chunk_kda`` does, a chunk is cut in sub-blocks of
    ``KDA_SUB`` rows (`_pairs_by_channel`): between two sub-blocks both
    factors are taken from the later one's first row, where each exponent
    is <= 0; inside a sub-block the (KDA_SUB, KDA_SUB, dk) decays are made
    one by one.  No exponent is ever positive.  The largest temporary is
    (B, H, T, KDA_SUB, dk), linear in T.  A ``T`` that ``chunk`` does not
    divide is padded with steps that change nothing (``k = 0``, ``beta =
    0``, ``g = 0``) and cut back; a ``chunk`` that ``KDA_SUB`` does not
    divide is one sub-block."""
    _count_route("kda.xla_chunked")
    return _delta_rule_chunked(q, k, v, g, beta, chunk, _pairs_by_channel,
                               "kda")


def _pairs_by_channel(q, k, G):
    """``(A_kk, A_qk)``, each (B, H, nc, chunk, chunk) and zero above the
    diagonal: ``sum_c a_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``a`` = k
    and q, s <= t, from the chunked ``q``, ``k`` and running log-decay ``G``
    (B, H, nc, chunk, dk) by sub-blocks of ``KDA_SUB`` rows."""
    B, H, nc, chunk, _ = k.shape
    f32 = jnp.float32
    sub = KDA_SUB if chunk % KDA_SUB == 0 else chunk
    n = chunk // sub
    blocks = lambda a: a.reshape(B, H, nc, n, sub, a.shape[-1])
    Gs, ks = blocks(G), blocks(k)
    rows = jnp.stack([ks, blocks(q)])                     # (2,B,H,nc,n,sub,dk)
    # a later sub-block i against an earlier one j, both factors taken from
    # the first row of i
    first = Gs[..., :1, :]                                # (B,H,nc,n,1,dk)
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)[:, :, None, None]
    cols = ks[:, :, :, None] * jnp.exp(jnp.where(
        earlier, first[:, :, :, :, None] - Gs[:, :, :, None], -jnp.inf))
    pair = jnp.einsum("xbhcitd,bhcijsd->xbhcitjs",
                      rows * jnp.exp(Gs - first), cols,
                      preferred_element_type=f32)
    # inside a sub-block, s <= t: every channel's decay by itself
    upto = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = jnp.exp(jnp.where(
        upto, Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    own = jnp.einsum("xbhcitd,bhcitsd->xbhcits", rows,
                     decay * ks[..., None, :, :],
                     preferred_element_type=f32)
    pair = pair + own[..., None, :] * jnp.eye(n, dtype=f32)[:, None, :, None]
    return pair.reshape(2, B, H, nc, chunk, chunk)


def _delta_rule_chunked(q, k, v, g, beta, chunk, pairs, kind):
    """The gated delta rule by chunks for a log-decay ``g`` (B, T, H, dk)
    a channel or (B, T, H, 1) a head; ``pairs(q, k, G)`` gives the pairwise
    decayed products inside a chunk; ``kind`` ("kda" or "gdn") names the
    counters.  Everything after the pairs broadcasts over the decay's last
    axis: the one triangular solve of this file (XLA's on every route) and
    the one recurrence over the chunk states — `pallas_kernels.
    delta_rule_fused` (``mx_delta_rule_fwd`` / ``mx_delta_rule_bwd``: the
    state in VMEM, the chunk's tiles read in place, ``w`` and ``u0`` out of
    the solve's one result ``[w | u0]``) where `delta_rule_use_pallas` says
    so, `_delta_states_scan` elsewhere.  The three inner scopes
    ``delta.pairs``, ``delta.solve`` and ``delta.states`` split the mixers'
    ``kda.scan`` / ``gdn.scan`` in a device trace."""
    from . import pallas_kernels as _pk
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    f32, out_dtype = jnp.float32, v.dtype
    pad = -T % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // chunk

    def chunks(a):                      # (B, T, H, X) -> (B, H, nc, chunk, X)
        return a.astype(f32).reshape(B, nc, chunk, H, -1) \
            .transpose(0, 3, 1, 2, 4)
    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])                        # (B,H,nc,chunk,1)
    G = jnp.cumsum(g, axis=3)
    with jax.named_scope("delta.pairs"):
        a_kk, a_qk = pairs(q, k, G)
    eg = jnp.exp(G)
    fused = _pk.delta_rule_use_pallas(T + pad, H, dk, dv, chunk, kind)
    # the kernels read w and u0 out of the solve's one result where it lies
    rhs = [k * eg, v] if fused else [v, k * eg]
    with jax.named_scope("delta.solve"):
        solved = lax.linalg.triangular_solve(
            beta * jnp.tril(a_kk, -1),
            beta * jnp.concatenate(rhs, axis=-1), left_side=True,
            lower=True, unit_diagonal=True)
    g_end = eg[..., -1, :]                                # (B,H,nc,dk or 1)
    k_end = k * jnp.exp(G[..., -1:, :] - G)
    with jax.named_scope("delta.states"):
        if fused:
            o = _pk.delta_rule_fused(kind, solved, q * eg, a_qk, k_end, g_end)
        else:
            o = _delta_states_scan(solved[..., :dv], solved[..., dv:], q * eg,
                                   a_qk, k_end, g_end)
    o = o.transpose(0, 2, 3, 1, 4)                        # (B,nc,chunk,H,dv)
    return o.reshape(B, T + pad, H, dv)[:, :T].astype(out_dtype)


def _delta_states_scan(u0, w, qg, a_qk, k_end, g_end):
    """The recurrence over the chunk states as a ``lax.scan``: with the state
    ``S`` (dk, dv) entering a chunk, ``u = u0 - w S``, ``o = qg S + a_qk u``,
    ``S <- g_end * S + k_end^T u``.  ``u0`` (B, H, nc, chunk, dv); ``w``,
    ``qg`` and ``k_end`` (B, H, nc, chunk, dk); ``a_qk`` (B, H, nc, chunk,
    chunk); ``g_end`` (B, H, nc, dk or 1) -> ``o`` (B, H, nc, chunk, dv).
    `pallas_kernels.delta_rule_fused` is the same quantity as a kernel pair."""
    f32 = jnp.float32
    B, H, _, _, dv = u0.shape
    dk = w.shape[-1]

    def step(S, xs):
        u0, w, qg, a_qk, k_end, g_end = xs
        u = u0 - jnp.einsum("bhtk,bhkv->bhtv", w, S,
                            preferred_element_type=f32)
        o = jnp.einsum("bhtk,bhkv->bhtv", qg, S, preferred_element_type=f32) \
            + jnp.einsum("bhts,bhsv->bhtv", a_qk, u,
                         preferred_element_type=f32)
        S = g_end[..., None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", k_end, u, preferred_element_type=f32)
        return S, o

    by_chunk = lambda a: jnp.moveaxis(a, 2, 0)
    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), f32),
                    tuple(by_chunk(a) for a in
                          (u0, w, qg, a_qk, k_end, g_end)))
    return jnp.moveaxis(o, 0, 2)


# ------------------------------------- gated delta rule, one decay a head
# Gated DeltaNet (arXiv:2412.06464; ``fla.layers.GatedDeltaNet``), the
# linear layers of models/olmo_hybrid.py.
def _pairs_by_head(q, k, G):
    """`_pairs_by_channel` for a running log-decay ``G`` (B, H, nc, chunk,
    1) that is one number a head and step: the decay leaves the sum over
    the channels, so ``A[t, s] = (a_t . k_s) exp(G_t - G_s)`` is one ``K
    K^T`` product times a (chunk, chunk) matrix whose exponents, taken for
    s <= t only, are never positive.  No sub-blocks, no per-channel tiles."""
    chunk = k.shape[3]
    upto = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(upto, G - jnp.swapaxes(G, -1, -2), -jnp.inf))
    return jnp.einsum("xbhctd,bhcsd->xbhcts", jnp.stack([k, q]), k,
                      preferred_element_type=jnp.float32) * decay


def gdn_chunked(q, k, v, g, beta, chunk=64):
    """The gated delta rule with **one decay a head and step** by chunks:
    per head, with a state ``S`` (dk, dv) from zero, ``S_t = exp(g_t) (I -
    beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T`` and ``o_t = S_t^T q_t``.

    ``q`` and ``k`` (B, T, H, dk), ``v`` (B, T, H, dv) with any dk and dv,
    ``g`` (B, T, H) the log-decay (not positive), ``beta`` (B, T, H) ->
    (B, T, H, dv).  The caller normalises and scales ``q`` and ``k``.

    It is `kda_chunked`'s computation (the WY / UT form: ``(I + A)`` solved
    once for ``[beta V | beta K exp G]`` over all chunks, the recurrence
    over the chunk states, the same padding of a ``T`` that ``chunk`` does
    not divide) with the pairwise decays of a chunk from `_pairs_by_head`:
    fed the same decay on every channel `kda_chunked` gives the same result
    and pays for (B, H, T, KDA_SUB, dk) tiles that this does not make.  A
    composition in XLA but for the recurrence over the chunk states, which
    is the kernel pair of `pallas_kernels.delta_rule_fused` where
    `delta_rule_use_pallas` allows: it counts ``dispatch.gdn.xla_chunked``,
    and ``dispatch.pallas.hits.gdn.<dk>`` or ``...fallbacks.gdn.<dk>``."""
    _count_route("gdn.xla_chunked")
    return _delta_rule_chunked(q, k, v, g[..., None], beta, chunk,
                               _pairs_by_head, "gdn")
