"""gluon.nn — layer zoo (≙ python/mxnet/gluon/nn/basic_layers.py,
conv_layers.py, activations.py).

TPU-first conventions: convolution/pooling layers default to **NHWC**
(channels-last — keeps the channel dim on the 128-lane registers; the
reference defaults to NCHW for cuDNN), weights are HWIO, and every layer's
forward is pure NDArray ops so hybridize() compiles the whole stack into a
single fused XLA computation.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax.numpy as jnp

from ... import tape
from ...ndarray import NDArray
from ...numpy import _call
from ...ops import nn as _nn
from ... import initializer as init
from ..block import (Block, HybridBlock, HybridSequential, Sequential)
from ..parameter import Parameter

__all__ = ["Dense", "Dropout", "Flatten", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "GELU", "Swish", "SiLU", "Conv1D", "Conv2D",
           "Conv2DTranspose", "MaxPool1D", "MaxPool2D", "AvgPool2D",
           "GlobalMaxPool2D", "GlobalAvgPool2D", "BatchNorm", "LayerNorm",
           "GroupNorm", "InstanceNorm", "Embedding", "Lambda", "HybridLambda",
           "Identity", "Sequential", "HybridSequential", "Block", "HybridBlock",
           "fused_conv_bn_relu", "fused_block_active"]


class Dense(HybridBlock):
    """≙ gluon.nn.Dense → FullyConnected (fully_connected.cc:255).
    Weight is (units, in_units) as in the reference; one MXU matmul."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zero", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self.act = activation
        self.weight = Parameter("weight", shape=(units, in_units), dtype=dtype,
                                init=weight_initializer)
        self.bias = Parameter("bias", shape=(units,), dtype=dtype,
                              init=init.create(bias_initializer or "zero")) \
            if use_bias else None

    def forward(self, x):
        if not self.weight._shape_known():
            in_units = int(jnp.prod(jnp.asarray(x.shape[1:]))) if self._flatten \
                else x.shape[-1]
            self.weight.shape = (self._units, in_units)
            self.weight._finish_deferred_init()
        if self.bias is not None and not self.bias.is_initialized:
            self.bias._finish_deferred_init()
        args = [x, self.weight.data()] + ([self.bias.data()] if self.bias is not None else [None])
        out = _call(_nn.fully_connected, *args, flatten=self._flatten)
        if self.act is not None:
            out = _call(_nn.activation, out, act_type=self.act)
        return out


class Dropout(HybridBlock):
    """≙ gluon.nn.Dropout (dropout.cc). Active only in train mode."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate

    def forward(self, x):
        from ... import numpy_extension as npx
        return npx.dropout(x, p=self._rate)


class Flatten(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def forward(self, x):
        return x.reshape(x.shape[0], -1)


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act = activation

    def forward(self, x):
        return _call(_nn.activation, x, act_type=self._act)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return _call(_nn.leaky_relu, x, slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=init.Constant(0.25), in_channels=1,
                 **kwargs):
        super().__init__(**kwargs)
        self.alpha = Parameter("alpha", shape=(in_channels,),
                               init=alpha_initializer)

    def forward(self, x):
        return _call(_nn.prelu, x, self.alpha.data())


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return _call(_nn.elu, x, alpha=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return _call(_nn.selu, x)


class GELU(HybridBlock):
    def __init__(self, approximation="erf", **kwargs):
        super().__init__(**kwargs)
        self._approx = approximation != "erf"

    def forward(self, x):
        return _call(_nn.gelu, x, approximate=self._approx)


class Swish(HybridBlock):
    def forward(self, x):
        return _call(_nn.silu, x)


SiLU = Swish


class _ConvBase(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels, activation, use_bias,
                 weight_initializer, bias_initializer, ndims, **kwargs):
        super().__init__(**kwargs)
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * ndims
        self._channels = channels
        self._kernel = tuple(kernel_size)
        self._strides = strides
        self._padding = padding
        self._dilation = dilation
        self._groups = groups
        self._layout = layout
        self.act = activation
        # HWIO weight layout (XLA-native; reference stores OIHW for cuDNN)
        wshape = self._kernel + (in_channels // groups if in_channels else 0, channels)
        self.weight = Parameter("weight", shape=wshape,
                                init=weight_initializer or init.Xavier())
        self.bias = Parameter("bias", shape=(channels,),
                              init=init.create(bias_initializer or "zero")) \
            if use_bias else None

    def _infer(self, x):
        if not self.weight._shape_known():
            c_in = x.shape[-1] if self._layout.endswith("C") else x.shape[1]
            self.weight.shape = self._kernel + (c_in // self._groups, self._channels)
            self.weight._finish_deferred_init()
        if self.bias is not None and not self.bias.is_initialized:
            self.bias._finish_deferred_init()


class Conv2D(_ConvBase):
    """≙ gluon.nn.Conv2D (src/operator/nn/convolution.cc)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 dilation=1, groups=1, layout="NHWC", in_channels=0,
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zero", **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, 2, **kwargs)

    def forward(self, x):
        self._infer(x)
        b = self.bias.data() if self.bias is not None else None
        out = _call(_nn.convolution, x, self.weight.data(), b,
                    stride=self._strides, pad=self._padding,
                    dilate=self._dilation, groups=self._groups,
                    layout=self._layout)
        if self.act is not None:
            out = _call(_nn.activation, out, act_type=self.act)
        return out


class Conv1D(_ConvBase):
    """1-D conv implemented as 2-D with unit height (layout NWC)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 dilation=1, groups=1, layout="NWC", in_channels=0,
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zero", **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, "NHWC", in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, 1, **kwargs)

    def forward(self, x):
        # x: (N, W, C) -> (N, 1, W, C)
        if not self.weight._shape_known():
            self.weight.shape = (1,) + self._kernel + \
                (x.shape[-1] // self._groups, self._channels)
            self.weight._finish_deferred_init()
        if self.bias is not None and not self.bias.is_initialized:
            self.bias._finish_deferred_init()
        x4 = x.expand_dims(1)
        b = self.bias.data() if self.bias is not None else None
        s = self._strides if isinstance(self._strides, int) else self._strides[0]
        p = self._padding if isinstance(self._padding, int) else self._padding[0]
        d = self._dilation if isinstance(self._dilation, int) else self._dilation[0]
        out = _call(_nn.convolution, x4, self.weight.data(), b,
                    stride=(1, s), pad=(0, p), dilate=(1, d),
                    groups=self._groups)
        out = out.squeeze(1)
        if self.act is not None:
            out = _call(_nn.activation, out, act_type=self.act)
        return out


class Conv2DTranspose(_ConvBase):
    """≙ gluon.nn.Conv2DTranspose (deconvolution.cc)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NHWC",
                 in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zero", **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, 2, **kwargs)
        self._output_padding = output_padding

    def forward(self, x):
        self._infer(x)
        b = self.bias.data() if self.bias is not None else None
        out = _call(_nn.conv_transpose, x, self.weight.data(), b,
                    stride=self._strides, pad=self._padding,
                    dilate=self._dilation, output_padding=self._output_padding,
                    groups=self._groups, layout=self._layout)
        if self.act is not None:
            out = _call(_nn.activation, out, act_type=self.act)
        return out


class _Pool(HybridBlock):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NHWC",
                 ceil_mode=False, count_include_pad=True, pool_type="max",
                 global_pool=False, **kwargs):
        super().__init__(**kwargs)
        self._kw = dict(kernel=pool_size, stride=strides, pad=padding,
                        pool_type=pool_type, global_pool=global_pool,
                        count_include_pad=count_include_pad, layout=layout)

    def forward(self, x):
        return _call(_nn.pooling, x, **self._kw)


class MaxPool2D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NHWC",
                 **kwargs):
        super().__init__(pool_size, strides, padding, layout,
                         pool_type="max", **kwargs)


class MaxPool1D(HybridBlock):
    def __init__(self, pool_size=2, strides=None, padding=0, **kwargs):
        super().__init__(**kwargs)
        self._kw = dict(kernel=(1, pool_size),
                        stride=(1, strides if strides else pool_size),
                        pad=(0, padding), pool_type="max")

    def forward(self, x):
        return _call(_nn.pooling, x.expand_dims(1), **self._kw).squeeze(1)


class AvgPool2D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NHWC",
                 count_include_pad=True, **kwargs):
        super().__init__(pool_size, strides, padding, layout,
                         count_include_pad=count_include_pad,
                         pool_type="avg", **kwargs)


class GlobalMaxPool2D(_Pool):
    def __init__(self, layout="NHWC", **kwargs):
        super().__init__(layout=layout, pool_type="max", global_pool=True,
                         **kwargs)


class GlobalAvgPool2D(_Pool):
    def __init__(self, layout="NHWC", **kwargs):
        super().__init__(layout=layout, pool_type="avg", global_pool=True,
                         **kwargs)


class BatchNorm(HybridBlock):
    """≙ gluon.nn.BatchNorm (src/operator/nn/batch_norm.cc).

    Channel axis defaults to -1 (NHWC). Running stats are aux parameters
    (grad_req='null'), functionally updated — under hybridize they become
    extra outputs of the jitted function, written back each step.
    """

    def __init__(self, axis=-1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._use_global_stats = use_global_stats
        sh = (in_channels,) if in_channels else (0,)
        self.gamma = Parameter("gamma", shape=sh, init=init.One(),
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=sh, init=init.Zero(),
                              grad_req="write" if center else "null")
        self.running_mean = Parameter("running_mean", shape=sh,
                                      init=init.Zero(), grad_req="null")
        self.running_var = Parameter("running_var", shape=sh,
                                     init=init.One(), grad_req="null")

    def forward(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            if not p._shape_known():
                p.shape = (c,)
            if not p.is_initialized:
                p._finish_deferred_init()
        training = tape.is_training()
        out = _call(_nn.batch_norm, x, self.gamma.data(), self.beta.data(),
                    self.running_mean.data(), self.running_var.data(),
                    momentum=self._momentum, eps=self._eps,
                    use_global_stats=self._use_global_stats,
                    training=training, axis=self._axis)
        y, new_mean, new_var = out
        if training and not self._use_global_stats:
            self.running_mean.set_data(new_mean)
            self.running_var.set_data(new_var)
        return y


class _Layerwise(threading.local):
    on = False


_layerwise = _Layerwise()


@contextlib.contextmanager
def _layerwise_forwards():
    """While entered (this thread), blocks take their layer-by-layer
    forwards: ``quantization.quantize_net`` hooks each layer's forward
    to calibrate it, and the fused route would pass them by."""
    was = _layerwise.on
    _layerwise.on = True
    try:
        yield
    finally:
        _layerwise.on = was


def fused_block_active() -> bool:
    """True when at least one stage routes to the fused residual-block
    pipeline on this process (``pallas_block.block_active``) — the
    resnet blocks' cue to take the fused forward.  False (the CPU
    default) keeps the legacy layer-by-layer path bit-for-bit, which is
    what trace/export (gluon2sym, ONNX, quantization) walk."""
    if _layerwise.on:
        return False
    from ...ops import pallas_block
    return pallas_block.block_active()


def fused_conv_bn_relu(conv: "Conv2D", bn: "BatchNorm", x,
                       residual=None, relu: bool = True):
    """Run a Conv2D + BatchNorm (+ residual add) (+ ReLU) segment through
    the fused ``residual_block`` op — ONE dispatched op (and, on a stage
    ``pallas_block.decide`` routes, one HBM round trip) instead of
    four.  The layers keep their parameters and running-stat writeback
    exactly as in the unfused path; segments the fused op cannot take
    (non-3×3/s1, grouped, biased, NCHW) fall back to the plain layer
    composition, numerically identical either way.

    After ``quantization.quantize_net`` the conv slot holds a
    ``QuantizedConv2D`` twin (and the BN slot its folded-away identity):
    the twin's ``fused_forward`` carries the same epilogue — dequant +
    folded-BN bias (+ residual add) (+ ReLU) — through the int8 kernel
    route, so quantized resnets keep the single-pass residual block.
    """
    fused = getattr(conv, "fused_forward", None)
    if fused is not None:
        return fused(x, residual=residual, relu=relu)
    strides = conv._strides if isinstance(conv._strides, tuple) \
        else (conv._strides,) * 2
    padding = conv._padding if isinstance(conv._padding, tuple) \
        else (conv._padding,) * 2
    dilation = conv._dilation if isinstance(conv._dilation, tuple) \
        else (conv._dilation,) * 2
    if not (conv._kernel == (3, 3) and strides == (1, 1)
            and padding == (1, 1) and dilation == (1, 1)
            and conv._groups == 1 and conv.bias is None
            and conv.act is None and conv._layout == "NHWC"
            and bn._axis in (-1, 3)):
        out = bn(conv(x))
        if residual is not None:
            out = out + residual
        return out.relu() if relu else out
    conv._infer(x)
    c = conv._channels
    for p in (bn.gamma, bn.beta, bn.running_mean, bn.running_var):
        if not p._shape_known():
            p.shape = (c,)
        if not p.is_initialized:
            p._finish_deferred_init()
    training = tape.is_training()
    args = [x, conv.weight.data(), bn.gamma.data(), bn.beta.data(),
            bn.running_mean.data(), bn.running_var.data()]
    if residual is not None:
        args.append(residual)
    y, new_mean, new_var = _call(_nn.residual_block, *args,
                                 momentum=bn._momentum, eps=bn._eps,
                                 use_global_stats=bn._use_global_stats,
                                 training=training, relu=relu)
    if training and not bn._use_global_stats:
        bn.running_mean.set_data(new_mean)
        bn.running_var.set_data(new_var)
    return y


class LayerNorm(HybridBlock):
    """≙ gluon.nn.LayerNorm (layer_norm.cc)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        sh = (in_channels,) if in_channels else (0,)
        self.gamma = Parameter("gamma", shape=sh, init=init.One(),
                               grad_req="write" if scale else "null")
        self.beta = Parameter("beta", shape=sh, init=init.Zero(),
                              grad_req="write" if center else "null")

    def forward(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p.shape = (c,)
            if not p.is_initialized:
                p._finish_deferred_init()
        return _call(_nn.layer_norm, x, self.gamma.data(), self.beta.data(),
                     axis=self._axis, eps=self._eps)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._ng = num_groups
        self._eps = epsilon
        sh = (in_channels,) if in_channels else (0,)
        self.gamma = Parameter("gamma", shape=sh, init=init.One())
        self.beta = Parameter("beta", shape=sh, init=init.Zero())

    def forward(self, x):
        c = x.shape[-1]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p.shape = (c,)
            if not p.is_initialized:
                p._finish_deferred_init()
        return _call(_nn.group_norm, x, self.gamma.data(), self.beta.data(),
                     num_groups=self._ng, eps=self._eps)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        sh = (in_channels,) if in_channels else (0,)
        self.gamma = Parameter("gamma", shape=sh, init=init.One())
        self.beta = Parameter("beta", shape=sh, init=init.Zero())

    def forward(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p.shape = (c,)
            if not p.is_initialized:
                p._finish_deferred_init()
        return _call(_nn.instance_norm, x, self.gamma.data(), self.beta.data(),
                     eps=self._eps, axis=self._axis)


class Embedding(HybridBlock):
    """≙ gluon.nn.Embedding (indexing_op.cc) — a gather from the table."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self.weight = Parameter("weight", shape=(input_dim, output_dim),
                                dtype=dtype,
                                init=weight_initializer or init.Normal(0.02))
        if sparse_grad:
            # ≙ Embedding(sparse_grad=True): the Trainer routes this
            # parameter through the optimizer's lazy row-sparse update
            self.weight.grad_stype = "row_sparse"

    def forward(self, x):
        return _call(_nn.embedding, x, self.weight.data())


class Lambda(Block):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Conv3D(_ConvBase):
    """≙ gluon.nn.Conv3D (NDHWC channels-last)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 dilation=1, groups=1, layout="NDHWC", in_channels=0,
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zero", **kwargs):
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, 3, **kwargs)

    def forward(self, x):
        self._infer(x)
        b = self.bias.data() if self.bias is not None else None
        out = _call(_nn.convolution_nd, x, self.weight.data(), b,
                    stride=self._strides, pad=self._padding,
                    dilate=self._dilation, groups=self._groups, ndims=3)
        if self.act is not None:
            out = _call(_nn.activation, out, act_type=self.act)
        return out


class Conv1DTranspose(HybridBlock):
    """≙ gluon.nn.Conv1DTranspose — 2-D transpose with unit height (NWC)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, in_channels=0, use_bias=True,
                 weight_initializer=None, bias_initializer="zero", **kwargs):
        super().__init__(**kwargs)
        self._inner = Conv2DTranspose(
            channels, (1, kernel_size), strides=(1, strides),
            padding=(0, padding), output_padding=(0, output_padding),
            in_channels=in_channels, use_bias=use_bias,
            weight_initializer=weight_initializer,
            bias_initializer=bias_initializer)

    def forward(self, x):
        return self._inner(x.expand_dims(1)).squeeze(1)


class _PoolND(HybridBlock):
    def __init__(self, ndims, pool_size, strides, padding, pool_type,
                 global_pool=False, count_include_pad=True, **kwargs):
        super().__init__(**kwargs)
        self._ndims = ndims
        self._kw = dict(kernel=pool_size, stride=strides, pad=padding,
                        pool_type=pool_type, global_pool=global_pool,
                        count_include_pad=count_include_pad, ndims=ndims)

    def forward(self, x):
        if self._ndims == 1:
            # (N, W, C): lift to 2-D pooling machinery via ndims=1 window
            return _call(_nn.pooling_nd, x, **self._kw)
        return _call(_nn.pooling_nd, x, **self._kw)


class MaxPool3D(_PoolND):
    def __init__(self, pool_size=2, strides=None, padding=0, **kwargs):
        super().__init__(3, pool_size, strides, padding, "max", **kwargs)


class AvgPool3D(_PoolND):
    def __init__(self, pool_size=2, strides=None, padding=0,
                 count_include_pad=True, **kwargs):
        super().__init__(3, pool_size, strides, padding, "avg",
                         count_include_pad=count_include_pad, **kwargs)


class AvgPool1D(_PoolND):
    def __init__(self, pool_size=2, strides=None, padding=0,
                 count_include_pad=True, **kwargs):
        super().__init__(1, pool_size, strides, padding, "avg",
                         count_include_pad=count_include_pad, **kwargs)


class GlobalMaxPool1D(_PoolND):
    def __init__(self, **kwargs):
        super().__init__(1, 1, None, 0, "max", global_pool=True, **kwargs)


class GlobalAvgPool1D(_PoolND):
    def __init__(self, **kwargs):
        super().__init__(1, 1, None, 0, "avg", global_pool=True, **kwargs)


class GlobalMaxPool3D(_PoolND):
    def __init__(self, **kwargs):
        super().__init__(3, 1, None, 0, "max", global_pool=True, **kwargs)


class GlobalAvgPool3D(_PoolND):
    def __init__(self, **kwargs):
        super().__init__(3, 1, None, 0, "avg", global_pool=True, **kwargs)


class ReflectionPad2D(HybridBlock):
    """≙ gluon.nn.ReflectionPad2D (NHWC)."""

    def __init__(self, padding=0, **kwargs):
        super().__init__(**kwargs)
        self._pad = padding

    def forward(self, x):
        return _call(_nn.reflection_pad2d, x, pad=self._pad)


class SyncBatchNorm(BatchNorm):
    """≙ gluon.contrib.nn.SyncBatchNorm (sync_batch_norm.cc).

    TPU-native: inside shard_map/pmap with a named data-parallel axis,
    batch statistics are pmean'd across shards (the reference syncs via a
    cross-GPU key-value store). `axis_name` names the mesh axis; without
    one (or outside a named-axis context) it behaves as BatchNorm.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, axis_name=None, **kwargs):
        super().__init__(axis=-1, momentum=momentum, epsilon=epsilon,
                         in_channels=in_channels, **kwargs)
        self._axis_name = axis_name

    def forward(self, x):
        if self._axis_name is None:
            return super().forward(x)
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean,
                  self.running_var):
            if not p._shape_known():
                p.shape = (c,)
            if not p.is_initialized:
                p._finish_deferred_init()
        training = tape.is_training()
        out = _call(_nn.sync_batch_norm, x, self.gamma.data(),
                    self.beta.data(), self.running_mean.data(),
                    self.running_var.data(), momentum=self._momentum,
                    eps=self._eps, training=training, axis=self._axis,
                    axis_name=self._axis_name)
        y, new_mean, new_var = out
        if training:
            self.running_mean.set_data(new_mean)
            self.running_var.set_data(new_var)
        return y


class HybridConcatenate(HybridBlock):
    """≙ gluon.nn.HybridConcatenate — parallel branches, concat outputs."""

    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._layers = []

    def add(self, *blocks):
        for b in blocks:
            setattr(self, str(len(self._layers)), b)
            self._layers.append(b)
        return self

    def forward(self, x):
        import jax.numpy as jnp
        outs = [b(x) for b in self._layers]
        ax = self._axis
        return _call(lambda *xs: jnp.concatenate(xs, axis=ax), *outs)


Concatenate = HybridConcatenate

__all__ += ["Conv3D", "Conv1DTranspose", "MaxPool3D", "AvgPool3D",
            "AvgPool1D", "GlobalMaxPool1D", "GlobalAvgPool1D",
            "GlobalMaxPool3D", "GlobalAvgPool3D", "ReflectionPad2D",
            "SyncBatchNorm", "HybridConcatenate", "Concatenate"]


class RMSNorm(HybridBlock):
    """``gamma * x / sqrt(mean(x**2, axis) + epsilon)`` (no mean taken off,
    no shift): the norm of pre-norm language models."""

    def __init__(self, axis=-1, epsilon=1e-5, in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.One())

    def forward(self, x):
        if not self.gamma._shape_known():
            self.gamma.shape = (x.shape[self._axis],)
        if not self.gamma.is_initialized:
            self.gamma._finish_deferred_init()
        return _call(_nn.rms_norm, x, self.gamma.data(), axis=self._axis,
                     eps=self._eps)


class GatedGroupRMSNorm(HybridBlock):
    """``gamma * groupRMSNorm(x * silu(gate))`` with the RMS over each of
    ``groups`` equal runs of the last axis (Mamba-2's output norm)."""

    def __init__(self, groups=1, epsilon=1e-5, in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._groups = groups
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.One())

    def forward(self, x, gate):
        if not self.gamma._shape_known():
            self.gamma.shape = (x.shape[-1],)
        if not self.gamma.is_initialized:
            self.gamma._finish_deferred_init()
        return _call(_nn.gated_group_rms_norm, x, gate, self.gamma.data(),
                     groups=self._groups, eps=self._eps)


__all__ += ["RMSNorm", "GatedGroupRMSNorm"]
