"""mx.npx — NumPy-extension namespace: operator-level NN ops on NDArrays.

Equivalent of the reference's python/mxnet/numpy_extension/ (npx.relu,
npx.softmax, npx.convolution, npx.batch_norm, npx.topk, npx.pick,
npx.sequence_mask, npx.waitall ...), each lowering to the pure-jax kernels in
ops/nn.py through the autograd tape.
"""
from __future__ import annotations

import numpy as _onp
import jax.numpy as jnp

from .ndarray import NDArray, invoke_op, waitall  # noqa: F401
from .numpy import _call
from .numpy import random as _random
from .ops import nn as _nn

__all__ = [
    "relu", "sigmoid", "tanh", "softmax", "log_softmax", "masked_softmax",
    "masked_log_softmax", "multihead_self_attention", "activation",
    "leaky_relu", "gelu", "elu", "selu", "fully_connected", "dense",
    "convolution", "conv_transpose", "pooling", "batch_norm", "layer_norm",
    "rms_norm", "instance_norm", "group_norm", "dropout", "embedding",
    "one_hot", "pick", "topk", "sequence_mask", "sequence_last",
    "sequence_reverse", "softmax_cross_entropy", "amp_cast", "amp_multicast",
    "all_finite", "waitall", "seed", "save", "load", "set_np", "reset_np",
    "is_np_array", "use_np", "gamma", "erf", "erfinv", "ctc_loss",
    "gather_nd", "scatter_nd", "batch_dot", "smooth_l1",
    "slice", "slice_axis", "slice_like", "arange_like",
    "broadcast_like", "broadcast_axis",
    "rnn", "lrn", "roi_pooling", "deformable_convolution",
    "grid_generator", "bilinear_sampler", "correlation",
]


def _wrap1(fun):
    def op(*args, **kwargs):
        return _call(fun, *args, **kwargs)
    op.__name__ = fun.__name__
    return op


relu = _wrap1(_nn.relu)
sigmoid = _wrap1(_nn.sigmoid)
tanh = _wrap1(_nn.tanh)
softmax = _wrap1(_nn.softmax)
log_softmax = _wrap1(_nn.log_softmax)
masked_softmax = _wrap1(_nn.masked_softmax)
masked_log_softmax = _wrap1(_nn.masked_log_softmax)
multihead_self_attention = _wrap1(_nn.multihead_self_attention)
activation = _wrap1(_nn.activation)
leaky_relu = _wrap1(_nn.leaky_relu)
gelu = _wrap1(_nn.gelu)
elu = _wrap1(_nn.elu)
selu = _wrap1(_nn.selu)
fully_connected = _wrap1(_nn.fully_connected)
dense = _wrap1(_nn.dense)
convolution = _wrap1(_nn.convolution)
conv_transpose = _wrap1(_nn.conv_transpose)
pooling = _wrap1(_nn.pooling)
batch_norm = _wrap1(_nn.batch_norm)
layer_norm = _wrap1(_nn.layer_norm)
rms_norm = _wrap1(_nn.rms_norm)
instance_norm = _wrap1(_nn.instance_norm)
group_norm = _wrap1(_nn.group_norm)
embedding = _wrap1(_nn.embedding)

from .ops import tensor as _tensor  # noqa: E402

gather_nd = _wrap1(_tensor.gather_nd)
scatter_nd = _wrap1(_tensor.scatter_nd)
batch_dot = _wrap1(_tensor.batch_dot)
smooth_l1 = _wrap1(_tensor.smooth_l1)
slice = _wrap1(_tensor.slice)
slice_axis = _wrap1(_tensor.slice_axis)
slice_like = _wrap1(_tensor.slice_like)
arange_like = _wrap1(_tensor.arange_like)
broadcast_like = _wrap1(_tensor.broadcast_like)
broadcast_axis = _wrap1(_tensor.broadcast_axis)
one_hot = _wrap1(_nn.one_hot)
pick = _wrap1(_nn.pick)
sequence_mask = _wrap1(_nn.sequence_mask)
sequence_last = _wrap1(_nn.sequence_last)
sequence_reverse = _wrap1(_nn.sequence_reverse)
softmax_cross_entropy = _wrap1(_nn.softmax_cross_entropy)
amp_cast = _wrap1(_nn.amp_cast)
amp_multicast = _wrap1(_nn.amp_multicast)
all_finite = _wrap1(_nn.all_finite)

from .ops import ctc as _ctc  # noqa: E402
from .ops import rnn as _rnn  # noqa: E402
from .ops import vision as _vision  # noqa: E402

# public fused RNN op (≙ src/operator/rnn.cc:306 RNN op; the kernels lived
# in ops/rnn.py since r1 — this is the npx-level surface).  params is a
# list of per-layer/per-direction dicts {wi, wh, bi, bh}; flattened here
# because the generic dispatcher only walks positional lists.
def rnn(x, params, mode="lstm", num_layers=1, hidden_size=None,
        bidirectional=False, h0=None, c0=None):
    keysets = [sorted(p.keys()) for p in params]
    flat = [p[k] for p, ks in zip(params, keysets) for k in ks]

    def unwrap_state(s):
        if s is None:
            return None
        return [v._data if isinstance(v, NDArray) else v for v in s]

    h0r, c0r = unwrap_state(h0), unwrap_state(c0)

    def fn(xr, *flatr):
        it = iter(flatr)
        ps = [{k: next(it) for k in ks} for ks in keysets]
        res = _rnn.rnn(xr, ps, mode=mode, num_layers=num_layers,
                       hidden_size=hidden_size, bidirectional=bidirectional,
                       h0=h0r, c0=c0r)
        # non-lstm modes have no cell state (cN is None) — the tape wraps
        # array outputs only, so strip it here and restore after
        return tuple(r for r in res if r is not None)

    outs = _call(fn, x, *flat)
    if len(outs) == 2:
        outs = (outs[0], outs[1], None)
    return outs
# vision long tail ≙ lrn.cc, roi_pooling.cc, contrib/deformable_convolution.cc,
# grid_generator.cc, bilinear_sampler.cc, correlation.cc
lrn = _wrap1(_vision.lrn)
roi_pooling = _wrap1(_vision.roi_pooling)
deformable_convolution = _wrap1(_vision.deformable_convolution)
grid_generator = _wrap1(_vision.grid_generator)
bilinear_sampler = _wrap1(_vision.bilinear_sampler)
correlation = _wrap1(_vision.correlation)


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=None, use_label_lengths=None,
             blank_label="first"):
    """≙ npx.ctc_loss (reference src/operator/nn/ctc_loss.cc).

    data: (seq_len, batch, alphabet); label: (batch, L).
    blank_label: 'first' → blank index 0, 'last' → alphabet_size - 1.
    """
    C = data.shape[-1]
    blank = 0 if blank_label == "first" else C - 1
    if use_data_lengths is False:
        data_lengths = None
    if use_label_lengths is False:
        label_lengths = None
    return _call(_ctc.ctc_loss, data, label,
                 data_lengths=data_lengths, label_lengths=label_lengths,
                 blank=blank)


import jax as _jax  # noqa: E402

gamma = _wrap1(_jax.scipy.special.gamma) if hasattr(_jax.scipy.special, "gamma") \
    else _wrap1(lambda x: jnp.exp(_jax.scipy.special.gammaln(x)))
erf = _wrap1(_jax.scipy.special.erf)
erfinv = _wrap1(_jax.scipy.special.erfinv)


def topk(x, k=1, axis=-1, ret_typ="indices", is_ascend=False):
    no_grad = ret_typ == "indices"
    return _call(_nn.topk, x, k=k, axis=axis, ret_typ=ret_typ,
                 is_ascend=is_ascend, _no_grad=no_grad)


def dropout(x, p=0.5, training=None):
    from . import tape
    if training is None:
        training = tape.is_training()
    if not training or p == 0.0:
        return x
    key = _random.new_key()
    return _call(_nn.dropout, x, rate=p, key=key, training=True)


def seed(s):
    _random.seed(s)


# ------------------------------------------------------- save/load (.npz)
def save(fname, data):
    """Save dict/list of NDArrays ≙ npx.savez / mx.nd.save (cnpy.h:36)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        data = {str(i): a for i, a in enumerate(data)}
    _onp.savez(fname, **{k: v.asnumpy() for k, v in data.items()})


def load(fname):
    with _onp.load(fname, allow_pickle=False) as z:
        return {k: NDArray(jnp.asarray(z[k])) for k in z.files}


# --------------------------------------------------- np-semantics switches
_np_active = True  # the TPU build is numpy-semantics-native


def set_np(shape=True, array=True, dtype=False):
    return None


def reset_np():
    return None


def is_np_array():
    return True


def is_np_shape():
    return True


def use_np(fn):
    return fn


# ------------------------------------------------ shape/graph utility ops
def reshape_like(lhs, rhs):
    """≙ npx.reshape_like (src/operator/tensor/elemwise_unary_op)."""
    return _call(lambda a, b: jnp.reshape(a, b.shape), lhs, rhs)


def shape_array(data):
    """≙ npx.shape_array — the shape as an integer NDArray (int64 under
    JAX_ENABLE_X64, the large-tensor build switch; int32 otherwise)."""
    from .ndarray import NDArray
    import jax as _j
    dt = jnp.int64 if _j.config.jax_enable_x64 else jnp.int32
    return NDArray(jnp.asarray(data.shape, dt))


def batch_flatten(data):
    """≙ npx.batch_flatten."""
    return _call(lambda x: jnp.reshape(x, (x.shape[0], -1)), data)


def stop_gradient(data):
    """≙ npx.stop_gradient / mx.nd.BlockGrad."""
    return _call(_jax.lax.stop_gradient, data)


def cast(data, dtype):
    return data.astype(dtype)


__all__ += ["reshape_like", "shape_array", "batch_flatten",
            "stop_gradient", "cast"]


# --------------------------------------------------------- op long tail
# (VERDICT r3 item 3 / docs/OP_PARITY.md: the reference's registered-op
# tail — kernels in ops/tail.py, ops/attention.py, ops/boxes.py,
# ops/vision.py, ops/linalg_ext.py; functional image ops in
# ops/image_ops.py exposed as the `npx.image` submodule.)
from .ops import tail as _tail  # noqa: E402
from .ops import attention as _att  # noqa: E402
from .ops import boxes as _boxes  # noqa: E402
from .ops import image_ops as _image_ops  # noqa: E402


class _ImageNS:
    """`npx.image` — functional image ops over NDArrays (kernels in
    ops/image_ops.py; ≙ the reference's mxnet.image operator exports)."""

    def __getattr__(self, name):
        fn = getattr(_image_ops, name)
        if not callable(fn):
            return fn

        def op(*args, **kwargs):
            return _call(fn, *args, **kwargs)
        op.__name__ = name
        op.__doc__ = fn.__doc__
        return op

    def __dir__(self):
        return [n for n in dir(_image_ops) if not n.startswith("_")]


image = _ImageNS()

digamma = _wrap1(_tail.digamma)
log_sigmoid = _wrap1(_tail.log_sigmoid)
softmin = _wrap1(_tail.softmin)
rsqrt = _wrap1(_tail.rsqrt)
rcbrt = _wrap1(_tail.rcbrt)
hard_sigmoid = _wrap1(_tail.hard_sigmoid)
moments = _wrap1(_tail.moments)
khatri_rao = _wrap1(_tail.khatri_rao)
depth_to_space = _wrap1(_tail.depth_to_space)
space_to_depth = _wrap1(_tail.space_to_depth)
im2col = _wrap1(_tail.im2col)
col2im = _wrap1(_tail.col2im)
round_ste = _wrap1(_tail.round_ste)
sign_ste = _wrap1(_tail.sign_ste)
gradientmultiplier = _wrap1(_tail.gradientmultiplier)
quadratic = _wrap1(_tail.quadratic)
index_copy = _wrap1(_tail.index_copy)
index_add = _wrap1(_tail.index_add)
index_update = _wrap1(_tail.index_update)
div_sqrt_dim = _wrap1(_tail.div_sqrt_dim)
size_array = _wrap1(_tail.size_array)
make_loss = _wrap1(_tail.make_loss)
constraint_check = _wrap1(_tail.constraint_check)
dynamic_reshape = _wrap1(_tail.dynamic_reshape)
edge_id = _wrap1(_tail.edge_id)
hawkesll = _wrap1(_tail.hawkesll)
linear_regression_output = _wrap1(_tail.linear_regression_output)
mae_regression_output = _wrap1(_tail.mae_regression_output)
logistic_regression_output = _wrap1(_tail.logistic_regression_output)
identity_attach_kl_sparse_reg = \
    _wrap1(_tail.identity_attach_kl_sparse_reg)

interleaved_matmul_selfatt_qk = _wrap1(_att.interleaved_matmul_selfatt_qk)
interleaved_matmul_selfatt_valatt = \
    _wrap1(_att.interleaved_matmul_selfatt_valatt)
interleaved_matmul_encdec_qk = _wrap1(_att.interleaved_matmul_encdec_qk)
interleaved_matmul_encdec_valatt = \
    _wrap1(_att.interleaved_matmul_encdec_valatt)
sldwin_atten_score = _wrap1(_att.sldwin_atten_score)
sldwin_atten_context = _wrap1(_att.sldwin_atten_context)
sldwin_atten_mask_like = _wrap1(_att.sldwin_atten_mask_like)

box_encode = _wrap1(_boxes.box_encode)
box_decode = _wrap1(_boxes.box_decode)
bipartite_matching = _wrap1(_boxes.bipartite_matching)
roi_align = _wrap1(_vision.roi_align)
rroi_align = _wrap1(_vision.rroi_align)
adaptive_avg_pooling2d = _wrap1(_vision.adaptive_avg_pool2d)
bilinear_resize2d = _wrap1(_vision.bilinear_resize2d)
upsampling = _wrap1(_vision.upsampling)
softmax_activation = _wrap1(_vision.softmax_activation)


def shares_memory(a, b):
    """≙ _npi_share_memory (host predicate, not a graph op)."""
    return _tail.shares_memory(
        a._data if isinstance(a, NDArray) else a,
        b._data if isinstance(b, NDArray) else b)


__all__ += [
    "digamma", "log_sigmoid", "softmin", "rsqrt", "rcbrt", "hard_sigmoid",
    "moments", "khatri_rao", "depth_to_space", "space_to_depth", "im2col",
    "col2im", "round_ste", "sign_ste", "gradientmultiplier", "quadratic",
    "index_copy", "index_add", "index_update", "div_sqrt_dim",
    "size_array", "make_loss", "constraint_check", "dynamic_reshape",
    "edge_id", "hawkesll", "linear_regression_output",
    "mae_regression_output", "logistic_regression_output",
    "identity_attach_kl_sparse_reg", "interleaved_matmul_selfatt_qk",
    "interleaved_matmul_selfatt_valatt", "interleaved_matmul_encdec_qk",
    "interleaved_matmul_encdec_valatt", "sldwin_atten_score",
    "sldwin_atten_context", "sldwin_atten_mask_like", "box_encode",
    "box_decode", "bipartite_matching", "roi_align", "rroi_align",
    "adaptive_avg_pooling2d", "bilinear_resize2d", "upsampling",
    "softmax_activation", "shares_memory", "image",
]
