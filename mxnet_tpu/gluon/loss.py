"""gluon.loss — ≙ python/mxnet/gluon/loss.py.

Each Loss is a HybridBlock returning per-sample loss (batch axis preserved),
with sample_weight support, matching the reference's contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ndarray import NDArray
from ..numpy import _call
from ..ops import nn as _nn
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "HuberLoss", "HingeLoss",
           "SquaredHingeLoss", "LogisticLoss", "SoftmaxCrossEntropyLoss",
           "SoftmaxCELoss", "SigmoidBinaryCrossEntropyLoss", "SigmoidBCELoss",
           "KLDivLoss", "TripletLoss", "CosineEmbeddingLoss"]


def _apply_weight(loss, weight, sample_weight):
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None and weight != 1.0:
        loss = loss * weight
    return loss


def _batch_mean(loss, batch_axis):
    axes = tuple(i for i in range(loss.ndim) if i != batch_axis)
    return loss.mean(axis=axes) if axes else loss


class Loss(HybridBlock):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        loss = _call(lambda p, l: (p - l) ** 2 / 2, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class L1Loss(Loss):
    def forward(self, pred, label, sample_weight=None):
        loss = _call(lambda p, l: jnp.abs(p - l), pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        rho = self._rho

        def fn(p, l):
            d = jnp.abs(p - l)
            return jnp.where(d > rho, d - 0.5 * rho, 0.5 / rho * d * d)
        loss = _call(fn, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class HingeLoss(Loss):
    def __init__(self, margin=1.0, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        m = self._margin
        loss = _call(lambda p, l: jnp.maximum(0.0, m - p * l), pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class SquaredHingeLoss(HingeLoss):
    def forward(self, pred, label, sample_weight=None):
        m = self._margin
        loss = _call(lambda p, l: jnp.maximum(0.0, m - p * l) ** 2, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class LogisticLoss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, label_format="signed", **kw):
        super().__init__(weight, batch_axis, **kw)
        self._fmt = label_format

    def forward(self, pred, label, sample_weight=None):
        fmt = self._fmt

        def fn(p, l):
            if fmt == "signed":
                l = (l + 1.0) / 2.0
            return jnp.maximum(p, 0) - p * l + jnp.log1p(jnp.exp(-jnp.abs(p)))
        loss = _call(fn, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class SoftmaxCrossEntropyLoss(Loss):
    """≙ gluon.loss.SoftmaxCrossEntropyLoss — fused log-softmax + NLL."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        axis, sparse, from_logits = self._axis, self._sparse, self._from_logits

        def fn(p, l):
            logp = p if from_logits else _nn.log_softmax(p, axis=axis)
            if sparse:
                return -_nn.pick(logp, l, axis=axis)
            return -jnp.sum(logp * l, axis=axis)
        # a head that offered its product ``h @ w.T`` in factors (a language
        # model's, while a training program is traced) gets the loss taken
        # in blocks of rows: the whole (T, V) matrix is never computed
        factors = _nn.claim_product(pred._data) \
            if sparse and not from_logits and axis in (-1, pred.ndim - 1) \
            else None
        if factors is not None:
            loss = _call(_nn.linear_cross_entropy, NDArray(factors[0]),
                         NDArray(factors[1]), label)
        else:
            loss = _call(fn, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=1.0, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, pos_weight=None, sample_weight=None):
        fs = self._from_sigmoid
        loss = _call(lambda p, l: _nn.sigmoid_binary_cross_entropy(p, l, fs),
                     pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=1.0, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        from_logits, axis = self._from_logits, self._axis

        def fn(p, l):
            logp = p if from_logits else _nn.log_softmax(p, axis=axis)
            return jnp.mean(l * (jnp.log(l + 1e-12) - logp), axis=axis)
        loss = _call(fn, pred, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


class TripletLoss(Loss):
    def __init__(self, margin=1.0, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        m = self._margin

        def fn(a, p, n):
            d = jnp.sum((a - p) ** 2 - (a - n) ** 2, axis=tuple(range(1, a.ndim)))
            return jnp.maximum(d + m, 0.0)
        loss = _call(fn, pred, positive, negative)
        return _apply_weight(loss, self._weight, sample_weight)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, margin=0.0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        m = self._margin

        def fn(a, b, l):
            cos = jnp.sum(a * b, axis=-1) / (
                jnp.sqrt(jnp.sum(a * a, axis=-1)) *
                jnp.sqrt(jnp.sum(b * b, axis=-1)) + 1e-12)
            return jnp.where(l == 1, 1 - cos, jnp.maximum(0.0, cos - m))
        loss = _call(fn, input1, input2, label)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis) if loss.ndim > 1 else loss


class CTCLoss(Loss):
    """≙ gluon.loss.CTCLoss (reference python/mxnet/gluon/loss.py).

    layout: 'NTC' (default) or 'TNC' for pred; label_layout 'NT' or 'TN'.
    The blank label is ``alphabet_size - 1`` (reference default
    blank_label='last' for the gluon wrapper).
    """

    def __init__(self, layout="NTC", label_layout="NT", weight=None,
                 **kwargs):
        if layout not in ("NTC", "TNC"):
            raise ValueError(f"unsupported layout {layout}")
        if label_layout not in ("NT", "TN"):
            raise ValueError(f"unsupported label layout {label_layout}")
        batch_axis = label_layout.find("N")
        super().__init__(weight, batch_axis, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        from ..ops import ctc as _ctc
        layout, label_layout = self._layout, self._label_layout

        def fn(p, l, pl=None, ll=None):
            if layout == "NTC":
                p = jnp.swapaxes(p, 0, 1)
            if label_layout == "TN":
                l = jnp.swapaxes(l, 0, 1)
            C = p.shape[-1]
            return _ctc.ctc_loss(p, l, data_lengths=pl, label_lengths=ll,
                                 blank=C - 1)

        args = [pred, label]
        if pred_lengths is not None:
            args.append(pred_lengths)
            if label_lengths is not None:
                args.append(label_lengths)
        elif label_lengths is not None:
            def fn(p, l, ll, _f=fn):  # noqa: F811
                return _f(p, l, None, ll)
            args.append(label_lengths)
        loss = _call(fn, *args)
        return _apply_weight(loss, self._weight, sample_weight)


__all__.append("CTCLoss")


class PoissonNLLLoss(Loss):
    """≙ gluon.loss.PoissonNLLLoss — NLL of a Poisson with rate=pred.

    compute_full adds the Stirling approximation term like the reference.
    """

    def __init__(self, weight=None, from_logits=True, batch_axis=0,
                 compute_full=False, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, target, sample_weight=None, epsilon=1e-08):
        from_logits, full = self._from_logits, self._compute_full

        def fn(p, t):
            if from_logits:
                loss = jnp.exp(p) - t * p
            else:
                loss = p - t * jnp.log(p + epsilon)
            if full:
                stirling = (t * jnp.log(t + epsilon) - t +
                            0.5 * jnp.log(2 * jnp.pi * (t + epsilon)))
                loss = loss + jnp.where(t > 1, stirling, 0.0)
            return loss
        loss = _call(fn, pred, target)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return loss.mean()


class SDMLLoss(Loss):
    """≙ gluon.loss.SDMLLoss — smoothed deep metric learning over a
    batch of paired embeddings (x1[i] matches x2[i])."""

    def __init__(self, smoothing_parameter=0.3, weight=1.0, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._smooth = smoothing_parameter

    def forward(self, x1, x2, sample_weight=None):
        smooth = self._smooth

        def fn(a, b):
            n = a.shape[0]
            # pairwise euclidean distances → similarity logits
            d = jnp.sqrt(jnp.sum((a[:, None, :] - b[None, :, :]) ** 2,
                                 axis=-1) + 1e-12)
            logits = -d
            labels = jnp.eye(n)
            labels = labels * (1 - smooth) + (1 - labels) * smooth / (n - 1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(labels * logp, axis=-1)
        loss = _call(fn, x1, x2)
        loss = _apply_weight(loss, self._weight, sample_weight)
        return _batch_mean(loss, self._batch_axis)


__all__ += ["PoissonNLLLoss", "SDMLLoss"]
