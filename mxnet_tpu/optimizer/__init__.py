"""mx.optimizer — optimizer zoo with fused multi-tensor updates.

Equivalent of the reference's python/mxnet/optimizer/ (21 optimizers,
registry + ``aggregate_num`` multi-tensor batching) and the fused update
kernels in src/operator/optimizer_op.cc:352-1130 (multi_sgd_update, lamb,
mp_*).  TPU-native design: each optimizer is a pure per-tensor update rule;
``update_multi`` jit-compiles ONE XLA computation applying the rule across
the whole parameter pytree (input buffers donated), which is the MXU/HBM
friendly equivalent of the reference's multi-tensor fused kernels — one
dispatch per step regardless of parameter count.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..ndarray import NDArray

__all__ = ["Optimizer", "create", "register", "SGD", "NAG", "Adam", "AdamW",
           "Adamax", "Nadam", "AdaGrad", "AdaDelta", "AdaBelief", "RMSProp",
           "Ftrl", "FTML", "LAMB", "LARS", "LANS", "Signum", "SGLD",
           "DCASGD"]

_REGISTRY: Dict[str, type] = {}


def register(cls):
    _REGISTRY[cls.__name__.lower()] = cls
    return cls


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _REGISTRY[str(name).lower()](**kwargs)


class Optimizer:
    """Base optimizer ≙ python/mxnet/optimizer/optimizer.py.

    Subclasses implement ``create_state(w)`` and ``_update(w, g, state, lr,
    wd, t)`` as pure jax functions. ``rescale_grad`` / ``clip_gradient`` /
    ``lr_scheduler`` handled here.
    """

    def __init__(self, learning_rate=0.01, wd=0.0, rescale_grad=1.0,
                 clip_gradient=None, lr_scheduler=None, aggregate_num=None,
                 multi_precision=False, **kwargs):
        self.lr = learning_rate
        self.wd = wd
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient
        self.lr_scheduler = lr_scheduler
        self.multi_precision = multi_precision
        # lazy row-sparse updates (≙ sgd/adam lazy_update): honored by
        # update() when the gradient is RowSparse
        self.lazy_update = bool(kwargs.get("lazy_update", True))
        self.num_update = 0
        self.begin_num_update = 0
        # per-key update counts ≙ Optimizer._index_update_count
        # (python/mxnet/optimizer/optimizer.py _update_count): the per-key
        # t drives Adam/LAMB bias correction and must NOT advance once per
        # parameter per step when the store applies updates key by key
        self._index_update_count = {}
        self.param_dict = {}
        self._jit_multi = None
        self._jit_multi_sig = None  # (rescale_grad, clip_gradient, wd) baked
                                    # into the _jit_multi trace

    # -- lr ----------------------------------------------------------------
    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        self.lr = lr

    # -- per-tensor API (reference Optimizer.update signature) -------------
    def create_state(self, index, weight):
        return self.init_state(weight._data if isinstance(weight, NDArray) else weight)

    def init_state(self, w) -> Dict[str, Any]:
        return {}

    def _update(self, w, g, state, lr, wd, t):
        raise NotImplementedError

    def _preprocess_grad(self, g):
        if self.rescale_grad != 1.0:
            g = g * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def _update_count(self, index):
        """Advance this key's step count; num_update = max over keys
        (≙ optimizer.py _update_count)."""
        idx = str(index)
        c = self._index_update_count.get(idx, self.begin_num_update) + 1
        self._index_update_count[idx] = c
        self.num_update = max(c, self.num_update)
        return c

    def update(self, index, weight, grad, state):
        """Single-tensor eager update (updates weight NDArray in place).

        RowSparse gradients take the LAZY path (≙ sgd/adam lazy_update,
        optimizer_op.cc:352 SGDUpdateRowSparse): only rows the gradient
        touches are gathered, pushed through the SAME ``_update`` rule,
        and scattered back — untouched rows (and their momentum/variance
        state) stay byte-identical, the reference's lazy semantics."""
        from ..sparse import RowSparseNDArray
        t_key = self._update_count(index)
        lr = jnp.asarray(self.learning_rate, jnp.float32)
        t = jnp.asarray(t_key, jnp.int32)
        wd = jnp.asarray(self.wd, jnp.float32)
        if isinstance(grad, RowSparseNDArray) and self.lazy_update:
            rows = grad._indices
            g_rows = self._preprocess_grad(
                grad._values.astype(weight._data.dtype))
            w_rows = weight._data[rows]

            def take_rows(s):
                return s[rows] if hasattr(s, "shape") and \
                    getattr(s, "shape", ()) == weight._data.shape else s
            state_rows = {k: take_rows(v) for k, v in state.items()} \
                if isinstance(state, dict) else state
            new_rows, new_state_rows = self._update(
                w_rows, g_rows, state_rows, lr, wd, t)
            weight._data = weight._data.at[rows].set(new_rows)
            if isinstance(state, dict):
                for k, v in new_state_rows.items():
                    old = state.get(k)
                    if hasattr(old, "shape") and \
                            getattr(old, "shape", ()) == weight._data.shape:
                        state[k] = old.at[rows].set(v)
                    else:
                        state[k] = v
            return state
        g = self._preprocess_grad(grad._data.astype(weight._data.dtype))
        new_w, new_state = self._update(weight._data, g, state, lr, wd, t)
        weight._data = new_w
        if isinstance(state, dict):
            state.clear()
            state.update(new_state)
        return new_state

    # -- fused multi-tensor API (the hot path) ------------------------------
    def _tree_update(self, ws, gs, states, lr, t):
        """Apply the update rule across a param pytree — deliberately
        UN-jitted so outer programs (update_multi's own jit, the fused
        train step) inline it into their trace.  ``rescale_grad`` /
        ``clip_gradient`` / ``wd`` are read as python constants and baked
        in; callers cache executables keyed on :meth:`_fused_sig`."""
        wd = jnp.asarray(self.wd, jnp.float32)
        out_w, out_s = {}, {}
        for k in ws:
            # the parameter's name on its update's ops in a device trace
            with jax.named_scope(str(k).replace("/", "_")):
                g = self._preprocess_grad(gs[k].astype(ws[k].dtype))
                out_w[k], out_s[k] = self._update(ws[k], g, states[k], lr,
                                                  wd, t)
        return out_w, out_s

    def _fused_sig(self):
        """The python constants a ``_tree_update`` trace bakes in.  A trace
        (update_multi's or the fused step's) is only valid while this
        tuple is unchanged — Trainer.step rewrites ``rescale_grad`` from
        batch_size every call, so the check is per step, not per build."""
        return (self.rescale_grad, self.clip_gradient, self.wd)

    def update_multi(self, weights: Dict[str, Any], grads: Dict[str, Any],
                     states: Dict[str, Any], advance=True):
        """One fused XLA computation updating every parameter (≙ the
        reference's multi_sgd_update/aggregate_num path). `advance=False`
        when the caller already advanced num_update this step (mixed
        sparse+dense updates must count the step ONCE)."""
        if advance:
            self.num_update += 1
        sig = self._fused_sig()
        if self._jit_multi is None or self._jit_multi_sig != sig:
            # rescale/clip/wd are trace-time constants of _tree_update: a
            # stale executable would silently keep applying the OLD values
            # (e.g. after Trainer.step recomputes rescale_grad for a new
            # batch_size) — re-jit when the baked signature changes
            self._jit_multi = jax.jit(self._tree_update, donate_argnums=(0, 2))
            self._jit_multi_sig = sig
        lr = jnp.asarray(self.learning_rate, jnp.float32)
        t = jnp.asarray(self.num_update, jnp.int32)
        return self._jit_multi(weights, grads, states, lr, t)


@register
class SGD(Optimizer):
    """≙ optimizer/sgd.py + multi_sgd_update (optimizer_op.cc:352)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, nesterov=False, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.momentum = momentum
        self.nesterov = nesterov

    def init_state(self, w):
        if self.momentum != 0.0:
            return {"mom": jnp.zeros_like(w)}
        return {}

    def _update(self, w, g, state, lr, wd, t):
        lr = lr.astype(w.dtype)
        g = g + wd.astype(w.dtype) * w
        if self.momentum == 0.0:
            return w - lr * g, state
        mom = state["mom"] * self.momentum - lr * g
        if self.nesterov:
            w = w + self.momentum * mom - lr * g
        else:
            w = w + mom
        return w, {"mom": mom}


@register
class NAG(SGD):
    """Nesterov accelerated gradient ≙ optimizer/nag.py."""

    def __init__(self, learning_rate=0.01, momentum=0.0, **kw):
        super().__init__(learning_rate=learning_rate, momentum=momentum,
                         nesterov=True, **kw)


@register
class Adam(Optimizer):
    """≙ optimizer/adam.py (adam_update optimizer_op.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kw):
        super().__init__(learning_rate=learning_rate,
                         lazy_update=lazy_update, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_state(self, w):
        return {"mean": jnp.zeros_like(w), "var": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        v = self.beta2 * state["var"] + (1 - self.beta2) * g * g
        tf = t.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** tf).astype(w.dtype)
        vhat = v / (1 - self.beta2 ** tf).astype(w.dtype)
        w = w - lr.astype(w.dtype) * mhat / (jnp.sqrt(vhat) + self.epsilon)
        return w, {"mean": m, "var": v}


@register
class AdamW(Adam):
    """Decoupled weight decay ≙ optimizer/adamW.py."""

    def _update(self, w, g, state, lr, wd, t):
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        v = self.beta2 * state["var"] + (1 - self.beta2) * g * g
        tf = t.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** tf).astype(w.dtype)
        vhat = v / (1 - self.beta2 ** tf).astype(w.dtype)
        lr = lr.astype(w.dtype)
        w = w - lr * (mhat / (jnp.sqrt(vhat) + self.epsilon) + wd.astype(w.dtype) * w)
        return w, {"mean": m, "var": v}


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.beta1, self.beta2 = beta1, beta2

    def init_state(self, w):
        return {"mean": jnp.zeros_like(w), "inf": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * state["inf"], jnp.abs(g))
        tf = t.astype(jnp.float32)
        lr_t = (lr / (1 - self.beta1 ** tf)).astype(w.dtype)
        w = w - lr_t * m / (u + 1e-8)
        return w, {"mean": m, "inf": u}


@register
class Nadam(Adam):
    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        v = self.beta2 * state["var"] + (1 - self.beta2) * g * g
        tf = t.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** tf).astype(w.dtype)
        ghat = g / (1 - self.beta1 ** tf).astype(w.dtype)
        vhat = v / (1 - self.beta2 ** tf).astype(w.dtype)
        m_bar = self.beta1 * mhat + (1 - self.beta1) * ghat
        w = w - lr.astype(w.dtype) * m_bar / (jnp.sqrt(vhat) + self.epsilon)
        return w, {"mean": m, "var": v}


@register
class AdaGrad(Optimizer):
    def __init__(self, learning_rate=0.01, eps=1e-7, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.float_eps = eps

    def init_state(self, w):
        return {"hist": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        hist = state["hist"] + g * g
        w = w - lr.astype(w.dtype) * g / (jnp.sqrt(hist) + self.float_eps)
        return w, {"hist": hist}


@register
class AdaDelta(Optimizer):
    def __init__(self, learning_rate=1.0, rho=0.9, epsilon=1e-5, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.rho, self.epsilon = rho, epsilon

    def init_state(self, w):
        return {"acc_g": jnp.zeros_like(w), "acc_d": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        acc_g = self.rho * state["acc_g"] + (1 - self.rho) * g * g
        delta = jnp.sqrt(state["acc_d"] + self.epsilon) / jnp.sqrt(acc_g + self.epsilon) * g
        acc_d = self.rho * state["acc_d"] + (1 - self.rho) * delta * delta
        return w - lr.astype(w.dtype) * delta, {"acc_g": acc_g, "acc_d": acc_d}


@register
class AdaBelief(Adam):
    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        diff = g - m
        v = self.beta2 * state["var"] + (1 - self.beta2) * diff * diff + self.epsilon
        tf = t.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** tf).astype(w.dtype)
        vhat = v / (1 - self.beta2 ** tf).astype(w.dtype)
        w = w - lr.astype(w.dtype) * mhat / (jnp.sqrt(vhat) + self.epsilon)
        return w, {"mean": m, "var": v}


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, rho=0.9, momentum=0.9,
                 epsilon=1e-8, centered=False, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.rho, self.momentum, self.epsilon, self.centered = rho, momentum, epsilon, centered

    def init_state(self, w):
        s = {"n": jnp.zeros_like(w)}
        if self.centered:
            s["g"] = jnp.zeros_like(w)
            s["delta"] = jnp.zeros_like(w)
        return s

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        n = self.rho * state["n"] + (1 - self.rho) * g * g
        lr = lr.astype(w.dtype)
        if self.centered:
            gm = self.rho * state["g"] + (1 - self.rho) * g
            delta = self.momentum * state["delta"] - lr * g / jnp.sqrt(n - gm * gm + self.epsilon)
            return w + delta, {"n": n, "g": gm, "delta": delta}
        return w - lr * g / (jnp.sqrt(n) + self.epsilon), {"n": n}


@register
class Ftrl(Optimizer):
    def __init__(self, learning_rate=0.1, lamda1=0.01, beta=1.0, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.lamda1, self.beta = lamda1, beta

    def init_state(self, w):
        return {"z": jnp.zeros_like(w), "n": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        lr = lr.astype(w.dtype)
        n_new = state["n"] + g * g
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(state["n"])) / lr
        z = state["z"] + g - sigma * w
        w = jnp.where(
            jnp.abs(z) > self.lamda1,
            -(z - jnp.sign(z) * self.lamda1) /
            ((self.beta + jnp.sqrt(n_new)) / lr + wd.astype(w.dtype)),
            0.0)
        return w, {"z": z, "n": n_new}


@register
class FTML(Optimizer):
    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def init_state(self, w):
        return {"d": jnp.zeros_like(w), "v": jnp.zeros_like(w),
                "z": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        tf = t.astype(jnp.float32)
        v = self.beta2 * state["v"] + (1 - self.beta2) * g * g
        lr = lr.astype(w.dtype)
        d = (1 - self.beta1 ** tf).astype(w.dtype) / lr * \
            (jnp.sqrt(v / (1 - self.beta2 ** tf).astype(w.dtype)) + self.epsilon)
        sigma = d - self.beta1 * state["d"]
        z = self.beta1 * state["z"] + (1 - self.beta1) * g - sigma * w
        return -z / d, {"d": d, "v": v, "z": z}


def _norm(x):
    return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))


@register
class LAMB(Optimizer):
    """Layer-wise adaptive moments ≙ optimizer/lamb.py (lamb ops
    optimizer_op.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def init_state(self, w):
        return {"mean": jnp.zeros_like(w), "var": jnp.zeros_like(w)}

    def _update(self, w, g, state, lr, wd, t):
        m = self.beta1 * state["mean"] + (1 - self.beta1) * g
        v = self.beta2 * state["var"] + (1 - self.beta2) * g * g
        if self.bias_correction:
            tf = t.astype(jnp.float32)
            mhat = m / (1 - self.beta1 ** tf).astype(w.dtype)
            vhat = v / (1 - self.beta2 ** tf).astype(w.dtype)
        else:
            mhat, vhat = m, v
        r = mhat / (jnp.sqrt(vhat) + self.epsilon) + wd.astype(w.dtype) * w
        w_norm = _norm(w)
        r_norm = _norm(r)
        ratio = jnp.where(jnp.logical_and(w_norm > 0, r_norm > 0),
                          w_norm / r_norm, 1.0)
        if self.lower_bound is not None:
            ratio = jnp.maximum(ratio, self.lower_bound)
        if self.upper_bound is not None:
            ratio = jnp.minimum(ratio, self.upper_bound)
        w = w - (lr * ratio).astype(w.dtype) * r
        return w, {"mean": m, "var": v}


@register
class LARS(SGD):
    """Layer-wise adaptive rate scaling ≙ optimizer/lars.py."""

    def __init__(self, learning_rate=0.1, momentum=0.9, eta=0.001,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate=learning_rate, momentum=momentum, **kw)
        self.eta, self.epsilon = eta, epsilon

    def _update(self, w, g, state, lr, wd, t):
        w_norm = _norm(w)
        g_norm = _norm(g)
        trust = jnp.where(
            jnp.logical_and(w_norm > 0, g_norm > 0),
            self.eta * w_norm / (g_norm + wd * w_norm + self.epsilon), 1.0)
        return super()._update(w, g, state, (lr * trust), wd, t)


@register
class LANS(LAMB):
    """LAMB + normalized gradients (optimizer/lans.py)."""

    def _update(self, w, g, state, lr, wd, t):
        g = g / (_norm(g).astype(w.dtype) + 1e-12)
        return super()._update(w, g, state, lr, wd, t)


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def init_state(self, w):
        if self.momentum != 0.0:
            return {"mom": jnp.zeros_like(w)}
        return {}

    def _update(self, w, g, state, lr, wd, t):
        lr = lr.astype(w.dtype)
        if self.momentum != 0.0:
            mom = self.momentum * state["mom"] - (1 - self.momentum) * g
            w = (1 - lr * self.wd_lh) * w + lr * jnp.sign(mom)
            return w, {"mom": mom}
        return (1 - lr * self.wd_lh) * w - lr * jnp.sign(g), state


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (optimizer/sgld.py)."""

    def init_state(self, w):
        return {"key": jax.random.PRNGKey(0)}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        key, sub = jax.random.split(jax.random.fold_in(state["key"], t))
        lr = lr.astype(w.dtype)
        noise = jax.random.normal(sub, w.shape, jnp.float32).astype(w.dtype)
        w = w - lr / 2 * g + jnp.sqrt(lr) * noise
        return w, {"key": key}


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (optimizer/dcasgd.py)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04, **kw):
        super().__init__(learning_rate=learning_rate, **kw)
        self.momentum, self.lamda = momentum, lamda

    def init_state(self, w):
        return {"mom": jnp.zeros_like(w), "prev": w}

    def _update(self, w, g, state, lr, wd, t):
        g = g + wd.astype(w.dtype) * w
        g = g + self.lamda * g * g * (w - state["prev"])
        mom = self.momentum * state["mom"] - lr.astype(w.dtype) * g
        return w + mom, {"mom": mom, "prev": w + mom}
