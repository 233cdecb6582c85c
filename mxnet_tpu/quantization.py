"""INT8 post-training quantization — ≙ src/operator/quantization/ (N13)
+ python/mxnet/contrib/quantization.py (P14).

TPU-native design: int8×int8→int32 matmuls/convs run natively on the MXU
(`lax.dot_general` / `lax.conv_general_dilated` with
``preferred_element_type=jnp.int32``), replacing the reference's oneDNN
int8 primitives (CPU) and quantized_conv.cu (GPU). The user flow is the
reference's: calibrate on a few batches (minmax or entropy/KL —
quantization.py:190-278), then `quantize_net` swaps Dense/Conv2D blocks
for quantized twins holding pre-quantized int8 weights.

Symmetric int8 scheme (the reference's default for int8): q = round(x *
127 / T), T = calibrated threshold = max(|min|, |max|).  Weights carry
*per-output-channel* thresholds (the reference's channel-wise
quantization for conv/FC weights), so one badly-scaled filter doesn't
blow the precision budget of the whole layer.

Three calibration sources feed the activation thresholds:

- ``calib_data`` batches through the in-process ``_Collector`` (naive
  minmax or entropy/KL) — the original flow;
- a precomputed ``thresholds=`` dict (layer path → T);
- the native telemetry registry: :func:`observe_activations` hooks the
  quantizable layers during any ordinary scoring run and publishes
  ``quant.amax.<layer>`` gauges + ``quant.act.<layer>`` histograms;
  :func:`thresholds_from_telemetry` later turns a snapshot back into
  thresholds (minmax exactly, entropy via the same KL sweep) — so a
  serving host can calibrate from production traffic it was already
  metering.

The quantized twins route through ``ops/nn.py``'s ``quantized_dense`` /
``quantized_conv`` cached-call kernels (MXU int8×int8→int32, fused
dequant epilogue, Pallas int8 fast path where
``pallas_int8.decide_int8`` routes), and
``QuantizedConv2D.fused_forward`` slots into the
``fused_conv_bn_relu`` residual-block route so quantized
BasicBlock/Bottleneck forwards keep the single-pass epilogue.
"""
from __future__ import annotations

import functools

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from .ndarray import NDArray
from .numpy import _call
from .gluon import nn as _gnn
from .ops import nn as _nn

__all__ = ["quantize_v2", "dequantize", "quantize_net",
           "QuantizedDense", "QuantizedConv2D",
           "observe_activations", "thresholds_from_telemetry",
           "_get_optimal_threshold"]


# ----------------------------------------------------------------- op layer

def _threshold_scale(t):
    return 127.0 / jnp.maximum(t, 1e-12)


def quantize_v2(data, min_calib_range=None, max_calib_range=None,
                out_type="int8"):
    """≙ quantize_v2 (src/operator/quantization/quantize_v2.cc).

    Returns (quantized, min_range, max_range). Symmetric int8.
    """
    assert out_type == "int8", "TPU build quantizes to int8"

    def fn(x):
        if min_calib_range is None:
            t = jnp.max(jnp.abs(x))
        else:
            t = jnp.maximum(abs(float(min_calib_range)),
                            abs(float(max_calib_range)))
        s = _threshold_scale(t)
        q = jnp.clip(jnp.round(x * s), -127, 127).astype(jnp.int8)
        return q, -t, t
    return _call(fn, data, _no_grad=True)


def dequantize(qdata, min_range, max_range):
    """≙ dequantize (quantization/dequantize.cc)."""
    def fn(q, lo, hi):
        t = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
        return q.astype(jnp.float32) * (t / 127.0)
    return _call(fn, qdata, min_range, max_range, _no_grad=True)


# The int8 dense/conv compute kernels live in ops/nn.py
# (quantized_dense / quantized_conv): module-level cached_call targets
# keyed on the pallas dispatch fingerprint, so eager quantized forwards
# hit the executable cache and re-key on any precision/table flip.


def _channel_scales(w, axes):
    """Per-output-channel weight quantization: threshold = max|w| over
    ``axes`` (everything but the out-channel dim), scale = 127/T."""
    t_w = onp.maximum(onp.abs(w).max(axis=axes), 1e-8)
    return (127.0 / t_w).astype(onp.float32)


# ------------------------------------------------------------- calibration

def _get_optimal_threshold(arr, num_bins=1001, num_quantized_bins=255):
    """KL-optimal |x| threshold (≙ quantization.py _get_optimal_threshold /
    calibrate.cc entropy mode): sweep thresholds, minimise
    KL(clipped reference || quantized distribution)."""
    arr = onp.abs(onp.asarray(arr, dtype=onp.float64).ravel())
    amax = arr.max() if arr.size else 0.0
    if amax == 0.0:
        return 1e-8
    hist, _ = onp.histogram(arr, bins=num_bins, range=(0.0, amax))
    return _get_optimal_threshold_from_hist(hist, amax, num_bins,
                                            num_quantized_bins)


def _get_optimal_threshold_from_hist(hist, amax, num_bins=1001,
                                     num_quantized_bins=255):
    """The KL sweep over an |x| histogram spanning [0, amax] — the form
    the device-side calibration collector feeds (only the histogram
    crosses host<->device, never the activations)."""
    if amax == 0.0:
        return 1e-8
    hist = onp.asarray(hist, dtype=onp.float64)
    edges = onp.linspace(0.0, amax, num_bins + 1)
    best_kl, best_t = onp.inf, amax
    # sweep from num_quantized_bins..num_bins like the reference
    for i in range(num_quantized_bins, num_bins + 1,
                   max(1, (num_bins - num_quantized_bins) // 64)):
        t = edges[i] if i < len(edges) else amax
        p = hist[:i].copy()
        p[-1] += hist[i:].sum()          # clip outliers into last bin
        if p.sum() == 0:
            continue
        # quantize the i bins down to num_quantized_bins
        factor = i / num_quantized_bins
        q = onp.zeros(i)
        for j in range(num_quantized_bins):
            lo = int(onp.floor(j * factor))
            hi = int(onp.ceil((j + 1) * factor))
            chunk = hist[lo:hi]
            nz = (chunk > 0).sum()
            if nz:
                q[lo:hi][chunk > 0] = chunk[chunk > 0].sum() / nz
        if q.sum() == 0:
            continue
        pn = _smooth_distribution(p / p.sum())
        qn = _smooth_distribution(q / q.sum())
        if pn is None or qn is None:
            continue
        kl = (pn * onp.log(pn / qn)).sum()
        if kl < best_kl:
            best_kl, best_t = kl, t
    return float(best_t)


def _smooth_distribution(p, eps=0.0001):
    """≙ quantization.py _smooth_distribution: move eps mass onto zero bins
    so KL is finite and clipping penalised."""
    is_zeros = p == 0
    n_zeros = int(is_zeros.sum())
    n_nonzeros = p.size - n_zeros
    if n_nonzeros == 0:
        return None
    eps1 = eps * n_zeros / n_nonzeros
    out = p.astype(onp.float64).copy()
    out[is_zeros] = eps
    out[~is_zeros] -= eps1
    if (out[~is_zeros] <= 0).any():
        return None
    return out


class _Collector:
    """Accumulate per-layer calibration statistics ON DEVICE.

    The first version fetched every hooked activation to host
    (``asnumpy`` per layer per batch) — ~50 MB per conv input moved
    off the device for ResNet-50.  Instead the hook
    reduces on device — a running max |x| scalar (naive), plus a
    ``_NUM_BINS``-bin histogram of |x| over the batch's own range
    (entropy) — and ``threshold()`` fetches only scalars/small vectors.
    """

    _NUM_BINS = 1001       # matches _get_optimal_threshold's grid

    def __init__(self, mode):
        self.mode = mode
        self.amax = {}      # key -> device scalar, running max |x|
        self.hists = {}     # key -> list of (device hist, device amax)

    def add(self, key, x):
        data = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        a = jnp.max(jnp.abs(data)).astype(jnp.float32)
        prev = self.amax.get(key)
        self.amax[key] = a if prev is None else jnp.maximum(prev, a)
        if self.mode == "entropy":
            h = _abs_hist(data, a, self._NUM_BINS)
            self.hists.setdefault(key, []).append((h, a))

    def threshold(self, key):
        amax = float(self.amax[key])
        if self.mode != "entropy":
            return amax                       # naive minmax (exact)
        if amax == 0.0:
            return 1e-8
        # merge per-batch histograms (each over its OWN [0, amax_b]
        # range) onto the global [0, amax] grid by bin centers — the
        # only host transfer is num_bins floats per calibration batch
        n = self._NUM_BINS
        merged = onp.zeros(n, onp.float64)
        for h, a in self.hists[key]:
            hb = onp.asarray(h, dtype=onp.float64)
            ab = float(a)
            if ab == 0.0:
                merged[0] += hb.sum()
                continue
            centers = (onp.arange(n) + 0.5) * (ab / n)
            idx = onp.minimum((centers / amax * n).astype(onp.int64),
                              n - 1)
            onp.add.at(merged, idx, hb)
        return _get_optimal_threshold_from_hist(merged, amax)


@functools.partial(jax.jit, static_argnums=(2,))
def _abs_hist(data, amax, num_bins):
    """Histogram of |data| over [0, amax] with num_bins bins, on device."""
    a = jnp.abs(data).ravel()
    scale = jnp.where(amax > 0, num_bins / jnp.maximum(amax, 1e-30), 0.0)
    idx = jnp.clip((a * scale).astype(jnp.int32), 0, num_bins - 1)
    # int32 counts: float32 scatter-adds stop incrementing at 2^24,
    # silently undercounting the dominant (zero) bin of big activations
    return jnp.zeros(num_bins, jnp.int32).at[idx].add(1)


# ------------------------------------------- telemetry-sourced calibration

_Q_FIX = 1e6        # fixed-point scale mapping |x| onto the µs bucket grid


def _telemetry():
    from . import telemetry
    return telemetry


class _ObserveHandle:
    """Uninstaller for :func:`observe_activations` hooks."""

    def __init__(self):
        self._sites = []
        self._amax = {}     # layer path -> running host max |x|

    def remove(self):
        for child, orig in self._sites:
            child.forward = orig
        self._sites = []


def observe_activations(net, layers=None, sample=None):
    """Hook every quantizable layer (the same sites ``quantize_net``
    targets) to publish per-layer activation statistics into the native
    telemetry registry during an ordinary scoring run:

    - ``quant.amax.<layer>`` gauge — running max |x| in fixed point
      (×1e6), so the minmax threshold survives the int-valued registry
      exactly (1e-6 resolution);
    - ``quant.act.<layer>`` histogram — a strided |x| subsample (default
      512 elements/batch, ``MXNET_QUANT_SAMPLE``) scaled ×1e6 onto the
      registry's fixed bucket grid, enough mass for the entropy sweep;
    - ``quant.calib.batches`` counter — one per hooked layer per batch.

    Returns a handle whose ``remove()`` restores the original forwards.
    Feed a later snapshot to :func:`thresholds_from_telemetry` to get
    the per-layer thresholds back out.
    """
    import os
    if sample is None:
        sample = int(os.environ.get("MXNET_QUANT_SAMPLE", "") or 512)
    handle = _ObserveHandle()
    for _, child, path in _walk(net):
        if not isinstance(child, _QUANTIZABLE):
            continue
        if layers is not None and path not in layers:
            continue
        orig = child.forward

        def hooked(x, _f=orig, _p=path):
            _observe_one(handle, _p, x, sample)
            return _f(x)
        child.forward = hooked
        handle._sites.append((child, orig))
    return handle


def _observe_one(handle, path, x, sample):
    tele = _telemetry()
    data = x._data if isinstance(x, NDArray) else jnp.asarray(x)
    a = jnp.abs(data).ravel()
    # two small host transfers per layer per batch: the scalar amax and
    # the strided subsample — never the full activation
    amax = float(jnp.max(a))
    run = max(handle._amax.get(path, 0.0), amax)
    handle._amax[path] = run
    tele.gauge_set(f"quant.amax.{path}", int(round(run * _Q_FIX)))
    stride = max(1, a.size // sample)
    sub = onp.asarray(a[::stride][:sample], dtype=onp.float64)
    for v in sub:
        tele.observe(f"quant.act.{path}", v * _Q_FIX)
    tele.counter_add("quant.calib.batches", 1)


def thresholds_from_telemetry(layers=None, mode="naive", snap=None):
    """Per-layer activation thresholds from a telemetry snapshot written
    by :func:`observe_activations` (pass ``snap=`` to calibrate from a
    serialized/remote snapshot; default reads the live registry).

    ``naive``: ``quant.amax.<layer>`` / 1e6 — exact parity with the
    in-process minmax collector.  ``entropy``: the ``quant.act.<layer>``
    fixed-bucket histogram is expanded onto the linear 1001-bin KL grid
    (mass spread uniformly within each bucket) and swept by the same
    ``_get_optimal_threshold_from_hist`` the direct path uses.
    """
    raw = snap if snap is not None else _telemetry().raw_snapshot()
    gauges = raw.get("gauges", {})
    hists = raw.get("histograms", {})
    out = {}
    for key in sorted(gauges):
        if not key.startswith("quant.amax."):
            continue
        layer = key[len("quant.amax."):]
        if layers is not None and layer not in layers:
            continue
        amax = float(gauges[key]) / _Q_FIX
        if mode != "entropy" or amax <= 0.0:
            out[layer] = amax if amax > 0.0 else 1e-8
            continue
        h = hists.get(f"quant.act.{layer}")
        out[layer] = _threshold_from_bucket_hist(h, amax) if h else amax
    return out


def _threshold_from_bucket_hist(h, amax, num_bins=1001):
    """Geometric registry buckets (``le`` bounds in fixed point) →
    linear [0, amax] histogram → the existing KL sweep.  Each bucket's
    count is spread uniformly over the linear bins it covers; the
    overflow bucket clips into the last bin."""
    le = [float(b) / _Q_FIX for b in h.get("le", ())]
    counts = list(h.get("counts", ()))
    if not counts or sum(counts) == 0:
        return amax
    lin = onp.zeros(num_bins, onp.float64)
    width = amax / num_bins
    lo = 0.0
    for bound, c in zip(le, counts):
        hi = min(bound, amax)
        if c and hi > lo:
            i0 = min(int(lo / width), num_bins - 1)
            i1 = min(max(int(onp.ceil(hi / width)), i0 + 1), num_bins)
            lin[i0:i1] += c / (i1 - i0)
        lo = bound
        if lo >= amax:
            break
    if len(counts) > len(le) and counts[len(le)]:
        lin[-1] += counts[len(le)]          # +inf overflow bucket
    if lin.sum() == 0:
        return amax
    return min(_get_optimal_threshold_from_hist(lin, amax), amax)


# -------------------------------------------------------- quantized blocks

class QuantizedDense(_gnn.HybridBlock):
    """int8 twin of gluon.nn.Dense (≙ _contrib_quantized_fully_connected).

    Weights are stored pre-quantized int8 with per-output-channel scales,
    transposed to (in, units) so the runtime dot is a plain MXU matmul.
    The forward is a stable cached-call target (``ops.nn.quantized_dense``
    with NDArray positionals), so eager scoring hits the executable cache
    instead of retracing a per-call closure."""

    def __init__(self, dense, in_threshold, **kwargs):
        super().__init__(**kwargs)
        w = dense.weight.data().asnumpy()            # (units, in)
        s_w = _channel_scales(w, axes=1)             # (units,)
        self._w_scale = NDArray(jnp.asarray(s_w))
        self._qw = NDArray(jnp.asarray(
            onp.clip(onp.round(w * s_w[:, None]), -127, 127)
            .astype(onp.int8).T))
        self._bias = (NDArray(jnp.asarray(dense.bias.data().asnumpy()
                                          .astype(onp.float32)))
                      if dense.bias is not None else None)
        self._in_t = float(in_threshold)
        self._flatten = dense._flatten
        self._act = dense.act

    def forward(self, x):
        return _call(_nn.quantized_dense, x, self._qw, self._w_scale,
                     self._bias, in_t=self._in_t, flatten=self._flatten,
                     act=self._act, _no_grad=True)


class QuantizedConv2D(_gnn.HybridBlock):
    """int8 twin of gluon.nn.Conv2D (≙ _contrib_quantized_conv), with
    per-output-channel weight scales and a :meth:`fused_forward` that
    carries the residual-block epilogue (dequant + folded-BN bias +
    residual add + ReLU) into a single kernel pass — the quantized leg of
    ``fused_conv_bn_relu``."""

    # duck-typed marker: gluon's fused_conv_bn_relu routes here instead
    # of reading Conv2D/BatchNorm attributes the twin doesn't have
    _mx_quantized_fused = True

    def __init__(self, conv, in_threshold, **kwargs):
        super().__init__(**kwargs)
        w = conv.weight.data().asnumpy()             # HWIO
        s_w = _channel_scales(w, axes=(0, 1, 2))     # (Cout,)
        self._w_scale = NDArray(jnp.asarray(s_w))
        self._qw = NDArray(jnp.asarray(
            onp.clip(onp.round(w * s_w), -127, 127).astype(onp.int8)))
        self._bias = (NDArray(jnp.asarray(conv.bias.data().asnumpy()
                                          .astype(onp.float32)))
                      if conv.bias is not None else None)
        self._in_t = float(in_threshold)
        self._stride = conv._strides if isinstance(conv._strides, tuple) \
            else (conv._strides,) * 2
        pad = conv._padding
        self._pad = pad if isinstance(pad, tuple) else (pad,) * 2
        dil = conv._dilation
        self._dilate = dil if isinstance(dil, tuple) else (dil,) * 2
        self._groups = conv._groups
        self._act = conv.act

    def forward(self, x):
        return _call(_nn.quantized_conv, x, self._qw, self._w_scale,
                     self._bias, None, in_t=self._in_t,
                     stride=self._stride, pad=self._pad,
                     dilate=self._dilate, groups=self._groups,
                     act=self._act, _no_grad=True)

    def fused_forward(self, x, residual=None, relu=True):
        """The fused residual-block route: conv + dequant + bias (already
        the folded-BN affine after ``_fold_batchnorm``) + optional
        residual add + ReLU in one kernel pass (Pallas int8 epilogue on
        the routed stages)."""
        return _call(_nn.quantized_conv, x, self._qw, self._w_scale,
                     self._bias, residual, in_t=self._in_t,
                     stride=self._stride, pad=self._pad,
                     dilate=self._dilate, groups=self._groups,
                     relu=relu, _no_grad=True)


# ------------------------------------------------------------------ driver

_QUANTIZABLE = (_gnn.Dense, _gnn.Conv2D)


def _walk(block, prefix="", visited=None):
    visited = set() if visited is None else visited
    for name, child in list(vars(block).items()):
        if isinstance(child, _gnn.Block) and id(child) not in visited:
            visited.add(id(child))
            yield block, child, f"{prefix}{name}"
            yield from _walk(child, f"{prefix}{name}.", visited)


def _replace(parent, old, new):
    """Swap `old` for `new` in every storage slot of `parent` (attribute
    and Sequential._layers list)."""
    for name, val in list(vars(parent).items()):
        if val is old:
            setattr(parent, name, new)
    layers = getattr(parent, "_layers", None)
    if layers is not None:
        parent._layers = [new if c is old else c for c in layers]


class _Identity(_gnn.HybridBlock):
    """Placeholder for a BatchNorm folded into the preceding conv."""

    def forward(self, x):
        return x


def _fold_batchnorm(net):
    """Fold Conv2D→BatchNorm pairs (scoring mode): the BN affine collapses
    into the conv's weight/bias, the BN becomes identity — ≙ the
    reference's quantize fusion folding BN into _contrib_quantized_conv
    (quantize_graph_pass.cc / dnnl conv-bn fusion). Run BEFORE
    quantization so the int8 conv carries the folded parameters and no
    f32 BN pass remains between quantized layers."""
    containers = [net] + [c for _, c, _ in _walk(net)]
    for cont in containers:
        layers = getattr(cont, "_layers", None)
        if not layers:
            continue
        for i in range(len(layers) - 1):
            conv, bn = layers[i], layers[i + 1]
            if not (isinstance(conv, _gnn.Conv2D) and
                    isinstance(bn, _gnn.BatchNorm)):
                continue
            if conv.act is not None:
                # fused activation runs BEFORE the BN — folding would move
                # the affine to the wrong side of the nonlinearity
                continue
            if bn.gamma._data is None or conv.weight._data is None:
                continue    # deferred shapes: caller never ran a forward
            gamma = bn.gamma.data().asnumpy()
            beta = bn.beta.data().asnumpy()
            mean = bn.running_mean.data().asnumpy()
            var = bn.running_var.data().asnumpy()
            scale = gamma / onp.sqrt(var + bn._eps)
            w = conv.weight.data().asnumpy()          # HWIO, C_out last
            conv.weight.set_data(NDArray(jnp.asarray(w * scale)))
            b0 = conv.bias.data().asnumpy() if conv.bias is not None \
                else onp.zeros_like(beta)
            new_b = beta + (b0 - mean) * scale
            if conv.bias is not None:
                conv.bias.set_data(NDArray(jnp.asarray(new_b)))
            else:
                from .gluon.parameter import Parameter
                p = Parameter("bias", shape=new_b.shape, dtype="float32")
                p.set_data(NDArray(jnp.asarray(new_b)))
                conv.bias = p
            _replace(cont, bn, _Identity())
    return net


# the calibration hooks ride the per-layer python forwards, which the
# fused residual-block route (fused_conv_bn_relu) would pass by
@_gnn._layerwise_forwards()
def quantize_net(net, calib_data=None, calib_mode="naive",
                 quantized_dtype="int8", exclude_layers=None,
                 fold_bn=True, thresholds=None, logger=None):
    """≙ contrib.quantization.quantize_net (quantization.py:~800).

    Mutates `net` in place: Conv2D→BatchNorm pairs fold first
    (`fold_bn`), then every Dense/Conv2D (except excluded) becomes a
    Quantized* twin calibrated from `calib_data` batches — or from a
    precomputed ``thresholds`` dict (layer path → T), e.g. the output of
    :func:`thresholds_from_telemetry`, in which case no calibration
    forwards run (calib_data may still supplement layers the dict
    misses). Returns net.
    """
    assert quantized_dtype == "int8"
    assert calib_mode in ("naive", "entropy", "none")
    exclude = set(exclude_layers or [])
    thresholds = dict(thresholds or {})
    if calib_mode != "none" and calib_data is None and not thresholds:
        # validate BEFORE any mutation (the BN fold below rewrites weights)
        raise ValueError(
            f"calib_mode={calib_mode!r} needs calib_data or thresholds")
    first_batch = None
    if calib_data is not None:
        # peel the first batch for the shape-resolving forward without
        # buffering a streaming loader; re-chain it for calibration
        import itertools
        it = iter(calib_data)
        first_batch = next(it, None)
        calib_data = itertools.chain(
            [first_batch], it) if first_batch is not None else []

    # hybridized blocks execute a cached jit, bypassing python forwards —
    # deactivate hybrid caching for the WHOLE rewrite (fold + calibrate +
    # replace); stale fp32 caches are cleared on both sides
    hybrid_state = []
    for blk in [net] + [c for _, c, _ in _walk(net)]:
        if getattr(blk, "_active", False):
            hybrid_state.append(blk)
            blk._active = False
            if hasattr(blk, "_clear_cache"):
                blk._clear_cache()

    try:
        if fold_bn:
            if first_batch is not None:
                # one forward materializes deferred parameter shapes so
                # the fold sees real BN statistics
                x0 = first_batch
                x0 = x0[0] if isinstance(x0, (tuple, list)) else x0
                if not isinstance(x0, NDArray):
                    x0 = NDArray(jnp.asarray(onp.asarray(x0)))
                net(x0)
            _fold_batchnorm(net)

        sites = []
        for parent, child, path in _walk(net):
            if isinstance(child, _QUANTIZABLE) and path not in exclude:
                sites.append((parent, child, path))
        if not sites:
            return net

        collector = _Collector(
            "entropy" if calib_mode == "entropy" else "naive")
        uncovered = [s for s in sites if s[2] not in thresholds]
        if calib_mode != "none" and uncovered and calib_data is None:
            raise ValueError(
                "thresholds= misses layer(s) "
                f"{[p for _, _, p in uncovered]} and no calib_data given")
        if calib_mode != "none" and uncovered and calib_data is not None:
            # hook each still-uncalibrated layer's forward to record its
            # input (layers covered by thresholds= skip the pass)
            originals = {}
            for _, child, path in uncovered:
                originals[path] = child.forward

                def hooked(x, _f=originals[path], _p=path):
                    collector.add(_p, x)
                    return _f(x)
                child.forward = hooked
            try:
                for batch in calib_data:
                    x = batch[0] if isinstance(batch, (tuple, list)) \
                        else batch
                    if not isinstance(x, NDArray):
                        x = NDArray(jnp.asarray(onp.asarray(x)))
                    net(x)
            finally:
                for _, child, path in uncovered:
                    child.forward = originals[path]

        for parent, child, path in sites:
            if path in thresholds:
                t = float(thresholds[path])
            else:
                t = collector.threshold(path) if calib_mode != "none" \
                    else 1.0
            qblock = (QuantizedDense(child, t)
                      if isinstance(child, _gnn.Dense)
                      else QuantizedConv2D(child, t))
            _replace(parent, child, qblock)
    finally:
        for blk in hybrid_state:
            blk._active = True
            if hasattr(blk, "_clear_cache"):
                blk._clear_cache()   # old cache captured fp32 layers
    return net


# --------------------------------------------------------------- selfcheck

def _selfcheck():     # pragma: no cover - exercised by `make int8-check`
    """``make int8-check`` gate (CPU, Pallas in interpret mode):

    1. int8 Pallas implicit-GEMM vs XLA int8 fallback parity, with and
       without the residual+ReLU epilogue;
    2. quantize a small seeded fused-residual net (BasicBlockV1 on the
       routed ``14x14x256`` stage, ``one_tpu`` held true off the chip):
       quantized-vs-float within tolerance, argmax agreement ≥ 0.9, and
       the ``quant.int8.hits.<stage>`` counter moved;
    3. serving engine at ``precision="int8"``: ladder outputs sane, 0
       post-warmup retraces, and re-registering under
       ``MXNET_SERVE_PRECISION=int8`` counts a fresh
       ``serve.precision.builds.int8``.
    """
    import os

    import mxnet_tpu as mx
    from . import telemetry as _tele
    from .ops import pallas_block as _pb
    from .ops import pallas_int8 as _pi8
    from .models.resnet import BasicBlockV1
    from .serve import ModelRegistry

    saved_precision = os.environ.pop("MXNET_SERVE_PRECISION", None)
    one_tpu, _pb.one_tpu = _pb.one_tpu, lambda: True
    rng = onp.random.RandomState(0)
    try:
        # (1) kernel parity: pallas interpret vs XLA composition
        qx = jnp.asarray(rng.randint(-127, 128, (2, 8, 8, 8))
                         .astype(onp.int8))
        qw = jnp.asarray(rng.randint(-127, 128, (3, 3, 8, 16))
                         .astype(onp.int8))
        scale = jnp.asarray((rng.rand(16) * 1e-3 + 1e-4)
                            .astype(onp.float32))
        shift = jnp.asarray(rng.randn(16).astype(onp.float32) * 0.1)
        res = jnp.asarray(rng.randn(2, 8, 8, 16).astype(onp.float32))
        for kw in ({"relu": False}, {"relu": True},
                   {"res": res, "relu": True}):
            a = onp.asarray(_pi8.qconv3x3_affine(qx, qw, scale, shift,
                                                 **kw))
            b = onp.asarray(_pi8.qconv3x3_xla(qx, qw, scale, shift, **kw))
            err = onp.abs(a - b).max()
            assert err < 1e-4, f"pallas/xla int8 parity {kw}: {err}"
        print("int8-check: pallas vs xla parity ok")

        # (2) quantized fused-residual net, routed through the kernel
        mx.seed(0)
        net = _gnn.HybridSequential()
        net.add(_gnn.Conv2D(256, 3, padding=1), _gnn.BatchNorm(),
                _gnn.Activation("relu"))
        net.add(BasicBlockV1(256, stride=1))
        net.add(_gnn.Flatten(), _gnn.Dense(10))
        net.initialize()
        calib = [NDArray(jnp.asarray(
            rng.rand(2, 14, 14, 3).astype("float32")))
            for _ in range(2)]
        xt = NDArray(jnp.asarray(
            rng.rand(16, 14, 14, 3).astype("float32")))
        ref = net(xt).asnumpy()
        quantize_net(net, calib_data=calib, calib_mode="naive")
        blocks = [c for _, c, _ in _walk(net)]
        assert any(isinstance(b, QuantizedConv2D) for b in blocks)
        h0 = _tele.raw_snapshot()["counters"].get(
            "quant.int8.hits.14x14x256", 0)
        out = net(xt).asnumpy()
        h1 = _tele.raw_snapshot()["counters"].get(
            "quant.int8.hits.14x14x256", 0)
        assert h1 > h0, "fused route never hit the int8 pallas kernel"
        rel = onp.abs(out - ref).mean() / (onp.abs(ref).mean() + 1e-9)
        assert rel < 0.1, f"quantized-vs-float rel err {rel}"
        agree = (out.argmax(1) == ref.argmax(1)).mean()
        assert agree >= 0.9, f"argmax agreement {agree}"
        print(f"int8-check: fused quantized net ok "
              f"(rel={rel:.4f}, agree={agree:.2f}, "
              f"pallas hits +{h1 - h0})")

        # (3) serving engine at precision=int8: 0 post-warmup retraces
        mx.seed(1)
        srv = _gnn.HybridSequential()
        srv.add(_gnn.Dense(16, activation="relu"), _gnn.Dense(4))
        srv.initialize()
        srv(NDArray(jnp.zeros((1, 8), jnp.float32)))
        with ModelRegistry(buckets=(1, 2)) as reg:
            entry = reg.register("m", srv, item_shape=(8,),
                                 precision="int8")
            assert entry.engine.precision == "int8"
            for n in (1, 2, 1, 2):
                y = reg.predict("m", onp.asarray(
                    rng.rand(n, 8), onp.float32))[0]
                assert onp.asarray(y).shape == (n, 4)
            st = entry.engine.stats()
            assert st["precision"] == "int8"
            assert st["retraces"] == 0, st
            print("int8-check: int8 serving ok (0 retraces)")

            os.environ["MXNET_SERVE_PRECISION"] = "int8"
            b0 = _tele.raw_snapshot()["counters"].get(
                "serve.precision.builds.int8", 0)
            reg.register("m", srv, item_shape=(8,))  # env default now
            b1 = _tele.raw_snapshot()["counters"].get(
                "serve.precision.builds.int8", 0)
            assert b1 > b0, "re-register did not rebuild at int8"
        print("int8-check: the env default builds at int8")
    finally:
        _pb.one_tpu = one_tpu
        if saved_precision is None:
            os.environ.pop("MXNET_SERVE_PRECISION", None)
        else:
            os.environ["MXNET_SERVE_PRECISION"] = saved_precision
    print("quantization selfcheck ok")
    return 0
