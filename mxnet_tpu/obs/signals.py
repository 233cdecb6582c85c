"""Derived health signals — raw registry metrics → the numbers an
operator actually pages on (docs/observability.md has the formulas,
units and caveats).

Every signal is computed per recorder frame from the frame's windowed
rates/delta-quantiles, returned as floats for reports/bench artifacts,
and mirrored into the registry as fixed-point ``obs.*_ppm`` gauges
(parts-per-million — the registry stores int64) so ``/metrics``,
``tools/diagnose.py`` and bench rows all see them:

* ``input_stall_frac`` — µs the consumer spent waiting on the feed
  (``datafeed.wait_us``) per µs of step-to-step interval
  (``fused.step_gap_us``: the whole loop, not the launch) in the
  window; the share of the loop's time spent waiting for input.
* ``ckpt_pause_frac`` — ``checkpoint.pause_us`` per µs of the same
  interval.  Neither is reported for a frame without
  ``fused.step_gap_us`` (no second step since the first or since a
  ``sync()``; the histogram is kept under MXNET_TELEMETRY, as the frame
  is, whatever MXNET_TRACE says): the launch alone (``fused.step_us``)
  is 1/30 to 1/280 of a step and no denominator.
* ``goodput`` — (admitted − rejected − abandoned) / offered request
  rate, clamped to [0, 1]; present only when the window offered load.
* ``mfu`` — ``obs.model_flops_per_step`` (published by the fused
  trainer via :func:`publish_model_flops`, 3× analytic forward FLOPs)
  × step rate ÷ the ``MXNET_OBS_PEAK_FLOPS`` rig constant.
* ``retrace_rate`` / ``queue_frac`` / ``steps_per_s`` — watchdog fuel.

``straggler_skew`` (relative spread of per-rank step-time p50s) needs
more than one process, so it is computed by the fleet aggregator
(tools/obs.py report), not here.
"""
from __future__ import annotations

import math
import os
from typing import Dict, Optional

from .. import telemetry as _telemetry

__all__ = ["compute", "publish", "publish_model_flops", "peak_flops"]

PPM = 1e6


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def peak_flops() -> float:
    """The rig constant MFU is measured against (0 = unset → no MFU).
    This is the PEAK of the part you run on — set it per rig; a wrong
    constant scales every MFU number by the same wrong factor."""
    return _env_float("MXNET_OBS_PEAK_FLOPS", 0.0)


def _win_sum_us(q: Optional[dict]) -> float:
    """µs accumulated in the window by one delta-quantile entry."""
    if not q:
        return 0.0
    return float(q.get("mean_us", 0.0)) * float(q.get("rate", 0.0))


def compute(frame: dict) -> Dict[str, float]:
    """Signals for one recorder frame (see module docstring); keys are
    present only when their inputs are — a report must distinguish
    'no serving tier' from 'goodput 0'."""
    rates = frame.get("rates", {})
    quants = frame.get("quantiles", {})
    gauges = frame.get("gauges", {})
    out: Dict[str, float] = {}

    step_q = quants.get("fused.step_us")
    if step_q:
        out["steps_per_s"] = float(step_q.get("rate", 0.0))
        if step_q.get("p50_us") is not None:
            out["step_p50_us"] = float(step_q["p50_us"])
    # µs of step-to-step interval per second of the window
    gap_us_per_s = _win_sum_us(quants.get("fused.step_gap_us"))
    if gap_us_per_s > 0.0:
        out["input_stall_frac"] = \
            _win_sum_us(quants.get("datafeed.wait_us")) / gap_us_per_s
        out["ckpt_pause_frac"] = \
            _win_sum_us(quants.get("checkpoint.pause_us")) / gap_us_per_s

    offered = rates.get("serve.requests", 0.0)
    if offered > 0.0:
        good = (rates.get("serve.admitted", 0.0)
                - rates.get("serve.rejected", 0.0)
                - rates.get("serve.abandoned", 0.0))
        out["goodput"] = min(max(good / offered, 0.0), 1.0)

    out["retrace_rate"] = (rates.get("fused.retraces", 0.0)
                           + rates.get("serve.retraces", 0.0))

    depth = gauges.get("serve.queue_depth")
    if depth is not None:
        cap = max(_env_float("MXNET_SERVE_QUEUE_DEPTH", 256.0), 1.0)
        out["queue_frac"] = float(depth) / cap

    flops_step = gauges.get("obs.model_flops_per_step")
    peak = peak_flops()
    if flops_step and peak > 0.0 and step_q:
        out["mfu"] = float(flops_step) * float(step_q["rate"]) / peak

    return {k: v for k, v in out.items() if math.isfinite(v)}


# gauge name ↔ signal key; ppm fixed point (gauges are int64)
_PPM_GAUGES = {
    "input_stall_frac": "obs.input_stall_ppm",
    "ckpt_pause_frac": "obs.ckpt_pause_ppm",
    "goodput": "obs.goodput_ppm",
    "mfu": "obs.mfu_ppm",
    "queue_frac": "obs.queue_frac_ppm",
}


def publish(sig: Dict[str, float]):
    """Mirror one frame's signals into obs.* registry gauges."""
    for key, gname in _PPM_GAUGES.items():
        v = sig.get(key)
        if v is not None:
            _telemetry.gauge_set(gname, int(round(v * PPM)))


def publish_model_flops(net, *example_inputs) -> Optional[int]:
    """Price one training step of `net` analytically and publish it as
    the ``obs.model_flops_per_step`` gauge: 3 × the forward-pass FLOPs
    from ``HybridBlock.flops()`` (the standard fwd + ~2× bwd accounting
    MFU uses).  Returns the per-step FLOPs, or None when the net cannot
    be priced (never raises — observability must not fail training)."""
    try:
        fwd = net.flops(*example_inputs)
    except Exception:
        return None
    if not fwd:
        return None
    per_step = 3 * int(fwd)
    _telemetry.gauge_set("obs.model_flops_per_step", per_step)
    return per_step
