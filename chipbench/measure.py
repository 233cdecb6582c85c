"""The measured part of a training cell, for the runners that came after
``runners/train.py`` (``train_mesh``, ``train_lm``): warm-up, then either the
plain window of ``cell["seconds"]`` or ``mix["trace_steps"]`` traced steps
between two drains, then the evidence every accepted reader reads.

It is ``train.run``'s second half with three things left to the caller: how
many samples a step holds, which devices' allocators are asked for the peak
(the fullest counts), and the context manager that profiles the window
(``trace_reduce.profiled``, or one that keeps more of the trace).  ``base``
is ``runners/train.py`` as ``files.load_module`` gave it: its ``loop``,
``Compiles``, ``counters``, ``delta``, ``peak_bytes`` and ``required_flops``
do the work, so both kinds of cell are timed by the same code.
"""
from __future__ import annotations

import math

import numpy as onp

from chipbench import trace_reduce


def train_window(cell, base, step, ring, batch, devices,
                 profiled=trace_reduce.profiled):
    """Warm ``step`` up on ``ring``, measure, and return ``(evidence, warm)``:
    the evidence of ``train.run`` with the checks that hold for any fused
    training step (finite, falling, one dispatch a step, nothing compiled in
    the window, fused path) and the warm-up losses, to which the caller adds
    the checks of its own (reference, placement)."""
    from jax.profiler import TraceAnnotation

    mix = cell["mix"]
    compiles = base.Compiles()
    c0 = base.counters()
    _, _, _, warm = base.loop(step, ring, steps=mix["warmup_steps"])
    step.sync()
    c1 = base.counters()
    base.say(cell, "warm-up losses " + " ".join(f"{l:.4f}" for l in warm))

    evidence = {"trace": None}
    seen, cw = compiles.n, base.counters()
    if cell["trace"]:
        with profiled(evidence):
            step.sync()
            with TraceAnnotation(trace_reduce.WINDOW):
                begin, done, dispatch, losses = base.loop(
                    step, ring, steps=mix["trace_steps"])
                step.sync()
        base.say(cell, f"traced {len(done)} steps, "
                 f"{evidence.get('trace_bytes', 0)} bytes of trace")
    else:
        begin, done, dispatch, losses = base.loop(step, ring,
                                                  seconds=cell["seconds"])
    ca, in_window = base.counters(), compiles.n - seen
    peaks = [base.peak_bytes(d) for d in devices]
    memory_peak, stats = max(peaks, key=lambda p: p[0])
    base.say(cell, f"peak {memory_peak} bytes on the fullest of "
             f"{len(devices)} device(s); allocator {stats}")
    window = done[-1] - begin
    gaps = onp.diff(done)
    base.say(cell, f"{len(done)} steps in {window:.3f}s, losses "
             f"{losses[0]:.4f} .. {losses[-1]:.4f}; longest gaps (ms, after "
             "step) " + " ".join(f"{1e3 * gaps[i]:.1f}@{i}"
                                 for i in onp.argsort(gaps)[:-4:-1]))

    n = len(done)
    bad = sum(1 for l in losses if not math.isfinite(l))
    retraces = (base.delta(ca, cw, "fused.retraces")
                + base.delta(ca, cw, "fused.fallbacks") + in_window)
    dispatched = base.delta(ca, cw, "fused.dispatches")
    evidence.update({
        "checks": [
            (f"every loss finite ({bad} of {n} not)", bad == 0 and
             all(math.isfinite(l) for l in warm)),
            ("mean of the last five losses below the mean of the first five",
             n >= 10 and onp.mean(losses[-5:]) < onp.mean(losses[:5])),
            (f"one fused dispatch per step ({dispatched} for {n})",
             dispatched == n),
            (f"0 retraces, fallbacks and compilations in the window "
             f"(saw {retraces})", retraces == 0),
            ("fused path taken (fallback_reason empty)",
             not getattr(step, "fallback_reason", None)),
        ],
        "attempted": len(dispatch), "failed": bad,
        "end_to_end": {
            "train_samples_s": n * batch / window,
            "step_p95_ms": float(onp.percentile(gaps, 95)) * 1e3,
            "setup_s": begin - cell["t0"],
        },
        "memory_peak_bytes": memory_peak,
        "batch": batch, "steps": n, "dispatch_s": dispatch,
        "retraces": retraces,
        "pallas_routes": sum(base.delta(c1, c0, k) for k in c1
                             if k.startswith("dispatch.pallas.hits.")),
        "flops_per_sample": base.required_flops(cell),
        "peaks": cell["peaks"],
        "counters": {"warm": (c0, c1), "window": (cw, ca)},
    })
    return evidence, warm
