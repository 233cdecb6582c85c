"""swa_ms.train (ms a step): device self time of the instructions whose scope
lies under a sliding-window attention block (``layers/<i>/`` with ``W`` at
place i of the pattern: both norms, the projections, QK-norm, rotary
embedding, the windowed kernels, gate and residual add; forward,
recomputation and backward) over the traced steps.  From
``scope_reduce.py``; nothing without a device trace, the step's HLO text or
such a block."""


def read(evidence):
    by_kind, n = evidence.get("layer_kind_s"), evidence.get("steps")
    if not by_kind or not n or "W" not in by_kind:
        return None
    return 1e3 * by_kind["W"] / n
