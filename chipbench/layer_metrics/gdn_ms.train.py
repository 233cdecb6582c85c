"""gdn_ms.train (ms a step): device self time of the instructions whose scope
lies under a gated-delta-net block (``layers/<i>/`` with ``L`` at place i of
the pattern: mixer, post-norm and residual add; forward, recomputation and
backward) over the traced steps.  From ``scope_reduce.py``; nothing without
a device trace or the step's HLO text."""


def read(evidence):
    by_kind, n = evidence.get("layer_kind_s"), evidence.get("steps")
    if not by_kind or not n or "L" not in by_kind:
        return None
    return 1e3 * by_kind["L"] / n
