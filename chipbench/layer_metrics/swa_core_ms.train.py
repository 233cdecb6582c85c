"""swa_core_ms.train (ms a step): device self time of the instructions whose
scope contains ``attn.window`` - the windowed attention calls alone
(``mx_window_attn_fwd`` forward and recomputed, ``mx_window_attn_bwd``, and
the row sums the backward kernel is handed) - over the traced steps.  From
``scope_reduce.marker_seconds``; nothing without a device trace, the step's
HLO text or such a scope."""

SCOPE = "attn.window"


def read(evidence):
    by_scope, n = evidence.get("scope_s"), evidence.get("steps")
    if not by_scope or not n or not by_scope.get(SCOPE):
        return None
    return 1e3 * by_scope[SCOPE] / n
