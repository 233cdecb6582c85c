"""gdn_scan_ms.train (ms a step): device self time of the instructions whose
scope contains ``gdn.scan`` - everything of ``ops.nn.gdn_chunked`` and the
L2 norms of q and k, forward, recomputation and backward: what a kernel
would replace - over the traced steps.  From
``scope_reduce.marker_seconds``; nothing without a device trace, the step's
HLO text or such a scope."""

SCOPE = "gdn.scan"


def read(evidence):
    by_scope, n = evidence.get("scope_s"), evidence.get("steps")
    if not by_scope or not n or not by_scope.get(SCOPE):
        return None
    return 1e3 * by_scope[SCOPE] / n
