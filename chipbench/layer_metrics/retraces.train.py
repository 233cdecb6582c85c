"""retraces.train (count): retraces and legacy fallbacks of the fused step
(window deltas of ``fused.retraces`` + ``fused.fallbacks``) plus every
compilation JAX reported inside the window.  Must be 0."""


def read(evidence):
    return evidence.get("retraces")
