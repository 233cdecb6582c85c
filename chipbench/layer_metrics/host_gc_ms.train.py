"""host_gc_ms.train (ms a step): the time inside the ``host.gc`` spans
(a generation-2 collection, or a younger one of a millisecond or more: the
interpreter stands still for it on every thread) that start inside the
window, a step of it.  The window is the newest ``evidence["steps"]``
``train.step`` spans in the program's span ring, first start to last end.
Nothing where the steps carry no ``gap_us``: a program from before PR 38
records no collection, which is not a reading of 0."""

NAME, START, DUR, ATTRS = 3, 4, 5, 7            # fields of a span record


def read(evidence):
    n = evidence.get("steps")
    if not n:
        return None
    from mxnet_tpu import telemetry
    spans = telemetry.trace_spans()
    steps = [s for s in spans if s[NAME] == "train.step"][-n:]
    if not any("gap_us" in (s[ATTRS] or {}) for s in steps):
        return None
    lo, hi = steps[0][START], steps[-1][START] + steps[-1][DUR]
    return sum(s[DUR] for s in spans if s[NAME] == "host.gc"
               and lo <= s[START] < hi) / len(steps) / 1e3
