"""host_exposed_ms.train (ms a step): the host time of a step that is not
a wait for the device: the loop, ``__call__``, the fetch's own cost, a feed
draw.  Mean ``gap_us`` (start of one ``__call__`` to the start of the next)
of the newest ``evidence["steps"]`` ``train.step`` spans in the program's
span ring, the first left out (it follows the drain before the window:
it carries none, or the drain), less
the mean a step of the time inside the ``nd.fetch`` spans (a fetch that
found its array not landed) that start between two successive starts on
the same thread.  The host becomes the bottleneck as this nears
``device_step_ms.train``.  Nothing where the spans carry no ``gap_us``: a
program from before PR 38."""

NAME, START, DUR, TID, ATTRS = 3, 4, 5, 6, 7     # fields of a span record


def read(evidence):
    n = evidence.get("steps")
    if not n:
        return None
    from mxnet_tpu import telemetry
    spans = telemetry.trace_spans()
    steps = [s for s in spans if s[NAME] == "train.step"][-n:]
    gaps = [s[ATTRS]["gap_us"] for s in steps[1:]
            if "gap_us" in (s[ATTRS] or {})]
    if not gaps:
        return None
    lo, hi, tid = steps[0][START], steps[-1][START], steps[0][TID]
    waited = sum(s[DUR] for s in spans if s[NAME] == "nd.fetch"
                 and s[TID] == tid and lo <= s[START] < hi)
    return (sum(gaps) - waited) / len(gaps) / 1e3
