"""step_gap_excess_ms.train (ms): the longest step-to-step interval of the
window less the median one, by the program's own clock: ``gap_us`` (start
of one ``__call__`` to the start of the next) of the newest
``evidence["steps"]`` ``train.step`` spans in the program's span ring, the
first left out (it follows the drain before the window: it carries none,
or the drain).  Under a millisecond in a window without a stall, the
stall's size in one with.
Nothing where the spans carry no ``gap_us``: a program from before PR 38."""
import statistics

NAME, ATTRS = 3, 7          # fields of a span record (mxnet_tpu.telemetry)


def read(evidence):
    n = evidence.get("steps")
    if not n:
        return None
    from mxnet_tpu import telemetry
    steps = [s for s in telemetry.trace_spans()
             if s[NAME] == "train.step"][-n:]
    gaps = [s[ATTRS]["gap_us"] for s in steps[1:]
            if "gap_us" in (s[ATTRS] or {})]
    return (max(gaps) - statistics.median(gaps)) / 1e3 if gaps else None
