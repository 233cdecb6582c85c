"""feed_stage_ms.train (ms a batch): what one batch costs the feed's
producer thread, to hold beside the device step it must stay under: the
mean, over the ``datafeed.stage`` spans that start inside the window, of
the ``datafeed.source`` span of the same ``batch`` before them (the draw
from the source) and of their children ``datafeed.h2d`` (enqueueing the
host-to-device copies) and ``datafeed.finalize`` (dispatching the
cast-and-normalise program); the wait for a ring slot
(``datafeed.backpressure``) is left out.  The window is the newest
``evidence["steps"]`` ``train.step`` spans in the program's span ring, first
start to last end.  Nothing where no ``datafeed.stage`` starts there: no
feed, or a program from before PR 38."""

SPAN, PARENT, NAME, START, DUR, TID, ATTRS = 1, 2, 3, 4, 5, 6, 7    # a record
PARTS = ("datafeed.h2d", "datafeed.finalize")


def read(evidence):
    n = evidence.get("steps")
    if not n:
        return None
    from mxnet_tpu import telemetry
    spans = telemetry.trace_spans()
    steps = [s for s in spans if s[NAME] == "train.step"][-n:]
    if not steps:
        return None
    lo, hi = steps[0][START], steps[-1][START] + steps[-1][DUR]
    draws, staged = {}, {}      # oldest first: a stage finds its own draw
    for s in spans:
        if s[NAME] == "datafeed.source":
            draws[s[TID], s[ATTRS]["batch"]] = s[DUR]
        elif s[NAME] == "datafeed.stage" and lo <= s[START] < hi:
            staged[s[SPAN]] = draws.get((s[TID], s[ATTRS]["batch"]), 0)
    if not staged:
        return None
    return (sum(staged.values())
            + sum(s[DUR] for s in spans
                  if s[NAME] in PARTS and s[PARENT] in staged)) \
        / len(staged) / 1e3
