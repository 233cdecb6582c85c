"""feed_wait_ms.train (ms a step): what the training loop waited for its
next batch, a step of the window: the delta of the feed's own
``consumer_wait_s`` (``DataFeed.stats()``: the time inside the
``datafeed.wait`` spans: for the ring to hold a staged batch, and for that
batch's host-to-device copy to have landed before it is handed over) over
the window's draws.  0 while the ring always holds a batch that has landed;
a program whose feed hands a batch over with its copy in flight (the parent
of PR 34) reads only the first; nothing where no feed ran."""


def read(evidence):
    feed = evidence.get("feed")
    if not feed or not feed.get("draws"):
        return None
    return 1e3 * feed["wait_s"] / feed["draws"]
