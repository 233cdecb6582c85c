"""device_idle_pct.train (%): 1 - union of the ``XLA Ops`` intervals over
the traced window, which two drains bound."""


def read(evidence):
    t = evidence.get("trace")
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
