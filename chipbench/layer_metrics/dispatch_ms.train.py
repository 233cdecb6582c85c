"""dispatch_ms.train (ms): mean host time inside ``step(x, y)`` until it
returns, with no fetch: what the host planes cost per step."""


def read(evidence):
    d = evidence.get("dispatch_s")
    return 1e3 * sum(d) / len(d) if d else None
