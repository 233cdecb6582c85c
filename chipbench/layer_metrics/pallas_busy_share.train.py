"""pallas_busy_share.train (%): device time of the ``tpu_custom_call``
events over the device's busy time in the traced window."""


def read(evidence):
    t = evidence.get("trace")
    return 100.0 * t["pallas_s"] / t["busy_s"] if t and t["busy_s"] else None
