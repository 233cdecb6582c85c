"""device_step_ms.train (ms): median duration of the step program's
``XLA Modules`` events inside the traced window."""


def read(evidence):
    t = evidence.get("trace")
    return 1e3 * t["step_s"] if t and t["step_s"] else None
