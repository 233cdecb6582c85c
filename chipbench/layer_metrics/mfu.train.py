"""mfu.train (%): required FLOPs per sample (``flops.py``: forward +
backward = 3 x forward, no recomputation) x batch / device step time /
the chip's published bf16 peak (``peaks.json``)."""


def read(evidence):
    t, per_sample = evidence.get("trace"), evidence.get("flops_per_sample")
    peaks = evidence.get("peaks")
    if not (t and t["step_s"] and per_sample and peaks):
        return None
    return 100.0 * per_sample * evidence["batch"] / t["step_s"] \
        / peaks["bf16_flops_per_s"]
