"""step_build_s.train (s): what the program spent making the step program
- collect + trace + lower + compile-or-load - by its own counter
``fused.build_us``, read in-process at the end of the run.  Nothing where
the program has no such counter (a commit before PR 27)."""


def read(evidence):
    if not evidence.get("steps"):
        return None
    from mxnet_tpu import telemetry
    us = telemetry.raw_snapshot()["counters"].get("fused.build_us")
    return us / 1e6 if us else None
