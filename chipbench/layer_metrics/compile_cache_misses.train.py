"""compile_cache_misses.train (count): programs JAX compiled and wrote to
the persistent cache since the process started, by the program's counter
``jit.cache_misses``; 0 in a warm run.  ``jit.compiles`` (every compile or
load) says that the program counts at all: nothing where it is absent."""


def read(evidence):
    if not evidence.get("steps"):
        return None
    from mxnet_tpu import telemetry
    counters = telemetry.raw_snapshot()["counters"]
    if "jit.compiles" not in counters:
        return None
    return counters.get("jit.cache_misses", 0)
