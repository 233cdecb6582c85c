"""step_host_ms.train (ms): mean duration of the newest
``evidence["steps"]`` ``train.step`` spans in the program's span ring: the
window's steps as the program times them, whole ``__call__`` (prepare,
launch, write-back).  Nothing where a ``train.step`` has no
``train.launch`` child: there the span meant something else."""

NAME, DUR = 3, 5            # fields of a span record (mxnet_tpu.telemetry)


def read(evidence):
    n = evidence.get("steps")
    if not n:
        return None
    from mxnet_tpu import telemetry
    spans = telemetry.trace_spans()
    if not any(s[NAME] == "train.launch" for s in spans):
        return None
    durs = [s[DUR] for s in spans if s[NAME] == "train.step"][-n:]
    return sum(durs) / len(durs) / 1e3 if durs else None
