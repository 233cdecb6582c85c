"""From a profiler trace to numbers: device busy time, idle gaps, the
operations that took most time, the step program's duration.

Works on *planes*, a plain structure a test can write by hand::

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Ops", "events": [(text, start_ns, dur_ns), ...]},
                {"name": "XLA Modules", "events": [...]}]},
     {"name": "/host:CPU",
      "lines": [{"name": "main/299", "events": [("chipbench.window", s, d)]}]}]

``load`` reads an ``.xplane.pb`` into that structure with JAX alone, and
``profiled`` is the context manager a runner wraps its traced window in.
What this stack's trace looks like (PERF.md, section 3): the device plane is
``/device:TPU:<n>``; ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's whole text; ``XLA Modules`` holds
one event per run of a program; a Pallas kernel is an ``XLA Ops`` event
whose text carries ``custom_call_target="tpu_custom_call"``.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import statistics
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "chipbench.window"          # the annotation that bounds the window
MARK = "chipbench."                  # every annotation of the benchmark
PALLAS = 'custom_call_target="tpu_custom_call"'
TOP = 10

_HEAD = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)
_TYPE = re.compile(r"(?P<tuple>\(?)(?P<type>[a-z][a-z0-9]*\[[^\]]*\])")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"\bkind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def shorten(text: str) -> str:
    """``%add_add_fusion.1 = bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)}
    fusion(...), kind=kLoop, calls=...`` -> ``add_add_fusion.1
    bf16[256,56,56,256] fusion:kLoop``: instruction name, result type
    without layout (the first element of a tuple, marked ``(...``), opcode,
    and the fusion kind or custom-call target.  Text that is not an HLO
    instruction is cut to 64 characters."""
    m = _HEAD.match(text)
    if not m:
        return text[:64]
    rest = m.group("rest")
    t = _TYPE.match(rest)
    if not t:
        return text[:64]
    typ = ("(" if t.group("tuple") else "") + t.group("type") + \
        (",..." if t.group("tuple") else "")
    op = _OPCODE.search(rest, t.end())
    opcode = op.group(1) if op else "?"
    detail = _TARGET.search(rest) if opcode == "custom-call" \
        else _KIND.search(rest)
    return f"{m.group('name')} {typ} {opcode}" + \
        (f":{detail.group(1)}" if detail else "")


def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted list of disjoint
    ones."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, lo, hi):
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def self_times(events):
    """``[(name, self_ns)]``: each event's duration less the part its
    children (events nested inside it) cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    selfs = [e[2] for e in evs]
    stack = []                                   # (end, index), innermost last
    for i, (_, s, d) in enumerate(evs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1][1]] -= min(d, stack[-1][0] - s)
        stack.append((s + d, i))
    return [(evs[i][0], selfs[i]) for i in range(len(evs))]


def gaps(busy, lo, hi):
    """The idle intervals of ``[lo, hi]``: what ``merge``d ``busy`` leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def _label(gap, marks):
    """The benchmark's annotation that covers most of ``gap``."""
    best, cover = "unannotated", 0
    for name, s, d in marks:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def _lines(plane, name):
    return [ev for line in plane["lines"] if line["name"] == name
            for ev in line["events"]]


def reduce(planes):
    """The numbers of one traced window, or None where the trace holds no
    device plane or no window annotation (a CPU run): then every reader
    that needs the trace returns nothing.

    Times in seconds.  ``busy_s`` is the union of the ``XLA Ops`` intervals
    inside the window, averaged over the device planes; ``window_s`` is the
    length of the ``chipbench.window`` annotation, which the runner opens
    after one drain and closes after the next."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    marks = [ev for p in planes if p["name"] == HOST_PLANE
             for line in p["lines"] for ev in line["events"]
             if ev[0].startswith(MARK)]
    window = [ev for ev in marks if ev[0] == WINDOW]
    if not devices or not window:
        return None
    lo, hi = window[0][1], window[0][1] + window[0][2]
    inner = [ev for ev in marks if ev[0] != WINDOW]
    busy_ns, ops_total, pallas_ns, idle, steps = [], {}, 0, {}, []
    for plane in devices:
        ops = clip(_lines(plane, OPS_LINE), lo, hi)
        busy = merge((s, s + d) for _, s, d in ops)
        busy_ns.append(sum(e - s for s, e in busy))
        for text, ns in self_times(ops):
            ops_total[text] = ops_total.get(text, 0) + ns
            if PALLAS in text:
                pallas_ns += ns
        for gap in gaps(busy, lo, hi):
            name = _label(gap, inner)
            idle[name] = idle.get(name, 0) + gap[1] - gap[0]
        runs = {}
        for name, s, d in _lines(plane, MODULES_LINE):
            if s >= lo and s + d <= hi:
                runs.setdefault(name, []).append(d)
        if runs:      # the step program is the one that ran longest in all
            steps += max(runs.values(), key=sum)
    n = len(devices)
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "pallas_s": pallas_ns / n / 1e9,
        "step_s": statistics.median(steps) / 1e9 if steps else None,
        "steps": len(steps),
        "device_ops": [[shorten(t), ns / n / 1e9] for t, ns in top],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def load(path):
    """An ``.xplane.pb`` as planes, keeping only what ``reduce`` reads."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                      if device or e.name.startswith(MARK)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


@contextlib.contextmanager
def profiled(result: dict):
    """Trace what runs inside into a directory under ``TMPDIR``, reduce it
    into ``result["trace"]`` and delete the directory.  The caller opens
    ``jax.profiler.TraceAnnotation(WINDOW)`` inside, between two drains."""
    import jax
    where = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(where)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                          recursive=True)
        result["trace_bytes"] = sum(os.path.getsize(f) for f in found)
        result["trace"] = reduce(load(found[0])) if found else None
    finally:
        shutil.rmtree(where, ignore_errors=True)
