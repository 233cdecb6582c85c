"""From a device trace and the step program's HLO text to device time per
*scope* of the program (PERF.md section 7 B, way two).

An ``XLA Ops`` event is named by its instruction's text (``%fusion.12 =
...``); the compiled program's HLO text gives every instruction its
``op_name`` (``jit(step)/.../jvp(mx.fwd)/layers/3/mixer/ssm.scan/...``, the
``jax.named_scope`` path the blocks opened).  ``instruction_scopes`` parses
the second, ``op_self_seconds`` reduces the first to self time per
instruction name, and ``layer_kind_seconds`` adds them up by the kind of
layer (``layers/<i>/`` and the configuration's pattern), forward and backward
together.  A fusion carries the scope of one of its instructions.

``profiled`` is ``trace_reduce.profiled`` keeping one thing more (the self
times); ``trace_reduce.py`` is not edited.  Everything returns nothing where
there is nothing to read: no device plane, no HLO text, no such scope.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

from chipbench import trace_reduce

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)
_LAYER = re.compile(r"/layers/(\d+)/")


def instruction_scopes(hlo_text):
    """``{instruction name: op_name}`` of every instruction that has one."""
    return dict(_INSTR.findall(hlo_text or ""))


def op_self_seconds(planes):
    """``{instruction name: seconds}``: self time inside the window, the mean
    over the device planes; None without a device plane or a window."""
    devices = [p for p in planes
               if trace_reduce.DEVICE_PLANE.match(p["name"])]
    window = [ev for p in planes if p["name"] == trace_reduce.HOST_PLANE
              for line in p["lines"] for ev in line["events"]
              if ev[0] == trace_reduce.WINDOW]
    if not devices or not window:
        return None
    lo, hi = window[0][1], window[0][1] + window[0][2]
    out = {}
    for plane in devices:
        ops = trace_reduce.clip(
            [ev for line in plane["lines"]
             if line["name"] == trace_reduce.OPS_LINE
             for ev in line["events"]], lo, hi)
        for text, ns in trace_reduce.self_times(ops):
            m = trace_reduce._HEAD.match(text)
            name = m.group("name") if m else text[:64]
            out[name] = out.get(name, 0.0) + ns / len(devices) / 1e9
    return out


def layer_kind_seconds(op_seconds, scopes, pattern):
    """``{kind: seconds}`` for the kinds of ``pattern`` (one letter a layer):
    the self time of every instruction whose scope lies under
    ``layers/<i>/``, forward, recomputation and backward alike."""
    if not op_seconds or not scopes:
        return None
    out = {}
    for name, s in op_seconds.items():
        m = _LAYER.search(scopes.get(name, ""))
        if m and int(m.group(1)) < len(pattern):
            kind = pattern[int(m.group(1))]
            out[kind] = out.get(kind, 0.0) + s
    return out or None


def marker_seconds(op_seconds, scopes, markers):
    """``{marker: seconds}``: the self time of the instructions whose scope
    contains ``marker`` (``"ssm.scan"``, ``"mx.opt"``, ...), and under
    ``"(no scope)"`` that of the instructions without one.  For the log and
    for PERF.md's breakdown; no metric reads it."""
    if not op_seconds or not scopes:
        return None
    out = {m: 0.0 for m in markers}
    out["(no scope)"] = 0.0
    for name, s in op_seconds.items():
        scope = scopes.get(name)
        if scope is None:
            out["(no scope)"] += s
            continue
        for m in markers:
            if m in scope:
                out[m] += s
    return out


@contextlib.contextmanager
def profiled(result: dict):
    """As ``trace_reduce.profiled``, and ``result["op_seconds"]`` besides."""
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
        planes = trace_reduce.load(found[0]) if found else []
        result["trace"] = trace_reduce.reduce(planes) if found else None
        result["op_seconds"] = op_self_seconds(planes)
    finally:
        shutil.rmtree(where, ignore_errors=True)
