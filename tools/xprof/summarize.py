#!/usr/bin/env python
"""Device time by scope: read a jax.profiler trace as an operator.

    python tools/xprof/summarize.py <trace dir or .xplane.pb> [--depth 2] [--top 15]

Prints, for the window of the trace (the ``chipbench.window`` annotation
where there is one, else everything): busy time by the program's named
scopes (``mx.fwd/<block path>`` with its backward beside it, ``mx.loss``,
``mx.opt`` ...; docs/tracing.md "Names in a device trace"), by Pallas
kernel name (``mx_block_dw``), the longest instructions with their scope,
the device's idle time by the program's span that covers it, and the
program's spans on the host plane (``train.*``, ``nd.fetch``, ``host.gc``,
``datafeed.*``: every ``telemetry.span`` is an annotation on that plane).

Events, times and host annotations come from ``jax.profiler.ProfileData``.
The scope path of an instruction is the ``tf_op`` stat of its event
*metadata*, which ProfileData (jaxlib 0.9.0) does not expose: it is read
with the xplane schema that an installed TensorFlow ships
(``xplane_pb2.py``, loaded by path; TensorFlow itself is not imported).
Without it everything is listed under ``(no scope: xplane_pb2 not found)``.
"""
import argparse
import bisect
import collections
import glob
import importlib.util
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench.trace_reduce import (DEVICE_PLANE, HOST_PLANE, OPS_LINE,  # noqa: E402
                                    WINDOW, clip, gaps, merge, self_times)

PHASE = re.compile(r"(transpose\(jvp\()?(?:jvp\()?(mx\.\w+)\)*")
KERNEL = re.compile(r"^%?(mx_\w+?)(\.\d+)? = .*tpu_custom_call")
NO_SCHEMA = "(no scope: xplane_pb2 not found)"
PROGRAM = ("train.", "nd.fetch", "host.gc", "datafeed.")   # the program's spans
NO_SPAN = "(no span)"


def scopes_of(path):
    """{instruction text: tf_op} from the event metadata of the device
    planes, or None where no xplane schema is installed."""
    spec = importlib.util.find_spec("tensorflow")
    where = spec and os.path.join(os.path.dirname(spec.origin), "tsl",
                                  "profiler", "protobuf", "xplane_pb2.py")
    if not where or not os.path.exists(where):
        return None
    s = importlib.util.spec_from_file_location("_xplane_pb2", where)
    pb2 = importlib.util.module_from_spec(s)
    s.loader.exec_module(pb2)
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        tf_op = [k for k, v in plane.stat_metadata.items()
                 if v.name == "tf_op"]
        for em in plane.event_metadata.values():
            out[em.name] = next((st.str_value for st in em.stats
                                 if st.metadata_id in tf_op), "")
    return out


def split(tf_op, depth):
    """``jit(step)/transpose(jvp(mx.fwd))/encoder/layer7/attention/dot:``
    -> (``mx.fwd/encoder/layer7``, ``bwd``).  What carries no ``mx.``
    scope goes under ``(outside)``."""
    parts = tf_op.rstrip(":").split("/")[1:-1]      # drop jit(...) and the op
    for i, p in enumerate(parts):
        m = PHASE.fullmatch(p)
        if m:
            return "/".join([m.group(2)] + parts[i + 1:i + 1 + depth]), \
                "bwd" if m.group(1) else "fwd"
    return "(outside)", "fwd"


def idle_by_span(idle, spans):
    """{span name: ns} of the ``(start, end)`` intervals ``idle``: every
    instant of them goes to the innermost (shortest) of the spans ``(name,
    start_ns, dur_ns)`` open at that instant, on any host thread, else to
    ``(no span)``.  ``trace_reduce._label`` gives a whole gap to the
    annotation that covers most of it; the program's spans nest and a gap
    may outlast several of them, so a gap is cut where a span starts or
    ends and each piece is labelled by itself."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    ends, top = [], 0               # the latest end so far: it only grows
    for _, s, d in spans:
        top = max(top, s + d)
        ends.append(top)
    out = collections.Counter()
    for lo, hi in idle:
        over = [sp for sp in spans[bisect.bisect_right(ends, lo):
                                   bisect.bisect_left(starts, hi)]
                if sp[1] + sp[2] > lo]
        cuts = sorted({lo, hi} | {t for _, s, d in over for t in (s, s + d)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            inner = min((sp for sp in over if sp[1] <= a and sp[1] + sp[2] >= b),
                        key=lambda sp: sp[2], default=(NO_SPAN,))
            out[inner[0]] += b - a
    return out


def table(title, rows, total, top):
    print(f"\n== {title} (rows of 0.05 % of busy time and more) ==")
    for key, cols in sorted(rows.items(), key=lambda kv: -sum(kv[1]))[:top]:
        if sum(cols) < 5e-4 * total:
            break
        ms = "".join(f"{c / 1e6:10.2f}" for c in cols)
        print(f"{ms} ms {100.0 * sum(cols) / total:6.2f} %  {key}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    # a line is a thread; threads share names (every one is "python3")
    threads = [(f"{ln.name} #{i}", [(e.name, e.start_ns, e.duration_ns)
                                    for e in ln.events
                                    if e.name.startswith(PROGRAM + (WINDOW,))])
               for p in planes if p.name == HOST_PLANE
               for i, ln in enumerate(p.lines)]
    host = [h for _, line in threads for h in line]
    by_plane = [[(e.name, e.start_ns, e.duration_ns) for ln in p.lines
                 if ln.name == OPS_LINE for e in ln.events]
                for p in planes if DEVICE_PLANE.match(p.name)]
    ops = [o for plane in by_plane for o in plane]
    if not ops:
        print("no device plane with an XLA Ops line in this trace")
        return 1
    window = [h for h in host if h[0] == WINDOW]
    lo, hi = (window[0][1], window[0][1] + window[0][2]) if window else \
        (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))
    ops = clip(ops, lo, hi)
    busy = sum(e - s for s, e in merge((s, s + d) for _, s, d in ops))
    spans = [h for h in host if h[0] != WINDOW]
    scope = scopes_of(path)
    by_scope = collections.defaultdict(lambda: [0, 0])
    by_phase = collections.defaultdict(lambda: [0, 0])
    by_kernel, by_op = collections.Counter(), collections.Counter()
    calls = collections.Counter()
    for text, ns in self_times(ops):
        key, side = split(scope.get(text, ""), args.depth) \
            if scope is not None else (NO_SCHEMA, "fwd")
        by_scope[key][side == "bwd"] += ns
        by_phase[key.split("/")[0]][side == "bwd"] += ns
        k = KERNEL.match(text)
        if k:
            by_kernel[k.group(1)] += ns
            calls[k.group(1)] += 1
        by_op[(text.split(" = ")[0].lstrip("%"),
               (scope or {}).get(text, "").rstrip(":"))] += ns
    print(f"{path}\nwindow {(hi - lo) / 1e6:.3f} ms, busy {busy / 1e6:.3f} ms"
          f" ({100.0 * busy / (hi - lo):.2f} %), {len(ops)} instructions run")
    named = sum(sum(v) for k, v in by_phase.items() if k.startswith("mx."))
    print(f"under an mx. scope: {100.0 * named / busy:.2f} % of busy time")
    table("by phase: forward ms, backward ms", by_phase, busy, 99)
    table(f"by scope, depth {args.depth}: forward ms, backward ms", by_scope,
          busy, args.top * 4)
    table("by Pallas kernel", {f"{k} x{calls[k]}": [v] for k, v in
                               by_kernel.items()}, busy, 99)
    table("longest instructions", {f"{n}  [{s}]": [v] for (n, s), v in
                                   by_op.items()}, busy, args.top)
    idle = [gaps(merge((s, s + d) for _, s, d in clip(plane, lo, hi)), lo, hi)
            for plane in by_plane]
    # all threads at once, then each thread that holds spans on its own:
    # where two threads wait through the same gap the shorter span wins
    alone = [(f"thread {name}", [h for h in line if h[0] != WINDOW])
             for name, line in threads if any(h[0] != WINDOW for h in line)]
    for view, of in [("any thread", spans)] + (alone if len(alone) > 1
                                               else []):
        rows = collections.Counter()
        for plane in idle:
            rows.update(idle_by_span(plane, of))
        n, total = len(idle), sum(rows.values())
        print(f"\n== device idle by the program's span, {view}: "
              f"{total / n / 1e6:.3f} ms of the window, "
              f"{100.0 * total / n / (hi - lo):.2f} % ==")
        for name, ns in rows.most_common(args.top):
            print(f"{ns / n / 1e6:10.3f} ms {100.0 * ns / max(total, 1):6.2f}"
                  f" %  {name}")
    durs = collections.defaultdict(list)
    for name, _, d in clip(spans, lo, hi):
        durs[name].append(d)
    print("\n== the program's spans on the host plane, inside the window ==")
    for name, ds in sorted(durs.items()):
        print(f"{len(ds):6d} x {sum(ds) / len(ds) / 1e3:10.1f} us  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
