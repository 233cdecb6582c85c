#!/usr/bin/env python3
"""chipbench — one cell of BENCHMARK.json, on the chip, as one process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` with
``--trace 1``).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.  Without a TPU, with another number
of chips than the cell asks for, or with ``MXNET_TPU_PALLAS_INTERPRET``
set, it exits non-zero and prints no result: there is no CPU route.

Nothing here knows a cell, a model or a metric by name.  Everything is
found from the names in ``BENCHMARK.json`` (README.md beside this file):

    configs[].file                        the configuration as it is run
    chipbench/traffic/<traffic>.json      the mix: its runner and parameters
    chipbench/runners/<runner>.py         run(cell) -> evidence
    chipbench/layer_metrics/<metric>.py   read(evidence) -> number or None
"""
from __future__ import annotations

import time

T0 = time.perf_counter()     # set-up is counted from here, before any import

import argparse              # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import sys                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)     # the program and this package live there

from chipbench.files import load_json, load_module  # noqa: E402

BENCH = "chipbench"
SEED_FOLD = 2 ** 31 - 1      # the driver's seeds pass 2**31


def say(msg):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json")


def device_problem(devs, chips):
    """Why this process may not run the cell, or None."""
    d0 = devs[0]
    if d0.platform != "tpu":
        return (f"no TPU (jax found {d0.platform!r} x{len(devs)}); "
                "the benchmark does not run on a CPU")
    if len(devs) != chips:
        return f"the cell wants {chips} chip(s), jax found {len(devs)}"
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET"):
        return ("MXNET_TPU_PALLAS_INTERPRET is set; the benchmark runs "
                "compiled kernels only")
    return None


def place_compile_cache(root):
    """The persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` if
    set, else the fixed ``<checkout>/.jax_cache`` (the program's own rule),
    and for this process every program is written to it whatever it cost
    to compile: JAX's default leaves out compiles under a second, and the
    set-up is made of dozens of those."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def run_cell(workload, seed, seconds, trace, devs, root=ROOT):
    """Run one cell and return the result line as a dict.  ``root`` is the
    checkout that holds ``BENCHMARK.json`` and the benchmark's files."""
    bench = load_json(root, "BENCHMARK.json")
    entry = find(bench["workloads"], workload, "workload")
    config = load_json(root, find(bench["configs"], entry["config"],
                                  "configuration")["file"])
    mix = load_json(root, BENCH, "traffic", entry["traffic"] + ".json")
    peaks = load_json(root, BENCH, "peaks.json")
    kind = devs[0].device_kind
    if devs[0].platform == "tpu" and kind not in peaks:
        # an unknown device has no peak: an error, never a default
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         "chipbench/peaks.json")
    cell = {
        "name": workload, "chips": entry["chips"], "config": config,
        "mix": mix, "seed": int(seed) % SEED_FOLD, "seconds": float(seconds),
        "trace": bool(trace), "devices": devs, "root": root,
        "t0": T0, "peaks": peaks.get(kind),
    }
    runner = load_module(root, BENCH, "runners", mix["runner"] + ".py")
    evidence = runner.run(cell)

    for what, ok in evidence["checks"]:
        say(("ok: " if ok else "FAILED: ") + what)
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            reader = load_module(root, BENCH, "layer_metrics",
                                 m["name"] + ".py")
            value = reader.read(evidence)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, workload) and m["name"] in evidence["end_to_end"]:
                metrics[m["name"]] = {
                    "value": float(evidence["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(evidence["memory_peak_bytes"])}
    line = {"correct": all(ok for _, ok in evidence["checks"]),
            "attempted": int(evidence["attempted"]),
            "failed": int(evidence["failed"]),
            "metrics": metrics, "device": device}
    reduced = evidence.get("trace")
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    entry = find(bench["workloads"], args.workload, "workload")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    import jax
    devs = jax.devices()
    problem = device_problem(devs, entry["chips"])
    if problem:
        print(f"chipbench: {problem}", file=sys.stderr)
        return 2
    place_compile_cache(ROOT)
    line = run_cell(args.workload, args.seed, seconds, args.trace, devs)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
