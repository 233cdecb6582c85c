"""bench.py orchestrator failure semantics — the driver-facing contract.

Two rounds of driver captures were lost to exactly these paths (r03: a
wedged backend produced rc=1 with no parseable row; r04: an external
timeout killed the run with zero stdout).  The orchestrator's promises:

1. A dead/wedged backend becomes ONE bounded, diagnosed probe row and a
   machine-readable failure JSON on stdout (fast, nonzero exit).
2. After EVERY completed row the cumulative JSON object is re-printed,
   so killing the process at any point still leaves the rows completed
   so far parseable from the last JSON line.
3. `_force` (the honest end-of-window barrier every benchmark shares)
   returns a real host float and tolerates the empty case.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _last_json(text):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    return None


def test_probe_failure_emits_failure_row_fast():
    """r03's failure mode: backend init fails → one bounded probe row,
    failure JSON on stdout, exit 1 — not a traceback with no row.

    Fault injection uses the probe row's own kill switch,
    BENCH_PROBE_FORCE_FAIL, honored before the row touches jax."""
    # load-aware bound: measure THIS host's current interpreter+jax
    # startup cost and allow the probe cap plus a few startups — a
    # fixed constant either flakes on a doubly-loaded 1-core host or
    # grows so large it stops guarding the 45 s cap
    t0 = time.monotonic()
    subprocess.run([sys.executable, "-c", "import jax"],
                   env={**os.environ, "JAX_PLATFORMS": "cpu"},
                   capture_output=True, timeout=240)
    startup = time.monotonic() - t0
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, BENCH],
        env={**os.environ, "BENCH_PROBE_FORCE_FAIL": "1",
             "BENCH_ROWS": "probe", "BENCH_PROBE_TIMEOUT": "45"},
        capture_output=True, text=True, timeout=600)
    dt = time.monotonic() - t0
    assert r.returncode == 1
    obj = _last_json(r.stdout)
    assert obj is not None, f"no JSON line on stdout:\n{r.stdout}"
    assert obj["metric"] == "resnet50_train_throughput_bf16"
    assert obj["value"] is None
    assert "probe" in obj.get("row_errors", {})
    bound = 45 + 4 * startup + 30
    assert dt < bound, (f"probe failure took {dt:.0f}s (bound {bound:.0f}, "
                        f"startup {startup:.0f}s) — not fail-fast")


def test_probe_success_emits_cumulative_row():
    """Happy path restricted to the probe row: rc=0 (headline never
    attempted under BENCH_ROWS), final JSON present and complete."""
    r = subprocess.run(
        [sys.executable, BENCH],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_ROWS": "probe"},
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    obj = _last_json(r.stdout)
    assert obj is not None and obj["partial"] is False
    assert "row_errors" not in obj


def test_kill_mid_run_leaves_parseable_capture(tmp_path):
    """r04's failure mode: an external kill must still leave the
    completed rows in the output tail.  Run probe (fast) + opperf, kill
    as soon as the probe's cumulative line appears, and parse it."""
    out_path = tmp_path / "out.txt"
    with open(out_path, "w") as out:
        p = subprocess.Popen(
            [sys.executable, BENCH],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "BENCH_ROWS": "probe,opperf"},
            stdout=out, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 150
            obj = None
            while time.monotonic() < deadline:
                obj = _last_json(out_path.read_text())
                if obj is not None:
                    break
                time.sleep(0.5)
            assert obj is not None, "no cumulative JSON before deadline"
            p.send_signal(signal.SIGKILL)     # the external-timeout kill
            p.wait(timeout=30)
        finally:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    # what a driver parsing the tail after rc=124/137 would recover
    obj = _last_json(out_path.read_text())
    assert obj is not None
    assert obj["metric"] == "resnet50_train_throughput_bf16"
    assert obj["partial"] in (True, False)


def test_force_returns_host_float():
    import jax.numpy as jnp
    sys.path.insert(0, REPO)
    from bench import _force
    v = _force(jnp.ones((4, 4)), jnp.full((2,), 2.0))
    assert v == pytest.approx(20.0)
    assert _force() == 0.0
