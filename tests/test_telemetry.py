"""mx.telemetry — unified runtime metrics registry (ISSUE 3).

Covers: native snapshot schema, engine span counters across an op burst,
histogram invariants, Prometheus exposition, the SIGUSR2 diagnostic dump
round trip, disabled-mode freezing, and the JsonCall bridge-arity
regression (py_runtime.cc must reject a malformed c_json return instead
of crashing)."""
import ctypes
import json
import os
import re
import signal
import subprocess
import sys
import time

import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import LIB

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def enabled_telemetry():
    """Force-enable for the test, restore the caller's flag after."""
    prev = telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(prev)


def _burst(n=32):
    eng = mx.engine.engine()
    v = eng.new_variable()
    for _ in range(n):
        eng.push(lambda: None, mutable_vars=[v])
    eng.wait_for_all()


# ------------------------------------------------------------------ schema
def test_raw_snapshot_schema(enabled_telemetry):
    _burst(8)
    raw = telemetry.raw_snapshot()
    assert set(raw.keys()) == {"enabled", "counters", "gauges",
                               "histograms", "engines"}
    assert raw["enabled"] is True
    assert all(isinstance(v, int) for v in raw["counters"].values())
    assert all(isinstance(v, int) for v in raw["gauges"].values())
    for name, h in raw["histograms"].items():
        assert set(h.keys()) == {"le", "counts", "count", "sum"}, name
    if LIB is not None:
        # native tier registers every live engine's queue state
        assert raw["engines"], "no engine state reported"
        for st in raw["engines"]:
            assert set(st.keys()) == {"naive", "workers", "pending",
                                      "executed", "vars", "has_exception"}
            assert st["has_exception"] is False


def test_sectioned_snapshot_shape(enabled_telemetry):
    _burst(8)
    snap = telemetry.snapshot()
    for sec in telemetry.SECTIONS + ("other",):
        assert {"counters", "gauges", "histograms"} <= set(snap[sec])
    assert isinstance(snap["engine"]["state"], list)
    assert isinstance(snap["datafeed"]["rings"], list)
    assert snap["device_memory"]["device_count"] >= 1
    json.dumps(snap, default=str)     # must be serializable as-is


# ------------------------------------------------------------ engine spans
def test_engine_span_counters_increment(enabled_telemetry):
    before = telemetry.raw_snapshot()
    _burst(48)
    after = telemetry.raw_snapshot()

    def delta(kind, name):
        return after[kind].get(name, 0) - before[kind].get(name, 0)

    assert delta("counters", "engine.ops_dispatched") >= 48
    assert delta("counters", "engine.ops_executed") >= 48
    h0 = before["histograms"].get("engine.run_us", {"count": 0})
    h1 = after["histograms"]["engine.run_us"]
    assert h1["count"] - h0["count"] >= 48
    # every executed op waited in a queue for a measurable >= 0 span
    q0 = before["histograms"].get("engine.queue_wait_us", {"count": 0})
    q1 = after["histograms"].get("engine.queue_wait_us")
    if q1 is not None:        # threaded engine only
        assert q1["count"] > q0["count"]


# ------------------------------------------------------- histogram buckets
def test_histogram_invariants(enabled_telemetry):
    _burst(16)
    raw = telemetry.raw_snapshot()
    assert raw["histograms"], "burst produced no histograms"
    for name, h in raw["histograms"].items():
        assert h["le"] == telemetry.BUCKET_BOUNDS_US, name
        assert all(a < b for a, b in zip(h["le"], h["le"][1:])), \
            f"{name}: bounds not strictly increasing"
        assert len(h["counts"]) == len(h["le"]) + 1, name
        assert all(c >= 0 for c in h["counts"]), name
        assert sum(h["counts"]) == h["count"], name
        assert h["sum"] >= 0.0, name


def test_observe_lands_in_correct_bucket(enabled_telemetry):
    name = "test.bucket_placement_us"
    for v, want_idx in ((0.5, 0), (3.0, 2), (2e6, len(
            telemetry.BUCKET_BOUNDS_US))):
        before = telemetry.raw_snapshot()["histograms"].get(name)
        before_counts = before["counts"] if before else \
            [0] * (len(telemetry.BUCKET_BOUNDS_US) + 1)
        telemetry.observe(name, v)
        counts = telemetry.raw_snapshot()["histograms"][name]["counts"]
        assert counts[want_idx] == before_counts[want_idx] + 1, \
            f"observe({v}) missed bucket {want_idx}"


# -------------------------------------------------------------- prometheus
def test_prometheus_exposition_parses(enabled_telemetry):
    # whatever earlier files of this worker left in the registry (names
    # stay interned through reset()), plus every way two metrics can
    # meet in one family: two kinds under one name, two names that
    # sanitise to one, a counter named like a histogram's sample, and
    # values with a negative exponent
    telemetry.reset()
    _burst(16)
    telemetry.counter_add("test.prom_counter", 3)
    telemetry.counter_add("test.prom_twice", 2)
    telemetry.gauge_set("test.prom_twice", 5)
    telemetry.observe("test.prom_twice", 1e-5)
    telemetry.counter_add("test.prom-twice", 1)
    telemetry.counter_add("test.prom_twice_count", 1)
    text = telemetry.dump_prometheus()
    assert "mxtpu_test_prom_counter 3" in text or \
        re.search(r"^mxtpu_test_prom_counter \d+$", text, re.M)
    series, helps, types = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            # strict comment conformance: only HELP/TYPE, well-formed
            m = re.match(r"^# (HELP|TYPE) ([a-zA-Z_:][a-zA-Z0-9_:]*) (.+)$",
                         line)
            assert m, f"malformed comment line: {line!r}"
            kind, fam, rest = m.groups()
            if kind == "HELP":
                assert fam not in helps, f"{fam}: duplicate HELP"
                helps[fam] = rest
            else:
                assert fam not in types, f"{fam}: duplicate TYPE"
                assert rest in ("counter", "gauge", "histogram"), \
                    f"{fam}: bad TYPE {rest!r}"
                types[fam] = rest
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? "
                     r"(-?[0-9.]+([eE][+-]?[0-9]+)?|NaN|[+-]Inf)$", line)
        assert m, f"malformed exposition line: {line!r}"
        series.setdefault(m.group(1), []).append(line)
    # every family is announced: a sample's base name (histogram
    # samples collapse _bucket/_sum/_count) has BOTH # HELP and # TYPE
    for name in series:
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = name if name in types else base
        assert fam in types, f"{name}: no # TYPE"
        assert fam in helps, f"{name}: no # HELP"
        if name != fam:       # a collapsed histogram sample suffix
            assert types[fam] == "histogram", \
                f"{name}: suffix on non-histogram family"
    # histogram series: cumulative buckets are monotonic and the +Inf
    # bucket equals _count
    for base in {n[:-7] for n in series if n.endswith("_bucket")}:
        assert types.get(base) == "histogram"
        cum = []
        for line in series[base + "_bucket"]:
            cum.append(float(line.rsplit(" ", 1)[1]))
        assert cum == sorted(cum), f"{base}: non-monotonic buckets"
        count = float(series[base + "_count"][0].rsplit(" ", 1)[1])
        assert cum[-1] == count, f"{base}: +Inf bucket != count"


# ----------------------------------------------------------- disabled mode
def test_disabled_mode_freezes_counters():
    prev = telemetry.set_enabled(True)
    try:
        _burst(4)                                    # intern the slots
        telemetry.set_enabled(False)
        before = telemetry.raw_snapshot()
        assert before["enabled"] is False
        _burst(32)
        telemetry.counter_add("test.disabled_counter", 5)
        telemetry.observe("test.disabled_hist_us", 10.0)
        after = telemetry.raw_snapshot()
        assert after["counters"] == before["counters"]
        assert after["histograms"] == before["histograms"]
        telemetry.set_enabled(True)
        telemetry.counter_add("test.disabled_counter", 5)
        assert telemetry.raw_snapshot()["counters"][
            "test.disabled_counter"] == before["counters"].get(
                "test.disabled_counter", 0) + 5
    finally:
        telemetry.set_enabled(prev)


def test_reset_zeroes_but_keeps_names(enabled_telemetry):
    telemetry.counter_add("test.reset_me", 7)
    telemetry.reset()
    raw = telemetry.raw_snapshot()
    assert raw["counters"].get("test.reset_me") == 0
    telemetry.counter_add("test.reset_me", 2)    # slot survives a reset
    assert telemetry.raw_snapshot()["counters"]["test.reset_me"] == 2


# ------------------------------------------------------------ kvstore tier
def test_local_kvstore_populates_registry(enabled_telemetry):
    before = telemetry.raw_snapshot()["counters"].get(
        "kvstore.push_total", 0)
    kv = mx.kv.create("local")
    kv.init("tw", mx.np.ones((4,)))
    kv.push("tw", mx.np.ones((4,)))
    out = mx.np.zeros((4,))
    kv.pull("tw", out=out)
    raw = telemetry.raw_snapshot()
    assert raw["counters"]["kvstore.push_total"] == before + 1
    assert raw["histograms"]["kvstore.push_us"]["count"] >= 1
    assert raw["counters"]["kvstore.pull_total"] >= 1


# ------------------------------------------------------------- SIGUSR2 dump
@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform has no SIGUSR2")
def test_sigusr2_dump_roundtrip(tmp_path):
    dump_path = str(tmp_path / "dump.json")
    code = (
        "import os, signal, time\n"
        "import mxnet_tpu as mx\n"
        "eng = mx.engine.engine()\n"
        "v = eng.new_variable()\n"
        "for _ in range(16):\n"
        "    eng.push(lambda: None, mutable_vars=[v])\n"
        "eng.wait_for_all()\n"
        "os.kill(os.getpid(), signal.SIGUSR2)\n"
        "time.sleep(0.5)\n"
        "print('ALIVE')\n"            # the handler must not kill the host
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "MXNET_TELEMETRY": "1",
           "MXNET_TELEMETRY_DUMP_PATH": dump_path}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "ALIVE" in r.stdout
    with open(dump_path) as f:
        d = json.load(f)
    assert d["reason"] == "SIGUSR2"
    assert d["pid"] > 0
    snap = d["snapshot"]
    assert snap["engine"]["counters"]["engine.ops_dispatched"] >= 16
    assert d["threads"], "thread stacks missing from dump"
    assert any("MainThread" in k for k in d["threads"])


def test_dump_on_exit(tmp_path):
    dump_path = str(tmp_path / "exit_dump.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "MXNET_TELEMETRY_DUMP_ON_EXIT": "1",
           "MXNET_TELEMETRY_DUMP_PATH": dump_path}
    r = subprocess.run(
        [sys.executable, "-c", "import mxnet_tpu as mx\n"
         "mx.telemetry.counter_add('test.exit_marker', 1)\n"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    with open(dump_path) as f:
        d = json.load(f)
    assert d["reason"] == "exit"
    assert d["snapshot"]["other"]["counters"]["test.exit_marker"] == 1


# ------------------------------------------- JsonCall arity regression
def test_jsoncall_rejects_malformed_bridge_return():
    """py_runtime.cc JsonCall must turn a c_json return that is not a
    2-list into rc=-1 with a diagnostic — not a segfault (the old code
    indexed the list unchecked)."""
    if LIB is None:
        pytest.skip("native lib not loaded")
    LIB.MXTListAllOpNames.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.POINTER(ctypes.c_int)]
    buf = ctypes.create_string_buffer(1 << 20)
    n = ctypes.c_int()
    if LIB.MXTListAllOpNames(buf, len(buf), ctypes.byref(n)) != 0:
        pytest.skip("python backend inactive: "
                    + LIB.MXTGetLastError().decode())
    import mxnet_tpu._embed as _embed
    orig = _embed.c_json
    try:
        for bad in ("not-a-list",
                    lambda: None,            # stringified below
                    [None],                  # arity 1
                    [None, [], "extra"]):    # arity 3
            _embed.c_json = (lambda *_a, _bad=bad: _bad)
            rc = LIB.MXTListAllOpNames(buf, len(buf), ctypes.byref(n))
            assert rc == -1, f"malformed return {bad!r} was accepted"
            err = LIB.MXTGetLastError().decode()
            assert "2-list" in err, err
            assert "list_all_op_names" in err, err
    finally:
        _embed.c_json = orig
    # the bridge must recover cleanly once the return shape is right
    assert LIB.MXTListAllOpNames(buf, len(buf), ctypes.byref(n)) == 0
    assert n.value > 0


# --------------------------------------------------------- profiler bridge
def test_profiler_counter_thread_safety():
    """Counter.increment is used from engine worker threads; the
    read-modify-write must be atomic (satellite: profiler race fix)."""
    import threading
    c = mx.profiler.Counter("test_atomic")
    N, T = 2000, 8

    def bump():
        for _ in range(N):
            c.increment()

    ts = [threading.Thread(target=bump) for _ in range(T)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == N * T


def test_profiler_dumps_min_max_avg():
    mx.profiler.set_config(profile_all=True)
    mx.profiler.start()
    try:
        with mx.profiler.Task("unit_span"):
            time.sleep(0.002)
        with mx.profiler.Task("unit_span"):
            time.sleep(0.004)
    finally:
        mx.profiler.stop()
    table = mx.profiler.dumps(reset=True)
    head = table.splitlines()[0]
    for col in ("Min(us)", "Max(us)", "Avg(us)"):
        assert col in head, head
    row = next(ln for ln in table.splitlines() if "unit_span" in ln)
    cnt, tot, mn, mx_, avg = row.split()[-5:]
    assert int(cnt) == 2
    assert float(mn) <= float(avg) <= float(mx_)
    assert abs(float(tot) - (float(mn) + float(mx_))) < 1.0


def test_snapshot_feeds_profiler_counters(enabled_telemetry):
    telemetry.counter_add("test.bridge_counter", 11)
    telemetry.snapshot()
    c = telemetry._prof_counters.get("test.bridge_counter")
    assert c is not None and c.value >= 11
