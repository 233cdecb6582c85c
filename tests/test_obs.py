"""mxnet_tpu.obs — the fleet observability plane (ISSUE 18).

Covers: recorder ring/rate/windowed-quantile derivation, shard
round-trip, watchdog rule hysteresis, derived signal math, analytic
HybridBlock.flops, the tools/obs.py prometheus parser + report,
diagnose --since delta columns, and the SIGUSR2-while-sampling dump
round trip."""
import importlib.util
import json
import math
import os
import signal
import subprocess
import sys
import time

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.obs import recorder as obs_recorder
from mxnet_tpu.obs import rules as obs_rules
from mxnet_tpu.obs import signals as obs_signals
from mxnet_tpu.obs.recorder import (Recorder, delta_hist, derive_between,
                                    split_label)
from mxnet_tpu.obs.rules import Rule, RuleEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def enabled_telemetry():
    prev = telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(prev)


def _hist(vals):
    le = list(telemetry.BUCKET_BOUNDS_US)
    counts = [0] * (len(le) + 1)
    for v in vals:
        for i, b in enumerate(le):
            if v <= b:
                counts[i] += 1
                break
        else:
            counts[-1] += 1
    return {"le": le, "counts": counts, "count": len(vals),
            "sum": float(sum(vals))}


# ------------------------------------------------------------- derivation
def test_split_label():
    assert split_label("trainer-rank3") == ("trainer", 3)
    assert split_label("feed-worker1") == ("feed-worker", 1)
    assert split_label("worker-rank0") == ("worker", 0)
    assert split_label("serve") == ("serve", 0)
    assert split_label("") == ("proc", 0)


def test_delta_hist_window():
    prev, cur = _hist([3, 30]), _hist([3, 30, 300, 3000])
    d = delta_hist(prev, cur)
    assert d["count"] == 2
    assert d["sum"] == pytest.approx(3300.0)
    assert sum(d["counts"]) == 2
    # empty window and reset (negative delta) both yield None
    assert delta_hist(cur, cur) is None
    assert delta_hist(cur, prev) is None
    # prev=None treats the whole cumulative hist as the window
    assert delta_hist(None, cur)["count"] == 4


def test_derive_between_rates_and_quantiles():
    prev = {"counters": {"a.x": 10, "a.reset": 100},
            "histograms": {"h.us": _hist([10])}}
    cur = {"counters": {"a.x": 30, "a.reset": 5, "a.new": 4},
           "histograms": {"h.us": _hist([10, 100, 100, 100])}}
    d = derive_between(prev, cur, 2.0)
    assert d["rates"]["a.x"] == pytest.approx(10.0)
    assert d["rates"]["a.new"] == pytest.approx(2.0)
    assert "a.reset" not in d["rates"]        # negative delta: no rate
    q = d["quantiles"]["h.us"]
    assert q["rate"] == pytest.approx(1.5)
    assert q["mean_us"] == pytest.approx(100.0)
    # windowed p50 sits in the 100us bucket, not skewed by the old 10us
    assert 50.0 <= q["p50_us"] <= 100.0


# --------------------------------------------------------------- recorder
def test_recorder_ring_shard_and_dropped_frames(tmp_path,
                                                enabled_telemetry):
    os.environ["MXNET_TRACE_LABEL"] = "trainer-rank2"
    try:
        rec = Recorder(interval_s=9999.0, ring=8, out_dir=str(tmp_path))
        for i in range(12):
            telemetry.counter_add("test.obs_tick", 2)
            rec.sample_once()
        frames = rec.frames()
        assert len(frames) == 8                      # bounded ring
        assert rec.state()["dropped_frames"] == 4
        assert frames[-1]["rates"]["test.obs_tick"] > 0
        path = rec.flush()
        lines = [json.loads(ln)
                 for ln in open(path).read().splitlines()]
        assert lines[0]["kind"] == "obs-shard"
        assert (lines[0]["role"], lines[0]["rank"]) == ("trainer", 2)
        assert len(lines) == 1 + 8
        assert path.endswith(".obs.jsonl")
        snap = telemetry.raw_snapshot()["counters"]
        assert snap.get("obs.dropped_frames", 0) >= 4
        assert snap.get("obs.frames", 0) >= 12
    finally:
        os.environ.pop("MXNET_TRACE_LABEL", None)


def test_recorder_state_in_dump(tmp_path, enabled_telemetry):
    rec = obs_recorder.start(interval_ms=10)
    try:
        time.sleep(0.1)
        p = str(tmp_path / "d.json")
        telemetry.dump(p, reason="test")
        d = json.load(open(p))
        assert d["obs"]["frames"] >= 1
        assert d["obs"]["running"] is True
        assert "alerts" in d["obs"]
    finally:
        obs_recorder.stop()
    assert not obs_recorder.active()


# ------------------------------------------------------------------ rules
def test_rule_for_duration_and_hysteresis():
    r = Rule("starved", "x", ">", 0.5, for_s=1.0,
             clear_threshold=0.25, clear_for_s=1.0)
    assert r.update(0.0, {"x": 0.9}) is None          # pending
    assert r.state == "pending"
    assert r.update(0.5, {"x": 0.1}) is None          # recovered early
    assert r.state == "ok"
    assert r.update(1.0, {"x": 0.9}) is None
    ev = r.update(2.1, {"x": 0.9})
    assert ev["event"] == "firing" and r.state == "firing"
    # 0.3 is below the FIRING threshold but not inside the CLEAR band:
    # the rule must hold (hysteresis, no flapping)
    assert r.update(3.0, {"x": 0.3}) is None
    assert r.state == "firing"
    assert r.update(4.0, {"x": 0.1}) is None          # clear pending
    ev = r.update(5.1, {"x": 0.1})
    assert ev["event"] == "cleared" and r.state == "ok"
    # a missing metric neither fires nor clears
    r2 = Rule("m", "y", "<", 1.0, for_s=0.0)
    assert r2.update(0.0, {}) is None and r2.state == "ok"


def test_rule_engine_counts_and_logs(enabled_telemetry):
    eng = RuleEngine([Rule("test_alert", "sig", ">", 1.0, for_s=0.0)],
                     log=open(os.devnull, "w"))
    before = telemetry.raw_snapshot()["counters"].get(
        "obs.alerts.test_alert", 0)
    evs = eng.update({"mono": 1.0, "signals": {"sig": 5.0}})
    assert [e["event"] for e in evs] == ["firing"]
    assert eng.firing() == ["test_alert"]
    after = telemetry.raw_snapshot()["counters"]["obs.alerts.test_alert"]
    assert after == before + 1
    assert eng.summary()["rules"]["test_alert"] == "firing"


def test_frame_view_namespaces():
    view = obs_rules.frame_view({
        "signals": {"goodput": 0.5},
        "rates": {"c.x": 2.0},
        "gauges": {"g.y": 7},
        "quantiles": {"h.us": {"p50_us": 10.0, "p99_us": 20.0,
                               "mean_us": 12.0, "rate": 3.0}}})
    assert view["goodput"] == 0.5
    assert view["rate:c.x"] == 2.0
    assert view["gauge:g.y"] == 7.0
    assert view["p99:h.us"] == 20.0
    assert view["hrate:h.us"] == 3.0    # hist rate ≠ counter rate ns


# ---------------------------------------------------------------- signals
def test_signals_compute():
    frame = {
        "rates": {"serve.requests": 10.0, "serve.admitted": 9.0,
                  "serve.rejected": 1.0, "fused.retraces": 0.5},
        "gauges": {"serve.queue_depth": 64,
                   "obs.model_flops_per_step": 1_000_000},
        "quantiles": {
            # the launch alone: 1 ms of a 10 ms step-to-step interval
            "fused.step_us": {"rate": 4.0, "mean_us": 1000.0,
                              "p50_us": 900.0},
            "fused.step_gap_us": {"rate": 4.0, "mean_us": 10000.0},
            "datafeed.wait_us": {"rate": 4.0, "mean_us": 5000.0},
            "checkpoint.pause_us": {"rate": 1.0, "mean_us": 2000.0}},
    }
    old = os.environ.get("MXNET_OBS_PEAK_FLOPS")
    os.environ["MXNET_OBS_PEAK_FLOPS"] = "1e8"
    try:
        sig = obs_signals.compute(frame)
    finally:
        if old is None:
            os.environ.pop("MXNET_OBS_PEAK_FLOPS", None)
        else:
            os.environ["MXNET_OBS_PEAK_FLOPS"] = old
    # both over the step-to-step interval, not over the launch
    assert sig["input_stall_frac"] == pytest.approx(0.5)
    assert sig["ckpt_pause_frac"] == pytest.approx(0.05)
    assert sig["step_p50_us"] == pytest.approx(900.0)
    assert sig["goodput"] == pytest.approx(0.8)
    assert sig["steps_per_s"] == pytest.approx(4.0)
    assert sig["retrace_rate"] == pytest.approx(0.5)
    assert sig["queue_frac"] == pytest.approx(64 / 256.0)
    # mfu = flops/step * steps/s / peak = 1e6 * 4 / 1e8
    assert sig["mfu"] == pytest.approx(0.04)
    # no steps in the window -> stall/ckpt/mfu absent, not 0/inf
    sig2 = obs_signals.compute({"rates": {}, "gauges": {},
                                "quantiles": {}})
    assert "input_stall_frac" not in sig2 and "mfu" not in sig2
    # steps but no waits -> stall is a true 0 (clears the alert)
    sig3 = obs_signals.compute({
        "rates": {}, "gauges": {},
        "quantiles": {"fused.step_us": {"rate": 4.0, "mean_us": 1000.0},
                      "fused.step_gap_us": {"rate": 4.0,
                                            "mean_us": 10000.0}}})
    assert sig3["input_stall_frac"] == 0.0
    assert sig3["ckpt_pause_frac"] == 0.0


def test_signals_without_the_step_gap_report_no_stall_fraction():
    """A frame whose window saw no step follow another (or one from a
    program before PR 38) holds the launch's histogram alone: the two
    fractions are left out, never divided by the launch; the step rate
    keeps its input."""
    sig = obs_signals.compute({
        "rates": {}, "gauges": {},
        "quantiles": {"fused.step_us": {"rate": 4.0, "mean_us": 1000.0,
                                        "p50_us": 900.0},
                      "datafeed.wait_us": {"rate": 4.0, "mean_us": 500.0},
                      "checkpoint.pause_us": {"rate": 1.0,
                                              "mean_us": 2000.0}}})
    assert "input_stall_frac" not in sig and "ckpt_pause_frac" not in sig
    assert sig["steps_per_s"] == pytest.approx(4.0)
    assert sig["step_p50_us"] == pytest.approx(900.0)


def test_signals_published_as_ppm_gauges(enabled_telemetry):
    obs_signals.publish({"goodput": 0.25, "mfu": 0.5})
    g = telemetry.raw_snapshot()["gauges"]
    assert g["obs.goodput_ppm"] == 250000
    assert g["obs.mfu_ppm"] == 500000


# ------------------------------------------------------------------ flops
def test_hybridblock_flops_dense():
    import jax.numpy as jnp
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ndarray import NDArray
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    x = NDArray(jnp.zeros((8, 6), jnp.float32))
    # 2*MACs: 8x6 @ 6x16 + 8x16 @ 16x4 = 2*(8*6*16 + 8*16*4) = 2560
    assert net.flops(x) == 2560
    # model-flops publication: 3x analytic forward
    per_step = obs_signals.publish_model_flops(net, x)
    assert per_step == 3 * 2560
    assert telemetry.raw_snapshot()["gauges"][
        "obs.model_flops_per_step"] == 3 * 2560


def test_hybridblock_flops_conv():
    import jax.numpy as jnp
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.ndarray import NDArray
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, kernel_size=3, padding=1))
    net.initialize()
    net.hybridize()
    # NHWC default layout in this build: (N=2, H=8, W=8, C=3)
    x = NDArray(jnp.zeros((2, 8, 8, 3), jnp.float32))
    # 2 * (kh*kw*cin) * out_elems = 2 * (3*3*3) * (2*8*8*4)
    assert net.flops(x) == 2 * 27 * 512


# ------------------------------------------------------- tools/obs.py
def test_parse_prometheus_roundtrip(enabled_telemetry):
    telemetry.counter_add("test.prom_rt", 7)
    telemetry.gauge_set("test.prom_g", 3)
    for v in (10.0, 400.0):
        telemetry.observe("test.prom_h_us", v)
    tool = _load_tool("obs")
    raw = tool.parse_prometheus(telemetry.dump_prometheus())
    assert raw["counters"]["mxtpu_test_prom_rt"] >= 7
    assert raw["gauges"]["mxtpu_test_prom_g"] == 3
    h = raw["histograms"]["mxtpu_test_prom_h_us"]
    assert h["count"] >= 2 and sum(h["counts"]) == h["count"]
    # de-cumulated buckets feed the shared quantile path unchanged
    assert telemetry.quantile_from_hist(h, 0.5) is not None
    assert tool._dotted("mxtpu_serve_queue_depth") == "serve.queue_depth"
    assert tool._dotted("mxtpu_feed_service_worker_bytes") == \
        "feed_service.worker_bytes"


def test_build_report_roles_signals_straggler():
    tool = _load_tool("obs")
    frames = []
    for t in (1.0, 2.0, 3.0, 4.0):
        frames.append({"t": t, "role": "serve", "rank": 0,
                       "source": "scrape",
                       "rates": {"serve.requests": 10.0,
                                 "serve.admitted": 8.0,
                                 "serve.rejected": 2.0},
                       "quantiles": {}, "gauges": {}})
        for rank, p50 in ((0, 1000.0), (1, 2500.0)):
            frames.append({
                "t": t, "role": "trainer", "rank": rank,
                "source": "shard",
                "rates": {"fused.steps": 5.0 * (1 + t)},   # regressing
                "quantiles": {"fused.step_us":
                              {"p50_us": p50, "rate": 5.0,
                               "mean_us": p50},
                              "fused.step_gap_us":
                              {"p50_us": 20 * p50, "rate": 5.0,
                               "mean_us": 20 * p50}},
                "signals": {"input_stall_frac": 0.1, "mfu": 0.3}})
    rep = tool.build_report({"frames": frames})
    assert rep["roles"]["serve"]["nonzero_rates"] == 3
    assert rep["roles"]["trainer"]["ranks"] == [0, 1]
    assert rep["signals"]["goodput"] == pytest.approx(0.6)
    assert rep["signals"]["input_stall_frac"] == pytest.approx(0.1)
    assert rep["signals"]["mfu"] == pytest.approx(0.3)
    # skew (2500-1000)/1750 ≈ 0.857 > 0.5 → the replayed rule fires
    assert rep["signals"]["straggler_skew"] > 0.5
    assert any(ev["rule"] == "straggler" and ev["event"] == "firing"
               for ev in rep["straggler_alerts"])
    assert any(r["metric"] == "fused.steps"
               for r in rep["regressions"])
    text = tool.render_report(rep)
    assert "straggler" in text and "goodput" in text


def test_read_shards_roundtrip(tmp_path, enabled_telemetry):
    os.environ["MXNET_TRACE_LABEL"] = "trainer-rank1"
    try:
        rec = Recorder(interval_s=9999.0, ring=8, out_dir=str(tmp_path))
        telemetry.counter_add("test.shard_rt", 1)
        rec.sample_once()
        telemetry.counter_add("test.shard_rt", 1)
        rec.sample_once()
        rec.flush()
    finally:
        os.environ.pop("MXNET_TRACE_LABEL", None)
    tool = _load_tool("obs")
    frames = tool.read_shards(str(tmp_path))
    assert frames and all(f["role"] == "trainer" and f["rank"] == 1
                          for f in frames)
    assert any(f["rates"].get("test.shard_rt", 0) > 0 for f in frames)


# ----------------------------------------------------- diagnose --since
def test_diagnose_since_columns(tmp_path, enabled_telemetry):
    telemetry.counter_add("serve.requests", 5)
    p0, p1 = str(tmp_path / "d0.json"), str(tmp_path / "d1.json")
    telemetry.dump(p0, reason="t0")
    telemetry.counter_add("serve.requests", 6)
    telemetry.observe("serve.e2e_us", 123.0)
    telemetry.dump(p1, reason="t1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py"),
         "--telemetry", p1, "--since", p0],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("serve.requests")][0]
    assert "[+6" in line and "/s]" in line
    hline = [ln for ln in r.stdout.splitlines()
             if ln.startswith("serve.e2e_us")][0]
    assert "window" in hline and "count=1" in hline


# --------------------------------------------- SIGUSR2 while sampling
@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform has no SIGUSR2")
def test_sigusr2_dump_with_live_sampler(tmp_path):
    """A dump taken while the sampler thread is mid-flight must not
    deadlock, must list the sampler thread, and must carry the ring
    state under "obs"."""
    dump_path = str(tmp_path / "dump.json")
    code = (
        "import os, signal, time\n"
        "import mxnet_tpu as mx\n"          # autostarts the recorder
        "from mxnet_tpu import obs\n"
        "assert obs.active()\n"
        "mx.telemetry.counter_add('test.obs_sig', 3)\n"
        "time.sleep(0.15)\n"
        "os.kill(os.getpid(), signal.SIGUSR2)\n"
        "time.sleep(0.5)\n"
        "print('ALIVE', len(obs.get().frames()))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "MXNET_TELEMETRY": "1",
           "MXNET_OBS_INTERVAL_MS": "20",
           "MXNET_TELEMETRY_DUMP_PATH": dump_path}
    env.pop("MXNET_OBS_DIR", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "ALIVE" in r.stdout
    d = json.load(open(dump_path))
    assert d["reason"] == "SIGUSR2"
    assert any("obs-sampler" in k for k in d["threads"]), \
        list(d["threads"])
    obs_state = d["obs"]
    assert obs_state["running"] is True
    assert obs_state["frames"] >= 1
    assert isinstance(obs_state["window"], list)
    assert math.isfinite(obs_state["interval_ms"])
