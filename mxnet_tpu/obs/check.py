"""``make obs-check`` — prove the observability plane end to end on a
real mini fleet (nothing mocked, same discipline as chaos-check):

* a **replica** subprocess (``python -m mxnet_tpu.serve
  --selftest-model web``) and a **feed decode worker** subprocess, both
  scraped over their ``/metrics`` endpoints;
* an in-process **router** fronting the replica, carrying light
  open-loop predict traffic;
* an in-process **fused-step trainer** (this process, labeled
  ``trainer-rank0``) consuming the worker through FeedClient→DataFeed,
  with the obs recorder sampling at 100 ms and the seeded watchdog
  armed.

The gate then injects a 250 ms ``client:delay`` fault into the feed
path (FaultDomain re-reads the env every call, so flipping
``MXNET_FEED_FAULT`` live in-process is enough), asserts the
``input_starved`` rule FIRES, removes the fault and asserts the rule
CLEARS through its hysteresis band.  While the fleet is still under
load, ``tools/obs.py scrape`` merges both /metrics targets with the
trainer's recorder shard; the merged report must show every role with
non-zero rates and finite input-stall / goodput / MFU signals.
"""
from __future__ import annotations

import http.client
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

SPEC = "synthetic:8x3x16x16:10:256"
SEED = 7


def _load_tool(name):
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(repo, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_mxtpu_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sub_env(label: str) -> dict:
    """Subprocess env: 1-device CPU, scrubbed dist/fault state, role
    label for its own telemetry artifacts."""
    env = dict(os.environ)
    for k in list(env):
        if k.startswith("DMLC_"):
            env.pop(k)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": " ".join(
            kept + ["--xla_force_host_platform_device_count=1"]),
        "MXNET_TRACE_LABEL": label,
        "MXNET_TELEMETRY": "1",
        "MXNET_TELEMETRY_DUMP_ON_EXIT": "",
        "MXNET_LOCK_CHECK": env.get("MXNET_LOCK_CHECK", "1"),
    })
    for k in ("MXNET_FEED_FAULT", "MXNET_SERVE_FAULT",
              "MXNET_OBS_INTERVAL_MS", "MXNET_OBS_DIR"):
        env.pop(k, None)
    return env


def _wait_ready(port: int, timeout_s: float = 120.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
            c.request("GET", "/healthz")
            ok = c.getresponse().status == 200
            c.close()
            if ok:
                return True
        except OSError:
            pass
        time.sleep(0.25)
    return False


def _serve_load(router, stop_evt: threading.Event, qps: float = 15.0):
    """Light open-loop predict traffic so the serving tier has live
    request rates for the goodput signal while we scrape."""
    import numpy as onp
    rs = onp.random.RandomState(0)
    period = 1.0 / qps
    while not stop_evt.is_set():
        body = json.dumps(
            {"model": "web",
             "inputs": rs.randn(64).astype("float32").tolist()}).encode()
        try:
            router.forward(body)
        except Exception:
            pass                     # replica hiccups are not the gate
        stop_evt.wait(period)


def _train_loop(feed, step, stop_evt: threading.Event, errs: list):
    """Consume the feed through the fused step until told to stop —
    the datafeed.wait_us / fused.step_gap_us ratio IS the stall signal."""
    import jax.numpy as jnp
    from ..ndarray import NDArray
    try:
        while not stop_evt.is_set():
            try:
                b = next(feed)
            except StopIteration:
                feed.reset()         # epoch rollover
                continue
            x = NDArray(jnp.asarray(b.data[0]._data, jnp.float32)
                        .reshape(b.data[0].shape[0], -1))
            y = NDArray(jnp.asarray(b.label[0]._data, jnp.int32)
                        .reshape(-1))
            step(x, y)
            # pace the consumer below the feed pipeline's throughput:
            # a healthy baseline must NOT be input-bound (the toy step
            # is far cheaper than a real model's), or input_stall_frac
            # sits above the clear threshold with no fault at all
            stop_evt.wait(0.01)
        step.sync()
    except Exception as e:           # surfaced as a gate failure
        errs.append(e)


def _poll(predicate, timeout_s: float, interval_s: float = 0.2) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


def _check(verbose: bool = True) -> int:
    os.environ["MXNET_TELEMETRY"] = "1"
    os.environ["MXNET_TRACE_LABEL"] = "trainer-rank0"
    # rig constant for the MFU signal: tiny on purpose, so the toy
    # model's utilization is comfortably finite and non-zero on CPU
    os.environ.setdefault("MXNET_OBS_PEAK_FLOPS", "1e9")
    os.environ.pop("MXNET_FEED_FAULT", None)

    from .. import telemetry as _telemetry
    from ..serve.router import Router
    from ..io.data_service import FeedClient
    from ..io.datafeed import DataFeed
    from ..gluon import nn, Trainer
    from ..gluon.loss import SoftmaxCrossEntropyLoss
    from . import recorder as _recorder

    obs_dir = tempfile.mkdtemp(prefix="mxtpu-obs-check-")
    procs, failures = [], []
    stop_evt = threading.Event()
    train_errs: list = []
    rec = None
    router = None
    feed = None

    def note(name, ok, detail=""):
        if not ok:
            failures.append(name)
        if verbose:
            print(f"[obs-check] {'ok  ' if ok else 'FAIL'} {name}"
                  + (f" — {detail}" if detail else ""))

    try:
        # ------------------------------------------------ fleet bring-up
        rport, fport = _free_port(), _free_port()
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.serve",
             "--selftest-model", "web", "--host", "127.0.0.1",
             "--port", str(rport)],
            env=_sub_env("serve0"), stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.io.data_service",
             "--worker", "--spec", SPEC, "--seed", str(SEED),
             "--host", "127.0.0.1", "--port", str(fport)],
            env=_sub_env("feed-worker0"), stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
        note("replica ready", _wait_ready(rport), f"port {rport}")
        note("feed worker ready", _wait_ready(fport), f"port {fport}")
        if failures:
            return 1

        router = Router([f"127.0.0.1:{rport}"], port=_free_port(),
                        probe_interval_ms=200.0).start()

        # recorder + watchdog armed BEFORE the first fused step so the
        # jit build publishes the model-flops gauge into a live ring.
        # 250 ms sampling: every window must contain at least one step
        # even under the injected 150 ms feed delay, or the stall
        # signal goes missing and the rule's for_s clock resets
        rec = _recorder.start(interval_ms=250, out_dir=obs_dir)
        note("recorder running", rec is not None and rec.running())

        feed = DataFeed(
            FeedClient(workers=[f"127.0.0.1:{fport}"], spec=SPEC,
                       seed=SEED, prefetch=4, retries=4,
                       timeout_ms=5000),
            depth=4)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(10))
        net.initialize()
        net.hybridize()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05})
        step = tr.fuse_step(SoftmaxCrossEntropyLoss())

        threading.Thread(target=_serve_load, args=(router, stop_evt),
                         daemon=True).start()
        threading.Thread(target=_train_loop,
                         args=(feed, step, stop_evt, train_errs),
                         daemon=True).start()

        engine = rec.engine

        # healthy steady state: steps flowing, no input_starved yet
        note("steady state reached", _poll(
            lambda: any(f.get("signals", {}).get("steps_per_s", 0) > 0
                        for f in rec.frames()), 60.0))

        # ------------------------------- fault: feed fetch delay 150 ms
        # (the `client` site fires inside THIS process's FeedClient;
        # FaultDomain re-reads the env on every call)
        def _events():
            return [(e["rule"], e["event"]) for e in engine.events]

        os.environ["MXNET_FEED_FAULT"] = "client:delay:1.0:150"
        fired = _poll(
            lambda: ("input_starved", "firing") in _events(), 45.0)
        note("input_starved fires under feed fault", fired,
             f"events={_events()}")

        # ------------------------------------ clear: hysteresis release
        os.environ.pop("MXNET_FEED_FAULT", None)
        cleared = _poll(
            lambda: ("input_starved", "cleared") in _events(), 45.0)
        note("input_starved clears after fault removed", cleared,
             f"events={_events()}")
        kinds = _events()
        note("watchdog logged firing→cleared transition",
             fired and cleared
             and kinds.index(("input_starved", "firing"))
             < kinds.index(("input_starved", "cleared")), f"{kinds}")
        snap = _telemetry.raw_snapshot()["counters"]
        note("obs.alerts.input_starved counted",
             snap.get("obs.alerts.input_starved", 0) >= 1)

        # -------------------------- merge the fleet while still loaded
        rec.flush()
        obs_tool = _load_tool("obs")
        timeline = obs_tool.scrape(
            [f"serve@127.0.0.1:{rport}", f"feed@127.0.0.1:{fport}"],
            shards_dir=obs_dir, interval_ms=400.0, duration_s=2.5)
        rec.flush()      # pick up frames landed during the scrape too
        timeline["frames"].extend(
            f for f in obs_tool.read_shards(obs_dir)
            if f["t"] > max((x["t"] for x in timeline["frames"]
                             if x.get("source") == "shard"),
                            default=0.0))
        report = obs_tool.build_report(timeline)
        if verbose:
            sys.stdout.write(obs_tool.render_report(report))

        roles = report["roles"]
        for role in ("serve", "feed", "trainer"):
            note(f"role {role} merged with non-zero rates",
                 roles.get(role, {}).get("nonzero_rates", 0) > 0,
                 f"{roles.get(role)}")
        sig = report["signals"]
        import math
        for name in ("input_stall_frac", "goodput", "mfu"):
            v = sig.get(name)
            note(f"signal {name} present and finite",
                 v is not None and math.isfinite(v), f"{name}={v}")
        note("mfu non-zero", bool(sig.get("mfu", 0.0) > 0.0),
             f"mfu={sig.get('mfu')}")
        note("trainer thread healthy", not train_errs,
             f"{train_errs[:1]}")
        return 1 if failures else 0
    finally:
        stop_evt.set()
        os.environ.pop("MXNET_FEED_FAULT", None)
        try:
            if rec is not None:
                _recorder.stop()
        except Exception:
            pass
        try:
            if feed is not None:
                feed.close()
        except Exception:
            pass
        try:
            if router is not None:
                router.stop()
        except Exception:
            pass
        for p in procs:
            try:
                p.terminate()
                p.wait(10)
            except Exception:
                try:
                    p.kill()
                except Exception:
                    pass
        shutil.rmtree(obs_dir, ignore_errors=True)


def _main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.obs", description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="run the mini-fleet observability gate")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if not args.check:
        ap.error("nothing to do (want --check)")
    rc = _check(verbose=not args.quiet)
    print(f"[obs-check] {'OK' if rc == 0 else 'FAIL'}")
    return rc
