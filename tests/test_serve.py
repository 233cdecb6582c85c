"""Serving tier (mxnet_tpu/serve/): continuous batching + HTTP front end.

The contracts under test:

- bucket selection / padding: a row's prediction is BIT-FOR-BIT what the
  same bucket program gives it alone — whatever fills the other rows,
  pad or neighbour, never leaks into it; the pad rows are computed and
  discarded, never returned.  Across buckets (and against the unbatched
  eager forward) two XLA programs agree within float tolerance, not in
  the last bits
- deadline flush: a lone request is served once max-wait expires, it
  does not wait for a full bucket
- admission control: a full bounded queue raises QueueFull at the
  batcher and maps to HTTP 429 at the front end — load is shed, not
  collapsed on
- multi-model multi-tenancy: per-model queues are isolated (one
  model's overload leaves another's latency untouched) and the
  registry LRU-evicts past its cap
- model loading: both trainer serialization formats round-trip into a
  FRESH deferred-init net — a CheckpointManager root via
  restore(subtree="params") (no Trainer on the serving host) and a
  .params file
- live server: a localhost HTTP round-trip through /v1/predict returns
  the same numbers, and /healthz /metrics /v1/models respond
- telemetry.quantile interpolates the fixed µs buckets (the audited
  p50/p99 path) and pure_fn(train=False) returns outputs only
"""
import json
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.serve import (Batcher, InferenceEngine, InferenceServer,
                             ModelRegistry, QueueFull, bucket_ladder)

ITEM = (12,)


def _small_net(seed=0, out=5, materialize=False):
    mx.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(24, activation="relu"), nn.Dense(out))
    net.initialize()
    net.hybridize()
    if materialize:     # publish deferred shapes (save/export paths)
        net(mx.np.array(onp.zeros((1,) + ITEM, "float32")))
    return net


def _ref(net, x):
    """Unbatched eager forward of one item (the parity oracle)."""
    return onp.asarray(net(mx.np.array(x[None]))._data)


def _close(a, b):
    """Agreement across two XLA programs (different batch sizes)."""
    return onp.allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ engine
def test_bucket_ladder_resolution(monkeypatch):
    assert bucket_ladder((8, 1, 4, 2, 4)) == (1, 2, 4, 8)
    monkeypatch.setenv("MXNET_SERVE_BUCKETS", "2, 4,16")
    assert bucket_ladder() == (2, 4, 16)
    monkeypatch.delenv("MXNET_SERVE_BUCKETS")
    assert bucket_ladder() == (1, 2, 4, 8)
    with pytest.raises(ValueError):
        bucket_ladder((0, 2))


def test_engine_bucket_selection_and_warmup():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(1, 2, 4), name="sel")
    assert eng.bucket_for(1) == 1
    assert eng.bucket_for(2) == 2
    assert eng.bucket_for(3) == 4
    with pytest.raises(ValueError):
        eng.bucket_for(5)
    eng.warmup()
    assert eng.warm and eng.retraces == 0
    # every ladder rung compiled exactly once during warmup
    assert all(c == 1 for c in eng.trace_counts().values())
    # post-warmup executions reuse the programs — still zero retraces
    x = onp.zeros((2,) + ITEM, "float32")
    eng.run(x)
    assert eng.retraces == 0 and eng.trace_counts()[2] == 1


def test_batched_forward_bit_for_bit_vs_unbatched():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(1, 2, 4, 8)).warmup()
    rs = onp.random.RandomState(3)
    xs = rs.randn(8, *ITEM).astype("float32")
    outs = onp.asarray(eng.run(xs)[0])
    for i in range(8):
        # same bucket, the row alone among zeros: equal bits
        alone = onp.zeros_like(xs)
        alone[i] = xs[i]
        assert (onp.asarray(eng.run(alone)[0])[i] == outs[i]).all()
        # another program (bucket 1 of the unbatched forward): close
        assert _close(outs[i:i + 1], _ref(net, xs[i]))


# ----------------------------------------------------------------- batcher
def test_padding_partial_batch_bit_for_bit():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(4,)).warmup()
    telemetry.reset()
    with Batcher(eng, max_wait_ms=5, name="pad") as b:
        rs = onp.random.RandomState(4)
        x = rs.randn(3, *ITEM).astype("float32")   # 3 rows → bucket 4
        (out,) = b.submit(x)
        assert out.shape == (3, 5)                 # pad row not returned
        # the same bucket run directly, whatever sits in the pad row
        for pad in (0.0, 1e3):
            direct = onp.asarray(eng.run(onp.concatenate(
                [x, onp.full((1,) + ITEM, pad, "float32")]))[0])
            assert (direct[:3] == out).all()
        for i in range(3):
            assert _close(out[i:i + 1], _ref(net, x[i]))
    c = telemetry.raw_snapshot()["counters"]
    assert c.get("serve.padded", 0) == 1
    assert c.get("serve.batches", 0) == 1


def test_deadline_flush_serves_lone_request():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(1, 8)).warmup()
    with Batcher(eng, max_wait_ms=40, name="flush") as b:
        x = onp.random.RandomState(5).randn(*ITEM).astype("float32")
        t0 = time.perf_counter()
        (out,) = b.submit(x, timeout=10.0)
        elapsed = time.perf_counter() - t0
        assert (out == _ref(net, x)).all()
        # flushed by the deadline, not by an (unreachable) full bucket
        assert elapsed < 5.0


def test_concurrent_burst_coalesces():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(1, 2, 4, 8)).warmup()
    telemetry.reset()
    with Batcher(eng, max_wait_ms=30, name="burst") as b:
        n = 12
        rs = onp.random.RandomState(6)
        xs = [rs.randn(*ITEM).astype("float32") for _ in range(n)]
        results = [None] * n
        barrier = threading.Barrier(n)

        def client(i):
            barrier.wait()
            results[i] = b.submit(xs[i], timeout=20.0)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30.0)
        for i in range(n):
            assert results[i] is not None
            # whichever bucket each request was coalesced into
            assert _close(results[i][0], _ref(net, xs[i]))
    c = telemetry.raw_snapshot()["counters"]
    assert c.get("serve.coalesced_batches", 0) >= 1
    assert eng.retraces == 0


def test_admission_control_queue_full():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(8,)).warmup()
    # deadline far away + bucket never fills ⇒ submissions sit queued
    b = Batcher(eng, max_wait_ms=5000, queue_depth=3, name="full")
    try:
        x = onp.zeros(ITEM, "float32")
        reqs = [b.submit_async(x) for _ in range(3)]
        with pytest.raises(QueueFull):
            b.submit_async(x)
    finally:
        b.close()       # drains: queued requests still get served
    for r in reqs:
        assert r.event.wait(10.0) and r.error is None


def test_submit_shape_validation():
    net = _small_net()
    eng = InferenceEngine(net, ITEM, buckets=(1, 2)).warmup()
    with Batcher(eng, name="shapes") as b:
        with pytest.raises(ValueError):
            b.submit(onp.zeros((7,), "float32"))       # wrong item shape
        with pytest.raises(ValueError):
            b.submit(onp.zeros((3,) + ITEM, "float32"))  # > max bucket


# ---------------------------------------------------------------- registry
def test_multi_model_isolation():
    reg = ModelRegistry(max_models=4, max_wait_ms=5000, queue_depth=2)
    try:
        a = reg.register("tenant_a", _small_net(seed=1), ITEM,
                         buckets=(8,))
        reg.register("tenant_b", _small_net(seed=2), ITEM,
                     buckets=(1, 2, 4))
        # drown tenant_a: its bounded queue fills and rejects...
        x = onp.zeros(ITEM, "float32")
        a.batcher.submit_async(x)
        a.batcher.submit_async(x)
        with pytest.raises(QueueFull):
            reg.predict("tenant_a", x)
        # ...while tenant_b still serves promptly
        xb = onp.random.RandomState(9).randn(*ITEM).astype("float32")
        (out,) = reg.predict("tenant_b", xb, timeout=10.0)
        assert (out == _ref(reg.get("tenant_b").net, xb)).all()
    finally:
        reg.close()


def test_registry_lru_eviction():
    reg = ModelRegistry(max_models=2)
    try:
        for i, name in enumerate(("m0", "m1", "m2")):
            reg.register(name, _small_net(seed=i), ITEM, buckets=(1, 2))
        assert reg.names() == ["m1", "m2"]      # m0 was LRU-evicted
        with pytest.raises(KeyError):
            reg.get("m0")
        # predicting on m1 touches it; registering m3 now evicts m2
        reg.predict("m1", onp.zeros(ITEM, "float32"))
        reg.register("m3", _small_net(seed=3), ITEM, buckets=(1, 2))
        assert reg.names() == ["m1", "m3"]
    finally:
        reg.close()
    # evicted/closed batchers leave no serve threads behind
    time.sleep(0.1)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("serve-")]


def test_load_from_checkpoint_manifest():
    from mxnet_tpu.checkpoint import CheckpointManager
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.randn(8, *ITEM).astype("float32"))
    y = mx.np.array(rs.randint(0, 5, (8,)).astype("int32"))
    net = _small_net()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = tr.fuse_step(SoftmaxCrossEntropyLoss())
    for _ in range(2):
        step(x, y)
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td, async_write=False)
        cm.save_trainer(tr, blocking=True)
        # params-only restore into a FRESH deferred-init net: no
        # Trainer, no optimizer states, shapes published from shards
        fresh = nn.HybridSequential()
        fresh.add(nn.Dense(24, activation="relu"), nn.Dense(5))
        reg = ModelRegistry(max_models=2)
        try:
            reg.load("ckpt_model", td, net=fresh, item_shape=ITEM)
            xi = rs.randn(*ITEM).astype("float32")
            (out,) = reg.predict("ckpt_model", xi)
            assert (out == _ref(net, xi)).all()
        finally:
            reg.close()


def test_load_from_params_file():
    net = _small_net(seed=11, materialize=True)
    with tempfile.TemporaryDirectory() as td:
        path = td + "/model.params"
        net.save_parameters(path)
        fresh = nn.HybridSequential()
        fresh.add(nn.Dense(24, activation="relu"), nn.Dense(5))
        reg = ModelRegistry(max_models=2)
        try:
            reg.load("file_model", path, net=fresh, item_shape=ITEM)
            xi = onp.random.RandomState(12).randn(*ITEM).astype("float32")
            (out,) = reg.predict("file_model", xi)
            assert (out == _ref(net, xi)).all()
        finally:
            reg.close()


def test_restore_subtree_params_only():
    """The checkpoint.py satellite directly: subtree= returns just the
    flat param dict, full validation still applies, and a missing
    subtree falls through to NoCheckpointError."""
    from mxnet_tpu.checkpoint import CheckpointManager, NoCheckpointError
    net = _small_net(materialize=True)
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td, async_write=False)
        cm.save_trainer(tr, blocking=True)
        tree, meta, s = cm.restore(subtree="params")
        assert sorted(tree) == sorted(net.collect_params().keys())
        for k, p in net.collect_params().items():
            assert (onp.asarray(tree[k]) ==
                    onp.asarray(p.data()._data)).all()
        full, _, _ = cm.restore()
        assert "params" in full and full["params"].keys() == tree.keys()
        with pytest.raises(NoCheckpointError):
            cm.restore(subtree="no_such_subtree")


# ------------------------------------------------------------- http server
@pytest.fixture
def live_server():
    reg = ModelRegistry(max_models=2)
    net = _small_net(seed=21)
    reg.register("web", net, ITEM, buckets=(1, 2, 4))
    srv = InferenceServer(reg, host="127.0.0.1", port=0).start()
    yield srv, net
    srv.stop(close_registry=True)


def _post(url, obj, timeout=15.0):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_live_server_round_trip(live_server):
    srv, net = live_server
    base = f"http://127.0.0.1:{srv.port}"
    xi = onp.random.RandomState(22).randn(*ITEM).astype("float32")
    status, body = _post(base + "/v1/predict",
                         {"model": "web", "inputs": xi.tolist()})
    assert status == 200 and body["model"] == "web"
    got = onp.asarray(body["outputs"][0], dtype="float32")
    # float32 → JSON double → float32 is exact: still bit-for-bit
    assert (got == _ref(net, xi)).all()

    with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
        assert r.status == 200
        assert "web" in json.loads(r.read())["models"]
    with urllib.request.urlopen(base + "/v1/models", timeout=10) as r:
        models = json.loads(r.read())["models"]
        assert models["web"]["warm"] and models["web"]["retraces"] == 0
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        text = r.read().decode()
        assert "mxtpu_serve_batches" in text
        assert "mxtpu_serve_e2e_us_bucket" in text


def test_http_error_paths(live_server):
    srv, _net = live_server
    base = f"http://127.0.0.1:{srv.port}"
    xi = onp.zeros(ITEM, "float32").tolist()
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(base + "/v1/predict", {"model": "nope", "inputs": xi})
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(base + "/v1/predict", {"inputs": xi})
    assert e.value.code == 400


def test_http_429_when_queue_full():
    reg = ModelRegistry(max_models=1, max_wait_ms=5000, queue_depth=2)
    net = _small_net(seed=23)
    entry = reg.register("shed", net, ITEM, buckets=(8,))
    srv = InferenceServer(reg, host="127.0.0.1", port=0).start()
    try:
        # pre-fill the bounded queue; the bucket (8) can't fill and the
        # deadline is far away, so the next arrival must be shed
        x = onp.zeros(ITEM, "float32")
        reqs = [entry.batcher.submit_async(x) for _ in range(2)]
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"http://127.0.0.1:{srv.port}/v1/predict",
                  {"model": "shed", "inputs": x.tolist()})
        assert e.value.code == 429
        assert e.value.headers.get("Retry-After")
    finally:
        srv.stop(close_registry=True)
    for r in reqs:      # close() drained them
        assert r.event.wait(10.0)


# ---------------------------------------------------------------- plumbing
def test_telemetry_quantile_interpolation():
    telemetry.reset()
    # four samples inside (2, 5]: rank interpolation is exact
    for v in (3.0, 3.0, 4.0, 4.0):
        telemetry.observe("serve.qtest_us", v)
    h = telemetry.raw_snapshot()["histograms"]["serve.qtest_us"]
    # all 4 in one bucket: p50 → lo + (2/4)*(5-2) = 3.5
    assert telemetry.quantile_from_hist(h, 0.5) == pytest.approx(3.5)
    assert telemetry.quantile_from_hist(h, 1.0) == pytest.approx(5.0)
    assert telemetry.quantile("serve", "qtest_us", 0.5) == \
        pytest.approx(3.5)
    assert telemetry.quantile("serve", "missing_us", 0.5) is None
    assert telemetry.quantile_from_hist(
        {"le": [], "counts": [], "count": 0, "sum": 0.0}, 0.5) is None


def test_pure_fn_inference_mode():
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Dropout(0.5), nn.Dense(3))
    net.initialize()
    net.hybridize()
    x = mx.np.array(onp.random.RandomState(30).randn(4, 6)
                    .astype("float32"))
    _ = net(x)          # materialize deferred shapes + running stats
    fn, params = net.pure_fn(x, train=False)
    pvals = {n: p.data()._data for n, p in params.items()}
    outs = fn(jax.random.PRNGKey(0), pvals, x._data)
    # outputs only — no aux tail in inference mode
    assert isinstance(outs, tuple) and len(outs) == 1
    # dropout is identity and BatchNorm uses running stats: the trace
    # matches the eager prediction-mode forward exactly
    assert (onp.asarray(outs[0]) == onp.asarray(net(x)._data)).all()
    # and it is deterministic across calls (no live rng dependence)
    outs2 = fn(jax.random.PRNGKey(1), pvals, x._data)
    assert (onp.asarray(outs[0]) == onp.asarray(outs2[0])).all()
