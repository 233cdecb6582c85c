"""INT8 PTQ parity (reference src/operator/quantization/ N13 +
contrib/quantization.py P14; tests/python/quantization/)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import quantization as q
from mxnet_tpu.gluon import nn


def test_quantize_dequantize_roundtrip():
    x = mx.np.array(np.random.RandomState(0).randn(4, 8).astype("float32"))
    qd, lo, hi = q.quantize_v2(x)
    assert qd.asnumpy().dtype == np.int8
    back = q.dequantize(qd, lo, hi)
    step = float(hi.asnumpy()) / 127
    assert np.abs(back.asnumpy() - x.asnumpy()).max() < step / 2 + 1e-7


def test_quantize_with_calib_range():
    x = mx.np.array(np.array([[-5.0, 0.5, 3.0]], np.float32))
    qd, lo, hi = q.quantize_v2(x, min_calib_range=-2.0, max_calib_range=2.0)
    # values beyond the calib range clip to ±127
    assert qd.asnumpy()[0, 0] == -127
    assert float(hi.asnumpy()) == 2.0


def test_entropy_threshold_distributions():
    rng = np.random.RandomState(1)
    t_uni = q._get_optimal_threshold(rng.rand(5000))
    assert 0.8 < t_uni <= 1.01          # uniform: keep ~everything
    t_gauss = q._get_optimal_threshold(rng.randn(10000))
    assert 2.0 < t_gauss < 4.5          # gaussian: clip far tail


def test_quantize_net_cnn_naive():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(), nn.Flatten(),
            nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize()
    rng = np.random.RandomState(1)
    calib = [mx.np.array(rng.rand(8, 16, 16, 3).astype("float32"))
             for _ in range(4)]
    xt = mx.np.array(rng.rand(8, 16, 16, 3).astype("float32"))
    ref = net(xt).asnumpy()
    q.quantize_net(net, calib_data=calib, calib_mode="naive")
    # blocks actually replaced
    kinds = [type(b).__name__ for b in net]
    assert "QuantizedConv2D" in kinds and "QuantizedDense" in kinds
    out = net(xt).asnumpy()
    rel = np.abs(out - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert rel < 0.05, rel
    assert (out.argmax(1) == ref.argmax(1)).mean() >= 0.75


def test_quantize_net_entropy_and_exclude():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(2)
    xe = mx.np.array(rng.rand(64, 8).astype("float32"))
    ref = net(xe).asnumpy()
    # exclude the output layer (reference flow excludes sensitive layers)
    q.quantize_net(net, calib_data=[xe], calib_mode="entropy",
                   exclude_layers=["1"])
    kinds = [type(b).__name__ for b in net]
    assert kinds[0] == "QuantizedDense" and kinds[1] == "Dense"
    out = net(xe).asnumpy()
    rel = np.abs(out - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert rel < 0.1, rel


def test_quantize_net_requires_calib_data():
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    net(mx.np.array(np.zeros((1, 3), np.float32)))
    with pytest.raises(ValueError):
        q.quantize_net(net, calib_data=None, calib_mode="naive")


def test_contrib_namespace():
    assert mx.contrib.quantization.quantize_net is q.quantize_net


def test_quantize_net_after_hybridize():
    """Calibration must see layer inputs even if the net was hybridized
    (cached jit bypasses python forwards)."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(3)
    x = mx.np.array(rng.rand(32, 8).astype("float32"))
    ref = net(x).asnumpy()
    net.hybridize()
    net(x)                          # warm the cache
    q.quantize_net(net, calib_data=[x], calib_mode="naive")
    out = net(x).asnumpy()
    assert (out.argmax(1) == ref.argmax(1)).mean() >= 0.9


def test_conv_bn_folding_numerics():
    """Conv→BN folds into the conv (scoring): folded fp32 net matches the
    original closely, and quantize_net removes the BN pass entirely."""
    import numpy as onp
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.quantization import _fold_batchnorm, _Identity

    mx.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
            nn.Activation("relu"), nn.Conv2D(4, 3, padding=1),
            nn.BatchNorm())
    net.initialize()
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.rand(2, 8, 8, 3).astype("float32"))
    net(x)  # materialize + settle running stats
    ref = net(x).asnumpy()
    _fold_batchnorm(net)
    assert sum(isinstance(l, _Identity) for l in net._layers) == 2
    got = net(x).asnumpy()
    assert onp.allclose(got, ref, atol=1e-4), onp.abs(got - ref).max()


def test_per_channel_weight_scales_roundtrip():
    """Per-output-channel scales: each channel keeps its own resolution
    even when channel magnitudes span five orders of magnitude (a
    per-tensor scale would crush the small channels to zero)."""
    rng = np.random.RandomState(4)
    w = rng.randn(6, 16).astype(np.float32) * \
        np.array([1e-3, 1e-2, 0.1, 1, 10, 100], np.float32)[:, None]
    s = q._channel_scales(w, axes=1)
    qw = np.clip(np.round(w * s[:, None]), -127, 127).astype(np.int8)
    back = qw.astype(np.float32) / s[:, None]
    for c in range(w.shape[0]):
        step = np.abs(w[c]).max() / 127
        assert np.abs(back[c] - w[c]).max() <= step / 2 + 1e-9, c


def test_telemetry_calibration_parity_with_minmax():
    """A scoring run under observe_activations hooks, then
    thresholds_from_telemetry(naive) — must equal the direct max|x| of
    the calibration stream exactly (the amax gauge is ×1e6 fixed point,
    not a lossy histogram read-back)."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(5)
    batches = [mx.np.array((rng.randn(16, 8) * 3).astype("float32"))
               for _ in range(3)]
    net(batches[0])                 # materialize params before hooking
    handle = q.observe_activations(net, sample=64)
    try:
        for b in batches:
            net(b)
    finally:
        handle.remove()
    th = q.thresholds_from_telemetry(layers={"0", "1"})
    direct = max(float(np.abs(b.asnumpy()).max()) for b in batches)
    assert abs(th["0"] - direct) <= 2e-6 * max(1.0, direct), (th, direct)
    assert th["1"] > 0.0


def test_telemetry_entropy_from_bucket_hist():
    """Entropy mode re-expands the geometric registry buckets onto the
    linear KL grid: the gaussian tail is clipped strictly below amax,
    the result never exceeds the amax cap, and a missing histogram falls
    back to the (exact) naive gauge."""
    from mxnet_tpu.telemetry import BUCKET_BOUNDS_US
    rng = np.random.RandomState(6)
    data = np.abs(rng.randn(20000) * 0.03)
    amax = float(data.max())
    fix = data * 1e6
    counts, lo = [], 0.0
    for b in BUCKET_BOUNDS_US:
        counts.append(int(((fix > lo) & (fix <= b)).sum()))
        lo = b
    counts.append(int((fix > lo).sum()))        # +inf overflow bucket
    snap = {"gauges": {"quant.amax.fc": int(round(amax * 1e6))},
            "histograms": {"quant.act.fc": {"le": list(BUCKET_BOUNDS_US),
                                            "counts": counts}}}
    naive = q.thresholds_from_telemetry(snap=snap)["fc"]
    ent = q.thresholds_from_telemetry(mode="entropy", snap=snap)["fc"]
    assert abs(naive - amax) <= 1e-6
    assert 0.0 < ent < amax             # tail clipped, cap respected
    # entropy without the act histogram degrades to the naive gauge
    bare = {"gauges": dict(snap["gauges"]), "histograms": {}}
    assert q.thresholds_from_telemetry(mode="entropy",
                                       snap=bare)["fc"] == naive


def test_quantize_net_explicit_thresholds():
    """thresholds= covering every site needs no calib_data; partial
    coverage without calib_data must refuse, never silently quantize
    with a garbage threshold."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(7)
    x = mx.np.array(rng.rand(32, 8).astype("float32"))
    ref = net(x).asnumpy()
    h = list(net)[0](x).asnumpy()
    th = {"0": float(np.abs(x.asnumpy()).max()),
          "1": float(np.abs(h).max())}
    q.quantize_net(net, thresholds=th, calib_mode="naive")
    out = net(x).asnumpy()
    assert (out.argmax(1) == ref.argmax(1)).mean() >= 0.9

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4))
    net2.initialize()
    net2(mx.np.array(np.zeros((1, 3), np.float32)))
    with pytest.raises(ValueError):
        q.quantize_net(net2, thresholds={"not_a_layer": 1.0},
                       calib_mode="naive")


def test_int8_pallas_vs_xla_parity():
    """The Pallas int8 implicit-GEMM (interpret mode off-TPU) must match
    the XLA int32-accumulating route bit-for-bit up to f32 epilogue
    rounding, for every epilogue variant."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_int8 as pi8
    rng = np.random.RandomState(8)
    qx = jnp.asarray(rng.randint(-127, 128, (2, 8, 8, 8)), jnp.int8)
    qw = jnp.asarray(rng.randint(-127, 128, (3, 3, 8, 16)), jnp.int8)
    scale = jnp.asarray((rng.rand(16) * 1e-3).astype(np.float32))
    shift = jnp.asarray((rng.randn(16) * 0.1).astype(np.float32))
    res = jnp.asarray(rng.randn(2, 8, 8, 16).astype(np.float32))
    for kw in ({"relu": False}, {"relu": True},
               {"res": res, "relu": True}):
        a = np.asarray(pi8.qconv3x3_affine(qx, qw, scale, shift, **kw))
        b = np.asarray(pi8.qconv3x3_xla(qx, qw, scale, shift, **kw))
        assert np.abs(a - b).max() < 1e-4, kw


def _fusable_net(channels=256, seed=9):
    """A stem and one BasicBlockV1 on the ``14x14x256`` stage, which the
    int8 table routes, and its (2, 14, 14, 3) input."""
    from mxnet_tpu.models.resnet import BasicBlockV1
    mx.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(channels, 3, padding=1), nn.BatchNorm(),
            nn.Activation("relu"), BasicBlockV1(channels, stride=1))
    net.initialize()
    x = mx.np.array(np.random.RandomState(seed).rand(2, 14, 14, 3)
                    .astype("float32"))
    net(x)                          # materialize + settle running stats
    return net, x


def test_quantize_net_fused_block_route(monkeypatch):
    """The fused residual-block route survives quantization: the
    QuantizedConv2D twins carry fused_forward, the routed stage fires
    the int8 Pallas kernel (interpret mode), and accuracy holds."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_block

    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    net, x = _fusable_net()
    ref = net(x).asnumpy()
    hits0 = telemetry.raw_snapshot()["counters"].get(
        "quant.int8.hits.14x14x256", 0)
    q.quantize_net(net, calib_data=[x], calib_mode="naive")
    got = net(x).asnumpy()
    rel = np.abs(got - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert rel < 0.1, rel
    hits1 = telemetry.raw_snapshot()["counters"].get(
        "quant.int8.hits.14x14x256", 0)
    assert hits1 > hits0            # the Pallas int8 route actually fired
    twins = [b for _, b, _ in q._walk(net)
             if isinstance(b, q.QuantizedConv2D)]
    assert twins and all(hasattr(b, "fused_forward") for b in twins)


def test_quantize_net_calibrates_layerwise_and_leaves_the_environment(
        monkeypatch):
    """Where the blocks would fuse (BatchNorm kept, so conv + BN is one
    fused op that passes ``Conv2D.forward`` by), the rewrite still runs
    every layer's own forward: each quantizable layer is calibrated —
    and the program's environment is not how it says so: ``os.environ``
    is byte-identical after."""
    import os
    from mxnet_tpu.gluon import nn as gnn
    from mxnet_tpu.ops import pallas_block

    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    net, x = _fusable_net(seed=10)
    assert gnn.fused_block_active()
    sites = [p for _, c, p in q._walk(net) if isinstance(c, nn.Conv2D)]
    assert len(sites) == 3
    seen = []
    real = q._Collector.add
    monkeypatch.setattr(q._Collector, "add",
                        lambda self, path, arr: (seen.append(path),
                                                 real(self, path, arr))[1])
    before = dict(os.environ)
    q.quantize_net(net, calib_data=[x], calib_mode="naive", fold_bn=False)
    assert dict(os.environ) == before
    assert sorted(set(seen)) == sorted(sites)
    assert gnn.fused_block_active()         # the context was left
    twins = [b for _, b, _ in q._walk(net)
             if isinstance(b, q.QuantizedConv2D)]
    assert len(twins) == 3 and all(b._in_t > 0 for b in twins)


def test_serve_precision_resolution(monkeypatch):
    from mxnet_tpu.serve.engine import resolve_precision
    monkeypatch.delenv("MXNET_SERVE_PRECISION", raising=False)
    assert resolve_precision() == "fp32"
    assert resolve_precision("bfloat16") == "bf16"
    assert resolve_precision("float32") == "fp32"
    monkeypatch.setenv("MXNET_SERVE_PRECISION", "int8")
    assert resolve_precision() == "int8"
    assert resolve_precision("fp32") == "fp32"          # argument wins
    monkeypatch.setenv("MXNET_SERVE_PRECISION", "int4")
    with pytest.raises(ValueError):
        resolve_precision()


def test_serve_int8_routing_and_admission():
    """precision="int8" at the registry quantizes the engine's net, and
    admission control stays precision-agnostic: the bounded queue still
    sheds with QueueFull (the HTTP 429 path)."""
    import numpy as onp
    from mxnet_tpu.serve import ModelRegistry, QueueFull

    mx.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(5))
    net.initialize()
    net(mx.np.array(np.zeros((1, 12), np.float32)))
    reg = ModelRegistry(max_models=2, max_wait_ms=300, queue_depth=2,
                        precision="int8")
    try:
        entry = reg.register("q", net, (12,), buckets=(8,))
        assert entry.engine.precision == "int8"
        assert entry.stats()["precision"] == "int8"
        x = onp.random.RandomState(0).randn(12).astype("float32")
        (out,) = reg.predict("q", x, timeout=10.0)
        assert out.shape[-1] == 5
        entry.batcher.submit_async(x)
        entry.batcher.submit_async(x)
        with pytest.raises(QueueFull):
            entry.batcher.submit_async(x)
    finally:
        reg.close()


def test_precision_flip_rekeys_dispatch(monkeypatch):
    """An engine keys its programs on the precision it resolved itself:
    two engines built under different ``MXNET_SERVE_PRECISION`` differ
    in ``_fp()``, and nothing below the serving layer reads the name."""
    from mxnet_tpu.serve.engine import InferenceEngine

    def engine():
        mx.seed(12)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
        net.initialize()
        net(mx.np.array(np.zeros((1, 6), np.float32)))
        return InferenceEngine(net, (6,), buckets=(1,))

    monkeypatch.delenv("MXNET_SERVE_PRECISION", raising=False)
    fp32 = engine()
    monkeypatch.setenv("MXNET_SERVE_PRECISION", "int8")
    int8 = engine()
    assert (fp32.precision, int8.precision) == ("fp32", "int8")
    assert fp32._fp() != int8._fp()
    monkeypatch.delenv("MXNET_SERVE_PRECISION")
    assert fp32._fp() == engine()._fp()


def test_eager_convolution_is_one_cache_entry_with_no_extra_key():
    """A routing decision cannot change inside a process, so the ops it
    is made in key on their arguments alone, like ``softmax``."""
    import jax.numpy as jnp
    from mxnet_tpu import dispatch_cache
    from mxnet_tpu.ops import nn as ops_nn
    for op in (ops_nn.convolution, ops_nn.residual_block,
               ops_nn.quantized_conv, ops_nn.quantized_dense,
               ops_nn.softmax):
        assert not hasattr(op, "__mx_extra_key__"), op
    rng = np.random.RandomState(13)
    x = jnp.asarray(rng.randn(1, 6, 6, 5).astype(np.float32))
    w = jnp.asarray(rng.randn(3, 3, 5, 7).astype(np.float32))
    dispatch_cache.clear()
    a = ops_nn.convolution(x, w, stride=1, pad=1)
    n, d0 = dispatch_cache.cache_len(), dispatch_cache.stats()
    b = ops_nn.convolution(x, w, stride=1, pad=1)
    d1 = dispatch_cache.stats()
    assert n == 1 and dispatch_cache.cache_len() == 1
    assert d1["misses"] == d0["misses"] and d1["hits"] == d0["hits"] + 1
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_quantize_net_folds_bn_and_keeps_argmax():
    import numpy as onp
    from mxnet_tpu.models import resnet
    from mxnet_tpu.quantization import quantize_net, _Identity, \
        QuantizedConv2D

    mx.seed(0)
    net = resnet.resnet18_v1(classes=10)
    net.initialize()
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.rand(4, 32, 32, 3).astype("float32"))
    ref = net(x).asnumpy()
    qnet = quantize_net(net, calib_data=[x], calib_mode="naive")
    blocks = [c for _, c, _ in
              __import__("mxnet_tpu.quantization",
                         fromlist=["_walk"])._walk(qnet)]
    assert any(isinstance(b, QuantizedConv2D) for b in blocks)
    assert any(isinstance(b, _Identity) for b in blocks)   # BN folded
    got = qnet(x).asnumpy()
    am = onp.argmax(ref, axis=1)
    qm = onp.argmax(got, axis=1)
    assert (am == qm).mean() >= 0.75, (am, qm)
