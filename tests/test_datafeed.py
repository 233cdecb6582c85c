"""DataFeed staging ring + scaled native decode (docs/datafeed.md).

Covers the pipelined-input subsystem contracts:
 * uint8 wire → device finalize parity with the float32 host path,
 * ring liveness (early close, mid-epoch reset, producer error, dead
   stager) — abandoning the iterator must never deadlock,
 * bounded queue: producer backpressure is counted and the ring never
   holds more than ``depth`` staged batches,
 * native decode worker scaling (slow-marked; needs real cores),
 * per-stage counters surfaced end-to-end (loader JSON → feed stats()).
"""
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx


def _make_rec(tmp_path, n=24, size=32):
    cv2 = pytest.importorskip("cv2")
    from mxnet_tpu import recordio as mrec
    rec_path = str(tmp_path / "feed.rec")
    idx_path = str(tmp_path / "feed.idx")
    w = mrec.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = onp.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 256, (size, size, 3), onp.uint8)
        ok, buf = cv2.imencode(".png", img)   # lossless → exact compare
        assert ok
        w.write_idx(i, mrec.pack(mrec.IRHeader(0, float(i % 7), i, 0),
                                 buf.tobytes()))
    w.close()
    return rec_path


def _native(rec, **kw):
    try:
        return mx.io.NativeImageRecordIter(
            path_imgrec=rec, data_shape=(3, 32, 32), batch_size=8,
            shuffle=False, **kw)
    except RuntimeError:
        pytest.skip("native runtime without OpenCV")


# ---------------------------------------------------------------- parity
def test_uint8_wire_matches_float32_wire(tmp_path):
    """Same records, same augment seed: the uint8 wire followed by a
    device-side cast must equal the float32 wire bit-for-bit (the cast
    is exact for 0..255)."""
    rec = _make_rec(tmp_path)
    f32 = _native(rec, dtype="float32", preprocess_threads=2)
    u8 = _native(rec, dtype="uint8", preprocess_threads=2)
    for _ in range(3):
        d_f, l_f, p_f = f32.next_raw()
        d_u, l_u, p_u = u8.next_raw()
        assert p_f == p_u
        assert d_u.dtype == onp.uint8 and d_f.dtype == onp.float32
        onp.testing.assert_array_equal(d_u.astype(onp.float32), d_f)
        onp.testing.assert_array_equal(l_u, l_f)


def test_datafeed_device_normalize_parity(tmp_path):
    """uint8 wire + device (x-mean)/std + NHWC transpose == the same
    math done on the float32 host batch."""
    rec = _make_rec(tmp_path)
    mean = onp.array([123.68, 116.78, 103.94], onp.float32)
    std = onp.array([58.4, 57.1, 57.4], onp.float32)
    host = _native(rec, dtype="float32")
    feed = mx.io.DataFeed(_native(rec, dtype="uint8"),
                          mean=mean, std=std, layout="NHWC")
    try:
        for _ in range(3):
            d_h, l_h, pad = host.next_raw()
            b = next(feed)
            want = ((d_h - mean.reshape(3, 1, 1)) /
                    std.reshape(3, 1, 1)).transpose(0, 2, 3, 1)
            got = b.data[0].asnumpy()
            assert got.shape == (8, 32, 32, 3)
            valid = 8 - pad
            onp.testing.assert_allclose(got[:valid], want[:valid],
                                        rtol=1e-5, atol=1e-4)
            onp.testing.assert_array_equal(
                b.label[0].asnumpy()[:valid], l_h[:valid])
    finally:
        feed.close()


def test_sync_mode_same_batches(tmp_path):
    """depth=0 runs fully synchronous and must yield identical data."""
    rec = _make_rec(tmp_path)
    ring = mx.io.DataFeed(_native(rec, dtype="uint8"), depth=2)
    sync = mx.io.DataFeed(_native(rec, dtype="uint8"), depth=0)
    try:
        ring_b = [b.data[0].asnumpy() for b in ring]
        sync_b = [b.data[0].asnumpy() for b in sync]
        assert len(ring_b) == len(sync_b) == 3
        for r, s in zip(ring_b, sync_b):
            onp.testing.assert_array_equal(r, s)
        assert sync.stats()["sync_mode"] is True
    finally:
        ring.close()
        sync.close()


# -------------------------------------------------------------- liveness
def _slow_source(n=50, delay=0.0, fail_at=None):
    class Src:
        batch_size = 4

        def __iter__(self):
            for i in range(n):
                if fail_at is not None and i == fail_at:
                    raise RuntimeError("decode exploded")
                if delay:
                    time.sleep(delay)
                yield onp.full((4, 3), float(i), onp.float32)
    return Src()


def test_early_close_does_not_deadlock():
    """Abandon the feed with a FULL ring and a blocked producer; close()
    must return promptly and the stager must exit."""
    feed = mx.io.DataFeed(_slow_source(n=50), depth=2)
    next(feed)                       # ring fills behind this
    time.sleep(0.2)
    t0 = time.monotonic()
    feed.close()
    assert time.monotonic() - t0 < 5.0
    assert feed._thread is None
    with pytest.raises(RuntimeError):
        next(feed)


def test_reset_mid_epoch_restarts(tmp_path):
    rec = _make_rec(tmp_path)
    feed = mx.io.DataFeed(_native(rec, dtype="uint8"), depth=2)
    try:
        first = next(feed).data[0].asnumpy()
        feed.reset()                 # mid-epoch, ring non-empty
        again = next(feed).data[0].asnumpy()
        onp.testing.assert_array_equal(first, again)
        assert feed.stats()["restarts"] == 1
    finally:
        feed.close()


def test_producer_error_surfaces_at_consumer():
    feed = mx.io.DataFeed(_slow_source(n=10, fail_at=3), depth=2)
    try:
        with pytest.raises(RuntimeError, match="decode exploded"):
            for _ in feed:
                pass
    finally:
        feed.close()


def test_exhaustion_then_stop_iteration():
    feed = mx.io.DataFeed(_slow_source(n=5), depth=2)
    try:
        got = list(feed)
        assert len(got) == 5
        with pytest.raises(StopIteration):
            next(feed)
    finally:
        feed.close()


# ---------------------------------------------------------- backpressure
def test_ring_is_bounded_and_backpressure_counted():
    """Fast producer, slow consumer: the ring never exceeds ``depth``
    staged batches and the producer's stalls are counted."""
    feed = mx.io.DataFeed(_slow_source(n=30), depth=3)
    try:
        seen_depth = 0
        for i, _ in enumerate(feed):
            time.sleep(0.02)         # consumer is the bottleneck
            if feed._queue is not None:
                seen_depth = max(seen_depth, feed._queue.qsize())
        s = feed.stats()
        assert seen_depth <= 3
        assert s["staged_batches"] == 30
        assert s["backpressure_waits"] > 0
        assert s["h2d_bytes"] == 30 * 4 * 3 * 4
    finally:
        feed.close()


def test_consumer_wait_counted_as_sync_fallback():
    """Slow producer, fast consumer: every get degrades to synchronous
    and is counted (the 'graceful degradation' contract)."""
    feed = mx.io.DataFeed(_slow_source(n=4, delay=0.05), depth=2)
    try:
        n = sum(1 for _ in feed)
        assert n == 4
        s = feed.stats()
        assert s["sync_fallbacks"] > 0
        assert s["consumer_waits"] == s["sync_fallbacks"]
        assert s["consumer_wait_s"] > 0.0
    finally:
        feed.close()


# ---------------------------------------------------- counters end-to-end
def test_loader_counters_through_feed_stats(tmp_path):
    rec = _make_rec(tmp_path)
    feed = mx.io.DataFeed(_native(rec, dtype="uint8",
                                  preprocess_threads=2), depth=2)
    try:
        for _ in feed:
            pass
        s = feed.stats()
        src = s["source"]            # native loader's StatsJson()
        assert src["uint8_wire"] == 1
        assert src["workers"] == 2
        assert src["samples"] == 24
        assert src["decode_us"] > 0
        assert src["batchify_us"] > 0
        assert {"read_us", "augment_us", "backpressure_waits",
                "consumer_waits", "queue_depth"} <= set(src)
    finally:
        feed.close()


def test_pipeline_env_knob_routes_record_iter(tmp_path, monkeypatch):
    """MXNET_DATAFEED=1 flips ImageRecordIter onto the DataFeed path
    with identical (pad-aware) batches in the NHWC contract layout."""
    rec = _make_rec(tmp_path)
    kw = dict(path_imgrec=rec, data_shape=(3, 32, 32), batch_size=8,
              shuffle=False)
    plain = [(b.data[0].asnumpy(), b.pad) for b in
             mx.io.ImageRecordIter(**kw, pipeline=False)]
    monkeypatch.setenv("MXNET_DATAFEED", "1")
    piped = mx.io.ImageRecordIter(**kw)
    got = [(b.data[0].asnumpy(), b.pad) for b in piped]
    assert len(plain) == len(got)
    for (d, pad), (g, gpad) in zip(plain, got):
        assert g.shape == d.shape    # NHWC preserved through the feed
        valid = 8 - max(pad or 0, gpad or 0)
        onp.testing.assert_allclose(g[:valid], d[:valid],
                                    rtol=1e-5, atol=1e-4)


# ------------------------------------------------------- worker scaling
@pytest.mark.slow
def test_native_decode_worker_scaling(tmp_path):
    """2 workers ≥ 1.6× 1 worker on the decode+augment stage.  Needs
    real parallel cores — meaningless (and flaky) on a 1-core host."""
    if (os.cpu_count() or 1) < 2:
        pytest.skip("needs >= 2 cores for a scaling assertion")
    rec = _make_rec(tmp_path, n=256, size=64)

    def epoch_rate(workers):
        it = _native(rec, dtype="uint8", preprocess_threads=workers,
                     rand_mirror=True, rand_crop=True)
        for _ in it:                 # warm epoch: page cache + pools
            pass
        it.reset()
        t0, n = time.perf_counter(), 0
        try:
            while True:
                _, _, pad = it.next_raw()
                n += 8 - pad
        except StopIteration:
            pass
        return n / (time.perf_counter() - t0)

    r1, r2 = epoch_rate(1), epoch_rate(2)
    assert r2 >= 1.6 * r1, f"2w={r2:.0f}/s vs 1w={r1:.0f}/s"


# ------------------------------------------------ channel-last sources (PR 34)
@pytest.mark.parametrize("depth", [0, 2])
def test_channel_last_source_is_normalised_over_its_last_axis(depth):
    """uint8 NHWC batches as a decoded-image loader hands them over:
    the per-channel constants find the last axis by its length and nothing
    is transposed; labels pass through as they are."""
    from mxnet_tpu.io import DataFeed
    rs = onp.random.RandomState(3)
    batches = [(rs.randint(0, 256, (2, 6, 5, 3), dtype=onp.uint8),
                rs.randint(0, 10, (2,), dtype=onp.int32)) for _ in range(3)]
    mean, std = [0.485, 0.456, 0.406], [0.229, 0.224, 0.225]
    with DataFeed(iter(batches), depth=depth, scale=1 / 255, mean=mean,
                  std=std) as feed:
        got = list(feed)
        stats = feed.stats()
    assert len(got) == 3
    for (x, y), (gx, gy) in zip(batches, got):
        want = (x.astype("float32") / 255 - onp.asarray(mean, "float32")) \
            / onp.asarray(std, "float32")
        assert gx.shape == (2, 6, 5, 3) and str(gx.dtype) == "float32"
        assert onp.allclose(gx.asnumpy(), want, atol=1e-5)
        assert str(gy.dtype) == "int32" and (gy.asnumpy() == y).all()
    # the wire carried uint8 images and int32 labels, nothing wider
    assert stats["h2d_bytes"] == 3 * (2 * 6 * 5 * 3 + 2 * 4)


def test_per_channel_constants_find_their_axis_by_its_length():
    from mxnet_tpu.io import DataFeed
    # the default wire is untouched: (C,) constants over NCHW, also when the
    # last axis has C entries too
    for shape in ((1, 3, 2, 2), (1, 3, 2, 3)):
        x = onp.full(shape, 255, onp.uint8)
        with DataFeed(iter([(x, onp.zeros((1,), onp.int32))]), depth=0,
                      scale=1 / 255, mean=[0.0, 0.5, 1.0]) as feed:
            gx, _ = next(feed)
        assert gx.shape == shape
        assert onp.allclose(gx.asnumpy()[0, :, 0, 0], [1.0, 0.5, 0.0],
                            atol=1e-6)
    # channel-last, and asked for NHWC: it is NHWC already
    x = onp.full((1, 2, 2, 3), 255, onp.uint8)
    with DataFeed(iter([(x, onp.zeros((1,), onp.int32))]), depth=0,
                  scale=1 / 255, mean=[0.0, 0.5, 1.0], layout="NHWC") as feed:
        gx, _ = next(feed)
    assert gx.shape == (1, 2, 2, 3)
    assert onp.allclose(gx.asnumpy()[0, 0, 0], [1.0, 0.5, 0.0], atol=1e-6)
    # constants that fit neither axis are refused when the batch is staged
    with DataFeed(iter([(onp.zeros((1, 2, 2, 4), onp.uint8),
                         onp.zeros((1,), onp.int32))]), depth=0,
                  mean=[0.0, 0.5, 1.0]) as feed:
        with pytest.raises((TypeError, ValueError)):
            next(feed)


def test_a_batch_is_handed_over_once_its_copy_has_landed(monkeypatch):
    """A staged batch whose copy is still in flight is waited for inside a
    ``datafeed.wait`` span and the time counts in ``consumer_wait_s``; one
    that has landed records nothing (the fast path)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.io import DataFeed
    from mxnet_tpu.ndarray import NDArray

    class InFlight:
        def __init__(self, lands_after):
            self.lands_after, self.blocked = lands_after, 0

        def is_ready(self):
            return self.lands_after == 0

        def block_until_ready(self):
            self.blocked += 1
            time.sleep(self.lands_after)
            self.lands_after = 0
            return self

    late, landed = InFlight(0.03), InFlight(0)
    x = onp.zeros((2, 3), onp.float32)
    with DataFeed(iter([(x, x)] * 2), depth=0) as feed:
        monkeypatch.setattr(
            feed, "_stage", lambda item: (NDArray(late), NDArray(landed)))
        telemetry.trace_reset()
        next(feed)
        waited = feed.stats()["consumer_wait_s"]
        assert late.blocked == 1 and landed.blocked == 0
        assert waited >= 0.03
        if telemetry.trace_enabled():
            # the draw (sync mode) and the wait for the copy name batch 0
            assert [(s[7]["mode"], s[7]["batch"])
                    for s in telemetry.trace_spans()
                    if s[3] == "datafeed.wait"] == [("sync", 0), ("copy", 0)]
        next(feed)                     # both have landed now: nothing more
        assert late.blocked == 1
        assert feed.stats()["consumer_wait_s"] == waited
