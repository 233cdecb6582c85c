#!/usr/bin/env python
"""Input-pipeline benchmark: can the loader feed the train step?

≙ the reference's data-pipeline story (src/io/iter_image_recordio_2.cc
decode threads + iter_prefetcher.h) measured end-to-end (VERDICT r2 item
6): the train step consumes ~2400 img/s (bench.py bf16 ResNet-50), so the
RecordIO-JPEG → decode → augment → device pipeline must sustain that.

Stages measured (each prints img/s):
  1. recordio-read     raw RecordIO unpack rate
  2. decode+augment    ImageRecordIter (resize/crop/mirror) host pipeline
  3. +device-prefetch  prefetch_to_device overlap: batches land in HBM
  4. end-to-end        loader feeding a real ResNet-50 bf16 train step
                       (TPU) vs the same step on a resident tensor —
                       within 10% means the pipeline keeps the chip fed

Usage: python benchmark/data_pipeline.py [--images N] [--batch B]
       [--train]   (the train stage needs the accelerator)
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# end-of-timed-window barrier: a host fetch of a dependent value
from bench import _force  # noqa: E402


def build_recfile(path, n, hw=224, workers=4):
    """Synthetic JPEG RecordIO (≙ tools/im2rec.py output)."""
    import cv2
    import numpy as np
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    idx = os.path.splitext(path)[0] + ".idx"   # ImageIter pairs foo.rec with foo.idx
    rec = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(n):
        img = rng.randint(0, 256, (hw, hw, 3), np.uint8)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack(header, buf.tobytes()))
    rec.close()
    return path


def bench_read(path, n):
    from mxnet_tpu import recordio
    rec = recordio.MXRecordIO(path, "r")
    t0 = time.perf_counter()
    k = 0
    while True:
        item = rec.read()
        if item is None:
            break
        k += 1
    dt = time.perf_counter() - t0
    rec.close()
    print(f"[pipe] recordio-read      : {k / dt:9.1f} rec/s")
    return k / dt


def bench_decode(path, n, batch, hw, epochs=2):
    import mxnet_tpu as mx
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
        shuffle=False, rand_mirror=True, rand_crop=True, resize=hw + 32)
    # warm one epoch (populates caches), then time
    for _ in it:
        pass
    it.reset()
    t0 = time.perf_counter()
    k = 0
    for b in it:
        k += b.data[0].shape[0]
    dt = time.perf_counter() - t0
    print(f"[pipe] decode+augment     : {k / dt:9.1f} img/s")
    it.reset()
    return k / dt


def bench_native_decode(path, n, batch, hw, threads=4):
    """No-GIL C++ loader (src/dataio.cc): decode+augment rate with real
    thread parallelism — the stage that answers 'build the C++ tier?'
    (VERDICT r3 item 4) empirically on a many-core host."""
    import mxnet_tpu as mx
    try:
        it = mx.io.NativeImageRecordIter(
            path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
            shuffle=False, preprocess_threads=threads)
    except RuntimeError as e:
        print(f"[pipe] native-decode      : unavailable ({e})")
        return None
    for _ in it:                     # warm epoch
        pass
    it.reset()
    t0 = time.perf_counter()
    k = 0
    for b in it:
        k += b.data[0].shape[0] - b.pad
    dt = time.perf_counter() - t0
    print(f"[pipe] native-decode      : {k / dt:9.1f} img/s "
          f"({threads} threads)")
    it.reset()
    return k / dt


def bench_h2d(batch, hw, reps=6):
    """TRUE host→device bandwidth: each upload is forced to materialize
    by fetching a dependent scalar.  (device_put is asynchronous: it
    returns before the bytes have moved, so the prefetch stage can
    report optimistic rates while this one reports what a train step
    actually experiences.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    mb = batch * hw * hw * 3 * 4 / 1e6
    red = jax.jit(lambda a: jnp.sum(a))
    buf = np.random.rand(batch, hw, hw, 3).astype(np.float32)
    float(red(jax.device_put(buf)))               # warm the executable
    t0 = time.perf_counter()
    for i in range(reps):
        buf[0, 0, 0, 0] = float(i) + 0.5          # distinct bytes per rep
        float(red(jax.device_put(buf)))
    rate = reps * mb / (time.perf_counter() - t0)
    print(f"[pipe] h2d (materialized) : {rate:9.1f} MB/s")
    return rate


def bench_device_prefetch(path, n, batch, hw):
    import jax
    import mxnet_tpu as mx
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
        shuffle=False, rand_mirror=True)
    t0 = time.perf_counter()
    k = 0
    last = None
    for b in mx.io.prefetch_to_device(it):
        last = b.data[0]
        k += last.shape[0]
    _force(last._data)
    dt = time.perf_counter() - t0
    print(f"[pipe] +device-prefetch   : {k / dt:9.1f} img/s")
    return k / dt


def bench_train(path, n, batch, hw):
    """End-to-end: loader + fused bf16 train step vs resident tensor.

    NB the first loader-fed leg pays ONE extra jit compile (device-put
    batches have a committed-device signature the resident row doesn't);
    at the real capture size (--images 512+) it amortizes to noise, but
    tiny smoke runs under-report that leg.  The native leg reuses the
    compiled executable and reports steady-state."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod, parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import resnet

    mx.seed(0)
    net = resnet.resnet50_v1(classes=1000)
    net.initialize()
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = par.FusedTrainStep(net, gloss.SoftmaxCrossEntropyLoss(), opt,
                              dtype="bfloat16")
    rng = np.random.RandomState()
    x = mx.np.array(rng.rand(batch, hw, hw, 3).astype(np.float32))
    y = mx.np.array(rng.randint(0, 1000, (batch,)))
    l = None
    for _ in range(3):
        l = step(x, y)
    _force(l._data)
    t0 = time.perf_counter()
    iters = max(10, n // batch)
    for _ in range(iters):
        l = step(x, y)
    _force(l._data)      # final loss depends on every update in the chain
    resident = batch * iters / (time.perf_counter() - t0)
    print(f"[pipe] train (resident)   : {resident:9.1f} img/s")

    def timed_epochs(make_iter, to_step, warm_shape, warm_dtype,
                     epochs=2):
        """Steady-state img/s: a SYNTHETIC committed-device batch warms
        the loader-fed jit signature (device-put batches differ from the
        resident row's) outside the timed window — one device_put, not a
        drained epoch of decode+H2D — then `epochs` full passes are
        timed."""
        import jax
        from mxnet_tpu.ndarray import NDArray
        warm = mx.io.DataBatch(
            data=[NDArray(jax.device_put(
                np.zeros((batch,) + warm_shape, warm_dtype)))],
            label=[NDArray(jax.device_put(
                np.zeros((batch, 1), np.float32)))], pad=0)
        _force(to_step(warm)._data)
        it = make_iter()
        t0 = time.perf_counter()
        k = 0
        last = None
        for _ in range(epochs):
            for b in mx.io.prefetch_to_device(it):
                if b.data[0].shape[0] - b.pad != batch:
                    continue
                last = to_step(b)
                k += batch
            it.reset()
        if last is not None:   # every batch padded/short → nothing ran
            _force(last._data)
        return k / (time.perf_counter() - t0)

    # ImageRecordIter emits NHWC batches + (B, label_width) float labels;
    # cast to the resident row's int class-id signature so the SAME
    # compiled executable serves both rows
    e2e = timed_epochs(
        lambda: mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
            shuffle=False, rand_mirror=True),
        lambda b: step(b.data[0], b.label[0][:, 0].astype("int32")),
        (hw, hw, 3), np.float32)
    print(f"[pipe] train (end-to-end) : {e2e:9.1f} img/s "
          f"({100 * e2e / resident:.1f}% of resident)")
    # uint8 wire format (dtype= ≙ iter_image_recordio_2.cc): pixels cross
    # host→device 4× smaller; the cast to compute dtype is fused into the
    # train step on device.  On transfer-bound hosts this leg should
    # approach 4× the float32 e2e leg.
    e2e_u8 = timed_epochs(
        lambda: mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
            shuffle=False, rand_mirror=True, dtype="uint8"),
        lambda b: step(b.data[0], b.label[0][:, 0].astype("int32")),
        (hw, hw, 3), np.uint8)
    print(f"[pipe] train (e2e uint8)  : {e2e_u8:9.1f} img/s "
          f"({100 * e2e_u8 / resident:.1f}% of resident)")
    # same step fed by the no-GIL C++ loader — on a many-core TPU host
    # this is the pipeline that must keep the chip fed
    try:
        e2e_native = timed_epochs(
            lambda: mx.io.NativeImageRecordIter(
                path_imgrec=path, data_shape=(3, hw, hw),
                batch_size=batch, shuffle=False, rand_mirror=True,
                rand_crop=True,
                preprocess_threads=max(4, os.cpu_count() or 4)),
            # native loader emits CHW; the step consumes NHWC
            lambda b: step(b.data[0].transpose(0, 2, 3, 1),
                           b.label[0][:, 0].astype("int32")),
            (3, hw, hw), np.float32)
        print(f"[pipe] train (e2e native) : {e2e_native:9.1f} img/s "
              f"({100 * e2e_native / resident:.1f}% of resident)")
    except RuntimeError as e:
        print(f"[pipe] train (e2e native) : unavailable ({e})")
        e2e_native = None
    return resident, e2e, e2e_u8, e2e_native


def _measure_native(path, batch, hw, resize, workers, decode=None):
    """One native-loader measurement: warm epoch, stats_reset (per-point
    stage deltas), timed epoch.  Returns (img_s, stats dict)."""
    import mxnet_tpu as mx

    kw = dict(path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
              shuffle=False, rand_mirror=True, rand_crop=True,
              resize=resize, preprocess_threads=workers, dtype="uint8")
    if decode is not None:
        kw["decode"] = decode
    it = mx.io.NativeImageRecordIter(**kw)
    while True:                        # warm epoch (page cache, pool)
        try:
            it.next_raw()
        except StopIteration:
            break
    it.reset()
    if hasattr(it, "stats_reset"):
        # per-POINT stage deltas: zero the warm epoch's accumulation so
        # each sweep point's counters describe only its own timed epoch
        # (MXTImageRecordLoaderStatsReset)
        it.stats_reset()
    t0 = time.perf_counter()
    k = 0
    while True:
        try:
            data, _, pad = it.next_raw()
        except StopIteration:
            break
        k += data.shape[0] - pad
    dt = time.perf_counter() - t0
    return k / dt, it.stats()


def bench_scaling(path, n, batch, hw, resize):
    """DataFeed row (docs/datafeed.md): native decode+augment img/s vs
    worker count on the uint8 wire, with the loader's per-stage counters
    attached to every point so a flat curve is attributable (decode-
    bound vs claim-window backpressure vs a 1-core host).  Returns
    (points, best_workers, best_img_s)."""
    counts_env = os.environ.get("BENCH_SCALING_WORKERS", "1,2,4,8")
    counts = [int(c) for c in counts_env.split(",") if c.strip()]
    points = {}
    best_w, best = None, 0.0
    for w in counts:
        try:
            rate, stats = _measure_native(path, batch, hw, resize, w)
        except RuntimeError as e:
            print(f"[pipe] scaling            : unavailable ({e})")
            return None, None, None
        points[str(w)] = {"img_s": round(rate, 1),
                          "decode_backend": stats.get("decode_backend"),
                          "scale_counts": stats.get("scale_counts"),
                          "counters": stats}
        print(f"[pipe] scaling {w:2d} workers: {rate:9.1f} img/s "
              f"({stats.get('decode_backend', '?')} decode "
              f"{stats['decode_us']}us, augment "
              f"{stats['augment_us']}us, batchify {stats['batchify_us']}"
              f"us, backpressure {stats['backpressure_waits']}, "
              f"scales {stats.get('scale_counts')})")
        if rate > best:
            best_w, best = w, rate
    return points, best_w, best


def bench_fed_train(path, n, batch, hw, workers, resize=-1):
    """Fed-train vs synthetic-train through the DataFeed staging ring:
    the same fused bf16 step consuming (a) a resident synthetic batch,
    (b) uint8 native-decoded batches staged + cast/transposed on device
    by DataFeed.  Within 10% = the chip stays fed; otherwise the ring's
    counters say who stalled."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod, parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import resnet
    from mxnet_tpu.ndarray import NDArray

    mx.seed(0)
    net = resnet.resnet50_v1(classes=1000)
    net.initialize()
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = par.FusedTrainStep(net, gloss.SoftmaxCrossEntropyLoss(), opt,
                              dtype="bfloat16")
    rng = np.random.RandomState()
    x = mx.np.array(rng.rand(batch, hw, hw, 3).astype(np.float32))
    y = mx.np.array(rng.randint(0, 1000, (batch,)))
    l = None
    for _ in range(3):
        l = step(x, y)
    _force(l._data)
    iters = max(8, n // batch)
    t0 = time.perf_counter()
    for _ in range(iters):
        l = step(x, y)
    _force(l._data)
    synth = batch * iters / (time.perf_counter() - t0)
    print(f"[pipe] train (synthetic)  : {synth:9.1f} img/s")

    # warm the fed signature (committed device batch) outside the window
    warm = step(NDArray(jax.device_put(
        np.zeros((batch, hw, hw, 3), np.float32))),
        NDArray(jax.device_put(np.zeros((batch,), np.int32))))
    _force(warm._data)
    src = mx.io.NativeImageRecordIter(
        path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
        shuffle=False, rand_mirror=True, rand_crop=True, resize=resize,
        preprocess_threads=workers, dtype="uint8")
    feed = mx.io.DataFeed(src, layout="NHWC")
    # one batch through the ring outside the window: compiles the
    # donated uint8→f32 cast/transpose kernel the staging thread runs
    b0 = next(feed)
    _force(step(b0.data[0], b0.label[0][:, 0].astype("int32"))._data)
    feed.reset()
    k, last = 0, None
    t0 = time.perf_counter()
    for epoch in range(2):
        for b in feed:
            if b.pad:
                continue
            last = step(b.data[0], b.label[0][:, 0].astype("int32"))
            k += batch
        feed.reset()
    if last is not None:
        _force(last._data)
    fed = k / (time.perf_counter() - t0)
    stats = feed.stats()
    feed.close()
    print(f"[pipe] train (datafeed)   : {fed:9.1f} img/s "
          f"({100 * fed / synth:.1f}% of synthetic)")
    return synth, fed, stats


def run_scaling(path, args):
    """The data_pipeline_scaling bench row: emit ONE JSON object with
    the worker-scaling curve (+ per-stage counters per point), the
    turbo-vs-opencv single-worker comparison, the DataFeed fed-train vs
    synthetic-train comparison, the feed-check gate verdict, and the
    decode_vs_train ratio (ROADMAP item 4's "decode ≥ train-step
    consumption" condition, in the artifact)."""
    import json

    # the scaling sweep decodes ImageNet-style: sources LARGER than the
    # crop with a resize-short pass, so the DCT-domain scaled decode has
    # real work to skip (a crop-sized source decodes at 8/8 and measures
    # only the fallback-equivalent path).  src 2·(hw+32) with resize
    # hw+32 puts the 4/8 scale exactly on target for the default 224 px.
    resize = args.hw + 32
    if path is not None:                  # explicit --rec: use as-is
        return _run_scaling_inner(path, resize, args)
    src_hw = int(os.environ.get("BENCH_SRC_HW", str(2 * resize)))
    scal_dir = tempfile.mkdtemp(prefix="mxtpu_pipe_scaling_")
    try:
        from mxnet_tpu.io import feedcheck
        t0 = time.perf_counter()
        scal_rec = feedcheck.build_rec(scal_dir, "scaling_src",
                                       n=args.images, size=src_hw)
        print(f"[pipe] built {args.images} {src_hw}px scaling records in "
              f"{time.perf_counter() - t0:.1f}s")
        return _run_scaling_inner(scal_rec, resize, args)
    finally:
        import shutil
        shutil.rmtree(scal_dir, ignore_errors=True)


def _run_scaling_inner(path, resize, args):
    import json

    points, best_w, best = bench_scaling(path, args.images, args.batch,
                                         args.hw, resize)
    # turbo vs opencv at the SAME worker count (1): the backend's own
    # win, isolated from thread scaling
    turbo_1w = opencv_1w = None
    if points and points.get("1", {}).get("decode_backend") == "turbo":
        turbo_1w = points["1"]["img_s"]
        try:
            r, _ = _measure_native(path, args.batch, args.hw, resize, 1,
                                   decode="opencv")
            opencv_1w = round(r, 1)
            print(f"[pipe] scaling  1 worker : {opencv_1w:9.1f} img/s "
                  f"(opencv baseline)")
        except RuntimeError as e:
            print(f"[pipe] opencv baseline    : unavailable ({e})")
    synth = fed = feed_stats = h2d = None
    err = None
    # BENCH_SCALING_FED=0 skips the chip-side fed-train legs (a chip-less
    # 1-core rig spends minutes per ResNet step there and the decode
    # curve — this row's whole point — would die at the row timeout)
    if os.environ.get("BENCH_SCALING_FED", "1") != "0":
        try:
            h2d = bench_h2d(args.batch, args.hw)
            synth, fed, feed_stats = bench_fed_train(
                path, args.images, args.batch, args.hw, best_w or 4,
                resize=resize)
        except Exception as e:  # decode scaling must still be captured
            err = f"{type(e).__name__}: {e}"[:200]   # on a chip-less run
            print(f"[pipe] fed-train unavailable: {err}", file=sys.stderr)
    # speedup is RELATIVE to the same-run 1-worker point: absolute
    # anchors (the old hard-coded r05 440 img/s) are flaky on loaded
    # 1-core hosts — the curve itself is the claim
    base_1w = points.get("1", {}).get("img_s") if points else None
    # the ratio ROADMAP item 4 closes on: native decode img/s over the
    # fused-train consumption rate.  bench.py injects the same-artifact
    # train row via BENCH_TRAIN_IMG_S; same-run synthetic is the
    # fallback denominator
    train_img_s = None
    train_src = None
    env_train = os.environ.get("BENCH_TRAIN_IMG_S")
    if env_train:
        try:
            train_img_s = float(env_train)
            train_src = "bench_train_row"
        except ValueError:
            pass
    if train_img_s is None and synth:
        train_img_s, train_src = synth, "same_run_synthetic"
    feed_gate = None
    try:
        from mxnet_tpu.io import feedcheck
        feed_gate = feedcheck.summary()
    except Exception as e:
        feed_gate = {"ok": False, "error": f"{type(e).__name__}: {e}"[:200]}
    img_mb_u8 = args.hw * args.hw * 3 / 1e6
    out = {
        "mode": "scaling",
        "batch": args.batch, "hw": args.hw, "images": args.images,
        "host_cpus": os.cpu_count(),
        "decode_scaling": points,
        "best_workers": best_w,
        "best_native_uint8_img_s": round(best, 1) if best else None,
        "baseline_1w_img_s": base_1w,
        "speedup_vs_1w": round(best / base_1w, 2)
        if best and base_1w else None,
        "decode_backend": (points or {}).get(
            str(best_w), {}).get("decode_backend"),
        # the backend's own win at identical worker count (acceptance:
        # turbo ≥2× the opencv single-worker baseline)
        "turbo_1w_img_s": turbo_1w,
        "opencv_1w_img_s": opencv_1w,
        "turbo_vs_opencv_1w": round(turbo_1w / opencv_1w, 2)
        if turbo_1w and opencv_1w else None,
        "resize_short": resize,
        "decode_vs_train": round(best / train_img_s, 2)
        if best and train_img_s else None,
        "train_img_s_source": train_src,
        "train_img_s_denominator": round(train_img_s, 1)
        if train_img_s else None,
        "feed_gate": feed_gate,
        "h2d_mb_s": round(h2d, 1) if h2d else None,
        "h2d_ceiling_img_s_uint8": round(h2d / img_mb_u8, 1)
        if h2d else None,
        "train_synthetic_img_s": round(synth, 1) if synth else None,
        "train_datafeed_img_s": round(fed, 1) if fed else None,
        "fed_pct_of_synthetic": round(100 * fed / synth, 1)
        if fed and synth else None,
        # rig attribution: when even the uint8 wire's link ceiling is
        # below the synthetic rate, a fed-train gap is the LINK's, not
        # the pipeline's (the acceptance escape hatch is evidence-based)
        "h2d_bound": bool(h2d and synth and
                          h2d / img_mb_u8 < 0.9 * synth),
        "datafeed_stats": feed_stats,
    }
    if err:
        out["train_error"] = err
    print(json.dumps(out))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=512)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--train", action="store_true",
                    help="run the accelerator end-to-end stage")
    ap.add_argument("--scaling", action="store_true",
                    help="DataFeed row: decode img/s vs worker count + "
                         "fed-train vs synthetic-train (ISSUE 2)")
    ap.add_argument("--rec", default=None,
                    help="existing .rec file (skips synthesis)")
    args = ap.parse_args()

    if args.scaling:
        # scaling mode owns its record synthesis (larger-than-crop
        # sources so the DCT-scaled decode engages); an explicit --rec
        # still wins
        return run_scaling(args.rec, args)

    path = args.rec
    tmp = None
    if path is None:
        tmp = tempfile.mkdtemp()
        path = os.path.join(tmp, "synth.rec")
        t0 = time.perf_counter()
        build_recfile(path, args.images, args.hw)
        print(f"[pipe] built {args.images} jpeg records in "
              f"{time.perf_counter() - t0:.1f}s")

    read = bench_read(path, args.images)
    dec = bench_decode(path, args.images, args.batch, args.hw)
    native = bench_native_decode(path, args.images, args.batch, args.hw)
    pref = bench_device_prefetch(path, args.images, args.batch, args.hw)
    resident = e2e = e2e_u8 = e2e_native = h2d = None
    if args.train:
        h2d = bench_h2d(args.batch, args.hw)
        resident, e2e, e2e_u8, e2e_native = bench_train(
            path, args.images, args.batch, args.hw)
    import json
    img_mb = args.hw * args.hw * 3 * 4 / 1e6
    # what the H2D link alone can feed, img/s, PER WIRE FORMAT — when
    # even the leanest format's ceiling is far below `resident`, the e2e
    # rows measure the LINK, not the decode pipeline.  Each leg must be judged against ITS OWN ceiling:
    # the uint8 leg moves 4× fewer bytes than float32.
    h2d_img_s = (h2d / img_mb) if h2d else None
    h2d_img_s_u8 = (h2d / (img_mb / 4)) if h2d else None
    print(json.dumps({
        "recordio_read_rec_s": round(read, 1),
        "decode_augment_img_s": round(dec, 1),
        "native_decode_img_s": round(native, 1) if native else None,
        "device_prefetch_img_s": round(pref, 1),
        "h2d_mb_s": round(h2d, 1) if h2d else None,
        "h2d_ceiling_img_s_f32": round(h2d_img_s, 1) if h2d_img_s else None,
        "h2d_ceiling_img_s_uint8": round(h2d_img_s_u8, 1)
        if h2d_img_s_u8 else None,
        "train_resident_img_s": round(resident, 1) if resident else None,
        # python pipeline and native pipeline are SEPARATE keys — a diff
        # across commits must never compare two different pipelines
        "train_e2e_img_s": round(e2e, 1) if e2e else None,
        "train_e2e_uint8_img_s": round(e2e_u8, 1) if e2e_u8 else None,
        "train_e2e_native_img_s": round(e2e_native, 1)
        if e2e_native else None,
        # the feeds-the-chip verdict uses the best available pipeline
        "e2e_pct_of_resident": round(
            100 * max(e2e, e2e_u8 or 0, e2e_native or 0) / resident, 1)
        if e2e and resident else None,
        # link-bound only when even the LEANEST wire format's ceiling
        # can't approach the chip — if uint8 could feed it, a shortfall
        # there is a real pipeline finding, not the link's fault
        "h2d_bound": bool(h2d_img_s_u8 is not None and resident is not None
                          and h2d_img_s_u8 < 0.5 * resident),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
