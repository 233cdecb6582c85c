"""DataFeed — the pipelined host→device input service (docs/datafeed.md).

≙ the reference's iter_prefetcher.h double buffering, lifted to the
device boundary: a background staging thread moves batch N+1 over the
h2d link and runs the deferred uint8→float32 cast + normalize ON DEVICE
while the accelerator computes on batch N.  Three properties the plain
PrefetchingIter lacks:

 * the wire carries uint8 (4× less h2d traffic) when the source is a
   ``NativeImageRecordIter(dtype="uint8")`` — the cast/normalize the
   host used to do per-pixel becomes one fused device kernel;
 * the staging buffer is DONATED to that kernel (`donate_argnums`), so
   XLA reuses the uint8 landing allocation instead of holding both
   copies (donation is skipped on backends that do not support it);
 * per-stage counters (staged batches, h2d bytes, producer backpressure,
   consumer starvation, sync fallbacks) are exported through ``stats()``
   and as ``mx.profiler`` gauges, so a starved accelerator is
   diagnosable from the profile, not inferred from throughput.

Ring semantics: a bounded queue of ``depth`` staged batches.  The
producer blocks (counted as backpressure) when the ring is full; the
consumer blocks (counted as a sync fallback — the pipeline degrades to
exactly synchronous behavior) when the ring is empty.  ``close()`` and
``reset()`` are safe at any point, including mid-epoch with a full ring
and a blocked producer; abandoning the iterator never deadlocks the
staging thread.
"""
from __future__ import annotations

import itertools
import os
import queue as _q
import threading
import time

import numpy as np

from .. import telemetry as _telemetry

__all__ = ["DataFeed"]

_SENTINEL = object()


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


class DataFeed:
    """Double-buffered device staging ring over any batch source.

    Parameters
    ----------
    source : DataIter | iterable
        Yields ``DataBatch``es, ``(data, label, pad)`` numpy tuples
        (``NativeImageRecordIter.next_raw``), or arbitrary array
        pytrees (gluon ``DataLoader`` batches).
    depth : int
        Ring capacity (staged batches in flight).  ``0`` runs fully
        synchronous — same results, no overlap.  Default from
        ``MXNET_DATAFEED_DEPTH``, else 2 (double buffering).
    device : jax.Device, optional
        Staging target; default ``jax.devices()[0]``.
    mean, std, scale : array-like / float, optional
        Device-side normalize applied to image data as
        ``(x.astype(f32) * scale - mean) / std`` with per-channel
        broadcasting.  When unset and the wire is uint8, the cast to
        float32 still happens on device.
    layout : {"NCHW", "NHWC"}, optional
        Output layout for 4-D image data.  Sources feed NCHW (the
        native loader's layout); ``"NHWC"`` adds a device-side
        transpose so DataFeed can sit behind the NHWC ImageRecordIter
        contract.

    Per-channel ``mean`` / ``std`` of C entries find their axis in a 4-D
    batch by its length: axis 1 (NCHW, also when both fit), else the last
    one - a loader that hands decoded channel-last batches over - and such
    a batch is left in its layout.
    """

    def __init__(self, source, depth=None, device=None, mean=None,
                 std=None, scale=None, layout=None, name="datafeed"):
        if depth is None:
            depth = _env_int("MXNET_DATAFEED_DEPTH", 2)
        self._source = source
        self._depth = max(0, int(depth))
        self._device = device
        self._name = name
        self._layout = layout
        self._norm = self._build_norm_spec(mean, std, scale)
        self._finalize_cache = {}
        self._lock = threading.Lock()
        self._stats = {
            "staged_batches": 0, "h2d_bytes": 0,
            "backpressure_waits": 0, "consumer_waits": 0,
            "consumer_wait_s": 0.0, "sync_fallbacks": 0,
            "restarts": 0, "consumed": 0,
            "depth": self._depth, "sync_mode": False,
        }
        self._queue = None
        self._thread = None
        self._abandoned = None
        self._err = None
        self._closed = False
        self._gauges = None
        try:
            from .. import telemetry
            telemetry.register_ring(self)   # weak — snapshot() polls stats()
        except Exception:
            pass
        self._start()

    # -------------------------------------------------------- lifecycle --
    def _start(self):
        self._drawn = 0         # draws since this ring started
        if self._depth == 0:
            self._stats["sync_mode"] = True
            self._sync_it = iter(self._iter_source())
            return
        self._queue = _q.Queue(maxsize=self._depth)
        self._abandoned = threading.Event()
        self._err = None
        try:
            self._thread = threading.Thread(
                target=self._stage_loop, daemon=True,
                name=f"{self._name}-stager")
            self._thread.start()
        except RuntimeError:
            # can't spawn a thread (interpreter teardown, thread limits):
            # degrade to synchronous staging rather than failing the run
            self._thread = None
            self._stats["sync_mode"] = True
            self._stats["sync_fallbacks"] += 1
            self._sync_it = iter(self._iter_source())

    def reset(self):
        """Stop the ring, reset the source, restart — a fresh epoch."""
        self._shutdown_ring()
        if hasattr(self._source, "reset"):
            self._source.reset()
        with self._lock:
            self._stats["restarts"] += 1
            self._stats["consumed"] = 0     # new epoch: batch position 0
        self._closed = False
        self._start()

    def close(self):
        """Release the staging thread and queued device batches."""
        self._shutdown_ring()
        self._closed = True

    def _shutdown_ring(self):
        if self._abandoned is not None:
            self._abandoned.set()
        if self._queue is not None:
            try:
                while True:
                    self._queue.get_nowait()
            except _q.Empty:
                pass
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self._queue = None
        self._abandoned = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # ------------------------------------------------------------ source --
    def _iter_source(self):
        src = self._source
        next_raw = getattr(src, "next_raw", None)
        if next_raw is not None:
            # native loader fast path: raw numpy buffers, no NDArray wrap
            while True:
                try:
                    yield next_raw()
                except StopIteration:
                    return
        else:
            for item in src:
                yield item

    # ----------------------------------------------------------- staging --
    def _build_norm_spec(self, mean, std, scale):
        if mean is None and std is None and scale is None:
            return None
        to_arr = (lambda v: None if v is None
                  else np.asarray(v, np.float32))
        return {"mean": to_arr(mean), "std": to_arr(std),
                "scale": None if scale is None else float(scale)}

    def _get_device(self):
        if self._device is None:
            import jax
            self._device = jax.devices()[0]
        return self._device

    def _finalize_fn(self, key):
        """Jitted device-side cast/normalize(/transpose), donated input.

        One compiled fn per (shape, dtype) — the donation means XLA may
        reuse the uint8 staging allocation for the output, which is the
        'donated staging buffers' half of the double-buffer design.
        """
        fn = self._finalize_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        norm, layout = self._norm, self._layout
        ndim, shape = key[2], key[3]
        # where (C,) constants find C channels: axis 1 unless only the last
        # axis has that length (a channel-last source)
        per_channel = [v.shape[0] for v in (norm or {}).values()
                       if getattr(v, "ndim", 0) == 1]
        channel_last = ndim == 4 and bool(per_channel) and all(
            c != shape[1] and c == shape[-1] for c in per_channel)

        def _norm_shape(v):
            # per-channel constants broadcast over NCHW: (C,) → (C,1,1);
            # over a channel-last source they broadcast as they are
            if v is None or v.ndim == 0 or ndim != 4 or channel_last:
                return v
            return v.reshape(v.shape[0], *([1] * (ndim - 2)))

        mean = None if norm is None else _norm_shape(norm["mean"])
        std = None if norm is None else _norm_shape(norm["std"])
        scale = None if norm is None else norm["scale"]

        def finalize(x):
            y = x.astype(jnp.float32)
            if scale is not None:
                y = y * scale
            if mean is not None:
                y = y - mean
            if std is not None:
                y = y / std
            if layout == "NHWC" and y.ndim == 4 and not channel_last:
                y = jnp.transpose(y, (0, 2, 3, 1))
            return y

        donate = ()
        try:
            if self._get_device().platform != "cpu":
                donate = (0,)          # CPU backend can't donate; the
        except Exception:              # warning per-batch is pure noise
            pass
        fn = jax.jit(finalize, donate_argnums=donate)
        self._finalize_cache[key] = fn
        return fn

    def _needs_finalize(self, arr):
        return (self._norm is not None or self._layout == "NHWC" or
                getattr(arr, "dtype", None) == np.uint8)

    def _stage_array(self, arr, is_data):
        import jax
        from ..ndarray import NDArray
        host = arr._data if isinstance(arr, NDArray) else np.asarray(arr)
        nbytes = int(getattr(host, "nbytes", 0))
        # the host's cost of enqueueing the copy: nothing here waits for
        # it to land, so copy k overlaps the staging of batch k + 1
        with _telemetry.span("datafeed.h2d", bytes=nbytes):
            dev = jax.device_put(host, self._get_device())
        with self._lock:
            self._stats["h2d_bytes"] += nbytes
        if is_data and self._needs_finalize(host):
            with _telemetry.span("datafeed.finalize"):
                fn = self._finalize_fn((is_data, str(host.dtype), host.ndim,
                                        tuple(host.shape)))
                dev = fn(dev)
        return NDArray(dev)

    def _stage(self, item):
        """Host batch → device-resident DataBatch (or pytree)."""
        from . import DataBatch

        if isinstance(item, DataBatch):
            item.data = [self._stage_array(a, True) for a in item.data]
            if item.label is not None:
                item.label = [self._stage_array(a, False)
                              for a in item.label]
            return item
        if (isinstance(item, tuple) and len(item) == 3 and
                isinstance(item[0], np.ndarray) and
                isinstance(item[2], int)):
            # NativeImageRecordIter.next_raw(): (data, label, pad)
            data, label, pad = item
            return DataBatch(data=[self._stage_array(data, True)],
                             label=[self._stage_array(label, False)],
                             pad=pad)
        if isinstance(item, (tuple, list)):
            # generic pytree (gluon DataLoader batches): first entry is
            # the sample data, the rest ride along as labels/extras.
            # dtypes pass through UNCHANGED unless a normalize/layout
            # was configured — pipeline=True must not silently retype a
            # loader's uint8 batches
            explicit = (self._norm is not None or
                        self._layout is not None)
            return type(item)(
                self._stage_array(a, explicit and i == 0)
                if hasattr(a, "dtype") else a
                for i, a in enumerate(item))
        return self._stage_array(item, True)

    def _stage_loop(self):
        queue, abandoned = self._queue, self._abandoned
        source = self._iter_source()
        try:
            # batch = draws since the ring started, which is also the
            # consumer's count: its datafeed.wait names the same batch
            for seq in itertools.count():
                with _telemetry.span("datafeed.source", parent=None,
                                     batch=seq):
                    item = next(source, _SENTINEL)
                if item is _SENTINEL:
                    return
                # opened once there is a batch: the source's end is no stage
                with _telemetry.span("datafeed.stage", parent=None,
                                     batch=seq):
                    self._stage_into(queue, abandoned, item)
                if abandoned.is_set():
                    return
                self._gauge("datafeed/ring_depth", queue.qsize())
        except BaseException as e:          # surfaces at the consumer
            self._err = e
        finally:
            while not abandoned.is_set():
                try:
                    queue.put(_SENTINEL, timeout=0.1)
                    break
                except _q.Full:
                    continue

    def _stage_into(self, queue, abandoned, item):
        """Stage ``item`` and hand it over, on the producer's thread.
        Children of the caller's ``datafeed.stage``: ``datafeed.h2d`` and
        ``datafeed.finalize`` an array, and ``datafeed.backpressure`` where
        the ring was full."""
        staged = self._stage(item)
        with self._lock:
            self._stats["staged_batches"] += 1
        self._gauge("datafeed/staged", self._stats["staged_batches"])
        try:
            queue.put_nowait(staged)
        except _q.Full:
            # ring full: the device is the bottleneck (the healthy
            # state) — count once per batch, then wait
            with self._lock:
                self._stats["backpressure_waits"] += 1
            with _telemetry.span("datafeed.backpressure"):
                while not abandoned.is_set():
                    try:
                        queue.put(staged, timeout=0.1)
                        break
                    except _q.Full:
                        continue

    def _gauge(self, name, value):
        try:
            from .. import profiler, telemetry
            # registry twin of the trace gauge: datafeed/ring_depth →
            # datafeed.ring_depth (the '/' form stays for chrome traces)
            telemetry.gauge_set(name.replace("/", "."), value)
            if self._gauges is None:
                self._gauges = {}
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = profiler.Counter(name)
            g.set_value(value)
        except Exception:
            pass

    # ---------------------------------------------------------- consume --
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise RuntimeError("DataFeed is closed; call reset()")
        if self._queue is None:                      # synchronous mode
            # the draw+stage IS the wait in sync mode; the span lands in
            # the consumer thread's current (per-step) trace, so feed
            # stalls show up keyed to the step that paid for them; the
            # histogram twin (datafeed.wait_us) is what the obs recorder
            # derives the input-stall fraction from
            t0 = time.perf_counter()
            with _telemetry.span("datafeed.wait", mode="sync",
                                 batch=self._drawn):
                item = next(self._sync_it)           # StopIteration flows
                staged = self._stage(item)
            _telemetry.observe("datafeed.wait_us",
                               (time.perf_counter() - t0) * 1e6)
            self._wait_resident(staged)
            self._drawn += 1
            with self._lock:
                self._stats["consumed"] += 1
            return staged
        try:
            item = self._queue.get_nowait()
        except _q.Empty:
            # ring empty: behave exactly like a synchronous pipeline
            # (wait for the stager) and count the degradation
            with self._lock:
                self._stats["consumer_waits"] += 1
                self._stats["sync_fallbacks"] += 1
            t0 = time.perf_counter()
            with _telemetry.span("datafeed.wait", mode="stall",
                                 batch=self._drawn):
                item = self._wait_for_batch()
            waited = time.perf_counter() - t0
            _telemetry.observe("datafeed.wait_us", waited * 1e6)
            with self._lock:
                self._stats["consumer_wait_s"] += waited
        if item is _SENTINEL:
            err, self._err = self._err, None
            if err is not None:
                raise err
            raise StopIteration
        self._wait_resident(item)
        self._drawn += 1
        with self._lock:
            self._stats["consumed"] += 1
        return item

    def _wait_resident(self, item):
        """A batch is staged once its copy is *enqueued*; it is handed over
        once the copy (and the cast/normalise program behind it) has
        landed.  What that takes is the loop's wait for the host link and
        counts as such: otherwise it would show only as device idle time in
        the step that reads the batch.  A batch that has landed (the fast
        path) records nothing."""
        from . import DataBatch
        from ..ndarray import NDArray
        arrays = (item.data + (item.label or []) if isinstance(item, DataBatch)
                  else item if isinstance(item, (tuple, list)) else [item])
        late = [a._data for a in arrays if isinstance(a, NDArray)
                and not a._data.is_ready()]
        if not late:
            return
        import jax
        t0 = time.perf_counter()
        with _telemetry.span("datafeed.wait", mode="copy",
                             batch=self._drawn):
            jax.block_until_ready(late)
        waited = time.perf_counter() - t0
        _telemetry.observe("datafeed.wait_us", waited * 1e6)
        with self._lock:
            self._stats["consumer_wait_s"] += waited

    next = __next__

    # -------------------------------------------------------- checkpoint --
    def position(self):
        """``{"epoch", "batch"}`` consumed so far — recorded in a
        checkpoint manifest's meta so a resumed run can re-align the
        feed (see :meth:`seek`)."""
        with self._lock:
            return {"epoch": self._stats["restarts"],
                    "batch": self._stats["consumed"]}

    def seek(self, batch, epoch=None):
        """Fast-forward to ``batch`` consumed batches (resume-after-
        restore).  ``batch`` may land past the epoch boundary — a
        service cursor restore legitimately does — and the feed
        advances THROUGH the rollover (reset → re-permute → keep
        counting) instead of silently clamping at epoch end; the
        return value is the true :meth:`position` reached.  With
        ``epoch=`` the feed first rolls forward to that absolute
        epoch, then to ``batch`` within it.

        Sources that carry their own cursor protocol
        (``position()``/``seek()`` — the distributed data service's
        FeedClient) get an O(1) jump: the source's cursor moves and
        the ring restarts on it, no draw-and-discard.  Everything
        else draws and discards — correctness over cleverness."""
        batch = int(batch)
        if batch < 0:
            raise ValueError(f"negative batch {batch}")
        src = self._source
        if (callable(getattr(src, "seek", None))
                and callable(getattr(src, "position", None))):
            self._shutdown_ring()
            pos = (src.seek(batch) if epoch is None
                   else src.seek(batch, epoch=epoch))
            with self._lock:
                self._stats["restarts"] = int(pos.get("epoch", 0))
                self._stats["consumed"] = int(pos.get("batch", 0))
            self._closed = False
            self._start()
            return self.position()
        empty_streak = 0
        if epoch is not None:
            while self.position()["epoch"] < int(epoch):
                drew = False
                try:
                    while True:
                        next(self)
                        drew = True
                except StopIteration:
                    pass
                empty_streak = 0 if drew else empty_streak + 1
                if empty_streak >= 2:    # source yields nothing at
                    return self.position()   # all: don't spin forever
                self.reset()
        with self._lock:
            remaining = max(0, batch - self._stats["consumed"])
        while remaining > 0:
            try:
                next(self)
                remaining -= 1
                empty_streak = 0
            except StopIteration:
                # epoch boundary mid-seek: roll through it
                empty_streak += 1
                if empty_streak >= 2:
                    break
                self.reset()
        return self.position()

    def _wait_for_batch(self):
        """Blocking get that stays LIVE: a stager killed without its
        sentinel (hard thread death) or a concurrent close() must end
        the iteration, never deadlock the consumer."""
        queue, abandoned, thread = self._queue, self._abandoned, \
            self._thread
        while True:
            try:
                return queue.get(timeout=0.5)
            except _q.Empty:
                if abandoned is None or abandoned.is_set():
                    raise StopIteration
                if thread is not None and not thread.is_alive():
                    err, self._err = self._err, None
                    if err is not None:
                        raise err
                    raise StopIteration

    # ------------------------------------------------------------- stats --
    @property
    def batch_size(self):
        return getattr(self._source, "batch_size", 0)

    @property
    def provide_data(self):
        return getattr(self._source, "provide_data", None)

    @property
    def provide_label(self):
        return getattr(self._source, "provide_label", None)

    def stats(self):
        """Ring + source counters as one dict (the bench/profiler
        observability surface; see docs/datafeed.md)."""
        with self._lock:
            out = dict(self._stats)
        src_stats = getattr(self._source, "stats", None)
        if callable(src_stats):
            try:
                out["source"] = src_stats()
            except Exception:
                pass
        return out
