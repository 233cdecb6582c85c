"""The host's side of a long gap (docs/tracing.md, PR 38): the step-to-step
interval and the stall detector of both step classes, the wait for the
device where the host waits (``nd.fetch``), the collector (``host.gc``),
the feed's producer thread (``datafeed.stage`` and its children), the four
readers under ``chipbench/layer_metrics`` that turn them into numbers, and
the operator's table of device idle time by the program's span.  CPU only:
names, attributes and arithmetic on hand-written rings, never a rate."""
import gc
import importlib.util
import os
import resource
import threading
import time

import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu import parallel as par
from mxnet_tpu import telemetry
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.io import DataFeed
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import train as train_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME, START, DUR, TID, ATTRS = 3, 4, 5, 6, 7        # fields of a span record
# registry names that doubled a span in this PR's first hand-in and went
DOUBLES = ("nd.fetch_ready", "nd.fetch_wait_us", "fused.stall_us",
           "datafeed.stage_us", "host.gc_pause_us",
           "host.gc_collections.gen0", "host.gc_collections.gen1",
           "host.gc_collections.gen2")


def _batch():
    rs = onp.random.RandomState(0)
    return (NDArray(jnp.asarray(rs.randn(8, 6), jnp.float32)),
            NDArray(jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32)))


def _net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    return net


def _fused_train_step():
    return par.FusedTrainStep(_net(), SoftmaxCrossEntropyLoss(),
                              opt_mod.create("sgd", learning_rate=0.1))


def _trainer_fuse_step():
    net = _net()
    step = Trainer(net.collect_params(), "adam",
                   {"learning_rate": 1e-3}).fuse_step(
                       SoftmaxCrossEntropyLoss())
    step.net = net          # the trainer's parameters hold it only weakly
    return step


ENTRIES = {"FusedTrainStep": _fused_train_step,
           "Trainer.fuse_step": _trainer_fuse_step}


@pytest.fixture
def tracing_on():
    """Spans on, the ring empty, and only the collections a test asks for:
    one of a millisecond or more that the interpreter starts on its own
    would be one more span in a ring these tests read whole."""
    prev = telemetry.set_trace_enabled(True)
    telemetry.reset()
    gc.disable()
    yield
    gc.enable()
    telemetry.set_trace_enabled(prev)


def _spans(name):
    return [s for s in telemetry.trace_spans() if s[NAME] == name]


def _hist_count(name):
    return telemetry.raw_snapshot()["histograms"].get(name, {}).get("count", 0)


def _counter(name):
    return telemetry.raw_snapshot()["counters"].get(name, 0)


class Clock:
    """Stands in for the ``time`` module inside ``parallel/train.py``: the
    test moves the wall clock and the thread's CPU clock by hand, so a gap
    is what the test says it is, whatever the machine does meanwhile."""

    def __init__(self):
        self.wall_ns = self.cpu_ns = 10 ** 12

    def perf_counter_ns(self):
        return self.wall_ns

    def thread_time_ns(self):
        return self.cpu_ns

    def pass_ms(self, wall, cpu=0.0):
        self.wall_ns += int(wall * 1e6)
        self.cpu_ns += int(cpu * 1e6)

    def __getattr__(self, name):
        return getattr(time, name)


# ------------------------------------------------ the step-to-step interval
@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_gap_and_cpu_time_from_the_second_step_on(monkeypatch, tracing_on,
                                                  entry):
    clock = Clock()
    monkeypatch.setattr(train_mod, "time", clock)
    step, (x, y) = ENTRIES[entry](), _batch()
    for wall, cpu in ((0, 0), (70, 3), (70, 2), (75, 70)):
        clock.pass_ms(wall, cpu)
        step(x, y)
    attrs = [s[ATTRS] for s in _spans("train.step")]
    assert [a["step"] for a in attrs] == [1, 2, 3, 4]
    assert "gap_us" not in attrs[0] and "cpu_us" not in attrs[0]
    assert [a["gap_us"] for a in attrs[1:]] == [70000, 70000, 75000]
    assert [a["cpu_us"] for a in attrs[1:]] == [3000, 2000, 70000]
    assert _hist_count("fused.step_gap_us") == 3
    # the launch's histogram is what it was: one observation a step
    assert _hist_count("fused.step_us") == 4
    assert _spans("train.stall") == [] and _counter("fused.stalls") == 0


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_long_gap_is_a_stall_with_its_diagnosis(monkeypatch, tracing_on,
                                                  entry):
    """Eight gaps of 100 ms, then one of 400 ms during which the thread ran
    for 280 ms: one ``train.stall`` over the 300 ms of excess.  A gap of
    119 ms (under the median + 20 ms) and one of 124 ms with a median of
    100 (over the floor, under 1.25 x) are none."""
    clock = Clock()
    monkeypatch.setattr(train_mod, "time", clock)
    step, (x, y) = ENTRIES[entry](), _batch()
    for wall in [0] + [100] * 8 + [119]:
        clock.pass_ms(wall, 2)
        step(x, y)
    assert _spans("train.stall") == []
    before = resource.getrusage(resource.RUSAGE_SELF)
    clock.pass_ms(400, 280)
    wall_us = time.time_ns() // 1000
    step(x, y)
    after = resource.getrusage(resource.RUSAGE_SELF)
    stall, = _spans("train.stall")
    a = stall[ATTRS]
    assert (a["step"], a["gap_us"], a["excess_us"]) == (11, 400000, 300000)
    assert a["cpu_us"] == 280000 and a["gc_us"] >= 0
    assert a["waits"] == 0 and a["wait_us"] == 0 and a["ready"] is True
    for key, field in (("majflt", "ru_majflt"), ("nivcsw", "ru_nivcsw"),
                       ("nvcsw", "ru_nvcsw")):
        assert 0 <= a[key] <= getattr(after, field)
    assert after.ru_nvcsw >= before.ru_nvcsw
    # back-dated over the excess: it ends where the late step starts
    assert stall[DUR] == 300000
    assert abs(stall[START] + stall[DUR] - wall_us) < 5_000_000
    late = _spans("train.step")[-1]
    assert late[ATTRS]["gap_us"] == 400000 and stall[0] == late[0]
    assert _counter("fused.stalls") == 1
    # the median of the last sixteen still is 100 ms: 124 is under 1.25 x
    clock.pass_ms(124, 2)
    step(x, y)
    assert len(_spans("train.stall")) == 1


def test_a_stall_on_the_real_clock_spinning_and_sleeping(tracing_on):
    """The same on the machine's own clocks: a thread that spins through
    the excess was running (``cpu_us`` about the excess), one that sleeps
    was not.  Only the two steps after the pauses are looked at: a loaded
    machine may stall the others on its own."""
    step, (x, y) = _trainer_fuse_step(), _batch()
    for _ in range(8):
        step(x, y).asnumpy()
    until = time.perf_counter() + 0.25
    while time.perf_counter() < until:
        pass
    step(x, y).asnumpy()                     # step 9 ends the spin
    for _ in range(3):
        step(x, y).asnumpy()
    time.sleep(0.25)
    step(x, y).asnumpy()                     # step 13 ends the sleep
    stalls = {s[ATTRS]["step"]: s[ATTRS] for s in _spans("train.stall")}
    spun, slept = stalls[9], stalls[13]
    for a in (spun, slept):
        assert 200_000 <= a["excess_us"] <= a["gap_us"] < 5_000_000
    assert spun["cpu_us"] >= 150_000
    assert slept["cpu_us"] <= 100_000
    # each step's loss was fetched before the pause: no wait inside it
    # unless the CPU "device" was late itself
    for a in (spun, slept):
        assert a["ready"] is (a["waits"] == 0) and a["wait_us"] <= a["gap_us"]


# ------------------------------------------------ the wait for the device
class InFlight:
    """An array whose value lands ``lands_after`` seconds after the host
    first waits for it."""

    nbytes = 24

    def __init__(self, lands_after):
        self.lands_after, self.asked = lands_after, 0

    def is_ready(self):
        self.asked += 1
        return self.lands_after == 0

    def block_until_ready(self):
        time.sleep(self.lands_after)
        self.lands_after = 0
        return self

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()
        return onp.zeros((2, 3), onp.float32)

    def item(self):
        self.block_until_ready()
        return 0.0


FETCHES = {"asnumpy": NDArray.asnumpy, "item": NDArray.item,
           "asscalar": NDArray.asscalar, "wait_to_read": NDArray.wait_to_read,
           "wait_to_write": NDArray.wait_to_write}


@pytest.mark.parametrize("how", sorted(FETCHES))
def test_a_fetch_records_the_wait_and_only_the_wait(tracing_on, how):
    before = telemetry.raw_snapshot()
    FETCHES[how](NDArray(InFlight(0)))
    landed = NDArray(jnp.ones((2, 3)))
    landed._data.block_until_ready()
    FETCHES["asnumpy"](landed)
    assert telemetry.trace_spans() == []
    FETCHES[how](NDArray(InFlight(0.03)))
    span, = telemetry.trace_spans()
    assert span[NAME] == "nd.fetch" and span[ATTRS] == {"bytes": 24}
    assert span[DUR] >= 30_000 and span[TID] == threading.get_ident()
    # the span is the record: no counter or histogram beside it
    after = telemetry.raw_snapshot()
    for kind in ("counters", "histograms"):
        assert not [n for n in set(after[kind]) - set(before[kind])
                    if n.startswith("nd.")]


def test_a_stall_says_whether_the_result_had_landed(monkeypatch, tracing_on):
    """``ready`` of a ``train.stall``: true where every fetch of the
    interval found its array landed (the host was late), false where one
    waited (with the wait's length beside it)."""
    clock = Clock()
    monkeypatch.setattr(train_mod, "time", clock)
    step, (x, y) = _fused_train_step(), _batch()
    for wall in [0] + [100] * 6:
        clock.pass_ms(wall)
        step(x, y)
    NDArray(InFlight(0)).asnumpy()
    clock.pass_ms(300)
    step(x, y)
    NDArray(InFlight(0.03)).wait_to_read()
    NDArray(InFlight(0)).asnumpy()
    clock.pass_ms(300)
    step(x, y)
    late_host, late_device = (s[ATTRS] for s in _spans("train.stall"))
    assert late_host["ready"] is True and late_host["waits"] == 0
    assert late_host["wait_us"] == 0
    assert late_device["ready"] is False and late_device["waits"] == 1
    assert late_device["wait_us"] >= 30_000


# --------------------------------------------------------- the collector
@pytest.fixture
def collector_hooked():
    if telemetry._on_gc not in gc.callbacks:
        pytest.skip("telemetry was off at import: no gc.callbacks entry")
    gc.collect()


class Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened and
    whether it was closed."""

    opened = []

    def __init__(self, name, **attrs):
        self.name, self.attrs, self.open = name, attrs, None

    def __enter__(self):
        self.open = True
        Annotation.opened.append(self)
        return self

    def __exit__(self, *exc):
        self.open = False


def test_a_full_collection_is_a_span_and_a_young_one_is_not(
        collector_hooked, tracing_on, monkeypatch):
    monkeypatch.setattr(telemetry, "_TraceAnnotation", Annotation)
    monkeypatch.setattr(Annotation, "opened", [])
    gc.collect(0)
    assert telemetry.trace_spans() == [] and Annotation.opened == []
    t0 = time.time_ns() // 1000
    gc.collect()
    # written by the ring's next reader, not by the collector's callback
    assert not telemetry._span_recorder.spans()
    span, = telemetry.trace_spans()
    assert span[NAME] == "host.gc" and span[2] is None
    assert span[ATTRS]["generation"] == 2 and "collected" in span[ATTRS]
    assert span[TID] == threading.get_ident()
    assert t0 <= span[START] and span[START] + span[DUR] <= t0 + 5_000_000
    # on the profiler's clock too, from "start" to "stop"
    ann, = Annotation.opened
    assert (ann.name, ann.attrs, ann.open) == ("host.gc", {"generation": 2},
                                               False)
    assert telemetry.raw_snapshot()["counters"].get("fused.stalls", 0) == 0


def test_a_long_young_collection_is_written_afterwards(
        collector_hooked, tracing_on, monkeypatch):
    monkeypatch.setattr(telemetry, "_GC_SPAN_US", 0)
    gc.collect(1)
    span, = _spans("host.gc")
    assert span[ATTRS]["generation"] == 1


@pytest.mark.parametrize("held", ["registry", "ring"])
def test_a_collection_under_a_telemetry_lock_cannot_deadlock(
        collector_hooked, tracing_on, monkeypatch, held):
    """The collector runs its callbacks inside whatever bytecode set it
    off: ``_PyRegistry.observe`` and ``snapshot`` allocate under the
    registry's lock (the whole registry with MXNET_TPU_NO_NATIVE=1),
    ``_SpanRecorder.spans`` and ``reset`` under each shard's.  Neither
    lock is reentrant, so a callback that took one would block for good:
    a full collection on a thread that holds them has to come back."""
    if held == "registry":
        monkeypatch.setattr(telemetry, "LIB", None)
        monkeypatch.setattr(telemetry, "_py_enabled", True)
        locks = [telemetry._pyreg._mu]
    else:
        locks = [sh.mu for sh in telemetry._span_recorder.shards]
    with telemetry.span("loads the annotation class"):
        pass
    telemetry.trace_reset()
    came_back = threading.Event()

    def collect_holding_the_locks():
        for lock in locks:
            lock.acquire()
        try:
            gc.collect()
            came_back.set()
        finally:
            for lock in locks:
                lock.release()

    thread = threading.Thread(target=collect_holding_the_locks, daemon=True)
    thread.start()
    assert came_back.wait(20), "the collector's callback took a lock"
    thread.join(20)
    span, = _spans("host.gc")
    assert span[ATTRS]["generation"] == 2


# ------------------------------------------------- the feed's producer
STATS_KEYS = {"staged_batches", "h2d_bytes", "backpressure_waits",
              "consumer_waits", "consumer_wait_s", "sync_fallbacks",
              "restarts", "consumed", "depth", "sync_mode"}


def test_the_producer_thread_names_what_it_did(tracing_on):
    """Four uint8 batches through a ring of one: the consumer comes late
    (the producer waits for a slot), then the source is slow (the consumer
    waits for batch 2 and says so)."""
    def source():
        for k in range(4):
            if k == 2:
                time.sleep(0.15)
            yield (onp.full((2, 3, 4, 4), k, onp.uint8),
                   onp.full((2,), k, onp.int32))

    with DataFeed(source(), depth=1, scale=1 / 255) as feed:
        late = time.time() + 30
        while not feed.stats()["backpressure_waits"] and time.time() < late:
            time.sleep(0.01)
        time.sleep(0.12)
        got = [int(b[1].asnumpy()[0]) for b in feed]
        stats = feed.stats()
    assert got == [0, 1, 2, 3]
    assert set(stats) == STATS_KEYS
    assert stats["staged_batches"] == 4 and stats["backpressure_waits"] >= 1

    draws, stages = _spans("datafeed.source"), _spans("datafeed.stage")
    # the fifth draw found the source at its end: a draw and no stage
    assert [s[ATTRS]["batch"] for s in draws] == [0, 1, 2, 3, 4]
    assert [s[ATTRS]["batch"] for s in stages] == [0, 1, 2, 3]
    producer = {s[TID] for s in draws + stages}
    assert len(producer) == 1 and threading.get_ident() not in producer
    assert all(s[2] is None for s in draws + stages)    # roots
    for draw, stage in zip(draws, stages):              # a draw, then a stage
        assert draw[START] + draw[DUR] <= stage[START]
    assert draws[2][DUR] >= 150_000                     # the slow source
    by_parent = {}
    for s in telemetry.trace_spans():
        if s[NAME].startswith("datafeed.") and s[2] is not None:
            by_parent.setdefault(s[2], []).append(s)
    for stage in stages:
        kids = by_parent[stage[1]]
        assert {k[TID] for k in kids} == producer
        names = [k[NAME] for k in kids]
        assert names.count("datafeed.h2d") == 2          # images, labels
        assert names.count("datafeed.finalize") == 1     # the images' cast
        assert set(names) <= {"datafeed.h2d", "datafeed.finalize",
                              "datafeed.backpressure"}
        h2d = sorted(k[ATTRS]["bytes"] for k in kids
                     if k[NAME] == "datafeed.h2d")
        assert h2d == [8, 96]
    held = [s for s in stages
            if any(k[NAME] == "datafeed.backpressure"
                   for k in by_parent[s[1]])]
    assert held and held[0][DUR] >= 100_000
    assert not any(n.startswith("datafeed.stage")
                   for n in telemetry.raw_snapshot()["histograms"])
    # the consumer's wait names the batch it waited for
    waits = [s for s in _spans("datafeed.wait")
             if s[ATTRS]["mode"] == "stall"]
    assert 2 in [s[ATTRS]["batch"] for s in waits]
    assert all(s[TID] == threading.get_ident() for s in waits)


def test_synchronous_staging_names_the_same_parts(tracing_on):
    x = onp.zeros((2, 3, 4, 4), onp.uint8)
    with DataFeed(iter([(x, onp.zeros((2,), onp.int32))] * 2),
                  depth=0, scale=1 / 255) as feed:
        list(feed)
    waits = _spans("datafeed.wait")
    assert [s[ATTRS]["batch"] for s in waits if s[ATTRS]["mode"] == "sync"] \
        == [0, 1, 2]
    kids = [s for s in telemetry.trace_spans()
            if s[2] in {w[1] for w in waits}]
    assert sorted({k[NAME] for k in kids}) == ["datafeed.finalize",
                                               "datafeed.h2d"]
    assert _spans("datafeed.stage") == []


# --------------------------------------------------- tracing switched off
def _forbidden(*_a, **_k):
    raise AssertionError("called with span recording off")


def _run_everything_once():
    """Both step classes three steps each, two fetches of a late array, a
    feed of three batches and a full collection; the late array."""
    for entry in sorted(ENTRIES):
        step, (x, y) = ENTRIES[entry](), _batch()
        for _ in range(3):
            step(x, y).asnumpy()
    late = InFlight(0.01)
    NDArray(late).asnumpy()
    NDArray(late).wait_to_read()
    x = onp.zeros((2, 3, 4, 4), onp.uint8)
    with DataFeed(iter([(x, onp.zeros((2,), onp.int32))] * 3),
                  depth=1) as feed:
        assert len(list(feed)) == 3
    gc.collect()
    return late


@pytest.mark.parametrize("telemetry_on", [True, False])
def test_with_tracing_off_only_the_interval_is_kept(monkeypatch,
                                                    telemetry_on):
    """MXNET_TRACE=0: no span or attribute of PR 38, and the step and fetch
    paths call neither ``getrusage`` nor ``thread_time_ns`` nor
    ``is_ready()``.  The registry keeps ``fused.step_gap_us`` alone, under
    its own switch: the observability signals divide by it
    (``input_starved`` must not go silent with MXNET_TRACE=0)."""
    prev = telemetry.set_trace_enabled(False)
    was = telemetry.set_enabled(telemetry_on)
    telemetry.reset()
    try:
        monkeypatch.setattr(train_mod.resource, "getrusage", _forbidden)
        monkeypatch.setattr(time, "thread_time_ns", _forbidden)
        telemetry.record_span("train.stall", 1, 2, step=3)
        late = _run_everything_once()
        assert late.asked == 0
    finally:
        telemetry.set_enabled(was)
        telemetry.set_trace_enabled(prev)
    assert telemetry.trace_spans() == []
    snap = telemetry.raw_snapshot()
    assert snap["counters"].get("fused.stalls", 0) == 0
    # two gaps a step object of three steps, two step objects
    assert snap["histograms"].get("fused.step_gap_us", {}).get("count", 0) \
        == (4 if telemetry_on else 0)


def test_the_names_that_doubled_a_span_are_gone(tracing_on):
    """A fetch's wait, a collection, a staged batch and a stall's excess
    are spans; nothing in the registry repeats them."""
    _run_everything_once()
    snap = telemetry.raw_snapshot()
    assert _spans("nd.fetch") and _spans("datafeed.stage")
    for name in DOUBLES:
        assert name not in snap["counters"], name
        assert name not in snap["histograms"], name


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_sync_is_a_drain_and_no_stall(monkeypatch, tracing_on, entry):
    """``sync()`` fetches all that is in flight: the step after it carries
    no ``gap_us``, is no stall however long the drain took, and leaves the
    interval out of ``fused.step_gap_us`` and of the median.  The gaps
    before the drain stay the history: the loop that dispatches a step
    ahead refills its pipeline in 3 ms, and the whole step after that is
    no stall beside it."""
    clock = Clock()
    monkeypatch.setattr(train_mod, "time", clock)
    step, (x, y) = ENTRIES[entry](), _batch()
    for wall in [0] + [100] * 8:
        clock.pass_ms(wall, 2)
        step(x, y)
    step.sync()
    for wall in (900, 3, 100, 100):
        clock.pass_ms(wall, 2)
        step(x, y)
    after_drain, *following = (s[ATTRS] for s in _spans("train.step")[-4:])
    assert "gap_us" not in after_drain and "cpu_us" not in after_drain
    assert [a["gap_us"] for a in following] == [3000, 100000, 100000]
    assert _spans("train.stall") == [] and _counter("fused.stalls") == 0
    assert _hist_count("fused.step_gap_us") == 11
    clock.pass_ms(400, 2)                   # the detector still works
    step(x, y)
    assert [s[ATTRS]["excess_us"] for s in _spans("train.stall")] == [300000]


def test_switching_span_recording_off_and_on_leaves_no_false_gap(
        monkeypatch, tracing_on):
    """``benchmark/telemetry_overhead.py`` flips ``set_trace_enabled``
    between legs: the first step after it comes back on has no CPU stamp
    to subtract from (plain span, no stall), and the wall clock was kept
    meanwhile under the telemetry switch, so the next gap is a step's.
    With both switches off nothing is kept and the object starts afresh."""
    clock = Clock()
    monkeypatch.setattr(train_mod, "time", clock)
    step, (x, y) = _fused_train_step(), _batch()
    for wall in [0] + [100] * 8:
        clock.pass_ms(wall, 2)
        step(x, y)
    telemetry.set_trace_enabled(False)
    for _ in range(3):
        clock.pass_ms(100, 2)
        step(x, y)
    telemetry.set_trace_enabled(True)
    clock.pass_ms(100, 2)
    step(x, y)
    clock.pass_ms(100, 2)
    step(x, y)
    back_on, following = (s[ATTRS] for s in _spans("train.step")[-2:])
    assert "gap_us" not in back_on
    assert (following["gap_us"], following["cpu_us"]) == (100000, 2000)
    assert _hist_count("fused.step_gap_us") == 13       # every interval
    was = telemetry.set_enabled(False)
    telemetry.set_trace_enabled(False)
    try:
        clock.pass_ms(60_000)
        step(x, y)
    finally:
        telemetry.set_enabled(was)
        telemetry.set_trace_enabled(True)
    clock.pass_ms(60_000)
    step(x, y)
    clock.pass_ms(100, 2)
    step(x, y)
    afresh, following = (s[ATTRS] for s in _spans("train.step")[-2:])
    assert "gap_us" not in afresh and following["gap_us"] == 100000
    assert _spans("train.stall") == []
    assert _hist_count("fused.step_gap_us") == 14


# ----------------------------------------------------------- the readers
def _load(*parts):
    """A Python file by its path under the checkout (the benchmark's rule:
    a metric's file has dots in its name)."""
    spec = importlib.util.spec_from_file_location(
        "loaded_" + parts[-1].replace(".", "_"), os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reader(name):
    return _load("chipbench", "layer_metrics", name + ".py")


def _write_ring(gaps=True):
    """A window of five steps 100 ms apart but for one gap of 300 ms (a
    stall of 200 ms, in Python: the fetch before it waited 90 ms like the
    others' 95), a collection of 150 ms inside it and one before it, two
    staged batches of 5.5 ms inside it and one before it, each a draw
    (``datafeed.source``) followed by its ``datafeed.stage``."""
    main, stager, other = 11, 12, 13
    ids = iter(range(100, 1000))

    def put(name, start, dur, tid=main, parent=None, **attrs):
        sid = next(ids)
        telemetry._span_recorder.record(
            (1, sid, parent, name, start, dur, tid, attrs or None, None))
        return sid

    starts = [1_000_000, 1_100_000, 1_200_000, 1_500_000, 1_600_000]
    gap_us = [4_000_000, 100_000, 100_000, 300_000, 100_000]
    put("train.step", 500_000, 2000, step=0)            # the warm-up's
    for k, (t, g) in enumerate(zip(starts, gap_us)):
        put("train.step", t, 2000, step=k + 1,
            **({"gap_us": g, "cpu_us": 3000} if gaps else {}))
        put("train.launch", t + 500, 1000)
    if gaps:
        put("train.stall", 1_300_000, 200_000, step=4, gap_us=300_000,
            excess_us=200_000, cpu_us=195_000)
        for t, d in ((1_003_000, 95_000), (1_103_000, 95_000),
                     (1_203_000, 90_000), (1_503_000, 95_000),
                     (1_603_000, 95_000)):              # the last: after
            put("nd.fetch", t, d, bytes=4)
        put("nd.fetch", 1_250_000, 40_000, tid=other, bytes=4)
        put("host.gc", 700_000, 80_000, generation=2)
        put("host.gc", 1_250_000, 150_000, tid=stager, generation=2)
        # batch numbers start again with a ring: 1 was drawn twice
        for t, k in ((300_000, 1), (600_000, 0), (1_050_000, 1),
                     (1_400_000, 2)):
            put("datafeed.source", t, 400_000 if t == 300_000 else 1000,
                stager, batch=k)
            if t == 300_000:
                continue
            stage = put("datafeed.stage", t + 1000, 99_000, tid=stager,
                        batch=k)
            put("datafeed.h2d", t + 1000, 3500, stager, stage, bytes=9)
            put("datafeed.h2d", t + 4500, 500, stager, stage, bytes=1)
            put("datafeed.finalize", t + 5000, 500, stager, stage)
            put("datafeed.backpressure", t + 5500, 94_000, stager, stage)


READINGS = {"step_gap_excess_ms.train": 200.0,
            "host_exposed_ms.train": (600.0 - 375.0) / 4,
            "host_gc_ms.train": 150.0 / 5,
            "feed_stage_ms.train": 5.5}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_on_a_hand_written_ring(tracing_on, name):
    reader = _reader(name)
    assert reader.read({"steps": 5}) is None            # an empty ring
    assert reader.read({}) is None
    _write_ring()
    assert reader.read({"steps": 5}) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_reads_nothing_from_a_program_without_the_spans(tracing_on,
                                                               name):
    """The parent's ring: ``train.step`` and its children, no ``gap_us``, no
    ``nd.fetch``, ``host.gc`` or ``datafeed.stage``."""
    _write_ring(gaps=False)
    assert _reader(name).read({"steps": 5}) is None


def test_readers_on_a_real_loop(tracing_on):
    """Dispatch step k + 1, then fetch loss k, as the benchmark's loop
    does: the three readers of every cell give numbers that hang together
    (counts, not rates: this is a CPU)."""
    step, (x, y) = _trainer_fuse_step(), _batch()
    pending = None
    for _ in range(12):
        nxt = step(x, y)
        if pending is not None:
            float(pending.asnumpy())
        pending = nxt
    float(pending.asnumpy())
    excess = _reader("step_gap_excess_ms.train").read({"steps": 8})
    exposed = _reader("host_exposed_ms.train").read({"steps": 8})
    collected = _reader("host_gc_ms.train").read({"steps": 8})
    assert excess >= 0 and exposed > 0 and collected >= 0
    gaps = [s[ATTRS]["gap_us"] for s in _spans("train.step")[-7:]]
    assert exposed <= sum(gaps) / len(gaps) / 1e3 + 1e-9
    assert _reader("feed_stage_ms.train").read({"steps": 8}) is None


# ------------------------------------------------- the operator's table
def test_device_idle_goes_to_the_innermost_span_open_at_the_time():
    tool = _load("tools", "xprof", "summarize.py")
    spans = [("train.step", 0, 100), ("train.launch", 10, 50),
             ("nd.fetch", 120, 500), ("datafeed.stage", 90, 600),
             ("datafeed.h2d", 300, 100)]
    idle = [(20, 40), (130, 200), (310, 390), (700, 800), (95, 125),
            (50, 110), (380, 420)]
    assert tool.idle_by_span(idle, spans) == {
        # inside train.step the launch; after it the step, also where the
        # longer stage has opened on another thread; then the stage alone
        "train.launch": 20 + 10, "train.step": 40 + 5,
        "datafeed.stage": 10 + 20,
        # the fetch is shorter than the stage over it, the copy than both
        "nd.fetch": 70 + 5 + 20, "datafeed.h2d": 80 + 20,
        "(no span)": 100}
    assert tool.idle_by_span([(0, 10)], []) == {"(no span)": 10}
