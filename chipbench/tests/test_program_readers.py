"""The per-layer readers that read the program's own registry and span
ring (PR 27), on a hand-filled registry.  Counts only: nothing here is a
time measured on a device."""
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import files  # noqa: E402

READERS = ["step_build_s.train", "compile_cache_misses.train",
           "step_host_ms.train"]


def reader(name):
    return files.load_module(REPO, "chipbench", "layer_metrics", name + ".py")


class Registry:
    """Stands where ``mxnet_tpu.telemetry`` is for a reader: the two calls
    they make, over hand-made content."""

    def __init__(self, counters=(), spans=()):
        self.counters, self.spans = dict(counters), list(spans)

    def raw_snapshot(self):
        return {"counters": self.counters}

    def trace_spans(self):
        return self.spans


def span(name, dur_us):
    return (1, 2, None, name, 0, dur_us, 0, None, None)


@pytest.fixture
def registry(monkeypatch):
    import mxnet_tpu

    def put(**kw):
        reg = Registry(**kw)
        monkeypatch.setattr(mxnet_tpu, "telemetry", reg)
        return reg
    return put


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_says_nothing(registry, name):
    """An empty registry is what a commit before PR 27 has: the reader
    returns None and does not raise, with or without a run's evidence."""
    registry()
    assert reader(name).read({}) is None
    assert reader(name).read({"steps": 20}) is None


def test_step_build_s(registry):
    registry(counters={"fused.build_us": 4_250_000})
    assert reader("step_build_s.train").read({"steps": 20}) == 4.25


def test_compile_cache_misses(registry):
    reg = registry(counters={"jit.compiles": 31})
    read = reader("compile_cache_misses.train").read
    assert read({"steps": 20}) == 0             # warm: compiled, none missed
    reg.counters["jit.cache_misses"] = 7
    assert read({"steps": 20}) == 7


def test_step_host_ms_takes_the_windows_steps(registry):
    """Four warm-up steps of 9 ms, then the window's three of 2, 3 and
    4 ms: the newest ``steps`` spans are the window's."""
    spans = []
    for ms in (9, 9, 9, 9, 2, 3, 4):
        spans += [span("train.prep", 100), span("train.launch", 500),
                  span("train.writeback", 100), span("train.step", ms * 1000)]
    registry(spans=spans)
    read = reader("step_host_ms.train").read
    assert read({"steps": 3}) == pytest.approx(3.0)
    # a train.step with no train.launch inside meant another interval
    registry(spans=[s for s in spans if s[3] == "train.step"])
    assert read({"steps": 3}) is None


def test_readers_on_a_real_fused_step():
    """The program's side of the contract: after fused steps the registry
    holds what the three readers read."""
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon import Trainer, nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.ndarray import NDArray

    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    step = Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 0.1}).fuse_step(SoftmaxCrossEntropyLoss())
    rs = onp.random.RandomState(0)
    x = NDArray(jnp.asarray(rs.randn(8, 6), jnp.float32))
    y = NDArray(jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32))
    prev = telemetry.set_trace_enabled(True)
    try:
        for _ in range(5):
            step(x, y)
    finally:
        telemetry.set_trace_enabled(prev)
    step.sync()
    evidence = {"steps": 3}
    assert reader("step_build_s.train").read(evidence) > 0
    assert reader("compile_cache_misses.train").read(evidence) >= 0
    assert reader("step_host_ms.train").read(evidence) > 0
