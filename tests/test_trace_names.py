"""Names the program puts into a device trace and its spans on the
profiler's clock (docs/tracing.md "Names in a device trace"): named scopes
per block and step phase, a name on every Pallas kernel, telemetry.span as
a TraceAnnotation, route counters of pallas_kernels, JAX's own events as
``jit.*`` counters.  CPU only: names and counts, never a time."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu import parallel as par
from mxnet_tpu import telemetry
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import (pallas_block, pallas_int8,
                           pallas_kernels)


def _batch():
    rs = onp.random.RandomState(0)
    return (NDArray(jnp.asarray(rs.randn(8, 6), jnp.float32)),
            NDArray(jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32)))


def _net():
    net = nn.HybridSequential()
    body = nn.HybridSequential()
    body.add(nn.Dense(16, activation="relu"))
    net.add(body, nn.Dense(4))
    net.initialize()
    net.hybridize()
    return net


def _fused_train_step():
    return par.FusedTrainStep(_net(), SoftmaxCrossEntropyLoss(),
                              opt_mod.create("sgd", learning_rate=0.1),
                              dtype="bfloat16")


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
    prev = telemetry.set_trace_enabled(True)
    telemetry.trace_reset()
    yield
    telemetry.set_trace_enabled(prev)


# ------------------------------------------------------- (a) named scopes
class _Launched(Exception):
    pass


def _lowered_text(step, x, y):
    """The step program's text with debug info: the arguments of the first
    launch, lowered again (a second trace of the same function)."""
    seen = {}
    compiled = step._compiled

    def grab(*args):
        seen["args"] = args
        raise _Launched
    step._compiled = grab
    try:
        with pytest.raises(_Launched):
            step(x, y)
    finally:
        step._compiled = compiled
    return compiled.lower(*seen["args"]).as_text(debug_info=True)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_step_program_carries_phase_and_block_scopes(entry):
    step, (x, y) = ENTRIES[entry](), _batch()
    step(x, y)
    text = _lowered_text(step, x, y)
    for path in ("jvp(mx.fwd)/0/0/dot_general", "jvp(mx.fwd)/1/dot_general",
                 "transpose(jvp(mx.fwd))/0/0/", "jvp(mx.loss)/",
                 "mx.opt/0.0.weight/", "mx.opt/1.bias/"):
        assert path in text, f"{entry}: no scope path {path!r}"
    assert ("mx.cast" in text) == (entry == "FusedTrainStep")


def test_an_eager_forward_opens_no_scope(monkeypatch):
    net, (x, _) = _net(), _batch()
    net.hybridize(False)
    opened = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: opened.append(name) or real(name))
    net(x)
    assert opened == []
    fn, params = net.pure_fn()
    jax.make_jaxpr(lambda pv, xr: fn(jax.random.PRNGKey(0), pv, xr))(
        {n: p.data()._data for n, p in params.items()}, x._data)
    assert opened == ["0", "0", "1"]     # body, its Dense, the head


# ------------------------------------------- (b) a name on every kernel
def _subjaxprs(value):
    if hasattr(value, "eqns"):                  # a Jaxpr
        yield value
    elif hasattr(getattr(value, "jaxpr", None), "eqns"):   # a ClosedJaxpr
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _kernel_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                out += _kernel_names(sub)
    return out


_X = jnp.ones((1, 8, 8, 8), jnp.float32)
_W = jnp.ones((3, 3, 8, 8), jnp.float32)
_C = jnp.ones((8,), jnp.float32)
_ROWS = jnp.ones((16, 128), jnp.float32)
_QKV = jnp.ones((1, 2, 128, 128), jnp.float32)
_Q8 = jnp.ones((1, 8, 8, 8), jnp.int8)
_QC = jnp.ones((1, 128, 2 * 128), jnp.float32)  # (B, T, Hq·hd), two heads
_KC = jnp.ones((1, 128, 128), jnp.float32)      # their one key-value head
_LSE = jnp.ones((1, 2, 1, 128), jnp.float32)    # (B, Hq, 1, T) row statistics

_DT = jnp.ones((1, 128, 2), jnp.float32)        # (B, T, H): two heads of 64
_A = -jnp.ones((2,), jnp.float32)
_SSD_COL = jnp.ones((1, 1, 128, 4), jnp.float32)    # (B, G, T, cum | dt)
_SSD_STATES = jnp.ones((1, 1, 128, 128), jnp.bfloat16)  # (B, T/128, N, H·P)

KERNEL_SITES = {
    "block.conv3x3": (lambda: pallas_block.conv3x3(_X, _W),
                      ["mx_block_conv"]),
    "block.conv3x3_dgrad": (lambda: pallas_block.conv3x3_dgrad(_W, _X),
                            ["mx_block_dx"]),
    "block.conv3x3_wgrad": (lambda: pallas_block.conv3x3_wgrad(_X, _X),
                            ["mx_block_dw"]),
    "block._conv_affine": (
        lambda: pallas_block._conv_affine(_X, _W, _C, _C, None, True),
        ["mx_block_fwd"]),
    "block._conv_stats": (lambda: pallas_block._conv_stats(_X, _W),
                          ["mx_block_stats"]),
    "block._affine": (lambda: pallas_block._affine(_X, _C, _C, _X, True),
                      ["mx_block_affine"]),
    "kernels.softmax": (lambda: pallas_kernels._softmax_pallas(_ROWS),
                        ["mx_softmax_fwd"]),
    "kernels.layernorm": (
        lambda: pallas_kernels._layernorm_pallas(_ROWS, _ROWS[0], _ROWS[0],
                                                 1e-5),
        ["mx_layernorm_fwd"]),
    "kernels.attention": (
        lambda: pallas_kernels._attention_pallas(_QKV[0], _QKV[0], _QKV[0],
                                                 0.1, d=128),
        ["mx_attn_fwd"]),
    "kernels.attention_bwd": (
        lambda: pallas_kernels._attn_bwd_pallas(
            _QKV[0], _QKV[0], _QKV[0], _QKV[0], 0.1, d=128),
        ["mx_attn_bwd"]),
    "kernels.causal_attention": (
        lambda: pallas_kernels._causal_fwd_pallas(_QC, _KC, _KC, 2, 1,
                                                  (128, 128)),
        ["mx_causal_attn_fwd"]),
    "kernels.causal_attention_bwd": (
        lambda: pallas_kernels._causal_bwd_pallas(
            _QC, _KC, _KC, _QC, _LSE, _QC, 2, 1, (128, 128)),
        ["mx_causal_attn_bwd"]),
    "int8.qconv3x3": (
        lambda: pallas_int8.qconv3x3_affine(_Q8, _W.astype(jnp.int8), _C, _C),
        ["mx_qconv3x3"]),
    "kernels.ssd": (
        lambda: pallas_kernels._ssd_fwd_pallas(_KC, _DT, _A, _KC, _KC, _A, 2,
                                               1, 128, emit=True),
        ["mx_ssd_fwd"]),
    "kernels.ssd_bwd": (
        lambda: pallas_kernels._ssd_bwd_pallas(
            _KC, _KC, _KC, _A, _SSD_COL, _SSD_COL.transpose(0, 1, 3, 2),
            _SSD_STATES, _KC, 2, 1, 128),
        ["mx_ssd_bwd"]),
}


@pytest.mark.parametrize("site", sorted(KERNEL_SITES))
def test_every_pallas_call_site_names_its_kernel(site):
    fn, want = KERNEL_SITES[site]
    assert _kernel_names(jax.make_jaxpr(fn)().jaxpr) == want


# ------------------------------------------------ (c) route counters
def _routes():
    return {k[len("dispatch.pallas."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.pallas.") and v}


ROUTED = {
    "softmax": (lambda x: pallas_kernels.softmax_fused(x).sum(),
                _ROWS, "hits.softmax.128"),
    # LayerNorm's training forward is XLA by construction (_ln_fwd)
    "layernorm": (lambda x: pallas_kernels.layernorm_fused(
        x, x[0], x[0]).sum(), _ROWS, "fallbacks.layernorm.128"),
    "attention": (lambda q: pallas_kernels.attention_fused(q, q, q).sum(),
                  _QKV, "hits.attention.128"),
    # (B, T, Hq, hd) through ops/nn.py, which asks the kernel first
    "causal_attention": (lambda q: ops_nn.causal_gqa_attention(
        q, q[:, :, :1], q[:, :, :1]).sum(), _QKV.transpose(0, 2, 1, 3),
        "hits.causal_attention.128"),
    # b and c (B, T, G, N) of two heads of 64 through ops/nn.py
    "ssd": (lambda bc: ops_nn.ssd_chunked(
        jnp.ones((1, 128, 2, 64)), _DT, _A, bc, bc).sum(),
        _QKV[:, :1].transpose(0, 2, 1, 3), "hits.ssd.128"),
}


@pytest.mark.parametrize("kernel", sorted(ROUTED))
def test_one_count_per_routed_call_under_grad(monkeypatch, kernel):
    fn, arg, want = ROUTED[kernel]
    monkeypatch.setattr(pallas_kernels, "_FORCE_INTERPRET", True)
    telemetry.reset()
    jax.jit(jax.grad(fn))(arg)
    assert _routes() == {want: 1}
    if kernel == "layernorm":            # the inference forward is the kernel
        telemetry.reset()
        jax.jit(fn)(arg)
        assert _routes() == {"hits.layernorm.128": 1}


@pytest.mark.parametrize("kernel", sorted(ROUTED))
def test_a_route_that_says_no_counts_a_fallback(kernel):
    """Off the TPU (or off the tile) the answer is XLA, and it is counted:
    once per routed call, whether the caller asks first (ops/nn.py) or
    calls the fused entry point."""
    fn, arg, _ = ROUTED[kernel]
    telemetry.reset()
    jax.jit(jax.grad(fn))(arg[..., :96])
    assert _routes() == {f"fallbacks.{kernel}.96": 1}


def test_ops_nn_softmax_counts_its_fallback_once():
    from mxnet_tpu.ops import nn as ops_nn
    telemetry.reset()
    jax.jit(jax.grad(lambda x: ops_nn.softmax(x).sum()))(_ROWS[:, :96])
    assert _routes() == {"fallbacks.softmax.96": 1}


# ------------------------------------- (d) spans on the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("train.")]
    return out


def _traced_steps(tmp_path, entry, n=3):
    step, (x, y) = ENTRIES[entry](), _batch()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(n):
            step(x, y)
        step.sync()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path))


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_spans_land_in_a_profiler_trace(tmp_path, tracing_on, entry):
    events = _traced_steps(tmp_path, entry)
    steps = sorted(e for e in events if e[0] == "train.step")
    assert len(steps) == 3
    assert [int(s[3]["step"]) for s in steps] == [1, 2, 3]
    # the step-to-step interval rides on the annotation from the second on
    assert ["gap_us" in s[3] and "cpu_us" in s[3] for s in steps] == \
        [False, True, True]

    def inside(name, outer):
        return [e for e in events if e[0] == name
                and outer[1] <= e[1] and e[2] <= outer[2]]
    for s in steps:
        for child in ("train.prep", "train.launch", "train.writeback"):
            assert len(inside(child, s)) == 1, (child, s)
    # the first call builds, and the build holds the launch that compiles
    build, = [e for e in events if e[0] == "train.build"]
    assert inside("train.build", steps[0]) == [build]
    assert len(inside("train.launch", build)) == 1
    # the same steps in the program's own ring
    ring = [s for s in telemetry.trace_spans() if s[3] == "train.step"]
    assert [s[7]["step"] for s in ring] == [1, 2, 3]


def test_no_span_and_no_annotation_with_tracing_off(tmp_path):
    prev = telemetry.set_trace_enabled(False)
    telemetry.trace_reset()
    try:
        events = _traced_steps(tmp_path, "Trainer.fuse_step")
    finally:
        telemetry.set_trace_enabled(prev)
    assert events == [] and telemetry.trace_spans() == []


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_build_time_is_counted_once(entry):
    step, (x, y) = ENTRIES[entry](), _batch()
    before = telemetry.raw_snapshot()["counters"].get("fused.build_us", 0)
    step(x, y)
    built = telemetry.raw_snapshot()["counters"]["fused.build_us"]
    assert built > before
    step(x, y)
    assert telemetry.raw_snapshot()["counters"]["fused.build_us"] == built


# ------------------------------------------------ (e) JAX's own events
def test_a_fresh_jitted_function_counts_one_compile():
    def jit_counters():
        c = telemetry.raw_snapshot()["counters"]
        return [c.get(k, 0) for k in ("jit.compiles", "jit.compile_us",
                                      "jit.trace_us", "jit.lower_us")]
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    fresh = jax.jit(lambda v: v * 3.0 + 1.0)
    before = jit_counters()
    fresh(x)
    after = jit_counters()
    assert after[0] == before[0] + 1
    assert all(a > b for a, b in zip(after[1:], before[1:]))
    fresh(x)
    assert jit_counters() == after


def test_span_ids_are_unique_across_threads_and_a_fork_drops_the_pool():
    import threading
    ids, lock = set(), threading.Lock()

    def draw():
        mine = [telemetry._new_id() for _ in range(2000)]
        with lock:
            ids.update(mine)
    threads = [threading.Thread(target=draw) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(ids) == 12000 and 0 not in ids
    telemetry._new_id()
    pool = telemetry._trace_tl.id_pool
    telemetry._after_fork()              # what a forked child runs
    telemetry._new_id()
    assert telemetry._trace_tl.id_pool is not pool
