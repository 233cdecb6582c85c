"""The ``train`` runner: a fused training step fed from a ring of batches.

``run(cell)`` builds the configuration's net through the program's own
entry point, checks the first fused loss against the same net run un-fused
in float32, warms the step up, and then either measures for
``cell["seconds"]`` (plain run) or traces ``mix["trace_steps"]`` steps
between two drains (traced run).  It returns *evidence*: what the
end-to-end metrics and the readers under ``layer_metrics/`` are taken from.

The loop is the one a training script runs: dispatch step k+1, *then* fetch
loss k.  One step is always in flight, so the device never drains, and
every step has a completion time on the host's clock.
"""
from __future__ import annotations

import contextlib
import importlib
import math
import sys
import time

import numpy as onp

from chipbench import trace_reduce
from chipbench.files import load_module

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
now = time.perf_counter


def say(cell, msg):
    print(f"[chipbench] {cell['name']}: {msg} at {now() - cell['t0']:.1f}s",
          file=sys.stderr, flush=True)


def counters():
    from mxnet_tpu import telemetry
    return dict(telemetry.raw_snapshot()["counters"])


def delta(after, before, name):
    return after.get(name, 0) - before.get(name, 0)


class Compiles:
    """Counts what JAX compiles (or loads from its cache) from now on: a
    listener on the event JAX records around every backend compile."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1


# ------------------------------------------------------------------ inputs
def make_ring(config, mix, seed):
    """``mix["batches"]`` seeded batches, made on the device by one jitted
    call and left there: the input feed is bypassed."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray

    batch, spec = config["batch"], config["inputs"]

    def draw(key, s):
        shape = (batch, *s["shape"])
        if s["dist"] == "uniform":
            return jax.random.uniform(key, shape, jnp.dtype(s["dtype"]))
        if s["dist"] == "randint":
            return jax.random.randint(key, shape, 0, s["high"],
                                      jnp.dtype(s["dtype"]))
        raise ValueError(f"unknown input distribution {s['dist']!r}")

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * mix["batches"])
        return tuple((draw(keys[2 * i], spec["x"]),
                      draw(keys[2 * i + 1], spec["y"]))
                     for i in range(mix["batches"]))

    ring = make(jax.random.PRNGKey(seed))
    jax.block_until_ready(ring)
    return [(NDArray(x), NDArray(y)) for x, y in ring]


# ------------------------------------------------------------------- build
def build(config, seed, x0):
    """The net, initialised from ``seed``, and the step object of the
    configuration's entry point.  One hybridized forward of a single row
    resolves the deferred shapes, as a user's script does."""
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon import loss as gloss

    mx.seed(seed)
    m = config["model"]
    net = getattr(importlib.import_module(m["module"]),
                  m["builder"])(**m.get("kwargs", {}))
    net.initialize()
    net.hybridize()
    net(x0[:1])
    loss_fn = getattr(gloss, config["loss"])()
    opt, entry = config["optimizer"], config["entry"]
    if entry["kind"] == "FusedTrainStep":
        step = par.FusedTrainStep(
            net, loss_fn, opt_mod.create(opt["name"], **opt["params"]),
            dtype=entry.get("dtype"))
    elif entry["kind"] == "Trainer.fuse_step":
        step = Trainer(net.collect_params(), opt["name"],
                       dict(opt["params"])).fuse_step(loss_fn)
    else:
        raise ValueError(f"unknown entry point {entry['kind']!r}")
    return net, loss_fn, step


@contextlib.contextmanager
def kernels_off():
    """While the reference is traced every default Pallas route answers
    XLA: all of them ask ``pallas_block.one_tpu()``."""
    from mxnet_tpu.ops import pallas_block
    was = pallas_block.one_tpu
    pallas_block.one_tpu = lambda: False
    try:
        yield
    finally:
        pallas_block.one_tpu = was


def reference_loss(net, loss_fn, x, y):
    """The same net un-fused: its forward in training mode and the loss, as
    one jitted float32 program under the highest matmul precision, no
    Pallas kernel, no AMP cast, on the same whole batch (BatchNorm's batch
    statistics make a slice of it a different problem).  Independent of
    the fused step, the optimizer and the kernels; not of the layer code."""
    import jax
    from mxnet_tpu import tape
    from mxnet_tpu.ndarray import NDArray

    fn, params = net.pure_fn()
    pvals = {n: p.data()._data for n, p in params.items()}

    def loss_of(pvals, x, y):
        prev = tape.set_training(True)
        try:
            outs, _aux = fn(jax.random.PRNGKey(0), pvals, x)
        finally:
            tape.set_training(prev)
        return loss_fn(NDArray(outs[0]), NDArray(y))._data.mean()

    with jax.default_matmul_precision("highest"), kernels_off():
        return float(jax.jit(loss_of)(pvals, x._data, y._data))


def peak_bytes(device):
    """Peak bytes on the device as its allocator reports them: live arrays
    (``peak_bytes_in_use``) plus what it reserves for the temporaries of
    loaded programs (``peak_bytes_reserved``).  The first alone leaves a
    running program's temporaries out (PERF.md, section 6, PR 25)."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0)), stats


def required_flops(cell):
    """Required operations per sample, by the function the configuration
    names under ``flops`` (``module`` is a file beside ``flops.py``)."""
    f = cell["config"].get("flops")
    if not f:
        return None
    module = load_module(cell["root"], "chipbench",
                         f.get("module", "flops") + ".py")
    return getattr(module, f["function"])(**f["kwargs"])


# -------------------------------------------------------------------- loop
def loop(step, ring, seconds=None, steps=None):
    """Dispatch step k+1, then fetch loss k, until ``seconds`` have passed
    or ``steps`` steps are dispatched.  Returns the start, each step's
    completion time, each dispatch's host time and the losses."""
    from jax.profiler import TraceAnnotation
    done, dispatch, losses = [], [], []
    begin, pending, k = now(), None, 0
    while True:
        nxt = None
        if (k < steps) if steps is not None else (now() < begin + seconds):
            x, y = ring[k % len(ring)]
            with TraceAnnotation("chipbench.dispatch"):
                t = now()
                nxt = step(x, y)
                dispatch.append(now() - t)
            k += 1
        if pending is not None:
            with TraceAnnotation("chipbench.fetch"):
                losses.append(float(pending.asnumpy()))
            done.append(now())
        pending = nxt
        if pending is None:
            return begin, done, dispatch, losses


# --------------------------------------------------------------------- run
def run(cell):
    import jax
    from jax.profiler import TraceAnnotation

    config, mix, device = cell["config"], cell["mix"], cell["devices"][0]
    say(cell, f"{config['name']} entry={config['entry']} "
        f"batch={config['batch']} on {len(cell['devices'])} x "
        f"{device.device_kind}; imports done")
    compiles = Compiles()
    ring = make_ring(config, mix, cell["seed"])
    say(cell, f"ring of {len(ring)} batches on the device")
    net, loss_fn, step = build(config, cell["seed"], ring[0][0])
    say(cell, "net initialised, shapes resolved, step object")
    ref = reference_loss(net, loss_fn, *ring[0])
    say(cell, f"float32 reference loss {ref:.5f}")

    c0 = counters()
    _, _, _, warm = loop(step, ring, steps=mix["warmup_steps"])
    step.sync()
    c1 = counters()
    say(cell, "warm-up losses " + " ".join(f"{l:.4f}" for l in warm))

    evidence = {"trace": None}
    seen, cw = compiles.n, counters()
    if cell["trace"]:
        with trace_reduce.profiled(evidence):
            step.sync()
            with TraceAnnotation(trace_reduce.WINDOW):
                begin, done, dispatch, losses = loop(
                    step, ring, steps=mix["trace_steps"])
                step.sync()
        say(cell, f"traced {len(done)} steps, "
            f"{evidence.get('trace_bytes', 0)} bytes of trace")
    else:
        begin, done, dispatch, losses = loop(step, ring,
                                             seconds=cell["seconds"])
    ca, in_window = counters(), compiles.n - seen
    memory_peak, stats = peak_bytes(device)
    say(cell, f"peak {memory_peak} bytes; allocator {stats}")
    window = done[-1] - begin
    gaps = onp.diff(done)
    say(cell, f"{len(done)} steps in {window:.3f}s, losses "
        f"{losses[0]:.4f} .. {losses[-1]:.4f}; longest gaps (ms, after step) "
        + " ".join(f"{1e3 * gaps[i]:.1f}@{i}"
                   for i in onp.argsort(gaps)[:-4:-1]))

    n, batch = len(done), config["batch"]
    rtol = config["reference"]["rtol"]
    bad = sum(1 for l in losses if not math.isfinite(l))
    retraces = (delta(ca, cw, "fused.retraces")
                + delta(ca, cw, "fused.fallbacks") + in_window)
    homes = {frozenset(p.data()._data.devices())
             for p in net.collect_params().values()}
    checks = [
        (f"first fused loss {warm[0]:.5f} within {rtol} relative of the "
         f"float32 reference {ref:.5f}",
         math.isfinite(warm[0]) and abs(warm[0] - ref) <= rtol * abs(ref)),
        (f"every loss finite ({bad} of {n} not)", bad == 0 and
         all(math.isfinite(l) for l in warm)),
        ("mean of the last five losses below the mean of the first five",
         n >= 10 and onp.mean(losses[-5:]) < onp.mean(losses[:5])),
        (f"one fused dispatch per step ({delta(ca, cw, 'fused.dispatches')} "
         f"for {n})", delta(ca, cw, "fused.dispatches") == n),
        (f"0 retraces, fallbacks and compilations in the window "
         f"(saw {retraces})", retraces == 0),
        ("fused path taken (fallback_reason empty)",
         not getattr(step, "fallback_reason", None)),
        (f"every parameter on the one device (saw {len(homes)} placements)",
         homes == {frozenset([device])}),
    ]
    evidence.update({
        "checks": checks, "attempted": len(dispatch), "failed": bad,
        "end_to_end": {
            "train_samples_s": n * batch / window,
            "step_p95_ms": float(onp.percentile(gaps, 95)) * 1e3,
            "setup_s": begin - cell["t0"],
        },
        "memory_peak_bytes": memory_peak,
        "batch": batch, "steps": n, "dispatch_s": dispatch,
        "retraces": retraces,
        "pallas_routes": sum(delta(c1, c0, k) for k in c1
                             if k.startswith("dispatch.pallas.hits.")),
        "flops_per_sample": required_flops(cell),
        "peaks": cell["peaks"],
    })
    return evidence
