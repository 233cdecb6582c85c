#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU v5e chip: ResNet-50 v1 at full width is trained for a
few fused steps (bf16 AMP, batch 128, NHWC 224x224) and then served through
registry -> engine -> batcher -> HTTP, every result checked by the repo's
own means.  ``--chips 4`` runs instead the GSPMD dp=2 x tp=2 fused step on
BERT-base width against the single-device fused step, and nothing else.

The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else is printed on earlier ``[smoke]`` lines.  Off the chip
(JAX finds no TPU) the script exits non-zero before any phase runs and
prints no result; no rate printed here is a benchmark number.

Each phase is a function of its sizes: ``tests/test_chip_smoke.py`` runs
them at a toy size on the CPU so the script cannot rot.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as onp


def say(msg):
    print(f"[smoke] {msg}", flush=True)


def check(cond, what):
    """A failed check ends the run: no phase continues past one."""
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"ok: {what}")


def _counters(prefix=""):
    from mxnet_tpu import telemetry
    c = telemetry.raw_snapshot()["counters"]
    return {k: v for k, v in c.items() if k.startswith(prefix)}


def _delta(after, before, name):
    return after.get(name, 0) - before.get(name, 0)


def _on_device(arr, device):
    return set(arr.devices()) == {device}


# ------------------------------------------------------------------ device
def device_problem(devs, chips):
    """Why this process may not run the smoke, or None.  Asked by
    ``main`` before any phase, and before ``mxnet_tpu`` is imported."""
    import os
    d0 = devs[0]
    if d0.platform != "tpu":
        return (f"no TPU (jax found {d0.platform!r} x{len(devs)}); "
                "this script does not run on a CPU")
    if len(devs) != chips:
        return f"want {chips} chip(s), jax found {len(devs)}"
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET"):
        return ("MXNET_TPU_PALLAS_INTERPRET is set; the smoke runs "
                "compiled kernels only")
    return None


def device_phase(devs):
    """Versions, import cost, native library, compile-cache directory."""
    import jax
    d0 = devs[0]
    t0 = time.perf_counter()
    import mxnet_tpu as mx
    from mxnet_tpu.ops import pallas_block
    import_s = time.perf_counter() - t0
    check(pallas_block.interpret() is False, "Pallas kernels compile "
          "(interpret mode off)")
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    say(f"device: {d0.platform} {d0.device_kind} x{len(devs)} "
        f"ids={[d.id for d in devs]}")
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    native = mx.base.lib_path()
    say(f"import mxnet_tpu: {import_s:.2f}s, native library "
        + (f"loaded from {native}" if native else "absent (python fallbacks)"))
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    return d0


# ----------------------------------------------------------------- kernels
def kernel_phase(batch=8, dtype="bfloat16", seed=3):
    """Every stage the default table routes to the fused Pallas block:
    forward (batch stats and frozen) and all gradients against a plain
    float32 ``lax`` composition of conv + BN + add + ReLU on one seeded
    input."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import pallas_block as pb

    def ref(x, w, gamma, beta, mean, var, res, frozen):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        z = lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), (1, 1),
            [(1, 1), (1, 1)], dimension_numbers=dn,
            precision=lax.Precision.HIGHEST)
        if not frozen:
            mean = jnp.mean(z, axis=(0, 1, 2))
            var = jnp.var(z, axis=(0, 1, 2))
        y = (z - mean) * lax.rsqrt(var + 1e-5) * gamma + beta
        return jnp.maximum(y + res.astype(jnp.float32), 0.0)

    def close(name, got, want, tol=5e-2):
        # relative L2: bf16 rounding noise stays near 1e-2 while a wrong
        # tap, row block or accumulation is an error of order one
        got = onp.asarray(got, "float64")
        want = onp.asarray(want, "float64")
        rel = float(onp.linalg.norm(got - want) / onp.linalg.norm(want))
        check(onp.isfinite(got).all() and rel <= tol,
              f"{name}: relative L2 error {rel:.3g} <= {tol}")

    routed = {k: v for k, v in sorted(pb._DEFAULT_TABLE.items())
              if v.get("fwd") == "pallas"}
    say(f"kernels: default table routes {routed or 'nothing'} to Pallas")
    rs = onp.random.RandomState(seed)
    for stage, ent in routed.items():
        H, W, C = (int(t) for t in stage.split("x"))
        x = jnp.asarray(rs.randn(batch, H, W, C), dtype)
        w = jnp.asarray(rs.randn(3, 3, C, C) * (9 * C) ** -0.5, dtype)
        res = jnp.asarray(rs.randn(batch, H, W, C), dtype)
        gamma = jnp.asarray(rs.rand(C) + 0.5, jnp.float32)
        beta = jnp.asarray(rs.randn(C) * 0.1, jnp.float32)
        mean = jnp.asarray(rs.randn(C) * 0.1, jnp.float32)
        var = jnp.asarray(rs.rand(C) + 0.5, jnp.float32)
        check(pb.eligible_block(x.shape, w.shape, x.dtype, True),
              f"{stage} eligible at batch {batch} {dtype}")

        def fused(x, w, gamma, beta, res, frozen):
            return pb.residual_block_fused(
                x, w, gamma, beta, mean, var, res, frozen=frozen,
                bwd=ent.get("bwd", "xla"))[0].astype(jnp.float32)

        def plain(x, w, gamma, beta, res, frozen):
            return ref(x, w, gamma, beta, mean, var, res, frozen)

        for frozen in (False, True):
            mode = "frozen" if frozen else "train"
            args = (x, w, gamma, beta, res)
            close(f"{stage} {mode} forward",
                  jax.jit(fused, static_argnums=5)(*args, frozen),
                  jax.jit(plain, static_argnums=5)(*args, frozen))

            def grads(f):
                return jax.jit(jax.grad(
                    lambda *a: jnp.sum(jnp.square(f(*a, frozen))),
                    argnums=(0, 1, 2, 3, 4)))(*args)

            for nm, g, r in zip(("dx", "dw", "dgamma", "dbeta", "dres"),
                                grads(fused), grads(plain)):
                close(f"{stage} {mode} {nm} (bwd={ent.get('bwd')})", g, r)


# ------------------------------------------------------------------- train
def train_phase(device, model="resnet50_v1", classes=1000, batch=128,
                image=224, steps=5, dtype="bfloat16", lr=0.005, seed=0):
    """Fused train step: 1 compile step + ``steps`` steps on one fixed
    seeded batch, each ending in a host fetch of the loss."""
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import resnet

    say(f"train: {model} classes={classes} batch={batch} {image}x{image} "
        f"NHWC {dtype} sgd+momentum")
    # Trainer.fuse_step has no compute-dtype argument, so bf16 AMP (f32
    # master weights, bf16 fwd/bwd) is asked for the way bench.py does:
    say("train: entry point parallel.FusedTrainStep(dtype=...) "
        "(gluon.Trainer.fuse_step cannot ask for bf16)")
    mx.seed(seed)
    rng = onp.random.RandomState(seed)
    net = getattr(resnet, model)(classes=classes)
    net.initialize()
    net.hybridize()
    opt = opt_mod.create("sgd", learning_rate=lr, momentum=0.9, wd=1e-4)
    step = par.FusedTrainStep(net, gloss.SoftmaxCrossEntropyLoss(), opt,
                              dtype=dtype)
    x = mx.np.array(rng.rand(batch, image, image, 3).astype("float32"))
    y = mx.np.array(rng.randint(0, classes, (batch,)))

    c0 = _counters()
    t0 = time.perf_counter()
    first = float(step(x, y).asnumpy())
    compile_s = time.perf_counter() - t0
    c1 = _counters()
    say(f"train: compile step {compile_s:.1f}s loss={first:.4f}")
    losses, ms = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(x, y).asnumpy()))
        ms.append((time.perf_counter() - t0) * 1e3)
    c2 = _counters()
    say("train: losses " + " ".join(f"{l:.4f}" for l in losses))
    say("train: per-step ms (information, not a metric) "
        + " ".join(f"{m:.1f}" for m in ms))
    routes = {k: v for k, v in c2.items() if k.startswith("dispatch.pallas.")}
    say(f"train: route counters {json.dumps(routes, sort_keys=True)}")

    check(_delta(c2, c0, "fused.dispatches") == steps + 1
          and _delta(c2, c0, "fused.steps") == steps + 1,
          f"one fused dispatch per step ({steps + 1} of {steps + 1})")
    check(_delta(c2, c0, "fused.fallbacks") == 0, "no legacy-path fallback")
    check(step._trace_count == 1 and _delta(c2, c1, "fused.retraces") == 0,
          "step program traced once, 0 retraces after the first step")
    check(all(onp.isfinite(l) for l in [first] + losses), "losses finite")
    check(losses[-1] < losses[0],
          f"loss falls: step {steps} {losses[-1]:.4f} < step 1 "
          f"{losses[0]:.4f}")
    params = net.collect_params()
    check(all(_on_device(p.data()._data, device) for p in params.values()),
          f"all {len(params)} parameters live on {device}")
    stats = device.memory_stats() or {}
    say(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    return {"compile_s": compile_s, "losses": losses, "routes": routes}


# ------------------------------------------------------------------- serve
def _post(port, body, timeout=300.0):
    import urllib.request
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with opener.open(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def serve_phase(device, model="resnet50_v1", classes=1000, image=224,
                buckets=(1, 2, 4, 8), sizes=(1, 2, 3, 1, 4, 2, 1, 5),
                precision="bf16", seed=1):
    """registry -> engine -> batcher -> HTTP: ``len(sizes)`` /v1/predict
    requests, each compared with a direct ``engine.run`` of its rows."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.serve import InferenceServer, ModelRegistry

    say(f"serve: {model} classes={classes} {image}x{image} NHWC "
        f"{precision} buckets={tuple(buckets)} request sizes={tuple(sizes)}")
    tol = {"bf16": 2e-2, "fp32": 1e-4}[precision]
    mx.seed(seed)
    rng = onp.random.RandomState(seed)
    net = getattr(resnet, model)(classes=classes)
    net.initialize()
    net.hybridize()
    registry = ModelRegistry(buckets=buckets, max_wait_ms=20.0)
    t0 = time.perf_counter()
    entry = registry.register(model, net, (image, image, 3),
                              precision=precision)
    engine = entry.engine
    say(f"serve: registered + warmed {len(engine.buckets)} bucket programs "
        f"in {time.perf_counter() - t0:.1f}s")
    check(engine.warm and engine.retraces == 0, "engine warm, 0 retraces")
    check(all(_on_device(v, device) for v in engine._pvals.values()),
          f"all {len(engine._pvals)} served parameters live on {device}")

    # three decimals keep the JSON bodies small; the values are what the
    # reference below sees too
    inputs = [onp.round(rng.rand(n, image, image, 3), 3).astype("float32")
              for n in sizes]
    replies = [None] * len(sizes)
    server = InferenceServer(registry, host="127.0.0.1", port=0).start()
    try:
        def client(i):
            replies[i] = _post(server.port, {"model": model,
                                             "inputs": inputs[i].tolist()})
        # the first half one at a time (each request alone in its bucket),
        # the second half at once (the batcher may coalesce them)
        half = len(sizes) // 2
        for i in range(half):
            client(i)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(half, len(sizes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.stop(close_registry=True)

    check(all(r is not None and r[0] == 200 for r in replies),
          f"{len(sizes)} of {len(sizes)} requests answered HTTP 200")
    shaped, close, worst, scale = True, True, 0.0, 0.0
    for x, (_, body) in zip(inputs, replies):
        n = x.shape[0]
        b = engine.bucket_for(n)
        padded = onp.concatenate(
            [x, onp.zeros((b - n,) + x.shape[1:], x.dtype)])
        want = onp.asarray(engine.run(padded)[0]).astype("float32")[:n]
        got = onp.asarray(body["outputs"][0], "float32")
        shaped &= got.shape == (n, classes) and bool(onp.isfinite(got).all())
        close &= shaped and onp.allclose(got, want, rtol=tol, atol=tol)
        if shaped:
            worst = max(worst, float(onp.max(onp.abs(got - want))))
            scale = max(scale, float(onp.max(onp.abs(want))))
    check(shaped, f"every answer is finite, (rows, {classes})")
    check(close, f"every answer equals engine.run of its rows within "
          f"rtol=atol={tol} (worst abs diff {worst:.3g}, logit scale "
          f"{scale:.3g})")
    used = sorted({engine.bucket_for(n) for n in sizes})
    check(len(used) >= 2, f"requests landed in buckets {used}")
    check(engine.retraces == 0 and engine.rebuilds == 0,
          "0 retraces and 0 rebuilds after warm-up")
    left = [t.name for t in threading.enumerate()
            if t.name.startswith("serve-")]
    check(not left and server._thread is None,
          "server and batcher threads stopped")
    c = _counters("serve.")
    say(f"serve: batches={c.get('serve.batches', 0)} "
        f"coalesced={c.get('serve.coalesced_batches', 0)} "
        f"padded_rows={c.get('serve.padded', 0)}")
    return {"worst_abs_diff": worst}


# -------------------------------------------------------------- four chips
def mesh_phase(devices, units=768, heads=12, layers=4, ffn_units=3072,
               vocab=30522, seq=512, batch=16, steps=3, seed=2):
    """GSPMD fused step over dp=2 x tp=2, entered as users enter it
    (``gluon.Trainer(mesh=, sharding_plan=).fuse_step``), against the
    single-device fused step on the same seeded batch and weights."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models.bert_gluon import BERTModel
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.sharding import infer_plan, shard_bytes

    say(f"mesh: BERT units={units} heads={heads} ffn={ffn_units} "
        f"layers={layers} (depth cut) seq={seq} global batch={batch} fp32, "
        f"dp=2 x tp=2 vs one device")
    check(len(devices) == 4 and len({d.id for d in devices}) == 4,
          f"four distinct devices {[d.id for d in devices]}")
    rng = onp.random.RandomState(seed)
    tokens = mx.np.array(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    labels = mx.np.array(rng.randint(0, vocab, (batch, seq)).astype("int32"))

    def build():
        mx.seed(seed)
        net = BERTModel(units=units, heads=heads, layers=layers,
                        ffn_units=ffn_units, vocab_size=vocab,
                        max_length=seq)
        net.initialize()
        net.hybridize()
        net(tokens[:1])                 # resolve deferred shapes
        return net

    def run(net, **trainer_kw):
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9}, **trainer_kw)
        step = tr.fuse_step(SoftmaxCrossEntropyLoss())
        c0 = _counters("fused.")
        t0 = time.perf_counter()
        losses = [float(step(tokens, labels).asnumpy())]
        compile_s = time.perf_counter() - t0
        losses += [float(step(tokens, labels).asnumpy())
                   for _ in range(steps - 1)]
        c1 = _counters("fused.")
        check(step.fused and not step.fallback_reason,
              "fuse_step took the fused path (fallback_reason empty)")
        check(_delta(c1, c0, "fused.dispatches") == steps
              and _delta(c1, c0, "fused.retraces") == 0,
              f"{steps} dispatches for {steps} steps, 0 retraces")
        say(f"mesh: compile step {compile_s:.1f}s losses "
            + " ".join(f"{l:.5f}" for l in losses))
        return losses

    ref_net = build()
    init = {n: jnp.array(p.data()._data, copy=True)
            for n, p in ref_net.collect_params().items()}
    say("mesh: single-device fused step")
    ref = run(ref_net)

    net = build()
    for n, p in net.collect_params().items():
        p.set_data(NDArray(init[n]))
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    plan = infer_plan(net, mesh=mesh)
    say(f"mesh: {plan!r}")
    got = run(net, mesh=mesh, sharding_plan=plan)

    check(all(onp.isfinite(l) for l in ref + got), "losses finite")
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    check(rel <= 2e-2, f"sharded losses agree with single-device within "
          f"2e-2 relative (worst {rel:.3g})")
    params = {n: p.data()._data for n, p in net.collect_params().items()}
    homes = {n: sorted(s.device.id for s in a.addressable_shards)
             for n, a in params.items()}
    check(all(len(set(ids)) == 4 for ids in homes.values()),
          f"every parameter has shards on four distinct devices "
          f"{sorted(set(map(tuple, homes.values())))}")
    name = next(n for n in plan.sharded_names()
                if plan.entries[n]["rule"] == "dense_column")
    arr = params[name]
    check(shard_bytes(arr) * 2 == arr.nbytes,
          f"dense_column leaf {name} {tuple(arr.shape)}: "
          f"{shard_bytes(arr)} bytes per device = 1/tp of {arr.nbytes}")
    return {"ref": ref, "got": got}


# -------------------------------------------------------------------- main
def result_line(devs):
    """The last line of stdout: the device as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp=2 x tp=2 phase and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    problem = device_problem(devs, args.chips)
    if problem:
        print(f"chip_smoke: {problem}", file=sys.stderr)
        return 2
    d0 = device_phase(devs)
    if args.chips == 4:
        mesh_phase(devs, seed=args.seed + 2)
    else:
        kernel_phase(seed=args.seed + 3)
        train_phase(d0, seed=args.seed)
        serve_phase(d0, seed=args.seed + 1)
    print(result_line(devs), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
