"""mxnet_tpu.serve — production inference tier with continuous batching.

The "millions of users" leg of the north star (ROADMAP item 1): the
chip capacity for inference exists (scan-amortized device scoring runs
5.4× the V100 anchor) — what was missing is the serving glue that keeps
the device fed from many small concurrent requests without paying a
host round-trip per call.

Layered like the training runtime it sits on:

- :class:`InferenceEngine` (engine.py) — one donated XLA program per
  (model, bucket) via ``HybridBlock.pure_fn(train=False)``; warm-up
  precompiles the power-of-two bucket ladder, after which ANY retrace
  is a counted bug (``serve.retraces``, gated at 0 by serve-check).
- :class:`Batcher` (batcher.py) — continuous batching: request fan-in
  before one device execution, response replay after (the WorkersMerge
  shape at the serving layer).  Bounded-queue admission control raises
  :class:`QueueFull` instead of collapsing.
- :class:`ModelRegistry` (registry.py) — multi-model multi-tenancy:
  per-model engine + batcher + queue, LRU eviction, loading from
  CheckpointManager roots (``restore(subtree="params")`` — no Trainer
  on the serving host) or ``.params`` files.
- :class:`InferenceServer` (server.py) — stdlib threaded HTTP front
  end: ``/v1/predict``, ``/v1/models``, readiness-aware ``/healthz``,
  ``/metrics`` (Prometheus), 429 shedding with a derived
  ``Retry-After``, drain/undrain lifecycle, ``MXNET_SERVE_FAULT``
  injection (faults.py).
- :class:`Router` (router.py) — the resilience plane over N replicas:
  active health probing with ejection/reinstatement, per-replica
  circuit breakers, weighted least-loaded routing from scraped
  metrics, bounded retries with backoff + jitter, optional hedging.
  ``make chaos-check`` (chaos.py) proves kill-and-relaunch with zero
  client-visible failures.
- ``bench.serve_bench`` — synthetic open-loop load reporting sustained
  QPS + p50/p99 tail latency via ``telemetry.quantile``;
  ``bench.tp_serving_bench`` A/Bs the same load at tp=1 vs tp=2.
- Tensor-parallel sharding (docs/serving.md §sharded serving): a
  ``mesh=``/``MXNET_SERVE_MESH`` serving mesh makes every engine hold
  its parameters 1/tp-sharded (gather-at-use inside the same donated
  programs — bit-for-bit with unsharded, gated by ``make
  tp-serve-check``/tpcheck.py), with ``MXNET_SERVE_HBM_BUDGET``
  refusing builds that would not fit a chip unsharded.

Quick start::

    import mxnet_tpu as mx
    reg = mx.serve.ModelRegistry()
    reg.load("resnet", "/ckpts/run1", arch="resnet18_v1",
             item_shape=(3, 224, 224))
    srv = mx.serve.InferenceServer(reg, port=8080).start()

``make serve-check`` runs :func:`_selfcheck`; ``python -m
mxnet_tpu.serve`` starts a server from the command line.
"""
from __future__ import annotations

import sys

from .batcher import Batcher, DecodeBatcher, QueueFull, RequestError
from .engine import (DEFAULT_BUCKETS, HBMBudgetExceeded, InferenceEngine,
                     bucket_ladder, resolve_serve_mesh)
from .registry import ModelEntry, ModelRegistry
from .router import Router
from .server import InferenceServer

__all__ = ["InferenceEngine", "Batcher", "DecodeBatcher", "ModelRegistry",
           "ModelEntry", "InferenceServer", "Router", "QueueFull",
           "RequestError", "DEFAULT_BUCKETS", "bucket_ladder",
           "HBMBudgetExceeded", "resolve_serve_mesh"]


# --------------------------------------------------------------------- check
def _selfcheck(verbose: bool = True) -> int:
    """``make serve-check``: the acceptance contract, end to end.

    A small Dense net is registered and warmed over the (1, 2, 4, 8)
    ladder; a barrier-released burst of 16 concurrent single-item
    requests must be served through coalesced bucketed batches with

    - every prediction equal to the unbatched forward within float
      tolerance (a bucket-8 and a bucket-1 program are two XLA programs:
      bits are equal only within one bucket, tests/test_serve.py),
    - at least one batch with fill > 1 (coalescing actually happened),
    - exactly 0 retraces after warm-up,
    - a reportable p99 from telemetry.quantile,
    - clean shutdown with no leaked ``serve-`` threads.

    A second, generative leg drives the streaming decode path: a tiny
    GPT behind a :class:`DecodeBatcher` streams two concurrent
    generations token by token, bit-for-bit equal to the unbatched
    greedy decode, with joins/leaves observed at iteration boundaries
    and 0 decode retraces (the full gate is ``make decode-check``).
    """
    import threading
    import time

    import numpy as onp

    import mxnet_tpu as mx
    from .. import telemetry as _telemetry
    from ..gluon import nn

    _telemetry.reset()
    mx.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize()
    net.hybridize()

    item = (16,)
    reg = ModelRegistry(max_models=2)
    entry = reg.register("check", net, item, buckets=(1, 2, 4, 8),
                         warmup=True)
    # a generous deadline so the burst coalesces instead of trickling
    entry.batcher.max_wait_s = 0.03

    n_req = 16
    rs = onp.random.RandomState(7)
    xs = [rs.randn(*item).astype("float32") for _ in range(n_req)]
    results = [None] * n_req
    errors = [None] * n_req
    barrier = threading.Barrier(n_req)

    def _client(i):
        try:
            barrier.wait()
            results[i] = reg.predict("check", xs[i])
        except Exception as e:  # noqa: BLE001 — recorded, asserted below
            errors[i] = e

    threads = [threading.Thread(target=_client, args=(i,),
                                name=f"check-client-{i}")
               for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)

    # vs the unbatched eager forward of the same net: another XLA
    # program than the coalesced bucket, so close, not equal bits
    exact = True
    for i in range(n_req):
        if errors[i] is not None or results[i] is None:
            exact = False
            break
        ref = onp.asarray(net(mx.np.array(xs[i][None]))._data)
        got = results[i][0]
        if got.shape != ref.shape or not onp.allclose(
                got, ref, rtol=1e-5, atol=1e-6):
            exact = False
            break

    snap = _telemetry.raw_snapshot()
    counters = snap.get("counters", {})
    coalesced = int(counters.get("serve.coalesced_batches", 0))
    batches = int(counters.get("serve.batches", 0))
    p99 = _telemetry.quantile("serve", "e2e_us", 0.99, snap=snap)
    retraces = entry.engine.retraces

    # ------------------------------------------- streaming decode leg
    # A tiny GPT behind a DecodeBatcher: two concurrent generations
    # stream token by token through one donated ctl block, joining and
    # leaving at iteration boundaries — output bit-for-bit equal to the
    # unbatched greedy decode, 0 decode retraces.
    import jax

    from .. import generate as _generate
    from ..models import gpt as _gpt

    gcfg = _gpt.GPTConfig(vocab_size=61, hidden=32, layers=2, heads=2,
                          intermediate=64, max_len=64)
    gparams = _gpt.init_params(gcfg, jax.random.PRNGKey(0))
    eng = _generate.DecodeEngine(gparams, gcfg, name="sc-gpt", window=16,
                                 buckets=(2,), prompts=(8,)).warmup()
    gprompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    gsingles = [eng.generate([p], max_new=6)[0] for p in gprompts]
    gstream = [None] * len(gprompts)
    gerrors = [None] * len(gprompts)
    bat = DecodeBatcher(eng, slots=2, name="sc-gpt")
    try:
        gbarrier = threading.Barrier(len(gprompts))

        def _gen_client(i):
            try:
                gbarrier.wait()
                gstream[i] = list(bat.submit_stream(gprompts[i],
                                                    max_new=6))
            except Exception as e:  # noqa: BLE001 — asserted below
                gerrors[i] = e

        gthreads = [threading.Thread(target=_gen_client, args=(i,),
                                     name=f"check-gen-client-{i}")
                    for i in range(len(gprompts))]
        for t in gthreads:
            t.start()
        for t in gthreads:
            t.join(60.0)
        dstats = bat.stats()
    finally:
        bat.close()
    stream_exact = (all(e is None for e in gerrors) and
                    gstream == gsingles)
    dec_retraces = eng.retraces

    reg.close()
    time.sleep(0.1)
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("serve-")]

    checks = [
        ("all %d requests served" % n_req,
         all(e is None for e in errors) and
         all(r is not None for r in results)),
        ("predictions equal unbatched forward within tolerance", exact),
        ("≥1 coalesced batch (fill > 1) in %d batches" % batches,
         coalesced >= 1),
        ("0 retraces after warm-up", retraces == 0),
        ("p99 e2e latency reported", p99 is not None),
        ("streamed decode bit-for-bit vs unbatched greedy",
         stream_exact),
        ("decode joins/leaves at iteration boundaries",
         dstats["joins"] >= 2 and dstats["leaves"] >= 2),
        ("0 decode retraces across streaming", dec_retraces == 0),
        ("no leaked serve threads", not leaked),
    ]
    ok = all(c for _, c in checks)
    if verbose:
        for name, c in checks:
            print(f"[serve-check] {'ok  ' if c else 'FAIL'} {name}")
        print(f"[serve-check] batches={batches} coalesced={coalesced} "
              f"retraces={retraces} "
              f"p99={p99 / 1000.0 if p99 else p99}ms leaked={leaked}")
    if not ok:
        errs = [repr(e) for e in errors if e is not None]
        if errs:
            print(f"[serve-check] request errors: {errs[:3]}",
                  file=sys.stderr)
        print("[serve-check] FAIL", file=sys.stderr)
        return 1
    print("[serve-check] OK")
    return 0


def _main(argv):
    if "--check" in argv:
        return _selfcheck(verbose="--quiet" not in argv)
    # `python -m mxnet_tpu.serve --model name=arch:source ...` CLI
    import argparse

    p = argparse.ArgumentParser(prog="mxnet_tpu.serve")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=ARCH:SOURCE",
                   help="register a model from a checkpoint dir or "
                        ".params file (repeatable)")
    p.add_argument("--selftest-model", default=None, metavar="NAME",
                   help="register the small seeded bench mlp under NAME "
                        "(replica-worker mode for the chaos harness — "
                        "no checkpoint on disk needed)")
    p.add_argument("--item-shape", default="3,224,224",
                   help="comma shape of one request item")
    args = p.parse_args(argv)

    item = tuple(int(d) for d in args.item_shape.split(",") if d.strip())
    reg = ModelRegistry()
    if args.selftest_model:
        import mxnet_tpu as mx
        from .bench import _build_model
        mx.seed(0)
        net, st_item = _build_model("mlp")
        net.initialize()
        net.hybridize()
        reg.register(args.selftest_model, net, st_item)
        print(f"[serve] registered selftest model "
              f"{args.selftest_model!r} (mlp, item {st_item})")
    for spec in args.model:
        name, rest = spec.split("=", 1)
        arch, source = rest.split(":", 1)
        reg.load(name, source, arch=arch, item_shape=item)
        print(f"[serve] loaded {name} ({arch}) from {source}")
    srv = InferenceServer(reg, host=args.host, port=args.port)
    print(f"[serve] listening on {srv.host}:{srv.port} "
          f"models={reg.names()}")
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
