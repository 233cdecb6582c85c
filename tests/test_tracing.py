"""Distributed tracing (mxnet_tpu/telemetry.py span layer + the
propagation sites of docs/tracing.md).

The contracts under test:

- span mechanics: ids, in-process parent inheritance, child ⊆ parent
  intervals from ONE wall clock, explicit cross-thread handoff,
  exception annotation, and the `X-MXNet-Trace` header round trip
  (malformed headers start a fresh trace, never fail)
- flight recorder: bounded lock-sharded ring — overflow overwrites
  oldest and COUNTS drops; MXNET_TRACE=0 records nothing
- router: a retried request keeps ONE trace id across attempts and
  the replica-side serve.request joins it via the header; a hedged
  request's losing attempt span is marked cancelled=True
- batcher: the coalesced serve.execute span links EXACTLY the member
  request spans it served (len(links) == its requests attr)
- feed: local-fallback batches are still traced (feed.fetch
  source="local" with a feed.local_decode child)
- trainer: the per-step trace rotation numbers steps by num_update,
  so the step attr CONTINUES across a checkpoint save/restore
- tools/trace.py merge: shards from distinct pids stitch into valid
  Chrome trace JSON with deduplicated metadata rows and flow events
"""
import gc
import importlib.util
import json
import os
import socket
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np as mnp
from mxnet_tpu import telemetry
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.serve import (Batcher, InferenceEngine, InferenceServer,
                             ModelRegistry, Router)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITEM = (12,)


def _small_net(seed=0, out=5):
    mx.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(24, activation="relu"), nn.Dense(out))
    net.initialize()
    net.hybridize()
    return net


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# the raw record tuple layout (docs/tracing.md §flight recorder)
_FIELDS = ("trace_id", "span_id", "parent_id", "name", "ts", "dur",
           "tid", "attrs", "links")


def _spans(name=None):
    out = [dict(zip(_FIELDS, r)) for r in telemetry.trace_spans()]
    return [s for s in out
            if name is None or s["name"] == name]


def _predict_body(x):
    return json.dumps({"model": "web",
                       "inputs": onp.asarray(x).tolist()}).encode()


@pytest.fixture(autouse=True)
def _fresh_ring():
    telemetry.set_trace_enabled(True)
    telemetry.trace_reset()
    yield
    telemetry.set_trace_enabled(True)


# ------------------------------------------------------------ mechanics
def test_span_nesting_parent_ids_and_single_clock():
    with telemetry.span("root", kind="outer") as root:
        rtid, rsid = root.context()
        with telemetry.span("child") as child:
            ctid, csid = child.context()
    assert rtid == ctid and rsid != csid
    by = {s["name"]: s for s in _spans()}
    assert by["child"]["parent_id"] == by["root"]["span_id"]
    assert by["root"]["parent_id"] is None
    # one wall clock: the child interval sits inside the parent's
    c0, c1 = by["child"]["ts"], by["child"]["ts"] + by["child"]["dur"]
    r0, r1 = by["root"]["ts"], by["root"]["ts"] + by["root"]["dur"]
    assert r0 <= c0 and c1 <= r1
    assert by["root"]["attrs"]["kind"] == "outer"


def test_header_round_trip_and_malformed_header():
    with telemetry.span("client") as sp:
        hdr = sp.header()
        tid, sid = sp.context()
    assert telemetry.parse_trace_header(hdr) == (tid, sid)
    # a peer resumes the trace from the wire format
    with telemetry.span("server", parent=hdr) as srv:
        assert srv.context()[0] == tid
    assert _spans("server")[0]["parent_id"] == sid
    # malformed/zero headers start a FRESH trace, never raise
    for bad in ("", "nope", "zz-zz", "0-0", "abc", None):
        assert telemetry.parse_trace_header(bad) is None
        with telemetry.span("fresh", parent=bad) as f:
            assert f.context()[0] not in (None, tid)


def test_cross_thread_handoff_and_exception_annotation():
    with telemetry.span("submit") as sp:
        ctx = telemetry.current_context()

        def worker():
            with telemetry.span("execute", parent=ctx):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert _spans("execute")[0]["parent_id"] == ctx[1]
    with pytest.raises(RuntimeError):
        with telemetry.span("boom"):
            raise RuntimeError("x")
    assert _spans("boom")[0]["attrs"]["error"] == "RuntimeError"


def test_disabled_is_a_no_op_and_ring_bounds_with_drop_count():
    prev = telemetry.set_trace_enabled(False)
    try:
        with telemetry.span("invisible") as sp:
            assert sp.context() is None and sp.header() is None
    finally:
        telemetry.set_trace_enabled(prev)
    assert telemetry.trace_stats() == {"spans": 0, "dropped": 0}
    # single-threaded flood: one thread maps to ONE of the 8 shards,
    # so retention is ring/8 — but nothing is lost silently
    n = (telemetry._trace_ring_cap() // 8) * 2
    gc.disable()        # a collection of 1 ms or more is a span too (PR 38)
    try:
        for i in range(n):
            with telemetry.span("flood", i=i):
                pass
        st = telemetry.trace_stats()
    finally:
        gc.enable()
    assert st["spans"] + st["dropped"] == n
    assert st["spans"] <= telemetry._trace_ring_cap() // 8
    assert st["dropped"] > 0
    # the survivors are the NEWEST records
    kept = sorted(s["attrs"]["i"] for s in _spans("flood"))
    assert kept[-1] == n - 1 and kept == list(range(kept[0], n))


def test_set_current_trace_pins_a_step_scoped_trace():
    t1 = telemetry.set_current_trace()
    with telemetry.span("train.step") as sp:
        assert sp.context()[0] == t1
    with telemetry.span("datafeed.wait") as sp:   # sibling, same trace
        assert sp.context()[0] == t1
    t2 = telemetry.set_current_trace()
    assert t2 != t1
    steps = {s["name"]: s for s in _spans()}
    assert steps["train.step"]["trace_id"] == \
        steps["datafeed.wait"]["trace_id"] == t1
    assert steps["train.step"]["parent_id"] is None


# ------------------------------------------------------------ router
def test_router_retry_keeps_one_trace_and_header_reaches_replica():
    telemetry.reset()
    reg = ModelRegistry(max_models=2)
    net = _small_net(seed=41)
    reg.register("web", net, ITEM, buckets=(1, 2))
    srv = InferenceServer(reg, host="127.0.0.1", port=0).start()
    # replica 0 refuses connections → attempt 1 fails, retry reroutes
    router = Router([f"127.0.0.1:{_free_port()}",
                     f"127.0.0.1:{srv.port}"],
                    port=0, retries=3, backoff_ms=1, breaker_fails=10)
    try:
        for rep in router.replicas:
            rep.status = "ready"
        x = onp.random.RandomState(42).randn(*ITEM).astype("float32")
        status, _, _ = router.forward(_predict_body(x))
        assert status == 200
        fwd = _spans("router.forward")[0]
        assert fwd["attrs"]["attempts"] >= 2
        assert fwd["attrs"]["outcome"] == "ok"
        tid = fwd["trace_id"]
        tries = _spans("router.try")
        attempts = _spans("router.attempt")
        assert len(tries) >= 2 and len(attempts) >= 2
        # retry + reroute all ride ONE trace id
        assert {s["trace_id"] for s in tries + attempts} == {tid}
        outcomes = [a["attrs"].get("outcome") for a in attempts]
        assert "ok" in outcomes and len(set(outcomes)) >= 2
        # the winning attempt's header reached the replica: its
        # serve.request span joined the same trace, parented on it
        served = [s for s in _spans("serve.request")
                  if s["trace_id"] == tid]
        assert len(served) == 1
        winner = [a for a in attempts
                  if a["attrs"].get("outcome") == "ok"][0]
        assert served[0]["parent_id"] == winner["span_id"]
    finally:
        router.stop()
        srv.stop(close_registry=True)


def test_hedge_loser_span_is_marked_cancelled():
    telemetry.reset()
    # replica 0 accepts but never answers: the hedge must win and the
    # primary attempt must be cancelled
    hang = socket.socket()
    hang.bind(("127.0.0.1", 0))
    hang.listen(1)
    reg = ModelRegistry(max_models=2)
    reg.register("web", _small_net(seed=43), ITEM, buckets=(1, 2))
    srv = InferenceServer(reg, host="127.0.0.1", port=0).start()
    router = Router(
        [f"127.0.0.1:{hang.getsockname()[1]}", f"127.0.0.1:{srv.port}"],
        port=0, hedge=True, hedge_floor_ms=50, timeout_ms=8000,
        retries=2, backoff_ms=1)
    try:
        for rep in router.replicas:
            rep.status = "ready"
        x = onp.random.RandomState(44).randn(*ITEM).astype("float32")
        status, _, _ = router.forward(_predict_body(x))
        assert status == 200
        # the loser span closes when the router reaps its connection —
        # poll briefly rather than racing it
        deadline = time.monotonic() + 10.0
        loser = winner = None
        while time.monotonic() < deadline and loser is None:
            atts = _spans("router.attempt")
            loser = next((a for a in atts
                          if a["attrs"].get("cancelled")), None)
            winner = next((a for a in atts
                           if a["attrs"].get("outcome") == "ok"), None)
            if loser is None:
                time.sleep(0.05)
        assert loser is not None and winner is not None
        assert loser["trace_id"] == winner["trace_id"]
        assert loser["attrs"]["hedge"] != winner["attrs"]["hedge"]
    finally:
        router.stop()
        srv.stop(close_registry=True)
        hang.close()


# ------------------------------------------------------------ batcher
def test_execute_span_links_every_member_request_span():
    net = _small_net(seed=45)
    eng = InferenceEngine(net, ITEM, buckets=(1, 2, 4, 8)).warmup()
    telemetry.trace_reset()
    with Batcher(eng, max_wait_ms=30, name="tr-burst") as b:
        n = 8
        rs = onp.random.RandomState(46)
        xs = [rs.randn(*ITEM).astype("float32") for _ in range(n)]
        roots = [None] * n
        barrier = threading.Barrier(n)

        def client(i):
            with telemetry.span("client.request", i=i) as sp:
                roots[i] = sp.context()
                barrier.wait()
                b.submit(xs[i], timeout=20.0)

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30.0)
    execs = _spans("serve.execute")
    assert execs, "no serve.execute spans recorded"
    linked = set()
    for e in execs:
        links = e["links"] or []
        # the coalesce contract: one link per member request span
        assert len(links) == e["attrs"]["requests"]
        # single-item requests: items served == requests coalesced
        assert e["attrs"]["fill"] == e["attrs"]["requests"]
        linked.update(links)
    # every client span is linked from exactly the batch that ran it
    assert linked == set(roots)
    assert sum(e["attrs"]["requests"] for e in execs) == n


# ------------------------------------------------------------ feed
def test_local_fallback_batches_are_traced():
    from mxnet_tpu.io.data_service import FeedClient
    spec = "synthetic:4x3x8x8:10:16"
    dead = [f"127.0.0.1:{_free_port()}"]
    telemetry.trace_reset()
    with FeedClient(workers=dead, spec=spec, seed=3, prefetch=0,
                    retries=1, backoff_ms=1, timeout_ms=200,
                    deadline_ms=1500, start_probing=False,
                    name="tr-fallback") as client:
        d, lab, _pad = client.next_raw()
        assert d.shape == (4, 3, 8, 8) and lab.shape == (4, 1)
    fetch = _spans("feed.fetch")
    assert fetch and fetch[0]["attrs"]["source"] == "local"
    dec = _spans("feed.local_decode")
    assert dec, "local decode leg lost its span"
    assert dec[0]["trace_id"] == fetch[0]["trace_id"]
    assert dec[0]["parent_id"] == fetch[0]["span_id"]


# ------------------------------------------------------------ trainer
def test_step_trace_numbering_survives_checkpoint_restore(tmp_path):
    def build():
        mx.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(5))
        net.initialize()
        net.hybridize()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9})
        return net, tr, tr.fuse_step(SoftmaxCrossEntropyLoss())

    def batch(i):
        rs = onp.random.RandomState(100 + i)
        return (mnp.array(rs.randn(4, 12).astype("float32")),
                mnp.array(rs.randint(0, 5, (4,)).astype("int32")))

    net, tr, step = build()
    for i in range(3):
        step(*batch(i))
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save_trainer(tr, blocking=True)
    mgr.close()

    telemetry.trace_reset()
    net2, tr2, step2 = build()
    mgr2 = CheckpointManager(tmp_path)
    mgr2.restore_trainer(tr2)
    mgr2.close()
    step2(*batch(3))
    steps = _spans("train.step")
    assert steps, "fused step lost its train.step span"
    # numbered from restored num_update: the 4th step overall, even
    # though it is the FIRST step of this trainer object
    assert steps[-1]["attrs"]["step"] == 4


# ------------------------------------------------------------ merge tool
def _load_trace_tool():
    path = os.path.join(REPO, "tools", "trace.py")
    spec = importlib.util.spec_from_file_location("_mxtpu_trace_tool",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_merge_stitches_shards_into_valid_chrome_trace(tmp_path):
    tool = _load_trace_tool()
    with telemetry.span("local.parent") as sp:
        link_src = sp.context()
        hdr = sp.header()
    shard_a = str(tmp_path / "a" / f"trace_{os.getpid()}.json")
    os.makedirs(tmp_path / "a")
    telemetry.dump_trace(shard_a)
    # a second process's shard, hand-rolled: a remote child adopting
    # the local span via the header + an execute span linking it
    tid, sid = telemetry.parse_trace_header(hdr)
    remote_pid = os.getpid() + 1
    remote = {"traceEvents": [
        {"ph": "M", "pid": remote_pid, "tid": 1, "name": "process_name",
         "args": {"name": "fake-remote"}},
        {"ph": "X", "pid": remote_pid, "tid": 1, "name": "remote.child",
         "ts": 1, "dur": 5,
         "args": {"trace_id": f"{tid:016x}", "span_id": "00000000000000ab",
                  "parent_id": f"{sid:016x}"}},
        {"ph": "X", "pid": remote_pid, "tid": 1, "name": "remote.execute",
         "ts": 2, "dur": 2,
         "args": {"trace_id": f"{tid:016x}", "span_id": "00000000000000ac",
                  "links": [f"{tid:016x}-{link_src[1]:016x}"]}},
    ]}
    shard_b = tmp_path / "b" / "trace_fake.json"
    os.makedirs(tmp_path / "b")
    shard_b.write_text(json.dumps(remote))
    (tmp_path / "b" / "notes.json").write_text("not a shard")

    out = str(tmp_path / "merged.json")
    tool.merge([str(tmp_path)], out)
    with open(out) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert {"local.parent", "remote.child", "remote.execute"} <= names
    pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert len(pids) == 2
    # metadata rows for BOTH processes, deduplicated
    meta = [e for e in evs if e.get("ph") == "M"
            and e["name"] == "process_name"]
    assert len(meta) == len({m["pid"] for m in meta}) == 2
    # the links entry became a flow pair anchored on the two spans
    flows = [e for e in evs if e.get("ph") in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    # merging the MERGED file together with its inputs stays stable
    out2 = str(tmp_path / "merged2.json")
    tool.merge([str(tmp_path)], out2)
    with open(out2) as f:
        data2 = json.load(f)
    assert sum(1 for e in data2["traceEvents"] if e.get("ph") == "X") \
        == sum(1 for e in evs if e.get("ph") == "X")


def test_trace_events_and_dump_shape():
    with telemetry.span("alpha"):
        pass
    evs = telemetry.trace_events()
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(
        {"name", "ts", "dur", "pid", "tid", "args"} <= set(e) for e in xs)
    assert all(int(e["args"]["span_id"], 16) for e in xs)
