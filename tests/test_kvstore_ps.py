"""Parameter-server (dist_async) + gradient wire-packing tests.

≙ reference tests/nightly/dist_async_kvstore.py semantics, run
single-process (the multi-process version is tests/nightly/
dist_async_train.py via test_dist_kvstore.py), plus the 2-bit/1-bit
payload packing of src/kvstore/gradient_compression.h:115-122.
"""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kvstore.ps import (pack_1bit, pack_2bit, unpack_1bit,
                                  unpack_2bit)


def test_pack_2bit_roundtrip_and_size():
    g = onp.random.RandomState(0).randn(1000).astype(onp.float32)
    q = onp.where(g > 0.5, 0.5,
                  onp.where(g < -0.5, -0.5, 0.0)).astype(onp.float32)
    packed, shape, t = pack_2bit(q, 0.5)
    # 16× smaller than the f32 payload (4 codes per byte vs 4 bytes each)
    assert packed.nbytes == 250 and q.nbytes == 4000
    assert onp.array_equal(unpack_2bit(packed, shape, t), q)


def test_pack_2bit_nonmultiple_of_4():
    q = onp.array([0.5, -0.5, 0.0, 0.5, -0.5], onp.float32)
    packed, shape, t = pack_2bit(q, 0.5)
    assert onp.array_equal(unpack_2bit(packed, shape, t), q)


def test_pack_1bit_roundtrip_and_size():
    g = onp.random.RandomState(1).randn(800).astype(onp.float32)
    q = onp.where(g >= 0, 0.25, -0.25).astype(onp.float32)
    packed, shape, t = pack_1bit(q, 0.25)
    assert packed.nbytes == 100 and q.nbytes == 3200   # 32×
    assert onp.array_equal(unpack_1bit(packed, shape, t), q)


def test_dist_async_store_push_pull():
    kv = mx.kvstore.create("dist_async")
    kv.init("w", mx.np.array(onp.ones((4, 3), onp.float32)))
    kv.push("w", mx.np.array(onp.full((4, 3), 2.0, onp.float32)))
    out = mx.np.zeros((4, 3))
    kv.pull("w", out=out)
    # no optimizer → pushes accumulate (base push semantics)
    assert onp.allclose(out.asnumpy(), 3.0)


def test_dist_async_server_side_optimizer():
    from mxnet_tpu import optimizer as opt_mod
    kv = mx.kvstore.create("dist_async")
    kv.init("x", mx.np.array(onp.zeros(5, onp.float32)))
    kv.set_optimizer(opt_mod.create("sgd", learning_rate=0.5))
    kv.push("x", mx.np.array(onp.ones(5, onp.float32)))
    out = mx.np.zeros(5)
    kv.pull("x", out=out)
    # one SGD step on the server copy: 0 - 0.5*1
    assert onp.allclose(out.asnumpy(), -0.5)


def test_dist_async_packed_compression_wire():
    """With compression on, the wire payload is packed uint8 words; the
    server unpacks and applies — end-to-end through a real socket."""
    kv = mx.kvstore.create("dist_async")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("y", mx.np.array(onp.zeros(8, onp.float32)))
    payload = kv._pack("y", mx.np.array(
        onp.full(8, 0.7, onp.float32))._data)
    assert payload[0] == "2bit" and payload[1].nbytes == 2   # 8 f32 → 2 B
    kv.push("y", mx.np.array(onp.full(8, 0.7, onp.float32)))
    out = mx.np.zeros(8)
    kv.pull("y", out=out)
    assert onp.allclose(out.asnumpy(), 0.5)    # quantized to +threshold


def test_dist_async_pushpull_raises():
    kv = mx.kvstore.create("dist_async")
    with pytest.raises(RuntimeError):
        kv.pushpull(0, mx.np.ones(3))


def test_dist_async_trainer_requires_update_on_kvstore():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    net = nn.Dense(1)
    net.initialize()
    with pytest.raises(ValueError):
        gluon.Trainer(net.collect_params(), "sgd", kvstore="dist_async",
                      update_on_kvstore=False)


def test_dist_async_trainer_converges():
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn, loss as gloss
    mx.seed(0)
    net = nn.Dense(1)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="dist_async")
    X = onp.random.RandomState(0).rand(64, 4).astype(onp.float32)
    Y = X.sum(axis=1, keepdims=True)
    lf = gloss.L2Loss()
    first = last = None
    for _ in range(30):
        x, y = mx.np.array(X), mx.np.array(Y)
        with autograd.record():
            l = lf(net(x), y).mean()
        l.backward()
        tr.step(1)
        v = float(l.item())
        first = v if first is None else first
        last = v
    assert last < first * 0.1, (first, last)


def test_dist_async_fast_worker_never_waits_for_slow_pusher():
    """Async contract ≙ kvstore_dist_server.h:882: a straggler's pushes
    must not gate another client's pulls — the server applies work per
    connection thread, no barrier anywhere."""
    import threading
    import time
    from mxnet_tpu.kvstore.ps import ParameterServer, PSClient

    srv = ParameterServer()
    addr = srv.start(publish=False)
    try:
        fast = PSClient(addr=addr)
        slow = PSClient(addr=addr)
        fast.init("w", onp.zeros(4, onp.float32))

        release = threading.Event()
        slow_done = threading.Event()

        def straggler():
            release.wait(10)                     # "compute" stall
            slow.push("w", ("raw", onp.ones(4, onp.float32)))
            slow_done.set()

        t = threading.Thread(target=straggler, daemon=True)
        t.start()
        # while the straggler sleeps, the fast worker pushes AND pulls
        t0 = time.perf_counter()
        for _ in range(5):
            fast.push("w", ("raw", onp.ones(4, onp.float32)))
        out = fast.pull("w")
        dt = time.perf_counter() - t0
        assert onp.allclose(out, 5.0)            # straggler not included
        assert dt < 5.0, f"fast worker stalled {dt:.1f}s behind straggler"
        release.set()
        assert slow_done.wait(10)
        assert onp.allclose(fast.pull("w"), 6.0)  # late push lands
        fast.close()
        slow.close()
    finally:
        srv.stop()


def test_dist_async_client_surfaces_server_death():
    """A dead server must fail the worker FAST and loudly (connection
    error), not hang — the failure-detection contract SURVEY §5.3."""
    from mxnet_tpu.kvstore.ps import ParameterServer, PSClient

    srv = ParameterServer()
    addr = srv.start(publish=False)
    c = PSClient(addr=addr)
    c.init("w", onp.zeros(2, onp.float32))
    srv.stop()
    with pytest.raises((ConnectionError, OSError, RuntimeError)):
        for _ in range(10):                      # first call may still be
            c.pull("w")                          # buffered; soon it breaks
    c.close()


def test_ps_wire_rejects_garbage_frames():
    """The typed wire must fail cleanly on malformed input (a fuzzing
    byte-blast must never crash the server or execute anything —
    the no-pickle contract)."""
    import socket
    import struct
    from mxnet_tpu.kvstore.ps import ParameterServer, PSClient

    srv = ParameterServer()
    addr = srv.start(publish=False)
    try:
        host, _, port = addr.rpartition(":")
        # garbage opcode + garbage body
        s = socket.create_connection((host, int(port)), timeout=5)
        s.sendall(struct.pack("<IB", 4, 250) + b"\xde\xad\xbe\xef")
        hdr = b""
        while len(hdr) < 5:                      # TCP may segment
            chunk = s.recv(5 - len(hdr))
            assert chunk, "server closed instead of replying RE_ERR"
            hdr += chunk
        n, op = struct.unpack("<IB", hdr)
        assert op == 255                          # RE_ERR, not a crash
        s.close()
        # truncated frame then disconnect: server thread must survive
        s2 = socket.create_connection((host, int(port)), timeout=5)
        s2.sendall(struct.pack("<IB", 1000, 2) + b"short")
        s2.close()
        # the server still serves healthy clients afterwards
        c = PSClient(addr=addr)
        c.init("k", onp.ones(3, onp.float32))
        assert onp.allclose(c.pull("k"), 1.0)
        c.close()
    finally:
        srv.stop()


def test_ps_two_stores_share_standalone_servers_without_collision():
    """In standalone-server mode every store instance reaches the SAME
    server set; wire keys are seq-namespaced so a second store's keys and
    set_optimizer cannot collide with the first (PSGroup._wk)."""
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.kvstore.ps import ParameterServer, PSGroup

    srv = ParameterServer()
    addr = srv.start(publish=False)
    try:
        os.environ["MXNET_TPU_PS_ADDRS"] = addr
        a = PSGroup(seq=0, n=1)
        b = PSGroup(seq=1, n=1)
        a.init("x", onp.zeros(4, onp.float32))
        b.init("x", onp.full(4, 7.0, onp.float32))
        # store a gets a server-side optimizer; store b stays accumulate —
        # without namespacing b's pushes would run a's optimizer
        a.set_optimizer(opt_mod.create("sgd", learning_rate=0.5))
        a.push("x", ("raw", onp.ones(4, onp.float32)))
        b.push("x", ("raw", onp.ones(4, onp.float32)))
        assert onp.allclose(a.pull("x"), -0.5)   # one SGD step from 0
        assert onp.allclose(b.pull("x"), 8.0)    # plain += on 7
        a.close()
        b.close()
    finally:
        os.environ.pop("MXNET_TPU_PS_ADDRS", None)
        srv.stop()


def test_ps_updater_watchdog_surfaces_wedged_apply():
    """A wedged server-side update must become an RE_ERR frame within the
    watchdog budget — never a silent client hang (the round-3 failure
    mode: a first-use jit that never returns)."""
    import time
    from mxnet_tpu.kvstore.ps import ParameterServer

    srv = ParameterServer()
    srv.start(publish=False)
    old = os.environ.get("MXNET_TPU_PS_UPDATE_TIMEOUT")
    os.environ["MXNET_TPU_PS_UPDATE_TIMEOUT"] = "1"
    try:
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="updater wedged"):
            srv._exec_update(lambda abandoned: time.sleep(30))
        assert time.perf_counter() - t0 < 5.0
    finally:
        if old is None:
            os.environ.pop("MXNET_TPU_PS_UPDATE_TIMEOUT", None)
        else:
            os.environ["MXNET_TPU_PS_UPDATE_TIMEOUT"] = old
        srv.stop()


def test_ps_optimizer_step_runs_off_rpc_threads():
    """The optimizer step executes on the dedicated updater thread
    (reference: kvstore_dist_server.h:999 single-thread Executor), not on
    whichever socketserver handler received the push."""
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.kvstore.ps import ParameterServer, PSClient

    srv = ParameterServer()
    addr = srv.start(publish=False)
    seen = []
    orig = srv._opt_step

    def spy(key, opt, g, abandoned=None):
        import threading as _t
        seen.append(_t.current_thread().name)
        return orig(key, opt, g, abandoned)

    srv._opt_step = spy
    try:
        c = PSClient(addr=addr)
        c.init("w", onp.zeros(3, onp.float32))
        c.set_optimizer(opt_mod.create("sgd", learning_rate=1.0))
        c.push("w", ("raw", onp.ones(3, onp.float32)))
        assert onp.allclose(c.pull("w"), -1.0)
        assert seen and all(n == "mxtpu-ps-updater" for n in seen)
        c.close()
    finally:
        srv.stop()
