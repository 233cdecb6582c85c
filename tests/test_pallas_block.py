"""Fused residual-block pipeline (ops/pallas_block.py) vs the
layer-by-layer XLA composition — forward, dgrad/wgrad/dgamma, BN train
vs frozen, residual vs none, per-stage dispatch, and a fuse_step run
with zero steady-state retraces.  Runs the SAME kernels in interpret
mode on CPU; a route is taken here by patching the one seam,
``pallas_block.one_tpu``.  Chip numbers: the ``resnet50-train-ring``
cell (PERF_LEDGER.jsonl)."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.ops import pallas_block as pb

# the three ResNet 3×3/s1 stage shapes, batch 1 (interpret mode pays
# per-grid-cell python cost; parity is batch-size-independent)
STAGES = [
    ((1, 56, 56, 64), "56x56x64"),
    ((1, 28, 28, 128), "28x28x128"),
    ((1, 14, 14, 256), "14x14x256"),
]


@pytest.fixture
def pallas_on(monkeypatch):
    monkeypatch.setattr(pb, "one_tpu", lambda: True)


def _ref(x, w, gamma, beta, mean, var, res=None, *, training=True,
         relu=True, eps=1e-5):
    """What the unfused path lowers to: conv, BN, add, ReLU."""
    z = lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)
    if training:
        m = jnp.mean(z, axis=(0, 1, 2))
        v = jnp.maximum(jnp.mean(jnp.square(z), axis=(0, 1, 2)) - m * m, 0.)
    else:
        m, v = mean, var
    y = (z - m) * (gamma * lax.rsqrt(v + eps)) + beta
    if res is not None:
        y = y + res.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _data(shape, dtype=jnp.float32, seed=0, res=True):
    rs = onp.random.RandomState(seed)
    N, H, W, C = shape
    x = jnp.asarray(rs.randn(*shape), dtype)
    w = jnp.asarray(rs.randn(3, 3, C, C) * 0.05, dtype)
    r = jnp.asarray(rs.randn(N, H, W, C), dtype) if res else None
    gamma = jnp.asarray(rs.rand(C) + 0.5, jnp.float32)
    beta = jnp.asarray(rs.randn(C) * 0.1, jnp.float32)
    return x, w, r, gamma, beta, jnp.zeros(C, jnp.float32), \
        jnp.ones(C, jnp.float32)


@pytest.mark.parametrize("shape,stage", STAGES)
def test_train_fwd_and_grads_parity(shape, stage, pallas_on):
    """fp32 tight parity on every stage shape: fused forward (train-mode
    BN + residual + ReLU) and the custom-vjp dgrad/wgrad/dgamma with the
    Pallas backward kernels."""
    x, w, r, gamma, beta, mean, var = _data(shape)
    out, bm, bv = pb.residual_block_fused(x, w, gamma, beta, mean, var, r,
                                          frozen=False, bwd="pallas")
    want = _ref(x, w, gamma, beta, mean, var, r, training=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(want),
                                atol=1e-3, rtol=1e-3)
    # the returned batch stats feed the EMA update in ops/nn.py
    z = lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)
    onp.testing.assert_allclose(onp.asarray(bm),
                                onp.asarray(jnp.mean(z, axis=(0, 1, 2))),
                                atol=1e-3, rtol=1e-3)

    def loss_p(a, b, g):
        return jnp.sum(jnp.square(pb.residual_block_fused(
            a, b, g, beta, mean, var, r, frozen=False, bwd="pallas")[0]))

    def loss_r(a, b, g):
        return jnp.sum(jnp.square(_ref(a, b, g, beta, mean, var, r,
                                       training=True)))

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(x, w, gamma)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x, w, gamma)
    for name, a, b in zip(("dgrad", "wgrad", "dgamma"), gp, gr):
        scl = float(jnp.max(jnp.abs(b))) or 1.0
        onp.testing.assert_allclose(
            onp.asarray(a), onp.asarray(b), atol=2e-2 * scl, rtol=2e-3,
            err_msg=f"{name} mismatch on {stage}")


def test_bf16_loose_parity(pallas_on):
    """bf16 inputs, f32 accumulation/BN math: loose forward parity plus
    finite grads through the pallas backward."""
    x, w, r, gamma, beta, mean, var = _data((1, 28, 28, 128),
                                            jnp.bfloat16, seed=1)
    out, _, _ = pb.residual_block_fused(x, w, gamma, beta, mean, var, r,
                                        frozen=False, bwd="pallas")
    assert out.dtype == jnp.bfloat16
    want = _ref(x.astype(jnp.float32), w.astype(jnp.float32), gamma, beta,
                mean, var, r.astype(jnp.float32), training=True)
    onp.testing.assert_allclose(onp.asarray(out, onp.float32),
                                onp.asarray(want), atol=0.35, rtol=0.12)
    gx, gw = jax.grad(
        lambda a, b: jnp.sum(pb.residual_block_fused(
            a, b, gamma, beta, mean, var, r,
            frozen=False, bwd="pallas")[0].astype(jnp.float32)),
        argnums=(0, 1))(x, w)
    assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))
    assert bool(jnp.all(jnp.isfinite(gw.astype(jnp.float32))))


def test_frozen_vs_train(pallas_on):
    """Frozen BN folds running stats into a per-channel affine (one-pass
    kernel); train mode normalizes by batch stats (two-pass).  Both must
    match their reference, and differ from each other for nontrivial
    running stats."""
    x, w, r, gamma, beta, _, _ = _data((1, 14, 14, 256), seed=2)
    rs = onp.random.RandomState(3)
    mean = jnp.asarray(rs.randn(256) * 0.2, jnp.float32)
    var = jnp.asarray(rs.rand(256) + 0.5, jnp.float32)

    outf, mf, vf = pb.residual_block_fused(x, w, gamma, beta, mean, var, r,
                                           frozen=True, bwd="pallas")
    onp.testing.assert_allclose(
        onp.asarray(outf),
        onp.asarray(_ref(x, w, gamma, beta, mean, var, r, training=False)),
        atol=1e-3, rtol=1e-3)
    # frozen returns the running stats unchanged (no EMA drift at eval)
    onp.testing.assert_allclose(onp.asarray(mf), onp.asarray(mean))
    onp.testing.assert_allclose(onp.asarray(vf), onp.asarray(var))

    outt, _, _ = pb.residual_block_fused(x, w, gamma, beta, mean, var, r,
                                         frozen=False, bwd="pallas")
    assert not bool(jnp.allclose(outf, outt, atol=1e-3))
    # frozen grads flow (recomputes z rather than saving it)
    gx = jax.grad(lambda a: jnp.sum(jnp.square(pb.residual_block_fused(
        a, w, gamma, beta, mean, var, r, frozen=True,
        bwd="pallas")[0])))(x)
    assert bool(jnp.all(jnp.isfinite(gx)))


def test_residual_and_relu_optional(pallas_on):
    """residual=None and relu=False legs: parity with the reference and
    a real effect vs the full epilogue."""
    x, w, _, gamma, beta, mean, var = _data((1, 14, 14, 256), seed=4,
                                            res=False)
    out, _, _ = pb.residual_block_fused(x, w, gamma, beta, mean, var, None,
                                        frozen=False, bwd="pallas")
    onp.testing.assert_allclose(
        onp.asarray(out),
        onp.asarray(_ref(x, w, gamma, beta, mean, var, None,
                         training=True)),
        atol=1e-3, rtol=1e-3)
    out2, _, _ = pb.residual_block_fused(x, w, gamma, beta, mean, var,
                                         None, frozen=False, relu=False,
                                         bwd="pallas")
    onp.testing.assert_allclose(
        onp.asarray(out2),
        onp.asarray(_ref(x, w, gamma, beta, mean, var, None, training=True,
                         relu=False)),
        atol=1e-3, rtol=1e-3)
    assert not bool(jnp.allclose(out, out2))
    # None residual → None cotangent: grad must not explode
    gx = jax.grad(lambda a: jnp.sum(pb.residual_block_fused(
        a, w, gamma, beta, mean, var, None, frozen=False,
        bwd="pallas")[0]))(x)
    assert bool(jnp.all(jnp.isfinite(gx)))


def test_per_stage_dispatch(pallas_on, monkeypatch):
    """``_DEFAULT_TABLE`` drives decide(): the routed stage goes forward
    and backward through Pallas, the others and every ineligible shape
    take XLA, and off one TPU nothing routes."""
    r1 = pb.decide((1, 56, 56, 64), (3, 3, 64, 64), jnp.float32)
    assert (r1.fwd, r1.bwd, r1.stage) == ("pallas", "pallas", "56x56x64")
    r2 = pb.decide((1, 14, 14, 256), (3, 3, 256, 256), jnp.float32)
    assert (r2.fwd, r2.bwd, r2.stage) == ("xla", "xla", "14x14x256")
    assert pb.block_active()

    # ineligible shapes fall back regardless of the table (5×5 filter)
    assert not pb.eligible_block((1, 56, 56, 64), (5, 5, 64, 64),
                                 jnp.float32)
    assert pb.decide((1, 56, 56, 64), (5, 5, 64, 64), jnp.float32).fwd == "xla"

    monkeypatch.setattr(pb, "one_tpu", lambda: False)
    r4 = pb.decide((1, 56, 56, 64), (3, 3, 64, 64), jnp.float32)
    assert r4.fwd == "xla" and not pb.block_active()


class _Dev:
    def __init__(self, platform):
        self.platform = platform


_HOSTS = {"one-tpu": [_Dev("tpu")], "cpu": [_Dev("cpu")],
          "four-tpus": [_Dev("tpu")] * 4}


@pytest.mark.parametrize("host", sorted(_HOSTS))
@pytest.mark.parametrize("shape,stage", STAGES)
def test_shapes_and_one_tpu_decide(shape, stage, host, monkeypatch):
    """The whole rule: a stage's constant entry, on exactly one TPU.
    ``decide``, ``conv_wins`` and the int8 decision agree with it and
    count what they answered; nothing else is consulted."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_int8
    monkeypatch.setattr(jax, "devices", lambda *a: _HOSTS[host])
    c = shape[-1]
    w = (3, 3, c, c)
    block = host == "one-tpu" and pb._DEFAULT_TABLE[stage]["fwd"] == "pallas"
    int8 = host == "one-tpu" and \
        pallas_int8._DEFAULT_TABLE[stage]["fwd"] == "pallas"
    telemetry.reset()
    route = pb.decide(shape, w, jnp.bfloat16, has_residual=True)
    assert route.stage == stage
    assert route.fwd == ("pallas" if block else "xla")
    assert route.bwd == (pb._DEFAULT_TABLE[stage]["bwd"] if block else "xla")
    assert pb.conv_wins(shape, w, 1, 1, 1, 1, jnp.bfloat16) is block
    assert not pb.conv_wins(shape, w, 2, 1, 1, 1, jnp.bfloat16)   # stride 2
    assert not pb.conv_wins(shape, w, 1, 1, 1, 2, jnp.bfloat16)   # grouped
    assert pallas_int8.decide_int8(shape, w, True) == \
        ("pallas" if int8 else "xla")
    counters = {k: v for k, v in telemetry.raw_snapshot()["counters"].items()
                if k.startswith(("dispatch.pallas.", "quant.int8.")) and v}
    want = {}
    if host == "one-tpu":
        want[f"dispatch.pallas.{'hits' if block else 'fallbacks'}.{stage}"] = 1
        want[f"quant.int8.{'hits' if int8 else 'fallbacks'}.{stage}"] = 1
    assert counters == want


def test_fuse_step_zero_retraces(pallas_on):
    """A BasicBlockV1 head trained via Trainer.fuse_step with Pallas
    routing on: fused path active, 0 retraces, 0 rebuilds, exactly one
    dispatch per step, and no new per-stage routing decisions in steady
    state (routing happens at trace time only)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon import Trainer, nn as gnn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models.resnet import BasicBlockV1
    from mxnet_tpu.ndarray import NDArray

    class Head(gnn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.block = BasicBlockV1(64, 1)
            self.flat = gnn.Flatten()
            self.out = gnn.Dense(4)

        def forward(self, xx):
            return self.out(self.flat(self.block(xx)))

    mx.seed(0)
    net = Head()
    net.initialize()
    net.hybridize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    step = tr.fuse_step(SoftmaxCrossEntropyLoss())
    rs = onp.random.RandomState(0)
    xb = NDArray(jnp.asarray(rs.randn(2, 56, 56, 64), jnp.float32))
    yb = NDArray(jnp.asarray(rs.randint(0, 4, (2,)), jnp.int32))
    for _ in range(2):                       # warm-up: trace + compile
        step(xb, yb)
    step.sync()
    base = telemetry.summary()
    steps = 3
    for _ in range(steps):
        step(xb, yb)
    step.sync()
    cur = telemetry.summary()

    def delta(name):
        return cur.get(name, 0) - base.get(name, 0)

    assert step.fused, step.fallback_reason
    assert delta("fused.retraces") == 0
    assert delta("fused.rebuilds") == 0
    assert delta("fused.dispatches") == steps
    new_decisions = sum(cur.get(k, 0) - base.get(k, 0) for k in cur
                       if k.startswith("dispatch.pallas.hits."))
    assert new_decisions == 0


def test_default_routes_need_exactly_one_tpu(monkeypatch):
    """Default-on only where this process drives exactly one TPU: a
    Mosaic kernel cannot be partitioned by GSPMD, so on a multi-chip
    host (any program may be partitioned) every route answers XLA."""
    from mxnet_tpu.ops import pallas_int8, pallas_kernels

    gates = (pb.block_active,
             lambda: pallas_int8.decide_int8(
                 (1, 14, 14, 256), (3, 3, 256, 256)) == "pallas",
             lambda: pallas_kernels._use_pallas(128))
    for host, want in (("one-tpu", True), ("four-tpus", False),
                       ("cpu", False)):
        monkeypatch.setattr(jax, "devices", lambda *a, _h=host: _HOSTS[_h])
        assert pb.one_tpu() is want
        assert [g() for g in gates] == [want] * len(gates), host


def test_no_environment_name_chooses_a_kernel():
    """The one ``MXNET_TPU_PALLAS_*`` name the program knows is
    ``…_INTERPRET``, which chooses no path (the same kernels,
    interpreted): no file of the program, the row orchestrator, the
    smoke script or the Makefile reads or sets another."""
    import os
    import re
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(repo, f)
             for f in ("bench.py", "chip_smoke.py", "Makefile")]
    for root, _, names in os.walk(os.path.join(repo, "mxnet_tpu")):
        files += [os.path.join(root, n) for n in names if n.endswith(".py")]
    found = {}
    for path in files:
        with open(path, encoding="utf-8") as f:
            names = set(re.findall(r"MXNET_TPU_PALLAS_[A-Z0-9_]+", f.read()))
        if names - {"MXNET_TPU_PALLAS_INTERPRET"}:
            found[os.path.relpath(path, repo)] = sorted(names)
    assert not found
    assert len(files) > 100
