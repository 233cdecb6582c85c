"""Pallas implicit-GEMM conv (``pallas_block.conv3x3_s1``) vs
lax.conv_general_dilated — forward, dgrad, wgrad.  Runs the SAME kernels
in interpret mode on CPU; the route through ``ops/nn.py::convolution``
is taken by patching the one seam, ``pallas_block.one_tpu``."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.ops import pallas_block as pc


def _ref_conv(x, w):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("shape,cout", [
    ((2, 8, 8, 16), 16),
    ((1, 14, 14, 32), 16),
    ((2, 7, 9, 8), 24),      # non-square, W != H
])
def test_forward_matches_xla(shape, cout):
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(onp.float32))
    w = jnp.asarray(rng.randn(3, 3, shape[-1], cout).astype(onp.float32))
    got = pc.conv3x3_s1(x, w)
    want = _ref_conv(x, w)
    assert got.shape == want.shape
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                atol=1e-4, rtol=1e-4)


def test_gradients_match_xla():
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 8, 8).astype(onp.float32))
    w = jnp.asarray(rng.randn(3, 3, 8, 12).astype(onp.float32))

    def loss_pallas(x, w):
        return jnp.sum(jnp.square(pc.conv3x3_s1(x, w)))

    def loss_ref(x, w):
        return jnp.sum(jnp.square(_ref_conv(x, w)))

    gx, gw = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    onp.testing.assert_allclose(onp.asarray(gx), onp.asarray(rx),
                                atol=1e-3, rtol=1e-3)
    onp.testing.assert_allclose(onp.asarray(gw), onp.asarray(rw),
                                atol=1e-3, rtol=1e-3)


def test_bf16_forward_accumulates_f32():
    rng = onp.random.RandomState(2)
    x32 = rng.randn(1, 8, 8, 16).astype(onp.float32)
    w32 = rng.randn(3, 3, 16, 16).astype(onp.float32)
    x = jnp.asarray(x32, jnp.bfloat16)
    w = jnp.asarray(w32, jnp.bfloat16)
    got = pc.conv3x3_s1(x, w)
    assert got.dtype == jnp.bfloat16
    want = _ref_conv(jnp.asarray(x, jnp.float32),
                     jnp.asarray(w, jnp.float32))
    # bf16 inputs, f32 accumulation: ~2 decimal digits of agreement
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(want), atol=0.35, rtol=0.12)


@pytest.fixture
def one_tpu(monkeypatch):
    """As if this process drove one TPU; executables cached under the
    other answer are dropped on both sides."""
    from mxnet_tpu import dispatch_cache
    dispatch_cache.clear()
    monkeypatch.setattr(pc, "one_tpu", lambda: True)
    yield
    dispatch_cache.clear()


def test_eligibility_gate(one_tpu):
    bf16 = jnp.bfloat16
    x, w = (128, 56, 56, 64), (3, 3, 64, 64)
    assert pc.conv_wins(x, w, 1, 1, 1, 1, bf16)
    assert not pc.conv_wins(x, w, 2, 1, 1, 1, bf16)
    assert not pc.conv_wins(x, (1, 1, 64, 64), 1, 1, 1, 1, bf16)
    assert not pc.conv_wins(x, w, 1, 1, 1, 2, bf16)
    # too big for VMEM: 112×112×128 patches blow the budget
    assert not pc.eligible_block((64, 112, 112, 128), (3, 3, 128, 128), bf16)
    assert not pc.conv_wins((64, 112, 112, 128), (3, 3, 128, 128),
                            1, 1, 1, 1, bf16)


def _kernels_traced(monkeypatch):
    """Names of the conv kernels emitted from here on."""
    seen = []
    real = pc.conv3x3

    def spy(x, w, out_dtype=None, name="mx_block_conv"):
        seen.append(name)
        return real(x, w, out_dtype, name)
    monkeypatch.setattr(pc, "conv3x3", spy)
    return seen


def test_dispatch_through_ops_nn(one_tpu, monkeypatch):
    """On one TPU the framework convolution routes an eligible 3×3/s1
    conv of a routed stage through the Pallas kernel; another stage, or
    no TPU, takes XLA."""
    from mxnet_tpu.ops import nn as onn
    seen = _kernels_traced(monkeypatch)
    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 56, 56, 64).astype(onp.float32))
    w = jnp.asarray(rng.randn(3, 3, 64, 64).astype(onp.float32) * 0.05)
    got = onn.convolution(x, w, stride=1, pad=1)
    assert seen == ["mx_block_conv"]
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(_ref_conv(x, w)),
                                atol=1e-4, rtol=1e-4)
    onn.convolution(x[:, :8, :8, :16], w[:, :, :16, :16], stride=1, pad=1)
    assert seen == ["mx_block_conv"]         # stage 8x8x16 does not route


def test_training_step_through_pallas_path(one_tpu, monkeypatch):
    """A real gluon training step (forward+backward+update) whose conv
    takes the Pallas route: the custom-vjp kernels compose with the
    autograd tape and optimizer exactly like the XLA path."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn, loss as gloss

    seen = _kernels_traced(monkeypatch)
    mx.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(64, 3, padding=1, activation="relu"),
            nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(4))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    lf = gloss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.rand(2, 56, 56, 64).astype(onp.float32))
    y = mx.np.array(rng.randint(0, 4, (2,)))
    first = last = None
    for _ in range(5):
        with autograd.record():
            l = lf(net(x), y).mean()
        l.backward()
        tr.step(1)
        v = float(l.item())
        first = v if first is None else first
        last = v
    assert {"mx_block_conv", "mx_block_dx"} <= set(seen)
    assert onp.isfinite(last) and last < first, (first, last)
