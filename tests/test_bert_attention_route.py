"""The one route of the fused attention kernels: `BERTSelfAttention`.

The block decides from what it can observe — mask, active dropout, shape
and platform — and everything the kernels do not take runs the
matmul/softmax/matmul composition as before.  Interpret mode stands in
for the chip (`pallas_kernels._FORCE_INTERPRET`); the kernels round their
MXU operands to bfloat16, so the two paths agree to 2e-2 of the largest
value (tests/test_pallas_rtc.py states the tolerance).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.models.bert_gluon import BERTModel
from mxnet_tpu.ops import pallas_kernels as pk

LAYERS, HEADS, UNITS, T, VOCAB = 2, 2, 128, 128, 50     # head_dim 64


def _net(dropout=0.0):
    mx.seed(0)
    net = BERTModel(units=UNITS, heads=HEADS, layers=LAYERS, ffn_units=256,
                    vocab_size=VOCAB, max_length=T, dropout=dropout)
    net.initialize()
    return net


def _tokens(length=T):
    return mx.np.array(np.random.RandomState(1).randint(
        0, VOCAB, (2, length)).astype(np.int32))


def _routes():
    return {k[len("dispatch.pallas."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.pallas.") and v
            and ".layernorm." not in k}


def _loss_and_grads(net, tokens, **kwargs):
    with autograd.record():
        loss = (net(tokens, **kwargs) ** 2).mean()
    loss.backward()
    return float(loss.asnumpy()), {
        name: p.grad().asnumpy()
        for name, p in net.collect_params().items() if p.grad_req != "null"}


def _close(got, want, rtol=2e-2):
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= rtol * scale


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)


def test_unmasked_forward_takes_the_kernels(kernels):
    net, tokens = _net(), _tokens()
    net(tokens)                                   # resolve deferred shapes
    net.hybridize()
    telemetry.reset()
    routed = net(tokens).asnumpy()
    assert _routes() == {"hits.attention.64": LAYERS}      # no hits.softmax
    pk._FORCE_INTERPRET = False
    plain = _net()
    telemetry.reset()
    _close(routed, plain(tokens).asnumpy())
    routes = _routes()     # the block's own "no", then the softmax's
    assert routes.pop("fallbacks.attention.64") == LAYERS
    assert list(routes) == ["fallbacks.softmax.128"]


def test_routed_and_composed_gradients_agree(kernels):
    tokens = _tokens()
    loss_k, grads_k = _loss_and_grads(_net(), tokens)
    pk._FORCE_INTERPRET = False
    loss_c, grads_c = _loss_and_grads(_net(), tokens)
    assert abs(loss_k - loss_c) <= 2e-2 * abs(loss_c)
    assert grads_k.keys() == grads_c.keys() and len(grads_k) > 8 * LAYERS
    for name in grads_c:
        _close(grads_k[name], grads_c[name])


@pytest.mark.parametrize("case", ["mask", "dropout-in-training",
                                  "length-off-the-block", "not-one-tpu"])
def test_everything_else_takes_the_composition(monkeypatch, case):
    """One `fallbacks.attention.64` a layer, no kernel, and the answer of
    the same net with the kernels out of reach, bit for bit."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", case != "not-one-tpu")
    tokens = _tokens(96 if case == "length-off-the-block" else T)
    net = _net(dropout=0.1 if case == "dropout-in-training" else 0.0)
    kwargs = {}
    if case == "mask":
        kwargs["mask"] = mx.np.array(np.arange(T)[None, :] < np.array(
            [[T], [T // 2]]))

    def run():
        mx.seed(7)                                # the dropout masks' key
        telemetry.reset()
        if case == "dropout-in-training":
            with autograd.train_mode():
                return net(tokens).asnumpy()
        return net(tokens, **kwargs).asnumpy()

    run()                 # the first call resolves shapes (and draws keys)
    got = run()
    routes = _routes()
    assert routes.pop("fallbacks.attention.64") == LAYERS
    assert not any(k.startswith("hits.attention") for k in routes)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", False)
    assert np.array_equal(got, run())


def test_dropout_is_no_obstacle_outside_training(kernels):
    net, tokens = _net(dropout=0.1), _tokens()
    net(tokens)
    net.hybridize()
    telemetry.reset()
    net(tokens)
    assert _routes() == {"hits.attention.64": LAYERS}


def test_export_knows_the_op(kernels, tmp_path):
    """The deferred tracer records `multihead_self_attention`; the
    imported Symbol replays it through the kernels here and through the
    composition where they do not apply."""
    net, tokens = _net(), _tokens()
    want = net(tokens).asnumpy()
    sf, pf = net.export(str(tmp_path / "bert"))
    assert "multihead_self_attention" in open(sf).read()
    sb = gluon.SymbolBlock.imports(sf, ["data"], pf)
    out = sb(tokens)
    out = out[0] if isinstance(out, (list, tuple)) else out
    assert np.allclose(out.asnumpy(), want, atol=1e-5)
    pk._FORCE_INTERPRET = False
    out = gluon.SymbolBlock.imports(sf, ["data"], pf)(tokens)
    out = out[0] if isinstance(out, (list, tuple)) else out
    _close(out.asnumpy(), want)
