"""``gluon.block.materialize``: a value a recomputed block wants stored once
(an identity ``optimization_barrier`` while a training program is traced, a
pass-through everywhere else), its two sites in ``models/olmo_hybrid.py`` and
their counters, and the models that do not call it."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, tape, telemetry
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.gluon import block as gblock
from mxnet_tpu.gluon.block import _pure_trace, materialize
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.models import olmo_hybrid as oh
from mxnet_tpu.models.nemotron_h import nemotron_h_tiny
from mxnet_tpu.models.solar_open2 import solar_open2_tiny
from mxnet_tpu.ndarray import NDArray

PREFIX = "dispatch.materialized."
B, T = 2, 40


def _stored():
    return {k[len(PREFIX):]: v
            for k, v in telemetry.raw_snapshot()["counters"].items()
            if k.startswith(PREFIX)}


def _delta(before):
    now = _stored()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def _traced(fn, training):
    """``fn`` of raw arrays as a traced program of the package sees it."""
    def run(*raw):
        prev = tape.set_training(training)
        try:
            with _pure_trace({}):
                return fn(*raw)
        finally:
            tape.set_training(prev)
    return run


def _barriers(jaxpr):
    return str(jaxpr).count("optimization_barrier")


# ------------------------------------------------------------ the helper
@pytest.mark.parametrize("where", ["eager", "recording", "traced inference"])
def test_materialize_is_a_pass_through_outside_a_traced_training_program(where):
    x = mx.np.array(onp.arange(6, dtype="float32").reshape(2, 3))
    before = _stored()
    if where == "eager":
        assert materialize(x, "unit") is x
    elif where == "recording":        # training, but nothing is being traced
        with autograd.record():
            assert materialize(x, "unit") is x
    else:
        def same(a):
            h = NDArray(a)
            assert materialize(h, "unit") is h
            return a * 2
        jaxpr = jax.make_jaxpr(_traced(same, training=False))(x._data)
        assert _barriers(jaxpr) == 0
    assert _delta(before) == {}


def test_materialize_is_an_identity_in_value_and_gradient_inside_one():
    a = jnp.asarray(onp.random.RandomState(0).randn(3, 5).astype("float32"))
    f = _traced(lambda a: (jnp.sin(materialize(NDArray(a * 3), "unit")._data)
                           ** 2).sum(), training=True)
    plain = lambda a: (jnp.sin(a * 3) ** 2).sum()
    before = _stored()
    assert _barriers(jax.make_jaxpr(f)(a)) == 1
    assert _delta(before) == {"unit": 1}          # once a traced call
    # the transposition puts a barrier on the cotangent too
    assert _barriers(jax.make_jaxpr(jax.grad(f))(a)) >= 2
    got, want = jax.value_and_grad(f)(a), jax.value_and_grad(plain)(a)
    assert float(got[0]) == float(want[0])
    onp.testing.assert_array_equal(onp.asarray(got[1]), onp.asarray(want[1]))
    # the value keeps its dtype: nothing is cast
    low = _traced(lambda a: materialize(NDArray(a), "unit")._data, True)
    assert jax.eval_shape(low, a.astype(jnp.bfloat16)).dtype == jnp.bfloat16


# ------------------------------------------------------ the two call sites
def _olmo(seed=1):
    mx.seed(seed)
    net = oh.olmo_hybrid_tiny(4)
    net.initialize()
    net.hybridize()
    ids = onp.random.RandomState(seed).randint(0, 64, (B, T + 1))
    ids = ids.astype("int32")
    return net, mx.np.array(ids[:, :-1]), mx.np.array(ids[:, 1:])


def _sgd_step(net):
    """Plain SGD at rate 1 without decay: a weight moves by its gradient."""
    return Trainer(net.collect_params(), "sgd",
                   {"learning_rate": 1.0, "wd": 0.0}
                   ).fuse_step(SoftmaxCrossEntropyLoss())


def _loss_and_gradients(monkeypatch, stored):
    if not stored:
        monkeypatch.setattr(oh, "materialize", lambda x, site: x)
    net, x, y = _olmo()
    w0 = {n: onp.asarray(p.data()._data)
          for n, p in net.collect_params().items()}
    step = _sgd_step(net)
    with jax.default_matmul_precision("highest"):
        loss = float(step(x, y).asnumpy())
    step.sync()
    assert not step.fallback_reason
    return loss, {n: w0[n] - onp.asarray(p.data()._data)
                  for n, p in net.collect_params().items()}


def test_a_toy_step_s_loss_and_gradients_are_those_of_the_step_without_it(
        monkeypatch):
    before = _stored()
    loss, grads = _loss_and_gradients(monkeypatch, stored=True)
    assert _delta(before) == {"mlp_act": 4, "sublayer_out": 8}
    before = _stored()
    loss0, grads0 = _loss_and_gradients(monkeypatch, stored=False)
    assert _delta(before) == {}
    assert abs(loss - loss0) <= 1e-6 * abs(loss0)
    assert set(grads) == set(grads0)
    for name, g in grads.items():
        assert onp.abs(grads0[name]).max() > 0, name
        assert onp.abs(g - grads0[name]).max() \
            <= 1e-5 * onp.abs(grads0[name]).max(), name


@pytest.mark.parametrize("site,build", [
    ("mlp_act", lambda: oh.SwiGLUMLP(32, 48)),
    ("sublayer_out", lambda: oh.PostNormLayer(oh._dense(32, 32), 32)),
])
def test_a_site_counts_once_a_traced_block_and_nothing_in_eager_or_inference(
        site, build):
    mx.seed(3)
    block = build()
    block.initialize()
    x = mx.np.array(onp.random.RandomState(3).randn(2, 8, 32)
                    .astype("float32"))
    before = _stored()
    eager = block(x)                                   # eager
    block.hybridize()
    onp.testing.assert_allclose(block(x).asnumpy(), eager.asnumpy(),
                                rtol=1e-6, atol=1e-6)  # a traced inference
    assert _delta(before) == {}
    with autograd.record():                            # a traced training
        out = block(x)
    out.backward()
    assert _delta(before) == {site: 1}
    onp.testing.assert_allclose(out.asnumpy(), eager.asnumpy(), rtol=1e-6,
                                atol=1e-6)
    with autograd.record():                            # the cached program
        block(x)
    assert _delta(before) == {site: 1}


# ------------------------------------------- the models that do not call it
def _tiny_nemotron():
    return nemotron_h_tiny("MEMEM*EME")


def _tiny_solar():
    return solar_open2_tiny(4, (0,))


@pytest.mark.parametrize("build", [_tiny_nemotron, _tiny_solar])
def test_nemotron_s_and_solar_s_steps_store_nothing(build, monkeypatch):
    """Their pre-norm layers and expert paths have other producers: they
    trace no ``materialize``, so their step programs hold no count of it and
    the same text with the helper taken away."""
    def text(net_of):
        mx.seed(1)
        net = net_of()
        net.initialize()
        net.hybridize()
        ids = onp.random.RandomState(1).randint(0, 64, (B, 33)).astype("int32")
        x, y = mx.np.array(ids[:, :-1]), mx.np.array(ids[:, 1:])
        step = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3}
                       ).fuse_step(SoftmaxCrossEntropyLoss())
        step(x, y)
        assert not step.fallback_reason
        return step.hlo_text(x, y)

    def never(x, site):
        raise AssertionError(f"materialize({site!r}) in a model that has "
                             "no such site")
    before, texts = _stored(), []
    for helper in (True, False):      # one call site: the text names lines
        if not helper:
            monkeypatch.setattr(gblock, "materialize", never)
            monkeypatch.setattr(oh, "materialize", never)
        texts.append(text(build))
    assert texts[0] == texts[1]
    assert _delta(before) == {}
