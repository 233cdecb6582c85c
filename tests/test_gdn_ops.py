"""The operator Olmo-Hybrid added (ops/nn.py::gdn_chunked: the gated delta
rule with one decay a head and step, keys and values of different widths)
against the plain token-by-token recurrence of
chipbench/reference/olmo_hybrid.py at small sizes on the CPU: forward, the
gradients of every input, a T the chunk does not divide, beta in (0, 2),
strong decays, equality with kda_chunked fed the same decay on every
channel, and the route counter.  The model is in test_olmo_hybrid.py
(another file, so another worker takes it)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as N

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "gdn_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "olmo_hybrid.py")
B, T, H, DK, DV = 2, 80, 3, 12, 24          # dk != dv, neither a lane tile
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(seed=0, t=T, dk=DK, dv=DV):
    rs = onp.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    q = N.l2_normalize(draw(B, t, H, dk)) * dk ** -0.5
    k = N.l2_normalize(draw(B, t, H, dk))
    g = -jnp.exp(draw(B, t, H))                   # one log-decay a head
    beta = 2 * jax.nn.sigmoid(draw(B, t, H))      # in (0, 2)
    return q, k, draw(B, t, H, dv), g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token recurrence, a sequence at a time."""
    return jax.vmap(ref.gdn_recurrence)(q, k, v, g, beta)


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# 64, 32 and 24 do not divide T = 80 (padded); 80: one chunk
@pytest.mark.parametrize("chunk", [64, 32, 16, 8, 24, 80])
def test_chunked_rule_is_the_recurrence(chunk):
    args = _inputs()
    assert float(args[4].min()) > 0 and float(args[4].max()) > 1.5
    with jax.default_matmul_precision("highest"):
        got = N.gdn_chunked(*args, chunk=chunk)
        want = _recurrence(*args)
    assert got.shape == (B, T, H, DV)
    assert _err(got, want) < 1e-5


@pytest.mark.parametrize("dk,dv", [(12, 24), (24, 12), (8, 8), (5, 3)])
def test_key_and_value_widths_are_free(dk, dv):
    args = _inputs(7, dk=dk, dv=dv)
    with jax.default_matmul_precision("highest"):
        got = N.gdn_chunked(*args, chunk=16)
        assert got.shape == (B, T, H, dv)
        assert _err(got, _recurrence(*args)) < 1e-5


@pytest.mark.parametrize("wrt", range(5), ids=NAMES)
def test_chunked_rule_s_gradients_are_the_recurrence_s(wrt):
    args = _inputs(1)
    probe = jnp.asarray(onp.random.RandomState(2).randn(B, T, H, DV),
                        jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(N.gdn_chunked(*a, chunk=32)
                                          * probe), argnums=wrt)(*args)
        want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe),
                        argnums=wrt)(*args)
    assert got.shape == args[wrt].shape
    assert _err(got, want) < 1e-4


def test_a_chunk_that_does_not_divide_t_is_padded_not_refused():
    """T = 70 in chunks of 32: the last 26 steps are padding (k = 0, beta =
    0, g = 0) that changes no state and is cut back; so are its
    gradients."""
    args = _inputs(3, t=70)
    with jax.default_matmul_precision("highest"):
        got = N.gdn_chunked(*args, chunk=32)
        assert got.shape == (B, 70, H, DV)
        assert _err(got, _recurrence(*args)) < 1e-5
        dg = jax.grad(lambda g: jnp.sum(N.gdn_chunked(
            args[0], args[1], args[2], g, args[4], chunk=32)))(args[3])
        want = jax.grad(lambda g: jnp.sum(_recurrence(
            args[0], args[1], args[2], g, args[4])))(args[3])
    assert dg.shape == (B, 70, H) and _err(dg, want) < 1e-4


@pytest.mark.parametrize("decay", [-20.0, -200.0])
def test_strong_decay_and_beta_two_stay_finite(decay):
    """g = -20 a step (exp(-G) over a chunk of 64 would be exp(1280): the
    factored form overflows) and beta = 2: forward and all five gradients
    finite, and still the recurrence."""
    q, k, v, g, beta = _inputs(4)
    g, beta = jnp.full_like(g, decay), jnp.full_like(beta, 2.0)
    with jax.default_matmul_precision("highest"):
        got = N.gdn_chunked(q, k, v, g, beta)
        assert bool(jnp.isfinite(got).all())
        assert _err(got, _recurrence(q, k, v, g, beta)) < 1e-5
        grads = jax.grad(lambda *a: jnp.sum(N.gdn_chunked(*a)),
                         argnums=tuple(range(5)))(q, k, v, g, beta)
    assert all(bool(jnp.isfinite(a).all()) for a in grads)


def test_heads_that_hardly_decay_beside_heads_that_forget_at_once():
    q, k, v, g, beta = _inputs(5)
    g = jnp.asarray([-1e-4, -30.0, 0.0]) * jnp.ones_like(g)
    with jax.default_matmul_precision("highest"):
        assert _err(N.gdn_chunked(q, k, v, g, beta),
                    _recurrence(q, k, v, g, beta)) < 1e-5


@pytest.mark.parametrize("chunk", [64, 16, 24])
def test_kda_fed_the_same_decay_on_every_channel_agrees(chunk):
    """`kda_chunked` with the head's decay broadcast over the dk channels
    computes the same quantity through its sub-blocks; values and the
    gradients of the decay agree."""
    q, k, v, g, beta = _inputs(6)
    wide = lambda g: jnp.broadcast_to(g[..., None], (*g.shape, DK))
    with jax.default_matmul_precision("highest"):
        got = N.gdn_chunked(q, k, v, g, beta, chunk=chunk)
        want = N.kda_chunked(q, k, v, wide(g), beta, chunk=chunk)
        assert _err(got, want) < 1e-5
        dg = jax.grad(lambda g: jnp.sum(N.gdn_chunked(
            q, k, v, g, beta, chunk=chunk) ** 2))(g)
        dg_kda = jax.grad(lambda g: jnp.sum(N.kda_chunked(
            q, k, v, wide(g), beta, chunk=chunk) ** 2))(g)
    assert _err(dg, dg_kda) < 1e-4


def test_no_decay_and_beta_one_is_the_plain_delta_rule():
    """g = 0, beta = 1 and orthonormal keys: the state stores each value
    under its key, so a query equal to a stored key reads its value back."""
    rs = onp.random.RandomState(8)
    keys = jnp.asarray(onp.linalg.qr(rs.randn(DK, DK))[0], jnp.float32)
    k = jnp.broadcast_to(keys[None, :, None, :], (1, DK, 1, DK))
    v = jnp.asarray(rs.randn(1, DK, 1, DV), jnp.float32)
    zeros, ones = jnp.zeros((1, DK, 1)), jnp.ones((1, DK, 1))
    with jax.default_matmul_precision("highest"):
        o = N.gdn_chunked(k, k, v, zeros, ones, chunk=4)
    assert _err(o, v) < 1e-5


def test_the_route_counter_counts_its_own_name():
    snap = lambda: dict(telemetry.raw_snapshot()["counters"])
    before = snap()
    N.gdn_chunked(*_inputs())
    after = snap()
    assert after["dispatch.gdn.xla_chunked"] \
        == before.get("dispatch.gdn.xla_chunked", 0) + 1
    assert after.get("dispatch.kda.xla_chunked", 0) \
        == before.get("dispatch.kda.xla_chunked", 0)
