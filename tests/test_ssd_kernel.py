"""The trainable Mamba-2 scan kernels (`pallas_kernels.ssd_fused`:
`mx_ssd_fwd`, `mx_ssd_bwd`) in interpret mode on the CPU, against the
token-by-token recurrence and against the chunked composition they stand in
for, and the routing rule of `ops/nn.py::ssd_chunked`.  That they compile
for the chip at the cell's shape is tests/test_chip_compile.py."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops import pallas_block
from mxnet_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "nemotron_ref_ssd",
    os.path.join(REPO, "chipbench", "reference", "nemotron_h.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# The kernels round their MXU operands (x, b, c, m, the state, the
# cotangents) to bfloat16 as XLA's DEFAULT precision does on the chip; the
# CPU's references keep float32.  One rounding is 2^-9 of a value: a norm
# of the error of 1e-2 of the reference's norm holds a few of them.
_RTOL_BF16_OPERANDS = 1e-2


def _close(got, want, rtol=_RTOL_BF16_OPERANDS):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.linalg.norm((got - want).astype(jnp.float32)))
    assert err <= rtol * float(jnp.linalg.norm(want.astype(jnp.float32))), err


def _inputs(t, h, p, g, n, batch=1, d=True, seed=0):
    """x, dt, a, b, c, d as the mixer makes them (dt a softplus, a
    negative) and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed + t + 3 * h), 7)
    return (jax.random.normal(ks[0], (batch, t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, h)) - 1.0),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (batch, t, g, n)),
            jax.random.normal(ks[4], (batch, t, g, n)),
            jax.random.normal(ks[5], (h,)) if d else None), \
        jax.random.normal(ks[6], (batch, t, h, p))


def _recurrence(x, dt, a, b, c, d):
    if d is None:
        d = jnp.zeros_like(a)
    return jax.vmap(ref.ssm_recurrence,
                    in_axes=(0, 0, None, 0, 0, None))(x, dt, a, b, c, d)


def _fused(*args):
    x, dt, a, b, c, d = args
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    return pk.ssd_fused(x.reshape(bsz, t, h * p), dt, a,
                        b.reshape(bsz, t, g * n), c.reshape(bsz, t, g * n),
                        d, h, g).reshape(x.shape)


def _grads(fn, args, w):
    wrt = tuple(i for i, v in enumerate(args) if v is not None)
    return jax.jit(jax.grad(lambda *a: (fn(*a) * w).sum(), argnums=wrt))(*args)


# name -> (T, H, P, G, N, batch, d): chunks of 128 steps
CASES = {
    "one-chunk": (128, 2, 64, 1, 128, 1, True),
    "three-chunks-two-groups": (384, 4, 64, 2, 128, 1, True),
    "a-batch": (256, 2, 64, 1, 128, 2, True),
    "four-heads-a-block": (256, 8, 32, 2, 128, 1, True),
    "a-head-a-block": (256, 2, 128, 2, 128, 1, True),
    "eight-heads-a-group": (256, 8, 64, 1, 128, 1, True),
    "state-256": (256, 2, 64, 1, 256, 1, True),
    "no-d": (256, 4, 64, 2, 128, 1, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_kernel_is_the_recurrence_and_the_composition(monkeypatch,
                                                              case):
    t, h, p, g, n, batch, d = CASES[case]
    args, _ = _inputs(t, h, p, g, n, batch, d)
    telemetry.reset()
    composed = jax.jit(ops.ssd_chunked)(*args)
    assert telemetry.raw_snapshot()["counters"]["dispatch.ssm.xla_chunked"] \
        == 1
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = jax.jit(_fused)(*args)
    _close(got, jax.jit(_recurrence)(*args))
    _close(got, composed)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pair_gives_the_gradients_of_the_composition(monkeypatch,
                                                            case):
    """x, dt, a, b, c and d (where there is one), through several chunks:
    the carried state's cotangent walks them in reverse."""
    t, h, p, g, n, batch, d = CASES[case]
    args, w = _inputs(t, h, p, g, n, batch, d)
    want = _grads(ops.ssd_chunked, args, w)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = _grads(_fused, args, w)
    assert len(got) == (6 if d else 5)
    for i, (a, b) in enumerate(zip(got, want)):
        # a's cotangent is H sums over every step: a head's accumulated
        # rounding is not averaged over many entries (0.1 % to 2.3 % seen)
        _close(a, b, rtol=5e-2 if i == 2 else _RTOL_BF16_OPERANDS)


def test_decays_do_not_overflow_over_a_long_strongly_decaying_chunk(
        monkeypatch):
    """`test_scan_decays_do_not_overflow_…` of test_nemotron_h_ops.py for
    the kernels: every exponent is a difference that is not positive, the
    masked ones `_SSD_LOW`, in both passes."""
    (x, dt, a, b, c, d), w = _inputs(256, 2, 64, 1, 128)
    args = (x, dt * 50.0, a * 20.0, b, c, d)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = jax.jit(_fused)(*args)
    _close(got, jax.jit(_recurrence)(*args))
    for grad in _grads(_fused, args, w):
        assert bool(jnp.isfinite(grad).all())


def test_the_state_entering_every_chunk_is_the_recurrence_s(monkeypatch):
    """The residual the forward emits for the backward: S after the steps
    of all earlier chunks, transposed (N, H·P) and rounded to bfloat16;
    zero before the first."""
    (x, dt, a, b, c, d), _ = _inputs(384, 2, 64, 1, 128)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    (y, states), col, row = pk._ssd_fwd_pallas(
        x.reshape(1, 384, 128), dt, a, b.reshape(1, 384, 128),
        c.reshape(1, 384, 128), d, 2, 1, 128, emit=True)
    assert states.shape == (1, 3, 128, 128) and states.dtype == jnp.bfloat16
    assert col.shape == (1, 1, 384, 4) and row.shape == (1, 1, 4, 384)
    assert not bool(states[0, 0].any())

    def step(s, inp):
        x_t, dt_t, b_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, s
    _, every = jax.lax.scan(step, jnp.zeros((2, 64, 128)),
                            (x[0], dt[0], b[0, :, 0]))
    for k in (1, 2):
        want = every[k * 128 - 1].transpose(2, 0, 1).reshape(128, 128)
        _close(states[0, k].astype(jnp.float32), want)


def test_bfloat16_arrays_keep_their_shape_and_dtype(monkeypatch):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    args, w = _inputs(256, 2, 64, 1, 128)
    low = tuple(v.astype(jnp.bfloat16) for v in args)
    got = (jax.jit(ops.ssd_chunked)(*low),) + _grads(ops.ssd_chunked, low, w)
    args = tuple(v.astype(jnp.float32) for v in low)    # the same values
    want = (_recurrence(*args),) + _grads(_recurrence, args, w)
    for a, b, like in zip(got, want, (low[0],) + low):
        assert a.shape == like.shape and a.dtype == jnp.bfloat16
        _close(a.astype(jnp.float32), b, rtol=4e-2)


# ------------------------------------------------------------------ routing
def _dispatch():
    return {k[len("dispatch."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.") and v}


def test_the_route_takes_the_call_where_the_shapes_allow(monkeypatch):
    """Interpret switch on (what `one_tpu()` is on the chip), chunk 128
    dividing T, state 128, a group's heads 128 lanes: the kernels, counted
    once a trace of the forward — under `jax.grad` too — and the
    composition not at all."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    args, w = _inputs(256, 4, 64, 2, 128)
    telemetry.reset()
    out = jax.jit(ops.ssd_chunked)(*args)
    assert _dispatch() == {"pallas.hits.ssd.128": 1}
    _close(out, _recurrence(*args))
    telemetry.reset()
    _grads(ops.ssd_chunked, args, w)
    assert _dispatch() == {"pallas.hits.ssd.128": 1}


# name -> (interpret switch, one_tpu(), (T, H, P, G, N), chunk)
REFUSALS = {
    "state-8": (True, False, (32, 4, 8, 2, 8), 128),
    "chunk-64": (True, False, (128, 2, 64, 1, 128), 64),
    "length-not-in-chunks": (True, False, (192, 2, 64, 1, 128), 128),
    "a-group-of-64-lanes": (True, False, (128, 2, 64, 2, 128), 128),
    "no-single-tpu": (False, False, (128, 2, 64, 1, 128), 128),
    "a-tpu-but-head-48": (False, True, (128, 8, 48, 1, 128), 128),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_a_refused_route_counts_it_and_is_the_composition(monkeypatch, why):
    """Each "no" counts one `fallbacks.ssd.<n>` and then the composition's
    own route, emits no kernel, and returns bit for bit what the
    composition returns when nobody asks the kernel."""
    force, one_tpu, (t, h, p, g, n), chunk = REFUSALS[why]
    args, _ = _inputs(t, h, p, g, n)

    def run():
        return jax.jit(lambda *a: ops.ssd_chunked(*a, chunk=chunk))(*args)

    with monkeypatch.context() as m:
        m.setattr(pk, "ssd_use_pallas", lambda *a: False)
        want = run()
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", force)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: one_tpu)
    telemetry.reset()
    got = run()
    assert _dispatch() == {f"pallas.fallbacks.ssd.{n}": 1,
                           "ssm.xla_chunked": 1}
    assert bool((got == want).all())


@pytest.mark.parametrize("t,heads,groups,p,n,chunk,want", [
    (8192, 64, 8, 64, 128, 128, True),       # the Nemotron cell
    (8192, 64, 8, 64, 128, 256, False),      # another chunk
    (8192 + 64, 64, 8, 64, 128, 128, False),
    (128, 2, 1, 64, 128, 128, True),
    (128, 2, 1, 64, 64, 128, False),         # a state narrower than a tile
    (128, 3, 2, 128, 128, 128, False),       # no whole groups
    (128, 1, 1, 64, 128, 128, False),        # half a lane block
    (128, 8, 1, 48, 128, 128, False),        # heads straddle lane blocks
    (128, 2, 1, 256, 128, 128, True),
    (128, 64, 1, 64, 128, 128, False),       # a group of 4096 lanes
])
def test_the_routing_decision_reads_shapes_only(monkeypatch, t, heads, groups,
                                                p, n, chunk, want):
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    assert pk.ssd_use_pallas(t, heads, groups, p, n, chunk) is want
