"""The trainable causal grouped-query attention kernels
(`pallas_kernels.causal_gqa_attention_fused`: `mx_causal_attn_fwd`,
`mx_causal_attn_bwd`) in interpret mode on the CPU, against the plain
masked softmax and against the two-scan composition they stand in for,
and the routing rule of `ops/nn.py::causal_gqa_attention`.  That they
compile for the chip, and what the compiled step holds, is
tests/test_chip_compile.py."""
import math

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops import pallas_block
from mxnet_tpu.ops import pallas_kernels as pk

# The kernels round their MXU operands (q·scale, k, v, g, p, ds) to
# bfloat16 as XLA's DEFAULT precision does on the chip; the CPU's
# references keep float32.  The tolerance is the one of
# tests/test_pallas_rtc.py's attention tests: 2e-2 of the largest value.
_RTOL_BF16_OPERANDS = 2e-2


def _close(got, want):
    err = float(jnp.abs(got - want).max())
    assert err <= _RTOL_BF16_OPERANDS * float(jnp.abs(want).max()), err


def _plain(q, k, v):
    t, h, g = q.shape[1], q.shape[2], k.shape[2]
    kk, vv = jnp.repeat(k, h // g, axis=2), jnp.repeat(v, h // g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


def _inputs(heads, kv, t, hd=128, batch=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + 7 * t + heads), 4)
    return (jax.random.normal(ks[0], (batch, t, heads, hd)),
            jax.random.normal(ks[1], (batch, t, kv, hd)),
            jax.random.normal(ks[2], (batch, t, kv, hd)),
            jax.random.normal(ks[3], (batch, t, heads, hd)))


def _fused(blocks):
    def fn(q, k, v):
        b, t, heads, hd = q.shape
        kv = k.shape[2]
        return pk.causal_gqa_attention_fused(
            q.reshape(b, t, heads * hd), k.reshape(b, t, kv * hd),
            v.reshape(b, t, kv * hd), heads, kv, blocks).reshape(q.shape)
    return fn


def _out_and_grads(fn, q, k, v, w):
    return (fn(q, k, v),) + jax.grad(
        lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)


# (Hq, Hkv, T, (block_q, block_k) or None for the kernel's own choice).
# With block_k > block_q and with T = block: the last key block of a row
# of blocks is the diagonal one and nothing lies below it; with
# block_k < block_q several key blocks cross the diagonal.
CASES = [
    (16, 1, 256, None),            # one block: (256, 256)
    (16, 1, 512, (128, 256)),
    (4, 2, 256, (128, 128)),
    (4, 2, 512, (256, 128)),
    (4, 2, 1024, None),            # (512, 512)
    (2, 2, 512, (128, 512)),
    (2, 2, 1024, (256, 512)),
]


@pytest.mark.parametrize("heads,kv,t,blocks", CASES)
def test_kernel_pair_is_the_plain_causal_attention_and_the_composition(
        monkeypatch, heads, kv, t, blocks):
    """o, dq, dk, dv of the kernel pair against (a) the plain masked
    softmax and (b) `causal_gqa_attention`'s two-scan composition (what
    the CPU's route is)."""
    q, k, v, w = _inputs(heads, kv, t)
    plain = _out_and_grads(_plain, q, k, v, w)
    telemetry.reset()
    composed = _out_and_grads(ops.causal_gqa_attention, q, k, v, w)
    assert telemetry.raw_snapshot()["counters"][
        "dispatch.attention.causal.xla_blocked"] >= 1
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = _out_and_grads(_fused(blocks), q, k, v, w)
    for a, b, c in zip(got, plain, composed):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.isfinite(a).all())
        _close(a, b)
        _close(a, c)


def test_kernel_blocks_divide_the_length_and_spans_stop_at_the_diagonal():
    assert pk._causal_blocks(8192) == (512, 512)
    assert pk._causal_blocks(1024) == (512, 512)
    assert pk._causal_blocks(768) == (384, 384)
    assert pk._causal_blocks(640) == (128, 128)
    assert pk._causal_blocks(256) == (256, 256)
    # query rows 512..1023 against key blocks of 256: blocks 0 and 1 lie
    # wholly below row 512, blocks 2 and 3 cross the diagonal
    assert pk._causal_span(512, 512, 256) == (2, 4)
    assert pk._causal_span(0, 128, 512) == (0, 1)
    assert pk._causal_span(384, 128, 512) == (0, 1)
    assert pk._causal_span(512, 128, 512) == (1, 2)


def test_first_row_attends_to_one_key_and_its_lse_is_finite(monkeypatch):
    """Row 0 sees key 0 alone: its output is v[0] (as the MXU reads it)
    and its log-sum-exp is that one score, whatever the block holds."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, _ = _inputs(4, 2, 256)
    b, t, heads, hd = q.shape
    o, lse = pk._causal_fwd_pallas(
        q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
        heads, 2, (128, 128))
    assert lse.shape == (b, heads, 1, t) and lse.dtype == jnp.float32
    assert bool(jnp.isfinite(lse).all())
    bf = jnp.bfloat16
    first = jnp.einsum(
        "hd,hd->h", (q[0, 0] * hd ** -0.5).astype(bf).astype(jnp.float32),
        jnp.repeat(k[0, 0], 2, axis=0).astype(bf).astype(jnp.float32))
    assert jnp.allclose(lse[0, :, 0, 0], first, atol=1e-4)
    assert jnp.allclose(
        o.reshape(q.shape)[0, 0],
        jnp.repeat(v[0, 0], 2, axis=0).astype(bf).astype(jnp.float32),
        atol=1e-6)
    # every row's lse is the log of its row sum of the plain scores
    kk = jnp.repeat(k, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    _close(lse[:, :, 0], jax.nn.logsumexp(s, axis=-1))


def test_a_batch_and_bfloat16_arrays_keep_their_shape_and_dtype(monkeypatch):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, w = (a.astype(jnp.bfloat16) for a in _inputs(2, 1, 256, batch=2))
    got = _out_and_grads(ops.causal_gqa_attention, q, k, v, w)
    want = _out_and_grads(_plain, *(a.astype(jnp.float32)
                                    for a in (q, k, v, w)))
    for a, b, like in zip(got, want, (q, q, k, v)):
        assert a.shape == like.shape and a.dtype == jnp.bfloat16
        err = float(jnp.abs(a.astype(jnp.float32) - b).max())
        assert err <= 4e-2 * float(jnp.abs(b).max()), err


# ------------------------------------------------------------------ routing
def _dispatch():
    return {k[len("dispatch."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.") and v}


def test_the_route_takes_the_call_where_the_shapes_allow(monkeypatch):
    """Interpret switch on (what `one_tpu()` is on the chip), head_dim
    128, whole groups, T in 128s: the kernels, counted once a trace of the
    forward — under `jax.grad` too — and the composition not at all."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, w = _inputs(4, 2, 256)
    telemetry.reset()
    out = jax.jit(ops.causal_gqa_attention)(q, k, v)
    assert _dispatch() == {"pallas.hits.causal_attention.128": 1}
    _close(out, _plain(q, k, v))
    telemetry.reset()
    jax.jit(jax.grad(lambda *a: (ops.causal_gqa_attention(*a) * w).sum(),
                     argnums=(0, 1, 2)))(q, k, v)
    assert _dispatch() == {"pallas.hits.causal_attention.128": 1}


# name -> (interpret switch, one_tpu(), (Hq, Hkv, T, hd))
REFUSALS = {
    "head_dim-64": (True, False, (4, 2, 256, 64)),
    "length-not-in-128s": (True, False, (4, 2, 192, 128)),
    "no-single-tpu": (False, False, (4, 2, 256, 128)),
    "a-tpu-but-head_dim-8": (False, True, (4, 2, 64, 8)),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_a_refused_route_counts_it_and_is_the_composition(monkeypatch, why):
    """Each "no" counts one `fallbacks.causal_attention.<hd>` and the
    composition's own route a trace, emits no kernel, and returns bit for
    bit what the composition returns when nobody asks the kernel."""
    force, one_tpu, (heads, kv, t, hd) = REFUSALS[why]
    q, k, v, w = _inputs(heads, kv, t, hd)

    def run():
        return jax.jit(lambda *a: _out_and_grads(
            ops.causal_gqa_attention, *a))(q, k, v, w)

    with monkeypatch.context() as m:
        m.setattr(pk, "causal_attention_use_pallas", lambda *a: False)
        want = run()
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", force)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: one_tpu)
    telemetry.reset()
    got = run()
    # `_out_and_grads` traces the function twice: alone and under grad
    assert _dispatch() == {f"pallas.fallbacks.causal_attention.{hd}": 2,
                           "attention.causal.xla_blocked": 2}
    for a, b in zip(got, want):
        assert bool((a == b).all())


@pytest.mark.parametrize("t,heads,kv,hd,want", [
    (8192, 32, 2, 128, True),       # the Nemotron cell
    (4096, 32, 2, 256, True),
    (8192, 32, 2, 256, False),      # a K/V head no longer fits VMEM whole
    (128, 2, 2, 128, True),
    (16384, 32, 2, 128, False),
    (8192, 32, 3, 128, False),      # no whole groups
    (8192, 32, 2, 64, False),
    (8200, 32, 2, 128, False),
])
def test_the_routing_decision_reads_shapes_only(monkeypatch, t, heads, kv, hd,
                                                want):
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    assert pk.causal_attention_use_pallas(t, heads, kv, hd) is want
