"""The trainable causal grouped-query attention kernels
(`pallas_kernels.causal_gqa_attention_fused`: `mx_causal_attn_fwd`,
`mx_causal_attn_bwd`) in interpret mode on the CPU, against the plain
masked softmax and against the two-scan composition they stand in for,
and the routing rule of `ops/nn.py::causal_gqa_attention`; the same pair
with a static window (`mx_window_attn_fwd`, `mx_window_attn_bwd`) against
the masked softmax with the window as a mask.  That they compile for the
chip, and what the compiled step holds, is tests/test_chip_compile.py."""
import functools
import math
import re

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


def _plain(q, k, v, window=None):
    t, h, g = q.shape[1], q.shape[2], k.shape[2]
    kk, vv = jnp.repeat(k, h // g, axis=2), jnp.repeat(v, h // g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(q.shape[-1])
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None]
    seen = back >= 0 if window is None else (back >= 0) & (back < window)
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


def _inputs(heads, kv, t, hd=128, batch=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + 7 * t + heads), 4)
    return (jax.random.normal(ks[0], (batch, t, heads, hd)),
            jax.random.normal(ks[1], (batch, t, kv, hd)),
            jax.random.normal(ks[2], (batch, t, kv, hd)),
            jax.random.normal(ks[3], (batch, t, heads, hd)))


def _fused(blocks, window=None):
    def fn(q, k, v):
        b, t, heads, hd = q.shape
        kv = k.shape[2]
        return pk.causal_gqa_attention_fused(
            q.reshape(b, t, heads * hd), k.reshape(b, t, kv * hd),
            v.reshape(b, t, kv * hd), heads, kv, blocks,
            window).reshape(q.shape)
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


# ------------------------------------------------------------- the window
# (Hq, Hkv, T, (block_q, block_k) or None, window): T not a multiple of the
# window; a window smaller than, equal to and larger than a block; three keys;
# 8 query heads a key-value head; a window that is no multiple of anything.
WINDOWED = [
    (4, 2, 640, (128, 128), 200),
    (8, 1, 512, (128, 128), 128),
    (2, 2, 512, (128, 256), 64),
    (2, 2, 768, (256, 128), 300),
    (2, 1, 1024, None, 384),       # the kernel's own blocks: (256, 256)
    (2, 2, 512, (128, 128), 129),
    (2, 2, 256, (128, 128), 3),
]


@pytest.mark.parametrize("heads,kv,t,blocks,window", WINDOWED)
def test_windowed_pair_is_the_masked_softmax_and_the_composition(
        monkeypatch, heads, kv, t, blocks, window):
    """o, dq, dk, dv of `mx_window_attn_*` against (a) the plain softmax
    with the window as a mask and (b) `causal_gqa_attention(window=)`'s
    composition (the CPU's route, given the same window)."""
    q, k, v, w = _inputs(heads, kv, t)
    plain = _out_and_grads(functools.partial(_plain, window=window),
                           q, k, v, w)
    telemetry.reset()
    composed = _out_and_grads(functools.partial(
        ops.causal_gqa_attention, q_block=128, k_block=128, window=window),
        q, k, v, w)
    counters = telemetry.raw_snapshot()["counters"]
    assert counters["dispatch.attention.window.xla_blocked"] >= 1
    assert counters["dispatch.pallas.fallbacks.window_attention.128"] >= 1
    assert not counters.get("dispatch.attention.causal.xla_blocked")
    for a, b in zip(composed, plain):        # float32 on the CPU: tight
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(jnp.abs(b).max())
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = _out_and_grads(_fused(blocks, window), q, k, v, w)
    for a, b in zip(got, plain):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bool(jnp.isfinite(a).all())
        _close(a, b)


@pytest.mark.parametrize("window", [512, 513, 4096])
def test_a_window_of_the_whole_length_is_the_causal_kernel(monkeypatch,
                                                           window):
    """W >= T masks nothing the diagonal does not: the causal pair's result
    at the same blocks (the windowed loop takes the unmasked key blocks two
    at a time, so a row's running maximum moves at other tiles and its
    probabilities are rounded to bfloat16 from other exponents: the
    tolerance is that rounding's, a quarter of `_close`'s)."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, w = _inputs(4, 2, 512)
    want = _out_and_grads(_fused((128, 128)), q, k, v, w)
    got = _out_and_grads(_fused((128, 128), window), q, k, v, w)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) <= 5e-3 * float(jnp.abs(b).max())


def test_the_window_s_edge_is_exact(monkeypatch):
    """Row i sees key i - (W - 1) and not key i - W: with one far key's
    value made huge, only the rows within the window of it move."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, _ = _inputs(2, 2, 512)
    window, at = 130, 100
    base = _fused((128, 128), window)(q, k, v)
    moved = _fused((128, 128), window)(q, k, v.at[0, at].add(1e3))
    rows = jnp.nonzero(jnp.abs(moved - base).max(axis=(0, 2, 3)) > 0)[0]
    assert rows.tolist() == list(range(at, at + window))


def test_window_spans_and_tiles_follow_the_band():
    # rows 1024..1279 with a window of 512 against key blocks of 256: keys
    # 513..1279, blocks 2..4; block 2 holds keys too far back for row 1279
    # (masked), block 3 none, block 4 crosses the diagonal
    assert tuple(int(n) for n in pk._window_span(1024, 256, 256, 512)) \
        == (2, 3, 4, 5)
    # no leading edge while the window reaches back to key 0
    assert tuple(int(n) for n in pk._window_span(256, 256, 256, 512)) \
        == (0, 0, 1, 2)
    # a window shorter than a block: row 512 reaches back into block 3,
    # whose mask the leading edge applies; it stops at the diagonal block
    assert tuple(int(n) for n in pk._window_span(512, 128, 128, 64)) \
        == (3, 4, 4, 5)
    # the cell: 8192 rows, a window of 2048, blocks of 256: 9 key blocks a
    # query block (2048 + 255 keys) against 1..32
    assert pk._causal_blocks(8192, pk._WINDOW_BLOCK) == (256, 256)
    visited, causal = pk.window_tiles(8192, 2048)
    assert (visited, causal) == (36 + 24 * 9, 32 * 33 // 2)
    assert visited / causal < 0.5
    assert pk.window_tiles(8192, 8192) == (causal, causal)
    assert pk.window_tiles(512, 64, (128, 128)) == (1 + 3 * 2, 10)


def test_the_windowed_route_counts_its_hits_and_its_tiles(monkeypatch):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    q, k, v, w = _inputs(4, 2, 512)
    telemetry.reset()
    out = jax.jit(functools.partial(ops.causal_gqa_attention, window=200))(
        q, k, v)
    visited, causal = pk.window_tiles(512, 200)
    assert _dispatch() == {"pallas.hits.window_attention.128": 1}
    counters = telemetry.raw_snapshot()["counters"]
    assert counters["attn.window.tiles_visited"] == 4 * visited
    assert counters["attn.window.tiles_causal"] == 4 * causal
    _close(out, _plain(q, k, v, 200))
    telemetry.reset()
    jax.jit(ops.causal_gqa_attention)(q, k, v)
    assert _dispatch() == {"pallas.hits.causal_attention.128": 1}
    assert not telemetry.raw_snapshot()["counters"].get(
        "attn.window.tiles_visited")


def test_without_a_window_the_entry_traces_to_the_causal_kernels(monkeypatch):
    """`window=None` is a Python branch: the traced program holds the
    kernels `mx_causal_attn_fwd` / `_bwd` on the (B, Hkv, Hq/Hkv, T/512)
    grid, nothing windowed, and is the program that leaving the argument
    out gives; with a window the names and the grid (blocks of 256) are the
    windowed pair's."""
    monkeypatch.setattr(pallas_block, "interpret", lambda: False)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.float32)

    def traced(**kw):
        text = str(jax.make_jaxpr(jax.grad(lambda a, b, c: jnp.sum(
            ops.causal_gqa_attention(a, b, c, **kw) ** 2),
            argnums=(0, 1, 2)))(q, kv, kv))
        return text, re.findall(r"name=(mx_\w+)", text), \
            re.findall(r"grid=\(([^)]*)\)", text)

    plain, names, grids = traced()
    assert traced(window=None)[0] == plain
    assert names == ["mx_causal_attn_fwd", "mx_causal_attn_bwd"]
    assert grids == ["1, 2, 2, 2"] * 2
    assert "clip" not in plain               # no window's span is computed
    _, names, grids = traced(window=300)
    assert names == ["mx_window_attn_fwd", "mx_window_attn_bwd"]
    assert grids == ["1, 2, 2, 4"] * 2
