"""The delta rule's chunk-state kernels (`pallas_kernels.delta_rule_fused`:
`mx_delta_rule_fwd`, `mx_delta_rule_bwd`) in interpret mode on the CPU,
against the `lax.scan` they stand in for (`ops/nn.py::_delta_states_scan`)
on the same six arrays — the output and all six cotangents — and through
`gdn_chunked` / `kda_chunked`, and the routing rule of
`ops/nn.py::_delta_rule_chunked`.  That they compile for the chip at the
cells' shapes is tests/test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops import pallas_block
from mxnet_tpu.ops import pallas_kernels as pk

# The kernels round their MXU operands (the six arrays' tiles, the state, u
# and the cotangents) to bfloat16 as XLA's DEFAULT precision does on the
# chip; the CPU's composition keeps float32.  One rounding is 2^-9 of a
# value: a norm of the error of 1e-2 of the reference's norm holds a few.
_RTOL_BF16_OPERANDS = 1e-2
CHUNK = pk._DELTA_CHUNK
XS = ("u0", "w", "qg", "a_qk", "k_end", "g_end")
NAMES = ("q", "k", "v", "g", "beta")


def _close(got, want, rtol=_RTOL_BF16_OPERANDS, atol=0.0):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.linalg.norm((got - want).astype(jnp.float32)))
    norm = float(jnp.linalg.norm(want.astype(jnp.float32)))
    assert err <= rtol * norm + atol, (err, norm)


def _dispatch():
    """The routes counted: `dispatch.pallas.*` and the operators' own."""
    return {k[len("dispatch."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith(("dispatch.pallas.", "dispatch.gdn.",
                             "dispatch.kda.")) and v}


# ----------------------------------------------- the recurrence's six arrays
# name -> (B, H, nc, dk, dv, a decay a channel)
TILES = {
    "a-decay-a-head-24-48": (1, 3, 3, 24, 48, False),
    "a-decay-a-channel-24-48": (1, 3, 3, 24, 48, True),
    "a-decay-a-head-96-192": (1, 2, 2, 96, 192, False),     # Olmo's widths
    "a-decay-a-channel-128-128": (1, 2, 3, 128, 128, True),
    "a-batch-of-two": (2, 2, 2, 24, 48, True),
    "one-chunk": (1, 2, 1, 24, 48, False),
}


def _tiles(case, seed=0):
    """u0, w, qg, a_qk, k_end, g_end as `_delta_rule_chunked` makes them in
    size (w and k_end below one a row, a_qk lower triangular, g_end in
    (0, 1)) and a cotangent of o."""
    bsz, h, nc, dk, dv, channel = TILES[case]
    rs = onp.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rs.randn(bsz, h, nc, *s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    a_qk = jnp.tril(draw(CHUNK, CHUNK)) * dk ** -0.5
    g_end = jnp.exp(-jnp.exp(draw(dk if channel else 1)))
    return (draw(CHUNK, dv), unit(draw(CHUNK, dk)),
            unit(draw(CHUNK, dk)) * dk ** -0.5, a_qk, unit(draw(CHUNK, dk)),
            g_end), draw(CHUNK, dv)


def _fused(u0, w, *rest):
    """The kernels read w and u0 out of one array, [w | u0], as the
    triangular solve leaves them: the concatenation's own cotangent splits
    `dwu` back into the scan's two."""
    return pk.delta_rule_fused("kda", jnp.concatenate([w, u0], axis=-1),
                               *rest)


def _grads(fn, xs, weight):
    return jax.jit(jax.grad(lambda *a: (fn(*a) * weight).sum(),
                            argnums=tuple(range(len(xs)))))(*xs)


@pytest.mark.parametrize("case", sorted(TILES))
def test_forward_kernel_is_the_scan(monkeypatch, case):
    xs, _ = _tiles(case)
    want = jax.jit(ops._delta_states_scan)(*xs)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    telemetry.reset()
    got = jax.jit(_fused)(*xs)
    assert _dispatch() == {f"pallas.hits.kda.{TILES[case][3]}": 1}
    _close(got, want)


@pytest.mark.parametrize("case", sorted(TILES))
def test_kernel_pair_gives_the_scan_s_six_cotangents(monkeypatch, case):
    """Through several chunks: the state's cotangent walks them in reverse.
    The decay's cotangent is a sum over a whole (dk, dv) state (one number
    a head and chunk where the decay is one a head)."""
    xs, weight = _tiles(case, seed=1)
    want = _grads(ops._delta_states_scan, xs, weight)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = _grads(_fused, xs, weight)
    assert len(got) == 6
    for name, a, b in zip(XS, got, want):
        assert a.shape == b.shape, name
        _close(a, b)


def _blocks(dk, dv, backward):
    """The arrays a forward (emitting states) or a backward call blocks:
    only their last two axes count."""
    shape = lambda *s: jax.ShapeDtypeStruct((1, 1, 1) + s, jnp.float32)
    tiles = [shape(CHUNK, dk + dv), shape(CHUNK, dk), shape(CHUNK, CHUNK),
             shape(CHUNK, dk), shape(1, dk)]
    states, o = shape(dv, dk), shape(CHUNK, dv)
    return tiles + [states, o] + (tiles if backward else [])


def test_several_heads_a_step_or_one_give_the_same(monkeypatch):
    """The heads of a grid step are independent chains: how many share a
    step (a VMEM budget decides) changes no number."""
    xs, weight = _tiles("a-decay-a-channel-24-48")
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    assert pk._delta_heads_a_step(3, _blocks(24, 48, True)) == 3
    many = _grads(_fused, xs, weight) + (jax.jit(_fused)(*xs),)
    monkeypatch.setattr(pk, "_DELTA_VMEM", 1)
    assert pk._delta_heads_a_step(3, _blocks(24, 48, False)) == 1
    one = _grads(lambda *a: _fused(*a), xs, weight) + \
        (jax.jit(lambda *a: _fused(*a))(*xs),)
    for a, b in zip(many, one):
        assert bool((a == b).all())


def test_heads_a_step_divide_the_heads_and_fit_the_budget():
    """At the cells' shapes: all of Olmo's 15 heads a forward step and 5 a
    backward step (its blocks are 1.5 times the forward's), all 8 of
    Solar's in both; lanes are counted in whole tiles, as VMEM holds them."""
    assert pk._delta_heads_a_step(15, _blocks(96, 192, False)) == 15
    assert pk._delta_heads_a_step(15, _blocks(96, 192, True)) == 5
    assert pk._delta_heads_a_step(8, _blocks(128, 128, False)) == 8
    assert pk._delta_heads_a_step(8, _blocks(128, 128, True)) == 8
    assert pk._delta_heads_a_step(7, _blocks(256, 256, True)) == 1


# ------------------------------------------------- through the two operators
def _inputs(kind, t, h, dk, dv, seed=0, bsz=1):
    rs = onp.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rs.randn(bsz, t, *s), jnp.float32)
    q = ops.l2_normalize(draw(h, dk)) * dk ** -0.5
    k = ops.l2_normalize(draw(h, dk))
    g = -jnp.exp(draw(h, dk) if kind == "kda" else draw(h))
    return (q, k, draw(h, dv), g, 2 * jax.nn.sigmoid(draw(h))), draw(h, dv)


OPS = {"gdn": ops.gdn_chunked, "kda": ops.kda_chunked}
# name -> (operator, T, H, dk, dv)
CASES = {
    "gdn-24-48": ("gdn", 192, 3, 24, 48),
    "kda-24-48": ("kda", 192, 2, 24, 48),
    "gdn-96-192": ("gdn", 128, 2, 96, 192),
    "kda-128-128": ("kda", 128, 2, 128, 128),
    "gdn-a-length-the-chunk-does-not-divide": ("gdn", 150, 2, 24, 48),
    "kda-a-length-the-chunk-does-not-divide": ("kda", 150, 2, 24, 48),
}


def _composed(monkeypatch, fn, *args):
    with monkeypatch.context() as m:
        m.setattr(pk, "delta_rule_use_pallas", lambda *a, **k: False)
        return fn(*args)


@pytest.mark.parametrize("case", sorted(CASES))
def test_operator_on_the_kernels_is_the_composition(monkeypatch, case):
    """Output and the gradients of q, k, v, g and beta, the pairwise products
    and the triangular solve being the composition's on both sides; a `T`
    the chunk does not divide is padded with steps that change nothing."""
    kind, t, h, dk, dv = CASES[case]
    args, weight = _inputs(kind, t, h, dk, dv)
    fn = OPS[kind]
    want = _composed(monkeypatch, jax.jit(lambda *a: fn(*a)), *args)
    want_g = _composed(monkeypatch, _grads, lambda *a: fn(*a), args, weight)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    telemetry.reset()
    got = jax.jit(lambda *a: fn(*a))(*args)
    assert _dispatch() == {f"pallas.hits.{kind}.{dk}": 1,
                           f"{kind}.xla_chunked": 1}
    assert got.shape == (1, t, h, dv)
    _close(got, want)
    telemetry.reset()
    got_g = _grads(lambda *a: fn(*a), args, weight)
    assert _dispatch() == {f"pallas.hits.{kind}.{dk}": 1,
                           f"{kind}.xla_chunked": 1}
    for name, a, b in zip(NAMES, got_g, want_g):
        assert a.shape == b.shape, name
        _close(a, b)


@pytest.mark.parametrize("kind", sorted(OPS))
@pytest.mark.parametrize("decay", [-20.0, -200.0])
def test_strong_decay_and_beta_two_stay_the_composition(monkeypatch, kind,
                                                        decay):
    """Every step forgets the state (exp(decay) underflows towards 0) and
    beta is 2, the edge of the delta rule's stable range: no exponent of
    the kernels is positive, nothing overflows, and the answer is the
    composition's."""
    (q, k, v, g, beta), weight = _inputs(kind, 128, 2, 24, 48, seed=3)
    args = (q, k, v, jnp.full_like(g, decay), jnp.full_like(beta, 2.0))
    fn = OPS[kind]
    want = _composed(monkeypatch, jax.jit(lambda *a: fn(*a)), *args)
    want_g = _composed(monkeypatch, _grads, lambda *a: fn(*a), args, weight)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    _close(jax.jit(lambda *a: fn(*a))(*args), want)
    got_g = _grads(lambda *a: fn(*a), args, weight)
    for name, a, b in zip(NAMES, got_g, want_g):
        # what reaches the decay is exp(decay) of the rest: at -20 a norm
        # of 1e-7 to 1e-8 beside the others' 4 to 200, float32 rounding of
        # either side; exactly zero on both sides at -200
        if name == "g":
            _close(a, b, rtol=1e-1, atol=1e-7)
        else:
            _close(a, b)


@pytest.mark.parametrize("kind", sorted(OPS))
def test_a_head_that_hardly_decays_beside_one_that_forgets_at_once(
        monkeypatch, kind):
    """Two heads of one grid step, log-decays -1e-4 and -30 a step: each
    head's state is its own scratch slice and each is held to the
    composition by itself."""
    (q, k, v, g, beta), weight = _inputs(kind, 192, 2, 24, 48, seed=4)
    rate = jnp.asarray([-1e-4, -30.0]).reshape(
        (1, 1, 2) + (1,) * (g.ndim - 3))
    args = (q, k, v, jnp.broadcast_to(rate, g.shape), beta)
    fn = OPS[kind]
    want = _composed(monkeypatch, jax.jit(lambda *a: fn(*a)), *args)
    want_g = _composed(monkeypatch, _grads, lambda *a: fn(*a), args, weight)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    got = jax.jit(lambda *a: fn(*a))(*args)
    got_g = _grads(lambda *a: fn(*a), args, weight)
    for head in range(2):
        _close(got[:, :, head], want[:, :, head])
        for name, a, b in zip(NAMES[:3], got_g, want_g):
            _close(a[:, :, head], b[:, :, head])


# ------------------------------------------------------------------ routing
# name -> (interpret switch, one_tpu(), chunk)
REFUSALS = {
    "the-cpu-without-the-switch": (False, False, CHUNK),
    "a-mesh": (False, False, CHUNK),
    "a-chunk-that-is-not-the-kernel-s": (True, True, 32),
    "a-tpu-but-another-chunk": (False, True, 16),
}


@pytest.mark.parametrize("kind", sorted(OPS))
@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_a_refused_route_counts_it_and_is_the_composition(monkeypatch, kind,
                                                          why):
    """Each "no" counts one `fallbacks.<kind>.<dk>` beside the operator's
    own `xla_chunked`, emits no kernel, and returns bit for bit what the
    composition returns when nobody asks the kernel."""
    force, one_tpu, chunk = REFUSALS[why]
    args, _ = _inputs(kind, 128, 2, 24, 48)
    fn = OPS[kind]

    def run():
        return jax.jit(lambda *a: fn(*a, chunk=chunk))(*args)

    want = _composed(monkeypatch, run)
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", force)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: one_tpu)
    if why == "a-mesh":                   # what one_tpu() reads: not one TPU
        monkeypatch.undo()
        assert len(jax.devices()) > 1 and not pallas_block.one_tpu()
    telemetry.reset()
    got = run()
    assert _dispatch() == {f"pallas.fallbacks.{kind}.24": 1,
                           f"{kind}.xla_chunked": 1}
    assert bool((got == want).all())


@pytest.mark.parametrize("t,heads,dk,dv,chunk,want", [
    (8192, 15, 96, 192, 64, True),          # the Olmo cell
    (4096, 8, 128, 128, 64, True),          # the Solar cell
    (4096, 8, 128, 128, 128, False),        # another chunk
    (64, 1, 8, 8, 64, True),                # whole sublane tiles do
    (640, 7, 256, 256, 64, True),
    (640, 7, 384, 256, 64, False),          # a state past the VMEM budget
    (640, 7, 256, 264, 64, False),
    (640, 7, 12, 24, 64, False),            # no whole sublane tiles
    (640, 7, 24, 20, 64, False),
])
def test_the_routing_decision_reads_shapes_only(monkeypatch, t, heads, dk, dv,
                                                chunk, want):
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    telemetry.reset()
    assert pk.delta_rule_use_pallas(t, heads, dk, dv, chunk, "gdn") is want
    assert _dispatch() == ({} if want else {f"pallas.fallbacks.gdn.{dk}": 1})
