"""The routed experts' products on a slot's live rows (`pallas_kernels.
slot_products` / `slot_products_vjp`: `mx_moe_live_fwd`, `mx_moe_live_bwd`,
`mx_moe_live_up_grad`, `mx_moe_live_down_grad`) in interpret mode on the
CPU against products at the TPU's default precision (bfloat16 operands,
float32 sums), the routing rule `live_use_pallas` with its counters, and
`parallel/moe.py::moe_topk_held` through the kernels against its XLA route.
That they compile for the chip at the three cells' shapes is
tests/test_chip_compile.py."""
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops import pallas_block
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.moe import moe_topk_held

BF16, F32 = jnp.bfloat16, jnp.float32
ROWS = 384                      # a slot of three 128-row tiles
# relu2 on an expert width of no whole lane tiles (up's cotangent is then
# made transposed), swiglu on a fused (D, 2F) product
ACTS = {"relu2": (ops.relu2, 192, 192), "swiglu": (ops.swiglu, 128, 256)}
LIVE = [0, 1, 127, 128, 129, ROWS - 1, ROWS]


def _mm(a, b):
    """A product at the TPU's default precision."""
    return jnp.matmul(a.astype(BF16), b.astype(BF16),
                      preferred_element_type=F32)


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _dispatch():
    return {k[len("dispatch.pallas."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.pallas.") and v}


def _slot(act, live, d=256, count=3, seed=0):
    """A slot as `moe_topk_held` hands it over: `live` leading rows, the
    rest the gather's zeros (and weight 0); the held experts' stacks."""
    _, f, fu = ACTS[act]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    held = (jnp.arange(ROWS) < live)[:, None]
    xs = jax.random.normal(ks[0], (ROWS, d)) * held
    up = (jax.random.normal(ks[1], (count, d, fu)) * 0.1).astype(BF16)
    down = (jax.random.normal(ks[2], (count, f, d)) * 0.1).astype(BF16)
    w = jax.random.uniform(ks[3], (ROWS, 1)) * held
    dout = jax.random.normal(ks[4], (ROWS, d)) * held
    return xs, up, down, w, dout


def _tiles(live):
    """The rows of the tiles the kernels compute (one at least)."""
    return max(-(-live // 128), 1) * 128


def _want(act, e, xs, up, down, w, dout):
    """The slot's result and cotangents by XLA, rounded as the kernels
    round: the sorted weights' from ``dout downᵀ``, as theirs."""
    fn = ACTS[act][0]
    g = _mm(dout, down[e].T)
    h, back = jax.vjp(fn, _mm(xs, up[e]))
    dh = back(g * w)[0]
    return (_mm(h, down[e]) * w, _mm(dh, up[e].T), _mm(xs.T, dh),
            _mm(h.T, dout * w), jnp.sum(h * g, axis=1, keepdims=True))


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("live", LIVE)
def test_the_slot_s_result_is_its_default_precision_products(monkeypatch,
                                                             act, live):
    """Live rows on the output: ``act(xs up) down * w`` in the tiles that
    hold a live row, and zero in their rows past `live`."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    xs, up, down, w, dout = _slot(act, live)
    got = jax.jit(pk.slot_products, static_argnums=0)(
        ACTS[act][0], jnp.int32(1), jnp.int32(live), xs, up, down, w)
    want = _want(act, 1, xs, up, down, w, dout)[0]
    t = _tiles(live)
    assert got.shape == (ROWS, 256) and got.dtype == F32
    assert _err(got[:t], want[:t]) < 1e-6
    assert bool((got[live:t] == 0).all())


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("live", LIVE)
def test_the_slot_s_cotangents_are_its_default_precision_products(
        monkeypatch, act, live):
    """Live rows on the output (x's and the sorted weights' cotangents) and
    on the reduction (the weights'): the expert's pair of the stacks is
    written in place and the others' are kept; with `add` the slot's is
    added onto what was there."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    xs, up, down, w, dout = _slot(act, live)
    e = 1
    s_up, s_down = pk.weight_sums(up, down)
    sums = s_up.at[0].set(7.0), s_down.at[2].set(-3.0)
    vjp = jax.jit(pk.slot_products_vjp, static_argnums=(0, 9))
    args = (ACTS[act][0], jnp.int32(e), jnp.int32(live), xs, up, down, w,
            dout)
    dxs, *got, dw = vjp(*args, sums, False)
    d_up, d_down = pk.weights_of(got, up)
    _, want_dxs, want_up, want_down, want_dw = _want(act, e, xs, up, down, w,
                                                      dout)
    t = _tiles(live)
    assert _err(dxs[:t], want_dxs[:t]) < 1e-6 and _err(dw[:t],
                                                       want_dw[:t]) < 1e-6
    assert bool((dxs[live:t] == 0).all()) and bool((dw[live:t] == 0).all())
    assert d_up.shape == up.shape and d_down.shape == down.shape
    assert _err(d_up[e], want_up) < 1e-5 and _err(d_down[e], want_down) < 1e-5
    assert bool((d_up[0] == 7.0).all()) and bool((d_up[2] == 0).all())
    assert bool((d_down[2] == -3.0).all()) and bool((d_down[0] == 0).all())
    _, *again, _ = vjp(*args, got, True)
    d_up2, d_down2 = pk.weights_of(again, up)
    assert _err(d_up2[e], 2 * want_up) < 1e-5
    assert _err(d_down2[e], 2 * want_down) < 1e-5
    assert bool((d_up2[0] == 7.0).all())


def test_a_tile_wholly_past_the_live_rows_is_never_read(monkeypatch):
    """NaN in every row of the tiles past the live ones changes nothing:
    no product, epilogue or sum reads them."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    live = 130                                   # tiles 0 and 1
    xs, up, down, w, dout = _slot("swiglu", live)
    dead = jnp.arange(ROWS)[:, None] >= 256
    nan = lambda a: jnp.where(dead, jnp.nan, a)
    fn = ops.swiglu
    n, e = jnp.int32(live), jnp.int32(2)
    sums = pk.weight_sums(up, down)
    clean = pk.slot_products(fn, e, n, xs, up, down, w), \
        pk.slot_products_vjp(fn, e, n, xs, up, down, w, dout, sums, False)
    dirty = pk.slot_products(fn, e, n, nan(xs), up, down, nan(w)), \
        pk.slot_products_vjp(fn, e, n, nan(xs), up, down, nan(w),
                             nan(dout), sums, False)
    assert bool((dirty[0][:256] == clean[0][:256]).all())
    for a, b in zip(dirty[1], clean[1]):
        assert bool((a[:256] == b[:256]).all())
    for a, b in zip(dirty[1][1:3], clean[1][1:3]):    # the weights', whole
        assert bool((a == b).all())


@pytest.mark.parametrize("force,one_tpu,rows,d,f,fu,dtype,want", [
    (False, True, 5120, 2048, 1024, 2048, F32, True),   # the Trinity cell
    (False, True, 1024, 4096, 1280, 2560, F32, True),   # the Solar cell
    (False, True, 2304, 2688, 1856, 1856, F32, True),   # Nemotron: F whole
    (True, False, 128, 128, 64, 64, F32, True),         # the tests' switch
    (False, False, 5120, 2048, 1024, 2048, F32, False),  # a mesh, the CPU
    (False, True, 5120, 2048, 1024, 2048, BF16, False),  # packed rows
    (False, True, 5120, 2000, 1024, 2048, F32, False),   # no whole lane blocks
    (False, True, 5000, 2048, 1024, 2048, F32, False),   # no whole row tiles
    (False, True, 2304, 2688, 928, 1856, F32, False),    # halves off the lanes
    (False, True, 1024, 8192, 2048, 4096, F32, False),   # weights past VMEM
])
def test_the_routing_decision_reads_shapes_only(monkeypatch, force, one_tpu,
                                                rows, d, f, fu, dtype, want):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", force)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: one_tpu)
    telemetry.reset()
    assert pk.live_use_pallas(rows, d, f, fu, dtype) is want
    assert _dispatch() == {
        f"{'hits' if want else 'fallbacks'}.moe_live.{d}": 1}


def _layer(act, s=256, d=128, seed=0):
    _, f, fu = ACTS[act]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (s, d)),
            jax.random.normal(ks[1], (16, d)) * 0.2,
            jax.random.normal(ks[2], (4, d, fu)) * 0.2,
            jax.random.normal(ks[3], (4, f, d)) * 0.2,
            jax.random.normal(ks[4], (s, d)))


ROUTINGS = {
    "as_routed": jnp.zeros(16),
    # expert 5 is sent every token (two slots of 128), expert 4 none
    "further_slots": jnp.zeros(16).at[5].set(9.0).at[4].set(-9.0),
}


@pytest.mark.parametrize("act", sorted(ACTS))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_layer_through_the_kernels_is_its_xla_route(monkeypatch, act,
                                                       routing):
    """Forward and the gradients of x, the router weight, up and down with
    the products on the live tiles (slots of 128 rows of 128 float32)
    against XLA's route with its products rounded as the TPU's default
    precision rounds them (on the CPU XLA multiplies float32 exactly, which
    moves these gradients by 3-6 %): the result to the last bits, the
    gradients to the rounding of the cotangents' bfloat16 operands, which
    the two routes take at different points (2^-8 each); and the route is
    counted."""
    x, rw, up, down, gy = _layer(act)
    bias = ROUTINGS[routing]

    def rounded(act, xs, e_up, e_down, w):
        return _mm(act(_mm(xs, e_up)), e_down) * w[:, None]

    def run():
        # a new function a route: jit's cache does not see the switch
        def held(x, rw, up, down):
            y, load = moe_topk_held(x, rw, bias, up, down, (4, 4), 3, 2.5,
                                    act=ACTS[act][0], slot_rows=128)
            return jnp.sum(jnp.tanh(y) * gy), (y, load)
        return jax.jit(jax.grad(held, (0, 1, 2, 3), has_aux=True))(
            x, rw, up, down)

    telemetry.reset()
    with monkeypatch.context() as m:
        m.setattr(moe, "_slot_products", rounded)
        xla_grads, (xla, _) = run()
    assert _dispatch()["fallbacks.moe_live.128"] >= 1
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    telemetry.reset()
    grads, (got, load) = run()
    assert _dispatch()["hits.moe_live.128"] >= 1
    assert "fallbacks.moe_live.128" not in _dispatch()
    if routing == "further_slots":
        assert int(load[5]) == 256 and int(load[4]) == 0
    assert _err(got, xla) < 1e-6
    for g, a in zip(grads, xla_grads):
        assert bool(jnp.isfinite(g).all()) and _err(g, a) < 1e-2


def test_the_kernels_hold_the_gradient_s_products(monkeypatch):
    """With the kernels the gradient's program runs every product of a
    slot in them -- the first slot and the loop of further ones, forward
    and backward -- and no dot over a slot's rows is left to XLA."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    x, rw, up, down, gy = _layer("swiglu", d=256)   # no width of 128 rows

    def held(x, rw, up, down):
        return jnp.sum(moe_topk_held(x, rw, jnp.zeros(16), up, down, (4, 4),
                                     3, 2.5, act=ops.swiglu,
                                     slot_rows=128)[0] * gy)

    kernels, dots = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn.params["name"])
                continue                    # what the kernel does inside
            if eqn.primitive.name == "dot_general":
                dots.extend(v.aval.shape for v in eqn.invars)
            for v in eqn.params.values():
                for j in v if isinstance(v, (tuple, list)) else (v,):
                    if hasattr(j, "eqns"):
                        walk(j)
                    elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                        walk(j.jaxpr)

    walk(jax.make_jaxpr(jax.grad(held, (0, 1, 2, 3)))(x, rw, up, down).jaxpr)
    for name in ("mx_moe_live_fwd", "mx_moe_live_bwd", "mx_moe_live_up_grad",
                 "mx_moe_live_down_grad"):
        assert kernels.count(name) == 2, (name, kernels)
    # the router's products are left, over all 256 tokens
    assert dots and not [shape for shape in dots if shape[0] == 128], dots
