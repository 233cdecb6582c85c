"""The operators Solar-Open2 added (ops/nn.py::kda_chunked, swiglu; the
SwiGLU form of parallel/moe.py::moe_topk_held) against plain recurrences
and loops at small sizes on the CPU: forward, the gradients of every input,
strong decays, the route counter, and the expert shares that add up to the
uncut block.  The model is in test_solar_open2.py (another file, so another
worker takes it)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as N
from mxnet_tpu.parallel.moe import moe_topk_held

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "kda_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "solar_open2.py")
nemotron_ref = _load("chipbench", "reference", "nemotron_h.py")
B, T, H, DK, DV = 2, 80, 3, 8, 12
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(seed=0, t=T):
    rs = onp.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    q = N.l2_normalize(draw(B, t, H, DK)) * DK ** -0.5
    k = N.l2_normalize(draw(B, t, H, DK))
    g = -jnp.exp(draw(B, t, H, DK))                  # per-channel log-decay
    beta = 2 * jax.nn.sigmoid(draw(B, t, H))
    return q, k, draw(B, t, H, DV), g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token recurrence, a sequence at a time."""
    return jax.vmap(ref.kda_recurrence)(q, k, v, g, beta)


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# a chunk of 64 = 4 sub-blocks of 16; 8 and 24: one sub-block a chunk;
# 24, 32 and 64 do not divide T = 80 (padded); 80: one chunk
@pytest.mark.parametrize("chunk", [64, 32, 16, 8, 24, 80])
def test_chunked_kda_is_the_recurrence(chunk):
    args = _inputs()
    with jax.default_matmul_precision("highest"):
        got = N.kda_chunked(*args, chunk=chunk)
        want = _recurrence(*args)
    assert got.shape == (B, T, H, DV)
    assert _err(got, want) < 1e-5


@pytest.mark.parametrize("wrt", range(5), ids=NAMES)
def test_chunked_kda_gradients_are_the_recurrence_s(wrt):
    args = _inputs(1)
    probe = jnp.asarray(onp.random.RandomState(2).randn(B, T, H, DV),
                        jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(N.kda_chunked(*a, chunk=32)
                                          * probe), argnums=wrt)(*args)
        want = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe),
                        argnums=wrt)(*args)
    assert _err(got, want) < 1e-4


def test_a_chunk_that_does_not_divide_t_is_padded_not_refused():
    """T = 70 in chunks of 32: the last 26 steps are padding (k = 0, beta =
    0, g = 0) that changes no state and is cut back."""
    args = _inputs(3, t=70)
    with jax.default_matmul_precision("highest"):
        got = N.kda_chunked(*args, chunk=32)
        assert got.shape == (B, 70, H, DV)
        assert _err(got, _recurrence(*args)) < 1e-5


def test_strong_decay_and_beta_two_stay_finite():
    """g = -20 a step (exp(-G) over a chunk would be exp(1280)) and beta =
    2: forward and all five gradients finite, and still the recurrence."""
    q, k, v, g, beta = _inputs(4)
    g, beta = jnp.full_like(g, -20.0), jnp.full_like(beta, 2.0)
    with jax.default_matmul_precision("highest"):
        got = N.kda_chunked(q, k, v, g, beta)
        assert bool(jnp.isfinite(got).all())
        assert _err(got, _recurrence(q, k, v, g, beta)) < 1e-5
        grads = jax.grad(lambda *a: jnp.sum(N.kda_chunked(*a)),
                         argnums=tuple(range(5)))(q, k, v, g, beta)
    assert all(bool(jnp.isfinite(a).all()) for a in grads)
    # mixed: some channels hardly decay, some at once
    g = jnp.where(jnp.arange(DK) % 2 == 0, -1e-4, -30.0) * jnp.ones_like(g)
    with jax.default_matmul_precision("highest"):
        assert _err(N.kda_chunked(q, k, v, g, beta),
                    _recurrence(q, k, v, g, beta)) < 1e-5


def test_the_route_counter_counts():
    before = telemetry.raw_snapshot()["counters"].get(
        "dispatch.kda.xla_chunked", 0)
    N.kda_chunked(*_inputs())
    assert telemetry.raw_snapshot()["counters"][
        "dispatch.kda.xla_chunked"] == before + 1


# ---------------------------------------------------------------- experts
D, F, E, K = 16, 12, 40, 4


def _experts(seed=5, s=48):
    rs = onp.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape) * 0.3, jnp.float32)
    return {"x": draw(s, D), "router_weight": draw(E, D),
            "correction_bias": jnp.zeros((E,)),
            "experts_up": draw(E, D, 2 * F), "experts_down": draw(E, F, D),
            "shared_up.weight": draw(2 * F, D),
            "shared_down.weight": draw(D, F)}


def test_swiglu_splits_the_fused_product():
    h = jnp.asarray(onp.random.RandomState(6).randn(5, 2 * F), jnp.float32)
    want = jax.nn.silu(h[:, :F]) * h[:, F:]
    assert _err(N.swiglu(h), want) < 1e-6
    assert N._ACTIVATIONS["swiglu"] is N.swiglu


def test_the_forty_expert_shares_and_the_shared_expert_add_up():
    """Five shares of 8 of 40 experts through `moe_topk_held` with the
    SwiGLU activation, the shared expert counted once: the uncut expert
    block of the reference (all 40 held)."""
    w = _experts()
    x = w.pop("x")
    cfg = {"num_experts_per_tok": K, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0, "experts_held": (0, E)}
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x, w, cfg)
        total = ref.swiglu(x, w["shared_up.weight"].T,
                           w["shared_down.weight"].T)
        loads = []
        for first in range(0, E, 8):
            y, load = moe_topk_held(
                x, w["router_weight"], w["correction_bias"],
                w["experts_up"][first:first + 8],
                w["experts_down"][first:first + 8], (first, 8), K, 1.0,
                True, act=N.swiglu)
            total = total + y
            loads.append(load)
    assert _err(total, want) < 1e-5
    assert all(bool((l == loads[0]).all()) for l in loads)  # one routing
    assert int(loads[0].sum()) == x.shape[0] * K


def test_one_share_is_the_reference_told_the_same_share():
    w = _experts(7)
    x = w.pop("x")
    cfg = {"num_experts_per_tok": K, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0, "experts_held": (16, 8)}
    share = dict(w, experts_up=w["experts_up"][16:24],
                 experts_down=w["experts_down"][16:24])
    with jax.default_matmul_precision("highest"):
        y, _ = moe_topk_held(x, w["router_weight"], w["correction_bias"],
                             share["experts_up"], share["experts_down"],
                             (16, 8), K, 1.0, True, act=N.swiglu)
        want = ref.experts(x, share, cfg) - ref.swiglu(
            x, w["shared_up.weight"].T, w["shared_down.weight"].T)
    assert _err(y, want) < 1e-5


def test_relu2_experts_are_unchanged():
    """Nemotron's form (one `up`, `relu2`) through the same function: its
    own reference still, and the same jaxpr whether or not the SwiGLU
    activation exists (nothing in the function asks which it was given)."""
    rs = onp.random.RandomState(8)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape) * 0.3, jnp.float32)
    x, w = draw(48, D), {
        "router_weight": draw(16, D), "correction_bias": jnp.zeros((16,)),
        "experts_up": draw(4, D, F), "experts_down": draw(4, F, D),
        "shared_up.weight": draw(F, D), "shared_down.weight": draw(D, F)}
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "experts_held": (4, 4)}
    with jax.default_matmul_precision("highest"):
        y, load = moe_topk_held(x, w["router_weight"], w["correction_bias"],
                                w["experts_up"], w["experts_down"], (4, 4), 3,
                                2.5, True, act=N.relu2)
        want = nemotron_ref.experts(x, w, cfg) - nemotron_ref.relu2(
            x @ w["shared_up.weight"].T) @ w["shared_down.weight"].T
    assert _err(y, want) < 1e-5 and int(load.sum()) == 48 * 3


@pytest.mark.parametrize("slot_rows", [16, 20, 48, None])
def test_swiglu_experts_hand_backward_is_the_plain_sum_s_gradient(slot_rows):
    """The fused gate-up product and the activation that splits it through
    the backward pass written by hand: `act` is any callable there (its
    `jax.vjp` is taken a slot), so the gradients of x, the router weight,
    gate-up and down are the reference's - with every token sent to three
    of the four held experts, so further slots run."""
    rs = onp.random.RandomState(9)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape) * 0.3, jnp.float32)
    x, rw, gy = draw(48, D), draw(16, D), draw(48, D)
    up, down = draw(4, D, 2 * F), draw(4, F, D)
    bias = jnp.zeros((16,)).at[5].set(9.0).at[6].set(8.0).at[7].set(7.0)
    cfg = {"num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 1.5, "experts_held": (4, 4)}

    def held(x, rw, up, down):
        y, _ = moe_topk_held(x, rw, bias, up, down, (4, 4), 3, 1.5, True,
                             slot_rows=slot_rows, act=N.swiglu)
        return jnp.sum(y * gy)

    def plain(x, rw, up, down):
        w = {"router_weight": rw, "correction_bias": bias,
             "experts_up": up, "experts_down": down,
             "shared_up.weight": jnp.zeros((2, D)),
             "shared_down.weight": jnp.zeros((D, 1))}
        return jnp.sum(ref.experts(x, w, cfg) * gy)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(held, (0, 1, 2, 3)))(x, rw, up, down)
        want = jax.grad(plain, (0, 1, 2, 3))(x, rw, up, down)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        assert _err(g, w) < 1e-5


def test_slot_rows_at_the_cell_s_sizes():
    """8192 tokens, top-8 of 320: six even shares of 204.8 tokens in whole
    tiles of 256 rows are 1280 rows a held expert."""
    from mxnet_tpu.parallel import moe
    s, top_k, e = 8192, 8, 320
    assert -(-moe.SLOT_SHARES * s * top_k // (e * 256)) * 256 == 1280
