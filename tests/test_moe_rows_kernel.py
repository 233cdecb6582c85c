"""The row-move kernel of the routed experts (`pallas_kernels.
rows_scatter_add`: `mx_rows_scatter_add`) in interpret mode on the CPU,
against `.at[].add`, the routing rule `rows_use_pallas` with its counters,
and `parallel/moe.py::moe_topk_held` through the kernel against its XLA
route and the plain reference.  That it compiles for the chip at both
cells' shapes is tests/test_chip_compile.py."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.ops import pallas_block
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel.moe import moe_topk_held

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "nemotron_ref_rows",
    os.path.join(REPO, "chipbench", "reference", "nemotron_h.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _dispatch():
    return {k[len("dispatch.pallas."):]: v for k, v in
            telemetry.raw_snapshot()["counters"].items()
            if k.startswith("dispatch.pallas.") and v}


def _slot(s, rows, live, seed=0):
    """A slot's indices as `moe_topk_held` makes them: `live` sorted unique
    tokens, then dead row j with the index s + j."""
    tok = jnp.sort(jax.random.permutation(jax.random.PRNGKey(seed),
                                          s)[:live])
    return jnp.concatenate([tok, s + jnp.arange(live, rows)]).astype(
        jnp.int32)


@pytest.mark.parametrize("s,d,rows,live", [
    (96, 256, 256, 0),          # an expert sent nothing: nothing is moved
    (96, 256, 256, 37),         # dead rows after the live ones
    (300, 128, 256, 256),       # a slot exactly full
    (512, 384, 512, 300),       # two tiles, the second partly live
    (64, 128, 128, 64),         # every token, a tile of 128 rows
    (40, 4096, 384, 17),        # rows as wide as Solar's: tiles of 128
])
def test_rows_scatter_add_is_at_add(monkeypatch, s, d, rows, live):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    y = jax.random.normal(ks[0], (s, d))
    upd = jax.random.normal(ks[1], (rows, d))
    tok = _slot(s, rows, live)
    got = jax.jit(pk.rows_scatter_add)(
        y.reshape(s, d // 128, 128), tok, jnp.int32(live), upd)
    want = y.at[tok].add(upd, mode="drop")
    assert got.shape == (s, d // 128, 128) and got.dtype == y.dtype
    assert bool((got.reshape(s, d) == want).all())


def test_rows_past_live_are_never_read(monkeypatch):
    """`live` decides, not the index: a row past it moves nothing even if
    its index is a token's (the loop never passes one; the kernel must not
    depend on it)."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    y = jnp.ones((32, 128))
    upd = jnp.full((128, 128), 2.0)
    tok = jnp.arange(128, dtype=jnp.int32) % 32
    got = pk.rows_scatter_add(y.reshape(32, 1, 128), tok, jnp.int32(5), upd)
    assert got.reshape(32, 128)[:, 0].tolist() == [3.0] * 5 + [1.0] * 27


@pytest.mark.parametrize("force,one_tpu,rows,d,dtype,want", [
    (False, True, 2304, 2688, jnp.float32, True),     # the Nemotron cell
    (False, True, 1024, 4096, jnp.float32, True),     # the Solar cell
    (True, False, 128, 128, jnp.float32, True),       # the tests' switch
    (False, False, 2304, 2688, jnp.float32, False),   # a mesh, the CPU
    (False, True, 2304, 2688, jnp.bfloat16, False),   # packed rows
    (False, True, 2304, 2700, jnp.float32, False),    # no whole lane blocks
    (False, True, 48, 2688, jnp.float32, False),      # no whole row tiles
])
def test_the_routing_decision_reads_shapes_only(monkeypatch, force, one_tpu,
                                                rows, d, dtype, want):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", force)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: one_tpu)
    telemetry.reset()
    assert pk.rows_use_pallas(rows, d, dtype) is want
    assert _dispatch() == {
        f"{'hits' if want else 'fallbacks'}.moe_rows.{d}": 1}


def _layer(s=256, d=128, f=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (s, d)),
            jax.random.normal(ks[1], (16, d)) * 0.2,
            jax.random.normal(ks[2], (4, d, f)) * 0.2,
            jax.random.normal(ks[3], (4, f, d)) * 0.2,
            jax.random.normal(ks[4], (s, d)))


ROUTINGS = {
    "as_routed": jnp.zeros(16),
    # expert 5 is sent every token (two slots of 128), expert 4 none
    "further_slots": jnp.zeros(16).at[5].set(9.0).at[4].set(-9.0),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_layer_through_the_kernel_is_its_xla_route_and_the_plain_sum(
        monkeypatch, routing):
    """Forward and the gradients of x, the router weight, up and down with
    the rows added by `mx_rows_scatter_add` (slots of 128 rows of 128
    float32): the XLA route's to rounding, the plain reference's, and the
    route is counted."""
    # the products stay XLA's, whose rounding the tolerances are for: the
    # products on the live rows have tests/test_moe_live_rows_kernel.py
    monkeypatch.setattr(pk, "live_use_pallas", lambda *shapes: False)
    x, rw, up, down, gy = _layer()
    bias = ROUTINGS[routing]
    cfg = dict(num_experts_per_tok=3, routed_scaling_factor=2.5,
               norm_topk_prob=True, experts_held=(4, 4))

    def held(x, rw, up, down):
        y, load = moe_topk_held(x, rw, bias, up, down, (4, 4), 3, 2.5,
                                act=ops.relu2, slot_rows=128)
        return jnp.sum(jnp.tanh(y) * gy), load

    def plain(x, rw, up, down):
        w = {"router_weight": rw, "correction_bias": bias,
             "experts_up": up, "experts_down": down,
             "shared_up.weight": jnp.zeros((2, 128)),
             "shared_down.weight": jnp.zeros((128, 2))}
        return jnp.sum(jnp.tanh(ref.experts(x, w, cfg)) * gy)

    def run():
        return jax.jit(jax.value_and_grad(held, (0, 1, 2, 3), has_aux=True))(
            x, rw, up, down)

    with jax.default_matmul_precision("highest"):
        telemetry.reset()
        (xla, _), xla_grads = run()
        assert _dispatch()["fallbacks.moe_rows.128"] >= 1
        monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
        telemetry.reset()
        (got, load), grads = run()
        assert _dispatch()["hits.moe_rows.128"] >= 1
        assert "fallbacks.moe_rows.128" not in _dispatch()
        want, want_grads = jax.value_and_grad(plain, (0, 1, 2, 3))(
            x, rw, up, down)
    if routing == "further_slots":
        assert int(load[5]) == 256 and int(load[4]) == 0
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    assert abs(float(got) - float(xla)) < 1e-5 * abs(float(xla))
    for g, a, w in zip(grads, xla_grads, want_grads):
        assert _err(g, a) < 1e-6 and _err(g, w) < 1e-5


def test_the_kernel_is_in_the_gradient_s_program_and_no_xla_scatter(
        monkeypatch):
    """With the kernel route the gradient's program adds the slots' rows by
    `mx_rows_scatter_add` alone (first slot and the loop of further ones,
    forward and backward) into a (S, D / 128, 128) carry."""
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)
    x, rw, up, down, gy = _layer()

    def held(x, rw, up, down):
        return jnp.sum(moe_topk_held(x, rw, jnp.zeros(16), up, down, (4, 4),
                                     3, 2.5, act=ops.relu2,
                                     slot_rows=128)[0] * gy)

    text = str(jax.make_jaxpr(jax.grad(held, (0, 1, 2, 3)))(x, rw, up, down))
    assert text.count("name=mx_rows_scatter_add") == 4
    assert "f32[256,1,128]" in text
    assert not [l for l in text.splitlines()
                if "scatter-add" in l and "f32[256,128]" in l.split("=")[0]]
