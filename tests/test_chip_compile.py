"""Compile for the chip, without the chip.

The only file of the suite that describes a TPU: the installed TPU compiler
compiles for a v5e that is described and not attached, so what it refuses
(an unimplemented primitive in the Pallas TPU lowering, an unaligned slice,
too much VMEM) fails here, on the CPU, at no chip time.  A compile that
passes is not a chip run: ``chip_smoke.py`` is that.

Only one process may load the TPU's library, so the topology is described
inside a fixture (never at import, in a ``skipif`` or in ``parametrize``
arguments), the compiles run in this test's own process, and every such
test lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_block, pallas_int8, pallas_kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """jax.devices() still says CPU here: steer the kernels out of
    interpret mode in the test, not through an option of the program."""
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas_block, "interpret", lambda: False)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_softmax_fused_fwd_and_grad(one_chip, compiled_kernels):
    x = jax.ShapeDtypeStruct((4096, 1024), jnp.float32, sharding=one_chip)
    _compile(pallas_kernels.softmax_fused, x)
    _compile(jax.grad(lambda a: jnp.sum(pallas_kernels.softmax_fused(a) ** 2)),
             x)


def test_layernorm_fused(one_chip, compiled_kernels):
    x = jax.ShapeDtypeStruct((8, 512, 768), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((768,), jnp.float32, sharding=one_chip)
    _compile(lambda a, b, c: pallas_kernels.layernorm_fused(a, b, c, 1e-5),
             x, g, g)


def _no_square(text, length):
    """No (L, L) buffer of any batch/head arrangement in the program."""
    assert f"{length},{length}]" not in text, \
        f"an ({length}, {length}) matrix exists outside the kernels"


# the BERT cell's shape; head_dim 128 at the longest length the route takes
@pytest.mark.parametrize("shape", [(16, 12, 512, 64), (2, 4, 1024, 128)],
                         ids=["cell-hd64", "hd128"])
def test_attention_fused_fwd_and_grad(one_chip, compiled_kernels, shape):
    q = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    assert pallas_kernels._attn_route(shape[2], shape[2], shape[3])
    text = _compile(pallas_kernels.attention_fused, q, q, q)
    _no_square(text, shape[2])
    text = _compile(jax.grad(
        lambda a, b, c: jnp.sum(pallas_kernels.attention_fused(a, b, c) ** 2),
        argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") >= 2      # forward + backward
    _no_square(text, shape[2])


def test_self_attention_packed_fwd_and_grad(one_chip, compiled_kernels):
    """What `bert-train-ring` runs twelve times a step: the heads read out
    of the f32[16,512,2304] projection in place, two a 128-lane block."""
    qkv = jax.ShapeDtypeStruct((16, 512, 3 * 768), jnp.float32,
                               sharding=one_chip)
    text = _compile(lambda a: pallas_kernels.self_attention_fused(a, 12), qkv)
    _no_square(text, 512)
    assert "transpose" not in text          # no head moves in HBM
    text = _compile(jax.grad(lambda a: jnp.sum(
        pallas_kernels.self_attention_fused(a, 12) ** 2)), qkv)
    assert text.count("tpu_custom_call") >= 2
    _no_square(text, 512)


def test_causal_gqa_attention_fwd_and_grad_at_the_cell_shape(one_chip,
                                                            compiled_kernels):
    """What `nemotron3-nano-train-8k` runs: 32 query heads over 2
    key-value heads of 128 at T = 8192, routed from
    `ops/nn.py::causal_gqa_attention`.  The program holds the named
    kernels and no score tile: the two-scan composition it replaces kept
    `f32[8,1,2,16,512,1024]` residuals, eight tiles stacked."""
    from mxnet_tpu.ops import nn
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.float32,
                              sharding=one_chip)
    assert pallas_kernels.causal_attention_use_pallas(8192, 32, 2, 128)

    def no_tile(text):
        assert "512,1024]" not in text and "512,512]" not in text, \
            "a (q_block, k_block) tile exists outside the kernels"
        _no_square(text, 8192)

    text = _compile(nn.causal_gqa_attention, q, kv, kv)
    assert "mx_causal_attn_fwd" in text
    no_tile(text)
    text = _compile(jax.grad(
        lambda a, b, c: jnp.sum(nn.causal_gqa_attention(a, b, c) ** 2),
        argnums=(0, 1, 2)), q, kv, kv)
    assert "mx_causal_attn_fwd" in text and "mx_causal_attn_bwd" in text
    assert text.count("tpu_custom_call") >= 2
    no_tile(text)


def test_windowed_attention_fwd_and_grad_at_the_cell_shape(one_chip,
                                                          compiled_kernels):
    """What `trinity-mini-train-swa8k` runs four times a step: 32 query
    heads over 4 key-value heads of 128 at T = 8192 with a window of 2048,
    routed from `ops/nn.py::causal_gqa_attention(window=)`.  The program
    holds the windowed pair by name, not the causal one, and no score
    tile."""
    from mxnet_tpu.ops import nn
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.float32,
                              sharding=one_chip)
    assert pallas_kernels.causal_attention_use_pallas(8192, 32, 4, 128, 2048)
    text = _compile(jax.grad(
        lambda a, b, c: jnp.sum(
            nn.causal_gqa_attention(a, b, c, window=2048) ** 2),
        argnums=(0, 1, 2)), q, kv, kv)
    assert "mx_window_attn_fwd" in text and "mx_window_attn_bwd" in text
    assert "mx_causal_attn" not in text
    assert text.count("tpu_custom_call") >= 2
    assert "256,256]" not in text and "256,512]" not in text
    _no_square(text, 8192)


def test_ssd_fwd_and_grad_at_the_cell_shape(one_chip, compiled_kernels):
    """What `nemotron3-nano-train-8k` runs in each of its four Mamba-2
    layers: 64 heads of 64 in 8 groups, state 128, T = 8192 in chunks of
    128, routed from `ops/nn.py::ssd_chunked`.  The program holds the named
    kernels under the default scoped VMEM limit and none of the
    composition's (…, 128, 128) decay tiles or (…, 64, 128) chunk states
    in float32."""
    from mxnet_tpu.ops import nn

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (arg(1, 8192, 64, 64), arg(1, 8192, 64), arg(64),
            arg(1, 8192, 8, 128), arg(1, 8192, 8, 128), arg(64))
    assert pallas_kernels.ssd_use_pallas(8192, 64, 8, 64, 128, 128)

    def no_tile(text):
        for shape in ("f32[1,64,8,8,128,128]", "f32[1,64,8,8,64,128]"):
            assert shape not in text, f"{shape} exists outside the kernels"

    text = _compile(nn.ssd_chunked, *args)
    assert "mx_ssd_fwd" in text
    no_tile(text)
    text = _compile(jax.grad(lambda *a: jnp.sum(nn.ssd_chunked(*a) ** 2),
                             argnums=tuple(range(6))), *args)
    assert "mx_ssd_fwd" in text and "mx_ssd_bwd" in text
    assert text.count("tpu_custom_call") >= 2
    no_tile(text)


@pytest.mark.parametrize("s,d,f,fu,e,k,rows,act", [
    (8192, 2688, 1856, 1856, 128, 6, None, "relu2"),     # Nemotron's cell
    (4096, 4096, 1280, 2560, 320, 8, 1024, "swiglu"),    # Solar-Open2's
])
def test_routed_experts_rows_at_the_cell_shapes(one_chip, compiled_kernels,
                                                s, d, f, fu, e, k, rows,
                                                act):
    """`moe_topk_held` of both language-model cells, 8 held experts, forward
    and gradient: the slots' rows are added by `mx_rows_scatter_add` (first
    slot and the loop of further ones, forward and backward) under the
    default scoped VMEM limit, into an (S, D / 128, 128) carry, and no XLA
    scatter over an (S, D) array is left."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.parallel.moe import moe_topk_held

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def held(x, rw, up, down, gy):
        y, _ = moe_topk_held(x, rw, jnp.zeros((e,)), up, down, (0, 8), k,
                             2.5, slot_rows=rows,
                             act=getattr(nn, act))
        return jnp.sum(jnp.tanh(y) * gy)

    slot = rows or 2304
    assert pallas_kernels.rows_use_pallas(slot, d, jnp.float32)
    text = _compile(jax.grad(held, (0, 1, 2, 3)), arg(s, d), arg(e, d),
                    arg(8, d, fu), arg(8, f, d), arg(s, d))
    assert _kernel_calls(text, "mx_rows_scatter_add") == 4
    assert f"f32[{s},{d // 128},128]" in text
    assert not [line for line in text.splitlines()
                if " scatter(" in line and f"= f32[{s},{d}]" in line]


def _kernel_calls(text, name):
    """How many calls of the kernel `name` the compiled text makes."""
    return sum('custom_call_target="tpu_custom_call"' in line and
               f"/{name}/" in line for line in text.splitlines())


# held experts' slots at the three cells' shapes: tokens, D, F, up's width
# (2F for SwiGLU's fused product), experts, top-k, held, slot rows
EXPERT_CELLS = {
    "trinity-mini-train-swa8k": (8192, 2048, 1024, 2048, 128, 8, 16, 5120,
                                 "swiglu"),
    "solar-open2-train-kda": (4096, 4096, 1280, 2560, 320, 8, 8, 1024,
                              "swiglu"),
    "nemotron3-nano-train-8k": (8192, 2688, 1856, 1856, 128, 6, 8, None,
                                "relu2"),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_routed_experts_products_on_live_rows_at_the_cell_shapes(
        one_chip, compiled_kernels, monkeypatch, cell):
    """`moe_topk_held` forward and gradient at each expert cell's shape
    (Nemotron's F = 1856 taken as one whole-width block): every product of
    a slot runs in the live-row kernels -- forward, backward and both
    weight gradients, for the first slot and the loop of further ones --
    and the program needs no more memory than the dense route's (the
    kernels read the bfloat16 stacks in place and write the weights'
    cotangents into the stacks the scan carries)."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.parallel.moe import moe_topk_held
    s, d, f, fu, e, k, held, rows, act = EXPERT_CELLS[cell]

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def compiled():
        def layer(x, rw, up, down, gy):     # a new function a route
            y, _ = moe_topk_held(x, rw, jnp.zeros((e,)), up, down,
                                 (0, held), k, 2.5, slot_rows=rows,
                                 act=getattr(nn, act))
            return jnp.sum(jnp.tanh(y) * gy)
        return jax.jit(jax.grad(layer, (0, 1, 2, 3))).lower(
            arg(s, d), arg(e, d), arg(held, d, fu), arg(held, f, d),
            arg(s, d)).compile()

    assert pallas_kernels.live_use_pallas(rows or 2304, d, f, fu,
                                          jnp.float32)
    live = compiled()
    text = live.as_text()
    for name in ("mx_moe_live_fwd", "mx_moe_live_bwd", "mx_moe_live_up_grad",
                 "mx_moe_live_down_grad"):
        assert _kernel_calls(text, name) == 2, name
    monkeypatch.setattr(pallas_kernels, "live_use_pallas",
                        lambda *shapes: False)
    dense = compiled()
    assert "mx_moe_live" not in dense.as_text()
    assert live.memory_analysis().temp_size_in_bytes <= \
        dense.memory_analysis().temp_size_in_bytes


def _stage_shape(stage, n=128):
    h, w, c = (int(t) for t in stage.split("x"))
    return (n, h, w, c)


def test_default_block_routes_compile(one_chip, compiled_kernels):
    """The invariant: every stage the default block table routes to
    Pallas compiles for the chip at N=128 bf16 — forward with batch
    stats (conv+stats, affine), frozen forward, and the backward the
    table asks for (dgrad + wgrad when "pallas")."""
    for stage, ent in sorted(pallas_block._DEFAULT_TABLE.items()):
        if ent["fwd"] != "pallas":
            continue
        shape = _stage_shape(stage)
        c = shape[-1]
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        w = jax.ShapeDtypeStruct((3, 3, c, c), jnp.bfloat16,
                                 sharding=one_chip)
        v = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
        assert pallas_block.eligible_block(shape, w.shape, x.dtype, True)

        def block(x, w, g, b, m, s, r, frozen):
            return pallas_block.residual_block_fused(
                x, w, g, b, m, s, r, frozen=frozen, bwd=ent["bwd"])[0]

        def train(x, w, g, b, m, s, r):
            return jax.grad(
                lambda *a: jnp.sum(block(*a, m, s, r, False)
                                   .astype(jnp.float32)),
                argnums=(0, 1, 2, 3))(x, w, g, b)

        text = _compile(train, x, w, v, v, v, v, x)
        # conv+stats, affine, and dgrad + wgrad where the table says so
        want = 4 if ent["bwd"] == "pallas" else 2
        assert text.count("tpu_custom_call") >= want, stage
        _compile(lambda *a: block(*a, True), x, w, v, v, v, v, x)


def test_default_int8_routes_compile(one_chip, compiled_kernels):
    """Same invariant for the int8 table: every routed stage's fused
    dequant kernel compiles at N=128, with and without a residual."""
    for stage, ent in sorted(pallas_int8._DEFAULT_TABLE.items()):
        if ent["fwd"] != "pallas":
            continue
        shape = _stage_shape(stage)
        c = shape[-1]
        qx = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip)
        qw = jax.ShapeDtypeStruct((3, 3, c, c), jnp.int8, sharding=one_chip)
        v = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
        res = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        assert pallas_int8.eligible_int8(shape, qw.shape, True)
        _compile(pallas_int8.qconv3x3_affine, qx, qw, v, v, res)
        _compile(pallas_int8.qconv3x3_affine, qx, qw, v, v)


# name -> (operator, T, heads, dk, dv, a decay a channel)
DELTA_CELLS = {
    "olmo-hybrid-train-gdn": ("gdn_chunked", 8192, 15, 96, 192, False),
    "solar-open2-train-kda": ("kda_chunked", 4096, 8, 128, 128, True),
}


@pytest.mark.parametrize("cell", sorted(DELTA_CELLS))
def test_delta_rule_fwd_and_grad_at_the_cell_shapes(one_chip, compiled_kernels,
                                                    cell):
    """What the two linear-attention cells run in each of their three mixers
    through `ops/nn.py::gdn_chunked` / `kda_chunked`: the predicate says
    yes, the program holds the named kernels under the default scoped VMEM
    limit — 96 and 192 wide as they are, w and u0 sliced out of the solve's
    result in VMEM at lane 96 — and no `while` loop (the chunk-state
    `lax.scan`), no (T, T) matrix; the pairwise products and the triangular
    solve are still XLA's."""
    from mxnet_tpu.ops import nn
    name, t, heads, dk, dv, channel = DELTA_CELLS[cell]
    fn = getattr(nn, name)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    args = (s(1, t, heads, dk), s(1, t, heads, dk), s(1, t, heads, dv),
            s(1, t, heads, dk) if channel else s(1, t, heads),
            s(1, t, heads))
    assert pallas_kernels.delta_rule_use_pallas(t, heads, dk, dv, 64)
    text = _compile(fn, *args)
    assert "mx_delta_rule_fwd" in text and "mx_delta_rule_bwd" not in text
    assert " while(" not in text
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) ** 2),
        argnums=tuple(range(5)))).lower(*args).compile()
    text = compiled.as_text()
    assert "mx_delta_rule_fwd" in text and "mx_delta_rule_bwd" in text
    assert " while(" not in text, "a loop over the chunk states is left"
    if not channel:
        assert "16,96]" not in text, "a per-channel decay tile was made"
    _no_square(text, t)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_olmo_hybrid_attention_fwd_and_grad_at_the_cell_shape(
        one_chip, compiled_kernels):
    """What `olmo-hybrid-train-gdn` runs in its attention block: 15 query =
    15 key-value heads of 128 behind the QK-norm, which meet the causal
    attention kernels' rule."""
    from mxnet_tpu.models import olmo_hybrid
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    assert pallas_kernels.causal_attention_use_pallas(8192, 15, 15, 128)
    proj, w = s(1, 8192, 1920), s(1920)
    text = _compile(jax.grad(
        lambda q, k, v, qw, kw: jnp.sum(olmo_hybrid._qknorm_attention_core(
            q, k, v, qw, kw, heads=15, kv=15, hd=128, eps=1e-6) ** 2),
        argnums=(0, 1, 2, 3, 4)), proj, proj, proj, w, w)
    assert "mx_causal_attn_fwd" in text and "mx_causal_attn_bwd" in text
    _no_square(text, 8192)


def test_olmo_hybrid_mlp_block_s_weight_gradient_reads_stored_operands(
        one_chip):
    """One `F` block of `olmo-hybrid-train-gdn` (8192 x 3840 x 11008,
    float32) with Adam through `Trainer.fuse_step`: the fusion that writes
    `down_proj`'s updated weight (the weight gradient's product with the
    update as its epilogue) reads SwiGLU's output stored once as
    `bf16[1,8192,11008]` and the post-norm's cotangent stored once, not the
    `f32[1,8192,22016]` gate-up product with `silu(gate) * up` evaluated
    again for every output tile (`gluon.block.materialize`, PERF.md section
    6, PR 37: 19.8 -> 4-6 ms on the chip)."""
    import re

    from mxnet_tpu.gluon import Trainer, loss as gloss
    from mxnet_tpu.models import olmo_hybrid
    T, D, F = 8192, 3840, 11008
    net = olmo_hybrid.PostNormLayer(olmo_hybrid.SwiGLUMLP(D, F), D)
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-4})
    step = trainer.fuse_step(gloss.L2Loss())
    init_state = step._opt.init_state       # shapes of the moments only
    step._opt.init_state = lambda w: jax.eval_shape(init_state, w)
    step._build_data(jnp.zeros((1, 8, D), jnp.float32))
    step._build_jit()
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    weights = lambda names: {n: like(step._params[n].data()._data)
                             for n in names}
    states = {n: jax.tree_util.tree_map(like, trainer._states[step._tname[n]])
              for n in step._tr_names}
    x = jax.ShapeDtypeStruct((1, T, D), jnp.float32, sharding=one_chip)
    compiled = step._compiled.lower(
        weights(step._tr_names), weights(step._fr_names), states,
        jax.tree_util.tree_map(like, step._ctl),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip), x, x
    ).compile()
    text = compiled.as_text()
    types = dict(re.findall(
        r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (\(?\w+\[[\d,]*\])", text, re.M))
    updates = [line for line in text.splitlines()
               if re.search(r"= \(f32\[3840,11008\]", line)
               and " fusion(" in line and "mlp.down" in line]
    assert len(updates) == 1, updates
    operands = [types.get(name, "") for name in re.findall(
        r"%[\w.\-]+", updates[0].split(" fusion(", 1)[1].split(")", 1)[0])]
    assert "bf16[1,8192,11008]" in operands, operands
    assert "bf16[1,8192,3840]" in operands, operands
    assert "f32[1,8192,22016]" not in operands, operands
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
