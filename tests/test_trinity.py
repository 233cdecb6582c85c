"""Trinity (models/trinity.py) against its plain reference
(chipbench/reference/trinity.py, loaded by path: it imports nothing from the
program) at small sizes on the CPU, seeded weights: each kind of block and
the whole cut (forward, loss, gradients), the fused step, the window's edge,
the rotary embedding on ``W`` blocks alone, the eight expert shares that add
up to the uncut layer, the vocabulary slices, the builder's refusals, and
the configuration file against the catalog row.  The windowed kernel pair is
in test_causal_attention_kernel.py."""
import importlib.util
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, telemetry
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.models import solar_open2
from mxnet_tpu.models.trinity import (SandwichLayer, TrinityAttention,
                                      block_pattern, trinity, trinity_tiny)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "trinity-mini-26b-train-ep8.json")
SLIDING, FULL = "sliding_attention", "full_attention"


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "trinity_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "trinity.py")

TINY = dict(hidden_size=32, head_dim=8, rms_norm_eps=1e-5, sliding_window=6,
            rope_theta=10000.0, num_experts_per_tok=3, route_scale=2.826,
            route_norm=True, mup_enabled=True, experts_held=(4, 4))
B, T = 2, 24                 # four windows of 6 keys
# pattern -> (layer_types, num_dense_layers): each letter, and the cut
SHAPES = {"WD": ((SLIDING,), 1), "*E": ((FULL,), 0), "WE": ((SLIDING,), 0),
          "*D": ((FULL,), 1),
          "WDWEWEWE*E": ((SLIDING,) * 4 + (FULL,), 1)}


def _model(pattern, seed=1, **kwargs):
    mx.seed(seed)
    types, dense = SHAPES[pattern]
    net = trinity_tiny(len(types), layer_types=types, num_dense_layers=dense,
                       **kwargs)
    net.initialize()
    net.hybridize()
    assert net.pattern == pattern
    # norm weights of 1 would hide a norm whose weight sits at the wrong place
    rs = onp.random.RandomState(seed)
    for name, p in net.collect_params().items():
        if name.endswith(("norm_weight", "gamma")):
            p.set_data(mx.np.array(1 + 0.3 * rs.randn(*p.shape)
                                   .astype("float32")))
    ids = rs.randint(0, net.vocab_held[1], (B, T + 1)).astype("int32")
    return net, mx.np.array(ids[:, :-1]), mx.np.array(ids[:, 1:]), \
        dict(TINY, pattern=pattern)


def _floats(net):
    return {n: p.data()._data for n, p in net.collect_params().items()
            if jnp.issubdtype(p.data()._data.dtype, jnp.floating)}


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------ the model vs the reference
@pytest.mark.parametrize("pattern", list(SHAPES))
def test_forward_matches_reference(pattern):
    net, x, _, cfg = _model(pattern)
    with jax.default_matmul_precision("highest"):
        got = net(x)._data
    want = ref.logits(_floats(net), x._data, cfg)
    assert got.shape == (B, T, 64)
    assert _err(got, want) < 1e-5


@pytest.mark.parametrize("pattern", list(SHAPES))
def test_loss_and_gradients_match_reference(pattern):
    """The system's autograd path (hybridized forward, taped loss) against
    ``jax.grad`` of the reference, every trainable tensor."""
    net, x, y, cfg = _model(pattern)
    loss_fn = SoftmaxCrossEntropyLoss()
    with jax.default_matmul_precision("highest"):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        want, grads = jax.value_and_grad(
            lambda p: ref.loss(p, x._data, y._data, cfg))(_floats(net))
    assert abs(float(l.mean().asnumpy()) - float(want)) < 1e-5
    for name, p in net.collect_params().items():
        if p.grad_req == "null":
            continue
        # backward() seeds ones over the per-sample losses: B x the mean's
        assert _err(p.grad()._data / B, grads[name]) < 2e-4, name


def test_the_block_is_a_sandwich_and_the_embedding_is_scaled():
    """``h + norm_out(sub(norm(h)))``: with the outer norm's weight at zero
    a block is the identity; with the inner one at zero the sub-layer sees
    zeros, and a block whose sub-layer maps 0 to 0 is the identity too (a
    post-norm-only block is not).  The embedding's rows leave times
    sqrt(hidden_size)."""
    net, x, _, _ = _model("WDWEWEWE*E")
    with jax.default_matmul_precision("highest"):
        h = net.embed(x) * 32 ** 0.5
        assert _err(ref.hidden.__globals__["math"].sqrt(32)
                    * _floats(net)["embed.weight"][x._data], h._data) < 1e-6
        tables = mx.numpy._call(mx.ops.nn.rope_tables, mx.np.arange(T),
                                dim=8)
        for kind, layer in zip(net.pattern, net.layers):
            assert isinstance(layer, SandwichLayer)
            args = tables if kind == "W" else ()
            assert _err(layer(h, *args)._data, h._data) > 1e-3
            was = layer.norm.gamma.data()
            layer.norm.gamma.set_data(mx.np.zeros(32))
            assert _err(layer(h, *args)._data, h._data) < 1e-6
            layer.norm.gamma.set_data(was)
            layer.norm_out.gamma.set_data(mx.np.zeros(32))
            assert _err(layer(h, *args)._data, h._data) == 0.0


def _attention(window, seed=5, t=16, d=32, heads=4, kv=2, hd=8):
    mx.seed(seed)
    mixer = TrinityAttention(d, heads, kv, hd, window=window)
    mixer.initialize()
    rs = onp.random.RandomState(seed)
    x = mx.np.array(rs.randn(1, t, d).astype("float32"))
    tables = mx.numpy._call(mx.ops.nn.rope_tables, mx.np.arange(t), dim=hd)
    return mixer, x, tables


def _moved(mixer, x, tables, at):
    """Which rows of the mixer's output move when token ``at`` changes."""
    with jax.default_matmul_precision("highest"):
        a = mixer(x, *tables)._data
        bumped = x._data.at[0, at].add(1.0)
        b = mixer(mx.np.array(onp.asarray(bumped)), *tables)._data
    return onp.nonzero(onp.abs(onp.asarray(a - b)).max(axis=(0, 2))
                       > 1e-7)[0].tolist()


@pytest.mark.parametrize("window", [1, 4, 6])
def test_the_window_holds_the_token_and_the_window_minus_one_before_it(window):
    """A change of token 3 reaches rows 3 .. 3 + window - 1 and no other:
    the key exactly ``window - 1`` back is seen, the key ``window`` back is
    not."""
    mixer, x, tables = _attention(window)
    assert _moved(mixer, x, tables, 3) == list(range(3, 3 + window))
    full, x, _ = _attention(None)
    assert _moved(full, x, (), 3) == list(range(3, 16))


def test_rotary_embedding_is_on_the_sliding_blocks_and_not_on_the_full_ones():
    """Shifting every position by 5 leaves a sliding block's output as it
    was (rotary scores depend on i - j alone) while other tables change it;
    a full block takes no tables at all, and its reference reads no
    ``rope_theta``."""
    mixer, x, (cos, sin) = _attention(6)
    shifted = mx.numpy._call(mx.ops.nn.rope_tables, mx.np.arange(16) + 5,
                             dim=8)
    doubled = mx.numpy._call(mx.ops.nn.rope_tables, 2 * mx.np.arange(16),
                             dim=8)
    with jax.default_matmul_precision("highest"):
        base = mixer(x, cos, sin)._data
        assert _err(mixer(x, *shifted)._data, base) < 1e-5
        assert _err(mixer(x, *doubled)._data, base) > 1e-3
        assert _err(mixer(x)._data, base) > 1e-3          # no rotation
        # against the reference, with and without
        w = {n: p.data()._data for n, p in mixer.collect_params().items()}
        cfg = dict(TINY)
        assert _err(base, ref.attention(x._data[0], w, cfg, window=6)[None]) \
            < 1e-5
        full, x, _ = _attention(None)
        w = {n: p.data()._data for n, p in full.collect_params().items()}
        cfg.pop("rope_theta")
        assert _err(full(x)._data, ref.attention(x._data[0], w, cfg)[None]) \
            < 1e-5
    net, _, _, _ = _model("WDWEWEWE*E")
    kinds = [layer.mixer._sizes["window"] for k, layer
             in zip(net.pattern, net.layers) if k in "W*"]
    assert kinds == [6, 6, 6, 6, None]


def test_rope_tables_are_the_formula():
    cos, sin = mx.ops.nn.rope_tables(jnp.arange(7), 8, 10000.0)
    angle = onp.arange(7)[:, None] * 10000.0 ** (-2 * onp.arange(4) / 8)
    assert onp.allclose(cos, onp.cos(onp.concatenate([angle, angle], -1)),
                        atol=1e-6)
    assert onp.allclose(sin, onp.sin(onp.concatenate([-angle, angle], -1)),
                        atol=1e-6)
    x = jnp.asarray(onp.random.RandomState(0).randn(1, 7, 2, 8), jnp.float32)
    got = mx.ops.nn.rope(x, cos, sin)
    assert _err(got[0], ref.rotary(x[0], 10000.0)) < 1e-6


def _step(net, lr=1e-2):
    return Trainer(net.collect_params(), "adam",
                   {"learning_rate": lr}).fuse_step(SoftmaxCrossEntropyLoss())


def test_fused_step_loss_is_the_reference_loss_and_counts_its_routes():
    net, x, y, cfg = _model("WDWEWEWE*E")
    want = float(ref.loss(_floats(net), x._data, y._data, cfg))
    step = _step(net)
    c0 = dict(telemetry.raw_snapshot()["counters"])
    with jax.default_matmul_precision("highest"):
        first = float(step(x, y).asnumpy())
    c1 = dict(telemetry.raw_snapshot()["counters"])
    assert abs(first - want) < 1e-5
    routes = {k: c1[k] - c0.get(k, 0) for k in c1 if k.startswith("dispatch.")
              and c1[k] != c0.get(k, 0)}
    # a block is traced in the forward and again under the checkpoint's
    # transpose, where it does not replay a cached trace
    assert routes.pop("dispatch.attention.window.xla_blocked") \
        == routes.pop("dispatch.pallas.fallbacks.window_attention.8") >= 4
    assert routes.pop("dispatch.attention.causal.xla_blocked") \
        == routes.pop("dispatch.pallas.fallbacks.causal_attention.8") >= 1
    assert routes.pop("dispatch.moe.sorted_slots") \
        == routes.pop("dispatch.pallas.fallbacks.moe_rows.32") \
        == routes.pop("dispatch.pallas.fallbacks.moe_live.32") >= 4
    routes.pop("dispatch.cache_misses", None)
    assert routes == {"dispatch.loss.linear_blocked": 1,
                      # one stored value a traced site: SwiGLU's output in
                      # the dense MLP, the sub-layer's output in all ten
                      "dispatch.materialized.mlp_act": 1,
                      "dispatch.materialized.sublayer_out": 10}


def test_fused_step_trains_without_retraces_and_publishes_the_experts_load():
    net, x, y, _ = _model("WDWEWEWE*E")
    step = _step(net)
    step(x, y)
    step.sync()
    c0 = dict(telemetry.raw_snapshot()["counters"])
    losses = [float(step(x, y).asnumpy()) for _ in range(6)]
    step.sync()
    c1 = dict(telemetry.raw_snapshot()["counters"])
    d = lambda k: c1.get(k, 0) - c0.get(k, 0)
    assert not step.fallback_reason
    assert losses[-1] < losses[0] - 0.3
    assert d("fused.dispatches") == 6 and d("fused.retraces") == 0
    assert d("fused.fallbacks") == 0
    # four expert layers, top-3 of 16 experts for 48 tokens, six steps
    assert d("moe.tokens_routed") == 6 * 4 * B * T * 3
    assert 0 < d("moe.tokens_held") < d("moe.tokens_routed")


def test_hlo_text_names_the_blocks_scopes():
    net, x, y, _ = _model("WDWEWEWE*E")
    step = _step(net, 1e-3)
    step(x, y)
    text = step.hlo_text(x, y)
    for scope in ("layers/0/", "attn.rope", "attn.qknorm", "attn.window",
                  "attn.gate", "q_proj", "k_proj", "v_proj", "g_proj",
                  "o_proj", "layers/1/", "mlp.up", "mlp.act", "mlp.down",
                  "layers/3/", "moe.route", "moe.dispatch", "moe.experts",
                  "moe.combine", "moe.shared", "layers/8/", "attn.core",
                  "/norm/", "/norm_out/", "/embed/", "mx.loss", "mx.opt"):
        assert scope in text, scope
    # the windowed calls lie under the W blocks, forward and backward, the
    # full one under the * block; the full block neither rotates nor windows
    for i in (0, 2, 4, 6):
        assert re.search(rf'op_name="[^"]*jvp\(mx\.fwd\)[^"]*layers/{i}/'
                         rf'[^"]*mixer/attn\.window/', text), i
        assert re.search(rf'layers/{i}/[^"]*mixer/attn\.rope', text), i
    assert re.search(r'op_name="[^"]*transpose\(jvp\(mx\.fwd\)\)[^"]*'
                     r'layers/0/[^"]*mixer/attn\.window/', text)
    assert re.search(r'layers/8/[^"]*mixer/attn\.core/', text)
    assert not re.search(r'layers/8/[^"]*attn\.(window|rope)', text)
    assert not re.search(r'layers/[0246]/[^"]*attn\.core', text)
    assert not re.search(r'layers/[13579]/[^"]*attn\.', text)
    # the tables are made once, outside the blocks
    assert re.search(r'op_name="[^"]*mx\.fwd\)?/attn\.rope', text) \
        or re.search(r'op_name="[^"]*/attn\.rope/(cos|sin|mul|pow)', text)


# ------------------------------------------- the shares of the experts add up
def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Sixteen experts uncut in the reference; eight expert blocks of the
    program, each told its two experts and given their weights, return
    (before the block's outer norm) the shared expert's term plus their own
    experts' terms: summed, with the shared expert counted once, they are
    the reference's whole layer.  The router is whole in every share."""
    d, width, experts, top_k = 32, 16, 16, 3
    rs = onp.random.RandomState(21)
    draw = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    w = {"router_weight": draw(experts, d),
         "correction_bias": jnp.zeros(experts),
         "experts_up": draw(experts, d, 2 * width),
         "experts_down": draw(experts, width, d),
         "shared_up.weight": draw(2 * width, d),
         "shared_down.weight": draw(d, width)}
    x = draw(2, 24, d)
    cfg = dict(TINY, experts_held=(0, experts))
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda s: ref.experts(s, w, cfg))(x)
        shared = jax.vmap(lambda s: ref.swiglu(
            s, w["shared_up.weight"].T, w["shared_down.weight"].T))(x)
        total = 0
        for first in range(0, experts, 2):
            block = solar_open2.SwiGLUMoE(
                d, width, experts, top_k, experts_held=(first, 2),
                routed_scaling_factor=2.826, norm_topk_prob=True)
            block.initialize()
            part = dict(w, experts_up=w["experts_up"][first:first + 2],
                        experts_down=w["experts_down"][first:first + 2])
            for name, p in block.collect_params().items():
                if name in part:
                    p.set_data(mx.np.array(onp.asarray(part[name])))
            got = block(mx.np.array(onp.asarray(x)))._data
            # a share alone is the reference given the same share
            assert _err(got, jax.vmap(lambda s: ref.experts(
                s, part, dict(cfg, experts_held=(first, 2))))(x)) < 1e-5
            total = total + got - shared
    assert _err(total + shared, want) < 1e-5
    # and the attention is whole in every share: nothing of it is held
    assert "heads_held" not in inspect.signature(trinity).parameters


def test_the_eight_vocabulary_slices_logits_concatenate_to_the_uncut_head_s():
    full, x, _, cfg = _model("WD")
    weights = _floats(full)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(weights, x._data, cfg)
        got = []
        for s in range(8):
            mx.seed(1)
            part = trinity_tiny(1, layer_types=(SLIDING,), vocab_size=64,
                                vocab_held=(8 * s, 8))
            part.initialize()
            assert part.head.weight.shape == (8, 32)
            for name, p in part.collect_params().items():
                v = weights[name]
                if name == "head.weight":
                    v = v[8 * s:8 * s + 8]
                elif name == "embed.weight":
                    continue
                p.set_data(mx.np.array(onp.asarray(v)))
            # the slices share the hidden states: each is fed the uncut
            # embedding's rows (its own hold only its slice's ids)
            part.embed = full.embed
            got.append(part(x)._data)
    assert _err(jnp.concatenate(got, axis=-1), want) < 1e-5


# --------------------------------------------------------------- the builder
def test_builder_refuses_by_name_what_it_does_not_implement():
    assert block_pattern([SLIDING] * 4 + [FULL], 1) == "WDWEWEWE*E"
    assert block_pattern([SLIDING, SLIDING, SLIDING, FULL], 2) == "WDWDWE*E"
    with pytest.raises(ValueError, match="unknown layer type"):
        block_pattern(["linear_attention"], 0)
    for key, bad in (("n_group", 2), ("topk_group", 2),
                     ("rope_scaling", {"type": "yarn", "factor": 4}),
                     ("score_func", "softmax"), ("hidden_act", "gelu"),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=rf"not implemented: {key}$"):
            trinity_tiny(**{key: bad})
    with pytest.raises(ValueError, match="held"):
        trinity_tiny(experts_held=(14, 4))               # 16 experts
    with pytest.raises(ValueError, match="held"):
        trinity_tiny(vocab_held=(60, 8))                 # 64 rows
    with pytest.raises(ValueError, match="layer_types"):
        trinity_tiny(2, layer_types=(SLIDING, SLIDING, FULL))
    # the default pattern is the published period, repeated
    assert trinity_tiny(8, layer_types=(SLIDING,) * 3 + (FULL,),
                        num_dense_layers=2).pattern == "WDWDWE*EWEWEWE*E"
    net = trinity_tiny(experts_held=(8, 2), vocab_held=(16, 32))
    assert net.vocab_held == (16, 32)
    assert net.embed.weight.shape == net.head.weight.shape == (32, 32)
    attn, mlp, moe = (net.layers[i].mixer for i in (0, 1, 3))
    assert attn.q_proj.weight.shape == attn.g_proj.weight.shape == (32, 32)
    assert attn.k_proj.weight.shape == attn.v_proj.weight.shape == (16, 32)
    assert attn.q_norm_weight.shape == attn.k_norm_weight.shape == (8,)
    assert mlp.gate_up_proj.weight.shape == (96, 32)
    assert moe.router_weight.shape == (16, 32)           # whole
    assert moe.experts_up.shape == (2, 32, 32)           # the two held
    assert isinstance(moe, solar_open2.SwiGLUMoE)        # imported, not copied


# --------------------------------------------------------- the configuration
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Trinity-Mini), copied: the test reads no file outside the checkout
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = {"num_hidden_layers": 5, "layer_types": [SLIDING] * 4 + [FULL],
       "num_dense_layers": 1, "num_experts": 16, "vocab_size": 25024}


def _config():
    with open(CONFIG) as f:
        return json.load(f)


def test_configuration_file_holds_the_published_widths():
    cfg = _config()
    assert sorted(cfg["reduced"]) == sorted(CUT)
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert {k: cfg[k] for k in CUT} == CUT
    assert {k: cfg["published"][k] for k in CUT} \
        == {k: PUBLISHED[k] for k in CUT}
    kw = cfg["model"]["kwargs"]
    assert kw == {"num_experts": 128, "experts_held": [0, 16],
                  "vocab_size": 200192, "vocab_held": [0, 25024]}
    assert "8 chips share each layer" in cfg["deployment"]
    assert "4 sliding : 1 full" in cfg["deployment"]
    assert cfg["pattern"] == "WDWEWEWE*E" == block_pattern(
        CUT["layer_types"], CUT["num_dense_layers"])
    assert cfg["batch"] == 1 and cfg["sequence"] in (8192, 4096)
    assert set(cfg["assumed"]) >= {"embedding", "block", "attention",
                                   "experts", "initialisation", "optimizer",
                                   "dtype", "sequences"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == ("https://huggingface.co/arcee-ai/"
                               "Trinity-Mini/blob/main/config.json")


def _builder_kwargs(cfg):
    """`train_lm.build_net`'s rule: the top-level keys the signature names,
    then `model.kwargs` on top."""
    names = set(inspect.signature(trinity).parameters)
    kwargs = {k: v for k, v in cfg.items() if k in names}
    kwargs.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["model"]["kwargs"].items()})
    assert set(cfg["model"]["kwargs"]) <= names
    return kwargs


def test_the_builder_accepts_the_configuration_s_keys_at_a_small_size():
    cfg = _config()
    kwargs = _builder_kwargs(cfg)
    assert {"hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "layer_types", "num_dense_layers",
            "rms_norm_eps", "hidden_act", "tie_word_embeddings",
            "mup_enabled", "head_dim", "sliding_window", "rope_theta",
            "rope_scaling", "num_experts_per_tok", "num_shared_experts",
            "score_func", "route_norm", "route_scale", "n_group",
            "topk_group"} <= set(kwargs)
    kwargs.update(hidden_size=16, intermediate_size=24,
                  moe_intermediate_size=8, head_dim=4, vocab_size=128,
                  vocab_held=(0, 16))
    net = trinity(**kwargs)
    assert net.pattern == cfg["pattern"]
    assert set(cfg["reference"]["checked"]) <= set(net.collect_params())
    attn, moe = net.layers[0].mixer, net.layers[3].mixer
    assert attn._sizes == dict(heads=32, kv=4, hd=4, eps=1e-5, window=2048)
    assert net.layers[8].mixer._sizes["window"] is None
    assert moe.router_weight.shape == (128, 16)
    assert moe.experts_up.shape == (16, 16, 16)          # 16 of 128 experts
    assert moe._route == dict(top_k=8, scaling=2.826, norm_topk=True)


def test_the_net_of_the_configuration_counts_705_million_parameters():
    """Shapes only: the parameters are never initialised."""
    net = trinity(**_builder_kwargs(_config()))
    count = lambda ps: sum(int(onp.prod(p.shape)) for p in ps.values()
                           if p.grad_req != "null")
    per = [count(layer.collect_params()) for layer in net.layers]
    d = 2048
    norms = 2 * d
    attn = 2 * d * 4096 + 2 * d * 512 + 4096 * d + 2 * 128 + norms
    dense = 3 * d * 6144 + norms
    expert = 3 * d * 1024
    moe = 128 * d + 16 * expert + expert + norms
    assert per == [attn, dense, attn, moe, attn, moe, attn, moe, attn, moe]
    total = count(net.collect_params())
    assert total == 5 * attn + dense + 4 * moe + 2 * 25024 * d + d
    assert round(total / 1e6, 1) == 705.5
    assert round(12 * total / 1e9, 2) == 8.47      # GB of arguments


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "trinity.py")) as f:
        lines = [l for l in f if l.startswith(("import ", "from "))]
    assert lines and not any("mxnet_tpu" in l or "chipbench" in l
                             for l in lines)


@pytest.mark.parametrize("seq,window", [(8, 3), (8, 8), (8, 100), (5, 1)])
def test_band_pairs_is_a_count_by_hand(seq, window):
    flops = _load("chipbench", "flops_trinity.py")
    by_hand = sum(1 for i in range(seq) for j in range(seq)
                  if 0 <= i - j < window)
    assert flops.band_pairs(seq, window) == by_hand
    assert flops.band_pairs(seq) == seq * (seq + 1) // 2


def test_required_flops_equal_a_hand_count():
    flops = _load("chipbench", "flops_trinity.py")
    kwargs = _config()["flops"]["kwargs"]
    d, t = 2048, kwargs["seq"]
    proj = 2 * d * (3 * 4096 + 2 * 512)
    band = sum(min(i + 1, 2048) for i in range(t))
    triangle = t * (t + 1) // 2
    sliding = t * proj + 4 * 32 * 128 * band
    full = t * proj + 4 * 32 * 128 * triangle
    dense = t * 3 * 2 * d * 6144
    moe = t * (2 * d * 128 + 3 * 2 * d * 1024 + 8 * 16 / 128 * 3 * 2 * d * 1024)
    head = t * 2 * d * 25024
    assert flops.trinity_train(**kwargs) == pytest.approx(
        3 * (4 * sliding + full + dense + 4 * moe + head))
    shapes = {k: v for k, v in kwargs.items() if k != "pattern"}
    per = flops.per_block(**shapes)
    assert (per["W"], per["*"], per["D"], per["head"]) \
        == (sliding, full, dense, head)
    assert per["E"] == pytest.approx(moe)
    assert band / triangle < 0.44
    # the kernels' calls: forward twice (recomputed), backward once, 4 blocks
    assert flops.window_kernel_flops("mx_window_attn_fwd", **kwargs) \
        == 2 * 4 * 4 * 128 * 32 * band
    assert flops.window_kernel_flops("mx_window_attn_bwd", **kwargs) \
        == 4 * 10 * 128 * 32 * band
