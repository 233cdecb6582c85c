"""Olmo-Hybrid (models/olmo_hybrid.py) against its plain reference
(chipbench/reference/olmo_hybrid.py, loaded by path: it imports nothing from
the program) at small sizes on the CPU, seeded weights: each kind of block
and the whole period (forward, loss, gradients), the fused step, the head
shares that add up to the uncut mixer with the QK-norm's statistic summed
as the deployment sums it, the vocabulary slices, the builder, and the
configuration file against the catalog row.  The operator is in
test_gdn_ops.py."""
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
from mxnet_tpu.models.olmo_hybrid import (GatedDeltaNetMixer,
                                          QKNormAttention, block_pattern,
                                          olmo_hybrid, olmo_hybrid_tiny)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "olmo-hybrid-7b-train-tp2.json")
LINEAR, FULL = "linear_attention", "full_attention"


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "olmo_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "olmo_hybrid.py")

TINY = dict(hidden_size=32, num_attention_heads=4, rms_norm_eps=1e-6,
            linear_key_head_dim=12, linear_value_head_dim=24,
            linear_allow_neg_eigval=True)
B, T = 2, 40                 # chunks of 16 steps: the last one is padded
# one layer of each mixer, and the period of four
SHAPES = {"LF": (LINEAR,), "*F": (FULL,),
          "LFLFLF*F": (LINEAR, LINEAR, LINEAR, FULL)}


def _model(pattern, seed=1, **kwargs):
    mx.seed(seed)
    types = SHAPES[pattern]
    net = olmo_hybrid_tiny(len(types), layer_types=types, **kwargs)
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
    ``jax.grad`` of the reference, every trainable tensor: one gradient of
    each kind and all the others."""
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
        # backward() seeds ones over the per-sample losses: B x the mean's
        assert _err(p.grad()._data / B, grads[name]) < 2e-4, name


def test_the_post_norm_is_on_the_sub_layer_s_output():
    """A block is ``h + norm(sub(h))``: with the norm's weight at zero the
    block is the identity, which a pre-norm block ``h + sub(norm(h))`` is
    not."""
    net, x, _, _ = _model("LFLFLF*F")
    with jax.default_matmul_precision("highest"):
        h = net.embed(x)
        for layer in net.layers:
            layer.norm.gamma.set_data(mx.np.zeros(32))
            assert _err(layer(h)._data, h._data) == 0.0


def _step(net, lr=1e-2):
    return Trainer(net.collect_params(), "adam",
                   {"learning_rate": lr}).fuse_step(SoftmaxCrossEntropyLoss())


def test_fused_step_loss_is_the_reference_loss_and_counts_its_routes():
    net, x, y, cfg = _model("LFLFLF*F")
    want = float(ref.loss(_floats(net), x._data, y._data, cfg))
    step = _step(net)
    c0 = dict(telemetry.raw_snapshot()["counters"])
    with jax.default_matmul_precision("highest"):
        first = float(step(x, y).asnumpy())
    c1 = dict(telemetry.raw_snapshot()["counters"])
    assert abs(first - want) < 1e-5
    routes = {k: c1[k] - c0.get(k, 0) for k in c1 if k.startswith("dispatch.")
              and c1[k] != c0.get(k, 0)}
    # every block is traced once in the forward and once recomputed under
    # the checkpoint's transpose; the counters count the first only where
    # the second replays a cached trace
    assert routes == {"dispatch.gdn.xla_chunked": 3,
                      # no TPU here: the chunk states run as the lax.scan
                      "dispatch.pallas.fallbacks.gdn.12": 3,
                      "dispatch.attention.causal.xla_blocked": 1,
                      # head_dim 8 is no lane tile: the kernel says no
                      "dispatch.pallas.fallbacks.causal_attention.8": 1,
                      "dispatch.loss.linear_blocked": 1,
                      # one stored value a traced site: SwiGLU's output in
                      # the four MLPs, the sub-layer's output in all eight
                      "dispatch.materialized.mlp_act": 4,
                      "dispatch.materialized.sublayer_out": 8}


def test_fused_step_trains_without_retraces():
    net, x, y, _ = _model("LFLFLF*F")
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
    # no experts: nothing publishes a moe.* counter
    assert d("moe.tokens_routed") == 0


def test_hlo_text_names_the_blocks_scopes():
    net, x, y, _ = _model("LFLFLF*F")
    step = _step(net, 1e-3)
    step(x, y)
    text = step.hlo_text(x, y)
    for scope in ("layers/0/", "gdn.conv", "gdn.gate", "gdn.scan",
                  "gdn.norm", "layers/1/", "mlp.up", "mlp.act", "mlp.down",
                  "layers/6/", "attn.qknorm", "attn.core", "mx.loss",
                  "mx.opt"):
        assert scope in text, scope
    # the scan's instructions lie under the L blocks, forward and backward
    for i in (0, 2, 4):
        assert re.search(rf'op_name="[^"]*jvp\(mx\.fwd\)[^"]*layers/{i}/'
                         rf'[^"]*mixer/gdn\.scan/', text), i
    assert re.search(r'op_name="[^"]*transpose\(jvp\(mx\.fwd\)\)[^"]*'
                     r'layers/0/[^"]*mixer/gdn\.scan/', text)
    assert not re.search(r'layers/[13567]/[^"]*gdn\.scan', text)
    assert re.search(r'layers/6/[^"]*mixer/attn\.qknorm', text)
    assert not re.search(r'layers/[0-5]/[^"]*attn\.', text)
    assert re.search(r'layers/7/[^"]*mixer/mlp\.up', text)


# --------------------------------------- the shares of the heads add up
def _set(block, weights):
    block.initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.np.array(onp.asarray(weights[name])))


def _rows(w, first, count, per=1):
    return w[first * per:(first + count) * per]


def test_the_head_halves_of_a_linear_mixer_add_up_to_the_uncut_mixer():
    """Six linear heads uncut in the reference; two mixers of the program,
    each told its three heads and given their rows, return parts (before
    the block's post-norm) whose sum is the reference's output."""
    heads, dk, dv, d = 6, 12, 24, 32
    rs = onp.random.RandomState(11)
    draw = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    per = {"q": dk, "k": dk, "v": dv, "g": dv, "a": 1, "b": 1}
    w = {f"{n}_proj.weight": draw(heads * c, d) for n, c in per.items()}
    w.update({f"{n}_conv_weight": draw(heads * per[n], 4) for n in "qkv"})
    w.update({"dt_bias": draw(heads),
              "A_log": jnp.log(jnp.asarray(rs.uniform(1, 16, heads),
                                           jnp.float32)),
              "o_norm_weight": 1 + draw(dv),
              "o_proj.weight": draw(d, heads * dv)})
    x = draw(2, 24, d)
    cfg = dict(TINY, linear_key_head_dim=dk, linear_value_head_dim=dv)
    half = heads // 2

    def part_of(first):
        out = {}
        for name, v in w.items():
            n = name.split("_")[0]
            if name == "o_norm_weight":
                out[name] = v                      # replicated
            elif name == "o_proj.weight":
                out[name] = v[:, first * dv:(first + half) * dv]
            else:
                out[name] = _rows(v, first, half, per.get(n, 1))
        return out

    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda s: ref.gated_delta_net(s, w, cfg))(x)
        total = 0
        for first in (0, half):
            part = part_of(first)
            mixer = GatedDeltaNetMixer(d, heads, dk, dv,
                                       heads_held=(first, half),
                                       chunk_size=16)
            _set(mixer, part)
            # a share alone is the reference given the same rows
            got = mixer(mx.np.array(onp.asarray(x)))._data
            assert _err(got, jax.vmap(
                lambda s: ref.gated_delta_net(s, part, cfg))(x)) < 1e-5
            total = total + got
    assert _err(total, want) < 1e-5


def test_the_head_halves_of_the_full_mixer_add_up_under_a_summed_statistic():
    """Six heads uncut in the reference, whose QK-norm runs over all 48
    channels; the program's mixer told ``axis_name`` holds three heads, and
    its two halves run under ``jax.vmap(..., axis_name=)``, which adds the
    two sums of squares as the tensor-parallel group would: the parts
    (before the block's post-norm) add up to the reference's output.
    Without the axis a half norms over its own 24 channels - the chip's
    share as it is timed - and is the reference given the same rows."""
    heads, hd, d = 6, 8, 32
    rs = onp.random.RandomState(12)
    draw = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    w = {f"{n}_proj.weight": draw(heads * hd, d) for n in "qkv"}
    w.update({"q_norm_weight": 1 + draw(heads * hd),
              "k_norm_weight": 1 + draw(heads * hd),
              "o_proj.weight": draw(d, heads * hd)})
    x = draw(2, 24, d)
    cfg = dict(TINY, num_attention_heads=4)      # head_dim = 32 / 4 = 8
    half = heads // 2
    halves = [{k: (v[:, f * hd:(f + half) * hd] if k == "o_proj.weight"
                   else _rows(v, f, half, hd)) for k, v in w.items()}
              for f in (0, half)]
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda s: ref.attention(s, w, cfg))(x)
        mixer = QKNormAttention(d, heads, heads, hd, heads_held=(0, half),
                                axis_name="tp")
        mixer.initialize()
        fn, _ = mixer.pure_fn(train=False)
        stacked = {k: jnp.stack([h[k] for h in halves]) for k in w}
        parts = jax.vmap(lambda pv: fn(jax.random.PRNGKey(0), pv, x)[0],
                         axis_name="tp")(stacked)
        assert parts.shape == (2, *want.shape)
        assert _err(parts.sum(0), want) < 1e-5
        # the statistic matters: each half normed over its own channels
        # does not add up to the uncut mixer
        alone = 0
        for first, part in zip((0, half), halves):
            one = QKNormAttention(d, heads, heads, hd,
                                  heads_held=(first, half))
            _set(one, part)
            got = one(mx.np.array(onp.asarray(x)))._data
            assert _err(got, jax.vmap(
                lambda s: ref.attention(s, part, cfg))(x)) < 1e-5
            alone = alone + got
    assert _err(alone, want) > 1e-3


def test_the_eight_vocabulary_slices_logits_concatenate_to_the_uncut_head_s():
    """Eight nets that hold 8 of 64 rows each, given the uncut net's
    weights with their slice of the head: the logits side by side are the
    uncut head's (the embedding's rows are looked up by ids inside the
    slice, so every net is fed ids it holds rows for)."""
    full, x, _, cfg = _model("LF")
    weights = _floats(full)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(weights, x._data, cfg)
        hidden_in = full.embed(x)
        got = []
        for s in range(8):
            mx.seed(1)
            part = olmo_hybrid_tiny(1, layer_types=(LINEAR,), vocab_size=64,
                                    vocab_held=(8 * s, 8))
            part.initialize()
            assert part.head.weight.shape == (8, 32)
            for name, p in part.collect_params().items():
                v = weights[name]
                if name in ("head.weight", "embed.weight"):
                    v = v[8 * s:8 * s + 8]
                p.set_data(mx.np.array(onp.asarray(v)))
            # the slices share the hidden states: feed the uncut embedding's
            got.append(part.head(part.norm_f(part.layers(hidden_in)))._data)
    assert _err(jnp.concatenate(got, axis=-1), want) < 1e-5


# --------------------------------------------------------------- the builder
def test_builder_checks_its_keys_and_the_shares():
    assert block_pattern([LINEAR, LINEAR, LINEAR, FULL]) == "LFLFLF*F"
    with pytest.raises(ValueError, match="unknown layer type"):
        block_pattern(["sliding_attention"])
    for bad in ({"hidden_act": "gelu"}, {"attention_bias": True},
                {"tie_word_embeddings": True},
                {"rope_parameters": {"rope_theta": 500000}},
                {"linear_num_value_heads": 8}):
        with pytest.raises(ValueError, match="olmo_hybrid builds"):
            olmo_hybrid_tiny(**bad)
    with pytest.raises(ValueError, match="held"):
        olmo_hybrid_tiny(heads_held=(3, 2))          # 4 heads
    with pytest.raises(ValueError, match="layer_types"):
        olmo_hybrid_tiny(2, layer_types=(LINEAR, LINEAR, FULL))
    # the default pattern is the published period, repeated
    assert olmo_hybrid_tiny(8).pattern == "LFLFLF*F" * 2
    net = olmo_hybrid_tiny(heads_held=(2, 2), vocab_held=(16, 32),
                           rope_parameters={"rope_theta": None})
    assert net.vocab_held == (16, 32)
    assert net.embed.weight.shape == net.head.weight.shape == (32, 32)
    lin, mlp, attn = net.layers[0].mixer, net.layers[1].mixer, \
        net.layers[6].mixer
    assert lin.q_proj.weight.shape == lin.k_proj.weight.shape == (24, 32)
    assert lin.v_proj.weight.shape == lin.g_proj.weight.shape == (48, 32)
    assert lin.a_proj.weight.shape == lin.b_proj.weight.shape == (2, 32)
    assert lin.q_conv_weight.shape == (24, 4)
    assert lin.v_conv_weight.shape == (48, 4)
    assert lin.A_log.shape == lin.dt_bias.shape == (2,)
    assert lin.o_norm_weight.shape == (24,)
    assert lin.o_proj.weight.shape == (32, 48)
    assert attn.q_proj.weight.shape == attn.k_proj.weight.shape == (16, 32)
    assert attn.q_norm_weight.shape == attn.k_norm_weight.shape == (16,)
    assert attn.o_proj.weight.shape == (32, 16)
    assert mlp.gate_up_proj.weight.shape == (96, 32)     # whole, fused
    assert mlp.down_proj.weight.shape == (32, 48)
    assert "experts_held" not in inspect.signature(olmo_hybrid).parameters


# --------------------------------------------------------- the configuration
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Olmo-Hybrid-7B), copied: the test reads no file outside the checkout
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
CUT = {"num_hidden_layers": 4, "layer_types": [LINEAR, LINEAR, LINEAR, FULL],
       "num_attention_heads": 15, "num_key_value_heads": 15,
       "linear_num_key_heads": 15, "linear_num_value_heads": 15,
       "vocab_size": 12544}


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
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert kw[key] == 30, key
    assert kw["heads_held"] == [0, 15]
    assert kw["vocab_size"] == 100352 and kw["vocab_held"] == [0, 12544]
    assert "experts_held" not in kw
    assert "2 chips share each layer" in cfg["deployment"]
    assert cfg["pattern"] == block_pattern(CUT["layer_types"]) == "LFLFLF*F"
    assert cfg["batch"] == 1 and cfg["sequence"] in (8192, 4096)
    assert set(cfg["assumed"]) >= {"head_dim", "linear_attention", "block",
                                   "positions", "initialisation",
                                   "optimizer", "dtype", "sequences"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == ("https://huggingface.co/allenai/"
                               "Olmo-Hybrid-7B/blob/main/config.json")


def _builder_kwargs(cfg):
    """`train_lm.build_net`'s rule: the top-level keys the signature names,
    then `model.kwargs` on top."""
    names = set(inspect.signature(olmo_hybrid).parameters)
    kwargs = {k: v for k, v in cfg.items() if k in names}
    kwargs.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["model"]["kwargs"].items()})
    assert set(cfg["model"]["kwargs"]) <= names
    return kwargs


def test_the_builder_accepts_the_configuration_s_keys_at_a_small_size():
    cfg = _config()
    kwargs = _builder_kwargs(cfg)
    assert {"hidden_size", "intermediate_size", "num_hidden_layers",
            "layer_types", "rms_norm_eps", "hidden_act", "attention_bias",
            "tie_word_embeddings", "rope_parameters", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval"} <= set(kwargs)
    kwargs.update(hidden_size=60, intermediate_size=16,
                  linear_key_head_dim=4, linear_value_head_dim=8,
                  vocab_size=128, vocab_held=(0, 16))
    net = olmo_hybrid(**kwargs)
    assert net.pattern == cfg["pattern"]
    assert set(cfg["reference"]["checked"]) <= set(net.collect_params())
    lin, attn = net.layers[0].mixer, net.layers[6].mixer
    assert lin.A_log.shape == (15,)                      # 15 of 30 heads
    assert lin.q_proj.weight.shape == (15 * 4, 60)
    assert lin.v_proj.weight.shape == (15 * 8, 60)
    assert attn.q_proj.weight.shape == (15 * 2, 60)      # head_dim 60 / 30
    assert attn.k_norm_weight.shape == (15 * 2,)


def test_the_net_of_the_configuration_counts_766_million_parameters():
    """Shapes only: the parameters are never initialised."""
    net = olmo_hybrid(**_builder_kwargs(_config()))
    count = lambda ps: sum(int(onp.prod(p.shape)) for p in ps.values())
    per = [count(layer.collect_params()) for layer in net.layers]
    d = 3840
    linear = d * 15 * (96 + 96 + 192 + 192) + 15 * 192 * d + 2 * d * 15 \
        + 4 * 15 * (96 + 96 + 192) + 2 * 15 + 192 + d
    full = 4 * d * 15 * 128 + 2 * 15 * 128 + d
    mlp = 3 * d * 11008 + d
    assert per == [linear, mlp, linear, mlp, linear, mlp, full, mlp]
    total = count(net.collect_params())
    assert total == 3 * linear + full + 4 * mlp + 2 * 12544 * d + d
    assert abs(total / 766.2e6 - 1) < 0.01


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "olmo_hybrid.py")) as f:
        lines = [l for l in f if l.startswith(("import ", "from "))]
    assert lines and not any("mxnet_tpu" in l or "chipbench" in l
                             for l in lines)


def test_required_flops_equal_a_hand_count():
    flops = _load("chipbench", "flops_olmo_hybrid.py")
    kwargs = _config()["flops"]["kwargs"]
    d, t = 3840, kwargs["seq"]
    linear = 2 * d * 15 * (96 + 96 + 192 + 192) + 2 * 15 * 192 * d \
        + 2 * 2 * d * 15 \
        + 32.5 * 15 * (4 * 96 + 2 * (192 + 96) + 2 * 192) \
        + 3 * 2 * 15 * 96 * 192
    full = 4 * 2 * d * 15 * 128 + 4 * 15 * 128 * (t + 1) / 2
    mlp = 3 * 2 * d * 11008
    token = 3 * linear + full + 4 * mlp + 2 * d * 12544
    assert flops.olmo_hybrid_train(**kwargs) == pytest.approx(3 * t * token)
    per = flops.per_token(**kwargs)
    assert per["F"] == mlp and per["head"] == 2 * d * 12544
    assert per["L"] == pytest.approx(linear)
    assert per["*"] == pytest.approx(full)
