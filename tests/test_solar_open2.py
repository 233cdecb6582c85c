"""Solar-Open2 (models/solar_open2.py) against its plain reference
(chipbench/reference/solar_open2.py, loaded by path: it imports nothing
from the program) at small sizes on the CPU, seeded weights: each kind of
block and the whole period (forward, loss, gradients), the fused step, the
head shares that add up to the uncut mixer, the builder, and the
configuration file against the catalog row.  The operators and the expert
shares are in test_kda_ops.py."""
import importlib.util
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
from mxnet_tpu.models.solar_open2 import (GatedGQAttention, KDAMixer,
                                          block_pattern, solar_open2,
                                          solar_open2_tiny)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "solar-open2-250b-train-ep40tp8.json")


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "solar_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "solar_open2.py")

TINY = dict(hidden_size=32, head_dim=8, rms_norm_eps=1e-5,
            linear_attn_config={"head_dim": 8, "short_conv_kernel_size": 4},
            kda_allow_neg_eigval=True, num_experts_per_tok=3,
            norm_topk_prob=True, routed_scaling_factor=1.0,
            experts_held=(4, 4))
B, T = 2, 40                 # chunks of 16 steps: the last one is padded
# one layer of each mixer, and the period of four
SHAPES = {"*E": (1, (0,)), "KE": (1, ()), "*EKEKEKE": (4, (0,))}


def _model(pattern, seed=1):
    mx.seed(seed)
    layers, gqa = SHAPES[pattern]
    net = solar_open2_tiny(layers, gqa)
    net.initialize()
    net.hybridize()
    assert net.pattern == pattern
    rs = onp.random.RandomState(seed)
    ids = rs.randint(0, 64, (B, T + 1)).astype("int32")
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


def _step(net, lr=1e-2):
    return Trainer(net.collect_params(), "adam",
                   {"learning_rate": lr}).fuse_step(SoftmaxCrossEntropyLoss())


def test_fused_step_loss_is_the_reference_loss_and_counts_its_routes():
    net, x, y, cfg = _model("*EKEKEKE")
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
    assert routes == {"dispatch.kda.xla_chunked": 3,
                      # no TPU here: the chunk states run as the lax.scan
                      "dispatch.pallas.fallbacks.kda.8": 3,
                      "dispatch.moe.sorted_slots": 4,
                      "dispatch.attention.causal.xla_blocked": 1,
                      # head_dim 8 is no lane tile: the kernel says no
                      "dispatch.pallas.fallbacks.causal_attention.8": 1,
                      # rows of 32 float32 are no lane block: XLA's adds
                      "dispatch.pallas.fallbacks.moe_rows.32": 4,
                      # and the products on the live rows: XLA's dense ones
                      "dispatch.pallas.fallbacks.moe_live.32": 4,
                      "dispatch.loss.linear_blocked": 1}


def test_fused_step_trains_counts_and_updates_the_load_state():
    net, x, y, _ = _model("*EKEKEKE")
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
    # 4 expert blocks x 6 steps x B*T tokens x top-3
    assert d("moe.tokens_routed") == 4 * 6 * B * T * 3
    assert 0 < d("moe.tokens_held") < d("moe.tokens_routed")
    moe = net.layers[1].mixer
    load = moe.load.data().asnumpy()
    assert load.sum() == B * T * 3 and load.shape == (16,)
    assert moe.load_total.data().asnumpy().sum() == 7 * B * T * 3


def test_hlo_text_names_the_blocks_scopes():
    net, x, y, _ = _model("*EKEKEKE")
    step = _step(net, 1e-3)
    step(x, y)
    text = step.hlo_text(x, y)
    for scope in ("layers/0/", "attn.core", "attn.gate", "layers/1/",
                  "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
                  "moe.shared", "layers/2/", "kda.conv", "kda.gate",
                  "kda.scan", "kda.norm", "mx.loss", "mx.opt"):
        assert scope in text, scope
    # the scan's instructions lie under the KDA blocks, forward and backward
    for i in (2, 4, 6):
        assert re.search(rf'op_name="[^"]*jvp\(mx\.fwd\)[^"]*layers/{i}/'
                         rf'[^"]*mixer/kda\.scan/', text), i
    assert re.search(r'op_name="[^"]*transpose\(jvp\(mx\.fwd\)\)[^"]*'
                     r'layers/2/[^"]*mixer/kda\.scan/', text)
    assert not re.search(r'layers/[0135]/[^"]*kda\.scan', text)


# --------------------------------------- the shares of the heads add up
def _set(block, weights):
    block.initialize()
    for name, p in block.collect_params().items():
        p.set_data(mx.np.array(onp.asarray(weights[name])))


def _rows(w, first, count, per=1):
    return w[first * per:(first + count) * per]


def test_the_head_shares_of_a_kda_mixer_add_up_to_the_uncut_mixer():
    """Eight KDA heads uncut in the reference; four mixers of the program,
    each told its two heads and given their rows, return parts whose sum is
    the reference's output (no bias anywhere, so nothing counts twice)."""
    heads, hd, d, rank = 8, 8, 32, 8
    rs = onp.random.RandomState(11)
    draw = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    w = {f"{n}_proj.weight": draw(heads * hd, d) for n in "qkv"}
    w.update({f"{n}_conv_weight": draw(heads * hd, 4) for n in "qkv"})
    w.update({"f_a.weight": draw(rank, d), "f_b.weight": draw(heads * hd,
                                                              rank),
              "g_a.weight": draw(rank, d), "g_b.weight": draw(heads * hd,
                                                              rank),
              "b_proj.weight": draw(heads, d), "dt_bias": draw(heads * hd),
              "A_log": jnp.log(jnp.asarray(rs.uniform(1, 16, heads),
                                           jnp.float32)),
              "o_norm_weight": 1 + draw(hd),
              "o_proj.weight": draw(d, heads * hd)})
    x = draw(2, 24, d)
    cfg = dict(TINY, linear_attn_config={"head_dim": hd})
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda s: ref.kda(s, w, cfg))(x)
        total = 0
        for first in range(0, heads, 2):
            part = {k: (_rows(v, first, 2) if k in ("b_proj.weight", "A_log")
                        else v[:, first * hd:(first + 2) * hd]
                        if k == "o_proj.weight"
                        else v if k in ("f_a.weight", "g_a.weight",
                                        "o_norm_weight")
                        else _rows(v, first, 2, hd)) for k, v in w.items()}
            mixer = KDAMixer(d, heads, hd, heads_held=(first, 2),
                             chunk_size=16)
            _set(mixer, part)
            # a share alone is the reference given the same rows
            got = mixer(mx.np.array(onp.asarray(x)))._data
            assert _err(got, jax.vmap(
                lambda s: ref.kda(s, part, cfg))(x)) < 1e-5
            total = total + got
    assert _err(total, want) < 1e-5


def test_the_head_shares_of_gated_attention_add_up_to_the_uncut_mixer():
    """Eight query heads on four key-value heads uncut in the reference;
    four shares of one key-value head with its two query heads."""
    heads, kv, hd, d = 8, 4, 8, 32
    rs = onp.random.RandomState(12)
    draw = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    w = {"q_proj.weight": draw(heads * hd, d),
         "k_proj.weight": draw(kv * hd, d), "v_proj.weight": draw(kv * hd, d),
         "gate_proj.weight": draw(heads * hd, d),
         "o_proj.weight": draw(d, heads * hd)}
    x = draw(2, 24, d)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda s: ref.attention(s, w, TINY))(x)
        total = 0
        for share in range(kv):
            q0 = 2 * share
            part = {"q_proj.weight": _rows(w["q_proj.weight"], q0, 2, hd),
                    "gate_proj.weight": _rows(w["gate_proj.weight"], q0, 2,
                                              hd),
                    "k_proj.weight": _rows(w["k_proj.weight"], share, 1, hd),
                    "v_proj.weight": _rows(w["v_proj.weight"], share, 1, hd),
                    "o_proj.weight":
                        w["o_proj.weight"][:, q0 * hd:(q0 + 2) * hd]}
            mixer = GatedGQAttention(d, heads, kv, hd, heads_held=(q0, 2))
            _set(mixer, part)
            total = total + mixer(mx.np.array(onp.asarray(x)))._data
    assert _err(total, want) < 1e-5


# --------------------------------------------------------------- the builder
def test_builder_checks_its_keys_and_the_shares():
    assert block_pattern(4, (0,)) == "*EKEKEKE"
    assert block_pattern(48, range(0, 48, 4)).count("*") == 12
    for bad in ({"use_rope": True}, {"use_gqa_gate": False},
                {"kda_use_full_proj": True}, {"first_k_dense_replace": 1}):
        with pytest.raises(ValueError, match="solar_open2 builds"):
            solar_open2_tiny(**bad)
    with pytest.raises(ValueError, match="cut a group"):
        solar_open2_tiny(heads_held=(1, 2))          # groups of 2 query heads
    with pytest.raises(ValueError, match="held"):
        solar_open2_tiny(kda_heads_held=(3, 2))      # 4 KDA heads
    net = solar_open2_tiny(heads_held=(2, 2), kda_heads_held=(0, 1),
                           vocab_held=(16, 32))
    assert net.vocab_held == (16, 32)
    assert net.embed.weight.shape == net.head.weight.shape == (32, 32)
    attn, moe, kda = (net.layers[i].mixer for i in range(3))
    assert attn.q_proj.weight.shape == attn.gate_proj.weight.shape == (16, 32)
    assert attn.k_proj.weight.shape == (8, 32)
    assert attn.o_proj.weight.shape == (32, 16)
    assert kda.q_proj.weight.shape == (8, 32) and kda.A_log.shape == (1,)
    assert kda.f_a.weight.shape == (8, 32) and kda.f_b.weight.shape == (8, 8)
    assert kda.b_proj.weight.shape == (1, 32)
    assert moe.experts_up.shape == (4, 32, 32)       # [gate | up] fused
    assert moe.experts_down.shape == (4, 16, 32)
    assert moe.router_weight.shape == (16, 32)


# --------------------------------------------------------- the configuration
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Solar-Open2-250B), copied: the test reads no file outside the checkout
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
CUT = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 8,
       "vocab_size": 24576, "num_attention_heads": 8,
       "num_key_value_heads": 1}


def test_configuration_file_holds_the_published_widths():
    with open(CONFIG) as f:
        cfg = json.load(f)
    # the nested group is named by its top-level key and by the key in it
    assert sorted(cfg["reduced"]) == sorted(
        [*CUT, "linear_attn_config", "linear_attn_config.num_heads"])
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert {k: cfg[k] for k in CUT} == CUT
    # inside the group only the number of heads is the share; no width moved
    assert cfg["linear_attn_config"] == dict(
        PUBLISHED["linear_attn_config"], num_heads=8)
    pub = cfg["published"]
    assert {k: pub[k] for k in CUT} == {k: PUBLISHED[k] for k in CUT}
    assert pub["linear_attn_config"] == PUBLISHED["linear_attn_config"]
    kw = cfg["model"]["kwargs"]
    assert kw["n_routed_experts"] == 320 and kw["experts_held"] == [0, 8]
    assert kw["vocab_size"] == 196608 and kw["vocab_held"] == [0, 24576]
    assert kw["num_attention_heads"] == 64 and kw["heads_held"] == [0, 8]
    assert kw["num_key_value_heads"] == 8 and kw["kda_heads_held"] == [0, 8]
    assert kw["linear_attn_config"] == PUBLISHED["linear_attn_config"]
    assert "40 chips share each layer" in cfg["deployment"]
    assert cfg["pattern"] == block_pattern(4, [0]) == "*EKEKEKE"
    assert cfg["batch"] == 1 and cfg["sequence"] == 4096
    assert set(cfg["assumed"]) >= {"kda", "attention", "experts",
                                   "initialisation", "optimizer", "dtype"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == ("https://huggingface.co/upstage/"
                               "Solar-Open2-250B/blob/main/config.json")


def test_the_builder_accepts_the_configuration_s_keys_at_a_small_size():
    """`train_lm.build_net`'s rule (top-level keys the signature names, then
    `model.kwargs`) on the file's own keys, with the sizes shrunk: the
    builder takes every key the file hands it and builds the listed
    pattern and the tensors the file lists as checked."""
    import inspect
    with open(CONFIG) as f:
        cfg = json.load(f)
    names = set(inspect.signature(solar_open2).parameters)
    kwargs = {k: v for k, v in cfg.items() if k in names}
    kwargs.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["model"]["kwargs"].items()})
    assert set(cfg["model"]["kwargs"]) <= names
    assert {"hidden_size", "num_hidden_layers", "gqa_layers", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "rms_norm_eps",
            "use_rope", "use_gqa_gate", "kda_use_full_proj",
            "kda_allow_neg_eigval", "first_k_dense_replace",
            "n_shared_experts", "norm_topk_prob",
            "routed_scaling_factor"} <= set(kwargs)
    lin = dict(kwargs["linear_attn_config"], head_dim=8)
    kwargs.update(hidden_size=32, head_dim=8, moe_intermediate_size=16,
                  vocab_size=128, vocab_held=(0, 64),
                  linear_attn_config=lin)
    net = solar_open2(**kwargs)
    assert net.pattern == cfg["pattern"]
    params = set(net.collect_params())
    assert set(cfg["reference"]["checked"]) <= params
    attn = net.layers[0].mixer
    assert attn.q_proj.weight.shape == (8 * 8, 32)       # 8 of 64 heads
    assert attn.k_proj.weight.shape == (8, 32)           # 1 of 8
    assert net.layers[2].mixer.A_log.shape == (8,)
    assert net.layers[1].mixer.experts_up.shape == (8, 32, 32)
    assert net.layers[1].mixer.router_weight.shape == (320, 32)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "solar_open2.py")) as f:
        lines = [l for l in f if l.startswith(("import ", "from "))]
    assert lines and not any("mxnet_tpu" in l or "chipbench" in l
                             for l in lines)


def test_required_flops_equal_a_hand_count():
    flops = _load("chipbench", "flops_solar_open2.py")
    with open(CONFIG) as f:
        kwargs = json.load(f)["flops"]["kwargs"]
    d, t = 4096, kwargs["seq"]
    kda = 4 * 2 * d * 1024 + 2 * 2 * d * 128 + 2 * 2 * 128 * 1024 \
        + 2 * d * 8 + 32.5 * 8 * 10 * 128 + 3 * 2 * 8 * 128 * 128
    attention = 3 * 2 * d * 1024 + 2 * 2 * d * 128 \
        + 4 * 8 * 128 * (t + 1) / 2
    experts = 2 * d * 320 + 3 * 2 * d * 1280 \
        + 8 * 8 / 320 * 3 * 2 * d * 1280
    token = 3 * kda + attention + 4 * experts + 2 * d * 24576
    assert flops.solar_open2_train(**kwargs) == pytest.approx(3 * t * token)
    assert 0.51e9 < token < 0.52e9            # 0.5105 GFLOP a token at 4096
    at_8k = flops.per_token(**dict(kwargs, seq=8192))["token"]
    assert 0.51e9 < at_8k < 0.53e9            # the issue's 0.52 at 8192
    per = flops.per_token(**kwargs)
    assert per["K"] == pytest.approx(kda) and per["E"] == pytest.approx(
        experts) and per["*"] == pytest.approx(attention)


def test_the_parameter_count_of_the_cut_is_the_issue_s():
    """840.8 M parameters at the cell's sizes, from the shapes alone (no
    array is made): 12 B a parameter is 10.09 GB of arguments."""
    d, hd, h, f, v = 4096, 128, 8, 1280, 24576
    kda = 4 * d * h * hd + 3 * h * hd * 4 + 2 * (d * hd + hd * h * hd) \
        + d * h + h * hd + h + hd
    attention = 3 * d * h * hd + 2 * d * hd
    experts = 320 * d + 3 * d * f + 8 * 3 * d * f
    norms = 9 * d
    floats = 3 * kda + attention + 4 * experts + 2 * v * d + norms
    assert 840.5e6 < floats < 841.5e6
