"""Nemotron-H (models/nemotron_h.py) against its plain reference
(chipbench/reference/nemotron_h.py, loaded by path: it imports nothing from
the program) at small sizes on the CPU, seeded weights: each mixer and the
whole 9-layer pattern (forward, loss, gradients), the fused step, the
builder, and the configuration file against the catalog row.  The operators
and the expert shares are in test_nemotron_h_ops.py (another file, so
another worker takes them)."""
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
from mxnet_tpu.models.nemotron_h import nemotron_h, nemotron_h_tiny

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "nemotron3-nano-30b-a3b-train-ep16.json")


def _load(*parts):
    path = os.path.join(REPO, *parts)
    spec = importlib.util.spec_from_file_location(
        "nemotron_ref_" + parts[-1].replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("chipbench", "reference", "nemotron_h.py")

TINY = dict(mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
            ssm_state_size=8, num_experts_per_tok=3,
            routed_scaling_factor=2.5, norm_topk_prob=True,
            experts_held=(4, 4), num_attention_heads=4,
            num_key_value_heads=2, head_dim=8)
B, T = 2, 32


def _model(pattern, seed=1):
    mx.seed(seed)
    net = nemotron_h_tiny(pattern)
    net.initialize()
    net.hybridize()
    rs = onp.random.RandomState(seed)
    ids = rs.randint(0, 64, (B, T + 1)).astype("int32")
    cfg = dict(TINY, hybrid_override_pattern=pattern)
    return net, mx.np.array(ids[:, :-1]), mx.np.array(ids[:, 1:]), cfg


def _floats(net):
    return {n: p.data()._data for n, p in net.collect_params().items()
            if jnp.issubdtype(p.data()._data.dtype, jnp.floating)}


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------ the model vs the reference
@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*EME"])
def test_forward_matches_reference(pattern):
    net, x, _, cfg = _model(pattern)
    with jax.default_matmul_precision("highest"):
        got = net(x)._data
    want = ref.logits(_floats(net), x._data, cfg)
    assert got.shape == (B, T, 64)
    assert _err(got, want) < 1e-5


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*EME"])
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


def test_fused_step_loss_is_the_reference_loss_and_takes_the_blocked_route():
    net, x, y, cfg = _model("MEMEM*EME")
    want = float(ref.loss(_floats(net), x._data, y._data, cfg))
    step = Trainer(net.collect_params(), "adam",
                   {"learning_rate": 1e-2}).fuse_step(SoftmaxCrossEntropyLoss())
    c0 = dict(telemetry.raw_snapshot()["counters"])
    with jax.default_matmul_precision("highest"):
        first = float(step(x, y).asnumpy())
    c1 = dict(telemetry.raw_snapshot()["counters"])
    assert abs(first - want) < 1e-5
    routes = {k: c1[k] - c0.get(k, 0) for k in c1 if k.startswith("dispatch.")
              and c1[k] != c0.get(k, 0)}
    assert routes == {"dispatch.ssm.xla_chunked": 4,
                      "dispatch.moe.sorted_slots": 4,
                      "dispatch.attention.causal.xla_blocked": 1,
                      # head_dim 8 is no lane tile: the kernel says no
                      "dispatch.pallas.fallbacks.causal_attention.8": 1,
                      # and so does the scan's for a state of 8
                      "dispatch.pallas.fallbacks.ssd.8": 4,
                      # rows of 32 float32 are no lane block: XLA's adds
                      "dispatch.pallas.fallbacks.moe_rows.32": 4,
                      # and the products on the live rows: XLA's dense ones
                      "dispatch.pallas.fallbacks.moe_live.32": 4,
                      "dispatch.loss.linear_blocked": 1}


def test_fused_step_trains_counts_and_updates_the_load_state():
    net, x, y, _ = _model("MEMEM*EME")
    step = Trainer(net.collect_params(), "adam",
                   {"learning_rate": 1e-2}).fuse_step(SoftmaxCrossEntropyLoss())
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
    # 4 expert layers x 6 steps x B*T tokens x top-3
    assert d("moe.tokens_routed") == 4 * 6 * B * T * 3
    assert 0 < d("moe.tokens_held") < d("moe.tokens_routed")
    moe = net.layers[1].mixer
    load = moe.load.data().asnumpy()
    assert load.sum() == B * T * 3 and load.shape == (16,)
    assert moe.load_total.data().asnumpy().sum() == 7 * B * T * 3
    assert telemetry.raw_snapshot()["gauges"]["moe.load_max_over_mean"] >= 1000


def test_hlo_text_names_the_blocks_scopes():
    net, x, y, _ = _model("ME*")
    step = Trainer(net.collect_params(), "adam",
                   {"learning_rate": 1e-3}).fuse_step(SoftmaxCrossEntropyLoss())
    with pytest.raises(RuntimeError):
        step.hlo_text(x, y)
    step(x, y)
    before = telemetry.raw_snapshot()["counters"].get("fused.retraces", 0)
    text = step.hlo_text(x, y)
    assert telemetry.raw_snapshot()["counters"].get(
        "fused.retraces", 0) == before
    for scope in ("layers/0/", "ssm.scan", "ssm.conv", "layers/1/",
                  "moe.route", "moe.experts", "moe.shared", "layers/2/",
                  "attn.core", "mx.loss", "mx.opt"):
        assert scope in text, scope


# ------------------------------------------- the scan's kernels in the step
# sizes the route takes (`pallas_kernels.ssd_use_pallas`): chunks of 128
# steps, state 128, a group's two heads of 64 one 128-lane block
SCAN = dict(mamba_num_heads=4, mamba_head_dim=64, n_groups=2,
            ssm_state_size=128, chunk_size=128)


def _scan_model(pattern):
    mx.seed(5)
    net = nemotron_h_tiny(pattern, **SCAN)
    net.initialize()
    net.hybridize()
    ids = onp.random.RandomState(5).randint(0, 64, (1, 129)).astype("int32")
    return net, mx.np.array(ids[:, :-1]), mx.np.array(ids[:, 1:])


def _loss_and_grads(pattern):
    net, x, y = _scan_model(pattern)
    with autograd.record():
        l = SoftmaxCrossEntropyLoss()(net(x), y)
    l.backward()
    return float(l.mean().asnumpy()), {
        n: p.grad()._data for n, p in net.collect_params().items()
        if p.grad_req != "null"}


def test_on_one_tpu_every_mamba_layer_takes_the_scan_kernels(monkeypatch):
    """Under the interpret switch (what `one_tpu()` is on the chip) a step
    counts the forward kernel twice a Mamba-2 layer (its forward and its
    recomputation, each traced once), the composition not at all, and the
    compiled program names both kernels under the layer's `ssm.scan` scope:
    what keeps `ssm_ms.train` reading their time."""
    from mxnet_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_FORCE_INTERPRET", True)
    net, x, y = _scan_model("MEMEM*EME")
    step = Trainer(net.collect_params(), "adam",
                   {"learning_rate": 1e-3}).fuse_step(SoftmaxCrossEntropyLoss())
    c0 = dict(telemetry.raw_snapshot()["counters"])
    first = float(step(x, y).asnumpy())
    c1 = dict(telemetry.raw_snapshot()["counters"])
    assert onp.isfinite(first)
    routes = {k: c1[k] - c0.get(k, 0) for k in c1
              if (".ssd." in k or ".ssm." in k) and c1[k] != c0.get(k, 0)}
    assert routes == {"dispatch.pallas.hits.ssd.128": 8}
    text = step.hlo_text(x, y)
    # …/layers/0/mixer/ssm.scan/mx_ssd_fwd, and in the backward
    # …/layers/0/checkpoint/[rematted_computation/]mixer/ssm.scan/mx_ssd_*
    for i in (0, 2, 4, 7):
        for kernel in ("mx_ssd_fwd", "mx_ssd_bwd"):
            assert re.search(rf'op_name="[^"]*layers/{i}/[^"]*mixer/'
                             rf'ssm\.scan/{kernel}', text), (i, kernel)


def test_the_kernel_route_gives_the_composition_s_loss_and_gradients(
        monkeypatch):
    """Two Mamba-2 layers around an expert layer, the same seeded weights
    on both routes.  The kernels round their MXU operands to bfloat16 as
    the chip's default precision does; the CPU's composition keeps
    float32."""
    from mxnet_tpu.ops import pallas_kernels
    want_loss, want = _loss_and_grads("MEM")
    monkeypatch.setattr(pallas_kernels, "_FORCE_INTERPRET", True)
    telemetry.reset()
    got_loss, got = _loss_and_grads("MEM")
    counters = telemetry.raw_snapshot()["counters"]
    assert counters["dispatch.pallas.hits.ssd.128"] >= 2
    assert not counters.get("dispatch.ssm.xla_chunked")
    assert abs(got_loss - want_loss) < 1e-3 * abs(want_loss)
    assert set(got) == set(want)
    for name in want:
        assert _err(got[name], want[name]) < 3e-2, name


# --------------------------------------------------------------- the builder
def test_builder_checks_the_pattern_and_the_shares():
    with pytest.raises(ValueError, match="layers"):
        nemotron_h(hybrid_override_pattern="ME", hidden_size=32,
                   vocab_size=64, num_hidden_layers=3)
    with pytest.raises(ValueError, match="unknown mixer"):
        nemotron_h_tiny("MX")
    net = nemotron_h_tiny("E", vocab_held=(16, 32))
    assert net.vocab_held == (16, 32)
    assert net.embed.weight.shape == (32, 32)
    assert net.head.weight.shape == (32, 32)
    assert net.layers[0].mixer.experts_up.shape == (4, 32, 16)
    assert net.layers[0].mixer.router_weight.shape == (16, 32)


# --------------------------------------------------------- the configuration
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), copied: the test reads no file
# outside the checkout
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def test_configuration_file_holds_the_published_widths():
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"])                   # depth (two keys), experts, vocabulary
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["hybrid_override_pattern"] == \
        PUBLISHED["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert cfg["num_hidden_layers"] == 9
    assert cfg["n_routed_experts"] == 8 and cfg["vocab_size"] == 16384
    pub = cfg["published"]
    assert {k: pub[k] for k in cfg["reduced"]} == \
        {k: PUBLISHED[k] for k in cfg["reduced"]}
    kw = cfg["model"]["kwargs"]
    assert kw["n_routed_experts"] == 128 and kw["experts_held"] == [0, 8]
    assert kw["vocab_size"] == 131072 and kw["vocab_held"] == [0, 16384]
    assert "16 chips share each layer" in cfg["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"]
    assert "config.json" in entry["source"] and len(entry["source"]) <= 200


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "chipbench", "reference",
                           "nemotron_h.py")) as f:
        lines = [l for l in f if l.startswith(("import ", "from "))]
    assert lines and not any("mxnet_tpu" in l or "chipbench" in l
                             for l in lines)


def test_required_flops_equal_a_hand_count():
    flops = _load("chipbench", "flops_nemotron_h.py")
    with open(CONFIG) as f:
        kwargs = json.load(f)["flops"]["kwargs"]
    d, t = 2688, 8192
    mamba = 2 * d * (4096 + 6144 + 64) + 2 * 4096 * d \
        + 64.5 * (2 * 8 * 128 + 2 * 64 * 64) + 4 * 64 * 64 * 128
    experts = 2 * d * 128 + 2 * 2 * d * 3712 + 6 * 8 / 128 * 2 * 2 * d * 1856
    attention = 2 * (2 * d * 4096) + 2 * (2 * d * 256) \
        + 4 * 32 * 128 * (t + 1) / 2
    token = 4 * mamba + 4 * experts + attention + 2 * d * 16384
    assert flops.nemotron_h_train(**kwargs) == pytest.approx(3 * t * token)
    assert 0.70e9 < token < 0.73e9            # the issue's 0.72 GFLOP a token
