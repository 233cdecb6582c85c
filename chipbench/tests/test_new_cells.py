"""The cells PR 29 added, on the CPU at toy size: both new runners' code
paths as far as a CPU takes them (``train_mesh`` on four virtual devices,
``train_lm`` against the plain reference), the new readers with nothing to
read, ``scope_reduce`` on a hand-made trace, and that adding all of it
changed no file the benchmark already had.  Not a measurement."""
import copy
import hashlib
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from chipbench import files, run, scope_reduce  # noqa: E402

TOY_LM = {
    "name": "toy-nemotron", "hybrid_override_pattern": "ME*",
    "num_hidden_layers": 3, "hidden_size": 32, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 8, "chunk_size": 8,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "n_routed_experts": 4,
    "vocab_size": 64,
    "model": {"module": "mxnet_tpu.models.nemotron_h",
              "builder": "nemotron_h",
              "kwargs": {"n_routed_experts": 16, "experts_held": [4, 4],
                         "vocab_size": 128, "vocab_held": [0, 64]}},
    "entry": {"kind": "Trainer.fuse_step"}, "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {"learning_rate": 0.01}},
    "batch": 2, "sequence": 32,
    "flops": {"module": "flops_nemotron_h", "function": "nemotron_h_train",
              "kwargs": {"pattern": "ME*", "hidden": 32, "seq": 32,
                         "vocab_rows": 64, "mamba_heads": 4,
                         "mamba_head_dim": 8, "n_groups": 2, "state": 8,
                         "chunk": 8, "experts": 16, "experts_held": 4,
                         "top_k": 3, "expert_width": 16, "shared_width": 24,
                         "heads": 4, "kv_heads": 2, "head_dim": 8}},
    "reference": {"module": "reference/nemotron_h.py",
                  "tolerances": {"loss_rtol": 1e-3, "logits": 1e-2,
                                 "logits_median": 1e-2, "grad": 2e-2}},
    "reduced": [],
}
TOY_BERT = {
    "name": "toy-bert-small",
    "model": {"module": "mxnet_tpu.models.bert_gluon",
              "builder": "bert_small", "kwargs": {}},
    "entry": {"kind": "Trainer.fuse_step"}, "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {"learning_rate": 0.001}},
    "batch": 2,
    "inputs": {"x": {"shape": [32], "dtype": "int32", "dist": "randint",
                     "high": 1000},
               "y": {"shape": [32], "dtype": "int32", "dist": "randint",
                     "high": 1000}},
    "flops": {"function": "bert_mlm", "kwargs": {
        "units": 64, "heads": 4, "layers": 2, "ffn_units": 128,
        "vocab_size": 1000, "seq": 32}},
    "reference": {"rtol": 0.02}, "reduced": [],
}
NEW_READERS = ["ssm_ms.train", "moe_ms.train", "attn_ms.train",
               "moe_tokens_per_expert.train"]


def _digest(root):
    out = {}
    for base, _dirs, names in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in names:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(os.path.join(root, "chipbench"))
    bench = files.load_json(REPO, "BENCHMARK.json")
    was = copy.deepcopy(bench)
    mixes = {"lm2": {"runner": "train_lm"},
             "mesh2": {"runner": "train_mesh", "mesh": {"dp": 2, "tp": 2},
                       "global_batch": 8}}
    for name, mix in mixes.items():
        with open(os.path.join(root, f"chipbench/traffic/{name}.json"),
                  "w") as f:
            json.dump(dict(mix, name=name, batches=2, warmup_steps=2,
                           trace_steps=10), f)
    for cfg, mix, chips in ((TOY_LM, "lm2", 1), (TOY_BERT, "mesh2", 4)):
        path = f"chipbench/configs/{cfg['name']}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": cfg["name"], "source": "test",
                                 "file": path, "reduced": [], "why": "toy"})
        bench["workloads"].append({
            "name": f"{cfg['name']}.{mix}", "config": cfg["name"],
            "traffic": mix, "chips": chips, "why": "toy"})
    for m in bench["per_layer"]:     # a toy cell reports what its model reports
        for real, toy in (("nemotron3-nano-train-8k", "toy-nemotron.lm2"),
                          ("bert-train-dp2tp2", "toy-bert-small.mesh2")):
            if real in m.get("workloads", ()):
                m["workloads"].append(toy)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digest(os.path.join(root, "chipbench"))
    assert {k: after[k] for k in before} == before, "an existing file changed"
    for key in ("configs", "workloads", "end_to_end"):
        assert bench[key][:len(was[key])] == was[key]
    return root


def test_nothing_of_the_accepted_benchmark_was_taken_away():
    """Every file and entry PR 28's benchmark had is still there, in its
    place; what was added stands at the end of its list."""
    for had in ("files.py", "flops.py", "peaks.json", "run.py",
                "trace_reduce.py", "runners/train.py", "traffic/ring.json",
                "configs/bert-base-train-fp32.json",
                "configs/resnet50_v1-train-bf16.json",
                "tests/test_chipbench.py", "tests/test_program_readers.py"):
        assert os.path.exists(os.path.join(REPO, "chipbench", had)), had
    bench = files.load_json(REPO, "BENCHMARK.json")
    assert [c["name"] for c in bench["configs"]][:2] == [
        "resnet50_v1-train-bf16", "bert-base-train-fp32"]
    assert [w["name"] for w in bench["workloads"]] == [
        "resnet50-train-ring", "bert-train-ring", "nemotron3-nano-train-8k",
        "bert-train-dp2tp2"]
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 1, 4]
    assert [m["name"] for m in bench["per_layer"]][11:] == NEW_READERS
    assert bench["run_seconds"] == 30
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_samples_s", 0.01), ("step_p95_ms", 0.01), ("setup_s", 0.1)]


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_runs_against_the_plain_reference(toy_root, trace):
    line = run.run_cell("toy-nemotron.lm2", seed=2 ** 31 + 7, seconds=1.0,
                        trace=trace, devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "step_build_s.train", "compile_cache_misses.train",
                "moe_tokens_per_expert.train"} <= got
        # 2 x 32 tokens x top-3 x 4 of 16 experts held / 4 experts, 1 layer:
        # 12 a step on average; the router is random, so roughly
        assert 4 < line["metrics"]["moe_tokens_per_expert.train"]["value"] < 24
        # no TPU plane in a CPU trace: the scope readers say nothing
        assert not got & {"ssm_ms.train", "moe_ms.train", "attn_ms.train",
                          "device_step_ms.train", "mfu.train"}
    else:
        assert got == {"train_samples_s", "step_p95_ms", "setup_s"}


def test_train_lm_refuses_a_model_that_is_not_the_reference(toy_root):
    """A tolerance of the comparison is what decides ``correct``: with the
    reference told another scaling factor the logits check fails."""
    cfg = files.load_json(toy_root, "chipbench/configs/toy-nemotron.json")
    cfg["name"] = "toy-nemotron-off"
    cfg["routed_scaling_factor"] = 2.0      # the reference reads this key
    cfg["model"]["kwargs"]["routed_scaling_factor"] = 2.5
    with open(os.path.join(toy_root, "chipbench/configs/toy-nemotron-off"
                           ".json"), "w") as f:
        json.dump(cfg, f)
    bench = files.load_json(toy_root, "BENCHMARK.json")
    bench["configs"].append({"name": cfg["name"], "source": "t", "reduced": [],
                             "file": "chipbench/configs/toy-nemotron-off.json",
                             "why": "t"})
    bench["workloads"].append({"name": "off.lm2", "config": cfg["name"],
                               "traffic": "lm2", "chips": 1, "why": "t"})
    with open(os.path.join(toy_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line = run.run_cell("off.lm2", seed=3, seconds=0.5, trace=0,
                        devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is False


@pytest.mark.parametrize("trace", [0, 1])
def test_train_mesh_runs_on_four_devices(toy_root, trace):
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 CPU devices (XLA_FLAGS was set too late)")
    line = run.run_cell("toy-bert-small.mesh2", seed=5, seconds=1.0,
                        trace=trace, devs=devs[:4], root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "step_build_s.train"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_samples_s", "step_p95_ms",
                                        "setup_s"}
        assert line["attempted"] >= 10


def test_new_readers_say_nothing_without_evidence():
    for name in NEW_READERS:
        reader = files.load_module(REPO, "chipbench", "layer_metrics",
                                   name + ".py")
        assert reader.read({}) is None
        assert reader.read({"steps": 20, "trace": None, "moe": None,
                            "layer_kind_s": None}) is None
    moe = files.load_module(REPO, "chipbench", "layer_metrics",
                            "moe_tokens_per_expert.train.py")
    assert moe.read({"steps": 20, "moe": {
        "tokens_held": 20 * 4 * 8 * 384, "tokens_routed": 20 * 4 * 49152,
        "experts_held": 8, "expert_layers": 4}}) == 384.0
    ssm = files.load_module(REPO, "chipbench", "layer_metrics",
                            "ssm_ms.train.py")
    assert ssm.read({"steps": 20, "layer_kind_s": {"M": 2.0, "E": 1.0}}) \
        == 100.0
    attn = files.load_module(REPO, "chipbench", "layer_metrics",
                             "attn_ms.train.py")
    assert attn.read({"steps": 20, "layer_kind_s": {"M": 2.0}}) is None


HLO_TEXT = '''
HloModule jit_step, entry_computation_layout={()}

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(mx.fwd)/layers/0/mixer/ssm.scan/mul"}
}

ENTRY %main {
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp(mx.fwd)/layers/0/checkpoint/mixer/ssm.scan/mul" source_file="x.py" source_line=3}
  %custom-call.7 = f32[8,4]{1,0} custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="some-kernel"}
  %relu2.7 = f32[8,4]{1,0} fusion(%b), kind=kLoop, calls=%g, metadata={op_name="jit(step)/transpose(jvp(mx.fwd))/layers/1/checkpoint/mixer/moe.experts/mul"}
  %while.9 = (s32[], f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(mx.fwd)/layers/2/mixer/attn.core/while"}
  %adam.1 = f32[8]{0} fusion(%g), kind=kLoop, calls=%f, metadata={op_name="jit(step)/mx.opt/layers.10.mixer.w/mul"}
  ROOT %copy.2 = f32[8]{0} copy(%adam.1)
}
'''


def test_scope_reduce_on_a_hand_made_trace():
    scopes = scope_reduce.instruction_scopes(HLO_TEXT)
    assert scopes["fusion.3"].endswith("layers/0/checkpoint/mixer/ssm.scan/mul")
    assert "copy.2" not in scopes and "adam.1" in scopes
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1000,
             100),
            ("%custom-call.7 = f32[8,4]{1,0} custom-call(%b)", 1100, 150),
            ("%relu2.7 = f32[8,4]{1,0} fusion(%b), kind=kLoop", 1250, 50),
            ("%while.9 = (s32[], f32[8]) while(%t)", 1300, 400),
            ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1400,
             100),                       # inside the while: its own time
            ("%adam.1 = f32[8]{0} fusion(%g), kind=kLoop", 1700, 50),
            ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 100,
             500)]}]},                   # before the window
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("chipbench.window", 1000, 1000)]}]}]
    secs = scope_reduce.op_self_seconds(planes)
    assert secs == pytest.approx({"fusion.3": 200e-9, "relu2.7": 50e-9,
                                  "custom-call.7": 150e-9,
                                  "while.9": 300e-9, "adam.1": 50e-9})
    kinds = scope_reduce.layer_kind_seconds(secs, scopes, "ME*")
    assert kinds == pytest.approx({"M": 200e-9, "E": 50e-9, "*": 300e-9})
    # an instruction whose scope names no layer belongs to no kind
    assert scopes["custom-call.7"] == "some-kernel"
    marks = scope_reduce.marker_seconds(secs, scopes,
                                        ("moe.experts", "attn.core"))
    assert marks == pytest.approx({"moe.experts": 50e-9, "attn.core": 300e-9,
                                   "(no scope)": 0.0})
    # layers.10 of mx.opt is no layer scope, and a pattern shorter than the
    # index (or without experts) takes neither
    assert scope_reduce.layer_kind_seconds(secs, scopes, "M") == \
        pytest.approx({"M": 200e-9})
    assert scope_reduce.op_self_seconds(planes[:1]) is None
    assert scope_reduce.layer_kind_seconds(None, scopes, "ME*") is None
    assert scope_reduce.layer_kind_seconds(secs, {}, "ME*") is None
