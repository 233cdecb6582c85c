"""The cells PR 34 added, on the CPU at toy size: ``train_lm_listed`` on a
tiny Solar-Open2 against the plain reference, ``train_feed`` on a tiny
ResNet fed uint8 NHWC host batches, the three new readers with and without
evidence, the new configuration and its FLOPs, and that adding all of it
changed no file the benchmark already had.  Not a measurement."""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from chipbench import files, run  # noqa: E402

CONFIG = "chipbench/configs/solar-open2-250b-train-ep40tp8.json"
LIN = {"short_conv_kernel_size": 4, "head_dim": 8, "num_heads": 2,
       "num_kv_heads": None}
TOY_SOLAR = {
    "name": "toy-solar", "hidden_size": 32, "num_hidden_layers": 2,
    "gqa_layers": [0], "num_attention_heads": 2, "num_key_value_heads": 1,
    "head_dim": 8, "linear_attn_config": LIN, "vocab_size": 64,
    "moe_intermediate_size": 16, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 0, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 3,
    "pattern": "*EKE",
    "model": {"module": "mxnet_tpu.models.solar_open2",
              "builder": "solar_open2",
              "kwargs": {"num_attention_heads": 4, "num_key_value_heads": 2,
                         "heads_held": [2, 2],
                         "linear_attn_config": dict(LIN, num_heads=4),
                         "kda_heads_held": [0, 2], "kda_chunk_size": 16,
                         "n_routed_experts": 16, "experts_held": [4, 4],
                         "vocab_size": 128, "vocab_held": [0, 64]}},
    "entry": {"kind": "Trainer.fuse_step"}, "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {"learning_rate": 0.01}},
    "batch": 2, "sequence": 40,
    "flops": {"module": "flops_solar_open2", "function": "solar_open2_train",
              "kwargs": {"pattern": "*EKE", "hidden": 32, "seq": 40,
                         "vocab_rows": 64, "kda_heads": 2, "kda_head_dim": 8,
                         "gate_rank": 8, "chunk": 16, "heads": 2,
                         "kv_heads": 1, "head_dim": 8, "experts": 16,
                         "experts_held": 4, "top_k": 3, "expert_width": 16,
                         "shared_width": 16}},
    "scopes": ["kda.scan", "kda.conv", "moe.experts", "attn.core", "mx.opt"],
    "reference": {"module": "reference/solar_open2.py",
                  "checked": ["embed.weight", "layers.2.mixer.q_proj.weight",
                              "layers.2.mixer.A_log",
                              "layers.2.mixer.q_conv_weight",
                              "layers.2.mixer.f_b.weight",
                              "layers.1.mixer.router_weight",
                              "layers.1.mixer.experts_up",
                              "layers.0.mixer.gate_proj.weight"],
                  "tolerances": {"loss_rtol": 1e-3, "logits": 1e-2,
                                 "logits_median": 1e-2, "grad": 2e-2}},
    "reduced": [],
}
TOY_RESNET = {
    "name": "toy-resnet18-fed",
    "model": {"module": "mxnet_tpu.models.resnet", "builder": "resnet18_v1",
              "kwargs": {"classes": 10}},
    "entry": {"kind": "FusedTrainStep", "dtype": "bfloat16"},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "sgd", "params": {
        "learning_rate": 0.005, "momentum": 0.9, "wd": 0.0001}},
    "batch": 4,
    # bf16 batch statistics over 4 rows of 1x1 pixels in the last stage
    "reference": {"rtol": 0.1}, "reduced": [],
}
NEW_READERS = ["kda_ms.train", "kda_scan_ms.train", "feed_wait_ms.train"]
NEW_FILES = {"configs/solar-open2-250b-train-ep40tp8.json",
             "flops_solar_open2.py", "reference/solar_open2.py",
             "reference/bf16_control.py",
             "runners/train_lm_listed.py", "runners/train_feed.py",
             "traffic/ring-lm-listed.json", "traffic/feed.json",
             "tests/test_solar_cells.py", "README.listed.md",
             *(f"layer_metrics/{n}.py" for n in NEW_READERS)}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = files.load_json(REPO, "BENCHMARK.json")
    feed = files.load_json(REPO, "chipbench/traffic/feed.json")
    mixes = {"listed2": {"runner": "train_lm_listed"},
             "feed2": dict(feed, wire=dict(feed["wire"], shape=[32, 32, 3]),
                           classes=10)}
    for name, mix in mixes.items():
        with open(os.path.join(root, f"chipbench/traffic/{name}.json"),
                  "w") as f:
            json.dump(dict(mix, name=name, batches=2, warmup_steps=2,
                           trace_steps=10), f)
    for cfg, mix in ((TOY_SOLAR, "listed2"), (TOY_RESNET, "feed2")):
        path = f"chipbench/configs/{cfg['name']}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": cfg["name"], "source": "test",
                                 "file": path, "reduced": [], "why": "toy"})
        bench["workloads"].append({
            "name": f"{cfg['name']}.{mix}", "config": cfg["name"],
            "traffic": mix, "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:     # a toy cell reports what its model reports
        for real, toy in (("solar-open2-train-kda", "toy-solar.listed2"),
                          ("resnet50-train-feed", "toy-resnet18-fed.feed2")):
            if real in m.get("workloads", ()):
                m["workloads"].append(toy)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_only_files_were_added_and_entries_appended():
    """Against the parent commit: no file under ``chipbench/`` the benchmark
    had is changed or gone, and ``BENCHMARK.json``'s old entries stand where
    they stood, the ``workloads`` lists of metrics only longer."""
    def git(*args):
        return subprocess.run(["git", "-C", REPO, *args], check=True,
                              capture_output=True, text=True).stdout
    try:
        base = git("merge-base", "HEAD", "aea2721c1df8a9781eb7aef87f1e651808a81cfa").strip()
        was = json.loads(git("show", f"{base}:BENCHMARK.json"))
        changed = git("diff", "--name-status", base, "--", "chipbench")
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history to compare with")
    for line in changed.splitlines():
        status, path = line.split("\t")[0], line.split("\t")[-1]
        assert status == "A", line
        assert path[len("chipbench/"):] in NEW_FILES, line
    now = files.load_json(REPO, "BENCHMARK.json")
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert now[key] == was[key]
    for key in ("configs", "workloads"):
        assert now[key][:len(was[key])] == was[key]
    assert [w["name"] for w in now["workloads"]][len(was["workloads"]):] == [
        "solar-open2-train-kda", "resnet50-train-feed"]
    assert [c["name"] for c in now["configs"]][len(was["configs"]):] == [
        "solar-open2-250b-train-ep40tp8"]
    assert [m["name"] for m in now["per_layer"]][len(was["per_layer"]):] \
        == NEW_READERS
    for old, new in zip(was["per_layer"], now["per_layer"]):
        grown = dict(new)
        if "workloads" in old:
            assert new["workloads"][:len(old["workloads"])] == old["workloads"]
            grown["workloads"] = old["workloads"]
        assert grown == old
    assert all(w["chips"] == 1 for w in now["workloads"][len(was["workloads"]):])


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_listed_runs_against_the_plain_reference(toy_root, trace):
    line = run.run_cell("toy-solar.listed2", seed=2 ** 31 + 11, seconds=1.0,
                        trace=trace, devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "step_build_s.train", "compile_cache_misses.train",
                "moe_tokens_per_expert.train"} <= got
        # 2 x 40 tokens x top-3 x 4 of 16 held / 4 experts: 15 on average
        assert 5 < line["metrics"]["moe_tokens_per_expert.train"]["value"] < 30
        # no TPU plane in a CPU trace: the scope readers say nothing
        assert not got & {"kda_ms.train", "kda_scan_ms.train", "moe_ms.train",
                          "attn_ms.train", "device_step_ms.train",
                          "mfu.train"}
    else:
        assert got == {"train_samples_s", "step_p95_ms", "setup_s"}


def test_train_lm_listed_refuses_a_model_that_is_not_the_reference(toy_root):
    """Against a reference that scales q by head_dim ** -0.4 the logits
    check fails: a tolerance decides ``correct``."""
    ref_dir = os.path.join(toy_root, "chipbench", "reference")
    with open(os.path.join(ref_dir, "solar_open2.py")) as f:
        text = f.read()
    assert "hd ** -0.5" in text
    with open(os.path.join(ref_dir, "solar_off.py"), "w") as f:
        f.write(text.replace("hd ** -0.5", "hd ** -0.4"))
    cfg = files.load_json(toy_root, "chipbench/configs/toy-solar.json")
    cfg["name"] = "toy-solar-off"
    cfg["reference"]["module"] = "reference/solar_off.py"
    with open(os.path.join(toy_root, "chipbench/configs/toy-solar-off.json"),
              "w") as f:
        json.dump(cfg, f)
    bench = files.load_json(toy_root, "BENCHMARK.json")
    bench["configs"].append({"name": cfg["name"], "source": "t", "reduced": [],
                             "file": "chipbench/configs/toy-solar-off.json",
                             "why": "t"})
    bench["workloads"].append({"name": "off.listed2", "config": cfg["name"],
                               "traffic": "listed2", "chips": 1, "why": "t"})
    with open(os.path.join(toy_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line = run.run_cell("off.listed2", seed=3, seconds=0.5, trace=0,
                        devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is False


@pytest.mark.parametrize("trace", [0, 1])
def test_train_feed_runs_uint8_nhwc_batches_through_the_feed(toy_root, trace):
    line = run.run_cell("toy-resnet18-fed.feed2", seed=2 ** 31 + 5,
                        seconds=1.0, trace=trace, devs=jax.devices()[:1],
                        root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "feed_wait_ms.train"} <= set(line["metrics"])
        assert line["metrics"]["feed_wait_ms.train"]["value"] >= 0.0
    else:
        assert set(line["metrics"]) == {"train_samples_s", "step_p95_ms",
                                        "setup_s"}
        assert line["attempted"] >= 10


def test_the_feed_normalises_channel_last_batches_on_the_device():
    import numpy as onp
    from mxnet_tpu.io import DataFeed
    mix = files.load_json(REPO, "chipbench/traffic/feed.json")
    rs = onp.random.RandomState(0)
    x = rs.randint(0, 256, (2, 8, 8, 3), dtype=onp.uint8)
    y = rs.randint(0, 10, (2,), dtype=onp.int32)
    with DataFeed(iter([(x, y)]), depth=2, scale=mix["scale"],
                  mean=mix["mean"], std=mix["std"]) as feed:
        got_x, got_y = next(feed)
    want = (x.astype("float32") / 255 - onp.asarray(mix["mean"], "float32")) \
        / onp.asarray(mix["std"], "float32")
    assert got_x.shape == (2, 8, 8, 3) and str(got_x.dtype) == "float32"
    assert onp.allclose(got_x.asnumpy(), want, atol=1e-5)
    assert str(got_y.dtype) == "int32" and (got_y.asnumpy() == y).all()


def test_new_readers_read_hand_made_evidence_and_nothing_without():
    load = lambda n: files.load_module(REPO, "chipbench", "layer_metrics",
                                       n + ".py")
    for name in NEW_READERS:
        reader = load(name)
        assert reader.read({}) is None
        assert reader.read({"steps": 20, "trace": None, "scope_s": None,
                            "layer_kind_s": None, "feed": None}) is None
    assert load("kda_ms.train").read(
        {"steps": 20, "layer_kind_s": {"K": 2.0, "E": 1.0}}) == 100.0
    assert load("kda_ms.train").read(
        {"steps": 20, "layer_kind_s": {"M": 2.0}}) is None
    assert load("kda_scan_ms.train").read(
        {"steps": 20, "scope_s": {"kda.scan": 0.5, "kda.conv": 1.0}}) == 25.0
    assert load("kda_scan_ms.train").read(
        {"steps": 20, "scope_s": {"ssm.scan": 0.5}}) is None
    assert load("feed_wait_ms.train").read(
        {"steps": 20, "feed": {"wait_s": 0.01, "draws": 20}}) == 0.5
    assert load("feed_wait_ms.train").read(
        {"steps": 20, "feed": {"wait_s": 0.0, "draws": 20}}) == 0.0


def test_the_new_configuration_loads_and_its_flops_are_perf_md_s():
    cfg = files.load_json(REPO, CONFIG)
    bench = files.load_json(REPO, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["file"] == CONFIG][0]
    assert entry["name"] == cfg["name"] and entry["reduced"] == cfg["reduced"]
    cell = [w for w in bench["workloads"]
            if w["config"] == cfg["name"]][0]
    mix = files.load_json(REPO, "chipbench", "traffic",
                          cell["traffic"] + ".json")
    assert mix["runner"] == "train_lm_listed"
    for key in ("pattern", "scopes", "sequence", "batch"):
        assert key in cfg
    assert len(cfg["reference"]["checked"]) == 8
    assert os.path.exists(os.path.join(
        REPO, "chipbench", *cfg["reference"]["module"].split("/")))
    flops = files.load_module(REPO, "chipbench",
                              cfg["flops"]["module"] + ".py")
    per = flops.per_token(**cfg["flops"]["kwargs"])
    # PERF.md section 4: M FLOP a token forward, by block kind
    assert round(per["K"] / 1e6, 2) == 37.36
    assert round(per["E"] / 1e6, 2) == 40.37
    assert round(per["*"] / 1e6, 2) == 35.65
    assert round(per["head"] / 1e6, 1) == 201.3
    assert round(per["token"] / 1e6, 1) == 510.5
    step = getattr(flops, cfg["flops"]["function"])(**cfg["flops"]["kwargs"])
    assert round(step / 1e12, 3) == 6.274
