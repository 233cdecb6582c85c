"""The cell PR 36 added, on the CPU at toy size: ``train_lm_dense`` on a tiny
Olmo-Hybrid against the plain reference with and without trace, refusing a
model that is not the reference and a configuration that holds experts, the
three new readers with and without evidence, the new configuration and its
FLOPs, and that adding all of it changed no file the benchmark already had.
Not a measurement."""
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

PARENT = "c07820d2afbcf41ba62b2add199a385a7ce43ad8"
CONFIG = "chipbench/configs/olmo-hybrid-7b-train-tp2.json"
CELL = "olmo-hybrid-train-gdn"
LINEAR, FULL = "linear_attention", "full_attention"
TOY = {
    "name": "toy-olmo", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 2, "layer_types": [LINEAR, FULL],
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "rope_parameters": {"rope_theta": None},
    "vocab_size": 64, "pattern": "LF*F",
    "model": {"module": "mxnet_tpu.models.olmo_hybrid",
              "builder": "olmo_hybrid",
              "kwargs": {"num_attention_heads": 4, "num_key_value_heads": 4,
                         "linear_num_key_heads": 4,
                         "linear_num_value_heads": 4, "heads_held": [2, 2],
                         "linear_chunk_size": 16, "vocab_size": 128,
                         "vocab_held": [0, 64]}},
    "entry": {"kind": "Trainer.fuse_step"}, "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {"learning_rate": 0.01}},
    "batch": 2, "sequence": 40,
    "flops": {"module": "flops_olmo_hybrid", "function": "olmo_hybrid_train",
              "kwargs": {"pattern": "LF*F", "hidden": 32, "seq": 40,
                         "vocab_rows": 64, "linear_heads": 2, "key_dim": 12,
                         "value_dim": 24, "chunk": 16, "heads": 2,
                         "kv_heads": 2, "head_dim": 8, "mlp_width": 48}},
    "scopes": ["gdn.scan", "gdn.conv", "mlp.up", "attn.qknorm", "attn.core",
               "mx.opt"],
    "reference": {"module": "reference/olmo_hybrid.py",
                  "checked": ["embed.weight", "layers.0.mixer.q_proj.weight",
                              "layers.0.mixer.A_log",
                              "layers.0.mixer.q_conv_weight",
                              "layers.0.mixer.g_proj.weight",
                              "layers.2.mixer.q_proj.weight",
                              "layers.2.mixer.q_norm_weight",
                              "layers.1.mixer.gate_up_proj.weight"],
                  "tolerances": {"loss_rtol": 1e-3, "logits": 1e-2,
                                 "logits_median": 1e-2, "grad": 2e-2}},
    "reduced": [],
}
NEW_READERS = ["gdn_ms.train", "gdn_scan_ms.train", "mlp_ms.train"]
LONGER = ["attn_ms.train", "step_build_s.train",
          "compile_cache_misses.train", "step_host_ms.train"]
NEW_FILES = {"configs/olmo-hybrid-7b-train-tp2.json", "flops_olmo_hybrid.py",
             "reference/olmo_hybrid.py", "reference/bf16_control_olmo.py",
             "runners/train_lm_dense.py", "traffic/ring-lm-dense.json",
             "tests/test_olmo_cells.py", "README.olmo.md",
             *(f"layer_metrics/{n}.py" for n in NEW_READERS)}


def _add(root, bench, cfg, cell, mix):
    path = f"chipbench/configs/{cfg['name']}.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({"name": cfg["name"], "source": "test",
                             "file": path, "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": cell, "config": cfg["name"],
                               "traffic": mix, "chips": 1, "why": "toy"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = files.load_json(REPO, "BENCHMARK.json")
    mix = files.load_json(REPO, "chipbench/traffic/ring-lm-dense.json")
    with open(os.path.join(root, "chipbench/traffic/dense2.json"), "w") as f:
        json.dump(dict(mix, name="dense2", batches=2, warmup_steps=2,
                       trace_steps=10), f)
    for m in bench["per_layer"]:     # a toy cell reports what its model reports
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-olmo.dense2")
    _add(root, bench, TOY, "toy-olmo.dense2", "dense2")
    return root


def test_only_files_were_added_and_entries_appended():
    """Against the parent commit: no file under ``chipbench/`` the benchmark
    had is changed or gone, and ``BENCHMARK.json``'s old entries stand where
    they stood, four ``workloads`` lists of metrics longer by this cell.
    What a later PR appends after this one's entries passes too."""
    def git(*args):
        return subprocess.run(["git", "-C", REPO, *args], check=True,
                              capture_output=True, text=True).stdout
    try:
        base = git("merge-base", "HEAD", PARENT).strip()
        was = json.loads(git("show", f"{base}:BENCHMARK.json"))
        changed = git("diff", "--name-status", base, "--", "chipbench")
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history to compare with")
    added = set()
    for line in changed.splitlines():
        status, path = line.split("\t")[0], line.split("\t")[-1]
        assert status == "A", line
        added.add(path[len("chipbench/"):])
    assert NEW_FILES <= added
    now = files.load_json(REPO, "BENCHMARK.json")
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert now[key] == was[key]
    for key in ("configs", "workloads"):
        assert now[key][:len(was[key])] == was[key]
    cell = now["workloads"][len(was["workloads"])]
    assert cell == {"name": CELL, "config": "olmo-hybrid-7b-train-tp2",
                    "traffic": "ring-lm-dense", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert "8192" in cell["why"] or "4096" in cell["why"]
    assert now["configs"][len(was["configs"])]["name"] \
        == "olmo-hybrid-7b-train-tp2"
    new = now["per_layer"][len(was["per_layer"]):][:len(NEW_READERS)]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"][0] == CELL and m["moves"] == "train_samples_s"
               for m in new)
    for old, new in zip(was["per_layer"], now["per_layer"]):
        grown = dict(new)
        if "workloads" in old:
            more = new["workloads"][len(old["workloads"]):]
            assert more[:1] == ([CELL] if old["name"] in LONGER else more[:1])
            assert CELL not in more[1:]
            grown["workloads"] = new["workloads"][:len(old["workloads"])]
        assert grown == old


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_dense_runs_against_the_plain_reference(toy_root, trace):
    line = run.run_cell("toy-olmo.dense2", seed=2 ** 31 + 11, seconds=1.0,
                        trace=trace, devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "step_build_s.train", "compile_cache_misses.train"} <= got
        assert line["metrics"]["retraces.train"]["value"] == 0
        # no experts, and no TPU plane in a CPU trace: the expert counter is
        # not this cell's and the scope readers say nothing
        assert not got & {"moe_tokens_per_expert.train", "moe_ms.train",
                          "gdn_ms.train", "gdn_scan_ms.train", "mlp_ms.train",
                          "attn_ms.train", "device_step_ms.train",
                          "mfu.train"}
    else:
        assert got == {"train_samples_s", "step_p95_ms", "setup_s"}


def test_the_evidence_holds_no_experts_and_the_builder_is_handed_none(
        toy_root, monkeypatch):
    from mxnet_tpu.models import olmo_hybrid as program
    seen = []
    builder = program.olmo_hybrid
    monkeypatch.setattr(program, "olmo_hybrid",
                        lambda **kw: seen.append(kw) or builder(**kw))
    program.olmo_hybrid.__wrapped__ = builder.__wrapped__
    cell = {"name": "toy-olmo.dense2", "chips": 1, "config": TOY,
            "mix": files.load_json(toy_root, "chipbench/traffic/dense2.json"),
            "seed": 5, "seconds": 0.5, "trace": False,
            "devices": jax.devices()[:1], "root": toy_root,
            "t0": run.T0, "peaks": None}
    runner = files.load_module(toy_root, "chipbench", "runners",
                               "train_lm_dense.py")
    evidence = runner.run(cell)
    assert "moe" not in evidence
    assert all(ok for _, ok in evidence["checks"])
    assert seen and all("experts_held" not in kw for kw in seen)
    held = dict(TOY, model=dict(TOY["model"], kwargs=dict(
        TOY["model"]["kwargs"], experts_held=[0, 4])))
    with pytest.raises(ValueError, match="holds experts"):
        runner.run(dict(cell, config=held))


def test_the_check_runs_first_and_leaves_nothing_on_the_device(toy_root):
    """The timed net and its step are laid out on a device that holds
    nothing of the check: no array of it (the program's random key, two
    numbers, is all that is new) and no program."""
    import gc

    from mxnet_tpu import dispatch_cache
    cell = {"name": "toy-olmo.dense2", "chips": 1, "config": TOY,
            "mix": files.load_json(toy_root, "chipbench/traffic/dense2.json"),
            "seed": 5, "seconds": 0.5, "trace": False,
            "devices": jax.devices()[:1], "root": toy_root,
            "t0": run.T0, "peaks": None}
    runner = files.load_module(toy_root, "chipbench", "runners",
                               "train_lm_dense.py")
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    ref_loss, dist = runner.check_first(
        cell, runner.train_lm_for(TOY, toy_root))
    left = [a for a in jax.live_arrays() if id(a) not in before]
    assert all(a.size <= 2 for a in left), [a.shape for a in left]
    assert dispatch_cache.cache_len() == 0
    assert 4.0 < ref_loss < 4.4 and dist["logits"] < 1e-5
    assert set(dist) == {"logits", "logits_median", *(
        f"grad {n}" for n in TOY["reference"]["checked"])}


def test_train_lm_dense_refuses_a_model_that_is_not_the_reference(toy_root):
    """Against a reference that scales q by dk ** -0.4 the logits check
    fails: a tolerance decides ``correct``."""
    ref_dir = os.path.join(toy_root, "chipbench", "reference")
    with open(os.path.join(ref_dir, "olmo_hybrid.py")) as f:
        text = f.read()
    assert "dk ** -0.5" in text
    with open(os.path.join(ref_dir, "olmo_off.py"), "w") as f:
        f.write(text.replace("dk ** -0.5", "dk ** -0.4"))
    cfg = dict(TOY, name="toy-olmo-off", reference=dict(
        TOY["reference"], module="reference/olmo_off.py"))
    _add(toy_root, files.load_json(toy_root, "BENCHMARK.json"), cfg,
         "off.dense2", "dense2")
    line = run.run_cell("off.dense2", seed=3, seconds=0.5, trace=0,
                        devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is False


def test_new_readers_read_hand_made_evidence_and_nothing_without():
    load = lambda n: files.load_module(REPO, "chipbench", "layer_metrics",
                                       n + ".py")
    for name in NEW_READERS:
        reader = load(name)
        assert reader.read({}) is None
        assert reader.read({"steps": 20, "trace": None, "scope_s": None,
                            "layer_kind_s": None}) is None
    kinds = {"L": 2.0, "F": 5.0, "*": 1.0}
    assert load("gdn_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 100.0
    assert load("mlp_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 250.0
    assert load("attn_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 50.0
    for name in ("gdn_ms.train", "mlp_ms.train"):    # the parent's program
        assert load(name).read(
            {"steps": 20, "layer_kind_s": {"K": 2.0, "E": 1.0}}) is None
    assert load("gdn_scan_ms.train").read(
        {"steps": 20, "scope_s": {"gdn.scan": 0.5, "gdn.conv": 1.0}}) == 25.0
    assert load("gdn_scan_ms.train").read(
        {"steps": 20, "scope_s": {"kda.scan": 0.5}}) is None


def test_the_new_configuration_loads_and_its_flops_are_perf_md_s():
    cfg = files.load_json(REPO, CONFIG)
    bench = files.load_json(REPO, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["file"] == CONFIG][0]
    assert entry["name"] == cfg["name"] and entry["reduced"] == cfg["reduced"]
    cell = [w for w in bench["workloads"] if w["config"] == cfg["name"]]
    assert [w["name"] for w in cell] == [CELL] and cell[0]["chips"] == 1
    mix = files.load_json(REPO, "chipbench", "traffic",
                          cell[0]["traffic"] + ".json")
    assert mix["runner"] == "train_lm_dense"
    for key in ("pattern", "scopes", "sequence", "batch", "deployment"):
        assert key in cfg
    assert {"gdn.conv", "gdn.gate", "gdn.scan", "gdn.norm", "attn.qknorm",
            "attn.core", "mlp.up", "mlp.act", "mlp.down", "/embed/",
            "/head/", "mx.loss", "mx.opt"} <= set(cfg["scopes"])
    assert len(cfg["reference"]["checked"]) == 8
    assert os.path.exists(os.path.join(
        REPO, "chipbench", *cfg["reference"]["module"].split("/")))
    tol = cfg["reference"]["tolerances"]
    assert {"loss_rtol", "logits", "logits_median", "grad", "why"} <= set(tol)
    flops = files.load_module(REPO, "chipbench",
                              cfg["flops"]["module"] + ".py")
    kwargs = dict(cfg["flops"]["kwargs"], seq=8192)
    per = flops.per_token(**kwargs)
    # PERF.md section 4: M FLOP a token forward at 8192 keys, by block kind
    assert round(per["L"] / 1e6, 2) == 91.02
    assert round(per["F"] / 1e6, 2) == 253.62
    assert round(per["*"] / 1e6, 2) == 90.44
    assert round(per["head"] / 1e6, 2) == 96.34
    assert round(per["token"] / 1e6, 1) == 1474.3
    step = getattr(flops, cfg["flops"]["function"])(**kwargs)
    assert round(step / 1e12, 2) == 36.23
    assert cfg["flops"]["kwargs"]["seq"] == cfg["sequence"]
