"""The cell PR 40 added, on the CPU at toy size: ``train_lm_listed`` on a
tiny Trinity against the plain reference with and without trace, refusing a
reference whose window is one key off, the four new readers with and without
evidence, the new configuration and its FLOPs, and that adding all of it
changed no file the benchmark already had.  Not a measurement."""
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

PARENT = "0a506e2bbb16ca0d60c62500e0bed4c27bbbf3f5"
CONFIG = "chipbench/configs/trinity-mini-26b-train-ep8.json"
CELL = "trinity-mini-train-swa8k"
SLIDING, FULL = "sliding_attention", "full_attention"
TOY = {
    "name": "toy-trinity", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 3,
    "layer_types": [SLIDING, SLIDING, FULL], "num_dense_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 6, "rope_theta": 10000, "rope_scaling": None,
    "rms_norm_eps": 1e-5, "hidden_act": "silu", "mup_enabled": True,
    "tie_word_embeddings": False, "num_experts": 4,
    "num_experts_per_tok": 3, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "n_group": 1, "topk_group": 1, "vocab_size": 64, "pattern": "WDWE*E",
    "model": {"module": "mxnet_tpu.models.trinity", "builder": "trinity",
              "kwargs": {"num_experts": 16, "experts_held": [4, 4],
                         "vocab_size": 128, "vocab_held": [0, 64]}},
    "entry": {"kind": "Trainer.fuse_step"}, "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {"learning_rate": 0.01}},
    "batch": 2, "sequence": 24,
    "flops": {"module": "flops_trinity", "function": "trinity_train",
              "kwargs": {"pattern": "WDWE*E", "hidden": 32, "seq": 24,
                         "vocab_rows": 64, "heads": 4, "kv_heads": 2,
                         "head_dim": 8, "window": 6, "mlp_width": 48,
                         "experts": 16, "experts_held": 4, "top_k": 3,
                         "expert_width": 16, "shared_width": 16}},
    "scopes": ["attn.rope", "attn.qknorm", "attn.window", "attn.core",
               "attn.gate", "moe.route", "mlp.up", "mx.opt"],
    "reference": {"module": "reference/trinity.py",
                  "checked": ["embed.weight", "layers.0.mixer.q_proj.weight",
                              "layers.0.mixer.q_norm_weight",
                              "layers.0.mixer.g_proj.weight",
                              "layers.4.mixer.q_proj.weight",
                              "layers.1.mixer.gate_up_proj.weight",
                              "layers.3.mixer.router_weight",
                              "layers.3.mixer.experts_up",
                              "layers.3.mixer.shared_up.weight"],
                  "tolerances": {"loss_rtol": 1e-3, "logits": 1e-2,
                                 "logits_median": 1e-2, "grad": 2e-2}},
    "reduced": [],
}
NEW_READERS = ["swa_ms.train", "swa_core_ms.train",
               "swa_fwd_roofline_pct.train", "swa_bwd_roofline_pct.train"]
LONGER = ["step_build_s.train", "compile_cache_misses.train",
          "step_host_ms.train", "moe_ms.train", "attn_ms.train",
          "moe_tokens_per_expert.train", "step_gap_excess_ms.train",
          "host_exposed_ms.train", "host_gc_ms.train"]
NEW_FILES = {"configs/trinity-mini-26b-train-ep8.json", "flops_trinity.py",
             "reference/trinity.py", "reference/bf16_control_trinity.py",
             "tests/test_trinity_cells.py", "README.trinity.md",
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
    mix = files.load_json(REPO, "chipbench/traffic/ring-lm-listed.json")
    with open(os.path.join(root, "chipbench/traffic/listed2.json"), "w") as f:
        json.dump(dict(mix, name="listed2", batches=2, warmup_steps=2,
                       trace_steps=10), f)
    for m in bench["per_layer"]:     # a toy cell reports what its model reports
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-trinity.listed2")
    _add(root, bench, TOY, "toy-trinity.listed2", "listed2")
    return root


def test_only_files_were_added_and_entries_appended():
    """Against the parent commit: no file under ``chipbench/`` the benchmark
    had is changed or gone, and ``BENCHMARK.json``'s old entries stand where
    they stood, nine ``workloads`` lists of metrics longer by this cell.
    What a later PR appends after this one's entries passes too."""
    def git(*args):
        return subprocess.run(["git", "-C", REPO, *args], check=True,
                              capture_output=True, text=True).stdout
    try:
        base = git("merge-base", "HEAD", PARENT).strip()
        was = json.loads(git("show", f"{base}:BENCHMARK.json"))
        changed = git("diff", "--name-status", base, "--", "chipbench")
        # files not yet committed count too
        untracked = git("ls-files", "--others", "--exclude-standard", "--",
                        "chipbench")
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history to compare with")
    added = {p[len("chipbench/"):] for p in untracked.splitlines()}
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
    assert cell == {"name": CELL, "config": "trinity-mini-26b-train-ep8",
                    "traffic": "ring-lm-listed", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert "8192" in cell["why"] and "512" in cell["why"]
    entry = now["configs"][len(was["configs"])]
    assert entry["name"] == "trinity-mini-26b-train-ep8"
    assert entry["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    new = now["per_layer"][len(was["per_layer"]):][:len(NEW_READERS)]
    assert [m["name"] for m in new] == NEW_READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_samples_s"
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
def test_train_lm_listed_runs_trinity_against_the_plain_reference(toy_root,
                                                                   trace):
    line = run.run_cell("toy-trinity.listed2", seed=2 ** 31 + 11, seconds=1.0,
                        trace=trace, devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    if trace:
        assert {"retraces.train", "dispatch_ms.train", "step_host_ms.train",
                "step_build_s.train", "compile_cache_misses.train",
                "moe_tokens_per_expert.train"} <= got
        assert line["metrics"]["retraces.train"]["value"] == 0
        # 24 tokens x 2 sequences x top-3 of 16 experts: 9 a held expert
        assert line["metrics"]["moe_tokens_per_expert.train"]["value"] > 0
        # no TPU plane in a CPU trace: the scope readers say nothing
        assert not got & {*NEW_READERS, "moe_ms.train", "attn_ms.train",
                          "device_step_ms.train", "mfu.train"}
    else:
        assert got == {"train_samples_s", "step_p95_ms", "setup_s"}


def test_train_lm_listed_refuses_a_window_one_key_short(toy_root):
    """Against a reference whose window leaves out the key ``window - 1``
    back the checks fail: a tolerance decides ``correct``."""
    ref_dir = os.path.join(toy_root, "chipbench", "reference")
    with open(os.path.join(ref_dir, "trinity.py")) as f:
        text = f.read()
    assert "(back < window)" in text
    with open(os.path.join(ref_dir, "trinity_off.py"), "w") as f:
        f.write(text.replace("(back < window)", "(back < window - 1)"))
    cfg = dict(TOY, name="toy-trinity-off", reference=dict(
        TOY["reference"], module="reference/trinity_off.py"))
    _add(toy_root, files.load_json(toy_root, "BENCHMARK.json"), cfg,
         "off.listed2", "listed2")
    line = run.run_cell("off.listed2", seed=3, seconds=0.5, trace=0,
                        devs=jax.devices()[:1], root=toy_root)
    assert line["correct"] is False


def test_new_readers_read_hand_made_evidence_and_nothing_without():
    load = lambda n: files.load_module(REPO, "chipbench", "layer_metrics",
                                       n + ".py")
    for name in NEW_READERS:
        reader = load(name)
        assert reader.read({}) is None
        assert reader.read({"steps": 20, "trace": None, "scope_s": None,
                            "layer_kind_s": None, "peaks": None}) is None
    kinds = {"W": 2.0, "E": 5.0, "*": 1.0, "D": 0.5}
    assert load("swa_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 100.0
    assert load("attn_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 50.0
    assert load("moe_ms.train").read(
        {"steps": 20, "layer_kind_s": kinds}) == 250.0
    assert load("swa_ms.train").read(        # the parent's program
        {"steps": 20, "layer_kind_s": {"K": 2.0, "E": 1.0}}) is None
    assert load("swa_core_ms.train").read(
        {"steps": 20, "scope_s": {"attn.window": 0.5, "attn.core": 1}}) == 25.0
    assert load("swa_core_ms.train").read(
        {"steps": 20, "scope_s": {"attn.core": 0.5}}) is None
    # the cell's shapes: 4 W blocks x 32 heads x 128 x the band's pairs
    flops = files.load_module(REPO, "chipbench", "flops_trinity.py")
    pairs = flops.band_pairs(8192, 2048)
    fwd = 2 * 4 * 4 * 128 * 32 * pairs
    bwd = 1 * 4 * 10 * 128 * 32 * pairs
    peaks = {"bf16_flops_per_s": 197e12}
    scope = {"mx_window_attn_fwd": 20 * 2 * fwd / 197e12,    # at half the peak
             "mx_window_attn_bwd": 20 * 4 * bwd / 197e12}    # at a quarter
    ev = {"steps": 20, "batch": 1, "peaks": peaks, "scope_s": scope}
    assert round(load("swa_fwd_roofline_pct.train").read(ev), 6) == 50.0
    assert round(load("swa_bwd_roofline_pct.train").read(ev), 6) == 25.0
    for name in NEW_READERS[2:]:             # no such kernel in the program
        assert load(name).read(dict(ev, scope_s={"attn.core": 1.0})) is None
        assert load(name).read(dict(ev, scope_s={
            "mx_window_attn_fwd": 0.0, "mx_window_attn_bwd": 0.0})) is None


def test_the_new_configuration_loads_and_its_flops_are_perf_md_s():
    cfg = files.load_json(REPO, CONFIG)
    bench = files.load_json(REPO, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["file"] == CONFIG][0]
    assert entry["name"] == cfg["name"] and entry["reduced"] == cfg["reduced"]
    cell = [w for w in bench["workloads"] if w["config"] == cfg["name"]]
    assert [w["name"] for w in cell] == [CELL] and cell[0]["chips"] == 1
    mix = files.load_json(REPO, "chipbench", "traffic",
                          cell[0]["traffic"] + ".json")
    assert mix["runner"] == "train_lm_listed"
    for key in ("pattern", "scopes", "sequence", "batch", "deployment",
                "published", "assumed"):
        assert key in cfg
    assert {"attn.rope", "attn.qknorm", "attn.window", "attn.core",
            "attn.gate", "q_proj", "k_proj", "v_proj", "g_proj", "o_proj",
            "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
            "moe.shared", "mlp.up", "mlp.act", "mlp.down", "/norm/",
            "/embed/", "/head/", "mx.loss", "mx.opt",
            "transpose(jvp(mx.fwd))", "rematted_computation",
            "mx_window_attn_fwd", "mx_window_attn_bwd"} <= set(cfg["scopes"])
    assert len(cfg["reference"]["checked"]) == 9
    assert os.path.exists(os.path.join(
        REPO, "chipbench", *cfg["reference"]["module"].split("/")))
    tol = cfg["reference"]["tolerances"]
    assert {"loss_rtol", "logits", "logits_median", "grad", "why"} <= set(tol)
    flops = files.load_module(REPO, "chipbench",
                              cfg["flops"]["module"] + ".py")
    kwargs = dict(cfg["flops"]["kwargs"])
    assert kwargs["seq"] == cfg["sequence"] == 8192
    assert kwargs["window"] == cfg["sliding_window"] == 2048
    kwargs.pop("pattern")
    per = flops.per_block(**kwargs)
    # PERF.md section 4: TFLOP a block forward at 8192 tokens, by kind
    assert round(per["W"] / 1e12, 4) == 0.6872
    assert round(per["*"] / 1e12, 4) == 0.9965
    assert round(per["D"] / 1e12, 4) == 0.6185
    assert round(per["E"] / 1e12, 4) == 0.2105
    assert round(per["head"] / 1e12, 4) == 0.8397
    step = getattr(flops, cfg["flops"]["function"])(**cfg["flops"]["kwargs"])
    assert round(step / 1e12, 2) == 18.14
