"""chipbench on the CPU at toy size: the harness cannot rot between chip
runs.  Run with ``python -m pytest chipbench/tests -q`` (outside tier-1).

Nothing here is a measurement: a CPU run proves control flow, the shape of
the result line and the arithmetic of the yardstick, never a time.
"""
import copy
import hashlib
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from chipbench import files, flops, run, trace_reduce  # noqa: E402

E2E = {"train_samples_s", "step_p95_ms", "setup_s"}

TOY_CONFIGS = {
    "toy-resnet18": {
        "model": {"module": "mxnet_tpu.models.resnet",
                  "builder": "resnet18_v1", "kwargs": {"classes": 10}},
        "entry": {"kind": "FusedTrainStep", "dtype": "bfloat16"},
        "optimizer": {"name": "sgd", "params": {
            "learning_rate": 0.005, "momentum": 0.9, "wd": 0.0001}},
        "batch": 4,
        "inputs": {"x": {"shape": [32, 32, 3], "dtype": "float32",
                         "dist": "uniform"},
                   "y": {"shape": [], "dtype": "int32", "dist": "randint",
                         "high": 10}},
        # bf16 batch statistics over 4 rows of 1x1 pixels in the last stage
        "reference": {"rtol": 0.1},
        "flops": {"module": "flops_toy", "function": "fixed",
                  "kwargs": {"n": 123.0}},
    },
    "toy-bert-small": {
        "model": {"module": "mxnet_tpu.models.bert_gluon",
                  "builder": "bert_small", "kwargs": {}},
        "entry": {"kind": "Trainer.fuse_step"},
        "optimizer": {"name": "adam", "params": {"learning_rate": 0.001}},
        "batch": 4,
        "inputs": {"x": {"shape": [32], "dtype": "int32", "dist": "randint",
                         "high": 1000},
                   "y": {"shape": [32], "dtype": "int32", "dist": "randint",
                         "high": 1000}},
        "flops": {"function": "bert_mlm", "kwargs": {
            "units": 64, "heads": 4, "layers": 2, "ffn_units": 128,
            "vocab_size": 1000, "seq": 32}},
    },
}


def _digest(root):
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A copy of the benchmark to which a later PR's additions are made as
    *new files plus new entries*: two configurations, a mix, a per-layer
    metric with its reader, two cells.  No file that was there is edited
    (BENCHMARK.json gains entries and loses none)."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(os.path.join(root, "chipbench"))
    bench = files.load_json(REPO, "BENCHMARK.json")
    was = copy.deepcopy(bench)
    for name, cfg in TOY_CONFIGS.items():
        cfg = dict({"reference": {"rtol": 0.02}}, **cfg, name=name,
                   loss="SoftmaxCrossEntropyLoss", reduced=[])
        path = f"chipbench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": name, "source": "test", "file": path,
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": f"{name}.ring2", "config": name,
                                   "traffic": "ring2", "chips": 1,
                                   "why": "toy"})
    with open(os.path.join(root, "chipbench/traffic/ring2.json"), "w") as f:
        json.dump({"name": "ring2", "runner": "train", "batches": 2,
                   "warmup_steps": 2, "trace_steps": 10}, f)
    with open(os.path.join(root, "chipbench/layer_metrics/steps.toy.py"),
              "w") as f:
        f.write("def read(evidence):\n    return evidence['steps']\n")
    with open(os.path.join(root, "chipbench/layer_metrics/flops.toy.py"),
              "w") as f:
        f.write("def read(evidence):\n"
                "    return evidence['flops_per_sample']\n")
    with open(os.path.join(root, "chipbench/flops_toy.py"), "w") as f:
        f.write("def fixed(n):\n    return n\n")
    bench["per_layer"].append({
        "name": "steps.toy", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Entry",
        "moves": "train_samples_s",
        "workloads": [f"{n}.ring2" for n in TOY_CONFIGS]})
    bench["per_layer"].append({
        "name": "flops.toy", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Step program",
        "moves": "train_samples_s", "workloads": ["toy-resnet18.ring2"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _digest(os.path.join(root, "chipbench"))
    assert {k: after[k] for k in before} == before, "an existing file changed"
    for key in ("configs", "workloads", "per_layer", "end_to_end"):
        assert bench[key][:len(was[key])] == was[key]
    return root


@pytest.mark.parametrize("config", sorted(TOY_CONFIGS))
def test_run_cell_line_has_the_contracts_keys(toy_root, config):
    line = run.run_cell(f"{config}.ring2", seed=2 ** 31 + 12345, seconds=1.0,
                        trace=0, devs=jax.devices()[:1], root=toy_root)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert set(line["metrics"]) == E2E
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["metrics"]["train_samples_s"]["unit"] == "samples/s"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


@pytest.mark.parametrize("config", sorted(TOY_CONFIGS))
def test_traced_run_reports_per_layer_metrics_and_new_readers(toy_root,
                                                              config):
    """The profiler runs on the CPU too, but its trace has no TPU plane:
    readers of the device trace return nothing and are left out, the
    host-side readers and the readers added as new files report, each in
    the cells its entry lists."""
    line = run.run_cell(f"{config}.ring2", seed=7, seconds=1.0, trace=1,
                        devs=jax.devices()[:1], root=toy_root)
    got = set(line["metrics"])
    assert {"retraces.train", "dispatch_ms.train", "pallas_routes.train",
            "steps.toy"} <= got
    if config == "toy-resnet18":        # a flops function from a new file
        assert line["metrics"]["flops.toy"]["value"] == 123.0
    else:
        assert "flops.toy" not in got
    assert not got & (E2E | {"device_step_ms.train", "mfu.train",
                             "device_idle_pct.train",
                             "pallas_busy_share.train"})
    assert line["metrics"]["retraces.train"]["value"] == 0
    assert line["metrics"]["steps.toy"] == {"value": 10.0, "unit": "count"}
    assert line["correct"] is True and line["attempted"] == 10


def test_same_seed_same_inputs(toy_root):
    sys.path.insert(0, toy_root)
    train = files.load_module(toy_root, "chipbench", "runners", "train.py")
    cfg = files.load_json(toy_root, "chipbench/configs/toy-bert-small.json")
    mix = files.load_json(toy_root, "chipbench/traffic/ring2.json")
    a, b, c = (train.make_ring(cfg, mix, s) for s in (5, 5, 6))
    assert (a[1][0].asnumpy() == b[1][0].asnumpy()).all()
    assert (a[1][0].asnumpy() != c[1][0].asnumpy()).any()


@pytest.mark.parametrize("argv", [
    ["--workload", "resnet50-train-ring", "--seed", "1", "--seconds", "1",
     "--trace", "0"],
    ["--workload", "bert-train-ring", "--seed", "3000000000", "--trace", "1"],
])
def test_main_refuses_without_a_tpu(argv, capsys):
    assert run.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_device_problem_rules(monkeypatch):
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    assert run.device_problem([Dev("tpu")], 1) is None
    assert "no TPU" in run.device_problem([Dev("cpu")], 1)
    assert "wants 1" in run.device_problem([Dev("tpu")] * 4, 1)
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    assert "INTERPRET" in run.device_problem([Dev("tpu")], 1)


def test_benchmark_json_names_files_that_exist():
    bench = files.load_json(REPO, "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = files.load_json(REPO, c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        mix = files.load_json(REPO, "chipbench", "traffic",
                            w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "runners", mix["runner"] + ".py"))
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in names
        reader = files.load_module(REPO, "chipbench", "layer_metrics",
                                 m["name"] + ".py")
        assert reader.read({}) is None      # nothing to read: nothing said
    assert files.load_json(REPO, "chipbench/peaks.json")["TPU v5 lite"][
        "bf16_flops_per_s"] == 197e12


# ---------------------------------------------------------------- flops
def test_required_flops():
    r = flops.resnet_v1_bottleneck([3, 4, 6, 3],
                                   [64, 256, 512, 1024, 2048], 224, 1000)
    assert abs(r / 1e9 - 23.1) <= 0.7         # 3.8 GMAC forward x 2 x 3
    b = flops.bert_mlm(768, 12, 12, 3072, 30522, 512)
    assert abs(b / 1e9 - 362) <= 5
    for c in files.load_json(REPO, "BENCHMARK.json")["configs"]:
        f = files.load_json(REPO, c["file"])["flops"]
        assert getattr(flops, f["function"])(**f["kwargs"]) in (r, b)


# ---------------------------------------------------------- trace_reduce
HLO = ('%add_add_fusion.1 = bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} '
       'fusion(bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} %add_add_fusion.2,'
       ' bf16[256]{0:T(256)(128)(2,1)S(1)} %copy-done.925), kind=kLoop, '
       'calls=%fused_computation.49')
KERNEL = ('%transpose_jvp___.9 = f32[576,64]{1,0:T(8,128)} custom-call('
          'bf16[256,58,58,64]{3,2,1,0} %pad.1), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
TUPLE = ('%fusion.7 = (f32[64]{0:T(64)}, f32[64]{0:T(64)}) fusion(bf16[8,4]'
         '{1,0} %p), kind=kInput, calls=%fused_computation.7')
WHILE = "%while.3 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t)"


def test_shorten():
    assert trace_reduce.shorten(HLO) == \
        "add_add_fusion.1 bf16[256,56,56,256] fusion:kLoop"
    assert trace_reduce.shorten(KERNEL) == \
        "transpose_jvp___.9 f32[576,64] custom-call:tpu_custom_call"
    assert trace_reduce.shorten(TUPLE) == "fusion.7 (f32[64],... fusion:kInput"
    assert trace_reduce.shorten(WHILE) == "while.3 (s32[],... while"
    assert trace_reduce.shorten("x" * 100) == "x" * 64


def test_union_gaps_and_self_time():
    assert trace_reduce.merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == \
        [[0, 4], [5, 9]]
    assert trace_reduce.gaps([[2, 4], [5, 9]], 0, 12) == \
        [(0, 2), (4, 5), (9, 12)]
    assert trace_reduce.clip([("a", 0, 10), ("b", 20, 5)], 5, 18) == \
        [("a", 5, 5)]
    got = dict(trace_reduce.self_times(
        [("parent", 0, 100), ("child", 10, 30), ("grandchild", 15, 5),
         ("child2", 50, 20), ("next", 100, 10)]))
    assert got == {"parent": 50, "child": 25, "grandchild": 5, "child2": 20,
                   "next": 10}


def test_reduce_a_hand_made_trace():
    """Window [1000, 2000] ns.  Two step-program runs of 400 ns; ops busy
    over [1000,1400] and [1500,1900] (a while whose body is the kernel);
    idle [1400,1500] under the fetch annotation and [1900,2000] under
    nothing.  An op before the window is cut away."""
    ops = [(HLO, 900, 200), (HLO, 1100, 300), (WHILE, 1500, 400),
           (KERNEL, 1600, 300), ("%early = f32[] add()", 0, 500)]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_step(1)", 1000, 400), ("jit_step(1)", 1500, 400),
                ("jit_convert(2)", 1450, 10), ("jit_step(1)", 100, 700)]},
            {"name": "Steps", "events": [("0", 1000, 400)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main/1", "events": [
                ("chipbench.window", 1000, 1000),
                ("chipbench.dispatch", 1000, 50),
                ("chipbench.fetch", 1380, 130)]}]},
        {"name": "#Chip0 Misc", "lines": []},
    ]
    r = trace_reduce.reduce(planes)
    assert r["devices"] == 1 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(800e-9)
    assert r["step_s"] == pytest.approx(400e-9)
    assert r["pallas_s"] == pytest.approx(300e-9)
    assert r["device_ops"] == [
        ["add_add_fusion.1 bf16[256,56,56,256] fusion:kLoop",
         pytest.approx(400e-9)],
        ["transpose_jvp___.9 f32[576,64] custom-call:tpu_custom_call",
         pytest.approx(300e-9)],
        ["while.3 (s32[],... while", pytest.approx(100e-9)]]
    assert r["idle_gaps"] == [["chipbench.fetch", pytest.approx(100e-9)],
                              ["unannotated", pytest.approx(100e-9)]]
    # no device plane, or no window annotation: nothing to read
    assert trace_reduce.reduce(planes[1:]) is None
    assert trace_reduce.reduce(planes[:1]) is None
