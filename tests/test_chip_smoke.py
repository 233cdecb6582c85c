"""chip_smoke.py's phases at a toy size on the CPU, so the script the
driver runs on the chip cannot rot: same functions, same checks, small
ResNet and BERT, kernels in interpret mode where a route is forced."""
import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_train_phase_toy():
    out = chip_smoke.train_phase(jax.devices()[0], model="resnet18_v1",
                                 classes=10, batch=4, image=32, steps=5)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]


def test_serve_phase_toy():
    out = chip_smoke.serve_phase(jax.devices()[0], model="resnet18_v1",
                                 classes=10, image=32, buckets=(2, 4),
                                 sizes=(1, 3, 2, 4))
    assert out["worst_abs_diff"] <= 2e-2


def test_kernel_phase_toy(monkeypatch):
    """Interpret mode, as if on one TPU: the parity phase the chip run
    opens with, on the stage the default table routes."""
    from mxnet_tpu.ops import pallas_block
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    chip_smoke.kernel_phase(batch=1)


def test_mesh_phase_toy():
    """The --chips 4 phase over four of the suite's virtual CPU devices."""
    out = chip_smoke.mesh_phase(jax.devices()[:4], units=64, heads=4,
                                layers=2, ffn_units=128, vocab=1000, seq=32,
                                batch=8, steps=3)
    assert len(out["got"]) == len(out["ref"]) == 3


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(argv, capsys):
    """Off the chip main() checks the device before any phase: non-zero,
    and no result line."""
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out and "[smoke]" not in out.out
    assert "no TPU" in out.err


def test_device_problem_rules(monkeypatch):
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    assert chip_smoke.device_problem([Dev("tpu")], 1) is None
    assert chip_smoke.device_problem([Dev("tpu")] * 4, 4) is None
    assert "no TPU" in chip_smoke.device_problem([Dev("cpu")], 1)
    assert "want 1" in chip_smoke.device_problem([Dev("tpu")] * 4, 1)
    assert "want 4" in chip_smoke.device_problem([Dev("tpu")], 4)
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    assert "INTERPRET" in chip_smoke.device_problem([Dev("tpu")], 1)


def test_last_line_shape():
    """The result line is exactly the contract's three device keys."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    line = chip_smoke.result_line([Dev()])
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert "\n" not in line
