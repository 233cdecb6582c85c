"""visualization / callback / model / tensorboard glue (reference
python/mxnet/{visualization,callback,model}.py + contrib/tensorboard.py)."""
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import symbol as S
from mxnet_tpu.ndarray import NDArray


def _mlp():
    x = S.Variable("data")
    w1, b1 = S.Variable("fc1_weight"), S.Variable("fc1_bias")
    w2 = S.Variable("fc2_weight")
    h = S._apply("FullyConnected", [x, w1, b1], {"flatten": True})
    h = S._apply("Activation", [h], {"act_type": "relu"})
    return S._apply("FullyConnected", [h, w2],
                    {"flatten": False, "no_bias": True})


def test_print_summary():
    out = mx.visualization.print_summary(
        _mlp(), shape={"data": (2, 8), "fc1_weight": (16, 8),
                       "fc1_bias": (16,), "fc2_weight": (4, 16)})
    assert "Total params:" in out
    assert "FullyConnected" in out
    # 16*8 + 16 + 4*16 = 208
    assert "Total params: 208" in out


def test_plot_network():
    dot = mx.visualization.plot_network(_mlp())
    src = dot.source
    assert "digraph" in src
    assert "fullyconnected" in src.lower()
    # weights hidden by default
    assert "fc1_weight" not in src


def test_speedometer_and_progressbar(caplog):
    from mxnet_tpu.gluon.metric import Accuracy
    metric = Accuracy()
    metric.update(mx.np.array(np.array([0, 1])),
                  mx.np.array(np.array([[0.9, 0.1], [0.1, 0.9]])))
    sp = mx.callback.Speedometer(batch_size=4, frequent=2)
    with caplog.at_level(logging.INFO):
        for nb in range(1, 5):
            sp(mx.callback.BatchEndParam(epoch=0, nbatch=nb,
                                         eval_metric=metric, locals=None))
    assert any("samples/sec" in r.message for r in caplog.records)
    pb = mx.callback.ProgressBar(total=4)
    with caplog.at_level(logging.INFO):
        pb(mx.callback.BatchEndParam(epoch=0, nbatch=2, eval_metric=None,
                                     locals=None))
    assert any("%" in r.message for r in caplog.records)


def test_checkpoint_roundtrip(tmp_path):
    sym = _mlp()
    rng = np.random.RandomState(0)
    arg = {"fc1_weight": NDArray(rng.randn(16, 8).astype(np.float32)),
           "fc1_bias": NDArray(rng.randn(16).astype(np.float32)),
           "fc2_weight": NDArray(rng.randn(4, 16).astype(np.float32))}
    aux = {"bn_mean": NDArray(np.zeros(3, np.float32))}
    prefix = str(tmp_path / "ckpt")
    mx.model.save_checkpoint(prefix, 3, sym, arg, aux)
    sym2, arg2, aux2 = mx.model.load_checkpoint(prefix, 3)
    assert sorted(arg2) == sorted(arg)
    assert np.allclose(arg2["fc1_weight"].asnumpy(),
                       arg["fc1_weight"].asnumpy())
    assert "bn_mean" in aux2
    # loaded symbol still evaluates
    x = NDArray(rng.randn(2, 8).astype(np.float32))
    out = sym2.eval(data=x, **arg2)
    out = out[0] if isinstance(out, (list, tuple)) else out
    assert out.shape == (2, 4)


def test_do_checkpoint_callback(tmp_path):
    prefix = str(tmp_path / "m")
    cb = mx.callback.do_checkpoint(prefix, period=2)
    arg = {"w": NDArray(np.ones((2, 2), np.float32))}
    cb(0, None, arg, {})      # epoch 0 → no save (period 2)
    import os
    cb(1, None, arg, {})      # epoch 1 → saves 0002
    assert os.path.exists(f"{prefix}-0002.params.npz") or \
        os.path.exists(f"{prefix}-0002.params")


def test_create_kvstore():
    kv, update = mx.model._create_kvstore("device", 1, {})
    assert kv is None and update is False
    kv, update = mx.model._create_kvstore("device", 4, {})
    assert kv is not None and update is True


def test_tensorboard_callback_fallback():
    from mxnet_tpu.gluon.metric import Accuracy
    metric = Accuracy()
    metric.update(mx.np.array(np.array([1])),
                  mx.np.array(np.array([[0.1, 0.9]])))
    cb = mx.contrib.tensorboard.LogMetricsCallback(logging_dir=None)
    cb(mx.callback.BatchEndParam(epoch=0, nbatch=1, eval_metric=metric,
                                 locals=None))
    assert cb.events and cb.events[0][0] == "accuracy"


def test_tpu_context_raises_without_a_tpu():
    """mx.tpu() never stands in for another platform: resolving it on a
    host without a TPU is an error, not a CPU device under another name."""
    import pytest
    assert mx.cpu().jax_device.platform == "cpu"
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        mx.tpu().jax_device
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        mx.np.ones((2,), ctx=mx.tpu())


def test_native_build_staleness_is_content_not_mtime(tmp_path, monkeypatch):
    """base._needs_build compares a hash of the build inputs stored
    beside the library: a copy that resets mtimes rebuilds nothing, an
    edited source does."""
    import os
    from mxnet_tpu import base
    src = tmp_path / "a.cc"
    src.write_text("int f() { return 1; }\n")
    lib = tmp_path / "lib.so"
    lib.write_text("")
    monkeypatch.setattr(base, "_build_inputs", lambda: [str(src)])
    monkeypatch.setattr(base, "_LIB_PATH", str(lib))
    monkeypatch.setattr(base, "_STAMP_PATH", str(lib) + ".inputs.sha256")
    assert base._needs_build()                    # no stamp yet
    (tmp_path / "lib.so.inputs.sha256").write_text(base._inputs_digest())
    assert not base._needs_build()
    os.utime(src, (2e9, 2e9))                     # newer mtime, same bytes
    assert not base._needs_build()
    src.write_text("int f() { return 2; }\n")     # new bytes
    assert base._needs_build()
    lib.unlink()
    assert base._needs_build()                    # library missing
