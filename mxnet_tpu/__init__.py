"""mxnet_tpu — a TPU-native deep-learning framework with MXNet 2.0's
capabilities (reference: Kaiser-Yang/mxnet), built on JAX/XLA/PJRT/Pallas.

Import as ``import mxnet_tpu as mx``:

- ``mx.np`` / ``mx.npx`` — NumPy-compatible array API on device
- ``mx.autograd`` — record/backward tape
- ``mx.gluon`` — Block/HybridBlock/Trainer module system
- ``mx.optimizer`` — optimizer zoo
- ``mx.kv`` — KVStore (collective-backed)
- ``mx.cpu()/mx.gpu()/mx.tpu()`` — device contexts

See SURVEY.md at the repo root for the layer-by-layer mapping to the
reference (file:line citations in each module docstring).
"""
from __future__ import annotations

__version__ = "2.0.0.tpu0"


def _place_compile_cache():
    """JAX's persistent compilation cache, placed from outside.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so a
    directory that moves never hits).  JAX's size/time thresholds stay
    at their defaults.  Must run before the first jit call, hence at
    package-import time."""
    import os as _os
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax as _jax
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))


_place_compile_cache()

# MXNET_LOCK_CHECK=1|warn: wrap threading.Lock/RLock/Condition with the
# order-recording watchdog BEFORE any submodule constructs its locks —
# lockwatch is stdlib-only so this adds nothing to import cost when off.
from . import lockwatch as _lockwatch
_lockwatch.install()

from .context import (Context, Device, cpu, gpu, tpu, current_context,
                      current_device, num_gpus, num_tpus)
from .ndarray import NDArray, waitall
from . import dispatch_cache  # eager executable cache (mx.dispatch_cache)
from . import numpy as np  # noqa: (shadows stdlib-style name on purpose)
from . import numpy_extension as npx
from . import autograd
from . import tape as _tape
from . import ops
from . import initializer
from . import optimizer
from .optimizer import Optimizer
from . import kvstore
from . import gluon
from . import lr_scheduler
from .util import use_np, set_np, reset_np
from . import profiler
from . import runtime
from . import base
from . import telemetry
from . import engine
from . import storage
from . import recordio
from . import dlpack     # DLPack interop (from_dlpack / to_dlpack_*)
from . import checkpoint  # durable async checkpointing (CheckpointManager)
from . import serve       # inference tier: continuous batching + HTTP
from . import generate    # autoregressive decode: donated ring-KV engine

init = initializer  # mx.init.Xavier() parity alias
kv = kvstore

from . import amp          # mixed precision (P12)
from . import nd           # legacy NDArray namespace (P8)
from . import symbol       # legacy Symbol API (P8)
from . import sparse       # row_sparse / csr storage types
from . import contrib      # control-flow ops + misc
from . import operator     # legacy CustomOp API (N31)
from . import io           # legacy DataIter interface (N22/P16)
from . import image        # image augmentation pipeline (P16)
from . import test_utils   # §4 test helpers
from .symbol import Symbol

sym = symbol

from .numpy import random  # mx.random parity: seed at top level


def seed(s):
    """Seed EVERY randomness source the framework draws from: the device
    PRNG key (mx.np.random), python's stdlib `random` (image augmenters,
    samplers), and host numpy (≙ the reference's mx.random.seed seeding
    all engine RNGs, MXNET_SEED in docs/env_var.md)."""
    import random as _pyrandom

    import numpy as _onp
    random.seed(s)
    _pyrandom.seed(s)
    _onp.random.seed(int(s) % (2 ** 32))

from . import onnx         # ONNX export/import (P13)
from . import quantization  # INT8 PTQ flow (N13/P14)
from . import subgraph       # partition backend registry (N12)
contrib.quantization = quantization  # mx.contrib.quantization parity path
from . import library        # external extension-lib loader (N28)
from . import rtc            # runtime-compiled Pallas user kernels (P15)
from . import tvmop          # compiler-generated op registry (N32)
from . import _ffi           # PackedFunc-style function registry (N24/P17)
register_func = _ffi.register_func
get_global_func = _ffi.get_global_func
from . import visualization  # print_summary / plot_network (P18)
from . import callback       # Speedometer, do_checkpoint (P18)
from . import model          # save/load_checkpoint, _create_kvstore (P18)
from . import tensorboard as _tb
contrib.tensorboard = _tb    # mx.contrib.tensorboard parity path

# observability recorder (P18+): imported ONLY when the sampler knob is
# set, so the off path costs one env read at import — the obs package
# autostarts its sampler thread on import (docs/observability.md)
import os as _os
if _os.environ.get("MXNET_OBS_INTERVAL_MS", ""):
    from . import obs        # noqa: F401
del _os
