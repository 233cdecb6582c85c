"""mx.runtime — feature detection (≙ python/mxnet/runtime.py, src/libinfo.cc).

Reports the capabilities compiled/available in this build: device platforms,
Pallas, distributed, precision support.
"""
from __future__ import annotations

import jax

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _platforms():
    plats = set()
    for d in jax.devices():
        plats.add(d.platform)
    return plats


def feature_list():
    plats = _platforms()
    feats = {
        "TPU": "tpu" in plats,
        "GPU": bool(plats & {"gpu", "cuda", "rocm"}),
        "CPU": True,
        "XLA": True,
        "PALLAS": True,
        "BF16": True,
        "INT8": True,
        "DIST_KVSTORE": True,
        "JIT": True,
        "AUTOGRAD": True,
    }
    return [Feature(k, v) for k, v in feats.items()]


class Features(dict):
    def __init__(self):
        super().__init__({f.name: f for f in feature_list()})

    def is_enabled(self, name):
        return self[name.upper()].enabled
