"""Device / Context abstraction over JAX devices.

TPU-native equivalent of the reference's ``Context`` (python/mxnet/context.py,
include/mxnet/base.h ``Context``): a lightweight (device_type, device_id) handle
plus a thread-local "current context" stack.  Instead of CUDA device ordinals,
a Context resolves to a concrete :class:`jax.Device` (PJRT device), so
``mx.tpu()`` places arrays on the TPU chip and ``mx.cpu()`` on the host
platform.  A kind with no device on this host raises when it is resolved
(``Context.jax_device``); it never stands in for another platform.  There
is no per-context stream/storage pool to manage here — PJRT owns device
memory and XLA owns scheduling.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Context", "Device", "cpu", "gpu", "tpu", "current_context",
    "current_device", "num_gpus", "num_tpus", "_context_stack",
]

# JAX platform names behind each device kind.
_KIND_PLATFORMS = {
    "cpu": ("cpu",),
    "gpu": ("gpu", "cuda", "rocm"),
    "tpu": ("tpu",),
}


def _devices_for(kind: str):
    out = []
    for plat in _KIND_PLATFORMS.get(kind, (kind,)):
        try:
            out.extend(jax.devices(plat))
        except RuntimeError:
            continue
    if not out:
        # no silent stand-in: mx.tpu() on a host without a TPU is an error,
        # not a CPU device under another name
        raise RuntimeError(
            f"no {kind!r} device here (jax default backend: "
            f"{jax.default_backend()!r})")
    return out


class Context:
    """A (device_type, device_id) pair resolving to a PJRT device."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution -------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_for(self.device_type)
        return devs[self.device_id % len(devs)]

    # -- protocol ---------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __enter__(self):
        _context_stack.stack.append(self)
        return self

    def __exit__(self, *exc):
        _context_stack.stack.pop()

    # parity helper mirroring mx.Context.empty_cache (no-op under PJRT)
    def empty_cache(self):
        pass


Device = Context  # 2.0 naming (python/mxnet/device.py)


class _ContextStack(threading.local):
    def __init__(self):
        self.stack = []


_context_stack = _ContextStack()


def current_context() -> Context:
    if _context_stack.stack:
        return _context_stack.stack[-1]
    return _default_context()


current_device = current_context


def _default_context() -> Context:
    plat = jax.default_backend()
    for kind, plats in _KIND_PLATFORMS.items():
        if plat in plats:
            return Context(kind, 0)
    return Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    try:
        return len(jax.devices("gpu"))
    except RuntimeError:
        return 0


def num_tpus() -> int:
    n = 0
    for plat in _KIND_PLATFORMS["tpu"]:
        try:
            n += len(jax.devices(plat))
        except RuntimeError:
            pass
    return n
