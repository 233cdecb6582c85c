"""InferenceEngine — one donated GSPMD program per (model, bucket).

The serving analogue of the fused train step (parallel/train.py): the
model's forward is lifted into a named pure function once via
``HybridBlock.pure_fn(train=False)`` (inference-mode trace: BatchNorm
uses running stats, no aux writeback, no grad tape), then one
``jax.jit`` program is compiled per batch bucket in the configured
power-of-two ladder.  The input batch is donated — it is freshly padded
for every execution and never reused — while the parameter dict is a
plain (non-donated) argument so every bucket program shares the same
device-resident weights.

Tensor-parallel serving (ROADMAP item 2's second half): with ``mesh=``
(or ``MXNET_SERVE_MESH``) the engine resolves a :class:`ShardingPlan`
(explicit > ``MXNET_SERVE_SHARDING_PLAN`` > ``infer_plan`` over the
net's collected params) and places parameter *storage* 1/tp-sharded
across the mesh — the memory scale-out that lets a model exceed one
chip's HBM.  Inside every bucket program the weights are gathered at
use (``with_sharding_constraint`` to replicated — an exact all-gather),
the same layout that makes the sharded train step bit-for-bit equal to
the replicated one (parallel/train.py, docs/sharding.md): tp only adds
exact gathers, never re-associates a contraction, so a tp=2 replica
serves byte-identical predictions to the unsharded engine (gated by
``make tp-serve-check``).  Inputs are ``batch_sharding``-placed; a
simulated per-device HBM budget (``MXNET_SERVE_HBM_BUDGET``) refuses
models whose per-device parameter bytes exceed it.

Retrace discipline follows generate.py's DecodeEngine: programs are
keyed by (bucket, plan fingerprint, precision, ``serve_fingerprint()``),
so a sharding-plan or serve-mesh edit compiles a NEW program (a
counted ``serve.rebuilds``) instead of serving a stale executable;
after :meth:`warmup` a SECOND trace of a warmed key is a shape leak and
increments ``serve.retraces`` — gated at zero by ``make serve-check``.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as onp

from .. import telemetry as _telemetry
from ..ndarray import NDArray

__all__ = ["InferenceEngine", "HBMBudgetExceeded", "DEFAULT_BUCKETS",
           "PRECISIONS", "HBM_BUDGET_ENV", "bucket_ladder",
           "resolve_precision", "resolve_serve_mesh", "hbm_budget"]

DEFAULT_BUCKETS = (1, 2, 4, 8)

PRECISIONS = ("fp32", "bf16", "int8")

# simulated per-device HBM budget in bytes (0/unset = unlimited): an
# engine whose per-device parameter bytes exceed it refuses to serve —
# the operator's dry-run probe for "does this model need sharding?"
HBM_BUDGET_ENV = "MXNET_SERVE_HBM_BUDGET"


class HBMBudgetExceeded(RuntimeError):
    """Per-device parameter bytes exceed ``MXNET_SERVE_HBM_BUDGET`` —
    shard the model over tp (docs/serving.md §sharded serving) or raise
    the budget."""


def resolve_precision(precision: Optional[str] = None) -> str:
    """Resolve the serving precision: explicit argument (per-model
    override) > ``MXNET_SERVE_PRECISION`` env default > fp32.  An engine
    resolves it once, at construction, and keys its programs on it."""
    p = str(precision or os.environ.get("MXNET_SERVE_PRECISION", "")
            or "fp32").lower()
    p = {"float32": "fp32", "bfloat16": "bf16"}.get(p, p)
    if p not in PRECISIONS:
        raise ValueError(
            f"precision {precision!r} not one of {PRECISIONS}")
    return p


def resolve_serve_mesh(mesh=None):
    """Resolve the serving mesh: explicit argument > ``MXNET_SERVE_MESH``
    (``tp=2`` grammar, mesh_from_env) > None (single-device, the
    pre-sharding behavior).  The env mesh may cover a subset of the rig
    — a tp=2 replica on an 8-chip host leaves six chips for
    co-tenants."""
    if mesh is not None:
        return mesh
    import jax

    from ..parallel.mesh import mesh_from_env
    from ..parallel.sharding import SERVE_MESH_ENV
    return mesh_from_env(devices=jax.devices(), env=SERVE_MESH_ENV)


def hbm_budget() -> int:
    """``MXNET_SERVE_HBM_BUDGET`` in bytes/device; 0 = unlimited."""
    v = os.environ.get(HBM_BUDGET_ENV, "").strip()
    if not v:
        return 0
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{HBM_BUDGET_ENV}={v!r}: want bytes (int)") \
            from None


def bucket_ladder(buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Resolve the bucket ladder: explicit argument, else
    ``MXNET_SERVE_BUCKETS`` (comma list), else (1, 2, 4, 8).  Sorted,
    deduplicated, all >= 1."""
    if buckets is None:
        env = os.environ.get("MXNET_SERVE_BUCKETS", "")
        if env.strip():
            buckets = [int(t) for t in env.split(",") if t.strip()]
        else:
            buckets = DEFAULT_BUCKETS
    out = tuple(sorted({int(b) for b in buckets}))
    if not out or out[0] < 1:
        raise ValueError(f"invalid bucket ladder {buckets!r}")
    return out


class InferenceEngine:
    """Compiled inference programs for one model over a bucket ladder.

    Parameters
    ----------
    net : HybridBlock
        The model.  Deferred-init nets are materialized by one example
        forward at ``buckets[0]``.
    item_shape : tuple
        Shape of ONE request item (no batch dim), e.g. ``(3, 224, 224)``.
    dtype : str
        Input dtype (default float32).
    buckets : sequence of int, optional
        Batch-size ladder; default from ``MXNET_SERVE_BUCKETS``.
    name : str
        Model name, used in telemetry/log labels.
    precision : str, optional
        ``fp32`` | ``bf16`` | ``int8``; default from
        ``MXNET_SERVE_PRECISION`` (fp32 when unset).  bf16 casts the
        model in place (amp.convert_model); int8 runs post-training
        quantization (quantization.quantize_net) before the pure-fn
        trace, so every bucket program bakes the int8 weights and
        per-channel scales as XLA constants.  Nets that are already
        quantized pass through untouched.
    calib_data : iterable, optional
        Calibration batches for ``precision="int8"``.  Falls back to two
        seeded synthetic uniform batches — fine for the gate, but real
        serving should calibrate from representative traffic (e.g.
        ``quantization.thresholds_from_telemetry``).
    mesh : jax.sharding.Mesh, optional
        Device mesh for tensor-parallel serving; default from
        ``MXNET_SERVE_MESH`` (None = single-device).
    sharding_plan : ShardingPlan, optional
        Per-parameter layout; default ``MXNET_SERVE_SHARDING_PLAN``
        (a JSON plan file), else ``infer_plan`` over the net when the
        mesh has tp > 1.  The plan fingerprint keys every compiled
        program, so a plan edit recompiles instead of serving a stale
        route.
    """

    def __init__(self, net, item_shape, dtype: str = "float32",
                 buckets: Optional[Sequence[int]] = None,
                 name: str = "default", precision: Optional[str] = None,
                 calib_data=None, mesh=None, sharding_plan=None):
        import jax
        import jax.numpy as jnp

        self.net = net
        self.name = name
        self.item_shape = tuple(int(d) for d in item_shape)
        self.dtype = onp.dtype(dtype)
        self.buckets = bucket_ladder(buckets)
        self._jnp = jnp
        self.precision = resolve_precision(precision)
        if self.precision == "bf16":
            from .. import amp as _amp
            _amp.convert_model(net, "bfloat16")
            if self.dtype == onp.dtype("float32"):
                import ml_dtypes
                self.dtype = onp.dtype(ml_dtypes.bfloat16)
        elif self.precision == "int8":
            self._quantize(net, calib_data)

        example = NDArray(jnp.zeros((self.buckets[0],) + self.item_shape,
                                    dtype=self.dtype.name))
        self._fn, params = net.pure_fn(example, train=False)
        # weights stay device-resident and shared across bucket programs
        self._pvals = {n: p.data()._data for n, p in params.items()}
        self._rng = jax.random.PRNGKey(0)   # closure constant: inference

        # ----------------------------------------- tensor-parallel layout
        from ..parallel import sharding as _sharding
        self.mesh = resolve_serve_mesh(mesh)
        self.plan = None
        self.tp = 1
        self._rep = None            # gather-at-use target inside programs
        self._in_sharding = None    # batch_sharding placement for inputs
        if self.mesh is not None:
            from ..parallel.mesh import (axis_size, batch_sharding,
                                         replicated)
            plan = _sharding.resolve_plan(sharding_plan,
                                          env=_sharding.SERVE_PLAN_ENV)
            self.tp = axis_size(self.mesh,
                                plan.tp_axis if plan is not None else "tp")
            if plan is None and self.tp > 1:
                plan = _sharding.infer_plan(net, mesh=self.mesh)
            self.plan = plan
            self._rep = replicated(self.mesh)
            self._in_sharding = batch_sharding(
                self.mesh, 1 + len(self.item_shape))
            # storage sharded 1/tp at rest; programs gather at use
            with _telemetry.timed("serve.shard_place_us"):
                self._pvals = {
                    n: jax.device_put(
                        v, plan.sharding(self.mesh, n)
                        if plan is not None else self._rep)
                    for n, v in self._pvals.items()}

        self.param_bytes_per_device = int(sum(
            _sharding.shard_bytes(v) for v in self._pvals.values()))
        budget = hbm_budget()
        if budget and self.param_bytes_per_device > budget:
            raise HBMBudgetExceeded(
                f"model {name!r}: {self.param_bytes_per_device} parameter "
                f"bytes/device exceeds {HBM_BUDGET_ENV}={budget}; serve it "
                f"sharded (mesh tp>1) or raise the budget")
        # gauges emit only for engines that will actually serve — a
        # budget-refused build must not clobber the live replica's values
        _telemetry.gauge_set("serve.tp", self.tp)
        _telemetry.gauge_set("serve.param_bytes_per_device",
                             self.param_bytes_per_device)

        self._programs: Dict[tuple, object] = {}
        self._trace_counts: Dict[tuple, int] = {}
        self._warm = False
        self.retraces = 0
        self.rebuilds = 0
        self._mu = threading.Lock()
        _telemetry.counter_add(f"serve.precision.builds.{self.precision}")

    def _quantize(self, net, calib_data):
        """PTQ the net in place for ``precision="int8"`` — unless the
        caller handed over an already-quantized net (pre-calibrated
        offline), which passes through untouched."""
        from .. import quantization as _q
        blocks = [net] + [c for _, c, _ in _q._walk(net)]
        if any(isinstance(b, (_q.QuantizedDense, _q.QuantizedConv2D))
               for b in blocks):
            return
        if calib_data is None:
            rs = onp.random.RandomState(0)
            calib_data = [
                NDArray(self._jnp.asarray(
                    (rs.rand(self.buckets[0], *self.item_shape) * 2.0 - 1.0)
                    .astype("float32")))
                for _ in range(2)]
        _q.quantize_net(net, calib_data=calib_data, calib_mode="naive")

    # ----------------------------------------------------------- programs
    def _fp(self) -> tuple:
        """Program-cache key tail, all of it resolved by this layer: the
        plan's fingerprint (an explicitly-passed plan never touches
        env), the engine's precision, and the env-resolved serve
        mesh/plan (``sharding.serve_fingerprint``)."""
        from ..parallel import sharding as _sharding
        return (self.plan.fingerprint if self.plan is not None else "",
                self.precision, _sharding.serve_fingerprint())

    def _note_trace(self, key):
        """Trace-time side effect inside every bucket program.  Like
        DecodeEngine: after warmup a FIRST trace of a NEW key is a
        sanctioned rebuild (the plan or serve fingerprint changed —
        counted ``serve.rebuilds``); only a SECOND trace of the same key
        is a shape leak (``serve.retraces``, gated at 0)."""
        with self._mu:
            n = self._trace_counts.get(key, 0) + 1
            self._trace_counts[key] = n
            if self._warm:
                if n > 1:
                    self.retraces += 1
                    _telemetry.counter_add("serve.retraces")
                else:
                    self.rebuilds += 1
                    _telemetry.counter_add("serve.rebuilds")

    def _prog(self, bucket: int):
        key = (bucket,) + self._fp()
        with self._mu:
            prog = self._programs.get(key)
        if prog is None:
            prog = self._build(bucket, key)
            with self._mu:
                prog = self._programs.setdefault(key, prog)
                n = len(self._programs)
            _telemetry.gauge_set("serve.programs", n)
        return prog

    def _build(self, bucket: int, key: tuple):
        import jax

        fn, rng = self._fn, self._rng
        note = self._note_trace
        rep = self._rep

        def run(pvals, x):
            note(key)
            if rep is not None:
                # gather-at-use: storage stays 1/tp, the program sees
                # replicated weights — an exact all-gather, so sharded
                # serving is bit-for-bit with the unsharded engine
                pvals = {k: jax.lax.with_sharding_constraint(v, rep)
                         for k, v in pvals.items()}
            return fn(rng, pvals, x)

        # donate the input batch (padded fresh per execution); params are
        # a plain argument shared by every bucket program
        return jax.jit(run, donate_argnums=(1,))

    def warmup(self):
        """Precompile every bucket program with a zero batch and block
        until done.  After this, a second trace of any warmed key counts
        as a retrace (a NEW key — plan/serve fingerprint flip — counts
        as a rebuild instead)."""
        import warnings

        jnp = self._jnp
        with _telemetry.timed("serve.warmup_us"), warnings.catch_warnings():
            # donation still releases the input batch early even when XLA
            # can't alias it into an output — the "not usable" warning at
            # lowering time is expected for classifier shapes
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for b in self.buckets:
                x = self._place(
                    jnp.zeros((b,) + self.item_shape, dtype=self.dtype.name))
                outs = self._prog(b)(self._pvals, x)
                for o in outs:
                    o.block_until_ready()
        # _note_trace tests _warm under _mu on the execute path; flip
        # it under the same lock so the retrace counter can't misfire
        # around the warm transition
        with self._mu:
            self._warm = True
        return self

    @property
    def warm(self) -> bool:
        return self._warm

    @property
    def ready(self) -> bool:
        """Readiness for traffic: every bucket program precompiled.
        The readiness-aware ``/healthz`` (server.py) reports a model as
        ``warming`` — and returns 503 — until this flips, so a router
        never shifts traffic onto a replica that would pay compile time
        on the serving path."""
        return self._warm

    def trace_counts(self) -> Dict[int, int]:
        """Trace count per bucket (summed over program-key generations)."""
        out: Dict[int, int] = {b: 0 for b in self.buckets}
        with self._mu:
            for key, n in self._trace_counts.items():
                out[key[0]] = out.get(key[0], 0) + n
        return out

    # ------------------------------------------------------------ dispatch
    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding n items; raises for n > max bucket."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} exceeds max bucket {self.buckets[-1]}")

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def _place(self, x):
        """batch_sharding-place an input batch on the mesh (leading dim
        over dp — size 1 on a tp-only serving mesh, so effectively
        replicated); no-op single-device."""
        if self._in_sharding is None:
            return x
        import jax
        return jax.device_put(x, self._in_sharding)

    def run(self, x) -> Tuple:
        """Execute the bucket program matching ``x.shape[0]`` (must be an
        exact ladder rung — the batcher pads to one).  Returns the tuple
        of raw device outputs (not blocked)."""
        x = self._jnp.asarray(x, dtype=self.dtype.name)
        b = int(x.shape[0])
        if b not in self.buckets:
            raise ValueError(
                f"batch size {b} is not a bucket of {self.buckets}")
        # dispatch-side span (outputs are NOT blocked here; device wall
        # time lands in the caller's serve.device_us once forced)
        _telemetry.counter_add(f"serve.precision.batches.{self.precision}")
        with _telemetry.span("serve.engine_run", model=self.name, bucket=b):
            return self._prog(b)(self._pvals, self._place(x))

    def stats(self) -> dict:
        return {
            "name": self.name,
            "item_shape": list(self.item_shape),
            "dtype": self.dtype.name,
            "precision": self.precision,
            "buckets": list(self.buckets),
            "warm": self._warm,
            "ready": self.ready,
            "retraces": self.retraces,
            "rebuilds": self.rebuilds,
            "trace_counts": self.trace_counts(),
            "tp": self.tp,
            "plan_fingerprint": (self.plan.fingerprint
                                 if self.plan is not None else None),
            "param_bytes_per_device": self.param_bytes_per_device,
            "programs": len(self._programs),
        }
