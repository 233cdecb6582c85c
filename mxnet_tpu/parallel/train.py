"""Fused training step: forward + backward + optimizer update in ONE XLA
computation with donated buffers.

This is the TPU-native equivalent of the reference's fast path stack —
CachedOp static_alloc forward (cached_op.cc:680), CachedOp::Backward
(cached_op.cc:1089) and the fused multi-tensor optimizer ops
(optimizer_op.cc:352 multi_sgd_update) — collapsed into a single compiled
executable, which is what XLA wants: fusion across fwd/bwd/update, no
host round-trips inside a step, buffer donation for in-place weight update.

With a mesh, parameters are replicated and the batch is sharded over 'dp';
XLA inserts the gradient all-reduce over ICI automatically (the
KVStore('device') pushpull of trainer.py:392, as a compiler-scheduled
collective).

With a mesh AND a :class:`~mxnet_tpu.parallel.sharding.ShardingPlan`,
parameter / gradient-at-optimizer / optimizer-state STORAGE is sharded
1/tp per device per the plan's PartitionSpecs; weights are gathered at
their use site inside the donated program (exact all-gather) and the
gradient cotangents are constrained back to the storage sharding, so the
dp all-reduce is the only gradient collective and the optimizer update
is tp-local.  This layout keeps the step bit-for-bit equal to the
replicated step at the same dp grouping (docs/sharding.md) while the
per-device parameter footprint drops to 1/tp.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import tape
from .. import telemetry as _telemetry
from ..ndarray import NDArray
from ..numpy.random import new_key, push_trace_key, pop_trace_key
from ..gluon.block import HybridBlock, _pure_trace
from .mesh import axis_size as _axis_size, batch_sharding as _batch_sharding

__all__ = ["FusedTrainStep", "TrainerFusedStep", "aggregate_grads",
           "data_parallel_shardings"]


def data_parallel_shardings(mesh, batch_ndim=4, batch_axis="dp"):
    """(param_sharding, batch_sharding) for pure data parallelism."""
    param_s = NamedSharding(mesh, PartitionSpec())
    batch_s = NamedSharding(
        mesh, PartitionSpec(batch_axis, *([None] * (batch_ndim - 1))))
    return param_s, batch_s


def aggregate_grads(grads, mesh=None, shardings=None):
    """Gradient aggregation INSIDE the fused program.

    Single device: identity — the kvstore('device') pushpull of one local
    gradient is a no-op sum and is elided entirely.  With a mesh the
    parameters are replicated and the batch is sharded over 'dp', so each
    gradient leaf is already a cross-replica sum waiting to happen: pinning
    the replicated sharding here makes GSPMD materialize the all-reduce AT
    THIS POINT of the program (over ICI, overlappable with the remaining
    backward), instead of deferring it to the first consumer — the
    compiler-scheduled equivalent of the reference's device-kvstore
    allreduce (kvstore_local.h comm_device).

    With per-name ``shardings`` (the plan's STORAGE shardings) each
    gradient is constrained to its parameter's stored layout instead:
    GSPMD emits the dp all-reduce AND keeps (or slices) the tensor-
    parallel dimension in one schedulable collective — gradients never
    materialize gathered, which is the sharded-optimizer memory story.
    """
    if mesh is None:
        return grads
    if shardings is not None:
        return {n: jax.lax.with_sharding_constraint(g, shardings[n])
                for n, g in grads.items()}
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(
        lambda g: jax.lax.with_sharding_constraint(g, rep), grads)


def _fused_step_env() -> Optional[bool]:
    """MXNET_FUSED_STEP: None = unset (default: on for hybridized blocks),
    False = explicitly off, True = explicitly on."""
    v = os.environ.get("MXNET_FUSED_STEP")
    if v is None or v == "":
        return None
    return v not in ("0", "false", "False", "off")


_NOT_BUILDING = contextlib.nullcontext()


@contextlib.contextmanager
def _building():
    """``train.build`` around the call that makes a step program: collect
    + trace + lower + compile-or-load.  Its duration also goes to the
    counter ``fused.build_us``."""
    t0 = time.perf_counter_ns()
    try:
        with _telemetry.span("train.build"):
            yield
    finally:
        _telemetry.counter_add("fused.build_us",
                               (time.perf_counter_ns() - t0) // 1000)


_programs_built = 0


def _note_program_built():
    """One compiled fused-step executable came alive (per (block,
    optimizer) identity); rebuilds replace, they don't re-count."""
    global _programs_built
    _programs_built += 1
    _telemetry.gauge_set("fused.programs", _programs_built)


def _note_trace(owner):
    """Trace-time side effect inside the fused step fn: fires once on the
    expected first trace and counts every later trace of the SAME
    executable as a retrace (donation misuse, unstable shapes/dtypes —
    steady state must stay at zero, gated by --check)."""
    owner._trace_count += 1
    if owner._trace_count > 1:
        _telemetry.counter_add("fused.retraces")


class FusedTrainStep:
    """Compile a gluon block + loss + optimizer into one train-step executable.

    >>> step = FusedTrainStep(net, loss_fn, optimizer, mesh=mesh)
    >>> l = step(x, y)          # one XLA call; returns scalar loss NDArray
    """

    def __init__(self, net, loss: Callable, optimizer, mesh=None,
                 batch_axis: str = "dp", grad_scale: Optional[float] = None,
                 dtype=None):
        from .mesh import current_mesh
        self._net = net
        self._loss = loss
        self._opt = optimizer
        self._mesh = mesh if mesh is not None else current_mesh()
        self._batch_axis = batch_axis
        self._grad_scale = grad_scale
        # Mixed precision ≙ amp (P12) fused into the step: master weights
        # stay f32 (donated through the optimizer update); params and batch
        # are cast to `dtype` (bf16 = native MXU input) at the top of the
        # traced step, the whole fwd/bwd runs low-precision (activations,
        # conv outputs, cotangents — halving HBM traffic), and the loss +
        # optimizer math stay f32.  bf16 keeps f32's exponent so no loss
        # scaling is required (amp/__init__.py rationale).
        self._dtype = jnp.dtype(dtype) if dtype is not None else None
        self._compiled = None
        self._tr_names = None     # trainable param names, stable order
        self._fr_names = None     # frozen params (running stats etc.)
        self._params = None       # name -> Parameter
        self._tr = None           # name -> raw jax array (donated through step)
        self._fr = None
        self._states = None
        self._ctl = None          # device-resident {rng, t}, donated
        self._lr_host = None      # last lr seen (host float)
        self._lr_dev = None       # cached device scalar for it

    # ------------------------------------------------------------------ build
    def _collect(self, x_nd):
        net = self._net
        pd = net.collect_params()
        uninit = [p for p in pd.values() if p._data is None]
        if uninit:
            # one eager forward resolves deferred shapes (≙ first
            # _build_cache call in the reference, block.py:1131)
            prev = tape.set_training(False)
            try:
                net(x_nd)
            finally:
                tape.set_training(prev)
            pd = net.collect_params()
        self._params = dict(pd.items())
        self._tr_names = [k for k, p in pd.items() if p.grad_req != "null"]
        self._fr_names = [k for k, p in pd.items() if p.grad_req == "null"]
        self._tr = {k: pd[k].data()._data for k in self._tr_names}
        self._fr = {k: pd[k].data()._data for k in self._fr_names}
        self._states = {k: self._opt.init_state(self._tr[k])
                        for k in self._tr_names}
        # rng key and step counter live on device and flow through the
        # donated step — no per-step host transfers (new_key/asarray were
        # ~3.5 ms/step of dispatch time on the profile)
        self._ctl = {"rng": new_key(),
                     "t": jnp.asarray(self._opt.num_update, jnp.int32)}
        self._t_host = self._opt.num_update   # mirror of ctl["t"]
        if self._mesh is not None:
            rep = NamedSharding(self._mesh, PartitionSpec())
            self._tr = jax.device_put(self._tr, rep)
            self._fr = jax.device_put(self._fr, rep)
            self._states = jax.device_put(self._states, rep)
            self._ctl = jax.device_put(self._ctl, rep)

    def _build(self):
        net, loss_fn, opt = self._net, self._loss, self._opt
        params = self._params

        def forward(sub_vals, rng, x, y):
            push_trace_key(rng)
            prev_train = tape.set_training(True)
            try:
                with _pure_trace({id(params[k]): v
                                  for k, v in sub_vals.items()}) as ctx:
                    if jnp.issubdtype(x.dtype, jnp.integer):
                        # uint8/int8 loader batches (ImageRecordIter dtype=):
                        # pixels ride the wire 4× smaller; the cast to compute
                        # dtype fuses into the step here, on device
                        with jax.named_scope("mx.cast"):
                            x = x.astype(self._dtype or jnp.float32)
                    with jax.named_scope("mx.fwd"):
                        out = net.forward(NDArray(x))
                    if self._dtype is not None:
                        # logits back to f32 before the loss (softmax/log stay
                        # full precision, ≙ amp FP32_OPS list)
                        with jax.named_scope("mx.cast"):
                            if isinstance(out, (tuple, list)):
                                out = type(out)(o.astype(jnp.float32)
                                                for o in out)
                            else:
                                out = out.astype(jnp.float32)
                    with jax.named_scope("mx.loss"):
                        l = loss_fn(out, NDArray(y))
                        l = l.mean() if l.ndim > 0 else l
                    by_id = {id(p): name for name, p in params.items()}
                    aux_vals = {by_id[id(p)]: ctx.aux_out[id(p)]
                                for p in ctx.aux_params}
            finally:
                tape.set_training(prev_train)
                pop_trace_key()
            return l._data, aux_vals

        scale = self._grad_scale
        dtype = self._dtype

        def cast_low(v):
            if dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(dtype)
            return v

        def cast_frozen(k, v):
            # BN running stats only feed the EMA in training mode (batch
            # stats drive the normalization), so keep them f32 — casting
            # would clamp the stored running stats to bf16 precision
            if k.endswith(("running_mean", "running_var")):
                return v
            return cast_low(v)

        def step(tr, fr, states, ctl, lr, x, y):
            _note_trace(self)
            rng, sub_key = jax.random.split(ctl["rng"])
            t = ctl["t"] + 1

            def loss_of(tr_):
                with jax.named_scope("mx.cast"):
                    sub = {k: cast_low(v) for k, v in tr_.items()}
                    sub.update({k: cast_frozen(k, v) for k, v in fr.items()})
                    x_low = cast_low(x)
                lval, aux = forward(sub, sub_key, x_low, y)
                if scale:
                    lval = lval * scale
                return lval, aux

            (lval, aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tr)
            if scale:
                lval = lval / scale
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
            with jax.named_scope("mx.grad_sync"):
                grads = aggregate_grads(grads, self._mesh)
            with jax.named_scope("mx.opt"):
                new_tr, new_states = opt._tree_update(tr, grads, states,
                                                      lr, t)
            new_fr = dict(fr)
            new_fr.update(aux)
            return lval, new_tr, new_fr, new_states, {"rng": rng, "t": t}

        self._trace_count = 0
        self._compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3))
        _note_program_built()

    # ------------------------------------------------------------------- call
    def __call__(self, x, y):
        first = self._compiled is None
        # rotate the per-step trace id: this step's span, the DataFeed
        # wait that follows it and any checkpoint pause share one trace
        _telemetry.set_current_trace()
        with _step_span(self, self._opt.num_update + 1):
            with _building() if first else _NOT_BUILDING:
                with _telemetry.span("train.prep"):
                    x_raw, y_raw = self._prepare(x, y)
                _telemetry.counter_add("fused.steps")
                _telemetry.counter_add("fused.dispatches")
                with _telemetry.span("train.launch"), \
                        _telemetry.timed("fused.step_us"):
                    (lval, self._tr, self._fr, self._states,
                     self._ctl) = self._compiled(
                        self._tr, self._fr, self._states, self._ctl,
                        self._lr_dev, x_raw, y_raw)
            with _telemetry.span("train.writeback"):
                self._writeback()
        return NDArray(lval)

    def _prepare(self, x, y):
        """Everything the host does before the launch: the first call's
        collect + build, batch placement, the ``t`` and ``lr`` resync."""
        x_raw = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        y_raw = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if self._compiled is None:
            # shape-collection runs the net eagerly once: give it float
            # even when the wire format is uint8/int8 (the jitted step
            # casts on device — forward() in _build)
            cx = x_raw.astype(jnp.float32) \
                if jnp.issubdtype(x_raw.dtype, jnp.integer) else x_raw
            self._collect(NDArray(cx))
            self._build()
        if self._mesh is not None:
            bs = _batch_sharding(self._mesh, x_raw.ndim, self._batch_axis)
            ys = _batch_sharding(self._mesh, y_raw.ndim, self._batch_axis)
            x_raw = jax.device_put(x_raw, bs)
            y_raw = jax.device_put(y_raw, ys)
        if self._opt.num_update != self._t_host:
            # num_update changed outside this step (checkpoint resume, a
            # second trainer sharing the optimizer) — re-sync the device
            # counter so Adam/LAMB bias correction sees the true t
            self._ctl = dict(self._ctl,
                             t=jnp.asarray(self._opt.num_update, jnp.int32))
        self._opt.num_update += 1
        self._t_host = self._opt.num_update
        lr = float(self._opt.learning_rate)
        if lr != self._lr_host:
            self._lr_host = lr
            self._lr_dev = jnp.asarray(lr, jnp.float32)
        return x_raw, y_raw

    def _writeback(self):
        """Reflect updated buffers into the user-visible Parameters (cheap:
        swaps the device buffer inside the existing NDArray handles — no
        transfer, no wrapper churn — ≙ engine write-var bump)."""
        for k in self._tr_names:
            d = self._params[k]._data
            if d is not None:
                d._data = self._tr[k]
            else:
                self._params[k]._data = NDArray(self._tr[k])
        for k in self._fr_names:
            d = self._params[k]._data
            if d is not None:
                d._data = self._fr[k]
            else:
                self._params[k]._data = NDArray(self._fr[k])

    def sync(self):
        jax.block_until_ready(_drained(self)._tr)


class TrainerFusedStep:
    """Whole-step executor behind ``Trainer.fuse_step(loss_fn)``.

    One donated XLA program per (block, optimizer) identity running
    forward + loss + vjp + gradient aggregation + the optimizer tree
    update; gradients never materialize as framework NDArrays and the
    returned loss is an async jax array (no per-step host sync).

    Unlike :class:`FusedTrainStep` (a standalone loop for benchmarks),
    this executor SHARES the Trainer's optimizer state: ``num_update``,
    ``trainer._states`` and the parameter buffers are read before and
    written back after every call, so fused and legacy steps can
    interleave freely — checkpointing (``save_states``), lr schedulers
    and a later plain ``trainer.step()`` all observe the same state.

    Semantics match the legacy path exactly (bit-for-bit on a single
    device): gradients of ``sum(loss)``, rescaled by
    ``trainer._scale / batch_size`` inside the optimizer rule, lr read
    AFTER advancing ``num_update`` (update_multi ordering).  Any
    condition the fused program cannot express routes the call through
    the legacy record/backward/step path and counts a
    ``fused.fallback.<reason>`` — stale-grad bookkeeping stays correct
    either way because the fused path consumes every trainable grad edge
    (``edge.grad = None``) after applying its update.

    The one deliberate divergence: a trainable parameter that does not
    participate in the forward gets a ZERO gradient applied (optimizer
    state still advances) where the legacy path raises the stale-grad
    ``UserWarning`` — the same zero-fill semantics the collective
    kvstore path uses for stale-here/live-elsewhere keys.
    """

    def __init__(self, trainer, loss_fn: Callable, net=None):
        self._trainer = trainer
        self._loss = loss_fn
        self._net = net
        self._opt = trainer._optimizer
        self._mesh = trainer._mesh
        self._batch_axis = trainer._batch_axis
        # sharding plan (parallel/sharding.py): storage layout of params /
        # grads-at-optimizer / optimizer states; None = fully replicated
        self._plan = getattr(trainer, "_sharding_plan", None) \
            if self._mesh is not None else None
        self._param_shardings = None  # pure name -> storage NamedSharding
        self._coll_bytes = None       # modeled per-step collective bytes
        self._compiled = None
        self._sig = None            # (optimizer constants, plan fingerprint)
        self._trace_count = 0
        self._built = False         # programs gauge bumped once per identity
        self._fn = None             # block pure fn (named pvals/aux)
        self._params = None         # pure name -> Parameter
        self._tr_names = None       # pure names, trainer-trainable
        self._fr_names = None       # pure names, frozen/untrained
        self._tname = None          # pure name -> trainer state key
        self._ctl = None            # device {rng, t}, donated
        self._t_host = None         # host mirror of ctl["t"]
        self._lr_host = None
        self._lr_dev = None
        self.fallback_reason = self._static_fallback()

    # -------------------------------------------------------------- gating
    def _static_fallback(self) -> Optional[str]:
        env = _fused_step_env()
        if env is False:
            return "disabled"
        net = self._net
        if net is None:
            return "no_net"
        if not isinstance(net, HybridBlock):
            return "not_hybrid_block"
        if not getattr(net, "_active", False) and env is not True:
            # default on only when hybridized; MXNET_FUSED_STEP=1 forces
            # the trace for plain (but traceable) forward bodies
            return "not_hybridized"
        tr = self._trainer
        if tr._update_on_kvstore:
            return "update_on_kvstore"
        kv = tr._kvstore
        if kv is not None and (getattr(kv, "num_workers", 1) > 1
                               or getattr(kv, "collective_push", False)
                               or getattr(kv, "batched_pushpull", False)):
            return "dist_kvstore"
        for name, p in tr._trainable:
            if getattr(p, "grad_stype", "default") == "row_sparse":
                return "sparse_param"
        return None

    @property
    def fused(self) -> bool:
        return self.fallback_reason is None

    # --------------------------------------------------------------- build
    def _build_data(self, x_raw):
        net, tr = self._net, self._trainer
        pd = net.collect_params()
        if any(p._data is None for p in pd.values()):
            # one eager forward resolves deferred shapes (≙ the first
            # _build_cache call in the reference, block.py:1131)
            cx = x_raw.astype(jnp.float32) \
                if jnp.issubdtype(x_raw.dtype, jnp.integer) else x_raw
            prev = tape.set_training(False)
            try:
                net(NDArray(cx))
            finally:
                tape.set_training(prev)
        self._fn, self._params = net.pure_fn()
        trainable_ids = {id(p): n for n, p in tr._trainable}
        net_ids = {id(p) for p in self._params.values()}
        for n, p in tr._trainable:
            if id(p) not in net_ids:
                # a trainer-managed trainable the net never touches would
                # silently stop training under fusion — route to legacy
                self.fallback_reason = "params_mismatch"
                return
        self._tr_names = [n for n, p in self._params.items()
                          if id(p) in trainable_ids]
        self._fr_names = [n for n in self._params if n not in
                          set(self._tr_names)]
        self._tname = {n: trainable_ids[id(p)]
                       for n, p in self._params.items()
                       if id(p) in trainable_ids}
        for n in self._tr_names:
            tn = self._tname[n]
            if tr._states.get(tn) is None:
                tr._states[tn] = self._opt.init_state(
                    self._params[n].data()._data)
        rng0 = getattr(tr, "_restored_rng", None)
        if rng0 is not None:
            # checkpoint restore before the first step: continue the
            # saved rng stream instead of opening a fresh one
            tr._restored_rng = None
            rng0 = jnp.asarray(rng0)
        else:
            rng0 = new_key()
        self._ctl = {"rng": rng0,
                     "t": jnp.asarray(self._opt.num_update, jnp.int32)}
        self._t_host = self._opt.num_update
        if self._mesh is not None:
            rep = NamedSharding(self._mesh, PartitionSpec())
            self._ctl = jax.device_put(self._ctl, rep)
            if self._plan is not None:
                self._place_storage()

    def _place_storage(self):
        """device_put parameter buffers and optimizer states into the
        plan's STORAGE shardings (1/tp per device for planned tensors).
        Runs once at build and again when a plan edit forces a rebuild —
        the reshard cost is observed as ``collective.<tp>.us``."""
        mesh, plan, tr = self._mesh, self._plan, self._trainer
        rep = NamedSharding(mesh, PartitionSpec())
        sh = {n: plan.sharding(mesh, n)
              for n in self._tr_names + self._fr_names}
        self._param_shardings = sh
        with _telemetry.timed(f"collective.{plan.tp_axis}.us"):
            for n in self._tr_names + self._fr_names:
                d = self._params[n]._data
                d._data = jax.device_put(d._data, sh[n])
            for n in self._tr_names:
                tn = self._tname[n]
                tr._states[tn] = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, sh[n]), tr._states[tn])
            self._ctl = jax.device_put(dict(self._ctl), rep)

    def _build_jit(self):
        fn, loss_fn, opt = self._fn, self._loss, self._opt
        mesh = self._mesh
        plan = self._plan
        rep = NamedSharding(mesh, PartitionSpec()) \
            if (mesh is not None and plan is not None) else None
        storage = {n: self._param_shardings[n] for n in self._tr_names} \
            if rep is not None else None

        def step(tr, fr, states, ctl, lr, x, y):
            _note_trace(self)
            rng, sub_key = jax.random.split(ctl["rng"])
            t = ctl["t"] + 1

            def loss_of(tr_):
                if rep is not None:
                    # gather-at-use: the stored 1/tp shards are all-gathered
                    # to replicated right at the consumer — an EXACT
                    # collective (pure data movement), which is why the
                    # sharded step stays bit-for-bit with the replicated
                    # one; the vjp of this constraint slices the cotangent
                    # back to the storage layout
                    tr_ = {k: jax.lax.with_sharding_constraint(v, rep)
                           for k, v in tr_.items()}
                pvals = dict(tr_)
                pvals.update(fr)
                prev_train = tape.set_training(True)
                try:
                    with jax.named_scope("mx.fwd"):
                        outs, aux = fn(sub_key, pvals, x)
                finally:
                    tape.set_training(prev_train)
                out_nd = tuple(NDArray(o) for o in outs)
                with jax.named_scope("mx.loss"):
                    l = loss_fn(out_nd[0] if len(out_nd) == 1 else out_nd,
                                NDArray(y))
                    lraw = l._data if isinstance(l, NDArray) else l
                    # grads of SUM(loss): identical to the legacy tape,
                    # which seeds backward() with ones over the per-sample
                    # loss — the mean comes from rescale_grad inside
                    # _tree_update
                    return lraw.sum(), (lraw, aux)

            (lsum, (lraw, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tr)
            # with a plan, grads land in the STORAGE layout (dp all-reduce
            # + tp slice in one collective — no gather of gradients) and
            # the optimizer update below is tp-local 1/tp work
            with jax.named_scope("mx.grad_sync"):
                grads = aggregate_grads(grads, mesh, shardings=storage)
            with jax.named_scope("mx.opt"):
                new_tr, new_states = opt._tree_update(tr, grads, states,
                                                      lr, t)
            if storage is not None:
                new_tr = {n: jax.lax.with_sharding_constraint(v, storage[n])
                          for n, v in new_tr.items()}
                new_states = {n: jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(a, storage[n]),
                    st) for n, st in new_states.items()}
            new_fr = dict(fr)
            new_fr.update(aux)
            lmean = lsum / lraw.size if lraw.ndim > 0 else lsum
            return lmean, new_tr, new_fr, new_states, {"rng": rng, "t": t}

        self._trace_count = 0
        self._compiled = jax.jit(step, donate_argnums=(0, 1, 2, 3))
        if plan is not None:
            # dispatch-cache convention (dispatch_cache.np_call_key): the
            # plan fingerprint joins any cache key built over this program,
            # so an edited plan can never be served a stale route
            self._compiled.__mx_extra_key__ = plan.extra_key
        self._sig = (opt._fused_sig(),
                     plan.fingerprint if plan is not None else None)
        if mesh is not None:
            shapes = {n: tuple(self._params[n]._data._data.shape)
                      for n in self._tr_names}
            from .sharding import ShardingPlan
            model = (plan or ShardingPlan()).collective_bytes(shapes)
            self._coll_bytes = {ax: b for ax, b in model.items()
                                if b and _axis_size(mesh, ax) > 1}
        if not self._built:
            self._built = True
            _note_program_built()
        # obs: price the model once per program identity so the recorder
        # can derive MFU; resolved via sys.modules so the sampler-off
        # path never even imports the package
        try:
            _obs = sys.modules.get("mxnet_tpu.obs")
            if _obs is not None and _obs.active() and self._net is not None:
                _obs.publish_model_flops(self._net)
        except Exception:
            pass

    # ---------------------------------------------------------------- call
    def __call__(self, x, y, batch_size=None, ignore_stale_grad=False):
        x_raw = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        y_raw = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if batch_size is None:
            batch_size = int(x_raw.shape[0])
        if self.fallback_reason is None:
            # per-step trace rotation (step id = the post-increment count
            # _fused_step is about to commit — continues across a
            # checkpoint restore because num_update is restored state)
            _telemetry.set_current_trace()
            with _step_span(self,
                            int(self._opt.num_update) + 1):
                out = self._fused_step(x_raw, y_raw, batch_size)
            if out is not None:
                return out
        return self._legacy_step(x_raw, y_raw, batch_size, ignore_stale_grad)

    def _legacy_step(self, x_raw, y_raw, batch_size, ignore_stale_grad):
        _telemetry.counter_add("fused.steps")
        _telemetry.counter_add("fused.fallbacks")
        _telemetry.counter_add("fused.fallback." + self.fallback_reason)
        from .. import autograd
        tr = self._trainer
        x_nd, y_nd = NDArray(x_raw), NDArray(y_raw)
        if tr._mesh is not None:
            x_nd, y_nd = tr.shard_batch(x_nd, y_nd)
        net = self._net if self._net is not None else None
        if net is None:
            raise ValueError(
                "fuse_step fallback needs a net to run the forward "
                "(construct the Trainer from net.collect_params() or pass "
                "net= to fuse_step)")
        with autograd.record():
            out = net(x_nd)
            l = self._loss(out, y_nd)
        l.backward()
        tr.step(batch_size, ignore_stale_grad=ignore_stale_grad)
        return l.mean() if l.ndim > 0 else l

    def _fused_step(self, x_raw, y_raw, batch_size):
        """One fused step, or None where the first call's collect found a
        reason to fall back (``fallback_reason`` then says which)."""
        tr, opt = self._trainer, self._opt
        # mirror Trainer.step's bookkeeping exactly: rescale from the
        # batch size, THEN advance num_update, THEN read the lr property
        # (the scheduler sees the post-increment count, ≙ update_multi)
        opt.rescale_grad = tr._scale / batch_size
        sig = (opt._fused_sig(),
               self._plan.fingerprint if self._plan is not None else None)
        build = self._compiled is None or sig != self._sig
        with _building() if build else _NOT_BUILDING:
            with _telemetry.span("train.prep"):
                if self._fn is None:
                    self._build_data(x_raw)
                    if self.fallback_reason is not None:
                        return None
                if self._compiled is None:
                    self._build_jit()
                elif build:
                    # rescale/clip/wd are python constants of the trace —
                    # a new batch size (or live optimizer mutation) means
                    # a new program; a changed PLAN fingerprint
                    # additionally re-lays the stored tensors before
                    # recompiling against the new shardings
                    _telemetry.counter_add("fused.rebuilds")
                    if self._plan is not None and sig[1] != self._sig[1]:
                        self._place_storage()
                    self._build_jit()
                if opt.num_update != self._t_host:
                    # legacy steps (or checkpoint resume) advanced the
                    # counter outside this executor — resync the device
                    # mirror
                    self._ctl = dict(self._ctl,
                                     t=jnp.asarray(opt.num_update, jnp.int32))
                opt.num_update += 1
                self._t_host = opt.num_update
                lr = float(opt.learning_rate)
                if lr != self._lr_host:
                    self._lr_host = lr
                    self._lr_dev = jnp.asarray(lr, jnp.float32)
                tr_vals = {n: self._params[n]._data._data
                           for n in self._tr_names}
                fr_vals = {n: self._params[n]._data._data
                           for n in self._fr_names}
                states = {n: tr._states[self._tname[n]]
                          for n in self._tr_names}
                if self._mesh is not None:
                    # batch_sharding resolves a nested data axis (dp_out,
                    # dp_in) to the tuple spec — the WorkersMerge hierarchy
                    # at the collective layer (ICI-first inner reduce,
                    # DCN-second outer)
                    bs = _batch_sharding(self._mesh, x_raw.ndim,
                                         self._batch_axis)
                    ys = _batch_sharding(self._mesh, y_raw.ndim,
                                         self._batch_axis)
                    x_raw = jax.device_put(x_raw, bs)
                    y_raw = jax.device_put(y_raw, ys)
            if self._coll_bytes:
                for ax, nbytes in self._coll_bytes.items():
                    _telemetry.counter_add(f"collective.{ax}.bytes", nbytes)
            _telemetry.counter_add("fused.steps")
            _telemetry.counter_add("fused.dispatches")
            with _telemetry.span("train.launch"), \
                    _telemetry.timed("fused.step_us"):
                lval, new_tr, new_fr, new_states, self._ctl = self._compiled(
                    tr_vals, fr_vals, states, self._ctl, self._lr_dev,
                    x_raw, y_raw)
        # write back: swap raw buffers inside the existing NDArray handles
        # (no transfer), push fresh optimizer state into trainer._states,
        # and CONSUME every trainable grad edge — a fused step counts as
        # backward+step, so a following legacy update() must see stale
        # grads (raise), never re-apply old ones
        with _telemetry.span("train.writeback"):
            for n in self._tr_names:
                d = self._params[n]._data
                d._data = new_tr[n]
                if d._grad_edge is not None:
                    d._grad_edge.grad = None
                tr._states[self._tname[n]] = new_states[n]
            for n in self._fr_names:
                self._params[n]._data._data = new_fr[n]
        return NDArray(lval)

    def sync(self):
        for n in self._tr_names or ():
            jax.block_until_ready(self._params[n]._data._data)
        _publish_aux(_drained(self))

    def hlo_text(self, x, y):
        """The compiled step program's HLO text for a batch like ``(x,
        y)``: every instruction with its ``op_name`` (the ``mx.fwd/<block
        path>`` scopes), which is how a reader of a device trace turns the
        ``XLA Ops`` line's instruction names into scopes.  Lowered from
        shapes, so no buffer is touched; the trace is the step's own (no
        retrace is counted), the compilation is served from the
        compilation cache where one is on."""
        if self._compiled is None:
            raise RuntimeError("hlo_text() needs a step that has run")

        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        raw = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
               for a in (x, y)]
        if self._mesh is not None:
            raw = [jax.device_put(a, _batch_sharding(
                self._mesh, a.ndim, self._batch_axis)) for a in raw]
        args = (
            {n: self._params[n]._data._data for n in self._tr_names},
            {n: self._params[n]._data._data for n in self._fr_names},
            {n: self._trainer._states[self._tname[n]]
             for n in self._tr_names},
            self._ctl, self._lr_dev, *raw)
        return self._compiled.lower(
            *jax.tree_util.tree_map(like, args)).compile().as_text()

    # ---------------------------------------------------------- checkpoint
    def export_ctl(self):
        """The live device ``{rng, t}`` control block (or None before the
        first fused step) — checkpointed alongside params/states so a
        resumed run continues the SAME rng stream and step counter."""
        if self._ctl is None:
            return None
        return {"rng": self._ctl["rng"], "t": self._ctl["t"]}

    def resync_ctl(self, rng=None):
        """Force the device ctl to the trainer's current ``num_update``
        (and optionally a restored rng key).  Called by
        ``Trainer.load_states`` / ``import_checkpoint_state`` — the lazy
        host-mirror comparison in ``_fused_step`` misses a restore that
        happens to land on the mirrored value, so a restore resyncs
        eagerly."""
        self._t_host = self._opt.num_update
        if self._ctl is None:
            return
        ctl = {"rng": jnp.asarray(rng) if rng is not None
               else self._ctl["rng"],
               "t": jnp.asarray(self._opt.num_update, jnp.int32)}
        if self._mesh is not None:
            rep = NamedSharding(self._mesh, PartitionSpec())
            ctl = jax.device_put(ctl, rep)
        self._ctl = ctl


def _publish_aux(step):
    """Aux state that a block marked for publication (``Parameter.publish``)
    becomes telemetry when the step is synced: the arrays are the newest
    step's outputs, so reading them waits for that step and no other, and a
    step in flight is never stalled for a counter.  ``("moe.load", held,
    "load_total")`` adds what came since the last sync to
    ``moe.tokens_routed`` (all experts) and ``moe.tokens_held`` (the experts
    held here); ``("moe.load", held, "load")`` sets the gauge
    ``moe.load_max_over_mean`` (thousandths; the fullest held expert of any
    layer over its layer's mean, last step)."""
    import numpy as onp
    seen = step.__dict__.setdefault("_published", {})
    worst = None
    for n in step._fr_names or ():
        mark = getattr(step._params[n], "publish", None)
        if not mark or mark[0] != "moe.load":
            continue
        (first, count), which = mark[1], mark[2]
        v = onp.asarray(step._params[n]._data._data).astype("int64")
        if which == "load_total":
            d = (v - seen.get(n, 0)) % (1 << 32)     # int32 wraps
            seen[n] = v
            _telemetry.counter_add("moe.tokens_routed", int(d.sum()))
            _telemetry.counter_add("moe.tokens_held",
                                   int(d[first:first + count].sum()))
        elif v[first:first + count].sum() > 0:
            held = v[first:first + count]
            worst = max(worst or 0.0, float(held.max() / held.mean()))
    if worst is not None:
        _telemetry.gauge_set("moe.load_max_over_mean", int(1000 * worst))


# ------------------------------------------------------- step-to-step gaps
# Below the step builders on purpose: what is traced above keeps its line
# numbers, and with them the compile cache's keys (PERF.md, PR 27).
import collections  # noqa: E402
import resource  # noqa: E402
import statistics  # noqa: E402
import threading  # noqa: E402

# A gap is a stall where it passes both 1.25 x the median of the step
# object's last 16 gaps and that median + 20 ms.
_STALL_RATIO = 1.25
_STALL_FLOOR_US = 20_000
_STALL_HISTORY = 16


class _StepGaps:
    """What a step object keeps from one ``__call__`` to the next: the
    wall clock at the last start (None before the first step and after a
    ``sync()``), the gaps before it and, while spans are recorded, the
    calling thread's CPU clock at the last start and the process's
    ``getrusage`` at the first step or the last stall."""

    __slots__ = ("wall", "cpu", "gaps", "rusage")

    def __init__(self, tracing):
        self.wall = self.cpu = None
        self.gaps = collections.deque(maxlen=_STALL_HISTORY)
        self.rusage = resource.getrusage(resource.RUSAGE_SELF) \
            if tracing else None


def _drained(step):
    """``step``, its last start forgotten: ``sync()`` has fetched all that
    was in flight, so the interval up to the next step holds a drain the
    caller asked for.  That step carries no ``gap_us`` and is no stall;
    the gaps before the drain stay the detector's history (the loop's
    period has not changed, and the step after the next refills the
    pipeline in no time at all)."""
    kept = step.__dict__.get("_gaps")
    if kept is not None:
        kept.wall = None
    return step


def _step_span(owner, step):
    """The ``train.step`` span of ``owner``'s step number ``step``.  From
    the second step after the object's first or a ``sync()``,
    ``fused.step_gap_us`` observes the time since the previous step
    *started* (under the telemetry switch: two clock reads, and the
    observability signals divide by it).  While spans are recorded the
    span carries that ``gap_us`` and ``cpu_us``, the calling thread's CPU
    time over the interval, and a gap that passes the two limits above
    also leaves a ``train.stall`` span over its excess, with what the host
    can say about the interval: a thread that was running for the excess
    was in Python, one that was not was blocked or descheduled."""
    tracing = _telemetry.trace_enabled()
    if not tracing and not _telemetry.enabled():
        owner._gaps = None          # switched on later, it starts afresh
        return _telemetry.span("train.step", step=step)
    wall = time.perf_counter_ns()
    cpu = time.thread_time_ns() if tracing else None
    kept = owner.__dict__.get("_gaps")
    if kept is None:
        kept = owner._gaps = _StepGaps(tracing)
    was_wall, was_cpu, kept.wall, kept.cpu = kept.wall, kept.cpu, wall, cpu
    if was_wall is None:            # the first step, or one after a sync()
        return _telemetry.span("train.step", step=step)
    gap_us = (wall - was_wall) // 1000
    _telemetry.observe("fused.step_gap_us", gap_us)
    if cpu is None or was_cpu is None:      # spans are off, or were
        kept.gaps.append(gap_us)
        return _telemetry.span("train.step", step=step)
    cpu_us = (cpu - was_cpu) // 1000
    median = statistics.median(kept.gaps) if kept.gaps else gap_us
    kept.gaps.append(gap_us)
    if gap_us > _STALL_RATIO * median and gap_us > median + _STALL_FLOOR_US:
        _record_stall(kept, step=step, gap_us=gap_us,
                      excess_us=int(gap_us - median), cpu_us=cpu_us)
    return _telemetry.span("train.step", step=step, gap_us=gap_us,
                           cpu_us=cpu_us)


def _record_stall(kept, gap_us, excess_us, **attrs):
    """One ``train.stall`` span back-dated over the excess of the gap that
    ends now; counter ``fused.stalls``.  From the ring, over the gap:
    ``waits`` and ``wait_us`` are this thread's ``nd.fetch`` spans,
    ``ready`` that there was none (whatever it fetched had landed: the
    device had finished and the host was late), ``gc_us`` the ``host.gc``
    spans of any thread.  The faults and context switches are the
    process's since the previous stall or the first step."""
    NAME, START, DUR, TID = 3, 4, 5, 6              # fields of a span record
    now_us = time.time_ns() // 1000
    since, me = now_us - gap_us, threading.get_ident()
    spans = [s for s in _telemetry.trace_spans() if s[START] >= since]
    waited = [s[DUR] for s in spans
              if s[NAME] == "nd.fetch" and s[TID] == me]
    after = resource.getrusage(resource.RUSAGE_SELF)
    before, kept.rusage = kept.rusage or after, after
    _telemetry.counter_add("fused.stalls")
    _telemetry.record_span(
        "train.stall", now_us - excess_us, excess_us, gap_us=gap_us,
        excess_us=excess_us, waits=len(waited), wait_us=sum(waited),
        ready=not waited,
        gc_us=sum(s[DUR] for s in spans if s[NAME] == "host.gc"),
        majflt=after.ru_majflt - before.ru_majflt,
        nivcsw=after.ru_nivcsw - before.ru_nivcsw,
        nvcsw=after.ru_nvcsw - before.ru_nvcsw, **attrs)


# --------------------------------------------------------------------- check
def _selfcheck(steps: int = 6, warmup: int = 2, verbose: bool = True) -> int:
    """``make fused-check`` gate: one compiled executable per (block,
    optimizer) identity, zero steady-state retraces, exactly one host
    dispatch per step, zero eager dispatch-cache traffic in the steady
    window — all read from the telemetry counters the fused path emits."""
    import numpy as onp
    from .. import telemetry, dispatch_cache
    from ..gluon import nn, Trainer
    from ..gluon.loss import SoftmaxCrossEntropyLoss

    rs = onp.random.RandomState(0)
    x = NDArray(jnp.asarray(rs.randn(8, 6), jnp.float32))
    y = NDArray(jnp.asarray(rs.randint(0, 4, (8,)), jnp.int32))
    loss_fn = SoftmaxCrossEntropyLoss()

    execs = []
    for opt_name, args in (("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
                           ("adam", {"learning_rate": 1e-3})):
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        net.hybridize()
        tr = Trainer(net.collect_params(), opt_name, args)
        execs.append(tr.fuse_step(loss_fn))

    for st in execs:
        for _ in range(warmup):
            st(x, y)
        st.sync()
    base = telemetry.summary()
    d0 = dispatch_cache.stats()
    for st in execs:
        for _ in range(steps):
            st(x, y)
        st.sync()
    cur = telemetry.summary()
    d1 = dispatch_cache.stats()

    def delta(name):
        return cur.get(name, 0) - base.get(name, 0)

    n_expected = len(execs) * steps
    eager = (d1["hits"] + d1["misses"]) - (d0["hits"] + d0["misses"])
    checks = [
        ("fused path active (no fallbacks)",
         all(st.fused for st in execs) and delta("fused.fallbacks") == 0),
        ("one executable per (block, optimizer) identity",
         cur.get("fused.programs", 0) == len(execs)),
        ("zero steady-state retraces", delta("fused.retraces") == 0),
        ("zero steady-state rebuilds", delta("fused.rebuilds") == 0),
        ("one host dispatch per step",
         delta("fused.dispatches") == n_expected
         and delta("fused.steps") == n_expected),
        ("zero eager dispatch-cache traffic in steady state", eager == 0),
    ]
    ok = True
    for name, passed in checks:
        ok = ok and passed
        if verbose:
            print(f"  [{'ok' if passed else 'FAIL'}] {name}")
    if verbose:
        print(f"fused-check: {'PASS' if ok else 'FAIL'} "
              f"({n_expected} steady steps, "
              f"programs={cur.get('fused.programs', 0)}, "
              f"retraces=+{delta('fused.retraces')}, "
              f"eager_dispatches=+{eager})")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    if "--check" in sys.argv:
        sys.exit(_selfcheck())
    print(__doc__)
