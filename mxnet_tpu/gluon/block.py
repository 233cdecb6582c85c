"""gluon.Block / HybridBlock — the module system (≙ gluon/block.py:204/1006).

TPU-native CachedOp equivalence: ``hybridize()`` makes the block trace its
``forward`` into ONE pure jax function of (rng, params, inputs) and jit it
(≙ deferred-compute trace → CachedOp, block.py:1131 _build_cache →
cached_op.cc:833 Forward). The compiled executable is cached per
(train-mode, input shapes/dtypes) — the reference's static_alloc/static_shape
fast path (cached_op.cc:680 StaticForward) is XLA's compiled-executable cache
here. Under autograd recording the whole cached call is taped as a single
node, so backward is one compiled XLA computation (≙ CachedOp::Backward
cached_op.cc:1089).

Mutable state (BatchNorm running stats) is captured at trace time as extra
aux outputs and written back after each call — the functional equivalent of
the reference's mutable aux NDArrays (FMutateInputs).
"""
from __future__ import annotations

import contextlib
import os
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as _onp

from .. import tape
from ..ndarray import NDArray, wrap
from ..numpy.random import new_key, push_trace_key, pop_trace_key
from .parameter import (Constant, DeferredInitializationError, Parameter,
                        ParameterDict, _trace_ctx)


@contextlib.contextmanager
def _pure_trace(sub: Dict[int, Any]):
    """Run a block's ``forward`` as a PURE function of the given parameter
    substitution (``id(param) -> raw tracer``): ``Parameter.data()`` returns
    the tracer, stat writes (BatchNorm running means) are captured as aux
    outputs instead of mutating eagerly.  This is the single trace primitive
    behind ``_build_cache``, ``pure_fn`` and the fused train step — all of
    them compose the same functionalization."""
    prev = (_trace_ctx.active, _trace_ctx.sub, _trace_ctx.aux_out,
            _trace_ctx.aux_params)
    _trace_ctx.active = True
    _trace_ctx.sub = sub
    _trace_ctx.aux_out = {}
    _trace_ctx.aux_params = []
    try:
        yield _trace_ctx
    finally:
        (_trace_ctx.active, _trace_ctx.sub, _trace_ctx.aux_out,
         _trace_ctx.aux_params) = prev


def _subjaxprs(params: Dict[str, Any]):
    """Every Jaxpr reachable from one equation's params — pjit bodies,
    scan/while carries, cond branches — duck-typed so it tracks JAX's
    internal layout (ClosedJaxpr has .jaxpr, Jaxpr has .eqns)."""
    def walk(v):
        if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from walk(x)
    for v in params.values():
        yield from walk(v)


def _jaxpr_matrix_flops(jaxpr) -> int:
    """2 × MACs of every dot_general / conv_general_dilated in a jaxpr
    (recursive) — the matrix-unit FLOPs count behind HybridBlock.flops().
    """
    def prod(xs):
        out = 1
        for x in xs:
            out *= int(x)
        return out

    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            # out elements each cost K MACs; K = prod of lhs contracted dims
            (lc, _rc), _b = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * prod(lhs[d] for d in lc) * \
                prod(eqn.outvars[0].aval.shape)
        elif name == "conv_general_dilated":
            # MACs per output element = kernel spatial × in-ch/group =
            # prod(rhs.shape) / out_channels
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            out_ch = max(int(rhs[dn.rhs_spec[0]]), 1)
            total += 2 * (prod(rhs) // out_ch) * \
                prod(eqn.outvars[0].aval.shape)
        for sub in _subjaxprs(eqn.params):
            total += _jaxpr_matrix_flops(sub)
    return total


def _bulk_exec_enabled() -> bool:
    """≙ MXNET_EXEC_BULK_EXEC_TRAIN / _INFERENCE (graph_executor.cc
    bulking): 0 disables the fused/compiled path for that mode.  Read per
    call so tests (and debug sessions) can toggle at runtime."""
    var = ("MXNET_EXEC_BULK_EXEC_TRAIN" if tape.is_training()
           else "MXNET_EXEC_BULK_EXEC_INFERENCE")
    return os.environ.get(var, "1") not in ("0", "false", "False")


__all__ = ["Block", "HybridBlock", "SymbolBlock", "Sequential",
           "HybridSequential"]


class _CacheEntry:
    __slots__ = ("jitted", "jit_fwd_vjp", "n_out", "multi", "aux_params",
                 "plist", "params", "fn")

    def __init__(self):
        self.fn = None              # pure traced closure (export_fn)
        self.jitted = None          # fwd only (inference path)
        self.jit_fwd_vjp = None     # fwd + linearization (training path)
        self.n_out = 1
        self.multi = False
        self.aux_params: List[Parameter] = []
        self.plist: List[Tuple[str, Parameter]] = []
        self.params: List[Parameter] = []   # values of plist, precomputed


class Block:
    """Base building block ≙ gluon.Block (block.py:204)."""

    def __init__(self, prefix=None, params=None):
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- attribute registration -------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", OrderedDict())[name] = value
            # the name this child's ops carry in a device trace
            # (jax.named_scope in __call__, only while a program is traced)
            value.__dict__["_scope_name"] = name.replace("/", "_")
        elif isinstance(value, Parameter):
            self.__dict__.setdefault("_reg_params", OrderedDict())[name] = value
        super().__setattr__(name, value)

    # -- parameters --------------------------------------------------------
    def collect_params(self, select=None) -> ParameterDict:
        out = ParameterDict()
        self._collect_params(out, "")
        if select is not None:
            import re
            pat = re.compile(select)
            out = ParameterDict((k, v) for k, v in out.items() if pat.match(k))
        # backref lets consumers (Trainer.fuse_step) recover the owning
        # block from the ParameterDict they were constructed with
        out._block_ref = weakref.ref(self)
        return out

    def _collect_params(self, out, prefix):
        for name, p in self._reg_params.items():
            out[prefix + name] = p
        for cname, child in self._children.items():
            child._collect_params(out, f"{prefix}{cname}.")

    @property
    def params(self) -> ParameterDict:
        return ParameterDict(self._reg_params)

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for child in self._children.values():
            child._clear_cache()

    def zero_grad(self):
        self.collect_params().zero_grad()

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    # -- persistence -------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """≙ Block.save_parameters (block.py:1506 area); .npz container
        (reference uses its legacy binary / cnpy .npz — §5.4)."""
        self.collect_params().save(filename)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        self.collect_params().load(filename, ctx=ctx,
                                   allow_missing=allow_missing,
                                   ignore_extra=ignore_extra)

    # -- execution ---------------------------------------------------------
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def __call__(self, *args, **kwargs):
        for h in self._forward_pre_hooks:
            h(self, args)
        if _trace_ctx.active:
            with self._named_scope():
                out = self.forward(*args, **kwargs)
        else:
            out = self.forward(*args, **kwargs)
        for h in self._forward_hooks:
            h(self, args, out)
        return out

    def _named_scope(self):
        """``jax.named_scope`` under this block's attribute name in its
        parent (the class name for a root): nested calls give the ops of a
        traced program the path ``features/4/0/body/3`` — the parameter
        path with ``/`` for ``.`` (docs/tracing.md)."""
        return jax.named_scope(
            self.__dict__.get("_scope_name") or type(self).__name__)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def _clear_cache(self):
        for child in self._children.values():
            child._clear_cache()

    # -- introspection -----------------------------------------------------
    def summary(self, *inputs):
        lines = [f"{self.__class__.__name__}:"]
        for k, p in self.collect_params().items():
            lines.append(f"  {k:<40} {str(p.shape):<20} {p.dtype}")
        return "\n".join(lines)

    def __repr__(self):
        s = self.__class__.__name__ + "("
        for name, child in self._children.items():
            s += f"\n  ({name}): {child.__class__.__name__}"
        return s + ("\n)" if self._children else ")")

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self


class HybridBlock(Block):
    """≙ gluon.HybridBlock (block.py:1006): hybridize → trace → compile."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cache: Dict[Any, _CacheEntry] = {}

    def hybridize(self, active=True, static_alloc=True, static_shape=True,
                  **kwargs):
        self._active = active
        self._cache.clear()
        super().hybridize(active, **kwargs)

    def _clear_cache(self):
        self._cache.clear()
        super()._clear_cache()

    def optimize_for(self, x, backend=None, clear=True, **kwargs):
        """≙ HybridBlock.optimize_for (block.py:1308): apply the named
        subgraph backend (mx.subgraph registry — XLA identity default,
        INT8 quantization, user-registered passes), then hybridize and
        warm the compile cache."""
        if backend is not None:
            from ..subgraph import apply_backend
            apply_backend(self, backend, **kwargs)
        self.hybridize(True)
        self(x)

    def export(self, path, epoch=0, remove_amp_cast=True,
               input_shape=None):
        """≙ HybridBlock.export → -symbol.json + -NNNN.params (block.py:1506).

        With `input_shape` (or after a forward pass that cached one), the
        structural tracer (gluon2sym.py ≙ deferred-compute trace) emits a
        REAL Symbol graph reloadable via mx.symbol.load / SymbolBlock and
        exportable to ONNX; untraceable custom-forward blocks fall back to
        the params-only structure JSON.
        """
        import json
        params_file = f"{path}-{epoch:04d}.params"
        # a captured signature (real dtypes, multi-input) beats a bare
        # float32 input_shape; the latter covers the never-called case
        sig = getattr(self, "_last_input_sig", None)
        if sig is None and input_shape is not None:
            sig = [(tuple(input_shape), "float32")]
        shape = sig[0][0] if sig else None
        sym = params = None
        if shape is not None:
            from .gluon2sym import trace_symbol, TraceError
            try:
                # fast path: structural registry (legacy CamelCase graphs)
                sym, params = trace_symbol(self, shape)
            except TraceError:
                pass
            if sym is None:
                # generic deferred-compute trace (any forward body);
                # ANY failure here falls back to the params-only export
                from . import deferred
                import jax.numpy as _jnp
                from ..ndarray import NDArray as _ND
                try:
                    examples = [_ND(_jnp.zeros(s, _jnp.dtype(dt)))
                                for s, dt in sig]
                    sym, params = deferred.trace(self, *examples)
                except Exception:
                    sym = None
        if sym is not None:
            sym.save(f"{path}-symbol.json")
            import numpy as _onp
            with open(params_file, "wb") as f:
                _onp.savez(f, **{k: v.asnumpy()
                                 for k, v in params.items()})
            return f"{path}-symbol.json", params_file
        self.save_parameters(params_file)
        symj = {"framework": "mxnet_tpu", "class": self.__class__.__name__,
                "params": {k: list(p.shape) for k, p in self.collect_params().items()}}
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(symj, f)
        return f"{path}-symbol.json", params_file

    def __call__(self, *args, **kwargs):
        if not kwargs and args and all(isinstance(a, NDArray) for a in args):
            # remember the input signature so export() can synthesize
            # example inputs for the deferred-compute trace
            self._last_input_sig = [(a.shape, str(a.dtype)) for a in args]
        if self._active and not kwargs and args and all(
                isinstance(a, NDArray) for a in args):
            if _trace_ctx.active:
                with self._named_scope():          # nested: outer jit covers us
                    return self.forward(*args)
            if not _bulk_exec_enabled():
                # MXNET_EXEC_BULK_EXEC_{TRAIN,INFERENCE}=0 disables op
                # batching in the reference's graph executor; the jit
                # cache IS this build's bulk execution — honoring the
                # flag runs imperatively op-by-op (debug parity)
                return self.forward(*args)
            return self._call_cached(*args)
        return super().__call__(*args, **kwargs)

    # ------------------------------------------------------------- caching
    def _call_cached(self, *args):
        key = (tape.is_training(),
               tuple((a.shape, str(a.dtype)) for a in args))
        entry = self._cache.get(key)
        if entry is None:
            # cache miss only: walk the module tree.  The steady-state hit
            # path must not rebuild the ParameterDict — for a ResNet-50
            # that walk is ~160 dict inserts of pure host glue per dispatch.
            plist = [(k, p) for k, p in self.collect_params().items()]
            if any(not p.is_initialized for _, p in plist):
                # first call performs deferred shape inference imperatively,
                # exactly like the reference's first _build_cache call
                return self.forward(*args)
            entry = self._build_cache(key, plist)
        params = entry.params
        raw_params = [p.data()._data for p in params]
        rng = new_key()

        if tape.is_recording():
            # Compiled forward that ALSO returns the linearized vjp closure
            # (a jax Partial pytree) — forward and backward are each one
            # cached XLA executable; no per-step retracing.
            arrays = [p.data() for p in params] + list(args)
            raw = raw_params + [a._data for a in args]
            raw_out, vjp_fn = entry.jit_fwd_vjp(rng, *raw)
            node = tape.TapeNode(vjp_fn, arrays, len(raw_out),
                                 [(o.shape, o.dtype) for o in raw_out],
                                 multi=True)
            res = tuple(NDArray(o) for o in raw_out)
            for i, w in enumerate(res):
                w._node = (node, i)
        else:
            raw_out = entry.jitted(rng, raw_params, *[a._data for a in args])
            res = tuple(NDArray(o) for o in raw_out)
        # entry.n_out/multi are populated by the trace, which runs lazily
        # inside the jit call above — only read them after it returns
        n_out = entry.n_out
        outs, auxs = res[:n_out], res[n_out:]
        for p, a in zip(entry.aux_params, auxs):
            p.set_data(a)
        if n_out == 1 and not entry.multi:
            return outs[0]
        return tuple(outs)

    def export_fn(self, *example_args):
        """Return ``(fn, raw_params)`` where ``fn(rng, raw_params,
        *raw_inputs) -> tuple(raw_outputs…)`` is this block's pure traced
        forward over jax arrays — composable with jax transforms.

        This is the TPU-idiomatic export path (≙ the reference's
        ``HybridBlock.export`` symbol-file story, block.py:1308): instead
        of a serialized graph, you get a function you can ``jax.jit``,
        ``vmap``, ``lax.scan`` or shard yourself, e.g. a serving loop
        that amortizes one host dispatch over many device batches::

            fn, raw = net.export_fn(example_batch)
            step = jax.jit(lambda xs: jax.lax.map(
                lambda x: fn(rng, raw, x)[0], xs))

        ``rng`` is a jax PRNG key (only consumed by stochastic layers —
        pass any fixed key for inference).  Outputs follow the cache
        entry's layout: ``n_out`` real outputs, then mutated aux state
        (BatchNorm running stats) — inference discards the tail.  The
        trace snapshot honors the CURRENT training mode
        (``tape.set_training``).
        """
        if not self._active:
            raise ValueError("export_fn requires hybridize() first")
        key = (tape.is_training(),
               tuple((a.shape, str(a.dtype)) for a in example_args))
        plist = [(k, p) for k, p in self.collect_params().items()]
        if self._cache.get(key) is None and (
                not plist or any(not p.is_initialized for _, p in plist)):
            # one forward only when needed: deferred shape inference
            # materializes parameters before the trace
            out = self(*example_args)
            del out
            plist = [(k, p) for k, p in self.collect_params().items()]
        entry = self._cache.get(key) or self._build_cache(key, plist)
        raw_params = [p.data()._data for _, p in entry.plist]
        return entry.fn, raw_params

    def _build_cache(self, key, plist) -> _CacheEntry:
        entry = _CacheEntry()
        entry.plist = plist
        params = [p for _, p in plist]
        entry.params = params
        self_ref = self

        def fn(rng, pvals, *inputs):
            push_trace_key(rng)
            try:
                with _pure_trace({id(p): v
                                  for p, v in zip(params, pvals)}) as ctx:
                    out = self_ref.forward(*[NDArray(x) for x in inputs])
                    multi = isinstance(out, (tuple, list))
                    outs = tuple(out) if multi else (out,)
                    entry.n_out = len(outs)
                    entry.multi = multi
                    entry.aux_params = list(ctx.aux_params)
                    aux_raw = tuple(ctx.aux_out[id(p)]
                                    for p in ctx.aux_params)
            finally:
                pop_trace_key()
            return tuple(o._data for o in outs) + aux_raw

        entry.fn = fn            # pure closure, reusable under jax
        entry.jitted = jax.jit(fn)
        n_params = len(params)

        def fwd_vjp(rng, *arrs):
            return jax.vjp(
                lambda *a: fn(rng, list(a[:n_params]), *a[n_params:]), *arrs)

        entry.jit_fwd_vjp = jax.jit(fwd_vjp)
        self._cache[key] = entry
        return entry

    def pure_fn(self, *example_args, train=True):
        """Return ``(fn, params)`` — the block's forward as a NAMED pure
        function, composable into larger jitted programs (the fused train
        step builds loss+vjp+optimizer around it).

        ``params`` is a ``{name: Parameter}`` dict (collect_params order);
        ``fn(rng, pvals, *raw_inputs) -> (outs, aux)`` takes ``pvals`` as a
        ``{name: raw jax array}`` dict and returns the tuple of raw outputs
        plus a ``{name: raw}`` dict of mutated aux state (BatchNorm running
        stats) — empty when the block has none.  Unlike ``export_fn`` the
        parameter pytree is keyed by name, so callers can thread the same
        dict through optimizer updates and donation without positional
        bookkeeping.

        ``train=False`` returns the INFERENCE variant: the trace runs with
        training mode forced off (BatchNorm normalizes by running stats,
        dropout is identity), the aux-writeback closure is skipped
        entirely, and ``fn(rng, pvals, *raw_inputs)`` returns just the
        tuple of raw outputs — the minimal program the serving engine
        (mxnet_tpu.serve) compiles per bucket, with no grad-tape
        interaction and no mutated-state tail to discard.

        Deferred-shape parameters are materialized by one eager forward
        over ``example_args`` when given; otherwise uninitialized params
        raise.
        """
        params = dict(self.collect_params().items())
        if any(not p.is_initialized for p in params.values()):
            if not example_args:
                raise DeferredInitializationError(
                    "pure_fn on a deferred-init block needs example inputs "
                    "(or run one forward first)")
            out = self.forward(*example_args)
            del out
            params = dict(self.collect_params().items())
        name_of = {id(p): n for n, p in params.items()}
        self_ref = self

        if not train:
            def infer_fn(rng, pvals, *inputs):
                push_trace_key(rng)
                prev_train = tape.set_training(False)
                try:
                    with _pure_trace({id(p): pvals[n]
                                      for n, p in params.items()}):
                        out = self_ref.forward(*[NDArray(x) for x in inputs])
                        multi = isinstance(out, (tuple, list))
                        outs = tuple(out) if multi else (out,)
                finally:
                    tape.set_training(prev_train)
                    pop_trace_key()
                return tuple(o._data for o in outs)

            return infer_fn, params

        def fn(rng, pvals, *inputs):
            push_trace_key(rng)
            try:
                with _pure_trace({id(p): pvals[n]
                                  for n, p in params.items()}) as ctx:
                    out = self_ref.forward(*[NDArray(x) for x in inputs])
                    multi = isinstance(out, (tuple, list))
                    outs = tuple(out) if multi else (out,)
                    aux = {name_of[id(p)]: ctx.aux_out[id(p)]
                           for p in ctx.aux_params}
            finally:
                pop_trace_key()
            return tuple(o._data for o in outs), aux

        return fn, params

    def flops(self, *example_args) -> int:
        """Analytic forward-pass FLOPs for one batch of the given
        signature — the model half of the MFU signal
        (docs/observability.md).

        The block's pure inference function is traced ABSTRACTLY
        (``jax.make_jaxpr`` — no compute, no device memory) and the
        matrix primitives are priced at 2 × MACs: ``dot_general``
        (Dense, attention, any einsum) and ``conv_general_dilated``
        (every Conv*D, including the fused conv+bn+relu block op),
        recursing into pjit/scan/cond sub-jaxprs.  Elementwise,
        normalization and pooling work is deliberately NOT counted:
        MFU convention prices the matrix units the peak-FLOPs rig
        constant describes, and counting vector work against a matrix
        peak would overstate utilization.

        ``example_args`` are NDArrays (or anything with
        ``.shape``/``.dtype``); with none, the signature captured by
        the last ``__call__`` is reused.  Parameters must be
        initialized (run one forward, or pass example NDArrays so the
        deferred init can resolve)."""
        if example_args:
            sig = [(tuple(a.shape), str(a.dtype)) for a in example_args]
        else:
            sig = getattr(self, "_last_input_sig", None)
            if not sig:
                raise ValueError("flops() needs example inputs "
                                 "(or run one forward first)")
        nd_args = tuple(a for a in example_args if isinstance(a, NDArray))
        fn, params = self.pure_fn(*nd_args, train=False)
        pvals = {n: p.data()._data for n, p in params.items()}
        structs = [jax.ShapeDtypeStruct(tuple(s), _onp.dtype(d))
                   for s, d in sig]
        closed = jax.make_jaxpr(fn)(
            jax.random.PRNGKey(0), pvals, *structs)
        return _jaxpr_matrix_flops(closed.jaxpr)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    # reference-compat alias: subclasses may implement hybrid_forward(F, x, ...)
    # 2.0 removed F; we accept forward only.


class SymbolBlock(HybridBlock):
    """Reload an exported model ≙ gluon.SymbolBlock (block.py:~1840).

    For a real graph JSON (nodes/arg_nodes — emitted by the structural or
    generic deferred-compute tracer) the block RE-EXECUTES the graph: the
    loaded Symbol lowers to one jitted XLA computation and forward() feeds
    (inputs + loaded params) in argument order. Legacy params-only JSON
    still imports as a parameter container."""

    def __init__(self, params: ParameterDict, sym=None, input_names=None):
        super().__init__()
        self._sym = sym
        self._input_names = list(input_names or ["data"])
        self._sym_fn = None
        self._arg_order = None
        for k, p in params.items():
            self._reg_params[k.replace(".", "_")] = p

    def forward(self, *args):
        if self._sym is None:
            raise NotImplementedError(
                "this SymbolBlock wraps a params-only export (no graph); "
                "re-instantiate the original class to run it")
        if self._sym_fn is None:
            self._arg_order = self._sym.list_arguments()
            self._sym_fn = self._sym.as_function()
        feeds = dict(zip(self._input_names, args))
        vals = []
        for name in self._arg_order:
            if name in feeds:
                v = feeds[name]
                vals.append(v if isinstance(v, NDArray) else
                            NDArray(_jnp_asarray(v)))
            else:
                pname = name.replace(".", "_")
                if pname not in self._reg_params:
                    raise KeyError(
                        f"graph argument {name} not among inputs or params")
                vals.append(self._reg_params[pname].data())
        return self._sym_fn(*vals)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        import json
        with open(symbol_file) as f:
            text = f.read()
        graph = json.loads(text)
        sym = None
        if isinstance(graph, dict) and "nodes" in graph:
            from .. import symbol as S
            sym = S.load_json(text)
        pd = ParameterDict()
        if param_file:
            import jax.numpy as jnp
            with _onp.load(param_file, allow_pickle=False) as z:
                for k in z.files:
                    p = Parameter(k, shape=z[k].shape, dtype=str(z[k].dtype))
                    p.set_data(NDArray(jnp.asarray(z[k])))
                    pd[k] = p
        if input_names is None:
            input_names = ["data"]
        elif isinstance(input_names, str):
            input_names = [input_names]
        return SymbolBlock(pd, sym=sym, input_names=input_names)


def _jnp_asarray(v):
    import jax.numpy as jnp
    return jnp.asarray(v)


class Sequential(Block):
    """≙ gluon.nn.Sequential."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._layers: List[Block] = []

    def add(self, *blocks):
        for b in blocks:
            idx = len(self._layers)
            self._layers.append(b)
            setattr(self, str(idx), b)
        return self

    def forward(self, x, *args):
        for b in self._layers:
            x = b(x)
        return x

    def __len__(self):
        return len(self._layers)

    def __getitem__(self, i):
        if isinstance(i, slice):
            out = self.__class__()
            out.add(*self._layers[i])
            return out
        return self._layers[i]

    def __iter__(self):
        return iter(self._layers)


class HybridSequential(HybridBlock):
    """≙ gluon.nn.HybridSequential."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._layers: List[Block] = []

    def add(self, *blocks):
        for b in blocks:
            idx = len(self._layers)
            self._layers.append(b)
            setattr(self, str(idx), b)
        return self

    def forward(self, x, *args):
        for b in self._layers:
            x = b(x)
        return x

    def __len__(self):
        return len(self._layers)

    def __getitem__(self, i):
        if isinstance(i, slice):
            out = self.__class__()
            out.add(*self._layers[i])
            return out
        return self._layers[i]

    def __iter__(self):
        return iter(self._layers)


def recompute(fn, *args):
    """``fn(*args)`` (NDArrays in, one NDArray out) so that, while a
    *training* program is traced, nothing ``fn`` computes is kept for the
    backward pass: it runs under ``jax.checkpoint`` and is recomputed from
    ``args`` and the parameters when its gradient is taken.  A block whose
    activations would not fit otherwise calls this around its own body (a
    layer of ``models/nemotron_h.py``); the step builders need no option.
    Aux state written inside (``Parameter.set_data``) leaves through the
    checkpoint as an output and is registered outside, so it reaches the
    step's write-back like BatchNorm's statistics.  Outside a traced
    training program (eager, inference) it is a plain call."""
    if not (_trace_ctx.active and tape.is_training()):
        return fn(*args)
    ctx, written = _trace_ctx, []

    def pure(*raw):
        outer = (ctx.aux_out, ctx.aux_params)
        ctx.aux_out, ctx.aux_params = dict(outer[0]), []
        try:
            out = fn(*[NDArray(r) for r in raw])
            written[:] = [p for p in ctx.aux_params
                          if ctx.aux_out[id(p)] is not outer[0].get(id(p))]
            aux = tuple(ctx.aux_out[id(p)] for p in written)
        finally:
            ctx.aux_out, ctx.aux_params = outer
        return out._data, aux

    out, aux = jax.checkpoint(pure)(*[a._data for a in args])
    for p, v in zip(written, aux):
        p.set_data(NDArray(v))
    return NDArray(out)


def materialize(x, site):
    """``x`` behind an identity ``lax.optimization_barrier`` while a
    *training* program is traced, so that XLA stores it once instead of
    fusing the elementwise code that produced it into every product that
    reads it (a producer fused into a product's operand is evaluated again
    for each output tile; its transposition puts the same barrier on the
    cotangent).  A recomputed block says so in its own ``forward`` for a
    value that stands between two products (``models/olmo_hybrid.py``) and
    names the ``site``: ``dispatch.materialized.<site>`` counts once a
    traced call.  Identity in value and in gradient; outside a traced
    training program (eager, inference, serving) ``x`` itself comes back
    and nothing is counted."""
    if not (_trace_ctx.active and tape.is_training()):
        return x
    from .. import telemetry
    telemetry.counter_add("dispatch.materialized." + site)
    return NDArray(jax.lax.optimization_barrier(x._data))
