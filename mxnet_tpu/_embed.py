"""Embedded-interpreter shim for the C ABI real-runtime backend.

src/py_runtime.cc embeds CPython, imports THIS module once, and routes the
MXTNDArray*/MXTImperativeInvoke/MXTAutograd* C entry points through these
functions — so a C/C++ caller runs the SAME jnp/XLA ops and autograd tape
as Python code (≙ the reference's c_api.cc forwarding into the one true
runtime, include/mxnet/c_api.h; the C tier is a binding, not a parallel
implementation).  Everything here takes/returns plain NDArrays and numpy
buffers; no handle bookkeeping (the C side owns PyObject refs).
"""
from __future__ import annotations

import os

import numpy as onp

# Multi-worker C++ jobs: jax.distributed.initialize must run BEFORE any
# call that initialises the XLA backend (which importing the framework
# below will do).  Same DMLC_* resolution as parallel/dist.initialize —
# the launcher contract is identical for python and C++ workers.
_nw = int(os.environ.get("DMLC_NUM_WORKER", "1") or 1)
if _nw > 1 and os.environ.get("DMLC_ROLE", "worker") == "worker":
    import jax
    _uri = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    _port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
    jax.distributed.initialize(
        coordinator_address=f"{_uri}:{_port}", num_processes=_nw,
        process_id=int(os.environ.get("DMLC_WORKER_ID", "0")))

import mxnet_tpu as mx
from mxnet_tpu import autograd, tape
from mxnet_tpu.ndarray import NDArray

__all__ = [
    "zeros", "from_numpy", "to_numpy", "shape_of", "uniform", "invoke",
    "set_recording", "is_recording", "mark_variables", "backward",
    "grad_of", "detach", "sgd_mom_update", "backend_name", "sym_load",
    "sym_invoke", "sym_n_outputs",
]


def zeros(shape):
    return mx.np.zeros(tuple(int(s) for s in shape))


def from_numpy(a):
    return mx.np.array(onp.asarray(a, onp.float32))


def to_numpy(x):
    return onp.ascontiguousarray(x.asnumpy(), onp.float32)


def shape_of(x):
    return [int(s) for s in x.shape]


def uniform(shape, lo, hi, seed):
    shp = tuple(int(s) for s in shape)
    if int(seed) == 0:
        # seed 0 = "use the framework RNG": draws advance the global
        # stream that MXTRandomSeed/mx.seed controls (≙ MXRandomSeed
        # seeding the RNG every unseeded op consumes)
        return mx.np.random.uniform(lo, hi, size=shp).astype("float32")
    rs = onp.random.RandomState(int(seed) & 0x7FFFFFFF)
    return mx.np.array(rs.uniform(lo, hi, shp).astype(onp.float32))


def from_flat(data, shape):
    """data: memoryview over the caller's float32 buffer (zero-copy until
    the explicit .copy() — the C buffer may not outlive this call)."""
    arr = onp.frombuffer(data, onp.float32).reshape(
        [int(s) for s in shape]).copy()
    return mx.np.array(arr)


def refill(x, data):
    """Swap x's buffer for new host data, preserving shape (the C
    SyncCopyFromCPU contract)."""
    arr = onp.frombuffer(data, onp.float32).reshape(x.shape).copy()
    x._data = mx.np.array(arr)._data


def fill_uniform(x, lo, hi, seed):
    x._data = uniform(x.shape, lo, hi, seed)._data


# Same op vocabulary as the host tier's registry (src/ndarray.cc) so
# cpp-package code is backend-agnostic; each lowers to the jnp/XLA op.
_OPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "matmul": lambda a, b: mx.np.matmul(a, b),
    "sigmoid": lambda a: mx.np.reciprocal(1.0 + mx.np.exp(-a)),
    "tanh": lambda a: mx.np.tanh(a),
    "relu": lambda a: mx.np.maximum(a, 0.0),
    "square": lambda a: mx.np.square(a),
    "exp": lambda a: mx.np.exp(a),
    "log": lambda a: mx.np.log(a),
    "negative": lambda a: -a,
    "mean": lambda a: a.mean(),
    "sum": lambda a: a.sum(),
}


def invoke(name, inputs, scalar=None):
    if name == "mul_scalar":
        return [inputs[0] * float(scalar)]
    fn = _OPS.get(name)
    if fn is None:
        # whole-frontend fallback ≙ the reference's MXImperativeInvoke
        # resolving ANY registered op by name (c_api_ndarray.cc): C
        # callers get the full mx.np / mx.npx / mx.nd vocabulary, not
        # just the curated registry above
        import mxnet_tpu.nd as _nd
        for ns in (mx.np, mx.npx, _nd):
            fn = getattr(ns, name, None)
            if callable(fn):
                break
        if fn is None:
            raise KeyError(f"unknown op {name!r}")
    if scalar is not None and _accepts_extra_positional(fn, len(inputs)):
        out = fn(*inputs, scalar)
    else:
        out = fn(*inputs)
    return list(out) if isinstance(out, (tuple, list)) else [out]


def _accepts_extra_positional(fn, n_fixed):
    """Whether fn can take one positional beyond n_fixed — decided by
    SIGNATURE, never by catching TypeError from the executed call (an op
    whose own validation raises TypeError must surface that error, not
    silently re-run without the scalar)."""
    import inspect
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):
        return True          # C-implemented / unsignatured: let it try
    n_positional = 0
    for p in params:
        if p.kind == inspect.Parameter.VAR_POSITIONAL:
            return True
        if p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                      inspect.Parameter.POSITIONAL_OR_KEYWORD):
            n_positional += 1
    return n_positional > n_fixed


def set_recording(flag):
    return bool(tape.set_recording(bool(flag)))


def is_recording():
    return bool(tape.is_recording())


def mark_variables(xs):
    autograd.mark_variables(list(xs))


def backward(loss):
    loss.backward()


def grad_of(x):
    g = x.grad
    if g is None:
        raise RuntimeError("no gradient: did you mark the variable and "
                           "run backward under recording?")
    return onp.ascontiguousarray(g.asnumpy(), onp.float32)


def detach(x):
    return x.detach()


def sgd_mom_update(w, mom, lr, momentum, wd):
    """In-place fused SGD-momentum step on the REAL buffers (identical
    semantics to the host tier's MXTSGDMomUpdate, ≙ sgd_mom_update
    optimizer_op.cc:352: mom = momentum*mom − lr*(grad + wd*w);
    w += mom)."""
    g = w.grad
    if g is None:
        raise RuntimeError("sgd_mom_update: variable has no gradient")
    new_mom = momentum * mom._data - lr * (g._data + wd * w._data)
    w._data = w._data + new_mom
    mom._data = new_mom
    if w._grad_edge is not None:
        w._grad_edge.grad = None


def backend_name():
    import jax
    return f"python-xla:{jax.devices()[0].platform}"


# ------------------------------------------------- symbol / CachedOp tier
def sym_load(symbol_file, param_file):
    """Load a python-exported model (symbol json + params) as a callable
    block — the CachedOp the C side invokes (≙ MXSymbolCreateFromFile +
    MXCreateCachedOp, c_api.cc)."""
    from mxnet_tpu.gluon.block import SymbolBlock
    net = SymbolBlock.imports(symbol_file, param_file=param_file or None)
    net.hybridize()
    return net


def sym_invoke(net, inputs):
    prev = tape.set_training(False)
    try:
        out = net(*inputs)
    finally:
        tape.set_training(prev)
    return list(out) if isinstance(out, (tuple, list)) else [out]


def sym_n_outputs(net, inputs):
    return len(sym_invoke(net, inputs))


# ------------------------------------------------- KVStore (C ABI face)
# ≙ the reference's MXKVStoreCreate/Init/Push/Pull C API family
# (include/mxnet/c_api.h KVStore section) — routed into the one true
# python kvstore so C++ trainers share semantics with python trainers.
def kv_create(type_name):
    import os as _os

    from mxnet_tpu import kvstore as kvs
    if "dist" in type_name and _os.environ.get("DMLC_NUM_WORKER"):
        from mxnet_tpu.parallel import dist as _dist
        _dist.initialize()
    return kvs.create(type_name)


def kv_init(kv, key, val):
    kv.init(str(key), val)


def kv_push(kv, key, val, priority):
    kv.push(str(key), val, priority=int(priority))


def kv_pull(kv, key):
    out = mx.np.zeros((1,))      # pull rebinds out._data to the value
    kv.pull(str(key), out=out)
    return out


def kv_pushpull(kv, key, val):
    out = mx.np.zeros(val.shape)
    kv.pushpull(str(key), val, out=out)
    return out


def kv_set_optimizer(kv, name, lr, momentum, wd):
    from mxnet_tpu import optimizer as opt_mod
    kw = {"learning_rate": float(lr), "wd": float(wd)}
    if name in ("sgd", "nag", "signum"):
        kw["momentum"] = float(momentum)
    kv.set_optimizer(opt_mod.create(name, **kw))


def kv_rank(kv):
    return [int(kv.rank), int(kv.num_workers)]


def kv_type(kv):
    return getattr(kv, "type", "local")


# ------------------------------------------------ profiler (C ABI face)
# ≙ MXSetProfilerConfig/MXSetProfilerState/MXDumpProfile
def profiler_set_config(filename):
    from mxnet_tpu import profiler
    profiler.set_config(filename=filename)


def profiler_set_state(state):
    from mxnet_tpu import profiler
    (profiler.start if int(state) else profiler.stop)()


def profiler_dump():
    from mxnet_tpu import profiler
    profiler.dump()


__all__ += ["kv_create", "kv_init", "kv_push", "kv_pull", "kv_pushpull",
            "kv_set_optimizer", "kv_rank", "kv_type",
            "profiler_set_config", "profiler_set_state", "profiler_dump"]


# ------------------------------------------------- DataIter (C ABI face)
# ≙ MXDataIterCreateIter/MXDataIterNext/MXDataIterBeforeFirst
# (include/mxnet/c_api.h DataIter section): C++ drives the SAME python
# input pipeline (ImageRecordIter decode threads, NDArrayIter, CSVIter).
def io_create(kind, kwargs_json):
    import json as _json

    from mxnet_tpu import io as mio
    kwargs = _json.loads(kwargs_json) if kwargs_json else {}
    ctor = getattr(mio, kind, None)
    if ctor is None:
        raise KeyError(f"unknown data iterator {kind!r}")
    if kind == "ImageRecordIter" and "data_shape" in kwargs:
        kwargs["data_shape"] = tuple(kwargs["data_shape"])
    return iter(ctor(**kwargs))


def io_next(it):
    """→ [data, label, pad] or None at epoch end."""
    try:
        batch = next(it)
    except StopIteration:
        return None
    data = batch.data[0]
    label = batch.label[0] if batch.label else mx.np.zeros((1,))
    return [data, label, int(getattr(batch, "pad", 0) or 0)]


def io_reset(it):
    # DataIters are self-iterable (reset() + __next__); plain generators
    # can't rewind
    if hasattr(it, "reset"):
        it.reset()
        return True
    return False


def io_free(it):
    """Terminal teardown for a C-ABI iterator handle: synchronously stop
    every thread it owns BEFORE the handle is released.

    The embedded interpreter is never finalized (src/py_runtime.cc), so
    python threads still alive when the host process exits race C++
    static destructors — a decode-pool thread inside cv2 after OpenCV's
    TLS container is destroyed aborts the process (cv::Exception
    escaping at teardown; reproduced via the DataIter C API with
    preprocess_threads>1).  A refcount-driven __del__ is not guaranteed
    to run at DECREF time, and the prefetcher's join doesn't reach the
    base iterator's decode pool — so the C ABI calls this explicitly.
    """
    close = getattr(it, "close", None)
    if callable(close):
        try:
            close()
        except Exception:
            pass
    for obj in (it, getattr(it, "_base", None)):
        pool = getattr(obj, "_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
            obj._pool = None
    return True


__all__ += ["io_create", "io_next", "io_reset", "io_free"]


# ------------------------------- round-4 C ABI long tail (c_api.h tail)
def profiler_pause(paused):
    from mxnet_tpu import profiler
    (profiler.pause if int(paused) else profiler.resume)()


def seed(n):
    mx.seed(int(n))


def set_training(flag):
    from mxnet_tpu import tape
    return bool(tape.set_training(bool(int(flag))))


def is_training():
    from mxnet_tpu import tape
    return bool(tape.is_training())


def reshape(x, shape):
    return x.reshape(tuple(int(s) for s in shape))


def slice0(x, begin, end):
    return x[int(begin):int(end)]


def at0(x, idx):
    return x[int(idx)]


def kv_barrier(kv):
    if hasattr(kv, "barrier"):
        kv.barrier()
    return True


__all__ += ["profiler_pause", "seed", "set_training", "is_training",
            "reshape", "slice0", "at0", "kv_barrier"]


def dtype_code(x):
    """numpy dtype → reference dtype enum (mshadow type codes)."""
    codes = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
             "int32": 4, "int8": 5, "int64": 6, "bool": 7,
             "bfloat16": 12}
    return codes.get(str(getattr(x, "dtype", "float32")), 0)


__all__ += ["dtype_code"]


# ------------------------- round-5 C ABI long tail: generic JSON bridge
#
# One C entry point (py_runtime.cc JsonCall) dispatches here: plain
# scalars/strings ride a JSON object, opaque handles (NDArray / Symbol /
# KVStore PyObjects) ride a separate positional list, and each API is a
# small python callable in _C_JSON_TABLE returning
# (jsonable_result, [out_handles]).  Adding a C function costs one table
# entry + one ~6-line typed C wrapper — the typed C signature stays the
# public contract (include/mxtpu/c_api.h documents each).

def _cj_nd_waitall(args, handles):
    from mxnet_tpu import ndarray as _nd
    _nd.waitall()
    return None, []


def _cj_nd_wait_to_read(args, handles):
    handles[0].wait_to_read()
    return None, []


def _cj_nd_save(args, handles):
    from mxnet_tpu import nd as _ndm
    names = args.get("names")
    if names and len(set(names)) != len(names):
        # a dict container cannot hold duplicates — dropping one
        # silently would lose caller data
        raise ValueError("duplicate keys in MXTNDArraySave")
    data = dict(zip(names, handles)) if names else list(handles)
    _ndm.save(args["fname"], data)
    return None, []


def _cj_nd_load(args, handles):
    from mxnet_tpu import nd as _ndm
    loaded = _ndm.load(args["fname"])
    if isinstance(loaded, dict):
        names = list(loaded.keys())
        return {"names": names}, [loaded[n] for n in names]
    return {"names": []}, list(loaded)


def _cj_nd_storage_type(args, handles):
    return {"stype": getattr(handles[0], "stype", "default")}, []


def _cj_nd_copy_from(args, handles):
    dst, src = handles
    dst[...] = src
    return None, []


def _cj_list_all_op_names(args, handles):
    import mxnet_tpu as mx
    names = sorted(set(
        [n for n in dir(mx.np) if not n.startswith("_")] +
        [n for n in dir(mx.npx) if not n.startswith("_")] +
        [n for n in dir(mx.nd) if not n.startswith("_")]))
    ops = [n for n in names if callable(
        getattr(mx.nd, n, None) or getattr(mx.np, n, None) or
        getattr(mx.npx, n, None))]
    # explicit count: the C shim must not have to infer it from quote
    # characters (an op name containing '"' or '\' would skew that)
    return {"names": ops, "count": len(ops)}, []


def _cj_sym_from_json(args, handles):
    from mxnet_tpu import symbol as _sym
    return None, [_sym.load_json(args["json"])]


def _cj_sym_tojson(args, handles):
    # return the symbol graph OBJECT itself (not a {"json": ...}
    # envelope): the C buffer then holds valid, round-trippable symbol
    # JSON — GraphSymbol::FromJSON(sym.ToJSON()) must work
    import json as _json
    return _json.loads(handles[0].tojson()), []


def _cj_sym_list(args, handles):
    s = handles[0]
    which = args["which"]
    if which == "arguments":
        return {"names": s.list_arguments()}, []
    if which == "outputs":
        return {"names": s.list_outputs()}, []
    raise KeyError(which)


def _cj_sym_name(args, handles):
    return {"name": getattr(handles[0], "name", "") or ""}, []


def _cj_sym_infer_shape(args, handles):
    shapes = {k: tuple(v) for k, v in (args.get("shapes") or {}).items()}
    arg_s, out_s, aux_s = handles[0].infer_shape(**shapes)
    return {"arg_shapes": [list(s) for s in arg_s],
            "out_shapes": [list(s) for s in out_s],
            "aux_shapes": [list(s) for s in aux_s]}, []


def _cj_kv_set_gc(args, handles):
    handles[0].set_gradient_compression(args["params"])
    return None, []


def _cj_kv_broadcast(args, handles):
    kv, val = handles
    import mxnet_tpu as mx
    out = mx.np.zeros(val.shape, dtype=val.dtype)
    kv.broadcast(args["key"], val, out=out)
    return None, [out]


def _cj_profile_task(args, handles):
    from mxnet_tpu import profiler as _prof
    name, action = args["name"], args["action"]
    tasks = _cj_profile_task._live
    if action == "start":
        # name-keyed (the reference API is handle-based): a re-start of a
        # live name must stop-and-replace the old Task, or it leaks — one
        # Task per never-stopped name, forever, in a long-running process
        old = tasks.pop(name, None)
        if old is not None:
            old.stop()
        t = _prof.Task(name)
        t.start()
        tasks[name] = t
        if len(tasks) > _cj_profile_task._cap:
            import warnings
            warnings.warn(
                f"{len(tasks)} profiler tasks started and never stopped "
                f"(cap {_cj_profile_task._cap}) — a C caller is leaking "
                "task names; stop tasks under the SAME name they were "
                "started with")
    else:
        t = tasks.pop(name, None)
        if t is not None:
            t.stop()
    return None, []


_cj_profile_task._live = {}
_cj_profile_task._cap = 512


def _cj_profile_marker(args, handles):
    from mxnet_tpu import profiler as _prof
    _prof.Marker(args["name"]).mark()
    return None, []


def _cj_shutdown(args, handles):
    from mxnet_tpu import ndarray as _nd
    _nd.waitall()
    return None, []


def _cj_context_count(args, handles):
    import jax
    dev_type = args.get("dev_type", "")
    try:
        devs = jax.devices()
    except RuntimeError:
        return {"count": 0}, []
    if dev_type in ("", "any"):
        return {"count": len(devs)}, []
    if dev_type == "cpu":
        return {"count": len([d for d in devs
                              if d.platform == "cpu"]) or 1}, []
    # gpu/tpu both mean "the accelerator" (context.py gpu()≙tpu())
    return {"count": len([d for d in devs if d.platform != "cpu"])}, []


def _cj_load_lib(args, handles):
    from mxnet_tpu import library as _lib
    _lib.load(args["path"], verbose=bool(args.get("verbose", 0)))
    return None, []


_C_JSON_TABLE = {
    "nd_waitall": _cj_nd_waitall,
    "nd_wait_to_read": _cj_nd_wait_to_read,
    "nd_save": _cj_nd_save,
    "nd_load": _cj_nd_load,
    "nd_storage_type": _cj_nd_storage_type,
    "nd_copy_from": _cj_nd_copy_from,
    "list_all_op_names": _cj_list_all_op_names,
    "sym_from_json": _cj_sym_from_json,
    "sym_tojson": _cj_sym_tojson,
    "sym_list": _cj_sym_list,
    "sym_name": _cj_sym_name,
    "sym_infer_shape": _cj_sym_infer_shape,
    "kv_set_gc": _cj_kv_set_gc,
    "kv_broadcast": _cj_kv_broadcast,
    "profile_task": _cj_profile_task,
    "profile_marker": _cj_profile_marker,
    "shutdown": _cj_shutdown,
    "context_count": _cj_context_count,
    "load_lib": _cj_load_lib,
}


def c_json(fn, args_json, handles):
    """Generic C-ABI JSON bridge (see table above).

    Returns ``[result_json_or_None, out_handles_list]`` — py_runtime.cc
    copies the json into the caller's buffer and INCREFs each returned
    handle into the C handle space.
    """
    import json as _json
    impl = _C_JSON_TABLE[fn]
    args = _json.loads(args_json) if args_json else {}
    res, outs = impl(args, list(handles or ()))
    return [None if res is None else _json.dumps(res), list(outs)]


__all__ += ["c_json"]
