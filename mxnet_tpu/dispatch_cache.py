"""Eager dispatch fast path: cached per-op jitted executables.

≙ the reference's imperative dispatch cost model: Imperative::Invoke
pushes an already-compiled kernel onto the async engine in microseconds
(src/imperative/imperative.cc), while plain `jnp.add(a, b)` re-traces
and re-lowers the op on every call.  This module memoizes `jax.jit`
executables keyed on (op identity, static attrs, input avals) so a
steady-state eager op is one dict probe plus jit's C++ fast-path call —
see docs/eager_dispatch.md for the keying rules.

Soundness contract: a cache key must fully determine the computation.
Three key shapes exist:

* ``("fn", fun)`` — `fun` is a stable module-level callable (jnp.add,
  jax.nn.relu); identity + input avals determine everything.  The key
  tuple holds a strong reference so CPython cannot recycle the id.
* ``("op", name, frozen_attrs)`` — call-site lambdas that pass
  ``invoke_op(op=..., attrs=...)``.  The deferred-compute tracer
  (gluon/deferred.py record/replay) already requires (op, attrs) to
  determine semantics, so keying on the same pair is equally sound.
* explicit ``cache_key`` — callers that know their own identity
  (binary_op scalar closures, the mx.np `_call` dispatcher, the
  `cached_call` kernel wrapper below).

Anything else — tracer inputs, NDArray/jax.Array-valued attrs (stale
closure hazard: the captured array is data, not key), unhashable attrs,
fresh lambdas without an op name — falls back to the direct eager call.

Numeric leaves freeze as ``(type(v), v)`` because hash(2) == hash(2.0)
== hash(True) while promotion semantics differ.

Telemetry: hit/miss/evict/fallback counts are plain local ints on the
hot path; ``publish()`` (registered with telemetry.register_publisher)
batches them into the PR-3 registry at snapshot time.  Only the miss
path — already paying an XLA trace — records `dispatch.retrace_us`.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from collections import OrderedDict

import jax
import numpy as _onp

__all__ = ["dispatch", "cached_call", "derive_key", "freeze", "np_call_key",
           "fn_token", "never_cache", "stats", "reset_stats", "clear",
           "publish",
           "enabled", "set_enabled", "set_capacity", "cache_len"]

_FALSY = ("0", "false", "off")

_enabled = os.environ.get("MXNET_DISPATCH_CACHE", "1").lower() not in _FALSY
_capacity = max(1, int(os.environ.get("MXNET_DISPATCH_CACHE_SIZE", "1024")))

_mu = threading.Lock()
_cache: "OrderedDict[tuple, object]" = OrderedDict()   # key → jitted callable
_bad: set = set()        # keys whose jit failed once → permanent fallback
_BAD_CAP = 512

# type(x) → is it a concrete (non-tracer) jax array?  Verdict memoized per
# type so the hot path pays one dict probe instead of two isinstance walks.
_type_concrete: dict = {}
_Tracer = getattr(jax.core, "Tracer", ())

_hits = 0
_misses = 0
_evictions = 0
_fallbacks = 0
_retraces: dict = {}     # op label → retrace count (histogram by op)


class _Unfreezable(Exception):
    pass


def _is_concrete(a):
    t = type(a)
    ok = _type_concrete.get(t)
    if ok is None:
        ok = _type_concrete[t] = bool(
            isinstance(a, jax.Array) and not isinstance(a, _Tracer))
    return ok


# ------------------------------------------------------------------- keying
def never_cache(fun):
    """Mark `fun` permanently uncacheable.  For ops whose *python-side*
    behavior depends on concrete values — e.g. constraint_check raises
    on host when eagerly False but stays graph-safe under trace; jitting
    it would silently swallow the eager raise."""
    fun.__mx_uncacheable__ = True
    return fun


def _stable_callable(fun):
    """Is identity-keying `fun` safe?  True for module-level functions
    and callable class instances (jnp ufunc, PjitFunction, custom_jvp —
    these lack __qualname__ but live for the process).  False for
    call-site lambdas/closures (`<locals>` in the qualname: a fresh
    object per call would churn the LRU) and functools.partial."""
    if isinstance(fun, functools.partial):
        return False
    if getattr(fun, "__mx_uncacheable__", False):
        return False
    q = getattr(fun, "__qualname__", None)
    return q is None or ("<locals>" not in q and "<lambda>" not in q)


def freeze(v):
    """Hashable, type-tagged encoding of a static attr value.  Raises
    _Unfreezable for anything that is (or may hide) device data."""
    if v is None or v is Ellipsis:
        return v
    t = type(v)
    if t is str:
        return v
    if t in (bool, int, float, complex):
        return (t, v)           # hash(2)==hash(2.0)==hash(True): tag the type
    if t in (tuple, list):
        return (t.__name__, tuple(freeze(x) for x in v))
    if t is dict:
        return ("dict", tuple(sorted((k, freeze(x)) for k, x in v.items())))
    if t is slice:
        return ("slice", freeze(v.start), freeze(v.stop), freeze(v.step))
    if isinstance(v, _onp.dtype):
        return ("dtype", v.str)
    if isinstance(v, type):     # dtype classes: _onp.float32, jnp.bfloat16
        return ("type", v.__module__, v.__qualname__)
    if isinstance(v, _onp.generic):
        return ("npscalar", v.dtype.str, v.item())
    if isinstance(v, str):
        return v
    # NDArray, jax.Array, numpy.ndarray, arbitrary objects: refuse — an
    # array captured in attrs is DATA and must never become a cache key.
    raise _Unfreezable(type(v).__name__)


def derive_key(fun, op, attrs):
    """Default cache key for an invoke_op call, or None (uncacheable)."""
    if _stable_callable(fun):
        # stable module-level callable: identity is the key (the token
        # pins a strong ref, so the id can never be recycled)
        return ("fn", fn_token(fun))
    if op is not None and type(op) is str:
        try:
            return ("op", op, freeze(attrs) if attrs else ())
        except _Unfreezable:
            return None
    return None


def np_call_key(jfun, spec, kw):
    """Key for the mx.np/_npx `_call` dispatcher: target jax function +
    frozen arg spec + frozen kwargs.  None when uncacheable (fresh
    lambda target, array-valued kwargs/consts).

    A callable whose lowering reads mutable state (the sharding plan of
    parallel/train.py's step) carries an ``__mx_extra_key__`` callable,
    set directly or installed by ``cached_call``; its result joins the
    key here too so the np-dispatcher path invalidates on an edit
    exactly like the raw-kernel path."""
    if not _stable_callable(jfun):
        return None
    xk = getattr(jfun, "__mx_extra_key__", None)
    try:
        return ("np", fn_token(jfun), freeze(spec), freeze(kw),
                xk() if xk is not None else None)
    except (_Unfreezable, TypeError):
        return None


# ----------------------------------------------------------------- dispatch
# memoized ("fn", token) keys, indexed by id(fun): skips the per-call
# qualname probe AND the (surprisingly expensive) hash of jnp ufunc
# objects on the hottest path.  _fn_refs pins a strong reference per
# token so CPython can never recycle the id; both tables are bounded by
# the process's count of module-level jnp/jax callables.
_fn_keys: dict = {}
_fn_refs: dict = {}


def fn_token(fun) -> int:
    """Intern `fun` and return a cheap-to-hash key token for it (its id,
    kept valid by a strong reference).  Callers building explicit cache
    keys use this instead of embedding the callable: hashing a jnp ufunc
    costs ~0.5 µs per call, hashing an int is free."""
    i = id(fun)
    if i not in _fn_refs:
        _fn_refs[i] = fun
    return i


def _note_trace(label):
    # Runs ONLY while jit traces the wrapped op — i.e. once per new
    # (avals, statics) combination — so it converts one optimistic hit
    # into a miss and feeds the retrace-by-op histogram.
    global _hits, _misses
    _hits -= 1
    _misses += 1
    with _mu:
        _retraces[label] = _retraces.get(label, 0) + 1


def _build(fun, label):
    def counted(*xs):
        _note_trace(label)
        return fun(*xs)
    counted.__name__ = label
    return jax.jit(counted)


def dispatch(fun, raw, op=None, attrs=None, cache_key=None):
    """Run ``fun(*raw)`` through the executable cache.

    `raw` are raw jax arrays (already unwrapped from NDArray).  Returns
    exactly what the direct call would; falls back to it whenever
    caching is unsafe (tracers, unkeyable call) or the jit fails.

    The cache maps op identity (+ static attrs) to ONE jitted callable;
    pjit's internal C++ cache keys the per-aval executables under it, so
    the python hot path never hashes a ShapedArray.  A new input
    shape/dtype on a cached key surfaces as a miss + retrace through the
    `_note_trace` hook (its body only runs while jit is tracing).

    The hit path is deliberately lock-free: dict reads are GIL-atomic,
    counter increments may (rarely) lose a unit under contention, and
    true-LRU reordering only starts once the cache is near capacity —
    below that, eviction order is moot.  All mutation takes `_mu`.
    """
    global _hits, _misses, _evictions, _fallbacks
    if not _enabled:
        return fun(*raw)
    for a in raw:
        t = type(a)
        ok = _type_concrete.get(t)
        if ok is None:
            ok = _type_concrete[t] = bool(
                isinstance(a, jax.Array) and not isinstance(a, _Tracer))
        if not ok:
            # tracer (vjp/hybridize/user jit) or host value: transparent
            return fun(*raw)
    if cache_key is None:
        i = id(fun)
        cache_key = _fn_keys.get(i)
        if cache_key is None:
            cache_key = derive_key(fun, op, attrs)
            if cache_key is None:
                _fallbacks += 1
                return fun(*raw)
            if cache_key[0] == "fn":
                _fn_refs[i] = fun
                _fn_keys[i] = cache_key
    try:
        ent = _cache.get(cache_key)
    except TypeError:           # unhashable leaked through a caller's key
        _fallbacks += 1
        return fun(*raw)
    if ent is not None:
        _hits += 1              # _note_trace flips this on an aval retrace
        if len(_cache) * 8 >= _capacity * 7:
            with _mu:
                try:
                    _cache.move_to_end(cache_key)
                except KeyError:     # concurrently evicted
                    pass
        try:
            return ent(*raw)
        except Exception:
            # jit-only failure: quarantine the key, keep eager semantics
            with _mu:
                if len(_bad) < _BAD_CAP:
                    _bad.add(cache_key)
                _cache.pop(cache_key, None)
                _fallbacks += 1
            return fun(*raw)
    if cache_key in _bad:
        _fallbacks += 1
        return fun(*raw)
    # first build for this op key
    label = op if type(op) is str else getattr(fun, "__name__", "op")
    if label in ("fun", "call", "<lambda>", "op") and \
            type(cache_key) is tuple and len(cache_key) > 1:
        # closure wrappers (_call, scalar closures): the keyed target in
        # slot 1 names the op better than the closure does
        target = cache_key[1]
        if type(target) is int:
            target = _fn_refs.get(target)
        label = getattr(target, "__name__", label)
    ent = _build(fun, label)
    with _mu:
        cur = _cache.get(cache_key)
        if cur is None:
            _cache[cache_key] = ent
            while len(_cache) > _capacity:
                _cache.popitem(last=False)
                _evictions += 1
        else:
            ent = cur            # lost a benign race: reuse the winner
    t0 = time.perf_counter()
    try:
        out = ent(*raw)
    except Exception:
        with _mu:
            if len(_bad) < _BAD_CAP:
                _bad.add(cache_key)
            _cache.pop(cache_key, None)
            _fallbacks += 1
        return fun(*raw)
    # only first builds are timed — aval retraces on the hit path are
    # counted (via _note_trace) but not timed, keeping hits cheap
    _tele().observe("dispatch.retrace_us", (time.perf_counter() - t0) * 1e6)
    _hits += 1                   # _note_trace already flipped one to a miss
    return out


def cached_call(fun, extra_key=None):
    """Decorator for raw-array kernels (ops/nn.py, ops/tensor.py): array
    positional args are dynamic, everything else freezes into the key.
    Tracer/ndarray args, array kwargs, or unfreezable statics fall
    through to the plain call unchanged.

    `extra_key`: zero-arg callable whose (hashable) result joins the key
    — for kernels whose lowering reads mutable process state at call
    time, so changing it cannot serve a stale executable."""
    if getattr(fun, "__mx_uncacheable__", False):
        return fun
    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        global _fallbacks
        if not _enabled:
            return fun(*args, **kwargs)
        dyn = []
        pos = []
        spec = []
        try:
            for i, a in enumerate(args):
                if _is_concrete(a):
                    dyn.append(a)
                    pos.append(i)
                    spec.append(("d",))
                elif isinstance(a, _Tracer):
                    return fun(*args, **kwargs)
                else:
                    spec.append(("s", freeze(a)))
            frozen_kw = freeze(kwargs) if kwargs else ()
        except _Unfreezable:
            _fallbacks += 1
            return fun(*args, **kwargs)
        if not dyn:
            return fun(*args, **kwargs)

        def call(*dyn_raw):
            ar = list(args)
            for i, v in zip(pos, dyn_raw):
                ar[i] = v
            return fun(*ar, **kwargs)

        key = ("kern", fn_token(fun), tuple(spec), frozen_kw,
               extra_key() if extra_key is not None else None)
        return dispatch(call, dyn, op=getattr(fun, "__name__", None),
                        cache_key=key)
    # functools.wraps sets __wrapped__, but AMP's init/deinit cycle uses
    # that attribute to detect ITS wrapping layer — keep it off ours
    del wrapper.__wrapped__
    if extra_key is not None:
        # surfaced for np_call_key: the np `_call` dispatcher keys the
        # SAME mutable routing state when it caches through this op
        wrapper.__mx_extra_key__ = extra_key
    return wrapper


# -------------------------------------------------------------- introspection
def stats() -> dict:
    """Point-in-time cache statistics (embedded in bench rows and the
    opperf --dispatch-overhead JSON)."""
    total = _hits + _misses
    return {
        "enabled": _enabled,
        "size": len(_cache),
        "capacity": _capacity,
        "hits": _hits,
        "misses": _misses,
        "evictions": _evictions,
        "fallbacks": _fallbacks,
        "hit_rate": round(_hits / total, 6) if total else None,
        "retraces_by_op": dict(sorted(_retraces.items(),
                                      key=lambda kv: -kv[1])),
    }


def reset_stats():
    """Zero the counters (the cache itself is kept warm)."""
    global _hits, _misses, _evictions, _fallbacks
    with _mu:
        _hits = _misses = _evictions = _fallbacks = 0
        _retraces.clear()
        _published.clear()


def clear():
    """Drop every cached executable and quarantined key."""
    with _mu:
        _cache.clear()
        _bad.clear()
        _type_concrete.clear()


def cache_len() -> int:
    return len(_cache)


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> bool:
    """Flip the cache at runtime; returns the previous flag."""
    global _enabled
    prev = _enabled
    _enabled = bool(on)
    return prev


def set_capacity(n: int) -> int:
    """Resize the LRU bound; returns the previous capacity."""
    global _capacity, _evictions
    prev = _capacity
    _capacity = max(1, int(n))
    with _mu:
        while len(_cache) > _capacity:
            _cache.popitem(last=False)
            _evictions += 1
    return prev


# ---------------------------------------------------------------- telemetry
_telemetry = None
_published: dict = {}    # metric name → last value flushed into the registry


def _tele():
    global _telemetry
    if _telemetry is None:
        from . import telemetry as _t
        _telemetry = _t
    return _telemetry


def publish():
    """Flush the local counters into the telemetry registry as deltas.
    Called by telemetry.raw_snapshot() (via register_publisher) so every
    snapshot/summary/scrape sees current numbers without the hot path
    paying a registry call per op."""
    t = _tele()
    if not t.enabled():
        return
    for name, v in (("dispatch.cache_hits", _hits),
                    ("dispatch.cache_misses", _misses),
                    ("dispatch.cache_evictions", _evictions),
                    ("dispatch.cache_fallbacks", _fallbacks)):
        d = v - _published.get(name, 0)
        if d:
            t.counter_add(name, d)
            _published[name] = v
    t.gauge_set("dispatch.cache_size", len(_cache))
