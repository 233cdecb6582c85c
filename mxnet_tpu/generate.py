"""Autoregressive decode engine — donated ring KV cache, one XLA
program per (model, bucket).

The generative counterpart of serve/engine.py, generalizing the fused
train step's ``{rng, t}`` ctl-block (parallel/train.py) to the decode
loop: ONE donated program per (model, batch bucket) threads the whole
mutable decode state — ring K/V caches, per-row positions, the current
token, the sampling rng, and a step counter — through itself, so a
steady-state ``generate()`` is one dispatch per token with zero host
round trips beyond reading the emitted token id.

Ring cache layout (docs/generate.md): per layer ``(B, S, H, hd)`` with
token ``t`` at slot ``t % S`` — a slot is readable once written
(``slot <= pos`` until the ring wraps, every slot after), so prefill
pad garbage and stale seek tails are never attended.  ``S`` is the
``MXNET_DECODE_CACHE_LEN`` window: generation beyond it slides the
attention window (ring overwrite), generation beyond ``cfg.max_len``
is refused (position embeddings end there).

Retrace discipline extends the PR 7 trace-time hook: programs are keyed
by (kind, bucket, prompt-bucket, serve fingerprint, plan fingerprint),
so a serve-mesh or plan edit compiles NEW prefill/step programs instead
of serving stale traces.  A *retrace* is the same key traced twice: after
:meth:`DecodeEngine.warmup` precompiles the ladder, any second trace of
a warmed key is a shape leak and increments ``decode.retraces`` — gated
at zero by ``make decode-check``.

Tensor-parallel decode (``mesh=`` / ``MXNET_SERVE_MESH``): params place
1/tp-sharded (``infer_plan_tree`` — the qkv column rule splits the
interleaved per-head output dim, so attention heads shard for free) and
are gathered at use inside every program; the donated ring KV cache
shards its heads dim along tp with identical in/out shardings, so the
ctl block still aliases in place and steady-state decode stays zero
retraces AND bit-for-bit with the unsharded engine (``make
tp-serve-check``).  ``decode.kv_bytes_per_device`` reports what one
device actually holds.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as onp

from . import telemetry as _telemetry
from .models import gpt as _gpt

__all__ = ["DecodeEngine", "DEFAULT_BUCKETS", "DEFAULT_PROMPT_BUCKETS",
           "decode_buckets", "prompt_buckets", "snapshot", "restore"]

_US = 1e6

DEFAULT_BUCKETS = (1, 2, 4, 8)
DEFAULT_PROMPT_BUCKETS = (16, 64, 256)


def _ladder(env_name: str, default: Tuple[int, ...],
            buckets: Optional[Sequence[int]]) -> Tuple[int, ...]:
    if buckets is None:
        env = os.environ.get(env_name, "")
        if env.strip():
            buckets = [int(t) for t in env.split(",") if t.strip()]
        else:
            buckets = default
    out = tuple(sorted({int(b) for b in buckets}))
    if not out or out[0] < 1:
        raise ValueError(f"invalid bucket ladder {buckets!r}")
    return out


def decode_buckets(buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Batch-size ladder for decode programs: explicit argument, else
    ``MXNET_DECODE_BUCKETS`` (comma list), else (1, 2, 4, 8)."""
    return _ladder("MXNET_DECODE_BUCKETS", DEFAULT_BUCKETS, buckets)


def prompt_buckets(buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """Prompt-length ladder (prefill padding): explicit argument, else
    ``MXNET_DECODE_PROMPT_BUCKETS``, else (16, 64, 256)."""
    return _ladder("MXNET_DECODE_PROMPT_BUCKETS", DEFAULT_PROMPT_BUCKETS,
                   buckets)


def snapshot(ctl) -> dict:
    """Host copy of a decode control block — the *seek* primitive.  Read
    BEFORE the next (donating) step; restoring the copy later resumes
    decoding bit-for-bit from that point (same program, same bits)."""
    return {k: onp.asarray(v) for k, v in ctl.items()}


def restore(snap) -> dict:
    """Device control block from a :func:`snapshot` host copy."""
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in snap.items()}


def _pick(rng, logits, temperature):
    """Next-token rule, traced into every prefill/step program:
    greedy argmax at temperature 0 (the parity-gated default), else
    categorical sampling with the rng threaded through the ctl block."""
    import jax
    import jax.numpy as jnp
    if temperature > 0.0:
        rng, sub = jax.random.split(rng)
        return rng, jax.random.categorical(
            sub, logits / temperature, axis=-1).astype(jnp.int32)
    return rng, jnp.argmax(logits, axis=-1).astype(jnp.int32)


class DecodeEngine:
    """Compiled decode programs for one GPT model over a bucket ladder.

    Parameters
    ----------
    params : pytree
        ``models.gpt.init_params`` output (device-resident, shared by
        every program — never donated).
    cfg : models.gpt.GPTConfig
    window : int, optional
        Ring cache length S; default ``MXNET_DECODE_CACHE_LEN`` env,
        else ``cfg.max_len``.
    buckets, prompts : sequences, optional
        Batch / prompt-length ladders (env defaults above).  Prompt
        rungs longer than the window are dropped (prefill must fit the
        ring).
    temperature : float
        0 (default) decodes greedily — the bit-for-bit parity mode the
        gates assert; > 0 samples via the donated rng.
    mesh : jax.sharding.Mesh, optional
        Serving mesh for tensor-parallel decode; default from
        ``MXNET_SERVE_MESH`` (None = single-device).  Params place
        1/tp-sharded (``infer_plan_tree`` — the qkv column rule is a
        per-head split) and are gathered at use inside every program, so
        tp decode stays bit-for-bit with unsharded decode; the donated
        ring KV cache shards its heads dim along tp (same in/out
        sharding, so ctl donation still aliases — zero steady-state
        retraces).
    sharding_plan : ShardingPlan, optional
        Per-leaf layout override; default ``MXNET_SERVE_SHARDING_PLAN``,
        else inferred.  Its fingerprint keys every program.
    """

    def __init__(self, params, cfg, name: str = "gpt",
                 window: Optional[int] = None,
                 buckets: Optional[Sequence[int]] = None,
                 prompts: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, seed: int = 0,
                 mesh=None, sharding_plan=None):
        import jax

        from .parallel import sharding as _sharding
        from .serve.engine import resolve_serve_mesh

        self.mesh = resolve_serve_mesh(mesh)
        self.plan = None
        self.tp = 1
        self._rep = None            # gather-at-use target for params
        self._kv_sharding = None    # ring-cache layout (heads over tp)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from .parallel.mesh import axis_size, replicated
            plan = _sharding.resolve_plan(sharding_plan,
                                          env=_sharding.SERVE_PLAN_ENV)
            axis = plan.tp_axis if plan is not None else "tp"
            self.tp = axis_size(self.mesh, axis)
            if plan is None and self.tp > 1:
                plan = _sharding.infer_plan_tree(params, mesh=self.mesh)
            self.plan = plan
            self._rep = replicated(self.mesh)
            # cache (layers, B, S, H, hd): shard H when divisible — the
            # per-head split the qkv column rule induces on K/V
            head_axis = axis if (self.tp > 1 and
                                 cfg.heads % self.tp == 0) else None
            self._kv_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, None, None, head_axis, None))
            with _telemetry.timed("decode.shard_place_us"):
                params = _sharding.place_tree(params, self.mesh, plan)
        self.params = params
        self.param_bytes_per_device = int(
            _sharding.tree_bytes_per_device(params))
        self.cfg = cfg
        self.name = name
        if window is None:
            try:
                window = int(os.environ.get("MXNET_DECODE_CACHE_LEN", ""))
            except ValueError:
                window = cfg.max_len
        self.window = int(window)
        if not 1 <= self.window:
            raise ValueError(f"invalid cache window {window!r}")
        self.buckets = decode_buckets(buckets)
        self.prompt_buckets = tuple(t for t in prompt_buckets(prompts)
                                    if t <= self.window)
        if not self.prompt_buckets:
            raise ValueError(
                f"no prompt bucket fits the cache window {self.window}")
        self.temperature = float(temperature)
        self._rng = jax.random.PRNGKey(seed)
        self._programs: Dict[tuple, object] = {}
        self._trace_counts: Dict[tuple, int] = {}
        self._warm = False
        self.retraces = 0
        self._mu = threading.Lock()

    # ----------------------------------------------------------- plumbing
    def _fp(self) -> tuple:
        from .parallel import sharding as _sharding
        return (_sharding.serve_fingerprint(),
                self.plan.fingerprint if self.plan is not None else "")

    def _gather(self, pvals):
        """Gather-at-use: constrain every param leaf to replicated
        inside the program (an exact all-gather; storage stays 1/tp)."""
        if self._rep is None:
            return pvals
        import jax
        return jax.tree_util.tree_map(
            lambda v: jax.lax.with_sharding_constraint(v, self._rep), pvals)

    def _kv(self, arr):
        """Constrain a ring-cache array to the heads-over-tp layout —
        applied to every program's cache outputs so the donated ctl
        keeps identical in/out shardings (aliasing preserved)."""
        if self._kv_sharding is None:
            return arr
        import jax
        return jax.lax.with_sharding_constraint(arr, self._kv_sharding)

    def _note_trace(self, key):
        """Trace-time side effect inside every decode program.  Unlike
        serve/engine.py's any-trace-after-warm rule, a FIRST trace of a
        new key after warmup is a sanctioned rebuild (the serve or plan
        fingerprint in the key changed); only a SECOND trace of the same key is a shape leak."""
        with self._mu:
            n = self._trace_counts.get(key, 0) + 1
            self._trace_counts[key] = n
            if self._warm and n > 1:
                self.retraces += 1
                _telemetry.counter_add("decode.retraces")

    def _cache_shape(self, b: int) -> tuple:
        cfg = self.cfg
        return (cfg.layers, b, self.window, cfg.heads,
                cfg.hidden // cfg.heads)

    def _prog(self, kind: str, b: int, tb: int = 0):
        key = (kind, b, tb, self._fp())
        with self._mu:
            prog = self._programs.get(key)
        if prog is None:
            prog = getattr(self, f"_build_{kind}")(b, tb, key)
            with self._mu:
                prog = self._programs.setdefault(key, prog)
        return prog

    # ----------------------------------------------------------- programs
    def _build_prefill(self, b, tb, key):
        import jax
        import jax.numpy as jnp

        cfg, S, temp = self.cfg, self.window, self.temperature
        note = self._note_trace

        def run(pvals, tokens, lens, rng):
            note(key)
            pvals = self._gather(pvals)
            logits, ks, vs = _gpt.prefill(pvals, cfg, tokens)
            kc = jnp.zeros(self._cache_shape(b), cfg.dtype).at[:, :, :tb] \
                .set(ks)
            vc = jnp.zeros(self._cache_shape(b), cfg.dtype).at[:, :, :tb] \
                .set(vs)
            pos = lens - 1
            last = jnp.take_along_axis(
                logits, pos[:, None, None], axis=1)[:, 0]
            rng, tok = _pick(rng, last, temp)
            return {"k": self._kv(kc), "v": self._kv(vc), "pos": pos,
                    "tok": tok, "rng": rng,
                    "t": jnp.zeros((), jnp.int32)}

        return jax.jit(run)

    def _build_step(self, b, tb, key):
        import jax

        cfg, temp = self.cfg, self.temperature
        note = self._note_trace

        def run(pvals, ctl):
            note(key)
            pvals = self._gather(pvals)
            p = ctl["pos"] + 1
            logits, kc, vc = _gpt.decode_step(
                pvals, cfg, ctl["tok"], p, ctl["k"], ctl["v"])
            rng, tok = _pick(ctl["rng"], logits, temp)
            return {"k": self._kv(kc), "v": self._kv(vc), "pos": p,
                    "tok": tok, "rng": rng, "t": ctl["t"] + 1}

        # the ctl block is donated across steps: the ring caches alias
        # in place and the decode loop allocates nothing per token
        return jax.jit(run, donate_argnums=(1,))

    def _build_join(self, b, tb, key):
        """Continuous-batching prefill: decode one request's prompt at
        B=1 and splice its cache rows / position / first token into row
        ``slot`` of the running batch's donated ctl block — the
        join-at-iteration-boundary primitive DecodeBatcher drives."""
        import jax
        import jax.numpy as jnp

        cfg, S, temp = self.cfg, self.window, self.temperature
        note = self._note_trace

        def run(pvals, ctl, tokens, length, slot):
            note(key)
            pvals = self._gather(pvals)
            logits, ks, vs = _gpt.prefill(pvals, cfg, tokens)
            krow = jnp.zeros(self._cache_shape(1), cfg.dtype) \
                .at[:, :, :tb].set(ks)
            vrow = jnp.zeros(self._cache_shape(1), cfg.dtype) \
                .at[:, :, :tb].set(vs)
            kc = jax.lax.dynamic_update_slice(
                ctl["k"], krow, (0, slot, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                ctl["v"], vrow, (0, slot, 0, 0, 0))
            last = jnp.take(logits[0], length - 1, axis=0)
            rng, tok0 = _pick(ctl["rng"], last, temp)
            return {"k": self._kv(kc), "v": self._kv(vc),
                    "pos": ctl["pos"].at[slot].set(length - 1),
                    "tok": ctl["tok"].at[slot].set(tok0),
                    "rng": rng, "t": ctl["t"]}

        return jax.jit(run, donate_argnums=(1,))

    def empty_ctl(self, b: int) -> dict:
        """Fresh all-slots-idle ctl block for a B-row continuous batch:
        pos -1 marks a row as never prefilled (its ring stays masked)."""
        import jax
        import jax.numpy as jnp

        with self._mu:
            self._rng, sub = jax.random.split(self._rng)
        ctl = {"k": jnp.zeros(self._cache_shape(b), self.cfg.dtype),
               "v": jnp.zeros(self._cache_shape(b), self.cfg.dtype),
               "pos": jnp.full((b,), -1, jnp.int32),
               "tok": jnp.zeros((b,), jnp.int32),
               "rng": sub, "t": jnp.zeros((), jnp.int32)}
        if self.mesh is not None:
            # match the program's output layout up front so the very
            # first donated step already aliases the ring in place
            ctl = {k: jax.device_put(
                       v, self._kv_sharding if k in ("k", "v")
                       else self._rep)
                   for k, v in ctl.items()}
        return ctl

    # ------------------------------------------------------------- ladder
    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} exceeds max bucket "
                         f"{self.buckets[-1]}")

    def prompt_bucket_for(self, n: int) -> int:
        for t in self.prompt_buckets:
            if n <= t:
                return t
        raise ValueError(f"prompt of {n} exceeds max prompt bucket "
                         f"{self.prompt_buckets[-1]}")

    def warmup(self):
        """Precompile prefill + step + join for every ladder rung and
        block until done.  After this, a second trace of any warmed key
        counts as a retrace (a NEW key — fingerprint flip — does not)."""
        import warnings

        import jax.numpy as jnp

        with _telemetry.timed("decode.warmup_us"), \
                warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for b in self.buckets:
                for tb in self.prompt_buckets:
                    toks = jnp.zeros((b, tb), jnp.int32)
                    ctl = self._prog("prefill", b, tb)(
                        self.params, toks, jnp.ones((b,), jnp.int32),
                        self._rng)
                    ctl = self._prog("join", b, tb)(
                        self.params, ctl, jnp.zeros((1, tb), jnp.int32),
                        jnp.ones((), jnp.int32), jnp.zeros((), jnp.int32))
                ctl = self._prog("step", b)(self.params, ctl)
                ctl["tok"].block_until_ready()
        with self._mu:
            self._warm = True
        _telemetry.gauge_set("decode.programs", len(self._programs))
        return self

    @property
    def warm(self) -> bool:
        return self._warm

    # ------------------------------------------------------------- decode
    def generate(self, prompts: List[Sequence[int]],
                 max_new: int) -> List[List[int]]:
        """Greedy/sampled batch decode: ``max_new`` tokens per prompt.
        One prefill dispatch, then one step dispatch per token — the
        only host work in the loop is reading the emitted token ids."""
        import jax
        import jax.numpy as jnp

        if not prompts or max_new < 1:
            raise ValueError("need >= 1 prompt and max_new >= 1")
        longest = max(len(p) for p in prompts)
        if longest < 1:
            raise ValueError("empty prompt")
        if longest + max_new > self.cfg.max_len:
            raise ValueError(
                f"prompt {longest} + max_new {max_new} exceeds max_len "
                f"{self.cfg.max_len}")
        n = len(prompts)
        b = self.bucket_for(n)
        tb = self.prompt_bucket_for(longest)
        toks = onp.zeros((b, tb), onp.int32)
        lens = onp.ones((b,), onp.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
            lens[i] = len(p)
        with self._mu:
            self._rng, sub = jax.random.split(self._rng)
        with _telemetry.span("decode.generate", model=self.name,
                             bucket=b, prompt_bucket=tb, max_new=max_new):
            t0 = time.perf_counter()
            ctl = self._prog("prefill", b, tb)(
                self.params, jnp.asarray(toks), jnp.asarray(lens), sub)
            first = onp.asarray(ctl["tok"])
            _telemetry.observe("decode.prefill_us",
                               (time.perf_counter() - t0) * _US)
            _telemetry.counter_add("decode.prefills")
            _telemetry.gauge_set(
                "decode.kv_cache_bytes",
                2 * ctl["k"].size * ctl["k"].dtype.itemsize)
            from .parallel.sharding import shard_bytes as _shard_bytes
            _telemetry.gauge_set("decode.kv_bytes_per_device",
                                 2 * _shard_bytes(ctl["k"]))
            outs = [[int(first[i])] for i in range(n)]
            step = self._prog("step", b)
            for _ in range(max_new - 1):
                t0 = time.perf_counter()
                ctl = step(self.params, ctl)
                tok = onp.asarray(ctl["tok"])
                _telemetry.observe("decode.decode_step_us",
                                   (time.perf_counter() - t0) * _US)
                _telemetry.counter_add("decode.steps")
                for i in range(n):
                    outs[i].append(int(tok[i]))
            _telemetry.counter_add("decode.tokens", n * max_new)
        return outs

    # -------------------------------------------------------------- admin
    def trace_counts(self) -> Dict[tuple, int]:
        with self._mu:
            return dict(self._trace_counts)

    def stats(self) -> dict:
        with self._mu:
            return {"name": self.name, "window": self.window,
                    "buckets": list(self.buckets),
                    "prompt_buckets": list(self.prompt_buckets),
                    "temperature": self.temperature,
                    "warm": self._warm, "retraces": self.retraces,
                    "programs": len(self._programs),
                    "tp": self.tp,
                    "plan_fingerprint": (self.plan.fingerprint
                                         if self.plan is not None else None),
                    "param_bytes_per_device": self.param_bytes_per_device}


def _selfcheck(verbose: bool = True) -> int:
    """``make decode-check``: continuous-batched decode bit-for-bit vs
    unbatched greedy, ring wraparound + seek parity, 0 steady-state
    retraces, join-at-iteration-boundary observed, and the
    flash-attention route flip re-keying both program-cache paths."""
    import jax

    from . import telemetry
    from .models import gpt as G
    from .serve.batcher import DecodeBatcher

    telemetry.reset()
    checks = []
    cfg = G.GPTConfig(vocab_size=61, hidden=32, layers=2, heads=2,
                      intermediate=64, max_len=64)
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(params, cfg, name="sc", window=16,
                       buckets=(1, 2), prompts=(8,)).warmup()

    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    outs = eng.generate(prompts, max_new=8)
    singles = [eng.generate([p], max_new=8)[0] for p in prompts]
    checks.append(("batched decode bit-for-bit vs per-request greedy",
                   outs == singles))
    checks.append(("decode emits max_new tokens per prompt",
                   all(len(o) == 8 for o in outs)))

    base = eng.retraces
    eng.generate(prompts, max_new=4)
    checks.append(("0 steady-state retraces", eng.retraces == base == 0))

    # ------------------------------------------- ring wraparound + seek
    import jax.numpy as jnp
    o1 = eng.generate([[7, 7, 2, 1, 5]], max_new=14)   # 19 tokens > S=16
    o2 = eng.generate([[7, 7, 2, 1, 5]], max_new=14)
    checks.append(("ring wraparound deterministic", o1 == o2))

    with eng._mu:
        eng._rng, sub = jax.random.split(jax.random.PRNGKey(7))
    toks = onp.zeros((1, 8), onp.int32)
    toks[0, :5] = [7, 7, 2, 1, 5]
    ctl = eng._prog("prefill", 1, 8)(
        eng.params, jnp.asarray(toks), jnp.asarray([5], onp.int32), sub)
    step = eng._prog("step", 1)
    for _ in range(3):
        ctl = step(eng.params, ctl)
    snap = snapshot(ctl)                       # seek point (host copy)
    cont = []
    for _ in range(3):
        ctl = step(eng.params, ctl)
        cont.append(int(onp.asarray(ctl["tok"])[0]))
    end_a = snapshot(ctl)
    ctl = restore(snap)                        # rewind and replay
    replay = []
    for _ in range(3):
        ctl = step(eng.params, ctl)
        replay.append(int(onp.asarray(ctl["tok"])[0]))
    end_b = snapshot(ctl)
    checks.append(("seek replay emits identical tokens", cont == replay))
    checks.append(("seek replay cache bit-for-bit vs recompute",
                   onp.array_equal(end_a["k"], end_b["k"]) and
                   onp.array_equal(end_a["v"], end_b["v"])))

    # -------------------------------------- token-level continuous batch
    bat = DecodeBatcher(eng, slots=2)
    try:
        import threading as _th
        got = {}

        def _one(i, p):
            got[i] = bat.submit(p, max_new=8)

        ts = [_th.Thread(target=_one, args=(i, p))
              for i, p in enumerate(prompts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        checks.append(("continuous-batched decode bit-for-bit vs "
                       "unbatched greedy",
                       [got[i] for i in range(len(prompts))] == singles))
        st = bat.stats()
        checks.append(("join-at-iteration-boundary observed",
                       st["joins"] >= 2 and st["leaves"] >= 2))
        checks.append(("requests overlapped in the running batch",
                       st["max_concurrent"] >= 2))
        checks.append(("0 retraces across continuous batching",
                       eng.retraces == 0))
    finally:
        bat.close()

    snap_t = telemetry.summary()
    checks.append(("decode telemetry emitted",
                   snap_t.get("decode.prefills", 0) > 0 and
                   snap_t.get("decode.steps", 0) > 0 and
                   snap_t.get("decode.tokens", 0) > 0))

    ok = True
    for name, passed in checks:
        ok = ok and passed
        if verbose:
            print(f"  [{'ok' if passed else 'FAIL'}] {name}")
    if verbose:
        print(f"decode-check: {'PASS' if ok else 'FAIL'} "
              f"({len(checks)} checks)")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(_selfcheck())
