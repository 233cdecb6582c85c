"""Causal flash-attention forward — the decode fast path's prefill op.

ROADMAP item 3 (generative decoding): the paper's "Pallas for fused
Softmax" promise applied to attention itself.  The kernel is an
online-softmax (FlashAttention) forward, streamed over key blocks,
with the causal band folded into the streaming loop:

- **row-blocked**: grid ``(B·H, Lq // block_q)`` — one (block_q, D)
  query tile per program, K/V streamed through VMEM ``block_k`` rows at
  a time, running max / sum / accumulator in f32 VMEM registers, ONE
  HBM pass over K/V and the (L, L) score matrix never materializes.
- **causal**: key blocks entirely above the tile's diagonal are never
  fetched (the ``fori_loop`` upper bound is the last intersecting
  block), and the partial diagonal block is masked in-register to a
  finite ``-1e30`` so ``exp`` underflows to exactly 0.0 without NaN.

Forward-only by design: ``generate()`` never differentiates, and the
trainable path keeps ``pallas_kernels.attention_fused`` (custom VJP).

Dispatch mirrors ``pallas_block`` / ``pallas_int8``: a per-stage
(``LxD``) decision table committed from ``benchmark/pallas_conv_ab.py
--attn`` A/B sweeps (``benchmark/results/pallas_attn_ab.json``), an env
master switch, and a memoised ``attn_fingerprint()`` folded into
``pallas_block.dispatch_fingerprint()`` so a route flip re-keys every
dispatch-cache path instead of serving a stale executable.  Env knobs
(docs/env_var.md): MXNET_TPU_PALLAS_ATTN (master),
MXNET_TPU_PALLAS_ATTN_TABLE (alternate table).

The XLA composition fallback (``causal_attention_xla``) is the masked
f32 einsum — also the interpret-mode parity reference.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_block as pb

__all__ = ["attn_enabled", "attn_stage_key", "attn_table",
           "attn_fingerprint", "eligible_attn", "decide_attn",
           "causal_attention", "causal_attention_xla"]

# finite mask value: exp(-1e30 - m) underflows to 0.0; a true -inf would
# poison the running max with inf - inf = nan on fully masked lanes
_NEG_INF = -1e30


def _tele():
    from .. import telemetry
    return telemetry


def attn_stage_key(L: int, D: int) -> str:
    """Attention stages key on (query length, head dim) — the two shape
    axes the kernel tiles over; batch and heads only scale the grid."""
    return f"{L}x{D}"


# Default decisions pending a chip A/B run (benchmark/pallas_conv_ab.py
# --attn --commit-table): the one-HBM-pass forward wins once the (L, L)
# score matrix stops fitting in VMEM, so the long-sequence stages are
# routed until real measurements say otherwise.
_DEFAULT_TABLE = {
    "512x128": {"fwd": "pallas"},
    "1024x128": {"fwd": "pallas"},
    "2048x128": {"fwd": "pallas"},
}

_table_cache = {"path": None, "mtime": None, "table": None}


_DEFAULT_TABLE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    "benchmark", "results", "pallas_attn_ab.json")


def _table_path() -> str:
    return os.environ.get("MXNET_TPU_PALLAS_ATTN_TABLE", "") or \
        _DEFAULT_TABLE_PATH


def attn_table() -> dict:
    """Per-stage attention route table from the committed A/B JSON
    (mtime-cached), or the built-in default when absent."""
    path = _table_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return dict(_DEFAULT_TABLE)
    c = _table_cache
    if c["path"] == path and c["mtime"] == mtime:
        return c["table"]
    try:
        with open(path) as f:
            doc = json.load(f)
        tab = {k: {"fwd": str(v.get("fwd", "xla"))}
               for k, v in doc.get("decisions", {}).items()}
    except (OSError, ValueError, AttributeError):
        tab = dict(_DEFAULT_TABLE)
    c.update(path=path, mtime=mtime, table=tab)
    return tab


def attn_enabled() -> bool:
    """Master switch for the causal Pallas route.  Default: table-driven
    on one TPU only (``pallas_block.one_tpu``; interpret mode is a
    correctness tool, not a fast path);
    ``MXNET_TPU_PALLAS_ATTN=1`` forces routing on any platform (tests /
    ``make decode-check``); ``0`` disables outright — every prefill
    takes the XLA masked-einsum composition."""
    v = os.environ.get("MXNET_TPU_PALLAS_ATTN", "")
    if v == "0":
        return False
    if v == "1":
        return True
    return pb.one_tpu()


_fp_cache = {"key": None, "fp": None}


def attn_fingerprint() -> tuple:
    """Hashable digest of the mutable attention routing state — the
    MXNET_TPU_PALLAS_ATTN / table knobs.  Folded into
    ``pallas_block.dispatch_fingerprint()`` and therefore into every
    cached-call extra_key and np-dispatcher ``__mx_extra_key__`` key,
    AND into the decode engine's program-cache keys (generate.py), so a
    route flip re-keys both cache paths — prefill programs and decode
    steps — instead of serving a stale executable.

    Runs on EVERY dispatch (it rides the extra_key hook), so the digest
    is memoised on exactly its mutable inputs — the two env knobs plus
    the table file's mtime — leaving the steady-state cost at two env
    reads and one stat."""
    env = (os.environ.get("MXNET_TPU_PALLAS_ATTN", ""),
           os.environ.get("MXNET_TPU_PALLAS_ATTN_TABLE", ""))
    try:
        mtime = os.stat(_table_path()).st_mtime_ns
    except OSError:
        mtime = -1
    c = _fp_cache
    if c["key"] == (env, mtime):
        return c["fp"]
    fp = ("attn", *env,
          tuple(sorted((k, v["fwd"]) for k, v in attn_table().items())))
    c.update(key=(env, mtime), fp=fp)
    return fp


def eligible_attn(q_shape, k_shape, dtype) -> bool:
    """Shape/VMEM gate: 4-D (B, H, L, D) with an MXU-aligned head dim,
    block-divisible sequence lengths, and the full K/V stream + one
    query/output tile double-buffered under the same 12 MiB budget the
    conv kernels measured against."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, H, Lq, D = q_shape
    Lk = k_shape[2]
    if D % 128 or Lq % 8 or Lk % 8 or Lq < 1 or Lk < 1:
        return False
    isz = jnp.dtype(dtype).itemsize
    block_q = _fit_block(Lq)
    bytes_needed = 2 * (2 * Lk * D * isz          # K + V, double-buffered
                        + block_q * D * isz * 2   # q tile + out tile
                        + block_q * D * 4)        # f32 accumulator
    return bytes_needed < 12 * 1024 * 1024


def decide_attn(q_shape, k_shape, dtype) -> str:
    """Route one causal prefill attention: ``"pallas"`` or ``"xla"``.
    Emits the ``dispatch.attn.{hits,fallbacks}.<stage>`` counters —
    these count routing *decisions* (trace/dispatch time), so a
    steady-state decode loop re-decides nothing, by design."""
    stage = attn_stage_key(q_shape[2] if len(q_shape) == 4 else 0,
                           q_shape[3] if len(q_shape) == 4 else 0)
    if not attn_enabled():
        return "xla"
    if not eligible_attn(q_shape, k_shape, dtype):
        _tele().counter_add(f"dispatch.attn.fallbacks.{stage}", 1)
        return "xla"
    ent = attn_table().get(stage)
    if not ent or ent.get("fwd") != "pallas":
        _tele().counter_add(f"dispatch.attn.fallbacks.{stage}", 1)
        return "xla"
    _tele().counter_add(f"dispatch.attn.hits.{stage}", 1)
    return "pallas"


# ----------------------------------------------------------------- kernel
def _fit_block(n: int, block: int = 128) -> int:
    """Largest divisor of n that is <= block (pallas_kernels idiom)."""
    b = min(n, block)
    while n % b:
        b -= 1
    return b


def _causal_attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_q,
                        block_k):
    """One (block_q, D) query tile vs the causal prefix of K/V, online
    softmax.  The loop bound is the last key block intersecting the
    tile's diagonal — blocks strictly above the band are never fetched —
    and the partial diagonal block is masked in-register."""
    i = pl.program_id(1)
    q = q_ref[0] * scale
    _, d = q.shape
    rows = i * block_q + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        cols = j * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    # last key block the band reaches: key col (i+1)*block_q - 1
    nblk = (i * block_q + block_q + block_k - 1) // block_k
    m, l, acc = jax.lax.fori_loop(0, nblk, body, (m, l, acc))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _causal_attention_pallas(q, k, v, scale):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _fit_block(Lq)
    block_k = _fit_block(Lk)
    q3 = q.reshape(B * H, Lq, D)
    k3 = k.reshape(B * H, Lk, D)
    v3 = v.reshape(B * H, Lk, D)
    out = pl.pallas_call(
        functools.partial(_causal_attn_kernel, scale=scale,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
        grid=(B * H, Lq // block_q),
        in_specs=[pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        interpret=pb.interpret(),
        name="mx_flash_fwd",
    )(q3, k3, v3)
    return out.reshape(B, H, Lq, D)


def causal_attention_xla(q, k, v, scale):
    """XLA composition fallback AND parity reference: causal-masked f32
    logits/softmax einsum for (B, H, L, D) tensors."""
    Lq, Lk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    rows = jnp.arange(Lq, dtype=jnp.int32)[:, None]
    cols = jnp.arange(Lk, dtype=jnp.int32)[None, :]
    s = jnp.where(cols <= rows, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def causal_attention(q, k, v, scale=None):
    """Causal softmax(QKᵀ·scale)V for (B, H, L, D) — routed per the
    committed ``LxD`` decision table (Pallas online-softmax forward where
    the A/B measured a win, masked-einsum XLA composition elsewhere).
    Forward-only: the decode fast path never differentiates."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if decide_attn(q.shape, k.shape, q.dtype) == "pallas":
        return _causal_attention_pallas(q, k, v, scale)
    return causal_attention_xla(q, k, v, scale)


def _selfcheck(verbose: bool = True) -> int:
    """Interpret-mode parity of the causal Pallas kernel vs the masked
    einsum reference, plus table/fingerprint plumbing.  Part of
    ``make decode-check``; CPU-safe (interpret mode)."""
    import numpy as onp

    rs = onp.random.RandomState(0)
    checks = []

    for (B, H, L, D) in ((1, 2, 128, 128), (2, 1, 256, 128)):
        q = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
        k = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
        v = jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
        scale = 1.0 / (D ** 0.5)
        out = _causal_attention_pallas(q, k, v, scale)
        ref = causal_attention_xla(q, k, v, scale)
        checks.append((f"causal kernel parity ({L}x{D})",
                       bool(jnp.allclose(out, ref, atol=2e-5, rtol=2e-5))))
        # future keys must not leak into row 0: row 0 attends key 0 only
        checks.append((f"row 0 sees only key 0 ({L}x{D})",
                       bool(jnp.allclose(out[:, :, 0], v[:, :, 0],
                                         atol=2e-5, rtol=2e-5))))

    old = os.environ.get("MXNET_TPU_PALLAS_ATTN")
    try:
        os.environ["MXNET_TPU_PALLAS_ATTN"] = "1"
        fp1 = attn_fingerprint()
        r1 = decide_attn((1, 2, 512, 128), (1, 2, 512, 128), jnp.float32)
        os.environ["MXNET_TPU_PALLAS_ATTN"] = "0"
        fp2 = attn_fingerprint()
        r2 = decide_attn((1, 2, 512, 128), (1, 2, 512, 128), jnp.float32)
        checks.append(("table routes 512x128 to pallas when forced",
                       r1 == "pallas"))
        checks.append(("master switch 0 falls back to xla", r2 == "xla"))
        checks.append(("flip changes the attn fingerprint", fp1 != fp2))
        checks.append(("attn fingerprint rides dispatch_fingerprint",
                       fp2 in pb.dispatch_fingerprint()))
    finally:
        if old is None:
            os.environ.pop("MXNET_TPU_PALLAS_ATTN", None)
        else:
            os.environ["MXNET_TPU_PALLAS_ATTN"] = old

    ok = True
    for name, passed in checks:
        ok = ok and passed
        if verbose:
            print(f"  [{'ok' if passed else 'FAIL'}] {name}")
    if verbose:
        print(f"pallas-attn: {'PASS' if ok else 'FAIL'} "
              f"({len(checks)} checks)")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(_selfcheck())
