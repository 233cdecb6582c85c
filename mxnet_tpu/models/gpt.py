"""GPT — decoder-only transformer for the autoregressive fast path.

The generative counterpart of models/bert.py (ROADMAP item 3): same
functional-core shape (``GPTConfig``, ``init_params``, ``apply``) with
pre-LN GPT-2 blocks, but every layer exposes its per-token K/V so the
decode engine (mxnet_tpu/generate.py) can keep a device-resident ring
cache donated across steps.

Attention reuses the ``ops/attention.py`` interleaved selfatt
projections — the qkv kernel is laid out per-head ``[q|k|v]`` exactly as
``_contrib_interleaved_matmul_selfatt_*`` expects — now with the causal
mask those ops grew for this model.  Where
``pallas_kernels.causal_attention_use_pallas`` says so (one TPU,
head_dim in 128s, a prompt length in 128s) the prefill pass takes the
causal kernel pair through ``ops.nn.causal_gqa_attention``; elsewhere
the interleaved-op composition.  The decision is made at trace time
from the shapes alone.

Three entry points:
- ``apply``: full causal forward → logits (training / reference).
- ``prefill``: same forward, also returning the stacked per-layer K/V
  ``(layers, B, T, H, hd)`` for the engine to seed its ring cache.
- ``decode_step``: one token per row against the ring cache — reads
  the caches ``(layers, B, S, H, hd)``, writes this token's K/V at
  ``pos % S``, masks ring slots not yet written (``slot <= pos`` until
  the ring wraps, everything after), returns logits + updated caches.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import attention as _att
from ..ops import nn as _nn

__all__ = ["GPTConfig", "GPTModel", "init_params", "apply", "prefill",
           "decode_step"]

# finite causal-mask value (see ops/attention.py): softmax zeroes these
# exactly while a true -inf would NaN fully-masked lanes
_NEG_INF = -1e30


@dataclass
class GPTConfig:
    vocab_size: int = 32000
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    intermediate: int = 3072
    max_len: int = 1024
    dtype: object = jnp.float32


def _dense_init(key, in_dim, out_dim, dtype):
    k1, _ = jax.random.split(key)
    scale = 1.0 / math.sqrt(in_dim)
    return {
        "kernel": (jax.random.normal(k1, (in_dim, out_dim), jnp.float32)
                   * scale).astype(dtype),
        "bias": jnp.zeros((out_dim,), dtype),
    }


def init_params(cfg: GPTConfig, key) -> Dict:
    keys = jax.random.split(key, cfg.layers + 3)
    d, dt = cfg.hidden, cfg.dtype
    params = {
        "embed": {
            "tok": (jax.random.normal(keys[0], (cfg.vocab_size, d),
                                      jnp.float32) * 0.02).astype(dt),
            "pos": (jax.random.normal(keys[1], (cfg.max_len, d),
                                      jnp.float32) * 0.02).astype(dt),
        },
        "layers": [],
        "ln_f_g": jnp.ones((d,), dt), "ln_f_b": jnp.zeros((d,), dt),
        "head": _dense_init(keys[2], d, cfg.vocab_size, dt),
    }
    for i in range(cfg.layers):
        k = jax.random.split(keys[3 + i], 4)
        params["layers"].append({
            # per-head [q|k|v] interleave — the layout
            # interleaved_matmul_selfatt_* splits on
            "qkv": _dense_init(k[0], d, 3 * d, dt),
            "out": _dense_init(k[1], d, d, dt),
            "ffn_in": _dense_init(k[2], d, cfg.intermediate, dt),
            "ffn_out": _dense_init(k[3], cfg.intermediate, d, dt),
            "ln1_g": jnp.ones((d,), dt), "ln1_b": jnp.zeros((d,), dt),
            "ln2_g": jnp.ones((d,), dt), "ln2_b": jnp.zeros((d,), dt),
        })
    return params


def _proj(x, p):
    return jnp.einsum("...d,df->...f", x, p["kernel"],
                      preferred_element_type=jnp.float32).astype(x.dtype) \
        + p["bias"]


def _ffn(x, p):
    h = _nn.layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = jax.nn.gelu(_proj(h, p["ffn_in"]))
    return x + _proj(h, p["ffn_out"])


def _layer_prefill(x, p, heads):
    """One pre-LN decoder block over the full prompt.
    → (x', k, v) with k/v (B, T, H, hd) for the ring cache."""
    B, T, D = x.shape
    H, hd = heads, D // heads
    h = _nn.layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = _proj(h, p["qkv"])                       # (B, T, 3D) interleaved
    t5 = qkv.reshape(B, T, H, 3, hd)
    k, v = t5[:, :, :, 1], t5[:, :, :, 2]          # (B, T, H, hd)
    from ..ops import pallas_kernels as _pk
    if _pk.causal_attention_use_pallas(T, H, H, hd):
        ctx = _nn.causal_gqa_attention(t5[:, :, :, 0], k, v).reshape(B, T, D)
    else:
        qkv_t = qkv.transpose(1, 0, 2)             # (T, B, 3D)
        scores = _att.interleaved_matmul_selfatt_qk(qkv_t, H, causal=True)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(x.dtype)
        ctx = _att.interleaved_matmul_selfatt_valatt(
            qkv_t, probs, H).transpose(1, 0, 2)    # (B, T, D)
    x = x + _proj(ctx, p["out"])
    return _ffn(x, p), k, v


def _layer_step(x, p, heads, k_cache, v_cache, slot, valid):
    """One block for ONE token per row against the ring cache.
    x (B, D); caches (B, S, H, hd); slot (B,) write index; valid (B, S)
    readable-slot mask.  Writes this token's K/V BEFORE attending — the
    current token always attends to itself."""
    B, D = x.shape
    H, hd = heads, D // heads
    h = _nn.layer_norm(x, p["ln1_g"], p["ln1_b"])
    t4 = _proj(h, p["qkv"]).reshape(B, H, 3, hd)
    q, kn, vn = t4[:, :, 0], t4[:, :, 1], t4[:, :, 2]
    rows = jnp.arange(B)
    k_cache = k_cache.at[rows, slot].set(kn)
    v_cache = v_cache.at[rows, slot].set(vn)
    s = jnp.einsum("bhd,bshd->bhs", q, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhs,bshd->bhd", probs, v_cache,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    x = x + _proj(ctx.reshape(B, D), p["out"])
    return _ffn(x, p), k_cache, v_cache


def _logits(params, x):
    return jnp.einsum("...d,dv->...v",
                      _nn.layer_norm(x, params["ln_f_g"], params["ln_f_b"]),
                      params["head"]["kernel"],
                      preferred_element_type=jnp.float32) \
        + params["head"]["bias"].astype(jnp.float32)


def prefill(params, cfg: GPTConfig, tokens):
    """Full causal forward: tokens (B, T) int32 → (logits (B, T, vocab),
    k (layers, B, T, H, hd), v (same)) — the K/V stacks seed the decode
    engine's ring cache."""
    B, T = tokens.shape
    e = params["embed"]
    x = jnp.take(e["tok"], tokens, axis=0) + e["pos"][:T][None]
    ks, vs = [], []
    for p in params["layers"]:
        x, k, v = _layer_prefill(x, p, cfg.heads)
        ks.append(k)
        vs.append(v)
    return _logits(params, x), jnp.stack(ks), jnp.stack(vs)


def apply(params, cfg: GPTConfig, tokens):
    """Forward: tokens (B, T) int32 → logits (B, T, vocab)."""
    return prefill(params, cfg, tokens)[0]


def decode_step(params, cfg: GPTConfig, tok, pos, k_cache, v_cache):
    """One decode iteration: tok (B,) int32 at absolute positions pos
    (B,) int32, ring caches (layers, B, S, H, hd) → (logits (B, vocab),
    k_cache', v_cache').

    Ring discipline: token t lives at slot ``t % S``; a slot is readable
    once written — ``slot <= pos`` before the ring wraps, every slot
    after (``pos >= S`` means the last S tokens fill the whole ring).
    Rows whose pos exceeds ``max_len`` clamp the position embedding —
    the engine evicts such rows before their output is ever read."""
    B = tok.shape[0]
    S = k_cache.shape[2]
    e = params["embed"]
    x = jnp.take(e["tok"], tok, axis=0) + \
        jnp.take(e["pos"], jnp.clip(pos, 0, cfg.max_len - 1), axis=0)
    slot = pos % S
    valid = (jnp.arange(S)[None, :] <= pos[:, None]) | (pos[:, None] >= S)
    for i, p in enumerate(params["layers"]):
        x, ki, vi = _layer_step(x, p, cfg.heads, k_cache[i], v_cache[i],
                                slot, valid)
        k_cache = k_cache.at[i].set(ki)
        v_cache = v_cache.at[i].set(vi)
    return _logits(params, x), k_cache, v_cache


class GPTModel:
    """Thin object wrapper so examples can instantiate/apply like a Block."""

    def __init__(self, cfg: Optional[GPTConfig] = None, **overrides):
        self.cfg = cfg or GPTConfig(**overrides)
        self.params = None

    def initialize(self, key=None):
        from ..numpy.random import new_key
        self.params = init_params(self.cfg,
                                  key if key is not None else new_key())
        return self.params

    def __call__(self, tokens):
        from ..ndarray import NDArray
        raw = tokens._data if isinstance(tokens, NDArray) else tokens
        return NDArray(apply(self.params, self.cfg, raw))
