"""Plain reference of the Nemotron-H language model (``model_type``
``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B, arXiv:2504.03624): forward,
next-token loss and, through ``jax.grad``, gradients, in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.

It imports nothing from ``mxnet_tpu``.  It takes the system's weights by
name (``{"layers.3.mixer.in_proj.weight": array, ...}``, as
``net.collect_params()`` names them) and the same share of the deployment:
``experts_held = (first, count)`` and a vocabulary slice that is simply the
rows it is given.  ``cfg`` holds the source's keys (``hidden_size``,
``hybrid_override_pattern``, ...) plus ``experts_held``.

Every layer is ``h <- h + Mixer(RMSNorm(h))`` with one mixer chosen by the
pattern: ``M`` Mamba-2 by its *recurrence* over time, ``E`` routed experts by
a *loop over the experts held*, ``*`` causal grouped-query attention by the
plain masked softmax.  Departures from the published description, each
marked ``# departure`` where it is made:

1. no rotary embedding in attention (the config still holds ``rope_theta``;
   the Nemotron-H report states the attention layers use none);
2. an expert layer adds only the terms of the experts held; what the absent
   experts would add is left out (the chip's share of expert parallelism);
3. the loss is over the vocabulary rows given (a slice is a smaller
   vocabulary);
4. time and queries are walked in blocks, and layers, query blocks and the
   recurrence's chunks are recomputed in the backward pass, so that 8192
   positions fit one chip; the mathematics is unchanged.

``dtype=jnp.bfloat16`` computes the same in the nearest precision below
(arrays stored in bfloat16): the benchmark reads how far that lies from
float32 to set its tolerances between the two.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
SCAN_CHUNK = 128        # steps of the recurrence recomputed together
QUERY_BLOCK = 256       # query rows scored at once


def rms_norm(x, w):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def _blocks(n, block):
    """``block`` if it divides ``n``, else ``n``: one block."""
    return block if n % block == 0 else n


# ---------------------------------------------------------------- Mamba-2
def causal_conv1d(x, w, b):
    """``y[t, c] = b[c] + sum_j w[c, j] x[t - (K-1) + j, c]`` with zeros
    before the sequence; ``x`` (T, C), ``w`` (C, K)."""
    k = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return b + sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(k))


def ssm_recurrence(x, dt, a, bmat, cmat, d):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    D x_t`` a head; ``x`` (T, H, P), ``dt`` (T, H), ``a`` (H,), ``bmat`` and
    ``cmat`` (T, G, N) with H / G heads a group, ``d`` (H,)."""
    t, h, p = x.shape
    g, n = bmat.shape[1:]
    bh = jnp.repeat(bmat, h // g, axis=1)                   # (T, H, N)
    ch = jnp.repeat(cmat, h // g, axis=1)

    def one(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    # departure 4: the inner scan is recomputed chunk by chunk in backward
    @jax.checkpoint
    def chunk(state, inp):
        return lax.scan(one, state, inp)

    q = _blocks(t, SCAN_CHUNK)
    split = lambda v: v.reshape(t // q, q, *v.shape[1:])
    _, y = lax.scan(chunk, jnp.zeros((h, p, n), x.dtype),
                    (split(x), split(dt), split(bh), split(ch)))
    return y.reshape(t, h, p) + d[:, None] * x


def mamba2(u, w, cfg):
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * hd
    zxbcdt = u @ w["in_proj.weight"].T
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * groups * n],
                           axis=-1)
    xbc = silu(causal_conv1d(xbc, w["conv_weight"], w["conv_bias"]))
    x, bmat, cmat = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    dt = softplus(dt + w["dt_bias"])
    y = ssm_recurrence(x.reshape(-1, heads, hd), dt, -jnp.exp(w["A_log"]),
                       bmat.reshape(-1, groups, n),
                       cmat.reshape(-1, groups, n), w["D"])
    y = y.reshape(-1, inner) * silu(z)
    # gated RMSNorm over groups of inner / n_groups channels
    y = rms_norm(y.reshape(-1, groups, inner // groups), 1.0)
    y = y.reshape(-1, inner) * w["norm.gamma"]
    return y @ w["out_proj.weight"].T


# ---------------------------------------------------------------- experts
def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def route(x, w, cfg):
    """Chosen experts ``(T, k)`` and their weights: sigmoid scores in
    float32 over all experts, top-k of score + correction bias, the scores
    of the chosen divided by their sum, times the scaling factor."""
    s = jax.nn.sigmoid(x.astype(jnp.float32)
                       @ w["router_weight"].astype(jnp.float32).T)
    _, idx = lax.top_k(s + w["correction_bias"].astype(jnp.float32),
                       cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, (chosen * cfg["routed_scaling_factor"]).astype(x.dtype)


def experts(x, w, cfg):
    first, count = cfg["experts_held"]
    idx, weight = route(x, w, cfg)
    y = relu2(x @ w["shared_up.weight"].T) @ w["shared_down.weight"].T
    # departure 2: only the experts held here add their term
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * (relu2(x @ w["experts_up"][e])
                                @ w["experts_down"][e])
    return y


# -------------------------------------------------------------- attention
def attention(x, w, cfg):
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    t = x.shape[0]
    # departure 1: q and k are used as projected, no rotary embedding
    q = (x @ w["q_proj.weight"].T).reshape(t, heads, hd)
    k = (x @ w["k_proj.weight"].T).reshape(t, kv, hd)
    v = (x @ w["v_proj.weight"].T).reshape(t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=1)          # each kv head serves
    v = jnp.repeat(v, heads // kv, axis=1)          # heads / kv query heads
    blk = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint                                  # departure 4
    def rows(args):
        q_b, pos = args                              # (blk, heads, hd), (blk,)
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        s = jnp.where(pos[None, :, None] >= jnp.arange(t)[None, None, :],
                      s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = lax.map(rows, (q.reshape(t // blk, blk, heads, hd),
                       jnp.arange(t).reshape(t // blk, blk)))
    return o.reshape(t, heads * hd) @ w["o_proj.weight"].T


MIXERS = {"M": mamba2, "E": experts, "*": attention}


# ------------------------------------------------------------------ model
def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(params, tokens, cfg):
    """Final-norm hidden states ``(T, D)`` of one sequence ``(T,)``."""
    h = params["embed.weight"][tokens]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        w = _sub(params, f"layers.{i}.")

        @jax.checkpoint                              # departure 4
        def layer(h, w, kind=kind):
            return h + MIXERS[kind](rms_norm(h, w["norm.gamma"]),
                                    _sub(w, "mixer."), cfg)
        h = layer(h, w)
    return rms_norm(h, params["norm_f.gamma"])


def _cast(params, dtype):
    return {k: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
            else v for k, v in params.items()}


def logits(params, tokens, cfg, dtype=jnp.float32):
    """``(B, T, V)`` over the vocabulary rows given (departure 3)."""
    params = _cast(params, dtype)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda t: hidden(params, t, cfg)
                        @ params["head.weight"].T)(tokens)


def loss(params, tokens, labels, cfg, dtype=jnp.float32, with_logits=False):
    """Mean next-token cross-entropy over the rows given; ``labels`` are the
    tokens shifted by one by the caller.  ``with_logits`` returns ``(loss,
    logits)``, for ``jax.value_and_grad(..., has_aux=True)``."""
    z = logits(params, tokens, cfg, dtype).astype(jnp.float32)
    m = jnp.max(z, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m[..., None]), axis=-1))
    value = jnp.mean(
        lse - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0])
    return (value, z) if with_logits else value
