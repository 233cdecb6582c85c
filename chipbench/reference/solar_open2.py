"""Plain reference of the Solar-Open2 language model (``model_type``
``solar_open2``: upstage/Solar-Open2-250B's ``config.json``; the linear
layers are Kimi Linear's KDA, arXiv:2510.26692): forward, next-token loss
and, through ``jax.grad``, gradients, in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``.

It imports nothing from ``mxnet_tpu``.  It takes the system's weights by
name (``{"layers.2.mixer.q_proj.weight": array, ...}``, as
``net.collect_params()`` names them) and the same share of the deployment,
given as arguments: the *heads* a mixer holds are the rows of the
projections it is given (``A_log`` has one entry a KDA head, ``k_proj`` has
``head_dim`` rows a key-value head), the *experts* are ``cfg["experts_held"]
= (first, count)`` of the experts the router scores, and the vocabulary
slice is simply the rows given.  ``cfg`` holds the source's keys
(``hidden_size``, ``head_dim``, ``linear_attn_config``, ...) plus
``pattern`` (one letter a block) and ``experts_held``.

Every block is ``h <- h + Sub(RMSNorm(h))`` with one sub-block chosen by the
pattern: ``K`` KDA by its *recurrence* over time, token by token; ``*``
gated causal grouped-query attention by the plain masked softmax; ``E``
routed SwiGLU experts by a *loop over the experts held* plus the shared
expert.  A published layer is a mixer block followed by an expert block.

Departures from the published description, each marked ``# departure``
where it is made:

1. a mixer adds only its held heads' part of ``W_o`` (row-parallel output
   projection without its all-reduce) and an expert block only the terms of
   the experts held: the chip's share of tensor and expert parallelism;
2. the loss is over the vocabulary rows given (a slice is a smaller
   vocabulary);
3. time and queries are walked in blocks, and blocks, query blocks and the
   recurrence's stretches are recomputed in the backward pass, so that 8192
   positions fit one chip; the mathematics is unchanged.

Assumed, where the config is silent (Kimi Linear's conventions; the
configuration's ``assumed`` lists the same): the two gates of a KDA layer
are low-rank, ``head_dim`` wide (``kda_use_full_proj`` false), without bias
but ``dt_bias``; q and k are L2-normalised a head after convolution and
SiLU, q scaled by ``head_dim ** -0.5``; ``beta = 2 sigmoid`` under
``kda_allow_neg_eigval``; value heads = heads (``num_kv_heads`` null); the
attention gate is per element, ``sigmoid(W_gate x)`` on the attention's
output before ``W_o``; the router is a sigmoid with a correction bias that
is added for the choice only, no expert groups, the chosen scores divided
by their sum (``norm_topk_prob``) times ``routed_scaling_factor``.

``dtype=jnp.bfloat16`` computes the same in the nearest precision below
(arrays stored in bfloat16): the benchmark reads how far that lies from
float32 to set its tolerances between the two.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

SCAN_STRETCH = 128      # steps of the recurrence recomputed together
QUERY_BLOCK = 256       # query rows scored at once
L2_EPS = 1e-6


def rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def _blocks(n, block):
    """``block`` if it divides ``n``, else ``n``: one block."""
    return block if n % block == 0 else n


# -------------------------------------------------------------------- KDA
def causal_conv1d(x, w):
    """``y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]`` with zeros before the
    sequence; ``x`` (T, C), ``w`` (C, K)."""
    k = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(k))


def kda_recurrence(q, k, v, g, beta):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t`` a head, from ``S_0 = 0``; ``q``, ``k``,
    ``g`` (T, H, dk), ``v`` (T, H, dv), ``beta`` (T, H)."""
    t, h, dk = k.shape
    dv = v.shape[-1]

    def one(state, inp):                                   # state (H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        # (I - b k k^T) S + b k v^T = S + k (b (v - S^T k))^T
        u = b_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint                                        # departure 3
    def stretch(state, inp):
        return lax.scan(one, state, inp)

    n = _blocks(t, SCAN_STRETCH)
    split = lambda a: a.reshape(t // n, n, *a.shape[1:])
    _, o = lax.scan(stretch, jnp.zeros((h, dk, dv), v.dtype),
                    tuple(split(a) for a in (q, k, v, g, beta)))
    return o.reshape(t, h, dv)


def kda(x, w, cfg):
    hd = cfg["linear_attn_config"]["head_dim"]
    heads = w["A_log"].shape[0]                            # the heads held
    short = lambda n: silu(causal_conv1d(x @ w[f"{n}_proj.weight"].T,
                                         w[f"{n}_conv_weight"]))
    q = l2_norm(short("q").reshape(-1, heads, hd)) * hd ** -0.5
    k = l2_norm(short("k").reshape(-1, heads, hd))
    v = short("v").reshape(-1, heads, hd)
    f = (x @ w["f_a.weight"].T) @ w["f_b.weight"].T + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * softplus(f.reshape(-1, heads, hd))
    beta = sigmoid(x @ w["b_proj.weight"].T)
    if cfg.get("kda_allow_neg_eigval", False):
        beta = 2.0 * beta
    o = kda_recurrence(q, k, v, g.astype(x.dtype), beta)
    o = rms_norm(o, w["o_norm_weight"], cfg["rms_norm_eps"])
    gate = sigmoid((x @ w["g_a.weight"].T) @ w["g_b.weight"].T)
    # departure 1: only the held heads' columns of W_o
    return (o.reshape(-1, heads * hd) * gate) @ w["o_proj.weight"].T


# -------------------------------------------------------- gated attention
def attention(x, w, cfg):
    hd = cfg["head_dim"]
    t = x.shape[0]
    # no rotary embedding: use_rope is false
    q = (x @ w["q_proj.weight"].T).reshape(t, -1, hd)
    k = (x @ w["k_proj.weight"].T).reshape(t, -1, hd)
    v = (x @ w["v_proj.weight"].T).reshape(t, -1, hd)
    heads, kv = q.shape[1], k.shape[1]                     # the heads held
    k = jnp.repeat(k, heads // kv, axis=1)          # each kv head serves
    v = jnp.repeat(v, heads // kv, axis=1)          # heads / kv query heads
    blk = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint                                        # departure 3
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
    gate = sigmoid(x @ w["gate_proj.weight"].T)
    # departure 1: only the held heads' columns of W_o
    return (o.reshape(t, heads * hd) * gate) @ w["o_proj.weight"].T


# ---------------------------------------------------------------- experts
def swiglu(x, gate_up, down):
    """``Down(silu(Gate x) * Up x)``; ``gate_up`` (D, 2F) holds Gate's
    columns, then Up's."""
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (silu(gate) * up) @ down


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
    y = swiglu(x, w["shared_up.weight"].T, w["shared_down.weight"].T)
    # departure 1: only the experts held here add their term
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(x, w["experts_up"][e],
                                      w["experts_down"][e])
    return y


BLOCKS = {"K": kda, "*": attention, "E": experts}


# ------------------------------------------------------------------ model
def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(params, tokens, cfg):
    """Final-norm hidden states ``(T, D)`` of one sequence ``(T,)``."""
    eps = cfg["rms_norm_eps"]
    h = params["embed.weight"][tokens]
    for i, kind in enumerate(cfg["pattern"]):
        w = _sub(params, f"layers.{i}.")

        @jax.checkpoint                                    # departure 3
        def block(h, w, kind=kind):
            return h + BLOCKS[kind](rms_norm(h, w["norm.gamma"], eps),
                                    _sub(w, "mixer."), cfg)
        h = block(h, w)
    return rms_norm(h, params["norm_f.gamma"], eps)


def _cast(params, dtype):
    return {k: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
            else v for k, v in params.items()}


def logits(params, tokens, cfg, dtype=jnp.float32):
    """``(B, T, V)`` over the vocabulary rows given (departure 2)."""
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
