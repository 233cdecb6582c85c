"""Plain reference of the Olmo-Hybrid language model (``model_type``
``olmo_hybrid``: allenai/Olmo-Hybrid-7B's ``config.json``; the linear layers
are Gated DeltaNet's, arXiv:2412.06464, the block is OLMo 2's,
arXiv:2501.00656): forward, next-token loss and, through ``jax.grad``,
gradients, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

It imports nothing from ``mxnet_tpu``.  It takes the system's weights by
name (``{"layers.0.mixer.q_proj.weight": array, ...}``, as
``net.collect_params()`` names them) and the same share of the deployment,
which is simply the rows given: the *heads* a mixer holds are the rows of
its projections (``A_log`` has one entry a linear head, ``q_proj`` of a full
layer ``head_dim`` rows a head) and the vocabulary slice the rows of the
embedding and the head.  ``cfg`` holds the source's keys (``hidden_size``,
``num_attention_heads`` as published, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_allow_neg_eigval``, ``rms_norm_eps``)
plus ``pattern``, one letter a block.

Every block is **post-norm**, ``h <- h + RMSNorm(Sub(h))``, with one
sub-block chosen by the pattern: ``L`` the gated delta rule with one decay a
head and step, by its *recurrence* over time, token by token; ``*`` causal
softmax attention with an RMSNorm over the whole q and the whole k
projection, by the plain masked softmax, without positional embedding;
``F`` the SwiGLU MLP.  A published layer is ``LF`` or ``*F``.

Departures from the published description, each marked ``# departure``
where it is made:

1. a mixer adds only its held heads' part of ``W_o`` (row-parallel output
   projection without its all-reduce), and the block's post-norm sees that
   partial product;
2. the QK-norm's mean square runs over the channels given (the held heads'),
   not over all heads': the deployment's tensor-parallel group would add
   one number a token for q and one for k;
3. the loss is over the vocabulary rows given (a slice is a smaller
   vocabulary);
4. time and queries are walked in blocks, and blocks, query blocks and the
   recurrence's stretches are recomputed in the backward pass, so that 8192
   positions fit one chip; the mathematics is unchanged.

Assumed, where the config is silent (``fla.layers.GatedDeltaNet``'s and
OLMo 2's conventions; the configuration's ``assumed`` lists the same): q, k
and v each through a causal depthwise convolution of
``linear_conv_kernel_dim`` taps without bias, then SiLU; q and k
L2-normalised a head (eps 1e-6), q scaled by ``linear_key_head_dim **
-0.5``; the log-decay ``-exp(A_log) softplus(W_a x + dt_bias)``, one number
a head; ``beta = sigmoid(W_b x)``, doubled under
``linear_allow_neg_eigval``; a per-head RMSNorm with one weight of
``linear_value_head_dim`` times ``SiLU(W_g x)`` before ``W_o``; attention
heads of ``hidden_size / num_attention_heads``; no rotary embedding
(``rope_theta`` null).

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


# ------------------------------------------------------- gated delta rule
def causal_conv1d(x, w):
    """``y[t, c] = sum_j w[c, j] x[t - (K-1) + j, c]`` with zeros before the
    sequence; ``x`` (T, C), ``w`` (C, K)."""
    k = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(k))


def gdn_recurrence(q, k, v, g, beta):
    """``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t`` a head, from ``S_0 = 0``; ``q`` and ``k`` (T, H,
    dk), ``v`` (T, H, dv), ``g`` and ``beta`` (T, H)."""
    t, h, dk = k.shape
    dv = v.shape[-1]

    def one(state, inp):                                   # state (H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        # (I - b k k^T) S + b k v^T = S + k (b (v - S^T k))^T
        u = b_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint                                        # departure 4
    def stretch(state, inp):
        return lax.scan(one, state, inp)

    n = _blocks(t, SCAN_STRETCH)
    split = lambda a: a.reshape(t // n, n, *a.shape[1:])
    _, o = lax.scan(stretch, jnp.zeros((h, dk, dv), v.dtype),
                    tuple(split(a) for a in (q, k, v, g, beta)))
    return o.reshape(t, h, dv)


def gated_delta_net(x, w, cfg):
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    heads = w["A_log"].shape[0]                            # the heads held
    short = lambda n: silu(causal_conv1d(x @ w[f"{n}_proj.weight"].T,
                                         w[f"{n}_conv_weight"]))
    q = l2_norm(short("q").reshape(-1, heads, dk)) * dk ** -0.5
    k = l2_norm(short("k").reshape(-1, heads, dk))
    v = short("v").reshape(-1, heads, dv)
    g = -jnp.exp(w["A_log"]) * softplus(x @ w["a_proj.weight"].T
                                        + w["dt_bias"])
    beta = sigmoid(x @ w["b_proj.weight"].T)
    if cfg.get("linear_allow_neg_eigval", False):
        beta = 2.0 * beta
    o = gdn_recurrence(q, k, v, g.astype(x.dtype), beta)
    o = rms_norm(o, w["o_norm_weight"], cfg["rms_norm_eps"])
    gate = silu(x @ w["g_proj.weight"].T)
    # departure 1: only the held heads' columns of W_o
    return (o.reshape(-1, heads * dv) * gate) @ w["o_proj.weight"].T


# -------------------------------------------------- QK-normed attention
def attention(x, w, cfg):
    hd = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    # departure 2: the mean square over the channels given.  No rotary
    # embedding: rope_theta is null
    q = rms_norm(x @ w["q_proj.weight"].T, w["q_norm_weight"], eps)
    k = rms_norm(x @ w["k_proj.weight"].T, w["k_norm_weight"], eps)
    q, k = q.reshape(t, -1, hd), k.reshape(t, -1, hd)
    v = (x @ w["v_proj.weight"].T).reshape(t, -1, hd)
    heads, kv = q.shape[1], k.shape[1]                     # the heads held
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    blk = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint                                        # departure 4
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
    # departure 1: only the held heads' columns of W_o
    return o.reshape(t, heads * hd) @ w["o_proj.weight"].T


# -------------------------------------------------------------------- MLP
def mlp(x, w, cfg):
    """``W_down (silu(W_gate x) * W_up x)``; ``gate_up_proj.weight`` holds
    W_gate's rows, then W_up's."""
    gate, up = jnp.split(x @ w["gate_up_proj.weight"].T, 2, axis=-1)
    return (silu(gate) * up) @ w["down_proj.weight"].T


BLOCKS = {"L": gated_delta_net, "*": attention, "F": mlp}


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

        @jax.checkpoint                                    # departure 4
        def block(h, w, kind=kind):
            # the norm is on the sub-block's *output* (OLMo 2's reordering)
            return h + rms_norm(BLOCKS[kind](h, _sub(w, "mixer."), cfg),
                                w["norm.gamma"], eps)
        h = block(h, w)
    return rms_norm(h, params["norm_f.gamma"], eps)


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
