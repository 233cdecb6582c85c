"""Plain reference of the Trinity language model (``model_type`` ``afmoe``:
arcee-ai/Trinity-Mini's ``config.json``): forward, next-token loss and,
through ``jax.grad``, gradients, in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``.

It imports nothing from ``mxnet_tpu``.  It takes the system's weights by
name (``{"layers.0.mixer.q_proj.weight": array, ...}``, as
``net.collect_params()`` names them) and the same share of the deployment,
given as arguments: the *experts* are ``cfg["experts_held"] = (first,
count)`` of the ``num_experts`` the router scores, and the vocabulary slice
is simply the rows given.  ``cfg`` holds the source's keys (``hidden_size``,
``head_dim``, ``sliding_window``, ``rope_theta``, ``route_scale``, ...)
plus ``pattern`` (one letter a block) and ``experts_held``.

The embedding's output is multiplied by ``sqrt(hidden_size)``
(``mup_enabled``).  Every block is ``h <- h + RMSNorm(Sub(RMSNorm(h)))``
(sandwich norms) with one sub-block chosen by the pattern:

- ``W`` sliding and ``*`` full attention by the plain masked softmax, the
  window as a mask: q and k RMS-normed a head over its ``head_dim``
  channels, one weight for q and one for k; on ``W`` blocks only, the rotary
  embedding by its formula (``rotate_half`` pairing: channel c with c +
  head_dim / 2, angle ``position * theta ** (-2 c / head_dim)``) and the
  mask ``0 <= i - j < sliding_window``; on ``*`` blocks no positional
  embedding and the mask ``0 <= i - j``; the output times ``sigmoid(W_g
  x)`` before ``W_o``;
- ``D`` the dense SwiGLU MLP;
- ``E`` routed SwiGLU experts by a *loop over the experts held* plus the
  shared expert: sigmoid scores in float32, top-k of score + bias, the
  chosen scores over their sum (``route_norm``) times ``route_scale``.

Departures from the published description, each marked ``# departure``
where it is made:

1. an expert block adds only the terms of the experts held: the chip's
   share of expert parallelism;
2. the loss is over the vocabulary rows given (a slice is a smaller
   vocabulary);
3. queries are walked in blocks, and blocks and query blocks are recomputed
   in the backward pass, so that 8192 positions fit one chip; the
   mathematics is unchanged.

Assumed, where the config names a key and not its meaning (HF
``transformers``' ``models/afmoe/modeling_afmoe.py``'s conventions; the
configuration's ``assumed`` lists the same): the sandwich block and its
four norms; the per-head QK-norm; rotary embedding on the sliding layers
alone and none on the full ones; the window holds the token itself and the
``sliding_window - 1`` before it; the per-element output gate; the
embedding's factor; ``expert_bias`` used for the choice only; no auxiliary
loss in the step.

``dtype=jnp.bfloat16`` computes the same in the nearest precision below
(arrays stored in bfloat16): the benchmark reads how far that lies from
float32 to set its tolerances between the two.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256       # query rows scored at once


def rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _blocks(n, block):
    """``block`` if it divides ``n``, else ``n``: one block."""
    return block if n % block == 0 else n


# -------------------------------------------------------------- attention
def rotary(x, theta):
    """``x`` (T, H, hd) rotated by its position: the pair (c, c + hd / 2)
    turns by ``t * theta ** (-2 c / hd)``."""
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(x, w, cfg, window=None):
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    q = (x @ w["q_proj.weight"].T).reshape(t, -1, hd)
    k = (x @ w["k_proj.weight"].T).reshape(t, -1, hd)
    v = (x @ w["v_proj.weight"].T).reshape(t, -1, hd)
    q = rms_norm(q, w["q_norm_weight"], eps)        # a head at a time
    k = rms_norm(k, w["k_norm_weight"], eps)
    if window is not None:          # sliding layers rotate, full ones do not
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    heads, kv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, heads // kv, axis=1)          # each kv head serves
    v = jnp.repeat(v, heads // kv, axis=1)          # heads / kv query heads
    blk = _blocks(t, QUERY_BLOCK)

    @jax.checkpoint                                        # departure 3
    def rows(args):
        q_b, pos = args                              # (blk, heads, hd), (blk,)
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        back = pos[:, None] - jnp.arange(t)[None, :]          # i - j
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = lax.map(rows, (q.reshape(t // blk, blk, heads, hd),
                       jnp.arange(t).reshape(t // blk, blk)))
    gate = sigmoid(x @ w["g_proj.weight"].T)
    return (o.reshape(t, heads * hd) * gate) @ w["o_proj.weight"].T


def sliding_attention(x, w, cfg):
    return attention(x, w, cfg, window=cfg["sliding_window"])


# ------------------------------------------------------------------- MLPs
def swiglu(x, gate_up, down):
    """``Down(silu(Gate x) * Up x)``; ``gate_up`` (D, 2F) holds Gate's
    columns, then Up's."""
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (silu(gate) * up) @ down


def dense_mlp(x, w, cfg):
    return swiglu(x, w["gate_up_proj.weight"].T, w["down_proj.weight"].T)


def route(x, w, cfg):
    """Chosen experts ``(T, k)`` and their weights: sigmoid scores in
    float32 over all experts, top-k of score + bias, the scores of the
    chosen divided by their sum, times ``route_scale``."""
    s = jax.nn.sigmoid(x.astype(jnp.float32)
                       @ w["router_weight"].astype(jnp.float32).T)
    _, idx = lax.top_k(s + w["correction_bias"].astype(jnp.float32),
                       cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("route_norm", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, (chosen * cfg["route_scale"]).astype(x.dtype)


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


BLOCKS = {"W": sliding_attention, "*": attention, "D": dense_mlp,
          "E": experts}


# ------------------------------------------------------------------ model
def _sub(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(params, tokens, cfg):
    """Final-norm hidden states ``(T, D)`` of one sequence ``(T,)``."""
    eps = cfg["rms_norm_eps"]
    h = params["embed.weight"][tokens]
    if cfg.get("mup_enabled", True):
        h = h * jnp.asarray(math.sqrt(cfg["hidden_size"]), h.dtype)
    for i, kind in enumerate(cfg["pattern"]):
        w = _sub(params, f"layers.{i}.")

        @jax.checkpoint                                    # departure 3
        def block(h, w, kind=kind):
            y = BLOCKS[kind](rms_norm(h, w["norm.gamma"], eps),
                             _sub(w, "mixer."), cfg)
            return h + rms_norm(y, w["norm_out.gamma"], eps)
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
