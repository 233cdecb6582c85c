"""Trinity language model (``model_type`` ``afmoe``; arcee-ai/Trinity-Mini's
``config.json``, 26B-A3B) as Gluon ``HybridBlock``s.

A published layer is an attention sub-layer followed by an MLP sub-layer,
each between two RMSNorms: ``h <- h + Norm(Sub(Norm(h)))`` (sandwich norms).
Here every sub-layer is one block of the stack, so a pattern string with
one letter a block describes the model (``WDWEWEWE*E``: a leading dense
layer, then three sliding and one full layer with experts):

- ``W`` sliding-window attention and ``*`` full attention
  (:class:`TrinityAttention`): grouped-query softmax attention, q and k
  RMS-normed **per head** (one weight of ``head_dim`` each), the output
  multiplied by ``sigmoid(W_g x)`` before ``o_proj``.  A sliding layer
  rotates q and k (rotary embedding over the whole head, ``rotate_half``
  pairing) and sees the last ``sliding_window`` keys, itself included; a
  full layer has **no positional embedding** and sees every earlier key;
- ``D`` the dense SwiGLU MLP of the ``num_dense_layers`` leading layers
  (``olmo_hybrid.SwiGLUMLP``);
- ``E`` routed SwiGLU experts plus the shared one (``solar_open2.SwiGLUMoE``
  -> ``parallel.moe.moe_topk_held``): sigmoid scores, top-k of score +
  ``expert_bias``, the chosen scores divided by their sum (``route_norm``)
  times ``route_scale``.

The embedding's output is scaled by ``sqrt(hidden_size)`` (``mup_enabled``).
What the config's keys do not say is HF ``transformers``'
``modeling_afmoe.py``'s convention; ``chipbench/configs/
trinity-mini-26b-train-ep8.json`` lists each under ``assumed``.

The builder's keyword arguments are the source's keys plus the share of an
expert-parallel deployment this process holds: ``experts_held = (first,
count)`` of the ``num_experts`` the router scores and ``vocab_held`` rows
of the vocabulary (``models/solar_open2.py``).  The expert block, the
output gate, the dense MLP, the head and the initialisers are the other
three models'; training enters through
``gluon.Trainer(...).fuse_step(SoftmaxCrossEntropyLoss())`` as there.
"""
from __future__ import annotations

import math

import jax

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import materialize, recompute
from ..gluon.parameter import Parameter
from ..numpy import _call
from ..ops import nn as _nn
from .nemotron_h import _dense, _LMHead
from .olmo_hybrid import SwiGLUMLP
from .solar_open2 import SwiGLUMoE, _gated_attention_core, _held

__all__ = ["TrinityAttention", "SandwichLayer", "TrinityModel", "trinity",
           "trinity_tiny", "block_pattern"]

KINDS = {"sliding_attention": "W", "full_attention": "*"}


def _rope_tables(tokens, *, dim, theta):
    with jax.named_scope("attn.rope"):
        return _nn.rope_tables(jax.numpy.arange(tokens.shape[1]), dim, theta)


def _trinity_attention_core(q, k, v, gate, q_w, k_w, *tables, heads, kv, hd,
                            eps, window):
    """From the projections to the gated output (B, T, heads * hd) that
    ``o_proj`` takes; ``tables`` is ``rope_tables``' pair or nothing."""
    B, T, _ = q.shape
    with jax.named_scope("attn.qknorm"):
        q = _nn.rms_norm(q.reshape(B, T, heads, hd), q_w, eps=eps)
        k = _nn.rms_norm(k.reshape(B, T, kv, hd), k_w, eps=eps)
    if tables:
        with jax.named_scope("attn.rope"):
            q, k = _nn.rope(q, *tables), _nn.rope(k, *tables)
    return _gated_attention_core(
        q.reshape(B, T, heads * hd), k.reshape(B, T, kv * hd), v, gate,
        heads=heads, kv=kv, hd=hd, window=window)


class TrinityAttention(nn.HybridBlock):
    """Gated grouped-query attention with a per-head QK-norm.  With
    ``window`` it is a sliding layer: ``forward`` takes the rotary tables
    and a query sees its own key and the ``window - 1`` before it."""

    def __init__(self, hidden_size, num_attention_heads, num_key_value_heads,
                 head_dim, window=None, eps=1e-5, out_sigma=0.02):
        super().__init__()
        if num_attention_heads % num_key_value_heads:
            raise ValueError(f"{num_attention_heads} query heads over "
                             f"{num_key_value_heads} key-value heads")
        self._sizes = dict(heads=num_attention_heads, kv=num_key_value_heads,
                           hd=head_dim, eps=eps, window=window)
        self.q_proj = _dense(num_attention_heads * head_dim, hidden_size)
        self.k_proj = _dense(num_key_value_heads * head_dim, hidden_size)
        self.v_proj = _dense(num_key_value_heads * head_dim, hidden_size)
        self.g_proj = _dense(num_attention_heads * head_dim, hidden_size)
        self.q_norm_weight = Parameter("q_norm_weight", shape=(head_dim,),
                                       init=init.One())
        self.k_norm_weight = Parameter("k_norm_weight", shape=(head_dim,),
                                       init=init.One())
        self.o_proj = _dense(hidden_size, num_attention_heads * head_dim,
                             out_sigma)

    def forward(self, x, *tables):
        return self.o_proj(_call(
            _trinity_attention_core, self.q_proj(x), self.k_proj(x),
            self.v_proj(x), self.g_proj(x), self.q_norm_weight.data(),
            self.k_norm_weight.data(), *tables, **self._sizes))


class SandwichLayer(nn.HybridBlock):
    """``h + norm_out(mixer(norm(h)))``: a norm on the sub-layer's input and
    one on its output.  Recomputed in the backward pass of a traced training
    program like ``NemotronHLayer``; the sub-layer's output is stored once
    like ``olmo_hybrid.PostNormLayer``'s.  Further arguments (the rotary
    tables) go to the mixer."""

    def __init__(self, mixer, hidden_size, eps=1e-5):
        super().__init__()
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.mixer = mixer
        self.norm_out = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)

    def forward(self, h, *args):
        return recompute(lambda h, *args: h + self.norm_out(materialize(
            self.mixer(self.norm(h), *args), "sublayer_out")), h, *args)


def block_pattern(layer_types, num_dense_layers):
    """Two letters a layer: ``W`` or ``*`` by its type, then ``D`` for the
    ``num_dense_layers`` leading layers and ``E`` after them."""
    try:
        return "".join(KINDS[t] + ("D" if i < num_dense_layers else "E")
                       for i, t in enumerate(layer_types))
    except KeyError as e:
        raise ValueError(f"unknown layer type {e.args[0]!r}: "
                         f"{' or '.join(KINDS)}") from None


class TrinityModel(nn.HybridBlock):
    """Embedding times ``sqrt(hidden_size)``, the blocks of
    ``block_pattern(layer_types, num_dense_layers)``, final RMSNorm and the
    untied head: tokens (B, T) int -> logits (B, T, rows held)."""

    def __init__(self, hidden_size=2048, intermediate_size=6144,
                 moe_intermediate_size=1024, num_hidden_layers=32,
                 layer_types=("sliding_attention",) * 3
                 + ("full_attention",), num_dense_layers=2,
                 vocab_size=200192, vocab_held=None, rms_norm_eps=1e-5,
                 hidden_act="silu", tie_word_embeddings=False,
                 mup_enabled=True,
                 # attention
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 sliding_window=2048, rope_theta=10000.0, rope_scaling=None,
                 # experts
                 num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
                 experts_held=None, score_func="sigmoid", route_norm=True,
                 route_scale=2.826, n_group=1, topk_group=1):
        super().__init__()
        refused = {
            "n_group": n_group != 1, "topk_group": topk_group != 1,
            "rope_scaling": rope_scaling is not None,
            "score_func": score_func != "sigmoid",
            "hidden_act": hidden_act != "silu",
            "tie_word_embeddings": bool(tie_word_embeddings),
        }
        if any(refused.values()):
            raise ValueError(
                "trinity builds a sigmoid router without expert groups, "
                "unscaled rotary embedding, SiLU MLPs and an untied head; "
                "not implemented: "
                + ", ".join(k for k, bad in refused.items() if bad))
        layer_types = tuple(layer_types)
        if len(layer_types) < num_hidden_layers:      # the period, repeated
            layer_types = (layer_types * num_hidden_layers)[:num_hidden_layers]
        if len(layer_types) != num_hidden_layers:
            raise ValueError(f"num_hidden_layers {num_hidden_layers} but "
                             f"{len(layer_types)} layer_types")
        first, rows = _held(vocab_held, vocab_size, "vocabulary rows")
        self.pattern = block_pattern(layer_types, num_dense_layers)
        self.vocab_held = (first, rows)
        self._embed_scale = math.sqrt(hidden_size) if mup_enabled else 1.0
        self._rope = dict(dim=head_dim, theta=float(rope_theta))
        # the projections that write into the residual stream start
        # 1/sqrt(blocks) smaller, as Nemotron-H's do
        out_sigma = 0.02 / math.sqrt(len(self.pattern))
        eps = rms_norm_eps
        self.embed = nn.Embedding(rows, hidden_size,
                                  weight_initializer=init.Normal(0.02))
        self.layers = nn.HybridSequential()
        for kind in self.pattern:
            if kind in "W*":
                mixer = TrinityAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, sliding_window if kind == "W" else None, eps,
                    out_sigma)
            elif kind == "D":
                mixer = SwiGLUMLP(hidden_size, intermediate_size, out_sigma)
            else:
                mixer = SwiGLUMoE(
                    hidden_size, moe_intermediate_size, num_experts,
                    num_experts_per_tok, experts_held, route_scale,
                    route_norm, num_shared_experts, out_sigma)
            self.layers.add(SandwichLayer(mixer, hidden_size, eps))
        self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.head = _LMHead(rows, hidden_size)

    def forward(self, tokens):
        h = self.embed(tokens) * self._embed_scale
        # the tables are made once a step; only the sliding layers rotate
        tables = _call(_rope_tables, tokens, **self._rope) \
            if "W" in self.pattern else ()
        with self.layers._named_scope():
            for kind, layer in zip(self.pattern, self.layers):
                h = layer(h, *tables) if kind == "W" else layer(h)
        return self.head(self.norm_f(h))


def trinity(**kwargs):
    """A Trinity model from the source's keys (``config.json`` of
    ``arcee-ai/Trinity-Mini``: the defaults are its sizes) plus the share:
    ``experts_held``, ``vocab_held``."""
    return TrinityModel(**kwargs)


# ``inspect.signature(trinity)`` names the keys the builder takes
trinity.__wrapped__ = TrinityModel


def trinity_tiny(num_hidden_layers=5, **kwargs):
    """Every mechanism at a size for CPU tests: ``WDWEWEWE*E``, attention 4
    / 2 heads of 8 channels with a window of 6, a dense MLP of 48, 16
    experts of 16 of which 4 are held, top-3."""
    cfg = dict(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_hidden_layers=num_hidden_layers,
               layer_types=("sliding_attention",) * (num_hidden_layers - 1)
               + ("full_attention",), num_dense_layers=1, vocab_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=8,
               sliding_window=6, num_experts=16, num_experts_per_tok=3,
               experts_held=(4, 4))
    cfg.update(kwargs)
    return TrinityModel(**cfg)
