"""Olmo-Hybrid language model (``model_type`` ``olmo_hybrid``;
allenai/Olmo-Hybrid-7B's ``config.json``) as Gluon ``HybridBlock``s.

A published layer is a token mixer followed by a dense MLP, each a
**post-norm** residual ``h <- h + RMSNorm(Sub(h))`` (OLMo 2's reordering,
arXiv:2501.00656: the norm is on the sub-layer's *output*).  Here every
such sub-block is one block of the stack, so a pattern string with one
letter a block describes the model (``LFLFLF*F`` is one period of four
layers, ``layer_types`` = three ``linear_attention`` then one
``full_attention``):

- ``L`` gated delta net (:class:`GatedDeltaNetMixer`; Gated DeltaNet,
  arXiv:2412.06464, as ``fla.layers.GatedDeltaNet`` builds it): q, k and v
  through a short causal convolution and SiLU, q and k L2-normalised a head,
  **one log-decay a head and step** ``-exp(A_log) softplus(a x + dt_bias)``,
  ``beta = 2 sigmoid(b x)`` (``linear_allow_neg_eigval``), the gated delta
  rule by chunks (``ops.nn.gdn_chunked``) with keys
  ``linear_key_head_dim`` and values ``linear_value_head_dim`` wide, a
  per-head RMSNorm times ``SiLU(g x)`` (full rank), ``o_proj``;
- ``*`` full attention (:class:`QKNormAttention`): causal softmax attention
  whose q and k pass an RMSNorm over the **whole projection** (every head's
  channels in one statistic), **no positional embedding**
  (``rope_parameters.rope_theta`` is null);
- ``F`` the MLP (:class:`SwiGLUMLP`): ``down(silu(gate x) * up x)`` with
  gate and up fused in one product.

The builder's keyword arguments are the source's keys plus the share of a
tensor-parallel deployment this process holds: ``heads_held = (first,
count)`` of the mixers' heads (linear and full alike) and ``vocab_held``
rows of the vocabulary.  A mixer holds only its heads' rows of the
projections and returns the part of the result those heads give (a
row-parallel ``o_proj`` without its all-reduce); the MLP is whole.  The
QK-norm's mean square crosses the heads, so under a share the group adds
one number a token for q and one for k: the attention core ``psum``s them
over ``axis_name`` where one is given; with none (one chip) the statistic is
over the channels held.

The head, the dense layers and the initialisers are
``models/nemotron_h.py``'s; training enters through
``gluon.Trainer(...).fuse_step(SoftmaxCrossEntropyLoss())`` as there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import materialize, recompute
from ..gluon.parameter import Parameter
from ..numpy import _call
from ..ops import nn as _nn
from .nemotron_h import (_attention_core, _dense, _InverseSoftplusDt,
                         _LMHead, _LogUniform)
from .solar_open2 import L2_EPS, _held

__all__ = ["GatedDeltaNetMixer", "QKNormAttention", "SwiGLUMLP",
           "PostNormLayer", "OlmoHybridModel", "olmo_hybrid",
           "olmo_hybrid_tiny", "block_pattern"]

KINDS = {"linear_attention": "L", "full_attention": "*"}


def _gdn_core(q, k, v, a, b, gate, q_conv, k_conv, v_conv, dt_bias, a_log,
              norm_w, *, heads, dk, dv, chunk, neg_eigval, eps):
    """From the projections to the gated, normed output (B, T, heads * dv)
    that ``o_proj`` takes."""
    B, T, _ = q.shape
    def short(x, w, width):
        return jax.nn.silu(_nn.causal_conv1d(x, w)).reshape(B, T, heads, width)
    with jax.named_scope("gdn.conv"):
        q, k, v = short(q, q_conv, dk), short(k, k_conv, dk), \
            short(v, v_conv, dv)
    with jax.named_scope("gdn.gate"):
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)    # (B, T, heads)
        beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    with jax.named_scope("gdn.scan"):
        o = _nn.gdn_chunked(
            _nn.l2_normalize(q, eps=L2_EPS) * dk ** -0.5,
            _nn.l2_normalize(k, eps=L2_EPS), v, g, beta, chunk=chunk)
    with jax.named_scope("gdn.norm"):
        o = _nn.rms_norm(o, norm_w, eps=eps).reshape(B, T, heads * dv)
        return o * jax.nn.silu(gate)


def _whole_rms_norm(x, w, eps, axis_name):
    """RMSNorm over the last axis of a projection of which this process may
    hold a slice: with ``axis_name`` the sum of squares and the number of
    channels are added over that axis (one number a token)."""
    xf = x.astype(jnp.float32)
    ss, n = jnp.sum(xf * xf, axis=-1, keepdims=True), x.shape[-1]
    if axis_name is not None:
        ss, n = lax.psum(ss, axis_name), lax.psum(n, axis_name)
    return (xf * lax.rsqrt(ss / n + eps)).astype(x.dtype) * w


def _qknorm_attention_core(q, k, v, q_w, k_w, *, heads, kv, hd, eps,
                           axis_name=None):
    with jax.named_scope("attn.qknorm"):
        q = _whole_rms_norm(q, q_w, eps, axis_name)
        k = _whole_rms_norm(k, k_w, eps, axis_name)
    return _attention_core(q, k, v, heads=heads, kv=kv, hd=hd)


class GatedDeltaNetMixer(nn.HybridBlock):
    """The gated delta net over the heads ``heads_held`` of ``num_heads``:
    the head norm's weight is replicated, everything else is the held
    heads' rows (``o_proj``: their columns)."""

    def __init__(self, hidden_size, num_heads, key_head_dim, value_head_dim,
                 heads_held=None, conv_kernel=4, chunk_size=64,
                 allow_neg_eigval=True, eps=1e-6, out_sigma=0.02):
        super().__init__()
        _, held = _held(heads_held, num_heads, "linear heads")
        self._sizes = dict(heads=held, dk=key_head_dim, dv=value_head_dim,
                           chunk=chunk_size, neg_eigval=allow_neg_eigval,
                           eps=eps)
        for name, width in (("q", key_head_dim), ("k", key_head_dim),
                            ("v", value_head_dim)):
            setattr(self, f"{name}_proj", _dense(held * width, hidden_size))
            setattr(self, f"{name}_conv_weight", Parameter(
                f"{name}_conv_weight", shape=(held * width, conv_kernel),
                init=init.Uniform(1.0 / math.sqrt(conv_kernel))))
        self.a_proj = _dense(held, hidden_size)
        self.b_proj = _dense(held, hidden_size)
        self.g_proj = _dense(held * value_head_dim, hidden_size)
        self.dt_bias = Parameter(
            "dt_bias", shape=(held,), wd_mult=0.0,
            init=_InverseSoftplusDt(0.001, 0.1, 1e-4))
        self.A_log = Parameter("A_log", shape=(held,), wd_mult=0.0,
                               init=_LogUniform(1.0, 16.0))
        self.o_norm_weight = Parameter(
            "o_norm_weight", shape=(value_head_dim,), init=init.One())
        self.o_proj = _dense(hidden_size, held * value_head_dim, out_sigma)

    def forward(self, x):
        with jax.named_scope("gdn.gate"):
            a, b, gate = self.a_proj(x), self.b_proj(x), self.g_proj(x)
        return self.o_proj(_call(
            _gdn_core, self.q_proj(x), self.k_proj(x), self.v_proj(x), a, b,
            gate, self.q_conv_weight.data(), self.k_conv_weight.data(),
            self.v_conv_weight.data(), self.dt_bias.data(), self.A_log.data(),
            self.o_norm_weight.data(), **self._sizes))


class QKNormAttention(nn.HybridBlock):
    """Causal softmax attention without positional embedding over the query
    heads ``heads_held`` (whole groups: a key-value head comes with the query
    heads it serves), q and k normed over the whole projection.
    ``axis_name`` names the tensor-parallel axis the other shares of the
    heads lie on (``shard_map``, ``vmap``): the norm's statistic is then
    summed over it."""

    def __init__(self, hidden_size, num_attention_heads, num_key_value_heads,
                 head_dim, heads_held=None, eps=1e-6, out_sigma=0.02,
                 axis_name=None):
        super().__init__()
        first, held = _held(heads_held, num_attention_heads,
                            "attention heads")
        group = num_attention_heads // num_key_value_heads
        if first % group or held % group:
            raise ValueError(f"attention heads held {heads_held} cut a "
                             f"group of {group} query heads")
        kv = held // group
        self._sizes = dict(heads=held, kv=kv, hd=head_dim, eps=eps,
                           axis_name=axis_name)
        self.q_proj = _dense(held * head_dim, hidden_size)
        self.k_proj = _dense(kv * head_dim, hidden_size)
        self.v_proj = _dense(kv * head_dim, hidden_size)
        self.q_norm_weight = Parameter(
            "q_norm_weight", shape=(held * head_dim,), init=init.One())
        self.k_norm_weight = Parameter(
            "k_norm_weight", shape=(kv * head_dim,), init=init.One())
        self.o_proj = _dense(hidden_size, held * head_dim, out_sigma)

    def forward(self, x):
        return self.o_proj(_call(
            _qknorm_attention_core, self.q_proj(x), self.k_proj(x),
            self.v_proj(x), self.q_norm_weight.data(),
            self.k_norm_weight.data(), **self._sizes))


class SwiGLUMLP(nn.HybridBlock):
    """``down(silu(gate x) * up x)``; ``gate_up_proj`` holds the gate's
    rows, then the up projection's: one product."""

    def __init__(self, hidden_size, intermediate_size, out_sigma=0.02):
        super().__init__()
        self.gate_up_proj = _dense(2 * intermediate_size, hidden_size)
        self.down_proj = _dense(hidden_size, intermediate_size, out_sigma)

    def forward(self, x):
        with jax.named_scope("mlp.up"):
            h = self.gate_up_proj(x)
        with jax.named_scope("mlp.act"):
            h = materialize(_call(_nn.swiglu, h), "mlp_act")
        with jax.named_scope("mlp.down"):
            return self.down_proj(h)


class PostNormLayer(nn.HybridBlock):
    """``h + norm(mixer(h))``: the norm on the sub-layer's output.
    Recomputed in the backward pass of a traced training program like
    ``NemotronHLayer``: only the layer's input is kept."""

    def __init__(self, mixer, hidden_size, eps=1e-6):
        super().__init__()
        self.mixer = mixer
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)

    def forward(self, h):
        return recompute(lambda h: h + self.norm(
            materialize(self.mixer(h), "sublayer_out")), h)


def block_pattern(layer_types):
    """One letter a block: a ``linear_attention`` layer is ``LF``, a
    ``full_attention`` layer ``*F``."""
    try:
        return "".join(KINDS[t] + "F" for t in layer_types)
    except KeyError as e:
        raise ValueError(f"unknown layer type {e.args[0]!r}: "
                         f"{' or '.join(KINDS)}") from None


class OlmoHybridModel(nn.HybridBlock):
    """Embedding, the blocks of ``block_pattern(layer_types)``, final
    RMSNorm and the untied head: tokens (B, T) int -> logits (B, T, rows
    held)."""

    def __init__(self, hidden_size=3840, intermediate_size=11008,
                 num_hidden_layers=32,
                 layer_types=("linear_attention",) * 3
                 + ("full_attention",), vocab_size=100352, vocab_held=None,
                 rms_norm_eps=1e-6, hidden_act="silu",
                 tie_word_embeddings=False, heads_held=None,
                 # full attention
                 num_attention_heads=30, num_key_value_heads=30,
                 head_dim=None, attention_bias=False, rope_parameters=None,
                 # gated delta net
                 linear_num_key_heads=30, linear_num_value_heads=30,
                 linear_key_head_dim=96, linear_value_head_dim=192,
                 linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                 linear_chunk_size=64):
        super().__init__()
        rope = (rope_parameters or {}).get("rope_theta")
        if hidden_act != "silu" or attention_bias or tie_word_embeddings \
                or rope is not None \
                or linear_num_key_heads != linear_num_value_heads:
            raise ValueError(
                "olmo_hybrid builds SiLU MLPs, projections without bias, an "
                "untied head, attention without rotary embedding and as "
                "many linear value heads as key heads (hidden_act silu, "
                "attention_bias false, tie_word_embeddings false, "
                "rope_parameters.rope_theta null, linear_num_value_heads = "
                "linear_num_key_heads)")
        layer_types = tuple(layer_types)
        if len(layer_types) < num_hidden_layers:      # the period, repeated
            layer_types = (layer_types * num_hidden_layers)[:num_hidden_layers]
        if len(layer_types) != num_hidden_layers:
            raise ValueError(f"num_hidden_layers {num_hidden_layers} but "
                             f"{len(layer_types)} layer_types")
        first, rows = _held(vocab_held, vocab_size, "vocabulary rows")
        self.pattern = block_pattern(layer_types)
        self.vocab_held = (first, rows)
        head_dim = head_dim or hidden_size // num_attention_heads
        # the projections that write into the residual stream start
        # 1/sqrt(blocks) smaller, as Nemotron-H's do
        out_sigma = 0.02 / math.sqrt(len(self.pattern))
        eps = rms_norm_eps
        self.embed = nn.Embedding(rows, hidden_size,
                                  weight_initializer=init.Normal(0.02))
        self.layers = nn.HybridSequential()
        for kind in self.pattern:
            if kind == "L":
                mixer = GatedDeltaNetMixer(
                    hidden_size, linear_num_key_heads, linear_key_head_dim,
                    linear_value_head_dim, heads_held, linear_conv_kernel_dim,
                    linear_chunk_size, linear_allow_neg_eigval, eps,
                    out_sigma)
            elif kind == "*":
                mixer = QKNormAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, heads_held, eps, out_sigma)
            else:
                mixer = SwiGLUMLP(hidden_size, intermediate_size, out_sigma)
            self.layers.add(PostNormLayer(mixer, hidden_size, eps))
        self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.head = _LMHead(rows, hidden_size)

    def forward(self, tokens):
        return self.head(self.norm_f(self.layers(self.embed(tokens))))


def olmo_hybrid(**kwargs):
    """An Olmo-Hybrid model from the source's keys (``config.json`` of
    ``allenai/Olmo-Hybrid-7B``: the defaults are its sizes) plus the share:
    ``heads_held``, ``vocab_held``."""
    return OlmoHybridModel(**kwargs)


# ``inspect.signature(olmo_hybrid)`` names the keys the builder takes
olmo_hybrid.__wrapped__ = OlmoHybridModel


def olmo_hybrid_tiny(num_hidden_layers=4, **kwargs):
    """Every mechanism at a size for CPU tests: one period ``LFLFLF*F``, 4
    heads everywhere, linear keys 12 and values 24 wide in chunks of 16
    steps, attention heads of 8, an MLP of 48."""
    cfg = dict(hidden_size=32, intermediate_size=48,
               num_hidden_layers=num_hidden_layers, vocab_size=64,
               num_attention_heads=4, num_key_value_heads=4,
               linear_num_key_heads=4, linear_num_value_heads=4,
               linear_key_head_dim=12, linear_value_head_dim=24,
               linear_chunk_size=16)
    cfg.update(kwargs)
    return OlmoHybridModel(**cfg)
