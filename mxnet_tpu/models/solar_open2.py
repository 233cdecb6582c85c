"""Solar-Open2 language model (``model_type`` ``solar_open2``;
upstage/Solar-Open2-250B's ``config.json``) as Gluon ``HybridBlock``s.

A published layer is a token mixer followed by an expert block, each a
pre-norm residual ``h <- h + Sub(RMSNorm(h))``.  Here every such sub-block
is one block of the stack, so a pattern string with one letter a block
describes the model (``*EKEKEKE`` is one period of four layers):

- ``*`` gated attention (:class:`GatedGQAttention`; the layers of
  ``gqa_layers``): causal grouped-query softmax attention, **no positional
  embedding** (``use_rope`` false), its output multiplied by
  ``sigmoid(W_gate x)`` before ``o_proj`` (``use_gqa_gate``);
- ``K`` Kimi delta attention (:class:`KDAMixer`; Kimi Linear,
  arXiv:2510.26692): q, k and v through a short causal convolution and SiLU,
  q and k L2-normalised a head, a per-channel log-decay ``-exp(A_log)
  softplus(f_b f_a x + dt_bias)``, ``beta = 2 sigmoid(b x)``
  (``kda_allow_neg_eigval``), the gated delta rule by chunks
  (``ops.nn.kda_chunked``), a per-head RMSNorm times a low-rank sigmoid gate,
  ``o_proj``;
- ``E`` routed experts (:class:`SwiGLUMoE`): sigmoid router over all
  experts, top-k, dropless, SwiGLU experts with a fused ``[gate | up]``
  projection, plus one shared expert (``parallel.moe.moe_topk_held``).

The builder's keyword arguments are the source's keys plus the share of a
tensor- and expert-parallel deployment this process holds: ``heads_held =
(first, count)`` of the ``num_attention_heads`` query heads (with their
key-value heads), ``kda_heads_held`` of the ``linear_attn_config``'s heads,
``experts_held`` of the ``n_routed_experts`` the router scores and
``vocab_held`` rows of the vocabulary.  A mixer holds only its heads'
rows of the projections and returns the part of the result those heads give
(a row-parallel ``o_proj`` without its all-reduce), as an expert block adds
only its own experts' terms: with every share's part added, the whole layer
results.  On one chip a share runs without its exchange.

The residual block, the head and the initialisers are
``models/nemotron_h.py``'s; training enters through
``gluon.Trainer(...).fuse_step(SoftmaxCrossEntropyLoss())`` as there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init
from .. import tape
from ..gluon import nn
from ..gluon.parameter import Parameter
from ..numpy import _call
from ..ops import nn as _nn
from ..parallel.moe import moe_topk_held
from .nemotron_h import (NemotronHLayer, _attention_core, _dense,
                         _InverseSoftplusDt, _LMHead, _LogUniform)

__all__ = ["KDAMixer", "GatedGQAttention", "SwiGLUMoE", "SolarOpen2Model",
           "solar_open2", "solar_open2_tiny", "block_pattern"]

L2_EPS = 1e-6


def _held(held, total, what):
    first, count = held or (0, total)
    if not (0 <= first and count > 0 and first + count <= total):
        raise ValueError(f"{what} held {held} of {total}")
    return int(first), int(count)


def _kda_core(q, k, v, f, b, gate, q_conv, k_conv, v_conv, dt_bias, a_log,
              norm_w, *, heads, hd, chunk, neg_eigval, eps):
    """From the projections to the gated, normed output (B, T, heads * hd)
    that ``o_proj`` takes."""
    B, T, _ = q.shape
    by_head = lambda a: a.reshape(B, T, heads, hd)
    with jax.named_scope("kda.conv"):
        q, k, v = (by_head(jax.nn.silu(_nn.causal_conv1d(a, w)))
                   for a, w in ((q, q_conv), (k, k_conv), (v, v_conv)))
    with jax.named_scope("kda.gate"):
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(by_head(f + dt_bias))
        beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    with jax.named_scope("kda.scan"):
        o = _nn.kda_chunked(
            _nn.l2_normalize(q, eps=L2_EPS) * hd ** -0.5,
            _nn.l2_normalize(k, eps=L2_EPS), v, g, beta, chunk=chunk)
    with jax.named_scope("kda.norm"):
        o = _nn.rms_norm(o, norm_w, eps=eps).reshape(B, T, heads * hd)
        return o * jax.nn.sigmoid(gate)


def _gated_attention_core(q, k, v, gate, *, heads, kv, hd, window=None):
    o = _attention_core(q, k, v, heads=heads, kv=kv, hd=hd, window=window)
    with jax.named_scope("attn.gate"):
        return o * jax.nn.sigmoid(gate)


# A held expert's dense slot, in even shares (tokens * top_k / experts) of
# whole 256-row tiles.  An untrained router over 320 experts sends a held
# expert up to a fifth of the tokens for many steps of a run: at 7.5 shares
# a quarter of one seed's steps took a further slot and its step read 4 %
# longer than the other seeds'; at 10 shares none of 13 runs did (PERF.md
# section 6, PR 34).  ``moe_topk_held``'s own default is 6 (Nemotron's).
SLOT_SHARES = 10


def _swiglu_experts_core(x, router_w, bias, up, down, *, held, top_k,
                         scaling, norm_topk):
    B, T, D = x.shape
    rows = -(-SLOT_SHARES * B * T * top_k // (router_w.shape[0] * 256)) * 256
    y, load = moe_topk_held(x.reshape(B * T, D), router_w, bias, up, down,
                            held, top_k, scaling, norm_topk, slot_rows=rows,
                            act=_nn.swiglu)
    return y.reshape(B, T, D), load


class KDAMixer(nn.HybridBlock):
    """Kimi delta attention over the heads ``heads_held`` of ``num_heads``:
    the low-rank gates' down projections and the head norm are replicated,
    everything else is the held heads' rows."""

    def __init__(self, hidden_size, num_heads, head_dim, heads_held=None,
                 short_conv_kernel_size=4, chunk_size=64,
                 allow_neg_eigval=True, eps=1e-5, out_sigma=0.02):
        super().__init__()
        _, held = _held(heads_held, num_heads, "KDA heads")
        rank = head_dim                 # the low-rank gates (assumed)
        inner = held * head_dim
        self._sizes = dict(heads=held, hd=head_dim, chunk=chunk_size,
                           neg_eigval=allow_neg_eigval, eps=eps)
        for name in "qkv":
            setattr(self, f"{name}_proj", _dense(inner, hidden_size))
            setattr(self, f"{name}_conv_weight", Parameter(
                f"{name}_conv_weight", shape=(inner, short_conv_kernel_size),
                init=init.Uniform(1.0 / math.sqrt(short_conv_kernel_size))))
        self.f_a = _dense(rank, hidden_size)
        self.f_b = _dense(inner, rank)
        self.b_proj = _dense(held, hidden_size)
        self.g_a = _dense(rank, hidden_size)
        self.g_b = _dense(inner, rank)
        self.dt_bias = Parameter(
            "dt_bias", shape=(inner,), wd_mult=0.0,
            init=_InverseSoftplusDt(0.001, 0.1, 1e-4))
        self.A_log = Parameter("A_log", shape=(held,), wd_mult=0.0,
                               init=_LogUniform(1.0, 16.0))
        self.o_norm_weight = Parameter("o_norm_weight", shape=(head_dim,),
                                       init=init.One())
        self.o_proj = _dense(hidden_size, inner, out_sigma)

    def forward(self, x):
        with jax.named_scope("kda.gate"):
            f = self.f_b(self.f_a(x))
            b = self.b_proj(x)
            gate = self.g_b(self.g_a(x))
        return self.o_proj(_call(
            _kda_core, self.q_proj(x), self.k_proj(x), self.v_proj(x), f, b,
            gate, self.q_conv_weight.data(), self.k_conv_weight.data(),
            self.v_conv_weight.data(), self.dt_bias.data(), self.A_log.data(),
            self.o_norm_weight.data(), **self._sizes))


class GatedGQAttention(nn.HybridBlock):
    """Causal grouped-query attention without positional embedding and with
    an output gate, over the query heads ``heads_held`` (whole groups: a
    key-value head comes with the query heads it serves)."""

    def __init__(self, hidden_size, num_attention_heads, num_key_value_heads,
                 head_dim, heads_held=None, out_sigma=0.02):
        super().__init__()
        first, held = _held(heads_held, num_attention_heads,
                            "attention heads")
        group = num_attention_heads // num_key_value_heads
        if first % group or held % group:
            raise ValueError(f"attention heads held {heads_held} cut a "
                             f"group of {group} query heads")
        self._sizes = dict(heads=held, kv=held // group, hd=head_dim)
        self.q_proj = _dense(held * head_dim, hidden_size)
        self.k_proj = _dense(held // group * head_dim, hidden_size)
        self.v_proj = _dense(held // group * head_dim, hidden_size)
        self.gate_proj = _dense(held * head_dim, hidden_size)
        self.o_proj = _dense(hidden_size, held * head_dim, out_sigma)

    def forward(self, x):
        return self.o_proj(_call(
            _gated_attention_core, self.q_proj(x), self.k_proj(x),
            self.v_proj(x), self.gate_proj(x), **self._sizes))


class SwiGLUMoE(nn.HybridBlock):
    """Routed SwiGLU experts of one expert-parallel share plus the shared
    expert.  ``experts_up`` holds ``[gate | up]`` of every held expert, one
    product; ``load`` and ``load_total`` are aux state that
    ``TrainerFusedStep.sync`` publishes as the ``moe.*`` counters."""

    def __init__(self, hidden_size, moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, experts_held=None,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 n_shared_experts=1, out_sigma=0.02):
        super().__init__()
        self._held = _held(experts_held, n_routed_experts, "experts")
        count, width = self._held[1], moe_intermediate_size
        self._route = dict(top_k=num_experts_per_tok,
                           scaling=routed_scaling_factor,
                           norm_topk=norm_topk_prob)
        self.router_weight = Parameter(
            "router_weight", shape=(n_routed_experts, hidden_size),
            init=init.Normal(0.02))
        # assumed: a correction bias moved by a load balancer, not by the
        # optimizer; zero here
        self.correction_bias = Parameter(
            "correction_bias", shape=(n_routed_experts,), init=init.Zero(),
            grad_req="null")
        self.experts_up = Parameter(
            "experts_up", shape=(count, hidden_size, 2 * width),
            init=init.Normal(0.02))
        self.experts_down = Parameter(
            "experts_down", shape=(count, width, hidden_size),
            init=init.Normal(out_sigma))
        self.shared_up = _dense(2 * n_shared_experts * width, hidden_size)
        self.shared_down = _dense(hidden_size, n_shared_experts * width,
                                  out_sigma)
        for name in ("load", "load_total"):
            p = Parameter(name, shape=(n_routed_experts,), dtype="int32",
                          init=init.Zero(), grad_req="null")
            p.publish = ("moe.load", self._held, name)
            setattr(self, name, p)

    def forward(self, x):
        y, load = _call(_swiglu_experts_core, x, self.router_weight.data(),
                        self.correction_bias.data(), self.experts_up.data(),
                        self.experts_down.data(), held=self._held,
                        **self._route)
        if tape.is_training():
            self.load.set_data(load)
            self.load_total.set_data(self.load_total.data() + load)
        with jax.named_scope("moe.shared"):
            shared = self.shared_down(
                _call(_nn.swiglu, self.shared_up(x)))
        return y + shared


def block_pattern(num_hidden_layers, gqa_layers):
    """One letter a block: layer ``i`` is ``*E`` if ``i`` is one of
    ``gqa_layers``, else ``KE``."""
    return "".join(("*" if i in gqa_layers else "K") + "E"
                   for i in range(num_hidden_layers))


class SolarOpen2Model(nn.HybridBlock):
    """Embedding, the blocks of ``block_pattern(num_hidden_layers,
    gqa_layers)``, final RMSNorm and the untied head: tokens (B, T) int ->
    logits (B, T, rows held)."""

    def __init__(self, hidden_size=4096, num_hidden_layers=48,
                 gqa_layers=tuple(range(0, 48, 4)), vocab_size=196608,
                 vocab_held=None, rms_norm_eps=1e-5, first_k_dense_replace=0,
                 # gated attention
                 num_attention_heads=64, num_key_value_heads=8, head_dim=128,
                 heads_held=None, use_rope=False, use_gqa_gate=True,
                 # KDA
                 linear_attn_config=None, kda_heads_held=None,
                 kda_use_full_proj=False, kda_allow_neg_eigval=True,
                 kda_chunk_size=64,
                 # experts
                 moe_intermediate_size=1280, n_routed_experts=320,
                 n_shared_experts=1, num_experts_per_tok=8,
                 experts_held=None, routed_scaling_factor=1.0,
                 norm_topk_prob=True):
        super().__init__()
        if use_rope or not use_gqa_gate or kda_use_full_proj \
                or first_k_dense_replace:
            raise ValueError(
                "solar_open2 builds NoPE attention with an output gate, "
                "low-rank KDA gates and no leading dense layer (use_rope "
                "false, use_gqa_gate true, kda_use_full_proj false, "
                "first_k_dense_replace 0)")
        lin = dict(linear_attn_config or
                   {"short_conv_kernel_size": 4, "head_dim": 128,
                    "num_heads": 64, "num_kv_heads": None})
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise ValueError("KDA's value heads are its heads "
                             "(num_kv_heads null)")
        first, rows = _held(vocab_held, vocab_size, "vocabulary rows")
        self.pattern = block_pattern(num_hidden_layers, tuple(gqa_layers))
        self.vocab_held = (first, rows)
        # the projections that write into the residual stream start
        # 1/sqrt(blocks) smaller, as Nemotron-H's do
        out_sigma = 0.02 / math.sqrt(len(self.pattern))
        eps = rms_norm_eps
        self.embed = nn.Embedding(rows, hidden_size,
                                  weight_initializer=init.Normal(0.02))
        self.layers = nn.HybridSequential()
        for kind in self.pattern:
            if kind == "*":
                mixer = GatedGQAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, heads_held, out_sigma)
            elif kind == "K":
                mixer = KDAMixer(
                    hidden_size, lin["num_heads"], lin["head_dim"],
                    kda_heads_held, lin["short_conv_kernel_size"],
                    chunk_size=kda_chunk_size,
                    allow_neg_eigval=kda_allow_neg_eigval, eps=eps,
                    out_sigma=out_sigma)
            else:
                mixer = SwiGLUMoE(
                    hidden_size, moe_intermediate_size, n_routed_experts,
                    num_experts_per_tok, experts_held, routed_scaling_factor,
                    norm_topk_prob, n_shared_experts, out_sigma)
            self.layers.add(NemotronHLayer(mixer, hidden_size, eps))
        self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.head = _LMHead(rows, hidden_size)

    def forward(self, tokens):
        return self.head(self.norm_f(self.layers(self.embed(tokens))))


def solar_open2(**kwargs):
    """A Solar-Open2 model from the source's keys (``config.json`` of
    ``upstage/Solar-Open2-250B``: the defaults are its sizes) plus the
    share: ``heads_held``, ``kda_heads_held``, ``experts_held``,
    ``vocab_held``."""
    return SolarOpen2Model(**kwargs)


# ``inspect.signature(solar_open2)`` names the keys the builder takes
solar_open2.__wrapped__ = SolarOpen2Model


def solar_open2_tiny(num_hidden_layers=4, gqa_layers=(0,), **kwargs):
    """Every mechanism at a size for CPU tests: one period ``*EKEKEKE``,
    attention 4 / 2 heads, 4 KDA heads of 8 channels in chunks of 16 steps,
    16 experts of which 4 are held, top-3."""
    cfg = dict(hidden_size=32, num_hidden_layers=num_hidden_layers,
               gqa_layers=gqa_layers, vocab_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8,
               linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 8,
                                   "num_heads": 4, "num_kv_heads": None},
               kda_chunk_size=16, moe_intermediate_size=16,
               n_routed_experts=16, num_experts_per_tok=3,
               experts_held=(4, 4), routed_scaling_factor=1.0)
    cfg.update(kwargs)
    return SolarOpen2Model(**cfg)
