"""Nemotron-H language model (``model_type`` ``nemotron_h``; NVIDIA
Nemotron-H, arXiv:2504.03624, and NVIDIA-Nemotron-3-Nano-30B-A3B's
``config.json``) as Gluon ``HybridBlock``s.

A stack of pre-norm residual layers ``h <- h + Mixer(RMSNorm(h))``, each with
*one* mixer chosen by a pattern string and no separate feed-forward:

- ``M`` Mamba-2 (:class:`Mamba2Mixer`): ``[z | xBC | dt] = in_proj(u)``,
  causal depthwise conv + SiLU on ``xBC``, the selective state-space scan by
  chunks, a gated group RMSNorm, ``out_proj``;
- ``E`` routed experts (:class:`NemotronHMoE`): sigmoid router over all
  experts with a correction bias, top-k, dropless, ``relu2`` experts without
  gate projection, plus one shared expert;
- ``*`` attention (:class:`NemotronHAttention`): causal grouped-query
  attention, **no positional embedding** (the report states none is used; the
  Mamba layers carry position).

The builder's keyword arguments are the source's keys plus the share of an
expert-parallel deployment this process holds: ``experts_held = (first,
count)`` of the ``n_routed_experts`` the router scores, and ``vocab_held =
(first, count)`` rows of the vocabulary for the embedding and the untied
head (token ids are then relative to the slice).  An expert layer adds only
its own experts' terms; on one chip it runs without its exchange.

Training enters through ``gluon.Trainer(net.collect_params(), "adam",
...).fuse_step(SoftmaxCrossEntropyLoss())``.  Two things a long sequence
needs are asked for by the blocks themselves, not by an option of the step:
every layer runs under :func:`gluon.block.recompute`, and the head offers
its product in factors so that the loss is taken in blocks of rows
(``ops.nn.offer_product``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init
from .. import tape
from ..gluon import nn
from ..gluon.block import recompute
from ..gluon.parameter import Parameter, _trace_ctx
from ..numpy import _call
from ..ops import nn as _nn
from ..parallel.moe import moe_topk_held

__all__ = ["Mamba2Mixer", "NemotronHMoE", "NemotronHAttention",
           "NemotronHLayer", "NemotronHModel", "nemotron_h",
           "nemotron_h_tiny"]


class _LogUniform(init.Initializer):
    """``log U(lo, hi)`` (Mamba-2's ``A_log``)."""

    def __init__(self, lo, hi):
        self.lo, self.hi = lo, hi

    def init_array(self, shape, dtype, key):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          self.lo, self.hi)).astype(dtype)


class _InverseSoftplusDt(init.Initializer):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[dt_min, dt_max]``, not below ``floor`` (Mamba-2's initialisation)."""

    def __init__(self, dt_min, dt_max, floor):
        self.dt_min, self.dt_max, self.floor = dt_min, dt_max, floor

    def init_array(self, shape, dtype, key):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.dt_max) - math.log(self.dt_min))
                     + math.log(self.dt_min))
        dt = jnp.maximum(dt, self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _dense(units, in_units, sigma=0.02):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    weight_initializer=init.Normal(sigma))


def _mamba2_core(zxbcdt, conv_w, conv_b, dt_bias, a_log, d, *, heads,
                 head_dim, groups, state, chunk):
    """From ``in_proj``'s output to the scan's output and the gate:
    ``(y, z)``, both (B, T, heads * head_dim)."""
    B, T, _ = zxbcdt.shape
    inner, gn = heads * head_dim, groups * state
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * gn]
    dt = zxbcdt[..., 2 * inner + 2 * gn:]
    with jax.named_scope("ssm.conv"):
        xbc = jax.nn.silu(_nn.causal_conv1d(xbc, conv_w, conv_b))
    with jax.named_scope("ssm.scan"):
        y = _nn.ssd_chunked(
            xbc[..., :inner].reshape(B, T, heads, head_dim),
            jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
            xbc[..., inner:inner + gn].reshape(B, T, groups, state),
            xbc[..., inner + gn:].reshape(B, T, groups, state), d,
            chunk=chunk)
    return y.reshape(B, T, inner), z


def _experts_core(x, router_w, bias, up, down, *, held, top_k, scaling,
                  norm_topk):
    B, T, D = x.shape
    y, load = moe_topk_held(x.reshape(B * T, D), router_w, bias, up, down,
                            held, top_k, scaling, norm_topk, act=_nn.relu2)
    return y.reshape(B, T, D), load


def _attention_core(q, k, v, *, heads, kv, hd, window=None):
    """Scope ``attn.core``; a windowed call's is ``attn.window``."""
    B, T, _ = q.shape
    with jax.named_scope("attn.core" if window is None else "attn.window"):
        o = _nn.causal_gqa_attention(q.reshape(B, T, heads, hd),
                                     k.reshape(B, T, kv, hd),
                                     v.reshape(B, T, kv, hd), window=window)
    return o.reshape(B, T, heads * hd)


class Mamba2Mixer(nn.HybridBlock):
    """Mamba-2 (Dao & Gu 2024) as Nemotron-H configures it."""

    def __init__(self, hidden_size, mamba_num_heads, mamba_head_dim,
                 n_groups, ssm_state_size, conv_kernel=4, chunk_size=128,
                 eps=1e-5, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, out_sigma=0.02):
        super().__init__()
        inner = mamba_num_heads * mamba_head_dim
        conv_dim = inner + 2 * n_groups * ssm_state_size
        self._sizes = dict(heads=mamba_num_heads, head_dim=mamba_head_dim,
                           groups=n_groups, state=ssm_state_size,
                           chunk=chunk_size)
        self.in_proj = _dense(inner + conv_dim + mamba_num_heads,
                              hidden_size)
        self.conv_weight = Parameter(
            "conv_weight", shape=(conv_dim, conv_kernel),
            init=init.Uniform(1.0 / math.sqrt(conv_kernel)))
        self.conv_bias = Parameter("conv_bias", shape=(conv_dim,),
                                   init=init.Zero())
        self.dt_bias = Parameter(
            "dt_bias", shape=(mamba_num_heads,), wd_mult=0.0,
            init=_InverseSoftplusDt(time_step_min, time_step_max,
                                    time_step_floor))
        self.A_log = Parameter("A_log", shape=(mamba_num_heads,),
                               wd_mult=0.0, init=_LogUniform(1.0, 16.0))
        self.D = Parameter("D", shape=(mamba_num_heads,), wd_mult=0.0,
                           init=init.One())
        self.norm = nn.GatedGroupRMSNorm(groups=n_groups, epsilon=eps,
                                         in_channels=inner)
        self.out_proj = _dense(hidden_size, inner, out_sigma)

    def forward(self, u):
        y, z = _call(_mamba2_core, self.in_proj(u), self.conv_weight.data(),
                     self.conv_bias.data(), self.dt_bias.data(),
                     self.A_log.data(), self.D.data(), **self._sizes)
        return self.out_proj(self.norm(y, z))


class NemotronHMoE(nn.HybridBlock):
    """Routed ``relu2`` experts of one expert-parallel share plus the shared
    expert (``parallel.moe.moe_topk_held``).  ``load`` (the tokens routed to
    each of the ``n_routed_experts`` experts in the last step) and
    ``load_total`` (their sum over the steps so far) are aux state like
    BatchNorm's running statistics: written by the step, read back by
    ``TrainerFusedStep.sync`` into the ``moe.*`` counters."""

    def __init__(self, hidden_size, moe_intermediate_size,
                 moe_shared_expert_intermediate_size, n_routed_experts,
                 num_experts_per_tok, experts_held=None,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 out_sigma=0.02):
        super().__init__()
        first, count = experts_held or (0, n_routed_experts)
        assert 0 <= first and first + count <= n_routed_experts
        self._held = (int(first), int(count))
        self._route = dict(top_k=num_experts_per_tok,
                           scaling=routed_scaling_factor,
                           norm_topk=norm_topk_prob)
        self.router_weight = Parameter(
            "router_weight", shape=(n_routed_experts, hidden_size),
            init=init.Normal(0.02))
        # a buffer in the source (moved by the load balancer, not by the
        # optimizer): zero here
        self.correction_bias = Parameter(
            "correction_bias", shape=(n_routed_experts,), init=init.Zero(),
            grad_req="null")
        self.experts_up = Parameter(
            "experts_up", shape=(count, hidden_size, moe_intermediate_size),
            init=init.Normal(0.02))
        self.experts_down = Parameter(
            "experts_down", shape=(count, moe_intermediate_size, hidden_size),
            init=init.Normal(out_sigma))
        self.shared_up = _dense(moe_shared_expert_intermediate_size,
                                hidden_size)
        self.shared_down = _dense(hidden_size,
                                  moe_shared_expert_intermediate_size,
                                  out_sigma)
        for name in ("load", "load_total"):
            p = Parameter(name, shape=(n_routed_experts,), dtype="int32",
                          init=init.Zero(), grad_req="null")
            p.publish = ("moe.load", self._held, name)
            setattr(self, name, p)

    def forward(self, x):
        y, load = _call(_experts_core, x, self.router_weight.data(),
                        self.correction_bias.data(), self.experts_up.data(),
                        self.experts_down.data(), held=self._held,
                        **self._route)
        if tape.is_training():
            self.load.set_data(load)
            self.load_total.set_data(self.load_total.data() + load)
        with jax.named_scope("moe.shared"):
            shared = self.shared_down(
                _call(_nn.relu2, self.shared_up(x)))
        return y + shared


class NemotronHAttention(nn.HybridBlock):
    """Causal grouped-query attention without positional embedding."""

    def __init__(self, hidden_size, num_attention_heads, num_key_value_heads,
                 head_dim, out_sigma=0.02):
        super().__init__()
        self._heads, self._kv, self._hd = (num_attention_heads,
                                           num_key_value_heads, head_dim)
        self.q_proj = _dense(num_attention_heads * head_dim, hidden_size)
        self.k_proj = _dense(num_key_value_heads * head_dim, hidden_size)
        self.v_proj = _dense(num_key_value_heads * head_dim, hidden_size)
        self.o_proj = _dense(hidden_size, num_attention_heads * head_dim,
                             out_sigma)

    def forward(self, x):
        return self.o_proj(_call(_attention_core, self.q_proj(x),
                                 self.k_proj(x), self.v_proj(x),
                                 heads=self._heads, kv=self._kv,
                                 hd=self._hd))


class NemotronHLayer(nn.HybridBlock):
    """``h + mixer(norm(h))``, recomputed in the backward pass of a traced
    training program: only the layer's input is kept."""

    def __init__(self, mixer, hidden_size, eps=1e-5):
        super().__init__()
        self.norm = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.mixer = mixer

    def forward(self, h):
        return recompute(lambda h: h + self.mixer(self.norm(h)), h)


class _LMHead(nn.HybridBlock):
    """Untied output projection without bias over the rows held.  While a
    training program is traced it offers ``(h, weight)`` beside the logits,
    so a loss that can work in blocks never makes the (T, V) matrix."""

    def __init__(self, rows, hidden_size):
        super().__init__()
        self.weight = Parameter("weight", shape=(rows, hidden_size),
                                init=init.Normal(0.02))

    def forward(self, h):
        out = _call(_nn.fully_connected, h, self.weight.data(), None,
                    flatten=False)
        if _trace_ctx.active and tape.is_training():
            _nn.offer_product(out._data, h._data, self.weight.data()._data)
        return out


class NemotronHModel(nn.HybridBlock):
    """Embedding, the layers of ``hybrid_override_pattern``, final RMSNorm
    and the untied head: tokens (B, T) int -> logits (B, T, rows held)."""

    def __init__(self, hybrid_override_pattern, hidden_size, vocab_size,
                 num_hidden_layers=None, vocab_held=None,
                 layer_norm_epsilon=1e-5, rescale_prenorm_residual=True,
                 # Mamba-2
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4,
                 # experts
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, num_experts_per_tok=6,
                 experts_held=None, routed_scaling_factor=2.5,
                 norm_topk_prob=True,
                 # attention
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128):
        super().__init__()
        pattern = hybrid_override_pattern
        if num_hidden_layers is not None and num_hidden_layers != len(pattern):
            raise ValueError(f"num_hidden_layers {num_hidden_layers} but the "
                             f"pattern {pattern!r} has {len(pattern)} layers")
        first, rows = vocab_held or (0, vocab_size)
        assert 0 <= first and first + rows <= vocab_size
        self.pattern, self.vocab_held = pattern, (int(first), int(rows))
        # rescale_prenorm_residual: the projections that write into the
        # residual stream start 1/sqrt(layers) smaller
        out_sigma = 0.02 / math.sqrt(len(pattern)) \
            if rescale_prenorm_residual else 0.02
        eps = layer_norm_epsilon
        self.embed = nn.Embedding(rows, hidden_size,
                                  weight_initializer=init.Normal(0.02))
        self.layers = nn.HybridSequential()
        for kind in pattern:
            if kind == "M":
                mixer = Mamba2Mixer(
                    hidden_size, mamba_num_heads, mamba_head_dim, n_groups,
                    ssm_state_size, conv_kernel, chunk_size, eps,
                    time_step_min, time_step_max, time_step_floor, out_sigma)
            elif kind == "E":
                mixer = NemotronHMoE(
                    hidden_size, moe_intermediate_size,
                    moe_shared_expert_intermediate_size, n_routed_experts,
                    num_experts_per_tok, experts_held, routed_scaling_factor,
                    norm_topk_prob, out_sigma)
            elif kind == "*":
                mixer = NemotronHAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, out_sigma)
            else:
                raise ValueError(f"unknown mixer {kind!r} in {pattern!r}: "
                                 "M (Mamba-2), E (experts) or * (attention)")
            self.layers.add(NemotronHLayer(mixer, hidden_size, eps))
        self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.head = _LMHead(rows, hidden_size)

    def forward(self, tokens):
        return self.head(self.norm_f(self.layers(self.embed(tokens))))


def nemotron_h(**kwargs):
    """A Nemotron-H model from the source's keys (``config.json`` of
    ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``: the defaults are its
    widths) plus the share: ``experts_held``, ``vocab_held``."""
    return NemotronHModel(**kwargs)


# ``inspect.signature(nemotron_h)`` names the keys the builder takes
nemotron_h.__wrapped__ = NemotronHModel


def nemotron_h_tiny(pattern="MEMEM*EME", **kwargs):
    """Every mechanism at a size for CPU tests: 16 experts of which 4 are
    held, top-3, grouped-query attention 4 / 2, 4 Mamba heads in 2 groups,
    chunks of 8 steps."""
    cfg = dict(hybrid_override_pattern=pattern, hidden_size=32,
               vocab_size=64, mamba_num_heads=4, mamba_head_dim=8,
               n_groups=2, ssm_state_size=8, chunk_size=8,
               moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24, n_routed_experts=16,
               num_experts_per_tok=3, experts_held=(4, 4),
               num_attention_heads=4, num_key_value_heads=2, head_dim=8)
    cfg.update(kwargs)
    return NemotronHModel(**cfg)
