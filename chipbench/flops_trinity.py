"""Operations a Trinity training step *requires* of the share one chip
holds, from the layer shapes (``flops.py``'s rules: matrix products only,
one multiply-accumulate = 2 FLOP, a training step = 3 x forward,
recomputation does not count, so a utilization from these numbers can only
read low), and the operations of the windowed attention kernels' calls, for
their shares of the roofline.

Counted per block kind of ``pattern``:

- ``W`` sliding and ``*`` full attention: the five projections (q, k, v,
  the output gate, o) a token, and ``q k^T`` and ``p v`` over the
  query-key pairs a layer *sees*: ``band_pairs`` - the sum over the
  queries i of min(i + 1, window) for a sliding layer, the causal triangle
  seq (seq + 1) / 2 for a full one.  Not the triangle for a sliding layer,
  and not whole tiles: what a kernel computes and masks away is not
  required.  The norms, the rotary embedding and the gate's sigmoid are
  left out.
- ``D`` the dense SwiGLU MLP: three products.
- ``E`` experts: ``flops_solar_open2``'s ``swiglu_experts_token`` (the
  router over all experts, the shared expert, the routed experts at their
  expected slots a token, ``top_k * held / experts``).
- the untied head over the vocabulary rows held.  The embedding is a gather.
"""
from __future__ import annotations

import os

from chipbench.files import load_json
from chipbench.flops_nemotron_h import TRAIN_OVER_FORWARD, dense
from chipbench.flops_solar_open2 import swiglu_experts_token

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = ("chipbench", "configs", "trinity-mini-26b-train-ep8.json")
# FLOPs a query-key pair and head of width hd, in units of hd: forward
# q k^T and p v; backward the scores again, dp = g v^T, dv = p^T g,
# dk = ds^T q, dq = ds k
PAIR = {"mx_window_attn_fwd": 4, "mx_window_attn_bwd": 10}
# the forward kernel runs again when its block is recomputed
CALLS = {"mx_window_attn_fwd": 2, "mx_window_attn_bwd": 1}


def band_pairs(seq, window=None):
    """Query-key pairs with ``0 <= i - j < window`` among ``seq`` positions
    (``window`` None: the causal triangle)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_block(hidden, heads, kv_heads, head_dim, seq, window=None):
    """Forward FLOPs of one gated attention block over a sequence."""
    proj = 3 * dense(hidden, heads * head_dim) \
        + 2 * dense(hidden, kv_heads * head_dim)
    return seq * proj + 2 * 2 * heads * head_dim * band_pairs(seq, window)


def per_block(hidden, seq, vocab_rows, heads, kv_heads, head_dim, window,
              mlp_width, experts, experts_held, top_k, expert_width,
              shared_width):
    """Forward FLOPs of a block of each kind, and of the head, over a
    sequence of ``seq`` tokens."""
    return {
        "W": attention_block(hidden, heads, kv_heads, head_dim, seq, window),
        "*": attention_block(hidden, heads, kv_heads, head_dim, seq),
        "D": seq * 3 * dense(hidden, mlp_width),
        "E": seq * swiglu_experts_token(hidden, experts, experts_held, top_k,
                                        expert_width, shared_width),
        "head": seq * dense(hidden, vocab_rows),
    }


def trinity_train(pattern, **shapes):
    """Training FLOPs of one sequence of ``seq`` tokens."""
    kinds = per_block(**shapes)
    return TRAIN_OVER_FORWARD * (sum(kinds[k] for k in pattern)
                                 + kinds["head"])


def window_kernel_flops(kernel, pattern, seq, window, heads, head_dim, **_):
    """FLOPs of one sequence's calls of ``kernel`` (``mx_window_attn_fwd``,
    forward and recomputation, or ``mx_window_attn_bwd``) in a step: the
    band's pairs, not the tiles visited."""
    return CALLS[kernel] * pattern.count("W") * PAIR[kernel] * head_dim \
        * heads * band_pairs(seq, window)


def window_roofline_pct(evidence, kernel):
    """The share of the chip's bfloat16 peak at which ``kernel``'s calls ran
    their required FLOPs, from the traced run's evidence: the self time of
    the instructions whose ``op_name`` names the kernel
    (``evidence["scope_s"]``: the configuration lists both kernels among its
    ``scopes``) and the configuration's own shapes.  Compute-bound: at 8192
    tokens a call moves 0.3 GB (0.4 ms at the HBM's peak) and needs 1 TFLOP
    (5 ms).  Nothing where there is no such instruction."""
    by_scope, n = evidence.get("scope_s"), evidence.get("steps")
    peaks = evidence.get("peaks")
    if not by_scope or not n or not peaks or not by_scope.get(kernel):
        return None
    shapes = load_json(ROOT, *CONFIG)["flops"]["kwargs"]
    flops = evidence["batch"] * window_kernel_flops(kernel, **shapes)
    return 100.0 * flops * n / by_scope[kernel] / peaks["bf16_flops_per_s"]
