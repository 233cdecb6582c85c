"""Operations a Solar-Open2 training step *requires* of the share one chip
holds, from the layer shapes (``flops.py``'s rules: matrix products only,
one multiply-accumulate = 2 FLOP, a training step = 3 x forward,
recomputation does not count, so a utilization from these numbers can only
read low).

Counted per token and block kind of ``pattern``:

- ``K`` KDA over the heads held: the q, k, v and output projections, the
  two low-rank gates (down and up) and the beta projection; of the chunked
  delta rule the products inside a chunk at their causal half (``K K^T``,
  ``Q K^T``, the triangular system applied to ``beta V`` and ``beta K exp
  G``, ``A_qk U``: (chunk + 1) / 2 earlier rows a token on average) and the
  three products with the chunk state (``W S`` that corrects ``U``, ``Q S``
  of the output, ``K^T U`` of the update).  The convolutions, the decays,
  the norms and the gates' activations are left out.
- ``*`` gated attention over the heads held: ``flops_nemotron_h``'s
  ``attention_token`` (the four projections and the causal half of ``q
  k^T`` and ``p v``), imported, plus the output gate's projection.
- ``E`` experts: the router over all experts, the shared expert and the
  routed experts at their *expected* slots a token, ``top_k * held /
  experts`` (uniform routing), each **three** products (gate, up, down):
  the terms of experts not held are not computed here and not counted.
- the untied head over the vocabulary rows held.  The embedding is a gather.
"""
from __future__ import annotations

from chipbench.flops_nemotron_h import (TRAIN_OVER_FORWARD, attention_token,
                                        dense)


def kda_token(hidden, heads, head_dim, gate_rank, chunk):
    inner = heads * head_dim
    proj = 4 * dense(hidden, inner) + 2 * dense(hidden, gate_rank) \
        + 2 * dense(gate_rank, inner) + dense(hidden, heads)
    # K K^T, Q K^T: 2 dk each; the solve on [V | K]: 2 (dv + dk); A_qk U: 2 dv
    inside = (chunk + 1) / 2 * heads * 10 * head_dim
    states = 3 * 2 * heads * head_dim * head_dim
    return proj + inside + states


def gated_attention_token(hidden, heads, kv_heads, head_dim, seq):
    return attention_token(hidden, heads, kv_heads, head_dim, seq) \
        + dense(hidden, heads * head_dim)


def swiglu_experts_token(hidden, experts, experts_held, top_k, expert_width,
                         shared_width):
    slots = top_k * experts_held / experts
    return dense(hidden, experts) + 3 * dense(hidden, shared_width) \
        + slots * 3 * dense(hidden, expert_width)


def per_token(pattern, hidden, seq, vocab_rows, kda_heads, kda_head_dim,
              gate_rank, chunk, heads, kv_heads, head_dim, experts,
              experts_held, top_k, expert_width, shared_width):
    """Forward FLOPs a token: ``{"K": ..., "*": ..., "E": ..., "head": ...,
    "token": the sum over the pattern and the head}``."""
    out = {
        "K": kda_token(hidden, kda_heads, kda_head_dim, gate_rank, chunk),
        "*": gated_attention_token(hidden, heads, kv_heads, head_dim, seq),
        "E": swiglu_experts_token(hidden, experts, experts_held, top_k,
                                  expert_width, shared_width),
        "head": dense(hidden, vocab_rows),
    }
    out["token"] = sum(out[k] for k in pattern) + out["head"]
    return out


def solar_open2_train(seq, **shapes):
    """Training FLOPs of one sequence of ``seq`` tokens."""
    return TRAIN_OVER_FORWARD * seq * per_token(seq=seq, **shapes)["token"]
