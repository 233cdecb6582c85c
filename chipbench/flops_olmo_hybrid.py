"""Operations an Olmo-Hybrid training step *requires* of the share one chip
holds, from the layer shapes (``flops.py``'s rules: matrix products only,
one multiply-accumulate = 2 FLOP, a training step = 3 x forward,
recomputation does not count, so a utilization from these numbers can only
read low).

Counted per token and block kind of ``pattern``:

- ``L`` the gated delta net over the heads held: the q, k, v, output-gate
  and output projections and the two one-a-head projections (decay, beta);
  of the chunked delta rule the products inside a chunk at their causal
  half (``K K^T`` and ``Q K^T`` over dk, the triangular system applied to
  ``beta V`` and ``beta K exp G`` over dv + dk, ``A_qk U`` over dv: (chunk +
  1) / 2 earlier rows a token on average) and the three products with the
  chunk state (``W S`` that corrects ``U``, ``Q S`` of the output, ``K^T
  U`` of the update), each dk x dv a head.  The convolutions, the decays,
  the norms and the gate's activation are left out.
- ``*`` full attention over the heads held: ``flops_nemotron_h``'s
  ``attention_token`` (the four projections and the causal half of ``q
  k^T`` and ``p v``), imported; the QK-norm is no matrix product.
- ``F`` the MLP, whole: gate, up and down.
- the untied head over the vocabulary rows held.  The embedding is a gather.
"""
from __future__ import annotations

from chipbench.flops_nemotron_h import (TRAIN_OVER_FORWARD, attention_token,
                                        dense)


def gdn_token(hidden, heads, key_dim, value_dim, chunk):
    proj = 2 * dense(hidden, heads * key_dim) \
        + 3 * dense(hidden, heads * value_dim) + 2 * dense(hidden, heads)
    inside = (chunk + 1) / 2 * heads * (
        2 * 2 * key_dim + 2 * (value_dim + key_dim) + 2 * value_dim)
    states = 3 * 2 * heads * key_dim * value_dim
    return proj + inside + states


def per_token(pattern, hidden, seq, vocab_rows, linear_heads, key_dim,
              value_dim, chunk, heads, kv_heads, head_dim, mlp_width):
    """Forward FLOPs a token: ``{"L": ..., "*": ..., "F": ..., "head": ...,
    "token": the sum over the pattern and the head}``."""
    out = {
        "L": gdn_token(hidden, linear_heads, key_dim, value_dim, chunk),
        "*": attention_token(hidden, heads, kv_heads, head_dim, seq),
        "F": 3 * dense(hidden, mlp_width),
        "head": dense(hidden, vocab_rows),
    }
    out["token"] = sum(out[k] for k in pattern) + out["head"]
    return out


def olmo_hybrid_train(seq, **shapes):
    """Training FLOPs of one sequence of ``seq`` tokens."""
    return TRAIN_OVER_FORWARD * seq * per_token(seq=seq, **shapes)["token"]
