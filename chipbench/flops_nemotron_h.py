"""Operations a Nemotron-H training step *requires* of the share one chip
holds, from the layer shapes (``flops.py``'s rules: matrix products only,
one multiply-accumulate = 2 FLOP, a training step = 3 x forward,
recomputation does not count, so a utilization from these numbers can only
read low).

Counted per token and layer kind of ``pattern``:

- ``M`` Mamba-2: ``in_proj`` and ``out_proj``; of the chunked scan the
  causal half of the two products inside a chunk (``C B^T`` per group,
  ``(L * C B^T) x`` per head) and the two products with the chunk states
  (a chunk's own end state, the entering state's output).  The convolution,
  the recurrence over chunk states and the norms are left out.
- ``E`` experts: the router over all experts, the shared expert, and the
  routed experts at their *expected* slots a token, ``top_k * held /
  experts`` (uniform routing): the terms of experts not held are not
  computed here and not counted.
- ``*`` attention: the four projections and the causal half of ``q k^T``
  and ``p v`` (a token attends to (seq + 1) / 2 keys on average).
- the untied head over the vocabulary rows held.  The embedding is a gather.
"""
from __future__ import annotations

TRAIN_OVER_FORWARD = 3


def dense(d_in, d_out):
    return 2 * d_in * d_out


def mamba2_token(hidden, heads, head_dim, n_groups, state, chunk):
    inner = heads * head_dim
    proj = dense(hidden, 2 * inner + 2 * n_groups * state + heads) \
        + dense(inner, hidden)
    inside = (chunk + 1) / 2 * (2 * n_groups * state + 2 * heads * head_dim)
    states = 2 * 2 * heads * head_dim * state
    return proj + inside + states


def experts_token(hidden, experts, experts_held, top_k, expert_width,
                  shared_width):
    slots = top_k * experts_held / experts
    return dense(hidden, experts) + 2 * dense(hidden, shared_width) \
        + slots * 2 * dense(hidden, expert_width)


def attention_token(hidden, heads, kv_heads, head_dim, seq):
    proj = dense(hidden, heads * head_dim) * 2 \
        + dense(hidden, kv_heads * head_dim) * 2
    core = 2 * 2 * heads * head_dim * (seq + 1) / 2
    return proj + core


def nemotron_h_train(pattern, hidden, seq, vocab_rows, mamba_heads,
                     mamba_head_dim, n_groups, state, chunk, experts,
                     experts_held, top_k, expert_width, shared_width, heads,
                     kv_heads, head_dim):
    """Training FLOPs of one sequence of ``seq`` tokens."""
    per_kind = {
        "M": mamba2_token(hidden, mamba_heads, mamba_head_dim, n_groups,
                          state, chunk),
        "E": experts_token(hidden, experts, experts_held, top_k,
                           expert_width, shared_width),
        "*": attention_token(hidden, heads, kv_heads, head_dim, seq),
    }
    token = sum(per_kind[k] for k in pattern) + dense(hidden, vocab_rows)
    return TRAIN_OVER_FORWARD * seq * token
