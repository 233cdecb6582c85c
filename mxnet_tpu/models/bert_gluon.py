"""Gluon-block BERT ≙ GluonNLP's bert.py model zoo (BERTModel/BERTEncoder).

The reference ecosystem's BERT (the BASELINE.md config "BERT-base
pretraining (GluonNLP)") is a gluon HybridBlock tree; this is its
TPU-native twin built from mxnet_tpu.gluon.nn layers and NDArray-level
ops, so it:
- hybridizes into one jitted XLA computation (CachedOp contract),
- traces through the generic deferred-compute tracer (gluon/deferred.py)
  → real Symbol JSON export + SymbolBlock.imports + ONNX,
- shares kernels with the functional SPMD BERT (models/bert.py) used by
  the multi-chip train path.

Layout: batch-major (B, T, D) like GluonNLP with use_pooler/use_decoder
reduced to the MLM decoder head.
"""
from __future__ import annotations

import math

from ..gluon import nn
from .. import numpy as mnp
from .. import numpy_extension as npx
from .. import tape
from ..ops import pallas_kernels as _pk

__all__ = ["BERTSelfAttention", "BERTEncoderCell", "BERTEncoder",
           "BERTModel", "bert_12_768_12", "bert_small"]


class BERTSelfAttention(nn.HybridBlock):
    """Multi-head self-attention ≙ gluon-nlp DotProductSelfAttentionCell;
    one fused QKV projection keeps the MXU busy."""

    def __init__(self, units, heads, dropout=0.0):
        super().__init__()
        assert units % heads == 0
        self._units = units
        self._heads = heads
        self.qkv = nn.Dense(3 * units, flatten=False)
        self.proj = nn.Dense(units, flatten=False)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        B, T, D = x.shape
        H = self._heads
        hd = D // H
        qkv = self.qkv(x)                               # (B, T, 3D)
        # nothing between the scores and their use but the softmax: the
        # fused kernels take it where shape and platform allow (the heads
        # are read out of qkv in place, no (T, T) matrix reaches HBM)
        plain = mask is None and not (self.dropout is not None
                                      and tape.is_training())
        if _pk.self_attention_use_pallas(T, hd, plain):
            return self.proj(npx.multihead_self_attention(qkv, H))
        qkv = qkv.reshape(B, T, 3, H, hd).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]                # (B, H, T, hd)
        scores = mnp.matmul(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
        if mask is not None:
            big_neg = -1e9
            scores = mnp.where(mask.reshape(B, 1, 1, T), scores, big_neg)
        attn = npx.softmax(scores, axis=-1)
        if self.dropout is not None:
            attn = self.dropout(attn)
        ctx = mnp.matmul(attn, v)                       # (B, H, T, hd)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, D)
        return self.proj(ctx)


class BERTEncoderCell(nn.HybridBlock):
    """Transformer layer ≙ gluon-nlp BERTEncoderCell (post-LN like BERT)."""

    def __init__(self, units, heads, ffn_units, dropout=0.0):
        super().__init__()
        self.attention = BERTSelfAttention(units, heads, dropout)
        self.ln1 = nn.LayerNorm()
        self.ffn_in = nn.Dense(ffn_units, flatten=False)
        self.gelu = nn.GELU()
        self.ffn_out = nn.Dense(units, flatten=False)
        self.ln2 = nn.LayerNorm()
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        a = self.attention(x, mask)
        if self.dropout is not None:
            a = self.dropout(a)
        x = self.ln1(x + a)
        h = self.ffn_out(self.gelu(self.ffn_in(x)))
        if self.dropout is not None:
            h = self.dropout(h)
        return self.ln2(x + h)


class BERTEncoder(nn.HybridBlock):
    """Embeddings + N transformer layers ≙ gluon-nlp BERTEncoder."""

    def __init__(self, units=768, heads=12, layers=12, ffn_units=3072,
                 vocab_size=30522, max_length=512, type_vocab=2,
                 dropout=0.0):
        super().__init__()
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units)
        self.position_embed = nn.Embedding(max_length, units)
        self.token_type_embed = nn.Embedding(type_vocab, units)
        self.ln = nn.LayerNorm()
        self.dropout = nn.Dropout(dropout) if dropout else None
        self._cells = []
        for i in range(layers):
            cell = BERTEncoderCell(units, heads, ffn_units, dropout)
            setattr(self, f"layer{i}", cell)
            self._cells.append(cell)

    def forward(self, tokens, token_types=None, mask=None):
        T = tokens.shape[1]
        positions = mnp.arange(T, dtype="int32")
        x = self.word_embed(tokens) + self.position_embed(positions)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.ln(x)
        if self.dropout is not None:
            x = self.dropout(x)
        for cell in self._cells:
            x = cell(x, mask)
        return x


class BERTModel(nn.HybridBlock):
    """Encoder + masked-LM decoder head ≙ gluon-nlp BERTModel
    (use_decoder path; the decoder shares no weights here, like the
    default `use_decoder=True, tie_weights=False` zoo entries)."""

    def __init__(self, units=768, heads=12, layers=12, ffn_units=3072,
                 vocab_size=30522, max_length=512, type_vocab=2,
                 dropout=0.0):
        super().__init__()
        self.encoder = BERTEncoder(units, heads, layers, ffn_units,
                                   vocab_size, max_length, type_vocab,
                                   dropout)
        self.decoder = nn.Dense(vocab_size, flatten=False)

    def forward(self, tokens, token_types=None, mask=None):
        x = self.encoder(tokens, token_types, mask)
        return self.decoder(x)


def bert_12_768_12(vocab_size=30522, **kwargs):
    """BERT-base ≙ gluon-nlp model zoo 'bert_12_768_12'."""
    return BERTModel(units=768, heads=12, layers=12, ffn_units=3072,
                     vocab_size=vocab_size, **kwargs)


def bert_small(vocab_size=1000, **kwargs):
    """Tiny config for tests/examples."""
    return BERTModel(units=64, heads=4, layers=2, ffn_units=128,
                     vocab_size=vocab_size, max_length=64, **kwargs)
