"""Operations a model *requires*, computed from its layer shapes.

Only convolutions and matrix multiplications are counted (one
multiply-accumulate = 2 FLOP): normalisation, activations, softmax and the
optimizer are left out, so a utilization computed from these numbers is a
little low and can never pass 100 % by counting too much.  A training step
requires forward + backward = 3 x forward; recomputation does not count.

A configuration names its function under ``"flops"``:
``{"function": "resnet_v1_bottleneck", "kwargs": {...}}``.  A later
configuration of another family brings a file of its own beside this one
(it may not edit this one) and names it with ``"module"``.
"""
from __future__ import annotations

TRAIN_OVER_FORWARD = 3


def conv2d(out_h, out_w, k, c_in, c_out):
    """Forward FLOPs of one k x k convolution for one sample."""
    return 2 * out_h * out_w * k * k * c_in * c_out


def dense(rows, d_in, d_out):
    return 2 * rows * d_in * d_out


def resnet_v1_bottleneck(layers, channels, image, classes, stem=7):
    """Training FLOPs per image of a ResNet v1 with bottleneck blocks as
    ``mxnet_tpu/models/resnet.py`` builds it: a ``stem`` x ``stem``/2 stem,
    3x3/2 max-pool, and in every stage after the first the stride sits on
    the block's *first 1x1* convolution (He et al. 2015; resnet.py:58), so
    the 3x3 runs at the reduced size."""
    side = image // 2                                  # stem, stride 2
    fwd = conv2d(side, side, stem, 3, channels[0])
    side //= 2                                         # max-pool, stride 2
    c_in = channels[0]
    for stage, blocks in enumerate(layers):
        c_out = channels[stage + 1]
        mid = c_out // 4
        for block in range(blocks):
            if block == 0 and stage > 0:
                side //= 2
            fwd += conv2d(side, side, 1, c_in, mid)
            fwd += conv2d(side, side, 3, mid, mid)
            fwd += conv2d(side, side, 1, mid, c_out)
            if block == 0:                             # projection shortcut
                fwd += conv2d(side, side, 1, c_in, c_out)
            c_in = c_out
    fwd += dense(1, c_in, classes)
    return TRAIN_OVER_FORWARD * fwd


def bert_mlm(units, heads, layers, ffn_units, vocab_size, seq):
    """Training FLOPs per sequence of ``models/bert_gluon.py``'s BERT with
    an untied masked-LM decoder over every position: per token the QKV,
    output and two feed-forward projections of each layer, the two
    attention products against ``seq`` keys, and the vocabulary
    projection.  Embedding look-ups are gathers and count nothing."""
    head = units // heads
    per_layer = (dense(1, units, 3 * units) + dense(1, units, units)
                 + dense(1, units, ffn_units) + dense(1, ffn_units, units)
                 + 2 * heads * dense(1, head, seq))    # QK^T and AV
    per_token = layers * per_layer + dense(1, units, vocab_size)
    return TRAIN_OVER_FORWARD * seq * per_token
