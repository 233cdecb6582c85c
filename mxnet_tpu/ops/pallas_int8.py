"""Int8 implicit-GEMM with a fused dequant epilogue — the cheap-math
sibling of ops/pallas_block.py (ROADMAP item 1: the MXU runs
int8×int8→int32 natively and the bench ``int8`` row had never exercised
it).

The kernel family keeps the int32 accumulator in VMEM and fuses the
whole post-GEMM tail into the same HBM pass:

    y = acc·dq[c] + shift[c]  (+ residual)  (ReLU)

where ``dq`` is the combined per-output-channel dequantization scale
(input threshold × per-channel weight threshold / 127²) and ``shift``
carries the conv bias — which, after ``quantization._fold_batchnorm``,
IS the folded-BN affine.  One kernel therefore covers the quantized
residual-block route end to end: int8 conv, dequant, folded BN,
residual add, ReLU, single output write.

Row-blocked exactly like the bf16 family — grid ``(N, H // bh)``, the
padded int8 image fetched once per batch index (its index map ignores
the row coordinate so Pallas double-buffers the next image's DMA), and
``bh`` from the same per-stage ``_TILES`` machinery (int8 patches are
¼ the bytes, so every stage fits with room to spare).  The XLA fallback
(:func:`qconv3x3_xla`, plus the generic-geometry path in ops/nn.py's
``quantized_conv``) composes ``lax.conv_general_dilated(...,
preferred_element_type=int32)`` with the identical epilogue math, so
both routes agree bit-for-bit up to f32 rounding.

Routing is pallas_block's rule: a quantized 3×3/s1 conv takes the
kernel when its shapes pass ``eligible_int8``, its stage is one
``_DEFAULT_TABLE`` routes, and the process drives exactly one TPU
(``pallas_block.one_tpu()``); ``decide_int8`` counts the answer
(``quant.int8.{hits,fallbacks}.<stage>``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import pallas_block as pb

__all__ = ["eligible_int8", "decide_int8", "qconv3x3_affine",
           "qconv3x3_xla"]


def _tele():
    from .. import telemetry
    return telemetry


# No chip measurement has chosen between the routes yet (ROADMAP W4):
# int8 patches are ¼ the bf16 bytes and the epilogue rides the int32
# accumulator, so every profiled stage is routed.
_DEFAULT_TABLE = {
    "56x56x64": {"fwd": "pallas"},
    "28x28x128": {"fwd": "pallas"},
    "14x14x256": {"fwd": "pallas"},
}


def eligible_int8(x_shape, w_shape, has_residual=False) -> bool:
    """Shape/VMEM gate, the int8 analogue of pallas_block's
    ``eligible_block``: 3×3 filters on 4-D NHWC, int8 patch matrix +
    int32 accumulator + f32 out/residual row blocks double-buffered
    under the same 12 MiB budget."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if tuple(w_shape[:2]) != (3, 3) or w_shape[2] != x_shape[-1]:
        return False
    _, H, W, C = x_shape
    cout = w_shape[-1]
    if H < 1 or W < 1:
        return False
    bh = pb._pick_bh(H, W, C, 1)
    blk = bh * W * (9 * C                  # int8 patch matrix
                    + cout * 4             # int32 accumulator
                    + cout * 4             # f32 out block
                    + (cout * 4 if has_residual else 0))
    bytes_needed = 2 * ((H + 2) * (W + 2) * C      # int8 image, dbl-buffered
                        + blk
                        + 9 * C * cout             # int8 weights
                        + 2 * cout * 4)            # dequant scale + shift
    return bytes_needed < 12 * 1024 * 1024


def decide_int8(x_shape, w_shape, has_residual=False) -> str:
    """Route one quantized 3×3/s1 conv: ``"pallas"`` or ``"xla"``.
    Emits the ``quant.int8.{hits,fallbacks}.<stage>`` counters — these
    count routing *decisions* (trace/dispatch time), so steady state
    stays flat just like ``dispatch.pallas.*``."""
    _, H, W, C = x_shape if len(x_shape) == 4 else (0, 0, 0, 0)
    stage = pb.stage_key(H, W, C)
    if not pb.one_tpu():
        return "xla"            # off one TPU is the normal quiet state
    ent = _DEFAULT_TABLE.get(stage)
    if not eligible_int8(x_shape, w_shape, has_residual) \
            or not ent or ent["fwd"] != "pallas":
        _tele().counter_add(f"quant.int8.fallbacks.{stage}", 1)
        return "xla"
    _tele().counter_add(f"quant.int8.hits.{stage}", 1)
    return "pallas"


# ---------------------------------------------------------------- kernels
def _qconv_affine_kernel(*refs, bh, W, C, Cout, add, relu):
    """int8 implicit-GEMM + fused dequant epilogue: the (bh·W, 9C) int8
    patch matrix hits the MXU with an int32 accumulator, then dequant ×
    per-channel scale + shift (folded-BN affine / bias), residual add
    and ReLU all happen on the accumulator in VMEM — one output write."""
    if add:
        xp_ref, w_ref, sc_ref, sh_ref, res_ref, out_ref = refs
    else:
        xp_ref, w_ref, sc_ref, sh_ref, out_ref = refs
    i = pl.program_id(1)
    acc = jnp.dot(pb._patches(xp_ref, i * bh, bh, W, C), w_ref[:],
                  preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * sc_ref[0] + sh_ref[0]
    if add:
        y += res_ref[0].reshape(bh * W, Cout).astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[0] = y.reshape(bh, W, Cout).astype(out_ref.dtype)


def qconv3x3_affine(qx, qw, scale, shift, res=None, relu=True,
                    out_dtype=jnp.float32):
    """Row-blocked int8 3×3/s1 SAME conv with the fused dequant + affine
    (+ add) (+ ReLU) epilogue.  ``qx`` is the already-quantized int8
    NHWC activation (symmetric, zero-point 0 — zero padding is exact),
    ``qw`` the pre-quantized int8 HWIO weights, ``scale``/``shift`` the
    per-output-channel f32 dequant scale and bias."""
    N, H, W, C = qx.shape
    Cout = qw.shape[-1]
    bh = pb._pick_bh(H, W, C, 1)
    xp = jnp.pad(qx, ((0, 0), (1, 1), (1, 1), (0, 0)))
    wf = qw.reshape(9 * C, Cout)
    add = res is not None
    kern = functools.partial(_qconv_affine_kernel, bh=bh, W=W, C=C,
                             Cout=Cout, add=add, relu=relu)
    args = [xp, wf, scale.reshape(1, Cout).astype(jnp.float32),
            shift.reshape(1, Cout).astype(jnp.float32)]
    if add:
        args.append(res)
    return pl.pallas_call(
        kern,
        grid=(N, H // bh),
        in_specs=pb._specs(N, H, W, C, Cout, bh, affine=True, add=add),
        out_specs=pb._out_spec(bh, W, Cout),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), out_dtype),
        interpret=pb.interpret(),
        name="mx_qconv3x3",
    )(*args)


def qconv3x3_xla(qx, qw, scale, shift, res=None, relu=True,
                 out_dtype=jnp.float32):
    """XLA fallback composition with identical math: int8 conv through
    ``lax.conv_general_dilated(preferred_element_type=int32)`` + the
    same f32 epilogue — the parity reference for the Pallas kernel and
    the route taken when the stage or eligibility says no."""
    dn = lax.conv_dimension_numbers(qx.shape, qw.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    acc = lax.conv_general_dilated(
        qx, qw, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * scale.astype(jnp.float32) \
        + shift.astype(jnp.float32)
    if res is not None:
        y = y + res.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(out_dtype)
