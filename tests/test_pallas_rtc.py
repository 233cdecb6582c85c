"""Pallas fused kernels, mx.rtc PallasModule, and numpy interop
(reference: fused softmax/layer_norm kernels N8/N11, rtc.py,
numpy_dispatch_protocol.py + numpy/fallback.py)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def force_interpret():
    pk._FORCE_INTERPRET = True
    yield
    pk._FORCE_INTERPRET = False


def test_softmax_fused_matches_reference():
    x = jnp.asarray(np.random.RandomState(0).randn(16, 256)
                    .astype("float32"))
    assert jnp.allclose(pk.softmax_fused(x), jax.nn.softmax(x, -1),
                        atol=1e-6)
    g1 = jax.grad(lambda x: jnp.sum(pk.softmax_fused(x) * jnp.cos(x)))(x)
    g2 = jax.grad(lambda x: jnp.sum(jax.nn.softmax(x, -1) * jnp.cos(x)))(x)
    assert jnp.allclose(g1, g2, atol=1e-5)


def test_layernorm_fused_matches_reference():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 128).astype("float32"))
    gamma = jnp.asarray(rng.randn(128).astype("float32"))
    beta = jnp.asarray(rng.randn(128).astype("float32"))

    def ref(x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * gamma + beta

    assert jnp.allclose(pk.layernorm_fused(x, gamma, beta), ref(x),
                        atol=1e-5)
    g1 = jax.grad(lambda x: jnp.sum(
        pk.layernorm_fused(x, gamma, beta) * jnp.sin(x)))(x)
    g2 = jax.grad(lambda x: jnp.sum(ref(x) * jnp.sin(x)))(x)
    assert jnp.allclose(g1, g2, atol=1e-4)
    # gamma/beta grads
    dg = jax.grad(lambda g: jnp.sum(pk.layernorm_fused(x, g, beta)))(gamma)
    dg_ref = jax.grad(lambda g: jnp.sum(
        (x - x.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            ((x - x.mean(-1, keepdims=True)) ** 2).mean(-1, keepdims=True)
            + 1e-5) * g + beta))(gamma)
    assert jnp.allclose(dg, dg_ref, atol=1e-4)


def test_ops_nn_dispatch():
    """ops.nn.softmax/layer_norm route through the fused kernels when
    eligible (interpret forced here)."""
    from mxnet_tpu.ops import nn as onn
    x = jnp.asarray(np.random.RandomState(3).randn(4, 128)
                    .astype("float32"))
    assert jnp.allclose(onn.softmax(x), jax.nn.softmax(x, -1), atol=1e-6)
    g = jnp.ones(128)
    b = jnp.zeros(128)
    ref = (x - x.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        x.var(-1, keepdims=True) + 1e-5)
    assert jnp.allclose(onn.layer_norm(x, g, b), ref, atol=1e-5)


def test_rtc_pallas_module():
    def axpy_kernel(x_ref, y_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0 + y_ref[:]

    mod = mx.rtc.PallasModule(axpy=axpy_kernel)
    kern = mod.get_kernel("axpy")
    x = mx.np.array(np.arange(8, dtype=np.float32))
    y = mx.np.array(np.ones(8, np.float32))
    out = kern.launch([x, y], out_shape=(8,), interpret=True)
    assert np.allclose(out.asnumpy(), np.arange(8) * 2 + 1)
    # compile cache hit on relaunch
    out2 = kern.launch([x, y], out_shape=(8,), interpret=True)
    assert np.allclose(out2.asnumpy(), out.asnumpy())
    with pytest.raises(KeyError):
        mod.get_kernel("nope")
    with pytest.raises(RuntimeError):
        mx.rtc.CudaModule("__global__ void k() {}")
    with pytest.raises(TypeError):
        mx.rtc.PallasModule("source text")


def test_numpy_array_function_protocol():
    x = mx.np.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    # official numpy function on an NDArray routes through the protocol
    out = np.concatenate([x, x], axis=0)
    assert out.shape == (4, 3)
    assert float(np.asarray(x).sum()) == 15.0


def test_numpy_fallback_namespace():
    # an op with no jnp twin actually exercises the host fallback
    from mxnet_tpu import np as mnp
    assert not hasattr(jnp, "in1d")      # host-only: hits __getattr__
    a = mx.np.array(np.array([1, 2, 3], np.int32))
    b = mx.np.array(np.array([2, 4], np.int32))
    out = mnp.in1d(a, b)
    assert isinstance(out, type(a))
    assert list(out.asnumpy()) == [False, True, False]
    with pytest.raises(AttributeError):
        mnp.definitely_not_an_op


def test_numpy_protocol_nested_sequences():
    # nested NDArrays inside sequences must not re-dispatch (np.block)
    x = mx.np.array(np.ones((2, 2), np.float32))
    out = np.block([[x, x], [x, x]])
    assert np.asarray(out).shape == (4, 4)


def test_rtc_blocked_launch_and_dtype_cache():
    def double_kernel(x_ref, o_ref):
        o_ref[:] = (x_ref[:] * 2.0).astype(o_ref.dtype)

    mod = mx.rtc.PallasModule(double=double_kernel)
    kern = mod.get_kernel("double")
    x = mx.np.array(np.arange(16, dtype=np.float32))
    out = kern.launch([x], grid=(2,), block_shapes=[(8,)],
                      out_shape=(16,), interpret=True)
    assert np.allclose(out.asnumpy(), np.arange(16) * 2)
    # block_shapes without grid is an explicit error
    with pytest.raises(ValueError):
        kern.launch([x], block_shapes=[(8,)], out_shape=(16,),
                    interpret=True)
    # changing out_dtype must not reuse the stale executable
    out_i = kern.launch([x], out_shape=(16,), out_dtype=jnp.int32,
                        interpret=True)
    assert out_i.asnumpy().dtype == np.int32


# Interpret mode computes in exact float32 EXCEPT where the kernels round
# themselves: the MXU operands (q·scale, k, v, g, p, ds) go to bfloat16,
# which is what XLA's DEFAULT precision does to the composition's float32
# dots on the chip.  Each rounding is 2**-9 relative, so against the
# float32 reference and its autodiff the tolerance is 2e-2 of the
# largest value; against a reference that rounds the forward's four
# operands the same way the forward agrees to float32 noise (1e-5).
_ATTN_RTOL_BF16_OPERANDS = 2e-2
_ATTN_ATOL_F32 = 1e-5


def _attention_ref_bf16_operands(q, k, v, scale):
    bf = jnp.bfloat16
    s = jnp.einsum("bhqd,bhkd->bhqk", (q * scale).astype(bf), k.astype(bf),
                   preferred_element_type=jnp.float32)
    e = jnp.exp(s - s.max(-1, keepdims=True))
    return jnp.einsum("bhqk,bhkd->bhqd", e.astype(bf), v.astype(bf),
                      preferred_element_type=jnp.float32) \
        / e.sum(-1, keepdims=True)


def _close(got, want, rtol):
    err = float(jnp.abs(got - want).max())
    assert err <= rtol * float(jnp.abs(want).max()), err


@pytest.mark.parametrize("T", [128, 512])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_attention_fused_forward_and_vjp(head_dim, T):
    """Both layouts of the fused attention — `attention_fused` over
    (B, H, L, D) and `self_attention_fused` over a packed (B, T, 3·H·D)
    projection — against `_attention_ref` and its autodiff: o, dq, dk,
    dv (tests/test_chip_compile.py shows that no (L, L) buffer exists)."""
    B, H = 1, 2
    rng = np.random.RandomState(head_dim + T)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, T, head_dim)
                              .astype("float32")) for _ in range(4))
    scale = 0.5 / np.sqrt(head_dim)      # not the default, not a power of 2

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, scale) * g)

    out = pk.attention_fused(q, k, v, scale)
    _close(out, pk._attention_ref(q, k, v, scale), _ATTN_RTOL_BF16_OPERANDS)
    assert jnp.allclose(out, _attention_ref_bf16_operands(q, k, v, scale),
                        atol=_ATTN_ATOL_F32)
    grad = jax.jit(jax.grad(loss(pk.attention_fused), argnums=(0, 1, 2)))
    want = jax.grad(loss(pk._attention_ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(q, k, v), want):
        _close(a, b, _ATTN_RTOL_BF16_OPERANDS)

    # the packed layout: same heads, read in place
    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(B, T, H * head_dim)

    qkv = jnp.concatenate([pack(q), pack(k), pack(v)], axis=-1)

    def packed_ref(qkv):
        return pack(pk._attention_ref(*pk._split_heads(qkv, H),
                                      head_dim ** -0.5))

    out = pk.self_attention_fused(qkv, H)
    _close(out, packed_ref(qkv), _ATTN_RTOL_BF16_OPERANDS)
    assert jnp.allclose(out, pack(_attention_ref_bf16_operands(
        q, k, v, head_dim ** -0.5)), atol=_ATTN_ATOL_F32)
    got = jax.grad(lambda a: jnp.sum(
        pk.self_attention_fused(a, H) * pack(g)))(qkv)
    want = jax.grad(lambda a: jnp.sum(packed_ref(a) * pack(g)))(qkv)
    _close(got, want, _ATTN_RTOL_BF16_OPERANDS)


def test_attention_fused_cross_lengths():
    """Lq != Lk (the (B, H, L, D) entry point serves cross-attention)."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 2, 128, 128).astype("float32"))
    k = jnp.asarray(rng.randn(2, 2, 256, 128).astype("float32"))
    v = jnp.asarray(rng.randn(2, 2, 256, 128).astype("float32"))
    scale = 1 / np.sqrt(128)
    _close(pk.attention_fused(q, k, v, scale),
           pk._attention_ref(q, k, v, scale), _ATTN_RTOL_BF16_OPERANDS)
    got = jax.grad(lambda *a: jnp.sum(pk.attention_fused(*a, scale) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(pk._attention_ref(*a, scale) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        _close(a, b, _ATTN_RTOL_BF16_OPERANDS)
