"""Operator long-tail tests (docs/OP_PARITY.md work list, VERDICT r3
item 3): forward semantics against the reference's documented examples
plus gradient checks through the autograd tape."""
import numpy as onp
import pytest

import mxnet_tpu as mx
import mxnet_tpu.nd as nd
from mxnet_tpu import autograd

npx = mx.npx


def test_depth_to_space_reference_example():
    # matrix_op.cc:1085 documented example
    x = onp.arange(24, dtype=onp.float32).reshape(1, 4, 2, 3)
    want = onp.array([[[[0, 6, 1, 7, 2, 8],
                        [12, 18, 13, 19, 14, 20],
                        [3, 9, 4, 10, 5, 11],
                        [15, 21, 16, 22, 17, 23]]]], onp.float32)
    got = nd.depth_to_space(nd.array(x), 2).asnumpy()
    assert onp.array_equal(got, want)
    # inverse
    back = nd.space_to_depth(nd.array(want), 2).asnumpy()
    assert onp.array_equal(back, x)


def test_im2col_col2im_adjoint():
    rng = onp.random.RandomState(0)
    x = rng.rand(2, 3, 6, 6).astype(onp.float32)
    col = npx.im2col(nd.array(x), kernel=(3, 3), stride=(1, 1), pad=(1, 1))
    assert col.shape == (2, 27, 36)
    # col2im(im2col(x)) multiplies each pixel by its patch count
    back = npx.col2im(col, (6, 6), kernel=(3, 3), stride=(1, 1),
                      pad=(1, 1)).asnumpy()
    ones = npx.col2im(npx.im2col(nd.array(onp.ones_like(x)), (3, 3),
                                 (1, 1), (1, 1), (1, 1)), (6, 6),
                      kernel=(3, 3), stride=(1, 1), pad=(1, 1)).asnumpy()
    assert onp.allclose(back, x * ones, atol=1e-5)
    # im2col matches manual patch extraction at one site
    got = col.asnumpy()[0, :, 7]          # output position (1, 1)
    want = x[0, :, 0:3, 0:3].reshape(-1)  # pad=1: window starts at -1+1
    assert onp.allclose(got, want, atol=1e-6)


def test_unary_tail():
    x = onp.linspace(0.3, 3.0, 7).astype(onp.float32)
    a = nd.array(x)
    from scipy import special as sp
    assert onp.allclose(npx.digamma(a).asnumpy(), sp.digamma(x), atol=1e-4)
    assert onp.allclose(npx.rsqrt(a).asnumpy(), 1 / onp.sqrt(x), atol=1e-5)
    assert onp.allclose(npx.rcbrt(a).asnumpy(), 1 / onp.cbrt(x), atol=1e-5)
    assert onp.allclose(npx.log_sigmoid(a).asnumpy(),
                        onp.log(1 / (1 + onp.exp(-x))), atol=1e-5)
    assert onp.allclose(npx.hard_sigmoid(a).asnumpy(),
                        onp.clip(0.2 * x + 0.5, 0, 1), atol=1e-6)
    s = npx.softmin(nd.array(x.reshape(1, -1))).asnumpy()
    assert onp.allclose(s, onp.exp(-x) / onp.exp(-x).sum(), atol=1e-5)


def test_moments_and_khatri_rao():
    rng = onp.random.RandomState(0)
    x = rng.rand(3, 5).astype(onp.float32)
    mean, var = npx.moments(nd.array(x), axes=(1,))
    assert onp.allclose(mean.asnumpy(), x.mean(1), atol=1e-5)
    assert onp.allclose(var.asnumpy(), x.var(1), atol=1e-5)
    a = rng.rand(2, 4).astype(onp.float32)
    b = rng.rand(3, 4).astype(onp.float32)
    kr = npx.khatri_rao(nd.array(a), nd.array(b)).asnumpy()
    want = onp.vstack([onp.kron(a[:, i], b[:, i]) for i in range(4)]).T
    assert onp.allclose(kr, want, atol=1e-5)


def test_straight_through_and_gradmult():
    x = nd.array(onp.array([-1.2, 0.3, 2.7], onp.float32))
    x.attach_grad()
    with autograd.record():
        y = (npx.round_ste(x) * 2).sum()
    y.backward()
    assert onp.allclose(x.grad.asnumpy(), 2.0)     # identity grad × 2
    x2 = nd.array(onp.array([1.0, -2.0], onp.float32))
    x2.attach_grad()
    with autograd.record():
        y2 = npx.gradientmultiplier(x2, -0.5).sum()
    y2.backward()
    assert onp.allclose(x2.grad.asnumpy(), -0.5)   # gradient reversal


def test_regression_outputs():
    d = onp.array([[0.5, 2.0]], onp.float32)
    l = onp.array([[1.0, 1.0]], onp.float32)
    x = nd.array(d)
    x.attach_grad()
    with autograd.record():
        out = nd.LinearRegressionOutput(x, nd.array(l))
    out.backward()
    assert onp.allclose(out.asnumpy(), d)
    assert onp.allclose(x.grad.asnumpy(), d - l, atol=1e-6)
    x2 = nd.array(d)
    x2.attach_grad()
    with autograd.record():
        out2 = nd.LogisticRegressionOutput(x2, nd.array(l))
    out2.backward()
    sig = 1 / (1 + onp.exp(-d))
    assert onp.allclose(out2.asnumpy(), sig, atol=1e-6)
    assert onp.allclose(x2.grad.asnumpy(), sig - l, atol=1e-6)
    x3 = nd.array(d)
    x3.attach_grad()
    with autograd.record():
        out3 = nd.MAERegressionOutput(x3, nd.array(l))
    out3.backward()
    assert onp.allclose(x3.grad.asnumpy(), onp.sign(d - l), atol=1e-6)


def test_index_ops():
    x = nd.array(onp.zeros((4, 3), onp.float32))
    upd = nd.array(onp.ones((2, 3), onp.float32))
    out = npx.index_copy(x, nd.array(onp.array([1, 3])), upd)
    assert onp.allclose(out.asnumpy()[[1, 3]], 1.0)
    assert onp.allclose(out.asnumpy()[[0, 2]], 0.0)
    # duplicate indices accumulate for index_add
    out2 = npx.index_add(nd.array(onp.zeros(3, onp.float32)),
                         nd.array(onp.array([0, 0, 2])),
                         nd.array(onp.array([1., 1., 5.], onp.float32)))
    assert onp.allclose(out2.asnumpy(), [2.0, 0.0, 5.0])


def test_attention_interleaved_and_sldwin():
    rng = onp.random.RandomState(0)
    L, B, H, D = 5, 2, 2, 3
    qkv = nd.array(rng.rand(L, B, H * D * 3).astype(onp.float32))
    score = npx.interleaved_matmul_selfatt_qk(qkv, H)
    assert score.shape == (B * H, L, L)
    att = nd.array(rng.rand(B * H, L, L).astype(onp.float32))
    ctx = npx.interleaved_matmul_selfatt_valatt(qkv, att, H)
    assert ctx.shape == (L, B, H * D)
    q = nd.array(rng.rand(2, 6, H, D).astype(onp.float32))
    k = nd.array(rng.rand(2, 6, H, D).astype(onp.float32))
    dil = nd.array(onp.array([1, 2], onp.int32))
    sc = npx.sldwin_atten_score(q, k, dil, 2, symmetric=True)
    assert sc.shape == (2, 6, H, 5)
    m = npx.sldwin_atten_mask_like(sc, dil, nd.array(
        onp.array([6, 4], onp.int32)), 2, symmetric=True)
    assert m.shape == sc.shape and set(onp.unique(m.asnumpy())) <= {0., 1.}
    v = nd.array(rng.rand(2, 6, H, D).astype(onp.float32))
    cx = npx.sldwin_atten_context(sc, v, dil, 2, symmetric=True)
    assert cx.shape == (2, 6, H, D)


def test_interleaved_selfatt_causal_parity():
    """ops/attention.py: interleaved qkv scores with causal=True +
    softmax + valatt == an explicit-mask attention over the
    de-interleaved heads."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    L, B, H, D = 16, 2, 2, 8
    rs = onp.random.RandomState(11)
    qkv = jnp.asarray(rs.randn(L, B, H * 3 * D), jnp.float32)
    scores = att.interleaved_matmul_selfatt_qk(qkv, H, causal=True)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    got = att.interleaved_matmul_selfatt_valatt(
        qkv, probs.astype(qkv.dtype), H)          # (L, B, H*D)

    t5 = qkv.reshape(L, B, H, 3, D).transpose(1, 2, 0, 3, 4)  # (B,H,L,3,D)
    q, k, v = t5[..., 0, :], t5[..., 1, :], t5[..., 2, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / float(D) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    ref = ref.transpose(2, 0, 1, 3).reshape(L, B, H * D)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-5, atol=1e-5)

    # masked scores really are the finite sentinel, not -inf (a true
    # -inf NaNs fully-masked lanes through inf - inf compositions)
    assert onp.isfinite(onp.asarray(scores)).all()


def test_boxes_encode_decode_matching():
    # bounding_box.cc documented example
    s = nd.array(onp.array([[0.5, 0.6], [0.1, 0.2], [0.3, 0.4]],
                           onp.float32))
    x, y = nd.contrib.bipartite_matching(s, is_ascend=False,
                                         threshold=1e-12)
    assert list(x.asnumpy().astype(int)) == [1, -1, 0]
    assert list(y.asnumpy().astype(int)) == [2, 0]
    anchors = nd.array(onp.array([[[0.1, 0.1, 0.3, 0.4]]], onp.float32))
    refs = nd.array(onp.array([[[0.12, 0.15, 0.28, 0.38]]], onp.float32))
    t, m = nd.contrib.box_encode(nd.array(onp.ones((1, 1), onp.float32)),
                                 nd.array(onp.zeros((1, 1), onp.float32)),
                                 anchors, refs, means=(0, 0, 0, 0),
                                 stds=(1, 1, 1, 1))
    dec = nd.contrib.box_decode(t, anchors, format="corner")
    assert onp.allclose(dec.asnumpy(), refs.asnumpy(), atol=1e-5)


def test_roi_align_and_pooling_resize():
    const = nd.array(onp.full((1, 2, 8, 8), 3.0, onp.float32))
    rois = nd.array(onp.array([[0, 0, 0, 8, 8]], onp.float32))
    out = nd.contrib.ROIAlign(const, rois, (4, 4), aligned=True)
    assert out.shape == (1, 2, 4, 4) and onp.allclose(out.asnumpy(), 3.0)
    rr = nd.array(onp.array([[0, 4, 4, 8, 8, 0]], onp.float32))
    out2 = nd.contrib.RROIAlign(const, rr, (2, 2))
    assert onp.allclose(out2.asnumpy(), 3.0, atol=1e-5)
    x = nd.array(onp.random.RandomState(0).rand(2, 3, 8, 8)
                 .astype(onp.float32))
    ap = nd.contrib.AdaptiveAvgPooling2D(x, (4, 4))
    want = x.asnumpy().reshape(2, 3, 4, 2, 4, 2).mean(axis=(3, 5))
    assert onp.allclose(ap.asnumpy(), want, atol=1e-5)
    br = nd.contrib.BilinearResize2D(x, height=8, width=8)
    assert onp.allclose(br.asnumpy(), x.asnumpy(), atol=1e-5)
    up = nd.UpSampling(x, 2)
    assert up.shape == (2, 3, 16, 16)


def test_legacy_linalg_zoo():
    rng = onp.random.RandomState(0)
    A = rng.rand(2, 4, 4).astype(onp.float32)
    B = rng.rand(2, 4, 4).astype(onp.float32)
    a, b = nd.array(A), nd.array(B)
    assert onp.allclose(nd.linalg.gemm2(a, b).asnumpy(), A @ B, atol=1e-5)
    spd = A @ A.transpose(0, 2, 1) + 4 * onp.eye(4, dtype=onp.float32)
    L = nd.linalg.potrf(nd.array(spd))
    Ln = L.asnumpy()
    assert onp.allclose(Ln @ Ln.transpose(0, 2, 1), spd, atol=1e-3)
    assert onp.allclose(nd.linalg.potri(L).asnumpy() @ spd, onp.eye(4),
                        atol=1e-3)
    xs = nd.linalg.trsm(L, b)
    assert onp.allclose(onp.tril(Ln) @ xs.asnumpy(), B, atol=1e-4)
    Q, Lw = nd.linalg.gelqf(a)
    assert onp.allclose(Lw.asnumpy() @ Q.asnumpy(), A, atol=1e-4)
    U, lam = nd.linalg.syevd(nd.array(spd))
    rec = U.asnumpy().transpose(0, 2, 1) @ (lam.asnumpy()[..., None]
                                            * U.asnumpy())
    assert onp.allclose(rec, spd, atol=1e-3)
    assert onp.allclose(
        nd.linalg.sumlogdiag(nd.array(spd)).asnumpy(),
        onp.log(onp.diagonal(spd, axis1=-2, axis2=-1)).sum(-1), atol=1e-4)
    # gradient flows
    av = nd.array(A)
    av.attach_grad()
    with autograd.record():
        out = nd.linalg.gemm2(av, b).sum()
    out.backward()
    assert onp.allclose(av.grad.asnumpy(),
                        onp.ones_like(A) @ B.transpose(0, 2, 1), atol=1e-4)


def test_npx_image_namespace():
    rng = onp.random.RandomState(0)
    img = nd.array(rng.randint(0, 255, (8, 10, 3)).astype(onp.uint8))
    t = npx.image.to_tensor(img)
    assert t.shape == (3, 8, 10) and float(t.asnumpy().max()) <= 1.0
    nrm = npx.image.normalize(t, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5))
    assert onp.allclose(nrm.asnumpy(), (t.asnumpy() - 0.5) / 0.5,
                        atol=1e-6)
    c = npx.image.crop(img, 2, 1, 4, 5)
    assert c.shape == (5, 4, 3)
    assert onp.array_equal(c.asnumpy(), img.asnumpy()[1:6, 2:6])
    r = npx.image.resize(img, (5, 4))
    assert r.shape == (4, 5, 3)
    f = npx.image.flip_left_right(img)
    assert onp.array_equal(f.asnumpy(), img.asnumpy()[:, ::-1])
    ab = npx.image.adjust_brightness(t, 2.0)
    assert onp.allclose(ab.asnumpy(), t.asnumpy() * 2.0, atol=1e-6)
    j = npx.image.random_color_jitter(img, 0.2, 0.2, 0.2, 0.1)
    assert j.shape == img.shape


def test_random_tail_distributions():
    mx.seed(3)
    r = mx.np.random
    b = r.binomial(6, 0.5, size=(4000,))
    assert abs(float(b.asnumpy().mean()) - 3.0) < 0.2
    d = r.dirichlet(onp.array([2.0, 2.0], onp.float32), size=(50,))
    assert onp.allclose(d.asnumpy().sum(-1), 1.0, atol=1e-5)
    nb = r.negative_binomial(3, 0.5, size=(4000,))
    assert abs(float(nb.asnumpy().mean()) - 3.0) < 0.5


def test_misc_tail():
    x = nd.array(onp.random.RandomState(0).rand(3, 4).astype(onp.float32))
    assert int(npx.size_array(x).asnumpy()[0]) == 12
    assert onp.allclose(npx.div_sqrt_dim(x).asnumpy(),
                        x.asnumpy() / 2.0, atol=1e-6)
    assert npx.shares_memory(x, x)
    assert not npx.shares_memory(x, nd.array(onp.ones((3, 4))))
    q = npx.quadratic(x, a=1.0, b=2.0, c=3.0)
    assert onp.allclose(q.asnumpy(),
                        x.asnumpy() ** 2 + 2 * x.asnumpy() + 3, atol=1e-5)
    with pytest.raises(ValueError):
        npx.constraint_check(nd.array(onp.array([True, False])), "bad")
    # hawkesll runs and returns finite ll + state
    N, K, T = 2, 3, 4
    ll, st = npx.hawkesll(
        nd.array(onp.full((N, K), 0.1, onp.float32)),
        nd.array(onp.full((N, K), 0.2, onp.float32)),
        nd.array(onp.full((N, K), 1.0, onp.float32)),
        nd.array(onp.zeros((N, K), onp.float32)),
        nd.array(onp.full((N, T), 0.5, onp.float32)),
        nd.array(onp.zeros((N, T), onp.int32)),
        nd.array(onp.array([4, 2], onp.int32)),
        nd.array(onp.array([3.0, 2.0], onp.float32)))
    assert onp.isfinite(ll.asnumpy()).all() and st.shape == (N, K)
    # edge_id over a tiny CSR graph
    indptr = onp.array([0, 2, 3], onp.int64)
    indices = onp.array([0, 1, 1], onp.int64)
    data = onp.array([10., 20., 30.], onp.float32)
    out = npx.edge_id(nd.array(indptr), nd.array(indices), nd.array(data),
                      nd.array(onp.array([0, 0, 1])),
                      nd.array(onp.array([1, 5, 1])))
    assert list(out.asnumpy()) == [20.0, -1.0, 30.0]


def test_dgl_graph_ops():
    # dgl_graph.cc:1137 documented subgraph example
    x = onp.array([[1, 0, 0, 2],
                   [3, 0, 4, 0],
                   [0, 5, 0, 0],
                   [0, 6, 7, 0]], onp.float32)
    g = nd.sparse.csr_matrix(nd.array(x))
    sub, mapping = nd.contrib.dgl_subgraph(
        g, onp.array([0, 1, 2]), return_mapping=True)
    assert onp.array_equal(sub.asnumpy(), [[1, 0, 0],
                                           [2, 0, 3],
                                           [0, 4, 0]])
    assert onp.array_equal(mapping.asnumpy(), [[1, 0, 0],
                                               [3, 0, 4],
                                               [0, 5, 0]])
    adj = nd.contrib.dgl_adjacency(g)
    assert onp.array_equal(adj.asnumpy(), (x != 0).astype(onp.float32))
    # neighbor sampling on the documented 5-clique
    data_np = onp.arange(1, 21, dtype=onp.float32)
    indices_np = onp.array([1, 2, 3, 4, 0, 2, 3, 4, 0, 1, 3, 4,
                            0, 1, 2, 4, 0, 1, 2, 3], onp.int64)
    indptr_np = onp.array([0, 4, 8, 12, 16, 20], onp.int64)
    a = nd.sparse.csr_matrix((data_np, indices_np, indptr_np),
                             shape=(5, 5))
    out = nd.contrib.dgl_csr_neighbor_uniform_sample(
        a, onp.array([0, 1, 2, 3, 4], onp.int64), num_args=2, num_hops=1,
        num_neighbor=2, max_num_vertices=5)
    verts, subg, layers = out
    assert verts.shape == (6,) and int(verts.asnumpy()[-1]) == 5
    sg = subg.asnumpy()
    assert sg.shape == (5, 5)
    assert all((sg[r] != 0).sum() == 2 for r in range(5))  # 2 per vertex
    assert onp.array_equal(layers.asnumpy(), onp.zeros(5))
    # compact a 6-max sample down to 5
    out6 = nd.contrib.dgl_csr_neighbor_uniform_sample(
        a, onp.array([0, 1, 2, 3, 4], onp.int64), num_hops=1,
        num_neighbor=2, max_num_vertices=6)
    comp = nd.contrib.dgl_graph_compact(
        out6[1], out6[0], graph_sizes=int(out6[0].asnumpy()[-1]),
        return_mapping=False)
    assert comp.shape == (5, 5)
    # non-uniform sampling runs and respects zero-probability exclusion
    prob = onp.array([1, 1, 0, 1, 1], onp.float32)
    outn = nd.contrib.dgl_csr_neighbor_non_uniform_sample(
        a, prob, onp.array([0], onp.int64), num_hops=1, num_neighbor=2,
        max_num_vertices=5)
    verts_n, sub_n, probs_n, layers_n = outn
    assert (sub_n.asnumpy()[0][2] == 0)   # vertex 2 never sampled from 0


def test_cast_storage_and_zipfian():
    x = onp.array([[0, 2.0], [1.5, 0]], onp.float32)
    c = nd.sparse.cast_storage(nd.array(x), "csr")
    assert c.stype == "csr" and onp.array_equal(c.asnumpy(), x)
    d = nd.sparse.cast_storage(c, "default")
    assert onp.array_equal(d.asnumpy(), x)
    rs = nd.sparse.cast_storage(nd.array(x), "row_sparse")
    assert rs.stype == "row_sparse"
    s, cnt = mx.np.random.unique_zipfian(1000, (16,))
    sn = s.asnumpy()
    assert len(set(sn.tolist())) == 16 and sn.max() < 1000
    samp, ct, cs = mx.np.random.rand_zipfian(
        nd.array(onp.array([1, 5], onp.int64)), 8, 1000)
    assert samp.shape == (8,) and ct.shape == (2,)


def test_image_copy_make_border():
    from mxnet_tpu import image as img
    x = onp.ones((2, 2, 3), onp.uint8) * 7
    out = img.copyMakeBorder(x, 1, 1, 2, 2, border_type=0, value=0)
    assert out.shape == (4, 6, 3)
    assert out[0].sum() == 0 and out[1, 2, 0] == 7
    rep = img.copyMakeBorder(x, 1, 0, 0, 0, border_type=1)
    assert onp.array_equal(rep[0], x[0])
