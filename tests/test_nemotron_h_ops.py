"""The operators Nemotron-H brought (ops/nn.py, parallel/moe.py, gluon.nn,
gluon.block.recompute) against plain formulations at small sizes on the
CPU: the chunked scan against the recurrence, the blocked causal GQA against
the plain one, the blocked loss, the norms, and the expert shares against
the uncut layer.  The reference is chipbench/reference/nemotron_h.py."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon.block import recompute
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.ops import nn as ops
from mxnet_tpu.parallel.moe import moe_topk_held

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "nemotron_ref_ops",
    os.path.join(REPO, "chipbench", "reference", "nemotron_h.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------------------ the operators
def _ssm_inputs(t=16, h=4, p=8, g=2, n=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (2, t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, t, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (2, t, g, n)),
            jax.random.normal(ks[4], (2, t, g, n)),
            jax.random.normal(ks[5], (h,)))


@pytest.mark.parametrize("chunk", [4, 16, 128])
def test_chunked_scan_is_the_recurrence(chunk):
    """Forward and gradients; ``chunk`` 128 does not divide 16: one chunk."""
    args = _ssm_inputs()
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def chunked(*a):
        return ops.ssd_chunked(*a, chunk=chunk)

    def recurrence(*a):
        return jax.vmap(ref.ssm_recurrence,
                        in_axes=(0, 0, None, 0, 0, None))(*a)
    with jax.default_matmul_precision("highest"):
        assert _err(chunked(*args), recurrence(*args)) < 1e-5
        got = jax.grad(lambda *a: (chunked(*a) * w).sum(),
                       argnums=range(6))(*args)
        want = jax.grad(lambda *a: (recurrence(*a) * w).sum(),
                        argnums=range(6))(*args)
    for a, b in zip(got, want):
        assert _err(a, b) < 1e-4


def test_scan_decays_do_not_overflow_over_a_long_strongly_decaying_chunk():
    x, dt, a, b, c, d = _ssm_inputs(t=64)
    y = ops.ssd_chunked(x, dt * 50.0, a * 20.0, b, c, d, chunk=64)
    assert bool(jnp.isfinite(y).all())


def _plain_attention(q, k, v):
    t, h, g = q.shape[1], q.shape[2], k.shape[2]
    kk, vv = jnp.repeat(k, h // g, axis=2), jnp.repeat(v, h // g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


@pytest.mark.parametrize("blocks", [(8, 16), (16, 8), (8, 8), (512, 1024)])
def test_blocked_causal_gqa_is_the_plain_one(blocks):
    """(512, 1024) divide nothing here: one block each way."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 8))
    k = jax.random.normal(ks[1], (2, 64, 2, 8))
    v = jax.random.normal(ks[2], (2, 64, 2, 8))
    w = jax.random.normal(ks[3], q.shape)

    def blocked(*a):
        return ops.causal_gqa_attention(*a, q_block=blocks[0],
                                        k_block=blocks[1])
    with jax.default_matmul_precision("highest"):
        assert _err(blocked(q, k, v), _plain_attention(q, k, v)) < 1e-5
        got = jax.grad(lambda *a: (blocked(*a) * w).sum(),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (_plain_attention(*a) * w).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert _err(a, b) < 1e-5


def test_causal_conv1d_and_gated_norm_are_the_references():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (2, 16, 12))
    w, b = jax.random.normal(ks[1], (12, 4)), jax.random.normal(ks[2], (12,))
    want = jax.vmap(ref.causal_conv1d, in_axes=(0, None, None))(x, w, b)
    assert _err(ops.causal_conv1d(x, w, b), want) < 1e-6
    # nothing of a later step reaches an earlier one
    later = ops.causal_conv1d(x.at[:, 9:].set(0.0), w, b)
    assert bool((later[:, :9] == ops.causal_conv1d(x, w, b)[:, :9]).all())
    z, g = jax.random.normal(ks[3], x.shape), jax.random.normal(ks[4], (12,))
    got = ops.gated_group_rms_norm(x, z, g, groups=3)
    want = ref.rms_norm((x * ref.silu(z)).reshape(2, 16, 3, 4), 1.0) \
        .reshape(x.shape) * g
    assert _err(got, want) < 1e-6


@pytest.mark.parametrize("block", [8, 1024])
def test_linear_cross_entropy_is_log_softmax_of_the_product(block):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(ks[0], (2, 16, 12))
    w = jax.random.normal(ks[1], (40, 12))
    y = jax.random.randint(ks[2], (2, 16), 0, 40)

    def plain(h, w):
        logp = jax.nn.log_softmax(h @ w.T, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]
    with jax.default_matmul_precision("highest"):
        got = ops.linear_cross_entropy(h, w, y, block=block)
        assert got.shape == (2, 16) and _err(got, plain(h, w)) < 1e-6
        g = jax.grad(lambda h, w: ops.linear_cross_entropy(
            h, w, y, block=block).sum(), argnums=(0, 1))(h, w)
        gp = jax.grad(lambda h, w: plain(h, w).sum(), argnums=(0, 1))(h, w)
    assert _err(g[0], gp[0]) < 1e-5 and _err(g[1], gp[1]) < 1e-5


def test_an_offered_product_is_claimed_once_and_only_by_identity():
    a, h, w = jnp.ones((2, 3)), jnp.ones((2, 4)), jnp.ones((3, 4))
    ops.offer_product(a, h, w)
    assert ops.claim_product(jnp.ones((2, 3))) is None     # another array
    assert ops.claim_product(a) is None                    # offer is spent
    ops.offer_product(a, h, w)
    assert ops.claim_product(a) == (h, w)
    assert ops.claim_product(a) is None


def test_rmsnorm_blocks_and_relu2_activation():
    x = mx.np.array(onp.random.RandomState(0).randn(2, 5, 8).astype("f4"))
    norm = nn.RMSNorm(in_channels=8)
    norm.initialize()
    want = ref.rms_norm(x._data, 1.0)
    assert _err(norm(x)._data, want) < 1e-6
    gated = nn.GatedGroupRMSNorm(groups=2, in_channels=8)
    gated.initialize()
    want = ref.rms_norm((x._data * ref.silu(x._data)).reshape(2, 5, 2, 4),
                        1.0).reshape(2, 5, 8)
    assert _err(gated(x, x)._data, want) < 1e-6
    act = nn.Activation("relu2")
    assert _err(act(x)._data, ref.relu2(x._data)) == 0.0


def test_recompute_carries_aux_state_out_of_the_checkpoint():
    """BatchNorm's running statistics written inside ``recompute`` reach the
    fused step's write-back, and the gradients are those of a plain call."""
    class Net(nn.HybridBlock):
        def __init__(self, re):
            super().__init__()
            self.re = re
            self.fc = nn.Dense(6, in_units=6)
            self.bn = nn.BatchNorm(in_channels=6)
            self.out = nn.Dense(3, in_units=6)

        def forward(self, x):
            body = lambda x: self.bn(self.fc(x))
            return self.out(recompute(body, x) if self.re else body(x))

    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.randn(8, 6).astype("f4"))
    y = mx.np.array(rs.randint(0, 3, (8,)).astype("int32"))
    seen = []
    for re in (True, False):
        mx.seed(5)
        net = Net(re)
        net.initialize()
        net.hybridize()
        step = Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}).fuse_step(
                           SoftmaxCrossEntropyLoss())
        losses = [float(step(x, y).asnumpy()) for _ in range(3)]
        seen.append((losses, net.bn.running_mean.data().asnumpy(),
                     net.fc.weight.data().asnumpy()))
    assert onp.abs(seen[0][1]).max() > 0          # the statistics moved
    for a, b in zip(seen[0], seen[1]):
        onp.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ expert shares
def _expert_layer(e=32, d=16, f=8, s=48, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (s, d)),
            jax.random.normal(ks[1], (e, d)) * 0.5,
            jax.random.normal(ks[2], (e, d, f)) * 0.3,
            jax.random.normal(ks[3], (e, f, d)) * 0.3)


def test_the_shares_of_all_16_chips_add_up_to_the_uncut_expert_layer():
    """32 experts over 16 chips, 2 a chip: the routed parts of every share
    plus the shared expert, counted once, are the reference's whole layer."""
    x, rw, up, down = _expert_layer()
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    su = jax.random.normal(ks[0], (12, 16)) * 0.3
    sd = jax.random.normal(ks[1], (16, 12)) * 0.3
    cfg = dict(num_experts_per_tok=6, routed_scaling_factor=2.5,
               norm_topk_prob=True, experts_held=(0, 32))
    w = {"router_weight": rw, "correction_bias": jnp.zeros(32),
         "experts_up": up, "experts_down": down,
         "shared_up.weight": su, "shared_down.weight": sd}
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x, w, cfg)
        total = ref.relu2(x @ su.T) @ sd.T
        routed = 0
        share = jax.jit(lambda up, down, first: moe_topk_held(
            x, rw, jnp.zeros(32), up, down, (first, 2), 6, 2.5,
            act=ops.relu2, slot_rows=8))
        for chip in range(16):
            first = 2 * chip
            y, load = share(up[first:first + 2], down[first:first + 2], first)
            total = total + y
            routed += int(load[first:first + 2].sum())
    assert routed == 48 * 6                       # every pair held once
    assert _err(total, whole) < 1e-5


@pytest.mark.parametrize("slot_rows", [16, 20, 48, None])
def test_every_token_to_one_expert_none_dropped(slot_rows):
    """All 48 tokens choose experts 5, 6, 7 (the bias decides): a top-1
    layer with a capacity would drop most; here each held expert serves all
    48, whatever the rows a round gives it (three rounds of 16, 20 + 20 + 8,
    one of 48; the default is 256 rows, cut to the 48 tokens there are)."""
    x, _, up, down = _expert_layer(e=16)
    bias = jnp.zeros(16).at[5].set(9.0).at[6].set(8.0).at[7].set(7.0)
    rw = jnp.zeros((16, 16))
    with jax.default_matmul_precision("highest"):
        y, load = moe_topk_held(x, rw, bias, up[4:8], down[4:8], (4, 4), 3,
                                2.5, act=ops.relu2,
                                slot_rows=slot_rows)
        want = sum(2.5 / 3 * (ops.relu2(x @ up[e]) @ down[e])
                   for e in (5, 6, 7))
    assert load.tolist() == [0] * 5 + [48] * 3 + [0] * 8
    assert _err(y, want) < 1e-5


def test_a_share_that_holds_none_of_the_chosen_experts_adds_nothing():
    x, rw, up, down = _expert_layer(e=16)
    bias = jnp.zeros(16).at[0].set(9.0).at[1].set(8.0).at[2].set(7.0)
    y, load = moe_topk_held(x, jnp.zeros((16, 16)), bias, up[8:12],
                            down[8:12], (8, 4), 3, 2.5, act=ops.relu2)
    assert float(jnp.abs(y).max()) == 0.0 and int(load.sum()) == 48 * 3


def test_the_work_of_the_routed_experts_does_not_follow_the_routing():
    """Every held expert serves one slot of slot_rows rows whatever the
    routing, while none is sent more than slot_rows tokens: a dense product
    of that shape and no grouped one in the compiled program, for an even
    and an uneven router alike, and the loads are counted exactly."""
    x, rw, up, down = _expert_layer(e=16)
    fn = jax.jit(lambda rw, bias: moe_topk_held(
        x, rw, bias, up[4:8], down[4:8], (4, 4), 3, 2.5, act=ops.relu2,
        slot_rows=32))
    even = fn(rw, jnp.zeros(16))
    uneven = fn(rw, jnp.zeros(16).at[5].set(0.3))
    assert int(uneven[1][5]) > int(even[1][5]) and int(uneven[1].max()) <= 32
    for y, load in (even, uneven):
        assert int(load.sum()) == 48 * 3
    hlo = fn.lower(rw, jnp.zeros(16)).compile().as_text()
    assert "ragged" not in hlo and "f32[32,16]" in hlo


def _shapes_of(jaxpr, found=None):
    """Every array shape a (closed) jaxpr makes, inner jaxprs included."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.update(tuple(v.aval.shape) for v in eqn.outvars
                     if hasattr(v.aval, "shape"))
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(q, "jaxpr", q)
                if hasattr(inner, "eqns"):
                    _shapes_of(inner, found)
    return found


@pytest.mark.parametrize("slot_rows", [16, 48])
def test_the_backward_pass_stacks_no_copy_of_x_over_the_held_experts(
        slot_rows):
    """What a ``cond`` keeps for its branch (x, an expert's weights) lives
    for one expert, and for one further slot, at a time: no array of
    (experts held, tokens, width) or (further slots, tokens, width), and no
    per-slot stack of an expert's weights, in the gradient's program - they
    were 1.6 GB of a step at 8192 tokens (PERF.md section 4, PR 34).  The
    gradients are those of the plain sum over the experts."""
    x, rw, up, down = _expert_layer(e=16)
    bias = jnp.zeros(16).at[5].set(9.0).at[6].set(8.0).at[7].set(7.0)
    args = (x, up[4:8], down[4:8])

    def held(x, up, down):
        return moe_topk_held(x, jnp.zeros((16, 16)), bias, up, down, (4, 4),
                             3, 2.5, act=ops.relu2,
                             slot_rows=slot_rows)[0].sum()

    def plain(x, up, down):
        return sum(2.5 / 3 * (ops.relu2(x @ up[e]) @ down[e]).sum()
                   for e in (1, 2, 3))
    s, d = x.shape
    slots = -(-s // slot_rows) - 1
    shapes = _shapes_of(jax.make_jaxpr(jax.grad(held, (0, 1, 2)))(*args).jaxpr)
    assert (4, s, d) not in shapes
    assert (slots, s, d) not in shapes
    assert (slots,) + up.shape[1:] not in shapes
    with jax.default_matmul_precision("highest"):
        got = jax.grad(held, (0, 1, 2))(*args)
        want = jax.grad(plain, (0, 1, 2))(*args)
    for g, w in zip(got, want):
        assert _err(g, w) < 1e-5


ROUTINGS = {
    # every token chooses experts 5, 6, 7: expert 4 is sent nothing, each of
    # the others all 48 tokens (further slots unless a slot holds 48)
    "three_experts": jnp.zeros(16).at[5].set(9.0).at[6].set(8.0).at[7].set(7.0),
    # expert 5 is sent every token (exactly a slot of 48), expert 4 none,
    # the others what the router gives them
    "one_full_one_empty": jnp.zeros(16).at[5].set(9.0).at[4].set(-9.0),
    "as_routed": jnp.zeros(16),
}


def _held_and_plain(bias, slot_rows):
    """A share of 4 of 16 experts, 48 tokens 12 wide, through
    ``moe_topk_held`` and the plain reference told the same share (its
    shared expert's weights zero), both as a scalar function of x, the
    router weight, up and down."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (48, 12))
    rw = jax.random.normal(ks[1], (16, 12)) * 0.5
    up = jax.random.normal(ks[2], (4, 12, 8)) * 0.3
    down = jax.random.normal(ks[3], (4, 8, 12)) * 0.3
    gy = jax.random.normal(ks[4], (48, 12))
    cfg = dict(num_experts_per_tok=3, routed_scaling_factor=2.5,
               norm_topk_prob=True, experts_held=(4, 4))

    def held(x, rw, up, down):
        y, _ = moe_topk_held(x, rw, bias, up, down, (4, 4), 3, 2.5,
                             act=ops.relu2, slot_rows=slot_rows)
        return jnp.sum(y * gy)

    def plain(x, rw, up, down):
        w = {"router_weight": rw, "correction_bias": bias,
             "experts_up": up, "experts_down": down,
             "shared_up.weight": jnp.zeros((2, 12)),
             "shared_down.weight": jnp.zeros((12, 2))}
        return jnp.sum(ref.experts(x, w, cfg) * gy)

    return held, plain, (x, rw, up, down)


@pytest.mark.parametrize("slot_rows", [16, 20, 48, None])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_hand_backward_is_the_plain_sum_s_gradient(routing, slot_rows):
    """x, the router weight (through the sorted pairs' weights), up and
    down: the backward pass written by hand over the sorted slots gives the
    gradients autodiff gives the plain sum over the held experts, whatever
    the slots hold: nothing, exactly their rows, or several slots' worth."""
    held, plain, args = _held_and_plain(ROUTINGS[routing], slot_rows)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(held, (0, 1, 2, 3)))(*args)
        want = jax.value_and_grad(plain, (0, 1, 2, 3))(*args)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert float(jnp.abs(w).max()) > 0
        assert _err(g, w) < 1e-5


def _eqns_of(jaxpr):
    """Every equation of a jaxpr, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(q, "jaxpr", q)
                if hasattr(inner, "eqns"):
                    yield from _eqns_of(inner)


def _row_moves(fn, args, shape):
    """``(gathers, scatter-adds)`` over an array of ``shape`` in the
    gradient's program, each as (params, whether the operand is an array of
    zeros made beside it)."""
    found = {"gather": [], "scatter-add": []}
    for jaxpr, eqn in _eqns_of(jax.make_jaxpr(
            jax.grad(fn, (0, 1, 2, 3)))(*args).jaxpr):
        name = eqn.primitive.name
        if name in found and tuple(eqn.invars[0].aval.shape) == shape:
            fresh = any(e.primitive.name == "broadcast_in_dim"
                        and eqn.invars[0] in e.outvars for e in jaxpr.eqns)
            found[name].append((eqn.params, fresh))
    return found["gather"], found["scatter-add"]


@pytest.mark.parametrize("slot_rows", [16, 48])
def test_every_row_is_moved_in_place_by_an_operation_told_it_is_alone(
        slot_rows):
    """The gradient's program scatter-adds into no fresh (S, D) zero array
    (autodiff's transpose of a gather did, once an expert, and added the
    result to the running sum), and every gather and scatter-add over the
    (S, D) arrays is told its indices are unique (the gathers: sorted too)
    - which they are, since a dead row j carries the index S + j."""
    held, _, args = _held_and_plain(ROUTINGS["three_experts"], slot_rows)
    gathers, scatters = _row_moves(held, args, (48, 12))
    # first slot and the loop's body: x forward; the cotangent and x backward
    assert len(gathers) == 6 and len(scatters) == 4
    for params, fresh in gathers + scatters:
        assert params["unique_indices"] and not fresh
    # told its indices are sorted, XLA's TPU scatter is slower and rounds
    # (PERF.md section 6, PR 35): the gathers alone are told
    assert all(params["indices_are_sorted"] for params, _ in gathers)
    assert not any(params["indices_are_sorted"] for params, _ in scatters)


def test_dead_rows_carry_indices_of_their_own():
    from mxnet_tpu.parallel.moe import _slot_pairs
    token = jnp.pad(jnp.array([1, 4, 7, 2, 3], jnp.int32), (0, 4))
    w = jnp.pad(jnp.arange(1.0, 6.0), (0, 4))
    tok, ws, at, live, n = _slot_pairs(token, w, 3, 0, 0, 4, 8)
    assert tok.tolist() == [1, 4, 7, 8 + 3] and ws.tolist() == [1, 2, 3, 0]
    assert int(n) == 3
    tok, ws, at, live, n = _slot_pairs(token, w, 2, 3, 0, 4, 8)
    assert tok.tolist() == [2, 3, 8 + 2, 8 + 3] and int(at) == 3
    assert live.tolist() == [True, True, False, False] and int(n) == 2
    tok, _, at, _, n = _slot_pairs(token, w, 5, 0, 1, 4, 8)  # a further slot
    assert tok.tolist() == [3, 8 + 5, 8 + 6, 8 + 7] and int(at) == 4
    assert int(n) == 1
    assert bool((jnp.diff(tok) > 0).all())


def test_the_blocked_loss_keeps_its_log_sum_exp_for_the_backward_pass():
    """The backward loop reads the forward loop's log-sum-exp (one number a
    row) and takes no maximum again: the forward loop cannot be scheduled
    after the weight's update, which cost a copy of the head's weight (0.4
    GB in the Solar-Open2 step; PERF.md section 4, PR 34)."""
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 40)
    text = str(jax.make_jaxpr(jax.grad(
        lambda h, w: ops.linear_cross_entropy(h, w, y, block=16).sum(),
        (0, 1)))(h, w))
    assert text.count("reduce_max") == 1
    assert "f32[4,16]" in text                     # the rows' log-sum-exp
