"""Autoregressive decode engine (mxnet_tpu/generate.py): the donated
ring-KV decode path vs recompute-from-scratch references, seek
(snapshot/restore) bit-for-bit replay, batched-vs-single parity, the
trace-time retrace hook, and DecodeBatcher join/leave/eviction — all
tiny models on CPU; the throughput row is ``bench.py --row generate``."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import generate as gen
from mxnet_tpu import telemetry
from mxnet_tpu.models import gpt
from mxnet_tpu.ops import nn as opsnn
from mxnet_tpu.serve.batcher import DecodeBatcher


def _engine(cfg, seed=0, **kw):
    params = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    return gen.DecodeEngine(params, cfg, **kw).warmup()


@pytest.fixture(scope="module")
def small():
    """2-layer engine, window == max_len (the ring never wraps)."""
    cfg = gpt.GPTConfig(vocab_size=53, hidden=32, layers=2, heads=2,
                        intermediate=64, max_len=32)
    return _engine(cfg, buckets=(1, 2), prompts=(8,))


def test_decode_matches_prefill_recompute(small):
    """The step path (ring cache, one token at a time) must emit the
    same greedy tokens as recomputing the full causal forward from
    scratch over the growing sequence — cache vs no-cache parity."""
    eng = small
    prompt = [3, 1, 4, 1, 5]
    out = eng.generate([prompt], max_new=8)[0]

    # fixed-shape reference: pad to the final length so the jit traces
    # once — causal masking makes the trailing zeros inert
    apply_fn = jax.jit(lambda t: gpt.apply(eng.params, eng.cfg, t))
    total = len(prompt) + 8
    toks = list(prompt)
    ref = []
    for _ in range(8):
        padded = jnp.zeros((1, total), jnp.int32)
        padded = padded.at[0, :len(toks)].set(jnp.asarray(toks, jnp.int32))
        logits = apply_fn(padded)
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        ref.append(nxt)
        toks.append(nxt)
    assert out == ref


def test_ring_wraparound_matches_sliding_window():
    """Generate past the window S: the ring overwrites oldest slots, so
    each new token attends exactly the last S tokens.  With ONE layer,
    cached K/V depend only on the token+position embeddings, so a
    plain-jnp sliding-window recompute (absolute position embeddings,
    causal attention inside the window) is an exact reference."""
    cfg = gpt.GPTConfig(vocab_size=47, hidden=32, layers=1, heads=2,
                        intermediate=64, max_len=64)
    eng = _engine(cfg, window=8, buckets=(1,), prompts=(8,))
    prompt = [7, 2, 1, 5, 3]
    max_new = 12                     # 17 total > S=8: wraps
    out = eng.generate([prompt], max_new=max_new)[0]

    def last_logits(all_toks):
        ctx = all_toks[-8:]                       # the ring's window
        pos0 = len(all_toks) - len(ctx)
        p = eng.params
        lay = p["layers"][0]
        e = p["embed"]
        x = jnp.take(e["tok"], jnp.asarray(ctx, jnp.int32), axis=0) \
            + e["pos"][pos0:pos0 + len(ctx)]
        x = x[None]                               # B=1
        B, T, D = x.shape
        H, hd = cfg.heads, D // cfg.heads
        h = opsnn.layer_norm(x, lay["ln1_g"], lay["ln1_b"])
        t5 = gpt._proj(h, lay["qkv"]).reshape(B, T, H, 3, hd)
        q, k, v = t5[..., 0, :], t5[..., 1, :], t5[..., 2, :]
        s = jnp.einsum("bthd,bshd->bhts", q, k) / float(hd) ** 0.5
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctx_v = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, D)
        x = x + gpt._proj(ctx_v, lay["out"])
        x = gpt._ffn(x, lay)
        return gpt._logits(p, x)[0, -1]

    toks = list(prompt)
    ref = []
    for _ in range(max_new):
        nxt = int(jnp.argmax(last_logits(toks)))
        ref.append(nxt)
        toks.append(nxt)
    assert out == ref


def test_seek_replay_bit_for_bit(small):
    """snapshot → decode on → restore → replay: same tokens AND the
    same cache bits as the continuous run — the seek contract."""
    eng = small
    toks = onp.zeros((1, 8), onp.int32)
    toks[0, :4] = [9, 2, 6, 1]
    sub = jax.random.PRNGKey(42)
    ctl = eng._prog("prefill", 1, 8)(
        eng.params, jnp.asarray(toks), jnp.asarray([4], onp.int32), sub)
    step = eng._prog("step", 1)
    for _ in range(3):
        ctl = step(eng.params, ctl)
    snap = gen.snapshot(ctl)         # host copy BEFORE the donating call
    cont, replay = [], []
    for _ in range(4):
        ctl = step(eng.params, ctl)
        cont.append(int(onp.asarray(ctl["tok"])[0]))
    end_a = gen.snapshot(ctl)
    ctl = gen.restore(snap)
    for _ in range(4):
        ctl = step(eng.params, ctl)
        replay.append(int(onp.asarray(ctl["tok"])[0]))
    end_b = gen.snapshot(ctl)
    assert cont == replay
    assert onp.array_equal(end_a["k"], end_b["k"])
    assert onp.array_equal(end_a["v"], end_b["v"])
    assert onp.array_equal(end_a["pos"], end_b["pos"])


def test_batched_equals_single(small):
    eng = small
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    batched = eng.generate(prompts, max_new=6)
    singles = [eng.generate([p], max_new=6)[0] for p in prompts]
    assert batched == singles


def test_zero_retraces_and_hook_counts(small):
    """Steady state retraces stay 0; a genuinely re-traced warmed key
    (program evicted behind the engine's back) IS counted."""
    eng = small
    base = eng.retraces
    eng.generate([[1, 2, 3]], max_new=4)
    eng.generate([[4, 5]], max_new=4)
    assert eng.retraces == base == 0

    key = ("step", 1, 0, eng._fp())
    assert key in eng._programs
    with eng._mu:
        del eng._programs[key]       # force the same key to trace again
    eng.generate([[1, 2, 3]], max_new=3)
    assert eng.retraces == 1
    with eng._mu:                    # leave the module-scoped engine clean
        eng.retraces = 0


def test_generate_refuses_past_max_len(small):
    with pytest.raises(ValueError):
        small.generate([[1] * 8], max_new=32 - 8 + 1)


def test_batcher_streams_and_evicts(small):
    """DecodeBatcher: streamed tokens equal the unbatched decode; a row
    whose position hits max_len - 1 is evicted (leaves early) instead
    of clamping into garbage."""
    telemetry.reset()
    eng = small
    with DecodeBatcher(eng, slots=2, name="t-gen") as bat:
        out = bat.submit([5, 3, 5], max_new=6)
        assert out == eng.generate([[5, 3, 5]], max_new=6)[0]

        # prompt ends at pos 4; eviction fires at pos >= 31 — the
        # stream ends after ~27 tokens, well short of the 40 requested
        evicted = list(bat.submit_stream([1, 2, 3, 4, 5], max_new=40))
        assert 0 < len(evicted) < 40
        st = bat.stats()
    assert st["evictions"] >= 1
    assert st["leaves"] >= 2
    assert eng.retraces == 0
    snap = telemetry.summary()
    assert snap.get("decode.evictions", 0) >= 1
    assert snap.get("decode.joins", 0) >= 2


@pytest.mark.parametrize("hd", [128, 64])
def test_prefill_route_follows_the_head_dim(hd, monkeypatch):
    """On one TPU a prefill at head_dim 128 takes the causal kernel pair
    (one ``mx_causal_attn_fwd`` a layer, counted once though gpt.py and
    ops/nn.py both ask) and agrees with the interleaved composition; at
    head_dim 64 it counts no kernel and stays on the composition."""
    from mxnet_tpu.ops import pallas_block
    cfg = gpt.GPTConfig(vocab_size=61, hidden=2 * hd, layers=1, heads=2,
                        intermediate=64, max_len=128)
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(
        onp.random.RandomState(3).randint(0, 61, (1, 128)), jnp.int32)

    # a fresh function each time: jit caches a trace by the function
    ref = jax.jit(lambda t: gpt.prefill(params, cfg, t))(tokens)
    monkeypatch.setattr(pallas_block, "one_tpu", lambda: True)
    text = str(jax.make_jaxpr(lambda t: gpt.prefill(params, cfg, t))(tokens))
    telemetry.reset()
    got = jax.jit(lambda t: gpt.prefill(params, cfg, t))(tokens)
    causal = {k: v for k, v in telemetry.raw_snapshot()["counters"].items()
              if "causal_attention" in k and v}
    if hd == 128:
        assert "mx_causal_attn_fwd" in text
        assert causal == {"dispatch.pallas.hits.causal_attention.128": 1}
    else:
        assert "mx_causal_attn" not in text
        assert causal == {"dispatch.pallas.fallbacks.causal_attention.64": 1}
    # the kernels round their MXU operands to bfloat16, as the chip's
    # default precision does to the composition's float32 products
    tol = 3e-2 if hd == 128 else 2e-4
    for a, b in zip(got, ref):                  # logits, k, v
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=tol, atol=tol)
