#!/usr/bin/env python
"""A/B the Pallas implicit-GEMM conv against XLA's conv emitter on the
REAL chip, per profiled worst tile AND on the full ResNet-50 train step.

Round-3 profiling pinned ~64% of the 49.5ms bf16 step on conv fusions
with batch-in-sublanes emitter tilings (layout flags measurably no-win).
This script answers, per stage-shape: does ops/pallas_conv.py beat the
emitter?  And end-to-end: does MXNET_TPU_PALLAS_CONV=1 cut the step?

Fresh device inputs per timed iteration.

Usage: python benchmark/pallas_conv_ab.py [--iters 20] [--full-step]
       python benchmark/pallas_conv_ab.py --block [--commit-table]
       python benchmark/pallas_conv_ab.py --int8 [--commit-table]
       python benchmark/pallas_conv_ab.py --attn [--commit-table]
Prints one JSON line with per-shape µs and the winner.  ``--block`` runs
the fused residual-block pipeline (ops/pallas_block.py) against the
layer-by-layer XLA composition and derives the per-stage route table;
``--int8`` A/Bs the quantized-serving kernels (ops/pallas_int8.py) —
int8 Pallas vs int8 XLA vs the bf16 inference block, forward only;
``--attn`` A/Bs the causal flash-attention forward
(ops/pallas_attention.py — the GPT prefill workhorse) against the XLA
masked-einsum composition over the decode prefill lengths.
``--commit-table`` writes the matching decision JSON
(benchmark/results/pallas_block_ab.json, pallas_int8_ab.json or
pallas_attn_ab.json) — refused off-TPU, so interpret-mode runs can
never poison the committed decisions.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the 3×3/s1 ResNet-50 bf16 layers (batch 128), worst first
SHAPES = [
    ("stage1_56x56x64", (128, 56, 56, 64), 64),
    ("stage2_28x28x128", (128, 28, 28, 128), 128),
    ("stage3_14x14x256", (128, 14, 14, 256), 256),
]

# causal-attention prefill shapes (B, H, L, D): GPT prefill over the
# sequence lengths the decode engine actually compiles — stage keys
# ("512x128", ...) match ops/pallas_attention.attn_stage_key
ATTN_SHAPES = [
    ("attn_512x128", (4, 8, 512, 128)),
    ("attn_1024x128", (2, 8, 1024, 128)),
    ("attn_2048x128", (1, 8, 2048, 128)),
]


def _time_fn(fn, args_stream, iters):
    """Pre-generate the fresh inputs OUTSIDE the timed window: every
    iteration still sees distinct data (anti-caching), but on-device RNG
    cost never biases the conv comparison toward 1.0."""
    # end-of-window barrier: a host fetch of a dependent value
    import jax
    from bench import _force

    def force(tree):
        _force(*jax.tree_util.tree_leaves(tree))

    force([fn(*next(args_stream)) for _ in range(3)])     # warm/compile
    batches = [next(args_stream) for _ in range(iters)]
    force(batches)
    t0 = time.perf_counter()
    outs = [fn(*b) for b in batches]
    force(outs)
    return (time.perf_counter() - t0) / iters * 1e6       # µs


def ab_shape(name, xshape, cout, iters, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import pallas_conv as pc

    key = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "little"))

    def stream():
        nonlocal key
        while True:
            key, kx, kw = jax.random.split(key, 3)
            x = jax.random.normal(kx, xshape, jnp.float32).astype(dtype)
            w = jax.random.normal(kw, (3, 3, xshape[-1], cout),
                                  jnp.float32).astype(dtype)
            yield x, w

    def xla_conv(x, w):
        # same-dtype in/out (the MXU accumulates f32 internally): a
        # preferred_element_type=f32 here breaks jax.grad — the conv
        # transpose rule would mix the f32 cotangent with bf16 weights
        return lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    s = stream()
    xla_fwd = _time_fn(jax.jit(xla_conv), s, iters)
    pal_fwd = _time_fn(jax.jit(pc.conv3x3_s1), s, iters)

    def xla_grad(x, w):
        return jax.grad(lambda a, b: jnp.sum(xla_conv(a, b).astype(
            jnp.float32)), argnums=(0, 1))(x, w)

    def pal_grad(x, w):
        return jax.grad(lambda a, b: jnp.sum(pc.conv3x3_s1(a, b).astype(
            jnp.float32)), argnums=(0, 1))(x, w)

    xla_bwd = _time_fn(jax.jit(xla_grad), s, iters)
    pal_bwd = _time_fn(jax.jit(pal_grad), s, iters)
    row = {
        "xla_fwd_us": round(xla_fwd, 1), "pallas_fwd_us": round(pal_fwd, 1),
        "xla_fwd_bwd_us": round(xla_bwd, 1),
        "pallas_fwd_bwd_us": round(pal_bwd, 1),
        "fwd_speedup": round(xla_fwd / pal_fwd, 3),
        "fwd_bwd_speedup": round(xla_bwd / pal_bwd, 3),
    }
    print(f"[ab] {name}: xla {xla_fwd:.0f}/{xla_bwd:.0f}µs "
          f"pallas {pal_fwd:.0f}/{pal_bwd:.0f}µs "
          f"(fwd×{row['fwd_speedup']}, fwd+bwd×{row['fwd_bwd_speedup']})",
          file=sys.stderr)
    return row


def ab_block(name, xshape, cout, iters, dtype):
    """Block-level leg: fused conv+BN(+add)+ReLU pipeline vs the XLA
    reference composition, train mode with a residual, fwd and fwd+bwd."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import pallas_block as pb

    key = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "little"))
    cin = xshape[-1]

    def stream():
        nonlocal key
        while True:
            key, kx, kw, kr = jax.random.split(key, 4)
            x = jax.random.normal(kx, xshape, jnp.float32).astype(dtype)
            w = jax.random.normal(kw, (3, 3, cin, cout),
                                  jnp.float32).astype(dtype)
            r = jax.random.normal(kr, xshape[:-1] + (cout,),
                                  jnp.float32).astype(dtype)
            yield x, w, r

    gamma = jnp.ones((cout,), jnp.float32)
    beta = jnp.zeros((cout,), jnp.float32)
    mean = jnp.zeros((cout,), jnp.float32)
    var = jnp.ones((cout,), jnp.float32)

    def ref_block(x, w, r):
        # what the layer-by-layer path lowers to: conv, train-mode BN,
        # residual add, ReLU — four HBM round trips for the fused one
        z = lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)
        m = jnp.mean(z, axis=(0, 1, 2))
        v = jnp.mean(jnp.square(z), axis=(0, 1, 2)) - jnp.square(m)
        y = (z - m) * (gamma * lax.rsqrt(v + 1e-5)) + beta
        return jax.nn.relu(y + r.astype(jnp.float32)).astype(x.dtype)

    def fused_block(x, w, r):
        return pb.residual_block_fused(x, w, gamma, beta, mean, var, r,
                                       frozen=False, bwd="pallas")[0]

    def grad_of(fn):
        def g(x, w, r):
            return jax.grad(lambda a, b, c: jnp.sum(
                fn(a, b, c).astype(jnp.float32)), argnums=(0, 1, 2))(x, w, r)
        return g

    s = stream()
    xla_fwd = _time_fn(jax.jit(ref_block), s, iters)
    pal_fwd = _time_fn(jax.jit(fused_block), s, iters)
    xla_bwd = _time_fn(jax.jit(grad_of(ref_block)), s, iters)
    pal_bwd = _time_fn(jax.jit(grad_of(fused_block)), s, iters)
    row = {
        "xla_fwd_us": round(xla_fwd, 1), "pallas_fwd_us": round(pal_fwd, 1),
        "xla_fwd_bwd_us": round(xla_bwd, 1),
        "pallas_fwd_bwd_us": round(pal_bwd, 1),
        "fwd_speedup": round(xla_fwd / pal_fwd, 3),
        "fwd_bwd_speedup": round(xla_bwd / pal_bwd, 3),
    }
    print(f"[ab-block] {name}: xla {xla_fwd:.0f}/{xla_bwd:.0f}µs "
          f"fused {pal_fwd:.0f}/{pal_bwd:.0f}µs "
          f"(fwd×{row['fwd_speedup']}, fwd+bwd×{row['fwd_bwd_speedup']})",
          file=sys.stderr)
    return row


def ab_int8(name, xshape, cout, iters, dtype):
    """Quantized-serving leg: int8 implicit-GEMM with the fused
    dequant+affine+add+ReLU epilogue (ops/pallas_int8.py) vs the XLA
    int8 route vs the bf16 inference-mode reference.  Forward only —
    this is the serving path; there is no int8 backward."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import pallas_int8 as pi8

    key = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "little"))
    cin = xshape[-1]
    scale = jnp.full((cout,), 1.0 / (127.0 * 9 * cin), jnp.float32)
    shift = jnp.zeros((cout,), jnp.float32)

    def q_stream():
        nonlocal key
        while True:
            key, kx, kw, kr = jax.random.split(key, 4)
            qx = jax.random.randint(kx, xshape, -127, 128, jnp.int8)
            qw = jax.random.randint(kw, (3, 3, cin, cout), -127, 128,
                                    jnp.int8)
            r = jax.random.normal(kr, xshape[:-1] + (cout,), jnp.float32)
            yield qx, qw, r

    def f_stream():
        nonlocal key
        while True:
            key, kx, kw, kr = jax.random.split(key, 4)
            x = jax.random.normal(kx, xshape, jnp.float32).astype(dtype)
            w = jax.random.normal(kw, (3, 3, cin, cout),
                                  jnp.float32).astype(dtype)
            r = jax.random.normal(kr, xshape[:-1] + (cout,),
                                  jnp.float32).astype(dtype)
            yield x, w, r

    def int8_pallas(qx, qw, r):
        return pi8.qconv3x3_affine(qx, qw, scale, shift, res=r, relu=True)

    def int8_xla(qx, qw, r):
        return pi8.qconv3x3_xla(qx, qw, scale, shift, res=r, relu=True)

    def bf16_ref(x, w, r):
        # the shipped inference-mode block: conv + folded-BN affine +
        # residual add + ReLU, same epilogue the int8 kernels fuse
        z = lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)
        return jax.nn.relu(z * scale + shift + r.astype(jnp.float32))

    qs, fs = q_stream(), f_stream()
    pal = _time_fn(jax.jit(int8_pallas), qs, iters)
    xla = _time_fn(jax.jit(int8_xla), qs, iters)
    bf16 = _time_fn(jax.jit(bf16_ref), fs, iters)
    row = {
        "int8_pallas_us": round(pal, 1), "int8_xla_us": round(xla, 1),
        "bf16_us": round(bf16, 1),
        "int8_speedup": round(xla / pal, 3),
        "vs_bf16_speedup": round(bf16 / pal, 3),
    }
    print(f"[ab-int8] {name}: int8 pallas {pal:.0f}µs xla {xla:.0f}µs "
          f"bf16 {bf16:.0f}µs (int8×{row['int8_speedup']}, "
          f"vs bf16×{row['vs_bf16_speedup']})", file=sys.stderr)
    return row


def ab_attn(name, qshape, iters, dtype):
    """Flash-attention leg: the online-softmax causal Pallas forward
    (one HBM pass over K/V) vs the XLA masked-einsum composition
    (materializes the L×L score matrix).  Forward only — the decode
    engine uses it in prefill programs where no gradient exists."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    key = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "little"))
    scale = 1.0 / float(qshape[-1]) ** 0.5

    def stream():
        nonlocal key
        while True:
            key, kq, kk, kv = jax.random.split(key, 4)
            q = jax.random.normal(kq, qshape, jnp.float32).astype(dtype)
            k = jax.random.normal(kk, qshape, jnp.float32).astype(dtype)
            v = jax.random.normal(kv, qshape, jnp.float32).astype(dtype)
            yield q, k, v

    def pallas_fwd(q, k, v):
        return pa._causal_attention_pallas(q, k, v, scale)

    def xla_fwd_fn(q, k, v):
        return pa.causal_attention_xla(q, k, v, scale)

    s = stream()
    xla = _time_fn(jax.jit(xla_fwd_fn), s, iters)
    pal = _time_fn(jax.jit(pallas_fwd), s, iters)
    row = {
        "xla_fwd_us": round(xla, 1), "pallas_fwd_us": round(pal, 1),
        "fwd_speedup": round(xla / pal, 3),
    }
    print(f"[ab-attn] {name}: xla {xla:.0f}µs pallas {pal:.0f}µs "
          f"(fwd×{row['fwd_speedup']})", file=sys.stderr)
    return row


# require a real margin before routing off the emitter: a ±5% wash must
# not flip the committed table back and forth between runs
_WIN = 1.05


def decisions_from(rows):
    """Per-stage route table from block-level rows.  ``fwd`` follows the
    forward-only margin; ``bwd`` needs the full fwd+bwd chain to win
    (dgrad/wgrad only pay off if the whole custom-vjp beats XLA's)."""
    out = {}
    for name, row in rows.items():
        if "error" in row or "_" not in name:
            continue
        stage = name.split("_", 1)[1]
        out[stage] = {
            "fwd": "pallas" if row["fwd_speedup"] >= _WIN else "xla",
            "bwd": "pallas" if row["fwd_bwd_speedup"] >= _WIN else "xla",
        }
    return out


def commit_table(rows, dtype):
    """Write the decision JSON the dispatcher reads — ONLY from a real
    TPU run.  Off-TPU (interpret-mode) timings are meaningless; refusing
    to write keeps the committed table grounded in chip measurements."""
    import jax

    from mxnet_tpu.ops import pallas_block as pb

    if jax.devices()[0].platform != "tpu" or pb.interpret():
        print("[ab-block] off-TPU (or interpret mode): NOT committing "
              f"{pb._table_path()}", file=sys.stderr)
        return False
    dec = decisions_from(rows)
    if not dec:
        print("[ab-block] no usable rows: NOT committing", file=sys.stderr)
        return False
    doc = {
        "schema": "pallas_block_ab/v1",
        "decisions": dec,
        "provenance": {
            "source": "pallas_conv_ab.py --block --commit-table",
            "dtype": str(dtype), "iters_rows": rows,
        },
    }
    path = pb._table_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"[ab-block] committed {path}: {json.dumps(dec)}", file=sys.stderr)
    return True


def int8_decisions_from(rows):
    """Per-stage int8 route table: the Pallas kernel must beat the XLA
    int8 route by the same wash margin before it owns a stage."""
    out = {}
    for name, row in rows.items():
        if "error" in row or "_" not in name:
            continue
        stage = name.split("_", 1)[1]
        out[stage] = {
            "fwd": "pallas" if row["int8_speedup"] >= _WIN else "xla"}
    return out


def commit_int8_table(rows, dtype):
    """Write the int8 decision JSON (``pallas_int8._table_path()``) —
    ONLY from a real TPU run, same grounding rule as the bf16 table."""
    import jax

    from mxnet_tpu.ops import pallas_block as pb
    from mxnet_tpu.ops import pallas_int8 as pi8

    if jax.devices()[0].platform != "tpu" or pb.interpret():
        print("[ab-int8] off-TPU (or interpret mode): NOT committing "
              f"{pi8._table_path()}", file=sys.stderr)
        return False
    dec = int8_decisions_from(rows)
    if not dec:
        print("[ab-int8] no usable rows: NOT committing", file=sys.stderr)
        return False
    doc = {
        "schema": "pallas_int8_ab/v1",
        "decisions": dec,
        "provenance": {
            "source": "pallas_conv_ab.py --int8 --commit-table",
            "dtype": str(dtype), "iters_rows": rows,
        },
    }
    path = pi8._table_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"[ab-int8] committed {path}: {json.dumps(dec)}", file=sys.stderr)
    return True


def attn_decisions_from(rows):
    """Per-stage flash-attention route table: the Pallas forward must
    beat the XLA masked einsum by the wash margin to own a stage."""
    out = {}
    for name, row in rows.items():
        if "error" in row or "_" not in name:
            continue
        stage = name.split("_", 1)[1]
        out[stage] = {
            "fwd": "pallas" if row["fwd_speedup"] >= _WIN else "xla"}
    return out


def commit_attn_table(rows, dtype):
    """Write the attention decision JSON
    (``pallas_attention._table_path()``) — ONLY from a real TPU run,
    same grounding rule as the conv tables."""
    import jax

    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import pallas_block as pb

    if jax.devices()[0].platform != "tpu" or pb.interpret():
        print("[ab-attn] off-TPU (or interpret mode): NOT committing "
              f"{pa._table_path()}", file=sys.stderr)
        return False
    dec = attn_decisions_from(rows)
    if not dec:
        print("[ab-attn] no usable rows: NOT committing", file=sys.stderr)
        return False
    doc = {
        "schema": "pallas_attn_ab/v1",
        "decisions": dec,
        "provenance": {
            "source": "pallas_conv_ab.py --attn --commit-table",
            "dtype": str(dtype), "iters_rows": rows,
        },
    }
    path = pa._table_path()
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"[ab-attn] committed {path}: {json.dumps(dec)}", file=sys.stderr)
    return True


def full_step(iters):
    """ResNet-50 bf16 train step, flag off vs on."""
    import subprocess
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = {}
    # the baseline leg must OVERRIDE any flag exported by the operator —
    # inheriting it would silently turn the A/B into A/A
    for tag, env in (("xla", {"MXNET_TPU_PALLAS_CONV": "0"}),
                     ("pallas", {"MXNET_TPU_PALLAS_CONV": "1"})):
        # one leg wedging/crashing must not discard the other leg or the
        # per-shape rows already computed (same fault isolation as
        # ab_shape) — always leave a value or an error marker per tag
        try:
            r = subprocess.run(
                [sys.executable, os.path.join(here, "bench.py"),
                 "--row", "train_bf16"],
                env={**os.environ, **env, "BENCH_ITERS": str(iters),
                     "BENCH_WARMUP": "3"},
                capture_output=True, text=True, timeout=2400)
            for line in reversed((r.stdout or "").splitlines()):
                if line.strip().startswith("{"):
                    out[tag] = json.loads(line).get("img_s")
                    break
            else:
                out[tag] = {"error": f"no JSON line (rc={r.returncode})"}
        except Exception as e:  # noqa: BLE001 — report per-leg
            out[tag] = {"error": f"{type(e).__name__}: {e}"}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--full-step", action="store_true")
    ap.add_argument("--block", action="store_true",
                    help="run the fused residual-block legs instead of "
                         "the lone-conv legs")
    ap.add_argument("--int8", action="store_true",
                    help="run the quantized int8 serving legs "
                         "(Pallas vs XLA int8 vs bf16, forward only)")
    ap.add_argument("--attn", action="store_true",
                    help="run the causal flash-attention legs "
                         "(Pallas online-softmax vs XLA masked einsum, "
                         "forward only)")
    ap.add_argument("--commit-table", action="store_true",
                    help="with --block/--int8/--attn: write the "
                         "per-stage decision JSON (refused off-TPU)")
    args = ap.parse_args()

    import jax.numpy as jnp
    dtype = jnp.dtype(args.dtype)
    if args.attn:
        rows = {}
        for name, qshape in ATTN_SHAPES:
            try:
                rows[name] = ab_attn(name, qshape, args.iters, dtype)
            except Exception as e:  # noqa: BLE001 — report per-shape
                rows[name] = {"error": f"{type(e).__name__}: {e}"}
                print(f"[ab-attn] {name} FAILED: {e}", file=sys.stderr)
        rows["decisions"] = attn_decisions_from(rows)
        if args.commit_table:
            rows["committed"] = commit_attn_table(
                {k: v for k, v in rows.items() if k != "decisions"}, dtype)
        print(json.dumps(rows))
        return 0
    leg = ab_int8 if args.int8 else ab_block if args.block else ab_shape
    tag = "ab-int8" if args.int8 else "ab-block" if args.block else "ab"
    rows = {}
    for name, xshape, cout in SHAPES:
        try:
            rows[name] = leg(name, xshape, cout, args.iters, dtype)
        except Exception as e:  # noqa: BLE001 — report per-shape
            rows[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[{tag}] {name} FAILED: {e}", file=sys.stderr)
    if args.int8:
        rows["decisions"] = int8_decisions_from(rows)
        if args.commit_table:
            rows["committed"] = commit_int8_table(
                {k: v for k, v in rows.items() if k != "decisions"}, dtype)
    elif args.block:
        rows["decisions"] = decisions_from(rows)
        if args.commit_table:
            rows["committed"] = commit_table(
                {k: v for k, v in rows.items() if k != "decisions"}, dtype)
    if args.full_step:
        rows["full_step_img_s"] = full_step(max(args.iters, 20))
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
