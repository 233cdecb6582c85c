"""GSPMD sharding planner: per-parameter PartitionSpecs for a 2-D
(dp × tp) mesh, derived from a gluon block tree.

The reference scales out with a parameter server (kvstore_dist.h) where
*keys* are placed on servers; on TPU the equivalent decision is which
mesh axis each parameter tensor is split over, and XLA inserts the
collectives (SNIPPETS [2]: named ("batch","model") axes + NamedSharding
annotations — "scales from 8-chip pods to 6000-chip superclusters
without changing application code").  This module makes that decision a
first-class, serializable artifact:

- :func:`infer_plan` walks a HybridBlock's children and derives a
  per-parameter ``PartitionSpec`` from a rule engine keyed on layer type
  and shape: FullyConnected (Dense — including attention QKV/proj, which
  are Dense children) weights split their ``units`` dim on ``tp``,
  embeddings split column-wise (output features) on ``tp``, everything
  else (conv, norm scales, running stats, indivisible shapes) stays
  replicated.
- :class:`ShardingPlan` round-trips to JSON and carries a stable content
  fingerprint.  The fingerprint keys compiled programs through the
  dispatch cache's ``__mx_extra_key__`` convention (dispatch_cache.
  np_call_key) and the fused-step rebuild signature, so *editing a plan
  recompiles* instead of serving a stale route compiled for the old
  layout.

Layout semantics — storage sharding, gathered at use:

The ``tp`` axis shards parameter/gradient/optimizer-state *storage*
(each device holds 1/tp of every planned tensor — the memory scale-out
that lets the model exceed one chip's HBM).  Inside the fused program
the weights are gathered at their use site (``with_sharding_constraint``
to replicated — an exact all-gather), and the gradient cotangents are
constrained back to the storage sharding before the optimizer, so the
optimizer update itself is tp-local 1/tp work and the only cross-replica
gradient reduction is the dp all-reduce.  This layout is what makes the
sharded step *bit-for-bit* equal to the replicated step at the same dp
grouping: every floating-point contraction runs over the identical
operand layout, tp only adds exact gathers/slices (docs/sharding.md —
tp-local partial-sum layouts re-associate the backward reductions and
are only tolerance-level reproducible).

The dp reduction maps the fork's ``KVStoreDist::WorkersMerge``
(kvstore_dist.h:84-146 — host-local fan-in before the server hop) onto
the mesh: split dp into ``dp_in`` (ICI / host-local, reduced first) and
``dp_out`` (DCN / cross-host, reduced second) axes via
``make_mesh({'dp_out': h, 'dp_in': w, 'tp': k})`` and batch specs name
the nested tuple — XLA schedules the hierarchical collective.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, Optional

from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["ShardingPlan", "infer_plan", "infer_plan_tree", "load_plan",
           "resolve_plan", "place_tree", "tree_bytes_per_device",
           "serve_fingerprint", "PLAN_ENV", "SERVE_MESH_ENV",
           "SERVE_PLAN_ENV"]

PLAN_ENV = "MXNET_SHARDING_PLAN"
# the serving tier resolves its own mesh/plan pair so one host can run a
# tp-sharded replica next to an unsharded trainer (docs/serving.md
# §sharded serving)
SERVE_MESH_ENV = "MXNET_SERVE_MESH"
SERVE_PLAN_ENV = "MXNET_SERVE_SHARDING_PLAN"
PLAN_VERSION = 1

# Rule names recorded per entry — the rule table in docs/sharding.md.
RULE_DENSE_W = "dense_column"        # Dense/FullyConnected weight (units, in)
RULE_DENSE_B = "dense_bias"          # Dense bias (units,)
RULE_EMBED = "embedding_column"      # Embedding weight (vocab, out)
RULE_REPLICATED = "replicated"       # everything else
RULE_INDIVISIBLE = "indivisible"     # tp-eligible but dim % tp != 0


def _canonical(entries: Dict[str, dict], tp_axis: str) -> str:
    """Deterministic JSON body the fingerprint hashes: sorted keys,
    no whitespace variance — dict insertion order must not change the
    fingerprint of the same plan."""
    return json.dumps({"version": PLAN_VERSION, "tp_axis": tp_axis,
                       "params": entries}, sort_keys=True,
                      separators=(",", ":"))


class ShardingPlan:
    """A per-parameter PartitionSpec assignment, serializable to JSON.

    ``entries`` maps the parameter's ``collect_params()`` name to
    ``{"partition": [axis-or-None per dim], "rule": str}``.  Parameters
    absent from the plan are replicated.
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 tp_axis: str = "tp"):
        self.tp_axis = tp_axis
        self.entries: Dict[str, dict] = {}
        for name, e in (entries or {}).items():
            part = [None if a in (None, "") else str(a)
                    for a in e.get("partition", ())]
            self.entries[name] = {"partition": part,
                                  "rule": str(e.get("rule", "manual"))}

    # ------------------------------------------------------------- lookup
    def spec(self, name: str) -> PartitionSpec:
        e = self.entries.get(name)
        if e is None:
            return PartitionSpec()
        part = e["partition"]
        # trailing replicated dims can be dropped; keep explicit for
        # round-trip fidelity but PartitionSpec treats them the same
        return PartitionSpec(*part)

    def sharding(self, mesh, name: str) -> NamedSharding:
        return NamedSharding(mesh, self.spec(name))

    def is_sharded(self, name: str) -> bool:
        e = self.entries.get(name)
        return e is not None and any(a is not None for a in e["partition"])

    def sharded_names(self):
        return [n for n in self.entries if self.is_sharded(n)]

    # -------------------------------------------------------------- keys
    @property
    def fingerprint(self) -> str:
        """Stable content hash — keys the fused-step rebuild signature
        and the dispatch cache (``extra_key``)."""
        return hashlib.sha256(
            _canonical(self.entries, self.tp_axis).encode()).hexdigest()[:16]

    def extra_key(self) -> str:
        """``__mx_extra_key__`` payload (dispatch_cache.np_call_key):
        joins the compiled-program cache key so a plan edit can never be
        served a stale executable compiled for the old layout."""
        return "sharding_plan:" + self.fingerprint

    # -------------------------------------------------------------- json
    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps({"version": PLAN_VERSION, "tp_axis": self.tp_axis,
                           "params": self.entries}, sort_keys=True,
                          indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "ShardingPlan":
        obj = json.loads(text)
        if obj.get("version", 0) > PLAN_VERSION:
            raise ValueError(f"sharding plan v{obj.get('version')} is newer "
                             f"than reader v{PLAN_VERSION}")
        return cls(obj.get("params") or {},
                   tp_axis=obj.get("tp_axis", "tp"))

    def save(self, path: str):
        from ..checkpoint import atomic_write
        atomic_write(path, self.to_json(indent=1).encode())

    # ---------------------------------------------------------- accounting
    def collective_bytes(self, shapes: Dict[str, tuple],
                         itemsize: int = 4) -> Dict[str, int]:
        """Modeled per-step collective traffic by axis, from the plan and
        the parameter shapes (docs/telemetry.md `collective` section):

        - ``tp``: weight all-gather at use — each device receives the
          (tp-1)/tp of every sharded tensor it doesn't hold.  Counted as
          full tensor bytes (upper bound; XLA may elide gathers whose
          consumer runs sharded).
        - ``dp``: gradient all-reduce — every trainable tensor's *stored*
          bytes cross the dp axis once.
        """
        import math
        tp_b = 0
        dp_b = 0
        for name, shape in shapes.items():
            n = int(math.prod(shape)) * itemsize
            dp_b += n
            if self.is_sharded(name):
                tp_b += n
        return {self.tp_axis: tp_b, "dp": dp_b}

    def __len__(self):
        return len(self.entries)

    def __repr__(self):
        ns = len(self.sharded_names())
        return (f"ShardingPlan({len(self.entries)} params, {ns} sharded "
                f"on '{self.tp_axis}', fp={self.fingerprint})")


# ------------------------------------------------------------- rule engine
def _walk_blocks(block, prefix=""):
    """Yield (param_name, owner_block) with collect_params() naming
    (gluon/block.py _collect_params: child names joined by '.')."""
    for name, p in getattr(block, "_reg_params", {}).items():
        yield prefix + name, block, p
    for cname, child in getattr(block, "_children", {}).items():
        yield from _walk_blocks(child, f"{prefix}{cname}.")


def _tp_size(mesh, tp, tp_axis):
    if tp is not None:
        return int(tp)
    if mesh is not None:
        return int(mesh.shape.get(tp_axis, 1))
    raise ValueError("infer_plan needs tp= or mesh= to size the tp axis")


def infer_plan(net, mesh=None, tp: Optional[int] = None,
               tp_axis: str = "tp") -> ShardingPlan:
    """Derive a :class:`ShardingPlan` for ``net``'s collected params.

    Rule table (docs/sharding.md):

    ==================  =======================  =======================
    layer.param         shape                    partition
    ==================  =======================  =======================
    Dense.weight        (units, in_units)        (tp, None)  column-wise
    Dense.bias          (units,)                 (tp,)
    Embedding.weight    (vocab, out)             (None, tp)  column-wise
    anything else       any                      replicated
    ==================  =======================  =======================

    Attention QKV/proj weights are Dense children (models/bert_gluon.py
    BERTSelfAttention.qkv/.proj) so the Dense rule covers them.  A
    tp-eligible dim that is not divisible by the tp size falls back to
    replicated with rule ``indivisible`` (recorded, not silent).
    Shapes must be resolved — run one forward (or ``initialize`` with
    known in_units) before planning a deferred-init net.
    """
    from ..gluon import nn
    k = _tp_size(mesh, tp, tp_axis)
    entries: Dict[str, dict] = {}
    for name, owner, p in _walk_blocks(net):
        shape = tuple(p.shape or ())
        if not shape or 0 in shape:
            raise ValueError(
                f"parameter {name!r} has unresolved shape {shape}; run one "
                "forward to materialize deferred shapes before infer_plan")
        part = [None] * len(shape)
        rule = RULE_REPLICATED
        if k > 1:
            if isinstance(owner, nn.Dense):
                if name.endswith("weight") and len(shape) == 2:
                    if shape[0] % k == 0:
                        part[0] = tp_axis
                        rule = RULE_DENSE_W
                    else:
                        rule = RULE_INDIVISIBLE
                elif name.endswith("bias") and len(shape) == 1:
                    if shape[0] % k == 0:
                        part[0] = tp_axis
                        rule = RULE_DENSE_B
                    else:
                        rule = RULE_INDIVISIBLE
            elif isinstance(owner, nn.Embedding) and len(shape) == 2:
                # column-wise: split output features, keep the vocab dim
                # whole so the gather (embedding lookup) stays local
                if shape[1] % k == 0:
                    part[1] = tp_axis
                    rule = RULE_EMBED
                else:
                    rule = RULE_INDIVISIBLE
        entries[name] = {"partition": part, "rule": rule}
    return ShardingPlan(entries, tp_axis=tp_axis)


# --------------------------------------------------- functional pytrees
def _walk_tree(tree, prefix=""):
    """Yield (slash-path, leaf) for a functional params pytree — the
    naming CheckpointManager flattens to (checkpoint.py _flatten), so
    plans derived here line up with sharded-restore keys."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk_tree(v, f"{prefix}{k}/")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk_tree(v, f"{prefix}{i}/")
    else:
        yield prefix[:-1], tree


def infer_plan_tree(tree, mesh=None, tp: Optional[int] = None,
                    tp_axis: str = "tp") -> ShardingPlan:
    """:func:`infer_plan` for functional params pytrees (models/gpt.py,
    models/bert.py) — nets with no gluon block tree to walk.

    Same rule table, transposed for the functional convention: kernels
    are ``(in, out)`` so the column split lands on dim 1 (gluon Dense
    stores ``(units, in)`` and splits dim 0).  The GPT qkv kernel's
    output dim orders as ``(head, q|k|v, head_dim)``, so the column
    split is a per-head split — attention and the ring KV cache shard
    along tp for free (generate.py).  Embedding tables (``embed/*``,
    2-D) split their feature dim; 1-D norm scales/biases that don't
    spell ``bias`` stay replicated.  Indivisible dims are recorded, not
    silently sharded (e.g. an odd vocab head stays whole).
    """
    k = _tp_size(mesh, tp, tp_axis)
    entries: Dict[str, dict] = {}
    for name, leaf in _walk_tree(tree):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        part = [None] * len(shape)
        rule = RULE_REPLICATED
        leaf_name = name.rsplit("/", 1)[-1]
        if k > 1 and shape:
            if leaf_name == "kernel" and len(shape) == 2:
                if shape[1] % k == 0:
                    part[1] = tp_axis
                    rule = RULE_DENSE_W
                else:
                    rule = RULE_INDIVISIBLE
            elif leaf_name == "bias" and len(shape) == 1:
                if shape[0] % k == 0:
                    part[0] = tp_axis
                    rule = RULE_DENSE_B
                else:
                    rule = RULE_INDIVISIBLE
            elif name.startswith("embed/") or "/embed/" in name:
                if len(shape) == 2:
                    if shape[1] % k == 0:
                        part[1] = tp_axis
                        rule = RULE_EMBED
                    else:
                        rule = RULE_INDIVISIBLE
        entries[name] = {"partition": part, "rule": rule}
    return ShardingPlan(entries, tp_axis=tp_axis)


def place_tree(tree, mesh, plan: Optional["ShardingPlan"]):
    """``device_put`` every leaf of a functional params pytree to its
    planned sharding over ``mesh`` (replicated when the plan omits it or
    ``plan`` is None) — the storage-sharded layout the fused trainer
    uses (_place_storage), for nets that are plain pytrees."""
    import jax
    from .mesh import replicated as _rep
    rep = _rep(mesh)

    def walk(sub, prefix):
        if isinstance(sub, dict):
            return {k: walk(v, f"{prefix}{k}/") for k, v in sub.items()}
        if isinstance(sub, (list, tuple)):
            out = [walk(v, f"{prefix}{i}/") for i, v in enumerate(sub)]
            return tuple(out) if isinstance(sub, tuple) else out
        sh = plan.sharding(mesh, prefix[:-1]) if plan is not None else rep
        return jax.device_put(sub, sh)

    return walk(tree, "")


def tree_bytes_per_device(tree) -> int:
    """Sum of :func:`shard_bytes` over a pytree's leaves — what one
    device actually holds (the ``serve.param_bytes_per_device`` /
    ``decode.kv_bytes_per_device`` gauges)."""
    return sum(shard_bytes(leaf) for _, leaf in _walk_tree(tree)
               if hasattr(leaf, "nbytes"))


# -------------------------------------------------------------- resolution
def load_plan(path: str) -> ShardingPlan:
    with open(path) as f:
        return ShardingPlan.from_json(f.read())


def resolve_plan(plan=None, env: str = PLAN_ENV) -> Optional[ShardingPlan]:
    """Explicit plan → else the env var (a JSON plan file; trainers read
    ``MXNET_SHARDING_PLAN``, serving reads ``MXNET_SERVE_SHARDING_PLAN``)
    → else None (fully replicated, the pre-plan behavior)."""
    if plan is not None:
        return plan
    path = os.environ.get(env)
    if path:
        return load_plan(path)
    return None


_serve_fp_cache = {"key": None, "fp": None}


def serve_fingerprint() -> tuple:
    """Hashable digest of the serving tier's sharding knobs — the mesh
    spec (``MXNET_SERVE_MESH``) and the plan file named by
    ``MXNET_SERVE_SHARDING_PLAN`` (its content fingerprint, so an
    in-place edit re-keys, not just a rename).  The serving engines
    (``serve.InferenceEngine``, ``generate.DecodeEngine``) put it in
    their program keys, so a plan or mesh edit compiles a new program
    instead of serving one compiled for the old layout.  Memoised on
    the env values + plan-file mtime; steady-state cost is two env reads
    and one stat."""
    env = (os.environ.get(SERVE_MESH_ENV, ""),
           os.environ.get(SERVE_PLAN_ENV, ""))
    mtime = -1
    if env[1]:
        try:
            mtime = os.stat(env[1]).st_mtime_ns
        except OSError:
            mtime = -2          # named but unreadable ≠ unset
    key = (env, mtime)
    c = _serve_fp_cache
    if c["key"] == key:
        return c["fp"]
    plan_fp = ""
    if env[1] and mtime != -2:
        try:
            plan_fp = load_plan(env[1]).fingerprint
        except (OSError, ValueError):
            plan_fp = "unreadable"
    fp = ("serve_shard", env[0], plan_fp)
    c.update(key=key, fp=fp)
    return fp


def shard_bytes(arr) -> int:
    """Per-device bytes actually held for ``arr`` on this process —
    the "params measurably sharded" probe (addressable shard 0)."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards:
        return arr.nbytes
    return shards[0].data.nbytes


# --------------------------------------------------------------------- check
def _selfcheck(verbose: bool = True) -> int:
    """``make shard-check``: plan inference on resnet50 + a 2-layer
    transformer, plan JSON round-trip + fingerprint re-key, and a fused
    sharded step over tp=2 × hierarchical dp (dp_out×dp_in) with
    0 retraces / 0 rebuilds / 1 dispatch per step, bit-for-bit replay
    equality vs the replicated fused step at the same dp grouping,
    tolerance replay vs single-device, and measurably sharded params."""
    import os as _os
    import jax

    # the gate needs 8 virtual devices BEFORE backend init (Makefile
    # exports the flags; replicate the __graft_entry__ guard for direct
    # invocations)
    flags = _os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        _os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import numpy as onp
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from .. import telemetry
    from ..gluon import Trainer, nn
    from ..gluon.loss import SoftmaxCrossEntropyLoss
    from ..models import bert_gluon, resnet
    from ..ndarray import NDArray
    from .mesh import make_mesh

    if jax.device_count() < 8:
        print(f"shard-check: FAIL — needs 8 devices, have "
              f"{jax.device_count()} (set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8)")
        return 1
    devices = jax.devices()[:8]
    checks = []

    def check(name, ok):
        checks.append((name, bool(ok)))
        if verbose:
            print(f"  [{'ok' if ok else 'FAIL'}] {name}")

    # ---- plan inference: resnet50 (conv tower replicated, head sharded)
    r50 = resnet.resnet50_v1(classes=8)
    r50.initialize()
    r50(NDArray(jnp.zeros((1, 32, 32, 3), jnp.float32)))
    rplan = infer_plan(r50, tp=2)
    names = list(rplan.entries)
    head_w = [n for n in names
              if rplan.entries[n]["rule"] == RULE_DENSE_W]
    conv_sharded = [n for n in rplan.sharded_names()
                    if "conv" in n or "batchnorm" in n or "bn" in n]
    check("resnet50 plan: fc head column-sharded, conv/bn replicated",
          len(head_w) >= 1 and not conv_sharded)

    # ---- plan inference: 2-layer transformer (qkv/proj/ffn + embeddings)
    bert = bert_gluon.BERTModel(units=16, heads=2, layers=2, ffn_units=32,
                                vocab_size=64, max_length=16)
    bert.initialize()
    bert(NDArray(jnp.zeros((2, 8), jnp.int32)))
    bplan = infer_plan(bert, tp=2)
    rules = {n: e["rule"] for n, e in bplan.entries.items()}
    qkv = [n for n in rules if "qkv.weight" in n]
    emb = [n for n in rules if "word_embed" in n]
    ln = [n for n in rules if ".ln" in n or "layernorm" in n]
    check("transformer plan: attention qkv/proj + ffn column-sharded",
          qkv and all(rules[n] == RULE_DENSE_W for n in qkv))
    check("transformer plan: embeddings column-sharded on tp",
          emb and all(rules[n] == RULE_EMBED for n in emb))
    check("transformer plan: layernorm replicated",
          ln and not any(bplan.is_sharded(n) for n in ln))

    # ---- JSON round-trip + fingerprint stability + re-key on edit
    rt = ShardingPlan.from_json(bplan.to_json())
    check("plan JSON round-trip preserves fingerprint",
          rt.fingerprint == bplan.fingerprint and
          rt.entries == bplan.entries)
    edited = ShardingPlan.from_json(bplan.to_json())
    some = edited.sharded_names()[0]
    edited.entries[some] = {"partition":
                            [None] * len(edited.entries[some]["partition"]),
                            "rule": "manual"}
    check("plan edit changes fingerprint (dispatch re-key)",
          edited.fingerprint != bplan.fingerprint and
          edited.extra_key() != bplan.extra_key())

    # ---- fused sharded step: tp=2 × hierarchical dp (dp_out=2 × dp_in=2)
    rs = onp.random.RandomState(0)
    x = rs.randn(8, 6).astype(onp.float32)
    y = rs.randint(0, 4, (8,)).astype(onp.int32)
    L = SoftmaxCrossEntropyLoss()

    def nets():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        net.hybridize()
        net(NDArray(jnp.asarray(x)))
        return net

    seed = nets()
    seed_vals = {n: jnp.array(p.data()._data, copy=True)
                 for n, p in seed.collect_params().items()}

    def clone():
        net = nets()
        for n, p in net.collect_params().items():
            p.set_data(NDArray(jnp.array(seed_vals[n], copy=True)))
        return net

    def run(mesh, plan, steps=5):
        net = clone()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9},
                     mesh=mesh, sharding_plan=plan)
        st = tr.fuse_step(L)
        losses = [onp.asarray(st(x, y)._data) for _ in range(steps)]
        st.sync()
        assert st.fused, st.fallback_reason
        params = {n: p.data()._data for n, p in
                  net.collect_params().items()}
        return losses, params, st

    mesh_s = make_mesh({"dp_out": 2, "dp_in": 2, "tp": 2}, devices=devices)
    mesh_r = make_mesh({"dp": 4}, devices=devices[:4])
    mesh_1 = make_mesh({"dp": 1}, devices=devices[:1])
    plan = infer_plan(seed, tp=2)

    base = telemetry.summary()
    losses_s, params_s, st_s = run(mesh_s, plan)
    cur = telemetry.summary()

    def delta(k):
        return cur.get(k, 0) - base.get(k, 0)

    check("0 retraces / 0 rebuilds / 1 dispatch per fused sharded step",
          delta("fused.retraces") == 0 and delta("fused.rebuilds") == 0 and
          delta("fused.dispatches") == 5 and delta("fused.steps") == 5)
    check("collective telemetry per-axis bytes recorded",
          delta("collective.tp.bytes") > 0 and
          delta("collective.dp.bytes") > 0)

    losses_r, params_r, _ = run(mesh_r, None)
    losses_1, params_1, _ = run(mesh_1, None)
    check("replay equality: bit-for-bit vs replicated step at same dp",
          all(a.tobytes() == b.tobytes()
              for a, b in zip(losses_s, losses_r)) and
          all(onp.asarray(params_s[n]).tobytes() ==
              onp.asarray(params_r[n]).tobytes() for n in params_s))
    check("replay equality vs single-device (dryrun tolerance)",
          all(abs(float(a) - float(b)) < 1e-5
              for a, b in zip(losses_s, losses_1)) and
          all(onp.allclose(onp.asarray(params_s[n]),
                           onp.asarray(params_1[n]),
                           rtol=1e-5, atol=1e-6) for n in params_s))
    w0 = next(n for n in params_s if plan.is_sharded(n))
    check("params measurably sharded (per-device bytes = 1/tp)",
          shard_bytes(params_s[w0]) * 2 == params_s[w0].nbytes and
          shard_bytes(params_r[w0]) == params_r[w0].nbytes)

    ok_all = all(ok for _, ok in checks)
    if verbose:
        print(f"shard-check: {'PASS' if ok_all else 'FAIL'} "
              f"({len(checks)} checks, plan fp={plan.fingerprint})")
    return 0 if ok_all else 1


if __name__ == "__main__":
    import sys
    if "--check" in sys.argv:
        sys.exit(_selfcheck())
    print(__doc__)
