"""The ``train_lm`` runner: a language model trained through
``gluon.Trainer.fuse_step`` on one long sequence a step, checked against a
*plain* reference (a file under ``chipbench/reference/`` that imports nothing
from the program) at the timed sizes.

The ring holds ``mix["batches"]`` batches of ``config["batch"]`` sequences of
``config["sequence"] + 1`` token ids, uniform over the vocabulary rows held:
the inputs are the first ``sequence`` ids, the labels the same ids shifted
by one.  Before the step is built, on the first batch and the seeded weights:

(a) the reference's loss, against which the first fused loss is held;
(b) the logits of the system's hybridized forward (training mode: the routes
    the step traces) against the reference's, as error norm over norm;
(c) the gradients of one tensor of each kind from the system's autograd path
    against ``jax.grad`` of the reference, likewise.

Each limit is the configuration's (``reference.tolerances``), set between
what the system reads and what float32 rounded to bfloat16 storage reads
(PERF.md section 6).  Then the arrays of the check are freed and
``chipbench/measure.py`` times the step like every training cell.  A traced
run keeps the self time of every instruction and, through
``step.hlo_text``, adds it up by kind of layer (``scope_reduce.py``).
"""
from __future__ import annotations

import importlib
import inspect
import math

from chipbench import measure, scope_reduce
from chipbench.files import load_module


# scopes the traced run's log splits the device time by (PERF.md section 5)
MARKERS = ("ssm.conv", "ssm.scan", "in_proj", "out_proj", "moe.route",
           "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
           "attn.core", "/embed/", "/head/", "mx.loss", "mx.opt",
           "transpose(jvp(mx.fwd))", "rematted_computation")


def make_ring(config, mix, seed, rows):
    """``mix["batches"]`` pairs ``(tokens, labels)`` of (batch, sequence)
    int32, made on the device by one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray

    shape = (mix["batches"], config["batch"], config["sequence"] + 1)
    ids = jax.jit(lambda key: jax.random.randint(key, shape, 0, rows,
                                                 jnp.int32))(
        jax.random.PRNGKey(seed))
    ring = [(ids[i, :, :-1], ids[i, :, 1:]) for i in range(shape[0])]
    jax.block_until_ready(ring)
    return [(NDArray(x), NDArray(y)) for x, y in ring]


def build_net(config, seed):
    """The net from the configuration's own top-level keys (those the
    builder's signature names) and ``model.kwargs`` on top, initialised from
    ``seed`` and hybridized.  Every shape is known: no forward is needed."""
    import mxnet_tpu as mx

    m = config["model"]
    builder = getattr(importlib.import_module(m["module"]), m["builder"])
    names = set(inspect.signature(builder).parameters)
    kwargs = {k: v for k, v in config.items() if k in names}
    kwargs.update({k: tuple(v) if isinstance(v, list) else v
                   for k, v in m.get("kwargs", {}).items()})
    mx.seed(seed)
    net = builder(**kwargs)
    net.initialize()
    net.hybridize()
    return net


def reference_cfg(config):
    cfg = {k: v for k, v in config.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["experts_held"] = tuple(config["model"]["kwargs"]["experts_held"])
    return cfg


def checked_tensors(pattern):
    """One tensor of each kind, of the first layer of its kind."""
    first = {k: pattern.index(k) for k in "ME*" if k in pattern}
    names = ["embed.weight"]
    if "M" in first:
        names += [f"layers.{first['M']}.mixer.{n}"
                  for n in ("in_proj.weight", "A_log", "conv_weight")]
    if "E" in first:
        names += [f"layers.{first['E']}.mixer.{n}"
                  for n in ("router_weight", "experts_up")]
    if "*" in first:
        names += [f"layers.{first['*']}.mixer.q_proj.weight"]
    return names


def distances(net, loss_fn, ref, cfg, x, y, names, dtype=None):
    """The reference's loss, and how far the system lies from it:
    ``{"logits": e, "grad <name>": e, ...}`` with ``e`` the norm of the
    difference over the reference's norm.  ``dtype`` computes the
    *reference* in that storage precision instead of the system (the
    reading that a tolerance has to refuse).  Frees what it made."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import autograd

    params = {n: p.data()._data for n, p in net.collect_params().items()
              if jnp.issubdtype(p.data()._data.dtype, jnp.floating)}
    sel = {n: params[n] for n in names}
    rest = {n: v for n, v in params.items() if n not in sel}

    def ref_all(sel, rest, x, y, dtype):
        (l, z), g = jax.value_and_grad(
            lambda s: ref.loss({**rest, **s}, x, y, cfg, dtype=dtype,
                               with_logits=True), has_aux=True)(sel)
        return l, z, g
    ref_all = jax.jit(ref_all, static_argnums=4)      # weights are arguments
    if dtype is None:
        with autograd.record():
            out = net(x)
            l = loss_fn(out, y)
        l.backward()
        s_z = out._data
        # backward() seeds ones over the per-sample losses: the gradient
        # of their sum, batch times the mean's
        s_g = {n: net.collect_params()[n].grad()._data / x.shape[0]
               for n in names}
        del out, l
        for p in net.collect_params().values():   # free the other gradients
            edge = p.data()._grad_edge
            if edge is not None:
                edge.grad = None
    else:
        _, s_z, s_g = ref_all(sel, rest, x._data, y._data, dtype)
    r_loss, r_z, r_g = ref_all(sel, rest, x._data, y._data, jnp.float32)

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def cut(n, g):          # one held expert's Up, not all of them
        return g[0] if n.endswith("experts_up") else g
    per_token = jnp.linalg.norm((s_z - r_z).astype(jnp.float32), axis=-1) \
        / jnp.linalg.norm(r_z.astype(jnp.float32), axis=-1)
    out = {"logits": err(s_z, r_z),
           # the median token: a token whose top-k flipped on a near-tie is
           # far off in both readings and says nothing about precision
           "logits_median": float(jnp.median(per_token))}
    out.update({f"grad {n}": err(cut(n, s_g[n]), cut(n, r_g[n]))
                for n in names})
    return float(r_loss), out


def run(cell):
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon import loss as gloss

    base = load_module(cell["root"], "chipbench", "runners", "train.py")
    config, mix, device = cell["config"], cell["mix"], cell["devices"][0]
    ref = load_module(cell["root"], "chipbench",
                      *config["reference"]["module"].split("/"))
    held = config["model"]["kwargs"]
    rows = held["vocab_held"][1]
    batch, pattern = config["batch"], config["hybrid_override_pattern"]
    base.say(cell, f"{config['name']} {pattern} batch={batch} x "
             f"{config['sequence']} tokens, experts {held['experts_held']}, "
             f"vocabulary rows {held['vocab_held']} on {device.device_kind}; "
             "imports done")
    ring = make_ring(config, mix, cell["seed"], rows)
    net = build_net(config, cell["seed"])
    n_params = sum(p.data().size for p in net.collect_params().values())
    base.say(cell, f"ring of {len(ring)} batches, net of {n_params / 1e6:.1f} "
             "M parameters initialised")
    loss_fn = getattr(gloss, config["loss"])()
    tol = config["reference"]["tolerances"]
    ref_loss, dist = distances(net, loss_fn, ref, reference_cfg(config),
                               *ring[0], checked_tensors(pattern))
    base.say(cell, f"reference loss {ref_loss:.6f}; distances " + " ".join(
        f"{k}={v:.3e}" for k, v in dist.items()))
    opt = config["optimizer"]
    step = Trainer(net.collect_params(), opt["name"],
                   dict(opt["params"])).fuse_step(loss_fn)

    evidence, warm = measure.train_window(
        cell, base, step, ring, batch, cell["devices"][:1],
        profiled=scope_reduce.profiled)
    homes = {frozenset(p.data()._data.devices())
             for p in net.collect_params().values()}
    limit = {k: tol["grad"] if k.startswith("grad ") else tol[k]
             for k in dist}
    limit.update(tol.get("by_name", {}))
    evidence["checks"] = [
        (f"(a) first fused loss {warm[0]:.6f} within {tol['loss_rtol']} "
         f"relative of the plain reference's {ref_loss:.6f} "
         f"(off by {abs(warm[0] - ref_loss) / abs(ref_loss):.2e})",
         math.isfinite(warm[0])
         and abs(warm[0] - ref_loss) <= tol["loss_rtol"] * abs(ref_loss)),
        *[(f"({'c' if k.startswith('grad ') else 'b'}) {k}: error over norm "
           f"{v:.3e} within {limit[k]}", math.isfinite(v) and v <= limit[k])
          for k, v in dist.items()],
        *evidence["checks"],
        (f"every parameter on the one device (saw {len(homes)} placements)",
         homes == {frozenset([device])}),
    ]
    cw, ca = evidence["counters"]["window"]
    evidence["moe"] = {
        "tokens_held": base.delta(ca, cw, "moe.tokens_held"),
        "tokens_routed": base.delta(ca, cw, "moe.tokens_routed"),
        "experts_held": held["experts_held"][1],
        "expert_layers": pattern.count("E")}
    hlo = getattr(step, "hlo_text", None)
    if cell["trace"] and hlo and evidence.get("op_seconds"):
        scopes = scope_reduce.instruction_scopes(hlo(*ring[0]))
        evidence["layer_kind_s"] = scope_reduce.layer_kind_seconds(
            evidence["op_seconds"], scopes, pattern)
        evidence["scope_s"] = scope_reduce.marker_seconds(
            evidence["op_seconds"], scopes, MARKERS)
        base.say(cell, f"{len(scopes)} instructions with a scope; seconds "
                 f"by kind of layer {evidence['layer_kind_s']}; by scope "
                 + " ".join(f"{k}={v:.3f}"
                            for k, v in evidence["scope_s"].items()))
    return evidence
