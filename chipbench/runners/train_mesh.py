"""The ``train_mesh`` runner: ``Trainer(mesh=, sharding_plan=).fuse_step``
over all the chips of one host, fed from a ring of *global* batches.

It is the ``train`` runner's cell across chips: the mix names the mesh
(``{"dp": 2, "tp": 2}``) and the global batch, the net is the configuration's,
the plan is what ``parallel.sharding.infer_plan`` derives, and the batches
are made once, sharded over ``dp`` and cycled.  ``train_samples_s`` counts
global samples; the step time is the median over the chips' planes;
``memory_peak_bytes`` is the fullest chip's; and ``evidence["peaks"]`` is
the host's (one chip's FLOP/s times the chips), or ``mfu.train`` would
divide four chips' work by one chip's peak.

``runners/train.py`` is loaded as a file and does the timing
(``chipbench/measure.py``).  Nothing is asked of the program that PR 28's
tree does not have.
"""
from __future__ import annotations

import importlib
import math

from chipbench import measure
from chipbench.files import load_module


def build(config, mesh, seed, x0):
    """The net from ``seed`` with its shapes resolved, the plan, and the
    step object of ``Trainer(mesh=, sharding_plan=)``: creating the Trainer
    places every parameter as the plan says."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.sharding import infer_plan

    mx.seed(seed)
    m, opt = config["model"], config["optimizer"]
    net = getattr(importlib.import_module(m["module"]),
                  m["builder"])(**m.get("kwargs", {}))
    net.initialize()
    net.hybridize()
    net(x0[:1])
    plan = infer_plan(net, mesh=mesh)
    loss_fn = getattr(gloss, config["loss"])()
    trainer = Trainer(net.collect_params(), opt["name"], dict(opt["params"]),
                      mesh=mesh, sharding_plan=plan)
    return net, loss_fn, plan, trainer.fuse_step(loss_fn)


def misplaced(net, mesh, plan):
    """Names of the parameters that do not lie as the plan says."""
    out = []
    for name, p in net.collect_params().items():
        a = p.data()._data
        if not a.sharding.is_equivalent_to(plan.sharding(mesh, name), a.ndim):
            out.append(name)
    return out


def run(cell):
    import jax
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import batch_sharding
    from mxnet_tpu.parallel.sharding import shard_bytes

    base = load_module(cell["root"], "chipbench", "runners", "train.py")
    config, mix, devices = cell["config"], cell["mix"], cell["devices"]
    batch = mix["global_batch"]
    if config["entry"]["kind"] != "Trainer.fuse_step":
        raise ValueError("train_mesh enters through Trainer.fuse_step, not "
                         f"{config['entry']['kind']!r}")
    base.say(cell, f"{config['name']} mesh={mix['mesh']} global batch={batch} "
             f"on {len(devices)} x {devices[0].device_kind}; imports done")
    mesh = make_mesh(mix["mesh"], devices=devices)
    ring = [tuple(NDArray(jax.device_put(a._data,
                                         batch_sharding(mesh, a.ndim)))
                  for a in pair)
            for pair in base.make_ring(dict(config, batch=batch), mix,
                                       cell["seed"])]
    base.say(cell, f"ring of {len(ring)} global batches, sharded over dp")
    net, loss_fn, plan, step = build(config, mesh, cell["seed"], ring[0][0])
    base.say(cell, f"net initialised, {plan!r}")
    ref = base.reference_loss(net, loss_fn, *ring[0])
    base.say(cell, f"float32 reference loss {ref:.5f}")

    evidence, warm = measure.train_window(cell, base, step, ring, batch,
                                          devices)
    rtol = config["reference"]["rtol"]
    wrong = misplaced(net, mesh, plan)
    sharded = plan.sharded_names()
    a = net.collect_params()[sharded[0]].data()._data if sharded else None
    evidence["checks"] = [
        (f"first fused loss {warm[0]:.5f} within {rtol} relative of the "
         f"float32 reference {ref:.5f}",
         math.isfinite(warm[0]) and abs(warm[0] - ref) <= rtol * abs(ref)),
        *evidence["checks"],
        (f"every parameter placed as the plan says ({len(wrong)} not: "
         f"{wrong[:3]})", not wrong),
        (f"{len(sharded)} parameters stored 1/tp a chip"
         + (f" ({sharded[0]}: {shard_bytes(a)} of {a.nbytes} bytes)"
            if sharded else ""),
         bool(sharded) and shard_bytes(a) * mix["mesh"]["tp"] == a.nbytes),
    ]
    if cell["peaks"]:
        evidence["peaks"] = dict(
            cell["peaks"], bf16_flops_per_s=len(devices)
            * cell["peaks"]["bf16_flops_per_s"])
    return evidence
