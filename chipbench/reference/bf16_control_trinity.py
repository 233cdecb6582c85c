"""The upper of the two readings a tolerance of ``trinity-mini-train-swa8k``
has to lie between, at the timed size on the chip: the distances from the
float32 reference of the reference *stored in bfloat16* (the nearest
precision below the one the configuration states), by the harness's own
comparison (``train_lm.distances``).  ``bf16_control.py`` and
``bf16_control_olmo.py`` beside this file read Solar-Open2's and
Olmo-Hybrid's files by name; this is the same reading for Trinity-Mini's.
No cell runs it: it is how the limits in the configuration's
``reference.tolerances`` were read (PERF.md section 6, PR 40) and can be
read again:

    chiprun -- python3 chipbench/reference/bf16_control_trinity.py <seed> ...

The system's own distances are in every plain run's log.  One JSON line a
seed, and ``chiprun_out/bf16_reading_trinity.json``."""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from chipbench.files import load_json, load_module  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

CONFIG = "chipbench/configs/trinity-mini-26b-train-ep8.json"
MIX = "chipbench/traffic/ring-lm-listed.json"


def main(seeds):
    config, mix = load_json(ROOT, CONFIG), load_json(ROOT, MIX)
    lm = load_module(ROOT, "chipbench", "runners", "train_lm.py")
    listed = load_module(ROOT, "chipbench", "runners", "train_lm_listed.py")
    ref = load_module(ROOT, "chipbench",
                      *config["reference"]["module"].split("/"))
    cfg, names = listed.reference_cfg(config), config["reference"]["checked"]
    rows = config["model"]["kwargs"]["vocab_held"][1]
    out = []
    for seed in seeds:
        t0 = time.time()
        seed %= 2 ** 31 - 1
        x, y = lm.make_ring(config, mix, seed, rows)[0]
        net = lm.build_net(config, seed)
        row = {"seed": seed, "sequence": config["sequence"]}
        l0, row["bf16_storage"] = lm.distances(
            net, None, ref, cfg, x, y, names, dtype=jnp.bfloat16)
        params = {n: p.data()._data for n, p in net.collect_params().items()
                  if jnp.issubdtype(p.data()._data.dtype, jnp.floating)}
        lb = float(jax.jit(lambda p, x, y: ref.loss(
            p, x, y, cfg, dtype=jnp.bfloat16))(params, x._data, y._data))
        row.update(bf16_loss_rel=abs(lb - l0) / abs(l0),
                   seconds=time.time() - t0)
        print(json.dumps(row), flush=True)
        out.append(row)
        del net, params
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bf16_reading_trinity.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main([int(s) for s in sys.argv[1:]] or [2136000001])
