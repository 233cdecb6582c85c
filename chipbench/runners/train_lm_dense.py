"""The ``train_lm_dense`` runner: ``train_lm_listed`` for a language model
that holds **no experts**.  ``train_lm.run`` reads
``model.kwargs.experts_held`` for its first line of log and for
``evidence["moe"]``, so no accepted runner can run a dense model, and the
accepted files are not edited.

Like ``train_lm_listed`` it runs ``train_lm.py``'s own ``run`` on a copy of
that module loaded by path, with the pattern, the checked tensors and the
scopes taken from the configuration's file (``pattern``,
``reference.checked``, ``scopes``).  The share of experts that
``train_lm.run`` asks for is answered *here*, on the copy of the
configuration that only that function sees, with ``[0, 0]``: none held.
The net is built from the configuration as it is written, so the program's
builder is never handed an ``experts_held`` (it takes no such argument), and
the plain reference is given the configuration's own keys.  The evidence
leaves without ``moe``: there is nothing to count.

**The check runs first, on arrays of its own, and leaves the device empty.**
``train_lm.run`` builds the ring and the net, checks them against the
reference and builds the step around the same net.  The programs that the
check loads onto the device (319 allocations, 80 MB, when they come from the
compilation cache; 22 when they were compiled in the process, or when the
runtime had to make room at the check's peak) stay loaded, scattered between
the weights, and the optimizer state and the step's temporaries, 12.6 of the
chip's 15.75 GiB here, are then laid around them: this cell's step ran at
577.1 or at 571.0 ms, fixed for the life of a process, by which of the two
it met (PERF.md section 6, PR 36).  So this runner makes a ring and a net
from the seed, runs ``train_lm.distances`` on them, and releases everything:
the arrays, the programs JAX holds (``jax.clear_caches()``) and the program's
own cache of calls.  ``train_lm.run`` then starts on an empty device, builds
the same ring and the same net from the same seed, is handed the distances
already read, and has the programs that built them unloaded once more before
it builds the step: the state and the step are laid out as a process that
only trains would lay them, the same way in every run (223 allocations at
the end of twenty runs of twenty; two layouts 512 B and 0.14 % apart,
571.1 | 571.9 ms, remain).

Everything else is ``train_lm``'s: the ring of token ids, the distances to
the plain reference at the timed sizes and their checks (a) loss, (b)
logits, (c) gradients against ``reference.tolerances``,
``measure.train_window`` and the evidence its readers read.
"""
from __future__ import annotations

import gc

from chipbench.files import load_module


def release_programs():
    """Unload every program this process has loaded onto the device: those
    behind ``jax.jit`` and the program's cache of calls.  Arrays stay."""
    import jax
    from mxnet_tpu import dispatch_cache

    jax.effects_barrier()
    dispatch_cache.clear()
    jax.clear_caches()
    gc.collect()


def heap(device):
    s = device.memory_stats() or {}
    return (f"{s.get('num_allocs')} allocations, {s.get('bytes_in_use')} "
            f"bytes in use, largest free block "
            f"{s.get('largest_free_block_bytes')}")


def run(cell):
    config = cell["config"]
    if "experts_held" in config["model"].get("kwargs", {}):
        raise ValueError(f"{config['name']} holds experts: its mix is "
                         "ring-lm-listed, not ring-lm-dense")
    lm = train_lm_for(config, cell["root"])
    read = check_first(cell, lm)

    def already_read(*_asked, **_kw):
        release_programs()      # what building the ring and the net loaded
        return read
    lm.distances = already_read
    model = dict(config["model"], kwargs=dict(config["model"]["kwargs"],
                                              experts_held=[0, 0]))
    evidence = lm.run(dict(cell, config=dict(
        config, model=model, hybrid_override_pattern=config["pattern"])))
    del evidence["moe"]
    return evidence


def train_lm_for(config, root):
    """``train_lm.py`` as a module object of this call's own (nothing another
    runner sees moves) that builds, names and splits by ``config``."""
    lm = load_module(root, "chipbench", "runners", "train_lm.py")
    listed = load_module(root, "chipbench", "runners", "train_lm_listed.py")
    build = lm.build_net
    lm.build_net = lambda _asked, seed: build(config, seed)
    lm.reference_cfg = lambda _asked: listed.reference_cfg(config)
    lm.checked_tensors = lambda _pattern: list(config["reference"]["checked"])
    lm.MARKERS = tuple(config["scopes"])
    return lm


def check_first(cell, lm):
    """``train_lm.distances`` on a ring and a net of this call's own, made
    from the cell's seed as ``train_lm.run`` makes its own, and nothing left
    on the device after it."""
    from mxnet_tpu.gluon import loss as gloss

    config, device = cell["config"], cell["devices"][0]
    say = load_module(cell["root"], "chipbench", "runners", "train.py").say
    ref = load_module(cell["root"], "chipbench",
                      *config["reference"]["module"].split("/"))
    rows = config["model"]["kwargs"]["vocab_held"][1]
    x, y = lm.make_ring(config, cell["mix"], cell["seed"], rows)[0]
    net = lm.build_net(config, cell["seed"])
    read = lm.distances(net, getattr(gloss, config["loss"])(), ref,
                        lm.reference_cfg(config), x, y,
                        lm.checked_tensors(config["pattern"]))
    say(cell, f"checked against the plain reference; device: {heap(device)}")
    del net, x, y
    release_programs()
    say(cell, f"the check's arrays and programs released; device: "
        f"{heap(device)}")
    return read
