"""The ``train_lm_listed`` runner: ``train_lm`` for a language model whose
layer letters that file does not know.  What ``train_lm`` derives from the
letters ``M``, ``E`` and ``*`` the configuration's file *lists*:

    pattern             one letter a block of ``net.layers`` (the scope
                        ``layers/<i>/`` of block i), for
                        ``scope_reduce.layer_kind_seconds``
    reference.checked   the tensors whose gradients are compared, one of
                        each kind (``distances`` takes one held expert of a
                        name that ends in ``experts_up``)
    scopes              the markers the traced run's log and
                        ``evidence["scope_s"]`` split the device time by

Everything else is ``train_lm``'s own ``run``, on a copy of that module
loaded by path (as it loads ``train``) whose three letter-bound names are
set from the lists: the ring of token ids, the net from the configuration's
keys, the distances to the plain reference at the timed sizes and their
checks (a) loss, (b) logits, (c) gradients against
``reference.tolerances``, ``measure.train_window`` and the evidence.  The
reference is given every top-level key of the configuration with
``model.kwargs`` (the share held) on top.
"""
from __future__ import annotations

from chipbench.files import load_module


def reference_cfg(config):
    """The configuration's keys with the share held (``model.kwargs``, lists
    as tuples) on top: what the plain reference reads its sizes from."""
    cfg = dict(config)
    cfg.update({k: tuple(v) if isinstance(v, list) else v
                for k, v in config["model"].get("kwargs", {}).items()})
    return cfg


def run(cell):
    config = cell["config"]
    # a module object of this call's own: nothing another runner sees moves
    lm = load_module(cell["root"], "chipbench", "runners", "train_lm.py")
    lm.checked_tensors = lambda _pattern: list(config["reference"]["checked"])
    lm.MARKERS = tuple(config["scopes"])
    lm.reference_cfg = reference_cfg
    return lm.run(dict(cell, config=dict(
        config, hybrid_override_pattern=config["pattern"])))
