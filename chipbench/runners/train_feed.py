"""The ``train_feed`` runner: the ``train`` cell with its batches *fed*, not
resident: host batches as a decoded-image loader hands them over (uint8,
channel-last, int32 labels) go through ``mxnet_tpu.io.DataFeed`` - the
staging thread's host-to-device copy, the cast and the per-channel
normalisation on the device, a ring of ``mix["depth"]`` staged batches -
and the training loop takes the next staged batch for every step.

The mix's file states the wire (``wire``: shape, dtype, layout), the
normalisation (``scale``, ``mean``, ``std``) and the ring's depth; the
configuration's ``inputs`` describe the resident ring and are not read.
``mix["batches"]`` seeded NumPy batches are made during set-up and cycled
on the host for the whole run: no decode, no augmentation, closed loop.

``runners/train.py`` (loaded by path) builds the net and the step and
gives the float32 reference loss.  The reference does not see what the feed
staged: the first host batch is normalised here with plain NumPy
(``(x * scale - mean) / std``, channel-last, float32), the staged batch -
the first step's - has to agree with that, and the reference loss is taken
on the NumPy array.  ``measure.train_window`` times the loop unedited: its
``loop`` reads ``ring[k % len(ring)]``, and ``Fed`` answers every ``[k]``
with the feed's next batch.  What the loop waited for a batch - for the
ring to hold one and for its copy to have landed - is the feed's own
``consumer_wait_s``, read after every draw.
"""
from __future__ import annotations

import itertools
import math

import numpy as onp

from chipbench import measure
from chipbench.files import load_module


def host_batches(config, mix, seed):
    """``mix["batches"]`` pairs ``(images, labels)`` of NumPy arrays as the
    wire carries them, from ``seed``."""
    rs = onp.random.RandomState(seed % (2 ** 32))
    wire, batch = mix["wire"], config["batch"]
    return [(rs.randint(0, 256, (batch, *wire["shape"]),
                        dtype=onp.dtype(wire["dtype"])),
             rs.randint(0, mix["classes"], (batch,), dtype=onp.int32))
            for _ in range(mix["batches"])]


def normalised(images, mix):
    """What the net is to be fed, by plain NumPy: the wire's uint8 images
    as float32, ``(x * scale - mean) / std`` a channel, layout unchanged."""
    x = images.astype(onp.float32) * onp.float32(mix["scale"])
    return (x - onp.asarray(mix["mean"], onp.float32)) \
        / onp.asarray(mix["std"], onp.float32)


class Fed:
    """What ``loop`` indexes: every ``[k]`` is the feed's next batch (the
    batch the reference saw first), and the feed's cumulative
    ``consumer_wait_s`` after each draw is kept."""

    def __init__(self, feed, first):
        self.feed, self.first, self.waited = feed, first, []

    def __len__(self):
        return 1

    def __getitem__(self, _k):
        batch, self.first = self.first, None
        if batch is None:
            batch = next(self.feed)
        self.waited.append(self.feed.stats()["consumer_wait_s"])
        return batch


def run(cell):
    import jax
    from mxnet_tpu.io import DataFeed
    from mxnet_tpu.ndarray import NDArray

    base = load_module(cell["root"], "chipbench", "runners", "train.py")
    config, mix, device = cell["config"], cell["mix"], cell["devices"][0]
    base.say(cell, f"{config['name']} entry={config['entry']} "
             f"batch={config['batch']} fed {mix['wire']} depth "
             f"{mix['depth']} on {device.device_kind}; imports done")
    # a program whose feed cannot take this wire fails here, at once
    hosts = host_batches(config, mix, cell["seed"])
    feed = DataFeed(itertools.cycle(hosts),
                    depth=mix["depth"], device=device, scale=mix["scale"],
                    mean=mix["mean"], std=mix["std"])
    try:
        first = next(feed)
        x0, y0 = first
        base.say(cell, f"feed running, first batch {x0.shape} {x0.dtype} "
                 "staged")
        net, loss_fn, step = base.build(config, cell["seed"], x0)
        plain = normalised(hosts[0][0], mix)
        staged = onp.asarray(x0._data, onp.float32)
        off = float(onp.abs(staged - plain).max())
        ref = base.reference_loss(net, loss_fn, NDArray(jax.device_put(
            plain, device)), y0)
        base.say(cell, f"float32 reference loss {ref:.5f} on the NumPy-"
                 f"normalised batch; the staged one is off by {off:.3g}")
        fed = Fed(feed, first)
        evidence, warm = measure.train_window(
            cell, base, step, fed, config["batch"], cell["devices"][:1])
        stats = feed.stats()
    finally:
        feed.close()
    n = evidence["attempted"]                 # draws of the window
    waited = fed.waited[-1] - fed.waited[-n - 1]
    base.say(cell, f"feed {stats}; the window's {n} draws waited "
             f"{1e3 * waited:.3f} ms in all")
    rtol = config["reference"]["rtol"]
    homes = {frozenset(p.data()._data.devices())
             for p in net.collect_params().values()}
    wire_bytes = int(onp.prod(mix["wire"]["shape"])) * config["batch"] \
        * onp.dtype(mix["wire"]["dtype"]).itemsize
    evidence["checks"] = [
        (f"first fused loss {warm[0]:.5f} within {rtol} relative of the "
         f"float32 reference {ref:.5f}",
         math.isfinite(warm[0]) and abs(warm[0] - ref) <= rtol * abs(ref)),
        *evidence["checks"],
        (f"the staged first batch is the wire's, normalised: {x0.shape} "
         f"{x0.dtype} within {mix['staged_atol']} of NumPy's (off by "
         f"{off:.3g})", staged.shape == plain.shape
         and off <= mix["staged_atol"]),
        (f"every parameter on the one device (saw {len(homes)} placements)",
         homes == {frozenset([device])}),
        (f"every step's batch went over the wire as {mix['wire']['dtype']} "
         f"({stats['h2d_bytes']} bytes for {stats['staged_batches']} "
         "batches)", stats["staged_batches"] >= len(fed.waited)
         and stats["h2d_bytes"] >= stats["staged_batches"] * wire_bytes
         and stats["h2d_bytes"] < stats["staged_batches"] * 1.01
         * (wire_bytes + 4 * config["batch"])),
        ("the feed staged on its own thread (no synchronous mode)",
         not stats["sync_mode"]),
    ]
    evidence["feed"] = {"wait_s": waited, "draws": n,
                        "consumer_waits": stats["consumer_waits"],
                        "h2d_bytes": stats["h2d_bytes"]}
    return evidence
