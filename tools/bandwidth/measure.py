#!/usr/bin/env python
"""Communication bandwidth benchmark — ≙ reference tools/bandwidth/
measure.py (KVStore push/pull cost sweep).

Measures, per tensor size: host→device transfer, device→host transfer,
and all-reduce (psum over every visible device — ICI on a TPU pod slice,
the virtual CPU mesh under XLA_FLAGS=--xla_force_host_platform_device_count
elsewhere). Prints GB/s per row.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def measure(sizes_mb, repeat=5):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    print(f"devices: {n} × {devs[0].platform}")
    mesh = Mesh(np.array(devs), ("d",))
    psum = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                     in_specs=P("d"), out_specs=P())
    rows = []
    # device_put is asynchronous: every timed upload carries distinct
    # bytes and is forced to materialize via a host fetch of a
    # dependent scalar
    red = jax.jit(jnp.sum)
    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 // 4)
        host = np.ones((elems,), np.float32)
        float(red(jax.device_put(host, devs[0])))       # warm executable
        t0 = time.perf_counter()
        for i in range(repeat):
            host[0] = float(i) + 0.5                    # distinct bytes
            dev_arr = jax.device_put(host, devs[0])
            float(red(dev_arr))
        h2d = mb * repeat / (time.perf_counter() - t0) / 1024
        t0 = time.perf_counter()
        for _ in range(repeat):
            _ = np.asarray(dev_arr)
        d2h = mb * repeat / (time.perf_counter() - t0) / 1024
        ar_gbs = float("nan")
        if n > 1:
            shard = np.ones((elems - elems % n,), np.float32)
            arr = jax.device_put(shard)
            # distinct executions without re-uploading: fuse a per-rep
            # scalar scale into the collective, fetch the reduced scalar
            ar = jax.jit(lambda a, s: jnp.sum(psum(a * s)))
            float(ar(arr, 1.0))                         # compile
            t0 = time.perf_counter()
            for i in range(repeat):
                float(ar(arr, float(i) + 0.5))
            ar_gbs = mb * repeat / (time.perf_counter() - t0) / 1024
        rows.append((mb, h2d, d2h, ar_gbs))
        print(f"size {mb:8.2f} MB | h2d {h2d:7.2f} GB/s | "
              f"d2h {d2h:7.2f} GB/s | allreduce {ar_gbs:7.2f} GB/s")
    return rows


def measure_kvstore(sizes_mb, repeat=5):
    """Time the dist KVStore pushpull data path itself (run under
    tools/launch.py -n N).  The collective transport moves O(tensor)
    bytes per key regardless of N (ring all-reduce), so the printed
    per-key wall time should be ~flat in worker count — the check the
    r1 allgather path failed (traffic ×N)."""
    import numpy as np
    from mxnet_tpu.parallel import dist
    dist.initialize()
    import jax
    import mxnet_tpu as mx
    kv = mx.kvstore.create("dist_sync")
    rank, n = kv.rank, kv.num_workers
    if rank == 0:
        print(f"kvstore pushpull path: {n} workers")
    import jax.numpy as jnp
    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 // 4)
        g = mx.np.array(np.ones((elems,), np.float32))
        out = mx.np.zeros((elems,))
        kv.pushpull(0, g, out=out)            # compile
        float(jnp.sum(out._data))
        t0 = time.perf_counter()
        for _ in range(repeat):
            kv.pushpull(0, g, out=out)
            float(jnp.sum(out._data))         # host fetch: honest barrier
        dt = (time.perf_counter() - t0) / repeat
        if rank == 0:
            print(f"size {mb:8.2f} MB | pushpull {dt*1e3:8.2f} ms | "
                  f"{mb / 1024 / dt:7.2f} GB/s per key")
    kv.barrier()


def measure_compression(sizes_mb, repeat=5):
    """Wire-byte accounting + wall time for the COMPRESSED dist_sync path
    (run under tools/launch.py -n N with ≥2 workers).

    With 2-bit compression the cross-process operand is the PACKED uint8
    code array (collective.py sum_packed), so the wire payload per worker
    is ceil(n/4) bytes vs 4·n for f32 — the printed ratio must be ≈1/16
    (≙ gradient_compression.h's 16× claim, verified on the actual
    transport operand, not on a host-side estimate)."""
    import numpy as np
    from mxnet_tpu.parallel import dist
    dist.initialize()
    import jax
    import mxnet_tpu as mx
    kv = mx.kvstore.create("dist_sync")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kvf = mx.kvstore.create("dist_sync")
    rank, n = kv.rank, kv.num_workers
    import jax.numpy as jnp
    if rank == 0:
        print(f"compressed pushpull path: {n} workers")
    for mb in sizes_mb:
        key = f"g{mb}"       # per-size key: the error-feedback residual
        elems = int(mb * 1024 * 1024 // 4)    # is shaped per key
        raw_bytes = elems * 4
        packed_bytes = (elems + 3) // 4
        g = mx.np.array(np.full((elems,), 0.7, np.float32))
        out = mx.np.zeros((elems,))
        kv.pushpull(key, g, out=out)          # compile
        float(jnp.sum(out._data))
        t0 = time.perf_counter()
        for _ in range(repeat):
            kv.pushpull(key, g, out=out)
            float(jnp.sum(out._data))         # host fetch: honest barrier
        dt2 = (time.perf_counter() - t0) / repeat
        kvf.pushpull(key, g, out=out)
        float(jnp.sum(out._data))
        t0 = time.perf_counter()
        for _ in range(repeat):
            kvf.pushpull(key, g, out=out)
            float(jnp.sum(out._data))
        dtf = (time.perf_counter() - t0) / repeat
        if rank == 0:
            print(f"size {mb:8.2f} MB | wire {packed_bytes:>10d} B vs "
                  f"f32 {raw_bytes:>10d} B (ratio 1/{raw_bytes // packed_bytes})"
                  f" | 2bit {dt2*1e3:8.2f} ms | f32 {dtf*1e3:8.2f} ms")
    kv.barrier()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,4,16,64",
                    help="comma-separated MB sizes")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--kvstore", action="store_true",
                    help="measure the dist KVStore pushpull path "
                         "(run under tools/launch.py -n N)")
    ap.add_argument("--compression", action="store_true",
                    help="measure the 2-bit compressed sync wire vs f32 "
                         "(run under tools/launch.py -n N)")
    args = ap.parse_args(argv)
    sizes = [float(s) for s in args.sizes.split(",")]
    if args.compression:
        measure_compression(sizes, args.repeat)
    elif args.kvstore:
        measure_kvstore(sizes, args.repeat)
    else:
        measure(sizes, args.repeat)
    return 0


if __name__ == "__main__":
    sys.exit(main())
