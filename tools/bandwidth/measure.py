#!/usr/bin/env python
"""Allreduce bandwidth harness (reference: tools/bandwidth/measure.py — the
judged GB/s-per-device metric, README.md:36-72: resnet-200-sized parameter
sets reduced across devices).

TPU-native: gradients allreduce as one jitted XLA `psum` over the device
mesh (ICI on real hardware) instead of KVStore push/pull. Reports the
reference's metric: per-device algorithmic bandwidth
  GB/s = 2 * (n-1)/n * bytes / time / n_devices-normalized
following the standard ring-allreduce accounting the reference README uses
(each device sends+receives 2(n-1)/n of the payload).
"""
import argparse
import os
import sys
import time

import numpy as np

# the package must import regardless of the caller's cwd
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def measure(total_mb=256.0, num_arrays=50, iters=10, devices=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel.collectives import shard_map

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))

    total_bytes = int(total_mb * 1e6)
    per_array = total_bytes // (4 * num_arrays)
    rng = np.random.RandomState(0)
    # per-device distinct shards so the reduce is real work
    shards = [jnp.asarray(rng.uniform(-1, 1, (n, per_array)).astype(np.float32))
              for _ in range(num_arrays)]

    def allreduce(arrs):
        return [jax.lax.psum(a, "dp") for a in arrs]

    fn = jax.jit(shard_map(allreduce, mesh=mesh,
                           in_specs=P("dp", None), out_specs=P("dp", None)))
    out = fn(shards)
    jax.block_until_ready(out)

    tic = time.time()
    for _ in range(iters):
        out = fn(shards)
    jax.block_until_ready(out)
    elapsed = (time.time() - tic) / iters

    payload = 4.0 * per_array * num_arrays
    algo_bytes = 2.0 * (n - 1) / n * payload
    gbps = algo_bytes / elapsed / 1e9
    return {"devices": n, "payload_mb": payload / 1e6,
            "time_ms": elapsed * 1e3, "gb_per_sec_per_device": gbps}


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--total-mb", type=float, default=256.0,
                        help="parameter payload (reference: 258MB resnet-200)")
    parser.add_argument("--num-arrays", type=int, default=50)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--cpu-devices", type=int, default=0,
                        help="test mode: run on N virtual CPU devices")
    args = parser.parse_args()
    if args.cpu_devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=%d"
                                   % args.cpu_devices)
        import jax
        jax.config.update("jax_platforms", "cpu")
    res = measure(args.total_mb, args.num_arrays, args.iters)
    print("devices=%(devices)d payload=%(payload_mb).1fMB "
          "time=%(time_ms).2fms bandwidth=%(gb_per_sec_per_device).3f GB/s"
          % res)
