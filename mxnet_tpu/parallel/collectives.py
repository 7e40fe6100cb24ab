"""Cross-host collectives (reference role: ps-lite ZeroMQ push/pull + NCCL,
src/kvstore/kvstore_dist.h:44).

TPU-native: the cross-worker gradient sum is ONE XLA program spanning every
device of every process — XLA lowers the sum to an AllReduce riding ICI
(same pod) or DCN (across pods). No parameter server, no host staging.
Single-host it degrades to the identity.

`ensure_distributed()` wires a process into the JAX coordination service from
the env the launcher sets (tools/launch.py: JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID) — the analog of ps-lite's scheduler
rendezvous (reference: kvstore_dist.h Customer startup).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as _np

_DIST_INITIALIZED = False


# manual SPMD entry point of the installed JAX, re-exported so the parallel
# stack (ring attention, pipeline, kernel islands) names it in one place
shard_map = jax.shard_map


def ensure_distributed():
    """Initialize jax.distributed once from the launcher env. No-op when the
    env names a single process (or none)."""
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return
    n = int(os.environ.get("JAX_NUM_PROCESSES", "1") or "1")
    if n <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=n,
        process_id=int(os.environ["JAX_PROCESS_ID"]))
    _DIST_INITIALIZED = True


_REDUCE_CACHE = {}


def _reduce_fn():
    """One jitted reduce program per process (cached — a fresh lambda per
    call would retrace/recompile on every gradient push)."""
    if "fn" not in _REDUCE_CACHE:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(_np.asarray(jax.devices()), ("w",))  # tpulint: allow-host-sync device handle list, not a device array
        L = len(jax.local_devices())
        _REDUCE_CACHE["mesh"] = mesh
        _REDUCE_CACHE["in_sharding"] = NamedSharding(mesh, P("w"))
        _REDUCE_CACHE["fn"] = jax.jit(
            lambda x: x.sum(axis=0) / L,
            out_shardings=NamedSharding(mesh, P()))
    return _REDUCE_CACHE["fn"], _REDUCE_CACHE["in_sharding"]


def allreduce_hosts(value):
    """Sum `value` across all JAX processes IN-GRAPH: the per-process value
    becomes one shard of a global array over a 'w' mesh axis and a jitted
    sum makes XLA emit the AllReduce (ICI/DCN). Single-process: identity."""
    if jax.process_count() == 1:
        return value
    v = jnp.asarray(value)
    local = jax.local_devices()
    fn, in_sharding = _reduce_fn()
    # every local device carries this process's value; the global sum
    # overcounts by len(local), divided out inside the program
    shards = [jax.device_put(v[None], d) for d in local]
    garr = jax.make_array_from_single_device_arrays(
        (len(jax.devices()),) + v.shape, in_sharding, shards)
    return fn(garr).addressable_data(0)


def host_barrier():
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("mxnet_tpu_kvstore_barrier")
