"""Shared CPU-mesh environment sanitization.

One definition of "force this (sub)process onto a virtual N-device CPU
mesh" — used by ci/run.py, bench.py's host-side phase children, and
__graft_entry__.dryrun_multichip. Deliberately imports nothing heavy (the
bench parent must never import jax).
"""
import os


def cpu_mesh_env(n_devices=8, base=None):
    """A copy of `base` (default os.environ) forcing JAX onto an
    `n_devices`-device host-platform CPU mesh."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=%d" % n_devices)
    env["XLA_FLAGS"] = " ".join(flags)
    return env
