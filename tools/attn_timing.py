"""Shared flash-attention timing methodology (bench.py + flash_tune.py).

One place defines how attention throughput is measured so the tuner's
block-size choice and the bench's reported TFLOP/s can never drift apart:

* distinct q per iteration — no iteration can reuse another's result;
* ALL iterations inside ONE jitted `lax.map` dispatch — per-dispatch host
  latency otherwise dominates the timing and caps the apparent TFLOP/s
  far below the kernel's real throughput;
* causal flops = 2 matmuls x 2 flops x B*H*S^2*D, halved by causality.
"""
import time

import numpy as np


def causal_flops(B, H, S, D, n_iter=1):
    return 2 * 2 * B * H * S * S * D * 0.5 * n_iter


def ideal_hbm_bytes(B, H, S, D, itemsize=2):
    """Roofline HBM floor of one attention forward: Q+K+V read + O write
    (bf16 by default). Shared by bench's flash and cost phases so the
    roofline gate and the reported ideal-bytes figure can't drift."""
    return 4 * B * H * S * D * itemsize


def make_inputs(B, H, S, D, n_iter, dtype, seed=0):
    """(qs [n_iter,B,H,S,D], k, v) staged on device in `dtype`.

    qs is filled per-iteration into a preallocated float32 buffer — one
    big rng.normal draw would transiently hold n_iter x the array in
    float64 (~2 GB at the TPU defaults)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    qs_host = np.empty((n_iter, B, H, S, D), np.float32)
    for i in range(n_iter):
        qs_host[i] = rng.normal(0, 1, (B, H, S, D)).astype(np.float32)
    qs = jnp.asarray(qs_host, dtype=dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, H, S, D)).astype(np.float32), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, H, S, D)).astype(np.float32), dtype)
    return qs, k, v


def timed_map_tflops(per_q_fn, qs, k, v, flops_total):
    """Compile + warm `lax.map(per_q_fn, qs)` as ONE dispatch, return
    (tflops, seconds_per_iter)."""
    import jax

    fn = jax.jit(lambda qs, k, v: jax.lax.map(
        lambda q: per_q_fn(q, k, v), qs))
    jax.block_until_ready([fn(qs, k, v), qs])  # compile + stage
    tic = time.time()
    jax.block_until_ready(fn(qs, k, v))
    dt = time.time() - tic
    return flops_total / dt / 1e12, dt / qs.shape[0]
