"""Global PRNG state (reference: mx.random.seed, src/common/random_generator.h).

TPU-native: a single functional JAX PRNG key chain. Eager stochastic ops draw
`next_key()`; traced/jitted programs receive an explicit key input (Executor /
CachedOp thread one in per step) so compiled code stays pure.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key", "current_key", "set_key"]


class _RngState(threading.local):
    def __init__(self):
        # the chain's key is made on first use: building it here would
        # initialise the default backend — take the chip — on `import
        # mxnet_tpu`, in every helper process that merely imports the package
        self._key = None
        self.trace_key = None  # set while tracing a jitted program
        self.trace_consumed = False  # did the current trace draw a key?

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(0)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_STATE = _RngState()


def seed(seed_state, ctx="all"):
    """reference: python/mxnet/random.py seed()."""
    _STATE.key = jax.random.PRNGKey(int(seed_state))


def next_key():
    if _STATE.trace_key is not None:
        _STATE.trace_consumed = True
        _STATE.trace_key, sub = jax.random.split(_STATE.trace_key)
        return sub
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


def reset_trace_consumed():
    """Clear the consumed flag before a trace probe (see trace_consumed)."""
    _STATE.trace_consumed = False


def trace_consumed():
    """True when the trace since reset_trace_consumed() drew a key —
    callers use it to skip per-call key splits for deterministic graphs
    (a split costs ~150us of host dispatch, most of a small forward)."""
    return _STATE.trace_consumed


def current_key():
    return _STATE.key


def set_key(key):
    """Restore the global key chain from raw key data (checkpoint resume:
    `CheckpointManager` saves `np.asarray(current_key())` in the manifest
    and reinstalls it here, so stochastic ops continue the exact sequence
    an uninterrupted run would have drawn)."""
    import jax.numpy as jnp
    _STATE.key = jnp.asarray(key, dtype=jnp.uint32)


_FIXED_KEY = None


def fixed_key():
    """Constant key for DETERMINISTIC jitted graphs (their key argument is
    never consumed). One shared accessor so executor / CachedOp / fused
    step all follow the same policy, and so running a deterministic graph
    never consumes a split from the user-visible global chain."""
    global _FIXED_KEY
    if _FIXED_KEY is None:
        _FIXED_KEY = jax.random.PRNGKey(0)
    return _FIXED_KEY


class trace_key_scope:
    """Context manager installing a traced key while building a jitted program."""

    def __init__(self, key):
        self.key = key
        self.prev = None

    def __enter__(self):
        self.prev = _STATE.trace_key
        _STATE.trace_key = self.key
        return self

    def __exit__(self, *exc):
        _STATE.trace_key = self.prev


_ND_RANDOM_NAMES = ("uniform", "normal", "randn", "gamma", "exponential",
                    "poisson", "randint", "negative_binomial",
                    "generalized_negative_binomial", "multinomial", "shuffle")


def __getattr__(name):
    """Re-export the nd.random distributions (reference random.py does
    `from .ndarray.random import *`; lazy here to avoid the import cycle —
    ndarray.random imports this module for the key chain)."""
    if name in _ND_RANDOM_NAMES:
        from .ndarray import random as _ndr
        return getattr(_ndr, name)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
