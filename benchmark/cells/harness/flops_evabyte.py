"""Operations and bytes of the EvaByte decoder (``configs/evabyte_l8.json``),
from the config's own keys alone.

One multiply-add counts as two operations. Recomputed work never counts: a
token's chunk summary is pooled once (by the prefill piece or the decode step
that completes the chunk), and a query is counted against the cache rows the
equations give it (its own window's keys up to itself, one summary a chunk of
every closed window), whatever pages a program walks on top.
"""
import numpy as np


def _z(config):
    c = config
    return (c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"], c["intermediate_size"],
            c["vocab_size"], c["window_size"], c["chunk_size"])


def token_flops_outside_attention_pairs(config):
    """One token through every layer held, without its attention pairs: the
    four projections, the gated MLP, and its share of its chunk's two
    poolings (two dot products with mu and phi, two weighted sums: 8 d)."""
    d, L, _, I, _, _, _ = _z(config)
    return L * (2 * (4 * d * d + 3 * d * I) + 8 * d)


def attention_pair_flops(config):
    """Score and weighted sum of ONE query against ONE cache row (a key and
    a value, or a chunk's two summaries), all heads."""
    return 4 * config["hidden_size"]


def attended_rows(config, t):
    """Cache rows the query at position ``t`` (array or int) attends: its
    window's keys up to itself, and one summary a chunk of every closed
    window."""
    _, _, _, _, _, W, C = _z(config)
    t = np.asarray(t, np.int64)
    return t % W + 1 + (t // W) * (W // C)


def sequence_flops(config, prompt_len, stepped):
    """A request's operations without the head: ``prompt_len`` tokens
    prefilled and ``stepped`` tokens decoded, each against the rows
    `attended_rows` gives its position."""
    n = prompt_len + stepped
    pairs = int(attended_rows(config, np.arange(n)).sum())
    return (n * token_flops_outside_attention_pairs(config)
            + config["num_hidden_layers"] * pairs
            * attention_pair_flops(config))


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def weights_read(config):
    """Parameters a decode step must read: every layer's projections, MLP,
    gains and pooling vectors, the final gain and the head. (The embedding
    is read a row a token: left out.)"""
    d, L, _, I, V, _, _ = _z(config)
    # a layer's two gains and its two pooling vectors: d numbers each
    return L * (4 * d * d + 3 * d * I + 4 * d) + d + d * V


def param_count(config):
    return weights_read(config) + config["vocab_size"] * config["hidden_size"]


def attn_kernel_bytes(config, rows, cache_bytes=2):
    """Bytes the attention kernels of ONE decode step must move for ``rows``
    attended cache rows (window rows and summaries, the active rows
    together, a layer once): a key row and a value row of ``hidden_size``
    in every layer."""
    d, L = config["hidden_size"], config["num_hidden_layers"]
    return rows * 2 * d * cache_bytes * L


def step_hbm_bytes(config, rows, param_bytes=2, cache_bytes=2):
    """Bytes ONE decode step must move: the weights and the cache rows it
    attends (`attn_kernel_bytes`). (What it writes, two rows a sequence and
    at most two more for a completed chunk, is left out: 0.4 % of the
    rest.)"""
    return param_bytes * weights_read(config) \
        + attn_kernel_bytes(config, rows, cache_bytes)
