"""Operations and bytes of the Motif-3 decoder (``configs/motif3_ep8.json``),
from the config's own keys alone.

One multiply-add counts as two operations. Recomputed work never counts: a
prefill piece expands keys and values a head over its whole span and a window
layer's query block scores twice the window, the count holds each query's
own pairs once. The mHC mixings count their one projection and their three
mixes; Sinkhorn, norms and PolyNorm's elementwise work are left out. Routed
experts are counted by ASSIGNMENT (the program's counters): this chip
computes only what its own experts are sent.
"""


def _z(config):
    c = config
    H, G = c["num_attention_heads"], c["num_key_value_heads"]
    return dict(d=c["hidden_size"], H=H, G=G, S=H // G - 1,
                rq=c["q_lora_rank"], rkv=c["kv_lora_rank"],
                dr=c["qk_rope_head_dim"],
                dn=c["head_dim"] - c["qk_rope_head_dim"],
                dv=c["v_head_dim"], n=c["mhc_expansion_rate"],
                W=c["sliding_window"])


def _kinds(config):
    """(window?, dense?) of each layer held, by its published index."""
    kept = config.get("layers_kept") or list(
        range(config["num_hidden_layers"]))
    return [(i >= config["max_window_layers"]
             and (i + 1) % config["sliding_window_period"] != 0,
             i < config["n_dense_first_layers"]) for i in kept]


def layer_counts(config):
    """(dense layers, expert layers, full layers, window layers) held
    here."""
    k = _kinds(config)
    dense = sum(d for _, d in k)
    window = sum(w for w, _ in k)
    return dense, len(k) - dense, len(k) - window, window


def gdla_projection_macs(config):
    """One token through one layer's attention projections: the two query
    projections, the latent down-projection, the up-projections of its own
    latent (a key and a value a group when expanded, onto its queries and
    its outputs when absorbed: the same count), lambda, the output gate and
    the output projection."""
    z = _z(config)
    d, H, G, S, dv = z["d"], z["H"], z["G"], z["S"], z["dv"]
    return (d * z["rq"] + z["rq"] * H * (z["dn"] + z["dr"])
            + d * (z["rkv"] + z["dr"]) + z["rkv"] * G * (z["dn"] + dv)
            + d * G * S + 2 * d * G * S * dv)


def mhc_flops(config):
    """One token through one sub-layer's mHC: the projection ``[n d] x [n
    d, 2 n + n^2]``, the pre-mix, the residual mix and the post-mix."""
    z = _z(config)
    n, d = z["n"], z["d"]
    return 2 * n * d * (2 * n + n * n) + 2 * (n * d + n * n * d + n * d)


def attention_pair_flops(config, absorbed):
    """Scores and weighted sum of ONE query against ONE cached token, all
    heads: over the latent row when absorbed, over the expanded head widths
    otherwise."""
    z = _z(config)
    if absorbed:
        return 2 * z["H"] * ((z["rkv"] + z["dr"]) + z["rkv"])
    return 2 * z["H"] * ((z["dn"] + z["dr"]) + z["dv"])


def gated_mlp_flops(d, width):
    return 2 * 3 * d * width


def token_flops_outside_attention_pairs(config):
    """One token through every layer held, without the attention pairs and
    without the routed experts: projections, the mHC of both sub-layers,
    dense MLPs, routers and shared experts."""
    d = config["hidden_size"]
    dense, expert, _, _ = layer_counts(config)
    per_expert_layer = 2 * d * config["num_experts"] + gated_mlp_flops(
        d, config["moe_intermediate_size"] * config["num_shared_experts"])
    return ((dense + expert) * (2 * gdla_projection_macs(config)
                                + 2 * mhc_flops(config))
            + dense * gated_mlp_flops(d, config["intermediate_size"])
            + expert * per_expert_layer)


def routed_flops(config, assignments):
    """``assignments`` (token, held expert) pairs through an expert's MLP."""
    return assignments * gated_mlp_flops(config["hidden_size"],
                                         config["moe_intermediate_size"])


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def _pairs(first, last, cap=None):
    """Keys the queries at positions ``first .. last - 1`` see: ``p + 1``
    each, or ``min(p + 1, cap)``."""
    if last <= first:
        return 0
    whole = (last * (last + 1) - first * (first + 1)) // 2
    if cap is None or last <= cap:
        return whole
    lo = max(first, cap)                # positions that see ``cap`` keys
    return (_pairs(first, lo) if lo > first else 0) + (last - lo) * cap


def sequence_flops(config, prompt_len, stepped):
    """A request's operations without routed experts and head:
    ``prompt_len`` tokens prefilled (expanded attention) and ``stepped``
    tokens decoded (absorbed); position ``p`` sees ``p + 1`` keys in a full
    layer and ``min(p + 1, sliding_window)`` in a window layer."""
    _, _, full, window = layer_counts(config)
    W = config["sliding_window"]
    p, n = prompt_len, stepped
    return ((p + n) * token_flops_outside_attention_pairs(config)
            + (full * _pairs(0, p) + window * _pairs(0, p, W))
            * attention_pair_flops(config, False)
            + (full * _pairs(p, p + n) + window * _pairs(p, p + n, W))
            * attention_pair_flops(config, True))


def weights_outside_routed(config):
    """Parameters a decode step must read whatever the routing: every
    layer's attention, mHC and norms, the dense MLP, routers and shared
    experts (with their PolyNorm coefficients), the final norm and the head
    over the slice. (The embedding is read a row a token: left out.)"""
    z = _z(config)
    d, n = z["d"], z["n"]
    dense, expert, _, _ = layer_counts(config)
    mhc = 2 * (n * d * (2 * n + n * n) + 3 + 2 * n + n * n)
    attn = gdla_projection_macs(config) + 2 * d + z["rq"] + z["rkv"]
    f = config["moe_intermediate_size"]
    return ((dense + expert) * (attn + mhc)
            + dense * (3 * d * config["intermediate_size"] + 4)
            + expert * (d * config["num_experts"]
                        + 3 * d * f * config["num_shared_experts"] + 4)
            + d + d * config["vocab_size"])


def latent_row_bytes(config, cache_bytes=2):
    """One latent row ``[c | k_rope]`` as the kernels must read it (the
    pool's lane padding is not needed)."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * cache_bytes


def attn_kernel_bytes(config, full_rows, window_rows, cache_bytes=2):
    """Bytes the attention kernels of one step must move: every latent row
    the full layers' walks and the window layers' rings read (the program's
    counters ``gdla_full_rows`` and ``gdla_window_rows``, all layers)."""
    return (full_rows + window_rows) * latent_row_bytes(config, cache_bytes)


def expert_step_bytes(config, experts_touched, param_bytes=2):
    """Bytes the grouped expert kernels of one step must move: each held
    expert that was sent a token (``experts_touched``, summed over the
    expert layers), its three matrices once."""
    return experts_touched * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"] * param_bytes


def stream_bytes(config, rows):
    """The mHC streams of ``rows`` tokens through every sub-layer: ``f32[n,
    d]`` a token read and written twice a layer."""
    z = _z(config)
    return rows * 2 * 2 * config["num_hidden_layers"] * z["n"] * z["d"] * 4


def step_hbm_bytes(config, experts_touched, full_rows, window_rows, rows,
                   param_bytes=2, cache_bytes=2):
    """Bytes ONE decode step must move: the weights outside the routed
    experts, each held expert that was sent a token, the latent rows the
    attention kernels read (`attn_kernel_bytes`) and the streams of the
    ``rows`` active tokens (`stream_bytes`)."""
    return (param_bytes * weights_outside_routed(config)
            + expert_step_bytes(config, experts_touched, param_bytes)
            + attn_kernel_bytes(config, full_rows, window_rows, cache_bytes)
            + stream_bytes(config, rows))
