"""Operations and bytes of the Kimi-Linear decoder
(``configs/kimi_linear_ep8.json``), from the config's own keys alone.

One multiply-add counts as two operations. Recomputed work never counts: the
gated delta rule is counted as its RECURRENCE (a token decays the state,
reads it once against its key, writes one outer product and reads it against
its query: ``7 d_k d_v`` a head), whatever the chunked scan of a prefill
piece computes on top; a prefill chunk's keys and values are expanded once a
token. Routed experts are counted by ASSIGNMENT (the program's counters):
this chip computes only what its own experts are sent. What the latent layers,
an expert's MLP and the head cost is ``harness/flops_moe_mla.py``'s, where it
reads only keys the two configurations share.
"""
from harness.flops_moe_mla import (attention_pair_flops,  # noqa: F401
                                   gated_mlp_flops, head_flops, routed_flops)


def _z(config):
    c, lin = config, config["linear_attn_config"]
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                rkv=c["kv_lora_rank"], dn=c["qk_nope_head_dim"],
                dr=c["qk_rope_head_dim"], dv=c["v_head_dim"],
                Hk=lin["num_heads"], dk=lin["head_dim"],
                taps=lin["short_conv_kernel_size"])


def layer_counts(config):
    """(KDA layers, MLA layers, dense layers, expert layers) held here."""
    lin = config["linear_attn_config"]
    L, k = config["num_hidden_layers"], config["first_k_dense_replace"]
    return (len(lin["kda_layers"]), len(lin["full_attn_layers"]),
            min(k, L), L - min(k, L))


def kda_projection_macs(config):
    """One token through one KDA layer's projections: q, k and v, the two
    low-rank gates (through ``head_dim``), the write strength, the output
    projection and the three convolutions' taps."""
    z = _z(config)
    d, Hd, dk = z["d"], z["Hk"] * z["dk"], z["dk"]
    return (d * 3 * Hd + 2 * (d * dk + dk * Hd) + d * z["Hk"] + Hd * d
            + z["taps"] * 3 * Hd)


def kda_state_flops(config):
    """One token through one KDA layer's recurrence, all heads: the decay
    (one multiply an entry) and three multiply-adds an entry of the state."""
    z = _z(config)
    return 7 * z["Hk"] * z["dk"] * z["dk"]


def mla_projection_macs(config):
    """One token through one MLA layer's projections: the query (direct),
    the latent down-projection, the up-projection of its own latent and the
    output projection."""
    z = _z(config)
    d, H = z["d"], z["H"]
    return (d * H * (z["dn"] + z["dr"]) + d * (z["rkv"] + z["dr"])
            + z["rkv"] * H * (z["dn"] + z["dv"]) + H * z["dv"] * d)


def token_flops_outside_attention_pairs(config):
    """One token through every layer held, without the MLA layers' attention
    pairs and without the routed experts: projections and recurrence, dense
    MLP, routers and shared experts."""
    d = config["hidden_size"]
    kda, mla, dense, expert = layer_counts(config)
    per_expert_layer = 2 * d * config["num_experts"] + gated_mlp_flops(
        d, config["moe_intermediate_size"] * config["num_shared_experts"])
    return (kda * (2 * kda_projection_macs(config) + kda_state_flops(config))
            + mla * 2 * mla_projection_macs(config)
            + dense * gated_mlp_flops(d, config["intermediate_size"])
            + expert * per_expert_layer)


def sequence_flops(config, prompt_len, stepped):
    """A request's operations without routed experts and head: ``prompt_len``
    tokens prefilled (expanded attention, token i sees i + 1 keys) and
    ``stepped`` tokens decoded (absorbed, the i-th sees ``prompt_len + i +
    1``) in the MLA layers; the KDA layers cost a token the same wherever it
    stands."""
    mla = layer_counts(config)[1]
    p, n = prompt_len, stepped
    pairs_prefill = p * (p + 1) // 2
    pairs_step = n * p + n * (n + 1) // 2
    return ((p + n) * token_flops_outside_attention_pairs(config)
            + mla * (pairs_prefill * attention_pair_flops(config, False)
                     + pairs_step * attention_pair_flops(config, True)))


def weights_outside_routed(config):
    """Parameters a decode step must read whatever the routing: every
    layer's mixer and norms, the dense MLP, routers and shared experts, the
    final norm and the head over the slice. (The embedding is read a row a
    token: left out.)"""
    z = _z(config)
    d, Hd = z["d"], z["Hk"] * z["dk"]
    kda, mla, dense, expert = layer_counts(config)
    # beside the matrices: A_log, dt_bias, the output norm's gain
    kda_w = kda_projection_macs(config) + z["Hk"] + Hd + z["dk"]
    mla_w = mla_projection_macs(config) + z["rkv"]
    f = config["moe_intermediate_size"]
    return (kda * kda_w + mla * mla_w + (kda + mla) * 2 * d
            + dense * 3 * d * config["intermediate_size"]
            + expert * (d * config["num_experts"]
                        + 3 * d * f * config["num_shared_experts"])
            + d + d * config["vocab_size"])


def kda_state_row_bytes(config, state_bytes=4):
    """One row's recurrent state in one KDA layer, all heads."""
    z = _z(config)
    return z["Hk"] * z["dk"] * z["dk"] * state_bytes


def kda_kernel_bytes(config, rows_updated, state_bytes=4):
    """Bytes the state-update kernel must move for ``rows_updated`` (row,
    KDA layer) updates: each row's state once in and once out. (The few
    vectors a row brings, 0.1 MB beside 4.2, are left out.)"""
    return rows_updated * 2 * kda_state_row_bytes(config, state_bytes)


def kda_step_bytes(config, rows_updated, state_bytes=4, tail_bytes=2):
    """Bytes of recurrent state ONE decode step must move for
    ``rows_updated`` (row, KDA layer) updates: the state once in and once
    out, and the convolution's tail (``taps - 1`` rows of ``3 H d_k``) once
    in and once out."""
    z = _z(config)
    tail = (z["taps"] - 1) * 3 * z["Hk"] * z["dk"] * tail_bytes
    return kda_kernel_bytes(config, rows_updated, state_bytes) \
        + rows_updated * 2 * tail


def step_hbm_bytes(config, experts_touched, live_tokens, rows_updated,
                   param_bytes=2, cache_bytes=2, state_bytes=4):
    """Bytes ONE decode step must move: the weights outside the routed
    experts, each held expert that was sent a token (``experts_touched``,
    summed over the expert layers), the live latent rows of every MLA layer
    (``live_tokens``: cached tokens over the active rows; a row is
    ``kv_lora_rank + qk_rope_head_dim`` numbers, padding is not needed) and
    each updated row's recurrent state (`kda_step_bytes`)."""
    d = config["hidden_size"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return (param_bytes * (weights_outside_routed(config)
                           + experts_touched * 3 * d
                           * config["moe_intermediate_size"])
            + cache_bytes * live_tokens * layer_counts(config)[1] * row
            + kda_step_bytes(config, rows_updated, state_bytes, cache_bytes))
