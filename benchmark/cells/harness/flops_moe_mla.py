"""Operations and bytes of the latent-attention expert decoder
(``configs/pangu_umoe_ep16.json``), from the config's own keys alone.

One multiply-add counts as two operations. Recomputed work never counts: a
prefill chunk expands keys and values of the whole table again, the count
holds each token's expansion once. Routed experts are counted by ASSIGNMENT
(the program's counters), not by token: this chip computes only what its own
experts are sent.
"""


def _z(config):
    c = config
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"])


def layer_counts(config):
    """(dense layers, expert layers) held here."""
    L, k = config["num_hidden_layers"], config["first_k_dense_replace"]
    return min(k, L), L - min(k, L)


def mla_projection_flops(config):
    """One token through one layer's attention projections: the two query
    projections, the latent down-projection, the up-projection of its own
    latent (to a key and a value when expanded, onto its query and its
    output when absorbed: the same count) and the output projection."""
    d, H, rq, rkv, dn, dr, dv = _z(config)
    return 2 * (d * rq + rq * H * (dn + dr) + d * (rkv + dr)
                + rkv * H * (dn + dv) + H * dv * d)


def attention_pair_flops(config, absorbed):
    """Scores and weighted sum of ONE query against ONE cached token, all
    heads: over the latent row when absorbed, over the expanded head widths
    otherwise."""
    d, H, rq, rkv, dn, dr, dv = _z(config)
    if absorbed:
        return 2 * H * ((rkv + dr) + rkv)
    return 2 * H * ((dn + dr) + dv)


def gated_mlp_flops(d, width):
    return 2 * 3 * d * width


def token_flops_outside_attention_pairs(config):
    """One token through every layer held, without the attention pairs and
    without the routed experts: projections, dense MLPs, routers and shared
    experts."""
    d = config["hidden_size"]
    dense, expert = layer_counts(config)
    per_expert_layer = 2 * d * config["n_routed_experts"] + gated_mlp_flops(
        d, config["moe_intermediate_size"] * config["n_shared_experts"])
    return ((dense + expert) * mla_projection_flops(config)
            + dense * gated_mlp_flops(d, config["intermediate_size"])
            + expert * per_expert_layer)


def routed_flops(config, assignments):
    """``assignments`` (token, held expert) pairs through an expert's MLP."""
    return assignments * gated_mlp_flops(config["hidden_size"],
                                         config["moe_intermediate_size"])


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def sequence_flops(config, prompt_len, stepped):
    """A request's operations without routed experts and head: ``prompt_len``
    tokens prefilled (expanded attention, token i sees i + 1 keys) and
    ``stepped`` tokens decoded (absorbed, the i-th sees ``prompt_len + i +
    1``), in every layer held."""
    layers = config["num_hidden_layers"]
    p, n = prompt_len, stepped
    pairs_prefill = p * (p + 1) // 2
    pairs_step = n * p + n * (n + 1) // 2
    return ((p + n) * token_flops_outside_attention_pairs(config)
            + layers * (pairs_prefill * attention_pair_flops(config, False)
                        + pairs_step * attention_pair_flops(config, True)))


def weights_outside_routed(config):
    """Parameters a decode step must read whatever the routing: every
    layer's attention, norms, dense MLP, router and shared expert, the final
    norm and the head over the slice. (The embedding is read a row a token:
    left out.)"""
    d, H, rq, rkv, dn, dr, dv = _z(config)
    dense, expert = layer_counts(config)
    mla = (d * rq + rq * H * (dn + dr) + d * (rkv + dr)
           + rkv * H * (dn + dv) + H * dv * d + 4 * d + rq + rkv)
    f = config["moe_intermediate_size"]
    return ((dense + expert) * mla
            + dense * 3 * d * config["intermediate_size"]
            + expert * (d * config["n_routed_experts"]
                        + 3 * d * f * config["n_shared_experts"])
            + d + d * config["vocab_size"])


def step_hbm_bytes(config, experts_touched, live_tokens, param_bytes=2,
                   cache_bytes=2):
    """Bytes ONE decode step must move: the weights outside the routed
    experts, each held expert that was sent a token (``experts_touched``,
    summed over the expert layers), and the live latent rows of every layer
    (``live_tokens``: cached tokens over the active rows; a row is
    ``kv_lora_rank + qk_rope_head_dim`` numbers, padding is not needed)."""
    d = config["hidden_size"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return (param_bytes * (weights_outside_routed(config)
                           + experts_touched * 3 * d
                           * config["moe_intermediate_size"])
            + cache_bytes * live_tokens * config["num_hidden_layers"] * row)
