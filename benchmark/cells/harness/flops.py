"""Operations and bytes an algorithm needs, from its shapes alone.

One multiply-add counts as two operations. Recomputed work never counts.
"""


def conv_flops(n, c_in, c_out, kernel, out_hw):
    """Forward pass of a dense 2-d convolution over ``n`` images."""
    kh, kw = kernel
    oh, ow = out_hw
    return 2 * n * c_out * oh * ow * c_in * kh * kw


def dense_flops(n, d_in, d_out):
    """Forward pass of y = x W over ``n`` rows."""
    return 2 * n * d_in * d_out


def attention_flops(q_len, kv_len, heads, head_dim, causal_offset=None):
    """QK^T and PV of one attention call. With ``causal_offset`` (the global
    position of the first query row) only keys at or before each query count:
    query i sees ``min(kv_len, causal_offset + i + 1)`` keys."""
    if causal_offset is None:
        pairs = q_len * kv_len
    else:
        pairs = sum(min(kv_len, causal_offset + i + 1) for i in range(q_len))
    return 4 * pairs * heads * head_dim


def train_flops_per_sample(layers):
    """Forward + backward of a network given as its matrix layers
    (``{"kind": "conv"|"dense", ...}`` for ONE sample): the backward pass
    costs two forward passes (input and weight gradients)."""
    fwd = 0
    for l in layers:
        if l["kind"] == "conv":
            fwd += conv_flops(1, l["c_in"], l["c_out"], l["kernel"],
                              l["out_hw"])
        elif l["kind"] == "dense":
            fwd += dense_flops(1, l["d_in"], l["d_out"])
        else:
            raise ValueError("unknown layer kind %r" % l["kind"])
    return 3 * fwd


def decoder_flops_per_token(num_layers, d_model, d_ff, context):
    """One token through a pre-LN decoder stack with ``context`` keys live
    (its own included): projections, MLP and attention. The logits are
    counted apart (``logits_flops``), only where a token is sampled."""
    proj = dense_flops(1, d_model, d_model) * 4
    mlp = dense_flops(1, d_model, d_ff) * 2
    attn = 4 * context * d_model
    return num_layers * (proj + mlp + attn)


def logits_flops(d_model, vocab):
    return dense_flops(1, d_model, vocab)
