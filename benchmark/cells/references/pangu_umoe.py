"""Plain reference: the openPangu-Ultra-MoE decoder (config.json of
FreedomIntelligence/openPangu-Ultra-MoE-718B) as one full causal forward pass
in ``jax.numpy``: no cache, no kernels, no absorbed attention, no grouped
products. float32 with matmul precision ``highest``; the control
(``float8_e4m3``) is the same forward with every matrix product's two
operands rounded to e4m3, per tensor scaled.

The layer equations (sizes by the config's own keys)::

    RMS(x; g)  = x / sqrt(mean(x^2) + rms_norm_eps) * g
    block      : h = x + RMS(Attn(RMS(x; g1)); g2)
                 y = h + RMS(FFN(RMS(h; g3)); g4)            (sandwich norm)
    MLA        : c_q = RMS(x W_qa; g_q);  [q_nope | q_rope]_h = c_q W_qb
                 [c_kv | k_r] = x W_kva;  c = RMS(c_kv; g_kv)
                 k_rope = RoPE(k_r, pos)                     (one for all heads)
                 [k_nope | v]_h = c W_kvb
                 score_h(i,j) = (q_nope_h(i) . k_nope_h(j)
                                 + RoPE(q_rope_h(i), i) . k_rope(j))
                                / sqrt(qk_nope_head_dim + qk_rope_head_dim)
                 causal softmax, o_h = sum_j p_h(i,j) v_h(j), concat_h(o_h) W_o
    gated MLP  : (silu(x W_gate) * (x W_up)) W_down
    experts    : sigma = sigmoid(x W_r); T = top-k(sigma)
                 w_e = routed_scaling_factor * sigma_e / (sum_{T} sigma + 1e-20)
                 out = Shared(x) + sum_{e in T, e held here} w_e Expert_e(x)
    head       : logits = RMS(x_L; g_f) W_head               (untied)

FFN is the gated MLP in the first ``first_k_dense_replace`` layers and the
expert layer after them.

Departures from the published model, each one stated:

* **The chip's share** (``experts_held``: ``first``, ``count``): only the
  held experts' weights exist; the router keeps its published width and its
  experts per token, the weights ``w_e`` are normalised over all the chosen
  experts, and what the absent experts would add is LEFT OUT, here as in the
  program; that partial result goes on to the next layer.
* **A sliced vocabulary**: ``vocab_size`` rows of embedding and head.
* **No multi-token-prediction module** (``num_nextn_predict_layers`` 0): it
  is the layer after the last and lies beyond the depth cut.
* **Assumed, not in the published config**: the router scores with a sigmoid
  and has no group limit and no bias (the family's convention for
  ``norm_topk_prob`` with a ``routed_scaling_factor``); the rotary pairing
  is the half-split one (with seeded weights the other pairing is a
  permutation of columns); no rotary scaling (the config has no such key);
  weights are seeded normal(0, ``initializer_range``), norm gains 1.
* Every held expert is applied densely to every token and masked by the
  routing; attention is computed a group of heads at a time. Neither changes
  a number.

It imports nothing of the program and takes nothing the program made. It makes
its own weights from a key, STORED in the dtype the configuration states
(``param_dtype``, bfloat16) and handed to the program as they are; the
forward upcasts one layer's leaves (inside an expert layer, one expert's) at a
time, so that it fits beside the stored weights: call it outside ``jax.jit``
and each layer is a program of its own.
"""
import functools

import numpy as np

HEAD_GROUP = 8          # heads whose score matrices are live together


def sizes(config):
    c = config
    return dict(
        d=c["hidden_size"], H=c["num_attention_heads"], rq=c["q_lora_rank"],
        rkv=c["kv_lora_rank"], dn=c["qk_nope_head_dim"],
        dr=c["qk_rope_head_dim"], dv=c["v_head_dim"],
        inner=c["intermediate_size"], f=c["moe_intermediate_size"],
        E=c["n_routed_experts"], k=c["num_experts_per_tok"],
        shared=c["n_shared_experts"], V=c["vocab_size"],
        L=c["num_hidden_layers"], dense=c["first_k_dense_replace"],
        held=int(c["experts_held"]["count"]),
        first=int(c["experts_held"]["first"]))


def layer_shapes(config, dense):
    z = sizes(config)
    d, H = z["d"], z["H"]
    out = {
        "norm_attn_in": (d,), "norm_attn_out": (d,),
        "norm_ffn_in": (d,), "norm_ffn_out": (d,),
        "wq_a": (d, z["rq"]), "norm_q": (z["rq"],),
        "wq_b": (z["rq"], H * (z["dn"] + z["dr"])),
        "wkv_a": (d, z["rkv"] + z["dr"]), "norm_kv": (z["rkv"],),
        "wkv_b": (z["rkv"], H * (z["dn"] + z["dv"])),
        "wo": (H * z["dv"], d),
    }
    if dense:
        out.update({"w_gate": (d, z["inner"]), "w_up": (d, z["inner"]),
                    "w_down": (z["inner"], d)})
    else:
        fs = z["f"] * z["shared"]
        out.update({
            "router": (d, z["E"]),
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d),
            "experts_gate": (z["held"], d, z["f"]),
            "experts_up": (z["held"], d, z["f"]),
            "experts_down": (z["held"], z["f"], d)})
    return out


def param_shapes(config):
    """``{"embed", "head", "norm_f", "layers": [one dict a layer]}``: the
    layers are separate leaves, so that one is upcast at a time."""
    z = sizes(config)
    return {"embed": (z["V"], z["d"]), "head": (z["d"], z["V"]),
            "norm_f": (z["d"],),
            "layers": [layer_shapes(config, l < z["dense"])
                       for l in range(z["L"])]}


def param_count(config):
    import jax
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(config), is_leaf=lambda s: isinstance(s, tuple)))


def init_params(config, key):
    """Seeded weights on the device in one jitted call, every leaf made in
    ``param_dtype`` directly (no float32 copy): normal(0,
    ``initializer_range``) for matrices and embeddings, norm gains 1."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(config)
    dt = jnp.dtype(config.get("param_dtype", "bfloat16"))
    std = float(config["initializer_range"])
    is_shape = lambda s: isinstance(s, tuple)           # noqa: E731
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                        is_leaf=is_shape)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            if str(path[-1].key).startswith("norm_"):
                out.append(jnp.ones(shape, dt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dt))
        return jax.tree_util.tree_unflatten(tree, out)

    return make(key)


# ---------------------------------------------------------------------------
def _mm(a, b, mode):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``, float32
    out. ``float32``: both operands upcast, precision ``highest``.
    ``float8_e4m3``: each operand scaled by its largest magnitude to the
    format's range, rounded to e4m3, multiplied and accumulated in float32."""
    import jax
    import jax.numpy as jnp
    if mode == "float32":
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode != "float8_e4m3":
        raise ValueError("unknown reference precision %r" % (mode,))
    f8 = jnp.float8_e4m3fn
    top = float(jnp.finfo(f8).max)

    def q(x):
        x = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        # an e4m3 value is exact in bfloat16: the product sees e4m3 operands
        return (x / s).astype(f8).astype(jnp.bfloat16), s

    qa, sa = q(a)
    qb, sb = q(b)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * (sa * sb)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Half-split rotary over the last axis of ``x`` (``[..., S, dr]`` with
    ``pos`` ``[S]``): pairs are (i, i + dr/2)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _mlp(x, wg, wu, wd, mode):
    return _mm(_silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def _attention(config, lp, x, mode):
    """``x`` ``[S, d]`` (already normed) -> ``[S, d]``; one sequence."""
    import jax
    import jax.numpy as jnp
    z = sizes(config)
    H, dn, dr, dv = z["H"], z["dn"], z["dr"], z["dv"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    S = x.shape[0]
    pos = jnp.arange(S)
    c_q = _rms(_mm(x, lp["wq_a"], mode), lp["norm_q"], eps)
    q = _mm(c_q, lp["wq_b"], mode).reshape(S, H, dn + dr).transpose(1, 0, 2)
    kv = _mm(x, lp["wkv_a"], mode)
    c = _rms(kv[:, :z["rkv"]], lp["norm_kv"], eps)
    k_rope = _rope(kv[:, z["rkv"]:], pos, theta)                # [S, dr]
    kvb = _mm(c, lp["wkv_b"], mode).reshape(S, H, dn + dv).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta)], -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope[None], (H, S, dr))], -1)
    v = kvb[..., dn:]
    mask = jnp.tril(jnp.ones((S, S), bool))
    scale = 1.0 / np.sqrt(dn + dr)

    def heads(qkv):
        qh, kh, vh = qkv                                        # [g, S, .]
        if mode == "float32":
            s = jnp.einsum("hqd,hkd->hqk", qh, kh,
                           precision=jax.lax.Precision.HIGHEST)
        else:
            s = jax.vmap(lambda a, b: _mm(a, b.T, mode))(qh, kh)
        p = jax.nn.softmax(jnp.where(mask, s * scale, -jnp.inf), axis=-1)
        if mode == "float32":
            return jnp.einsum("hqk,hkd->hqd", p, vh,
                              precision=jax.lax.Precision.HIGHEST)
        return jax.vmap(lambda a, b: _mm(a, b, mode))(p, vh)

    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    split = lambda t: t.reshape(H // g, g, S, t.shape[-1])      # noqa: E731
    o = jax.lax.map(heads, (split(q), split(k), split(v)))
    o = o.reshape(H, S, dv).transpose(1, 0, 2).reshape(S, H * dv)
    return _mm(o, lp["wo"], mode)


def routing(config, x, router):
    """``(chosen [S, k] expert ids, weights [S, k])`` over ALL experts; the
    scores are float32 whatever ``mode`` the rest runs in."""
    import jax
    import jax.numpy as jnp
    sigma = jax.nn.sigmoid(_mm(x, router, "float32"))
    top_s, top_e = jax.lax.top_k(sigma, config["num_experts_per_tok"])
    w = config["routed_scaling_factor"] * top_s \
        / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_e, w


def routed_part(config, lp, x, mode, first=None):
    """What the held experts add: every held expert applied to every token,
    masked by the routing. ``first``: the id of the first held expert
    (default ``experts_held.first``)."""
    import jax
    import jax.numpy as jnp
    z = sizes(config)
    first = z["first"] if first is None else first
    top_e, w = routing(config, x, lp["router"])

    def one(acc, ew):
        e, wg, wu, wd = ew
        w_e = jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _mlp(x, wg, wu, wd, mode), None

    n = lp["experts_gate"].shape[0]
    acc, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(n), lp["experts_gate"], lp["experts_up"],
         lp["experts_down"]))
    return acc


def expert_layer(config, lp, x, mode):
    return _mlp(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
                mode) + routed_part(config, lp, x, mode)


def _layer(config, mode, lp, x):
    """One block over ``x`` ``[B, S, d]`` float32."""
    import jax
    eps = config["rms_norm_eps"]
    ffn = (lambda h: _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"], mode)) \
        if "w_gate" in lp else (lambda h: expert_layer(config, lp, h, mode))

    def one(x):
        a = _attention(config, lp, _rms(x, lp["norm_attn_in"], eps), mode)
        h = x + _rms(a, lp["norm_attn_out"], eps)
        return h + _rms(ffn(_rms(h, lp["norm_ffn_in"], eps)),
                        lp["norm_ffn_out"], eps)

    return jax.lax.map(one, x)


@functools.lru_cache(maxsize=None)
def _jitted(fn, config_key, mode):
    import json
    import jax
    return jax.jit(functools.partial(fn, json.loads(config_key), mode))


def _head(config, mode, norm_f, head, x, positions):
    import jax.numpy as jnp
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _mm(_rms(x, norm_f, config["rms_norm_eps"]), head, mode)


def logits_at(config, params, tokens, positions, dtype="float32"):
    """Logits ``[B, K, vocab]`` of a full causal forward over ``tokens``
    ``[B, S]`` at ``positions`` ``[B, K]``, the matrix products in
    ``dtype`` (``float32`` | ``float8_e4m3``). Each layer is one jitted
    program (the four expert layers share theirs)."""
    import json
    import jax.numpy as jnp
    ck = json.dumps(config, sort_keys=True)
    x = params["embed"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = _jitted(_layer, ck, dtype)(lp, x)
    return _jitted(_head, ck, dtype)(params["norm_f"], params["head"], x,
                                     positions)


def served_gaps(config, params, tokens, positions, served, valid,
                yardstick_dtype):
    """As ``references/gpt2.py``: for each served position two gaps, each
    measured on the float32 reference's logits below the reference's best
    there: that of the token that was SERVED, and that of the token the same
    forward with ``yardstick_dtype`` matrix operands puts first. Invalid
    (padding) slots read 0. Two device arrays ``[B, K]``."""
    import jax.numpy as jnp
    ref = logits_at(config, params, tokens, positions)
    low = logits_at(config, params, tokens, positions, yardstick_dtype)
    best = jnp.max(ref, axis=-1)

    def gap(tok):
        got = jnp.take_along_axis(ref, tok[:, :, None], axis=-1)[..., 0]
        return jnp.where(valid, best - got, 0.0)

    return gap(served), gap(jnp.argmax(low, axis=-1))
