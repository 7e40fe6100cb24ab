"""Plain reference: the Motif-3 decoder (config.json of
Motif-Technologies/Motif-3-Beta) as one full causal forward pass in
``jax.numpy``: no cache, no kernels, no ring, no absorbed attention, no
grouped products. float32 with matmul precision ``highest``; the control
(``float8_e4m3``) is the same forward with every weight product's two
operands rounded to e4m3, per tensor scaled.

The layer equations (sizes by the config's own keys; ``n =
mhc_expansion_rate``, ``G = num_key_value_heads``, ``S = H / G - 1`` signal
heads a group, ``dn = head_dim - qk_rope_head_dim``)::

    RMS(x; g)  = x / sqrt(mean(x^2) + rms_norm_eps) * g     (g = 1: gainless)
    stream     : x in R^{n x d}; x_0 = the embedding in each of the n rows
    sub-layer  : x~ = RMS(vec x);  [p_pre | p_post | p_res] = x~ Phi
                 H_pre  = sigmoid(a_1 p_pre + b_pre)
                 H_post = 2 sigmoid(a_2 p_post + b_post)
                 H_res  = SK(exp(a_3 mat(p_res) + b_res))
                 SK: rows then columns normalised, mhc_sinkhorn_iters times
                 x <- clamp(H_res x + H_post F(RMS(H_pre x; g)),
                            +-hidden_clamp)
    layer      : the sub-layer with F = GDLA, then with F = FFN
    GDLA       : c_q = RMS(u W_DQ; g_q);  [q_nope | q_rope]_h = c_q W_UQ,h
                 [c_kv | k_r] = u W_DKV;  c = RMS(c_kv; g_kv)
                 k_g = [c W_UK,g | RoPE(k_r)],  v_g = c W_UV,g
                 head h = g (S + 1) + j: signal for j < S, noise for j = S
                 a_h(i) = softmax_j((q_h(i) . k_g(j)) / sqrt(head_dim)
                                    + mask(i, j)) v_g(j)
                 o_h = (a_h - sigmoid(u W_lambda)_h a_noise(g))
                       * sigmoid(u W_G)_h               (signal heads only)
                 out = [o_h] W_O
    mask       : causal; a WINDOW layer sees j only where
                 i - j < sliding_window
    FFN        : PolyNorm-gated: (PN(x W_gate) * (x W_up)) W_down
                 PN(z) = s (w0 N(z^3) + w1 N(z^2) + w2 N(z) + clamp(b, +-c))
                 N gainless RMS over the row (eps 1e-6), s, c =
                 polynorm_output_scale, polynorm_bias_clamp
    experts    : sigma = sigmoid(x W_r); T = top-k(sigma)
                 w_e = route_scale * sigma_e / (sum_{T} sigma + 1e-20)
                 out = Shared(x) + sum_{e in T, e held here} w_e Expert_e(x)
    head       : logits = RMS(sum of the n streams; g_f) W_head   (untied)

Layer ``l`` of the cut is the published layer ``layers_kept[l]``: full below
``max_window_layers`` and where ``(i + 1) % sliding_window_period == 0``,
window otherwise; dense below ``n_dense_first_layers``, experts after.

Readings, each one stated (the configuration's ``assumed`` lists them):
``diff_v2`` is the token- and head-wise ``lambda = sigmoid(u W_lambda)`` with
no per-head norm; the output gate is elementwise (``sigmoid(u W_G)``, one a
signal head's output number); rotary is half-split, theta 10,000, unscaled
(``apply_yarn_scaling`` false); the window holds the query's own position and
the 127 before it; the mHC norm is gainless and its three gains ``a`` and
biases ``b`` are seeded (``mhc_identity_init`` false): ``a`` uniform(0.05,
0.15), ``b`` normal(0, 1); PolyNorm's ``w0, w1, w2`` uniform(1/6, 1/2), ``b``
uniform(-0.5, 0.5), an MLP each (every held expert its own); no bias on any
projection; weights normal(0, ``initializer_range``), norm gains 1.

Departures from the published model, each one stated:

* **The chip's share** (``experts_held``): only the held experts' weights
  exist; the router keeps its published width and its experts per token,
  the weights ``w_e`` are normalised over all the chosen experts, and what
  the absent experts would add is LEFT OUT, here as in the program.
* **A sliced vocabulary** and **the depth cut** (``layers_kept``).
* **No multi-token-prediction module** (``num_nextn_predict_layers`` 0).
* Every held expert is applied densely to every token and masked by the
  routing; attention is computed a group and a query block at a time.
  Neither changes a number. The router and the mHC mixings' elementwise
  arithmetic stay float32 in the control: only weight products are rounded.

It imports nothing of the program and takes nothing the program made. It makes
its own weights from a key, STORED in the dtype the configuration states
(``param_dtype``, bfloat16; the mHC gains and biases and the PolyNorm
coefficients float32) and handed to the program as they are; the forward
upcasts one layer's leaves (inside an expert layer, one expert's) at a
time: call it outside ``jax.jit`` and each layer is a program of its own.
"""
import functools

import numpy as np

QUERY_BLOCK = 512       # query rows whose score matrices are live together
POLYNORM_EPS = 1e-6


def sizes(config):
    c = config
    H, G = c["num_attention_heads"], c["num_key_value_heads"]
    return dict(
        d=c["hidden_size"], H=H, G=G, S=H // G - 1, rq=c["q_lora_rank"],
        rkv=c["kv_lora_rank"], dr=c["qk_rope_head_dim"],
        dn=c["head_dim"] - c["qk_rope_head_dim"], dv=c["v_head_dim"],
        inner=c["intermediate_size"], f=c["moe_intermediate_size"],
        E=c["num_experts"], k=c["experts_top_k"],
        shared=c["num_shared_experts"], V=c["vocab_size"],
        L=c["num_hidden_layers"], n=c["mhc_expansion_rate"],
        held=int(c["experts_held"]["count"]),
        first=int(c["experts_held"]["first"]))


def published_index(config, l):
    kept = config.get("layers_kept")
    return l if kept is None else int(kept[l])


def is_window(config, l):
    i = published_index(config, l)
    return i >= config["max_window_layers"] \
        and (i + 1) % config["sliding_window_period"] != 0


def is_dense(config, l):
    return published_index(config, l) < config["n_dense_first_layers"]


def layer_shapes(config, l):
    z = sizes(config)
    d, H, G, S, n = z["d"], z["H"], z["G"], z["S"], z["n"]
    out = {"norm_attn_in": (d,), "norm_ffn_in": (d,)}
    for sub in ("attn", "ffn"):
        out.update({"mhc_%s_phi" % sub: (n * d, 2 * n + n * n),
                    "mhc_%s_alpha" % sub: (3,),
                    "mhc_%s_bias" % sub: (2 * n + n * n,)})
    out.update({
        "wq_a": (d, z["rq"]), "norm_q": (z["rq"],),
        "wq_b": (z["rq"], H * (z["dn"] + z["dr"])),
        "wkv_a": (d, z["rkv"] + z["dr"]), "norm_kv": (z["rkv"],),
        "wkv_b": (z["rkv"], G * (z["dn"] + z["dv"])),
        "w_lambda": (d, G * S), "wg_o": (d, G * S * z["dv"]),
        "wo": (G * S * z["dv"], d)})
    if is_dense(config, l):
        out.update({"w_gate": (d, z["inner"]), "w_up": (d, z["inner"]),
                    "w_down": (z["inner"], d), "mlp_poly": (4,)})
    else:
        fs = z["f"] * z["shared"]
        out.update({
            "router": (d, z["E"]),
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d), "shared_poly": (4,),
            "experts_gate": (z["held"], d, z["f"]),
            "experts_up": (z["held"], d, z["f"]),
            "experts_down": (z["held"], z["f"], d),
            "experts_poly": (z["held"], 4)})
    return out


def param_shapes(config):
    z = sizes(config)
    return {"embed": (z["V"], z["d"]), "head": (z["d"], z["V"]),
            "norm_f": (z["d"],),
            "layers": [layer_shapes(config, l) for l in range(z["L"])]}


def param_count(config, matrices_only=False):
    """Every parameter, or (``matrices_only``) those of the bfloat16
    leaves that are no norm gain."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(
        param_shapes(config), is_leaf=lambda s: isinstance(s, tuple))[0]
    return sum(int(np.prod(s)) for path, s in leaves
               if not matrices_only or _kind(str(path[-1].key)) == "matrix")


def _kind(name):
    last = name.rsplit("_", 1)[-1]
    if name.startswith("norm_"):
        return "norm"
    return last if last in ("alpha", "bias", "poly") else "matrix"


def init_params(config, key):
    """Seeded weights on the device in one jitted call: matrices and
    embeddings normal(0, ``initializer_range``) made in ``param_dtype``
    directly (no float32 copy), norm gains 1, the float32 leaves as the
    module docstring states."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(config)
    dt = jnp.dtype(config.get("param_dtype", "bfloat16"))
    std = float(config["initializer_range"])
    is_shape = lambda s: isinstance(s, tuple)           # noqa: E731
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                        is_leaf=is_shape)
    f32 = jnp.float32

    def special(kind, k, shape):
        if kind == "alpha":
            return jax.random.uniform(k, shape, f32, 0.05, 0.15)
        if kind == "bias":
            return jax.random.normal(k, shape, f32)
        return jnp.concatenate(                                 # poly
            [jax.random.uniform(k, shape[:-1] + (3,), f32, 1 / 6, 1 / 2),
             jax.random.uniform(jax.random.fold_in(k, 1), shape[:-1] + (1,),
                                f32, -0.5, 0.5)], -1)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            kind = _kind(str(path[-1].key))
            if kind == "norm":
                out.append(jnp.ones(shape, dt))
            elif kind == "matrix":
                out.append((jax.random.normal(k, shape, f32)
                            * std).astype(dt))
            else:
                out.append(special(kind, k, shape))
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
        return (x / s).astype(f8).astype(jnp.bfloat16), s

    qa, sa = q(a)
    qb, sb = q(b)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * (sa * sb)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if g is None else y * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """Half-split rotary over the last axis of ``x`` (``[S, ..., dr]``)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _sigmoid(x):
    import jax
    return jax.nn.sigmoid(x)


def polynorm(config, z, coef):
    import jax.numpy as jnp
    s, c = config["polynorm_output_scale"], config["polynorm_bias_clamp"]
    coef = coef.astype(jnp.float32)
    return s * (coef[..., 0:1] * _rms(z ** 3, None, POLYNORM_EPS)
                + coef[..., 1:2] * _rms(z ** 2, None, POLYNORM_EPS)
                + coef[..., 2:3] * _rms(z, None, POLYNORM_EPS)
                + jnp.clip(coef[..., 3:4], -c, c))


def _mlp(config, x, wg, wu, wd, coef, mode):
    return _mm(polynorm(config, _mm(x, wg, mode), coef) * _mm(x, wu, mode),
               wd, mode)


def sinkhorn(m, iters):
    import jax.numpy as jnp
    for _ in range(iters):
        m = m / jnp.sum(m, -1, keepdims=True)
        m = m / jnp.sum(m, -2, keepdims=True)
    return m


def _sublayer(config, lp, x, sub, norm, f, mode):
    """``x`` ``[S, n, d]`` -> the stream after sub-layer ``f``."""
    import jax.numpy as jnp
    S, n, d = x.shape
    xt = _rms(x.reshape(S, n * d), None, config["rms_norm_eps"])
    p = _mm(xt, lp["mhc_%s_phi" % sub], mode)
    a, b = lp["mhc_%s_alpha" % sub], lp["mhc_%s_bias" % sub]
    pre = _sigmoid(a[0] * p[:, :n] + b[:n])
    post = 2 * _sigmoid(a[1] * p[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(jnp.exp(a[2] * p[:, 2 * n:] + b[2 * n:]).reshape(S, n, n),
                   config["mhc_sinkhorn_iters"])
    h = _rms(jnp.einsum("si,sid->sd", pre, x), lp[norm],
             config["rms_norm_eps"])
    out = jnp.einsum("sij,sjd->sid", res, x) + post[..., None] \
        * f(h)[:, None, :]
    c = float(config["hidden_clamp"])
    return jnp.clip(out, -c, c)


def _attention(config, lp, u, window, mode):
    """GDLA over the normed ``u`` ``[S, d]`` of one sequence -> ``[S, d]``."""
    import jax
    import jax.numpy as jnp
    z = sizes(config)
    H, G, S_, dn, dr, dv = z["H"], z["G"], z["S"], z["dn"], z["dr"], z["dv"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    T = u.shape[0]
    pos = jnp.arange(T)
    c_q = _rms(_mm(u, lp["wq_a"], mode), lp["norm_q"], eps)
    q = _mm(c_q, lp["wq_b"], mode).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, theta)], -1)
    kv = _mm(u, lp["wkv_a"], mode)
    c = _rms(kv[:, :z["rkv"]], lp["norm_kv"], eps)
    k_r = _rope(kv[:, z["rkv"]:], pos, theta)                   # [T, dr]
    kvb = _mm(c, lp["wkv_b"], mode).reshape(T, G, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        k_r[:, None], (T, G, dr))], -1).transpose(1, 0, 2)      # [G, T, 192]
    v = kvb[..., dn:].transpose(1, 0, 2)                        # [G, T, dv]
    q = q.reshape(T, G, S_ + 1, dn + dr).transpose(1, 2, 0, 3)  # [G,S+1,T,.]
    scale = 1.0 / np.sqrt(config["head_dim"])
    W = int(config["sliding_window"])
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    def group(qkv):
        qg, kg, vg = qkv                            # [S+1, T, .], [T, .] x2

        def block(b):
            qs = jax.lax.dynamic_slice_in_dim(qg, b * qb, qb, axis=1)
            i = b * qb + jnp.arange(qb)[:, None]
            live = (j <= i) & ((i - j < W) if window else True)
            if mode == "float32":
                s = jnp.einsum("hqd,kd->hqk", qs, kg,
                               precision=jax.lax.Precision.HIGHEST)
            else:
                s = jax.vmap(lambda a: _mm(a, kg.T, mode))(qs)
            p = jax.nn.softmax(jnp.where(live, s * scale, -jnp.inf), -1)
            if mode == "float32":
                return jnp.einsum("hqk,kd->hqd", p, vg,
                                  precision=jax.lax.Precision.HIGHEST)
            return jax.vmap(lambda a: _mm(a, vg, mode))(p)

        o = jax.lax.map(block, jnp.arange(T // qb))     # [nb, S+1, qb, dv]
        return o.transpose(1, 0, 2, 3).reshape(S_ + 1, T, dv)

    a = jax.lax.map(group, (q, k, v))                   # [G, S+1, T, dv]
    lam = _sigmoid(_mm(u, lp["w_lambda"], mode)).reshape(T, G, S_)
    lam = lam.transpose(1, 2, 0)[..., None]             # [G, S, T, 1]
    o = (a[:, :S_] - lam * a[:, S_:]).transpose(2, 0, 1, 3)  # [T, G, S, dv]
    o = o.reshape(T, G * S_ * dv) * _sigmoid(_mm(u, lp["wg_o"], mode))
    return _mm(o, lp["wo"], mode)


def routing(config, x, router):
    """``(chosen [S, k] expert ids, weights [S, k])`` over ALL experts; the
    scores are float32 whatever ``mode`` the rest runs in."""
    import jax
    import jax.numpy as jnp
    sigma = jax.nn.sigmoid(_mm(x, router, "float32"))
    top_s, top_e = jax.lax.top_k(sigma, config["experts_top_k"])
    w = config["route_scale"] * top_s \
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
        e, wg, wu, wd, coef = ew
        w_e = jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _mlp(config, x, wg, wu, wd, coef,
                                         mode), None

    n = lp["experts_gate"].shape[0]
    acc, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(n), lp["experts_gate"], lp["experts_up"],
         lp["experts_down"], lp["experts_poly"]))
    return acc


def expert_layer(config, lp, x, mode):
    return _mlp(config, x, lp["shared_gate"], lp["shared_up"],
                lp["shared_down"], lp["shared_poly"], mode) \
        + routed_part(config, lp, x, mode)


def _layer(config, mode, window, lp, x):
    """One layer over the streams ``x`` ``[B, S, n, d]`` float32."""
    import jax
    ffn = (lambda h: _mlp(config, h, lp["w_gate"], lp["w_up"], lp["w_down"],
                          lp["mlp_poly"], mode)) if "w_gate" in lp \
        else (lambda h: expert_layer(config, lp, h, mode))

    def one(x):
        x = _sublayer(config, lp, x, "attn", "norm_attn_in",
                      lambda h: _attention(config, lp, h, window, mode), mode)
        return _sublayer(config, lp, x, "ffn", "norm_ffn_in", ffn, mode)

    return jax.lax.map(one, x)


@functools.lru_cache(maxsize=None)
def _jitted(fn, config_key, mode, *static):
    import json
    import jax
    return jax.jit(functools.partial(fn, json.loads(config_key), mode,
                                     *static))


def _head(config, mode, norm_f, head, x, positions):
    import jax.numpy as jnp
    x = jnp.sum(x, axis=2)                              # the streams summed
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _mm(_rms(x, norm_f, config["rms_norm_eps"]), head, mode)


def logits_at(config, params, tokens, positions, dtype="float32"):
    """Logits ``[B, K, vocab]`` of a full causal forward over ``tokens``
    ``[B, S]`` at ``positions`` ``[B, K]``, the weight products in ``dtype``
    (``float32`` | ``float8_e4m3``). Each layer is one jitted program
    (layers of one kind share theirs)."""
    import json
    import jax.numpy as jnp
    ck = json.dumps(config, sort_keys=True)
    n = int(config["mhc_expansion_rate"])
    x = params["embed"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(x[:, :, None], x.shape[:2] + (n, x.shape[-1]))
    for l, lp in enumerate(params["layers"]):
        x = _jitted(_layer, ck, dtype, is_window(config, l))(lp, x)
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
