"""Plain reference: the Kimi-Linear decoder (config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct; Kimi Linear, arXiv:2510.26692) as
one full causal forward pass in ``jax.numpy``: no cache, no kernels, no
chunked scan, no absorbed attention, no grouped products. float32 with matmul
precision ``highest``; the control (``float8_e4m3``) is the same forward with
every weight product's two operands rounded to e4m3, per tensor scaled.

The layer equations (sizes by the config's own keys; ``H``, ``d_k`` =
``linear_attn_config.num_heads``, ``.head_dim``; ``d_v = d_k``)::

    RMS(x; g)  = x / sqrt(mean(x^2) + rms_norm_eps) * g
    block      : h = x + Mix(RMS(x; g1));  y = h + FFN(RMS(h; g2))   (pre-norm)
    KDA        : [q^ | k^ | v^] = x W_qkv
                 c_t = SiLU(sum_{i<taps} w_i * c^_{t-taps+1+i})  for c in q, k, v
                       (zeros before the first token)
                 q_t = q_t / |q_t| / sqrt(d_k),  k_t = k_t / |k_t|     (a head)
                 g_t = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)   (a head
                       and CHANNEL, <= 0);  beta_t = sigmoid(x W_b)    (a head)
                 S'_t = Diag(exp g_t) S_{t-1};  S_0 = 0
                 S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
                 o_t  = S_t^T q_t
                 y_t  = [RMS_head(o_t; g_o) * sigmoid(x W_ga W_gb)] W_o
    MLA        : [q_nope | q_pe]_h = x W_q          (no down-projection)
                 [c_kv | k_pe] = x W_kva;  c = RMS(c_kv; g_kv)
                 [k_nope | v]_h = c W_kvb;  key_h = [k_nope_h | k_pe]
                 (NOTHING is rotated: mla_use_nope)
                 causal softmax at 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)
    gated MLP  : (silu(x W_gate) * (x W_up)) W_down
    experts    : sigma = sigmoid(x W_r); T = top-k(sigma)
                 w_e = routed_scaling_factor * sigma_e / (sum_{T} sigma + 1e-20)
                 out = Shared(x) + sum_{e in T, e held here} w_e Expert_e(x)
    head       : logits = RMS(x_L; g_f) W_head               (untied)

Layer ``l`` (1-based) is KDA if ``l`` is in ``linear_attn_config.kda_layers``,
MLA if in ``full_attn_layers``; FFN is the gated MLP in the first
``first_k_dense_replace`` layers and the expert layer after them.

The KDA state is computed as the RECURRENCE above, a token at a time
(``lax.scan``): the program's chunked scan is another derivation of the same
numbers, and the two check each other.

Departures from the published model, each one stated:

* **The chip's share** (``experts_held``: ``first``, ``count``): only the
  held experts' weights exist; the router keeps its published width and its
  experts per token, the weights ``w_e`` are normalised over all the chosen
  experts, and what the absent experts would add is LEFT OUT, here as in the
  program; that partial result goes on to the next layer.
* **A sliced vocabulary**: ``vocab_size`` rows of embedding and head.
* **Depth**: ``num_hidden_layers`` and the two layer lists are cut together.
* **Assumed, not in the published config**: no bias on any projection; the
  router's selection bias (``e_score_correction_bias``) is zero; ``A_log`` is
  the log of uniform(1, 16) and ``dt_bias`` zero from the seed; the L2
  norms add 1e-6 under the root (the published kernels' guard); the two
  low-rank gates pass through a width of ``head_dim``; weights are seeded
  normal(0, ``initializer_range``), norm gains 1.
* ``W_q``, ``W_k``, ``W_v`` of a KDA layer are ONE leaf ``wqkv`` (their
  columns side by side) and the three convolutions one ``conv``: layout only.
* Every held expert is applied densely to every token and masked by the
  routing; attention is computed a group of heads at a time. Neither changes
  a number. The recurrence and the convolution run in float32 in the control
  too: they are no weight products.

It imports nothing of the program and takes nothing the program made. It makes
its own weights from a key, STORED in the dtype the configuration states
(``param_dtype``, bfloat16) and handed to the program as they are; the
forward upcasts one layer's leaves at a time: call it outside ``jax.jit`` and
each layer is a program of its own.
"""
import functools

import numpy as np

HEAD_GROUP = 4          # heads whose score matrices are live together
L2_EPS = 1e-6


def sizes(config):
    c, lin = config, config["linear_attn_config"]
    return dict(
        d=c["hidden_size"], H=c["num_attention_heads"],
        rkv=c["kv_lora_rank"], dn=c["qk_nope_head_dim"],
        dr=c["qk_rope_head_dim"], dv=c["v_head_dim"],
        inner=c["intermediate_size"], f=c["moe_intermediate_size"],
        E=c["num_experts"], k=c["num_experts_per_token"],
        shared=c["num_shared_experts"], V=c["vocab_size"],
        L=c["num_hidden_layers"], dense=c["first_k_dense_replace"],
        held=int(c["experts_held"]["count"]),
        first=int(c["experts_held"]["first"]),
        Hk=lin["num_heads"], dk=lin["head_dim"],
        taps=lin["short_conv_kernel_size"],
        kda=tuple(lin["kda_layers"]), mla=tuple(lin["full_attn_layers"]))


def layer_shapes(config, l):
    """Layer ``l`` (0-based)."""
    z = sizes(config)
    d = z["d"]
    out = {"norm_attn_in": (d,), "norm_ffn_in": (d,)}
    if l + 1 in z["kda"]:
        Hd, dk = z["Hk"] * z["dk"], z["dk"]
        out.update({
            "wqkv": (d, 3 * Hd), "conv": (z["taps"], 3 * Hd),
            "A_log": (z["Hk"],), "wf_a": (d, dk), "wf_b": (dk, Hd),
            "dt_bias": (Hd,), "wb": (d, z["Hk"]), "wg_a": (d, dk),
            "wg_b": (dk, Hd), "norm_o": (dk,), "wo": (Hd, d)})
    else:
        H = z["H"]
        out.update({
            "wq": (d, H * (z["dn"] + z["dr"])),
            "wkv_a": (d, z["rkv"] + z["dr"]), "norm_kv": (z["rkv"],),
            "wkv_b": (z["rkv"], H * (z["dn"] + z["dv"])),
            "wo": (H * z["dv"], d)})
    if l < z["dense"]:
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
    z = sizes(config)
    return {"embed": (z["V"], z["d"]), "head": (z["d"], z["V"]),
            "norm_f": (z["d"],),
            "layers": [layer_shapes(config, l) for l in range(z["L"])]}


def param_count(config):
    import jax
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(config), is_leaf=lambda s: isinstance(s, tuple)))


def init_params(config, key):
    """Seeded weights on the device in one jitted call, every leaf made in
    ``param_dtype`` directly: normal(0, ``initializer_range``) for matrices,
    embeddings and convolution taps, norm gains 1; ``A_log`` = log of
    uniform(1, 16) and ``dt_bias`` = 0, both float32."""
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
            name = str(path[-1].key)
            if name.startswith("norm_"):
                out.append(jnp.ones(shape, dt))
            elif name == "dt_bias":
                out.append(jnp.zeros(shape, jnp.float32))
            elif name == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dt))
        return jax.tree_util.tree_unflatten(tree, out)

    return make(key)


# ---------------------------------------------------------------------------
def _mm(a, b, mode):
    """``a @ b``, float32 out. ``float32``: both operands upcast, precision
    ``highest``. ``float8_e4m3``: each operand scaled by its largest
    magnitude to the format's range, rounded to e4m3, multiplied and
    accumulated in float32."""
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


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _mlp(x, wg, wu, wd, mode):
    return _mm(_silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def kda_inputs(config, lp, x, mode):
    """``x`` ``[S, d]`` (normed) -> ``(q, k, v [S, Hk, dk], g [S, Hk, dk],
    beta [S, Hk])`` of one sequence from its first token."""
    import jax
    import jax.numpy as jnp
    z = sizes(config)
    S, H, dk, taps = x.shape[0], z["Hk"], z["dk"], z["taps"]
    rows = _mm(x, lp["wqkv"], mode)                             # [S, 3 H dk]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, rows.shape[1]), jnp.float32), rows], axis=0)
    w = lp["conv"].astype(jnp.float32)
    y = _silu(sum(w[i] * padded[i:i + S] for i in range(taps)))
    q, k, v = (t.reshape(S, H, dk) for t in jnp.split(y, 3, axis=-1))
    unit = lambda t: t / jnp.sqrt(                              # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) / np.sqrt(dk), unit(k)
    f = _mm(_mm(x, lp["wf_a"], mode), lp["wf_b"], mode) \
        + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(f.reshape(S, H, dk))
    beta = jax.nn.sigmoid(_mm(x, lp["wb"], mode))
    return q, k, v, g, beta


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token at a time from a zero state; elementwise
    float32 (no matrix unit, so no precision to state). ``[S, Hk, dv]``."""
    import jax
    import jax.numpy as jnp

    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S                         # [H, dk, dv]
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[..., None], axis=1))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.sum(S * q_t[..., None], axis=1)

    H, dk = q.shape[1:]
    _, o = jax.lax.scan(one, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def _kda(config, lp, x, mode):
    """``x`` ``[S, d]`` (already normed) -> ``[S, d]``; one sequence."""
    import jax
    z = sizes(config)
    S, H, dk = x.shape[0], z["Hk"], z["dk"]
    o = delta_rule(*kda_inputs(config, lp, x, mode))
    gate = jax.nn.sigmoid(_mm(_mm(x, lp["wg_a"], mode), lp["wg_b"], mode))
    y = _rms(o, lp["norm_o"], config["rms_norm_eps"]) * gate.reshape(S, H, dk)
    return _mm(y.reshape(S, H * dk), lp["wo"], mode)


def _mla(config, lp, x, mode):
    """``x`` ``[S, d]`` (already normed) -> ``[S, d]``; one sequence. No
    positional encoding: ``mla_use_nope``."""
    import jax
    import jax.numpy as jnp
    if not config.get("mla_use_nope", False) \
            or config.get("q_lora_rank") is not None:
        raise ValueError("this reference is the NoPE, direct-query form")
    z = sizes(config)
    H, dn, dr, dv = z["H"], z["dn"], z["dr"], z["dv"]
    S = x.shape[0]
    q = _mm(x, lp["wq"], mode).reshape(S, H, dn + dr).transpose(1, 0, 2)
    kv = _mm(x, lp["wkv_a"], mode)
    c = _rms(kv[:, :z["rkv"]], lp["norm_kv"], config["rms_norm_eps"])
    k_pe = kv[:, z["rkv"]:]                                     # [S, dr]
    kvb = _mm(c, lp["wkv_b"], mode).reshape(S, H, dn + dv).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe[None], (H, S, dr))], -1)
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
    scores are float32 whatever ``mode`` the rest runs in. One expert group
    and one group chosen (``num_expert_group``, ``topk_group`` 1) make the
    published grouped top-k a plain one."""
    import jax
    import jax.numpy as jnp
    sigma = jax.nn.sigmoid(_mm(x, router, "float32"))
    top_s, top_e = jax.lax.top_k(sigma, config["num_experts_per_token"])
    w = config["routed_scaling_factor"] * top_s \
        / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_e, w


def routed_part(config, lp, x, mode, first=None):
    """What the held experts add: every held expert applied to every token,
    masked by the routing. ``first``: the id of the first held expert
    (default ``experts_held.first``)."""
    import jax
    import jax.numpy as jnp
    first = sizes(config)["first"] if first is None else first
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
    """One pre-norm block over ``x`` ``[B, S, d]`` float32; the layer's kind
    is read off its leaves."""
    import jax
    eps = config["rms_norm_eps"]
    mix = _kda if "wqkv" in lp else _mla
    ffn = (lambda h: _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"], mode)) \
        if "w_gate" in lp else (lambda h: expert_layer(config, lp, h, mode))

    def one(x):
        h = x + mix(config, lp, _rms(x, lp["norm_attn_in"], eps), mode)
        return h + ffn(_rms(h, lp["norm_ffn_in"], eps))

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
    ``[B, S]`` at ``positions`` ``[B, K]``, the weight products in ``dtype``
    (``float32`` | ``float8_e4m3``). Each layer is one jitted program
    (layers of one kind and shape share theirs)."""
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
    """As ``references/pangu_umoe.py``: for each served position two gaps,
    each measured on the float32 reference's logits below the reference's
    best there: that of the token that was SERVED, and that of the token the
    same forward with ``yardstick_dtype`` operands puts first. Invalid
    (padding) slots read 0. Two device arrays ``[B, K]``."""
    import jax.numpy as jnp
    ref = logits_at(config, params, tokens, positions)
    low = logits_at(config, params, tokens, positions, yardstick_dtype)
    best = jnp.max(ref, axis=-1)

    def gap(tok):
        got = jnp.take_along_axis(ref, tok[:, :, None], axis=-1)[..., 0]
        return jnp.where(valid, best - got, 0.0)

    return gap(served), gap(jnp.argmax(low, axis=-1))
