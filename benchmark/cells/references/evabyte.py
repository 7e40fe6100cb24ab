"""Plain reference: the EvaByte decoder (config.json of EvaByte/EvaByte; EVA
attention is Zheng et al., "Efficient Attention via Control Variates", ICLR
2023) as one full forward pass in ``jax.numpy``: no cache, no pages, no
kernels. float32 with matmul precision ``highest``; the control
(``float8_e4m3``) is the same forward with the two operands of every weight
product and of both attention products rounded to e4m3, per tensor scaled.

The layer equations (sizes by the config's own keys; ``H`` heads of ``d =
hidden_size / H``, ``W = window_size``, ``C = chunk_size``)::

    norm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)
                                                    (norm_add_unit_offset)
    block      : h = x + Attn(norm(x; g1));  y = h + MLP(norm(h; g2))
                 (pre-norm; the sums in float32: fp32_skip_add)
    MLP        : (silu(x W_gate) * (x W_up)) W_down
    q, k, v    : x W_q, x W_k, x W_v, split into H heads; q_t and k_t rotated
                 to position t (rotary over the whole head, half-split
                 pairs, theta = rope_theta, no scaling)
    chunk c    : positions [C c, C c + C). Its two summaries, per head, with
                 the head's learned vectors mu, phi in R^d:
                 kbar_c = sum_m softmax_m(mu . k_m) k_m
                 vbar_c = sum_m softmax_m(s_phi phi . k_m) v_m
                 (both softmaxes over the C positions m of the chunk)
    query at t : w = t // W. Exact set E_t = {s : w W <= s <= t}; summary
                 set S_t = {c : C c < w W}: every chunk of every CLOSED
                 window, the open window's own chunks never.
                 o_t = (sum_E e^{q_t.k_s / sqrt d} v_s
                        + sum_S e^{q_t.kbar_c / sqrt d} vbar_c)
                       / (sum_E e^{q_t.k_s / sqrt d}
                          + sum_S e^{q_t.kbar_c / sqrt d})
                 ONE softmax over both sets; then W_o.
    head       : logits = norm(x_L; g_f) W_head   (untied, float32:
                 fp32_logits)

The forward is computed a WINDOW of queries at a time (`_attention`,
``blocked``): window ``w``'s queries against the summaries of the windows
before it and, causally, the keys of its own. ``blocked=False`` computes the
same numbers with one mask over every position and every summary (the tests
hold the two together at a small size).

Readings taken, and departures from the published model, each one stated:

* **Windows are aligned** (``t // W``), not sliding: the published
  implementation's cache keeps ONE window's keys and values and hands its
  chunks' summaries over when the window fills, so a position sees its own
  window exactly and every earlier window through summaries only.
* **Assumed, not in the published config** (``assumed`` in the
  configuration's file): ``s_phi = d^-1/2``; no scale on ``mu . k``; ``mu``
  and ``phi`` seeded normal(0, ``init_std``); every matrix seeded normal(0,
  ``init_std``); norm gains ``g`` 0 (a norm starts as the identity); rotary
  pairs are the half-split ones (lane ``i`` with lane ``i + d / 2``); no
  bias (``attention_bias`` false).
* **One prediction head** (``num_pred_heads`` 8 -> 1): the seven further
  heads (bytes t+2 .. t+8, for self-drafting) are left out; a step yields
  one byte.
* **Depth**: ``num_hidden_layers`` is cut; every width, head count and the
  vocabulary are as published.
* Attention is computed a group of heads at a time: layout only. The two
  pooling softmaxes and their weighted sums are float32 in the control too:
  the control rounds products against weights and the attention products.

It imports nothing of the program and takes nothing the program made. It
makes its own weights from a key, STORED in the dtype the configuration
states (``param_dtype``, bfloat16) and handed to the program as they are; the
forward upcasts one layer's leaves at a time: call it outside ``jax.jit`` and
each layer is a program of its own.
"""
import functools

import numpy as np

HEAD_GROUP = 4          # heads whose score matrices are live together


def sizes(config):
    c = config
    H = c["num_attention_heads"]
    if c.get("num_key_value_heads", H) != H:
        raise ValueError("EvaByte has one key-value head a query head")
    return dict(d=c["hidden_size"], L=c["num_hidden_layers"], H=H,
                dh=c["hidden_size"] // H, I=c["intermediate_size"],
                V=c["vocab_size"], W=c["window_size"], C=c["chunk_size"])


def layer_shapes(config):
    z = sizes(config)
    d, H, dh, I = z["d"], z["H"], z["dh"], z["I"]
    return {"norm_attn_in": (d,), "norm_ffn_in": (d,),
            "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "mu": (H, dh), "phi": (H, dh),
            "w_gate": (d, I), "w_up": (d, I), "w_down": (I, d)}


def param_shapes(config):
    z = sizes(config)
    return {"embed": (z["V"], z["d"]), "head": (z["d"], z["V"]),
            "norm_f": (z["d"],),
            "layers": [layer_shapes(config) for _ in range(z["L"])]}


def param_count(config):
    import jax
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(config), is_leaf=lambda s: isinstance(s, tuple)))


def init_params(config, key):
    """Seeded weights on the device in one jitted call, every leaf made in
    ``param_dtype`` directly: normal(0, ``init_std``) for matrices,
    embeddings and the two pooling vectors, norm gains 0."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(config)
    dt = jnp.dtype(config.get("param_dtype", "bfloat16"))
    std = float(config["init_std"])
    is_shape = lambda s: isinstance(s, tuple)           # noqa: E731
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                        is_leaf=is_shape)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            if str(path[-1].key).startswith("norm_"):
                out.append(jnp.zeros(shape, dt))
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


def _norm(x, g, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """``x`` ``[S, H, d]`` float32, row ``t`` rotated to position ``t``:
    lane ``i < d / 2`` pairs with lane ``i + d / 2``, at frequency
    ``theta^(-2 i / d)``."""
    import jax.numpy as jnp
    S, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def summaries(config, lp, k, v):
    """``k``, ``v`` ``[S, H, d]`` (``S`` a multiple of the chunk) -> ``(kbar,
    vbar)`` ``[S / C, H, d]``: each chunk's two softmax-pooled rows, all in
    float32 whatever the rest runs in."""
    import jax
    import jax.numpy as jnp
    z = sizes(config)
    C, H, dh = z["C"], z["H"], z["dh"]
    hi = jax.lax.Precision.HIGHEST
    kc, vc = (t.reshape(-1, C, H, dh) for t in (k, v))
    mu, phi = (lp[n].astype(jnp.float32) for n in ("mu", "phi"))
    a_k = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, mu, precision=hi), axis=1)
    a_v = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi, precision=hi) * dh ** -0.5,
        axis=1)
    return (jnp.einsum("nch,nchd->nhd", a_k, kc, precision=hi),
            jnp.einsum("nch,nchd->nhd", a_v, vc, precision=hi))


def _softmax_rows(q, k, v, mask, mode):
    """``softmax(q k^T / sqrt d, masked) v`` a group of heads at a time: ``q``
    ``[H, Q, d]``, ``k``, ``v`` ``[H, K, d]``, ``mask`` ``[Q, K]``."""
    import jax
    import jax.numpy as jnp
    H, dh = q.shape[0], q.shape[-1]
    hi = jax.lax.Precision.HIGHEST

    def heads(args):
        qh, kh, vh = args
        if mode == "float32":
            s = jnp.einsum("hqd,hkd->hqk", qh, kh, precision=hi)
        else:
            s = jax.vmap(lambda a, b: _mm(a, b.T, mode))(qh, kh)
        p = jax.nn.softmax(jnp.where(mask, s * dh ** -0.5, -jnp.inf), -1)
        if mode == "float32":
            return jnp.einsum("hqk,hkd->hqd", p, vh, precision=hi)
        return jax.vmap(lambda a, b: _mm(a, b, mode))(p, vh)

    g = HEAD_GROUP if H % HEAD_GROUP == 0 else H
    split = lambda t: t.reshape((H // g, g) + t.shape[1:])      # noqa: E731
    o = jax.lax.map(heads, (split(q), split(k), split(v)))
    return o.reshape((H,) + o.shape[2:])


def _attention(config, lp, x, mode, blocked=True):
    """The attention of one sequence: ``x`` ``[S, d]`` normed -> ``[S, d]``
    after ``W_o``. ``S`` is a multiple of the chunk."""
    import jax.numpy as jnp
    z = sizes(config)
    S, H, dh, W, C = x.shape[0], z["H"], z["dh"], z["W"], z["C"]
    q, k, v = (_mm(x, lp[n], mode).reshape(S, H, dh)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    kbar, vbar = summaries(config, lp, k, v)
    hm = lambda t: t.transpose(1, 0, 2)                         # noqa: E731
    q, k, v, kbar, vbar = (hm(t) for t in (q, k, v, kbar, vbar))
    t = jnp.arange(S)
    if not blocked:
        # every summary, then every position, under one mask
        c = jnp.arange(S // C)
        mask = jnp.concatenate(
            [(C * c)[None, :] // W < (t // W)[:, None],
             (t[None, :] // W == (t // W)[:, None])
             & (t[None, :] <= t[:, None])], axis=1)
        o = _softmax_rows(q, jnp.concatenate([kbar, k], 1),
                          jnp.concatenate([vbar, v], 1), mask, mode)
    else:
        outs = []
        for lo in range(0, S, W):
            hi_ = min(lo + W, S)
            n_sum = lo // C         # the chunks of the windows before
            u = jnp.arange(hi_ - lo)
            mask = jnp.concatenate(
                [jnp.ones((hi_ - lo, n_sum), bool),
                 u[None, :] <= u[:, None]], axis=1)
            outs.append(_softmax_rows(
                q[:, lo:hi_],
                jnp.concatenate([kbar[:, :n_sum], k[:, lo:hi_]], 1),
                jnp.concatenate([vbar[:, :n_sum], v[:, lo:hi_]], 1),
                mask, mode))
        o = jnp.concatenate(outs, axis=1)
    return _mm(o.transpose(1, 0, 2).reshape(S, H * dh), lp["wo"], mode)


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _layer(config, mode, blocked, lp, x):
    """One pre-norm block over ``x`` ``[B, S, d]`` float32."""
    import jax
    eps = config["rms_norm_eps"]

    def one(x):
        h = x + _attention(config, lp, _norm(x, lp["norm_attn_in"], eps),
                           mode, blocked)
        f = _norm(h, lp["norm_ffn_in"], eps)
        return h + _mm(_silu(_mm(f, lp["w_gate"], mode))
                       * _mm(f, lp["w_up"], mode), lp["w_down"], mode)

    return jax.lax.map(one, x)


@functools.lru_cache(maxsize=None)
def _jitted(fn, config_key, *static):
    import json
    import jax
    return jax.jit(functools.partial(fn, json.loads(config_key), *static))


def _head(config, mode, norm_f, head, x, positions):
    import jax.numpy as jnp
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _mm(_norm(x, norm_f, config["rms_norm_eps"]), head, mode)


def logits_at(config, params, tokens, positions, dtype="float32",
              blocked=True):
    """Logits ``[B, K, vocab]`` of a full forward over ``tokens`` ``[B, S]``
    (``S`` a multiple of ``chunk_size``) at ``positions`` ``[B, K]``, the
    products in ``dtype`` (``float32`` | ``float8_e4m3``). Each layer is one
    jitted program (the layers share it)."""
    import json
    import jax.numpy as jnp
    if tokens.shape[1] % config["chunk_size"]:
        raise ValueError("pad the sequences to a multiple of chunk_size")
    ck = json.dumps(config, sort_keys=True)
    x = params["embed"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = _jitted(_layer, ck, dtype, blocked)(lp, x)
    return _jitted(_head, ck, dtype)(params["norm_f"], params["head"], x,
                                     positions)


def served_gaps(config, params, tokens, positions, served, valid,
                yardstick_dtype):
    """As ``references/kimi_linear.py``: for each served position two gaps,
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
