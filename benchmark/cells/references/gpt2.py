"""Plain reference: the GPT-2 decoder (Radford et al. 2019) — learned position
embeddings, pre-LN blocks, tanh-approximated GELU, output head tied to the
token embedding — as one full causal forward pass in ``jax.numpy``: no cache,
no kernels, no batching tricks. float32 with matmul precision ``highest``
unless a lower ``dtype`` is asked for (the control).

It imports nothing of the program and takes nothing the program made. The
parameter layout (per-layer arrays stacked on a leading axis) is only the
layout both sides are handed the same values in.
"""
import numpy as np

LN_EPS = 1e-5


def param_shapes(config):
    d, f, L = config["n_embd"], config["n_inner"], config["n_layer"]
    return {
        "embed": (config["vocab_size"], d),
        "pos_embed": (config["n_positions"], d),
        "ln_f_scale": (d,), "ln_f_bias": (d,),
        "layers": {
            "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
            "wo": (L, d, d), "w1": (L, d, f), "b1": (L, f),
            "w2": (L, f, d), "b2": (L, d),
            "ln1_scale": (L, d), "ln1_bias": (L, d),
            "ln2_scale": (L, d), "ln2_bias": (L, d),
        },
    }


def init_params(config, key):
    """Seeded float32 weights on the device in one jitted call: normal with
    the published ``initializer_range`` for matrices and embeddings, LayerNorm
    scales 1, biases 0."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(config)
    std = np.float32(config["initializer_range"])

    def leaf(k, name, shape):
        if name.endswith("_scale"):
            return jnp.ones(shape, jnp.float32)
        if name.endswith("_bias") or name in ("b1", "b2"):
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(k, shape, jnp.float32) * std

    @jax.jit
    def make(key):
        names = [n for n in shapes if n != "layers"]
        lnames = list(shapes["layers"])
        keys = jax.random.split(key, len(names) + len(lnames))
        out = {n: leaf(k, n, shapes[n]) for k, n in zip(keys, names)}
        out["layers"] = {n: leaf(k, n, shapes["layers"][n])
                         for k, n in zip(keys[len(names):], lnames)}
        return out

    return make(key)


def _layer_norm(x, scale, bias):
    import jax
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def logits_at(config, params, tokens, positions, dtype="float32"):
    """Logits ``[B, K, vocab]`` of a full causal forward over ``tokens``
    ``[B, S]`` at ``positions`` ``[B, K]``. Everything (weights, activations,
    softmax, logits) is held in ``dtype``."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    B, S = tokens.shape
    H = config["n_head"]
    d = config["n_embd"]
    Dh = d // H
    p = jax.tree_util.tree_map(lambda v: v.astype(dt), params)
    x = p["embed"][tokens] + p["pos_embed"][:S]
    mask = jnp.tril(jnp.ones((S, S), bool))
    neg = jnp.asarray(jnp.finfo(dt).min, dt)
    def block(x, lp):
        h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        q, k, v = (jnp.matmul(h, lp[w], precision=prec)
                   .reshape(B, S, H, Dh).transpose(0, 2, 1, 3)
                   for w in ("wq", "wk", "wv"))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=prec) \
            * jnp.asarray(1.0 / np.sqrt(Dh), dt)
        w = jax.nn.softmax(jnp.where(mask, s, neg), axis=-1)
        a = jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=prec)
        a = a.transpose(0, 2, 1, 3).reshape(B, S, d)
        x = x + jnp.matmul(a, lp["wo"], precision=prec)
        h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        h = jax.nn.gelu(jnp.matmul(h, lp["w1"], precision=prec) + lp["b1"],
                        approximate=True)
        return x + jnp.matmul(h, lp["w2"], precision=prec) + lp["b2"], None

    # the layers are alike: one scanned body over the stacked weights
    x, _ = jax.lax.scan(block, x, p["layers"])
    x = _layer_norm(x, p["ln_f_scale"], p["ln_f_bias"])
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return jnp.matmul(x, p["embed"].T, precision=prec)


def served_gaps(config, params, tokens, positions, served, valid,
                yardstick_dtype):
    """Two gaps for each served position, each measured on the float32
    reference's logits below the reference's best there: that of the token
    that was SERVED, and that of the token a forward pass held wholly in
    ``yardstick_dtype`` (the nearest precision below the stated one) puts
    first. The second is the yardstick a run's served tokens are held
    against: how far a lower precision strays on THESE weights and prompts.
    Invalid (padding) slots read 0. Returns two device arrays ``[B, K]``."""
    import jax.numpy as jnp
    ref = logits_at(config, params, tokens, positions).astype(jnp.float32)
    low = logits_at(config, params, tokens, positions, yardstick_dtype)
    best = jnp.max(ref, axis=-1)

    def gap(tok):
        got = jnp.take_along_axis(ref, tok[:, :, None], axis=-1)[..., 0]
        return jnp.where(valid, best - got, 0.0)

    return gap(served), gap(jnp.argmax(low, axis=-1))
