"""Plain reference: pre-activation ResNet (He et al., arXiv:1603.05027) as
MXNet 1.2's ``example/image-classification/symbols/resnet.py`` builds it, with
softmax cross-entropy and SGD with momentum — forward, backward and update in
straightforward ``jax.numpy``, float32, matmul precision ``highest``.

It imports nothing of the program and takes nothing the program made: weights
and batches come from the benchmark. Parameter names are the symbol file's own
(``stage1_unit1_conv1_weight``, ...), which is how both sides are handed the
same values.

Departures from the published script, each noted:
- weight decay follows MXNet's ``wd_mult`` rule (weights and gammas only);
- ``bn_data`` has ``fix_gamma=True``: its gamma is read as ones and gets no
  gradient, as in the operator;
- each residual unit is rematerialised in the backward pass
  (``jax.checkpoint``) so that batch 256 in float32 fits beside nothing else,
  and the like-shaped units of a stage run as one ``lax.scan`` body so that
  the compiler sees a third of the program: the numbers are those of the
  plain computation.

``quant`` (a ``Precision``: the control, or the witness for the stated
precision) makes the same computation round as a lower precision would;
``rows`` (a planted fault) leaves part of the batch out of the mean.
"""
import functools

import numpy as np

BN_EPS = 2e-5
_UNITS = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
          101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def arch(config):
    layers, side = config["num_layers"], config["image_side"]
    if side <= 28:                  # the script's CIFAR rule (tests only)
        assert (layers - 2) % 6 == 0 and layers < 164, layers
        units, filters, bottleneck = [(layers - 2) // 6] * 3, \
            [16, 16, 32, 64], False
    else:
        bottleneck = layers >= 50
        units = _UNITS[layers]
        filters = ([64, 256, 512, 1024, 2048] if bottleneck
                   else [64, 64, 128, 256, 512])
    return {
        "units": units,
        "filters": filters,
        "bottleneck": bottleneck,
        "small_stem": side <= 32,       # 3x3 conv0, no bn0/relu0/pool
        "side": side,
        "classes": config["num_classes"],
    }


def _unit_convs(name, c_in, c_out, stride, dim_match, bottleneck):
    """(param name, c_in, c_out, kernel, stride) of one residual unit."""
    if bottleneck:
        mid = c_out // 4
        convs = [(name + "_conv1", c_in, mid, 1, 1),
                 (name + "_conv2", mid, mid, 3, stride),
                 (name + "_conv3", mid, c_out, 1, 1)]
    else:
        convs = [(name + "_conv1", c_in, c_out, 3, stride),
                 (name + "_conv2", c_out, c_out, 3, 1)]
    if not dim_match:
        convs.append((name + "_sc", c_in, c_out, 1, stride))
    return convs


def _walk(config):
    """Yields (unit name, c_in, c_out, stride, dim_match) in order."""
    a = arch(config)
    c_in = a["filters"][0]
    for s, n_units in enumerate(a["units"]):
        c_out = a["filters"][s + 1]
        for u in range(n_units):
            yield ("stage%d_unit%d" % (s + 1, u + 1), c_in, c_out,
                   (1 if s == 0 else 2) if u == 0 else 1, u != 0)
            c_in = c_out


def param_shapes(config):
    """(arg shapes, aux shapes), by the symbol file's names."""
    a = arch(config)
    args, aux = {}, {}

    def bn(name, c):
        args[name + "_gamma"] = (c,)
        args[name + "_beta"] = (c,)
        aux[name + "_moving_mean"] = (c,)
        aux[name + "_moving_var"] = (c,)

    bn("bn_data", 3)
    k0 = 3 if a["small_stem"] else 7
    args["conv0_weight"] = (a["filters"][0], 3, k0, k0)
    if not a["small_stem"]:
        bn("bn0", a["filters"][0])
    for name, c_in, c_out, stride, dim_match in _walk(config):
        convs = _unit_convs(name, c_in, c_out, stride, dim_match,
                            a["bottleneck"])
        for i, (cname, ci, co, k, _) in enumerate(convs):
            if not cname.endswith("_sc"):
                bn("%s_bn%d" % (name, i + 1), ci)
            args[cname + "_weight"] = (co, ci, k, k)
    bn("bn1", a["filters"][-1])
    args["fc1_weight"] = (a["classes"], a["filters"][-1])
    args["fc1_bias"] = (a["classes"],)
    return args, aux


def matrix_layers(config):
    """The convolutions and the classifier of ONE sample, with their output
    sizes: what ``harness.flops.train_flops_per_sample`` counts."""
    a = arch(config)
    layers = []
    hw = a["side"]
    if a["small_stem"]:
        layers.append(dict(kind="conv", c_in=3, c_out=a["filters"][0],
                           kernel=(3, 3), out_hw=(hw, hw)))
    else:
        hw = (hw + 6 - 7) // 2 + 1
        layers.append(dict(kind="conv", c_in=3, c_out=a["filters"][0],
                           kernel=(7, 7), out_hw=(hw, hw)))
        hw = (hw + 2 - 3) // 2 + 1
    for name, c_in, c_out, stride, dim_match in _walk(config):
        out = (hw - 1) // stride + 1
        for cname, ci, co, k, s in _unit_convs(name, c_in, c_out, stride,
                                               dim_match, a["bottleneck"]):
            # only the strided conv and the shortcut change the size; convs
            # before the strided one run at the input size
            before = a["bottleneck"] and cname.endswith("_conv1")
            size = hw if before else out
            layers.append(dict(kind="conv", c_in=ci, c_out=co, kernel=(k, k),
                               out_hw=(size, size)))
        hw = out
    layers.append(dict(kind="dense", d_in=a["filters"][-1],
                       d_out=a["classes"]))
    return layers


def init_params(config, key):
    """Seeded (args, aux) made on the device in one jitted call: He-normal
    weights (std sqrt(2 / fan_in), the script's Xavier(gaussian, in, 2)),
    gammas 1, betas and biases 0, moving mean 0 and variance 1."""
    import jax
    import jax.numpy as jnp
    arg_shapes, aux_shapes = param_shapes(config)

    @jax.jit
    def make(key):
        args = {}
        keys = jax.random.split(key, len(arg_shapes))
        for k, (name, shape) in zip(keys, arg_shapes.items()):
            if name.endswith("_weight"):
                fan_in = int(np.prod(shape[1:]))
                args[name] = jax.random.normal(k, shape, jnp.float32) \
                    * np.float32(np.sqrt(2.0 / fan_in))
            elif name.endswith("_gamma"):
                args[name] = jnp.ones(shape, jnp.float32)
            else:
                args[name] = jnp.zeros(shape, jnp.float32)
        aux = {n: (jnp.ones(s, jnp.float32) if n.endswith("_var")
                   else jnp.zeros(s, jnp.float32))
               for n, s in aux_shapes.items()}
        return args, aux

    return make(key)


# --------------------------------------------------------------------------
# forward, loss, step
# --------------------------------------------------------------------------

def _round_to(x, dtype_name):
    """``x`` rounded through ``dtype_name`` and back; 8-bit floats are scaled
    per tensor to the type's range first."""
    import jax.numpy as jnp
    dt = jnp.dtype(dtype_name)
    if dt.itemsize > 1:
        return x.astype(dt).astype(x.dtype)
    scale = float(jnp.finfo(dt).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dt).astype(x.dtype) / scale


def _rounding(fwd, bwd):
    """Identity that rounds the value through ``fwd`` on the way forward and
    the cotangent through ``bwd`` on the way back (None: left alone)."""
    import jax

    @jax.custom_vjp
    def r(x):
        return _round_to(x, fwd) if fwd else x

    def r_fwd(x):
        return r(x), None

    def r_bwd(_, g):
        return ((_round_to(g, bwd) if bwd else g),)

    r.defvjp(r_fwd, r_bwd)
    return r


class Precision:
    """The float32 computation made to round as a lower precision would.

    ``bfloat16``: every activation and every cotangent is held in bfloat16 at
    each operation's boundary, as are the weights the convolutions see — what
    the configuration states (bf16 compute, float32 masters): the witness for
    what that precision alone costs. ``float8_matmul``: the nearest
    precision below as it is trained in, the control: that bfloat16 pipeline
    with every convolution's and the classifier's operands in e4m3 and the
    cotangent that reaches them in e5m2 (per tensor scaled)."""

    def __init__(self, name):
        if name not in ("bfloat16", "float8_matmul"):
            raise ValueError("no emulation of %r" % name)
        self.name = name
        self.act = self.matmul_out = _rounding("bfloat16", "bfloat16")
        self.operand = _rounding("bfloat16", None)
        if name == "float8_matmul":
            self.matmul_out = _rounding("bfloat16", "float8_e5m2")
            self.operand = _rounding("float8_e4m3fn", None)


def _act(prec, x):
    return x if prec is None else prec.act(x)


def _bn(x, gamma, beta, fix_gamma=False):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    if fix_gamma:
        gamma = jnp.ones_like(jax.lax.stop_gradient(gamma))
    return ((x - mean) * jax.lax.rsqrt(var + BN_EPS)
            * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1))


def _conv(x, w, stride, prec):
    import jax
    pad = (w.shape[2] - 1) // 2
    if prec is not None:
        x, w = prec.operand(x), prec.operand(w)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)
    return y if prec is None else prec.matmul_out(y)


def _unit(p, x, name, stride, dim_match, bottleneck, quant):
    import jax

    def relu(t):
        return _act(quant, jax.nn.relu(t))

    def bn(i, t):
        return _act(quant, _bn(t, p["%s_bn%d_gamma" % (name, i)],
                               p["%s_bn%d_beta" % (name, i)]))

    act1 = relu(bn(1, x))
    if bottleneck:
        t = _conv(act1, p[name + "_conv1_weight"], 1, quant)
        t = _conv(relu(bn(2, t)), p[name + "_conv2_weight"], stride, quant)
        t = _conv(relu(bn(3, t)), p[name + "_conv3_weight"], 1, quant)
    else:
        t = _conv(act1, p[name + "_conv1_weight"], stride, quant)
        t = _conv(relu(bn(2, t)), p[name + "_conv2_weight"], 1, quant)
    short = x if dim_match else _conv(act1, p[name + "_sc_weight"], stride,
                                      quant)
    return _act(quant, t + short)


def _run_unit(params, x, name, stride, dim_match, bottleneck, quant):
    import jax
    keys = [k for k in params if k.startswith(name + "_")]
    unit = jax.checkpoint(functools.partial(
        _unit, name=name, stride=stride, dim_match=dim_match,
        bottleneck=bottleneck, quant=quant))
    return unit({k: params[k] for k in keys}, x)


def logits_fn(config, params, x, quant=None):
    import jax
    import jax.numpy as jnp
    a = arch(config)
    x = _act(quant, _bn(_act(quant, x), params["bn_data_gamma"],
                        params["bn_data_beta"], fix_gamma=True))
    if a["small_stem"]:
        x = _conv(x, params["conv0_weight"], 1, quant)
    else:
        x = _conv(x, params["conv0_weight"], 2, quant)
        x = _act(quant, jax.nn.relu(_act(quant, _bn(
            x, params["bn0_gamma"], params["bn0_beta"]))))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
    # the first unit of a stage changes the shape; the rest are alike, so
    # they run as ONE scanned body over their stacked weights (same numbers,
    # a third of the program for the compiler)
    units = list(_walk(config))
    i = 0
    while i < len(units):
        name, _, _, stride, dim_match = units[i]
        x = _run_unit(params, x, name, stride, dim_match, a["bottleneck"],
                      quant)
        j = i + 1
        while j < len(units) and units[j][4]:
            j += 1
        rest = [u[0] for u in units[i + 1:j]]
        if rest:
            suffixes = [k[len(rest[0]):] for k in params
                        if k.startswith(rest[0] + "_")]
            stacked = {sfx: jnp.stack([params[n + sfx] for n in rest])
                       for sfx in suffixes}

            def body(x, p, _proto=rest[0]):
                p = {_proto + sfx: v for sfx, v in p.items()}
                return _run_unit(p, x, _proto, 1, True, a["bottleneck"],
                                 quant), None
            x, _ = jax.lax.scan(body, x, stacked)
        i = j
    x = _act(quant, jax.nn.relu(_act(quant, _bn(
        x, params["bn1_gamma"], params["bn1_beta"]))))
    x = _act(quant, jnp.mean(x, axis=(2, 3)))
    w = params["fc1_weight"]
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = jnp.dot(x, w.T, precision=jax.lax.Precision.HIGHEST)
    if quant is not None:
        y = quant.matmul_out(y)
    return _act(quant, y + params["fc1_bias"])


def loss_fn(config, params, x, y, quant=None):
    """Mean softmax cross-entropy over the rows given."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits_fn(config, params, x, quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _decayed(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def train_reference(config, hp, params0, batches, quant=None, rows=None):
    """Follow ``len(batches)`` SGD-momentum steps from ``params0``.

    Returns ``losses`` per step (host floats) and, leaf by leaf as host
    float32 arrays, ``grad1`` the first gradient as the optimizer gets it
    (mean-loss gradient plus weight decay) and ``change`` the parameters'
    change after the last step."""
    import jax
    import jax.numpy as jnp
    lr, mom_c, wd = (np.float32(hp["learning_rate"]),
                     np.float32(hp["momentum"]), np.float32(hp["wd"]))

    def step(params, mom, x, y):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(config, p, x, y, quant))(params)
        grads = {n: g + (wd * params[n] if _decayed(n) else 0.0)
                 for n, g in grads.items()}
        mom = {n: mom_c * mom[n] - lr * grads[n] for n in params}
        new = {n: params[n] + mom[n] for n in params}
        return loss, grads, new, mom

    step = jax.jit(step, donate_argnums=(1,))
    params0 = {n: jnp.asarray(v, jnp.float32) for n, v in params0.items()}
    params = params0
    mom = {n: jnp.zeros_like(v) for n, v in params0.items()}
    losses, grad1 = [], None
    for i, (x, y) in enumerate(batches):
        loss, grads, params, mom = step(
            params, mom, jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.int32))
        losses.append(float(loss))
        if i == 0:
            grad1 = jax.device_get(grads)
        del grads
    change = jax.device_get(jax.jit(
        lambda a, b: {n: a[n] - b[n] for n in a})(params, params0))
    return {"losses": losses, "grad1": grad1, "change": change}
