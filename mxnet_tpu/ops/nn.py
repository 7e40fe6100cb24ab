"""Neural-network ops (reference: src/operator/nn/*, src/operator/rnn-inl.h).

TPU-native: convs/matmuls go straight to `lax.conv_general_dilated` / `jnp.dot`
so XLA tiles them onto the MXU; normalization/activation stay as jnp elementwise
(XLA fuses them into neighbors). The fused RNN op is a `lax.scan` over time —
the compiler-friendly TPU formulation of the reference's cuDNN RNN kernels.
Loss-layer ops (SoftmaxOutput family) use `jax.custom_vjp` to reproduce the
reference semantics where backward emits its own gradient; the head
cotangent enters multiplicatively so seeds-of-ones stay bitwise reference
and the supervised loss-scale seed reaches the chain
(reference: src/operator/softmax_output-inl.h).
"""
from __future__ import annotations

import contextlib
import contextvars

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .. import profiler
from ..base import Params, param_field, np_dtype, MXNetError
from .registry import register_op

# ---------------------------------------------------------------------------
# Supervised loss-scale plumbing for IMPLICIT loss sites. Loss heads get
# the scale through their cotangent seed (see _loss_op), but an op that
# injects a gradient mid-chain with no head above it (e.g.
# IdentityAttachKLSparseReg's sparsity penalty) has no seed to carry it —
# without the multiply, the supervised step's post-backward unscale would
# silently divide that gradient by the scale. The supervised fused step
# (parallel/tpu_step.py) traces its backward with this set to the TRACED
# scale scalar; None (every other trace) keeps the op bitwise unchanged.
# ---------------------------------------------------------------------------
_loss_grad_scale = contextvars.ContextVar("mx_loss_grad_scale", default=None)


def current_loss_grad_scale():
    """The traced loss-scale scalar of an in-progress supervised backward
    trace, or None. Read by implicit-loss vjp rules at trace time."""
    return _loss_grad_scale.get()


@contextlib.contextmanager
def loss_grad_scale_scope(scale):
    token = _loss_grad_scale.set(scale)
    try:
        yield
    finally:
        _loss_grad_scale.reset(token)

# ---------------------------------------------------------------------------
# FullyConnected (nn/fully_connected.cc:228-309)
# ---------------------------------------------------------------------------


class FCParam(Params):
    num_hidden = param_field(int, required=True)
    no_bias = param_field(bool, default=False)
    flatten = param_field(bool, default=True)


def _fc_inputs(p):
    if p is not None and p.no_bias:
        return ("data", "weight")
    return ("data", "weight", "bias")


@register_op("FullyConnected", param_cls=FCParam, input_names=_fc_inputs)
def _fully_connected(params, x, weight, bias=None):
    if params.flatten and x.ndim > 2:
        x = x.reshape((x.shape[0], -1))
    y = jnp.dot(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (nn/convolution.cc, nn/deconvolution.cc)
# ---------------------------------------------------------------------------


class ConvParam(Params):
    kernel = param_field(tuple, required=True)
    stride = param_field(tuple, default=())
    dilate = param_field(tuple, default=())
    pad = param_field(tuple, default=())
    num_filter = param_field(int, required=True)
    num_group = param_field(int, default=1)
    no_bias = param_field(bool, default=False)
    workspace = param_field(int, default=1024)
    cudnn_tune = param_field(str, default=None)
    cudnn_off = param_field(bool, default=False)
    layout = param_field(str, default=None)


def _conv_inputs(p):
    if p is not None and p.no_bias:
        return ("data", "weight")
    return ("data", "weight", "bias")


def _conv_tuples(params, nd):
    stride = params.stride or (1,) * nd
    dilate = params.dilate or (1,) * nd
    pad = params.pad or (0,) * nd
    return stride, dilate, pad


def _space_to_depth_stride(params, x, stride, dilate):
    """The stride ``s`` of a 2-d convolution that is lowered through
    space-to-depth, else 0: ungrouped and undilated, equal strides ``s >= 2``,
    a kernel of at least ``s`` on both axes, and fewer input channels than
    one sublane tile (8), as an image network's stem on RGB input. Its
    transpose then needs no ``lhs_dilation`` (three of four products on
    inserted zeros at ``s`` 2) and its channels fill four times the tile."""
    s = stride[0]
    if (len(params.kernel) != 2 or params.num_group != 1
            or any(d != 1 for d in dilate) or s < 2 or stride[1] != s
            or min(params.kernel) < s or x.shape[1] >= 8):
        return 0
    return s


def _to_depth(a, s):
    """``[N, C, s*H, s*W] -> [N, C*s*s, H, W]``, channels ordered (c, dy, dx)."""
    n, c, h, w = a.shape
    a = a.reshape((n, c, h // s, s, w // s, s)).transpose((0, 1, 3, 5, 2, 4))
    return a.reshape((n, c * s * s, h // s, w // s))


def _conv_space_to_depth(x, weight, s, pad, preferred):
    """A stride-``s`` convolution as a stride-1 one over ``s*s`` times the
    channels: the same products re-indexed, autodiff giving stride-1
    gradients. On each axis the kernel takes zero taps in front, as many as
    round the pad ``p`` up to a multiple of ``s``, and behind, up to a
    multiple of ``s``; so the input splits into whole blocks as it is (zero
    rows are appended only to a size that is no multiple of ``s``) and the
    padding, in blocks, stays inside the convolution. The input gradient is
    then one convolution of the input's own blocks, with no pad, crop or
    re-indexing between it and a reduction over it."""
    pads, grow, taps = [], [], []
    for size, k, p in zip(x.shape[2:], weight.shape[2:], pad):
        front = -(-p // s) * s - p
        kb = -(-(front + k) // s)                # kernel blocks
        blocks = -(-size // s)
        lo = (p + front) // s
        out = (size + 2 * p - k) // s + 1
        pads.append((lo, out - 1 + kb - blocks - lo))
        grow.append((0, blocks * s - size))
        taps.append((front, kb * s - front - k))
    if any(g for _, g in grow):
        x = jnp.pad(x, ((0, 0), (0, 0)) + tuple(grow))
    weight = jnp.pad(weight, ((0, 0), (0, 0)) + tuple(taps))
    # one split input for the forward and the weight gradient: without the
    # barrier XLA folds the split back into the weight gradient's operand,
    # which is then the strided correlation over C channels again (on a
    # v5e, ResNet-50's stem at batch 256: 1.82 ms against 1.14)
    x = lax.optimization_barrier(_to_depth(x, s))
    return lax.conv_general_dilated(
        x, _to_depth(weight, s), window_strides=(1, 1),
        padding=pads, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=preferred)


@register_op("Convolution", param_cls=ConvParam, input_names=_conv_inputs)
def _convolution(params, x, weight, bias=None):
    nd = len(params.kernel)
    stride, dilate, pad = _conv_tuples(params, nd)
    if nd == 1:  # run 1D conv as 2D with unit height (XLA handles both; keeps one path)
        x = x[:, :, None, :]
        weight = weight[:, :, None, :]
        stride, dilate, pad = (1,) + tuple(stride), (1,) + tuple(dilate), (0,) + tuple(pad)
        nd = 2
    preferred = jnp.float32 if x.dtype == jnp.float32 else None
    s = _space_to_depth_stride(params, x, stride, dilate)
    if s:
        profiler.record_lowering("conv_space_to_depth")
        out = _conv_space_to_depth(x, weight, s, pad, preferred)
    else:
        dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                        ("NCHW", "OIHW", "NCHW") if nd == 2 else
                                        ("NCDHW", "OIDHW", "NCDHW"))
        out = lax.conv_general_dilated(
            x, weight, window_strides=tuple(stride),
            padding=[(p, p) for p in pad],
            rhs_dilation=tuple(dilate),
            dimension_numbers=dn,
            feature_group_count=params.num_group,
            preferred_element_type=preferred)
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * (out.ndim - 2))
    if len(params.kernel) == 1:
        out = out[:, :, 0, :]
    return out


class DeconvParam(ConvParam):
    adj = param_field(tuple, default=())
    target_shape = param_field(tuple, default=())


@register_op("Deconvolution", param_cls=DeconvParam, input_names=_conv_inputs)
def _deconvolution(params, x, weight, bias=None):
    nd = len(params.kernel)
    if nd != 2:
        raise NotImplementedError("Deconvolution only supports 2D kernels for now")
    stride, dilate, pad = _conv_tuples(params, nd)
    adj = params.adj or (0,) * nd
    # weight layout (C_in, F/num_group, kh, kw) as in the reference; transposed conv =
    # conv with lhs dilation and flipped kernels.
    g = params.num_group
    cin, fpg, kh, kw = weight.shape
    w = weight.reshape((g, cin // g, fpg, kh, kw))
    w = jnp.flip(w, axis=(-1, -2)).transpose((0, 2, 1, 3, 4)).reshape(
        (g * fpg, cin // g, kh, kw))
    pads = [(params.kernel[i] - 1 - pad[i] + (params.kernel[i] - 1) * (dilate[i] - 1),
             params.kernel[i] - 1 - pad[i] + (params.kernel[i] - 1) * (dilate[i] - 1)
             + adj[i]) for i in range(nd)]
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pads,
        lhs_dilation=tuple(stride), rhs_dilation=tuple(dilate),
        dimension_numbers=dn, feature_group_count=g)
    if bias is not None:
        out = out + bias.reshape((1, -1, 1, 1))
    return out


# ---------------------------------------------------------------------------
# Pooling (nn/pooling.cc)
# ---------------------------------------------------------------------------


class PoolParam(Params):
    kernel = param_field(tuple, default=())
    pool_type = param_field(str, default="max", enum=("max", "avg", "sum"))
    global_pool = param_field(bool, default=False)
    stride = param_field(tuple, default=())
    pad = param_field(tuple, default=())
    pooling_convention = param_field(str, default="valid", enum=("valid", "full"))
    cudnn_off = param_field(bool, default=False)


@register_op("Pooling", param_cls=PoolParam)
def _pooling(params, x):
    spatial = x.ndim - 2
    if params.global_pool:
        axes = tuple(range(2, x.ndim))
        if params.pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        if params.pool_type == "sum":
            return jnp.sum(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    kernel = params.kernel
    stride = params.stride or (1,) * spatial
    pad = params.pad or (0,) * spatial
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if params.pooling_convention == "full":
        # ceil output size: pad extra on the right where needed
        for i in range(spatial):
            size = x.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
            pads[2 + i] = (pad[i], pad[i] + extra)
    # init values must be CONCRETE scalars (np, not jnp): a traced init defeats
    # jax's monoid matching and reduce_window falls back to the generic,
    # non-differentiable reduce_window_p under jit+vjp.
    if params.pool_type == "max":
        init = -_np.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, _np.asarray(init, x.dtype), lax.max,
                                 window, strides, pads)
    summed = lax.reduce_window(x, _np.asarray(0, x.dtype), lax.add, window, strides, pads)
    if params.pool_type == "sum":
        return summed
    return summed / float(_np.prod(kernel))


# ---------------------------------------------------------------------------
# Activations (nn/activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------


class ActivationParam(Params):
    act_type = param_field(str, required=True,
                           enum=("relu", "sigmoid", "tanh", "softrelu", "softsign"))


_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": lambda x: jnp.logaddexp(x, 0.0),
    "softsign": jax.nn.soft_sign,
}


@register_op("Activation", param_cls=ActivationParam)
def _activation(params, x):
    return _ACTS[params.act_type](x)


class LeakyReLUParam(Params):
    act_type = param_field(str, default="leaky",
                           enum=("leaky", "prelu", "elu", "selu", "rrelu", "gelu"))
    slope = param_field(float, default=0.25)
    lower_bound = param_field(float, default=0.125)
    upper_bound = param_field(float, default=0.334)


def _lrelu_inputs(p):
    if p is not None and p.act_type == "prelu":
        return ("data", "gamma")
    return ("data",)


@register_op("LeakyReLU", param_cls=LeakyReLUParam, input_names=_lrelu_inputs,
             need_rng=True, need_train=True)
def _leaky_relu(params, x, gamma=None, is_train=False, rng=None):
    t = params.act_type
    if t == "leaky":
        return jnp.where(x > 0, x, params.slope * x)
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if t == "elu":
        return jnp.where(x > 0, x, params.slope * (jnp.exp(x) - 1.0))
    if t == "selu":
        return 1.0507009873554805 * jnp.where(
            x > 0, x, 1.6732632423543772 * (jnp.exp(x) - 1.0))
    if t == "gelu":
        return jax.nn.gelu(x)
    # rrelu: random slope in train, mean slope in test
    if is_train and rng is not None:
        slope = jax.random.uniform(rng, x.shape, minval=params.lower_bound,
                                   maxval=params.upper_bound, dtype=x.dtype)
    else:
        slope = (params.lower_bound + params.upper_bound) / 2.0
    return jnp.where(x > 0, x, slope * x)


# ---------------------------------------------------------------------------
# softmax family (nn/softmax.cc)
# ---------------------------------------------------------------------------


class SoftmaxParam(Params):
    axis = param_field(int, default=-1)
    temperature = param_field(float, default=None)


@register_op("softmax", param_cls=SoftmaxParam)
def _softmax(params, x):
    if params.temperature:
        x = x / params.temperature
    return jax.nn.softmax(x, axis=params.axis)


@register_op("log_softmax", param_cls=SoftmaxParam)
def _log_softmax(params, x):
    if params.temperature:
        x = x / params.temperature
    return jax.nn.log_softmax(x, axis=params.axis)


class SoftmaxActivationParam(Params):
    mode = param_field(str, default="instance", enum=("instance", "channel"))


@register_op("SoftmaxActivation", param_cls=SoftmaxActivationParam)
def _softmax_activation(params, x):
    axis = 1 if params.mode == "channel" else -1
    if params.mode == "instance" and x.ndim > 2:
        x2 = x.reshape((x.shape[0], -1))
        return jax.nn.softmax(x2, axis=-1).reshape(x.shape)
    return jax.nn.softmax(x, axis=axis)


# ---------------------------------------------------------------------------
# normalization (nn/batch_norm.cc, nn/layer_norm.cc, instance_norm.cc,
# l2_normalization.cc, nn/lrn.cc)
# ---------------------------------------------------------------------------


class BatchNormParam(Params):
    eps = param_field(float, default=1e-3)
    momentum = param_field(float, default=0.9)
    fix_gamma = param_field(bool, default=True)
    use_global_stats = param_field(bool, default=False)
    output_mean_var = param_field(bool, default=False)
    axis = param_field(int, default=1)
    cudnn_off = param_field(bool, default=False)


@register_op("BatchNorm", param_cls=BatchNormParam,
             input_names=("data", "gamma", "beta"),
             aux_names=("moving_mean", "moving_var"),
             num_outputs=lambda p: 3 if (p and p.output_mean_var) else 1,
             need_train=True)
def _batch_norm(params, x, gamma, beta, moving_mean, moving_var, is_train=False):
    ax = params.axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != ax)
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    if params.fix_gamma:
        gamma = jnp.ones_like(lax.stop_gradient(gamma))
    use_batch_stats = is_train and not params.use_global_stats
    if use_batch_stats:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        new_mean = moving_mean * params.momentum + lax.stop_gradient(mean) * (1 - params.momentum)
        new_var = moving_var * params.momentum + lax.stop_gradient(var) * (1 - params.momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + params.eps)
    out = ((x.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape)
           * gamma.astype(jnp.float32).reshape(bshape)
           + beta.astype(jnp.float32).reshape(bshape)).astype(x.dtype)
    if params.output_mean_var:
        return out, mean, inv, new_mean, new_var
    return out, new_mean, new_var


class LayerNormParam(Params):
    axis = param_field(int, default=-1)
    eps = param_field(float, default=1e-5)
    output_mean_var = param_field(bool, default=False)


@register_op("LayerNorm", param_cls=LayerNormParam,
             input_names=("data", "gamma", "beta"),
             num_outputs=lambda p: 3 if (p and p.output_mean_var) else 1)
def _layer_norm(params, x, gamma, beta):
    ax = params.axis % x.ndim
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=ax, keepdims=True)
    var = jnp.var(xf, axis=ax, keepdims=True)
    inv = lax.rsqrt(var + params.eps)
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    out = ((xf - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)).astype(x.dtype)
    if params.output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(inv, ax)
    return out


class InstanceNormParam(Params):
    eps = param_field(float, default=1e-3)


@register_op("InstanceNorm", param_cls=InstanceNormParam,
             input_names=("data", "gamma", "beta"))
def _instance_norm(params, x, gamma, beta):
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return ((x - mean) * lax.rsqrt(var + params.eps) * gamma.reshape(bshape)
            + beta.reshape(bshape))


class L2NormParam(Params):
    eps = param_field(float, default=1e-10)
    mode = param_field(str, default="instance", enum=("instance", "channel", "spatial"))


@register_op("L2Normalization", param_cls=L2NormParam)
def _l2_normalization(params, x):
    if params.mode == "instance":
        red = tuple(range(1, x.ndim))
        kd = True
    elif params.mode == "channel":
        red = (1,)
        kd = True
    else:  # spatial
        red = tuple(range(2, x.ndim))
        kd = True
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=kd) + params.eps)
    return x / norm


class LRNParam(Params):
    alpha = param_field(float, default=1e-4)
    beta = param_field(float, default=0.75)
    knorm = param_field(float, default=2.0)
    nsize = param_field(int, required=True)


@register_op("LRN", param_cls=LRNParam)
def _lrn(params, x):
    sq = jnp.square(x)
    half = params.nsize // 2
    pad = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    acc = jnp.zeros_like(x)
    for i in range(params.nsize):
        acc = acc + lax.dynamic_slice_in_dim(pad, i, x.shape[1], axis=1)
    scale = jnp.power(params.knorm + params.alpha * acc / params.nsize, -params.beta)
    return x * scale


# ---------------------------------------------------------------------------
# Dropout (nn/dropout.cc)
# ---------------------------------------------------------------------------


class DropoutParam(Params):
    p = param_field(float, default=0.5)
    mode = param_field(str, default="training", enum=("training", "always"))
    axes = param_field(tuple, default=())


@register_op("Dropout", param_cls=DropoutParam, need_rng=True, need_train=True)
def _dropout(params, x, is_train=False, rng=None):
    if params.p <= 0 or (not is_train and params.mode != "always") or rng is None:
        return x
    keep = 1.0 - params.p
    shape = x.shape
    if params.axes:
        shape = tuple(1 if i in params.axes else s for i, s in enumerate(shape))
    mask = jax.random.bernoulli(rng, keep, shape)
    return jnp.where(mask, x / keep, jnp.zeros((), x.dtype)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding (tensor/indexing_op.cc Embedding)
# ---------------------------------------------------------------------------


class EmbeddingParam(Params):
    input_dim = param_field(int, required=True)
    output_dim = param_field(int, required=True)
    dtype = param_field(str, default="float32")
    sparse_grad = param_field(bool, default=False)


@register_op("Embedding", param_cls=EmbeddingParam, input_names=("data", "weight"))
def _embedding(params, data, weight):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# UpSampling (upsampling.cc)
# ---------------------------------------------------------------------------


class UpSamplingParam(Params):
    scale = param_field(int, required=True)
    sample_type = param_field(str, default="nearest", enum=("nearest", "bilinear"))
    num_args = param_field(int, default=1)
    num_filter = param_field(int, default=0)
    multi_input_mode = param_field(str, default="concat")


@register_op("UpSampling", param_cls=UpSamplingParam, key_var_num_args="num_args",
             input_names=lambda p: tuple("arg%d" % i
                                         for i in range((p.num_args if p else 1))))
def _upsampling(params, *args):
    x = args[0]
    s = params.scale
    if params.sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
    else:
        out = jax.image.resize(x, x.shape[:2] + (x.shape[2] * s, x.shape[3] * s),
                               method="bilinear")
    return out


# ---------------------------------------------------------------------------
# Loss-layer ops with reference backward semantics (emit their own gradient;
# the head cotangent — ones everywhere but the loss-scaled supervised step —
# enters multiplicatively)
# ---------------------------------------------------------------------------


def _loss_op(forward, backward_grad):
    """Build a custom-vjp fn: forward(data, label) -> out;
    d(data) = backward_grad(data, label) * g (reference loss layers emit
    their own gradient; the head cotangent enters MULTIPLICATIVELY).
    Every standard backward seeds ones, so `* g` is a bitwise identity —
    the multiply exists so the supervised fused step's loss-scale seed
    (resilience/supervisor.py, a power of two) actually reaches the
    backward chain instead of dying at the head."""

    @jax.custom_vjp
    def op(data, label):
        return forward(data, label)

    def fwd(data, label):
        return forward(data, label), (data, label)

    def bwd(res, g):
        data, label = res
        return ((backward_grad(data, label) * g).astype(data.dtype),
                jnp.zeros_like(label))

    op.defvjp(fwd, bwd)
    return op


class SoftmaxOutputParam(Params):
    grad_scale = param_field(float, default=1.0)
    ignore_label = param_field(float, default=-1.0)
    multi_output = param_field(bool, default=False)
    use_ignore = param_field(bool, default=False)
    preserve_shape = param_field(bool, default=False)
    normalization = param_field(str, default="null", enum=("null", "batch", "valid"))
    out_grad = param_field(bool, default=False)
    smooth_alpha = param_field(float, default=0.0)


def _softmax_output_impl(params):
    def forward(data, label):
        if params.multi_output or data.ndim > 2:
            return jax.nn.softmax(data, axis=1)
        return jax.nn.softmax(data, axis=-1)

    def backward_grad(data, label):
        if params.multi_output or data.ndim > 2:
            prob = jax.nn.softmax(data, axis=1)
            lab = label.astype(jnp.int32)
            oh = jnp.moveaxis(jax.nn.one_hot(lab, data.shape[1], dtype=prob.dtype), -1, 1)
            grad = prob - oh
            valid = jnp.ones(lab.shape, prob.dtype)
            if params.use_ignore:
                valid = (lab != int(params.ignore_label)).astype(prob.dtype)
                grad = grad * jnp.expand_dims(valid, 1)
        else:
            prob = jax.nn.softmax(data, axis=-1)
            lab = label.astype(jnp.int32)
            oh = jax.nn.one_hot(lab, data.shape[-1], dtype=prob.dtype)
            grad = prob - oh
            valid = jnp.ones(lab.shape, prob.dtype)
            if params.use_ignore:
                valid = (lab != int(params.ignore_label)).astype(prob.dtype)
                grad = grad * valid[..., None]
        if params.normalization == "batch":
            grad = grad / data.shape[0]
        elif params.normalization == "valid":
            grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
        return grad * params.grad_scale

    return forward, backward_grad


@register_op("SoftmaxOutput", aliases=("Softmax",), param_cls=SoftmaxOutputParam,
             input_names=("data", "label"))
def _softmax_output(params, data, label):
    fwd, bwd = _softmax_output_impl(params)
    return _loss_op(fwd, bwd)(data, label)


class RegOutputParam(Params):
    grad_scale = param_field(float, default=1.0)


@register_op("LinearRegressionOutput", param_cls=RegOutputParam,
             input_names=("data", "label"))
def _linear_regression_output(params, data, label):
    return _loss_op(
        lambda d, l: d,
        lambda d, l: (d - l.reshape(d.shape)) * params.grad_scale / d.shape[0])(data, label)


@register_op("MAERegressionOutput", param_cls=RegOutputParam,
             input_names=("data", "label"))
def _mae_regression_output(params, data, label):
    return _loss_op(
        lambda d, l: d,
        lambda d, l: jnp.sign(d - l.reshape(d.shape)) * params.grad_scale / d.shape[0])(
            data, label)


@register_op("LogisticRegressionOutput", param_cls=RegOutputParam,
             input_names=("data", "label"))
def _logistic_regression_output(params, data, label):
    return _loss_op(
        lambda d, l: jax.nn.sigmoid(d),
        lambda d, l: (jax.nn.sigmoid(d) - l.reshape(d.shape)) * params.grad_scale
        / d.shape[0])(data, label)


class SVMOutputParam(Params):
    margin = param_field(float, default=1.0)
    regularization_coefficient = param_field(float, default=1.0)
    use_linear = param_field(bool, default=False)


@register_op("SVMOutput", param_cls=SVMOutputParam, input_names=("data", "label"))
def _svm_output(params, data, label):
    def bwd(d, l):
        lab = jax.nn.one_hot(l.astype(jnp.int32), d.shape[-1], dtype=d.dtype) * 2 - 1
        margin_viol = (params.margin - lab * d) > 0
        if params.use_linear:
            g = jnp.where(margin_viol, -lab, 0.0)
        else:
            g = jnp.where(margin_viol, -2 * (params.margin - lab * d) * lab, 0.0)
        return g * params.regularization_coefficient

    return _loss_op(lambda d, l: d, bwd)(data, label)


class MakeLossParam(Params):
    grad_scale = param_field(float, default=1.0)
    valid_thresh = param_field(float, default=0.0)
    normalization = param_field(str, default="null", enum=("null", "batch", "valid"))


@register_op("MakeLoss", param_cls=MakeLossParam)
def _make_loss_op(params, data):
    """Forward identity; backward seeds grad_scale (reference: make_loss.cc)."""

    @jax.custom_vjp
    def op(d):
        return d

    def fwd(d):
        return d, d

    def bwd(d, g):
        # * g: ones in every standard backward (bitwise identity); the
        # supervised loss-scale seed must reach the chain (see _loss_op)
        scale = params.grad_scale
        if params.normalization == "batch":
            scale = scale / d.shape[0]
        elif params.normalization == "valid":
            valid = jnp.maximum(jnp.sum((d > params.valid_thresh).astype(jnp.float32)), 1.0)
            return (jnp.full(d.shape, params.grad_scale, d.dtype) / valid * g,)
        return (jnp.full(d.shape, scale, d.dtype) * g,)

    op.defvjp(fwd, bwd)
    return op(data)


# ---------------------------------------------------------------------------
# Fused RNN (rnn-inl.h; cuDNN path cudnn_rnn-inl.h) — lax.scan formulation
# ---------------------------------------------------------------------------


class RNNParam(Params):
    state_size = param_field(int, required=True)
    num_layers = param_field(int, required=True)
    bidirectional = param_field(bool, default=False)
    mode = param_field(str, required=True, enum=("rnn_relu", "rnn_tanh", "lstm", "gru"))
    p = param_field(float, default=0.0)
    state_outputs = param_field(bool, default=False)
    lstm_state_clip_min = param_field(float, default=None)
    lstm_state_clip_max = param_field(float, default=None)


def _rnn_inputs(p):
    if p is not None and p.mode == "lstm":
        return ("data", "parameters", "state", "state_cell")
    return ("data", "parameters", "state")


def _rnn_n_outputs(p):
    if p is None:
        return 1
    if not p.state_outputs:
        return 1
    return 3 if p.mode == "lstm" else 2


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    """Total packed parameter count — packing: all weights (layer-major,
    direction-minor: i2h then h2h), then all biases (same order)."""
    g = _gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        ins = input_size if layer == 0 else state_size * d
        size += d * g * state_size * (ins + state_size)     # weights
    size += num_layers * d * 2 * g * state_size             # biases
    return size


def _unpack_rnn_params(flat, mode, input_size, state_size, num_layers, bidirectional):
    g = _gates(mode)
    d = 2 if bidirectional else 1
    H = state_size
    layers = []
    off = 0
    for layer in range(num_layers):
        ins = input_size if layer == 0 else H * d
        dirs = []
        for _ in range(d):
            wi = flat[off:off + g * H * ins].reshape((g * H, ins)); off += g * H * ins
            wh = flat[off:off + g * H * H].reshape((g * H, H)); off += g * H * H
            dirs.append([wi, wh, None, None])
        layers.append(dirs)
    for layer in range(num_layers):
        for dd in range(d):
            layers[layer][dd][2] = flat[off:off + g * H]; off += g * H
            layers[layer][dd][3] = flat[off:off + g * H]; off += g * H
    return layers


def _rnn_cell_step(mode, H):
    if mode == "lstm":
        def step(carry, xw, wh, bh):
            h, c = carry
            gates = xw + jnp.dot(h, wh.T) + bh
            i, f, gg, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            c = f * c + i * jnp.tanh(gg)
            h = o * jnp.tanh(c)
            return (h, c), h
    elif mode == "gru":
        def step(carry, xw, wh, bh):
            (h,) = carry
            xr, xz, xn = jnp.split(xw, 3, axis=-1)
            hr, hz, hn = jnp.split(jnp.dot(h, wh.T) + bh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h = (1 - z) * n + z * h
            return (h,), h
    else:
        act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

        def step(carry, xw, wh, bh):
            (h,) = carry
            h = act(xw + jnp.dot(h, wh.T) + bh)
            return (h,), h
    return step


def _run_rnn_layer(mode, x, wi, wh, bi, bh, h0, c0, reverse=False):
    """x: (T, N, I); returns (out (T,N,H), h_T, c_T)."""
    H = h0.shape[-1]
    step = _rnn_cell_step(mode, H)
    xw = jnp.dot(x, wi.T) + bi  # (T, N, G*H) — one big MXU matmul over all steps

    def scan_fn(carry, xw_t):
        carry, out = step(carry, xw_t, wh, bh)
        return carry, out

    carry0 = (h0, c0) if mode == "lstm" else (h0,)
    carry, outs = lax.scan(scan_fn, carry0, xw, reverse=reverse)
    if mode == "lstm":
        return outs, carry[0], carry[1]
    return outs, carry[0], None


@register_op("RNN", param_cls=RNNParam, input_names=_rnn_inputs,
             num_outputs=_rnn_n_outputs, need_train=True, need_rng=True)
def _rnn(params, data, parameters, state, state_cell=None, is_train=False, rng=None):
    """data: (T, N, I); state: (L*D, N, H). reference: src/operator/rnn-inl.h."""
    mode, H = params.mode, params.state_size
    L, d = params.num_layers, (2 if params.bidirectional else 1)
    layers = _unpack_rnn_params(parameters, mode, data.shape[-1], H, L, params.bidirectional)
    x = data
    h_states, c_states = [], []
    for li, dirs in enumerate(layers):
        outs = []
        for di, (wi, wh, bi, bh) in enumerate(dirs):
            sidx = li * d + di
            h0 = state[sidx]
            c0 = state_cell[sidx] if state_cell is not None else None
            o, hT, cT = _run_rnn_layer(mode, x, wi, wh, bi, bh, h0, c0, reverse=(di == 1))
            outs.append(o)
            h_states.append(hT)
            if cT is not None:
                c_states.append(cT)
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        if params.p > 0 and is_train and li < L - 1 and rng is not None:
            rng, sub = jax.random.split(rng)
            mask = jax.random.bernoulli(sub, 1.0 - params.p, x.shape)
            x = jnp.where(mask, x / (1.0 - params.p), 0.0).astype(x.dtype)
    out = x
    if not params.state_outputs:
        return out
    hs = jnp.stack(h_states, axis=0)
    if mode == "lstm":
        return out, hs, jnp.stack(c_states, axis=0)
    return out, hs
