"""A strided convolution over few input channels (an image network's stem) is
lowered as a stride-1 convolution over a space-to-depth input
(`ops/nn.py::_conv_space_to_depth`): the same products re-indexed, so value
and both gradients equal `lax.conv_general_dilated`'s, and its transpose
carries no `lhs_dilation`. The shape rule alone selects it; the profiler's
`lowering_counters()` counts each trace that takes it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.ops.nn import ConvParam, _convolution

RTOL = 1e-5


def _reference(x, w, b, stride, pad, dilate=(1, 1), groups=1):
    out = lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, preferred_element_type=jnp.float32)
    if b is not None:
        out = out + b.reshape((1, -1, 1, 1))
    return out


def _op(params):
    return lambda x, w, b=None: _convolution(params, x, w, b)


def _engaged(fn, *args):
    """Trace ``fn`` once (no run) and return how many of its convolutions
    took the space-to-depth form."""
    profiler.lowering_counters(reset=True)
    jax.eval_shape(fn, *args)
    return profiler.lowering_counters()["conv_space_to_depth"]


def _assert_close(a, b):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                               atol=RTOL * float(np.abs(np.asarray(b)).max()))


# (height, width, channels, kernel, stride, pad, bias)
_FORMS = {
    "stem_31": (31, 31, 3, 7, 2, 3, False),
    "stem_32": (32, 32, 3, 7, 2, 3, False),
    "stem_33": (33, 33, 3, 7, 2, 3, False),
    "stem_non_square": (30, 37, 3, 7, 2, 3, False),
    "stem_1_channel": (32, 32, 1, 7, 2, 3, False),
    "stem_4_channels": (32, 32, 4, 7, 2, 3, False),
    "stem_bias": (32, 32, 3, 7, 2, 3, True),
    "k3_s2_p1": (17, 17, 3, 3, 2, 1, False),
    "k3_s2_p1_bias": (16, 16, 3, 3, 2, 1, True),
    "k2_s2": (16, 16, 3, 2, 2, 0, False),
    "k2_s2_odd_input": (17, 15, 3, 2, 2, 0, True),
    "k5_s3_p2": (20, 22, 3, 5, 3, 2, False),
}


@pytest.mark.parametrize("case", sorted(_FORMS))
def test_space_to_depth_equals_the_strided_convolution(case):
    h, w, c, k, s, p, with_bias = _FORMS[case]
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, c, h, w)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((5, c, k, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(5), jnp.float32) if with_bias else None
    params = ConvParam(kernel=(k, k), stride=(s, s), pad=(p, p), num_filter=5,
                       no_bias=not with_bias)
    op = _op(params)
    got, want = op(x, wt, b), _reference(x, wt, b, (s, s), (p, p))
    assert got.shape == want.shape
    _assert_close(got, want)
    # the cotangent weighs every output position differently, so a tap
    # or a row out of place cannot cancel
    cot = jnp.asarray(rng.standard_normal(want.shape), jnp.float32)
    argnums = (0, 1, 2) if with_bias else (0, 1)
    g_got = jax.grad(lambda *a: jnp.vdot(op(*a), cot), argnums)(x, wt, b)
    g_want = jax.grad(lambda *a: jnp.vdot(_reference(*a, (s, s), (p, p)), cot),
                      argnums)(x, wt, b)
    for gg, gw in zip(g_got, g_want):
        assert gg.shape == gw.shape
        _assert_close(gg, gw)
    assert _engaged(op, x, wt, b) == 1


# (channels, kernel, stride, dilate, groups): each breaks one clause of the rule
_KEPT = {
    "64_channels": (64, 7, 2, 1, 1),
    "8_channels": (8, 7, 2, 1, 1),
    "stride_1": (3, 7, 1, 1, 1),
    "grouped": (4, 3, 2, 1, 2),
    "dilated": (3, 3, 2, 2, 1),
    "kernel_under_stride": (3, 1, 2, 1, 1),
}


@pytest.mark.parametrize("case", sorted(_KEPT))
def test_other_convolutions_keep_the_plain_lowering(case):
    c, k, s, d, g = _KEPT[case]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, c, 16, 16)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((4, c // g, k, k)), jnp.float32)
    params = ConvParam(kernel=(k, k), stride=(s, s), dilate=(d, d),
                       pad=(k // 2, k // 2), num_filter=4, num_group=g,
                       no_bias=True)
    op = _op(params)
    assert _engaged(op, x, wt) == 0
    _assert_close(op(x, wt), _reference(x, wt, None, (s, s), (k // 2, k // 2),
                                        (d, d), g))


def test_unequal_strides_and_1d_keep_the_plain_lowering():
    x = jnp.zeros((1, 3, 16, 16), jnp.float32)
    params = ConvParam(kernel=(3, 3), stride=(2, 1), num_filter=4, no_bias=True)
    assert _engaged(_op(params), x, jnp.zeros((4, 3, 3, 3))) == 0
    params = ConvParam(kernel=(5,), stride=(2,), num_filter=4, no_bias=True)
    assert _engaged(_op(params), jnp.zeros((1, 3, 32)),
                    jnp.zeros((4, 3, 5))) == 0


def _graph(sym, shapes):
    """The symbol's pure graph function and abstract arguments for it."""
    from mxnet_tpu.context import cpu
    from mxnet_tpu.executor import Executor
    from mxnet_tpu.ndarray.ndarray import zeros as nd_zeros
    from mxnet_tpu import random as _rnd
    names, auxn = sym.list_arguments(), sym.list_auxiliary_states()
    arg_shapes, _, aux_shapes = sym.infer_shape(**shapes)
    ashape = dict(zip(names, arg_shapes))
    runner = Executor(sym, cpu(), {n: nd_zeros(ashape[n]) for n in names}, {},
                      "null", {n: nd_zeros(s) for n, s in zip(auxn, aux_shapes)})
    key = _rnd.fixed_key()

    def run(args, aux):
        return runner._run_graph(args, aux, key, True)

    sds = jax.ShapeDtypeStruct
    return (run, {n: sds(ashape[n], jnp.float32) for n in names},
            {n: sds(s, jnp.float32) for n, s in zip(auxn, aux_shapes)})


def test_resnet50_lowers_exactly_its_stem_through_space_to_depth():
    from mxnet_tpu.models.resnet import get_symbol
    sym = get_symbol(num_classes=1000, num_layers=50, image_shape="3,224,224")
    run, args, aux = _graph(sym, {"data": (2, 3, 224, 224),
                                  "softmax_label": (2,)})
    assert _engaged(run, args, aux) == 1


def _conv_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conv_eqns(sub)


def test_stem_gradient_has_no_lhs_dilated_convolution():
    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(data, fix_gamma=True, eps=2e-5, name="bn_data")
    net = mx.sym.Convolution(net, num_filter=8, kernel=(7, 7), stride=(2, 2),
                             pad=(3, 3), no_bias=True, name="conv0")
    run, args, aux = _graph(net, {"data": (2, 3, 32, 32)})
    data_sds = args.pop("data")

    def loss(params, data, aux):
        outs, _ = run({**params, "data": data}, aux)
        return sum(jnp.sum(o) for o in outs)

    closed = jax.make_jaxpr(jax.grad(loss))(args, data_sds, aux)
    convs = list(_conv_eqns(closed.jaxpr))
    assert len(convs) == 3          # forward, input gradient, weight gradient
    for eqn in convs:
        assert tuple(eqn.params["lhs_dilation"]) == (1, 1), eqn
        assert tuple(eqn.params["window_strides"]) == (1, 1), eqn


def test_lowering_counters_reset():
    profiler.record_lowering("conv_space_to_depth")
    assert profiler.lowering_counters(reset=True)["conv_space_to_depth"] >= 1
    assert profiler.lowering_counters() == {"conv_space_to_depth": 0,
                                            "latent_heads_major": 0}
