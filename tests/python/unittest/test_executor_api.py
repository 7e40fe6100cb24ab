"""Executor behaviors (reference: tests/python/unittest/test_executor.py):
bind/simple_bind surfaces, pre-allocated outputs, backward with head
gradients, grad_req add, reshape, shared-memory bind, output_dict."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError


def test_outputs_preallocated_at_bind():
    """exe.outputs exists (zeros of the right shape) before any forward —
    reference graph executors allocate outputs at bind time."""
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=4, name="fc")
    exe = y.simple_bind(mx.cpu(), x=(2, 3))
    assert len(exe.outputs) == 1
    assert exe.outputs[0].shape == (2, 4)
    assert (exe.outputs[0].asnumpy() == 0).all()


def test_bind_with_explicit_arrays():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a + b
    av = mx.nd.array([1.0, 2.0])
    bv = mx.nd.array([10.0, 20.0])
    exe = c.bind(mx.cpu(), {"a": av, "b": bv})
    out = exe.forward()[0].asnumpy()
    np.testing.assert_allclose(out, [11.0, 22.0])
    # re-forward with updated kwarg
    out = exe.forward(a=mx.nd.array([5.0, 5.0]))[0].asnumpy()
    np.testing.assert_allclose(out, [15.0, 25.0])


def test_backward_with_head_gradient():
    x = mx.sym.Variable("x")
    y = x * 3.0
    xv = mx.nd.array([1.0, 1.0, 1.0])
    gx = mx.nd.zeros((3,))
    exe = y.bind(mx.cpu(), {"x": xv}, args_grad={"x": gx})
    exe.forward(is_train=True)
    exe.backward(out_grads=mx.nd.array([1.0, 2.0, 4.0]))
    np.testing.assert_allclose(exe.grad_dict["x"].asnumpy(),
                               [3.0, 6.0, 12.0])


def test_grad_req_add_accumulates():
    x = mx.sym.Variable("x")
    y = mx.sym.sum(x * x)
    exe = x_exe = y.simple_bind(mx.cpu(), x=(3,), grad_req="add")
    exe.arg_dict["x"][:] = [1.0, 2.0, 3.0]
    for i in range(2):
        exe.forward(is_train=True)
        exe.backward()
    # dy/dx = 2x accumulated twice
    np.testing.assert_allclose(x_exe.grad_dict["x"].asnumpy(),
                               [4.0, 8.0, 12.0])


def test_output_dict_and_arg_dict():
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    exe = y.simple_bind(mx.cpu(), x=(1, 3))
    assert set(exe.arg_dict) == {"x", "fc_weight", "fc_bias"}
    exe.forward()
    assert list(exe.output_dict) == ["fc_output"]
    assert exe.output_dict["fc_output"].shape == (1, 2)


def test_executor_reshape():
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=4, name="fc")
    exe = y.simple_bind(mx.cpu(), x=(2, 3))
    exe.arg_dict["fc_weight"][:] = 0.5
    new_exe = exe.reshape(x=(8, 3))
    assert new_exe.arg_dict["x"].shape == (8, 3)
    # weights carried over
    assert (new_exe.arg_dict["fc_weight"].asnumpy() == 0.5).all()
    new_exe.forward()
    assert new_exe.outputs[0].shape == (8, 4)


def test_copy_params_from_validates():
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    exe = y.simple_bind(mx.cpu(), x=(1, 3))
    exe.copy_params_from({"fc_weight": mx.nd.ones((2, 3))})
    assert (exe.arg_dict["fc_weight"].asnumpy() == 1).all()
    with pytest.raises(MXNetError):
        exe.copy_params_from({"nope": mx.nd.ones((1,))})
    exe.copy_params_from({"nope": mx.nd.ones((1,))},
                         allow_extra_params=True)


def test_multi_output_executor():
    x = mx.sym.Variable("x")
    s = mx.sym.SliceChannel(x, num_outputs=3, axis=1, name="split")
    exe = s.simple_bind(mx.cpu(), x=(2, 6))
    assert len(exe.outputs) == 3
    exe.arg_dict["x"][:] = np.arange(12).reshape(2, 6).astype(np.float32)
    outs = exe.forward()
    assert all(o.shape == (2, 2) for o in outs)
    np.testing.assert_allclose(outs[1].asnumpy(), [[2, 3], [8, 9]])


def test_shared_weight_between_executors():
    """Two executors bound to the SAME NDArray see each other's updates
    (how BucketingModule shares weights across buckets)."""
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    w = mx.nd.ones((2, 3))
    b = mx.nd.zeros((2,))
    e1 = y.bind(mx.cpu(), {"x": mx.nd.ones((1, 3)), "fc_weight": w,
                           "fc_bias": b})
    e2 = y.bind(mx.cpu(), {"x": mx.nd.ones((4, 3)), "fc_weight": w,
                           "fc_bias": b})
    np.testing.assert_allclose(e1.forward()[0].asnumpy(), [[3.0, 3.0]])
    w[:] = 2.0  # mutate the shared buffer
    np.testing.assert_allclose(e2.forward()[0].asnumpy(),
                               np.full((4, 2), 6.0))


def test_held_output_reference_sees_forward_results():
    """Output NDArrays obtained before/between forwards track new values
    (reference bind-allocated outputs are written in place)."""
    x = mx.sym.Variable("x")
    y = x * 2.0
    exe = y.simple_bind(mx.cpu(), x=(2,))
    held = exe.outputs[0]          # pre-forward (zeros)
    assert (held.asnumpy() == 0).all()
    exe.arg_dict["x"][:] = [1.0, 3.0]
    exe.forward()
    np.testing.assert_allclose(held.asnumpy(), [2.0, 6.0])
    exe.arg_dict["x"][:] = [5.0, 5.0]
    exe.forward()
    np.testing.assert_allclose(held.asnumpy(), [10.0, 10.0])


def test_forward_moves_a_host_array_to_the_bound_device():
    """A default-context NDArray handed to an executor bound on another
    device is moved there (the reference copies into the bound array); jit
    refuses arguments committed to two devices — on a chip machine that is
    every `exe.forward(data=mx.nd.array(x))`."""
    import jax
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    exe = net.simple_bind(mx.tpu(0), grad_req="null", data=(2, 4))
    exe.arg_dict["fc_weight"][:] = 0.5
    x = np.arange(8, dtype=np.float32).reshape(2, 4)
    bound = mx.tpu(0).jax_device
    for value in (mx.nd.array(x, ctx=mx.cpu(1)),           # NDArray elsewhere
                  jax.device_put(x, mx.cpu(1).jax_device),  # jax.Array elsewhere
                  x):                                       # host numpy
        out = exe.forward(is_train=False, data=value)[0]
        assert exe.arg_dict["data"]._data.devices() == {bound}
        assert out._data.devices() == {bound}
        np.testing.assert_allclose(out.asnumpy(),
                                   x @ np.full((4, 3), 0.5, np.float32))
