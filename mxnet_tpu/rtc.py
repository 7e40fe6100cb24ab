"""Runtime kernel compilation (reference: python/mxnet/rtc.py CudaModule over
NVRTC, src/common/rtc.cc:35).

TPU-native analog: runtime-registered **Pallas** kernels. `PallasModule`
wraps user kernel functions into launchable ops (VMEM-blocked `pallas_call`),
and `register_pallas_op` exposes a kernel through the full op registry so it
works from `mx.nd` / `mx.sym` like any built-in.

`CudaModule` is kept as an API shim that raises with guidance — CUDA C++
source has no TPU backend.
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .ops.registry import register_op

__all__ = ["CudaModule", "PallasModule", "register_pallas_op"]


class CudaModule(object):
    def __init__(self, source, options=(), exports=()):
        raise MXNetError(
            "CudaModule compiles CUDA C++ and has no TPU backend; write the "
            "kernel as a Pallas function and wrap it with mx.rtc.PallasModule "
            "(see mxnet_tpu/kernels/flash_attention.py for the pattern)")


class PallasKernel(object):
    """A launchable kernel (reference analog: CudaModule.Kernel.launch)."""

    def __init__(self, kernel_fn, out_shape_fn, interpret=None):
        self._kernel_fn = kernel_fn
        self._out_shape_fn = out_shape_fn
        self._interpret = interpret

    def launch(self, args, grid=None, block_shapes=None, out_specs=None):
        """Run the kernel on NDArray/array args; returns NDArray(s)."""
        from jax.experimental import pallas as pl
        from .ndarray.ndarray import NDArray
        vals = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in args]
        out_shape = self._out_shape_fn(*[jax.ShapeDtypeStruct(v.shape, v.dtype)
                                         for v in vals])
        # compiled where the ARGUMENTS live on a TPU, interpreted anywhere
        # else: default-context (host) arrays on a chip machine lower for
        # the CPU, where Pallas only interprets
        platform = (next(iter(vals[0].devices())).platform if vals
                    else jax.default_backend())
        interpret = (self._interpret if self._interpret is not None
                     else platform != "tpu")
        call_kwargs = dict(out_shape=out_shape, interpret=interpret)
        if grid is not None:
            call_kwargs["grid"] = grid
        if block_shapes is not None:
            call_kwargs["in_specs"] = block_shapes
        if out_specs is not None:
            call_kwargs["out_specs"] = out_specs
        out = pl.pallas_call(self._kernel_fn, **call_kwargs)(*vals)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)


class PallasModule(object):
    """Holds runtime-defined Pallas kernels (reference: CudaModule role)."""

    def __init__(self):
        self._kernels = {}

    def add_kernel(self, name, kernel_fn, out_shape_fn, interpret=None):
        kernel = PallasKernel(kernel_fn, out_shape_fn, interpret)
        self._kernels[name] = kernel
        return kernel

    def get_kernel(self, name):
        if name not in self._kernels:
            raise MXNetError("no kernel %r in module" % name)
        return self._kernels[name]


def register_pallas_op(name, kernel_fn, out_shape_fn, interpret=None,
                       input_names=("data",)):
    """Expose a Pallas kernel as a first-class op (mx.nd.<name> /
    mx.sym.<name>); the runtime analog of NNVM_REGISTER_OP for user kernels.

    Note: ops registered after `import mxnet_tpu` are reachable via
    `mx.nd.<name>` only if registered before namespace generation; use the
    returned function for late registration.
    """
    from jax.experimental import pallas as pl

    def op_fn(params, *inputs):
        out_shape = out_shape_fn(*[jax.ShapeDtypeStruct(v.shape, v.dtype)
                                   for v in inputs])
        use_interp = (interpret if interpret is not None
                      else jax.default_backend() != "tpu")
        return pl.pallas_call(kernel_fn, out_shape=out_shape,
                              interpret=use_interp)(*inputs)

    register_op(name, input_names=input_names)(op_fn)

    def nd_fn(*arrays):
        from .ndarray.ndarray import NDArray
        vals = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in arrays]
        out = op_fn(None, *vals)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)

    return nd_fn
