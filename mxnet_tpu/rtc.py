"""Runtime-compiled user kernels.

The reference's MXRtc JIT-compiles user CUDA source with NVRTC and launches
it on NDArrays (ref: src/common/mxrtc.cc, include/mxnet/mxrtc.h,
python/mxnet/rtc.py, USE_NVRTC=1). The TPU-native equivalent is user Pallas
kernels: you write the kernel body against ``pl.Ref``s and this module wraps
it with pallas_call, gridding, and NDArray marshalling — same role, same
"escape hatch" position in the stack.

Example::

    import mxnet_tpu as mx
    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    k = mx.rtc.PallasKernel(scale_kernel, out_like=0)
    y = k(mx.nd.ones((8, 128)))          # on a CPU host: interpret=True
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray


class PallasKernel(object):
    """Wrap a user Pallas kernel body into an NDArray-callable.

    Parameters
    ----------
    kernel : callable(*in_refs, *out_refs)
        Pallas kernel body.
    out_like : int or jax.ShapeDtypeStruct or list
        Output spec: an input index to mirror, a ShapeDtypeStruct, or a list
        of either for multiple outputs.
    grid : tuple, optional
        Pallas grid; default single program instance.
    in_specs / out_specs : optional pl.BlockSpec lists.
    interpret : bool
        Run in the Pallas interpreter (CPU debugging). Never chosen for
        you: without it the kernel compiles for the attached chip, and a
        host with no chip gets the compiler's error, not the interpreter.
    """

    def __init__(self, kernel, out_like=0, grid=None, in_specs=None,
                 out_specs=None, interpret=False):
        self.kernel = kernel
        self.out_like = out_like
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.interpret = bool(interpret)
        self._jitted = None

    def _out_shape(self, arrays):
        def resolve(spec):
            if isinstance(spec, int):
                a = arrays[spec]
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return spec
        if isinstance(self.out_like, (list, tuple)):
            return [resolve(s) for s in self.out_like]
        return resolve(self.out_like)

    def __call__(self, *args):
        from jax.experimental import pallas as pl
        arrays = [a.data if isinstance(a, NDArray) else jnp.asarray(a)
                  for a in args]
        out_shape = self._out_shape(arrays)
        kwargs = {}
        if self.grid is not None:
            kwargs["grid"] = self.grid
        if self.in_specs is not None:
            kwargs["in_specs"] = self.in_specs
        if self.out_specs is not None:
            kwargs["out_specs"] = self.out_specs
        fn = pl.pallas_call(self.kernel, out_shape=out_shape,
                            interpret=self.interpret, **kwargs)
        out = fn(*arrays)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)


class Rtc(object):
    """API-compatibility shim for the reference's mx.rtc.Rtc (CUDA source).

    CUDA source cannot run on TPU; this class exists to give reference code a
    precise error pointing at PallasKernel (ref: python/mxnet/rtc.py)."""

    def __init__(self, name, inputs, outputs, kernel):
        raise MXNetError(
            "mx.rtc.Rtc compiles CUDA source, which has no TPU analog. "
            "Write the kernel as Pallas and wrap it with mx.rtc.PallasKernel "
            "(see module docstring).")
