"""Runtime-compiled device kernels (`mx.rtc`), rebuilt on Pallas.

The reference's mx.rtc (python/mxnet/rtc.py; src/common/mxrtc.cc,
SURVEY.md §2.1) JIT-compiles user CUDA source with NVRTC and launches it
on NDArrays.  The TPU-native equivalent of "write your own kernel at
runtime" is a Pallas TPU kernel: the user supplies a Python kernel
function over VMEM refs instead of CUDA C, and this module compiles it
through pallas_call and applies it to NDArrays.  Same contract —
named inputs/outputs, explicit launch geometry — with the grid mapping
onto Pallas grid/BlockSpecs rather than CUDA blocks/threads.
"""
import numpy as np
import jax
from jax.experimental import pallas as pl

from . import ndarray as nd
from .base import MXNetError
from .pallas_ops import default_interpret


class Rtc(object):
    """A runtime-compiled kernel.

    Parameters
    ----------
    name : str
        kernel name (diagnostic only).
    inputs : list of str
        names of input arrays, in call order.
    outputs : list of str
        names of output arrays, in call order.
    kernel : callable
        Pallas kernel body `kernel(*in_refs, *out_refs)` reading/writing
        VMEM refs (the reference took CUDA C source instead).

    Example
    -------
    >>> def body(x_ref, y_ref, out_ref):
    ...     out_ref[:] = x_ref[:] * y_ref[:] + 1.0
    >>> k = mx.rtc.Rtc('saxpy1', ['x', 'y'], ['out'], body)
    >>> out = k.push([x, y], out_shapes=[x.shape])
    """

    def __init__(self, name, inputs, outputs, kernel):
        if isinstance(inputs, dict):
            inputs = list(inputs)
        if isinstance(outputs, dict):
            outputs = list(outputs)
        self.name = name
        self.input_names = list(inputs)
        self.output_names = list(outputs)
        self.kernel = kernel
        self._compiled = {}

    def _get_fn(self, in_shapes, in_dtypes, out_shapes, out_dtypes,
                grid, interpret):
        key = (tuple(in_shapes), tuple(str(d) for d in in_dtypes),
               tuple(out_shapes), tuple(str(d) for d in out_dtypes),
               grid, interpret)
        if key not in self._compiled:
            out_spec = [jax.ShapeDtypeStruct(s, d)
                        for s, d in zip(out_shapes, out_dtypes)]
            kwargs = {'out_shape': out_spec if len(out_spec) > 1
                      else out_spec[0], 'interpret': interpret}
            if grid:
                kwargs['grid'] = tuple(grid)
            call = pl.pallas_call(self.kernel, **kwargs)
            self._compiled[key] = jax.jit(call)
        return self._compiled[key]

    def push(self, ins, outs=None, out_shapes=None, out_dtypes=None,
             grid=None, grid_dims=None, block_dims=None):
        """Run the kernel (reference Rtc.push(ins, outs, grid_dims,
        block_dims)).  On TPU the launch geometry is the Pallas `grid`;
        CUDA-style grid_dims are collapsed to a grid for source
        compatibility, while block_dims has no Pallas equivalent
        (blocking lives in BlockSpecs) and is ignored with a warning."""
        ins = [x if isinstance(x, nd.NDArray) else nd.array(x)
               for x in ins]
        if len(ins) != len(self.input_names):
            raise MXNetError('Rtc %s expects %d inputs' %
                             (self.name, len(self.input_names)))
        if outs is not None:
            out_shapes = [o.shape for o in outs]
            out_dtypes = [o.dtype for o in outs]
        if out_shapes is None:
            out_shapes = [ins[0].shape] * len(self.output_names)
        if out_dtypes is None:
            out_dtypes = [ins[0].dtype] * len(out_shapes)
        if grid is not None:
            grid = tuple(int(g) for g in grid)
        elif grid_dims is not None:
            grid = tuple(int(g) for g in grid_dims if int(g) > 1) or None
        if block_dims is not None:
            import warnings
            warnings.warn(
                'Rtc.push: block_dims has no Pallas equivalent (blocking '
                'is expressed via BlockSpecs inside the kernel); ignoring',
                stacklevel=2)
        interpret = default_interpret(ins[0]._data)
        fn = self._get_fn(
            tuple(tuple(x.shape) for x in ins),
            tuple(x.dtype for x in ins),
            tuple(tuple(s) for s in out_shapes), tuple(out_dtypes),
            grid, interpret)
        res = fn(*[x._data for x in ins])
        if not isinstance(res, (tuple, list)):
            res = (res,)
        results = [nd.NDArray(r, ins[0].context) for r in res]
        if outs is not None:
            for dst, src in zip(outs, results):
                dst[:] = src
            return outs
        return results if len(results) > 1 else results[0]
