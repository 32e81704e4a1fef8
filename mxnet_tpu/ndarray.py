"""NDArray: the imperative tensor API.

TPU-native redesign of the reference NDArray
(include/mxnet/ndarray.h:77, src/ndarray/ndarray.cc; SURVEY.md §2.1).
The reference pairs each array with an engine variable and pushes every
op through the ThreadedEngine for async execution; here the array wraps a
`jax.Array`, and asynchrony comes for free from JAX/PJRT async dispatch —
`wait_to_read` maps to `block_until_ready`.  All operator wrappers are
code-generated from the op registry at import time, exactly like the
reference generates `mx.nd.*` from MXListAllOpNames
(python/mxnet/ndarray.py:2624 _init_ndarray_module).
"""
import struct
import sys

import numpy as np
import jax
import jax.numpy as jnp

from . import random as _random
from . import profiler as _profiler
from . import autograd as _autograd
from .base import MXNetError, parse_attr_value
from .context import Context, current_context, cpu
from .ops import registry as _reg

# builtins that op codegen will shadow at module level (nd.slice, nd.sum, ...)
_py_slice = slice

_DTYPE_ALIASES = {'float32': np.float32, 'float64': np.float64,
                  'float16': np.float16, 'bfloat16': jnp.bfloat16,
                  'uint8': np.uint8, 'int8': np.int8,
                  'int32': np.int32, 'int64': np.int64}


class NDArray:
    """An n-dimensional array on a device (CPU or TPU)."""
    __slots__ = ('_data', '_ctx', 'grad_req', '_grad', '_fresh_grad',
                 '__weakref__')

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx if ctx is not None else _infer_ctx(data)
        self.grad_req = None
        self._grad = None
        self._fresh_grad = False

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        d = self._data.dtype
        return d.type if hasattr(d, 'type') else d

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def handle(self):
        return self._data

    # -- data access -------------------------------------------------------
    def asnumpy(self):
        return np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise ValueError('The current array is not a scalar')
        return self.asnumpy().reshape(-1)[0]

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        jax.block_until_ready(self._data)

    def __len__(self):
        if not self.shape:
            raise TypeError('len() of unsized object')
        return self.shape[0]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError('The truth value of an NDArray with multiple '
                         'elements is ambiguous.')

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __repr__(self):
        return '%s\n<NDArray %s @%s>' % (
            str(self.asnumpy()), 'x'.join(map(str, self.shape)), self._ctx)

    # -- conversion / movement --------------------------------------------
    def astype(self, dtype, copy=True):
        dtype = _DTYPE_ALIASES.get(dtype, dtype) if isinstance(dtype, str) else dtype
        return NDArray(self._data.astype(dtype), self._ctx)

    def copy(self):
        return NDArray(self._data + 0, self._ctx)

    def copyto(self, other):
        """Copy to another NDArray (in place) or a Context (new array).
        Reference: CopyFromTo (ndarray.h:471)."""
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError('shape mismatch in copyto')
            other._data = jax.device_put(self._data,
                                         other._ctx.jax_device()).astype(other.dtype)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        raise TypeError('copyto does not support type %s' % type(other))

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def to_dlpack(self):
        return jax.dlpack.to_dlpack(self._data)

    # -- shape manipulation ------------------------------------------------
    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        return invoke('Reshape', [self], {'shape': tuple(shape), **kwargs})

    def expand_dims(self, axis):
        return invoke('expand_dims', [self], {'axis': axis})

    def flatten(self):
        return invoke('Flatten', [self], {})

    def transpose(self, axes=None):
        return invoke('transpose', [self], {'axes': axes})

    @property
    def T(self):
        return self.transpose()

    def broadcast_to(self, shape):
        return invoke('broadcast_to', [self], {'shape': tuple(shape)})

    def flip(self, axis):
        return invoke('reverse', [self], {'axis': axis})

    def tile(self, reps):
        return invoke('tile', [self], {'reps': reps})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke('SliceChannel', [self],
                      {'num_outputs': num_outputs, 'axis': axis,
                       'squeeze_axis': squeeze_axis})

    # -- reductions (method forms) ----------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke('sum', [self], {'axis': axis, 'keepdims': keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke('mean', [self], {'axis': axis, 'keepdims': keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke('max', [self], {'axis': axis, 'keepdims': keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke('min', [self], {'axis': axis, 'keepdims': keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke('argmax', [self], {'axis': axis, 'keepdims': keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke('argmin', [self], {'axis': axis, 'keepdims': keepdims})

    def norm(self):
        return invoke('norm', [self], {})

    def abs(self):
        return invoke('abs', [self], {})

    def square(self):
        return invoke('square', [self], {})

    def sqrt(self):
        return invoke('sqrt', [self], {})

    def exp(self):
        return invoke('exp', [self], {})

    def log(self):
        return invoke('log', [self], {})

    def clip(self, a_min, a_max):
        return invoke('clip', [self], {'a_min': a_min, 'a_max': a_max})

    def sort(self, axis=-1, is_ascend=True):
        return invoke('sort', [self], {'axis': axis, 'is_ascend': is_ascend})

    def topk(self, **kwargs):
        return invoke('topk', [self], kwargs)

    def one_hot(self, depth, **kwargs):
        return invoke('one_hot', [self], {'depth': depth, **kwargs})

    def astuple(self):
        return tuple(self.asnumpy())

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data
        out = self._data[key]
        return NDArray(out, self._ctx)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, (np.ndarray, list, tuple, float, int)):
            value = jnp.asarray(value, dtype=self.dtype)
        if isinstance(key, _py_slice) and key == _py_slice(None):
            new = jnp.broadcast_to(value, self.shape).astype(self.dtype)
        else:
            if isinstance(key, NDArray):
                key = key._data
            new = self._data.at[key].set(value)
        # assignment must not silently migrate this array off its
        # device(s) — restore the full sharding, not one device
        # (reference CopyFromTo is the cross-device writer, ndarray.h:471)
        if not isinstance(new, jax.core.Tracer) and \
                not isinstance(self._data, jax.core.Tracer) and \
                new.devices() != self._data.devices():
            new = jax.device_put(new, self._data.sharding)
        self._data = new

    # -- arithmetic --------------------------------------------------------
    def _binary(self, other, elem_op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            if other.shape == self.shape:
                op = elem_op
            else:
                op = elem_op.replace('elemwise', 'broadcast') \
                    if elem_op.startswith('elemwise') else 'broadcast' + elem_op
            lhs, rhs = (other, self) if reverse else (self, other)
            return invoke(op, [lhs, rhs], {})
        if isinstance(other, (int, float, np.floating, np.integer)):
            return invoke(scalar_op, [self], {'scalar': float(other)})
        raise TypeError('unsupported operand type %s' % type(other))

    def __add__(self, other):
        return self._binary(other, 'elemwise_add', '_plus_scalar')

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, 'elemwise_sub', '_minus_scalar')

    def __rsub__(self, other):
        if isinstance(other, (int, float)):
            return invoke('_rminus_scalar', [self], {'scalar': float(other)})
        return self._binary(other, 'elemwise_sub', '_minus_scalar', reverse=True)

    def __mul__(self, other):
        return self._binary(other, 'elemwise_mul', '_mul_scalar')

    __rmul__ = __mul__

    def __div__(self, other):
        return self._binary(other, 'elemwise_div', '_div_scalar')

    __truediv__ = __div__

    def __rdiv__(self, other):
        if isinstance(other, (int, float)):
            return invoke('_rdiv_scalar', [self], {'scalar': float(other)})
        return self._binary(other, 'elemwise_div', '_div_scalar', reverse=True)

    __rtruediv__ = __rdiv__

    def __mod__(self, other):
        return self._binary(other, '_mod', '_mod_scalar')

    def __rmod__(self, other):
        if isinstance(other, (int, float)):
            return invoke('_rmod_scalar', [self], {'scalar': float(other)})
        return self._binary(other, '_mod', '_mod_scalar', reverse=True)

    def __pow__(self, other):
        return self._binary(other, '_power', '_power_scalar')

    def __rpow__(self, other):
        return invoke('_rpower_scalar', [self], {'scalar': float(other)})

    def __neg__(self):
        return invoke('negative', [self], {})

    def __abs__(self):
        return invoke('abs', [self], {})

    def __iadd__(self, other):
        out = self.__add__(other)
        self._data = out._data
        return self

    def __isub__(self, other):
        out = self.__sub__(other)
        self._data = out._data
        return self

    def __imul__(self, other):
        out = self.__mul__(other)
        self._data = out._data
        return self

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._data = out._data
        return self

    def _cmp(self, other, op, scalar_op):
        if isinstance(other, NDArray):
            name = op if other.shape == self.shape else \
                op.replace('_', 'broadcast_', 1)
            return invoke(name, [self, other], {})
        return invoke(scalar_op, [self], {'scalar': float(other)})

    def __eq__(self, other):
        if other is None:
            return False
        return self._cmp(other, '_equal', '_equal_scalar')

    def __ne__(self, other):
        if other is None:
            return True
        return self._cmp(other, '_not_equal', '_not_equal_scalar')

    def __gt__(self, other):
        return self._cmp(other, '_greater', '_greater_scalar')

    def __ge__(self, other):
        return self._cmp(other, '_greater_equal', '_greater_equal_scalar')

    def __lt__(self, other):
        return self._cmp(other, '_lesser', '_lesser_scalar')

    def __le__(self, other):
        return self._cmp(other, '_lesser_equal', '_lesser_equal_scalar')

    __hash__ = None

    # -- autograd ----------------------------------------------------------
    def attach_grad(self, grad_req='write'):
        """Attach a gradient buffer (reference: autograd MarkVariables,
        src/ndarray/autograd.h:96)."""
        self._grad = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        self.grad_req = grad_req
        _autograd.mark_variable(self)

    @property
    def grad(self):
        return self._grad

    def detach(self):
        return NDArray(self._data, self._ctx)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _autograd.backward([self], [out_grad], retain_graph=retain_graph)


def _infer_ctx(data):
    try:
        dev = list(data.devices())[0]
        if dev.platform == 'cpu':
            return cpu(dev.id)
        return Context('tpu', dev.id)
    except Exception:
        return current_context()


# ---------------------------------------------------------------------------
# Imperative invoke — the equivalent of MXImperativeInvoke
# (reference src/c_api/c_api_ndarray.cc:423, SURVEY.md §3.3)
# ---------------------------------------------------------------------------

def invoke(op_name, inputs, attrs, out=None):
    op = _reg.get(op_name)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    is_train = _autograd.is_training()
    op_ctx = _reg.OpContext(
        is_train=is_train,
        rng=_random.next_key() if op.needs_rng else None)
    n_aux = op.aux_count(attrs)
    args = inputs[:len(inputs) - n_aux] if n_aux else inputs
    auxs = inputs[len(inputs) - n_aux:] if n_aux else []
    in_data = [x._data for x in args]
    aux_data = [x._data for x in auxs]
    if _profiler.is_running() and _profiler.mode() == 'all':
        # imperative-op spans under mode='all' (reference kAllOperator)
        with _profiler.scope(op_name, 'imperative'):
            outs, new_auxs = op.apply(attrs, in_data, aux_data, op_ctx)
            jax.block_until_ready(outs)
    else:
        outs, new_auxs = op.apply(attrs, in_data, aux_data, op_ctx)
    ctx = args[0]._ctx if args else _attr_ctx(attrs)
    results = [NDArray(o, ctx) for o in outs]
    if op.mutable_aux and (is_train or op.aux_always):
        for holder, new in zip(auxs, new_auxs):
            holder._data = new
    if _autograd.is_recording():
        _autograd.record_op(op, dict(attrs), list(args), list(auxs),
                            results, op_ctx)
    if out is not None:
        outlist = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outlist, results):
            dst._data = src._data
        return out
    if len(results) == 1:
        return results[0]
    return results


def invoke_fn(fcompute, inputs, attrs=None, name='_fn'):
    """Run an ad-hoc pure-JAX op through the imperative machinery:
    tape-recorded and differentiable like any registered op.

    `fcompute(attrs, in_arrays, aux_arrays, op_ctx) -> (outs, new_auxs)`
    is the canonical registry compute signature.  Used by fused blocks
    (gluon RNN layers) and the CustomOp bridge."""
    attrs = attrs or {}
    op = _reg.OpDef(name, fcompute,
                    input_names=tuple('arg%d' % i
                                      for i in range(len(inputs))),
                    needs_rng=True)
    op_ctx = _reg.OpContext(is_train=_autograd.is_training(),
                            rng=_random.next_key())
    in_data = [x._data for x in inputs]
    outs, _ = op.apply(attrs, in_data, [], op_ctx)
    ctx = inputs[0]._ctx if inputs else current_context()
    results = [NDArray(o, ctx) for o in outs]
    if _autograd.is_recording():
        _autograd.record_op(op, dict(attrs), list(inputs), [],
                            results, op_ctx)
    return results


def _attr_ctx(attrs):
    ctx = attrs.pop('ctx', None) if isinstance(attrs, dict) else None
    if isinstance(ctx, str):
        dt, rest = ctx.split('(')
        return Context(dt, int(rest.rstrip(')')))
    return ctx if ctx is not None else current_context()


# ---------------------------------------------------------------------------
# Array creation
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    elif isinstance(source_array, np.ndarray):
        src = source_array
    else:
        # python lists/scalars default to float32 (reference ndarray.py array)
        src = np.asarray(source_array, dtype=np.float32 if dtype is None else dtype)
    if dtype is None:
        dtype = src.dtype if src.dtype not in (np.float64, np.int64) else \
            (np.float32 if src.dtype == np.float64 else np.int32)
    ctx = ctx or current_context()
    data = jax.device_put(jnp.asarray(src, dtype=dtype), ctx.jax_device())
    return NDArray(data, ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    data = jax.device_put(jnp.zeros(shape, dtype=dtype or np.float32),
                          ctx.jax_device())
    return NDArray(data, ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    data = jax.device_put(jnp.ones(shape, dtype=dtype or np.float32),
                          ctx.jax_device())
    return NDArray(data, ctx)


def full(shape, val, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    data = jax.device_put(jnp.full(shape, val, dtype=dtype or np.float32),
                          ctx.jax_device())
    return NDArray(data, ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    return invoke('_arange', [], {'start': start, 'stop': stop, 'step': step,
                                  'repeat': repeat, 'dtype': dtype,
                                  'ctx': str(ctx) if ctx else None})


def concatenate(arrays, axis=0, always_copy=True):
    return invoke('Concat', list(arrays),
                  {'num_args': len(arrays), 'dim': axis})


def stack(*arrays, **kwargs):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return invoke('stack', list(arrays),
                  {'num_args': len(arrays), 'axis': kwargs.get('axis', 0)})


def from_dlpack(capsule):
    return NDArray(jax.dlpack.from_dlpack(capsule))


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def waitall():
    """Block until all async computation completes (reference
    MXNDArrayWaitAll).  JAX dispatch is async per-array; an effects
    barrier covers outstanding work."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Save / load — reference NDArray::Save/Load (ndarray.h:353-366): magic +
# shapes + dtypes binary blob, dict or list of arrays.  Same capability,
# TPU-era container format.
# ---------------------------------------------------------------------------

_SAVE_MAGIC = b'MXTPU001'


def save(fname, data):
    """Write via a same-directory temp file + os.replace (crash-safe):
    a process killed mid-save leaves either the previous file or the
    complete new one under `fname`, never a torn blob that a later
    load would trust — the availability contract checkpoint callbacks
    (callback.do_checkpoint, Module.save_checkpoint) rely on."""
    from .base import atomic_file
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        items = list(data.items())
    else:
        items = [('', v) for v in data]
    with atomic_file(fname) as f:
        f.write(_SAVE_MAGIC)
        f.write(struct.pack('<q', len(items)))
        for name, arr in items:
            if not isinstance(arr, NDArray):
                raise TypeError('save only supports NDArray values')
            nb = name.encode('utf-8')
            a = arr.asnumpy()
            if a.dtype == jnp.bfloat16:
                a = a.astype(np.float32)
            dt = np.dtype(a.dtype).str.encode('utf-8')
            f.write(struct.pack('<q', len(nb)))
            f.write(nb)
            f.write(struct.pack('<q', len(dt)))
            f.write(dt)
            f.write(struct.pack('<q', a.ndim))
            f.write(struct.pack('<%dq' % a.ndim, *a.shape))
            raw = np.ascontiguousarray(a).tobytes()
            f.write(struct.pack('<q', len(raw)))
            f.write(raw)


def _load_fail(fname, why):
    raise MXNetError('Truncated or corrupt NDArray file %s: %s '
                     '(a crash mid-write, torn copy, or not an '
                     'MXTPU params blob)' % (fname, why))


def load(fname):
    """Load a save() blob.  Every length field is validated before it
    is trusted, so a truncated or bit-flipped file raises a clear
    MXNetError naming the file instead of an opaque struct/reshape
    traceback from deep inside the decoder."""
    def read_exact(f, n, what):
        b = f.read(n)
        if len(b) != n:
            _load_fail(fname, 'expected %d more byte(s) for %s, file '
                       'ends after %d' % (n, what, len(b)))
        return b

    def read_len(f, what, limit=1 << 40):
        v, = struct.unpack('<q', read_exact(f, 8, what))
        if v < 0 or v > limit:
            _load_fail(fname, 'implausible %s %d' % (what, v))
        return v

    with open(fname, 'rb') as f:
        magic = f.read(len(_SAVE_MAGIC))
        if magic != _SAVE_MAGIC:
            _load_fail(fname, 'bad magic %r' % magic[:16])
        n = read_len(f, 'entry count', limit=1 << 32)
        items = []
        named = False
        for i in range(n):
            what = 'entry %d/%d' % (i + 1, n)
            ln = read_len(f, '%s name length' % what, limit=1 << 20)
            try:
                name = read_exact(f, ln, '%s name' % what) \
                    .decode('utf-8')
            except UnicodeDecodeError as e:
                _load_fail(fname, 'bad name for %s (%s)' % (what, e))
            ld = read_len(f, '%s dtype length' % what, limit=1 << 10)
            try:
                dt = np.dtype(read_exact(f, ld, '%s dtype' % what)
                              .decode('utf-8'))
            except (TypeError, ValueError, UnicodeDecodeError) as e:
                _load_fail(fname, 'bad dtype for %s (%s)' % (what, e))
            ndim = read_len(f, '%s ndim' % what, limit=64)
            shape = struct.unpack(
                '<%dq' % ndim,
                read_exact(f, 8 * ndim, '%s shape' % what)) \
                if ndim else ()
            if any(s < 0 for s in shape):
                _load_fail(fname, 'negative dim in %s shape %s'
                           % (what, shape))
            lr = read_len(f, '%s payload length' % what)
            expect = int(np.prod(shape, dtype=np.int64)) * dt.itemsize \
                if shape else dt.itemsize
            if lr != expect:
                _load_fail(fname, '%s payload is %d bytes but shape %s '
                           'dtype %s needs %d' % (what, lr, shape,
                                                  dt.name, expect))
            a = np.frombuffer(read_exact(f, lr, '%s payload' % what),
                              dtype=dt).reshape(shape)
            if name:
                named = True
            # honor the stored dtype exactly (no float64/int64 narrowing)
            items.append((name, NDArray(jnp.asarray(a, dtype=dt))))
    if named:
        return dict(items)
    return [v for _, v in items]


# ---------------------------------------------------------------------------
# Operator codegen — mirror of _init_ndarray_module (reference
# python/mxnet/ndarray.py:2624)
# ---------------------------------------------------------------------------

def _make_op_func(op_name):
    op = _reg.get(op_name)

    def fn(*args, **kwargs):
        out = kwargs.pop('out', None)
        kwargs.pop('name', None)
        inputs = [a for a in args if isinstance(a, NDArray)]
        extra = [a for a in args if not isinstance(a, NDArray)]
        if extra:
            raise TypeError(
                'Operator %s: positional arguments must be NDArrays; pass '
                'attributes as keywords (got positional %r)' % (op_name, extra))
        # named tensor kwargs (e.g. data=x, weight=w)
        names = None
        try:
            names = op.input_names(kwargs)
        except Exception:
            pass
        if names:
            for nm in names:
                if nm in kwargs and isinstance(kwargs[nm], NDArray):
                    inputs.append(kwargs.pop(nm))
        attrs = {k: v for k, v in kwargs.items()}
        return invoke(op_name, inputs, attrs, out=out)

    fn.__name__ = op_name
    fn.__doc__ = 'Auto-generated wrapper for operator %s.' % op_name
    return fn


def _init_module():
    mod = sys.modules[__name__]
    for name in _reg.list_ops():
        if hasattr(mod, name):  # keep hand-written wrappers (zeros, ones, ...)
            continue
        setattr(mod, name, _make_op_func(name))
    # random submodule conveniences with reference positional signatures
    # (python/mxnet/random.py: uniform(low, high, shape, ...))
    from . import random as rnd

    def uniform(low=0.0, high=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_uniform',
                      [], {'low': low, 'high': high, 'shape': shape,
                           'dtype': dtype, 'ctx': ctx}, out=out)

    def normal(loc=0.0, scale=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_normal',
                      [], {'loc': loc, 'scale': scale, 'shape': shape,
                           'dtype': dtype, 'ctx': ctx}, out=out)

    def gamma(alpha=1.0, beta=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_gamma',
                      [], {'alpha': alpha, 'beta': beta, 'shape': shape,
                           'dtype': dtype, 'ctx': ctx}, out=out)

    def exponential(lam=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_exponential',
                      [], {'lam': lam, 'shape': shape, 'dtype': dtype,
                           'ctx': ctx}, out=out)

    def poisson(lam=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_poisson',
                      [], {'lam': lam, 'shape': shape, 'dtype': dtype,
                           'ctx': ctx}, out=out)

    def negative_binomial(k=1, p=1.0, shape=(), dtype=None, ctx=None, out=None):
        return invoke('_random_negative_binomial',
                      [], {'k': k, 'p': p, 'shape': shape, 'dtype': dtype,
                           'ctx': ctx}, out=out)

    def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=(), dtype=None,
                                      ctx=None, out=None):
        return invoke('_random_generalized_negative_binomial',
                      [], {'mu': mu, 'alpha': alpha, 'shape': shape,
                           'dtype': dtype, 'ctx': ctx}, out=out)

    def multinomial(data, shape=1, get_prob=False, dtype=None, out=None):
        return invoke('_sample_multinomial',
                      [data], {'shape': shape, 'get_prob': get_prob,
                               'dtype': dtype}, out=out)

    for f in (uniform, normal, gamma, exponential, poisson,
              negative_binomial, generalized_negative_binomial, multinomial):
        setattr(rnd, f.__name__, f)
        setattr(mod, 'random_' + f.__name__, f)


_init_module()


def __getattr__(name):
    """Late-registered ops (e.g. `Custom`, registered when
    mxnet_tpu.operator is imported) resolve on first access."""
    if _reg.exists(name):
        fn = _make_op_func(name)
        setattr(sys.modules[__name__], name, fn)
        return fn
    raise AttributeError('module %r has no attribute %r'
                         % (__name__, name))
