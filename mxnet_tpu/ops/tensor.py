"""Tensor operators (elemwise / broadcast / reduce / matrix / init / index).

TPU-native re-implementation of the reference's src/operator/tensor/
(~12.7k LoC of CUDA/mshadow kernels, SURVEY.md §2.3) as pure JAX ops.
Each reference kernel family collapses into a jnp/lax expression that XLA
fuses and tiles onto the MXU/VPU; no hand-written kernels are needed at
this layer.  Op names/attrs mirror the reference registry so symbol JSON
and generated frontend wrappers line up.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .registry import (register, astuple, asbool, asint, asfloat,
                       normalize_axis)
from ..base import parse_attr_value, MXNetError


def _dtype(attrs, default=np.float32):
    d = attrs.get('dtype', None)
    if d is None:
        return np.dtype(default)
    return np.dtype(d)


# ---------------------------------------------------------------------------
# Elementwise binary (same-shape) — reference elemwise_binary_op_basic.cc
# ---------------------------------------------------------------------------

def _reg_binary(name, fn, aliases=()):
    @register(name, input_names=('lhs', 'rhs'), aliases=aliases,
              hint=name.lstrip('_'), shape_rule='same')
    def _op(attrs, lhs, rhs, _fn=fn):
        return _fn(lhs, rhs)
    return _op


_reg_binary('elemwise_add', jnp.add, aliases=('_add', '_plus', '_Plus'))
_reg_binary('elemwise_sub', jnp.subtract, aliases=('_sub', '_minus', '_Minus'))
_reg_binary('elemwise_mul', jnp.multiply, aliases=('_mul', '_Mul'))
_reg_binary('elemwise_div', jnp.divide, aliases=('_div', '_Div'))
_reg_binary('_power', jnp.power, aliases=('_Power',))
_reg_binary('_maximum', jnp.maximum, aliases=('_Maximum', 'maximum'))
_reg_binary('_minimum', jnp.minimum, aliases=('_Minimum', 'minimum'))
_reg_binary('_hypot', jnp.hypot)
_reg_binary('_mod', jnp.mod, aliases=('_Mod',))

for _n, _f in [('_equal', jnp.equal), ('_not_equal', jnp.not_equal),
               ('_greater', jnp.greater), ('_greater_equal', jnp.greater_equal),
               ('_lesser', jnp.less), ('_lesser_equal', jnp.less_equal)]:
    def _cmp(attrs, lhs, rhs, _f=_f):
        return _f(lhs, rhs).astype(lhs.dtype)
    register(_n, input_names=('lhs', 'rhs'), shape_rule='same')(_cmp)


# ---------------------------------------------------------------------------
# Scalar ops — reference elemwise_binary_scalar_op_*.cc
# ---------------------------------------------------------------------------

def _reg_scalar(name, fn):
    @register(name, input_names=('data',), shape_rule='same')
    def _op(attrs, data, _fn=fn):
        # a HOST numpy scalar in the data's dtype: it inlines into the
        # op on the data's device.  jnp.asarray here would COMMIT the
        # scalar to the default device — with an accelerator attached
        # and the array on cpu, that drags a cross-device transfer
        # into every eager scalar op.
        s = np.dtype(data.dtype).type(asfloat(attrs['scalar']))
        return _fn(data, s)
    return _op


_reg_scalar('_plus_scalar', jnp.add)
_reg_scalar('_minus_scalar', jnp.subtract)
_reg_scalar('_rminus_scalar', lambda x, s: s - x)
_reg_scalar('_mul_scalar', jnp.multiply)
_reg_scalar('_div_scalar', jnp.divide)
_reg_scalar('_rdiv_scalar', lambda x, s: s / x)
_reg_scalar('_power_scalar', jnp.power)
_reg_scalar('_rpower_scalar', lambda x, s: s ** x)
_reg_scalar('_maximum_scalar', jnp.maximum)
_reg_scalar('_minimum_scalar', jnp.minimum)
_reg_scalar('_mod_scalar', jnp.mod)
_reg_scalar('_rmod_scalar', lambda x, s: s % x)
_reg_scalar('_hypot_scalar', jnp.hypot)
for _n, _f in [('_equal_scalar', jnp.equal), ('_not_equal_scalar', jnp.not_equal),
               ('_greater_scalar', jnp.greater),
               ('_greater_equal_scalar', jnp.greater_equal),
               ('_lesser_scalar', jnp.less),
               ('_lesser_equal_scalar', jnp.less_equal)]:
    _reg_scalar(_n, lambda x, s, _f=_f: _f(x, s).astype(x.dtype))


# ---------------------------------------------------------------------------
# Elementwise unary — reference elemwise_unary_op.cc
# ---------------------------------------------------------------------------

def _reg_unary(name, fn, aliases=()):
    @register(name, input_names=('data',), aliases=aliases,
              shape_rule='same')
    def _op(attrs, data, _fn=fn):
        return _fn(data)
    return _op


from jax.scipy.special import gammaln as _gammaln

_gammafn = lambda x: jnp.exp(_gammaln(x))

_UNARY = {
    'negative': jnp.negative, 'reciprocal': jnp.reciprocal,
    'abs': jnp.abs, 'sign': jnp.sign, 'round': jnp.round,
    'rint': jnp.rint, 'ceil': jnp.ceil, 'floor': jnp.floor,
    'trunc': jnp.trunc, 'fix': jnp.trunc,
    'square': jnp.square, 'sqrt': jnp.sqrt,
    'rsqrt': lambda x: 1.0 / jnp.sqrt(x),
    'cbrt': jnp.cbrt, 'rcbrt': lambda x: 1.0 / jnp.cbrt(x),
    'exp': jnp.exp, 'log': jnp.log, 'log10': jnp.log10, 'log2': jnp.log2,
    'log1p': jnp.log1p, 'expm1': jnp.expm1,
    'sin': jnp.sin, 'cos': jnp.cos, 'tan': jnp.tan,
    'arcsin': jnp.arcsin, 'arccos': jnp.arccos, 'arctan': jnp.arctan,
    'degrees': jnp.degrees, 'radians': jnp.radians,
    'sinh': jnp.sinh, 'cosh': jnp.cosh, 'tanh': jnp.tanh,
    'arcsinh': jnp.arcsinh, 'arccosh': jnp.arccosh, 'arctanh': jnp.arctanh,
    'sigmoid': jax.nn.sigmoid, 'relu': jax.nn.relu,
    'softsign': jax.nn.soft_sign,
    'zeros_like': jnp.zeros_like, 'ones_like': jnp.ones_like,
    'gamma': _gammafn, 'gammaln': _gammaln,
}
for _n, _f in _UNARY.items():
    if _f is not None:
        _reg_unary(_n, _f)

_reg_unary('_copy', lambda x: x, aliases=('identity',))


@register('BlockGrad', input_names=('data',), aliases=('stop_gradient',))
def _block_grad(attrs, data):
    return jax.lax.stop_gradient(data)


# ---------------------------------------------------------------------------
# Graph-plumbing ops.  In the reference these are nodes the executor
# inserts while building/augmenting the graph (gradient aggregation
# chains graph_executor.cc:122-137, PlaceDevice copies, init_op.cc);
# here the same jobs are done by jax.vjp and XLA SPMD, so the ops are
# registered as their plain functional meaning for API parity.
# ---------------------------------------------------------------------------

_reg_binary('_grad_add', jnp.add)


@register('_identity_with_attr_like_rhs', input_names=('lhs', 'rhs'))
def _identity_like_rhs(attrs, lhs, rhs):
    # reference init_op.cc: forwards lhs; rhs only contributes node
    # attrs (storage type/shape) during graph rewrites
    return lhs


@register('_CrossDeviceCopy', input_names=('data',), shape_rule='same')
def _cross_device_copy(attrs, data):
    # reference cross_device_copy.cc: explicit inter-device transport at
    # ctx_group boundaries; under XLA SPMD placement transfers are the
    # compiler's job, so this is an identity marker
    return data


@register('_NoGradient', input_names=())
def _no_gradient(attrs):
    # reference init_op.cc: placeholder head-grad for outputs whose
    # gradient is undefined; never consumed numerically
    return jnp.zeros((1,), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _make_loss_fn(grad_scale, data):
    return data


def _make_loss_fwd(grad_scale, data):
    return data, data


def _make_loss_bwd(grad_scale, res, g):
    # Reference MakeLoss (src/operator/make_loss-inl.h): backward is
    # grad_scale * ones, ignoring the head gradient.
    return (jnp.full_like(g, grad_scale),)


_make_loss_fn.defvjp(_make_loss_fwd, _make_loss_bwd)


@register('make_loss', input_names=('data',), aliases=('MakeLoss',))
def _make_loss(attrs, data):
    return _make_loss_fn(asfloat(attrs.get('grad_scale', 1.0)), data)


@register('Cast', input_names=('data',), aliases=('cast',),
          infer_dtype=lambda attrs, in_dt: (
              [np.dtype(np.float32) if in_dt[0] is None else in_dt[0]],
              [_dtype(attrs)]))
def _cast(attrs, data):
    return data.astype(_dtype(attrs))


@register('clip', input_names=('data',))
def _clip(attrs, data):
    return jnp.clip(data, asfloat(attrs['a_min']), asfloat(attrs['a_max']))


# ---------------------------------------------------------------------------
# Broadcast binary — reference elemwise_binary_broadcast_op_*.cc
# ---------------------------------------------------------------------------

def _reg_broadcast(name, fn, aliases=()):
    # NO shape_rule='same': operands legitimately differ in shape, so
    # bidirectional unification must not backfill unknown operands
    @register(name, input_names=('lhs', 'rhs'), aliases=aliases,
              hint=name.lstrip('_'))
    def _op(attrs, lhs, rhs, _fn=fn):
        return _fn(lhs, rhs)
    return _op


for _n, _f in [('broadcast_add', jnp.add), ('broadcast_plus', jnp.add),
               ('broadcast_sub', jnp.subtract), ('broadcast_minus', jnp.subtract),
               ('broadcast_mul', jnp.multiply), ('broadcast_div', jnp.divide),
               ('broadcast_mod', jnp.mod),
               ('broadcast_power', jnp.power),
               ('broadcast_maximum', jnp.maximum),
               ('broadcast_minimum', jnp.minimum),
               ('broadcast_hypot', jnp.hypot)]:
    _reg_broadcast(_n, _f)

for _n, _f in [('broadcast_equal', jnp.equal),
               ('broadcast_not_equal', jnp.not_equal),
               ('broadcast_greater', jnp.greater),
               ('broadcast_greater_equal', jnp.greater_equal),
               ('broadcast_lesser', jnp.less),
               ('broadcast_lesser_equal', jnp.less_equal)]:
    _reg_broadcast(_n, lambda a, b, _f=_f: _f(a, b).astype(a.dtype))


@register('broadcast_to', input_names=('data',))
def _broadcast_to(attrs, data):
    shape = astuple(attrs['shape'])
    shape = tuple(d if s == 0 else s for s, d in zip(shape, data.shape))
    return jnp.broadcast_to(data, shape)


@register('broadcast_axis', input_names=('data',), aliases=('broadcast_axes',))
def _broadcast_axis(attrs, data):
    axes = astuple(attrs['axis'])
    sizes = astuple(attrs['size'])
    shape = list(data.shape)
    for ax, sz in zip(axes, sizes):
        shape[normalize_axis(ax, data.ndim)] = sz
    return jnp.broadcast_to(data, tuple(shape))


# ---------------------------------------------------------------------------
# Reductions — reference broadcast_reduce_op_value.cc / _index.cc
# ---------------------------------------------------------------------------

def _red_axes(attrs, ndim):
    axis = parse_attr_value(attrs.get('axis', None))
    if axis is None or axis == ():
        axes = tuple(range(ndim))
    elif isinstance(axis, int):
        axes = (normalize_axis(axis, ndim),)
    else:
        axes = tuple(normalize_axis(a, ndim) for a in axis)
    if asbool(attrs.get('exclude', False)):
        axes = tuple(a for a in range(ndim) if a not in axes)
    return axes


def _reg_reduce(name, fn, aliases=()):
    @register(name, input_names=('data',), aliases=aliases)
    def _op(attrs, data, _fn=fn):
        axes = _red_axes(attrs, data.ndim)
        keepdims = asbool(attrs.get('keepdims', False))
        return _fn(data, axis=axes, keepdims=keepdims)
    return _op


_reg_reduce('sum', jnp.sum, aliases=('sum_axis',))
_reg_reduce('mean', jnp.mean)
_reg_reduce('prod', jnp.prod)
_reg_reduce('nansum', jnp.nansum)
_reg_reduce('nanprod', jnp.nanprod)
_reg_reduce('max', jnp.max, aliases=('max_axis',))
_reg_reduce('min', jnp.min, aliases=('min_axis',))


@register('norm', input_names=('data',))
def _norm(attrs, data):
    # Reference 0.11 norm: L2 over the whole array, shape-(1,) output.
    return jnp.sqrt(jnp.sum(jnp.square(data))).reshape((1,))


def _reg_arg_reduce(name, fn):
    @register(name, input_names=('data',))
    def _op(attrs, data, _fn=fn):
        axis = parse_attr_value(attrs.get('axis', None))
        keepdims = asbool(attrs.get('keepdims', False))
        if axis is None:
            out = _fn(data.reshape(-1), axis=0)
            if keepdims:
                out = out.reshape((1,) * data.ndim)
            return out.astype(data.dtype)
        axis = normalize_axis(axis, data.ndim)
        out = _fn(data, axis=axis)
        if keepdims:
            out = jnp.expand_dims(out, axis)
        # Reference returns indices in the input float dtype
        # (broadcast_reduce_op_index.cc).
        return out.astype(data.dtype)
    return _op


_reg_arg_reduce('argmax', jnp.argmax)
_reg_arg_reduce('argmin', jnp.argmin)


@register('argmax_channel', input_names=('data',))
def _argmax_channel(attrs, data):
    return jnp.argmax(data, axis=1).astype(data.dtype)


# ---------------------------------------------------------------------------
# Matrix / linear algebra — reference matrix_op.cc (dot → MXU)
# ---------------------------------------------------------------------------

@register('dot', input_names=('lhs', 'rhs'))
def _dot(attrs, lhs, rhs):
    ta = asbool(attrs.get('transpose_a', False))
    tb = asbool(attrs.get('transpose_b', False))
    if ta:
        lhs = jnp.moveaxis(lhs, 0, -1) if lhs.ndim > 1 else lhs
    if tb:
        rhs = jnp.moveaxis(rhs, -1, 0) if rhs.ndim > 1 else rhs
    if lhs.ndim == 1 and rhs.ndim == 1:
        return jnp.dot(lhs, rhs).reshape((1,))
    return jnp.tensordot(lhs, rhs, axes=1)


@register('batch_dot', input_names=('lhs', 'rhs'))
def _batch_dot(attrs, lhs, rhs):
    ta = asbool(attrs.get('transpose_a', False))
    tb = asbool(attrs.get('transpose_b', False))
    if ta:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if tb:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


@register('transpose', input_names=('data',))
def _transpose(attrs, data):
    axes = parse_attr_value(attrs.get('axes', None))
    if axes is None or axes == ():
        axes = tuple(reversed(range(data.ndim)))
    return jnp.transpose(data, axes)


@register('SwapAxis', input_names=('data',), aliases=('swapaxes',))
def _swapaxes(attrs, data):
    return jnp.swapaxes(data, asint(attrs.get('dim1', 0)),
                        asint(attrs.get('dim2', 0)))


@register('expand_dims', input_names=('data',))
def _expand_dims(attrs, data):
    return jnp.expand_dims(data, asint(attrs['axis']))


def _reshape_target(shape_spec, ishape, reverse=False):
    """Implements reference Reshape special codes 0,-1,-2,-3,-4
    (src/operator/tensor/matrix_op-inl.h ReshapeInferShape)."""
    if reverse:
        rev = _reshape_target(tuple(reversed(shape_spec)),
                              tuple(reversed(ishape)), False)
        return tuple(reversed(rev))
    out = []
    src = list(ishape)
    i = 0  # position in src
    spec = list(shape_spec)
    j = 0
    infer_at = None
    while j < len(spec):
        s = spec[j]
        if s > 0:
            out.append(s)
            i += 1
        elif s == 0:
            out.append(src[i])
            i += 1
        elif s == -1:
            assert infer_at is None, 'only one -1 allowed in reshape'
            infer_at = len(out)
            out.append(1)
            i += 1
        elif s == -2:
            out.extend(src[i:])
            i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1])
            i += 2
        elif s == -4:
            d1, d2 = spec[j + 1], spec[j + 2]
            cur = src[i]
            if d1 == -1:
                d1 = cur // d2
            if d2 == -1:
                d2 = cur // d1
            out.extend([d1, d2])
            i += 1
            j += 2
        else:
            raise ValueError('bad reshape code %d' % s)
        j += 1
    if infer_at is not None:
        known = int(np.prod([d for k, d in enumerate(out) if k != infer_at]))
        total = int(np.prod(ishape)) if ishape else 1
        out[infer_at] = total // max(known, 1)
    return tuple(out)


@register('Reshape', input_names=('data',), aliases=('reshape',))
def _reshape(attrs, data):
    shape = astuple(attrs['shape'])
    reverse = asbool(attrs.get('reverse', False))
    return jnp.reshape(data, _reshape_target(shape, data.shape, reverse))


@register('Flatten', input_names=('data',), aliases=('flatten',))
def _flatten(attrs, data):
    return jnp.reshape(data, (data.shape[0], -1))


def _concat_names(attrs):
    return ['arg%d' % i for i in range(asint(attrs.get('num_args', 1)))]


@register('Concat', input_names=_concat_names, aliases=('concat',))
def _concat(attrs, *args):
    return jnp.concatenate(args, axis=asint(attrs.get('dim', 1)))


@register('SliceChannel', input_names=('data',), aliases=('split',),
          num_outputs=lambda attrs: asint(attrs['num_outputs']))
def _slice_channel(attrs, data):
    n = asint(attrs['num_outputs'])
    axis = normalize_axis(attrs.get('axis', 1), data.ndim)
    squeeze = asbool(attrs.get('squeeze_axis', False))
    outs = jnp.split(data, n, axis=axis)
    if squeeze:
        outs = [jnp.squeeze(o, axis=axis) for o in outs]
    return tuple(outs)


@register('slice', input_names=('data',), aliases=('crop',))
def _slice(attrs, data):
    begin = parse_attr_value(attrs['begin'])
    end = parse_attr_value(attrs['end'])
    if isinstance(begin, int):
        begin = (begin,)
    if isinstance(end, int):
        end = (end,)
    step = parse_attr_value(attrs.get('step', None)) or (None,) * len(begin)
    if isinstance(step, int):
        step = (step,)
    idx = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register('slice_axis', input_names=('data',))
def _slice_axis(attrs, data):
    axis = normalize_axis(attrs['axis'], data.ndim)
    begin = asint(attrs.get('begin', 0))
    end = parse_attr_value(attrs.get('end', None))
    idx = [slice(None)] * data.ndim
    idx[axis] = slice(begin, None if end is None else int(end))
    return data[tuple(idx)]


@register('reverse', input_names=('data',), aliases=('flip',))
def _reverse(attrs, data):
    axis = parse_attr_value(attrs['axis'])
    if isinstance(axis, int):
        axis = (axis,)
    return jnp.flip(data, axis=axis)


@register('tile', input_names=('data',))
def _tile(attrs, data):
    return jnp.tile(data, astuple(attrs['reps']))


@register('repeat', input_names=('data',))
def _repeat(attrs, data):
    repeats = asint(attrs['repeats'])
    axis = parse_attr_value(attrs.get('axis', None))
    if axis is None:
        return jnp.repeat(data.reshape(-1), repeats)
    return jnp.repeat(data, repeats, axis=int(axis))


@register('Pad', input_names=('data',), aliases=('pad',))
def _pad(attrs, data):
    pw = astuple(attrs['pad_width'])
    mode = str(parse_attr_value(attrs.get('mode', 'constant')))
    pads = tuple((pw[2 * i], pw[2 * i + 1]) for i in range(data.ndim))
    if mode == 'constant':
        cv = asfloat(attrs.get('constant_value', 0.0))
        return jnp.pad(data, pads, mode='constant', constant_values=cv)
    return jnp.pad(data, pads, mode={'edge': 'edge', 'reflect': 'reflect'}[mode])


@register('stack', input_names=_concat_names)
def _stack(attrs, *args):
    return jnp.stack(args, axis=asint(attrs.get('axis', 0)))


@register('space_to_depth', input_names=('data',))
def _space_to_depth(attrs, data):
    bs = asint(attrs['block_size'])
    n, c, h, w = data.shape
    x = data.reshape(n, c, h // bs, bs, w // bs, bs)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return x.reshape(n, c * bs * bs, h // bs, w // bs)


@register('depth_to_space', input_names=('data',))
def _depth_to_space(attrs, data):
    bs = asint(attrs['block_size'])
    n, c, h, w = data.shape
    x = data.reshape(n, bs, bs, c // (bs * bs), h, w)
    x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
    return x.reshape(n, c // (bs * bs), h * bs, w * bs)


# ---------------------------------------------------------------------------
# Indexing — reference indexing_op.cc
# ---------------------------------------------------------------------------

def _embedding_infer_shape(attrs, in_shapes):
    if in_shapes[1] is None:
        in_shapes[1] = (asint(attrs['input_dim']), asint(attrs['output_dim']))
    return in_shapes


# Sparse-embedding interception point, bound by parallel/embedding.py at
# import (the same late-binding pattern parameter.py uses for
# _lookup_param_substitution): inside a capture/override scope the hook
# records the traced ids or serves the deduped-rows lookup; outside any
# scope it returns None and the dense gather below runs.  No scope can
# exist before parallel.embedding is imported, so the default None never
# misses one.
_embed_hook = None


def _embedding_infer_dtype(attrs, in_dtypes):
    """The table's type is the output's; `dtype` names it where nothing
    upstream does (the ids are float32 whatever the table holds)."""
    f32 = np.dtype(np.float32)
    if attrs.get('dtype') is not None:
        table = _dtype(attrs)
    else:
        table = in_dtypes[1] if in_dtypes[1] is not None else f32
    return [in_dtypes[0] if in_dtypes[0] is not None else f32, table], \
        [table]


@register('Embedding', input_names=('data', 'weight'),
          infer_shape=_embedding_infer_shape,
          infer_dtype=_embedding_infer_dtype)
def _embedding(attrs, data, weight):
    if _embed_hook is not None:
        out = _embed_hook(attrs, data, weight)
        if out is not None:
            return out
    idx = data.astype(jnp.int32)
    # reference EmbeddingOpForward clips out-of-range ids (negative or
    # >= input_dim) to the table edge; jnp.take's default 'fill' mode
    # would return zeros/NaN-adjacent garbage instead
    return jnp.take(weight, idx, axis=0, mode='clip')


@register('take', input_names=('a', 'indices'))
def _take(attrs, a, indices):
    axis = asint(attrs.get('axis', 0))
    mode = str(parse_attr_value(attrs.get('mode', 'clip')))
    if mode not in ('clip', 'wrap'):
        # 'raise' (and any typo) used to silently degrade to clip —
        # out-of-range ids then read the table edge with no signal
        raise MXNetError(
            "take: unsupported mode %r — this backend implements "
            "'clip' and 'wrap'; 'raise' needs a host-synchronous "
            "bounds check that a jitted program cannot express" % mode)
    idx = indices.astype(jnp.int32)
    return jnp.take(a, idx, axis=axis, mode=mode)


@register('batch_take', input_names=('a', 'indices'))
def _batch_take(attrs, a, indices):
    idx = indices.astype(jnp.int32)
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


@register('pick', input_names=('data', 'index'))
def _pick(attrs, data, index):
    """Pick elements along `axis` by per-position index
    (reference src/operator/tensor/broadcast_reduce_op_index.cc pick;
    axis defaults to -1 — flattened axis=None mode is not supported)."""
    axis = int(parse_attr_value(attrs.get('axis', -1)))
    keepdims = asbool(attrs.get('keepdims', False))
    idx = index.astype(jnp.int32)
    idx = jnp.expand_dims(idx, axis=axis)
    out = jnp.take_along_axis(data, idx, axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register('one_hot', input_names=('indices',))
def _one_hot(attrs, indices):
    depth = asint(attrs['depth'])
    on = asfloat(attrs.get('on_value', 1.0))
    off = asfloat(attrs.get('off_value', 0.0))
    dt = _dtype(attrs)
    idx = indices.astype(jnp.int32)
    oh = jax.nn.one_hot(idx, depth, dtype=dt)
    return (oh * (on - off) + off).astype(dt)


@register('where', input_names=('condition', 'x', 'y'))
def _where(attrs, condition, x, y):
    if condition.ndim != x.ndim:
        cond = condition.astype(bool).reshape(
            condition.shape + (1,) * (x.ndim - condition.ndim))
    else:
        cond = condition.astype(bool)
    return jnp.where(cond, x, y)


@register('gather_nd', input_names=('data', 'indices'))
def _gather_nd(attrs, data, indices):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    return data[tuple(idx[i] for i in range(m))]


@register('scatter_nd', input_names=('data', 'indices'))
def _scatter_nd(attrs, data, indices):
    shape = astuple(attrs['shape'])
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(shape, dtype=data.dtype)
    return out.at[tuple(idx[i] for i in range(m))].set(data)


@register('_backward_gather_nd', input_names=('data', 'indices'),
          aliases=('scatter_nd_acc',))
def _backward_gather_nd(attrs, data, indices):
    """Accumulating scatter (the reference's gather_nd gradient,
    indexing_op.cc GatherNDBackward): duplicate indices ADD instead of
    scatter_nd's undefined last-wins — the semantics a sparse gradient
    path needs, where several batch positions hit the same row."""
    shape = astuple(attrs['shape'])
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(shape, dtype=data.dtype)
    return out.at[tuple(idx[i] for i in range(m))].add(data)


# ---------------------------------------------------------------------------
# Ordering — reference ordering_op.cc
# ---------------------------------------------------------------------------

@register('sort', input_names=('data',))
def _sort(attrs, data):
    axis = parse_attr_value(attrs.get('axis', -1))
    is_ascend = asbool(attrs.get('is_ascend', True))
    if axis is None:
        out = jnp.sort(data.reshape(-1), axis=0)
        return out if is_ascend else out[::-1]
    out = jnp.sort(data, axis=int(axis))
    return out if is_ascend else jnp.flip(out, axis=int(axis))


@register('argsort', input_names=('data',))
def _argsort(attrs, data):
    axis = parse_attr_value(attrs.get('axis', -1))
    is_ascend = asbool(attrs.get('is_ascend', True))
    if axis is None:
        data = data.reshape(-1)
        axis = 0
    out = jnp.argsort(data, axis=int(axis))
    if not is_ascend:
        out = jnp.flip(out, axis=int(axis))
    return out.astype(attrs.get('dtype', data.dtype))


@register('topk', input_names=('data',),
          num_outputs=lambda attrs: 2 if str(parse_attr_value(
              attrs.get('ret_typ', 'indices'))) == 'both' else 1)
def _topk(attrs, data):
    axis = parse_attr_value(attrs.get('axis', -1))
    k = asint(attrs.get('k', 1))
    ret_typ = str(parse_attr_value(attrs.get('ret_typ', 'indices')))
    is_ascend = asbool(attrs.get('is_ascend', False))
    if axis is None:
        data = data.reshape(-1)
        axis = 0
    axis = normalize_axis(axis, data.ndim)
    x = jnp.moveaxis(data, axis, -1)
    vals, idx = jax.lax.top_k(-x if is_ascend else x, k)
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)
    if ret_typ == 'value':
        return vals
    if ret_typ == 'indices':
        return idx.astype(data.dtype)
    if ret_typ == 'mask':
        oh = jax.nn.one_hot(idx, x.shape[-1], dtype=data.dtype)
        return jnp.moveaxis(oh.sum(axis=-2), -1, axis)
    # both
    return vals, idx.astype(data.dtype)


# ---------------------------------------------------------------------------
# Init ops — reference init_op.cc
# ---------------------------------------------------------------------------

def _init_shape(attrs, op_ctx):
    """Init-op shape: the attr may carry unknown 0-dims (reference
    TShape convention, e.g. zeros(shape=(0, H)) from rnn begin_state);
    bidirectional inference resolves them and the executor threads the
    resolved shape in via op_ctx.out_shapes."""
    shape = astuple(attrs['shape'])
    if any(d == 0 for d in shape) and op_ctx.out_shapes and \
            op_ctx.out_shapes[0] is not None:
        shape = tuple(op_ctx.out_shapes[0])
    return shape


@register('_zeros', input_names=(), aliases=('zeros',), simple=False,
          needs_out_shapes=True)
def _zeros(attrs, inputs, auxs, op_ctx):
    return [jnp.zeros(_init_shape(attrs, op_ctx),
                      dtype=_dtype(attrs))], []


@register('_ones', input_names=(), aliases=('ones',), simple=False,
          needs_out_shapes=True)
def _ones(attrs, inputs, auxs, op_ctx):
    return [jnp.ones(_init_shape(attrs, op_ctx), dtype=_dtype(attrs))], []


@register('_full', input_names=(), aliases=('full',), simple=False,
          needs_out_shapes=True)
def _full(attrs, inputs, auxs, op_ctx):
    return [jnp.full(_init_shape(attrs, op_ctx),
                     asfloat(attrs['value']), dtype=_dtype(attrs))], []


@register('_arange', input_names=(), aliases=('arange',))
def _arange(attrs):
    start = asfloat(attrs.get('start', 0))
    stop = parse_attr_value(attrs.get('stop', None))
    step = asfloat(attrs.get('step', 1.0))
    repeat = asint(attrs.get('repeat', 1))
    out = jnp.arange(start, None if stop is None else float(stop), step,
                     dtype=_dtype(attrs))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return out


@register('_eye', input_names=(), aliases=('eye',))
def _eye(attrs):
    n = asint(attrs['N'])
    m = parse_attr_value(attrs.get('M', None))
    k = asint(attrs.get('k', 0))
    return jnp.eye(n, None if not m else int(m), k, dtype=_dtype(attrs))


# ---------------------------------------------------------------------------
# N-ary sum — reference elemwise_sum.cc
# ---------------------------------------------------------------------------

@register('add_n', input_names=_concat_names,
          aliases=('ElementWiseSum', '_sum'))
def _add_n(attrs, *args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


# ---------------------------------------------------------------------------
# Slice-assign — reference tensor/matrix_op.cc:289 (_slice_assign /
# _crop_assign) and :314 (_crop_assign_scalar): functional form of
# lhs[begin:end] = rhs (the imperative NDArray.__setitem__ path already
# exists; these are the graph ops).
# ---------------------------------------------------------------------------

def _assign_slices(attrs, shape):
    begin = astuple(attrs['begin'])
    end = astuple(attrs['end'])
    idx = tuple(slice(int(b), int(e)) for b, e in zip(begin, end))
    return idx + tuple(slice(None) for _ in range(len(shape) - len(idx)))


@register('_slice_assign', input_names=('lhs', 'rhs'),
          aliases=('_crop_assign',), hint='slice_assign')
def _slice_assign(attrs, lhs, rhs):
    idx = _assign_slices(attrs, lhs.shape)
    return lhs.at[idx].set(rhs.astype(lhs.dtype))


@register('_crop_assign_scalar', input_names=('data',),
          hint='crop_assign_scalar')
def _crop_assign_scalar(attrs, data):
    idx = _assign_slices(attrs, data.shape)
    val = asfloat(attrs.get('scalar', 0.0))
    return data.at[idx].set(np.dtype(data.dtype).type(val))
