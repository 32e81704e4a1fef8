"""Neural-network layer operators.

TPU-native re-implementation of the reference's src/operator/*.{cc,cu}
layer zoo (convolution, batch_norm, pooling, activation, dropout, loss
output ops… SURVEY.md §2.3).  Where the reference hand-picks cuDNN
algorithms and manages per-op workspaces, here every layer is a pure JAX
function: convs/matmuls lower to MXU ops via lax.conv_general_dilated /
tensordot, and XLA fuses the elementwise epilogues (bias, activation,
batch-norm scale) into them — the fusion the reference could only get
from cuDNN fused paths.

Loss ops (SoftmaxOutput & friends) replicate the reference's semantics of
*ignoring the incoming head gradient* (softmax_output-inl.h backward is
`softmax(x) - onehot(label)` regardless of out_grad) via jax.custom_vjp,
so `Executor.backward()` with no head grads behaves exactly like the
reference executor.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import (register, astuple, asbool, asint, asfloat,
                       normalize_axis)
from ..base import parse_attr_value


# ---------------------------------------------------------------------------
# FullyConnected — reference src/operator/fully_connected-inl.h
# ---------------------------------------------------------------------------

def _fc_names(attrs):
    if asbool(attrs.get('no_bias', False)):
        return ['data', 'weight']
    return ['data', 'weight', 'bias']


def _fc_infer_shape(attrs, in_shapes):
    num_hidden = asint(attrs['num_hidden'])
    flatten = asbool(attrs.get('flatten', True))
    if in_shapes[0] is not None and in_shapes[1] is None:
        d = in_shapes[0]
        # feature dims must be fully known (batch may still be the
        # unknown 0 placeholder) before the weight shape can backfill
        if all(x != 0 for x in d[1:]):
            in_dim = int(np.prod(d[1:])) if flatten else d[-1]
            in_shapes[1] = (num_hidden, in_dim)
    if len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes[2] = (num_hidden,)
    return in_shapes


def _fc_infer_shape_bwd(attrs, in_shapes, out_shapes):
    """Batch dim flows output -> data (bidirectional InferShape:
    resolves zeros(shape=(0, H)) initial states fed through h2h
    projections, reference rnn begin_state)."""
    out = out_shapes[0] if out_shapes else None
    d = in_shapes[0]
    if out is not None and out[0] != 0 and d is not None and d[0] == 0:
        in_shapes[0] = (out[0],) + tuple(d[1:])
    return in_shapes


@register('FullyConnected', input_names=_fc_names,
          infer_shape=_fc_infer_shape, infer_shape_bwd=_fc_infer_shape_bwd,
          hint='fullyconnected')
def _fully_connected(attrs, data, weight, bias=None):
    flatten = asbool(attrs.get('flatten', True))
    if flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    out = jnp.tensordot(x, weight.T, axes=1)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Activation — reference src/operator/activation-inl.h
# ---------------------------------------------------------------------------

_ACTS = {
    'relu': jax.nn.relu,
    'sigmoid': jax.nn.sigmoid,
    'tanh': jnp.tanh,
    'softrelu': jax.nn.softplus,
    'softsign': jax.nn.soft_sign,
    'silu': jax.nn.silu,
}


@register('Activation', input_names=('data',), hint='activation')
def _activation(attrs, data):
    return _ACTS[str(parse_attr_value(attrs['act_type']))](data)


@register('LeakyReLU', input_names=lambda attrs: (
    ['data', 'gamma'] if str(parse_attr_value(attrs.get('act_type', 'leaky'))) == 'prelu'
    else ['data']), hint='leakyrelu',
    infer_shape=lambda attrs, s: (
        s if len(s) < 2 or s[1] is not None or s[0] is None
        else [s[0], (s[0][1],)]))
def _leaky_relu(attrs, data, gamma=None):
    act = str(parse_attr_value(attrs.get('act_type', 'leaky')))
    slope = asfloat(attrs.get('slope', 0.25))
    if act == 'prelu':
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act == 'elu':
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    # leaky / rrelu(test-mode uses mean slope)
    if act == 'rrelu':
        lo = asfloat(attrs.get('lower_bound', 0.125))
        hi = asfloat(attrs.get('upper_bound', 0.334))
        slope = (lo + hi) / 2.0
    return jnp.where(data >= 0, data, slope * data)


# ---------------------------------------------------------------------------
# Softmax family — reference src/operator/tensor/nn/softmax.cc
# ---------------------------------------------------------------------------

@register('softmax', input_names=('data',))
def _softmax(attrs, data):
    axis = asint(attrs.get('axis', -1))
    t = parse_attr_value(attrs.get('temperature', None))
    x = data / t if t else data
    return jax.nn.softmax(x, axis=axis)


@register('log_softmax', input_names=('data',))
def _log_softmax(attrs, data):
    axis = asint(attrs.get('axis', -1))
    return jax.nn.log_softmax(data, axis=axis)


@register('SoftmaxActivation', input_names=('data',), hint='softmaxactivation')
def _softmax_activation(attrs, data):
    mode = str(parse_attr_value(attrs.get('mode', 'instance')))
    if mode == 'channel':
        return jax.nn.softmax(data, axis=1)
    flat = data.reshape(data.shape[0], -1)
    return jax.nn.softmax(flat, axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# Loss output ops — custom VJPs reproducing reference backward semantics
# ---------------------------------------------------------------------------

def _softmax_out_fwd_impl(params, data, label):
    multi_output, preserve_shape = params[3], params[5]
    axis = 1 if not preserve_shape and (multi_output or data.ndim > 2) \
        else -1
    # exponentials and their sum in float32 whatever the logits' type
    # (one fusion: nothing wider is stored); the output keeps the type
    return jax.nn.softmax(data.astype(jnp.float32),
                          axis=axis).astype(data.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _softmax_output_fn(params, data, label):
    return _softmax_out_fwd_impl(params, data, label)


def _softmax_output_bwd(params, res, g):
    grad_scale, ignore_label, use_ignore, multi_output, normalization, preserve_shape = params
    out, label = res
    if preserve_shape or (not multi_output and out.ndim <= 2):
        axis = out.ndim - 1
    else:
        axis = 1
    k = out.shape[axis]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, k, dtype=out.dtype)
    onehot = jnp.moveaxis(onehot, -1, axis)
    grad = out - onehot
    valid = None
    if use_ignore:
        mask = (lab != int(ignore_label)).astype(out.dtype)
        grad = grad * jnp.expand_dims(mask, axis)
        valid = jnp.maximum(mask.sum(), 1.0)
    grad = grad * grad_scale
    if normalization == 'batch':
        grad = grad / out.shape[0]
    elif normalization == 'valid':
        n = valid if valid is not None else float(np.prod(lab.shape))
        grad = grad / n
    # scale by the incoming cotangent: the executor always seeds loss
    # ops with ones (reference "ignores head grads" semantics —
    # executor._default_head_grads), so this is identity there, while
    # a ZERO cotangent — the pipelined engine masking the loss total
    # to the last pipe stage (parallel/pipeline.make_pipe_step_fn) —
    # correctly kills the gradient instead of leaking (p - y) from
    # every stage's garbage activations
    return grad * g, jnp.zeros_like(label)


_softmax_output_fn.defvjp(
    lambda params, data, label: (_softmax_out_fwd_impl(params, data, label),
                                 (_softmax_out_fwd_impl(params, data, label), label)),
    _softmax_output_bwd)


def _softmax_output_infer_dtype(attrs, in_dtypes):
    """The label does not follow a low-precision graph's type: class
    indices above 256 are not exact in bfloat16."""
    f32 = np.dtype(np.float32)
    d = np.dtype(in_dtypes[0]) if in_dtypes[0] is not None else f32
    return [d, in_dtypes[1] if in_dtypes[1] is not None else f32], [d]


@register('SoftmaxOutput', input_names=('data', 'label'),
          aliases=('Softmax',), hint='softmaxoutput',
          infer_dtype=_softmax_output_infer_dtype,
          infer_shape=lambda attrs, s: (
              s if s[0] is None or s[1] is not None
              else [s[0], _softmax_label_shape(attrs, s[0])]))
def _softmax_output(attrs, data, label):
    params = (asfloat(attrs.get('grad_scale', 1.0)),
              asfloat(attrs.get('ignore_label', -1.0)),
              asbool(attrs.get('use_ignore', False)),
              asbool(attrs.get('multi_output', False)),
              str(parse_attr_value(attrs.get('normalization', 'null'))),
              asbool(attrs.get('preserve_shape', False)))
    return _softmax_output_fn(params, data, label)


def _softmax_label_shape(attrs, dshape):
    if asbool(attrs.get('multi_output', False)) or len(dshape) > 2:
        return (dshape[0],) + tuple(dshape[2:])
    return (dshape[0],)


def _make_regression(name, fwd, grad):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def fn(grad_scale, data, label):
        return fwd(data)

    def fwd_rule(grad_scale, data, label):
        out = fwd(data)
        return out, (out, data, label)

    def bwd_rule(grad_scale, res, g):
        out, data, label = res
        lab = label.reshape(out.shape)
        # no batch normalization here — the optimizer's rescale_grad
        # (1/batch) carries it, as in the reference convention.  The
        # cotangent scale is identity under the executor's all-ones
        # seed and zeroes the gradient under the pipelined engine's
        # last-stage loss masking (see _softmax_output_bwd)
        return (grad(out, data, lab) * grad_scale * g,
                jnp.zeros_like(label))

    fn.defvjp(fwd_rule, bwd_rule)

    @register(name, input_names=('data', 'label'), hint=name.lower(),
              infer_shape=lambda attrs, s: (
                  s if s[0] is None or s[1] is not None else [s[0], s[0]]))
    def op(attrs, data, label):
        return fn(asfloat(attrs.get('grad_scale', 1.0)), data, label)
    return op


# Reference src/operator/regression_output-inl.h: backward ignores head
# grads; grad = f(out) - label (linear/logistic), sign(out - label) (MAE).
_make_regression('LinearRegressionOutput', lambda x: x,
                 lambda out, data, lab: out - lab)
_make_regression('LogisticRegressionOutput', jax.nn.sigmoid,
                 lambda out, data, lab: out - lab)
_make_regression('MAERegressionOutput', lambda x: x,
                 lambda out, data, lab: jnp.sign(out - lab))


@register('softmax_cross_entropy', input_names=('data', 'label'))
def _softmax_cross_entropy(attrs, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return nll.sum().reshape((1,))


# ---------------------------------------------------------------------------
# Convolution — reference src/operator/convolution-inl.h (+cudnn autotune);
# here a single lax.conv_general_dilated that XLA tiles onto the MXU.
# ---------------------------------------------------------------------------

def _conv_names(attrs):
    if asbool(attrs.get('no_bias', False)):
        return ['data', 'weight']
    return ['data', 'weight', 'bias']


def _conv_infer_shape(attrs, in_shapes):
    kernel = astuple(attrs['kernel'])
    num_filter = asint(attrs['num_filter'])
    num_group = asint(attrs.get('num_group', 1))
    if in_shapes[0] is not None and in_shapes[1] is None:
        c = in_shapes[0][1]
        in_shapes[1] = (num_filter, c // num_group) + kernel
    if len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes[2] = (num_filter,)
    return in_shapes


_CONV_DN = {1: ('NCW', 'OIW', 'NCW'),
            2: ('NCHW', 'OIHW', 'NCHW'),
            3: ('NCDHW', 'OIDHW', 'NCDHW')}

_CONV_NHWC = None


def _conv_prefer_nhwc():
    """TPU MXU tiling prefers channels-minor; compute 2-D convs in NHWC
    internally (user-facing layout stays NCHW — XLA cancels the
    boundary transposes between consecutive layers).  Env override
    MXNET_TPU_CONV_LAYOUT={nhwc,nchw,auto}; auto = NHWC on
    accelerators, NCHW on the CPU backend."""
    global _CONV_NHWC
    if _CONV_NHWC is None:
        import os
        pref = os.environ.get('MXNET_TPU_CONV_LAYOUT', 'auto')
        if pref == 'nhwc':
            _CONV_NHWC = True
        elif pref == 'nchw':
            _CONV_NHWC = False
        else:
            _CONV_NHWC = jax.default_backend() != 'cpu'
    return _CONV_NHWC


@register('Convolution', input_names=_conv_names,
          infer_shape=_conv_infer_shape, hint='convolution',
          aliases=('Convolution_v1',))
def _convolution(attrs, data, weight, bias=None):
    kernel = astuple(attrs['kernel'])
    nd = len(kernel)
    stride = astuple(attrs.get('stride', (1,) * nd), nd)
    dilate = astuple(attrs.get('dilate', (1,) * nd), nd)
    pad = astuple(attrs.get('pad', (0,) * nd), nd)
    num_group = asint(attrs.get('num_group', 1))
    nhwc_io = attrs.get('__layout__') == 'NHWC'
    if nd == 2 and (nhwc_io or _conv_prefer_nhwc()):
        # nhwc_io: the executor layout pass delivers data already
        # permuted and consumes the output permuted — no boundary
        # transposes here (they are exactly the non-cancelling HBM
        # passes the pass exists to remove)
        x = data if nhwc_io else jnp.transpose(data, (0, 2, 3, 1))
        w = jnp.transpose(weight, (2, 3, 1, 0))  # OIHW -> HWIO
        out = lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            feature_group_count=num_group)
        if bias is not None:
            out = out + bias.reshape((1, 1, 1, -1))
        return out if nhwc_io else jnp.transpose(out, (0, 3, 1, 2))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=_CONV_DN[nd],
        feature_group_count=num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


def _deconv_infer_shape(attrs, in_shapes):
    kernel = astuple(attrs['kernel'])
    num_filter = asint(attrs['num_filter'])
    num_group = asint(attrs.get('num_group', 1))
    if in_shapes[0] is not None and in_shapes[1] is None:
        c = in_shapes[0][1]
        in_shapes[1] = (c, num_filter // num_group) + kernel
    if len(in_shapes) > 2 and in_shapes[2] is None:
        in_shapes[2] = (num_filter,)
    return in_shapes


@register('Deconvolution', input_names=_conv_names,
          infer_shape=_deconv_infer_shape, hint='deconvolution')
def _deconvolution(attrs, data, weight, bias=None):
    """Transposed convolution (reference src/operator/deconvolution-inl.h).
    Weight layout (C_in, num_filter//group, *kernel); output size
    (i-1)*s + k - 2p + adj."""
    kernel = astuple(attrs['kernel'])
    nd = len(kernel)
    stride = astuple(attrs.get('stride', (1,) * nd), nd)
    pad = astuple(attrs.get('pad', (0,) * nd), nd)
    adj = astuple(attrs.get('adj', (0,) * nd), nd)
    num_group = asint(attrs.get('num_group', 1))
    ci = weight.shape[0]
    # (I, O/g, *k) -> grouped (O, I/g, *k) with spatial flip
    w = weight.reshape((num_group, ci // num_group) + weight.shape[1:])
    w = jnp.swapaxes(w, 1, 2)  # (g, O/g, I/g, *k)
    w = w.reshape((-1,) + w.shape[2:])  # (O, I/g, *k)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    padding = [(k - 1 - p, k - 1 - p + a)
               for k, p, a in zip(kernel, pad, adj)]
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, dimension_numbers=_CONV_DN[nd],
        feature_group_count=num_group)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling — reference src/operator/pooling-inl.h via lax.reduce_window
# ---------------------------------------------------------------------------

@register('Pooling', input_names=('data',), hint='pooling',
          aliases=('Pooling_v1',))
def _pooling(attrs, data):
    pool_type = str(parse_attr_value(attrs.get('pool_type', 'max')))
    global_pool = asbool(attrs.get('global_pool', False))
    # executor layout pass: data arrives channels-last; spatial dims
    # shift from (2..) to (1..ndim-1) and the output stays permuted
    nhwc_io = attrs.get('__layout__') == 'NHWC' and data.ndim == 4
    sp0 = 1 if nhwc_io else 2
    nspatial = data.ndim - 2
    if global_pool:
        axes = tuple(range(sp0, sp0 + nspatial))
        if pool_type == 'max':
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type == 'sum':
            return jnp.sum(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = astuple(attrs['kernel'])
    stride = astuple(attrs.get('stride', (1,) * nspatial), nspatial)
    pad = astuple(attrs.get('pad', (0,) * nspatial), nspatial)
    convention = str(parse_attr_value(attrs.get('pooling_convention', 'valid')))
    pads = []
    for i, (k, s, p) in enumerate(zip(kernel, stride, pad)):
        size = data.shape[sp0 + i]
        if convention == 'full':
            out = int(np.ceil((size + 2 * p - k) / s)) + 1
        else:
            out = (size + 2 * p - k) // s + 1
        hi = max((out - 1) * s + k - size - p, p)
        pads.append((p, hi))
    if nhwc_io:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padcfg = ((0, 0),) + tuple(pads) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        padcfg = ((0, 0), (0, 0)) + tuple(pads)
    if pool_type == 'max':
        # scalar -inf init so JAX recognizes the differentiable
        # reduce_window_max pattern
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, padcfg)
    out = lax.reduce_window(data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
                            lax.add, window, strides, padcfg)
    if pool_type == 'avg':
        # cuDNN COUNT_INCLUDE_PADDING semantics (reference default)
        out = out / float(np.prod(kernel))
    return out


# ---------------------------------------------------------------------------
# BatchNorm — reference src/operator/batch_norm-inl.h (aux moving stats)
# ---------------------------------------------------------------------------

def _bn_infer_shape(attrs, in_shapes):
    if in_shapes[0] is not None:
        axis = normalize_axis(attrs.get('axis', 1), len(in_shapes[0]))
        c = (in_shapes[0][axis],)
        for i in range(1, len(in_shapes)):
            if in_shapes[i] is None:
                in_shapes[i] = c
    return in_shapes


def _bn_infer_dtype(attrs, in_dtypes):
    """Mixed precision: scale/bias and the moving statistics stay
    float32 regardless of the compute dtype (the reference's cuDNN BN
    keeps fp32 params/stats for fp16 inputs); output follows data."""
    d = np.dtype(in_dtypes[0]) if in_dtypes[0] is not None \
        else np.dtype(np.float32)
    f32 = np.dtype(np.float32)
    n_out = 3 if asbool(attrs.get('output_mean_var', False)) else 1
    return [d, f32, f32, f32, f32], [d] + [f32] * (n_out - 1)


def _bn_compute(attrs, inputs, auxs, op_ctx):
    """HBM-friendly formulation: statistics in ONE pass over the data
    (fused convert+sum of x and x**2 with fp32 accumulation — the
    two-pass mean/var costs an extra full read of the activation), and
    the normalize applied as a per-channel scale/shift multiply-add in
    the input dtype, so the elementwise pass moves bf16 bytes while all
    statistic math stays fp32 (the reference's cuDNN BN keeps fp32
    stats for fp16 data the same way)."""
    data, gamma, beta = inputs
    moving_mean, moving_var = auxs
    in_dtype = data.dtype
    eps = asfloat(attrs.get('eps', 1e-3))
    momentum = asfloat(attrs.get('momentum', 0.9))
    fix_gamma = asbool(attrs.get('fix_gamma', True))
    use_global = asbool(attrs.get('use_global_stats', False))
    output_mean_var = asbool(attrs.get('output_mean_var', False))
    axis = normalize_axis(attrs.get('axis', 1), data.ndim)
    if attrs.get('__layout__') == 'NHWC' and axis == 1 and \
            data.ndim == 4:
        # executor layout pass: data is channels-last
        axis = 3
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    bshape = tuple(shape)
    if fix_gamma:
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    gamma = gamma.astype(jnp.float32)
    beta = beta.astype(jnp.float32)
    red = tuple(i for i in range(data.ndim) if i != axis)

    def apply(mean, var):
        scale = gamma * lax.rsqrt(var + eps)
        shift = beta - mean * scale
        out = data * scale.astype(in_dtype).reshape(bshape) + \
            shift.astype(in_dtype).reshape(bshape)
        return out.astype(in_dtype)

    if op_ctx.is_train and not use_global:
        nelem = 1
        for i in red:
            nelem *= data.shape[i]
        dataf = data.astype(jnp.float32)
        if data.dtype == jnp.float32:
            # full precision: two-pass variance (E[(x-m)^2]) — the
            # one-pass E[x^2]-m^2 cancels catastrophically when
            # |mean| >> std, and for f32 data the extra read is the
            # accuracy-bearing path, not the perf path
            mean = jnp.mean(dataf, axis=red)
            var = jnp.var(dataf, axis=red)
        else:
            # low precision (the training hot path): one pass over the
            # activation for both sums; the input's own quantization
            # (bf16 ~0.4% relative) dominates the cancellation error
            # for any realistically-normalized activation
            mean = jnp.sum(dataf, axis=red) / nelem
            var = jnp.maximum(
                jnp.sum(dataf * dataf, axis=red) / nelem - mean * mean,
                0.0)
        smean, svar = lax.stop_gradient(mean), lax.stop_gradient(var)
        new_mean = moving_mean * momentum + smean * (1 - momentum)
        new_var = moving_var * momentum + svar * (1 - momentum)
        outs = [apply(mean, var), mean, var] if output_mean_var \
            else [apply(mean, var)]
        return outs, [new_mean, new_var]
    out = apply(moving_mean, moving_var)
    outs = [out, moving_mean, moving_var] if output_mean_var else [out]
    return outs, [moving_mean, moving_var]


register('BatchNorm', input_names=('data', 'gamma', 'beta',
                                   'moving_mean', 'moving_var'),
         num_aux=2, mutable_aux=True, mode_dependent=True,
         infer_shape=_bn_infer_shape, infer_dtype=_bn_infer_dtype,
         hint='batchnorm',
         num_outputs=lambda attrs: 3 if asbool(attrs.get('output_mean_var', False)) else 1,
         output_names=lambda attrs: (['output', 'mean', 'var']
                                     if asbool(attrs.get('output_mean_var', False))
                                     else ['output']),
         aliases=('BatchNorm_v1',), simple=False)(_bn_compute)


def _in_infer_shape(attrs, in_shapes):
    if in_shapes[0] is not None:
        c = (in_shapes[0][1],)
        for i in (1, 2):
            if in_shapes[i] is None:
                in_shapes[i] = c
    return in_shapes


@register('InstanceNorm', input_names=('data', 'gamma', 'beta'),
          infer_shape=_in_infer_shape, hint='instancenorm')
def _instance_norm(attrs, data, gamma, beta):
    eps = asfloat(attrs.get('eps', 1e-3))
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return ((data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape)
            + beta.reshape(bshape))


@register('L2Normalization', input_names=('data',), hint='l2normalization')
def _l2_normalization(attrs, data):
    eps = asfloat(attrs.get('eps', 1e-10))
    mode = str(parse_attr_value(attrs.get('mode', 'instance')))
    if mode == 'instance':
        red = tuple(range(1, data.ndim))
    elif mode == 'channel':
        red = (1,)
    else:  # spatial
        red = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / norm


@register('LRN', input_names=('data',), hint='lrn')
def _lrn(attrs, data):
    """Local response norm across channels
    (reference src/operator/lrn-inl.h)."""
    nsize = asint(attrs['nsize'])
    alpha = asfloat(attrs.get('alpha', 1e-4))
    beta = asfloat(attrs.get('beta', 0.75))
    knorm = asfloat(attrs.get('knorm', 2.0))
    sq = jnp.square(data)
    # pad so output channel count == input for both odd and even nsize
    lo, hi = nsize // 2, (nsize - 1) // 2
    acc = lax.reduce_window(sq, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
                            lax.add, (1, nsize, 1, 1), (1, 1, 1, 1),
                            ((0, 0), (lo, hi), (0, 0), (0, 0)))
    return data / jnp.power(knorm + alpha / nsize * acc, beta)


# ---------------------------------------------------------------------------
# Dropout — reference src/operator/dropout-inl.h
# ---------------------------------------------------------------------------

def _dropout_compute(attrs, inputs, auxs, op_ctx):
    data, = inputs
    p = asfloat(attrs.get('p', 0.5))
    mode = str(parse_attr_value(attrs.get('mode', 'training')))
    if (op_ctx.is_train or mode == 'always') and p > 0:
        keep = 1.0 - p
        mask = jax.random.bernoulli(op_ctx.rng, keep, data.shape)
        return [jnp.where(mask, data / keep, jnp.zeros_like(data))], []
    return [data], []


register('Dropout', input_names=('data',), needs_rng=True,
         mode_dependent=True, hint='dropout', simple=False)(_dropout_compute)


# ---------------------------------------------------------------------------
# Sequence ops — reference src/operator/sequence_{last,mask,reverse}-inl.h
# Layout (max_sequence_length, batch, ...)
# ---------------------------------------------------------------------------

def _seq_names(attrs):
    if asbool(attrs.get('use_sequence_length', False)):
        return ['data', 'sequence_length']
    return ['data']


@register('SequenceLast', input_names=_seq_names, hint='sequencelast')
def _sequence_last(attrs, data, sequence_length=None):
    if sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1)
    batch = jnp.arange(data.shape[1])
    return data[idx, batch]


@register('SequenceMask', input_names=_seq_names, hint='sequencemask')
def _sequence_mask(attrs, data, sequence_length=None):
    if sequence_length is None:
        return data
    value = asfloat(attrs.get('value', 0.0))
    steps = jnp.arange(data.shape[0])
    mask = steps[:, None] < sequence_length.astype(jnp.int32)[None, :]
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, np.dtype(data.dtype).type(value))


@register('SequenceReverse', input_names=_seq_names, hint='sequencereverse')
def _sequence_reverse(attrs, data, sequence_length=None):
    if sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)
    lens = sequence_length.astype(jnp.int32)[None, :]
    src = jnp.where(steps[:, None] < lens, lens - 1 - steps[:, None],
                    steps[:, None])
    batch = jnp.arange(data.shape[1])[None, :]
    return data[src, batch]


# ---------------------------------------------------------------------------
# UpSampling — reference src/operator/upsampling-inl.h (nearest)
# ---------------------------------------------------------------------------

@register('UpSampling', input_names=lambda attrs: (
    ['arg%d' % i for i in range(asint(attrs.get('num_args', 1)))]
    if str(parse_attr_value(attrs.get('sample_type', 'nearest'))) == 'nearest'
    else ['data', 'weight']), hint='upsampling')
def _upsampling(attrs, *args):
    scale = asint(attrs['scale'])
    sample_type = str(parse_attr_value(attrs.get('sample_type', 'nearest')))
    if sample_type == 'nearest':
        outs = []
        for data in args:
            x = jnp.repeat(data, scale, axis=2)
            x = jnp.repeat(x, scale, axis=3)
            outs.append(x)
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=1)
    data = args[0]
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale),
                            method='bilinear')


@register('Crop', input_names=lambda attrs: (
    ['data', 'crop_like'] if asint(attrs.get('num_args', 1)) > 1 else ['data']),
    hint='crop')
def _crop(attrs, data, crop_like=None):
    if crop_like is not None:
        th, tw = crop_like.shape[2], crop_like.shape[3]
    else:
        th, tw = astuple(attrs['h_w'], 2)
    center = asbool(attrs.get('center_crop', False))
    if center:
        oh = (data.shape[2] - th) // 2
        ow = (data.shape[3] - tw) // 2
    else:
        offset = astuple(attrs.get('offset', (0, 0)), 2)
        oh, ow = offset
    return data[:, :, oh:oh + th, ow:ow + tw]
