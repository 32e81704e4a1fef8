"""The plain reference for convolutional classifiers: float32, jax.numpy.

It imports nothing of the program under test.  A network is a function
`forward(net, x, **arguments)` in a file of its own beside this one; it
calls the layers below on a `Net`, which looks parameters up by the name
the published symbol files give them.  The same call serves three ends:

* with no parameters (`Net()`), under `jax.eval_shape`, it records every
  parameter's shape and every convolution's and dense layer's shapes,
  from which `init_params` makes the weights and `benchmark/work.py`
  counts operations and bytes;
* with parameters it is the float32 forward pass, every product at
  `Precision.HIGHEST` (a TPU otherwise multiplies float32 in bfloat16);
* with `lowp='int8'` it is the control: the same mathematics with every
  convolution and dense product fed int8 values (activations, weights
  and the gradients coming back), per-tensor scales, the step a later
  PR would be tempted by after bfloat16;
* with `lowp='bfloat16'` it is a second witness for what bfloat16 alone
  costs: every product's operands and result, and every BatchNorm's
  result, rounded to bfloat16 on the way forward and on the way back.

Layout is NCHW / OIHW, as the symbol files have it.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _int8_forward(x):
    return _int8(x)


_int8_forward.defvjp(lambda x: (_int8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _int8_backward(y):
    return y


_int8_backward.defvjp(lambda y: (y, None), lambda _, g: (_int8(g),))


def round_to(x, dtype):
    """float32 values rounded to `dtype`'s precision.  Not a pair of
    casts: inside a jit the TPU compiler removes f32->bf16->f32 as excess
    precision, and the weights would then differ from the program's."""
    info = jnp.finfo(dtype)
    return lax.reduce_precision(x, exponent_bits=info.nexp,
                                mantissa_bits=info.nmant)


def _both_ways(rounding):
    """x -> rounding(x) whose gradient is rounded the same way."""
    @jax.custom_vjp
    def f(x):
        return rounding(x)

    f.defvjp(lambda x: (rounding(x), None), lambda _, g: (rounding(g),))
    return f


_bf16 = _both_ways(lambda x: round_to(x, jnp.bfloat16))


class Net:
    """One evaluation of a network: parameters in, layer records out."""

    def __init__(self, params=None, lowp=None, remat=True):
        if lowp not in (None, 'int8', 'bfloat16'):
            raise ValueError('unknown lower precision %r' % (lowp,))
        self.params = params
        self.lowp = lowp
        self.remat = remat
        self.spec = {}      # name -> {'shape', 'init', 'lowp', 'aux'}
        self.layers = []    # convolutions and dense layers, with shapes
        self.data = None    # the network's input: no gradient flows to it

    # -- parameters --------------------------------------------------------
    def param(self, name, shape, init, lowp=False, aux=False):
        shape = tuple(int(d) for d in shape)
        if self.params is None:
            self.spec[name] = {'shape': shape, 'init': init, 'lowp': lowp,
                               'aux': aux}
            return jnp.zeros(shape, jnp.float32)
        value = self.params[name]
        if tuple(value.shape) != shape:
            raise ValueError('%s: shape %s, the network wants %s'
                             % (name, tuple(value.shape), shape))
        return value

    def block(self, fn, x):
        """A run of layers whose activations are recomputed in the
        backward pass, so that float32 at the published batch fits."""
        if self.remat and self.params is not None:
            return jax.checkpoint(fn)(x)
        return fn(x)

    def _product(self, fn, x, w):
        if self.lowp == 'int8':
            return _int8_backward(fn(_int8_forward(x), _int8_forward(w)))
        if self.lowp == 'bfloat16':
            return _bf16(fn(_bf16(x), _bf16(w)))
        return fn(x, w)

    # -- layers ------------------------------------------------------------
    def cast_data(self, x):
        """The symbol's Cast on the data: the values the network sees
        are the stored ones rounded to the compute type."""
        self.data = x
        return x

    def conv(self, name, x, num_filter, kernel, stride=(1, 1), pad=(0, 0)):
        w = self.param(name + '_weight',
                       (num_filter, x.shape[1]) + tuple(kernel), 'he_in',
                       lowp=True)
        y = self._product(
            lambda a, b: lax.conv_general_dilated(
                a, b, tuple(stride), [(p, p) for p in pad],
                dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
                precision=HIGHEST), x, w)
        self.layers.append({'name': name, 'kind': 'conv', 'x': x.shape,
                            'w': w.shape, 'y': y.shape,
                            'needs_dx': x is not self.data})
        return y

    def dense(self, name, x, num_hidden):
        w = self.param(name + '_weight', (num_hidden, x.shape[1]), 'he_in',
                       lowp=True)
        b = self.param(name + '_bias', (num_hidden,), 'zeros', lowp=True)
        y = self._product(
            lambda a, c: jnp.dot(a, c.T, precision=HIGHEST), x, w) + b
        self.layers.append({'name': name, 'kind': 'dense', 'x': x.shape,
                            'w': w.shape, 'y': y.shape, 'needs_dx': True})
        return y

    def batchnorm(self, name, x, eps, fix_gamma=False):
        """Training mode: the batch's own mean and biased variance."""
        c = x.shape[1]
        gamma = self.param(name + '_gamma', (c,), 'ones')
        beta = self.param(name + '_beta', (c,), 'zeros')
        self.param(name + '_moving_mean', (c,), 'zeros', aux=True)
        self.param(name + '_moving_var', (c,), 'ones', aux=True)
        if fix_gamma:
            gamma = jnp.ones_like(lax.stop_gradient(gamma))
        mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
        xhat = (x - mean) * lax.rsqrt(var + eps)
        y = xhat * gamma.reshape(1, c, 1, 1) + beta.reshape(1, c, 1, 1)
        return _bf16(y) if self.lowp == 'bfloat16' else y

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0.0)

    @staticmethod
    def pool(x, kernel, stride, pad=(0, 0), pool_type='max'):
        """'valid' convention; an average counts the padding in its
        divisor (the reference framework's default)."""
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
        if pool_type == 'max':
            return lax.reduce_window(x, -jnp.inf, lax.max, window, strides,
                                     padding)
        if pool_type != 'avg':
            raise ValueError('pool_type %r' % (pool_type,))
        total = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        return total / float(kernel[0] * kernel[1])

    @staticmethod
    def global_avg_pool(x):
        return jnp.mean(x, axis=(2, 3))

    @staticmethod
    def concat(*xs):
        return jnp.concatenate(xs, axis=1)


def describe(forward, arguments, data_shape):
    """Parameter specification and layer shapes of one network at one
    input shape, with nothing computed."""
    net = Net()
    jax.eval_shape(lambda x: forward(net, x, **arguments),
                   jax.ShapeDtypeStruct(tuple(data_shape), jnp.float32))
    return net.spec, net.layers


def _fan_in(shape):
    return int(shape[1]) * int(math.prod(shape[2:]))


def make_init(spec, lowp_dtype=jnp.bfloat16):
    """A jitted key -> {name: float32 array}: every parameter in one
    call on the default device.  He-normal (variance 2 / fan-in) for
    convolution and dense weights, ones and zeros for the rest.
    Parameters the program keeps in its compute type are rounded to it
    here, so that the program and the reference start from the same
    numbers."""
    names = sorted(spec)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            s = spec[name]
            if s['init'] == 'he_in':
                std = math.sqrt(2.0 / _fan_in(s['shape']))
                v = std * jax.random.normal(jax.random.fold_in(key, i),
                                            s['shape'], jnp.float32)
            elif s['init'] == 'ones':
                v = jnp.ones(s['shape'], jnp.float32)
            elif s['init'] == 'zeros':
                v = jnp.zeros(s['shape'], jnp.float32)
            else:
                raise ValueError('init %r' % (s['init'],))
            if s['lowp']:
                v = round_to(v, lowp_dtype)
            out[name] = v
        return out

    return make


def cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None],
                                 axis=1)
    return -jnp.mean(picked)


def decays(name):
    """Weight decay reaches weights and BatchNorm scales, not biases or
    shifts (the reference framework's default multipliers)."""
    return name.endswith(('_weight', '_gamma'))


def make_train_step(forward, arguments, optimizer, lowp=None, remat=True,
                    rows=None):
    """SGD with momentum and weight decay on float32 parameters:
    m <- momentum*m - lr*(g + wd*w); w <- w + m, with g the gradient of
    the mean cross-entropy.  `rows`: a planted fault for the tests, the
    loss taken over the first `rows` rows only.  Returns a jitted
    step(train, moms, aux, x, labels) -> (train, moms, loss); `aux`
    holds BatchNorm's moving statistics, which training does not read."""
    lr = float(optimizer['learning_rate'])
    wd = float(optimizer.get('wd', 0.0))
    momentum = float(optimizer.get('momentum', 0.0))

    def loss_fn(train, aux, x, labels):
        net = Net({**train, **aux}, lowp=lowp, remat=remat)
        return cross_entropy(forward(net, x, **arguments), labels)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(train, moms, aux, x, labels):
        x = x.astype(jnp.float32)
        if rows is not None:
            x, labels = x[:rows], labels[:rows]
        loss, grads = jax.value_and_grad(loss_fn)(train, aux, x, labels)
        new_train, new_moms = {}, {}
        for name, w in train.items():
            g = grads[name] + (wd * w if decays(name) else 0.0)
            m = momentum * moms[name] - lr * g
            new_moms[name] = m
            new_train[name] = w + m
        return new_train, new_moms, loss

    return step
