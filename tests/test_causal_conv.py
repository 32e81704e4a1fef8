"""CausalConv1D's kernels (pallas_ops.causal_conv1d, interpret mode off
the TPU) against the operator's XLA statement before them, kept here as
the plain reference: forward, dx and dw in bfloat16 and float32, widths
2 to 4, one sequence and three, several blocks of rows and of lanes;
which path each shape takes, and what the counter says of it."""
import collections
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import pallas_ops, profiler
from mxnet_tpu.ops import lm

F32 = jnp.float32


def reference(x, w):
    """y[b, t, c] = sum_j w[c, j] * x[b, t - (W-1) + j, c] in float32,
    as ops/lm.py stated it before the kernels."""
    width, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (width - 1, 0), (0, 0)))
    wf = w.astype(F32)
    return sum(xp[:, j:j + t] * wf[:, j] for j in range(width)).astype(
        x.dtype)


# blocks of 16 rows and 128 lanes: (B, 64, 384) is 4 blocks of rows by 3
# of lanes, and a block's halo one tile of rows (8 of float32, 16 of
# bfloat16: the whole block before)
SMALL_BLOCKS = (16, 128)
T, C = 64, 384


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(pallas_ops, 'CONV_ROWS', SMALL_BLOCKS[0])
    monkeypatch.setattr(pallas_ops, 'CONV_LANES', SMALL_BLOCKS[1])


def _inputs(dtype, width, bsz):
    keys = jax.random.split(jax.random.PRNGKey(7 * width + bsz), 3)
    x = jax.random.normal(keys[0], (bsz, T, C), F32).astype(dtype)
    w = jax.random.normal(keys[1], (C, width), F32).astype(dtype)
    dy = jax.random.normal(keys[2], (bsz, T, C), F32).astype(dtype)
    return x, w, dy


@functools.lru_cache(maxsize=None)
def _both(dtype, width, bsz):
    """(y, dx, dw) of the kernels and of the reference."""
    x, w, dy = _inputs(dtype, width, bsz)
    out = {}
    for name, fn in (('kernel', pallas_ops.causal_conv1d),
                     ('reference', reference)):
        y, vjp = jax.vjp(fn, x, w)
        out[name] = dict(zip(('y', 'dx', 'dw'), (y,) + vjp(dy)))
    return out


@pytest.mark.parametrize('bsz', [1, 3])
@pytest.mark.parametrize('width', [2, 3, 4])
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('what', ['y', 'dx', 'dw'])
def test_kernels_match_the_xla_statement(small_blocks, what, dtype, width,
                                         bsz):
    """Each result in the input's type, equal to the reference's to
    float32's rounding (bfloat16: to one unit in the last place of the
    reference's largest element, where the float32 sums' order may
    round a few the other way)."""
    assert pallas_ops._conv_plan(jnp.zeros((bsz, T, C), dtype))[:2] == \
        SMALL_BLOCKS
    out = _both(dtype, width, bsz)
    got, want = out['kernel'][what], out['reference'][what]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max()
    tol = 2e-6 if dtype == jnp.float32 else 2 ** -8
    assert np.abs(got - want).max() <= tol * scale


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
def test_no_sequence_reads_another(small_blocks, dtype):
    """Sequence 1's first rows read no row of sequence 0 (its y is the
    same whatever sequence 0 holds), and its dx's last rows no row of
    sequence 2's dy."""
    x, w, dy = _inputs(dtype, 4, 3)
    other = (1e3 * jax.random.normal(jax.random.PRNGKey(99), x.shape,
                                     F32)).astype(dtype)
    x2 = x.at[0].set(other[0]).at[2].set(other[2])
    dy2 = dy.at[0].set(other[0]).at[2].set(other[2])
    for xs, dys in ((x, dy), (x2, dy2)):
        y, vjp = jax.vjp(pallas_ops.causal_conv1d, xs, w)
        if xs is x:
            y1, dx1 = y[1], vjp(dys)[0][1]
        else:
            np.testing.assert_array_equal(np.asarray(y[1], F32),
                                          np.asarray(y1, F32))
            np.testing.assert_array_equal(np.asarray(vjp(dys)[0][1], F32),
                                          np.asarray(dx1, F32))


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name != 'pallas_call':
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _primitives(sub)


def _kernel_names(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            names.append(eqn.params['name'])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names += _kernel_names(sub)
    return names


@pytest.mark.parametrize('what,kernels', [
    ('forward', ['causal_conv1d']),
    ('gradient', ['causal_conv1d', 'causal_conv1d_bwd'])])
def test_a_kernel_shape_lowers_to_the_kernels(what, kernels):
    """At a shape conv_fits() takes, the forward is one pallas_call and
    the gradient that and the backward's, with no pad of x left."""
    x, w, _ = _inputs(jnp.bfloat16, 4, 2)

    def conv(rows, w):
        return lm.causal_conv(rows, w, T)

    fn = {'forward': conv,
          'gradient': jax.grad(lambda x, w: jnp.sum(conv(x, w).astype(F32)),
                               argnums=(0, 1))}[what]
    jaxpr = jax.make_jaxpr(fn)(x.reshape(-1, C), w).jaxpr
    assert sorted(_kernel_names(jaxpr)) == kernels
    assert not collections.Counter(_primitives(jaxpr))['pad']


@pytest.mark.parametrize('t,c,width,dtype,fits', [
    (64, 384, 4, jnp.bfloat16, True), (64, 384, 4, jnp.float32, True),
    (8, 128, 9, jnp.float32, True), (8, 128, 10, jnp.float32, False),
    (6, 128, 4, jnp.float32, False), (24, 128, 4, jnp.bfloat16, False),
    (64, 100, 4, jnp.float32, False), (12, 5, 4, jnp.float32, False)])
def test_conv_fits_follows_the_shape(t, c, width, dtype, fits):
    """Rows whole sublane tiles of the type (8 of float32, 16 of
    bfloat16), whole lanes, and W - 1 rows within one tile."""
    assert pallas_ops.conv_fits(t, c, width, dtype) == fits


def _counted(fn):
    profiler.clear()
    fn()
    return profiler.causal_conv_stats()


def test_the_conformance_shape_takes_xla():
    """The operator's (12, 5) rows of sequences of 6 keep the XLA
    statement: no pallas_call, the reference's numbers, one 'xla'
    lowering of 2 sequences of 6 rows of 5 channels."""
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 5), F32)
    w = jax.random.normal(jax.random.PRNGKey(2), (5, 4), F32)

    def op(x, w):
        return lm._causal_conv1d({'kernel': '4', 'seq_len': '6'}, x, w)

    stats = _counted(lambda: jax.make_jaxpr(op)(x, w))
    assert 'pallas_call' not in set(_primitives(
        jax.make_jaxpr(op)(x, w).jaxpr))
    assert stats == {'kernel': 0, 'xla': 1, 'shapes': [dict(
        path='xla', sequences=2, t=6, channels=5, width=4, lowerings=1)]}
    got = mx.nd.CausalConv1D(mx.nd.NDArray(x), mx.nd.NDArray(w), kernel=4,
                             seq_len=6).asnumpy()
    np.testing.assert_allclose(got, reference(x.reshape(2, 6, 5), w)
                               .reshape(12, 5), rtol=1e-6, atol=1e-6)


def test_the_counter_records_both_paths():
    """causal_conv_stats() by path and shape, emptied by profiler.clear()
    (dump_profile's reset).  Shapes no other test traces: jax traces a
    function once a shape."""
    x = jnp.zeros((48, 256), jnp.bfloat16)
    w = jnp.zeros((256, 3), jnp.bfloat16)

    def trace():
        jax.make_jaxpr(functools.partial(lm.causal_conv, seq_len=48))(x, w)
        jax.make_jaxpr(functools.partial(lm.causal_conv, seq_len=40))(
            x[:40], w)

    stats = _counted(trace)
    assert (stats['kernel'], stats['xla']) == (1, 1)
    assert stats['shapes'] == [
        dict(path='kernel', sequences=1, t=48, channels=256, width=3,
             lowerings=1),
        dict(path='xla', sequences=1, t=40, channels=256, width=3,
             lowerings=1)]
    profiler.clear()
    assert profiler.causal_conv_stats() == {'kernel': 0, 'xla': 0,
                                            'shapes': []}
