"""The gated delta rule's chunk-local half (pallas_ops.delta_rule_local
and its backward, in interpret mode here) against the plain jax.numpy
statement of what happens inside a chunk: the batched XLA code ops/lm.py
ran before the kernels, kept here as the reference."""
import collections
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu import pallas_ops, profiler
from mxnet_tpu.ops import lm

CHUNK = 64
RESULTS = ('u', 'w', 'intra', 'q_in', 'k_out', 'gamma')
INPUTS = ('q', 'k', 'v', 'g', 'beta')


def unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular a (..., C, C): a is
    nilpotent, so the Neumann series ends, and its C terms are the
    product (I - a)(I + a^2)(I + a^4)... of log2(C) factors."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    inv, power = eye - a, a
    for _ in range(max(0, (c - 1).bit_length() - 1)):
        power = mm(power, power)
        inv = mm(inv, eye + power)
    return inv


def strictly_lower(k, g, beta):
    """a of every chunk: (k beta) k^T * exp(g_i - g_j) below the
    diagonal (g the cumulative sum already)."""
    chunk = k.shape[-2]
    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.exp(jnp.where(below, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    return jnp.einsum('...ik,...jk->...ij', k * beta[..., None], k) * decay


def chunk_local(q, k, v, g, beta):
    """The half of the rule that stays inside a chunk, every chunk at
    once: the unit lower triangular system of the WY form solved, and
    what the loop over the chunks takes from each.  q, k (..., chunks,
    C, dk), v (..., chunks, C, dv), g and beta (..., chunks, C).
    Returns u (C, dv), w (C, dk), intra (C, C), q_in (C, dk), k_out
    (C, dk) of every chunk and gamma (..., chunks), the decay over a
    whole chunk."""
    chunk = q.shape[-2]
    g = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(g_i - g_j) for i >= j, masked before exp: the other half of
    # the difference is positive and can overflow
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    k_beta = k * beta[..., None]
    inv = unit_lower_inverse(strictly_lower(k, g, beta))
    u = jnp.matmul(inv, v * beta[..., None])            # (.., C, dv)
    w = jnp.matmul(inv, k_beta * jnp.exp(g)[..., None])  # (.., C, dk)
    intra = jnp.einsum('...ik,...jk->...ij', q, k) * decay
    q_in = q * jnp.exp(g)[..., None]
    g_last = g[..., -1]
    k_out = k * jnp.exp(g_last[..., None] - g)[..., None]
    return u, w, intra, q_in, k_out, jnp.exp(g_last)


def close(a, b, tol=2e-5, floor=1e-30):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.isfinite(a).all()
    scale = max(np.abs(b).max(), floor)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# (t, heads, dk, dv): tier-1's narrow heads, the cell's heads with T
# whole chunks and not, widths past one lane; `strong` a decay near -20
# a token, where exp of the unmasked half of g_i - g_j would overflow
CASES = {'narrow': (100, 2, 8, 4), 'cell': (256, 2, 128, 128),
         'cell-padded': (200, 2, 128, 128), 'wide': (100, 1, 136, 200),
         'strong': (128, 2, 128, 128)}


@functools.lru_cache(maxsize=None)
def chunked_inputs(case):
    """q, k, v (heads, chunks, C, lanes), g, beta (heads, chunks, C):
    padded as chunk_gated_delta_rule pads them, rows with beta = g = 0
    and zero columns up to whole lanes."""
    t, h, dk, dv = CASES[case]

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k, v = unit(rand(1, h, t, dk)), unit(rand(2, h, t, dk)), \
        rand(3, h, t, dv)
    g = -jax.nn.softplus(rand(4, h, t))
    if case == 'strong':
        g = g - 20.0
    beta = jax.nn.sigmoid(rand(5, h, t))
    q, k, v = (lm._pad_axis(lm._pad_axis(x, 1, CHUNK), 2, lm.LANES)
               for x in (q, k, v))
    g, beta = (lm._pad_axis(x, 1, CHUNK) for x in (g, beta))
    return tuple(x.reshape((h, -1, CHUNK) + x.shape[2:])
                 for x in (q, k, v, g, beta))


@functools.lru_cache(maxsize=None)
def made(case):
    """The kernel's results (with T) and the plain statement's."""
    args = chunked_inputs(case)
    return (pallas_ops.delta_rule_local(*args, with_inverse=True),
            chunk_local(*args))


@pytest.mark.parametrize('which', range(6), ids=RESULTS)
@pytest.mark.parametrize('case', sorted(CASES))
def test_local_make_is_the_plain_statement(case, which):
    got, want = made(case)
    assert got[which].shape == want[which].shape
    close(got[which], want[which])


@pytest.mark.parametrize('case', sorted(CASES))
def test_the_solved_system_is_the_inverse(case):
    """T (I + a) = I to float32, and T is unit lower triangular."""
    _, k, _, g, beta = chunked_inputs(case)
    inv = made(case)[0][6]
    system = jnp.eye(CHUNK) + strictly_lower(k, jnp.cumsum(g, -1), beta)
    product = jnp.matmul(inv, system, precision=lax.Precision.HIGHEST)
    assert np.abs(np.asarray(product) - np.eye(CHUNK)).max() < 1e-5
    assert np.abs(np.triu(np.asarray(inv), 1)).max() == 0
    close(unit_lower_inverse(system - jnp.eye(CHUNK)), inv, 1e-6)


def test_without_the_inverse_the_make_returns_six():
    args = chunked_inputs('narrow')
    six = pallas_ops.delta_rule_local(*args)
    assert len(six) == 6
    for a, b in zip(six, made('narrow')[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@functools.lru_cache(maxsize=None)
def cotangents(case):
    """(dq, dk, dv, dg, dbeta) by delta_rule_local_bwd and by jax.vjp
    of the plain statement, under random cotangents of the six."""
    args = chunked_inputs(case)
    want, vjp = jax.vjp(chunk_local, *args)
    given = tuple(rand(10 + i, *x.shape) for i, x in enumerate(want))
    inv = made(case)[0][6]
    return pallas_ops.delta_rule_local_bwd(*args, inv, given), vjp(given)


@pytest.mark.parametrize('wrt', range(5), ids=INPUTS)
@pytest.mark.parametrize('case', sorted(CASES))
def test_local_backward_is_the_vjp_of_the_plain_statement(case, wrt):
    got, want = cotangents(case)
    assert got[wrt].shape == want[wrt].shape
    # g's cotangent is row sums less column sums of products of order
    # one: under a strong decay it is 1e-9 itself and either side's
    # rounding is that of the sums
    close(got[wrt], want[wrt], 1e-4, floor=1e-2 if wrt == 3 else 1e-30)


def _primitives(jaxpr, inside_kernel=False):
    """(primitive, inside a pallas_call, output shapes) of every
    equation, kernels' bodies and other sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield (eqn.primitive.name, inside_kernel,
               tuple(getattr(v.aval, 'shape', ()) for v in eqn.outvars), eqn)
        inside = inside_kernel or eqn.primitive.name == 'pallas_call'
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, inside)


def _rule_args(t=130, h=2, dk=8, dv=4):
    return (rand(1, 1, h, t, dk), rand(2, 1, h, t, dk), rand(3, 1, h, t, dv),
            -jax.nn.softplus(rand(4, 1, h, t)),
            jax.nn.sigmoid(rand(5, 1, h, t)))


# the kernels of the rule and of its gradient, in the order they run; the
# gradient's first two are the forward's (jax.grad keeps the primal)
KERNELS = {
    'forward': ['delta_rule_local', 'delta_rule_chunks'],
    'gradient': ['delta_rule_local', 'delta_rule_chunks', 'delta_rule_local',
                 'delta_rule_states', 'delta_rule_chunks_bwd',
                 'delta_rule_local_bwd']}


@pytest.mark.parametrize('what', sorted(KERNELS))
def test_the_chunk_local_half_is_kernels_and_no_xla_product(what):
    """The XLA half cannot come back unnoticed: the rule and its
    gradient hold these pallas_calls and, outside them, no dot_general
    at all (so none over the chunks' 64 x 64 matrices); inside, the
    solve's products carry Precision.HIGHEST (ten a pair of heads in
    the local make, two in its backward) and every product float32
    operands and results."""
    fn = {'forward': lm.chunk_gated_delta_rule,
          'gradient': jax.grad(
              lambda *a: jnp.sum(lm.chunk_gated_delta_rule(*a)),
              argnums=range(5))}[what]
    found = list(_primitives(jax.make_jaxpr(fn)(*_rule_args()).jaxpr))
    calls = [e.params['name'] for name, _, _, e in found
             if name == 'pallas_call']
    assert calls == KERNELS[what]
    dots = [(inside, e) for name, inside, _, e in found
            if name == 'dot_general']
    assert dots and all(inside for inside, _ in dots)
    for _, e in dots:
        assert {v.aval.dtype for v in e.invars + e.outvars} == {
            jnp.dtype('float32')}
    exact = collections.Counter(
        e.params['precision'] is not None
        and lax.Precision.HIGHEST in tuple(e.params['precision'])
        for _, e in dots)
    pairs = 1       # of heads a grid step at these shapes
    solves = {'forward': 10, 'gradient': 10 + 10 + 2}[what]
    assert exact[True] == solves * pairs


def test_delta_rule_stats_count_the_lowerings():
    """profiler.delta_rule_stats(): by the padded shape the kernels
    see, how often the rule was traced, and its local makes and
    backward rules: a forward is one make, a gradient the forward's and
    the backward rule's."""
    before = profiler.delta_rule_stats()
    args = _rule_args(t=150, h=5)   # a shape no other test traces
    jax.make_jaxpr(lm.chunk_gated_delta_rule)(*args)
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        lm.chunk_gated_delta_rule(*a)), argnums=range(5)))(*args)
    after = profiler.delta_rule_stats()
    grown = {k: after[k] - before[k]
             for k in ('lowerings', 'local_makes', 'backward_rules')}
    assert grown == {'lowerings': 2, 'local_makes': 3, 'backward_rules': 1}
    shape = [s for s in after['shapes']
             if (s['heads'], s['chunks']) == (5, 3)]
    assert len(shape) == 1
    assert {k: shape[0][k] for k in ('chunk', 'dk', 'dv')} == {
        'chunk': 64, 'dk': 128, 'dv': 128}
    assert shape[0]['lowerings'] == 2
