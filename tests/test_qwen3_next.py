"""Qwen3-Next on the CPU at a small size: each new operator, the shares
of the expert layer and a whole tiny model through Module.bulk_step,
against the plain float32 reference the benchmark compares with
(benchmark/reference/qwen3_next.py, loaded from where it lives)."""
import collections
import functools
import hashlib
import os
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, pallas_ops, profiler
from mxnet_tpu.ops import lm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
sys.path.insert(0, BENCH)
from reference import convnet, qwen3_next as ref     # noqa: E402

TINY = dict(vocab_size=64, hidden_size=32, num_hidden_layers=4,
            full_attention_interval=4, num_attention_heads=8,
            num_key_value_heads=1, head_dim=16, partial_rotary_factor=0.25,
            rope_theta=1e7, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, linear_conv_kernel_dim=4,
            num_experts=32, num_experts_held=8, expert_offset=8,
            num_experts_per_tok=4, norm_topk_prob=True,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            rms_norm_eps=1e-6)
SEQ = 40


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# -- the chunked gated delta rule against the recurrence ---------------------

def _rule_inputs(t, h=3, dk=8, dv=4, rep=1):
    """`rep` value heads share each key head's q and k, as the
    operator's jnp.repeat hands them over."""
    def unit(x):
        return jnp.repeat(x / jnp.linalg.norm(x, axis=-1, keepdims=True),
                          rep, axis=1)
    q, k = unit(rand(1, t, h // rep, dk)), unit(rand(2, t, h // rep, dk))
    v = rand(3, t, h, dv)
    g = -jax.nn.softplus(rand(4, t, h))
    beta = jax.nn.sigmoid(rand(5, t, h))
    return q, k, v, g, beta


def _chunked(q, k, v, g, beta, chunk):
    def heads_first(x):
        return jnp.moveaxis(x, 1, 0)[None]
    o = lm.chunk_gated_delta_rule(*(heads_first(x) for x in
                                    (q, k, v, g, beta)), chunk=chunk)
    return jnp.moveaxis(o[0], 0, 1)


# (t, chunk, heads, dk, dv, value heads a key head): tier-1's narrow
# heads (padded to the lanes), the cell's head shape with T whole
# chunks and not, and widths past one lane that need the padding
TINY_HEADS = (3, 8, 4, 1)
CELL_HEADS = (4, 128, 128, 2)
WIDE_HEADS = (2, 136, 200, 1)
RULE_CASES = [(64, 64) + TINY_HEADS, (100, 64) + TINY_HEADS,
              (37, 16) + TINY_HEADS, (130, 64) + TINY_HEADS,
              (256, 64) + CELL_HEADS, (200, 64) + CELL_HEADS,
              (100, 64) + WIDE_HEADS]


@pytest.mark.parametrize('t,chunk,h,dk,dv,rep', RULE_CASES)
def test_chunked_delta_rule_is_the_recurrence(t, chunk, h, dk, dv, rep):
    args = _rule_inputs(t, h, dk, dv, rep)
    net = convnet.Net({})
    close(_chunked(*args, chunk), ref.delta_rule_recurrence(net, *args))


@functools.lru_cache(maxsize=None)
def _rule_gradients(t, chunk, h, dk, dv, rep):
    """All five gradients of the chunked rule and of the recurrence."""
    args = _rule_inputs(t, h, dk, dv, rep)
    weight = rand(9, t, h, dv)
    net = convnet.Net({})

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=range(5))(*args)

    return (grads(lambda *a: _chunked(*a, chunk)),
            grads(lambda *a: ref.delta_rule_recurrence(net, *a)))


@pytest.mark.parametrize('wrt', range(5))
@pytest.mark.parametrize('t,chunk,h,dk,dv,rep', [
    (100, 64) + TINY_HEADS, (256, 64) + CELL_HEADS, (200, 64) + CELL_HEADS,
    (100, 64) + WIDE_HEADS])
def test_chunked_delta_rule_gradients(t, chunk, h, dk, dv, rep, wrt):
    got, want = _rule_gradients(t, chunk, h, dk, dv, rep)
    close(got[wrt], want[wrt], 1e-4)


def test_the_kernel_keeps_the_state_the_recurrence_has_at_each_chunk():
    """delta_rule_states' S_c is the state the token-by-token rule has
    reached when chunk c starts, and v_new what the chunk's o is made of."""
    t, chunk, h, d = 256, 64, 2, 128
    q, k, v, g, beta = (jnp.moveaxis(x, 1, 0).reshape(
        (h, t // chunk, chunk) + x.shape[2:])
        for x in _rule_inputs(t, h, d, d))
    u, w, intra, q_in, k_out, gamma = pallas_ops.delta_rule_local(
        q, k, v, g, beta)
    s0, v_new = pallas_ops.delta_rule_states(u, w, k_out, gamma)

    def token(state, xs):
        k_t, v_t, g_t, beta_t = xs
        decayed = state * jnp.exp(g_t)
        delta = beta_t * (v_t - k_t @ decayed)
        return decayed + jnp.outer(k_t, delta), state

    for head in range(h):
        _, before = jax.lax.scan(token, jnp.zeros((d, d)), tuple(
            x[head].reshape((t,) + x.shape[3:]) for x in (k, v, g, beta)))
        close(s0[head], before[::chunk])
    o = pallas_ops.delta_rule_chunks(u, w, intra, q_in, k_out, gamma)
    close(o, jnp.matmul(q_in, s0) + jnp.matmul(intra, v_new))


def _primitives(jaxpr):
    """Every primitive outside the kernels' own bodies (those loop over
    the heads of a grid step)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name != 'pallas_call':
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _primitives(sub)


@pytest.mark.parametrize('what', ['forward', 'gradient'])
def test_the_chunk_loop_is_a_kernel_and_no_scan(what):
    """The loop over the chunks cannot come back unnoticed: two
    pallas_calls forward (the chunk's own system, then the loop), six
    in the gradient (those two; the local make and the states again,
    the loop backward, the local half's backward), and no scan or
    while in either."""
    args = _rule_inputs(130)
    fn = {'forward': lambda *a: _chunked(*a, 64),
          'gradient': jax.grad(lambda *a: jnp.sum(_chunked(*a, 64)),
                               argnums=range(5))}[what]
    found = collections.Counter(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
    assert found['pallas_call'] == {'forward': 2, 'gradient': 6}[what]
    assert not found['scan'] and not found['while'], found


def test_gated_delta_rule_gradient_under_checkpoint():
    """The half layer's recomputation wraps the operator in
    jax.checkpoint: the custom rule gives the same gradients there."""
    attrs = dict(num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8,
                 seq_len=SEQ)
    n = 2 * SEQ
    args = (rand(1, n, 2 * 2 * 8 + 4 * 8), rand(2, n, 4), rand(3, n, 4),
            0.1 * rand(4, 4), 0.1 * rand(5, 4))
    weight = rand(6, n, 4 * 8)

    op = functools.partial(lm._gated_delta_rule, attrs)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=range(5))(*args)

    plain, again = grads(op), grads(jax.checkpoint(op))
    for a, b in zip(plain, again):
        assert np.abs(np.asarray(b)).max() > 0
        close(a, b, 1e-6)


def test_causal_conv_and_rms_norm():
    x, w = rand(1, 2 * SEQ, 6), rand(2, 6, 4)
    got = mx.nd.CausalConv1D(mx.nd.NDArray(x), mx.nd.NDArray(w), kernel=4,
                             seq_len=SEQ).asnumpy()
    want = np.concatenate([ref.causal_conv(x[:SEQ], w),
                           ref.causal_conv(x[SEQ:], w)])
    close(got, want)
    gamma = rand(3, 6)
    for zero_centered in (False, True):
        close(mx.nd.RMSNorm(mx.nd.NDArray(x), mx.nd.NDArray(gamma),
                            eps=1e-6, zero_centered=zero_centered).asnumpy(),
              ref.rms_norm(x, gamma, 1e-6, zero_centered))


# -- gated attention ---------------------------------------------------------

def _net_with(spec_fn):
    """A Net holding seeded weights for whatever leaves spec_fn's call
    declares."""
    net = convnet.Net()
    jax.eval_shape(lambda: spec_fn(net))
    params = {}
    for i, (name, s) in enumerate(sorted(net.spec.items())):
        if s['init'] == 'he_in':
            params[name] = rand(100 + i, *s['shape']) * np.sqrt(
                2.0 / s['shape'][1])
        elif name.endswith('_counts'):
            params[name] = jnp.zeros(s['shape'], jnp.float32)
        else:           # scales and rates: away from their neutral start
            params[name] = 0.3 * rand(100 + i, *s['shape'])
    return convnet.Net(params), params


def test_gated_attention_against_the_reference():
    """8 query heads on one key-value head, rotary on a quarter of the
    head, the sigmoid gate: two sequences of a length that the blocks
    of query rows do not divide."""
    c = dict(TINY, seq_len=SEQ)
    x = rand(7, 2 * SEQ, c['hidden_size'])
    net, p = _net_with(lambda n: ref.gated_attention(
        n, 'l3', jnp.zeros((SEQ, c['hidden_size'])), c))
    want = np.concatenate([ref.gated_attention(net, 'l3', x[:SEQ], c),
                           ref.gated_attention(net, 'l3', x[SEQ:], c)])
    data = mx.sym.Variable('data')
    sym = models.qwen3_next.gated_attention(data, 'l3', c)
    args = {n: mx.nd.NDArray(p[n]) for n in sym.list_arguments()
            if n != 'data'}
    ex = sym.bind(mx.cpu(), dict(args, data=mx.nd.NDArray(x)))
    close(ex.forward()[0].asnumpy(), want)
    for t in (SEQ, 24):     # blocks of 16 rows: whole and ragged
        q, k, v = rand(1, 1, t, 1, 8, 16), rand(2, 1, t, 1, 16), \
            rand(3, 1, t, 1, 16)
        close(lm.causal_attention(q, k, v, 0.25, block_q=16)[0],
              ref.causal_attention(convnet.Net({}), q[0], k[0], v[0]))


# -- the expert layer --------------------------------------------------------

def _expert_layer(c, params, x, is_train=False):
    """The program's expert layer (routed share and shared expert) as a
    bound symbol; returns (output, counts after one training pass)."""
    data = mx.sym.Variable('data')
    sym = models.qwen3_next.expert_layer(data, 'l0', c)
    args = {n: mx.nd.NDArray(params[n]) for n in sym.list_arguments()
            if n != 'data'}
    aux = {'l0_moe_counts': mx.nd.zeros((2, c['num_experts']),
                                        dtype='int32')}
    ex = sym.bind(mx.cpu(), dict(args, data=mx.nd.NDArray(x)),
                  aux_states=aux)
    out = ex.forward(is_train=is_train)[0].asnumpy()
    return out, ex.aux_dict['l0_moe_counts'].asnumpy()


def _reference_layer(c, params, x, shared=True):
    net = convnet.Net(params)
    y = ref.routed_experts(net, 'l0', x, c)
    return y + ref.shared_expert(net, 'l0', x, c) if shared else y


def _layer_params(c):
    x = jnp.zeros((4, c['hidden_size']))
    return _net_with(lambda n: ref.routed_experts(n, 'l0', x, c) +
                     ref.shared_expert(n, 'l0', x, c))[1]


def test_expert_layer_uncut_against_the_reference():
    c = dict(TINY, num_experts_held=32, expert_offset=0)
    p = _layer_params(c)
    x = rand(11, 300, c['hidden_size'])
    out, counts = _expert_layer(c, p, x, is_train=True)
    close(out, _reference_layer(c, p, x), 1e-4)
    assert counts[0].sum() == 300 * c['num_experts_per_tok']
    assert (counts[0] == counts[1]).all()       # all held: all computed


@pytest.mark.parametrize('held', [8, 16])
def test_expert_shares_sum_to_the_uncut_layer(held):
    """Every share routes over all 32 experts and computes its own;
    the shared expert, which every chip computes alike, counts once."""
    whole = dict(TINY, num_experts_held=32, expert_offset=0)
    p = _layer_params(whole)
    x = rand(12, 200, whole['hidden_size'])
    net = convnet.Net(p)
    shared = np.asarray(ref.shared_expert(net, 'l0', x, whole))
    total = shared.copy()
    computed = np.zeros(32, np.int64)
    for first in range(0, 32, held):
        c = dict(whole, num_experts_held=held, expert_offset=first)
        rows = slice(first * 16, (first + held) * 16)
        down = slice(first * 32, (first + held) * 32)
        part = dict(p, l0_moe_gate_weight=p['l0_moe_gate_weight'][rows],
                    l0_moe_up_weight=p['l0_moe_up_weight'][rows],
                    l0_moe_down_weight=p['l0_moe_down_weight'][down])
        out, counts = _expert_layer(c, part, x, is_train=True)
        close(out, _reference_layer(c, part, x), 1e-4)
        total += out - shared
        computed += counts[1]
        assert counts[1][:first].sum() == 0
        assert counts[1][first + held:].sum() == 0
    close(total, _reference_layer(whole, p, x), 1e-4)
    assert computed.sum() == 200 * whole['num_experts_per_tok']


def test_nothing_is_dropped_when_every_token_picks_the_same_experts():
    """A router that sends every token to the same four experts, all
    held here: every pair is computed (the worst case the grouped
    product's arrays are sized for)."""
    c = dict(TINY, num_experts_held=8, expert_offset=8)
    p = dict(_layer_params(c))
    router = np.zeros((32, c['hidden_size']), np.float32)
    router[[9, 10, 12, 15], 0] = [8.0, 7.0, 6.0, 5.0]
    p['l0_moe_router_weight'] = jnp.asarray(router)
    x = jnp.abs(rand(13, 700, c['hidden_size'])) + 0.5
    out, counts = _expert_layer(c, p, x, is_train=True)
    close(out, _reference_layer(c, p, x), 1e-4)
    assert counts[0].sum() == counts[1].sum() == 700 * 4
    assert set(np.nonzero(counts[1])[0]) == {9, 10, 12, 15}


def _held_pairs(c, p, x):
    """(tokens,) how many of each token's pairs go to experts held
    here, and (held,) how many pairs each held expert gets."""
    _, idx = lm.route(x, p['l0_moe_router_weight'],
                      c['num_experts_per_tok'], True)
    local = np.asarray(idx) - c['expert_offset']
    held = (local >= 0) & (local < c['num_experts_held'])
    return held.sum(axis=1), np.bincount(local[held],
                                         minlength=c['num_experts_held'])


def _sparse_moe_loss(c, weight, tile):
    def program(x, *ws):
        held, hidden = c['num_experts_held'], c['hidden_size']
        y, _, _ = lm.sparse_moe(
            x, ws[0], ws[1].reshape(held, -1, hidden),
            ws[2].reshape(held, -1, hidden), ws[3].reshape(held, hidden, -1),
            c['num_experts_per_tok'], c['expert_offset'], tile=tile)
        return jnp.sum(y * weight), y
    return program


MOE_NAMES = ['l0_moe_router_weight', 'l0_moe_gate_weight',
             'l0_moe_up_weight', 'l0_moe_down_weight']


@pytest.mark.parametrize('first_token', ['elsewhere', 'held'])
@pytest.mark.parametrize('tile', [8, 32])
def test_expert_layer_gradients_against_the_reference(tile, first_token):
    """Output and gradients at tiles of 8 and 32 rows, where some token's
    pairs land in the tiles of different experts and some expert's last
    tile is partial.  Token 0 is routed to no held expert (its output
    and input gradient must be the reference's exactly: a partial tile's
    dead rows add to no token) or to several."""
    c = dict(TINY, num_experts_held=8, expert_offset=8)
    p = _layer_params(c)
    x = rand(14, 150, c['hidden_size'])
    per_token, per_expert = _held_pairs(c, p, x)
    if first_token == 'held':
        j = int(np.argmax(per_token))
        x = x.at[jnp.array([0, j])].set(x[jnp.array([j, 0])])
        per_token[[0, j]] = per_token[[j, 0]]
    assert per_token[0] >= 2 if first_token == 'held' else \
        per_token[0] == 0
    assert per_token.max() >= 2 and (per_expert % tile).any()
    weight = rand(15, 150, c['hidden_size'])

    def reference(x, *ws):
        q = dict(p, **dict(zip(MOE_NAMES, ws)))
        y = _reference_layer(c, q, x, shared=False)
        return jnp.sum(y * weight), y

    ws = [p[n] for n in MOE_NAMES]
    got, got_y = jax.grad(_sparse_moe_loss(c, weight, tile),
                          argnums=range(5), has_aux=True)(x, *ws)
    want, want_y = jax.grad(reference, argnums=range(5),
                            has_aux=True)(x, *ws)
    close(got_y, want_y, 1e-4)
    for a, b in zip(got, want):
        close(a, b, 1e-4)
    if first_token == 'elsewhere':
        assert not np.asarray(got_y[0]).any() and not np.asarray(want_y[0]).any()
        assert not np.asarray(got[0][0]).any()
        np.testing.assert_array_equal(got[0][0], want[0][0])


@pytest.mark.parametrize('hidden', [32, 256])
@pytest.mark.parametrize('count', [0, 5, 16])
def test_add_rows_adds_the_live_rows_once(hidden, count):
    """pallas_ops.add_rows adds the first `count` rows to their (unique)
    destinations and leaves every other row of the sum as it was; a
    hidden width that 128 divides is held as whole lanes, another as one
    line a row."""
    acc, rows = rand(21, 64, hidden), rand(22, 16, hidden)
    dest = jnp.sort(jax.random.permutation(jax.random.PRNGKey(23), 64)[:16])
    got = jax.jit(pallas_ops.add_rows)(
        pallas_ops.row_tiles(acc), dest, pallas_ops.row_tiles(rows), count)
    np.testing.assert_array_equal(got.reshape(acc.shape),
                                  acc.at[dest[:count]].add(rows[:count]))


def test_no_array_is_sized_for_every_pair():
    """Each tile's rows are added to their tokens inside the loop: at 150
    tokens, top 4 and tiles of 32, neither the forward nor its gradient
    holds an array of tokens * k + tile rows (a zero-filled buffer of
    every pair, gathered back k times), in the jaxpr or the lowering."""
    c = dict(TINY, num_experts_held=8, expert_offset=8)
    p = _layer_params(c)
    x = rand(14, 150, c['hidden_size'])
    ws = [p[n] for n in MOE_NAMES]
    program = _sparse_moe_loss(c, rand(15, 150, c['hidden_size']), 32)
    rows = 150 * c['num_experts_per_tok'] + 32
    for f in (lambda *a: program(*a)[1],
              jax.grad(lambda *a: program(*a)[0], argnums=range(5))):
        jaxpr = str(jax.make_jaxpr(f)(x, *ws))
        text = jax.jit(f).lower(x, *ws).as_text()
        assert not re.search(r'(?<!\d)%d,\d' % rows, jaxpr)
        assert not re.search(r'(?<!\d)%dx\d+x' % rows, text)


# (experts, held, top k) of the three expert cells' layers
PLAN_CELLS = {'qwen3-next': (512, 32, 10), 'trinity-mini': (128, 16, 8),
              'kanana': (128, 16, 6)}
PLAN_ROUTES = ['random-first', 'random-last', 'none-held', 'all-held',
               'one-token-held']


def _routed_keys(experts, held, k, route, tokens=37):
    """(tokens * k,) slots of a routing: a held expert's 0..held-1, or
    `held` for a pair held elsewhere.  random-first and random-last: a
    uniform router, expert_offset 0 and experts - held; none-held: no
    pair lands here; all-held: every pair does; one-token-held: token 0
    alone has pairs here, several."""
    rng = np.random.default_rng([experts, held, k, PLAN_ROUTES.index(route)])

    def picks(pool):
        return np.stack([rng.choice(pool, k, replace=False)
                         for _ in range(tokens)])
    offset = experts - held if route == 'random-last' else 0
    if route.startswith('random'):
        idx = picks(np.arange(experts))
    elif route == 'all-held':
        idx = picks(np.arange(held))
    else:
        idx = picks(np.arange(held, experts))
        if route == 'one-token-held':
            idx[0, :3] = rng.choice(held, 3, replace=False)
    local = idx.reshape(-1) - offset
    return jnp.asarray(np.where((local >= 0) & (local < held), local, held),
                       jnp.int32)


def _argsort_plan(key, weight, held):
    """The parent's plan: a stable argsort, searchsorted over the sorted
    keys, gathers, and a second argsort for the inverse."""
    order = jnp.argsort(key, stable=True)
    starts = jnp.searchsorted(key[order], jnp.arange(held + 1), side='left')
    return (order, weight[order], jnp.argsort(order),
            (starts[1:] - starts[:-1]).astype(jnp.int32))


@pytest.mark.parametrize('path', ['packed', 'stable'])
@pytest.mark.parametrize('route', PLAN_ROUTES)
@pytest.mark.parametrize('cell', sorted(PLAN_CELLS))
def test_the_plan_is_the_stable_argsorts(monkeypatch, cell, route, path):
    """SparseMoE's plan, on both paths, against the stable argsorts it
    replaced, exactly: every row of the order, the tokens and the
    weights, the held experts' group sizes, and the backward's return to
    the pairs' order (the inverse permutation, applied to the pairs'
    own indices)."""
    experts, held, k = PLAN_CELLS[cell]
    if path == 'stable':
        monkeypatch.setattr(lm, '_key_bits', lambda pairs, held: None)
    key = _routed_keys(experts, held, k, route)
    weight = jnp.asarray(np.random.default_rng(5).random(key.shape[0]),
                         jnp.float32)
    order, weight_of, group_sizes = lm._pair_plan(key, weight, held, k)
    want = _argsort_plan(key, weight, held)
    np.testing.assert_array_equal(order, want[0])
    np.testing.assert_array_equal(order // k, want[0] // k)
    np.testing.assert_array_equal(weight_of, want[1])
    np.testing.assert_array_equal(
        lm._to_pairs(order, jnp.arange(key.shape[0], dtype=jnp.int32)),
        want[2])
    np.testing.assert_array_equal(group_sizes, want[3])
    held_pairs = {'none-held': 0, 'all-held': key.shape[0],
                  'one-token-held': 3}.get(route)
    if held_pairs is not None:
        assert int(group_sizes.sum()) == held_pairs


def test_key_bits_choose_the_path_from_the_shapes():
    """The packed keys need 2**b >= the pairs and (held + 1) * 2**b
    within an int32: 18 + 6 bits at Qwen3-Next's 163,840 pairs and 33
    slots; past the int32 the stable sort."""
    assert lm._key_bits(16384 * 10, 32) == 18
    assert lm._key_bits(8192 * 8, 16) == 16
    assert lm._key_bits(8192 * 6, 16) == 16
    assert lm._key_bits(1, 0) == 1
    assert lm._key_bits(2 ** 20, 2047) == 20
    assert lm._key_bits(2 ** 20 + 1, 2047) is None
    assert lm._key_bits(2 ** 20, 2048) is None


def _ops_over(text, op, elements):
    """How many `op`s (sort, gather) of a StableHLO text have a result of
    `elements` elements."""
    if op == 'sort':
        found = re.findall(r'"stablehlo\.sort".*?\}\) : \([^)]*\) -> '
                           r'\((tensor<[^>]*>)', text, re.S)
    else:
        found = re.findall(r'"stablehlo\.%s"[^\n]* -> (tensor<[^>]*>)' % op,
                           text)
    return sum(int(np.prod([int(d) for d in re.findall(r'(\d+)x', t)]))
               == elements for t in found)


def test_the_plan_sorts_once_and_gathers_no_pair():
    """At 150 tokens and top 4: the forward sorts the 600 pairs once
    and gathers none of them (the parent sorted twice and gathered every
    pair's key and weight); its gradient adds one sort, the weights'
    gradient back to the pairs' order, and no gather of every pair."""
    c = dict(TINY, num_experts_held=8, expert_offset=8)
    p = _layer_params(c)
    x = rand(14, 150, c['hidden_size'])
    ws = [p[n] for n in MOE_NAMES]
    program = _sparse_moe_loss(c, rand(15, 150, c['hidden_size']), 32)
    pairs = 150 * c['num_experts_per_tok']
    for f, sorts in ((lambda *a: program(*a)[1], 1),
                     (jax.grad(lambda *a: program(*a)[0], argnums=range(5)),
                      2)):
        text = jax.jit(f).lower(x, *ws).as_text()
        assert _ops_over(text, 'sort', pairs) == sorts
        assert _ops_over(text, 'gather', pairs) == 0


def test_the_plan_counter_reads_the_packed_path():
    """profiler.moe_plan_stats() after the tiny model's step lowers:
    every plan on the packed path, at 80 tokens x top 4, 8 held experts
    and one slot for the rest."""
    profiler._MOE_PLAN.clear()
    _bulk_step_text(*_tiny_module()[:2])
    stats = profiler.moe_plan_stats()
    assert stats['packed'] > 0 and stats['stable'] == 0
    assert [(s['path'], s['pairs'], s['buckets'], s['top_k'])
            for s in stats['shapes']] == [
        ('packed', 2 * SEQ * TINY['num_experts_per_tok'],
         TINY['num_experts_held'] + 1, TINY['num_experts_per_tok'])]


def test_counters_reach_the_profiler_without_a_sync_in_the_step():
    """The counts live on the device as auxiliary state;
    fold_device_counters() folds what is new into moe_stats(), and
    moe_stats() alone reads nothing from the device."""
    mod, batches, _ = _tiny_module()
    profiler.fold_device_counters()
    before = profiler.moe_stats()
    mod.bulk_step(batches=batches)
    assert profiler.moe_stats()['moe_assignments'] == \
        before['moe_assignments']
    profiler.fold_device_counters()
    after = profiler.moe_stats()
    tokens = 2 * len(batches) * SEQ * TINY['num_hidden_layers']
    assert after['moe_assignments'] - before['moe_assignments'] == \
        tokens * TINY['num_experts_per_tok']
    routed = after['moe_routed_tokens'] - before['moe_routed_tokens']
    assert 0 < routed < tokens * TINY['num_experts_per_tok']
    assert after['moe_dropped_tokens'] == before['moe_dropped_tokens']
    held = {'e%d' % e for e in range(8, 16)}
    assert {e for e, v in after['moe_experts'].items()
            if v['routed']} >= held
    profiler.fold_device_counters()     # nothing new: nothing folded twice
    again = profiler.moe_stats()
    assert again['moe_assignments'] == after['moe_assignments']


# -- the factory and the whole model -----------------------------------------

@pytest.mark.parametrize('layers,interval', [(4, 4), (8, 4), (6, 3)])
def test_factory_layer_pattern(layers, interval):
    """Layer l is attention exactly where (l + 1) mod interval = 0."""
    arguments = dict(TINY, num_hidden_layers=layers,
                     full_attention_interval=interval)
    arguments.pop('vocab_size')
    sym = models.get_symbol('qwen3_next', num_classes=64, seq_len=SEQ,
                            **arguments)
    ops = {n.name: n.op.name for n in sym._topo() if n.op is not None}
    for l in range(layers):
        attention = (l + 1) % interval == 0
        assert attention == ref.is_attention_layer(l, interval)
        assert ('l%d_attn' % l in ops) == attention
        assert ('l%d_gdr' % l in ops) == (not attention)
        assert ops['l%d_moe' % l] == 'SparseMoE'
    marked = [n for n in sym._topo() if n.op is not None and
              n.user_attrs.get('__force_mirroring__')]
    assert len(marked) > 10 * layers


def _tiny_module(dtype='float32', steps=2, seed=3):
    arguments = dict(TINY, seq_len=SEQ)
    program = {k: v for k, v in arguments.items() if k != 'vocab_size'}
    sym = models.get_symbol('qwen3_next', num_classes=TINY['vocab_size'],
                            dtype=dtype, **program)
    n = 2 * SEQ
    spec, _ = convnet.describe(ref.forward, arguments, (n,))
    params = convnet.make_init(spec, jnp.float32)(jax.random.PRNGKey(seed))
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc('data', (n,), 'float32')],
             label_shapes=[mx.io.DataDesc('softmax_label', (n,), 'float32')],
             for_training=True)
    arg = {k: mx.nd.NDArray(v) for k, v in params.items()
           if not spec[k]['aux']}
    aux = {k: mx.nd.NDArray(v) for k, v in params.items() if spec[k]['aux']}
    mod.init_params(initializer=None, arg_params=arg, aux_params=aux)
    mod.init_optimizer(kvstore='local', optimizer='sgd', optimizer_params={
        'learning_rate': 0.005, 'momentum': 0.9, 'wd': 1e-4})
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY['vocab_size'], (steps, n + 1))
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(row[:-1].astype(np.float32))],
        label=[mx.nd.array(row[1:].astype(np.float32))]) for row in ids]
    return mod, batches, (arguments, spec, params, ids)


def test_whole_model_two_bulk_steps_against_the_reference():
    """Module.bulk_step (fused, no per-step fallback) against the
    reference's SGD step: the last step's loss and the change of every
    leaf."""
    mod, batches, (arguments, spec, params, ids) = _tiny_module()
    assert mod._fusable_step()
    ex = mod._exec_group.executor
    mod.bulk_step(batches=batches, scan_dtype='float32')
    assert ex.fused_dispatches == 1
    probs = mod.get_outputs()[0].asnumpy()
    got, _ = mod.get_params()

    step = convnet.make_train_step(
        ref.forward, arguments,
        {'learning_rate': 0.005, 'momentum': 0.9, 'wd': 1e-4})
    aux = {k: v for k, v in params.items() if spec[k]['aux']}
    train = {k: jnp.array(v) for k, v in params.items()
             if not spec[k]['aux']}
    moms = {k: jnp.zeros_like(v) for k, v in train.items()}
    for row in ids:
        train, moms, loss = step(train, moms, aux,
                                 jnp.asarray(row[:-1], jnp.float32),
                                 jnp.asarray(row[1:], jnp.float32))
    labels = ids[-1][1:]
    got_loss = -np.mean(np.log(probs[np.arange(len(labels)), labels]))
    assert abs(got_loss - float(loss)) < 1e-4 * float(loss)
    assert set(got) == set(train)
    gaps = {}
    for name in sorted(train):
        change = np.asarray(train[name]) - np.asarray(params[name])
        mine = got[name].asnumpy() - np.asarray(params[name])
        gaps[name] = np.linalg.norm(mine - change) / np.linalg.norm(change)
    # float32 on both sides, but the chunked rule, the grouped product
    # and the blocks of attention sum in another order than the
    # reference, and four layers of norms carry that to the first
    # layer's leaves: 2e-3 was the worst leaf over the seeds tried
    assert max(gaps.values()) < 1e-2, max(gaps, key=gaps.get)
    assert np.median(list(gaps.values())) < 2e-3


def _bulk_step_text(mod, batches):
    """The text the module's bulk step program lowers to for its
    operands (nothing runs)."""
    ex = mod._exec_group.executor
    run, texts = ex.run_fused_multistep, []

    class Lowered(Exception):
        pass

    def spied(step, *args, **kwargs):
        def record(*operands):
            texts.append(step.lower(*operands).as_text())
            raise Lowered()
        return run(record, *args, **kwargs)

    ex.run_fused_multistep = spied
    with pytest.raises(Lowered):
        mod.bulk_step(batches=batches, scan_dtype='float32')
    return texts[0]


# sha256 of the tiny model's bulk step program (jax 0.9.0, the CPU
# backend, tests/conftest.py's eight devices): GatedDeltaRule's
# chunk-local half as two kernels, interpreted here, GatedAttention's
# grouped heads on the flash kernels, and SparseMoE's tiles added to
# their tokens inside the tile loop by pallas_ops.add_rows, and its
# plan one sort of packed keys, the weights' gradient sorted back to the
# pairs' order.  A change to an operator of this model on purpose
# replaces it; a change that says it leaves Qwen3-Next's program alone
# keeps it.
STEP_TEXT_SHA256 = (
    '9962ef280aea8b8ea6a4a4ba24b7db6530170e876d7b5ea76df8416060f657fb')


def test_grouped_heads_take_the_kernels_and_the_program_keeps_its_text():
    """GatedAttention's 8 query heads a key-value head are the flash
    kernels' since PR 35: every lowering of causal_attention in the
    whole model's step takes them, none the blocked core; the delta
    rule's lowerings are counted by the one shape its kernels see (4
    value heads of a sequence's block, one chunk, widths padded to the
    lanes; the makes and backward rules traced with them depend on what
    jax has cached of earlier traces: tests/test_delta_rule_local.py
    counts them on a shape of its own); and the step program is, to the
    byte, the one STEP_TEXT_SHA256 records."""
    profiler._ATTENTION.clear()
    profiler._DELTA_RULE.clear()
    text = _bulk_step_text(*_tiny_module()[:2])
    stats = profiler.attention_stats()
    assert stats['blocked'] == 0 and stats['kernel'] > 0
    assert {(s['group'], s['dk'], s['dv'], s['t'], s['window'])
            for s in stats['shapes']} == {(8, 16, 16, SEQ, None)}
    rule = profiler.delta_rule_stats()
    assert [(s['heads'], s['chunks'], s['chunk'], s['dk'], s['dv'])
            for s in rule['shapes']] == [(4, 1, 64, 128, 128)]
    assert rule['lowerings'] > 0
    assert hashlib.sha256(text.encode()).hexdigest() == STEP_TEXT_SHA256


def test_fit_trains_on_the_normal_path():
    mod, batches, _ = _tiny_module(steps=1)
    data = batches[0].data[0].asnumpy()
    label = batches[0].label[0].asnumpy()
    it = mx.io.NDArrayIter(data, label, batch_size=2 * SEQ)
    losses = []
    metric = mx.metric.CrossEntropy()
    mod.fit(it, num_epoch=4, eval_metric=metric, force_init=False,
            force_rebind=False,
            optimizer_params={'learning_rate': 0.05, 'momentum': 0.9},
            batch_end_callback=lambda p: losses.append(
                p.eval_metric.get()[1]))
    assert losses[-1] < losses[0]


def test_bulk_step_refuses_ids_in_a_narrower_scan_type():
    mod, batches, _ = _tiny_module()
    with pytest.raises(mx.base.MXNetError, match='Embedding'):
        mod.bulk_step(batches=batches, scan_dtype='bfloat16')


def test_labels_and_scales_keep_float32_in_a_bfloat16_graph():
    """Class indices above 256 are not exact in bfloat16: the label of
    a bfloat16 loss head binds as float32, as do the norm scales and
    the decay rates; the matrices bind in the compute type."""
    arguments = {k: v for k, v in dict(TINY, seq_len=SEQ).items()
                 if k != 'vocab_size'}
    sym = models.get_symbol('qwen3_next', num_classes=1000,
                            dtype='bfloat16', **arguments)
    ex = sym.simple_bind(mx.cpu(), data=(2 * SEQ,),
                         softmax_label=(2 * SEQ,))
    types = {n: np.dtype(a.dtype).name for n, a in ex.arg_dict.items()}
    assert types['softmax_label'] == types['data'] == 'float32'
    for name, t in types.items():
        if name.endswith(('_gamma', '_a_log', '_dt_bias')):
            assert t == 'float32', name
        elif name.endswith('_weight'):
            assert t == 'bfloat16', name
    assert np.dtype(ex.aux_dict['l0_moe_counts'].dtype).name == 'int32'
    ids = (np.arange(2 * SEQ) * 13 + 300) % 1000
    ex.arg_dict['softmax_label'][:] = ids
    assert (ex.arg_dict['softmax_label'].asnumpy() == ids).all()


def test_mirrored_segments_change_nothing():
    """Recomputation marked on the nodes gives the same step."""
    outs = []
    for marked in (True, False):
        arguments = {k: v for k, v in dict(TINY, seq_len=SEQ).items()
                     if k != 'vocab_size'}
        sym = models.get_symbol('qwen3_next', num_classes=64, **arguments)
        if not marked:
            for node in sym._topo():
                node.user_attrs.pop('__force_mirroring__', None)
        ex = sym.simple_bind(mx.cpu(), data=(2 * SEQ,),
                             softmax_label=(2 * SEQ,))
        for i, (name, arr) in enumerate(sorted(ex.arg_dict.items())):
            if name not in ('data', 'softmax_label'):
                arr[:] = 0.1 * np.asarray(rand(i, *arr.shape))
        ex.arg_dict['data'][:] = np.arange(2 * SEQ) % 64
        ex.arg_dict['softmax_label'][:] = (np.arange(2 * SEQ) + 1) % 64
        ex.forward(is_train=True)
        ex.backward()
        outs.append({n: g.asnumpy() for n, g in ex.grad_dict.items()
                     if n not in ('data', 'softmax_label')})
        assert bool(ex._mirror_segments) == marked
    for name in outs[0]:
        close(outs[0][name], outs[1][name], 1e-5)
