"""AFMoE's layer kinds (Trinity-Mini's) on the CPU at a small size: the
sliding window of the attention core at whole and ragged T, GatedAttention's
attributes one at a time, the shares of the expert layer and a whole
tiny model through Module.bulk_step and fit, against the plain float32
reference the benchmark compares with (benchmark/reference/afmoe.py,
loaded from where it lives)."""
import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, profiler
from mxnet_tpu.ops import lm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
sys.path.insert(0, BENCH)
from reference import convnet, afmoe as ref            # noqa: E402
from reference import qwen3_next as ref_qwen           # noqa: E402

SEQ = 40
# a dense layer, then a windowed, a full and a windowed expert layer;
# the window is under the sequence, a kernel tile above it
TINY = dict(vocab_size=64, hidden_size=32, num_hidden_layers=4,
            layer_types=['sliding_attention', 'sliding_attention',
                         'full_attention', 'sliding_attention'],
            sliding_window=12, num_dense_layers=1, intermediate_size=48,
            num_attention_heads=8, num_key_value_heads=2, head_dim=8,
            rope_theta=10000.0, num_experts=32, num_experts_held=8,
            expert_offset=8, num_shared_experts=1, num_experts_per_tok=4,
            moe_intermediate_size=16, route_norm=True, route_scale=2.826,
            score_func='sigmoid', mup_enabled=True, rms_norm_eps=1e-5)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, \
        (np.abs(a - b).max(), scale)


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def program_args(c):
    """The factory's arguments from the reference's."""
    return {k: v for k, v in c.items() if k != 'vocab_size'}


@pytest.fixture
def attention_paths():
    """profiler.attention_stats() counted from here on."""
    profiler._ATTENTION.clear()
    yield profiler.attention_stats
    profiler._ATTENTION.clear()


# -- the window of the attention core ------------------------------------------

def dense_attention(q, k, v, scale, window):
    """softmax over a T x T score matrix with the mask written out: q
    (T, kv, group, d), k (T, kv, d), v (T, kv, dv)."""
    t = q.shape[0]
    s = jnp.einsum('qghd,kgd->ghqk', q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('ghqk,kgd->qghd', p, v,
                      precision=jax.lax.Precision.HIGHEST)


# (T, rows a block, window, key-value heads, query heads a key head)
WINDOW_CASES = {
    'ragged-T': (50, 16, 24, 2, 4),
    'window-under-a-block': (50, 16, 5, 2, 4),
    'window-of-one-block': (64, 16, 16, 1, 8),
    'window-past-T': (50, 16, 50, 2, 4),
    'ungrouped-ragged-window-of-two-keys': (37, 8, 2, 3, 1),
    'one-block': (24, 512, 7, 2, 4),
    # a T no block of 8 rows divides, padded onto the kernels
    'ragged-33': (33, 16, None, 2, 4),
    'ungrouped-ragged-33-window': (33, 16, 9, 2, 1),
    'ungrouped-ragged-36': (36, 16, None, 2, 1),
    'ragged-36-window': (36, 16, 5, 2, 4),
    'ragged-50': (50, 16, None, 2, 4),
    'ungrouped-ragged-50': (50, 16, None, 2, 1),
}


@functools.lru_cache(maxsize=None)
def _window_case(case):
    t, block, window, kv, group = WINDOW_CASES[case]
    q, k, v = rand(1, t, kv, group, 12), rand(2, t, kv, 12), \
        rand(3, t, kv, 6)
    weight = rand(4, t, kv, group, 6)
    scale = 1.0 / np.sqrt(12)

    def program(q, k, v):
        return lm.causal_attention(q[None], k[None], v[None], scale,
                                   block_q=block, window=window)[0]

    def dense(q, k, v):
        return dense_attention(q, k, v, scale, window)

    def value_and_grads(fn):
        return jax.jit(lambda *a: (fn(*a),) + jax.grad(
            lambda *b: jnp.sum(fn(*b) * weight), argnums=(0, 1, 2))(*a))(
                q, k, v)

    return value_and_grads(program), value_and_grads(dense)


@pytest.mark.parametrize('what', ['value', 'dq', 'dk', 'dv'])
@pytest.mark.parametrize('case', sorted(WINDOW_CASES))
def test_windowed_core_against_a_dense_masked_softmax(case, what):
    got, want = _window_case(case)
    i = ['value', 'dq', 'dk', 'dv'].index(what)
    assert np.abs(np.asarray(want[i])).max() > 0
    close(got[i], want[i], 1e-4)


@pytest.mark.parametrize('window', [40, 41, 4096])
def test_a_window_that_reaches_every_key_is_no_window(attention_paths,
                                                      window):
    """The causal result, bit for bit, on the causal path: grouped and
    ungrouped heads alike on the flash kernels, and the counter keeps
    no window."""
    q, k, v = rand(1, 1, 40, 2, 4, 12), rand(2, 1, 40, 2, 12), \
        rand(3, 1, 40, 2, 6)
    close(lm.causal_attention(q, k, v, 0.3, block_q=16, window=window),
          lm.causal_attention(q, k, v, 0.3, block_q=16), 0)
    close(lm.causal_attention(q[:, :, :, :1], k, v, 0.3, block_q=8,
                              window=window),
          lm.causal_attention(q[:, :, :, :1], k, v, 0.3, block_q=8), 0)
    stats = attention_paths()
    assert (stats['kernel'], stats['blocked']) == (4, 0)
    assert {s['window'] for s in stats['shapes']} == {None}
    # two lowerings of each shape, 8 heads and 2
    assert {s['keys_needed'] for s in stats['shapes']} == {
        2 * 8 * 40 * 41 // 2, 2 * 2 * 40 * 41 // 2}


def test_a_window_takes_ungrouped_heads_to_the_kernels_too(attention_paths):
    """The flash kernels skip the tiles left of the band: a window goes
    to them whatever the heads, the counter says which window decided,
    and the result is the dense masked softmax's."""
    q, k, v = rand(1, 1, 64, 2, 1, 12), rand(2, 1, 64, 2, 12), \
        rand(3, 1, 64, 2, 6)
    lm.causal_attention(q, k, v, 0.3, block_q=16)
    got = lm.causal_attention(q, k, v, 0.3, block_q=16, window=24)
    shapes = attention_paths()['shapes']
    assert [(s['path'], s['window']) for s in shapes] == [
        ('kernel', None), ('kernel', 24)]
    windowed, full = shapes[1], shapes[0]
    assert windowed['keys_needed'] < full['keys_needed']
    assert windowed['keys_visited'] < full['keys_visited']
    close(got[0], dense_attention(q[0], k[0], v[0], 0.3, 24), 1e-5)


def positions(t, block, window):
    """(visited, needed) of one head of one sequence, counted a row at a
    time from the rule: a block of rows reads whole blocks of keys."""
    visited = needed = 0
    for i in range(t):
        r0 = i // block * block
        first = 0 if window is None else \
            max(0, (r0 - window + 1) // block * block)
        visited += min(r0 + block, t) - first
        needed += min(i + 1, window or t)
    return visited, needed


@pytest.mark.parametrize('t,block,window', [
    (64, 16, 24), (50, 16, 24), (50, 16, 5), (33, 16, 1), (64, 16, None),
    (8192, 512, 2048), (8192, 512, None)])
def test_the_counter_of_positions_visited_and_needed(attention_paths, t,
                                                     block, window):
    """From shapes alone, while the operator is traced: nothing runs."""
    kv, group = 2, 4
    jax.eval_shape(
        lambda q, k, v: lm.causal_attention(q, k, v, 0.3, block_q=block,
                                            window=window),
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in
          ((3, t, kv, group, 16), (3, t, kv, 16), (3, t, kv, 16))))
    (shape,) = attention_paths()['shapes']
    # the kernels' square tiles of `block` (halved until they divide T,
    # ragged T padded to whole sublanes) are the same whole blocks of
    # keys a block of rows reads; the mask lets through those of T
    padded = -(-t // 8) * 8
    tile = block
    while padded % tile:
        tile //= 2
    visited, needed = positions(padded, tile, window)[0], \
        positions(t, block, window)[1]
    assert shape == dict(path='kernel',
                         heads=kv * group, group=group,
                         dk=16, dv=16, t=t, window=window, lowerings=1,
                         keys_visited=3 * kv * group * visited,
                         keys_needed=3 * kv * group * needed)
    assert needed <= visited
    if (t, block) == (8192, 512):
        # the cell's layers at tiles of 512: 1.25 and 1.06 of what the
        # mask lets through
        assert round(visited / needed, 2) == (1.25 if window else 1.06)
        assert needed == (14681088 if window else 33558528)


def test_a_windowed_layers_work_grows_with_T_and_not_its_square(
        attention_paths):
    """The positions the kernels' grids score at twice the length: twice
    with a window, four times without."""
    def visited(t, window):
        jax.eval_shape(
            lambda q, k, v: lm.causal_attention(q, k, v, 0.25, block_q=64,
                                                window=window),
            *(jax.ShapeDtypeStruct(s, jnp.float32) for s in
              ((1, t, 2, 4, 16), (1, t, 2, 16), (1, t, 2, 16))))
        (shape,) = attention_paths()['shapes']
        profiler._ATTENTION.clear()
        assert shape['path'] == 'kernel'
        return shape['keys_visited']

    assert visited(1024, 128) / visited(512, 128) < 2.2
    assert visited(1024, None) / visited(512, None) > 3.5


# -- GatedAttention's attributes -------------------------------------------------

HEADS, KV, D = 8, 2, 8
QWEN = dict(num_heads=HEADS, num_kv_heads=KV, head_dim=D, rotary_dim=4,
            rope_theta=1e4, eps=1e-6, seq_len=SEQ)


def _attention_inputs():
    n = 2 * SEQ
    return dict(q=rand(1, n, HEADS, D), gate=rand(2, n, HEADS, D),
                k=rand(3, n, KV * D), v=rand(4, n, KV * D),
                gq=0.3 * rand(5, D), gk=0.3 * rand(6, D))


def _operator(attrs, x):
    """GatedAttention as it is called; the gate packed beside the query
    unless the attributes say it comes apart."""
    nd = {k: mx.nd.NDArray(v) for k, v in x.items()}
    if attrs.get('separate_gate'):
        return mx.nd.GatedAttention(
            nd['q'].reshape((-1, HEADS * D)), nd['k'], nd['v'], nd['gq'],
            nd['gk'], nd['gate'].reshape((-1, HEADS * D)), **attrs).asnumpy()
    packed = mx.nd.NDArray(jnp.concatenate([x['q'], x['gate']], axis=-1)
                           .reshape(-1, HEADS * 2 * D))
    return mx.nd.GatedAttention(packed, nd['k'], nd['v'], nd['gq'],
                                nd['gk'], **attrs).asnumpy()


def _written_out(x, rotary_dim=4, window=None, zero_centered=True,
                 eps=1e-6):
    """The layer from the references' pieces, a sequence at a time."""
    net = convnet.Net({})

    def one(q, gate, k, v):
        q = ref_qwen.rms_norm(q, x['gq'], eps, zero_centered)
        k = ref_qwen.rms_norm(k.reshape(SEQ, KV, D), x['gk'], eps,
                              zero_centered)
        if rotary_dim:
            q = ref_qwen.rotary(q, rotary_dim, 1e4)
            k = ref_qwen.rotary(k, rotary_dim, 1e4)
        o = ref.masked_attention(net, q.reshape(SEQ, KV, HEADS // KV, D), k,
                                 v.reshape(SEQ, KV, D), window)
        return o.reshape(SEQ, HEADS * D) * jax.nn.sigmoid(
            gate.reshape(SEQ, HEADS * D))

    return jnp.concatenate([one(*(x[n][rows] for n in ('q', 'gate', 'k',
                                                       'v')))
                            for rows in (slice(0, SEQ), slice(SEQ, None))])


@pytest.mark.parametrize('attrs,written', [
    ({}, {}),
    ({'window': 12}, {'window': 12}),
    ({'window': 3}, {'window': 3}),
    ({'rotary_dim': 0}, {'rotary_dim': 0}),
    ({'rotary_dim': D}, {'rotary_dim': D}),
    ({'zero_centered': False}, {'zero_centered': False}),
    ({'separate_gate': True}, {}),
    ({'eps': 1e-2}, {'eps': 1e-2}),
    ({'window': 12, 'rotary_dim': 0, 'zero_centered': False,
      'separate_gate': True, 'eps': 1e-5},
     {'window': 12, 'rotary_dim': 0, 'zero_centered': False, 'eps': 1e-5}),
], ids=['defaults', 'window', 'window-of-3', 'no-rotary', 'whole-rotary',
        'plain-norms', 'separate-gate', 'eps', 'afmoe'])
def test_gated_attention_attributes_one_at_a_time(attrs, written):
    x = _attention_inputs()
    close(_operator(dict(QWEN, **attrs), x), _written_out(x, **written))


@pytest.mark.parametrize('spelled', [
    {'zero_centered': True}, {'separate_gate': False}, {'window': 4096},
    {'zero_centered': True, 'separate_gate': False, 'window': SEQ}],
    ids=['zero_centered', 'separate_gate', 'window', 'all'])
def test_gated_attention_defaults_are_qwen3_nexts_layer(spelled):
    """An attribute left out and its default spelled out lower to one
    program: the same bits."""
    x = _attention_inputs()
    close(_operator(dict(QWEN, **spelled), x), _operator(QWEN, x), 0)


def test_gated_attention_input_names_follow_the_gate():
    packed = mx.sym.GatedAttention(num_heads=8, num_kv_heads=2, head_dim=8,
                                   seq_len=SEQ, name='a')
    apart = mx.sym.GatedAttention(num_heads=8, num_kv_heads=2, head_dim=8,
                                  seq_len=SEQ, separate_gate=True, name='a')
    assert packed.list_arguments() == [
        'a_query_gate', 'a_key', 'a_value', 'a_q_norm_gamma',
        'a_k_norm_gamma']
    assert apart.list_arguments() == [
        'a_query', 'a_key', 'a_value', 'a_q_norm_gamma', 'a_k_norm_gamma',
        'a_gate']
    with pytest.raises(ValueError, match='window'):
        _operator(dict(QWEN, window=0), _attention_inputs())


@pytest.mark.parametrize('wrt', ['q', 'gate', 'k', 'v', 'gq', 'gk'])
def test_afmoe_attention_gradients(wrt):
    """The windowed, position-free, plain-normed layer with its own
    gate: every input's gradient against the written-out layer's."""
    got, want = _attention_gradients()
    assert np.abs(np.asarray(want[wrt])).max() > 0
    close(got[wrt], want[wrt], 1e-4)


@functools.lru_cache(maxsize=None)
def _attention_gradients():
    x = _attention_inputs()
    attrs = dict(QWEN, window=12, rotary_dim=D, zero_centered=False,
                 separate_gate=True)
    weight = rand(9, 2 * SEQ, HEADS * D)

    def program(x):
        return jnp.sum(weight * lm._gated_attention(
            attrs, x['q'].reshape(-1, HEADS * D), x['k'], x['v'], x['gq'],
            x['gk'], x['gate'].reshape(-1, HEADS * D)))

    def written(x):
        return jnp.sum(weight * _written_out(x, rotary_dim=D, window=12,
                                             zero_centered=False))

    return jax.jit(jax.grad(program))(x), jax.jit(jax.grad(written))(x)


# -- the expert layer ------------------------------------------------------------

def _net_with(spec_fn):
    """Seeded weights for whatever leaves spec_fn's call declares."""
    net = convnet.Net()
    jax.eval_shape(lambda: spec_fn(net))
    params = {}
    for i, (name, s) in enumerate(sorted(net.spec.items())):
        if s['init'] == 'he_in':
            params[name] = rand(100 + i, *s['shape']) * np.sqrt(
                2.0 / s['shape'][1])
        elif name.endswith('_counts'):
            params[name] = jnp.zeros(s['shape'], jnp.float32)
        elif name.endswith('_selection_bias'):
            params[name] = 0.3 * rand(100 + i, *s['shape'])
        else:
            params[name] = 1.0 + 0.3 * rand(100 + i, *s['shape'])
    return params


def _layer_params(c):
    x = jnp.zeros((4, c['hidden_size']))
    return _net_with(lambda n: ref.expert_layer(n, 'l1', x, c))


def _expert_layer(c, params, x):
    """The program's expert layer as a bound symbol after one training
    pass; returns (output, counts)."""
    sym = models.afmoe.expert_layer(mx.sym.Variable('data'), 'l1',
                                    dict(c, bias_update_rate=0.0))
    args = {n: mx.nd.NDArray(params[n]) for n in sym.list_arguments()
            if n != 'data'}
    assert sym.list_auxiliary_states() == ['l1_moe_counts',
                                           'l1_moe_selection_bias']
    aux = {'l1_moe_counts': mx.nd.zeros((2, c['num_experts']),
                                        dtype='int32'),
           'l1_moe_selection_bias': mx.nd.NDArray(
               params['l1_moe_selection_bias'])}
    ex = sym.bind(mx.cpu(), dict(args, data=mx.nd.NDArray(x)),
                  aux_states=aux)
    out = ex.forward(is_train=True)[0].asnumpy()
    return out, ex.aux_dict['l1_moe_counts'].asnumpy()


def _share(c, p, first, held):
    """The weights of experts first .. first + held of the whole layer."""
    inter, hidden = c['moe_intermediate_size'], c['hidden_size']
    rows = slice(first * inter, (first + held) * inter)
    down = slice(first * hidden, (first + held) * hidden)
    return dict(p, l1_moe_gate_weight=p['l1_moe_gate_weight'][rows],
                l1_moe_up_weight=p['l1_moe_up_weight'][rows],
                l1_moe_down_weight=p['l1_moe_down_weight'][down])


@pytest.mark.parametrize('bias', ['biased', 'unbiased'])
def test_expert_layer_uncut_against_the_reference(bias):
    c = dict(TINY, num_experts_held=32, expert_offset=0)
    p = _layer_params(c)
    if bias == 'unbiased':
        p['l1_moe_selection_bias'] = jnp.zeros((32,), jnp.float32)
    x = rand(11, 300, c['hidden_size'])
    out, counts = _expert_layer(c, p, x)
    close(out, ref.expert_layer(convnet.Net(p), 'l1', x, c), 1e-4)
    assert counts[0].sum() == 300 * c['num_experts_per_tok']
    assert (counts[0] == counts[1]).all()       # all held: all computed


def test_the_eight_shares_of_16_experts_sum_to_the_uncut_layer():
    """Trinity-Mini's cut at small widths: 128 experts, top 8, eight
    chips with 16 each.  Every share routes over all 128 and computes
    its own; the shared expert, which every chip computes alike, counts
    once."""
    whole = dict(TINY, num_experts=128, num_experts_per_tok=8,
                 moe_intermediate_size=8, num_experts_held=128,
                 expert_offset=0)
    p = _layer_params(whole)
    x = rand(12, 200, whole['hidden_size'])
    net = convnet.Net(p)
    shared = np.asarray(ref._gated_mlp(net, 'l1_shared', x, 8))
    total = shared.copy()
    computed = np.zeros(128, np.int64)
    for first in range(0, 128, 16):
        c = dict(whole, num_experts_held=16, expert_offset=first)
        part = _share(whole, p, first, 16)
        out, counts = _expert_layer(c, part, x)
        close(out, ref.expert_layer(convnet.Net(part), 'l1', x, c), 1e-4)
        total += out - shared
        computed += counts[1]
        assert counts[0].sum() == 200 * 8
        assert counts[1][:first].sum() == 0
        assert counts[1][first + 16:].sum() == 0
    close(total, ref.expert_layer(net, 'l1', x, whole), 1e-4)
    assert computed.sum() == 200 * 8


def test_the_router_is_kananas_route_with_afmoes_keys():
    """sigmoid scores, the top 8 of score + bias, the chosen over their
    sum + 1e-20, times route_scale: route() as it is."""
    c = dict(TINY)
    x = rand(21, 200, c['hidden_size'])
    w = rand(22, c['num_experts'], c['hidden_size']) * 0.3
    bias = 0.5 * rand(23, c['num_experts'])
    net = convnet.Net({'l1_moe_router_weight': w,
                       'l1_moe_selection_bias': bias})
    vals, idx = lm.route(x, w, c['num_experts_per_tok'], True,
                         scoring='sigmoid', bias=bias, scale=2.826)
    dense = jnp.sum(jax.nn.one_hot(idx, c['num_experts']) * vals[..., None],
                    axis=1)
    close(dense, ref.routing(net, 'l1', x, c))
    close(np.asarray(vals).sum(axis=-1), np.full(200, 2.826))


# -- the factory and the whole model ---------------------------------------------

@pytest.mark.parametrize('layers,dense,every', [(4, 1, 4), (8, 2, 4),
                                                (6, 0, 3)])
def test_factory_layer_pattern(layers, dense, every):
    """`num_dense_layers` leading dense layers, then expert layers; the
    published rule makes every n-th layer full, a window and rotary
    on the others; four norms a layer."""
    c = dict(TINY, num_hidden_layers=layers, num_dense_layers=dense,
             layer_types=None, global_attn_every_n_layers=every)
    sym = models.get_symbol('afmoe', num_classes=64, seq_len=SEQ,
                            **program_args(c))
    nodes = {n.name: n for n in sym._topo() if n.op is not None}
    kinds = models.afmoe.layer_types_of(layers, every)
    assert kinds.count('full_attention') == layers // every
    for l in range(layers):
        attn = nodes['l%d_attn' % l]
        assert attn.op.name == 'GatedAttention'
        full = (l + 1) % every == 0
        assert kinds[l] == ('full_attention' if full else 'sliding_attention')
        assert ('window' in attn.attrs) == (not full)
        assert int(attn.attrs['rotary_dim']) == (0 if full else 8)
        assert ('l%d_mlp_down_proj' % l in nodes) == (l < dense)
        assert ('l%d_moe' % l in nodes) == (l >= dense)
        assert ('l%d_shared_down_proj' % l in nodes) == (l >= dense)
        for norm in ('input', 'post_attn', 'pre_mlp', 'post_mlp'):
            assert nodes['l%d_%s_norm' % (l, norm)].op.name == 'RMSNorm'
    aux = sym.list_auxiliary_states()
    assert aux == [n for l in range(dense, layers) for n in
                   ('l%d_moe_counts' % l, 'l%d_moe_selection_bias' % l)]
    marked = [n for n in sym._topo() if n.op is not None and
              n.user_attrs.get('__force_mirroring__')]
    assert len(marked) > 10 * layers
    # the reference declares the same leaves
    spec, _ = convnet.describe(ref.forward, dict(
        c, layer_types=kinds, seq_len=SEQ), (2 * SEQ,))
    assert set(spec) == set(sym.list_arguments() + aux) - {
        'data', 'softmax_label'}


@pytest.mark.parametrize('extra', [
    {'layer_types': ['full_attention']},
    {'layer_types': ['sliding_attention'] * 3 + ['chunked_attention']},
    {'n_group': 8, 'topk_group': 4}], ids=['count', 'kind', 'groups'])
def test_factory_refuses_what_it_does_not_build(extra):
    with pytest.raises(mx.base.MXNetError, match='afmoe'):
        models.get_symbol('afmoe', num_classes=64, seq_len=SEQ,
                          **dict(program_args(TINY), **extra))


def _tiny_module(dtype='float32', steps=2, seed=3, **extra):
    arguments = dict(TINY, seq_len=SEQ, **extra)
    sym = models.get_symbol('afmoe', num_classes=TINY['vocab_size'],
                            dtype=dtype, **program_args(arguments))
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


OPTIMIZER = {'learning_rate': 0.005, 'momentum': 0.9, 'wd': 1e-4}


@functools.lru_cache(maxsize=None)
def _followed(steps):
    """`steps` bulk steps of the tiny model and of the reference's SGD
    from the same weights: (program's loss and change of every leaf,
    the reference's, the counts)."""
    mod, batches, (arguments, spec, params, ids) = _tiny_module(steps=steps)
    assert mod._fusable_step()
    mod.bulk_step(batches=batches, scan_dtype='float32')
    assert mod._exec_group.executor.fused_dispatches == 1
    probs = mod.get_outputs()[0].asnumpy()
    got, got_aux = mod.get_params()
    step = convnet.make_train_step(ref.forward, arguments, OPTIMIZER)
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
    assert set(got) == set(train)
    mine = {n: got[n].asnumpy() - np.asarray(params[n]) for n in train}
    theirs = {n: np.asarray(train[n]) - np.asarray(params[n]) for n in train}
    return (got_loss, mine), (float(loss), theirs), {
        n: a.asnumpy() for n, a in got_aux.items()}


LEAVES = sorted(
    ['embed_weight', 'lm_head_weight', 'final_norm_gamma'] +
    ['l%d_%s' % (l, n) for l in range(4) for n in (
        'q_proj_weight', 'k_proj_weight', 'v_proj_weight',
        'gate_proj_weight', 'o_proj_weight', 'attn_q_norm_gamma',
        'attn_k_norm_gamma', 'input_norm_gamma', 'post_attn_norm_gamma',
        'pre_mlp_norm_gamma', 'post_mlp_norm_gamma')] +
    ['l0_mlp_%s_proj_weight' % n for n in ('gate', 'up', 'down')] +
    ['l%d_%s_weight' % (l, n) for l in (1, 2, 3) for n in (
        'moe_router', 'moe_gate', 'moe_up', 'moe_down', 'shared_gate_proj',
        'shared_up_proj', 'shared_down_proj')])


@pytest.mark.parametrize('steps', [1, 2])
def test_whole_model_loss_against_the_reference(steps):
    """Module.bulk_step (fused, no per-step fallback) against the
    reference's SGD: the last step's loss."""
    (got, _), (want, _), _ = _followed(steps)
    assert abs(got - want) < 1e-4 * want


@pytest.mark.parametrize('leaf', LEAVES)
def test_whole_model_first_gradient_against_the_reference(leaf):
    """After one step from rest a leaf's change is -lr (g + wd w): the
    first gradient, leaf by leaf (a dense layer, both kinds of
    attention layer, held experts)."""
    (_, mine), (_, theirs), _ = _followed(1)
    assert set(mine) == set(LEAVES)
    gap = np.linalg.norm(mine[leaf] - theirs[leaf]) / \
        np.linalg.norm(theirs[leaf])
    assert gap < 1e-2, gap


@pytest.mark.parametrize('leaf', LEAVES)
def test_whole_model_two_bulk_steps_against_the_reference(leaf):
    """Every leaf's change after K = 2 steps of one dispatch.  float32
    on both sides; the grouped product and the blocks of attention sum
    in another order than the reference."""
    (_, mine), (_, theirs), aux = _followed(2)
    gap = np.linalg.norm(mine[leaf] - theirs[leaf]) / \
        np.linalg.norm(theirs[leaf])
    assert gap < 1e-2, gap
    gaps = [np.linalg.norm(mine[n] - theirs[n]) / np.linalg.norm(theirs[n])
            for n in LEAVES]
    assert np.median(gaps) < 2e-3
    # rate 0: the bias is what was loaded, and the counts have grown
    for layer in (1, 2, 3):
        assert not aux['l%d_moe_selection_bias' % layer].any()
        counts = aux['l%d_moe_counts' % layer]
        assert counts[0].sum() == 2 * 2 * SEQ * TINY['num_experts_per_tok']


def test_the_embedding_is_scaled_only_with_mup(attention_paths):
    """mup_enabled multiplies the embedding by sqrt(hidden_size); the
    step's lowerings are three windowed layers and one full, all on
    the flash kernels (one tile holds the sequence), forward and
    recomputed."""
    def logits(**extra):
        mod, batches, _ = _tiny_module(steps=1, **extra)
        mod.forward(batches[0], is_train=False)
        return mod.get_outputs()[0].asnumpy()

    assert np.abs(logits() - logits(mup_enabled=False)).max() > 1e-3
    stats = attention_paths()
    assert stats['blocked'] == 0
    by_window = {s['window']: s for s in stats['shapes']}
    assert set(by_window) == {None, 12}
    assert by_window[12]['lowerings'] == 3 * by_window[None]['lowerings']
    visited, needed = positions(SEQ, 512, 12)
    n = by_window[None]['lowerings']
    assert by_window[12]['keys_visited'] == 3 * n * 2 * 8 * visited
    assert by_window[12]['keys_needed'] == 3 * n * 2 * 8 * needed
    assert by_window[None]['keys_needed'] == \
        n * 2 * 8 * SEQ * (SEQ + 1) // 2


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


def test_counters_reach_the_profiler():
    mod, batches, _ = _tiny_module()
    profiler.fold_device_counters()
    before = profiler.moe_stats()
    mod.bulk_step(batches=batches)
    profiler.fold_device_counters()
    after = profiler.moe_stats()
    tokens = 2 * len(batches) * SEQ * 3         # three expert layers
    assert after['moe_assignments'] - before['moe_assignments'] == \
        tokens * TINY['num_experts_per_tok']
    routed = after['moe_routed_tokens'] - before['moe_routed_tokens']
    assert 0 < routed < tokens * TINY['num_experts_per_tok']
    assert after['moe_dropped_tokens'] == before['moe_dropped_tokens']


def test_scales_and_bias_keep_float32_in_a_bfloat16_graph():
    sym = models.get_symbol('afmoe', num_classes=1000, dtype='bfloat16',
                            seq_len=SEQ, **program_args(TINY))
    ex = sym.simple_bind(mx.cpu(), data=(2 * SEQ,),
                         softmax_label=(2 * SEQ,))
    types = {n: np.dtype(a.dtype).name for n, a in ex.arg_dict.items()}
    assert types['softmax_label'] == types['data'] == 'float32'
    for name, t in types.items():
        if name.endswith('_gamma'):
            assert t == 'float32', name
        elif name.endswith('_weight'):
            assert t == 'bfloat16', name
    assert np.dtype(ex.aux_dict['l1_moe_counts'].dtype).name == 'int32'
    assert np.dtype(
        ex.aux_dict['l1_moe_selection_bias'].dtype).name == 'float32'
