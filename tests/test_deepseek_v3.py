"""DeepSeek-V3's layer kinds (Kanana-2-30B-A3B's) on the CPU at a small
size: the latent attention operator, the sigmoid router with its
selection bias, the shares of the expert layer and a whole tiny model
through Module.bulk_step and fit, against the plain float32 reference
the benchmark compares with (benchmark/reference/deepseek_v3.py, loaded
from where it lives)."""
import functools
import os
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
from reference import convnet, deepseek_v3 as ref      # noqa: E402
from reference import qwen3_next as ref_qwen           # noqa: E402

TINY = dict(vocab_size=64, hidden_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, intermediate_size=48,
            num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=6, rope_theta=1e6,
            n_routed_experts=32, num_experts_held=8, expert_offset=8,
            n_shared_experts=2, num_experts_per_tok=4,
            moe_intermediate_size=16, norm_topk_prob=True,
            routed_scaling_factor=2.448, rms_norm_eps=1e-6)
SEQ = 40
HEADS, NOPE, ROPE, DV = 4, 8, 4, 6
ATTN = dict(num_heads=HEADS, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
            v_head_dim=DV, rope_theta=1e6, seq_len=SEQ)


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


# -- latent attention --------------------------------------------------------

def _reference_core(q, kv, k_pe):
    """The operator's mathematics from the reference's pieces, two
    sequences of SEQ rows each."""
    net = convnet.Net({})

    def one(q, kv, k_pe):
        q = q.reshape(SEQ, HEADS, NOPE + ROPE)
        kv = kv.reshape(SEQ, HEADS, NOPE + DV)
        q_pe = ref.rotary_interleaved(q[..., NOPE:], 1e6)
        k_rot = ref.rotary_interleaved(k_pe[:, None, :], 1e6)[:, 0]
        o = ref.causal_attention(net, q[..., :NOPE], q_pe, kv[..., :NOPE],
                                 k_rot, kv[..., NOPE:])
        return o.reshape(SEQ, HEADS * DV)

    return jnp.concatenate([one(q[:SEQ], kv[:SEQ], k_pe[:SEQ]),
                            one(q[SEQ:], kv[SEQ:], k_pe[SEQ:])])


def _core_inputs():
    n = 2 * SEQ
    return (rand(1, n, HEADS * (NOPE + ROPE)), rand(2, n, HEADS * (NOPE + DV)),
            rand(3, n, ROPE))


def test_latent_attention_against_the_reference():
    """Keys of width 12 (8 and the shared rotary 4), values of width 6."""
    q, kv, k_pe = _core_inputs()
    got = mx.nd.LatentAttention(mx.nd.NDArray(q), mx.nd.NDArray(kv),
                                mx.nd.NDArray(k_pe), **ATTN).asnumpy()
    assert got.shape == (2 * SEQ, HEADS * DV)
    close(got, _reference_core(q, kv, k_pe))


@functools.lru_cache(maxsize=None)
def _core_gradients():
    args = _core_inputs()
    weight = rand(4, 2 * SEQ, HEADS * DV)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=range(3))(*args)

    return (grads(functools.partial(lm._latent_attention, ATTN)),
            grads(_reference_core))


@pytest.mark.parametrize('wrt,name', enumerate(['query', 'key_value',
                                                'key_rope']))
def test_latent_attention_gradients(wrt, name):
    got, want = _core_gradients()
    assert np.abs(np.asarray(want[wrt])).max() > 0
    close(got[wrt], want[wrt], 1e-4)


@pytest.mark.parametrize('t', [SEQ, 24, 33])
def test_blocked_core_takes_a_value_width_of_its_own(t):
    """Tiles of 16 query rows, whole and ragged (33 rows padded to 40
    onto the kernels); keys 12 wide, values 6: values and all three
    gradients against the reference's core."""
    net = convnet.Net({})
    q, k, v = rand(1, t, HEADS, 12), rand(2, t, HEADS, 12), \
        rand(3, t, HEADS, 6)
    weight = rand(4, t, HEADS, 6)

    def program(q, k, v):
        return lm.causal_attention(
            q[None, :, :, None, :], k[None], v[None], 1.0 / np.sqrt(12),
            block_q=16)[0, :, :, 0]

    _core_against_the_reference(net, program, q, k, v, weight, nope=8)


def dense_core(q, k, v, scale):
    """causal_attention's operands and result through the dense float32
    softmax of pallas_ops (_dense_attention_lse), K and V repeated over
    a group."""
    b, t, kv, group, d = q.shape
    o = pallas_ops._dense_attention_lse(
        jnp.swapaxes(q.reshape(b, t, kv * group, d), 1, 2),
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), True, scale)[0]
    return jnp.swapaxes(o, 1, 2).reshape(b, t, kv, group, v.shape[3])


def _core_against_the_reference(net, program, q, k, v, weight, nope,
                                tol=1e-4):
    """Values and all three gradients of `program(q, k, v)` (q, k
    (T, heads, nope + rope), v (T, heads, dv)) against the reference's
    core, which takes the keys' rotary part as one shared head."""
    def reference(q, k, v):
        return ref.causal_attention(net, q[..., :nope], q[..., nope:],
                                    k[..., :nope], k[:, 0, nope:], v)

    # the reference takes one rotary head: give every head the same
    k = k.at[..., nope:].set(k[:, :1, nope:])
    close(program(q, k, v), reference(q, k, v), tol)
    for wrt in range(3):
        got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                              argnums=wrt)(q, k, v)
                     for fn in (program, reference))
        if wrt == 1:    # the reference's gradient of the one shared head
            got = got.at[:, 0, nope:].set(got[..., nope:].sum(axis=1))
            got = got.at[:, 1:, nope:].set(0.0)
        close(got, want, tol)


@pytest.fixture
def attention_paths():
    """profiler.attention_stats() counted from here on."""
    profiler._ATTENTION.clear()
    yield profiler.attention_stats
    profiler._ATTENTION.clear()


# the flash kernels under causal_attention (interpret mode off the TPU):
# (T, rows a block) so that the forward and, with the backward's tile
# edge cut to the same, the backward kernel walk one block or several
KERNEL_SHAPES = {'one-block': (16, 16), 'four-blocks': (64, 16)}


@pytest.mark.parametrize('schedule', ['resident', 'streaming'])
@pytest.mark.parametrize('shape', sorted(KERNEL_SHAPES))
@pytest.mark.parametrize('nope,rope,dv,heads', [(8, 4, 6, HEADS),
                                                (128, 64, 128, 2)])
def test_kernel_core_takes_a_value_width_of_its_own(
        monkeypatch, attention_paths, nope, rope, dv, heads, shape,
        schedule):
    """Ungrouped heads at a T the kernels tile go to the flash kernels,
    keys 12 over values 6 and keys 192 over values 128: values and all
    three gradients against the reference's core and against the dense
    softmax, on the resident and the streaming schedule of the
    forward, through the one backward kernel."""
    t, block = KERNEL_SHAPES[shape]
    monkeypatch.setattr(pallas_ops, '_BWD_BLOCK', block)
    if schedule == 'streaming':
        monkeypatch.setattr(pallas_ops, '_VMEM_RESIDENT_BYTES', 1)
    dk = nope + rope
    q, k, v = rand(1, t, heads, dk), rand(2, t, heads, dk), \
        rand(3, t, heads, dv)
    weight = rand(4, t, heads, dv)
    scale = 1.0 / np.sqrt(dk)

    def kernel(q, k, v):
        return lm.causal_attention(q[None, :, :, None, :], k[None],
                                   v[None], scale, block_q=block)[0, :, :, 0]

    def dense(q, k, v):
        return dense_core(q[None, :, :, None, :], k[None], v[None],
                          scale)[0, :, :, 0]

    _core_against_the_reference(convnet.Net({}), kernel, q, k, v, weight,
                                nope)
    stats = attention_paths()
    assert stats['blocked'] == 0 and stats['kernel'] > 0
    assert {(s['heads'], s['group'], s['dk'], s['dv'], s['t'])
            for s in stats['shapes']} == {(heads, 1, dk, dv, t)}
    close(kernel(q, k, v), dense(q, k, v), 1e-5)
    for wrt in range(3):
        got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                              argnums=wrt)(q, k, v)
                     for fn in (kernel, dense))
        close(got, want, 1e-4)


@pytest.mark.parametrize('t,group,why', [
    (33, 1, 'a ragged T'), (36, 8, 'no block of 8 rows, grouped heads'),
    (33, 8, 'a ragged T, grouped heads')])
def test_what_the_kernel_refuses_takes_the_blocked_core(attention_paths, t,
                                                        group, why):
    """What the kernels refuse, a T that no block of 8 rows divides
    whatever the heads, is padded with zero rows to the next multiple
    of 8 and run on them: bit for bit the kernels on the padded
    operands, sliced back; values and all three gradients those of the
    dense softmax; the counter says which shape decided and counts the
    positions the padded kernels' tiles score."""
    q, k, v = rand(1, 1, t, 2, group, 12), rand(2, 1, t, 2, 12), \
        rand(3, 1, t, 2, 6)
    got = lm.causal_attention(q, k, v, 0.3, block_q=16)
    stats = attention_paths()
    assert (stats['kernel'], stats['blocked']) == (1, 0)
    (shape,) = stats['shapes']
    assert {k: shape[k] for k in ('path', 'heads', 'group', 'dk', 'dv', 't',
                                  'window', 'lowerings')} == dict(
        path='kernel', heads=2 * group, group=group, dk=12, dv=6, t=t,
        window=None, lowerings=1)
    # tiles of 8 (16 does not divide the padded T) on and under the
    # diagonal; the keys up to each row
    tiles = -(-t // 8)
    assert shape['keys_needed'] == 2 * group * t * (t + 1) // 2
    assert shape['keys_visited'] == \
        2 * group * 64 * tiles * (tiles + 1) // 2
    pad = [(0, 0), (0, tiles * 8 - t)]
    close(got, lm.causal_attention(
        *(jnp.pad(x, pad + [(0, 0)] * (x.ndim - 2)) for x in (q, k, v)),
        0.3, block_q=16)[:, :t], 0)
    weight = rand(4, 1, t, 2, group, 6)
    close(got, dense_core(q, k, v, 0.3))
    for wrt in range(3):
        grad, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                               argnums=wrt)(q, k, v)
                      for fn in (lambda *a: lm.causal_attention(
                          *a, 0.3, block_q=16),
                          lambda *a: dense_core(*a, 0.3)))
        close(grad, want, 1e-4)


def test_adjacent_pair_rotary_gives_the_published_scores():
    """The program turns pairs (2i, 2i+1) in place; the published code
    moves the even dims before the odd ones and turns halves.  Same
    scores, and position 0 is left as it is."""
    q, k = rand(1, SEQ, 3, 8), rand(2, SEQ, 1, 8)
    mine_q = lm.rotary_pairs(q[None], 1e6)[0]
    mine_k = lm.rotary_pairs(k[None], 1e6)[0]
    theirs_q = ref.rotary_interleaved(q, 1e6)
    theirs_k = ref.rotary_interleaved(k, 1e6)
    close(jnp.einsum('qhd,kgd->hqk', mine_q, mine_k),
          jnp.einsum('qhd,kgd->hqk', theirs_q, theirs_k))
    close(mine_q[0], q[0])
    # in place: pair i of the program is (i, i + d/2) of the published
    close(mine_q[..., 0::2], theirs_q[..., :4])
    close(mine_q[..., 1::2], theirs_q[..., 4:])
    # and it is a rotation by pos * theta^(-2i/d) of each pair
    pos, i = 7, 1
    ang = pos * 1e6 ** (-2.0 * i / 8)
    x, y = q[pos, 0, 2 * i], q[pos, 0, 2 * i + 1]
    close(mine_q[pos, 0, 2 * i:2 * i + 2],
          [x * np.cos(ang) - y * np.sin(ang),
           y * np.cos(ang) + x * np.sin(ang)])


# -- the router --------------------------------------------------------------

def _dense_weights(vals, idx, n_exp):
    return np.asarray(jnp.sum(jax.nn.one_hot(idx, n_exp) * vals[..., None],
                              axis=1))


def _router_case(bias_scale):
    c = dict(TINY)
    x = rand(21, 200, c['hidden_size'])
    w = rand(22, c['n_routed_experts'], c['hidden_size']) * 0.3
    bias = bias_scale * rand(23, c['n_routed_experts'])
    net = convnet.Net({'l1_moe_router_weight': w,
                       'l1_moe_selection_bias': bias})
    return c, x, w, bias, np.asarray(ref.routing(net, 'l1', x, c))


@pytest.mark.parametrize('bias_scale', [0.0, 0.5])
def test_sigmoid_router_against_the_reference(bias_scale):
    c, x, w, bias, want = _router_case(bias_scale)
    vals, idx = lm.route(x, w, c['num_experts_per_tok'], True,
                         scoring='sigmoid', bias=bias, scale=2.448)
    close(_dense_weights(vals, idx, c['n_routed_experts']), want)
    # normalised over the chosen, then scaled
    close(np.asarray(vals).sum(axis=-1), np.full(200, 2.448))


def test_selection_bias_changes_the_choice_and_not_the_weights():
    c, x, w, bias, _ = _router_case(0.5)
    k = c['num_experts_per_tok']
    _, plain_idx = lm.route(x, w, k, False, scoring='sigmoid')
    vals, idx = lm.route(x, w, k, False, scoring='sigmoid', bias=bias)
    changed = (np.sort(np.asarray(idx)) !=
               np.sort(np.asarray(plain_idx))).any(axis=-1)
    assert changed.sum() > 20
    # the weight of a chosen expert is its sigmoid score, bias or no
    scores = np.asarray(jax.nn.sigmoid(x @ w.T))
    close(vals, np.take_along_axis(scores, np.asarray(idx), axis=-1))
    # and the choice is the top k of score + bias
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :k]
    assert (np.sort(np.asarray(idx)) == np.sort(want)).all()
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(lm.route(
        x, w, k, True, scoring='sigmoid', bias=b, scale=2.448)[0] ** 2))(bias)
    assert not np.asarray(g).any()


def test_softmax_routing_is_as_it_was():
    """route()'s defaults are Qwen3-Next's router."""
    c, x, w, _, _ = _router_case(0.0)
    vals, idx = lm.route(x, w, 4, True)
    probs = np.asarray(jax.nn.softmax(x @ w.T, axis=-1))
    top = np.sort(probs, axis=-1)[:, ::-1][:, :4]
    close(vals, top / top.sum(axis=-1, keepdims=True))
    with pytest.raises(ValueError, match='scoring'):
        lm.route(x, w, 4, True, scoring='tanh')


# -- the expert layer --------------------------------------------------------

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


def _expert_layer(c, params, x, is_train=False, **extra):
    """The program's expert layer as a bound symbol; returns (output,
    counts, selection bias) after one pass."""
    cc = dict(c, scoring_func='sigmoid', topk_method='noaux_tc',
              bias_update_rate=0.0, **extra)
    sym = models.deepseek_v3.expert_layer(mx.sym.Variable('data'), 'l1', cc)
    args = {n: mx.nd.NDArray(params[n]) for n in sym.list_arguments()
            if n != 'data'}
    assert sym.list_auxiliary_states() == ['l1_moe_counts',
                                           'l1_moe_selection_bias']
    aux = {'l1_moe_counts': mx.nd.zeros((2, c['n_routed_experts']),
                                        dtype='int32'),
           'l1_moe_selection_bias': mx.nd.NDArray(
               params['l1_moe_selection_bias'])}
    ex = sym.bind(mx.cpu(), dict(args, data=mx.nd.NDArray(x)),
                  aux_states=aux)
    out = ex.forward(is_train=is_train)[0].asnumpy()
    return (out, ex.aux_dict['l1_moe_counts'].asnumpy(),
            ex.aux_dict['l1_moe_selection_bias'].asnumpy())


def _share(c, p, first, held):
    """The weights of experts first .. first + held of the whole layer."""
    inter, hidden = c['moe_intermediate_size'], c['hidden_size']
    rows = slice(first * inter, (first + held) * inter)
    down = slice(first * hidden, (first + held) * hidden)
    return dict(p, l1_moe_gate_weight=p['l1_moe_gate_weight'][rows],
                l1_moe_up_weight=p['l1_moe_up_weight'][rows],
                l1_moe_down_weight=p['l1_moe_down_weight'][down])


def test_expert_layer_uncut_against_the_reference():
    c = dict(TINY, num_experts_held=32, expert_offset=0)
    p = _layer_params(c)
    x = rand(11, 300, c['hidden_size'])
    out, counts, bias = _expert_layer(c, p, x, is_train=True)
    close(out, ref.expert_layer(convnet.Net(p), 'l1', x, c), 1e-4)
    assert counts[0].sum() == 300 * c['num_experts_per_tok']
    assert (counts[0] == counts[1]).all()       # all held: all computed
    close(bias, p['l1_moe_selection_bias'], 0)  # rate 0: as loaded


def test_the_eight_shares_of_16_experts_sum_to_the_uncut_layer():
    """Kanana's cut at small widths: 128 experts, top 6, eight chips
    with 16 each.  Every share routes over all 128 and computes its
    own; the two shared experts, which every chip computes alike, count
    once."""
    whole = dict(TINY, n_routed_experts=128, num_experts_per_tok=6,
                 moe_intermediate_size=8, num_experts_held=128,
                 expert_offset=0)
    p = _layer_params(whole)
    x = rand(12, 200, whole['hidden_size'])
    net = convnet.Net(p)
    shared = np.asarray(ref._gated_mlp(net, 'l1_shared', x, 2 * 8))
    total = shared.copy()
    computed = np.zeros(128, np.int64)
    for first in range(0, 128, 16):
        c = dict(whole, num_experts_held=16, expert_offset=first)
        part = _share(whole, p, first, 16)
        out, counts, _ = _expert_layer(c, part, x, is_train=True)
        close(out, ref.expert_layer(convnet.Net(part), 'l1', x, c), 1e-4)
        total += out - shared
        computed += counts[1]
        assert counts[0].sum() == 200 * 6
        assert counts[1][:first].sum() == 0
        assert counts[1][first + 16:].sum() == 0
    close(total, ref.expert_layer(net, 'l1', x, whole), 1e-4)
    assert computed.sum() == 200 * 6


def test_nothing_is_dropped_when_the_bias_sends_every_token_one_way():
    """A selection bias that puts four held experts first for every
    token: every pair lands here and is computed."""
    c = dict(TINY)
    p = dict(_layer_params(c))
    bias = np.zeros(32, np.float32)
    bias[[9, 10, 12, 15]] = [8.0, 7.0, 6.0, 5.0]
    p['l1_moe_selection_bias'] = jnp.asarray(bias)
    x = rand(13, 700, c['hidden_size'])
    out, counts, _ = _expert_layer(c, p, x, is_train=True)
    close(out, ref.expert_layer(convnet.Net(p), 'l1', x, c), 1e-4)
    assert counts[0].sum() == counts[1].sum() == 700 * 4
    assert set(np.nonzero(counts[1])[0]) == {9, 10, 12, 15}


def test_expert_layer_gradients_against_the_reference():
    c = dict(TINY)
    p = _layer_params(c)
    x = rand(14, 150, c['hidden_size'])
    weight = rand(15, 150, c['hidden_size'])
    names = ['l1_moe_router_weight', 'l1_moe_gate_weight',
             'l1_moe_up_weight', 'l1_moe_down_weight']

    def program(x, *ws):
        held, hidden = 8, c['hidden_size']
        y, _, _ = lm.sparse_moe(
            x, ws[0], ws[1].reshape(held, -1, hidden),
            ws[2].reshape(held, -1, hidden), ws[3].reshape(held, hidden, -1),
            c['num_experts_per_tok'], c['expert_offset'], tile=32,
            scoring='sigmoid', bias=p['l1_moe_selection_bias'], scale=2.448)
        return jnp.sum(y * weight)

    def reference(x, *ws):
        q = dict(p, **dict(zip(names, ws)))
        no_shared = dict(c, n_shared_experts=0)
        return jnp.sum(ref.expert_layer(convnet.Net(q), 'l1', x, no_shared) *
                       weight)

    ws = [p[n] for n in names]
    got = jax.grad(program, argnums=range(5))(x, *ws)
    want = jax.grad(reference, argnums=range(5))(x, *ws)
    for a, b in zip(got, want):
        close(a, b, 1e-4)


def test_a_greedy_router_has_no_bias_state():
    sym = mx.sym.SparseMoE(mx.sym.Variable('data'), num_experts=8,
                           num_experts_held=8, top_k=2, intermediate_size=4,
                           scoring_func='sigmoid', name='m')
    assert sym.list_auxiliary_states() == ['m_counts']
    with pytest.raises(ValueError, match='topk_method'):
        mx.sym.SparseMoE(mx.sym.Variable('data'), num_experts=8,
                         num_experts_held=8, top_k=2, intermediate_size=4,
                         topk_method='group_limited_greedy', name='m')


# -- the factory and the whole model -----------------------------------------

@pytest.mark.parametrize('layers,dense', [(3, 1), (5, 1), (4, 2)])
def test_factory_layer_pattern(layers, dense):
    """The first `first_k_dense_replace` layers are dense, the rest are
    expert layers; every layer has latent attention."""
    arguments = program_args(dict(TINY, num_hidden_layers=layers,
                                  first_k_dense_replace=dense))
    sym = models.get_symbol('deepseek_v3', num_classes=64, seq_len=SEQ,
                            **arguments)
    ops = {n.name: n.op.name for n in sym._topo() if n.op is not None}
    for l in range(layers):
        assert ref.is_dense_layer(l, dense) == (l < dense)
        assert ops['l%d_attn' % l] == 'LatentAttention'
        assert ('l%d_mlp_down_proj' % l in ops) == (l < dense)
        assert ('l%d_moe' % l in ops) == (l >= dense)
        assert ('l%d_shared_down_proj' % l in ops) == (l >= dense)
    aux = sym.list_auxiliary_states()
    assert aux == [n for l in range(dense, layers) for n in
                   ('l%d_moe_counts' % l, 'l%d_moe_selection_bias' % l)]
    marked = [n for n in sym._topo() if n.op is not None and
              n.user_attrs.get('__force_mirroring__')]
    assert len(marked) > 10 * layers
    # the reference declares the same leaves
    spec, _ = convnet.describe(ref.forward, dict(
        TINY, num_hidden_layers=layers, first_k_dense_replace=dense,
        seq_len=SEQ), (2 * SEQ,))
    assert set(spec) == set(sym.list_arguments() + aux) - {
        'data', 'softmax_label'}


def test_factory_refuses_what_it_does_not_build():
    for extra in ({'q_lora_rank': 24}, {'rope_interleave': False},
                  {'n_group': 8, 'topk_group': 4}):
        with pytest.raises(mx.base.MXNetError, match='deepseek_v3'):
            models.get_symbol('deepseek_v3', num_classes=64, seq_len=SEQ,
                              **dict(program_args(TINY), **extra))


def test_qwen3_next_keeps_its_arguments_and_auxiliary_states():
    """The router's new attributes bring Qwen3-Next nothing: its symbol
    lists the leaves its (unedited) reference declares, no more."""
    from test_qwen3_next import TINY as QWEN
    arguments = {k: v for k, v in QWEN.items() if k != 'vocab_size'}
    sym = models.get_symbol('qwen3_next', num_classes=64, seq_len=SEQ,
                            **arguments)
    spec, _ = convnet.describe(ref_qwen.forward, dict(QWEN, seq_len=SEQ),
                               (2 * SEQ,))
    assert sorted(sym.list_auxiliary_states()) == sorted(
        n for n, s in spec.items() if s['aux'])
    assert sym.list_auxiliary_states() == [
        'l%d_moe_counts' % l for l in range(QWEN['num_hidden_layers'])]
    assert sorted(set(sym.list_arguments()) - {'data', 'softmax_label'}) == \
        sorted(n for n, s in spec.items() if not s['aux'])
    moe = [n for n in sym._topo() if n.op is not None and
           n.op.name == 'SparseMoE']
    assert all(set(n.attrs) == {
        'num_experts', 'num_experts_held', 'expert_offset', 'top_k',
        'normalize', 'intermediate_size'} for n in moe)


def _tiny_module(dtype='float32', steps=2, seed=3, **extra):
    arguments = dict(TINY, seq_len=SEQ)
    sym = models.get_symbol('deepseek_v3', num_classes=TINY['vocab_size'],
                            dtype=dtype, **program_args(arguments), **extra)
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
    got, got_aux = mod.get_params()

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
    # float32 on both sides; the grouped product and the blocks of
    # attention sum in another order than the reference
    assert max(gaps.values()) < 1e-2, max(gaps, key=gaps.get)
    assert np.median(list(gaps.values())) < 2e-3
    # rate 0: the bias is what was loaded, and the counts have grown
    for layer in (1, 2):
        assert not got_aux['l%d_moe_selection_bias' % layer].asnumpy().any()
        counts = got_aux['l%d_moe_counts' % layer].asnumpy()
        assert counts[0].sum() == 2 * 2 * SEQ * TINY['num_experts_per_tok']


def test_bias_rule_is_applied_once_a_step_under_a_mirrored_half_layer():
    """With a rate, each training step moves every expert's bias by the
    rate towards the mean load of that step's own assignments, once:
    the half layer's second forward in the backward pass adds nothing
    (neither to the bias nor to the counts)."""
    rate = 0.01
    mod, batches, _ = _tiny_module(steps=1, bias_update_rate=rate)
    marked = [n for n in mod.symbol._topo() if n.op is not None and
              n.op.name == 'SparseMoE' and
              n.user_attrs.get('__force_mirroring__')]
    assert len(marked) == 2
    tokens = 2 * SEQ
    seen = {}
    for step in (1, 2):
        mod.bulk_step(batches=batches, scan_dtype='float32')
        _, aux = mod.get_params()
        for layer in (1, 2):
            counts = aux['l%d_moe_counts' % layer].asnumpy()
            bias = aux['l%d_moe_selection_bias' % layer].asnumpy()
            assert counts[0].sum() == step * tokens * 4     # not doubled
            load = counts[0] - seen.get(layer, (0, 0))[0]
            move = rate * np.sign(load.mean() - load)
            assert np.abs(move).max() == rate
            close(bias, seen.get(layer, (0, 0))[1] + move, 1e-6)
            seen[layer] = (counts[0], bias)


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
    """The sigmoid router's counts fold into moe_stats() as the softmax
    router's do; the float bias beside them is no counter."""
    mod, batches, _ = _tiny_module()
    profiler.fold_device_counters()
    before = profiler.moe_stats()
    mod.bulk_step(batches=batches)
    profiler.fold_device_counters()
    after = profiler.moe_stats()
    tokens = 2 * len(batches) * SEQ * 2         # two expert layers
    assert after['moe_assignments'] - before['moe_assignments'] == \
        tokens * TINY['num_experts_per_tok']
    routed = after['moe_routed_tokens'] - before['moe_routed_tokens']
    assert 0 < routed < tokens * TINY['num_experts_per_tok']
    assert after['moe_dropped_tokens'] == before['moe_dropped_tokens']


def test_scales_and_bias_keep_float32_in_a_bfloat16_graph():
    sym = models.get_symbol('deepseek_v3', num_classes=1000,
                            dtype='bfloat16', seq_len=SEQ,
                            **program_args(TINY))
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
