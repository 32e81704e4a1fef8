"""AFMoE's decoder (Trinity-Mini's `model_type`), float32 and plain:
gated grouped-head attention in every layer, windowed with rotary
positions or full with none by `layer_types`, four RMS norms a layer,
leading dense layers, then expert layers with a sigmoid router, a
selection bias and an ungated shared expert.

After the published model's own code (modeling_afmoe.py beside its
config.json); parameter names are the program's symbol arguments.
`forward(net, x, ...)` is called as reference/convnet.py describes: x is
(N,) token ids carried as float32, N = sequences x seq_len, and the
result is (N, vocabulary) logits.  Every product runs at
Precision.HIGHEST through net._product, so `lowp` gives the int8 control
and the bfloat16 witness.  It imports nothing of the program under test;
the plain products, the norms, rotary and the one-at-a-time experts are
reference/qwen3_next.py's and reference/deepseek_v3.py's.

To fit float32 at the published widths: every layer of every sequence
is a net.block, sequences go one at a time (lax.map), attention goes in
blocks of query rows against every key of the sequence (the window is a
mask on row - key, nothing is sliced to a band), the experts go one at a
time.

Departures from the published model are marked "departure:" at their
lines.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from .deepseek_v3 import _gated_mlp, _norm, is_dense_layer
from .qwen3_next import _einsum, _linear, rms_norm, rotary, routed_experts

ATTN_BLOCK = 256        # query rows a block: 32 heads x 8,192 keys each


# -- attention -----------------------------------------------------------------

def masked_attention(net, q, k, v, window):
    """q (T, kv, group, d), k, v (T, kv, d) -> (T, kv, group, d).  Row i
    sees key j iff j <= i and, with a window, i - j < window.  A block
    of query rows at a time against every key (lax.map: one block's
    program serves all)."""
    t, d = q.shape[0], q.shape[-1]
    block_rows = min(ATTN_BLOCK, t)
    pad = (-t) % block_rows
    keys = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qb, first_row = args
        s = _einsum(net, 'qghd,kgd->ghqk', qb, k) / math.sqrt(d)
        rows = first_row + jnp.arange(block_rows)[:, None]
        seen = keys <= rows
        if window is not None:
            seen = seen & (rows - keys < window)
        s = jnp.where(seen, s, -jnp.inf)
        return _einsum(net, 'ghqk,kgd->qghd', jax.nn.softmax(s, axis=-1), v)

    qp = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3)
    blocks = qp.reshape((-1, block_rows) + q.shape[1:])
    o = lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * block_rows))
    return o.reshape((-1,) + q.shape[1:])[:t]


def gated_attention(net, name, x, kind, c):
    """AfmoeAttention: per-head RMS norms on q and k with plain scales;
    on a `sliding_attention` layer rotary (rotate-half, all of the
    head) and the window, on a `full_attention` layer neither; the
    output times the sigmoid of a projection of the layer's input."""
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    t = x.shape[0]
    q = _linear(net, name + '_q_proj', x, heads * d).reshape(t, heads, d)
    k = _linear(net, name + '_k_proj', x, kv * d).reshape(t, kv, d)
    v = _linear(net, name + '_v_proj', x, kv * d).reshape(t, kv, d)
    gate = _linear(net, name + '_gate_proj', x, heads * d)
    eps = c['rms_norm_eps']
    q = rms_norm(q, net.param(name + '_attn_q_norm_gamma', (d,), 'ones'),
                 eps, False)
    k = rms_norm(k, net.param(name + '_attn_k_norm_gamma', (d,), 'ones'),
                 eps, False)
    window = None
    if kind == 'sliding_attention':
        q, k = rotary(q, d, c['rope_theta']), rotary(k, d, c['rope_theta'])
        window = c['sliding_window']
    elif kind != 'full_attention':
        raise ValueError('layer type %r' % (kind,))
    o = masked_attention(net, q.reshape(t, kv, heads // kv, d), k, v, window)
    o = o.reshape(t, heads * d) * jax.nn.sigmoid(gate)
    return _linear(net, name + '_o_proj', o, x.shape[-1])


# -- experts -------------------------------------------------------------------

def routing(net, name, x, c):
    """(T, num_experts) weights as AfmoeTokenChoiceRouter gives them:
    sigmoid scores in float32, the top k of scores + expert_bias (one
    group), the chosen scores over their sum + 1e-20 (`route_norm`),
    times `route_scale`.  The bias is not in the weights."""
    n_exp, k = c['num_experts'], c['num_experts_per_tok']
    scores = jax.nn.sigmoid(_linear(net, name + '_moe_router', x, n_exp))
    # departure: the bias is auxiliary state the harness holds at its
    # start, zeros; the published training moves it after every step
    bias = net.param(name + '_moe_selection_bias', (n_exp,), 'zeros',
                     aux=True)
    _, idx = lax.top_k(scores + bias, k)
    weights = scores * jax.nn.one_hot(idx, n_exp).sum(axis=1)
    if c['route_norm']:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * c['route_scale']


def expert_layer(net, name, x, c):
    """The routed experts held here (one at a time, masked by the
    routing) and the shared experts, one gated feed-forward of
    num_shared_experts x moe_intermediate_size, added without a gate."""
    y = routed_experts(net, name, x, c, weights=routing(net, name, x, c))
    if c['num_shared_experts']:
        y = y + _gated_mlp(net, name + '_shared', x, c['num_shared_experts'] *
                           c['moe_intermediate_size'])
    return y


def decoder_layer(net, layer, x, c):
    """One sequence (T, hidden) through layer `layer`: a norm before
    and a norm after each half, the residual around both."""
    name = 'l%d' % layer
    eps = c['rms_norm_eps']
    a = gated_attention(net, name, _norm(net, name + '_input_norm', x, eps),
                        c['layer_types'][layer], c)
    h = x + _norm(net, name + '_post_attn_norm', a, eps)
    n = _norm(net, name + '_pre_mlp_norm', h, eps)
    f = _gated_mlp(net, name + '_mlp', n, c['intermediate_size']) \
        if is_dense_layer(layer, c['num_dense_layers']) \
        else expert_layer(net, name, n, c)
    return h + _norm(net, name + '_post_mlp_norm', f, eps)


def forward(net, x, seq_len, **c):
    """Logits (N, vocab_size) of N = sequences x seq_len token ids.
    departure: no load-balance auxiliary loss and no update of the
    selection bias; norm scales start at 1 (the published
    initialisation scales them with depth); `num_experts_held` experts
    from `expert_offset` of `num_experts` are computed, and the
    vocabulary and the depth are the configuration's cut."""
    if len(c['layer_types']) != c['num_hidden_layers']:
        raise ValueError('%d layer types for %d layers' % (
            len(c['layer_types']), c['num_hidden_layers']))
    ids = x.astype(jnp.int32)
    embed = net.param('embed_weight', (c['vocab_size'], c['hidden_size']),
                      'he_in', lowp=True)
    h = jnp.take(embed, ids, axis=0).reshape(-1, seq_len, c['hidden_size'])
    if c['mup_enabled']:
        h = h * math.sqrt(c['hidden_size'])
    for layer in range(c['num_hidden_layers']):
        h = lax.map(lambda xs, layer=layer: net.block(
            lambda y: decoder_layer(net, layer, y, c), xs), h)
    h = _norm(net, 'final_norm', h.reshape(-1, c['hidden_size']),
              c['rms_norm_eps'])
    return _linear(net, 'lm_head', h, c['vocab_size'])
