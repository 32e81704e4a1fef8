"""Qwen3-Next's decoder, float32 and plain: Gated DeltaNet layers, gated
softmax attention every `full_attention_interval`-th layer, a top-k
expert layer with a shared expert in every layer.

After the published model's own code (transformers' modeling_qwen3_next.py)
and its config.json; parameter names are the program's symbol arguments.
`forward(net, x, ...)` is called as reference/convnet.py describes: x is
(N,) token ids carried as float32, N = sequences x seq_len, and the
result is (N, vocabulary) logits.  Every product runs at
Precision.HIGHEST through net._product, so `lowp` gives the int8 control
and the bfloat16 witness.  It imports nothing of the program under test.

To fit float32 at the published widths beside 16 bytes a parameter of
training state: every layer of every sequence is a net.block (recomputed
in the backward pass), sequences go one at a time (lax.map), attention
goes in blocks of query rows, the recurrence keeps its state at every
64th token only, and the experts go one at a time.

Departures from the published model are marked "departure:" at their
lines.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ATTN_BLOCK = 512        # query rows a block
STATE_EVERY = 64        # the recurrence's state is kept at every 64th token


def _matmul_t(net, x, w):
    """x (.., K) times w (M, K) transposed."""
    return net._product(
        lambda a, b: jnp.matmul(a, b.T, precision=HIGHEST), x, w)


def _einsum(net, spec, a, b):
    return net._product(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), a, b)


def _linear(net, name, x, num_out):
    w = net.param(name + '_weight', (num_out, x.shape[-1]), 'he_in',
                  lowp=True)
    return _matmul_t(net, x, w)


def rms_norm(x, w, eps, zero_centered):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def _norm(net, name, x, eps):
    """The model's zero-centred norm: scale 1 + w, w starting at 0."""
    return rms_norm(x, net.param(name + '_gamma', (x.shape[-1],), 'zeros'),
                    eps, True)


# -- gated attention ---------------------------------------------------------

def rotary(x, rotary_dim, theta):
    """x (T, heads, d): rotate-half on the first rotary_dim dims."""
    t, half = x.shape[0], rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2,
                                           dtype=jnp.float32) / rotary_dim))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def causal_attention(net, q, k, v):
    """q (T, kv, group, d), k, v (T, kv, d) -> (T, kv, group, d).  A
    block of query rows at a time against every key, the keys after a
    row masked: one block's program serves all (lax.map), at twice the
    products of blocks cut to the keys they can see."""
    t, d = q.shape[0], q.shape[-1]
    block_rows = min(ATTN_BLOCK, t)
    pad = (-t) % block_rows
    keys = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qb, first_row = args
        s = _einsum(net, 'qghd,kgd->ghqk', qb, k) / math.sqrt(d)
        rows = first_row + jnp.arange(block_rows)[:, None]
        s = jnp.where(keys <= rows, s, -jnp.inf)
        return _einsum(net, 'ghqk,kgd->qghd', jax.nn.softmax(s, axis=-1), v)

    qp = jnp.pad(q, ((0, pad),) + ((0, 0),) * 3)
    blocks = qp.reshape((-1, block_rows) + q.shape[1:])
    o = lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * block_rows))
    return o.reshape((-1,) + q.shape[1:])[:t]


def gated_attention(net, name, x, c):
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    t = x.shape[0]
    qg = _linear(net, name + '_q_proj', x, heads * 2 * d)
    qg = qg.reshape(t, heads, 2 * d)         # [q, gate] split per head
    q, gate = qg[..., :d], qg[..., d:].reshape(t, heads * d)
    k = _linear(net, name + '_k_proj', x, kv * d).reshape(t, kv, d)
    v = _linear(net, name + '_v_proj', x, kv * d).reshape(t, kv, d)
    eps = c['rms_norm_eps']
    q = rms_norm(q, net.param(name + '_attn_q_norm_gamma', (d,), 'zeros'),
                 eps, True)
    k = rms_norm(k, net.param(name + '_attn_k_norm_gamma', (d,), 'zeros'),
                 eps, True)
    rotary_dim = int(d * c['partial_rotary_factor'])
    q = rotary(q, rotary_dim, c['rope_theta'])
    k = rotary(k, rotary_dim, c['rope_theta'])
    o = causal_attention(net, q.reshape(t, kv, heads // kv, d), k, v)
    o = o.reshape(t, heads * d) * jax.nn.sigmoid(gate)
    return _linear(net, name + '_o_proj', o, x.shape[-1])


# -- gated delta rule ----------------------------------------------------------

def delta_rule_recurrence(net, q, k, v, g, beta):
    """The rule as written, token by token.  q, k (T, H, dk), v
    (T, H, dv), g, beta (T, H).  S <- exp(g_t) S; d = beta_t (v_t -
    S^T k_t); S <- S + k_t (x) d; o_t = S^T q_t."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % STATE_EVERY
    if pad:     # beta = 0 and g = 0 leave the state as it is
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, pad), (0, 0))) for a in (g, beta))

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        read = _einsum(net, 'hkv,hk->hv', state, k_t)
        delta = beta_t[:, None] * (v_t - read)
        state = state + _einsum(net, 'hk,hv->hkv', k_t, delta)
        return state, _einsum(net, 'hkv,hk->hv', state, q_t)

    @jax.checkpoint
    def span(state, xs):
        return lax.scan(token, state, xs)

    xs = tuple(a.reshape((-1, STATE_EVERY) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = lax.scan(span, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(-1, h, dv)[:t]


def causal_conv(x, w):
    """Depthwise, causal: y[t, c] = sum_j w[c, j] x[t - (W-1) + j, c]."""
    t, width = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(width))


def gated_delta_net(net, name, x, c):
    hk, hv = c['linear_num_key_heads'], c['linear_num_value_heads']
    dk, dv = c['linear_key_head_dim'], c['linear_value_head_dim']
    t = x.shape[0]
    # departure: the published in_proj_qkvz and in_proj_ba interleave
    # their outputs by key head; here the rows are [q | k | v | z] and
    # [b | a], a permutation of a randomly initialised matrix's rows
    n_qkv = 2 * hk * dk + hv * dv
    qkvz = _linear(net, name + '_qkvz_proj', x, n_qkv + hv * dv)
    qkv, z = qkvz[:, :n_qkv], qkvz[:, n_qkv:]
    ba = _linear(net, name + '_ba_proj', x, 2 * hv)
    b, a = ba[:, :hv], ba[:, hv:]
    conv_w = net.param(name + '_conv_weight',
                       (qkv.shape[-1], c['linear_conv_kernel_dim']),
                       'he_in', lowp=True)
    qkv = jax.nn.silu(causal_conv(qkv, conv_w))
    q = qkv[:, :hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    # departure: A_log and dt_bias start at 0 (the published draw is
    # A ~ U(0, 16), dt_bias from a log-uniform step size): the harness
    # initialises a leaf by he_in, ones or zeros
    a_log = net.param(name + '_gdr_a_log', (hv,), 'zeros')
    dt_bias = net.param(name + '_gdr_dt_bias', (hv,), 'zeros')
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    beta = jax.nn.sigmoid(b)

    def l2norm(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2norm(q) / math.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(l2norm(k), hv // hk, axis=1)
    o = delta_rule_recurrence(net, q, k, v, g, beta)
    o = rms_norm(o, net.param(name + '_out_norm_gamma', (dv,), 'ones'),
                 c['rms_norm_eps'], False)
    o = o.reshape(t, hv * dv) * jax.nn.silu(z)
    return _linear(net, name + '_out_proj', o, x.shape[-1])


# -- experts -------------------------------------------------------------------

def routing(net, name, x, c):
    """(T, num_experts) weights: softmax over all experts in float32,
    the top k kept, normalised over the k chosen."""
    n_exp, k = c['num_experts'], c['num_experts_per_tok']
    logits = _linear(net, name + '_moe_router', x, n_exp)
    vals, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if c['norm_topk_prob']:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, n_exp) * vals[..., None], axis=1)


def routed_experts(net, name, x, c, weights=None):
    """The part of the expert layer that the experts held here give:
    each of them applied to every token, weighted by the routing (0
    where the token did not choose it).  What the experts held
    elsewhere would have added is left out."""
    hidden, inter = x.shape[-1], c['moe_intermediate_size']
    held, first = c['num_experts_held'], c['expert_offset']
    if weights is None:
        weights = routing(net, name, x, c)
    wg = net.param(name + '_moe_gate_weight', (held * inter, hidden),
                   'he_in', lowp=True).reshape(held, inter, hidden)
    wu = net.param(name + '_moe_up_weight', (held * inter, hidden),
                   'he_in', lowp=True).reshape(held, inter, hidden)
    wd = net.param(name + '_moe_down_weight', (held * hidden, inter),
                   'he_in', lowp=True).reshape(held, hidden, inter)
    # the program's per-expert counters live beside the parameters as
    # auxiliary state; the reference only declares them
    net.param(name + '_moe_counts', (2, c['num_experts']), 'zeros', aux=True)

    @jax.checkpoint
    def expert(x, w_e, wg_e, wu_e, wd_e):
        h = jax.nn.silu(_matmul_t(net, x, wg_e)) * _matmul_t(net, x, wu_e)
        return _matmul_t(net, h, wd_e) * w_e[:, None]

    def add(y, xs):
        return y + expert(x, *xs), None

    y, _ = lax.scan(add, jnp.zeros_like(x),
                    (weights[:, first:first + held].T, wg, wu, wd))
    return y


def shared_expert(net, name, x, c):
    inter = c['shared_expert_intermediate_size']
    h = jax.nn.silu(_linear(net, name + '_shared_gate_proj', x, inter)) * \
        _linear(net, name + '_shared_up_proj', x, inter)
    y = _linear(net, name + '_shared_down_proj', h, x.shape[-1])
    return y * jax.nn.sigmoid(_linear(net, name + '_shared_gate', x, 1))


def is_attention_layer(layer, interval):
    return (layer + 1) % interval == 0


def decoder_layer(net, layer, x, c):
    """One sequence (T, hidden) through layer `layer`."""
    name = 'l%d' % layer
    eps = c['rms_norm_eps']
    mixer = gated_attention if is_attention_layer(
        layer, c['full_attention_interval']) else gated_delta_net
    h = x + mixer(net, name, _norm(net, name + '_input_norm', x, eps), c)
    n = _norm(net, name + '_post_norm', h, eps)
    return h + routed_experts(net, name, n, c) + shared_expert(net, name, n, c)


def forward(net, x, seq_len, **c):
    """Logits (N, vocab_size) of N = sequences x seq_len token ids.
    departure: no multi-token-prediction module (the catalog's config
    carries no key of it) and no router auxiliary loss; `num_experts_held`
    experts from `expert_offset` of `num_experts` are computed, and the
    vocabulary and the depth are the configuration's cut."""
    ids = x.astype(jnp.int32)
    embed = net.param('embed_weight', (c['vocab_size'], c['hidden_size']),
                      'he_in', lowp=True)
    h = jnp.take(embed, ids, axis=0).reshape(-1, seq_len, c['hidden_size'])
    for layer in range(c['num_hidden_layers']):
        h = lax.map(lambda xs, layer=layer: net.block(
            lambda y: decoder_layer(net, layer, y, c), xs), h)
    h = _norm(net, 'final_norm', h.reshape(-1, c['hidden_size']),
              c['rms_norm_eps'])
    return _linear(net, 'lm_head', h, c['vocab_size'])
