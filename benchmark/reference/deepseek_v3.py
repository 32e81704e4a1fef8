"""DeepSeek-V3's decoder (Kanana-2-30B-A3B's `model_type`), float32 and
plain: multi-head latent attention in every layer, a leading dense
layer, then expert layers with a sigmoid router, a selection bias and
ungated shared experts.

After the published model's own code (transformers'
modeling_deepseek_v3.py) and its config.json; parameter names are the
program's symbol arguments.  `forward(net, x, ...)` is called as
reference/convnet.py describes: x is (N,) token ids carried as float32,
N = sequences x seq_len, and the result is (N, vocabulary) logits.
Every product runs at Precision.HIGHEST through net._product, so `lowp`
gives the int8 control and the bfloat16 witness.  It imports nothing of
the program under test; the plain products, the norm and the one-at-a-
time experts are reference/qwen3_next.py's.

To fit float32 at the published widths: every layer of every sequence
is a net.block, sequences go one at a time (lax.map), attention goes in
blocks of query rows against every key, the experts go one at a time.

Departures from the published model are marked "departure:" at their
lines.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from .qwen3_next import _einsum, _linear, rms_norm, routed_experts

ATTN_BLOCK = 256        # query rows a block: 32 heads x 8,192 keys each


def _norm(net, name, x, eps):
    """A plain RMS norm: scale w, starting at 1."""
    return rms_norm(x, net.param(name + '_gamma', (x.shape[-1],), 'ones'),
                    eps, False)


def _gated_mlp(net, name, x, width):
    h = jax.nn.silu(_linear(net, name + '_gate_proj', x, width)) * \
        _linear(net, name + '_up_proj', x, width)
    return _linear(net, name + '_down_proj', h, x.shape[-1])


# -- latent attention ----------------------------------------------------------

def rotary_interleaved(x, theta):
    """The published apply_rotary_pos_emb_interleave on x (T, heads, d):
    the even dims moved before the odd ones, then rotate-half with the
    frequencies theta^(-2i/d) repeated over both halves.  (The result
    keeps the moved order; queries and keys share it.)"""
    t, d = x.shape[0], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def causal_attention(net, q_nope, q_pe, k_nope, k_pe, v):
    """q_nope, k_nope (T, H, dn), q_pe (T, H, dr), k_pe (T, dr): one
    rotary head for all H, v (T, H, dv) -> (T, H, dv).  Head h's
    scores are q_nope_h k_nope_h^T + q_pe_h k_pe^T over sqrt(dn + dr):
    the key of width dn + dr is never joined, the value keeps its own
    width.  A block of query rows at a time against every key, the keys
    after a row masked (lax.map: one block's program serves all)."""
    t = q_nope.shape[0]
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_pe.shape[-1])
    block_rows = min(ATTN_BLOCK, t)
    pad = (-t) % block_rows
    keys = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(args):
        qn, qp, first_row = args
        s = (_einsum(net, 'qhd,khd->hqk', qn, k_nope) +
             _einsum(net, 'qhd,kd->hqk', qp, k_pe)) * scale
        rows = first_row + jnp.arange(block_rows)[:, None]
        s = jnp.where(keys <= rows, s, -jnp.inf)
        return _einsum(net, 'hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, block_rows) + a.shape[1:])

    qn, qp = blocks(q_nope), blocks(q_pe)
    o = lax.map(block, (qn, qp, jnp.arange(qn.shape[0]) * block_rows))
    return o.reshape((-1,) + v.shape[1:])[:t]


def latent_attention(net, name, x, c):
    """DeepseekV3Attention with q_lora_rank null: the query is one
    projection; keys and values come up from a normed latent of
    kv_lora_rank, the keys' rotary part straight from the input."""
    heads, rank = c['num_attention_heads'], c['kv_lora_rank']
    nope, rope, dv = (c['qk_nope_head_dim'], c['qk_rope_head_dim'],
                      c['v_head_dim'])
    t = x.shape[0]
    q = _linear(net, name + '_q_proj', x, heads * (nope + rope))
    q = q.reshape(t, heads, nope + rope)
    kv_a = _linear(net, name + '_kv_a_proj', x, rank + rope)
    latent = _norm(net, name + '_kv_a_norm', kv_a[:, :rank],
                   c['rms_norm_eps'])
    kv = _linear(net, name + '_kv_b_proj', latent, heads * (nope + dv))
    kv = kv.reshape(t, heads, nope + dv)
    q_pe = rotary_interleaved(q[..., nope:], c['rope_theta'])
    k_pe = rotary_interleaved(kv_a[:, None, rank:], c['rope_theta'])[:, 0]
    o = causal_attention(net, q[..., :nope], q_pe, kv[..., :nope], k_pe,
                         kv[..., nope:])
    return _linear(net, name + '_o_proj', o.reshape(t, heads * dv),
                   x.shape[-1])


# -- experts -------------------------------------------------------------------

def routing(net, name, x, c):
    """(T, n_routed_experts) weights as DeepseekV3TopkRouter gives
    them: sigmoid scores in float32, the top k of scores + bias (one
    group: n_group = topk_group = 1), the chosen scores over their sum
    + 1e-20, times routed_scaling_factor.  The bias is not in the
    weights."""
    n_exp, k = c['n_routed_experts'], c['num_experts_per_tok']
    scores = jax.nn.sigmoid(_linear(net, name + '_moe_router', x, n_exp))
    # departure: the bias is auxiliary state the harness holds at its
    # start, zeros; the published training moves it after every step
    bias = net.param(name + '_moe_selection_bias', (n_exp,), 'zeros',
                     aux=True)
    _, idx = lax.top_k(scores + bias, k)
    chosen = jax.nn.one_hot(idx, n_exp).sum(axis=1)
    weights = scores * chosen
    if c['norm_topk_prob']:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * c['routed_scaling_factor']


def expert_layer(net, name, x, c):
    """The routed experts held here (one at a time, masked by the
    routing) and the shared experts, one gated feed-forward of
    n_shared_experts x moe_intermediate_size, added without a gate."""
    y = routed_experts(net, name, x,
                       dict(c, num_experts=c['n_routed_experts']),
                       weights=routing(net, name, x, c))
    if c['n_shared_experts']:
        y = y + _gated_mlp(net, name + '_shared', x, c['n_shared_experts'] *
                           c['moe_intermediate_size'])
    return y


def is_dense_layer(layer, first_k_dense_replace):
    return layer < first_k_dense_replace


def decoder_layer(net, layer, x, c):
    """One sequence (T, hidden) through layer `layer`."""
    name = 'l%d' % layer
    eps = c['rms_norm_eps']
    h = x + latent_attention(net, name,
                             _norm(net, name + '_input_norm', x, eps), c)
    n = _norm(net, name + '_post_norm', h, eps)
    if is_dense_layer(layer, c['first_k_dense_replace']):
        return h + _gated_mlp(net, name + '_mlp', n, c['intermediate_size'])
    return h + expert_layer(net, name, n, c)


def forward(net, x, seq_len, **c):
    """Logits (N, vocab_size) of N = sequences x seq_len token ids.
    departure: no multi-token-prediction module and no auxiliary loss
    (the catalog's config carries no key of either); `num_experts_held`
    experts from `expert_offset` of `n_routed_experts` are computed, and
    the vocabulary and the depth are the configuration's cut."""
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
