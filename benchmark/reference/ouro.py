"""Ouro's looped decoder (`model_type: ouro`), float32 and plain: the
stack of layers run `total_ut_steps` times with the same weights, the
final RMS norm after each pass, the logits the last pass's.  A layer is
rotary multi-head attention (rotate-half on every dim of the head, no
biases, no q/k norms) and a gated feed-forward, each between two plain
RMS norms (sandwich).

After the published model's own code (modeling_ouro.py beside its
config.json); parameter names are the program's symbol arguments.
`forward(net, x, ...)` is called as reference/convnet.py describes: x is
(N,) token ids carried as float32, N = sequences x seq_len, and the
result is (N, vocabulary) logits.  Every product runs at
Precision.HIGHEST through net._product, so `lowp` gives the int8 control
and the bfloat16 witness.  It imports nothing of the program under test;
the plain products, the norms, rotary and the gated feed-forward are
reference/qwen3_next.py's and reference/deepseek_v3.py's, attention is
reference/afmoe.py's with one query head a key head and no window.

To fit float32 at the published widths: the passes are a loop
(lax.fori_loop) whose body, one pass, is a net.block, and inside it
every layer of every sequence is one (lax.map: sequences one at a
time); the backward keeps the stream where each pass starts and, while
it goes back through a pass, where each layer starts.  The same weights
are read in each pass, their gradients summed in float32.

Departures from the published model are marked "departure:" at their
lines.
"""
import jax.numpy as jnp
from jax import lax

from .afmoe import masked_attention
from .deepseek_v3 import _gated_mlp, _norm
from .qwen3_next import _linear, rotary


def attention(net, name, x, c):
    """Multi-head attention with rotary on every dim of the head."""
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    t = x.shape[0]
    q = _linear(net, name + '_q_proj', x, heads * d).reshape(t, heads, d)
    k = _linear(net, name + '_k_proj', x, kv * d).reshape(t, kv, d)
    v = _linear(net, name + '_v_proj', x, kv * d).reshape(t, kv, d)
    q, k = rotary(q, d, c['rope_theta']), rotary(k, d, c['rope_theta'])
    o = masked_attention(net, q.reshape(t, kv, heads // kv, d), k, v, None)
    return _linear(net, name + '_o_proj', o.reshape(t, heads * d),
                   x.shape[-1])


def decoder_layer(net, layer, x, c):
    """One sequence (T, hidden) through layer `layer`: a norm before
    and a norm after each half, the residual around both."""
    name = 'l%d' % layer
    eps = c['rms_norm_eps']
    a = attention(net, name, _norm(net, name + '_input_norm', x, eps), c)
    h = x + _norm(net, name + '_post_attn_norm', a, eps)
    f = _gated_mlp(net, name + '_mlp', _norm(net, name + '_pre_mlp_norm', h,
                                              eps), c['intermediate_size'])
    return h + _norm(net, name + '_post_mlp_norm', f, eps)


def forward(net, x, seq_len, **c):
    """Logits (N, vocab_size) of N = sequences x seq_len token ids.
    departure: no exit gate and no loss over the exits (at
    early_exit_threshold 1 every pass runs and the output is the last
    pass's; the harness's step takes the cross-entropy of one output);
    norm scales start at 1; the depth is the configuration's cut."""
    ids = x.astype(jnp.int32)
    hidden = c['hidden_size']
    embed = net.param('embed_weight', (c['vocab_size'], hidden), 'he_in',
                      lowp=True)
    h = jnp.take(embed, ids, axis=0).reshape(-1, seq_len, hidden)

    def one_pass(h):
        for layer in range(c['num_hidden_layers']):
            h = lax.map(lambda xs, layer=layer: net.block(
                lambda y: decoder_layer(net, layer, y, c), xs), h)
        # departure (assumed): the final norm closes every pass, and its
        # output is the next pass's input
        return _norm(net, 'final_norm', h, c['rms_norm_eps'])

    h = lax.fori_loop(0, c['total_ut_steps'],
                      lambda _, h: net.block(one_pass, h), h)
    return _linear(net, 'lm_head', h.reshape(-1, hidden), c['vocab_size'])
