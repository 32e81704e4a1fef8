"""Operations and least bytes of one training step of a looped decoder
(Ouro's: the stack of layers run `total_ut_steps` times with the same
weights, rotary multi-head attention and a gated feed-forward a layer),
from the configuration's keys.

This is the work the model needs, whatever program does it: every layer
counted once a pass, its dense products once forward and twice
backward, attention over the causal half of the score matrix, the head
once.  Nothing recomputed is counted (the half layers the program makes
again in the backward pass), so a share of a peak worked out from it
cannot pass 100 %.

Least bytes of a product: each operand read once and the result written
once, in the configuration's compute type; a weight is read again in
each pass.
"""
from work_lm import _dense, seq_len_of


def attention(c, tokens, seq_len):
    """Scores and weighted values over the causal half; q, k, v read
    once and o written once."""
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    pairs = seq_len * (seq_len + 1) // 2
    return {'name': 'attention',
            'flops': 2 * 2 * pairs * d * heads * (tokens // seq_len),
            'elements': tokens * d * (2 * heads + 2 * kv)}


def layer_products(c, tokens, seq_len):
    """[{'name', 'flops', 'elements'}] of one layer's forward pass."""
    hidden, heads, kv, d = (c['hidden_size'], c['num_attention_heads'],
                            c['num_key_value_heads'], c['head_dim'])
    inter = c['intermediate_size']
    return [_dense('q_proj', tokens, hidden, heads * d),
            _dense('kv_proj', tokens, hidden, 2 * kv * d),
            _dense('o_proj', tokens, heads * d, hidden),
            attention(c, tokens, seq_len),
            _dense('mlp_gate_up', tokens, hidden, 2 * inter),
            _dense('mlp_down', tokens, inter, hidden)]


def forward_products(config, tokens, seq_len):
    """One forward pass over `tokens` tokens in sequences of `seq_len`:
    every layer once a pass, then the head."""
    c = config
    applications = c['total_ut_steps'] * c['num_hidden_layers']
    return layer_products(c, tokens, seq_len) * applications + [
        _dense('lm_head', tokens, c['hidden_size'], c['vocab_size'])]


def train_flops(config, tokens):
    """Forward and backward operations of one step: every product has
    two gradients, each of the forward product's operations."""
    return 3 * sum(p['flops'] for p in
                   forward_products(config, tokens, seq_len_of(config)))


def roofline_seconds(config, tokens, peak_flops, peak_bytes_per_s,
                     bytes_per_el):
    """The least time one chip could take over one step: the forward
    product and its two gradients, each at the larger of its compute
    time and its memory time."""
    total = 0.0
    for p in forward_products(config, tokens, seq_len_of(config)):
        total += 3 * max(p['flops'] / peak_flops,
                         p['elements'] * bytes_per_el / peak_bytes_per_s)
    return total
