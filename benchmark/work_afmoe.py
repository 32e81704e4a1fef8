"""Operations and least bytes of one training step of an AFMoE decoder
(gated grouped-head attention in every layer, windowed or full by
`layer_types`, leading dense layers, then expert layers with shared
experts), from the configuration's keys.

This is the work the model needs, whatever program does it: every dense
product once forward and twice backward, attention over the query-key
pairs the layer's mask lets through (a row's last `sliding_window` keys
on a `sliding_attention` layer, the causal half on a `full_attention`
one), the routed experts by the share of the top k that is held here.
Nothing recomputed and no pair a blocked program scores beyond its mask
is counted, so a share of a peak worked out from it cannot pass 100 %.

Least bytes of a product: each operand read once and the result written
once, in the configuration's compute type.
"""
from work_lm import _dense, seq_len_of


def needed_pairs(seq_len, window=None):
    """Query-key pairs of one head over one sequence: row i sees
    min(i + 1, window) keys."""
    reach = seq_len if window is None else min(window, seq_len)
    return reach * (reach + 1) // 2 + (seq_len - reach) * reach


def attention(c, kind, tokens, seq_len):
    """Scores and weighted values over the needed pairs; q, k, v read
    once and o written once."""
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    window = c['sliding_window'] if kind == 'sliding_attention' else None
    return {'name': 'attention_' + kind.split('_')[0],
            'flops': 2 * 2 * needed_pairs(seq_len, window) * d * heads *
            (tokens // seq_len),
            'elements': tokens * d * (2 * heads + 2 * kv)}


def forward_products(config, tokens, seq_len):
    """[{'name', 'flops', 'elements'}] of one forward pass over
    `tokens` tokens in sequences of `seq_len`."""
    c = config
    hidden, heads, kv, d = (c['hidden_size'], c['num_attention_heads'],
                            c['num_key_value_heads'], c['head_dim'])
    out = []
    for layer, kind in enumerate(c['layer_types']):
        out += [_dense('q_gate_proj', tokens, hidden, 2 * heads * d),
                _dense('kv_proj', tokens, hidden, 2 * kv * d),
                _dense('o_proj', tokens, heads * d, hidden),
                attention(c, kind, tokens, seq_len)]
        if layer < c['num_dense_layers']:
            inter = c['intermediate_size']
            out += [_dense('mlp_gate_up', tokens, hidden, 2 * inter),
                    _dense('mlp_down', tokens, inter, hidden)]
            continue
        inter, held = c['moe_intermediate_size'], c['num_experts_held']
        held_pairs = tokens * c['num_experts_per_tok'] * held // \
            c['num_experts']
        rows = max(held_pairs // held, 1)
        shared = c['num_shared_experts'] * inter
        out += [_dense('router', tokens, hidden, c['num_experts']),
                _dense('experts_gate_up', rows, hidden, 2 * inter, held),
                _dense('experts_down', rows, inter, hidden, held)]
        if shared:
            out += [_dense('shared_gate_up', tokens, hidden, 2 * shared),
                    _dense('shared_down', tokens, shared, hidden)]
    out.append(_dense('lm_head', tokens, hidden, c['vocab_size']))
    return out


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
