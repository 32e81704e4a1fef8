"""Operations and least bytes of one training step of a DeepSeek-V3-
family decoder (multi-head latent attention in every layer, leading
dense layers, then expert layers with shared experts), from the
configuration's keys.

This is the work the model needs, whatever program does it: every dense
product once forward and twice backward, attention over the causal half
of the score matrix with keys of qk_nope_head_dim + qk_rope_head_dim and
values of v_head_dim, the routed experts by the share of the top k that
is held here.  Nothing recomputed is counted, so a share of a peak
worked out from it cannot pass 100 %.

Least bytes of a product: each operand read once and the result written
once, in the configuration's compute type.
"""
from work_lm import _dense, seq_len_of


def attention(c, tokens, seq_len):
    """Scores and weighted values over the causal half; q, the keys'
    per-head part and their one shared rotary head, and v read once, o
    written once."""
    heads = c['num_attention_heads']
    nope, rope, dv = (c['qk_nope_head_dim'], c['qk_rope_head_dim'],
                      c['v_head_dim'])
    pairs = seq_len * (seq_len + 1) // 2
    return {'name': 'attention',
            'flops': 2 * pairs * (nope + rope + dv) * heads *
            (tokens // seq_len),
            'elements': tokens * (heads * (nope + rope) + heads * nope +
                                  rope + 2 * heads * dv)}


def forward_products(config, tokens, seq_len):
    """[{'name', 'flops', 'elements'}] of one forward pass over
    `tokens` tokens in sequences of `seq_len`."""
    c = config
    hidden, heads = c['hidden_size'], c['num_attention_heads']
    nope, rope, dv = (c['qk_nope_head_dim'], c['qk_rope_head_dim'],
                      c['v_head_dim'])
    rank = c['kv_lora_rank']
    out = []
    for layer in range(c['num_hidden_layers']):
        out += [_dense('q_proj', tokens, hidden, heads * (nope + rope)),
                _dense('kv_a_proj', tokens, hidden, rank + rope),
                _dense('kv_b_proj', tokens, rank, heads * (nope + dv)),
                _dense('o_proj', tokens, heads * dv, hidden),
                attention(c, tokens, seq_len)]
        if layer < c['first_k_dense_replace']:
            inter = c['intermediate_size']
            out += [_dense('mlp_gate_up', tokens, hidden, 2 * inter),
                    _dense('mlp_down', tokens, inter, hidden)]
            continue
        inter, held = c['moe_intermediate_size'], c['num_experts_held']
        held_pairs = tokens * c['num_experts_per_tok'] * held // \
            c['n_routed_experts']
        rows = max(held_pairs // held, 1)
        shared = c['n_shared_experts'] * inter
        out += [_dense('router', tokens, hidden, c['n_routed_experts']),
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
