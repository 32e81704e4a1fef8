"""Operations and least bytes of one training step of a hybrid sparse
language model (Gated DeltaNet and gated attention layers, a top-k
expert layer beside a shared expert), from the configuration's keys.

This is the work the model needs, whatever program does it: every dense
product once forward and twice backward, attention over the causal half
of the score matrix, the delta rule as the token-by-token recurrence
(three products of a dk x dv state and its decay a token and head), the
routed experts by the share of the top k that is held here.  Nothing
recomputed is counted, so a share of a peak worked out from it cannot
pass 100 %.

Least bytes of a product: each operand read once and the result written
once, in the configuration's compute type.
"""


def _dense(name, rows, n_in, n_out, count=1):
    """rows x n_in times n_in x n_out, `count` of them."""
    return {'name': name, 'flops': 2 * rows * n_in * n_out * count,
            'elements': (rows * n_in + n_in * n_out + rows * n_out) * count}


def forward_products(config, tokens, seq_len):
    """[{'name', 'flops', 'elements'}] of one forward pass over
    `tokens` tokens in sequences of `seq_len`."""
    c = config
    hidden = c['hidden_size']
    sequences = tokens // seq_len
    out = []
    for layer in range(c['num_hidden_layers']):
        if (layer + 1) % c['full_attention_interval'] == 0:
            heads, kv, d = (c['num_attention_heads'],
                            c['num_key_value_heads'], c['head_dim'])
            out += [_dense('q_proj', tokens, hidden, 2 * heads * d),
                    _dense('kv_proj', tokens, hidden, 2 * kv * d),
                    _dense('o_proj', tokens, heads * d, hidden)]
            pairs = seq_len * (seq_len + 1) // 2    # the causal half
            out.append({
                'name': 'attention',
                'flops': 2 * 2 * pairs * d * heads * sequences,
                'elements': tokens * d * (2 * heads + 2 * kv)})
        else:
            hk, hv = c['linear_num_key_heads'], c['linear_num_value_heads']
            dk, dv = c['linear_key_head_dim'], c['linear_value_head_dim']
            n_qkv = 2 * hk * dk + hv * dv
            out += [_dense('qkvz_proj', tokens, hidden, n_qkv + hv * dv),
                    _dense('ba_proj', tokens, hidden, 2 * hv),
                    _dense('out_proj', tokens, hv * dv, hidden)]
            out.append({'name': 'conv',
                        'flops': 2 * tokens * n_qkv *
                        c['linear_conv_kernel_dim'],
                        'elements': 2 * tokens * n_qkv})
            # S^T k, k (x) delta, S^T q: three products of dk x dv, and
            # the decay of the state; q, k, v read and o written once
            out.append({'name': 'recurrence',
                        'flops': tokens * hv * 7 * dk * dv,
                        'elements': tokens * hv * (2 * dk + 2 * dv + 2)})
        inter = c['moe_intermediate_size']
        held_pairs = tokens * c['num_experts_per_tok'] * \
            c['num_experts_held'] // c['num_experts']
        rows = max(held_pairs // c['num_experts_held'], 1)
        shared = c['shared_expert_intermediate_size']
        out += [_dense('router', tokens, hidden, c['num_experts']),
                _dense('experts_gate_up', rows, hidden, 2 * inter,
                       c['num_experts_held']),
                _dense('experts_down', rows, inter, hidden,
                       c['num_experts_held']),
                _dense('shared_gate_up', tokens, hidden, 2 * shared + 1),
                _dense('shared_down', tokens, shared, hidden)]
    out.append(_dense('lm_head', tokens, hidden, c['vocab_size']))
    return out


def seq_len_of(config):
    return int(config['reference']['arguments']['seq_len'])


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
