"""Qwen3-Next: a hybrid decoder of Gated DeltaNet layers with a gated
softmax-attention layer every `full_attention_interval`-th, and in every
layer a top-k expert layer beside a shared expert.

After the published model's code (transformers' modeling_qwen3_next.py)
and config.json, whose keys the arguments keep.  Tokens are rows: the
data is (N,) ids with N = sequences x seq_len, the label the next ids.
`num_experts_held` experts from `expert_offset` are this device's share
of `num_experts`: the router still chooses among all of them, and what
the experts held elsewhere would add is left out (the layer on one
member of an expert-parallel group, without its exchange).

Every half layer (norm and mixer, norm and experts) carries
`__force_mirroring__`: the executor recomputes it in the backward pass
and keeps only the residual stream between them.
"""
from .. import initializer
from .. import symbol as sym
from ..attribute import AttrScope


def is_attention_layer(layer, full_attention_interval):
    return (layer + 1) % full_attention_interval == 0


def _linear(x, name, num_hidden):
    return sym.FullyConnected(x, num_hidden=num_hidden, no_bias=True,
                              name=name)


def _zeros(name):
    """A leaf that starts at 0 whatever initializer the user passes: a
    zero-centred scale, a decay rate, a counter."""
    return sym.Variable(name, init=initializer.Zero())


def _norm(x, name, eps):
    return sym.RMSNorm(x, gamma=_zeros(name + '_gamma'), eps=eps,
                       zero_centered=True, name=name)


def _columns(x, begin, end):
    return sym.slice_axis(x, axis=1, begin=begin, end=end)


def gated_attention(x, name, c):
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    o = sym.GatedAttention(
        query_gate=_linear(x, name + '_q_proj', heads * 2 * d),
        key=_linear(x, name + '_k_proj', kv * d),
        value=_linear(x, name + '_v_proj', kv * d),
        q_norm_gamma=_zeros(name + '_attn_q_norm_gamma'),
        k_norm_gamma=_zeros(name + '_attn_k_norm_gamma'),
        num_heads=heads, num_kv_heads=kv, head_dim=d,
        rotary_dim=int(d * c['partial_rotary_factor']),
        rope_theta=c['rope_theta'], eps=c['rms_norm_eps'],
        seq_len=c['seq_len'], name=name + '_attn')
    return _linear(o, name + '_o_proj', c['hidden_size'])


def gated_delta_net(x, name, c):
    hk, hv = c['linear_num_key_heads'], c['linear_num_value_heads']
    dk, dv = c['linear_key_head_dim'], c['linear_value_head_dim']
    n_qkv = 2 * hk * dk + hv * dv
    qkvz = _linear(x, name + '_qkvz_proj', n_qkv + hv * dv)
    ba = _linear(x, name + '_ba_proj', 2 * hv)
    qkv = sym.CausalConv1D(_columns(qkvz, 0, n_qkv),
                           kernel=c['linear_conv_kernel_dim'],
                           seq_len=c['seq_len'], name=name + '_conv')
    o = sym.GatedDeltaRule(
        data=sym.Activation(qkv, act_type='silu'),
        a=_columns(ba, hv, 2 * hv), b=_columns(ba, 0, hv),
        a_log=_zeros(name + '_gdr_a_log'),
        dt_bias=_zeros(name + '_gdr_dt_bias'),
        num_k_heads=hk, num_v_heads=hv, head_k_dim=dk, head_v_dim=dv,
        seq_len=c['seq_len'], name=name + '_gdr')
    o = sym.RMSNorm(sym.Reshape(o, shape=(-1, dv)), eps=c['rms_norm_eps'],
                    name=name + '_out_norm')
    z = sym.Activation(_columns(qkvz, n_qkv, n_qkv + hv * dv),
                       act_type='silu')
    return _linear(sym.Reshape(o, shape=(-1, hv * dv)) * z,
                   name + '_out_proj', c['hidden_size'])


def expert_layer(x, name, c):
    routed = sym.SparseMoE(
        x, counts=_zeros(name + '_moe_counts'), num_experts=c['num_experts'],
        num_experts_held=c['num_experts_held'],
        expert_offset=c['expert_offset'], top_k=c['num_experts_per_tok'],
        normalize=c['norm_topk_prob'],
        intermediate_size=c['moe_intermediate_size'], name=name + '_moe')
    inter = c['shared_expert_intermediate_size']
    h = sym.Activation(_linear(x, name + '_shared_gate_proj', inter),
                       act_type='silu') * \
        _linear(x, name + '_shared_up_proj', inter)
    shared = sym.broadcast_mul(
        _linear(h, name + '_shared_down_proj', c['hidden_size']),
        sym.Activation(_linear(x, name + '_shared_gate', 1),
                       act_type='sigmoid'))
    return routed + shared


def get_symbol(num_classes=151936, seq_len=8192, dtype='float32',
               hidden_size=2048, num_hidden_layers=48,
               full_attention_interval=4, num_attention_heads=16,
               num_key_value_heads=2, head_dim=256,
               partial_rotary_factor=0.25, rope_theta=10000000.0,
               linear_num_key_heads=16, linear_num_value_heads=32,
               linear_key_head_dim=128, linear_value_head_dim=128,
               linear_conv_kernel_dim=4, num_experts=512,
               num_experts_held=None, expert_offset=0,
               num_experts_per_tok=10, norm_topk_prob=True,
               moe_intermediate_size=512,
               shared_expert_intermediate_size=512, rms_norm_eps=1e-6,
               **kwargs):
    """num_classes: the rows of the vocabulary held here (embedding and
    head, untied).  dtype: the compute type; float32 scales and decay
    rates stay float32.  Every half layer (mixer, experts) is marked
    `__force_mirroring__`: the fused step makes it again in the
    backward pass."""
    c = dict(locals())
    c.pop('kwargs')
    if num_experts_held is None:
        c['num_experts_held'] = num_experts
    data = sym.Variable('data')
    h = sym.Embedding(data, input_dim=num_classes, output_dim=hidden_size,
                      dtype=dtype, name='embed')
    for layer in range(num_hidden_layers):
        name = 'l%d' % layer
        mixer = gated_attention if is_attention_layer(
            layer, full_attention_interval) else gated_delta_net
        with AttrScope(__force_mirroring__='True'):
            mixed = mixer(_norm(h, name + '_input_norm', rms_norm_eps),
                          name, c)
        h = h + mixed
        with AttrScope(__force_mirroring__='True'):
            experts = expert_layer(
                _norm(h, name + '_post_norm', rms_norm_eps), name, c)
        h = h + experts
    # the logits stay in the compute type: tokens x vocabulary in
    # float32 three times over (logits, probabilities, their gradient)
    # would be a quarter of a chip; SoftmaxOutput sums in float32 inside
    logits = _linear(_norm(h, 'final_norm', rms_norm_eps), 'lm_head',
                     num_classes)
    return sym.SoftmaxOutput(logits, name='softmax')
