"""DeepSeek-V3's decoder (`model_type: deepseek_v3`; Kanana-2-30B-A3B is
one): multi-head latent attention in every layer, `first_k_dense_replace`
leading layers with a dense gated feed-forward, then expert layers: a
sigmoid router with a selection bias (`topk_method: noaux_tc`) over
`n_routed_experts`, beside `n_shared_experts` shared experts that are
added without a gate.

After the published code (transformers' modeling_deepseek_v3.py) and
config.json, whose keys the arguments keep.  Tokens are rows: the data
is (N,) ids with N = sequences x seq_len, the label the next ids.
`num_experts_held` experts from `expert_offset` are this device's share
of `n_routed_experts`, as in qwen3_next.  Rotary turns adjacent pairs in
place (`rope_interleave`): the projections keep the published rows'
order.

Every half layer (norm and attention, norm and feed-forward) carries
`__force_mirroring__`, as in qwen3_next.
"""
from .. import initializer
from .. import symbol as sym
from ..attribute import AttrScope
from ..base import MXNetError
from .qwen3_next import _columns, _linear, _zeros


def is_dense_layer(layer, first_k_dense_replace):
    return layer < first_k_dense_replace


def _norm(x, name, eps):
    """A plain RMS norm: scale w, starting at 1."""
    return sym.RMSNorm(
        x, gamma=sym.Variable(name + '_gamma', init=initializer.One()),
        eps=eps, name=name)


def _gated_mlp(x, name, width, hidden_size):
    h = sym.Activation(_linear(x, name + '_gate_proj', width),
                       act_type='silu') * _linear(x, name + '_up_proj', width)
    return _linear(h, name + '_down_proj', hidden_size)


def latent_attention(x, name, c):
    heads, rank = c['num_attention_heads'], c['kv_lora_rank']
    nope, rope, dv = (c['qk_nope_head_dim'], c['qk_rope_head_dim'],
                      c['v_head_dim'])
    kv_a = _linear(x, name + '_kv_a_proj', rank + rope)     # [c | k_pe]
    latent = _norm(_columns(kv_a, 0, rank), name + '_kv_a_norm',
                   c['rms_norm_eps'])
    o = sym.LatentAttention(
        query=_linear(x, name + '_q_proj', heads * (nope + rope)),
        key_value=_linear(latent, name + '_kv_b_proj', heads * (nope + dv)),
        key_rope=_columns(kv_a, rank, rank + rope),
        num_heads=heads, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=dv, rope_theta=c['rope_theta'], seq_len=c['seq_len'],
        name=name + '_attn')
    return _linear(o, name + '_o_proj', c['hidden_size'])


def expert_layer(x, name, c):
    bias = {'selection_bias': _zeros(name + '_moe_selection_bias')} \
        if c['topk_method'] == 'noaux_tc' else {}
    routed = sym.SparseMoE(
        x, counts=_zeros(name + '_moe_counts'),
        num_experts=c['n_routed_experts'],
        num_experts_held=c['num_experts_held'],
        expert_offset=c['expert_offset'], top_k=c['num_experts_per_tok'],
        normalize=c['norm_topk_prob'], scoring_func=c['scoring_func'],
        topk_method=c['topk_method'],
        routed_scaling_factor=c['routed_scaling_factor'],
        bias_update_rate=c['bias_update_rate'],
        intermediate_size=c['moe_intermediate_size'], name=name + '_moe',
        **bias)
    if not c['n_shared_experts']:
        return routed
    return routed + _gated_mlp(
        x, name + '_shared', c['n_shared_experts'] *
        c['moe_intermediate_size'], c['hidden_size'])


def get_symbol(num_classes=128256, seq_len=8192, dtype='float32',
               hidden_size=2048, num_hidden_layers=48,
               first_k_dense_replace=1, intermediate_size=6144,
               num_attention_heads=32, q_lora_rank=None, kv_lora_rank=512,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
               rope_theta=1000000.0, rope_interleave=True,
               n_routed_experts=128, num_experts_held=None, expert_offset=0,
               n_shared_experts=2, num_experts_per_tok=6,
               moe_intermediate_size=768, norm_topk_prob=True,
               scoring_func='sigmoid', topk_method='noaux_tc',
               routed_scaling_factor=2.448, n_group=1, topk_group=1,
               bias_update_rate=0.0, rms_norm_eps=1e-6, **kwargs):
    """num_classes: the rows of the vocabulary held here (embedding and
    head, untied).  dtype: the compute type; norm scales and the
    selection bias stay float32.  bias_update_rate: the step of
    DeepSeek-V3's bias rule (0: the bias stays as loaded)."""
    c = dict(locals())
    c.pop('kwargs')
    if q_lora_rank is not None:
        raise MXNetError('deepseek_v3: q_lora_rank %r (a compressed query) '
                         'is not built; pass None' % (q_lora_rank,))
    if not rope_interleave or n_group != 1 or topk_group != 1:
        raise MXNetError('deepseek_v3: built for rope_interleave and one '
                         'group of experts (n_group = topk_group = 1)')
    if num_experts_held is None:
        c['num_experts_held'] = n_routed_experts
    data = sym.Variable('data')
    h = sym.Embedding(data, input_dim=num_classes, output_dim=hidden_size,
                      dtype=dtype, name='embed')
    for layer in range(num_hidden_layers):
        name = 'l%d' % layer
        with AttrScope(__force_mirroring__='True'):
            mixed = latent_attention(
                _norm(h, name + '_input_norm', rms_norm_eps), name, c)
        h = h + mixed
        with AttrScope(__force_mirroring__='True'):
            n = _norm(h, name + '_post_norm', rms_norm_eps)
            ffn = _gated_mlp(n, name + '_mlp', intermediate_size,
                             hidden_size) \
                if is_dense_layer(layer, first_k_dense_replace) \
                else expert_layer(n, name, c)
        h = h + ffn
    # the logits stay in the compute type, as in qwen3_next
    logits = _linear(_norm(h, 'final_norm', rms_norm_eps), 'lm_head',
                     num_classes)
    return sym.SoftmaxOutput(logits, name='softmax')
