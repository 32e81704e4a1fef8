"""AFMoE's decoder (`model_type: afmoe`; Arcee's Trinity-Mini is one):
gated softmax attention over grouped heads in every layer, by
`layer_types` either `sliding_attention` (rotary positions, a row sees
its last `sliding_window` keys) or `full_attention` (no positions at
all, every earlier key); four RMS norms a layer, one before and one
after each half (sandwich); `num_dense_layers` leading layers with a
dense gated feed-forward, then expert layers: a sigmoid router with a
selection bias over `num_experts`, beside `num_shared_experts` shared
experts that are added without a gate.  With `mup_enabled` the
embedding is scaled by sqrt(hidden_size).

After the published code (modeling_afmoe.py beside the model's
config.json), whose keys the arguments keep.  Tokens are rows: the data
is (N,) ids with N = sequences x seq_len, the label the next ids.
`num_experts_held` experts from `expert_offset` are this device's share
of `num_experts`, as in qwen3_next.

Every half layer (norm, attention, norm; norm, feed-forward, norm)
carries `__force_mirroring__`, as in qwen3_next.
"""
import math

from .. import initializer
from .. import symbol as sym
from ..attribute import AttrScope
from ..base import MXNetError
from .deepseek_v3 import _gated_mlp, _norm, is_dense_layer
from .qwen3_next import _linear, _zeros

LAYER_TYPES = ('sliding_attention', 'full_attention')


def _ones(name):
    """A plain norm's scale: a leaf that starts at 1."""
    return sym.Variable(name, init=initializer.One())


def layer_types_of(num_hidden_layers, global_attn_every_n_layers):
    """The published rule: every n-th layer attends to all keys."""
    return [LAYER_TYPES[(layer + 1) % global_attn_every_n_layers == 0]
            for layer in range(num_hidden_layers)]


def gated_attention(x, name, kind, c):
    """Windowed layers carry rotary positions; full layers carry none."""
    heads, kv, d = (c['num_attention_heads'], c['num_key_value_heads'],
                    c['head_dim'])
    local = kind == 'sliding_attention'
    o = sym.GatedAttention(
        query=_linear(x, name + '_q_proj', heads * d),
        key=_linear(x, name + '_k_proj', kv * d),
        value=_linear(x, name + '_v_proj', kv * d),
        gate=_linear(x, name + '_gate_proj', heads * d),
        q_norm_gamma=_ones(name + '_attn_q_norm_gamma'),
        k_norm_gamma=_ones(name + '_attn_k_norm_gamma'),
        num_heads=heads, num_kv_heads=kv, head_dim=d, separate_gate=True,
        zero_centered=False, rotary_dim=d if local else 0,
        window=c['sliding_window'] if local else None,
        rope_theta=c['rope_theta'], eps=c['rms_norm_eps'],
        seq_len=c['seq_len'], name=name + '_attn')
    return _linear(o, name + '_o_proj', c['hidden_size'])


def expert_layer(x, name, c):
    routed = sym.SparseMoE(
        x, counts=_zeros(name + '_moe_counts'),
        selection_bias=_zeros(name + '_moe_selection_bias'),
        num_experts=c['num_experts'],
        num_experts_held=c['num_experts_held'],
        expert_offset=c['expert_offset'], top_k=c['num_experts_per_tok'],
        normalize=c['route_norm'], scoring_func=c['score_func'],
        topk_method='noaux_tc', routed_scaling_factor=c['route_scale'],
        bias_update_rate=c['bias_update_rate'],
        intermediate_size=c['moe_intermediate_size'], name=name + '_moe')
    if not c['num_shared_experts']:
        return routed
    return routed + _gated_mlp(
        x, name + '_shared', c['num_shared_experts'] *
        c['moe_intermediate_size'], c['hidden_size'])


def get_symbol(num_classes=200192, seq_len=8192, dtype='float32',
               hidden_size=2048, num_hidden_layers=32, layer_types=None,
               global_attn_every_n_layers=4, sliding_window=2048,
               num_dense_layers=2, intermediate_size=6144,
               num_attention_heads=32, num_key_value_heads=4, head_dim=128,
               rope_theta=10000.0, num_experts=128, num_experts_held=None,
               expert_offset=0, num_shared_experts=1, num_experts_per_tok=8,
               moe_intermediate_size=1024, route_norm=True,
               route_scale=2.826, score_func='sigmoid', n_group=1,
               topk_group=1, bias_update_rate=0.0, mup_enabled=True,
               rms_norm_eps=1e-5, **kwargs):
    """num_classes: the rows of the vocabulary held here (embedding and
    head, untied).  dtype: the compute type; norm scales and the
    selection bias stay float32.  layer_types: one of LAYER_TYPES a
    layer; left out, the published rule on global_attn_every_n_layers.
    bias_update_rate: the step of the selection bias's rule (0: the
    bias stays as loaded)."""
    c = dict(locals())
    c.pop('kwargs')
    if layer_types is None:
        layer_types = layer_types_of(num_hidden_layers,
                                     global_attn_every_n_layers)
    if len(layer_types) != num_hidden_layers or \
            set(layer_types) - set(LAYER_TYPES):
        raise MXNetError('afmoe: layer_types %r for %d layers (each one of '
                         '%s)' % (layer_types, num_hidden_layers,
                                  ', '.join(LAYER_TYPES)))
    if n_group != 1 or topk_group != 1:
        raise MXNetError('afmoe: built for one group of experts '
                         '(n_group = topk_group = 1)')
    if num_experts_held is None:
        c['num_experts_held'] = num_experts
    data = sym.Variable('data')
    h = sym.Embedding(data, input_dim=num_classes, output_dim=hidden_size,
                      dtype=dtype, name='embed')
    if mup_enabled:
        h = h * math.sqrt(hidden_size)
    for layer, kind in enumerate(layer_types):
        name = 'l%d' % layer
        with AttrScope(__force_mirroring__='True'):
            mixed = _norm(gated_attention(
                _norm(h, name + '_input_norm', rms_norm_eps), name, kind, c),
                name + '_post_attn_norm', rms_norm_eps)
        h = h + mixed
        with AttrScope(__force_mirroring__='True'):
            n = _norm(h, name + '_pre_mlp_norm', rms_norm_eps)
            ffn = _gated_mlp(n, name + '_mlp', intermediate_size,
                             hidden_size) \
                if is_dense_layer(layer, num_dense_layers) \
                else expert_layer(n, name, c)
            ffn = _norm(ffn, name + '_post_mlp_norm', rms_norm_eps)
        h = h + ffn
    # the logits stay in the compute type, as in qwen3_next
    logits = _linear(_norm(h, 'final_norm', rms_norm_eps), 'lm_head',
                     num_classes)
    return sym.SoftmaxOutput(logits, name='softmax')
