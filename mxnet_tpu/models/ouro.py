"""Ouro's looped decoder (`model_type: ouro`; ByteDance's Ouro-2.6B is
one): a stack of decoder layers that every token runs `total_ut_steps`
times with the same weights, the final RMS norm after each pass and its
output the next pass's input.  A layer is rotary multi-head attention
(no biases, no q/k norms) and a gated feed-forward, each between two
plain RMS norms (sandwich).  At `early_exit_threshold` 1 every pass
runs and the logits are the last pass's.

After the published code (modeling_ouro.py beside the model's
config.json), whose keys the arguments keep.  Tokens are rows: the data
is (N,) ids with N = sequences x seq_len, the label the next ids.  The
stack is one operator, LoopedDecoder: the program holds its layers once
(a lax.scan over the passes) and recomputes each half layer in the
backward pass, as `__force_mirroring__` does for the other models.  The
exit gate is not built: at threshold 1 it changes nothing forward.
"""
from .. import initializer
from .. import symbol as sym
from ..base import MXNetError
from ..ops.lm import _LAYER_INPUTS
from .qwen3_next import _linear


def _ones(name):
    """A plain norm's scale: a leaf that starts at 1."""
    return sym.Variable(name, init=initializer.One())


def get_symbol(num_classes=49152, seq_len=8192, dtype='float32',
               hidden_size=2048, num_hidden_layers=48,
               num_attention_heads=16, num_key_value_heads=16, head_dim=128,
               intermediate_size=5632, rope_theta=1000000.0,
               rms_norm_eps=1e-6, total_ut_steps=4, early_exit_threshold=1.0,
               tie_word_embeddings=False, **kwargs):
    """num_classes: the vocabulary (embedding and head, untied).  dtype:
    the compute type; norm scales stay float32."""
    if early_exit_threshold != 1 or tie_word_embeddings:
        raise MXNetError('ouro: built for early_exit_threshold 1 (every '
                         'pass runs) and an untied head')
    data = sym.Variable('data')
    h = sym.Embedding(data, input_dim=num_classes, output_dim=hidden_size,
                      dtype=dtype, name='embed')
    weights = {}
    for layer in range(num_hidden_layers):
        for name in _LAYER_INPUTS:
            key = 'l%d_%s' % (layer, name)
            weights[key] = _ones(key) if name.endswith('_gamma') \
                else sym.Variable(key)
    h = sym.LoopedDecoder(
        h, final_norm_gamma=_ones('final_norm_gamma'),
        num_layers=num_hidden_layers, num_loops=total_ut_steps,
        num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
        head_dim=head_dim, intermediate_size=intermediate_size,
        rope_theta=rope_theta, eps=rms_norm_eps, seq_len=seq_len,
        name='decoder', **weights)
    # the logits stay in the compute type, as in qwen3_next
    logits = _linear(h, 'lm_head', num_classes)
    return sym.SoftmaxOutput(logits, name='softmax')
