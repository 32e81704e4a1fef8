"""Auto-generated per-op conformance sweep (VERDICT.md item 3).

Model: reference tests/python/unittest/test_operator.py — there every
operator gets numeric-gradient-checked against finite differences and
cross-checked across dtypes (test_utils.py:439 check_numeric_gradient,
:784 check_consistency).  Here ONE parametrized test walks the whole op
registry; every primary op must either have a case in CASES or an entry
in SKIP with a reason — test_registry_fully_covered enforces it, so a
newly registered op fails CI until it's covered.

Each case runs up to three checks on tiny shapes:
  * forward: symbolic forward executes, outputs finite (unless the op
    intentionally emits non-finite values);
  * grad: symbolic backward vs central finite differences
    (check_numeric_gradient), for ops marked differentiable;
  * dtype: float32 vs bfloat16 forward consistency (the reference's
    check_consistency across dtypes), loose tolerance.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, ops
from mxnet_tpu.test_utils import check_numeric_gradient


class Case:
    def __init__(self, shapes, attrs=None, low=-1.0, high=1.0,
                 grad=True, dtype=True, finite=True, grad_nodes=None,
                 int_inputs=(), values=None, rtol=1e-2, atol=1e-3,
                 wrap=None, eps=1e-3):
        self.shapes = shapes          # list aligned with op arg names
        self.attrs = attrs or {}
        self.low, self.high = low, high
        self.grad = grad
        self.dtype = dtype
        self.finite = finite
        self.grad_nodes = grad_nodes  # None -> all float inputs
        self.int_inputs = int_inputs  # indices drawn as integers
        self.values = values          # explicit input arrays
        self.rtol, self.atol = rtol, atol
        self.eps = eps                # FD step (bigger when the loss
        #   magnitude makes 1e-3 steps vanish in f32 resolution)
        self.wrap = wrap              # 'square': check grads of out**2
        #   (for ops whose plain output-sum is constant by construction,
        #   e.g. BatchNorm: sum((x-mean)/std) == 0)


def u(low, high, shapes=((2, 3),), grad=True, **kw):
    return Case(list(shapes), low=low, high=high, grad=grad, **kw)


_S = [(2, 3)]          # default elementwise shape
_B = [(2, 3), (2, 3)]  # binary same-shape

CASES = {
    # -- elementwise unary: (domain, differentiable) ---------------------
    'abs': u(0.2, 1.0), 'negative': u(-1, 1), 'reciprocal': u(0.5, 2.0),
    'square': u(-1, 1), 'sqrt': u(0.3, 2.0), 'rsqrt': u(0.3, 2.0),
    'cbrt': u(0.3, 2.0), 'rcbrt': u(0.3, 2.0),
    'exp': u(-1, 1), 'expm1': u(-1, 1),
    'log': u(0.5, 2.0), 'log10': u(0.5, 2.0), 'log2': u(0.5, 2.0),
    'log1p': u(-0.5, 1.0),
    'sin': u(-1, 1), 'cos': u(-1, 1), 'tan': u(-0.5, 0.5),
    'arcsin': u(-0.8, 0.8), 'arccos': u(-0.8, 0.8), 'arctan': u(-1, 1),
    'sinh': u(-1, 1), 'cosh': u(-1, 1), 'tanh': u(-1, 1),
    'arcsinh': u(-1, 1), 'arccosh': u(1.2, 2.0), 'arctanh': u(-0.8, 0.8),
    'degrees': u(-1, 1), 'radians': u(-90, 90),
    'sigmoid': u(-2, 2), 'relu': u(0.2, 1.0), 'softsign': u(-1, 1),
    'gamma': u(1.2, 3.0), 'gammaln': u(1.2, 3.0),
    'sign': u(0.2, 1.0, grad=False), 'round': u(0.2, 0.4, grad=False),
    'rint': u(0.2, 0.4, grad=False), 'ceil': u(0.2, 0.4, grad=False),
    'floor': u(0.2, 0.4, grad=False), 'trunc': u(0.2, 0.4, grad=False),
    'fix': u(0.2, 0.4, grad=False),
    'zeros_like': u(-1, 1, grad=False), 'ones_like': u(-1, 1, grad=False),
    '_copy': u(-1, 1), 'BlockGrad': u(-1, 1, grad=False),
    'Cast': u(-1, 1, attrs={'dtype': 'float32'}),
    'clip': u(-2, 2, attrs={'a_min': -0.5, 'a_max': 0.5}, grad=False),
    'smooth_l1': u(-2, 2, attrs={'scalar': 1.0}),
    'make_loss': u(-1, 1, grad=False),
    'Flatten': Case([(2, 3, 4)]),
    'Reshape': Case([(2, 6)], attrs={'shape': (3, 4)}),
    'expand_dims': Case(_S, attrs={'axis': 1}),
    'Pad': Case([(2, 2, 3, 3)],
                attrs={'mode': 'constant',
                       'pad_width': (0, 0, 0, 0, 1, 1, 1, 1)}),

    # -- binary / scalar -------------------------------------------------
    'elemwise_add': Case(_B), 'elemwise_sub': Case(_B),
    'elemwise_mul': Case(_B),
    '_grad_add': Case(_B),
    '_identity_with_attr_like_rhs': Case(_B, grad=False),
    '_CrossDeviceCopy': u(-1, 1),
    'elemwise_div': Case(_B, low=0.5, high=2.0),
    '_power': Case(_B, low=0.5, high=2.0),
    '_maximum': Case(_B, grad=False), '_minimum': Case(_B, grad=False),
    '_hypot': Case(_B, low=0.5, high=2.0),
    '_mod': Case(_B, low=0.5, high=2.0, grad=False),
    '_equal': Case(_B, grad=False), '_not_equal': Case(_B, grad=False),
    '_greater': Case(_B, grad=False),
    '_greater_equal': Case(_B, grad=False),
    '_lesser': Case(_B, grad=False),
    '_lesser_equal': Case(_B, grad=False),
    '_plus_scalar': u(-1, 1, attrs={'scalar': 1.5}),
    '_minus_scalar': u(-1, 1, attrs={'scalar': 1.5}),
    '_rminus_scalar': u(-1, 1, attrs={'scalar': 1.5}),
    '_mul_scalar': u(-1, 1, attrs={'scalar': 1.5}),
    '_div_scalar': u(-1, 1, attrs={'scalar': 1.5}),
    '_rdiv_scalar': u(0.5, 2.0, attrs={'scalar': 1.5}),
    '_power_scalar': u(0.5, 2.0, attrs={'scalar': 2.0}),
    '_rpower_scalar': u(0.5, 2.0, attrs={'scalar': 2.0}),
    '_maximum_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_minimum_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_mod_scalar': u(0.5, 2.0, attrs={'scalar': 1.5}, grad=False),
    '_rmod_scalar': u(0.5, 2.0, attrs={'scalar': 1.5}, grad=False),
    '_hypot_scalar': u(0.5, 2.0, attrs={'scalar': 1.5}),
    '_equal_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_not_equal_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_greater_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_greater_equal_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_lesser_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),
    '_lesser_equal_scalar': u(-1, 1, attrs={'scalar': 0.0}, grad=False),

    # -- broadcast binary -------------------------------------------------
    'broadcast_add': Case([(2, 3), (1, 3)]),
    'broadcast_sub': Case([(2, 3), (1, 3)]),
    'broadcast_mul': Case([(2, 3), (1, 3)]),
    'broadcast_div': Case([(2, 3), (1, 3)], low=0.5, high=2.0),
    'broadcast_power': Case([(2, 3), (1, 3)], low=0.5, high=2.0),
    'broadcast_maximum': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_minimum': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_mod': Case([(2, 3), (1, 3)], low=0.5, high=2.0,
                          grad=False),
    'broadcast_hypot': Case([(2, 3), (1, 3)], low=0.5, high=2.0),
    'broadcast_equal': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_not_equal': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_greater': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_greater_equal': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_lesser': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_lesser_equal': Case([(2, 3), (1, 3)], grad=False),
    'broadcast_plus': Case([(2, 3), (1, 3)]),
    'broadcast_minus': Case([(2, 3), (1, 3)]),
    'broadcast_to': Case([(1, 3)], attrs={'shape': (2, 3)}),
    'broadcast_axis': Case([(1, 3)], attrs={'axis': 0, 'size': 2}),

    # -- reductions --------------------------------------------------------
    'sum': Case(_S, attrs={'axis': 1}),
    'mean': Case(_S, attrs={'axis': 1}),
    'prod': Case(_S, attrs={'axis': 1}, low=0.5, high=1.5),
    'nansum': Case(_S, attrs={'axis': 1}),
    'nanprod': Case(_S, attrs={'axis': 1}, low=0.5, high=1.5),
    'max': Case(_S, attrs={'axis': 1}, grad=False),
    'min': Case(_S, attrs={'axis': 1}, grad=False),
    'norm': Case(_S, low=0.5, high=1.0),
    'argmax': Case(_S, grad=False, attrs={'axis': 1}, dtype=False),
    'argmin': Case(_S, grad=False, attrs={'axis': 1}, dtype=False),
    'argmax_channel': Case(_S, grad=False, dtype=False),

    # -- matrix / shape ----------------------------------------------------
    'dot': Case([(2, 3), (3, 2)]),
    'linalg_gemm': Case([(2, 3), (3, 2), (2, 2)]),
    'linalg_gemm2': Case([(2, 3), (3, 2)]),
    'linalg_potrf': Case([(3, 3)], values=[
        (lambda a: (a @ a.T + 3 * np.eye(3)).astype(np.float32))(
            np.random.RandomState(7).rand(3, 3))], grad=False,
        dtype=False),
    'linalg_potri': Case([(3, 3)], values=[
        np.linalg.cholesky((lambda a: a @ a.T + 3 * np.eye(3))(
            np.random.RandomState(7).rand(3, 3))).astype(np.float32)],
        grad=False, dtype=False),
    'linalg_sumlogdiag': Case([(3, 3)], low=0.5, high=2.0, grad=False),
    'linalg_syrk': Case([(2, 3)]),
    'linalg_trmm': Case([(3, 3), (3, 3)], values=[
        np.tril(np.random.RandomState(8).rand(3, 3) + 1).astype(
            np.float32), None], grad=False, dtype=False),
    'linalg_trsm': Case([(3, 3), (3, 3)], values=[
        np.tril(np.random.RandomState(8).rand(3, 3) + 1).astype(
            np.float32), None], grad=False, dtype=False),
    'batch_dot': Case([(2, 2, 3), (2, 3, 2)]),
    'transpose': Case(_S),
    'SwapAxis': Case([(2, 3, 4)], attrs={'dim1': 0, 'dim2': 2}),
    'slice': Case([(4, 4)], attrs={'begin': (1, 0), 'end': (3, 2)}),
    'slice_axis': Case([(4, 4)],
                       attrs={'axis': 1, 'begin': 1, 'end': 3}),
    'SliceChannel': Case([(2, 4)],
                         attrs={'num_outputs': 2, 'axis': 1}),
    'Concat': Case([(2, 2), (2, 3)],
                   attrs={'num_args': 2, 'dim': 1}),
    'stack': Case([(2, 3), (2, 3)], attrs={'num_args': 2, 'axis': 0}),
    'add_n': Case([(2, 3), (2, 3)], attrs={'num_args': 2}),
    'repeat': Case(_S, attrs={'repeats': 2, 'axis': 1}),
    'tile': Case(_S, attrs={'reps': (2, 1)}),
    'reverse': Case(_S, attrs={'axis': 1}),
    'flip': Case(_S, attrs={'axis': 1}),
    'depth_to_space': Case([(1, 4, 2, 2)], attrs={'block_size': 2}),
    'space_to_depth': Case([(1, 1, 4, 4)], attrs={'block_size': 2}),
    'Crop': Case([(1, 1, 4, 4)], attrs={'h_w': (2, 2), 'num_args': 1},
                 grad=False),
    '_eye': Case([], attrs={'N': 3}, grad=False, dtype=False),
    '_zeros': Case([], attrs={'shape': (2, 3)}, grad=False, dtype=False),
    '_ones': Case([], attrs={'shape': (2, 3)}, grad=False, dtype=False),
    '_full': Case([], attrs={'shape': (2, 3), 'value': 2.5}, grad=False,
                  dtype=False),
    '_arange': Case([], attrs={'start': 0, 'stop': 6}, grad=False,
                    dtype=False),
    'where': Case([(2, 3), (2, 3), (2, 3)], grad=False),

    # -- ordering ----------------------------------------------------------
    'sort': Case(_S, grad=False, dtype=False),
    'argsort': Case(_S, grad=False, dtype=False),
    'topk': Case(_S, attrs={'k': 2}, grad=False, dtype=False),
    'pick': Case([(3, 4), (3,)], grad_nodes=['arg0'], grad=False,
                 int_inputs=(1,)),

    # -- indexing ----------------------------------------------------------
    'take': Case([(4, 3), (2,)], grad=False, int_inputs=(1,)),
    'batch_take': Case([(3, 4), (3,)], grad=False, int_inputs=(1,)),
    'one_hot': Case([(4,)], attrs={'depth': 3}, grad=False,
                    int_inputs=(0,)),
    'Embedding': Case([(4,), (5, 3)],
                      attrs={'input_dim': 5, 'output_dim': 3},
                      grad=False, int_inputs=(0,)),
    'gather_nd': Case([(4, 3), (2, 2)], grad=False, int_inputs=(1,)),
    'scatter_nd': Case([(2,), (2, 2)],
                       attrs={'shape': (4, 3)}, grad=False,
                       int_inputs=(1,)),
    # accumulating variant (duplicate-index ADD semantics pinned by
    # tests/test_sparse_embed.py)
    '_backward_gather_nd': Case([(2,), (2, 2)],
                                attrs={'shape': (4, 3)}, grad=False,
                                int_inputs=(1,)),

    # -- neural network ----------------------------------------------------
    'FullyConnected': Case([(2, 3), (4, 3), (4,)],
                           attrs={'num_hidden': 4}),
    'Convolution': Case([(1, 2, 5, 5), (3, 2, 3, 3), (3,)],
                        attrs={'kernel': (3, 3), 'num_filter': 3,
                               'pad': (1, 1)}, rtol=2e-2),
    'Deconvolution': Case([(1, 2, 4, 4), (2, 3, 2, 2), (3,)],
                          attrs={'kernel': (2, 2), 'num_filter': 3,
                                 'stride': (2, 2)}, rtol=2e-2),
    'Pooling': Case([(1, 2, 4, 4)],
                    attrs={'kernel': (2, 2), 'pool_type': 'avg',
                           'stride': (2, 2)}),
    'Activation': Case(_S, attrs={'act_type': 'tanh'}),
    'LeakyReLU': Case(_S, attrs={'act_type': 'leaky', 'slope': 0.1},
                      low=0.2, high=1.0),
    'SoftmaxActivation': Case(_S),
    'softmax': Case(_S), 'log_softmax': Case(_S),
    'Dropout': Case(_S, attrs={'p': 0.5}, grad=False),
    'BatchNorm': Case([(2, 3, 4, 4), (3,), (3,)],
                      attrs={'fix_gamma': False}, low=0.5, high=1.5,
                      grad_nodes=['data'], rtol=5e-2, atol=5e-3,
                      wrap='square', eps=1e-2),
    'InstanceNorm': Case([(2, 3, 4), (3,), (3,)], low=0.5, high=1.5,
                         grad_nodes=['data'], rtol=5e-2, atol=5e-3,
                         wrap='square', eps=1e-2),
    'L2Normalization': Case([(2, 6)], low=0.5, high=1.5),
    'RMSNorm': Case([(3, 8), (8,)], attrs={'eps': 1e-6,
                                           'zero_centered': True},
                    low=0.5, high=1.5),
    'CausalConv1D': Case([(12, 5), (5, 4)],
                         attrs={'kernel': 4, 'seq_len': 6}),
    'GatedAttention': Case([(12, 32), (12, 8), (12, 8), (8,), (8,)],
                           attrs={'num_heads': 2, 'num_kv_heads': 1,
                                  'head_dim': 8, 'rotary_dim': 4,
                                  'seq_len': 6},
                           rtol=5e-2, atol=5e-3, wrap='square', eps=1e-2),
    'LatentAttention': Case([(12, 24), (12, 28), (12, 4)],
                            attrs={'num_heads': 2, 'qk_nope_head_dim': 8,
                                   'qk_rope_head_dim': 4, 'v_head_dim': 6,
                                   'rope_theta': 100.0, 'seq_len': 6},
                            rtol=5e-2, atol=5e-3, wrap='square', eps=1e-2),
    'GatedDeltaRule': Case([(10, 20), (10, 2), (10, 2), (2,), (2,)],
                           attrs={'num_k_heads': 1, 'num_v_heads': 2,
                                  'head_k_dim': 4, 'head_v_dim': 6,
                                  'seq_len': 5, 'chunk_size': 4},
                           rtol=5e-2, atol=5e-3, wrap='square', eps=1e-2),
    'LoopedDecoder': Case([(12, 8), (8,), (8, 8), (8, 8), (8, 8), (8, 8),
                           (8,), (8,), (6, 8), (6, 8), (8, 6), (8,), (8,)],
                          attrs={'num_layers': 1, 'num_loops': 2,
                                 'num_heads': 2, 'num_kv_heads': 2,
                                 'head_dim': 4, 'intermediate_size': 6,
                                 'rope_theta': 100.0, 'seq_len': 6},
                          low=0.5, high=1.5, rtol=5e-2, atol=5e-3,
                          wrap='square', eps=1e-2),
    'LRN': Case([(1, 4, 3, 3)], attrs={'nsize': 3}, low=0.5, high=1.5),
    'LSoftmax': Case([(3, 4), (5, 4), (3,)],
                     attrs={'num_hidden': 5, 'margin': 2},
                     grad=False, int_inputs=(2,)),
    'UpSampling': Case([(1, 2, 3, 3)],
                       attrs={'scale': 2, 'sample_type': 'nearest',
                              'num_args': 1}),
    'GridGenerator': Case([(1, 6)],
                          attrs={'transform_type': 'affine',
                                 'target_shape': (4, 4)}, grad=False),
    'BilinearSampler': Case([(1, 1, 4, 4), (1, 2, 3, 3)],
                            low=-0.8, high=0.8, grad=False),
    'SpatialTransformer': Case(
        [(1, 1, 4, 4), (1, 6)],
        attrs={'transform_type': 'affine', 'sampler_type': 'bilinear',
               'target_shape': (4, 4)}, low=-0.5, high=0.5, grad=False),
    'ROIPooling': Case([(1, 2, 6, 6), (1, 5)],
                       attrs={'pooled_size': (2, 2),
                              'spatial_scale': 1.0},
                       values=[None,
                               np.array([[0, 0, 0, 4, 4]], np.float32)],
                       grad=False),
    'Correlation': Case([(1, 2, 4, 4), (1, 2, 4, 4)],
                        attrs={'kernel_size': 1, 'max_displacement': 1,
                               'pad_size': 1}, grad=False),
    'Correlation1D': Case([(1, 2, 4, 6), (1, 2, 4, 6)],
                          attrs={'kernel_size': 1,
                                 'max_displacement': 1, 'pad_size': 1},
                          grad=False),
    'SequenceLast': Case([(3, 2, 4)], grad=False),
    'SequenceMask': Case([(3, 2, 4)], grad=False),
    'SequenceReverse': Case([(3, 2, 4)], grad=False),
    'IdentityAttachKLSparseReg': Case(_S, low=0.1, high=0.9,
                                      grad=False),

    # -- losses (head-grad-ignoring custom VJPs: fwd + finite bwd) --------
    'SoftmaxOutput': Case([(3, 4), (3,)], grad=False, int_inputs=(1,)),
    'LinearRegressionOutput': Case([(3, 2), (3, 2)], grad=False),
    'LogisticRegressionOutput': Case([(3, 2), (3, 2)], grad=False),
    'MAERegressionOutput': Case([(3, 2), (3, 2)], grad=False),
    'SVMOutput': Case([(3, 4), (3,)], grad=False, int_inputs=(1,)),
    'MultiLogistic': Case([(3, 2), (3, 2)], grad=False),
    'WeightedL1': Case([(3, 2), (3, 2)], grad=False),
    'softmax_cross_entropy': Case([(3, 4), (3,)], grad=False,
                                  int_inputs=(1,)),

    # -- random (shape/finiteness only) -----------------------------------
    '_random_uniform': Case([], attrs={'shape': (2, 3)}, grad=False,
                            dtype=False),
    '_random_normal': Case([], attrs={'shape': (2, 3)}, grad=False,
                           dtype=False),
    '_random_exponential': Case([], attrs={'shape': (2, 3)},
                                grad=False, dtype=False),
    '_random_gamma': Case([], attrs={'shape': (2, 3), 'alpha': 2.0},
                          grad=False, dtype=False),
    '_random_poisson': Case([], attrs={'shape': (2, 3), 'lam': 3.0},
                            grad=False, dtype=False),
    '_random_negative_binomial': Case(
        [], attrs={'shape': (2, 3), 'k': 2, 'p': 0.5}, grad=False,
        dtype=False),
    '_random_generalized_negative_binomial': Case(
        [], attrs={'shape': (2, 3), 'mu': 2.0, 'alpha': 0.5},
        grad=False, dtype=False),
    'sample_uniform': Case([(2,), (2,)], values=[
        np.zeros(2, np.float32), np.ones(2, np.float32)],
        grad=False, dtype=False),
    'sample_normal': Case([(2,), (2,)], values=[
        np.zeros(2, np.float32), np.ones(2, np.float32)],
        grad=False, dtype=False),
    'sample_gamma': Case([(2,), (2,)], values=[
        np.full(2, 2.0, np.float32), np.ones(2, np.float32)],
        grad=False, dtype=False),
    'sample_exponential': Case([(2,)], values=[
        np.ones(2, np.float32)], grad=False, dtype=False),
    'sample_poisson': Case([(2,)], values=[
        np.full(2, 3.0, np.float32)], grad=False, dtype=False),
    'sample_negative_binomial': Case([(2,), (2,)], values=[
        np.full(2, 2.0, np.float32), np.full(2, 0.5, np.float32)],
        grad=False, dtype=False),
    'sample_generalized_negative_binomial': Case([(2,), (2,)], values=[
        np.full(2, 2.0, np.float32), np.full(2, 0.5, np.float32)],
        grad=False, dtype=False),
    '_sample_multinomial': Case([(2, 4)], low=0.1, high=0.9,
                                grad=False, dtype=False),

    # -- contrib -----------------------------------------------------------
    'fft': Case([(2, 4)], grad=False),
    'ifft': Case([(2, 8)], grad=False),
    'count_sketch': Case([(2, 4), (4,), (4,)],
                         attrs={'out_dim': 3},
                         values=[None,
                                 np.array([1, -1, 1, -1], np.float32),
                                 np.array([0, 1, 2, 0], np.float32)],
                         grad=False),
    'quantize': Case([(2, 3), (1,), (1,)],
                     values=[None, np.array([-1.0], np.float32),
                             np.array([1.0], np.float32)],
                     grad=False, dtype=False),
    'dequantize': Case([(2, 3), (1,), (1,)],
                       values=[np.random.RandomState(0).randint(
                           0, 255, (2, 3)).astype(np.uint8),
                           np.array([-1.0], np.float32),
                           np.array([1.0], np.float32)],
                       grad=False, dtype=False),
    'ctc_loss': Case([(4, 2, 5), (2, 3)],
                     values=[None,
                             np.array([[1, 2, 0], [2, 3, 1]],
                                      np.float32)],
                     grad=False),
    'MultiBoxPrior': Case([(1, 2, 4, 4)],
                          attrs={'sizes': (0.5,), 'ratios': (1.0,)},
                          grad=False),
    'MultiBoxDetection': Case(
        [(1, 4, 2), (1, 8), (1, 2, 4)],
        values=[np.array([[[0.6, 0.4], [0.3, 0.7]]], np.float32)
                .transpose(0, 2, 1),
                np.zeros((1, 8), np.float32),
                np.array([[[0.1, 0.1, 0.4, 0.4],
                           [0.5, 0.5, 0.9, 0.9]]], np.float32)],
        grad=False),
    'MultiBoxTarget': Case(
        [(1, 2, 4), (1, 1, 5), (1, 2, 2)],
        values=[np.array([[[0.1, 0.1, 0.4, 0.4],
                           [0.5, 0.5, 0.9, 0.9]]], np.float32),
                np.array([[[0, 0.1, 0.1, 0.4, 0.4]]], np.float32),
                np.zeros((1, 2, 2), np.float32)],
        grad=False),
    'Proposal': Case(
        [(1, 2, 4, 4), (1, 4, 4, 4), (1, 3)],
        values=[None, None, np.array([[16.0, 16.0, 1.0]], np.float32)],
        attrs={'feature_stride': 4, 'scales': (4.0,), 'ratios': (1.0,),
               'rpn_pre_nms_top_n': 8, 'rpn_post_nms_top_n': 4,
               'rpn_min_size': 1},
        grad=False, dtype=False),
    'PSROIPooling': Case(
        [(1, 8, 4, 4), (1, 5)],
        attrs={'output_dim': 2, 'pooled_size': 2, 'spatial_scale': 1.0},
        values=[None, np.array([[0, 0, 0, 3, 3]], np.float32)],
        grad=False),
    'DeformableConvolution': Case(
        [(1, 2, 5, 5), (1, 18, 5, 5), (3, 2, 3, 3), (3,)],
        attrs={'kernel': (3, 3), 'num_filter': 3, 'pad': (1, 1),
               'num_deformable_group': 1},
        grad=False),
    'DeformablePSROIPooling': Case(
        [(1, 8, 4, 4), (1, 5), (1, 2, 2, 2)],
        attrs={'output_dim': 2, 'pooled_size': 2, 'group_size': 2,
               'spatial_scale': 1.0, 'trans_std': 0.1, 'no_trans': False,
               'part_size': 2, 'sample_per_part': 1},
        values=[None, np.array([[0, 0, 0, 3, 3]], np.float32), None],
        grad=False),
}

SKIP = {
    # exercised end-to-end by dedicated tests
    'RNN': 'scan-fused RNN covered by tests/test_rnn.py',
    'Custom': 'host-callback bridge covered by tests/test_autograd.py',
    '_Native': 'legacy bridge covered by tests/test_missing_ops.py',
    '_NDArray': 'legacy bridge covered by tests/test_missing_ops.py',
    'sgd_update': 'covered by tests/test_missing_ops.py',
    'sgd_mom_update': 'covered by tests/test_missing_ops.py',
    'mp_sgd_update': 'covered by tests/test_missing_ops.py',
    'mp_sgd_mom_update': 'covered by tests/test_missing_ops.py',
    'sparse_sgd_update': 'rows-only COO update parity covered by '
                         'tests/test_sparse_embed.py',
    'sparse_sgd_mom_update': 'rows-only lazy-momentum parity covered '
                             'by tests/test_sparse_embed.py',
    'adam_update': 'covered by tests/test_missing_ops.py',
    'rmsprop_update': 'covered by tests/test_missing_ops.py',
    'rmspropalex_update': 'covered by tests/test_missing_ops.py',
    '_slice_assign': 'covered by tests/test_missing_ops.py',
    '_crop_assign_scalar': 'covered by tests/test_missing_ops.py',
    'MultiProposal': 'batch variant of Proposal (same kernel), '
                     'covered by tests/test_contrib.py',
    'SparseMoE': 'top-k routing is piecewise (a finite difference can '
                 'cross a choice) and the counts are aux state: values, '
                 'gradients, shares and counters against the plain '
                 'references in tests/test_qwen3_next.py (softmax) and '
                 'tests/test_deepseek_v3.py (sigmoid, selection bias)',
    '_NoGradient': 'zero-input placeholder node (reference '
                   'init_op.cc); nothing to gradient-check',
}


def test_reference_registry_parity():
    """Every registration name in the reference (314 NNVM_REGISTER_OP +
    MXNET_REGISTER_OP_PROPERTY sites, vendored in
    tests/data_reference_op_names.txt) is either a registered op here
    or carries an explicit N/A reason in ops.registry.REFERENCE_NA —
    the mechanical op diff vs the reference is empty-or-annotated."""
    from mxnet_tpu.ops import registry as reg
    path = os.path.join(os.path.dirname(__file__),
                        'data_reference_op_names.txt')
    names = [ln.strip() for ln in open(path) if ln.strip()]
    assert len(names) > 300
    unaccounted = [n for n in names
                   if not reg.exists(n)
                   and reg.reference_na_reason(n) is None]
    assert not unaccounted, (
        'reference registration names neither registered nor '
        'N/A-annotated: %s' % unaccounted)


def _primary_ops():
    return sorted(n for n in ops.list_ops()
                  if ops.get(n).name == n)


def test_registry_fully_covered():
    """Every primary op has a conformance case or an explicit skip."""
    missing = [n for n in _primary_ops()
               if n not in CASES and n not in SKIP]
    assert not missing, ('ops with neither a conformance case nor a '
                         'skip reason: %s' % missing)


def _build(op_name, case, dtype=np.float32):
    op = ops.get(op_name)
    attrs = dict(case.attrs)
    arg_names = op.arg_names(attrs)
    n_in = len(case.shapes)
    rng = np.random.RandomState(42)
    variables = []
    location = {}
    for i in range(n_in):
        name = arg_names[i] if i < len(arg_names) else 'arg%d' % i
        name = 'arg%d_%s' % (i, name)
        variables.append(sym.Variable(name))
        if case.values is not None and case.values[i] is not None:
            arr = np.asarray(case.values[i])
        elif i in case.int_inputs:
            arr = rng.randint(0, 3, case.shapes[i]).astype(np.float32)
        else:
            arr = rng.uniform(case.low, case.high,
                              case.shapes[i]).astype(dtype)
        location[name] = arr
    fn = getattr(sym, op_name)
    net = fn(*variables, **attrs)
    if case.wrap == 'square':
        net = sym.square(net if len(net.list_outputs()) == 1 else net[0])
    return net, location


@pytest.mark.parametrize('op_name', sorted(CASES))
def test_op_conformance(op_name):
    case = CASES[op_name]
    net, location = _build(op_name, case)
    shapes = {k: v.shape for k, v in location.items()}
    ex = net.simple_bind(mx.cpu(), grad_req='null', **shapes)
    ex.forward(is_train=False, **location)
    outs = [o.asnumpy() for o in ex.outputs]
    if case.finite:
        for o in outs:
            assert np.isfinite(o).all(), '%s: non-finite forward' % op_name

    if case.grad:
        grad_nodes = case.grad_nodes
        if grad_nodes is None:
            grad_nodes = [k for i, k in enumerate(location)
                          if i not in case.int_inputs]
        else:
            grad_nodes = [k for k in location
                          if any(k.endswith('_' + g) or k == g
                                 for g in grad_nodes)]
        check_numeric_gradient(net, location, numeric_eps=case.eps,
                               rtol=case.rtol, atol=case.atol or 1e-3,
                               grad_nodes=grad_nodes)

    if case.dtype:
        # bfloat16 forward consistency vs float32 (reference
        # check_consistency across dtype list, test_utils.py:784)
        import jax.numpy as jnp
        loc16 = {k: v for k, v in location.items()}
        ex16 = net.simple_bind(mx.cpu(), grad_req='null',
                               type_dict={k: jnp.bfloat16
                                          for i, k in
                                          enumerate(location)
                                          if i not in case.int_inputs},
                               **shapes)
        ex16.forward(is_train=False, **loc16)
        for o32, o16 in zip(outs, ex16.outputs):
            got = np.asarray(o16.asnumpy(), np.float32)
            if not np.issubdtype(np.asarray(o32).dtype, np.floating):
                continue
            np.testing.assert_allclose(
                got, o32, rtol=0.06, atol=0.06,
                err_msg='%s: bf16 vs f32 forward diverged' % op_name)


# ---------------------------------------------------------------------------
# contrib quantize/dequantize: the signed int8 mode's edge semantics
# (reference contrib/quantize-inl.h — symmetric ±max(|min|,|max|) onto
# ±127, round half away from zero, code -128 never produced) and the
# zero-range guard both modes share (PERF round 17 satellite)
# ---------------------------------------------------------------------------

def _run_quantize(data, lo, hi, **attrs):
    d = sym.Variable('data')
    mn = sym.Variable('mn')
    mx_ = sym.Variable('mx')
    net = sym.quantize(d, mn, mx_, **attrs)
    ex = net.simple_bind(mx.cpu(), grad_req='null',
                         data=data.shape, mn=(1,), mx=(1,))
    ex.forward(is_train=False, data=data,
               mn=np.asarray([lo], np.float32),
               mx=np.asarray([hi], np.float32))
    return [o.asnumpy() for o in ex.outputs]


def _run_dequantize(q, lo, hi):
    d = sym.Variable('data')
    mn = sym.Variable('mn')
    mx_ = sym.Variable('mx')
    net = sym.dequantize(d, mn, mx_)
    ex = net.simple_bind(mx.cpu(), grad_req='null',
                         data=q.shape, mn=(1,), mx=(1,),
                         type_dict={'data': q.dtype})
    ex.forward(is_train=False, data=q,
               mn=np.asarray([lo], np.float32),
               mx=np.asarray([hi], np.float32))
    return ex.outputs[0].asnumpy()


def test_quantize_int8_symmetric_edges():
    # exact ±range lands on ±127; the asymmetric min widens nothing
    data = np.array([[2.0, -2.0, 1.0, -1.0, 0.0, 1.999]], np.float32)
    q, mn, mx_ = _run_quantize(data, -1.0, 2.0, out_type='int8')
    assert q.dtype == np.int8
    np.testing.assert_array_equal(
        q[0], [127, -127, 64, -64, 0, 127])   # 1.999*127/2 -> 126.9 + .5
    # symmetric range reported: ∓max(|min|,|max|)
    assert mn[0] == -2.0 and mx_[0] == 2.0
    # beyond-range inputs SATURATE at ±127 (never wrap to -128)
    wild = np.array([[50.0, -50.0]], np.float32)
    q, _, _ = _run_quantize(wild, -1.0, 1.0, out_type='int8')
    np.testing.assert_array_equal(q[0], [127, -127])


def test_quantize_int8_rounding_half_away_from_zero():
    # codes at exactly x.5 round AWAY from zero (reference std::round),
    # not to even: 0.5/127ths -> 1, -0.5/127ths -> -1
    step = 1.0 / 127.0
    data = np.array([[0.5 * step, -0.5 * step, 1.5 * step]], np.float32)
    q, _, _ = _run_quantize(data, -1.0, 1.0, out_type='int8')
    np.testing.assert_array_equal(q[0], [1, -1, 2])


def test_quantize_zero_range_inputs():
    # min == max == 0 (an all-zero tensor's calibrated range): both
    # modes map to code 0 and dequantize back to exact zeros — no
    # division by zero, no NaNs
    zeros = np.zeros((2, 3), np.float32)
    for out_type in ('uint8', 'int8'):
        q, mn, mx_ = _run_quantize(zeros, 0.0, 0.0, out_type=out_type)
        assert np.isfinite(q.astype(np.float32)).all()
        np.testing.assert_array_equal(q, np.zeros((2, 3)))
        back = _run_dequantize(q, float(mn[0]), float(mx_[0]))
        np.testing.assert_array_equal(back, zeros)


def test_quantize_int8_round_trip():
    # quantize -> dequantize round trip error bounded by half a step
    rng = np.random.RandomState(7)
    data = rng.uniform(-3, 3, (4, 5)).astype(np.float32)
    q, mn, mx_ = _run_quantize(data, float(data.min()),
                               float(data.max()), out_type='int8')
    back = _run_dequantize(q, float(mn[0]), float(mx_[0]))
    step = max(abs(data.min()), abs(data.max())) / 127.0
    assert np.abs(back - data).max() <= step / 2 + 1e-7
