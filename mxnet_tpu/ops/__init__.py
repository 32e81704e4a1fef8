"""Operator registry and implementations (see registry.py).

Importing this package registers all operators, mirroring the reference's
static registration of NNVM ops at library load
(src/operator/*.cc NNVM_REGISTER_OP sites, SURVEY.md §2.3).
"""
from . import registry
from . import tensor
from . import nn
from . import random_ops
from . import spatial
from . import extra
from . import rnn_op
from . import contrib_ops
from . import optimizer_ops
from . import lm

from .registry import get, exists, list_ops, register, OpDef, OpContext

# Same-shape ops outside the tensor.py wrapper families: mark them for
# bidirectional shape unification (nnvm ElemwiseShape semantics) so
# unknown dims (0 / None) propagate backward through them.  Only ops
# whose EVERY input shares the output shape qualify (LeakyReLU doesn't:
# prelu mode adds a per-channel gamma input).
for _same_name in ('Activation', 'Dropout', 'Cast',
                   'BlockGrad', 'SoftmaxActivation', 'softmax',
                   'log_softmax', 'identity', '_copy', 'relu',
                   'sigmoid', 'make_loss', 'negative'):
    if exists(_same_name):
        get(_same_name).shape_rule = 'same'
del _same_name
