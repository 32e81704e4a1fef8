"""Operator registry — the single source of truth for all ops.

TPU-native redesign of the reference's NNVM op registry
(/root/reference include/mxnet/op_attr_types.h:224 FCompute,
src/operator/ registration sites; SURVEY.md §2.3).  Instead of per-op
CUDA kernels dispatched through a dependency engine, every op here is a
pure JAX function over `jax.Array`s.  The registry drives:

  * imperative `nd.<op>` wrappers (codegen like python/mxnet/ndarray.py:2624)
  * symbolic `sym.<op>` node constructors (python/mxnet/symbol.py:2352)
  * shape/type inference (nnvm InferShape/InferType passes)
  * autograd (jax.vjp through the same compute functions; loss ops carry
    custom VJPs reproducing MXNet head-grad-ignoring semantics)

Because compute is pure JAX, the whole graph lowers to one XLA module —
memory planning, kernel fusion and async scheduling are XLA's job
(replacing PlanMemory / ThreadedEngine / mshadow in the reference).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp


class OpContext:
    """Per-invocation execution context: train/test mode, PRNG key, and
    (for shape-carrying init ops like zeros(shape=(0,H))) the
    bidirectionally-inferred output shapes."""
    __slots__ = ('is_train', 'rng', 'out_shapes')

    def __init__(self, is_train=False, rng=None, out_shapes=None):
        self.is_train = is_train
        self.rng = rng
        self.out_shapes = out_shapes


# ---------------------------------------------------------------------------
# Partial shapes — the reference TShape convention: a 0 in a dimension
# means "unknown" (nnvm InferShape unifies these bidirectionally;
# graph_executor.cc:506).  None = completely unknown shape.
# ---------------------------------------------------------------------------

_INFER_KEY = None


def _infer_key():
    """Shared PRNG key for shape-inference eval_shape calls (allocating
    one per call adds a device op to every rng-op inference)."""
    global _INFER_KEY
    if _INFER_KEY is None:
        _INFER_KEY = jax.random.PRNGKey(0)
    return _INFER_KEY


def shape_is_complete(s):
    return s is not None and all(d != 0 for d in s)


def merge_shape(a, b):
    """Unify two partial shapes.  Returns the merged shape, or None if
    they conflict (callers keep their existing value on conflict —
    backward propagation is strictly additive)."""
    if a is None:
        return tuple(b) if b is not None else None
    if b is None:
        return tuple(a)
    if len(a) != len(b):
        return None
    out = []
    for da, db in zip(a, b):
        if da == 0:
            out.append(db)
        elif db == 0 or db == da:
            out.append(da)
        else:
            return None
    return tuple(out)


class OpDef:
    """A registered operator.

    Canonical compute signature:
        fcompute(attrs, inputs, auxs, op_ctx) -> (outputs, new_auxs)
    where inputs/auxs/outputs are lists of jax arrays; attrs is a dict of
    parsed python values.

    infer_shape(attrs, in_shapes) -> completed in_shapes (list, None where
    still unknown).  Forward output-shape inference is generic via
    jax.eval_shape; per-op infer_shape only needs to back-fill parameter
    shapes (the reference's bidirectional InferShape, e.g. FullyConnected
    inferring weight=(num_hidden, D)).
    """

    def __init__(self, name, fcompute, input_names=('data',), num_aux=0,
                 num_outputs=1, output_names=None, infer_shape=None,
                 infer_dtype=None, needs_rng=False, mode_dependent=False,
                 mutable_aux=False, hint=None, shape_rule=None,
                 needs_out_shapes=False, infer_shape_bwd=None,
                 aux_always=False, fold_aux=None):
        self.name = name
        self.fcompute = fcompute
        self._input_names = input_names
        self.num_aux = num_aux
        self._num_outputs = num_outputs
        self._output_names = output_names
        self.infer_shape_fn = infer_shape
        self.infer_dtype_fn = infer_dtype
        self.needs_rng = needs_rng
        self.mode_dependent = mode_dependent
        self.mutable_aux = mutable_aux
        # aux states mutate regardless of train mode (optimizer update
        # ops: momentum/mean/var states advance on every call)
        self.aux_always = aux_always
        # the op's aux states are running counters kept on the device:
        # fold_aux(attrs, deltas) takes what each has grown by since it
        # was last read (numpy) into the profiler's counters; called by
        # profiler.fold_device_counters(), never inside a step
        self.fold_aux = fold_aux
        self.hint = hint or name.lstrip('_').lower()
        # 'same': all (non-aux) inputs and outputs share one shape —
        # enables bidirectional unification (nnvm ElemwiseShape)
        self.shape_rule = shape_rule
        # op-specific backward rule: fn(attrs, in_shapes, out_shapes)
        # -> in_shapes (e.g. FullyConnected: batch dim out->data)
        self.infer_shape_bwd_fn = infer_shape_bwd
        # op's compute wants the inferred output shapes (init ops whose
        # attr shape may contain unknown 0-dims)
        self.needs_out_shapes = needs_out_shapes

    # -- metadata ----------------------------------------------------------
    def input_names(self, attrs):
        names = self._input_names
        if callable(names):
            names = names(attrs)
        return list(names)

    def aux_count(self, attrs):
        """How many trailing inputs are aux states: `num_aux`, or for
        an operator whose aux states depend on its attributes (SparseMoE's
        selection bias) what `num_aux(attrs)` says."""
        n = self.num_aux
        return n(attrs) if callable(n) else n

    def arg_names(self, attrs):
        """Non-aux input names."""
        names = self.input_names(attrs)
        n_aux = self.aux_count(attrs)
        return names[:-n_aux] if n_aux else names

    def aux_names(self, attrs):
        n_aux = self.aux_count(attrs)
        return self.input_names(attrs)[-n_aux:] if n_aux else []

    def num_outputs(self, attrs):
        n = self._num_outputs
        if callable(n):
            n = n(attrs)
        return n

    def output_names(self, attrs):
        if self._output_names is None:
            n = self.num_outputs(attrs)
            if n == 1:
                return ['output']
            return ['output%d' % i for i in range(n)]
        names = self._output_names
        if callable(names):
            names = names(attrs)
        return list(names)

    # -- compute -----------------------------------------------------------
    def apply(self, attrs, inputs, auxs, op_ctx):
        outs, new_auxs = self.fcompute(attrs, list(inputs), list(auxs), op_ctx)
        return list(outs), list(new_auxs)

    # -- inference ---------------------------------------------------------
    def infer_shape(self, attrs, in_shapes, in_dtypes=None,
                    out_shapes=None):
        """Bidirectional per-op shape inference (nnvm InferShape role).

        in_shapes/out_shapes may be None (unknown) or partial (0-dims
        unknown).  Returns (in_shapes, out_shapes) with everything this
        op could deduce filled in; out_shapes is None when the outputs
        cannot be determined yet.  Generic forward inference runs
        jax.eval_shape over the compute function once all inputs are
        complete; shape_rule='same' additionally unifies inputs and
        outputs in both directions."""
        in_shapes = list(in_shapes)
        if self.infer_shape_fn is not None:
            in_shapes = self.infer_shape_fn(attrs, in_shapes)
        if self.infer_shape_bwd_fn is not None and out_shapes and \
                any(s is not None for s in out_shapes):
            in_shapes = self.infer_shape_bwd_fn(attrs, in_shapes,
                                                out_shapes)
        n_arg = len(in_shapes) - self.aux_count(attrs)
        if self.shape_rule == 'same':
            unified = None
            cands = in_shapes[:n_arg] + list(out_shapes or [])
            for s in cands:
                m = merge_shape(unified, s)
                if m is not None:
                    unified = m
            if unified is not None:
                for i in range(n_arg):
                    m = merge_shape(in_shapes[i], unified)
                    if m is not None:
                        in_shapes[i] = m
                if not any(shape_is_complete(s)
                           for s in in_shapes[:n_arg]) or \
                        not all(shape_is_complete(s)
                                for s in in_shapes):
                    # can't run eval_shape yet — report what we know
                    return in_shapes, [unified] * self.num_outputs(attrs)
        if not all(shape_is_complete(s) for s in in_shapes):
            return in_shapes, None
        if in_dtypes is None:
            in_dtypes = [np.float32] * len(in_shapes)
        args = [jax.ShapeDtypeStruct(tuple(s), dt)
                for s, dt in zip(in_shapes[:n_arg], in_dtypes[:n_arg])]
        auxs = [jax.ShapeDtypeStruct(tuple(s), dt)
                for s, dt in zip(in_shapes[n_arg:], in_dtypes[n_arg:])]
        # a real key: jax.random.* type-checks its key argument, and as
        # a closure constant it doesn't affect the abstract evaluation
        ctx = OpContext(is_train=False,
                        rng=_infer_key() if self.needs_rng else None,
                        out_shapes=list(out_shapes) if out_shapes else None)
        outs, _ = jax.eval_shape(
            lambda a, x: self.apply(attrs, x, a, ctx), auxs, args)
        return in_shapes, [tuple(o.shape) for o in outs]

    def infer_dtype(self, attrs, in_dtypes):
        in_dtypes = list(in_dtypes)
        if self.infer_dtype_fn is not None:
            return self.infer_dtype_fn(attrs, in_dtypes)
        known = [d for d in in_dtypes if d is not None]
        d = np.dtype(known[0]) if known else np.dtype(np.float32)
        in_dtypes = [d if x is None else x for x in in_dtypes]
        return in_dtypes, [d] * self.num_outputs(attrs)


_OP_REGISTRY = {}
_OP_ALIASES = {}
# bumped on every register() call (including RE-registration of an
# existing name, which leaves the dict sizes unchanged) — consumers
# caching registry-derived data key on generation(), not on len()
_GENERATION = [0]


def generation():
    """Monotonic registry mutation stamp: changes whenever register()
    runs.  The dict sizes are folded in only as a weak tripwire for
    direct del/pop edits (tests) — a size-compensating direct
    mutation (pop one name, insert another) is NOT detected; mutate
    through register() for the stamp to advance."""
    return (_GENERATION[0] << 20) + len(_OP_REGISTRY) + len(_OP_ALIASES)


def register(name, input_names=('data',), num_aux=0, num_outputs=1,
             output_names=None, infer_shape=None, infer_dtype=None,
             needs_rng=False, mode_dependent=False, mutable_aux=False,
             aliases=(), hint=None, simple=True, shape_rule=None,
             needs_out_shapes=False, infer_shape_bwd=None,
             aux_always=False, fold_aux=None):
    """Decorator registering an op.

    With simple=True (default) the decorated function has signature
    `fn(attrs, *inputs) -> out | tuple(outs)` and is adapted to the
    canonical form.  With simple=False the function must use the canonical
    signature `fn(attrs, inputs, auxs, op_ctx) -> (outs, new_auxs)`.
    """
    def do_register(fn):
        if simple:
            inner = fn

            @functools.wraps(fn)
            def fcompute(attrs, inputs, auxs, op_ctx):
                out = inner(attrs, *inputs)
                if not isinstance(out, (tuple, list)):
                    out = (out,)
                return list(out), []
        else:
            fcompute = fn
        op = OpDef(name, fcompute, input_names=input_names, num_aux=num_aux,
                   num_outputs=num_outputs, output_names=output_names,
                   infer_shape=infer_shape, infer_dtype=infer_dtype,
                   needs_rng=needs_rng, mode_dependent=mode_dependent,
                   mutable_aux=mutable_aux, hint=hint,
                   shape_rule=shape_rule,
                   needs_out_shapes=needs_out_shapes,
                   infer_shape_bwd=infer_shape_bwd, aux_always=aux_always,
                   fold_aux=fold_aux)
        _OP_REGISTRY[name] = op
        for alias in aliases:
            _OP_ALIASES[alias] = name
        _GENERATION[0] += 1
        fn.op = op
        return fn
    return do_register


def get(name):
    if name in _OP_REGISTRY:
        return _OP_REGISTRY[name]
    if name in _OP_ALIASES:
        return _OP_REGISTRY[_OP_ALIASES[name]]
    raise KeyError('Operator %s is not registered' % name)


def exists(name):
    return name in _OP_REGISTRY or name in _OP_ALIASES


def list_ops():
    return sorted(_OP_REGISTRY.keys()) + sorted(_OP_ALIASES.keys())


# ---------------------------------------------------------------------------
# Reference registration names with NO graph-op equivalent here, each
# with the reason the capability is delivered another way.  A trailing
# '*' matches any suffix.  tests/test_op_conformance.py asserts every
# reference registration name (tests/data_reference_op_names.txt,
# extracted from /root/reference/src NNVM_REGISTER_OP +
# MXNET_REGISTER_OP_PROPERTY sites) is either registered or listed
# here — the mechanical op diff vs the reference is empty-or-annotated.
# ---------------------------------------------------------------------------

REFERENCE_NA = {
    '_backward_*': (
        'backward graph nodes: the reference materializes a gradient '
        'node per op (nnvm pass::Gradient); here every registered '
        'fcompute is differentiated by jax.vjp inside the one compiled '
        'step, so no backward registrations exist'),
    '_broadcast_backward': (
        'broadcast gradient-reduction node, same collapse: jax.vjp '
        'emits the sum-over-broadcast-axes reduction itself'),
    'CuDNNBatchNorm': (
        'cuDNN backend alias of BatchNorm '
        '(src/operator/cudnn_batch_norm.cc); kernel selection is '
        "XLA's job on TPU, the framework registers only BatchNorm"),
    '_CustomFunction': (
        'graph node backing autograd.Function; here custom-gradient '
        'functions run through the host-side autograd tape '
        '(mxnet_tpu/autograd.py Function) with jax.custom_vjp, no '
        'graph node needed'),
    '_cvimdecode': (
        'host-side OpenCV NDArray op; image decode lives in '
        'mxnet_tpu.image.imdecode (cv2/NumPy) and the C++ threaded '
        'decoder src/io/image_record_iter.cc'),
    '_cvimread': 'see _cvimdecode — mxnet_tpu.image.imread',
    '_cvimresize': 'see _cvimdecode — mxnet_tpu.image.imresize',
    '_cvcopyMakeBorder': 'see _cvimdecode — mxnet_tpu.image.copyMakeBorder',
}


def reference_na_reason(name):
    """Reason `name` (a reference registration name) is intentionally
    not a registered op, or None if it should exist."""
    if name in REFERENCE_NA:
        return REFERENCE_NA[name]
    for pat, reason in REFERENCE_NA.items():
        if pat.endswith('*') and name.startswith(pat[:-1]):
            return reason
    return None


# ---------------------------------------------------------------------------
# Shared helpers for op implementations
# ---------------------------------------------------------------------------

def astuple(v, n=None):
    """Parse kernel/stride/pad style attrs: accepts int, tuple, or
    '(1, 2)' string (the reference parses these via dmlc::Parameter
    TShape fields)."""
    from ..base import parse_attr_value
    v = parse_attr_value(v)
    if isinstance(v, (int, float)):
        v = (int(v),) * (n or 1)
    v = tuple(int(x) for x in v)
    if n is not None and len(v) == 1:
        v = v * n
    return v


def asbool(v):
    from ..base import parse_attr_value
    v = parse_attr_value(v)
    if isinstance(v, str):
        return v.lower() in ('true', '1')
    return bool(v)


def asint(v):
    from ..base import parse_attr_value
    return int(parse_attr_value(v))


def asfloat(v):
    from ..base import parse_attr_value
    return float(parse_attr_value(v))


def normalize_axis(axis, ndim):
    axis = asint(axis)
    return axis + ndim if axis < 0 else axis
