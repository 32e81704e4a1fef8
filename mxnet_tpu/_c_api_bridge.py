"""Python side of the TRAINING C ABI (src/c_api_train.cc).

The reference exposes its full training surface through 139 C functions
(/root/reference/include/mxnet/c_api.h: NDArray create/copy, Symbol
compose/infer, Executor bind/forward/backward, KVStore push/pull) so
that every language binding — cpp-package first of all
(/root/reference/cpp-package/example/mlp.cpp trains end-to-end from
C++) — can train without Python in the caller.  This module is the
TPU-era equivalent: src/c_api_train.cc embeds CPython and drives these
functions through a minimal scalar/bytes call surface; each returned
object (NDArray / Symbol / Executor / KVStore / updater) is held by the
C side as an opaque PyObject* handle.

Everything here is a thin adapter over the public mxnet_tpu API — no
logic of its own beyond argument shaping, so the C ABI can never drift
from what Python users get.
"""
import numpy as np

from . import autograd as ag
from . import context as ctx_mod
from . import kvstore as kv_mod
from . import ndarray as nd
from . import optimizer as opt_mod
from . import symbol as sym_mod
from .ops import registry as _reg


def _ctx(dev_type, dev_id):
    # reference dev_type convention: 1 = cpu, 2 = accelerator
    return ctx_mod.cpu(dev_id) if int(dev_type) == 1 \
        else ctx_mod.tpu(dev_id)


# -- NDArray ----------------------------------------------------------------

def nd_create(shape, dev_type, dev_id):
    return nd.zeros(tuple(int(d) for d in shape), ctx=_ctx(dev_type, dev_id))


def nd_from_bytes(shape, buf, dev_type, dev_id):
    arr = np.frombuffer(buf, dtype='<f4').reshape(
        tuple(int(d) for d in shape))
    return nd.array(arr, ctx=_ctx(dev_type, dev_id), dtype=np.float32)


def nd_to_bytes(arr):
    return np.ascontiguousarray(
        arr.asnumpy().astype('<f4', copy=False)).tobytes()


def nd_copy_from(arr, buf):
    """In-place refill from flat float32 bytes (shape preserved)."""
    src = np.frombuffer(buf, dtype='<f4').reshape(arr.shape)
    arr[:] = nd.array(src, dtype=np.float32)


def nd_shape(arr):
    return tuple(int(d) for d in arr.shape)


def nd_save(fname, keys, arrays):
    nd.save(fname, dict(zip(keys, arrays)) if keys else list(arrays))


def nd_load(fname):
    """-> (keys, arrays); keys are '' for list-style files."""
    loaded = nd.load(fname)
    if isinstance(loaded, dict):
        names = list(loaded.keys())
        return names, [loaded[k] for k in names]
    return [''] * len(loaded), list(loaded)


def nd_slice(arr, begin, end):
    begin, end = int(begin), int(end)
    if not 0 <= begin < end <= arr.shape[0]:
        raise ValueError('invalid slice [%d, %d) for axis of length %d'
                         % (begin, end, arr.shape[0]))
    return arr[begin:end]


def nd_reshape(arr, shape):
    return arr.reshape(tuple(int(d) for d in shape))


# -- Symbol -----------------------------------------------------------------

def sym_variable(name):
    return sym_mod.Variable(name)


def sym_create(op_name, name, attr_keys, attr_vals, arg_names, arg_syms):
    """Atomic symbol creation + composition in one call (the reference
    splits this into MXSymbolCreateAtomicSymbol + MXSymbolCompose)."""
    op = getattr(sym_mod, op_name, None)
    if op is None:
        raise ValueError('unknown operator %r' % op_name)
    kwargs = dict(zip(attr_keys, attr_vals))
    for aname, asym in zip(arg_names, arg_syms):
        kwargs[aname] = asym
    if name:
        kwargs['name'] = name
    return op(**kwargs)


def sym_from_json(text):
    return sym_mod.load_json(text)


def sym_to_json(sym):
    return sym.tojson()


def sym_list_arguments(sym):
    return list(sym.list_arguments())


def sym_list_outputs(sym):
    return list(sym.list_outputs())


def sym_list_aux(sym):
    return list(sym.list_auxiliary_states())


def sym_get_internals(sym):
    return sym.get_internals()


def sym_get_output(sym, index):
    return sym[int(index)]


def sym_get_internal_by_name(sym, name):
    return sym.get_internals()[name]


def sym_attr_get(sym, key):
    """-> (present, value); '' value with present=0 means unset."""
    value = sym.attr(key)
    if value is None:
        return 0, ''
    return 1, str(value)


def sym_attr_set(sym, key, value):
    sym._set_attr(**{key: value})


def sym_infer_shape(sym, names, shapes):
    known = {n: tuple(int(d) for d in s) for n, s in zip(names, shapes)}
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**known)
    return (list(arg_shapes or []), list(out_shapes or []),
            list(aux_shapes or []))


# -- Executor ---------------------------------------------------------------

def simple_bind(sym, dev_type, dev_id, grad_req, names, shapes):
    known = {n: tuple(int(d) for d in s) for n, s in zip(names, shapes)}
    return sym.simple_bind(_ctx(dev_type, dev_id), grad_req=grad_req,
                           **known)


def ex_forward(ex, is_train):
    ex.forward(is_train=bool(is_train))


def ex_backward(ex):
    ex.backward()


def ex_num_outputs(ex):
    return len(ex.outputs)


def ex_output(ex, index):
    return ex.outputs[int(index)]


def ex_arg(ex, name):
    return ex.arg_dict[name]


def ex_grad(ex, name):
    grad = ex.grad_dict.get(name)
    if grad is None:
        raise KeyError('no gradient bound for %r' % name)
    return grad


# -- Imperative invoke + autograd -------------------------------------------

def imperative_invoke(op_name, inputs, attr_keys, attr_vals):
    """Run any registered op by name on NDArray inputs (reference
    MXImperativeInvoke, c_api_ndarray.cc:423).  Attr values arrive as
    strings — the same convention symbol composition uses; ops parse
    their own attrs.  -> list of output NDArrays."""
    if not _reg.exists(op_name):
        raise ValueError('unknown operator %r' % op_name)
    out = nd.invoke(op_name, list(inputs), dict(zip(attr_keys, attr_vals)))
    return list(out) if isinstance(out, (list, tuple)) else [out]


def random_seed(seed):
    """Reference MXRandomSeed: seed the global op RNG stream."""
    from . import random as _random
    _random.seed(int(seed))


def wait_all():
    """Reference MXNDArrayWaitAll.  A device's compute stream executes
    in dispatch order, so a trivial computation enqueued AFTER the
    queued work is ready only once the stream has drained.  Failures
    surface (C callers get -1), they are not swallowed."""
    global _drain
    import jax
    import jax.numpy as jnp
    if _drain is None:   # one cached jit, not a fresh trace per call
        _drain = jax.jit(lambda v: v + 1)
    for d in jax.devices():
        x = jax.device_put(jnp.zeros((), jnp.int32), d)
        _drain(x).block_until_ready()


_drain = None


def list_op_names():
    """Every invokable registry name, aliases included (reference
    MXSymbolListAtomicSymbolCreators — the list a binding's codegen
    walks to build its op namespace)."""
    return [str(n) for n in _reg.list_ops()]


def op_registry_generation():
    """Live registry generation stamp.  The C introspection caches
    (MXTListOpNames / MXTOpGetInfo) poll this and rebuild when it
    changes, so runtime-registered ops appear instead of a stale
    first-call snapshot.  A mutation counter, not a cardinality:
    RE-registering an existing name (same dict sizes, new inputs)
    also invalidates."""
    return _reg.generation()


def op_info(name):
    """-> flat string list [canonical_name, description, in0, in1, ...]
    (reference MXSymbolGetAtomicSymbolInfo).  Input names for ops whose
    arity depends on attrs are resolved with empty attrs — the same
    default composition sees."""
    op = _reg.get(name)
    try:
        inputs = [str(i) for i in op.input_names({})]
    except Exception:
        inputs = []
    doc = (getattr(op.fcompute, '__doc__', None) or '').strip()
    return [str(op.name), doc] + inputs


def autograd_set_recording(flag):
    """-> previous state (reference MXAutogradSetIsRecording)."""
    prev = ag.is_recording()
    ag.set_recording(bool(flag))
    return int(prev)


def autograd_set_training(flag):
    prev = ag.is_training()
    ag.set_training(bool(flag))
    return int(prev)


def autograd_mark_variables(variables, grad_reqs):
    ag.mark_variables(list(variables), grad_reqs=list(grad_reqs))


def autograd_backward(heads, retain_graph):
    ag.backward(list(heads), retain_graph=bool(retain_graph))


def nd_get_grad(arr):
    """Gradient buffer attached by mark_variables + backward (reference
    MXNDArrayGetGrad)."""
    if arr._grad is None:
        raise ValueError('array has no gradient: mark it with '
                         'MXTAutogradMarkVariables and run backward first')
    return arr._grad


# -- CachedOp ---------------------------------------------------------------

class _CachedOp(object):
    """Mini-JIT graph replay (reference CachedOp, c_api_ndarray.cc:464).

    TPU-native design: the symbol's whole DAG executes as ONE jitted XLA
    callable per distinct input signature (shape/dtype/context), and the
    invocation is tape-recorded as a single op — so an enclosing
    autograd.record() scope differentiates straight through the cached
    graph, exactly like the reference's CachedOp under MXAutogradBackward.
    Inputs arrive in list_arguments() + list_auxiliary_states() order.
    """

    def __init__(self, sym):
        self._sym = sym
        self.arg_names = sym.list_arguments()
        self.aux_names = sym.list_auxiliary_states()
        self.n_outputs = len(sym.list_outputs())
        self._cache = {}

    def _compiled(self, args, ctx):
        import jax
        key = (str(ctx),) + tuple((tuple(a.shape), str(a.dtype))
                                  for a in args)
        fn = self._cache.get(key)
        if fn is None:
            shapes = {n: tuple(a.shape)
                      for n, a in zip(self.arg_names, args)}
            ex = self._sym.simple_bind(ctx, grad_req='null', **shapes)
            fn = jax.jit(ex._run_graph, static_argnums=(3,))
            # run_graph takes its values positionally; the executor's
            # bound zero-arrays are dead weight the cached jit closure
            # would otherwise pin for the CachedOp's lifetime
            ex.arg_dict.clear()
            ex.grad_dict.clear()
            ex.aux_dict.clear()
            self._cache[key] = fn
        return fn

    def invoke(self, inputs):
        n_args = len(self.arg_names)
        n_aux = len(self.aux_names)
        if len(inputs) != n_args + n_aux:
            raise ValueError(
                'CachedOp expects %d inputs (%d args + %d aux), got %d'
                % (n_args + n_aux, n_args, n_aux, len(inputs)))
        args, auxs = list(inputs[:n_args]), list(inputs[n_args:])
        ctx = args[0].context if args else ctx_mod.current_context()
        fn = self._compiled(args, ctx)

        def fcompute(attrs, in_data, aux_data, op_ctx):
            outs, new_aux = fn(tuple(in_data[:n_args]),
                               tuple(in_data[n_args:]),
                               op_ctx.rng, op_ctx.is_train)
            return list(outs) + list(new_aux), []

        results = nd.invoke_fn(fcompute, args + auxs, name='_cached_op')
        outs = results[:self.n_outputs]
        # write updated auxiliary state (BN moving stats) back into the
        # caller's arrays, mirroring executor semantics
        for holder, new in zip(auxs, results[self.n_outputs:]):
            holder._data = new._data
        return outs


def cached_op_create(sym):
    return _CachedOp(sym)


def cached_op_invoke(op, inputs):
    return op.invoke(list(inputs))


# -- Optimizer --------------------------------------------------------------

def updater_create(opt_name, attr_keys, attr_vals):
    """An updater closure over a fresh optimizer (reference
    MXOptimizerCreateOptimizer + KVStore updater role)."""
    kwargs = {}
    for k, v in zip(attr_keys, attr_vals):
        try:
            kwargs[k] = float(v) if '.' in v or 'e' in v.lower() \
                else int(v)
        except ValueError:
            kwargs[k] = v
    optimizer = opt_mod.create(opt_name, **kwargs)
    return opt_mod.get_updater(optimizer)


def updater_step(updater, index, grad, weight):
    updater(int(index), grad, weight)


# -- DataIter ---------------------------------------------------------------
#
# The reference exposes its data pipeline to every binding through
# MXListDataIters / MXDataIterCreateIter / Next / GetData / GetLabel
# (/root/reference/src/c_api/c_api.cc iter block; include/mxnet/c_api.h)
# — its C++/Scala/R frontends all train from .rec files through it.
# Same contract here: create by registered name with string params.

def _parse_iter_param(value):
    s = str(value).strip()
    low = s.lower()
    if low in ('true', 'false'):
        return low == 'true'
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    if s.startswith('(') and s.endswith(')'):
        items = [x for x in s[1:-1].split(',') if x.strip()]
        return tuple(int(float(x)) for x in items)
    return value


def _iter_registry():
    from . import io as io_mod
    # the string-creatable iterators (NDArrayIter needs in-memory
    # arrays, so like the reference it is not in the C create registry)
    return {
        'CSVIter': io_mod.CSVIter,
        'ImageRecordIter': io_mod.ImageRecordIter,
        'MNISTIter': io_mod.MNISTIter,
    }


def list_data_iters():
    return sorted(_iter_registry().keys())


class _CDataIter(object):
    """C-handle wrapper: the iterator plus its current batch, so
    GetData/GetLabel have a stable batch to hand out between Next
    calls (the reference's DataIter::Value() contract)."""

    def __init__(self, it):
        self.it = it
        self.cur = None


def data_iter_create(name, keys, vals):
    registry = _iter_registry()
    if name not in registry:
        raise ValueError('unknown data iter %r (have: %s)'
                         % (name, ', '.join(sorted(registry))))
    kwargs = {k: _parse_iter_param(v) for k, v in zip(keys, vals)}
    return _CDataIter(registry[name](**kwargs))


def data_iter_before_first(handle):
    handle.it.reset()
    handle.cur = None


def data_iter_next(handle):
    try:
        handle.cur = handle.it.next()
    except StopIteration:
        handle.cur = None
        return 0
    return 1


def _current_batch(handle):
    if handle.cur is None:
        raise ValueError('no current batch: call Next first')
    return handle.cur


def data_iter_get_data(handle):
    return _current_batch(handle).data[0]


def data_iter_get_label(handle):
    return _current_batch(handle).label[0]


def data_iter_get_pad(handle):
    return int(_current_batch(handle).pad or 0)


def nd_copy_from_nd(dst, src):
    """Device-side refill: dst[:] = src (the reference's
    _copyto/_load_general path; used by C callers to feed executor-bound
    arrays from iterator batches without a host round-trip)."""
    if tuple(dst.shape) != tuple(src.shape):
        raise ValueError('shape mismatch: dst %s vs src %s'
                         % (dst.shape, src.shape))
    dst[:] = src


# -- KVStore ----------------------------------------------------------------

def kv_create(kind):
    return kv_mod.create(kind)


def kv_init(kv, key, value):
    kv.init(key, value)


def kv_push(kv, key, value):
    kv.push(key, value)


def kv_pull(kv, key, out):
    kv.pull(key, out=out)
