"""Executor: compiled execution of a Symbol.

TPU-native replacement for the reference GraphExecutor
(src/executor/graph_executor.cc; SURVEY.md §3.2).  `bind` lowers the
whole symbol DAG into one pure JAX function and compiles it with
jax.jit: the reference's Gradient pass becomes jax.vjp over that
function, PlanMemory/InitCachedOps/InitOpSegs collapse into XLA buffer
assignment and fusion, and the per-node engine push loop (RunOps,
graph_executor.cc:1236) disappears — one XLA execution per
forward/backward instead of O(#nodes) kernel dispatches.

Semantics kept from the reference:
  * arg/grad/aux NDArray dictionaries owned by the executor
  * grad_req write/add/null per argument
  * aux states (BatchNorm moving stats) updated by train-mode forward
  * backward() with no head grads relies on loss ops' internal gradients
    (custom VJPs — see ops/nn.py)
"""
import os
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp


def _maybe_remat(f, mode=None):
    """Gradient rematerialization for the fused train step
    (MXNET_TPU_REMAT): 'conv' saves only convolution/matmul results as
    forward residuals and recomputes the elementwise chains between
    them (BatchNorm apply, relu, residual adds) during backward —
    trading cheap VPU recompute for whole HBM passes of activation
    traffic.  The jax.checkpoint analog of the reference's
    MXNET_BACKWARD_DO_MIRROR (graph_executor.cc:243).  'none' keeps
    XLA's default residual choice.  `mode` pins a value captured at
    bind time (jit traces run later, when the env may have changed)."""
    if mode is None:
        mode = os.environ.get('MXNET_TPU_REMAT', 'none').lower()
    if mode in ('none', '0', ''):
        return f
    if mode != 'conv':
        raise ValueError("MXNET_TPU_REMAT must be 'none' or 'conv', "
                         'got %r' % mode)

    def save_matmuls(prim, *_, **__):
        return prim in (jax.lax.dot_general_p,
                        jax.lax.conv_general_dilated_p)

    return jax.checkpoint(f, policy=save_matmuls)

from . import exec_cache
from . import ndarray as nd
from . import random as _random
from . import profiler
from .base import MXNetError
from .ops.registry import OpContext, astuple, normalize_axis


# ---------------------------------------------------------------------------
# NHWC layout planning (executor-level "PlaceLayout" pass).
#
# The user-facing tensor semantics are NCHW (MXNet parity), but the MXU
# wants channels on the minor (lane) dimension.  Round 2 transposed
# inside each Convolution and relied on XLA to cancel the boundary
# transposes; profiling the compiled step shows that cancellation FAILS
# whenever BatchNorm/residual-add/pooling sit between convolutions
# (each stage paid multi-hundred-MB transpose fusions in fwd AND bwd,
# several GB of HBM traffic per step on an HBM-bound chip).  This pass
# instead carries activations physically as NHWC through every
# layout-flexible op — Convolution/Pooling consume NHWC natively, and
# BatchNorm re-targets its channel axis — and re-permutes to NCHW only
# where an op (Flatten/FC/reshape/...) needs the semantic layout.
# Reference analog: MXNet's cuDNN NHWC layout optimization.
# Controlled by MXNET_TPU_LAYOUT_OPT={auto,1,0}; auto = on whenever
# convs prefer NHWC (accelerator backends).
# ---------------------------------------------------------------------------

# elementwise ops whose outputs follow the input permutation unchanged
_LAYOUT_FLEX = frozenset((
    'Activation', 'Dropout', 'elemwise_add', 'elemwise_sub',
    'elemwise_mul', 'elemwise_div', '_grad_add', '_copy', 'BlockGrad',
    'Cast', 'relu', 'sigmoid', 'tanh', 'softsign', 'clip',
    '_plus_scalar', '_minus_scalar', '_mul_scalar', '_div_scalar',
    '_maximum_scalar', '_minimum_scalar', '_CrossDeviceCopy',
))


def _to_nchw(v, cur):
    if cur == 'NHWC':
        return jnp.transpose(v, (0, 3, 1, 2))
    return v


def _to_nhwc(v, cur):
    if cur == 'NHWC':
        return v
    return jnp.transpose(v, (0, 2, 3, 1))


def _layout_mode(op, attrs, vals):
    """'io' = op consumes/produces its data input in NHWC when asked
    (via the private __layout__ attr); 'elemwise' = op is permutation-
    transparent; None = op needs semantic NCHW inputs."""
    name = op.name
    if name == 'Convolution':
        try:
            if len(astuple(attrs['kernel'])) != 2:
                return None
        except Exception:
            return None
        return 'io'
    if name == 'Pooling':
        v = vals[0]
        return 'io' if getattr(v, 'ndim', 0) == 4 else None
    if name == 'BatchNorm':
        v = vals[0]
        if getattr(v, 'ndim', 0) != 4:
            return None
        try:
            axis = normalize_axis(attrs.get('axis', 1), 4)
        except Exception:
            return None
        return 'io' if axis == 1 else None
    if name in _LAYOUT_FLEX:
        return 'elemwise'
    return None


def _mirror_segments(topo, node_index, out_entries, aux_pos, skip):
    """Recomputation marked on the nodes, as the reference's
    `__force_mirroring__` is (graph_executor.cc:243): every maximal run
    of marked operator nodes in topological order (variables between
    them do not break a run; nodes in `skip` are never part of one) is
    one segment, computed under jax.checkpoint in the training pass.
    Returns {node idx: None, or for a run's last node its segment}:
    'nodes' in order, 'reads' the outside entries (node idx, output)
    it consumes, 'kept' its entries that the rest of the graph or the
    outputs consume, 'aux' the positions of aux states it advances."""
    def marked(ni):
        n = topo[ni]
        return n.op is not None and ni not in skip and str(
            n.user_attrs.get('__force_mirroring__', '')).lower() in (
                'true', '1')

    runs, run = [], []
    for ni, n in enumerate(topo):
        if n.op is None:
            continue
        if marked(ni):
            run.append(ni)
        elif run:
            runs.append(run)
            run = []
    if run:
        runs.append(run)
    consumers = {}
    for ni, n in enumerate(topo):
        for src, oi in n.inputs:
            consumers.setdefault((node_index[id(src)], oi), []).append(ni)
    out = {}
    for nodes in runs:
        if len(nodes) < 2:
            continue
        inside = set(nodes)
        reads, kept, aux = [], [], []
        for ni in nodes:
            n = topo[ni]
            for src, oi in n.inputs:
                key = (node_index[id(src)], oi)
                if key[0] not in inside and key not in reads:
                    reads.append(key)
                if n.op.mutable_aux and src.op is None and \
                        src.name in aux_pos:
                    aux.append(aux_pos[src.name])
            for oi in range(n.op.num_outputs(n.attrs)):
                if (ni, oi) in out_entries or any(
                        c not in inside
                        for c in consumers.get((ni, oi), ())):
                    kept.append((ni, oi))
        seg = {'nodes': nodes, 'reads': reads, 'kept': kept, 'aux': aux}
        out.update({ni: None for ni in nodes})
        out[nodes[-1]] = seg
    return out


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict,
                 grad_req_dict, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        # capture the remat knob now: jit tracing happens later
        self._remat_mode = os.environ.get('MXNET_TPU_REMAT',
                                          'none').lower()
        # ctx_group model parallelism (reference AttrScope ctx_group +
        # PlaceDevice pass, graph_executor.cc:367): nodes whose
        # 'ctx_group' attr maps to a device get their outputs pinned
        # there; XLA inserts the cross-device copies the reference's
        # _CrossDeviceCopy nodes did
        self._group2ctx = dict(group2ctx or {})
        self._group2dev = {k: v.jax_device()
                           for k, v in self._group2ctx.items()}
        self.arg_dict = arg_dict        # OrderedDict name -> NDArray
        self.grad_dict = grad_dict      # name -> NDArray (or absent)
        self.aux_dict = aux_dict        # OrderedDict name -> NDArray
        self._grad_req = grad_req_dict  # name -> 'write'|'add'|'null'
        self._arg_names = list(arg_dict.keys())
        self._aux_names = list(aux_dict.keys())
        self._diff_names = [n for n in self._arg_names
                            if grad_req_dict.get(n, 'null') != 'null']
        self.outputs = []
        # committed to the executor's device: the fused train step
        # returns the (donated) key committed, and an uncommitted key
        # on call 1 vs committed on call 2 would change the jit
        # sharding signature and force a full recompile
        self._key = jax.device_put(_random.next_key(), ctx.jax_device())
        self._monitor_callback = None
        # observability: how many whole-step fused dispatches ran (the
        # per-step fusion invariant "1 dispatch per batch" is asserted
        # on this in tests)
        self.fused_dispatches = 0
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        # on-disk XLA cache (cross-process warm starts) must be
        # configured before the first compilation; idempotent
        exec_cache.setup_persistent_cache()
        sym = self._symbol
        topo = sym._topo()
        # only drop to eager per-op dispatch when some node actually
        # maps to a group device; a group2ctx dict that matches nothing
        # must not forfeit the single fused XLA execution
        self._grouped = bool(self._group2dev) and any(
            n.op is not None and
            n.user_attrs.get('ctx_group') in self._group2dev
            for n in topo)
        node_index = {id(n): i for i, n in enumerate(topo)}
        arg_pos = {n: i for i, n in enumerate(self._arg_names)}
        aux_pos = {n: i for i, n in enumerate(self._aux_names)}
        out_entries = [(node_index[id(n)], i) for n, i in sym._outputs]
        # shape-carrying init ops (zeros(shape=(0,H)) from rnn
        # begin_state) need their bidirectionally-resolved output
        # shapes at execution time — but only when the attr shape
        # actually has unknown 0-dims (a plain zeros((2,3)) constant
        # must not trigger a second full inference pass at bind)
        def _unresolved_init(n):
            if n.op is None or not n.op.needs_out_shapes:
                return False
            shape = n.attrs.get('shape')
            if shape is None:
                return True
            from .base import parse_attr_value
            parsed = parse_attr_value(shape)
            try:
                return any(int(d) == 0 for d in parsed)
            except TypeError:
                return False

        node_shapes = {}
        if any(_unresolved_init(n) for n in topo):
            known = {name: tuple(a.shape)
                     for name, a in self.arg_dict.items()}
            known.update({name: tuple(a.shape)
                          for name, a in self.aux_dict.items()})
            by_id = sym._infer_node_shapes(known)
            node_shapes = {node_index[nid]: v for nid, v in by_id.items()
                           if nid in node_index}
        self._node_shapes = node_shapes
        self._has_aux_always = any(
            n.op is not None and n.op.mutable_aux and n.op.aux_always
            for n in topo)

        # -- input-BN / conv linearity split (MXNET_TPU_STEM_SPLIT) -------
        # Pattern: Convolution(no_bias) fed by BatchNorm(fix_gamma=True)
        # whose own input carries no gradient (a data leaf, possibly
        # through Cast) — the ResNet "bn_data" stem.  Autodiff of the
        # straight form needs dL/d(bn_out) = full-batch conv dgrad just
        # to reduce it to dβ (C numbers); measured 4.1 ms at 220 GB/s on
        # ResNet-50 batch 256 (docs/PERF.md round 5).  Because conv is
        # linear in its input,  conv(x̂γ + β·1) = conv(x̂γ) + conv(β·1),
        # and the second term is a batch-1 conv of a constant image — so
        # computing the split form gives autodiff a β path that costs a
        # batch-1 dgrad (~1/N of the work) and lets XLA drop the
        # full-batch dgrad entirely (x̂γ needs no gradient).
        split_bn = set()       # BN node idx: compute with β zeroed
        split_conv = {}        # conv node idx -> its BN node idx
        if os.environ.get('MXNET_TPU_STEM_SPLIT', '1') not in ('0', ''):
            from .ops.registry import asbool as _asbool, \
                astuple as _astuple
            uses = {}
            for n in topo:
                for src, oi in n.inputs:
                    uses[(id(src), oi)] = uses.get((id(src), oi), 0) + 1
            for n, oi in sym._outputs:
                uses[(id(n), oi)] = uses.get((id(n), oi), 0) + 1

            def _grad_free(n):
                while n.op is not None and n.op.name == 'Cast':
                    n = n.inputs[0][0]
                if n.op is not None:
                    return False
                if n.name in aux_pos:
                    return True
                return self._grad_req.get(n.name, 'null') == 'null'

            for ci, cnode in enumerate(topo):
                if cnode.op is None or cnode.op.name != 'Convolution':
                    continue
                if not _asbool(cnode.attrs.get('no_bias', False)):
                    continue
                if len(_astuple(cnode.attrs.get('kernel', ()))) != 2:
                    continue
                bnode, boi = cnode.inputs[0]
                if bnode.op is None or bnode.op.name != 'BatchNorm' \
                        or boi != 0:
                    continue
                if not _asbool(bnode.attrs.get('fix_gamma', False)):
                    continue
                if _asbool(bnode.attrs.get('output_mean_var', False)):
                    continue
                if uses.get((id(bnode), 0), 0) != 1:
                    continue
                if not _grad_free(bnode.inputs[0][0]):
                    continue
                bi = node_index[id(bnode)]
                split_bn.add(bi)
                split_conv[ci] = bi
        # introspection (tests assert the pattern engaged)
        self._split_conv = dict(split_conv)
        pref = os.environ.get('MXNET_TPU_LAYOUT_OPT', 'auto')
        if pref == '1':
            layout_opt = True
        elif pref == 'auto':
            from .ops import nn as _nn
            layout_opt = not self._grouped and _nn._conv_prefer_nhwc()
        elif pref in ('0', ''):
            layout_opt = False
        else:
            raise ValueError(
                "MXNET_TPU_LAYOUT_OPT must be 'auto', '1' or '0', "
                'got %r' % pref)
        self._layout_opt = layout_opt

        # locals for the traced closures: cached jitted functions are
        # shared across executors, so they must not capture `self`
        # (that would pin the first executor's whole arg/aux arrays in
        # the process-wide cache for the entry's lifetime)
        group2dev = self._group2dev
        remat_mode = self._remat_mode
        remat_last = _mirror_segments(topo, node_index, out_entries,
                                      aux_pos, set(split_bn) |
                                      set(split_conv))
        self._mirror_segments = [s for s in remat_last.values() if s]
        # nodes whose aux states are counters kept on the device (the
        # op declares fold_aux): profiler.fold_device_counters() reads
        # them through counter_aux() when it is asked
        self._counter_nodes = [
            n for n in topo if n.op is not None and n.op.fold_aux]
        if self._counter_nodes:
            profiler.watch_device_counters(self)

        def run_graph(arg_vals, aux_vals, rng, is_train, collect_all=False):
            """Evaluate the DAG; returns (outputs, new_aux_tuple), plus
            every node's outputs when collect_all (monitor mode)."""
            results = [None] * len(topo)   # per node: list of outputs
            layouts = [None] * len(topo)   # per node: layout per output
            new_aux = list(aux_vals)
            # collect_all (monitor) must expose every node's TRUE output,
            # so the β-split is disabled for that mode
            do_split = not collect_all
            split_beta = {}                # BN node idx -> β value

            def run_node(ni, results, layouts, new_aux):
                node = topo[ni]
                if node.op is None:
                    if node.name in arg_pos:
                        results[ni] = [arg_vals[arg_pos[node.name]]]
                    else:
                        results[ni] = [new_aux[aux_pos[node.name]]]
                    layouts[ni] = ['NCHW']
                    return
                op = node.op
                n_aux = op.aux_count(node.attrs)
                in_entries = node.inputs
                vals = [results[node_index[id(src)]][idx]
                        for src, idx in in_entries]
                in_l = [layouts[node_index[id(src)]][idx]
                        for src, idx in in_entries]
                eff_attrs = node.attrs
                out_layout = 'NCHW'
                if layout_opt:
                    mode = _layout_mode(op, node.attrs, vals)
                    if mode == 'io':
                        # data input rides NHWC; params/aux stay as-is
                        vals = [_to_nhwc(v, l) if j == 0 else
                                _to_nchw(v, l)
                                for j, (v, l) in enumerate(zip(vals,
                                                               in_l))]
                        eff_attrs = dict(node.attrs,
                                         __layout__='NHWC')
                        out_layout = 'NHWC'
                    elif mode == 'elemwise' and any(
                            l == 'NHWC' for l in in_l):
                        # permutation-transparent: align every 4-D
                        # input to NHWC instead of paying transposes
                        vals = [_to_nhwc(v, l)
                                if getattr(v, 'ndim', 0) == 4 else v
                                for v, l in zip(vals, in_l)]
                        out_layout = 'NHWC'
                    else:
                        vals = [_to_nchw(v, l)
                                for v, l in zip(vals, in_l)]
                # layout_opt off: nothing ever carries NHWC, vals pass
                # through untouched
                args = vals[:len(vals) - n_aux] if n_aux else vals
                auxs = vals[len(vals) - n_aux:] if n_aux else []
                op_ctx = OpContext(
                    is_train=is_train,
                    rng=jax.random.fold_in(rng, ni) if op.needs_rng else None,
                    out_shapes=node_shapes.get(ni)
                    if op.needs_out_shapes else None)
                group = node.user_attrs.get('ctx_group')
                if group is not None and group in group2dev:
                    # grouped (model-parallel) execution: inputs
                    # transfer to the group's device and the op
                    # dispatches there — the reference's PlaceDevice +
                    # _CrossDeviceCopy design (graph_executor.cc:367).
                    # (Under jit these device_puts are ignored by
                    # lowering; grouped executors run un-jitted.)
                    dev = group2dev[group]
                    args = [jax.device_put(a, dev) for a in args]
                    auxs = [jax.device_put(a, dev) for a in auxs]
                    if op_ctx.rng is not None:
                        op_ctx.rng = jax.device_put(op_ctx.rng, dev)
                if do_split and ni in split_bn:
                    # β-split stem: run the BN with β zeroed (stats and
                    # aux updates are β-independent); the partner conv
                    # adds conv(β·1) back — see the pattern comment above
                    args = list(args)
                    split_beta[ni] = args[2]
                    args[2] = jnp.zeros_like(args[2])
                # HLO metadata only ('BatchNorm.stage1_unit1_bn1'): a
                # device trace then sums kernel time by operator and node
                with jax.named_scope('%s.%s' % (op.name, node.name)):
                    outs, updated = op.apply(eff_attrs, args, auxs,
                                             op_ctx)
                    if do_split and ni in split_conv:
                        bval = split_beta[split_conv[ni]]
                        x1 = args[0]
                        bval = bval.astype(x1.dtype)
                        if eff_attrs.get('__layout__') == 'NHWC':
                            b_in = jnp.broadcast_to(
                                bval, (1,) + x1.shape[1:])
                        else:
                            b_in = jnp.broadcast_to(
                                bval[:, None, None], (1,) + x1.shape[1:])
                        outs2, _ = op.apply(eff_attrs, [b_in, args[1]],
                                            [], op_ctx)
                        outs = [outs[0] + outs2[0]]
                results[ni] = outs
                layouts[ni] = [out_layout
                               if getattr(o, 'ndim', 0) == 4 else 'NCHW'
                               for o in outs]
                if op.mutable_aux and (is_train or op.aux_always) and updated:
                    for (src, _), newv in zip(
                            in_entries[len(vals) - n_aux:], updated):
                        if src.op is None and src.name in aux_pos:
                            new_aux[aux_pos[src.name]] = newv

            def run_segment(seg):
                """A run of __force_mirroring__ nodes as one
                jax.checkpoint: what it reads from outside goes in, what
                the rest of the graph reads of it (and the aux states it
                advances) comes out and is kept; everything between is
                recomputed in the backward pass."""
                seg_layouts = {}

                def inside(ext_vals):
                    loc_r, loc_l = list(results), list(layouts)
                    loc_aux = list(new_aux)
                    for (src, oi), v in zip(seg['reads'], ext_vals):
                        loc_r[src] = list(loc_r[src])
                        loc_r[src][oi] = v
                    for ni in seg['nodes']:
                        run_node(ni, loc_r, loc_l, loc_aux)
                        seg_layouts[ni] = loc_l[ni]
                    return ([loc_r[ni][oi] for ni, oi in seg['kept']],
                            [loc_aux[p] for p in seg['aux']])

                kept, aux_new = jax.checkpoint(inside)(
                    [results[src][oi] for src, oi in seg['reads']])
                for ni in seg['nodes']:
                    layouts[ni] = seg_layouts[ni]
                    results[ni] = [None] * len(layouts[ni])
                for (ni, oi), v in zip(seg['kept'], kept):
                    results[ni][oi] = v
                for p, v in zip(seg['aux'], aux_new):
                    new_aux[p] = v

            mirrored = remat_last if is_train and not collect_all else {}
            for ni in range(len(topo)):
                if ni in mirrored:
                    if mirrored[ni] is not None:    # the run's last node
                        run_segment(mirrored[ni])
                else:
                    run_node(ni, results, layouts, new_aux)
            outputs = tuple(_to_nchw(results[ni][oi], layouts[ni][oi])
                            for ni, oi in out_entries)
            if collect_all:
                mon = []
                for node, outs_, ls in zip(topo, results, layouts):
                    if node.op is None:
                        continue
                    mon.extend(_to_nchw(o, l)
                               for o, l in zip(outs_, ls))
                return outputs, tuple(new_aux), tuple(mon)
            return outputs, tuple(new_aux)

        self._n_outputs = len(out_entries)

        # monitor mode: also emit every node's outputs (the reference's
        # executor monitor callback, graph_executor.cc:1214 — there it
        # disables bulk segments; here it is a separate jit)
        monitor_names = []
        for node in topo:
            if node.op is None:
                continue
            n_out = node.op.num_outputs(node.attrs)
            if n_out == 1:
                monitor_names.append(node.name + '_output')
            else:
                monitor_names.extend('%s_output%d' % (node.name, i)
                                     for i in range(n_out))
        self._monitor_names = monitor_names

        def fwd_monitor(arg_vals, aux_vals, rng, is_train):
            return run_graph(arg_vals, aux_vals, rng, is_train,
                             collect_all=True)

        diff_idx = [arg_pos[n] for n in self._diff_names]

        def fwd_bwd_impl(arg_vals, aux_vals, rng, head_grads):
            arg_vals = list(arg_vals)

            def f(diff_vals):
                merged = list(arg_vals)
                for i, v in zip(diff_idx, diff_vals):
                    merged[i] = v
                outs, new_aux = run_graph(tuple(merged), aux_vals, rng, True)
                return outs, new_aux

            f = _maybe_remat(f, remat_mode)   # remat covers this path too
            diff_vals = tuple(arg_vals[i] for i in diff_idx)
            (outs, vjp_fn, new_aux) = jax.vjp(f, diff_vals, has_aux=True)
            grads, = vjp_fn(tuple(head_grads))
            return outs, new_aux, grads

        if self._grouped:
            # ctx_group model parallelism: execute eagerly so each op
            # dispatches on its group's device with real transfers at
            # the boundaries (per-op dispatch is the reference's own
            # granularity); jit would collapse everything to one device
            self._sig = None
            self._fwd_monitor = fwd_monitor
            self._fwd_train = lambda a, x, r: run_graph(a, x, r, True)
            self._fwd_eval = lambda a, x, r: run_graph(a, x, r, False)
            self._fwd_bwd = fwd_bwd_impl
        else:
            # compiled-program cache: equivalent graphs (same canonical
            # signature — see exec_cache.graph_signature) share ONE set
            # of jitted step functions, so a rebind/reshape back to a
            # seen configuration re-traces and re-compiles NOTHING
            self._sig = exec_cache.graph_signature(
                sym, self._ctx, self.arg_dict, self.aux_dict,
                self._grad_req, self._group2ctx, self._remat_mode) \
                if exec_cache.enabled() else None
            fns = exec_cache.get((self._sig, 'step_fns'), count=True) \
                if self._sig is not None else None
            if fns is None:
                fns = {
                    'fwd_train': exec_cache.TimedJit(jax.jit(
                        lambda a, x, r: run_graph(a, x, r, True))),
                    'fwd_eval': exec_cache.TimedJit(jax.jit(
                        lambda a, x, r: run_graph(a, x, r, False))),
                    'fwd_monitor': exec_cache.TimedJit(jax.jit(
                        fwd_monitor, static_argnums=(3,))),
                    'fwd_bwd': exec_cache.TimedJit(jax.jit(fwd_bwd_impl)),
                }
                if self._sig is not None:
                    exec_cache.put((self._sig, 'step_fns'), fns)
            self._fwd_monitor = fns['fwd_monitor']
            self._fwd_train = fns['fwd_train']
            self._fwd_eval = fns['fwd_eval']
            self._fwd_bwd = fns['fwd_bwd']
        self._stash = None
        self._run_graph = run_graph
        self._arg_pos = arg_pos
        # un-jitted graph functions (for AOT export / driver compile checks)
        self.raw_forward = lambda arg_vals, aux_vals, rng: \
            run_graph(arg_vals, aux_vals, rng, False)
        self.raw_forward_train = lambda arg_vals, aux_vals, rng: \
            run_graph(arg_vals, aux_vals, rng, True)

    def counter_aux(self):
        """[(aux names, their arrays, attrs, the op's fold_aux)] of the
        nodes that keep counters on the device (see
        profiler.watch_device_counters).  Reading waits for the
        dispatches in flight."""
        out = []
        for n in self._counter_nodes:
            names = [src.name for src, _ in
                     n.inputs[-n.op.aux_count(n.attrs):]]
            out.append((names, [np.asarray(self.aux_dict[a]._data)
                                for a in names], n.attrs, n.op.fold_aux))
        return out

    # ------------------------------------------------------------------
    def sparse_diff_positions(self):
        """Positions (in self._diff_names order) of sparse_grad
        Embedding tables.  Module's init_optimizer passes these to
        create_fused_updater (rows-only update math) and its
        GradReducePlan is built over the dense complement — the COO
        (unique_ids, rows) gradients skip the bucketed all-reduce;
        GSPMD schedules their reduction from the gather/scatter
        shardings itself."""
        return tuple(e['dpos'] for e in self._sparse_embed_entries())

    def _sparse_embed_entries(self):
        """Module-path sparse-embedding plan, derived from the bound
        symbol (parallel/embedding.find_symbol_tables) and the bound
        arg shapes.  One entry per sparse_grad table that is a
        differentiable arg; lookups of the same table are grouped (the
        COO gradient dedups across all of them).

        Unlike the gluon path (fused.py), the rung is STATIC:
        min(vocab, total bound id slots).  A Module executor's arg
        shapes are fixed per bind/bucket, so the worst case is known at
        trace time and the program never recompiles on id-distribution
        shifts — the bucket ladder exists to solve a problem this path
        does not have.  Pad-heavy batches cost gather/scatter width,
        never correctness (padded uids are inert under clip/drop).

        Refuses (typed MXNetError) the configurations the two-pass
        capture/override rewrite cannot express here:
          * graph-DERIVED ids (the lookup input is not a bound
            variable) — pass-2 would need the pass-1 trace's
            intermediate values;
          * ids that are themselves differentiable args — integer ids
            carry no gradient, so a diff ids arg means a miswired
            graph.
        Frozen sparse tables (not in _diff_names) fall back to the
        plain dense forward gather — nothing to do."""
        if getattr(self, '_sparse_entries', None) is not None:
            return self._sparse_entries
        entries = []
        if self._symbol is not None and not self._grouped:
            from .parallel import embedding as embed_mod
            diff_set = set(self._diff_names)
            dpos = {n: j for j, n in enumerate(self._diff_names)}
            by_w = OrderedDict()
            for t in embed_mod.find_symbol_tables(self._symbol,
                                                  sparse_only=True):
                if t['weight'] not in diff_set:
                    continue
                if t['ids_input'] is None:
                    raise MXNetError(
                        'sparse embedding (Module path): table %r is '
                        'looked up with graph-derived ids; the fused '
                        'sparse rewrite needs the ids as a bound input '
                        'variable. Feed the ids directly or set '
                        'sparse_grad=False on this table.' % t['weight'])
                if t['ids_input'] in diff_set:
                    raise MXNetError(
                        'sparse embedding (Module path): ids input %r '
                        'of table %r is a differentiable arg — integer '
                        'ids carry no gradient; rebind it with '
                        "grad_req='null'." % (t['ids_input'],
                                              t['weight']))
                by_w.setdefault(t['weight'], []).append(t)
            for w, ts in by_w.items():
                slots = sum(
                    max(1, int(np.prod(self.arg_dict[t['ids_input']]
                                       .shape)))
                    for t in ts)
                entries.append({
                    'weight': w,
                    'dpos': dpos[w],
                    'arg_i': self._arg_pos[w],
                    'ids': [t['ids_input'] for t in ts],
                    'vocab': int(ts[0]['vocab']),
                    'dim': int(ts[0]['dim']),
                    'rung': min(int(ts[0]['vocab']), slots),
                })
        self._sparse_entries = entries
        return entries

    def make_fused_multistep(self, step_math, scan_names, repeat=None,
                             step_key=None, grad_reduce=None,
                             metric=None):
        """K whole training steps (forward + backward + optimizer
        update) in ONE donated XLA dispatch, looping on-device with
        lax.scan; the single step is repeat=1 with no scan_names, and
        its program holds no loop.

        TPU-native analog of the reference's bulk-exec segments
        (MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN, graph_executor.cc:1135):
        where the reference amortizes engine-push overhead by fusing op
        runs into segments, this amortizes the host->device dispatch
        latency over K full steps, keeping the MXU busy back-to-back
        (the reference pays per-op dispatch on all three phases,
        graph_executor.cc:1236 + per-key optimizer pushes).

        step_math(ws, gs, moms, masters, lrs, wds) ->
            (new_ws, new_moms, new_masters)
        is the optimizer's whole-model update math (FusedSGD.step).
        moms/masters are opaque pytrees: per-param arrays in the
        replicated mode, per-bucket dp-sharded flat buffers under
        ZeRO-1 (the sharded step_math reduce-scatters gradients and
        all-gathers updated params inside this same donated dispatch).
        Weights, aux states, momenta, and fp32 masters are donated, so
        params update in place in HBM; the PRNG split happens inside the
        step so the host issues exactly one dispatch per K batches.

        Returns None when this executor cannot fuse (ctx-group eager
        mode).  Caller contract: every differentiable arg is a weight
        updated by step_math (grad_req 'write'), in self._diff_names
        order.

        scan_names: args fed per-step (data/label).  In stacked mode
        the caller passes them stacked on a leading K axis; with
        `repeat=K` the currently bound batch is reused K times
        (only the schedule rows are scanned).  step_key: canonical
        identity of step_math (e.g. FusedSGD.cache_key()) — when
        given, the compiled step is shared through the process-wide
        executable cache across equivalent executors; it MUST also
        identify grad_reduce/metric (both bake into the traced program
        but are opaque callables here).

        grad_reduce: optional callable list->list applied to the
        gradients before step_math — the backward-interleaved bucketed
        all-reduce (collectives.GradReducePlan.apply) or its
        end-of-backward barrier baseline.

        metric: optional (init, update) pair folding metric
        accumulation into the scan carry — `init()` returns the zero
        carry, `update(carry, outs, scan_step_vals)` is pure jnp.  The
        final carry comes back from run_fused_multistep so per-batch
        metric host syncs stop breaking the bulk.

        lrs/wds arrive as ONE (K, n_params) float32 schedule array
        each (K = 1 for the single step), scanned alongside the
        batches so each step sees ITS row (FactorScheduler boundaries
        crossed mid-dispatch decay at the right step) — one
        host->device transfer per dispatch regardless of parameter
        count; the per-param split happens inside the trace.
        """
        if self._grouped:
            return None
        run_graph = self._run_graph
        remat_mode = self._remat_mode   # no self capture: fn is cached
        scan_set = set(scan_names)
        diff_set = set(self._diff_names)
        n_args = len(self._arg_names)
        diff_idx = [i for i, n in enumerate(self._arg_names)
                    if n in diff_set]
        scan_idx = [i for i, n in enumerate(self._arg_names)
                    if n in scan_set and n not in diff_set]
        inv_idx = [i for i, n in enumerate(self._arg_names)
                   if n not in diff_set and n not in scan_set]
        # scan stacks may arrive in a narrower storage dtype than the
        # bound arg (bulk_step scan_dtype); restore the bound dtype at
        # the top of each step so the graph sees its declared inputs
        scan_dt = [self.arg_dict[self._arg_names[i]]._data.dtype
                   for i in scan_idx]
        # row-sparse embedding tier (docs/SPARSE.md): tables whose
        # backward produces (unique_ids, rows) COO pairs instead of a
        # dense (vocab, dim) cotangent.  Resolved here, once per trace.
        sparse_rt = self._sparse_embed_entries()
        from .parallel import embedding as embed_mod
        scan_pos = {i: p for p, i in enumerate(scan_idx)}
        inv_pos = {i: p for p, i in enumerate(inv_idx)}
        # ('scan'|'inv', position) per lookup — where run_one finds
        # each table's traced id values without threading them
        # through the differentiated region
        sparse_src = [[('scan', scan_pos[self._arg_pos[n]])
                       if self._arg_pos[n] in scan_pos
                       else ('inv', inv_pos[self._arg_pos[n]])
                       for n in e['ids']]
                      for e in sparse_rt]
        sparse_dset = frozenset(e['dpos'] for e in sparse_rt)
        cache_key = None
        if self._sig is not None and step_key is not None:
            # step_key stays the LAST component (tests and tools key
            # off it positionally); the embed token slots in before it.
            # (The token is belt-and-braces: weight names/attrs live in
            # _sig and the updater's sparse_idx in step_key already.)
            embed_tok = tuple((e['weight'], e['rung'])
                              for e in sparse_rt) if sparse_rt else None
            cache_key = (self._sig, 'multistep', tuple(scan_idx), repeat,
                         tuple(str(d) for d in scan_dt),
                         embed_tok, step_key)
            fn = exec_cache.get(cache_key)
            if fn is not None:
                return fn

        def multistep(diff_vals, scan_vals, inv_vals, aux_vals, key,
                      moms, masters, lrs, wds):
            def run_one(diff_vals, aux_vals, moms, masters, key, sv,
                        lr_t, wd_t, mc):
                # (n,) schedule row -> per-param traced scalars
                lr_t = [lr_t[j] for j in range(len(diff_idx))]
                wd_t = [wd_t[j] for j in range(len(diff_idx))]
                key, sub = jax.random.split(key)

                def merge(dv):
                    merged = [None] * n_args
                    for i, v in zip(diff_idx, dv):
                        merged[i] = v
                    for i, v, dt in zip(scan_idx, sv, scan_dt):
                        merged[i] = v if v.dtype == dt else v.astype(dt)
                    for i, v in zip(inv_idx, inv_vals):
                        merged[i] = v
                    return merged

                # Pre-pass (outside the differentiated region): dedup
                # each sparse table's ids to a static rung and gather
                # its touched rows.  The rewrite then serves every
                # lookup as rows[inverse] — the vjp of that gather IS
                # the segment-sum, so the cotangent arriving at `rows`
                # is the per-unique-id summed row-gradient, (rung,
                # dim).  A dense model has no such table: rows_l is
                # empty and nothing is overridden.
                uids_l, rows_l, invs_l = [], [], []
                for e, src in zip(sparse_rt, sparse_src):
                    ids_vals = [sv[p] if cat == 'scan'
                                else inv_vals[p] for cat, p in src]
                    uids, invs = embed_mod.dedup_ids(
                        ids_vals, e['rung'], e['vocab'])
                    rows = embed_mod.gather_rows(
                        diff_vals[e['dpos']], uids)
                    uids_l.append(uids)
                    rows_l.append(rows)
                    invs_l.append(invs)

                def f(dv, rv):
                    merged = merge(dv)
                    # the full tables stay in dv so donation and the
                    # carry signature are unchanged; their lookups are
                    # overridden, so their dense cotangent is zero and
                    # XLA DCEs it
                    ov = {id(merged[e['arg_i']]):
                          embed_mod._Override(r, iv, e['dim'])
                          for e, r, iv in zip(sparse_rt, rv, invs_l)}
                    with embed_mod.override_scope(ov), \
                            jax.named_scope('forward'):
                        outs, new_aux = run_graph(
                            tuple(merged), aux_vals, sub, True)
                    return outs, new_aux

                f = _maybe_remat(f, remat_mode)
                outs, vjp_fn, new_aux = jax.vjp(
                    f, tuple(diff_vals), tuple(rows_l), has_aux=True)
                heads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                grads, rgrads = vjp_fn(heads)
                grads = list(grads)
                for e, uids, dr in zip(sparse_rt, uids_l, rgrads):
                    grads[e['dpos']] = (uids, dr)
                if grad_reduce is not None:
                    # COO grads skip the bucketed all-reduce: the plan
                    # was built over the dense complement
                    # (module._ensure_reduce_plan); GSPMD schedules the
                    # sparse reduction itself
                    didx = [j for j in range(len(grads))
                            if j not in sparse_dset]
                    with jax.named_scope('grad_reduce'):
                        red = grad_reduce([grads[j] for j in didx])
                    for j, g in zip(didx, red):
                        grads[j] = g
                with jax.named_scope('update'):
                    new_ws, new_moms, new_masters = step_math(
                        list(diff_vals), grads, moms, masters, lr_t,
                        wd_t)
                if metric is not None:
                    with jax.named_scope('metric_fold'):
                        mc = metric[1](mc, outs, sv)
                return (tuple(new_ws), new_aux, new_moms, new_masters,
                        key, outs, mc)

            mc0 = metric[0]() if metric is not None else ()
            lr0, wd0 = lrs[0], wds[0]
            if repeat == 1:
                # single step: no scan wrapper (keeps the whole body in
                # one fusion scope and avoids a trip-count-1 while loop)
                (new_ws, new_aux, new_moms, new_masters, key, outs,
                 mc) = run_one(tuple(diff_vals), aux_vals, moms,
                               masters, key, scan_vals, lr0, wd0, mc0)
                return (outs, new_aux, new_ws, new_moms, new_masters,
                        key, mc)

            out_shapes = jax.eval_shape(
                lambda dv: run_one(dv, aux_vals, moms, masters, key,
                                   jax.tree_util.tree_map(
                                       lambda x: x[0], scan_vals)
                                   if repeat is None else scan_vals,
                                   lr0, wd0, mc0)[5],
                tuple(diff_vals))
            outs0 = tuple(jnp.zeros(o.shape, o.dtype) for o in out_shapes)

            def body(carry, xs):
                diff_vals, aux_vals, moms, masters, key, _, mc = carry
                if repeat is None:
                    sv, lr_t, wd_t = xs
                else:
                    (lr_t, wd_t), sv = xs, scan_vals
                (new_ws, new_aux, new_moms, new_masters, key, outs,
                 mc) = run_one(diff_vals, aux_vals, moms, masters,
                               key, sv, lr_t, wd_t, mc)
                return (new_ws, new_aux, new_moms, new_masters, key,
                        outs, mc), None

            init = (tuple(diff_vals), aux_vals, moms, masters, key,
                    outs0, mc0)
            if repeat is not None:
                carry, _ = jax.lax.scan(body, init, (lrs, wds))
            else:
                carry, _ = jax.lax.scan(body, init,
                                        (tuple(scan_vals), lrs, wds))
            (new_ws, new_aux, new_moms, new_masters, key, outs,
             mc) = carry
            return (outs, new_aux, new_ws, new_moms, new_masters, key,
                    mc)

        fn = exec_cache.TimedJit(
            jax.jit(multistep, donate_argnums=(0, 3, 4, 5, 6)))
        if cache_key is not None:
            exec_cache.put(cache_key, fn)
        return fn

    def _align_step_placement(self, diff_vals, moms, masters,
                              zero=False):
        """A donated jit call requires every committed argument to live
        on the same device set, and the weights define it: when they are
        sharded over a multi-device mesh, a PRNG key (or optimizer state
        restored before the mesh bind) still committed to one device
        makes jax refuse the dispatch.  Re-commit the key replicated
        over the weights' mesh and any stale moms/masters to their
        weight's sharding.  moms/masters are aligned with diff_vals —
        except under ZeRO (zero=True), where they are per-BUCKET flat
        shards that own their dp-axis sharding (FusedSGD
        host_prep_steps committed them); only the key is aligned then."""
        shard = mesh = None
        for v in diff_vals:
            s = getattr(v, 'sharding', None)
            m = getattr(s, 'mesh', None)
            if m is not None and m.devices.size > 1:
                shard, mesh = s, m
                break
        if mesh is None:
            return moms, masters
        from jax.sharding import NamedSharding, PartitionSpec
        devset = shard.device_set
        key_sh = getattr(self._key, 'sharding', None)
        if key_sh is None or key_sh.device_set != devset:
            self._key = jax.device_put(
                self._key, NamedSharding(mesh, PartitionSpec()))
        if zero:
            return moms, masters

        def recommit(state, w):
            if state is None:
                return state
            sh = getattr(state, 'sharding', None)
            if sh is not None and sh.device_set == devset:
                return state
            return jax.device_put(state, w.sharding)

        moms = [recommit(m, w) for m, w in zip(moms, diff_vals)]
        masters = [recommit(m, w) for m, w in zip(masters, diff_vals)]
        return moms, masters

    def _step_operands(self, diff_names, scan_names, scan_stacks, moms,
                       masters, zero=False):
        """The positional operands of a make_fused_multistep program
        up to its schedule arrays, over the bound arrays: (diff_vals,
        scan_vals, inv_vals, aux_vals, key, moms, masters), placed as
        a donated call needs them (_align_step_placement).
        scan_stacks: per-name stacked (K, ...) arrays, or None where
        the bound batch is what the program reads (repeat mode, the
        single step).  zero=True marks moms/masters as ZeRO bucket
        shards."""
        args = self.arg_dict
        diff_set = set(diff_names)
        scan_set = set(scan_names) - diff_set
        scanned = scan_stacks if scan_stacks is not None else \
            {n: args[n]._data for n in scan_set}
        diff_vals = tuple(args[n]._data for n in diff_names)
        scan_vals = tuple(scanned[n] for n in self._arg_names
                          if n in scan_set)
        inv_vals = tuple(args[n]._data for n in self._arg_names
                         if n not in diff_set and n not in scan_set)
        aux_vals = tuple(self.aux_dict[n]._data for n in self._aux_names)
        moms, masters = self._align_step_placement(diff_vals, moms,
                                                   masters, zero=zero)
        return (diff_vals, scan_vals, inv_vals, aux_vals, self._key,
                moms, masters)

    def run_fused_multistep(self, step, diff_names, scan_names,
                            scan_stacks, moms, masters, lrs, wds,
                            zero=False):
        """Execute a step from make_fused_multistep over the bound
        arrays and write everything back (operands: _step_operands;
        lrs/wds: the (K, n_params) float32 schedule arrays).  Returns
        (new_moms, new_masters, metric_carry) — the states for the
        optimizer to reclaim, and the device-resident metric fold's
        final carry (() when the program has no metric fold)."""
        operands = self._step_operands(diff_names, scan_names,
                                       scan_stacks, moms, masters, zero)
        self.fused_dispatches += 1
        with profiler.scope('executor.dispatch', 'fused_step'):
            (outs, new_aux, new_ws, new_moms, new_masters, self._key,
             mcarry) = step(*operands, lrs, wds)
            self._maybe_block(outs)
        for n, w in zip(diff_names, new_ws):
            self.arg_dict[n]._data = w
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        self._stash = None
        self.outputs = [nd.NDArray(o, self._ctx) for o in outs]
        return new_moms, new_masters, mcarry

    def warm_fused_multistep(self, step, diff_names, scan_names,
                             scan_stacks, moms, masters, lrs, wds,
                             zero=False, rounds=2):
        """AOT warmup: execute a make_fused_multistep program on CLONED
        buffers so its XLA executable(s) compile now, without mutating
        any bound parameter, aux state, optimizer state, or the PRNG
        key (the bucket-ladder warmup — BucketingModule.warmup_buckets
        — drives this for every rung before training starts).

        Two rounds by default: round 1 calls with clones of the CURRENT
        buffers — the exact signature of the module's first real step —
        and round 2 feeds round 1's outputs back in, which is the
        STEADY-STATE signature (donated jit outputs carry a different
        committed/placement flavor than freshly-created arrays, and jax
        keys executables on it).  Without round 2 the second real step
        would still stall on a compile."""
        (diff_vals, scan_vals, inv_vals, aux_vals, key, moms,
         masters) = self._step_operands(diff_names, scan_names,
                                        scan_stacks, moms, masters, zero)
        dv, av, key, mo, ma = jax.tree_util.tree_map(
            jnp.copy, (diff_vals, aux_vals, key, moms, masters))
        for _ in range(max(1, int(rounds))):
            (_, av, dv, mo, ma, key, _mc) = step(
                dv, scan_vals, inv_vals, av, key, mo, ma, lrs, wds)
        jax.block_until_ready((dv, av))

    # ------------------------------------------------------------------
    def _gather(self):
        arg_vals = tuple(self.arg_dict[n]._data for n in self._arg_names)
        aux_vals = tuple(self.aux_dict[n]._data for n in self._aux_names)
        return arg_vals, aux_vals

    def _set_args(self, kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                dst = self.arg_dict[k]
                if isinstance(v, nd.NDArray):
                    if v.shape != dst.shape:
                        raise MXNetError(
                            'forward: shape mismatch for %s: %s vs bound %s'
                            % (k, v.shape, dst.shape))
                    val = v._data.astype(dst.dtype)
                else:
                    val = jnp.asarray(v, dtype=dst.dtype)
                # commit to the executor's device (inputs often arrive on
                # cpu(0) from host-side iterators)
                dst._data = jax.device_put(val, self._ctx.jax_device())
            else:
                raise MXNetError('forward: unknown argument %s' % k)

    def forward(self, is_train=False, **kwargs):
        if kwargs:
            self._set_args(kwargs)
        arg_vals, aux_vals = self._gather()
        self._key, sub = jax.random.split(self._key)
        monitor_active = self._monitor_callback is not None and \
            getattr(self._monitor_callback, 'active', True)
        if monitor_active:
            # collect-all jit: every node output is materialized — only
            # when the monitor is actually collecting this batch
            with profiler.scope('executor.forward_monitor'):
                outs, new_aux, mon = self._fwd_monitor(
                    arg_vals, aux_vals, sub, bool(is_train))
                self._maybe_block(outs)
            if is_train:
                self._stash = (arg_vals, aux_vals, sub)
            for name, v in zip(self._monitor_names, mon):
                self._monitor_callback(name, nd.NDArray(v, self._ctx))
        elif is_train:
            self._stash = (arg_vals, aux_vals, sub)
            with profiler.scope('executor.forward_train'):
                outs, new_aux = self._fwd_train(arg_vals, aux_vals, sub)
                self._maybe_block(outs)
        else:
            with profiler.scope('executor.forward'):
                outs, new_aux = self._fwd_eval(arg_vals, aux_vals, sub)
                self._maybe_block(outs)
            if self._has_aux_always:
                # optimizer-update-style ops advance their states on
                # every call, train mode or not (run_graph applies
                # their updates under aux_always) — persist them
                for n, v in zip(self._aux_names, new_aux):
                    self.aux_dict[n]._data = v
            new_aux = None
        if is_train and new_aux is not None:
            for n, v in zip(self._aux_names, new_aux):
                self.aux_dict[n]._data = v
        self.outputs = [nd.NDArray(o, self._ctx) for o in outs]
        return self.outputs

    def partial_forward(self, step=None, is_train=False, **kwargs):
        """Run the forward graph only up to op-node `step` (reference
        Executor::PartialForward, graph_executor.cc:54 — memory-limited
        stepping / debugging).  Executes the topo prefix eagerly and
        keeps the partial state so successive calls continue where the
        last one stopped; step=None finishes the graph.  Returns the
        number of op nodes still to run."""
        sym = self._symbol
        topo = sym._topo()
        op_nodes = [n for n in topo if n.op is not None]
        total = len(op_nodes)
        if kwargs:
            self._set_args(kwargs)
            self._partial_state = None
        state = getattr(self, '_partial_state', None)
        if state is None:
            arg_vals, aux_vals = self._gather()
            self._key, sub = jax.random.split(self._key)
            state = {'done': 0, 'results': {}, 'rng': sub,
                     'args': arg_vals, 'auxs': aux_vals}
        arg_pos = {n: i for i, n in enumerate(self._arg_names)}
        aux_pos = {n: i for i, n in enumerate(self._aux_names)}
        node_index = {id(n): i for i, n in enumerate(topo)}
        target = total if step is None else min(int(step), total)
        done_ops = 0
        for ni, node in enumerate(topo):
            if node.op is None:
                if ni not in state['results']:
                    if node.name in arg_pos:
                        state['results'][ni] = [
                            state['args'][arg_pos[node.name]]]
                    else:
                        state['results'][ni] = [
                            state['auxs'][aux_pos[node.name]]]
                continue
            done_ops += 1
            if done_ops <= state['done']:
                continue
            if done_ops > target:
                break
            vals = [state['results'][node_index[id(src)]][idx]
                    for src, idx in node.inputs]
            n_aux = node.op.aux_count(node.attrs)
            args = vals[:len(vals) - n_aux] if n_aux else vals
            auxs = vals[len(vals) - n_aux:] if n_aux else []
            op_ctx = OpContext(
                is_train=is_train,
                rng=jax.random.fold_in(state['rng'], ni)
                if node.op.needs_rng else None,
                out_shapes=self._node_shapes.get(ni)
                if node.op.needs_out_shapes else None)
            outs, updated = node.op.apply(node.attrs, args, auxs, op_ctx)
            state['results'][ni] = outs
            if node.op.mutable_aux and (is_train or node.op.aux_always) \
                    and updated:
                state['auxs'] = list(state['auxs'])
                # matches run_graph: consumers keep the pre-update
                # value (the var's result slot is not rewritten)
                for (src, _), newv in zip(
                        node.inputs[len(vals) - n_aux:], updated):
                    if src.op is None and src.name in aux_pos:
                        state['auxs'][aux_pos[src.name]] = newv
        state['done'] = min(target, total)
        self._partial_state = state
        if state['done'] == total:
            out_entries = [(node_index[id(n)], i)
                           for n, i in sym._outputs]
            self.outputs = [
                nd.NDArray(state['results'][ni][oi], self._ctx)
                for ni, oi in out_entries]
            for n, v in zip(self._aux_names, state['auxs']):
                self.aux_dict[n]._data = v
            self._partial_state = None
        return total - state['done'] if state['done'] < total else 0

    @staticmethod
    def _maybe_block(outs):
        """When profiling, wait for device completion INSIDE the scope —
        jit dispatch is async, so without this the recorded span would
        measure only enqueue time, not execution."""
        if profiler.is_running():
            jax.block_until_ready(outs)

    def backward(self, out_grads=None):
        if self._stash is None:
            raise MXNetError('backward called before forward(is_train=True)')
        arg_vals, aux_vals, sub = self._stash
        heads = self._default_head_grads(out_grads)
        with profiler.scope('executor.backward'):
            outs, new_aux, grads = self._fwd_bwd(arg_vals, aux_vals, sub,
                                                 heads)
            self._maybe_block(grads)
        self.outputs = [nd.NDArray(o, self._ctx) for o in outs]
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        self._write_grads(grads)

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused train-mode forward+backward: ONE XLA execution per step
        (the fast path Module uses; no reference counterpart — the
        reference pays per-op dispatch on both passes)."""
        if kwargs:
            self._set_args(kwargs)
        arg_vals, aux_vals = self._gather()
        self._key, sub = jax.random.split(self._key)
        self._stash = (arg_vals, aux_vals, sub)
        heads = self._default_head_grads(out_grads)
        with profiler.scope('executor.forward_backward'):
            outs, new_aux, grads = self._fwd_bwd(arg_vals, aux_vals, sub,
                                                 heads)
            self._maybe_block(grads)
        self.outputs = [nd.NDArray(o, self._ctx) for o in outs]
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        self._write_grads(grads)
        return self.outputs

    def _default_head_grads(self, out_grads):
        """No head grads: all-ones.  Loss outputs (SoftmaxOutput & co)
        scale their custom-VJP gradient by the head cotangent —
        identity under ones — so ones reproduces reference backward()
        exactly.  For multi-output graphs whose
        outputs are NOT loss ops, ones-head backward computes
        d(sum(outputs)) — the reference errors there instead; we warn
        once so silent sum-gradients don't masquerade as per-output
        gradients."""
        if out_grads is None:
            if self._n_outputs > 1 and not getattr(
                    self, '_warned_multi_head', False):
                self._warned_multi_head = True
                import warnings
                warnings.warn(
                    'backward() without head gradients on a %d-output '
                    'graph: gradients are of the SUM of outputs '
                    '(loss ops are unaffected; pass out_grads for '
                    'per-output control)' % self._n_outputs)
            shapes = [o.shape for o in self.outputs] if self.outputs else None
            if shapes is None:
                arg_vals, aux_vals = self._gather()
                outs = jax.eval_shape(
                    lambda a, x, r: self._fwd_eval(x, a, r)[0],
                    aux_vals, arg_vals, jax.ShapeDtypeStruct((2,), np.uint32))
                return tuple(jnp.ones(o.shape, o.dtype) for o in outs)
            return tuple(jnp.ones(o.shape,
                                  self.outputs[i].dtype)
                         for i, o in enumerate(self.outputs))
        if isinstance(out_grads, nd.NDArray):
            out_grads = [out_grads]
        return tuple(g._data if isinstance(g, nd.NDArray) else jnp.asarray(g)
                     for g in out_grads)

    def _write_grads(self, grads):
        for n, g in zip(self._diff_names, grads):
            holder = self.grad_dict.get(n)
            if holder is None:
                continue
            if self._grad_req.get(n) == 'add':
                holder._data = holder._data + g
            else:
                holder._data = g

    # ------------------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return OrderedDict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = jnp.asarray(
                    v.asnumpy() if isinstance(v, nd.NDArray) else v,
                    dtype=self.arg_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError('Found name "%s" not in arguments' % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    self.aux_dict[k]._data = jnp.asarray(
                        v.asnumpy() if isinstance(v, nd.NDArray) else v,
                        dtype=self.aux_dict[k].dtype)
                elif not allow_extra_params:
                    raise MXNetError('Found name "%s" not in aux states' % k)

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def memory_cost(self, mode='forward'):
        """Memory statistics of this executor's compiled XLA module —
        the reference example/memcost role (there: the NNVM allocation
        plan's 'Total x MB allocated'; here: the XLA buffer
        assignment, which IS this runtime's allocation plan).  mode is
        'forward' (inference program), 'train' (train-mode forward) or
        'train_backward' (forward+backward, honoring MXNET_TPU_REMAT).
        Returns a dict of argument/output/temp/peak/code byte counts."""
        if self._grouped:
            raise MXNetError('memory_cost: ctx_group executors run '
                             'eagerly per-op; no single compiled module')
        if mode not in ('forward', 'train', 'train_backward'):
            raise ValueError("memory_cost mode must be 'forward', "
                             "'train' or 'train_backward', got %r" % mode)
        # this debug path AOT-compiles outside the jit dispatch cache;
        # share the compiled module through the process-wide cache so
        # repeated memory_cost calls (and equivalent executors) pay
        # ONE compile per mode.  AOT lowering bakes concrete shardings
        # in (jit would re-trace), so they join the key: a mesh-sharded
        # rebind must not reuse a single-device compile
        cache_key = None
        if self._sig is not None:
            shard_fp = tuple(
                str(getattr(a._data, 'sharding', None))
                for a in list(self.arg_dict.values()) +
                list(self.aux_dict.values()))
            cache_key = (self._sig, 'memcost', mode, shard_fp)
        compiled = exec_cache.get(cache_key) \
            if cache_key is not None else None
        if compiled is None:
            arg_vals, aux_vals = self._gather()
            key = jax.random.PRNGKey(0)
            if mode == 'forward':
                lowered = self._fwd_eval.lower(arg_vals, aux_vals, key)
            elif mode == 'train':
                lowered = self._fwd_train.lower(arg_vals, aux_vals, key)
            else:
                outs, _ = jax.eval_shape(self.raw_forward_train, arg_vals,
                                         aux_vals, key)
                # abstract head grads: .lower() needs only shapes/dtypes
                heads = tuple(jax.ShapeDtypeStruct(o.shape, o.dtype)
                              for o in outs)
                lowered = self._fwd_bwd.lower(arg_vals, aux_vals, key,
                                              heads)
            compiled = exec_cache.timed_compile(lowered)
            if cache_key is not None:
                exec_cache.put(cache_key, compiled)
        stats = compiled.memory_analysis()
        if stats is None:
            raise MXNetError('memory_cost: this backend reports no '
                             'compiled-module memory statistics')
        out = {}
        for field in ('argument_size_in_bytes', 'output_size_in_bytes',
                      'temp_size_in_bytes', 'peak_memory_in_bytes',
                      'generated_code_size_in_bytes'):
            out[field.replace('_size_in_bytes', '_bytes')
                .replace('_in_bytes', '_bytes')] = \
                int(getattr(stats, field, 0) or 0)
        return out

    def debug_str(self):
        """Plan dump: topo-ordered ops, output shapes, and memory
        totals (reference Executor::Print / MXExecutorPrint,
        graph_executor.cc:81-89)."""
        lines = ['Symbol outputs: %s' % ', '.join(
            self._symbol.list_outputs())]
        total = 0
        for name, arr in list(self.arg_dict.items()) + \
                list(self.aux_dict.items()):
            total += arr.size * np.dtype(arr.dtype).itemsize
        for node in self._symbol._topo():
            if node.op is None:
                continue
            group = node.user_attrs.get('ctx_group')
            lines.append('  op %s (%s)%s' % (
                node.name, node.op.name,
                ' @%s' % group if group else ''))
        lines.append('Total bytes in args/aux: %d (%.1f MB)'
                     % (total, total / 1e6))
        lines.append('Compiled: %s' % (
            'eager per-op (ctx groups)' if getattr(self, '_grouped',
                                                   False)
            else 'single fused XLA module'))
        return '\n'.join(lines)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new shapes (reference
        executor.py reshape; used by bucketing/DataParallel resize)."""
        sym = self._symbol
        arg_shapes, _, aux_shapes = sym.infer_shape(**kwargs)
        arg_dict = OrderedDict()
        for name, shape in zip(sym.list_arguments(), arg_shapes):
            cur = self.arg_dict[name]
            if cur.shape == tuple(shape):
                arg_dict[name] = cur
            else:
                arg_dict[name] = nd.zeros(shape, self._ctx, dtype=cur.dtype)
        grad_dict = {}
        for name, g in self.grad_dict.items():
            shape = arg_shapes[sym.list_arguments().index(name)]
            grad_dict[name] = g if g.shape == tuple(shape) else \
                nd.zeros(shape, self._ctx, dtype=g.dtype)
        aux_dict = OrderedDict()
        for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
            cur = self.aux_dict[name]
            aux_dict[name] = cur if cur.shape == tuple(shape) else \
                nd.zeros(shape, self._ctx, dtype=cur.dtype)
        return Executor(sym, self._ctx, arg_dict, grad_dict, aux_dict,
                        dict(self._grad_req),
                        group2ctx=self._group2ctx)

    # ------------------------------------------------------------------
    @staticmethod
    def _normalize_grad_req(grad_req, arg_names):
        if isinstance(grad_req, str):
            return {n: grad_req for n in arg_names}
        if isinstance(grad_req, (list, tuple)):
            return dict(zip(arg_names, grad_req))
        out = {n: 'null' for n in arg_names}
        out.update(grad_req or {})
        return out

    @staticmethod
    def _simple_bind(symbol, ctx, grad_req='write', type_dict=None,
                     shared_exec=None, shape_kwargs=None, group2ctx=None):
        """The reference simple_bind flow (graph_executor.cc:789):
        infer shapes/types, allocate arg/grad/aux arrays, compile."""
        shape_kwargs = shape_kwargs or {}
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_shapes, _, aux_shapes = symbol.infer_shape(**shape_kwargs)
        type_dict = type_dict or {}
        # dtype inference: params downstream of a Cast allocate in the
        # compute dtype (mixed-precision graphs, reference --dtype fp16)
        arg_types, _, aux_types = symbol.infer_type(**type_dict)
        inferred = dict(zip(arg_names, arg_types))
        inferred.update(zip(aux_names, aux_types))
        req = Executor._normalize_grad_req(grad_req, arg_names)
        arg_dict = OrderedDict()
        grad_dict = {}
        for name, shape in zip(arg_names, arg_shapes):
            dtype = type_dict.get(name, inferred.get(name, np.float32))
            if shared_exec is not None and name in shared_exec.arg_dict and \
                    shared_exec.arg_dict[name].shape == tuple(shape):
                arg_dict[name] = shared_exec.arg_dict[name]
            else:
                arg_dict[name] = nd.zeros(shape, ctx, dtype=dtype)
            if req.get(name, 'null') != 'null':
                if shared_exec is not None and \
                        name in shared_exec.grad_dict and \
                        shared_exec.grad_dict[name].shape == tuple(shape):
                    grad_dict[name] = shared_exec.grad_dict[name]
                else:
                    grad_dict[name] = nd.zeros(shape, ctx, dtype=dtype)
        aux_dict = OrderedDict()
        for name, shape in zip(aux_names, aux_shapes):
            if shared_exec is not None and name in shared_exec.aux_dict and \
                    shared_exec.aux_dict[name].shape == tuple(shape):
                aux_dict[name] = shared_exec.aux_dict[name]
            else:
                aux_dict[name] = nd.zeros(
                    shape, ctx, dtype=inferred.get(name, np.float32))
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, req,
                        group2ctx=group2ctx)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad=None, grad_req='write',
              aux_states=None, shared_exec=None, group2ctx=None):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = OrderedDict(zip(arg_names, args))
        else:
            arg_dict = OrderedDict((n, args[n]) for n in arg_names)
        req = Executor._normalize_grad_req(grad_req, arg_names)
        if args_grad is None:
            grad_dict = {n: nd.zeros(arg_dict[n].shape, ctx,
                                     dtype=arg_dict[n].dtype)
                         for n in arg_names if req.get(n, 'null') != 'null'}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        else:
            grad_dict = dict(args_grad)
        if aux_states is None:
            _, _, aux_shapes = symbol.infer_shape(
                **{n: a.shape for n, a in arg_dict.items()})
            aux_dict = OrderedDict(
                (n, nd.zeros(s, ctx)) for n, s in zip(aux_names, aux_shapes))
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = OrderedDict(zip(aux_names, aux_states))
        else:
            aux_dict = OrderedDict((n, aux_states[n]) for n in aux_names)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, req,
                        group2ctx=group2ctx)
