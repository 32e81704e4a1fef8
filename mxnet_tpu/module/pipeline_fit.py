"""GPipe dp×pipe training for the symbolic Module path (round 16).

`Module.fit(pipeline=(num_stages, num_micro))` — or
MXNET_TPU_PIPE='stages,micro' — lands here: the symbol's layer chain
partitions into an optional stem, `num_stages` architecturally
identical stages, and an optional head (the same longest-identical-run
rule as the gluon PipelinedStep, applied to the symbol's op spine
instead of Sequential children), stage parameters stack on a leading
stage dim sharded over the 'pipe' axis of a 2D {'data': dp,
'pipe': S} mesh (parallel/pipeline.stack_stage_params /
place_pipeline_params), and every training step runs the fill-drain
microbatch schedule through parallel/pipeline.make_pipe_step_fn — the
SAME engine the gluon path compiles, so forward + backward + gradient
reduction over dp (psum, or psum_scatter under ZeRO-1 via
MXNET_TPU_ZERO=1) + the SGD/NAG update are ONE donated XLA dispatch,
and fit(bulk=K) scans K steps inside it.

Stage bodies evaluate through the op registry's own `apply` (the one
compute definition the imperative API and the executor share), as a
pure function of (parameter values, activation) — a minimal chain
evaluator, not the full Executor (no layout opt, ctx groups, or
monitor: none compose with the pipelined schedule).  Gradient
semantics match Executor backward(): loss ops' custom VJPs ignore
head gradients, so differentiating sum(outputs) reproduces the
reference gradients exactly (executor._default_head_grads).

Programs resolve through the process-wide exec_cache keyed on the
abstract-jaxpr fingerprint + mesh fingerprint + stage/bucket layout,
so an equivalent re-created Module performs ZERO new XLA compilations.

Restrictions (all raise loudly): chain-style single-output symbols
(every op has one graph input), exactly one data and one label, no
auxiliary state (BatchNorm running stats), no fixed/state params, and
a plain SGD/NAG optimizer without multi_precision.
"""
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import ndarray as nd
from .. import optimizer as opt_mod
from .. import profiler
from .. import random as _random
from ..base import MXNetError
from ..ops.registry import OpContext
from ..parallel import mesh as pmesh
from ..parallel import pipeline as pipe_mod
from ..parallel import zero as zero_mod


# ---------------------------------------------------------------------------
# symbol chain partitioning
# ---------------------------------------------------------------------------

def _spine_nodes(symbol, data_set, label_set, param_set):
    """The symbol's op chain, input-first.  Each op must have exactly
    one graph input (an op node or the data variable); every other
    input must be a parameter or label variable."""
    if len(symbol._outputs) != 1:
        raise MXNetError(
            'fit(pipeline): the symbol must have exactly one output, '
            'got %d' % len(symbol._outputs))
    node = symbol._outputs[0][0]
    spine = []
    while True:
        if node.op.aux_count(node.attrs):
            raise MXNetError(
                'fit(pipeline): op %r (%s) carries auxiliary state — '
                'BatchNorm & co are not composed with the pipelined '
                'schedule yet' % (node.name, node.op.name))
        if node.op.needs_out_shapes:
            raise MXNetError(
                'fit(pipeline): op %r (%s) needs inferred output '
                'shapes at execution time; not supported in the '
                'pipelined evaluator' % (node.name, node.op.name))
        spine.append(node)
        preds = []
        for src, soi in node.inputs:
            if src.op is not None or src.name in data_set:
                preds.append((src, soi))
            elif src.name not in param_set and src.name not in label_set:
                raise MXNetError(
                    'fit(pipeline): input %r of node %r is neither '
                    'data, label nor parameter (state inputs are not '
                    'supported)' % (src.name, node.name))
        if len(preds) != 1:
            raise MXNetError(
                'fit(pipeline): node %r has %d graph inputs — the '
                'pipelined mode partitions a single-chain symbol'
                % (node.name, len(preds)))
        src, _ = preds[0]
        if src.op is None:
            break
        node = src
    spine.reverse()
    return spine


def _segments(spine, param_set):
    """Group the spine into parameter-anchored segments: a segment
    starts at each parameter-consuming op; parameter-free followers
    (activations, reshapes) ride with their predecessor."""
    segs = []
    for node in spine:
        has_param = any(src.op is None and src.name in param_set
                        for src, _ in node.inputs)
        if has_param or not segs:
            segs.append([node])
        else:
            segs[-1].append(node)
    return segs


def _canon_attrs(node):
    return tuple(sorted((k, str(v)) for k, v in node.attrs.items()))


def _seg_sig(seg, param_shapes, param_set, label_set):
    """Structural identity of one segment for stage partitioning:
    op names + hyperparams + each input's kind (spine / param
    shape+dtype / label).  Necessary, not sufficient — the traced
    stage-jaxpr equality check (_check_homogeneity) is definitive."""
    sig = []
    for node in seg:
        ins = []
        for src, _ in node.inputs:
            if src.op is None and src.name in param_set:
                ins.append(('param',) + param_shapes[src.name])
            elif src.op is None and src.name in label_set:
                ins.append('label')
            else:
                ins.append('spine')    # op node or the data variable
        sig.append((node.op.name, _canon_attrs(node), tuple(ins)))
    return tuple(sig)


def _partition_spine(symbol, num_stages, data_names, label_names,
                     param_names, param_shapes):
    """(stem_nodes, [stage_nodes...], head_nodes) by the longest run
    of consecutive structurally identical segments (must divide by
    num_stages) — the same rule the gluon PipelinedStep applies to
    Sequential children."""
    data_set, label_set = set(data_names), set(label_names)
    param_set = set(param_names)
    spine = _spine_nodes(symbol, data_set, label_set, param_set)
    segs = _segments(spine, param_set)
    sigs = [_seg_sig(s, param_shapes, param_set, label_set)
            for s in segs]
    best_start, best_len = 0, 1
    start = 0
    for i in range(1, len(sigs) + 1):
        if i == len(sigs) or sigs[i] != sigs[start]:
            if i - start > best_len:
                best_start, best_len = start, i - start
            start = i
    if best_len % num_stages:
        raise MXNetError(
            'fit(pipeline): the longest run of identical layer '
            'segments has length %d, not divisible into %d stages — '
            'stack a multiple of %d identical layers'
            % (best_len, num_stages, num_stages))
    per = best_len // num_stages
    flat = lambda ss: [n for seg in ss for n in seg]
    stages = [flat(segs[best_start + s * per:
                        best_start + (s + 1) * per])
              for s in range(num_stages)]
    return (flat(segs[:best_start]), stages,
            flat(segs[best_start + best_len:]))


def _run_params(nodes, param_set):
    """Parameter names a node run consumes, in consumption order."""
    names = []
    for node in nodes:
        for src, _ in node.inputs:
            if src.op is None and src.name in param_set \
                    and src.name not in names:
                names.append(src.name)
    return names


def _eval_nodes(nodes, pnames, pvals, x, rng, label=None,
                label_set=(), out_idx=0):
    """Evaluate a chain run as a pure function: parameter values by
    name, the incoming activation `x` substituted for every graph
    input from outside the run (the previous stage's output / the
    data variable), labels by name.  Ops run through the registry's
    apply — the one compute definition."""
    inside = {id(n) for n in nodes}
    byp = dict(zip(pnames, pvals))
    env = {}
    for i, node in enumerate(nodes):
        args = []
        for src, soi in node.inputs:
            if src.op is not None and id(src) in inside:
                args.append(env[(id(src), soi)])
            elif src.op is not None:
                args.append(x)
            elif src.name in byp:
                args.append(byp[src.name])
            elif src.name in label_set:
                args.append(label)
            else:
                args.append(x)          # the data variable
        ctx = OpContext(
            is_train=True,
            rng=jax.random.fold_in(rng, i) if node.op.needs_rng
            else None)
        outs, _ = node.op.apply(node.attrs, args, [], ctx)
        for j, o in enumerate(outs):
            env[(id(node), j)] = o
    return env[(id(nodes[-1]), out_idx)]


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

class ModulePipeTrainer:
    """Owns the dp×pipe device state of one pipelined Module.fit run:
    stacked stage leaves (P('pipe')), replicated stem/head leaves,
    momentum state (ZeRO-sharded buckets when MXNET_TPU_ZERO=1), the
    step RNG, and the compiled step programs (resolved through the
    process-wide exec_cache).  sync_to_module() writes the trained
    weights back into the module's host params."""

    def __init__(self, module, spec, zero=None):
        self._mod = module
        self._pipe_s, self._pipe_m = pipe_mod.pipe_spec(spec)
        S = self._pipe_s
        if module._aux_names:
            raise MXNetError(
                'fit(pipeline): auxiliary states %s are not composed '
                'with the pipelined schedule yet'
                % module._aux_names)
        if module._fixed_param_names or module._state_names:
            raise MXNetError('fit(pipeline): fixed_param_names / '
                             'state_names are not supported')
        if len(module._data_names) != 1 or \
                len(module._label_names) != 1:
            raise MXNetError(
                'fit(pipeline): exactly one data and one label input '
                'required, got data=%s label=%s'
                % (module._data_names, module._label_names))
        kv = module._kvstore
        if kv is not None and \
                getattr(kv, 'type', '').startswith('dist'):
            raise MXNetError(
                'fit(pipeline): kvstore %r is not composed with the '
                'pipelined mode — the pipelined dispatch reduces '
                'gradients only over its own mesh dp axis, so '
                'cross-host sync would be silently skipped'
                % kv.type)
        opt = module._optimizer
        if type(opt) not in (opt_mod.SGD, opt_mod.NAG):
            raise MXNetError(
                'fit(pipeline): only plain SGD/NAG compose with the '
                'pipelined fused update, got %s' % type(opt).__name__)
        if getattr(opt, 'multi_precision', False):
            raise MXNetError('fit(pipeline): multi_precision is not '
                             'composed with the pipelined update yet')
        ctxs = list(module._context)
        if len(ctxs) < S or len(ctxs) % S:
            raise MXNetError(
                'fit(pipeline=(%d, %d)): %d contexts do not divide '
                'into %d pipeline stages'
                % (S, self._pipe_m, len(ctxs), S))
        devices = [c.jax_device() for c in ctxs]
        if len(set(devices)) != len(devices):
            raise MXNetError('duplicate devices in the module '
                             'contexts: %s' % (ctxs,))
        self._mesh = pipe_mod.make_pipe_mesh(devices, S)
        self._dp = int(self._mesh.shape['data'])

        arg_params = module._arg_params
        pshapes = {n: (tuple(a.shape), str(np.dtype(a.dtype)))
                   for n, a in arg_params.items()}
        stem, stages, head = _partition_spine(
            module._symbol, S, module._data_names,
            module._label_names, module._param_names, pshapes)
        pset = set(module._param_names)
        self._stem_nodes, self._stage_nodes, self._head_nodes = \
            stem, stages, head
        self._label_set = set(module._label_names)
        self._out_idx = module._symbol._outputs[0][1]
        self._stage_pnames = [_run_params(ns, pset) for ns in stages]
        n_leaf = len(self._stage_pnames[0])
        for s, pl in enumerate(self._stage_pnames):
            if len(pl) != n_leaf:
                raise MXNetError(
                    'pipeline stage %d consumes %d parameters, stage '
                    '0 consumes %d' % (s, len(pl), n_leaf))
        self._stem_pnames = _run_params(stem, pset)
        self._head_pnames = _run_params(head, pset)
        covered = ({n for pl in self._stage_pnames for n in pl} |
                   set(self._stem_pnames) | set(self._head_pnames))
        missing = [n for n in module._param_names if n not in covered]
        if missing:
            raise MXNetError(
                'fit(pipeline): parameters %s are not consumed by the '
                'symbol chain' % missing)
        # leaf order [stage-groups..., stem..., head...] — the engine
        # and the lr/wd schedule rows share it
        self._group_names = (
            [[self._stage_pnames[s][j] for s in range(S)]
             for j in range(n_leaf)] +
            [[n] for n in self._stem_pnames] +
            [[n] for n in self._head_pnames])
        pidx = {n: i for i, n in enumerate(module._param_names)}
        self._group_pidx = [[pidx[n] for n in g]
                            for g in self._group_names]

        # placement: stage leaves stack (S, ...) sharded P('pipe')
        # (stack_stage_params/place_pipeline_params), stem/head
        # replicate
        host = lambda n: arg_params[n]._data
        per_stage = [[host(n) for n in pl] for pl in self._stage_pnames]
        stacked = pipe_mod.stack_stage_params(per_stage)
        self._stage_ws = pipe_mod.place_pipeline_params(
            stacked, self._mesh)
        repl = pmesh.replicated(self._mesh)
        self._stem_ws = [jax.device_put(host(n), repl)
                         for n in self._stem_pnames]
        self._head_ws = [jax.device_put(host(n), repl)
                         for n in self._head_pnames]
        self._rng = jax.device_put(_random.next_key(), repl)

        local_shapes = ([tuple(w.shape[1:]) for w in self._stage_ws] +
                        [tuple(w.shape) for w in
                         self._stem_ws + self._head_ws])
        local_dts = [np.dtype(w.dtype) for w in
                     self._stage_ws + self._stem_ws + self._head_ws]
        self._zero = zero_mod.zero_stage(zero)
        self._layout = zero_mod.ZeroBucketLayout(
            local_shapes, local_dts, [False] * len(local_dts),
            self._dp) if self._zero else None
        self._opt = self._init_opt_state()
        self._programs = {}
        self._homog_checked = False
        self._synced = True

    # -- state -------------------------------------------------------------
    def _init_opt_state(self):
        return pipe_mod.init_pipe_opt_state(
            self._mesh, self._layout, self._pipe_s, self._stage_ws,
            self._stem_ws, self._head_ws)

    def state_accounting(self):
        """(param_bytes, opt_state_bytes) resident PER DEVICE — one
        shared model, parallel/pipeline.pipe_residency."""
        shapes = ([tuple(w.shape[1:]) for w in self._stage_ws] +
                  [tuple(w.shape)
                   for w in self._stem_ws + self._head_ws])
        dts = [np.dtype(w.dtype) for w in
               self._stage_ws + self._stem_ws + self._head_ws]
        return pipe_mod.pipe_residency(shapes, dts, self._layout)

    # -- traced bodies -----------------------------------------------------
    def _make_fns(self):
        stem_nodes, stem_pnames = self._stem_nodes, self._stem_pnames
        stage0, stage0_pnames = self._stage_nodes[0], \
            self._stage_pnames[0]
        head_nodes, head_pnames = self._head_nodes, self._head_pnames
        label_set, out_idx = self._label_set, self._out_idx

        def stem_fn(ws, mb, rng):
            if not stem_nodes:
                return mb
            return _eval_nodes(stem_nodes, stem_pnames, ws, mb, rng)

        def stage_fn(ws, act, rng):
            return _eval_nodes(stage0, stage0_pnames, ws, act, rng)

        def head_fn(ws, acts, label, rng):
            out = _eval_nodes(head_nodes, head_pnames, ws, acts, rng,
                              label=label, label_set=label_set,
                              out_idx=out_idx)
            # ones-head == reference backward: loss ops' custom VJPs
            # ignore the head gradient (executor._default_head_grads)
            total = jnp.sum(out).astype(jnp.float32)
            return (out,), total

        return stem_fn, stage_fn, head_fn

    def _check_homogeneity(self, act_sds, rng_sds):
        """Traced-jaxpr stage equality (segment-signature equality is
        necessary, not sufficient) — one shared check,
        parallel/pipeline.check_stage_homogeneity."""
        if self._homog_checked:
            return
        sds = [jax.ShapeDtypeStruct(w.shape[1:], w.dtype)
               for w in self._stage_ws]

        def trace(nodes, pnames):
            def fn(ws, x, k, _n=nodes, _p=pnames):
                return _eval_nodes(_n, _p, ws, x, k)
            return (fn, sds, act_sds, rng_sds)

        pipe_mod.check_stage_homogeneity(
            [trace(n, p) for n, p in zip(self._stage_nodes,
                                         self._stage_pnames)],
            lambda s: MXNetError(
                'fit(pipeline): stage %d traces a different '
                'computation than stage 0 — pipeline stages must '
                'be architecturally identical (same ops, '
                'hyperparams and shapes)' % s))
        self._homog_checked = True

    # -- schedules ---------------------------------------------------------
    def _hyper(self):
        opt = self._mod._optimizer
        clip = opt.clip_gradient
        return {'momentum': float(opt.momentum),
                'rescale': float(opt.rescale_grad),
                'clip': None if clip is None else float(clip),
                'nesterov': isinstance(opt, opt_mod.NAG)}

    def _schedules(self, k):
        """(k, n_leaf) float32 lr/wd rows in leaf order — one shared
        builder, parallel/pipeline.grouped_schedule_rows."""
        return pipe_mod.grouped_schedule_rows(
            self._mod._optimizer, len(self._mod._param_names),
            self._group_pidx, k,
            lambda lrs, wds: MXNetError(
                'fit(pipeline): stage parameters of one stacked '
                'group have diverging lr/wd (%s / %s) — per-stage '
                'lr_mult does not compose with stacked stages'
                % (lrs, wds)))

    # -- programs ----------------------------------------------------------
    def _step_key(self, hyper):
        return ('module_pipe', self._pipe_s, self._pipe_m, self._zero,
                self._layout.key if self._layout is not None else None,
                tuple(sorted(hyper.items())))

    def _placement_fp(self):
        return ('pipemesh', self._pipe_s,
                ) + pmesh.mesh_fingerprint(self._mesh)

    def _get_program(self, hyper, bulk, k, pargs):
        stem_fn, stage_fn, head_fn = self._make_fns()
        data = pargs[5]
        b_local = data.shape[1 if bulk else 0] // self._dp
        mb_sds = jax.ShapeDtypeStruct(
            (b_local // self._pipe_m,) + tuple(
                data.shape[2 if bulk else 1:]),
            np.dtype(data.dtype))
        key_sds = jax.ShapeDtypeStruct(self._rng.shape,
                                       self._rng.dtype)
        if self._stem_nodes:
            stem_sds = [jax.ShapeDtypeStruct(w.shape, w.dtype)
                        for w in self._stem_ws]
            act_sds = jax.eval_shape(stem_fn, stem_sds, mb_sds,
                                     key_sds)
        else:
            act_sds = mb_sds
        self._check_homogeneity(act_sds, key_sds)
        step_fn = pipe_mod.make_pipe_step_fn(
            self._mesh, self._pipe_s, self._pipe_m, stem_fn, stage_fn,
            head_fn, hyper, layout=self._layout, bulk=bulk)
        return pipe_mod.resolve_pipe_program(
            step_fn, pargs, self._step_key(hyper),
            'module_pipe_bulk' if bulk else 'module_pipe_step', k,
            self._placement_fp())

    # -- execution ---------------------------------------------------------
    @staticmethod
    def _in(v):
        return v._data if isinstance(v, nd.NDArray) else jnp.asarray(v)

    def dispatch(self, group):
        """Run one dispatch over a group of DataBatch: K=1 single
        step, K>1 bulk lax.scan.  Returns the last stage's outputs
        ((B, ...) or (K, B, ...)) for host metric updates."""
        k = len(group)
        bulk = k > 1
        for b in group:
            if len(b.data) != 1 or not b.label or len(b.label) != 1:
                raise MXNetError(
                    'fit(pipeline): each batch must carry exactly one '
                    'data and one label array')
        if bulk:
            data = jnp.stack([self._in(b.data[0]) for b in group])
            label = jnp.stack([self._in(b.label[0]) for b in group])
        else:
            data = self._in(group[0].data[0])
            label = self._in(group[0].label[0])
        B = int(data.shape[1 if bulk else 0])
        S, M, dp = self._pipe_s, self._pipe_m, self._dp
        if B % (dp * M):
            raise MXNetError(
                'fit(pipeline=(%d, %d)): batch %d must divide by '
                'dp*num_micro = %d' % (S, M, B, dp * M))
        hyper = self._hyper()
        lr_rows, wd_rows = self._schedules(k)
        repl = pmesh.replicated(self._mesh)
        if bulk:
            lrs = jax.device_put(jnp.asarray(lr_rows), repl)
            wds = jax.device_put(jnp.asarray(wd_rows), repl)
        else:
            lrs = [float(v) for v in lr_rows[0]]
            wds = [float(v) for v in wd_rows[0]]
        data = pmesh.shard_batch(self._mesh, data,
                                 dim=1 if bulk else 0)
        label = pmesh.shard_batch(self._mesh, label,
                                  dim=1 if bulk else 0)
        shapes = ((tuple(data.shape), str(data.dtype)),
                  (tuple(label.shape), str(label.dtype)))
        local = ('bulk' if bulk else 'step', k, shapes,
                 self._step_key(hyper))
        pargs = (self._stage_ws, self._stem_ws, self._head_ws,
                 self._opt, self._rng, data, label, lrs, wds)
        prog = self._programs.get(local)
        if prog is None:
            prog = self._get_program(hyper, bulk, k, pargs)
            self._programs[local] = prog
        with profiler.scope('module_pipe_%s'
                            % ('bulk' if bulk else 'step'),
                            'fused_step'):
            (leaves, self._stage_ws, self._stem_ws, self._head_ws,
             self._opt, self._rng) = prog(*pargs)
            if profiler.is_running():
                jax.block_until_ready(leaves)
        self._synced = False
        self._mod._params_dirty = True
        self._note_counters(k)
        return leaves[0]

    def _note_counters(self, k):
        param_b, state_b = self.state_accounting()
        pipe_mod.note_pipe_counters(
            self._pipe_s, self._pipe_m, k, self._layout, self._dp,
            param_b, state_b)

    def sync_to_module(self):
        """Write the trained weights back into the module's host
        params (and its executor, so score/predict/save see them)."""
        if self._synced:
            return
        mod = self._mod
        for j, pl in enumerate(zip(*self._stage_pnames)):
            rows = np.asarray(self._stage_ws[j])
            for s, name in enumerate(pl):
                nd.array(rows[s]).copyto(mod._arg_params[name])
        for names, ws in ((self._stem_pnames, self._stem_ws),
                          (self._head_pnames, self._head_ws)):
            for name, w in zip(names, ws):
                nd.array(np.asarray(w)).copyto(mod._arg_params[name])
        mod._exec_group.set_params(mod._arg_params, mod._aux_params)
        mod._params_dirty = False
        self._synced = True


# ---------------------------------------------------------------------------
# the fit loop
# ---------------------------------------------------------------------------

def fit_pipeline(module, train_data, spec, eval_data, eval_metric,
                 validation_metric, epoch_end_callback,
                 batch_end_callback, eval_end_callback,
                 eval_batch_end_callback, begin_epoch, num_epoch,
                 bulk):
    """The pipelined epoch loop behind Module.fit(pipeline=...):
    batches group into fit(bulk=K) dispatches (K=1 without bulk), the
    metric updates host-side from each dispatch's returned last-stage
    outputs, and the trained weights sync back into the module at
    every epoch boundary (so epoch callbacks / validation / get_params
    see them)."""
    from .base_module import BatchEndParam, _as_list, _fire
    trainer = ModulePipeTrainer(module, spec)
    k_bulk = int(bulk) if bulk is not None and int(bulk) > 1 else 1
    ctx0 = module._context[0]
    for epoch in range(begin_epoch, num_epoch):
        tic = time.time()
        eval_metric.reset()
        state = {'nbatch': 0}
        group = []

        def flush():
            if not group:
                return
            outs = trainer.dispatch(group)
            for i, b in enumerate(group):
                pred = outs[i] if len(group) > 1 else outs
                eval_metric.update(b.label,
                                   [nd.NDArray(pred, ctx0)])
            state['nbatch'] += len(group)
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch,
                                    nbatch=state['nbatch'] - 1,
                                    eval_metric=eval_metric,
                                    locals=locals()))
            del group[:]

        for data_batch in train_data:
            group.append(data_batch)
            if len(group) >= k_bulk:
                flush()
        flush()
        for name, val in eval_metric.get_name_value():
            module.logger.info('Epoch[%d] Train-%s=%f', epoch, name,
                               val)
        module.logger.info('Epoch[%d] Time cost=%.3f', epoch,
                           time.time() - tic)
        trainer.sync_to_module()
        arg_snap, aux_snap = module.get_params()
        if epoch_end_callback is not None:
            for callback in _as_list(epoch_end_callback):
                callback(epoch, module.symbol, arg_snap, aux_snap)
        if eval_data:
            for name, val in module.score(
                    eval_data, validation_metric,
                    score_end_callback=eval_end_callback,
                    batch_end_callback=eval_batch_end_callback,
                    epoch=epoch):
                module.logger.info('Epoch[%d] Validation-%s=%f',
                                   epoch, name, val)
        train_data.reset()
    return trainer
