"""Module: symbol + data-parallel execution + optimizer.

Reference: python/mxnet/module/module.py:63 (bind :351, init_optimizer
:461, forward :556, backward :598, update :615, checkpoint :114-173).
The intermediate machinery differs (one sharded executor instead of
per-GPU executors + KVStore push/pull — see executor_group.py), but the
public API and KVStore interplay (update_on_kvstore, optimizer state
save/load) match the reference.
"""
import contextlib
import logging
import weakref

from .. import context as ctx_mod
from .. import initializer as init_mod
from .. import metric as metric_mod
from .. import model as model_mod
from .. import ndarray as nd
from .. import optimizer as opt_mod
from .. import profiler
from ..base import MXNetError
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=('data',),
                 label_names=('softmax_label',), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        if context is None:
            context = ctx_mod.cpu()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + list(state_names or [])
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names, self._label_names = data_names, label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()

        self._arg_params = self._aux_params = None
        self._params_dirty = False

        self._optimizer = self._kvstore = self._updater = None
        self._update_on_kvstore = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = self._label_shapes = None
        # whole-step fusion (fwd+bwd+update in one donated XLA dispatch)
        self._pending_fused = False
        # compiled steps by (form, k, scan_dtype, fkey), and weak
        # references to the executor and updater they were built for
        self._step_programs = {}
        self._step_programs_owner = None

    # -- checkpoint (reference module.py:114-173) -------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = model_mod.load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = '%s-%04d.states' % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._symbol.save('%s-symbol.json' % prefix)
        param_name = '%s-%04d.params' % (prefix, epoch)
        self.save_params(param_name)
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = '%s-%04d.states' % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {('arg:%s' % k): v for k, v in arg_params.items()}
        save_dict.update({('aux:%s' % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        buckets = {'arg': {}, 'aux': {}}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(':')
            if kind not in buckets:
                raise ValueError('Invalid param file ' + fname)
            buckets[kind][name] = value
        self.set_params(buckets['arg'], buckets['aux'])

    # -- properties --------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, o.shape) for n, o in
                zip(self._output_names,
                    self._exec_group.executor.outputs)] \
            if self._exec_group.executor.outputs else None

    # -- parameters --------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def init_params(self, initializer=init_mod.Uniform(0.01),
                    arg_params=None, aux_params=None, allow_missing=False,
                    force_init=False, allow_extra=False):
        """Reference module.py init_params semantics."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, 'call bind before initializing the parameters'
        with profiler.scope('module.init_params', 'setup'):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing, allow_extra)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing, allow_extra):
        """init_params' work.  set_params shares it without the span:
        weights written again later (fit's epoch end) are no set-up."""
        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(arr.shape, dtype=arr.dtype)
                for name, arr in zip(
                    self._param_names, self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(arr.shape, dtype=arr.dtype)
                for name, arr in zip(
                    self._aux_names, self._exec_group.aux_arrays)}

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    if cache_arr.shape != arr.shape:
                        raise MXNetError(
                            'shape mismatch for %s: %s vs %s'
                            % (name, cache_arr.shape, arr.shape))
                    cache_arr.copyto(arr)
            else:
                if not allow_missing:
                    if cache is not None:
                        raise RuntimeError(
                            '%s is not presented' % name)
                if initializer is not None:
                    # `name` is already an InitDesc carrying the
                    # variable's attrs (__init__ dispatch happens inside)
                    initializer(name, arr)

        attrs = self._symbol.attr_dict()
        for name, arr in sorted(self._arg_params.items()):
            desc = init_mod.InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            desc = init_mod.InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, aux_params)
        if not allow_extra:
            self._check_extra_params(arg_params, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def _check_extra_params(self, arg_params, aux_params):
        """allow_extra=False contract (reference module.py init_params):
        provided dictionaries must not carry parameters this module's
        symbol does not know — a typo'd or mismatched checkpoint key
        must fail loudly, not be silently dropped."""
        extra = []
        if arg_params:
            extra += [n for n in arg_params if n not in self._param_names
                      and n not in self._data_names
                      and n not in self._label_names
                      and n not in self._state_names]
        if aux_params:
            extra += [n for n in aux_params if n not in self._aux_names]
        if extra:
            raise MXNetError(
                'set_params/init_params got parameters not in the '
                'symbol (pass allow_extra=True to ignore them): %s'
                % sorted(extra))

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        if not allow_missing:
            assert self.binded, \
                'call bind before initializing the parameters'
            self._init_params(None, arg_params, aux_params, False,
                              allow_extra)
            return
        if not allow_extra:
            self._check_extra_params(arg_params, aux_params)
        self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        """Reference module.py:351."""
        if force_rebind:
            self._exec_group = None
            self.binded = False
            self._pending_fused = False
        if self.binded:
            self.logger.warning('Already binded, ignoring bind()')
            return
        with profiler.scope('module.bind', 'setup'):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        if not for_training:
            assert not inputs_need_grad
        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else []
        shared_group = shared_module._exec_group if shared_module else None
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group=shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names)
        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False, zero=None):
        """Reference module.py:461.

        zero: ZeRO stage for the in-step sharded optimizer update
        (parallel/zero.py) — 1 reduce-scatters gradients over the data
        mesh, updates only the local 1/N shard of momenta / fp32
        masters, and all-gathers the updated params.  None (default)
        defers to the kvstore's `zero_stage` / the MXNET_TPU_ZERO env
        knob."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning('optimizer already initialized, '
                                'ignoring...')
            return
        with profiler.scope('module.init_optimizer', 'setup'):
            self._init_optimizer(kvstore, optimizer, optimizer_params,
                                 zero)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params, zero):
        (kvstore, update_on_kvstore) = model_mod._create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and 'dist' in kvstore.type and \
                '_sync' in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if 'rescale_grad' not in optimizer_params:
                optimizer_params['rescale_grad'] = rescale_grad
            optimizer = opt_mod.create(optimizer, sym=self.symbol,
                                       param_idx2name=idx2name,
                                       **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            # copy initialized params to the store
            model_mod._initialize_kvstore(
                kvstore=kvstore,
                param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params,
                param_names=self._param_names,
                update_on_kvstore=update_on_kvstore)
        from .. import kvstore as kvs_mod
        from ..parallel import zero as zero_mod
        if zero is None and kvstore is not None:
            zero = getattr(kvstore, 'zero_stage', None)
        zero = zero_mod.zero_stage(zero)
        host_span = False
        if kvstore is not None and kvstore._is_dist and \
                not isinstance(kvstore, kvs_mod.KVStoreDistPS):
            from .. import dist
            host_span = dist.host_span_active()
        self._fused_updater = None
        if kvstore is None or \
                (not isinstance(kvstore, kvs_mod.KVStoreDistPS) and
                 not host_span):
            # In-XLA store (or none): the executor group is one SPMD
            # program whose gradient all-reduce is already an in-step
            # psum over the mesh — `dist_sync` without parameter
            # servers under jax.distributed is the SAME program
            # spanning processes — so the optimizer update folds into
            # the same donated dispatch (ZeRO-1 sharded when zero=1).
            # The store stays as the parameter facade; the
            # multi-process PS keeps the per-key eager push/pull path,
            # and the dist-runtime host-allreduce mode
            # (dist.host_span_active) routes through the store so each
            # step's mesh-reduced gradients cross hosts once.
            # sparse_grad Embedding tables take the rows-only update
            # (COO (unique_ids, rows) grads from the fused step —
            # executor._sparse_embed_entries); positions are in the
            # executor's diff order, which is the order the step hands
            # weights to step_math
            ex = self._exec_group.executor
            sparse_idx = () if ex is None or ex._grouped \
                else ex.sparse_diff_positions()
            self._fused_updater = opt_mod.create_fused_updater(
                optimizer, self._param_names, zero=zero,
                mesh=self._exec_group.mesh, sparse_idx=sparse_idx)
        if zero and self._fused_updater is None:
            if isinstance(kvstore, kvs_mod.KVStoreDistPS):
                reason = ('the parameter-server kvstore runs updates '
                          'server-side (per-key, already state-sharded '
                          'across servers)')
            elif host_span:
                reason = ('the dist runtime host-allreduce mode runs '
                          'the per-key kvstore update (ZeRO needs the '
                          'in-step sharded dispatch — use '
                          'MXNET_TPU_DIST_JAX=1 multi-host SPMD)')
            else:
                reason = ('the %s optimizer has no fused sharded '
                          'update path' % type(optimizer).__name__)
            self.logger.warning(
                'ZeRO stage-1 requested but %s; running without the '
                'sharded in-step update', reason)
        if self._fused_updater is not None:
            update_on_kvstore = False
            self._update_on_kvstore = False
        elif update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
            if host_span and hasattr(kvstore, 'mark_sparse'):
                # sparse_grad tables cross hosts as COO (unique_ids,
                # rows) pairs instead of re-densified (vocab, dim)
                # bytes; a config the sparse rewrite refuses just
                # stays on the dense wire
                ex = self._exec_group.executor
                if ex is not None and not ex._grouped:
                    try:
                        entries = ex._sparse_embed_entries()
                    except MXNetError:
                        entries = ()
                    for e in entries:
                        kvstore.mark_sparse(e['weight'], e['vocab'])
        else:
            self._updater = opt_mod.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """Share optimizer state with another module (used by
        BucketingModule; reference module.py borrow_optimizer)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._fused_updater = getattr(shared_module, '_fused_updater', None)
        self.optimizer_initialized = True

    # -- per-batch ---------------------------------------------------------
    def _fusable_step(self):
        """True when the whole train step (fwd+bwd+update) can compile
        into one donated XLA dispatch: a fused updater is active, the
        executor is a single fused XLA module (no ctx groups / monitor),
        no input grads are requested, and every differentiable arg is a
        grad_req='write' parameter the updater owns."""
        if self._fused_updater is None or not self.optimizer_initialized:
            return False
        if self.inputs_need_grad:
            return False
        ex = self._exec_group.executor
        if ex._grouped or ex._monitor_callback is not None:
            return False
        fnames = [n for n, g in zip(self._param_names,
                                    self._exec_group.grad_arrays)
                  if g is not None]
        if ex._diff_names != fnames:
            return False
        return all(ex._grad_req.get(n) == 'write' for n in fnames)

    def _materialize_fused(self):
        """A deferred step is pending but something other than update()
        needs its results: fall back to the plain fwd+bwd execution
        (grads land in grad_dict; update() then takes the two-dispatch
        path — exactly the pre-fusion behavior)."""
        if self._pending_fused:
            self._pending_fused = False
            self._exec_group.forward_backward()

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._materialize_fused()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._materialize_fused()
        self._exec_group.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused fwd+bwd (one XLA execution).  When the whole step can
        fuse (see _fusable_step), execution is deferred to update() so
        forward+backward+optimizer run as ONE donated dispatch; any
        other access (get_outputs, backward, ...) materializes the
        plain fwd+bwd first."""
        assert self.binded and self.params_initialized
        if self._fusable_step():
            self._exec_group.load_data_batch(data_batch)
            self._pending_fused = True
            return
        self._pending_fused = False
        self._exec_group.forward_backward(data_batch)

    def _mesh_fp(self):
        """Device fingerprint of the exec group's mesh (None when
        single-device) — joins cache keys for programs whose closures
        bind the mesh by value."""
        from ..parallel import mesh as pmesh
        return pmesh.mesh_fingerprint(self._exec_group.mesh)

    def _ensure_reduce_plan(self, ex, fu, fnames):
        """The backward-interleaved gradient-reduce plan for the fused
        step (parallel/collectives.GradReducePlan), or None when no
        explicit in-step all-reduce applies (single device, or ZeRO —
        the sharded step_math buckets and reduce-scatters itself).
        Cached: plan construction must stay off the per-step host hot
        path."""
        if self._exec_group.mesh is None or fu.zero:
            return None
        import numpy as np
        # COO sparse-embedding grads never enter the bucketed
        # all-reduce (GSPMD reduces them from the gather/scatter
        # shardings); the plan covers the dense complement, matching
        # the sublist the fused step feeds through grad_reduce
        sp = set(fu.sparse_idx)
        dnames = [n for j, n in enumerate(fnames) if j not in sp]
        shapes = tuple(tuple(ex.arg_dict[n].shape) for n in dnames)
        dtypes = tuple(np.dtype(ex.arg_dict[n].dtype).str
                       for n in dnames)
        if getattr(self, '_reduce_plan_inputs', None) != (shapes,
                                                         dtypes):
            from ..parallel import collectives
            self._reduce_plan = collectives.GradReducePlan(shapes,
                                                           dtypes)
            self._reduce_plan_inputs = (shapes, dtypes)
        return self._reduce_plan

    def _schedule_arrays(self, lrs, wds):
        """The (K, n_params) float32 learning rates and weight decays
        of a dispatch as the compiled step takes them: ONE array each
        — a single transfer regardless of parameter count; the
        per-param split happens in the trace.  Uncommitted on one
        device, replicated over the mesh where there is one.  The
        warm-ups place theirs here too: another kind of array would be
        another signature, compiled at the first real step."""
        import jax
        mesh = self._exec_group.mesh
        repl = None
        if mesh is not None:
            from ..parallel import mesh as pmesh
            repl = pmesh.replicated(mesh)
        return jax.device_put((lrs, wds), repl)

    def _note_step_counters(self, k, metric_steps=0):
        """Feed the profiler's comm/memory counters after k fused
        steps: ZeRO reduce-scatter / all-gather payload bytes,
        per-device optimizer-state residency, and the round-11
        reduce/metric counters (one model,
        profiler.note_reduce_dispatch)."""
        fu = self._fused_updater
        if fu is None:
            return
        rs, ag = fu.comm_bytes_per_step()
        if rs or ag:
            profiler.add_comm_bytes(reduce_scattered=rs * k,
                                    all_gathered=ag * k)
        profiler.set_optimizer_state_bytes(fu.state_bytes_per_device())
        buckets = 0
        if self._exec_group.mesh is not None:
            if fu.zero and fu._layout is not None:
                buckets = len(fu._layout.buckets)
            elif not fu.zero and \
                    getattr(self, '_reduce_plan', None) is not None:
                buckets = self._reduce_plan.n_buckets
        profiler.note_reduce_dispatch(buckets, k,
                                      metric_steps=metric_steps)

    def _fused_program(self, form, k, scan_names, scan_dtype, fold):
        """Build (or fetch) the compiled step of k steps: form
        'single' (k = 1: the bound batch, no loop), 'stacked' (k
        batches stacked on a leading axis and scanned) or 'repeat' (the
        bound batch k times).  Must run AFTER fu.host_prep_steps:
        under ZeRO, fu.cache_key() carries the bucket layout it may
        have just rebuilt.

        The table holds what was built for THIS executor and updater
        (init_optimizer(force_init=True) makes a new FusedSGD whose
        step_math bakes new hyperparams; a stale program would run
        old-layout buckets against new state shapes) and is dropped
        with either; weak references, so that a released module does
        not keep their weights and optimizer state on the device.  The
        reduce plan (bucketing + schedule) and the metric fold bake
        into the traced step, so they join the key — the plan WITH the
        mesh fingerprint: the grad_reduce closure binds a concrete
        mesh, so unlike the mesh-free step body it cannot be retraced
        for a different device set.  (step_key routes the compiled
        step through the process-wide executable cache, so a miss here
        rarely means a recompile.)"""
        eg = self._exec_group
        ex, fu = eg.executor, self._fused_updater
        fnames = ex._diff_names
        owner = self._step_programs_owner
        if owner is None or owner[0]() is not ex or owner[1]() is not fu:
            self._step_programs = {}
            self._step_programs_owner = (weakref.ref(ex), weakref.ref(fu))
        plan = self._ensure_reduce_plan(ex, fu, fnames)
        fkey = (fu.cache_key(),
                (plan.key, self._mesh_fp()) if plan is not None
                else None,
                fold.key if fold is not None else None)
        key = (form, k, str(scan_dtype), fkey)
        program = self._step_programs.get(key)
        if program is None:
            mesh = eg.mesh
            gr = (lambda grads: plan.apply(grads, mesh)) \
                if plan is not None else None
            metric_arg = None
            if fold is not None:
                scan_order = [n for n in ex._arg_names
                              if n in set(scan_names) and
                              n not in set(fnames)]
                label_pos = {n: i for i, n in enumerate(scan_order)
                             if n in eg.label_names}
                out_names = self._symbol.list_outputs()

                def m_update(mc, outs, sv, _lp=label_pos,
                             _on=out_names, _fold=fold):
                    label = {n: sv[i] for n, i in _lp.items()}
                    pred = dict(zip(_on, outs))
                    return _fold.update(mc, label, pred)

                metric_arg = (fold.init, m_update)
            program = self._step_programs[key] = ex.make_fused_multistep(
                fu.step_math, scan_names,
                repeat=(None if form == 'stacked' else k),
                step_key=fkey, grad_reduce=gr, metric=metric_arg)
        return program

    def _step_program(self, form='single', k=1):
        """The compiled step of this form and k that this module
        built last (None when it has built none): what tests and
        chip_smoke.py lower again or compare."""
        for key in reversed(self._step_programs):
            if key[:2] == (form, k):
                return self._step_programs[key]
        return None

    def _dispatch_fused(self, form, k, scan_names=(), scan_stacks=None,
                        scan_dtype=None, fold=None, warm=False):
        """The one driver of the compiled step: k whole training steps
        over the bound batch (form 'single', 'repeat') or over
        scan_stacks ('stacked') as one dispatch.  Spans:
        'module.host_prep' (optimizer state, schedule rows and their
        one put, program lookup), then the executor's
        'executor.dispatch'.  warm=True compiles the same program for
        the same operands on cloned buffers instead
        (executor.warm_fused_multistep): no state, schedule or counter
        changes and no span opens."""
        ex = self._exec_group.executor
        fu = self._fused_updater
        fnames = ex._diff_names
        span = contextlib.nullcontext() if warm else \
            profiler.scope('module.host_prep', 'fused_step')
        with span:
            if fu.param_names != fnames:
                fu.param_names = list(fnames)
            weights = [ex.arg_dict[n] for n in fnames]
            # counts bump and lr/wd evaluate at every step index (host
            # scheduler semantics)
            moms, masters, lrs, wds = fu.host_prep_steps(
                weights, k, advance=not warm)
            lrs, wds = self._schedule_arrays(lrs, wds)
            program = self._fused_program(form, k, scan_names,
                                          scan_dtype, fold)
        if warm:
            ex.warm_fused_multistep(program, fnames, scan_names,
                                    scan_stacks, moms, masters, lrs, wds,
                                    zero=bool(fu.zero))
            return
        new_moms, new_masters, mcarry = ex.run_fused_multistep(
            program, fnames, scan_names, scan_stacks, moms, masters,
            lrs, wds, zero=bool(fu.zero))
        fu.commit(new_moms, new_masters)
        if fold is not None:
            # device scalars queue on the host metric WITHOUT a sync;
            # the first metric.get() drains them
            fold.commit(mcarry)
        self._note_step_counters(
            k, metric_steps=k if fold is not None else 0)

    def warmup_fused(self, bulk=None, eval_metric=None, scan_dtype=None,
                     single=True):
        """AOT-warm this module's fused train program(s): compile the
        single-step whole-train-step program — and, for bulk=K > 1, the
        K-step stacked lax.scan program (with eval_metric's device fold
        baked in when it has one) — by executing them on CLONED buffers
        through executor.warm_fused_multistep.  No parameter, aux,
        optimizer-state, or lr-schedule state changes.  The compiled
        programs land in the process-wide exec_cache under the graph
        signature + updater key, so an equivalent re-created module
        re-warms entirely from cache (zero new XLA compiles).

        Returns True when the step can fuse (False → nothing warmed:
        ctx-group executors, monitors, or a non-fusable optimizer run
        the legacy multi-dispatch path, which compiles lazily).
        single=False skips the single-step warm (caller knows it is
        already warm and only wants the bulk program)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if not self._fusable_step():
            return False
        if single:
            self._dispatch_fused('single', 1, warm=True)
        if bulk is None or int(bulk) <= 1:
            return True
        import jax
        import jax.numpy as jnp
        k = int(bulk)
        eg = self._exec_group
        ex = eg.executor
        fold = metric_mod.device_fold(eval_metric) \
            if eval_metric is not None else None
        scan_names = self._scan_names()
        data_set = set(eg.data_names)
        scan_stacks = {}
        for n in scan_names:
            bound = ex.arg_dict[n]._data
            store = scan_dtype if (scan_dtype is not None and
                                   n in data_set) else bound.dtype
            scan_stacks[n] = jnp.zeros((k,) + tuple(bound.shape), store)
        if eg.mesh is not None:
            from ..parallel import mesh as pmesh
            scan_stacks = {n: pmesh.shard_batch(eg.mesh, v, dim=1)
                           for n, v in scan_stacks.items()}
        else:
            # real batches arrive committed (nd.array device_puts);
            # the warm stacks must carry the same placement flavor or
            # the first real bulk dispatch compiles a third signature
            dev = self._context[0].jax_device()
            scan_stacks = {n: jax.device_put(v, dev)
                           for n, v in scan_stacks.items()}
        self._dispatch_fused('stacked', k, scan_names, scan_stacks,
                             scan_dtype, fold, warm=True)
        return True

    def bulk_step(self, batches=None, batch=None, repeat=None,
                  scan_dtype=None, eval_metric=None):
        """Run several full training steps (forward+backward+optimizer
        update) as ONE XLA dispatch, looping on-device.

        TPU-native counterpart of the reference's bulk-exec segments
        (MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN, graph_executor.cc:1135):
        amortizes host dispatch latency over K steps — essential when
        the accelerator sits behind a high-latency link.  Either pass
        `batches` (list of DataBatch; stacked on a leading axis and
        scanned) or `batch` + `repeat=K` (the one batch is reused K
        times — synthetic/steady-state benchmarking).

        lr/wd schedules evaluate at EVERY step index of the dispatch
        (per-step schedule columns scanned alongside the batches), so
        a FactorScheduler boundary crossed mid-dispatch decays at the
        right step — bit-identical to the per-step loop.

        eval_metric: optional EvalMetric with a device fold
        (metric.device_fold) — its accumulation then runs INSIDE the
        scan from each step's outputs and labels, and ONE queued
        device-scalar pair per dispatch reaches the host metric
        (no sync until metric.get()).  This is what lets `fit(bulk=K)`
        stretch steps_per_dispatch across metric/logging boundaries.
        Metrics without a device fold raise — use the per-step loop.

        Remaining caveats vs the per-step loop: only the final step's
        outputs are kept (get_outputs), and monitors don't fire.
        Falls back to the plain loop when the step cannot fuse.

        scan_dtype: optional storage dtype for the stacked DATA arrays
        (labels keep their bound dtype — low-precision floats can't
        represent large class indices exactly).  The fused step casts
        back to the bound dtype before the graph runs, so this is
        value-preserving exactly when the graph's first use of the data
        is itself a cast to (or below) scan_dtype — e.g. a bfloat16
        mixed-precision model — and halves the device memory the K
        stacked batches occupy, allowing larger K.
        """
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if batches is not None:
            k = len(batches)
        else:
            assert batch is not None and repeat is not None
            k = repeat
        if k == 0:
            return
        if not self._fusable_step():
            for b in (batches if batches is not None
                      else [batch] * repeat):
                self.forward_backward(b)
                self.update()
                if eval_metric is not None:
                    self.update_metric(eval_metric, b.label)
            return
        with profiler.scope('module.bulk_step', 'fused_step'):
            return self._bulk_step_fused(k, batches, batch, scan_dtype,
                                         eval_metric)

    def _bulk_step_fused(self, k, batches, batch, scan_dtype,
                         eval_metric):
        """bulk_step's fused path.  Spans inside 'module.bulk_step':
        'module.bulk_stack' (the K batches cast and stacked on the
        device), then the driver's (_dispatch_fused)."""
        self._materialize_fused()
        import jax.numpy as jnp
        eg = self._exec_group
        ex = eg.executor
        fold = None
        if eval_metric is not None:
            fold = metric_mod.device_fold(eval_metric)
            if fold is None:
                raise ValueError(
                    'bulk_step: metric %r has no device fold (see '
                    'metric.device_fold); run the per-step loop for '
                    'host-only metrics'
                    % (getattr(eval_metric, 'name', eval_metric),))
        scan_names = self._scan_names()
        if scan_dtype is not None:
            self._check_scan_dtype_holds_ids(ex, scan_dtype)
        scan_stacks = None
        if batches is not None:
            if k == 1:
                ret = self._single_step(batches[0])
                if eval_metric is not None:
                    self.update_metric(eval_metric, batches[0].label)
                return ret
            eg.load_data_batch(batches[0])  # dtype/shape checks + cast
            with profiler.scope('module.bulk_stack', 'fused_step'):
                data_set = set(eg.data_names)
                per_name = {n: [] for n in scan_names}
                for b in batches:
                    vals = dict(zip(eg.data_names, b.data))
                    if eg.label_names and b.label:
                        vals.update(zip(eg.label_names, b.label))
                    for n in scan_names:
                        v = vals[n]
                        v = v._data if isinstance(v, nd.NDArray) else \
                            jnp.asarray(v)
                        store = scan_dtype \
                            if (scan_dtype is not None and
                                n in data_set) else ex.arg_dict[n].dtype
                        per_name[n].append(v.astype(store))
                scan_stacks = {n: jnp.stack(per_name[n])
                               for n in scan_names}
                if eg.mesh is not None:
                    from ..parallel import mesh as pmesh
                    scan_stacks = {
                        n: pmesh.shard_batch(eg.mesh, v, dim=1)
                        for n, v in scan_stacks.items()}
        else:
            eg.load_data_batch(batch)
        self._dispatch_fused(
            'stacked' if batches is not None else 'repeat', k,
            scan_names, scan_stacks, scan_dtype, fold)
        self._params_dirty = True

    def _scan_names(self):
        """The data and label inputs a K-step program is fed step by
        step."""
        eg = self._exec_group
        ex = eg.executor
        diff_set = set(ex._diff_names)
        return [n for n in eg.data_names + eg.label_names
                if n in ex.arg_dict and n not in diff_set]

    def _check_scan_dtype_holds_ids(self, ex, scan_dtype):
        """Token ids are carried as floats: a data input that feeds an
        Embedding cannot be staged in a type with fewer mantissa bits
        than its bound type (bfloat16 is exact up to 256 only) without
        training on other ids than were given."""
        import jax.numpy as jnp

        def exact_bits(dtype):
            dtype = jnp.dtype(dtype)
            if jnp.issubdtype(dtype, jnp.floating):
                return jnp.finfo(dtype).nmant + 1
            return jnp.iinfo(dtype).bits

        data_names = tuple(self._exec_group.data_names)
        cached = getattr(self, '_embedding_id_inputs', None)
        if cached is None or cached[0] != data_names:
            # once a binding: bulk_step calls this at every dispatch
            cached = self._embedding_id_inputs = (data_names, [
                (node.inputs[0][0].name, node.name)
                for node in self._symbol._topo()
                if node.op is not None and node.op.name == 'Embedding'
                and node.inputs[0][0].op is None
                and node.inputs[0][0].name in data_names])
        for data_name, node_name in cached[1]:
            bound = ex.arg_dict[data_name].dtype
            if exact_bits(scan_dtype) < exact_bits(bound):
                raise MXNetError(
                    'bulk_step: scan_dtype %s is narrower than %s, the '
                    'bound type of %r, which feeds Embedding %r: ids '
                    'above %d are not exact in it; stage the ids in %s'
                    % (jnp.dtype(scan_dtype).name, jnp.dtype(bound).name,
                       data_name, node_name, 2 ** exact_bits(scan_dtype),
                       jnp.dtype(bound).name))

    def _single_step(self, data_batch):
        self.forward_backward(data_batch)
        self.update()

    def _fit_pipeline(self, train_data, spec, eval_data, eval_metric,
                      validation_metric, epoch_end_callback,
                      batch_end_callback, eval_end_callback,
                      eval_batch_end_callback, begin_epoch, num_epoch,
                      bulk):
        """fit(pipeline=(S, M)): the dp×pipe GPipe training mode —
        symbol chain partitioned into stages, fill-drain microbatch
        schedule + gradient reduction + SGD/NAG update as ONE donated
        XLA dispatch per step group (module/pipeline_fit.py)."""
        from .pipeline_fit import fit_pipeline
        return fit_pipeline(
            self, train_data, spec, eval_data, eval_metric,
            validation_metric, epoch_end_callback, batch_end_callback,
            eval_end_callback, eval_batch_end_callback, begin_epoch,
            num_epoch, bulk)

    def update(self):
        """Reference module.py:615."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._pending_fused:
            self._pending_fused = False
            self._dispatch_fused('single', 1)
            return
        if self._fused_updater is not None:
            weights, grads = [], []
            fnames = []
            for n, w, g in zip(self._param_names,
                               self._exec_group.param_arrays,
                               self._exec_group.grad_arrays):
                if g is not None:
                    fnames.append(n)
                    weights.append(w)
                    grads.append(g)
            if self._fused_updater.param_names != fnames:
                self._fused_updater.param_names = fnames
            self._fused_updater(weights, grads)
            self._note_step_counters(1)
            return
        if self._update_on_kvstore:
            model_mod._update_params_on_kvstore(
                self._exec_group.param_arrays,
                self._exec_group.grad_arrays,
                self._kvstore, self._param_names)
        else:
            model_mod._update_params(
                self._exec_group.param_arrays,
                self._exec_group.grad_arrays,
                updater=self._updater,
                num_device=len(self._context),
                kvstore=self._kvstore,
                param_names=self._param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        self._materialize_fused()
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        self._materialize_fused()
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._materialize_fused()
        self._exec_group.update_metric(eval_metric, labels)

    def metric_snapshot(self, labels):
        """Capture this step's (labels, prediction futures) for a
        DEFERRED metric fold (fit's overlapped train loop): the
        executor reassigns `.outputs` to fresh NDArrays on every
        dispatch and in-place NDArray writes swap the underlying
        buffer rather than mutate it, so the captured refs keep this
        step's exact values while later steps enqueue — folding them
        after N more dispatches reads bit-identical data to a
        synchronous update_metric, without the per-step host sync.
        Returns (labels_dict, preds_dict) for
        `eval_metric.update_dict`, or None when a deferred fused step
        is still pending (its outputs do not exist yet) — callers
        fall back to the synchronous path."""
        if self._pending_fused:
            return None
        eg = self._exec_group
        outs = eg.executor.outputs
        if not outs:
            return None
        preds = dict(zip(self._symbol.list_outputs(), list(outs)))
        if isinstance(labels, (list, tuple)):
            labels = dict(zip(eg.label_names, list(labels)))
        return labels, preds

    # -- optimizer states --------------------------------------------------
    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from ..base import atomic_file
            updater = self._fused_updater or self._updater
            with atomic_file(fname) as fout:
                fout.write(updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            updater = self._fused_updater or self._updater
            with open(fname, 'rb') as fin:
                updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        self._exec_group.install_monitor(mon)

    def _wrap_train_iter(self, train_data):
        """fit() input pipeline: turn on the parallel host decode pool
        for image iterators that were left at their default worker
        count (MXNET_TPU_DECODE_WORKERS), then stage upcoming batches
        device-resident (io.prefetch_to_device) so the host→device
        copy of batch N+1 overlaps step N's compute.  MXNET_TPU_PREFETCH
        sets the buffer depth (default 2; 0 disables)."""
        import os
        from .. import io as mxio
        from ..image.image import decode_workers_from_env
        workers = decode_workers_from_env()
        if workers >= 2 and \
                getattr(train_data, '_workers_explicit', None) is False:
            # an env set after the iterator was constructed still takes
            # effect; an explicit preprocess_threads=N always wins
            train_data.set_preprocess_threads(workers)
        try:
            depth = int(os.environ.get('MXNET_TPU_PREFETCH', '2'))
        except ValueError:
            depth = 2
        if depth <= 0 or \
                isinstance(train_data, mxio.PrefetchToDeviceIter) or \
                not self.binded:
            return train_data
        eg = self._exec_group
        device = None if eg.mesh is not None \
            else self._context[0].jax_device()
        return mxio.prefetch_to_device(train_data, size=depth,
                                       device=device, mesh=eg.mesh)

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._pending_fused = False  # bound buffers are replaced
        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else []
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
