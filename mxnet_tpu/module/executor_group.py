"""DataParallelExecutorGroup: multi-device execution of one symbol.

Reference: python/mxnet/module/executor_group.py:99 — there, the batch
is sliced in Python (decide_slices :233) across one executor per GPU,
and gradients meet again in the KVStore.  TPU-native redesign: ONE
executor compiled over the whole batch; when several contexts are bound,
their devices form a 1-D 'data' mesh and the batch arrays are placed
batch-sharded over it, so XLA partitions the single compiled step (SPMD)
and inserts the gradient all-reduce over ICI — the Python slicing loop,
per-device executors, and CommDevice reduction all collapse into the
compiled program.
"""
import numpy as np
import jax

from .. import ndarray as nd
from .. import profiler
from ..base import MXNetError
from ..executor import Executor
from ..parallel import mesh as pmesh


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=None, fixed_param_names=None,
                 grad_req='write', state_names=None):
        self.symbol = symbol
        self.contexts = contexts
        if workload:
            decide_slices(0, workload)  # reject non-uniform workloads
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.logger = logger
        self.data_shapes = list(data_shapes)
        self.label_shapes = list(label_shapes) if label_shapes else []
        self.data_names = [d[0] if isinstance(d, (list, tuple)) else d.name
                           for d in self.data_shapes]
        self.label_names = [l[0] if isinstance(l, (list, tuple)) else l.name
                            for l in self.label_shapes]
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.batch_size = (self.data_shapes[0][1]
                           if isinstance(self.data_shapes[0], (list, tuple))
                           else self.data_shapes[0].shape)[0]

        # -- device mesh ('data' axis) over the bound contexts ------------
        self.mesh = None
        if len(contexts) > 1:
            devices = [c.jax_device() for c in contexts]
            if len(set(devices)) != len(devices):
                raise MXNetError('duplicate devices in context list')
            if self.batch_size % len(devices) != 0:
                raise MXNetError(
                    'batch size %d not divisible by %d devices'
                    % (self.batch_size, len(devices)))
            self.mesh = pmesh.make_mesh(devices=devices)

        # -- grad req ------------------------------------------------------
        input_names = set(self.data_names) | set(self.label_names)
        req = {}
        for name in self.arg_names:
            if name in self.fixed_param_names:
                req[name] = 'null'
            elif name in input_names:
                req[name] = grad_req if (
                    inputs_need_grad and name in self.data_names) else 'null'
            elif not for_training:
                req[name] = 'null'
            else:
                req[name] = grad_req
        self.grad_req = req

        shapes = {}
        for d in self.data_shapes + self.label_shapes:
            name, shape = (d[0], d[1]) if isinstance(d, (list, tuple)) else \
                (d.name, d.shape)
            shapes[name] = shape
        shared_exec = shared_group.executor if shared_group is not None \
            else None
        ctx = contexts[0]
        self.executor = Executor._simple_bind(
            symbol, ctx, grad_req=req, shared_exec=shared_exec,
            shape_kwargs=shapes)
        if self.mesh is not None:
            self._apply_shardings()

    # ------------------------------------------------------------------
    def _apply_shardings(self):
        """Place params replicated and inputs batch-sharded on the mesh."""
        input_names = set(self.data_names) | set(self.label_names)
        repl = pmesh.replicated(self.mesh)
        for name, arr in self.executor.arg_dict.items():
            if name in input_names:
                arr._data = pmesh.shard_batch(self.mesh, arr._data)
            else:
                arr._data = jax.device_put(arr._data, repl)
        for arr in self.executor.aux_dict.values():
            arr._data = jax.device_put(arr._data, repl)
        for arr in self.executor.grad_dict.values():
            arr._data = jax.device_put(arr._data, repl)

    def _commit_params(self):
        """One device: commit weights and aux states to it, as the
        compiled step's donated outputs are.  copy_params_from leaves
        them uncommitted, which gives the first step another jit
        signature than the second: the whole program compiled twice."""
        dev = self.contexts[0].jax_device()
        input_names = set(self.data_names) | set(self.label_names)
        for name, arr in self.executor.arg_dict.items():
            if name not in input_names:
                arr._data = jax.device_put(arr._data, dev)
        for arr in self.executor.aux_dict.values():
            arr._data = jax.device_put(arr._data, dev)

    def _place_input(self, name, value):
        dst = self.executor.arg_dict[name]
        data = value._data if isinstance(value, nd.NDArray) else \
            jax.numpy.asarray(value)
        if data.shape != dst.shape:
            raise MXNetError('input %s shape %s != bound %s'
                             % (name, data.shape, dst.shape))
        data = data.astype(dst.dtype)
        if self.mesh is not None:
            data = pmesh.shard_batch(self.mesh, data)
        else:
            # batches commonly arrive from host-side iterators on cpu(0);
            # commit them to the executor's device (the reference's
            # _load_general does the cross-device copy the same way,
            # executor_group.py:31-73)
            data = jax.device_put(data, self.contexts[0].jax_device())
        dst._data = data

    def load_data_batch(self, data_batch):
        """The reference's _load_data/_load_label slicing loop
        (executor_group.py:388) becomes sharded placement."""
        with profiler.scope('module.load_batch', 'fused_step'):
            for name, value in zip(self.data_names, data_batch.data):
                self._place_input(name, value)
            if self.label_names and data_batch.label:
                for name, value in zip(self.label_names,
                                       data_batch.label):
                    self._place_input(name, value)

    # ------------------------------------------------------------------
    def forward(self, data_batch=None, is_train=None):
        if data_batch is not None:
            self.load_data_batch(data_batch)
        if is_train is None:
            is_train = self.for_training
        return self.executor.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, 're-bind with for_training=True'
        self.executor.backward(out_grads=out_grads)

    def forward_backward(self, data_batch=None):
        """Fused step: one XLA execution for fwd+bwd."""
        if data_batch is not None:
            self.load_data_batch(data_batch)
        return self.executor.forward_backward()

    def get_outputs(self, merge_multi_context=True):
        return self.executor.outputs

    def get_input_grads(self, merge_multi_context=True):
        return [self.executor.grad_dict.get(n) for n in self.data_names]

    def get_params(self, arg_params, aux_params):
        for name in self.param_names:
            if name in self.executor.arg_dict:
                arg_params[name] = self.executor.arg_dict[name].copy()
        for name in self.aux_names:
            aux_params[name] = self.executor.aux_dict[name].copy()

    def set_params(self, arg_params, aux_params, allow_extra=False):
        self.executor.copy_params_from(
            {k: v for k, v in arg_params.items()
             if k in self.executor.arg_dict},
            {k: v for k, v in (aux_params or {}).items()
             if k in self.executor.aux_dict})
        if self.mesh is not None:
            self._apply_shardings()
        else:
            self._commit_params()

    def reshape(self, data_shapes, label_shapes=None):
        """Rebind to new input shapes (reference executor_group.py reshape):
        refreshes batch_size and re-applies mesh shardings so gradient
        rescaling and device placement stay consistent."""
        self.data_shapes = list(data_shapes)
        self.label_shapes = list(label_shapes) if label_shapes else []
        self.batch_size = (self.data_shapes[0][1]
                           if isinstance(self.data_shapes[0], (list, tuple))
                           else self.data_shapes[0].shape)[0]
        if self.mesh is not None and \
                self.batch_size % len(self.contexts) != 0:
            raise MXNetError(
                'batch size %d not divisible by %d devices'
                % (self.batch_size, len(self.contexts)))
        shapes = {}
        for d in self.data_shapes + self.label_shapes:
            name, shape = (d[0], d[1]) if isinstance(d, (list, tuple)) else \
                (d.name, d.shape)
            shapes[name] = shape
        self.executor = self.executor.reshape(**shapes)
        if self.mesh is not None:
            self._apply_shardings()

    @property
    def param_arrays(self):
        return [self.executor.arg_dict[n] for n in self.param_names]

    @property
    def grad_arrays(self):
        return [self.executor.grad_dict.get(n) for n in self.param_names]

    @property
    def aux_arrays(self):
        return [self.executor.aux_dict[n] for n in self.aux_names]

    def update_metric(self, eval_metric, labels):
        preds = dict(zip(self.symbol.list_outputs(), self.executor.outputs))
        if isinstance(labels, (list, tuple)):
            labels = dict(zip(self.label_names, labels))
        eval_metric.update_dict(labels, preds)

    def install_monitor(self, mon):
        self.executor.set_monitor_callback(mon.stat_helper)


def decide_slices(batch_size, work_load_list):
    """Reference executor_group.py:233.  The TPU build shards the batch
    evenly over the mesh (SPMD partitioning needs identical per-device
    shapes), so a non-uniform work_load_list cannot be honored — raise
    instead of silently ignoring it."""
    n = len(work_load_list)
    if len(set(work_load_list)) > 1:
        raise MXNetError(
            'non-uniform work_load_list %s is not supported: the SPMD '
            'mesh shards the batch evenly across devices (uneven '
            'per-device shapes would break XLA partitioning)'
            % (list(work_load_list),))
    base = batch_size // n
    slices = []
    start = 0
    for _ in range(n):
        slices.append(slice(start, start + base))
        start += base
    return slices
