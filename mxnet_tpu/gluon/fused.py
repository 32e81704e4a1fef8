"""Fused Gluon training: whole-step compilation for imperative loops.

The early-Gluon imperative path trains op-by-op: `autograd.backward`
replays the tape with one `jax.vjp` dispatch per node, and
`Trainer.step` runs a Python loop doing per-parameter reduce + updater
calls — the dispatch-bound regime this project exists to eliminate.
The Module path already escaped it (executor.make_fused_multistep:
fwd+bwd+update as ONE donated XLA dispatch, exec_cache'd, ZeRO-1
sharded).  This module brings the same whole-program compilation to
hybrid nets trained imperatively:

    net = nn.HybridSequential(); ...; net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd', {...})
    fused = gluon.fuse_step(net, loss_fn, trainer)
    for x, y in batches:
        loss = fused(x, y)          # ONE donated XLA dispatch

`fused(x, y)` compiles `forward -> loss -> backward -> grad-reduce ->
optimizer update` into one jitted program: the block's imperative
forward is lifted into a pure function of the flattened parameter
pytree (block.param_trace — the same substitution machinery
hybridize's cached forward uses), `jax.value_and_grad` runs the
backward with the ones-head semantics of `loss.backward()`, gradients
reduce across the device mesh with GSPMD collectives
(parallel/collectives.py) instead of per-param kvstore.push/pull —
composing with ZeRO-1 bucketed reduce-scatter when zero=1 /
MXNET_TPU_ZERO=1 — and the FusedSGD update math runs on the results
with parameter/momentum/fp32-master buffers donated.  `fused.bulk(xs,
ys)` loops K steps on-device via lax.scan (the Module bulk_step
analog).

Programs go through the process-wide exec_cache keyed on a canonical
signature (abstract-jaxpr fingerprint of the whole step + input
shapes/dtypes + FusedSGD.cache_key() carrying optimizer hypers and the
ZeRO bucket layout/mesh), so re-creating the net and Trainer — same
architecture, fresh Parameter objects, different auto-prefixes —
performs ZERO new XLA compilations.

Round 11 (backward-interleaved reduction + epoch-level fusion):
gradients all-reduce bucket-by-bucket in backward-availability order
(parallel/collectives.GradReducePlan — each bucket's collective
issues as soon as its wgrads exist and overlaps the remaining
backward; MXNET_TPU_INTERLEAVE_REDUCE=0 restores the end-of-backward
baseline), and `bulk` carries metric running sums
(metric.device_fold), per-step lr/wd schedule columns
(FusedSGD.host_prep_steps — schedules no longer advance in bulk-size
units), and an optional weight-EMA arm (ema_decay=...; read with
FusedStep.ema()) as pure lax.scan carry state, so steps_per_dispatch
stretches across what used to be per-batch metric/LR host syncs.

Observability: profiler.gluon_fused_stats() (gluon_fused_steps /
gluon_fused_dispatches), the 'gluon_fused' span category, the
reduce_buckets_issued / scan_fused_metric_steps
comm counters, and the ZeRO comm/state counters Module feeds.
Gates: tests/test_gluon_fused.py (fused vs imperative parity) and
tests/test_overlap_fusion.py (interleaved vs end-of-backward reduce).
Docs: docs/PERF.md rounds 10-11.
"""
import hashlib
import os
import re
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from .. import exec_cache
from .. import metric as metric_mod
from .. import ndarray as nd
from .. import optimizer as opt_mod
from .. import profiler
from .. import random as _random
from ..base import MXNetError
from ..parallel import collectives
from ..parallel import embedding as embed_mod
from ..parallel import mesh as pmesh
from ..parallel import zero as zero_mod
from . import block as block_mod


def resolve_step_ahead(step_ahead=None):
    """How many donated train dispatches may be IN FLIGHT behind the
    host (MXNET_TPU_TRAIN_STEP_AHEAD, default 1): XLA dispatch is
    async, so the host can stage + enqueue step t+1 while step t's
    result is still computing — this bound is the backpressure that
    keeps it from running unboundedly ahead (donated-buffer chains
    grow with every un-drained step).  0 = block on every step's loss
    before returning (the serialized parity baseline the overlap A/B
    gates against).  The depth changes only WHEN the host waits,
    never what is computed — loss curves are bit-identical at any
    depth."""
    if step_ahead is not None:
        return max(0, int(step_ahead))
    raw = (os.environ.get('MXNET_TPU_TRAIN_STEP_AHEAD', '') or '') \
        .strip().lower()
    if raw in ('0', 'off', 'none', 'false'):
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        return 1


def fuse_step(net, loss, trainer, mesh=None, zero=None, metric=None,
              ema_decay=None, interleave=None, checkpoint=None,
              pipeline=None, step_ahead=None):
    """Build (and register on `trainer`) a FusedStep compiling the
    whole train step for `net` into one donated XLA dispatch.

    net: a Block whose forward is pure NDArray math (HybridBlocks
    always qualify; hybridize() is not required — tracing takes the
    imperative path either way).  loss: a gluon loss (or any callable
    of (out, label) -> per-sample loss), or None when the net's output
    IS the loss.  trainer: the gluon.Trainer owning the parameters;
    its optimizer must have a fused update (SGD / NAG — see
    optimizer.create_fused_updater).

    mesh: optional jax Mesh for data-parallel execution; defaults to a
    1-D 'data' mesh over the trainer's contexts when there are several
    (batches shard over it, parameters replicate, gradients reduce
    in-step).  zero: ZeRO stage for the sharded optimizer update
    (None defers to MXNET_TPU_ZERO).

    metric: optional EvalMetric with a device fold
    (metric.device_fold) — its accumulation then runs INSIDE the
    compiled step from (net output, label): `bulk` carries the running
    sums through the lax.scan and one queued device-scalar pair per
    dispatch reaches the host metric, so metric logging no longer
    breaks the bulk (steps_per_dispatch stretches across it; the first
    metric.get() syncs).  ema_decay: optional float in (0, 1) adding a
    weight-EMA arm as pure carry state of the same dispatch
    (ema <- d*ema + (1-d)*w after each update; read with
    FusedStep.ema()).  interleave: override for the gradient-reduction
    schedule (None = MXNET_TPU_INTERLEAVE_REDUCE; see
    parallel/collectives.GradReducePlan).

    checkpoint: optional elastic.CheckpointManager — wires the
    elastic runtime into the imperative loop: before the FIRST fused
    dispatch the newest intact checkpoint (if any) restores into the
    net + trainer (parameters, optimizer state re-sharded for this
    run's mode, RNG key), and every dispatch afterwards feeds the
    manager's cadence/preemption hook (k steps per bulk dispatch), so
    a SIGTERM mid-loop commits a final checkpoint and raises
    elastic.Preempted out of the fused call.  The DATA position is
    the caller's to restore (`checkpoint.last_resume.step` says how
    many optimizer steps already ran).  A manager wired with an
    on_commit push hook (fleet_supervisor.CheckpointPusher.attach)
    additionally closes the train->serve loop: each commit pushes
    into a live fleet as a canary, verdicts log at the next fused
    step boundary, and N consecutive rollbacks raise RollbackStop
    out of the fused call (docs/ELASTIC.md).

    pipeline: optional (num_stages, num_micro) — or None to defer to
    MXNET_TPU_PIPE='stages,micro' — switches to the dp×pipe 2D-mesh
    GPipe training mode (PipelinedStep): the net's children partition
    into `num_stages` architecturally identical stages (plus an
    optional input stem and output head), each stage's parameters live
    ONLY on its pipe row of the mesh, and every step runs the
    fill-drain microbatch schedule inside the same single donated XLA
    dispatch — composing with ZeRO-1 sharding of the optimizer state
    over the dp axis (zero=1: per-device state ~1/(dp·pipe) of the
    replicated single-device baseline).  Requires a Sequential-style
    net and trainer contexts divisible by num_stages; device-resident
    metrics, EMA, and elastic checkpoints are not yet composed with
    the pipelined mode (pass them only without `pipeline`).  Call the
    returned step's `sync_params()` before imperative eval/predict —
    stage weights live only on their pipe row during training (see
    PipelinedStep.sync_params).

    step_ahead: bound on the async-dispatch pipeline depth — how many
    fused dispatches may be in flight before the host blocks on the
    oldest one's loss (None = MXNET_TPU_TRAIN_STEP_AHEAD, default 1;
    0 = serialized, bit-identical either way — see
    resolve_step_ahead).

    After this call `trainer.step_fused(batch_size, *args)` also runs
    the fused step."""
    from ..parallel import pipeline as pipe_mod
    spec = pipe_mod.pipe_spec(pipeline)
    if spec is not None:
        for bad, name in ((metric, 'metric'), (ema_decay, 'ema_decay'),
                          (checkpoint, 'checkpoint'), (mesh, 'mesh'),
                          (interleave, 'interleave')):
            if bad is not None:
                raise ValueError(
                    'fuse_step: %s= does not compose with the '
                    'pipelined mode yet (pipeline=%r)' % (name, spec))
        return PipelinedStep(net, loss, trainer, spec, zero=zero)
    return FusedStep(net, loss, trainer, mesh=mesh, zero=zero,
                     metric=metric, ema_decay=ema_decay,
                     interleave=interleave, checkpoint=checkpoint,
                     step_ahead=step_ahead)


class FusedStep:
    """One whole training step as a single compiled, donated XLA
    program (see module docstring).  Instances are callable:
    `loss = fused(x, y)` runs one step; `losses = fused.bulk(xs, ys)`
    runs K steps on-device (leading axis of the stacked inputs)."""

    def __init__(self, net, loss, trainer, mesh=None, zero=None,
                 metric=None, ema_decay=None, interleave=None,
                 checkpoint=None, step_ahead=None):
        self._checkpoint = checkpoint
        self._step_ahead = resolve_step_ahead(step_ahead)
        self._inflight = deque()     # loss futures of enqueued steps
        self._ckpt_resume_tried = False
        self._net = net
        self._loss = loss
        self._trainer = trainer
        self._metric = metric
        self._metric_fold = None
        if metric is not None:
            if loss is None:
                raise ValueError(
                    'fuse_step: device-resident metrics need the net '
                    'output and a label (loss=None nets expose '
                    'neither)')
            self._metric_fold = metric_mod.device_fold(metric)
            if self._metric_fold is None:
                raise ValueError(
                    'fuse_step: metric %r has no device fold (see '
                    'metric.device_fold); update it on the host loop '
                    'instead' % (getattr(metric, 'name', metric),))
            for leaf in self._metric_fold.leaves:
                if leaf.output_names is not None or \
                        leaf.label_names is not None:
                    # the gluon step routes under synthetic names
                    # ('output%d'/'label'); a metric's own name filter
                    # cannot resolve against them — fail here, not
                    # with a KeyError inside the trace
                    raise ValueError(
                        'fuse_step: metric %r declares output_names/'
                        'label_names; name routing only applies on '
                        'the Module path (bulk_step/fit)' % leaf.name)
        if ema_decay is not None and not 0.0 < float(ema_decay) < 1.0:
            raise ValueError('ema_decay must be in (0, 1), got %r'
                             % (ema_decay,))
        self._ema_decay = None if ema_decay is None else float(ema_decay)
        self._ema_state = None       # list aligned with self._params
        self._interleave = collectives.interleave_reduce_enabled(
            interleave)
        self._reduce_plan = None     # built once shapes are known
        if type(trainer._optimizer) not in (opt_mod.SGD, opt_mod.NAG):
            # fail at build time, not deep inside the training loop
            raise ValueError(
                'fuse_step: optimizer %s has no fused whole-model '
                'update (SGD and NAG fuse); use trainer.step instead'
                % type(trainer._optimizer).__name__)
        ctxs = list(trainer._contexts) or [None]
        self._ctxs = ctxs
        if mesh is None and len(ctxs) > 1:
            devices = [c.jax_device() for c in ctxs]
            if len(set(devices)) != len(devices):
                raise ValueError('duplicate devices in the trainer '
                                 'contexts: %s' % (ctxs,))
            mesh = pmesh.make_mesh(devices=devices)
        self._mesh = mesh
        self._zero = zero_mod.zero_stage(zero)
        self._params = None          # trainable, trainer order
        self._aux_params = None      # grad_req='null' (BatchNorm stats)
        self._frozen_params = None   # in the net but not the trainer
        self._splan = None           # sparse embedding plan (or None)
        self._sparse_pids = set()
        self._programs = {}          # local key -> compiled step fn
        self._loss_treedef = None
        self._rng = None
        self._placed = False
        self._deferred_done = False
        # mesh mode: id(param) -> (replicated parent, ctx0 shard view).
        # The parent is the fused step's truth; the per-context slots
        # hold per-device shard VIEWS of it so eager/imperative code
        # (eval forwards, metrics) keeps seeing single-device arrays.
        # The view identity doubles as the staleness check: a user
        # set_data() replaces the slot array, and the next step
        # re-replicates from it.
        self._repl = {}
        trainer._fused_step = self

    # -- parameter partition ---------------------------------------------
    def _collect_params(self):
        if self._params is not None:
            return
        allp = dict(self._net.collect_params().items())
        if hasattr(self._loss, 'collect_params'):
            for name, p in self._loss.collect_params().items():
                allp.setdefault(name, p)
        trainable = {id(p) for p in self._trainer._params}
        aux, frozen = [], []
        for name in sorted(allp):
            p = allp[name]
            if id(p) in trainable:
                continue
            (aux if p.grad_req == 'null' else frozen).append(p)
        # trainable params keep the TRAINER's order: FusedSGD state is
        # keyed by the trainer's integer indices, so fused checkpoints
        # are byte-compatible with the per-key Updater's (Trainer
        # save_states/load_states round-trips across both paths)
        self._params = list(self._trainer._params)
        self._aux_params = aux
        self._frozen_params = frozen
        # sparse embedding tier (Embedding(sparse_grad=True)): host plan
        # over the tables' positions; the step trace captures their ids,
        # dedups, and routes (unique_ids, rows) COO grads to the updater
        self._splan = embed_mod.gluon_sparse_plan(self._params)
        self._sparse_pids = {id(self._params[i])
                             for i in self._splan.positions} \
            if self._splan else set()
        if self._splan and self._ema_decay is not None:
            raise MXNetError(
                'fuse_step: ema_decay does not compose with '
                'sparse_grad embedding tables — the EMA arm '
                '(ema <- d*ema + (1-d)*w) reads and writes every table '
                'row every step, densifying exactly the traffic the '
                'sparse tier removes; drop ema_decay or set '
                'sparse_grad=False')

    def _finish_deferred(self, arrays, bulk):
        """Deferred-shape params complete on a real (eager, paused)
        forward — run one with the first batch before compiling.
        One-time: once nothing is pending it never can be again, so
        the per-step hot path skips the block-tree walk."""
        if self._deferred_done:
            return
        pending = any(p._deferred_init for p in
                      self._net.collect_params().values())
        if not pending:
            self._deferred_done = True
            return
        n_data = len(arrays) if self._loss is None else len(arrays) - 1
        from .. import autograd
        with autograd.pause(train_mode=False):
            ins = [nd.NDArray(a[0] if bulk else a) for a in
                   arrays[:n_data]]
            self._net(*ins)
        self._deferred_done = True

    def _place(self):
        """Commit parameters/PRNG to the step's placement once:
        replicated over the mesh (batches arrive sharded; XLA partitions
        the one program — SPMD), or the single context's device."""
        if self._mesh is not None:
            for p in (self._params + self._aux_params +
                      self._frozen_params):
                self._gather_param(p)
            self._rng = jax.device_put(_random.next_key(),
                                       pmesh.replicated(self._mesh))
        else:
            dev = self._ctxs[0].jax_device() if self._ctxs[0] is not None \
                else None
            key = _random.next_key()
            self._rng = jax.device_put(key, dev) if dev is not None \
                else key
        self._placed = True

    def _param_sharding(self, p):
        """Persistent placement of one parameter on the mesh:
        replicated, except sparse_grad embedding tables, which
        row-stripe over the dp axis (each device persistently holds
        ~1/dp of the rows — the EncodeKey big-array split)."""
        if id(p) in self._sparse_pids and \
                'data' in self._mesh.axis_names and \
                int(self._mesh.shape['data']) > 1:
            return embed_mod.row_sharding(self._mesh)
        return pmesh.replicated(self._mesh)

    def _gather_param(self, p):
        """The parameter's value as the step program sees it: the
        mesh-replicated parent when current, re-replicated from the
        ctx0 slot when user code replaced it (set_data, load_params).
        Sparse tables place row-sharded instead of replicated; their
        ctx slots then hold shard VIEWS (a row range per device), so
        eager per-context reads see only local rows — use the trainer
        checkpoint path (or the fused step's writeback parents) for
        full-table access."""
        cur = p.list_data()[0]._data
        if self._mesh is None:
            return cur
        ent = self._repl.get(id(p))
        if ent is not None and ent[1] is cur:
            return ent[0]
        repl = jax.device_put(cur, self._param_sharding(p))
        self._writeback_param(p, repl)
        return repl

    def _writeback_param(self, p, value):
        """Write a step result (or fresh replication) back into the
        parameter: single-device mode rebinds all slots to `value`;
        mesh mode keeps `value` as the replicated parent and gives
        each context its device's shard view (no copy)."""
        if self._mesh is None:
            p._rebind_all_ctx(value)
            return
        p._rebind_all_ctx({s.device: s.data
                           for s in value.addressable_shards})
        self._repl[id(p)] = (value, p.list_data()[0]._data)

    # -- program construction ---------------------------------------------
    def _forward_loss(self, ws, auxs, frozen, ins, rng):
        """The pure forward+loss body: substitute every parameter,
        route RNG through the traced key, return (scalar_total,
        (loss_leaves, new_aux, metric_outs)).  The scalar is the SUM
        of all loss elements (each leaf summed in its own dtype) —
        exactly the ones-head cotangent `loss.backward()` uses, so
        gradients match the imperative path.  metric_outs carries the
        net outputs only when a device-resident metric consumes them
        (empty otherwise — the backward never sees extra residuals)."""
        tps, aps, fps = self._params, self._aux_params, \
            self._frozen_params
        from .nn import moe as moe_mod
        sub = {p: nd.NDArray(v) for p, v in zip(tps, ws)}
        sub.update({p: nd.NDArray(v) for p, v in zip(aps, auxs)})
        sub.update({p: nd.NDArray(v) for p, v in zip(fps, frozen)})
        mouts = ()
        moe_aux = []
        with block_mod.param_trace(sub, rng, train_mode=True), \
                moe_mod.aux_loss_scope(moe_aux):
            in_nd = [nd.NDArray(v) for v in ins]
            if self._loss is not None:
                out = self._net(*in_nd[:-1])
                if isinstance(out, (list, tuple)):
                    l = self._loss(*out, in_nd[-1])
                    if self._metric_fold is not None:
                        mouts = tuple(o._data for o in out)
                else:
                    l = self._loss(out, in_nd[-1])
                    if self._metric_fold is not None:
                        mouts = (out._data,)
            else:
                l = self._net(*in_nd)
        leaves, treedef = jtu.tree_flatten(
            l, is_leaf=lambda a: isinstance(a, nd.NDArray))
        self._loss_treedef = treedef     # static; fixed at trace time
        loss_leaves = tuple(x._data for x in leaves)
        total = None
        for x in loss_leaves:
            s = jnp.sum(x).astype(jnp.float32)
            total = s if total is None else total + s
        # MoE load-balancing auxiliary losses (weighted by each block)
        # fold into the differentiated total but NOT the reported
        # per-sample loss leaves
        for a in moe_aux:
            total = total + jnp.sum(a).astype(jnp.float32)
        new_aux = tuple(sub[p]._data for p in aps)
        return total, (loss_leaves, new_aux, mouts)

    def _make_step_fn(self, fu, bulk, k, rungs=None):
        mesh, zero = self._mesh, self._zero
        step_math = fu.step_math
        forward_loss = self._forward_loss
        plan = self._reduce_plan
        fold = self._metric_fold
        decay = self._ema_decay
        splan = self._splan
        sparse_set = frozenset(splan.positions) if splan else frozenset()
        dense_idx = [j for j in range(len(self._params))
                     if j not in sparse_set]

        def sparse_grads(ws, auxs, frozen, ins, sub):
            """The sparse two-pass backward.  Pass 1 re-traces the
            forward under a capture scope recording each sparse
            table's traced id arrays (outputs discarded — everything
            downstream is dead code XLA eliminates; the pass costs
            trace time only).  The ids then dedup to a ladder-padded
            unique set, the touched rows gather OUTSIDE the
            differentiated region, and pass 2 differentiates the
            forward with every sparse lookup overridden to
            rows[inverse]: the cotangent arriving at `rows` IS the
            per-unique-id summed row-gradient (the segment-sum), so
            sparse positions get (unique_ids, d_rows) COO pairs and
            the (vocab, dim) table never enters the backward."""
            watch = {id(ws[p]): p for p in sparse_set}
            ins_map = {id(a): j for j, a in enumerate(ins)}
            with embed_mod.capture_scope(watch, ins_map,
                                         splan.note_source) as cs:
                forward_loss(list(ws), auxs, frozen, ins, sub)
            uids_list, rows_list, invs_list = [], [], []
            for e, req in zip(splan.entries, rungs):
                pos = e['pos']
                ids = cs.records.get(pos)
                if not ids:
                    raise MXNetError(
                        'sparse embedding: table %s (sparse_grad=True) '
                        'was never looked up in the traced forward — '
                        'unused sparse tables cannot ride the fused '
                        'step; set sparse_grad=False or remove it from '
                        'the trainer' % e['name'])
                splan.note_slots(pos, sum(
                    int(np.prod(a.shape)) for a in ids))
                # the host-requested rung and the trace-observed
                # capacity each cover the step's unique count (the
                # host counts exactly when it sees the ids; capacity
                # = min(id slots, vocab) bounds it always), so their
                # min covers too — and keeps first-trace padding sane
                eff = min(int(req), splan.capacity(e))
                uids, invs = embed_mod.dedup_ids(ids, eff, e['vocab'])
                rows = embed_mod.gather_rows(ws[pos], uids)
                uids_list.append(uids)
                rows_list.append(rows)
                invs_list.append(invs)

            def f(dense_vals, rows_vals):
                full = list(ws)
                for j, v in zip(dense_idx, dense_vals):
                    full[j] = v
                ov = {id(full[e['pos']]):
                      embed_mod._Override(r, iv, e['dim'])
                      for e, r, iv in zip(splan.entries, rows_vals,
                                          invs_list)}
                with embed_mod.override_scope(ov):
                    return forward_loss(full, auxs, frozen, ins, sub)

            (out, (dg, rg)) = jax.value_and_grad(
                f, argnums=(0, 1), has_aux=True)(
                    tuple(ws[j] for j in dense_idx), tuple(rows_list))
            grads = [None] * len(ws)
            for j, g in zip(dense_idx, dg):
                grads[j] = g
            for e, uids, dr in zip(splan.entries, uids_list, rg):
                grads[e['pos']] = (uids, dr)
            return out, grads

        def one_step(ws, auxs, moms, masters, emas, rng, mcarry,
                     frozen, ins, lrs, wds):
            if hasattr(lrs, 'ndim'):
                # bulk mode: (n,) schedule row -> per-param scalars
                lrs = [lrs[j] for j in range(len(ws))]
                wds = [wds[j] for j in range(len(ws))]
            rng, sub = jax.random.split(rng)
            if splan:
                ((_, (loss_leaves, new_aux, mouts)),
                 grads) = sparse_grads(ws, auxs, frozen, ins, sub)
            else:
                f = lambda w: forward_loss(w, auxs, frozen, ins, sub)
                ((_, (loss_leaves, new_aux, mouts)),
                 grads) = jax.value_and_grad(f, has_aux=True)(tuple(ws))
                grads = list(grads)
            if mesh is not None and not zero:
                # bucket-by-bucket all-reduce in backward-availability
                # order — each bucket's collective issues as soon as
                # its wgrads exist, overlapping the remaining backward
                # (the kvstore push/pull role; end-of-backward mode
                # barriers first; under ZeRO the sharded step_math
                # reduce-scatters its own buckets instead).  Sparse COO
                # grads skip the plan: their reduction is GSPMD's to
                # schedule (the constraint-bucketing only guides dense
                # wgrads)
                if sparse_set:
                    dg = plan.apply([grads[j] for j in dense_idx], mesh)
                    for j, g in zip(dense_idx, dg):
                        grads[j] = g
                else:
                    grads = plan.apply(grads, mesh)
            new_ws, new_moms, new_masters = step_math(
                list(ws), grads, moms, masters, lrs, wds)
            if decay is not None:
                # weight-EMA arm: pure carry math on the POST-update
                # weights, in the weight's dtype (decay is weak-typed)
                emas = tuple(decay * e + (1.0 - decay) * w
                             for e, w in zip(emas, new_ws))
            if fold is not None:
                mcarry = fold.update(
                    mcarry, {'label': ins[-1]},
                    {'output%d' % i: o for i, o in enumerate(mouts)})
            return (loss_leaves, tuple(new_ws), new_aux, new_moms,
                    new_masters, emas, mcarry, rng)

        def init_mcarry():
            return fold.init() if fold is not None else ()

        if not bulk:
            def step_fn(ws, auxs, moms, masters, emas, rng, frozen,
                        ins, lrs, wds):
                return one_step(ws, auxs, moms, masters, emas, rng,
                                init_mcarry(), frozen, ins, lrs, wds)
            return step_fn

        def step_fn(ws, auxs, moms, masters, emas, rng, frozen, ins,
                    lrs, wds):
            def body(carry, xs):
                ws, auxs, moms, masters, emas, rng, mc = carry
                sv, lr_t, wd_t = xs
                (loss_leaves, ws, auxs, moms, masters, emas, mc,
                 rng) = one_step(ws, auxs, moms, masters, emas, rng,
                                 mc, frozen, sv, lr_t, wd_t)
                return (ws, auxs, moms, masters, emas, rng, mc), \
                    loss_leaves

            init = (tuple(ws), tuple(auxs), moms, masters, emas, rng,
                    init_mcarry())
            (ws, auxs, moms, masters, emas, rng, mc), losses = \
                jax.lax.scan(body, init, (tuple(ins), lrs, wds))
            if mesh is not None:
                # pin the carry OUTPUTS replicated: GSPMD may choose a
                # dp-sharded layout for the scan carry (observed under
                # ZeRO — the in-body all-gather constraint doesn't bind
                # the carry), and the writeback hands each context its
                # device's shard view, which must be the FULL value.
                # Sparse tables are the exception: they LIVE row-sharded
                # (that is the point — all-gathering one would
                # materialize the full vocab per device), so their carry
                # pins to the row stripe instead
                ws = tuple(
                    collectives.row_shard_constraint(w, mesh)
                    if j in sparse_set
                    else collectives.allgather_bucket(w, mesh)
                    for j, w in enumerate(ws))
                auxs = tuple(collectives.allgather_bucket(a, mesh)
                             for a in auxs)
                emas = tuple(collectives.allgather_bucket(e, mesh)
                             for e in emas)
            return (losses, ws, auxs, moms, masters, emas, mc, rng)

        return step_fn

    def _full_step_key(self, fkey, rungs=None):
        """FusedSGD.cache_key extended with the epoch-fusion carry
        signature and reduction plan: EMA decay, the metric fold's
        identity, and the gradient-bucket layout/schedule all bake
        into the traced program, so they join the cache key (the jaxpr
        fingerprint reflects them too — this makes aliasing impossible
        even across a printing subtlety).  Sparse plans key on table
        positions/shapes plus this dispatch's ladder rungs — the rung
        is a static shape of the traced program."""
        return (fkey,
                ('ema', self._ema_decay),
                ('metric', self._metric_fold.key
                 if self._metric_fold is not None else None),
                ('reduce', self._reduce_plan.key
                 if self._reduce_plan is not None else None),
                ('embed', self._splan.key(rungs)
                 if self._splan else None))

    def _placement_fp(self):
        """Device identity for the program cache: AOT compilation
        bakes concrete placements, so same-architecture steps on
        different devices/meshes must key apart."""
        if self._mesh is not None:
            return ('mesh',) + pmesh.mesh_fingerprint(self._mesh)
        if self._ctxs[0] is not None:
            return ('dev', str(self._ctxs[0].jax_device()))
        return ('dev', 'default')

    def _get_program(self, fu, fkey, bulk, k, args, rungs=None):
        """Resolve the compiled step through the process-wide
        exec_cache: the key is the blake2b fingerprint of the step
        function's ABSTRACT jaxpr (name-free: auto-prefixes and
        Parameter identities trace away) + FusedSGD.cache_key +
        device placement, so an equivalent re-created net/Trainer
        reuses the executable with zero new XLA compilations (the
        fingerprint trace itself compiles nothing).  The cached value
        is the AOT-COMPILED executable: it holds no Python closure,
        so a cache entry never pins a discarded net's weights."""
        step_fn = self._make_step_fn(fu, bulk, k, rungs)
        sds = jtu.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, 'shape') else a, args)
        # mesh-aware layers (gluon.nn.MoE) read the active mesh during
        # tracing to place their sharding constraints
        with pmesh.use_mesh(self._mesh):
            jaxpr = jax.make_jaxpr(step_fn)(*sds)
        # the pretty-printer leaks object identities into some eqn
        # params (custom_jvp thunks print as '<function ... at 0x...>');
        # scrub addresses so equal programs fingerprint equally
        canon = re.sub(r'0x[0-9a-f]+', '0x', str(jaxpr))
        fp = hashlib.blake2b(canon.encode(), digest_size=16).hexdigest()
        key = exec_cache.gluon_step_key(fp,
                                        self._full_step_key(fkey, rungs),
                                        'bulk' if bulk else 'step', k,
                                        self._placement_fp())
        if exec_cache.enabled():
            fn = exec_cache.get(key, count=True)
            if fn is not None:
                return fn
        with pmesh.use_mesh(self._mesh):
            lowered = jax.jit(step_fn,
                              donate_argnums=(0, 1, 2, 3, 4, 5)
                              ).lower(*args)
        fn = exec_cache.timed_compile(lowered)
        if exec_cache.enabled():
            exec_cache.put(key, fn)
        return fn

    # -- optimizer plumbing -----------------------------------------------
    def _ensure_updater(self, batch_size):
        """The trainer-owned FusedSGD, rebuilt when rescale_grad
        changes (Trainer.step semantics: rescale = scale/batch_size is
        baked into the step closure and its cache key; optimizer state
        transfers through the mode-portable checkpoint format)."""
        tr = self._trainer
        rescale = tr._scale / batch_size
        fu = tr._fused_updater
        # compare the BAKED rescale, not the live optimizer attribute:
        # an interleaved trainer.step(other_batch) mutates
        # optimizer.rescale_grad without touching fu's captured value
        if fu is not None and fu.optimizer is tr._optimizer and \
                fu._baked['rescale'] == float(rescale):
            return fu
        tr._optimizer.rescale_grad = rescale
        new = opt_mod.create_fused_updater(
            tr._optimizer, list(range(len(self._params))),
            zero=self._zero, mesh=self._mesh,
            interleave=self._interleave,
            sparse_idx=tuple(self._splan.positions)
            if self._splan else ())
        if new is None:
            raise ValueError(
                'fuse_step: optimizer %s has no fused whole-model '
                'update (SGD and NAG fuse); use trainer.step instead'
                % type(tr._optimizer).__name__)
        if fu is not None:
            new.transfer_states_from(fu)
        elif tr._pending_fused_states is not None:
            new.set_states(tr._pending_fused_states)
            tr._pending_fused_states = None
        tr._fused_updater = new
        return new

    # -- sparse embedding plumbing -----------------------------------------
    def _sparse_pos_set(self):
        return frozenset(self._splan.positions) if self._splan \
            else frozenset()

    def _dispatch_rungs(self, arrays, shapes, bulk):
        """Per-table ladder rungs for one dispatch: bind the plan to
        this dispatch's shape signature, adopt previously published
        trace facts from the exec_cache (a re-created trainer lands on
        the steady-state rungs — and the cached program — without a
        discovery trace), then count host uniques for every table
        whose id source input is known."""
        plan = self._splan
        plan.set_sig(shapes)
        if exec_cache.enabled() and not plan.src:
            facts = exec_cache.get(plan.facts_key())
            if facts is not None:
                plan.src.update(facts[0])
                plan.slots.update(facts[1])
        host_ids = {}
        for kidx in set(plan.src.values()):
            if kidx is not None and kidx < len(arrays):
                host_ids[kidx] = np.asarray(arrays[kidx])
        return plan.pick_rungs(host_ids, bulk=bulk)

    def _note_embed_counters(self, fu, k, rungs):
        """Feed the profiler's embed_* family after a sparse dispatch:
        k steps' lookups, padded unique rows, optimizer-touched bytes
        vs the dense-equivalent, and the ladder rungs in effect."""
        mom = bool(float(getattr(self._trainer._optimizer, 'momentum',
                                 0.0) or 0.0))
        plan = self._splan
        profiler.add_embed_stats(
            steps=k, dispatches=1,
            lookups=k * len(plan.entries),
            unique_rows=k * sum(rungs),
            touched_bytes=k * plan.touched_bytes(rungs, mom),
            dense_equiv_bytes=k * plan.dense_equiv_bytes(mom),
            max_rung=max(rungs))

    # -- execution ---------------------------------------------------------
    def __call__(self, *args, batch_size=None):
        """One fused training step.  args: the net inputs followed by
        the loss label (no label when loss is None).  batch_size
        defaults to the first input's leading dim (Trainer.step's
        1/batch_size gradient scaling).  Returns the per-sample
        loss (net output structure preserved)."""
        return self._run(args, bulk=False, batch_size=batch_size)

    def bulk(self, *args, batch_size=None):
        """K fused steps in ONE dispatch, looping on-device via
        lax.scan (Module.bulk_step analog).  Each arg carries a
        leading K axis ((K, batch, ...) stacks); lr/wd schedules
        evaluate at EVERY step index (per-step schedule rows scanned
        alongside the batches — bit-identical to the per-step loop).
        Returns the per-step losses stacked on a leading K axis."""
        return self._run(args, bulk=True, batch_size=batch_size)

    def _run(self, args, bulk, batch_size):
        if self._loss is not None and len(args) < 2:
            raise ValueError('fused step needs (inputs..., label); '
                             'got %d argument(s)' % len(args))
        arrays = tuple(a._data if isinstance(a, nd.NDArray)
                       else jnp.asarray(a) for a in args)
        k = int(arrays[0].shape[0]) if bulk else 1
        if bulk and k == 0:
            raise ValueError('bulk: stacked inputs have K=0 steps')
        if batch_size is None:
            batch_size = int(arrays[0].shape[1 if bulk else 0])
        self._collect_params()
        self._finish_deferred(arrays, bulk)
        if self._checkpoint is not None and not self._ckpt_resume_tried:
            # elastic resume: restore BEFORE the updater is built so
            # the restored optimizer state applies at its creation
            # (trainer._pending_fused_states).  Placement must happen
            # FIRST: _restore_rng overwrites self._rng, which only
            # exists after _place() — restoring earlier would silently
            # drop the checkpointed key and replay dropout masks from
            # the fresh seed (restored params re-replicate via the
            # set_data staleness check, so placing early is safe)
            self._ckpt_resume_tried = True
            if not self._placed:
                self._place()
            self._checkpoint.attach(self)
            # coordinated elastic restart: a heartbeat-detected peer
            # death preempts this manager — the next step_end commits
            # the final checkpoint and raises Preempted(dead_ranks)
            from .. import dist
            rt = dist.runtime()
            if rt is not None:
                rt.watch(self._checkpoint)
            if self._checkpoint.last_resume is None:
                self._checkpoint.restore(metric=self._metric)
        fu = self._ensure_updater(batch_size)
        tr = self._trainer
        if tr._last_update_mode == 'unfused' and tr._updaters and \
                tr._updaters[0].states:
            # the per-key path trained since the last fused step: adopt
            # its momenta/update-counts so the two paths share ONE
            # optimizer-state history (mode switches only — one host
            # round-trip per switch, not per step)
            fu.set_states(tr._updaters[0].get_states())
        if not self._placed:
            self._place()
        ws = [self._gather_param(p) for p in self._params]
        if self._reduce_plan is None:
            # reverse-availability bucketing over the trainable grads
            # (static: shapes/dtypes are fixed once params are known).
            # Sparse tables stay out: their grads are COO pairs the
            # bucketing constraints cannot express (and must not — a
            # bucketed all-reduce would densify them)
            didx = [j for j in range(len(ws))
                    if j not in self._sparse_pos_set()]
            self._reduce_plan = collectives.GradReducePlan(
                [ws[j].shape for j in didx],
                [ws[j].dtype for j in didx],
                interleave=self._interleave)
        if self._ema_decay is not None and self._ema_state is None:
            # EMA starts as a COPY of the current weights (jnp.add
            # allocates fresh buffers with the weights' placement —
            # the dispatch donates both lists, so they must not alias)
            self._ema_state = [jnp.add(w, 0) for w in ws]
        emas = tuple(self._ema_state) if self._ema_decay is not None \
            else ()
        # host_prep_steps reads shape/dtype/_data (momenta adopt the
        # weight's sharding) — hand it the replicated parents, not the
        # views
        weights = [nd.NDArray(w, self._ctxs[0]) for w in ws]
        # per-step schedule stacks: counts bump and lr/wd schedules
        # evaluate at EVERY step index of the dispatch (host scheduler
        # semantics, bit-identical to the per-step loop)
        moms, masters, lr_stack, wd_stack = fu.host_prep_steps(
            weights, k)
        if bulk:
            # ONE (K, n) schedule array each, scanned row-per-step —
            # a single transfer per dispatch regardless of parameter
            # count (the per-param split happens in the trace)
            lrs, wds = jnp.asarray(lr_stack), jnp.asarray(wd_stack)
            if self._mesh is not None:
                repl = pmesh.replicated(self._mesh)
                lrs = jax.device_put(lrs, repl)
                wds = jax.device_put(wds, repl)
        else:
            # plain floats: the AOT program baked weak-f32 scalar avals
            # (an np scalar from an lr scheduler would mismatch them)
            lrs = [float(v) for v in lr_stack[0]]
            wds = [float(v) for v in wd_stack[0]]
        if self._mesh is not None:
            arrays = tuple(pmesh.shard_batch(self._mesh, a,
                                             dim=1 if bulk else 0)
                           for a in arrays)
        elif self._ctxs[0] is not None:
            # inputs often arrive committed to the default device; the
            # donated dispatch needs them on the weights' device
            dev = self._ctxs[0].jax_device()
            arrays = tuple(jax.device_put(a, dev) for a in arrays)
        fkey = fu.cache_key()
        shapes = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        rungs = self._dispatch_rungs(arrays, shapes, bulk) \
            if self._splan else None
        local = ('bulk' if bulk else 'step', k, shapes,
                 self._full_step_key(fkey, rungs))
        auxs = [self._gather_param(p) for p in self._aux_params]
        frozen = [self._gather_param(p) for p in self._frozen_params]
        # MoE routing counters: snapshot the cumulative aux counts
        # BEFORE the dispatch donates them (profiler-on dispatches are
        # synchronized anyway — see `synced` below)
        moe_idx = [(i, p._moe_counter)
                   for i, p in enumerate(self._aux_params)
                   if getattr(p, '_moe_counter', None)]
        moe_pre = {i: np.asarray(auxs[i]) for i, _ in moe_idx} \
            if moe_idx and profiler.is_running() else None
        prog = self._programs.get(local)
        if prog is None:
            prog = self._get_program(
                fu, fkey, bulk, k,
                (ws, auxs, moms, masters, emas, self._rng, frozen,
                 arrays, lrs, wds), rungs)
            self._programs[local] = prog
            if self._splan is not None and exec_cache.enabled():
                # publish the trace-discovered plan facts so an
                # equivalent re-created net/trainer picks steady-state
                # rungs up front (see SparseEmbedPlan.facts_key)
                exec_cache.put(self._splan.facts_key(),
                               (dict(self._splan.src),
                                dict(self._splan.slots)))
        synced = profiler.is_running()
        with profiler.scope('gluon_fused_%s' % ('bulk' if bulk
                                                else 'step'),
                            'gluon_fused'):
            (loss_out, new_ws, new_aux, new_moms, new_masters,
             new_emas, mdeltas, self._rng) = prog(
                ws, auxs, moms, masters, emas, self._rng, frozen,
                arrays, lrs, wds)
            if synced:
                jax.block_until_ready(loss_out)
        for p, w in zip(self._params, new_ws):
            self._writeback_param(p, w)
        for p, a in zip(self._aux_params, new_aux):
            self._writeback_param(p, a)
        if moe_pre is not None:
            self._note_moe_counters(moe_idx, moe_pre, new_aux)
        fu.commit(new_moms, new_masters)
        if self._ema_decay is not None:
            self._ema_state = list(new_emas)
        if self._metric_fold is not None:
            # device scalars queue on the host metric WITHOUT a sync;
            # the first metric.get() (epoch end / logging) drains them
            self._metric_fold.commit(mdeltas)
        self._trainer._last_update_mode = 'fused'
        profiler.add_gluon_fused_stats(steps=k, dispatches=1)
        self._note_reduce_counters(fu, k)
        if self._splan is not None:
            self._note_embed_counters(fu, k, rungs)
        rs, ag = fu.comm_bytes_per_step()
        if rs or ag:
            profiler.add_comm_bytes(reduce_scattered=rs * k,
                                    all_gathered=ag * k)
        profiler.set_optimizer_state_bytes(fu.state_bytes_per_device())
        if self._checkpoint is not None:
            # cadence / preemption hook: k optimizer steps ran in this
            # dispatch; a pending SIGTERM commits the final checkpoint
            # here (the snapshot copies queue behind the dispatch —
            # that IS the drain) and raises Preempted
            self._checkpoint.step_end(steps=k, batch_size=batch_size,
                                      metric=self._metric, target=self)
        if not synced:
            # bounded async-dispatch depth: the returned losses are
            # FUTURES, so the host is free to stage + enqueue the next
            # dispatch while this one computes — but only step_ahead
            # deep, or it runs unboundedly ahead of the device.  The
            # timed block on the OLDEST loss is the backpressure (and
            # the measured overlap window); a profiler-synced dispatch
            # already blocked above.
            self._inflight.append(loss_out)
            while len(self._inflight) > self._step_ahead:
                tw = time.perf_counter()
                jax.block_until_ready(self._inflight.popleft())
                profiler.add_overlap_stats(
                    dispatch_wait_ms=(time.perf_counter() - tw) * 1e3)
        profiler.add_overlap_stats(train_steps=k,
                                   steps_ahead=len(self._inflight))
        ctx = self._ctxs[0]
        out = [nd.NDArray(v, ctx) for v in loss_out]
        return jtu.tree_unflatten(self._loss_treedef, out)

    def _note_reduce_counters(self, fu, k):
        """Feed the round-11 profiler counters after a dispatch of k
        steps: gradient-bucket collectives issued (reduce plan
        buckets, or the ZeRO layout's) and device-folded metric steps
        (one model, profiler.note_reduce_dispatch)."""
        buckets = 0
        if self._mesh is not None:
            if self._zero and fu._layout is not None:
                buckets = len(fu._layout.buckets)
            elif not self._zero and self._reduce_plan is not None:
                buckets = self._reduce_plan.n_buckets
        profiler.note_reduce_dispatch(
            buckets, k,
            metric_steps=k if self._metric_fold is not None else 0)

    @staticmethod
    def _note_moe_counters(moe_idx, pre, new_aux):
        """Feed the profiler's moe_* counters from the per-dispatch
        deltas of the MoE blocks' cumulative routed/dropped aux counts
        (per-expert tables sum across blocks by expert index)."""
        totals = {'routed': 0.0, 'dropped': 0.0}
        for i, kind in moe_idx:
            delta = np.asarray(new_aux[i]) - pre[i]
            totals[kind] += float(delta.sum())
            profiler.add_moe_stats(**{'per_expert_%s' % kind: delta})
        profiler.add_moe_stats(routed=totals['routed'],
                               dropped=totals['dropped'], dispatches=1)

    def ema(self):
        """Snapshot of the weight-EMA arm as {parameter name:
        NDArray}, aligned with the trainable parameters.  Before the
        first step the EMA equals the current weights."""
        if self._ema_decay is None:
            raise ValueError('fuse_step was built without ema_decay')
        self._collect_params()
        if self._ema_state is None:
            if not self._placed:
                self._place()
            vals = [self._gather_param(p) for p in self._params]
        else:
            vals = self._ema_state
        ctx = self._ctxs[0]
        return {p.name: nd.NDArray(v, ctx)
                for p, v in zip(self._params, vals)}


# ---------------------------------------------------------------------------
# dp×pipe pipelined mode
# ---------------------------------------------------------------------------

def _child_struct_sig(block):
    """Structural identity of one child block for stage partitioning:
    class name, its parameters' (relative name, shape, dtype, grad_req)
    in traversal order, and the child subtree's signatures.  Two
    children with equal signatures are stacking-compatible stage
    material (the traced-jaxpr equality check at program build time is
    the definitive functional test — this one only decides the
    partition)."""
    plist = sorted(block._collect_params_with_prefix().items())
    psig = tuple((name, tuple(p.shape) if p.shape else None,
                  str(np.dtype(p.dtype)) if p.dtype else None,
                  p.grad_req) for name, p in plist)
    return (type(block).__name__, psig)


def _partition_pipeline_children(net, num_stages):
    """Partition a Sequential-style net's children into
    (stem_children, [stage_children...], head_children): the longest
    run of consecutive structurally identical children forms the stage
    body (run length must divide by num_stages); the prefix before it
    is the stem (applied by stage 0), the suffix after it the head
    (applied with the loss by the last stage)."""
    children = list(getattr(net, '_children', ()))
    if len(children) < num_stages:
        raise ValueError(
            'fuse_step(pipeline=(%d, ...)): net has %d children; the '
            'pipelined mode partitions a Sequential of repeated '
            'blocks — need at least one block per stage'
            % (num_stages, len(children)))
    sigs = [_child_struct_sig(c) for c in children]
    best_start, best_len = 0, 1
    start = 0
    for i in range(1, len(sigs) + 1):
        if i == len(sigs) or sigs[i] != sigs[start]:
            if i - start > best_len:
                best_start, best_len = start, i - start
            start = i
    if best_len % num_stages:
        raise ValueError(
            'fuse_step(pipeline): the longest run of identical '
            'children has length %d, not divisible into %d stages — '
            'stack a multiple of %d identical blocks'
            % (best_len, num_stages, num_stages))
    per = best_len // num_stages
    stages = [children[best_start + s * per:best_start + (s + 1) * per]
              for s in range(num_stages)]
    return (children[:best_start], stages,
            children[best_start + best_len:])


def _ordered_child_params(children):
    """The parameters of a run of children in structural order
    (per-child relative-name order — aligned across identically
    structured stages regardless of auto-prefix counters)."""
    out = []
    for c in children:
        out.extend(p for _, p in
                   sorted(c._collect_params_with_prefix().items()))
    return out


class PipelinedStep(FusedStep):
    """GPipe dp×pipe training as ONE donated XLA dispatch (the
    pipeline=(num_stages, num_micro) mode of fuse_step).

    The net's children partition into an optional stem, `num_stages`
    architecturally identical stages, and an optional head (see
    _partition_pipeline_children).  Stage parameters stack on a
    leading stage dim sharded over the 'pipe' axis of a 2D
    {'data': dp, 'pipe': S} mesh — each device holds ONLY its stage's
    weights (1/S of the stage-body parameters) — while stem/head
    parameters replicate.  Every training step runs the fill-drain
    microbatch schedule (parallel/pipeline.make_pipe_step_fn) with the
    batch sharded over dp, gradients psum'd over dp (or
    psum_scatter'd under ZeRO-1, which also shards the momentum
    buckets over dp: per-device optimizer state ~1/(dp·S) of the
    single-device replicated baseline), and the SGD/NAG update fused
    into the same program.  `bulk` scans K steps on-device exactly
    like FusedStep.bulk.  Programs resolve through the process-wide
    exec_cache keyed on the abstract-jaxpr fingerprint + mesh
    fingerprint + stage/bucket layout, so an equivalent re-created
    net/Trainer performs ZERO new XLA compilations."""

    def __init__(self, net, loss, trainer, pipeline, zero=None):
        from ..parallel import pipeline as pipe_mod
        self._pipe_mod = pipe_mod
        spec = pipe_mod.pipe_spec(pipeline)
        self._pipe_s, self._pipe_m = spec
        if loss is None:
            raise ValueError(
                'fuse_step(pipeline): loss=None nets are not '
                'supported — the pipelined head needs an explicit '
                'loss on the last stage')
        ctxs = list(trainer._contexts)
        if len(ctxs) < self._pipe_s or len(ctxs) % self._pipe_s:
            raise ValueError(
                'fuse_step(pipeline=(%d, %d)): %d trainer contexts do '
                'not divide into %d pipeline stages'
                % (self._pipe_s, self._pipe_m, len(ctxs), self._pipe_s))
        devices = [c.jax_device() for c in ctxs]
        if len(set(devices)) != len(devices):
            raise ValueError('duplicate devices in the trainer '
                             'contexts: %s' % (ctxs,))
        mesh = pipe_mod.make_pipe_mesh(devices, self._pipe_s)
        super().__init__(net, loss, trainer, mesh=mesh, zero=zero)
        if bool(getattr(trainer._optimizer, 'multi_precision', False)):
            raise ValueError(
                'fuse_step(pipeline): multi_precision is not composed '
                'with the pipelined update yet')
        if any(getattr(p, 'sparse_grad', False)
               for p in trainer._params):
            raise MXNetError(
                'fuse_step(pipeline): sparse_grad embedding tables '
                'are not composed with the pipelined schedule yet — '
                'keep sparse tables on the plain fused step '
                '(dp mesh), or set sparse_grad=False here')
        self._dp = int(mesh.shape['data'])
        self._partitioned = False
        self._stage_children = None
        self._stem_children = None
        self._head_children = None
        self._stage_groups = None    # leaf j -> [param_s0, ..., param_S-1]
        self._stem_params2 = None
        self._head_params2 = None
        self._group_tr_idx = None    # leaf j -> trainer indices
        self._stage_state = {}       # leaf j -> (stacked, slot datas)
        self._pipe_opt = None
        self._pipe_layout = None
        self._baked_rescale = None
        self._homog_checked = False

    # -- partitioning ------------------------------------------------------
    def _partition(self):
        if self._partitioned:
            return
        stem, stages, head = _partition_pipeline_children(
            self._net, self._pipe_s)
        stage_plists = [_ordered_child_params(cs) for cs in stages]
        n_leaf = len(stage_plists[0])
        for s, pl in enumerate(stage_plists):
            if len(pl) != n_leaf:
                raise ValueError('pipeline stage %d has %d parameters, '
                                 'stage 0 has %d' % (s, len(pl), n_leaf))
        groups = []
        for j in range(n_leaf):
            group = [stage_plists[s][j] for s in range(self._pipe_s)]
            shapes = {tuple(p.shape) for p in group}
            dts = {str(np.dtype(p.dtype)) for p in group}
            if len(shapes) != 1 or len(dts) != 1:
                raise ValueError(
                    'pipeline stages are not stacking-compatible: '
                    'leaf %d has shapes %s dtypes %s'
                    % (j, sorted(shapes), sorted(dts)))
            groups.append(group)
        stem_params = _ordered_child_params(stem)
        head_params = _ordered_child_params(head)
        allp = ([p for g in groups for p in g] + stem_params +
                head_params)
        if any(p.grad_req == 'null' for p in allp):
            raise ValueError(
                'fuse_step(pipeline): grad_req=null (aux) parameters '
                '(BatchNorm running stats, MoE counters) are not '
                'composed with the pipelined schedule yet')
        if hasattr(self._loss, 'collect_params') and \
                list(self._loss.collect_params().items()):
            raise ValueError('fuse_step(pipeline): losses with their '
                             'own parameters are not supported')
        trainable = {id(p) for p in self._trainer._params}
        missing = [p.name for p in allp if id(p) not in trainable]
        extra = len(self._trainer._params) != len(allp)
        if missing or extra:
            raise ValueError(
                'fuse_step(pipeline): the trainer must own exactly '
                "the net's parameters (missing from trainer: %s; "
                'trainer has %d params, net has %d)'
                % (missing, len(self._trainer._params), len(allp)))
        tr_idx = {id(p): i for i, p in
                  enumerate(self._trainer._params)}
        self._stem_children, self._stage_children, \
            self._head_children = stem, stages, head
        self._stage_groups = groups
        self._stem_params2 = stem_params
        self._head_params2 = head_params
        self._group_tr_idx = (
            [[tr_idx[id(p)] for p in g] for g in groups] +
            [[tr_idx[id(p)]] for p in stem_params] +
            [[tr_idx[id(p)]] for p in head_params])
        self._partitioned = True

    # -- traced stage/stem/head bodies -------------------------------------
    def _seq_forward(self, children, params, values, x_data, rng):
        """Apply a run of children sequentially as a pure function of
        (param values, input) — the param_trace substitution the
        whole-step trace rides on."""
        sub = {p: nd.NDArray(v) for p, v in zip(params, values)}
        with block_mod.param_trace(sub, rng, train_mode=True):
            x = nd.NDArray(x_data)
            for c in children:
                x = c(x)
        return x._data

    def _make_fns(self):
        stage0 = self._stage_children[0]
        stage0_params = _ordered_child_params(stage0)
        stem_children = self._stem_children
        stem_params = self._stem_params2
        head_children = self._head_children
        head_params = self._head_params2
        loss = self._loss
        seq = self._seq_forward
        outer = self

        def stem_fn(ws, mb, rng):
            if not stem_children:
                return mb
            return seq(stem_children, stem_params, ws, mb, rng)

        def stage_fn(ws, act, rng):
            return seq(stage0, stage0_params, ws, act, rng)

        def head_fn(ws, acts, label, rng):
            sub = {p: nd.NDArray(v) for p, v in zip(head_params, ws)}
            with block_mod.param_trace(sub, rng, train_mode=True):
                out = nd.NDArray(acts)
                for c in head_children:
                    out = c(out)
                l = loss(out, nd.NDArray(label))
            leaves, treedef = jtu.tree_flatten(
                l, is_leaf=lambda a: isinstance(a, nd.NDArray))
            outer._loss_treedef = treedef
            leaves = tuple(x._data for x in leaves)
            total = None
            for x in leaves:
                s = jnp.sum(x).astype(jnp.float32)
                total = s if total is None else total + s
            return leaves, total

        return stem_fn, stage_fn, head_fn

    def _check_stage_homogeneity(self, act_sds, rng_sds):
        """Traced-jaxpr stage equality (the partition's structural
        equality is necessary, not sufficient) — one shared check,
        parallel/pipeline.check_stage_homogeneity."""
        if self._homog_checked:
            return

        def trace(children):
            params = _ordered_child_params(children)
            sds = [jax.ShapeDtypeStruct(tuple(p.shape),
                                        np.dtype(p.dtype))
                   for p in params]

            def fn(ws, x, k, _c=children, _p=params):
                return self._seq_forward(_c, _p, ws, x, k)

            return (fn, sds, act_sds, rng_sds)

        self._pipe_mod.check_stage_homogeneity(
            [trace(c) for c in self._stage_children],
            lambda s: ValueError(
                'fuse_step(pipeline): stage %d traces a different '
                'computation than stage 0 — pipeline stages must '
                'be architecturally identical (same layer types, '
                'activations and shapes)' % s))
        self._homog_checked = True

    # -- placement ---------------------------------------------------------
    def _gather_stage_leaf(self, j):
        """The stacked (S, ...) device value of stage-leaf group j —
        re-stacked from the per-parameter slots when any member was
        replaced by user code (set_data / load_params), else the
        cached donated output of the last step."""
        from ..parallel import mesh as pmesh
        group = self._stage_groups[j]
        slots = tuple(p.list_data()[0]._data for p in group)
        ent = self._stage_state.get(j)
        # identity against LIVE row references (not id()s of possibly
        # freed arrays — address reuse could spuriously match and
        # silently ignore a user's load_params/set_data)
        if ent is not None and len(ent[1]) == len(slots) and \
                all(a is b for a, b in zip(ent[1], slots)):
            return ent[0]
        stacked = jax.device_put(
            jnp.stack([jnp.asarray(s) for s in slots]),
            jax.sharding.NamedSharding(self._mesh,
                                       jax.sharding.PartitionSpec('pipe')))
        self._writeback_stage_leaf(j, stacked)
        return stacked

    def _writeback_stage_leaf(self, j, stacked):
        """Hand every stage parameter its row VIEW of the stacked
        leaf; the row identity doubles as the staleness check."""
        rows = [stacked[s] for s in range(self._pipe_s)]
        for p, row in zip(self._stage_groups[j], rows):
            p._rebind_all_ctx(row)
        self._stage_state[j] = (stacked, tuple(rows))

    def _pipe_schedules(self, k, n_leaf):
        """(k, n_leaf) float32 lr/wd schedule rows in leaf order
        [stage-groups..., stem..., head...] — one shared builder,
        parallel/pipeline.grouped_schedule_rows."""
        return self._pipe_mod.grouped_schedule_rows(
            self._trainer._optimizer, len(self._trainer._params),
            self._group_tr_idx, k,
            lambda lrs, wds: ValueError(
                'fuse_step(pipeline): stage parameters of one '
                'stacked group have diverging lr/wd (%s / %s) '
                '— per-stage lr_mult does not compose with '
                'stacked stages' % (lrs, wds)))

    def _pipe_hyper(self, batch_size):
        tr = self._trainer
        opt = tr._optimizer
        rescale = float(tr._scale / batch_size)
        opt.rescale_grad = rescale
        clip = opt.clip_gradient
        return {'momentum': float(opt.momentum),
                'rescale': rescale,
                'clip': None if clip is None else float(clip),
                'nesterov': isinstance(opt, opt_mod.NAG)}

    def _pipe_state_accounting(self):
        """(param_bytes, opt_state_bytes) resident PER DEVICE — one
        shared model, parallel/pipeline.pipe_residency."""
        leaves = ([g[0] for g in self._stage_groups] +
                  self._stem_params2 + self._head_params2)
        return self._pipe_mod.pipe_residency(
            [tuple(p.shape) for p in leaves],
            [np.dtype(p.dtype) for p in leaves], self._pipe_layout)

    # -- execution ---------------------------------------------------------
    def _run(self, args, bulk, batch_size):
        if len(args) != 2:
            raise ValueError(
                'pipelined fused step takes exactly (data, label); '
                'got %d argument(s)' % len(args))
        arrays = tuple(a._data if isinstance(a, nd.NDArray)
                       else jnp.asarray(a) for a in args)
        k = int(arrays[0].shape[0]) if bulk else 1
        if bulk and k == 0:
            raise ValueError('bulk: stacked inputs have K=0 steps')
        if batch_size is None:
            batch_size = int(arrays[0].shape[1 if bulk else 0])
        B = int(arrays[0].shape[1 if bulk else 0])
        S, M, dp = self._pipe_s, self._pipe_m, self._dp
        if B % (dp * M):
            raise ValueError(
                'fuse_step(pipeline=(%d, %d)): batch %d must divide '
                'by dp*num_micro = %d' % (S, M, B, dp * M))
        self._collect_params()
        self._finish_deferred(arrays, bulk)
        self._partition()
        from ..parallel import mesh as pmesh
        if not self._placed:
            self._rng = jax.device_put(_random.next_key(),
                                       pmesh.replicated(self._mesh))
            self._placed = True
        hyper = self._pipe_hyper(batch_size)
        stage_ws = [self._gather_stage_leaf(j)
                    for j in range(len(self._stage_groups))]
        stem_ws = [self._gather_param(p) for p in self._stem_params2]
        head_ws = [self._gather_param(p) for p in self._head_params2]
        local_shapes = ([tuple(w.shape[1:]) for w in stage_ws] +
                        [tuple(w.shape) for w in stem_ws + head_ws])
        local_dts = [np.dtype(w.dtype) for w in
                     stage_ws + stem_ws + head_ws]
        if self._zero and self._pipe_layout is None:
            self._pipe_layout = zero_mod.ZeroBucketLayout(
                local_shapes, local_dts, [False] * len(local_dts), dp)
        self._ensure_pipe_opt(stage_ws, stem_ws, head_ws)
        n_leaf = len(local_shapes)
        lr_rows, wd_rows = self._pipe_schedules(k, n_leaf)
        repl = pmesh.replicated(self._mesh)
        if bulk:
            lrs = jax.device_put(jnp.asarray(lr_rows), repl)
            wds = jax.device_put(jnp.asarray(wd_rows), repl)
        else:
            lrs = [float(v) for v in lr_rows[0]]
            wds = [float(v) for v in wd_rows[0]]
        arrays = tuple(pmesh.shard_batch(self._mesh, a,
                                         dim=1 if bulk else 0)
                       for a in arrays)
        shapes = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        local = ('pipe', 'bulk' if bulk else 'step', k, shapes,
                 self._pipe_step_key(hyper))
        prog = self._programs.get(local)
        if prog is None:
            prog = self._get_pipe_program(
                hyper, bulk, k,
                (stage_ws, stem_ws, head_ws, self._pipe_opt,
                 self._rng, arrays[0], arrays[1], lrs, wds))
            self._programs[local] = prog
        with profiler.scope('gluon_pipe_%s' % ('bulk' if bulk
                                               else 'step'),
                            'gluon_fused'):
            (loss_out, new_stage, new_stem, new_head, self._pipe_opt,
             self._rng) = prog(stage_ws, stem_ws, head_ws,
                               self._pipe_opt, self._rng, arrays[0],
                               arrays[1], lrs, wds)
            if profiler.is_running():
                jax.block_until_ready(loss_out)
        for j, stacked in enumerate(new_stage):
            self._writeback_stage_leaf(j, stacked)
        for p, w in zip(self._stem_params2, new_stem):
            self._writeback_param(p, w)
        for p, w in zip(self._head_params2, new_head):
            self._writeback_param(p, w)
        self._trainer._last_update_mode = 'fused'
        self._note_pipe_counters(k)
        ctx = self._ctxs[0]
        out = [nd.NDArray(v, ctx) for v in loss_out]
        return jtu.tree_unflatten(self._loss_treedef, out)

    def _ensure_pipe_opt(self, stage_ws, stem_ws, head_ws):
        if self._pipe_opt is not None:
            return
        self._pipe_opt = self._pipe_mod.init_pipe_opt_state(
            self._mesh, self._pipe_layout, self._pipe_s, stage_ws,
            stem_ws, head_ws)

    def _pipe_step_key(self, hyper):
        return ('pipe', self._pipe_s, self._pipe_m, self._zero,
                self._pipe_layout.key if self._pipe_layout is not None
                else None,
                tuple(sorted(hyper.items(),
                             key=lambda kv: kv[0])))

    def _placement_fp(self):
        from ..parallel import mesh as pmesh
        return ('pipemesh', self._pipe_s,
                ) + pmesh.mesh_fingerprint(self._mesh)

    def _get_pipe_program(self, hyper, bulk, k, pargs):
        """Resolve the compiled pipelined step through the process-wide
        exec_cache (one shared discipline,
        parallel/pipeline.resolve_pipe_program)."""
        stem_fn, stage_fn, head_fn = self._make_fns()
        data = pargs[5]
        b_local = data.shape[1 if bulk else 0] // self._dp
        mb_sds = jax.ShapeDtypeStruct(
            (b_local // self._pipe_m,) + tuple(
                data.shape[2 if bulk else 1:]),
            np.dtype(data.dtype))
        key_sds = jax.ShapeDtypeStruct(self._rng.shape,
                                       self._rng.dtype)
        if self._stem_children:
            stem_sds = [jax.ShapeDtypeStruct(tuple(p.shape),
                                             np.dtype(p.dtype))
                        for p in self._stem_params2]
            act_sds = jax.eval_shape(stem_fn, stem_sds, mb_sds,
                                     key_sds)
        else:
            act_sds = mb_sds
        self._check_stage_homogeneity(act_sds, key_sds)
        step_fn = self._pipe_mod.make_pipe_step_fn(
            self._mesh, self._pipe_s, self._pipe_m, stem_fn, stage_fn,
            head_fn, hyper, layout=self._pipe_layout, bulk=bulk)
        return self._pipe_mod.resolve_pipe_program(
            step_fn, pargs, self._pipe_step_key(hyper),
            'pipe_bulk' if bulk else 'pipe_step', k,
            self._placement_fp())

    def _note_pipe_counters(self, k):
        param_b, state_b = self._pipe_state_accounting()
        profiler.add_gluon_fused_stats(steps=k, dispatches=1)
        self._pipe_mod.note_pipe_counters(
            self._pipe_s, self._pipe_m, k, self._pipe_layout, self._dp,
            param_b, state_b)

    def sync_params(self):
        """Materialize the trained weights as ordinary per-context
        arrays for imperative use (eval/predict/save outside the
        fused step).  During pipelined training each stage's weights
        live ONLY on their pipe row of the mesh — that is the memory
        win — so the per-step writeback hands the parameters row
        VIEWS of the stacked mesh arrays: `.asnumpy()` reads are
        always current, but eager forward math mixing them with a
        single-device input raises jax's incompatible-devices error.
        This performs ONE host round-trip per stage leaf and rewrites
        every context copy (Parameter.set_data); the next fused step
        re-places the rows through the same staleness path user
        set_data takes (one re-stack, ZERO recompiles).  Stem/head
        copies are per-device views of replicated parents and are
        already eager-usable."""
        self._collect_params()
        if not self._partitioned:
            return
        for j, group in enumerate(self._stage_groups):
            ent = self._stage_state.pop(j, None)
            if ent is None:
                continue
            rows = np.asarray(ent[0])
            for s, p in enumerate(group):
                p.set_data(nd.array(rows[s]))

    # pipelined mode does not carry an EMA arm
    def ema(self):
        raise ValueError('fuse_step(pipeline) has no EMA arm')
