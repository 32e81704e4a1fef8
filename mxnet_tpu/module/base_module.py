"""BaseModule: the high-level training interface.

Reference: python/mxnet/module/base_module.py (fit :376, score, predict;
SURVEY.md §3.1).  The epoch/batch loop structure, callbacks, metric
handling, and checkpoint hooks mirror the reference so training scripts
port unchanged; per-batch work runs as one fused XLA step via the
executor group.
"""
import logging
import threading
import time
from collections import namedtuple

import numpy as np

from .. import metric as metric_mod
from .. import ndarray as nd
from ..base import MXNetError
from ..initializer import Uniform

BatchEndParam = namedtuple('BatchEndParams',
                           ['epoch', 'nbatch', 'eval_metric', 'locals'])


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _fire(callbacks, *cb_args):
    """Invoke a callback or list of callbacks (no-op on None)."""
    if callbacks is None:
        return
    for cb in _as_list(callbacks):
        cb(*cb_args)


def _wait_for(outputs):
    """One wait for all of a step's outputs (NDArrays): fit's
    'fit.wait' span, so that the metric fold's own time is apart.  The
    fold reads them on the host next, so their copies are started
    before the wait, behind the step on the device, as the fold's own
    read started them when it was what waited."""
    import jax
    arrays = [getattr(o, '_data', o) for o in outputs]
    for a in arrays:
        if isinstance(a, jax.Array):
            a.copy_to_host_async()
    jax.block_until_ready(arrays)


def _trim_pad(arrays, pad):
    """Drop the trailing `pad` rows that a padded final batch carries."""
    if not pad:
        return list(arrays)
    return [a[:a.shape[0] - pad] for a in arrays]


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        for flag in ('binded', 'for_training', 'inputs_need_grad',
                     'params_initialized', 'optimizer_initialized'):
            setattr(self, flag, False)
        self._symbol = None
        self._total_exec_bytes = 0

    # -- abstract interface (implemented by Module etc.) ------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, *args, **kwargs):
        raise NotImplementedError

    def init_params(self, *args, **kwargs):
        raise NotImplementedError

    def init_optimizer(self, *args, **kwargs):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    # -- shared high-level logic ------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate on a data iterator (reference base_module.py score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        seen = 0
        for eval_batch in eval_data:
            if num_batch is not None and seen >= num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch, nbatch=seen,
                                    eval_metric=eval_metric,
                                    locals=locals()))
            seen += 1
        if score_end_callback:
            _fire(score_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=seen,
                                eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        # Pair each batch with its index; zip bounds the stream when a
        # batch budget is given.
        stream = (enumerate(eval_data) if num_batch is None
                  else zip(range(num_batch), eval_data))
        for nbatch, eval_batch in stream:
            self.forward(eval_batch, is_train=False)
            yield (_trim_pad(self.get_outputs(), eval_batch.pad),
                   nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run prediction (reference base_module.py predict)."""
        collected = [[out.copy() for out in outputs]
                     for outputs, _, _ in self.iter_predict(
                         eval_data, num_batch=num_batch, reset=reset)]
        if not collected or not merge_batches:
            return collected
        widths = {len(outs) for outs in collected}
        assert len(widths) == 1, \
            'Cannot merge batches: different number of outputs'
        merged = [nd.concatenate(list(column)) for column in zip(*collected)]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    def fit(self, train_data, eval_data=None, eval_metric='acc',
            epoch_end_callback=None, batch_end_callback=None,
            kvstore='local', optimizer='sgd',
            optimizer_params=(('learning_rate', 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, bulk=None, checkpoint=None, pipeline=None):
        """The training loop (reference base_module.py:376).

        pipeline: optional (num_stages, num_micro) — or None to defer
        to MXNET_TPU_PIPE='stages,micro' — switches to the dp×pipe
        2D-mesh GPipe training mode (module/pipeline_fit.py): the
        symbol's layer chain partitions into `num_stages`
        architecturally identical stages, each stage's parameters live
        only on its pipe row of the mesh, and every step runs the
        fill-drain microbatch schedule inside one donated XLA dispatch
        — composing with ZeRO-1 optimizer-state sharding over the dp
        axis (MXNET_TPU_ZERO=1) and with bulk=K (K steps per dispatch
        through the same lax.scan).  Requires a Module over a
        chain-style symbol and contexts divisible by num_stages;
        monitor/checkpoint do not compose with the pipelined mode.

        bulk: optional K > 1 — run the epoch in K-step fused
        dispatches (Module.bulk_step) with the metric accumulating
        device-resident inside the bulk lax.scan and lr schedules
        evaluated per step, so steps_per_dispatch stretches across
        what the per-batch loop treats as metric/logging boundaries.
        batch_end_callback fires once per dispatch (nbatch advances by
        the group size); an installed monitor, or a metric without a
        device fold, falls back to the per-batch loop.

        checkpoint: optional elastic.CheckpointManager — enables the
        elastic runtime: if its directory holds a checkpoint, training
        RESUMES from the newest intact one (params, optimizer state,
        RNG, partial-epoch metric; the data pipeline fast-forwards to
        the consumed-sample watermark, so continuation is
        bit-identical to the uninterrupted run); each step feeds the
        cadence (async non-blocking snapshots); SIGTERM/SIGINT drains
        the in-flight dispatch, commits a final checkpoint and raises
        elastic.Preempted.  A manager wired with an on_commit push
        hook (fleet_supervisor.CheckpointPusher.attach(mgr)) closes
        the train->serve loop: every commit pushes into a live fleet
        as a canary, the verdicts log at the next step boundary, and
        N consecutive rollbacks raise the pusher's RollbackStop out
        of fit — a diverging run stops burning fleet pushes.  See
        docs/ELASTIC.md."""
        assert num_epoch is not None, 'please specify number of epochs'
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label, for_training=True,
                  force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        validation_metric = validation_metric or eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        from ..parallel import pipeline as pipe_mod
        pipe_spec = pipe_mod.pipe_spec(pipeline)
        if pipe_spec is not None:
            for bad, name in ((monitor, 'monitor'),
                              (checkpoint, 'checkpoint')):
                if bad is not None:
                    raise ValueError(
                        'fit(pipeline=%r): %s= does not compose with '
                        'the pipelined mode yet' % (pipe_spec, name))
            return self._fit_pipeline(
                train_data, pipe_spec, eval_data, eval_metric,
                validation_metric, epoch_end_callback,
                batch_end_callback, eval_end_callback,
                eval_batch_end_callback, begin_epoch, num_epoch, bulk)
        use_bulk = bulk is not None and int(bulk) > 1 and \
            hasattr(self, 'bulk_step') and monitor is None
        if use_bulk and metric_mod.device_fold(eval_metric) is None:
            self.logger.warning(
                'fit(bulk=%d): metric %s has no device fold; '
                'falling back to per-batch metric updates', int(bulk),
                eval_metric.name)
            use_bulk = False
        # AOT ladder warmup hook (BucketingModule): compile every
        # rung's train program up front — through the process-wide
        # exec_cache — so variable-length epochs hit ZERO mid-epoch
        # XLA compile stalls.  Modules without the hook warm lazily.
        warm = getattr(self, '_warmup_for_fit', None)
        if warm is not None:
            warm(bulk=int(bulk) if use_bulk else None,
                 eval_metric=eval_metric if use_bulk else None)
        # elastic resume: restore the newest intact checkpoint and
        # fast-forward the pipeline to its consumed-sample watermark —
        # on the RAW iterator, BEFORE the prefetch wrapper hides the
        # positional jump (ImageIter skips the consumed prefix without
        # re-decoding it) — so the continuation is bit-identical to
        # the uninterrupted run (metric state restores after the
        # epoch's reset below)
        resume_info = None
        signals_installed_here = False
        watched_runtime = None
        batch_size = getattr(train_data, 'batch_size', 0)
        if checkpoint is not None:
            from .. import dist, elastic
            checkpoint.attach(self)
            if not checkpoint._old_handlers and \
                    threading.current_thread() is \
                    threading.main_thread():
                checkpoint.install_signal_handlers()
                signals_installed_here = True
            # coordinated elastic restart: heartbeat-detected peer
            # deaths preempt this manager, so the next step boundary
            # drains, commits the final checkpoint and raises
            # Preempted carrying the dead-rank set
            watched_runtime = dist.runtime()
            if watched_runtime is not None:
                watched_runtime.watch(checkpoint)
            resume_info = checkpoint.restore()
            if resume_info is not None:
                begin_epoch = max(begin_epoch, resume_info.epoch)
                elastic.fast_forward(
                    train_data, epochs=resume_info.epoch,
                    batches=resume_info.batches_in_epoch,
                    batch_size=batch_size)

        # stage upcoming batches device-resident so the H2D copy of
        # batch N+1 overlaps step N's compute (Module overrides; the
        # default is identity)
        train_data = self._wrap_train_iter(train_data)

        def _ckpt_step(nbatch_done, steps, epoch):
            """nbatch_done: ABSOLUTE batches consumed this epoch (the
            resumed epoch's offset included) — the consumed-sample
            watermark the manifest records."""
            if checkpoint is None:
                return
            checkpoint.step_end(epoch=epoch,
                                batches_in_epoch=nbatch_done,
                                batch_size=batch_size, steps=steps,
                                metric=eval_metric)

        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, epoch_end_callback,
                             batch_end_callback, eval_end_callback,
                             eval_batch_end_callback, monitor,
                             begin_epoch, num_epoch, use_bulk, bulk,
                             resume_info, checkpoint, _ckpt_step)
        finally:
            if signals_installed_here:
                # fit armed the handlers, fit disarms them: a Ctrl-C
                # AFTER training must be a normal KeyboardInterrupt,
                # not silently swallowed into a preempt flag no
                # step_end will ever consume
                checkpoint.uninstall_signal_handlers()
            if watched_runtime is not None:
                watched_runtime.unwatch(checkpoint)

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, begin_epoch,
                    num_epoch, use_bulk, bulk, resume_info, checkpoint,
                    _ckpt_step):
        """The epoch loop body of fit() (split out so fit can disarm
        its signal handlers in one finally regardless of how the loop
        exits — normal completion, Preempted, or an error).

        Spans: each pass of the per-step loop is one 'fit.step' (with
        the iterator's 'io.next' it tiles the loop); inside it
        'fit.metric' is the metric fold, which waits for the step's
        outputs ('fit.wait' inside it is that wait alone, so the
        fold's self time is the fold's own work), and 'fit.callback'
        the user's batch_end_callback.

        Overlapped metric pipeline: XLA dispatch is async, but the
        reference loop's per-batch `update_metric` materializes the
        step's outputs — a host sync that re-serializes every step.
        When the module can snapshot (labels, output futures) without
        syncing (Module.metric_snapshot) and no monitor is installed,
        the fold + batch_end_callback DEFER by up to
        MXNET_TPU_TRAIN_STEP_AHEAD batches (gluon
        resolve_step_ahead; 0 restores the serialized loop), so step
        t+1's donated dispatch enqueues while step t computes.  The
        queue drains before anything that CONSUMES the metric — a
        checkpoint boundary that will act (CheckpointManager.
        will_act), the peer-death preempt path, and the epoch-end
        log — so every observable value is bit-identical to the
        serialized loop, later."""
        import os
        from .. import profiler
        from ..gluon.fused import resolve_step_ahead
        from collections import deque
        env_set = bool((os.environ.get('MXNET_TPU_TRAIN_STEP_AHEAD')
                        or '').strip())
        ahead = 0
        if monitor is None and hasattr(self, 'metric_snapshot') and \
                (batch_end_callback is None or env_set):
            # with a batch_end_callback installed the deferral SHIFTS
            # when the callback observes the metric (and when a
            # callback-requested preemption lands) by up to `ahead`
            # batches — reference semantics by default, opt in with
            # the env knob
            ahead = resolve_step_ahead()
        pending = deque()               # (labels, preds, epoch, nbatch)
        steps_done = 0                  # the 'fit.step' spans' number

        def _fold_one():
            labels, preds, ep, nb = pending.popleft()
            with profiler.scope('fit.metric', 'fit') as fold:
                with profiler.scope('fit.wait', 'fit'):
                    _wait_for(preds.values())
                eval_metric.update_dict(labels, preds)
            profiler.add_overlap_stats(
                deferred_metric_folds=1,
                dispatch_wait_ms=fold.seconds * 1e3)
            if batch_end_callback is not None:
                with profiler.scope('fit.callback', 'fit'):
                    _fire(batch_end_callback,
                          BatchEndParam(epoch=ep, nbatch=nb,
                                        eval_metric=eval_metric,
                                        locals=locals()))

        def _drain():
            while pending:
                _fold_one()

        for epoch in range(begin_epoch, num_epoch):
            epoch_start = time.time()
            eval_metric.reset()
            # the resumed epoch continues mid-stream: its partial
            # metric restores and nbatch continues at the watermark so
            # callbacks/manifests see the indices an uninterrupted run
            # would
            epoch_off = 0
            if resume_info is not None and epoch == resume_info.epoch:
                from .. import elastic
                elastic._restore_metric(
                    eval_metric, resume_info.manifest.get('metric'))
                epoch_off = resume_info.batches_in_epoch
            if use_bulk:
                self._fit_epoch_bulk(train_data, int(bulk), eval_metric,
                                     batch_end_callback, epoch,
                                     step_cb=_ckpt_step,
                                     nbatch0=epoch_off,
                                     checkpoint=checkpoint)
            else:
                for nbatch, data_batch in enumerate(train_data):
                    nbatch += epoch_off
                    steps_done += 1
                    with profiler.scope('fit.step', 'fit',
                                        step=steps_done):
                        if monitor is not None:
                            monitor.tic()
                        try:
                            self.forward_backward(data_batch)
                            self.update()
                        except MXNetError:
                            _drain()    # preempt commit reads metric
                            self._peer_death_preempt(
                                checkpoint, _ckpt_step, nbatch, epoch)
                            raise
                        snap = self.metric_snapshot(data_batch.label) \
                            if ahead else None
                        if snap is None:
                            with profiler.scope('fit.metric', 'fit'):
                                with profiler.scope('fit.wait', 'fit'):
                                    _wait_for(self.get_outputs())
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if snap is None:
                            if batch_end_callback is not None:
                                with profiler.scope('fit.callback',
                                                    'fit'):
                                    _fire(batch_end_callback,
                                          BatchEndParam(
                                              epoch=epoch, nbatch=nbatch,
                                              eval_metric=eval_metric,
                                              locals=locals()))
                        else:
                            pending.append(snap + (epoch, nbatch))
                            while len(pending) > ahead:
                                _fold_one()
                            profiler.add_overlap_stats(
                                train_steps=1,
                                steps_ahead=len(pending))
                        if checkpoint is not None and \
                                checkpoint.will_act(1):
                            # the coming boundary consumes the metric
                            # (best-tracking in save / the preemption
                            # commit): flush the deferred folds so the
                            # snapshot sees exactly the serialized
                            # loop's state
                            _drain()
                        _ckpt_step(nbatch + 1, 1, epoch)

            _drain()                    # epoch boundary logs the metric
            for name, val in eval_metric.get_name_value():
                self.logger.info('Epoch[%d] Train-%s=%f', epoch, name, val)
            self.logger.info('Epoch[%d] Time cost=%.3f', epoch,
                             time.time() - epoch_start)

            # Sync a parameter snapshot host-side so checkpoints see the
            # post-epoch weights, then hand it to the epoch callbacks.
            arg_snap, aux_snap = self.get_params()
            self.set_params(arg_snap, aux_snap)
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_snap, aux_snap)
            if eval_data:
                for name, val in self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch):
                    self.logger.info('Epoch[%d] Validation-%s=%f',
                                     epoch, name, val)
            train_data.reset()
            if checkpoint is not None and checkpoint.preempted:
                # a signal that landed AFTER the epoch's last step_end
                # (during validation / callbacks) must not be
                # swallowed: commit the epoch boundary as the final
                # checkpoint and unwind — a resume replays from the
                # start of the next epoch (or exits immediately when
                # this was the last one)
                from .. import elastic
                ckpt = checkpoint.save(epoch=epoch + 1,
                                       batches_in_epoch=0,
                                       batch_size=0, sync=True)
                raise elastic.Preempted(
                    checkpoint.step, ckpt,
                    dead_ranks=checkpoint.preempt_dead_ranks)
        if checkpoint is not None:
            checkpoint.wait()   # drain pending async commits

    def _fit_pipeline(self, train_data, spec, eval_data, eval_metric,
                      validation_metric, epoch_end_callback,
                      batch_end_callback, eval_end_callback,
                      eval_batch_end_callback, begin_epoch, num_epoch,
                      bulk):
        """The dp×pipe GPipe training loop (fit(pipeline=...)).
        Module implements it (module/pipeline_fit.py); other module
        types do not partition into pipeline stages."""
        raise NotImplementedError(
            'fit(pipeline=...) is only supported on Module '
            '(%s does not partition into pipeline stages)'
            % type(self).__name__)

    @staticmethod
    def _peer_death_preempt(checkpoint, step_cb, nbatch, epoch):
        """Convert a cross-host step failure caused by a
        heartbeat-detected PEER death into a coordinated preemption:
        params are still the consistent post-step-(nbatch-1) state
        (the batched cross-host sum fails before ANY key updates), so
        commit the final checkpoint and unwind as Preempted for the
        elastic supervisor.  No-op (the caller re-raises the original
        error) when no checkpoint manager is wired or no peer is
        actually dead."""
        if checkpoint is None or step_cb is None:
            return
        from .. import dist
        dead = dist.detect_dead()
        if not dead:
            return
        checkpoint.request_preempt(dead_ranks=dead)
        step_cb(nbatch, 0, epoch)   # commits + raises Preempted

    def _fit_epoch_bulk(self, train_data, bulk, eval_metric,
                        batch_end_callback, epoch, step_cb=None,
                        nbatch0=0, checkpoint=None):
        """One fit epoch in K-step fused dispatches — ONE loop for
        Module AND BucketingModule (the PR-9 `checkpoint=` kwarg had
        to be patched into two near-identical copies; new kwargs now
        land here once).  Subclasses customize through two hooks:
        `_bulk_group_key(batch)` — consecutive batches group only
        while the key is stable (the bucket ladder returns the rung;
        the default None never splits) — and
        `_bulk_dispatch_group(group, bulk, eval_metric)` — how a
        flushed group executes (bulk_step vs the per-step fallback).

        Callbacks fire once per dispatch with nbatch at the group's
        last batch — the values a per-batch loop would show there.
        step_cb(nbatch_done, steps, epoch): elastic checkpoint hook,
        fired once per dispatch.  nbatch0: batch counter start (the
        resumed epoch's consumed-batch watermark).  checkpoint:
        elastic manager — a dispatch failing on a heartbeat-detected
        peer death converts to a coordinated preemption
        (_peer_death_preempt); nbatch counts only COMPLETED
        dispatches, the consistent state the final checkpoint must
        record."""
        state = {'nbatch': int(nbatch0)}
        group = []
        group_key = [None]

        def flush():
            if not group:
                return
            try:
                self._bulk_dispatch_group(list(group), bulk,
                                          eval_metric)
            except MXNetError:
                self._peer_death_preempt(checkpoint, step_cb,
                                         state['nbatch'], epoch)
                raise
            k = len(group)
            state['nbatch'] += k
            del group[:]
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch,
                                    nbatch=state['nbatch'] - 1,
                                    eval_metric=eval_metric,
                                    locals=locals()))
            if step_cb is not None:
                step_cb(state['nbatch'], k, epoch)

        for data_batch in train_data:
            key = self._bulk_group_key(data_batch)
            if group and key != group_key[0]:
                flush()
            group_key[0] = key
            group.append(data_batch)
            if len(group) >= bulk:
                flush()
        flush()

    def _bulk_group_key(self, data_batch):
        """Group-compatibility key for _fit_epoch_bulk: consecutive
        batches join one dispatch only while it is stable.  The base
        key never splits; BucketingModule returns the ladder rung."""
        return None

    def _bulk_dispatch_group(self, group, bulk, eval_metric):
        """Execute one flushed _fit_epoch_bulk group.  Base policy: a
        single batch runs per-step (a K=1 scan program would be a
        pointless extra compile); anything larger is one bulk_step
        dispatch (trailing partial groups included — the smaller scan
        program compiles once and epochs reuse it)."""
        if len(group) == 1:
            self.forward_backward(group[0])
            self.update()
            self.update_metric(eval_metric, group[0].label)
        else:
            self.bulk_step(batches=group, eval_metric=eval_metric)

    def _wrap_train_iter(self, train_data):
        """Hook for subclasses to decorate the training iterator (e.g.
        device-resident prefetch).  Default: pass through."""
        return train_data

    # -- properties --------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def install_monitor(self, mon):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError
