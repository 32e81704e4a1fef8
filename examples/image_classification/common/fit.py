"""Shared training harness for the image-classification examples.

Rebuild of the reference's example/image-classification/common/fit.py
(the script behind every BASELINE table row): argument surface, kvstore
creation, lr-factor schedule, checkpoint/resume, Speedometer, monitor —
wired to this framework's Module.
"""
import argparse
import logging
import os

import mxnet_tpu as mx


def add_fit_args(parser):
    """CLI mirroring the reference (common/fit.py add_fit_args)."""
    train = parser.add_argument_group('Training')
    train.add_argument('--network', type=str, default='mlp')
    train.add_argument('--num-layers', type=int, default=50)
    train.add_argument('--gpus', type=str, default=None,
                       help='unused; kept for script compatibility')
    train.add_argument('--tpus', type=str, default=None,
                       help='e.g. "0" or "0,1,2,3"')
    train.add_argument('--kv-store', type=str, default='local')
    train.add_argument('--num-epochs', type=int, default=10)
    train.add_argument('--lr', type=float, default=0.05)
    train.add_argument('--lr-factor', type=float, default=0.1)
    train.add_argument('--lr-step-epochs', type=str, default='')
    train.add_argument('--optimizer', type=str, default='sgd')
    train.add_argument('--mom', type=float, default=0.9)
    train.add_argument('--wd', type=float, default=1e-4)
    train.add_argument('--batch-size', type=int, default=64)
    train.add_argument('--disp-batches', type=int, default=20)
    train.add_argument('--model-prefix', type=str, default=None)
    train.add_argument('--load-epoch', type=int, default=None)
    train.add_argument('--dtype', type=str, default='float32')
    train.add_argument('--monitor', type=int, default=0)
    train.add_argument('--top-k', type=int, default=0)
    return train


def _contexts(args):
    """--tpus names chips, and a missing one is an error; without it the
    first chip when there is one, else the CPU.  Either way the choice
    is logged."""
    if args.tpus:
        ctxs = [mx.tpu(int(i)) for i in args.tpus.split(',')]
    else:
        ctxs = [mx.tpu(0)] if mx.num_gpus() else [mx.cpu(0)]
    logging.info('training on %s: %s', ctxs,
                 [str(c.jax_device()) for c in ctxs])
    return ctxs


def _lr_scheduler(args, epoch_size, kv):
    if not args.lr_step_epochs:
        return args.lr, None
    begin = args.load_epoch or 0
    step_epochs = [int(x) for x in args.lr_step_epochs.split(',')]
    lr = args.lr
    for s in step_epochs:
        if begin >= s:
            lr *= args.lr_factor
    steps = [epoch_size * (x - begin) for x in step_epochs
             if x - begin > 0]
    sched = mx.lr_scheduler.MultiFactorScheduler(
        step=steps, factor=args.lr_factor) if steps else None
    return lr, sched


def fit(args, network, data_loader):
    """Train `network` on the loaders (reference common/fit.py fit)."""
    logging.basicConfig(level=logging.INFO,
                        format='%(asctime)-15s %(message)s')
    kv = mx.kvstore.create(args.kv_store)
    train, val = data_loader(args, kv)

    epoch_size = max(1, getattr(train, 'num_data', args.batch_size)
                     // args.batch_size)
    lr, lr_sched = _lr_scheduler(args, epoch_size, kv)

    arg_params = aux_params = None
    if args.model_prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)

    mod = mx.mod.Module(network, context=_contexts(args))
    optimizer_params = {'learning_rate': lr, 'wd': args.wd}
    if args.optimizer in ('sgd', 'nag'):
        optimizer_params['momentum'] = args.mom
        optimizer_params['multi_precision'] = args.dtype != 'float32'
    if lr_sched is not None:
        optimizer_params['lr_scheduler'] = lr_sched

    eval_metrics = ['accuracy']
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create('top_k_accuracy',
                                             top_k=args.top_k))
    cbs = [mx.callback.Speedometer(args.batch_size, args.disp_batches)]
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(mx.callback.do_checkpoint(args.model_prefix))
    monitor = mx.mon.Monitor(args.monitor, pattern='.*') \
        if args.monitor > 0 else None

    mod.fit(train, eval_data=val, eval_metric=eval_metrics,
            num_epoch=args.num_epochs,
            begin_epoch=args.load_epoch or 0,
            arg_params=arg_params, aux_params=aux_params,
            kvstore=args.kv_store, optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            initializer=mx.init.Xavier(rnd_type='gaussian',
                                       factor_type='in', magnitude=2),
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs,
            monitor=monitor, allow_missing=True)
    return mod
