#!/usr/bin/env python
"""Training-throughput benchmark matrix (reference
example/image-classification/benchmark.py: the --networks sweep whose
published numbers are BASELINE.md's K80 table).

Sweeps model x batch-size on synthetic ImageNet-shaped data using the
fused bulk training step, printing img/s per configuration.

  python examples/image_classification/benchmark.py \\
      --networks resnet-18,resnet-50 --batch-sizes 64,128
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import numpy as np                      # noqa: E402
import mxnet_tpu as mx                  # noqa: E402


def get_symbol(name, dtype):
    from mxnet_tpu.models import resnet
    if name.startswith('resnet-'):
        return resnet.get_symbol(num_classes=1000,
                                 num_layers=int(name.split('-')[1]),
                                 dtype=dtype)
    raise ValueError('unknown network %s (supported: resnet-N)' % name)


def run_one(name, batch, steps, bulk, dtype, image_shape):
    ctx = mx.tpu()
    net = get_symbol(name, dtype)
    mod = mx.mod.Module(net, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (batch,) + image_shape)],
             label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
    mod.init_params(initializer=mx.init.Xavier(rnd_type='gaussian',
                                               factor_type='in',
                                               magnitude=2))
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': 0.9, 'wd': 1e-4,
                                         'multi_precision':
                                             dtype != 'float32'})
    rng = np.random.RandomState(0)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(batch, *image_shape)
                          .astype(np.float32), ctx=ctx)],
        label=[mx.nd.array((rng.rand(batch) * 1000)
                           .astype(np.float32), ctx=ctx)])
        for _ in range(bulk)]

    def step():
        mod.bulk_step(batches=batches)

    step()  # compile + warm
    w = mod._exec_group.executor.arg_dict['fc1_weight']
    float(w._data.ravel()[0])
    tic = time.time()
    for _ in range(steps):
        step()
    float(w._data.ravel()[0])
    return batch * bulk * steps / (time.time() - tic)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--networks', default='resnet-50')
    ap.add_argument('--batch-sizes', default='64,128')
    ap.add_argument('--steps', type=int, default=4)
    ap.add_argument('--bulk', type=int, default=4)
    ap.add_argument('--dtype', default='bfloat16')
    ap.add_argument('--image-shape', default='3,224,224')
    args = ap.parse_args()
    shape = tuple(int(x) for x in args.image_shape.split(','))
    # no TPU: MXNetError here, not an 'error' row per configuration
    device = mx.tpu().jax_device()
    print('device: %s (%s)' % (device, device.device_kind))
    rows = []
    for net in args.networks.split(','):
        for bs in (int(b) for b in args.batch_sizes.split(',')):
            try:
                ips = run_one(net, bs, args.steps, args.bulk,
                              args.dtype, shape)
                rows.append({'network': net, 'batch_size': bs,
                             'dtype': args.dtype,
                             'images_per_sec': round(ips, 1)})
                print(json.dumps(rows[-1]))
            except Exception as e:  # OOM etc: record and continue
                rows.append({'network': net, 'batch_size': bs,
                             'error': str(e)[:200]})
                print(json.dumps(rows[-1]))
    best = max((r for r in rows if 'images_per_sec' in r),
               key=lambda r: r['images_per_sec'], default=None)
    if best:
        print('best: %s' % json.dumps(best))


if __name__ == '__main__':
    main()
