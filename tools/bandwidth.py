#!/usr/bin/env python
"""Gradient-aggregation bandwidth benchmark.

Rebuild of the reference's tools/bandwidth/measure.py (the KVStore
allreduce-bandwidth BASELINE metric: 11.1 GB/s/GPU at 2 GPUs —
SURVEY.md §6).  Measures the two aggregation paths of this framework:

  * mesh: in-XLA all-reduce (psum) over the device mesh — the path
    training actually uses on TPU (ICI).
  * ps:   host-side parameter-server push+pull round trip
    (kvstore_server.py), for the DCN/host path.

Example:
  python tools/bandwidth.py --test mesh --size-mb 64 --iters 10
"""
import argparse
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), '..')))


def measure_mesh(size_mb, iters):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    devs = jax.devices()
    n = len(devs)
    elems = int(size_mb * 1e6 / 4)
    mesh = Mesh(np.array(devs), ('d',))
    x = jnp.ones((n, elems), jnp.float32)

    @jax.jit
    def allreduce(x):
        def f(v):
            return jax.lax.psum(v, 'd')
        return shard_map(f, mesh=mesh, in_specs=P('d'),
                         out_specs=P())(x)

    allreduce(x).block_until_ready()      # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    # bytes reduced per device per iteration (algorithm bandwidth)
    gb = size_mb / 1e3
    print('devices=%d payload=%.1fMB time=%.2fms algbw=%.2f GB/s/dev'
          % (n, size_mb, dt * 1e3, gb / dt))
    return gb / dt


def _ps_worker_proc(port, size_mb, iters, q):
    """One worker PROCESS (threads would share the GIL with the server
    and each other, understating what separate worker hosts achieve).
    Times its own loop after a server barrier so process startup and
    import cost stay out of the measurement."""
    from mxnet_tpu import kvstore_server as ps
    elems = int(size_mb * 1e6 / 4)
    grad = np.ones((elems,), np.float32)
    c = ps.DistServerClient('127.0.0.1', port, 1)
    c.push('g', grad)   # warm both directions before timing
    c.pull('g')
    c.barrier()
    t0 = time.perf_counter()
    for _ in range(iters):
        # the fused round the training path uses (push_pull_multi):
        # grads up, updated weights back, one round trip
        c.push_pull_multi([('g', grad)])
    q.put(time.perf_counter() - t0)
    c.close()


def measure_ps(size_mb, iters, num_workers):
    import multiprocessing as mp
    from mxnet_tpu import kvstore_server as ps
    srv = ps.KVStoreServer(0, num_workers, sync_mode=True)
    t = threading.Thread(target=srv.run, daemon=True)
    t.start()
    elems = int(size_mb * 1e6 / 4)
    ctl = ps.DistServerClient('127.0.0.1', srv.port, 1)
    ctl.init('g', np.zeros((elems,), np.float32))

    ctx = mp.get_context('spawn')
    q = ctx.Queue()
    procs = [ctx.Process(target=_ps_worker_proc,
                         args=(srv.port, size_mb, iters, q))
             for _ in range(num_workers)]
    for p in procs:
        p.start()
    # poll with liveness checks: a worker that dies before q.put()
    # must surface as an immediate error, not a 600 s queue timeout
    # that masks its traceback
    import queue as _queue
    dts = []
    deadline = time.time() + 600
    while len(dts) < len(procs):
        try:
            dts.append(q.get(timeout=5))
        except _queue.Empty:
            dead = [p for p in procs
                    if not p.is_alive() and p.exitcode not in (0, None)]
            if dead:
                raise RuntimeError(
                    'ps worker process failed (exitcode %s)'
                    % dead[0].exitcode)
            if time.time() > deadline:
                raise RuntimeError('ps workers timed out')
    for p in procs:
        p.join()
    if any(p.exitcode != 0 for p in procs):
        raise RuntimeError('ps worker process failed')
    dt = max(dts) / iters
    ctl.stop_servers()
    gb = 2 * size_mb / 1e3      # push + pull
    print('workers=%d payload=%.1fMB time=%.2fms bw=%.2f GB/s/worker'
          % (num_workers, size_mb, dt * 1e3, gb / dt))
    return gb / dt


def _cliff_model():
    from mxnet_tpu import sym
    data = sym.Variable('data')
    net = sym.FullyConnected(data, num_hidden=1024, name='fc1')
    net = sym.Activation(net, act_type='relu')
    net = sym.FullyConnected(net, num_hidden=1024, name='fc2')
    net = sym.Activation(net, act_type='relu')
    net = sym.FullyConnected(net, num_hidden=10, name='fc3')
    return sym.SoftmaxOutput(net, name='softmax')


def _cliff_train(kvstore, batch, steps):
    """samples/sec for the same model+batch under a given kvstore mode
    (the PS-vs-fused training cliff, docs/PERF.md)."""
    import mxnet_tpu as mx
    net = _cliff_model()
    mod = mx.mod.Module(net, label_names=['softmax_label'])
    mod.bind(data_shapes=[mx.io.DataDesc('data', (batch, 784))],
             label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
    np.random.seed(0)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(kvstore=kvstore, optimizer='sgd',
                       optimizer_params={'learning_rate': 0.01})
    rs = np.random.RandomState(1)
    batchobj = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(batch, 784).astype(np.float32))],
        label=[mx.nd.array((rs.rand(batch) * 10).astype(np.float32))])

    def sync():
        float(mod._exec_group.executor.arg_dict['fc1_weight']
              ._data.ravel()[0])

    for _ in range(3):
        mod.forward_backward(batchobj)
        mod.update()
    sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(batchobj)
        mod.update()
    sync()
    return batch * steps / (time.perf_counter() - t0)


def measure_train_cliff(batch, steps):
    """Quantifies the dist-PS fusion cliff: single-process fused
    kvstore='device' vs 2-process dist_sync through the localhost PS
    (launch.py local), same model and per-worker batch."""
    import subprocess
    import sys as _sys
    rate_fused = _cliff_train('device', batch, steps)
    print('single-process kvstore=device: %.0f samples/s' % rate_fused)

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    # apples-to-apples: the fused baseline above is pinned to cpu, so
    # the workers must be too, even if the caller exported a platform
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.path.dirname(here) + os.pathsep + \
        env.get('PYTHONPATH', '')
    for stale in ('DMLC_PS_ROOT_URI', 'DMLC_PS_ROOT_PORT', 'DMLC_ROLE'):
        env.pop(stale, None)
    res = subprocess.run(
        [_sys.executable, os.path.join(here, 'launch.py'),
         '-n', '2', '-s', '1', '--launcher', 'local', _sys.executable,
         os.path.abspath(__file__), '--test', 'train-cliff-worker',
         '--iters', str(steps), '--batch', str(batch)],
        capture_output=True, text=True, timeout=900, env=env)
    if res.returncode != 0:
        raise RuntimeError('dist run failed: %s\n%s'
                           % (res.stdout, res.stderr))
    rates = [float(line.split()[1]) for line in res.stdout.splitlines()
             if line.startswith('CLIFF ')]
    assert len(rates) == 2, res.stdout
    agg = sum(rates)
    print('2-process dist_sync PS:        %.0f samples/s aggregate '
          '(per-worker %s)' % (agg, ['%.0f' % r for r in rates]))
    print('fusion cliff: fused/dist = x%.1f   (per-worker x%.1f)'
          % (rate_fused / agg, rate_fused / (agg / 2)))
    return rate_fused, agg


def _train_cliff_worker(batch, steps):
    rate = _cliff_train('dist_sync', batch, steps)
    print('CLIFF %.2f' % rate, flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--test', choices=['mesh', 'ps', 'train-cliff',
                                      'train-cliff-worker'],
                   default='mesh')
    p.add_argument('--size-mb', type=float, default=64.0)
    p.add_argument('--iters', type=int, default=10)
    p.add_argument('--batch', type=int, default=256)
    p.add_argument('-n', '--num-workers', type=int, default=2)
    args = p.parse_args()
    if args.test == 'mesh':
        measure_mesh(args.size_mb, args.iters)
    elif args.test == 'ps':
        measure_ps(args.size_mb, args.iters, args.num_workers)
    elif args.test == 'train-cliff':
        # apples-to-apples on one backend: the cliff isolates the
        # kvstore path difference, not chip dispatch
        import jax
        jax.config.update('jax_platforms', 'cpu')
        measure_train_cliff(args.batch, args.iters)
    else:
        import jax
        jax.config.update('jax_platforms', 'cpu')
        _train_cliff_worker(args.batch, args.iters)


if __name__ == '__main__':
    main()
