"""Entry `fit`: Module.fit's per-step loop fed from host memory, one
call for warm-up and window alike.

A real mx.io.NDArrayIter serves a pool of float32 host batches made
from the seed; `Cycle` resets it on exhaustion, so that the one epoch
ends only when the window has closed (an epoch boundary syncs and sends
the weights through the host, which a user meets once in thousands of
steps).  `TimedPrefetch` is the program's own PrefetchToDeviceIter at
the depth fit() would give it, with the benchmark's span around next().

fit() is given a batch_end_callback, as train_imagenet.py gives it a
Speedometer; with one installed and no MXNET_TPU_TRAIN_STEP_AHEAD set,
the program folds the metric synchronously every step, so each callback
runs after its step has finished on the device.  During warm-up the
callback reads, after steps 1 to 3, what the comparison needs; in the
window it reads the clock and counts, and closes the window at a
block_until_ready after the first step that ends past --seconds.
"""
import jax

import check


def feed(h):
    """What the reference follows: the first steps of the one fit call,
    each on the next batch of the host pool, with every step's loss and
    the state after the first."""
    follow = int(h.traffic['reference_steps'])
    pool = int(h.traffic['pool_batches'])
    x, y = h.host_pool(pool)

    def batch_of_step(i):       # the pool is cycled
        lo = ((i - 1) % pool) * h.batch
        return x[lo:lo + h.batch], y[lo:lo + h.batch]

    return {'steps': follow, 'loss_steps': tuple(range(1, follow + 1)),
            'first_step_state': True, 'batch_of_step': batch_of_step,
            'x': x, 'y': y}


def run(h):
    import mxnet_tpu as mx
    traffic = h.traffic
    warm = int(traffic['warm_steps'])
    fed = feed(h)
    follow, x, y = fed['steps'], fed.pop('x'), fed.pop('y')
    if warm <= follow:
        raise ValueError('warm_steps must exceed reference_steps')
    inner = mx.io.NDArrayIter(x, y, batch_size=h.batch,
                              label_name='softmax_label')
    state = {'stop': False}

    class Cycle(mx.io.DataIter):
        def __init__(self):
            super().__init__(h.batch)
            self.provide_data = inner.provide_data
            self.provide_label = inner.provide_label

        def reset(self):
            inner.reset()

        def next(self):
            try:
                return inner.next()
            except StopIteration:
                inner.reset()
                return inner.next()

    class TimedPrefetch(mx.io.PrefetchToDeviceIter):
        def next(self):
            if state['stop']:
                raise StopIteration
            with h.spans.span('iter.next'):
                return super().next()

    mod = h.make_module()
    train = TimedPrefetch(Cycle(), size=int(traffic['prefetch']),
                          device=None if len(h.devices) > 1 else
                          h.devices[0])
    metric = mx.metric.create(list(traffic['eval_metric']))
    fold = metric.update_dict

    def timed_fold(*args, **kwargs):
        with h.spans.span('metric.update'):
            return fold(*args, **kwargs)

    metric.update_dict = timed_fold

    keep = {'start': h.initial_params()}    # until the last step followed
    arg, aux = h.nd_params(keep['start'])
    produced = {**fed, 'losses': {}}
    counted = {'steps': 0}

    def block():
        ex = mod._exec_group.executor
        jax.block_until_ready([ex.arg_dict[n]._data
                               for n in ex._diff_names])

    def on_batch(param):
        if state['stop']:
            return
        step = param.nbatch + 1
        if step <= follow:
            produced['losses'][step] = check.loss_of_outputs(
                h.last_outputs(mod)[0], fed['batch_of_step'](step)[1])
            if step in (1, follow) or h.every_step:
                weights, moms = h.read_state(mod)
                norms = check.state_norms(keep['start'], weights, moms,
                                          h.config['optimizer'])
                produced.setdefault('norms_by_step', {})[step] = norms
                if step == 1:
                    produced['norms_first'] = norms
                if step == follow:
                    produced['norms'] = norms
                    keep.clear()
        if step < warm:
            return
        if step == warm:
            block()
            h.open_window()
            return
        with h.spans.span('callback'):
            counted['steps'] += 1
            if h.elapsed() >= h.window_seconds():
                block()
                h.close_window(steps=counted['steps'],
                               dispatches=counted['steps'])
                state['stop'] = True

    name, opt = h.optimizer_params()
    mod.fit(train, eval_metric=metric, num_epoch=1, optimizer=name,
            optimizer_params=opt, initializer=None, arg_params=arg,
            aux_params=aux, batch_end_callback=on_batch)

    produced['optimizer_state_bytes'] = h.optimizer_state_bytes(mod)

    produced['release'] = lambda: h.release_module(mod)
    return produced
