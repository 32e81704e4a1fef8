"""Entry `bulk_step_lm`: Module.bulk_step dispatches of K steps of a
language model, each step whole sequences of token ids with the next
ids as labels, made on the device from the seed and replayed by every
dispatch.

As entries/bulk_step.py in everything else: the first dispatch is the
one the comparison reads (its K steps start from the benchmark's
weights; the reference follows all K), the warm-up dispatch after it
runs the program on its own donated outputs, as every later one does,
and the window's loop enqueues dispatch i+1 and then waits for
dispatch i.

What differs: ids are stored in the traffic's scan_dtype exactly
(float32: bfloat16 cannot hold an id above 256), and the norms are
taken from the optimizer's own arrays in place, one leaf at a time
against the seed's weights kept in host memory: float32 copies of
weights, momenta and the start beside the state would be the model's
state again, and the step program's temporaries stay reserved.
"""
import jax
import jax.numpy as jnp

import check


def feed(h):
    """What the reference follows: the first dispatch's K steps on the
    K staged batches, and the one loss the entry shows, the last."""
    traffic = h.traffic
    k, t = int(traffic['steps_per_dispatch']), int(traffic['seq_len'])
    sequences = int(traffic['sequences_per_step']) * len(h.contexts)
    if sequences * t != h.batch:
        raise ValueError('%d sequences of %d tokens are not the '
                         'configuration\'s %d tokens a step'
                         % (sequences, t, h.batch))
    dtype = jnp.dtype(traffic['scan_dtype'])

    def make(key):
        ids = jax.random.randint(jax.random.fold_in(key, 1),
                                 (k, sequences, t + 1), 0, h.num_classes)
        x = ids[:, :, :-1].reshape(k, h.batch).astype(dtype)
        y = ids[:, :, 1:].reshape(k, h.batch).astype(jnp.float32)
        return [x[i] for i in range(k)], [y[i] for i in range(k)]

    xs, ys = jax.jit(make, out_shardings=h.batch_sharding())(h.key)
    return {'steps': k, 'loss_steps': (k,), 'first_step_state': False,
            'batch_of_step': lambda i: (xs[i - 1], ys[i - 1]),
            'xs': xs, 'ys': ys}


def norms_leaf_by_leaf(h, mod, start):
    """check.state_norms of the optimizer's own arrays (the float32
    master where there is one), read where they lie, one leaf at a
    time against `start`, the seed's weights kept in host memory: the
    device holds no second copy of the model beside its state."""
    ex = mod._exec_group.executor
    fu = mod._fused_updater
    out = {}
    for name in ex._diff_names:
        master = fu.masters.get(name)
        weight = master if master is not None else ex.arg_dict[name]._data
        one = check.state_norms({name: start[name]}, {name: weight},
                                {name: fu.states[name]},
                                h.config['optimizer'])
        for kind, leaf in one.items():
            out.setdefault(kind, {}).update(leaf)
    return out


def run(h):
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    traffic = h.traffic
    scan_dtype = traffic['scan_dtype']
    # the seed's key committed to its device, so that everything made
    # from it is: the reference's donated step then sees at its first
    # call the arrays its later calls see, and compiles once, not twice
    h.key = jax.device_put(h.key, h.devices[0])
    start = jax.device_get(h.initial_params())      # to host memory
    mod = h.make_module()
    h.bind_and_init(mod)
    fed = feed(h)
    k, xs, ys = fed['steps'], fed.pop('xs'), fed.pop('ys')
    batches = [mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                               label=[mx.nd.NDArray(y)])
               for x, y in zip(xs, ys)]
    if not mod._fusable_step():
        raise RuntimeError('the step does not fuse: bulk_step would fall '
                           'back to the per-step loop')
    ex = mod._exec_group.executor

    def dispatch():
        mod.bulk_step(batches=batches, scan_dtype=scan_dtype)
        return h.last_outputs(mod)

    h.mark('inputs staged')
    jax.block_until_ready(dispatch())
    if ex.fused_dispatches != 1:
        raise RuntimeError('bulk_step made %d fused dispatches, not 1'
                           % ex.fused_dispatches)
    h.mark('first dispatch')
    produced = {
        **fed,
        'losses': {k: check.loss_of_outputs(h.last_outputs(mod)[0],
                                            ys[k - 1])},
        'norms': norms_leaf_by_leaf(h, mod, start),
    }
    del start
    h.mark('state read')
    for _ in range(int(traffic['warm_dispatches']) - 1):
        jax.block_until_ready(dispatch())

    h.open_window()
    done, in_flight = 0, None
    while True:
        with h.spans.span('dispatch'):
            newest = dispatch()
        done += 1
        if in_flight is not None:
            with h.spans.span('wait'):
                jax.block_until_ready(in_flight)
            if h.elapsed() >= h.window_seconds():
                break
        in_flight = newest
    with h.spans.span('wait'):
        jax.block_until_ready(newest)
    h.close_window(steps=done * k, dispatches=done)

    # the expert layers' counts are the module's auxiliary state: fold
    # them into the profiler's counters before the module is released
    # (a program without them has nothing to fold)
    getattr(profiler, 'fold_device_counters', lambda: None)()
    produced['optimizer_state_bytes'] = h.optimizer_state_bytes(mod)

    def release():
        batches.clear()
        h.release_module(mod)

    produced['release'] = release
    return produced
