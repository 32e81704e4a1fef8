"""Entry `bulk_step`: Module.bulk_step dispatches of K batches that are
staged on the device, the same K replayed by every dispatch.

The first dispatch is the one the comparison reads: its K steps start
from the benchmark's weights, and what they leave (every weight's
change, every momentum, the last step's loss) is kept as norms for
check.py.  The entry shows no state inside a dispatch, so the reference
follows all K steps.  The warm-up dispatches after it compile the
program a second time, for the committed, donated weights.

The window's loop is the benchmark's own: it enqueues dispatch i+1 and
then waits for dispatch i, so the host runs one dispatch ahead and the
device never waits for it; the window closes at the block_until_ready of
the dispatch in flight when --seconds had passed.
"""
import jax

import check


def feed(h):
    """What the reference follows: the first dispatch's K steps on the
    K staged batches, and the one loss the entry shows, the last."""
    k = int(h.traffic['steps_per_dispatch'])
    xs, ys = h.device_batches(k, h.traffic['scan_dtype'])
    return {'steps': k, 'loss_steps': (k,), 'first_step_state': False,
            'batch_of_step': lambda i: (xs[i - 1], ys[i - 1]),
            'xs': xs, 'ys': ys}


def run(h):
    import mxnet_tpu as mx
    traffic = h.traffic
    scan_dtype = traffic['scan_dtype']
    mod = h.make_module()
    h.bind_and_init(mod)
    fed = feed(h)
    k, xs, ys = fed['steps'], fed.pop('xs'), fed.pop('ys')
    batches = [mx.io.DataBatch(data=[mx.nd.NDArray(x)],
                               label=[mx.nd.NDArray(y)])
               for x, y in zip(xs, ys)]

    def dispatch():
        mod.bulk_step(batches=batches, scan_dtype=scan_dtype)
        return h.last_outputs(mod)

    start = h.initial_params()
    h.mark('inputs staged')
    jax.block_until_ready(dispatch())
    h.mark('first dispatch')
    weights, moms = h.read_state(mod)
    produced = {
        **fed,
        'norms': check.state_norms(start, weights, moms,
                                   h.config['optimizer']),
        'losses': {k: check.loss_of_outputs(h.last_outputs(mod)[0],
                                            ys[k - 1])},
    }
    del start, weights, moms
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

    produced['optimizer_state_bytes'] = h.optimizer_state_bytes(mod)

    def release():
        batches.clear()
        h.release_module(mod)

    produced['release'] = release
    return produced
