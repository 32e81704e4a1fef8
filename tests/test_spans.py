"""The span primitive (profiler.scope / span_tail / SPANS) and the spans
and named scopes the training paths carry: one clock with the jax
profiler, a bounded ring that is always on, and no span that waits for
the device."""
import glob
import os
import re
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, sym

BATCH, DIM, STEPS = 4, 10, 3


class _Clock:
    """Hand-set perf_counter, so that intervals are exact."""

    def __init__(self, *ticks):
        self.ticks = list(ticks)

    def perf_counter(self):
        return self.ticks.pop(0)


def _ring(name):
    return list(profiler._RING.get(name, ()))


def test_nesting_gives_parent_and_self_time(monkeypatch):
    profiler.clear()
    # outer 0..10 holds a 1..4 and b 5..7; b holds c 5.5..6.5
    monkeypatch.setattr(profiler, 'time',
                        _Clock(0.0, 1.0, 4.0, 5.0, 5.5, 6.5, 7.0, 10.0))
    with profiler.scope('t.outer') as outer:
        with profiler.scope('t.a'):
            pass
        with profiler.scope('t.b'):
            with profiler.scope('t.c'):
                pass
    assert outer.seconds == 10.0
    assert _ring('t.outer') == [(0.0, 10.0, 5.0, None, None)]
    assert _ring('t.a') == [(1.0, 4.0, 3.0, 't.outer', None)]
    assert _ring('t.b') == [(5.0, 7.0, 1.0, 't.outer', None)]
    assert _ring('t.c') == [(5.5, 6.5, 1.0, 't.b', None)]
    assert profiler.span_tail('t.outer', 1) == [(0.0, 10.0, 5.0)]


def test_step_number_is_inherited():
    profiler.clear()
    with profiler.scope('t.step', step=7):
        with profiler.scope('t.child'):
            with profiler.scope('t.grandchild'):
                pass
    with profiler.scope('t.child'):
        pass
    assert [r[4] for r in _ring('t.step')] == [7]
    assert [r[4] for r in _ring('t.child')] == [7, None]
    assert [r[4] for r in _ring('t.grandchild')] == [7]


def test_span_tail_none_when_short_else_newest():
    profiler.clear()
    assert profiler.span_tail('t.tail', 1) is None
    for _ in range(5):
        with profiler.scope('t.tail'):
            pass
    assert profiler.span_tail('t.tail', 6) is None
    assert profiler.span_tail('t.tail', 0) == []
    ring = _ring('t.tail')
    assert profiler.span_tail('t.tail', 2) == [r[:3] for r in ring[-2:]]
    starts = [s for s, _, _ in profiler.span_tail('t.tail', 5)]
    assert starts == sorted(starts)
    profiler.clear()
    assert profiler.span_tail('t.tail', 1) is None


def test_ring_is_bounded():
    profiler.clear()
    for _ in range(profiler._RING_LEN + 10):
        with profiler.scope('t.many'):
            pass
    assert len(profiler._RING['t.many']) == profiler._RING_LEN
    assert profiler.span_tail('t.many', profiler._RING_LEN + 1) is None
    assert len(profiler.span_tail('t.many', profiler._RING_LEN)) == \
        profiler._RING_LEN


def test_span_head_is_the_oldest_until_the_ring_is_full():
    profiler.clear()
    assert profiler.span_head('t.head', 1) is None
    for _ in range(5):
        with profiler.scope('t.head'):
            pass
    ring = _ring('t.head')
    assert profiler.span_head('t.head', 1) == [ring[0][:3]]
    assert profiler.span_head('t.head', 3) == [r[:3] for r in ring[:3]]
    assert profiler.span_head('t.head', 6) is None
    assert profiler.span_head('t.head', 1) != profiler.span_tail('t.head', 1)
    for _ in range(profiler._RING_LEN):     # the first five are dropped
        with profiler.scope('t.head'):
            pass
    assert profiler.span_head('t.head', 1) is None
    assert len(profiler.span_tail('t.head', 1)) == 1


def test_open_span_is_the_innermost_of_this_thread():
    assert profiler.open_span() is None
    with profiler.scope('t.outer'):
        assert profiler.open_span() == 't.outer'
        with profiler.scope('t.inner'):
            assert profiler.open_span() == 't.inner'
            seen = []
            worker = threading.Thread(
                target=lambda: seen.append(profiler.open_span()))
            worker.start()
            worker.join(10)
            assert seen == [None]
        assert profiler.open_span() == 't.outer'
    assert profiler.open_span() is None


def test_threads_do_not_share_a_stack():
    profiler.clear()
    inside, release = threading.Event(), threading.Event()

    def other():
        with profiler.scope('t.other'):
            inside.set()
            assert release.wait(10)

    worker = threading.Thread(target=other)
    worker.start()
    assert inside.wait(10)
    with profiler.scope('t.main'):      # opened while t.other is open
        pass
    release.set()
    worker.join(10)
    assert not worker.is_alive()
    assert _ring('t.main')[0][3] is None
    other_rec = _ring('t.other')[0]
    assert other_rec[3] is None
    # t.main is no child of t.other: nothing is taken off its self time
    assert other_rec[2] == other_rec[1] - other_rec[0]


def test_span_does_not_wait_for_the_device():
    """A span around an un-awaited jitted call ends when the call has
    been enqueued, long before the program has run."""
    @jax.jit
    def slow(x):
        return jax.lax.fori_loop(0, 60, lambda _, a: jnp.tanh(a @ a), x)

    x = jnp.eye(500, dtype=jnp.float32)
    jax.block_until_ready(slow(x))      # compiled
    profiler.clear()
    with profiler.scope('t.enqueue') as span:
        out = slow(x)
    t0 = time.perf_counter()
    jax.block_until_ready(out)
    waited = time.perf_counter() - t0
    assert waited > 0.05, 'the program is too fast to tell'
    assert span.seconds < waited / 5


def test_chrome_records_keep_their_names(tmp_path):
    """Under profiler_set_state('run') a span is still a Chrome-trace
    record under its own name; with no XLA trace taken it is dumped."""
    import json
    profiler.clear()
    profiler.profiler_set_config(filename=str(tmp_path / 'p.json'))
    profiler.profiler_set_state('run')
    with profiler.scope('t.recorded', 'kvstore'):
        pass
    profiler.profiler_set_state('stop')
    with profiler.scope('t.not_recorded'):
        pass
    with open(profiler.dump_profile()) as f:
        events = json.load(f)['traceEvents']
    spans = {e['name']: e for e in events if e['ph'] == 'X'}
    assert set(spans) == {'t.recorded'}
    assert spans['t.recorded']['cat'] == 'kvstore'
    profiler.profiler_set_config(filename='profile.json')
    profiler.clear()


# ---------------------------------------------------------------------------
# three fit steps and two bulk dispatches of a tiny network
# ---------------------------------------------------------------------------

def _net():
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, name='fc1', num_hidden=8)
    bn = sym.BatchNorm(fc1, name='bn1')
    act = sym.Activation(bn, name='relu1', act_type='relu')
    fc2 = sym.FullyConnected(act, name='fc2', num_hidden=2)
    return sym.SoftmaxOutput(fc2, name='softmax')


def _host_events(trace_dir):
    """name -> [(start_ns, end_ns)] of the mx.* events on /host:CPU."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == '/host:CPU']
    events = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith('mx.'):
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return events


@pytest.fixture(scope='module')
def fit_run(tmp_path_factory):
    """One Module.fit epoch of STEPS batches behind the program's own
    PrefetchToDeviceIter, inside a jax profiler session."""
    rng = np.random.RandomState(0)
    x = rng.rand(STEPS * BATCH, DIM).astype(np.float32)
    y = rng.randint(0, 2, STEPS * BATCH).astype(np.float32)
    train = mx.io.PrefetchToDeviceIter(
        mx.io.NDArrayIter(x, y, batch_size=BATCH,
                          label_name='softmax_label'),
        size=2, device=mx.cpu(0))
    mod = mx.mod.Module(_net(), context=mx.cpu(0))
    trace_dir = str(tmp_path_factory.mktemp('trace'))
    profiler.clear()
    jax.profiler.start_trace(trace_dir)
    try:
        mod.fit(train, num_epoch=1, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
                batch_end_callback=lambda param: None)
    finally:
        jax.profiler.stop_trace()
    return {'ring': {k: list(v) for k, v in profiler._RING.items()},
            'input': profiler.input_stats(), 'mod': mod,
            'bytes': x.nbytes + y.nbytes,
            'events': _host_events(trace_dir)}


def _bound_module():
    mod = mx.mod.Module(_net(), context=mx.cpu(0))
    mod.bind(data_shapes=[('data', (BATCH, DIM))],
             label_shapes=[('softmax_label', (BATCH,))])
    mod.init_params()
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1})
    return mod


@pytest.mark.parametrize('name', ['module.bind', 'module.init_params',
                                  'module.init_optimizer'])
def test_set_up_by_hand_leaves_one_span_each(name):
    profiler.clear()
    mod = _bound_module()
    (start, end, self_s, parent, step), = _ring(name)
    assert parent is None and step is None and end >= start
    assert profiler.SPANS[name].startswith('set-up (')
    # what returns at once opens none: a second bind() that is ignored,
    # weights and an optimizer that are there already
    mod.bind(data_shapes=[('data', (BATCH, DIM))],
             label_shapes=[('softmax_label', (BATCH,))])
    mod.init_params()
    mod.init_optimizer()
    assert len(_ring(name)) == 1
    # set_params writes weights without being set-up
    mod.set_params(*mod.get_params())
    assert len(_ring('module.init_params')) == 1
    mod.init_params(force_init=True)
    assert len(_ring('module.init_params')) == 2


def test_setup_stats_has_every_key():
    profiler.clear()
    empty = profiler.setup_stats()
    assert set(empty) == {
        'import_s', 'bind_s', 'bind_n', 'init_params_s', 'init_params_n',
        'init_optimizer_s', 'init_optimizer_n', 'first_step_s', 'trace_s',
        'lower_s', 'backend_compile_s', 'cache_load_s',
        'persistent_requests', 'persistent_hits', 'persistent_misses'}
    assert empty['import_s'] == mx.import_s > 0
    assert empty['first_step_s'] is None
    assert (empty['bind_s'], empty['bind_n']) == (0, 0)
    # from a ring made by hand: a fit's set-up, then its steps
    for name, spans in (
            ('module.bind', [(0.0, 2.0)]),
            ('module.init_params', [(2.0, 2.5), (9.0, 9.25)]),
            ('module.init_optimizer', [(2.5, 2.75)]),
            ('fit.step', [(3.0, 7.0), (7.0, 7.5)])):
        profiler._RING[name] = deque(
            (t0, t1, t1 - t0, None, None) for t0, t1 in spans)
    st = profiler.setup_stats()
    assert (st['bind_s'], st['bind_n']) == (2.0, 1)
    assert (st['init_params_s'], st['init_params_n']) == (0.75, 2)
    assert (st['init_optimizer_s'], st['init_optimizer_n']) == (0.25, 1)
    assert st['first_step_s'] == 4.0
    # a bulk dispatch is the first step wherever there is one
    profiler._RING['module.bulk_step'] = deque(
        [(8.0, 8.5, 0.5, None, None), (8.5, 8.6, 0.1, None, None)],
        maxlen=profiler._RING_LEN)
    assert profiler.setup_stats()['first_step_s'] == 0.5
    text = profiler.summary(print_out=False)
    assert 'set-up: import_s=' in text and 'first_step_s=0.500' in text
    assert 'persistent_hits=' in text
    profiler.clear()


def test_summary_names_compiles_over_a_second_with_their_span(
        monkeypatch):
    from mxnet_tpu import exec_cache
    monkeypatch.setattr(exec_cache, '_COMPILE_LOG', deque([
        (10.0, 48.25, 'jit(multistep)', 'module.bulk_step'),
        (11.0, 0.5, 'jit(iota)', 'module.bind'),
        (12.0, 1.5, 'jit(zeros)', None)]))
    text = profiler.summary(print_out=False)
    assert 'compiled jit(multistep) in 48.250 s under module.bulk_step' \
        in text
    assert 'compiled jit(zeros) in 1.500 s under no span' in text
    assert 'jit(iota)' not in text


@pytest.fixture(scope='module')
def bulk_run():
    """Two Module.bulk_step dispatches of K=2 staged batches."""
    rng = np.random.RandomState(1)
    mod = _bound_module()
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(BATCH, DIM).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 2, BATCH).astype(np.float32))])
        for _ in range(2)]
    profiler.clear()
    for _ in range(2):
        mod.bulk_step(batches=batches)
    return {k: list(v) for k, v in profiler._RING.items()}


@pytest.mark.parametrize('name,count', [
    ('fit.step', STEPS), ('io.next', STEPS), ('executor.dispatch', STEPS),
    ('io.stage', STEPS), ('module.load_batch', STEPS),
    ('module.host_prep', STEPS), ('fit.metric', STEPS),
    ('fit.callback', STEPS), ('fit.wait', STEPS),
    ('io.host_batch', STEPS + 1),    # the last finds the iterator empty
    # set-up, once a fit: the epoch's end writes the weights again
    # through set_params, which is no 'module.init_params'
    ('module.bind', 1), ('module.init_params', 1),
    ('module.init_optimizer', 1),
])
def test_fit_steps_leave_one_span_each(fit_run, name, count):
    assert name in profiler.SPANS
    assert len(fit_run['ring'][name]) == count
    # and the same spans are in the profiler's trace, as mx.<name>
    assert len(fit_run['events']['mx.' + name]) == count


@pytest.mark.parametrize('name,parent', [
    ('fit.step', None), ('io.next', None), ('io.host_batch', 'io.next'),
    ('io.stage', 'io.next'), ('module.load_batch', 'fit.step'),
    ('module.host_prep', 'fit.step'), ('executor.dispatch', 'fit.step'),
    ('fit.metric', 'fit.step'), ('fit.callback', 'fit.step'),
    ('fit.wait', 'fit.metric'), ('module.bind', None),
    ('module.init_params', None), ('module.init_optimizer', None),
])
def test_fit_spans_know_their_parent_and_step(fit_run, name, parent):
    records = fit_run['ring'][name]
    assert {r[3] for r in records} == {parent}
    if parent in ('fit.step', 'fit.metric') or name == 'fit.step':
        assert [r[4] for r in records] == [1, 2, 3]


def test_the_fold_waits_once_before_it_folds(fit_run):
    """'fit.wait' is the first thing inside each 'fit.metric', and the
    fold's self time is what the wait leaves of it."""
    for fold, wait in zip(fit_run['ring']['fit.metric'],
                          fit_run['ring']['fit.wait']):
        assert fold[0] <= wait[0] <= wait[1] <= fold[1]
        assert fold[2] == pytest.approx(
            (fold[1] - fold[0]) - (wait[1] - wait[0]), abs=1e-9)


def test_a_deferred_fold_waits_inside_its_span_too(monkeypatch):
    """With no batch_end_callback the folds run a step late
    (_fold_one): the same two spans, the wait inside the fold."""
    monkeypatch.delenv('MXNET_TPU_TRAIN_STEP_AHEAD', raising=False)
    rng = np.random.RandomState(3)
    x = rng.rand(STEPS * BATCH, DIM).astype(np.float32)
    y = rng.randint(0, 2, STEPS * BATCH).astype(np.float32)
    mod = mx.mod.Module(_net(), context=mx.cpu(0))
    profiler.clear()
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=BATCH,
                              label_name='softmax_label'),
            num_epoch=1, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1})
    assert profiler.overlap_stats()['overlap_deferred_metric_folds'] == \
        STEPS
    assert [r[3] for r in _ring('fit.wait')] == ['fit.metric'] * STEPS
    assert len(_ring('fit.metric')) == STEPS


def test_top_level_spans_tile_the_fit_loop(fit_run):
    top = sorted(fit_run['ring']['fit.step'] + fit_run['ring']['io.next'])
    wall = top[-1][1] - top[0][0]
    covered = sum(end - start for start, end, *_ in top)
    assert covered <= wall
    assert covered >= 0.95 * wall
    for before, after in zip(top, top[1:]):     # one after the other
        assert before[1] <= after[0]
    # a step's self time is what its five children leave
    for start, end, self_s, _, step in fit_run['ring']['fit.step']:
        children = sum(r[1] - r[0] for n in (
            'module.load_batch', 'module.host_prep', 'executor.dispatch',
            'fit.metric', 'fit.callback') for r in fit_run['ring'][n]
            if r[4] == step)
        assert self_s == pytest.approx(end - start - children, abs=1e-9)


def test_h2d_bytes_are_the_batches_served(fit_run):
    assert fit_run['input']['input_batches'] == STEPS
    assert fit_run['input']['h2d_bytes'] == fit_run['bytes']


def test_each_batch_is_staged_exactly_once():
    """Step by step with an NDArrayIter behind PrefetchToDeviceIter: the
    stager runs `size` batches ahead of those served, and h2d_bytes is one
    batch's bytes for each batch staged so far, never twice."""
    size, batches = 2, 5
    rng = np.random.RandomState(2)
    x = rng.rand(batches * BATCH, DIM).astype(np.float32)
    y = rng.randint(0, 2, batches * BATCH).astype(np.float32)
    per_batch = BATCH * (DIM + 1) * 4
    train = mx.io.PrefetchToDeviceIter(
        mx.io.NDArrayIter(x, y, batch_size=BATCH), size=size,
        device=mx.cpu(1))
    profiler.clear()
    for served in range(1, batches + 1):
        batch = train.next()
        stats = profiler.input_stats()
        assert stats['input_batches'] == served
        assert stats['h2d_bytes'] == min(served + size, batches) * per_batch
        lo = (served - 1) * BATCH
        for arr, rows in ((batch.data[0], x[lo:lo + BATCH]),
                          (batch.label[0], y[lo:lo + BATCH])):
            assert arr._data.devices() == {mx.cpu(1).jax_device()}
            np.testing.assert_array_equal(arr.asnumpy(), rows)
    with pytest.raises(StopIteration):
        train.next()
    assert profiler.input_stats()['h2d_bytes'] == batches * per_batch


def test_input_stall_is_the_sum_of_io_next(fit_run):
    total_ms = sum((end - start) * 1e3
                   for start, end, *_ in fit_run['ring']['io.next'])
    assert fit_run['input']['input_stall_ms'] == pytest.approx(
        total_ms, rel=1e-9)


def test_spans_are_on_the_profilers_clock(fit_run):
    """Read back from the .xplane.pb: the program's spans lie on
    /host:CPU among the profiler's own events, nested by timestamps."""
    events = fit_run['events']

    def inside(inner, outer):
        return all(any(o0 <= i0 and i1 <= o1 for o0, o1 in events[outer])
                   for i0, i1 in events[inner])

    assert len(events['mx.fit.step']) == STEPS
    assert inside('mx.io.host_batch', 'mx.io.next')
    assert inside('mx.io.stage', 'mx.io.next')
    for child in ('mx.module.load_batch', 'mx.module.host_prep',
                  'mx.executor.dispatch', 'mx.fit.metric',
                  'mx.fit.callback'):
        assert inside(child, 'mx.fit.step')
    assert not inside('mx.io.next', 'mx.fit.step')
    # the two clocks agree on every span's length to a fifth of a ms
    ring = sorted(fit_run['ring']['fit.step'])
    for (t0, t1), rec in zip(sorted(events['mx.fit.step']), ring):
        assert (t1 - t0) * 1e-9 == pytest.approx(rec[1] - rec[0],
                                                 abs=2e-4)


def test_fused_step_carries_named_scopes(fit_run):
    """HLO metadata of the compiled step: forward, its transpose (the
    backward), the update, and one scope an operator node."""
    mod = fit_run['mod']
    ex, fu = mod._exec_group.executor, mod._fused_updater
    names = ex._diff_names
    moms, masters, lrs, wds = fu.host_prep_steps(
        [ex.arg_dict[n] for n in names], 1, advance=False)
    text = mod._step_program('single').lower(
        *ex._step_operands(names, (), None, moms, masters),
        *mod._schedule_arrays(lrs, wds)).as_text(debug_info=True)
    scopes = set(re.findall(r'jit\(multistep\)/([^"]*)/[a-z_]+"', text))
    assert 'jvp(forward)/BatchNorm.bn1' in scopes
    assert 'transpose(jvp(forward))/BatchNorm.bn1' in scopes
    assert 'transpose(jvp(forward))/FullyConnected.fc1' in scopes
    assert 'update' in scopes


@pytest.mark.parametrize('name,parent,count', [
    ('module.bulk_step', None, 2),
    ('module.bulk_stack', 'module.bulk_step', 2),
    ('module.host_prep', 'module.bulk_step', 2),
    ('executor.dispatch', 'module.bulk_step', 2),
    ('module.load_batch', 'module.bulk_step', 2),
])
def test_bulk_dispatches_leave_one_span_each(bulk_run, name, parent,
                                             count):
    assert name in profiler.SPANS
    assert [r[3] for r in bulk_run[name]] == [parent] * count


@pytest.mark.parametrize('bulk', [None, 2])
def test_a_warm_up_opens_no_span(bulk):
    """The readers divide a span's time by dispatches: a warm-up runs
    the driver's code and is none."""
    mod = _bound_module()
    profiler.clear()
    assert mod.warmup_fused(bulk=bulk)
    assert mod._step_program(*(('single', 1) if bulk is None
                               else ('stacked', bulk)))
    assert not any(profiler._RING.get(name) for name in profiler.SPANS)


def test_every_fixed_span_name_was_seen(fit_run, bulk_run):
    assert set(profiler.SPANS) <= set(fit_run['ring']) | set(bulk_run)
    assert all(profiler.SPANS.values())
