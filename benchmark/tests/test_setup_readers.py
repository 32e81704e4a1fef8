"""Tests of the readers of set-up, of the fold's wait and of the
dispatch periods, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Each reader against a ring and stats made by hand, against a program
without them, the six intervals of set-up summing to the run's setup_s,
and the tiny fit-host and bulk4-device cells end to end with the new
entries appended to a copy of tests/tiny (which itself stays as it is).
"""
import json
import os
import shutil
import sys
from collections import deque

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import trace_reduce     # noqa: E402
import program_setup    # noqa: E402
from mxnet_tpu import exec_cache, profiler      # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
TINY_CELL = {'resnet50.fit-host': 'tiny-resnet.fit-host',
             'resnet50.bulk16-device': 'tiny-resnet.bulk4-device'}
SETUP_LAYER = profiler.SPANS['module.bind']
STEP_LAYER = profiler.SPANS['executor.dispatch']
CACHE_LAYER = 'compile caches (exec_cache, jax persistent cache)'

# setup_stats() made by hand, and what each metric reads of it
STATS = {'import_s': 3.5, 'bind_s': 7.25, 'bind_n': 1,
         'init_params_s': 2.0, 'init_params_n': 1,
         'init_optimizer_s': 0.25, 'init_optimizer_n': 1,
         'first_step_s': 25.0, 'trace_s': 6.0, 'lower_s': 1.5,
         'backend_compile_s': 9.0, 'cache_load_s': 4.0,
         'persistent_requests': 40, 'persistent_hits': 30,
         'persistent_misses': 8}
SETUP_S = 50.0
# metric -> (value from STATS and SETUP_S, layer, unit, source)
SETUP_METRICS = {
    'setup_import_s': (3.5, SETUP_LAYER, 's', 'host_clock'),
    'setup_bind_s': (7.25, SETUP_LAYER, 's', 'host_clock'),
    'setup_init_params_s': (2.0, SETUP_LAYER, 's', 'host_clock'),
    'setup_init_optimizer_s': (0.25, SETUP_LAYER, 's', 'host_clock'),
    'setup_first_step_s': (25.0, STEP_LAYER, 's', 'host_clock'),
    'setup_outside_program_s': (12.0, SETUP_LAYER, 's', 'host_clock'),
    'setup_trace_lower_s': (7.5, CACHE_LAYER, 's', 'program_counter'),
    'setup_compile_s': (5.0, CACHE_LAYER, 's', 'program_counter'),
    'setup_cache_load_s': (4.0, CACHE_LAYER, 's', 'program_counter'),
    'setup_cache_hit_share': (75.0, CACHE_LAYER, '%', 'program_counter'),
}
DISJOINT = ('setup_import_s', 'setup_bind_s', 'setup_init_params_s',
            'setup_init_optimizer_s', 'setup_first_step_s',
            'setup_outside_program_s')
FIT_METRICS = ('metric_wait_ms_per_step.fit',
               'metric_fold_self_ms_per_step.fit')
PERIOD_METRIC = 'dispatch_period_max_over_median.bulk'
NEW = set(SETUP_METRICS) | set(FIT_METRICS) | {PERIOD_METRIC}


def new_entries(also=()):
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    return [m for m in bench['per_layer']
            if m['name'] in NEW or m['name'] in also]


def reader(name):
    return run.Cell('resnet50.fit-host').reader(name)


def test_the_entries_are_appended_and_well_formed():
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    entries = new_entries()
    assert len(entries) == len(NEW) == 13
    assert bench['per_layer'][-13:] == entries      # appended, in order
    cells = [w['name'] for w in bench['workloads']]
    bulk = [c for c in cells if c != 'resnet50.fit-host']
    by_name = {m['name']: m for m in entries}
    for name, (_, layer, unit, source) in SETUP_METRICS.items():
        m = by_name[name]
        assert (m['layer'], m['unit'], m['source']) == (layer, unit, source)
        assert m['moves'] == 'setup_s' and m['workloads'] == cells
        assert m['better'] == ('higher' if unit == '%' else 'lower')
    for name in FIT_METRICS:
        m = by_name[name]
        assert m['layer'] == profiler.SPANS['fit.wait'] == \
            profiler.SPANS['fit.metric']
        assert (m['unit'], m['source'], m['moves'], m['workloads']) == (
            'ms', 'host_clock', 'fit_throughput', ['resnet50.fit-host'])
    m = by_name[PERIOD_METRIC]
    assert m['layer'] == profiler.SPANS['module.bulk_step']
    assert (m['unit'], m['better'], m['moves'], m['workloads']) == (
        'ratio', 'lower', 'train_throughput', bulk)
    # every span the program names has a metric that reads it
    assert set(profiler.SPANS.values()) <= {
        m['layer'] for m in bench['per_layer']}


@pytest.mark.parametrize('name', sorted(SETUP_METRICS))
def test_setup_reader_against_stats_made_by_hand(name, monkeypatch):
    monkeypatch.setattr(profiler, 'setup_stats', lambda: dict(STATS))
    assert reader(name).read({'setup_s': SETUP_S}) == pytest.approx(
        SETUP_METRICS[name][0])


@pytest.mark.parametrize('name', sorted(SETUP_METRICS))
def test_setup_reader_on_a_program_without_setup_stats(name, monkeypatch):
    monkeypatch.delattr(profiler, 'setup_stats')
    assert reader(name).read({'setup_s': SETUP_S}) is None


def test_the_six_intervals_sum_to_the_runs_setup(monkeypatch):
    monkeypatch.setattr(profiler, 'setup_stats', lambda: dict(STATS))
    context = {'setup_s': SETUP_S}
    assert sum(reader(n).read(context) for n in DISJOINT) == \
        pytest.approx(SETUP_S)
    assert program_setup.outside(context) == pytest.approx(12.0)


def test_no_first_step_no_sum(monkeypatch):
    """A ring that wrapped (or a run that made no step) has no first
    step: neither it nor what is left of set-up is reported."""
    stats = dict(STATS, first_step_s=None)
    monkeypatch.setattr(profiler, 'setup_stats', lambda: stats)
    context = {'setup_s': SETUP_S}
    assert reader('setup_first_step_s').read(context) is None
    assert reader('setup_outside_program_s').read(context) is None
    assert reader('setup_bind_s').read(context) == 7.25


def test_no_request_no_hit_share(monkeypatch):
    stats = dict(STATS, persistent_requests=0, persistent_hits=0)
    monkeypatch.setattr(profiler, 'setup_stats', lambda: stats)
    assert reader('setup_cache_hit_share').read({}) is None


def test_setup_readers_read_the_programs_own_ring(monkeypatch):
    """Through the real setup_stats: spans made by hand in the ring."""
    monkeypatch.setattr(profiler, '_RING', {
        'module.bind': deque([(1.0, 3.0, 2.0, None, None)]),
        'module.init_params': deque([(3.0, 3.5, 0.5, None, None)]),
        'module.init_optimizer': deque([(3.5, 3.75, 0.25, None, None)]),
        'module.bulk_step': deque([(5.0, 9.0, 4.0, None, None),
                                   (9.5, 9.75, 0.25, None, None)],
                                  maxlen=8)})
    import mxnet_tpu
    context = {'setup_s': 10.0 + mxnet_tpu.import_s}
    assert reader('setup_bind_s').read(context) == 2.0
    assert reader('setup_init_params_s').read(context) == 0.5
    assert reader('setup_init_optimizer_s').read(context) == 0.25
    assert reader('setup_first_step_s').read(context) == 4.0
    assert reader('setup_import_s').read(context) == mxnet_tpu.import_s
    assert reader('setup_outside_program_s').read(context) == \
        pytest.approx(10.0 - 6.75)


def fold_ring():
    """Five folds of 10 i ms, each waiting 8 i ms first."""
    waits = deque((10.0 * i, 10.0 * i + 0.008 * i, 0.008 * i,
                   'fit.metric', i) for i in range(1, 6))
    folds = deque((10.0 * i, 10.0 * i + 0.010 * i, 0.002 * i, 'fit.step', i)
                  for i in range(1, 6))
    return {'fit.wait': waits, 'fit.metric': folds}


def test_fold_readers_against_a_ring_made_by_hand(monkeypatch):
    monkeypatch.setattr(profiler, '_RING', fold_ring())
    context = {'window': {'steps': 3}}      # the newest three: i = 3, 4, 5
    wait = reader('metric_wait_ms_per_step.fit').read(context)
    own = reader('metric_fold_self_ms_per_step.fit').read(context)
    whole = reader('metric_ms_per_step.fit').read(context)
    assert (wait, own) == (pytest.approx(32.0), pytest.approx(8.0))
    assert wait + own == pytest.approx(whole)
    context = {'window': {'steps': 6}}      # more steps than spans
    assert reader('metric_wait_ms_per_step.fit').read(context) is None
    assert reader('metric_fold_self_ms_per_step.fit').read(context) is None


def test_fold_readers_on_a_program_without_the_wait(monkeypatch):
    """The parent has 'fit.metric' and no 'fit.wait': its self time is
    wait and fold together, and is not reported as the fold's own."""
    ring = fold_ring()
    del ring['fit.wait']
    monkeypatch.setattr(profiler, '_RING', ring)
    context = {'window': {'steps': 3}}
    assert reader('metric_wait_ms_per_step.fit').read(context) is None
    assert reader('metric_fold_self_ms_per_step.fit').read(context) is None
    monkeypatch.delattr(profiler, 'span_tail')
    assert reader('metric_fold_self_ms_per_step.fit').read(context) is None


def dispatch_ring(starts):
    return {'module.bulk_step': deque(
        (s, s + 0.005, 0.001, None, None) for s in starts)}


@pytest.mark.parametrize('starts,dispatches,expected', [
    # warm-up at 0 and 2, then the window: two dispatches back to back,
    # then one every 1.5 s
    ([0.0, 2.0, 10.0, 10.01, 11.5, 13.0, 14.5, 16.0], 6, 1.0),
    # the same with a stall of a second before the last but one
    ([0.0, 2.0, 10.0, 10.01, 11.5, 13.0, 15.5, 17.0], 6, 2.5 / 1.5),
    # three dispatches: one period beside the window's first
    ([10.0, 10.01, 11.5], 3, 1.0),
    ([10.0, 10.01], 2, None),               # too few
    ([10.0, 10.01, 11.5], 4, None),         # fewer spans than dispatches
])
def test_dispatch_period_by_hand(monkeypatch, starts, dispatches,
                                 expected):
    monkeypatch.setattr(profiler, '_RING', dispatch_ring(starts))
    value = reader(PERIOD_METRIC).read(
        {'window': {'dispatches': dispatches}})
    assert value == (None if expected is None
                     else pytest.approx(expected))


def test_dispatch_period_on_a_program_without_the_ring(monkeypatch):
    monkeypatch.delattr(profiler, 'span_tail')
    assert reader(PERIOD_METRIC).read(
        {'window': {'dispatches': 6}}) is None


@pytest.fixture
def tiny_with_new_entries(tmp_path):
    """A copy of tests/tiny whose BENCHMARK.json also has the new
    entries (and the whole fold's, which the two halves sum to), under
    the tiny cells' names."""
    root = str(tmp_path / 'tiny')
    shutil.copytree(TINY, root)
    path = os.path.join(root, 'BENCHMARK.json')
    bench = run.read_json(path)
    for m in new_entries(also=('metric_ms_per_step.fit',)):
        bench['per_layer'].append(dict(
            m, workloads=[TINY_CELL[w] for w in m['workloads']
                          if w in TINY_CELL]))
    with open(path, 'w') as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize('cell_name,others', [
    ('tiny-resnet.fit-host', FIT_METRICS),
    ('tiny-resnet.bulk4-device', (PERIOD_METRIC,))])
def test_tiny_cell_prints_every_new_metric(tiny_with_new_entries,
                                           monkeypatch, cell_name, others):
    """A traced run of the tiny cell: every new metric of the cell is in
    the result line, and the six intervals sum to the run's set-up.
    (The CPU's trace has no device plane, so the reduction is stood in
    for; no new reader reads it.)"""
    import mxnet_tpu as mx
    monkeypatch.setattr(trace_reduce, 'reduce_dir', lambda trace_dir: {
        'busy_s': 0.1, 'window_s': 0.3, 'idle_by_span': {}, 'gaps': [],
        'ops': []})
    seen = {}
    harness_init = run.Harness.__init__

    def keep_harness(self, *args, **kwargs):
        harness_init(self, *args, **kwargs)
        seen['harness'] = self

    monkeypatch.setattr(run.Harness, '__init__', keep_harness)
    profiler.clear()        # the ring of this process's earlier tests
    exec_cache.clear()      # and what jax had reported of them
    cell = run.Cell(cell_name, root=tiny_with_new_entries,
                    data=tiny_with_new_entries)
    result = run.measure(cell, 2 ** 31 + 79, 0.3, True,
                         [mx.cpu(i) for i in range(cell.chips)], PEAK)
    assert result['correct'], result['compared']
    got = {k: v['value'] for k, v in result['metrics'].items()}
    assert set(SETUP_METRICS) | set(others) <= set(got)
    assert not (NEW - set(SETUP_METRICS) - set(others)) & set(got)
    # the harness's clock started when run.py was imported, long before
    # this test: what lies outside the program holds that too
    setup_s = seen['harness'].setup_s
    assert sum(got[n] for n in DISJOINT) == pytest.approx(setup_s,
                                                          abs=1e-6)
    stats = profiler.setup_stats()
    assert (stats['bind_n'], stats['init_params_n'],
            stats['init_optimizer_n']) == (1, 1, 1)
    for name in DISJOINT:
        assert got[name] > 0, name
    assert got['setup_trace_lower_s'] > 0 and got['setup_compile_s'] > 0
    assert got['setup_cache_load_s'] == 0       # the CPU keeps no cache
    assert got['setup_cache_hit_share'] == 0
    if cell_name.endswith('fit-host'):
        assert got['metric_wait_ms_per_step.fit'] + \
            got['metric_fold_self_ms_per_step.fit'] == pytest.approx(
                got['metric_ms_per_step.fit'], abs=1e-6)
    else:
        assert got[PERIOD_METRIC] >= 1.0
