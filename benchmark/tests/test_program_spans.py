"""Tests of the readers that read the program's own spans, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Each new reader against a ring made by hand, against a program without
the ring, and the tiny fit-host and bulk4-device cells end to end with
the new entries appended to a copy of tests/tiny (which itself stays as
it is).
"""
import json
import math
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
import program_spans    # noqa: E402
from mxnet_tpu import profiler      # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
TINY_CELL = {'resnet50.fit-host': 'tiny-resnet.fit-host',
             'resnet50.bulk16-device': 'tiny-resnet.bulk4-device'}

# metric -> (span, the window's count it is a mean over, self time?)
SPAN_METRICS = {
    'fit_loop_self_ms_per_step.fit': ('fit.step', 'steps', True),
    'input_next_ms_per_step.fit': ('io.next', 'steps', False),
    'input_host_batch_ms_per_step.fit': ('io.host_batch', 'steps', False),
    'input_h2d_ms_per_step.fit': ('io.stage', 'steps', False),
    'step_load_batch_ms.fit': ('module.load_batch', 'dispatches', False),
    'step_host_prep_ms.fit': ('module.host_prep', 'dispatches', False),
    'step_host_prep_ms.bulk': ('module.host_prep', 'dispatches', False),
    'bulk_stack_ms.bulk': ('module.bulk_stack', 'dispatches', False),
    'step_dispatch_ms.fit': ('executor.dispatch', 'dispatches', False),
    'step_dispatch_ms.bulk': ('executor.dispatch', 'dispatches', False),
    'metric_ms_per_step.fit': ('fit.metric', 'steps', False),
}
COUNTER_METRIC = 'input_h2d_mib_per_step.fit'


def new_entries():
    """The per-layer entries of BENCHMARK.json that read the program."""
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    names = set(SPAN_METRICS) | {COUNTER_METRIC}
    return [m for m in bench['per_layer'] if m['name'] in names]


def reader(name):
    return run.Cell('resnet50.fit-host').reader(name)


def test_the_entries_are_the_twelve_and_well_formed():
    entries = new_entries()
    assert len(entries) == 12
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    assert bench['per_layer'][-12:] == entries      # appended, in order
    layers = set(profiler.SPANS.values())
    for m in entries:
        cell, = m['workloads']
        fit = m['name'].endswith('.fit')
        assert cell == ('resnet50.fit-host' if fit
                        else 'resnet50.bulk16-device')
        assert m['moves'] == ('fit_throughput' if fit
                              else 'train_throughput')
        assert m['layer'] in layers
        if m['name'] in SPAN_METRICS:
            assert m['layer'] == profiler.SPANS[SPAN_METRICS[m['name']][0]]
            assert (m['unit'], m['source']) == ('ms', 'host_clock')
        else:
            assert (m['unit'], m['source']) == ('MiB', 'program_counter')


@pytest.mark.parametrize('name', sorted(SPAN_METRICS))
def test_span_reader_against_a_ring_made_by_hand(name, monkeypatch):
    span, count, self_time = SPAN_METRICS[name]
    # five spans: (start, end, self seconds, parent, step); the window
    # holds the newest three, of 30, 40 and 50 ms (self 3, 4 and 5 ms)
    ring = deque((10.0 * i, 10.0 * i + 0.01 * i, 0.001 * i, None, None)
                 for i in range(1, 6))
    monkeypatch.setattr(profiler, '_RING', {span: ring})
    window = {'steps': 7, 'dispatches': 7}
    window[count] = 3
    value = reader(name).read({'window': window})
    assert value == pytest.approx(4.0 if self_time else 40.0)
    # fewer spans than the window's steps: nothing to read, no error
    window[count] = 6
    assert reader(name).read({'window': window}) is None
    window[count] = 0
    assert reader(name).read({'window': window}) is None


@pytest.mark.parametrize('name', sorted(SPAN_METRICS))
def test_span_reader_on_a_program_without_the_ring(name, monkeypatch):
    monkeypatch.delattr(profiler, 'span_tail')
    assert reader(name).read({'window': {'steps': 3,
                                         'dispatches': 3}}) is None


def test_mean_ms_by_hand(monkeypatch):
    monkeypatch.setattr(profiler, '_RING', {
        'x': deque([(0.0, 1.0, 0.5, None, None), (2.0, 2.5, 0.1, None, 1)])})
    assert program_spans.mean_ms('x', 2) == pytest.approx(750.0)
    assert program_spans.mean_ms('x', 1) == pytest.approx(500.0)
    assert program_spans.mean_ms('x', 2, self_time=True) == \
        pytest.approx(300.0)
    assert program_spans.mean_ms('x', 3) is None
    assert program_spans.mean_ms('y', 1) is None


def test_counter_reader_by_hand(monkeypatch):
    read = reader(COUNTER_METRIC).read
    run_ = {'traffic': {'prefetch': 2}}
    stats = {'input_batches': 8, 'h2d_bytes': 10 * 3 * 2 ** 20}
    monkeypatch.setattr(profiler, 'input_stats', lambda: dict(stats))
    assert read(run_) == pytest.approx(3.0)   # 8 served + 2 in the buffer
    stats['input_batches'] = 0
    assert read(run_) is None
    stats = {'input_batches': 8}              # a program from before
    assert read(run_) is None


@pytest.fixture
def tiny_with_new_entries(tmp_path):
    """A copy of tests/tiny whose BENCHMARK.json also has the new
    entries, under the tiny cells' names."""
    root = str(tmp_path / 'tiny')
    shutil.copytree(TINY, root)
    path = os.path.join(root, 'BENCHMARK.json')
    bench = run.read_json(path)
    for m in new_entries():
        bench['per_layer'].append(dict(
            m, workloads=[TINY_CELL[w] for w in m['workloads']]))
    with open(path, 'w') as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize('cell_name,suffix', [
    ('tiny-resnet.fit-host', '.fit'), ('tiny-resnet.bulk4-device', '.bulk')])
def test_tiny_cell_prints_every_new_metric(tiny_with_new_entries,
                                           monkeypatch, cell_name, suffix):
    """A traced run of the tiny cell: every new metric of the cell is in
    the result line with a number.  (The CPU's trace has no device
    plane, so the reduction is stood in for; no new reader reads it.)"""
    import mxnet_tpu as mx
    monkeypatch.setattr(trace_reduce, 'reduce_dir', lambda trace_dir: {
        'busy_s': 0.1, 'window_s': 0.3, 'idle_by_span': {}, 'gaps': [],
        'ops': []})
    cell = run.Cell(cell_name, root=tiny_with_new_entries,
                    data=tiny_with_new_entries)
    result = run.measure(cell, 2 ** 31 + 79, 0.3, True,
                         [mx.cpu(i) for i in range(cell.chips)], PEAK)
    assert result['correct'], result['compared']
    expected = {m['name'] for m in new_entries()
                if m['name'].endswith(suffix)}
    assert len(expected) == {'.fit': 9, '.bulk': 3}[suffix]
    got = result['metrics']
    assert expected <= set(got)
    for name in expected:
        assert got[name]['value'] is not None and got[name]['value'] >= 0
    steps = result['window']['steps']
    if suffix == '.fit':
        cfg = cell.config
        rows = int(cfg['batch_per_chip'])
        per_batch = 4 * rows * (1 + math.prod(cfg['data_shape']))
        assert got[COUNTER_METRIC]['value'] == pytest.approx(
            per_batch / 2.0 ** 20)
        # the inside twin of the benchmark's own span around next()
        assert got['input_next_ms_per_step.fit']['value'] <= \
            got['input_stall_ms_per_step.fit']['value']
        assert got['input_host_batch_ms_per_step.fit']['value'] + \
            got['input_h2d_ms_per_step.fit']['value'] <= \
            got['input_next_ms_per_step.fit']['value']
        assert len(profiler.span_tail('fit.step', steps)) == steps

