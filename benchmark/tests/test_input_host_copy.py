"""Tests of the reader of the input layer's host-copy counter, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The reader against counters made by hand, against a program without the
counter, and the tiny fit-host cell end to end with the entry appended to
a copy of tests/tiny (which itself stays as it is).
"""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import trace_reduce     # noqa: E402
from mxnet_tpu import profiler      # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
METRIC = 'input_host_copy_mib_per_step.fit'


def entry():
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    found, = [m for m in bench['per_layer'] if m['name'] == METRIC]
    return found


def test_the_entry_is_well_formed():
    m = entry()
    assert m == {'name': METRIC, 'unit': 'MiB', 'better': 'lower',
                 'source': 'program_counter',
                 'layer': profiler.SPANS['io.host_batch'],
                 'moves': 'fit_throughput',
                 'workloads': ['resnet50.fit-host']}
    bench = run.read_json(ROOT, 'BENCHMARK.json')
    h2d, = [m for m in bench['per_layer']
            if m['name'] == 'input_h2d_mib_per_step.fit']
    assert m['layer'] == h2d['layer']


def test_reader_by_hand(monkeypatch):
    read = run.Cell('resnet50.fit-host').reader(METRIC).read
    run_ = {'traffic': {'prefetch': 2}}
    stats = {'input_batches': 8, 'host_copy_bytes': 10 * 3 * 2 ** 20,
             'view_batches': 0}
    monkeypatch.setattr(profiler, 'input_stats', lambda: dict(stats))
    assert read(run_) == pytest.approx(3.0)   # 8 served + 2 made ahead
    stats['host_copy_bytes'] = 0              # every batch a view
    assert read(run_) == 0.0
    stats['input_batches'] = 0
    assert read(run_) is None


def test_reader_on_a_program_without_the_counter(monkeypatch):
    read = run.Cell('resnet50.fit-host').reader(METRIC).read
    monkeypatch.setattr(profiler, 'input_stats',
                        lambda: {'input_batches': 8, 'h2d_bytes': 1024})
    assert read({'traffic': {'prefetch': 2}}) is None


@pytest.fixture
def fresh_counters():
    """The program's counters are the process's: start from zero, and
    leave zero to the tests that run after this file."""
    profiler.clear()
    yield
    profiler.clear()


def test_tiny_fit_cell_prints_the_metric(tmp_path, monkeypatch,
                                         fresh_counters):
    """A traced run of the tiny fit cell: the pool is served in order and
    divides evenly, so every batch's images are a view.  Its 8 labels of
    4 bytes start on a 64-byte boundary in every other batch only, and
    where they do not, the CPU runtime's copy of those 32 bytes is all
    the line counts beside the whole batch handed to the device."""
    import mxnet_tpu as mx
    root = str(tmp_path / 'tiny')
    shutil.copytree(TINY, root)
    path = os.path.join(root, 'BENCHMARK.json')
    bench = run.read_json(path)
    real = run.read_json(ROOT, 'BENCHMARK.json')
    for m in real['per_layer']:
        if m['name'] in (METRIC, 'input_h2d_mib_per_step.fit'):
            bench['per_layer'].append(
                dict(m, workloads=['tiny-resnet.fit-host']))
    with open(path, 'w') as f:
        json.dump(bench, f)
    monkeypatch.setattr(trace_reduce, 'reduce_dir', lambda trace_dir: {
        'busy_s': 0.1, 'window_s': 0.3, 'idle_by_span': {}, 'gaps': [],
        'ops': []})
    cell = run.Cell('tiny-resnet.fit-host', root=root, data=root)
    result = run.measure(cell, 2 ** 31 + 83, 0.3, True, [mx.cpu(0)], PEAK)
    assert result['correct'], result['compared']
    stats = profiler.input_stats()
    made = stats['input_batches'] + int(cell.traffic['prefetch'])
    label_bytes = 4 * int(cell.config['batch_per_chip'])
    assert label_bytes == 32
    assert stats['view_batches'] == (made + 1) // 2
    assert stats['host_copy_bytes'] == label_bytes * (made // 2)
    got = result['metrics']
    assert got[METRIC]['unit'] == 'MiB'
    assert got[METRIC]['value'] == pytest.approx(
        stats['host_copy_bytes'] / made / 2.0 ** 20)
    assert got['input_h2d_mib_per_step.fit']['value'] * 2 ** 20 > \
        1000 * label_bytes
