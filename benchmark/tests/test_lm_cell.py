"""The language-model cell's entry, work counts and readers on the CPU,
on a tiny benchmark of its own (tests/tiny_lm/): the same entry file,
reference and readers as the cell on the chip, at a size the CPU holds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lm_cell.py -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny_lm')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import work_lm          # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
CELL = 'tiny-qwen3-next.bulk2-seq40-device'


def test_tiny_lm_cell_runs_and_agrees_with_the_reference():
    import mxnet_tpu as mx
    cell = run.Cell(CELL, root=TINY, data=TINY)
    result = run.measure(cell, 2 ** 31 + 5, 0.3, False, [mx.cpu(0)], PEAK)
    assert result['correct'], result['compared']
    assert result['window']['compiles'] == 0
    assert set(result['metrics']) == {'train_throughput', 'peak_hbm_gib',
                                      'setup_s'}
    # the readers of the counters and of the step's share, on the run
    # above (a traced run needs a chip)
    context = {'window': {'steps': 4, 'seconds': 1.0,
                          'dispatches': result['window']['dispatches']},
               'config': cell.config,
               'peak': PEAK, 'chips': 1, 'batch': 80,
               'trace': {'busy_s': 1.0}}
    got = {m['name']: cell.reader(m['name']).read(context)
           for m in cell.metrics('per_layer') if 'lm_' in m['name'] or
           m['name'].startswith(('moe_', 'step_', 'bulk_stack'))}
    assert got['moe_dropped_tokens.bulk'] == 0
    assert 0 < got['moe_held_assignment_share.bulk'] < 100
    assert got['moe_load_max_over_mean.bulk'] >= 1.0
    assert 0 < got['lm_step_mfu.bulk'] < 100
    assert 0 < got['lm_roofline.bulk'] < 100
    # the program's spans of a dispatch, by the accepted readers
    for name in ('step_host_prep_ms.lm', 'bulk_stack_ms.lm',
                 'step_dispatch_ms.lm'):
        assert got[name] > 0, name


def test_work_counts_of_the_published_cell():
    """The step's operations at the cell's sizes, against the sum by
    hand in PERF.md section 4: 6 x 192 M active parameters x 16,384
    tokens, 3.3 TFLOP of causal attention, 0.54 TFLOP of recurrence."""
    config = run.read_json(BENCH, 'configs', 'qwen3-next-80b-a3b.json')
    by_name = {}
    for p in work_lm.forward_products(config, 16384, 8192):
        by_name[p['name']] = by_name.get(p['name'], 0) + 3 * p['flops']
    assert abs(by_name['attention'] / 3.3e12 - 1) < 0.01
    assert abs(by_name['recurrence'] / 0.541e12 - 1) < 0.01
    dense = sum(v for k, v in by_name.items()
                if k not in ('attention', 'recurrence', 'conv'))
    assert abs(dense / (6 * 192.0e6 * 16384) - 1) < 0.01
    total = work_lm.train_flops(config, 16384)
    assert abs(total / 22.7e12 - 1) < 0.01
    least = work_lm.roofline_seconds(config, 16384, 197e12, 819e9, 2)
    assert total / 197e12 <= least < 2 * total / 197e12
