"""The looped-decoder cell's configuration, work counts and readers on
the CPU, on a tiny benchmark of its own (tests/tiny_ouro/): the same
entry file (entries/bulk_step_lm.py), reference and readers as the cell
on the chip, at a size the CPU holds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_loop_cell.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny_ouro')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import trace_reduce     # noqa: E402
import work_ouro        # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
CELL = 'tiny-ouro.bulk2-seq64-device'
PUBLISHED = 'ouro-2.6b.bulk2-seq8k-device'
NEW_READERS = ('loop_step_mfu.bulk', 'loop_roofline.bulk',
               'loop_saved_mib.loop')


def published_config():
    return run.read_json(BENCH, 'configs', 'ouro-2.6b.json')


def test_tiny_loop_cell_runs_and_agrees_with_the_reference():
    import mxnet_tpu as mx
    cell = run.Cell(CELL, root=TINY, data=TINY)
    result = run.measure(cell, 2 ** 31 + 17, 0.3, False, [mx.cpu(0)], PEAK)
    assert result['correct'], result['compared']
    assert result['window']['compiles'] == 0
    assert set(result['metrics']) == {'train_throughput', 'peak_hbm_gib',
                                      'setup_s'}
    # every per-layer reader of the new cell but the trace's own, on the
    # run above (a traced run needs a chip)
    context = {'window': {'steps': 4, 'seconds': 1.0,
                          'dispatches': result['window']['dispatches']},
               'config': cell.config, 'peak': PEAK, 'chips': 1,
               'batch': 128, 'trace': {'busy_s': 1.0}}
    got = {m['name']: cell.reader(m['name']).read(context)
           for m in cell.metrics('per_layer') if 'workloads' in m and
           not m['name'].startswith(('device_idle', 'dispatch_gap'))}
    assert 0 < got['loop_step_mfu.bulk'] < 100
    assert 0 < got['loop_roofline.bulk'] < 100
    # the stream where 4 half layers and the final norm take it, in 4
    # passes: 128 tokens of 64 float32
    assert got['loop_saved_mib.loop'] == 4 * 5 * 128 * 64 * 4 / 2.0 ** 20
    for name in ('step_host_prep_ms.lm', 'bulk_stack_ms.lm',
                 'step_dispatch_ms.lm'):
        assert got[name] > 0, name


def test_the_new_readers_on_the_recorded_trace():
    """testdata/small.xplane.pb reduced as a traced run's would be: the
    roofline's share reads the device's busy time from it, the step's
    share the window's time."""
    reduced = trace_reduce.reduce(trace_reduce.load(
        os.path.join(BENCH, 'testdata', 'small.xplane.pb')))
    config = published_config()
    cell = run.Cell(PUBLISHED)
    context = {'window': {'steps': 3, 'seconds': reduced['window_s']},
               'config': config, 'peak': PEAK, 'chips': 1, 'batch': 8192,
               'trace': reduced}
    least = work_ouro.roofline_seconds(config, 8192, 1e12, 1e11, 2)
    assert cell.reader('loop_roofline.bulk').read(context) == pytest.approx(
        100 * least / (reduced['busy_s'] / 3))
    assert cell.reader('loop_step_mfu.bulk').read(context) == pytest.approx(
        100 * 3 * work_ouro.train_flops(config, 8192) /
        (reduced['window_s'] * 1e12))
    assert cell.reader('loop_roofline.bulk').read(
        dict(context, trace=None)) is None


def test_the_new_readers_say_nothing_of_another_model(monkeypatch):
    """On a configuration that loops no layers and on the parent's
    program, whose profiler has no looped_decoder_stats() (the driver
    runs these files over it), they return None and do not raise; the
    accepted language-model readers return None on this configuration."""
    from mxnet_tpu import profiler
    mine = published_config()
    cell = run.Cell(PUBLISHED)
    context = {'window': {'steps': 4, 'seconds': 1.0}, 'peak': PEAK,
               'chips': 1, 'batch': 8192, 'trace': {'busy_s': 1.0}}
    for other in ('qwen3-next-80b-a3b', 'kanana-2-30b-a3b', 'trinity-mini'):
        config = run.read_json(BENCH, 'configs', other + '.json')
        for name in NEW_READERS:
            assert cell.reader(name).read(dict(context, config=config)) \
                is None
    for name in ('loop_step_mfu.bulk', 'loop_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=mine)) > 0
    for name in ('lm_step_mfu.bulk', 'lm_roofline.bulk', 'mla_step_mfu.bulk',
                 'mla_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=mine)) is None
    read = cell.reader('loop_saved_mib.loop').read
    monkeypatch.setattr(profiler, 'looped_decoder_stats', lambda: {
        'lowerings': 0, 'saved_bytes': 0, 'shapes': []})
    assert read(dict(context, config=mine)) is None     # nothing lowered
    monkeypatch.delattr(profiler, 'looped_decoder_stats')
    assert read(dict(context, config=mine)) is None


def test_the_published_cell_is_well_formed():
    cell = run.Cell(PUBLISHED)
    c, t = cell.config, cell.traffic
    assert cell.chips == 1 and t['entry'] == 'bulk_step_lm'
    assert int(t['sequences_per_step']) * int(t['seq_len']) == \
        c['batch_per_chip']
    assert set(cell.limits) == {'loss', 'delta_median', 'delta_worst'}
    names = [m['name'] for m in cell.metrics('per_layer')]
    assert [n for n in names if n.endswith('.loop') or
            n.startswith('loop_')] == list(NEW_READERS)
    # the shared readers under the accepted language-model cell's names
    assert {'compile_s', 'compiles_in_window', 'optimizer_state_mib_per_chip',
            'dispatch_period_max_over_median.bulk', 'setup_first_step_s',
            'device_idle_share.lm', 'dispatch_gap_ms.lm',
            'step_host_prep_ms.lm', 'bulk_stack_ms.lm', 'step_dispatch_ms.lm',
            'swa_attention_visited_over_needed.bulk'} <= set(names)
    assert [m['name'] for m in cell.metrics('end_to_end')] == [
        'train_throughput', 'peak_hbm_gib', 'setup_s']
    for m in cell.metrics('per_layer'):
        cell.reader(m['name'])          # every one has its reader file
    # the program's and the reference's arguments are one shape
    prog, ref = c['program']['arguments'], c['reference']['arguments']
    for key, value in ref.items():
        if key != 'vocab_size':
            assert prog[key] == value, key
        if key in c and key != 'seq_len':
            assert c[key] == value, key
    assert prog['num_classes'] == ref['vocab_size'] == c['num_classes']
    # the cut: one of 8 pipeline stages, every layer of the same kind
    assert c['num_hidden_layers'] * 8 == c['published']['num_hidden_layers']
    assert c['layer_types'] == c['published']['layer_types'][:6]


# the catalog row's `config` (model-configs/architectures.jsonl, Ouro-2.6B)
CATALOG = dict(
    head_dim=128, hidden_act='silu', hidden_size=2048,
    intermediate_size=5632, layer_types=['full_attention'] * 48,
    max_position_embeddings=65536, max_window_layers=48, model_type='ouro',
    num_attention_heads=16, num_hidden_layers=48, num_key_value_heads=16,
    rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False, total_ut_steps=4,
    early_exit_threshold=1, use_sliding_window=False, vocab_size=49152)


def test_the_configuration_keeps_every_published_number():
    """Each key of the catalog row's `config` is in the file under the
    same name with the same value, but those `reduced` lists, whose
    published values the file states beside them; none of the reduced
    keys is a width."""
    c = published_config()
    for key, value in CATALOG.items():
        assert key in c, key
        if key not in c['reduced']:
            assert c[key] == value, key
        else:
            assert c['published'][key] == value, key
    assert set(c['reduced']) == {'num_hidden_layers', 'layer_types'}
    assert {'no_biases', 'no_qk_norm', 'norm_between_passes'} <= \
        set(c['assumed'])
    assert 'exit_gate' in c['left_out']


def test_parameters_by_hand():
    """509.7 M parameters: 51,388,416 a layer (16.78 M attention, 34.60 M
    feed-forward, 8,192 norm scales), 6 of them, and 201.3 M in the
    embedding and the head; the reference declares each leaf once,
    however many passes read it."""
    from reference import convnet
    cell = run.Cell(PUBLISHED)
    forward, arguments = cell.reference_forward()
    spec, _ = convnet.describe(forward, arguments, (8192,))
    count = {}
    for name, s in spec.items():
        n = 1
        for d in s['shape']:
            n *= d
        count[name] = n
    layer = sum(v for k, v in count.items() if k.startswith('l0_'))
    assert layer == 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 == 51388416
    assert count['embed_weight'] + count['lm_head_weight'] == \
        2 * 49152 * 2048
    assert sum(count.values()) == 6 * 51388416 + 2 * 49152 * 2048 + 2048
    assert round(sum(count.values()) / 1e6, 1) == 509.7


def test_work_counts_of_the_published_cell():
    """A dense configuration: 6 x the products' parameters x the passes
    x the tokens, plus the head's and attention over the causal half
    (23 % of the step); 8.54e13 a step of 8,192 tokens."""
    c = published_config()
    tokens, loops, layers = 8192, 4, 6
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    attention = 3 * 2 * 2 * (8192 * 8193 // 2) * 128 * 16
    total = work_ouro.train_flops(c, tokens)
    assert total == 6 * tokens * (loops * layers * per_layer +
                                  2048 * 49152) + loops * layers * attention
    assert abs(total / 8.54e13 - 1) < 0.01
    assert 0.22 < loops * layers * attention / total < 0.24
    products = work_ouro.forward_products(c, tokens, 8192)
    assert len(products) == loops * layers * 6 + 1
    least = work_ouro.roofline_seconds(c, tokens, 197e12, 819e9, 2)
    assert total / 197e12 * (1 - 1e-12) <= least < 1.1 * total / 197e12
    # a pass more is a stack more; the head counts once
    more = dict(c, total_ut_steps=5)
    assert work_ouro.train_flops(more, tokens) - total == \
        6 * tokens * layers * per_layer + layers * attention
