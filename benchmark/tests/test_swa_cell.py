"""The windowed-attention cell's configuration, work counts and readers
on the CPU, on a tiny benchmark of its own (tests/tiny_afmoe/): the same
entry file (entries/bulk_step_lm.py), reference and readers as the cell
on the chip, at a size the CPU holds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_swa_cell.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny_afmoe')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import trace_reduce     # noqa: E402
import work_afmoe       # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
CELL = 'tiny-trinity.bulk2-seq40-device'
PUBLISHED = 'trinity-mini.bulk4-seq8k-device'
NEW_READERS = ('swa_step_mfu.bulk', 'swa_roofline.bulk',
               'swa_attention_visited_over_needed.bulk')


def published_config():
    return run.read_json(BENCH, 'configs', 'trinity-mini.json')


def test_tiny_swa_cell_runs_and_agrees_with_the_reference():
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    profiler._ATTENTION.clear()
    cell = run.Cell(CELL, root=TINY, data=TINY)
    result = run.measure(cell, 2 ** 31 + 11, 0.3, False, [mx.cpu(0)], PEAK)
    assert result['correct'], result['compared']
    assert result['window']['compiles'] == 0
    assert set(result['metrics']) == {'train_throughput', 'peak_hbm_gib',
                                      'setup_s'}
    # every per-layer reader of the new cell but the trace's own, on the
    # run above (a traced run needs a chip)
    context = {'window': {'steps': 4, 'seconds': 1.0,
                          'dispatches': result['window']['dispatches']},
               'config': cell.config,
               'peak': PEAK, 'chips': 1, 'batch': 80,
               'trace': {'busy_s': 1.0}}
    got = {m['name']: cell.reader(m['name']).read(context)
           for m in cell.metrics('per_layer') if 'workloads' in m and
           not m['name'].startswith(('device_idle', 'dispatch_gap'))}
    assert got['moe_dropped_tokens.swa'] == 0
    assert 0 < got['moe_held_assignment_share.swa'] < 100
    assert got['moe_load_max_over_mean.swa'] >= 1.0
    assert 0 < got['swa_step_mfu.bulk'] < 100
    assert 0 < got['swa_roofline.bulk'] < 100
    # three windowed layers (12 of 40 keys: one block of rows reads all
    # 40) and one full: 3 x 40 x 40 + 40 x 40 over 3 x 414 + 820
    assert got['swa_attention_visited_over_needed.bulk'] == \
        pytest.approx(4 * 1600 / (3 * 414 + 820))
    for name in ('step_host_prep_ms.swa', 'bulk_stack_ms.swa',
                 'step_dispatch_ms.swa'):
        assert got[name] > 0, name


def test_the_new_readers_on_the_recorded_trace():
    """testdata/small.xplane.pb reduced as a traced run's would be: the
    two shares read the device's busy time from it, the counter's
    reader reads no trace at all."""
    from mxnet_tpu import profiler
    reduced = trace_reduce.reduce(trace_reduce.load(
        os.path.join(BENCH, 'testdata', 'small.xplane.pb')))
    config = published_config()
    cell = run.Cell(PUBLISHED)
    context = {'window': {'steps': 3, 'seconds': reduced['window_s']},
               'config': config, 'peak': PEAK, 'chips': 1, 'batch': 8192,
               'trace': reduced}
    least = work_afmoe.roofline_seconds(config, 8192, 1e12, 1e11, 2)
    assert cell.reader('swa_roofline.bulk').read(context) == pytest.approx(
        100 * least / (reduced['busy_s'] / 3))
    assert cell.reader('swa_step_mfu.bulk').read(context) == pytest.approx(
        100 * 3 * work_afmoe.train_flops(config, 8192) /
        (reduced['window_s'] * 1e12))
    # without a trace the roofline's reader says nothing; the other two
    # do not need one
    assert cell.reader('swa_roofline.bulk').read(
        dict(context, trace=None)) is None
    profiler._ATTENTION.clear()
    read = cell.reader('swa_attention_visited_over_needed.bulk').read
    assert read(dict(context, trace=None)) is None      # nothing lowered
    profiler.note_attention_lowering('blocked', 32, 8, 128, 128, 8192,
                                     window=2048, keys_visited=5,
                                     keys_needed=4)
    profiler.note_attention_lowering('blocked', 32, 8, 128, 128, 8192,
                                     window=2048, keys_visited=5,
                                     keys_needed=4)
    profiler.note_attention_lowering('blocked', 32, 8, 128, 128, 8192,
                                     keys_visited=11, keys_needed=10)
    assert read(context) == pytest.approx((2 * 5 + 11) / (2 * 4 + 10))
    assert profiler.attention_stats()['shapes'][1]['keys_visited'] == 10
    profiler._ATTENTION.clear()


def test_the_new_readers_say_nothing_of_another_model(monkeypatch):
    """On a configuration without a window and on the parent's program,
    whose attention_stats() has no count of positions (the driver runs
    these files over it), they return None and do not raise; the
    accepted language-model readers return None on this configuration."""
    from mxnet_tpu import profiler
    mine = published_config()
    cell = run.Cell(PUBLISHED)
    context = {'window': {'steps': 4, 'seconds': 1.0}, 'peak': PEAK,
               'chips': 1, 'batch': 8192, 'trace': {'busy_s': 1.0}}
    for other in ('qwen3-next-80b-a3b', 'kanana-2-30b-a3b'):
        config = run.read_json(BENCH, 'configs', other + '.json')
        for name in ('swa_step_mfu.bulk', 'swa_roofline.bulk'):
            assert cell.reader(name).read(dict(context, config=config)) \
                is None
    for name in ('swa_step_mfu.bulk', 'swa_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=mine)) > 0
    for name in ('lm_step_mfu.bulk', 'lm_roofline.bulk', 'mla_step_mfu.bulk',
                 'mla_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=mine)) is None
    read = cell.reader('swa_attention_visited_over_needed.bulk').read
    monkeypatch.setattr(profiler, 'attention_stats', lambda: {
        'kernel': 0, 'blocked': 1, 'shapes': [dict(
            path='blocked', heads=16, group=8, dk=256, dv=256, t=8192,
            lowerings=1)]})
    assert read(dict(context, config=mine)) is None
    monkeypatch.delattr(profiler, 'attention_stats')
    assert read(dict(context, config=mine)) is None


def test_the_published_cell_is_well_formed():
    cell = run.Cell(PUBLISHED)
    c, t = cell.config, cell.traffic
    assert cell.chips == 1 and t['entry'] == 'bulk_step_lm'
    assert int(t['sequences_per_step']) * int(t['seq_len']) == \
        c['batch_per_chip']
    assert set(cell.limits) == {'loss', 'delta_median', 'delta_worst'}
    names = [m['name'] for m in cell.metrics('per_layer')]
    assert [n for n in names if n.endswith('.swa') or n.startswith('swa_')] \
        == list(NEW_READERS) + [
            'device_idle_share.swa', 'dispatch_gap_ms.swa',
            'moe_load_max_over_mean.swa', 'moe_held_assignment_share.swa',
            'moe_dropped_tokens.swa', 'step_host_prep_ms.swa',
            'bulk_stack_ms.swa', 'step_dispatch_ms.swa']
    assert {'compile_s', 'compiles_in_window',
            'optimizer_state_mib_per_chip'} <= set(names)
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
    for key in c['reduced']:
        assert key in c and (key == 'num_experts_held' or
                             c[key] != c['published'][key])
    # the cut keeps the kinds in their published ratio after the one
    # leading dense layer
    assert c['layer_types'] == ['sliding_attention'] + \
        c['published']['layer_types'][4:8]
    assert c['num_experts_held'] * 8 == c['num_experts'] == \
        c['published']['num_experts']
    assert c['vocab_size'] * 8 == c['published']['vocab_size']


# the catalog row's `config` (model-configs/architectures.jsonl,
# Trinity-Mini), layer_types written as its rule
CATALOG = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act='silu',
    hidden_size=2048, intermediate_size=6144,
    layer_types=(['sliding_attention'] * 3 + ['full_attention']) * 8,
    load_balance_coeff=0.001, max_position_embeddings=131072,
    model_type='afmoe', moe_intermediate_size=1024, mup_enabled=True,
    n_group=1, num_attention_heads=32, num_dense_layers=2,
    num_expert_groups=1, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=32, num_key_value_heads=4, num_limited_groups=1,
    num_shared_experts=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, route_norm=True, route_scale=2.826,
    score_func='sigmoid', sliding_window=2048, tie_word_embeddings=False,
    topk_group=1, use_grouped_mm=True, vocab_size=200192)


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
    assert set(c['reduced']) == {'num_hidden_layers', 'num_dense_layers',
                                 'layer_types', 'num_experts_held',
                                 'vocab_size'}
    assert {'sandwich_norms', 'qk_norm', 'nope_on_full_layers',
            'attention_gate', 'expert_bias'} <= set(c['assumed'])


def test_parameters_by_hand():
    """705.5 M parameters at 10 bytes of state each (ISSUE 34's 705.4 is
    the sum of its rounded parts): the leaves the reference declares at
    the cell's sizes."""
    from reference import convnet
    cell = run.Cell(PUBLISHED)
    forward, arguments = cell.reference_forward()
    spec, _ = convnet.describe(forward, arguments, (8192,))
    count = {}
    for name, s in spec.items():
        if not s['aux']:
            n = 1
            for d in s['shape']:
                n *= d
            count[name] = n
    attention = sum(v for k, v in count.items() if k.startswith('l4_') and
                    ('_proj' in k) and 'shared' not in k)
    # q, gate and o of 32 heads, k and v of 4: 27.26 M
    assert attention == 3 * 2048 * 4096 + 2 * 2048 * 512
    layer4 = sum(v for k, v in count.items() if k.startswith('l4_'))
    assert round(layer4 / 1e6, 1) == 134.5
    layer0 = sum(v for k, v in count.items() if k.startswith('l0_'))
    assert round(layer0 / 1e6, 1) == 65.0
    assert count['embed_weight'] + count['lm_head_weight'] == \
        2 * 25024 * 2048
    assert sum(count.values()) == 705473792


def test_one_layers_products_by_hand():
    """The dense layer and a full-attention expert layer of the
    published widths over 8,192 tokens."""
    c = dict(published_config(), num_hidden_layers=2,
             layer_types=['sliding_attention', 'full_attention'])
    tokens = 8192
    products = work_afmoe.forward_products(c, tokens, 8192)
    assert len(products) == 6 + 9 + 1
    dense = {p['name']: p['flops'] for p in products[:6]}
    assert dense['q_gate_proj'] == 2 * tokens * 2048 * 2 * 4096
    assert dense['kv_proj'] == 2 * tokens * 2048 * 2 * 512
    assert dense['o_proj'] == 2 * tokens * 4096 * 2048
    assert dense['mlp_gate_up'] == 2 * tokens * 2048 * 2 * 6144
    assert dense['mlp_down'] == 2 * tokens * 6144 * 2048
    assert 'router' not in dense and 'attention_sliding' in dense
    layer = {p['name']: p['flops'] for p in products[6:-1]}
    assert layer['router'] == 2 * tokens * 2048 * 128
    assert layer['shared_gate_up'] == 2 * tokens * 2048 * 2 * 1024
    assert layer['shared_down'] == 2 * tokens * 1024 * 2048
    # 8,192 x 8 pairs, an eighth of them here: 512 rows an expert
    assert layer['experts_gate_up'] == 16 * 2 * 512 * 2048 * 2 * 1024
    assert layer['experts_down'] == 16 * 2 * 512 * 1024 * 2048
    assert 'attention_full' in layer
    assert products[-1]['flops'] == 2 * tokens * 2048 * 25024


@pytest.mark.parametrize('seq_len,window,pairs', [
    (8192, None, 8192 * 8193 // 2), (8192, 2048, 14681088),
    (8192, 8192, 8192 * 8193 // 2), (8192, 1, 8192), (40, 12, 414),
    (40, 4096, 820)])
def test_the_pairs_a_mask_lets_through(seq_len, window, pairs):
    assert work_afmoe.needed_pairs(seq_len, window) == pairs
    assert pairs == sum(min(i + 1, window or seq_len)
                        for i in range(seq_len))


def test_the_attention_terms():
    """Scores and weighted values of 32 heads of 128 over the needed
    pairs of a sequence (two sequences: twice); least bytes: q and o of
    32 heads, k and v of 4."""
    c = published_config()
    windowed = work_afmoe.attention(c, 'sliding_attention', 8192, 8192)
    full = work_afmoe.attention(c, 'full_attention', 8192, 8192)
    assert windowed['flops'] == 2 * 2 * 14681088 * 32 * 128
    assert full['flops'] == 2 * 2 * (8192 * 8193 // 2) * 32 * 128
    assert work_afmoe.attention(c, 'full_attention', 16384, 8192)[
        'flops'] == 2 * full['flops']
    assert windowed['elements'] == full['elements'] == \
        8192 * 128 * (2 * 32 + 2 * 4)
    assert 0.43 < windowed['flops'] / full['flops'] < 0.44


def test_work_counts_of_the_published_cell():
    """The step's operations at the cell's sizes against the sum by hand
    in PERF.md section 4: 6 x 276.7 M active parameters x 8,192 tokens,
    4.54 TFLOP of attention (four windowed layers and one full), a
    quarter of the step."""
    config = published_config()
    by_name = {}
    for p in work_afmoe.forward_products(config, 8192, 8192):
        by_name[p['name']] = by_name.get(p['name'], 0) + 3 * p['flops']
    attention = by_name['attention_sliding'] + by_name['attention_full']
    assert abs(attention / 4.535e12 - 1) < 0.01
    assert abs(by_name['attention_sliding'] / (4 * by_name['attention_full'])
               - 14681088 / (8192 * 8193 // 2)) < 1e-9
    dense = sum(v for k, v in by_name.items()
                if not k.startswith('attention'))
    assert abs(dense / (6 * 276.7e6 * 8192) - 1) < 0.01
    total = work_afmoe.train_flops(config, 8192)
    assert abs(total / 18.13e12 - 1) < 0.01
    assert 0.24 < attention / total < 0.26
    least = work_afmoe.roofline_seconds(config, 8192, 197e12, 819e9, 2)
    assert total / 197e12 <= least < 2 * total / 197e12
