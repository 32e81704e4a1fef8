"""The latent-attention cell's configuration, work counts and readers on
the CPU, on a tiny benchmark of its own (tests/tiny_mla/): the same
entry file (entries/bulk_step_lm.py), reference and readers as the cell
on the chip, at a size the CPU holds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mla_cell.py -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny_mla')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import work_mla         # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}
CELL = 'tiny-kanana.bulk2-seq40-device'
PUBLISHED = 'kanana-2-30b-a3b.bulk2-seq8k-device'


def test_tiny_mla_cell_runs_and_agrees_with_the_reference():
    import mxnet_tpu as mx
    cell = run.Cell(CELL, root=TINY, data=TINY)
    result = run.measure(cell, 2 ** 31 + 7, 0.3, False, [mx.cpu(0)], PEAK)
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
    assert got['moe_dropped_tokens.mla'] == 0
    assert 0 < got['moe_held_assignment_share.mla'] < 100
    assert got['moe_load_max_over_mean.mla'] >= 1.0
    assert 0 < got['mla_step_mfu.bulk'] < 100
    assert 0 < got['mla_roofline.bulk'] < 100
    for name in ('step_host_prep_ms.mla', 'bulk_stack_ms.mla',
                 'step_dispatch_ms.mla'):
        assert got[name] > 0, name


def test_the_new_readers_say_nothing_of_another_model():
    """On a configuration without latent attention (and on the parent's
    program, which the driver runs these files over) they return None
    and do not raise; the accepted language-model readers return None
    on this configuration."""
    other = run.read_json(BENCH, 'configs', 'qwen3-next-80b-a3b.json')
    mine = run.read_json(BENCH, 'configs', 'kanana-2-30b-a3b.json')
    cell = run.Cell(PUBLISHED)
    context = {'window': {'steps': 4, 'seconds': 1.0}, 'peak': PEAK,
               'chips': 1, 'batch': 8192, 'trace': {'busy_s': 1.0}}
    for name in ('mla_step_mfu.bulk', 'mla_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=other)) is None
        assert cell.reader(name).read(dict(context, config=mine)) > 0
    for name in ('lm_step_mfu.bulk', 'lm_roofline.bulk'):
        assert cell.reader(name).read(dict(context, config=mine)) is None


def test_the_published_cell_is_well_formed():
    cell = run.Cell(PUBLISHED)
    c, t = cell.config, cell.traffic
    assert cell.chips == 1 and t['entry'] == 'bulk_step_lm'
    assert int(t['sequences_per_step']) * int(t['seq_len']) == \
        c['batch_per_chip']
    assert set(cell.limits) == {'loss', 'delta_median', 'delta_worst'}
    names = [m['name'] for m in cell.metrics('per_layer')]
    assert [n for n in names if n.endswith('.mla') or n.startswith('mla_')] \
        == ['mla_step_mfu.bulk', 'mla_roofline.bulk',
            'device_idle_share.mla', 'dispatch_gap_ms.mla',
            'moe_load_max_over_mean.mla', 'moe_held_assignment_share.mla',
            'moe_dropped_tokens.mla', 'step_host_prep_ms.mla',
            'bulk_stack_ms.mla', 'step_dispatch_ms.mla']
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
                             c[key] < c['published'][key])


def test_one_layers_products_by_hand():
    """An expert layer of the published widths over 8,192 tokens."""
    c = dict(run.read_json(BENCH, 'configs', 'kanana-2-30b-a3b.json'),
             num_hidden_layers=2)
    tokens = 8192
    products = work_mla.forward_products(c, tokens, 8192)
    assert len(products) == 7 + 10 + 1
    layer = products[7:-1]          # layer 1: an expert layer
    flops = {p['name']: p['flops'] for p in layer}
    assert flops['q_proj'] == 2 * tokens * 2048 * 32 * 192
    assert flops['kv_a_proj'] == 2 * tokens * 2048 * (512 + 64)
    assert flops['kv_b_proj'] == 2 * tokens * 512 * 32 * (128 + 128)
    assert flops['o_proj'] == 2 * tokens * 32 * 128 * 2048
    assert flops['router'] == 2 * tokens * 2048 * 128
    assert flops['shared_gate_up'] == 2 * tokens * 2048 * 2 * 1536
    assert flops['shared_down'] == 2 * tokens * 1536 * 2048
    # the dense layer before it
    dense = {p['name']: p['flops'] for p in products[:7]}
    assert dense['mlp_gate_up'] == 2 * tokens * 2048 * 2 * 6144
    assert dense['mlp_down'] == 2 * tokens * 6144 * 2048
    assert 'router' not in dense
    assert products[-1]['flops'] == 2 * tokens * 2048 * 16032


def test_the_attention_term():
    """Scores over keys of 192 and weighted values of 128, the causal
    half, 32 heads; least bytes: q, k_nope, the one k_pe head, v, o."""
    c = run.read_json(BENCH, 'configs', 'kanana-2-30b-a3b.json')
    one = work_mla.attention(c, 8192, 8192)
    pairs = 8192 * 8193 // 2
    assert one['flops'] == 2 * pairs * 32 * 192 + 2 * pairs * 32 * 128
    assert one['elements'] == 8192 * (32 * 192 + 32 * 128 + 64 +
                                      32 * 128 + 32 * 128)
    assert work_mla.attention(c, 16384, 8192)['flops'] == 2 * one['flops']


def test_the_held_share_of_the_experts():
    """Of 8,192 x 6 pairs an eighth lands on the 16 experts held: 384
    rows an expert; with all 128 held the routed work is 8 times it."""
    c = run.read_json(BENCH, 'configs', 'kanana-2-30b-a3b.json')

    def routed(config):
        return sum(p['flops'] for p in
                   work_mla.forward_products(config, 8192, 8192)
                   if p['name'].startswith('experts_'))

    assert routed(c) == 4 * 16 * 384 * 3 * 2 * 2048 * 768
    assert routed(dict(c, num_experts_held=128)) == 8 * routed(c)


def test_work_counts_of_the_published_cell():
    """The step's operations at the cell's sizes against the sum by hand
    in PERF.md section 4: 6 x 255.3 M active parameters x 8,192 tokens,
    10.3 TFLOP of causal attention, 45 % of the step."""
    config = run.read_json(BENCH, 'configs', 'kanana-2-30b-a3b.json')
    by_name = {}
    for p in work_mla.forward_products(config, 8192, 8192):
        by_name[p['name']] = by_name.get(p['name'], 0) + 3 * p['flops']
    assert abs(by_name['attention'] / 10.31e12 - 1) < 0.01
    dense = sum(v for k, v in by_name.items() if k != 'attention')
    assert abs(dense / (6 * 255.3e6 * 8192) - 1) < 0.01
    total = work_mla.train_flops(config, 8192)
    assert abs(total / 22.86e12 - 1) < 0.01
    assert 0.44 < by_name['attention'] / total < 0.46
    least = work_mla.roofline_seconds(config, 8192, 197e12, 819e9, 2)
    assert total / 197e12 <= least < 2 * total / 197e12
