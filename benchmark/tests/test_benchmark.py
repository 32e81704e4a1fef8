"""Tests of the benchmark's own code, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The run itself refuses the CPU, so these drive the pieces below the look
for a chip (`run.measure`) on a tiny benchmark of their own, tests/tiny/:
a residual network in float32 that the CPU holds, under three cells that
exist only here (bulk_step, fit, and bulk_step over four devices).  A
new cell there needed new files and entries only, which is the claim.
"""
import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, 'tiny')
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run              # noqa: E402
import check            # noqa: E402
import trace_reduce     # noqa: E402
import work             # noqa: E402

PEAK = {'bf16_flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11, 'hbm_bytes': 16e9}


def tiny_cell(name):
    return run.Cell(name, root=TINY, data=TINY)


def measure(name, trace=False, seed=2 ** 31 + 78):
    import mxnet_tpu as mx
    cell = tiny_cell(name)
    return run.measure(cell, seed, 0.3, trace,
                       [mx.cpu(i) for i in range(cell.chips)], PEAK)


# -- BENCHMARK.json and the files it names -----------------------------------

@pytest.mark.parametrize('root,data', [(ROOT, BENCH), (TINY, TINY)])
def test_every_entry_resolves(root, data):
    bench = run.read_json(root, 'BENCHMARK.json')
    cells = [w['name'] for w in bench['workloads']]
    e2e = {m['name']: m for m in bench['end_to_end']}
    reports = {}
    for name in cells:
        cell = run.Cell(name, root=root, data=data)
        assert os.path.exists(os.path.join(
            run.HERE, 'entries', cell.traffic['entry'] + '.py'))
        forward, _ = cell.reference_forward()
        assert callable(forward)
        mine = [m['name'] for m in cell.metrics('end_to_end')]
        assert 'setup_s' in mine and len(mine) >= 2
        assert cell.traffic['reports'] in mine
        assert cell.metrics('per_layer')
        reports[name] = set(mine)
        assert set(cell.limits) <= {'loss', 'grad_first_median',
                                    'grad_first_worst', 'delta_median',
                                    'delta_worst'}
    rates = [n for n in e2e if n.endswith('_throughput')]
    for name in cells:       # no cell reports two rates
        assert len(reports[name] & set(rates)) == 1
    for m in bench['per_layer']:
        assert m['moves'] in e2e
        for name in m.get('workloads', cells):
            assert m['moves'] in reports[name], (m['name'], name)
            assert hasattr(run.Cell(name, root=root, data=data).reader(
                m['name']), 'read')


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    cmd = [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
           'resnet50.bulk16-device', '--seed', '1', '--seconds', '1',
           '--trace', '0']
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ''
    assert 'TPU' in out.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind, id = 'tpu', 'TPU v9 imaginary', 0

    monkeypatch.setattr(jax, 'devices', lambda *a: [Fake()])
    peaks = run.read_json(BENCH, 'peaks.json')['device_kinds']
    with pytest.raises(SystemExit):
        run.find_devices(1, peaks)
    with pytest.raises(SystemExit):     # fewer chips than the cell asks
        run.find_devices(4, {'TPU v9 imaginary': PEAK})
    assert run.find_devices(1, {'TPU v9 imaginary': PEAK})[1] is PEAK


# -- the harness end to end, and the plain reference against Module ----------

@pytest.mark.parametrize('name', ['tiny-resnet.bulk4-device',
                                  'tiny-resnet.fit-host'])
def test_tiny_cell_runs_and_agrees_with_the_reference(name):
    """In float32 the program and the plain reference agree on the
    losses, the first gradient and the change of every leaf over the
    steps followed, to rounding: the limits in tests/tiny are 1e-3 for
    the median leaf and 1e-2 for the worst."""
    result = measure(name)
    assert result['correct'], result['compared']
    assert list(result)[-1] == 'compared'
    assert result['failed'] == 0 and result['attempted'] >= 1
    assert result['window']['compiles'] == 0
    cell = tiny_cell(name)
    assert set(result['metrics']) == {m['name'] for m in
                                      cell.metrics('end_to_end')}
    assert all(v['value'] > 0 for k, v in result['metrics'].items()
               if k != 'peak_hbm_gib')      # the CPU reports no memory
    for c in result['compared'].values():
        assert c['value'] < 1e-4, result['compared']


def test_chips_4_in_a_child_process():
    """The same tiny cell with chips: 4, on four virtual CPU devices in
    a child that sets the flag before it imports jax."""
    code = (
        "import os, sys, json\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "sys.path[:0] = [%r, %r]\n"
        "import run\n"
        "import mxnet_tpu as mx\n"
        "cell = run.Cell('tiny-resnet.dp4-bulk4-device', root=%r, data=%r)\n"
        "assert cell.chips == 4\n"
        "r = run.measure(cell, 5, 0.3, False,"
        " [mx.cpu(i) for i in range(4)], %r)\n"
        "print(json.dumps(r))\n" % (BENCH, ROOT, TINY, TINY, PEAK))
    out = subprocess.run([sys.executable, '-c', code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result['device']['count'] == 4
    assert result['correct'], result['compared']


# -- the comparison has been shown to fail ------------------------------------

def _half_batch(batch):
    """Rows of the second half replaced by the first half's: the mean
    over the batch is then the mean over half of it."""
    import jax.numpy as jnp
    import mxnet_tpu as mx

    def dup(arr):
        a = arr._data
        h = a.shape[0] // 2
        return mx.nd.NDArray(jnp.concatenate([a[:h], a[:h]]))
    return mx.io.DataBatch(data=[dup(a) for a in batch.data],
                           label=[dup(a) for a in batch.label])


@pytest.mark.parametrize('fault', ['state_unchanged', 'half_batch'])
@pytest.mark.parametrize('name', ['tiny-resnet.bulk4-device',
                                  'tiny-resnet.fit-host'])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    """The rest of a run, with the program broken underneath."""
    import mxnet_tpu as mx
    Module = mx.mod.Module
    if name.endswith('fit-host'):
        if fault == 'state_unchanged':
            def update(self):
                self._pending_fused = False     # the step is never run
            real_fb = Module.forward_backward

            def forward_backward(self, batch):
                real_fb(self, batch)
                self.forward(batch, is_train=True)   # outputs, no update
            monkeypatch.setattr(Module, 'update', update)
            monkeypatch.setattr(Module, 'forward_backward',
                                forward_backward)
        else:
            real_fb = Module.forward_backward
            monkeypatch.setattr(
                Module, 'forward_backward',
                lambda self, batch: real_fb(self, _half_batch(batch)))
    else:
        real_bulk = Module.bulk_step
        if fault == 'state_unchanged':
            def bulk_step(self, batches=None, **kwargs):
                self.forward(batches[-1], is_train=True)
            monkeypatch.setattr(Module, 'bulk_step', bulk_step)
        else:
            monkeypatch.setattr(
                Module, 'bulk_step',
                lambda self, batches=None, **kw: real_bulk(
                    self, batches=[_half_batch(b) for b in batches], **kw))
    if fault == 'state_unchanged':
        # the optimizer's state is made lazily by the step that never ran
        real_read = run.Harness.read_state

        def read_state(self, mod):
            import jax.numpy as jnp
            ex = mod._exec_group.executor
            if mod._fused_updater is None or not mod._fused_updater.states:
                w = {n: jnp.array(ex.arg_dict[n]._data, jnp.float32)
                     for n in ex._diff_names}
                return w, {n: jnp.zeros_like(v) for n, v in w.items()}
            return real_read(self, mod)
        monkeypatch.setattr(run.Harness, 'read_state', read_state)
        monkeypatch.setattr(run.Harness, 'optimizer_state_bytes',
                            lambda self, mod: 0)
    result = measure(name)
    assert not result['correct'], result['compared']
    if fault == 'state_unchanged':
        # every leaf's change is nought against the reference's own
        assert result['compared']['delta_median']['value'] > 0.9


@pytest.mark.parametrize('stand_in,kwargs', [
    ('int8', {'lowp': 'int8'}), ('bfloat16', {'lowp': 'bfloat16'}),
    ('half batch', {'rows': 4})])
def test_the_control_comes_out_not_correct(stand_in, kwargs):
    """The reference in the program's place, computed in a precision
    below the configuration's, or with half of the batch left out, fails
    the tiny cell's limits.  (The same is read on the chip at the cells'
    own size by tests/readings.py; PERF.md section 2 has the readings.)"""
    import mxnet_tpu as mx
    cell = tiny_cell('tiny-resnet.fit-host')
    h = run.Harness(cell, 11, 0.3, False, [mx.cpu(0)], run.CompileLog())
    entry = run.load_file_module(
        os.path.join(BENCH, 'entries', 'fit.py'), 'entry_fit_control')
    fed = entry.feed(h)
    reference = check.run_reference(h, fed)
    control = check.run_reference(h, fed, **kwargs)
    numbers = check.numbers(control, reference)
    failed = [n for n, limit in cell.limits.items()
              if numbers[n][0] > limit]
    assert failed, numbers
    same = check.numbers(check.run_reference(h, fed), reference)
    assert all(v == 0.0 for v, _ in same.values())


def test_worst_leaf_measures_against_the_median_leaf():
    ref = {'a': 1.0, 'b': 2.0, 'c': 0.0}
    got = {'a': 1.1, 'b': 2.0, 'c': 0.5}
    gaps = check.leaf_gaps(got, ref, sorted(ref))
    assert gaps['a'] == pytest.approx(0.1)
    assert gaps['c'] == pytest.approx(0.5)       # over the median, 1.0
    (worst, leaf), (median, _) = check.worst_and_median(gaps)
    assert (leaf, worst) == ('c', pytest.approx(0.5))
    assert median == pytest.approx(0.1)


# -- the trace reduction, against a trace recorded on the chip ----------------

def test_trace_reduction_against_the_recorded_trace():
    """testdata/small.xplane.pb (testdata/record.py, TPU v5 lite): four
    runs of one small program, each in a `step` span with a 2 ms `nap`
    span after it, inside `window`.  In milliseconds on the trace's
    clock: window 44.709575 .. 58.062273; runs at 44.478315 (before the
    window opens: the device's clock leads the host's here, so the first
    run is clipped away), 47.959149, 51.239930 and 54.597428, each three
    operations of 13 ns, 3 ns and 90.197 us with 1 ns between them."""
    trace = trace_reduce.load(os.path.join(BENCH, 'testdata',
                                           'small.xplane.pb'))
    assert list(trace['devices']) == ['/device:TPU:0']
    assert [n for n, _, _ in trace['spans']].count('step') == 4
    r = trace_reduce.reduce(trace)
    window = 58.062273 - 44.709575
    assert r['window_s'] * 1e3 == pytest.approx(window, abs=1e-5)
    busy = 3 * (0.000013 + 0.000003 + 0.090197)
    assert r['busy_s'] * 1e3 == pytest.approx(busy, abs=2e-5)
    assert 100 * r['idle_s'] / r['window_s'] == pytest.approx(
        100 * (1 - busy / window), abs=1e-3)        # 97.973 %
    # between the end of one run and the start of the next
    assert [g * 1e3 for g in r['dispatch_gaps']] == pytest.approx(
        [51.239930 - 48.049368, 54.597428 - 51.330148], abs=1e-5)
    # no operation runs inside any `step` span (each run ended before
    # its span opened, by the clocks' offset), so all of them is idle
    steps = (45.570445 - 44.714135) + (48.847314 - 48.241164) + \
        (52.280074 - 51.528234) + (55.623623 - 54.801684)
    assert r['idle_by_span']['step'] * 1e3 == pytest.approx(steps, abs=1e-5)
    assert sum(r['idle_by_span'].values()) == pytest.approx(r['idle_s'])
    assert r['idle_by_span']['nap'] * 1e3 == pytest.approx(
        window - busy - steps - r['idle_by_span']['outside'] * 1e3,
        abs=1e-5)
    assert r['idle_by_span']['outside'] * 1e3 < 0.05
    assert r['ops'][0][1] == 'fusion.Output_bf16'
    b = trace_reduce.breakdown(r, 'fit')
    assert b['device_ops'][0][0] == 'fusion.Output_bf16'
    assert b['idle_gaps'][0][0] == 'sum_in_nap'


def test_reduce_by_hand():
    """Two devices, a wrapper operation, overlapping operations."""
    trace = {
        'spans': [('window', 10.0, 20.0), ('dispatch', 10.0, 11.0),
                  ('wait', 11.0, 19.0)],
        'devices': {
            '/device:TPU:0': {
                'ops': [('while_s32', 9.0, 21.0),       # wraps, not work
                        ('fusion_bf16_8', 9.5, 12.0),   # clipped to 10
                        ('copy_bf16_8', 11.5, 13.0),    # overlaps
                        ('fusion_bf16_8', 15.0, 19.5)],
                'modules': [('jit_step(1)', 9.5, 13.0),
                            ('jit_small(2)', 13.5, 13.6),
                            ('jit_step(1)', 15.0, 19.5)]},
            '/device:TPU:1': {'ops': [('fusion_bf16_8', 10.0, 20.0)],
                              'modules': [('jit_step(1)', 10.0, 20.0)]}}}
    r = trace_reduce.reduce(trace)
    assert r['window_s'] == 10.0
    assert r['busy_s'] == pytest.approx((3.0 + 4.5 + 10.0) / 2)
    assert r['dispatch_gaps'] == [pytest.approx(2.0)]
    # device 0 idles 13..15 (in wait) and 19.5..20 (wait to 19, then out)
    assert r['idle_by_span'] == {
        'wait': pytest.approx(2.0 / 2), 'outside': pytest.approx(0.5 / 2)}
    assert trace_reduce.op_name(
        '%fusion.37 = bf16[256,56,56,256]{3,2,1,0} fusion(%p), kind=kOutput'
    ) == 'fusion.Output_bf16_256_56_56_256'


# -- operations and least bytes -----------------------------------------------

def test_work_of_two_layers_by_hand():
    conv = {'kind': 'conv', 'x': (2, 3, 8, 8), 'w': (4, 3, 3, 3),
            'y': (2, 4, 8, 8), 'needs_dx': False}
    # 2*4*8*8 outputs, each of 3*3*3 multiply-adds
    assert work.forward_macs([conv]) == 512 * 27
    assert work.train_flops([conv]) == 2 * (2 * 512 * 27)   # no dx
    dense = {'kind': 'dense', 'x': (2, 16), 'w': (10, 16), 'y': (2, 10),
             'needs_dx': True}
    assert work.train_flops([dense]) == 3 * (2 * 2 * 10 * 16)
    # 960 flops and (32 + 160 + 20) elements of 2 bytes a product: at
    # 1e3 flop/s and 1e3 byte/s the three products are compute-bound
    seconds, memory = work.roofline_seconds([dense], 1e3, 1e3, 2)
    assert seconds == pytest.approx(3 * 0.64) and memory == 0.0
    seconds, memory = work.roofline_seconds([dense], 1e6, 1e3, 2)
    assert seconds == memory == pytest.approx(3 * 0.424)


def test_resnet50_work_against_the_ledger():
    """PR 22's driver run read 21.321 % of 197e12 FLOP/s at 1,719.8
    items/s: 24.4 GFLOP an image, forward and backward; the paper gives
    about 4.1e9 multiply-adds forward at 224x224 (3.8e9 without the
    pre-activation variant's full-width shortcuts)."""
    cell = run.Cell('resnet50.bulk16-device')
    from reference import convnet
    forward, arguments = cell.reference_forward()
    batch = cell.config['batch_per_chip']
    spec, layers = convnet.describe(
        forward, arguments, (batch,) + tuple(cell.config['data_shape']))
    assert len(layers) == 54                 # 53 convolutions and fc1
    assert work.forward_macs(layers) / batch == pytest.approx(4.09e9,
                                                              rel=0.01)
    implied = 0.21321 * 197e12 / 1719.8
    assert work.train_flops(layers) / batch == pytest.approx(implied,
                                                             rel=0.01)
    params = sum(math.prod(s['shape']) for s in spec.values()
                 if not s['aux'])
    assert params == pytest.approx(25.55e6, rel=0.01)
