"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell in BENCHMARK.json,
the cell's configuration, traffic mix, limits and per-layer readers by
their names in files of their own under benchmark/, builds the weights
and the inputs from --seed, drives the program under test (mxnet_tpu)
through the entry the mix names, measures for --seconds, compares what
the timed path produced with the plain float32 reference, and prints one
JSON object as the last line of standard output.

Nothing here names a cell, a configuration or a model: a later PR adds
those as files and entries.  With no TPU, fewer chips than the cell
asks for, or a device kind that peaks.json does not list, it exits
non-zero before anything compiles and prints no result.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's workloads with the files it names."""

    def __init__(self, name, root=ROOT, data=HERE):
        """`root` holds BENCHMARK.json and `data` the traffic/ and
        limits/ it names (the tests keep a tiny benchmark of their own);
        readers and entries are always this directory's."""
        self.here = HERE
        self.bench = read_json(root, 'BENCHMARK.json')
        cells = {w['name']: w for w in self.bench['workloads']}
        if name not in cells:
            raise SystemExit('no workload %r in BENCHMARK.json (it has %s)'
                             % (name, ', '.join(sorted(cells))))
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry['chips'])
        configs = {c['name']: c for c in self.bench['configs']}
        self.config = read_json(root, configs[self.entry['config']]['file'])
        self.traffic = read_json(data, 'traffic',
                                 self.entry['traffic'] + '.json')
        self.limits = read_json(data, 'limits', name + '.json')['limits']

    def metrics(self, group):
        """The metrics of `group` this cell reports."""
        return [m for m in self.bench[group]
                if self.name in m.get('workloads', [self.name])]

    def reader(self, metric_name):
        """readers/<name>.py, else readers/<name before its first dot>.py:
        a quantity split by the end-to-end metric it moves shares one."""
        for stem in (metric_name, metric_name.split('.')[0]):
            path = os.path.join(self.here, 'readers', stem + '.py')
            if os.path.exists(path):
                return load_file_module(path, 'reader_' + stem.replace(
                    '.', '_').replace('-', '_'))
        raise SystemExit('no reader for per-layer metric %r' % metric_name)

    def reference_forward(self):
        ref = self.config['reference']
        mod = importlib.import_module('reference.' + ref['module'])
        return mod.forward, dict(ref['arguments'])


class CompileLog:
    """What jax compiled, as jax itself reports it (jax.monitoring):
    every backend compile request with its seconds, and how many the
    persistent cache answered.  (After chip_smoke.py's.)"""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.requests += 1
            self.seconds += duration

    def mark(self):
        return (self.requests, self.seconds)


class Spans:
    """Host spans from the benchmark's own files, around its calls into
    the program: kept in memory on the host clock, and written into the
    profiler's trace (TraceAnnotation) when one is being taken, so that
    device idle time can be laid against them."""

    PREFIX = 'bench.'

    def __init__(self):
        self.total_s = {}
        self.count = {}
        self.recording = False

    def span(self, name):
        return _Span(self, name)

    def reset(self):
        self.total_s.clear()
        self.count.clear()


class _Span:
    def __init__(self, spans, name):
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.recording:
            import jax
            self.ann = jax.profiler.TraceAnnotation(
                Spans.PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        s = self.spans
        s.total_s[self.name] = s.total_s.get(self.name, 0.0) + dt
        s.count[self.name] = s.count.get(self.name, 0) + 1
        return False


class Harness:
    """What an entry (entries/<entry>.py) needs to drive one cell: the
    module built from the configuration with the benchmark's weights,
    inputs from the seed, the window's clock, spans and the trace."""

    def __init__(self, cell, seed, seconds, trace, contexts, clog):
        import jax
        from reference import convnet
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.contexts = list(contexts)
        self.devices = [c.jax_device() for c in self.contexts]
        self.clog = clog
        self.spans = Spans()
        self.trace_dir = os.path.join(cell.here, '.trace')
        self.batch = int(self.config['batch_per_chip']) * len(self.contexts)
        self.data_shape = (self.batch,) + tuple(self.config['data_shape'])
        self.num_classes = int(self.config['num_classes'])
        forward, arguments = cell.reference_forward()
        self.spec, self.layers = convnet.describe(forward, arguments,
                                                  self.data_shape)
        self.key = jax.random.PRNGKey(self.seed)
        self._init = convnet.make_init(
            self.spec, jax.numpy.dtype(self.config['compute_dtype']))
        self.window = None
        self.every_step = False     # tests/readings.py: norms each step

    def mark(self, what):
        """Where set-up's seconds go, on standard error."""
        log('set-up %7.2f s  %s' % (time.perf_counter() - T_START, what))

    # -- the module --------------------------------------------------------
    def initial_params(self):
        """name -> float32 jax array, from the seed (see
        reference/convnet.py make_init)."""
        return self._init(self.key)

    def make_module(self):
        import mxnet_tpu as mx
        prog = self.config['program']
        mod_name, fn_name = prog['factory'].split(':')
        factory = getattr(importlib.import_module(mod_name), fn_name)
        sym = factory(**prog['arguments'])
        ctx = self.contexts if len(self.contexts) > 1 else self.contexts[0]
        return mx.mod.Module(sym, context=ctx)

    def nd_params(self, params):
        """The benchmark's weights as the program takes them: NDArrays
        in the type the bound module keeps each in."""
        import jax.numpy as jnp
        import mxnet_tpu as mx
        lowp = jnp.dtype(self.config['compute_dtype'])
        arg, aux = {}, {}
        for name, value in params.items():
            s = self.spec[name]
            if s['lowp']:
                value = value.astype(lowp)
            (aux if s['aux'] else arg)[name] = mx.nd.NDArray(value)
        return arg, aux

    def optimizer_params(self):
        opt = dict(self.config['optimizer'])
        name = opt.pop('name')
        return name, opt

    def bind_and_init(self, mod, data_dtype='float32'):
        """bind, the benchmark's weights, the configuration's optimizer:
        what fit() does itself, for the entries that do not call fit."""
        import mxnet_tpu as mx
        mod.bind(data_shapes=[mx.io.DataDesc('data', self.data_shape,
                                             data_dtype)],
                 label_shapes=[mx.io.DataDesc('softmax_label',
                                              (self.batch,), 'float32')],
                 for_training=True)
        self.mark('bound')
        arg, aux = self.nd_params(self.initial_params())
        mod.init_params(initializer=None, arg_params=arg, aux_params=aux)
        self.mark('weights in')
        name, opt = self.optimizer_params()
        mod.init_optimizer(kvstore='local', optimizer=name,
                           optimizer_params=opt)

    # -- inputs ------------------------------------------------------------
    def device_batches(self, k, dtype):
        """k batches made on the device from the seed in one jitted
        call: uniform [0, 1) pixels in `dtype`, uniform labels.  With
        several devices each batch is sharded over them by rows."""
        import jax
        import jax.numpy as jnp
        shape = (k,) + self.data_shape

        def make(key):
            kx, ky = jax.random.split(jax.random.fold_in(key, 1))
            x = jax.random.uniform(kx, shape, jnp.float32).astype(dtype)
            y = jax.random.randint(ky, (k, self.batch), 0,
                                   self.num_classes).astype(jnp.float32)
            return [x[i] for i in range(k)], [y[i] for i in range(k)]

        sharding = self.batch_sharding()
        xs, ys = jax.jit(make, out_shardings=sharding)(self.key)
        return xs, ys

    def batch_sharding(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        if len(self.devices) == 1:
            return jax.sharding.SingleDeviceSharding(self.devices[0])
        import numpy as np
        mesh = Mesh(np.array(self.devices), ('data',))
        return NamedSharding(mesh, PartitionSpec('data'))

    def host_pool(self, n):
        """n float32 batches in host memory from the seed, all rows
        different, as a user's decoded images would arrive."""
        import numpy as np
        rng = np.random.default_rng(self.seed)
        x = rng.random((n * self.batch,) + self.data_shape[1:],
                       dtype=np.float32)
        y = rng.integers(0, self.num_classes,
                         n * self.batch).astype(np.float32)
        return x, y

    # -- the program's state, read for the comparison -----------------------
    def read_state(self, mod):
        """(weights, momenta): name -> float32 copies on the device of
        what the optimizer holds (the fp32 master where there is one)."""
        import jax.numpy as jnp
        ex = mod._exec_group.executor
        fu = mod._fused_updater
        weights, moms = {}, {}
        for name in ex._diff_names:
            master = fu.masters.get(name)
            w = master if master is not None else ex.arg_dict[name]._data
            weights[name] = jnp.array(w, jnp.float32, copy=True)
            moms[name] = jnp.array(fu.states[name], jnp.float32, copy=True)
        return weights, moms

    def last_outputs(self, mod):
        """The newest dispatch's outputs: fresh arrays every dispatch,
        never donated, so they can be waited on later."""
        return [o._data for o in mod._exec_group.executor.outputs]

    @staticmethod
    def release_module(mod):
        """Drop the module's device state (weights, gradients, optimizer
        state) so that the reference has the chip's memory."""
        mod._exec_group = None
        mod._fused_updater = None

    def optimizer_state_bytes(self, mod):
        """Bytes of momenta and fp32 masters on the fullest device."""
        fu = mod._fused_updater
        per_device = {}
        for group in (fu.states, fu.masters):
            for arr in group.values():
                if arr is None:
                    continue
                for shard in arr.addressable_shards:
                    per_device[shard.device] = per_device.get(
                        shard.device, 0) + shard.data.nbytes
        return max(per_device.values()) if per_device else 0

    # -- the window ----------------------------------------------------------
    def window_seconds(self):
        """A traced run measures a shorter window, all of it traced."""
        if self.trace:
            return min(self.seconds, float(self.traffic['trace_seconds']))
        return self.seconds

    def open_window(self):
        """Call at a block_until_ready, after warm-up."""
        import jax
        self.mark('warm, window opens')
        self.setup_s = time.perf_counter() - T_START
        self.compile_setup = self.clog.mark()
        self.spans.reset()
        if self.trace:
            jax.profiler.start_trace(self.trace_dir)
            self.spans.recording = True
            self._window_ann = jax.profiler.TraceAnnotation(
                Spans.PREFIX + 'window')
            self._window_ann.__enter__()
        self.t_open = time.perf_counter()

    def elapsed(self):
        return time.perf_counter() - self.t_open

    def close_window(self, steps, dispatches):
        """Call at the block_until_ready that ends the last dispatch."""
        import jax
        t = time.perf_counter()
        if self.trace:
            self._window_ann.__exit__(None, None, None)
            self.spans.recording = False
            jax.profiler.stop_trace()
        requests, seconds = self.clog.mark()
        self.window = {
            'seconds': t - self.t_open, 'steps': int(steps),
            'dispatches': int(dispatches),
            'items': int(steps) * self.batch,
            'compiles': requests - self.compile_setup[0],
            'compile_s_in_window': seconds - self.compile_setup[1],
            'span_s': dict(self.spans.total_s),
            'span_n': dict(self.spans.count)}


def find_devices(chips, peaks):
    """The chips the cell asks for, or exit: no CPU stands in."""
    import jax
    devices = jax.devices()
    tpus = [d for d in devices if d.platform == 'tpu']
    if len(tpus) < chips:
        log('benchmark: the cell needs %d TPU chip(s); jax found %s'
            % (chips, [str(d) for d in devices]))
        raise SystemExit(3)
    kind = tpus[0].device_kind
    if kind not in peaks:
        log('benchmark: device kind %r is not in peaks.json' % kind)
        raise SystemExit(3)
    return tpus[:chips], peaks[kind]


def memory_peak_bytes(devices):
    """The most of its memory that the fullest chip had claimed: the
    peak of the arrays in use plus the peak of what the runtime had
    reserved for compiled programs' temporaries, which the TPU's
    allocator counts apart (a ResNet-50 step's activations are in the
    second and not in the first).  The two peaks need not fall
    together, so this is an upper bound on the true peak."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        log('memory_stats %s: %s' % (d, stats))
        peaks.append(stats.get('peak_bytes_in_use', 0) +
                     stats.get('peak_bytes_reserved', 0))
    return max(peaks) if peaks else 0


def measure(cell, seed, seconds, trace, contexts, peak):
    """Everything below the look for a chip: drive the cell's entry,
    reduce the trace, run the comparison, and return the result object
    (the tests call this with CPU contexts and a made-up peak)."""
    import gc
    import shutil
    import check
    from mxnet_tpu import exec_cache
    exec_cache.setup_persistent_cache()
    clog = CompileLog()
    h = Harness(cell, seed, seconds, trace, contexts, clog)
    h.mark('imports, layer shapes')
    entry = load_file_module(
        os.path.join(cell.here, 'entries', cell.traffic['entry'] + '.py'),
        'entry_' + cell.traffic['entry'])
    produced = entry.run(h)       # drives set-up and the window
    if h.window is None:
        raise RuntimeError('entry %s closed no window'
                           % cell.traffic['entry'])
    peak_bytes = memory_peak_bytes(h.devices)
    window = h.window
    context = {'window': window, 'config': cell.config,
               'traffic': cell.traffic, 'peak': peak,
               'chips': len(contexts), 'layers': h.layers,
               'batch': h.batch, 'setup_s': h.setup_s,
               'compile_s_setup': h.compile_setup[1],
               'optimizer_state_bytes': produced.pop(
                   'optimizer_state_bytes'),
               'trace': None}
    device = {'platform': h.devices[0].platform,
              'kind': h.devices[0].device_kind, 'count': len(h.devices),
              'memory_peak_bytes': int(peak_bytes)}
    result = {'correct': False, 'attempted': window['dispatches'],
              'failed': 0, 'metrics': {}, 'device': device}
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(h.trace_dir)
        shutil.rmtree(h.trace_dir, ignore_errors=True)   # tens of MB
        context['trace'] = reduced
        device['busy_s'] = reduced['busy_s']
        device['window_s'] = reduced['window_s']
        result['breakdown'] = trace_reduce.breakdown(
            reduced, cell.traffic['entry'])
    # the program's state goes before the reference takes its room
    release = produced.pop('release')
    release()
    gc.collect()
    rate = window['items'] / window['seconds']
    if trace:
        for m in cell.metrics('per_layer'):
            value = cell.reader(m['name']).read(context)
            if value is not None:
                result['metrics'][m['name']] = {'value': float(value),
                                                'unit': m['unit']}
    else:
        values = {cell.traffic['reports']: rate,
                  'peak_hbm_gib': peak_bytes / 2.0 ** 30,
                  'setup_s': h.setup_s}
        for m in cell.metrics('end_to_end'):
            result['metrics'][m['name']] = {
                'value': float(values[m['name']]), 'unit': m['unit']}
    t0 = time.perf_counter()
    compared, others = check.compare_with_reference(h, produced,
                                                    cell.limits)
    result['correct'] = all(c['value'] <= c['limit']
                            for c in compared.values())
    result['reference_s'] = time.perf_counter() - t0
    result['window'] = {k: window[k] for k in
                        ('seconds', 'steps', 'dispatches', 'compiles')}
    result['not_compared'] = others
    result['compared'] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    peaks = read_json(HERE, 'peaks.json')['device_kinds']
    devices, peak = find_devices(cell.chips, peaks)
    import mxnet_tpu as mx
    contexts = [mx.tpu(d.id) for d in devices]
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     contexts, peak)
    for name, c in result['compared'].items():
        log('compared %s: %.6g (limit %.6g)' % (name, c['value'],
                                                c['limit']))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
