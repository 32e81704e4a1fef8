"""Low-precision stack (PERF round 17): the shared quantization core,
int8 serving (weight-storage quantization + parity gate), quantized
registry residency/paging, and the int8/bf16 collective wire format
with error feedback.  CPU-sized — every engine here is a tiny MLP."""
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import dist, exec_cache, nd, profiler, sym
from mxnet_tpu import quantization as Q
from mxnet_tpu.base import MXNetError
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.quantization import (QuantConfig, QuantParityError,
                                    WireCodec)
from mxnet_tpu.serving_fleet import ModelRegistry


def _mlp(dim=64, hidden=128, classes=8):
    data = sym.Variable('data')
    x = sym.Activation(sym.FullyConnected(data, num_hidden=hidden,
                                          name='fc1'), act_type='relu')
    x = sym.FullyConnected(x, num_hidden=classes, name='fc2')
    return sym.SoftmaxOutput(x, name='softmax')


def _params(net, dim=64, seed=0, scale=0.2):
    probe = net.simple_bind(mx.cpu(), grad_req='null', data=(1, dim))
    rng = np.random.RandomState(seed)
    return {k: nd.array(rng.randn(*v.shape).astype(np.float32) * scale)
            for k, v in probe.arg_dict.items() if k != 'data'}


def _predictor(seed=0):
    net = _mlp()
    return Predictor(symbol=net, arg_params=_params(net, seed=seed),
                     input_shapes={'data': (1, 64)})


# ---------------------------------------------------------------------------
# core math
# ---------------------------------------------------------------------------

def test_symmetric_int8_round_trip_and_edges():
    rng = np.random.RandomState(0)
    a = rng.randn(16, 32).astype(np.float32)
    for axis in (None, 0):
        q, s = Q.quantize_int8(a, axis=axis)
        assert q.dtype == np.int8
        assert int(q.min()) >= -127          # -128 never produced
        back = Q.dequantize_int8(q, s, axis=axis)
        step = np.max(np.abs(a)) / 127.0
        assert np.abs(back - a).max() <= step / 2 + 1e-7
    # exact extremes land on the extreme codes
    e = np.array([3.0, -3.0, 0.0], np.float32)
    q, s = Q.quantize_int8(e)
    np.testing.assert_array_equal(q, [127, -127, 0])


def test_zero_range_quantizes_to_exact_zeros():
    z = np.zeros((3, 3), np.float32)
    q, s = Q.quantize_int8(z)
    assert float(s) == 0.0
    np.testing.assert_array_equal(q, np.zeros((3, 3), np.int8))
    np.testing.assert_array_equal(Q.dequantize_int8(q, s), z)


def test_per_channel_beats_per_tensor_on_skewed_channels():
    rng = np.random.RandomState(1)
    a = rng.randn(4, 256).astype(np.float32)
    a[0] *= 100.0                            # one hot output channel
    qt, st = Q.quantize_int8(a)
    qc, sc = Q.quantize_int8(a, axis=0)
    err_t = np.abs(Q.dequantize_int8(qt, st) - a)[1:].max()
    err_c = np.abs(Q.dequantize_int8(qc, sc, axis=0) - a)[1:].max()
    assert err_c < err_t / 10


def test_calibrate_modes():
    batches = [np.linspace(-1, 1, 100, dtype=np.float32),
               np.asarray([50.0], np.float32)]     # one outlier
    lo, hi = Q.calibrate(batches, 'minmax')
    assert hi == 50.0 and lo == -1.0
    lo_p, hi_p = Q.calibrate(batches, 'percentile', percentile=99.0)
    assert hi_p < 2.0                        # outlier clipped
    with pytest.raises(MXNetError):
        Q.calibrate(batches, 'bogus')
    with pytest.raises(MXNetError):
        Q.calibrate([])


def test_wire_codec_int8_roundtrip_bytes_and_ef():
    rng = np.random.RandomState(2)
    arrays = [rng.randn(500).astype(np.float32),
              rng.randn(8, 8).astype(np.float32)]
    c = WireCodec('int8')
    p, s = c.encode(arrays)
    assert all(x.dtype == np.int8 for x in p)
    wire = WireCodec.wire_nbytes(p, s)
    assert wire * 3.5 < sum(a.nbytes for a in arrays)
    dec = c.decode(p, s, [np.float32] * 2)
    step = max(np.abs(a).max() for a in arrays) / 127.0
    assert max(np.abs(a - d).max()
               for a, d in zip(arrays, dec)) <= step / 2 + 1e-7
    # error feedback: encoding the SAME value repeatedly averages the
    # quantization bias out (the residual carries it forward)
    # (a constant array would round-trip EXACTLY — every element sits
    # at the max, whose code is always exact — so spread the values)
    x = [np.linspace(0.001, 0.0123, 50).astype(np.float32)]
    c2 = WireCodec('int8')
    p, s = c2.encode(x)
    assert c2.residual_norm() > 0.0          # first round's error held
    tot = c2.decode(p, s, [np.float32])[0].astype(np.float64)
    for _ in range(63):
        p, s = c2.encode(x)
        tot += c2.decode(p, s, [np.float32])[0]
    assert np.abs(tot / 64 - x[0]).max() < 2e-5
    # shape change resets the residual stream, never corrupts
    c2.encode([np.zeros(7, np.float32)])
    with pytest.raises(MXNetError):
        WireCodec('int4')


def test_wire_codec_bf16_and_fp32():
    a = [np.asarray([1.0, 2.0, 3.0], np.float32)]
    c = WireCodec('bf16')
    p, s = c.encode(a)
    assert p[0].nbytes == 6 and s.size == 0
    np.testing.assert_allclose(c.decode(p, s, [np.float32])[0], a[0],
                               rtol=1e-2)
    c32 = WireCodec('fp32')
    p, s = c32.encode(a)
    np.testing.assert_array_equal(p[0], a[0])
    assert c32.residual_norm() == 0.0


# ---------------------------------------------------------------------------
# int8 serving (arm a)
# ---------------------------------------------------------------------------

def test_int8_engine_parity_residency_and_bitwise_recreation():
    x = np.random.RandomState(3).randn(2, 64).astype(np.float32)
    p_fp = _predictor(seed=4)
    eng_fp = p_fp.serve(max_batch=4, max_wait_us=0)
    fp_out = eng_fp.predict(x)
    fp_bytes = eng_fp.resident_bytes()
    eng_fp.close()

    eng = _predictor(seed=4).serve(max_batch=4, max_wait_us=0,
                                   quantize='int8')
    q_out = eng.predict(x)
    st = eng.stats()
    # parity: int8 weights move the outputs only within the gate tol
    assert np.abs(fp_out - q_out).max() < 0.05
    assert st['quantized']['dtype'] == 'int8'
    assert st['quantized']['parity_measured'] <= 0.05
    # residency: int8 codes + scales ~4x below the fp engine
    assert eng.resident_bytes() * 3 < fp_bytes
    assert st['compiles_after_warmup'] == 0
    eng.close()

    # re-created engine: zero new compiles, bitwise-identical answers
    c0 = exec_cache.stats()['total_compile_s']
    eng2 = _predictor(seed=4).serve(max_batch=4, max_wait_us=0,
                                    quantize='int8')
    q2 = eng2.predict(x)
    assert exec_cache.stats()['total_compile_s'] == c0
    np.testing.assert_array_equal(q_out, q2)
    eng2.close()


def test_int8_engine_batching_parity_within_bucket():
    # rows sliced out of one padded bucket dispatch must not depend
    # on what they were co-batched with (row independence survives
    # the dequant path) — compare AT THE SAME RUNG: a 3-row request
    # pads to bucket 4, and its rows must bitwise-match the same rows
    # inside a full 4-row batch (whose 4th row differs)
    eng = _predictor(seed=5).serve(max_batch=4, max_wait_us=0,
                                   quantize='int8')
    rng = np.random.RandomState(6)
    xs = rng.randn(4, 64).astype(np.float32)
    full = eng.predict(xs)
    padded = eng.predict(xs[:3])
    np.testing.assert_array_equal(full[:3], padded)
    eng.close()


def test_parity_gate_refuses_and_mutates_nothing():
    pred = _predictor(seed=7)
    before = pred._executor.arg_dict['fc1_weight'].asnumpy().copy()
    with pytest.raises(QuantParityError):
        pred.serve(max_batch=4, quantize=QuantConfig(parity_tol=0.0))
    after = pred._executor.arg_dict['fc1_weight']
    assert np.dtype(after.dtype) == np.float32
    np.testing.assert_array_equal(before, after.asnumpy())
    # the refused predictor still serves fp
    eng = pred.serve(max_batch=4, max_wait_us=0)
    eng.predict(np.zeros((1, 64), np.float32))
    eng.close()


def test_quantize_rejects_model_without_quantizable_weights():
    data = sym.Variable('data')
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=2, name='t'), name='softmax')
    probe = net.simple_bind(mx.cpu(), grad_req='null', data=(1, 4))
    args = {k: nd.array(np.ones(v.shape, np.float32) * 0.1)
            for k, v in probe.arg_dict.items() if k != 'data'}
    pred = Predictor(symbol=net, arg_params=args,
                     input_shapes={'data': (1, 4)})
    with pytest.raises(MXNetError, match='no quantizable'):
        pred.serve(max_batch=2, quantize='int8')


def test_bf16_engine_mode():
    x = np.random.RandomState(8).randn(1, 64).astype(np.float32)
    p_fp = _predictor(seed=9)
    eng_fp = p_fp.serve(max_batch=2, max_wait_us=0)
    fp_out = eng_fp.predict(x)
    fp_bytes = eng_fp.resident_bytes()
    eng_fp.close()
    eng = _predictor(seed=9).serve(max_batch=2, max_wait_us=0,
                                   quantize='bf16')
    out = eng.predict(x)
    assert np.abs(fp_out - out).max() < 0.05
    assert eng.resident_bytes() * 1.5 < fp_bytes
    eng.close()


def test_quant_config_resolve_and_env_default(monkeypatch):
    assert QuantConfig.resolve(None) is None
    cfg = QuantConfig.resolve('int8')
    assert isinstance(cfg, QuantConfig) and cfg.dtype == 'int8'
    assert QuantConfig.resolve(cfg) is cfg
    with pytest.raises(MXNetError):
        QuantConfig.resolve('fp8')
    monkeypatch.setenv('MXNET_TPU_SERVE_QUANTIZE', 'int8')
    eng = _predictor(seed=10).serve(max_batch=2, max_wait_us=0)
    assert eng._quant_live
    eng.close()
    # disable-style env values mean OFF, not a crash
    for off in ('0', 'off', 'none', 'fp32'):
        monkeypatch.setenv('MXNET_TPU_SERVE_QUANTIZE', off)
        eng = _predictor(seed=10).serve(max_batch=2, max_wait_us=0)
        assert not eng._quant_live
        eng.close()


# ---------------------------------------------------------------------------
# quantized registry (arm b)
# ---------------------------------------------------------------------------

@pytest.fixture
def checkpoints(tmp_path):
    from mxnet_tpu.module import Module
    prefixes = []
    for i in range(3):
        net = _mlp()
        m = Module(net, data_names=['data'],
                   label_names=['softmax_label'], context=mx.cpu())
        m.bind(data_shapes=[('data', (4, 64))],
               label_shapes=[('softmax_label', (4,))])
        m.init_params(mx.init.Normal(0.2 + 0.01 * i))
        prefix = str(tmp_path / ('m%d' % i))
        m.save_checkpoint(prefix, 0)
        prefixes.append(prefix)
    return prefixes


def test_registry_quantized_residency_multiplier(checkpoints):
    fp_size = os.path.getsize(checkpoints[0] + '-0000.params')
    budget = int(fp_size * 1.2)              # fits ONE fp model
    x = np.random.RandomState(0).randn(1, 64).astype(np.float32)

    reg = ModelRegistry(budget_bytes=budget)
    for i, p in enumerate(checkpoints):
        reg.register('m%d' % i, prefix=p, epoch=0,
                      input_shapes={'data': (1, 64)}, max_batch=4)
    for i in range(3):
        reg.predict('m%d' % i, x)
    st = reg.stats()
    assert sum(1 for m in st['models'].values() if m['resident']) == 1
    assert st['evictions'] == 2
    reg.close()

    reg2 = ModelRegistry(budget_bytes=budget)
    for i, p in enumerate(checkpoints):
        reg2.register('q%d' % i, prefix=p, epoch=0,
                      input_shapes={'data': (1, 64)}, max_batch=4,
                      quantize='int8')
    for i in range(3):
        reg2.predict('q%d' % i, x)
    st = reg2.stats()
    # >= 2x more models live under the SAME budget (measured ~3.6x
    # per-model byte ratio, so all 3 fit)
    assert sum(1 for m in st['models'].values() if m['resident']) == 3
    assert st['evictions'] == 0
    assert st['resident_bytes'] <= budget
    # est_bytes honesty: the pre-load estimate counts the QUANTIZED
    # representation (satellite fix) — with fp32-file estimates the
    # strict budget would have refused the 2nd model
    assert st['peak_resident_bytes'] <= budget
    assert profiler.quant_stats()['quant_models_resident'] == 3
    # evict/re-warm a quantized model: zero new XLA compiles
    c0 = exec_cache.stats()['total_compile_s']
    reg2.evict('q0')
    reg2.predict('q0', x)
    assert exec_cache.stats()['total_compile_s'] == c0
    reg2.close()


def test_registry_strict_budget_uses_quantized_estimate(checkpoints,
                                                        monkeypatch):
    monkeypatch.setenv('MXNET_TPU_SERVE_STRICT_BUDGET', '1')
    fp_size = os.path.getsize(checkpoints[0] + '-0000.params')
    x = np.zeros((1, 64), np.float32)
    # budget below ONE fp32 file but above the int8 estimate: a
    # fp32-file estimate would 507 before even loading
    reg = ModelRegistry(budget_bytes=int(fp_size * 0.45))
    reg.register('q', prefix=checkpoints[0], epoch=0,
                 input_shapes={'data': (1, 64)}, max_batch=4,
                 quantize='int8')
    reg.predict('q', x)                      # loads fine
    st = reg.stats()
    assert st['models']['q']['resident']
    assert st['resident_bytes'] <= reg.budget_bytes
    reg.close()


def test_registry_page_dtype_round_trip(checkpoints):
    x = np.random.RandomState(1).randn(1, 64).astype(np.float32)
    reg = ModelRegistry()
    reg.register('p', prefix=checkpoints[0], epoch=0,
                 input_shapes={'data': (1, 64)}, max_batch=4,
                 page_dtype='int8')
    y1 = reg.predict('p', x)
    reg.evict('p')
    st = reg.stats()
    fp_size = os.path.getsize(checkpoints[0] + '-0000.params')
    assert 0 < st['paged_bytes'] < fp_size / 2
    assert st['models']['p']['paged']
    y2 = reg.predict('p', x)                 # page-in from the image
    st = reg.stats()
    assert st['page_ins'] == 1
    assert st['paged_bytes'] == 0            # image consumed
    # int8 round trip through the image moves outputs only slightly
    assert np.abs(np.asarray(y1) - np.asarray(y2)).max() < 0.05
    assert profiler.quant_stats()['quant_page_ins'] >= 1
    reg.close()


def test_registry_page_dtype_validation(checkpoints):
    reg = ModelRegistry()
    with pytest.raises(MXNetError, match='prefix'):
        reg.register('a', loader=lambda: None, page_dtype='int8')
    with pytest.raises(MXNetError, match='exclusive'):
        reg.register('b', prefix=checkpoints[0], epoch=0,
                     input_shapes={'data': (1, 64)},
                     quantize='int8', page_dtype='int8')
    reg.close()


def test_registry_env_quantize_respects_page_dtype(checkpoints,
                                                   monkeypatch):
    # the fleet-wide MXNET_TPU_SERVE_QUANTIZE default must resolve in
    # register(), not behind the registry's back in the engine: a
    # page_dtype model's holder weights must stay fp for the page-out
    # snapshot (env-quantizing them would image raw int8 codes as
    # 'fp' passthrough arrays — garbage on page-in), while a plain
    # model picks the env default up WITH the scaled byte estimate
    monkeypatch.setenv('MXNET_TPU_SERVE_QUANTIZE', 'int8')
    x = np.zeros((1, 64), np.float32)
    reg = ModelRegistry()
    reg.register('p', prefix=checkpoints[0], epoch=0,
                 input_shapes={'data': (1, 64)}, max_batch=4,
                 page_dtype='int8')
    reg.register('q', prefix=checkpoints[1], epoch=0,
                 input_shapes={'data': (1, 64)}, max_batch=4)
    y1 = reg.predict('p', x)
    ent = reg._entry('p')
    assert not ent.engine._quant_live        # env knob did NOT apply
    assert np.dtype(ent.holder._executor.arg_dict['fc1_weight'].dtype) \
        == np.float32
    reg.predict('q', x)
    assert reg._entry('q').engine._quant_live  # plain model DID
    fp_file = os.path.getsize(checkpoints[1] + '-0000.params')
    assert reg._entry('q').bytes < fp_file / 2  # measured, quantized
    reg.evict('p')
    y2 = reg.predict('p', x)                 # page round trip intact
    assert np.abs(np.asarray(y1) - np.asarray(y2)).max() < 0.05
    reg.close()


def test_registry_paged_budget_drops_oldest(checkpoints, monkeypatch):
    x = np.zeros((1, 64), np.float32)
    reg = ModelRegistry()
    for i, p in enumerate(checkpoints[:2]):
        reg.register('p%d' % i, prefix=p, epoch=0,
                     input_shapes={'data': (1, 64)}, max_batch=4,
                     page_dtype='int8')
    reg.predict('p0', x)
    reg.evict('p0')
    one_image = reg.stats()['paged_bytes']
    assert one_image > 0
    # budget for exactly one image: paging the second drops the first
    monkeypatch.setenv('MXNET_TPU_SERVE_PAGED_BYTES',
                       str(int(one_image * 1.5)))
    reg.predict('p1', x)
    reg.evict('p1')
    st = reg.stats()
    assert st['page_drops'] == 1
    assert st['models']['p1']['paged'] and not st['models']['p0']['paged']
    reg.close()


# ---------------------------------------------------------------------------
# collective wire format (arm c)
# ---------------------------------------------------------------------------

def _dist_pair():
    coord = dist.Coordinator(port=0, world=2, bind_addr='127.0.0.1',
                             dead_after=10).start()
    rts = [None, None]
    errs = [None, None]

    def mk(r):
        try:
            rts[r] = dist.DistRuntime(
                r, 2, address='127.0.0.1', port=coord.port,
                start_coordinator=False, timeout=15, hb_interval=0.2)
        except BaseException as e:
            errs[r] = e
    ts = [threading.Thread(target=mk, args=(r,)) for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(e is None for e in errs), errs
    return coord, rts


def test_dist_allreduce_int8_wire_deterministic_and_4x():
    coord, rts = _dist_pair()
    try:
        results = {}

        def work(rank):
            rng = np.random.RandomState(rank)
            outs = []
            for step in range(4):
                arrays = [rng.randn(1000).astype(np.float32),
                          rng.randn(16, 16).astype(np.float32)]
                outs.append(rts[rank].allreduce(arrays, name='t',
                                                wire='int8'))
            results[rank] = outs
        b0 = profiler.dist_stats()['dist_allreduce_bytes']
        ts = [threading.Thread(target=work, args=(r,)) for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert set(results) == {0, 1}
        # every rank decodes the identical compressed bytes
        for s in range(4):
            for a, b in zip(results[0][s], results[1][s]):
                np.testing.assert_array_equal(a, b)
        # the counter records ACTUAL wire bytes: ~4x below fp32
        wire = profiler.dist_stats()['dist_allreduce_bytes'] - b0
        fp = (1000 * 4 + 16 * 16 * 4) * 2 * 2 * 4
        assert wire * 3.5 < fp
        qs = profiler.quant_stats()
        assert qs['quant_wire_bytes_saved'] > 0
        assert qs['quant_error_feedback_norm'] > 0.0
    finally:
        for rt in reversed(rts):
            rt.shutdown()
        coord.stop()


def test_dist_allreduce_wire_error_feedback_converges():
    coord, rts = _dist_pair()
    try:
        sums = {}

        def work(rank):
            acc = np.zeros(64)
            val = np.full(64, 0.00789 * (rank + 1), np.float32)
            for _ in range(32):
                acc += rts[rank].allreduce([val], name='ef',
                                           wire='int8')[0]
            sums[rank] = acc
        ts = [threading.Thread(target=work, args=(r,)) for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        exact = 32 * (0.00789 + 2 * 0.00789)
        # EF cancels the per-round quantization bias: the 32-round
        # accumulation lands within a fraction of ONE round's step
        assert np.abs(sums[0] - exact).max() < 5e-4
    finally:
        for rt in reversed(rts):
            rt.shutdown()
        coord.stop()


def test_dist_allreduce_wire_mismatch_and_bf16():
    coord, rts = _dist_pair()
    try:
        res = {}

        def work(rank, wire, name):
            try:
                res[rank] = rts[rank].allreduce(
                    [np.ones(8, np.float32) * (rank + 1)],
                    name=name, wire=wire)
            except MXNetError as e:
                res[rank] = e
        # bf16 wire sums fine
        ts = [threading.Thread(target=work, args=(r, 'bf16', 'b'))
              for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        np.testing.assert_allclose(res[0][0], np.full(8, 3.0), rtol=1e-2)
        # mismatched wire modes fail typed, naming the knob
        ts = [threading.Thread(target=work,
                               args=(r, 'int8' if r == 0 else 'fp32',
                                     'mm'))
              for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert any(isinstance(res[r], MXNetError) and
                   'WIRE_DTYPE' in str(res[r]) for r in (0, 1))
    finally:
        for rt in reversed(rts):
            rt.shutdown()
        coord.stop()


def test_quantized_allreduce_shardmap_parity():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import collectives
    from jax import shard_map
    from mxnet_tpu.parallel.mesh import make_mesh
    mesh = make_mesh()                       # all 8 virtual devices
    rng = np.random.RandomState(4)
    x = rng.randn(8, 32).astype(np.float32)

    def f(xs):
        return collectives.quantized_allreduce(xs, 'data')

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P('data'),
                            out_specs=P('data')))(jnp.asarray(x))
    # per-shard int8 quantization: each row's contribution rounds to
    # its own scale's grid; the sum of 8 shards stays within the sum
    # of half-steps of the true allreduce
    exact = x.sum(axis=0)
    tol = sum(np.abs(x[i]).max() / 127.0 / 2 for i in range(8)) + 1e-6
    got = np.asarray(out)
    for i in range(8):                       # identical on every shard
        np.testing.assert_array_equal(got[i], got[0])
    assert np.abs(got[0] - exact).max() <= tol


def test_wire_dtype_from_env(monkeypatch):
    assert Q.wire_dtype_from_env(None) == 'fp32'
    monkeypatch.setenv('MXNET_TPU_DIST_WIRE_DTYPE', 'int8')
    assert Q.wire_dtype_from_env(None) == 'int8'
    assert Q.wire_dtype_from_env('bf16') == 'bf16'   # explicit wins
    monkeypatch.setenv('MXNET_TPU_DIST_WIRE_DTYPE', 'nope')
    with pytest.raises(MXNetError):
        Q.wire_dtype_from_env(None)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_quant_counters_in_summary_and_dump(tmp_path):
    profiler.add_quant_stats(int8_rungs_warmed=2, wire_bytes_saved=100,
                             models_resident=1,
                             error_feedback_norm=0.5, page_ins=1,
                             paged_bytes=64)
    st = profiler.quant_stats()
    assert st['quant_int8_rungs_warmed'] >= 2
    assert st['quant_models_resident'] == 1
    assert st['quant_error_feedback_norm'] == 0.5
    text = profiler.summary(print_out=False)
    for key in ('quant_models_resident', 'quant_int8_rungs_warmed',
                'quant_wire_bytes_saved', 'quant_error_feedback_norm',
                'quant_page_ins', 'quant_paged_bytes'):
        assert key in text
    import json
    profiler.profiler_set_config(filename=str(tmp_path / 'p.json'))
    profiler.profiler_set_state('run')
    profiler.profiler_set_state('stop')
    path = profiler.dump_profile()
    lanes = {e.get('name'): e for e in
             json.load(open(path))['traceEvents'] if e.get('ph') == 'M'}
    assert 'quant' in lanes
    assert 'quant_wire_bytes_saved' in lanes['quant']['args']
    profiler.clear()
    assert profiler.quant_stats()['quant_models_resident'] == 0
