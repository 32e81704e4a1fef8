"""Compiled-program cache + device-resident input prefetch tests.

Covers: graph-signature canonicalization (same net built twice -> same
key; attr / dtype / donation changes -> different keys), zero-recompile
rebinds (simple_bind twice, Module.reshape back to a seen shape), fused
train-step sharing across Modules, the memory_cost AOT reuse, profiler
counter exposure, prefetch_to_device equivalence/placement, and
PrefetchingIter worker-thread lifecycle."""
import gc
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import exec_cache, io as mxio, nd, profiler, sym


def _mlp(num_hidden=16, n_out=3):
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, num_hidden=num_hidden)
    act = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(act, num_hidden=n_out)
    return sym.SoftmaxOutput(fc2, name='softmax')


# ---------------------------------------------------------------------------
# graph-signature canonicalization
# ---------------------------------------------------------------------------

def test_signature_same_symbol_built_twice():
    # two builds of the same net get different auto-generated node
    # names; the signature alpha-renames them away
    ex1 = _mlp().simple_bind(mx.cpu(), data=(8, 20))
    ex2 = _mlp().simple_bind(mx.cpu(), data=(8, 20))
    assert ex1._sig is not None
    assert ex1._sig == ex2._sig


def test_signature_attr_change():
    ex1 = _mlp(num_hidden=16).simple_bind(mx.cpu(), data=(8, 20))
    ex2 = _mlp(num_hidden=17).simple_bind(mx.cpu(), data=(8, 20))
    assert ex1._sig != ex2._sig


def test_signature_shape_change():
    ex1 = _mlp().simple_bind(mx.cpu(), data=(8, 20))
    ex2 = _mlp().simple_bind(mx.cpu(), data=(4, 20))
    assert ex1._sig != ex2._sig


def test_signature_dtype_change():
    a = sym.Variable('a')
    b = sym.Variable('b')
    c = a * b
    ex1 = c.bind(mx.cpu(), {'a': nd.array([1.0, 2.0]),
                            'b': nd.array([3.0, 4.0])})
    ex2 = c.bind(mx.cpu(), {'a': nd.array(np.array([1, 2], np.float16)),
                            'b': nd.array(np.array([3, 4], np.float16))})
    assert ex1._sig != ex2._sig


def test_signature_donation_change():
    # grad_req is part of the key: the traced backward differs
    net = _mlp()
    ex_w = net.simple_bind(mx.cpu(), grad_req='write', data=(8, 20))
    ex_n = net.simple_bind(mx.cpu(), grad_req='null', data=(8, 20))
    assert ex_w._sig != ex_n._sig


# ---------------------------------------------------------------------------
# zero-recompile rebinds
# ---------------------------------------------------------------------------

def test_simple_bind_twice_zero_new_compiles():
    exec_cache.clear()      # other tests may have seeded this topology
    net = _mlp()
    before = exec_cache.stats()
    ex1 = net.simple_bind(mx.cpu(), data=(8, 20))
    ex1.arg_dict['data'][:] = np.random.rand(8, 20)
    out1 = ex1.forward()[0].asnumpy()
    compiled = ex1._fwd_eval.fn._cache_size()
    mid = exec_cache.stats()
    assert mid['misses'] == before['misses'] + 1

    ex2 = net.simple_bind(mx.cpu(), data=(8, 20))
    after = exec_cache.stats()
    assert after['hits'] == mid['hits'] + 1
    assert after['misses'] == mid['misses']
    # the jitted step functions are literally shared...
    assert ex2._fwd_eval is ex1._fwd_eval
    assert ex2._fwd_bwd is ex1._fwd_bwd
    # ...so running the second executor compiles NOTHING new
    ex2.arg_dict['data'][:] = ex1.arg_dict['data'].asnumpy()
    out2 = ex2.forward()[0].asnumpy()
    assert ex1._fwd_eval.fn._cache_size() == compiled
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


def test_module_reshape_back_to_seen_shape_hits_cache():
    exec_cache.clear()
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[mxio.DataDesc('data', (8, 20))],
             label_shapes=[mxio.DataDesc('softmax_label', (8,))])
    mod.init_params()
    ex0 = mod._exec_group.executor
    fwd0 = ex0._fwd_train
    # populate the jit cache at the original shape first
    batch = mxio.DataBatch(data=[nd.array(np.random.rand(8, 20))],
                           label=[nd.array(np.arange(8.0) % 3)])
    mod.forward(batch, is_train=True)
    compiled0 = fwd0.fn._cache_size()

    mod.reshape(data_shapes=[mxio.DataDesc('data', (4, 20))],
                label_shapes=[mxio.DataDesc('softmax_label', (4,))])
    before = exec_cache.stats()
    mod.reshape(data_shapes=[mxio.DataDesc('data', (8, 20))],
                label_shapes=[mxio.DataDesc('softmax_label', (8,))])
    after = exec_cache.stats()
    assert after['hits'] == before['hits'] + 1
    assert after['misses'] == before['misses']
    ex2 = mod._exec_group.executor
    assert ex2._fwd_train is fwd0
    # run a forward at the seen shape: zero new XLA compilations
    batch = mxio.DataBatch(data=[nd.array(np.random.rand(8, 20))],
                           label=[nd.array(np.arange(8.0) % 3)])
    mod.forward(batch, is_train=True)
    assert fwd0.fn._cache_size() == compiled0


def test_fused_train_step_shared_across_modules():
    X = np.random.rand(16, 10).astype(np.float32)
    y = (np.random.rand(16) * 3).astype(np.float32)
    batch = mxio.DataBatch(data=[nd.array(X)], label=[nd.array(y)])

    def train_one():
        mod = mx.mod.Module(_mlp(num_hidden=9), context=mx.cpu())
        mod.bind(data_shapes=[mxio.DataDesc('data', (16, 10))],
                 label_shapes=[mxio.DataDesc('softmax_label', (16,))])
        mod.init_params()
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.1,
                                             'momentum': 0.9})
        mod.forward_backward(batch)
        mod.update()
        return mod

    mod1 = train_one()
    before = exec_cache.stats()
    mod2 = train_one()
    after = exec_cache.stats()
    assert mod2._step_program('single') is mod1._step_program('single')
    assert after['total_compile_s'] == before['total_compile_s']


def test_memory_cost_reuses_cache():
    net = _mlp()
    ex1 = net.simple_bind(mx.cpu(), data=(8, 20))
    stats1 = ex1.memory_cost('forward')
    before = exec_cache.stats()['total_compile_s']
    # second call (and a second equivalent executor) reuse the AOT
    # compile instead of triggering another one
    ex2 = net.simple_bind(mx.cpu(), data=(8, 20))
    stats2 = ex2.memory_cost('forward')
    assert exec_cache.stats()['total_compile_s'] == before
    assert stats1 == stats2


def test_exec_cache_disabled(monkeypatch):
    monkeypatch.setenv('MXNET_TPU_EXEC_CACHE', '0')
    net = _mlp()
    ex1 = net.simple_bind(mx.cpu(), data=(8, 20))
    ex2 = net.simple_bind(mx.cpu(), data=(8, 20))
    assert ex1._sig is None and ex2._sig is None
    assert ex1._fwd_eval is not ex2._fwd_eval
    ex1.arg_dict['data'][:] = np.random.rand(8, 20)
    assert ex1.forward()[0].shape == (8, 3)


JAX_KEYS = {'trace_s', 'lower_s', 'backend_compile_s', 'cache_load_s',
            'persistent_requests', 'persistent_hits', 'persistent_misses'}


def test_profiler_counters_exposed():
    st = profiler.exec_cache_stats()
    assert set(st) == {'exec_cache_hits', 'exec_cache_misses',
                       'total_compile_s'} | JAX_KEYS
    assert set(exec_cache.stats()) == {'hits', 'misses',
                                       'total_compile_s'} | JAX_KEYS
    text = profiler.summary(print_out=False)
    assert 'exec_cache_hits=' in text and 'total_compile_s=' in text


# ---------------------------------------------------------------------------
# what jax reports of its compile path, folded into stats()
# ---------------------------------------------------------------------------

def _fresh_jit(scale):
    """A jitted function jax has not seen (the closure is new), whose
    program differs by `scale` from any other in the persistent cache."""
    import jax

    def setup_probe(x):
        return (x * scale).sum()

    return jax.jit(setup_probe)


def test_listeners_fold_a_fresh_jit_and_nothing_of_its_second_call():
    import jax.numpy as jnp
    f, x = _fresh_jit(1.5), jnp.arange(7.0)
    st0 = exec_cache.stats()
    f(x).block_until_ready()
    st1 = exec_cache.stats()
    for key in ('trace_s', 'lower_s', 'backend_compile_s'):
        assert st1[key] > st0[key], key
    for key in ('hits', 'misses', 'total_compile_s'):   # not jax's
        assert st1[key] == st0[key], key
    f(x).block_until_ready()
    assert exec_cache.stats() == st1


def test_a_trace_inside_a_trace_is_counted_once():
    """jax reports the trace of a jit called inside another's trace on
    its own and inside the outer one's duration: trace_s holds the
    outermost only, so it never passes the wall time."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        time.sleep(0.1)         # runs while inner is traced
        return x * 2.0

    def outer(x):
        return inner(x) + inner(x + 1.0)    # traced once: jax caches it

    st0 = exec_cache.stats()
    t0 = time.perf_counter()
    jax.jit(outer)(jnp.arange(3.0)).block_until_ready()
    wall = time.perf_counter() - t0
    traced = exec_cache.stats()['trace_s'] - st0['trace_s']
    assert 0.1 <= traced < 0.2 and traced < wall
    assert exec_cache._TRACING.depth == 0


def test_listeners_are_registered_once():
    from jax._src import monitoring
    assert monitoring.get_event_listeners().count(
        exec_cache._on_jax_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        exec_cache._on_jax_duration) == 1
    assert monitoring.get_scalar_listeners().count(
        exec_cache._on_jax_scalar) == 1


def test_compile_log_names_the_function_and_the_open_span():
    import jax.numpy as jnp
    x = jnp.arange(5.0)
    t0 = time.perf_counter()
    with profiler.scope('t.outer'):
        with profiler.scope('t.compiling'):
            _fresh_jit(2.5)(x).block_until_ready()
    _fresh_jit(3.5)(x).block_until_ready()
    t1 = time.perf_counter()
    inside, outside = [e for e in exec_cache.compile_log()
                       if e[2] == 'jit(setup_probe)'][-2:]
    assert inside[3] == 't.compiling' and outside[3] is None
    for end, seconds, _, _ in (inside, outside):
        assert t0 < end < t1 and 0 < seconds < t1 - t0
    assert inside[0] < outside[0]


def test_compile_log_is_bounded_and_cleared_with_the_stats():
    for i in range(70):
        exec_cache._on_jax_duration(
            '/jax/core/compile/backend_compile_duration', 0.25,
            fun_name='f%d' % i)
    log = exec_cache.compile_log()
    assert len(log) == 64
    assert [e[2] for e in log[-2:]] == ['f68', 'f69']
    assert exec_cache.stats()['backend_compile_s'] >= 70 * 0.25
    exec_cache._on_jax_duration('/jax/some/other_duration', 9.0)
    exec_cache._on_jax_event('/jax/some/other_event')
    assert len(exec_cache.compile_log()) == 64
    exec_cache.clear()
    assert exec_cache.compile_log() == []
    st = exec_cache.stats()
    assert all(st[k] == 0 for k in JAX_KEYS)
    assert isinstance(st['trace_s'], float)
    assert isinstance(st['persistent_hits'], int)


def test_persistent_cache_hit_counts_and_its_read_is_cache_load_s(
        tmp_path):
    """A second compile of the same program, as a second process would
    make it (jax's in-memory caches cleared): the persistent cache
    answers, and its read is both backend_compile_s and cache_load_s."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache as cc
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    cc.reset_cache()
    x = jnp.arange(9.0)
    try:
        st0 = exec_cache.stats()
        _fresh_jit(4.5)(x).block_until_ready()
        st1 = exec_cache.stats()
        assert st1['persistent_requests'] == st0['persistent_requests'] + 1
        assert st1['persistent_misses'] == st0['persistent_misses'] + 1
        assert st1['persistent_hits'] == st0['persistent_hits']
        assert st1['cache_load_s'] == st0['cache_load_s']
        _fresh_jit(4.5)(x).block_until_ready()
        st2 = exec_cache.stats()
        assert st2['persistent_requests'] == st1['persistent_requests'] + 1
        assert st2['persistent_hits'] == st1['persistent_hits'] + 1
        assert st2['persistent_misses'] == st1['persistent_misses']
        load = st2['cache_load_s'] - st1['cache_load_s']
        assert load > 0
        # the backend compile of a hit is the cache's read and a little
        assert st2['backend_compile_s'] - st1['backend_compile_s'] >= load
    finally:
        jax.config.update('jax_compilation_cache_dir', None)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          1.0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
        cc.reset_cache()


def test_persistent_cache_dir_from_jax_env(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: that directory and no other —
    jax reads the variable itself, the code sets no directory."""
    import jax
    from jax._src import compilation_cache as cc
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.setattr(exec_cache, '_PERSISTENT_DIR', None)
    default = exec_cache.default_cache_dir()
    before = os.listdir(default) if os.path.isdir(default) else None
    # jax read its environment at import; stand in for that read
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    try:
        assert exec_cache.setup_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        # jax memoizes cache usability at first compile; this process
        # has long since compiled
        cc.reset_cache()
        ex = _mlp(num_hidden=21).simple_bind(mx.cpu(), data=(2, 6))
        ex.arg_dict['data'][:] = np.random.rand(2, 6)
        ex.forward()
        assert list(tmp_path.iterdir()), \
            'no on-disk compilation cache entry'
        assert before == (os.listdir(default)
                          if os.path.isdir(default) else None)
    finally:
        # turn the disk cache back OFF for the rest of the suite
        # (every later compile would otherwise pay disk writes)
        jax.config.update('jax_compilation_cache_dir', None)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          1.0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
        cc.reset_cache()


def test_persistent_cache_off_on_cpu(monkeypatch):
    import jax
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    monkeypatch.setattr(exec_cache, '_PERSISTENT_DIR', None)
    assert exec_cache.setup_persistent_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_default_cache_dir_ignores_cwd(tmp_path, monkeypatch):
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(mx.__file__)))
    monkeypatch.chdir(tmp_path)
    assert exec_cache.default_cache_dir() == \
        os.path.join(checkout, '.jax_cache')


# ---------------------------------------------------------------------------
# device-resident input prefetch
# ---------------------------------------------------------------------------

def test_prefetch_to_device_matches_source():
    X = np.random.rand(40, 4).astype(np.float32)
    y = (np.random.rand(40) * 3).astype(np.float32)
    raw = mxio.NDArrayIter(X, y, batch_size=8)
    pf = mxio.prefetch_to_device(mxio.NDArrayIter(X, y, batch_size=8),
                                 size=2, device=mx.cpu())
    assert pf.provide_data == raw.provide_data
    assert pf.provide_label == raw.provide_label
    for _epoch in range(2):
        raw.reset()
        pf.reset()
        n = 0
        for braw, bpf in zip(raw, pf):
            np.testing.assert_array_equal(braw.data[0].asnumpy(),
                                          bpf.data[0].asnumpy())
            np.testing.assert_array_equal(braw.label[0].asnumpy(),
                                          bpf.label[0].asnumpy())
            assert braw.pad == bpf.pad
            n += 1
        assert n == 5
    assert pf.batches_served == 10
    assert pf.stall_ms_per_batch() >= 0.0


def test_prefetch_to_device_commits_batches():
    X = np.random.rand(16, 4).astype(np.float32)
    pf = mxio.prefetch_to_device(
        mxio.NDArrayIter(X, None, batch_size=8), size=2, device=mx.cpu())
    dev = mx.cpu().jax_device()
    for batch in pf:
        assert batch.data[0]._data.devices() == {dev}


def test_fit_wraps_train_iter_with_prefetch(monkeypatch):
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[mxio.DataDesc('data', (8, 20))],
             label_shapes=[mxio.DataDesc('softmax_label', (8,))])
    it = mxio.NDArrayIter(np.random.rand(16, 20).astype(np.float32),
                          np.zeros(16, np.float32), batch_size=8)
    wrapped = mod._wrap_train_iter(it)
    assert isinstance(wrapped, mxio.PrefetchToDeviceIter)
    # idempotent: an already-wrapped iterator is not double-wrapped
    assert mod._wrap_train_iter(wrapped) is wrapped
    monkeypatch.setenv('MXNET_TPU_PREFETCH', '0')
    assert mod._wrap_train_iter(it) is it


def test_fit_end_to_end_with_prefetch():
    X = np.random.rand(32, 10).astype(np.float32)
    y = (np.random.rand(32) * 3).astype(np.float32)
    mod = mx.mod.Module(_mlp(num_hidden=8), context=mx.cpu())
    it = mxio.NDArrayIter(X, y, batch_size=8, label_name='softmax_label')
    mod.fit(it, num_epoch=2, optimizer_params={'learning_rate': 0.1})
    args, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in args.values())


# ---------------------------------------------------------------------------
# PrefetchingIter worker-thread lifecycle
# ---------------------------------------------------------------------------

def _drain(it):
    n = 0
    while it.iter_next():
        n += 1
    return n


def test_prefetching_iter_joins_threads_on_close():
    X = np.random.rand(24, 4).astype(np.float32)
    y = np.zeros(24, np.float32)
    pf = mxio.PrefetchingIter(mxio.NDArrayIter(X, y, batch_size=8))
    workers = list(pf.prefetch_threads)
    assert workers and all(w.daemon for w in workers)
    assert _drain(pf) == 3
    pf.reset()
    assert _drain(pf) == 3          # second epoch
    pf.close()
    assert all(not w.is_alive() for w in workers)
    assert pf.prefetch_threads == []
    pf.close()                      # idempotent


def test_prefetching_iter_joins_threads_on_del():
    X = np.random.rand(16, 4).astype(np.float32)
    pf = mxio.PrefetchingIter(
        mxio.NDArrayIter(X, np.zeros(16, np.float32), batch_size=8))
    workers = list(pf.prefetch_threads)
    _drain(pf)
    del pf
    gc.collect()
    for w in workers:
        w.join(timeout=5)
    assert all(not w.is_alive() for w in workers)
    assert all(w not in threading.enumerate() for w in workers)
