"""Module API tests — the end-to-end slice of SURVEY.md §7 step 5
(model: reference tests/python/unittest/test_module.py +
tests/python/train/test_mlp.py convergence runs)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, nd


def _make_blobs(n=400, dim=10, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, dim) * 3
    X = np.zeros((n, dim), dtype=np.float32)
    y = np.zeros((n,), dtype=np.float32)
    for i in range(n):
        c = i % classes
        X[i] = centers[c] + rng.randn(dim) * 0.5
        y[i] = c
    return X, y


def _mlp_sym(classes=3):
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, name='fc1', num_hidden=32)
    act = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(act, name='fc2', num_hidden=classes)
    return sym.SoftmaxOutput(fc2, name='softmax')


def test_module_fit_converges():
    X, y = _make_blobs()
    train = mx.io.NDArrayIter(X, y, batch_size=40, shuffle=True)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, num_epoch=10,
            optimizer_params={'learning_rate': 0.5})
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=40), 'acc')
    assert score[0][1] > 0.95, 'MLP failed to fit blobs: %s' % score


def test_module_multi_device_data_parallel():
    """Multi-context DP via mesh sharding (the reference tests this with
    cpu(0)/cpu(1), test_multi_device_exec.py)."""
    X, y = _make_blobs()
    train = mx.io.NDArrayIter(X, y, batch_size=40, shuffle=True)
    mod = mx.mod.Module(_mlp_sym(), context=[mx.cpu(i) for i in range(4)])
    mod.fit(train, num_epoch=8, optimizer_params={'learning_rate': 0.5})
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=40), 'acc')
    assert score[0][1] > 0.95, 'multi-device MLP failed: %s' % score


def test_module_predict_and_pad():
    X, y = _make_blobs(n=110)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=40)  # 110 -> pad 10 in last
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=False)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (110, 3)


def test_module_checkpoint_roundtrip(tmp_path):
    X, y = _make_blobs()
    train = mx.io.NDArrayIter(X, y, batch_size=40)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, num_epoch=2, optimizer_params={'learning_rate': 0.5})
    prefix = str(tmp_path / 'mlp')
    mod.save_checkpoint(prefix, 2, save_optimizer_states=True)

    mod2 = mx.mod.Module.load(prefix, 2, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=40)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        np.testing.assert_allclose(a1[k].asnumpy(), a2[k].asnumpy(),
                                   rtol=1e-5)
    # predictions identical
    p1 = mod.predict(mx.io.NDArrayIter(X, y, batch_size=40)).asnumpy()
    p2 = mod2.predict(mx.io.NDArrayIter(X, y, batch_size=40)).asnumpy()
    np.testing.assert_allclose(p1, p2, rtol=1e-5)


def test_module_update_on_kvstore_matches_local():
    """push/pull-on-store and local-updater paths produce identical
    updates (the reference asserts exact sync-SGD arithmetic in
    tests/nightly/dist_sync_kvstore.py)."""
    X, y = _make_blobs(n=80)

    def run(kv):
        mx.random.seed(7)
        train = mx.io.NDArrayIter(X, y, batch_size=40)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
        mod.fit(train, num_epoch=2, kvstore=kv,
                optimizer_params={'learning_rate': 0.1},
                initializer=mx.init.Xavier(),
                force_init=True)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    p_none = run(None)
    p_local = run('local')  # single device -> kv is None internally
    p_device = run('device')
    for k in p_none:
        np.testing.assert_allclose(p_none[k], p_local[k], rtol=1e-5)
        np.testing.assert_allclose(p_none[k], p_device[k], rtol=1e-5)


def test_lenet_trains():
    """Conv net end-to-end (reference tests/python/train/test_conv.py
    shape, synthetic data instead of MNIST download)."""
    rng = np.random.RandomState(0)
    n = 160
    X = np.zeros((n, 1, 12, 12), dtype=np.float32)
    y = np.zeros((n,), dtype=np.float32)
    for i in range(n):
        c = i % 2
        X[i, 0] = rng.rand(12, 12) * 0.2
        if c:
            X[i, 0, 3:9, 3:9] += 1.0  # bright square for class 1
        y[i] = c
    data = sym.Variable('data')
    c1 = sym.Convolution(data, name='c1', kernel=(3, 3), num_filter=8)
    a1 = sym.Activation(c1, act_type='relu')
    p1 = sym.Pooling(a1, kernel=(2, 2), stride=(2, 2), pool_type='max')
    fl = sym.Flatten(p1)
    fc = sym.FullyConnected(fl, name='fc', num_hidden=2)
    net = sym.SoftmaxOutput(fc, name='softmax')
    train = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, num_epoch=5, optimizer_params={'learning_rate': 0.1})
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=16), 'acc')
    assert score[0][1] > 0.95, 'LeNet-style net failed: %s' % score


def test_bucketing_module():
    """Variable-length training via bucketing (reference
    test_bucketing.py pattern, tiny scale)."""
    def sym_gen(seq_len):
        data = sym.Variable('data')
        label = sym.Variable('softmax_label')
        fc = sym.FullyConnected(data, name='fc_shared', num_hidden=8)
        act = sym.Activation(fc, act_type='relu')
        out = sym.FullyConnected(act, name='out_shared', num_hidden=2)
        net = sym.SoftmaxOutput(out, label=label, name='softmax')
        return net, ('data',), ('softmax_label',)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
    rng = np.random.RandomState(0)

    def make_batch(seq_len, batch=8):
        X = rng.rand(batch, seq_len).astype(np.float32)
        y = (X.sum(axis=1) > seq_len / 2).astype(np.float32)
        return mx.io.DataBatch(
            data=[nd.array(X)], label=[nd.array(y)], bucket_key=seq_len,
            provide_data=[mx.io.DataDesc('data', (batch, seq_len))],
            provide_label=[mx.io.DataDesc('softmax_label', (batch,))])

    mod.bind(data_shapes=[mx.io.DataDesc('data', (8, 8))],
             label_shapes=[mx.io.DataDesc('softmax_label', (8,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer_params={'learning_rate': 0.5})
    for i in range(300):
        batch = make_batch(8)
        mod.forward_backward(batch)
        mod.update()
    metric = mx.metric.create('acc')
    for _ in range(10):
        batch = make_batch(8)
        mod.forward(batch, is_train=False)
        mod.update_metric(metric, batch.label)
    assert metric.get()[1] > 0.65, metric.get()


def test_optimizers_step():
    """Each optimizer makes a step without error and reduces a quadratic."""
    for name in ['sgd', 'adam', 'rmsprop', 'adagrad', 'adadelta', 'nag',
                 'adamax', 'nadam', 'signum', 'ftrl']:
        opt = mx.optimizer.create(name, rescale_grad=1.0)
        w = nd.array([5.0])
        state = opt.create_state(0, w)
        for i in range(50):
            g = 2 * w  # d/dw w^2
            opt.update(0, w, g, state)
        assert abs(w.asscalar()) < 5.0, '%s failed to descend' % name


def test_lr_scheduler():
    sched = mx.lr_scheduler.FactorScheduler(step=10, factor=0.5)
    sched.base_lr = 1.0
    assert sched(5) == 1.0
    assert sched(11) == 0.5
    msched = mx.lr_scheduler.MultiFactorScheduler(step=[5, 10], factor=0.1)
    msched.base_lr = 1.0
    assert abs(msched(6) - 0.1) < 1e-9
    assert abs(msched(11) - 0.01) < 1e-9


def test_metrics():
    acc = mx.metric.create('acc')
    acc.update([nd.array([1, 0])], [nd.array([[0.3, 0.7], [0.6, 0.4]])])
    assert acc.get()[1] == 1.0
    mse = mx.metric.create('mse')
    mse.update([nd.array([1.0, 2.0])], [nd.array([[1.5], [2.5]])])
    assert abs(mse.get()[1] - 0.25) < 1e-6
    comp = mx.metric.create(['acc', 'mse'])
    assert isinstance(comp, mx.metric.CompositeEvalMetric)


def _bulk_mod(ctxs, ap=None, ax=None, batch=16, kvstore='local'):
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, name='fc1', num_hidden=16)
    act = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(act, name='fc2', num_hidden=4)
    net = sym.SoftmaxOutput(fc2, name='softmax')
    mod = mx.mod.Module(net, context=ctxs)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (batch, 8))],
             label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
    if ap is None:
        mod.init_params(initializer=mx.init.Xavier())
    else:
        mod.init_params(initializer=None, arg_params=ap, aux_params=ax)
    mod.init_optimizer(kvstore=kvstore, optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': 0.9})
    return mod


@pytest.mark.parametrize('n_ctx,kvstore', [(1, 'local'), (4, 'local'),
                                           (4, None), (8, 'local'),
                                           (8, None)])
def test_bulk_step_matches_per_step_loop(n_ctx, kvstore):
    """Module.bulk_step (K steps in one on-device lax.scan dispatch —
    the TPU analog of the reference's bulk-exec segments,
    graph_executor.cc:1135) must produce the same parameters as the
    plain forward_backward+update loop.  (4, 'local') exercises the
    kvstore fallback loop; (4, None) the fused mesh-sharded scan path
    with the stacked batch sharded along dim 1."""
    rng = np.random.RandomState(0)
    batches = [mx.io.DataBatch(
        data=[nd.array(rng.rand(16, 8).astype(np.float32))],
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
        for _ in range(5)]
    seed_mod = _bulk_mod([mx.cpu(0)])
    ap, ax = seed_mod.get_params()
    ap = {k: v.copy() for k, v in ap.items()}
    ax = {k: v.copy() for k, v in ax.items()}
    ctxs = [mx.cpu(i) for i in range(n_ctx)]
    a = _bulk_mod(ctxs, ap, ax, kvstore=kvstore)
    b = _bulk_mod(ctxs, ap, ax, kvstore=kvstore)
    c = _bulk_mod(ctxs, ap, ax, kvstore=kvstore)
    d = _bulk_mod(ctxs, ap, ax, kvstore=kvstore)
    if kvstore is None:
        assert b._fused_updater is not None, \
            'kvstore=None must enable the fused whole-step path'
    for bt in batches:
        a.forward_backward(bt)
        a.update()
    b.bulk_step(batches=batches)
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5)
    # repeat mode: K steps on one batch == per-step loop on that batch
    c.bulk_step(batch=batches[0], repeat=3)
    for _ in range(3):
        d.forward_backward(batches[0])
        d.update()
    pc, _ = c.get_params()
    pd, _ = d.get_params()
    for k in pc:
        np.testing.assert_allclose(pc[k].asnumpy(), pd[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('given', [False, True])
def test_module_weights_are_committed_and_the_step_compiles_once(given):
    """set_params commits every weight to the executor's device, as
    the compiled step's donated outputs are: the second dispatch sees
    the first's jit signature and nothing compiles again; the module's
    own copies outlive the step's donation.  `given`: weights handed
    in, or made by the initializer."""
    import jax
    rng = np.random.RandomState(2)
    batches = [mx.io.DataBatch(
        data=[nd.array(rng.rand(16, 8).astype(np.float32))],
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
        for _ in range(2)]
    ap = ax = None
    if given:
        ap, ax = _bulk_mod([mx.cpu(0)], kvstore=None).get_params()
    mod = _bulk_mod([mx.cpu(0)], ap, ax, kvstore=None)
    ex = mod._exec_group.executor
    for name in ex._diff_names:
        assert ex.arg_dict[name]._data._committed, name
    before = {n: mod._arg_params[n].asnumpy().copy()
              for n in ex._diff_names}
    compiles = []

    def on_duration(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        mod.bulk_step(batches=batches)
        first = len(compiles)
        mod.bulk_step(batches=batches)
        jax.block_until_ready(mod.get_outputs()[0]._data)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert len(compiles) == first    # the first may be served by exec_cache
    for n, w in before.items():
        np.testing.assert_array_equal(mod._arg_params[n].asnumpy(), w)


@pytest.mark.parametrize('k', [1, 4])
def test_released_module_frees_its_state(k):
    """A module whose executor group and updater were dropped (as the
    benchmark's release_module drops them) keeps neither alive through
    the table of its compiled steps: the single step and K = 4."""
    import gc
    import weakref
    rng = np.random.RandomState(3)
    batches = [mx.io.DataBatch(
        data=[nd.array(rng.rand(16, 8).astype(np.float32))],
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
        for _ in range(k)]
    mod = _bulk_mod([mx.cpu(0)], kvstore=None)
    if k == 1:
        mod.forward_backward(batches[0])
        mod.update()
    else:
        mod.bulk_step(batches=batches)
    assert mod._step_program('single' if k == 1 else 'stacked', k)
    alive = [weakref.ref(mod._exec_group.executor),
             weakref.ref(mod._fused_updater)]
    mod._exec_group = None
    mod._fused_updater = None
    gc.collect()
    assert [ref() for ref in alive] == [None, None]


def test_bulk_step_scan_dtype_storage():
    """bulk_step(scan_dtype=...) stores the stacked data batches in a
    narrower dtype and the fused step casts back before the graph
    (docs/PERF.md round 5) — for inputs the model itself quantizes on
    entry the result must match the default-storage path exactly, and
    labels must keep their bound dtype."""
    rng = np.random.RandomState(1)
    # quantize the data to bf16-representable values so bf16 storage is
    # lossless for this check regardless of the model's own entry cast
    raw = rng.rand(16, 8).astype(np.float32)
    import jax.numpy as jnp
    raw = np.asarray(jnp.asarray(raw, jnp.bfloat16).astype(jnp.float32))
    batches = [mx.io.DataBatch(
        data=[nd.array(raw * (2.0 ** i))],  # ×2^i stays bf16-exact
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
        for i in range(3)]
    seed_mod = _bulk_mod([mx.cpu(0)], kvstore=None)
    ap, ax = seed_mod.get_params()
    ap = {k: v.copy() for k, v in ap.items()}
    ax = {k: v.copy() for k, v in ax.items()}
    a = _bulk_mod([mx.cpu(0)], ap, ax, kvstore=None)
    b = _bulk_mod([mx.cpu(0)], ap, ax, kvstore=None)
    a.bulk_step(batches=batches)
    b.bulk_step(batches=batches, scan_dtype='bfloat16')
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=k)


def test_fused_step_with_device_kvstore_single_dispatch():
    """A single-process kvstore ('local'/'device') must not forfeit
    whole-step fusion: the grad all-reduce is already the in-step psum
    of the one SPMD program, so fit() should issue exactly ONE fused
    dispatch per batch instead of per-key eager push/pull (reference
    runs the eager path, model.py:106)."""
    X, y = _make_blobs(n=64, dim=8, classes=4, seed=7)
    train = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                              label_name='softmax_label')
    ctxs = [mx.cpu(i) for i in range(8)]
    mod = mx.mod.Module(_mlp_sym(classes=4), context=ctxs)
    mod.fit(train, num_epoch=2, kvstore='device',
            optimizer_params={'learning_rate': 0.1})
    assert mod._fused_updater is not None, \
        "kvstore='device' must keep the fused whole-step path"
    assert not mod._update_on_kvstore
    ex = mod._exec_group.executor
    # 2 epochs x 4 batches, one donated dispatch each
    assert ex.fused_dispatches == 8, ex.fused_dispatches


def test_fused_kvstore_matches_no_kvstore():
    """kvstore='local' (fused in-step update) must produce identical
    parameters to kvstore=None — the store is a facade, not different
    math."""
    rng = np.random.RandomState(11)
    batches = [mx.io.DataBatch(
        data=[nd.array(rng.rand(16, 8).astype(np.float32))],
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
        for _ in range(4)]
    seed_mod = _bulk_mod([mx.cpu(0)])
    ap, ax = seed_mod.get_params()
    ap = {k: v.copy() for k, v in ap.items()}
    ax = {k: v.copy() for k, v in ax.items()}
    ctxs = [mx.cpu(i) for i in range(4)]
    a = _bulk_mod(ctxs, ap, ax, kvstore='local')
    b = _bulk_mod(ctxs, ap, ax, kvstore=None)
    assert a._fused_updater is not None
    for bt in batches:
        a.forward_backward(bt)
        a.update()
        b.forward_backward(bt)
        b.update()
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=1e-5, atol=1e-5)


def test_nhwc_layout_pass_matches_nchw():
    """The executor's NHWC layout pass (MXNET_TPU_LAYOUT_OPT=1) must be
    numerically equivalent to semantic NCHW execution across conv/BN/
    relu/pooling/residual-add/global-pool/FC — same outputs, params,
    and BN moving stats after training steps."""
    import os

    seed_params = {}
    prior = os.environ.get('MXNET_TPU_LAYOUT_OPT')

    def run(layout_env):
        os.environ['MXNET_TPU_LAYOUT_OPT'] = layout_env
        try:
            rng = np.random.RandomState(0)
            data = sym.Variable('data')
            c1 = sym.Convolution(data, name='c1', num_filter=8,
                                 kernel=(3, 3), pad=(1, 1))
            b1 = sym.BatchNorm(c1, name='b1', fix_gamma=False)
            a1 = sym.Activation(b1, act_type='relu')
            p1 = sym.Pooling(a1, kernel=(2, 2), stride=(2, 2),
                             pool_type='max')
            c2 = sym.Convolution(p1, name='c2', num_filter=8,
                                 kernel=(3, 3), pad=(1, 1))
            res = c2 + sym.Convolution(p1, name='sc', num_filter=8,
                                       kernel=(1, 1))
            b2 = sym.BatchNorm(res, name='b2', fix_gamma=False)
            gp = sym.Pooling(b2, global_pool=True, pool_type='avg',
                             kernel=(1, 1))
            fc = sym.FullyConnected(sym.Flatten(gp), num_hidden=4,
                                    name='fc')
            net = sym.SoftmaxOutput(fc, name='softmax')
            mod = mx.mod.Module(net, context=[mx.cpu(0)])
            mod.bind(data_shapes=[mx.io.DataDesc('data', (8, 3, 16, 16))],
                     label_shapes=[mx.io.DataDesc('softmax_label', (8,))])
            if seed_params:
                mod.init_params(initializer=None,
                                arg_params=seed_params['arg'],
                                aux_params=seed_params['aux'])
            else:
                mod.init_params(initializer=mx.init.Xavier())
                ap, ax = mod.get_params()
                seed_params['arg'] = {k: v.copy() for k, v in ap.items()}
                seed_params['aux'] = {k: v.copy() for k, v in ax.items()}
            mod.init_optimizer(optimizer_params={'learning_rate': 0.1})
            X = mx.nd.array(rng.rand(8, 3, 16, 16).astype(np.float32))
            y = mx.nd.array((rng.rand(8) * 4).astype(np.float32))
            bt = mx.io.DataBatch(data=[X], label=[y])
            for _ in range(3):
                mod.forward_backward(bt)
                mod.update()
            mod.forward(bt, is_train=False)
            out = mod.get_outputs()[0].asnumpy()
            params, aux = mod.get_params()
            return (out, {k: v.asnumpy() for k, v in params.items()},
                    {k: v.asnumpy() for k, v in aux.items()})
        finally:
            if prior is None:
                os.environ.pop('MXNET_TPU_LAYOUT_OPT', None)
            else:
                os.environ['MXNET_TPU_LAYOUT_OPT'] = prior

    o0, p0, a0 = run('0')
    o1, p1, a1 = run('1')
    np.testing.assert_allclose(o0, o1, rtol=2e-4, atol=2e-5)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)
    for k in a0:
        np.testing.assert_allclose(a0[k], a1[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_fused_step_deferred_materialization():
    """forward_backward defers when the whole step can fuse; accessing
    outputs before update() must still yield correct results, and the
    fused path must match the unfused two-dispatch path."""
    rng = np.random.RandomState(1)
    bt = mx.io.DataBatch(
        data=[nd.array(rng.rand(16, 8).astype(np.float32))],
        label=[nd.array((rng.rand(16) * 4).astype(np.float32))])
    seed_mod = _bulk_mod([mx.cpu(0)])
    ap, ax = seed_mod.get_params()
    ap = {k: v.copy() for k, v in ap.items()}
    ax = {k: v.copy() for k, v in ax.items()}
    a = _bulk_mod([mx.cpu(0)], ap, ax)
    b = _bulk_mod([mx.cpu(0)], ap, ax)
    # a: read outputs between fwd_bwd and update (materialization path)
    a.forward_backward(bt)
    out_a = a.get_outputs()[0].asnumpy()
    a.update()
    # b: straight fused path
    b.forward_backward(bt)
    b.update()
    out_b = b.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out_a, out_b, rtol=1e-5, atol=1e-6)
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5)
