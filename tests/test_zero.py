"""ZeRO-1 sharded optimizer update (parallel/zero.py) on the 8-device
virtual CPU mesh: numeric parity with the replicated fused step,
bucket-layout mechanics, cache-key separation (no program aliasing),
per-device state-memory accounting, checkpoint portability, and the
KVStore multi-value push merge fix."""
import os
import pickle

import numpy as np
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import exec_cache, optimizer as opt_mod, profiler
from mxnet_tpu import sym as S
from mxnet_tpu.parallel import zero as zero_mod

N_DEV = 8
BATCH = 16
FEAT = 12


def _net(dtype='float32'):
    data = S.Variable('data')
    x = data if dtype == 'float32' else S.Cast(data, dtype=dtype)
    fc1 = S.FullyConnected(x, name='fc1', num_hidden=24)
    act = S.Activation(fc1, act_type='relu')
    fc2 = S.FullyConnected(act, name='fc2', num_hidden=5)
    if dtype != 'float32':
        fc2 = S.Cast(fc2, dtype='float32')
    return S.SoftmaxOutput(fc2, name='softmax')


def _params(net, seed=3):
    rs = np.random.RandomState(seed)
    shapes, _, _ = net.infer_shape(data=(BATCH, FEAT))
    out = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name in ('data', 'softmax_label'):
            continue
        out[name] = mx.nd.array(
            (rs.rand(*shape).astype(np.float32) - 0.5) * 0.2)
    return out


def _batches(k=4, seed=5):
    rs = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(BATCH, FEAT).astype(np.float32))],
        label=[mx.nd.array((rs.rand(BATCH) * 5).astype(np.float32))])
        for _ in range(k)]


def _train(zero, dtype='float32', steps=4, opt_kwargs=None,
           n_ctx=N_DEV, bulk=False):
    net = _net(dtype)
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(n_ctx)])
    mod.bind(data_shapes=[mx.io.DataDesc('data', (BATCH, FEAT))],
             label_shapes=[mx.io.DataDesc('softmax_label', (BATCH,))])
    mod.init_params(initializer=None, arg_params=_params(net),
                    aux_params={})
    kw = {'learning_rate': 0.1, 'momentum': 0.9, 'wd': 1e-3,
          'multi_precision': dtype != 'float32'}
    kw.update(opt_kwargs or {})
    mod.init_optimizer(optimizer='sgd', optimizer_params=kw, zero=zero)
    assert mod._fused_updater is not None
    if zero is not None:
        assert mod._fused_updater.zero == zero
    batches = _batches(steps)
    if bulk:
        mod.bulk_step(batches=batches)
    else:
        for b in batches:
            mod.forward_backward(b)
            mod.update()
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy().astype(np.float32)
                 for k, v in params.items()}


def _assert_params_close(pa, pb, rtol, atol):
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=rtol, atol=atol,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# numeric parity: sharded step == replicated step
# ---------------------------------------------------------------------------

def test_zero_parity_sgd_momentum_wd():
    _, pr = _train(zero=0)
    _, pz = _train(zero=1)
    _assert_params_close(pr, pz, rtol=1e-5, atol=1e-6)


def test_zero_parity_clip_gradient():
    kw = {'clip_gradient': 0.05}
    _, pr = _train(zero=0, opt_kwargs=kw)
    _, pz = _train(zero=1, opt_kwargs=kw)
    _assert_params_close(pr, pz, rtol=1e-5, atol=1e-6)


def test_zero_parity_bf16_fp32_masters():
    """bf16 weights with fp32 masters: the masters live sharded under
    ZeRO and the all-gather runs in bf16; parity within bf16 noise."""
    _, pr = _train(zero=0, dtype='bfloat16')
    _, pz = _train(zero=1, dtype='bfloat16')
    _assert_params_close(pr, pz, rtol=1e-2, atol=1e-2)


def test_zero_parity_bulk_multistep():
    """The K-step lax.scan fused dispatch with the sharded update."""
    _, pr = _train(zero=0, bulk=True)
    _, pz = _train(zero=1, bulk=True)
    _assert_params_close(pr, pz, rtol=1e-5, atol=1e-6)


def test_zero_parity_tiny_buckets(monkeypatch):
    """Force multi-bucket layouts (bucket target smaller than any one
    param) — parity must survive arbitrary bucket boundaries."""
    monkeypatch.setenv('MXNET_TPU_ZERO_BUCKET_MB', '0.0001')
    _, pz = _train(zero=1)
    monkeypatch.delenv('MXNET_TPU_ZERO_BUCKET_MB')
    _, pr = _train(zero=0)
    _assert_params_close(pr, pz, rtol=1e-5, atol=1e-6)


def test_zero_single_device_runs():
    """dp=1 (no mesh): the bucketed path degenerates to no collectives
    but must still match the replicated math exactly."""
    _, pr = _train(zero=0, n_ctx=1)
    _, pz = _train(zero=1, n_ctx=1)
    _assert_params_close(pr, pz, rtol=1e-6, atol=1e-7)


def test_zero_env_knob(monkeypatch):
    """MXNET_TPU_ZERO=1 turns the mode on without API changes."""
    monkeypatch.setenv('MXNET_TPU_ZERO', '1')
    mod, _ = _train(zero=None, steps=1)
    assert mod._fused_updater.zero == 1


# ---------------------------------------------------------------------------
# bucket layout mechanics
# ---------------------------------------------------------------------------

def test_bucket_layout_padding_and_grouping():
    layout = zero_mod.ZeroBucketLayout(
        shapes=[(3, 5), (7,), (2, 2)],
        dtypes=[np.float32, np.float32, np.float32],
        mp_flags=[False, False, False], dp=8,
        max_bytes=1 << 30)
    assert len(layout.buckets) == 1
    b = layout.buckets[0]
    assert b.size == 15 + 7 + 4
    assert b.padded % 8 == 0 and b.padded >= b.size
    # mp params bucket separately from non-mp ones
    layout2 = zero_mod.ZeroBucketLayout(
        shapes=[(4,), (4,)], dtypes=[jnp.bfloat16, np.float32],
        mp_flags=[True, False], dp=2, max_bytes=1 << 30)
    assert len(layout2.buckets) == 2
    assert layout2.buckets[0].mp and not layout2.buckets[1].mp
    assert layout2.buckets[0].acc_dtype == np.dtype(np.float32)


def test_bucket_pack_unpack_roundtrip():
    layout = zero_mod.ZeroBucketLayout(
        shapes=[(2, 3), (5,)], dtypes=[np.float32, np.float32],
        mp_flags=[False, False], dp=4, max_bytes=1 << 30)
    b = layout.buckets[0]
    vals = [jnp.arange(6.0).reshape(2, 3), jnp.arange(5.0) + 10]
    flat = layout.pack(b, vals)
    assert flat.shape == (b.padded,)
    back = layout.unpack(b, flat)
    for v, r in zip(vals, back):
        np.testing.assert_array_equal(np.asarray(v), np.asarray(r))


def test_bucket_split_over_target():
    """Greedy fill: params overflow into new buckets at the byte
    target instead of growing one giant buffer."""
    layout = zero_mod.ZeroBucketLayout(
        shapes=[(100,)] * 5, dtypes=[np.float32] * 5,
        mp_flags=[False] * 5, dp=2, max_bytes=400)
    assert len(layout.buckets) == 5


def test_state_and_comm_accounting():
    layout = zero_mod.ZeroBucketLayout(
        shapes=[(64,)], dtypes=[jnp.bfloat16], mp_flags=[True], dp=8,
        max_bytes=1 << 30)
    # per device: 8 fp32 momentum + 8 fp32 master elements
    assert layout.state_bytes_per_device() == 8 * 4 + 8 * 4
    rs, ag = layout.comm_bytes_per_step()
    assert rs == 64 * 4          # grads reduce-scatter in fp32 (acc)
    assert ag == 64 * 2          # params all-gather in bf16
    # dp=1 emits no collectives
    l1 = zero_mod.ZeroBucketLayout([(64,)], [np.float32], [False], 1)
    assert l1.comm_bytes_per_step() == (0, 0)


def test_zero_state_bytes_drop_8x():
    """Acceptance: per-device optimizer-state bytes drop ~8x on the
    8-device mesh."""
    mr, _ = _train(zero=0, steps=1)
    mz, _ = _train(zero=1, steps=1)
    rep = mr._fused_updater.state_bytes_per_device()
    shard = mz._fused_updater.state_bytes_per_device()
    assert rep > 0 and shard > 0
    assert rep / shard >= 6.0, (rep, shard)
    # profiler counter mirrors the updater's accounting
    assert profiler.comm_stats()['optimizer_state_bytes_per_device'] \
        in (rep, shard)


def test_zero_states_actually_sharded():
    """The momenta/masters must be committed dp-sharded (that IS the
    memory win), while the weights stay replicated."""
    mod, _ = _train(zero=1, dtype='bfloat16', steps=1)
    fu = mod._fused_updater
    for buf in fu._zero_moms + [m for m in fu._zero_masters
                                if m is not None]:
        assert not buf.sharding.is_fully_replicated
    ex = mod._exec_group.executor
    for name in fu.param_names:
        assert ex.arg_dict[name]._data.sharding.is_fully_replicated


def test_zero_comm_counters_accumulate():
    profiler.clear()
    mod, _ = _train(zero=1, steps=3)
    st = profiler.comm_stats()
    rs, ag = mod._fused_updater.comm_bytes_per_step()
    assert rs > 0 and ag > 0
    assert st['bytes_reduce_scattered'] == 3 * rs
    assert st['bytes_all_gathered'] == 3 * ag
    # summary() surfaces them
    assert 'bytes_reduce_scattered' in profiler.summary(print_out=False)


# ---------------------------------------------------------------------------
# compiled-program cache: no aliasing between sharded and replicated
# ---------------------------------------------------------------------------

def test_zero_and_replicated_programs_never_alias():
    exec_cache.clear()
    _train(zero=0, steps=1)
    _train(zero=1, steps=1)
    with exec_cache._LOCK:
        multistep_keys = [k for k in exec_cache._CACHE
                          if isinstance(k, tuple) and len(k) > 1
                          and k[1] == 'multistep']
    assert len(multistep_keys) == 2, multistep_keys
    # the step_key component (FusedSGD.cache_key) differs by zero cfg
    assert multistep_keys[0][-1] != multistep_keys[1][-1]


def test_fused_sgd_cache_key_carries_zero_and_layout():
    o1 = opt_mod.create('sgd', learning_rate=0.1, momentum=0.9)
    o2 = opt_mod.create('sgd', learning_rate=0.1, momentum=0.9)
    fr = opt_mod.FusedSGD(o1, ['w'])
    fz = opt_mod.FusedSGD(o2, ['w'], zero=1, mesh=None)
    assert fr.cache_key() != fz.cache_key()
    # layout joins the key once built
    w = mx.nd.array(np.zeros((4, 4), np.float32))
    fz.host_prep_steps([w], 1)
    k1 = fz.cache_key()
    assert any('zero' in str(part) for part in k1)
    o3 = opt_mod.create('sgd', learning_rate=0.1, momentum=0.9)
    fz2 = opt_mod.FusedSGD(o3, ['w'], zero=1, mesh=None)
    fz2.host_prep_steps([mx.nd.array(np.zeros((8, 4), np.float32))], 1)
    assert fz2.cache_key() != k1           # different bucket layout


# ---------------------------------------------------------------------------
# checkpoint portability across modes
# ---------------------------------------------------------------------------

def test_zero_checkpoint_roundtrip_cross_mode():
    """A sharded run's optimizer states restore into a replicated
    updater (and back): the wire format stays per-param."""
    mz, _ = _train(zero=1, steps=2)
    blob = mz._fused_updater.get_states()
    states, counts, masters = pickle.loads(blob)
    assert set(states) == set(mz._fused_updater.param_names)
    # momenta are real (training moved them off zero)
    assert any(np.abs(v).sum() > 0 for v in states.values())

    # restore into a replicated updater: per-param arrays, full shapes
    o = opt_mod.create('sgd', learning_rate=0.1, momentum=0.9)
    fr = opt_mod.FusedSGD(o, list(states))
    fr.set_states(blob)
    for n, v in states.items():
        np.testing.assert_allclose(np.asarray(fr.states[n]).ravel(),
                                   np.asarray(v).ravel())

    # and back into a fresh sharded updater via Module API
    net = _net()
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(N_DEV)])
    mod.bind(data_shapes=[mx.io.DataDesc('data', (BATCH, FEAT))],
             label_shapes=[mx.io.DataDesc('softmax_label', (BATCH,))])
    mod.init_params(initializer=None, arg_params=_params(net),
                    aux_params={})
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': 0.9, 'wd': 1e-3},
                       zero=1)
    mod._fused_updater.set_states(blob)
    b = _batches(1, seed=99)[0]
    mod.forward_backward(b)
    mod.update()       # host_prep re-buckets the staged states
    blob2 = mod._fused_updater.get_states()
    states2, _, _ = pickle.loads(blob2)
    assert set(states2) == set(states)


def test_zero_get_states_before_first_step_preserves_staged():
    """Regression: set_states then get_states WITHOUT an intervening
    step must round-trip the restored values, not write an empty
    (state-resetting) checkpoint."""
    mz, _ = _train(zero=1, steps=2)
    blob = mz._fused_updater.get_states()
    states, _, _ = pickle.loads(blob)
    net = _net()
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(N_DEV)])
    mod.bind(data_shapes=[mx.io.DataDesc('data', (BATCH, FEAT))],
             label_shapes=[mx.io.DataDesc('softmax_label', (BATCH,))])
    mod.init_params(initializer=None, arg_params=_params(net),
                    aux_params={})
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': 0.9},
                       zero=1)
    mod._fused_updater.set_states(blob)
    states2, _, _ = pickle.loads(mod._fused_updater.get_states())
    assert set(states2) == set(states)
    for n in states:
        np.testing.assert_allclose(np.asarray(states2[n]),
                                   np.asarray(states[n]))


def test_zero_bucket_relayout_mid_run(monkeypatch):
    """Regression: changing the bucket layout between steps (env knob
    re-read per step) must rebuild the fused step, not run the stale
    program against new-shape bucket states."""
    batches = _batches(4)
    net = _net()
    mods = {}
    for zero in (0, 1):
        mod = mx.mod.Module(net,
                            context=[mx.cpu(i) for i in range(N_DEV)])
        mod.bind(data_shapes=[mx.io.DataDesc('data', (BATCH, FEAT))],
                 label_shapes=[mx.io.DataDesc('softmax_label',
                                              (BATCH,))])
        mod.init_params(initializer=None, arg_params=_params(net),
                        aux_params={})
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.1,
                                             'momentum': 0.9,
                                             'wd': 1e-3}, zero=zero)
        for i, b in enumerate(batches):
            if zero and i == 2:   # shrink buckets mid-run
                monkeypatch.setenv('MXNET_TPU_ZERO_BUCKET_MB', '0.0001')
            mod.forward_backward(b)
            mod.update()
        monkeypatch.delenv('MXNET_TPU_ZERO_BUCKET_MB', raising=False)
        mods[zero] = mod
    pr, _ = mods[0].get_params()
    pz, _ = mods[1].get_params()
    _assert_params_close({k: v.asnumpy() for k, v in pr.items()},
                         {k: v.asnumpy() for k, v in pz.items()},
                         rtol=1e-5, atol=1e-6)


def test_zero_stage_validation():
    assert zero_mod.zero_stage(None) == 0
    assert zero_mod.zero_stage(1) == 1
    with pytest.raises(ValueError):
        zero_mod.zero_stage(2)


def test_kvstore_zero_stage_facade(monkeypatch):
    kv = mx.kvstore.create('local', zero=1)
    assert kv.zero_stage == 1
    monkeypatch.setenv('MXNET_TPU_ZERO', '1')
    assert mx.kvstore.create('local').zero_stage == 1
    monkeypatch.delenv('MXNET_TPU_ZERO')
    assert mx.kvstore.create('local').zero_stage == 0


# ---------------------------------------------------------------------------
# satellite: KVStore multi-value push merges with ONE stacked reduction
# ---------------------------------------------------------------------------

def test_kvstore_push_multi_value_merge():
    kv = mx.kvstore.create('local')
    kv.init('g', mx.nd.zeros((3, 2)))
    vals = [mx.nd.array(np.full((3, 2), float(i + 1), np.float32))
            for i in range(5)]
    kv.push('g', vals)                       # no updater: staged merge
    out = mx.nd.zeros((3, 2))
    kv.pull('g', out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((3, 2), 15.0))

    kv2 = mx.kvstore.create('local')
    kv2.init('w', mx.nd.ones((2, 2)))
    kv2.set_optimizer(opt_mod.create('test', rescale_grad=1.0))
    kv2.push('w', [mx.nd.ones((2, 2)) * 2, mx.nd.ones((2, 2)) * 3])
    out2 = mx.nd.zeros((2, 2))
    kv2.pull('w', out=out2)
    np.testing.assert_allclose(out2.asnumpy(), np.full((2, 2), 6.0))
