"""Weight-delta channel tests (mxnet_tpu/delta.py, PERF round 22):
the move-only-what-changed layer across its three consumers.

* core: versioned delta format — touched-rows COO for tables, raw /
  int8-with-error-feedback for dense params — with typed chain gates
  (DeltaChainError / DeltaParityError) that mutate NOTHING on refusal.
* elastic: CheckpointManager(incremental=K) delta commits between full
  bases, bit-exact chain-replay resume (params AND optimizer state),
  torn-delta fallback to the newest intact prefix, chain-aware
  retention that never reaps a base referenced by a retained delta,
  chain replay across a virtual dp-width change.
* serving/fleet: InferenceEngine.apply_delta bitwise vs full reload at
  zero re-warm compiles, ModelRegistry paged-image deltas, the replica
  `:delta` admin op with its typed 409 refusal, the pusher's delta
  channel (chain advances only on promote; fingerprint mismatch falls
  back to a full push and the next promote rebases), and the
  LrBackoff on_verdict hook that turns consecutive rollbacks into a
  learning-rate cut instead of a RollbackStop.
"""
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic, model as model_mod, nd, profiler
from mxnet_tpu import delta as delta_mod
from mxnet_tpu import sym as S
from mxnet_tpu import fleet_supervisor as fs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.delta import (DeltaChainError, DeltaConfig,
                             DeltaParityError, apply_delta,
                             fingerprint, make_delta)
from mxnet_tpu.fleet_supervisor import (CheckpointPusher,
                                        FleetSupervisor, PushVerdict,
                                        ReplicaServer)
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import InferenceEngine

DIM, HID, OUT = 6, 8, 3


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _head(hid=HID):
    data = S.Variable('data')
    fc1 = S.FullyConnected(data, num_hidden=hid, name='fc1')
    act = S.Activation(fc1, act_type='relu')
    return S.FullyConnected(act, num_hidden=OUT, name='fc2')


def _module(seed=3, momentum=0.9):
    net = S.SoftmaxOutput(_head(), name='softmax')
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (4, DIM))],
             label_shapes=[mx.io.DataDesc('softmax_label', (4,))])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': momentum})
    return mod


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(4, DIM).astype(np.float32))],
        label=[mx.nd.array((rng.rand(4) * OUT).astype(np.float32))])
        for _ in range(n)]


def _train(mod, batches):
    for b in batches:
        mod.forward_backward(b)
        mod.update()


def _state(seed=0, rows=64):
    rs = np.random.RandomState(seed)
    return {
        'arg:table': rs.randn(rows, 8).astype(np.float32),
        'arg:w': rs.randn(32, 16).astype(np.float32),
        'arg:b': rs.randn(16).astype(np.float32),
        'aux:m': rs.randn(4).astype(np.float32),
    }


def _frozen(state):
    return {n: a.copy() for n, a in state.items()}


def _assert_unchanged(state, frozen):
    for n in frozen:
        np.testing.assert_array_equal(state[n], frozen[n], err_msg=n)


def _wait(pred, timeout=60, msg='condition'):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError('timed out waiting for %s' % msg)


# ---------------------------------------------------------------------------
# core format: kinds, bitwise roundtrip, error feedback
# ---------------------------------------------------------------------------

def test_make_apply_roundtrip_kinds_and_bitwise():
    base = _state(0)
    rs = np.random.RandomState(1)
    cur = _frozen(base)
    cur['arg:table'][rs.choice(64, 5, replace=False)] += \
        rs.randn(5, 8).astype(np.float32)
    cur['arg:b'] += rs.randn(16).astype(np.float32) * 0.1
    # arg:w and aux:m untouched -> must be OMITTED from the payload
    cfg = DeltaConfig(dense='raw', min_dense=1)
    entries, meta, new_state = make_delta(
        base, cur, seq=1, base_fp=fingerprint(base), config=cfg)
    kinds = {n: e['kind'] for n, e in meta['entries'].items()}
    assert kinds['arg:table'] == 'rows'
    assert 'arg:w' not in kinds and 'aux:m' not in kinds
    assert meta['seq'] == 1 and meta['base_fp'] == fingerprint(base)
    assert 0 < meta['bytes'] < meta['full_bytes']
    out = apply_delta(base, meta, dict(entries),
                      expect_fp=fingerprint(base), expect_seq=1)
    for n in cur:
        np.testing.assert_array_equal(out[n], cur[n], err_msg=n)
    assert fingerprint(out) == meta['new_fp']
    # the encoder's resident new_state is the SAME state the applier
    # lands on (the chain both sides walk)
    for n in cur:
        np.testing.assert_array_equal(new_state[n], out[n], err_msg=n)


def test_int8_dense_delta_error_feedback_and_parity_meta():
    base = _state(2)
    rs = np.random.RandomState(3)
    cur = _frozen(base)
    cur['arg:w'] += rs.randn(32, 16).astype(np.float32) * 0.05
    cfg = DeltaConfig(dense='int8', min_dense=1, sparse_frac=0.0)
    entries, meta, new_state = make_delta(
        base, cur, seq=1, base_fp=fingerprint(base), config=cfg)
    assert meta['entries']['arg:w']['kind'] == 'int8'
    assert meta['rel_err'] > 0           # random diffs never exact
    out = apply_delta(base, meta, dict(entries),
                      expect_fp=fingerprint(base))
    # bit-identical to the ENCODER's resident state (base + dequant),
    # close to the true target (the int8 quantization error)
    np.testing.assert_array_equal(out['arg:w'], new_state['arg:w'])
    rel = np.abs(out['arg:w'] - cur['arg:w']).max() / \
        np.abs(cur['arg:w']).max()
    assert rel < 0.01


def test_typed_gates_refuse_with_nothing_mutated():
    base = _state(4)
    rs = np.random.RandomState(5)
    cur = _frozen(base)
    cur['arg:table'][:3] += rs.randn(3, 8).astype(np.float32)
    cfg = DeltaConfig(dense='raw', min_dense=1)
    entries, meta, _ = make_delta(base, cur, seq=2,
                                  base_fp=fingerprint(base),
                                  config=cfg)
    frozen = _frozen(base)
    with pytest.raises(DeltaChainError, match='fingerprint'):
        apply_delta(base, meta, dict(entries),
                    expect_fp='deadbeefdeadbeef')
    with pytest.raises(DeltaChainError, match='seq'):
        apply_delta(base, meta, dict(entries),
                    expect_fp=fingerprint(base), expect_seq=7)
    # corrupt payload bytes -> per-entry crc gate
    bad = dict(entries)
    key = [k for k in bad if k.startswith('drows:')][0]
    bad[key] = np.asarray(bad[key]).copy()
    bad[key].ravel()[0] += 1.0
    with pytest.raises(DeltaChainError, match='crc'):
        apply_delta(base, meta, bad, expect_fp=fingerprint(base))
    # parity gate on a lossy dense delta
    cur2 = _frozen(base)
    cur2['arg:w'] += rs.randn(32, 16).astype(np.float32) * 0.05
    e2, m2, _ = make_delta(base, cur2, seq=1,
                           base_fp=fingerprint(base),
                           config=DeltaConfig(dense='int8',
                                              min_dense=1,
                                              sparse_frac=0.0))
    with pytest.raises(DeltaParityError):
        apply_delta(base, m2, dict(e2), expect_fp=fingerprint(base),
                    parity_tol=1e-12)
    _assert_unchanged(base, frozen)       # every refusal staged first


def test_shape_or_nameset_change_needs_rebase():
    base = _state(6)
    cur = _frozen(base)
    cur['arg:w'] = np.zeros((8, 8), np.float32)        # shape change
    with pytest.raises(MXNetError):
        make_delta(base, cur, seq=1, base_fp=fingerprint(base))
    cur2 = _frozen(base)
    del cur2['arg:b']                                  # name-set change
    with pytest.raises(MXNetError):
        make_delta(base, cur2, seq=1, base_fp=fingerprint(base))


# ---------------------------------------------------------------------------
# elastic: incremental commits, chain replay, fallback, retention
# ---------------------------------------------------------------------------

def test_incremental_layout_and_chain_resume_bit_parity(tmp_path):
    """K delta commits between full bases; resuming from the chain
    TAIL replays base + deltas and lands bit-identical (params and
    momentum — the default delta_config keeps dense diffs raw)."""
    profiler.clear()
    mod = _module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                    async_=False, incremental=3)
    mgr.attach(mod)
    for b in _batches(6):
        mod.forward_backward(b)
        mod.update()
        mgr.step_end()
    # commits 1..6 with incremental=3: fulls at 1 and 5, deltas else
    assert elastic.list_checkpoints(str(tmp_path)) == [5, 1]
    assert elastic.list_deltas(str(tmp_path)) == [6, 4, 3, 2]
    st = profiler.delta_stats()
    assert st['delta_committed'] == 4
    # tiny fully-dense model: every array moves every step, so the
    # raw-exact deltas carry ~full bytes — the byte WIN is held on
    # the embedding workload by the next test; here the contract is
    # the chain replay, not the ratio
    assert 0 < st['delta_bytes'] <= st['delta_full_bytes']
    # newest intact is the chain tail; replay == live module, bitwise
    man, arrays, tail = elastic.load_newest_intact(str(tmp_path))
    assert os.path.basename(tail).startswith('delta-')
    assert man['step'] == 6
    pa, aa = mod.get_params()
    for n in pa:
        np.testing.assert_array_equal(arrays['param:%s' % n],
                                      pa[n].asnumpy(), err_msg=n)
    # full restore into a twin: params AND optimizer state bit-equal
    twin = _module(seed=9)
    info = elastic.CheckpointManager(str(tmp_path)).attach(twin) \
        .restore()
    assert info is not None and info.step == 6
    pb, _ = twin.get_params()
    for n in pa:
        np.testing.assert_array_equal(pa[n].asnumpy(),
                                      pb[n].asnumpy(), err_msg=n)
    import pickle
    sa = pickle.loads(mod._fused_updater.get_states())[0]
    sb = pickle.loads(twin._fused_updater.get_states())[0]
    assert sorted(sa) == sorted(sb)
    for k in sa:
        np.testing.assert_array_equal(np.asarray(sa[k]),
                                      np.asarray(sb[k]), err_msg=str(k))
    mgr.close()


def test_incremental_commits_of_a_table_move_5x_fewer_bytes(tmp_path):
    """The embedding workload the channel exists for: a step touches a
    hot pool of 32 of a table's 2,000 rows under plain SGD, so a delta
    commit carries those rows and the dense head, and a chain of a full
    base and six deltas commits at least 5x fewer bytes than a full
    checkpoint at every step; the chain's tail replays onto the live
    weights bit for bit."""
    vocab, dim, batch, classes, steps = 2000, 16, 32, 10, 14
    rs = np.random.RandomState(0)
    pool = rs.choice(vocab, size=32, replace=False)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(pool[rs.randint(0, 32, size=batch)]
                          .astype(np.float32))],
        label=[mx.nd.array((rs.rand(batch) * classes)
                           .astype(np.float32))])
        for _ in range(steps)]

    def committed_bytes(directory, **incremental):
        emb = S.Embedding(S.Variable('data'), input_dim=vocab,
                          output_dim=dim, name='emb')
        mod = mx.mod.Module(S.SoftmaxOutput(
            S.FullyConnected(emb, name='out', num_hidden=classes),
            name='softmax'))
        mod.bind(data_shapes=[mx.io.DataDesc('data', (batch,))],
                 label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
        mx.random.seed(1)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer='sgd', optimizer_params={
            'learning_rate': 0.1, 'momentum': 0.0, 'wd': 0.0})
        mgr = elastic.CheckpointManager(directory, every_n_steps=1,
                                        async_=False, **incremental)
        mgr.attach(mod)
        before = profiler.ckpt_stats()['ckpt_bytes']
        for b in batches:
            mod.forward_backward(b)
            mod.update()
            mgr.step_end()
        mgr.close()
        return profiler.ckpt_stats()['ckpt_bytes'] - before, mod

    full, _ = committed_bytes(str(tmp_path / 'full'))
    chained, mod = committed_bytes(str(tmp_path / 'incr'), incremental=6)
    assert full >= 5 * chained > 0
    _man, arrays, tail = elastic.load_newest_intact(str(tmp_path / 'incr'))
    assert os.path.basename(tail).startswith('delta-')
    pa, _ = mod.get_params()
    for n in pa:
        np.testing.assert_array_equal(arrays['param:%s' % n],
                                      pa[n].asnumpy(), err_msg=n)


def test_torn_delta_falls_back_to_newest_intact_prefix(tmp_path,
                                                       monkeypatch):
    profiler.clear()
    mod = _module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                    async_=False, incremental=4)
    mgr.attach(mod)
    for i, b in enumerate(_batches(4)):
        mod.forward_backward(b)
        mod.update()
        if i == 3:
            # crash mid-write on the LAST delta commit
            monkeypatch.setenv('MXNET_TPU_FAULT_TORN_CKPT', '1')
        mgr.step_end()
    monkeypatch.delenv('MXNET_TPU_FAULT_TORN_CKPT')
    # chain: full-1, delta-2, delta-3, delta-4(torn).  Every chain
    # prefix is itself a committed checkpoint -> fall back to delta-3
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None and res[0]['step'] == 3
    assert os.path.basename(res[2]).startswith('delta-')
    assert profiler.delta_stats()['delta_fallbacks'] >= 1
    mgr.close()


def test_chain_aware_retention_never_orphans_a_base(tmp_path):
    """Regression (satellite): keep-last-K counted only full dirs
    once, letting a base slide out while deltas chained on it were
    retained — every survivor must replay end-to-end after pruning,
    and a retain_refs pin (the fleet's in-flight push) holds its
    whole chain."""
    pinned = {2}
    mod = _module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                    async_=False, incremental=2,
                                    keep=2)
    mgr.retain_refs = lambda: pinned
    mgr.attach(mod)
    for b in _batches(8):
        mod.forward_backward(b)
        mod.update()
        mgr.step_end()
    fulls = elastic.list_checkpoints(str(tmp_path))
    deltas = elastic.list_deltas(str(tmp_path))
    # the pinned delta-2 survived retention, and so did its base
    assert 2 in deltas and 1 in fulls
    # EVERY surviving commit (either kind) must load end-to-end —
    # chain-aware pruning may never leave an unloadable delta behind
    for s in deltas:
        man, arrays = elastic.load_state(
            os.path.join(str(tmp_path), 'delta-%08d' % s))
        assert man['step'] == s and arrays
    # dropping the pin lets the old chain go at the next commit
    pinned.clear()
    _train(mod, _batches(1, seed=9))
    mgr.step_end()
    assert 2 not in elastic.list_deltas(str(tmp_path))
    mgr.close()


def test_abandoned_writer_chain_resumes_and_prunes(tmp_path):
    """SIGKILL-mid-chain shape: a writer dies (no close) with a live
    chain; a NEW manager in the same dir resumes from the tail,
    starts a FRESH full base (the dead writer's resident chain state
    is gone), and retention with the old chain present stays safe."""
    mod = _module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                    async_=False, incremental=3)
    mgr.attach(mod)
    for b in _batches(3):
        mod.forward_backward(b)
        mod.update()
        mgr.step_end()
    del mgr                      # abandoned: no close(), like SIGKILL
    twin = _module(seed=9)
    mgr2 = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                     async_=False, incremental=3,
                                     keep=2)
    mgr2.attach(twin)
    info = mgr2.restore()
    assert info is not None and info.step == 3
    pa, _ = mod.get_params()
    pb, _ = twin.get_params()
    for n in pa:
        np.testing.assert_array_equal(pa[n].asnumpy(),
                                      pb[n].asnumpy(), err_msg=n)
    # post-resume commits start a fresh FULL base (step 4), then chain
    for b in _batches(2, seed=7):
        twin.forward_backward(b)
        twin.update()
        mgr2.step_end()
    assert 4 in elastic.list_checkpoints(str(tmp_path))
    assert 5 in elastic.list_deltas(str(tmp_path))
    man, _arr, tail = elastic.load_newest_intact(str(tmp_path))
    assert man['step'] == 5
    mgr2.close()


def test_chain_replay_across_virtual_dp_width_change(tmp_path):
    """Satellite: a chain written under a world=2 manager (full base
    sharded into per-rank files) resumes bit-exactly through a
    world=1 manager — delta replay is mode-portable like full
    checkpoints."""
    mod = _module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1,
                                    async_=False, incremental=2,
                                    world=2)
    mgr.attach(mod)
    for b in _batches(3):
        mod.forward_backward(b)
        mod.update()
        mgr.step_end()
    assert elastic.list_deltas(str(tmp_path)) == [3, 2]
    twin = _module(seed=11)
    info = elastic.CheckpointManager(str(tmp_path), world=1) \
        .attach(twin).restore()
    assert info is not None and info.step == 3
    pa, _ = mod.get_params()
    pb, _ = twin.get_params()
    for n in pa:
        np.testing.assert_array_equal(pa[n].asnumpy(),
                                      pb[n].asnumpy(), err_msg=n)
    mgr.close()


# ---------------------------------------------------------------------------
# serving: engine + registry deltas
# ---------------------------------------------------------------------------

def _save_ckpt(tmp_path, name='m0', hid=64, seed=3):
    net = S.SoftmaxOutput(_head(hid=hid), name='softmax')
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (4, DIM))],
             label_shapes=[mx.io.DataDesc('softmax_label', (4,))])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.init.Xavier())
    args, auxs = mod.get_params()
    prefix = os.path.join(str(tmp_path), name)
    model_mod.save_checkpoint(prefix, 0, _head(hid=hid),
                              {n: a for n, a in args.items()}, auxs)
    return prefix


def test_engine_apply_delta_bitwise_and_typed_refusals(tmp_path):
    from mxnet_tpu import exec_cache
    profiler.clear()
    prefix = _save_ckpt(tmp_path)
    eng = InferenceEngine(
        Predictor.from_checkpoint(prefix, 0, {'data': (1, DIM)}),
        max_batch=1, max_wait_us=0)
    x = np.random.RandomState(0).randn(1, DIM).astype(np.float32)
    eng.predict(x)                       # warm every program
    rs = np.random.RandomState(1)

    def ref_out(state):
        args = {k[4:]: nd.array(v) for k, v in state.items()
                if k.startswith('arg:')}
        auxs = {k[4:]: nd.array(v) for k, v in state.items()
                if k.startswith('aux:')}
        ref = Predictor(symbol=_head(hid=64), arg_params=args,
                        aux_params=auxs,
                        input_shapes={'data': (1, DIM)})
        return ref.forward(data=nd.array(x))[0].asnumpy()

    # mixed sparse+raw delta -> BITWISE parity with a full reload, at
    # zero new compiles
    base = eng._resident_host_state()
    new = {n: a.copy() for n, a in base.items()}
    new['arg:fc1_weight'][rs.choice(64, 4, replace=False)] += \
        rs.randn(4, DIM).astype(np.float32) * 0.1
    new['arg:fc2_bias'] += rs.randn(OUT).astype(np.float32) * 0.1
    ent, meta, _ = make_delta(base, new, seq=1,
                              base_fp=fingerprint(base),
                              config=DeltaConfig(dense='raw',
                                                 min_dense=1))
    assert meta['entries']['arg:fc1_weight']['kind'] == 'rows'
    c0 = exec_cache.stats()['total_compile_s']
    fp = eng.apply_delta(dict(ent), meta,
                         expect_fp=fingerprint(base))
    assert fp == meta['new_fp']
    assert exec_cache.stats()['total_compile_s'] == c0
    np.testing.assert_array_equal(np.asarray(eng.predict(x)),
                                  ref_out(new))
    assert profiler.delta_stats()['delta_applied'] >= 1

    # chain gate: the delta's base_fp no longer matches the resident
    # state (it already advanced) -> typed refusal, nothing mutated
    before = np.asarray(eng.predict(x)).copy()
    with pytest.raises(DeltaChainError, match='fingerprint'):
        eng.apply_delta(dict(ent), meta,
                        expect_fp=fingerprint(
                            eng._resident_host_state()))
    np.testing.assert_array_equal(np.asarray(eng.predict(x)), before)

    # parity gate on a lossy int8 delta: tight tol refuses typed with
    # NOTHING mutated; the default tol applies
    base2 = eng._resident_host_state()
    new2 = {n: a.copy() for n, a in base2.items()}
    new2['arg:fc2_weight'] += \
        rs.randn(OUT, 64).astype(np.float32) * 0.05
    e2, m2, _ = make_delta(base2, new2, seq=1,
                           base_fp=fingerprint(base2),
                           config=DeltaConfig(dense='int8',
                                              min_dense=1,
                                              sparse_frac=0.0))
    assert m2['entries']['arg:fc2_weight']['kind'] == 'int8'
    with pytest.raises(DeltaParityError):
        eng.apply_delta(dict(e2), m2, expect_fp=fingerprint(base2),
                        parity_tol=1e-12)
    np.testing.assert_array_equal(np.asarray(eng.predict(x)), before)
    assert profiler.delta_stats()['delta_parity_refusals'] >= 1
    eng.apply_delta(dict(e2), m2, expect_fp=fingerprint(base2))
    assert not np.array_equal(np.asarray(eng.predict(x)), before)
    eng.close()


def test_registry_delta_resident_and_paged_image(tmp_path):
    from mxnet_tpu.serving_fleet import ModelRegistry
    profiler.clear()
    prefix = _save_ckpt(tmp_path)
    x = np.random.RandomState(0).randn(1, DIM).astype(np.float32)
    reg = ModelRegistry()
    reg.register('p', prefix=prefix, epoch=0,
                 input_shapes={'data': (1, DIM)}, max_batch=1,
                 max_wait_us=0, page_dtype='int8')
    y0 = np.asarray(reg.predict('p', x)).copy()
    # resident path: in-place engine delta
    eng = reg.engine('p')
    base = eng._resident_host_state()
    rs = np.random.RandomState(2)
    new = {n: a.copy() for n, a in base.items()}
    new['arg:fc1_weight'][rs.choice(64, 4, replace=False)] += \
        rs.randn(4, DIM).astype(np.float32) * 0.2
    ent, meta, _ = make_delta(base, new, seq=1,
                              base_fp=fingerprint(base),
                              config=DeltaConfig(dense='raw',
                                                 min_dense=1))
    reg.apply_delta('p', dict(ent), meta,
                    expect_fp=fingerprint(base))
    y1 = np.asarray(reg.predict('p', x)).copy()
    assert not np.array_equal(y1, y0)
    # paged path: evict to the quantized host image, delta the IMAGE,
    # and the next page-in already reflects the push
    reg.evict('p')
    assert reg.stats()['models']['p']['paged']
    new2 = {n: a.copy() for n, a in new.items()}
    new2['arg:fc1_weight'][rs.choice(64, 4, replace=False)] += \
        rs.randn(4, DIM).astype(np.float32) * 0.2
    e2, m2, _ = make_delta(new, new2, seq=2, base_fp=meta['new_fp'],
                           config=DeltaConfig(dense='raw',
                                              min_dense=1))
    reg.apply_delta('p', dict(e2), m2)     # lossy image: no expect_fp
    assert profiler.delta_stats()['delta_page_applies'] >= 1
    y2 = np.asarray(reg.predict('p', x))   # page-in from the image
    assert reg.stats()['page_ins'] >= 1
    # int8 image roundtrip is lossy but must track the delta's target
    ref = Predictor(symbol=_head(hid=64),
                    arg_params={k[4:]: nd.array(v)
                                for k, v in new2.items()
                                if k.startswith('arg:')},
                    aux_params={k[4:]: nd.array(v)
                                for k, v in new2.items()
                                if k.startswith('aux:')},
                    input_shapes={'data': (1, DIM)})
    want = ref.forward(data=nd.array(x))[0].asnumpy()
    assert np.abs(y2 - want).max() < 0.05
    # a model that is neither resident nor imaged refuses typed
    reg.register('bare', prefix=prefix, epoch=0,
                 input_shapes={'data': (1, DIM)}, max_batch=1,
                 max_wait_us=0)
    with pytest.raises(MXNetError, match='neither resident'):
        reg.apply_delta('bare', dict(ent), meta)
    reg.close()


# ---------------------------------------------------------------------------
# fleet: replica :delta op, pusher delta channel, fallback + rebase
# ---------------------------------------------------------------------------

def _perturb(mod, seed, scale=0.05):
    rs = np.random.RandomState(seed)
    args, auxs = mod.get_params()
    new = {n: nd.array(a.asnumpy() +
                       rs.randn(*a.shape).astype(np.float32) * scale)
           for n, a in args.items()}
    mod.set_params(new, auxs)


def test_pusher_delta_channel_end_to_end(tmp_path):
    """Full push -> promote commits the chain base; the next push
    ships a DELTA the replica applies onto its resident arm (bitwise
    vs a full reload of the same export); a tampered chain draws the
    replica's typed 409 and the pusher falls back to a FULL push whose
    promote rebases the chain to seq 0."""
    profiler.clear()
    mod = _module(momentum=0.0)
    prefix0 = _save_ckpt(tmp_path, name='stable', hid=HID)
    spec = {'name': 'm', 'prefix': prefix0, 'epoch': 0,
            'input_shapes': {'data': [1, DIM]},
            'max_batch': 4, 'max_wait_us': 0}
    live = ReplicaServer(models=[spec], index=0).start()
    sup = FleetSupervisor(models=[spec], replicas=1)
    rep = fs._Replica(0)
    rep.host, rep.port = live.address
    sup._replicas = [rep]
    pusher = CheckpointPusher(sup, 'm', symbol=_head(),
                              push_dir=str(tmp_path / 'push'),
                              delta=True, delta_rebase=8)
    mgr = pusher.attach(elastic.CheckpointManager(
        str(tmp_path / 'ck'), every_n_steps=1))
    mgr.attach(mod)
    try:
        # push 1: no promoted base yet -> full
        mgr.step_end()
        mgr.wait()
        _wait(lambda: profiler.loop_stats()['loop_pushes'] == 1,
              msg='push 1')
        cand1 = [n for n in live.registry.models() if '@' in n][0]
        assert profiler.delta_stats()['delta_pushes'] == 0
        sup._on_router_event('promote', 'm', {'candidate': cand1,
                                              'report': None})
        _wait(lambda: pusher._base is not None, msg='chain base')
        assert pusher._base['seq'] == 0

        # push 2: delta ships; replica builds the candidate from its
        # RESIDENT arm + payload, bitwise vs full reload
        _perturb(mod, seed=11)
        mgr.step_end()
        mgr.wait()
        _wait(lambda: profiler.loop_stats()['loop_pushes'] == 2,
              msg='push 2')
        st = profiler.delta_stats()
        assert st['delta_pushes'] == 1
        assert st['delta_applied'] >= 1
        assert 0 < st['delta_bytes'] < st['delta_full_bytes']
        cand2 = sorted(n for n in live.registry.models()
                       if '@' in n)[-1]
        _s, fargs, fauxs = model_mod.load_checkpoint(
            str(tmp_path / 'push' / ('push-%08d' % 2)), 0)
        ref = Predictor(symbol=_head(), arg_params=fargs,
                        aux_params=fauxs,
                        input_shapes={'data': (1, DIM)})
        x = np.random.RandomState(0).randn(1, DIM) \
            .astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(live.registry.engine(cand2).predict(x)),
            ref.forward(data=nd.array(x))[0].asnumpy())
        sup._on_router_event('promote', 'm', {'candidate': cand2,
                                              'report': None})
        _wait(lambda: pusher._base is not None and
              pusher._base['seq'] == 1, msg='chain seq 1')

        # push 3: tampered chain -> 409 -> full-push fallback; the
        # fallback's promote REBASES the chain
        _perturb(mod, seed=13)
        with pusher._lock:
            pusher._base['fp'] = 'deadbeefdeadbeef'
        mgr.step_end()
        mgr.wait()
        _wait(lambda: profiler.loop_stats()['loop_pushes'] == 3,
              msg='push 3')
        st = profiler.delta_stats()
        assert st['delta_push_fallbacks'] == 1
        assert st['delta_pushes'] == 1           # fallback went FULL
        cand3 = sorted(n for n in live.registry.models()
                       if '@' in n)[-1]
        assert cand3 != cand2
        sup._on_router_event('promote', 'm', {'candidate': cand3,
                                              'report': None})
        _wait(lambda: pusher._base is not None and
              pusher._base['seq'] == 0, msg='chain rebased')
    finally:
        pusher.close()
        mgr.close()
        sup.router.close()
        live.close()


# ---------------------------------------------------------------------------
# verdict hook: LrBackoff instead of RollbackStop
# ---------------------------------------------------------------------------

class _StubSupervisor(object):
    """Scripted fleet (the test_train_serve_loop stub): push()
    accepts; verdicts fire on demand through on_push_verdict."""

    def __init__(self):
        self.pushes = []
        self._cbs = []
        self._seq = 0
        self._active = set()

    def on_push_verdict(self, cb):
        self._cbs.append(cb)
        return self

    def push_active(self, name):
        return name in self._active

    def active_prefixes(self, name):
        return set()

    def push(self, name, prefix, epoch=0, frac=None, mode='canary',
             tag=None):
        self._seq += 1
        cand = '%s@v%d' % (name, self._seq)
        self.pushes.append((name, prefix, cand))
        self._active.add(name)
        return cand

    def decide(self, kind, cand, model='m'):
        self._active.discard(model)
        v = PushVerdict(kind, model, cand)
        for cb in self._cbs:
            cb(v)
        return v


def test_lr_backoff_hook_replaces_rollback_stop(tmp_path):
    """Satellite: with an on_verdict hook installed the pusher's
    consecutive-rollback limit does NOT stop training — LrBackoff
    owns the response and cuts the lr every `after` rollbacks."""
    profiler.clear()
    sup = _StubSupervisor()
    mod = _module()
    pusher = CheckpointPusher(sup, 'm', symbol=_head(),
                              push_dir=str(tmp_path / 'push'),
                              max_consecutive_rollbacks=2)
    mgr = pusher.attach(elastic.CheckpointManager(
        str(tmp_path / 'ck'), every_n_steps=1))
    mgr.attach(mod)
    lb = elastic.LrBackoff(mgr, factor=0.5, after=2)
    assert mgr.on_verdict is lb
    opt = lb._optimizer()
    assert opt is not None and opt.lr == pytest.approx(0.1)
    for i in range(4):
        mgr.step_end()                    # commit -> push
        mgr.wait()
        _wait(lambda: len(sup.pushes) == i + 1, msg='push %d' % i)
        sup.decide('rolled_back', sup.pushes[-1][2])
        _wait(lambda: len(pusher.verdicts()) == i + 1,
              msg='verdict %d' % i)
    assert pusher.consecutive_rollbacks == 4
    # past max_consecutive_rollbacks=2, but the hook owns it: the next
    # step boundary must NOT raise RollbackStop...
    mgr.step_end()
    mgr.wait()
    _wait(lambda: len(sup.pushes) == 5, msg='push 5')
    # ...and the lr was cut at streaks 2 and 4 (0.1 -> 0.05 -> 0.025)
    assert lb.backoffs == 2
    assert opt.lr == pytest.approx(0.025)
    assert profiler.loop_stats()['loop_lr_backoffs'] == 2
    # never below min_lr; a promote resets the streak
    sup.decide('promoted', sup.pushes[-1][2])
    _wait(lambda: pusher.consecutive_rollbacks == 0, msg='reset')
    pusher.close()
    mgr.close()


# ---------------------------------------------------------------------------
# observability: counters in summary + dump lane
# ---------------------------------------------------------------------------

def test_delta_counters_in_summary_and_dump(tmp_path):
    profiler.clear()
    profiler.add_delta_stats(committed=2, applied=1, bytes=100,
                             full_bytes=1000, chain_len=2, pushes=1,
                             parity_refusals=1)
    text = profiler.summary(print_out=False)
    assert 'delta_committed=2' in text
    assert 'delta_parity_refusals=1' in text
    fname = str(tmp_path / 'prof.json')
    profiler.profiler_set_config(mode='symbolic', filename=fname)
    path = profiler.dump_profile()
    lane = [e for e in json.load(open(path))['traceEvents']
            if e.get('name') == 'delta']
    assert lane and lane[0]['args']['delta_committed'] == 2
    assert lane[0]['args']['delta_chain_len'] == 2
