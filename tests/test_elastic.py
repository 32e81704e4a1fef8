"""Elastic training runtime tests (mxnet_tpu/elastic.py): async
sharded checkpoints, preemption-safe resume, kill-resume bit-parity
(plain / ZeRO-1 / bucket-ladder), torn-checkpoint fallback, fault
injection, and the atomic-write / load-validation satellites.

The kill-resume contract under test: a run SIGKILLed mid-epoch and
resumed from its newest intact checkpoint finishes with weights,
optimizer state, and metric BIT-IDENTICAL to the uninterrupted run.
"""
import json
import os
import pickle
import signal
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import elastic, profiler
from mxnet_tpu import sym as S
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tiny deterministic training fixtures
# ---------------------------------------------------------------------------

def _mlp_symbol():
    data = S.Variable('data')
    fc1 = S.FullyConnected(data, name='fc1', num_hidden=16)
    act = S.Activation(fc1, act_type='relu')
    fc2 = S.FullyConnected(act, name='fc2', num_hidden=4)
    return S.SoftmaxOutput(fc2, name='softmax')


def _make_module(seed=5, ndev=1, zero=None, momentum=0.9, bsz=8):
    ctxs = [mx.Context('cpu', i) for i in range(ndev)] if ndev > 1 \
        else None
    mod = mx.mod.Module(_mlp_symbol(), context=ctxs)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (bsz, 6))],
             label_shapes=[mx.io.DataDesc('softmax_label', (bsz,))])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': momentum},
                       zero=zero)
    return mod


def _batches(n, bsz=8, width=6, seed=0):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(bsz, width).astype(np.float32))],
        label=[mx.nd.array((rng.rand(bsz) * 4).astype(np.float32))])
        for _ in range(n)]


def _train(mod, batches):
    for b in batches:
        mod.forward_backward(b)
        mod.update()


def _assert_params_equal(mod_a, mod_b):
    pa, aa = mod_a.get_params()
    pb, ab = mod_b.get_params()
    for n in pa:
        np.testing.assert_array_equal(pa[n].asnumpy(), pb[n].asnumpy(),
                                      err_msg=n)
    for n in aa:
        np.testing.assert_array_equal(aa[n].asnumpy(), ab[n].asnumpy(),
                                      err_msg=n)


def _opt_states(mod):
    states, counts, masters = pickle.loads(
        mod._fused_updater.get_states())
    return ({n: np.asarray(v) for n, v in states.items()}, counts)


# ---------------------------------------------------------------------------
# shard-file container + satellites
# ---------------------------------------------------------------------------

def test_shard_file_roundtrip_and_torn(tmp_path):
    import jax.numpy as jnp
    path = str(tmp_path / 's.bin')
    entries = [('a', np.arange(12, dtype=np.float32).reshape(3, 4)),
               ('b:0:4', np.array([1, 2, 3], np.int64)),
               # bfloat16 (ml_dtypes) rejects memoryview — the writer
               # must reinterpret its buffer, and the reader must get
               # the dtype back (mixed-precision masters checkpoint)
               ('bf', np.asarray(jnp.arange(6, dtype=jnp.bfloat16)))]
    nbytes, crc = elastic.write_shard_file(path, entries)
    assert nbytes == os.path.getsize(path) and crc
    out = elastic.read_shard_file(path)
    np.testing.assert_array_equal(out['a'], entries[0][1])
    np.testing.assert_array_equal(out['b:0:4'], entries[1][1])
    assert out['bf'].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        out['bf'].astype(np.float32), entries[2][1].astype(np.float32))
    # truncation (torn write on a non-atomic store)
    blob = open(path, 'rb').read()
    with open(path, 'wb') as f:
        f.write(blob[:len(blob) // 2])
    with pytest.raises(MXNetError, match='torn'):
        elastic.read_shard_file(path)
    # single flipped payload bit fails the checksum
    flipped = bytearray(blob)
    flipped[len(elastic._CKPT_MAGIC) + 30] ^= 0x40
    with open(path, 'wb') as f:
        f.write(bytes(flipped))
    with pytest.raises(MXNetError, match='checksum'):
        elastic.read_shard_file(path)


def test_nd_save_is_atomic_and_load_validates(tmp_path):
    fname = str(tmp_path / 'p.params')
    good = {'arg:w': mx.nd.array(np.arange(6).reshape(2, 3)
                                 .astype(np.float32))}
    mx.nd.save(fname, good)
    # a failing later save must leave the original intact (temp +
    # os.replace — the old in-place writer left a torn file)
    with pytest.raises(TypeError):
        mx.nd.save(fname, {'arg:w': good['arg:w'],
                           'arg:bad': 'not an ndarray'})
    out = mx.nd.load(fname)
    np.testing.assert_array_equal(out['arg:w'].asnumpy(),
                                  good['arg:w'].asnumpy())
    assert not [n for n in os.listdir(str(tmp_path))
                if '.tmp' in n], 'temp files must not leak'
    # truncated blob -> clear MXNetError naming the file (was an
    # opaque struct.error deep in the decoder)
    blob = open(fname, 'rb').read()
    for cut in (4, len(blob) - 3):
        with open(fname, 'wb') as f:
            f.write(blob[:cut])
        with pytest.raises(MXNetError, match='p.params'):
            mx.nd.load(fname)
    # bad magic
    with open(fname, 'wb') as f:
        f.write(b'NOTAPARAMSFILE' + blob)
    with pytest.raises(MXNetError, match='magic'):
        mx.nd.load(fname)
    # implausible entry count
    with open(fname, 'wb') as f:
        f.write(blob[:8] + b'\xff' * 8 + blob[16:])
    with pytest.raises(MXNetError):
        mx.nd.load(fname)


def test_model_checkpoint_atomic_and_validated(tmp_path):
    from mxnet_tpu import model as model_mod
    mod = _make_module()
    prefix = str(tmp_path / 'ck')
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    sym2, args, auxs = model_mod.load_checkpoint(prefix, 1)
    assert set(args) == {'fc1_weight', 'fc1_bias', 'fc2_weight',
                         'fc2_bias'}
    # corrupt the params blob: load_checkpoint raises a clear error
    pfile = '%s-0001.params' % prefix
    blob = open(pfile, 'rb').read()
    with open(pfile, 'wb') as f:
        f.write(blob[:len(blob) - 9])
    with pytest.raises(MXNetError, match='ck-0001.params'):
        model_mod.load_checkpoint(prefix, 1)
    assert not [n for n in os.listdir(str(tmp_path)) if '.tmp' in n]


# ---------------------------------------------------------------------------
# kill-resume parity (in-process crash simulation = fresh objects)
# ---------------------------------------------------------------------------

def test_module_kill_resume_parity(tmp_path):
    batches = _batches(10)
    straight = _make_module()
    _train(straight, batches)

    victim = _make_module()
    _train(victim, batches[:5])
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False)
    mgr.attach(victim)
    mgr._step = 5
    mgr.save(epoch=0, batches_in_epoch=5, batch_size=8, sync=True)

    resumed = _make_module(seed=11)   # different init: must be overwritten
    info = elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          resumed)
    assert info is not None and info.step == 5
    assert info.samples_consumed == 40
    _train(resumed, batches[5:])
    _assert_params_equal(straight, resumed)
    sa, ca = _opt_states(straight)
    sb, cb = _opt_states(resumed)
    assert ca == cb
    for n in sa:
        np.testing.assert_array_equal(sa[n], sb[n], err_msg=n)


def test_save_before_first_step_restores(tmp_path):
    mod = _make_module()
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False)
    mgr.attach(mod)
    mgr.save(sync=True)
    other = _make_module(seed=9)
    assert elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          other) is not None
    _assert_params_equal(mod, other)
    batches = _batches(3)
    _train(mod, batches)
    _train(other, batches)
    _assert_params_equal(mod, other)


def test_zero_sharded_kill_resume_and_resharding(tmp_path):
    ndev, bsz = 4, 8
    batches = _batches(8, bsz=bsz)
    straight = _make_module(ndev=ndev, zero=1)
    assert straight._fused_updater.zero == 1
    _train(straight, batches)

    victim = _make_module(ndev=ndev, zero=1)
    _train(victim, batches[:4])
    # virtual world=2: the dp-sharded momentum buckets split across two
    # per-rank shard files (the LOCAL-shard-only save path)
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False,
                                    world=2)
    mgr.attach(victim)
    mgr._step = 4
    d = mgr.save(sync=True)
    assert sorted(os.listdir(d)) == ['manifest.json',
                                    'state-r00000.bin',
                                    'state-r00001.bin']
    man = json.load(open(os.path.join(d, 'manifest.json')))
    assert man['opt']['mode'] == 'zero' and man['opt']['zero_buckets']

    # same-width ZeRO resume: bit-exact continuation
    resumed_mod = _make_module(seed=11, ndev=ndev, zero=1)
    assert elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          resumed_mod) is not None
    _train(resumed_mod, batches[4:])
    _assert_params_equal(straight, resumed_mod)

    # mode portability: the same shard files restore into zero=0
    repl = _make_module(seed=12, ndev=ndev, zero=0)
    assert elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          repl) is not None
    _train(repl, batches[4:])
    pa, _ = straight.get_params()
    pb, _ = repl.get_params()
    for n in pa:
        np.testing.assert_allclose(pa[n].asnumpy(), pb[n].asnumpy(),
                                   rtol=2e-6, atol=1e-7, err_msg=n)

    # dp re-sharding: dp=4 buckets reassemble and re-bucket at dp=2,
    # momenta surviving bit-exactly through the flat-bucket round trip
    narrow = _make_module(seed=13, ndev=2, zero=1)
    assert elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          narrow) is not None
    sv, _ = _opt_states(victim)
    sn, _ = _opt_states(narrow)
    for n in sv:
        np.testing.assert_array_equal(sv[n], sn[n], err_msg=n)


def _bucket_sym_gen(nrows):
    data = S.Variable('data')
    fc1 = S.FullyConnected(data, name='fc1', num_hidden=16)
    act = S.Activation(fc1, act_type='relu')
    fc2 = S.FullyConnected(act, name='fc2', num_hidden=4)
    net = S.SoftmaxOutput(fc2, name='softmax', use_ignore=True,
                          ignore_label=-1)
    return net, ['data'], ['softmax_label']


def _make_bucket_module(seed=5):
    mod = mx.mod.BucketingModule(_bucket_sym_gen, default_bucket_key=8,
                                 bucket_ladder=[4, 8], mask_label=-1)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (8, 6))],
             label_shapes=[mx.io.DataDesc('softmax_label', (8,))])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params={'learning_rate': 0.1,
                                         'momentum': 0.9})
    return mod


def _bucket_batches(n, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        w = 4 if i % 2 else 8   # rows -> bucket key (ladder rungs 4/8)
        out.append(mx.io.DataBatch(
            data=[mx.nd.array(rng.rand(w, 6).astype(np.float32))],
            label=[mx.nd.array((rng.rand(w) * 4).astype(np.float32))],
            bucket_key=w,
            provide_data=[mx.io.DataDesc('data', (w, 6))],
            provide_label=[mx.io.DataDesc('softmax_label', (w,))]))
    return out


def test_bucket_ladder_kill_resume_parity(tmp_path):
    batches = _bucket_batches(8)
    straight = _make_bucket_module()
    _train(straight, batches)

    victim = _make_bucket_module()
    _train(victim, batches[:4])
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False)
    mgr.attach(victim)
    mgr._step = 4
    d = mgr.save(sync=True)
    man = json.load(open(os.path.join(d, 'manifest.json')))
    assert man['rung'] == 4       # ladder rung at the snapshot

    resumed = _make_bucket_module(seed=11)
    info = elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          resumed)
    assert info is not None and info.rung == 4
    _train(resumed, batches[4:])
    _assert_params_equal(straight, resumed)


# ---------------------------------------------------------------------------
# fit() wiring: auto-resume, watermark fast-forward, metric continuity
# ---------------------------------------------------------------------------

def _fit_iter():
    rng = np.random.RandomState(3)
    return mx.io.NDArrayIter(rng.rand(48, 6).astype(np.float32),
                             (rng.rand(48) * 4).astype(np.float32),
                             batch_size=8)


def _fit(mod, ckpt=None, cb=None, log=None):
    def tail_cb(param):
        if cb is not None:
            cb(param)
        if log is not None:
            log[(param.epoch, param.nbatch)] = \
                param.eval_metric.get_name_value()[0][1]
    mx.random.seed(7)
    mod.fit(_fit_iter(), eval_metric='acc', optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
            initializer=mx.init.Xavier(), num_epoch=2,
            checkpoint=ckpt, batch_end_callback=tail_cb)


def test_fit_preempt_resume_bit_parity(tmp_path):
    log_a = {}
    straight = mx.mod.Module(_mlp_symbol())
    _fit(straight, log=log_a)

    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=4)
    victim = mx.mod.Module(_mlp_symbol())
    fired = []

    def preempt_cb(param):
        if param.epoch == 1 and param.nbatch == 2 and not fired:
            fired.append(1)
            mgr.request_preempt()   # what the SIGTERM handler does

    with pytest.raises(elastic.Preempted):
        _fit(victim, ckpt=mgr, cb=preempt_cb)
    mgr.close()
    assert elastic.list_checkpoints(str(tmp_path))

    log_c = {}
    resumed = mx.mod.Module(_mlp_symbol())
    mgr2 = elastic.CheckpointManager(str(tmp_path))
    _fit(resumed, ckpt=mgr2, log=log_c)
    info = mgr2.last_resume
    assert info is not None and info.epoch == 1
    assert info.batches_in_epoch == 3    # mid-epoch watermark
    _assert_params_equal(straight, resumed)
    # metric continuity: the resumed epoch's running train metric
    # matches the uninterrupted run at every post-resume batch —
    # the restored partial-epoch (sum, count) carried forward
    resumed_points = [k for k in log_c if k[0] == 1]
    assert resumed_points
    for k in resumed_points:
        assert log_a[k] == log_c[k], k
    mgr2.close()


def test_checkpoint_will_act_predicts_cadence(tmp_path):
    # will_act(k) is the overlapped-fit drain predicate: it must say
    # True exactly when the NEXT step_end would take a cadence
    # checkpoint or commit a pending preemption — never on the
    # common no-op steps that keep the async pipeline unbroken
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=4,
                                    async_=False)
    assert not mgr.will_act(1)          # step 0 -> 1: not due
    mgr._step = 3
    assert mgr.will_act(1)              # 3 -> 4: cadence fires
    mgr._step = 0
    mgr.request_preempt()
    assert mgr.will_act(1)              # pending preempt always acts
    mgr.close()


def test_fit_deferred_metric_pipeline_parity(tmp_path, monkeypatch):
    # overlapped fit (MXNET_TPU_TRAIN_STEP_AHEAD): metric folds and
    # batch_end_callbacks defer up to `ahead` batches behind the
    # dispatches.  Depth changes only WHEN the host folds, never what
    # is folded — the per-batch metric log and final params must
    # match the serialized run exactly, including across a blocking
    # checkpoint cadence where will_act() drains the pipeline to a
    # consistent step boundary first
    log_a, log_b = {}, {}
    monkeypatch.setenv('MXNET_TPU_TRAIN_STEP_AHEAD', '0')
    a = mx.mod.Module(_mlp_symbol())
    mgr_a = elastic.CheckpointManager(str(tmp_path / 'a'),
                                      every_n_steps=4, async_=False)
    _fit(a, ckpt=mgr_a, log=log_a)
    mgr_a.close()
    monkeypatch.setenv('MXNET_TPU_TRAIN_STEP_AHEAD', '2')
    profiler.clear()
    b = mx.mod.Module(_mlp_symbol())
    mgr_b = elastic.CheckpointManager(str(tmp_path / 'b'),
                                      every_n_steps=4, async_=False)
    _fit(b, ckpt=mgr_b, log=log_b)
    mgr_b.close()
    assert log_a == log_b
    _assert_params_equal(a, b)
    ov = profiler.overlap_stats()
    assert ov['overlap_train_steps'] >= 1
    assert ov['overlap_deferred_metric_folds'] >= 1
    # both cadences actually checkpointed through the drain
    assert elastic.list_checkpoints(str(tmp_path / 'a'))
    assert elastic.list_checkpoints(str(tmp_path / 'b'))
    profiler.clear()


def test_preempt_during_validation_not_swallowed(tmp_path):
    """A signal landing AFTER the epoch's last step (during
    validation) must still commit a final checkpoint and raise — not
    be silently absorbed by fit's handler teardown."""
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1000)
    mod = mx.mod.Module(_mlp_symbol())
    mx.random.seed(7)
    with pytest.raises(elastic.Preempted):
        mod.fit(_fit_iter(), eval_data=_fit_iter(), eval_metric='acc',
                optimizer='sgd',
                optimizer_params={'learning_rate': 0.1},
                initializer=mx.init.Xavier(), num_epoch=2,
                checkpoint=mgr,
                eval_batch_end_callback=lambda p: mgr.request_preempt())
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None
    # the boundary checkpoint marks the START of the next epoch
    assert res[0]['epoch'] == 1 and res[0]['batches_in_epoch'] == 0
    mgr.close()


def test_sigterm_commits_final_checkpoint(tmp_path):
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=1000)
    mod = _make_module()
    mgr.attach(mod).install_signal_handlers()
    try:
        batches = _batches(4)
        _train(mod, batches[:2])
        mgr.step_end(epoch=0, batches_in_epoch=1, batch_size=8)
        os.kill(os.getpid(), signal.SIGTERM)   # delivered to main thread
        _train(mod, batches[2:3])              # drain: one more dispatch
        with pytest.raises(elastic.Preempted):
            mgr.step_end(epoch=0, batches_in_epoch=2, batch_size=8)
    finally:
        mgr.close()
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None
    manifest, arrays, _ = res
    assert manifest['step'] == 2
    pm, _ = mod.get_params()
    np.testing.assert_array_equal(
        np.asarray(arrays['param:fc1_weight']),
        pm['fc1_weight'].asnumpy())


@pytest.mark.slow
def test_fit_sigkill_subprocess_resume(tmp_path):
    """slow (~20s, round-16 headroom): the subprocess SIGKILL E2E also
    runs in dryrun phase (h); kill/resume bit-parity and the final
    commit stay tier-1 via test_module_kill_resume_parity,
    test_fit_preempt_resume_bit_parity and
    test_sigterm_commits_final_checkpoint.

    The real preemption path: a fit() child is SIGKILLed mid-epoch
    by MXNET_TPU_FAULT_KILL_AT_STEP (no warning, no cleanup), a second
    child resumes from the cadence checkpoint, and the final weights
    match an uninterrupted child bit-exactly."""
    def run(tag, kill_at=None):
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   PYTHONPATH=_REPO + os.pathsep +
                   os.environ.get('PYTHONPATH', ''))
        env.pop('MXNET_TPU_FAULT_KILL_AT_STEP', None)
        if kill_at is not None:
            env['MXNET_TPU_FAULT_KILL_AT_STEP'] = str(kill_at)
        out = str(tmp_path / ('%s.npz' % tag))
        ck = str(tmp_path / ('ck_%s' % ('straight' if tag == 'straight'
                                        else 'elastic')))
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), 'fit-worker',
             ck, out], env=env, capture_output=True, text=True,
            timeout=300)
        return proc, out

    proc, out_a = run('straight')
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    proc, _ = run('killed', kill_at=7)
    assert proc.returncode == -signal.SIGKILL
    assert elastic.list_checkpoints(str(tmp_path / 'ck_elastic')), \
        'cadence checkpoint must exist before the kill'
    proc, out_b = run('resumed')
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    # the SIGKILL may land while step-6's async write is mid-flight:
    # resume comes from 6 when its manifest committed, else falls
    # back to the step-4 checkpoint — parity holds either way
    assert 'RESUMED step=' in proc.stdout, proc.stdout
    a = np.load(out_a)
    b = np.load(out_b)
    assert sorted(a.files) == sorted(b.files)
    for n in a.files:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)


# ---------------------------------------------------------------------------
# async overlap, fault injection, retention
# ---------------------------------------------------------------------------

def test_async_save_overlaps_training(tmp_path, monkeypatch):
    import time as _time
    profiler.clear()
    mod = _make_module()
    batches = _batches(3)
    _train(mod, batches[:1])
    mgr = elastic.CheckpointManager(str(tmp_path), async_=True)
    mgr.attach(mod)
    mgr._step = 0
    mgr.save(sync=True)   # warm the per-shape device-copy programs
    monkeypatch.setenv('MXNET_TPU_FAULT_WRITE_DELAY_MS', '120')
    mgr._step = 1
    t0 = _time.perf_counter()
    d = mgr.save()
    enqueue_ms = (_time.perf_counter() - t0) * 1e3
    assert d is not None
    assert enqueue_ms < 100, \
        'async save blocked the train thread %.1fms' % enqueue_ms
    _train(mod, batches[1:2])     # training overlaps the write
    # a cadence save while the write is in flight is SKIPPED, not a
    # stall
    assert mgr.save() is None
    assert mgr.wait(10)
    st = profiler.ckpt_stats()
    assert st['ckpt_snapshots'] == 2   # warm + timed
    assert st['ckpt_skipped'] == 1
    assert st['ckpt_async_overlap_ms'] > 0
    # the committed checkpoint holds the PRE-overlap-step weights
    # (snapshot semantics: state at save() time, not at commit time)
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None and res[0]['step'] == 1
    mgr.close()


@pytest.mark.parametrize('async_', [True, False])
def test_cadence_saves_leave_training_bitwise_unchanged(tmp_path, async_):
    """Checkpoints at a cadence, the snapshot taken on the train thread
    and written behind it or in line, do not perturb training: the
    weights are those of the same steps run without a manager, bit for
    bit."""
    batches = _batches(8)
    plain = _make_module()
    _train(plain, batches)
    mod = _make_module()
    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=2,
                                    keep=2, async_=async_)
    mgr.attach(mod)
    for b in batches:
        mod.forward_backward(b)
        mod.update()
        mgr.step_end()
    assert mgr.wait(10)
    mgr.close()
    assert elastic.list_checkpoints(str(tmp_path))
    _assert_params_equal(plain, mod)


def test_write_failure_keeps_training_alive(tmp_path, monkeypatch):
    profiler.clear()
    mod = _make_module()
    mgr = elastic.CheckpointManager(str(tmp_path), async_=True)
    mgr.attach(mod)
    monkeypatch.setenv('MXNET_TPU_FAULT_WRITE_FAIL', '1')
    mgr._step = 1
    mgr.save()
    assert mgr.wait(10)
    monkeypatch.delenv('MXNET_TPU_FAULT_WRITE_FAIL')
    assert profiler.ckpt_stats()['ckpt_failed_writes'] == 1
    # training continues; the next checkpoint lands fine
    _train(mod, _batches(1))
    mgr._step = 2
    mgr.save(sync=True)
    assert elastic.load_newest_intact(str(tmp_path))[0]['step'] == 2
    mgr.close()


def test_torn_checkpoint_falls_back_and_retention(tmp_path,
                                                  monkeypatch):
    profiler.clear()
    mod = _make_module()
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False, keep=2)
    mgr.attach(mod)
    for s in (1, 2):
        mgr._step = s
        mgr.save(sync=True)
    monkeypatch.setenv('MXNET_TPU_FAULT_TORN_CKPT', '1')
    mgr._step = 3
    mgr.save(sync=True)
    monkeypatch.delenv('MXNET_TPU_FAULT_TORN_CKPT')
    # keep=2 retention pruned step-1; newest (3) is torn -> fall back
    # to 2
    assert elastic.list_checkpoints(str(tmp_path)) == [3, 2]
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None and res[0]['step'] == 2
    assert profiler.ckpt_stats()['ckpt_torn_fallbacks'] >= 1
    # restore() (not just load) also lands on the intact one
    other = _make_module(seed=9)
    info = elastic.resume(elastic.CheckpointManager(str(tmp_path)),
                          other)
    assert info is not None and info.step == 2
    # a SIGKILL mid-write leaves a manifest-less orphan dir: retention
    # reaps it (it can never become valid) once it is older than the
    # newest real checkpoint
    orphan = tmp_path / 'step-00000001'
    orphan.mkdir()
    (orphan / 'state-r00000.bin.tmpdead').write_bytes(b'partial')
    mgr._step = 4
    mgr.save(sync=True)
    assert not orphan.exists()
    assert elastic.load_newest_intact(str(tmp_path))[0]['step'] == 4


def test_dead_virtual_host_and_kvstore_facade(tmp_path, monkeypatch):
    # a ZeRO run's shards are UNIQUE state: withholding a dead host's
    # file makes that checkpoint incomplete and resume falls back
    mod = _make_module(ndev=4, zero=1)
    _train(mod, _batches(1))
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False,
                                    world=2)
    mgr.attach(mod)
    mgr._step = 1
    mgr.save(sync=True)
    monkeypatch.setenv('MXNET_TPU_FAULT_DEAD_HOST', '1')
    mgr._step = 2
    mgr.save(sync=True)
    res = elastic.load_newest_intact(str(tmp_path))
    assert res is not None and res[0]['step'] == 1
    # the KVStore facade reports the dead node honestly and the
    # barrier fails fast instead of hanging the collective
    kv = mx.kvstore.create('local')
    assert kv.num_dead_node == 1
    with pytest.raises(MXNetError, match='dead node'):
        kv.barrier()
    monkeypatch.delenv('MXNET_TPU_FAULT_DEAD_HOST')
    assert kv.num_dead_node == 0
    kv.barrier()


def test_restore_falls_back_past_bucket_incomplete_checkpoint(tmp_path):
    """A live-only FINAL checkpoint (dist runtime: a peer died, the
    survivors' manifest lists only their files) can validate file-by-
    file while a dead rank's unique ZeRO shards are simply gone.
    restore() must assemble-validate the optimizer BEFORE mutating the
    target and fall back to the older complete checkpoint — not crash
    the resume with 'checkpoint bucket incomplete'."""
    import shutil
    profiler.clear()
    mod = _make_module(ndev=4, zero=1)
    _train(mod, _batches(1))
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False,
                                    world=2)
    mgr.attach(mod)
    mgr._step = 1
    mgr.save(sync=True)
    _train(mod, _batches(1, seed=1))
    mgr._step = 2
    mgr.save(sync=True)
    # simulate the live-only commit: rank 1's shard never landed and
    # the manifest lists only rank 0's file (all listed files intact)
    newest = os.path.join(str(tmp_path), 'step-%08d' % 2)
    os.unlink(os.path.join(newest, 'state-r00001.bin'))
    mpath = os.path.join(newest, elastic._MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    manifest['files'] = ['state-r00000.bin']
    with open(mpath, 'w') as f:
        json.dump(manifest, f)
    ref = os.path.join(str(tmp_path), 'ref')
    shutil.copytree(os.path.join(str(tmp_path), 'step-%08d' % 1),
                    os.path.join(ref, 'step-%08d' % 1))
    other = _make_module(seed=9, ndev=4, zero=1)
    info = elastic.CheckpointManager(str(tmp_path),
                                     world=2).attach(other).restore()
    assert info is not None and info.step == 1
    assert profiler.ckpt_stats()['ckpt_torn_fallbacks'] >= 1
    # ...and the state it applied is exactly the step-1 checkpoint's
    twin = _make_module(seed=11, ndev=4, zero=1)
    elastic.CheckpointManager(ref, world=2).attach(twin).restore()
    _assert_params_equal(other, twin)
    mgr.close()


# ---------------------------------------------------------------------------
# gluon fused wiring
# ---------------------------------------------------------------------------

def _gluon_run(ckpt=None, start=0, upto=8):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(2)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation='relu'), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), 'sgd',
                       {'learning_rate': 0.1, 'momentum': 0.9})
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    fused = gluon.fuse_step(net, loss, tr, checkpoint=ckpt)
    rng = np.random.RandomState(0)
    xs = [mx.nd.array(rng.rand(8, 6).astype(np.float32))
          for _ in range(8)]
    ys = [mx.nd.array((rng.rand(8) * 4).astype(np.float32))
          for _ in range(8)]
    for i in range(start, upto):
        fused(xs[i], ys[i])
    return net


def test_gluon_fused_checkpoint_resume(tmp_path):
    net_a = _gluon_run()

    mgr = elastic.CheckpointManager(str(tmp_path), every_n_steps=4,
                                    async_=False)
    _gluon_run(ckpt=mgr, upto=4)   # cadence fires at step 4
    mgr.close()
    assert elastic.list_checkpoints(str(tmp_path)) == [4]

    mgr2 = elastic.CheckpointManager(str(tmp_path))
    net_c = _gluon_run(ckpt=mgr2, start=4)
    assert mgr2.last_resume is not None and mgr2.last_resume.step == 4
    # re-created nets carry different auto-prefixes: compare by the
    # positional order the checkpoint itself uses
    pa = [v.data().asnumpy() for v in net_a.collect_params().values()]
    pc = [v.data().asnumpy() for v in net_c.collect_params().values()]
    assert len(pa) == len(pc)
    for i, (a, c) in enumerate(zip(pa, pc)):
        np.testing.assert_array_equal(a, c, err_msg=str(i))
    mgr2.close()


# ---------------------------------------------------------------------------
# data pipeline: fast-forward + worker-error satellites
# ---------------------------------------------------------------------------

def test_fast_forward_ndarray_iter_matches_drain():
    it_a = _fit_iter()
    for _ in range(3):
        next(it_a)
    b_ref = next(it_a)
    it_b = _fit_iter()
    assert elastic.fast_forward(it_b, batches=3, batch_size=8) == 3
    b = next(it_b)
    np.testing.assert_array_equal(b.data[0].asnumpy(),
                                  b_ref.data[0].asnumpy())


def test_fast_forward_imageiter_positional(tmp_path):
    from mxnet_tpu import image, recordio
    import cv2
    prefix = str(tmp_path / 'ff')
    rec = recordio.MXIndexedRecordIO(prefix + '.idx', prefix + '.rec',
                                     'w')
    rng = np.random.RandomState(0)
    for i in range(16):
        ok, buf = cv2.imencode('.png', rng.randint(
            0, 255, (12, 12, 3)).astype(np.uint8))
        assert ok
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.tobytes()))
    rec.close()

    def make():
        return image.ImageIter(batch_size=4, data_shape=(3, 12, 12),
                               path_imgrec=prefix + '.rec',
                               preprocess_threads=2)
    ref = make()
    for _ in range(2):
        ref.next()
    b_ref = ref.next()
    ref.close()
    ff = make()
    elastic.fast_forward(ff, batches=2, batch_size=4)
    b = ff.next()   # positional jump, no re-decode of skipped batches
    np.testing.assert_array_equal(b.data[0].asnumpy(),
                                  b_ref.data[0].asnumpy())
    np.testing.assert_array_equal(b.label[0].asnumpy(),
                                  b_ref.label[0].asnumpy())
    ff.close()


def test_worker_error_carries_record_position(tmp_path):
    from mxnet_tpu import image, recordio
    import cv2
    prefix = str(tmp_path / 'bad')
    rec = recordio.MXIndexedRecordIO(prefix + '.idx', prefix + '.rec',
                                     'w')
    rng = np.random.RandomState(0)
    for i in range(10):
        if i == 6:
            payload = b'definitely not an image'
        else:
            ok, buf = cv2.imencode('.png', rng.randint(
                0, 255, (12, 12, 3)).astype(np.uint8))
            assert ok
            payload = buf.tobytes()
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), payload))
    rec.close()
    it = image.ImageIter(batch_size=4, data_shape=(3, 12, 12),
                         path_imgrec=prefix + '.rec',
                         preprocess_threads=3)
    with pytest.raises(MXNetError) as excinfo:
        for _ in range(3):
            it.next()
    err = excinfo.value
    assert err.record_key == 6 and err.position == 6
    assert 'key=6' in str(err) and 'position 6' in str(err)
    assert err.__cause__ is not None
    # close() after the worker error still joins the pool cleanly and
    # the iterator stays usable (restarts from the watermark)
    it.close()
    import threading
    assert not [t for t in threading.enumerate()
                if 'decode' in t.name and t.is_alive()]
    it.reset()
    assert it.next().data[0].shape == (4, 3, 12, 12)
    it.close()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_ckpt_counters_in_summary_and_dump(tmp_path):
    profiler.clear()
    mod = _make_module()
    mgr = elastic.CheckpointManager(str(tmp_path), async_=False)
    mgr.attach(mod)
    mgr._step = 1
    mgr.save(sync=True)
    other = _make_module(seed=9)
    elastic.resume(elastic.CheckpointManager(str(tmp_path)), other)
    text = profiler.summary(print_out=False)
    assert 'ckpt_snapshots=1' in text
    assert 'ckpt_restores=1' in text
    fname = str(tmp_path / 'prof.json')
    profiler.profiler_set_config(mode='symbolic', filename=fname)
    path = profiler.dump_profile()
    meta = [e for e in json.load(open(path))['traceEvents']
            if e.get('name') == 'checkpoint']
    assert meta and meta[0]['args']['ckpt_snapshots'] == 1


def test_metric_state_roundtrip_composite():
    from mxnet_tpu import metric as metric_mod
    comp = metric_mod.CompositeEvalMetric(
        [metric_mod.Accuracy(), metric_mod.MSE()])
    comp.metrics[0].sum_metric = 7.0
    comp.metrics[0].num_inst = 9
    comp.metrics[1].sum_metric = 1.5
    comp.metrics[1].num_inst = 3
    state = elastic._metric_state(comp)
    comp2 = metric_mod.CompositeEvalMetric(
        [type(comp.metrics[0])(), type(comp.metrics[1])()])
    elastic._restore_metric(comp2, state)
    assert comp2.metrics[0].get() == comp.metrics[0].get()
    assert comp2.metrics[1].get() == comp.metrics[1].get()


# ---------------------------------------------------------------------------
# subprocess fit worker (test_fit_sigkill_subprocess_resume)
# ---------------------------------------------------------------------------

def _fit_worker(ckdir, out_path):
    """Child: fit 2 epochs with a 2-step checkpoint cadence; under
    MXNET_TPU_FAULT_KILL_AT_STEP the manager SIGKILLs mid-epoch.  On a
    clean finish, dump the final params for the parent's parity
    check.  Steps are PACED (a real model's step is ms-to-100ms of
    device work; this toy step is ~free, and an unpaced SIGKILL would
    land before the async writer ever commits a cadence
    checkpoint)."""
    import time as _time
    mod = mx.mod.Module(_mlp_symbol())
    mgr = elastic.CheckpointManager(ckdir, every_n_steps=2)
    _fit(mod, ckpt=mgr, cb=lambda param: _time.sleep(0.08))
    if mgr.last_resume is not None:
        print('RESUMED step=%d' % mgr.last_resume.step)
    params, auxs = mod.get_params()
    np.savez(out_path, **{n: v.asnumpy() for n, v in params.items()})
    mgr.close()
    print('FIT_WORKER_DONE')


if __name__ == '__main__':
    if len(sys.argv) >= 4 and sys.argv[1] == 'fit-worker':
        _fit_worker(sys.argv[2], sys.argv[3])
    else:
        raise SystemExit('usage: test_elastic.py fit-worker <ckdir> '
                         '<out.npz>')
