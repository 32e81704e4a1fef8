"""Cross-host gradient transport topologies (mxnet_tpu/dist.py ring
reduce-scatter + all-gather, async overlap handles, sparse COO wire;
kvstore.py mark_sparse rows-only application; tools/launch.py ring
port contract).

The invariants under test, per ISSUE 20:
  * every rank decodes IDENTICAL bytes per topology mode (the PR 9/13
    bitwise-determinism contract), and at world 2 the ring's rotation
    order coincides with the star's rank order, so the two topologies
    agree bitwise there;
  * int8/bf16 WireCodec composition rides per-chunk on the ring
    (MXNET_TPU_DIST_WIRE_DTYPE composes unchanged) with integer
    arrays kept exact;
  * dead/stalled peers are NAMED in the error, never a hang;
  * sparse COO rounds match the densified dense-wire result;
  * async handles overlap the round with local work (dist_overlap_ms)
    without changing the summed bytes;
  * per-topology tx/rx byte counters split star/ring/sparse.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import dist, elastic, profiler
from mxnet_tpu import ndarray as nd
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LAUNCH = os.path.join(_REPO, 'tools', 'launch.py')
_DIST_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            'test_dist_runtime.py')


def _pair(dead_after=30.0, hb=0.1, world=2):
    coord = dist.Coordinator(port=0, world=world,
                             bind_addr='127.0.0.1',
                             dead_after=dead_after).start()
    rts = [None] * world
    errs = [None] * world

    def mk(r):
        try:
            rts[r] = dist.DistRuntime(
                r, world, address='127.0.0.1', port=coord.port,
                start_coordinator=False, timeout=15,
                hb_interval=hb, dead_after=dead_after)
        except BaseException as e:
            errs[r] = e
    ts = [threading.Thread(target=mk, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(e is None for e in errs), errs
    return coord, rts


def _teardown(coord, rts):
    for rt in reversed(rts):
        if rt is not None:
            rt.shutdown()
    coord.stop()


def _all_ranks(rts, fn, timeout=40):
    """Run fn(rank) on every runtime concurrently; surface errors."""
    out = [None] * len(rts)
    errs = []

    def go(r):
        try:
            out[r] = fn(r)
        except BaseException as e:
            errs.append((r, e))
    ts = [threading.Thread(target=go, args=(r,))
          for r in range(len(rts))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
    return out, errs


def _contrib(r):
    return [np.arange(11, dtype=np.float32) * (r + 1) / 3.0,
            np.full((3, 5), r + 0.25, np.float32),
            np.arange(4, dtype=np.int64) * (r + 1)]


# ---------------------------------------------------------------------------
# topology knob
# ---------------------------------------------------------------------------

def test_topology_knob(monkeypatch):
    monkeypatch.delenv('MXNET_TPU_DIST_TOPOLOGY', raising=False)
    assert dist.topology_from_env() == 'star'
    monkeypatch.setenv('MXNET_TPU_DIST_TOPOLOGY', 'ring')
    assert dist.topology_from_env() == 'ring'
    assert dist.topology_from_env('star') == 'star'   # explicit wins
    with pytest.raises(MXNetError, match='topology'):
        dist.topology_from_env('mesh')
    # the ring stall knob falls back to the barrier stall knob (one
    # injection covers both collective shapes)
    monkeypatch.setenv('MXNET_TPU_FAULT_BARRIER_STALL_S', '1:0.4')
    assert elastic.ring_stall_s(1) == 0.4
    assert elastic.ring_stall_s(0) is None
    monkeypatch.setenv('MXNET_TPU_FAULT_RING_STALL_S', '0:0.2')
    assert elastic.ring_stall_s(0) == 0.2
    assert elastic.ring_stall_s(1) is None


# ---------------------------------------------------------------------------
# ring allreduce: bitwise parity + counters
# ---------------------------------------------------------------------------

def test_ring_matches_star_bitwise_at_world2():
    profiler.clear()

    def round_of(topo):
        coord, rts = _pair()
        try:
            out, errs = _all_ranks(
                rts, lambda r: rts[r].allreduce(
                    _contrib(r), name='g', topology=topo, timeout=20))
            assert not errs, errs
            for a, b in zip(out[0], out[1]):
                assert a.dtype == b.dtype
                assert a.tobytes() == b.tobytes()   # identical bytes
            return out[0]
        finally:
            _teardown(coord, rts)

    star = round_of('star')
    ring = round_of('ring')
    # world 2: rank order == rotation order, so the topologies agree
    # BITWISE (IEEE addition is commutative; it is associativity that
    # breaks at world >= 3)
    for a, b in zip(star, ring):
        assert a.tobytes() == b.tobytes()
    np.testing.assert_array_equal(ring[2],
                                  np.arange(4, dtype=np.int64) * 3)
    st = profiler.dist_stats()
    assert st['dist_star_bytes'] > 0 and st['dist_ring_bytes'] > 0
    assert st['dist_tx_bytes'] > 0 and st['dist_rx_bytes'] > 0
    assert st['dist_allreduce_bytes'] == \
        st['dist_tx_bytes'] + st['dist_rx_bytes']
    text = profiler.summary(print_out=False)
    assert 'dist_tx_bytes=' in text and 'dist_ring_bytes=' in text


def test_ring_world3_identical_bytes_and_correct_sums():
    coord, rts = _pair(world=3)
    try:
        for rnd in range(2):     # round 2 reuses the built links
            out, errs = _all_ranks(
                rts, lambda r: rts[r].allreduce(
                    _contrib(r), name='g', topology='ring',
                    timeout=20))
            assert not errs, errs
            for r in (1, 2):
                for a, b in zip(out[0], out[r]):
                    assert a.tobytes() == b.tobytes()
            expect = [np.sum([np.asarray(c, np.float64) for c in cols],
                             axis=0)
                      for cols in zip(*[_contrib(r) for r in range(3)])]
            for got, want in zip(out[0], expect):
                np.testing.assert_allclose(
                    np.asarray(got, np.float64), want, rtol=1e-5)
    finally:
        _teardown(coord, rts)


def test_ring_int8_wire_composes():
    profiler.clear()
    coord, rts = _pair()
    try:
        out, errs = _all_ranks(
            rts, lambda r: rts[r].allreduce(
                _contrib(r), name='g8', topology='ring', wire='int8',
                timeout=20))
        assert not errs, errs
        for a, b in zip(out[0], out[1]):
            assert a.tobytes() == b.tobytes()
        # integer groups ride the ring RAW — exact even on the
        # compressed wire (the star path quantizes them)
        np.testing.assert_array_equal(out[0][2],
                                      np.arange(4, dtype=np.int64) * 3)
        exact = np.arange(11, dtype=np.float64) * (1 + 2) / 3.0
        np.testing.assert_allclose(np.asarray(out[0][0], np.float64),
                                   exact, atol=0.5)
        # compressed hops move fewer bytes than fp32 hops would
        qs = profiler.quant_stats()
        assert qs['quant_wire_bytes_saved'] > 0
    finally:
        _teardown(coord, rts)


# ---------------------------------------------------------------------------
# failure paths: stalled / dead peers NAMED
# ---------------------------------------------------------------------------

def test_ring_stalled_peer_names_rank(monkeypatch):
    coord, rts = _pair()
    try:
        out, errs = _all_ranks(       # round 1 builds the links
            rts, lambda r: rts[r].allreduce(
                [np.ones(6, np.float32)], name='w', topology='ring',
                timeout=20))
        assert not errs, errs
        # rank 1 stalls 3s at round entry; rank 0's 1s deadline must
        # convert the silence into an error NAMING rank 1 (its left
        # neighbor on a 2-ring), never a hang
        monkeypatch.setenv('MXNET_TPU_FAULT_RING_STALL_S', '1:3')
        res = {}

        def go(r):
            try:
                res[r] = rts[r].allreduce(
                    [np.ones(6, np.float32)], name='w',
                    topology='ring', timeout=1.0)
            except MXNetError as e:
                res[r] = e
        ts = [threading.Thread(target=go, args=(r,)) for r in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(20)
        assert isinstance(res[0], MXNetError), res
        msg = str(res[0])
        assert 'rank 1' in msg and 'stalled or dead' in msg
    finally:
        monkeypatch.delenv('MXNET_TPU_FAULT_RING_STALL_S',
                           raising=False)
        _teardown(coord, rts)


def test_ring_dead_peer_names_rank(monkeypatch):
    coord, rts = _pair(dead_after=0.5)
    try:
        out, errs = _all_ranks(
            rts, lambda r: rts[r].allreduce(
                [np.ones(6, np.float32)], name='w', topology='ring',
                timeout=20))
        assert not errs, errs
        # rank 1 goes silent (injected partition): rank 0's next ring
        # round sees the heartbeat-declared death and fails fast
        # naming the dead set
        monkeypatch.setenv('MXNET_TPU_FAULT_HEARTBEAT_DROP', '1')
        with pytest.raises(MXNetError, match=r'\[1\]'):
            rts[0].allreduce([np.ones(6, np.float32)], name='w',
                             topology='ring', timeout=15)
    finally:
        _teardown(coord, rts)


# ---------------------------------------------------------------------------
# sparse COO wire
# ---------------------------------------------------------------------------

def _coo_contrib(world, vocab=50, dim=4, n=12):
    rngs = [np.random.RandomState(7 + r) for r in range(world)]
    return [(rngs[r].randint(0, vocab, n),
             rngs[r].randn(n, dim).astype(np.float32))
            for r in range(world)]


@pytest.mark.parametrize('world,topo', [(2, 'star'), (2, 'ring'),
                                        (3, 'ring')])
def test_coo_allreduce_parity_vs_densified(world, topo):
    profiler.clear()
    VOCAB, DIM = 50, 4
    contrib = _coo_contrib(world, VOCAB, DIM)
    coord, rts = _pair(world=world)
    try:
        out, errs = _all_ranks(
            rts, lambda r: rts[r].allreduce_coo(
                contrib[r][0], contrib[r][1], name='e', vocab=VOCAB,
                topology=topo, timeout=20))
        assert not errs, errs
        for r in range(1, world):
            assert out[r][0].tobytes() == out[0][0].tobytes()
            assert out[r][1].tobytes() == out[0][1].tobytes()
        dense = np.zeros((VOCAB, DIM), np.float64)
        for ids, rows in contrib:
            np.add.at(dense, ids, rows.astype(np.float64))
        uids, rows = out[0]
        assert np.all(np.diff(uids) > 0)        # sorted unique ids
        got = np.zeros((VOCAB, DIM), np.float64)
        got[uids] = rows
        np.testing.assert_allclose(got, dense, atol=1e-5)
        assert profiler.dist_stats()['dist_sparse_bytes'] > 0
    finally:
        _teardown(coord, rts)


def test_rank0_ingress_and_coo_wire_bytes():
    """The transports' gains as byte counts, three ranks: under the
    star every rank's upload lands in rank 0's process (the
    coordinator's), under the ring a rank receives its left peer's
    chunks only, so rank 0's ingress falls by at least (world - 1) / 2;
    an embedding gradient of 64 touched rows in 4,096 crosses as COO
    in at least 10x fewer bytes than densified."""
    world, vocab, dim = 3, 4096, 16
    grads = []
    for r in range(world):
        rng = np.random.RandomState(500 + r)
        g = np.zeros((vocab, dim), np.float32)
        g[rng.randint(0, vocab, 64)] = rng.randn(64, dim)
        grads.append(g)
    coord, rts = _pair(world=world)
    try:
        def moved(counters, fn):
            before = profiler.dist_stats()
            _, errs = _all_ranks(rts, fn)
            assert not errs, errs
            after = profiler.dist_stats()
            return sum(after[c] - before[c] for c in counters)

        star = moved(['dist_tx_bytes'], lambda r: rts[r].allreduce(
            [grads[r]], name='d', topology='star', timeout=20))
        ring = moved(['dist_rx_bytes'], lambda r: rts[r].allreduce(
            [grads[r]], name='d', topology='ring', timeout=20)) / world
        assert star >= (world - 1) / 2.0 * ring > 0

        def coo(r):
            nz = np.flatnonzero(np.any(grads[r] != 0.0, axis=1))
            return rts[r].allreduce_coo(nz, grads[r][nz], name='e',
                                        vocab=vocab, topology='ring',
                                        timeout=20)
        both = ['dist_tx_bytes', 'dist_rx_bytes']
        dense = moved(both, lambda r: rts[r].allreduce(
            [grads[r]], name='d', topology='ring', timeout=20))
        assert dense >= 10 * moved(both, coo)
    finally:
        _teardown(coord, rts)


def test_coo_requires_vocab_on_ring_and_dedups_locally():
    coord, rts = _pair(world=2)
    try:
        # the ring chunks the id space — without a vocab bound there
        # is no chunking, and the error must say so before any peer
        # traffic happens
        with pytest.raises(MXNetError, match='vocab'):
            rts[0].allreduce_coo(np.arange(3),
                                 np.ones((3, 2), np.float32),
                                 topology='ring')
    finally:
        _teardown(coord, rts)
    # before initialize(): identity plus local dedup + sort
    ids, rows = dist.allreduce_coo(
        np.array([5, 2, 5]), np.ones((3, 2), np.float32))
    np.testing.assert_array_equal(ids, [2, 5])
    np.testing.assert_allclose(rows, [[1, 1], [2, 2]])


# ---------------------------------------------------------------------------
# async overlap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('topo', ['star', 'ring'])
def test_allreduce_async_parity_and_overlap_gauge(topo):
    profiler.clear()
    coord, rts = _pair()
    try:
        def go(r):
            hs = [rts[r].allreduce_async(
                [np.full(8, (r + 1) * (i + 1), np.float32)],
                name='k%d' % i, topology=topo) for i in range(4)]
            time.sleep(0.05)          # the "local optimizer work"
            return [h.wait(20) for h in hs]
        out, errs = _all_ranks(rts, go)
        assert not errs, errs
        for i in range(4):
            # per-key rounds sum in rank order: bitwise equal to the
            # same sum computed directly
            want = np.full(8, 3.0 * (i + 1), np.float32)
            assert out[0][i][0].tobytes() == want.tobytes()
            assert out[1][i][0].tobytes() == want.tobytes()
        assert profiler.dist_stats()['dist_overlap_ms'] > 0
    finally:
        _teardown(coord, rts)


# ---------------------------------------------------------------------------
# kvstore: mark_sparse rows-only application + overlap mode
# ---------------------------------------------------------------------------

def _kv_with_runtime(monkeypatch, rt, sparse, overlap=False):
    monkeypatch.setattr(dist, '_RUNTIME', rt)
    if overlap:
        monkeypatch.setenv('MXNET_TPU_DIST_OVERLAP', '1')
    else:
        monkeypatch.delenv('MXNET_TPU_DIST_OVERLAP', raising=False)
    kv = mx.kvstore.KVStore('dist_sync')
    opt = opt_mod.SGD(learning_rate=0.1, momentum=0.9)
    kv.set_optimizer(opt)
    return kv


def test_kvstore_sparse_coo_matches_densified(monkeypatch):
    """The rows-only sparse application must land on the same weights
    as the dense wire + dense updater when the same rows are touched
    (fresh momentum state — the lazy-semantics caveat in
    docs/SPARSE.md only appears once an UNtouched row has nonzero
    momentum)."""
    VOCAB, DIM = 10, 3
    w0 = np.random.RandomState(0).randn(VOCAB, DIM).astype(np.float32)
    grad = np.zeros((VOCAB, DIM), np.float32)
    grad[[1, 4, 7]] = np.random.RandomState(1).randn(3, DIM)
    coord, rts = _pair(world=1)
    try:
        results = {}
        for mode in ('dense', 'sparse', 'sparse_overlap'):
            kv = _kv_with_runtime(monkeypatch, rts[0], mode,
                                  overlap=(mode == 'sparse_overlap'))
            kv.init('emb', nd.array(w0))
            if mode != 'dense':
                kv.mark_sparse('emb', VOCAB)
            out = nd.array(w0)
            for _ in range(2):       # same rows touched both steps
                kv.push_pull_all(['emb'], [nd.array(grad)], [out])
            results[mode] = out.asnumpy()
        np.testing.assert_allclose(results['sparse'],
                                   results['dense'], atol=1e-5)
        np.testing.assert_allclose(results['sparse_overlap'],
                                   results['dense'], atol=1e-5)
        # untouched rows never move
        np.testing.assert_array_equal(results['sparse'][0], w0[0])
    finally:
        _teardown(coord, rts)


def test_kvstore_overlap_dense_matches_batched(monkeypatch):
    w0 = np.random.RandomState(3).randn(6, 4).astype(np.float32)
    grad = np.random.RandomState(4).randn(6, 4).astype(np.float32)
    coord, rts = _pair(world=1)
    try:
        outs = {}
        for overlap in (False, True):
            kv = _kv_with_runtime(monkeypatch, rts[0], 'd',
                                  overlap=overlap)
            kv.init('fc', nd.array(w0))
            out = nd.array(w0)
            kv.push_pull_all(['fc'], [nd.array(grad)], [out])
            outs[overlap] = out.asnumpy()
        np.testing.assert_array_equal(outs[False], outs[True])
    finally:
        _teardown(coord, rts)


# ---------------------------------------------------------------------------
# launcher contract + E2E kill-resume under ring
# ---------------------------------------------------------------------------

def test_launch_exports_ring_port_contract(tmp_path):
    prog = ("import os\n"
            "base = int(os.environ['MXNET_TPU_DIST_RING_PORT'])\n"
            "dist = int(os.environ['MXNET_TPU_DIST_PORT'])\n"
            "assert base == dist + 2, (base, dist)\n"
            "print('RINGPORT_OK', base)\n")
    script = tmp_path / 'w.py'
    script.write_text(prog)
    env = dict(os.environ, PYTHONPATH=_REPO + os.pathsep +
               os.environ.get('PYTHONPATH', ''))
    for stale in ('DMLC_PS_ROOT_URI', 'DMLC_PS_ROOT_PORT', 'DMLC_ROLE',
                  'DMLC_NUM_WORKER', 'DMLC_NUM_SERVER',
                  'MXNET_TPU_DIST_PORT', 'MXNET_TPU_DIST_RING_PORT'):
        env.pop(stale, None)
    proc = subprocess.run(
        [sys.executable, _LAUNCH, '-n', '2', '-s', '0',
         '--launcher', 'local', sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert proc.stdout.count('RINGPORT_OK') == 2, proc.stdout


@pytest.mark.slow
def test_ring_kill_one_of_two_workers_coordinated_restart(tmp_path):
    """slow (~35s): the star-topology twin
    (test_kill_one_of_two_workers_coordinated_restart) plus the ring
    pieces stay tier-1 via test_ring_matches_star_bitwise_at_world2 /
    test_ring_dead_peer_names_rank (ring transport + death naming)
    and test_launch_exports_ring_port_contract (port contract).

    End to end under MXNET_TPU_DIST_TOPOLOGY=ring: launcher-spawned
    workers form the peer ring from the exported port contract,
    SIGKILL of rank 1 mid-epoch surfaces as a named ring/death error,
    the survivor commits a final checkpoint + exits PREEMPTED_EXIT,
    the --elastic supervisor relaunches shrunk, and the final weights
    are BIT-IDENTICAL to the uninterrupted run."""
    def run(tag, n, elastic_mode=False, **fault):
        env = dict(os.environ,
                   PYTHONPATH=_REPO + os.pathsep +
                   os.environ.get('PYTHONPATH', ''))
        for stale in ('DMLC_PS_ROOT_URI', 'DMLC_PS_ROOT_PORT',
                      'DMLC_ROLE', 'DMLC_NUM_WORKER',
                      'DMLC_NUM_SERVER', 'MXNET_TPU_DIST_PORT',
                      'MXNET_TPU_DIST_RING_PORT'):
            env.pop(stale, None)
        env.update({'MXNET_TPU_DIST_HEARTBEAT_S': '0.1',
                    'MXNET_TPU_DIST_DEAD_AFTER_S': '0.8',
                    'MXNET_TPU_BARRIER_TIMEOUT_S': '30',
                    'MXNET_TPU_DIST_TOPOLOGY': 'ring',
                    'JAX_PLATFORMS': 'cpu'})
        env.update({k: str(v) for k, v in fault.items()})
        cmd = [sys.executable, _LAUNCH, '-n', str(n), '-s', '0',
               '--launcher', 'local']
        if elastic_mode:
            cmd += ['--elastic', '--elastic-shrink', '--max-restarts',
                    '2', '--elastic-grace', '30']
        cmd += [sys.executable, _DIST_WORKER, 'dist-worker',
                str(tmp_path), tag]
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=300)

    proc = run('rstraight', 1)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    proc = run('relastic', 2, elastic_mode=True,
               MXNET_TPU_FAULT_KILL_AT_STEP='5',
               MXNET_TPU_FAULT_KILL_RANK='1')
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert 'PREEMPTED' in proc.stdout and 'dead_ranks=[1]' in \
        proc.stdout, (proc.stdout, proc.stderr)
    assert 'RESUMED step=' in proc.stdout, proc.stdout
    a = np.load(str(tmp_path / 'params_rstraight_r0.npz'))
    b = np.load(str(tmp_path / 'params_relastic_r0.npz'))
    assert sorted(a.files) == sorted(b.files)
    for name in a.files:
        np.testing.assert_array_equal(a[name], b[name], err_msg=name)
