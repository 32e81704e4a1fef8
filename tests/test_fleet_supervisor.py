"""Self-healing fleet tests (fleet_supervisor: ReplicaServer +
FleetRouter + FleetSupervisor + canary/shadow deployment).

Covers the ISSUE-11 contract: the replica-death window (requests in
flight when a replica dies either complete via retry on a survivor or
fail typed within the SLO deadline — never hang, never double-execute
a non-idempotent submit), fast 503s from a fully-dead fleet, the
Retry-After-honoring client helper, canary auto-rollback under the
injected degrade knob / auto-promote when healthy, shadow-replay
divergence counting, the wedge/kill fault knobs, the pure ScalePolicy
hysteresis, replica admin load/unload ops, and the fleet_supervisor_*
profiler family.

The precise fault shapes (connection refused vs connection dropped
after delivery) run against in-process raw-socket stubs and in-process
ReplicaServers — these are the fast tier-1 behavior-keepers for the
end-to-end subprocess drill (test_supervisor_sigkill_respawn_e2e here),
which spawns real replica processes and SIGKILLs one mid-load.
"""
import json
import signal
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import model as model_mod, nd, profiler, sym
from mxnet_tpu import fleet_supervisor as fs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.fleet_supervisor import (FleetRouter, FleetSupervisor,
                                        ReplicaServer, ScalePolicy,
                                        post_with_backoff)
from mxnet_tpu.predictor import Predictor

DIM = 6
HID = 8
OUT = 3


def _mlp():
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, num_hidden=HID, name='fc1')
    act = sym.Activation(fc1, act_type='relu')
    return sym.FullyConnected(act, num_hidden=OUT, name='fc2')


def _params(seed=7):
    rs = np.random.RandomState(seed)
    return {
        'fc1_weight': nd.array(rs.randn(HID, DIM).astype(np.float32) * .5),
        'fc1_bias': nd.array(rs.randn(HID).astype(np.float32) * .1),
        'fc2_weight': nd.array(rs.randn(OUT, HID).astype(np.float32) * .5),
        'fc2_bias': nd.array(rs.randn(OUT).astype(np.float32) * .1),
    }


def _loader(seed):
    return lambda: Predictor(symbol=_mlp(), arg_params=_params(seed),
                             input_shapes={'data': (1, DIM)})


def _spec(seed, name='m'):
    return {'name': name, 'loader': _loader(seed), 'max_batch': 4,
            'max_wait_us': 0}


def _x(rows=1, seed=0):
    return np.random.RandomState(seed).randn(rows, DIM).astype(
        np.float32)


def _post_router(router, name='m', seed=0, headers=None, timeout=30):
    host, port = router.address
    req = urllib.request.Request(
        'http://%s:%d/v1/models/%s:predict' % (host, port, name),
        data=json.dumps({'instances': _x(seed=seed).tolist()}).encode(),
        headers=dict({'Content-Type': 'application/json'},
                     **(headers or {})))
    return urllib.request.urlopen(req, timeout=timeout)


# ---------------------------------------------------------------------------
# raw-socket stub backends: precise fault shapes the router must handle
# ---------------------------------------------------------------------------

class _Stub(object):
    """Minimal raw HTTP backend with a scripted behavior per request
    (last entry repeats): 'ok' answers 200, 'drop' reads the full
    request then closes the connection WITHOUT replying (the crash-
    after-delivery shape), '429' answers the overload contract,
    'sleep' stalls 2s then answers (the wedged-service shape)."""

    def __init__(self, script=('ok',)):
        self.script = list(script)
        self.received = []
        self._lock = threading.Lock()
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(('127.0.0.1', 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self._closed = False
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while not self._closed:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        try:
            buf = b''
            while b'\r\n\r\n' not in buf:
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
            head, _, body = buf.partition(b'\r\n\r\n')
            n = 0
            for line in head.split(b'\r\n'):
                if line.lower().startswith(b'content-length:'):
                    n = int(line.split(b':', 1)[1])
            while len(body) < n:
                chunk = conn.recv(65536)
                if not chunk:
                    break
                body += chunk
            with self._lock:
                mode = self.script.pop(0) if len(self.script) > 1 \
                    else self.script[0]
                self.received.append(body)
            if mode == 'drop':
                conn.close()
                return
            if mode == 'sleep':
                time.sleep(2.0)
                mode = 'ok'
            if mode == '429':
                payload = (b'{"error": "overloaded", '
                           b'"retry_after_ms": 150}')
                status = b'429 Too Many Requests'
            else:
                payload = b'{"outputs": [[[1.0, 2.0, 3.0]]]}'
                status = b'200 OK'
            conn.sendall(
                b'HTTP/1.1 ' + status +
                b'\r\nContent-Type: application/json'
                b'\r\nContent-Length: ' + str(len(payload)).encode() +
                b'\r\nConnection: close\r\n\r\n' + payload)
            conn.close()
        except OSError:
            pass

    def n_received(self):
        with self._lock:
            return len(self.received)

    def close(self):
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


def _refused_port():
    """A port with no listener: connecting is refused instantly."""
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# client retry helper (satellite: Retry-After honor)
# ---------------------------------------------------------------------------

def test_post_with_backoff_honors_retry_after():
    stub = _Stub(script=['429', '429', 'ok'])
    try:
        t0 = time.monotonic()
        status, body = post_with_backoff(
            'http://127.0.0.1:%d/v1/models/m:predict' % stub.port,
            {'instances': [[0.0]]}, deadline_s=30)
        dt = time.monotonic() - t0
        assert status == 200 and 'outputs' in body
        assert stub.n_received() == 3       # two 429s then success
        # backed off per retry_after_ms=150 twice, not a hot loop
        assert dt >= 0.25
    finally:
        stub.close()


def test_post_with_backoff_deadline_is_bounded():
    port = _refused_port()
    t0 = time.monotonic()
    with pytest.raises(MXNetError, match='within'):
        post_with_backoff('http://127.0.0.1:%d/x' % port, {},
                          deadline_s=0.5)
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# router: the replica-death window
# ---------------------------------------------------------------------------

def test_router_retries_refused_replica_to_survivor():
    profiler.clear()
    ok = _Stub(script=['ok'])
    with FleetRouter(port=0) as router:
        router.start()
        # insertion [ok, dead]: round robin picks index 1 (dead) first
        router.add_backend('ok', '127.0.0.1', ok.port)
        router.add_backend('dead', '127.0.0.1', _refused_port())
        resp = _post_router(router)
        assert resp.status == 200
        assert json.loads(resp.read())['outputs']
        assert router.stats()['retries'] == 1
        assert ok.n_received() == 1
    ok.close()
    assert profiler.fleet_supervisor_stats()[
        'fleet_supervisor_router_retries'] >= 1


def test_router_replica_death_mid_request_retries_idempotent():
    # the stub that READS the request then drops the connection is the
    # replica-crashed-mid-request shape: the router redispatches the
    # (idempotent) predict to the survivor — the caller sees one clean
    # 200, within the deadline, no hang
    ok = _Stub(script=['ok'])
    dropper = _Stub(script=['drop'])
    with FleetRouter(port=0) as router:
        router.start()
        router.add_backend('ok', '127.0.0.1', ok.port)
        router.add_backend('dropper', '127.0.0.1', dropper.port)
        t0 = time.monotonic()
        resp = _post_router(router)
        assert resp.status == 200
        assert time.monotonic() - t0 < 10.0
        assert dropper.n_received() == 1    # delivered once
        assert ok.n_received() == 1         # retried to the survivor
        assert router.stats()['retries'] == 1
    ok.close()
    dropper.close()


def test_router_never_double_executes_non_idempotent():
    # same crash shape, but the request is marked non-idempotent: a
    # redispatch could double-execute it on the survivor, so the
    # router must fail typed instead — the survivor receives NOTHING
    ok = _Stub(script=['ok'])
    dropper = _Stub(script=['drop'])
    with FleetRouter(port=0) as router:
        router.start()
        router.add_backend('ok', '127.0.0.1', ok.port)
        router.add_backend('dropper', '127.0.0.1', dropper.port)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_router(router,
                         headers={'X-Mxtpu-Non-Idempotent': '1'})
        assert ei.value.code == 502
        body = json.loads(ei.value.read())
        assert body['retriable'] is False
        assert dropper.n_received() == 1
        assert ok.n_received() == 0         # never double-executed
        # a non-idempotent request that was NEVER DELIVERED (connect
        # refused) is still safe to redispatch
        router.remove_backend('dropper')
        router.add_backend('dead', '127.0.0.1', _refused_port())
        resp = _post_router(router,
                            headers={'X-Mxtpu-Non-Idempotent': '1'})
        assert resp.status == 200
        assert ok.n_received() == 1
    ok.close()
    dropper.close()


def test_router_dead_fleet_fast_503_and_deadline_bound():
    profiler.clear()
    with FleetRouter(port=0, deadlines={'m': 500.0}) as router:
        router.start()
        host, port = router.address
        # (1) zero backends: fast typed 503 + Retry-After, no hang
        t0 = time.monotonic()
        status, hdrs, body = fs._http_json(
            'POST', host, port, '/v1/models/m:predict',
            {'instances': _x().tolist()}, timeout=10)
        assert status == 503 and body['error'] == 'fleet unavailable'
        assert 'Retry-After' in hdrs
        assert time.monotonic() - t0 < 2.0
        # (2) every backend refused: exhausts the fleet fast
        router.add_backend('d1', '127.0.0.1', _refused_port())
        router.add_backend('d2', '127.0.0.1', _refused_port())
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/m:predict',
            {'instances': _x().tolist()}, timeout=10)
        assert status == 503
        # (3) a wedged replica: the 500ms SLO deadline bounds the
        # wait — typed 503 within ~the deadline, never a hang
        slow = _Stub(script=['sleep'])
        router.remove_backend('d1')
        router.remove_backend('d2')
        router.add_backend('slow', '127.0.0.1', slow.port)
        t0 = time.monotonic()
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/m:predict',
            {'instances': _x().tolist()}, timeout=10)
        dt = time.monotonic() - t0
        assert status == 503
        assert 0.4 <= dt < 1.9, dt          # deadline, not the 2s stall
        slow.close()
    assert profiler.fleet_supervisor_stats()[
        'fleet_supervisor_router_503'] >= 3


# ---------------------------------------------------------------------------
# canary / shadow deployment (in-process replicas)
# ---------------------------------------------------------------------------

def _two_replica_router(monkeypatch=None):
    r1 = ReplicaServer(models=[_spec(1)], index=0).start()
    r2 = ReplicaServer(models=[_spec(1)], index=1).start()
    router = FleetRouter(port=0).start()
    router.add_backend('r0', *r1.address)
    router.add_backend('r1', *r2.address)
    return r1, r2, router


def test_canary_auto_rollback_on_injected_degrade(monkeypatch):
    profiler.clear()
    monkeypatch.setenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS', '60')
    monkeypatch.setenv('MXNET_TPU_FLEET_CANARY_MIN_SAMPLES', '5')
    r1, r2, router = _two_replica_router()
    try:
        for r in (r1, r2):
            r.load_model('m@v1', _spec(2, name='m@v1'))
        router.start_canary('m', 'm@v1', frac=0.5)
        for i in range(40):
            assert _post_router(router, seed=i).status == 200
            if router.canary_report('m')['state'] != 'running':
                break
        rep = router.canary_report('m')
        assert rep['state'] == 'rolled_back'
        # medians, not p99s: a cold-start outlier in the small stable
        # window can push stable_p99 ABOVE the degraded candidate's —
        # exactly the case the median decision branch exists for
        assert rep['cand_p50_ms'] > rep['stable_p50_ms']
        assert router.stable_arm('m') == 'm'    # stable survived
        # traffic keeps flowing, all on the stable arm
        before = rep['cand_samples']
        for i in range(4):
            assert _post_router(router, seed=i).status == 200
        assert router.canary_report('m')['cand_samples'] == before
        # the candidate arm is unloaded from the replicas
        deadline = time.time() + 10
        while time.time() < deadline and any(
                'm@v1' in r.registry.models() for r in (r1, r2)):
            time.sleep(0.05)
        assert all('m@v1' not in r.registry.models()
                   for r in (r1, r2))
        st = router.statsz()
        assert st['fleet_supervisor'][
            'fleet_supervisor_canary_rollbacks'] >= 1
        assert st['canary']['m']['state'] == 'rolled_back'
    finally:
        router.close()
        r1.close()
        r2.close()


def test_canary_auto_promote_when_healthy(monkeypatch):
    monkeypatch.delenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS',
                       raising=False)
    monkeypatch.setenv('MXNET_TPU_FLEET_CANARY_MIN_SAMPLES', '4')
    monkeypatch.setenv('MXNET_TPU_FLEET_CANARY_PROMOTE_SAMPLES', '8')
    # identical arms: this test exercises the PROMOTE mechanics, so a
    # throttle spike in the tiny windows must not fake a regression
    monkeypatch.setenv('MXNET_TPU_FLEET_CANARY_REGRESS_FACTOR', '8')
    events = []
    r1, r2, router = _two_replica_router()
    router.on_event = lambda kind, name, info: events.append(
        (kind, name, info['candidate']))
    try:
        for r in (r1, r2):
            r.load_model('m@v1', _spec(1, name='m@v1'))
        router.start_canary('m', 'm@v1', frac=0.5)
        for i in range(60):
            assert _post_router(router, seed=i).status == 200
            if router.canary_report('m')['state'] != 'running':
                break
        assert router.canary_report('m')['state'] == 'promoted'
        assert router.stable_arm('m') == 'm@v1'
        assert events == [('promote', 'm', 'm@v1')]
        # public name still serves (now from the promoted arm), even
        # after the old stable registration is dropped
        deadline = time.time() + 10
        while time.time() < deadline and any(
                'm' in r.registry.models() for r in (r1, r2)):
            time.sleep(0.05)
        assert _post_router(router).status == 200
    finally:
        router.close()
        r1.close()
        r2.close()


def test_canary_served_nowhere_rolls_back_and_serves_stable(
        monkeypatch):
    """A candidate arm that NO replica serves (its loaders all died /
    never converged): clients still get 200s (the router falls back
    to the stable arm per request), the all-backends-404 misses
    accumulate as candidate failures, and the canary ROLLS BACK
    instead of staying pending forever (which would silently wedge
    the train->serve pusher)."""
    monkeypatch.delenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS',
                       raising=False)
    monkeypatch.setenv('MXNET_TPU_FLEET_CANARY_MIN_SAMPLES', '4')
    r1, r2, router = _two_replica_router()
    try:
        router.start_canary('m', 'm@ghost', frac=1.0)   # served nowhere
        for i in range(16):
            assert _post_router(router, seed=i).status == 200
            if router.canary_report('m')['state'] != 'running':
                break
        rep = router.canary_report('m')
        assert rep['state'] == 'rolled_back'
        assert rep['cand_err_frac'] == 1.0
        assert router.stable_arm('m') == 'm'
    finally:
        router.close()
        r1.close()
        r2.close()


def test_shadow_tee_counts_divergences(monkeypatch):
    profiler.clear()
    monkeypatch.delenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS',
                       raising=False)
    r1, r2, router = _two_replica_router()
    try:
        # identical weights -> zero divergence
        for r in (r1, r2):
            r.load_model('m@same', _spec(1, name='m@same'))
        router.start_canary('m', 'm@same', mode='shadow')
        for i in range(6):
            assert _post_router(router, seed=i).status == 200
        assert router.shadow_drain(timeout=30)
        rep = router.canary_report('m')
        assert rep['mode'] == 'shadow'
        assert rep['shadow_requests'] >= 6
        assert rep['shadow_divergences'] == 0
        assert rep['cand_samples'] == 0     # candidate never served
        # different weights -> every teed request diverges
        for r in (r1, r2):
            r.load_model('m@diff', _spec(2, name='m@diff'))
        router.start_canary('m', 'm@diff', mode='shadow')
        for i in range(6):
            assert _post_router(router, seed=i).status == 200
        assert router.shadow_drain(timeout=30)
        rep = router.canary_report('m')
        assert rep['shadow_divergences'] >= 5
        # replay of the logged bodies against an arm, on demand
        out = router.replay('m', arm='m@diff')
        assert out['replayed'] >= 6
        assert out['divergences'] == out['replayed']
        out = router.replay('m', arm='m@same')
        assert out['divergences'] == 0
        fsn = profiler.fleet_supervisor_stats()
        assert fsn['fleet_supervisor_shadow_requests'] >= 12
        assert fsn['fleet_supervisor_shadow_divergences'] >= 5
    finally:
        router.close()
        r1.close()
        r2.close()


# ---------------------------------------------------------------------------
# replica admin ops + fault knobs
# ---------------------------------------------------------------------------

def test_replica_admin_load_unload_roundtrip(tmp_path):
    prefix = str(tmp_path / 'admin_m')
    model_mod.save_checkpoint(prefix, 2, _mlp(), _params(9), {})
    with ReplicaServer(models=[], index=0) as rs:
        rs.start()
        host, port = rs.address
        spec = {'prefix': prefix, 'epoch': 2,
                'input_shapes': {'data': [1, DIM]},
                'max_batch': 4, 'max_wait_us': 0}
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/hot:load', spec)
        assert status == 200 and body['status'] == 'loaded'
        # idempotent re-load (a supervisor retry) is not an error
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/hot:load', spec)
        assert status == 200 and body['status'] == 'already'
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/hot:predict',
            {'instances': _x().tolist()})
        assert status == 200
        assert np.asarray(body['outputs'][0]).shape == (1, OUT)
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/hot:unload', {})
        assert status == 200
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/hot:predict',
            {'instances': _x().tolist()})
        assert status == 404
        status, _h, body = fs._http_json(
            'POST', host, port, '/v1/models/ghost:unload', {})
        assert status == 404


def test_fault_knob_parsers(monkeypatch):
    monkeypatch.setenv('MXNET_TPU_FAULT_REPLICA_KILL_AFTER_S', '3.5')
    assert fs.replica_kill_after_s(0) == 3.5
    assert fs.replica_kill_after_s(2) == 3.5
    monkeypatch.setenv('MXNET_TPU_FAULT_REPLICA_KILL_AFTER_S', '1:2.0')
    assert fs.replica_kill_after_s(0) is None
    assert fs.replica_kill_after_s(1) == 2.0
    monkeypatch.delenv('MXNET_TPU_FAULT_REPLICA_KILL_AFTER_S')
    assert fs.replica_kill_after_s(0) is None
    monkeypatch.setenv('MXNET_TPU_FAULT_REPLICA_WEDGE', '0,2')
    assert fs.replica_wedged(0, 0.0) and fs.replica_wedged(2, 99.0)
    assert not fs.replica_wedged(1, 99.0)
    monkeypatch.setenv('MXNET_TPU_FAULT_REPLICA_WEDGE', '1:5')
    assert not fs.replica_wedged(1, 4.0)
    assert fs.replica_wedged(1, 6.0)
    assert not fs.replica_wedged(0, 6.0)
    monkeypatch.setenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS', '80')
    assert fs.canary_degrade_ms() == 80.0
    assert fs.canary_degrade_ms('m@v1') == 80.0    # bare MS: any arm
    monkeypatch.setenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS', '@v1:90')
    assert fs.canary_degrade_ms('m@v1') == 90.0
    assert fs.canary_degrade_ms('m@v2') == 0.0     # other arms healthy
    assert fs.canary_degrade_ms() == 0.0           # no name: no match
    monkeypatch.delenv('MXNET_TPU_FAULT_CANARY_DEGRADE_MS')
    assert fs.canary_degrade_ms() == 0.0
    monkeypatch.setenv('MXNET_TPU_FAULT_PUSH_FAIL', '2')
    assert fs.push_fail_n() == 2
    monkeypatch.delenv('MXNET_TPU_FAULT_PUSH_FAIL')
    assert fs.push_fail_n() is None


# ---------------------------------------------------------------------------
# scale policy (pure decision over the PR-10 counter windows)
# ---------------------------------------------------------------------------

def test_scale_policy_hysteresis():
    p = ScalePolicy(up_after=3, down_after=4, backlog_hot=64)
    hot = {'p99_over_deadline': True, 'backlog_rows': 0,
           'requests_delta': 5}
    idle = {'p99_over_deadline': False, 'backlog_rows': 0,
            'requests_delta': 0}
    busy = {'p99_over_deadline': False, 'backlog_rows': 3,
            'requests_delta': 9}
    assert [p.decide(hot) for _ in range(3)] == [0, 0, 1]
    # a healthy-busy window resets the idle streak — no flapping
    assert [p.decide(idle) for _ in range(3)] == [0, 0, 0]
    assert p.decide(busy) == 0
    assert [p.decide(idle) for _ in range(4)] == [0, 0, 0, -1]
    # backlog alone (no deadline) also counts as hot
    deep = {'p99_over_deadline': False, 'backlog_rows': 100,
            'requests_delta': 1}
    assert [p.decide(deep) for _ in range(3)] == [0, 0, 1]


def test_autoscale_follows_a_diurnal_load_without_flapping(monkeypatch,
                                                         tmp_path):
    """A night, a peak and a night through the scale loop, without
    processes: the peak's hot windows add a replica every `up_after`
    up to three, the idle night retires them one every `down_after`
    down to the floor, and nothing else moves.  The router's latency
    window still holds the peak's p99 through both nights: with no
    request in a window it reads idle, not hot (read as hot it kept
    the fleet at its peak size all night)."""
    sup = FleetSupervisor(
        models=[{'name': 'm', 'prefix': str(tmp_path / 'nope'),
                 'input_shapes': {'data': [1, DIM]}, 'deadline_ms': 50}],
        replicas=1, max_replicas=3,
        scale_policy=ScalePolicy(up_after=2, down_after=3))
    try:
        live, requests = [1], [0]
        monkeypatch.setattr(sup.router, 'requests_delta',
                            lambda: requests[0])
        monkeypatch.setattr(sup.router, 'latency_p99_ms',
                            lambda name: 500.0)
        monkeypatch.setattr(sup, 'replicas', lambda: [])
        monkeypatch.setattr(sup, 'live_replicas', lambda: live[0])
        monkeypatch.setattr(sup, 'spawn_replica',
                            lambda: live.__setitem__(0, live[0] + 1))
        monkeypatch.setattr(sup, 'retire_replica',
                            lambda: live.__setitem__(0, live[0] - 1))
        timeline = []
        for n, windows in ((0, 2), (9, 5), (0, 8)):
            requests[0] = n
            for _ in range(windows):
                sup._scale_once()
                timeline.append(live[0])
        assert timeline == [1, 1, 1, 2, 2, 3, 3, 3, 3, 2, 2, 2, 1, 1, 1]
        assert sup._scale_obs() == {'p99_over_deadline': False,
                                    'backlog_rows': 0,
                                    'requests_delta': 0}
    finally:
        sup.router.close()


# ---------------------------------------------------------------------------
# supervisor: wedge detection, restart budget (no subprocesses)
# ---------------------------------------------------------------------------

def _fake_supervisor(tmp_path):
    return FleetSupervisor(
        models=[{'name': 'm', 'prefix': str(tmp_path / 'nope'),
                 'input_shapes': {'data': [1, DIM]}}], replicas=1)


def test_supervisor_declares_wedged_replica_dead(monkeypatch,
                                                tmp_path):
    profiler.clear()
    monkeypatch.setenv('MXNET_TPU_FLEET_DEAD_AFTER_S', '0.3')
    monkeypatch.setenv('MXNET_TPU_FAULT_REPLICA_WEDGE', '7')
    wedged = ReplicaServer(models=[_spec(1)], index=7).start()
    sup = _fake_supervisor(tmp_path)
    try:
        rep = fs._Replica(7)
        rep.host, rep.port = wedged.address
        rep.last_ok = time.monotonic() - 10.0
        sup._replicas.append(rep)
        sup.router.add_backend(rep.bid, rep.host, rep.port)
        monkeypatch.setattr(sup, '_respawn_due', lambda: None)
        t0 = time.monotonic()
        sup._health_once()
        # the wedge answers nothing: detection is by probe TIMEOUT
        assert time.monotonic() - t0 < 5.0
        assert sup.router.backends() == []      # routing stopped
        assert sup._dead_pending and \
            sup._dead_pending[0].index == 7     # respawn scheduled
        assert rep.backoff >= fs.restart_backoff_s()
        assert rep.next_attempt > t0
    finally:
        sup.router.close()
        wedged.close()


def test_supervisor_restart_budget_abandons_slot(monkeypatch,
                                                 tmp_path):
    monkeypatch.setenv('MXNET_TPU_FLEET_MAX_RESTARTS', '1')
    sup = _fake_supervisor(tmp_path)
    try:
        rep = fs._Replica(0)
        rep.host, rep.port = '127.0.0.1', _refused_port()
        sup._declare_dead(rep, 'test kill 1')
        assert len(sup._dead_pending) == 1      # within budget
        sup._dead_pending.clear()
        sup._declare_dead(rep, 'test kill 2')
        assert sup._dead_pending == []          # budget exhausted
        assert sup.stats()['abandoned_slots'] == 1
    finally:
        sup.router.close()


# ---------------------------------------------------------------------------
# push vs replica death/respawn (ISSUE-14 satellite: the reconcile fix)
# ---------------------------------------------------------------------------

def _ckpt_prefix(tmp_path, tag, seed):
    prefix = str(tmp_path / tag)
    model_mod.save_checkpoint(prefix, 0, _mlp(), _params(seed), {})
    return prefix


def _push_spec(prefix):
    return {'name': 'm', 'prefix': prefix, 'epoch': 0,
            'input_shapes': {'data': [1, DIM]},
            'max_batch': 4, 'max_wait_us': 0}


def _fake_rep(index, host, port):
    rep = fs._Replica(index)
    rep.host, rep.port = host, port
    return rep


def test_push_survives_dead_replica_mid_fanout(tmp_path):
    """A replica that died before/while the push fans out must NOT
    abort the push: the live replicas get the candidate, the canary
    opens, and the pending set keeps the candidate so the dead slot's
    respawn reconciles to it.  (Previously one OSError unwound the
    whole push.)"""
    prefix_a = _ckpt_prefix(tmp_path, 'stable', 1)
    prefix_b = _ckpt_prefix(tmp_path, 'cand', 2)
    live = ReplicaServer(models=[_push_spec(prefix_a)], index=0).start()
    sup = FleetSupervisor(models=[_push_spec(prefix_a)], replicas=2)
    try:
        sup._replicas = [
            _fake_rep(0, '127.0.0.1', _refused_port()),   # dead first
            _fake_rep(1, *live.address)]
        cand = sup.push('m', prefix_b, epoch=0, frac=0.5)
        assert cand in live.registry.models()
        assert sup.push_active('m')
        assert prefix_b in sup.active_prefixes('m')
        rep = sup.router.canary_report('m')
        assert rep is not None and rep['state'] == 'running'
    finally:
        sup.router.close()
        live.close()


def test_push_refused_by_live_replica_still_unwinds(tmp_path,
                                                    monkeypatch):
    """A REFUSAL (not a transport failure) keeps the abort semantics:
    the fleet must never route to an arm only some replicas serve."""
    monkeypatch.setenv('MXNET_TPU_SERVE_STRICT_BUDGET', '1')
    prefix_a = _ckpt_prefix(tmp_path, 'stable2', 1)
    prefix_b = _ckpt_prefix(tmp_path, 'cand2', 2)
    live = ReplicaServer(models=[], index=0,
                         budget_bytes=1).start()   # any load -> 507
    sup = FleetSupervisor(models=[_push_spec(prefix_a)], replicas=1)
    try:
        sup._replicas = [_fake_rep(0, *live.address)]
        with pytest.raises(MXNetError, match='refused'):
            sup.push('m', prefix_b, epoch=0)
        assert not sup.push_active('m')      # pending unwound
    finally:
        sup.router.close()
        live.close()


def test_respawn_reconciles_to_pushed_and_promoted_model(tmp_path):
    """The respawn-vs-push race closer: a replica that rejoins with
    the PRE-push arm set baked into its spawn config converges to the
    fleet's intended model set — the pending candidate while a push is
    judged, and the promoted arm (old stable dropped) afterwards."""
    prefix_a = _ckpt_prefix(tmp_path, 'stable3', 1)
    prefix_b = _ckpt_prefix(tmp_path, 'cand3', 2)
    live = ReplicaServer(models=[_push_spec(prefix_a)], index=0).start()
    sup = FleetSupervisor(models=[_push_spec(prefix_a)], replicas=1)
    try:
        sup._replicas = [_fake_rep(0, *live.address)]
        cand = sup.push('m', prefix_b, epoch=0, frac=0.5)
        # a "respawned" replica that booted from the pre-push config
        rejoin = ReplicaServer(models=[_push_spec(prefix_a)],
                               index=1).start()
        try:
            sup._reconcile(*rejoin.address, cfg_names=('m',))
            assert set(rejoin.registry.models()) == {'m', cand}
            # the push promotes: desired set flips to the candidate
            sup._on_router_event('promote', 'm',
                                 {'candidate': cand, 'report': None})
            assert not sup.push_active('m')
            assert sup.active_prefixes('m') == {prefix_b}
            sup._reconcile(*rejoin.address, cfg_names=('m', cand))
            assert set(rejoin.registry.models()) == {cand}
        finally:
            rejoin.close()
    finally:
        sup.router.close()
        live.close()


# ---------------------------------------------------------------------------
# the end-to-end drill: real replica processes, SIGKILL mid-load
# ---------------------------------------------------------------------------

def test_supervisor_sigkill_respawn_e2e(monkeypatch, tmp_path):
    """The acceptance window: requests in flight when a replica is
    SIGKILLed all complete (router retry + client backoff — zero lost
    accepted requests), and the supervisor respawns the replica within
    the grace window, visible in /statsz."""
    prefix = str(tmp_path / 'fleet_m')
    model_mod.save_checkpoint(prefix, 0, _mlp(), _params(1), {})
    monkeypatch.setenv('MXNET_TPU_FLEET_HEARTBEAT_S', '0.2')
    monkeypatch.setenv('MXNET_TPU_FLEET_DEAD_AFTER_S', '1.0')
    sup = FleetSupervisor(
        models=[{'name': 'm', 'prefix': prefix, 'epoch': 0,
                 'input_shapes': {'data': [1, DIM]},
                 'max_batch': 4, 'max_wait_us': 0,
                 'deadline_ms': 10000}],
        replicas=2)
    try:
        sup.start()
        sup.wait_healthy(timeout=120)
        host, port = sup.router.address
        url = 'http://%s:%d/v1/models/m:predict' % (host, port)
        x = _x().tolist()
        failures = []
        done = threading.Event()

        def client():
            for _ in range(30):
                try:
                    st, _ = post_with_backoff(url, {'instances': x},
                                              deadline_s=60)
                    if st != 200:
                        failures.append(st)
                except Exception as e:
                    failures.append(repr(e))
            done.set()

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.3)                 # requests in flight
        victim = sup.replicas()[0]
        victim.proc.send_signal(signal.SIGKILL)
        t_kill = time.monotonic()
        t.join(timeout=180)
        assert done.is_set(), 'client hung through the replica death'
        assert not failures, failures[:3]
        # the supervisor respawns within the grace window
        respawned = False
        while time.monotonic() - t_kill < 90:
            live = sup.replicas()
            if len(live) >= 2 and all(sup._probe(r) for r in live):
                respawned = True
                break
            time.sleep(0.2)
        assert respawned, 'replica not respawned within the window'
        assert sup.stats()['restarts'] >= 1
        st = json.loads(urllib.request.urlopen(
            'http://%s:%d/statsz' % (host, port), timeout=30).read())
        assert st['fleet_supervisor'][
            'fleet_supervisor_replica_restarts'] >= 1
        assert st['supervisor']['restarts'] >= 1
        assert len([r for r in st['supervisor']['replicas']
                    if r['alive']]) >= 2
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# profiler family
# ---------------------------------------------------------------------------

def test_fleet_supervisor_counters_in_summary_and_dump(tmp_path):
    profiler.clear()
    profiler.add_fleet_supervisor_stats(
        replica_spawns=3, replica_restarts=1, replica_retires=1,
        router_requests=10, router_retries=2, router_503=1,
        canary_pushes=1, canary_rollbacks=1, shadow_requests=4,
        shadow_divergences=2, replicas_live=2)
    fsn = profiler.fleet_supervisor_stats()
    assert fsn['fleet_supervisor_replica_spawns'] == 3
    assert fsn['fleet_supervisor_replicas_live'] == 2   # gauge
    profiler.add_fleet_supervisor_stats(replicas_live=3)
    assert profiler.fleet_supervisor_stats()[
        'fleet_supervisor_replicas_live'] == 3
    text = profiler.summary(print_out=False)
    for key in ('fleet_supervisor_replica_restarts',
                'fleet_supervisor_replicas_live',
                'fleet_supervisor_router_retries',
                'fleet_supervisor_canary_rollbacks',
                'fleet_supervisor_shadow_divergences'):
        assert key in text
    out = tmp_path / 'fleet_sup_profile.json'
    profiler.profiler_set_config(filename=str(out))
    profiler.dump_profile()
    events = json.loads(out.read_text())['traceEvents']
    meta = [e for e in events if e.get('name') == 'fleet_supervisor']
    assert meta and \
        meta[0]['args']['fleet_supervisor_replica_spawns'] == 3
    profiler.clear()
    assert profiler.fleet_supervisor_stats()[
        'fleet_supervisor_replica_spawns'] == 0
