"""Fleet serving tier: model registry, SLO-aware batching, HTTP front
with backpressure, continuous batching for sequence models.

`serving.InferenceEngine` (PERF round 9) is one-engine-one-model with a
single global batching knob.  This module grows it into the fleet shape
ROADMAP item 2 asks for — the first user-facing surface of the stack:

  * **ModelRegistry** — hosts many named models' AOT rung artifacts
    through the process-wide `exec_cache`, with byte-budgeted LRU
    paging: a cold model's *weights* are evicted (engine closed +
    drained, Predictor dropped — device memory freed), while its
    compiled rung programs stay cached process-wide (they hold graph
    code, not weight buffers — see serving._make_serve_fn), so a
    re-warm rebinds + reloads params from the checkpoint artifacts and
    performs ZERO new XLA compilations.  Cross-process, the
    `export_compiled` artifacts + the PR-1 persistent XLA cache warm a
    fresh process where the backend allows it (the PR-7 CPU-backend
    guard keeps the on-disk cache off on XLA:CPU — in-process paging is
    unaffected by that guard).
  * **SLO-aware batching** — each model/tenant carries a deadline
    (`SLO(deadline_ms=..., priority=...)`) instead of the one global
    `max_wait_us` knob: the batcher hold is derived from the deadline
    budget (`MXNET_TPU_SERVE_WAIT_FRACTION` of it), and admission
    control sheds on backlog with a typed `Overloaded` error once
    backlog rows x the engine-local service rate (the per-engine
    counter window `InferenceEngine.stats()` now scopes) exceeds the
    deadline — a client that cannot be served in time learns NOW, not
    after its deadline already passed in a queue.
  * **HTTP front** (`HttpFront`, driven by tools/serve_http.py) —
    stdlib `http.server` threads, no new deps: POST
    `/v1/models/<name>:predict`, GET `/healthz` and `/statsz`, with
    bounded in-flight admission so backpressure propagates to clients
    as 429s (+ Retry-After) instead of unbounded queues.
  * **Continuous batching** (`ContinuousEngine`) — the sequence-model
    analog of the dynamic batcher: a per-timestep cell runs at a fixed
    slot count, and requests are ADMITTED into free slots and RETIRED
    at their own length at every tick boundary, so a long sequence no
    longer convoys short ones (the convoy baseline — fill the batch,
    run everyone to the longest length — is the `convoy=True` mode the
    bench A/Bs against).  One fixed program shape -> zero steady-state
    compiles, and row independence makes co-residency bit-exact vs a
    solo run.

Env knobs (docs/SERVING.md has the full table):
  MXNET_TPU_SERVE_REGISTRY_BYTES   registry byte budget (0 = unbounded)
  MXNET_TPU_SERVE_STRICT_BUDGET    1 = refuse (typed BudgetExceeded)
                                   instead of transiently overshooting
  MXNET_TPU_SERVE_DEADLINE_MS      default SLO deadline (unset = none)
  MXNET_TPU_SERVE_WAIT_FRACTION    batcher hold as deadline fraction
  MXNET_TPU_SERVE_SHED_FACTOR      shed when est > factor x deadline
  MXNET_TPU_SERVE_MAX_QUEUE_ROWS   hard backlog cap per model (4096)
  MXNET_TPU_SERVE_HTTP_INFLIGHT    bounded HTTP admission (64)
  MXNET_TPU_SERVE_HTTP_PORT        default front port (8000)
  MXNET_TPU_SERVE_QUANTIZE         default engine weight quantization
                                   ('int8'/'bf16'; see serving.py)
  MXNET_TPU_SERVE_PAGED_BYTES      host budget for page_dtype images
                                   (0 = unbounded)
"""
import json
import os
import threading
import time
from collections import deque

import numpy as np

from . import exec_cache
from . import profiler
from . import quantization
from .base import MXNetError
from .quantization import QuantConfig
from .serving import (InferenceEngine, _env_int, _quiet_donation,
                      chunk_for_deadline, resolve_tick_chunk)

__all__ = ['Overloaded', 'BudgetExceeded', 'SLO', 'ModelRegistry',
           'ContinuousEngine', 'HttpFront']

# tick_chunk='auto' EMA smoothing: one chunk's measured per-tick wall
# folds in at this weight, so K re-derives from a few recent chunks
# without chasing single-dispatch jitter
_TICK_EMA_ALPHA = 0.25


def _env_float(name, default):
    try:
        return float(os.environ.get(name, '') or default)
    except ValueError:
        return default


class Overloaded(MXNetError):
    """Typed shed error: the model's backlog x service rate exceeds its
    deadline (or the hard queue cap), so admitting this request would
    only burn queue memory on an answer that arrives too late.  The
    HTTP front maps it to 429 + Retry-After; direct callers can back
    off on `retry_after_ms`."""

    def __init__(self, model, backlog_rows, est_ms, deadline_ms):
        self.model = model
        self.backlog_rows = int(backlog_rows)
        self.est_ms = float(est_ms)
        self.deadline_ms = None if deadline_ms is None \
            else float(deadline_ms)
        # suggest retrying after the excess backlog should have
        # drained; clamped finite (the hard queue-cap path sheds with
        # est=inf) so HTTP Retry-After arithmetic stays sane
        self.retry_after_ms = min(
            60000.0, max(1.0, (self.est_ms - (self.deadline_ms or 0.0))
                         if np.isfinite(self.est_ms) else 1000.0))
        super(Overloaded, self).__init__(
            'model %r overloaded: estimated %.1fms for %d backlog rows'
            '%s' % (model, self.est_ms, self.backlog_rows,
                    '' if deadline_ms is None
                    else ' > deadline %.1fms' % self.deadline_ms))


class BudgetExceeded(MXNetError):
    """Typed strict-budget refusal (MXNET_TPU_SERVE_STRICT_BUDGET=1):
    making this model resident would push the registry past its byte
    budget and nothing evictable remains to make room — the load is
    refused (or undone) instead of transiently overshooting.  The HTTP
    front maps it to 507 Insufficient Storage."""

    def __init__(self, model, need_bytes, budget_bytes, resident_bytes):
        self.model = model
        self.need_bytes = int(need_bytes)
        self.budget_bytes = int(budget_bytes)
        self.resident_bytes = int(resident_bytes)
        super(BudgetExceeded, self).__init__(
            'model %r refused under the strict registry budget: needs '
            '%d bytes but only %d of the %d-byte budget is free and '
            'nothing evictable remains (set '
            'MXNET_TPU_SERVE_STRICT_BUDGET=0 to allow transient '
            'overshoot)' % (model, self.need_bytes,
                            max(0, self.budget_bytes -
                                self.resident_bytes),
                            self.budget_bytes))


def _strict_budget():
    return os.environ.get('MXNET_TPU_SERVE_STRICT_BUDGET',
                          '').strip() in ('1', 'true')


class SLO(object):
    """Per-model/tenant serving objective.

    deadline_ms : float or None
        End-to-end latency target.  Drives BOTH the batcher hold (the
        engine's `max_wait_us` becomes WAIT_FRACTION of the deadline
        budget instead of the global knob) and admission control
        (shed with `Overloaded` once the backlog estimate exceeds
        shed_factor x deadline).  None (and no
        MXNET_TPU_SERVE_DEADLINE_MS default) = no deadline: global
        batching knob, shed only at the hard queue cap.
    priority : int
        Higher = more important.  The registry evicts lowest-priority
        models first (LRU within a priority), and the HTTP front's
        scarce last admission slots are reserved for priority >= 1
        (see HttpFront).
    service_ms_hint : float or None
        Estimated per-ROW service time used for shed decisions before
        the engine-local counter window has observed real traffic
        (after the first completed batch the measured EMA takes over).
    shed_factor : float
        Backlog estimate tolerance before shedding (default
        MXNET_TPU_SERVE_SHED_FACTOR or 1.0).
    """

    def __init__(self, deadline_ms=None, priority=0,
                 service_ms_hint=None, shed_factor=None):
        if deadline_ms is None:
            d = _env_float('MXNET_TPU_SERVE_DEADLINE_MS', 0.0)
            deadline_ms = d if d > 0 else None
        self.deadline_ms = None if deadline_ms is None \
            else float(deadline_ms)
        self.priority = int(priority)
        self.service_ms_hint = None if service_ms_hint is None \
            else float(service_ms_hint)
        self.shed_factor = float(
            shed_factor if shed_factor is not None else
            _env_float('MXNET_TPU_SERVE_SHED_FACTOR', 1.0))

    def wait_us(self):
        """Deadline-driven batcher hold: the engine may hold an
        underfull batch open for WAIT_FRACTION of the deadline budget
        (coalescing opportunity without eating the whole budget in the
        queue).  None when no deadline — the engine's global default
        knob applies."""
        if self.deadline_ms is None:
            return None
        frac = _env_float('MXNET_TPU_SERVE_WAIT_FRACTION', 0.25)
        return max(0, int(self.deadline_ms * 1000.0 * frac))

    def describe(self):
        return {'deadline_ms': self.deadline_ms,
                'priority': self.priority,
                'shed_factor': self.shed_factor}


# ---------------------------------------------------------------------------
# model registry
# ---------------------------------------------------------------------------

class _ModelEntry(object):
    __slots__ = ('name', 'loader', 'slo', 'engine_kwargs', 'pinned',
                 'lock', 'engine', 'holder', 'bytes', 'last_used',
                 'est_bytes', 'dead', 'quantize', 'page_dtype',
                 'paged', 'paged_bytes', 'tick_chunk')

    def __init__(self, name, loader, slo, engine_kwargs, pinned,
                 est_bytes=None, quantize=None, page_dtype=None,
                 tick_chunk=None):
        self.name = name
        self.loader = loader
        self.slo = slo
        self.engine_kwargs = engine_kwargs
        self.pinned = pinned
        self.quantize = quantize        # QuantConfig (live int8 engine)
        self.page_dtype = page_dtype    # QuantConfig (evicted image)
        self.tick_chunk = tick_chunk    # forwarded to a cont loader
        self.paged = None               # quantized host weight image
        self.paged_bytes = 0
        self.lock = threading.Lock()    # serializes load vs evict
        self.engine = None              # engine-like (resident only)
        self.holder = None              # the Predictor (weight owner)
        self.bytes = 0
        self.last_used = 0.0
        # estimated resident bytes BEFORE the first load (checkpoint
        # param-file size for prefix= models, or an explicit
        # est_bytes= at register); replaced by the exact measured
        # bytes after the first load so later re-warms pre-enforce
        # the budget precisely
        self.est_bytes = est_bytes
        # set (under self.lock) by unregister(): a _load that raced
        # the pop must refuse instead of resurrecting an engine no
        # map entry can ever reach again
        self.dead = False


def _weight_bytes(executor):
    """Resident weight/aux bytes of one bound executor — the unit the
    registry's byte budget accounts (input staging is transient and
    compiled programs are host-side code shared via exec_cache)."""
    total = 0
    for d in (executor.arg_dict, executor.aux_dict):
        for a in d.values():
            total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
    return total


class ModelRegistry(object):
    """Hosts many named models behind one serving surface, paging
    their weights through a byte budget with LRU eviction while the
    process-wide exec_cache keeps every model's compiled rung
    programs warm (evict/re-warm cycles perform zero XLA compiles —
    the programs hold graph code, not weight buffers).

    Models are *registered* cheaply (a loader spec, nothing resident)
    and made resident on first use.  A loader is either:

      * ``prefix=/path/prefix, epoch=N, input_shapes={...}`` — the
        Module.save_checkpoint artifacts; re-warm reloads params from
        disk (the pageable, production shape), or
      * ``loader=callable`` returning a fresh Predictor (or an
        engine-like object with .infer/.close — a ContinuousEngine
        for sequence models), or
      * ``source=<live Predictor/Module>`` — registered PINNED: its
        weights exist only in memory, so the registry counts but
        never evicts it.

    Parameters
    ----------
    budget_bytes : int, optional
        Resident-weight budget (default MXNET_TPU_SERVE_REGISTRY_BYTES;
        0/unset = unbounded).  A load may transiently overshoot by the
        incoming model's size — the budget is enforced by evicting
        colder models immediately after, so steady state stays under.
    ctx : Context, optional
        Device for checkpoint loaders (default cpu()).
    """

    def __init__(self, budget_bytes=None, ctx=None):
        self.budget_bytes = int(
            budget_bytes if budget_bytes is not None else
            _env_int('MXNET_TPU_SERVE_REGISTRY_BYTES', 0))
        self.max_queue_rows = _env_int('MXNET_TPU_SERVE_MAX_QUEUE_ROWS',
                                       4096)
        self._ctx = ctx
        self._lock = threading.Lock()   # registry map + byte ledger
        self._entries = {}
        self._resident_bytes = 0
        self._peak_resident_bytes = 0   # high-water mark: with known
                                        # estimates the pre-load
                                        # enforcement keeps it <= budget
        self._paged_bytes = 0           # host bytes held by quantized
                                        # page-out images (page_dtype)
        self._n_loads = 0
        self._n_evictions = 0
        self._n_shed = 0
        self._n_page_ins = 0
        self._n_page_drops = 0
        self._closed = False

    # -- registration ---------------------------------------------------
    def register(self, name, loader=None, prefix=None, epoch=0,
                 input_shapes=None, source=None, slo=None,
                 est_bytes=None, quantize=None, page_dtype=None,
                 tick_chunk=None, **engine_kwargs):
        """Register a model spec (nothing loads until first use).
        Exactly one of `loader` / `prefix` / `source`.  `engine_kwargs`
        forward to InferenceEngine (max_batch, batch_buckets,
        free_dim_buckets, ...); `max_wait_us` defaults to the SLO's
        deadline-derived hold instead of the global knob.

        `tick_chunk` (loader= sequence models only) forwards to the
        loader as a keyword — a ContinuousEngine loader passes it
        through so the engine runs K ticks per dispatch
        (chunk-boundary admission; see ContinuousEngine docs).  It is
        parsed HERE by the shared resolve_tick_chunk parser
        (0/'off'/1 = unchunked), so a malformed value fails typed at
        register time, not at first use; the engine re-parses against
        its slot count (K > slots is rejected there).  `est_bytes`
        pre-sizes the model for budget enforcement BEFORE its first
        load (prefix= models default to the checkpoint param-file
        size).  est_bytes is the FP32-EQUIVALENT size: with quantize=
        it is scaled by the documented EST_BYTES_RATIO before
        enforcement.  After the first load the measured bytes take
        over.

        `quantize` (QuantConfig or 'int8'/'bf16') serves the model
        through a weight-quantized engine: its RESIDENT bytes drop
        ~4x (int8), so the byte-budgeted LRU fits that many more
        models live — the pre-load estimate is scaled by the
        documented quantization.EST_BYTES_RATIO so strict-budget
        enforcement and the peak_resident_bytes gauge account the
        QUANTIZED representation, not the fp32 param-file size, and
        the first load's measured bytes take over exactly.

        `page_dtype` ('int8'/'bf16' or a QuantConfig; prefix= models
        only, and exclusive with `quantize`) keeps a HOST-side
        quantized weight image when the model is paged out: page-in
        dequantizes from the image instead of re-reading the
        checkpoint, still at zero XLA compiles (programs bind
        run_graph, not weight buffers).  Image bytes are tracked in
        stats()['paged_bytes'] and bounded by
        MXNET_TPU_SERVE_PAGED_BYTES (0 = unbounded): over it, the
        oldest images drop and those models page in from disk
        again."""
        given = [x is not None for x in (loader, prefix, source)]
        if sum(given) != 1:
            raise MXNetError('register(%r): exactly one of loader= / '
                             'prefix= / source= required' % name)
        if tick_chunk is not None:
            if loader is None:
                raise MXNetError(
                    'register(%r): tick_chunk= applies to loader= '
                    'sequence models (a loader accepting tick_chunk= '
                    'and returning a ContinuousEngine); prefix=/'
                    'source= models serve through the request '
                    'coalescer, which has no tick loop' % name)
            if isinstance(tick_chunk, str) and \
                    tick_chunk.strip().lower() == 'auto':
                # forwarded unresolved: only the engine has the SLO
                # deadline the adaptive chooser derives K against
                # (resolve_tick_chunk rejects auto-without-deadline
                # typed at construction)
                tick_chunk = 'auto'
            elif resolve_tick_chunk(tick_chunk) == 1:
                tick_chunk = None       # 0/'off'/1: the loader's own
                                        # default (unchunked) applies
        quantize = QuantConfig.resolve(quantize)
        page_dtype = QuantConfig.resolve(page_dtype)
        if quantize is None and page_dtype is None:
            # resolve the fleet-wide env default HERE, not engine-side:
            # the exclusivity guard, the est_bytes scaling, and the
            # stats()/gauge attribution below must all see it — an
            # engine-side-only resolution would silently int8-swap a
            # page_dtype model's holder weights out from under the
            # page-out snapshot
            quantize = QuantConfig.from_env()
        if page_dtype is not None:
            if prefix is None:
                raise MXNetError(
                    'register(%r): page_dtype= needs a prefix= model '
                    '(page-in rebuilds from the checkpoint symbol + '
                    'input shapes)' % name)
            if quantize is not None:
                raise MXNetError(
                    'register(%r): page_dtype= and quantize= are '
                    'exclusive — a quantize= engine is already its '
                    'own compressed representation' % name)
        pinned = False
        if prefix is not None:
            if input_shapes is None:
                raise MXNetError('register(%r): prefix= needs '
                                 'input_shapes=' % name)
            from .predictor import Predictor
            ctx = self._ctx
            shapes = dict(input_shapes)

            def loader(_p=prefix, _e=int(epoch), _s=shapes, _c=ctx):
                return Predictor.from_checkpoint(_p, _e, _s, ctx=_c)
            if est_bytes is None:
                # the serialized params are a close upper bound on the
                # resident arg/aux bytes (names + shape headers ride
                # along) — good enough to pre-enforce the budget
                try:
                    est_bytes = os.path.getsize(
                        '%s-%04d.params' % (prefix, int(epoch)))
                except OSError:
                    est_bytes = None
        elif source is not None:
            # live object: weights exist only in memory — evicting
            # would lose them, so it is resident-forever (pinned)
            pinned = True

            def loader(_src=source):
                return _src
        if est_bytes is not None and quantize is not None:
            # est_bytes is the FP32-EQUIVALENT size (param file or
            # caller estimate); the model will be RESIDENT in its
            # quantized form, so pre-enforcing the budget against the
            # fp32 number would evict colder tenants (or 507 under
            # the strict knob) for ~4x the bytes the load takes —
            # applied uniformly to prefix-file AND caller estimates;
            # the first load's measured bytes replace it exactly
            est_bytes = max(1, int(est_bytes * quantize.est_ratio()))
        # quantize=False is the engine's explicit OFF: a page_dtype
        # model must not be env-quantized behind the registry's back
        engine_kwargs = dict(engine_kwargs,
                             quantize=quantize if quantize is not None
                             else False)
        entry = _ModelEntry(name, loader, slo or SLO(),
                            dict(engine_kwargs), pinned,
                            est_bytes=est_bytes, quantize=quantize,
                            page_dtype=page_dtype,
                            tick_chunk=tick_chunk)
        with self._lock:
            if self._closed:
                raise MXNetError('ModelRegistry is closed')
            if name in self._entries:
                raise MXNetError('model %r already registered' % name)
            self._entries[name] = entry
        profiler.add_fleet_stats(models_registered=1)
        return self

    def models(self):
        with self._lock:
            return sorted(self._entries)

    def _entry(self, name):
        with self._lock:
            ent = self._entries.get(name)
        if ent is None:
            raise MXNetError('unknown model %r (registered: %s)'
                             % (name, self.models()))
        return ent

    # -- residency / paging ---------------------------------------------
    def engine(self, name):
        """The model's resident engine, loading (and byte-budget
        paging) on demand.  Thread-safe; concurrent callers of the
        same cold model serialize on the entry lock so the load and
        ladder warmup happen once."""
        ent = self._entry(name)
        ent.last_used = time.monotonic()
        eng = ent.engine
        if eng is not None and not eng.closed:
            return eng
        return self._load(ent)

    def _load(self, ent):
        # pre-load budget enforcement: when the incoming model's size
        # is known (param-file estimate, explicit est_bytes, or exact
        # bytes from an earlier residency), colder models are paged
        # out BEFORE the load so the ledger never overshoots — and
        # under MXNET_TPU_SERVE_STRICT_BUDGET=1 an unsatisfiable load
        # is refused with a typed BudgetExceeded instead of
        # transiently overshooting.  Runs OUTSIDE ent.lock: evicting a
        # victim takes the victim's entry lock, and two concurrent
        # loads evicting each other while holding their own locks
        # would deadlock.
        if self.budget_bytes > 0 and ent.est_bytes:
            self._make_room(ent, int(ent.est_bytes))
        with ent.lock:
            if self._closed:
                raise MXNetError('ModelRegistry is closed')
            if ent.dead:
                # unregister() raced this load: the entry is gone from
                # the map, so loading would leak an unreachable live
                # engine and permanently inflate the byte ledger
                raise MXNetError('unknown model %r (unregistered)'
                                 % ent.name)
            if ent.engine is not None and not ent.engine.closed:
                return ent.engine
            obj = self._page_in(ent)    # quantized host image, if any
            if obj is None:
                obj = ent.loader() if ent.tick_chunk is None \
                    else ent.loader(tick_chunk=ent.tick_chunk)
            if hasattr(obj, 'infer'):   # engine-like (ContinuousEngine
                eng, holder = obj, obj  # or a pre-built engine)
                nbytes = int(obj.resident_bytes()) \
                    if hasattr(obj, 'resident_bytes') else 0
            else:                       # a Predictor: wrap + warm
                kwargs = dict(ent.engine_kwargs)
                if 'max_wait_us' not in kwargs:
                    w = ent.slo.wait_us()
                    if w is not None:
                        kwargs['max_wait_us'] = w
                eng = InferenceEngine(obj, **kwargs)
                holder = obj
                # the engine's own accounting: excludes input staging
                # and counts a quantize= engine's int8 codes + scales
                # — the HONEST unit the budget/peak gauge enforce
                nbytes = eng.resident_bytes() \
                    if hasattr(eng, 'resident_bytes') else \
                    _weight_bytes(obj._executor)
            ent.engine, ent.holder, ent.bytes = eng, holder, nbytes
            ent.est_bytes = nbytes or ent.est_bytes
            with self._lock:
                self._resident_bytes += nbytes
                self._peak_resident_bytes = max(
                    self._peak_resident_bytes, self._resident_bytes)
                self._n_loads += 1
            profiler.add_fleet_stats(
                loads=1, resident_bytes=self._resident_bytes)
            self._note_quant_gauges()
        # budget enforcement after the load backstops the estimate
        # (the measured bytes may exceed it, or no estimate existed):
        # colder models are paged out immediately (never the one just
        # loaded); under the strict knob a load that STILL overshoots
        # with nothing left to evict is undone and refused typed
        self._enforce_budget(keep=ent)
        if self.budget_bytes > 0 and _strict_budget() and \
                not ent.pinned:
            with self._lock:
                over = self._resident_bytes - self.budget_bytes
                resident = self._resident_bytes
            if over > 0:
                self._evict_one(ent)
                raise BudgetExceeded(ent.name, ent.est_bytes or 0,
                                     self.budget_bytes,
                                     resident - (ent.est_bytes or 0))
        # return the engine THIS call loaded (or found), not
        # ent.engine: a concurrent load's budget enforcement may have
        # evicted the entry again already (ent.engine = None) — the
        # returned closed engine then surfaces the typed closed error
        # that infer()'s reload-retry absorbs
        return eng

    def _make_room(self, ent, need):
        """Evict colder models until `need` bytes fit under the
        budget (same victim order as _enforce_budget).  Under the
        strict knob, raise typed BudgetExceeded when room cannot be
        made — BEFORE the load spends time and memory."""
        with self._lock:
            if ent.engine is not None and not ent.engine.closed:
                return                  # concurrent load already won
            resident = self._resident_bytes
            evictable = sum(
                e.bytes for e in self._entries.values()
                if e is not ent and not e.pinned and
                e.engine is not None and not e.engine.closed)
        if resident - evictable + need > self.budget_bytes:
            # unsatisfiable even after evicting EVERY unpinned tenant
            # (the floor is the pinned/unevictable bytes, not zero):
            # decidable NOW — never destroy resident tenants for a
            # load that could not fit anyway
            if _strict_budget():
                raise BudgetExceeded(ent.name, need,
                                     self.budget_bytes, resident)
            return                      # overshoot stands (documented)
        while True:
            with self._lock:
                if ent.engine is not None and not ent.engine.closed:
                    return              # a concurrent load already won:
                                        # ent's bytes are in the ledger,
                                        # counting `need` again would
                                        # evict colder tenants (or 507)
                                        # for a model already serving
                if self._resident_bytes + need <= self.budget_bytes:
                    return
                victims = [e for e in self._entries.values()
                           if e is not ent and not e.pinned and
                           e.engine is not None and
                           not e.engine.closed]
                if not victims:
                    resident = self._resident_bytes
                    break
                victim = min(victims, key=lambda e:
                             (e.slo.priority, e.last_used))
            self._evict_one(victim)
        if _strict_budget() and \
                (ent.engine is None or ent.engine.closed):
            raise BudgetExceeded(ent.name, need, self.budget_bytes,
                                 resident)

    def _enforce_budget(self, keep=None):
        if self.budget_bytes <= 0:
            return
        while True:
            with self._lock:
                if self._resident_bytes <= self.budget_bytes:
                    return
                victims = [e for e in self._entries.values()
                           if e is not keep and not e.pinned and
                           e.engine is not None and
                           not e.engine.closed]
                if not victims:
                    return      # nothing evictable: overshoot stands
                # lowest priority first, LRU within a priority
                victim = min(victims, key=lambda e:
                             (e.slo.priority, e.last_used))
            self._evict_one(victim)

    def _evict_one(self, ent):
        """Page one model out: reject-new + drain its engine (close),
        drop the weight holder, free the byte ledger.  The compiled
        rung programs stay in exec_cache (host-side graph code, no
        weight buffers) so a later re-warm compiles nothing.  With
        page_dtype a quantized HOST image of the weights is kept so
        the next page-in skips the checkpoint read entirely."""
        with ent.lock:
            eng = ent.engine
            if eng is None:
                return
            image = None
            if ent.page_dtype is not None and not ent.pinned and \
                    not ent.dead and not self._closed and \
                    hasattr(ent.holder, '_symbol'):
                image = self._page_out(ent)
            eng.close()
            ent.engine = None
            ent.holder = None
            freed, ent.bytes = ent.bytes, 0
            with self._lock:
                self._resident_bytes -= freed
                self._n_evictions += 1
            if image is not None:
                self._store_page(ent, image)
            profiler.add_fleet_stats(
                evictions=1, resident_bytes=self._resident_bytes)
            self._note_quant_gauges()

    # -- quantized page-out images (page_dtype=) ------------------------
    def _page_out(self, ent):
        """Snapshot the holder Predictor's weights as a quantized host
        image (called under ent.lock, before the engine closes).
        Never raises — a model that cannot be imaged just pages in
        from disk like before."""
        try:
            holder = ent.holder
            ex = holder._executor
            input_names = set(holder._input_names)
            shapes = {n: tuple(ex.arg_dict[n].shape)
                      for n in holder._input_names}
            args = {n: a.asnumpy() for n, a in ex.arg_dict.items()
                    if n not in input_names}
            aux = {n: a.asnumpy() for n, a in ex.aux_dict.items()}
            quantized, passthrough = quantization.quantize_weights(
                args, ent.page_dtype)
            keep = {n: args[n] for n in passthrough}
            nbytes = quantization.quantized_nbytes(
                quantized, list(keep.values()) + list(aux.values()))
            return {'symbol': holder._symbol, 'shapes': shapes,
                    'quantized': quantized, 'passthrough': keep,
                    'aux': aux, 'nbytes': nbytes}
        except Exception as e:          # pragma: no cover - safety net
            import warnings
            warnings.warn('page_dtype image of %r failed (%s); will '
                          'page in from the checkpoint instead'
                          % (ent.name, e))
            return None

    def _store_page(self, ent, image):
        """Commit an image to the host page store, dropping the
        OLDEST other images past MXNET_TPU_SERVE_PAGED_BYTES."""
        with self._lock:
            ent.paged = image
            ent.paged_bytes = int(image['nbytes'])
            self._paged_bytes += ent.paged_bytes
            budget = _env_int('MXNET_TPU_SERVE_PAGED_BYTES', 0)
            if budget > 0:
                victims = sorted(
                    (e for e in self._entries.values()
                     if e.paged is not None and e is not ent),
                    key=lambda e: e.last_used)
                while self._paged_bytes > budget and victims:
                    v = victims.pop(0)
                    self._paged_bytes -= v.paged_bytes
                    v.paged, v.paged_bytes = None, 0
                    self._n_page_drops += 1
                if self._paged_bytes > budget:
                    self._paged_bytes -= ent.paged_bytes
                    ent.paged, ent.paged_bytes = None, 0
                    self._n_page_drops += 1

    def _page_in(self, ent):
        """Rebuild a Predictor from the entry's quantized host image
        (dequantize-on-page-in: no checkpoint read; the rung programs
        are still warm in exec_cache, so the whole page-in performs
        zero XLA compiles).  Consumes the image.  Returns None when
        there is none (or the rebuild fails — loader fallback)."""
        with self._lock:
            image, ent.paged = ent.paged, None
            self._paged_bytes -= ent.paged_bytes
            ent.paged_bytes = 0
        if image is None:
            return None
        try:
            from . import ndarray as nd
            from .predictor import Predictor
            cfg = ent.page_dtype
            args = {n: nd.array(quantization.dequantize_weight(
                        q, s, cfg, dtype=np.dtype(dt)))
                    for n, (q, s, dt) in image['quantized'].items()}
            for n, a in image['passthrough'].items():
                args[n] = nd.array(a)
            aux = {n: nd.array(a) for n, a in image['aux'].items()}
            pred = Predictor(symbol=image['symbol'], arg_params=args,
                             aux_params=aux,
                             input_shapes=image['shapes'],
                             ctx=self._ctx)
            with self._lock:
                self._n_page_ins += 1
            profiler.add_quant_stats(page_ins=1)
            self._note_quant_gauges()
            return pred
        except Exception as e:          # pragma: no cover - safety net
            import warnings
            warnings.warn('page-in of %r from its quantized image '
                          'failed (%s); falling back to the loader'
                          % (ent.name, e))
            return None

    def apply_delta(self, name, entries, meta, expect_fp=None,
                    parity_tol=None):
        """Apply one weight delta to a registered model WITHOUT a
        full reload: a RESIDENT model updates its engine's device
        weights in place (zero re-warm compiles —
        InferenceEngine.apply_delta); a paged-out model with a
        quantized host image updates the IMAGE instead (dequantize ->
        apply -> requantize per touched weight), so the next page-in
        already reflects the push without ever re-reading a
        checkpoint.  All the delta gates apply (typed DeltaChainError
        / DeltaParityError, nothing mutated on refusal); a model that
        is neither resident nor imaged raises MXNetError — the caller
        falls back to a full (re)load.  Returns the delta's new_fp."""
        from . import delta as delta_mod
        ent = self._entry(name)
        with ent.lock:
            if ent.dead:
                raise MXNetError('model %r is shutting down' % name)
            if ent.engine is not None and not ent.engine.closed:
                if not hasattr(ent.engine, 'apply_delta'):
                    raise MXNetError(
                        'model %r is served by %s, which does not '
                        'take in-place deltas — full reload required'
                        % (name, type(ent.engine).__name__))
                fp = ent.engine.apply_delta(entries, meta,
                                            expect_fp=expect_fp,
                                            parity_tol=parity_tol)
                ent.last_used = time.time()
                return fp
            if ent.paged is None:
                raise MXNetError(
                    'model %r is neither resident nor paged — apply '
                    'the delta after a load, or full-load instead'
                    % name)
            image = ent.paged
            cfg = ent.page_dtype
            if parity_tol is None:
                parity_tol = getattr(cfg, 'parity_tol', None) or \
                    delta_mod.DeltaConfig().parity_tol
            state = {}
            for n, (q, s, dt) in image['quantized'].items():
                state['arg:' + n] = quantization.dequantize_weight(
                    q, s, cfg, dtype=np.dtype(dt))
            for n, a in image['passthrough'].items():
                state['arg:' + n] = np.asarray(a)
            for n, a in image['aux'].items():
                state['aux:' + n] = np.asarray(a)
            lossy = {'arg:' + n for n in image['quantized']}
            new_state = delta_mod.apply_delta(
                state, meta, entries, expect_fp=expect_fp,
                parity_tol=parity_tol, skip_crc=lossy)
            plan = []
            for key in meta.get('entries', {}):
                n = key[4:]
                if key.startswith('arg:') and n in image['quantized']:
                    plan.append((key, n, 'quantized'))
                elif key.startswith('arg:') and \
                        n in image['passthrough']:
                    plan.append((key, n, 'passthrough'))
                elif key.startswith('aux:') and n in image['aux']:
                    plan.append((key, n, 'aux'))
                else:
                    raise delta_mod.DeltaChainError(
                        'delta touches %r which the page image of %r '
                        'does not hold' % (key, name))
            for key, n, dest in plan:
                new = np.asarray(new_state[key])
                if dest == 'quantized':
                    requant, _pass = quantization.quantize_weights(
                        {n: new}, cfg)
                    image['quantized'][n] = requant[n]
                elif dest == 'passthrough':
                    image['passthrough'][n] = new
                else:
                    image['aux'][n] = new
            nbytes = quantization.quantized_nbytes(
                image['quantized'],
                list(image['passthrough'].values()) +
                list(image['aux'].values()))
            with self._lock:
                self._paged_bytes += int(nbytes) - ent.paged_bytes
                ent.paged_bytes = int(nbytes)
            image['nbytes'] = int(nbytes)
            profiler.add_delta_stats(applied=1, page_applies=1)
            self._note_quant_gauges()
            return meta.get('new_fp')

    def _note_quant_gauges(self):
        with self._lock:
            n = sum(1 for e in self._entries.values()
                    if e.engine is not None and not e.engine.closed and
                    getattr(e.engine, '_quant_live', False))
            pb = self._paged_bytes
        profiler.add_quant_stats(models_resident=n, paged_bytes=pb)

    def evict(self, name):
        """Manually page a model out (no-op when not resident).
        Refuses pinned (source=) models: their weights exist only in
        memory, so the loader would hand back the same closed object
        forever — close() the registry to shut them down instead."""
        ent = self._entry(name)
        if ent.pinned:
            raise MXNetError('model %r is pinned (registered from a '
                             'live source=): evicting would lose its '
                             'only weight copy; use close() to shut '
                             'the registry down' % name)
        self._evict_one(ent)
        return self

    def unregister(self, name):
        """Remove a model from the registry entirely: reject-new (the
        name is unknown the moment this returns), drain + close its
        engine, free its bytes.  Unlike evict(), this applies to
        pinned (source=) models too — it is explicit destruction, the
        fleet hot-swap path for retiring a rolled-back or superseded
        model version."""
        with self._lock:
            ent = self._entries.pop(name, None)
        if ent is None:
            raise MXNetError('unknown model %r (registered: %s)'
                             % (name, self.models()))
        with ent.lock:                  # serialize with an in-flight
            ent.dead = True             # _load: it must not resurrect
        self._evict_one(ent)            # an unreachable engine
        with self._lock:                # and drop any page-out image
            if ent.paged is not None:
                self._paged_bytes -= ent.paged_bytes
                ent.paged, ent.paged_bytes = None, 0
        self._note_quant_gauges()
        return self

    # -- serving --------------------------------------------------------
    def infer(self, name, *pos_inputs, **named_inputs):
        """Admission-controlled inference: sheds with `Overloaded`
        when the model's backlog x service rate exceeds its SLO
        deadline (or the hard queue-row cap), else forwards to the
        resident engine.  Concurrent evictions racing this call are
        absorbed by transparent reload+retry (time-bounded)."""
        ent = self._entry(name)
        # the retry window is bounded by the model's OWN deadline when
        # it has one ("fast typed error over slow useless answer" —
        # a 20ms tenant must not spin load/evict cycles for 30s while
        # holding an HTTP inflight slot), else by a fixed cap
        budget = 30.0
        if ent.slo.deadline_ms:
            budget = min(budget, ent.slo.deadline_ms / 1e3)
        deadline = time.monotonic() + budget
        while True:
            eng = self.engine(name)
            self._admit(ent, eng)
            try:
                return eng.infer(*pos_inputs, **named_inputs)
            except MXNetError as e:
                # eviction race: the engine closed between our
                # engine() and the enqueue — reload and retry.  The
                # bound is TIME, not attempts: under a two-model
                # thrash against a one-model budget each reload can
                # lose the race again (the other side's PRE-load
                # enforcement closes it), but every loss needs the
                # close to land in a sub-ms window, so retries
                # converge; a registry-closed error raises from
                # engine() itself and is never retried
                if time.monotonic() < deadline and \
                        getattr(eng, 'closed', False) and \
                        'closed' in str(e):
                    continue
                raise

    def predict(self, name, *pos_inputs, **named_inputs):
        """First output of infer() (same conventions)."""
        return self.infer(name, *pos_inputs, **named_inputs)[0]

    def _admit(self, ent, eng):
        """Shed-on-backlog: estimated time-to-answer for the CURRENT
        backlog (rows x per-row service estimate from the
        engine-local counter window, or the SLO hint before traffic)
        against the deadline.  Estimates only — but an estimate that
        says 'this answer arrives after its deadline' is enough to
        prefer a fast typed error over a slow useless answer."""
        slo = ent.slo
        backlog = eng.backlog_rows() if hasattr(eng, 'backlog_rows') \
            else 0
        if backlog > self.max_queue_rows:
            self._shed(ent, backlog, float('inf'))
        if slo.deadline_ms is None:
            return
        est = eng.service_estimate() \
            if hasattr(eng, 'service_estimate') else None
        if est is not None:
            svc_ms, rows_per_batch = est
            per_row_ms = svc_ms / rows_per_batch
        elif slo.service_ms_hint is not None:
            per_row_ms = slo.service_ms_hint
        else:
            return                      # nothing to judge with yet
        est_ms = (backlog + 1) * per_row_ms
        if est_ms > slo.deadline_ms * slo.shed_factor:
            self._shed(ent, backlog, est_ms)

    def _shed(self, ent, backlog, est_ms):
        with self._lock:
            self._n_shed += 1
        profiler.add_fleet_stats(shed_requests=1)
        raise Overloaded(ent.name, backlog, est_ms,
                         ent.slo.deadline_ms)

    # -- observability / lifecycle --------------------------------------
    def stats(self):
        """Registry paging counters + per-model attribution (each
        resident model's ENGINE-LOCAL window — fill, p50/p99, backlog
        — which the per-engine counter scoping makes per-model
        honest, unlike the process-global serve_* family)."""
        with self._lock:
            entries = list(self._entries.values())
            out = {
                'budget_bytes': self.budget_bytes,
                'resident_bytes': self._resident_bytes,
                'peak_resident_bytes': self._peak_resident_bytes,
                'paged_bytes': self._paged_bytes,
                'strict_budget': _strict_budget(),
                'loads': self._n_loads,
                'evictions': self._n_evictions,
                'shed_requests': self._n_shed,
                'page_ins': self._n_page_ins,
                'page_drops': self._n_page_drops,
            }
        models = {}
        for ent in entries:
            eng = ent.engine
            m = {'resident': eng is not None and not eng.closed,
                 'pinned': ent.pinned,
                 'bytes': ent.bytes}
            if ent.quantize is not None:
                m['quantize'] = ent.quantize.describe()
            if ent.page_dtype is not None:
                m['page_dtype'] = ent.page_dtype.dtype
                m['paged'] = ent.paged is not None
                m['paged_bytes'] = ent.paged_bytes
            m.update(ent.slo.describe())
            if m['resident'] and hasattr(eng, 'stats'):
                es = eng.stats()
                m['engine'] = es
                hr = es.get('hot_rows')
                if hr:
                    # top-level per-model signal (docs/SPARSE.md): a
                    # cold hit rate on a hot-row model says the cache
                    # is undersized for its id distribution — the
                    # operator-facing cue to raise hot_rows= before
                    # latency (page-in per batch) degrades
                    hits = sum(t['hits'] for t in hr.values())
                    total = hits + sum(t['misses'] for t in hr.values())
                    m['hot_row_hit_rate'] = hits / total if total \
                        else 0.0
            models[ent.name] = m
        out['models'] = models
        return out

    def export_artifacts(self, name, batch_buckets=None):
        """The model's `export_compiled` artifacts (one per rung when
        batch_buckets is given) — with the on-disk compile cache on
        (exec_cache.setup_persistent_cache) the compile also lands
        there, so a FRESH process re-warms this model from disk."""
        ent = self._entry(name)
        self.engine(name)               # ensure resident
        holder = ent.holder
        if not hasattr(holder, 'export_compiled'):
            raise MXNetError('model %r source has no export_compiled '
                             '(sequence/engine-like models export via '
                             'their own artifacts)' % name)
        return holder.export_compiled(batch_buckets=batch_buckets)

    def close(self):
        """Evict everything and reject further use (idempotent)."""
        with self._lock:
            if self._closed:
                return self
            self._closed = True
            entries = list(self._entries.values())
        for ent in entries:
            self._evict_one(ent)
        return self

    @property
    def closed(self):
        return self._closed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# continuous batching for sequence models
# ---------------------------------------------------------------------------

class _ContRequest(object):
    __slots__ = ('seq', 'length', 't', 'ys', 'event', 'outputs',
                 'error', 't_enq', 'mig_state', 'staged_t')

    def __init__(self, seq):
        self.seq = seq
        self.length = seq.shape[0]
        self.t = 0
        self.ys = None                  # per-output list of step rows
        self.event = threading.Event()
        self.outputs = None
        self.error = None
        self.t_enq = time.perf_counter()
        self.mig_state = None           # migrated cell state (hot-swap)
        self.staged_t = 0               # position incl. staged chunks
                                        # (t advances at PROCESS time;
                                        # staged_t at STAGING time)


class _StagedChunk(object):
    """The shadow buffer: one chunk's host staging prepared AHEAD of
    (or concurrently with) the device executing earlier chunks.
    Retire/admit decisions are DETERMINISTIC — a slot frees when its
    request's staged position reaches its own length, never a device
    output — so admit rows, the reset mask and the per-row retire
    bookkeeping can all be computed before the previous dispatch
    returns.  Carries its own K: the adaptive chooser may move
    tick_chunk between stagings."""
    __slots__ = ('K', 'xs', 'reset', 'rows', 'admits', 'mig', 'lone',
                 'lane', 'start', 'exact', 'outs', 'error', 't_disp',
                 'waiting')

    def __init__(self, K):
        self.K = K
        self.waiting = 0                # queue depth at staging time
        self.xs = None                  # host (K, width, ...) inputs
        self.reset = None               # host admission-reset mask
        self.rows = ()                  # (slot, request, n) per row
        self.admits = ()                # (slot, request) fresh admits
        self.mig = ()                   # (slot, state dict) hot-swap
        self.lone = False
        self.lane = 0
        self.start = 0
        self.exact = False
        self.outs = None                # dispatched output futures
        self.error = None               # dispatch-time exception
        self.t_disp = 0.0


class ContinuousEngine(object):
    """Continuous batching over a per-timestep sequence cell: the
    RNN/BucketingModule analog of the dynamic batcher.

    The model is a SINGLE-timestep symbol — inputs `data_name` (one
    step of the sequence, shape (slots,) + data_shape) plus named
    recurrent state variables; outputs carry the per-step user outputs
    and the next states (`state_outputs` maps each state input name to
    the output index that feeds it back).  The engine binds it ONCE at
    a fixed `slots` batch — one program shape, zero steady-state
    compiles — and runs a tick loop:

      tick:  admit waiting requests into free slots (their state is
             reset via an in-graph `where(reset, init, state)` — no
             second program), run one step for all slots, append each
             ACTIVE slot's output row, retire slots whose sequence
             just finished (hand back their stacked outputs), repeat.

    A request occupies a slot for exactly its own length: a long
    sequence no longer convoys short ones, and a freed slot is re-used
    by the next request mid-flight.  Row independence of the cell
    makes co-residency BIT-exact against running the same request
    alone (same program, same slot arithmetic — tested).

    `convoy=True` is the baseline the bench A/Bs against: admission
    only into an EMPTY batch, everyone runs to the longest admitted
    length (what a naive sequence batcher does).

    **Chunked ticks** (`tick_chunk=K` / MXNET_TPU_SERVE_TICK_CHUNK,
    PERF round 20): one donated dispatch runs K ticks as a lax.scan
    over the fixed slots batch — the same per-tick math (the
    in-graph reset applies before the chunk's first tick; a
    continuing slot's `where(False, init, state)` is the identity),
    so chunked answers stay BIT-identical to the unchunked loop
    while per-tick dispatch overhead amortizes K-fold, exactly as
    `steps_per_dispatch` did for training.  The cost is quantized
    admission/retire: slots free only at chunk BOUNDARIES, so a slot
    whose sequence ends mid-chunk stays masked (zero inputs, outputs
    discarded host-side) for up to K-1 ticks while the next request
    waits — that boundary latency is counted
    (stats()['boundary_wait_ms'], profiler cont_boundary_wait_ms),
    K is capped at `slots` (resolve_tick_chunk rejects more, typed),
    and an SLO deadline + tick_ms_hint derive a default K the same
    way SLO.wait_us() derives the coalescer hold.  `tick_chunk=1`
    (the default) IS the literal unchunked loop — byte-for-byte the
    same dispatch path, the parity baseline.

    Two request-shaped fast paths (ported from the coalescer's
    exact-fill / lone-request staging shortcuts) ride on chunked
    mode: a LONE active request runs a narrow rung (the full-width
    program is skipped; the rung dynamic-slices its slot's state in
    graph, at width 1 or — where the backend rounds batch-1 gemms
    differently — width 2, and is enabled only when its warmup probe
    is BIT-equal to the full program: stats()['lone_fast_path'] /
    ['lone_fast_path_width']), and an
    exact-fill chunk (every slot active for the full K ticks) skips
    the staging memset.  Both are counted (cont_lone_fast_path /
    cont_exact_fill_admits).

    **Hot-swap sequence migration** (PERF round 18): `export_state()`
    halts the tick loop at a boundary and hands every accepted
    request — in-flight slot state + positions + partial outputs, and
    the waiting queue — to a replacement engine's `admit_state()`, so
    an engine swap completes all accepted sequences (bit-identical to
    an unswapped run when the model is unchanged; counted divergence
    when it isn't — profiler loop_swap_* counters, and the
    MXNET_TPU_FAULT_SWAP_DROP_STATE drill for the state-loss path).

    Parameters
    ----------
    symbol : Symbol
        The per-timestep cell graph.
    arg_params / aux_params : dict
        Parameter NDArrays (state variables must NOT appear here).
    data_shape : tuple
        Per-timestep input shape WITHOUT the slot dim, e.g. (16,).
    state_shapes : dict name -> tuple
        Recurrent state shapes WITHOUT the slot dim.
    state_outputs : dict name -> int
        Which output index carries each state's next value.
    slots : int
        Fixed co-resident request capacity (default
        MXNET_TPU_SERVE_MAX_BATCH or 4).
    init_states : dict name -> array, optional
        Initial state per admitted request (default zeros).  Non-zero
        inits are baked into the step program as constants, so that
        program is NOT shared through exec_cache (zeros — the common
        case — is).
    max_queue : int
        Backlog cap in REQUESTS: beyond it, infer() sheds with
        `Overloaded` (default MXNET_TPU_SERVE_MAX_QUEUE_ROWS).
    tick_chunk : int or str, optional
        Ticks per dispatch (serving.resolve_tick_chunk: explicit
        value, else MXNET_TPU_SERVE_TICK_CHUNK, else the SLO-derived
        default, else 1; 0/'off'/1 = the literal unchunked loop;
        K > slots rejected typed).
    slo : SLO, optional / tick_ms_hint : float, optional
        Together derive the default chunk when neither tick_chunk=
        nor the env knob is set: the largest K whose worst-case
        boundary wait (K-1)*tick_ms_hint fits in WAIT_FRACTION of
        the SLO deadline (serving.chunk_for_deadline).
    """

    def __init__(self, symbol, arg_params=None, aux_params=None,
                 data_name='data', data_shape=None, state_shapes=None,
                 state_outputs=None, slots=None, ctx=None,
                 init_states=None, convoy=False, max_queue=None,
                 tick_chunk=None, slo=None, tick_ms_hint=None,
                 stage_ahead=None):
        from .context import cpu
        if data_shape is None or not state_shapes or not state_outputs:
            raise MXNetError('ContinuousEngine needs data_shape, '
                             'state_shapes and state_outputs')
        if set(state_shapes) != set(state_outputs):
            raise MXNetError('state_shapes and state_outputs must name '
                             'the same states')
        self._ctx = ctx or cpu()
        self.slots = int(slots if slots is not None else
                         _env_int('MXNET_TPU_SERVE_MAX_BATCH', 4))
        self.convoy = bool(convoy)
        self.max_queue = int(max_queue if max_queue is not None else
                             _env_int('MXNET_TPU_SERVE_MAX_QUEUE_ROWS',
                                      4096))
        tk = resolve_tick_chunk(
            tick_chunk, self.slots, slo=slo, tick_ms_hint=tick_ms_hint)
        self._auto = tk == 'auto'
        self._rungs = ()
        self._deadline_ms = None
        self._tick_ms_ema = None        # live per-tick wall EMA (auto)
        self._auto_decisions = 0
        if self._auto:
            # adaptive K: re-derive chunk_for_deadline from the live
            # tick-time EMA, quantized DOWN to a warmed pow-2 rung so
            # a K change never compiles
            self._deadline_ms = float(slo.deadline_ms)
            rungs, r = [], 1
            while r < self.slots:
                rungs.append(r)
                r *= 2
            rungs.append(self.slots)
            self._rungs = tuple(sorted(set(rungs)))
            if tick_ms_hint:
                self._tick_ms_ema = float(tick_ms_hint)
                self.tick_chunk = self._quantize_k(chunk_for_deadline(
                    self._deadline_ms, tick_ms_hint, self.slots))
            else:
                self.tick_chunk = 1     # no hint: start small, the
                                        # EMA raises K at run time
        else:
            self.tick_chunk = tk
        # double-buffered chunk staging depth (0 = the serialized
        # stage->dispatch->drain loop, the parity baseline)
        if stage_ahead is None:
            s = os.environ.get('MXNET_TPU_SERVE_STAGE_AHEAD',
                               '').strip().lower()
            if s in ('0', 'off', 'none', 'false'):
                stage_ahead = 0
            else:
                try:
                    stage_ahead = int(s) if s else 1
                except ValueError:
                    stage_ahead = 1
        self._stage_ahead = max(0, int(stage_ahead))
        self._data_name = data_name
        self._data_shape = tuple(int(d) for d in data_shape)
        self._state_names = sorted(state_shapes)
        self._state_out_idx = [int(state_outputs[s])
                               for s in self._state_names]
        shapes = {data_name: (self.slots,) + self._data_shape}
        for s in self._state_names:
            shapes[s] = (self.slots,) + tuple(int(d)
                                              for d in state_shapes[s])
        ex = symbol.simple_bind(self._ctx, grad_req='null', **shapes)
        ex.copy_params_from(arg_params or {}, aux_params or {})
        for s in self._state_names:
            if s in (arg_params or {}):
                raise MXNetError('state %r must not be a parameter' % s)
        self._ex = ex
        self._symbol = symbol
        n_outs = ex._n_outputs
        bad = [i for i in self._state_out_idx
               if i < 0 or i >= n_outs]
        if bad:
            raise MXNetError('state_outputs index %r out of range '
                             '(%d outputs)' % (bad, n_outs))
        self._y_idx = [i for i in range(n_outs)
                       if i not in set(self._state_out_idx)]
        self._dtype = np.dtype(ex.arg_dict[data_name].dtype)
        self._step = _make_cont_step(ex, data_name, self._state_names,
                                     self._state_out_idx, init_states)
        # device-resident recurrent state (one buffer set, reused)
        import jax
        self._states = tuple(
            jax.numpy.zeros(ex.arg_dict[s].shape,
                            np.dtype(ex.arg_dict[s].dtype))
            for s in self._state_names)
        self._rng = jax.random.PRNGKey(0)
        # warm the single program + validate the slot-dim contract
        outs, states = self._step(
            jax.numpy.zeros((self.slots,) + self._data_shape,
                            self._dtype),
            jax.numpy.zeros((self.slots,), np.bool_),
            self._states, self._weights(), self._aux(), self._rng)
        for i, o in zip(self._y_idx, outs):
            if o.ndim == 0 or o.shape[0] != self.slots:
                raise MXNetError(
                    'ContinuousEngine requires row-independent outputs '
                    'with a leading slot dim: output %d has shape %r '
                    '(slots=%d) — a slot-reducing cell would mix '
                    'co-resident sequences' % (i, tuple(o.shape),
                                               self.slots))
        jax.block_until_ready(outs)
        self._chunk_steps = {}          # K -> chunked scan program
        self._lone_steps = {}           # K -> (lone rung fn, width)
        if self._auto:
            # warm EVERY rung at construction: the adaptive chooser
            # moves K at run time and steady state must stay at zero
            # compiles.  Rung 1 is a length-1 scan chunk, so every
            # auto K shares one dispatch path (and one cache kind).
            for k in self._rungs:
                self._warm_chunk_programs(init_states, k)
        elif self.tick_chunk > 1:
            self._warm_chunk_programs(init_states, self.tick_chunk)
        self._warm_snapshot = exec_cache.stats()
        # request plumbing
        self._cond = threading.Condition()
        self._queue = deque()
        self._active = [None] * self.slots
        self._closed = False
        self._halt = False              # export_state tick-loop stop
        # engine-local counters
        self._lock = threading.Lock()
        self._ticks = 0
        self._chunks = 0                # dispatches (== ticks at K=1)
        self._active_row_ticks = 0
        self._admitted = 0
        self._retired = 0
        self._boundary_wait_ms = 0.0    # est. queue wait behind slots
                                        # freed mid-chunk (masked until
                                        # the boundary)
        self._lone_hits = 0             # 1-slot rung dispatches
        self._exact_fill = 0            # staging-memset skips
        self._staged_chunks = 0         # chunks built in the shadow
                                        # buffer behind a live dispatch
        self._stage_overlap_ms = 0.0    # staging wall hidden that way
        self._sview = None              # staged slot view (staged loop
                                        # only): slot occupancy incl.
                                        # staged-but-unprocessed chunks
        self._last_done = None          # last chunk-completion stamp
                                        # (auto-K per-tick estimation)
        self._close_lock = threading.Lock()
        self._loop = threading.Thread(target=self._tick_loop,
                                      name='mxtpu-cont-batch',
                                      daemon=True)
        self._loop.start()
        self._started = True

    def _weights(self):
        ex = self._ex
        skip = set(self._state_names) | {self._data_name}
        return tuple(ex.arg_dict[n]._data for n in ex.arg_dict
                     if n not in skip)

    def _aux(self):
        ex = self._ex
        return tuple(ex.aux_dict[n]._data for n in ex.aux_dict)

    def _warm_chunk_programs(self, init_states, K):
        """Build + warm the K-tick scan program and the lone-request
        rung, and gate the rung on a BIT-equality probe against the
        full-width program: a 1-row gemm may round differently from
        the same row inside the slots-wide gemm on some backends
        (XLA CPU strength-reduces the batch-1 dot), and the rung must
        never trade bitwise parity for speed.  The probe ladders the
        rung width — try 1, then 2 (per-row gemm math is stable from
        batch 2 up, so the wider rung usually recovers parity at
        still a fraction of the full program) — and enables the first
        width that matches bit-for-bit; if none does (or the rung
        would not shrink the program, width >= slots), the rung is
        disabled and lone requests run the full program, costing
        nothing but the skipped shortcut."""
        import jax
        jnp = jax.numpy
        ex = self._ex
        self._chunk_steps[K] = _make_cont_chunk_step(
            ex, self._data_name, self._state_names,
            self._state_out_idx, init_states, K)
        n = int(np.prod((K, self.slots) + self._data_shape))
        probe = ((np.arange(n, dtype=np.float64) % 13) / 8.0 - 0.75)
        probe = probe.reshape(
            (K, self.slots) + self._data_shape).astype(self._dtype)

        def zstates():
            return tuple(
                jnp.zeros(ex.arg_dict[s].shape,
                          np.dtype(ex.arg_dict[s].dtype))
                for s in self._state_names)

        reset = jnp.ones((self.slots,), np.bool_)
        with _quiet_donation():         # CPU can't alias the donated
            fouts, fsts = self._chunk_steps[K](  # state buffers: noise
                jnp.asarray(probe), reset, zstates(),
                self._weights(), self._aux(), self._rng)
        for w in (1, 2):
            if w >= self.slots:
                break
            cand = _make_cont_lone_step(
                ex, self._data_name, self._state_names,
                self._state_out_idx, init_states, K, w)
            lxs = np.zeros((K, w) + self._data_shape, self._dtype)
            lxs[:, 0] = probe[:, 0]     # lane 0 = the full prog's slot 0
            lreset = np.zeros((w,), np.bool_)
            lreset[0] = True
            with _quiet_donation():
                louts, lsts = cand(
                    jnp.asarray(lxs), jnp.asarray(lreset),
                    np.int32(0), np.int32(0), zstates(),
                    self._weights(), self._aux(), self._rng)
            lone_ok = all(
                np.array_equal(np.asarray(f)[:, :1],
                               np.asarray(l)[:, :1])
                for f, l in zip(fouts, louts))
            lone_ok = lone_ok and all(
                np.array_equal(np.asarray(a)[0], np.asarray(b)[0])
                for a, b in zip(fsts, lsts))
            if lone_ok:
                self._lone_steps[K] = (cand, w)
                break
        # the probe calls consumed (donated) only their own zero
        # buffers — self._states is untouched and still pristine

    def _quantize_k(self, k):
        """Largest warmed rung <= k (rung 1 always exists), so the
        adaptive chooser only ever lands on a compiled program."""
        best = self._rungs[0]
        for r in self._rungs:
            if r <= k:
                best = r
        return best

    # -- public API -----------------------------------------------------
    def infer(self, seq):
        """Submit ONE sequence (np array (T,) + data_shape; T >= 1)
        and block for its per-step outputs — a list of np arrays, one
        per non-state model output, each (T,) + that output's
        per-step shape.  Thread-safe; requests admit into free slots
        at tick boundaries."""
        return self.infer_many([seq])[0]

    def infer_many(self, seqs):
        """Submit several sequences ATOMICALLY (one queue hold — the
        tick loop sees all of them at its next admission boundary, so
        slot packing is deterministic for a quiet engine) and block
        for all answers.  Returns a list of per-sequence output
        lists, in submission order."""
        reqs = [self._validate(s) for s in seqs]
        with self._cond:
            if self._closed:
                raise MXNetError('ContinuousEngine is closed')
            if len(self._queue) + len(reqs) > self.max_queue:
                profiler.add_fleet_stats(shed_requests=1)
                raise Overloaded('<continuous>', len(self._queue),
                                 float('inf'), None)
            self._queue.extend(reqs)
            self._cond.notify_all()
        for r in reqs:
            r.event.wait()
        for r in reqs:
            if r.error is not None:
                raise r.error
        return [r.outputs for r in reqs]

    def _validate(self, seq):
        a = seq.asnumpy() if hasattr(seq, 'asnumpy') else \
            np.asarray(seq)
        a = np.ascontiguousarray(a, dtype=self._dtype)
        if a.ndim != 1 + len(self._data_shape) or \
                tuple(a.shape[1:]) != self._data_shape or \
                a.shape[0] < 1:
            raise MXNetError('sequence shape %r != (T,)+%r with T>=1'
                             % (tuple(a.shape), self._data_shape))
        return _ContRequest(a)

    def stats(self):
        """Engine-local continuous-batching counters: ticks
        (timesteps advanced — at tick_chunk=1 also the dispatch
        count), chunks (XLA dispatches: ticks/K), slot utilization
        (active row-ticks / slot-ticks — 1.0 means every slot of
        every tick advanced a real sequence), admit/retire totals,
        the chunk-boundary latency estimate and fast-path hit
        counters, and the zero-compile check relative to
        construction."""
        with self._lock:
            ticks = self._ticks
            lone = self._lone_steps.get(self.tick_chunk)
            out = {
                'ticks': ticks,
                'chunks': self._chunks,
                'tick_chunk': self.tick_chunk,
                'active_row_ticks': self._active_row_ticks,
                'slot_ticks': ticks * self.slots,
                'utilization': (self._active_row_ticks /
                                (ticks * self.slots) if ticks else 0.0),
                'admitted': self._admitted,
                'retired': self._retired,
                'slots': self.slots,
                'convoy': self.convoy,
                'boundary_wait_ms': round(self._boundary_wait_ms, 3),
                'lone_fast_path_hits': self._lone_hits,
                'exact_fill_admits': self._exact_fill,
                'lone_fast_path': lone is not None,
                'lone_fast_path_width': lone[1] if lone else 0,
                'stage_ahead': self._stage_ahead,
                'staged_chunks': self._staged_chunks,
                'stage_overlap_ms': round(self._stage_overlap_ms, 3),
                'auto_tick_chunk': self._auto,
                'tick_ms_ema': round(self._tick_ms_ema, 4)
                if self._tick_ms_ema is not None else 0.0,
                'auto_k_decisions': self._auto_decisions,
            }
        now = exec_cache.stats()
        snap = self._warm_snapshot
        out['compiles_after_warmup'] = now['misses'] - snap['misses']
        out['compile_s_after_warmup'] = round(
            now['total_compile_s'] - snap['total_compile_s'], 6)
        return out

    def backlog_rows(self):
        with self._cond:
            # the staged view supersedes _active when the staged loop
            # runs: a request admitted into an in-flight chunk is
            # neither queued nor (yet) in _active, but it IS backlog
            slots_src = self._sview if self._sview is not None \
                else self._active
            return len(self._queue) + \
                sum(1 for s in slots_src
                    if s is not None and not s.event.is_set())

    def service_estimate(self):
        return None                     # per-tick model: no batch EMA

    def resident_bytes(self):
        return _weight_bytes(self._ex)

    # -- hot-swap sequence migration (PERF round 18) --------------------
    def export_state(self, timeout=30):
        """Halt the tick loop at a tick boundary and export EVERY
        accepted request — in-flight slots (cell state rows + position
        + partial outputs) and the waiting queue — for re-admission
        into a replacement engine (`admit_state`).  This engine is
        closed afterwards (new submits are rejected; the blocked
        infer() callers stay blocked and are completed by the engine
        the requests migrate INTO), so an engine hot-swap loses zero
        accepted sequence requests.

        When the model is unchanged the migrated run is BIT-IDENTICAL
        to an unswapped one: the exported state rows are exactly the
        post-tick device values (float round-trips host<->device are
        bitwise), the new engine writes them into its slot buffers
        instead of the in-graph reset, and positions/partial outputs
        continue where they stopped.  MXNET_TPU_FAULT_SWAP_DROP_STATE
        drops the exported slot state (the degradation drill): those
        requests REPLAY from t=0 on re-admission — still zero lost
        requests, paid in recomputation (loop_swap_dropped_slots)."""
        from .elastic import fault_knob
        with self._cond:
            if self._closed:
                raise MXNetError('ContinuousEngine is closed')
            self._closed = True         # reject new submits
            self._halt = True
            self._cond.notify_all()
        if self._started:
            self._loop.join(timeout=timeout)
            if self._loop.is_alive():
                # the halt did not land (a wedged tick): UNDO it so
                # the engine keeps serving its accepted requests —
                # leaving the flags set would strand every in-flight
                # caller blocked forever with no recovery path
                with self._cond:
                    self._halt = False
                    self._closed = False
                    self._cond.notify_all()
                self._loop.join(timeout=1.0)
                if not self._loop.is_alive():
                    # the loop observed the halt in the undo window
                    # and exited: restart it (state is intact — it
                    # parks/resumes at tick boundaries)
                    self._loop = threading.Thread(
                        target=self._tick_loop,
                        name='mxtpu-cont-batch', daemon=True)
                    self._loop.start()
                raise MXNetError('export_state: tick loop did not '
                                 'halt within %ss (engine kept '
                                 'serving; retry the swap)' % timeout)
            self._started = False
        drop = fault_knob('SWAP_DROP_STATE') is not None
        states_np = [np.asarray(s) for s in self._states]
        requests = []
        n_dropped = 0
        with self._cond:
            for i, r in enumerate(self._active):
                if r is None:
                    continue
                if drop:
                    # injected state loss: replay from the start — the
                    # request still completes (deterministic cell), at
                    # recompute cost
                    r.mig_state = None
                    r.t = 0
                    r.ys = [[] for _ in self._y_idx]
                    n_dropped += 1
                else:
                    r.mig_state = {
                        n: states_np[k][i].copy()
                        for k, n in enumerate(self._state_names)}
                requests.append(r)
                self._active[i] = None
            requests.extend(self._queue)
            self._queue.clear()
        if n_dropped:
            profiler.add_loop_stats(swap_dropped_slots=n_dropped)
        return {'requests': requests,
                'data_shape': self._data_shape,
                'state_names': tuple(self._state_names),
                'n_outputs': len(self._y_idx),
                'dropped': n_dropped}

    def admit_state(self, exported, model_changed=False):
        """Re-admit another engine's `export_state()` payload into
        THIS engine: in-flight requests resume from their exported
        cell state + position (their original infer() callers wake
        when the sequences finish HERE), queued ones join the queue.
        Admission bypasses max_queue — these requests were already
        ACCEPTED by the fleet and must not be shed by the swap.

        `model_changed=True` declares that this engine's weights
        differ from the exporting engine's (a hot-swap promotion):
        migrated in-flight slots finish their remaining steps under
        the NEW weights — and in-flight slots whose state was DROPPED
        (SWAP_DROP_STATE) replay entirely under them — so their
        outputs diverge from an unswapped run; both are counted
        (loop_swap_divergent_slots), never hidden.  Returns the
        number of migrated in-flight slots."""
        if tuple(exported['data_shape']) != self._data_shape or \
                tuple(exported['state_names']) != \
                tuple(self._state_names) or \
                int(exported.get('n_outputs', len(self._y_idx))) != \
                len(self._y_idx):
            raise MXNetError(
                'admit_state: incompatible engines (data_shape %r vs '
                '%r, states %r vs %r, outputs %s vs %d)'
                % (tuple(exported['data_shape']), self._data_shape,
                   tuple(exported['state_names']),
                   tuple(self._state_names),
                   exported.get('n_outputs'), len(self._y_idx)))
        reqs = list(exported['requests'])
        migrated = sum(1 for r in reqs if r.mig_state is not None)
        with self._cond:
            if self._closed:
                raise MXNetError('ContinuousEngine is closed')
            self._queue.extend(reqs)
            self._cond.notify_all()
        profiler.add_loop_stats(
            swap_migrated_slots=migrated,
            swap_divergent_slots=(migrated +
                                  int(exported.get('dropped', 0)))
            if model_changed else 0)
        return migrated

    # -- tick loop ------------------------------------------------------
    def _tick_loop(self):
        import jax
        jnp = jax.numpy
        if self._stage_ahead and (self._auto or self.tick_chunk > 1):
            self._staged_loop(jnp)
        else:
            self._serial_loop(jnp)

    def _serial_loop(self, jnp):
        """The unbuffered stage->dispatch->drain loop: the parity
        baseline double-buffered staging (stage_ahead=0 forces it)
        is gated against, and the only path at fixed tick_chunk=1."""
        while True:
            admitted = []
            with self._cond:
                while not self._closed and not self._halt and \
                        not self._queue and \
                        all(s is None for s in self._active):
                    self._cond.wait()
                if self._halt:
                    # export_state(): stop at the tick boundary and
                    # leave queue + in-flight slots INTACT for the
                    # handover (close() drains them instead)
                    break
                if self._closed and not self._queue and \
                        all(s is None for s in self._active):
                    break
                # admission at the tick boundary: continuous mode
                # fills any free slot NOW; convoy mode only admits
                # into an all-empty batch (then runs that cohort to
                # its longest length — the baseline being beaten)
                can_admit = any(s is None for s in self._active) if \
                    not self.convoy else \
                    all(s is None for s in self._active)
                if can_admit:
                    for i in range(self.slots):
                        if self._active[i] is None and self._queue:
                            req = self._queue.popleft()
                            if req.ys is None:
                                req.ys = [[] for _ in self._y_idx]
                            self._active[i] = req
                            admitted.append(i)
            active = [(i, r) for i, r in enumerate(self._active)
                      if r is not None]
            if not active:
                continue
            reset = np.zeros((self.slots,), np.bool_)
            mig = []
            for i in admitted:
                r = self._active[i]
                if r is not None and r.mig_state is not None:
                    # migrated mid-flight slot (hot-swap re-admission):
                    # its cell state is the EXPORTED rows, not the
                    # fresh-sequence init — written into the state
                    # buffers below instead of the in-graph reset
                    mig.append((i, r.mig_state))
                    r.mig_state = None
                else:
                    reset[i] = True
            if mig:
                bufs = [np.array(s) for s in self._states]
                for i, st in mig:
                    for k, n in enumerate(self._state_names):
                        bufs[k][i] = st[n]
                self._states = tuple(jnp.asarray(b) for b in bufs)
            if self.tick_chunk == 1 and not self._auto:
                self._tick_once(active, admitted, reset, jnp)
            else:
                # auto mode always dispatches through the chunk
                # programs (rung 1 is a length-1 scan), so a K move
                # never switches dispatch paths
                self._chunk_once(active, admitted, reset, jnp)

    def _tick_once(self, active, admitted, reset, jnp):
        """One timestep for every slot — the LITERAL unchunked
        dispatch path (tick_chunk=1, the parity baseline chunked mode
        A/Bs against)."""
        x = np.zeros((self.slots,) + self._data_shape, self._dtype)
        for i, r in active:
            x[i] = r.seq[r.t]
        try:
            outs, self._states = self._step(
                jnp.asarray(x), jnp.asarray(reset), self._states,
                self._weights(), self._aux(), self._rng)
            np_outs = [np.asarray(o) for o in outs]
        except Exception as e:          # surface to every co-resident
            with self._cond:
                for i, r in active:
                    r.error = e
                    r.event.set()
                    self._active[i] = None
            return
        retired = 0
        for i, r in active:
            for k, o in enumerate(np_outs):
                r.ys[k].append(o[i].copy())
            r.t += 1
            if r.t >= r.length:
                r.outputs = [np.stack(rows) for rows in r.ys]
                r.event.set()
                retired += 1
                with self._cond:
                    self._active[i] = None
        with self._lock:
            self._ticks += 1
            self._chunks += 1
            self._active_row_ticks += len(active)
            self._admitted += len(admitted)
            self._retired += retired
        profiler.add_fleet_stats(
            cont_ticks=1, cont_active_row_ticks=len(active),
            cont_slot_ticks=self.slots,
            cont_admitted=len(admitted), cont_retired=retired)

    def _chunk_once(self, active, admitted, reset, jnp):
        """K timesteps for every slot in ONE donated dispatch
        (tick_chunk=K): per-slot inputs for this chunk are staged as
        (K, slots)+data_shape, the scan program applies the admission
        reset before tick 0 and stacks (K, slots, ...) outputs, and
        each request's own min(K, remaining) rows are sliced out
        host-side.  A slot whose sequence ends mid-chunk stays MASKED
        (zero inputs, outputs discarded) until the boundary — those
        wasted slot-ticks are priced into boundary_wait_ms when
        requests were actually waiting.  Fast paths: a lone active
        request runs the narrow rung (batch = the probe-gated rung
        width); a chunk with every slot active for all K ticks skips
        the staging memset (np.empty)."""
        K = self.tick_chunk
        ns = [min(K, r.length - r.t) for _, r in active]
        lone_ent = self._lone_steps.get(K) if len(active) == 1 \
            else None
        lone = lone_ent is not None
        exact = False
        lane = 0
        t0 = time.perf_counter()
        try:
            if lone:
                i, r = active[0]
                n = ns[0]
                W = lone_ent[1]
                start = min(i, self.slots - W)
                lane = i - start        # request's lane in the window
                if n == K and W == 1:
                    # exact-fill staging: the request's own contiguous
                    # rows ARE the chunk — a reshaped view, no copy
                    xs = r.seq[r.t:r.t + K].reshape(
                        (K, 1) + self._data_shape)
                else:
                    xs = np.zeros((K, W) + self._data_shape,
                                  self._dtype)
                    xs[:n, lane] = r.seq[r.t:r.t + n]
                lreset = np.zeros((W,), np.bool_)
                lreset[lane] = reset[i]
                outs, self._states = lone_ent[0](
                    jnp.asarray(xs), jnp.asarray(lreset),
                    np.int32(start), np.int32(lane), self._states,
                    self._weights(), self._aux(), self._rng)
            else:
                exact = len(active) == self.slots and \
                    all(n == K for n in ns)
                xs = (np.empty if exact else np.zeros)(
                    (K, self.slots) + self._data_shape, self._dtype)
                for (i, r), n in zip(active, ns):
                    xs[:n, i] = r.seq[r.t:r.t + n]
                outs, self._states = self._chunk_steps[K](
                    jnp.asarray(xs), jnp.asarray(reset), self._states,
                    self._weights(), self._aux(), self._rng)
            np_outs = [np.asarray(o) for o in outs]
        except Exception as e:          # surface to every co-resident
            with self._cond:
                for i, r in active:
                    r.error = e
                    r.event.set()
                    self._active[i] = None
            return
        wall_ms = (time.perf_counter() - t0) * 1e3
        retired = 0
        wasted = 0                      # masked slot-ticks behind the
        for (i, r), n in zip(active, ns):   # boundary (retire < K)
            col = lane if lone else i
            for k, o in enumerate(np_outs):
                for t in range(n):
                    r.ys[k].append(np.array(o[t, col]))
            r.t += n
            if r.t >= r.length:
                r.outputs = [np.stack(rows) for rows in r.ys]
                r.event.set()
                retired += 1
                wasted += K - n
                with self._cond:
                    self._active[i] = None
        with self._cond:
            waiting = len(self._queue)
        wait_ms = 0.0
        if wasted and waiting:
            # the boundary-latency estimate: slot-ticks burned masked
            # while requests queued, priced at this chunk's measured
            # per-tick wall time — the cost of quantized admission
            wait_ms = wasted * wall_ms / K
        with self._lock:
            self._ticks += K
            self._chunks += 1
            self._active_row_ticks += sum(ns)
            self._admitted += len(admitted)
            self._retired += retired
            self._boundary_wait_ms += wait_ms
            self._lone_hits += int(lone)
            self._exact_fill += int(exact)
        profiler.add_fleet_stats(
            cont_ticks=K, cont_active_row_ticks=sum(ns),
            cont_slot_ticks=K * self.slots,
            cont_admitted=len(admitted), cont_retired=retired,
            cont_chunks_dispatched=1, cont_chunk_ticks=K,
            cont_lone_fast_path=int(lone),
            cont_exact_fill_admits=int(exact),
            cont_boundary_wait_ms=wait_ms)
        if self._auto:
            self._auto_update(wall_ms, K)

    # -- double-buffered chunk staging (PERF round 21) ------------------
    def _staged_loop(self, jnp):
        """The pipelined tick loop: stage chunk t+1 into the shadow
        buffer and ENQUEUE its dispatch while chunk t's results are
        still in flight, then drain t's outputs — the boundary cost
        drops to a buffer swap, and the host staging wall is hidden
        behind device compute (cont_stage_overlap_ms).  Depth is
        1 + stage_ahead dispatches in flight (default 2: classic
        double buffering).  Chunk answers are BIT-identical to the
        serialized loop: staging consumes only host-known state
        (positions, queue order, the request's own input rows), and
        the dispatched programs are the very same ones."""
        with self._cond:
            # rebuild the staged view from canonical slots (non-empty
            # after an export_state undo restarted the loop)
            self._sview = list(self._active)
        inflight = deque()
        depth = 1 + self._stage_ahead
        while True:
            with self._cond:
                while not self._closed and not self._halt and \
                        not self._queue and \
                        all(s is None for s in self._sview) and \
                        not inflight:
                    self._cond.wait()
                if self._halt:
                    break
                if self._closed and not self._queue and \
                        all(s is None for s in self._sview) and \
                        not inflight:
                    break
            while len(inflight) < depth:
                t0 = time.perf_counter()
                busy = bool(inflight)   # a dispatch is on the device
                chunk = self._stage_next(jnp)
                if chunk is None:
                    break
                self._dispatch_staged(chunk, jnp)
                inflight.append(chunk)
                if busy:
                    dt = (time.perf_counter() - t0) * 1e3
                    with self._lock:
                        self._staged_chunks += 1
                        self._stage_overlap_ms += dt
                    profiler.add_fleet_stats(cont_staged_chunks=1,
                                             cont_stage_overlap_ms=dt)
                    profiler.add_overlap_stats(stage_chunks=1,
                                               stage_overlap_ms=dt)
            if inflight:
                self._process_staged(inflight.popleft(), jnp)
        # halt (export_state): DRAIN the pipeline atomically — every
        # dispatched chunk completes and folds into positions/partial
        # outputs/states before the loop exits, so the export sees one
        # consistent chunk boundary.  Nothing is ever staged without
        # being dispatched in the same step, so there is no discarded
        # shadow state to unwind.
        while inflight:
            self._process_staged(inflight.popleft(), jnp)

    def _stage_next(self, jnp):
        """Admission + host staging for the NEXT chunk against the
        staged slot view.  Retires are deterministic — a slot frees
        when its request's STAGED position reaches the sequence
        length, no device output needed — so this runs correctly
        while earlier chunks are still executing.  Returns the filled
        shadow buffer, or None when no slot would be active."""
        with self._cond:
            if self._halt:
                return None
            view = self._sview
            for i in range(self.slots):
                r = view[i]
                if r is not None and r.staged_t >= r.length:
                    view[i] = None      # frees at the staged boundary
            can_admit = any(s is None for s in view) \
                if not self.convoy else all(s is None for s in view)
            admits = []
            if can_admit:
                for i in range(self.slots):
                    if view[i] is None and self._queue:
                        req = self._queue.popleft()
                        req.staged_t = req.t
                        if req.ys is None:
                            req.ys = [[] for _ in self._y_idx]
                        view[i] = req
                        admits.append((i, req))
            active = [(i, r) for i, r in enumerate(view)
                      if r is not None]
            waiting = len(self._queue)
        if not active:
            return None
        K = self.tick_chunk
        reset = np.zeros((self.slots,), np.bool_)
        mig = []
        for i, req in admits:
            if req.mig_state is not None:
                mig.append((i, req.mig_state))
                req.mig_state = None
            else:
                reset[i] = True
        ns = [min(K, r.length - r.staged_t) for _, r in active]
        ch = _StagedChunk(K)
        ch.mig = mig
        ch.admits = admits
        ch.waiting = waiting
        lone_ent = self._lone_steps.get(K) if len(active) == 1 \
            else None
        if lone_ent is not None:
            i, r = active[0]
            n = ns[0]
            W = lone_ent[1]
            start = min(i, self.slots - W)
            lane = i - start
            if n == K and W == 1:
                xs = r.seq[r.staged_t:r.staged_t + K].reshape(
                    (K, 1) + self._data_shape)
            else:
                xs = np.zeros((K, W) + self._data_shape, self._dtype)
                xs[:n, lane] = r.seq[r.staged_t:r.staged_t + n]
            lreset = np.zeros((W,), np.bool_)
            lreset[lane] = reset[i]
            ch.lone, ch.lane, ch.start = True, lane, start
            ch.xs, ch.reset = xs, lreset
        else:
            exact = len(active) == self.slots and \
                all(n == K for n in ns)
            xs = (np.empty if exact else np.zeros)(
                (K, self.slots) + self._data_shape, self._dtype)
            for (i, r), n in zip(active, ns):
                xs[:n, i] = r.seq[r.staged_t:r.staged_t + n]
            ch.exact = exact
            ch.xs, ch.reset = xs, reset
        ch.rows = [(i, r, n) for (i, r), n in zip(active, ns)]
        for _i, r, n in ch.rows:
            r.staged_t += n
        return ch

    def _dispatch_staged(self, ch, jnp):
        """Enqueue the staged chunk's dispatch.  The states argument
        is the PREVIOUS chunk's output futures — XLA executes in
        submission order, so this lands on the device queue right
        behind it with no host sync.  A dispatch-call exception is
        parked on the chunk and surfaced at process time."""
        try:
            if ch.mig:
                # hot-swap re-admission rows must be host-written into
                # the canonical buffers: materializing blocks on any
                # in-flight chunk first — rare, swap-time only
                bufs = [np.array(s) for s in self._states]
                for i, st in ch.mig:
                    for k, n in enumerate(self._state_names):
                        bufs[k][i] = st[n]
                self._states = tuple(jnp.asarray(b) for b in bufs)
            ch.t_disp = time.perf_counter()
            if ch.lone:
                ent = self._lone_steps[ch.K]
                ch.outs, self._states = ent[0](
                    jnp.asarray(ch.xs), jnp.asarray(ch.reset),
                    np.int32(ch.start), np.int32(ch.lane),
                    self._states, self._weights(), self._aux(),
                    self._rng)
            else:
                ch.outs, self._states = self._chunk_steps[ch.K](
                    jnp.asarray(ch.xs), jnp.asarray(ch.reset),
                    self._states, self._weights(), self._aux(),
                    self._rng)
        except Exception as e:
            ch.error = e

    def _process_staged(self, ch, jnp):
        """Drain one dispatched chunk: block on its outputs, slice
        per-request rows, advance CANONICAL positions, retire, and
        fold the counters — the same bookkeeping as the serialized
        loop, shifted one pipeline stage later."""
        try:
            if ch.error is not None:
                raise ch.error
            np_outs = [np.asarray(o) for o in ch.outs]
        except Exception as e:          # surface to every co-resident
            with self._cond:
                for i, r, _n in ch.rows:
                    r.error = e
                    r.event.set()
                    self._active[i] = None
                    if self._sview[i] is r:
                        self._sview[i] = None
            # a failed async chunk poisons its donated-state outputs:
            # rebuild zero state so the next admission (in-graph
            # reset) starts clean
            self._states = tuple(
                jnp.zeros(self._ex.arg_dict[s].shape,
                          np.dtype(self._ex.arg_dict[s].dtype))
                for s in self._state_names)
            return
        K = ch.K
        now = time.perf_counter()
        wall_ms = (now - ch.t_disp) * 1e3
        retired = 0
        wasted = 0
        for i, r, n in ch.rows:
            col = ch.lane if ch.lone else i
            for k, o in enumerate(np_outs):
                for t in range(n):
                    r.ys[k].append(np.array(o[t, col]))
            r.t += n
            if r.t >= r.length:
                r.outputs = [np.stack(rows) for rows in r.ys]
                r.event.set()
                retired += 1
                wasted += K - n
                with self._cond:
                    self._active[i] = None
                    if self._sview[i] is r:
                        self._sview[i] = None
            else:
                with self._cond:
                    self._active[i] = r
        wait_ms = 0.0
        if wasted and ch.waiting:
            # priced against the STAGING-time queue depth: the
            # pipeline may have admitted the waiter into the next
            # staged chunk already, but it still waited behind these
            # masked slot-ticks
            wait_ms = wasted * wall_ms / K
        ns_sum = sum(n for _i, _r, n in ch.rows)
        with self._lock:
            self._ticks += K
            self._chunks += 1
            self._active_row_ticks += ns_sum
            self._admitted += len(ch.admits)
            self._retired += retired
            self._boundary_wait_ms += wait_ms
            self._lone_hits += int(ch.lone)
            self._exact_fill += int(ch.exact)
        profiler.add_fleet_stats(
            cont_ticks=K, cont_active_row_ticks=ns_sum,
            cont_slot_ticks=K * self.slots,
            cont_admitted=len(ch.admits), cont_retired=retired,
            cont_chunks_dispatched=1, cont_chunk_ticks=K,
            cont_lone_fast_path=int(ch.lone),
            cont_exact_fill_admits=int(ch.exact),
            cont_boundary_wait_ms=wait_ms)
        if self._auto:
            # a pipelined chunk's dispatch->done wall includes the
            # previous chunk's remaining device time; the completion-
            # to-completion delta is the honest per-chunk estimate
            # when the pipeline is busy, and the raw wall when idle —
            # take the smaller
            last = self._last_done
            est = wall_ms if last is None else \
                min(wall_ms, (now - last) * 1e3)
            self._auto_update(est, K)
        self._last_done = now

    def _auto_update(self, wall_ms, K):
        """Fold one chunk's measured wall into the per-tick EMA and
        re-derive K against the SLO deadline (tick_chunk='auto'),
        quantized DOWN to the warmed rung ladder so steady state
        performs zero compiles.  Runs on the tick-loop thread only."""
        tick_ms = wall_ms / K
        ema = self._tick_ms_ema
        self._tick_ms_ema = tick_ms if ema is None else \
            _TICK_EMA_ALPHA * tick_ms + (1 - _TICK_EMA_ALPHA) * ema
        new_k = self._quantize_k(chunk_for_deadline(
            self._deadline_ms, self._tick_ms_ema, self.slots))
        if new_k != self.tick_chunk:
            self.tick_chunk = new_k
            with self._lock:
                self._auto_decisions += 1
            profiler.add_overlap_stats(auto_k=new_k,
                                       auto_k_decisions=1)

    # -- lifecycle ------------------------------------------------------
    def close(self, timeout=30):
        """Reject-new + drain (queued and in-flight sequences finish)
        + join the tick loop.  Idempotent and safe to call from a
        registry eviction thread while another thread is mid-infer()
        — same contract as InferenceEngine.close()."""
        with self._close_lock:
            if self._closed and not self._started:
                return self
            with self._cond:
                self._closed = True
                self._cond.notify_all()
            if self._started:
                self._loop.join(timeout=timeout)
                if self._loop.is_alive():
                    import warnings
                    warnings.warn('ContinuousEngine.close(): tick loop '
                                  'still running after %ss; call '
                                  'close() again to re-join' % timeout)
                else:
                    self._started = False
        return self

    @property
    def closed(self):
        return self._closed

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close(timeout=5)
        except Exception:               # interpreter teardown
            pass


def _make_cont_step(ex, data_name, state_names, state_out_idx,
                    init_states):
    """The continuous batcher's single step program: one timestep for
    every slot, with per-slot state reset folded INTO the graph
    (`where(reset, init, state)`) so admission costs no second
    program.  Cached process-wide under the cell executor's graph
    signature (zeros-init only — custom init values are baked-in
    constants, see ContinuousEngine docs), so a re-created engine
    compiles nothing."""
    import jax
    jnp = jax.numpy
    names = list(ex.arg_dict)
    data_pos = names.index(data_name)
    state_pos = [names.index(s) for s in state_names]
    skip = set(state_names) | {data_name}
    other_pos = [i for i, n in enumerate(names) if n not in skip]
    y_idx = [i for i in range(ex._n_outputs)
             if i not in set(state_out_idx)]
    key = None
    if ex._sig is not None and not init_states:
        key = exec_cache.cont_step_key(ex._sig, 'cont_step',
                                       data_name, state_names,
                                       state_out_idx)
        fn = exec_cache.get(key)
        if fn is not None:
            return fn
    inits = None
    if init_states:
        inits = [jnp.asarray(np.asarray(init_states[s]))
                 for s in state_names]
    raw = ex.raw_forward
    n_args = len(names)

    def step(x, reset, state_vals, weight_vals, aux_vals, rng):
        merged = [None] * n_args
        merged[data_pos] = x
        for k, (i, v) in enumerate(zip(state_pos, state_vals)):
            mask = reset.reshape((-1,) + (1,) * (v.ndim - 1))
            init = inits[k] if inits is not None else \
                jnp.zeros((), v.dtype)
            merged[i] = jnp.where(mask, init, v)
        for i, v in zip(other_pos, weight_vals):
            merged[i] = v
        outs, _ = raw(tuple(merged), aux_vals, rng)
        return (tuple(outs[i] for i in y_idx),
                tuple(outs[i] for i in state_out_idx))

    fn = exec_cache.TimedJit(jax.jit(step))
    if key is not None:
        exec_cache.put(key, fn)
    return fn


def _cont_cell_plumbing(ex, data_name, state_names, state_out_idx,
                        init_states):
    """Shared argument plumbing for the chunked cont programs: the
    cell executor's positional layout, the non-state output indices,
    and the admission-init values (zeros unless init_states bakes
    constants in — which also disables exec_cache sharing, same rule
    as the single-tick program)."""
    import jax
    jnp = jax.numpy
    names = list(ex.arg_dict)
    data_pos = names.index(data_name)
    state_pos = [names.index(s) for s in state_names]
    skip = set(state_names) | {data_name}
    other_pos = [i for i, n in enumerate(names) if n not in skip]
    y_idx = [i for i in range(ex._n_outputs)
             if i not in set(state_out_idx)]
    inits = None
    if init_states:
        inits = [jnp.asarray(np.asarray(init_states[s]))
                 for s in state_names]
    return (len(names), data_pos, state_pos, other_pos, y_idx, inits)


def _make_cont_chunk_step(ex, data_name, state_names, state_out_idx,
                          init_states, chunk):
    """The chunked tick program: K timesteps for every slot as ONE
    donated dispatch — `lax.scan` over the (K, slots)-leading input
    chunk, with the admission reset (`where(reset, init, state)`)
    applied before the first tick and the per-tick outputs stacked
    (K, slots, ...) for host-side per-request slicing.  Each scan
    iteration is the SAME math as the single-tick program (a
    continuing slot's where(False, ...) there is the identity), so
    chunked serving stays bit-identical to the unchunked loop while
    dispatch overhead amortizes K-fold.  The state buffers are
    donated: the engine only ever keeps the returned ones.  Cached
    process-wide under exec_cache.cont_step_key (which carries K; the
    executor signature already carries the slots-wide shapes and any
    quantization), zeros-init only."""
    import jax
    jnp = jax.numpy
    (n_args, data_pos, state_pos, other_pos, y_idx,
     inits) = _cont_cell_plumbing(ex, data_name, state_names,
                                  state_out_idx, init_states)
    key = None
    if ex._sig is not None and not init_states:
        key = exec_cache.cont_step_key(ex._sig, 'cont_chunk_step',
                                       data_name, state_names,
                                       state_out_idx, chunk=chunk)
        fn = exec_cache.get(key)
        if fn is not None:
            return fn
    raw = ex.raw_forward

    def chunk_step(xs, reset, state_vals, weight_vals, aux_vals, rng):
        def tick(states, x):
            merged = [None] * n_args
            merged[data_pos] = x
            for i, v in zip(state_pos, states):
                merged[i] = v
            for i, v in zip(other_pos, weight_vals):
                merged[i] = v
            outs, _ = raw(tuple(merged), aux_vals, rng)
            return (tuple(outs[i] for i in state_out_idx),
                    tuple(outs[i] for i in y_idx))

        states0 = []
        for k, v in enumerate(state_vals):
            mask = reset.reshape((-1,) + (1,) * (v.ndim - 1))
            init = inits[k] if inits is not None else \
                jnp.zeros((), v.dtype)
            states0.append(jnp.where(mask, init, v))
        final_states, ys = jax.lax.scan(tick, tuple(states0), xs)
        return ys, final_states

    fn = exec_cache.TimedJit(jax.jit(chunk_step, donate_argnums=(2,)))
    if key is not None:
        exec_cache.put(key, fn)
    return fn


def _make_cont_lone_step(ex, data_name, state_names, state_out_idx,
                         init_states, chunk, width):
    """The lone-request rung: when exactly one slot is active, skip
    the full-`slots` program and run its K ticks at batch `width` —
    the serving analog of the coalescer's lone-request staging
    shortcut, except the program SHAPE shrinks too.  A `width`-row
    window of state starting at `start` is dynamic-sliced out of the
    full buffers IN graph; the request lives in lane `lane` of that
    window (both host-computed: start = min(slot, slots - width)),
    and only the request's final row is written back — the padding
    lanes run on zero inputs and their evolved state is discarded, so
    the engine's state invariants (export_state, later full-width
    chunks) are untouched.  Width is usually 1; some backends lower a
    batch-1 cell with different rounding than the wide program, so
    the engine ladders to width 2 (per-row gemm math is stable from
    batch 2 up) and enables whichever width first passes its
    build-time bitwise-parity probe against the full program
    (ContinuousEngine._warm_chunk_programs).  Cached under its own
    cont_step_key kind (carrying K and width) so it never aliases the
    full-width chunk program or a different-width rung."""
    import jax
    jnp = jax.numpy
    (n_args, data_pos, state_pos, other_pos, y_idx,
     inits) = _cont_cell_plumbing(ex, data_name, state_names,
                                  state_out_idx, init_states)
    key = None
    if ex._sig is not None and not init_states:
        key = exec_cache.cont_step_key(ex._sig, 'cont_lone_step',
                                       data_name, state_names,
                                       state_out_idx, chunk=chunk,
                                       width=width)
        fn = exec_cache.get(key)
        if fn is not None:
            return fn
    raw = ex.raw_forward

    def lone_step(xs, reset, start, lane, state_vals, weight_vals,
                  aux_vals, rng):
        def tick(states, x):
            merged = [None] * n_args
            merged[data_pos] = x
            for i, v in zip(state_pos, states):
                merged[i] = v
            for i, v in zip(other_pos, weight_vals):
                merged[i] = v
            outs, _ = raw(tuple(merged), aux_vals, rng)
            return (tuple(outs[i] for i in state_out_idx),
                    tuple(outs[i] for i in y_idx))

        rows = []
        for k, v in enumerate(state_vals):
            win = jax.lax.dynamic_slice_in_dim(v, start, width, axis=0)
            mask = reset.reshape((-1,) + (1,) * (win.ndim - 1))
            init = inits[k] if inits is not None else \
                jnp.zeros((), win.dtype)
            rows.append(jnp.where(mask, init, win))
        final_rows, ys = jax.lax.scan(tick, tuple(rows), xs)
        new_states = tuple(
            jax.lax.dynamic_update_slice_in_dim(
                v, jax.lax.dynamic_slice_in_dim(r, lane, 1, axis=0),
                start + lane, axis=0)
            for v, r in zip(state_vals, final_rows))
        return ys, new_states

    fn = exec_cache.TimedJit(jax.jit(lone_step, donate_argnums=(4,)))
    if key is not None:
        exec_cache.put(key, fn)
    return fn


# ---------------------------------------------------------------------------
# HTTP front (stdlib http.server — no new deps)
# ---------------------------------------------------------------------------

try:
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
except ImportError:                     # py<3.7 has no Threading server
    from http.server import BaseHTTPRequestHandler, HTTPServer
    from socketserver import ThreadingMixIn

    class ThreadingHTTPServer(ThreadingMixIn, HTTPServer):
        daemon_threads = True


class _FleetHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True


class _FleetHandler(BaseHTTPRequestHandler):
    """POST /v1/models/<name>:predict   {"inputs": {name: nested-list}}
                                     or {"instances": nested-list}
       GET  /healthz                    liveness
       GET  /statsz                     registry + fleet counters

    Error mapping: unknown model -> 404, malformed request -> 400,
    `Overloaded` / admission-full -> 429 (+ Retry-After), registry
    closed -> 503, anything else -> 500.  Every predict passes the
    front's bounded in-flight gate FIRST, so a client flood turns
    into fast 429s (backpressure), never an unbounded queue."""

    protocol_version = 'HTTP/1.1'
    server_version = 'mxtpu-serve/1.0'

    def log_message(self, fmt, *args):  # quiet: profiler counts us
        pass

    def _read_body(self):
        """Drain and return the request body.  MUST run before ANY
        reply on these HTTP/1.1 keep-alive connections: unread body
        bytes left in rfile would be parsed as the NEXT request line
        on the persistent connection, corrupting every subsequent
        request from that client.  Shared by every handler subclass
        (replica admin ops, the fleet router) so the invariant lives
        in one place."""
        try:
            n = int(self.headers.get('Content-Length', 0) or 0)
        except ValueError:
            n = 0
        return self.rfile.read(n) if n > 0 else b''

    def _reply(self, code, payload, retry_after_ms=None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header('Content-Type', 'application/json')
        self.send_header('Content-Length', str(len(body)))
        if retry_after_ms is not None:
            self.send_header('Retry-After',
                             '%d' % max(1, int(retry_after_ms / 1000.0)
                                        + 1))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        front = self.server.front
        if self.path == '/healthz':
            if front.closed or front.registry.closed:
                self._reply(503, {'status': 'closing'})
            else:
                self._reply(200, {'status': 'ok',
                                  'models': front.registry.models()})
        elif self.path == '/statsz':
            stats = front.registry.stats()
            stats['fleet'] = profiler.fleet_stats()
            stats['http'] = front.stats()
            self._reply(200, stats)
        else:
            self._reply(404, {'error': 'not found', 'path': self.path})

    def do_POST(self):
        front = self.server.front
        profiler.add_fleet_stats(http_requests=1)
        front.note_request()
        raw = self._read_body()         # drain-before-reply contract
        name = _predict_model(self.path)
        if name is None:
            self._reply(404, {'error': 'not found', 'path': self.path})
            return
        if not front.admit(name):
            profiler.add_fleet_stats(http_429=1)
            front.note_429()
            self._reply(429, {'error': 'overloaded',
                              'reason': 'in-flight limit',
                              'model': name},
                        retry_after_ms=1000)
            return
        try:
            try:
                body = json.loads(raw or b'{}')
                pos, named = _decode_inputs(body)
            except (ValueError, TypeError) as e:
                self._reply(400, {'error': 'bad request',
                                  'detail': str(e)})
                return
            try:
                outs = front.registry.infer(name, *pos, **named)
            except BudgetExceeded as e:
                self._reply(507, {'error': 'insufficient storage',
                                  'model': name,
                                  'need_bytes': e.need_bytes,
                                  'budget_bytes': e.budget_bytes})
                return
            except Overloaded as e:
                profiler.add_fleet_stats(http_429=1)
                front.note_429()
                self._reply(429, {'error': 'overloaded',
                                  'model': name,
                                  'backlog_rows': e.backlog_rows,
                                  'est_ms': _json_num(e.est_ms),
                                  'deadline_ms': e.deadline_ms},
                            retry_after_ms=e.retry_after_ms)
                return
            except MXNetError as e:
                msg = str(e)
                if 'unknown model' in msg:
                    self._reply(404, {'error': 'unknown model',
                                      'model': name})
                elif 'closed' in msg:
                    self._reply(503, {'error': 'closing'})
                else:
                    self._reply(400, {'error': 'bad request',
                                      'detail': msg})
                return
            except Exception as e:      # pragma: no cover - safety net
                self._reply(500, {'error': 'internal',
                                  'detail': str(e)})
                return
            self._reply(200,
                        {'outputs': [np.asarray(o).tolist()
                                     for o in outs]})
        finally:
            front.release(name)


def _predict_model(path):
    """Model name from /v1/models/<name>:predict, else None."""
    prefix, suffix = '/v1/models/', ':predict'
    if path.startswith(prefix) and path.endswith(suffix):
        name = path[len(prefix):-len(suffix)]
        if name and '/' not in name:
            return name
    return None


def _decode_inputs(body):
    """JSON body -> (positional, named) np inputs.  {"inputs": {...}}
    feeds named inputs; {"instances": [...]} is the single-input
    shorthand (one positional array)."""
    if not isinstance(body, dict):
        raise ValueError('JSON object body required')
    if 'inputs' in body:
        named = body['inputs']
        if not isinstance(named, dict):
            raise ValueError('"inputs" must be an object of arrays')
        return (), {k: np.asarray(v) for k, v in named.items()}
    if 'instances' in body:
        return (np.asarray(body['instances']),), {}
    raise ValueError('body needs "inputs" or "instances"')


def _json_num(x):
    return None if x is None or not np.isfinite(x) else float(x)


class HttpFront(object):
    """The fleet's HTTP surface: a threaded stdlib server over a
    ModelRegistry with BOUNDED in-flight admission — at most
    `max_inflight` predicts execute concurrently, and the last
    `priority_reserve` slots admit only models whose SLO priority is
    >= 1, so under pressure the cheap/batch tenants 429 first and the
    interactive ones keep their headroom.  Backpressure therefore
    reaches clients as fast typed 429s (+ Retry-After), never as an
    unbounded queue the deadline silently dies in.

    Usage::

        front = HttpFront(registry, port=8000).start()
        ...
        front.close()
    """

    def __init__(self, registry, host='127.0.0.1', port=None,
                 max_inflight=None, priority_reserve=None,
                 handler_cls=None):
        self.registry = registry
        self.max_inflight = int(
            max_inflight if max_inflight is not None else
            _env_int('MXNET_TPU_SERVE_HTTP_INFLIGHT', 64))
        if priority_reserve is None:
            priority_reserve = max(1, self.max_inflight // 8) \
                if self.max_inflight > 1 else 0
        self.priority_reserve = int(priority_reserve)
        self._lock = threading.Lock()
        self._inflight = 0
        self._n_requests = 0
        self._n_429 = 0
        self._closed = False
        port = int(port if port is not None else
                   _env_int('MXNET_TPU_SERVE_HTTP_PORT', 8000))
        self._server = _FleetHTTPServer((host, port),
                                        handler_cls or _FleetHandler)
        self._server.front = self
        self._thread = None

    @property
    def address(self):
        """(host, port) actually bound (port 0 resolves here)."""
        return self._server.server_address[:2]

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._server.serve_forever,
                name='mxtpu-serve-http', daemon=True)
            self._thread.start()
        return self

    def admit(self, name):
        """Bounded admission; the reserve tail only admits priority
        >= 1 tenants (registry SLO), unknown models pass through (the
        handler 404s them with full detail)."""
        if self._closed:
            return False
        prio = 0
        try:
            prio = self.registry._entry(name).slo.priority
        except MXNetError:
            pass
        with self._lock:
            limit = self.max_inflight if prio >= 1 else \
                self.max_inflight - self.priority_reserve
            if self._inflight >= limit:
                return False
            self._inflight += 1
            return True

    def release(self, name):
        with self._lock:
            self._inflight -= 1

    def note_request(self):
        with self._lock:
            self._n_requests += 1

    def note_429(self):
        with self._lock:
            self._n_429 += 1

    def stats(self):
        with self._lock:
            return {'inflight': self._inflight,
                    'max_inflight': self.max_inflight,
                    'priority_reserve': self.priority_reserve,
                    'requests': self._n_requests,
                    'rejected_429': self._n_429}

    @property
    def closed(self):
        return self._closed

    def close(self):
        """Stop accepting, shut the server down, join the serve
        thread (idempotent).  The registry is NOT closed — it may
        outlive the front (or be shared by several)."""
        if self._closed:
            return self
        self._closed = True
        if self._thread is not None:
            # shutdown() BLOCKS until serve_forever exits — only safe
            # when start() actually ran it
            self._server.shutdown()
            self._thread.join(timeout=10)
        self._server.server_close()
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
