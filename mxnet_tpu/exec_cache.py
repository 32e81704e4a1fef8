"""Process-wide compiled-program cache for executors.

Every `Executor` bind used to build fresh `jax.jit` closures, so
rebinding an equivalent graph (batch-ladder sweeps, Module.reshape,
bucketing, Predictor.reshape, a second simple_bind of the same net)
re-traced and re-compiled the whole XLA program from scratch.  This
module keys the jitted step functions on a canonical *graph signature*
— the topo-sorted op list with attrs, positional arg/aux
shapes+dtypes+grad_req (names are alpha-renamed away), output wiring,
ctx-group placement, and the bind-time env knobs that change the traced
math (remat / layout / stem-split) — so an equivalent rebind reuses the
already-compiled executable: zero new XLA compilations.

Two layers of reuse:

  * in-process: the jitted callable bundle (fwd_train / fwd_eval /
    fwd_monitor / fwd_bwd, plus fused multistep programs and AOT
    memory-analysis compilations) is shared across executors whose
    signatures match, LRU-bounded by MXNET_TPU_EXEC_CACHE_SIZE.
  * cross-process: JAX's on-disk compilation cache, so a second
    process cold-starts warm — the XLA compile is fetched from disk
    even though Python re-traces.  Its directory is placed from
    outside: JAX_COMPILATION_CACHE_DIR when set, else a fixed
    <checkout>/.jax_cache on a TPU backend, else off (see
    setup_persistent_cache).

Env knobs (documented in docs/PERF.md):
  MXNET_TPU_EXEC_CACHE=1|0         in-process cache (default on)
  MXNET_TPU_EXEC_CACHE_SIZE=N      LRU entries (default 64)

Counters (exposed via profiler.exec_cache_stats / profiler.summary):
  hits / misses        signature lookups at bind time
  total_compile_s      wall time spent tracing+compiling XLA programs
                       (TimedJit: trace, compile and the first run,
                       undivided)
and, as jax itself reports them (jax.monitoring; see _JAX_SECONDS and
_JAX_COUNTS below): trace_s, lower_s, backend_compile_s, cache_load_s,
persistent_requests, persistent_hits, persistent_misses.
compile_log() holds the newest backend compiles one by one.
"""
import os
import threading
import time
from collections import OrderedDict, deque

import jax.monitoring
import numpy as np

from . import profiler

_LOCK = threading.RLock()
_CACHE = OrderedDict()          # signature-scoped key -> cached object
# What jax reports of every trace, lowering and backend compile, and of
# every request to its persistent cache, by the key it is summed under.
# backend_compile_s is the compiler's time on a miss and the cache's
# read on a hit (cache_load_s is that read alone, so the difference is
# the compiler proper); jax counts a persistent miss only where it
# writes an entry; trace_s counts outermost traces only (a jit traced
# inside another's trace is in the outer one's time).  jax emits these
# from its compile path only: no listener runs in a steady step.
_TRACE_EVENT = '/jax/core/compile/jaxpr_trace_duration'
_TRACING = threading.local()    # .depth: traces open on this thread
_JAX_SECONDS = {
    _TRACE_EVENT: 'trace_s',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower_s',
    '/jax/core/compile/backend_compile_duration': 'backend_compile_s',
    '/jax/compilation_cache/cache_retrieval_time_sec': 'cache_load_s',
}
_JAX_COUNTS = {
    '/jax/compilation_cache/compile_requests_use_cache':
        'persistent_requests',
    '/jax/compilation_cache/cache_hits': 'persistent_hits',
    '/jax/compilation_cache/cache_misses': 'persistent_misses',
}
_STATS = {'hits': 0, 'misses': 0, 'total_compile_s': 0.0,
          **{k: 0.0 for k in _JAX_SECONDS.values()},
          **{k: 0 for k in _JAX_COUNTS.values()}}
# the newest backend compiles: (end on the perf_counter clock, seconds,
# fun_name, the innermost open profiler span's name or None)
_COMPILE_LOG = deque(maxlen=64)
_PERSISTENT_DIR = None          # set once by setup_persistent_cache

# Every env knob whose value is baked into the TRACED program must be
# registered here ((name, default) read at bind time) — a trace-affecting
# knob missing from this list would let a rebind after flipping it hit a
# stale executable: wrong numerics with no error.  MXNET_TPU_REMAT is
# covered separately (the executor passes its captured remat_mode into
# graph_signature explicitly).  MXNET_TPU_ZERO / MXNET_TPU_ZERO_BUCKET_MB
# are ALSO deliberately absent: they alter only the fused train-step
# update math, which is keyed explicitly — FusedSGD.cache_key() carries
# (zero stage, bucket layout, mesh) into the executor's 'multistep'
# cache key, so sharded and replicated step programs never alias, while
# the zero-independent fwd/eval/bwd programs still share one entry
# across both modes.
TRACE_ENV_KNOBS = (
    ('MXNET_TPU_LAYOUT_OPT', 'auto'),
    ('MXNET_TPU_STEM_SPLIT', '1'),
    ('MXNET_TPU_CONV_LAYOUT', ''),
)


def enabled():
    """In-process executable cache on? (MXNET_TPU_EXEC_CACHE, default 1)"""
    return os.environ.get('MXNET_TPU_EXEC_CACHE', '1') not in ('0', '')


def _max_entries():
    try:
        return max(1, int(os.environ.get('MXNET_TPU_EXEC_CACHE_SIZE',
                                         '64')))
    except ValueError:
        return 64


def default_cache_dir():
    """<checkout>/.jax_cache: fixed relative to the package's parent
    directory, whatever the cwd — the path is part of the cache key, so
    a directory that moves between runs never hits."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        '.jax_cache')


def setup_persistent_cache():
    """Turn on JAX's on-disk compilation cache and return its directory
    (None when off).  Idempotent; Executor calls it at every bind, and
    only the first call does work — it must run before the first
    compilation, because jax decides whether the cache is in use then.

    The directory is placed from outside.  JAX_COMPILATION_CACHE_DIR
    set: jax already reads it, so no directory is set in code.  Unset,
    on a TPU backend: `default_cache_dir()`.  Unset, on the CPU
    backend: off — XLA:CPU executable deserialization returned
    corrupted buffers for gather/scatter programs (an Embedding
    gradient: cold process exact, warm process weights at 1e12+ after a
    handful of steps on the identical script, docs/PERF.md round 12),
    and silent wrong-weights training is disqualifying."""
    global _PERSISTENT_DIR
    if _PERSISTENT_DIR is not None:
        return _PERSISTENT_DIR
    import jax
    target = os.environ.get('JAX_COMPILATION_CACHE_DIR') or None
    if target is None:
        if jax.default_backend() != 'tpu':
            return None
        target = default_cache_dir()
        jax.config.update('jax_compilation_cache_dir', target)
    # default thresholds skip small/fast programs; cache everything —
    # the point is cold-start elimination, not disk economy
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    _PERSISTENT_DIR = target
    return _PERSISTENT_DIR


# ---------------------------------------------------------------------------
# canonical graph signature
# ---------------------------------------------------------------------------

def graph_signature(symbol, ctx, arg_dict, aux_dict, grad_req,
                    group2ctx=None, remat_mode='none'):
    """Hashable canonical form of everything that determines the traced
    step program.  Node *names* are deliberately excluded (auto-naming
    counters differ between two builds of the same net; the compiled
    math is name-free): variables appear as their positional role in
    the arg/aux lists with shape+dtype+grad_req, ops as (op, sorted
    attrs, input wiring by topo index, ctx_group, __force_mirroring__)."""
    topo = symbol._topo()
    index = {id(n): i for i, n in enumerate(topo)}
    arg_pos = {n: i for i, n in enumerate(arg_dict)}
    aux_pos = {n: i for i, n in enumerate(aux_dict)}
    nodes = []
    for n in topo:
        if n.op is None:
            if n.name in arg_pos:
                a = arg_dict[n.name]
                nodes.append(('arg', arg_pos[n.name], tuple(a.shape),
                              np.dtype(a.dtype).str,
                              grad_req.get(n.name, 'null')))
            elif n.name in aux_pos:
                a = aux_dict[n.name]
                nodes.append(('aux', aux_pos[n.name], tuple(a.shape),
                              np.dtype(a.dtype).str))
            else:       # unbound variable: name is the only identity
                nodes.append(('unbound', n.name))
        else:
            attrs = tuple(sorted((str(k), repr(v))
                          for k, v in n.attrs.items()))
            ins = tuple((index[id(s)], oi) for s, oi in n.inputs)
            nodes.append(('op', n.op.name, attrs, ins,
                          n.user_attrs.get('ctx_group'),
                          n.user_attrs.get('__force_mirroring__')))
    outs = tuple((index[id(n)], oi) for n, oi in symbol._outputs)
    groups = tuple(sorted((k, str(v))
                   for k, v in (group2ctx or {}).items()))
    # bind-time env knobs baked into the traced program (see
    # TRACE_ENV_KNOBS — new trace-affecting knobs register there)
    env = (remat_mode,) + tuple(os.environ.get(k, d)
                                for k, d in TRACE_ENV_KNOBS)
    return (str(ctx), tuple(nodes), outs, groups, env)


# ---------------------------------------------------------------------------
# cache proper
# ---------------------------------------------------------------------------

def get(key, count=False):
    """Lookup.  count=True records a bind-level hit/miss in the stats
    (sub-entries like AOT compiles pass count=False)."""
    with _LOCK:
        found = key in _CACHE
        if found:
            _CACHE.move_to_end(key)
        if count:
            _STATS['hits' if found else 'misses'] += 1
        return _CACHE[key] if found else None


def put(key, value):
    with _LOCK:
        _CACHE[key] = value
        _CACHE.move_to_end(key)
        limit = _max_entries()
        while len(_CACHE) > limit:
            _CACHE.popitem(last=False)
    return value


def note_compile(seconds):
    """Account wall time of one trace+compile (called by TimedJit and
    the AOT paths)."""
    with _LOCK:
        _STATS['total_compile_s'] += float(seconds)


def timed_compile(lowered):
    """`lowered.compile()` with the wall time billed to
    total_compile_s — the one idiom every AOT path shares."""
    t0 = time.perf_counter()
    compiled = lowered.compile()
    note_compile(time.perf_counter() - t0)
    return compiled


def stats():
    with _LOCK:
        return dict(_STATS)


def _on_jax_event(event, **_):
    key = _JAX_COUNTS.get(event)
    if key is not None:
        with _LOCK:
            _STATS[key] += 1


def _on_jax_scalar(event, _start, **_):
    """jax marks the start of a trace with a scalar event of the trace's
    name: the depth of this thread's open traces."""
    if event == _TRACE_EVENT:
        _TRACING.depth = getattr(_TRACING, 'depth', 0) + 1


def _on_jax_duration(event, seconds, fun_name=None, **_):
    key = _JAX_SECONDS.get(event)
    if key is None:
        return
    if event == _TRACE_EVENT:
        # a jit traced inside another's trace reports its own duration,
        # which the outer one's holds already: count the outermost
        depth = _TRACING.depth = getattr(_TRACING, 'depth', 1) - 1
        if depth > 0:
            return
    with _LOCK:
        _STATS[key] += seconds
        if key == 'backend_compile_s':
            _COMPILE_LOG.append((time.perf_counter(), seconds, fun_name,
                                 profiler.open_span()))


jax.monitoring.register_event_listener(_on_jax_event)
jax.monitoring.register_scalar_listener(_on_jax_scalar)
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def compile_log():
    """The newest 64 backend compiles, oldest first, as (end on the
    perf_counter clock, seconds, fun_name, span): which program jax
    handed to the compiler or read from its persistent cache
    ('jit(multistep)' is the fused train step), for how long, and the
    innermost profiler span open on that thread then, or None."""
    with _LOCK:
        return list(_COMPILE_LOG)


# ---------------------------------------------------------------------------
# serving bucket ladder
# ---------------------------------------------------------------------------
# The serving engine (serving.py) pads requests up to a ladder of
# bucket shapes; each rung binds its own executor, whose graph
# signature (shape included) is its cache identity — warming the
# ladder populates this cache, and steady-state traffic then reuses
# the rungs with ZERO new compilations.  The helpers below are the
# ladder's shared vocabulary so predictor.export_compiled and
# serving.InferenceEngine key identically.

def batch_ladder(max_batch, min_batch=1):
    """Default batch-dim bucket ladder: powers of two from min_batch
    up to and including max_batch (always included even when not a
    power of two)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError('max_batch must be >= 1')
    out = []
    b = max(1, int(min_batch))
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def train_ladder(bucket_keys):
    """Normalized TRAINING bucket ladder: sorted unique rung keys (ints,
    or equal-length tuples ordered lexicographically).  The training
    analog of batch_ladder: BucketingModule pads each incoming batch up
    to its covering rung (`ladder_rung`), so only the rung shapes ever
    bind executors / compile programs — a mid-epoch novel length costs
    pad waste instead of an XLA compile stall."""
    keys = sorted(set(bucket_keys))
    if not keys:
        raise ValueError('train_ladder: empty bucket ladder')
    return tuple(keys)


def _rung_covers(rung, key):
    r_seq = isinstance(rung, (tuple, list))
    k_seq = isinstance(key, (tuple, list))
    if r_seq != k_seq:
        return False        # int ladder vs tuple key (or vice versa)
    if r_seq:
        return len(rung) == len(key) and \
            all(int(r) >= int(k) for r, k in zip(rung, key))
    return rung >= key


def ladder_rung(ladder, key):
    """Smallest rung of `ladder` (a train_ladder tuple) covering `key`
    — every extent >= the key's, elementwise for tuple keys — or None
    when no rung covers it (callers decide whether that is an error)."""
    for rung in ladder:
        if _rung_covers(rung, key):
            return rung
    return None


def embed_plan_key(positions, vocabs, dims, rungs=None):
    """Hashable identity of a sparse-embedding plan as it joins a
    compiled-program cache key: which parameter slots are sparse
    tables, their (vocab, dim) geometry, and — when rung-resolved —
    the unique-count ladder rungs this program was traced at.  The
    rungs change the traced shapes (so the jaxpr fingerprint would
    differ anyway), but joining them explicitly keeps ladder programs
    from ever aliasing through a fingerprint subtlety, mirroring how
    the ZeRO bucket layout key joins FusedSGD.cache_key.  A row-shard
    layout needs no extra token here: the mesh/placement fingerprint
    every fused key already carries covers it."""
    key = ('embed', tuple(int(p) for p in positions),
           tuple(int(v) for v in vocabs), tuple(int(d) for d in dims))
    if rungs is not None:
        key += (tuple(int(r) for r in rungs),)
    return key


def serve_step_key(sig, input_names=(), quant=None, embed=None):
    """Cache key of one bucket rung's donated serve program (the
    forward-only jit serving.py dispatches).  `sig` is the bucket
    executor's graph signature — shape-distinct per rung, so rungs
    never alias and an equivalent engine re-creation hits every
    entry.  `input_names` is the engine's input ORDER: the signature
    deliberately alpha-renames variable names away, but the serve
    closure bakes the data_vals->argument mapping in, so engines over
    the same graph with differently-ordered data_names must not share
    a program (they'd silently swap inputs).  `quant` is the
    quantized engine's config token (QuantConfig.key + the quantized
    weight positions): the quantized serve program takes int8 codes +
    scale arguments and bakes the dequant math in, so it must never
    alias the fp program — nor a program quantizing a different
    weight subset.  `embed` is the hot-row-cached engine's token
    (per-table (weight name, capacity) pairs): a hot engine's serve
    program gathers from the (C, dim) hot buffer with host-remapped
    slot ids — it must never alias the full-table program, nor a
    different capacity's."""
    return (sig, 'serve_step', tuple(input_names)) + \
        (() if quant is None else (quant,)) + \
        (() if embed is None else (('hotrow',) + tuple(embed),))


def cont_step_key(sig, kind, data_name, state_names, state_out_idx,
                  chunk=None, width=None):
    """Cache key of one continuous-batching tick program
    (serving_fleet.ContinuousEngine).  `sig` is the cell executor's
    graph signature: it fingerprints the jaxpr AND the slots-wide
    bind shapes, so fp/int8 cells and different slot counts already
    never alias.  `kind` separates the program families —
    'cont_step' (the single-tick baseline), 'cont_chunk_step' (K
    ticks per dispatch via lax.scan), 'cont_lone_step' (the
    narrow lone-request rung, which dynamic-slices a `width`-row
    window of state out of the full buffers) — and `chunk` is the
    scan length K for the chunked kinds: a K=4 program's
    (K, slots)-leading input shapes must never alias a K=16
    program's, and neither may alias the unchunked tick.  `width`
    is the lone rung's batch width (1 or 2 — some backends lower a
    batch-1 cell with different rounding than the wide program, so
    the engine ladders the rung up to the narrowest bitwise-clean
    width): a width-1 program's shapes must never alias a
    width-2's.  With every degree of freedom in the key, a
    re-created engine (same cell, slots, K) warms every program
    from cache at zero XLA compiles."""
    key = (sig, kind, data_name, tuple(state_names),
           tuple(int(i) for i in state_out_idx))
    if chunk is not None:
        key += (('chunk', int(chunk)),)
    if width is not None:
        key += (('lone_width', int(width)),)
    return key


def gluon_step_key(fingerprint, step_key, mode, k, placement):
    """Cache key of one fused Gluon whole-train-step program
    (gluon/fused.py).  `fingerprint` is the blake2b hash of the step
    function's abstract jaxpr — a canonical, name-free identity of the
    ENTIRE traced computation (net forward + loss + backward + grad
    reduce + optimizer update, with every input shape/dtype and any
    mesh sharding constraints baked in), so a re-created net/Trainer of
    the same architecture hits the same entry regardless of parameter
    names/prefixes.  `step_key` is FusedSGD.cache_key() extended with
    the epoch-fusion carry signature and gradient-reduce plan
    (FusedStep._full_step_key: EMA decay, metric fold identity, bucket
    layout + schedule) — all already part of the traced math, but
    joined explicitly so optimizer-state layout changes (ZeRO bucket
    relayout, rescale/clip/momentum) or carry changes can never alias
    even if a jaxpr printing subtlety collided.  `mode`/`k`
    distinguish single-step from K-step lax.scan bulk programs.
    `placement` is the device/mesh fingerprint: the cached object is an
    AOT-COMPILED executable (holds no Python closure, so cache entries
    never pin a discarded net's weights) and AOT bakes concrete device
    placements in — same-architecture steps on different devices must
    not alias."""
    return ('gluon_fused', fingerprint, step_key, mode, int(k),
            placement)


def clear(reset_stats=True):
    """Drop every cached executable (tests / memory pressure)."""
    with _LOCK:
        _CACHE.clear()
        if reset_stats:
            for k, v in _STATS.items():
                _STATS[k] = type(v)()
            _COMPILE_LOG.clear()


def size():
    with _LOCK:
        return len(_CACHE)


class TimedJit:
    """Thin wrapper over a jax.jit callable that bills trace+compile
    wall time to the process counters: a call that grows the jit's
    internal executable cache was a compilation (steady-state calls
    pay one extra _cache_size() read, negligible next to dispatch)."""

    __slots__ = ('fn',)

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, *args):
        try:
            before = self.fn._cache_size()
        except Exception:     # non-jit callable or future jax
            return self.fn(*args)
        t0 = time.perf_counter()
        out = self.fn(*args)
        if self.fn._cache_size() > before:
            note_compile(time.perf_counter() - t0)
        return out

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)
