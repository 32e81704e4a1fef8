"""Profiler: per-operation execution records -> Chrome trace JSON.

TPU-native rebuild of the reference profiler
(/root/reference src/engine/profiler.{h,cc}: OprExecStat records with
start/end microseconds dumped as chrome://tracing "traceEvents";
python/mxnet/profiler.py:27-55 API — SURVEY.md §5.1).  The reference
tags each engine OprBlock; here device work happens inside whole XLA
executions, so the recorded spans are the framework's dispatch units:
executor forward/backward (device-synchronized inside the span while
profiler_set_state('run') is on, so durations reflect execution, not
async enqueue), kvstore push/pull, per-op imperative spans under
mode='all', and any user `profiler.scope`.  For intra-XLA
kernel timing, `profiler_set_config(profile_xla=True)` additionally
starts a JAX device trace (PJRT/XPlane) alongside.

`scope` is the one way the program marks time.  Every span is also a
jax TraceAnnotation named 'mx.<name>', so it lands in whatever jax
profiler session is open on the device trace's clock, and is kept in
a bounded in-memory ring per name that `span_tail` reads (always on;
a span never waits for the device).

Env autostart mirrors the reference: MXNET_PROFILER_AUTOSTART=1.
"""
import json
import os
import threading
import time
import weakref
from collections import deque

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

_STATE = {
    'mode': 'symbolic',        # 'symbolic' | 'all'
    'filename': 'profile.json',
    'running': False,
    'records': [],             # (name, category, ts_us, dur_us, tid)
    'lock': threading.Lock(),
    'jax_trace': False,
    'jax_trace_dir': None,
}

# communication / memory counters for the sharded (ZeRO-1) update:
# logical collective payload bytes the fused steps moved, and the
# optimizer-state bytes each device currently holds (Module feeds
# these after every fused step — see module.py _note_step_counters)
_COMM = {
    'bytes_reduce_scattered': 0,
    'bytes_all_gathered': 0,
    'optimizer_state_bytes_per_device': 0,
    # backward-interleaved reduction + epoch-level fusion (round 11):
    # gradient-bucket collectives issued inside fused steps, and
    # training steps whose metric accumulation ran device-resident
    # inside the bulk scan
    'reduce_buckets_issued': 0,
    'scan_fused_metric_steps': 0,
}


def note_reduce_dispatch(buckets, k, metric_steps=0):
    """ONE counter model for a fused dispatch of k steps, shared by
    the Module and gluon fused paths: `buckets` gradient-bucket
    collectives issue per step."""
    with _STATE['lock']:
        _COMM['reduce_buckets_issued'] += int(buckets) * int(k)
        _COMM['scan_fused_metric_steps'] += int(metric_steps)


# pipeline-parallel counters (round 16: the dp×pipe GPipe training
# mode — gluon/fused.PipelinedStep and module/pipeline_fit.py feed one
# call per fused dispatch).  stages/num_micro/bubble_frac and the
# per-device param/optimizer-state residency are GAUGES (the last
# dispatch's configuration); the rest accumulate.  bubble_frac is the
# schedule's analytic fill-drain bubble (S-1)/(M+S-1) — the fraction
# of pipeline ticks below full stage occupancy.
_PIPE = {
    'pipe_dispatches': 0,
    'pipe_steps': 0,
    'pipe_microbatches': 0,
    'pipe_stages': 0,
    'pipe_num_micro': 0,
    'pipe_bubble_frac': 0.0,
    'pipe_param_bytes_per_device': 0,
    'pipe_state_bytes_per_device': 0,
}


def note_pipe_dispatch(stages, micro, k, bubble_frac, param_bytes=0,
                       state_bytes=0):
    """ONE counter model for a pipelined fused dispatch of k steps,
    shared by the gluon and Module dp×pipe paths."""
    with _STATE['lock']:
        _PIPE['pipe_dispatches'] += 1
        _PIPE['pipe_steps'] += int(k)
        _PIPE['pipe_microbatches'] += int(micro) * int(k)
        _PIPE['pipe_stages'] = int(stages)
        _PIPE['pipe_num_micro'] = int(micro)
        _PIPE['pipe_bubble_frac'] = float(bubble_frac)
        if param_bytes:
            _PIPE['pipe_param_bytes_per_device'] = int(param_bytes)
        if state_bytes:
            _PIPE['pipe_state_bytes_per_device'] = int(state_bytes)


def pipe_stats():
    """Snapshot of the pipeline-parallel counters (also merged into
    summary() and dump_profile's 'pipeline' metadata lane)."""
    with _STATE['lock']:
        return dict(_PIPE)


# expert-parallel MoE counters: tokens routed to experts vs dropped at
# capacity (overflow is otherwise SILENT — the residual passes them
# through), plus the per-expert table for load-balance reading.  Fed by
# gluon.nn.MoE through the fused step (add_moe_stats once a dispatch)
# and by the SparseMoE operator's device-resident counts, which an
# executor offers through watch_device_counters and
# fold_device_counters() folds in when it is called: no step ever waits
# for them, and moe_stats() itself reads host counters only
_MOE = {
    'moe_routed_tokens': 0,
    'moe_dropped_tokens': 0,
    'moe_dispatches': 0,
    'moe_assignments': 0,
}
_MOE_EXPERTS = {}       # 'e<i>' -> {'routed': n, 'dropped': n}
# owner -> {aux name: the count last read}: fold_device_counters()
_WATCHED = weakref.WeakKeyDictionary()


def add_moe_stats(routed=0, dropped=0, per_expert_routed=None,
                  per_expert_dropped=None, dispatches=0, assignments=0):
    """Accumulate MoE routing counters (the fused step feeds one call
    per dispatch from the block's device-resident count deltas).
    assignments: (token, expert) pairs the router made over ALL experts,
    held here or not."""
    with _STATE['lock']:
        _MOE['moe_routed_tokens'] += int(routed)
        _MOE['moe_dropped_tokens'] += int(dropped)
        _MOE['moe_dispatches'] += int(dispatches)
        _MOE['moe_assignments'] += int(assignments)
        for key, vals in (('routed', per_expert_routed),
                          ('dropped', per_expert_dropped)):
            if vals is None:
                continue
            for i, v in enumerate(vals):
                e = _MOE_EXPERTS.setdefault('e%d' % i,
                                            {'routed': 0, 'dropped': 0})
                e[key] += int(v)


def watch_device_counters(owner):
    """`owner.counter_aux()` -> [(aux names, arrays, attrs, fold_aux)]:
    running totals an operator keeps on the device as auxiliary state,
    with the operator's own fold (ops.registry.OpDef.fold_aux).  Held
    weakly; read only by fold_device_counters()."""
    _WATCHED.setdefault(owner, {})


def fold_device_counters():
    """Read every watched counter from the device and hand what it has
    grown by since the last read to its operator's fold (SparseMoE:
    add_moe_stats).  Waits for the dispatches in flight: call it where
    a wait costs nothing, not inside a step."""
    for owner, seen in list(_WATCHED.items()):
        for names, arrays, attrs, fold in owner.counter_aux():
            deltas = []
            for name, now in zip(names, arrays):
                now = np.asarray(now, np.int64)
                delta = now - seen.get(name, 0)
                if (delta < 0).any():       # the state was set anew
                    delta = now
                seen[name] = now
                deltas.append(delta)
            fold(attrs, deltas)


def moe_stats():
    """Snapshot of the MoE routing counters plus the derived drop
    fraction and the per-expert table."""
    with _STATE['lock']:
        out = dict(_MOE)
        out['moe_experts'] = {k: dict(v)
                              for k, v in _MOE_EXPERTS.items()}
    total = out['moe_routed_tokens'] + out['moe_dropped_tokens']
    out['moe_drop_frac'] = \
        out['moe_dropped_tokens'] / total if total else 0.0
    return out


# causal_attention's lowerings by path (ops/lm.py): 'kernel' is the
# Pallas flash kernel, 'blocked' the XLA core, and the key beside each
# count is the shape that decided, with the query-key positions those
# lowerings score (`keys_visited`: whole blocks, forward) and the
# positions their masks let through (`keys_needed`), over all sequences
# and heads.  Taken from shapes while the operator is traced into a
# program (a step's forward, its recomputation and each re-trace count
# one apiece), never inside a step
_ATTENTION = {}     # (path, heads, group, dk, dv, t, window) ->
#                     [lowerings, keys visited, keys needed]
_ATTENTION_KEY = ('path', 'heads', 'group', 'dk', 'dv', 't', 'window')


def note_attention_lowering(path, heads, group, dk, dv, t, window=None,
                            keys_visited=0, keys_needed=0):
    key = (path, int(heads), int(group), int(dk), int(dv), int(t),
           None if window is None else int(window))
    with _STATE['lock']:
        seen = _ATTENTION.setdefault(key, [0, 0, 0])
        for i, more in enumerate((1, keys_visited, keys_needed)):
            seen[i] += int(more)


def attention_stats():
    """causal_attention's lowerings: {'kernel': n, 'blocked': n,
    'shapes': [{'path', 'heads', 'group', 'dk', 'dv', 't', 'window',
    'lowerings', 'keys_visited', 'keys_needed'}, ...]}; the last two
    are sums over the lowerings."""
    with _STATE['lock']:
        seen = sorted(_ATTENTION.items(),
                      key=lambda kv: kv[0][:6] + (kv[0][6] or 0,))
    out = {'kernel': 0, 'blocked': 0, 'shapes': []}
    for key, (n, visited, needed) in seen:
        out[key[0]] += n
        out['shapes'].append(dict(
            zip(_ATTENTION_KEY, key), lowerings=n, keys_visited=visited,
            keys_needed=needed))
    return out


# GatedDeltaRule's core by shape (ops/lm.py): how often the rule was
# traced into a program (`lowerings`), and how many of its chunk-local
# makes (pallas_ops.delta_rule_local: one a forward, one more in the
# backward rule) and backward rules went with them.  From shapes while
# the operator is traced, like the attention counts, never inside a step
_DELTA_RULE = {}    # (heads, chunks, chunk, dk, dv) ->
#                     [lowerings, local makes, backward rules]
_DELTA_RULE_KEY = ('heads', 'chunks', 'chunk', 'dk', 'dv')
_DELTA_RULE_COUNTS = ('lowerings', 'local_makes', 'backward_rules')


def note_delta_rule(heads, chunks, chunk, dk, dv, **counts):
    key = tuple(int(x) for x in (heads, chunks, chunk, dk, dv))
    with _STATE['lock']:
        seen = _DELTA_RULE.setdefault(key, [0] * len(_DELTA_RULE_COUNTS))
        for name, more in counts.items():
            seen[_DELTA_RULE_COUNTS.index(name)] += int(more)


def delta_rule_stats():
    """The gated delta rule's lowerings: {'lowerings': n, 'local_makes':
    n, 'backward_rules': n, 'shapes': [{'heads', 'chunks', 'chunk',
    'dk', 'dv', 'lowerings', 'local_makes', 'backward_rules'}, ...]};
    `heads` counts every sequence's (B * H), the widths are the padded
    ones the kernels see."""
    with _STATE['lock']:
        seen = sorted((k, list(v)) for k, v in _DELTA_RULE.items())
    out = dict.fromkeys(_DELTA_RULE_COUNTS, 0)
    out['shapes'] = []
    for key, counts in seen:
        for name, n in zip(_DELTA_RULE_COUNTS, counts):
            out[name] += n
        out['shapes'].append(dict(zip(_DELTA_RULE_KEY + _DELTA_RULE_COUNTS,
                                      key + tuple(counts))))
    return out


# CausalConv1D's lowerings by path (ops/lm.py causal_conv): 'kernel' is
# pallas_ops.causal_conv1d, 'xla' the plain statement other shapes keep,
# by the shape that decided.  From shapes while the operator is traced,
# like the attention counts, never inside a step
_CAUSAL_CONV = {}   # (path, sequences, t, channels, width) -> lowerings
_CAUSAL_CONV_KEY = ('path', 'sequences', 't', 'channels', 'width')


def note_causal_conv(path, sequences, t, channels, width):
    key = (path,) + tuple(int(x) for x in (sequences, t, channels, width))
    with _STATE['lock']:
        _CAUSAL_CONV[key] = _CAUSAL_CONV.get(key, 0) + 1


def causal_conv_stats():
    """CausalConv1D's lowerings: {'kernel': n, 'xla': n, 'shapes':
    [{'path', 'sequences', 't', 'channels', 'width', 'lowerings'},
    ...]}."""
    with _STATE['lock']:
        seen = sorted(_CAUSAL_CONV.items())
    out = {'kernel': 0, 'xla': 0, 'shapes': []}
    for key, n in seen:
        out[key[0]] += n
        out['shapes'].append(dict(zip(_CAUSAL_CONV_KEY, key), lowerings=n))
    return out


# SparseMoE's plan by path (ops/lm.py _pair_plan): 'packed' sorts each
# pair's slot and index packed into one int32, 'stable' sorts (slot,
# index) stably where they do not fit one, by the shape that decided.
# From shapes while the operator is traced, never inside a step
_MOE_PLAN = {}      # (path, pairs, buckets, top_k) -> lowerings
_MOE_PLAN_KEY = ('path', 'pairs', 'buckets', 'top_k')


def note_moe_plan(path, pairs, buckets, top_k):
    key = (path,) + tuple(int(x) for x in (pairs, buckets, top_k))
    with _STATE['lock']:
        _MOE_PLAN[key] = _MOE_PLAN.get(key, 0) + 1


def moe_plan_stats():
    """SparseMoE's plans: {'packed': n, 'stable': n, 'shapes': [{'path',
    'pairs', 'buckets', 'top_k', 'lowerings'}, ...]}; `buckets` is the
    held experts and one for the pairs held elsewhere."""
    with _STATE['lock']:
        seen = sorted(_MOE_PLAN.items())
    out = {'packed': 0, 'stable': 0, 'shapes': []}
    for key, n in seen:
        out[key[0]] += n
        out['shapes'].append(dict(zip(_MOE_PLAN_KEY, key), lowerings=n))
    return out


# LoopedDecoder's stacks by shape (ops/lm.py): how often the operator was
# traced for training (`lowerings`; shape inference's traces are not
# counted), with the passes, the layers and what its scan keeps for the
# backward.  From shapes while the operator is traced, never inside a step
_LOOPED = {}        # (loops, layers, tokens, hidden, saved bytes) ->
#                     lowerings
_LOOPED_KEY = ('loops', 'layers', 'tokens', 'hidden', 'saved_bytes')


def note_looped_decoder(loops, layers, tokens, hidden, saved_bytes):
    key = tuple(int(x) for x in (loops, layers, tokens, hidden, saved_bytes))
    with _STATE['lock']:
        _LOOPED[key] = _LOOPED.get(key, 0) + 1


def looped_decoder_stats():
    """The looped stacks' lowerings: {'lowerings': n, 'loops', 'layers',
    'layer_applications', 'saved_bytes' (those of the largest stack: the
    activations its scan keeps for the backward over all passes, bytes),
    'shapes': [{'loops', 'layers', 'tokens', 'hidden', 'saved_bytes',
    'lowerings'}, ...]}; zeros where nothing was traced."""
    with _STATE['lock']:
        seen = sorted(_LOOPED.items())
    out = {'lowerings': sum(n for _, n in seen), 'loops': 0, 'layers': 0,
           'layer_applications': 0, 'saved_bytes': 0, 'shapes': []}
    for key, n in seen:
        shape = dict(zip(_LOOPED_KEY, key), lowerings=n)
        out['shapes'].append(shape)
        if shape['saved_bytes'] >= out['saved_bytes']:
            out.update(loops=shape['loops'], layers=shape['layers'],
                       layer_applications=shape['loops'] * shape['layers'],
                       saved_bytes=shape['saved_bytes'])
    return out


# sparse embedding counters (Embedding(sparse_grad=True) through the
# fused step, plus the serving hot-row cache): the touched-bytes
# ledger is THE quantity this tier exists to shrink — the dense
# equivalent is what the same steps would have paid at vocab rows
_EMBED = {
    'embed_steps': 0,
    'embed_dispatches': 0,
    'embed_lookups': 0,
    'embed_unique_rows': 0,          # ladder-padded rows updated
    'embed_touched_bytes': 0,        # optimizer-touched (rows-only)
    'embed_dense_equiv_bytes': 0,    # dense-path equivalent
    'embed_max_rung': 0,             # largest ladder rung seen
    'hotrow_hits': 0,
    'hotrow_misses': 0,
    'hotrow_evictions': 0,
    'hotrow_resident_bytes': 0,      # gauge, not cumulative
    'hotrow_prefetched': 0,          # rows paged ahead of demand
    'hotrow_prefetch_hits': 0,       # prefetched rows later demanded
}


def add_embed_stats(steps=0, dispatches=0, lookups=0, unique_rows=0,
                    touched_bytes=0, dense_equiv_bytes=0, max_rung=0,
                    hits=0, misses=0, evictions=0, prefetched=0,
                    prefetch_hits=0, resident_bytes=None):
    """Accumulate sparse-embedding counters (the fused step feeds one
    call per sparse dispatch; the serving hot-row cache feeds
    hits/misses/evictions per batch, prefetched/prefetch_hits from
    the queued-request speculation, and the resident-bytes gauge)."""
    with _STATE['lock']:
        _EMBED['embed_steps'] += int(steps)
        _EMBED['embed_dispatches'] += int(dispatches)
        _EMBED['embed_lookups'] += int(lookups)
        _EMBED['embed_unique_rows'] += int(unique_rows)
        _EMBED['embed_touched_bytes'] += int(touched_bytes)
        _EMBED['embed_dense_equiv_bytes'] += int(dense_equiv_bytes)
        _EMBED['embed_max_rung'] = max(_EMBED['embed_max_rung'],
                                       int(max_rung))
        _EMBED['hotrow_hits'] += int(hits)
        _EMBED['hotrow_misses'] += int(misses)
        _EMBED['hotrow_evictions'] += int(evictions)
        _EMBED['hotrow_prefetched'] += int(prefetched)
        _EMBED['hotrow_prefetch_hits'] += int(prefetch_hits)
        if resident_bytes is not None:
            _EMBED['hotrow_resident_bytes'] = int(resident_bytes)


def embed_stats():
    """Snapshot of the sparse-embedding counters plus the derived
    touched-bytes saving factor and hot-row hit rate."""
    with _STATE['lock']:
        out = dict(_EMBED)
    out['embed_touched_frac'] = (
        out['embed_touched_bytes'] / out['embed_dense_equiv_bytes']
        if out['embed_dense_equiv_bytes'] else 0.0)
    lookups = out['hotrow_hits'] + out['hotrow_misses']
    out['hotrow_hit_rate'] = \
        out['hotrow_hits'] / lookups if lookups else 0.0
    return out


# host input-pipeline counters (parallel decode pool + device prefetch):
# decode work done by the workers, time the consumer waited on the pool,
# ready-chunk queue depth observations, training-loop-visible input
# stall (the 'io.next' spans of PrefetchToDeviceIter), the bytes
# io.stage_to_device handed to the device, and what NDArrayIter copied
# on the host to make its batches (none for a batch served as a view)
_INPUT = {
    'decode_ms': 0.0,
    'decoded_samples': 0,
    'decode_wait_ms': 0.0,
    'queue_depth_sum': 0,
    'queue_depth_obs': 0,
    'input_stall_ms': 0.0,
    'input_batches': 0,
    'h2d_bytes': 0,
    'host_copy_bytes': 0,
    'view_batches': 0,
}


def add_input_stats(decode_ms=0.0, decoded_samples=0, decode_wait_ms=0.0,
                    queue_depth=None, stall_ms=0.0, batches=0,
                    h2d_bytes=0, host_copy_bytes=0, view_batches=0):
    """Accumulate host input-pipeline counters (decode workers feed
    decode_ms/decoded_samples; the batch consumer feeds decode_wait_ms
    + queue_depth; PrefetchToDeviceIter feeds stall_ms/batches;
    stage_to_device feeds h2d_bytes; NDArrayIter feeds host_copy_bytes,
    the bytes it read and wrote again on the host to make a batch, and
    view_batches, the batches it served without such a copy)."""
    with _STATE['lock']:
        _INPUT['decode_ms'] += decode_ms
        _INPUT['decoded_samples'] += decoded_samples
        _INPUT['decode_wait_ms'] += decode_wait_ms
        if queue_depth is not None:
            _INPUT['queue_depth_sum'] += int(queue_depth)
            _INPUT['queue_depth_obs'] += 1
        _INPUT['input_stall_ms'] += stall_ms
        _INPUT['input_batches'] += batches
        _INPUT['h2d_bytes'] += h2d_bytes
        _INPUT['host_copy_bytes'] += host_copy_bytes
        _INPUT['view_batches'] += view_batches


def input_stats():
    """Snapshot of the input-pipeline counters plus derived means
    (queue_depth_avg, input_stall_ms_per_batch)."""
    with _STATE['lock']:
        out = dict(_INPUT)
    out['queue_depth_avg'] = (out['queue_depth_sum'] /
                              out['queue_depth_obs']
                              if out['queue_depth_obs'] else 0.0)
    out['input_stall_ms_per_batch'] = (out['input_stall_ms'] /
                                       out['input_batches']
                                       if out['input_batches'] else 0.0)
    return out


# fused Gluon training counters (gluon/fused.py): optimizer steps that
# ran whole-step-compiled and the host dispatches that carried them
# (bulk lax.scan programs run K steps per dispatch)
_GLUON_FUSED = {
    'gluon_fused_steps': 0,
    'gluon_fused_dispatches': 0,
}


def add_gluon_fused_stats(steps=0, dispatches=0):
    """Accumulate fused-Gluon counters (FusedStep feeds one call per
    compiled dispatch; bulk dispatches carry steps=K)."""
    with _STATE['lock']:
        _GLUON_FUSED['gluon_fused_steps'] += int(steps)
        _GLUON_FUSED['gluon_fused_dispatches'] += int(dispatches)


def gluon_fused_stats():
    """Snapshot of the fused-Gluon counters plus the derived mean
    steps-per-dispatch (the on-device bulking factor actually
    achieved)."""
    with _STATE['lock']:
        out = dict(_GLUON_FUSED)
    out['gluon_fused_steps_per_dispatch'] = (
        out['gluon_fused_steps'] / out['gluon_fused_dispatches']
        if out['gluon_fused_dispatches'] else 0.0)
    return out


# bucketed-training counters (BucketingModule's fused bucket ladder,
# PERF round 12) — mirroring the serve_* family: bucket switches, pad
# waste from running short batches at their ladder rung, and per-rung
# step/compile/warmup accounting (the zero-compile-steady-state story
# is "every rung's compiles happened at warmup, none during steps")
_BUCKET = {
    'train_bucket_switches': 0,
    'train_pad_waste_rows': 0,
    'train_rows': 0,
}
_BUCKET_RUNGS = {}      # str(rung) -> {'steps','dispatches','compiles',
#                                       'warmups','warm_compiles'}


def _rung_entry(rung):
    e = _BUCKET_RUNGS.get(str(rung))
    if e is None:
        e = {'steps': 0, 'dispatches': 0, 'compiles': 0,
             'warmups': 0, 'warm_compiles': 0}
        _BUCKET_RUNGS[str(rung)] = e
    return e


def add_bucket_stats(switches=0, pad_rows=0, rows=0):
    """Accumulate bucket-ladder counters (BucketingModule feeds
    switches from switch_bucket and pad/total label rows from the
    pad-to-rung path)."""
    with _STATE['lock']:
        _BUCKET['train_bucket_switches'] += int(switches)
        _BUCKET['train_pad_waste_rows'] += int(pad_rows)
        _BUCKET['train_rows'] += int(rows)


def note_bucket_dispatch(rung, steps=1, compiled=False):
    """One train dispatch of `steps` steps on `rung`; compiled=True
    when exec_cache compile time moved during it (a mid-epoch compile
    stall — zero of these after warmup is the ladder's contract)."""
    with _STATE['lock']:
        e = _rung_entry(rung)
        e['steps'] += int(steps)
        e['dispatches'] += 1
        if compiled:
            e['compiles'] += 1


def note_bucket_warmup(rung, compiled=False):
    """One warmup_buckets visit of `rung`; compiled=False means the
    rung's programs came entirely from the process-wide exec_cache
    (the re-created-module re-warm path)."""
    with _STATE['lock']:
        e = _rung_entry(rung)
        e['warmups'] += 1
        if compiled:
            e['warm_compiles'] += 1


def bucketing_stats():
    """Snapshot of the bucket-ladder counters plus the derived
    train_pad_waste_frac (padded / total label rows) and the per-rung
    table."""
    with _STATE['lock']:
        out = dict(_BUCKET)
        out['train_rungs'] = {k: dict(v)
                              for k, v in _BUCKET_RUNGS.items()}
    total = out['train_rows'] + out['train_pad_waste_rows']
    out['train_pad_waste_frac'] = \
        out['train_pad_waste_rows'] / total if total else 0.0
    return out


# elastic-checkpoint counters (elastic.CheckpointManager): snapshots
# committed, payload bytes written, host-side materialize+write wall
# time that ran on the background writer WHILE training continued
# (ckpt_async_overlap_ms — an upper bound on the overlap; 0 for
# synchronous/final commits), end-to-end
# commit time, torn/incomplete checkpoints skipped at resume, restores
# performed, cadence snapshots skipped because a write was in flight,
# and injected/real write failures survived
_CKPT = {
    'ckpt_snapshots': 0,
    'ckpt_bytes': 0,
    'ckpt_async_overlap_ms': 0.0,
    'ckpt_commit_ms': 0.0,
    'ckpt_torn_fallbacks': 0,
    'ckpt_restores': 0,
    'ckpt_skipped': 0,
    'ckpt_failed_writes': 0,
}


def add_ckpt_stats(snapshots=0, bytes=0, async_overlap_ms=0.0,
                   commit_ms=0.0, torn_fallbacks=0, restores=0,
                   skipped=0, failed_writes=0):
    """Accumulate elastic-checkpoint counters (the CheckpointManager's
    writer/resume paths feed one call per event)."""
    with _STATE['lock']:
        _CKPT['ckpt_snapshots'] += int(snapshots)
        _CKPT['ckpt_bytes'] += int(bytes)
        _CKPT['ckpt_async_overlap_ms'] += float(async_overlap_ms)
        _CKPT['ckpt_commit_ms'] += float(commit_ms)
        _CKPT['ckpt_torn_fallbacks'] += int(torn_fallbacks)
        _CKPT['ckpt_restores'] += int(restores)
        _CKPT['ckpt_skipped'] += int(skipped)
        _CKPT['ckpt_failed_writes'] += int(failed_writes)


def ckpt_stats():
    """Snapshot of the elastic-checkpoint counters (also merged into
    summary() and dump_profile 'checkpoint' metadata)."""
    with _STATE['lock']:
        return dict(_CKPT)


# multi-host distributed-runtime counters (mxnet_tpu/dist.py): liveness
# heartbeats sent / missed (dropped by fault injection or a lost
# coordinator), health-checked barrier rounds + the wall time spent
# waiting in them, real cross-process deaths this process learned of
# through heartbeat loss, cross-host gradient allreduce rounds (the
# DCN dp leg), and how many elastic relaunches this process is
# downstream of (the launch.py --elastic supervisor exports
# MXNET_TPU_DIST_RESTART_COUNT).
#
# Wire-byte accounting is PER DIRECTION and PER TOPOLOGY so bench arms
# A/B like-for-like: dist_tx_bytes / dist_rx_bytes are what THIS
# process actually put on / took off the socket, attributed to the
# transport that moved them ('star' coordinator round trips, 'ring'
# neighbor hops, 'sparse' COO rounds on either topology).  The star
# coordinator's ingress is therefore every peer's tx — rank 0's rx
# does not count its own coordinator's fan-in (it never crosses a
# host).  dist_allreduce_bytes stays as the tx+rx total for
# compatibility with pre-round-23 readers.  dist_overlap_ms is the
# wall time allreduce_async rounds ran concurrently with the caller
# (launch -> wait begin, clipped at completion).
_DIST = {
    'dist_heartbeats_sent': 0,
    'dist_heartbeats_missed': 0,
    'dist_barriers': 0,
    'dist_barrier_wait_ms': 0.0,
    'dist_dead_hosts_detected': 0,
    'dist_allreduce_rounds': 0,
    'dist_allreduce_bytes': 0,
    'dist_tx_bytes': 0,
    'dist_rx_bytes': 0,
    'dist_star_bytes': 0,
    'dist_ring_bytes': 0,
    'dist_sparse_bytes': 0,
    'dist_overlap_ms': 0.0,
    'dist_restarts': 0,
}


def add_dist_stats(heartbeats_sent=0, heartbeats_missed=0, barriers=0,
                   barrier_wait_ms=0.0, dead_hosts_detected=0,
                   allreduce_rounds=0, allreduce_bytes=0, restarts=0,
                   tx_bytes=0, rx_bytes=0, topology=None,
                   overlap_ms=0.0):
    """Accumulate dist-runtime counters (the heartbeat thread, barrier
    and allreduce paths feed one call per event).  `tx_bytes` /
    `rx_bytes` are directional wire bytes; `topology`
    ('star'/'ring'/'sparse') attributes them to the transport that
    moved them; allreduce_bytes defaults to tx+rx when directional
    bytes are given without an explicit total."""
    if (tx_bytes or rx_bytes) and not allreduce_bytes:
        allreduce_bytes = int(tx_bytes) + int(rx_bytes)
    with _STATE['lock']:
        _DIST['dist_heartbeats_sent'] += int(heartbeats_sent)
        _DIST['dist_heartbeats_missed'] += int(heartbeats_missed)
        _DIST['dist_barriers'] += int(barriers)
        _DIST['dist_barrier_wait_ms'] += float(barrier_wait_ms)
        _DIST['dist_dead_hosts_detected'] += int(dead_hosts_detected)
        _DIST['dist_allreduce_rounds'] += int(allreduce_rounds)
        _DIST['dist_allreduce_bytes'] += int(allreduce_bytes)
        _DIST['dist_tx_bytes'] += int(tx_bytes)
        _DIST['dist_rx_bytes'] += int(rx_bytes)
        if topology is not None:
            _DIST['dist_%s_bytes' % topology] += \
                int(tx_bytes) + int(rx_bytes)
        _DIST['dist_overlap_ms'] += float(overlap_ms)
        _DIST['dist_restarts'] += int(restarts)


def dist_stats():
    """Snapshot of the dist-runtime counters (also merged into
    summary() and dump_profile 'dist' metadata)."""
    with _STATE['lock']:
        return dict(_DIST)


# serving-engine counters (serving.InferenceEngine's dynamic batcher):
# coalesced dispatches, batch fill / pad waste, batcher queue depth
# observations, and a bounded ring of request latencies for p50/p99
_SERVING = {
    'serve_requests': 0,
    'serve_batches': 0,
    'serve_rows': 0,
    'serve_padded_rows': 0,
    'serve_fill_sum': 0.0,
    'serve_pad_elem_frac_sum': 0.0,
    'serve_queue_depth_sum': 0,
    'serve_queue_depth_obs': 0,
}
_SERVE_LAT_CAP = 8192
_SERVE_LAT = []                 # ring buffer of request latencies (ms)
_SERVE_LAT_POS = [0]


def add_serving_stats(requests=0, batches=0, rows=0, padded_rows=0,
                      fill=None, pad_elem_frac=None, queue_depth=None,
                      latencies_ms=()):
    """Accumulate serving counters (the engine's completion thread
    feeds one call per coalesced dispatch)."""
    with _STATE['lock']:
        _SERVING['serve_requests'] += requests
        _SERVING['serve_batches'] += batches
        _SERVING['serve_rows'] += rows
        _SERVING['serve_padded_rows'] += padded_rows
        if fill is not None:
            _SERVING['serve_fill_sum'] += float(fill)
        if pad_elem_frac is not None:
            _SERVING['serve_pad_elem_frac_sum'] += float(pad_elem_frac)
        if queue_depth is not None:
            _SERVING['serve_queue_depth_sum'] += int(queue_depth)
            _SERVING['serve_queue_depth_obs'] += 1
        for lat in latencies_ms:
            if len(_SERVE_LAT) < _SERVE_LAT_CAP:
                _SERVE_LAT.append(float(lat))
            else:   # overwrite oldest: percentiles track recent traffic
                _SERVE_LAT[_SERVE_LAT_POS[0]] = float(lat)
                _SERVE_LAT_POS[0] = (_SERVE_LAT_POS[0] + 1) \
                    % _SERVE_LAT_CAP


def serving_stats():
    """Snapshot of the serving counters plus derived means and request
    latency percentiles (serve_latency_p50_ms / p99; 0.0 when no
    requests were served)."""
    with _STATE['lock']:
        out = dict(_SERVING)
        lats = list(_SERVE_LAT)
    b = out.pop('serve_fill_sum'), out.pop('serve_pad_elem_frac_sum')
    nb = out['serve_batches']
    out['serve_batch_fill_avg'] = b[0] / nb if nb else 0.0
    out['serve_pad_elem_frac_avg'] = b[1] / nb if nb else 0.0
    qs = out.pop('serve_queue_depth_sum')
    qo = out.pop('serve_queue_depth_obs')
    out['serve_queue_depth_avg'] = qs / qo if qo else 0.0
    total = out['serve_rows'] + out['serve_padded_rows']
    out['serve_pad_waste_frac'] = \
        out['serve_padded_rows'] / total if total else 0.0
    if lats:
        out['serve_latency_p50_ms'] = float(np.percentile(lats, 50))
        out['serve_latency_p99_ms'] = float(np.percentile(lats, 99))
    else:
        out['serve_latency_p50_ms'] = 0.0
        out['serve_latency_p99_ms'] = 0.0
    return out


# fleet serving-tier counters (serving_fleet.ModelRegistry + HTTP
# front + continuous batcher): registry paging activity, SLO shed
# decisions, HTTP admission, and continuous-batching slot utilization
_FLEET = {
    'fleet_models_registered': 0,
    'fleet_loads': 0,            # model made resident (engine warmed)
    'fleet_evictions': 0,        # byte-budget LRU paged a model out
    'fleet_shed_requests': 0,    # Overloaded raised at admission
    'fleet_http_requests': 0,
    'fleet_http_429': 0,         # backpressure surfaced to a client
    'fleet_resident_bytes': 0,   # gauge: registry-resident weight bytes
    'cont_ticks': 0,             # continuous-batcher timesteps run
    'cont_active_row_ticks': 0,  # slot-ticks doing real sequence work
    'cont_slot_ticks': 0,        # slot-ticks available (ticks x slots)
    'cont_admitted': 0,
    'cont_retired': 0,
    'cont_chunks_dispatched': 0,    # K-tick scan dispatches (PERF r20)
    'cont_chunk_ticks': 0,          # timesteps run inside those chunks
    'cont_boundary_wait_ms': 0.0,   # est. queue wait behind slots
                                    # freed mid-chunk (masked until the
                                    # chunk boundary)
    'cont_lone_fast_path': 0,       # 1-slot-rung dispatches (lone
                                    # active request skipped the
                                    # full-slots program)
    'cont_exact_fill_admits': 0,    # chunk stagings that skipped the
                                    # pad memset (every slot active
                                    # for all K ticks)
    'cont_staged_chunks': 0,        # chunks built in the shadow buffer
                                    # while the previous dispatch ran
    'cont_stage_overlap_ms': 0.0,   # host staging wall hidden behind
                                    # an in-flight chunk dispatch
}


def add_fleet_stats(resident_bytes=None, **deltas):
    """Accumulate fleet serving-tier counters (resident_bytes is a
    GAUGE — set, not added; everything else adds — counters seeded
    as floats, e.g. cont_boundary_wait_ms, accumulate fractional
    deltas instead of truncating)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            key = 'fleet_' + k if 'fleet_' + k in _FLEET else k
            _FLEET[key] += float(v) if isinstance(_FLEET[key], float) \
                else int(v)
        if resident_bytes is not None:
            _FLEET['fleet_resident_bytes'] = int(resident_bytes)


def fleet_stats():
    """Snapshot of the fleet serving counters plus the derived
    continuous-batching utilization (active slot-ticks / available
    slot-ticks; 1.0 = every slot of every dispatch did real work)."""
    with _STATE['lock']:
        out = dict(_FLEET)
    st = out['cont_slot_ticks']
    out['cont_utilization'] = \
        out['cont_active_row_ticks'] / st if st else 0.0
    return out


# low-precision counters (PERF round 17: the int8 stack's three arms —
# serving.InferenceEngine(quantize=), the registry's quantized
# residency/paging, and the dist.allreduce wire format).  Gauges:
# quant_models_resident (registry-resident engines serving quantized
# weights), quant_paged_bytes (host bytes held by quantized page-out
# images), quant_error_feedback_norm (L2 of the wire codec's carried
# residual after the last round).  The rest accumulate:
# quant_int8_rungs_warmed (ladder rungs compiled/warmed in quantized
# mode), quant_wire_bytes_saved (fp32 bytes minus actual wire bytes
# across compressed allreduce rounds, both directions), quant_page_ins
# (models re-warmed from a quantized host image instead of their
# loader/disk).
_QUANT = {
    'quant_models_resident': 0,         # gauge
    'quant_int8_rungs_warmed': 0,
    'quant_wire_bytes_saved': 0,
    'quant_error_feedback_norm': 0.0,   # gauge
    'quant_page_ins': 0,
    'quant_paged_bytes': 0,             # gauge
}


def add_quant_stats(models_resident=None, error_feedback_norm=None,
                    paged_bytes=None, **deltas):
    """Accumulate low-precision counters (the three gauge keyword
    args SET; everything else adds — keys arrive without the quant_
    prefix: int8_rungs_warmed=1, wire_bytes_saved=n, page_ins=1)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            _QUANT['quant_' + k] += int(v)
        if models_resident is not None:
            _QUANT['quant_models_resident'] = int(models_resident)
        if error_feedback_norm is not None:
            _QUANT['quant_error_feedback_norm'] = \
                float(error_feedback_norm)
        if paged_bytes is not None:
            _QUANT['quant_paged_bytes'] = int(paged_bytes)


def quant_stats():
    """Snapshot of the low-precision counters (also merged into
    summary() and dump_profile's 'quant' metadata lane)."""
    with _STATE['lock']:
        return dict(_QUANT)


# train->serve loop counters (PERF round 18): the elastic on_commit ->
# FleetSupervisor.push canary -> PushVerdict feedback pipeline
# (fleet_supervisor.CheckpointPusher) and mid-flight sequence migration
# across ContinuousEngine hot-swaps.  loop_pushes counts candidates that
# reached the fleet; loop_push_failures counts pushes that raised
# (BudgetExceeded, dead fleet, injected MXNET_TPU_FAULT_PUSH_FAIL);
# loop_push_queue_skipped counts commits dropped because a push was
# still in flight / the bounded queue was full (training never stalls —
# the checkpoint-writer skip discipline).  Verdicts count by kind;
# loop_consecutive_rollbacks is a GAUGE of the pusher's current
# rollback streak (the divergence-stop signal).  Swap counters:
# migrated = in-flight slots re-admitted into a replacement engine,
# dropped = slots whose exported state was lost (replayed from t=0,
# MXNET_TPU_FAULT_SWAP_DROP_STATE), divergent = slots migrated across a
# MODEL change (their remaining steps run under different weights).
_LOOP = {
    'loop_pushes': 0,
    'loop_push_failures': 0,
    'loop_push_queue_skipped': 0,
    'loop_verdicts_promoted': 0,
    'loop_verdicts_rolled_back': 0,
    'loop_consecutive_rollbacks': 0,    # gauge
    'loop_swap_migrated_slots': 0,
    'loop_swap_dropped_slots': 0,
    'loop_swap_divergent_slots': 0,
    'loop_lr_backoffs': 0,
}


def add_loop_stats(consecutive_rollbacks=None, **deltas):
    """Accumulate train->serve loop counters (consecutive_rollbacks is
    a GAUGE — set, not added; everything else adds).  Keys arrive
    without the loop_ prefix (pushes=1, verdicts_promoted=1,
    swap_migrated_slots=n, ...)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            _LOOP['loop_' + k] += int(v)
        if consecutive_rollbacks is not None:
            _LOOP['loop_consecutive_rollbacks'] = \
                int(consecutive_rollbacks)


def loop_stats():
    """Snapshot of the train->serve loop counters (also merged into
    summary() and dump_profile's 'loop' metadata lane)."""
    with _STATE['lock']:
        return dict(_LOOP)


# weight-delta counters (PERF round 22): the move-only-what-changed
# layer — incremental checkpoint commits (elastic delta-* dirs), the
# push channel's delta shipping, and delta page-image updates.
# delta_committed/applied count delta commits written / deltas applied
# to a resident state (engine, registry image, chain replay);
# delta_bytes vs delta_full_bytes is the byte story (what the deltas
# cost vs what full images would have);  delta_chain_len is a GAUGE of
# the writer's current chain sequence number (0 right after a full
# base).  delta_rebases counts delta-role commits that fell back to a
# full base (no chain / shape change / encoder refusal) plus push-
# channel rebases;  delta_fallbacks counts resume-time chain breaks
# skipped past (torn delta payload, reaped base, fingerprint
# mismatch);  delta_push_fallbacks counts pushes that shipped a FULL
# image because the replica's resident fingerprint didn't match;
# delta_parity_refusals counts typed DeltaParityError refusals (gate
# tripped, nothing mutated).
_DELTA = {
    'delta_committed': 0,
    'delta_applied': 0,
    'delta_bytes': 0,
    'delta_full_bytes': 0,
    'delta_chain_len': 0,       # gauge
    'delta_rebases': 0,
    'delta_fallbacks': 0,
    'delta_pushes': 0,
    'delta_push_fallbacks': 0,
    'delta_page_applies': 0,
    'delta_parity_refusals': 0,
}


def add_delta_stats(chain_len=None, **deltas):
    """Accumulate weight-delta counters (chain_len is a GAUGE — set,
    not added; everything else adds).  Keys arrive without the delta_
    prefix (committed=1, bytes=n, push_fallbacks=1, ...)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            _DELTA['delta_' + k] += int(v)
        if chain_len is not None:
            _DELTA['delta_chain_len'] = int(chain_len)


def delta_stats():
    """Snapshot of the weight-delta counters (also merged into
    summary() and dump_profile's 'delta' metadata lane)."""
    with _STATE['lock']:
        return dict(_DELTA)


# host-hiding counters (PERF round 21): the overlap layer across both
# hot paths — bounded-depth train-step pipelining (gluon.FusedStep /
# Module.fit's deferred metric drain), the continuous batcher's
# shadow-buffer chunk staging, and the adaptive tick-chunk chooser.
# Gauges: overlap_steps_ahead (current in-flight train-step depth),
# overlap_auto_k (the chunk length the adaptive chooser last picked).
_OVERLAP = {
    'overlap_train_steps': 0,        # steps run through the pipeline
    'overlap_steps_ahead': 0,        # gauge: in-flight depth now
    'overlap_dispatch_wait_ms': 0.0,  # host blocked draining the
                                      # oldest in-flight step
    'overlap_deferred_metric_folds': 0,  # fit metric updates run at
                                         # drain time, not per batch
    'overlap_stage_chunks': 0,       # serving chunks staged ahead
    'overlap_stage_overlap_ms': 0.0,  # staging wall hidden behind an
                                      # in-flight chunk dispatch
    'overlap_auto_k_decisions': 0,   # adaptive chooser changed K
    'overlap_auto_k': 0,             # gauge: current auto-chosen K
}


def add_overlap_stats(steps_ahead=None, auto_k=None, **deltas):
    """Accumulate host-hiding counters (steps_ahead and auto_k are
    GAUGES — set, not added; everything else adds — float-seeded keys
    accumulate fractional deltas).  Keys arrive without the overlap_
    prefix (train_steps=1, dispatch_wait_ms=0.4, stage_chunks=1,
    auto_k_decisions=1, ...)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            key = 'overlap_' + k
            _OVERLAP[key] += float(v) \
                if isinstance(_OVERLAP[key], float) else int(v)
        if steps_ahead is not None:
            _OVERLAP['overlap_steps_ahead'] = int(steps_ahead)
        if auto_k is not None:
            _OVERLAP['overlap_auto_k'] = int(auto_k)


def overlap_stats():
    """Snapshot of the host-hiding counters (also merged into
    summary() and dump_profile's 'overlap' metadata lane)."""
    with _STATE['lock']:
        return dict(_OVERLAP)


# self-healing fleet-supervisor counters (fleet_supervisor.FleetRouter +
# FleetSupervisor): replica lifecycle (spawn/restart/retire + the live
# gauge), router retry/fast-503 behavior under replica death, and
# continuous-deployment outcomes (canary pushes/promotions/rollbacks,
# shadow-replay traffic and divergences)
_FLEET_SUP = {
    'fleet_supervisor_replica_spawns': 0,
    'fleet_supervisor_replica_restarts': 0,
    'fleet_supervisor_replica_retires': 0,
    'fleet_supervisor_replicas_live': 0,    # gauge
    'fleet_supervisor_router_requests': 0,
    'fleet_supervisor_router_retries': 0,
    'fleet_supervisor_router_503': 0,
    'fleet_supervisor_canary_pushes': 0,
    'fleet_supervisor_canary_promotions': 0,
    'fleet_supervisor_canary_rollbacks': 0,
    'fleet_supervisor_shadow_requests': 0,
    'fleet_supervisor_shadow_divergences': 0,
}


def add_fleet_supervisor_stats(replicas_live=None, **deltas):
    """Accumulate fleet-supervisor counters (replicas_live is a GAUGE
    — set, not added; everything else adds).  Keys arrive without the
    fleet_supervisor_ prefix (router_retries=1, canary_rollbacks=1,
    ...)."""
    with _STATE['lock']:
        for k, v in deltas.items():
            _FLEET_SUP['fleet_supervisor_' + k] += int(v)
        if replicas_live is not None:
            _FLEET_SUP['fleet_supervisor_replicas_live'] = \
                int(replicas_live)


def fleet_supervisor_stats():
    """Snapshot of the fleet-supervisor counters (also merged into
    summary(), dump_profile's 'fleet_supervisor' metadata lane, and
    the router's /statsz)."""
    with _STATE['lock']:
        return dict(_FLEET_SUP)


def add_comm_bytes(reduce_scattered=0, all_gathered=0):
    """Accumulate logical collective payload bytes (ZeRO-1 fused
    steps: gradients reduce-scattered, updated params all-gathered)."""
    with _STATE['lock']:
        _COMM['bytes_reduce_scattered'] += int(reduce_scattered)
        _COMM['bytes_all_gathered'] += int(all_gathered)


def set_optimizer_state_bytes(n):
    """Record the optimizer-state bytes resident PER DEVICE (momenta +
    fp32 masters; 1/dp of the total under ZeRO-1)."""
    with _STATE['lock']:
        _COMM['optimizer_state_bytes_per_device'] = int(n)


def comm_stats():
    """Snapshot of the comm/memory counters (also merged into
    summary() and dump_profile metadata)."""
    with _STATE['lock']:
        return dict(_COMM)


def profiler_set_config(mode='symbolic', filename='profile.json',
                        profile_xla=False, xla_trace_dir=None):
    """Configure the profiler (reference profiler_set_config,
    c_api.cc MXSetProfilerConfig:98).  mode: 'symbolic' records
    executor/engine-level spans; 'all' also records imperative ops."""
    assert mode in ('symbolic', 'all', 'all_ops')
    _STATE['mode'] = 'all' if mode in ('all', 'all_ops') else 'symbolic'
    _STATE['filename'] = filename
    _STATE['jax_trace'] = bool(profile_xla)
    _STATE['jax_trace_dir'] = xla_trace_dir or \
        os.path.splitext(filename)[0] + '_xla'


def profiler_set_state(state='stop'):
    """'run' starts recording, 'stop' halts it (reference
    MXSetProfilerState, c_api.cc:122)."""
    assert state in ('run', 'stop')
    running = state == 'run'
    if running and not _STATE['running'] and _STATE['jax_trace']:
        import jax
        jax.profiler.start_trace(_STATE['jax_trace_dir'])
    if not running and _STATE['running'] and _STATE['jax_trace']:
        import jax
        jax.profiler.stop_trace()
    _STATE['running'] = running


def dump_profile():
    """Write accumulated records as a Chrome trace-event file
    (reference Profiler::DumpProfile, profiler.cc:139-192).

    When profile_xla was enabled, the XLA trace's per-op spans are
    merged in as additional process lanes (pid >= 100): on TPU the
    '/device:TPU:N' lanes carry real device-side op attribution (the
    reference's per-op OprExecStat timing, §5.1); on the CPU backend
    the '/host:CPU' XLA runtime lane appears instead.  Python-frame
    spans ('$...' names) from the XLA trace are dropped — the host
    story is this profiler's own spans, which are in those lanes too
    ('mx.<name>' TraceAnnotations, on the trace's clock): the pid-0
    copies on the host's perf_counter clock are written only when no
    XLA trace was taken."""
    events = [{'ph': 'M', 'name': 'process_name', 'pid': 0,
               'args': {'name': 'mxnet_tpu host spans'}}]
    # compiled-program cache + ZeRO comm/memory counters ride along
    # as trace metadata
    events.append({'ph': 'M', 'name': 'exec_cache', 'pid': 0,
                   'args': exec_cache_stats()})
    events.append({'ph': 'M', 'name': 'comm', 'pid': 0,
                   'args': comm_stats()})
    events.append({'ph': 'M', 'name': 'input_pipeline', 'pid': 0,
                   'args': input_stats()})
    events.append({'ph': 'M', 'name': 'serving', 'pid': 0,
                   'args': serving_stats()})
    events.append({'ph': 'M', 'name': 'gluon_fused', 'pid': 0,
                   'args': gluon_fused_stats()})
    events.append({'ph': 'M', 'name': 'bucketing', 'pid': 0,
                   'args': bucketing_stats()})
    events.append({'ph': 'M', 'name': 'pipeline', 'pid': 0,
                   'args': pipe_stats()})
    events.append({'ph': 'M', 'name': 'moe', 'pid': 0,
                   'args': moe_stats()})
    events.append({'ph': 'M', 'name': 'embed', 'pid': 0,
                   'args': embed_stats()})
    events.append({'ph': 'M', 'name': 'checkpoint', 'pid': 0,
                   'args': ckpt_stats()})
    events.append({'ph': 'M', 'name': 'dist', 'pid': 0,
                   'args': dist_stats()})
    events.append({'ph': 'M', 'name': 'fleet', 'pid': 0,
                   'args': fleet_stats()})
    events.append({'ph': 'M', 'name': 'fleet_supervisor', 'pid': 0,
                   'args': fleet_supervisor_stats()})
    events.append({'ph': 'M', 'name': 'quant', 'pid': 0,
                   'args': quant_stats()})
    events.append({'ph': 'M', 'name': 'loop', 'pid': 0,
                   'args': loop_stats()})
    events.append({'ph': 'M', 'name': 'delta', 'pid': 0,
                   'args': delta_stats()})
    events.append({'ph': 'M', 'name': 'overlap', 'pid': 0,
                   'args': overlap_stats()})
    lanes = _collect_xla_lanes()
    if not lanes:
        with _STATE['lock']:
            records = list(_STATE['records'])
        for name, cat, ts, dur, tid in records:
            events.append({'name': name, 'cat': cat, 'ph': 'X',
                           'ts': ts, 'dur': dur, 'pid': 0, 'tid': tid})
    events.extend(lanes)
    with open(_STATE['filename'], 'w') as f:
        json.dump({'traceEvents': events, 'displayTimeUnit': 'ms'}, f)
    return _STATE['filename']


def _collect_xla_lanes():
    """Parse the newest XLA trace dump (plugins/profile/<ts>/
    *.trace.json.gz) and remap its processes to pids 100+."""
    trace_dir = _STATE['jax_trace_dir']
    if not _STATE['jax_trace'] or not trace_dir:
        return []
    import glob
    import gzip
    dumps = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.trace.json.gz')))
    if not dumps:
        return []
    try:
        with gzip.open(dumps[-1]) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return []
    raw = data.get('traceEvents', [])
    names = {}
    for e in raw:
        if e.get('ph') == 'M' and e.get('name') == 'process_name':
            names[e['pid']] = e['args'].get('name', str(e['pid']))
    pid_map = {pid: 100 + i for i, pid in enumerate(sorted(names))}
    out = [{'ph': 'M', 'name': 'process_name', 'pid': new,
            'args': {'name': 'xla %s' % names[old]}}
           for old, new in pid_map.items()]
    for e in raw:
        if e.get('ph') != 'X' or e['pid'] not in pid_map:
            continue
        name = e.get('name', '')
        if name.startswith('$'):
            continue  # python-frame span, not an XLA op
        out.append({'name': name, 'cat': 'xla', 'ph': 'X',
                    'ts': e.get('ts', 0), 'dur': e.get('dur', 0),
                    'pid': pid_map[e['pid']], 'tid': e.get('tid', 0)})
    return out


def exec_cache_stats():
    """Executor compiled-program cache counters: exec_cache_hits /
    exec_cache_misses (signature lookups at bind), total_compile_s
    (wall time spent tracing+compiling XLA programs this process), and
    under exec_cache's own names what jax reported of its traces,
    lowerings, backend compiles and persistent cache."""
    from . import exec_cache
    st = exec_cache.stats()
    st['exec_cache_hits'] = st.pop('hits')
    st['exec_cache_misses'] = st.pop('misses')
    return st


def setup_stats():
    """Where the time before the first steady step went, assembled from
    what the program keeps anyway (this call holds no state): import_s,
    the package's own import; the summed seconds and the count of the
    'module.bind', 'module.init_params' and 'module.init_optimizer'
    spans in the ring (bind_s, bind_n, ...); first_step_s, the oldest
    'module.bulk_step' span or, with none, the oldest 'fit.step' (the
    one that traced, lowered and compiled or loaded the step program;
    None with neither, or once that ring has wrapped); and exec_cache's
    figures from jax (trace_s ... persistent_misses)."""
    from . import exec_cache, import_s
    out = {'import_s': import_s}
    for name in ('bind', 'init_params', 'init_optimizer'):
        ring = _RING.get('module.' + name)
        spans = ring.copy() if ring else ()     # copy() is atomic
        out[name + '_s'] = sum(s[1] - s[0] for s in spans)
        out[name + '_n'] = len(spans)
    first = span_head('module.bulk_step' if _RING.get('module.bulk_step')
                      else 'fit.step', 1)
    out['first_step_s'] = first[0][1] - first[0][0] if first else None
    st = exec_cache.stats()
    for key in ('trace_s', 'lower_s', 'backend_compile_s', 'cache_load_s',
                'persistent_requests', 'persistent_hits',
                'persistent_misses'):
        out[key] = st[key]
    return out


def _setup_lines():
    """summary()'s set-up block: setup_stats() and every backend compile
    of over a second with the span it ran under."""
    from . import exec_cache
    st = setup_stats()
    first = st['first_step_s']
    lines = ['  set-up: import_s=%.3f bind_s=%.3f (%d) '
             'init_params_s=%.3f (%d) init_optimizer_s=%.3f (%d) '
             'first_step_s=%s'
             % (st['import_s'], st['bind_s'], st['bind_n'],
                st['init_params_s'], st['init_params_n'],
                st['init_optimizer_s'], st['init_optimizer_n'],
                'none' if first is None else '%.3f' % first),
             '    trace_s=%.3f lower_s=%.3f backend_compile_s=%.3f '
             'cache_load_s=%.3f persistent_requests=%d '
             'persistent_hits=%d persistent_misses=%d'
             % (st['trace_s'], st['lower_s'], st['backend_compile_s'],
                st['cache_load_s'], st['persistent_requests'],
                st['persistent_hits'], st['persistent_misses'])]
    for _end, seconds, fun_name, span in exec_cache.compile_log():
        if seconds > 1.0:
            lines.append('    compiled %s in %.3f s under %s'
                         % (fun_name, seconds, span or 'no span'))
    return lines


def summary(print_out=True):
    """Human-readable profile summary: span time by category plus the
    compiled-program cache counters (reference: the profiler's
    aggregate stats print, profiler.cc DumpProfile summary mode)."""
    with _STATE['lock']:
        records = list(_STATE['records'])
    by_cat = {}
    for _name, cat, _ts, dur, _tid in records:
        by_cat[cat] = by_cat.get(cat, 0) + dur
    st = exec_cache_stats()
    lines = ['profile summary: %d spans' % len(records)]
    for cat in sorted(by_cat):
        lines.append('  %-16s %10.3f ms' % (cat, by_cat[cat] / 1e3))
    lines.append('  exec_cache_hits=%d exec_cache_misses=%d '
                 'total_compile_s=%.3f'
                 % (st['exec_cache_hits'], st['exec_cache_misses'],
                    st['total_compile_s']))
    lines.extend(_setup_lines())
    cm = comm_stats()
    lines.append('  bytes_reduce_scattered=%d bytes_all_gathered=%d '
                 'optimizer_state_bytes_per_device=%d'
                 % (cm['bytes_reduce_scattered'],
                    cm['bytes_all_gathered'],
                    cm['optimizer_state_bytes_per_device']))
    lines.append('  reduce_buckets_issued=%d scan_fused_metric_steps=%d'
                 % (cm['reduce_buckets_issued'],
                    cm['scan_fused_metric_steps']))
    ip = input_stats()
    lines.append('  decode_ms=%.3f decoded_samples=%d '
                 'decode_wait_ms=%.3f queue_depth_avg=%.2f '
                 'input_stall_ms_per_batch=%.3f'
                 % (ip['decode_ms'], ip['decoded_samples'],
                    ip['decode_wait_ms'], ip['queue_depth_avg'],
                    ip['input_stall_ms_per_batch']))
    sv = serving_stats()
    lines.append('  serve_requests=%d serve_batches=%d '
                 'serve_queue_depth_avg=%.2f serve_batch_fill_avg=%.2f '
                 'serve_pad_waste_frac=%.3f serve_latency_p50_ms=%.3f '
                 'serve_latency_p99_ms=%.3f'
                 % (sv['serve_requests'], sv['serve_batches'],
                    sv['serve_queue_depth_avg'],
                    sv['serve_batch_fill_avg'],
                    sv['serve_pad_waste_frac'],
                    sv['serve_latency_p50_ms'],
                    sv['serve_latency_p99_ms']))
    gf = gluon_fused_stats()
    lines.append('  gluon_fused_steps=%d gluon_fused_dispatches=%d '
                 'gluon_fused_steps_per_dispatch=%.2f'
                 % (gf['gluon_fused_steps'],
                    gf['gluon_fused_dispatches'],
                    gf['gluon_fused_steps_per_dispatch']))
    pi = pipe_stats()
    lines.append('  pipe_dispatches=%d pipe_steps=%d '
                 'pipe_microbatches=%d pipe_stages=%d '
                 'pipe_num_micro=%d pipe_bubble_frac=%.3f '
                 'pipe_param_bytes_per_device=%d '
                 'pipe_state_bytes_per_device=%d'
                 % (pi['pipe_dispatches'], pi['pipe_steps'],
                    pi['pipe_microbatches'], pi['pipe_stages'],
                    pi['pipe_num_micro'], pi['pipe_bubble_frac'],
                    pi['pipe_param_bytes_per_device'],
                    pi['pipe_state_bytes_per_device']))
    mo = moe_stats()
    lines.append('  moe_routed_tokens=%d moe_dropped_tokens=%d '
                 'moe_drop_frac=%.3f moe_dispatches=%d'
                 % (mo['moe_routed_tokens'], mo['moe_dropped_tokens'],
                    mo['moe_drop_frac'], mo['moe_dispatches']))
    for ek in sorted(mo['moe_experts'],
                     key=lambda s: int(s[1:])):
        e = mo['moe_experts'][ek]
        lines.append('    expert %-4s routed=%d dropped=%d'
                     % (ek, e['routed'], e['dropped']))
    em = embed_stats()
    lines.append('  embed_steps=%d embed_dispatches=%d '
                 'embed_unique_rows=%d embed_touched_bytes=%d '
                 'embed_dense_equiv_bytes=%d embed_touched_frac=%.4f '
                 'embed_max_rung=%d'
                 % (em['embed_steps'], em['embed_dispatches'],
                    em['embed_unique_rows'], em['embed_touched_bytes'],
                    em['embed_dense_equiv_bytes'],
                    em['embed_touched_frac'], em['embed_max_rung']))
    lines.append('  hotrow_hits=%d hotrow_misses=%d '
                 'hotrow_hit_rate=%.3f hotrow_evictions=%d '
                 'hotrow_resident_bytes=%d'
                 % (em['hotrow_hits'], em['hotrow_misses'],
                    em['hotrow_hit_rate'], em['hotrow_evictions'],
                    em['hotrow_resident_bytes']))
    bk = bucketing_stats()
    lines.append('  train_bucket_switches=%d train_pad_waste_rows=%d '
                 'train_pad_waste_frac=%.3f'
                 % (bk['train_bucket_switches'],
                    bk['train_pad_waste_rows'],
                    bk['train_pad_waste_frac']))
    for rung in sorted(bk['train_rungs']):
        e = bk['train_rungs'][rung]
        lines.append('    rung %-8s steps=%d dispatches=%d compiles=%d '
                     'warmups=%d warm_compiles=%d'
                     % (rung, e['steps'], e['dispatches'],
                        e['compiles'], e['warmups'],
                        e['warm_compiles']))
    ck = ckpt_stats()
    lines.append('  ckpt_snapshots=%d ckpt_bytes=%d '
                 'ckpt_async_overlap_ms=%.3f ckpt_commit_ms=%.3f '
                 'ckpt_torn_fallbacks=%d ckpt_restores=%d '
                 'ckpt_skipped=%d ckpt_failed_writes=%d'
                 % (ck['ckpt_snapshots'], ck['ckpt_bytes'],
                    ck['ckpt_async_overlap_ms'], ck['ckpt_commit_ms'],
                    ck['ckpt_torn_fallbacks'], ck['ckpt_restores'],
                    ck['ckpt_skipped'], ck['ckpt_failed_writes']))
    ds = dist_stats()
    lines.append('  dist_heartbeats_sent=%d dist_heartbeats_missed=%d '
                 'dist_barriers=%d dist_barrier_wait_ms=%.3f '
                 'dist_dead_hosts_detected=%d dist_allreduce_rounds=%d '
                 'dist_allreduce_bytes=%d dist_restarts=%d'
                 % (ds['dist_heartbeats_sent'],
                    ds['dist_heartbeats_missed'], ds['dist_barriers'],
                    ds['dist_barrier_wait_ms'],
                    ds['dist_dead_hosts_detected'],
                    ds['dist_allreduce_rounds'],
                    ds['dist_allreduce_bytes'], ds['dist_restarts']))
    lines.append('  dist_tx_bytes=%d dist_rx_bytes=%d '
                 'dist_star_bytes=%d dist_ring_bytes=%d '
                 'dist_sparse_bytes=%d dist_overlap_ms=%.3f'
                 % (ds['dist_tx_bytes'], ds['dist_rx_bytes'],
                    ds['dist_star_bytes'], ds['dist_ring_bytes'],
                    ds['dist_sparse_bytes'], ds['dist_overlap_ms']))
    fl = fleet_stats()
    lines.append('  fleet_loads=%d fleet_evictions=%d '
                 'fleet_shed_requests=%d fleet_http_requests=%d '
                 'fleet_http_429=%d fleet_resident_bytes=%d '
                 'cont_ticks=%d cont_utilization=%.3f'
                 % (fl['fleet_loads'], fl['fleet_evictions'],
                    fl['fleet_shed_requests'],
                    fl['fleet_http_requests'], fl['fleet_http_429'],
                    fl['fleet_resident_bytes'], fl['cont_ticks'],
                    fl['cont_utilization']))
    lines.append('  cont_chunks_dispatched=%d cont_chunk_ticks=%d '
                 'cont_boundary_wait_ms=%.3f cont_lone_fast_path=%d '
                 'cont_exact_fill_admits=%d'
                 % (fl['cont_chunks_dispatched'],
                    fl['cont_chunk_ticks'],
                    fl['cont_boundary_wait_ms'],
                    fl['cont_lone_fast_path'],
                    fl['cont_exact_fill_admits']))
    fs = fleet_supervisor_stats()
    lines.append('  fleet_supervisor_replica_spawns=%d '
                 'fleet_supervisor_replica_restarts=%d '
                 'fleet_supervisor_replica_retires=%d '
                 'fleet_supervisor_replicas_live=%d '
                 'fleet_supervisor_router_retries=%d '
                 'fleet_supervisor_router_503=%d'
                 % (fs['fleet_supervisor_replica_spawns'],
                    fs['fleet_supervisor_replica_restarts'],
                    fs['fleet_supervisor_replica_retires'],
                    fs['fleet_supervisor_replicas_live'],
                    fs['fleet_supervisor_router_retries'],
                    fs['fleet_supervisor_router_503']))
    lines.append('  fleet_supervisor_canary_pushes=%d '
                 'fleet_supervisor_canary_promotions=%d '
                 'fleet_supervisor_canary_rollbacks=%d '
                 'fleet_supervisor_shadow_requests=%d '
                 'fleet_supervisor_shadow_divergences=%d'
                 % (fs['fleet_supervisor_canary_pushes'],
                    fs['fleet_supervisor_canary_promotions'],
                    fs['fleet_supervisor_canary_rollbacks'],
                    fs['fleet_supervisor_shadow_requests'],
                    fs['fleet_supervisor_shadow_divergences']))
    qt = quant_stats()
    lines.append('  quant_models_resident=%d quant_int8_rungs_warmed=%d '
                 'quant_wire_bytes_saved=%d '
                 'quant_error_feedback_norm=%.6f quant_page_ins=%d '
                 'quant_paged_bytes=%d'
                 % (qt['quant_models_resident'],
                    qt['quant_int8_rungs_warmed'],
                    qt['quant_wire_bytes_saved'],
                    qt['quant_error_feedback_norm'],
                    qt['quant_page_ins'], qt['quant_paged_bytes']))
    lp = loop_stats()
    lines.append('  loop_pushes=%d loop_push_failures=%d '
                 'loop_push_queue_skipped=%d '
                 'loop_verdicts_promoted=%d '
                 'loop_verdicts_rolled_back=%d '
                 'loop_consecutive_rollbacks=%d'
                 % (lp['loop_pushes'], lp['loop_push_failures'],
                    lp['loop_push_queue_skipped'],
                    lp['loop_verdicts_promoted'],
                    lp['loop_verdicts_rolled_back'],
                    lp['loop_consecutive_rollbacks']))
    lines.append('  loop_swap_migrated_slots=%d '
                 'loop_swap_dropped_slots=%d '
                 'loop_swap_divergent_slots=%d loop_lr_backoffs=%d'
                 % (lp['loop_swap_migrated_slots'],
                    lp['loop_swap_dropped_slots'],
                    lp['loop_swap_divergent_slots'],
                    lp['loop_lr_backoffs']))
    dl = delta_stats()
    lines.append('  delta_committed=%d delta_applied=%d '
                 'delta_bytes=%d delta_full_bytes=%d '
                 'delta_chain_len=%d'
                 % (dl['delta_committed'], dl['delta_applied'],
                    dl['delta_bytes'], dl['delta_full_bytes'],
                    dl['delta_chain_len']))
    lines.append('  delta_rebases=%d delta_fallbacks=%d '
                 'delta_pushes=%d delta_push_fallbacks=%d '
                 'delta_page_applies=%d delta_parity_refusals=%d'
                 % (dl['delta_rebases'], dl['delta_fallbacks'],
                    dl['delta_pushes'], dl['delta_push_fallbacks'],
                    dl['delta_page_applies'],
                    dl['delta_parity_refusals']))
    ov = overlap_stats()
    lines.append('  overlap_train_steps=%d overlap_steps_ahead=%d '
                 'overlap_dispatch_wait_ms=%.3f '
                 'overlap_deferred_metric_folds=%d'
                 % (ov['overlap_train_steps'],
                    ov['overlap_steps_ahead'],
                    ov['overlap_dispatch_wait_ms'],
                    ov['overlap_deferred_metric_folds']))
    lines.append('  overlap_stage_chunks=%d overlap_stage_overlap_ms'
                 '=%.3f overlap_auto_k_decisions=%d overlap_auto_k=%d'
                 % (ov['overlap_stage_chunks'],
                    ov['overlap_stage_overlap_ms'],
                    ov['overlap_auto_k_decisions'],
                    ov['overlap_auto_k']))
    text = '\n'.join(lines)
    if print_out:
        print(text)
    return text


def is_running():
    return _STATE['running']


def mode():
    return _STATE['mode']


def record(name, category, ts_us, dur_us):
    """Append one span (internal hook used by executor/kvstore/io)."""
    if not _STATE['running']:
        return
    with _STATE['lock']:
        _STATE['records'].append(
            (name, category, ts_us, dur_us, threading.get_ident() % 1000))


def clear():
    _RING.clear()
    with _STATE['lock']:
        _STATE['records'].clear()
        for k in _COMM:
            _COMM[k] = 0
        for k in _INPUT:
            _INPUT[k] = type(_INPUT[k])()
        for k in _SERVING:
            _SERVING[k] = type(_SERVING[k])()
        for k in _GLUON_FUSED:
            _GLUON_FUSED[k] = 0
        for k in _BUCKET:
            _BUCKET[k] = 0
        for k in _PIPE:
            _PIPE[k] = type(_PIPE[k])()
        for k in _MOE:
            _MOE[k] = 0
        _MOE_EXPERTS.clear()
        _ATTENTION.clear()
        _DELTA_RULE.clear()
        _CAUSAL_CONV.clear()
        _MOE_PLAN.clear()
        _LOOPED.clear()
        for k in _EMBED:
            _EMBED[k] = 0
        for k in _CKPT:
            _CKPT[k] = type(_CKPT[k])()
        for k in _DIST:
            _DIST[k] = type(_DIST[k])()
        for k in _FLEET:
            _FLEET[k] = type(_FLEET[k])()
        for k in _FLEET_SUP:
            _FLEET_SUP[k] = 0
        for k in _QUANT:
            _QUANT[k] = type(_QUANT[k])()
        for k in _LOOP:
            _LOOP[k] = 0
        for k in _DELTA:
            _DELTA[k] = 0
        for k in _OVERLAP:
            _OVERLAP[k] = type(_OVERLAP[k])()
        _BUCKET_RUNGS.clear()
        del _SERVE_LAT[:]
        _SERVE_LAT_POS[0] = 0


# The fixed span names at the layer boundaries of the training paths
# the benchmark runs, with the layer of each as PERF.md section 3 and
# BENCHMARK.json name it.  In a jax profiler trace each appears as
# 'mx.' + name on the host plane.
_SETUP_LAYER = \
    'set-up (Module.bind, init_params, init_optimizer, package import)'
SPANS = {
    'fit.step': 'entry (Module.fit, _fit_epochs)',
    'fit.metric': 'metric fold (metric.EvalMetric.update_dict)',
    'fit.callback': 'entry (Module.fit, _fit_epochs)',
    'io.next': 'input (io.NDArrayIter, io.prefetch_to_device)',
    'io.host_batch': 'input (io.NDArrayIter, io.prefetch_to_device)',
    'io.stage': 'input (io.NDArrayIter, io.prefetch_to_device)',
    'module.load_batch':
        'executor group (DataParallelExecutorGroup.load_data_batch)',
    'module.host_prep': 'optimizer (optimizer.FusedSGD)',
    'module.bulk_step': 'entry (Module.bulk_step)',
    'module.bulk_stack': 'entry (Module.bulk_step)',
    'executor.dispatch':
        'compiled step (executor.make_fused_multistep, fused train step)',
    'fit.wait': 'metric fold (metric.EvalMetric.update_dict)',
    'module.bind': _SETUP_LAYER,
    'module.init_params': _SETUP_LAYER,
    'module.init_optimizer': _SETUP_LAYER,
}

_RING_LEN = 4096
_RING = {}      # name -> deque of (start, end, self_s, parent, step)
_OPEN = threading.local()       # .stack: this thread's open spans


class scope(object):
    """Context manager marking one span of host time:
    `with profiler.scope('forward'): ...`

    The span is a jax TraceAnnotation 'mx.<name>' (a StepTraceAnnotation
    when `step` is given), so an open jax profiler session records it on
    the device trace's clock; with none open that costs a flag test.
    On exit (start, end, self seconds, parent's name, step) on the
    perf_counter clock joins the ring of its name (the newest
    _RING_LEN; see span_tail), and `seconds` holds the duration.
    Self time is the duration less what the spans opened inside it on
    the same thread took; a span given no step inherits its parent's.
    Under profiler_set_state('run') the span is also appended to the
    Chrome-trace records.  A span never waits for the device."""

    def __init__(self, name, category='operator', step=None):
        self.name = name
        self.category = category
        self.step = step
        self.seconds = 0.0

    def __enter__(self):
        try:
            stack = _OPEN.stack
        except AttributeError:
            stack = _OPEN.stack = []
        self._parent = stack[-1] if stack else None
        if self.step is None:
            if self._parent is not None:
                self.step = self._parent.step
            self._ann = TraceAnnotation('mx.' + self.name)
        else:
            self._ann = StepTraceAnnotation('mx.' + self.name,
                                            step_num=self.step)
        self._child_s = 0.0
        stack.append(self)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _OPEN.stack.pop()
        self.seconds = t1 - self._t0
        parent = self._parent
        if parent is not None:
            parent._child_s += self.seconds
        ring = _RING.get(self.name)
        if ring is None:
            ring = _RING.setdefault(self.name, deque(maxlen=_RING_LEN))
        ring.append((self._t0, t1, self.seconds - self._child_s,
                     parent.name if parent is not None else None,
                     self.step))
        if _STATE['running']:
            record(self.name, self.category,
                   int(self._t0 * 1e6), int(self.seconds * 1e6))
        return False


def span_tail(name, n):
    """The newest n completed spans named `name`, oldest first, as
    (start, end, self_seconds) on the perf_counter clock, or None when
    fewer than n exist (since the last clear())."""
    ring = _RING.get(name)
    if ring is None or len(ring) < n:
        return None
    tail = list(ring.copy())    # copy() is atomic under the GIL
    return [s[:3] for s in tail[len(tail) - n:]]


def span_head(name, n):
    """The oldest n completed spans named `name`, oldest first, as
    span_tail gives them, or None when fewer than n exist or the ring
    is full: its oldest may have been dropped by then."""
    ring = _RING.get(name)
    if ring is None or len(ring) < n or len(ring) == ring.maxlen:
        return None
    return [s[:3] for s in list(ring.copy())[:n]]


def open_span():
    """The name of the innermost span open on this thread, or None."""
    stack = getattr(_OPEN, 'stack', None)
    return stack[-1].name if stack else None


if os.environ.get('MXNET_PROFILER_AUTOSTART', '0') == '1':
    profiler_set_state('run')
