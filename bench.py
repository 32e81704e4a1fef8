"""Headline benchmark: ResNet-50 training throughput on one TPU chip.

Baseline (BASELINE.md): reference MXNet trains ResNet-50 at 109 img/s on
1x K80 (batch 32).  The whole training step (fwd+bwd+fused SGD update)
compiles into ONE donated XLA dispatch, and `Module.bulk_step` loops K
steps on-device per dispatch (lax.scan device loop — the TPU analog of
the reference's bulk-exec segments, graph_executor.cc:1135), so host and
link latency amortize over K full steps.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The dtype rides in the JSON so the comparison basis is explicit
(bfloat16 mixed precision with fp32 master weights by default, matching
the reference's fp16 multi_precision headline mode — NEWS.md:18).
The line names what it ran on (`platform`, `device_kind`, `n_devices`);
this training path needs a TPU and fails without one.
Besides throughput the line reports dispatch-overhead metrics:
`cold_start_s` (bind -> first completed step, includes XLA compile; the
on-disk compile cache sits at a fixed place — `compile_cache_dir`,
exec_cache.setup_persistent_cache — so the same command run a second
time reports the warm start here),
and `input_stall_ms_per_step` (host time blocked in the input pipeline
per training step; 0.0 in the default device-resident input mode).
Env knobs: BENCH_BATCH (default: the per-model DEFAULT_BATCH, else
256 — the batch is part of the configuration, and one that does not
fit is an error that names it), BENCH_STEPS (bulk
dispatches), BENCH_BULK (steps per dispatch), BENCH_DTYPE, BENCH_MODEL
(any K80_IMG_S key below — resnet-N, inception-bn, inception-v3,
alexnet; tools/bench_family.py sweeps them all via this harness),
BENCH_INPUT=device|host|rec (device: batches pre-staged
device-resident, the headline configuration; host: in-memory batches
flow through io.prefetch_to_device and the measured stall is reported;
rec: a synthesized JPEG .rec dataset is decoded+augmented end-to-end
through the parallel host decode pool — BENCH_DECODE_WORKERS /
MXNET_TPU_DECODE_WORKERS sets the worker count, default 8, and the
JSON's input_stall_ms_per_step shows whether the pipeline keeps the
chip fed; BENCH_REC_IMAGES sizes the dataset),
BENCH_INFER=serve (serving mode: measure the dynamic-batching
InferenceEngine against serial per-request Predictor.forward and emit
a throughput + latency-percentile JSON line instead of the training
bench — see serve_bench() / tools/serve_bench.py for the knobs),
BENCH_GLUON=1 (fused Gluon training mode: whole-step-compiled
imperative training vs the per-dispatch early-Gluon loop, plus the
scan-fused-metrics arm — see gluon_bench() for the BENCH_GLUON_*
knobs),
BENCH_OVERLAP=1 (gradient-reduction schedule A/B: backward-interleaved
bucket-by-bucket all-reduce vs the end-of-backward baseline on a
data-parallel mesh — see overlap_bench() for the BENCH_OVERLAP_*
knobs; re-execs onto a virtual CPU mesh when the process has too few
devices),
BENCH_BUCKET=1 (dynamic-shape training mode: legacy 3-dispatch
per-bucket loop vs the AOT-warmed fused bucket ladder vs the
bucket-major bulked ladder on a synthetic length-mixed workload —
see bucket_bench() for the BENCH_BUCKET_* knobs),
BENCH_PIPE=1 (dp×pipe GPipe training mode A/B: dp-only vs dp×pipe vs
dp×pipe+ZeRO on a self-spawned virtual mesh, parity-gated, per-device
param+optimizer-state residency — see pipe_bench() for the
BENCH_PIPE_* knobs),
BENCH_INT8=1 (low-precision stack A/B: fp vs int8 serving with parity
    gate + quantized-registry residency/thrash, and the 2-worker
    allreduce wire-format A/B with loss-curve parity and per-mode
    determinism; BENCH_INT8_* knobs),
BENCH_RING=1 (cross-host gradient transport topology A/B: star
    coordinator vs peer-to-peer ring reduce-scatter vs ring+async
    overlap, launcher-spawned workers, rank-0 ingress counter-verified,
    per-mode bitwise loss determinism, plus the embedding COO-vs-dense
    wire-bytes arm — see ring_bench() for the BENCH_RING_* knobs),
BENCH_LOOP=1 (diurnal autoscale drill: open-loop diurnal trace through
    a real autoscaling localhost fleet — scale-up lag, scale-down flap
    count, peak shed rate; see loop_bench() for the BENCH_LOOP_* knobs),
BENCH_EMBED=1 (sparse embedding A/B: dense vs touched-rows-only
    gradients/updates across uniform/zipf/repeat id distributions,
    parity- and zero-recompile-gated, with a 2x-virtual-device table
    sharding child — see embed_bench() for the BENCH_EMBED_* knobs),
BENCH_CKPT=1 (elastic-checkpoint overhead A/B: no-checkpoint vs
async cadence vs blocking cadence, ckpt_* counters + bit-parity
gate — see ckpt_bench() for the BENCH_CKPT_* knobs),
BENCH_DELTA=1 (incremental delta-checkpoint + weight-delta push A/B:
    full-every-commit vs incremental chain commit bytes on an
    embedding workload, chain-replay resume parity, sparse delta
    applied to a live engine bitwise vs full reload, dense int8 delta
    parity-gated — see delta_bench() for the BENCH_DELTA_* knobs),
MXNET_TPU_ZERO=1 (ZeRO-1 sharded optimizer update on multi-device
meshes; the JSON's `optimizer_state_bytes_per_device` / `zero` fields
track the per-device memory win in BENCH_*/MULTICHIP_* trajectories).
CLI: --no-exec-cache disables the in-process compiled-program cache
(A/B of MXNET_TPU_EXEC_CACHE).
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

# per-model 1x K80 fp32 img/s (BASELINE.md / reference
# example/image-classification/README.md:149-156) — the single source
# tools/bench_family.py imports
K80_IMG_S = {
    'inception-bn': 152.0,
    'resnet-18': 185.0,
    'resnet-34': 172.0,
    'resnet-50': 109.0,
    'resnet-101': 78.0,
    'resnet-152': 57.0,
    # from the scaling table's 1-GPU rows (BASELINE.md; batch 512 / 32)
    'alexnet': 457.07,
    'inception-v3': 30.4,
}

# input edge per model (everything else trains at 224)
IMAGE_EDGE = {'inception-v3': 299}

# per-model default batch (everything else: 256): alexnet's baseline
# row was measured at batch 512
DEFAULT_BATCH = {'alexnet': 512}


def make_symbol(model, dtype):
    """BASELINE.md-family symbol by name (resnet-N / inception-bn /
    inception-v3 / alexnet)."""
    from mxnet_tpu import models
    if model.startswith('resnet-'):
        return models.get_symbol('resnet', num_classes=1000,
                                 num_layers=int(model.split('-')[1]),
                                 dtype=dtype)
    return models.get_symbol(model, num_classes=1000, dtype=dtype)


def _rec_input_source(batch, edge):
    """BENCH_INPUT=rec: synthesize a JPEG .rec dataset in a tempdir and
    open it through the parallel host decode pipeline (ImageIter with
    MXNET_TPU_DECODE_WORKERS / BENCH_DECODE_WORKERS workers, default 8).
    Returns (iterator, worker_count, cleanup)."""
    import cv2
    import mxnet_tpu as mx
    from mxnet_tpu import recordio

    rec_dir = tempfile.mkdtemp(prefix='bench_rec_')
    prefix = os.path.join(rec_dir, 'data')
    n = int(os.environ.get('BENCH_REC_IMAGES', str(max(2 * batch, 512))))
    rng = np.random.RandomState(7)
    rec = recordio.MXIndexedRecordIO(prefix + '.idx', prefix + '.rec', 'w')
    src_edge = edge + 32   # headroom for the random crop
    for i in range(n):
        img = rng.randint(0, 256, (src_edge, src_edge, 3), dtype=np.uint8)
        ok, buf = cv2.imencode('.jpg', img, [cv2.IMWRITE_JPEG_QUALITY, 90])
        assert ok, 'jpeg encode failed'
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 1000), i, 0), buf.tobytes()))
    rec.close()
    workers = int(os.environ.get(
        'MXNET_TPU_DECODE_WORKERS',
        os.environ.get('BENCH_DECODE_WORKERS', '8')))
    it = mx.image.ImageIter(
        batch_size=batch, data_shape=(3, edge, edge),
        path_imgrec=prefix + '.rec', shuffle=False,
        rand_crop=True, rand_mirror=True,
        preprocess_threads=workers)

    def cleanup():
        import shutil
        it.close()
        shutil.rmtree(rec_dir, ignore_errors=True)
    return it, workers, cleanup


def run_symbol(sym, batch, steps, warmup, bulk, dtype, edge=224,
               input_mode='device'):
    """The shared measurement harness: bind, fused bulk_step loop,
    timings closed by block_until_ready.  Runs on TPU 0 and raises
    without one.  Returns a dict: images/sec plus cold_start_s,
    input_stall_ms_per_step and the device it ran on."""
    import jax
    import mxnet_tpu as mx

    ctx = mx.tpu()
    device = ctx.jax_device()       # no TPU: MXNetError, not a CPU run
    mod = mx.mod.Module(sym, context=ctx)
    rng = np.random.RandomState(0)
    # mixed-precision models cast data to the compute dtype as their
    # first op, so storing the K stacked scan batches in that dtype is
    # value-preserving (bulk_step casts back before the graph) and
    # halves their footprint — which is what lets K reach 32
    scan_dtype = dtype if dtype != 'float32' else None

    prefetch = None
    cleanup = None
    decode_workers = None
    if input_mode in ('host', 'rec'):
        if input_mode == 'rec':
            # end-to-end .rec path: JPEG decode + augment in the
            # parallel worker pool, batches through the device prefetch
            # — the measured stall is the REAL input-pipeline stall
            src, decode_workers, cleanup = _rec_input_source(batch, edge)
        else:
            # host input pipeline: a small cycling dataset flows through
            # io.prefetch_to_device, so the H2D copy of upcoming batches
            # overlaps device compute and the real stall gets measured
            nb = max(2, min(4, bulk))
            Xh = rng.rand(nb * batch, 3, edge, edge).astype(np.float32)
            yh = (rng.rand(nb * batch) * 1000).astype(np.float32)
            src = mx.io.NDArrayIter(Xh, yh, batch_size=batch,
                                    label_name='softmax_label')
        prefetch = mx.io.prefetch_to_device(src, size=2, device=ctx)

        def pull(k):
            out = []
            while len(out) < k:
                try:
                    out.append(prefetch.next())
                except StopIteration:
                    prefetch.reset()
            return out

        def step():
            bs = pull(bulk)
            if bulk > 1:
                mod.bulk_step(batches=bs, scan_dtype=scan_dtype)
            else:
                mod.forward_backward(bs[0])
                mod.update()
    else:
        # headline configuration: batches pre-staged device-resident
        # (pure compute measurement, zero input stall by construction)
        batches = [
            mx.io.DataBatch(
                data=[mx.nd.array(
                    rng.rand(batch, 3, edge, edge).astype(np.float32),
                    ctx=ctx)],
                label=[mx.nd.array(
                    (rng.rand(batch) * 1000).astype(np.float32),
                    ctx=ctx)])
            for _ in range(bulk)]

        def step():
            if bulk > 1:
                mod.bulk_step(batches=batches, scan_dtype=scan_dtype)
            else:
                mod.forward_backward(batches[0])
                mod.update()

    def block():
        # every weight the last dispatch wrote
        ex = mod._exec_group.executor
        jax.block_until_ready([ex.arg_dict[n]._data
                               for n in ex._diff_names])

    # cold start: bind -> first completed training dispatch (includes
    # trace + XLA compile; with the persistent cache warm, the compile
    # is fetched from disk and this shrinks — that delta IS warm start)
    try:
        tic = time.time()
        mod.bind(data_shapes=[mx.io.DataDesc('data',
                                             (batch, 3, edge, edge))],
                 label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
        mod.init_params(initializer=mx.init.Xavier(rnd_type='gaussian',
                                                   factor_type='in',
                                                   magnitude=2))
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.1,
                                             'momentum': 0.9, 'wd': 1e-4,
                                             'multi_precision':
                                                 dtype != 'float32'})
        step()
        block()
        cold_start_s = time.time() - tic

        for _ in range(max(0, warmup - 1)):
            step()
        block()
        if prefetch is not None:   # count stall over the measured loop only
            prefetch.input_stall_ms = 0.0
            prefetch.batches_served = 0
        tic = time.time()
        for _ in range(steps):
            step()
        block()
        dt = time.time() - tic
        fu = getattr(mod, '_fused_updater', None)
        return {
            'ips': batch * bulk * steps / dt,
            'platform': device.platform,
            'device_kind': device.device_kind,
            'n_devices': len(jax.devices()),
            'cold_start_s': round(cold_start_s, 3),
            'input_stall_ms_per_step': round(
                prefetch.stall_ms_per_batch(), 3) if prefetch is not None
            else 0.0,
            'decode_workers': decode_workers,
            # ZeRO-1 memory trajectory: momenta + fp32 masters resident
            # per device (drops ~dp-fold under MXNET_TPU_ZERO=1)
            'optimizer_state_bytes_per_device':
                int(fu.state_bytes_per_device()) if fu is not None
                else None,
            'zero': int(getattr(fu, 'zero', 0)) if fu is not None else 0,
        }
    finally:
        if cleanup is not None:
            cleanup()


def run(batch, steps, warmup, bulk, num_layers=50, dtype='float32'):
    return run_symbol(make_symbol('resnet-%d' % num_layers, dtype),
                      batch, steps, warmup, bulk, dtype)['ips']


# ---------------------------------------------------------------------------
# BENCH_GLUON=1: fused whole-step Gluon training vs the imperative loop
# ---------------------------------------------------------------------------

def gluon_bench():
    """BENCH_GLUON=1: measure the fused Gluon training step
    (gluon/fused.py: forward+loss+backward+update as ONE donated XLA
    dispatch) against the imperative early-Gluon loop (per-tape-node
    autograd.backward + Trainer.step) on the same MLP workload, and
    emit ONE JSON line with steps/s for three arms — imperative,
    fused, fused-bulk (lax.scan, BENCH_GLUON_BULK steps/dispatch) —
    plus total_compile_s, the gluon_fused_* counters, and a parity
    check (both arms trained from identical init; the gate reflects
    the float32-ulp agreement of the two program partitions).

    Round 11 adds two metric arms: `metric_scan` (accuracy folded
    INTO the bulk lax.scan — device-resident carry, one queued delta
    pair per dispatch, no host sync) vs `metric_host` (per-step fused
    dispatch + eager metric forward + host update — the pre-round-11
    way to see per-batch train accuracy, which breaks the bulk at
    every metric boundary).  Their ratio is the epoch-fusion win;
    the JSON also carries scan_fused_metric_steps.

    Arms run best-of-BENCH_GLUON_PASSES interleaved (the rig's
    cpu-shares throttle swings single passes ~2x).  Knobs:
    BENCH_GLUON_BATCH (64), BENCH_GLUON_DIM (64), BENCH_GLUON_HIDDEN
    (128), BENCH_GLUON_LAYERS (4), BENCH_GLUON_STEPS (20 per pass),
    BENCH_GLUON_PASSES (5), BENCH_GLUON_BULK (8),
    BENCH_GLUON_HYBRID=1 (hybridize the imperative arm: forward
    becomes one CachedOp jit, backward one whole-graph vjp — isolates
    the Trainer.step + per-step dispatch overhead the fusion removes)."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd, profiler
    from mxnet_tpu.gluon import nn

    batch = int(os.environ.get('BENCH_GLUON_BATCH', 64))
    dim = int(os.environ.get('BENCH_GLUON_DIM', 64))
    hidden = int(os.environ.get('BENCH_GLUON_HIDDEN', 128))
    layers = int(os.environ.get('BENCH_GLUON_LAYERS', 4))
    steps = int(os.environ.get('BENCH_GLUON_STEPS', 20))
    passes = max(1, int(os.environ.get('BENCH_GLUON_PASSES', 5)))
    bulk = int(os.environ.get('BENCH_GLUON_BULK', 8))
    hybrid = os.environ.get('BENCH_GLUON_HYBRID', '0') == '1'
    classes = 10
    opt_params = {'learning_rate': 0.05, 'momentum': 0.9, 'wd': 1e-4}

    def make_net(seed):
        net = nn.HybridSequential()
        with net.name_scope():
            for _ in range(layers):
                net.add(nn.Dense(hidden, activation='relu'))
            net.add(nn.Dense(classes))
        net.initialize()
        net(mx.nd.zeros((batch, dim)))   # complete deferred shapes
        rs = np.random.RandomState(seed)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                (rs.rand(*p.shape).astype(np.float32) - 0.5) * 0.2))
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.rand(batch, dim).astype(np.float32))
    y = mx.nd.array((rs.rand(batch) * classes).astype(np.float32))
    xs = mx.nd.NDArray(jnp.stack([x._data] * bulk))
    ys = mx.nd.NDArray(jnp.stack([y._data] * bulk))

    # -- arms (shared nets/trainers; measurement loops below) ----------
    net_i = make_net(1)
    if hybrid:
        net_i.hybridize()
    tr_i = gluon.Trainer(net_i.collect_params(), 'sgd', dict(opt_params))

    def imperative_steps(n):
        for _ in range(n):
            with autograd.record():
                l = loss_fn(net_i(x), y)
            l.backward()
            tr_i.step(batch)
        l.asnumpy()          # host-fetch barrier

    net_f = make_net(1)
    tr_f = gluon.Trainer(net_f.collect_params(), 'sgd', dict(opt_params))
    fused = gluon.fuse_step(net_f, loss_fn, tr_f)

    def fused_steps(n):
        for _ in range(n):
            l = fused(x, y)
        l.asnumpy()

    def bulk_steps(n):
        for _ in range(max(1, n // bulk)):
            l = fused.bulk(xs, ys)
        l.asnumpy()

    # scan-fused-metrics arm (round 11): accuracy accumulates INSIDE
    # the bulk scan (device-resident carry, deltas queued without a
    # sync) vs the pre-round-11 way to get per-batch train accuracy —
    # a per-step fused dispatch plus an eager metric forward + host
    # update, which breaks the bulk at every metric boundary
    from mxnet_tpu import metric as metric_mod
    acc_scan = metric_mod.Accuracy()
    net_m = make_net(1)
    tr_m = gluon.Trainer(net_m.collect_params(), 'sgd', dict(opt_params))
    fused_m = gluon.fuse_step(net_m, loss_fn, tr_m, metric=acc_scan)
    acc_host = metric_mod.Accuracy()
    net_h = make_net(1)
    tr_h = gluon.Trainer(net_h.collect_params(), 'sgd', dict(opt_params))
    fused_h = gluon.fuse_step(net_h, loss_fn, tr_h)

    def metric_scan_steps(n):
        for _ in range(max(1, n // bulk)):
            l = fused_m.bulk(xs, ys)
        l.asnumpy()

    def metric_host_steps(n):
        for _ in range(n):
            l = fused_h(x, y)
            acc_host.update([y], [net_h(x)])
        l.asnumpy()

    # warmup (compiles) outside the clock
    imperative_steps(2)
    fused_steps(2)
    bulk_steps(bulk)
    metric_scan_steps(bulk)
    metric_host_steps(2)

    best = {'imperative': 0.0, 'fused': 0.0, 'bulk': 0.0,
            'metric_scan': 0.0, 'metric_host': 0.0}
    for _ in range(passes):
        for name, fn, n in (('imperative', imperative_steps, steps),
                            ('fused', fused_steps, steps),
                            ('bulk', bulk_steps,
                             max(bulk, (steps // bulk) * bulk)),
                            ('metric_scan', metric_scan_steps,
                             max(bulk, (steps // bulk) * bulk)),
                            ('metric_host', metric_host_steps, steps)):
            tic = time.time()
            fn(n)
            sps = n / (time.time() - tic)
            best[name] = max(best[name], sps)
    assert 0.0 <= acc_scan.get()[1] <= 1.0   # deltas drained cleanly

    # parity from identical init (fresh nets: the measured ones drifted
    # apart over different step counts)
    net_pi = make_net(7)
    tr_pi = gluon.Trainer(net_pi.collect_params(), 'sgd',
                          dict(opt_params))
    net_pf = make_net(7)
    tr_pf = gluon.Trainer(net_pf.collect_params(), 'sgd',
                          dict(opt_params))
    pf = gluon.fuse_step(net_pf, loss_fn, tr_pf)
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net_pi(x), y)
        l.backward()
        tr_pi.step(batch)
        pf(x, y)
    max_diff = max(
        float(np.abs(a.list_data()[0].asnumpy() -
                     b.list_data()[0].asnumpy()).max())
        for (_, a), (_, b) in zip(
            sorted(net_pi.collect_params().items()),
            sorted(net_pf.collect_params().items())))

    gf = profiler.gluon_fused_stats()
    cache = profiler.exec_cache_stats()
    print(json.dumps({
        'metric': 'gluon_fused_train',
        'value': round(best['fused'], 2),
        'unit': 'steps/sec',
        'imperative_sps': round(best['imperative'], 2),
        'bulk_sps': round(best['bulk'], 2),
        'speedup_vs_imperative': round(
            best['fused'] / best['imperative'], 3),
        'speedup_bulk_vs_imperative': round(
            best['bulk'] / best['imperative'], 3),
        'metric_scan_sps': round(best['metric_scan'], 2),
        'metric_host_sps': round(best['metric_host'], 2),
        'speedup_metric_scan_vs_host': round(
            best['metric_scan'] / max(best['metric_host'], 1e-9), 3),
        'scan_fused_metric_steps':
            profiler.comm_stats()['scan_fused_metric_steps'],
        'batch': batch, 'dim': dim, 'hidden': hidden, 'layers': layers,
        'steps_per_pass': steps, 'passes': passes, 'bulk': bulk,
        'imperative_hybridized': hybrid,
        'gluon_fused_steps': gf['gluon_fused_steps'],
        'gluon_fused_dispatches': gf['gluon_fused_dispatches'],
        'total_compile_s': round(cache['total_compile_s'], 3),
        'exec_cache_misses': cache['exec_cache_misses'],
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff < 1e-5),
    }))


# ---------------------------------------------------------------------------
# BENCH_PIPE=1: dp-only vs dp×pipe vs dp×pipe+ZeRO (GPipe fill-drain)
# ---------------------------------------------------------------------------

def pipe_bench():
    """BENCH_PIPE=1: measure the dp×pipe GPipe training mode (round
    16) in three arms on one device set and emit ONE JSON line:

      * dp    — plain data parallelism over all BENCH_PIPE_DEVICES
        devices (every device holds every weight + momentum).
      * pipe  — the same net through fuse_step(pipeline=(S, M)): 2D
        {data: dp, pipe: S} mesh, stage weights stacked P('pipe')
        (each device holds ~1/S of the stage-body weights), GPipe
        fill-drain over M microbatches inside the same single donated
        dispatch.
      * pipe+zero — plus ZeRO-1: momentum buckets sharded over the dp
        axis on top of the stage split (per-device optimizer state
        ~1/(dp·S) of the replicated baseline).

    All arms train the SAME weights on the SAME batches; a parity
    gate asserts the final parameters agree (the schedule reorders
    float sums — tolerance 1e-5).  The JSON reports best-of-
    BENCH_PIPE_PASSES steps/s per arm (this rig's cpu-shares throttle
    swings single passes ~2x) plus the measured per-device
    param/optimizer-state bytes per arm and the analytic bubble
    fraction (S-1)/(M+S-1).  NOTE on reading CPU numbers: virtual
    host devices share the same cores, so the pipeline cannot
    shorten wall-clock the way real per-stage chips do — treat the
    arm as a schedule-correctness + residency smoke; the speedup
    story needs real chips.

    Needs >= BENCH_PIPE_DEVICES devices: when the process has fewer
    (no TPU pod on this rig), re-execs itself on a virtual CPU mesh
    (same technique as dryrun_multichip).

    Knobs: BENCH_PIPE_DEVICES (8), BENCH_PIPE_STAGES (2),
    BENCH_PIPE_MICRO (4), BENCH_PIPE_BATCH (64), BENCH_PIPE_DIM (32),
    BENCH_PIPE_UNITS (64), BENCH_PIPE_BODY (4 — body layers, must
    divide by stages), BENCH_PIPE_STEPS (16 per pass),
    BENCH_PIPE_PASSES (5)."""
    ndev = int(os.environ.get('BENCH_PIPE_DEVICES', 8))
    import jax
    try:
        have = jax.device_count()
    except Exception:
        have = 0
    if have < ndev:
        if os.environ.get('BENCH_PIPE_SPAWNED') == '1':
            raise RuntimeError('spawned pipe bench still has %d < %d '
                               'devices' % (have, ndev))
        env = dict(os.environ, BENCH_PIPE='1', BENCH_PIPE_SPAWNED='1',
                   JAX_PLATFORMS='cpu')
        flags = [f for f in env.get('XLA_FLAGS', '').split()
                 if 'xla_force_host_platform_device_count' not in f]
        flags.append('--xla_force_host_platform_device_count=%d'
                     % ndev)
        env['XLA_FLAGS'] = ' '.join(flags)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('pipe bench child failed (rc=%d)'
                               % proc.returncode)
        lines = proc.stdout.strip().splitlines()
        if not lines:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('pipe bench child produced no output')
        print(lines[-1], flush=True)
        return

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.gluon import nn

    stages = int(os.environ.get('BENCH_PIPE_STAGES', 2))
    micro = int(os.environ.get('BENCH_PIPE_MICRO', 4))
    batch = int(os.environ.get('BENCH_PIPE_BATCH', 64))
    dim = int(os.environ.get('BENCH_PIPE_DIM', 32))
    units = int(os.environ.get('BENCH_PIPE_UNITS', 64))
    body = int(os.environ.get('BENCH_PIPE_BODY', 4))
    steps = int(os.environ.get('BENCH_PIPE_STEPS', 16))
    passes = max(1, int(os.environ.get('BENCH_PIPE_PASSES', 5)))
    classes = 10
    ctxs = [mx.cpu(i) for i in range(ndev)]
    opt_params = {'learning_rate': 0.05, 'momentum': 0.9, 'wd': 1e-4}
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.rand(batch, dim).astype(np.float32))
    y = mx.nd.array((rs.rand(batch) * classes).astype(np.float32))

    def make_arm(pipeline=None, zero=None):
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(units, activation='relu', in_units=dim))
            for _ in range(body):
                net.add(nn.Dense(units, activation='tanh',
                                 in_units=units))
            net.add(nn.Dense(classes, in_units=units))
        net.initialize(ctx=ctxs)
        prs = np.random.RandomState(7)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                (prs.rand(*p.shape).astype(np.float32) - 0.5) * 0.2))
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           dict(opt_params))
        return net, gluon.fuse_step(net, loss_fn, tr,
                                    pipeline=pipeline, zero=zero)

    arms = {
        'dp': make_arm(),
        'pipe': make_arm(pipeline=(stages, micro)),
        'pipe_zero': make_arm(pipeline=(stages, micro), zero=1),
    }

    def run_steps(fs, n):
        for _ in range(n):
            l = fs(x, y)
        l.asnumpy()

    for _, fs in arms.values():
        run_steps(fs, 2)
    best = {name: 0.0 for name in arms}
    profiler.clear()
    profiler.profiler_set_state('run')
    try:
        for _ in range(passes):
            for name, (_, fs) in arms.items():
                tic = time.time()
                run_steps(fs, steps)
                best[name] = max(best[name],
                                 steps / (time.time() - tic))
    finally:
        profiler.profiler_set_state('stop')

    # parity: same seeds + same batches on every arm
    def pvals(net):
        return [p.list_data()[0].asnumpy()
                for _, p in sorted(net.collect_params().items())]

    ref = pvals(arms['dp'][0])
    max_diff = max(
        float(np.abs(a - b).max())
        for name in ('pipe', 'pipe_zero')
        for a, b in zip(ref, pvals(arms[name][0])))

    # per-device residency: the dp arm replicates everything; the
    # pipe arms report the engine's own accounting
    dp_param_b = sum(
        int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
        for _, p in sorted(arms['dp'][0].collect_params().items()))
    pipe_param_b, pipe_state_b = \
        arms['pipe'][1]._pipe_state_accounting()
    _, zero_state_b = arms['pipe_zero'][1]._pipe_state_accounting()
    pi = profiler.pipe_stats()
    print(json.dumps({
        'metric': 'pipe_train',
        'value': round(best['pipe'], 2),
        'unit': 'steps/sec',
        'dp_sps': round(best['dp'], 2),
        'pipe_zero_sps': round(best['pipe_zero'], 2),
        'devices': ndev, 'stages': stages, 'num_micro': micro,
        'dp_width': ndev // stages,
        'batch': batch, 'dim': dim, 'units': units,
        'body_layers': body,
        'bubble_frac': round(pi['pipe_bubble_frac'], 4),
        'dp_param_bytes_per_device': dp_param_b,
        'dp_state_bytes_per_device': dp_param_b,
        'pipe_param_bytes_per_device': pipe_param_b,
        'pipe_state_bytes_per_device': pipe_state_b,
        'pipe_zero_state_bytes_per_device': zero_state_b,
        'pipe_microbatches': pi['pipe_microbatches'],
        'steps_per_pass': steps, 'passes': passes,
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff < 1e-5),
    }))


# ---------------------------------------------------------------------------
# BENCH_CKPT=1: async elastic checkpoint overhead vs no-checkpoint
# ---------------------------------------------------------------------------

def ckpt_bench():
    """BENCH_CKPT=1: measure the step-time overhead of the elastic
    checkpoint cadence (mxnet_tpu/elastic.py CheckpointManager:
    device-side async snapshot on the train thread, materialize+write
    on a background thread) against the identical training loop with
    no checkpointing, and emit ONE JSON line with steps/s for three
    arms — nockpt, ckpt (async, every BENCH_CKPT_EVERY steps), and
    ckpt_sync (the legacy blocking save at the same cadence, the
    contrast that shows what async buys) — plus the ckpt_* counters
    (ckpt_async_overlap_ms > 0 proves the host materialize+write ran
    concurrent with training steps) and a bit-parity gate
    (checkpointing must not perturb training).

    The async arm's pass time INCLUDES the end-of-pass writer drain
    (conservative: on this rig the writer contends for the same
    cores).  Arms run best-of-BENCH_CKPT_PASSES interleaved (rig
    note: single passes swing ~2x).  Knobs: BENCH_CKPT_BATCH (512 —
    compute scales with batch while snapshot bytes don't, which is
    what makes the smoke's overhead honest), BENCH_CKPT_DIM (128),
    BENCH_CKPT_HIDDEN (512), BENCH_CKPT_LAYERS (4), BENCH_CKPT_STEPS
    (80 per pass), BENCH_CKPT_EVERY (40), BENCH_CKPT_PASSES (5)."""
    import shutil

    import mxnet_tpu as mx
    from mxnet_tpu import elastic, profiler
    from mxnet_tpu import sym as S

    batch = int(os.environ.get('BENCH_CKPT_BATCH', 512))
    dim = int(os.environ.get('BENCH_CKPT_DIM', 128))
    hidden = int(os.environ.get('BENCH_CKPT_HIDDEN', 512))
    layers = int(os.environ.get('BENCH_CKPT_LAYERS', 4))
    steps = int(os.environ.get('BENCH_CKPT_STEPS', 80))
    every = int(os.environ.get('BENCH_CKPT_EVERY', 40))
    passes = max(1, int(os.environ.get('BENCH_CKPT_PASSES', 5)))
    classes = 10

    def make_module(seed):
        x = S.Variable('data')
        for i in range(layers):
            x = S.Activation(S.FullyConnected(
                x, name='fc%d' % i, num_hidden=hidden),
                act_type='relu')
        net = S.SoftmaxOutput(S.FullyConnected(
            x, name='out', num_hidden=classes), name='softmax')
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[mx.io.DataDesc('data', (batch, dim))],
                 label_shapes=[mx.io.DataDesc('softmax_label',
                                              (batch,))])
        mx.random.seed(seed)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.05,
                                             'momentum': 0.9})
        return mod

    rs = np.random.RandomState(0)
    b = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(batch, dim).astype(np.float32))],
        label=[mx.nd.array((rs.rand(batch) * classes)
                           .astype(np.float32))])

    def run_steps(mod, n, mgr=None):
        for s in range(n):
            mod.forward_backward(b)
            mod.update()
            if mgr is not None:
                mgr.step_end(epoch=0, batches_in_epoch=s + 1,
                             batch_size=batch)
        mod.get_params()        # host-fetch barrier

    mod_plain = make_module(1)
    mod_async = make_module(1)
    mod_sync = make_module(1)
    ckdirs = {'async': tempfile.mkdtemp(prefix='bench_ckpt_a_'),
              'sync': tempfile.mkdtemp(prefix='bench_ckpt_s_')}
    mgr_async = elastic.CheckpointManager(ckdirs['async'],
                                          every_n_steps=every, keep=2)
    mgr_async.attach(mod_async)
    mgr_sync = elastic.CheckpointManager(ckdirs['sync'],
                                         every_n_steps=every, keep=2,
                                         async_=False)
    mgr_sync.attach(mod_sync)

    # warmup (compiles + first-snapshot copy programs) off the clock —
    # the SAME step count for every arm, so the parity gate below
    # compares identically-trained weights
    run_steps(mod_plain, every)
    run_steps(mod_async, every, mgr_async)
    mgr_async.wait()
    run_steps(mod_sync, every, mgr_sync)

    profiler.clear()
    best = {'nockpt': 0.0, 'ckpt': 0.0, 'ckpt_sync': 0.0}
    # ckpt_* counters are process-global and the sync arm feeds them
    # too — report the ASYNC arm's deltas only, so the JSON counters
    # describe the cadence being measured
    async_acc = {k: type(v)() for k, v in profiler.ckpt_stats().items()}

    def timed_async(n):
        before = profiler.ckpt_stats()
        tic = time.time()
        run_steps(mod_async, n, mgr_async)
        mgr_async.wait()      # drain inside the clock (conservative)
        dt = time.time() - tic
        after = profiler.ckpt_stats()
        for k in async_acc:
            async_acc[k] += after[k] - before[k]
        return n / dt

    for _ in range(passes):
        tic = time.time()
        run_steps(mod_plain, steps)
        best['nockpt'] = max(best['nockpt'],
                             steps / (time.time() - tic))
        best['ckpt'] = max(best['ckpt'], timed_async(steps))
        tic = time.time()
        run_steps(mod_sync, steps, mgr_sync)
        best['ckpt_sync'] = max(best['ckpt_sync'],
                                steps / (time.time() - tic))

    # parity gate: the checkpointing arm trained the same number of
    # steps from the same init — snapshots must not perturb training
    pa, _ = mod_plain.get_params()
    pb, _ = mod_async.get_params()
    max_diff = max(float(np.abs(pa[n].asnumpy() -
                                pb[n].asnumpy()).max()) for n in pa)

    mgr_async.close()
    mgr_sync.close()
    st = async_acc          # async-arm deltas only (see above)
    overhead = 1.0 - best['ckpt'] / max(best['nockpt'], 1e-9)
    print(json.dumps({
        'metric': 'elastic_ckpt_train',
        'value': round(best['ckpt'], 2),
        'unit': 'steps/sec',
        'nockpt_sps': round(best['nockpt'], 2),
        'ckpt_sync_sps': round(best['ckpt_sync'], 2),
        'ckpt_overhead_frac': round(overhead, 4),
        'ckpt_every': every,
        'ckpt_snapshots': st['ckpt_snapshots'],
        'ckpt_bytes': st['ckpt_bytes'],
        'ckpt_async_overlap_ms': round(st['ckpt_async_overlap_ms'], 3),
        'ckpt_commit_ms': round(st['ckpt_commit_ms'], 3),
        'ckpt_skipped': st['ckpt_skipped'],
        'batch': batch, 'dim': dim, 'hidden': hidden, 'layers': layers,
        'steps_per_pass': steps, 'passes': passes,
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff == 0.0),
    }))
    for d in ckdirs.values():
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# BENCH_DELTA=1: incremental delta checkpoints + weight-delta push channel
# ---------------------------------------------------------------------------

def delta_bench():
    """BENCH_DELTA=1: measure the weight-delta channel (mxnet_tpu/
    delta.py, PERF round 22) on the workload it exists for — an
    embedding-dominated model where each step touches a few hundred
    table rows out of tens of thousands.  Two arms, ONE JSON line:

    * ckpt arm: twin modules train on the SAME batches, one under a
      full-every-commit CheckpointManager, one under
      CheckpointManager(incremental=K) (K touched-rows deltas between
      full bases).  Headline = full-arm commit bytes / incremental-arm
      commit bytes (acceptance wants >= 5x).  A resume gate then
      replays the newest delta CHAIN (load_newest_intact: base + K
      deltas) and requires the restored params bitwise-equal to the
      live module.
    * push/engine arm: the newest DELTA commit exports through
      export_serving_checkpoint (chain replay inside the export path),
      boots an InferenceEngine, then (1) a sparse touched-rows delta
      applies at zero re-warm compiles with outputs bitwise-identical
      to a full reload of the new state, and (2) a dense int8 delta
      built from RANDOM perturbations: a tight parity_tol draws a
      typed DeltaParityError with NOTHING mutated (outputs bit-equal
      before/after the refusal), the default tol applies and reports
      the measured rel_err.

    Plain SGD (momentum=0, wd=0) keeps untouched rows bit-identical
    between steps — the property the touched-rows encoder keys on;
    momentum or weight decay would smear every row every step and the
    honest answer there is the full base (the encoder falls back on
    its own via the sparse_frac cutoff).  Both managers run
    async_=False so the two arms commit at every step
    deterministically (no in-flight skips).  Knobs: BENCH_DELTA_VOCAB
    (20000), BENCH_DELTA_DIM (64), BENCH_DELTA_BATCH (256),
    BENCH_DELTA_HOT (512 — ids draw from a hot pool this big),
    BENCH_DELTA_STEPS (14, one commit per step), BENCH_DELTA_INCR
    (6 -> chain full,d1..d6,full,d1..)."""
    import shutil

    import mxnet_tpu as mx
    from mxnet_tpu import delta as delta_mod
    from mxnet_tpu import elastic, profiler
    from mxnet_tpu import sym as S
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import InferenceEngine, \
        export_serving_checkpoint

    vocab = int(os.environ.get('BENCH_DELTA_VOCAB', 20000))
    dim = int(os.environ.get('BENCH_DELTA_DIM', 64))
    batch = int(os.environ.get('BENCH_DELTA_BATCH', 256))
    hot = int(os.environ.get('BENCH_DELTA_HOT', 512))
    steps = int(os.environ.get('BENCH_DELTA_STEPS', 14))
    incr = int(os.environ.get('BENCH_DELTA_INCR', 6))
    classes = 10

    def head_sym():
        ids = S.Variable('data')
        emb = S.Embedding(ids, input_dim=vocab, output_dim=dim,
                          name='emb')
        return S.FullyConnected(emb, name='out', num_hidden=classes)

    def make_module(seed):
        net = S.SoftmaxOutput(head_sym(), name='softmax')
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[mx.io.DataDesc('data', (batch,))],
                 label_shapes=[mx.io.DataDesc('softmax_label',
                                              (batch,))])
        mx.random.seed(seed)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.1,
                                             'momentum': 0.0,
                                             'wd': 0.0})
        return mod

    rs = np.random.RandomState(0)
    pool = rs.choice(vocab, size=hot, replace=False)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(pool[rs.randint(0, hot, size=batch)]
                          .astype(np.float32))],
        label=[mx.nd.array((rs.rand(batch) * classes)
                           .astype(np.float32))])
        for _ in range(steps)]

    def run_arm(mod, mgr):
        before = profiler.ckpt_stats()['ckpt_bytes']
        tic = time.time()
        for s, b in enumerate(batches):
            mod.forward_backward(b)
            mod.update()
            mgr.step_end(epoch=0, batches_in_epoch=s + 1,
                         batch_size=batch)
        mod.get_params()        # host-fetch barrier
        dt = time.time() - tic
        return profiler.ckpt_stats()['ckpt_bytes'] - before, dt

    profiler.clear()
    mod_full = make_module(1)
    mod_incr = make_module(1)
    dirs = {'full': tempfile.mkdtemp(prefix='bench_delta_f_'),
            'incr': tempfile.mkdtemp(prefix='bench_delta_i_'),
            'push': tempfile.mkdtemp(prefix='bench_delta_p_')}
    mgr_full = elastic.CheckpointManager(dirs['full'],
                                         every_n_steps=1,
                                         async_=False)
    mgr_full.attach(mod_full)
    mgr_incr = elastic.CheckpointManager(dirs['incr'],
                                         every_n_steps=1,
                                         async_=False,
                                         incremental=incr)
    mgr_incr.attach(mod_incr)

    bytes_full, dt_full = run_arm(mod_full, mgr_full)
    d0 = profiler.delta_stats()
    bytes_incr, dt_incr = run_arm(mod_incr, mgr_incr)
    d1 = profiler.delta_stats()
    ratio = bytes_full / max(1.0, float(bytes_incr))

    # resume gate: the newest commit must be a DELTA (the chain tail),
    # and replaying base + chain must land bitwise on the live params
    res = elastic.load_newest_intact(dirs['incr'])
    assert res is not None, 'incremental arm left no intact checkpoint'
    _man, arrays, tail_dir = res
    from_delta = os.path.basename(tail_dir).startswith('delta-')
    pa, _ = mod_incr.get_params()
    resume_ok = all(np.array_equal(arrays['param:%s' % n],
                                   pa[n].asnumpy()) for n in pa)

    # --- push/engine arm: export FROM the delta commit, then apply
    # live deltas to the resident engine ---
    prefix = os.path.join(dirs['push'], 'serve')
    export_serving_checkpoint(tail_dir, head_sym(), prefix)
    full_params_bytes = os.path.getsize(prefix + '-0000.params')
    eng = InferenceEngine(
        Predictor.from_checkpoint(prefix, 0, {'data': (4,)}),
        max_batch=4, max_wait_us=0)
    x = pool[:4].astype(np.float32)

    def ref_out(state):
        args = {k[4:]: mx.nd.array(v) for k, v in state.items()
                if k.startswith('arg:')}
        auxs = {k[4:]: mx.nd.array(v) for k, v in state.items()
                if k.startswith('aux:')}
        ref = Predictor(symbol=head_sym(), arg_params=args,
                        aux_params=auxs, input_shapes={'data': (4,)})
        return ref.forward(data=mx.nd.array(x))[0].asnumpy()

    # (1) sparse touched-rows delta -> bitwise parity vs full reload
    rs2 = np.random.RandomState(1)
    state = eng._resident_host_state()
    new_state = dict(state)
    tbl = state['arg:emb_weight'].copy()
    rows = rs2.choice(vocab, size=64, replace=False)
    tbl[rows] += (rs2.randn(64, dim) * 0.05).astype(tbl.dtype)
    new_state['arg:emb_weight'] = tbl
    ent, meta, _ = delta_mod.make_delta(
        state, new_state, seq=1,
        base_fp=delta_mod.fingerprint(state),
        config=delta_mod.DeltaConfig(dense='raw'))
    eng.apply_delta(dict(ent), meta,
                    expect_fp=delta_mod.fingerprint(state))
    sparse_ok = np.array_equal(np.asarray(eng.predict(x)),
                               ref_out(new_state))

    # (2) dense int8 delta: tight tol -> typed refusal, nothing
    # mutated; default tol -> applies, rel_err measured
    base2 = eng._resident_host_state()
    new2 = dict(base2)
    w = base2['arg:out_weight'].copy()
    w += (rs2.randn(*w.shape) * 0.05).astype(w.dtype)
    new2['arg:out_weight'] = w
    ent2, meta2, _ = delta_mod.make_delta(
        base2, new2, seq=1,
        base_fp=delta_mod.fingerprint(base2),
        config=delta_mod.DeltaConfig(dense='int8', min_dense=1))
    before = np.asarray(eng.predict(x)).copy()
    refused = False
    try:
        eng.apply_delta(dict(ent2), meta2,
                        expect_fp=delta_mod.fingerprint(base2),
                        parity_tol=1e-12)
    except delta_mod.DeltaParityError:
        refused = True
    untouched = np.array_equal(np.asarray(eng.predict(x)), before)
    eng.apply_delta(dict(ent2), meta2,
                    expect_fp=delta_mod.fingerprint(base2))
    int8_moved = not np.array_equal(np.asarray(eng.predict(x)), before)

    mgr_full.close()
    mgr_incr.close()
    print(json.dumps({
        'metric': 'delta_channel',
        'value': round(ratio, 2),
        'unit': 'x_fewer_commit_bytes',
        'ratio_ok': bool(ratio >= 5.0),
        'full_commit_bytes': int(bytes_full),
        'incr_commit_bytes': int(bytes_incr),
        'commits_per_arm': steps, 'incremental': incr,
        'delta_commits': int(d1['delta_committed'] -
                             d0['delta_committed']),
        'delta_fallback_rebases': int(d1['delta_rebases'] -
                                      d0['delta_rebases']),
        'full_arm_s': round(dt_full, 2),
        'incr_arm_s': round(dt_incr, 2),
        'resume_from_delta_chain': bool(from_delta),
        'resume_parity_ok': bool(resume_ok),
        'push_sparse_wire_bytes': int(meta['bytes']),
        'push_full_params_bytes': int(full_params_bytes),
        'push_sparse_ratio': round(full_params_bytes /
                                   max(1.0, float(meta['bytes'])), 2),
        'push_sparse_bitwise_ok': bool(sparse_ok),
        'push_int8_wire_bytes': int(meta2['bytes']),
        'push_int8_rel_err': round(float(meta2['rel_err']), 6),
        'push_int8_tight_tol_refused': bool(refused),
        'push_int8_refusal_left_engine_untouched': bool(untouched),
        'push_int8_applied': bool(int8_moved),
        'vocab': vocab, 'dim': dim, 'batch': batch, 'hot': hot,
    }))
    for d in dirs.values():
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# BENCH_EMBED=1: dense vs sparse (touched-rows-only) embedding training
# ---------------------------------------------------------------------------

def embed_bench():
    """BENCH_EMBED=1: measure the sparse embedding-gradient path
    (parallel/embedding.py: dedup'd touched-rows-only backward +
    rows-only FusedSGD update inside the single donated gluon fused
    dispatch) against the identical model trained dense
    (sparse_grad=False: full (vocab, dim) gradient + full-table
    update), and emit ONE JSON line with per-distribution arms —
    uniform (ids ~ U[0, vocab)), zipf (heavy head, the
    recommendation-workload shape), repeat (a hot pool of
    BENCH_EMBED_HOT ids — the steady-feature case) — each carrying
    dense/sparse steps/s, the speedup, the sparse arm's
    touched-bytes/step vs the dense-equivalent bytes from the
    profiler's embed_* plan accounting, and the max ladder rung in
    effect.

    Two gates ride along: a parity gate (fresh dense + sparse nets
    from identical init, plain SGD wd=0 — the rows-only update must be
    BITWISE equal to dense; lazy momentum/wd are documented
    divergences so the gate pins them to zero) and a zero-recompile
    gate (exec_cache misses + total_compile_s deltas across every
    measured pass must be ZERO once the warmup has visited each
    distribution's ladder rungs — re-bucketing between distributions
    is a cache hit, not a compile).  A 2x-virtual-device child
    (BENCH_EMBED_DRYRUN=1 re-exec with
    --xla_force_host_platform_device_count=2) reports the sparse
    table's addressable-shard bytes: per-device ~ 1/dp of the table
    proves the rows really stripe over the dp mesh axis.

    Arms run best-of-BENCH_EMBED_PASSES interleaved (rig note: single
    passes swing ~2x).  Knobs: BENCH_EMBED_VOCAB (100000),
    BENCH_EMBED_DIM (64), BENCH_EMBED_BATCH (512), BENCH_EMBED_HOT
    (256), BENCH_EMBED_STEPS (10 per pass), BENCH_EMBED_PASSES (4),
    BENCH_EMBED_SHARD_DEVICES (2; 0 skips the child)."""
    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache, gluon, nd, profiler
    from mxnet_tpu.gluon import nn

    vocab = int(os.environ.get('BENCH_EMBED_VOCAB', 100000))
    dim = int(os.environ.get('BENCH_EMBED_DIM', 64))
    batch = int(os.environ.get('BENCH_EMBED_BATCH', 512))
    hot = int(os.environ.get('BENCH_EMBED_HOT', 256))
    steps = int(os.environ.get('BENCH_EMBED_STEPS', 10))
    passes = max(1, int(os.environ.get('BENCH_EMBED_PASSES', 4)))
    shard_dev = int(os.environ.get('BENCH_EMBED_SHARD_DEVICES', 2))

    def make_net(sparse, seed=3, ctxs=None):
        net = nn.HybridSequential()
        net.add(nn.Embedding(vocab, dim, sparse_grad=sparse))
        net.add(nn.Dense(16, flatten=False, in_units=dim))
        net.initialize(force_reinit=True, ctx=ctxs)
        rs = np.random.RandomState(seed)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(nd.array(
                (rs.rand(*p.shape).astype(np.float32) - 0.5) * 0.1))
        return net

    def make_fused(sparse, seed=3, ctxs=None):
        net = make_net(sparse, seed, ctxs)
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           {'learning_rate': 0.1, 'wd': 0.0})
        return net, gluon.fuse_step(
            net, gluon.loss.L2Loss(), tr), tr

    if os.environ.get('BENCH_EMBED_DRYRUN') == '1':
        # 2x-virtual-device child: train a few sparse steps on the dp
        # mesh and report the table's real per-device shard bytes
        import jax
        ndev = jax.device_count()
        ctxs = [mx.cpu(i) for i in range(ndev)]
        net, fused, tr = make_fused(True, ctxs=ctxs)
        rs = np.random.RandomState(0)
        for _ in range(3):
            x = nd.array(rs.randint(0, vocab, size=(batch,))
                         .astype(np.float32))
            y = nd.array(rs.randn(batch, 16).astype(np.float32))
            fused(x, y).asnumpy()
        p = next(p for p in tr._params
                 if getattr(p, 'sparse_grad', False))
        ent = fused._repl.get(id(p))
        arr = ent[0] if ent else p.list_data()[0]._data
        total = int(np.prod(arr.shape)) * arr.dtype.itemsize
        per_dev = max(int(np.prod(s.data.shape)) * arr.dtype.itemsize
                      for s in arr.addressable_shards)
        print(json.dumps({
            'devices': ndev, 'table_bytes': total,
            'per_device_bytes': per_dev,
            'per_device_frac': round(per_dev / total, 4)}))
        return

    rs = np.random.RandomState(0)
    nb = 4                       # distinct batches cycled per pass

    def id_batches(dist):
        out = []
        for _ in range(nb):
            if dist == 'uniform':
                ids = rs.randint(0, vocab, size=(batch,))
            elif dist == 'zipf':
                ids = np.minimum(rs.zipf(1.3, size=(batch,)) - 1,
                                 vocab - 1)
            else:                # repeat-heavy hot pool
                ids = rs.randint(0, hot, size=(batch,))
            out.append((nd.array(ids.astype(np.float32)),
                        nd.array(rs.randn(batch, 16)
                                 .astype(np.float32))))
        return out

    dists = {d: id_batches(d) for d in ('uniform', 'zipf', 'repeat')}
    _, fused_d, _ = make_fused(False)
    _, fused_s, _ = make_fused(True)

    def run(fused, bs, n):
        for i in range(n):
            x, y = bs[i % nb]
            l = fused(x, y)
        l.asnumpy()              # host-fetch barrier

    # warmup: visit every distribution's ladder rungs off the clock
    for bs in dists.values():
        run(fused_d, bs, nb)
        run(fused_s, bs, nb)
    cache0 = exec_cache.stats()
    c0_s, c0_m = cache0['total_compile_s'], cache0['misses']

    results = {}
    for dist, bs in dists.items():
        best = {'dense': 0.0, 'sparse': 0.0}
        # embed_max_rung is a running max — without a reset it would
        # report the warmup's one-shot discovery trace (rung == vocab)
        # instead of this distribution's steady-state ladder rung
        profiler.clear()
        e0 = profiler.embed_stats()
        for _ in range(passes):
            for name, f in (('dense', fused_d), ('sparse', fused_s)):
                tic = time.time()
                run(f, bs, steps)
                best[name] = max(best[name],
                                 steps / (time.time() - tic))
        e1 = profiler.embed_stats()
        es = passes * steps      # sparse steps measured in this dist
        results[dist] = {
            'dense_sps': round(best['dense'], 2),
            'sparse_sps': round(best['sparse'], 2),
            'speedup': round(best['sparse'] /
                             max(best['dense'], 1e-9), 3),
            'touched_bytes_per_step': (
                e1['embed_touched_bytes'] -
                e0['embed_touched_bytes']) // es,
            'dense_equiv_bytes_per_step': (
                e1['embed_dense_equiv_bytes'] -
                e0['embed_dense_equiv_bytes']) // es,
            'max_rung': e1['embed_max_rung'],
        }
    cache1 = exec_cache.stats()
    steady_compile_s = cache1['total_compile_s'] - c0_s
    steady_misses = cache1['misses'] - c0_m

    # parity gate: fresh nets, identical init, same batches; plain SGD
    # wd=0 makes the rows-only update bitwise equal to dense
    net_pd, fp_d, _ = make_fused(False, seed=7)
    net_ps, fp_s, _ = make_fused(True, seed=7)
    for x, y in dists['uniform'][:3]:
        fp_d(x, y)
        fp_s(x, y)
    max_diff = max(
        float(np.abs(a.list_data()[0].asnumpy() -
                     b.list_data()[0].asnumpy()).max())
        for (_, a), (_, b) in zip(
            sorted(net_pd.collect_params().items()),
            sorted(net_ps.collect_params().items())))

    shard = None
    if shard_dev > 0:
        env = dict(os.environ, BENCH_EMBED='1', BENCH_EMBED_DRYRUN='1',
                   JAX_PLATFORMS='cpu')
        flags = [f for f in env.get('XLA_FLAGS', '').split()
                 if 'xla_force_host_platform_device_count' not in f]
        flags.append('--xla_force_host_platform_device_count=%d'
                     % shard_dev)
        env['XLA_FLAGS'] = ' '.join(flags)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('embed shard child failed (rc=%d)'
                               % proc.returncode)
        lines = proc.stdout.strip().splitlines()
        if not lines:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('embed shard child produced no output')
        shard = json.loads(lines[-1])

    uni = results['uniform']
    print(json.dumps({
        'metric': 'sparse_embed_train',
        'value': uni['sparse_sps'],
        'unit': 'steps/sec',
        'vocab': vocab, 'dim': dim, 'batch': batch, 'hot': hot,
        'steps_per_pass': steps, 'passes': passes,
        'dists': results,
        'steady_state_compile_s': round(steady_compile_s, 3),
        'steady_state_misses': steady_misses,
        'zero_recompiles_ok': bool(steady_misses == 0),
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff == 0.0),
        'shard': shard,
    }))


# ---------------------------------------------------------------------------
# BENCH_OVERLAP=1: interleaved vs end-of-backward gradient reduction
# ---------------------------------------------------------------------------

def overlap_bench():
    """BENCH_OVERLAP=1: A/B the gradient-reduction schedule on a
    data-parallel mesh — backward-interleaved bucket-by-bucket
    all-reduce (each bucket's collective issues as soon as its wgrads
    exist, overlapping the remaining backward) vs the end-of-backward
    baseline (optimization_barrier: all wgrads complete before any
    reduce).  Values are identical across schedules (the barrier is
    identity and the packed bucket psum is elementwise the per-param
    psum), so the measured delta is schedule-only; a parity gate
    asserts it.  Emits ONE JSON line with best-of-N steps/s per arm
    (the rig's cpu-shares throttle swings single passes ~2x), the
    reduce_buckets_issued counter, and the parity max-abs-diff.

    Needs >= BENCH_OVERLAP_DEVICES devices: when the process has
    fewer (no TPU pod on this rig), re-execs itself on a virtual CPU
    mesh (same technique as dryrun_multichip).  NOTE on reading CPU
    numbers: virtual host devices share the same cores, so collective
    overlap cannot shorten wall-clock the way a real ICI fabric does —
    expect parity there and treat the arm as a schedule-correctness +
    counter smoke; the speedup story needs real chips (PERF round 11).

    Knobs: BENCH_OVERLAP_DEVICES (4), BENCH_OVERLAP_BATCH (64),
    BENCH_OVERLAP_DIM (64), BENCH_OVERLAP_HIDDEN (256),
    BENCH_OVERLAP_LAYERS (4), BENCH_OVERLAP_STEPS (20 per pass),
    BENCH_OVERLAP_PASSES (5), BENCH_OVERLAP_ZERO (0: plain all-reduce;
    1: compose with the ZeRO-1 reduce-scatter),
    MXNET_TPU_REDUCE_BUCKETS (defaulted to 4 here so the schedule has
    buckets to interleave)."""
    ndev = int(os.environ.get('BENCH_OVERLAP_DEVICES', 4))
    import jax
    try:
        have = jax.device_count()
    except Exception:
        have = 0
    if have < ndev:
        if os.environ.get('BENCH_OVERLAP_SPAWNED') == '1':
            raise RuntimeError('spawned overlap bench still has %d < '
                               '%d devices' % (have, ndev))
        env = dict(os.environ, BENCH_OVERLAP='1',
                   BENCH_OVERLAP_SPAWNED='1', JAX_PLATFORMS='cpu')
        flags = [f for f in env.get('XLA_FLAGS', '').split()
                 if 'xla_force_host_platform_device_count' not in f]
        flags.append('--xla_force_host_platform_device_count=%d'
                     % ndev)
        env['XLA_FLAGS'] = ' '.join(flags)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('overlap bench child failed (rc=%d)'
                               % proc.returncode)
        lines = proc.stdout.strip().splitlines()
        if not lines:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('overlap bench child produced no '
                               'output')
        print(lines[-1], flush=True)
        return
    os.environ.setdefault('MXNET_TPU_REDUCE_BUCKETS', '4')

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.gluon import nn

    batch = int(os.environ.get('BENCH_OVERLAP_BATCH', 64))
    dim = int(os.environ.get('BENCH_OVERLAP_DIM', 64))
    hidden = int(os.environ.get('BENCH_OVERLAP_HIDDEN', 256))
    layers = int(os.environ.get('BENCH_OVERLAP_LAYERS', 4))
    steps = int(os.environ.get('BENCH_OVERLAP_STEPS', 20))
    passes = max(1, int(os.environ.get('BENCH_OVERLAP_PASSES', 5)))
    zero = int(os.environ.get('BENCH_OVERLAP_ZERO', 0))
    classes = 10
    ctxs = [mx.cpu(i) for i in range(ndev)]
    opt_params = {'learning_rate': 0.05, 'momentum': 0.9, 'wd': 1e-4}
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.rand(batch, dim).astype(np.float32))
    y = mx.nd.array((rs.rand(batch) * classes).astype(np.float32))

    def make_fused(seed, interleave):
        net = nn.HybridSequential()
        with net.name_scope():
            for _ in range(layers):
                net.add(nn.Dense(hidden, activation='relu'))
            net.add(nn.Dense(classes))
        net.initialize(ctx=ctxs)
        net(mx.nd.zeros((batch, dim)))
        prs = np.random.RandomState(seed)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                (prs.rand(*p.shape).astype(np.float32) - 0.5) * 0.2))
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           dict(opt_params))
        return net, gluon.fuse_step(net, loss_fn, tr, zero=zero,
                                    interleave=interleave)

    net_i, fs_i = make_fused(1, True)
    net_e, fs_e = make_fused(1, False)

    def run_steps(fs, n):
        for _ in range(n):
            l = fs(x, y)
        l.asnumpy()

    run_steps(fs_i, 2)
    run_steps(fs_e, 2)
    # the reduce plan materializes on the first step
    buckets = fs_i._reduce_plan.n_buckets if not zero else None
    best = {'interleaved': 0.0, 'end': 0.0}
    # measure with the profiler ON: dispatches then synchronize, so
    # per-dispatch wall time reflects execution, not async enqueue —
    # both arms pay the same sync
    profiler.clear()
    profiler.profiler_set_state('run')
    try:
        for _ in range(passes):
            for name, fs in (('interleaved', fs_i), ('end', fs_e)):
                tic = time.time()
                run_steps(fs, steps)
                best[name] = max(best[name],
                                 steps / (time.time() - tic))
    finally:
        profiler.profiler_set_state('stop')

    # parity: same step counts on both arms -> identical weights
    max_diff = max(
        float(np.abs(a.list_data()[0].asnumpy() -
                     b.list_data()[0].asnumpy()).max())
        for (_, a), (_, b) in zip(
            sorted(net_i.collect_params().items()),
            sorted(net_e.collect_params().items())))
    cm = profiler.comm_stats()

    # -- host-hiding A/B (PERF round 21): bounded step-ahead ------------
    # step_ahead=1 returns with the dispatch still in flight (the host
    # stages + enqueues step t+1 behind it; the block on step t's loss
    # is the backpressure); step_ahead=0 blocks on every step's loss
    # before returning — the serialized baseline.  The depth changes
    # only WHEN the host waits, never what is computed, so the
    # per-step loss curves must match BIT for BIT.  Measured with the
    # profiler OFF (a synced dispatch would serialize both arms).
    ahead_steps = int(os.environ.get('BENCH_OVERLAP_AHEAD_STEPS',
                                     steps))

    def make_single(seed, step_ahead):
        net = nn.HybridSequential()
        with net.name_scope():
            for _ in range(layers):
                net.add(nn.Dense(hidden, activation='relu'))
            net.add(nn.Dense(classes))
        net.initialize()
        net(mx.nd.zeros((batch, dim)))
        prs = np.random.RandomState(seed)
        for _, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                (prs.rand(*p.shape).astype(np.float32) - 0.5) * 0.2))
        tr = gluon.Trainer(net.collect_params(), 'sgd',
                           dict(opt_params))
        return gluon.fuse_step(net, loss_fn, tr,
                               step_ahead=step_ahead)

    def loss_curve(fs, n):
        curves = [fs(x, y) for _ in range(n)]
        return [c.asnumpy().copy() for c in curves]

    fs_a1 = make_single(2, 1)
    fs_a0 = make_single(2, 0)
    curve_a1 = loss_curve(fs_a1, 2)     # warm outside the clock
    curve_a0 = loss_curve(fs_a0, 2)
    best_ahead = {'ahead1': 0.0, 'ahead0': 0.0}
    for _ in range(passes):
        for name, fs in (('ahead1', fs_a1), ('ahead0', fs_a0)):
            tic = time.time()
            curve = loss_curve(fs, ahead_steps)
            best_ahead[name] = max(best_ahead[name],
                                   ahead_steps / (time.time() - tic))
            if name == 'ahead1':
                curve_a1 = curve
            else:
                curve_a0 = curve
    step_parity = len(curve_a1) == len(curve_a0) and all(
        np.array_equal(a, b) for a, b in zip(curve_a1, curve_a0))
    ov = profiler.overlap_stats()

    print(json.dumps({
        'metric': 'overlap_reduce',
        'value': round(best['interleaved'], 2),
        'unit': 'steps/sec',
        'end_of_backward_sps': round(best['end'], 2),
        'speedup_vs_end': round(best['interleaved'] /
                                max(best['end'], 1e-9), 3),
        'devices': ndev, 'batch': batch, 'dim': dim,
        'hidden': hidden, 'layers': layers, 'zero': zero,
        'reduce_buckets': buckets,
        'reduce_buckets_issued': cm['reduce_buckets_issued'],
        'steps_per_pass': steps, 'passes': passes,
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff < 1e-5),
        'step_ahead1_sps': round(best_ahead['ahead1'], 2),
        'step_ahead0_sps': round(best_ahead['ahead0'], 2),
        'step_ahead_speedup': round(
            best_ahead['ahead1'] / max(best_ahead['ahead0'], 1e-9), 3),
        'step_ahead_steps': ahead_steps,
        'step_ahead_loss_bit_parity': bool(step_parity),
        'overlap_train_steps': ov['overlap_train_steps'],
        'overlap_dispatch_wait_ms': round(
            ov['overlap_dispatch_wait_ms'], 3),
    }))


# ---------------------------------------------------------------------------
# BENCH_BUCKET=1: fused bucket-ladder training vs the legacy 3-dispatch loop
# ---------------------------------------------------------------------------

def bucket_bench():
    """BENCH_BUCKET=1: measure dynamic-shape (bucketed) training on a
    synthetic length-mixed workload in three arms and emit ONE JSON
    line:

      * legacy   — the pre-round-12 per-bucket loop: forward() /
        backward() / update() = 3 dispatches per step, programs
        compiled lazily per length.
      * fused    — forward_backward()+update() through the fused
        single-dispatch train program, on an AOT-warmed bucket ladder
        (bucket_ladder + mask_label: off-rung lengths pad up, masked
        positions contribute zero — ZERO XLA compiles in the measured
        steady state).
      * bulk     — the same ladder driven bucket-major: runs of
        BENCH_BUCKET_BULK same-rung batches fuse into ONE lax.scan
        dispatch each (fit(bulk=K) for variable-length data).

    All arms process the same multiset of batch lengths; the bulk arm
    sees them bucket-major (that reordering is exactly what
    BucketSentenceIter(bucket_major=True) provides).  Arms run
    best-of-BENCH_BUCKET_PASSES interleaved (this rig's cpu-shares
    throttle swings single passes ~2x).  Parity gates: legacy vs
    fused, and fused vs bulk, trained from identical init on identical
    schedules.

    Knobs: BENCH_BUCKET_BATCH (32), BENCH_BUCKET_VOCAB (64),
    BENCH_BUCKET_EMBED (32), BENCH_BUCKET_HIDDEN (64),
    BENCH_BUCKET_LADDER ('8,16'), BENCH_BUCKET_LENGTHS ('5,8,11,16'),
    BENCH_BUCKET_STEPS (24 per pass), BENCH_BUCKET_PASSES (5),
    BENCH_BUCKET_BULK (8)."""
    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache, profiler
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import sym

    batch = int(os.environ.get('BENCH_BUCKET_BATCH', 32))
    vocab = int(os.environ.get('BENCH_BUCKET_VOCAB', 64))
    embed = int(os.environ.get('BENCH_BUCKET_EMBED', 32))
    hidden = int(os.environ.get('BENCH_BUCKET_HIDDEN', 64))
    ladder = tuple(int(x) for x in os.environ.get(
        'BENCH_BUCKET_LADDER', '8,16').split(','))
    lengths = tuple(int(x) for x in os.environ.get(
        'BENCH_BUCKET_LENGTHS', '5,8,11,16').split(','))
    steps = int(os.environ.get('BENCH_BUCKET_STEPS', 24))
    passes = max(1, int(os.environ.get('BENCH_BUCKET_PASSES', 5)))
    bulk = int(os.environ.get('BENCH_BUCKET_BULK', 8))
    mask = 0
    default_key = max(ladder)

    def sym_gen(seq_len):
        data = sym.Variable('data')
        label = sym.Variable('softmax_label')
        emb = sym.Embedding(data, input_dim=vocab, output_dim=embed,
                            name='embed')
        h = sym.Reshape(emb, shape=(-1, embed))
        h = sym.Activation(sym.FullyConnected(h, num_hidden=hidden,
                                              name='fc1'),
                           act_type='relu')
        fc = sym.FullyConnected(h, num_hidden=vocab, name='pred')
        lab = sym.Reshape(label, shape=(-1,))
        out = sym.SoftmaxOutput(fc, label=lab, use_ignore=True,
                                ignore_label=mask, name='softmax')
        return out, ('data',), ('softmax_label',)

    def make_module(with_ladder, warm):
        mx.random.seed(5)
        mod = mx.mod.BucketingModule(
            sym_gen, default_bucket_key=default_key,
            bucket_ladder=(ladder if with_ladder else None),
            mask_label=mask, warmup_buckets=warm)
        mod.bind(
            data_shapes=[mx.io.DataDesc('data', (batch, default_key),
                                        layout='NT')],
            label_shapes=[mx.io.DataDesc('softmax_label',
                                         (batch, default_key),
                                         layout='NT')])
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer_params={'learning_rate': 0.05,
                                             'momentum': 0.9})
        return mod

    rng = np.random.RandomState(3)

    def make_batch(seq_len, seed):
        rs = np.random.RandomState(1000 + 31 * seed + seq_len)
        X = rs.randint(1, vocab, (batch, seq_len)).astype(np.float32)
        y = np.roll(X, -1, axis=1)
        y[:, -1] = mask
        return mx.io.DataBatch(
            [nd.array(X)], [nd.array(y)], bucket_key=seq_len,
            provide_data=[mx.io.DataDesc('data', (batch, seq_len),
                                         layout='NT')],
            provide_label=[mx.io.DataDesc('softmax_label',
                                          (batch, seq_len),
                                          layout='NT')])

    # one length schedule for every arm: mixed order for legacy/fused,
    # bucket-major (sorted) for the bulk arm — same multiset of work
    schedule = [lengths[rng.randint(len(lengths))] for _ in range(steps)]
    mixed = [make_batch(l, i) for i, l in enumerate(schedule)]
    major = sorted(mixed, key=lambda b: b.bucket_key)

    # legacy arm = the true pre-round-12 configuration: NO ladder (one
    # exact-shape module compiled lazily per length) driven through the
    # 3-dispatch forward/backward/update loop; its compiles land in the
    # warmup pass below, so the measured window is its steady state
    mod_l = make_module(with_ladder=False, warm=None)
    mod_f = make_module(with_ladder=True, warm=True)
    mod_b = make_module(with_ladder=True, warm=True)
    mod_b.warmup_buckets(bulk=bulk)

    def legacy_steps():
        for b in mixed:
            mod_l.forward(b, is_train=True)   # dispatch 1 (fwd)
            mod_l.backward()                  # dispatch 2 (fwd+bwd)
            mod_l.update()                    # dispatch 3 (update)
        mod_l.get_outputs()[0].asnumpy()      # host-fetch barrier

    def fused_steps():
        for b in mixed:
            mod_f.forward_backward(b)
            mod_f.update()
        mod_f.get_outputs()[0].asnumpy()

    def bulk_steps():
        group = []
        for b in major + [None]:
            if b is not None and (not group or
                                  (mod_b._rung_for(b.bucket_key) ==
                                   mod_b._rung_for(group[0].bucket_key)
                                   and len(group) < bulk)):
                group.append(b)
                continue
            if len(group) >= 2:
                mod_b.bulk_step(batches=group)
            else:
                for g in group:
                    mod_b.forward_backward(g)
                    mod_b.update()
            group = [b] if b is not None else []
        mod_b.get_outputs()[0].asnumpy()

    # warmup passes (any lazy compiles happen here, outside the clock).
    # bulk runs twice: partial-K trailing groups are not AOT-warmed, and
    # their programs need both the fresh-buffer and the donated-output
    # signatures compiled before the clock starts
    legacy_steps()
    fused_steps()
    bulk_steps()
    bulk_steps()

    best = {'legacy': 0.0, 'fused': 0.0, 'bulk': 0.0}
    c0 = exec_cache.stats()['total_compile_s']
    for _ in range(passes):
        for name, fn in (('legacy', legacy_steps), ('fused', fused_steps),
                         ('bulk', bulk_steps)):
            tic = time.time()
            fn()
            best[name] = max(best[name], steps / (time.time() - tic))
    steady_compile_s = exec_cache.stats()['total_compile_s'] - c0

    # parity: identical init + identical schedule per pair.  legacy
    # (exact shapes) vs fused (padded to rung) also gates the masked-pad
    # semantics: the two trajectories agree to float rounding
    def clone_pair(ladder_a=True):
        a = make_module(with_ladder=ladder_a, warm=None)
        b = make_module(with_ladder=True, warm=None)
        b.set_params(*a.get_params())
        return a, b

    pl, pf = clone_pair(ladder_a=False)
    for b in mixed[:6]:
        pl.forward(b, is_train=True)
        pl.backward()
        pl.update()
        pf.forward_backward(b)
        pf.update()

    def max_diff(m1, m2):
        a1, _ = m1.get_params()
        a2, _ = m2.get_params()
        return max(float(np.abs(a1[k].asnumpy() -
                                a2[k].asnumpy()).max()) for k in a1)

    parity_lf = max_diff(pl, pf)
    ps, pb = clone_pair()
    grp = major[:bulk]
    grp = [g for g in grp
           if ps._rung_for(g.bucket_key) ==
           ps._rung_for(grp[0].bucket_key)]
    for b in grp:
        ps.forward_backward(b)
        ps.update()
    pb.bulk_step(batches=grp)
    parity_fb = max_diff(ps, pb)

    bs = profiler.bucketing_stats()
    print(json.dumps({
        'metric': 'bucket_ladder_train',
        'value': round(best['fused'], 2),
        'unit': 'steps/sec',
        'legacy_sps': round(best['legacy'], 2),
        'bulk_sps': round(best['bulk'], 2),
        'speedup_vs_legacy': round(
            best['fused'] / max(best['legacy'], 1e-9), 3),
        'speedup_bulk_vs_legacy': round(
            best['bulk'] / max(best['legacy'], 1e-9), 3),
        'batch': batch, 'vocab': vocab, 'embed': embed,
        'hidden': hidden, 'ladder': list(ladder),
        'lengths': list(lengths), 'steps_per_pass': steps,
        'passes': passes, 'bulk': bulk,
        'steady_compile_s': round(steady_compile_s, 4),
        'zero_compile_steady_state': bool(steady_compile_s == 0.0),
        'train_pad_waste_frac': round(bs['train_pad_waste_frac'], 4),
        'train_bucket_switches': bs['train_bucket_switches'],
        'parity_legacy_vs_fused': parity_lf,
        'parity_fused_vs_bulk': parity_fb,
        'parity_ok': bool(parity_lf < 1e-5 and parity_fb < 1e-5),
    }))


# ---------------------------------------------------------------------------
# BENCH_INFER=serve: dynamic-batching inference engine vs serial predict
# ---------------------------------------------------------------------------

def _serve_symbol(hidden, classes, dim):
    """CPU-sized serving workload: a small MLP (the serving engine's
    mechanics — coalescing, padding, slicing, staging — are model-size
    independent; the rig has no TPU, so the smoke must stay tiny)."""
    from mxnet_tpu import sym
    data = sym.Variable('data')
    x = sym.Activation(sym.FullyConnected(data, num_hidden=hidden,
                                          name='fc1'), act_type='relu')
    x = sym.Activation(sym.FullyConnected(x, num_hidden=hidden,
                                          name='fc2'), act_type='relu')
    x = sym.FullyConnected(x, num_hidden=classes, name='fc3')
    return sym.SoftmaxOutput(x, name='softmax')


def serve_bench():
    """BENCH_INFER=serve: measure the dynamic-batching InferenceEngine
    (mxnet_tpu/serving.py) against serial per-request Predictor.forward
    on the same request stream, and emit ONE JSON line with request
    throughput, latency percentiles, fill/pad-waste, and the
    zero-compile steady-state check (exec_cache misses after warmup).

    Closed loop: BENCH_SERVE_CLIENTS client threads (default 8) each
    issue BENCH_SERVE_REQS single-row requests back-to-back (a new
    request the moment the previous answer lands).  The serial
    baseline runs the IDENTICAL client loop against the pre-engine
    serving story: per-request Predictor.forward behind one lock
    (forward is set-input-then-run on shared executor state, so
    concurrent callers must serialize — that lock is what the engine
    replaces).  A 1-thread serial pass is also timed and reported
    (serial_rps_1thread) so the client-contention cost is visible.
    Parity: engine answers must match the serial answers
    (same-bucket co-batching is bit-exact; across gemm shapes XLA
    differs at float rounding, so the gate is atol 1e-5 with the
    measured max reported).

    Knobs: BENCH_SERVE_CLIENTS (8), BENCH_SERVE_REQS (per client, 100),
    BENCH_SERVE_PASSES (best-of passes per arm, 7),
    BENCH_SERVE_MAX_BATCH (= clients), BENCH_SERVE_WAIT_US (2000),
    BENCH_SERVE_DIM (256), BENCH_SERVE_HIDDEN (256 — enough
    per-request compute that dispatch amortization dominates noise;
    the whole smoke stays a few seconds per pass),
    BENCH_SERVE_MIXED=1 (alternate two request widths; the narrow one
    zero-pads up the free-dim bucket — the shape-bucket story under
    mixed traffic).
    """
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.predictor import Predictor

    # both arms are thread-ping-pong-bound on a CPU rig; the default
    # 5ms GIL switch interval adds multi-ms scheduling bubbles to
    # every client wakeup, swamping the sub-ms dispatch being measured
    sys.setswitchinterval(0.001)

    clients = int(os.environ.get('BENCH_SERVE_CLIENTS', 8))
    reqs_per_client = int(os.environ.get('BENCH_SERVE_REQS', 100))
    max_batch = int(os.environ.get('BENCH_SERVE_MAX_BATCH', clients))
    wait_us = int(os.environ.get('BENCH_SERVE_WAIT_US', 2000))
    dim = int(os.environ.get('BENCH_SERVE_DIM', 256))
    hidden = int(os.environ.get('BENCH_SERVE_HIDDEN', 256))
    classes = 16
    mixed = os.environ.get('BENCH_SERVE_MIXED', '0') == '1'

    rng = np.random.RandomState(11)
    net = _serve_symbol(hidden, classes, dim)
    probe = net.simple_bind(mx.cpu(), grad_req='null', data=(1, dim))
    args = {k: mx.nd.array(rng.randn(*v.shape).astype(np.float32) * 0.1)
            for k, v in probe.arg_dict.items() if k != 'data'}
    pred = Predictor(symbol=net, arg_params=args,
                     input_shapes={'data': (1, dim)})

    n_total = clients * reqs_per_client
    dims = [dim] * n_total
    if mixed:
        # two free-dim rungs; the narrow one zero-pads up to `dim`,
        # which this MLP treats as extra zero features (value-neutral)
        dims = [dim if i % 2 == 0 else dim // 2 for i in range(n_total)]
    requests = [rng.randn(1, d).astype(np.float32) for d in dims]

    def run_clients(serve_one):
        """The closed loop both arms share: `clients` threads, each
        issuing its requests back-to-back.  Returns elapsed seconds."""
        errors = []

        def client(c):
            try:
                for j in range(reqs_per_client):
                    serve_one(c * reqs_per_client + j)
            except Exception as e:   # surface, don't hang the join
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        tic = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.time() - tic
        if errors:
            raise errors[0]
        return elapsed

    # -- serial baseline: per-request forward behind one lock -----------
    # (run FIRST so its own first-shape compiles don't pollute the
    # engine's post-warmup zero-compile accounting)
    serial_out = [None] * n_total
    serial_lock = threading.Lock()

    def serial_one(i):
        a = requests[i]
        if a.shape[1] != dim:
            # narrow request: the model's input width is fixed, so the
            # serial server zero-pads too (value-neutral for this MLP —
            # exactly what the engine's free-dim bucket does)
            buf = np.zeros((1, dim), np.float32)
            buf[:, :a.shape[1]] = a
            a = buf
        with serial_lock:
            serial_out[i] = pred.forward(data=a)[0].asnumpy()

    serial_one(0)                     # warmup outside the clock
    tic = time.time()
    for i in range(n_total):
        serial_one(i)
    serial_1thread_rps = n_total / (time.time() - tic)

    # -- engine: the same closed loop, coalesced dispatches -------------
    # (mixed mode opts into free-dim zero-padding with ONE rung at
    # the model's bound width — value-neutral for an MLP, padded
    # features multiply zero weights; a narrower graph rung would be
    # a different model, fc1_weight binds at the rung width.  The
    # default engine keeps the serial exact-shape contract and would
    # reject the narrow requests.)
    eng = pred.serve(max_batch=max_batch, max_wait_us=wait_us,
                     **({'free_dim_buckets': [((dim,),)]} if mixed
                        else {}))
    stats0 = profiler.exec_cache_stats()
    engine_out = [None] * n_total

    def engine_one(i):
        engine_out[i] = eng.predict(requests[i])

    # the rig runs under cpu-shares throttling whose multi-second
    # bursts swing any single pass by ~2x, so the arms run
    # BENCH_SERVE_PASSES times INTERLEAVED (serial, engine, serial,
    # ...) and each reports its best pass — peak vs peak sampled from
    # the same throttle climate compares the serving mechanisms, not
    # the throttle phase.  (Serial passes after the engine exists
    # compile nothing — the predictor's executor is long bound — so
    # the zero-compile accounting from stats0 is undisturbed.)
    passes = max(1, int(os.environ.get('BENCH_SERVE_PASSES', 7)))
    serial_rps = engine_rps = 0.0
    best_sv = None
    for _ in range(passes):
        serial_rps = max(serial_rps,
                         n_total / run_clients(serial_one))
        # the latency percentiles must be measured on the SAME pass as
        # the throughput they sit beside: reset the profiler's serving
        # window before each engine pass and keep the best pass's
        # snapshot (a cumulative ring would pair best-of throughput
        # with latencies dominated by the throttled passes;
        # exec_cache_stats reads through to exec_cache, so the
        # zero-compile accounting is untouched by clear())
        profiler.clear()
        rps = n_total / run_clients(engine_one)
        if rps > engine_rps:
            engine_rps = rps
            best_sv = profiler.serving_stats()
    stats1 = profiler.exec_cache_stats()
    est = eng.stats()
    eng.close()

    max_diff = max(float(np.abs(engine_out[i] - serial_out[i]).max())
                   for i in range(n_total))
    print(json.dumps({
        'metric': 'serve_throughput',
        'value': round(engine_rps, 2),
        'unit': 'requests/sec',
        'serial_rps': round(serial_rps, 2),
        'serial_rps_1thread': round(serial_1thread_rps, 2),
        'speedup_vs_serial': round(engine_rps / serial_rps, 3),
        'speedup_vs_1thread': round(engine_rps / serial_1thread_rps, 3),
        'clients': clients,
        'requests': n_total,
        'max_batch': max_batch,
        'max_wait_us': wait_us,
        'mixed_shapes': mixed,
        'batch_buckets': list(eng.batch_buckets),
        'p50_ms': round(best_sv['serve_latency_p50_ms'], 3),
        'p99_ms': round(best_sv['serve_latency_p99_ms'], 3),
        'batch_fill_avg': round(est['batch_fill_avg'], 3),
        'pad_waste_frac': round(est['pad_waste_frac'], 3),
        'queue_depth_avg': round(best_sv['serve_queue_depth_avg'], 2),
        'exec_cache_misses_after_warmup':
            stats1['exec_cache_misses'] - stats0['exec_cache_misses'],
        'compiles_after_warmup': est['compiles_after_warmup'],
        'parity_max_abs_diff': max_diff,
        'parity_ok': bool(max_diff < 1e-5),
    }))


# ---------------------------------------------------------------------------
# BENCH_FLEET=1: fleet serving tier (registry + SLO batching + HTTP front +
# continuous batching) — the ISSUE-10 acceptance measurements
# ---------------------------------------------------------------------------

def fleet_bench():
    """BENCH_FLEET=1: measure the fleet serving tier
    (mxnet_tpu/serving_fleet.py) and emit ONE JSON line covering the
    three acceptance claims:

      (a) **SLO batching** — two tenants through the REAL HTTP front
          (localhost sockets): 'fast' (small MLP, tight deadline,
          priority 1) and 'bulk' (bigger MLP, loose deadline).  The
          single-knob arm gives both engines one global max_wait_us
          (tuned high for bulk coalescing, the pre-fleet story); the
          SLO arm derives each tenant's batcher hold from its own
          deadline.  Client-side p99 for the fast tenant must meet
          its deadline under SLO and miss it under the global knob.
      (b) **continuous batching** — mixed-length sequences through
          ContinuousEngine vs the same engine in convoy mode
          (admission only into an empty batch): throughput best-of-N,
          gated on BIT-parity of the continuous outputs vs solo runs.
      (b2) **chunked ticks** — the tick_chunk ladder (K=1/4/16 per
          dispatch, its own slot count since the engine rejects
          K > slots): throughput best-of-N per rung, gated on
          BIT-parity of every chunked run vs the K=1 baseline and on
          zero steady-state compiles; reports the dispatch-count drop
          (ticks per XLA dispatch at the top rung).
      (c) **registry paging** — evict/re-warm cycles under a byte
          budget that fits one model: steady-state exec_cache miss
          delta must be ZERO.

    Knobs: BENCH_FLEET_PASSES (3), BENCH_FLEET_REQS (per client, 40),
    BENCH_FLEET_FAST_CLIENTS / _BULK_CLIENTS (2/2),
    BENCH_FLEET_FAST_DEADLINE_MS (50 — sized so this rig's ~2x
    cpu-shares throttle swings cannot flip either arm's verdict: the
    SLO arm's measured p99 sits well under it, the single-knob arm's
    well over), BENCH_FLEET_GLOBAL_WAIT_US (60000 — the single knob,
    tuned for bulk fill), BENCH_FLEET_SEQS (24),
    BENCH_FLEET_SLOTS (4), BENCH_FLEET_CHUNKS ('1,4,16'),
    BENCH_FLEET_CHUNK_SLOTS (max rung), BENCH_FLEET_CHUNK_LEN (48).
    """
    import threading
    import urllib.request

    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache, nd, sym
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving_fleet import (ContinuousEngine, HttpFront,
                                         ModelRegistry, SLO)

    sys.setswitchinterval(0.001)
    passes = max(1, int(os.environ.get('BENCH_FLEET_PASSES', 3)))
    reqs = int(os.environ.get('BENCH_FLEET_REQS', 40))
    fast_clients = int(os.environ.get('BENCH_FLEET_FAST_CLIENTS', 2))
    bulk_clients = int(os.environ.get('BENCH_FLEET_BULK_CLIENTS', 2))
    fast_deadline = float(os.environ.get('BENCH_FLEET_FAST_DEADLINE_MS',
                                         50))
    global_wait = int(os.environ.get('BENCH_FLEET_GLOBAL_WAIT_US',
                                     60000))
    n_seqs = int(os.environ.get('BENCH_FLEET_SEQS', 24))
    slots = int(os.environ.get('BENCH_FLEET_SLOTS', 4))
    rng = np.random.RandomState(11)

    def mlp_pred(dim, hidden, seed):
        net = _serve_symbol(hidden, 16, dim)
        probe = net.simple_bind(mx.cpu(), grad_req='null',
                                data=(1, dim))
        rs = np.random.RandomState(seed)
        args = {k: nd.array(rs.randn(*v.shape).astype(np.float32) * .1)
                for k, v in probe.arg_dict.items() if k != 'data'}
        return lambda: Predictor(symbol=net, arg_params=args,
                                 input_shapes={'data': (1, dim)})

    fast_dim, bulk_dim = 32, 256
    fast_loader = mlp_pred(fast_dim, 32, 1)
    bulk_loader = mlp_pred(bulk_dim, 256, 2)

    # -- (a) SLO vs single-knob, through the HTTP front ----------------
    def http_arm(slo_mode):
        reg = ModelRegistry()
        fast_kw = dict(max_batch=8)
        bulk_kw = dict(max_batch=8)
        if not slo_mode:    # ONE global knob for every tenant,
            fast_kw['max_wait_us'] = global_wait   # tuned for bulk
            bulk_kw['max_wait_us'] = global_wait   # coalescing
        reg.register('fast', loader=fast_loader,
                     slo=SLO(deadline_ms=fast_deadline, priority=1),
                     **fast_kw)
        # bulk's deadline is 3x fast: its derived hold (~0.75x the
        # global knob) keeps the arms' BULK behavior comparable, so
        # the A/B isolates the fast tenant's treatment
        reg.register('bulk', loader=bulk_loader,
                     slo=SLO(deadline_ms=3 * fast_deadline),
                     **bulk_kw)
        reg.engine('fast')      # load + AOT-warm outside the clock:
        reg.engine('bulk')      # the arms measure batching policy,
        front = HttpFront(reg, port=0).start()   # not cold starts
        host, port = front.address

        def post(name, arr):
            body = json.dumps({'instances': arr.tolist()}).encode()
            req = urllib.request.Request(
                'http://%s:%d/v1/models/%s:predict' % (host, port,
                                                       name),
                data=body,
                headers={'Content-Type': 'application/json'})
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                resp.read()

        best = None
        for _ in range(passes):
            fast_lat = []
            errors = []

            def fast_client():
                x = rng.randn(1, fast_dim).astype(np.float32)
                try:
                    for _ in range(reqs):
                        t0 = time.perf_counter()
                        post('fast', x)
                        fast_lat.append(
                            (time.perf_counter() - t0) * 1e3)
                except Exception as e:
                    errors.append(e)

            def bulk_client():
                x = rng.randn(1, bulk_dim).astype(np.float32)
                try:
                    for _ in range(reqs):
                        post('bulk', x)
                except Exception as e:
                    errors.append(e)

            ts = [threading.Thread(target=fast_client)
                  for _ in range(fast_clients)] + \
                 [threading.Thread(target=bulk_client)
                  for _ in range(bulk_clients)]
            tic = time.time()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            elapsed = time.time() - tic
            if errors:
                raise errors[0]
            p99 = float(np.percentile(fast_lat, 99))
            p50 = float(np.percentile(fast_lat, 50))
            total = (fast_clients + bulk_clients) * reqs
            if best is None or p99 < best['fast_p99_ms']:
                best = {'fast_p99_ms': p99, 'fast_p50_ms': p50,
                        'rps': total / elapsed}
        front.close()
        reg.close()
        return best

    single = http_arm(slo_mode=False)
    slo = http_arm(slo_mode=True)

    # -- (b) continuous vs convoy on mixed-length sequences ------------
    sdim, shid = 16, 32
    data = sym.Variable('data')
    h_in = sym.Variable('h')
    pre = sym.FullyConnected(data, num_hidden=shid, name='ix') + \
        sym.FullyConnected(h_in, num_hidden=shid, no_bias=True,
                           name='hh')
    h_new = sym.Activation(pre, act_type='tanh')
    head = sym.FullyConnected(h_new, num_hidden=8, name='out')
    cell = sym.Group([head, h_new])
    rs = np.random.RandomState(5)
    cp = {'ix_weight': nd.array(rs.randn(shid, sdim).astype(np.float32)
                                * .3),
          'ix_bias': nd.array(np.zeros(shid, np.float32)),
          'hh_weight': nd.array(rs.randn(shid, shid).astype(np.float32)
                                * .3),
          'out_weight': nd.array(rs.randn(8, shid).astype(np.float32)
                                 * .3),
          'out_bias': nd.array(np.zeros(8, np.float32))}

    def mk_cont(convoy):
        return ContinuousEngine(cell, arg_params=cp, data_shape=(sdim,),
                                state_shapes={'h': (shid,)},
                                state_outputs={'h': 1}, slots=slots,
                                convoy=convoy)

    lens = [3 if i % 2 == 0 else 18 for i in range(n_seqs)]
    seqs = [rs.randn(L, sdim).astype(np.float32) for L in lens]

    # parity gate: co-resident continuous answers vs solo (sequential)
    eng = mk_cont(convoy=False)
    solo = [eng.infer(s) for s in seqs]
    res = [None] * len(seqs)
    ts = [threading.Thread(
        target=lambda i=i: res.__setitem__(i, eng.infer(seqs[i])))
        for i in range(len(seqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    cont_bit_parity = all(
        all(np.array_equal(a, b) for a, b in zip(res[i], solo[i]))
        for i in range(len(seqs)))
    eng.close()

    def seq_pass(convoy):
        engine = mk_cont(convoy)
        out = [None] * len(seqs)
        ts = [threading.Thread(
            target=lambda i=i: out.__setitem__(i,
                                               engine.infer(seqs[i])))
            for i in range(len(seqs))]
        tic = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        elapsed = time.time() - tic
        st = engine.stats()
        engine.close()
        return len(seqs) / elapsed, st

    cont_sps = convoy_sps = 0.0
    cont_st = convoy_st = None
    for _ in range(passes):
        s, st = seq_pass(convoy=False)
        if s > cont_sps:
            cont_sps, cont_st = s, st
        s, st = seq_pass(convoy=True)
        if s > convoy_sps:
            convoy_sps, convoy_st = s, st

    # -- (b2) chunk ladder: K ticks per XLA dispatch -------------------
    chunks_env = os.environ.get('BENCH_FLEET_CHUNKS', '1,4,16')
    ladder = [max(1, int(t)) for t in chunks_env.split(',')
              if t.strip()]
    chunk_slots = int(os.environ.get('BENCH_FLEET_CHUNK_SLOTS',
                                     max([slots] + ladder)))
    chunk_len = int(os.environ.get('BENCH_FLEET_CHUNK_LEN', 48))
    cseqs = [rs.randn(chunk_len, sdim).astype(np.float32)
             for _ in range(n_seqs)]

    def chunk_pass(K, stage_ahead=0, slo=None):
        engine = ContinuousEngine(cell, arg_params=cp,
                                  data_shape=(sdim,),
                                  state_shapes={'h': (shid,)},
                                  state_outputs={'h': 1},
                                  slots=chunk_slots, tick_chunk=K,
                                  stage_ahead=stage_ahead, slo=slo)
        out = [None] * len(cseqs)
        ts = [threading.Thread(
            target=lambda i=i: out.__setitem__(i,
                                               engine.infer(cseqs[i])))
            for i in range(len(cseqs))]
        tic = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        elapsed = time.time() - tic
        st = engine.stats()
        engine.close()
        assert st['compiles_after_warmup'] == 0, \
            'chunked engine compiled mid-flight (K=%s)' % (K,)
        return out, len(cseqs) / elapsed, st

    chunk_sps = {}
    chunk_st = {}
    chunk_parity = True
    ref_out = None
    for K in ladder:
        best_s, best_st = 0.0, None
        for _ in range(passes):
            out, s, st = chunk_pass(K)
            if K == ladder[0] and ref_out is None:
                ref_out = out       # K=1 leads the ladder: baseline
            chunk_parity = chunk_parity and all(
                all(np.array_equal(a, b)
                    for a, b in zip(out[i], ref_out[i]))
                for i in range(len(cseqs)))
            if s > best_s:
                best_s, best_st = s, st
        chunk_sps[K] = best_s
        chunk_st[K] = best_st
    k_top, k_base = ladder[-1], ladder[0]
    top_st = chunk_st[k_top]

    # -- (b3) double-buffered staging A/B at identical K ---------------
    # same workload, same K: stage_ahead=1 stages + enqueues chunk t+1
    # while chunk t executes (the serial ladder above, stage_ahead=0,
    # is the PR-17 baseline); gated on bit-parity vs the K=1 reference
    staged_sps, staged_st = 0.0, None
    staged_parity = True
    for _ in range(passes):
        out, s, st = chunk_pass(k_top, stage_ahead=1)
        staged_parity = staged_parity and all(
            all(np.array_equal(a, b)
                for a, b in zip(out[i], ref_out[i]))
            for i in range(len(cseqs)))
        if s > staged_sps:
            staged_sps, staged_st = s, st

    # -- (b4) tick_chunk='auto': EMA-adapted K on the warmed rungs -----
    auto_sps, auto_st = 0.0, None
    auto_parity = True
    auto_deadline = float(os.environ.get('BENCH_FLEET_AUTO_DEADLINE_MS',
                                         200))
    for _ in range(passes):
        out, s, st = chunk_pass('auto', stage_ahead=1,
                                slo=SLO(deadline_ms=auto_deadline))
        auto_parity = auto_parity and all(
            all(np.array_equal(a, b)
                for a, b in zip(out[i], ref_out[i]))
            for i in range(len(cseqs)))
        if s > auto_sps:
            auto_sps, auto_st = s, st

    # -- (c) registry paging: evict/re-warm at zero compiles -----------
    reg = ModelRegistry(budget_bytes=1)      # forces single residency
    reg.register('m1', loader=fast_loader, max_batch=4, max_wait_us=0)
    reg.register('m2', loader=bulk_loader, max_batch=4, max_wait_us=0)
    xf = rng.randn(1, fast_dim).astype(np.float32)
    xb = rng.randn(1, bulk_dim).astype(np.float32)
    reg.infer('m1', xf)
    reg.infer('m2', xb)                      # both warmed once
    before = exec_cache.stats()['misses']
    cycles = 3
    for _ in range(cycles):
        reg.infer('m1', xf)
        reg.infer('m2', xb)
    rewarm_misses = exec_cache.stats()['misses'] - before
    evictions = reg.stats()['evictions']
    reg.close()

    print(json.dumps({
        'metric': 'serve_fleet',
        'value': round(slo['fast_p99_ms'], 3),
        'unit': 'ms_fast_tenant_p99',
        'passes': passes,
        'fast_deadline_ms': fast_deadline,
        'fast_p99_single_knob_ms': round(single['fast_p99_ms'], 3),
        'fast_p50_single_knob_ms': round(single['fast_p50_ms'], 3),
        'fast_p99_slo_ms': round(slo['fast_p99_ms'], 3),
        'fast_p50_slo_ms': round(slo['fast_p50_ms'], 3),
        'slo_met': bool(slo['fast_p99_ms'] <= fast_deadline),
        'single_knob_met': bool(
            single['fast_p99_ms'] <= fast_deadline),
        'global_wait_us': global_wait,
        'http_rps_single_knob': round(single['rps'], 2),
        'http_rps_slo': round(slo['rps'], 2),
        'cont_seqs_per_s': round(cont_sps, 2),
        'convoy_seqs_per_s': round(convoy_sps, 2),
        'cont_speedup': round(cont_sps / convoy_sps, 3)
        if convoy_sps else None,
        'cont_utilization': round(cont_st['utilization'], 3),
        'convoy_utilization': round(convoy_st['utilization'], 3),
        'cont_bit_parity': bool(cont_bit_parity),
        'cont_compiles_after_warmup':
            cont_st['compiles_after_warmup'],
        'chunk_slots': chunk_slots,
        'chunk_seq_len': chunk_len,
        'chunk_seqs_per_s': {str(k): round(v, 2)
                             for k, v in chunk_sps.items()},
        'chunk_speedup': round(chunk_sps[k_top] / chunk_sps[k_base], 3)
        if chunk_sps[k_base] else None,
        'chunk_bit_parity': bool(chunk_parity),
        'chunk_dispatches_per_tick_drop': round(
            top_st['ticks'] / top_st['chunks'], 2)
        if top_st['chunks'] else None,
        'chunk_boundary_wait_ms': top_st['boundary_wait_ms'],
        'chunk_lone_fast_path': bool(top_st['lone_fast_path']),
        'chunk_compiles_after_warmup':
            top_st['compiles_after_warmup'],
        'staged_seqs_per_s': round(staged_sps, 2),
        'staged_speedup_vs_serial': round(
            staged_sps / chunk_sps[k_top], 3)
        if chunk_sps[k_top] else None,
        'staged_bit_parity': bool(staged_parity),
        'staged_chunks': staged_st['staged_chunks'],
        'stage_overlap_ms': staged_st['stage_overlap_ms'],
        'staged_boundary_wait_ms': staged_st['boundary_wait_ms'],
        'staged_compiles_after_warmup':
            staged_st['compiles_after_warmup'],
        'auto_seqs_per_s': round(auto_sps, 2),
        'auto_bit_parity': bool(auto_parity),
        'auto_steady_k': auto_st['tick_chunk'],
        'auto_k_decisions': auto_st['auto_k_decisions'],
        'auto_tick_ms_ema': auto_st['tick_ms_ema'],
        'auto_deadline_ms': auto_deadline,
        'auto_compiles_after_warmup':
            auto_st['compiles_after_warmup'],
        'evict_rewarm_cycles': cycles,
        'evictions': evictions,
        'evict_rewarm_compiles': rewarm_misses,
    }))


def fleet_supervisor_bench():
    """BENCH_FLEET=1 + BENCH_FLEET_SUPERVISOR=1 (tools/serve_bench.py
    --fleet --supervisor): the localhost fault drill for the
    self-healing fleet (mxnet_tpu/fleet_supervisor.py) — one JSON line
    covering the ISSUE-11 acceptance claims:

      (a) **replica-death survival** — a BENCH_FLEET_SUP_REPLICAS
          (3) replica fleet under a closed-loop client load survives
          SIGKILL of one replica with ZERO lost accepted requests
          (the router retries to survivors; clients honor the
          429/Retry-After contract via post_with_backoff), and the
          supervisor respawns the replica within the grace window.
      (b) **canary auto-rollback** — a push with
          MXNET_TPU_FAULT_CANARY_DEGRADE_MS injected into the
          candidate arm auto-rolls back to the prior model, with the
          rollback visible in /statsz counters.

    Steady-state routed throughput is measured best-of
    BENCH_FLEET_SUP_PASSES (3) per the rig note; the kill and canary
    drills are pass/fail and run once each (they assert behavior, not
    speed).  Knobs: BENCH_FLEET_SUP_REPLICAS (3), _CLIENTS (2),
    _REQS (30 per client), _PASSES (3), _GRACE_S (60).
    """
    import shutil
    import signal as _signal
    import threading

    from mxnet_tpu import nd
    from mxnet_tpu import model as model_mod
    from mxnet_tpu.fleet_supervisor import (FleetSupervisor,
                                            post_with_backoff)

    sys.setswitchinterval(0.001)
    replicas = int(os.environ.get('BENCH_FLEET_SUP_REPLICAS', 3))
    clients = int(os.environ.get('BENCH_FLEET_SUP_CLIENTS', 2))
    reqs = int(os.environ.get('BENCH_FLEET_SUP_REQS', 30))
    passes = max(1, int(os.environ.get('BENCH_FLEET_SUP_PASSES', 3)))
    grace_s = float(os.environ.get('BENCH_FLEET_SUP_GRACE_S', 60))
    dim, hidden, out_dim = 32, 32, 8
    rng = np.random.RandomState(11)

    def mlp(seed):
        net = _serve_symbol(hidden, out_dim, dim)
        import mxnet_tpu as mx
        probe = net.simple_bind(mx.cpu(), grad_req='null',
                                data=(1, dim))
        rs = np.random.RandomState(seed)
        args = {k: nd.array(rs.randn(*v.shape).astype(np.float32) * .1)
                for k, v in probe.arg_dict.items() if k != 'data'}
        return net, args

    tmp = tempfile.mkdtemp(prefix='mxnet_tpu_fleet_sup_')
    sup = None
    try:
        net, args = mlp(1)
        prefix_a = os.path.join(tmp, 'stable')
        model_mod.save_checkpoint(prefix_a, 0, net, args, {})
        net2, args2 = mlp(2)
        prefix_b = os.path.join(tmp, 'candidate')
        model_mod.save_checkpoint(prefix_b, 0, net2, args2, {})

        # fast liveness for the drill; degrade pre-armed (it only
        # bites '@' canary arms, which exist only during the push)
        env = {'JAX_PLATFORMS': 'cpu',
               'MXNET_TPU_FAULT_CANARY_DEGRADE_MS': '100'}
        os.environ['MXNET_TPU_FLEET_HEARTBEAT_S'] = '0.25'
        os.environ['MXNET_TPU_FLEET_DEAD_AFTER_S'] = '1.5'
        os.environ['MXNET_TPU_FLEET_CANARY_MIN_SAMPLES'] = '8'
        sup = FleetSupervisor(
            models=[{'name': 'm', 'prefix': prefix_a, 'epoch': 0,
                     'input_shapes': {'data': [1, dim]},
                     'max_batch': 8, 'max_wait_us': 0,
                     'deadline_ms': 5000}],
            replicas=replicas, env=env)
        t0 = time.time()
        sup.start()
        sup.wait_healthy()
        boot_s = time.time() - t0
        host, port = sup.router.address
        url = 'http://%s:%d/v1/models/m:predict' % (host, port)
        x = rng.randn(1, dim).astype(np.float32).tolist()

        def drive(n, failures, latencies=None):
            for _ in range(n):
                t1 = time.perf_counter()
                try:
                    st, _ = post_with_backoff(url, {'instances': x},
                                              deadline_s=30)
                    if st != 200:
                        failures.append(st)
                except Exception as e:
                    failures.append(repr(e))
                if latencies is not None:
                    latencies.append(
                        (time.perf_counter() - t1) * 1e3)

        # steady-state routed throughput, best-of-N passes
        best_rps = 0.0
        for _ in range(passes):
            failures = []
            ts = [threading.Thread(target=drive,
                                   args=(reqs, failures))
                  for _ in range(clients)]
            tic = time.time()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.time() - tic
            if failures:
                raise RuntimeError('steady-state failures: %r'
                                   % failures[:3])
            best_rps = max(best_rps, clients * reqs / dt)

        # (a) kill drill: SIGKILL one replica mid-load; every accepted
        # request must still complete (router retry + client backoff)
        failures = []
        lats = []
        ts = [threading.Thread(target=drive,
                               args=(reqs, failures, lats))
              for _ in range(clients)]
        for t in ts:
            t.start()
        time.sleep(0.2)
        victim = sup.replicas()[0]
        victim.proc.send_signal(_signal.SIGKILL)
        t_kill = time.time()
        for t in ts:
            t.join()
        lost = len(failures)
        respawn_s = None
        deadline = time.time() + grace_s
        while time.time() < deadline:
            live = sup.replicas()
            if len(live) >= replicas and all(sup._probe(r)
                                             for r in live):
                respawn_s = time.time() - t_kill
                break
            time.sleep(0.1)
        restarts = sup.stats()['restarts']

        # (b) canary push with degraded candidate -> auto-rollback,
        # observed through the public /statsz endpoint
        sup.push('m', prefix_b, epoch=0, frac=0.5)
        rollback_seen = False
        deadline = time.time() + grace_s
        while time.time() < deadline and not rollback_seen:
            failures2 = []
            drive(8, failures2)
            import urllib.request
            st = json.loads(urllib.request.urlopen(
                'http://%s:%d/statsz' % (host, port),
                timeout=30).read())
            fs = st['fleet_supervisor']
            rollback_seen = \
                fs['fleet_supervisor_canary_rollbacks'] >= 1 and \
                st['canary']['m']['state'] == 'rolled_back'
        stable_after = sup.router.stable_arm('m')
        router_stats = sup.router.stats()
        sup.stop()

        print(json.dumps({
            'metric': 'fleet_supervisor',
            'value': round(respawn_s, 3) if respawn_s else None,
            'unit': 's_respawn_after_sigkill',
            'replicas': replicas,
            'passes': passes,
            'boot_s': round(boot_s, 3),
            'rps_routed_best': round(best_rps, 2),
            'kill_drill_lost_accepted': lost,
            'kill_drill_p99_ms': round(float(np.percentile(lats, 99)),
                                       3) if lats else None,
            'supervisor_restarts': restarts,
            'router_retries': router_stats['retries'],
            'router_503': router_stats['unavailable_503'],
            'canary_rollback_in_statsz': bool(rollback_seen),
            'stable_arm_after_rollback': stable_after,
            'survived': bool(lost == 0 and respawn_s is not None and
                             rollback_seen and stable_after == 'm'),
        }))
        if lost or respawn_s is None or not rollback_seen or \
                stable_after != 'm':
            raise SystemExit('fleet supervisor drill FAILED: lost=%d '
                             'respawn=%s rollback=%s stable=%r'
                             % (lost, respawn_s, rollback_seen,
                                stable_after))
    finally:
        # a failed drill must not orphan the replica PROCESSES (they
        # outlive this bench process and keep burning the rig's cores)
        if sup is not None:
            try:
                sup.stop()              # idempotent
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def loop_bench():
    """BENCH_LOOP=1 (tools/bench_family.py --loop): the diurnal
    autoscale drill (ISSUE-14 / PERF round 18) — replay an OPEN-LOOP
    diurnal request trace through a REAL localhost fleet under
    ScalePolicy autoscaling, measuring what the tier-1 synthetic
    ScalePolicy tests cannot:

      * **scale-up lag** — seconds from load onset (morning-ramp
        start) to the first live-replica increase, paid in real
        replica boot time (subprocess spawn + model warm);
      * **scale-down flap count** — direction changes of the
        live-replica timeline beyond the ideal one-up-one-down cycle
        (the hysteresis knobs exist to keep this 0);
      * **peak shed rate** — the fraction of peak-phase requests
        answered 429/503/transport-failure.  Open loop: requests fire
        on schedule regardless of completion — the arrival process
        does not slow down because the fleet is saturated, which is
        exactly what makes shedding measurable.

    Trace: night (base rps) -> morning ramp (base->peak) -> midday
    peak -> evening ramp (peak->base) -> night (idle, so the
    scale-down path runs).  Knobs: BENCH_LOOP_BASE_RPS (3),
    BENCH_LOOP_PEAK_RPS (40), BENCH_LOOP_PHASE_S (8; peak runs 1.5x,
    final night 2x), BENCH_LOOP_REPLICAS (1 initial; max 3),
    BENCH_LOOP_POOL (24 client threads).
    """
    import shutil
    import threading
    from queue import Queue, Empty

    from mxnet_tpu import nd
    from mxnet_tpu import model as model_mod
    from mxnet_tpu import fleet_supervisor as fsup
    from mxnet_tpu.fleet_supervisor import FleetSupervisor, ScalePolicy

    sys.setswitchinterval(0.001)
    base_rps = float(os.environ.get('BENCH_LOOP_BASE_RPS', 3))
    peak_rps = float(os.environ.get('BENCH_LOOP_PEAK_RPS', 40))
    phase_s = float(os.environ.get('BENCH_LOOP_PHASE_S', 8))
    replicas = int(os.environ.get('BENCH_LOOP_REPLICAS', 1))
    pool_n = int(os.environ.get('BENCH_LOOP_POOL', 24))
    dim, hidden, out_dim = 32, 32, 8
    rng = np.random.RandomState(7)

    tmp = tempfile.mkdtemp(prefix='mxnet_tpu_loop_')
    sup = None
    try:
        net = _serve_symbol(hidden, out_dim, dim)
        import mxnet_tpu as mx
        probe = net.simple_bind(mx.cpu(), grad_req='null',
                                data=(1, dim))
        args = {k: nd.array(rng.randn(*v.shape).astype(np.float32)
                            * .1)
                for k, v in probe.arg_dict.items() if k != 'data'}
        prefix = os.path.join(tmp, 'diurnal_m')
        model_mod.save_checkpoint(prefix, 0, net, args, {})

        os.environ['MXNET_TPU_FLEET_HEARTBEAT_S'] = '0.25'
        os.environ['MXNET_TPU_FLEET_DEAD_AFTER_S'] = '1.5'
        sup = FleetSupervisor(
            models=[{'name': 'm', 'prefix': prefix, 'epoch': 0,
                     'input_shapes': {'data': [1, dim]},
                     'max_batch': 8, 'max_wait_us': 0,
                     'deadline_ms': 60}],
            replicas=replicas, min_replicas=replicas, max_replicas=3,
            autoscale=True,
            scale_policy=ScalePolicy(up_after=2, down_after=8,
                                     backlog_hot=16),
            env={'JAX_PLATFORMS': 'cpu'})
        t0 = time.time()
        sup.start()
        sup.wait_healthy()
        boot_s = time.time() - t0
        host, port = sup.router.address
        x = rng.randn(1, dim).astype(np.float32).tolist()
        payload = {'instances': x}

        # live-replica timeline sampler (0.25s cadence)
        timeline = []
        stop_sampling = threading.Event()

        def sampler():
            while not stop_sampling.is_set():
                timeline.append((time.monotonic(),
                                 sup.live_replicas()))
                stop_sampling.wait(0.25)

        smp = threading.Thread(target=sampler, daemon=True)
        smp.start()

        # open-loop firing through a bounded worker pool; per-phase
        # outcome buckets
        results = {}            # phase -> {'ok': n, 'shed': n}
        res_lock = threading.Lock()
        jobs = Queue()
        done_firing = threading.Event()

        def worker():
            while not (done_firing.is_set() and jobs.empty()):
                try:
                    phase = jobs.get(timeout=0.2)
                except Empty:
                    continue
                try:
                    status, _h, _b = fsup._http_json(
                        'POST', host, port, '/v1/models/m:predict',
                        payload, timeout=3.0)
                    ok = status == 200
                except Exception:
                    ok = False
                with res_lock:
                    d = results.setdefault(phase,
                                           {'ok': 0, 'shed': 0})
                    d['ok' if ok else 'shed'] += 1

        workers = [threading.Thread(target=worker, daemon=True)
                   for _ in range(pool_n)]
        for w in workers:
            w.start()

        def rate_at(phase, frac):
            if phase == 'night':
                return base_rps
            if phase == 'ramp_up':
                return base_rps + frac * (peak_rps - base_rps)
            if phase == 'peak':
                return peak_rps
            if phase == 'ramp_down':
                return peak_rps - frac * (peak_rps - base_rps)
            return 0.0                  # night2: idle -> scale-down

        phases = [('night', phase_s), ('ramp_up', phase_s),
                  ('peak', 1.5 * phase_s), ('ramp_down', phase_s),
                  ('night2', 2.0 * phase_s)]
        marks = {}
        for phase, dur in phases:
            marks[phase] = time.monotonic()
            t_phase0 = time.monotonic()
            while True:
                el = time.monotonic() - t_phase0
                if el >= dur:
                    break
                r = rate_at(phase, el / dur)
                if r <= 0:
                    time.sleep(min(0.25, dur - el))
                    continue
                jobs.put(phase)
                time.sleep(1.0 / r)
        marks['end'] = time.monotonic()
        done_firing.set()
        for w in workers:
            w.join(timeout=30)
        stop_sampling.set()
        smp.join(timeout=5)

        # scale-up lag: load onset (ramp start) -> first live increase
        scale_up_lag = None
        for t, n in timeline:
            if t >= marks['ramp_up'] and n > replicas:
                scale_up_lag = t - marks['ramp_up']
                break
        # flaps: direction changes of the replica-count series beyond
        # the ideal single up-then-down cycle
        deltas = [b[1] - a[1] for a, b in zip(timeline, timeline[1:])
                  if b[1] != a[1]]
        changes = 1 if deltas else 0
        for a, b in zip(deltas, deltas[1:]):
            if (a > 0) != (b > 0):
                changes += 1
        flaps = max(0, changes - 2)
        peak = results.get('peak', {'ok': 0, 'shed': 0})
        peak_total = peak['ok'] + peak['shed']
        shed_rate = peak['shed'] / peak_total if peak_total else None
        sup_stats = sup.stats()
        max_live = max((n for _t, n in timeline), default=replicas)
        final_live = timeline[-1][1] if timeline else replicas
        sup.stop()

        print(json.dumps({
            'metric': 'loop_autoscale_drill',
            'value': round(scale_up_lag, 3)
            if scale_up_lag is not None else None,
            'unit': 's_scale_up_lag',
            'boot_s': round(boot_s, 3),
            'trace': {'base_rps': base_rps, 'peak_rps': peak_rps,
                      'phase_s': phase_s},
            'replicas_initial': replicas,
            'replicas_peak': max_live,
            'replicas_final': final_live,
            'scale_down_flaps': flaps,
            'peak_requests': peak_total,
            'peak_shed_rate': round(shed_rate, 4)
            if shed_rate is not None else None,
            'per_phase': {p: results.get(p, {'ok': 0, 'shed': 0})
                          for p, _d in phases},
            'retired': sup_stats['retired'],
            'survived': bool(scale_up_lag is not None and
                             max_live > replicas),
        }))
        if scale_up_lag is None or max_live <= replicas:
            raise SystemExit('loop autoscale drill FAILED: fleet '
                             'never scaled up under the peak '
                             '(timeline %r)' % timeline[-10:])
    finally:
        if sup is not None:
            try:
                sup.stop()              # idempotent
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# BENCH_INT8=1: the low-precision stack (PERF round 17) — int8 serving,
# quantized registry residency, allreduce wire-format A/B
# ---------------------------------------------------------------------------

def _int8_wire_child():
    """Worker body of the wire A/B (spawned 2x under tools/launch.py
    with BENCH_INT8_WIRE_CHILD=1): bootstrap the dist runtime, train a
    tiny MLP with a dist_sync kvstore (every step's gradients cross
    ranks through dist.allreduce, riding whatever
    MXNET_TPU_DIST_WIRE_DTYPE the parent set), and print rank 0's loss
    curve + the wire counters as one tagged JSON line."""
    import mxnet_tpu as mx
    from mxnet_tpu import dist, profiler
    from mxnet_tpu import sym as S

    rt = dist.initialize()
    steps = int(os.environ.get('BENCH_INT8_WIRE_STEPS', 12))
    bsz, dim, classes = 32, 16, 4
    data = S.Variable('data')
    h = S.Activation(S.FullyConnected(data, name='fc1', num_hidden=32),
                     act_type='relu')
    net = S.SoftmaxOutput(S.FullyConnected(h, name='fc2',
                                           num_hidden=classes),
                          name='softmax')
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (bsz, dim))],
             label_shapes=[mx.io.DataDesc('softmax_label', (bsz,))])
    mx.random.seed(7)
    mod.init_params(initializer=mx.init.Xavier())
    kv = mx.kvstore.create('dist_sync')
    mod.init_optimizer(kvstore=kv, optimizer='sgd',
                       optimizer_params={'learning_rate': 0.5,
                                         'momentum': 0.9})
    feed = np.random.RandomState(100 + rt.rank)   # per-rank dp shard
    losses = []
    for _ in range(steps):
        x = feed.rand(bsz, dim).astype(np.float32)
        y = (feed.rand(bsz) * classes).astype(np.float32)
        batch = mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(y)])
        mod.forward_backward(batch)
        mod.update()
        mod.forward(batch, is_train=False)
        p = mod.get_outputs()[0].asnumpy()
        losses.append(float(-np.log(np.clip(
            p[np.arange(bsz), y.astype(int)], 1e-9, 1.0)).mean()))
    kv.barrier()
    if rt.rank == 0:
        ds = profiler.dist_stats()
        qs = profiler.quant_stats()
        print('INT8WIRE ' + json.dumps({
            'losses': losses,
            'allreduce_bytes': ds['dist_allreduce_bytes'],
            'allreduce_rounds': ds['dist_allreduce_rounds'],
            'wire_bytes_saved': qs['quant_wire_bytes_saved'],
            'ef_norm': qs['quant_error_feedback_norm'],
        }), flush=True)
    rt.shutdown()


def int8_bench():
    """BENCH_INT8=1: measure the low-precision stack
    (mxnet_tpu/quantization.py + the serving/registry/dist arms) and
    emit ONE JSON line covering the three acceptance claims:

      (a) **int8 serving** — the same closed client loop against an fp
          engine and a weight-quantized int8 engine (same weights,
          parity-gated at build), best-of-BENCH_INT8_PASSES; plus the
          REGISTRY THRASH arm: two models alternating traffic under a
          byte budget that fits one fp model — the fp ladder pays an
          evict+reload per alternation while both int8 models stay
          resident, which is the serving throughput quantized
          residency actually buys.  NOTE on reading the single-model
          numbers on this rig: XLA:CPU has no int8 compute units (an
          s8 dot lowers to a scalar loop measured 3-6x SLOWER than
          the Eigen f32 gemm), so the int8 engine dequantizes inline
          per dispatch and lands at parity-to-slightly-below fp
          per-dispatch speed — the wins it buys are bytes (residency,
          paging, wire), which the thrash/residency arms measure.  On
          accelerator backends the same weight-storage mode saves HBM
          and the convert rides the gemm's bandwidth headroom.
      (b) **quantized registry residency** — BENCH_INT8_MODELS int8
          models under the one-fp-model budget: all resident at once
          (>= 2x the fp arm's count), evict/re-warm cycles at ZERO
          exec_cache compiles.
      (c) **allreduce wire A/B** — two launcher-spawned workers train
          the same MLP under fp32 vs int8 wire
          (MXNET_TPU_DIST_WIRE_DTYPE): loss curves must agree within
          BENCH_INT8_WIRE_TOL (error feedback carries the
          quantization error across steps), the int8 run repeated
          must be BITWISE identical (per-mode determinism), and the
          measured wire bytes must drop ~4x.

    Knobs: BENCH_INT8_PASSES (3), BENCH_INT8_CLIENTS (4),
    BENCH_INT8_REQS (50/client), BENCH_INT8_DIM / _HIDDEN (256/256),
    BENCH_INT8_MODELS (3), BENCH_INT8_ALTERNATIONS (24),
    BENCH_INT8_WIRE_STEPS (12), BENCH_INT8_WIRE_TOL (0.05).
    """
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache, nd
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving_fleet import ModelRegistry

    sys.setswitchinterval(0.001)
    # the fp BASELINE arms must actually be fp: an inherited
    # fleet-wide quantize default would silently turn the A/B into
    # int8-vs-int8 (the arms pass quantize= explicitly where wanted)
    os.environ.pop('MXNET_TPU_SERVE_QUANTIZE', None)
    passes = max(1, int(os.environ.get('BENCH_INT8_PASSES', 3)))
    clients = int(os.environ.get('BENCH_INT8_CLIENTS', 4))
    reqs_per_client = int(os.environ.get('BENCH_INT8_REQS', 50))
    dim = int(os.environ.get('BENCH_INT8_DIM', 256))
    hidden = int(os.environ.get('BENCH_INT8_HIDDEN', 256))
    n_models = int(os.environ.get('BENCH_INT8_MODELS', 3))
    alts = int(os.environ.get('BENCH_INT8_ALTERNATIONS', 24))
    wire_tol = float(os.environ.get('BENCH_INT8_WIRE_TOL', 0.05))

    rng = np.random.RandomState(11)
    net = _serve_symbol(hidden, 16, dim)
    probe = net.simple_bind(mx.cpu(), grad_req='null', data=(1, dim))
    base_args = {k: rng.randn(*v.shape).astype(np.float32) * 0.1
                 for k, v in probe.arg_dict.items() if k != 'data'}

    def loader():
        return Predictor(symbol=net,
                         arg_params={k: nd.array(v)
                                     for k, v in base_args.items()},
                         input_shapes={'data': (1, dim)})

    n_total = clients * reqs_per_client
    requests = [rng.randn(1, dim).astype(np.float32)
                for _ in range(n_total)]

    def run_clients(serve_one):
        errors = []

        def client(c):
            try:
                for j in range(reqs_per_client):
                    serve_one(c * reqs_per_client + j)
            except Exception as e:
                errors.append(e)
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        tic = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return time.time() - tic

    # -- (a) single-model fp vs int8, same closed loop -----------------
    eng_fp = loader().serve(max_batch=clients, max_wait_us=1000)
    eng_q = loader().serve(max_batch=clients, max_wait_us=1000,
                           quantize='int8')
    fp_bytes = eng_fp.resident_bytes()
    q_bytes = eng_q.resident_bytes()
    parity = max(
        float(np.abs(eng_fp.predict(r) - eng_q.predict(r)).max())
        for r in requests[:8])
    fp_rps = q_rps = 0.0
    for _ in range(passes):               # interleaved best-of passes
        fp_rps = max(fp_rps, n_total / run_clients(
            lambda i: eng_fp.predict(requests[i])))
        q_rps = max(q_rps, n_total / run_clients(
            lambda i: eng_q.predict(requests[i])))
    q_stats = eng_q.stats()
    eng_fp.close()
    eng_q.close()

    # -- (a2) registry thrash: 2 tenants vs a 1-fp-model budget --------
    budget = int(fp_bytes * 1.3)
    x1 = requests[0]

    def thrash(quantize, est):
        reg = ModelRegistry(budget_bytes=budget)
        for i in range(2):
            reg.register('t%d' % i, loader=loader, est_bytes=est,
                         max_batch=clients, max_wait_us=0,
                         **({'quantize': quantize} if quantize
                            else {}))
        best = 0.0
        for _ in range(passes):
            tic = time.time()
            for i in range(alts):
                reg.predict('t%d' % (i % 2), x1)
            best = max(best, alts / (time.time() - tic))
        st = reg.stats()
        reg.close()
        return best, st

    # est_bytes is the FP32-equivalent size for BOTH arms (register()
    # scales it by EST_BYTES_RATIO for the quantized one)
    thrash_fp_rps, fp_st = thrash(None, fp_bytes)
    thrash_q_rps, q_st = thrash('int8', fp_bytes)

    # -- (b) residency: n_models int8 tenants under the same budget ----
    reg = ModelRegistry(budget_bytes=budget)
    for i in range(n_models):
        reg.register('r%d' % i, loader=loader, est_bytes=fp_bytes,
                     max_batch=clients, max_wait_us=0,
                     quantize='int8')
    for i in range(n_models):
        reg.predict('r%d' % i, x1)
    res_st = reg.stats()
    resident_int8 = sum(1 for m in res_st['models'].values()
                        if m['resident'])
    c0 = exec_cache.stats()['total_compile_s']
    reg.evict('r0')
    reg.predict('r0', x1)
    rewarm_compile_s = exec_cache.stats()['total_compile_s'] - c0
    reg.close()

    # -- (c) allreduce wire A/B: 2 launcher-spawned workers ------------
    launch = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'tools', 'launch.py')

    def wire_run(wire):
        env = dict(os.environ, BENCH_INT8='1',
                   BENCH_INT8_WIRE_CHILD='1', JAX_PLATFORMS='cpu')
        for stale in ('DMLC_PS_ROOT_URI', 'DMLC_PS_ROOT_PORT',
                      'DMLC_ROLE', 'DMLC_NUM_WORKER',
                      'DMLC_NUM_SERVER', 'DMLC_WORKER_ID',
                      'MXNET_TPU_DIST_PORT'):
            env.pop(stale, None)
        if wire == 'fp32':
            env.pop('MXNET_TPU_DIST_WIRE_DTYPE', None)
        else:
            env['MXNET_TPU_DIST_WIRE_DTYPE'] = wire
        proc = subprocess.run(
            [sys.executable, launch, '-n', '2', '-s', '0',
             '--launcher', 'local', sys.executable,
             os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('wire child (%s) failed rc=%d'
                               % (wire, proc.returncode))
        for line in proc.stdout.splitlines():
            if line.startswith('INT8WIRE '):
                return json.loads(line[len('INT8WIRE '):])
        sys.stderr.write(proc.stderr)
        raise RuntimeError('wire child (%s) printed no INT8WIRE line'
                           % wire)

    wire_fp = wire_run('fp32')
    wire_q = wire_run('int8')
    wire_q2 = wire_run('int8')           # per-mode determinism
    loss_diff = max(abs(a - b) for a, b in zip(wire_fp['losses'],
                                               wire_q['losses']))
    wire_ratio = wire_fp['allreduce_bytes'] / \
        max(1, wire_q['allreduce_bytes'])

    print(json.dumps({
        'metric': 'int8_serving_throughput',
        'value': round(q_rps, 2),
        'unit': 'requests/sec',
        'fp_rps': round(fp_rps, 2),
        'int8_vs_fp': round(q_rps / fp_rps, 3),
        'parity_max_abs_diff': parity,
        'parity_gate_measured': q_stats['quantized']['parity_measured'],
        'parity_ok': bool(parity < 0.05),
        'resident_bytes_fp': fp_bytes,
        'resident_bytes_int8': q_bytes,
        'bytes_ratio': round(fp_bytes / q_bytes, 2),
        'compiles_after_warmup': q_stats['compiles_after_warmup'],
        'thrash_fp_rps': round(thrash_fp_rps, 2),
        'thrash_int8_rps': round(thrash_q_rps, 2),
        'thrash_speedup': round(thrash_q_rps / thrash_fp_rps, 2),
        'thrash_fp_loads': fp_st['loads'],
        'thrash_int8_loads': q_st['loads'],
        'budget_bytes': budget,
        'models_resident_int8': resident_int8,
        'models_resident_fp': 1,
        'rewarm_compile_s': round(rewarm_compile_s, 6),
        'wire_steps': len(wire_fp['losses']),
        'wire_loss_diff_max': round(loss_diff, 6),
        'wire_loss_ok': bool(loss_diff < wire_tol),
        'wire_bytes_fp32': wire_fp['allreduce_bytes'],
        'wire_bytes_int8': wire_q['allreduce_bytes'],
        'wire_bytes_ratio': round(wire_ratio, 2),
        'wire_bytes_saved': wire_q['wire_bytes_saved'],
        'wire_ef_norm': wire_q['ef_norm'],
        'wire_deterministic': bool(wire_q['losses'] ==
                                   wire_q2['losses']),
    }))


# ---------------------------------------------------------------------------
# BENCH_RING=1: cross-host gradient transport topologies (PERF round 23)
# — star coordinator vs p2p ring reduce-scatter, async overlap, COO wire
# ---------------------------------------------------------------------------

def _ring_bench_child():
    """Worker body of the topology A/B (spawned world× under
    tools/launch.py with BENCH_RING_CHILD=1): train the same tiny MLP
    through a dist_sync kvstore under whatever MXNET_TPU_DIST_TOPOLOGY
    / MXNET_TPU_DIST_OVERLAP the parent set, then run one embedding
    COO round against one densified dense round of the SAME gradient.
    EVERY rank prints its own counters as a tagged JSON line — the
    parent reconstructs rank-0 process ingress from them (under star,
    every rank's tx lands at the rank-0-process coordinator; under
    ring, only rank 0's own rx arrives there)."""
    import mxnet_tpu as mx
    from mxnet_tpu import dist, profiler
    from mxnet_tpu import sym as S

    rt = dist.initialize()
    steps = int(os.environ.get('BENCH_RING_STEPS', 12))
    bsz, dim, classes = 32, 16, 4
    data = S.Variable('data')
    h = S.Activation(S.FullyConnected(data, name='fc1', num_hidden=32),
                     act_type='relu')
    net = S.SoftmaxOutput(S.FullyConnected(h, name='fc2',
                                           num_hidden=classes),
                          name='softmax')
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[mx.io.DataDesc('data', (bsz, dim))],
             label_shapes=[mx.io.DataDesc('softmax_label', (bsz,))])
    mx.random.seed(7)
    mod.init_params(initializer=mx.init.Xavier())
    kv = mx.kvstore.create('dist_sync')
    mod.init_optimizer(kvstore=kv, optimizer='sgd',
                       optimizer_params={'learning_rate': 0.5,
                                         'momentum': 0.9})
    feed = np.random.RandomState(100 + rt.rank)   # per-rank dp shard
    losses = []
    tic = time.time()
    for _ in range(steps):
        x = feed.rand(bsz, dim).astype(np.float32)
        y = (feed.rand(bsz) * classes).astype(np.float32)
        batch = mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(y)])
        mod.forward_backward(batch)
        mod.update()
        mod.forward(batch, is_train=False)
        p = mod.get_outputs()[0].asnumpy()
        losses.append(float(-np.log(np.clip(
            p[np.arange(bsz), y.astype(int)], 1e-9, 1.0)).mean()))
    train_s = time.time() - tic
    kv.barrier()
    ds = dict(profiler.dist_stats())   # train-phase snapshot

    # -- embedding wire arm: COO round vs densified round, same grad --
    vocab = int(os.environ.get('BENCH_RING_VOCAB', 4096))
    edim = int(os.environ.get('BENCH_RING_EDIM', 16))
    touched = int(os.environ.get('BENCH_RING_TOUCHED', 64))
    rng = np.random.RandomState(500 + rt.rank)
    g = np.zeros((vocab, edim), np.float32)
    g[rng.randint(0, vocab, touched)] = \
        rng.randn(touched, edim).astype(np.float32)
    nz = np.flatnonzero(np.any(g != 0.0, axis=1))
    dist.allreduce_coo(nz, np.ascontiguousarray(g[nz]),
                       name='bench_coo', vocab=vocab)
    mid = dict(profiler.dist_stats())
    dist.allreduce([g], name='bench_dense')
    end = dict(profiler.dist_stats())
    coo_bytes = (mid['dist_tx_bytes'] + mid['dist_rx_bytes'] -
                 ds['dist_tx_bytes'] - ds['dist_rx_bytes'])
    dense_bytes = (end['dist_tx_bytes'] + end['dist_rx_bytes'] -
                   mid['dist_tx_bytes'] - mid['dist_rx_bytes'])
    # ONE os-level write: every rank shares the launcher's stdout pipe
    # and print()'s separate text/newline writes interleave under
    # contention (pipe writes under PIPE_BUF are atomic)
    sys.stdout.write('RINGBENCH ' + json.dumps({
        'rank': rt.rank,
        'world': rt.world,
        'losses': [round(v, 10) for v in losses],
        'train_s': round(train_s, 3),
        'tx_bytes': ds['dist_tx_bytes'],
        'rx_bytes': ds['dist_rx_bytes'],
        'star_bytes': ds['dist_star_bytes'],
        'ring_bytes': ds['dist_ring_bytes'],
        'overlap_ms': round(ds['dist_overlap_ms'], 3),
        'rounds': ds['dist_allreduce_rounds'],
        'coo_bytes': coo_bytes,
        'dense_bytes': dense_bytes,
    }) + '\n')
    sys.stdout.flush()
    kv.barrier()   # nobody tears the ring down mid-round
    rt.shutdown()


def ring_bench():
    """BENCH_RING=1: measure the cross-host gradient transport
    topologies (mxnet_tpu/dist.py ring reduce-scatter + all-gather,
    async overlap handles, sparse COO wire) and emit ONE JSON line
    covering the four acceptance claims of PERF round 23:

      (a) **rank-0 ingress** — under the star (coordinator) topology
          every rank's gradient upload lands in rank 0's process:
          ingress grows O(world x bytes).  Under the ring each rank
          receives only ~2x bytes x (world-1)/world from its left
          peer.  Both are reconstructed from the per-rank
          dist_tx/rx_bytes counters (counter-verified, not inferred)
          and the ratio must be >= (world-1)/2.
      (b) **per-mode bitwise determinism** — the ring arm AND the
          ring+overlap arm repeated must each reproduce their loss
          curve BIT-identically; star-vs-ring and ring-vs-overlap
          must agree within BENCH_RING_TOL (summation ORDER differs:
          star sums in rank order, the batched ring in per-chunk
          rotation order over one flattened buffer, the overlapped
          ring per key — at world 2 all three coincide bitwise).
      (c) **async overlap** — the ring+overlap arm must bank
          dist_overlap_ms > 0 (optimizer math for key k running while
          key k+1's bytes are on the wire) while keeping (b).
      (d) **embedding COO wire** — one sparse embedding gradient
          crossing as deduped (unique_ids, rows) COO must move >= 10x
          fewer bytes than the same gradient densified.

    Knobs: BENCH_RING_WORLD (3), BENCH_RING_STEPS (12),
    BENCH_RING_TOL (1e-3), BENCH_RING_VOCAB / _EDIM / _TOUCHED
    (4096 / 16 / 64).
    """
    world = int(os.environ.get('BENCH_RING_WORLD', 3))
    tol = float(os.environ.get('BENCH_RING_TOL', 1e-3))
    launch = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'tools', 'launch.py')

    def arm(topology, overlap=False):
        env = dict(os.environ, BENCH_RING='1', BENCH_RING_CHILD='1',
                   JAX_PLATFORMS='cpu')
        for stale in ('DMLC_PS_ROOT_URI', 'DMLC_PS_ROOT_PORT',
                      'DMLC_ROLE', 'DMLC_NUM_WORKER',
                      'DMLC_NUM_SERVER', 'DMLC_WORKER_ID',
                      'MXNET_TPU_DIST_PORT',
                      'MXNET_TPU_DIST_RING_PORT',
                      'MXNET_TPU_DIST_WIRE_DTYPE',
                      'MXNET_TPU_DIST_OVERLAP',
                      'MXNET_TPU_DIST_TOPOLOGY'):
            env.pop(stale, None)
        env['MXNET_TPU_DIST_TOPOLOGY'] = topology
        if overlap:
            env['MXNET_TPU_DIST_OVERLAP'] = '1'
        proc = subprocess.run(
            [sys.executable, launch, '-n', str(world), '-s', '0',
             '--launcher', 'local', sys.executable,
             os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('ring bench child (%s%s) failed rc=%d'
                               % (topology,
                                  '+overlap' if overlap else '',
                                  proc.returncode))
        ranks = {}
        for line in proc.stdout.splitlines():
            if line.startswith('RINGBENCH '):
                rec = json.loads(line[len('RINGBENCH '):])
                ranks[rec['rank']] = rec
        if sorted(ranks) != list(range(world)):
            sys.stderr.write(proc.stderr)
            raise RuntimeError('ring bench (%s): got rank lines %s, '
                               'expected %d ranks'
                               % (topology, sorted(ranks), world))
        return [ranks[r] for r in range(world)]

    star = arm('star')
    ring = arm('ring')
    ring2 = arm('ring')                   # per-mode determinism
    ringov = arm('ring', overlap=True)    # async overlap arm
    ringov2 = arm('ring', overlap=True)   # ...is a mode of its own

    # rank-0 PROCESS ingress: star pushes all land at the coordinator
    # (rank 0's process) — sum every rank's tx; ring peers talk p2p —
    # only rank 0's own rx arrives there
    star_ingress = sum(r['tx_bytes'] for r in star)
    ring_ingress = ring[0]['rx_bytes']
    ingress_ratio = star_ingress / max(1, ring_ingress)
    loss_diff = max(abs(a - b) for a, b in zip(star[0]['losses'],
                                               ring[0]['losses']))
    ov_diff = max(abs(a - b) for a, b in zip(ringov[0]['losses'],
                                             ring[0]['losses']))
    coo_ratio = ring[0]['dense_bytes'] / max(1, ring[0]['coo_bytes'])

    print(json.dumps({
        'metric': 'ring_rank0_ingress_ratio',
        'value': round(ingress_ratio, 2),
        'unit': 'star_bytes/ring_bytes',
        'world': world,
        'steps': len(ring[0]['losses']),
        'star_rank0_ingress_bytes': star_ingress,
        'ring_rank0_ingress_bytes': ring_ingress,
        'ingress_gate': round((world - 1) / 2.0, 2),
        'ingress_ok': bool(ingress_ratio >= (world - 1) / 2.0),
        'star_tx_per_rank': star[0]['tx_bytes'],
        'ring_tx_per_rank': ring[0]['tx_bytes'],
        'train_s_star': star[0]['train_s'],
        'train_s_ring': ring[0]['train_s'],
        'train_s_ring_overlap': ringov[0]['train_s'],
        'loss_diff_star_vs_ring': round(loss_diff, 9),
        'loss_parity_ok': bool(loss_diff < tol),
        'ring_deterministic': bool(ring[0]['losses'] ==
                                   ring2[0]['losses']),
        'overlap_deterministic': bool(ringov[0]['losses'] ==
                                      ringov2[0]['losses']),
        'loss_diff_ring_vs_overlap': round(ov_diff, 9),
        'overlap_parity_ok': bool(ov_diff < tol),
        'overlap_ms': ringov[0]['overlap_ms'],
        'overlap_ok': bool(ringov[0]['overlap_ms'] > 0),
        'coo_bytes': ring[0]['coo_bytes'],
        'dense_bytes': ring[0]['dense_bytes'],
        'coo_bytes_ratio': round(coo_ratio, 1),
        'coo_ok': bool(coo_ratio >= 10.0),
    }))


def is_oom(text):
    return 'RESOURCE_EXHAUSTED' in text or 'Out of memory' in text


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--no-exec-cache', action='store_true',
                        help='disable the in-process compiled-program '
                             'cache (sets MXNET_TPU_EXEC_CACHE=0; '
                             'A/B the cache overhead/benefit)')
    args = parser.parse_args()
    if args.no_exec_cache:
        os.environ['MXNET_TPU_EXEC_CACHE'] = '0'
    if os.environ.get('BENCH_INT8_WIRE_CHILD', '') == '1':
        _int8_wire_child()   # one rank of the wire A/B (under launch.py)
        return
    if os.environ.get('BENCH_RING_CHILD', '') == '1':
        _ring_bench_child()   # one rank of the topology A/B
        return
    if os.environ.get('BENCH_RING', '') == '1':
        ring_bench()   # star vs ring vs ring+overlap, COO wire arm
        return
    if os.environ.get('BENCH_INT8', '') == '1':
        int8_bench()   # low-precision stack: serving/registry/wire
        return
    if os.environ.get('BENCH_INFER', '') == 'serve':
        serve_bench()   # dynamic-batching inference engine bench
        return
    if os.environ.get('BENCH_LOOP', '') == '1':
        loop_bench()   # diurnal autoscale drill (train->serve loop)
        return
    if os.environ.get('BENCH_FLEET', '') == '1':
        if os.environ.get('BENCH_FLEET_SUPERVISOR', '') == '1':
            fleet_supervisor_bench()   # self-healing fleet fault drill
        else:
            fleet_bench()   # fleet tier: SLO / continuous / paging
        return
    if os.environ.get('BENCH_GLUON', '') == '1':
        gluon_bench()   # fused vs imperative Gluon training
        return
    if os.environ.get('BENCH_OVERLAP', '') == '1':
        overlap_bench()   # interleaved vs end-of-backward reduce
        return
    if os.environ.get('BENCH_BUCKET', '') == '1':
        bucket_bench()   # fused bucket ladder vs legacy per-bucket loop
        return
    if os.environ.get('BENCH_PIPE', '') == '1':
        pipe_bench()   # dp-only vs dp×pipe vs dp×pipe+ZeRO
        return
    if os.environ.get('BENCH_CKPT', '') == '1':
        ckpt_bench()   # async elastic checkpoint overhead A/B
        return
    if os.environ.get('BENCH_DELTA', '') == '1':
        delta_bench()   # incremental delta checkpoints + delta push
        return
    if os.environ.get('BENCH_EMBED', '') == '1':
        embed_bench()   # dense vs touched-rows-only embedding training
        return
    model = os.environ.get('BENCH_MODEL', 'resnet-50')
    if model not in K80_IMG_S:
        raise SystemExit('BENCH_MODEL must be one of %s'
                         % ', '.join(sorted(K80_IMG_S)))
    batch = int(os.environ.get('BENCH_BATCH',
                               DEFAULT_BATCH.get(model, 256)))
    steps = int(os.environ.get('BENCH_STEPS', 6))
    warmup = int(os.environ.get('BENCH_WARMUP', 2))
    bulk = int(os.environ.get('BENCH_BULK', 16))
    dtype = os.environ.get('BENCH_DTYPE', 'bfloat16')
    input_mode = os.environ.get('BENCH_INPUT', 'device')
    k80 = K80_IMG_S[model]
    try:
        res = run_symbol(make_symbol(model, dtype), batch, steps, warmup,
                         bulk, dtype, edge=IMAGE_EDGE.get(model, 224),
                         input_mode=input_mode)
    except Exception as e:
        if is_oom(str(e)):
            # the batch is part of the configuration: no retry at a
            # smaller one (a child process could not take the chip this
            # process holds anyway)
            raise RuntimeError(
                '%s does not fit at BENCH_BATCH=%d x BENCH_BULK=%d (%s); '
                'set a smaller BENCH_BATCH' % (model, batch, bulk, dtype)
            ) from e
        raise
    from mxnet_tpu import exec_cache, profiler
    cache_stats = profiler.exec_cache_stats()
    print(json.dumps({
        'metric': '%s_train_throughput_1chip' % model.replace('-', ''),
        'value': round(res['ips'], 2),
        'unit': 'images/sec',
        'vs_baseline': round(res['ips'] / k80, 3),
        'platform': res['platform'],
        'device_kind': res['device_kind'],
        'n_devices': res['n_devices'],
        'dtype': dtype,
        'batch': batch,
        'steps_per_dispatch': bulk,
        'input': input_mode,
        'cold_start_s': res['cold_start_s'],
        'compile_cache_dir': exec_cache.setup_persistent_cache(),
        'input_stall_ms_per_step': res['input_stall_ms_per_step'],
        'decode_workers': res['decode_workers'],
        'optimizer_state_bytes_per_device':
            res['optimizer_state_bytes_per_device'],
        'zero': res['zero'],
        'exec_cache': os.environ.get('MXNET_TPU_EXEC_CACHE', '1')
        not in ('0', ''),
        'total_compile_s': round(cache_stats['total_compile_s'], 3),
        'baseline': 'K80 fp32 %.0f img/s (BASELINE.md)' % k80,
    }))


if __name__ == '__main__':
    main()
