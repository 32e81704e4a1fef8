"""ZeRO stage-1: sharded optimizer update over the data-parallel axis.

Rajbhandari et al., "ZeRO: Memory Optimizations Toward Training
Trillion Parameter Models" (SC'20), stage 1 (P_os): instead of every
data-parallel replica all-reducing the full gradient and then running
the *identical* optimizer update against *fully replicated* momenta and
fp32 master weights, each device owns 1/N of the optimizer state —
gradients are reduce-scattered (same total wire bytes as the
all-reduce), the update math runs on the local 1/N shard only, and the
updated parameters are all-gathered back.  Optimizer-state and
master-weight memory drop by the dp degree; update FLOPs shard too.

How this maps onto the executor's GSPMD design: the fused train step is
ONE `jax.jit` program partitioned by XLA over the 'data' mesh axis —
there is no shard_map region exposing per-device partial gradients, so
the reduce-scatter cannot be written as an explicit `lax.psum_scatter`
(the partial sums only exist inside XLA's partitioner).  Instead the
step constrains the flattened gradient buckets to be SHARDED over the
dp axis (`collectives.reduce_scatter_bucket`): XLA's partitioner then
lowers the cross-replica sum directly as a reduce-scatter rather than
an all-reduce, and the replicated constraint on the updated bucket
(`collectives.allgather_bucket`) becomes the all-gather.  Optimizer
state buckets are *persistently* sharded (committed with a
`P('data')` NamedSharding) — that is the memory win.

Bucketing: tiny tensors must not each pay a collective (and padding to
the dp degree per-tensor would waste real memory), so parameters are
flattened and concatenated into a small number of contiguous 1-D
buckets (grouped by dtype/precision class, greedily filled up to
MXNET_TPU_ZERO_BUCKET_MB, each padded to a multiple of the dp degree).
The optimizer math is elementwise, so running it on a concatenated
bucket with per-element lr/wd vectors is exactly the per-parameter
math.

Env knobs (documented in docs/PERF.md round 7):
  MXNET_TPU_ZERO=1            enable the sharded update (default 0)
  MXNET_TPU_ZERO_BUCKET_MB=N  bucket fill target in MiB (default 32)

Wire formats (PERF round 17): the gradient buckets here already run
the narrowest wire the GSPMD layer can express — multi-precision
buckets all-gather updated params in the bf16 WEIGHT dtype (half the
fp32 bytes, see sharded_sgd_step).  An int8 bucket wire is NOT
expressible from this layer: the reduce-scatter is a sharding
constraint whose per-device partial sums exist only inside XLA's
partitioner, and quantization is nonlinear, so it cannot cross the
implicit sum (collectives.quantized_allreduce documents the
argument).  Compressed int8 gradient wire with per-bucket scales and
error-feedback therefore lives on the legs where per-device values
are explicit: `dist.allreduce(wire='int8')` for the cross-host DCN
leg (the ps-lite-era bandwidth cliff this attacks), and
`collectives.quantized_allreduce` for shard_map regions.
"""
import os

import numpy as np

DEFAULT_BUCKET_MB = 32.0


def zero_stage(explicit=None):
    """Resolve the ZeRO stage: an explicit API value wins, else the
    MXNET_TPU_ZERO env knob.  Only stages 0 (replicated) and 1
    (sharded optimizer state) exist."""
    if explicit is not None:
        stage = int(explicit)
    else:
        v = os.environ.get('MXNET_TPU_ZERO', '0').strip()
        stage = 0 if v in ('', '0') else int(v)
    if stage not in (0, 1):
        raise ValueError('MXNET_TPU_ZERO must be 0 or 1 (ZeRO stage-1 '
                         'optimizer-state sharding), got %r' % stage)
    return stage


def bucket_bytes():
    """Bucket fill target in bytes (MXNET_TPU_ZERO_BUCKET_MB)."""
    try:
        mb = float(os.environ.get('MXNET_TPU_ZERO_BUCKET_MB',
                                  str(DEFAULT_BUCKET_MB)))
    except ValueError:
        mb = DEFAULT_BUCKET_MB
    return max(1, int(mb * (1 << 20)))


class _Bucket:
    """One contiguous flat buffer: a run of same-precision-class params
    concatenated, padded to a multiple of the dp degree."""

    __slots__ = ('index', 'param_idx', 'sizes', 'shapes', 'offsets',
                 'w_dtype', 'acc_dtype', 'mp', 'size', 'padded')

    def __init__(self, index, w_dtype, acc_dtype, mp):
        self.index = index
        self.param_idx = []
        self.sizes = []
        self.shapes = []
        self.offsets = []
        self.w_dtype = w_dtype
        self.acc_dtype = acc_dtype
        self.mp = mp
        self.size = 0
        self.padded = 0


class ZeroBucketLayout:
    """Static flatten-and-bucket plan for one parameter list.

    Derived deterministically from (shapes, dtypes, mp flags, dp degree,
    bucket byte target); `key` is the hashable identity that joins the
    compiled-program cache key (exec_cache) so sharded and replicated
    step programs — or two different bucketings — never alias."""

    def __init__(self, shapes, dtypes, mp_flags, dp, max_bytes=None):
        if max_bytes is None:
            max_bytes = bucket_bytes()
        self.dp = max(1, int(dp))
        self.n_params = len(shapes)
        self.buckets = []
        open_buckets = {}       # (dtype str, mp) -> bucket being filled
        for i, (shape, dtype, mp) in enumerate(zip(shapes, dtypes,
                                                   mp_flags)):
            w_dt = np.dtype(dtype)
            acc_dt = np.dtype(np.float32) if mp else w_dt
            gkey = (w_dt.str, bool(mp))
            b = open_buckets.get(gkey)
            size = int(np.prod(shape)) if len(shape) else 1
            if b is None or b.size * acc_dt.itemsize >= max_bytes:
                b = _Bucket(len(self.buckets), w_dt, acc_dt, bool(mp))
                self.buckets.append(b)
                open_buckets[gkey] = b
            b.param_idx.append(i)
            b.offsets.append(b.size)
            b.sizes.append(size)
            b.shapes.append(tuple(shape))
            b.size += size
        for b in self.buckets:
            b.padded = -(-b.size // self.dp) * self.dp
        self.key = ('zero1', self.dp, tuple(
            (b.w_dtype.str, b.acc_dtype.str, b.mp, b.padded,
             tuple(b.param_idx), tuple(b.sizes))
            for b in self.buckets))

    # -- flat-buffer plumbing (traceable: shapes/dtypes are static) ----
    def pack(self, b, vals):
        """Concatenate per-param arrays into bucket `b`'s flat buffer in
        the accumulation dtype, zero-padded to the dp multiple."""
        import jax.numpy as jnp
        parts = [jnp.reshape(v, (-1,)).astype(b.acc_dtype) for v in vals]
        if b.padded > b.size:
            parts.append(jnp.zeros((b.padded - b.size,), b.acc_dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def pack_scalars(self, b, scalars):
        """Per-element vector of per-param scalars (lr/wd), built in the
        accumulation dtype, as the replicated path casts its traced
        scalars (optimizer.sgd_update_math)."""
        import jax.numpy as jnp
        parts = [jnp.full((n,), s, dtype=b.acc_dtype)
                 for s, n in zip(scalars, b.sizes)]
        if b.padded > b.size:
            parts.append(jnp.zeros((b.padded - b.size,), b.acc_dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def unpack(self, b, flat):
        """Split a full (gathered) bucket back into per-param views."""
        return [flat[o:o + n].reshape(shape)
                for o, n, shape in zip(b.offsets, b.sizes, b.shapes)]

    # -- accounting ----------------------------------------------------
    def state_bytes_per_device(self):
        """Optimizer-state bytes each device holds: its 1/dp bucket
        shard of the momenta plus (for multi-precision buckets) the
        fp32 masters."""
        total = 0
        for b in self.buckets:
            shard = b.padded // self.dp
            total += shard * b.acc_dtype.itemsize          # momentum
            if b.mp:
                total += shard * 4                          # fp32 master
        return total

    def comm_bytes_per_step(self):
        """Logical collective payload per training step:
        (bytes_reduce_scattered, bytes_all_gathered).  Zero when dp==1
        (no collective is emitted)."""
        if self.dp <= 1:
            return 0, 0
        rs = sum(b.padded * b.acc_dtype.itemsize for b in self.buckets)
        ag = sum(b.padded * b.w_dtype.itemsize for b in self.buckets)
        return rs, ag


def make_sharded_sgd_step(layout, mesh, hyper):
    """Bind `sharded_sgd_step` to a layout/mesh/hyper BY VALUE.  The
    executor caches compiled step programs keyed on the layout
    (FusedSGD.cache_key), so the traced function must capture the
    layout it was keyed under — not read a mutable attribute that a
    later param-list change may have rebuilt."""
    def step_math(ws, gs, moms, masters, lrs, wds):
        return sharded_sgd_step(layout, mesh, hyper, ws, gs, moms,
                                masters, lrs, wds)
    return step_math


def sharded_sgd_step(layout, mesh, hyper, ws, gs, moms, masters, lrs,
                     wds):
    """The ZeRO-1 whole-model SGD/NAG update (FusedSGD step_math body,
    sharded form).  ws/gs/lrs/wds are per-parameter (layout order);
    moms/masters are per-BUCKET flat shards.  Returns (new_ws,
    new_moms, new_masters) with new_ws per-parameter full arrays and
    the states still bucket-sharded.

    Elementwise-identical to FusedSGD's replicated step BY
    CONSTRUCTION: both call optimizer.sgd_update_math (one definition
    of the rescale/clip/wd/momentum core), here on concatenated 1-D
    buckets with per-element lr/wd vectors built in the accumulation
    dtype (as the replicated path casts its traced scalars).

    Reduction schedule: each gradient bucket's reduce-scatter issues as
    soon as its member wgrads exist (backward-interleaved — XLA's
    latency-hiding scheduler overlaps it with the remaining backward).
    hyper['interleave']=False (MXNET_TPU_INTERLEAVE_REDUCE=0) restores
    the end-of-backward baseline: an optimization_barrier makes every
    wgrad complete before any collective issues.  Values are identical
    either way; only the schedule changes."""
    from .collectives import (reduce_scatter_bucket, allgather_bucket,
                              grad_barrier)
    from ..optimizer import sgd_update_math

    if not hyper.get('interleave', True):
        gs = grad_barrier(gs)
    new_ws = [None] * len(ws)
    new_moms, new_masters = [], []
    for b in layout.buckets:
        # gradient bucket: the sharding constraint is the
        # reduce-scatter point (XLA lowers the dp-axis sum directly
        # into each device's shard)
        g = reduce_scatter_bucket(
            layout.pack(b, [gs[i] for i in b.param_idx]), mesh)
        if b.mp:
            # fp32 masters live permanently sharded — the memory win
            acc = masters[b.index]
        else:
            # replicated weight -> sharded view is a local slice
            # (no communication); the update runs on the shard only
            acc = reduce_scatter_bucket(
                layout.pack(b, [ws[i] for i in b.param_idx]), mesh)
        lr = layout.pack_scalars(b, [lrs[i] for i in b.param_idx])
        wd = layout.pack_scalars(b, [wds[i] for i in b.param_idx])
        acc, nm = sgd_update_math(
            acc, g, moms[b.index], lr, wd, momentum=hyper['momentum'],
            rescale=hyper['rescale'], clip=hyper['clip'],
            nesterov=hyper['nesterov'])
        new_moms.append(reduce_scatter_bucket(nm, mesh))
        if b.mp:
            new_masters.append(reduce_scatter_bucket(acc, mesh))
            # all-gather in the low-precision WEIGHT dtype (half the
            # wire bytes of gathering the fp32 master)
            full = allgather_bucket(acc.astype(b.w_dtype), mesh)
        else:
            new_masters.append(None)
            full = allgather_bucket(acc, mesh)
        for i, v in zip(b.param_idx, layout.unpack(b, full)):
            new_ws[i] = v
    return new_ws, new_moms, new_masters
