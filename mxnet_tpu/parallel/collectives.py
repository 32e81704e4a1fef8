"""Collective wrappers.

The reference's communication layer is a parameter server
(ps-lite ZPush/ZPull, SURVEY.md §2.4) plus a hand-rolled CUDA P2P
reduce (comm.h:222).  On TPU every one of those patterns is an XLA
collective over a named mesh axis; these wrappers exist so framework
code and user custom ops have one obvious place to call them from
inside shard_map/pjit-compiled code.

Also home of the backward-interleaved gradient-reduction plan
(`GradReducePlan`): instead of one end-of-backward reduce of every
gradient, gradients are grouped into a few contiguous buckets ordered
by backward AVAILABILITY (last layer's grads exist first) and each
bucket's collective is issued as soon as its members are produced —
XLA's latency-hiding scheduler then overlaps bucket i's collective
with bucket i+1's wgrad compute.  The packed bucket psum is
elementwise-identical to per-parameter reduces (a cross-replica sum
doesn't care about concatenation), so the two modes agree bitwise.

Env knobs (docs/PERF.md round 11):
  MXNET_TPU_INTERLEAVE_REDUCE=0  force the end-of-backward baseline
      (an optimization_barrier makes every wgrad complete before any
      reduce issues — the arm
      tests/test_overlap_fusion.py::test_interleaved_vs_end_parity_mesh
      holds the interleaved schedule to)
  MXNET_TPU_REDUCE_BUCKETS=N     exact bucket count (per dtype group)
  MXNET_TPU_ZERO_BUCKET_MB       bucket fill target otherwise (shared
      with the ZeRO-1 bucketing, parallel/zero.py)
"""
import os

import numpy as np

import jax
from jax import lax


def allreduce_sum(x, axis_name):
    """Gradient aggregation (the role of ps-lite server merge +
    CommDevice tree reduce)."""
    return lax.psum(x, axis_name)


def allreduce_mean(x, axis_name):
    return lax.pmean(x, axis_name)


def allgather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=tiled)


def reduce_scatter_bucket(x, mesh, axis='data'):
    """GSPMD form of `reduce_scatter` for code compiled under plain
    `jax.jit` (no shard_map region, so the per-device partial sums are
    never exposed as named-axis values): constraining the summed array
    to be SHARDED over the dp axis makes XLA's partitioner lower the
    cross-replica sum as a psum_scatter instead of a full all-reduce —
    each device keeps only its 1/N shard.  Identity when no mesh is
    active (dp==1).  This is the ZeRO-1 gradient-sharding primitive
    (parallel/zero.py)."""
    if mesh is None:
        return x
    import jax
    from .mesh import flat_sharding
    return jax.lax.with_sharding_constraint(x, flat_sharding(mesh, axis))


def allgather_bucket(x, mesh):
    """GSPMD form of `allgather` under plain `jax.jit`: constraining a
    dp-sharded array back to replicated emits the all-gather.  Identity
    when no mesh is active.  ZeRO-1 parameter re-materialization
    (parallel/zero.py)."""
    if mesh is None:
        return x
    import jax
    from .mesh import replicated
    return jax.lax.with_sharding_constraint(x, replicated(mesh))


def allreduce_bucket(x, mesh):
    """GSPMD all-reduce under plain `jax.jit`: constraining a value
    whose partial sums live per-device (a gradient of replicated
    params w.r.t. a dp-sharded batch) to be REPLICATED makes XLA's
    partitioner lower the cross-replica sum as an all-reduce.  This is
    the fused Gluon step's gradient aggregation — the role of
    Trainer.step's per-parameter kvstore.push/pull, collapsed into the
    compiled step (identity when no mesh is active)."""
    return allgather_bucket(x, mesh)


def row_shard_constraint(x, mesh, axis='data'):
    """GSPMD row-striping constraint for big 2-D tables under plain
    `jax.jit`: pin dim 0 (the vocabulary rows) SHARDED over the dp
    axis so each device persistently holds ~1/N of the rows — the
    EncodeKey big-array striping of the reference's parameter server
    (SURVEY §2.4), expressed as a sharding constraint instead of
    key-chunking.  GSPMD handles a row count that does not divide the
    axis (last shard is short).  Identity when no mesh is active.
    parallel/embedding.py uses this on embedding tables and their
    momenta; like every constraint here it is its own transpose, so a
    table passing through it keeps its cotangent row-sharded too."""
    if mesh is None or axis not in mesh.axis_names or \
            int(mesh.shape[axis]) <= 1:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*([axis] + [None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def expert_shard(x, dim=0, axis='data'):
    """GSPMD expert-parallel constraint for plain-jit fused code
    (gluon.nn.MoE): shard `x`'s expert dimension over the ACTIVE
    mesh's dp axis (mesh.current_mesh — set by the fused trace paths
    via mesh.use_mesh), so XLA's partitioner places each device's
    expert slice locally and inserts the token all_to_alls itself —
    the Switch-style "expert axis aliases the data axis" layout.
    Identity when no mesh is active (single device, or a manual-axes
    shard_map trace) or when the expert count does not divide the
    axis."""
    from .mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return x
    n = int(mesh.shape[axis])
    if n <= 1 or x.shape[dim] % n:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*([None] * dim + [axis] + [None] * (x.ndim - dim - 1)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def replicate_constraint(x):
    """Pin `x` fully replicated on the ACTIVE mesh (identity when no
    mesh is active).  with_sharding_constraint is its own transpose,
    so this also pins the COTANGENT replicated — gluon.nn.MoE uses it
    on the expert weights so their gradients (and therefore the
    donated new-weight outputs) do not inherit the expert-sharded
    dispatch layout and drift the compiled program's input shardings
    between dispatches."""
    from .mesh import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P()))


def interleave_reduce_enabled(explicit=None):
    """Resolve the gradient-reduction schedule: an explicit API value
    wins, else MXNET_TPU_INTERLEAVE_REDUCE (default on — interleaved
    bucket-by-bucket reduces; 0 = one end-of-backward reduce)."""
    if explicit is not None:
        return bool(explicit)
    return os.environ.get('MXNET_TPU_INTERLEAVE_REDUCE', '1').strip() \
        not in ('0',)


def reduce_bucket_count():
    """MXNET_TPU_REDUCE_BUCKETS as an int, or None (fill buckets by
    the shared ZeRO bucket-MB target instead)."""
    v = os.environ.get('MXNET_TPU_REDUCE_BUCKETS', '').strip()
    if not v:
        return None
    n = int(v)
    if n < 1:
        raise ValueError('MXNET_TPU_REDUCE_BUCKETS must be >= 1, '
                         'got %d' % n)
    return n


def grad_barrier(grads):
    """Force every gradient to be computed before ANY use downstream:
    the end-of-backward baseline the interleaved schedule is measured
    against (and the historical behavior of one post-backward reduce).
    Identity on values; only the schedule changes."""
    grads = tuple(grads)
    if not grads:
        return []
    return list(lax.optimization_barrier(grads))


class GradReducePlan:
    """Static bucketing plan for in-step gradient all-reduce.

    Buckets are built over the REVERSED parameter order — the backward
    pass produces the last layer's wgrads first, so the bucket holding
    them closes (and its collective issues) while earlier layers'
    wgrads are still computing.  Same-dtype runs concatenate into flat
    buffers (one collective per bucket instead of one per parameter);
    a dtype change always closes the current bucket.

    `key` is the hashable identity joining the compiled-program cache
    key (exec_cache) so programs built under different bucketings or
    schedules never alias.
    """

    def __init__(self, shapes, dtypes, max_bytes=None, n_buckets=None,
                 interleave=None):
        if max_bytes is None:
            from . import zero as zero_mod
            max_bytes = zero_mod.bucket_bytes()
        if n_buckets is None:
            n_buckets = reduce_bucket_count()
        self.interleave = interleave_reduce_enabled(interleave)
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = [np.dtype(d) for d in dtypes]
        sizes = [int(np.prod(s)) if len(s) else 1 for s in self.shapes]
        rev = list(range(len(shapes)))[::-1]
        if n_buckets is not None:
            # exact bucket count: split the reversed order into
            # n roughly-equal-bytes chunks (dtype changes still split)
            total = sum(sizes[i] * self.dtypes[i].itemsize for i in rev)
            target = max(1, -(-total // n_buckets))
        else:
            target = max_bytes
        buckets = []
        cur, cur_bytes, cur_dt = [], 0, None
        for i in rev:
            nbytes = sizes[i] * self.dtypes[i].itemsize
            if cur and (self.dtypes[i] != cur_dt or
                        cur_bytes >= target):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
            cur_dt = self.dtypes[i]
        if cur:
            buckets.append(cur)
        self.buckets = buckets
        self.key = ('gradreduce', self.interleave,
                    tuple(tuple(b) for b in buckets),
                    tuple((s, dt.str)
                          for s, dt in zip(self.shapes, self.dtypes)))

    @property
    def n_buckets(self):
        return len(self.buckets)

    def apply(self, grads, mesh):
        """All-reduce `grads` (list aligned with the plan's parameter
        order) across `mesh` bucket-by-bucket.  Under the
        end-of-backward schedule (interleave off) a barrier first makes
        every wgrad complete before any collective issues.  Identity
        when no mesh is active.  Values are bitwise-identical across
        schedules and to per-parameter reduces."""
        if mesh is None:
            return list(grads)
        grads = list(grads)
        if not self.interleave:
            grads = grad_barrier(grads)
        import jax.numpy as jnp
        out = list(grads)
        for b in self.buckets:
            if len(b) == 1:
                i = b[0]
                out[i] = allreduce_bucket(grads[i], mesh)
                continue
            flat = jnp.concatenate([jnp.reshape(grads[i], (-1,))
                                    for i in b])
            red = allreduce_bucket(flat, mesh)
            off = 0
            for i in b:
                n = int(np.prod(self.shapes[i])) \
                    if len(self.shapes[i]) else 1
                out[i] = jnp.reshape(red[off:off + n], self.shapes[i])
                off += n
        return out


def quantized_allreduce(x, axis_name):
    """Explicit int8-WIRE allreduce for shard_map code (PERF round
    17): each device quantizes its local partial to symmetric int8
    with its own per-device scale, all-gathers (codes + one f32
    scale per device — the only payload on the links), then
    dequantizes and sums locally in float32.  Every device sums the
    identical gathered bytes in axis-index order, so the result is
    BITWISE identical across devices (per-mode determinism, like the
    host-level dist.allreduce wire).

    Why this exists as a shard_map primitive and NOT as a mode of the
    GSPMD bucket constraints (reduce_scatter_bucket /
    allreduce_bucket, used by the plain-jit fused train steps): under
    those, the per-device partial sums only exist INSIDE XLA's
    partitioner — user code sees the logical (already-summed) value,
    and quantization is nonlinear, so `quantize(sum(partials))`
    cannot be rewritten as `sum(quantize(partials))` without changing
    semantics.  The partitioner therefore must reduce in f32 BEFORE
    any quantize op we insert: the wire cannot be compressed from
    that layer.  Compressed gradient wire lives where per-device
    values are explicit — here (shard_map regions, e.g. a pipeline
    trainer's dp reduction) and on the host-level DCN leg
    (dist.allreduce wire='int8', which also carries error-feedback
    residuals across steps).

    Wire bytes per device: ~N x n/4 gathered vs an fp32 allreduce's
    ~2 x n — a net saving for axis sizes up to ~8; past that, prefer
    the reduce-then-broadcast shape of the host-level wire."""
    import jax.numpy as jnp
    from ..quantization import (INT8_RANGE, quantize_int8_math,
                                symmetric_scale)
    scale = symmetric_scale(x)
    q = quantize_int8_math(x, scale)
    qs = lax.all_gather(q, axis_name)                  # int8 wire
    ss = lax.all_gather(scale.astype(jnp.float32), axis_name)
    deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * x.ndim)
    return jnp.sum(deq, axis=0).astype(x.dtype)


def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    return lax.psum(1, axis_name)


def barrier_all_hosts(name='mxnet_tpu_barrier', timeout=None):
    """Host-level barrier (the reference's ps::Postoffice::Barrier role
    at bootstrap, kvstore_dist.h:56).  Under the dist runtime this is
    the coordinator's HEALTH-CHECKED barrier: it raises an MXNetError
    naming ranks that failed to arrive within `timeout` (default
    MXNET_TPU_BARRIER_TIMEOUT_S) or died while waiting, instead of
    hanging the collective."""
    from .. import dist
    rt = dist.runtime()
    if rt is not None:
        rt.barrier(name, timeout=timeout)
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)
