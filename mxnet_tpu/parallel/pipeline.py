"""Pipeline parallelism: GPipe-style microbatched stage execution over a
mesh axis.

No counterpart in the reference (MXNet 0.11's closest feature is
engine-async `PartialForward` overlap, SURVEY.md §2.4 item 5) — this is
the §7-step-9 new-design extension.  Each device along the 'pipe' axis
holds ONE stage's parameters; microbatches stream through the stages
with `lax.ppermute` hops over ICI inside a `lax.scan`, so the whole
pipeline schedule — warmup bubble, steady state, drain — is a single
XLA program.  Backward is plain autodiff: the transpose of ppermute is
ppermute with the inverse permutation, so XLA derives the reverse
schedule automatically.

Schedule: plain GPipe fill-drain over T = M + S - 1 ticks (M
microbatches, S stages).  Bubble fraction (S-1)/T shrinks as M grows —
pick M a few multiples of S.

Round 16 grew this module from a standalone primitive into the engine
behind the user-facing dp×pipe training mode (`Module.fit` /
`gluon.fuse_step` with `pipeline=(num_stages, num_micro)` or
MXNET_TPU_PIPE=stages,micro — see gluon/fused.py PipelinedStep and
module/pipeline_fit.py): `make_pipe_step_fn` composes the fill-drain
schedule with a stem (input-side params, applied by stage 0), a head
(output-side params + loss, applied by the last stage), the SGD/NAG
update (optimizer.sgd_update_math — ONE definition shared with every
other fused path), ZeRO-1 optimizer-state sharding over the dp axis of
the 2D mesh (explicit psum_scatter/all_gather inside shard_map, the
manual-axes form of parallel/zero.py's GSPMD constraints), and the
K-step bulk lax.scan — all of it ONE donated XLA dispatch.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def pipe_spec(explicit=None):
    """Resolve the pipeline mode: an explicit (num_stages, num_micro)
    pair wins, else the MXNET_TPU_PIPE env knob ('stages,micro').
    Returns (S, M) or None (pipelining off).  S >= 2 (a 1-stage
    pipeline is just data parallelism) and M >= 1."""
    if explicit is None:
        v = os.environ.get('MXNET_TPU_PIPE', '').strip()
        if not v or v == '0':
            return None
        parts = v.split(',')
        if len(parts) != 2:
            raise ValueError(
                "MXNET_TPU_PIPE must be 'stages,micro', got %r" % v)
        explicit = (int(parts[0]), int(parts[1]))
    s, m = int(explicit[0]), int(explicit[1])
    if s < 2:
        raise ValueError('pipeline needs >= 2 stages, got %d' % s)
    if m < 1:
        raise ValueError('pipeline needs >= 1 microbatch, got %d' % m)
    return (s, m)


def make_pipe_mesh(devices, num_stages, data_axis='data',
                   pipe_axis='pipe'):
    """The 2D dp×pipe mesh over `devices`: dp = n_devices / num_stages
    (must divide).  Device (d, s) holds stage s's parameters and the
    d-th dp slice of every microbatch."""
    from .mesh import make_mesh
    n = len(devices)
    if n % num_stages:
        raise ValueError(
            'pipeline: %d devices do not divide into %d stages'
            % (n, num_stages))
    return make_mesh({data_axis: n // num_stages,
                      pipe_axis: num_stages}, devices=devices)


def pipeline_run(stage_fn, params, microbatches, num_stages,
                 axis_name='pipe', ingest=None):
    """Run inside shard_map: stream microbatches through the stages.

    stage_fn(params, x) -> y: one stage's computation; every stage must
    map activations of the same shape/dtype.
    params: THIS stage's parameter pytree (leading 'pipe'-sharded dim of
    size 1 removed by the caller or kept — stage_fn decides).
    microbatches: (M, mb, ...) — only stage 0 reads them.
    ingest: optional callable(mb) -> activation applied to each raw
    microbatch before stage 0 consumes it (the STEM: input-side layers
    whose output shape is the pipeline's homogeneous activation shape).
    Every device traces the stem, but only stage 0's result enters the
    schedule — the `where` masks both the value and its cotangent, so
    stem gradients are nonzero on stage 0 only (callers psum them over
    the pipe axis).
    Returns (M, mb, ...act): stage S-1's outputs (garbage elsewhere).
    """
    idx = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + num_stages - 1
    perm = [(i, i + 1) for i in range(num_stages - 1)]

    if ingest is None:
        ingest = lambda mb: mb
    act0 = ingest(microbatches[0])
    state = jnp.zeros_like(act0)
    outputs = jnp.zeros((M,) + act0.shape, act0.dtype)

    def body(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped; out-of-range ticks feed
        # garbage that never reaches a valid output slot)
        mb = lax.dynamic_index_in_dim(microbatches,
                                      jnp.clip(t, 0, M - 1), 0,
                                      keepdims=False)
        inp = jnp.where(idx == 0, ingest(mb), state)
        out = stage_fn(params, inp)
        # last stage writes its result for microbatch (t - S + 1)
        oidx = jnp.clip(t - (num_stages - 1), 0, M - 1)
        valid = t >= (num_stages - 1)
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(o, out, oidx, 0),
            lambda o: o, outputs)
        state = lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(body, (state, outputs),
                                   jnp.arange(T))
    return outputs


def make_pipeline_train_step(stage_fn, loss_fn, mesh, num_micro,
                             axis_name='pipe', lr=0.1):
    """Compile a full pipeline-parallel training step.

    stage_fn(stage_params, x) -> y        (same activation shape in/out)
    loss_fn(y, targets) -> scalar         (applied on the LAST stage)

    Parameters are passed with a leading stage dim (S, ...) sharded over
    the pipe axis; inputs (B, ...) are split into `num_micro`
    microbatches and replicated to all stages (only stage 0 reads them).
    Returns jitted step(params, x, targets) -> (loss, new_params).
    """
    S = mesh.shape[axis_name]

    def step(params, x, targets):
        # shard_map gives this stage params[1, ...] -> drop stage dim
        sparams = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = lax.axis_index(axis_name)
        mb = x.shape[0] // num_micro
        micro = x.reshape((num_micro, mb) + x.shape[1:])
        tmicro = targets.reshape((num_micro, mb) + targets.shape[1:])

        def loss_of(sp):
            outs = pipeline_run(stage_fn, sp, micro, S, axis_name)
            # loss counts only on the last stage; other stages emit 0.
            # Do NOT psum inside the differentiated function: per-device
            # cotangent seeds of 1 already make this differentiate
            # sum_i(local_i) (earlier stages' grads arrive through the
            # ppermute transposes), and a psum here would scale every
            # gradient by the stage count.
            return jnp.where(
                idx == S - 1,
                loss_fn(outs.reshape((-1,) + outs.shape[2:]),
                        tmicro.reshape((-1,) + tmicro.shape[2:])),
                0.0)

        loss_local, grads = jax.value_and_grad(loss_of)(sparams)
        loss = lax.psum(loss_local, axis_name)   # reporting only
        new_sparams = jax.tree_util.tree_map(
            lambda w, g: w - lr * g, sparams, grads)
        new_params = jax.tree_util.tree_map(
            lambda p: p[None], new_sparams)
        return loss, new_params

    pspec = P(axis_name)
    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(pspec, P(), P()),
        out_specs=(P(), pspec),
        check_vma=False)

    return jax.jit(sharded, donate_argnums=(0,))


def bubble_fraction(num_stages, num_micro):
    """GPipe fill-drain bubble fraction: (S-1)/(M+S-1) of the schedule's
    ticks run below full stage occupancy."""
    return (num_stages - 1) / float(num_micro + num_stages - 1)


# ---------------------------------------------------------------------------
# shared engine plumbing for the two pipelined trainers
# (gluon/fused.PipelinedStep and module/pipeline_fit.ModulePipeTrainer
# — ONE definition each, so a fix cannot land in only one of them)
# ---------------------------------------------------------------------------

def check_stage_homogeneity(stage_traces, err):
    """Require every stage to trace the SAME abstract jaxpr as stage 0
    before a program runs stage 0's ops with every stage's weights —
    structural partition equality is necessary, not sufficient (two
    Dense(D) blocks with different activations match structurally).
    stage_traces: per-stage (fn, ws_sds, act_sds, rng_sds);
    err(stage_idx) -> the exception to raise on a mismatch."""
    import re
    fps = []
    for fn, ws_sds, act_sds, rng_sds in stage_traces:
        jaxpr = jax.make_jaxpr(fn)(ws_sds, act_sds, rng_sds)
        fps.append(re.sub(r'0x[0-9a-f]+', '0x', str(jaxpr)))
    for s, fp in enumerate(fps[1:], start=1):
        if fp != fps[0]:
            raise err(s)


def grouped_schedule_rows(opt, n_params, group_idx, k, err):
    """(k, n_leaf) float32 lr/wd schedule rows in leaf order: the
    update count bumps for EVERY parameter each step (host optimizer
    semantics); each stacked group must resolve to ONE lr/wd —
    err(sorted_lrs, sorted_wds) raises when a group's stage members
    diverge (per-stage lr_mult cannot share a stacked update)."""
    n_leaf = len(group_idx)
    k = max(1, int(k))
    lrs = np.empty((k, n_leaf), np.float32)
    wds = np.empty((k, n_leaf), np.float32)
    for s in range(k):
        per_lr, per_wd = {}, {}
        for i in range(n_params):
            opt._update_count(i)
            per_lr[i] = opt._get_lr(i)
            per_wd[i] = opt._get_wd(i)
        for j, idxs in enumerate(group_idx):
            glr = {per_lr[i] for i in idxs}
            gwd = {per_wd[i] for i in idxs}
            if len(glr) > 1 or len(gwd) > 1:
                raise err(sorted(glr), sorted(gwd))
            lrs[s, j] = glr.pop()
            wds[s, j] = gwd.pop()
    return lrs, wds


def init_pipe_opt_state(mesh, layout, num_stages, stage_ws, stem_ws,
                        head_ws):
    """Fresh momentum state for the pipelined update: per-bucket
    (S, padded) buffers sharded P('pipe', 'data') under ZeRO-1, else
    zeros mirroring each weight group's placement."""
    from .mesh import replicated
    if layout is not None:
        sh = NamedSharding(mesh, P('pipe', 'data'))
        return [jax.device_put(
            jnp.zeros((num_stages, b.padded), b.acc_dtype), sh)
            for b in layout.buckets]
    repl = replicated(mesh)
    pipe_sh = NamedSharding(mesh, P('pipe'))
    return (
        [jax.device_put(jnp.zeros(w.shape, w.dtype), pipe_sh)
         for w in stage_ws],
        [jax.device_put(jnp.zeros(w.shape, w.dtype), repl)
         for w in stem_ws],
        [jax.device_put(jnp.zeros(w.shape, w.dtype), repl)
         for w in head_ws])


def pipe_residency(local_shapes, local_dts, layout):
    """(param_bytes, opt_state_bytes) resident PER DEVICE from the
    local leaf shapes [stage (stage dim dropped)..., stem..., head...];
    replicated momenta mirror the weights, ZeRO momenta report the
    layout's sharded bucket bytes."""
    param_b = sum(int(np.prod(s)) * np.dtype(dt).itemsize
                  for s, dt in zip(local_shapes, local_dts))
    state_b = layout.state_bytes_per_device() if layout is not None \
        else param_b
    return param_b, state_b


def resolve_pipe_program(step_fn, pargs, step_key, kind, k,
                         placement_fp):
    """Resolve the compiled pipelined step through the process-wide
    exec_cache — same fingerprint discipline as the other fused paths:
    blake2b of the abstract jaxpr (object addresses scrubbed) +
    explicit step/layout keys + the mesh placement fingerprint;
    AOT-compiled executable cached, so an equivalent re-created
    trainer performs ZERO new XLA compilations."""
    import hashlib
    import re
    import jax.tree_util as jtu
    from .. import exec_cache
    sds = jtu.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, 'shape') else a, pargs)
    jaxpr = jax.make_jaxpr(step_fn)(*sds)
    canon = re.sub(r'0x[0-9a-f]+', '0x', str(jaxpr))
    fp = hashlib.blake2b(canon.encode(), digest_size=16).hexdigest()
    key = exec_cache.gluon_step_key(fp, step_key, kind, k,
                                    placement_fp)
    if exec_cache.enabled():
        fn = exec_cache.get(key, count=True)
        if fn is not None:
            return fn
    lowered = jax.jit(step_fn,
                      donate_argnums=(0, 1, 2, 3, 4)).lower(*pargs)
    fn = exec_cache.timed_compile(lowered)
    if exec_cache.enabled():
        exec_cache.put(key, fn)
    return fn


def note_pipe_counters(num_stages, num_micro, k, layout, dp, param_b,
                       state_b):
    """ONE profiler model for a pipelined dispatch of k steps (both
    trainers): pipe_* family + optimizer-state gauge + ZeRO comm
    bytes."""
    from .. import profiler
    profiler.set_optimizer_state_bytes(state_b)
    profiler.note_pipe_dispatch(
        num_stages, num_micro, k, bubble_fraction(num_stages, num_micro),
        param_bytes=param_b, state_bytes=state_b)
    if layout is not None and dp > 1:
        rs, ag = layout.comm_bytes_per_step()
        profiler.add_comm_bytes(reduce_scattered=rs * k,
                                all_gathered=ag * k)


def make_pipe_step_fn(mesh, num_stages, num_micro, stem_fn, stage_fn,
                      head_fn, hyper, layout=None, bulk=False,
                      data_axis='data', pipe_axis='pipe'):
    """Build the whole dp×pipe training step as ONE shard_map'd pure
    function (callers fingerprint + jit + donate it): GPipe fill-drain
    forward, autodiff backward (the ppermute transposes ARE the reverse
    schedule), gradient reduction over the dp axis, and the SGD/NAG
    update — optionally ZeRO-1-sharded over dp — in a single program.

    The caller provides three pure per-device functions over LOCAL
    parameter leaf lists:
      stem_fn(stem_ws, mb, rng)          -> act  (input layers; identity
                                                  when there is no stem)
      stage_fn(stage_ws, act, rng)       -> act  (ONE stage's layers —
                                                  the same traced fn
                                                  runs every stage with
                                                  its own leaf rows)
      head_fn(head_ws, acts, label, rng) -> (loss_leaves, total_scalar)
                                                  (output layers + loss
                                                  on the LAST stage)
    and the parameter groups as flat leaf lists:
      stage_ws  leaves stacked (S, ...) — sharded P(pipe) on the mesh
      stem_ws / head_ws leaves          — replicated
    `hyper`: {'momentum','rescale','clip','nesterov'} captured BY VALUE
    (optimizer.sgd_update_math — the one update-math definition).
    `layout`: a zero.ZeroBucketLayout over the LOCAL leaf order
    [stage..., stem..., head...] for the ZeRO-1 sharded update (None =
    replicated optimizer state).  `bulk`: K-step lax.scan mode (inputs
    gain a leading K axis; lr/wd arrive as (K, n) schedule rows).

    Wire compression (PERF round 18 stretch): with
    MXNET_TPU_DIST_WIRE_DTYPE=int8|bf16 set at BUILD time, the
    replicated-mode data-axis gradient reduction rides a narrow wire —
    int8 through collectives.quantized_allreduce (per-device scales,
    bitwise-deterministic per mode), bf16 through a cast-psum-cast.
    shard_map's manual axes make the per-device partials explicit, so
    unlike the GSPMD fused paths the wire genuinely compresses here
    (see quantized_allreduce's docstring).  The mode is baked into the
    traced program, so the jaxpr fingerprint keys int8/bf16/fp32
    programs separately in exec_cache.  ZeRO mode keeps its f32
    psum_scatter (quantize is nonlinear — it cannot ride a scatter
    that must sum in transit); the pipe-axis stem/head shares stay f32
    (correctness shares, not the dp wire).

    Gradient semantics (mirrors make_pipeline_train_step): the loss
    total is masked to the last stage and NOT psum'd inside the
    differentiated function — per-device cotangent seeds of 1 plus the
    ppermute transposes already deliver each stage's true gradient;
    stem/head gradients are nonzero only on their owning stage and are
    psum'd over the pipe axis after the backward.  Data-axis reduction
    is a psum (replicated state) or psum_scatter (ZeRO-1).

    Step signature (all leaves per-device local under shard_map):
      step(stage_ws, stem_ws, head_ws, opt, rng, data, label, lrs, wds)
        -> (loss_leaves, new_stage_ws, new_stem_ws, new_head_ws,
            new_opt, new_rng)
    `opt` is (stage_moms, stem_moms, head_moms) mirroring the weights
    (replicated mode) or the per-bucket (S, padded)-global momentum
    buffers sharded P(pipe, data) (ZeRO mode)."""
    from ..optimizer import sgd_update_math
    from ..quantization import wire_dtype_from_env
    from .collectives import quantized_allreduce

    S = int(num_stages)
    M = int(num_micro)
    dp = int(mesh.shape[data_axis])
    momentum = hyper['momentum']
    rescale = hyper['rescale']
    clip = hyper['clip']
    nesterov = hyper['nesterov']
    # dp-reduction wire dtype, resolved once at build and BAKED into
    # the traced program (the jaxpr fingerprint separates the modes)
    wire = wire_dtype_from_env(None) if dp > 1 and layout is None \
        else None

    def dp_reduce(g):
        if wire == 'int8':
            return quantized_allreduce(g, data_axis)
        if wire == 'bf16':
            return lax.psum(g.astype(jnp.bfloat16),
                            data_axis).astype(g.dtype)
        return lax.psum(g, data_axis)

    def one_step(stage_ws, stem_ws, head_ws, opt, rng, data, label,
                 lrs, wds):
        pidx = lax.axis_index(pipe_axis)
        sws = [w[0] for w in stage_ws]          # drop the stage dim
        rng, sub = jax.random.split(rng)
        b_local = data.shape[0]
        micro = data.reshape((M, b_local // M) + data.shape[1:])

        def loss_of(tws):
            sws_, stem_, head_ = tws
            outs = pipeline_run(
                lambda p, x: stage_fn(p, x, sub), sws_, micro, S,
                axis_name=pipe_axis,
                ingest=lambda m: stem_fn(stem_, m, sub))
            acts = outs.reshape((b_local,) + outs.shape[2:])
            leaves, total = head_fn(head_, acts, label, sub)
            # mask to the LAST stage; no psum here (see docstring)
            return jnp.where(pidx == S - 1, total,
                             jnp.zeros_like(total)), tuple(leaves)

        (_, leaves), grads = jax.value_and_grad(
            loss_of, has_aux=True)((sws, list(stem_ws), list(head_ws)))
        g_stage, g_stem, g_head = grads
        g_stem = [lax.psum(g, pipe_axis) for g in g_stem]
        g_head = [lax.psum(g, pipe_axis) for g in g_head]
        # loss reporting: valid on the last stage only — mask + share
        leaves = tuple(
            lax.psum(jnp.where(pidx == S - 1, l, jnp.zeros_like(l)),
                     pipe_axis) for l in leaves)

        n_stage = len(sws)
        n_stem = len(stem_ws)
        if layout is None:
            smoms, stem_moms, head_moms = opt
            g_stage = [dp_reduce(g) for g in g_stage]
            g_stem = [dp_reduce(g) for g in g_stem]
            g_head = [dp_reduce(g) for g in g_head]

            def upd(w, g, m, lr, wd):
                return sgd_update_math(
                    w, g.astype(w.dtype), m, lr, wd, momentum=momentum,
                    rescale=rescale, clip=clip, nesterov=nesterov)

            new_stage, new_smoms = [], []
            for j, (w, g, m) in enumerate(zip(sws, g_stage,
                                              [m[0] for m in smoms])):
                nw, nm = upd(w, g, m, lrs[j], wds[j])
                new_stage.append(nw[None])
                new_smoms.append(nm[None])
            new_stem, new_stem_moms = [], []
            for j, (w, g, m) in enumerate(zip(stem_ws, g_stem,
                                              stem_moms)):
                nw, nm = upd(w, g, m, lrs[n_stage + j],
                             wds[n_stage + j])
                new_stem.append(nw)
                new_stem_moms.append(nm)
            new_head, new_head_moms = [], []
            for j, (w, g, m) in enumerate(zip(head_ws, g_head,
                                              head_moms)):
                nw, nm = upd(w, g, m, lrs[n_stage + n_stem + j],
                             wds[n_stage + n_stem + j])
                new_head.append(nw)
                new_head_moms.append(nm)
            new_opt = (new_smoms, new_stem_moms, new_head_moms)
        else:
            # ZeRO-1 over dp, manual-axes form: pack local grads into
            # flat buckets, psum_scatter over the data axis (each dp
            # rank keeps its reduced 1/dp shard), update ONLY the
            # shard's momentum + weights, all_gather the new weights
            # back.  Stem/head leaves ride the same buckets — their
            # grads are already pipe-shared, so every pipe row holds
            # the same shard content.
            all_ws = sws + list(stem_ws) + list(head_ws)
            all_gs = g_stage + g_stem + g_head
            rank = lax.axis_index(data_axis)
            new_flat = [None] * len(all_ws)
            new_opt = []
            for b in layout.buckets:
                shard = b.padded // dp
                gflat = layout.pack(b, [all_gs[i] for i in b.param_idx])
                gsh = lax.psum_scatter(gflat, data_axis,
                                       scatter_dimension=0, tiled=True)
                wflat = layout.pack(b, [all_ws[i] for i in b.param_idx])
                off = rank * shard
                wsh = lax.dynamic_slice(wflat, (off,), (shard,))
                lrv = lax.dynamic_slice(
                    layout.pack_scalars(b, [lrs[i] for i in b.param_idx]),
                    (off,), (shard,))
                wdv = lax.dynamic_slice(
                    layout.pack_scalars(b, [wds[i] for i in b.param_idx]),
                    (off,), (shard,))
                nwsh, nm = sgd_update_math(
                    wsh, gsh, opt[b.index][0], lrv, wdv,
                    momentum=momentum, rescale=rescale, clip=clip,
                    nesterov=nesterov)
                full = lax.all_gather(nwsh, data_axis, axis=0,
                                      tiled=True)
                for i, v in zip(b.param_idx, layout.unpack(b, full)):
                    new_flat[i] = v
                new_opt.append(nm[None])
            new_stage = [v[None] for v in new_flat[:n_stage]]
            new_stem = new_flat[n_stage:n_stage + n_stem]
            new_head = new_flat[n_stage + n_stem:]
        return (leaves, new_stage, new_stem, new_head, new_opt, rng)

    if bulk:
        def step(stage_ws, stem_ws, head_ws, opt, rng, data, label,
                 lrs, wds):
            def body(carry, xs):
                stage_ws, stem_ws, head_ws, opt, rng = carry
                sv, lv, lr_t, wd_t = xs
                n = lr_t.shape[0]
                (leaves, stage_ws, stem_ws, head_ws, opt,
                 rng) = one_step(stage_ws, stem_ws, head_ws, opt, rng,
                                 sv, lv, [lr_t[j] for j in range(n)],
                                 [wd_t[j] for j in range(n)])
                return (stage_ws, stem_ws, head_ws, opt, rng), leaves

            init = (list(stage_ws), list(stem_ws), list(head_ws), opt,
                    rng)
            (stage_ws, stem_ws, head_ws, opt, rng), leaves = lax.scan(
                body, init, (data, label, lrs, wds))
            return (leaves, stage_ws, stem_ws, head_ws, opt, rng)
    else:
        step = one_step

    # tree-PREFIX specs: a bare P broadcasts over each list/tuple
    # subtree, so the argument structure (leaf counts, loss tree) never
    # has to be known here
    opt_spec = (P(pipe_axis), P(), P()) if layout is None \
        else P(pipe_axis, data_axis)
    batch_spec = P(None, data_axis) if bulk else P(data_axis)
    return shard_map(
        step, mesh=mesh,
        in_specs=(P(pipe_axis), P(), P(), opt_spec, P(), batch_spec,
                  batch_spec, P(), P()),
        out_specs=(batch_spec, P(pipe_axis), P(), P(), opt_spec, P()),
        check_vma=False)


def stack_stage_params(per_stage_params):
    """[stage0_pytree, stage1_pytree, ...] -> single pytree with leading
    stage dim, ready to device_put with P('pipe') sharding."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def place_pipeline_params(params, mesh, axis_name='pipe'):
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(axis_name))), params)
