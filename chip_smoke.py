"""Chip smoke test: the ResNet-50 training path, end to end, on the TPU.

    python3 chip_smoke.py

One process.  Drives `mx.mod.Module` over `mxnet_tpu.models` ResNet-50
at full width (batch 256, 3x224x224, bfloat16, random weights from a
seed) through the entry points a user calls — `Module.fit` and
`Module.bulk_step` — then compiles and runs the Pallas flash-attention
kernels at four lengths and at the latent-attention cell's head shape
(32 heads, keys of 192 over values of 128, T = 8,192, against dense
attention) and the gated delta rule's kernels at one block
of the language model's cell (against the XLA code they replaced) and
its causal convolution's (against two XLA forms), then,
on a host with four chips, runs the same network data-parallel over
them.  It fails (non-zero exit, no result
line) when JAX finds no TPU, when any phase raises, and when run
without the rest of the checkout.  It sets no JAX_PLATFORMS and no
compile-cache directory: both are placed from outside.

The timings it prints are smoke timings, not a benchmark.  The last
line of stdout is one JSON object with exactly these keys,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}};
phases run, compile seconds and cache hits are on the `summary:` line
before it.
"""
import gc
import importlib.metadata
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926
# dispatches of a new program before the steady state: the first
# compiles it; a program whose weights were written past Module
# (executor.arg_dict[k][:] = v leaves them uncommitted) compiles once
# more at the second, when it is fed its own donated, committed outputs
WARM = 2


def log(msg):
    print(msg, flush=True)


class CompileLog:
    """What jax compiled, as jax itself reports it (jax.monitoring):
    every backend compile request with its seconds, and how many the
    persistent cache answered."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.requests += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# Phase A — train, full width, one chip
# ---------------------------------------------------------------------------

def make_module(ctxs, num_layers, num_classes, image):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    sym = models.get_symbol(
        'resnet', num_layers=num_layers, num_classes=num_classes,
        image_shape=','.join(str(d) for d in image), dtype='bfloat16')
    return mx.mod.Module(sym, context=ctxs if len(ctxs) > 1 else ctxs[0])


# the optimizer benchmark/configs/resnet50.json and
# examples/image_classification/common/fit.py train this model with
OPTIMIZER_PARAMS = {'learning_rate': 0.1, 'momentum': 0.9, 'wd': 1e-4,
                    'multi_precision': True}


def synthetic(rng, n, image, num_classes):
    x = rng.random((n,) + tuple(image), dtype=np.float32)
    y = rng.integers(0, num_classes, n).astype(np.float32)
    return x, y


def assert_placed(mod, devices):
    """Every parameter, gradient, aux state, optimizer state and input
    buffer of the bound module lives on exactly `devices`."""
    want = set(devices)
    ex = mod._exec_group.executor
    fu = mod._fused_updater
    groups = {'arg': ex.arg_dict, 'grad': ex.grad_dict, 'aux': ex.aux_dict,
              'momentum': fu.states, 'master': fu.masters}
    n = 0
    for kind, group in groups.items():
        for name, arr in group.items():
            for leaf in jax.tree_util.tree_leaves(
                    getattr(arr, '_data', arr)):
                got = leaf.devices()
                assert got == want, \
                    '%s %s lives on %s, wanted %s' % (kind, name, got, want)
                n += 1
    return n


def block(mod):
    """Wait for the device: every weight the last dispatch wrote."""
    ex = mod._exec_group.executor
    jax.block_until_ready([ex.arg_dict[n]._data for n in ex._diff_names])


def phase_a(clog, ctx, batch=256, image=(3, 224, 224), num_layers=50,
            num_classes=1000, fit_batches=6, bulk=16):
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    dev = ctx.jax_device()
    rng = np.random.default_rng(SEED)
    mx.random.seed(SEED)
    mod = make_module([ctx], num_layers, num_classes, image)

    # (a) Module.fit, one short epoch from NDArrayIter — the entry
    # examples/image_classification/train_imagenet.py uses
    x, y = synthetic(rng, fit_batches * batch, image, num_classes)
    train = mx.io.NDArrayIter(x, y, batch_size=batch,
                              label_name='softmax_label')
    metric = mx.metric.create(['acc', 'ce'])
    marks = []          # (seconds, exec-cache misses, jax compile requests)
    w_first = []        # fc1_weight rows after the first step

    def on_batch(param):
        block(mod)
        marks.append((time.perf_counter(),
                      profiler.exec_cache_stats()['exec_cache_misses'],
                      clog.requests))
        ce = dict(param.eval_metric.get_name_value())['cross-entropy']
        assert np.isfinite(ce), 'fit batch %d: loss %r' % (param.nbatch, ce)
        if not w_first:
            w = mod._exec_group.executor.arg_dict['fc1_weight']
            w_first.append(np.asarray(w._data[:2], np.float32))

    t0 = time.perf_counter()
    mod.fit(train, eval_metric=metric, num_epoch=1, optimizer='sgd',
            optimizer_params=dict(OPTIMIZER_PARAMS),
            initializer=mx.init.Xavier(rnd_type='gaussian',
                                       factor_type='in', magnitude=2),
            batch_end_callback=on_batch)
    block(mod)
    assert len(marks) == fit_batches
    fit_warm_s = [marks[0][0] - t0, marks[1][0] - marks[0][0]]
    steady = [(b[0] - a[0]) * 1e3 for a, b in zip(marks[WARM - 1:],
                                                   marks[WARM:])]
    assert marks[-1][1] == marks[WARM - 1][1], \
        'fit: exec-cache misses after warm-up: %s' % [m[1] for m in marks]
    assert marks[-1][2] == marks[WARM - 1][2], \
        'fit: jax compiles after warm-up: %s' % [m[2] for m in marks]
    ex = mod._exec_group.executor
    w_fit = np.asarray(ex.arg_dict['fc1_weight']._data[:2], np.float32)
    assert np.isfinite(w_fit).all()
    assert not np.array_equal(w_fit, w_first[0]), \
        'fit: fc1_weight did not change'
    out = mod.get_outputs()[0]
    assert out.shape == (batch, num_classes), out.shape
    assert np.isfinite(np.asarray(out._data, np.float32)).all()
    n_fit = assert_placed(mod, [dev])
    ce = dict(metric.get_name_value())['cross-entropy']
    log('phase A fit: %d batches of %d, cross-entropy %.4f, warm-up steps '
        '%.1f s + %.1f s (compiles included), steady steps %s ms, '
        '%d buffers on %s'
        % (fit_batches, batch, ce, fit_warm_s[0], fit_warm_s[1],
           ' '.join('%.1f' % ms for ms in steady), n_fit, dev))
    del train, x, y

    # (b) bulk_step dispatches of K pre-staged batches — the device-fed
    # ResNet-50 cells of BENCHMARK.json
    batches = []
    for _ in range(bulk):
        bx, by = synthetic(rng, batch, image, num_classes)
        batches.append(mx.io.DataBatch(
            data=[mx.nd.array(bx, ctx=ctx)],
            label=[mx.nd.array(by, ctx=ctx)]))
    for b in batches:
        assert b.data[0]._data.devices() == {dev}
    bulk_ms = []
    for i in range(WARM + 1):
        before = (profiler.exec_cache_stats()['exec_cache_misses'],
                  clog.requests)
        t0 = time.perf_counter()
        mod.bulk_step(batches=batches, scan_dtype='bfloat16')
        block(mod)
        bulk_ms.append((time.perf_counter() - t0) * 1e3)
        after = (profiler.exec_cache_stats()['exec_cache_misses'],
                 clog.requests)
        if i >= WARM:
            assert after == before, \
                'bulk_step: compiled in steady state: %s -> %s' \
                % (before, after)
    w_bulk = np.asarray(ex.arg_dict['fc1_weight']._data[:2], np.float32)
    assert np.isfinite(w_bulk).all()
    assert not np.array_equal(w_bulk, w_fit), \
        'bulk_step: fc1_weight did not change'
    out = mod.get_outputs()[0]
    assert np.isfinite(np.asarray(out._data, np.float32)).all()
    n_bulk = assert_placed(mod, [dev])
    log('phase A bulk_step: K=%d, warm-up dispatches %.1f s + %.1f s '
        '(compiles included), steady dispatch %.1f ms = %.2f ms a step, '
        '%d buffers on %s'
        % (bulk, bulk_ms[0] / 1e3, bulk_ms[1] / 1e3, bulk_ms[-1],
           bulk_ms[-1] / bulk, n_bulk, dev))
    return {'fit_steady_step_ms': [round(ms, 2) for ms in steady],
            'bulk_steady_step_ms': round(bulk_ms[-1] / bulk, 2)}


def compile_and_time(fn, args, n_kernels, calls=1):
    """(result, compile seconds, milliseconds a call) of jit(fn) on
    args, after one warm call; the lowered text must hold n_kernels
    Mosaic custom calls."""
    lowered = jax.jit(fn).lower(*args)
    found = lowered.as_text().count('tpu_custom_call')
    assert found >= n_kernels, \
        'lowered text has %d tpu_custom_call, wanted %d: the kernel ' \
        'did not take the Mosaic path' % (found, n_kernels)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = compiled(*args)
    jax.block_until_ready(out)
    return out, compile_s, (time.perf_counter() - t0) * 1e3 / calls


# ---------------------------------------------------------------------------
# Phase B — the Pallas flash-attention kernels compile
# ---------------------------------------------------------------------------

def phase_b(lengths=(2048, 12288, 16384, 32768), parity_at=2048, bh=8,
            d=128, latent=(32, 8192, 192, 128),
            grouped=((32, 4, 8192, 128, None), (32, 4, 8192, 128, 2048),
                     (16, 2, 8192, 256, None)),
            parity_heads=4, expect_custom_call=True):
    """Flash attention at `bh` heads of `d` over each of `lengths`; at
    `latent` = (heads, T, dk, dv): latent attention's shape in the
    Kanana cell, keys wider than values; and at each of `grouped` =
    (heads, key-value heads, T, d, window): gated attention's shapes
    in the Trinity-Mini cell, its full and its windowed layers, and in
    the Qwen3-Next cell.  Forward and gradient are compared with dense
    attention at `parity_at` and at the latent and grouped cases,
    `parity_heads` query heads at a time (the dense float32 scores of
    32 heads at T = 8,192 would be 8.6 GB), dK and dV summed over the
    heads that share a key-value head."""
    from mxnet_tpu import pallas_ops
    from mxnet_tpu.ops import lm

    def dense_all(window):
        def dense(q, k, v):
            return pallas_ops._dense_attention_lse(
                q, k, v, True, 1.0 / q.shape[-1] ** 0.5, window)[0]
        return jax.jit(lambda q, k, v: (dense(q, k, v),) + jax.grad(
            lambda *a: dense(*a).astype(jnp.float32).sum(), (0, 1, 2))(
                q, k, v))

    def timed(fn, n_kernels, *args):
        return compile_and_time(fn, args, n_kernels if expect_custom_call
                                else 0)

    def scaled_gap(what, name, a, b):
        """bf16 carries 8 bits: compare against the tensor's scale."""
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert err < 2e-2, 'flash %s: %s differs from dense by %.3g of ' \
            'its scale' % (what, name, err)
        return err

    result = {}
    cases = [(t, bh, bh, d, d, None, None, t == parity_at) for t in lengths]
    if latent:
        heads, t, dk, dv = latent
        cases.append((t, heads, heads, dk, dv, None, lm.FLASH_BLOCK, True))
    for heads, kv, t, width, window in grouped:
        # the tiles causal_attention gives the latent and grouped cases
        cases.append((t, heads, kv, width, width, window,
                      lm.FLASH_BLOCK if window is None else
                      pallas_ops.window_block(window, lm.FLASH_BLOCK), True))
    for t, heads, kv, dk, dv, window, tile, parity in cases:
        def fwd(q, k, v):
            return pallas_ops.flash_attention(q, k, v, causal=True,
                                              block_q=tile, window=window)

        def loss(q, k, v):
            return fwd(q, k, v).astype(jnp.float32).sum()

        keys = jax.random.split(jax.random.PRNGKey(SEED + t + dk), 3)
        q, k, v = (jax.random.normal(kk, (1, n, t, w), jnp.bfloat16)
                   for kk, n, w in zip(keys, (heads, kv, kv), (dk, dk, dv)))
        out, fwd_s, fwd_ms = timed(fwd, 1, q, k, v)
        grads, bwd_s, bwd_ms = timed(jax.grad(loss, (0, 1, 2)), 2, q, k, v)
        assert out.shape == q.shape[:3] + (dv,)
        what = 'T=%d' % t if (dk, kv) == (dv, heads) else \
            'T=%d heads=%d dk=%d dv=%d' % (t, heads, dk, dv)
        if kv != heads:
            what += ' kv=%d window=%s' % (kv, window)
        got = dict(zip(('out', 'dq', 'dk', 'dv'), (out,) + grads))
        for name, a in got.items():
            assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), \
                'flash %s: %s not finite' % (what, name)
        line = ('phase B flash %s: forward compile %.1f s run %.2f ms, '
                'forward+backward compile %.1f s run %.2f ms'
                % (what, fwd_s, fwd_ms, bwd_s, bwd_ms))
        if parity:
            worst, group, reference = 0.0, heads // kv, dense_all(window)
            # key-value heads a time, and of their query heads
            per = max(1, parity_heads // group)
            for j0 in range(0, kv, per):
                mine = slice(j0, j0 + per)
                sums = [0.0, 0.0]
                for h0 in range(j0 * group, (j0 + per) * group,
                                parity_heads):
                    part = slice(h0, h0 + parity_heads)
                    ref = reference(q[:, part], k[:, mine], v[:, mine])
                    for i, name in enumerate(('out', 'dq')):
                        worst = max(worst, scaled_gap(
                            what, name, got[name][:, part], ref[i]))
                    sums = [s + np.asarray(r, np.float32)
                            for s, r in zip(sums, ref[2:])]
                for name, ref_sum in zip(('dk', 'dv'), sums):
                    worst = max(worst, scaled_gap(
                        what, name, got[name][:, mine], ref_sum))
            line += ', parity with dense %.2g of scale' % worst
        log(line)
        result[what] = {'forward_ms': round(fwd_ms, 2),
                        'forward_backward_ms': round(bwd_ms, 2)}
    return result


# ---------------------------------------------------------------------------
# Phase D — the gated delta rule's kernels compile, and agree with the XLA
# code they replaced
# ---------------------------------------------------------------------------

def xla_chunk_local(q, k, v, g, beta):
    """The half of the rule that stays inside a chunk as batched XLA
    over every chunk at once, as ops/lm.py had it before the kernels
    (PR 36): kept here, and only here, as what phase D compares
    pallas_ops.delta_rule_local and its backward with.  q, k (...,
    chunks, C, dk), v (..., chunks, C, dv), g and beta (..., chunks,
    C).  Returns u, w, intra, q_in, k_out of every chunk and gamma."""
    from jax import lax
    chunk = q.shape[-2]
    g = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    k_beta = k * beta[..., None]
    a = jnp.where(jnp.tril(lower, -1), jnp.einsum(
        '...ik,...jk->...ij', k_beta, k) * decay, 0.0)
    # (I + a)^-1 = (I - a)(I + a^2)(I + a^4)...: a is nilpotent
    eye = jnp.eye(chunk, dtype=a.dtype)
    inv, power = eye - a, a
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision=lax.Precision.HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=lax.Precision.HIGHEST)
    u = jnp.matmul(inv, v * beta[..., None])
    w = jnp.matmul(inv, k_beta * jnp.exp(g)[..., None])
    intra = jnp.einsum('...ik,...jk->...ij', q, k) * decay
    q_in = q * jnp.exp(g)[..., None]
    g_last = g[..., -1]
    k_out = k * jnp.exp(g_last[..., None] - g)[..., None]
    return u, w, intra, q_in, k_out, jnp.exp(g_last)


def _chunks(xs, chunk):
    """(B, H, T, ...) -> (B * H, T / chunk, chunk, ...) of each."""
    return [x.reshape((-1, x.shape[2] // chunk, chunk) + x.shape[3:])
            for x in xs]


@jax.custom_vjp
def xla_local_delta_rule(q, k, v, g, beta):
    """The rule as PR 30 to 36 ran it: the chunk-local half in XLA,
    differentiated by jax.vjp, around the loop's three kernels."""
    from mxnet_tpu import pallas_ops
    local = xla_chunk_local(*_chunks((q, k, v, g, beta), 64))
    return pallas_ops.delta_rule_chunks(*local).reshape(v.shape)


def _xla_local_fwd(q, k, v, g, beta):
    return xla_local_delta_rule(q, k, v, g, beta), (q, k, v, g, beta)


def _xla_local_bwd(inputs, do):
    from mxnet_tpu import pallas_ops
    local, local_vjp = jax.vjp(xla_chunk_local, *_chunks(inputs, 64))
    u, w, intra, q_in, k_out, gamma = local
    s0, v_new = pallas_ops.delta_rule_states(u, w, k_out, gamma)
    grads = pallas_ops.delta_rule_chunks_bwd(
        do.reshape(u.shape), w, intra, q_in, k_out, gamma, s0, v_new)
    return tuple(d.reshape(x.shape)
                 for d, x in zip(local_vjp(grads), inputs))


xla_local_delta_rule.defvjp(_xla_local_fwd, _xla_local_bwd)


def scan_delta_rule(q, k, v, g, beta, chunk=64):
    """The whole rule in XLA, the chunk loop a lax.scan, as ops/lm.py
    had it before any kernel (PR 29).  T a whole number of chunks."""
    from jax import lax
    bsz, h, t, dk = q.shape
    nc = t // chunk
    u, w, intra, q_in, k_out, gamma = xla_chunk_local(*(
        a.reshape((bsz, h, nc, chunk) + a.shape[3:])
        for a in (q, k, v, g, beta)))

    def step(state, xs):
        u_c, w_c, intra_c, q_c, k_c, decay_c = xs
        v_new = u_c - jnp.matmul(w_c, state)
        o_c = jnp.matmul(q_c, state) + jnp.matmul(intra_c, v_new)
        state = state * decay_c[..., None, None] + jnp.einsum(
            '...ck,...cv->...kv', k_c, v_new)
        return state, o_c

    _, o = lax.scan(step, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
                    tuple(jnp.moveaxis(x, 2, 0)
                          for x in (u, w, intra, q_in, k_out, gamma)))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape)


def phase_d(shape=(1, 8, 8192, 128), calls=5, expect_custom_call=True):
    """Forward and gradient of chunk_gated_delta_rule at one block of
    the language model's cell (five kernels), against the chunk-local
    half in XLA around the loop's kernels (what the kernels of the
    local half replaced) and against the scan above, to 1e-3 of each
    tensor's norm."""
    from mxnet_tpu.ops import lm
    bsz, h, t, d = shape
    keys = jax.random.split(jax.random.PRNGKey(SEED + 4), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (unit(jax.random.normal(kk, shape, jnp.float32))
            for kk in keys[:2])
    v = jax.random.normal(keys[2], shape, jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(keys[3], shape[:3], jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3], jnp.float32))
    weight = jax.random.normal(keys[5], shape, jnp.float32)
    args = (q / d ** 0.5, k, v, g, beta)

    def grad_of(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=(0, 1, 2, 3, 4))

    def timed(fn, n_kernels):
        return compile_and_time(fn, args, n_kernels if expect_custom_call
                                else 0, calls)

    result, outs = {}, {}
    for name, fn, kernels in (
            # forward: the local make and the loop; the gradient alone
            # needs no o: the local make and the states again, the
            # loop backward, the local half's backward
            ('kernel', lm.chunk_gated_delta_rule, (2, 4)),
            ('xla_local', xla_local_delta_rule, (1, 2)),
            ('scan', scan_delta_rule, (0, 0))):
        o, fwd_s, fwd_ms = timed(fn, kernels[0])
        grads, bwd_s, bwd_ms = timed(grad_of(fn), kernels[1])
        outs[name] = (o,) + tuple(grads)
        log('phase D delta rule %s %s: forward compile %.1f s run %.2f ms, '
            'forward+backward compile %.1f s run %.2f ms'
            % (name, shape, fwd_s, fwd_ms, bwd_s, bwd_ms))
        result[name] = {'forward_ms': round(fwd_ms, 2),
                        'forward_backward_ms': round(bwd_ms, 2)}
    worst = 0.0
    for other in ('xla_local', 'scan'):
        for name, a, b in zip(('o', 'dq', 'dk', 'dv', 'dg', 'dbeta'),
                              outs['kernel'], outs[other]):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            assert np.isfinite(a).all(), 'delta rule: %s not finite' % name
            err = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
            assert err < 1e-3, 'delta rule: %s differs from %s by %.3g ' \
                'of its norm' % (name, other, err)
            worst = max(worst, err)
    log('phase D: kernels against the XLA local half and the scan, worst '
        '%.2g of a norm' % worst)
    return result


# ---------------------------------------------------------------------------
# Phase E — CausalConv1D alone at the Qwen3-Next cell's shape: its kernels
# against the XLA forms
# ---------------------------------------------------------------------------

def cast_after_shift_conv(data, w, seq_len):
    """causal_conv in XLA with the shifts in data's own type, each tap
    cast to float32 after its slice: the best XLA form found before the
    kernels, kept here, and only here, as what phase E compares them
    with.  data (N, C) rows of sequences of seq_len, w (C, W)."""
    width = w.shape[1]
    x = data.reshape((-1, seq_len) + data.shape[1:])
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = sum(xp[:, j:j + seq_len].astype(jnp.float32) * w[:, j]
            for j in range(width))
    return y.astype(data.dtype).reshape(data.shape)


def phase_e(rows=16384, channels=8192, seq_len=8192, width=4, calls=10,
            expect_custom_call=True):
    """The Qwen3-Next cell's causal convolution alone (16,384 rows of
    8,192 bfloat16 as 2 sequences, a kernel of 4), forward and forward
    with its vjp, in three forms: lm.causal_conv_xla (the operator
    before the kernels), the cast-after-shift XLA form above and
    lm.causal_conv (the kernels at this shape).  y, dx and dw of the
    last two against the first, to 1e-2 of the largest element."""
    import functools
    from mxnet_tpu.ops import lm
    keys = jax.random.split(jax.random.PRNGKey(SEED + 5), 3)
    x = jax.random.normal(keys[0], (rows, channels), jnp.bfloat16)
    w = (0.5 * jax.random.normal(keys[1], (channels, width))).astype(
        jnp.bfloat16)
    dy = jax.random.normal(keys[2], (rows, channels), jnp.bfloat16)

    def with_vjp(fn):
        def run(x, w, dy):
            y, vjp = jax.vjp(fn, x, w)
            return (y,) + vjp(dy)
        return run

    result, outs = {}, {}
    for name, fn, kernels in (
            ('xla', lm.causal_conv_xla, (0, 0)),
            ('cast_after_shift', cast_after_shift_conv, (0, 0)),
            ('kernel', lm.causal_conv, (1, 2))):
        fn = functools.partial(fn, seq_len=seq_len)
        if not expect_custom_call:
            kernels = (0, 0)
        _, fwd_s, fwd_ms = compile_and_time(fn, (x, w), kernels[0], calls)
        outs[name], bwd_s, bwd_ms = compile_and_time(
            with_vjp(fn), (x, w, dy), kernels[1], calls)
        log('phase E causal conv %s (%d, %d) in sequences of %d: forward '
            'compile %.1f s run %.3f ms, forward+backward compile %.1f s '
            'run %.3f ms' % (name, rows, channels, seq_len, fwd_s, fwd_ms,
                             bwd_s, bwd_ms))
        result[name] = {'forward_ms': round(fwd_ms, 3),
                        'forward_backward_ms': round(bwd_ms, 3)}
    worst = 0.0
    for other in ('cast_after_shift', 'kernel'):
        for name, a, b in zip(('y', 'dx', 'dw'), outs[other], outs['xla']):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            assert np.isfinite(a).all(), 'causal conv: %s not finite' % name
            err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
            assert err < 1e-2, 'causal conv: %s of %s differs by %.3g of ' \
                'the largest element' % (name, other, err)
            worst = max(worst, err)
    log('phase E: the other forms against lm.causal_conv_xla, worst %.2g '
        'of the largest element' % worst)
    result['worst'] = worst
    return result


# ---------------------------------------------------------------------------
# Phase C — data parallel over four chips
# ---------------------------------------------------------------------------

def phase_c(ctxs, batch=1024, image=(3, 224, 224), num_layers=50,
            num_classes=1000):
    import mxnet_tpu as mx
    n = len(ctxs)
    devices = [c.jax_device() for c in ctxs]
    assert len(set(devices)) == n
    gc.collect()
    base = [(d.memory_stats() or {}).get('bytes_in_use') for d in devices]
    rng = np.random.default_rng(SEED + 1)
    x, y = synthetic(rng, batch, image, num_classes)
    data_batch = mx.io.DataBatch(data=[mx.nd.array(x)],
                                 label=[mx.nd.array(y)])
    shapes = dict(
        data_shapes=[mx.io.DataDesc('data', (batch,) + tuple(image))],
        label_shapes=[mx.io.DataDesc('softmax_label', (batch,))])
    init = mx.init.Xavier(rnd_type='gaussian', factor_type='in',
                          magnitude=2)

    def cross_entropy(mod):
        p = np.asarray(mod.get_outputs()[0]._data, np.float32)
        assert p.shape == (batch, num_classes) and np.isfinite(p).all()
        return float(-np.log(np.maximum(
            p[np.arange(batch), y.astype(np.int64)], 1e-30)).mean())

    mx.random.seed(SEED)
    mod = make_module(ctxs, num_layers, num_classes, image)
    mod.bind(**shapes)
    mod.init_params(initializer=init)
    mod.init_optimizer(optimizer='sgd',
                       optimizer_params=dict(OPTIMIZER_PARAMS))
    arg0, aux0 = mod.get_params()
    arg0 = {k: v.copy() for k, v in arg0.items()}
    aux0 = {k: v.copy() for k, v in aux0.items()}

    eg = mod._exec_group
    mesh_devs = list(eg.mesh.devices.flat)
    assert len(set(mesh_devs)) == n and set(mesh_devs) == set(devices), \
        'mesh holds %s' % mesh_devs
    step_s = []
    for i in range(WARM + 1):
        t0 = time.perf_counter()
        mod.forward_backward(data_batch)
        mod.update()
        block(mod)
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            loss_n = cross_entropy(mod)

    ex = eg.executor
    shards = ex.arg_dict['data']._data.addressable_shards
    per = (batch // n,) + tuple(image)
    assert len(shards) == n and \
        {s.device for s in shards} == set(devices) and \
        all(s.data.shape == per for s in shards), \
        'data shards: %s' % [(s.device, s.data.shape) for s in shards]
    for name in ex._diff_names:
        a = ex.arg_dict[name]._data
        assert a.sharding.is_fully_replicated and \
            a.devices() == set(devices), \
            '%s is not replicated over the mesh: %s' % (name, a.sharding)
    n_bufs = assert_placed(mod, devices)
    # the program the module dispatched, lowered again for the operands
    # of its next step
    fu = mod._fused_updater
    moms, masters, lrs, wds = fu.host_prep_steps(
        [ex.arg_dict[name] for name in ex._diff_names], 1, advance=False)
    text = mod._step_program('single').lower(
        *ex._step_operands(ex._diff_names, (), None, moms, masters,
                           zero=bool(fu.zero)),
        *mod._schedule_arrays(lrs, wds)).compile().as_text()
    assert 'all-reduce' in text, 'compiled step has no all-reduce'
    del fu, moms, masters, lrs, wds
    gc.collect()
    # nothing piled on the first chip: what this phase added to each
    # (the programs phases A and B loaded onto chip 0 are in `base`;
    # the CPU backend, where this is dry-run at a tiny size, keeps no
    # memory statistics)
    grown = None
    if base[0] is not None:
        grown = [d.memory_stats()['bytes_in_use'] - b
                 for d, b in zip(devices, base)]
        assert (max(grown) - min(grown)) <= 0.1 * max(grown), \
            'device memory grew unevenly: %s' % grown

    # the same first step on one chip: forward in training mode from
    # the same weights over the same global batch
    del mod, eg, ex
    gc.collect()
    one = make_module(ctxs[:1], num_layers, num_classes, image)
    one.bind(**shapes)
    one.init_params(initializer=init, arg_params=arg0, aux_params=aux0)
    one.forward(data_batch, is_train=True)
    loss_1 = cross_entropy(one)
    assert abs(loss_n - loss_1) <= 2e-2 * abs(loss_1), \
        'first-step loss: %d chips %.5f, one chip %.5f' \
        % (n, loss_n, loss_1)
    log('phase C: %d chips, global batch %d, data shards of %s, %d '
        'buffers on the mesh, all-reduce in the compiled step, bytes '
        'added a chip %s, warm-up steps %.1f s + %.1f s (compiles included), '
        'steady step %.1f ms, first-step loss %.5f against %.5f on one '
        'chip' % (n, batch, per, n_bufs, grown, step_s[0], step_s[1],
                  step_s[-1] * 1e3, loss_n, loss_1))
    return {'steady_step_ms': round(step_s[-1] * 1e3, 2)}


# ---------------------------------------------------------------------------

def result_line(devices):
    """The last line of stdout: these keys and no others (the driver's
    contract), the device as jax reports it."""
    return json.dumps({'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': len(devices)}})


def main():
    platforms = os.environ.get('JAX_PLATFORMS')
    devices = jax.devices()
    d0 = devices[0]
    log('jax %s  jaxlib %s  libtpu %s  python %s'
        % (jax.__version__, jaxlib.__version__,
           importlib.metadata.version('libtpu'), sys.version.split()[0]))
    log('default backend %s  devices[0] platform=%s device_kind=%r  '
        'count=%d  JAX_PLATFORMS=%r'
        % (jax.default_backend(), d0.platform, d0.device_kind,
           len(devices), platforms))
    if d0.platform != 'tpu':
        sys.exit('chip_smoke: no TPU: jax.devices() holds %s under '
                 'JAX_PLATFORMS=%r' % ([str(d) for d in devices],
                                       platforms))
    clog = CompileLog()

    # the native runtime is built from what git commits, never loaded
    # stale (make is a no-op when libmxtpu.so is current)
    make = subprocess.run(['make', '-s', '-C', os.path.join(HERE, 'src')],
                          capture_output=True, text=True)
    if make.returncode:
        sys.exit('native: build failed\n%s%s' % (make.stdout, make.stderr))
    log('native: built')

    import mxnet_tpu as mx
    from mxnet_tpu import _core, exec_cache
    assert os.path.dirname(os.path.dirname(
        os.path.abspath(mx.__file__))) == HERE, mx.__file__
    cache_dir = exec_cache.setup_persistent_cache()
    log('compile cache: %s (JAX_COMPILATION_CACHE_DIR=%r)'
        % (cache_dir, os.environ.get('JAX_COMPILATION_CACHE_DIR')))
    assert _core.available(), 'native runtime did not load'
    log('native: loaded %s' % _core._LIB_PATH)

    t_all = time.perf_counter()
    log('--- smoke timings below, not a benchmark ---')
    result = {'A': phase_a(clog, mx.tpu(0))}
    gc.collect()
    result['B'] = phase_b()
    result['D'] = phase_d()
    result['E'] = phase_e()
    if len(devices) >= 4:
        result['C'] = phase_c([mx.tpu(i) for i in range(4)])
    else:
        log('phase C not run: %d chip(s)' % len(devices))
    log('compiles: %d requests, %.1f s in the backend, persistent cache '
        '%d hits / %d misses; %.1f s in all'
        % (clog.requests, clog.seconds, clog.cache_hits,
           clog.cache_misses, time.perf_counter() - t_all))
    log('summary: ' + json.dumps({
        'phases': list(result), 'compile_requests': clog.requests,
        'compile_s': round(clog.seconds, 1),
        'cache_hits': clog.cache_hits, 'cache_misses': clog.cache_misses,
        'cache_dir': cache_dir, 'smoke_timing_ms': result}))
    print(result_line(devices), flush=True)


if __name__ == '__main__':
    main()
