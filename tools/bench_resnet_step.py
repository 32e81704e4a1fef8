#!/usr/bin/env python3
"""ResNet-50 train-step decomposition + device-profile harness.

Round-4 established (docs/PERF.md:160-195) that the step's backward runs
at ~2.9x the forward where FLOP proportionality says ~2x.  This harness
makes that gap attackable:

  --phase fwd|fwdbwd|step   chained in-dispatch timing of each phase
  --profile                 one traced dispatch, then aggregate the
                            device lane by fused-kernel name (top-k)
  --bn train|frozen|none    BN ablation (round-4 table reproduction)
  --remat none|unit         jax.checkpoint at residual-unit granularity
  --batch / --iters / --dtype

The hand model mirrors mxnet_tpu/models/resnet.py (pre-act v2,
bottleneck, BN eps 2e-5) in NHWC bf16 — measured round 2 to match the
framework executor within ~5%, so findings transfer.

Timing: K dependent steps ride a lax.scan inside ONE dispatch (params
thread the carry, so the chain serializes for free); the per-dispatch
floor is removed two-point (long minus short chain).
"""
import argparse
import functools
import glob
import gzip
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 2e-5

UNITS = [3, 4, 6, 3]
FILTERS = [64, 256, 512, 1024, 2048]


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), 'SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _bn(x, gamma, beta, mode):
    if mode == 'none':
        return x
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean)
    if mode == 'frozen':
        mean = lax.stop_gradient(mean)
        var = lax.stop_gradient(var)
    inv = lax.rsqrt(var + BN_EPS)
    scale = (gamma * inv).astype(x.dtype)
    shift = (beta - mean * gamma * inv).astype(x.dtype)
    return x * scale + shift


def init_params(rng, dtype):
    params = {}

    def conv_w(name, k, cin, cout):
        fan_in = k * k * cin
        params[name] = jnp.asarray(
            rng.randn(k, k, cin, cout) * np.sqrt(2.0 / fan_in), dtype)

    def bn_p(name, c):
        params[name + '_g'] = jnp.ones((c,), jnp.float32)
        params[name + '_b'] = jnp.zeros((c,), jnp.float32)

    bn_p('bn_data', 3)
    conv_w('conv0', 7, 3, 64)
    bn_p('bn0', 64)
    for i in range(4):
        cin = FILTERS[i] if i else 64
        for j in range(UNITS[i]):
            name = 's%du%d' % (i + 1, j + 1)
            nf = FILTERS[i + 1]
            c_in = cin if j == 0 else nf
            bn_p(name + '_bn1', c_in)
            conv_w(name + '_conv1', 1, c_in, nf // 4)
            bn_p(name + '_bn2', nf // 4)
            conv_w(name + '_conv2', 3, nf // 4, nf // 4)
            bn_p(name + '_bn3', nf // 4)
            conv_w(name + '_conv3', 1, nf // 4, nf)
            if j == 0:
                conv_w(name + '_sc', 1, c_in, nf)
    bn_p('bn1', FILTERS[4])
    params['fc_w'] = jnp.asarray(
        rng.randn(FILTERS[4], 1000) * 0.01, dtype)
    params['fc_b'] = jnp.zeros((1000,), jnp.float32)
    return params


def unit(x, p, name, stride, dim_match, bn_mode):
    bn1 = _bn(x, p[name + '_bn1_g'], p[name + '_bn1_b'], bn_mode)
    act1 = jax.nn.relu(bn1)
    c1 = _conv(act1, p[name + '_conv1'])
    bn2 = _bn(c1, p[name + '_bn2_g'], p[name + '_bn2_b'], bn_mode)
    act2 = jax.nn.relu(bn2)
    c2 = _conv(act2, p[name + '_conv2'], stride)
    bn3 = _bn(c2, p[name + '_bn3_g'], p[name + '_bn3_b'], bn_mode)
    act3 = jax.nn.relu(bn3)
    c3 = _conv(act3, p[name + '_conv3'])
    sc = x if dim_match else _conv(act1, p[name + '_sc'], stride)
    return c3 + sc


def forward(params, x, labels, bn_mode='train', remat='none'):
    x = x.astype(params['conv0'].dtype)
    x = _bn(x, params['bn_data_g'], params['bn_data_b'],
            'frozen' if bn_mode == 'none' else bn_mode)
    x = _conv(x, params['conv0'], 2)
    x = jax.nn.relu(_bn(x, params['bn0_g'], params['bn0_b'], bn_mode))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), 'SAME')
    unit_fn = unit
    if remat == 'unit':
        unit_fn = jax.checkpoint(unit, static_argnums=(2, 3, 4, 5))
    for i in range(4):
        stride = 1 if i == 0 else 2
        for j in range(UNITS[i]):
            name = 's%du%d' % (i + 1, j + 1)
            x = unit_fn(x, params, name,
                        stride if j == 0 else 1, j > 0, bn_mode)
    x = jax.nn.relu(_bn(x, params['bn1_g'], params['bn1_b'], bn_mode))
    x = jnp.mean(x, axis=(1, 2))
    logits = (x @ params['fc_w']).astype(jnp.float32) + params['fc_b']
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def make_phase(phase, bn_mode, remat, momentum=0.9, lr=0.1):
    def loss_fn(params, x, labels):
        return forward(params, x, labels, bn_mode, remat)

    if phase == 'fwd':
        def one(params, mom, x, labels):
            loss = loss_fn(params, x, labels)
            # serialize the chain through the input: nonzero in f32,
            # numerically null once cast into the bf16 conv
            return params, mom, x + (1e-12 * loss), loss
    elif phase == 'fwdbwd':
        def one(params, mom, x, labels):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, labels)
            params = jax.tree.map(
                lambda p, g: p + (1e-12 * g.astype(p.dtype)
                                  if g is not None else 0), params, grads)
            return params, mom, x, loss
    else:  # full step: fwd+bwd+SGD(momentum, wd)
        def one(params, mom, x, labels):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, labels)
            new_mom = jax.tree.map(
                lambda m, g: momentum * m + g.astype(m.dtype) if g is not None
                else m, mom, grads)
            params = jax.tree.map(
                lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
                params, new_mom)
            return params, new_mom, x, loss
    return one


def chained(one, iters):
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(params, mom, x, labels):
        def body(carry, _):
            params, mom, x = carry
            params, mom, x, loss = one(params, mom, x, labels)
            return (params, mom, x), loss
        (params, mom, _), losses = lax.scan(
            body, (params, mom, x), None, length=iters)
        return params, mom, losses[-1]
    return run


def timed(run, params, mom, x, labels, reps):
    p, m, loss = run(params, mom, x, labels)     # compile + warm
    float(loss)
    best = float('inf')
    for _ in range(reps):
        t0 = time.perf_counter()
        p2, m2, loss = run(p, m, x, labels)
        float(loss)
        p, m = p2, m2
        best = min(best, time.perf_counter() - t0)
    return best, (p, m)


def profile_dispatch(run, params, mom, x, labels, outdir, topk=40):
    p, m, loss = run(params, mom, x, labels)
    float(loss)
    with jax.profiler.trace(outdir):
        _, _, loss = run(p, m, x, labels)
        float(loss)
    files = sorted(glob.glob(os.path.join(
        outdir, 'plugins/profile/*/*.trace.json.gz')))
    if not files:
        print('no trace produced under', outdir)
        return
    with gzip.open(files[-1], 'rt') as f:
        trace = json.load(f)
    # device lanes: pick the pid whose events carry the most total time
    # and are not python/host threads
    pid_name = {}
    for ev in trace.get('traceEvents', []):
        if ev.get('ph') == 'M' and ev.get('name') == 'process_name':
            pid_name[ev['pid']] = ev['args'].get('name', '')
    agg = {}
    lane_total = {}
    for ev in trace.get('traceEvents', []):
        if ev.get('ph') != 'X':
            continue
        pname = pid_name.get(ev.get('pid'), '')
        if not any(k in pname.lower() for k in ('tpu', 'device', 'xla')):
            continue
        # leaf HLO kernels only: module-level spans (jit_* / while bodies)
        # nest the per-kernel spans and would double-count the totals
        args = ev.get('args', {})
        cat = args.get('hlo_category')
        if cat is None or cat == 'while':
            continue
        dur = ev.get('dur', 0)
        lane_total[pname] = lane_total.get(pname, 0) + dur
        key = ev['name']
        a = agg.setdefault(key, [0, 0])
        a[0] += dur
        a[1] += 1
    print('lanes:', {k: round(v / 1e3, 1) for k, v in lane_total.items()})
    total = sum(v[0] for v in agg.values())
    print('%-72s %10s %6s %6s' % ('kernel', 'total ms', 'count', '%'))
    for name, (dur, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:topk]:
        print('%-72s %10.3f %6d %5.1f%%'
              % (name[:72], dur / 1e3, cnt, 100.0 * dur / total))
    print('device total: %.1f ms over %d kernels' % (total / 1e3, len(agg)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--phase', default='step',
                    choices=['fwd', 'fwdbwd', 'step'])
    ap.add_argument('--bn', default='train',
                    choices=['train', 'frozen', 'none'])
    ap.add_argument('--remat', default='none', choices=['none', 'unit'])
    ap.add_argument('--batch', type=int, default=256)
    ap.add_argument('--dtype', default='bfloat16')
    ap.add_argument('--iters', type=int, default=24)
    ap.add_argument('--lo-iters', type=int, default=4)
    ap.add_argument('--reps', type=int, default=3)
    ap.add_argument('--profile', action='store_true')
    ap.add_argument('--profile-dir', default='/tmp/rs_prof')
    ap.add_argument('--profile-steps', type=int, default=4)
    args = ap.parse_args()
    if args.iters <= args.lo_iters:
        ap.error('--iters must exceed --lo-iters (two-point slope)')

    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)
    params = init_params(rng, dtype)
    mom = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32), params)
    x = jnp.asarray(rng.rand(args.batch, 224, 224, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, (args.batch,)), jnp.int32)

    one = make_phase(args.phase, args.bn, args.remat)
    print('device:', jax.devices()[0], '| phase:', args.phase,
          '| bn:', args.bn, '| remat:', args.remat,
          '| batch:', args.batch)

    if args.profile:
        run = chained(one, args.profile_steps)
        profile_dispatch(run, params, mom, x, labels, args.profile_dir)
        return

    hi, state = timed(chained(one, args.iters), params, mom, x, labels,
                      args.reps)
    lo, _ = timed(chained(one, args.lo_iters), *state, x, labels, args.reps)
    per = (hi - lo) / (args.iters - args.lo_iters)
    print('%s: %.2f ms/step  (%.1f img/s at batch %d)'
          % (args.phase, per * 1e3, args.batch / per, args.batch))


if __name__ == '__main__':
    main()
