#!/usr/bin/env python3
"""Microbenchmark: fused Pallas conv+BN-stats vs XLA conv + stats re-read.

Times the forward conv + statistics pattern at every distinct conv+BN
shape in the ResNet-50 body (batch configurable), on the attached
accelerator.  Prints one line per shape and a traffic-weighted total.

Usage: python tools/bench_conv_bn.py [--batch 256] [--dtype bfloat16]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import sys
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mxnet_tpu import pallas_conv as pc  # noqa: E402

# (H, Cin, Cout, K, stride, count) — every conv feeding a BN in the
# ResNet-50 body (stem 7x7 Cin=3 excluded: kernel declines Cin<8).
RESNET50_CONVS = [
    (56, 64, 64, 1, 1, 1), (56, 64, 64, 3, 1, 3), (56, 64, 256, 1, 1, 3),
    (56, 256, 64, 1, 1, 2), (56, 256, 128, 1, 2, 1),
    (56, 256, 512, 1, 2, 1),
    (28, 128, 128, 3, 1, 4), (28, 128, 512, 1, 1, 4),
    (28, 512, 128, 1, 1, 3), (28, 512, 256, 1, 2, 1),
    (28, 512, 1024, 1, 2, 1),
    (14, 256, 256, 3, 1, 6), (14, 256, 1024, 1, 1, 6),
    (14, 1024, 256, 1, 1, 5), (14, 1024, 512, 1, 2, 1),
    (14, 1024, 2048, 1, 2, 1),
    (7, 512, 512, 3, 1, 3), (7, 512, 2048, 1, 1, 3),
    (7, 2048, 512, 1, 1, 2),
]


def chained_timer(fn_one, iters):
    """Time `iters` dependent applications inside ONE jit dispatch.

    Each iteration's weights are perturbed by (a numerically-zero
    function of) the previous iteration's stats, which serializes the
    chain and defeats CSE without adding measurable traffic; the single
    dispatch amortizes the per-dispatch floor that otherwise swamps
    kernel-level differences."""
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(x, w):
        y0, _, _ = jax.eval_shape(fn_one, x, w)

        def body(_, carry):
            ww, acc, y_prev = carry
            y, s1, s2 = fn_one(x, ww)
            # Serialize + defeat CSE with a data-dependent weight nudge.
            # 1e-12*s2 is nonzero in f32 (not constant-foldable) but
            # rounds away entirely in the weight dtype's ulp, so the
            # chain is numerically stationary.
            ww = ww + (1e-12 * s2[:1]).astype(w.dtype)
            # y rides the loop carry so it must MATERIALIZE every
            # iteration — otherwise XLA DCEs the activation write and
            # flatters the baseline (docs/PERF.md harness pitfall #3).
            acc = acc + s1[0] + y_prev[0, 0, 0, 0].astype(jnp.float32)
            return ww, acc, y
        _, acc, _ = lax.fori_loop(
            0, iters, body,
            (w, jnp.float32(0), jnp.zeros(y0.shape, y0.dtype)))
        return acc

    return run


def _measure_total(run, x, w, reps=3):
    """Wall time of one dispatch, closed by block_until_ready."""
    run(x, w).block_until_ready()  # compile + warm
    best = float('inf')
    for _ in range(reps):
        t0 = time.perf_counter()
        run(x, w).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def time_fn(fn_one, x, w, iters=1024):
    """Per-iteration kernel time via a two-point measurement: the
    chain must be long enough that compute dominates the dispatch
    floor; the short-chain point subtracts the floor."""
    iters = max(iters, 16)
    lo_iters = max(4, iters // 32)
    hi = _measure_total(chained_timer(fn_one, iters), x, w)
    lo = _measure_total(chained_timer(fn_one, lo_iters), x, w)
    return max(hi - lo, 1e-9) / (iters - lo_iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=256)
    ap.add_argument('--dtype', default='bfloat16')
    ap.add_argument('--iters', type=int, default=512)
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)

    print('device:', jax.devices()[0])
    tot_fused = tot_base = 0.0
    wins = losses = skipped = 0
    for h, cin, cout, k, s, count in RESNET50_CONVS:
        pad = (k // 2, k // 2)
        xs = (args.batch, h, h, cin)
        ws = (k, k, cin, cout)
        if not pc.supported(xs, ws, (s, s), pad, dtype):
            print('%-28s SKIP (unsupported)' % ((h, cin, cout, k, s),))
            skipped += 1
            continue
        x = jnp.asarray(rng.randn(*xs), dtype)
        w = jnp.asarray(rng.randn(*ws) * 0.05, dtype)

        def fused(x, w, s=s, pad=pad):
            return pc.conv2d_bn_stats(x, w, (s, s), pad)

        def base(x, w, s=s, pad=pad):
            return pc.reference_conv_bn_stats(x, w, (s, s), pad)

        try:
            t_fused = time_fn(fused, x, w, iters=args.iters)
        except Exception as e:  # compile failure -> report, keep going
            print('%-28s FUSED-FAIL %s' % ((h, cin, cout, k, s),
                                           str(e)[:80]))
            skipped += 1
            continue
        t_base = time_fn(base, x, w, iters=args.iters)
        # correctness spot check
        yf, s1f, s2f = jax.jit(fused)(x, w)
        yb, s1b, s2b = jax.jit(base)(x, w)
        rel = float(jnp.max(jnp.abs(s2f - s2b)) /
                    (jnp.max(jnp.abs(s2b)) + 1e-9))
        speedup = t_base / t_fused
        tot_fused += count * t_fused
        tot_base += count * t_base
        wins += count * (speedup > 1.0)
        losses += count * (speedup <= 1.0)
        print('%-28s fused %7.3f ms  xla %7.3f ms  x%.2f  (x%d, s2 rel %.1e)'
              % ((h, cin, cout, k, s), t_fused * 1e3, t_base * 1e3,
                 speedup, count, rel))
    if tot_base:
        print('TOTAL (count-weighted): fused %.2f ms, xla %.2f ms, x%.2f '
              '(%d faster / %d slower / %d skipped)'
              % (tot_fused * 1e3, tot_base * 1e3, tot_base / tot_fused,
                 wins, losses, skipped))


if __name__ == '__main__':
    main()
