"""Training-throughput sweep across the BASELINE.md model family.

The reference publishes single-K80 numbers for the image-classification
family (example/image-classification/README.md:149-156 + the scaling
table's 1-GPU rows, reproduced in BASELINE.md).  bench.py measures ONE
model per process (BENCH_MODEL); this tool just drives bench.py once per
model and relays the JSON lines — one emitter, no duplicated harness.
It imports bench.py for its tables only and never touches jax itself,
so each child finds the chip free.

  python tools/bench_family.py [--models resnet-50,inception-bn]
                               [--batch N] [--steps N] [--bulk N]
"""
import argparse
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..'))

import bench  # noqa: E402  (repo-root bench.py: harness + K80 table)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--models', default=','.join(bench.K80_IMG_S))
    p.add_argument('--batch', type=int, default=0,
                   help='0 = bench.py per-model default '
                        '(bench.DEFAULT_BATCH, else 256)')
    p.add_argument('--steps', type=int, default=4)
    p.add_argument('--warmup', type=int, default=2)
    p.add_argument('--bulk', type=int, default=16)
    p.add_argument('--dtype', default='bfloat16')
    p.add_argument('--gluon', action='store_true',
                   help='run the BENCH_GLUON fused-Gluon training '
                        'smoke (one bench.py child) instead of the '
                        'model-family sweep')
    p.add_argument('--overlap', action='store_true',
                   help='run the BENCH_OVERLAP host-hiding A/B suite '
                        '(gradient-reduction schedule A/B plus the '
                        'overlapped train-step arm: step_ahead=1 vs '
                        'serialized dispatch with a bitwise loss-curve '
                        'parity gate; one bench.py child that spawns '
                        'its own virtual CPU mesh when needed) '
                        'instead of the model-family sweep')
    p.add_argument('--bucket', action='store_true',
                   help='run the BENCH_BUCKET dynamic-shape training '
                        'smoke (legacy per-bucket loop vs fused '
                        'bucket ladder vs bulked ladder; one bench.py '
                        'child) instead of the model-family sweep')
    p.add_argument('--pipe', action='store_true',
                   help='run the BENCH_PIPE dp×pipe GPipe training '
                        'A/B (dp-only vs dp×pipe vs dp×pipe+ZeRO; '
                        'parity-gated, per-device param+state '
                        'residency; one bench.py child that spawns '
                        'its own virtual CPU mesh when needed) '
                        'instead of the model-family sweep')
    p.add_argument('--embed', action='store_true',
                   help='run the BENCH_EMBED sparse-embedding A/B '
                        '(dense vs touched-rows-only gradients across '
                        'uniform/zipf/repeat id distributions, parity '
                        'and zero-recompile gated, plus the '
                        '2x-virtual-device table-sharding child; one '
                        'bench.py child) instead of the model-family '
                        'sweep')
    p.add_argument('--ckpt', action='store_true',
                   help='run the BENCH_CKPT elastic-checkpoint '
                        'overhead A/B (no-checkpoint vs async cadence '
                        'vs blocking cadence; one bench.py child) '
                        'instead of the model-family sweep')
    p.add_argument('--delta', action='store_true',
                   help='run the BENCH_DELTA incremental '
                        'delta-checkpoint / weight-delta push A/B '
                        '(full-every-commit vs incremental chain '
                        'commit bytes on an embedding workload, '
                        'chain-replay resume parity, sparse delta '
                        'applied to a live engine bitwise vs full '
                        'reload, dense int8 delta parity-gated; one '
                        'bench.py child) instead of the model-family '
                        'sweep')
    p.add_argument('--serve-fleet', action='store_true',
                   help='run the BENCH_FLEET fleet serving-tier smoke '
                        '(SLO vs single-knob batching through the '
                        'HTTP front, continuous vs convoy sequence '
                        'batching, the tick_chunk K=1/4/16 ladder '
                        'with bitwise-parity + zero-compile gates, '
                        'the double-buffered staging A/B at identical '
                        'K and the tick_chunk=auto steady-state arm, '
                        'registry evict/re-warm zero-compile '
                        'check; one bench.py child) instead of the '
                        'model-family sweep')
    p.add_argument('--loop', action='store_true',
                   help='run the BENCH_LOOP diurnal autoscale drill '
                        '(open-loop diurnal request trace through a '
                        'real autoscaling localhost fleet: scale-up '
                        'lag, scale-down flap count, peak shed rate; '
                        'one bench.py child) instead of the '
                        'model-family sweep')
    p.add_argument('--int8', action='store_true',
                   help='run the BENCH_INT8 low-precision smoke (fp '
                        'vs int8 serving throughput with parity gate '
                        'and the quantized-registry residency/thrash '
                        'A/B, plus the 2-worker allreduce wire-format '
                        'A/B with loss-curve parity; one bench.py '
                        'child) instead of the model-family sweep')
    p.add_argument('--ring', action='store_true',
                   help='run the BENCH_RING cross-host transport '
                        'topology A/B (star coordinator vs p2p ring '
                        'reduce-scatter vs ring+async-overlap across '
                        'launcher-spawned workers: rank-0 ingress '
                        'counter-verified, per-mode bitwise loss '
                        'determinism, dist_overlap_ms gauge, plus the '
                        'embedding COO-vs-dense wire-bytes arm; one '
                        'bench.py child) instead of the model-family '
                        'sweep')
    args = p.parse_args()

    bench_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            '..', 'bench.py')
    if args.gluon or args.overlap or args.bucket or args.pipe or \
            args.ckpt or args.serve_fleet or args.int8 or args.loop \
            or args.embed or args.delta or args.ring:
        name, var = (('gluon', 'BENCH_GLUON') if args.gluon
                     else ('overlap', 'BENCH_OVERLAP') if args.overlap
                     else ('bucket', 'BENCH_BUCKET') if args.bucket
                     else ('pipe', 'BENCH_PIPE') if args.pipe
                     else ('ckpt', 'BENCH_CKPT') if args.ckpt
                     else ('delta', 'BENCH_DELTA') if args.delta
                     else ('embed', 'BENCH_EMBED') if args.embed
                     else ('int8', 'BENCH_INT8') if args.int8
                     else ('ring', 'BENCH_RING') if args.ring
                     else ('loop', 'BENCH_LOOP') if args.loop
                     else ('serve-fleet', 'BENCH_FLEET'))
        env = dict(os.environ, **{var: '1'})
        proc = subprocess.run([sys.executable, bench_py], env=env,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('%s bench failed' % name)
        lines = proc.stdout.strip().splitlines()
        if not lines:
            # zero-exit child with no JSON: broken relay, not success
            sys.stderr.write(proc.stderr)
            raise RuntimeError('%s bench produced no output' % name)
        print(lines[-1], flush=True)
        return
    for name in args.models.split(','):
        name = name.strip()
        env = dict(os.environ, BENCH_MODEL=name,
                   BENCH_STEPS=str(args.steps),
                   BENCH_WARMUP=str(args.warmup),
                   BENCH_BULK=str(args.bulk), BENCH_DTYPE=args.dtype)
        if args.batch:
            env['BENCH_BATCH'] = str(args.batch)
        else:
            # a stray exported BENCH_BATCH must not silently override
            # the per-model default
            env.pop('BENCH_BATCH', None)
        proc = subprocess.run([sys.executable, bench_py], env=env,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise RuntimeError('%s failed' % name)
        lines = proc.stdout.strip().splitlines()
        if not lines:
            # a zero-exit child that printed nothing has no JSON to
            # relay — treat it as a failure, not an IndexError
            sys.stderr.write(proc.stderr)
            raise RuntimeError('%s produced no output' % name)
        print(lines[-1], flush=True)


if __name__ == '__main__':
    main()
