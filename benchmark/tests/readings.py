"""The readings the limits are set from, taken on the chip at a cell's
own size, several seeds in one process (set-up is most of a run):

    python3 benchmark/tests/readings.py --workload <cell> --seeds 1,2,3 \\
        [--program 1] [--stand-ins int8,half,bfloat16] --out <file.json>

For each seed: the program's numbers against the reference (the lower
reading), then the reference put in the program's place and computed in
int8 (the control), with half of the batch left out (a planted fault),
and rounded to bfloat16 (a second witness for what bfloat16 alone
costs).  Per-leaf norms go to --out for a look by hand.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run          # noqa: E402
import check        # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--program', type=int, default=1)
    ap.add_argument('--stand-ins', default='int8,half')
    ap.add_argument('--out', required=True)
    ap.add_argument('--rehearse', action='store_true',
                    help="the tests' tiny cells on the CPU")
    args = ap.parse_args()
    import mxnet_tpu as mx
    from mxnet_tpu import exec_cache
    if args.rehearse:
        tiny = os.path.join(HERE, 'tiny')
        cell = run.Cell(args.workload, root=tiny, data=tiny)
        contexts = [mx.cpu(i) for i in range(cell.chips)]
    else:
        cell = run.Cell(args.workload)
        peaks = run.read_json(run.HERE, 'peaks.json')['device_kinds']
        devices, _ = run.find_devices(cell.chips, peaks)
        contexts = [mx.tpu(d.id) for d in devices]
    exec_cache.setup_persistent_cache()
    clog = run.CompileLog()
    entry = run.load_file_module(
        os.path.join(run.HERE, 'entries', cell.traffic['entry'] + '.py'),
        'entry_readings')
    stand_ins = [s for s in args.stand_ins.split(',') if s]
    rows = []
    for seed in (int(s) for s in args.seeds.split(',')):
        h = run.Harness(cell, seed, 0.5, False, contexts, clog)
        h.every_step = True
        sides = {}
        if args.program:
            produced = entry.run(h)
            produced.pop('release')()
            gc.collect()
            fed = produced
            sides['program'] = produced
        else:
            fed = entry.feed(h)
        reference = check.run_reference(h, fed)
        for name in stand_ins:
            kwargs = {'int8': {'lowp': 'int8'}, 'bfloat16': {'lowp': 'bfloat16'},
                      'half': {'rows': h.batch // 2}}[name]
            sides[name] = check.run_reference(h, fed, **kwargs)
        row = {'seed': seed, 'numbers': {}, 'norms': {'reference': {
            'norms': reference['norms'],
            'norms_first': reference['norms_first'],
            'norms_by_step': reference.get('norms_by_step', {}),
            'losses': reference['losses']}}}
        for name, side in sides.items():
            nums = check.numbers(side, reference)
            row['numbers'][name] = {k: [v, at] for k, (v, at) in nums.items()}
            row['norms'][name] = {k: side[k] for k in
                                  ('norms', 'norms_first', 'losses',
                                   'norms_by_step') if k in side}
            print('seed %d %-9s %s' % (seed, name, '  '.join(
                '%s=%.4g@%s' % (k, v, at) for k, (v, at) in nums.items())),
                flush=True)
        rows.append(row)
        with open(args.out, 'w') as f:
            json.dump(rows, f)
        del sides, reference, fed, h
        gc.collect()


if __name__ == '__main__':
    main()
