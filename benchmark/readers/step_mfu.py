"""The whole step's share of the chips' peak: the forward and backward
operations the configuration's layers need (work.py; nothing recomputed
is counted) times the batches this run completed in its window, over the
window's time and chips x peak bfloat16 FLOP/s.  Source: host clock and
shapes; the peak is of the device kind in peaks.json."""
import work


def read(run):
    w = run['window']
    if not w['steps']:
        return None
    flops = work.train_flops(run['layers']) * w['steps']
    return 100.0 * flops / (w['seconds'] * run['chips'] *
                            run['peak']['bf16_flops_per_s'])
