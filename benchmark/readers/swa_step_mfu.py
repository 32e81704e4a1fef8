"""The whole step's share of the chips' peak, for a language model of
windowed and full attention layers: the forward and backward operations
its layers need (work_afmoe.py, from the configuration; nothing
recomputed and nothing outside a layer's mask is counted) times the
steps this run completed in its window, over the window's time and
chips x peak bfloat16 FLOP/s.  Source: host clock and shapes."""
import work_afmoe


def read(run):
    w = run['window']
    if not w['steps'] or 'sliding_window' not in run['config']:
        return None
    flops = work_afmoe.train_flops(run['config'], run['batch']) * w['steps']
    return 100.0 * flops / (w['seconds'] * run['chips'] *
                            run['peak']['bf16_flops_per_s'])
