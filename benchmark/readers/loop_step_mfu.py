"""The whole step's share of the chips' peak, for a looped decoder: the
forward and backward operations its layers need, each layer once a pass
and the head once (work_ouro.py, from the configuration; nothing
recomputed is counted), times the steps this run completed in its
window, over the window's time and chips x peak bfloat16 FLOP/s.  None
on a configuration that loops no layers.  Source: host clock and
shapes."""
import work_ouro


def read(run):
    w = run['window']
    if not w['steps'] or 'total_ut_steps' not in run['config']:
        return None
    flops = work_ouro.train_flops(run['config'], run['batch']) * w['steps']
    return 100.0 * flops / (w['seconds'] * run['chips'] *
                            run['peak']['bf16_flops_per_s'])
