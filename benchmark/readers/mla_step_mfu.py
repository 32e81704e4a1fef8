"""The whole step's share of the chips' peak, for a latent-attention
language model: the forward and backward operations its layers need
(work_mla.py, from the configuration; nothing recomputed is counted)
times the steps this run completed in its window, over the window's
time and chips x peak bfloat16 FLOP/s.  Source: host clock and shapes."""
import work_mla


def read(run):
    w = run['window']
    if not w['steps'] or 'kv_lora_rank' not in run['config']:
        return None
    flops = work_mla.train_flops(run['config'], run['batch']) * w['steps']
    return 100.0 * flops / (w['seconds'] * run['chips'] *
                            run['peak']['bf16_flops_per_s'])
