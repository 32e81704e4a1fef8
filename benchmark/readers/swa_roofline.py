"""The windowed-attention model's products and attention cores as a
share of their roofline: the least time the chip could take over one
step (each product forward and backward at the larger of operations
over peak FLOP/s and least bytes over peak bytes/s, attention over the
pairs each layer's mask lets through: work_afmoe.py) over the time the
device was busy a step.  The time is all the device did in the window,
so it cannot pass 100 %.  Source: device trace."""
import jax.numpy as jnp

import work_afmoe


def read(run):
    t, w = run['trace'], run['window']
    if not t or not w['steps'] or 'sliding_window' not in run['config']:
        return None
    least = work_afmoe.roofline_seconds(
        run['config'], run['batch'], run['peak']['bf16_flops_per_s'],
        run['peak']['hbm_bytes_per_s'],
        jnp.dtype(run['config']['compute_dtype']).itemsize)
    return 100.0 * (least / run['chips']) / (t['busy_s'] / w['steps'])
