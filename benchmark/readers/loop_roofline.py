"""The looped stack's and the head's share of their roofline: the least
time the chip could take over one step (each product forward and
backward at the larger of operations over peak FLOP/s and least bytes
over peak bytes/s, attention over the causal half, every layer once a
pass: work_ouro.py) over the time the device was busy a step.  The time
is all the device did in the window, so it cannot pass 100 %.  None on
a configuration that loops no layers.  Source: device trace."""
import jax.numpy as jnp

import work_ouro


def read(run):
    t, w = run['trace'], run['window']
    if not t or not w['steps'] or 'total_ut_steps' not in run['config']:
        return None
    least = work_ouro.roofline_seconds(
        run['config'], run['batch'], run['peak']['bf16_flops_per_s'],
        run['peak']['hbm_bytes_per_s'],
        jnp.dtype(run['config']['compute_dtype']).itemsize)
    return 100.0 * (least / run['chips']) / (t['busy_s'] / w['steps'])
