"""The convolutions' and dense layers' share of their roofline: the
least time the chip could take over one step's products (each forward
product and both backward ones at the larger of operations over peak
FLOP/s and least bytes over peak bytes/s, from shapes: work.py) over
the time the device was busy a step.  The work is the model's, not the
compiled program's, so it reads the same whatever implements it; the
time is all the device did in the window, so it cannot pass 100 %.
Source: device trace."""
import jax.numpy as jnp

import work


def read(run):
    t, w = run['trace'], run['window']
    if not t or not w['steps']:
        return None
    least, _ = work.roofline_seconds(
        run['layers'], run['peak']['bf16_flops_per_s'],
        run['peak']['hbm_bytes_per_s'],
        jnp.dtype(run['config']['compute_dtype']).itemsize)
    # every chip runs its share of the batch: the layer shapes are the
    # global batch's, so the least time is divided among the chips
    return 100.0 * (least / run['chips']) / (t['busy_s'] / w['steps'])
