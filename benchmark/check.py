"""The comparison that decides `correct`.

The program's first steps, driven through the window's own call on the
window's own object, leave norms (state_norms) and losses; the plain
float32 reference (reference/convnet.py) is then run from the same
seed's weights over the same batches, and `numbers` sets the two side by
side.  Each number has a limit in limits/<cell>.json; a number without
one there is printed and not compared.

A norm is compared leaf by leaf as the gap between the program's norm
and the reference's (not the norm of their difference), over the
reference's norm of that leaf or of the median leaf, whichever is
larger; the number is the worst leaf's.  Leaves whose first gradient in
the reference is under a thousandth of the median leaf's (a scale that
is fixed, a bias under a normalisation) move by round-off alone and are
left out.
"""
import functools
import statistics

import jax
import jax.numpy as jnp

from reference import convnet

DEAD_LEAF = 1e-3    # of the median leaf's first gradient


def loss_of_outputs(probs, labels):
    """Mean cross-entropy from the program's softmax output."""
    p = jnp.take_along_axis(jnp.asarray(probs, jnp.float32),
                            jnp.asarray(labels).astype(jnp.int32)[:, None],
                            axis=1)
    return float(-jnp.mean(jnp.log(jnp.maximum(p, 1e-30))))


def state_norms(start, weights, moms, optimizer):
    """Per-leaf norms of the change since `start` and, if `moms` is the
    state after one step from rest, of the gradient as the optimizer
    got it, g = -m/lr - wd*w0."""
    names = tuple(sorted(weights))
    norms = _norms_fn(names, float(optimizer['learning_rate']),
                      float(optimizer.get('wd', 0.0)))
    got = jax.device_get(norms({n: start[n] for n in names},
                               {n: weights[n] for n in names},
                               {n: moms[n] for n in names}))
    return {k: {n: float(v) for n, v in d.items()} for k, d in got.items()}


@functools.lru_cache(maxsize=None)
def _norms_fn(names, lr, wd):
    def norms(start, weights, moms):
        out = {'delta': {}, 'grad': {}}
        for n in names:
            w0 = start[n].astype(jnp.float32)
            m = moms[n].astype(jnp.float32)
            out['delta'][n] = jnp.linalg.norm(
                (weights[n].astype(jnp.float32) - w0).ravel())
            g = -m / lr - (wd * w0 if convnet.decays(n) else 0.0)
            out['grad'][n] = jnp.linalg.norm(g.ravel())
        return out

    return jax.jit(norms)


def leaf_gaps(program, reference, leaves):
    """leaf -> gap between the two norms, over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    median = statistics.median(reference[n] for n in leaves)
    out = {}
    for n in leaves:
        scale = max(reference[n], median)
        out[n] = abs(program[n] - reference[n]) / scale if scale > 0 else 0.0
    return out


def worst_and_median(gaps):
    """((gap, leaf) of the worst leaf, (gap, 'median of n leaves'))."""
    worst = max(gaps, key=gaps.get)
    return ((gaps[worst], worst),
            (statistics.median(gaps.values()),
             'median of %d leaves' % len(gaps)))


def numbers(produced, reference):
    """name -> (value, which leaf or step): every number the two sides
    allow.  `produced` and `reference` each hold 'losses' (step ->
    loss), 'norms' (after the last step followed) and, where the entry
    shows the state after one step, 'norms_first'.  The worst leaf's
    gap swings with the noise of one small leaf (PERF.md section 2); the
    median leaf's is steady from seed to seed."""
    out = {}
    out['loss'] = max(
        (abs(loss - reference['losses'][s]) / abs(reference['losses'][s]),
         'step %d' % s) for s, loss in sorted(produced['losses'].items()))
    first = reference['norms_first']['grad']
    floor = DEAD_LEAF * statistics.median(first.values())
    live = [n for n in sorted(first) if first[n] >= floor]
    if produced['first_step_state']:
        out['grad_first_worst'], out['grad_first_median'] = worst_and_median(
            leaf_gaps(produced['norms_first']['grad'], first, live))
    out['delta_worst'], out['delta_median'] = worst_and_median(
        leaf_gaps(produced['norms']['delta'], reference['norms']['delta'],
                  live))
    return out


_STEPS = {}


def reference_step(h, lowp=None, rows=None):
    """The reference's jitted step for this cell, built once a process."""
    key = (h.cell.name, lowp, rows)
    if key not in _STEPS:
        forward, arguments = h.cell.reference_forward()
        _STEPS[key] = convnet.make_train_step(
            forward, arguments, h.config['optimizer'], lowp=lowp, rows=rows)
    return _STEPS[key]


def run_reference(h, fed, lowp=None, rows=None):
    """Follow the steps an entry's feed() names, from the seed's
    weights, in the plain reference; returns what `numbers` takes.
    lowp and rows are the control and the planted fault (see
    convnet.make_train_step)."""
    steps, batch_of_step = fed['steps'], fed['batch_of_step']
    step = reference_step(h, lowp, rows)
    params = h.initial_params()
    aux = {n: v for n, v in params.items() if h.spec[n]['aux']}
    start = {n: v for n, v in params.items() if not h.spec[n]['aux']}
    train = {n: jnp.array(v, copy=True) for n, v in start.items()}
    moms = {n: jnp.zeros_like(v) for n, v in start.items()}
    out = {'losses': {}, 'first_step_state': fed['first_step_state']}
    optimizer = h.config['optimizer']
    for i in range(1, steps + 1):
        x, y = batch_of_step(i)
        train, moms, loss = step(train, moms, aux, jnp.asarray(x),
                                 jnp.asarray(y))
        if i in fed['loss_steps']:
            out['losses'][i] = float(loss)
        if i == 1:
            out['norms_first'] = state_norms(start, train, moms, optimizer)
        elif h.every_step and i < steps:
            out.setdefault('norms_by_step', {})[i] = state_norms(
                start, train, moms, optimizer)
    out['norms'] = state_norms(start, train, moms, optimizer)
    return out


def compare_with_reference(h, produced, limits):
    """(compared, others): name -> {'value', 'limit', 'at'} for each
    number that has a limit, and name -> {'value', 'at'} for the rest,
    which decide nothing."""
    reference = run_reference(h, produced)
    compared, others = {}, {}
    for name, (value, at) in numbers(produced, reference).items():
        if name in limits:
            compared[name] = {'value': value, 'limit': float(limits[name]),
                              'at': at}
        else:
            others[name] = {'value': value, 'at': at}
    missing = set(limits) - set(compared)
    if missing:
        raise RuntimeError('limits name numbers this entry does not '
                           'produce: %s' % sorted(missing))
    return compared, others
