"""Set-up as the program itself measured it
(mxnet_tpu.profiler.setup_stats): the package's import, the spans
'module.bind', 'module.init_params' and 'module.init_optimizer' summed,
the first step (the oldest 'module.bulk_step' span or, with none, the
oldest 'fit.step': the one that traced, lowered and compiled or loaded
the step program), and what jax reported to exec_cache of its traces,
lowerings, backend compiles and persistent cache.

The five intervals lie one after another inside the run's set-up, so
with what is left of run['setup_s'] (`outside`) they sum to it.  The
counters are the process's when the readers run: set-up and whatever
compiled after it (in the window nothing, compiles_in_window; after a
fit's window the epoch's end).  A program without setup_stats (one from
before it existed) gives None, and the result line leaves the metric
out.
"""

INSIDE = ('import_s', 'bind_s', 'init_params_s', 'init_optimizer_s',
          'first_step_s')


def stats():
    from mxnet_tpu import profiler
    read = getattr(profiler, 'setup_stats', None)
    return read() if read is not None else None


def seconds(*keys):
    """The sum of setup_stats()'s `keys`, or None where the program has
    no setup_stats or one of them is None."""
    s = stats()
    if s is None or any(s.get(k) is None for k in keys):
        return None
    return sum(s[k] for k in keys)


def outside(run):
    """The run's set-up less the five intervals the program measured:
    the harness's own work (layer shapes, weights and inputs from the
    seed, the norms it reads), jax's import and the runtime's start,
    and waits for the device."""
    inside = seconds(*INSIDE)
    return None if inside is None else run['setup_s'] - inside
