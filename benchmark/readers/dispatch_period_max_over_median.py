"""The longest start-to-start period of the window's 'module.bulk_step'
spans over the median one: 1.0 in a clean window, about 2 in one that
holds a stall of a dispatch's length.  The window's first period is
left out: its first two dispatches are enqueued back to back, before
the first wait.  Beside device_idle_share and the breakdown's idle gaps
it says whether the device idled through a stall.  None with fewer than
three dispatches.  Source: the program's spans, host clock."""
import statistics


def read(run):
    from mxnet_tpu import profiler
    span_tail = getattr(profiler, 'span_tail', None)
    n = run['window']['dispatches']
    spans = span_tail('module.bulk_step', n) if span_tail and n >= 3 \
        else None
    if not spans:
        return None
    starts = [start for start, _, _ in spans[1:]]
    periods = [b - a for a, b in zip(starts, starts[1:])]
    return max(periods) / statistics.median(periods)
