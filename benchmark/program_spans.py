"""The program's own spans, read for the per-layer metrics.

mxnet_tpu.profiler keeps every span it closes (profiler.scope) in a
bounded ring on the host clock, and span_tail(name, n) hands back the
newest n of a name.  The entries run nothing of the program between the
window's last step and the readers, so the newest n spans of a name are
the window's: n is the window's steps for what happens once a step and
its dispatches for what happens once a dispatch.

A program without the ring (one from before the spans existed) gives
None, and the result line leaves the metric out.
"""


def mean_ms(name, n, self_time=False):
    """Mean over the newest n spans `name` of their duration, or of
    their self time (the duration less what their child spans cover),
    in milliseconds; None where the program has no such spans."""
    from mxnet_tpu import profiler
    span_tail = getattr(profiler, 'span_tail', None)
    if span_tail is None or n <= 0:
        return None
    spans = span_tail(name, n)
    if not spans:
        return None
    if self_time:
        total = sum(self_s for _, _, self_s in spans)
    else:
        total = sum(end - start for start, end, _ in spans)
    return 1e3 * total / len(spans)
