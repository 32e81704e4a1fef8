"""Device idle time a step that did not fall inside the span around the
iterator's next(): what the host's loop, not the input, left the chip
waiting for.  Source: device trace and the benchmark's host spans."""


def read(run):
    t, w = run['trace'], run['window']
    if not t or not w['steps']:
        return None
    idle = t['idle_s'] - t['idle_by_span'].get('iter.next', 0.0)
    return 1e3 * idle / w['steps']
