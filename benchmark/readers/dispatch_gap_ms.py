"""Device idle time between the end of one run of the step program and
the start of the next, mean over the window.  Source: device trace."""


def read(run):
    t = run['trace']
    if not t or not t['dispatch_gaps']:
        return None
    gaps = t['dispatch_gaps']
    return 1e3 * sum(gaps) / len(gaps)
