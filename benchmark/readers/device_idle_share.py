"""Share of the traced window in which no operation ran on the device,
mean over the chips used.  Source: device trace."""


def read(run):
    t = run['trace']
    if not t:
        return None
    return 100.0 * t['idle_s'] / t['window_s']
