"""Host time inside the iterator's next() a step: the benchmark's own
span around PrefetchToDeviceIter.next(), which fetches the next host
batch and enqueues its copy to the device.  Source: host clock."""


def read(run):
    w = run['window']
    if not w['steps'] or 'iter.next' not in w['span_s']:
        return None
    return 1e3 * w['span_s']['iter.next'] / w['steps']
