"""MiB a step that io.stage_to_device handed to the device: the program's
h2d_bytes counter over the batches staged.  The program counts the
batches it has served (input_batches); the iterator behind the fit entry
never ends, so when the run stops its buffer still holds the traffic
mix's `prefetch` staged batches beyond those.  Source: program counter."""


def read(run):
    from mxnet_tpu import profiler
    stats = profiler.input_stats()
    staged = stats['input_batches'] + int(run['traffic']['prefetch'])
    if 'h2d_bytes' not in stats or not stats['input_batches']:
        return None
    return stats['h2d_bytes'] / staged / 2.0 ** 20
