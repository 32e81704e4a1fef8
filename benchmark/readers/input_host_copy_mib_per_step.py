"""MiB a step that NDArrayIter read and wrote again on the host to make
its batches: the program's host_copy_bytes counter over the batches it
made (0 where every batch is a view of the iterator's own rows; a
batch's bytes where it is a gather, two runs joined, or rows the CPU
runtime cannot take as they are).  The iterator behind the fit entry
never ends, so when the run stops it has made the traffic mix's
`prefetch` batches beyond those the program counts as served
(input_batches).  A program without the counter gives None.  Source:
program counter."""


def read(run):
    from mxnet_tpu import profiler
    stats = profiler.input_stats()
    if 'host_copy_bytes' not in stats or not stats['input_batches']:
        return None
    made = stats['input_batches'] + int(run['traffic']['prefetch'])
    return stats['host_copy_bytes'] / made / 2.0 ** 20
