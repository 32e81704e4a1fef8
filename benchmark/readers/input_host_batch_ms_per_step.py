"""Host time a step in the wrapped iterator's next() (NDArrayIter: the
gather and the copy into a host NDArray), by the program's
'io.host_batch' span inside 'io.next'.  Source: the program's spans,
host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms('io.host_batch', run['window']['steps'])
