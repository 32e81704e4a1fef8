"""Host time a step inside PrefetchToDeviceIter.iter_next, by the program's
own 'io.next' span: the inside twin of input_stall_ms_per_step.fit, which
times the same call from outside.  Source: the program's spans, host
clock."""
import program_spans


def read(run):
    return program_spans.mean_ms('io.next', run['window']['steps'])
