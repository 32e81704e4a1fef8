"""Host time a step that the metric fold waits for the step's outputs,
by the program's 'fit.wait' span (one block_until_ready inside
'fit.metric').  Source: the program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms('fit.wait', run['window']['steps'])
