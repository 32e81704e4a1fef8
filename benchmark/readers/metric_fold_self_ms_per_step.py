"""Host time a step of the metric fold's own work: the self time of
the program's 'fit.metric' span, its duration less the 'fit.wait'
inside it.  A program without 'fit.wait' gives None: there the self
time is wait and fold together.  Source: the program's spans, host
clock."""
import program_spans


def read(run):
    steps = run['window']['steps']
    if program_spans.mean_ms('fit.wait', steps) is None:
        return None
    return program_spans.mean_ms('fit.metric', steps, self_time=True)
