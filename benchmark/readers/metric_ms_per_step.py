"""Host time a step in the metric fold (update_metric, EvalMetric
.update_dict), which reads the step's outputs and so waits for the step
on the device, by the program's 'fit.metric' span.  Source: the
program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms('fit.metric', run['window']['steps'])
