"""Host seconds of the first step: the oldest 'module.bulk_step' span
or, with none, the oldest 'fit.step', which traces, lowers and compiles
the step program or loads it from the persistent cache (a bulk_step
span ends at the enqueue; a fit step waits for its outputs too).
Source: the program's spans, host clock."""
import program_setup


def read(run):
    return program_setup.seconds('first_step_s')
