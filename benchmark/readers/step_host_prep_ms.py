"""Host time a dispatch between the entry and the call of the compiled
program: FusedSGD.host_prep (host_prep_steps and the schedule columns in
bulk_step) and the program lookup, by the program's 'module.host_prep'
span.  Source: the program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        'module.host_prep', run['window']['dispatches'])
