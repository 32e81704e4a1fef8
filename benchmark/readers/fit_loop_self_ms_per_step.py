"""Host time a step that Module.fit's loop spends in no layer below it:
the self time of the program's 'fit.step' span, its duration less the
spans opened inside it (load_batch, host_prep, dispatch, metric,
callback).  Source: the program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        'fit.step', run['window']['steps'], self_time=True)
