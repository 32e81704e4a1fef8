"""Host time a step in DataParallelExecutorGroup.load_data_batch (cast
and placement of the batch on the executor's device), by the program's
'module.load_batch' span.  Source: the program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        'module.load_batch', run['window']['dispatches'])
