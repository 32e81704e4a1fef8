"""Host time a dispatch from the call of the compiled step program to its
return (the enqueue: the span never waits for the device), by the
program's 'executor.dispatch' span.  Source: the program's spans, host
clock."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        'executor.dispatch', run['window']['dispatches'])
