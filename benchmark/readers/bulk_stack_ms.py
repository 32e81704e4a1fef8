"""Host time a dispatch in bulk_step's cast and stack of the K batches into
one array a name (and their sharding over a mesh), by the program's
'module.bulk_stack' span.  The copy itself runs on the device and is
busy time there.  Source: the program's spans, host clock."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        'module.bulk_stack', run['window']['dispatches'])
