"""Host time a step to enqueue the batch's copy to the device
(PrefetchToDeviceIter._stage, jax.device_put), by the program's
'io.stage' span inside 'io.next'.  Source: the program's spans, host
clock."""
import program_spans


def read(run):
    return program_spans.mean_ms('io.stage', run['window']['steps'])
