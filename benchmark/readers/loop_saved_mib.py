"""MiB of activations the looped stack's scan keeps for the backward
pass, over all its passes (the stream where each half layer and the
final norm take it), from mxnet_tpu.profiler.looped_decoder_stats(),
which takes it from shapes while the operator is traced for training,
never in a step.  None on a configuration that loops no layers and on a program
without the counter.  Source: program counter."""


def read(run):
    from mxnet_tpu import profiler
    stats = getattr(profiler, 'looped_decoder_stats', None)
    if stats is None or 'total_ut_steps' not in run['config']:
        return None
    saved = stats().get('saved_bytes', 0)
    return saved / 2.0 ** 20 if saved else None
