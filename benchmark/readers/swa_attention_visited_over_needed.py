"""Query-key positions the program's attention cores score over the
positions their masks let through, summed over every lowering of
causal_attention this process made (a step's forward and its
recomputation alike): 1 is a core that reads nothing it masks; whole
blocks of key rows cost the rest.  From
mxnet_tpu.profiler.attention_stats(), which takes both from shapes while
the operator is traced, never in a step; a program without the two
counts gives None.  Source: program counter."""


def read(run):
    from mxnet_tpu import profiler
    stats = getattr(profiler, 'attention_stats', dict)()
    visited = needed = 0
    for shape in stats.get('shapes', []):
        if 'keys_visited' not in shape or 'keys_needed' not in shape:
            return None
        visited += shape['keys_visited']
        needed += shape['keys_needed']
    return visited / needed if needed else None
