"""The expert layer's counters as the program keeps them
(mxnet_tpu.profiler.moe_stats): pairs (token, expert) the router made
over all experts, those computed by the experts held here, those
dropped, and the table by expert.  The counts are the whole process's,
warm-up included: shares of them read the same as the window's.  A
program without the counters gives None."""


def stats():
    from mxnet_tpu import profiler
    read = getattr(profiler, 'moe_stats', None)
    s = read() if read is not None else {}
    return s if s.get('moe_assignments') else None


def held_counts(run):
    """Pairs computed by each expert held here, or None."""
    s = stats()
    prog = run['config'].get('program', {}).get('arguments', {})
    if s is None or 'num_experts_held' not in prog:
        return None
    first = int(prog.get('expert_offset', 0))
    return [s['moe_experts'].get('e%d' % e, {}).get('routed', 0)
            for e in range(first, first + int(prog['num_experts_held']))]
