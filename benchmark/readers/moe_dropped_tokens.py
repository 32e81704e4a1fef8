"""Pairs routed to an expert held here and not computed: 0 on a path
that drops nothing.  Source: program counter."""
import moe_counters


def read(run):
    s = moe_counters.stats()
    if s is None:
        return None
    return s['moe_dropped_tokens']
