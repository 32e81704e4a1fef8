"""The busiest held expert's pairs over the mean held expert's: 1.0 is
an even load; the grouped product's time follows the sum, a later
exchange's the maximum.  Source: program counter."""
import moe_counters


def read(run):
    counts = moe_counters.held_counts(run)
    if not counts or not sum(counts):
        return None
    return max(counts) * len(counts) / sum(counts)
