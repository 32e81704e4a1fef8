"""Share of the router's (token, expert) pairs that the experts held
here computed: near held / all experts under an even router.  Source:
program counter."""
import moe_counters


def read(run):
    s = moe_counters.stats()
    if s is None:
        return None
    return 100.0 * s['moe_routed_tokens'] / s['moe_assignments']
