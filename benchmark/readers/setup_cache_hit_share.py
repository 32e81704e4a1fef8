"""Share of jax's compile requests that its persistent cache answered:
100 x persistent_hits / persistent_requests of the program's
exec_cache (near 100 warm, 0 cold or with the cache off).  Source:
program counter."""
import program_setup


def read(run):
    s = program_setup.stats()
    if s is None or not s['persistent_requests']:
        return None
    return 100.0 * s['persistent_hits'] / s['persistent_requests']
