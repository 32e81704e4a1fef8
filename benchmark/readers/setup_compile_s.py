"""Seconds in the compiler proper: jax's backend compile durations less
what of them was the persistent cache's read (backend_compile_s -
cache_load_s of the program's exec_cache).  Source: program counter."""
import program_setup


def read(run):
    s = program_setup.stats()
    if s is None:
        return None
    return s['backend_compile_s'] - s['cache_load_s']
