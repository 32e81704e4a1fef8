"""Seconds jax spent reading executables from its persistent cache
(cache_retrieval_time_sec of every hit), as reported to the program's
exec_cache.  Source: program counter."""
import program_setup


def read(run):
    return program_setup.seconds('cache_load_s')
