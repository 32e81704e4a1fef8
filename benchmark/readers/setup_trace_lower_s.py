"""Seconds jax spent tracing functions to jaxprs and lowering them to
MLIR modules, as jax reported them to the program's exec_cache
(trace_s + lower_s).  Source: program counter."""
import program_setup


def read(run):
    return program_setup.seconds('trace_s', 'lower_s')
