"""Seconds jax's backend spent compiling during set-up
(jax.monitoring's backend_compile_duration, persistent-cache hits
included at what they cost).  Source: program counter."""


def read(run):
    return run['compile_s_setup']
