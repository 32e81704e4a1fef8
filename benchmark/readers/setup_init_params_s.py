"""Seconds of set-up inside Module.init_params, by the program's
'module.init_params' spans, summed.  Source: the program's spans, host
clock."""
import program_setup


def read(run):
    return program_setup.seconds('init_params_s')
