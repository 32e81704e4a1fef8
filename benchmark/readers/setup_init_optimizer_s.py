"""Seconds of set-up inside Module.init_optimizer, by the program's
'module.init_optimizer' spans, summed.  Source: the program's spans,
host clock."""
import program_setup


def read(run):
    return program_setup.seconds('init_optimizer_s')
