"""Seconds of set-up inside Module.bind, by the program's 'module.bind'
spans, summed.  Source: the program's spans, host clock."""
import program_setup


def read(run):
    return program_setup.seconds('bind_s')
