"""Seconds the program's package took to import (mxnet_tpu times its
own import; jax, imported before it, is not in).  Source: the
program's clock, host."""
import program_setup


def read(run):
    return program_setup.seconds('import_s')
