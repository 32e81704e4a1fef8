"""Backend compile requests inside the measured window; 0 is expected.
Source: program counter."""


def read(run):
    return run['window']['compiles']
