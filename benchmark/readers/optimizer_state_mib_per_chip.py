"""MiB of momenta and float32 master weights on the fullest device,
from the arrays the fused updater holds.  Source: program counter."""


def read(run):
    return run['optimizer_state_bytes'] / 2.0 ** 20
