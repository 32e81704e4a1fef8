"""Seconds of the run's set-up outside the five intervals the program
measured (import, bind, init_params, init_optimizer, the first step):
the harness's own work, jax's import, the runtime's start and waits for
the device.  With the five it sums to the run's setup_s.  Source: host
clock."""
import program_setup


def read(run):
    return program_setup.outside(run)
