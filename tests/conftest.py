"""Test configuration: run the suite on an 8-device virtual CPU mesh so
multi-device sharding paths are exercised without TPU hardware (the
reference's analogous trick is cpu(0)/cpu(1) contexts in
tests/python/unittest/test_multi_device_exec.py, and launcher=local
multi-process for dist kvstore — SURVEY.md §4).
"""
import os

flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ['JAX_PLATFORMS'] = 'cpu'

import jax

assert jax.default_backend() == 'cpu', 'tests must run on the CPU backend'
assert jax.device_count() == 8, 'tests expect 8 virtual CPU devices'
