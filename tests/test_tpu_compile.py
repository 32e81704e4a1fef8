"""The kernels of the language model's cell compile at the cell's widths
for a TPU v5e that is described, not attached: what Mosaic refuses (a
block off the tiling, too much VMEM) it refuses here, at no chip time.
Nothing runs, so this says nothing about results or speed.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU's library, and every
xdist worker imports every test file."""
import functools
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import pallas_ops
from mxnet_tpu.ops import lm


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # no libtpu here, or another process has it
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('what,kernels', [('forward', 1), ('gradient', 2)])
def test_delta_rule_kernels_compile_for_the_chip(one_chip, monkeypatch,
                                                 what, kernels):
    """One block of the cell: 8 value heads of 128 at 8,192 tokens.
    The code asks jax for its backend (the CPU here), so the test
    steers it onto the Mosaic path."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    h, t, d = 8, 8192, 128

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    args = (shape(1, h, t, d), shape(1, h, t, d), shape(1, h, t, d),
            shape(1, h, t), shape(1, h, t))
    fn = {'forward': lm.chunk_gated_delta_rule,
          'gradient': jax.grad(
              lambda *a: jnp.sum(lm.chunk_gated_delta_rule(*a)),
              argnums=(0, 1, 2, 3, 4))}[what]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('tpu_custom_call') >= kernels
    assert ' while(' not in text


@pytest.mark.parametrize('what,kernels', [('forward', 1), ('gradient', 2)])
def test_latent_attention_core_compiles_for_the_chip(one_chip, monkeypatch,
                                                     what, kernels):
    """The Kanana cell's core through causal_attention: one sequence of
    8,192 tokens, 32 heads, keys of 192 over values of 128, bfloat16.
    The forward is one flash kernel, the gradient that and the one of
    the backward; the blocked core's loop over sequences is gone."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    t, h, dk, dv = 8192, 32, 192, 128

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

    args = (shape(1, t, h, 1, dk), shape(1, t, h, dk), shape(1, t, h, dv))

    def core(q, k, v):
        return lm.causal_attention(q, k, v, dk ** -0.5)

    fn = {'forward': core,
          'gradient': jax.grad(
              lambda *a: jnp.sum(core(*a).astype(jnp.float32)),
              argnums=(0, 1, 2))}[what]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('tpu_custom_call') == kernels
    assert 'flash_attention_fwd' in text
    assert ('flash_attention_bwd' in text) == (what == 'gradient')
    assert ' while(' not in text


@pytest.fixture(scope='module')
def gated_core_memory(one_chip):
    """Temporaries of the Trinity-Mini cell's attention core, forward
    and gradient, compiled for the chip: one sequence of `t` tokens, 32
    query heads over 4 key-value heads of 128, bfloat16."""
    @functools.lru_cache(maxsize=None)
    def temporaries(t, window):
        def shape(*s):
            return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

        args = (shape(1, t, 4, 8, 128), shape(1, t, 4, 128),
                shape(1, t, 4, 128))
        fn = jax.grad(lambda *a: jnp.sum(lm.causal_attention(
            *a, 128 ** -0.5, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2))
        compiled = jax.jit(fn).lower(*args).compile()
        assert 'tpu_custom_call' not in compiled.as_text()
        return compiled.memory_analysis().temp_size_in_bytes

    return temporaries


@pytest.mark.parametrize('window', [2048, 512])
def test_windowed_core_compiles_for_the_chip_in_memory_linear_in_t(
        gated_core_memory, window):
    """Grouped heads under a window stay plain XLA (no kernel takes
    them), and the blocks' bands make the temporaries of a sequence
    twice as long about twice as large (PR 34: 232 -> 328 MB under a
    window of 2,048, 42 -> 99 MB under one of 512; 977 MB without):
    a mask over the causal triangle would make them four times."""
    short, long = gated_core_memory(4096, window), \
        gated_core_memory(8192, window)
    assert long < 3 * short
    assert long < gated_core_memory(8192, None)
