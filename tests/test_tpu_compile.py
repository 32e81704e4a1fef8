"""The kernels of the language model's cell compile at the cell's widths
for a TPU v5e that is described, not attached: what Mosaic refuses (a
block off the tiling, too much VMEM) it refuses here, at no chip time.
Nothing runs, so this says nothing about results or speed.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU's library, and every
xdist worker imports every test file."""
import base64
import functools
import os
import re

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


def _mosaic_kernels(lowered_text):
    """{kernel name: its Mosaic module as text} of a lowered program's
    tpu_custom_calls (the modules travel as base64 bytecode)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    found = {}
    with ctx:
        for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                               lowered_text):
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=False)
            found[re.search(r'module @(\w+)', asm).group(1)] = asm
    return found


# the solve's products in each kernel (its body is one pair of heads,
# looped over a grid step's pairs): the doubling
# chain's ten (two heads' 64 x 64 side by side against a block diagonal:
# five squarings, five factors) and dA = -T^T dT T^T's two
SOLVE_PRODUCTS = {'delta_rule_local': 10, 'delta_rule_chunks': 0,
                  'delta_rule_states': 0, 'delta_rule_chunks_bwd': 0,
                  'delta_rule_local_bwd': 2}
# forward: the chunk's own system, then the loop; the gradient alone
# needs no o: the local make again (with T), the states again, the loop
# backward, the local half's backward
DELTA_KERNELS = {
    'forward': ('delta_rule_local', 'delta_rule_chunks'),
    'gradient': ('delta_rule_local', 'delta_rule_states',
                 'delta_rule_chunks_bwd', 'delta_rule_local_bwd')}


@pytest.mark.parametrize('what', sorted(DELTA_KERNELS))
def test_delta_rule_kernels_compile_for_the_chip(one_chip, monkeypatch,
                                                 what):
    """One block of the cell: 8 value heads of 128 at 8,192 tokens.
    The code asks jax for its backend (the CPU here), so the test
    steers it onto the Mosaic path.  Mosaic takes Precision.HIGHEST on
    the float32 products of the chunk's solve as it stands
    (`contract_precision<fp32>` on those matmuls and on no other; no
    split into bfloat16 parts by hand), and XLA is left no product over
    the chunks' 64 x 64 matrices."""
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
    lowered = jax.jit(fn).lower(*args)
    kernels = _mosaic_kernels(lowered.as_text())
    assert set(DELTA_KERNELS[what]) <= set(kernels)
    for name, asm in kernels.items():
        assert asm.count('contract_precision<fp32>') == \
            SOLVE_PRODUCTS[name], name
    text = lowered.compile().as_text()
    assert text.count('tpu_custom_call') == len(DELTA_KERNELS[what])
    assert ' while(' not in text
    assert not re.search(r'f32\[8,128,64,64\]\S* (dot|convolution)\(', text)


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


# (sequences, key-value heads, head width, window) of gated attention's
# core in the cells: 8 query heads a key-value head at 8,192 tokens
GATED_CORES = {'trinity-mini-full': (1, 4, 128, None),
               'trinity-mini-windowed': (1, 4, 128, 2048),
               'qwen3-next': (2, 2, 256, None)}


@pytest.fixture(scope='module')
def gated_core(one_chip):
    """The gradient of a cell's attention core through causal_attention,
    compiled for the chip at `t` tokens."""
    @functools.lru_cache(maxsize=None)
    def compiled(case, t=8192):
        b, kv, d, window = GATED_CORES[case]

        def shape(*s):
            return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

        args = (shape(b, t, kv, 8, d), shape(b, t, kv, d), shape(b, t, kv, d))
        fn = jax.grad(lambda *a: jnp.sum(lm.causal_attention(
            *a, d ** -0.5, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2))
        return jax.jit(fn).lower(*args).compile()

    return compiled


@pytest.mark.parametrize('case', sorted(GATED_CORES))
def test_gated_attention_core_compiles_for_the_chip(gated_core, monkeypatch,
                                                    case):
    """Grouped heads, with and without a window, are one forward and
    one backward flash kernel over the whole batch: Mosaic takes the
    backward's dQ accumulator of a whole group (8 heads x 8,192 rows in
    float32: 32 MiB at heads of 128, 64 MiB at 256) beside its tiles,
    and no loop of XLA blocks is left."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    text = gated_core(case).as_text()
    assert text.count('tpu_custom_call') == 2
    assert 'flash_attention_fwd' in text and 'flash_attention_bwd' in text
    assert ' while(' not in text


@pytest.mark.parametrize('case', ['trinity-mini-full',
                                  'trinity-mini-windowed'])
def test_gated_core_compiles_for_the_chip_in_memory_linear_in_t(
        gated_core, monkeypatch, case):
    """On the kernels no score leaves VMEM: the temporaries of a
    sequence twice as long are under three times as large (134 -> 353
    MB) with a window and without: the head-major copies of q, o and
    their gradients and the rows' float32 sums.  PR 34's blocked core
    held 328 MB at 8,192 under a window of 2,048 and 977 MB without
    one, the blocks' scores."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    short, long = (gated_core(case, t).memory_analysis().temp_size_in_bytes
                   for t in (4096, 8192))
    assert long < 3 * short
    assert long < 400e6


# (tokens, hidden, experts, held, expert width, top k, scoring) of the
# expert layers of the cells
EXPERT_LAYERS = {'qwen3-next': (16384, 2048, 512, 32, 512, 10, 'softmax'),
                 'trinity-mini': (8192, 2048, 128, 16, 1024, 8, 'sigmoid'),
                 'kanana': (8192, 2048, 128, 16, 768, 6, 'sigmoid')}


def _pair_gathers(text, pairs):
    """The compiled program's fusions of kind kCustom (gathers, on the
    TPU) whose result is one value for each of the `pairs` pairs."""
    return re.findall(r'= [a-z0-9]+\[%d\]\S* fusion\([^)]*\), kind=kCustom'
                      % pairs, text)


@pytest.mark.parametrize('case', sorted(EXPERT_LAYERS))
def test_expert_layer_compiles_for_the_chip(one_chip, monkeypatch, case):
    """A cell's expert layer and its gradient: the forward's and the
    backward's tile loops each add their rows to the tokens with one
    add_rows kernel, and no array of every pair's rows (tokens x k +
    tile of them) is left in the program."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    n, h, e, held, inter, k, scoring = EXPERT_LAYERS[case]

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def loss(x, router, wg, wu, wd):
        y = lm.sparse_moe(x, router, wg, wu, wd, k, 0, scoring=scoring)[0]
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(n, h), shape(e, h, dtype=jnp.float32), shape(held, inter, h),
        shape(held, inter, h), shape(held, h, inter)).compile().as_text()
    assert text.count('tpu_custom_call') == 2
    assert 'add_rows' in text
    assert not re.search(r'\[%d,' % (n * k + lm.EXPERT_TILE), text)
    assert not _pair_gathers(text, n * k)


@pytest.mark.parametrize('case', sorted(EXPERT_LAYERS))
def test_expert_plan_compiles_to_one_sort_and_no_pair_gather(
        one_chip, monkeypatch, case):
    """A cell's expert layer forward: its plan is one sort over the
    tokens x k pairs (each pair's packed slot and index, carrying its
    weight), and no fusion gathers a value for every pair (the parent's
    key[order] and weight[order])."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    n, h, e, held, inter, k, scoring = EXPERT_LAYERS[case]

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def forward(x, router, wg, wu, wd):
        return lm.sparse_moe(x, router, wg, wu, wd, k, 0, scoring=scoring)[0]

    text = jax.jit(forward).lower(
        shape(n, h), shape(e, h, dtype=jnp.float32), shape(held, inter, h),
        shape(held, inter, h), shape(held, h, inter)).compile().as_text()
    assert not _pair_gathers(text, n * k)
    sorts = re.findall(r'^\s*%%\S+ = \((s32\[%d\]\S*), (f32\[%d\]\S*)\) '
                       r'sort\(' % (n * k, n * k), text, re.M)
    assert len(sorts) == 1
    assert not re.search(r'\[%d\]\S*\) sort\(.*is_stable=true' % (n * k),
                         text)


@pytest.mark.parametrize('what,kernels', [
    ('forward', ['causal_conv1d']),
    ('value_and_gradient', ['causal_conv1d', 'causal_conv1d_bwd'])])
def test_causal_conv_compiles_for_the_chip(one_chip, monkeypatch, what,
                                           kernels):
    """The Qwen3-Next cell's causal convolution ((16,384, 8,192)
    bfloat16 rows as 2 sequences, a kernel of 4) through causal_conv:
    Mosaic takes the kernels' blocks within the scoped VMEM, and the
    program's temporaries hold no float32 copy of x (512 MiB; the XLA
    statement's gradient held 3 GiB of them)."""
    monkeypatch.setattr(pallas_ops, 'default_interpret', lambda *a: False)
    x = jax.ShapeDtypeStruct((16384, 8192), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8192, 4), jnp.bfloat16, sharding=one_chip)

    def conv(x, w):
        return lm.causal_conv(x, w, 8192)

    fn = {'forward': conv,
          'value_and_gradient': jax.value_and_grad(
              lambda x, w: jnp.sum(conv(x, w).astype(jnp.float32)),
              argnums=(0, 1))}[what]
    lowered = jax.jit(fn).lower(x, w)
    assert sorted(_mosaic_kernels(lowered.as_text())) == kernels
    compiled = lowered.compile()
    assert compiled.as_text().count('tpu_custom_call') == len(kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29
