"""Ouro's looped stack (LoopedDecoder) on the CPU at a small size: a
whole tiny model through Module.bulk_step against the plain float32
reference the benchmark compares with (benchmark/reference/ouro.py,
loaded from where it lives), one pass against the reference's single
pass, the passes' gradients summed in float32, and the step program
holding the layers once."""
import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, profiler
from mxnet_tpu.ops import lm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
sys.path.insert(0, BENCH)
from reference import convnet, ouro as ref             # noqa: E402

SEQ = 64
TINY = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            intermediate_size=96, rope_theta=1e6, rms_norm_eps=1e-6,
            total_ut_steps=4)
OPTIMIZER = {'learning_rate': 0.005, 'momentum': 0.9, 'wd': 1e-4}
BF16 = jnp.bfloat16


def program_args(c):
    """The factory's arguments from the reference's."""
    return {k: v for k, v in c.items() if k != 'vocab_size'}


def _tiny_module(steps=2, seed=3, dtype='float32', **extra):
    arguments = dict(TINY, seq_len=SEQ, **extra)
    sym = models.get_symbol('ouro', num_classes=TINY['vocab_size'],
                            dtype=dtype, **program_args(arguments))
    n = 2 * SEQ
    spec, _ = convnet.describe(ref.forward, arguments, (n,))
    params = convnet.make_init(spec, jnp.float32)(jax.random.PRNGKey(seed))
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc('data', (n,), 'float32')],
             label_shapes=[mx.io.DataDesc('softmax_label', (n,), 'float32')],
             for_training=True)
    mod.init_params(initializer=None, arg_params={
        k: mx.nd.NDArray(v) for k, v in params.items()}, aux_params={})
    mod.init_optimizer(kvstore='local', optimizer='sgd',
                       optimizer_params=OPTIMIZER)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY['vocab_size'], (steps, n + 1))
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(row[:-1].astype(np.float32))],
        label=[mx.nd.array(row[1:].astype(np.float32))]) for row in ids]
    return mod, batches, (arguments, params, ids)


@functools.lru_cache(maxsize=None)
def _followed(steps, loops=4):
    """`steps` bulk steps of the tiny model and of the reference's SGD
    from the same weights: (program's last loss and change of every
    leaf, the reference's)."""
    mod, batches, (arguments, params, ids) = _tiny_module(
        steps=steps, total_ut_steps=loops)
    assert mod._fusable_step()
    mod.bulk_step(batches=batches, scan_dtype='float32')
    assert mod._exec_group.executor.fused_dispatches == 1
    probs = mod.get_outputs()[0].asnumpy()
    got, _ = mod.get_params()
    step = convnet.make_train_step(ref.forward, arguments, OPTIMIZER)
    train = {k: jnp.array(v) for k, v in params.items()}
    moms = {k: jnp.zeros_like(v) for k, v in train.items()}
    for row in ids:
        train, moms, loss = step(train, moms, {},
                                 jnp.asarray(row[:-1], jnp.float32),
                                 jnp.asarray(row[1:], jnp.float32))
    labels = ids[-1][1:]
    got_loss = -np.mean(np.log(probs[np.arange(len(labels)), labels]))
    assert set(got) == set(train)
    mine = {n: got[n].asnumpy() - np.asarray(params[n]) for n in train}
    theirs = {n: np.asarray(train[n]) - np.asarray(params[n]) for n in train}
    return (got_loss, mine), (float(loss), theirs)


LEAVES = sorted(
    ['embed_weight', 'lm_head_weight', 'final_norm_gamma'] +
    ['l%d_%s' % (layer, name) for layer in range(2)
     for name in lm._LAYER_INPUTS])


def _gap(mine, theirs):
    return np.linalg.norm(mine - theirs) / np.linalg.norm(theirs)


def test_logits_against_the_reference():
    """The forward pass alone: the program's softmax of the last pass's
    logits against the reference's, on the seed's weights."""
    mod, batches, (arguments, params, ids) = _tiny_module(steps=1)
    mod.forward(batches[0], is_train=False)
    probs = mod.get_outputs()[0].asnumpy()
    logits = ref.forward(convnet.Net(params), jnp.asarray(
        ids[0][:-1], jnp.float32), **arguments)
    want = np.asarray(jax.nn.softmax(logits, axis=-1))
    assert np.abs(probs - want).max() < 1e-5 * want.max()


@pytest.mark.parametrize('steps', [1, 2])
def test_whole_model_loss_against_the_reference(steps):
    """Module.bulk_step (fused, no per-step fallback) against the
    reference's SGD: the last step's loss."""
    (got, _), (want, _) = _followed(steps)
    assert abs(got - want) < 1e-5 * want


@pytest.mark.parametrize('leaf', LEAVES)
def test_whole_model_first_gradient_against_the_reference(leaf):
    """After one step from rest a leaf's change is -lr (g + wd w): the
    first gradient, leaf by leaf, every weight's summed over its four
    passes."""
    (_, mine), (_, theirs) = _followed(1)
    assert set(mine) == set(LEAVES)
    assert _gap(mine[leaf], theirs[leaf]) < 1e-3


@pytest.mark.parametrize('leaf', LEAVES)
def test_whole_model_two_bulk_steps_against_the_reference(leaf):
    """Every leaf's change after K = 2 steps of one dispatch.  float32
    on both sides; attention's tiles sum in another order than the
    reference's blocks."""
    (_, mine), (_, theirs) = _followed(2)
    assert _gap(mine[leaf], theirs[leaf]) < 1e-3
    assert np.median([_gap(mine[n], theirs[n]) for n in LEAVES]) < 1e-4


def test_one_pass_against_the_references_single_pass():
    """num_loops = 1 is the plain stack with the final norm: the loss
    and every leaf's change after one step."""
    (got, mine), (want, theirs) = _followed(1, loops=1)
    assert abs(got - want) < 1e-5 * want
    assert max(_gap(mine[n], theirs[n]) for n in LEAVES) < 1e-3
    # and it is not the four passes' model
    (got4, _), _ = _followed(1)
    assert abs(got4 - got) > 1e-3 * got


# -- the passes' gradients summed in float32 ---------------------------------

def _fixed_point_case():
    """A bfloat16 stack whose every pass sees the same stream and the
    same cotangent, so that a weight's gradient is known pass by pass:
    W_o and W_down are zero (each half layer adds exactly nothing and
    passes the cotangent through unchanged), the stream is +-1 (the
    final norm's fixed point) and the cotangent of the output has rows
    orthogonal to the stream's, of values the norm's Jacobian keeps.
    From 2 x the stream the first pass halves the cotangent its layers
    see, so W_o's passes give g, g, g and g / 2: 3.5 g in all."""
    loop = lm._Loop(4, 4, 4, 16, SEQ, 1e6, 1e-6)
    hidden, inter, width = 64, 96, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 4)

    def he(key, shape):
        return (jax.random.normal(key, shape) *
                (2.0 / shape[1]) ** 0.5).astype(BF16)

    one = jnp.ones(hidden, jnp.float32)
    layers = tuple(
        (one, he(jax.random.fold_in(keys[0], i), (width, hidden)),
         he(jax.random.fold_in(keys[1], i), (width, hidden)),
         he(jax.random.fold_in(keys[2], i), (width, hidden)),
         jnp.zeros((hidden, width), BF16), one, one,
         he(jax.random.fold_in(keys[3], i), (inter, hidden)),
         he(jax.random.fold_in(keys[3], 10 + i), (inter, hidden)),
         jnp.zeros((hidden, inter), BF16), one) for i in range(2))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    sign = jnp.where(jax.random.bernoulli(k1, 0.5, (2 * SEQ, hidden)),
                     1.0, -1.0)
    w = jax.random.choice(k2, jnp.array([0.25, 0.5, 1.0, -0.25, -0.5, -1.0]),
                          (2 * SEQ, hidden // 2))
    half = hidden // 2
    dy = jnp.concatenate([w, -w * sign[:, :half] * sign[:, half:]], axis=1)
    assert not np.asarray(jnp.sum(dy * sign, axis=1)).any()
    return loop, layers, sign, dy.astype(BF16)


def test_a_shared_weights_gradient_is_its_passes_summed_in_float32():
    """W_o of the first layer: the operator's gradient is the exact sum
    of its four passes (float64 here: 3.5 x 1000 x dy^T a, a the
    attention's output, 1000 the post-attention norm's slope at zero)
    rounded once to bfloat16, in every element.  Summed in bfloat16
    over the passes (what jax's own scan would carry for a bfloat16
    weight) it is so in about two elements of three: 1 rounding against
    3."""
    loop, layers, sign, dy = _fixed_point_case()
    gamma = jnp.ones(64, jnp.float32)

    @jax.jit
    def grads(x):
        _, back = jax.vjp(lambda x, w: lm.looped_decoder(loop, x, w, gamma),
                          x, layers)
        return back(dy)[1][0][4]

    got = np.asarray(grads((2 * sign).astype(BF16)), np.float64)
    a = jax.jit(lambda h: lm._rope_attention(loop, h, *layers[0][1:4]))(
        sign.astype(BF16))
    g = 1000.0 * np.einsum('ti,tj->ij', np.asarray(dy, np.float64),
                           np.asarray(a, np.float64))

    def bf16(v):
        return np.asarray(jnp.asarray(np.float32(v), BF16), np.float64)

    assert (got == bf16(3.5 * g)).all()
    # from x = the stream itself every pass gives g: 4 g, exactly
    assert (np.asarray(grads(sign.astype(BF16)), np.float64) ==
            bf16(4 * g)).all()
    carried = bf16(g)
    for term in (g, g, g / 2):
        carried = bf16(carried + bf16(term))
    assert (carried == bf16(3.5 * g)).mean() < 0.8


# -- the step program holds the layers once -----------------------------------

def _scans_and_kernels(jaxpr, found=None):
    """(length, reverse) of every scan and the name of every Pallas call
    in a jaxpr and the jaxprs inside it."""
    found = found if found is not None else {'scans': [], 'kernels': [],
                                             'stacked': []}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'scan':
            found['scans'].append((eqn.params['length'],
                                   eqn.params['reverse']))
            # what a forward scan stacks for its backward: its outputs
            # after the carry
            found['stacked'].append(sum(
                v.aval.size * v.aval.dtype.itemsize
                for v in eqn.outvars[eqn.params['num_carry']:]))
        if eqn.primitive.name == 'pallas_call':
            found['kernels'].append(str(eqn.params['name']))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, 'jaxpr', sub)
                if hasattr(inner, 'eqns'):
                    _scans_and_kernels(inner, found)
    return found


def _bulk_step_jaxpr(mod, batches):
    """The jaxpr the module's bulk step program is traced to (nothing
    runs)."""
    ex = mod._exec_group.executor
    run, traced = ex.run_fused_multistep, []

    class Traced(Exception):
        pass

    def spied(step, *args, **kwargs):
        def record(*operands):
            traced.append(step.fn.trace(*operands).jaxpr)
            raise Traced()
        return run(record, *args, **kwargs)

    ex.run_fused_multistep = spied
    with pytest.raises(Traced):
        mod.bulk_step(batches=batches, scan_dtype='float32')
    return traced[0]


def test_the_step_holds_the_layers_once():
    """K = 2 steps a dispatch: the step's scan of 2 holds one scan of 4
    passes forward and one backward; L = 2 layers give 2 L forward
    kernels (the pass and each half layer's recomputation) and L
    backward ones, not 4 times as many.  The operator is traced twice a
    bulk build (the executor's eval_shape of the outputs and the step
    scan's body), and counts what its scan keeps for the backward."""
    mod, batches, _ = _tiny_module()
    jax.clear_caches()      # traced anew, whatever an earlier test built
    profiler._LOOPED.clear()
    found = _scans_and_kernels(_bulk_step_jaxpr(mod, batches).jaxpr)
    assert sorted(found['scans']) == [(2, False), (4, False), (4, True)]
    kernels = found['kernels']
    assert len(kernels) == 3 * 2
    assert sum('flash_attention_fwd' in k for k in kernels) == 2 * 2
    assert sum('flash_attention_bwd' in k for k in kernels) == 2
    stats = profiler.looped_decoder_stats()
    assert stats['lowerings'] == 2
    assert (stats['loops'], stats['layers'], stats['layer_applications']) \
        == (4, 2, 8)
    # the stream where each of the 4 half layers and the final norm
    # take it, in each of 4 passes: 2 sequences of 64 x 64 float32
    assert stats['saved_bytes'] == 4 * 5 * 2 * SEQ * 64 * 4
    # and that is what the forward scan over the passes stacks
    passes = found['scans'].index((4, False))
    assert found['stacked'][passes] == stats['saved_bytes']
    profiler._LOOPED.clear()


def test_only_the_training_traces_are_counted():
    """Module.bind's shape inference traces the operator in float32
    whatever the graph's type; it keeps nothing for a backward and is
    not counted, so a bfloat16 stack's saved bytes are bfloat16's."""
    jax.clear_caches()
    profiler._LOOPED.clear()
    mod, batches, _ = _tiny_module(dtype='bfloat16')
    assert profiler.looped_decoder_stats()['lowerings'] == 0
    _bulk_step_jaxpr(mod, batches)
    stats = profiler.looped_decoder_stats()
    assert stats['lowerings'] == 2
    assert stats['saved_bytes'] == 4 * 5 * 2 * SEQ * 64 * 2
    profiler._LOOPED.clear()


def test_factory_refuses_what_it_does_not_build():
    for extra in ({'early_exit_threshold': 0.9},
                  {'tie_word_embeddings': True}):
        with pytest.raises(mx.base.MXNetError, match='ouro'):
            models.get_symbol('ouro', num_classes=128, seq_len=SEQ,
                              **dict(program_args(TINY), **extra))


def test_scales_keep_float32_in_a_bfloat16_graph():
    sym = models.get_symbol('ouro', num_classes=1000, dtype='bfloat16',
                            seq_len=SEQ, **program_args(TINY))
    ex = sym.simple_bind(mx.cpu(), data=(2 * SEQ,),
                         softmax_label=(2 * SEQ,))
    types = {n: np.dtype(a.dtype).name for n, a in ex.arg_dict.items()}
    assert types['softmax_label'] == types['data'] == 'float32'
    for name, t in types.items():
        if name.endswith('_gamma'):
            assert t == 'float32', name
        elif name.endswith('_weight'):
            assert t == 'bfloat16', name
    assert ex.arg_dict['l1_mlp_down_proj_weight'].shape == (64, 96)


def test_fit_trains_on_the_normal_path():
    mod, batches, _ = _tiny_module(steps=1)
    data = batches[0].data[0].asnumpy()
    label = batches[0].label[0].asnumpy()
    it = mx.io.NDArrayIter(data, label, batch_size=2 * SEQ)
    losses = []
    mod.fit(it, num_epoch=4, eval_metric=mx.metric.CrossEntropy(),
            force_init=False, force_rebind=False,
            optimizer_params={'learning_rate': 0.05, 'momentum': 0.9},
            batch_end_callback=lambda p: losses.append(
                p.eval_metric.get()[1]))
    assert losses[-1] < losses[0]
