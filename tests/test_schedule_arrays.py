"""The learning rates and weight decays of a step reach every compiled
program as ONE (K, n_params) float32 array each (K = 1 for the single
step): what the single step is handed, that the per-step loop, the
bulk scan and the two eager updaters agree on the numbers, and that a
warmed step compiles nothing more."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import exec_cache, nd, sym
from mxnet_tpu import optimizer as opt_mod

BATCH, FEAT, STEPS = 16, 8, 5


def _net(dtype):
    net = sym.Variable('data')
    if dtype != 'float32':
        net = sym.Cast(net, dtype=dtype)
    net = sym.FullyConnected(net, name='fc1', num_hidden=16)
    net = sym.Activation(net, act_type='relu')
    net = sym.FullyConnected(net, name='fc2', num_hidden=4)
    if dtype != 'float32':
        net = sym.Cast(net, dtype='float32')
    return sym.SoftmaxOutput(net, name='softmax')


def _optimizer(dtype, names):
    """SGD whose rate halves after every second update (two boundaries
    inside STEPS) and whose parameters do not share a rate or a decay."""
    opt = mx.optimizer.create(
        'sgd', learning_rate=0.2, momentum=0.9, wd=1e-2,
        rescale_grad=1.0 / BATCH, multi_precision=dtype != 'float32',
        lr_scheduler=mx.lr_scheduler.FactorScheduler(step=2, factor=0.5),
        param_idx2name=dict(enumerate(names)))
    opt.set_lr_mult({'fc1_weight': 0.5, 'fc2_bias': 2.0})
    opt.set_wd_mult({'fc2_weight': 3.0, 'fc1_bias': 0.5})
    return opt


def _module(dtype='float32', ctxs=None, params=None, zero=None):
    mod = mx.mod.Module(_net(dtype), context=ctxs or [mx.cpu(0)])
    mod.bind(data_shapes=[mx.io.DataDesc('data', (BATCH, FEAT))],
             label_shapes=[mx.io.DataDesc('softmax_label', (BATCH,))])
    if params is None:
        mod.init_params(initializer=mx.init.Xavier())
    else:
        mod.init_params(initializer=None, arg_params=params[0],
                        aux_params=params[1])
    mod.init_optimizer(kvstore=None, zero=zero,
                       optimizer=_optimizer(dtype, mod._param_names))
    return mod


def _batches(n=STEPS, seed=0):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        data=[nd.array(rng.rand(BATCH, FEAT).astype(np.float32))],
        label=[nd.array((rng.rand(BATCH) * 4).astype(np.float32))])
        for _ in range(n)]


def _seed_params(dtype):
    mx.random.seed(7)
    ap, ax = _module(dtype).get_params()
    return ({k: v.copy() for k, v in ap.items()},
            {k: v.copy() for k, v in ax.items()})


# ---------------------------------------------------------------------------
# (a) what the compiled single step is handed
# ---------------------------------------------------------------------------

def _spy_on_steps(mod, lowered=None):
    """Every call of a compiled step by the module's executor, as the
    program's positional arguments; with `lowered`, also the text each
    program lowers to for them."""
    ex = mod._exec_group.executor
    run, calls = ex.run_fused_multistep, []

    def spied(step, *args, **kwargs):
        def record(*operands):
            calls.append(operands)
            if lowered is not None:
                lowered.append(step.lower(*operands).as_text())
            return step(*operands)
        return run(record, *args, **kwargs)

    ex.run_fused_multistep = spied
    return calls


@pytest.mark.parametrize('entry', ['update', 'fit'])
def test_single_step_is_handed_two_schedule_arrays(entry):
    mod = _module()
    mod.warmup_fused()
    calls = _spy_on_steps(mod)
    if entry == 'update':
        for b in _batches(2):
            mod.forward_backward(b)
            mod.update()
    else:
        rng = np.random.RandomState(3)
        it = mx.io.NDArrayIter(
            rng.rand(2 * BATCH, FEAT).astype(np.float32),
            (rng.rand(2 * BATCH) * 4).astype(np.float32), BATCH)
        mod.fit(it, num_epoch=1, eval_metric='acc',
                batch_end_callback=lambda p: None)
    assert len(calls) == 2
    n = len(mod._exec_group.executor._diff_names)
    for args in calls:
        assert len(args) == 9
        for hyper in args[7:]:
            assert isinstance(hyper, jax.Array)
            assert hyper.dtype == jnp.float32 and hyper.shape == (1, n)
            assert not hyper.weak_type
        leaves = jax.tree_util.tree_leaves(args)
        assert all(isinstance(leaf, jax.Array) for leaf in leaves), \
            [type(leaf) for leaf in leaves
             if not isinstance(leaf, jax.Array)]
        # weights, key, momenta and the two arrays: nothing else (no
        # masters in float32), so no per-parameter hyper leaf hides in
        # another argument
        assert len(leaves) == n + 2 + 1 + n + 2
    # the rows are the optimizer's own numbers: update 1 at the base
    # rate, each parameter by its multiplier
    lrs, wds = (np.asarray(a)[0] for a in calls[0][7:])
    names = mod._exec_group.executor._diff_names
    want_lr = {'fc1_weight': 0.1, 'fc2_bias': 0.4}
    want_wd = {'fc1_weight': 1e-2, 'fc2_weight': 3e-2, 'fc1_bias': 5e-3,
               'fc2_bias': 0.0}
    np.testing.assert_array_equal(
        lrs, np.float32([want_lr.get(k, 0.2) for k in names]))
    np.testing.assert_array_equal(
        wds, np.float32([want_wd[k] for k in names]))


# ---------------------------------------------------------------------------
# (b), (d) one set of numbers, whichever way the steps are run
# ---------------------------------------------------------------------------

def _run_per_step(mod, batches):
    for b in batches:
        mod.forward_backward(b)
        mod.update()


def _run_bulk(mod, batches):
    mod.bulk_step(batches=batches)


def _run_eager_fused(mod, batches):
    """forward, backward, then FusedSGD.__call__: the standalone
    whole-model update of the path that cannot fuse the step."""
    for b in batches:
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()


def _run_per_key(mod, batches):
    """forward, backward, then the per-key Updater over SGD.update, one
    parameter at a time (what a kvstore's server runs).  SGD.update
    reads the schedule BEFORE it bumps the count, so the first key of
    a step lags the others by one update; a key of no parameter goes
    first and takes that lag."""
    names = mod._exec_group.executor._diff_names
    upd = opt_mod.get_updater(
        _optimizer(str(mod._exec_group.executor.arg_dict[names[0]].dtype),
                   mod._param_names))
    lag = nd.array(np.zeros(1, np.float32))
    for b in batches:
        mod.forward(b, is_train=True)
        mod.backward()
        upd('lag', nd.array(np.zeros(1, np.float32)), lag)
        for n, w, g in zip(mod._param_names,
                           mod._exec_group.param_arrays,
                           mod._exec_group.grad_arrays):
            upd(n, g, w)
    mod._params_dirty = True
    return upd


def _state(mod, upd=None):
    """name -> (weight, momentum, master or None), as float32 numpy."""
    ex = mod._exec_group.executor
    out = {}
    for n in ex._diff_names:
        if upd is None:
            fu = mod._fused_updater
            mom, master = fu.states[n], fu.masters.get(n)
        else:
            st = upd.states[n]
            mom, master = st if isinstance(st, (list, tuple)) else (st, None)
        out[n] = tuple(
            None if v is None else np.asarray(
                getattr(v, '_data', v)).astype(np.float32)
            for v in (ex.arg_dict[n], mom, master))
    return out


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('arm', ['bulk', 'eager_fused', 'per_key'])
def test_steps_agree_across_paths(arm, dtype):
    """STEPS per-step update()s, against one bulk_step of K=STEPS
    (bit for bit: the same row of the same array reaches the same
    update), FusedSGD.__call__ and the per-key SGD.update (other
    programs for the gradient: to rounding)."""
    params = _seed_params(dtype)
    batches = _batches()
    ref = _module(dtype, params=params)
    _run_per_step(ref, batches)
    want = _state(ref)
    assert all(np.abs(m).max() > 0 for _, m, _ in want.values())
    mod = _module(dtype, params=params)
    run = {'bulk': _run_bulk, 'eager_fused': _run_eager_fused,
           'per_key': _run_per_key}[arm]
    got = _state(mod, run(mod, batches))
    for name, ref_vals in want.items():
        for what, a, b in zip(('weight', 'momentum', 'master'),
                              got[name], ref_vals):
            assert (a is None) == (b is None), (name, what)
            if a is None:
                continue
            if arm == 'bulk':
                np.testing.assert_array_equal(a, b, err_msg=name + what)
            else:
                # against the leaf's largest element.  bfloat16: each
                # program rounds its gradient where its own fusions
                # end (0.1-0.5 % over eight seeds of the weights); a
                # multiplier left out moves a momentum by half
                tol = 1e-5 if dtype == 'float32' else 2e-2
                assert np.abs(a - b).max() <= tol * np.abs(b).max(), \
                    (name, what, np.abs(a - b).max(), np.abs(b).max())
    assert (want['fc1_weight'][2] is not None) == (dtype == 'bfloat16')
    # both boundaries were crossed: update 5 runs at a quarter the rate
    assert ref._optimizer.lr_scheduler.base_lr == pytest.approx(0.05)


def test_host_prep_returns_float32_rows():
    """One row of the schedule arrays a step; the single step's is the
    first of K."""
    mod = _module()
    ex, fu = mod._exec_group.executor, mod._fused_updater
    weights = [ex.arg_dict[n] for n in ex._diff_names]
    _, _, lrs, wds = fu.host_prep_steps(weights, 1, advance=False)
    _, _, lr_stack, wd_stack = fu.host_prep_steps(weights, 3,
                                                  advance=False)
    n = len(weights)
    for row, stack in ((lrs, lr_stack), (wds, wd_stack)):
        assert isinstance(row, np.ndarray)
        assert row.dtype == np.float32 and row.shape == (1, n)
        assert stack.dtype == np.float32 and stack.shape == (3, n)
        np.testing.assert_array_equal(stack[:1], row)
    np.testing.assert_array_equal(lr_stack[2], lr_stack[0] * 0.5)
    assert mod._optimizer.num_update == 0          # advance=False


# ---------------------------------------------------------------------------
# (c) a warmed step compiles nothing more
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('bulk', [None, 4])
@pytest.mark.parametrize('n_ctx,zero', [(1, 0), (4, 0), (4, 1)])
def test_warmed_step_compiles_nothing_more(n_ctx, zero, bulk):
    """warmup_fused hands the step the same kind of operands as the
    real dispatch (the schedule arrays uncommitted on one device,
    replicated over the mesh; the stacks placed as staged batches
    are): jax sees one signature, for the single step and for K=4."""
    mod = _module(ctxs=[mx.cpu(i) for i in range(n_ctx)], zero=zero)
    compiles = []

    def on_duration(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles.append(duration)

    mod.warmup_fused(bulk=bulk)
    form = ('single', 1) if bulk is None else ('stacked', bulk)
    step = mod._step_program(*form).fn
    sizes = step._cache_size()
    billed = exec_cache.stats()['total_compile_s']
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        if bulk is None:
            for b in _batches(3):
                mod.forward_backward(b)
                mod.update()
        else:
            for seed in range(2):
                mod.bulk_step(batches=_batches(bulk, seed))
        jax.block_until_ready(mod.get_outputs()[0]._data)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert mod._step_program(*form).fn is step
    assert step._cache_size() == sizes
    assert exec_cache.stats()['total_compile_s'] == billed
    if bulk is None:        # bulk_step's stacking compiles its own ops
        assert compiles == []


# ---------------------------------------------------------------------------
# (e) one table of programs, one builder
# ---------------------------------------------------------------------------

def test_a_k_seen_before_builds_nothing():
    """K = 4, 2, 4 in turn: two programs, each built once."""
    mod = _module()
    ex = mod._exec_group.executor
    make, built = ex.make_fused_multistep, []

    def spied(*args, **kwargs):
        built.append(args)
        return make(*args, **kwargs)

    ex.make_fused_multistep = spied
    for k in (4, 2, 4):
        mod.bulk_step(batches=_batches(k))
    assert len(built) == 2


@pytest.mark.parametrize('form,k,loops', [
    ('single', 1, 0), ('stacked', 4, 1), ('repeat', 4, 1)])
def test_only_k_steps_make_a_loop(form, k, loops):
    """The single step is the K-step builder's program without the
    scan: no loop in its main function (the random key's split keeps
    one in a function of its own), one for K = 4."""
    mod = _module()
    texts = []
    calls = _spy_on_steps(mod, texts)
    batches = _batches(k)
    if form == 'single':
        mod.forward_backward(batches[0])
        mod.update()
    elif form == 'stacked':
        mod.bulk_step(batches=batches)
    else:
        mod.bulk_step(batch=batches[0], repeat=k)
    assert len(calls) == 1 and calls[0][7].shape[0] == k
    main = texts[0][texts[0].index('func.func public @main'):]
    main = main[:main.index('func.func private')]
    assert main.count('stablehlo.while') == loops
