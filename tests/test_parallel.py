"""Parallelism tests on the 8-device virtual CPU mesh: mesh building,
ring attention vs full attention, and the dp×tp×sp transformer train
step (the reference has no counterpart — SURVEY.md §5.7/§7 step 9;
multi-node testing model: launcher=local in §4)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.parallel import (make_mesh, ring_attention, shard_batch,
                                collectives)
from mxnet_tpu.parallel.ring_attention import (ring_self_attention,
                                               full_attention)
from mxnet_tpu.parallel import transformer as tfm


def test_make_mesh():
    mesh = make_mesh()
    assert mesh.devices.size == 8
    mesh2 = make_mesh({'data': 2, 'model': 2})
    assert mesh2.axis_names == ('data', 'model')
    assert mesh2.devices.shape == (2, 2)


def test_shard_batch_placement():
    mesh = make_mesh({'data': 4})
    x = jnp.arange(32.0).reshape(8, 4)
    sx = shard_batch(mesh, x)
    assert sx.sharding.is_fully_replicated is False
    np.testing.assert_allclose(np.asarray(sx), np.asarray(x))


@pytest.mark.parametrize('causal', [False, True])
def test_ring_attention_matches_full(causal):
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    mesh = make_mesh({'sp': 4})
    out_ring = ring_self_attention(q, k, v, mesh, seq_axis='sp',
                                   causal=causal)
    out_full = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_full),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_transformer_train_step_flash_attention():
    """The dp x tp x sp train step with cfg['use_flash']: identical
    loss to the XLA ring path on the same data/params."""
    mesh = make_mesh({'data': 2, 'sp': 2, 'model': 2})
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 32, (4, 32)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1),
                          jnp.int32)
    losses = {}
    for use_flash in (False, True):
        cfg = tfm.lm_config(vocab=32, dim=16, heads=4, layers=1,
                            use_flash=use_flash)
        params = tfm.place_params(
            tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
        step = tfm.make_train_step(cfg, mesh, lr=0.05)
        loss, params = step(params, tokens, targets)
        losses[use_flash] = float(loss)
    assert np.isfinite(losses[True])
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4)


@pytest.mark.slow
def test_ring_attention_flash_grad():
    """jax.grad flows through the flash-kernel ring (the with-lse
    custom VJP folds the merge's logsumexp cotangent into the fused
    backward) and matches the plain XLA ring's gradients."""
    rng = np.random.RandomState(2)
    B, H, T, D = 1, 2, 128, 16
    q = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    g = jnp.asarray(rng.randn(B, H, T, D) * 0.3, jnp.float32)
    mesh = make_mesh({'sp': 4})

    def loss(q, use_flash):
        out = ring_self_attention(q, k, v, mesh, seq_axis='sp',
                                  causal=True, use_flash=use_flash)
        return jnp.sum(out * g)

    gflash = jax.grad(lambda q: loss(q, True))(q)
    gplain = jax.grad(lambda q: loss(q, False))(q)
    np.testing.assert_allclose(np.asarray(gflash), np.asarray(gplain),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_ring_attention_flash_hops(causal):
    """The flash-kernel ring (each hop through the Pallas kernel,
    logsumexp merge across hops) matches the dense reference — the
    long-context sp path without T_local^2 score blocks."""
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 128, 16
    q = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D) * 0.4, jnp.float32)
    mesh = make_mesh({'sp': 4})
    out_ring = ring_self_attention(q, k, v, mesh, seq_axis='sp',
                                   causal=causal, use_flash=True)
    out_full = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_ring),
                               np.asarray(out_full),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_transformer_train_step_dp_tp_sp():
    """Full train step over a 3-axis mesh: loss decreases and sharded
    params stay consistent with a single-device run.

    slow (~15s, round-14 headroom): the 3-axis transformer step stays
    continuously exercised by dryrun_multichip phase (a) (the
    driver-checked deliverable) and tier-1 keeps
    test_transformer_train_step_flash_attention + the ring-attention
    parity tests; this single-device consistency sweep runs in full
    CI."""
    cfg = tfm.lm_config(vocab=32, dim=16, heads=4, layers=2)
    mesh = make_mesh({'data': 2, 'sp': 2, 'model': 2})
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    params = tfm.place_params(params, cfg, mesh)
    step = tfm.make_train_step(cfg, mesh, lr=0.05)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 32, (4, 8)), jnp.int32)
    targets = jnp.asarray(np.roll(np.asarray(tokens), -1, axis=1), jnp.int32)
    losses = []
    for _ in range(30):
        loss, params = step(params, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.9, losses


def test_collectives_api():
    mesh = make_mesh({'data': 8})
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def f(x):
        s = collectives.allreduce_sum(x.sum(), 'data')
        return x * 0 + s

    out = shard_map(f, mesh=mesh, in_specs=P('data'), out_specs=P('data'))(
        jnp.ones((8, 2)))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 2), 16.0))


# ---------------------------------------------------------------------------
# Pipeline parallelism (parallel/pipeline.py; new-design, SURVEY.md §7.9)
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    """4-stage pipeline over the mesh == running the 4 stages in
    sequence on one device."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline as pp
    from mxnet_tpu.parallel import make_mesh

    S, M, mb, D = 4, 8, 2, 6
    mesh = make_mesh({'pipe': S})
    rs = np.random.RandomState(0)
    stage_params = [
        {'w': jnp.asarray(rs.randn(D, D).astype(np.float32) * 0.3),
         'b': jnp.asarray(rs.randn(D).astype(np.float32) * 0.1)}
        for _ in range(S)]

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'] + p['b'])

    stacked = pp.stack_stage_params(stage_params)
    stacked = pp.place_pipeline_params(stacked, mesh)
    x = rs.randn(M, mb, D).astype(np.float32)

    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def run(params, micro):
        sp = jax.tree_util.tree_map(lambda p: p[0], params)
        outs = pp.pipeline_run(stage_fn, sp, micro, S, 'pipe')
        # valid outputs live on the last stage only; broadcast them
        idx = jax.lax.axis_index('pipe')
        return jax.lax.psum(jnp.where(idx == S - 1, outs, 0.0), 'pipe')

    outs = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P('pipe'), P()), out_specs=P(),
        check_vma=False))(stacked, jnp.asarray(x))
    ref = jnp.asarray(x)
    for p in stage_params:
        ref = jnp.tanh(ref @ p['w'] + p['b'])
    # fetch the last stage's shard
    np.testing.assert_allclose(np.asarray(outs), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_train_step_learns():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline as pp
    from mxnet_tpu.parallel import make_mesh

    S, B, D = 4, 16, 8
    mesh = make_mesh({'pipe': S})
    rs = np.random.RandomState(1)
    stage_params = [
        {'w': jnp.asarray((np.eye(D) + rs.randn(D, D) * 0.05)
                          .astype(np.float32))}
        for _ in range(S)]

    def stage_fn(p, x):
        return x @ p['w']

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    step = pp.make_pipeline_train_step(stage_fn, loss_fn, mesh,
                                       num_micro=4, lr=0.05)
    params = pp.place_pipeline_params(
        pp.stack_stage_params(stage_params), mesh)
    x = rs.randn(B, D).astype(np.float32)
    t = (x * 2.0).astype(np.float32)
    losses = []
    for _ in range(30):
        loss, params = step(params, jnp.asarray(x), jnp.asarray(t))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.2, losses[::10]


# ---------------------------------------------------------------------------
# Expert parallelism (parallel/moe.py; new-design, SURVEY.md §7.9)
# ---------------------------------------------------------------------------

def test_moe_routing_dispatch_combine():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import switch_route

    rs = np.random.RandomState(0)
    T, D, E, C = 8, 4, 2, 8
    x = jnp.asarray(rs.randn(T, D).astype(np.float32))
    router = jnp.asarray(rs.randn(D, E).astype(np.float32))
    disp, combine, aux = switch_route(x, router, E, C)
    assert disp.shape == (E, C, D)
    assert combine.shape == (T, E, C)
    assert float(aux) > 0
    # identity experts: combine @ disp reconstructs gate-weighted tokens
    recon = jnp.einsum('tec,ecd->td', combine, disp)
    probs = np.asarray(jax.nn.softmax(x @ router, -1))
    gate = probs.max(-1)
    np.testing.assert_allclose(np.asarray(recon),
                               np.asarray(x) * gate[:, None], rtol=1e-5)


def test_moe_train_step_learns():
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import (init_moe_params,
                                        make_moe_train_step,
                                        moe_param_specs)
    from mxnet_tpu.parallel import make_mesh
    from jax.sharding import NamedSharding

    E, D, H, C = 8, 4, 8, 16
    mesh = make_mesh({'expert': 8})
    params = init_moe_params(jax.random.PRNGKey(0), D, H, E)
    # fan-in-scaled init so the toy regression converges quickly (the
    # default 0.02 init starts the two-matmul product near zero)
    params = {'router': params['router'],
              'w1': params['w1'] * 25.0, 'w2': params['w2'] * 25.0}
    specs = moe_param_specs()
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    step = make_moe_train_step(mesh, D, H, E, C, lr=2.0)
    rs = np.random.RandomState(0)
    x = rs.randn(64, D).astype(np.float32)
    y = np.tanh(x) * 0.5
    losses = []
    for _ in range(40):
        loss, params = step(params, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_pipeline_gradients_match_sequential():
    """Pipeline-parallel gradients == sequential autodiff (regression:
    a psum inside the differentiated loss scaled grads by num_stages)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline as pp
    from mxnet_tpu.parallel import make_mesh

    S, M, mb, D = 4, 8, 2, 4
    mesh = make_mesh({'pipe': S})
    rs = np.random.RandomState(0)
    Ws = [jnp.asarray((np.eye(D) + rs.randn(D, D) * 0.05)
                      .astype(np.float32)) for _ in range(S)]
    x = jnp.asarray(rs.randn(M * mb, D).astype(np.float32))
    t = x * 2.0

    step = pp.make_pipeline_train_step(
        lambda p, v: v @ p['w'],
        lambda y, tv: jnp.mean((y - tv) ** 2), mesh, num_micro=M, lr=1.0)
    params = pp.place_pipeline_params(
        pp.stack_stage_params([{'w': w} for w in Ws]), mesh)
    loss, newp = step(params, x, t)
    g_pipe = np.asarray(jnp.stack(Ws) - newp['w'])   # lr=1 -> grad

    def seq_loss(ws):
        y = x
        for w in ws:
            y = y @ w
        return jnp.mean((y - t) ** 2)

    ref_loss, g_ref = jax.value_and_grad(seq_loss)(Ws)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for i in range(S):
        np.testing.assert_allclose(g_pipe[i], np.asarray(g_ref[i]),
                                   rtol=1e-4, atol=1e-5)


def test_model_parallel_ctx_group():
    """ctx_group model parallelism: layers placed on different devices
    via AttrScope + group2ctx (reference test_model_parallel.py — there
    cpu(0)/cpu(1); PlaceDevice's _CrossDeviceCopy becomes XLA device
    placement)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym, nd

    with mx.AttrScope(ctx_group='dev1'):
        data = sym.Variable('data')
        fc1 = sym.FullyConnected(data, num_hidden=8, name='fc1')
        act1 = sym.Activation(fc1, act_type='relu')
    with mx.AttrScope(ctx_group='dev2'):
        fc2 = sym.FullyConnected(act1, num_hidden=4, name='fc2')
        net = sym.SoftmaxOutput(fc2, name='softmax')

    ex = net.simple_bind(mx.cpu(0), data=(4, 6),
                         group2ctx={'dev1': mx.cpu(0),
                                    'dev2': mx.cpu(1)})
    rs = np.random.RandomState(0)
    for k, v in ex.arg_dict.items():
        v[:] = rs.rand(*v.shape).astype(np.float32)
    out = ex.forward(is_train=True)[0]
    # dev2-group ops executed on device 1 (the output is theirs)
    assert any(d.id == 1 for d in out.handle.devices()), \
        out.handle.devices()
    ex.backward()
    # gradients flow across the device boundary
    g = ex.grad_dict['fc1_weight'].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
    # numerics match the single-device run
    ex2 = net.simple_bind(mx.cpu(0), data=(4, 6))
    for k in ex.arg_dict:
        ex2.arg_dict[k][:] = ex.arg_dict[k].asnumpy()
    out2 = ex2.forward(is_train=False)[0]
    ex.forward(is_train=False)
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), out2.asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_model_parallel_monitor_keeps_placement():
    """Monitor mode must not collapse ctx_group placement (regression:
    _fwd_monitor stayed jitted for grouped executors)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    with mx.AttrScope(ctx_group='a'):
        data = sym.Variable('data')
        fc1 = sym.FullyConnected(data, num_hidden=4, name='fc1')
    with mx.AttrScope(ctx_group='b'):
        net = sym.SoftmaxOutput(sym.FullyConnected(fc1, num_hidden=2,
                                                   name='fc2'),
                                name='softmax')
    ex = net.simple_bind(mx.cpu(0), data=(2, 4),
                         group2ctx={'a': mx.cpu(0), 'b': mx.cpu(1)})
    seen = []
    ex.set_monitor_callback(lambda name, arr: seen.append(name))
    out = ex.forward(is_train=False)[0]
    assert seen  # monitor fired
    assert any(d.id == 1 for d in out.handle.devices())


def test_group2ctx_without_groups_stays_jitted():
    """Passing group2ctx that matches no node must keep the fused jit
    path (regression: any non-empty dict forced eager dispatch)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    data = sym.Variable('data')
    net = sym.SoftmaxOutput(sym.FullyConnected(data, num_hidden=2,
                                               name='fc'), name='softmax')
    ex = net.simple_bind(mx.cpu(0), data=(2, 4),
                         group2ctx={'unused': mx.cpu(1)})
    assert not ex._grouped


# ---------------------------------------------------------------------------
# Pallas flash attention (pallas_ops.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('causal', [False, True])
def test_pallas_flash_attention_matches_reference(causal):
    from mxnet_tpu import pallas_ops

    rs = np.random.RandomState(0)
    B, H, T, D = 2, 3, 64, 16
    q = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    out = pallas_ops.flash_attention(q, k, v, causal=causal, block_q=32)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_pallas_flash_attention_grad():
    """Recompute-based backward matches autodiff through the reference."""
    from mxnet_tpu import pallas_ops

    rs = np.random.RandomState(1)
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(pallas_ops.flash_attention(q, k, v, causal=True,
                                                  block_q=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_pallas_flash_attention_odd_lengths():
    """block_q halves until it divides the sequence length."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(1, 1, 48, 8).astype(np.float32))
    out = pallas_ops.flash_attention(q, q, q, block_q=32)
    ref = full_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_pallas_flash_streaming_schedule():
    """The 3D-grid streaming schedule (K/V never resident) matches the
    reference; forced by shrinking the residency threshold."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 2, 64, 16).astype(np.float32))
    old = pallas_ops._VMEM_RESIDENT_BYTES
    pallas_ops._VMEM_RESIDENT_BYTES = 1   # force streaming
    try:
        for causal in (False, True):
            out = pallas_ops.flash_attention(q, q, q, causal=causal,
                                             block_q=16)
            ref = full_attention(q, q, q, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)
    finally:
        pallas_ops._VMEM_RESIDENT_BYTES = old


@pytest.mark.slow
def test_pallas_flash_streaming_backward():
    """The Pallas backward behind the streaming (non-resident) forward
    matches the dense oracle's gradients and is bitwise-identical to
    the one behind the resident forward (the backward is one kernel on
    either; the forwards' log-sum-exp must agree to the bit); forced
    by shrinking the residency threshold."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(5)
    shape = (1, 2, 256, 32)
    q, k, v, g = (jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.3)
                  for _ in range(4))
    for causal in (False, True):
        def loss_flash(q, k, v, causal=causal):
            return jnp.sum(pallas_ops.flash_attention(
                q, k, v, causal=causal, block_q=64) * g)

        def loss_ref(q, k, v, causal=causal):
            return jnp.sum(full_attention(q, k, v, causal=causal) * g)

        resident = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        old = pallas_ops._VMEM_RESIDENT_BYTES
        pallas_ops._VMEM_RESIDENT_BYTES = 1
        try:
            streamed = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        finally:
            pallas_ops._VMEM_RESIDENT_BYTES = old
        oracle = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for s, r, o in zip(streamed, resident, oracle):
            np.testing.assert_array_equal(np.asarray(s), np.asarray(r))
            np.testing.assert_allclose(np.asarray(s), np.asarray(o),
                                       rtol=5e-3, atol=5e-4)


def test_flash_attention_with_lse():
    """The with-lse entry point: out/lse match the dense formulas, the
    lse cotangent is honored (the ring-merge currency), and odd
    sequence lengths fall back to the dense path."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(4)
    B, H, T, D = 1, 2, 64, 16
    q, k, v = (jnp.asarray(rs.randn(B, H, T, D).astype(np.float32) * 0.4)
               for _ in range(3))
    out, lse = pallas_ops.flash_attention_with_lse(q, k, v, causal=True,
                                                   interpret=True)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * (D ** -0.5)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    lse_ref = jax.scipy.special.logsumexp(s, axis=-1)
    out_ref = jnp.einsum('bhqk,bhkd->bhqd',
                         jnp.exp(s - lse_ref[..., None]), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse).reshape(B, H, T),
                               np.asarray(lse_ref), rtol=2e-4, atol=2e-5)

    w = jnp.asarray(rs.randn(B * H, T, 1).astype(np.float32) * 0.3)

    def loss_flash(q):
        o, l = pallas_ops.flash_attention_with_lse(q, k, v, causal=True,
                                                   interpret=True)
        return (o * out_ref).sum() + (l * w).sum()

    def loss_dense(q):
        s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * (D ** -0.5)
        s = jnp.where(mask, s, -jnp.inf)
        l = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum('bhqk,bhkd->bhqd', jnp.exp(s - l[..., None]), v)
        return (o * out_ref).sum() + (l.reshape(B * H, T, 1) * w).sum()

    gf = jax.grad(loss_flash)(q)
    gd = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                               rtol=5e-4, atol=5e-5)

    # prime-ish length -> dense fallback, still correct
    qq = jnp.asarray(rs.randn(1, 1, 30, 8).astype(np.float32))
    o2, l2 = pallas_ops.flash_attention_with_lse(qq, qq, qq)
    assert o2.shape == qq.shape and l2.shape == (1, 30, 1)


def test_pallas_flash_accepts_cross_attention():
    """Round 5 lifted the v1 square-only constraint: rectangular
    q/k shapes are first-class (conformance in
    test_pallas_flash_rectangular; this is the API-level check that
    the old rejection is gone)."""
    from mxnet_tpu import pallas_ops
    q = jnp.ones((1, 1, 4, 8))
    k = jnp.ones((1, 1, 16, 8))
    out = pallas_ops.flash_attention(q, k, k)
    assert out.shape == q.shape


@pytest.mark.parametrize('tq,tk', [
    pytest.param(128, 512, marks=pytest.mark.slow),
    pytest.param(8, 512, marks=pytest.mark.slow),
    pytest.param(128, 384, marks=pytest.mark.slow),
    (512, 128)])
def test_pallas_flash_rectangular(tq, tk):
    """q_len != kv_len (cross-attention / KV-cache decode): forward and
    all three gradients match the dense oracle under both causal
    conventions, on every schedule (resident + forced-streaming).
    Causal rows are SUFFIX-aligned to the keys (docs/PERF.md round 5);
    full_attention shares the same convention."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(7)
    B, H, D = 2, 2, 32
    q = jnp.asarray(rs.randn(B, H, tq, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(B, H, tk, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(B, H, tk, D).astype(np.float32) * 0.3)
    g = jnp.asarray(rs.randn(B, H, tq, D).astype(np.float32))
    for causal in (False, True):
        if causal and tq > tk:
            continue  # rejected by design (suffix alignment)
        def loss_flash(q, k, v, causal=causal):
            return jnp.sum(pallas_ops.flash_attention(
                q, k, v, causal=causal, block_q=64) * g)

        def loss_ref(q, k, v, causal=causal):
            return jnp.sum(full_attention(q, k, v, causal=causal) * g)

        out = pallas_ops.flash_attention(q, k, v, causal=causal,
                                         block_q=64)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-4)
        resident = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        old = pallas_ops._VMEM_RESIDENT_BYTES
        pallas_ops._VMEM_RESIDENT_BYTES = 1
        try:
            streamed = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        finally:
            pallas_ops._VMEM_RESIDENT_BYTES = old
        oracle = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for s, r, o in zip(streamed, resident, oracle):
            np.testing.assert_allclose(np.asarray(s), np.asarray(r),
                                       rtol=5e-3, atol=5e-4)
            np.testing.assert_allclose(np.asarray(s), np.asarray(o),
                                       rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize('schedule', ['resident', 'streaming',
                                      'xla-backward'])
@pytest.mark.parametrize('tq,tk', [(64, 64), (32, 128), (8, 64)])
@pytest.mark.parametrize('dk,dv', [(12, 6), (192, 128)])
def test_pallas_flash_value_width_of_its_own(monkeypatch, dk, dv, tq, tk,
                                             schedule):
    """Keys wider than values (latent attention: 192 over 128), square
    and with tq < tk, causal and not: forward and all three gradients
    match the dense oracle on the forward's resident and streaming
    schedule, through the backward kernel with several blocks a side
    and through the XLA-level blocked recompute that takes over where
    the kernel's dQ accumulator would not fit."""
    from mxnet_tpu import pallas_ops
    monkeypatch.setattr(pallas_ops, '_BWD_BLOCK', 16)
    if schedule == 'streaming':
        monkeypatch.setattr(pallas_ops, '_VMEM_RESIDENT_BYTES', 1)
    if schedule == 'xla-backward':
        monkeypatch.setattr(pallas_ops, '_BWD_ACC_BYTES', 1)
    rs = np.random.RandomState(11)
    B, H = 1, 2
    q = jnp.asarray(rs.randn(B, H, tq, dk).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(B, H, tk, dk).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(B, H, tk, dv).astype(np.float32) * 0.3)
    g = jnp.asarray(rs.randn(B, H, tq, dv).astype(np.float32))
    for causal in (False, True):
        def loss_flash(q, k, v, causal=causal):
            return jnp.sum(pallas_ops.flash_attention(
                q, k, v, causal=causal, block_q=16) * g)

        def loss_ref(q, k, v, causal=causal):
            return jnp.sum(full_attention(q, k, v, causal=causal) * g)

        out = pallas_ops.flash_attention(q, k, v, causal=causal, block_q=16)
        assert out.shape == (B, H, tq, dv)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(full_attention(q, k, v,
                                                       causal=causal)),
            rtol=2e-3, atol=2e-4)
        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        oracle = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, o in zip(got, oracle):
            assert a.shape == o.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                       rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize('dk,dv', [(12, 6), (192, 128)])
def test_flash_attention_with_lse_value_width_of_its_own(dk, dv):
    """The with-lse entry at dk != dv: out and lse match the dense
    formulas, and the cotangent of the SECOND output (the log-sum-exp)
    reaches q and k (v's gradient does not depend on it)."""
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(12)
    B, H, T = 1, 2, 32
    q = jnp.asarray(rs.randn(B, H, T, dk).astype(np.float32) * 0.4)
    k = jnp.asarray(rs.randn(B, H, T, dk).astype(np.float32) * 0.4)
    v = jnp.asarray(rs.randn(B, H, T, dv).astype(np.float32) * 0.4)
    wo = jnp.asarray(rs.randn(B, H, T, dv).astype(np.float32))
    wl = jnp.asarray(rs.randn(B * H, T, 1).astype(np.float32) * 0.3)

    def loss_flash(q, k, v):
        o, l = pallas_ops.flash_attention_with_lse(
            q, k, v, causal=True, block_q=16, interpret=True)
        return (o * wo).sum() + (l * wl).sum()

    def loss_dense(q, k, v):
        o, l = pallas_ops._dense_attention_lse(q, k, v, True, dk ** -0.5)
        return (o * wo).sum() + (l * wl).sum()

    out, lse = pallas_ops.flash_attention_with_lse(
        q, k, v, causal=True, block_q=16, interpret=True)
    ref, lse_ref = pallas_ops._dense_attention_lse(q, k, v, True,
                                                   dk ** -0.5)
    assert out.shape == (B, H, T, dv) and lse.shape == (B * H, T, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_rectangular_validation():
    from mxnet_tpu import pallas_ops
    q = jnp.zeros((1, 1, 64, 16))
    k = jnp.zeros((1, 1, 32, 16))
    v = jnp.zeros((1, 1, 32, 16))
    with pytest.raises(ValueError, match='q_len <= kv_len'):
        pallas_ops.flash_attention(q, k, v, causal=True)
    # a v of another LENGTH than k's is refused; of another width it
    # is not (the values' width is their own)
    with pytest.raises(ValueError, match='identical k/v'):
        pallas_ops.flash_attention(q, k, jnp.zeros((1, 1, 16, 16)))
    with pytest.raises(ValueError, match='identical k/v'):
        pallas_ops.flash_attention_with_lse(q, k, jnp.zeros((1, 1, 16, 8)))
    out = pallas_ops.flash_attention(q, k, jnp.zeros((1, 1, 32, 8)))
    assert out.shape == (1, 1, 64, 8)
    # the dense fallback enforces the same convention
    with pytest.raises(ValueError, match='q_len <= kv_len'):
        full_attention(q, k, v, causal=True)
    # non-causal tq > tk is legal
    out = pallas_ops.flash_attention(q, k, v, causal=False)
    assert out.shape == q.shape


def test_pallas_flash_fallback_predicate_matches_kernels():
    """The dense-fallback predicate must derive k-block caps from the
    POST-fit q block exactly as the kernels do: with a pre-fit cap,
    (tq=8, tk=258, block_q=320) passed the predicate but the forward
    kernel raised instead of falling back (round-5 review repro)."""
    import jax.numpy as jnp
    from mxnet_tpu import pallas_ops
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 8, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 1, 258, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 1, 258, 16).astype(np.float32))
    assert pallas_ops._needs_dense_fallback(8, 258, 320)
    out = pallas_ops.flash_attention(q, k, v, block_q=320)
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(16.0)
    ref = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
