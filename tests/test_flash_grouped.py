"""Grouped key-value heads and a sliding window inside the flash kernels
(pallas_ops.flash_attention): forward and all three gradients against a
dense masked float32 softmax written here, in Pallas interpret mode at
tiny shapes, so the kernels' own code runs.  T = 64 with tiles of 16:
four tiles a side, forward and (the backward's tile edge cut to the
same) backward."""
import hashlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu import pallas_ops
from mxnet_tpu.ops import lm

T, TILE, KV, DK, DV = 64, 16, 2, 12, 6
WINDOWS = {'none': None, 'under-a-tile': 5, 'a-tiles-edge': 16,
           'over-two-tiles': 40, 'every-key': 64}


def rand(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32) * 0.5)


def operands(group, t=T, kv=KV):
    return (rand(1, 1, kv * group, t, DK), rand(2, 1, kv, t, DK),
            rand(3, 1, kv, t, DV))


def dense(q, k, v, window):
    """softmax over the keys a row sees, every query head against the
    key-value head it shares: (out, log-sum-exp (heads, t, 1))."""
    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                   precision='highest') * DK ** -0.5
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (ahead >= 0) & (ahead < (window or t))
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', jnp.exp(s - lse[..., None]), v,
                     precision='highest')
    return out, lse.reshape(-1, t, 1)


def schedule(monkeypatch, which):
    """Tiles of TILE in the backward too, and the schedule asked for."""
    monkeypatch.setattr(pallas_ops, '_BWD_BLOCK', TILE)
    if which == 'streaming':
        monkeypatch.setattr(pallas_ops, '_VMEM_RESIDENT_BYTES', 1)
    if which == 'xla-backward':
        monkeypatch.setattr(pallas_ops, '_BWD_ACC_BYTES', 1)


def value_and_grads(fn, weight, *args):
    return jax.jit(lambda *a: (fn(*a),) + jax.grad(
        lambda *b: jnp.sum(fn(*b) * weight), argnums=(0, 1, 2))(*a))(*args)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize('which', ['resident', 'streaming'])
@pytest.mark.parametrize('window', sorted(WINDOWS))
@pytest.mark.parametrize('group', [1, 2, 8])
def test_kernels_against_a_dense_masked_softmax(monkeypatch, group, window,
                                                which):
    """Keys of 12 over values of 6; dK and dV are the sums over the
    group's heads."""
    schedule(monkeypatch, which)
    window = WINDOWS[window]
    q, k, v = operands(group)
    weight = rand(4, 1, KV * group, T, DV)
    got = value_and_grads(
        lambda *a: pallas_ops.flash_attention(
            *a, causal=True, block_q=TILE, window=window), weight, q, k, v)
    want = value_and_grads(lambda *a: dense(*a, window)[0], weight, q, k, v)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0
        close(a, b)


@pytest.mark.parametrize('group,window', [(1, 40), (8, None), (8, 5),
                                          (8, 40)])
def test_the_xla_backward_takes_groups_and_windows(monkeypatch, group,
                                                   window):
    """Where the dQ accumulator would not fit VMEM the blocked recompute
    stands in, the group's heads as more rows of their key-value head."""
    schedule(monkeypatch, 'xla-backward')
    q, k, v = operands(group)
    weight = rand(4, 1, KV * group, T, DV)
    got = value_and_grads(
        lambda *a: pallas_ops.flash_attention(
            *a, causal=True, block_q=TILE, window=window), weight, q, k, v)
    want = value_and_grads(lambda *a: dense(*a, window)[0], weight, q, k, v)
    for a, b in zip(got, want):
        close(a, b)


@pytest.mark.parametrize('group,window', [(1, 24), (2, None), (8, 5),
                                          (8, 40)])
def test_with_lse_takes_a_cotangent_on_the_log_sum_exp(monkeypatch, group,
                                                       window):
    schedule(monkeypatch, 'resident')
    q, k, v = operands(group)
    wo, wl = rand(4, 1, KV * group, T, DV), rand(5, KV * group, T, 1)

    def loss(fn):
        def of(q, k, v):
            out, lse = fn(q, k, v)
            return (out * wo).sum() + (lse * wl).sum()
        return of

    kernel = lambda *a: pallas_ops.flash_attention_with_lse(
        *a, causal=True, block_q=TILE, window=window)
    for a, b in zip(kernel(q, k, v), dense(q, k, v, window)):
        close(a, b)
    for a, b in zip(jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
                    jax.grad(loss(lambda *a: dense(*a, window)),
                             (0, 1, 2))(q, k, v)):
        close(a, b)


def tiles_with_a_visible_pair(t, tile, window):
    """(q tile, k tile) pairs that hold a pair the masks let through,
    a row at a time."""
    return {(i // tile, j) for i in range(t)
            for j in range(max(0, i - (window or t) + 1) // tile,
                           i // tile + 1)}


@pytest.mark.parametrize('t,tile,window', [
    (64, 16, 5), (64, 16, 16), (64, 16, 17), (64, 16, 40), (64, 16, None),
    (128, 16, 5), (8192, 512, 2048), (8192, 1024, 2048), (8192, 256, 2048),
    (8192, 1024, None)])
def test_the_grids_hold_the_bands_tiles_and_no_other(t, tile, window):
    """What visited_positions counts, and the extents of the windowed
    grids' inner dimensions, are those of the tiles that hold a visible
    pair."""
    needed = tiles_with_a_visible_pair(t, tile, window)
    assert pallas_ops.visited_positions(t, tile, window) == \
        len(needed) * tile * tile
    if window is not None:
        row, column = pallas_ops._band_steps(t, tile, window)
        assert row == max(sum(1 for q, _ in needed if q == i)
                          for i in range(t // tile))
        assert column == max(sum(1 for _, k in needed if k == j)
                             for j in range(t // tile))
    if (t, window) == (8192, 2048):
        # the cell's windowed layers, by the tile's edge
        assert round(len(needed) * tile * tile / 14681088, 3) == {
            256: 1.125, 512: 1.25, 1024: 1.5}[tile]


@pytest.mark.parametrize('which', ['resident', 'streaming'])
def test_a_window_under_a_tile_visits_no_tile_left_of_the_band(monkeypatch,
                                                               which):
    """A tile that is scored and masked would still multiply its zero
    weights into the values: with not-a-number there the result says
    whether a tile was visited.  Going forward the last q tile (rows
    48 to 63, window 5) reads k tiles 2 and 3 alone; going backward
    the first k tile (keys 0 to 15) is reached by q tiles 0 and 1
    alone."""
    schedule(monkeypatch, which)
    window, group = 5, 2
    q, k, v = operands(group)
    weight = rand(4, 1, KV * group, T, DV)
    core = lambda *a: pallas_ops.flash_attention(
        *a, causal=True, block_q=TILE, window=window)
    want = value_and_grads(lambda *a: dense(*a, window)[0], weight, q, k, v)
    poisoned = core(q, k.at[:, :, :2 * TILE].set(np.nan),
                    v.at[:, :, :2 * TILE].set(np.nan))
    close(poisoned[:, :, 3 * TILE:], want[0][:, :, 3 * TILE:])
    assert np.isnan(np.asarray(poisoned[:, :, :TILE])).all()
    _, dq, dk, dv = value_and_grads(
        core, weight.at[:, :, 2 * TILE:].set(np.nan),
        q.at[:, :, 2 * TILE:].set(np.nan), k, v)
    close(dq[:, :, :TILE], want[1][:, :, :TILE])
    close(dk[:, :, :TILE], want[2][:, :, :TILE])
    close(dv[:, :, :TILE], want[3][:, :, :TILE])
    assert np.isnan(np.asarray(dk[:, :, 2 * TILE:])).all()


# sha256 of the programs the parent of PR 35 lowered these calls to (jax
# 0.9.0, the CPU backend, the kernels interpreted: the text holds the
# kernels' own operations and no source locations).  Ungrouped heads
# without a window are Kanana's cell and the ring's hops: a PR that
# leaves them alone keeps these.
UNGROUPED_TEXT_SHA256 = {
    'resident': '65378cc47c5ec784c9a9b21b3a8fd6a41c2a8bc9707ec9ead57c853f77ecb421',
    'streaming': '8bd1b290c009573216bae32a01a7efe5db85bef8404e36f6c276636638b162cd',
    'with-lse': '7642a4ac2a0097b827e3c045e73c30a6f513f3de60a97d20c271d7382828305c',
}


@pytest.mark.parametrize('which', sorted(UNGROUPED_TEXT_SHA256))
def test_ungrouped_heads_without_a_window_lower_to_the_parents_program(
        monkeypatch, which):
    schedule(monkeypatch, 'streaming' if which == 'streaming'
             else 'resident')
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
              ((2, 4, T, DK), (2, 4, T, DK), (2, 4, T, DV))]

    def loss(q, k, v):
        if which == 'with-lse':
            out, lse = pallas_ops.flash_attention_with_lse(
                q, k, v, causal=True, block_q=TILE, interpret=True)
            return out.sum() + lse.sum()
        return pallas_ops.flash_attention(
            q, k, v, causal=True, block_q=TILE, interpret=True).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*shapes).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        UNGROUPED_TEXT_SHA256[which]


def test_what_the_kernels_refuse_says_so():
    q, k, v = operands(2)
    with pytest.raises(ValueError, match='divide the query heads'):
        pallas_ops.flash_attention(q[:, :3], k, v, causal=True)
    with pytest.raises(ValueError, match='a window is'):
        pallas_ops.flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match='a window is'):
        pallas_ops.flash_attention(q[:, :, :32], k, v, causal=True, window=8)
    with pytest.raises(ValueError, match='a window is'):
        pallas_ops.flash_attention_with_lse(q, k, v, causal=True, window=0)


@pytest.mark.parametrize('window', [None, 7])
def test_a_ragged_length_takes_dense_attention_groups_and_window_too(window):
    """No block of 8 rows divides 36: the dense route, natively
    differentiable."""
    q, k, v = operands(2, t=36)
    close(pallas_ops.flash_attention(q, k, v, causal=True, window=window),
          dense(q, k, v, window)[0])
    for a, b in zip(pallas_ops.flash_attention_with_lse(
            q, k, v, causal=True, window=window), dense(q, k, v, window)):
        close(a, b)


@pytest.mark.parametrize('window,cap,tile', [
    (2048, 1024, 1024), (4096, 1024, 1024), (1023, 1024, 512),
    (1024, 1024, 1024), (512, 1024, 512), (5, 1024, 128), (40, 16, 16),
    (5, 16, 16)])
def test_the_tile_under_a_window_follows_the_window(window, cap, tile):
    assert pallas_ops.window_block(window, cap) == tile


@pytest.mark.parametrize('group,window', [(8, None), (8, 24), (1, 24)])
def test_causal_attention_lays_grouped_heads_out_for_the_kernels(group,
                                                                 window):
    """causal_attention's (B, T, kv, group, d) against the same dense
    softmax: head h of key-value head j is query head j * group + h."""
    q, k, v = operands(group)
    by_row = lambda x: jnp.swapaxes(x, 1, 2)
    got = lm.causal_attention(
        by_row(q).reshape(1, T, KV, group, DK), by_row(k), by_row(v),
        DK ** -0.5, block_q=TILE, window=window)
    assert got.shape == (1, T, KV, group, DV)
    close(by_row(got.reshape(1, T, KV * group, DV)),
          dense(q, k, v, window)[0])
