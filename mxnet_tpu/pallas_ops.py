"""In-tree Pallas TPU kernels for hot ops.

The reference hand-writes CUDA for its hottest kernels; the TPU
counterpart is Pallas (jax.readthedocs.io/en/latest/pallas).  Four
families live here: flash attention, (further down) the gated delta
rule's chunks and the loop over them, rows added to their tokens by
DMA (SparseMoE's combine) and (last) CausalConv1D's depthwise pass.

Flash attention — a (batch*head, q-block, k-block) grid streams K/V
blocks through VMEM with the online-softmax recurrence in fp32 scratch,
so neither the T^2 score matrix nor the full K/V sequence ever sits in
VMEM/HBM at once, and causal q-tiles skip their fully-masked k-blocks
and mask only the tiles the diagonal crosses.  q is (batch, heads, T,
dk), k (batch, kv_heads, T, dk); v (batch, kv_heads, T, dv) and the
output (batch, heads, T, dv): the values' width is their own (latent
attention has keys of 192 over values of 128), so q, k, dQ, dK and
their blocks are dk wide and v, o, dO, dV and the accumulators dv wide.
Grouped heads: kv_heads divides heads, query head i reads key-value
head i // group through the BlockSpec index maps (K and V are never
repeated in HBM), and the backward kernel walks the q-blocks of all
the heads of a group under one k-block, so dK and dV sum over the group
in their float32 scratch.  A sliding window (causal self-attention:
row i sees key j iff 0 <= i - j < window) is a second mask on the tiles
the band's left edge crosses, and the grids' inner dimensions shrink to
the widest band: the forward's k-blocks count from a q-tile's first
visible one, the backward's q-blocks stop at the last whose rows still
see the k-block.  Ungrouped heads without a window lower to the program
they lowered to before either existed.  Available as
`pallas_ops.flash_attention`, opt-in via
`parallel.ring_attention.full_attention(use_flash=True)`, and under
`ops.lm.causal_attention` (the LatentAttention and GatedAttention
operators).

Backward is ONE Pallas kernel: the forward saves the per-row logsumexp,
D = rowsum(dO∘O) is a fused XLA preprocess, and the kernel (gridded
over k-blocks, q-blocks innermost) recomputes p = exp(s − lse) once a
tile for all three gradients, dK/dV in float32 scratch a k-block, dQ in
a float32 scratch that holds the whole sequence of every head of the
group — nothing O(T^2) is materialized.  Operands keep their type (bf16
in the cells); scores, max, sums and accumulators are float32, p and dS
are cast to the operands' type for their products.

Which shapes take which path (lanes counted as VMEM holds them, a width
of 192 as 256).  Forward (`_fwd_resident`): the resident schedule keeps
one head's K and V in VMEM and loops over their blocks; the streaming
schedule puts that loop on the grid and holds O(block) for any T.  At
d = 128 in bf16 the forward is resident up to T = 12,288 under the
default tiles; tiles of 1024 x 1024 at T = 8,192 (keys of 192 over
values of 128, heads of 128 and of 256) stream.  Backward
(`_flash_bwd_shared`): the kernel wherever its dQ accumulator
(group x tq x dk float32) is at most 64 MiB: T = 131,072 at d = 128
ungrouped, and the cells' 8 heads a group at T = 8,192 (32 MiB at d =
128, 64 MiB at 256, beside the tiles' 32: both compile for a v5e's
128 MiB); beyond, and for lengths no block of whole sublanes divides,
dense attention (forward) and an XLA-level blocked recompute (backward),
which take groups and a window too.  Every pallas_call is named
(`flash_attention_fwd_stream`, `flash_attention_fwd_resident`,
`flash_attention_bwd`): the names are the custom calls' in a trace.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def default_interpret(*operands):
    """Pallas interpret mode for a call on `operands` when the caller
    did not choose: False wherever the call will run on a TPU (Mosaic
    compiles it), True elsewhere (Mosaic targets nothing else).
    Concrete arrays answer with the devices they live on; tracers
    (inside jit / shard_map) carry no placement, and jit runs
    uncommitted work on the default backend, so that answers for
    them."""
    for x in operands:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return any(d.platform != 'tpu' for d in x.devices())
    return jax.default_backend() != 'tpu'


def _lanes(d):
    """A head width as VMEM holds it: rounded up to whole 128-lane
    tiles (latent attention's keys of 192 occupy 256)."""
    return -(-d // 128) * 128


def _sublanes(rows):
    """Rows of float32 as VMEM holds them: whole tiles of 8."""
    return -(-rows // 8) * 8


# what a masked score reads under a window.  There a row's first live
# tile may hold none of its keys, and -inf as the row's running maximum
# would make exp(-inf - -inf); with a finite floor the tile's weights
# are wiped by the correction factor once a visible key arrives (every
# row sees itself).  Without a window every row sees its first tile's
# first key, and the mask stays -inf
_MASKED_SCORE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _scores(q, kblk, scale, row0, col0, masked, window=None):
    """Scaled scores (rows, cols) of a q tile against a k tile in
    float32; `masked` (a Python bool) applies the masks of a tile an
    edge crosses: rows from `row0` see columns from `col0` up to their
    own index (the diagonal) and, with `window`, no further back than
    `window - 1` before it (the band's left edge).  Tiles wholly
    between the edges skip it: the mask would change nothing there."""
    s = lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if window is None:
            s = jnp.where(rows >= cols, s, -jnp.inf)
        else:
            s = jnp.where((rows >= cols) & (rows - cols < window), s,
                          _MASKED_SCORE)
    return s


def _online_softmax_step(q, kblk, vblk, m, l, acc, scale, masked,
                         row0, col0, window=None):
    """One K-block of the online-softmax recurrence — the ONE numerics
    definition both schedules share.  q, kblk are dk wide, vblk and acc
    dv wide."""
    s = _scores(q, kblk, scale, row0, col0, masked, window)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc * correction + pv


def _last_live_kb(qi, block_q, block_k, num_kb, offset):
    """The last k block a causal q tile sees (diagonal inclusive)."""
    return jnp.minimum(
        (qi * block_q + block_q - 1 + offset) // block_k, num_kb - 1)


def _first_masked_kb(qi, block_q, block_k, num_kb, offset):
    """The first k block the diagonal crosses for a causal q tile: the
    blocks before it lie wholly under the diagonal (their last column
    is no later than the tile's first row)."""
    return jnp.minimum((qi * block_q + offset + 1) // block_k, num_kb)


def _first_live_qb(kb, block_q, block_k, offset):
    """The first q block whose rows reach a causal k block's columns."""
    return jnp.maximum(kb * block_k - offset, 0) // block_q


def _first_unmasked_qb(kb, block_q, block_k, num_qb, offset):
    """The first q block wholly under the diagonal of a causal k block
    (its first row is no earlier than the block's last column)."""
    return jnp.clip(
        (kb * block_k + block_k - 1 - offset + block_q - 1) // block_q,
        0, num_qb)


# The band of a window (square self-attention, offset 0: row i sees
# keys i - window + 1 .. i).  Traced forms for the kernels and index
# maps, and `_band` in Python ints for the grids' extents and the count
# of positions visited.

def _band_first_kb(qi, block_q, block_k, window):
    """The k block that holds the first key a q tile's first row sees."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _band_inside_kb(qi, block_q, block_k, window):
    """The first k block wholly right of the band's left edge for a q
    tile: its first column is within the window of the tile's last
    row."""
    return jnp.maximum(
        qi * block_q + block_q - 1 - window + block_k, 0) // block_k


def _band_last_qb(kb, block_q, block_k, num_qb, window):
    """The last q block with a row that still sees a k block's columns."""
    return jnp.minimum(
        (kb * block_k + block_k - 1 + window - 1) // block_q, num_qb - 1)


def _band_crossed_qb(kb, block_q, block_k, window):
    """The first q block the band's left edge crosses for a k block:
    its last row no longer sees the block's first column."""
    return (kb * block_k + window) // block_q


def _band(t, block, window):
    """(first, last) k block of every q tile of a windowed square call
    with tiles of `block` x `block`, in Python ints."""
    return [(max(r0 - (window - 1), 0) // block, r0 // block)
            for r0 in range(0, t, block)]


def _band_steps(t, block, window):
    """Tiles of the widest row of the band (a q tile's k blocks) and of
    its widest column (a k block's q tiles): the extents of the
    windowed grids' inner dimensions."""
    band = _band(t, block, window)
    return (max(last - first + 1 for first, last in band),
            max(sum(first <= kb <= last for first, last in band)
                for kb in range(len(band))))


def visited_positions(t, block, window=None):
    """Query-key positions one head's forward scores over a causal
    square call of length t with tiles of `block` (fitted to t as the
    kernels fit it): whole tiles on and under the diagonal and, with a
    window, from the band's left edge on: what the grids compute."""
    block = _try_fit(t, block)
    band = _band(t, block, t if window is None else window)
    return block * block * sum(last - first + 1 for first, last in band)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                 acc_ref, *, scale, causal, block_q, block_k, num_kb,
                 offset, steps, window=None):
    """One (bh, qi, kb) grid step of the streaming schedule.  kb is the
    minor grid dim: scratch (m, l, acc) carries the online softmax
    across kb steps; the last step writes o_ref and the per-row
    logsumexp (saved for the fused backward).  `offset` = tk - tq:
    causal q rows sit suffix-aligned against the keys (KV-decode
    convention); 0 for square self-attention.  The minor dim has
    `steps` steps: num_kb, or with `window` the widest band's, counted
    from the q tile's first visible k block."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kb = step if window is None else \
        step + _band_first_kb(qi, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(masked):
        m_new, l_new, acc_new = _online_softmax_step(
            q_ref[0], k_ref[0], v_ref[0], m_ref[...], l_ref[...],
            acc_ref[...], scale, masked, qi * block_q + offset,
            kb * block_k, window)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    if causal:
        first_masked = _first_masked_kb(qi, block_q, block_k, num_kb,
                                        offset)
        if window is None:
            pl.when(kb < first_masked)(lambda: compute(False))
            pl.when((kb >= first_masked) &
                    (kb <= _last_live_kb(qi, block_q, block_k, num_kb,
                                         offset)))(lambda: compute(True))
        else:
            # tiles between the band's left edge and the diagonal need
            # no mask; a tile either edge crosses takes both
            plain = (kb >= _band_inside_kb(qi, block_q, block_k, window)) \
                & (kb < first_masked)
            pl.when(plain)(lambda: compute(False))
            pl.when(~plain & (kb <= _last_live_kb(
                qi, block_q, block_k, num_kb, offset)))(
                    lambda: compute(True))
    else:
        compute(False)

    @pl.when(step == steps - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _attn_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                          causal, block_q, block_k, num_kb, offset,
                          window=None):
    """Resident-K schedule: the whole K/V sequence for one head sits in
    VMEM (fetched once per head, once per group of heads that share
    it); a fori_loop walks k-blocks with the online-softmax recurrence,
    and causal q-tiles stop at the diagonal and, with `window`, start
    at the band's left edge (skipping both compute AND reads of what
    the masks hide).  Fastest when K/V fit in VMEM."""
    q = q_ref[0]                          # (block_q, dk)
    qi = pl.program_id(1)
    carry = (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32))

    def body(masked, kb, carry):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        return _online_softmax_step(q, kblk, vblk, *carry, scale, masked,
                                    qi * block_q + offset, kb * block_k,
                                    window)

    if causal:
        first_masked = _first_masked_kb(qi, block_q, block_k, num_kb,
                                        offset)
        if window is None:
            carry = lax.fori_loop(0, first_masked,
                                  functools.partial(body, False), carry)
            carry = lax.fori_loop(
                first_masked,
                _last_live_kb(qi, block_q, block_k, num_kb, offset) + 1,
                functools.partial(body, True), carry)
        else:
            # the tiles the band's left edge crosses, the unmasked ones
            # between the edges, those on the diagonal; a window under
            # a tile leaves the middle loop empty
            stop = _last_live_kb(qi, block_q, block_k, num_kb, offset) + 1
            inside = jnp.clip(
                _band_inside_kb(qi, block_q, block_k, window), 0, stop)
            first_masked = jnp.clip(first_masked, inside, stop)
            carry = lax.fori_loop(
                _band_first_kb(qi, block_q, block_k, window), inside,
                functools.partial(body, True), carry)
            carry = lax.fori_loop(inside, first_masked,
                                  functools.partial(body, False), carry)
            carry = lax.fori_loop(first_masked, stop,
                                  functools.partial(body, True), carry)
    else:
        carry = lax.fori_loop(0, num_kb, functools.partial(body, False),
                              carry)
    m, l, acc = carry
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


# resident-K schedule is used while K+V for one head fit comfortably in
# VMEM (~16 MB/core); beyond that the 3D-grid streaming schedule keeps
# VMEM bounded at O(block) regardless of T.  The budget must leave room
# for Mosaic's double-buffered window of the SAME resident operands
# (measured: a 10 MB threshold OOMs at 2x), hence ~6 MB.
_VMEM_RESIDENT_BYTES = 6 * 1024 * 1024

# backward tile edge (see _bwd_blocks); 1024 measured best on
# v5e-class among 256 to 2048
_BWD_BLOCK = 1024

# scoped VMEM the backward kernel asks for beside its dQ accumulator:
# the double-buffered blocks and a tile's float32 temporaries (scores,
# p, dP, dS and three products).  Tiles of 1024 x 1024 over heads of
# 256 need more than Mosaic's default 16 MiB and compile in 32
_BWD_TILE_VMEM_BYTES = 32 * 1024 * 1024

# the largest dQ accumulator (tq x dk float32, lanes as VMEM holds
# them) the kernel takes: with the tiles' share it stays inside a
# v5e's 128 MiB of VMEM (T = 131,072 at heads of 128 compiles for it);
# longer sequences take the XLA-level blocked recompute
_BWD_ACC_BYTES = 64 * 1024 * 1024


def _pair_bytes(t, dk, dv, itemsize):
    """Bytes of VMEM one head's K and V take, dk + dv wide, lanes
    counted as VMEM holds them."""
    return t * (_lanes(dk) + _lanes(dv)) * itemsize


def _fwd_resident(tk, dk, dv, itemsize, block_q, block_k):
    """Whether the forward keeps one head's K and V in VMEM.  Mosaic
    holds the pair twice (its double-buffered window) beside a tile's
    float32 scores and probabilities, and the sum has to leave q, o and
    the accumulators their room in the 16 MB scoped VMEM: a 6 MiB pair
    compiles with tiles of 384 x 384 (T=12288, d=128) and is refused,
    20.3 MB of 16, with tiles of 1024 x 1024 (T=8192, keys of 192 over
    values of 128; v5e, libtpu 0.0.34)."""
    return 2 * _pair_bytes(tk, dk, dv, itemsize) + \
        2 * 4 * block_q * block_k <= _VMEM_RESIDENT_BYTES * 7 // 3


def _try_fit(t, cap):
    """Largest block <= cap dividing t (halving from cap) — the ONE
    divisibility rule every schedule and the dense-fallback predicate
    share, so they can never disagree about a shape's viability."""
    b = min(cap, t)
    while t % b:
        b //= 2
    return b


def _tiles(t, block):
    """Whether blocks of `block` rows are ones Mosaic takes for a
    sequence of t: whole sublanes (8 rows; it cannot place a block of 33
    even where that is the whole sequence), or a sequence of at most
    one."""
    return block % 8 == 0 or t <= 8


def _fit_block(t, block_q):
    """_try_fit, raising on degenerate results.  Sequence lengths with
    no small power-of-two factor (e.g. prime T) would degenerate to
    1-row blocks that Mosaic rejects or runs pathologically — raise
    with guidance instead."""
    b = _try_fit(t, block_q)
    if not _tiles(t, b):
        raise ValueError(
            'flash_attention: sequence length %d has no block of whole '
            'sublanes (a multiple of 8 rows dividing it); pad the '
            'sequence to a multiple of 128 or use full_attention for '
            'unaligned lengths' % t)
    return b


def _schedule_caps(tq, tk, block_q):
    """The (q, k) block caps each schedule fits with — forward first,
    then backward (which prefers larger tiles, _BWD_BLOCK).  The k caps
    derive from the POST-fit q blocks, exactly as the kernel impls
    compute them — a cap from the user's pre-fit block_q can disagree
    with the kernels and turn the promised dense fallback into a
    raise (e.g. tq=8, tk=258, block_q=320)."""
    fq = _try_fit(tq, block_q)
    bq = _try_fit(tq, max(block_q, _BWD_BLOCK))
    fwd_k = fq if tq == tk else max(fq, 256)
    bwd_k = bq if tq == tk else max(bq, _BWD_BLOCK)
    return ((tq, block_q), (tk, fwd_k),
            (tq, max(block_q, _BWD_BLOCK)), (tk, bwd_k))


def _flash_fwd_impl(q, k, v, causal, scale, block_q, interpret,
                    return_lse=False, window=None):
    b, h, tq, dk = q.shape
    kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // kv
    offset = tk - tq          # causal rows suffix-align to the keys
    bh = b * h
    qf = q.reshape(bh, tq, dk)
    kf = k.reshape(b * kv, tk, dk)
    vf = v.reshape(b * kv, tk, dv)
    # query head i reads key-value head i // group: K and V are never
    # repeated in HBM, and the heads of a group follow one another, so
    # the resident schedule fetches their pair once
    kv_head = (lambda i: i) if group == 1 else (lambda i: i // group)
    block_q = _fit_block(tq, block_q)
    block_k = _fit_block(tk, block_q if tq == tk else max(block_q, 256))
    num_kb = tk // block_k
    resident = _fwd_resident(tk, dk, dv, jnp.dtype(q.dtype).itemsize,
                             block_q, block_k)
    # lse rides along as (bh, tq, 1): the trailing singleton keeps the
    # row axis on the sublane dim so (block_q, 1) kernel views
    # broadcast directly against (block_q, block_k) scores
    out_shapes = [jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
                  jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)]
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, num_kb=num_kb, offset=offset,
                  window=window)

    if resident:
        out, lse = pl.pallas_call(
            functools.partial(_attn_kernel_resident, **static),
            grid=(bh, tq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, dk), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, tk, dk), lambda i, j: (kv_head(i), 0, 0)),
                pl.BlockSpec((1, tk, dv), lambda i, j: (kv_head(i), 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            ],
            out_shape=out_shapes,
            interpret=interpret,
            name='flash_attention_fwd_resident',
        )(qf, kf, vf)
        out = out.reshape(b, h, tq, dv)
        return (out, lse) if return_lse else out

    steps = num_kb
    if window is not None:
        # the k dimension of the grid is as long as the widest band and
        # counts from a q tile's first visible block; steps past the
        # diagonal repeat its block (no fetch) and compute nothing
        steps = _band_steps(tq, block_q, window)[0]
        kv_index = lambda i, j, n: (
            kv_head(i), jnp.minimum(
                n + _band_first_kb(j, block_q, block_k, window),
                (j * block_q + block_q - 1) // block_k), 0)
    elif causal:
        # clamp masked k-blocks to the diagonal: repeated block indices
        # skip the HBM->VMEM fetch (compute is gated by pl.when)
        kv_index = lambda i, j, n: (
            kv_head(i), jnp.minimum(
                n, (j * block_q + block_q - 1 + offset) // block_k), 0)
    else:
        kv_index = lambda i, j, n: (kv_head(i), n, 0)
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, steps=steps, **static),
        grid=(bh, tq // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, dk), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_k, dk), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, n: (i, j, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # normalizer
            pltpu.VMEM((block_q, dv), jnp.float32),    # output accum
        ],
        interpret=interpret,
        name='flash_attention_fwd_stream',
    )(qf, kf, vf)
    out = out.reshape(b, h, tq, dv)
    return (out, lse) if return_lse else out


def _blocked_backward(q, k, v, g, causal, scale, block_q, glse=None,
                      group=1, window=None):
    """Recompute-based gradients, q-block at a time: live memory is
    O(block_q * T) instead of the dense O(T^2).  q (bh, group * t, dk):
    the heads of a group, one after another, as more rows of their
    key-value head; k (bh, tk, dk); v (bh, tk, dv); g like q, dv wide.
    dK and dV sum over the q blocks, so over the group, in float32.
    glse: optional logsumexp cotangent, folded into the softmax vjp."""
    bh, t, dk_w = q.shape
    tk = k.shape[1]
    offset = tk - t // group
    block_q = _fit_block(t // group, block_q)
    nq = t // block_q
    qb = q.reshape(bh, nq, block_q, dk_w)
    gb = g.reshape(bh, nq, block_q, g.shape[-1])
    lb = (jnp.zeros((bh, nq, block_q, 1), jnp.float32) if glse is None
          else glse.astype(jnp.float32).reshape(bh, nq, block_q, 1))

    def one_block(carry, blk):
        dk, dv = carry
        qi, qblk, gblk, lblk = blk
        s = jnp.einsum('bqd,bkd->bqk', qblk, k).astype(
            jnp.float32) * scale                       # (bh, bq, Tk)
        if group > 1:
            qi = qi % (nq // group)         # the block's place in its head
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, (block_q, tk), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_q, tk), 1)
            keep = rows >= cols
            if window is not None:
                keep &= rows - cols < window
            s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        pv = p.astype(v.dtype)
        dp = jnp.einsum('bqd,bkd->bqk', gblk, v).astype(jnp.float32)
        # softmax vjp (+ lse cotangent): ds = p * (dp - sum(dp*p) + glse)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True) + lblk)
        dq_blk = jnp.einsum('bqk,bkd->bqd', ds, k.astype(
            jnp.float32)) * scale
        dk = dk + jnp.einsum('bqk,bqd->bkd', ds, qblk.astype(
            jnp.float32)) * scale
        dv = dv + jnp.einsum('bqk,bqd->bkd', pv.astype(jnp.float32),
                             gblk.astype(jnp.float32))
        return (dk, dv), dq_blk.astype(q.dtype)

    idx = jnp.arange(nq)
    (dk, dv), dq_blocks = lax.scan(
        one_block,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)),
        (idx, qb.transpose(1, 0, 2, 3), gb.transpose(1, 0, 2, 3),
         lb.transpose(1, 0, 2, 3)))
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(bh, t, dk_w)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Fused Pallas backward: ONE kernel.  Pass 0 is the (fused, XLA-level)
# preprocess D = rowsum(dO * O); the kernel's grid is (head, k-block,
# q-block) with the q-blocks innermost, and each live (q tile, k tile)
# pair recomputes p = exp(s - lse) from the saved logsumexp ONCE for all
# three gradients: dV += p^T dO and dK += dS^T Q accumulate in float32
# scratch over the q-blocks of a k-block and are written at its last;
# dQ += dS K accumulates in a float32 scratch that holds the head's
# whole (tq, dk) and is written, block by block, during the last
# k-block.  Nothing O(T^2) is ever materialized; causal tiles above the
# diagonal are fetch-clamped and compute-gated.  It replaced a dK/dV
# kernel and a dQ kernel that each made the scores and dP again (7
# products a pair against 5): 23.5 -> 16.0 ms at 32 heads, T = 8,192,
# keys 192 over values 128, and a quarter to a third less at every
# other shape timed (T 2,048 to 32,768, heads of 64, 128 and 256; v5e,
# PERF.md section 6, PR 32), bit for bit the same gradients.
# (Reference analog: the hand-tuned cuDNN-class backward kernels,
# cudnn_convolution-inl.h-level effort, done the Mosaic way.)
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k, num_qb, num_kb, offset, group, steps,
                window=None):
    """One (bh, kb, j) grid step: q/dO/lse/D arrive one q-block a step,
    k/v one k-block a kb.  q, k, dQ, dK are dk wide; v, dO, dV dv wide.
    bh counts key-value heads: the `group` query heads that share one
    lie behind one another along the rows of q, dO, lse, D and dQ, and
    the minor dim walks all their q-blocks, so dK and dV sum over the
    group in their float32 scratch.  j = head * steps + r: without a
    window `steps` is num_qb and r the q-block; with one r counts
    q-blocks from the k-block's own (the first whose rows reach it,
    square tiles), `steps` the widest band's count, and a q-block's dQ
    is whole once its diagonal tile is done, r == 0, and leaves then."""
    kb = pl.program_id(1)
    j = pl.program_id(2)
    # qi: the q-block's place in its sequence; slot: among all rows
    if window is None:
        qi, slot = (j if group == 1 else j % num_qb), j
    else:
        r = j if group == 1 else j % steps
        qi = _first_live_qb(kb, block_q, block_k, offset) + r
        live = qi <= _band_last_qb(kb, block_q, block_k, num_qb, window)
        qi = jnp.minimum(qi, num_qb - 1)
        slot = qi if group == 1 else j // steps * num_qb + qi
    rows = pl.ds(pl.multiple_of(slot * block_q, block_q), block_q)

    @pl.when(j == 0)
    def _new_k_block():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(kb == 0 if window is None else live & (
        kb == _band_first_kb(qi, block_q, block_k, window)))
    def _first_visit():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[-1]),
                                    jnp.float32)

    def compute(masked):
        qblk, doblk, kblk, vblk = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        p = jnp.exp(_scores(qblk, kblk, scale, qi * block_q + offset,
                            kb * block_k, masked, window) - lse_ref[0])
        # p/ds matmuls run in the input dtype: a f32xf32 MXU pass is
        # several times slower than bf16 and the f32 accumulate
        # (preferred_element_type) already carries the precision
        dv_acc[...] += lax.dot_general(
            p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # p^T @ dO
        dp = lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # dO @ V^T
        ds = (p * (dp - dd_ref[0])).astype(qblk.dtype)
        dk_acc[...] += lax.dot_general(
            ds, qblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # ds^T @ Q
        dq_acc[rows, :] += lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # ds @ K

    if causal:
        # from the first q-block whose rows reach this k-block's
        # columns; the diagonal crosses the first few of them
        unmasked = _first_unmasked_qb(kb, block_q, block_k, num_qb, offset)
        if window is None:
            pl.when((qi >= _first_live_qb(kb, block_q, block_k, offset)) &
                    (qi < unmasked))(lambda: compute(True))
            pl.when(qi >= unmasked)(lambda: compute(False))
        else:
            # ... and the band's left edge the last few
            plain = (qi >= unmasked) & (
                qi < _band_crossed_qb(kb, block_q, block_k, window))
            pl.when(live & ~plain)(lambda: compute(True))
            pl.when(live & plain)(lambda: compute(False))
    else:
        compute(False)

    @pl.when(j == group * steps - 1)
    def _k_block_done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kb == num_kb - 1 if window is None else r == 0)
    def _q_block_done():
        dq_ref[0] = dq_acc[rows, :].astype(dq_ref.dtype)


def _bwd_blocks(t, tk, block_q, window=None):
    """(block_q, block_k) of the backward kernel.  It wants larger
    tiles than the forward: the per-tile matmul chain (5 MXU passes)
    amortizes the grid step better.  Under a window the tile follows
    it (window_block), square, and an explicit block_q still raises
    it."""
    cap = _BWD_BLOCK if window is None else window_block(window, _BWD_BLOCK)
    block_q = _fit_block(t, max(block_q, cap))
    return block_q, block_q if t == tk else _fit_block(
        tk, max(block_q, _BWD_BLOCK))


def _dq_acc_bytes(tq, dk):
    """Bytes of the backward kernel's dQ accumulator in VMEM: tq rows,
    those of every head of a group."""
    return tq * _lanes(dk) * 4


def _flash_bwd_impl(q, k, v, g, o, lse, causal, scale, block_q,
                    interpret, glse=None, group=1, window=None):
    """The one-kernel backward over flat tensors: q (bh, group * t,
    dk), the heads of a group one after another as more rows of their
    key-value head; k (bh, tk, dk); v (bh, tk, dv); g, o, lse like q,
    dv and 1 wide.  glse: optional cotangent on the logsumexp output —
    it folds exactly into the D preprocess (ds = p*(dp - (D - glse)))."""
    bh, t, dk = q.shape
    t //= group
    tk, dv = k.shape[1], v.shape[2]
    offset = tk - t
    block_q, block_k = _bwd_blocks(t, tk, block_q, window)
    num_qb = t // block_q
    num_kb = tk // block_k
    # pass 0: D_i = dO_i . O_i — one fused elementwise+reduce XLA pass
    dd = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1, keepdims=True)                    # (bh, t, 1)
    if glse is not None:
        dd = dd - glse.astype(jnp.float32)

    steps = num_qb if window is None else \
        _band_steps(t, block_q, window)[1]

    def q_block(j, pick):
        """The q block of step j along the rows of all the group's
        heads: pick(r) within its head, r the step's count there."""
        if group == 1:
            return pick(j)
        return j // steps * num_qb + pick(j % steps)

    if window is not None:
        # the minor dim is as long as the widest band and counts q
        # blocks from the k block's own; those past the sequence's end
        # repeat its last (no fetch) and compute nothing.  A q block's
        # dQ leaves when its diagonal tile is done: the output window
        # follows the k block
        q_index = lambda i, n, j: (i, q_block(
            j, lambda r: jnp.minimum(n + r, num_qb - 1)), 0)
        dq_index = lambda i, n, j: (i, q_block(j, lambda r: n), 0)
    else:
        if causal:
            # fetch-clamp the q-blocks above a k-block's diagonal (their
            # compute is pl.when-gated): a repeated index skips the fetch
            q_index = lambda i, n, j: (i, q_block(j, lambda r: jnp.maximum(
                r, jnp.maximum(n * block_k - offset, 0) // block_q)), 0)
        else:
            q_index = lambda i, n, j: (i, j, 0)
        # dQ's blocks leave during the last k-block only: until then the
        # output window stays on block 0 and nothing is written back
        dq_index = lambda i, n, j: (i, jnp.where(n == num_kb - 1, j, 0), 0)
    k_index = lambda i, n, j: (i, n, 0)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_qb=num_qb, num_kb=num_kb, offset=offset,
                          group=group, steps=steps, window=window),
        grid=(bh, num_kb, group * steps),
        in_specs=[
            pl.BlockSpec((1, block_q, dk), q_index),           # q
            pl.BlockSpec((1, block_q, dv), q_index),           # dO
            pl.BlockSpec((1, block_q, 1), q_index),            # lse
            pl.BlockSpec((1, block_q, 1), q_index),            # D
            pl.BlockSpec((1, block_k, dk), k_index),           # k
            pl.BlockSpec((1, block_k, dv), k_index),           # v
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dk), dq_index),
            pl.BlockSpec((1, block_k, dk), k_index),
            pl.BlockSpec((1, block_k, dv), k_index),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, group * t, dk), q.dtype),
                   jax.ShapeDtypeStruct((bh, tk, dk), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, dv), v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((group * t, dk), jnp.float32),          # dQ
            pltpu.VMEM((block_k, dk), jnp.float32),            # dK
            pltpu.VMEM((block_k, dv), jnp.float32),            # dV
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
            vmem_limit_bytes=_BWD_TILE_VMEM_BYTES +
            _dq_acc_bytes(group * t, dk)),
        interpret=interpret,
        name='flash_attention_bwd',
    )(q, g, lse, dd, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, interpret, window):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, interpret,
                           window=window)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, interpret, window):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q,
                               interpret, return_lse=True, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd_shared(causal, scale, block_q, interpret, window, res, g,
                      glse=None):
    """The backward shared by the plain and with-lse custom VJPs; glse
    is the optional logsumexp cotangent.  The kernel wherever its
    blocks tile both lengths and its dQ accumulator (the rows of all
    the heads of a group) fits VMEM, else the XLA-level blocked
    recompute.  Both take the heads of a group as more rows of their
    key-value head: (batch, kv * group, t, d) is (batch * kv, group *
    t, d) without a copy."""
    q, k, v, o, lse = res
    b, h, tq, dk = q.shape
    kv, tk = k.shape[1], k.shape[2]
    group = h // kv
    flat = lambda x: x.reshape((b * kv, -1) + x.shape[3:])
    glse_flat = None if glse is None else glse.reshape(b * kv, group * tq, 1)
    cap = max(block_q, _BWD_BLOCK)
    if _tiles(tq, _try_fit(tq, cap)) and _tiles(tk, _try_fit(tk, cap)) \
            and _dq_acc_bytes(group * tq, dk) <= _BWD_ACC_BYTES:
        dq, dk_, dv_ = _flash_bwd_impl(
            flat(q), flat(k), flat(v), flat(g), flat(o),
            lse.reshape(b * kv, group * tq, 1), causal, scale, block_q,
            interpret, glse=glse_flat, group=group, window=window)
    else:
        dq, dk_, dv_ = _blocked_backward(flat(q), flat(k), flat(v),
                                         flat(g), causal, scale, block_q,
                                         glse=glse_flat, group=group,
                                         window=window)
    return (dq.reshape(q.shape), dk_.reshape(k.shape),
            dv_.reshape(v.shape))


def _flash_bwd_rule(causal, scale, block_q, interpret, window, res, g):
    return _flash_bwd_shared(causal, scale, block_q, interpret, window,
                             res, g)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, interpret, window):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, interpret,
                           return_lse=True, window=window)


def _flash_lse_fwd_rule(q, k, v, causal, scale, block_q, interpret,
                        window):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q,
                               interpret, return_lse=True, window=window)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd_rule(causal, scale, block_q, interpret, window, res,
                        cts):
    g, glse = cts
    b, h, t, _ = res[0].shape
    return _flash_bwd_shared(causal, scale, block_q, interpret, window,
                             res, g, glse=glse.reshape(b, h, t, 1))


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _validate_attn_shapes(q, k, v, causal, fn, window=None):
    """Rectangular attention contract: q and k share (batch, head_dim)
    and k's heads divide q's (query head i reads key-value head i //
    group), k and v share everything but the head width (the values'
    and the output's is v's own), and causal requires tq <= tk (rows
    suffix-align to the keys — the KV-cache decode convention; tq > tk
    would leave the leading rows with no visible key).  A window is a
    causal one over a square call: row i sees keys i - window + 1 to
    i."""
    if k.ndim != v.ndim or k.shape[:-1] != v.shape[:-1]:
        raise ValueError('%s requires identical k/v shapes up to the '
                         'head width; got %s / %s'
                         % (fn, k.shape, v.shape))
    if q.ndim != 4 or k.ndim != 4 or q.shape[0] != k.shape[0] or \
            q.shape[1] % k.shape[1] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            '%s wants (batch, heads, seq, head_dim) with matching '
            'batch/head_dim and key-value heads that divide the query '
            'heads; got q %s vs k %s' % (fn, q.shape, k.shape))
    if causal and q.shape[2] > k.shape[2]:
        raise ValueError(
            '%s: causal masking needs q_len <= kv_len (suffix '
            'alignment); got q_len=%d kv_len=%d'
            % (fn, q.shape[2], k.shape[2]))
    if window is not None and (
            window < 1 or not causal or q.shape[2] != k.shape[2]):
        raise ValueError(
            '%s: a window is one of at least 1 key, causal, over q_len '
            '== kv_len; got window=%d causal=%s q_len=%d kv_len=%d'
            % (fn, window, bool(causal), q.shape[2], k.shape[2]))


def _needs_dense_fallback(tq, tk, block_q):
    """A length no schedule can tile — a property of the shape, never
    of the device: the check runs _try_fit with exactly the caps the
    forward AND backward schedules will use (_schedule_caps), so the
    predicate and the kernels can never disagree.  (Under a window the
    backward's cap is a smaller power of two: what the larger one
    tiles it tiles.)"""
    return not all(_tiles(t, _try_fit(t, cap))
                   for t, cap in _schedule_caps(tq, tk, block_q))


def _default_block_q(tq):
    return max(256, min(1024, tq // 32))


def window_block(window, cap=1024):
    """The tile edge of a windowed call, from `window` (and, fitted to
    it by the kernels, T) alone: the largest power of two no larger
    than the window, between 128 (a whole lane tile) and `cap`.  The
    MXU pays for visited positions, about 1 + tile / window of the
    needed ones once T is several windows, and the grid and the masks
    for tiles.  Timed at window = 2,048 over T = 8,192, 32 heads of
    128 over 4 (v5e, PERF.md section 6, PR 35), forward / backward ms a
    call: tiles of 1024 2.90 / 5.72 (1.50 of the needed positions), 512
    3.19 / 5.96 (1.25), 256 4.96 / 11.4 (1.125): a position costs a
    third more in a tile of 512 than in one of 1024, which is what a
    tile half as large saves once the window is as small as the
    tile."""
    block = min(128, cap)
    while block * 2 <= min(cap, window):
        block *= 2
    return block


def _dense_attention_lse(q, k, v, causal, scale, window=None):
    b, h, tq, _ = q.shape
    kv, tk = k.shape[1], k.shape[2]
    if h != kv:
        k, v = (jnp.repeat(x, h // kv, axis=1) for x in (k, v))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k).astype(jnp.float32) * scale
    if causal:
        mask = ((tk - tq) + jnp.arange(tq)[:, None] >=
                jnp.arange(tk)[None, :])
        if window is not None:
            mask &= jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :] < window
        s = jnp.where(mask, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd',
                     jnp.exp(s - lse[..., None]), v.astype(
                         jnp.float32)).astype(q.dtype)
    return out, lse.reshape(b * h, tq, 1)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=None, interpret=None, window=None):
    """flash_attention variant that ALSO returns the per-row logsumexp
    (bh, tq, 1) — the merge currency for ring attention / partial
    softmax combination — and is differentiable in BOTH outputs (the
    lse cotangent folds into the backward's D preprocess).  Lengths
    no schedule can tile take the dense jnp computation."""
    _validate_attn_shapes(q, k, v, causal, 'flash_attention_with_lse',
                          window)
    tq, tk = q.shape[2], k.shape[2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_q is None:
        block_q = _default_block_q(tq) if window is None else \
            window_block(window)
    # dense route: a sequence length no block of whole sublanes
    # divides (natively differentiable either way)
    if _needs_dense_fallback(tq, tk, block_q):
        return _dense_attention_lse(q, k, v, causal, scale, window)
    if interpret is None:
        interpret = default_interpret(q, k, v)
    return _flash_lse(q, k, v, bool(causal), float(scale), int(block_q),
                      bool(interpret), window)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    interpret=None, window=None):
    """Streaming Pallas attention.

    q: (batch, heads, q_len, dk); k: (batch, kv_heads, kv_len, dk); v:
    (batch, kv_heads, kv_len, dv), a width of its own (latent
    attention: keys of 192 over values of 128).  kv_heads divides
    heads: query head i reads key-value head i // (heads // kv_heads),
    K and V are never repeated, and dK and dV sum over the group in
    float32 inside the backward kernel.  q_len == kv_len is
    self-attention; q_len != kv_len covers cross-attention and
    KV-cache decode, where causal rows are SUFFIX-aligned to the keys
    (query row i sees keys up to kv_len - q_len + i — the standard
    decode convention).  `window` (causal self-attention only): row i
    sees key j iff 0 <= i - j < window; the grids skip the tiles left
    of the band as they skip those above the diagonal.  Returns
    (batch, heads, q_len, dv).  On non-TPU backends runs in Pallas
    interpret mode (slow but correct) unless `interpret` is passed
    explicitly.  Lengths no schedule can tile take the dense jnp
    computation (ops.lm.causal_attention pads them to whole sublanes
    instead).

    block_q: row-tile edge.  Default (None) auto-scales with the
    sequence — 256 for short T, up to 1024 for long T, where the
    smaller grid measures 170 -> 117 ms at T=32k (docs/PERF.md) — or,
    under a window, follows the window (window_block).  An explicit
    value is honored exactly (e.g. to bound VMEM for large head_dim).
    """
    _validate_attn_shapes(q, k, v, causal, 'flash_attention', window)
    tq, tk = q.shape[2], k.shape[2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_q is None:
        block_q = _default_block_q(tq) if window is None else \
            window_block(window)
    # dense route, as in flash_attention_with_lse
    if _needs_dense_fallback(tq, tk, block_q):
        return _dense_attention_lse(q, k, v, causal, scale, window)[0]
    if interpret is None:
        interpret = default_interpret(q, k, v)
    return _flash(q, k, v, bool(causal), float(scale), int(block_q),
                  bool(interpret), window)


# ---------------------------------------------------------------------------
# Gated delta rule: five kernels, every intermediate in VMEM.
#
# The chunk-local half (delta_rule_local, delta_rule_local_bwd): what the
# rule computes inside a chunk of C tokens, one chunk of a few heads a
# grid step.  The C x C matrices of a chunk (the decay exp(g_i - g_j),
# a = (k beta) k^T * decay below the diagonal, the powers of the doubling
# chain, T = (I + a)^-1, and backward dT, dA and the decay's cotangent)
# never reach HBM; the backward kernel is the gradient written by rule
# (dA = -T^T dT T^T), not a transpose of the chain.  The chain's
# products and dA's two run at Precision.HIGHEST, as the XLA code they
# replaced did, on two heads at a time side by side in the lanes against
# a block diagonal operand: a product 64 wide fills a quarter of the
# MXU's array, the pair's half, in the same passes (1.72 -> 1.12 ms a
# make of the cell's block); every other product takes Mosaic's default
# for float32 operands.
#
# The loop over a head's chunks (delta_rule_chunks, _states,
# _chunks_bwd): a recurrence, S <- gamma S + k^T v_new with v_new = u -
# w S, whose state (dk x dv, float32) would cross HBM between every two
# operations as the carry of a lax.scan.  There the grid is (blocks of
# heads: parallel, chunks: arbitrary), the state is a VMEM scratch zeroed
# at the first chunk of a head, and BlockSpecs stream one chunk's tensors
# a grid step.
#
# A grid step takes several heads (fewer, longer steps: a step costs a
# third of a microsecond whatever it holds) in a loop inside the kernel.
# Every operand, accumulator and stored tensor is float32.
# ---------------------------------------------------------------------------

# heads a grid step of the five kernels, at most, and the VMEM their
# blocks (twice: Pallas double-buffers them) and the state may take: in
# steps of 8 heads a block of the cell (8 heads of 128, one sequence)
# takes 6.17 ms over its seven calls, of 4 heads 6.33, of 2 6.51
# (PERF.md section 6, PR 37), in 6.7 MiB at most; wider heads get fewer
DELTA_HEADS_PER_STEP = 8
_DELTA_VMEM_BYTES = 8 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _mm(a, b, dims=_NN, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


# the products of the chunk's solve: float32 in full, as the XLA code had
_mm_solve = functools.partial(_mm, precision=lax.Precision.HIGHEST)


def _each(n, body):
    """body(i) for every i < n as a loop inside the kernel.  Unrolled
    in Python, 8 heads run a block of the cell 9 % faster (5.64 ms for
    6.17) but are traced and lowered 8 times over in every trace of the
    step: a second a gradient of the rule for 0.3, and the cell's warm
    set-up read 73.8 s for 62.8 (PERF.md section 6, PR 37)."""
    lax.fori_loop(0, n, lambda i, carry: (body(i), carry)[1], 0)


def _pair_scalars(g_row, beta_row):
    """The local kernels take two heads at a time, side by side in the
    lanes: a chunk's C x C matrices of both are one (C, 2C) array, whole
    vector registers at C = 64, and a product of it with a block
    diagonal (2C, 2C) operand is both heads' products in the passes of
    one.  From the pair's cumulative log decays and write strengths,
    rows (1, 2C): `beta`, `grown` = exp(g) and `tail` = exp(g_last - g)
    of each head as columns (C, 1); `decay`, both heads' exp(g_i - g_j)
    on and below the diagonal, 0 above, masked before exp: the other
    half of the difference is positive and can overflow; and the masks
    `left` (the first head's lanes; `sides`: each head's), `eye`,
    `strict` (below the diagonal) and `last` (each head's last column).  A row becomes
    columns through the diagonal of its broadcast: one term a sum, so
    exact."""
    c = g_row.shape[-1] // 2
    row = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    left = lane < c
    col = jnp.where(left, lane, lane - c)
    eye = row == col

    sides = (left, ~left)

    def columns(x):
        on = jnp.where(eye, x, 0.0)
        return [jnp.sum(jnp.where(mine, on, 0.0), axis=1, keepdims=True)
                for mine in sides]

    g = columns(g_row)
    decay = jnp.exp(jnp.where(row >= col,
                              jnp.where(left, g[0], g[1]) - g_row, -jnp.inf))
    # the decay's last row is exp(g_last - g_j)
    tail = columns(jnp.sum(jnp.where(row == c - 1, decay, 0.0), axis=0,
                           keepdims=True))
    return dict(beta=columns(beta_row), grown=[jnp.exp(x) for x in g],
                tail=tail, decay=decay, left=left, sides=sides, eye=eye,
                strict=row > col, last=col == c - 1)


def _side_by_side(xs):
    return jnp.concatenate(xs, axis=1)


def _pair_scores(xs, ys):
    """x y^T (C, C) of each head, side by side."""
    return _side_by_side([_mm(x, y, _NT) for x, y in zip(xs, ys)])


def _halves(x):
    c = x.shape[1] // 2
    return x[:, :c], x[:, c:]


def _block_diagonal(x, left):
    """(C, 2C), two heads side by side -> (2C, 2C), one on each block."""
    return jnp.concatenate([jnp.where(left, x, 0.0),
                            jnp.where(left, 0.0, x)], axis=0)


def _delta_local_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u_ref, w_ref,
                        intra_ref, qin_ref, kout_ref, *inv_ref, heads):
    """One chunk of `heads` heads, a pair at a time: the unit lower
    triangular system of the WY form made and solved, and what the loop
    over the chunks takes from the chunk.  T = (I + a)^-1 for the
    strictly lower a: a is nilpotent, so the Neumann series ends, and
    its C terms are the product (I - a)(I + a^2)(I + a^4)... of log2(C)
    factors."""
    def one_pair(pair):
        both = (2 * pair, 2 * pair + 1)
        q, k, v = ([ref[h, 0] for h in both] for ref in (q_ref, k_ref, v_ref))
        s = _pair_scalars(g_ref[pair, 0], beta_ref[pair, 0])
        k_beta = [x * beta for x, beta in zip(k, s['beta'])]
        a = jnp.where(s['strict'], s['decay'] * _pair_scores(k_beta, k), 0.0)
        unit = s['eye'].astype(jnp.float32)
        inv, power = unit - a, a
        for _ in range(max(0, (a.shape[0] - 1).bit_length() - 1)):
            power = _mm_solve(power, _block_diagonal(power, s['left']))
            inv = _mm_solve(inv, _block_diagonal(unit + power, s['left']))
        inv = _halves(inv)
        intra = _halves(s['decay'] * _pair_scores(q, k))
        for i, h in enumerate(both):
            u_ref[h, 0] = _mm(inv[i], v[i] * s['beta'][i])
            w_ref[h, 0] = _mm(inv[i], k_beta[i] * s['grown'][i])
            intra_ref[h, 0] = intra[i]
            qin_ref[h, 0] = q[i] * s['grown'][i]
            kout_ref[h, 0] = k[i] * s['tail'][i]
            if inv_ref:
                inv_ref[0][h, 0] = inv[i]

    _each(heads // 2, one_pair)


def _delta_local_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref,
                            du_ref, dw_ref, dintra_ref, dqin_ref, dkout_ref,
                            dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *,
                            heads):
    """One chunk of `heads` heads, a pair at a time: the cotangents of
    q, k, v, of the cumulative log decay and of beta from those of the
    local kernel's results, by rule: dT = du (v beta)^T + dw (k beta
    e^g)^T, dA = -T^T dT T^T below the diagonal, then the product rules
    of a and intra; the decay exp(g_i - g_j) hands row i the row sums
    of (dA * a + dintra * intra) and takes the column sums from row
    j."""
    def lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def one_pair(pair):
        both = (2 * pair, 2 * pair + 1)
        q, k, v, inv, du, dw, dq_in, dk_out = (
            [ref[h, 0] for h in both] for ref in (
                q_ref, k_ref, v_ref, inv_ref, du_ref, dw_ref, dqin_ref,
                dkout_ref))
        s = _pair_scalars(g_ref[pair, 0], beta_ref[pair, 0])
        beta, grown, tail, left = s['beta'], s['grown'], s['tail'], s['left']
        k_beta = [x * b for x, b in zip(k, beta)]
        d_inv = _side_by_side([
            _mm(du[i], v[i] * beta[i], _NT)
            + _mm(dw[i], k_beta[i] * grown[i], _NT) for i in (0, 1)])
        da = jnp.where(s['strict'], -_mm_solve(
            _mm_solve(jnp.concatenate(inv, axis=0),
                      _block_diagonal(d_inv, left), _TN),
            _block_diagonal(_side_by_side(inv), left), _NT), 0.0)
        dkk = da * s['decay']
        dqk = s['decay'] * _side_by_side([dintra_ref[h, 0] for h in both])
        # of the decay: (dA * a + dintra * intra), row sums less column
        # sums; the diagonal (decay 1) is in both and left out of both
        through = dkk * _pair_scores(k_beta, k) + jnp.where(
            s['strict'], dqk * _pair_scores(q, k), 0.0)
        dg, dbeta, d_tails = [], [], []
        for i, h in enumerate(both):
            dkk_i, dqk_i = _halves(dkk)[i], _halves(dqk)[i]
            d_vbeta = _mm(inv[i], du[i], _TN)
            d_kgrown = _mm(inv[i], dw[i], _TN)
            d_kbeta = _mm(dkk_i, k[i]) + d_kgrown * grown[i]
            dq_ref[h, 0] = _mm(dqk_i, k[i]) + dq_in[i] * grown[i]
            dk_ref[h, 0] = (_mm(dkk_i, k_beta[i], _TN) + _mm(dqk_i, q[i], _TN)
                            + d_kbeta * beta[i] + dk_out[i] * tail[i])
            dv_ref[h, 0] = d_vbeta * beta[i]
            d_tail = lanes(dk_out[i] * k[i]) * tail[i]
            d_tails.append(jnp.sum(d_tail, axis=0, keepdims=True))
            dg.append(lanes(jnp.where(s['sides'][i], through, 0.0))
                      + lanes(d_kgrown * k_beta[i] + dq_in[i] * q[i])
                      * grown[i] - d_tail)
            dbeta.append(lanes(d_kbeta * k[i]) + lanes(d_vbeta * v[i]))

        def rows(xs):
            return jnp.sum(jnp.where(s['eye'], jnp.where(left, *xs), 0.0),
                           axis=0, keepdims=True)

        dg = _halves(
            rows(dg) - jnp.sum(through, axis=0, keepdims=True)
            + jnp.where(s['last'][:1], jnp.where(left[:1], *d_tails), 0.0))
        dbeta = _halves(rows(dbeta))
        for i, h in enumerate(both):
            dg_ref[h, 0] = dg[i]
            dbeta_ref[h, 0] = dbeta[i]

    _each(heads // 2, one_pair)


def _delta_fwd_kernel(*refs, heads, states):
    """One chunk of `heads` heads.  states=False: o_c = q_in S + intra
    v_new.  states=True (the backward rule's second make): S_c, the
    state the chunk starts from, and v_new; o and its operands are
    left out."""
    if states:
        u_ref, w_ref, k_ref, gamma_ref, s0_ref, vnew_ref, s_ref = refs
    else:
        (u_ref, w_ref, intra_ref, q_ref, k_ref, gamma_ref, o_ref,
         s_ref) = refs

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        s_ref[...] = jnp.zeros_like(s_ref)

    def one_head(h):
        s = s_ref[h]
        v_new = u_ref[h, 0] - _mm(w_ref[h, 0], s)
        if states:
            s0_ref[h, 0] = s
            vnew_ref[h, 0] = v_new
        else:
            o_ref[h, 0] = _mm(q_ref[h, 0], s) + _mm(intra_ref[h, 0], v_new)
        s_ref[h] = s * gamma_ref[h, 0] + _mm(k_ref[h, 0], v_new, _TN)

    _each(heads, one_head)


def _delta_bwd_kernel(do_ref, w_ref, intra_ref, q_ref, k_ref, gamma_ref,
                      s0_ref, vnew_ref, du_ref, dw_ref, dintra_ref, dq_ref,
                      dk_ref, dgamma_ref, ds_ref, *, heads):
    """One chunk of `heads` heads, the chunks taken last to first: dS is
    the cotangent of the state the chunk leaves behind."""
    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def one_head(h):
        ds, do = ds_ref[h], do_ref[h, 0]
        s0, v_new = s0_ref[h, 0], vnew_ref[h, 0]
        dv_new = _mm(intra_ref[h, 0], do, _TN) + _mm(k_ref[h, 0], ds)
        du_ref[h, 0] = dv_new
        dw_ref[h, 0] = -_mm(dv_new, s0, _NT)
        dintra_ref[h, 0] = _mm(do, v_new, _NT)
        dq_ref[h, 0] = _mm(do, s0, _NT)
        dk_ref[h, 0] = _mm(v_new, ds, _NT)
        # <dS, S_c> summed over dk here, over dv by the caller
        dgamma_ref[h, 0] = jnp.sum(ds * s0, axis=0, keepdims=True)
        ds_ref[h] = (ds * gamma_ref[h, 0] + _mm(q_ref[h, 0], do, _TN)
                     - _mm(w_ref[h, 0], dv_new, _TN))

    _each(heads, one_head)


def _delta_call(name, kernel, operands, outs, state=None, reverse=False,
                pairs=1):
    """pallas_call of a delta rule kernel over (heads, chunks, rows,
    cols) operands; `outs` are (rows, cols) of each result and `state`
    (dk, dv) of the scratch a head's state lives in (the loop's
    kernels: their chunks follow one another; without one every grid
    step stands alone).  pairs=2: a grid step takes an even number of
    heads, and an operand with half as many leading entries holds one
    for each pair.  A grid step takes as many heads, up to
    DELTA_HEADS_PER_STEP, as divide the heads and fit _DELTA_VMEM_BYTES.
    `name` is the custom call's in the compiled program and in a
    trace."""
    bh, nc = operands[0].shape[:2]
    a_head = 4 * (2 * sum(
        _sublanes(rows) * _lanes(cols) for rows, cols in
        [x.shape[2:] for x in operands if x.shape[0] == bh] + list(outs))
        + (state[0] * state[1] if state else 0))
    fit = [d for d in range(pairs, DELTA_HEADS_PER_STEP + 1, pairs)
           if bh % d == 0]
    heads = max([d for d in fit if d * a_head <= _DELTA_VMEM_BYTES]
                or fit[:1])

    def spec(rows, cols, of=1):
        return pl.BlockSpec(
            (heads // of, 1, rows, cols),
            (lambda i, j: (i, nc - 1 - j, 0, 0)) if reverse
            else (lambda i, j: (i, j, 0, 0)))

    return pl.pallas_call(
        functools.partial(kernel, heads=heads),
        grid=(bh // heads, nc),
        in_specs=[spec(*x.shape[2:], of=bh // x.shape[0]) for x in operands],
        out_specs=[spec(*s) for s in outs],
        out_shape=[jax.ShapeDtypeStruct((bh, nc) + s, jnp.float32)
                   for s in outs],
        scratch_shapes=[] if state is None else
        [pltpu.VMEM((heads,) + state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            'parallel', 'parallel' if state is None else 'arbitrary')),
        interpret=default_interpret(*operands),
        name=name,
    )(*operands)


def _gamma_rows(gamma, dv):
    """(heads, chunks) -> (heads, chunks, 1, dv): a chunk's decay as a
    row the kernel multiplies the state by."""
    return jnp.broadcast_to(gamma[..., None, None], gamma.shape + (1, dv))


def delta_rule_chunks(u, w, intra, q_in, k_out, gamma):
    """o of every chunk.  u (heads, chunks, C, dv); w, q_in, k_out
    (heads, chunks, C, dk); intra (heads, chunks, C, C); gamma (heads,
    chunks); dk and dv multiples of 128, C of 8, all float32."""
    c, dv = u.shape[2:]
    return _delta_call(
        'delta_rule_chunks',
        functools.partial(_delta_fwd_kernel, states=False),
        (u, w, intra, q_in, k_out, _gamma_rows(gamma, dv)), [(c, dv)],
        (w.shape[-1], dv))[0]


def delta_rule_states(u, w, k_out, gamma):
    """(S_c, v_new) of every chunk: the state each chunk starts from
    (heads, chunks, dk, dv) and u - w S_c (heads, chunks, C, dv)."""
    c, dv = u.shape[2:]
    dk = w.shape[-1]
    return _delta_call(
        'delta_rule_states',
        functools.partial(_delta_fwd_kernel, states=True),
        (u, w, k_out, _gamma_rows(gamma, dv)), [(dk, dv), (c, dv)], (dk, dv))


def delta_rule_chunks_bwd(do, w, intra, q_in, k_out, gamma, s0, v_new):
    """Cotangents (du, dw, dintra, dq_in, dk_out, dgamma) of
    delta_rule_chunks' operands for the cotangent `do` of its result,
    given delta_rule_states' (s0, v_new)."""
    c, dv = do.shape[2:]
    dk = w.shape[-1]
    du, dw, dintra, dq, dkk, dgamma = _delta_call(
        'delta_rule_chunks_bwd', _delta_bwd_kernel,
        (do, w, intra, q_in, k_out, _gamma_rows(gamma, dv), s0, v_new),
        [(c, dv), (c, dk), (c, c), (c, dk), (c, dk), (1, dv)], (dk, dv),
        reverse=True)
    return du, dw, dintra, dq, dkk, jnp.sum(dgamma, axis=(2, 3))


def _pair_rows(x):
    """(heads, chunks, C) -> (heads / 2, chunks, 1, 2C): a chunk's
    scalars of two heads as one row the local kernels read."""
    h, nc, c = x.shape
    return jnp.moveaxis(x.reshape(h // 2, 2, nc, c), 1, 2).reshape(
        h // 2, nc, 1, 2 * c)


def _whole_pairs(xs):
    """An odd count of heads gets one more, of zeros: it decays nothing
    (g = 0), writes nothing (beta = 0) and is cut off again."""
    if xs[0].shape[0] % 2 == 0:
        return list(xs)
    return [jnp.pad(x, [(0, 1)] + [(0, 0)] * (x.ndim - 1)) for x in xs]


def delta_rule_local(q, k, v, g, beta, with_inverse=False):
    """The half of the rule that stays inside a chunk.  q, k (heads,
    chunks, C, dk), v (heads, chunks, C, dv), g (log decay a token) and
    beta (heads, chunks, C); dk and dv multiples of 128, C of 8, all
    float32.  Returns what delta_rule_chunks takes: u (C, dv), w (C,
    dk), intra (C, C), q_in (C, dk), k_out (C, dk) of every chunk and
    gamma (heads, chunks), the decay over a whole chunk; with_inverse
    (the backward rule's make): T (C, C) of every chunk after them."""
    heads, _, c, dk = q.shape
    dv = v.shape[-1]
    g = jnp.cumsum(g, axis=-1)
    q, k, v, g_pairs, beta = _whole_pairs((q, k, v, g, beta))
    made = _delta_call(
        'delta_rule_local', _delta_local_kernel,
        (q, k, v, _pair_rows(g_pairs), _pair_rows(beta)),
        [(c, dv), (c, dk), (c, c), (c, dk), (c, dk)]
        + [(c, c)] * with_inverse, pairs=2)
    made = [x[:heads] for x in made]
    return tuple(made[:5]) + (jnp.exp(g[..., -1]),) + tuple(made[5:])


def delta_rule_local_bwd(q, k, v, g, beta, inv, cotangents):
    """Cotangents (dq, dk, dv, dg, dbeta) of delta_rule_local's operands
    for the `cotangents` (du, dw, dintra, dq_in, dk_out, dgamma) of its
    results, given its T (`inv`)."""
    du, dw, dintra, dq_in, dk_out, dgamma = cotangents
    heads, _, c, dk = q.shape
    g = jnp.cumsum(g, axis=-1)
    q, k, v, g_pairs, beta, *given = _whole_pairs(
        (q, k, v, g, beta, inv, du, dw, dintra, dq_in, dk_out))
    dq, dkk, dv, dg, dbeta = (x[:heads] for x in _delta_call(
        'delta_rule_local_bwd', _delta_local_bwd_kernel,
        (q, k, v, _pair_rows(g_pairs), _pair_rows(beta), *given),
        [(c, dk), (c, dk), (c, v.shape[-1]), (1, c), (1, c)], pairs=2))
    # gamma = exp(g_last); g the cumulative sum of what the caller gave
    dg = dg[:, :, 0].at[..., -1].add(dgamma * jnp.exp(g[..., -1]))
    return dq, dkk, dv, lax.cumsum(dg, axis=2, reverse=True), dbeta[:, :, 0]


# ---------------------------------------------------------------------------
# Rows added to their tokens (SparseMoE's combine)
# ---------------------------------------------------------------------------

def row_tiles(x):
    """(..., H) -> (..., H / 128, 128) where 128 divides H, else
    (..., 1, H): a row as whole lines of (8, 128) tiles, which a DMA
    can address alone (HBM tiles an (N, H) array's rows eight at a
    time)."""
    h = x.shape[-1]
    return x.reshape(x.shape[:-1] + ((h // 128, 128) if h % 128 == 0
                                     else (1, h)))


def _add_rows_kernel(count_ref, dest_ref, rows_ref, _, acc_ref, buf, sem):
    """acc[dest[i]] += rows[i] for i < count: every live row's line of
    the sum read at once, added to in VMEM and written back at once."""
    count = count_ref[0]

    def each(copy):
        def go(i, carry):
            copy(i)
            return carry
        lax.fori_loop(0, count, go, 0)

    def fetch(i):
        return pltpu.make_async_copy(acc_ref.at[dest_ref[i]], buf.at[i],
                                     sem.at[0])

    def store(i):
        return pltpu.make_async_copy(buf.at[i], acc_ref.at[dest_ref[i]],
                                     sem.at[1])

    each(lambda i: fetch(i).start())
    each(lambda i: fetch(i).wait())
    buf[...] += rows_ref[...]
    each(lambda i: store(i).start())
    each(lambda i: store(i).wait())


def add_rows(acc, dest, rows, count):
    """acc[dest[i]] += rows[i] for the first `count` rows, in place (acc
    is aliased to the result).  acc (N, S, L) and rows (tile, S, L)
    float32, rows as row_tiles() shapes them; dest (tile,) int32,
    unique and in range among the first count; the other rows are left
    out.  Rows move by DMA, so the work is the live rows, whatever N
    is."""
    block = rows.shape
    return pl.pallas_call(
        _add_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(block, lambda i, c, d: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(block, jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={3: 0},
        interpret=default_interpret(acc, rows),
        name='add_rows',
    )(jnp.reshape(count, (1,)).astype(jnp.int32), dest.astype(jnp.int32),
      rows, acc)


# ---------------------------------------------------------------------------
# Depthwise causal convolution along the sequence (CausalConv1D): one pass
# over (rows, lanes) blocks forward and one backward, each block read with
# a tile of its neighbour's rows (custom calls `causal_conv1d`,
# `causal_conv1d_bwd`)
# ---------------------------------------------------------------------------

# rows and lanes of a grid step's block, at most; lanes of a strip
# (forward, backward); tiles of rows a turn.  A block is worked through
# a tile of rows by a strip of lanes at a time, each strip's values in
# registers, in a loop over turns of CONV_TURN tiles unrolled.  Compiled
# for a v5e at the Qwen3-Next cell's (2, 8192, 8192) bfloat16, a grid
# step of 512 x 1024 is about 2,300 bundles forward and 4,400 backward,
# under the 3,840 and 5,760 cycles (1.5 GHz) its bytes take at 819 GB/s;
# as whole-array operations (each value held in VMEM) 5,187 and 9,810;
# a turn of one tile 3,400 and 11,000.  Unrolled whole (1,994 and
# 4,102), a block traced 128 and 256 tile bodies at every call site:
# 90 s more set-up for that cell on the chip
CONV_ROWS = 512
CONV_LANES = 1024
CONV_STRIPS = (256, 128)
CONV_TURN = 4


def _sublane_tile(dtype):
    """Rows of one (rows, 128) tile of `dtype` as HBM and VMEM hold it:
    8 of 32 bits, 16 of bfloat16 (two rows packed in a sublane)."""
    return 32 // jnp.dtype(dtype).itemsize


def conv_fits(t, c, width, dtype):
    """Whether causal_conv1d takes (B, t, c) of `dtype` under a kernel of
    `width`: t whole sublane tiles, c whole lanes, and the width - 1
    rows a block reads of its neighbour inside one tile (the halo)."""
    tile = _sublane_tile(dtype)
    return t % tile == 0 and c % 128 == 0 and 0 < width <= tile + 1


def _conv_block(n, unit, cap):
    """The largest multiple of `unit` that divides n and is at most cap
    (unit divides n)."""
    return max(b for b in range(unit, min(n, cap) + 1, unit) if n % b == 0)


def _rows_from(ext, start, rows):
    """ext[start:start + rows] of a float32 value: a roll along the
    sublanes, then the first rows."""
    shift = -start % ext.shape[0]
    return (pltpu.roll(ext, shift, 0) if shift else ext)[:rows]


def _halo(rows, edge):
    """A neighbour's rows in float32, zeros where `edge`: no sequence
    reads the rows of another."""
    return jnp.where(edge, 0.0, rows.astype(jnp.float32))


def _taps(ext, w, first, rows):
    """sum_j w[j] * ext[first + d_j : first + d_j + rows], d_j = j -
    (W-1): in the taps' order, in float32."""
    return functools.reduce(jnp.add, (
        _rows_from(ext, first + j - (len(w) - 1), rows) * w[j]
        for j in range(len(w))))


def _strips(w_ref, lanes, tile, cap):
    """(columns, the taps' weights broadcast to a tile) of each strip of
    a block's lanes: whole lanes, at most cap."""
    strip = _conv_block(lanes, 128, cap)
    for k in range(0, lanes, strip):
        cols = slice(k, k + strip)
        yield cols, [jnp.broadcast_to(w_ref[j:j + 1, cols], (tile, strip))
                     for j in range(w_ref.shape[0])]


def _turns(rows, turn, body, carry, last=None):
    """body(first row, carry) over a block's rows in turns of `turn`
    rows: a loop, each turn unrolled; `last` (a static body, for the
    last turn) ends the loop."""
    steps = rows // turn - (last is not None)
    carry = lax.fori_loop(
        0, steps, lambda i, c: body(pl.multiple_of(i * turn, turn), c),
        carry)
    return carry if last is None else last(rows - turn, carry)


def _conv_fwd_kernel(x_ref, prev_ref, w_ref, y_ref, *, strip, tiles):
    """y of a (rows, lanes) block: sum_j w[j] * x[r - (W-1) + j].  Each
    tile of rows is read below the tile above it, the first below the
    last tile of the sequence's previous block (zeros at its start)."""
    tile = prev_ref.shape[1]
    rows, lanes = x_ref.shape[1:]
    for cols, w in _strips(w_ref, lanes, tile, strip):
        def turn(first, above, cols=cols, w=w):
            for k in range(tiles):
                at = pl.ds(first + k * tile, tile)
                x = x_ref[0, at, cols].astype(jnp.float32)
                y = _taps(jnp.concatenate([above, x]), w, tile, tile)
                y_ref[0, at, cols] = y.astype(y_ref.dtype)
                above = x
            return above

        _turns(rows, tiles * tile, turn,
               _halo(prev_ref[0, :, cols], pl.program_id(1) == 0))


def _conv_bwd_kernel(x_ref, prev_ref, dy_ref, next_ref, w_ref, dx_ref,
                     dw_ref, *, strip, tiles):
    """dx of a block: sum_j w[j] * dy[r + (W-1) - j], each tile of dy
    read above the tile below it, the last above the first tile of the
    sequence's next block (zeros at its end); dw: each tap's x[r - (W-1)
    + j] times dy, summed over the block's rows in float32 and added to
    the lanes' sums over every block of every sequence (the grid's inner
    axes)."""
    t = pl.program_id(2)
    tile, width = prev_ref.shape[1], w_ref.shape[0]
    rows, lanes = x_ref.shape[1:]

    @pl.when((pl.program_id(1) == 0) & (t == 0))
    def _first_block():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for cols, w in _strips(w_ref, lanes, tile, strip):
        after = _halo(next_ref[0, :, cols], t == pl.num_programs(2) - 1)

        def turn(first, carry, ends=False, cols=cols, w=w, after=after):
            dy, x_above, sums = carry
            for k in range(tiles):
                at = pl.ds(first + k * tile, tile)
                below = (after if ends and k == tiles - 1 else
                         dy_ref[0, pl.ds(first + (k + 1) * tile, tile),
                                cols].astype(jnp.float32))
                dx = _taps(jnp.concatenate([dy, below]), w[::-1], width - 1,
                           tile)
                dx_ref[0, at, cols] = dx.astype(dx_ref.dtype)
                x = x_ref[0, at, cols].astype(jnp.float32)
                ext = jnp.concatenate([x_above, x])
                sums = tuple(
                    acc + _rows_from(ext, tile + j - (width - 1), tile) * dy
                    for j, acc in enumerate(sums))
                dy, x_above = below, x
            return dy, x_above, sums

        _, _, sums = _turns(
            rows, tiles * tile, turn,
            (dy_ref[0, :tile, cols].astype(jnp.float32),
             _halo(prev_ref[0, :, cols], t == 0),
             (jnp.zeros((tile, cols.stop - cols.start), jnp.float32),)
             * width),
            functools.partial(turn, ends=True))
        for j in range(width):
            dw_ref[j:j + 1, cols] += jnp.sum(sums[j], axis=0, keepdims=True)


def _conv_plan(x, *operands):
    """(rows, lanes) of x's blocks, its halo's rows, the strips'
    (forward, backward) lanes, the tiles of rows a turn and interpret
    mode: all a call's trace depends on beside its operands' shapes."""
    tile = _sublane_tile(x.dtype)
    rows = _conv_block(x.shape[1], tile, CONV_ROWS)
    return (rows, _conv_block(x.shape[2], 128, CONV_LANES), tile,
            CONV_STRIPS, _conv_block(rows // tile, 1, CONV_TURN),
            default_interpret(*operands))


# jitted with the plan static: traced once a shape, not at every call
# site of every trace of a step (the backward kernel alone traced in
# 0.4 s, and a step of Qwen3-Next traces its three layers' kernels
# several times over)
@functools.partial(jax.jit, static_argnums=2)
def _conv_fwd_call(x, w_rows, plan):
    bsz, t, c = x.shape
    rows, lanes, tile, strips, tiles, interpret = plan
    per = rows // tile
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, strip=strips[0], tiles=tiles),
        grid=(bsz, t // rows, c // lanes),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), lambda b, i, k: (b, i, k)),
            pl.BlockSpec((1, tile, lanes),
                         lambda b, i, k: (b, jnp.maximum(i * per - 1, 0), k)),
            pl.BlockSpec((w_rows.shape[0], lanes), lambda b, i, k: (0, k))],
        out_specs=pl.BlockSpec((1, rows, lanes), lambda b, i, k: (b, i, k)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',) * 3),
        interpret=interpret,
        name='causal_conv1d',
    )(x, x, w_rows)


@functools.partial(jax.jit, static_argnums=3)
def _conv_bwd_call(x, w_rows, dy, plan):
    bsz, t, c = x.shape
    rows, lanes, tile, strips, tiles, interpret = plan
    per = rows // tile
    block = pl.BlockSpec((1, rows, lanes), lambda k, b, i: (b, i, k))
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, strip=strips[1], tiles=tiles),
        grid=(c // lanes, bsz, t // rows),
        in_specs=[
            block,
            pl.BlockSpec((1, tile, lanes),
                         lambda k, b, i: (b, jnp.maximum(i * per - 1, 0), k)),
            block,
            pl.BlockSpec((1, tile, lanes), lambda k, b, i: (
                b, jnp.minimum((i + 1) * per, t // tile - 1), k)),
            pl.BlockSpec((w_rows.shape[0], lanes), lambda k, b, i: (0, k))],
        out_specs=[block,
                   pl.BlockSpec((w_rows.shape[0], lanes),
                                lambda k, b, i: (0, k))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w_rows.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary', 'arbitrary')),
        interpret=interpret,
        name='causal_conv1d_bwd',
    )(x, x, dy, dy, w_rows)


@jax.custom_vjp
def causal_conv1d(x, w):
    """Depthwise causal convolution of every sequence: y[b, t, c] =
    sum_j w[c, j] * x[b, t - (W-1) + j, c], rows before a sequence's
    start read as zero, in float32, y in x's type.  x (B, T, C), w
    (C, W), as conv_fits() admits them.  One kernel over (B, T / rows,
    C / lanes) blocks reads each element once (and one tile of rows of
    the block before); the gradient is one kernel too."""
    return _conv_fwd_call(x, w.astype(jnp.float32).T, _conv_plan(x, x))


def _causal_conv1d_fwd(x, w):
    return causal_conv1d(x, w), (x, w)


def _causal_conv1d_bwd(res, dy):
    x, w = res
    dx, dw = _conv_bwd_call(x, w.astype(jnp.float32).T, dy,
                            _conv_plan(x, x, dy))
    return dx, dw.T.astype(w.dtype)


causal_conv1d.defvjp(_causal_conv1d_fwd, _causal_conv1d_bwd)
