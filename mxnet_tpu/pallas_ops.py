"""In-tree Pallas TPU kernels for hot ops.

The reference hand-writes CUDA for its hottest kernels; the TPU
counterpart is Pallas (jax.readthedocs.io/en/latest/pallas).  This module
ships the first production kernel: flash attention — a 3D
(batch*head, q-block, k-block) grid streams K/V blocks through VMEM with
the online-softmax recurrence in fp32 scratch, so neither the T^2 score
matrix nor the full K/V sequence ever sits in VMEM/HBM at once, and
causal q-tiles skip their fully-masked k-blocks.  Available directly as
`pallas_ops.flash_attention` and opt-in via
`parallel.ring_attention.full_attention(use_flash=True)`.

Backward is the fused two-pass FlashAttention recipe in Pallas: the
forward saves the per-row logsumexp, D = rowsum(dO∘O) is a fused XLA
preprocess, and two kernels (dK/dV gridded over k-blocks, dQ over
q-blocks) recompute p = exp(s − lse) tile by tile — nothing O(T^2) is
materialized.  Sequences too long for the resident-VMEM kernels fall
back to an XLA-level blocked recompute.
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


def _online_softmax_step(q, kblk, vblk, m, l, acc, scale, causal,
                         row0, col0):
    """One K-block of the online-softmax recurrence — the ONE numerics
    definition both schedules share."""
    s = lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc * correction + pv


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                 acc_ref, *, scale, causal, block_q, block_k, num_kb,
                 offset):
    """One (bh, qi, kb) grid step of the streaming schedule.  kb is the
    minor grid dim: scratch (m, l, acc) carries the online softmax
    across kb steps; the last live kb writes o_ref and the per-row
    logsumexp (saved for the fused backward).  `offset` = tk - tq:
    causal q rows sit suffix-aligned against the keys (KV-decode
    convention); 0 for square self-attention."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    # causal: this q tile's last live k block (diagonal inclusive)
    last_kb = num_kb - 1
    if causal:
        last_kb = jnp.minimum(
            (qi * block_q + block_q - 1 + offset) // block_k, num_kb - 1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_not(causal) | (kb <= last_kb))
    def _compute():
        m_new, l_new, acc_new = _online_softmax_step(
            q_ref[0], k_ref[0], v_ref[0], m_ref[...], l_ref[...],
            acc_ref[...], scale, causal, qi * block_q + offset,
            kb * block_k)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _attn_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                          causal, block_q, block_k, num_kb, offset):
    """Resident-K schedule: the whole K/V sequence for one head sits in
    VMEM (fetched once per head); a fori_loop walks k-blocks with the
    online-softmax recurrence, and causal q-tiles stop at the diagonal
    (skipping both compute AND reads of the masked tail).  Fastest when
    K/V fit in VMEM."""
    q = q_ref[0]                          # (block_q, D)
    qi = pl.program_id(1)
    d = q.shape[-1]
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        return _online_softmax_step(q, kblk, vblk, m, l, acc, scale,
                                    causal, qi * block_q + offset,
                                    kb * block_k)

    if causal:
        upper = jnp.minimum(
            (qi * block_q + block_q - 1 + offset) // block_k + 1, num_kb)
    else:
        upper = num_kb
    m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


# resident-K schedule is used while K+V for one head fit comfortably in
# VMEM (~16 MB/core); beyond that the 3D-grid streaming schedule keeps
# VMEM bounded at O(block) regardless of T.  The budget must leave room
# for Mosaic's double-buffered window of the SAME resident operands
# (measured: a 10 MB threshold OOMs at 2x), hence ~6 MB.
_VMEM_RESIDENT_BYTES = 6 * 1024 * 1024

# backward tile edge (see _flash_bwd_impl); 1024 measured best on
# v5e-class — 2048 OOMs the 16 MB VMEM with double buffering
_BWD_BLOCK = 1024


def _bwd_resident_bytes():
    """Resident budget of the BACKWARD kernels: two thirds of the
    forward's.  They hold the same double-buffered sequence pair next
    to the f32 score temporaries of a _BWD_BLOCK-edge tile, which the
    forward's smaller tiles do not have: at a 6 MB pair (T=12288,
    d=128, bf16) Mosaic asks 19.5 MB of the 16 MB scoped VMEM and
    refuses; at 4 MB (T=8192) it compiles (v5e, libtpu 0.0.34)."""
    return _VMEM_RESIDENT_BYTES * 2 // 3


def _try_fit(t, cap):
    """Largest block <= cap dividing t (halving from cap) — the ONE
    divisibility rule every schedule and the dense-fallback predicate
    share, so they can never disagree about a shape's viability."""
    b = min(cap, t)
    while t % b:
        b //= 2
    return b


def _fit_block(t, block_q):
    """_try_fit, raising on degenerate results.  Sequence lengths with
    no small power-of-two factor (e.g. prime T) would degenerate to
    1-row blocks that Mosaic rejects or runs pathologically — raise
    with guidance instead."""
    b = _try_fit(t, block_q)
    if b < 8 and t > 8:
        raise ValueError(
            'flash_attention: sequence length %d has no power-of-two '
            'block factor >= 8; pad the sequence to a multiple of 128 '
            'or use full_attention for unaligned lengths' % t)
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
                    return_lse=False):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    offset = tk - tq          # causal rows suffix-align to the keys
    bh = b * h
    qf = q.reshape(bh, tq, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, d)
    block_q = _fit_block(tq, block_q)
    block_k = _fit_block(tk, block_q if tq == tk else max(block_q, 256))
    num_kb = tk // block_k
    itemsize = jnp.dtype(q.dtype).itemsize
    resident = 2 * tk * d * itemsize <= _VMEM_RESIDENT_BYTES
    # lse rides along as (bh, tq, 1): the trailing singleton keeps the
    # row axis on the sublane dim so (block_q, 1) kernel views
    # broadcast directly against (block_q, block_k) scores
    out_shapes = [jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                  jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)]

    if resident:
        out, lse = pl.pallas_call(
            functools.partial(_attn_kernel_resident, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, num_kb=num_kb,
                              offset=offset),
            grid=(bh, tq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            ],
            out_shape=out_shapes,
            interpret=interpret,
        )(qf, kf, vf)
        out = out.reshape(b, h, tq, d)
        return (out, lse) if return_lse else out

    grid = (bh, tq // block_q, num_kb)
    if causal:
        # clamp masked k-blocks to the diagonal: repeated block indices
        # skip the HBM->VMEM fetch (compute is gated by pl.when)
        kv_index = lambda i, j, n: (
            i, jnp.minimum(
                n, (j * block_q + block_q - 1 + offset) // block_k), 0)
    else:
        kv_index = lambda i, j, n: (i, n, 0)
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_kb=num_kb, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, n: (i, j, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # normalizer
            pltpu.VMEM((block_q, d), jnp.float32),     # output accum
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, tq, d)
    return (out, lse) if return_lse else out


def _blocked_backward(q, k, v, g, causal, scale, block_q, glse=None):
    """Recompute-based gradients, q-block at a time: live memory is
    O(block_q * T) instead of the dense O(T^2).  glse: optional
    logsumexp cotangent, folded into the softmax vjp."""
    bh, t, d = q.shape
    tk = k.shape[1]
    offset = tk - t
    block_q = _fit_block(t, block_q)
    nq = t // block_q
    qb = q.reshape(bh, nq, block_q, d)
    gb = g.reshape(bh, nq, block_q, d)
    lb = (jnp.zeros((bh, nq, block_q, 1), jnp.float32) if glse is None
          else glse.astype(jnp.float32).reshape(bh, nq, block_q, 1))

    def one_block(carry, blk):
        dk, dv = carry
        qi, qblk, gblk, lblk = blk
        s = jnp.einsum('bqd,bkd->bqk', qblk, k).astype(
            jnp.float32) * scale                       # (bh, bq, Tk)
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, (block_q, tk), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block_q, tk), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
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
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(bh, t, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Fused Pallas backward: the FlashAttention two-pass recipe.  Pass 0 is
# the (fused, XLA-level) preprocess D = rowsum(dO * O); pass 1 is two
# kernels — dK/dV with k-blocks as the parallel grid dim, dQ with
# q-blocks — each recomputing p = exp(s - lse) from the saved
# logsumexp, so nothing O(T^2) is ever materialized and both kernels
# stream their counterpart sequence through a fori_loop with causal
# skipping.  (Reference analog: the hand-tuned cuDNN-class backward
# kernels, cudnn_convolution-inl.h-level effort, done the Mosaic way.)
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                     dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                     num_qb, offset):
    kb = pl.program_id(1)
    kblk = k_ref[0]                       # (block_k, D)
    vblk = v_ref[0]
    d = kblk.shape[-1]
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        qblk = q_ref[0, pl.ds(qi * block_q, block_q), :]
        doblk = do_ref[0, pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), :]   # (bq, 1)
        dd = dd_ref[0, pl.ds(qi * block_q, block_q), :]     # (bq, 1)
        s = lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)                                # (bq, bk)
        # p/ds matmuls run in the input dtype: a f32xf32 MXU pass is
        # several times slower than bf16 and the f32 accumulate
        # (preferred_element_type) already carries the precision
        dv = dv + lax.dot_general(
            p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # p^T @ dO
        dp = lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # dO @ V^T
        ds = p * (dp - dd)
        dk = dk + lax.dot_general(
            ds.astype(qblk.dtype), qblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # ds^T @ Q
        return dk, dv

    # causal: the first q-block whose rows reach this k-block's columns
    lower = jnp.maximum(kb * block_k - offset, 0) // block_q \
        if causal else 0
    dk, dv = lax.fori_loop(lower, num_qb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dd_ref, dq_ref,
                   *, scale, causal, block_q, block_k, num_kb, offset):
    qi = pl.program_id(1)
    qblk = q_ref[0]                       # (block_q, D)
    doblk = do_ref[0]
    lse = lse_ref[0]                      # (block_q, 1)
    dd = dd_ref[0]
    d = qblk.shape[-1]
    dq0 = jnp.zeros((block_q, d), jnp.float32)

    def body(kb, dq):
        kblk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        return dq + lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # ds @ K

    if causal:
        upper = jnp.minimum(
            (qi * block_q + block_q - 1 + offset) // block_k + 1,
            num_kb)
    else:
        upper = num_kb
    dq = lax.fori_loop(0, upper, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkdv_stream_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                            dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                            causal, block_q, block_k, num_qb, offset):
    """Streaming dK/dV: grid (bh, kb, qi) with the q-block axis
    innermost; q/dO/lse/D arrive one block per grid step (O(block)
    VMEM regardless of T), dk/dv accumulate in f32 scratch and write
    once on the final q-block.  Causal q-blocks below the diagonal are
    fetch-clamped and compute-gated, matching the resident schedule's
    FLOP skipping."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    lower = jnp.maximum(kb * block_k - offset, 0) // block_q \
        if causal else 0

    @pl.when(qi >= lower)
    def _compute():
        qblk = q_ref[0]
        doblk = do_ref[0]
        lse = lse_ref[0]
        dd = dd_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds.astype(qblk.dtype), qblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == num_qb - 1)
    def _store():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_stream_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dd_ref,
                          dq_ref, dq_acc, *, scale, causal, block_q,
                          block_k, num_kb, offset):
    """Streaming dQ: grid (bh, qi, kb) with the k-block axis innermost;
    k/v stream one block per step, dq accumulates in f32 scratch."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        upper = (qi * block_q + block_q - 1 + offset) // block_k + 1
    else:
        upper = num_kb

    @pl.when(kb < upper)
    def _compute():
        qblk = q_ref[0]
        doblk = do_ref[0]
        lse = lse_ref[0]
        dd = dd_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + offset + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        dq_acc[:] = dq_acc[:] + lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kb == num_kb - 1)
    def _store():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_stream_impl(q, k, v, g, o, lse, causal, scale, block_q,
                           interpret, glse=None):
    """HBM-streaming backward: same math as _flash_bwd_impl but no
    operand is sequence-resident — VMEM stays O(block) for any T.
    glse: optional cotangent on the logsumexp output — it folds exactly
    into the D preprocess (ds = p*(dp - (D - glse)))."""
    bh, t, d = q.shape
    tk = k.shape[1]
    offset = tk - t
    block_q = _fit_block(t, max(block_q, _BWD_BLOCK))
    block_k = block_q if t == tk else _fit_block(
        tk, max(block_q, _BWD_BLOCK))
    num_qb = t // block_q
    num_kb = tk // block_k
    dd = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1, keepdims=True)
    if glse is not None:
        dd = dd - glse.astype(jnp.float32)

    if causal:
        # fetch-clamp skipped diagonal blocks (compute is pl.when-gated)
        q_index = lambda i, n, j: (
            i, jnp.maximum(
                j, jnp.maximum(n * block_k - offset, 0) // block_q), 0)
        k_index_dq = lambda i, j, n: (
            i, jnp.minimum(
                n, (j * block_q + block_q - 1 + offset) // block_k), 0)
    else:
        q_index = lambda i, n, j: (i, j, 0)
        k_index_dq = lambda i, j, n: (i, n, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_stream_kernel, scale=scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_qb=num_qb,
                          offset=offset),
        grid=(bh, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),            # q
            pl.BlockSpec((1, block_q, d), q_index),            # dO
            pl.BlockSpec((1, block_q, 1), q_index),            # lse
            pl.BlockSpec((1, block_q, 1), q_index),            # D
            pl.BlockSpec((1, block_k, d), lambda i, n, j: (i, n, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, n, j: (i, n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, n, j: (i, n, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, n, j: (i, n, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d), v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, g, lse, dd, k, v)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_stream_kernel, scale=scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, num_kb=num_kb,
                          offset=offset),
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_k, d), k_index_dq),         # k
            pl.BlockSpec((1, block_k, d), k_index_dq),         # v
            pl.BlockSpec((1, block_q, d), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, n: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, n: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda i, j, n: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(k, v, q, g, lse, dd)
    return dq, dk, dv


def _flash_bwd_impl(q, k, v, g, o, lse, causal, scale, block_q,
                    interpret, glse=None):
    """Fused two-kernel backward over flat (bh, t, d) tensors."""
    bh, t, d = q.shape
    tk = k.shape[1]
    offset = tk - t
    # the backward wants larger tiles than the forward: its per-tile
    # matmul chain (5 MXU passes) amortizes loop overhead better, and
    # VMEM pressure is lower (no online-softmax scratch)
    block_q = _fit_block(t, max(block_q, _BWD_BLOCK))
    block_k = block_q if t == tk else _fit_block(
        tk, max(block_q, _BWD_BLOCK))
    num_qb = t // block_q
    num_kb = tk // block_k
    # pass 0: D_i = dO_i . O_i — one fused elementwise+reduce XLA pass.
    # A logsumexp cotangent folds in here: ds = p*(dp - (D - glse)).
    dd = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1, keepdims=True)                    # (bh, t, 1)
    if glse is not None:
        dd = dd - glse.astype(jnp.float32)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_qb=num_qb, offset=offset),
        grid=(bh, num_kb),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, n: (i, 0, 0)),   # q
            pl.BlockSpec((1, t, d), lambda i, n: (i, 0, 0)),   # dO
            pl.BlockSpec((1, t, 1), lambda i, n: (i, 0, 0)),   # lse
            pl.BlockSpec((1, t, 1), lambda i, n: (i, 0, 0)),   # D
            pl.BlockSpec((1, block_k, d), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, n: (i, n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, n: (i, n, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d), v.dtype)],
        interpret=interpret,
    )(q, g, lse, dd, k, v)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_kb=num_kb, offset=offset),
        grid=(bh, num_qb),
        in_specs=[
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),  # k
            pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
    )(k, v, q, g, lse, dd)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, interpret):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, interpret)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q,
                               interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd_shared(causal, scale, block_q, interpret, res, g,
                      glse=None):
    """Schedule-selecting backward shared by the plain and with-lse
    custom VJPs; glse is the optional logsumexp cotangent."""
    q, k, v, o, lse = res
    b, h, tq, d = q.shape
    tk = k.shape[2]
    flatq = lambda x: x.reshape(b * h, tq, d)
    flatk = lambda x: x.reshape(b * h, tk, d)
    itemsize = jnp.dtype(q.dtype).itemsize
    glse_flat = None if glse is None else glse.reshape(b * h, tq, 1)
    args = (flatq(q), flatk(k), flatk(v), flatq(g), flatq(o),
            lse.reshape(b * h, tq, 1), causal, scale, block_q,
            interpret)
    fitted_q = _try_fit(tq, max(block_q, _BWD_BLOCK))
    fitted_k = _try_fit(tk, max(block_q, _BWD_BLOCK))
    if 2 * max(tq, tk) * d * itemsize <= _bwd_resident_bytes():
        # resident schedule: one head's full sequence (q+dO in the
        # dK/dV kernel, k+v in the dQ kernel) sits in VMEM — BOTH
        # sides must fit, hence max(tq, tk)
        dq, dk, dv = _flash_bwd_impl(*args, glse=glse_flat)
    elif fitted_q >= 8 and fitted_k >= 8:
        # streaming schedule: O(block) VMEM for any T (the long-context
        # path — T=32k+ stays on the fused Pallas kernels)
        dq, dk, dv = _flash_bwd_stream_impl(*args, glse=glse_flat)
    else:
        dq, dk, dv = _blocked_backward(flatq(q), flatk(k), flatk(v),
                                       flatq(g), causal, scale, block_q,
                                       glse=glse_flat)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


def _flash_bwd_rule(causal, scale, block_q, interpret, res, g):
    return _flash_bwd_shared(causal, scale, block_q, interpret, res, g)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, scale, block_q, interpret):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, interpret,
                           return_lse=True)


def _flash_lse_fwd_rule(q, k, v, causal, scale, block_q, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q,
                               interpret, return_lse=True)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd_rule(causal, scale, block_q, interpret, res, cts):
    g, glse = cts
    b, h, t, d = res[0].shape
    return _flash_bwd_shared(causal, scale, block_q, interpret, res, g,
                             glse=glse.reshape(b, h, t, 1))


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _validate_attn_shapes(q, k, v, causal, fn):
    """Rectangular attention contract: same (batch, heads, head_dim),
    k/v identical, and causal requires tq <= tk (rows suffix-align to
    the keys — the KV-cache decode convention; tq > tk would leave the
    leading rows with no visible key)."""
    if k.shape != v.shape:
        raise ValueError('%s requires identical k/v shapes; got %s / %s'
                         % (fn, k.shape, v.shape))
    if q.ndim != 4 or k.ndim != 4 or \
            q.shape[:2] != k.shape[:2] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            '%s wants (batch, heads, seq, head_dim) with matching '
            'batch/heads/head_dim; got q %s vs k %s'
            % (fn, q.shape, k.shape))
    if causal and q.shape[2] > k.shape[2]:
        raise ValueError(
            '%s: causal masking needs q_len <= kv_len (suffix '
            'alignment); got q_len=%d kv_len=%d'
            % (fn, q.shape[2], k.shape[2]))


def _needs_dense_fallback(tq, tk, block_q):
    """A length no schedule can tile — a property of the shape, never
    of the device: the check runs _try_fit with exactly the caps the
    forward AND backward schedules will use (_schedule_caps), so the
    predicate and the kernels can never disagree."""
    return any(_try_fit(t, cap) < 8 and t > 8
               for t, cap in _schedule_caps(tq, tk, block_q))


def _dense_attention_lse(q, k, v, causal, scale):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k).astype(jnp.float32) * scale
    if causal:
        mask = ((tk - tq) + jnp.arange(tq)[:, None] >=
                jnp.arange(tk)[None, :])
        s = jnp.where(mask, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd',
                     jnp.exp(s - lse[..., None]), v.astype(
                         jnp.float32)).astype(q.dtype)
    return out, lse.reshape(b * h, tq, 1)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=None, interpret=None):
    """flash_attention variant that ALSO returns the per-row logsumexp
    (bh, tq, 1) — the merge currency for ring attention / partial
    softmax combination — and is differentiable in BOTH outputs (the
    lse cotangent folds into the backward's D preprocess).  Lengths
    no schedule can tile take the dense jnp computation."""
    _validate_attn_shapes(q, k, v, causal, 'flash_attention_with_lse')
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if block_q is None:
        block_q = max(256, min(1024, tq // 32))
    # dense route: a sequence length with no usable power-of-two
    # block factor (natively differentiable either way)
    if _needs_dense_fallback(tq, tk, block_q):
        return _dense_attention_lse(q, k, v, causal, scale)
    if interpret is None:
        interpret = default_interpret(q, k, v)
    return _flash_lse(q, k, v, bool(causal), float(scale), int(block_q),
                      bool(interpret))


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    interpret=None):
    """Streaming Pallas attention.

    q: (batch, heads, q_len, head_dim); k, v: (batch, heads, kv_len,
    head_dim).  q_len == kv_len is self-attention; q_len != kv_len
    covers cross-attention and KV-cache decode, where causal rows are
    SUFFIX-aligned to the keys (query row i sees keys up to
    kv_len - q_len + i — the standard decode convention).  Returns
    q's shape.  On non-TPU backends runs in Pallas interpret mode
    (slow but correct) unless `interpret` is passed explicitly.

    block_q: row-tile edge.  Default (None) auto-scales with the
    sequence — 256 for short T, up to 1024 for long T, where the
    smaller grid measures 170 -> 117 ms at T=32k (docs/PERF.md).  An
    explicit value is honored exactly (e.g. to bound VMEM for large
    head_dim).
    """
    _validate_attn_shapes(q, k, v, causal, 'flash_attention')
    tq, tk = q.shape[2], k.shape[2]
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_q is None:
        block_q = max(256, min(1024, tq // 32))
    if _needs_dense_fallback(tq, tk, block_q):
        from .parallel.ring_attention import full_attention
        return full_attention(q, k, v, causal=causal, scale=scale)
    if interpret is None:
        interpret = default_interpret(q, k, v)
    return _flash(q, k, v, bool(causal), float(scale), int(block_q),
                  bool(interpret))


# ---------------------------------------------------------------------------
# Gated delta rule: the loop over a head's chunks with the state in VMEM.
# ops/lm.py makes the chunk-local tensors (batched XLA over all chunks)
# and calls these; what is left is a recurrence, S <- gamma S + k^T v_new
# with v_new = u - w S, whose state (dk x dv, float32) would cross HBM
# between every two operations as the carry of a lax.scan.  Here the grid
# is (blocks of heads: parallel, chunks: arbitrary), the state is a VMEM
# scratch zeroed at the first chunk of a head, and BlockSpecs stream one
# chunk's tensors a grid step.  The heads of a grid step are independent
# chains of small products, unrolled so the scheduler may interleave them.
# Every operand, accumulator and stored tensor is float32; the products
# take Mosaic's default for float32 operands.
# ---------------------------------------------------------------------------

# heads a grid step of the three kernels: 1, 2, 4 and 8 time the same on
# a v5e (the kernels wait for HBM, not for the chains), 2 holds least VMEM
DELTA_HEADS_PER_STEP = 2

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _mm(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


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

    for h in range(heads):
        s = s_ref[h]
        v_new = u_ref[h, 0] - _mm(w_ref[h, 0], s)
        if states:
            s0_ref[h, 0] = s
            vnew_ref[h, 0] = v_new
        else:
            o_ref[h, 0] = _mm(q_ref[h, 0], s) + _mm(intra_ref[h, 0], v_new)
        s_ref[h] = s * gamma_ref[h, 0] + _mm(k_ref[h, 0], v_new, _TN)


def _delta_bwd_kernel(do_ref, w_ref, intra_ref, q_ref, k_ref, gamma_ref,
                      s0_ref, vnew_ref, du_ref, dw_ref, dintra_ref, dq_ref,
                      dk_ref, dgamma_ref, ds_ref, *, heads):
    """One chunk of `heads` heads, the chunks taken last to first: dS is
    the cotangent of the state the chunk leaves behind."""
    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for h in range(heads):
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


def _delta_call(name, kernel, operands, outs, state, reverse=False):
    """pallas_call of a delta rule kernel over (heads, chunks, rows,
    cols) operands; `outs` are (rows, cols) of each result and `state`
    (dk, dv) of the scratch a head's state lives in.  `name` is the
    custom call's in the compiled program and in a trace."""
    bh, nc = operands[0].shape[:2]
    heads = max(d for d in range(1, DELTA_HEADS_PER_STEP + 1) if bh % d == 0)

    def spec(rows, cols):
        return pl.BlockSpec(
            (heads, 1, rows, cols),
            (lambda i, j: (i, nc - 1 - j, 0, 0)) if reverse
            else (lambda i, j: (i, j, 0, 0)))

    return pl.pallas_call(
        functools.partial(kernel, heads=heads),
        grid=(bh // heads, nc),
        in_specs=[spec(*x.shape[2:]) for x in operands],
        out_specs=[spec(*s) for s in outs],
        out_shape=[jax.ShapeDtypeStruct((bh, nc) + s, jnp.float32)
                   for s in outs],
        scratch_shapes=[pltpu.VMEM((heads,) + state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
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
