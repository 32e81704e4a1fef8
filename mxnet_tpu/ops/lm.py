"""Operators of language models (the layer kinds of Qwen3-Next, of
DeepSeek-V3's family, of AFMoE and of Ouro's looped stack).

Every operator takes tokens as rows, `(N, C)` with `N = sequences x
seq_len`, as `Embedding` gives them and `FullyConnected` takes them; the
operators that look along a sequence carry `seq_len` as an attribute and
fold the rows to `(N / seq_len, seq_len, ...)` themselves.  All are pure
JAX functions differentiated by jax.vjp inside the one compiled step,
like every other operator of the registry, and plain XLA but for three
things under custom gradient rules: GatedDeltaRule's core (five Pallas
kernels, pallas_ops.delta_rule_*: a chunk's own system and the loop
over the chunks, each forward and backward), the
attention core (pallas_ops.flash_attention's kernels, forward and
backward: grouped heads and a sliding window inside them) and
SparseMoE's combine (pallas_ops.add_rows: a tile's rows added to their
tokens by DMA, forward and backward).

  RMSNorm          x * rsqrt(mean x^2 + eps) * gamma, or * (1 + gamma)
  GatedAttention   per-head q/k RMS norm, partial or no rotary, grouped-
                   head causal softmax attention over every earlier key
                   or a sliding window of them (causal_attention:
                   the flash kernels, K and V not repeated over a
                   group, the tiles left of a window's band skipped,
                   a ragged T padded to whole sublanes), and the
                   sigmoid gate on the output, packed beside the query
                   or an input of its own
  LatentAttention  the core of multi-head latent attention: rotary by
                   adjacent pairs on the keys' one shared rotary head
                   and on each query head's rotary part, causal softmax
                   attention with keys wider than values
                   (causal_attention: the flash kernels)
  CausalConv1D     depthwise causal convolution along the sequence (2 kernels)
  GatedDeltaRule   the gated delta rule in chunks (WY form): a unit
                   lower triangular solve inside a chunk and the state
                   carried between chunks, both in VMEM by kernels,
                   forward and backward (the gradients by rule)
  SparseMoE        top-k routing over all experts (softmax scores, or
                   sigmoid scores with a selection bias and a scaling
                   factor), the held experts' part of the result by a
                   grouped product over the sorted (token, expert)
                   pairs; nothing is dropped
  LoopedDecoder    a stack of decoder layers (rotary multi-head
                   attention, a gated feed-forward, four plain RMS
                   norms a layer) run num_loops times with the same
                   weights and a final norm after each pass, as one
                   lax.scan whose body is traced once, each half layer
                   recomputed in the backward pass; each weight's
                   gradient summed over the passes in float32
"""
import collections
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, asbool, asfloat, asint
from .. import pallas_ops, profiler

F32 = jnp.float32
FLASH_BLOCK = 1024          # rows and keys a tile of the flash kernels
CHUNK = 64                  # tokens a chunk of GatedDeltaRule
LANES = 128                 # head widths GatedDeltaRule's kernels take
KEY_HEADS_PER_BLOCK = 4     # key heads GatedDeltaRule takes at a time
EXPERT_TILE = 256           # rows a tile of SparseMoE's grouped product


def _data_dtype(in_dtypes):
    return np.dtype(in_dtypes[0]) if in_dtypes[0] is not None \
        else np.dtype(np.float32)


def _infer_dtype(f32_inputs=()):
    """Inputs follow the data's type, except the small vectors named in
    `f32_inputs` (norm scales, decay rates), which stay float32 under a
    low-precision graph as BatchNorm's do."""
    def infer(attrs, in_dtypes):
        d = _data_dtype(in_dtypes)
        return [np.dtype(np.float32) if i in f32_inputs else d
                for i in range(len(in_dtypes))], [d]
    return infer


def _fold(x, seq_len):
    """(N, ...) rows of tokens -> (N / seq_len, seq_len, ...)."""
    if x.shape[0] % seq_len:
        raise ValueError('%d rows are no whole number of sequences of '
                         '%d tokens' % (x.shape[0], seq_len))
    return x.reshape((x.shape[0] // seq_len, seq_len) + x.shape[1:])


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps, zero_centered=False):
    """Over the last axis, in float32; the result in x's type."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = gamma.astype(F32)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


def _rms_infer_shape(attrs, in_shapes):
    if in_shapes[0] is not None and in_shapes[1] is None \
            and in_shapes[0][-1] != 0:
        in_shapes[1] = (in_shapes[0][-1],)
    return in_shapes


@register('RMSNorm', input_names=('data', 'gamma'),
          infer_shape=_rms_infer_shape, infer_dtype=_infer_dtype((1,)),
          hint='rmsnorm')
def _rms_norm(attrs, data, gamma):
    return rms_norm(data, gamma, asfloat(attrs.get('eps', 1e-6)),
                    asbool(attrs.get('zero_centered', False)))


# ---------------------------------------------------------------------------
# GatedAttention
# ---------------------------------------------------------------------------

def _rotary_tables(t, rotary_dim, theta):
    """cos and sin of position * theta^(-2i / rotary_dim), each
    (1, t, 1, rotary_dim / 2) in float32; position = index in the
    sequence."""
    inv_freq = 1.0 / (theta ** (np.arange(0, rotary_dim, 2,
                                          dtype=np.float64) / rotary_dim))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(ang), F32)[None, :, None, :],
            jnp.asarray(np.sin(ang), F32)[None, :, None, :])


def rotary(x, rotary_dim, theta):
    """Rotate-half rotary embedding on the first `rotary_dim` of the
    head dimension of x (B, T, heads, head_dim)."""
    half = rotary_dim // 2
    cos, sin = _rotary_tables(x.shape[1], rotary_dim, theta)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], \
        x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            rest], axis=-1)


def causal_attention(q, k, v, scale, block_q=None, window=None):
    """softmax(q k^T * scale + causal) v with grouped heads: q
    (B, T, kv, group, d), k (B, T, kv, d) and v (B, T, kv, dv), whose
    width is its own (latent attention's keys are wider than its
    values); the result is (B, T, kv, group, dv).  With `window` row i
    sees key j iff 0 <= i - j < window (a window that reaches the
    sequence's first key from its last row is no window).

    One path, pallas_ops.flash_attention's kernels: the whole batch in
    one call with the heads in front of the rows (one transpose each of
    q, k, v and the result, whatever the group: (B, kv * group, T, d)
    is the layout the kernels read a group's heads from).  K and V are
    not repeated; dK and dV sum over a group in float32 inside the
    backward kernel; under a window the grids skip the tiles left of
    the band.  Scores, probabilities and their gradients live in VMEM a
    tile at a time, forward and backward; residuals are q, k, v, o and
    the rows' log-sum-exp; operands keep their type, with float32
    scores, sums and accumulators.

    A T the kernels' schedules do not tile (no block of whole sublanes,
    8 rows, divides it) is padded with zero rows at its end up to the
    next multiple of 8 and the result sliced back, exactly: a real row
    sees no key after itself, so never a padded one; a padded row sees
    real keys or itself, so it stays finite, and the slice gives it a
    zero cotangent, so it adds nothing to dK or dV.  A T they tile is
    passed as it is.

    `block_q`, a power of two of at least 8 rows where given, is the
    tile's edge; left out it is FLASH_BLOCK (tiles of 1024 x 1024 timed
    best at T = 8,192 with keys of 192 over values of 128: PERF.md
    section 6, PR 32) or under a window what pallas_ops.window_block
    makes of it.  profiler.attention_stats() counts the lowerings, with
    the query-key positions the kernels' tiles score (at the padded
    length) and those the mask lets through (at T)."""
    t, group = q.shape[1], q.shape[3]
    if window is not None and window >= t:
        window = None
    if block_q is not None:
        tile = block_q
    elif window is None:
        tile = FLASH_BLOCK
    else:
        tile = pallas_ops.window_block(window, FLASH_BLOCK)
    pad = -t % 8 if pallas_ops._needs_dense_fallback(t, t, tile) else 0
    reach = t if window is None else window
    heads = q.shape[2] * group
    profiler.note_attention_lowering(
        'kernel', heads=heads, group=group, dk=q.shape[4], dv=v.shape[3],
        t=t, window=window, keys_visited=q.shape[0] * heads *
        pallas_ops.visited_positions(t + pad, tile, window),
        keys_needed=q.shape[0] * heads *
        (reach * (reach + 1) // 2 + (t - reach) * reach))
    if pad:
        q, k, v = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                   for x in (q, k, v))
    b, tp = q.shape[:2]
    # ungrouped heads keep the slice they had (their step lowers to the
    # program it was); a group's heads follow one another, as the
    # kernels' index maps read them
    o = pallas_ops.flash_attention(
        jnp.swapaxes(q[:, :, :, 0] if group == 1 else
                     q.reshape(b, tp, heads, q.shape[4]), 1, 2),
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), causal=True,
        scale=scale, block_q=tile, window=window)
    o = jnp.swapaxes(o, 1, 2)[:, :t]
    return o[:, :, :, None] if group == 1 else \
        o.reshape(b, t, q.shape[2], group, v.shape[3])


def _attn_infer_shape(attrs, in_shapes):
    d = asint(attrs['head_dim'])
    for i in (3, 4):
        if in_shapes[i] is None:
            in_shapes[i] = (d,)
    return in_shapes


def _attn_input_names(attrs):
    """The gate comes packed beside each head's query, or (attribute
    `separate_gate`) as an input of its own, heads * head_dim wide."""
    if asbool(attrs.get('separate_gate', False)):
        return ('query', 'key', 'value', 'q_norm_gamma', 'k_norm_gamma',
                'gate')
    return ('query_gate', 'key', 'value', 'q_norm_gamma', 'k_norm_gamma')


@register('GatedAttention', input_names=_attn_input_names,
          infer_shape=_attn_infer_shape,
          infer_dtype=_infer_dtype((3, 4)), hint='gatedattention')
def _gated_attention(attrs, qg, k, v, q_gamma, k_gamma, gate=None):
    """Attributes beside the heads' counts and sizes, each defaulting
    to Qwen3-Next's layer: `rotary_dim` (the head's first dims that
    rotary turns; 0: no rotary, keys carry no position), `window` (a
    row sees its last `window` keys; left out: every earlier key),
    `zero_centered` (the q/k norms' scale is 1 + gamma; false: gamma),
    `separate_gate` (see _attn_input_names)."""
    heads, kv = asint(attrs['num_heads']), asint(attrs['num_kv_heads'])
    d, seq_len = asint(attrs['head_dim']), asint(attrs['seq_len'])
    rotary_dim = asint(attrs.get('rotary_dim', d))
    theta = asfloat(attrs.get('rope_theta', 10000.0))
    eps = asfloat(attrs.get('eps', 1e-6))
    centered = asbool(attrs.get('zero_centered', True))
    window = asint(attrs['window']) if 'window' in attrs else None
    if heads % kv:
        raise ValueError('%d query heads over %d key-value heads'
                         % (heads, kv))
    if window is not None and window < 1:
        raise ValueError('a window of %d keys' % window)
    n = qg.shape[0]
    if gate is None:
        qg = qg.reshape(n, heads, 2 * d)    # [q, gate] split per head
        q, gate = qg[..., :d], qg[..., d:].reshape(n, heads * d)
    else:
        q = qg.reshape(n, heads, d)
    q = rms_norm(q, q_gamma, eps, zero_centered=centered)
    k = rms_norm(k.reshape(n, kv, d), k_gamma, eps, zero_centered=centered)

    def positioned(x):
        x = _fold(x, seq_len)
        return rotary(x.astype(F32), rotary_dim, theta) if rotary_dim else x

    q, k = positioned(q), positioned(k)
    b = q.shape[0]
    o = causal_attention(
        q.astype(v.dtype).reshape(b, seq_len, kv, heads // kv, d),
        k.astype(v.dtype), _fold(v.reshape(n, kv, d), seq_len),
        1.0 / math.sqrt(d), window=window)
    o = o.reshape(n, heads * d)
    return (o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))
            ).astype(o.dtype)


# ---------------------------------------------------------------------------
# LatentAttention
# ---------------------------------------------------------------------------

def rotary_pairs(x, theta):
    """Rotary embedding that turns adjacent pairs: dims (2i, 2i + 1) of
    the last axis of x (B, T, heads, d) by position * theta^(-2i / d),
    in place (the published weights' own order; DeepSeek-V3's code
    moves the even dims before the odd ones and turns halves, which
    gives the same scores since queries and keys share the order)."""
    d = x.shape[-1]
    cos, sin = _rotary_tables(x.shape[1], d, theta)
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@register('LatentAttention', input_names=('query', 'key_value', 'key_rope'),
          hint='latentattention')
def _latent_attention(attrs, q, kv, k_pe):
    """query (N, heads * (nope + rope)): each head [q_nope | q_pe];
    key_value (N, heads * (nope + v)): each head [k_nope | v], the
    latent's up-projection; key_rope (N, rope): the keys' one rotary
    head, shared by all heads.  Head h attends with q_h = [q_nope_h |
    rot(q_pe_h)] over k_h = [k_nope_h | rot(k_pe)], scaled by
    1 / sqrt(nope + rope), to values of width v.  Returns
    (N, heads * v).  Every query head has its own key head (group 1);
    causal_attention runs the flash kernels with a value width of
    their own (seq_len 8,192 in the cell: tiles of 1024 x 1024, float32
    scores in VMEM only)."""
    heads, seq_len = asint(attrs['num_heads']), asint(attrs['seq_len'])
    nope, rope = (asint(attrs['qk_nope_head_dim']),
                  asint(attrs['qk_rope_head_dim']))
    dv = asint(attrs['v_head_dim'])
    theta = asfloat(attrs.get('rope_theta', 10000.0))
    n = q.shape[0]
    q = _fold(q.reshape(n, heads, nope + rope), seq_len)
    kv = _fold(kv.reshape(n, heads, nope + dv), seq_len)
    k_pe = _fold(k_pe.reshape(n, 1, rope), seq_len)
    dtype = kv.dtype
    q_pe = rotary_pairs(q[..., nope:].astype(F32), theta).astype(dtype)
    k_pe = rotary_pairs(k_pe.astype(F32), theta).astype(dtype)
    b = q.shape[0]
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (b, seq_len, heads, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    o = causal_attention(q[:, :, :, None, :], k, kv[..., nope:],
                         1.0 / math.sqrt(nope + rope))
    return o.reshape(n, heads * dv)


# ---------------------------------------------------------------------------
# CausalConv1D
# ---------------------------------------------------------------------------

def _conv_infer_shape(attrs, in_shapes):
    if in_shapes[0] is not None and in_shapes[1] is None \
            and in_shapes[0][-1] != 0:
        in_shapes[1] = (in_shapes[0][-1], asint(attrs['kernel']))
    return in_shapes


def causal_conv(data, weight, seq_len):
    """Depthwise, over every sequence of seq_len rows: y[t, c] = sum_j
    w[c, j] * x[t - (W-1) + j, c], positions before a sequence's start
    read as zero, in float32; y in data's type.  data (N, C), weight (C,
    W).  Where seq_len is whole sublane tiles of data's type, C whole
    lanes and W - 1 rows fit in one tile (pallas_ops.conv_fits: the
    Qwen3-Next cell's 2 sequences of 8,192 rows of 8,192 bfloat16 under
    a width of 4), pallas_ops.causal_conv1d's kernels, which read and
    write each element once; any other shape causal_conv_xla, which
    copies x to float32 and shifts it along the sublanes.
    profiler.causal_conv_stats() counts the lowerings by path."""
    n, c = data.shape
    width = weight.shape[1]
    if pallas_ops.conv_fits(seq_len, c, width, data.dtype):
        profiler.note_causal_conv('kernel', n // seq_len, seq_len, c, width)
        return pallas_ops.causal_conv1d(_fold(data, seq_len),
                                        weight).reshape(data.shape)
    profiler.note_causal_conv('xla', n // seq_len, seq_len, c, width)
    return causal_conv_xla(data, weight, seq_len)


def causal_conv_xla(data, weight, seq_len):
    """causal_conv as plain XLA, for any shape."""
    width = weight.shape[1]
    x = _fold(data, seq_len).astype(F32)
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = weight.astype(F32)
    y = sum(xp[:, j:j + seq_len] * w[:, j] for j in range(width))
    return y.reshape(data.shape).astype(data.dtype)


@register('CausalConv1D', input_names=('data', 'weight'),
          infer_shape=_conv_infer_shape, hint='causalconv1d')
def _causal_conv1d(attrs, data, weight):
    """causal_conv; weight (C, kernel)."""
    return causal_conv(data, weight, asint(attrs['seq_len']))


# ---------------------------------------------------------------------------
# GatedDeltaRule
# ---------------------------------------------------------------------------

def _heads_flat(xs):
    """(B, H, chunks, ...) -> (B * H, chunks, ...), as the kernels take."""
    return [x.reshape((-1,) + x.shape[2:]) for x in xs]


def _note_delta_rule(q, v, **what):
    """profiler.delta_rule_stats(): taken from shapes while the rule is
    traced.  q (B, H, chunks, C, dk), v (..., dv)."""
    profiler.note_delta_rule(heads=q.shape[0] * q.shape[1],
                             chunks=q.shape[2], chunk=q.shape[3],
                             dk=q.shape[4], dv=v.shape[4], **what)


@jax.custom_vjp
def _delta_rule_chunked(q, k, v, g, beta):
    """o (B, H, chunks, C, dv) of inputs already cut into chunks, dk
    and dv whole lanes.  Inside a chunk the unit lower triangular system
    of the WY form is made and solved (pallas_ops.delta_rule_local: u,
    w, intra, q_in, k_out, gamma of every chunk).  Between chunks:
    v_new = u_c - w_c S; o_c = q_in_c S + intra_c v_new; S <- gamma_c S
    + k_out_c^T v_new, in one kernel that keeps S in VMEM
    (pallas_ops.delta_rule_chunks)."""
    _note_delta_rule(q, v, local_makes=1)
    local = pallas_ops.delta_rule_local(*_heads_flat((q, k, v, g, beta)))
    return pallas_ops.delta_rule_chunks(*local).reshape(v.shape)


def _delta_rule_chunked_fwd(q, k, v, g, beta):
    return _delta_rule_chunked(q, k, v, g, beta), (q, k, v, g, beta)


def _delta_rule_chunked_bwd(inputs, do):
    """Only the inputs were kept: the chunk-local tensors (with T, the
    solved system) and every chunk's state are made again, the loop
    runs last chunk to first in its kernel, and the chunk-local half's
    gradient is a kernel too, written by rule."""
    _note_delta_rule(inputs[0], inputs[2], local_makes=1, backward_rules=1)
    flat = _heads_flat(inputs)
    u, w, intra, q_in, k_out, gamma, inv = pallas_ops.delta_rule_local(
        *flat, with_inverse=True)
    s0, v_new = pallas_ops.delta_rule_states(u, w, k_out, gamma)
    grads = pallas_ops.delta_rule_chunks_bwd(
        do.reshape(u.shape), w, intra, q_in, k_out, gamma, s0, v_new)
    return tuple(d.reshape(x.shape) for d, x in zip(
        pallas_ops.delta_rule_local_bwd(*flat, inv, grads), inputs))


_delta_rule_chunked.defvjp(_delta_rule_chunked_fwd, _delta_rule_chunked_bwd)


def _pad_axis(x, axis, multiple):
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """Per head, token by token: S <- exp(g_t) S; d = beta_t (v_t -
    S^T k_t); S <- S + k_t (x) d; o_t = S^T q_t.  Computed a chunk at a
    time: inside a chunk the rule is a unit lower triangular system
    (the WY form), between chunks the state S (dk x dv) is carried;
    both halves are Pallas kernels that keep a chunk's matrices and the
    state in VMEM, forward and backward (off the TPU the same kernels
    in interpret mode).
    q, k (B, H, T, dk), v (B, H, T, dv), g (log decay <= 0) and beta
    (B, H, T), all float32.  Returns o (B, H, T, dv) in float32.

    T is padded to whole chunks with beta = 0 and g = 0, tokens that
    leave the state alone; dk and dv to whole lanes with zeros: a zero
    column of q and k adds nothing to any product and a zero column of
    v gives a zero column of o."""
    bsz, h, t, _ = q.shape
    dv = v.shape[-1]
    q, k, v = (_pad_axis(_pad_axis(a, 2, chunk), 3, LANES)
               for a in (q, k, v))
    g, beta = (_pad_axis(a, 2, chunk) for a in (g, beta))
    nc = q.shape[2] // chunk
    q, k, v, g, beta = (a.reshape((bsz, h, nc, chunk) + a.shape[3:])
                        for a in (q, k, v, g, beta))
    _note_delta_rule(q, v, lowerings=1)
    o = _delta_rule_chunked(q, k, v, g, beta)
    return o.reshape(bsz, h, nc * chunk, -1)[:, :, :t, :dv]


def _gdr_infer_shape(attrs, in_shapes):
    hv = asint(attrs['num_v_heads'])
    for i in (3, 4):
        if in_shapes[i] is None:
            in_shapes[i] = (hv,)
    return in_shapes


@register('GatedDeltaRule',
          input_names=('data', 'a', 'b', 'a_log', 'dt_bias'),
          infer_shape=_gdr_infer_shape,
          infer_dtype=_infer_dtype((3, 4)), hint='gateddeltarule')
def _gated_delta_rule(attrs, qkv, a, b, a_log, dt_bias):
    """data: [q | k | v] rows after the causal convolution and silu;
    a, b: the decay's and the write strength's pre-activations, one a
    value head.  Returns (N, num_v_heads * head_v_dim).

    One sequence and KEY_HEADS_PER_BLOCK key heads (with their value
    heads) at a time: the chunk-local tensors of a block in float32
    are many times its inputs, and all heads of all sequences at once
    do not fit.  A block keeps its bfloat16 inputs for the backward
    pass and no more (jax.checkpoint): the rule's own gradient keeps
    the float32 q, k, v it was called with, which are the block's
    normalised and repeated inputs over again, and makes everything
    else again; the forward kernel is not run a second time (nothing
    reads its result there)."""
    hk, hv = asint(attrs['num_k_heads']), asint(attrs['num_v_heads'])
    dk, dv = asint(attrs['head_k_dim']), asint(attrs['head_v_dim'])
    seq_len = asint(attrs['seq_len'])
    per_block = min(KEY_HEADS_PER_BLOCK, hk)
    if hv % hk or hk % per_block:
        raise ValueError('%d value heads on %d key heads in blocks of %d'
                         % (hv, hk, per_block))
    groups, rep = hk // per_block, hv // hk
    n = qkv.shape[0]
    bsz = n // seq_len

    def blocks(x, heads):
        """(N, heads * d) -> (sequences * groups, T, heads / groups, d)"""
        x = _fold(x, seq_len).reshape(bsz, seq_len, groups,
                                      heads // groups, -1)
        return jnp.moveaxis(x, 2, 1).reshape(
            (bsz * groups, seq_len) + x.shape[3:])

    def per_group(x):
        """(heads,) -> (sequences * groups, heads / groups)"""
        return jnp.tile(x.astype(F32).reshape(groups, -1), (bsz, 1))

    def l2norm(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    def one_block(xs):
        q, k, v, a_, b_, a_log_, dt_bias_ = xs
        q = jnp.repeat(l2norm(q.astype(F32)) / math.sqrt(dk), rep, axis=1)
        k = jnp.repeat(l2norm(k.astype(F32)), rep, axis=1)
        g = -jnp.exp(a_log_) * jax.nn.softplus(
            a_[..., 0].astype(F32) + dt_bias_)
        beta = jax.nn.sigmoid(b_[..., 0].astype(F32))
        o = chunk_gated_delta_rule(*(jnp.moveaxis(x, 1, 0)[None] for x in
                                     (q, k, v.astype(F32), g, beta)))
        return jnp.moveaxis(o[0], 0, 1).astype(qkv.dtype)

    o = lax.map(jax.checkpoint(one_block), (
        blocks(qkv[:, :hk * dk], hk), blocks(qkv[:, hk * dk:2 * hk * dk], hk),
        blocks(qkv[:, 2 * hk * dk:], hv), blocks(a, hv), blocks(b, hv),
        per_group(a_log), per_group(dt_bias)))
    o = o.reshape(bsz, groups, seq_len, hv // groups, dv)
    return jnp.moveaxis(o, 1, 2).reshape(n, hv * dv)


# ---------------------------------------------------------------------------
# SparseMoE
# ---------------------------------------------------------------------------

def _tile_plan(group_sizes, tile, max_tiles):
    """The sorted pairs of each expert cut into tiles of `tile` rows: a
    tile never spans two experts.  Returns (expert, first row, valid
    rows) of every tile slot, and how many slots are in use."""
    tiles = (group_sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    row_start = jnp.cumsum(group_sizes) - group_sizes
    t = jnp.arange(max_tiles, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(tile_end, t, side='right'),
                    group_sizes.shape[0] - 1).astype(jnp.int32)
    j = t - (tile_end - tiles)[e]
    valid = jnp.where(t < tile_end[-1],
                      jnp.clip(group_sizes[e] - j * tile, 0, tile), 0)
    return e, row_start[e] + j * tile, valid, tile_end[-1]


def _expert_mlp(xt, wg, wu, wd):
    """down(silu(gate x) * up x) of one expert on a tile of rows, with
    what the backward pass needs of it."""
    g = jnp.dot(xt, wg.T, preferred_element_type=F32)
    u = jnp.dot(xt, wu.T, preferred_element_type=F32)
    h = (jax.nn.silu(g) * u).astype(xt.dtype)
    return jnp.dot(h, wd.T, preferred_element_type=F32), g, u, h


def _add_at(acc, e, value):
    """acc[e] += value, in place."""
    index = (e,) + (0,) * value.ndim
    return lax.dynamic_update_slice(
        acc, lax.dynamic_slice(acc, index, (1,) + value.shape) + value[None],
        index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def grouped_experts(tile, x, wg, wu, wd, token_of, weight_of, pair_weight,
                    order, group_sizes):
    """y[n] = sum over token n's pairs (n, e) of weight * mlp_e(x[n]).
    x (N, H); wg, wu (E, I, H); wd (E, H, I).  The pairs are sorted by
    expert: `group_sizes` of them for each expert held here, the rest
    (pairs of experts held elsewhere) behind them.  token_of (M,): the
    token and (not differentiated) the routing weight of each sorted
    pair; order (M,): each sorted pair's index n * k + j, by which the
    backward sorts the weights' gradient back to the pairs' order;
    pair_weight (N, k), through which that gradient goes.

    The loop runs over the tiles in use only and adds each tile's
    weighted rows to their tokens in a float32 (N, H) sum
    (pallas_ops.add_rows: a live row's line of the sum is read, added
    to and written back by DMA): a token's pairs are summed in expert
    order, and nothing is dropped.  Only token_of and order are
    sized for the worst case (every pair lands here); the work and the
    rows moved are what was routed here."""
    return _grouped_fwd(tile, x, wg, wu, wd, token_of, weight_of,
                        pair_weight, order, group_sizes)[0]


def _max_tiles(pairs, tile, experts):
    return -(-pairs // tile) + experts


def _grouped_plan(tile, token_of, wg, group_sizes):
    return _tile_plan(group_sizes, tile,
                      _max_tiles(token_of.shape[0], tile, wg.shape[0]))


def _tile(plan, t, tile, token_of):
    """Tile t's expert, first sorted row, how many rows are live (the
    first ones) and which, and their tokens: unique, since a token
    picks an expert once (rows past the expert's last pair read
    token_of's row 0 and add to no token)."""
    expert, row0, valid, _ = plan
    live = jnp.arange(tile) < valid[t]
    rows = jnp.where(live, row0[t] + jnp.arange(tile), 0)
    return expert[t], row0[t], valid[t], live[:, None], token_of[rows]


def _add_tile(acc, tok, rows, valid):
    """acc[tok] += rows for a tile's live rows, acc as row_tiles()
    shapes it."""
    return pallas_ops.add_rows(acc, tok, pallas_ops.row_tiles(rows), valid)


def _row_sum(x):
    """A float32 zero sum of x's shape, as _add_tile takes it."""
    return pallas_ops.row_tiles(jnp.zeros(x.shape, F32))


def _grouped_fwd(tile, x, wg, wu, wd, token_of, weight_of, pair_weight,
                 order, group_sizes):
    plan = _grouped_plan(tile, token_of, wg, group_sizes)
    weights = jnp.pad(weight_of.astype(F32), (0, tile))

    def body(t, y):
        e, row0, valid, _, tok = _tile(plan, t, tile, token_of)
        yt = _expert_mlp(x[tok], wg[e], wu[e], wd[e])[0]
        wt = lax.dynamic_slice(weights, (row0,), (tile,))[:, None]
        return _add_tile(y, tok, yt * wt, valid)

    y = lax.fori_loop(0, plan[3], body, _row_sum(x))
    return y.reshape(x.shape).astype(x.dtype), (
        x, wg, wu, wd, token_of, weight_of, order, group_sizes)


def _grouped_bwd(tile, res, dy):
    x, wg, wu, wd, token_of, weight_of, order, group_sizes = res
    plan = _grouped_plan(tile, token_of, wg, group_sizes)
    m = token_of.shape[0]
    weight_of = jnp.pad(weight_of.astype(F32), (0, tile))

    def body(t, carry):
        dx, dws, dwg, dwu, dwd = carry
        e, row0, valid, live, tok = _tile(plan, t, tile, token_of)
        xt = x[tok]
        yt, g, u, h = _expert_mlp(xt, wg[e], wu[e], wd[e])
        dyt = dy[tok].astype(F32)
        dws = lax.dynamic_update_slice(dws, jnp.where(
            live[:, 0], jnp.sum(dyt * yt, axis=-1), 0.0), (row0,))
        wt = lax.dynamic_slice(weight_of, (row0,), (tile,))[:, None]
        dyt = jnp.where(live, dyt * wt, 0.0).astype(x.dtype)
        dh = jnp.dot(dyt, wd[e], preferred_element_type=F32)
        sig = jax.nn.sigmoid(g)
        du = (dh * g * sig).astype(x.dtype)
        dg = (dh * u * sig * (1.0 + g * (1.0 - sig))).astype(x.dtype)
        dxt = jnp.dot(dg, wg[e], preferred_element_type=F32) + \
            jnp.dot(du, wu[e], preferred_element_type=F32)
        return (_add_tile(dx, tok, dxt, valid), dws,
                _add_at(dwg, e, jnp.dot(dg.T, xt,
                                        preferred_element_type=F32)),
                _add_at(dwu, e, jnp.dot(du.T, xt,
                                        preferred_element_type=F32)),
                _add_at(dwd, e, jnp.dot(dyt.T, h,
                                        preferred_element_type=F32)))

    dx, dws, dwg, dwu, dwd = lax.fori_loop(
        0, plan[3], body,
        (_row_sum(x), jnp.zeros((m + tile,), F32), jnp.zeros(wg.shape, F32),
         jnp.zeros(wu.shape, F32), jnp.zeros(wd.shape, F32)))
    return (dx.reshape(x.shape).astype(x.dtype), dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype), None, None,
            _to_pairs(order, dws[:m]).reshape(x.shape[0], -1).astype(
                weight_of.dtype), None, None)


def _to_pairs(order, values):
    """values of the sorted pairs back in the pairs' own order:
    values[position], position the inverse of `order`, by one sort (on
    a TPU v5e 0.21 ms at 163,840 pairs, where that gather took 1.18)."""
    return lax.sort((order, values), num_keys=1, is_stable=False)[1]


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def route(x, router_weight, top_k, normalize, scoring='softmax',
          bias=None, scale=1.0):
    """Scores over all experts in float32 (`scoring`: softmax, or
    sigmoid as DeepSeek-V3 has it), the top k and their weights (over
    the chosen k, whether held here or not).  `bias` (experts,) is
    added for the choice alone and is not in the weights; a sigmoid's
    weights are normalised over sum + 1e-20, as published; `scale`
    multiplies them last."""
    logits = jnp.dot(x, router_weight.T, preferred_element_type=F32)
    if scoring == 'softmax':
        scores, tiny = jax.nn.softmax(logits, axis=-1), 0.0
    elif scoring == 'sigmoid':
        scores, tiny = jax.nn.sigmoid(logits), 1e-20
    else:
        raise ValueError('scoring %r: softmax or sigmoid' % (scoring,))
    if bias is None:
        vals, idx = lax.top_k(scores, top_k)
    else:
        _, idx = lax.top_k(scores + lax.stop_gradient(bias.astype(F32)),
                           top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        total = jnp.sum(vals, axis=-1, keepdims=True)
        vals = vals / (total + tiny) if tiny else vals / total
    if scale != 1.0:
        vals = vals * scale
    return vals, idx


def _key_bits(pairs, held):
    """b with 2**b >= pairs, where slot * 2**b + pair fits an int32 for
    every slot 0..held; None where it does not."""
    bits = max(pairs - 1, 1).bit_length()
    return bits if (held + 1) << bits <= 2 ** 31 else None


def _pair_plan(key, weight, held, top_k):
    """The (token, expert) pairs in expert order for grouped_experts:
    (order, weight[order], group_sizes).  `key` is each pair's slot: a
    held expert, or `held` for the pairs held elsewhere; a slot's pairs
    keep their own order.  One sort carries the weights: where slot *
    2**b + pair fits an int32 (_key_bits, from the shapes alone) of
    those unique keys, else of (slot, pair) stably by slot (on a TPU
    v5e at 163,840 pairs a plan took 0.15 ms packed, 0.23 stable); the
    group sizes are counted from the slots' one-hot.
    profiler.moe_plan_stats() counts the lowerings by path."""
    bits = _key_bits(key.shape[0], held)
    profiler.note_moe_plan('stable' if bits is None else 'packed',
                           key.shape[0], held + 1, top_k)
    group_sizes = jnp.sum(key == jnp.arange(held)[:, None], axis=1,
                          dtype=jnp.int32)
    pair = lax.iota(jnp.int32, key.shape[0])
    if bits is None:
        _, order, weight_of = lax.sort((key, pair, weight), num_keys=1)
        return order, weight_of, group_sizes
    packed, weight_of = lax.sort(((key << bits) | pair, weight), num_keys=1,
                                 is_stable=False)
    return packed & ((1 << bits) - 1), weight_of, group_sizes


def sparse_moe(x, router_weight, wg, wu, wd, top_k, expert_offset,
               normalize=True, tile=EXPERT_TILE, **routing):
    """The held experts' part of a top-k expert layer.  wg, wu
    (held, I, H) and wd (held, H, I) are experts expert_offset ..
    expert_offset + held of router_weight.shape[0]; `routing` is
    route()'s scoring, bias and scale.  Returns (y, assigned,
    computed): per-expert counts of the pairs the router made (all
    experts) and of those computed here."""
    n_exp, held = router_weight.shape[0], wg.shape[0]
    vals, idx = route(x, router_weight, top_k, normalize, **routing)
    local = idx.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    order, weight_of, group_sizes = _pair_plan(
        key, lax.stop_gradient(vals).reshape(-1), held, top_k)
    y = grouped_experts(tile, x, wg, wu, wd, order // top_k, weight_of,
                        vals, order, group_sizes)
    assigned = jnp.sum(idx[..., None] == jnp.arange(n_exp), axis=(0, 1),
                       dtype=jnp.int32)
    # what the grouped product's loop ran over: the tiles' valid rows
    e, _, valid, _ = _grouped_plan(tile, key, wg, group_sizes)
    computed = jnp.zeros((n_exp,), jnp.int32).at[e + expert_offset].add(
        valid.astype(jnp.int32))
    return y, assigned, computed


def _has_selection_bias(attrs):
    """topk_method 'noaux_tc' (DeepSeek-V3's name for it): the choice
    is made on scores + selection_bias, auxiliary state of the node."""
    method = str(attrs.get('topk_method', 'greedy'))
    if method not in ('greedy', 'noaux_tc'):
        raise ValueError('topk_method %r: greedy or noaux_tc' % method)
    return method == 'noaux_tc'


def _moe_input_names(attrs):
    names = ('data', 'router_weight', 'gate_weight', 'up_weight',
             'down_weight', 'counts')
    return names + ('selection_bias',) if _has_selection_bias(attrs) \
        else names


def _moe_num_aux(attrs):
    return 2 if _has_selection_bias(attrs) else 1


def _moe_infer_shape(attrs, in_shapes):
    if in_shapes[0] is None or in_shapes[0][-1] == 0:
        return in_shapes
    hidden = in_shapes[0][-1]
    n_exp, held = asint(attrs['num_experts']), asint(
        attrs['num_experts_held'])
    inter = asint(attrs['intermediate_size'])
    wanted = [(n_exp, hidden), (held * inter, hidden),
              (held * inter, hidden), (held * hidden, inter), (2, n_exp),
              (n_exp,)]
    for i, s in enumerate(wanted[:len(in_shapes) - 1], start=1):
        if in_shapes[i] is None:
            in_shapes[i] = s
    return in_shapes


def _moe_infer_dtype(attrs, in_dtypes):
    """Weights follow the data; `counts` is int32 and the selection
    bias float32 whatever the graph's type."""
    d = _data_dtype(in_dtypes)
    aux = [np.dtype(np.int32), np.dtype(np.float32)][:_moe_num_aux(attrs)]
    return [d] * (len(in_dtypes) - len(aux)) + aux, [d]


def _sparse_moe(attrs, inputs, auxs, op_ctx):
    x, router_weight, wg, wu, wd = inputs
    held = asint(attrs['num_experts_held'])
    offset = asint(attrs.get('expert_offset', 0))
    if not 0 <= offset <= router_weight.shape[0] - held:
        raise ValueError('experts %d..%d of %d' % (
            offset, offset + held, router_weight.shape[0]))
    hidden = x.shape[-1]
    biased = _has_selection_bias(attrs)
    y, assigned, computed = sparse_moe(
        x, router_weight, wg.reshape(held, -1, hidden),
        wu.reshape(held, -1, hidden), wd.reshape(held, hidden, -1),
        asint(attrs['top_k']), offset,
        asbool(attrs.get('normalize', True)),
        scoring=str(attrs.get('scoring_func', 'softmax')),
        bias=auxs[1] if biased else None,
        scale=asfloat(attrs.get('routed_scaling_factor', 1.0)))
    new_auxs = [auxs[0] + lax.stop_gradient(jnp.stack([assigned,
                                                       computed]))]
    if biased:
        new_auxs.append(_updated_bias(
            auxs[1], assigned, asfloat(attrs.get('bias_update_rate', 0.0))))
    return [y], new_auxs


def _updated_bias(bias, assigned, rate):
    """DeepSeek-V3's rule (arXiv:2412.19437, 2.1.2), once a training
    pass: b_e += rate * sign(mean load - load_e), the loads this pass's
    own assignments over all experts.  No gradient reaches the bias."""
    if not rate:
        return bias
    load = lax.stop_gradient(assigned).astype(F32)
    return bias + rate * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


def _fold_counts(attrs, deltas):
    """`counts`' growth into profiler.moe_stats(): row 0 the pairs the
    router assigned to each expert, row 1 those computed here (the
    selection bias, where there is one, is no counter)."""
    assigned, computed = deltas[0]
    first = asint(attrs.get('expert_offset', 0))
    here = slice(first, first + asint(attrs['num_experts_held']))
    profiler.add_moe_stats(
        routed=computed.sum(),
        dropped=(assigned[here] - computed[here]).sum(),
        per_expert_routed=computed, assignments=assigned.sum())


register('SparseMoE', input_names=_moe_input_names, num_aux=_moe_num_aux,
         mutable_aux=True, infer_shape=_moe_infer_shape,
         infer_dtype=_moe_infer_dtype, hint='sparsemoe',
         simple=False, fold_aux=_fold_counts)(_sparse_moe)


# ---------------------------------------------------------------------------
# LoopedDecoder
# ---------------------------------------------------------------------------

# a layer's inputs in order: the attention half's, then the feed-forward
# half's (each half is one function of the stream and its own weights)
_ATTENTION_HALF = ('input_norm_gamma', 'q_proj_weight', 'k_proj_weight',
                   'v_proj_weight', 'o_proj_weight', 'post_attn_norm_gamma')
_MLP_HALF = ('pre_mlp_norm_gamma', 'mlp_gate_proj_weight',
             'mlp_up_proj_weight', 'mlp_down_proj_weight',
             'post_mlp_norm_gamma')
_LAYER_INPUTS = _ATTENTION_HALF + _MLP_HALF


# the static shape of a looped stack
_Loop = collections.namedtuple(
    '_Loop', 'loops heads kv d seq_len theta eps')


def _rope_attention(loop, x, wq, wk, wv):
    """Causal softmax attention of x (N, hidden) with rotate-half rotary
    on every dim of the heads, no q/k norm and no gate: causal_attention
    with `kv` key-value heads (16 over 16 in Ouro: group 1)."""
    n, d, kv = x.shape[0], loop.d, loop.kv
    group = loop.heads // kv

    def heads(w, count):
        return _fold(jnp.dot(x, w.T).reshape(n, count, d), loop.seq_len)

    q = rotary(heads(wq, loop.heads).astype(F32), d, loop.theta)
    k = rotary(heads(wk, kv).astype(F32), d, loop.theta)
    b = q.shape[0]
    o = causal_attention(
        q.astype(x.dtype).reshape(b, loop.seq_len, kv, group, d),
        k.astype(x.dtype), heads(wv, kv), 1.0 / math.sqrt(d))
    return o.reshape(n, loop.heads * d)


def _attention_half(loop, h, w):
    """h + N2(W_o MHA(N1(h))), the sandwich norms plain RMS norms."""
    g1, wq, wk, wv, wo, g2 = w
    with jax.named_scope('norm'):
        x = rms_norm(h, g1, loop.eps)
    with jax.named_scope('attention'):
        o = jnp.dot(_rope_attention(loop, x, wq, wk, wv), wo.T)
    with jax.named_scope('norm'):
        return h + rms_norm(o, g2, loop.eps)


def _mlp_half(loop, h, w):
    """h + N4(W_down (silu(W_gate N3(h)) * W_up N3(h)))."""
    g3, wg, wu, wd, g4 = w
    with jax.named_scope('norm'):
        x = rms_norm(h, g3, loop.eps)
    with jax.named_scope('mlp'):
        f = jnp.dot(jax.nn.silu(jnp.dot(x, wg.T)) * jnp.dot(x, wu.T), wd.T)
    with jax.named_scope('norm'):
        return h + rms_norm(f, g4, loop.eps)


def _halves(layers):
    """[(half function, its weights)] of one pass, in order."""
    split = len(_ATTENTION_HALF)
    return [half for w in layers for half in (
        (_attention_half, w[:split]), (_mlp_half, w[split:]))]


def _final_norm(loop, h, gamma):
    with jax.named_scope('norm'):
        return rms_norm(h, gamma, loop.eps)


def looped_decoder(loop, x, layers, gamma):
    """x (N, hidden) through the layers `loop.loops` times with the same
    weights, the final norm after each pass, as one lax.scan whose body,
    one pass, is traced once.  `layers`: a tuple a layer of its
    _LAYER_INPUTS; `gamma`: the final norm's scale.

    Each half layer and the final norm run under jax.checkpoint, as
    `__force_mirroring__` runs the other models' half layers: the scan
    keeps for the backward only the stream where each takes it,
    (2 L + 1) x N x hidden a pass.  The weights enter the scan in
    float32 and the body casts them back to their type: jax's rule for
    a scan then sums each weight's gradient over the passes in float32,
    and the cast's own gradient rounds the sum once."""
    wide = tuple(tuple(w.astype(F32) for w in layer) for layer in layers)

    def one_pass(h, _):
        for (fn, w), (_, kind) in zip(_halves(wide), _halves(layers)):
            narrow = tuple(a.astype(k.dtype) for a, k in zip(w, kind))
            h = jax.checkpoint(functools.partial(fn, loop))(h, narrow)
        return jax.checkpoint(functools.partial(_final_norm, loop))(
            h, gamma), None

    return lax.scan(one_pass, x, None, length=loop.loops)[0]


def _decoder_input_names(attrs):
    """data, each layer's inputs as l<i>_<name>, the final norm's scale."""
    return ('data',) + tuple(
        'l%d_%s' % (i, name) for i in range(asint(attrs['num_layers']))
        for name in _LAYER_INPUTS) + ('final_norm_gamma',)


def _decoder_infer_shape(attrs, in_shapes):
    if in_shapes[0] is None or in_shapes[0][-1] == 0:
        return in_shapes
    hidden = in_shapes[0][-1]
    d = asint(attrs['head_dim'])
    q, kv = asint(attrs['num_heads']) * d, asint(attrs['num_kv_heads']) * d
    inter = asint(attrs['intermediate_size'])
    layer = [(hidden,), (q, hidden), (kv, hidden), (kv, hidden), (hidden, q),
             (hidden,), (hidden,), (inter, hidden), (inter, hidden),
             (hidden, inter), (hidden,)]
    wanted = layer * asint(attrs['num_layers']) + [(hidden,)]
    for i, s in enumerate(wanted, start=1):
        if in_shapes[i] is None:
            in_shapes[i] = s
    return in_shapes


def _decoder_infer_dtype(attrs, in_dtypes):
    """Weights follow the data; every norm scale stays float32."""
    d = _data_dtype(in_dtypes)
    names = _decoder_input_names(attrs)
    return [np.dtype(np.float32) if n.endswith('_gamma') else d
            for n in names], [d]


@register('LoopedDecoder', input_names=_decoder_input_names,
          infer_shape=_decoder_infer_shape,
          infer_dtype=_decoder_infer_dtype, hint='loopeddecoder',
          simple=False)
def _looped_decoder(attrs, inputs, auxs, op_ctx):
    """Attributes: num_layers, num_loops, num_heads, num_kv_heads,
    head_dim, intermediate_size (the weights' shapes say it), rope_theta,
    eps, seq_len.  The step program holds the layers once: one scan of
    num_loops passes forward and one backward (looped_decoder).  A
    training trace is counted (profiler.looped_decoder_stats); shape
    inference's, in float32 whatever the graph's type, is not."""
    data, weights = inputs[0], inputs[1:]
    layers = asint(attrs['num_layers'])
    heads, kv = asint(attrs['num_heads']), asint(attrs['num_kv_heads'])
    if heads % kv:
        raise ValueError('%d query heads over %d key-value heads'
                         % (heads, kv))
    loop = _Loop(asint(attrs['num_loops']), heads, kv,
                 asint(attrs['head_dim']), asint(attrs['seq_len']),
                 asfloat(attrs.get('rope_theta', 10000.0)),
                 asfloat(attrs.get('eps', 1e-6)))
    if loop.loops < 1:
        raise ValueError('%d passes over the layers' % loop.loops)
    per = len(_LAYER_INPUTS)
    stack = tuple(tuple(weights[i * per:(i + 1) * per])
                  for i in range(layers))
    if op_ctx.is_train:
        # the stream where each half layer and the final norm take it
        profiler.note_looped_decoder(
            loops=loop.loops, layers=layers, tokens=data.shape[0],
            hidden=data.shape[1], saved_bytes=loop.loops *
            (2 * layers + 1) * data.size * data.dtype.itemsize)
    return [looped_decoder(loop, data, stack, weights[-1])], []
