"""Ring attention: sequence/context parallelism over the mesh.

No reference counterpart — MXNet 0.11 has no attention or sequence
parallelism at all (SURVEY.md §5.7); this is the new-design extension
called for by §7 step 9.  The sequence axis is sharded over a mesh axis;
keys/values rotate around the ring via lax.ppermute while each device
accumulates its queries' attention online (flash-attention style
running max / denominator), so peak memory is O(T_local²) and the
K/V transfers ride ICI concurrently with compute.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, scale, q_pos, k_pos, causal, m, l, o):
    """One block's contribution with online-softmax accumulation."""
    s = jnp.einsum('...qd,...kd->...qk', q, k) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (m_new == -inf)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    if causal:
        p = jnp.where(q_pos[:, None] >= k_pos[None, :], p, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    corr = jnp.where(jnp.isfinite(m), corr, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum('...qk,...kd->...qd', p, v)
    return m_new, l_new, o_new


def _flash_hop(q, k_blk, v_blk, scale, my_idx, k_idx, causal, interpret):
    """One ring hop through the Pallas flash kernel (the differentiable
    with-lse entry point, so jax.grad flows through the whole ring):
    returns this block's NORMALIZED partial output and its per-row
    logsumexp, with the hop's causal relationship (past / diagonal /
    future) selected by lax.switch so only one kernel runs."""
    from .. import pallas_ops

    b_h_t_d = q.shape  # (B, H, T_local, D)

    def past(_):
        out, lse = pallas_ops.flash_attention_with_lse(
            q, k_blk, v_blk, causal=False, scale=scale,
            interpret=interpret)
        return out.astype(jnp.float32), lse

    def diag(_):
        out, lse = pallas_ops.flash_attention_with_lse(
            q, k_blk, v_blk, causal=True, scale=scale,
            interpret=interpret)
        return out.astype(jnp.float32), lse

    def future(_):
        bh = b_h_t_d[0] * b_h_t_d[1]
        return (jnp.zeros(b_h_t_d, jnp.float32),
                jnp.full((bh, b_h_t_d[2], 1), -jnp.inf, jnp.float32))

    if not causal:
        return past(None)
    case = jnp.clip(k_idx - my_idx + 1, 0, 2)  # 0 past, 1 diag, 2 future
    return lax.switch(case, [past, diag, future], None)


def _ring_attention_flash(q, k, v, axis_name, causal, scale, interpret):
    """Flash-kernel ring: each hop's local attention runs through the
    Pallas kernel (O(block) VMEM, no T_local^2 scores); hops combine in
    flash style — unnormalized output accumulator + running max +
    running weight sum over the per-hop logsumexps."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    perm = [(j, (j - 1) % n) for j in range(n)]

    def body(carry, _):
        k_blk, v_blk, k_idx, o_u, m, l = carry
        o_new, lse_new = _flash_hop(q, k_blk, v_blk, scale, idx, k_idx,
                                    causal, interpret)
        lse_new = lse_new.reshape(b, h, t_local, 1)
        m2 = jnp.maximum(m, lse_new)
        safe_m2 = jnp.where(jnp.isfinite(m2), m2, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m2), 0.0)
        w = jnp.where(jnp.isfinite(lse_new),
                      jnp.exp(lse_new - safe_m2), 0.0)
        o_u = o_u * corr + o_new * w
        l = l * corr + w
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        k_idx = lax.ppermute(k_idx, axis_name, perm)
        return (k_blk, v_blk, k_idx, o_u, m2, l), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((b, h, t_local, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_local, 1), jnp.float32)
    o0, m0, l0 = (lax.pcast(t, (axis_name,), to='varying')
                  for t in (o0, m0, l0))
    (_, _, _, o_u, _, l), _ = lax.scan(body, (k, v, idx, o0, m0, l0),
                                       None, length=n)
    return (o_u / jnp.maximum(l, 1e-37)).astype(q.dtype)


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   use_flash=False):
    """Attention over a sequence sharded on `axis_name`.

    Call inside shard_map/pjit-sharded code.  q,k,v: [..., T_local, D]
    local shards; returns the local output shard [..., T_local, D].

    use_flash=True routes each hop's local attention through the Pallas
    streaming kernel (4-D [B, H, T_local, D] shards only): peak memory
    drops from O(T_local^2) scores to O(block * T_local), which is what
    makes long per-shard sequences viable.  Hops combine by the
    associative logsumexp merge, so numerics match the XLA path.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if use_flash:
        assert q.ndim == 4, 'use_flash needs [B, H, T_local, D] shards'
        from .. import pallas_ops
        interpret = pallas_ops.default_interpret(q, k, v)
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     interpret)
    q_pos = idx * t_local + jnp.arange(t_local)
    perm = [(j, (j - 1) % n) for j in range(n)]  # send to previous; recv from next

    def body(carry, _):
        k_blk, v_blk, k_idx, m, l, o = carry
        k_pos = k_idx * t_local + jnp.arange(t_local)
        m, l, o = _block_attn(q, k_blk, v_blk, scale, q_pos, k_pos,
                              causal, m, l, o)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        k_idx = lax.ppermute(k_idx, axis_name, perm)
        return (k_blk, v_blk, k_idx, m, l, o), None

    m0 = jnp.full(q.shape[:-1], -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], dtype=jnp.float32)
    o0 = jnp.zeros(q.shape, dtype=jnp.float32)
    # mark accumulators as varying over the ring axis so scan carry
    # types line up under JAX's manual-axes checking
    m0, l0, o0 = (lax.pcast(t, (axis_name,), to='varying')
                  for t in (m0, l0, o0))
    (k, v, _, m, l, o), _ = lax.scan(
        body, (k, v, idx, m0, l0, o0), None, length=n)
    out = o / jnp.maximum(l, 1e-37)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh, seq_axis='sp', causal=False,
                        scale=None, use_flash=False):
    """Wrapper: full [B, H, T, D] arrays, T sharded over `seq_axis`.
    use_flash routes each hop through the Pallas kernel (Pallas calls
    carry no vma metadata, so the flash path disables shard_map's vma
    checking for this call)."""
    spec = P(None, None, seq_axis, None)
    kwargs = {'check_vma': False} if use_flash else {}
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis,
                          causal=causal, scale=scale,
                          use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **kwargs)
    return fn(q, k, v)


def full_attention(q, k, v, causal=False, scale=None, use_flash=False):
    """Single-device attention; q_len may differ from kv_len
    (cross-attention / KV-cache decode — causal rows suffix-align to
    the keys).  use_flash=True routes (B, H, Tq, D) inputs through the
    streaming Pallas kernel (pallas_ops.py) — same numerics, no T^2
    HBM scores, ~2x faster at long causal T."""
    if use_flash and q.ndim == 4 and k.shape == v.shape and \
            q.shape[:2] == k.shape[:2] and q.shape[-1] == k.shape[-1] \
            and (not causal or q.shape[2] <= k.shape[2]):
        from .. import pallas_ops
        return pallas_ops.flash_attention(q, k, v, causal=causal,
                                          scale=scale)
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if causal and q.shape[-2] > k.shape[-2]:
        raise ValueError(
            'full_attention: causal masking needs q_len <= kv_len '
            '(suffix alignment — the leading rows would see no keys); '
            'got q_len=%d kv_len=%d' % (q.shape[-2], k.shape[-2]))
    s = jnp.einsum('...qd,...kd->...qk', q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        # suffix alignment: query row i attends keys <= tk - tq + i
        # (equals the plain lower triangle when tq == tk)
        mask = (tk - tq) + jnp.arange(tq)[:, None] >= \
            jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('...qk,...kd->...qd', p, v)
