"""SPMD transformer LM: the reference end-to-end for dp × tp × sp.

No counterpart in the reference (MXNet 0.11 predates attention;
SURVEY.md §5.7) — this is the §7-step-9 new-design extension that
exercises every mesh axis the framework supports in one training step:

  * data parallelism   — batch sharded on the 'data' axis
  * tensor parallelism — Megatron-style: attention heads + MLP hidden
    sharded on 'model'; row-parallel matmuls psum over 'model'
  * sequence parallel  — tokens sharded on 'sp'; ring attention rotates
    K/V shards over ICI (ring_attention.py)

The whole step (fwd + bwd + SGD update) is one shard_map-under-jit
program: XLA sees the collectives explicitly and overlaps the ring
ppermutes with block attention compute.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import (ring_attention, ring_self_attention,
                             full_attention)


def attention(q, k, v, causal=False, scale=None, impl='auto',
              seq_axis='sp', use_flash=False):
    """Attention dispatch for the FUSED (GSPMD plain-jit) path: pick
    the ring-attention sequence-parallel implementation when the
    ACTIVE mesh (parallel.mesh.current_mesh — set by the fused trace
    paths via mesh.use_mesh) has a `seq_axis` dimension the sequence
    divides over, else single-device full_attention.

    q, k, v: GLOBAL [B, H, T, D] arrays (self-attention shapes — the
    ring path has no cross-attention form).  impl: 'auto' (ring when
    the active mesh can carry it), 'ring' (require it — raise when the
    mesh can't), 'full' (force the dense path).  The ring path wraps
    ring_self_attention's shard_map over the active mesh, so it nests
    inside an outer jit exactly like the fused step's other mesh-aware
    layers (gluon.nn.MoE) — XLA sees the K/V ppermute ring explicitly
    and overlaps it with the block attention compute; numerics match
    full_attention to ulp-level (the online-softmax merge is exact).
    """
    if impl not in ('auto', 'ring', 'full'):
        raise ValueError("attention impl must be 'auto', 'ring' or "
                         "'full', got %r" % (impl,))
    from .mesh import current_mesh
    mesh = current_mesh()
    n = 0
    if mesh is not None and seq_axis in mesh.axis_names:
        n = int(mesh.shape[seq_axis])
    can_ring = (n > 1 and q.ndim == 4 and q.shape == k.shape
                and k.shape == v.shape and q.shape[-2] % n == 0)
    if impl == 'ring' and not can_ring:
        raise ValueError(
            "attention(impl='ring'): needs an active mesh with a "
            "'%s' axis > 1 dividing T, and identical 4-D q/k/v; got "
            "mesh=%r q=%s k=%s v=%s"
            % (seq_axis, None if mesh is None else dict(mesh.shape),
               q.shape, k.shape, v.shape))
    if impl == 'full' or not can_ring:
        return full_attention(q, k, v, causal=causal, scale=scale,
                              use_flash=use_flash)
    return ring_self_attention(q, k, v, mesh, seq_axis=seq_axis,
                               causal=causal, scale=scale,
                               use_flash=use_flash)


def lm_config(vocab=64, dim=32, heads=4, layers=2, mlp_mult=4,
              use_flash=False):
    """use_flash routes the sp ring attention through the Pallas
    kernels (flash-merge hops; see ring_attention) — the long-context
    setting.  Default off: tiny shapes (tests, dryruns) are faster and
    simpler on the XLA path."""
    return dict(vocab=vocab, dim=dim, heads=heads, layers=layers,
                mlp_mult=mlp_mult, head_dim=dim // heads,
                use_flash=use_flash)


def init_params(cfg, key, dtype=jnp.float32):
    """Parameter pytree.  Shapes are global; shardings in param_specs."""
    k = jax.random.split(key, 2 + 6 * cfg['layers'])
    D, V, H = cfg['dim'], cfg['vocab'], cfg['mlp_mult'] * cfg['dim']
    s = 0.02
    params = {
        'embed': jax.random.normal(k[0], (V, D), dtype) * s,
        'ln_f': jnp.ones((D,), dtype),
        'layers': [],
    }
    for i in range(cfg['layers']):
        kk = k[2 + 6 * i: 8 + 6 * i]
        params['layers'].append({
            'ln1': jnp.ones((D,), dtype),
            'wqkv': jax.random.normal(kk[0], (D, 3 * D), dtype) * s,
            'wo': jax.random.normal(kk[1], (D, D), dtype) * s,
            'ln2': jnp.ones((D,), dtype),
            'w1': jax.random.normal(kk[2], (D, H), dtype) * s,
            'w2': jax.random.normal(kk[3], (H, D), dtype) * s,
        })
    return params


def param_specs(cfg):
    """Megatron-style tensor-parallel shardings over 'model'."""
    layer = {
        'ln1': P(), 'wqkv': P(None, 'model'), 'wo': P('model', None),
        'ln2': P(), 'w1': P(None, 'model'), 'w2': P('model', None),
    }
    return {'embed': P(), 'ln_f': P(),
            'layers': [dict(layer) for _ in range(cfg['layers'])]}


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + 1e-6) * scale


def _local_forward(cfg, params, tokens):
    """Per-shard forward.  tokens: [B_local, T_local] int32.
    'model'-sharded weights arrive as local shards; row-parallel matmuls
    finish with psum over 'model'."""
    x = params['embed'][tokens]                      # [B, T, D] replicated D
    n_model = lax.psum(1, 'model')
    heads_local = cfg['heads'] // n_model
    dh = cfg['head_dim']
    for lp in params['layers']:
        h = _rmsnorm(x, lp['ln1'])
        qkv = jnp.einsum('btd,df->btf', h, lp['wqkv'])   # f = 3*D/n_model
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def split_heads(t):
            b, tt, _ = t.shape
            return t.reshape(b, tt, heads_local, dh).transpose(0, 2, 1, 3)
        q, k, v = split_heads(q), split_heads(k), split_heads(v)
        att = ring_attention(q, k, v, 'sp', causal=True,
                             use_flash=cfg.get('use_flash', False))
        att = att.transpose(0, 2, 1, 3).reshape(
            x.shape[0], x.shape[1], heads_local * dh)
        o = jnp.einsum('btf,fd->btd', att, lp['wo'])
        o = lax.psum(o, 'model')                          # row-parallel
        x = x + o
        h = _rmsnorm(x, lp['ln2'])
        y = jnp.einsum('btd,dh->bth', h, lp['w1'])
        y = jax.nn.gelu(y)
        y = jnp.einsum('bth,hd->btd', y, lp['w2'])
        y = lax.psum(y, 'model')                          # row-parallel
        x = x + y
    x = _rmsnorm(x, params['ln_f'])
    logits = jnp.einsum('btd,vd->btv', x, params['embed'])
    return logits


def _local_loss(cfg, params, tokens, targets):
    logits = _local_forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    # mean over the GLOBAL batch*seq (tokens are sharded on data & sp)
    local_sum = nll.sum()
    total = lax.psum(local_sum, ('data', 'sp'))
    count = lax.psum(jnp.asarray(nll.size, jnp.float32), ('data', 'sp'))
    return total / count


def make_train_step(cfg, mesh, lr=0.1):
    """Compile the full train step: fwd + bwd + SGD, sharded dp×tp×sp."""
    pspecs = param_specs(cfg)
    tok_spec = P('data', 'sp')

    all_axes = mesh.axis_names

    def _sync_grad(g, spec):
        """All-reduce a per-shard grad over every mesh axis the param is
        NOT sharded on (the KVStore/ps-lite role, as one XLA psum)."""
        used = set()
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                used.update(entry)
            else:
                used.add(entry)
        axes = tuple(ax for ax in all_axes if ax not in used)
        return lax.psum(g, axes) if axes else g

    def step(params, tokens, targets):
        def loss_fn(p):
            return _local_loss(cfg, p, tokens, targets)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        g_flat, g_def = jax.tree_util.tree_flatten(grads)
        s_flat = jax.tree_util.tree_flatten(
            pspecs, is_leaf=lambda x: isinstance(x, P))[0]
        g_flat = [_sync_grad(g, s) for g, s in zip(g_flat, s_flat)]
        grads = jax.tree_util.tree_unflatten(g_def, g_flat)
        new_params = jax.tree_util.tree_map(
            lambda w, g: w - lr * g, params, grads)
        return loss, new_params

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, tok_spec, tok_spec),
        out_specs=(P(), pspecs),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


def place_params(params, cfg, mesh):
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
