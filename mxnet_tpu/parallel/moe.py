"""Expert parallelism: switch-routed mixture-of-experts over a mesh axis.

No counterpart in the reference (SURVEY.md §2.4 item 5 lists expert
parallelism as absent) — §7-step-9 new-design extension.  Experts live
sharded on the 'expert' mesh axis; tokens are top-1 routed (Switch
Transformer style), dispatched to their expert's device with ONE
`lax.all_to_all` over ICI, transformed, and combined back with a second
all_to_all — the canonical TPU MoE data path.  Capacity is static
(XLA-friendly): each device sends at most `capacity` tokens to each
expert; overflow tokens are dropped (standard switch behavior) and pass
through via the residual connection in the caller.
"""
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def capacity_for(num_tokens, num_experts, capacity_factor=1.0):
    """Static per-expert token capacity from a capacity factor
    (Switch Transformer eq. 3): ceil(cf * T / E), at least 1.  Static
    so the dispatch shapes — and therefore the XLA program — do not
    depend on the routing."""
    import math
    return max(1, int(math.ceil(
        int(num_tokens) * float(capacity_factor) / int(num_experts))))


def switch_route(x, router_w, num_experts, capacity, with_counts=False):
    """Top-1 routing with per-expert capacity.

    x (T, D) local tokens -> (dispatch (E, C, D), combine (T, E, C),
    aux_loss scalar).  dispatch holds the tokens bucketed per expert;
    combine scatters expert outputs back to token positions weighted by
    the router gate.  with_counts=True appends (routed (E,),
    dropped (E,)) int32 per-expert token counts — capacity overflow is
    otherwise SILENT (dropped tokens ride the caller's residual), so
    these feed the profiler's moe_* counter family.
    """
    T, D = x.shape
    logits = x @ router_w                        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)               # (T,)
    expert = jnp.argmax(probs, axis=-1)          # (T,)

    # position of each token within its expert's bucket
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot  # 1-based
    pos = jnp.sum(pos_in_expert, axis=-1) - 1            # (T,)
    keep = pos < capacity

    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    density = jnp.mean(onehot.astype(x.dtype), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * num_experts

    disp = jnp.zeros((num_experts, capacity, D), x.dtype)
    idxs = (expert, jnp.clip(pos, 0, capacity - 1))
    disp = disp.at[idxs[0], idxs[1]].add(
        jnp.where(keep[:, None], x, 0.0))

    combine = jnp.zeros((T, num_experts, capacity), x.dtype)
    combine = combine.at[jnp.arange(T), expert,
                         jnp.clip(pos, 0, capacity - 1)].set(
        jnp.where(keep, gate, 0.0))
    if with_counts:
        assigned = jnp.sum(onehot, axis=0)                    # (E,)
        routed = jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                         axis=0)
        return disp, combine, aux, (routed, assigned - routed)
    return disp, combine, aux


def moe_ffn(x, params, num_experts_total, capacity, axis_name='expert'):
    """Run inside shard_map: switch-MoE feed-forward.

    x (T, D): this device's tokens.
    params: {'router': (D, E_total), 'w1': (E_local, D, H),
             'w2': (E_local, H, D)} — expert weights sharded on the
             expert axis (leading dim = experts on THIS device).
    Returns (y (T, D), aux_loss).
    """
    n_dev = num_experts_total // params['w1'].shape[0]
    e_local = params['w1'].shape[0]
    disp, combine, aux = switch_route(x, params['router'],
                                      num_experts_total, capacity)
    # dispatch: (E_total, C, D) -> exchange so each device holds its
    # local experts' buckets from ALL devices: (n_dev * E_local, C, D)
    # all_to_all splits axis 0 across devices and concatenates the
    # received blocks -> (E_local * n_dev, C, D) token-major per source
    disp = disp.reshape(n_dev, e_local, capacity, -1)
    recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)            # (n_dev, e_local, C, D)
    buckets = recv.transpose(1, 0, 2, 3).reshape(
        e_local, n_dev * capacity, -1)            # per local expert

    # expert computation: two MXU matmuls per expert
    h = jnp.einsum('ecd,edh->ech', buckets, params['w1'])
    h = jax.nn.relu(h)
    y = jnp.einsum('ech,ehd->ecd', h, params['w2'])

    # send results back: inverse exchange
    y = y.reshape(e_local, n_dev, capacity, -1).transpose(1, 0, 2, 3)
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)            # (n_dev, e_local, C, D)
    back = back.reshape(num_experts_total, capacity, -1)

    out = jnp.einsum('tec,ecd->td', combine, back)
    return out, aux


def init_moe_params(key, dim, hidden, num_experts, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s = 0.02
    return {
        'router': jax.random.normal(k1, (dim, num_experts), dtype) * s,
        'w1': jax.random.normal(k2, (num_experts, dim, hidden),
                                dtype) * s,
        'w2': jax.random.normal(k3, (num_experts, hidden, dim),
                                dtype) * s,
    }


def moe_param_specs(axis_name='expert'):
    return {'router': P(), 'w1': P(axis_name), 'w2': P(axis_name)}


def make_moe_train_step(mesh, dim, hidden, num_experts, capacity,
                        axis_name='expert', lr=0.1, aux_weight=0.01):
    """Compile a toy MoE regression step exercising the full expert-
    parallel data path (router -> all_to_all -> experts -> all_to_all)."""
    specs = moe_param_specs(axis_name)

    def step(params, x, y):
        def loss_fn(p):
            out, aux = moe_ffn(x, p, num_experts, capacity, axis_name)
            return jnp.mean((out - y) ** 2) + aux_weight * aux
        loss, grads = jax.value_and_grad(loss_fn)(params)
        n_dev = lax.psum(1, axis_name)
        # uniform gradient scale: everything is d(mean over devices of
        # local loss)/dθ.  Router is replicated -> pmean its per-device
        # grads; expert grads already sum every device's contribution
        # (per-device cotangent seeds of 1 through the all_to_all
        # transposes), so divide by n_dev to match the mean loss.
        grads = {
            'router': lax.pmean(grads['router'], axis_name),
            'w1': grads['w1'] / n_dev,
            'w2': grads['w2'] / n_dev,
        }
        loss = lax.pmean(loss, axis_name)
        new = jax.tree_util.tree_map(lambda w, g: w - lr * g, params,
                                     grads)
        return loss, new

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(specs, P(axis_name), P(axis_name)),
        out_specs=(P(), specs),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))
