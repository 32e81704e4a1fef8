"""Optimizers.

Reference: python/mxnet/optimizer.py (993 LoC; SURVEY.md §2.7) plus the
fused update kernels in src/operator/optimizer_op.* — here the update
math is plain NDArray (JAX) expressions, so XLA fuses each update into a
couple of kernels; the Module layer can additionally fuse ALL parameter
updates into the train step (no per-key dispatch at all).

Semantics kept: per-index update counts, lr/wd multipliers (including
__lr_mult__/__wd_mult__ symbol attrs), rescale_grad, clip_gradient, the
Updater closure that KVStore servers run (kvstore.py set_optimizer
pickles it — §2.4), and the reference's update formulas.
"""
import math
import pickle

import numpy as np

from . import base
from . import ndarray as nd
from .ndarray import NDArray, zeros


class Optimizer:
    def __init__(self, rescale_grad=1., param_idx2name=None, wd=0.,
                 clip_gradient=None, learning_rate=0.01,
                 lr_scheduler=None, sym=None, begin_num_update=0):
        self.lr, self.wd = learning_rate, wd
        self.rescale_grad, self.clip_gradient = rescale_grad, clip_gradient
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            lr_scheduler.base_lr = learning_rate
        self.begin_num_update = self.num_update = begin_num_update
        self._index_update_count = {}
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict)
        self.idx2name = dict(param_idx2name)
        self.sym = sym
        self.set_lr_mult({})
        self.set_wd_mult({})

    # -- registry ----------------------------------------------------------
    opt_registry = {}

    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError('Cannot find optimizer %s' % name)

    # -- state -------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    # -- multipliers (reference optimizer.py set_lr_mult/set_wd_mult) -----
    def _mults_from_sym(self, attr_key):
        """Per-arg multiplier overrides declared as symbol attributes
        (__lr_mult__ / __wd_mult__)."""
        if self.sym is None:
            return {}
        attrs = self.sym.attr_dict()
        return {name: float(attrs[name][attr_key])
                for name in self.sym.list_arguments()
                if attr_key in attrs.get(name, {})}

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = self._mults_from_sym('__lr_mult__')
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        # Parity contract with the reference: only *_weight / *_gamma
        # params decay by default; biases/betas/running stats are exempt.
        self.wd_mult = {name: 0.0 for name in self.idx2name.values()
                        if not name.endswith(('_weight', '_gamma'))}
        self.wd_mult.update(self._mults_from_sym('__wd_mult__'))
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index],
                              self.num_update)

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        if index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _preprocess_grad(self, grad):
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, a_min=-self.clip_gradient,
                           a_max=self.clip_gradient)
        return grad


register = Optimizer.register
create = Optimizer.create_optimizer


@register
class SGD(Optimizer):
    """SGD with momentum and fp16 multi-precision master weights
    (reference optimizer.py:334 + optimizer_op kernels)."""

    def __init__(self, momentum=0.0, multi_precision=False, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.multi_precision = multi_precision

    def _is_low_precision(self, weight):
        import jax.numpy as jnp
        return weight.dtype in (np.dtype(np.float16),
                                np.dtype(jnp.bfloat16))

    def create_state(self, index, weight):
        momentum = None
        weight_master_copy = None
        if self.multi_precision and self._is_low_precision(weight):
            weight_master_copy = weight.astype(np.float32)
            if self.momentum != 0.0:
                momentum = zeros(weight.shape, weight.context,
                                 dtype=np.float32)
            return (momentum, weight_master_copy)
        if self.momentum != 0.0:
            momentum = zeros(weight.shape, weight.context, dtype=weight.dtype)
        return momentum

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        use_mp = isinstance(state, (list, tuple))
        if use_mp:
            mom, master = state
            w = master
            g = grad.astype(np.float32)
        else:
            mom, w = state, weight
            g = grad
        g = self._preprocess_grad(g)
        g = g + wd * w
        if self.momentum == 0.0:
            w -= lr * g
        else:
            mom *= self.momentum
            mom -= lr * g
            w += mom
        if use_mp:
            weight._data = w._data.astype(weight.dtype)


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference optimizer.py NAG)."""

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad) + wd * weight
        if self.momentum == 0.0:
            weight -= lr * grad
        else:
            mom = state
            mom *= self.momentum
            mom += grad
            grad += self.momentum * mom
            weight -= lr * grad


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        noise = nd.random_normal(0, math.sqrt(lr), weight.shape)
        weight -= lr / 2 * (grad + wd * weight)
        weight += noise


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        mom, previous_weight = state
        delta = grad + wd * weight + \
            self.lamda * grad * grad * (weight - previous_weight)
        if mom is not None:
            mom *= self.momentum
            mom += -lr * delta
            d = mom
        else:
            d = -lr * delta
        previous_weight._data = weight._data
        weight += d


@register
class Adam(Optimizer):
    """Adam (reference optimizer.py:538)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        grad = self._preprocess_grad(grad) + wd * weight
        mean, var = state
        mean *= self.beta1
        mean += (1. - self.beta1) * grad
        var *= self.beta2
        var += (1. - self.beta2) * grad * grad
        weight -= lr * mean / (nd.sqrt(var) + self.epsilon)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        history = state
        history += grad * grad
        weight -= lr * (grad / nd.sqrt(history + self.float_stable_eps) +
                        wd * weight)


@register
class RMSProp(Optimizer):
    """RMSProp, centered variant optional (reference optimizer.py RMSProp)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered, self.epsilon = centered, epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.context),
                    zeros(weight.shape, weight.context),
                    zeros(weight.shape, weight.context))
        return (zeros(weight.shape, weight.context),)

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad) + wd * weight
        if self.centered:
            n, g, delta = state
            n *= self.gamma1
            n += (1 - self.gamma1) * grad * grad
            g *= self.gamma1
            g += (1 - self.gamma1) * grad
            delta *= self.gamma2
            delta -= lr * grad / nd.sqrt(n - g * g + self.epsilon)
            weight += delta
        else:
            n, = state
            n *= self.gamma1
            n += (1 - self.gamma1) * grad * grad
            weight -= lr * grad / nd.sqrt(n + self.epsilon)
        if self.clip_weights:
            weight._data = nd.clip(weight, a_min=-self.clip_weights,
                                   a_max=self.clip_weights)._data


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        acc_g, acc_delta = state
        acc_g *= self.rho
        acc_g += (1. - self.rho) * grad * grad
        current_delta = nd.sqrt(acc_delta + self.epsilon) / \
            nd.sqrt(acc_g + self.epsilon) * grad
        acc_delta *= self.rho
        acc_delta += (1. - self.rho) * current_delta * current_delta
        weight -= current_delta + wd * weight


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        z, n = state
        sigma = -nd.sqrt(n)
        n += grad * grad
        denom = nd.sqrt(n)
        sigma += denom
        sigma /= lr
        z += grad - sigma * weight
        # update weight
        d = (nd.sign(z) * self.lamda1 - z) / \
            ((self.beta + denom) / lr + wd)
        weight._data = (d * (nd.abs(z) > self.lamda1))._data


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        lr /= (1. - self.beta1 ** t)
        grad = self._preprocess_grad(grad) + wd * weight
        m_t, u_t = state
        m_t *= self.beta1
        m_t += (1. - self.beta1) * grad
        u_t._data = nd.maximum(self.beta2 * u_t, nd.abs(grad))._data
        weight -= lr * m_t / u_t


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon, self.schedule_decay = epsilon, schedule_decay
        self.m_schedule = 1.

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),
                zeros(weight.shape, weight.context))

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        t = self._index_update_count[index]
        grad = self._preprocess_grad(grad) + wd * weight
        momentum_t = self.beta1 * (1. - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1. - 0.5 * 0.96 **
                                     ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t *= self.beta1
        m_t += (1. - self.beta1) * grad
        v_t *= self.beta2
        v_t += (1. - self.beta2) * grad * grad
        grad_prime = grad / (1. - self.m_schedule)
        m_t_prime = m_t / (1. - m_schedule_next)
        v_t_prime = v_t / (1. - self.beta2 ** t)
        m_t_bar = (1. - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight -= lr * m_t_bar / (nd.sqrt(v_t_prime) + self.epsilon)


@register
class Signum(Optimizer):
    """Sign-momentum SGD (bandwidth-light; TPU-era addition)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return zeros(weight.shape, weight.context, dtype=weight.dtype)
        return None

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        grad = self._preprocess_grad(grad)
        if state is not None:
            mom = state
            mom *= self.momentum
            mom -= (1 - self.momentum) * (grad + wd * weight)
            weight += lr * (nd.sign(mom) - self.wd_lh * weight)
        else:
            weight -= lr * (nd.sign(grad) + wd * weight)


@register
class Test(Optimizer):
    """Trivially adds grad (reference optimizer.py Test)."""

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight += grad * self.rescale_grad
        state._data = weight._data


ccSGD = SGD  # deprecated alias kept for script compatibility


class Updater:
    """The serializable update closure run by KVStore servers
    (reference optimizer.py:941; pickled to servers via
    kvstore.set_optimizer — SURVEY.md §2.4)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state(index, weight)
        self.optimizer.update(index, weight, grad, self.states[index])

    def set_states(self, states):
        payload = pickle.loads(states)
        masters = None
        if isinstance(payload, tuple) and len(payload) == 3:
            states, counts, masters = payload
        elif isinstance(payload, tuple):
            states, counts = payload
        else:
            states, counts = payload, None
        self.states = {
            k: ([nd.array(x) if x is not None else None for x in v]
                if isinstance(v, (list, tuple)) else
                (nd.array(v) if v is not None else None))
            for k, v in states.items()}
        if masters:
            # fused-updater checkpoints carry the fp32 masters as a
            # third member: rebuild the per-key (momentum, master)
            # pair states, because the mp update path cannot re-derive
            # a lost master (create_state never re-runs once the index
            # has a state) — dropping it would silently promote the
            # low-precision weight to fp32 on the next update
            for k, m in masters.items():
                if m is None or isinstance(self.states.get(k), list):
                    continue
                self.states[k] = [self.states.get(k), nd.array(m)]
        if counts is not None:
            self.optimizer._index_update_count = dict(counts)

    def get_states(self):
        def conv(v):
            if isinstance(v, (list, tuple)):
                return [x.asnumpy() if isinstance(x, NDArray) else x
                        for x in v]
            return v.asnumpy() if isinstance(v, NDArray) else v
        return pickle.dumps(({k: conv(v) for k, v in self.states.items()},
                             dict(self.optimizer._index_update_count)))


def get_updater(optimizer):
    return Updater(optimizer)


def sgd_update_math(acc, g, m, lr, wd, momentum=0.0, rescale=1.0,
                    clip=None, nesterov=False):
    """The SGD/NAG elementwise update core shared by the replicated
    FusedSGD step (per-param, scalar lr/wd) and the ZeRO-1 sharded
    step (per-bucket, per-element lr/wd vectors) — ONE definition so
    the two modes cannot drift.  `g` must already be in `acc`'s dtype;
    returns (new_acc, new_momentum).

    lr/wd reach the Module's and the standalone steps as traced
    float32 scalars (elements of the schedule arrays) and are cast to
    acc's dtype, so a strong float32 scalar cannot silently promote a
    low-precision update; the ahead-of-time K=1 programs of
    gluon/fused.py and module/pipeline_fit.py still pass python floats
    (weak-typed: the multiply stays in acc's dtype)."""
    import jax.numpy as jnp
    if hasattr(lr, 'dtype') and lr.dtype != acc.dtype:
        lr = lr.astype(acc.dtype)
    if hasattr(wd, 'dtype') and wd.dtype != acc.dtype:
        wd = wd.astype(acc.dtype)
    g = g * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    g = g + wd * acc
    if momentum == 0.0:
        return acc - lr * g, m
    if nesterov:
        nm = momentum * m + g
        return acc - lr * (g + momentum * nm), nm
    nm = momentum * m - lr * g
    return acc + nm, nm


class FusedSGD:
    """Whole-model SGD step as ONE jitted XLA call.

    The reference fuses per-weight updates into CUDA kernels
    (src/operator/optimizer_op.*) but still dispatches one per key per
    step through the engine; here all parameter updates compile into a
    single XLA executable with buffer donation, so the update adds one
    device dispatch per step regardless of parameter count.

    ZeRO stage-1 (`zero=1`, parallel/zero.py): the same update math run
    on flattened-and-bucketed parameters with the momenta and fp32
    masters permanently SHARDED over the data-parallel mesh axis —
    gradients reduce-scatter, each device updates its 1/N shard, the
    updated buckets all-gather back into per-param views.  Per-device
    optimizer-state memory drops by the dp degree with the same total
    collective bytes on the wire."""

    def __init__(self, optimizer, param_names, zero=0, mesh=None,
                 interleave=None, sparse_idx=()):
        import jax
        import jax.numpy as jnp
        assert type(optimizer) in (SGD, NAG)
        self.optimizer = optimizer
        self.param_names = list(param_names)
        # positions (into param_names) updated ROWS-ONLY from COO
        # gradients (parallel/embedding.py): the fused step hands
        # gs[pos] = (unique_ids, row_grads) instead of a dense array
        self.sparse_idx = tuple(sorted(set(int(i) for i in sparse_idx)))
        if self.sparse_idx and bool(getattr(optimizer, 'multi_precision',
                                            False)):
            from .base import MXNetError
            raise MXNetError(
                'sparse_grad embedding tables do not compose with '
                'multi_precision: a row-sliced fp32 master would need '
                'its own lazy-materialization scheme — keep sparse '
                'tables fp32 (their update already touches only rows)')
        self.states = {}
        self.masters = {}     # fp32 master copies for low-precision params
        self.zero = int(zero or 0)
        self.mesh = mesh
        # static mesh fingerprint for cache_key (computed once: per-step
        # key checks must not re-stringify every device on large meshes)
        from .parallel.mesh import mesh_fingerprint
        self._mesh_fp = mesh_fingerprint(mesh)
        if self.zero and mesh is not None and \
                'data' not in mesh.axis_names:
            raise ValueError(
                "ZeRO-1 shards optimizer state over the 'data' mesh "
                'axis; mesh axes are %s' % (mesh.axis_names,))
        # ZeRO bucket state: layout + per-bucket flat shards (momenta /
        # fp32 masters), plus per-param staged values from set_states
        # waiting to be re-bucketed at the next host_prep_steps
        self._layout = None
        self._layout_inputs = None
        self._layout_names = None
        self._zero_moms = None
        self._zero_masters = None
        self._staged = None
        momentum = optimizer.momentum
        rescale = optimizer.rescale_grad
        clip = optimizer.clip_gradient
        nesterov = isinstance(optimizer, NAG)
        multi_precision = bool(getattr(optimizer, 'multi_precision',
                                       False))
        # hypers are captured BY VALUE here (the step closures bake
        # them in); cache_key must report these captured values, not
        # live optimizer attributes — the gluon Trainer mutates
        # rescale_grad per step() call, and a key that tracked the
        # mutation would relabel this object's unchanged math
        self._baked = {'momentum': float(momentum),
                       'rescale': float(rescale),
                       'clip': None if clip is None else float(clip),
                       'nesterov': nesterov}

        sparse_set = frozenset(self.sparse_idx)
        sgd_mesh = mesh

        def step(ws, gs, moms, masters, lrs, wds):
            from .parallel.embedding import sparse_row_update
            new_ws, new_moms, new_masters = [], [], []
            for j, (w, g, m, mw) in enumerate(
                    zip(ws, gs, moms, masters)):
                lr, wd = lrs[j], wds[j]
                if j in sparse_set:
                    # rows-only update from the (unique_ids, row_grads)
                    # COO pair — same sgd_update_math core on the row
                    # slices, lazy momentum/wd (parallel/embedding.py)
                    uids, d_rows = g
                    nw, nm = sparse_row_update(
                        w, m, uids, d_rows, lr, wd, momentum=momentum,
                        rescale=rescale, clip=clip, nesterov=nesterov,
                        mesh=sgd_mesh)
                    new_ws.append(nw)
                    new_moms.append(nm)
                    new_masters.append(None)
                    continue
                # with multi_precision, math runs on the fp32 master and
                # the low-precision weight is a cast of it (reference
                # mp_sgd_update, src/operator/optimizer_op-inl.h)
                acc = mw if mw is not None else w
                acc, nm = sgd_update_math(
                    acc, g.astype(acc.dtype), m, lr, wd,
                    momentum=momentum, rescale=rescale, clip=clip,
                    nesterov=nesterov)
                if mw is not None:
                    new_masters.append(acc)
                    new_ws.append(acc.astype(w.dtype))
                else:
                    new_masters.append(None)
                    new_ws.append(acc)
                new_moms.append(nm)
            return new_ws, new_moms, new_masters

        self.multi_precision = multi_precision
        if self.zero:
            from .parallel import zero as zero_mod
            from .parallel import collectives as coll
            self._zero_mod = zero_mod
            # reduction schedule is baked into the traced sharded step
            # (end-of-backward mode inserts a barrier) — resolved once
            # here (explicit API value > env) and reported by
            # cache_key so the two schedules' programs never alias
            self._interleave = coll.interleave_reduce_enabled(
                interleave)
            self._zero_hyper = {'momentum': momentum, 'rescale': rescale,
                                'clip': clip, 'nesterov': nesterov,
                                'interleave': self._interleave}
            # step_math / _jit_step are (re)bound in _host_prep_zero,
            # which captures the bucket layout BY VALUE: a step program
            # cached under one layout's key must never read a layout
            # this object later rebuilt (host_prep_steps always runs
            # before step_math is handed to the executor or traced)
            self.step_math = None
            self._jit_step = None
        else:
            self.step_math = step
            self._jit_step = jax.jit(step, donate_argnums=(0, 2, 3))

    def cache_key(self):
        """Canonical identity of step_math for the executor's
        compiled-program cache: exactly the values the step closure
        bakes in (lr/wd are runtime arguments, not part of the key).
        The ZeRO stage, bucket layout, and mesh join the key so sharded
        and replicated step programs never alias in exec_cache."""
        b = self._baked
        key = ('FusedSGD', type(self.optimizer).__name__,
               b['momentum'], b['rescale'], b['clip'],
               self.multi_precision)
        if self.sparse_idx:
            key += (('sparse', self.sparse_idx),)
        if self.zero:
            key += (('zero', self.zero,
                     self._layout.key if self._layout is not None
                     else None, self._mesh_fp, self._interleave),)
        return key

    def host_prep_steps(self, weights, k, advance=True):
        """The host side of a dispatch of k steps, shared by the
        standalone update (k = 1) and the compiled steps
        (executor.make_fused_multistep): lazily create momenta / fp32
        masters (the ZeRO bucket layout under zero=1), then bump the
        update counts and evaluate the lr/wd schedules at EVERY step
        index, exactly as the per-step loop would, so a
        FactorScheduler boundary crossed mid-dispatch decays at the
        right step.  Returns (moms, masters, lrs, wds): the states
        aligned with param_names, lrs/wds float32 arrays of shape
        (k, n_params), one row a step.

        advance=False (AOT warmup, Module.warmup_fused): states still
        materialize lazily — the warmup call must see exactly the
        buffers a real step would — but the update counts / schedule
        state are restored afterwards, so warming a ladder of bucket
        programs does not advance the lr schedule."""
        opt = self.optimizer
        saved = None if advance else self._snapshot_schedule_state()
        moms, masters = self._host_prep_zero(weights) if self.zero \
            else self._host_prep_states(weights)
        shape = (max(1, k), len(self.param_names))
        lrs, wds = np.empty(shape, np.float32), np.empty(shape, np.float32)
        for lr_row, wd_row in zip(lrs, wds):
            for j, name in enumerate(self.param_names):
                opt._update_count(name)
                lr_row[j] = opt._get_lr(name)
                wd_row[j] = opt._get_wd(name)
        if saved is not None:
            self._restore_schedule_state(saved)
        return moms, masters, lrs, wds

    def _host_prep_states(self, weights):
        """Replicated lazy state init: a momentum for every parameter
        and an fp32 master for every low-precision one."""
        import jax
        import jax.numpy as jnp
        for name, w in zip(self.param_names, weights):
            mp = self._is_mp(w)
            if name not in self.states:
                mdtype = np.float32 if mp else w.dtype
                # commit fresh state to the weight's placement: an
                # uncommitted zeros on call 1 vs a committed donated
                # output on call 2 changes the jit sharding
                # signature and forces a full recompile of the
                # fused step
                sharding = getattr(w._data, 'sharding', None)
                zeros = jnp.zeros(w.shape, dtype=mdtype)
                self.states[name] = jax.device_put(zeros, sharding) \
                    if sharding is not None else zeros
            if name not in self.masters:
                # backfill (fresh start or restored checkpoint
                # without masters): re-derive from the current
                # weight
                self.masters[name] = w._data.astype(np.float32) \
                    if mp else None
        return ([self.states[n] for n in self.param_names],
                [self.masters[n] for n in self.param_names])

    def _snapshot_schedule_state(self):
        """Everything _get_lr mutates: the update counts AND the
        stateful lr_scheduler's own attributes (FactorScheduler decays
        base_lr / bumps count inside __call__ — restoring only the
        counts would leave the schedule permanently advanced after an
        advance=False warmup)."""
        opt = self.optimizer
        sched = getattr(opt, 'lr_scheduler', None)
        return (dict(opt._index_update_count), opt.num_update,
                dict(sched.__dict__) if sched is not None else None)

    def _restore_schedule_state(self, saved):
        opt = self.optimizer
        counts, num_update, sched_state = saved
        opt._index_update_count = counts
        opt.num_update = num_update
        if sched_state is not None:
            opt.lr_scheduler.__dict__.clear()
            opt.lr_scheduler.__dict__.update(sched_state)

    def _is_mp(self, w):
        import jax.numpy as jnp
        return self.multi_precision and w.dtype in \
            (np.dtype(np.float16), jnp.bfloat16)

    def _host_prep_zero(self, weights):
        """ZeRO lazy state init: (re)build the bucket layout from the
        current parameter list and materialize the momentum / fp32
        master buckets as dp-sharded flat buffers.  Staged per-param
        values (restored checkpoints, or states carried across a
        param-list change) fold in here."""
        import jax
        import jax.numpy as jnp
        zm = self._zero_mod
        names = list(self.param_names)
        # sparse tables stay OUT of the flat buckets: their update is a
        # rows-only scatter (COO gradient), which cannot ride a
        # concatenated 1-D bucket; their momenta live as row-sharded
        # full tables in self.states and are appended after the bucket
        # shards in the moms list the step math receives
        sparse_idx = list(self.sparse_idx)
        sparse_set = set(sparse_idx)
        dense_idx = [i for i in range(len(names)) if i not in sparse_set]
        # degree = the 'data' AXIS size, not the whole device count:
        # the bucket sharding spans only that axis, and padding /
        # per-device accounting must match it on multi-axis meshes
        dp = 1 if self.mesh is None else int(self.mesh.shape['data'])
        # cheap per-step change detection; the full bucket plan is only
        # rebuilt when an input actually changed (this runs in the
        # one-dispatch-per-batch host hot path)
        inputs_key = (tuple(tuple(w.shape) for w in weights),
                      tuple(str(np.dtype(w.dtype)) for w in weights),
                      tuple(self._is_mp(w) for w in weights),
                      dp, zm.bucket_bytes(), tuple(names),
                      tuple(sparse_idx))
        if getattr(self, '_layout_inputs', None) != inputs_key:
            layout = zm.ZeroBucketLayout(
                [tuple(weights[i].shape) for i in dense_idx],
                [np.dtype(weights[i].dtype) for i in dense_idx],
                [self._is_mp(weights[i]) for i in dense_idx], dp)
            if self._zero_moms is not None:
                # param list changed under us: preserve existing state
                # by name, re-bucketed below under the new layout
                self._stage_current()
            self._layout = layout
            self._layout_inputs = inputs_key
            self._layout_names = [names[i] for i in dense_idx]
            self._zero_moms = None
            self._zero_masters = None
            # rebind the step math with the NEW layout captured by
            # value (see __init__: a cached/compiled step must never
            # observe a later layout through this object).  With sparse
            # tables the sharded bucket step runs on the dense subset
            # and the rows-only updates run beside it in the same
            # traced program.
            if not sparse_idx:
                self.step_math = zm.make_sharded_sgd_step(
                    layout, self.mesh, self._zero_hyper)
            else:
                self.step_math = self._make_zero_sparse_step(
                    layout, dense_idx, sparse_idx)
            self._jit_step = jax.jit(self.step_math,
                                     donate_argnums=(0, 2, 3))
        if self._zero_moms is None:
            staged_moms, staged_masters = self._staged or ({}, {})
            self._staged = None
            sharding = None
            if self.mesh is not None:
                from .parallel import mesh as pmesh
                sharding = pmesh.flat_sharding(self.mesh)

            def build(b, per_name, fallback):
                # gather per-param initial values, then let the layout
                # assemble the bucket (single definition of the
                # cast/pad/concat invariant — zero.py pack)
                vals = []
                for i, n in zip(b.param_idx, b.sizes):
                    v = per_name.get(self._layout_names[i])
                    vals.append(fallback(i, n) if v is None
                                else jnp.asarray(v))
                buf = self._layout.pack(b, vals)
                return jax.device_put(buf, sharding) \
                    if sharding is not None else buf

            self._zero_moms = [
                build(b, staged_moms,
                      lambda i, n, b=b: jnp.zeros((n,), b.acc_dtype))
                for b in self._layout.buckets]
            self._zero_masters = [
                build(b, staged_masters,
                      lambda i, n: weights[dense_idx[i]]._data
                      .reshape(-1).astype(np.float32))
                if b.mp else None
                for b in self._layout.buckets]
            # sparse momenta: staged values (restored checkpoint) fold
            # into self.states; lazily created below
            for i in sparse_idx:
                v = staged_moms.get(names[i])
                if v is not None:
                    self.states[names[i]] = jnp.asarray(v)
        # sparse momenta ride self.states in zero mode too: full
        # (vocab, dim) tables committed to the WEIGHT's sharding (row
        # -striped under a mesh — the "row-sharded momenta" half of
        # zero=1 composition; the rows-only update touches rung rows)
        sparse_moms = []
        for i in sparse_idx:
            n, w = names[i], weights[i]
            if n not in self.states:
                sharding = getattr(w._data, 'sharding', None)
                zeros = jnp.zeros(w.shape, dtype=w.dtype)
                self.states[n] = jax.device_put(zeros, sharding) \
                    if sharding is not None else zeros
            sparse_moms.append(self.states[n])
        return list(self._zero_moms) + sparse_moms, self._zero_masters

    def _make_zero_sparse_step(self, layout, dense_idx, sparse_idx):
        """ZeRO-1 step math with sparse tables beside the buckets, all
        captured BY VALUE (same contract as make_sharded_sgd_step).
        moms arrives as [bucket shards...] + [sparse momentum
        tables...]; returns new_ws aligned with the FULL param list and
        the moms list in the same layered order."""
        zm = self._zero_mod
        mesh = self.mesh
        hyper = dict(self._zero_hyper)
        nb = len(layout.buckets)

        def step_math(ws, gs, moms, masters, lrs, wds):
            from .parallel.embedding import sparse_row_update
            d_new, new_bmoms, new_masters = zm.sharded_sgd_step(
                layout, mesh, hyper,
                [ws[i] for i in dense_idx], [gs[i] for i in dense_idx],
                list(moms[:nb]), masters,
                [lrs[i] for i in dense_idx], [wds[i] for i in dense_idx])
            new_ws = list(ws)
            for k, i in enumerate(dense_idx):
                new_ws[i] = d_new[k]
            new_smoms = []
            for k, i in enumerate(sparse_idx):
                uids, d_rows = gs[i]
                nw, nm = sparse_row_update(
                    ws[i], moms[nb + k], uids, d_rows, lrs[i], wds[i],
                    momentum=hyper['momentum'], rescale=hyper['rescale'],
                    clip=hyper['clip'], nesterov=hyper['nesterov'],
                    mesh=mesh)
                new_ws[i] = nw
                new_smoms.append(nm)
            return new_ws, list(new_bmoms) + new_smoms, new_masters

        return step_math

    def _stage_current(self):
        """Unpack the current ZeRO buckets into per-param staged values
        (keyed by name) so a layout rebuild re-buckets them.  Each
        sharded bucket is fetched to host ONCE and sliced there — not
        one cross-device gather per parameter."""
        moms, masters = {}, {}
        for b, mom, mas in zip(self._layout.buckets, self._zero_moms,
                               self._zero_masters):
            for i, seg in zip(b.param_idx,
                              self._layout.unpack(b, np.asarray(mom))):
                moms[self._layout_names[i]] = seg
            if b.mp and mas is not None:
                for i, seg in zip(b.param_idx,
                                  self._layout.unpack(
                                      b, np.asarray(mas))):
                    masters[self._layout_names[i]] = seg
        self._staged = (moms, masters)

    def state_bytes_per_device(self):
        """Bytes of optimizer state (momenta + fp32 masters) resident
        on EACH device — the ZeRO-1 memory metric (profiler/bench).
        Replicated mode holds the full state everywhere; ZeRO mode
        holds the 1/dp bucket shards."""
        if self.zero:
            total = self._layout.state_bytes_per_device() \
                if self._layout is not None else 0
            # sparse momentum tables: row-striped under a mesh, so each
            # device holds ~1/dp of the rows
            dp = 1 if self.mesh is None else int(self.mesh.shape['data'])
            for i in self.sparse_idx:
                v = self.states.get(self.param_names[i])
                if v is not None:
                    total += -(-int(v.size) *
                               np.dtype(v.dtype).itemsize // dp)
            return total
        total = 0
        for n in self.param_names:
            v = self.states.get(n)
            if v is not None:
                total += int(v.size) * np.dtype(v.dtype).itemsize
            m = self.masters.get(n)
            if m is not None:
                total += int(m.size) * 4
        return total

    def comm_bytes_per_step(self):
        """Logical (bytes_reduce_scattered, bytes_all_gathered) one
        training step moves for the sharded update; (0, 0) in
        replicated mode or when no mesh is active."""
        if self.zero and self._layout is not None:
            return self._layout.comm_bytes_per_step()
        return 0, 0

    def commit(self, new_moms, new_masters):
        """Write back optimizer state returned by a step execution.
        In ZeRO mode the lists are per-bucket dp-sharded buffers,
        with sparse momentum tables appended after the buckets."""
        if self.zero:
            nb = len(self._layout.buckets) if self._layout is not None \
                else len(new_moms) - len(self.sparse_idx)
            self._zero_moms = list(new_moms[:nb])
            self._zero_masters = list(new_masters)
            for k, i in enumerate(self.sparse_idx):
                self.states[self.param_names[i]] = new_moms[nb + k]
            return
        for n, nm, nmw in zip(self.param_names, new_moms, new_masters):
            self.states[n] = nm
            self.masters[n] = nmw

    def __call__(self, weights, grads):
        """weights/grads: lists of NDArray aligned with param_names.
        Updates weights in place (rebinding device buffers)."""
        if self.sparse_idx:
            from .base import MXNetError
            raise MXNetError(
                'a sparse-table FusedSGD only runs inside the fused '
                'train step (its sparse gradients are COO pairs the '
                'step constructs in-trace, not standalone arrays)')
        moms, masters, lrs, wds = self.host_prep_steps(weights, 1)
        ws = [w._data for w in weights]
        gs = [g._data for g in grads]
        new_ws, new_moms, new_masters = self._jit_step(
            ws, gs, moms, masters, lrs[0], wds[0])
        for w, nw in zip(weights, new_ws):
            w._data = nw
        self.commit(new_moms, new_masters)

    def transfer_states_from(self, other):
        """Adopt another FusedSGD's optimizer state (same param_names):
        the gluon fused path rebuilds its updater when rescale_grad
        changes (the step closure bakes it in), and the momenta / fp32
        masters must survive.  Replicated->replicated transfers share
        the device buffers by reference (no host round-trip — the old
        updater is discarded, so nothing else aliases them); ZeRO
        sources/targets go through the mode-portable checkpoint
        format."""
        if not self.zero and not other.zero:
            self.states = dict(other.states)
            self.masters = dict(other.masters)
            if other.optimizer is not self.optimizer:
                self.optimizer._index_update_count = \
                    dict(other.optimizer._index_update_count)
            return
        self.set_states(other.get_states())

    @staticmethod
    def _split_updater_states(states, masters):
        """Normalize checkpoint state values into (momenta, masters)
        dicts: the per-key Updater stores None for momentum-free SGD
        and [momentum, fp32_master] pairs for multi-precision params,
        while FusedSGD checkpoints carry momenta and masters
        separately.  Missing entries re-materialize lazily in
        host_prep_steps (zeros momenta / masters re-derived from
        weights) — the same backfill a fresh start uses."""
        moms = {}
        out_masters = {n: v for n, v in (masters or {}).items()
                       if v is not None}
        for n, v in states.items():
            if isinstance(v, (list, tuple)):
                if len(v) > 0 and v[0] is not None:
                    moms[n] = v[0]
                if len(v) > 1 and v[1] is not None:
                    out_masters.setdefault(n, v[1])
            elif v is not None:
                moms[n] = v
        return moms, out_masters

    # checkpoint compatibility with Updater.get_states/set_states
    def get_states(self):
        """Checkpoint format is MODE-INDEPENDENT: ZeRO buckets are
        unpacked back to per-param arrays (gathering the shards), so a
        sharded run's checkpoint restores into a replicated run and
        vice versa — same portability contract as the reference's
        server-side states."""
        if self.zero and self._staged is not None:
            # restored states not yet re-bucketed (no step ran since
            # set_states): round-trip the staged per-param values —
            # falling through to the (empty) legacy dicts here would
            # silently reset all momenta in the written checkpoint
            staged_moms, staged_masters = self._staged
            return pickle.dumps(
                ({n: np.asarray(v) for n, v in staged_moms.items()},
                 dict(self.optimizer._index_update_count),
                 {n: np.asarray(v) for n, v in staged_masters.items()}))
        if self.zero and self._layout is not None and \
                self._zero_moms is not None:
            names = self._layout_names
            states, masters = {}, {}
            # one host fetch per BUCKET (gathers the dp shards), then
            # slice on host — not one device round-trip per parameter
            for b, mom, mas in zip(self._layout.buckets,
                                   self._zero_moms,
                                   self._zero_masters):
                for i, seg in zip(b.param_idx,
                                  self._layout.unpack(
                                      b, np.asarray(mom))):
                    states[names[i]] = seg
                for i in b.param_idx:
                    masters[names[i]] = None
                if b.mp and mas is not None:
                    for i, seg in zip(b.param_idx,
                                      self._layout.unpack(
                                          b, np.asarray(mas))):
                        masters[names[i]] = seg
            # sparse momentum tables live beside the buckets in
            # self.states — without this merge a zero=1 sparse run's
            # checkpoint would silently reset every table's momentum
            for i in self.sparse_idx:
                n = self.param_names[i]
                v = self.states.get(n)
                if v is not None:
                    states[n] = np.asarray(v)
                    masters.setdefault(n, None)
            return pickle.dumps(
                (states, dict(self.optimizer._index_update_count),
                 masters))
        states = {n: np.asarray(v) for n, v in self.states.items()}
        masters = {n: (np.asarray(v) if v is not None else None)
                   for n, v in self.masters.items()}
        return pickle.dumps((states,
                             dict(self.optimizer._index_update_count),
                             masters))

    def set_states(self, states):
        payload = pickle.loads(states)
        masters = None
        if isinstance(payload, tuple) and len(payload) == 3:
            states, counts, masters = payload
        elif isinstance(payload, tuple):
            states, counts = payload
        else:
            states, counts = payload, None
        # normalize: per-key Updater checkpoints carry None (no
        # momentum) and [mom, master] pair values — a fused updater
        # must restore from those too (Trainer.load_states feeds both
        # formats to both paths)
        moms, masters = self._split_updater_states(states, masters)
        if self.zero:
            # stage per-param values; the next host_prep_steps
            # re-buckets them into dp-sharded flat buffers (the layout,
            # if already built, stays valid — only the state buffers
            # rebuild)
            self._staged = (moms, masters)
            self._zero_moms = None
            self._zero_masters = None
        else:
            import jax.numpy as jnp
            self.states = {n: jnp.asarray(v) for n, v in moms.items()}
            # fp32 masters ride along with the momentum states;
            # checkpoints without them re-derive masters from the
            # weights at the next host_prep_steps (backfills missing
            # keys)
            self.masters = {n: jnp.asarray(v)
                            for n, v in masters.items()}
        if counts is not None:
            self.optimizer._index_update_count = dict(counts)


def create_fused_updater(optimizer, param_names, zero=0, mesh=None,
                         interleave=None, sparse_idx=()):
    """Return a fused whole-model updater when the optimizer supports it,
    else None (caller falls back to the per-key Updater).  FusedSGD
    handles multi_precision natively (fp32 masters inside the jitted
    step, reference mp_sgd_update).  zero=1 selects the ZeRO stage-1
    sharded update over `mesh`'s data axis (parallel/zero.py);
    interleave overrides the gradient-reduction schedule the sharded
    step bakes in (None = MXNET_TPU_INTERLEAVE_REDUCE).  sparse_idx
    marks the positions whose gradients arrive as (unique_ids,
    row_grads) COO pairs for the rows-only update
    (parallel/embedding.py).  Sparse tables need the fused SGD/NAG
    path: with a non-SGD optimizer this returns None and the caller's
    fallback would feed dense grads to a per-key Updater, so callers
    with sparse params must treat None as an error."""
    if type(optimizer) in (SGD, NAG):
        return FusedSGD(optimizer, param_names, zero=zero, mesh=mesh,
                        interleave=interleave, sparse_idx=sparse_idx)
    return None
