"""Device contexts.

Mirrors the reference's python/mxnet/context.py:1-206 (`mx.cpu()`,
`mx.gpu()`, `Context.default_ctx`), redesigned for TPU: the accelerator
context is `tpu`, and `gpu` is kept as a compatibility alias so
reference-era scripts run unchanged (BASELINE.json north star: "--gpus
swapped for a TPU context list").  A Context resolves to a concrete
`jax.Device`; computation placement is done with explicit device/sharding
arguments rather than a thread-global device stack, which is the JAX way —
`with ctx:` scoping is still provided for API parity.
"""
import threading


class Context:
    """A device context descriptor.

    Parameters
    ----------
    device_type : {'cpu', 'tpu', 'gpu', 'cpu_pinned'}
        'gpu' and 'cpu_pinned' are accepted for reference-script
        compatibility; 'gpu' resolves like 'tpu', 'cpu_pinned' like
        'cpu'.
    device_id : int
    """
    _default_ctx = threading.local()
    devtype2str = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 4: 'tpu'}
    devstr2type = {'cpu': 1, 'gpu': 2, 'cpu_pinned': 3, 'tpu': 4}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = (device_type.device_type,
                                      device_type.device_id)
        self.device_typeid = Context.devstr2type[device_type]
        self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def _key(self):
        return (self.device_typeid, self.device_id)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Context) and self._key() == other._key()

    def __str__(self):
        return '%s(%d)' % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, 'value', None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    # -- JAX resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        'cpu'/'cpu_pinned' wrap modulo the CPU device count (the
        reference's cpu(0)/cpu(1) multi-device-testing trick,
        tests/python/unittest/test_multi_device_exec.py).  'tpu'/'gpu'
        resolve only to a TPU device with that index: a host without
        one, or a device_id out of range, raises — an accelerator
        context never lands on the CPU or on another chip.
        """
        import jax
        if self.device_type in ('cpu', 'cpu_pinned'):
            devs = jax.devices('cpu')
            return devs[self.device_id % len(devs)]
        devs = _tpu_devices()
        if not 0 <= self.device_id < len(devs):
            from .base import MXNetError
            raise MXNetError(
                '%s: no such accelerator; jax.devices() holds %s'
                % (self, ['%s:%d' % (d.platform, d.id)
                          for d in jax.devices()]))
        return devs[self.device_id]


def _tpu_devices():
    import jax
    return [d for d in jax.devices() if d.platform == 'tpu']


def cpu(device_id=0):
    return Context('cpu', device_id)


def tpu(device_id=0):
    return Context('tpu', device_id)


def gpu(device_id=0):
    """Compatibility alias: accelerator context (TPU-backed)."""
    return Context('gpu', device_id)


def cpu_pinned(device_id=0):
    return Context('cpu_pinned', device_id)


def num_gpus():
    """Number of accelerator (TPU) devices visible (reference:
    mx.context.num_gpus); 0 on a CPU-only host."""
    return len(_tpu_devices())


def current_context():
    ctx = getattr(Context._default_ctx, 'value', None)
    if ctx is None:
        ctx = Context('cpu', 0)
        Context._default_ctx.value = ctx
    return ctx


Context.default_ctx = property(lambda self: current_context())
