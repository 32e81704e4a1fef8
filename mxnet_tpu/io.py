"""Data iterators.

Reference: python/mxnet/io.py (908 LoC) + the C++ iterator framework
(include/mxnet/io.h:42, SURVEY.md §2.5).  The layered-decorator design
(batch loader → augmenter → prefetcher) is kept: NDArrayIter handles
in-memory data, PrefetchingIter adds a background thread so host-side
batch prep overlaps device compute (the reference's iter_prefetcher.h
role; with JAX async dispatch the overlap comes naturally), and
prefetch_to_device stages upcoming batches *device-resident* so the
host→device copy of batch N+1 overlaps the device compute of batch N.
"""
import threading
import time
from collections import deque, namedtuple, OrderedDict
from itertools import chain

import jax
import numpy as np

from . import profiler
from .context import current_context
from .ndarray import NDArray

DataDesc = namedtuple('DataDesc', ['name', 'shape', 'dtype', 'layout'])
DataDesc.__new__.__defaults__ = (np.float32, 'NCHW')


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data, self.label = data, label
        self.pad, self.index = pad, index
        self.bucket_key = bucket_key
        self.provide_data, self.provide_label = provide_data, provide_label


def _batch_field(field):
    """Getter for one field of the staged batch (get<field>())."""
    def getter(self):
        return getattr(self.current_batch, field)
    getter.__name__ = 'get' + field
    return getter


class _StagedBatchMixin:
    """Iterators that stage whole DataBatches expose the batch's fields."""
    getdata = _batch_field('data')
    getlabel = _batch_field('label')
    getindex = _batch_field('index')
    getpad = _batch_field('pad')


class DataIter:
    """Base iterator (reference io.py:174)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


# XLA's CPU runtime takes a host buffer as it is, without a copy, when
# its first byte lies on this boundary; any other buffer it copies
_HOST_ALIGN = 64


def _aligned_empty(shape, dtype):
    """An uninitialised C-contiguous array that starts on a _HOST_ALIGN
    boundary (numpy's own large allocations start 16 bytes past one)."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    raw = np.empty(nbytes + _HOST_ALIGN, np.uint8)
    start = -raw.ctypes.data % _HOST_ALIGN
    return raw[start:start + nbytes].view(dtype).reshape(shape)


def _served_dtype(dtype):
    """The dtype a source's batches are served in: float64 as float32,
    int64 as int32 (what nd.array makes of them), any other as it is."""
    if dtype == np.float64:
        return np.dtype(np.float32)
    return jax.dtypes.canonicalize_dtype(dtype)


def _init_data(data, allow_empty, default_name):
    """Normalize input data to list of (name, numpy array)
    (reference io.py _init_data).  As in the reference the arrays are
    copied: the iterator's rows are its own, in the dtype they are served
    in, aligned and read-only, so a batch can be a view of them that
    nothing the user writes afterwards reaches."""
    assert (data is not None) or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = OrderedDict([(default_name, data[0])])
        else:
            data = OrderedDict(
                [('_%d_%s' % (i, default_name), d)
                 for i, d in enumerate(data)])
    if not isinstance(data, dict):
        raise TypeError('Input must be NDArray, numpy.ndarray, a list of '
                        'them or dict with them as values')
    out = OrderedDict()
    for k, v in data.items():
        src = v.asnumpy() if isinstance(v, NDArray) else np.asarray(v)
        own = _aligned_empty(src.shape, _served_dtype(src.dtype))
        np.copyto(own, src, casting='unsafe')
        own.flags.writeable = False
        out[k] = own
    return list(out.items())


class NDArrayIter(DataIter):
    """Iterator over in-memory arrays with shuffle/pad/discard handling
    (reference io.py NDArrayIter).

    The arrays are copied once, when the iterator is made (_init_data).
    A batch is then made in one pass over its rows at most: in order and
    inside the data it is a view of that copy, which the CPU device
    takes as it is and a stager (stage_to_device) sends to its device in
    one transfer; a batch that wraps is two runs joined, a shuffled one
    a gather, one copy each.  profiler.input_stats() counts the bytes so
    copied (host_copy_bytes) and the batches that needed none
    (view_batches)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle='pad', data_name='data',
                 label_name='softmax_label'):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        self.idx = np.arange(self.num_data)
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        if last_batch_handle == 'discard':
            new_n = self.num_data - self.num_data % batch_size
            self.num_data = new_n
        assert self.num_data >= batch_size, \
            'batch_size needs to be smaller than data size.'
        self.cursor = -batch_size
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.shuffle:
            np.random.shuffle(self.idx)
        offset = 0
        if self.last_batch_handle == 'roll_over' and \
                self.cursor > self.num_data:
            # Carry the partial batch's offset into the new epoch.
            offset = (self.cursor % self.num_data) % self.batch_size
        self.cursor = offset - self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            data, copied = self._getdata(self.data)
            label, more = self._getdata(self.label)
            copied += more
            profiler.add_input_stats(host_copy_bytes=copied,
                                     view_batches=int(not copied))
            return DataBatch(data=data, label=label, pad=self.getpad(),
                             index=None)
        raise StopIteration

    def _overrun(self):
        """How far the current batch extends past the data end (>= 0)."""
        return max(0, self.cursor + self.batch_size - self.num_data)

    def _getdata(self, data_source):
        """The current batch of each array of `data_source` on the current
        context's device, and the bytes copied on the host to make them.
        A batch's rows are read at most once: a view where they are one
        run (no shuffle, no overrun), else one copy into an aligned
        buffer, two runs joined or a gather."""
        assert self.cursor < self.num_data, 'DataIter needs reset.'
        lo, hi = self.cursor, self.cursor + self.batch_size
        overrun = self._overrun()
        if self.shuffle:
            # Wrap around: pad the batch with rows from the epoch start.
            sel = np.concatenate([self.idx[lo:hi], self.idx[:overrun]])
        ctx = current_context()
        device = ctx.jax_device()
        out, copied = [], 0
        for _, arr in data_source:
            if not (self.shuffle or overrun):
                rows = arr[lo:hi]
            else:
                rows = _aligned_empty(
                    (self.batch_size,) + arr.shape[1:], arr.dtype)
                if self.shuffle:
                    np.take(arr, sel, axis=0, out=rows, mode='clip')
                else:
                    np.concatenate([arr[lo:hi], arr[:overrun]], out=rows)
                copied += rows.nbytes
            if device.platform == 'cpu' and rows.ctypes.data % _HOST_ALIGN:
                copied += rows.nbytes       # the runtime's copy
            # straight to the context's device: aligned rows become a
            # CPU-device array without a copy, and no other device is
            # visited (jnp.asarray would go by jax's default device)
            out.append(NDArray(jax.device_put(rows, device), ctx))
        return out, copied

    def getdata(self):
        return self._getdata(self.data)[0]

    def getlabel(self):
        return self._getdata(self.label)[0]

    def getpad(self):
        if self.last_batch_handle == 'pad':
            return self._overrun()
        return 0


class ResizeIter(_StagedBatchMixin, DataIter):
    """Clamp or stretch an iterator to a fixed epoch length (role of
    reference io.py ResizeIter): exactly ``size`` batches per epoch, with
    the wrapped source rewound transparently whenever it runs dry (so a
    short source cycles and a long one is truncated).
    """

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = int(size)
        self.reset_internal = reset_internal
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.current_batch = None
        self._remaining = self.size

    def reset(self):
        self._remaining = self.size
        if self.reset_internal:
            self.data_iter.reset()

    def _pull_cycling(self):
        """One batch from the source, rewinding it once if exhausted."""
        for attempt in range(2):
            try:
                return self.data_iter.next()
            except StopIteration:
                if attempt:
                    raise
                self.data_iter.reset()
        raise StopIteration  # unreachable; keeps control flow explicit

    def iter_next(self):
        if self._remaining <= 0:
            return False
        self.current_batch = self._pull_cycling()
        self._remaining -= 1
        return True


def _prefetch_worker(src, slot, next_batch, taken, ready, alive):
    """PrefetchingIter worker: refill `slot` whenever the consumer
    drains it.  Module-level on purpose — holding only the shared
    cells (never the iterator object) lets the owner be collected
    while workers run; see PrefetchingIter.__init__."""
    while True:
        taken.wait()
        if not alive[0]:
            return
        try:
            fetched = src.next()
        except StopIteration:
            fetched = None
        next_batch[slot] = fetched
        taken.clear()
        ready.set()


class PrefetchingIter(_StagedBatchMixin, DataIter):
    """Threaded prefetch over one or more iterators
    (reference io.py PrefetchingIter / C++ iter_prefetcher.h).

    Each source iterator gets a worker thread and a pair of event gates
    (ready/taken); iter_next zips the staged per-source batches into one.
    """

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        self.iters = iters if isinstance(iters, list) else [iters]
        self.n_iter = len(self.iters)
        assert self.n_iter > 0
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.started = True
        self.current_batch = [None] * self.n_iter
        self.next_batch = [None] * self.n_iter
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for gate in self.data_taken:
            gate.set()
        # the alive flag is a shared cell (not an attribute) so the
        # workers never hold a reference to `self`: a running thread is
        # pinned by threading's global registry, and a worker->self ref
        # would therefore keep the iterator alive forever and stop
        # __del__ from ever running
        self._alive = [True]
        self.prefetch_threads = []
        for i in range(self.n_iter):
            # daemonic so a leaked iterator can never hang interpreter
            # exit; close() joins them deterministically
            worker = threading.Thread(
                target=_prefetch_worker,
                args=(self.iters[i], i, self.next_batch,
                      self.data_taken[i], self.data_ready[i],
                      self._alive),
                daemon=True)
            self.prefetch_threads.append(worker)
            worker.start()

    def close(self):
        """Stop and join the worker threads (idempotent).  Called on
        teardown (__del__); safe to call early — the iterator is
        unusable after.  The gate is re-set while joining: a worker
        mid-fetch clears data_taken after staging, so a single set()
        can be lost."""
        self._alive[0] = False
        self.started = False
        deadline = time.time() + 5
        remaining = []
        for worker in self.prefetch_threads:
            while worker.is_alive() and time.time() < deadline:
                for gate in self.data_taken:
                    gate.set()
                worker.join(timeout=0.05)
            if worker.is_alive():
                # keep it visible: a worker stuck >5s in src.next()
                # gets retried by the next close()/__del__ instead of
                # being silently orphaned
                remaining.append(worker)
        self.prefetch_threads = remaining

    def __del__(self):
        try:
            self.close()
        except Exception:   # interpreter teardown: attrs may be gone
            pass

    def _merged_desc(self, attr, renames):
        per_iter = [getattr(it, attr) for it in self.iters]
        if renames is None:
            return list(chain.from_iterable(per_iter))
        out = []
        for mapping, descs in zip(renames, per_iter):
            for d in descs:
                d = d if isinstance(d, DataDesc) else DataDesc(*d)
                out.append(DataDesc(mapping[d.name], d.shape, d.dtype))
        return out

    @property
    def provide_data(self):
        return self._merged_desc('provide_data', self.rename_data)

    @property
    def provide_label(self):
        return self._merged_desc('provide_label', self.rename_label)

    def reset(self):
        for gate in self.data_ready:
            gate.wait()
        for it in self.iters:
            it.reset()
        for gate in self.data_ready:
            gate.clear()
        for gate in self.data_taken:
            gate.set()

    def iter_next(self):
        for gate in self.data_ready:
            gate.wait()
        staged = self.next_batch
        if staged[0] is None:
            assert all(b is None for b in staged), \
                'Number of entry mismatches between iterators'
            return False
        pad = staged[0].pad
        assert all(b.pad == pad for b in staged), \
            'Different pad between iterators'
        self.current_batch = DataBatch(
            list(chain.from_iterable(b.data for b in staged)),
            list(chain.from_iterable(b.label for b in staged)),
            pad, staged[0].index)
        for gate in self.data_ready:
            gate.clear()
        for gate in self.data_taken:
            gate.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration


class PrefetchToDeviceIter(_StagedBatchMixin, DataIter):
    """Device-resident input prefetch (decorator).

    Keeps up to `size` upcoming batches' host→device copies in flight:
    `jax.device_put` is asynchronous, so enqueueing the copy of batch
    N+1 while the device computes batch N overlaps the transfer with
    compute — by the time the training loop binds batch N+1 its arrays
    are already resident on the target device (or batch-sharded over
    the mesh when one is given).  The reference's PrefetchingIter
    buffers in *host* memory; this stage buffers in *device* memory —
    the missing half of the input pipeline on accelerators.

    Served batches carry NDArray data committed to the device, which
    the executor's load path recognizes as already-placed (device_put
    to the same device is a no-op).

    input_stall_ms accumulates host wall time spent inside next() —
    the time the training loop was blocked on input (the 'io.next'
    span; 'io.host_batch' and 'io.stage' split it into the wrapped
    iterator's next() and the enqueueing of the copy) — so callers
    can report per-step input stall (tests/test_image_pipeline.py:
    test_prefetch_to_device_feeds_stall_counter).
    """

    def __init__(self, data_iter, size=2, device=None, mesh=None):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = max(1, int(size))
        # accept a Context or a raw jax device
        self.device = device.jax_device() \
            if hasattr(device, 'jax_device') else device
        self.mesh = mesh
        self._buf = deque()
        self._exhausted = False
        self.current_batch = None
        self.input_stall_ms = 0.0
        self.batches_served = 0

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def reset(self):
        self.data_iter.reset()
        self._buf.clear()
        self._exhausted = False

    def _put(self, arrays):
        if arrays is None:
            return None
        return [NDArray(d) for d in stage_to_device(
            arrays, device=self.device, mesh=self.mesh)]

    def _stage(self, batch):
        return DataBatch(self._put(batch.data), self._put(batch.label),
                         pad=batch.pad, index=batch.index,
                         bucket_key=batch.bucket_key,
                         provide_data=batch.provide_data,
                         provide_label=batch.provide_label)

    def _fill(self):
        while not self._exhausted and len(self._buf) < self.size:
            try:
                with profiler.scope('io.host_batch', 'io'):
                    batch = self.data_iter.next()
            except StopIteration:
                self._exhausted = True
                return
            with profiler.scope('io.stage', 'io'):
                self._buf.append(self._stage(batch))

    def iter_next(self):
        if self._exhausted and not self._buf:
            self.current_batch = None
            return False
        with profiler.scope('io.next', 'io') as span:
            self._fill()
            served = int(bool(self._buf))
            self.current_batch = self._buf.popleft() if served else None
            self._fill()     # enqueue the next copy before returning
        stall_ms = span.seconds * 1e3
        self.input_stall_ms += stall_ms
        self.batches_served += served
        profiler.add_input_stats(stall_ms=stall_ms, batches=served)
        return bool(served)

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def stall_ms_per_batch(self):
        """Mean host time blocked in next() per served batch."""
        if not self.batches_served:
            return 0.0
        return self.input_stall_ms / self.batches_served


def stage_to_device(arrays, device=None, mesh=None):
    """Enqueue the (async) host->device copy of each array and return
    the raw jax arrays — the staging primitive PrefetchToDeviceIter
    and the serving engine's dynamic batcher share.  `device` accepts
    a Context or a raw jax device; with `mesh` the arrays are
    batch-sharded over it instead.  The bytes handed to either count
    as the profiler's h2d_bytes."""
    if hasattr(device, 'jax_device'):
        device = device.jax_device()
    out = []
    put_bytes = 0
    for a in arrays:
        # host memory (a CPU-device array, a numpy array) goes to its
        # target in the one put below, by way of no other device
        data = a._data if isinstance(a, NDArray) else np.asarray(a)
        if mesh is not None:
            from .parallel import mesh as pmesh
            data = pmesh.shard_batch(mesh, data)
        elif device is not None:
            data = jax.device_put(data, device)
        else:
            data = jax.numpy.asarray(data)
        if mesh is not None or device is not None:
            put_bytes += data.nbytes
        out.append(data)
    if put_bytes:
        profiler.add_input_stats(h2d_bytes=put_bytes)
    return out


def prefetch_to_device(data_iter, size=2, device=None, mesh=None):
    """Wrap `data_iter` so upcoming batches are staged device-resident
    (see PrefetchToDeviceIter).  size=2 double-buffers: one batch being
    consumed, one in flight."""
    return PrefetchToDeviceIter(data_iter, size=size, device=device,
                                mesh=mesh)


class CSVIter(DataIter):
    """CSV file iterator (reference src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=',', dtype=np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=',', dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size,
            last_batch_handle='pad' if round_batch else 'discard',
            label_name='label')

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class _NativeImageRecordIter(DataIter):
    """The C++ threaded decode pipeline (src/io/image_record_iter.cc) —
    reader thread + OpenCV worker pool + bounded prefetch, the direct
    port of the reference's iter_image_recordio_2.cc architecture."""

    def __init__(self, path_imgrec, idx_path, data_shape, batch_size,
                 label_width, shuffle, rand_crop, rand_mirror, resize,
                 mean, std, num_parts, part_index, preprocess_threads,
                 prefetch_buffer, seed, data_name, label_name):
        import ctypes
        from . import _core
        super().__init__(batch_size)
        self._core = _core
        lib = _core.lib(required=True)
        self._lib = lib
        self._shape = tuple(data_shape)
        self._label_width = label_width
        self._data_name = data_name
        self._label_name = label_name
        c3 = (ctypes.c_float * 3)
        mean_arr = c3(*([float(m) for m in mean] if mean is not None
                        else [0., 0., 0.]))
        std_arr = c3(*([float(s) for s in std] if std is not None
                       else [1., 1., 1.]))
        self._handle = lib.MXTImageRecordIterCreate(
            path_imgrec.encode(), idx_path.encode(), batch_size,
            self._shape[0], self._shape[1], self._shape[2], label_width,
            int(shuffle), int(rand_crop), int(rand_mirror), int(resize),
            mean_arr, std_arr, num_parts, part_index,
            preprocess_threads, prefetch_buffer, seed)
        if not self._handle:
            raise _core.NativeError(lib.MXTGetLastError().decode())

    def __del__(self):
        if getattr(self, '_handle', None):
            self._lib.MXTImageRecordIterFree(self._handle)
            self._handle = None

    @property
    def provide_data(self):
        return [DataDesc(self._data_name,
                         (self.batch_size,) + self._shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self._label_width == 1 \
            else (self.batch_size, self._label_width)
        return [DataDesc(self._label_name, shape)]

    def reset(self):
        self._core.check_call(
            self._lib.MXTImageRecordIterReset(self._handle))

    def next(self):
        import ctypes
        from . import ndarray as _nd
        data_p = ctypes.POINTER(ctypes.c_float)()
        label_p = ctypes.POINTER(ctypes.c_float)()
        pad = ctypes.c_int()
        ret = self._lib.MXTImageRecordIterNext(
            self._handle, ctypes.byref(data_p), ctypes.byref(label_p),
            ctypes.byref(pad))
        if ret < 0:
            raise self._core.NativeError(
                self._lib.MXTGetLastError().decode())
        if ret == 0:
            raise StopIteration
        n = self.batch_size
        dshape = (n,) + self._shape
        data = np.ctypeslib.as_array(data_p, shape=dshape).copy()
        lshape = (n, self._label_width) if self._label_width > 1 \
            else (n,)
        label = np.ctypeslib.as_array(
            label_p, shape=(n * self._label_width,)) \
            .reshape(lshape).copy()
        return DataBatch(data=[_nd.array(data)], label=[_nd.array(label)],
                         pad=pad.value, index=None,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)


class ImageRecordIter(DataIter):
    """RecordIO image iterator with augmentation and prefetch
    (reference src/io/iter_image_recordio_2.cc registered as
    ImageRecordIter at :577).  Uses the native C++ threaded pipeline
    when available (and the request fits its feature set); otherwise
    layers image.ImageIter + PrefetchingIter — the same
    decode->augment->batch->prefetch structure in Python."""

    def __init__(self, path_imgrec, data_shape, batch_size,
                 label_width=1, shuffle=False, rand_crop=False,
                 rand_mirror=False, mean_img=None,
                 mean_r=0, mean_g=0, mean_b=0,
                 std_r=0, std_g=0, std_b=0,
                 resize=0, num_parts=1, part_index=0,
                 preprocess_threads=4, prefetch_buffer=4,
                 seed=0, use_native=None,
                 data_name='data', label_name='softmax_label', **kwargs):
        super().__init__(batch_size)
        from . import _core
        from .image import ImageIter, Augmenter
        import os as _os
        idx_path = _os.path.splitext(path_imgrec)[0] + '.idx'
        if use_native is None:
            use_native = (_core.available() and mean_img is None and
                          _os.path.isfile(idx_path))
        if use_native:
            mean = None
            if mean_r or mean_g or mean_b:
                mean = [mean_r, mean_g, mean_b]
            std = None
            if std_r or std_g or std_b:
                std = [std_r, std_g, std_b]
            self._inner = _NativeImageRecordIter(
                path_imgrec, idx_path, tuple(data_shape), batch_size,
                label_width, shuffle, rand_crop, rand_mirror, resize,
                mean, std, num_parts, part_index, preprocess_threads,
                prefetch_buffer, seed, data_name, label_name)
            return
        # pure-Python fallback
        mean = None
        std = None
        if mean_r or mean_g or mean_b:
            mean = np.array([mean_r, mean_g, mean_b], np.float32)
        if std_r or std_g or std_b:
            std = np.array([std_r, std_g, std_b], np.float32)
        aug_list = None
        if mean_img is not None:
            # mean-image normalization (reference iter_normalize.h):
            # mean_img is an NDArray blob saved by a previous pass
            from . import ndarray as _nd
            if not isinstance(mean_img, str):
                raise ValueError('mean_img must be a path to a saved '
                                 'NDArray mean image')
            loaded = _nd.load(mean_img)
            marr = (list(loaded.values())[0] if isinstance(loaded, dict)
                    else loaded[0]).asnumpy().astype(np.float32)
            if marr.ndim == 3 and marr.shape[0] in (1, 3):
                marr = marr.transpose(1, 2, 0)  # CHW -> HWC

            class _MeanImageAug(Augmenter):
                def __call__(self, src):
                    from .image import _asnp, _like
                    return [_like(_asnp(src).astype(np.float32) - marr,
                                  src)]
            from .image import CreateAugmenter
            aug_list = CreateAugmenter(
                tuple(data_shape), resize=resize, rand_crop=rand_crop,
                rand_mirror=rand_mirror, mean=mean, std=std)
            aug_list.append(_MeanImageAug())
        # the python pipeline keeps the reference's layering — decode
        # workers (preprocess_threads, the parallel decode pool inside
        # ImageIter) under a batch-prefetch thread (PrefetchingIter)
        if aug_list is not None:
            self._inner = PrefetchingIter(ImageIter(
                batch_size=batch_size, data_shape=tuple(data_shape),
                label_width=label_width, path_imgrec=path_imgrec,
                shuffle=shuffle, part_index=part_index,
                num_parts=num_parts, aug_list=aug_list,
                preprocess_threads=preprocess_threads,
                data_name=data_name, label_name=label_name))
        else:
            self._inner = PrefetchingIter(ImageIter(
                batch_size=batch_size, data_shape=tuple(data_shape),
                label_width=label_width, path_imgrec=path_imgrec,
                shuffle=shuffle, part_index=part_index,
                num_parts=num_parts,
                rand_crop=rand_crop, rand_mirror=rand_mirror,
                resize=resize, mean=mean, std=std,
                preprocess_threads=preprocess_threads,
                data_name=data_name, label_name=label_name))

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class MNISTIter(DataIter):
    """MNIST idx-file iterator (reference src/io/iter_mnist.cc:259)."""

    def __init__(self, image, label, batch_size=128, shuffle=True,
                 flat=False, seed=0, silent=False, num_parts=1,
                 part_index=0, **kwargs):
        super().__init__(batch_size)
        import gzip
        import struct as _struct

        def _open(path):
            return gzip.open(path, 'rb') if path.endswith('.gz') \
                else open(path, 'rb')
        with _open(label) as fin:
            _struct.unpack('>II', fin.read(8))
            lab = np.frombuffer(fin.read(), dtype=np.uint8) \
                .astype(np.float32)
        with _open(image) as fin:
            _, n, r, c = _struct.unpack('>IIII', fin.read(16))
            img = np.frombuffer(fin.read(), dtype=np.uint8) \
                .reshape(n, r, c).astype(np.float32) / 255.0
        if num_parts > 1:
            C = n // num_parts
            img = img[part_index * C:(part_index + 1) * C]
            lab = lab[part_index * C:(part_index + 1) * C]
        if shuffle:
            rng = np.random.RandomState(seed)
            perm = rng.permutation(len(img))
            img, lab = img[perm], lab[perm]
        data = img.reshape(len(img), -1) if flat \
            else img[:, None, :, :]
        self._inner = NDArrayIter(data, lab, batch_size,
                                  last_batch_handle='discard')

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()
