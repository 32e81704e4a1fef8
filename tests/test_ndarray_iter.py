"""NDArrayIter's batches against a plain NumPy model of its cursor:
values, pad, dtype, shape and context for every last_batch_handle, with
and without shuffling, over sizes that divide the data and do not; that
a served batch keeps its values; and the input layer's counters of the
host copies it made."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler


def _sources(n):
    """Two data sources and a label whose rows say which row they are:
    float64 (served as float32), float32 (served as it is), int64
    (served as int32)."""
    rows = np.arange(n)
    a = (rows[:, None, None] + np.arange(12).reshape(3, 4) / 16.0)
    b = (rows[:, None] * 2.0 + np.arange(16) / 32.0).astype(np.float32)
    y = rows.astype(np.int64) * 3
    return a.astype(np.float64), b, y


def _model_epoch(idx, n, batch, handle, offset):
    """[(row numbers, pad)] of one epoch that starts `offset` rows in,
    and the offset of the next: the cursor walks the order `idx` in
    steps of `batch`; a batch that runs past the end takes the rows it
    lacks from the head of the same order ('pad' reports how many,
    'roll_over' starts the next epoch after them), 'discard' drops it."""
    end = n - n % batch if handle == 'discard' else n
    out, lacks = [], 0
    for start in range(offset, end, batch):
        lacks = max(0, start + batch - end)
        rows = np.concatenate([idx[start:start + batch], idx[:lacks]])
        out.append((rows, lacks if handle == 'pad' else 0))
    return out, lacks if handle == 'roll_over' else 0


@pytest.mark.parametrize('n,batch', [(32, 8), (37, 8)],
                         ids=['divides', 'remainder'])
@pytest.mark.parametrize('shuffle', [False, True],
                         ids=['in_order', 'shuffled'])
@pytest.mark.parametrize('handle', ['pad', 'discard', 'roll_over'])
def test_batches_follow_the_numpy_model(handle, shuffle, n, batch):
    a, b, y = _sources(n)
    np.random.seed(5)
    it = mx.io.NDArrayIter({'a': a, 'b': b}, y, batch_size=batch,
                           shuffle=shuffle, last_batch_handle=handle)
    assert [(d.name, d.shape) for d in it.provide_data] == \
        [('a', (batch, 3, 4)), ('b', (batch, 16))]
    assert [(d.name, d.shape) for d in it.provide_label] == \
        [('softmax_label', (batch,))]
    offset, orders = 0, []
    for epoch in range(2):
        if epoch:
            it.reset()
        idx = np.array(it.idx)          # the order of this epoch
        orders.append(idx)
        assert sorted(idx) == list(range(n))
        expected, offset = _model_epoch(idx, n, batch, handle, offset)
        served = list(it)
        assert len(served) == len(expected)
        for got, (rows, pad) in zip(served, expected):
            assert got.pad == pad
            wanted = [(a[rows].astype(np.float32), got.data[0]),
                      (b[rows], got.data[1]),
                      (y[rows].astype(np.int32), got.label[0])]
            assert len(got.data) == 2 and len(got.label) == 1
            for want, arr in wanted:
                assert isinstance(arr, mx.nd.NDArray)
                assert arr.context == mx.cpu(0)
                assert arr.shape == want.shape
                assert arr.dtype == want.dtype
                np.testing.assert_array_equal(arr.asnumpy(), want)
        with pytest.raises(StopIteration):
            it.next()
    in_order = [np.array_equal(o, np.arange(n)) for o in orders]
    assert in_order == [not shuffle] * 2


def _iterators(x, y, shuffle):
    bare = mx.io.NDArrayIter(x, y, batch_size=8, shuffle=shuffle)
    staged = mx.io.PrefetchToDeviceIter(
        mx.io.NDArrayIter(x, y, batch_size=8, shuffle=shuffle), size=2,
        device=mx.cpu(1))
    return {'bare': bare, 'staged': staged}


@pytest.mark.parametrize('shuffle', [False, True],
                         ids=['in_order', 'shuffled'])
@pytest.mark.parametrize('how', ['bare', 'staged'])
def test_a_served_batch_keeps_its_values(how, shuffle):
    """Writing into the source array after next() has served a batch
    changes neither that batch nor, behind the stager, the batches whose
    copies were already enqueued."""
    n = 32
    x = np.arange(n * 16, dtype=np.float32).reshape(n, 16)
    y = np.arange(n, dtype=np.float32)
    x0, y0 = x.copy(), y.copy()
    np.random.seed(11)
    it = _iterators(x, y, shuffle)[how]
    first = it.next()
    x[...] = -1.0
    y[...] = -1.0
    rows = first.label[0].asnumpy().astype(int)
    if not shuffle:
        assert list(rows) == list(range(8))
    np.testing.assert_array_equal(first.label[0].asnumpy(), y0[rows])
    np.testing.assert_array_equal(first.data[0].asnumpy(), x0[rows])
    if how == 'staged':
        dev, = first.data[0]._data.devices()
        assert dev == mx.cpu(1).jax_device()


def test_the_iterator_owns_its_data():
    """As in the reference, the arrays are copied when the iterator is
    made: what the user writes into them afterwards reaches no batch,
    served before or after, in this epoch or the next."""
    x = np.arange(64, dtype=np.float32).reshape(16, 4)
    y = np.arange(16, dtype=np.float32)
    x0 = x.copy()
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    first = it.next()
    x[...] = -1.0
    second = it.next()
    it.reset()
    again = it.next()
    np.testing.assert_array_equal(first.data[0].asnumpy(), x0[:8])
    np.testing.assert_array_equal(second.data[0].asnumpy(), x0[8:])
    np.testing.assert_array_equal(again.data[0].asnumpy(), x0[:8])


def _drain(it, epochs=2):
    profiler.clear()
    n = 0
    for epoch in range(epochs):
        if epoch:
            it.reset()
        for _ in it:
            n += 1
    return n, profiler.input_stats()


# 16 float32 a row: every batch of 8 rows starts on a 64-byte boundary
def _pool(n):
    return (np.random.rand(n, 16).astype(np.float32),
            np.arange(n * 16, dtype=np.float32).reshape(n, 16))


def test_counters_in_order_every_batch_is_a_view():
    x, y = _pool(64)
    it = mx.io.PrefetchToDeviceIter(
        mx.io.NDArrayIter(x, y, batch_size=8), size=2, device=mx.cpu(1))
    n, stats = _drain(it)
    assert n == 16
    assert stats['input_batches'] == 16
    assert stats['view_batches'] == 16
    assert stats['host_copy_bytes'] == 0
    assert stats['h2d_bytes'] == 16 * 8 * (x[0].nbytes + y[0].nbytes)


def test_counters_shuffled_one_copy_a_batch():
    x, y = _pool(64)
    it = mx.io.PrefetchToDeviceIter(
        mx.io.NDArrayIter(x, y, batch_size=8, shuffle=True), size=2,
        device=mx.cpu(1))
    n, stats = _drain(it)
    assert n == 16
    assert stats['input_batches'] == 16
    assert stats['view_batches'] == 0
    assert stats['host_copy_bytes'] == 16 * 8 * (x[0].nbytes + y[0].nbytes)


@pytest.mark.parametrize('handle', ['pad', 'roll_over'])
def test_counters_a_batch_that_wraps_is_one_join(handle):
    x, y = _pool(20)
    it = mx.io.NDArrayIter(x, y, batch_size=8, last_batch_handle=handle)
    profiler.clear()
    served = list(it)
    stats = profiler.input_stats()
    assert len(served) == 3
    assert stats['view_batches'] == 2       # rows 0-7 and 8-15
    assert stats['host_copy_bytes'] == 8 * (x[0].nbytes + y[0].nbytes)


def test_counters_a_source_in_another_dtype_is_converted_once():
    """float64 and int64 are converted when the iterator copies them,
    not batch by batch: in order, their batches are views too."""
    x = np.random.rand(32, 16)                  # float64
    y = np.arange(32 * 16).reshape(32, 16)      # int64
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    profiler.clear()
    served = list(it)
    stats = profiler.input_stats()
    assert served[1].data[0].dtype == np.float32
    assert served[1].label[0].dtype == np.int32
    assert stats['view_batches'] == 4
    assert stats['host_copy_bytes'] == 0


def test_counters_a_view_the_runtime_cannot_alias_counts_as_a_copy():
    """Rows of 4 bytes in batches of 3: most batches start off a 64-byte
    boundary, where the CPU runtime copies the rows it is handed."""
    y = np.arange(48, dtype=np.float32)
    it = mx.io.NDArrayIter(y, batch_size=3, data_name='y')
    profiler.clear()
    served = list(it)
    stats = profiler.input_stats()
    aligned = [i for i in range(16) if (i * 3 * 4) % 64 == 0]
    assert stats['view_batches'] == len(aligned) == 1
    assert stats['host_copy_bytes'] == (16 - len(aligned)) * 12
    np.testing.assert_array_equal(served[5].data[0].asnumpy(), y[15:18])
