"""NDArray imperative API tests (model: reference
tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_create_and_asnumpy():
    a = nd.array([[1, 2], [3, 4]])
    assert a.shape == (2, 2)
    assert a.dtype == np.float32
    np.testing.assert_allclose(a.asnumpy(), [[1, 2], [3, 4]])


def test_zeros_ones_full_arange():
    assert nd.zeros((2, 3)).asnumpy().sum() == 0
    assert nd.ones((2, 3)).asnumpy().sum() == 6
    np.testing.assert_allclose(nd.full((2,), 3.5).asnumpy(), [3.5, 3.5])
    np.testing.assert_allclose(nd.arange(0, 5).asnumpy(), np.arange(0, 5.0))


def test_elemwise_arith():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    np.testing.assert_allclose((a + b).asnumpy(), [5, 7, 9])
    np.testing.assert_allclose((a - b).asnumpy(), [-3, -3, -3])
    np.testing.assert_allclose((a * b).asnumpy(), [4, 10, 18])
    np.testing.assert_allclose((b / a).asnumpy(), [4, 2.5, 2])
    np.testing.assert_allclose((a + 1).asnumpy(), [2, 3, 4])
    np.testing.assert_allclose((1 - a).asnumpy(), [0, -1, -2])
    np.testing.assert_allclose((2 * a).asnumpy(), [2, 4, 6])
    np.testing.assert_allclose((6 / a).asnumpy(), [6, 3, 2])
    np.testing.assert_allclose((a ** 2).asnumpy(), [1, 4, 9])
    np.testing.assert_allclose((-a).asnumpy(), [-1, -2, -3])


def test_broadcast_in_dunder():
    a = nd.ones((2, 3))
    b = nd.array([1.0, 2.0, 3.0])
    np.testing.assert_allclose((a + b).asnumpy(), [[2, 3, 4], [2, 3, 4]])


def test_comparisons():
    a = nd.array([1.0, 2.0, 3.0])
    np.testing.assert_allclose((a > 1.5).asnumpy(), [0, 1, 1])
    np.testing.assert_allclose((a == 2).asnumpy(), [0, 1, 0])


def test_inplace():
    a = nd.ones((3,))
    a += 2
    np.testing.assert_allclose(a.asnumpy(), [3, 3, 3])
    a *= 2
    np.testing.assert_allclose(a.asnumpy(), [6, 6, 6])


def test_indexing():
    a = nd.array(np.arange(12).reshape(3, 4))
    np.testing.assert_allclose(a[1].asnumpy(), [4, 5, 6, 7])
    np.testing.assert_allclose(a[1:3].asnumpy(),
                               np.arange(12).reshape(3, 4)[1:3])
    a[:] = 0
    assert a.asnumpy().sum() == 0
    a[1] = 5
    np.testing.assert_allclose(a.asnumpy()[1], [5, 5, 5, 5])


def test_reshape_transpose():
    a = nd.array(np.arange(6).reshape(2, 3))
    assert a.reshape((3, 2)).shape == (3, 2)
    assert a.reshape((-1,)).shape == (6,)
    assert a.T.shape == (3, 2)
    assert a.reshape((0, -1)).shape == (2, 3)


def test_reshape_special_codes():
    a = nd.zeros((2, 3, 4))
    assert a.reshape((-2,)).shape == (2, 3, 4)
    assert a.reshape((0, -3)).shape == (2, 12)
    assert a.reshape((-4, 1, 2, 0, 0)).shape == (1, 2, 3, 4)


def test_reductions():
    a = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    assert a.sum().asscalar() == 15
    np.testing.assert_allclose(a.sum(axis=0).asnumpy(), [3, 5, 7])
    np.testing.assert_allclose(a.mean(axis=1).asnumpy(), [1, 4])
    np.testing.assert_allclose(a.max(axis=1).asnumpy(), [2, 5])
    np.testing.assert_allclose(a.argmax(axis=1).asnumpy(), [2, 2])
    np.testing.assert_allclose(a.norm().asnumpy(),
                               [np.sqrt((np.arange(6) ** 2).sum())], rtol=1e-6)


def test_dot():
    a = nd.array(np.random.rand(3, 4))
    b = nd.array(np.random.rand(4, 5))
    np.testing.assert_allclose(nd.dot(a, b).asnumpy(),
                               a.asnumpy() @ b.asnumpy(), rtol=1e-5)
    c = nd.dot(a, b, transpose_a=False, transpose_b=False)
    assert c.shape == (3, 5)
    d = nd.dot(b, a, transpose_a=True, transpose_b=True)
    assert d.shape == (5, 3)


def test_concat_split_stack():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concatenate([a, b], axis=0)
    assert c.shape == (4, 3)
    c2 = nd.Concat(a, b, num_args=2, dim=1)
    assert c2.shape == (2, 6)
    parts = nd.SliceChannel(c2, num_outputs=2, axis=1)
    assert parts[0].shape == (2, 3)
    s = nd.stack(a, b, num_args=2, axis=0)
    assert s.shape == (2, 2, 3)


def test_unary_math():
    a = nd.array([1.0, 4.0, 9.0])
    np.testing.assert_allclose(nd.sqrt(a).asnumpy(), [1, 2, 3], rtol=1e-6)
    np.testing.assert_allclose(nd.square(a).asnumpy(), [1, 16, 81])
    np.testing.assert_allclose(nd.exp(nd.log(a)).asnumpy(), [1, 4, 9],
                               rtol=1e-5)


def test_save_load_dict(tmp_path):
    fname = str(tmp_path / 'test-0001.params')
    data = {'arg:w': nd.array(np.random.rand(3, 4)),
            'aux:m': nd.array(np.random.rand(7))}
    nd.save(fname, data)
    loaded = nd.load(fname)
    assert set(loaded) == set(data)
    for k in data:
        np.testing.assert_allclose(loaded[k].asnumpy(), data[k].asnumpy())


def test_save_load_list(tmp_path):
    fname = str(tmp_path / 'list.params')
    data = [nd.ones((2,)), nd.zeros((3, 3))]
    nd.save(fname, data)
    loaded = nd.load(fname)
    assert len(loaded) == 2
    assert loaded[1].shape == (3, 3)


def test_copyto_context():
    a = nd.ones((2, 2))
    b = a.copyto(mx.cpu(0))
    np.testing.assert_allclose(b.asnumpy(), a.asnumpy())
    c = nd.zeros((2, 2))
    a.copyto(c)
    np.testing.assert_allclose(c.asnumpy(), a.asnumpy())


def test_accelerator_context_never_lands_on_cpu():
    """On the CPU backend tpu()/gpu() raise and name the platform they
    found; cpu() still wraps modulo the eight virtual devices."""
    import jax
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(mx.MXNetError, match=r'cpu:0'):
            ctx.jax_device()
    assert mx.num_gpus() == 0
    cpus = jax.devices('cpu')
    assert mx.cpu(7).jax_device() == cpus[7]
    assert mx.cpu(9).jax_device() == cpus[1]


def test_astype():
    a = nd.ones((2,))
    assert a.astype(np.int32).dtype == np.int32
    assert nd.Cast(a, dtype='int32').dtype == np.int32


def test_take_embedding_onehot():
    w = nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    idx = nd.array([0, 2])
    out = nd.Embedding(idx, w, input_dim=4, output_dim=3)
    np.testing.assert_allclose(out.asnumpy(), [[0, 1, 2], [6, 7, 8]])
    oh = nd.one_hot(idx, depth=4)
    np.testing.assert_allclose(oh.asnumpy(),
                               [[1, 0, 0, 0], [0, 0, 1, 0]])


def test_topk_sort():
    a = nd.array([[3.0, 1.0, 2.0], [6.0, 5.0, 4.0]])
    v = nd.topk(a, k=2, ret_typ='value')
    np.testing.assert_allclose(v.asnumpy(), [[3, 2], [6, 5]])
    s = nd.sort(a, axis=1)
    np.testing.assert_allclose(s.asnumpy(), [[1, 2, 3], [4, 5, 6]])


def test_random_ops():
    mx.random.seed(42)
    u = nd.uniform(low=0, high=1, shape=(100,))
    assert u.shape == (100,)
    assert 0 <= u.asnumpy().min() and u.asnumpy().max() <= 1
    mx.random.seed(42)
    u2 = nd.uniform(low=0, high=1, shape=(100,))
    np.testing.assert_allclose(u.asnumpy(), u2.asnumpy())
    n = nd.normal(loc=5.0, scale=0.1, shape=(1000,))
    assert abs(n.asnumpy().mean() - 5.0) < 0.1


def test_waitall():
    a = nd.ones((4,)) * 2
    a.wait_to_read()
    nd.waitall()


def test_batchnorm_imperative():
    x = nd.array(np.random.rand(4, 3, 5, 5).astype(np.float32))
    gamma = nd.ones((3,))
    beta = nd.zeros((3,))
    mmean = nd.zeros((3,))
    mvar = nd.ones((3,))
    out = nd.BatchNorm(x, gamma, beta, mmean, mvar, fix_gamma=False)
    assert out.shape == x.shape


def test_convolution_imperative():
    x = nd.array(np.random.rand(1, 2, 5, 5).astype(np.float32))
    w = nd.array(np.random.rand(4, 2, 3, 3).astype(np.float32))
    b = nd.zeros((4,))
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4)
    assert out.shape == (1, 4, 3, 3)
    out2 = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4,
                          stride=(2, 2), pad=(1, 1))
    assert out2.shape == (1, 4, 3, 3)


def test_pooling_imperative():
    x = nd.array(np.random.rand(1, 2, 4, 4).astype(np.float32))
    out = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type='max')
    assert out.shape == (1, 2, 2, 2)
    g = nd.Pooling(x, global_pool=True, pool_type='avg', kernel=(2, 2))
    assert g.shape == (1, 2, 1, 1)
    np.testing.assert_allclose(g.asnumpy().reshape(2),
                               x.asnumpy().mean(axis=(0, 2, 3)), rtol=1e-6)


def test_fullyconnected_imperative():
    x = nd.array(np.random.rand(2, 8).astype(np.float32))
    w = nd.array(np.random.rand(4, 8).astype(np.float32))
    b = nd.zeros((4,))
    out = nd.FullyConnected(x, w, b, num_hidden=4)
    np.testing.assert_allclose(out.asnumpy(),
                               x.asnumpy() @ w.asnumpy().T, rtol=1e-5)
