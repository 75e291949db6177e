"""NDArray tests (reference: tests/python/unittest/test_ndarray.py —
elementwise/negate/choose/copy/scalar/pickle/saveload/slice/clip/dot)."""

import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _same(a, b, tol=1e-5):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def check_with_uniform(uf, arg_shapes, dim=None, npuf=None, rmin=-10, type_list=None):
    """Random-input consistency vs numpy (mirrors the reference helper)."""
    for _ in range(3):
        if isinstance(arg_shapes, int):
            assert dim
            shape = tuple(np.random.randint(1, int(9), size=dim))
            arg_shapes = [shape] * arg_shapes
        ndarray_arg = []
        numpy_arg = []
        for s in arg_shapes:
            npy = np.random.uniform(rmin, 10, s).astype(np.float32)
            ndarray_arg.append(mx.nd.array(npy))
            numpy_arg.append(npy)
        out1 = uf(*ndarray_arg)
        out2 = (npuf or uf)(*numpy_arg)
        assert out1.shape == out2.shape
        _same(out1.asnumpy(), out2)


def test_ndarray_elementwise():
    check_with_uniform(lambda a, b: a + b, 2, 3)
    check_with_uniform(lambda a, b: a - b, 2, 3)
    check_with_uniform(lambda a, b: a * b, 2, 3)
    check_with_uniform(lambda a, b: a / b, 2, 3, rmin=1)
    check_with_uniform(lambda a: a + 3.0, 1, 3)
    check_with_uniform(lambda a: 3.0 - a, 1, 3)
    check_with_uniform(lambda a: a * 4.5, 1, 3)
    check_with_uniform(lambda a: a / 3.3, 1, 3)
    check_with_uniform(lambda a: 2.0 / a, 1, 3, rmin=1)


def test_ndarray_negate():
    npy = np.random.uniform(-10, 10, (2, 3, 4)).astype(np.float32)
    arr = mx.nd.array(npy)
    _same(npy, arr.asnumpy())
    _same(-npy, (-arr).asnumpy())
    # negation is out-of-place: arr unchanged
    _same(npy, arr.asnumpy())


def test_ndarray_inplace():
    npy = np.ones((4, 5), np.float32)
    arr = mx.nd.array(npy)
    arr += 2.0
    _same(arr.asnumpy(), npy + 2.0)
    arr *= 3.0
    _same(arr.asnumpy(), (npy + 2.0) * 3.0)
    other = mx.nd.ones((4, 5))
    arr -= other
    _same(arr.asnumpy(), (npy + 2.0) * 3.0 - 1.0)


def test_ndarray_scalar_ops_functions():
    a = mx.nd.ones((3, 4))
    out = mx.nd.empty((3, 4))
    nd._plus_scalar(a, 5.0, out=out)
    _same(out.asnumpy(), np.ones((3, 4)) + 5.0)
    nd._rminus_scalar(a, 5.0, out=out)
    _same(out.asnumpy(), 5.0 - np.ones((3, 4)))


def test_ndarray_choose():
    npy = np.arange(20).reshape(4, 5).astype(np.float32)
    arr = mx.nd.array(npy)
    idx = mx.nd.array([1, 3, 2, 0])
    out = nd.choose_element_0index(arr, idx)
    _same(out.asnumpy(), npy[np.arange(4), [1, 3, 2, 0]])


def test_ndarray_onehot():
    idx = mx.nd.array([1, 0, 2])
    out = mx.nd.zeros((3, 4))
    # reference signature: the second argument IS the output buffer
    nd.onehot_encode(idx, out)
    expect = np.zeros((3, 4), np.float32)
    expect[np.arange(3), [1, 0, 2]] = 1
    _same(out.asnumpy(), expect)


def test_ndarray_copy():
    c = mx.nd.array(np.random.uniform(-10, 10, (10, 10)))
    d = c.copyto(mx.cpu(0))
    _same(c.asnumpy(), d.asnumpy())
    e = mx.nd.zeros((10, 10))
    c.copyto(e)
    _same(c.asnumpy(), e.asnumpy())
    assert e is not c


def test_ndarray_slice():
    shape = (10,)
    npy = np.random.uniform(-10, 10, shape).astype(np.float32)
    arr = mx.nd.array(npy)
    _same(arr[3:8].asnumpy(), npy[3:8])
    arr[3:8] = npy[3:8] + 1
    npy[3:8] += 1
    _same(arr.asnumpy(), npy)
    sl = arr.slice(2, 5)
    _same(sl.asnumpy(), npy[2:5])


def test_ndarray_setitem_full():
    arr = mx.nd.zeros((3, 4))
    arr[:] = 7.5
    _same(arr.asnumpy(), np.full((3, 4), 7.5))
    arr[:] = np.arange(4)
    _same(arr.asnumpy(), np.broadcast_to(np.arange(4), (3, 4)))


def test_ndarray_reshape_transpose():
    npy = np.random.uniform(size=(2, 3, 4)).astype(np.float32)
    arr = mx.nd.array(npy)
    _same(arr.reshape((3, 8)).asnumpy(), npy.reshape(3, 8))
    m = mx.nd.array(npy.reshape(6, 4))
    _same(m.T.asnumpy(), npy.reshape(6, 4).T)


def test_ndarray_dot():
    a = np.random.uniform(size=(4, 5)).astype(np.float32)
    b = np.random.uniform(size=(5, 6)).astype(np.float32)
    out = nd.dot(mx.nd.array(a), mx.nd.array(b))
    _same(out.asnumpy(), a @ b, tol=1e-4)


def test_ndarray_unary():
    a = np.random.uniform(0.5, 10, (3, 4)).astype(np.float32)
    _same(nd.square(mx.nd.array(a)).asnumpy(), np.square(a))
    _same(nd.sqrt(mx.nd.array(a)).asnumpy(), np.sqrt(a), tol=1e-4)
    _same(nd.exp(mx.nd.array(a * 0.1)).asnumpy(), np.exp(a * 0.1), tol=1e-4)
    _same(nd.log(mx.nd.array(a)).asnumpy(), np.log(a), tol=1e-4)
    norm = nd.norm(mx.nd.array(a))
    assert norm.shape == (1,)
    _same(norm.asnumpy(), [np.sqrt((a ** 2).sum())], tol=1e-4)


def test_ndarray_clip():
    a = np.random.uniform(-10, 10, (4, 4)).astype(np.float32)
    out = nd.clip(mx.nd.array(a), -2.0, 2.0)
    _same(out.asnumpy(), np.clip(a, -2, 2))


def test_ndarray_pickle():
    a = mx.nd.array(np.random.uniform(size=(4, 5)))
    data = pickle.dumps(a)
    b = pickle.loads(data)
    _same(a.asnumpy(), b.asnumpy())


def test_ndarray_saveload(tmp_path):
    fname = str(tmp_path / "nd.bin")
    data = [mx.nd.array(np.random.uniform(size=(3, 4))) for _ in range(4)]
    nd.save(fname, data)
    loaded = nd.load(fname)
    assert len(loaded) == 4
    for x, y in zip(data, loaded):
        _same(x.asnumpy(), y.asnumpy())
    named = {"w": data[0], "b": data[1]}
    nd.save(fname, named)
    loaded = nd.load(fname)
    assert set(loaded.keys()) == {"w", "b"}
    _same(loaded["w"].asnumpy(), data[0].asnumpy())


def test_ndarray_saveload_dtypes(tmp_path):
    fname = str(tmp_path / "nd_dt.bin")
    arrs = {
        "f32": mx.nd.array(np.random.uniform(size=(3,)), dtype=np.float32),
        "i32": mx.nd.array(np.arange(5), dtype=np.int32),
        "u8": mx.nd.array(np.arange(5), dtype=np.uint8),
    }
    nd.save(fname, arrs)
    loaded = nd.load(fname)
    for k, v in arrs.items():
        assert loaded[k].dtype == v.dtype
        _same(loaded[k].asnumpy(), v.asnumpy())


def test_ndarray_creation():
    z = mx.nd.zeros((2, 3))
    _same(z.asnumpy(), np.zeros((2, 3)))
    o = mx.nd.ones((2, 3))
    _same(o.asnumpy(), np.ones((2, 3)))
    f = mx.nd.full((2, 2), 3.14)
    _same(f.asnumpy(), np.full((2, 2), 3.14, np.float32))
    r = mx.nd.arange(0, 10, 2)
    _same(r.asnumpy(), np.arange(0, 10, 2, dtype=np.float32))


def test_ndarray_context():
    a = mx.nd.zeros((2, 2), ctx=mx.cpu(1))
    assert a.context.device_id == 1
    b = a.as_in_context(mx.cpu(0))
    assert b.context.device_id == 0
    assert a.context.device_id == 1


def test_accelerator_context_never_resolves_to_cpu():
    """Without an accelerator mx.tpu()/mx.gpu() raise on use instead of
    landing on a host device; so does a device_id past the last chip
    (which used to alias onto chip id % n)."""
    import jax

    assert all(d.platform == "cpu" for d in jax.local_devices())
    for make in (mx.tpu, mx.gpu):
        for device_id in (0, 5):
            with pytest.raises(mx.MXNetError, match="accelerator"):
                make(device_id).jax_device
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.num_devices("tpu")
    assert mx.num_devices("cpu") == len(jax.local_devices())


def test_ndarray_asscalar_wait():
    a = mx.nd.ones((1,))
    assert float(a) == 1.0
    a.wait_to_read()
    mx.nd.waitall()
