"""Drive the flat C API (native/mxtpu_capi.cc) exactly as an external
binding would — through ctypes with C types only, no Python objects crossing
the boundary. Reference parity target: include/mxnet/c_api.h; the flows
tested here are the ones the reference's R/Python bindings are built from
(NDArray round-trips, registered functions, symbol compose/infer,
executor bind/forward/backward = a real SGD step, iterators, kvstore with a
C updater callback, RecordIO).

The library runs hosted here (loaded into an existing interpreter:
Py_IsInitialized() is true, so it attaches rather than re-initializing);
embedded operation (R / standalone C hosts) takes the Py_InitializeEx path
with PYTHONPATH pointing at the package.
"""

import ctypes
import os
import subprocess

import numpy as np
import pytest

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "mxnet_tpu", "native")
_SO = os.path.join(_DIR, "libmxtpu_capi.so")

mx_uint = ctypes.c_uint
NDHandle = ctypes.c_void_p


@pytest.fixture(scope="module")
def lib():
    # always through make (a no-op on a fresh build): never a leftover .so
    r = subprocess.run(["make", "-C", _DIR, "capi", "-s"],
                       capture_output=True, text=True, timeout=300)
    if r.returncode != 0 or not os.path.exists(_SO):
        pytest.skip(f"cannot build libmxtpu_capi.so: {r.stderr[-400:]}")
    lib = ctypes.CDLL(_SO)
    lib.MXGetLastError.restype = ctypes.c_char_p
    return lib


def check(lib, rc):
    assert rc == 0, lib.MXGetLastError().decode()


def make_ndarray(lib, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    shape = (mx_uint * arr.ndim)(*arr.shape)
    h = NDHandle()
    check(lib, lib.MXNDArrayCreate(shape, arr.ndim, 1, 0, 0,
                                   ctypes.byref(h)))
    check(lib, lib.MXNDArraySyncCopyFromCPU(
        h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), arr.size))
    return h


def read_ndarray(lib, h):
    ndim = mx_uint()
    pdata = ctypes.POINTER(mx_uint)()
    check(lib, lib.MXNDArrayGetShape(h, ctypes.byref(ndim),
                                     ctypes.byref(pdata)))
    shape = tuple(pdata[i] for i in range(ndim.value))
    out = np.empty(shape, np.float32)
    n = int(np.prod(shape)) if shape else 1
    check(lib, lib.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    return out


def test_ndarray_roundtrip_slice_context(lib):
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    h = make_ndarray(lib, a)
    assert np.array_equal(read_ndarray(lib, h), a)

    sl = NDHandle()
    check(lib, lib.MXNDArraySlice(h, 1, 3, ctypes.byref(sl)))
    assert np.array_equal(read_ndarray(lib, sl), a[1:3])

    dt, di = ctypes.c_int(), ctypes.c_int()
    check(lib, lib.MXNDArrayGetContext(h, ctypes.byref(dt), ctypes.byref(di)))
    assert dt.value == 1
    check(lib, lib.MXNDArrayFree(sl))
    check(lib, lib.MXNDArrayFree(h))


def test_ndarray_save_load(lib, tmp_path):
    f = str(tmp_path / "arrays.nd").encode()
    a = np.random.RandomState(0).randn(2, 3).astype(np.float32)
    h = make_ndarray(lib, a)
    keys = (ctypes.c_char_p * 1)(b"w")
    check(lib, lib.MXNDArraySave(f, 1, (NDHandle * 1)(h), keys))

    n = mx_uint()
    arrs = ctypes.POINTER(NDHandle)()
    nn = mx_uint()
    names = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXNDArrayLoad(f, ctypes.byref(n), ctypes.byref(arrs),
                                 ctypes.byref(nn), ctypes.byref(names)))
    assert n.value == 1 and nn.value == 1
    assert names[0] == b"w"
    assert np.allclose(read_ndarray(lib, NDHandle(arrs[0])), a)


def test_functions_list_and_invoke(lib):
    n = mx_uint()
    fns = ctypes.POINTER(ctypes.c_void_p)()
    check(lib, lib.MXListFunctions(ctypes.byref(n), ctypes.byref(fns)))
    assert n.value >= 18  # the reference registers 18 (ndarray.cc:601-652)

    fh = ctypes.c_void_p()
    check(lib, lib.MXGetFunction(b"_plus", ctypes.byref(fh)))
    nuse, nsc, nmut, mask = mx_uint(), mx_uint(), mx_uint(), ctypes.c_int()
    check(lib, lib.MXFuncDescribe(fh, ctypes.byref(nuse), ctypes.byref(nsc),
                                  ctypes.byref(nmut), ctypes.byref(mask)))
    assert (nuse.value, nsc.value, nmut.value) == (2, 0, 1)

    a = make_ndarray(lib, np.ones((2, 2)))
    b = make_ndarray(lib, np.full((2, 2), 3.0))
    out = make_ndarray(lib, np.zeros((2, 2)))
    check(lib, lib.MXFuncInvoke(fh, (NDHandle * 2)(a, b), None,
                                (NDHandle * 1)(out)))
    assert np.allclose(read_ndarray(lib, out), 4.0)


def _make_mlp_symbol(lib):
    """data -> FullyConnected(4) -> relu -> FullyConnected(2) -> softmax,
    built the way bindings do: CreateAtomicSymbol + Compose."""
    def atomic(opname, **params):
        creators_n = mx_uint()
        creators = ctypes.POINTER(ctypes.c_void_p)()
        check(lib, lib.MXSymbolListAtomicSymbolCreators(
            ctypes.byref(creators_n), ctypes.byref(creators)))
        name_p = ctypes.c_char_p()
        # find the creator whose name matches
        for i in range(creators_n.value):
            desc = ctypes.c_char_p()
            nargs = mx_uint()
            an = ctypes.POINTER(ctypes.c_char_p)()
            at = ctypes.POINTER(ctypes.c_char_p)()
            ad = ctypes.POINTER(ctypes.c_char_p)()
            kv = ctypes.c_char_p()
            check(lib, lib.MXSymbolGetAtomicSymbolInfo(
                ctypes.c_void_p(creators[i]), ctypes.byref(name_p),
                ctypes.byref(desc), ctypes.byref(nargs), ctypes.byref(an),
                ctypes.byref(at), ctypes.byref(ad), ctypes.byref(kv)))
            if name_p.value == opname.encode():
                keys = (ctypes.c_char_p * len(params))(
                    *[k.encode() for k in params])
                vals = (ctypes.c_char_p * len(params))(
                    *[str(v).encode() for v in params.values()])
                h = ctypes.c_void_p()
                check(lib, lib.MXSymbolCreateAtomicSymbol(
                    ctypes.c_void_p(creators[i]), len(params), keys, vals,
                    ctypes.byref(h)))
                return h
        raise AssertionError(f"op {opname} not found")

    def compose(sym, name, **inputs):
        keys = (ctypes.c_char_p * len(inputs))(*[k.encode() for k in inputs])
        args = (ctypes.c_void_p * len(inputs))(*inputs.values())
        check(lib, lib.MXSymbolCompose(sym, name.encode(), len(inputs), keys,
                                       args))

    data = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateVariable(b"data", ctypes.byref(data)))
    fc1 = atomic("FullyConnected", num_hidden=4)
    compose(fc1, "fc1", data=data)
    act = atomic("Activation", act_type="relu")
    compose(act, "relu1", data=fc1)
    fc2 = atomic("FullyConnected", num_hidden=2)
    compose(fc2, "fc2", data=act)
    sm = atomic("SoftmaxOutput")
    compose(sm, "softmax", data=fc2)
    return sm


def test_symbol_compose_infer_json(lib):
    sm = _make_mlp_symbol(lib)
    n = mx_uint()
    names = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXSymbolListArguments(sm, ctypes.byref(n),
                                         ctypes.byref(names)))
    args = [names[i].decode() for i in range(n.value)]
    assert args == ["data", "fc1_weight", "fc1_bias", "fc2_weight",
                    "fc2_bias", "softmax_label"]

    js = ctypes.c_char_p()
    check(lib, lib.MXSymbolSaveToJSON(sm, ctypes.byref(js)))
    back = ctypes.c_void_p()
    check(lib, lib.MXSymbolCreateFromJSON(js.value, ctypes.byref(back)))

    # infer shapes for data=(5, 3)
    keys = (ctypes.c_char_p * 1)(b"data")
    ind = (mx_uint * 2)(0, 2)
    shp = (mx_uint * 2)(5, 3)
    in_n, out_n, aux_n = mx_uint(), mx_uint(), mx_uint()
    in_nd = ctypes.POINTER(mx_uint)()
    out_nd = ctypes.POINTER(mx_uint)()
    aux_nd = ctypes.POINTER(mx_uint)()
    in_d = ctypes.POINTER(ctypes.POINTER(mx_uint))()
    out_d = ctypes.POINTER(ctypes.POINTER(mx_uint))()
    aux_d = ctypes.POINTER(ctypes.POINTER(mx_uint))()
    comp = ctypes.c_int()
    check(lib, lib.MXSymbolInferShape(
        sm, 1, keys, ind, shp, ctypes.byref(in_n), ctypes.byref(in_nd),
        ctypes.byref(in_d), ctypes.byref(out_n), ctypes.byref(out_nd),
        ctypes.byref(out_d), ctypes.byref(aux_n), ctypes.byref(aux_nd),
        ctypes.byref(aux_d), ctypes.byref(comp)))
    assert comp.value == 1
    # fc1_weight is argument 1: shape (4, 3)
    assert [in_d[1][j] for j in range(in_nd[1])] == [4, 3]
    # output: (5, 2)
    assert [out_d[0][j] for j in range(out_nd[0])] == [5, 2]


def test_executor_trains_through_c_api(lib):
    """The training FFI: bind with gradients, forward/backward, SGD in C
    caller space — proves an external binding can train (what the R
    training layer needs)."""
    rng = np.random.RandomState(0)
    sm = _make_mlp_symbol(lib)

    X = rng.randn(40, 3).astype(np.float32)
    w_true = rng.randn(3)
    y = (X @ w_true > 0).astype(np.float32)

    shapes = {"data": (8, 3), "fc1_weight": (4, 3), "fc1_bias": (4,),
              "fc2_weight": (2, 4), "fc2_bias": (2,), "softmax_label": (8,)}
    arg_names = list(shapes)
    args, grads, reqs = [], [], []
    for name in arg_names:
        init = (rng.randn(*shapes[name]) * 0.3).astype(np.float32) \
            if "weight" in name else np.zeros(shapes[name], np.float32)
        args.append(make_ndarray(lib, init))
        if name in ("data", "softmax_label"):
            grads.append(None)
            reqs.append(0)  # null
        else:
            grads.append(make_ndarray(lib, np.zeros(shapes[name])))
            reqs.append(1)  # write

    exec_h = ctypes.c_void_p()
    arg_arr = (NDHandle * len(args))(*args)
    grad_arr = (NDHandle * len(args))(*[g or None for g in grads])
    req_arr = (mx_uint * len(args))(*reqs)
    check(lib, lib.MXExecutorBind(sm, 1, 0, len(args), arg_arr, grad_arr,
                                  req_arr, 0, None, ctypes.byref(exec_h)))

    losses = []
    lr = 0.5
    for epoch in range(15):
        correct = 0
        for i in range(0, 40, 8):
            xb, yb = X[i:i + 8], y[i:i + 8]
            check(lib, lib.MXNDArraySyncCopyFromCPU(
                args[0], xb.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                xb.size))
            check(lib, lib.MXNDArraySyncCopyFromCPU(
                args[5], yb.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                yb.size))
            check(lib, lib.MXExecutorForward(exec_h, 1))
            n_out = mx_uint()
            outs = ctypes.POINTER(NDHandle)()
            check(lib, lib.MXExecutorOutputs(exec_h, ctypes.byref(n_out),
                                             ctypes.byref(outs)))
            prob = read_ndarray(lib, NDHandle(outs[0]))
            correct += int(np.sum(np.argmax(prob, 1) == yb))
            check(lib, lib.MXExecutorBackward(exec_h, 0, None))
            # SGD on the C side: w -= lr * g, via the registered functions
            for j, name in enumerate(arg_names):
                if grads[j] is None:
                    continue
                w = read_ndarray(lib, args[j])
                g = read_ndarray(lib, grads[j])
                w2 = (w - lr * g / 8).astype(np.float32)
                check(lib, lib.MXNDArraySyncCopyFromCPU(
                    args[j],
                    w2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    w2.size))
        losses.append(correct / 40.0)
    assert losses[-1] >= 0.9, f"C-API training failed to converge: {losses}"


def test_kvstore_with_c_updater(lib):
    UPDATER = ctypes.CFUNCTYPE(None, ctypes.c_int, NDHandle, NDHandle,
                               ctypes.c_void_p)
    calls = []

    @UPDATER
    def sgd_updater(key, recv, local, _):
        # ctypes delivers handle params as bare ints: re-wrap as c_void_p
        # before passing back (else they truncate to 32-bit C ints)
        recv, local = NDHandle(recv), NDHandle(local)
        g = read_ndarray(lib, recv)
        w = read_ndarray(lib, local)
        w2 = (w - 0.1 * g).astype(np.float32)
        check(lib, lib.MXNDArraySyncCopyFromCPU(
            local, w2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            w2.size))
        calls.append(key)

    kv = ctypes.c_void_p()
    check(lib, lib.MXKVStoreCreate(b"local", ctypes.byref(kv)))
    t = ctypes.c_char_p()
    check(lib, lib.MXKVStoreGetType(kv, ctypes.byref(t)))
    assert t.value == b"local"
    check(lib, lib.MXKVStoreSetUpdater(kv, sgd_updater, None))

    w0 = np.ones((4,), np.float32)
    wh = make_ndarray(lib, w0)
    keys = (ctypes.c_int * 1)(3)
    check(lib, lib.MXKVStoreInit(kv, 1, keys, (NDHandle * 1)(wh)))

    gh = make_ndarray(lib, np.full((4,), 2.0, np.float32))
    check(lib, lib.MXKVStorePush(kv, 1, keys, (NDHandle * 1)(gh), 0))
    out = make_ndarray(lib, np.zeros((4,), np.float32))
    check(lib, lib.MXKVStorePull(kv, 1, keys, (NDHandle * 1)(out), 0))
    assert calls == [3]
    assert np.allclose(read_ndarray(lib, out), 1.0 - 0.1 * 2.0)

    rank, size = ctypes.c_int(), ctypes.c_int()
    check(lib, lib.MXKVStoreGetRank(kv, ctypes.byref(rank)))
    check(lib, lib.MXKVStoreGetGroupSize(kv, ctypes.byref(size)))
    assert (rank.value, size.value) == (0, 1)


def test_data_iter_through_c_api(lib, tmp_path):
    # pack a small RecordIO file through the C API writer...
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from mxnet_tpu import recordio as rio

    rec = str(tmp_path / "it.rec")
    w = ctypes.c_void_p()
    check(lib, lib.MXRecordIOWriterCreate(rec.encode(), ctypes.byref(w)))
    rng = np.random.RandomState(0)
    for i in range(24):
        img = rng.randint(0, 255, (12, 12, 3), np.uint8)
        payload = rio.pack_img(rio.IRHeader(0, float(i % 3), i, 0), img,
                               img_fmt=".jpg")
        check(lib, lib.MXRecordIOWriterWriteRecord(
            w, payload, len(payload)))
    check(lib, lib.MXRecordIOWriterFree(w))

    # ...read one record back through the reader...
    r = ctypes.c_void_p()
    check(lib, lib.MXRecordIOReaderCreate(rec.encode(), ctypes.byref(r)))
    buf = ctypes.c_char_p()
    size = ctypes.c_size_t()
    check(lib, lib.MXRecordIOReaderReadRecord(r, ctypes.byref(buf),
                                              ctypes.byref(size)))
    assert size.value > 0
    check(lib, lib.MXRecordIOReaderFree(r))

    # ...and drive ImageRecordIter over it
    n = mx_uint()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    check(lib, lib.MXListDataIters(ctypes.byref(n), ctypes.byref(creators)))
    target = None
    for i in range(n.value):
        name = ctypes.c_char_p()
        desc = ctypes.c_char_p()
        na = mx_uint()
        an = ctypes.POINTER(ctypes.c_char_p)()
        at = ctypes.POINTER(ctypes.c_char_p)()
        ad = ctypes.POINTER(ctypes.c_char_p)()
        check(lib, lib.MXDataIterGetIterInfo(
            ctypes.c_void_p(creators[i]), ctypes.byref(name),
            ctypes.byref(desc), ctypes.byref(na), ctypes.byref(an),
            ctypes.byref(at), ctypes.byref(ad)))
        if name.value == b"ImageRecordIter":
            target = ctypes.c_void_p(creators[i])
    assert target is not None

    keys = [b"path_imgrec", b"data_shape", b"batch_size"]
    vals = [rec.encode(), b"(3, 10, 10)", b"8"]
    it = ctypes.c_void_p()
    check(lib, lib.MXDataIterCreateIter(
        target, len(keys), (ctypes.c_char_p * 3)(*keys),
        (ctypes.c_char_p * 3)(*vals), ctypes.byref(it)))

    total, batches = 0, 0
    has = ctypes.c_int(1)
    while True:
        check(lib, lib.MXDataIterNext(it, ctypes.byref(has)))
        if not has.value:
            break
        data_h, label_h = NDHandle(), NDHandle()
        check(lib, lib.MXDataIterGetData(it, ctypes.byref(data_h)))
        check(lib, lib.MXDataIterGetLabel(it, ctypes.byref(label_h)))
        d = read_ndarray(lib, data_h)
        lab = read_ndarray(lib, label_h)
        assert d.shape == (8, 3, 10, 10)
        assert lab.shape == (8,)
        pad = ctypes.c_int()
        check(lib, lib.MXDataIterGetPadNum(it, ctypes.byref(pad)))
        total += 8 - pad.value
        batches += 1
    assert total == 24 and batches == 3
    check(lib, lib.MXDataIterBeforeFirst(it))
    check(lib, lib.MXDataIterNext(it, ctypes.byref(has)))
    assert has.value == 1


def test_random_seed_and_error_path(lib):
    check(lib, lib.MXRandomSeed(7))
    # error path: bad op name through atomic creator is caught and reported
    h = ctypes.c_void_p()
    rc = lib.MXSymbolCreateFromJSON(b"{not json", ctypes.byref(h))
    assert rc == -1
    assert len(lib.MXGetLastError()) > 0


def test_ndarray_raw_bytes_roundtrip(lib):
    a = np.random.RandomState(1).randn(3, 5).astype(np.float32)
    h = make_ndarray(lib, a)
    size = ctypes.c_size_t()
    buf = ctypes.POINTER(ctypes.c_char)()
    check(lib, lib.MXNDArraySaveRawBytes(h, ctypes.byref(size),
                                         ctypes.byref(buf)))
    assert size.value > a.nbytes
    raw = ctypes.string_at(buf, size.value)
    h2 = NDHandle()
    check(lib, lib.MXNDArrayLoadFromRawBytes(raw, len(raw),
                                             ctypes.byref(h2)))
    assert np.array_equal(read_ndarray(lib, h2), a)


def test_symbol_internals_and_output_slice(lib):
    sm = _make_mlp_symbol(lib)
    internals = ctypes.c_void_p()
    check(lib, lib.MXSymbolGetInternals(sm, ctypes.byref(internals)))
    n = mx_uint()
    names = ctypes.POINTER(ctypes.c_char_p)()
    check(lib, lib.MXSymbolListOutputs(internals, ctypes.byref(n),
                                       ctypes.byref(names)))
    outs = [names[i].decode() for i in range(n.value)]
    assert "fc1_output" in outs
    idx = outs.index("fc1_output")
    head = ctypes.c_void_p()
    check(lib, lib.MXSymbolGetOutput(internals, idx, ctypes.byref(head)))
    n2 = mx_uint()
    check(lib, lib.MXSymbolListOutputs(head, ctypes.byref(n2),
                                       ctypes.byref(names)))
    assert n2.value == 1 and names[0] == b"fc1_output"


def test_wait_and_shutdown_and_getdata(lib):
    a = make_ndarray(lib, np.arange(6, dtype=np.float32).reshape(2, 3))
    check(lib, lib.MXNDArrayWaitToRead(a))
    check(lib, lib.MXNDArrayWaitToWrite(a))
    check(lib, lib.MXNDArrayWaitAll())
    p = ctypes.POINTER(ctypes.c_float)()
    check(lib, lib.MXNDArrayGetData(a, ctypes.byref(p)))
    assert [p[i] for i in range(6)] == [0, 1, 2, 3, 4, 5]
    check(lib, lib.MXNotifyShutdown())  # no-op, must not invalidate state
    b = make_ndarray(lib, np.ones((2, 2)))
    assert np.allclose(read_ndarray(lib, b), 1.0)
