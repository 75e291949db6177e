"""R-binding shim test (reference: R-package/): the shim exposes the predict
ABI through the .C calling convention (plain pointers, id-registry handles),
so it can be verified without an R installation by calling it via ctypes
exactly the way R's .C() would."""

import ctypes
import os
import subprocess

import numpy as np
import pytest

import mxnet_tpu.symbol as S
from mxnet_tpu import ndarray as nd
from mxnet_tpu.predictor import Predictor

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def shim(tmp_path_factory):
    so = str(tmp_path_factory.mktemp("rshim") / "mxtpu_rshim.so")
    try:
        subprocess.run(
            ["g++", "-O1", "-std=c++17", "-shared", "-fPIC",
             os.path.join(ROOT, "R-package", "src", "mxtpu_shim.cc"),
             os.path.join(ROOT, "mxnet_tpu", "native", "mxtpu_predict.cc"),
             "-lz", "-o", so], check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        pytest.fail(f"shim build failed: {e.stderr.decode()[-2000:]}")
    return ctypes.CDLL(so)


def _int(v):
    return ctypes.byref(ctypes.c_int(v))


def test_r_shim_roundtrip(shim, tmp_path):
    x = S.Variable("data")
    out = S.SoftmaxOutput(S.FullyConnected(data=x, num_hidden=3, name="fc"),
                          name="softmax")
    rng = np.random.RandomState(0)
    params = {"fc_weight": nd.array(rng.randn(3, 5).astype(np.float32)),
              "fc_bias": nd.array(rng.randn(3).astype(np.float32))}
    pred = Predictor(out, params, {}, input_names=["data"])
    inp = rng.randn(2, 5).astype(np.float32)
    pred.forward(data=inp)
    expected = pred.get_output(0)
    bundle = str(tmp_path / "m.mxtpu")
    pred.export(bundle)

    # create — .C passes scalars as pointers, strings as char**
    path = ctypes.c_char_p(bundle.encode())
    pid, status = ctypes.c_int(0), ctypes.c_int(0)
    shim.mxtpu_r_create(ctypes.byref(path), ctypes.byref(pid),
                        ctypes.byref(status))
    assert status.value == 0, status.value
    assert pid.value > 0

    # set_input with R's doubles
    data = inp.astype(np.float64)
    name = ctypes.c_char_p(b"data")
    shape = (ctypes.c_int * 2)(2, 5)
    shim.mxtpu_r_set_input(
        ctypes.byref(ctypes.c_int(pid.value)), ctypes.byref(name),
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), shape,
        _int(2), ctypes.byref(status))
    assert status.value == 0

    shim.mxtpu_r_forward(ctypes.byref(ctypes.c_int(pid.value)),
                         ctypes.byref(status))
    assert status.value == 0

    n = ctypes.c_int(0)
    shim.mxtpu_r_num_outputs(ctypes.byref(ctypes.c_int(pid.value)),
                             ctypes.byref(n))
    assert n.value == 1

    ndim = ctypes.c_int(0)
    oshape = (ctypes.c_int * 8)()
    shim.mxtpu_r_output_shape(ctypes.byref(ctypes.c_int(pid.value)),
                              _int(0), ctypes.byref(ndim), oshape)
    assert ndim.value == 2
    assert tuple(oshape[:2]) == (2, 3)

    out_buf = np.zeros(6, np.float64)
    shim.mxtpu_r_get_output(
        ctypes.byref(ctypes.c_int(pid.value)), _int(0),
        out_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _int(6), ctypes.byref(status))
    assert status.value == 0
    np.testing.assert_allclose(out_buf.reshape(2, 3), expected,
                               atol=2e-4, rtol=1e-3)

    shim.mxtpu_r_free(ctypes.byref(ctypes.c_int(pid.value)))
    # bad handle after free
    shim.mxtpu_r_forward(ctypes.byref(ctypes.c_int(pid.value)),
                         ctypes.byref(status))
    assert status.value == -2


def test_r_shim_bad_bundle(shim, tmp_path):
    bad = str(tmp_path / "nope.mxtpu")
    path = ctypes.c_char_p(bad.encode())
    pid, status = ctypes.c_int(0), ctypes.c_int(0)
    shim.mxtpu_r_create(ctypes.byref(path), ctypes.byref(pid),
                        ctypes.byref(status))
    assert status.value == -1
    buf = ctypes.create_string_buffer(512)
    msg = ctypes.cast(buf, ctypes.c_char_p)
    shim.mxtpu_r_last_error(ctypes.byref(msg), _int(512))
    assert buf.value  # error message populated


def _r_call(shim, pid, fn, *args):
    status = ctypes.c_int(0)
    getattr(shim, fn)(ctypes.byref(ctypes.c_int(pid)), *args,
                      ctypes.byref(status))
    assert status.value == 0, f"{fn} failed: {status.value}"


def test_r_shim_lenet_batched_predict(shim, tmp_path):
    """Conv-net (LeNet) bundle through the shim, driven exactly the way
    R's mx.pred.predict does it: batches over the leading dim with a
    padded final batch, outputs de-padded and stacked — parity vs the
    Python predictor (reference capability: R-package/R/model.R
    predict.MXFeedForwardModel)."""
    x = S.Variable("data")
    net = S.Convolution(data=x, kernel=(3, 3), pad=(1, 1), num_filter=8,
                        name="c1")
    net = S.Activation(data=net, act_type="relu", name="a1")
    net = S.Pooling(data=net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                    name="p1")
    net = S.Flatten(data=net, name="flat")
    net = S.FullyConnected(data=net, num_hidden=10, name="fc")
    out = S.SoftmaxOutput(data=net, name="softmax")

    rng = np.random.RandomState(1)
    params = {
        "c1_weight": nd.array(rng.randn(8, 1, 3, 3).astype(np.float32) * 0.3),
        "c1_bias": nd.array(np.zeros(8, np.float32)),
        "fc_weight": nd.array(rng.randn(10, 8 * 4 * 4).astype(np.float32) * 0.1),
        "fc_bias": nd.array(np.zeros(10, np.float32)),
    }
    pred = Predictor(out, params, {}, input_names=["data"])
    X = rng.randn(10, 1, 8, 8).astype(np.float32)  # 10 samples, batch 4 -> pad
    bundle = str(tmp_path / "lenet.mxtpu")
    pred.export(bundle)

    # expected from the Python predictor, full batch
    pred.forward(data=X)
    expected = pred.get_output(0)

    path = ctypes.c_char_p(bundle.encode())
    pid, status = ctypes.c_int(0), ctypes.c_int(0)
    shim.mxtpu_r_create(ctypes.byref(path), ctypes.byref(pid),
                        ctypes.byref(status))
    assert status.value == 0

    batch, n = 4, len(X)
    outs = []
    i = 0
    while i < n:
        take = min(batch, n - i)
        chunk = X[i:i + take]
        if take < batch:  # pad the tail like mx.pred.predict
            chunk = np.concatenate(
                [chunk, np.zeros((batch - take,) + X.shape[1:], X.dtype)])
        data = chunk.astype(np.float64)
        name = ctypes.c_char_p(b"data")
        shape = (ctypes.c_int * 4)(*chunk.shape)
        _r_call(shim, pid.value, "mxtpu_r_set_input", ctypes.byref(name),
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), shape,
                _int(4))
        _r_call(shim, pid.value, "mxtpu_r_forward")
        buf = np.zeros(batch * 10, np.float64)
        _r_call(shim, pid.value, "mxtpu_r_get_output", _int(0),
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                _int(batch * 10))
        outs.append(buf.reshape(batch, 10)[:take])
        i += take
    got = np.concatenate(outs)
    np.testing.assert_allclose(got, expected, atol=2e-4, rtol=1e-3)
    shim.mxtpu_r_free(ctypes.byref(ctypes.c_int(pid.value)))


# ---------------------------------------------------------------------------
# Training shim (R-package/src/mxtpu_r_train.cc over the flat C API):
# exercised through ctypes with R's exact .C convention — every argument a
# pointer — so the R training layer (R-package/R/mxtpu_train.R) is verified
# end-to-end without an R installation. When Rscript exists, the demo
# R script runs for real (test_r_train_demo_under_rscript).

def _p_int(*vals):
    return (ctypes.c_int * len(vals))(*vals)


def _p_str(*strs):
    return (ctypes.c_char_p * len(strs))(*[s.encode() for s in strs])


@pytest.fixture(scope="module")
def train_shim():
    capi_dir = os.path.join(ROOT, "mxnet_tpu", "native")
    subprocess.run(["make", "-C", capi_dir, "capi", "-s"],
                   capture_output=True, timeout=300)
    so = os.path.join(ROOT, "R-package", "src", "libmxtpu_r_train.so")
    src = os.path.join(ROOT, "R-package", "src", "mxtpu_r_train.cc")
    if os.path.exists(so) and os.path.getmtime(so) < os.path.getmtime(src):
        os.remove(so)  # stale build: shim source is newer
    if not os.path.exists(so):
        r = subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-shared",
             os.path.join(ROOT, "R-package", "src", "mxtpu_r_train.cc"),
             "-o", so, "-L" + capi_dir, "-lmxtpu_capi",
             "-Wl,-rpath," + os.path.abspath(capi_dir)],
            capture_output=True, text=True)
        if not os.path.exists(so):
            pytest.skip(f"cannot build train shim: {r.stderr[-500:]}")
    return ctypes.CDLL(so)


def _st(lib, r, status):
    if status[0] != 0:
        buf = ctypes.create_string_buffer(2048)
        pbuf = ctypes.cast(
            ctypes.pointer(ctypes.c_char_p(ctypes.addressof(buf))),
            ctypes.POINTER(ctypes.c_char_p))
        lib.mxr_last_error(pbuf, _p_int(2048))
        raise AssertionError(buf.value.decode(errors="replace"))
    return r


def test_r_train_shim_trains_mlp(train_shim):
    lib = train_shim

    def nd_create(shape):
        out, st = _p_int(0), _p_int(1)
        lib.mxr_nd_create(_p_int(*shape), _p_int(len(shape)), out, st)
        _st(lib, None, st)
        return out[0]

    def nd_set(h, arr):
        arr = np.ascontiguousarray(arr, np.float64).ravel()
        st = _p_int(1)
        lib.mxr_nd_set(_p_int(h),
                       arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                       _p_int(arr.size), st)
        _st(lib, None, st)

    def nd_get(h, n):
        buf = np.empty(n, np.float64)
        st = _p_int(1)
        lib.mxr_nd_get(_p_int(h),
                       buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                       _p_int(n), st)
        _st(lib, None, st)
        return buf

    def sym_variable(name):
        out, st = _p_int(0), _p_int(1)
        lib.mxr_sym_variable(_p_str(name), out, st)
        _st(lib, None, st)
        return out[0]

    def sym_atomic(opname, **params):
        out, st = _p_int(0), _p_int(1)
        keys = _p_str(*params.keys())
        vals = _p_str(*[str(v) for v in params.values()])
        lib.mxr_sym_atomic(_p_str(opname), _p_int(len(params)), keys, vals,
                           out, st)
        _st(lib, None, st)
        return out[0]

    def sym_compose(sym, name, **inputs):
        st = _p_int(1)
        lib.mxr_sym_compose(_p_int(sym), _p_str(name),
                            _p_int(len(inputs)), _p_str(*inputs.keys()),
                            _p_int(*inputs.values()), st)
        _st(lib, None, st)

    # the same MLP the R demo builds
    data = sym_variable("data")
    fc1 = sym_atomic("FullyConnected", num_hidden=8)
    sym_compose(fc1, "fc1", data=data)
    act = sym_atomic("Activation", act_type="relu")
    sym_compose(act, "relu1", data=fc1)
    fc2 = sym_atomic("FullyConnected", num_hidden=2)
    sym_compose(fc2, "fc2", data=act)
    sm = sym_atomic("SoftmaxOutput")
    sym_compose(sm, "softmax", data=fc2)

    # arguments via the '\n'-joined string return
    buf = ctypes.create_string_buffer(1 << 14)
    pbuf = ctypes.cast(ctypes.pointer(ctypes.c_char_p(ctypes.addressof(buf))),
                       ctypes.POINTER(ctypes.c_char_p))
    st = _p_int(1)
    lib.mxr_sym_arguments(_p_int(sm), pbuf, _p_int(1 << 14), st)
    _st(lib, None, st)
    arg_names = buf.value.decode().split("\n")
    assert arg_names == ["data", "fc1_weight", "fc1_bias", "fc2_weight",
                         "fc2_bias", "softmax_label"]

    # infer shapes for batch 16, 4 features
    max_args = 256
    n_args, n_aux = _p_int(0), _p_int(0)
    arg_ndims = (ctypes.c_int * max_args)()
    arg_shapes = (ctypes.c_int * (max_args * 8))()
    aux_ndims = (ctypes.c_int * max_args)()
    aux_shapes = (ctypes.c_int * (max_args * 8))()
    st = _p_int(1)
    lib.mxr_sym_infer_shapes(_p_int(sm), _p_str("data"), _p_int(16, 4),
                             _p_int(2), _p_int(max_args), n_args, arg_ndims,
                             arg_shapes, n_aux, aux_ndims, aux_shapes, st)
    _st(lib, None, st)
    assert n_args[0] == 6
    shapes = []
    for i in range(n_args[0]):
        shapes.append([arg_shapes[i * 8 + j] for j in range(arg_ndims[i])])
    assert shapes[1] == [8, 4]  # fc1_weight

    # allocate, bind, train
    rng = np.random.RandomState(0)
    X = rng.randn(64, 4).astype(np.float64)
    w_true = rng.randn(4)
    y = (X @ w_true > 0).astype(np.float64)

    args, grads, reqs, inits = [], [], [], {}
    for i, name in enumerate(arg_names):
        h = nd_create(shapes[i])
        args.append(h)
        if name == "data" or "label" in name:
            grads.append(0)
            reqs.append(0)
        else:
            grads.append(nd_create(shapes[i]))
            reqs.append(1)
            init = (rng.randn(*shapes[i]) * 0.3 if "weight" in name
                    else np.zeros(shapes[i]))
            nd_set(h, init)

    ex, st = _p_int(0), _p_int(1)
    lib.mxr_exec_bind(_p_int(sm), _p_int(len(args)), _p_int(*args),
                      _p_int(*grads), _p_int(*reqs), _p_int(0), _p_int(0),
                      ex, st)
    _st(lib, None, st)

    lr = 0.5
    acc = 0.0
    for _ in range(12):
        correct = 0
        for s in range(0, 64, 16):
            xb, yb = X[s:s + 16], y[s:s + 16]
            nd_set(args[0], xb)
            nd_set(args[5], yb)
            st = _p_int(1)
            lib.mxr_exec_forward(ex, _p_int(1), st)
            _st(lib, None, st)
            outs = (ctypes.c_int * 64)()
            n_out = _p_int(0)
            st = _p_int(1)
            lib.mxr_exec_outputs(ex, outs, n_out, st)
            _st(lib, None, st)
            prob = nd_get(outs[0], 16 * 2).reshape(16, 2)
            correct += int(np.sum(np.argmax(prob, 1) == yb))
            st = _p_int(1)
            lib.mxr_exec_backward(ex, st)
            _st(lib, None, st)
            for i, name in enumerate(arg_names):
                if reqs[i] == 0:
                    continue
                n = int(np.prod(shapes[i]))
                w = nd_get(args[i], n)
                g = nd_get(grads[i], n)
                nd_set(args[i], w - lr * g / 16)
        acc = correct / 64.0
    assert acc >= 0.9, f"R train shim failed to converge: {acc}"


def test_r_train_demo_under_rscript(train_shim):
    import shutil

    if shutil.which("Rscript") is None:
        pytest.skip("Rscript not installed in this image")
    demo = os.path.join(ROOT, "R-package", "demo", "lenet_train.R")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(ROOT))
    r = subprocess.run(["Rscript", demo], capture_output=True, text=True,
                       timeout=1200, env=env,
                       cwd=os.path.join(ROOT, "R-package"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "train accuracy" in (r.stdout + r.stderr)


# ---------------------------------------------------------------------------
# Round-5 widening: checkpoint save/load through the
# shim (format parity with Python), kvstore surface, and the registered-
# function route the R optimizer layer uses — each driven with the exact
# .C pointer convention the new R files (model.R/kvstore.R/optimizer.R)
# emit.

def _shim_nd_helpers(lib):
    def nd_create(shape):
        out, st = _p_int(0), _p_int(1)
        lib.mxr_nd_create(_p_int(*shape), _p_int(len(shape)), out, st)
        _st(lib, None, st)
        return out[0]

    def nd_set(h, arr):
        arr = np.ascontiguousarray(arr, np.float64).ravel()
        st = _p_int(1)
        lib.mxr_nd_set(_p_int(h),
                       arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                       _p_int(arr.size), st)
        _st(lib, None, st)

    def nd_get(h, n):
        buf = np.empty(n, np.float64)
        st = _p_int(1)
        lib.mxr_nd_get(_p_int(h),
                       buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                       _p_int(n), st)
        _st(lib, None, st)
        return buf

    return nd_create, nd_set, nd_get


def test_r_shim_nd_save_load_python_roundtrip(train_shim, tmp_path):
    """mx.model.save writes the SAME container Python mx.nd.load reads —
    and vice versa (reference parity: R-package/R/model.R mx.model.save /
    mxnet_tpu/model.py:63-85)."""
    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)
    rng = np.random.RandomState(3)

    # R -> Python
    w = rng.randn(4, 3).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    hw, hb = nd_create([4, 3]), nd_create([3])
    nd_set(hw, w)
    nd_set(hb, b)
    fname = str(tmp_path / "rsave.params")
    st = _p_int(1)
    lib.mxr_nd_save(_p_str(fname), _p_int(2), _p_int(hw, hb),
                    _p_str("arg:fc_weight", "arg:fc_bias"), st)
    _st(lib, None, st)
    loaded = nd.load(fname)
    assert set(loaded) == {"arg:fc_weight", "arg:fc_bias"}
    np.testing.assert_allclose(loaded["arg:fc_weight"].asnumpy(), w,
                               atol=1e-6)
    np.testing.assert_allclose(loaded["arg:fc_bias"].asnumpy(), b,
                               atol=1e-6)

    # Python -> R
    fname2 = str(tmp_path / "pysave.params")
    nd.save(fname2, {"aux:mean": nd.array(w), "arg:scale": nd.array(b)})
    n_out = _p_int(0)
    ids = (ctypes.c_int * 16)()
    buf = ctypes.create_string_buffer(1 << 12)
    pbuf = ctypes.cast(ctypes.pointer(ctypes.c_char_p(ctypes.addressof(buf))),
                       ctypes.POINTER(ctypes.c_char_p))
    st = _p_int(1)
    lib.mxr_nd_load(_p_str(fname2), _p_int(16), n_out, ids, pbuf,
                    _p_int(1 << 12), st)
    _st(lib, None, st)
    assert n_out[0] == 2
    names = buf.value.decode().split("\n")
    by_name = {names[i]: ids[i] for i in range(2)}
    np.testing.assert_allclose(
        nd_get(by_name["aux:mean"], 12).reshape(4, 3), w, atol=1e-6)
    np.testing.assert_allclose(nd_get(by_name["arg:scale"], 3), b,
                               atol=1e-6)


def test_r_shim_func_invoke_optimizer_math(train_shim):
    """The R optimizer's update math runs through MXFuncInvoke on
    runtime-resident arrays (optimizer.R .mxr.func): verify the exact SGD
    momentum sequence model.R drives gives the numpy closed form."""
    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)
    rng = np.random.RandomState(7)
    w = rng.randn(6).astype(np.float64)
    g = rng.randn(6).astype(np.float64)
    mom = np.zeros(6)
    lr, momentum, rescale = 0.5, 0.9, 1 / 16.0

    hw, hg = nd_create([6]), nd_create([6])
    hmom, hscratch = nd_create([6]), nd_create([6])
    nd_set(hw, w)
    nd_set(hg, g)
    nd_set(hmom, mom)

    def func(name, use, scalars, mutate):
        st = _p_int(1)
        sc = (ctypes.c_double * max(1, len(scalars)))(*scalars)
        lib.mxr_func_invoke(_p_str(name), _p_int(len(use)), _p_int(*use),
                            _p_int(len(scalars)), sc, _p_int(1),
                            _p_int(mutate), st)
        _st(lib, None, st)

    for _ in range(3):  # momentum accumulates over steps
        # scratch = lr * rescale * grad ; mom = momentum*mom - scratch
        func("_mul_scalar", [hg], [rescale], hscratch)
        func("_mul_scalar", [hscratch], [lr], hscratch)
        func("_mul_scalar", [hmom], [momentum], hmom)
        func("_minus", [hmom, hscratch], [], hmom)
        func("_plus", [hw, hmom], [], hw)
        mom = momentum * mom - lr * (rescale * g)
        w = w + mom

    np.testing.assert_allclose(nd_get(hw, 6), w, atol=1e-5)
    np.testing.assert_allclose(nd_get(hmom, 6), mom, atol=1e-5)

    # _set_value with no use-vars: optimizer.R's mx.nd.zeros.like fill
    func("_set_value", [], [0.0], hscratch)
    np.testing.assert_allclose(nd_get(hscratch, 6), np.zeros(6), atol=0)


def test_r_shim_kvstore(train_shim):
    """mx.kv.* surface: init/push/pull aggregation on a local store plus
    rank/size/barrier (reference: R-package/R/kvstore.R over MXKVStore*)."""
    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)

    kv, st = _p_int(0), _p_int(1)
    lib.mxr_kv_create(_p_str("local"), kv, st)
    _st(lib, None, st)

    h0 = nd_create([4])
    nd_set(h0, np.arange(4.0))
    st = _p_int(1)
    lib.mxr_kv_init(_p_int(kv[0]), _p_int(1), _p_int(3), _p_int(h0), st)
    _st(lib, None, st)

    # one push with the key repeated: the C API groups repeated keys and
    # the store merges (sums) the group — reference GroupKVPairs semantics
    ha, hb, hout = nd_create([4]), nd_create([4]), nd_create([4])
    nd_set(ha, np.ones(4))
    nd_set(hb, 2 * np.ones(4))
    st = _p_int(1)
    lib.mxr_kv_push(_p_int(kv[0]), _p_int(2), _p_int(3, 3), _p_int(ha, hb),
                    _p_int(0), st)
    _st(lib, None, st)
    st = _p_int(1)
    lib.mxr_kv_pull(_p_int(kv[0]), _p_int(1), _p_int(3), _p_int(hout),
                    _p_int(0), st)
    _st(lib, None, st)
    np.testing.assert_allclose(nd_get(hout, 4), 3 * np.ones(4), atol=1e-6)

    rank, size = _p_int(-1), _p_int(-1)
    st = _p_int(1)
    lib.mxr_kv_rank(_p_int(kv[0]), rank, st)
    _st(lib, None, st)
    st = _p_int(1)
    lib.mxr_kv_size(_p_int(kv[0]), size, st)
    _st(lib, None, st)
    assert rank[0] == 0 and size[0] == 1
    st = _p_int(1)
    lib.mxr_kv_barrier(_p_int(kv[0]), st)
    _st(lib, None, st)
    st = _p_int(1)
    lib.mxr_kv_free(_p_int(kv[0]), st)
    _st(lib, None, st)


def test_r_shim_load_bind_predict_sequence(train_shim, tmp_path):
    """The exact call sequence R's mx.model.load -> mx.model.bind ->
    mx.model.predict emits (model.R): load a Python-written checkpoint
    through the shim, bind an executor over the LOADED parameter handles
    (no grad buffers), forward a batch, and match the Python executor's
    output."""
    import jax.numpy as jnp

    import mxnet_tpu as mx

    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)
    rng = np.random.RandomState(9)

    # train-free checkpoint written by the PYTHON layer
    net = S.SoftmaxOutput(S.FullyConnected(
        data=S.Variable("data"), num_hidden=3, name="fc"), name="softmax")
    w = rng.randn(3, 5).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    from mxnet_tpu.model import save_checkpoint

    save_checkpoint(str(tmp_path / "m"), 1, net,
                    {"fc_weight": nd.array(w), "fc_bias": nd.array(b)}, {})

    # R sequence 1: symbol from the json file
    with open(str(tmp_path / "m-symbol.json")) as f:
        js = f.read()
    sym_id, st = _p_int(0), _p_int(1)
    lib.mxr_sym_fromjson(_p_str(js), sym_id, st)
    _st(lib, None, st)

    # R sequence 2: params from the container
    n_out = _p_int(0)
    ids = (ctypes.c_int * 16)()
    buf = ctypes.create_string_buffer(1 << 12)
    pbuf = ctypes.cast(ctypes.pointer(ctypes.c_char_p(ctypes.addressof(buf))),
                       ctypes.POINTER(ctypes.c_char_p))
    st = _p_int(1)
    lib.mxr_nd_load(_p_str(str(tmp_path / "m-0001.params")), _p_int(16),
                    n_out, ids, pbuf, _p_int(1 << 12), st)
    _st(lib, None, st)
    by_name = {buf.value.decode().split("\n")[i]: ids[i]
               for i in range(n_out[0])}

    # R sequence 3: bind with loaded ids + fresh zero data/label slots,
    # reqs all 0, grads all 0 (mx.model.bind)
    h_data, h_label = nd_create([4, 5]), nd_create([4])
    args = [h_data, by_name["arg:fc_weight"], by_name["arg:fc_bias"],
            h_label]
    ex, st = _p_int(0), _p_int(1)
    lib.mxr_exec_bind(_p_int(sym_id[0]), _p_int(4), _p_int(*args),
                      _p_int(0, 0, 0, 0), _p_int(0, 0, 0, 0),
                      _p_int(0), _p_int(0), ex, st)
    _st(lib, None, st)

    # R sequence 4: predict
    X = rng.randn(4, 5).astype(np.float64)
    nd_set(h_data, X)
    st = _p_int(1)
    lib.mxr_exec_forward(ex, _p_int(0), st)
    _st(lib, None, st)
    outs = (ctypes.c_int * 64)()
    n = _p_int(0)
    st = _p_int(1)
    lib.mxr_exec_outputs(ex, outs, n, st)
    _st(lib, None, st)
    got = nd_get(outs[0], 4 * 3).reshape(4, 3)

    logits = X.astype(np.float32) @ w.T + b
    e = np.exp(logits - logits.max(1, keepdims=True))
    expected = e / e.sum(1, keepdims=True)
    np.testing.assert_allclose(got, expected, atol=2e-4, rtol=1e-3)


def _shim_func_invoke(lib):
    """The exact .C("mxr_func_invoke") call shape every R math wrapper
    makes (ndarray.R .mxr.func): name, use-var handles, scalars, one
    mutate handle."""
    def func(name, use, scalars, mutate):
        st = _p_int(1)
        sc = (ctypes.c_double * max(1, len(scalars)))(*scalars)
        lib.mxr_func_invoke(_p_str(name), _p_int(len(use)),
                            _p_int(*(use or [0])), _p_int(len(scalars)), sc,
                            _p_int(1), _p_int(mutate), st)
        _st(lib, None, st)
    return func


def test_r_shim_random_layer(train_shim):
    """random.R's device-RNG route: mxr_random_seed + the registered
    sampler functions mutate runtime arrays (R never generates numbers).
    Seeding must make the sequence reproducible, like the reference's
    mx.set.seed contract (R-package/R/random.R examples)."""
    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)
    func = _shim_func_invoke(lib)

    def seed(s):
        st = _p_int(1)
        lib.mxr_random_seed(_p_int(s), st)
        _st(lib, None, st)

    h = nd_create([64])
    seed(11)
    func("_random_uniform", [], [0.0, 1.0], h)
    first = nd_get(h, 64)
    assert 0.0 <= first.min() and first.max() < 1.0
    func("_random_uniform", [], [0.0, 1.0], h)
    second = nd_get(h, 64)
    assert not np.allclose(first, second)  # stream advances
    seed(11)
    func("_random_uniform", [], [0.0, 1.0], h)
    np.testing.assert_allclose(nd_get(h, 64), first)  # reseed replays

    # gaussian with mean/sd scalars lands in the right distribution
    hg = nd_create([4096])
    seed(5)
    func("_random_gaussian", [], [3.0, 0.5], hg)
    draw = nd_get(hg, 4096)
    assert abs(draw.mean() - 3.0) < 0.05
    assert abs(draw.std() - 0.5) < 0.05

    # bounds ride the scalar slots: uniform in [10, 12)
    seed(6)
    func("_random_uniform", [], [10.0, 12.0], h)
    u = nd_get(h, 64)
    assert 10.0 <= u.min() and u.max() < 12.0


def test_r_shim_ndarray_math_surface(train_shim):
    """ndarray.R's Ops group generics and math helpers: every call the R
    layer makes (fresh out ndarray + mxr_func_invoke) verified against
    numpy, including the reversed scalar forms and the dot/clip/unary
    registered functions."""
    lib = train_shim
    nd_create, nd_set, nd_get = _shim_nd_helpers(lib)
    func = _shim_func_invoke(lib)

    rng = np.random.RandomState(2)
    a = rng.rand(3, 4) + 0.5
    b = rng.rand(3, 4) + 0.5
    ha, hb, hout = nd_create([3, 4]), nd_create([3, 4]), nd_create([3, 4])
    nd_set(ha, a)
    nd_set(hb, b)

    # Ops.mxtpu.ndarray: nd (+,-,*,/) nd — fresh out per expression
    for fname, ref in [("_plus", a + b), ("_minus", a - b),
                       ("_mul", a * b), ("_div", a / b)]:
        func(fname, [ha, hb], [], hout)
        np.testing.assert_allclose(nd_get(hout, 12).reshape(3, 4), ref,
                                   rtol=1e-6)

    # scalar forms incl. the reversed ones (scalar - nd, scalar / nd)
    for fname, sc, ref in [("_plus_scalar", 2.5, a + 2.5),
                           ("_minus_scalar", 2.5, a - 2.5),
                           ("_mul_scalar", 2.5, a * 2.5),
                           ("_div_scalar", 2.5, a / 2.5),
                           ("_rminus_scalar", 2.5, 2.5 - a),
                           ("_rdiv_scalar", 2.5, 2.5 / a)]:
        func(fname, [ha], [sc], hout)
        np.testing.assert_allclose(nd_get(hout, 12).reshape(3, 4), ref,
                                   rtol=1e-6)

    # mx.nd.clip's two scalar bounds
    func("clip", [ha], [0.6, 1.1], hout)
    np.testing.assert_allclose(nd_get(hout, 12).reshape(3, 4),
                               np.clip(a, 0.6, 1.1), rtol=1e-6)

    # unary family
    for fname, ref in [("square", a * a), ("sqrt", np.sqrt(a)),
                       ("exp", np.exp(a)), ("log", np.log(a))]:
        func(fname, [ha], [], hout)
        np.testing.assert_allclose(nd_get(hout, 12).reshape(3, 4), ref,
                                   rtol=1e-5)

    # mx.nd.norm reduces to one element
    hn = nd_create([1])
    func("norm", [ha], [], hn)
    np.testing.assert_allclose(nd_get(hn, 1)[0], np.linalg.norm(a),
                               rtol=1e-5)

    # mx.nd.dot shape logic: (3,4) x (4,2) -> (3,2)
    c = rng.rand(4, 2)
    hc, hd = nd_create([4, 2]), nd_create([3, 2])
    nd_set(hc, c)
    func("dot", [ha, hc], [], hd)
    np.testing.assert_allclose(nd_get(hd, 6).reshape(3, 2), a @ c,
                               rtol=1e-5)
