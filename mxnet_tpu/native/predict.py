"""ctypes wrapper over the native C++ predictor (libmxtpu_predict.so).

Reference counterpart: the C predict API (include/mxnet/c_predict_api.h,
handle-based MXPredCreate/MXPredSetInput/MXPredForward/MXPredGetOutput) as
shipped by the amalgamation build — a deployment path with no Python
framework dependency.  Here the artifact is the `.mxtpu` bundle written by
``mxnet_tpu.predictor.Predictor.export``; the C++ runtime parses the bundle
(zip + symbol JSON + npy params) and executes the graph with plain CPU
kernels, so exported models run anywhere a C++17 toolchain exists.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from ..analysis.lockwatch import named_lock

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxtpu_predict.so")
_lock = named_lock("native.predict.loader")
_lib = None
_tried = False

__all__ = ["NativePredictor", "get_predict_lib", "load_lib"]


def load_lib(path):
    """Load and configure a predict library from an explicit .so path
    (used by the amalgamation build's self-test)."""
    lib = ctypes.CDLL(path)
    _configure(lib)
    return lib


def get_predict_lib():
    """The loaded native predict library, or None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # Always invoke make (a no-op on a fresh .so) and load only what
        # it just built from the tracked source, never a leftover binary.
        # Only the predict target: it needs just zlib, and must not fail
        # on hosts missing the pipeline library's libjpeg dep.
        try:
            subprocess.run(["make", "-C", _DIR, "-s",
                            "libmxtpu_predict.so"], check=True,
                           capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError):
            return None
        if not os.path.exists(_SO):
            return None
        try:
            _lib = load_lib(_SO)
        except OSError:
            return None
        return _lib


def _configure(lib):
    lib.mxtpu_pred_create.restype = ctypes.c_void_p
    lib.mxtpu_pred_create.argtypes = [ctypes.c_char_p]
    lib.mxtpu_pred_last_error.restype = ctypes.c_char_p
    lib.mxtpu_pred_set_input.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.mxtpu_pred_forward.argtypes = [ctypes.c_void_p]
    lib.mxtpu_pred_num_outputs.argtypes = [ctypes.c_void_p]
    lib.mxtpu_pred_output_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mxtpu_pred_output_shape.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64)]
    lib.mxtpu_pred_get_output.restype = ctypes.c_int64
    lib.mxtpu_pred_get_output.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64]
    lib.mxtpu_pred_free.argtypes = [ctypes.c_void_p]


class NativePredictor:
    """Forward-only model runner on the C++ CPU runtime.

    Usage mirrors the reference predict API::

        pred = NativePredictor("model.mxtpu")
        pred.set_input("data", batch)           # MXPredSetInput
        pred.forward()                          # MXPredForward
        probs = pred.get_output(0)              # MXPredGetOutput
    """

    def __init__(self, bundle_path: str, lib=None):
        lib = lib if lib is not None else get_predict_lib()
        if lib is None:
            raise RuntimeError("native predict library unavailable")
        self._lib = lib
        self._handle = lib.mxtpu_pred_create(os.fspath(bundle_path).encode())
        if not self._handle:
            raise RuntimeError(
                f"failed to load bundle: {lib.mxtpu_pred_last_error().decode()}")

    def _err(self) -> str:
        return self._lib.mxtpu_pred_last_error().decode()

    def set_input(self, name: str, value) -> None:
        arr = np.ascontiguousarray(np.asarray(value), dtype=np.float32)
        shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
        self._lib.mxtpu_pred_set_input(
            self._handle, name.encode(),
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            shape, arr.ndim)

    def forward(self, **inputs) -> None:
        for name, value in inputs.items():
            self.set_input(name, value)
        if self._lib.mxtpu_pred_forward(self._handle) != 0:
            raise RuntimeError(f"native forward failed: {self._err()}")

    @property
    def num_outputs(self) -> int:
        return self._lib.mxtpu_pred_num_outputs(self._handle)

    def get_output(self, index: int = 0) -> np.ndarray:
        ndim = self._lib.mxtpu_pred_output_ndim(self._handle, index)
        if ndim < 0:
            raise IndexError(f"output {index} out of range")
        shape = (ctypes.c_int64 * max(ndim, 1))()
        self._lib.mxtpu_pred_output_shape(self._handle, index, shape)
        out_shape = tuple(shape[i] for i in range(ndim))
        buf = np.empty(out_shape, np.float32)
        n = self._lib.mxtpu_pred_get_output(
            self._handle, index,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), buf.size)
        if n < 0:
            raise RuntimeError(f"get_output failed: {self._err()}")
        return buf

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.mxtpu_pred_free(self._handle)
            self._handle = None
