"""ctypes loader for the native data-pipeline library.

The reference's IO stack is C++ (src/io/ + dmlc-core); so is ours: RecordIO
parsing, libjpeg decode, augmentation and batch assembly run in
mxtpu_native.cc worker threads, keeping the Python side to a thin ctypes
wrapper. Built with `make` from the tracked sources on first use (no pip
involved) and loaded only when that build succeeded; every consumer falls
back to the pure-Python path when the toolchain or libjpeg is unavailable,
so the native library is an accelerator, never a requirement.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

import numpy as np

from ..analysis.lockwatch import named_lock

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxtpu_native.so")
_lock = named_lock("native.loader")
_lib = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _DIR, "-s"], check=True,
                       capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logging.warning("native IO library not built (%s); using the "
                        "pure-Python pipeline. %s", e,
                        detail.decode(errors="replace").strip()[-400:])
        return False
    return os.path.exists(_SO)


def get_lib():
    """The loaded native library, or None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # Always invoke make: its mxtpu_native.cc dependency makes a fresh
        # .so a no-op. A .so that make could not rebuild is never loaded:
        # one built before an ABI change (e.g. the nhwc/out_u8 pipeline
        # args) would corrupt batches without a word.
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.mxtpu_scan_offsets.restype = ctypes.c_int64
        lib.mxtpu_scan_offsets.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.mxtpu_pipeline_create.restype = ctypes.c_void_p
        lib.mxtpu_pipeline_create.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_float, ctypes.c_int,
            ctypes.c_uint32, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
            ctypes.c_int]
        lib.mxtpu_pipeline_next.restype = ctypes.c_int
        lib.mxtpu_pipeline_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int)]
        lib.mxtpu_pipeline_reset.argtypes = [ctypes.c_void_p]
        lib.mxtpu_pipeline_batches.restype = ctypes.c_int64
        lib.mxtpu_pipeline_batches.argtypes = [ctypes.c_void_p]
        lib.mxtpu_pipeline_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def scan_offsets(path: str):
    """Record offsets of a CREC file via the native scanner (or None)."""
    lib = get_lib()
    if lib is None:
        return None
    cap = 1 << 16
    while True:
        buf = (ctypes.c_int64 * cap)()
        n = lib.mxtpu_scan_offsets(path.encode(), buf, cap)
        if n < 0:
            return None
        if n <= cap:
            return list(buf[:n])
        cap = n


class NativePipeline:
    """RAII wrapper over the C++ ImagePipeline."""

    def __init__(self, path, offsets, batch, data_shape, label_width=1,
                 rand_crop=False, rand_mirror=False, resize=-1, mean=None,
                 scale=1.0, shuffle=False, seed=0, num_threads=None,
                 prefetch=4, round_batch=True, nhwc=False, out_u8=False,
                 min_random_scale=1.0, max_random_scale=1.0,
                 min_img_size=0.0, max_img_size=0.0,
                 max_random_contrast=0.0, max_random_illumination=0.0,
                 mirror=False):
        if out_u8 and (mean is not None or scale != 1.0
                       or max_random_contrast or max_random_illumination):
            raise ValueError("uint8 output emits raw pixels: mean/scale and "
                             "contrast/illumination must be left for the "
                             "device side")
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.batch = batch
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        off = (ctypes.c_int64 * len(offsets))(*offsets)
        mean_ptr = None
        if mean is not None:
            mean_arr = (ctypes.c_float * 3)(*[float(m) for m in mean])
            mean_ptr = mean_arr
        num_threads = num_threads or max(1, (os.cpu_count() or 2) - 1)
        c, h, w = self.data_shape
        self.nhwc = bool(nhwc)
        self.out_u8 = bool(out_u8)
        aug = (min_random_scale, max_random_scale, min_img_size,
               max_img_size, max_random_contrast, max_random_illumination)
        aug_ptr = None
        if aug != (1.0, 1.0, 0.0, 0.0, 0.0, 0.0):
            aug_arr = (ctypes.c_float * 6)(*[float(a) for a in aug])
            aug_ptr = aug_arr
        self._handle = lib.mxtpu_pipeline_create(
            path.encode(), off, len(offsets), batch, c, h, w, label_width,
            int(rand_crop), int(rand_mirror), int(resize), mean_ptr,
            float(scale), int(shuffle), int(seed) & 0xFFFFFFFF,
            num_threads, prefetch, int(round_batch), int(self.nhwc),
            int(self.out_u8), aug_ptr, int(mirror))
        if not self._handle:
            raise RuntimeError(f"failed to open native pipeline on {path!r}")

    def next(self):
        """Returns (data in NCHW — or NHWC when so configured — f32, or raw
        uint8 under out_u8; labels f32; pad) or raises StopIteration."""
        c, h, w = self.data_shape
        batch_shape = (h, w, c) if self.nhwc else (c, h, w)
        dtype = np.uint8 if self.out_u8 else np.float32
        data = np.empty((self.batch,) + batch_shape, dtype)
        shape = (self.batch,) if self.label_width == 1 else \
            (self.batch, self.label_width)
        labels = np.empty(shape, np.float32)
        pad = ctypes.c_int(0)
        rc = self._lib.mxtpu_pipeline_next(
            self._handle,
            data.ctypes.data_as(ctypes.c_void_p),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.byref(pad))
        if rc == 1:
            raise StopIteration
        if rc != 0:
            raise RuntimeError("native pipeline failed (bad record or non-JPEG)")
        return data, labels, pad.value

    def reset(self):
        self._lib.mxtpu_pipeline_reset(self._handle)

    @property
    def batches_per_epoch(self):
        return self._lib.mxtpu_pipeline_batches(self._handle)

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.mxtpu_pipeline_destroy(self._handle)
            self._handle = None
