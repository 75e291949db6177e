"""Device context model.

TPU-native replacement for the reference's ``Context{kCPU,kGPU,kCPUPinned}``
(reference: include/mxnet/base.h:90-175). We add ``tpu()`` as the first-class
accelerator context; ``gpu()`` is accepted as an alias for "the accelerator
backend" so reference scripts run unchanged. ``cpu_pinned`` maps to plain host
memory (JAX manages transfer pinning internally).

Unlike the reference, a Context resolves to a ``jax.Device``; placement happens
via ``jax.device_put`` rather than a per-device stream pool.
"""

from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_devices"]

_thread_local = threading.local()


def _accelerator_devices():
    """Local (addressable) non-CPU JAX devices, or [] when CPU-only.

    Local, not global: under jax.distributed each process may only place
    data on its own devices; Contexts address the local slice, meshes
    (parallel/mesh.py) address the global device set."""
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _cpu_devices():
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        # CPU platform not initialised (rare); fall back to default devices.
        return jax.local_devices()


class Context:
    """A device context. Constructed via :func:`cpu`, :func:`tpu` or :func:`gpu`.

    Reference parity: mimics mxnet.context.Context incl. ``with`` support and
    the (device_type, device_id) identity; adds ``.jax_device``.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError(
                    f"unknown device type {device_type!r}; expected one of "
                    f"{sorted(self.devstr2type)}"
                )
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        ``tpu``/``gpu`` name exactly one attached accelerator: no
        accelerator, or a ``device_id`` past the last one, raises rather
        than landing the work on the host CPU or on another chip.
        """
        if self.device_type in ("tpu", "gpu"):
            accel = _accelerator_devices()
            if not 0 <= self.device_id < len(accel):
                raise MXNetError(
                    f"{self!r}: this process has {len(accel)} accelerator "
                    f"device(s) (jax.local_devices(): "
                    f"{[str(d) for d in jax.local_devices()]}); use "
                    "mx.cpu() to run on the host")
            return accel[self.device_id]
        cpus = _cpu_devices()
        return cpus[self.device_id % len(cpus)]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __enter__(self):
        self._old_ctx = getattr(_thread_local, "default_ctx", None)
        _thread_local.default_ctx = self
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        _thread_local.default_ctx = self._old_ctx
        return False


def cpu(device_id=0):
    """Host-memory context (reference: Context::CPU)."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    """Pinned host memory. On TPU this is ordinary host memory; kept for parity."""
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Accelerator context, alias of :func:`tpu` for reference-script parity."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """TPU chip ``device_id`` (the native accelerator context of this framework)."""
    return Context("tpu", device_id)


def current_context() -> Context:
    """The default context (innermost ``with Context`` block, else cpu(0))."""
    ctx = getattr(_thread_local, "default_ctx", None)
    if ctx is None:
        ctx = Context("cpu", 0)
        _thread_local.default_ctx = ctx
    return ctx


def num_devices(device_type="tpu") -> int:
    """Number of attached devices of ``device_type`` ('tpu' counts
    accelerators, and raises on a CPU-only host rather than count CPUs)."""
    if device_type in ("tpu", "gpu"):
        accel = _accelerator_devices()
        if not accel:
            raise MXNetError(
                f"num_devices({device_type!r}): no accelerator attached "
                "to this process; num_devices('cpu') counts host devices")
        return len(accel)
    return len(_cpu_devices())
