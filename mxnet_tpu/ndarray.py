"""Imperative NDArray: the user-facing tensor type, backed by ``jax.Array``.

Reference counterpart: include/mxnet/ndarray.h + src/ndarray/ndarray.cc — a
ref-counted buffer plus an engine variable, where every operation is pushed
asynchronously to the dependency engine and ``.asnumpy()`` is the sync point.

TPU-native design decisions:
  - The buffer is an immutable ``jax.Array``. "Mutation" (``+=``, ``a[i:j]=x``,
    ``out=``) rebinds the wrapper's ``_data`` to a new functional value
    (``.at[].set``), which XLA turns into in-place updates via buffer
    donation/aliasing inside jit. This preserves every reference API contract
    (pull into preallocated arrays, kAddTo accumulation) without exposing
    mutability to the compiler.
  - Async semantics come for free: JAX dispatch is asynchronous on TPU, ops
    enqueue in launch order per device, and ``wait_to_read`` maps to
    ``block_until_ready`` (reference: WaitToRead; engine push per op).
  - There is no storage manager: TPU HBM allocation is owned by the XLA
    runtime (reference src/storage/ becomes ``utils.memory_stats``).
  - dtype is configurable (reference is float32-only, ndarray.cc:468-470);
    default stays float32, bfloat16 is first-class for TPU compute.

The registered-function surface (``_plus``, ``dot``, ``clip`` ... —
reference src/ndarray/ndarray.cc:601-652) is exposed both as operators on
NDArray and as module-level functions accepting ``out=``.
"""

from __future__ import annotations

import functools
import struct

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, dtype_code, dtype_from_code
from .context import Context, cpu, current_context

__all__ = [
    "NDArray",
    "array",
    "empty",
    "zeros",
    "ones",
    "full",
    "arange",
    "save",
    "load",
    "waitall",
    "concatenate",
    "dot",
    "onehot_encode",
    "choose_element_0index",
    "clip",
    "square",
    "sqrt",
    "exp",
    "log",
    "norm",
    "maximum",
    "minimum",
    "abs",
    "sum",
    "max",
    "min",
    "argmax_channel",
]

real_t = np.float32

# Live-array ledger hook (telemetry.memory.track_arrays installs/removes
# it): None keeps the NDArray hot path at one global load + None check;
# when set, every construction registers a weakref-tracked byte entry.
_LEDGER = None


def _ctx_of(device: jax.Device) -> Context:
    if device.platform == "cpu":
        return Context("cpu", device.id)
    return Context("tpu", device.id)


class NDArray:
    """Multi-dimensional array on a device, with async execution semantics."""

    # __weakref__ lets the telemetry memory ledger track live arrays
    # without keeping them alive (weakref callbacks decrement on GC)
    __slots__ = ("_data", "writable", "__weakref__")

    def __init__(self, data, ctx: Context | None = None, writable: bool = True):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            dtype = None if hasattr(data, "dtype") else real_t
            data = jnp.asarray(data, dtype=dtype)
        if ctx is not None:
            data = jax.device_put(data, ctx.jax_device)
        self._data = data
        self.writable = writable
        if _LEDGER is not None:
            _LEDGER.add(self)

    # -- core properties ------------------------------------------------------
    @property
    def data(self) -> jax.Array:
        """The underlying jax.Array (read-only view of current value)."""
        return self._data

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def size(self):
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def context(self) -> Context:
        devs = self._data.devices()
        return _ctx_of(next(iter(devs)))

    ctx = context

    # -- sync points ----------------------------------------------------------
    def wait_to_read(self):
        """Block until the value is computed (reference: NDArray::WaitToRead)."""
        self._data.block_until_ready()
        return self

    # Writes are ordered by rebinding; waiting on the current value covers both.
    wait_to_write = wait_to_read

    def asnumpy(self) -> np.ndarray:
        """Copy to host as numpy; this is the explicit synchronization point."""
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("asscalar requires size-1 NDArray")
        return self.asnumpy().reshape(())[()]

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    # -- mutation facade ------------------------------------------------------
    def _set_data(self, new_data: jax.Array):
        if not self.writable:
            raise MXNetError("trying to write to a read-only NDArray")
        if tuple(new_data.shape) != self.shape:
            raise MXNetError(
                f"shape mismatch writing {tuple(new_data.shape)} into {self.shape}"
            )
        if new_data.dtype != self.dtype:
            new_data = new_data.astype(self.dtype)
        self._data = new_data
        return self

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if key == slice(None) or key is Ellipsis:
            if np.isscalar(value):
                self._set_data(jnp.full(self.shape, value, dtype=self.dtype))
            else:
                value = jnp.asarray(value, dtype=self.dtype)
                self._set_data(jnp.broadcast_to(value, self.shape))
        else:
            self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key):
        """Slicing returns a *copy* (the reference returns zero-copy views;
        with immutable buffers a copy is semantically equivalent for reads).
        """
        return NDArray(self._data[key])

    def slice(self, start, stop):
        """Slice along axis 0 (reference: NDArray::Slice, ndarray.h)."""
        return NDArray(self._data[start:stop])

    def reshape(self, shape):
        if isinstance(shape, int):
            shape = (shape,)
        return NDArray(jnp.reshape(self._data, shape))

    @property
    def T(self):
        return NDArray(jnp.transpose(self._data))

    def astype(self, dtype):
        return NDArray(self._data.astype(np.dtype(dtype) if dtype != "bfloat16" else jnp.bfloat16))

    # -- placement ------------------------------------------------------------
    def copyto(self, other):
        """Copy into another NDArray (writes it) or to a new array on a Context.

        Reference: NDArray::CopyTo / CopyFromTo (ndarray.cc:158-218); the
        device-pair dispatch there becomes a single ``jax.device_put``.
        """
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device))
        if not isinstance(other, NDArray):
            raise TypeError("copyto target must be NDArray or Context")
        dst_dev = next(iter(other._data.devices()))
        other._set_data(jax.device_put(self._data, dst_dev).astype(other.dtype))
        return other

    def copy(self):
        return NDArray(jnp.copy(self._data))

    def as_in_context(self, ctx: Context):
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    # -- arithmetic -----------------------------------------------------------
    def _binary(self, other, fn):
        if isinstance(other, NDArray):
            return NDArray(fn(self._data, other._data))
        return NDArray(fn(self._data, other))

    def __add__(self, other):
        return self._binary(other, _plus_jit)

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, _minus_jit)

    def __rsub__(self, other):
        return self._binary(other, lambda a, b: _minus_jit(b, a) if isinstance(b, jax.Array) else _rminus_jit(a, b))

    def __mul__(self, other):
        return self._binary(other, _mul_jit)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, _div_jit)

    def __rdiv__(self, other):
        return self._binary(other, _rdiv_jit)

    __rtruediv__ = __rdiv__

    def __pow__(self, other):
        return self._binary(other, lambda a, b: a ** b)

    def __neg__(self):
        return NDArray(-self._data)

    def __iadd__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        return self._set_data(_plus_jit(self._data, o))

    def __isub__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        return self._set_data(_minus_jit(self._data, o))

    def __imul__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        return self._set_data(_mul_jit(self._data, o))

    def __itruediv__(self, other):
        o = other._data if isinstance(other, NDArray) else other
        return self._set_data(_div_jit(self._data, o))

    def __eq__(self, other):  # identity, like the reference's handle equality
        return self is other

    def __hash__(self):
        return id(self)

    def __repr__(self):
        return f"<NDArray {self.shape} @{self.context}>"

    # pickle support (reference: test_ndarray.py pickles NDArrays)
    def __getstate__(self):
        return {"data": self.asnumpy(), "writable": self.writable}

    def __setstate__(self, state):
        self._data = jnp.asarray(state["data"])
        self.writable = state["writable"]

    def __reduce__(self):
        return (NDArray, (self.asnumpy(),), None)


# -- jitted elementwise kernels (shared by operators and functions) -----------
@jax.jit
def _plus_jit(a, b):
    return a + b


@jax.jit
def _minus_jit(a, b):
    return a - b


@jax.jit
def _rminus_jit(a, b):
    return b - a


@jax.jit
def _mul_jit(a, b):
    return a * b


@jax.jit
def _div_jit(a, b):
    return a / b


@jax.jit
def _rdiv_jit(a, b):
    return b / a


# -- creation -----------------------------------------------------------------
def _resolve_ctx(ctx):
    return ctx if ctx is not None else current_context()


def array(source_array, ctx: Context | None = None, dtype=real_t) -> NDArray:
    """Create an NDArray from any array-like (reference: mx.nd.array)."""
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    arr = np.asarray(source_array, dtype=dtype)
    return NDArray(jax.device_put(arr, _resolve_ctx(ctx).jax_device))


def empty(shape, ctx=None, dtype=real_t) -> NDArray:
    """Uninitialized array. XLA has no uninitialized buffers; zeros are used.

    (Reference: delayed allocation, ndarray.h — here allocation is also lazy:
    nothing materializes until the value is consumed.)
    """
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=real_t) -> NDArray:
    # host-side np.zeros + one device_put: jnp.zeros would allocate on the
    # DEFAULT backend first and then copy to ctx — this is the hot path of
    # parameter init, ~270 arrays for a ResNet
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(np.zeros(shape, dtype=dtype), _resolve_ctx(ctx).jax_device)
    )


def ones(shape, ctx=None, dtype=real_t) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(np.ones(shape, dtype=dtype), _resolve_ctx(ctx).jax_device)
    )


def full(shape, val, ctx=None, dtype=real_t) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(
        jax.device_put(jnp.full(shape, val, dtype=dtype), _resolve_ctx(ctx).jax_device)
    )


def arange(start, stop=None, step=1.0, ctx=None, dtype=real_t) -> NDArray:
    return NDArray(
        jax.device_put(jnp.arange(start, stop, step, dtype=dtype), _resolve_ctx(ctx).jax_device)
    )


def waitall():
    """Block until all launched work is complete (reference: MXNDArrayWaitAll).

    XLA executes programs in launch order per device, so synchronizing a
    freshly-launched no-op on every device drains each queue.
    """
    for dev in jax.local_devices():
        jax.device_put(np.zeros((), np.int32), dev).block_until_ready()


# -- registered functions (reference ndarray.cc:601-652) ----------------------
def _out_wrap(result: jax.Array, out: NDArray | None) -> NDArray:
    if out is None:
        return NDArray(result)
    out._set_data(result)
    return out


def _fn2(fn):
    @functools.wraps(fn)
    def wrapped(lhs, rhs, out=None):
        a = lhs._data if isinstance(lhs, NDArray) else lhs
        b = rhs._data if isinstance(rhs, NDArray) else rhs
        return _out_wrap(fn(a, b), out)

    return wrapped


def _fn1(fn):
    @functools.wraps(fn)
    def wrapped(src, out=None):
        a = src._data if isinstance(src, NDArray) else src
        return _out_wrap(fn(a), out)

    return wrapped


_plus = _fn2(_plus_jit)
_minus = _fn2(_minus_jit)
_mul = _fn2(_mul_jit)
_div = _fn2(_div_jit)
_plus_scalar = _fn2(_plus_jit)
_minus_scalar = _fn2(_minus_jit)
_mul_scalar = _fn2(_mul_jit)
_div_scalar = _fn2(_div_jit)
_rminus_scalar = _fn2(_rminus_jit)
_rdiv_scalar = _fn2(_rdiv_jit)
dot = _fn2(jax.jit(lambda a, b: jnp.dot(a, b)))
maximum = _fn2(jax.jit(jnp.maximum))
minimum = _fn2(jax.jit(jnp.minimum))

square = _fn1(jax.jit(jnp.square))
sqrt = _fn1(jax.jit(jnp.sqrt))
exp = _fn1(jax.jit(jnp.exp))
log = _fn1(jax.jit(jnp.log))
abs = _fn1(jax.jit(jnp.abs))  # noqa: A001 - reference exposes `abs`


@_fn1
@jax.jit
def norm(a):
    """L2 norm, returns a 1-element NDArray (reference: unary_function-inl.h)."""
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))).reshape((1,))


def sum(src, out=None):  # noqa: A001
    return _fn1(jax.jit(lambda a: jnp.sum(a).reshape((1,))))(src, out)


def max(src, out=None):  # noqa: A001
    return _fn1(jax.jit(lambda a: jnp.max(a).reshape((1,))))(src, out)


def min(src, out=None):  # noqa: A001
    return _fn1(jax.jit(lambda a: jnp.min(a).reshape((1,))))(src, out)


@_fn1
@jax.jit
def argmax_channel(a):
    """Row-wise argmax of a 2-D array (reference: used by Accuracy metric)."""
    return jnp.argmax(a, axis=1).astype(a.dtype)


@jax.jit
def _onehot_jit(indices, out_like):
    depth = out_like.shape[1]
    return jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=out_like.dtype)


def onehot_encode(indices, out, **_ignored):
    """Fill ``out`` (batch, depth) with one-hot rows from ``indices`` (batch,).

    Reference semantics (_onehot_encode, ndarray_function.h OneHotEncode):
    the second argument IS the output buffer and is written in place."""
    idx = indices._data if isinstance(indices, NDArray) else indices
    return _out_wrap(_onehot_jit(idx, out._data), out)


@_fn2
@jax.jit
def choose_element_0index(lhs, rhs):
    """out[i] = lhs[i, rhs[i]] (reference: MatChooseRowElem)."""
    idx = rhs.astype(jnp.int32)
    return jnp.take_along_axis(lhs, idx[:, None], axis=1)[:, 0]


def clip(src, a_min, a_max, out=None):
    a = src._data if isinstance(src, NDArray) else src
    return _out_wrap(jnp.clip(a, a_min, a_max), out)


def _copyto(src, out=None):
    if out is None:
        raise MXNetError("_copyto requires out=")
    return src.copyto(out)


def concatenate(arrays, axis=0):
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis))


def random_uniform(low=0.0, high=1.0, shape=None, out=None):
    """Registered sampling fn (reference: _random_uniform, ndarray.cc:645;
    the kRandom engine resource becomes an explicit PRNG key stream)."""
    from . import random as _random

    return _random.uniform(low, high, shape, out=out)


def random_gaussian(loc=0.0, scale=1.0, shape=None, out=None):
    """Registered sampling fn (reference: _random_gaussian, ndarray.cc:647)."""
    from . import random as _random

    return _random.normal(loc, scale, shape, out=out)


# -- serialization (reference: NDArray::Save/Load, ndarray.cc:450-536) --------
# Redesigned container, same layering: magic + per-tensor header + raw bytes,
# with an optional name table for dict-style save/load.
_NDAR_MAGIC = 0x112
_NAMED_MAGIC = 0x1121


def _write_one(f, arr: NDArray):
    a = np.ascontiguousarray(arr.asnumpy())
    f.write(struct.pack("<II", dtype_code(a.dtype), a.ndim))
    f.write(struct.pack(f"<{a.ndim}q", *a.shape))
    f.write(a.tobytes())


def _read_one(f) -> NDArray:
    code, ndim = struct.unpack("<II", f.read(8))
    shape = struct.unpack(f"<{ndim}q", f.read(8 * ndim)) if ndim else ()
    dt = dtype_from_code(code)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    buf = f.read(n * dt.itemsize)
    return array(np.frombuffer(buf, dtype=dt).reshape(shape), ctx=cpu(), dtype=dt)


def save(fname: str, data):
    """Save a list or str->NDArray dict (reference: mx.nd.save, model.py:417)."""
    if isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
        magic = _NAMED_MAGIC
    elif isinstance(data, (list, tuple)):
        names, arrays = None, list(data)
        magic = _NDAR_MAGIC
    else:
        raise MXNetError("save expects dict or list of NDArray")
    for a in arrays:
        if not isinstance(a, NDArray):
            raise MXNetError("save expects NDArray values")
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", magic, len(arrays)))
        for a in arrays:
            _write_one(f, a)
        if names is not None:
            for name in names:
                b = name.encode("utf-8")
                f.write(struct.pack("<I", len(b)))
                f.write(b)


def load(fname: str):
    """Load what :func:`save` wrote; returns list or dict accordingly."""
    try:
        with open(fname, "rb") as f:
            magic, count = struct.unpack("<QQ", f.read(16))
            if magic not in (_NDAR_MAGIC, _NAMED_MAGIC):
                raise MXNetError(f"invalid NDArray file {fname!r}")
            arrays = [_read_one(f) for _ in range(count)]
            if magic == _NDAR_MAGIC:
                return arrays
            names = []
            for _ in range(count):
                (ln,) = struct.unpack("<I", f.read(4))
                names.append(f.read(ln).decode("utf-8"))
            return dict(zip(names, arrays))
    except (struct.error, ValueError) as e:
        raise MXNetError(f"corrupt NDArray file {fname!r}: {e}") from None
