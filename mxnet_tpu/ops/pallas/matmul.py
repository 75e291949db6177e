"""Int8 matmul with per-channel scales and int32 accumulation (serving path).

The predict/serving matmuls (ROADMAP item 1) are weight-stationary and
error-tolerant: int8 operands run the MXU at twice the bf16 rate and
quarter the weight HBM traffic, and per-output-channel scales keep the
quantization error at the well-known ~1e-3 relative level. The kernel:

    x  (M, K) float      -- activations, quantized per ROW inside the
                            kernel (dynamic: scale = max|row|/127)
    wq (N, K) int8       -- weights, pre-quantized per output CHANNEL
                            (:func:`quantize_channels`, FC layout so
                            checkpoints map 1:1)
    y  (M, N) float32    -- dot(int8, int8) accumulated in int32 (the
                            MXU's integer mode; exact), converted and
                            rescaled by sx[m] * sw[n] in f32

Serving integration: ``ops.nn.FullyConnectedOp`` routes inference-mode
matmuls here under :func:`int8_predict_scope` (or env
``MXNET_TPU_INT8_PREDICT``), which ``Predictor(quantize="int8")`` arms —
the gate is read at TRACE time, so it must be active when the program
first compiles (Predictor wraps its jit dispatch in the scope).

Accuracy contract (tests/test_pallas_kernels.py): relative Frobenius
error vs the f32 matmul bounded (~1e-2 for gaussian operands); exact
when inputs are already int8-representable.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...base import ENV_ON_VALUES
from ._common import resolve_interpret
from .registry import KernelCost, io_bytes, register_kernel

__all__ = ["int8_matmul", "quantize_channels", "int8_predict_scope",
           "int8_predict_active"]

_SCOPE = contextvars.ContextVar("mxnet_tpu_int8_predict", default=None)


@contextlib.contextmanager
def int8_predict_scope(enabled=True):
    """Arm (or explicitly disarm) the int8 inference matmul path for
    code traced inside the scope."""
    token = _SCOPE.set(bool(enabled))
    try:
        yield
    finally:
        _SCOPE.reset(token)


def int8_predict_active() -> bool:
    """Is the int8 serving path armed? Scope wins; else the env gate."""
    val = _SCOPE.get()
    if val is not None:
        return val
    return os.environ.get("MXNET_TPU_INT8_PREDICT",
                          "").strip().lower() in ENV_ON_VALUES


def quantize_channels(w):
    """Per-output-channel int8 weight quantization for the FC layout
    ``(num_hidden, input_dim)``: one f32 scale per output channel.
    Returns ``(wq int8, scale (N,) f32)``."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=1) / 127.0, 1e-30)
    wq = jnp.clip(jnp.round(w / scale[:, None]), -127, 127).astype(jnp.int8)
    return wq, scale.astype(jnp.float32)


def _pad2(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _int8_mm_kernel(x_ref, wq_ref, sw_ref, o_ref):
    x = x_ref[:]                                     # (bm, K) f32
    # dynamic per-row activation quantization, fused into the matmul
    # pass: the row never round-trips through HBM as int8. Recomputed
    # once per (i, j) grid cell — deliberate: the quantize is
    # ~4/(2*block_n) (<1% at bn=256) of the cell's contraction FLOPs,
    # cheaper than materializing qx/sx to HBM and re-reading them
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0,
                     1e-30)
    qx = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        qx, wq_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)            # exact accumulate
    o_ref[:] = acc.astype(jnp.float32) * sx * sw_ref[:]


def int8_matmul(x, w, *, w_scale=None, block_m=256, block_n=256,
                interpret=None):
    """``x @ w.T`` through the int8 kernel. ``w`` is ``(N, K)`` float
    (quantized here per channel) or pre-quantized int8 with ``w_scale``
    ``(N,)``. Returns ``(M, N) float32``."""
    interpret = resolve_interpret(interpret)
    if w.dtype == jnp.int8:
        if w_scale is None:
            raise ValueError("int8_matmul: pre-quantized w needs w_scale=")
        wq, sw = w, w_scale.astype(jnp.float32)
    else:
        wq, sw = quantize_channels(w)
    M, K = x.shape
    N = wq.shape[0]
    bm = min(int(block_m), max(8, M))
    bn = min(int(block_n), max(8, N))
    xp = _pad2(x.astype(jnp.float32), bm, 128)
    wp = _pad2(wq, bn, 128)
    sp = _pad2(sw.reshape(1, N), 1, bn)
    Kp = xp.shape[1]
    y = pl.pallas_call(
        _int8_mm_kernel,
        grid=(xp.shape[0] // bm, wp.shape[0] // bn),
        in_specs=[
            pl.BlockSpec((bm, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, Kp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[0]),
                                       jnp.float32),
        interpret=interpret,
        name="int8_matmul",
    )(xp, wp, sp)
    return y[:M, :N]


def _int8_mm_cost(in_avals, out_avals):
    x, wq = in_avals[0], in_avals[1]
    m, k = (int(d) for d in x.shape)
    n = int(wq.shape[0])
    # contraction + the fused in-kernel activation quantize
    return KernelCost(flops=2.0 * m * n * k + 4.0 * m * k,
                      bytes=io_bytes(in_avals, out_avals))


register_kernel(
    "int8_matmul", _int8_mm_cost, module=__name__,
    doc="per-channel-scaled int8 matmul, int32 accumulate, fused dynamic "
        "activation quantization (serving path)")
