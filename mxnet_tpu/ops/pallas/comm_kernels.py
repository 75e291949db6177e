"""Fused gradient-compression kernels: quantize + scales in one VMEM pass.

The comm layer's reference codecs (comm/compression.py) are pure jnp —
correct, but XLA lowers each encode/decode as its own chain of full-slab
elementwise passes (abs -> max -> divide -> round -> clip -> convert ...),
each one a round-trip of the whole gradient bucket through HBM. EQuARX
(arXiv 2506.17615) makes the case that quantization belongs *inside* the
collective's kernel; these Pallas kernels are that shape for our
decomposed allreduce: one pass that streams a slab block through VMEM and
emits the wire payload AND the per-chunk scales (and, fused, the
dequantized round-trip the error-feedback residual needs), plus the
inverse pass that dequantizes received rows and accumulates the f32
reduction without ever materializing the decoded (ndev, per) slab in HBM.

The bitwise contract: for every mode the emitted wire payload is
BIT-IDENTICAL to ``compression.encode``'s — the kernels reproduce the
reference arithmetic exactly (same ops, same order), so a fleet can mix
kernel and codec ranks mid-rollout and the wire, the error-feedback
ledgers, and the convergence trajectory do not fork. Enforced by
tests/test_pallas_kernels.py against the reference codecs.

Layout: the wire format is row-major (one f32 scale per ``chunk``
adjacent elements; four adjacent twobit codes per byte), but a TPU block
must be a whole number of (sublane, lane) tiles. So every kernel sees the
slab re-viewed — a free reshape outside the kernel — with the
quantization unit along the lanes: int8 as ``(L/chunk, chunk)`` rows (the
chunk max is a lane reduction, the scale a ``(rows, 1)`` column), twobit
as ``(L/512, 512)`` rows packing to ``(L/512, 128)`` bytes. The 4-to-1
lane (de)interleave twobit needs runs on the MXU as a product with a
constant 0/1 selection matrix: codes are small integers, exact in bf16
with f32 accumulation, and ~256 FLOPs/element is noise there. Blocks are
whole rows, a multiple of 32 (the 8-bit sublane tile) unless they span
the array; a ragged last block is masked by the grid.

Entry points (all run under interpret mode off-TPU, ``_common`` gate):

  fused_quantize      (R, L) f32 rows -> payload dict {q[, scale]}
                      (+ the decode round-trip when ``want_dequant``)
  fused_dequant_sum   payload rows -> (L,) f32 column sums (the
                      reduce-scatter accumulate, decode fused in)
  fused_dequant       payload rows -> (R, L) f32 (the all-gather side)

Wired behind ``comm.CommKernelConfig`` (comm/allreduce.py) so the fused
and codec paths stay selectable per program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...base import MXNetError
from ._common import resolve_interpret
from .registry import KernelCost, io_bytes, register_kernel

__all__ = ["fused_quantize", "fused_dequant_sum", "fused_dequant",
           "rows_per_block"]

DEFAULT_BLOCK_ELEMS = 65536  # 256 KB of f32 per VMEM block
_ROW_TILE = 32               # sublanes of one int8/uint8 tile
_TWOBIT_LANES = 512          # f32 lanes per twobit row: 128 packed bytes


def rows_per_block(n_rows: int, row_elems: int, cap=None) -> int:
    """Rows of ``row_elems`` elements per kernel block: as many as fit
    under ``cap`` elements, rounded down to the 8-bit sublane tile (never
    below one tile), or all ``n_rows`` when they fit in one block."""
    cap = DEFAULT_BLOCK_ELEMS if cap is None else int(cap)
    rows = max(cap // int(row_elems), 1)
    rows = max(rows // _ROW_TILE * _ROW_TILE, _ROW_TILE)
    return int(n_rows) if rows >= n_rows else rows


def _pad_cols(x, mult):
    pad = (-x.shape[-1]) % mult
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def _twobit_selectors():
    """(pack, unpack) 0/1-valued selection matrices for the 4-codes-per-
    byte lane interleave. ``codes @ pack`` weights code ``4j+k`` by
    ``4**k`` into byte ``j``; ``sum_k field_k @ unpack[k]`` puts byte
    ``j``'s k-th field at lane ``4j+k``."""
    w, b = _TWOBIT_LANES, _TWOBIT_LANES // 4
    lane = np.arange(w)
    pack = np.zeros((w, b), np.float32)
    pack[lane, lane // 4] = 4.0 ** (lane % 4)
    unpack = np.zeros((4, b, w), np.float32)
    unpack[lane % 4, lane // 4, lane] = 1.0
    return (jnp.asarray(pack, jnp.bfloat16),
            jnp.asarray(unpack, jnp.bfloat16))


# --------------------------------------------------------------------------
# quantize: payload (+ scales + dequant round-trip) in one pass
# --------------------------------------------------------------------------

def _quant_int8_kernel(x_ref, q_ref, s_ref, *dq_ref):
    # mirrors compression.encode('int8') op-for-op: the payload must be
    # bit-identical to the reference codec (wire-parity contract)
    x = x_ref[:]                                     # (rows, chunk)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-30).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale
    if dq_ref:
        # decode(encode(x)) fused in: q is integral, so the int8 cast
        # round-trips exactly and the product matches the codec bitwise
        dq_ref[0][:] = (q * scale).astype(jnp.float32)


def _twobit_values(codes, t):
    return (jnp.where(codes == 1, t, 0.0)
            + jnp.where(codes == 2, -t, 0.0)).astype(jnp.float32)


def _quant_twobit_kernel(x_ref, pack_ref, q_ref, *dq_ref, threshold):
    t = threshold
    x = x_ref[:]                                     # (rows, 512)
    # inclusive boundary, exactly like the reference: +/-t transmits
    c = jnp.where(x >= t, 1, 0) + jnp.where(x <= -t, 2, 0)
    packed = jnp.dot(c.astype(jnp.bfloat16), pack_ref[:],
                     preferred_element_type=jnp.float32)
    q_ref[:] = packed.astype(jnp.int32).astype(jnp.uint8)
    if dq_ref:
        dq_ref[0][:] = _twobit_values(c, t)


def fused_quantize(spec, rows, *, want_dequant=False, block_elems=None,
                   interpret=None):
    """Quantize ``rows`` ((R, L) f32, L a multiple of the mode's unit)
    into the wire payload dict — per-chunk scales computed in the same
    VMEM pass — and, with ``want_dequant``, the decode round-trip the
    error-feedback residual is built from. Returns ``(payload, dq)``
    with ``dq=None`` unless requested; payload shapes match
    ``compression.encode`` exactly."""
    interpret = resolve_interpret(interpret)
    rows = rows.astype(jnp.float32)
    squeeze = rows.ndim == 1
    if squeeze:
        rows = rows[None]
    R, L = rows.shape
    n_out = 2 if want_dequant else 1
    if spec.mode == "int8":
        chunk = spec.chunk
        if L % chunk:
            raise MXNetError(f"row length {L} not a multiple of the "
                             f"int8 chunk {chunk}")
        n = R * L // chunk
        tr = rows_per_block(n, chunk, block_elems)
        wide = pl.BlockSpec((tr, chunk), lambda i: (i, 0))
        q, scale, *dq = pl.pallas_call(
            _quant_int8_kernel,
            grid=(pl.cdiv(n, tr),),
            in_specs=[wide],
            out_specs=[wide, pl.BlockSpec((tr, 1), lambda i: (i, 0))]
            + [wide] * (n_out - 1),
            out_shape=[jax.ShapeDtypeStruct((n, chunk), jnp.int8),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)]
            + [jax.ShapeDtypeStruct((n, chunk), jnp.float32)] * (n_out - 1),
            interpret=interpret,
            name="quant_int8",
        )(rows.reshape(n, chunk))
        payload = {"q": q.reshape(R, L),
                   "scale": scale.reshape(R, L // chunk)}
        dq = dq[0].reshape(R, L) if want_dequant else None
    elif spec.mode == "twobit":
        if L % 4:
            raise MXNetError(f"row length {L} not a multiple of 4")
        w = _TWOBIT_LANES
        xp = _pad_cols(rows, w)                      # zeros pack to code 0
        Lp = xp.shape[1]
        n = R * Lp // w
        tr = rows_per_block(n, w, block_elems)
        pack, _ = _twobit_selectors()
        wide = pl.BlockSpec((tr, w), lambda i: (i, 0))
        q, *dq = pl.pallas_call(
            functools.partial(_quant_twobit_kernel,
                              threshold=spec.threshold),
            grid=(pl.cdiv(n, tr),),
            in_specs=[wide, pl.BlockSpec(pack.shape, lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((tr, w // 4), lambda i: (i, 0))]
            + [wide] * (n_out - 1),
            out_shape=[jax.ShapeDtypeStruct((n, w // 4), jnp.uint8)]
            + [jax.ShapeDtypeStruct((n, w), jnp.float32)] * (n_out - 1),
            interpret=interpret,
            name="quant_twobit",
        )(xp.reshape(n, w), pack)
        payload = {"q": q.reshape(R, Lp // 4)[:, :L // 4]}
        dq = dq[0].reshape(R, Lp)[:, :L] if want_dequant else None
    else:
        raise MXNetError(f"fused_quantize: no kernel for mode {spec.mode!r} "
                         "(none/bf16 are plain converts)")
    if squeeze:
        payload = {k: v[0] for k, v in payload.items()}
        if want_dequant:
            dq = dq[0]
    return payload, dq


# --------------------------------------------------------------------------
# dequantize (+ f32 accumulate): the inverse pass
# --------------------------------------------------------------------------

def _dq_int8_block(q, scale):
    return (q.astype(jnp.float32) * scale).astype(jnp.float32)


def _dq_twobit_block(packed, unpack_ref, threshold):
    p = packed.astype(jnp.int32)                     # (rows, 128) bytes
    codes = sum(jnp.dot(((p >> s) & 3).astype(jnp.bfloat16), unpack_ref[k],
                        preferred_element_type=jnp.float32)
                for k, s in enumerate((0, 2, 4, 6)))  # (rows, 512) codes
    return _twobit_values(codes, threshold)


def _accumulate(o_ref, block):
    # grid axis 1 walks the R payload rows of one output block, which
    # stays resident in VMEM: sum(decode(recv), axis=0) without the
    # decoded slab ever reaching HBM
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] = o_ref[:] + block


def _dqsum_int8_kernel(q_ref, s_ref, o_ref):
    _accumulate(o_ref, _dq_int8_block(q_ref[:], s_ref[:]))


def _dqsum_twobit_kernel(q_ref, unpack_ref, o_ref, *, threshold):
    _accumulate(o_ref, _dq_twobit_block(q_ref[:], unpack_ref, threshold))


def _dq_int8_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = _dq_int8_block(q_ref[:], s_ref[:])


def _dq_twobit_kernel(q_ref, unpack_ref, o_ref, *, threshold):
    o_ref[:] = _dq_twobit_block(q_ref[:], unpack_ref, threshold)


def _dequant(spec, payload, *, reduce_rows, block_elems, interpret):
    """Shared driver: decode ``(R, ·)`` payload rows to ``(R, L)`` f32, or
    with ``reduce_rows`` to their ``(L,)`` column sum."""
    q = payload["q"]
    R = q.shape[0]
    if spec.mode == "int8":
        chunk = spec.chunk
        L = q.shape[1]
        ops = [q, payload["scale"]]
        widths = [chunk, 1]
        kern = _dqsum_int8_kernel if reduce_rows else _dq_int8_kernel
        consts = []
        out_w, name = chunk, "int8"
    elif spec.mode == "twobit":
        L = q.shape[1] * 4
        ops = [_pad_cols(q, _TWOBIT_LANES // 4)]
        widths = [_TWOBIT_LANES // 4]
        kern = functools.partial(
            _dqsum_twobit_kernel if reduce_rows else _dq_twobit_kernel,
            threshold=spec.threshold)
        consts = [_twobit_selectors()[1]]
        out_w, name = _TWOBIT_LANES, "twobit"
    else:
        raise MXNetError(f"fused dequant: no kernel for mode {spec.mode!r}")
    nc = ops[0].shape[1] // widths[0]                # unit-rows per payload row
    const_specs = [pl.BlockSpec(c.shape, lambda *_: (0,) * c.ndim)
                   for c in consts]
    if reduce_rows:
        tr = rows_per_block(nc, out_w, block_elems)
        out = pl.pallas_call(
            kern,
            grid=(pl.cdiv(nc, tr), R),
            in_specs=[pl.BlockSpec((None, tr, w), lambda i, r: (r, i, 0))
                      for w in widths] + const_specs,
            out_specs=pl.BlockSpec((tr, out_w), lambda i, r: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nc, out_w), jnp.float32),
            interpret=interpret,
            name=f"dequant_sum_{name}",
        )(*[o.reshape(R, nc, w) for o, w in zip(ops, widths)], *consts)
        return out.reshape(nc * out_w)[:L]
    n = R * nc
    tr = rows_per_block(n, out_w, block_elems)
    out = pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, tr),),
        in_specs=[pl.BlockSpec((tr, w), lambda i: (i, 0)) for w in widths]
        + const_specs,
        out_specs=pl.BlockSpec((tr, out_w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_w), jnp.float32),
        interpret=interpret,
        name=f"dequant_{name}",
    )(*[o.reshape(n, w) for o, w in zip(ops, widths)], *consts)
    return out.reshape(R, nc * out_w)[:, :L]


def fused_dequant_sum(spec, payload, *, block_elems=None, interpret=None):
    """Decode payload rows and accumulate their f32 sum in one pass:
    the reduce-scatter's ``sum(decode(recv), axis=0)`` without the
    decoded (R, L) slab ever hitting HBM. Returns ``(L,) float32``."""
    return _dequant(spec, payload, reduce_rows=True,
                    block_elems=block_elems,
                    interpret=resolve_interpret(interpret))


def fused_dequant(spec, payload, *, block_elems=None, interpret=None):
    """Decode payload rows back to float32 (the all-gather side); same
    values as ``compression.decode``, one blocked pass."""
    squeeze = payload["q"].ndim == 1
    if squeeze:
        payload = {k: v[None] for k, v in payload.items()}
    out = _dequant(spec, payload, reduce_rows=False,
                   block_elems=block_elems,
                   interpret=resolve_interpret(interpret))
    return out[0] if squeeze else out


# --------------------------------------------------------------------------
# registry cost models — elementwise op counts per slab element
# --------------------------------------------------------------------------

def _elemwise_cost(ops_per_elem):
    def cost(in_avals, out_avals):
        n = max((int(getattr(a, "size", 0)) for a in in_avals), default=0)
        return KernelCost(flops=float(ops_per_elem) * n,
                          bytes=io_bytes(in_avals, out_avals))
    return cost


def _dq_cost(ops_per_elem, unpack=1):
    # payload elements expand by `unpack` on decode (twobit: 4 per byte)
    def cost(in_avals, out_avals):
        n = max((int(getattr(a, "size", 0)) for a in out_avals), default=0)
        if not n and in_avals:
            n = int(getattr(in_avals[0], "size", 0)) * unpack
        return KernelCost(flops=float(ops_per_elem) * n,
                          bytes=io_bytes(in_avals, out_avals))
    return cost


register_kernel(
    "quant_int8", _elemwise_cost(5), module=__name__,
    doc="per-chunk-scaled int8 quantize + scales (+ fused dequant "
        "round-trip) in one VMEM pass")
register_kernel(
    "quant_twobit", _elemwise_cost(5), module=__name__,
    doc="threshold ternarize + 4-per-byte pack (+ fused dequant) in one "
        "VMEM pass")
register_kernel(
    "dequant_sum_int8", _dq_cost(3), module=__name__,
    doc="int8 dequantize fused with the f32 row-sum accumulate")
register_kernel(
    "dequant_sum_twobit", _dq_cost(5, unpack=4), module=__name__,
    doc="twobit unpack/dequantize fused with the f32 row-sum accumulate")
register_kernel(
    "dequant_int8", _dq_cost(2), module=__name__,
    doc="blocked int8 dequantize (all-gather side)")
register_kernel(
    "dequant_twobit", _dq_cost(4, unpack=4), module=__name__,
    doc="blocked twobit unpack/dequantize (all-gather side)")
