"""Flash attention as Pallas TPU kernels (forward + backward).

Design (pallas_guide.md patterns): the softmax is computed online per
query-block with a running (max, sum) carried in VMEM scratch across the
key-block grid dimension — the full [seq, seq] score matrix never
materializes in HBM. Backward recomputes the probabilities from the saved
log-sum-exp (the flash-attention trick) in two kernels: one accumulating dq
over key blocks, one accumulating dk/dv over query blocks.

Replaces the dense ``attention_reference`` einsum path wherever attention is
the hot op (models/transformer.py); numerics are validated against the dense
path in tests/test_pallas.py on CPU via interpret mode.

On-chip rates (TPU v5e via tools/bench_flash.py, bf16 operands, s=16k,
full sweep in FLASH_r03.json; measured bf16 matmul peak 172 TF/s): d=128
fwd 136 TF/s (79% of matmul peak) / fwd+bwd 133 TF/s at the default
(block_q=512, block_k=2048); d=64 tops out at 68 TF/s fwd — the QK^T
contraction dim is half the MXU's 128 lanes, so half rate is the ceiling.
bf16 numerics vs dense f32: max abs err ~1e-3 fwd, rel ~0.5% on grads.

Grouped heads and windows: ``k``/``v`` may carry fewer heads than ``q``
(query head ``i`` reads key-value head ``i // (hq / hkv)``; the index maps
do the sharing, nothing is repeated in HBM), and ``window=W`` with
``causal=True`` lets query ``t`` see keys ``t - W + 1 .. t``. Key blocks
outside the causal band or the window are SKIPPED, not masked: the key
dimension of the grid only spans the blocks a query block can need
(``_kv_steps``), the index maps clamp to the last needed block so that a
skipped step moves nothing, and the body runs under ``pl.when``. The
backward kernels do the same over query blocks; ``flash_bwd_dkv``
accumulates a key-value head's gradient over the query heads that share
it. Block shapes are chosen in the wrapper from the length and the window
(``_choose_blocks``) unless a caller passes them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import REMAT_KEEP
from ._common import use_interpret as _use_interpret
from .registry import io_bytes, register_kernel

NEG_INF = -1e30  # large-negative instead of -inf: avoids inf-inf NaNs on VPU
_LANES = 128     # TPU lane count; m/l scratch is broadcast across lanes


def _mxu(x):
    """Matmul-operand dtype policy: keep the input dtype (bf16 runs the MXU
    at full rate; upcasting to f32 quarters it — accumulation is f32 via
    preferred_element_type either way). MXNET_TPU_FLASH_F32=1 restores the
    f32-operand kernels as an escape hatch for backends whose Mosaic builds
    mishandle bf16 tiles."""
    from ...base import env_int

    if env_int("MXNET_TPU_FLASH_F32", 0):
        return x.astype(jnp.float32)
    return x


def _cdiv(a, b):
    return -(-a // b)


def _kv_lo(qi, bq, bk, window):
    """First key block that query block ``qi`` can need."""
    if window is None:
        return 0
    return jnp.maximum(qi * bq - (window - 1), 0) // bk


def _kv_hi(qi, bq, bk, nk, causal):
    """Last key block that query block ``qi`` can need."""
    if not causal:
        return nk - 1
    return jnp.minimum((qi * bq + bq - 1) // bk, nk - 1)


def _kv_steps(bq, bk, nk, causal, window):
    """Static size of the grid's key dimension: the most key blocks one
    query block can need (a span of ``bq + window - 1`` keys)."""
    if not causal or window is None:
        return nk
    # a span that ends on a block boundary (bk divides bq) starts one
    # block later than one that may end anywhere
    return min(nk, _cdiv(bq + window - 1, bk) + (1 if bq % bk else 0))


def _q_lo(kj, bq, bk, causal):
    """First query block that can need key block ``kj``."""
    if not causal:
        return 0
    return (kj * bk) // bq


def _q_hi(kj, bq, bk, nq, window):
    """Last query block that can need key block ``kj``."""
    if window is None:
        return nq - 1
    return jnp.minimum((kj * bk + bk - 1 + window - 1) // bq, nq - 1)


def _q_steps(bq, bk, nq, causal, window):
    if window is None:
        return nq
    # under the causal mask the span starts on a block boundary where bq
    # divides bk
    return min(nq, _cdiv(bk + window - 1, bq)
               + (1 if bk % bq or not causal else 0))


def _kv_head(b, hq, hkv):
    """Row of the flattened (batch x key-value heads) arrays that row ``b``
    of the flattened (batch x query heads) arrays reads."""
    if hq == hkv:
        return b
    return (b // hq) * hkv + (b % hq) // (hq // hkv)


def _q_index(row, qi, hq, packed):
    """Block index of a query-side array for row ``row`` of the flattened
    (batch x query heads): heads a leading axis (``(batch x heads, seq,
    d)``) or, ``packed``, side by side on the last one (``(batch, seq,
    heads x d)``, as a projection leaves them: no transpose on the way in
    or out)."""
    return (row // hq, qi, row % hq) if packed else (row, qi, 0)


def _kv_index(row, kj, hkv, packed):
    """The same for a key-value-side array and a row of the flattened
    (batch x key-value heads)."""
    return (row // hkv, kj, row % hkv) if packed else (row, kj, 0)


def _block_mask(qi, kj, bq, bk, seq_k, causal, window):
    """[bq, bk] bool mask for this (query block, key block) tile."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k  # key-side padding
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


# --------------------------------------------------------------------------
# rotary positions and the head gate, inside the kernels
# --------------------------------------------------------------------------
# A query block is (bq, d) in VMEM whatever the array's layout in HBM, so
# the rotation and the gate cost no pass over HBM and no relayout there:
#   rotated q = q * cos + (q @ rot) * sin      (``rot`` a signed permutation)
#   gated   o = o * gate                       (one factor a row)
# ``extras`` is the static pair (rotary, gated); the refs they add follow
# the kernel's own inputs in the order cos, sin, rot, gate.

def _split_refs(refs, n_in, extras):
    """``(inputs, (cos, sin, rot), gate, rest)`` of a kernel's refs."""
    rotary, gated = extras
    ins, k = refs[:n_in], n_in
    rope = refs[k:k + 3] if rotary else None
    k += 3 if rotary else 0
    gate = refs[k] if gated else None
    k += 1 if gated else 0
    return ins, rope, gate, refs[k:]


def _rotated(x, rope):
    """``x`` (rows, d) rotated to its positions, in ``x``'s dtype."""
    if rope is None:
        return x
    cos_ref, sin_ref, rot_ref = rope
    turned = jax.lax.dot_general(
        x, rot_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos_ref[...]
            + turned * sin_ref[...]).astype(x.dtype)


def _unrotated(g, rope, dtype):
    """Gradient by the unrotated ``x`` from the gradient ``g`` (float32) by
    the rotated one: ``g * cos + (g * sin) @ rot^T``."""
    if rope is None:
        return g
    cos_ref, sin_ref, rot_ref = rope
    return g * cos_ref[...] + jax.lax.dot_general(
        (g * sin_ref[...]).astype(dtype), rot_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _gated(do, gate):
    """The gradient by the ungated output: ``do`` times the row's gate."""
    if gate is None:
        return do
    return (do.astype(jnp.float32) * gate[0]).astype(do.dtype)


def _extra_specs(extras, d, q_block_map, stat_map, bq):
    """BlockSpecs of the refs ``extras`` adds: cos and sin by the query
    block, the whole ``rot``, the gate as the row statistics are."""
    rotary, gated = extras
    specs = []
    if rotary:
        specs += [pl.BlockSpec((bq, d), q_block_map),
                  pl.BlockSpec((bq, d), q_block_map),
                  pl.BlockSpec((d, d), lambda *_: (0, 0))]
    if gated:
        specs.append(pl.BlockSpec((1, bq, 1), stat_map))
    return specs


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, window, bq, bk, seq_k, nk, steps,
                extras):
    (q_ref, k_ref, v_ref), rope, gate, rest = _split_refs(refs, 3, extras)
    o_ref, lse_ref, acc, m_scr, l_scr = rest[:5]
    q_scr = rest[5] if rope is not None else None
    qi, j = pl.program_id(1), pl.program_id(2)
    kj = _kv_lo(qi, bq, bk, window) + j

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        if rope is not None:     # once a query block, kept for its steps
            q_scr[...] = _rotated(q_ref[0], rope)

    @pl.when(kj <= _kv_hi(qi, bq, bk, nk, causal))
    def _body():
        # matmul operands per the _mxu policy; products accumulate f32
        q = _mxu(q_ref[0] if rope is None else q_scr[...])
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qi, kj, bq, bk, seq_k, causal, window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                    # [bq, 1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                   # [bq, bk] f32
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)          # [bq, 1]
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == steps - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.maximum(l, 1e-30)
        out = acc[:] / l_safe
        if gate is not None:
            out = out * gate[0]
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, :1] + jnp.log(l_safe)).astype(jnp.float32)


def _kv_block_map(hq, hkv, bq, bk, nk, causal, window, packed):
    """Index map of a key/value block on a (row, query block, step) grid:
    the step's key block, clamped to the last one needed so that a skipped
    step fetches nothing new."""
    def index(b, i, j):
        kj = jnp.minimum(_kv_lo(i, bq, bk, window) + j,
                         _kv_hi(i, bq, bk, nk, causal))
        return _kv_index(_kv_head(b, hq, hkv), kj, hkv, packed)

    return index


def _flash_fwd_padded(q, k, v, *, scale, causal, window, hq, hkv, bq, bk,
                      seq_k, interpret, packed=False, rope=None, gate=None):
    d = q.shape[2] // hq if packed else q.shape[2]
    bh = q.shape[0] * hq if packed else q.shape[0]
    sq = q.shape[1]
    nq, nk = sq // bq, k.shape[1] // bk
    steps = _kv_steps(bq, bk, nk, causal, window)
    extras = (rope is not None, gate is not None)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bk=bk, seq_k=seq_k,
                             nk=nk, steps=steps, extras=extras)
    kv_map = _kv_block_map(hq, hkv, bq, bk, nk, causal, window, packed)

    def q_map(b, i, j):
        return _q_index(b, i, hq, packed)

    def stat_map(b, i, j):
        return (b, i, 0)

    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ] + _extra_specs(extras, d, lambda b, i, j: (i, 0), stat_map, bq),
        out_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, 1), stat_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ] + ([pltpu.VMEM((bq, d), q.dtype)] if rope is not None else []),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *(rope or ()), *(() if gate is None else (gate,)))
    return o, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, window, bq, bk, seq_k, nk, steps,
                   extras):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope, gate, rest = \
        _split_refs(refs, 6, extras)
    dq_ref, dq_acc = rest[:2]
    q_scr = rest[2] if rope is not None else None
    qi, j = pl.program_id(1), pl.program_id(2)
    kj = _kv_lo(qi, bq, bk, window) + j

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        if rope is not None:
            q_scr[...] = _rotated(q_ref[0], rope)

    @pl.when(kj <= _kv_hi(qi, bq, bk, nk, causal))
    def _body():
        q = _mxu(q_ref[0] if rope is None else q_scr[...])
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        do = _mxu(_gated(do_ref[0], gate))
        lse = lse_ref[0]                         # [bq, 1]
        delta = delta_ref[0]                     # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qi, kj, bq, bk, seq_k, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _finalize():
        dq_ref[0] = _unrotated(dq_acc[:], rope, dq_ref.dtype).astype(
            dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, window, bq, bk, seq_k, nq, steps,
                    group, extras):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope, gate, rest = \
        _split_refs(refs, 6, extras)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    # the inner grid dimension walks the query heads that share this
    # key-value head, and within each the query blocks that can need
    # this key block
    kj, t = pl.program_id(1), pl.program_id(2)
    qi = _q_lo(kj, bq, bk, causal) + t % steps

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(qi <= _q_hi(kj, bq, bk, nq, window))
    def _body():
        # every step has another query block: rotated as it comes
        q = _mxu(_rotated(q_ref[0], rope))
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        do = _mxu(_gated(do_ref[0], gate))
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qi, kj, bq, bk, seq_k, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)        # [bq, bk] f32
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)   # [bq, bk]
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == group * steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_padded(q, k, v, o, lse, do, *, scale, causal, window, hq,
                      hkv, bq, bk, seq_k, interpret, packed=False, rope=None,
                      gate=None):
    """``(dq, dk, dv, delta)``. With a gate ``o`` is the gated output and
    ``delta`` (the row sums of ``do * o``) serves both the kernels and the
    gate's own gradient."""
    extras = (rope is not None, gate is not None)
    more = (*(rope or ()), *(() if gate is None else (gate,)))
    d = q.shape[2] // hq if packed else q.shape[2]
    bh = q.shape[0] * hq if packed else q.shape[0]
    bkv = k.shape[0] * hkv if packed else k.shape[0]
    sq, sk = q.shape[1], k.shape[1]
    nq, nk = sq // bq, sk // bk
    group = hq // hkv
    if packed:
        delta = jnp.sum(
            (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
                q.shape[0], sq, hq, d), axis=-1).transpose(0, 2, 1).reshape(
                    bh, sq, 1)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)

    kv_steps = _kv_steps(bq, bk, nk, causal, window)
    kv_map = _kv_block_map(hq, hkv, bq, bk, nk, causal, window, packed)

    def q_of_row(b, i, j):
        return _q_index(b, i, hq, packed)

    def stat_of_row(b, i, j):
        return (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=seq_k, nk=nk,
                          steps=kv_steps, extras=extras),
        grid=(bh, nq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_of_row),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, d), q_of_row),
            pl.BlockSpec((1, bq, 1), stat_of_row),
            pl.BlockSpec((1, bq, 1), stat_of_row),
        ] + _extra_specs(extras, d, lambda b, i, j: (i, 0), stat_of_row, bq),
        out_specs=pl.BlockSpec((1, bq, d), q_of_row),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]
        + ([pltpu.VMEM((bq, d), q.dtype)] if rope is not None else []),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta, *more)

    q_steps = _q_steps(bq, bk, nq, causal, window)

    def q_row(b, j, t):
        # row of the flattened (batch x query heads) and query block of
        # step t: this key-value head's ``t // q_steps``-th query head;
        # block clamped to the last one needed
        head = b if hq == hkv else \
            (b // hkv) * hq + (b % hkv) * group + t // q_steps
        qi = jnp.minimum(_q_lo(j, bq, bk, causal) + t % q_steps,
                         _q_hi(j, bq, bk, nq, window))
        return head, qi

    def q_map(b, j, t):
        return _q_index(*q_row(b, j, t), hq, packed)

    def stat_map(b, j, t):
        return (*q_row(b, j, t), 0)

    def kv_of_row(b, j, t):
        return _kv_index(b, j, hkv, packed)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=seq_k, nq=nq,
                          steps=q_steps, group=group, extras=extras),
        grid=(bkv, nk, group * q_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_of_row),
            pl.BlockSpec((1, bk, d), kv_of_row),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, 1), stat_map),
            pl.BlockSpec((1, bq, 1), stat_map),
        ] + _extra_specs(extras, d, lambda b, j, t: (q_row(b, j, t)[1], 0),
                         stat_map, bq),
        out_specs=[
            pl.BlockSpec((1, bk, d), kv_of_row),
            pl.BlockSpec((1, bk, d), kv_of_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta, *more)
    return dq, dk, dv, delta


# --------------------------------------------------------------------------
# public entry: padding + custom VJP
# --------------------------------------------------------------------------

def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# static configuration of one call: (causal, window, hq, hkv, bq, bk,
# interpret, packed), hashable so that it rides as one non-differentiable
# argument. ``rope`` is ``None`` or ``(cos, sin, rot)`` (tables (seq, d)
# float32, ``rot`` (d, d)): the QUERY is rotated inside the kernels; ``gate``
# is ``None`` or the logits (batch x heads, seq, 1) of a sigmoid gate on the
# output, a factor a head and position.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash(q, k, v, rope, gate, cfg):
    return _flash_fwd(q, k, v, rope, gate, cfg)[0]


def _flash_fwd(q, k, v, rope, gate, cfg):
    causal, window, hq, hkv, bq, bk, interpret, packed = cfg
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[2] // hq if packed else q.shape[2]
    scale = 1.0 / (d ** 0.5)
    # Blocks span the full head_dim, so any d equal to the array dim lowers
    # fine; Mosaic pads lanes in VMEM itself without extra HBM traffic.
    # Only round tiny/odd head dims up to a sublane multiple (packed heads
    # are whole lane tiles already).
    dm = 1 if packed else 8 if d >= 8 else d
    qp = _pad_to(_pad_to(q, 2, dm), 1, bq)
    kp = _pad_to(_pad_to(k, 2, dm), 1, bk)
    vp = _pad_to(_pad_to(v, 2, dm), 1, bk)
    ropep = None if rope is None else (
        _pad_to(rope[0], 0, bq), _pad_to(rope[1], 0, bq),
        rope[2].astype(q.dtype))
    factor = None if gate is None else _pad_to(
        jax.nn.sigmoid(gate.astype(jnp.float32)), 1, bq)
    o, lse = _flash_fwd_padded(qp, kp, vp, scale=scale, causal=causal,
                               window=window, hq=hq, hkv=hkv, bq=bq, bk=bk,
                               seq_k=sk, interpret=interpret, packed=packed,
                               rope=ropep, gate=factor)
    # under a recomputation segment (executor._remat_segments) the output
    # and the row statistics are kept: recomputing them is this kernel again
    o = checkpoint_name(o, REMAT_KEEP)
    lse = checkpoint_name(lse, REMAT_KEEP)
    return o[:, :sq, :q.shape[2]], (qp, kp, vp, o, lse, ropep, factor, rope,
                                     gate, scale, sq, sk, q.shape[2])


def _flash_bwd(cfg, res, g):
    causal, window, hq, hkv, bq, bk, interpret, packed = cfg
    qp, kp, vp, o, lse, ropep, factor, rope, gate, scale, sq, sk, d = res
    gp = _pad_to(_pad_to(g, 2, qp.shape[-1]), 1, bq)  # match residual padding
    dq, dk, dv, delta = _flash_bwd_padded(
        qp, kp, vp, o, lse, gp, scale=scale, causal=causal, window=window,
        hq=hq, hkv=hkv, bq=bq, bk=bk, seq_k=sk, interpret=interpret,
        packed=packed, rope=ropep, gate=factor)
    dkv = kp.shape[2] if packed else d
    d_rope = None if rope is None else tuple(jnp.zeros_like(a) for a in rope)
    # o is the gated output: sum(do * o) = s * sum(do * ungated), and the
    # sigmoid's slope is s (1 - s)
    d_gate = None if gate is None else \
        (delta * (1.0 - factor))[:, :sq].astype(gate.dtype)
    return dq[:, :sq, :d], dk[:, :sk, :dkv], dv[:, :sk, :dkv], d_rope, d_gate


_flash.defvjp(_flash_fwd, _flash_bwd)


def _choose_blocks(causal, window):
    """Block shapes where the caller names none. Without a mask the blocks
    that reached 64.5 % of the roofline on a v5e (PERF.md, PR 26). Under a
    causal mask square blocks, so that the blocks above the diagonal are
    whole and skipped (512 x 2,048 could skip a quarter of them at 4,096
    positions, and saved 7 %). With a window the block is the window
    rounded up to a power of two inside 128..512: a query block then needs
    two or three key blocks whatever the length."""
    if not causal:
        return 512, 2048
    if window is None:
        return 1024, 1024
    b = 128
    while b < min(window, 512):
        b *= 2
    return b, b


def _blocks(q, k, block_q, block_k):
    bq = min(block_q, max(8, q.shape[2]))
    bk = min(block_k, max(8, k.shape[2]))
    return bq, bk


def flash_attention_with_lse(q, k, v, causal=False, block_q=512,
                             block_k=2048, interpret=None):
    """Forward flash returning ``(o, lse)`` with lse = log-sum-exp of the
    scaled scores per query row, shape [b, h, seq].

    The lse output is what makes per-shard results mergeable across a ring
    (parallel.sequence.ring_flash_attention): softmax over a sequence split
    into blocks recombines exactly from per-block (o, lse) pairs. Not
    differentiable — the ring layer owns the custom VJP."""
    if interpret is None:
        interpret = _use_interpret()
    b, h, sq, d = q.shape
    bq, bk = _blocks(q, k, block_q, block_k)
    o, res = _flash_fwd(q.reshape(b * h, sq, d),
                        k.reshape(b * h, k.shape[2], d),
                        v.reshape(b * h, v.shape[2], d), None, None,
                        (causal, None, h, h, bq, bk, interpret, False))
    lse = res[4][:, :sq, 0]
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def flash_block_grads(q, k, v, o, lse, do, causal=False, block_q=512,
                      block_k=2048, interpret=None):
    """Backward of one attention block given the GLOBAL (o, lse).

    This is flash attention's decomposition property: with p recomputed as
    exp(s - lse_global), each key/value shard's (dq, dk, dv) contribution is
    exact, so a ring backward is a sum of per-block calls. q rows beyond
    seq pad with zeros (their do is zero, so contributions vanish)."""
    if interpret is None:
        interpret = _use_interpret()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _blocks(q, k, block_q, block_k)
    scale = 1.0 / (d ** 0.5)
    dm = 8 if d >= 8 else d

    def p3(x, axis_mult):
        return _pad_to(_pad_to(x.reshape(b * h, x.shape[2], d), 2, dm),
                       1, axis_mult)

    qp, op, dop = p3(q, bq), p3(o, bq), p3(do, bq)
    kp, vp = p3(k, bk), p3(v, bk)
    # pad lse with 0: padded q rows are zero, so s=0, p=exp(0-0)=1, but
    # do=0 there makes every gradient contribution vanish
    lsep = _pad_to(lse.reshape(b * h, sq, 1), 1, bq)
    dq, dk, dv, _ = _flash_bwd_padded(qp, kp, vp, op, lsep, dop, scale=scale,
                                      causal=causal, window=None, hq=h,
                                      hkv=h, bq=bq, bk=bk, seq_k=sk,
                                      interpret=interpret)
    return (dq[:, :sq, :d].reshape(b, h, sq, d),
            dk[:, :sk, :d].reshape(b, h, sk, d),
            dv[:, :sk, :d].reshape(b, h, sk, d))


def rotary_tables(seq, head_dim, inv_freq, attention_factor=1.0):
    """``(cos, sin, rot)`` for ``flash_attention(rotary=...)``: the first
    ``2 * len(inv_freq)`` dimensions of a head rotate, dimension ``i``
    paired with ``i + len(inv_freq)``; the rest pass through (cos 1, sin
    0). ``rot`` is the signed permutation with ``x @ rot = (-x2, x1, 0)``."""
    import numpy as np

    half = len(inv_freq)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    still = head_dim - 2 * half
    cos = jnp.cos(angle) * attention_factor
    sin = jnp.sin(angle) * attention_factor
    rot = np.zeros((head_dim, head_dim), np.float32)
    rot[np.arange(half) + half, np.arange(half)] = -1.0
    rot[np.arange(half), np.arange(half) + half] = 1.0
    return (jnp.concatenate([cos, cos, jnp.ones((seq, still))], axis=1),
            jnp.concatenate([sin, sin, jnp.zeros((seq, still))], axis=1),
            jnp.asarray(rot))


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None, window=None, heads_last=False,
                    rotary=None, gate=None):
    """Blocked flash attention. q: [batch, heads, seq, head_dim]; k, v:
    [batch, kv_heads, seq, head_dim] with ``heads`` a multiple of
    ``kv_heads`` (query head ``i`` reads key-value head ``i // (heads /
    kv_heads)``). ``window=W`` (with ``causal=True``) lets query ``t`` see
    keys ``t - W + 1 .. t``; key blocks outside the band are skipped.

    ``heads_last``: q [batch, seq, heads, head_dim], k, v [batch, seq,
    kv_heads, head_dim], as a projection's rows are, and the output in the
    same layout; with a head_dim of whole lane tiles (a multiple of 128)
    the kernels read and write that layout directly, and nothing is
    transposed in HBM.

    ``rotary=(cos, sin, rot)`` (``rotary_tables``) rotates the QUERY to its
    positions inside the kernels (the caller rotates the keys, which are
    few); ``gate`` [batch, seq, heads] (``heads_last``) multiplies every
    head's output by ``sigmoid(gate)``, a factor a head and position. Both
    need ``heads_last`` and whole lane tiles: what they save is the pass
    over HBM and the relayout of the widest tensor of the layer.

    Exact (up to fp accumulation order) match of the dense masked softmax
    attention, with O(block) VMEM footprint. Differentiable via Pallas
    backward kernels. Block shapes are chosen here from the mask
    (``_choose_blocks``) unless given. On non-TPU backends defaults to
    interpret mode so the same kernel code runs in tests.
    """
    if interpret is None:
        interpret = _use_interpret()
    if (rotary is not None or gate is not None) and not (
            heads_last and q.shape[3] % _LANES == 0):
        raise ValueError("flash_attention: rotary and gate need heads_last "
                         f"and a head_dim that is a multiple of {_LANES}")
    if heads_last and q.shape[3] % _LANES:
        # narrow heads cannot be cut out of the packed last axis
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal, block_q,
                            block_k, interpret, window)
        return o.transpose(0, 2, 1, 3)
    seq_axis, head_axis = (1, 2) if heads_last else (2, 1)
    b, d = q.shape[0], q.shape[3]
    hq, sq = q.shape[head_axis], q.shape[seq_axis]
    hkv, sk = k.shape[head_axis], k.shape[seq_axis]
    if hq % hkv or v.shape[head_axis] != hkv:
        raise ValueError(f"flash_attention: {hq} query heads over "
                         f"{hkv} / {v.shape[head_axis]} key / value heads")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_attention: a window needs causal=True "
                             "and at least one key")
        if window >= sk:
            window = None   # the causal mask alone
    chosen = _choose_blocks(causal, window)
    bq = min(chosen[0] if block_q is None else block_q, max(8, sq))
    bk = min(chosen[1] if block_k is None else block_k, max(8, sk))
    cfg = (causal, window, hq, hkv, bq, bk, interpret, heads_last)
    # pad seq blocks up so bq | sq_padded handled inside _flash_fwd
    if heads_last:
        if gate is not None:     # as the row statistics lie: a row a head
            gate = gate.transpose(0, 2, 1).reshape(b * hq, sq, 1)
        o = _flash(q.reshape(b, sq, hq * d), k.reshape(b, sk, hkv * d),
                   v.reshape(b, sk, hkv * d), rotary, gate, cfg)
        return o.reshape(b, sq, hq, d)
    o = _flash(q.reshape(b * hq, sq, d), k.reshape(b * hkv, sk, d),
               v.reshape(b * hkv, sk, d), None, None, cfg)
    return o.reshape(b, hq, sq, d)


# --------------------------------------------------------------------------
# registry cost models (ops/pallas/registry.py contract)
# --------------------------------------------------------------------------
# Model FLOPs from the FULL (padded) avals — exact trace-time arithmetic,
# comparable across runs. Counts the matmul work (the softmax elementwise
# tail is <1% at any real head_dim); causal masking is NOT discounted so
# the number matches the dense attention it replaces (MFU convention:
# model FLOPs, not grid-cell recompute).

def _flash_dims(in_avals):
    q, k = in_avals[0], in_avals[1]
    bh, sq, d = q.shape
    sk = k.shape[1]
    return int(bh), int(sq), int(sk), int(d)


def _flash_fwd_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d = _flash_dims(in_avals)
    # QK^T and PV: 2 contractions of 2*sq*sk*d each, per batch*head slab
    return KernelCost(flops=4.0 * bh * sq * sk * d,
                      bytes=io_bytes(in_avals, out_avals))


def _flash_bwd_dq_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d = _flash_dims(in_avals)
    # recomputed scores + dp + dq accumulation: 3 contractions
    return KernelCost(flops=6.0 * bh * sq * sk * d,
                      bytes=io_bytes(in_avals, out_avals))


def _flash_bwd_dkv_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d = _flash_dims(in_avals)
    # recomputed scores + dp + dv + dk accumulations: 4 contractions
    return KernelCost(flops=8.0 * bh * sq * sk * d,
                      bytes=io_bytes(in_avals, out_avals))


register_kernel(
    "flash_fwd", _flash_fwd_cost, module=__name__,
    doc="blocked online-softmax attention forward (o, lse)")
register_kernel(
    "flash_bwd_dq", _flash_bwd_dq_cost, module=__name__,
    doc="flash attention backward: dq accumulated over key blocks")
register_kernel(
    "flash_bwd_dkv", _flash_bwd_dkv_cost, module=__name__,
    doc="flash attention backward: dk/dv accumulated over query blocks")
