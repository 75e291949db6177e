"""The expert layer's way to its sorted space and back, as Pallas kernels.

``MixtureOfExperts`` (ops/decoder.py) sorts the picks of a step by expert
into a space of ``rows * top_k`` entries of which only the first ``n``
hold a pick on an expert kept on this rank; ``n`` is known on the device
only. Two kernels move rows between the two orders, and both do work in
proportion to ``n``, not to the space, with no control flow in the program
around them: ``n`` and the entries' rows arrive as prefetched scalars, a
grid step behind ``n`` does nothing, and its index maps are clamped to the
last live tile so that it moves nothing either.

- ``moe_dispatch``: ``out[i] = x[token[i]] (* scale[i])`` for the sorted
  entries ``i < n``. The tile that straddles ``n`` is zero behind it;
  tiles wholly behind ``n`` are never written: what lies there is whatever
  the buffer held.
- ``moe_combine``: ``out[r] = sum of weight[i] * y[i]`` in float32 over
  the sorted entries ``i < n`` with ``token[i] == r``, in the order of the
  sorted space; nothing behind ``n`` is read, so nothing there has to be
  finite, and a row none of whose picks is held comes out zero.

Each is the forward of one direction and the gradient of the other (the
custom VJPs in ops/decoder.py). Mosaic moves memory by whole tiles of 8
sublanes (a single row is no DMA it accepts), so rows move through VMEM:
the grid's outer dimension walks the width by chunks of lanes, the rows'
side of a chunk (``x``, or the sum's accumulator) stays in VMEM while the
inner dimension walks the sorted tiles, and an entry is one dynamic
sublane load and store a vector register. Rows of 16 bits lie two to a
32-bit word (an even row in the low half), so they are taken from and put
into the words they share.

Off the chip both run through the Pallas interpreter
(``resolve_interpret``), which fills unwritten outputs with NaN: the tests
there see what a consumer reading behind ``n`` would get. Every wrapper
leaves one zero-length ``moe.space`` record of ``telemetry.phase`` when it
is traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import MXNetError
from ._common import resolve_interpret
from .registry import KernelCost, register_kernel

__all__ = ["moe_dispatch", "moe_combine", "space_tile"]

# Swept on a v5e at 8,192 rows of 2,048 bfloat16, top-8, an eighth of the
# space held (PERF.md section 6, PR 30): the entries' loop is what costs, so
# few wide chunks and a body of several entries win; the tile hardly matters.
_TILE = 1024            # sorted entries a grid step
_RESIDENT = 16 << 20    # bytes of a chunk's rows kept in VMEM
_UNROLL = 4             # entries (pairs of 16-bit rows) a loop iteration
_LANES = 128


def space_tile(space):
    """Sorted entries a grid step of either kernel over ``space``."""
    return min(_TILE, space)


def _cdiv(a, b):
    return -(-a // b)


def _chunk(rows, width, itemsize):
    """Lanes a chunk: all of ``width`` if the rows' side fits the budget,
    else as many whole vector registers of lanes as do."""
    if rows * width * itemsize <= _RESIDENT or width <= _LANES:
        return width
    return max(_RESIDENT // (rows * itemsize) // _LANES, 1) * _LANES


def _packed(dtype):
    size = jnp.dtype(dtype).itemsize
    if size not in (2, 4):
        raise MXNetError(f"expert dispatch: rows of {jnp.dtype(dtype).name} "
                         f"(2 or 4 bytes an element are moved)")
    return size == 2


def _space_record(kernel, rows, top_k, tile, tiles_max):
    from ... import telemetry

    with telemetry.phase("moe.space", kernel=kernel, rows=rows, top_k=top_k,
                         tile=tile, tiles_max=tiles_max):
        pass


def _live_map(tile, column):
    """Index map of a sorted tile: a step behind the count stays on the
    last live tile (the first, when nothing is held)."""
    def index(c, i, token_ref, n_ref, *more_scalars):
        last = jax.lax.div(jnp.maximum(n_ref[0], 1) - 1, jnp.int32(tile))
        return jnp.minimum(i, last), (c if column else 0)
    return index


def _each(body, count):
    """``body(i)`` for ``i < count``, ``_UNROLL`` to a loop iteration: the
    last iteration may run up to ``_UNROLL - 1`` past ``count``, so a body
    bounds what it touches and adds nothing there."""
    def group(q, carry):
        for u in range(_UNROLL):
            body(q * _UNROLL + u)
        return carry

    jax.lax.fori_loop(0, jax.lax.div(count + (_UNROLL - 1),
                                     jnp.int32(_UNROLL)), group, 0)


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _row(ref, i):
    return ref[pl.ds(i, 1), :]


# -- dispatch -----------------------------------------------------------------

def _dispatch_kernel(token_ref, n_ref, x_ref, *refs, tile, space, scaled,
                     packed):
    out_ref = refs[1 if scaled else 0]
    base = pl.program_id(1) * tile
    n = n_ref[0]

    # the first tile is live whatever n is: with no pick held it is zeros
    @pl.when(base < jnp.maximum(n, 1))
    def _():
        live = jnp.minimum(n - base, tile)
        if packed:
            words, stage = x_ref.bitcast(jnp.uint32), refs[-1]

            def half(p):
                """Entry ``p``'s row, in the low halves of a row of words."""
                t = token_ref[jnp.minimum(base + p, space - 1)]
                word = _row(words, t >> 1)
                return jax.lax.shift_right_logical(
                    word, jnp.full_like(word, _u32(16 * (t & 1)))) \
                    & _u32(0xFFFF)

            def pair(m):
                m = jnp.minimum(m, tile // 2 - 1)
                stage[pl.ds(m, 1), :] = half(2 * m) | jax.lax.shift_left(
                    half(2 * m + 1), _u32(16))

            _each(pair, (live + 1) >> 1)
            v = pltpu.bitcast(stage[...], out_ref.dtype)
        else:
            def one(r):
                r = jnp.minimum(r, tile - 1)
                out_ref[pl.ds(r, 1), :] = _row(
                    x_ref, token_ref[jnp.minimum(base + r, space - 1)])

            _each(one, live)
            v = out_ref[...]
        if scaled:
            v = (v.astype(jnp.float32) * refs[0][...]).astype(v.dtype)
        entry = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
        out_ref[...] = jnp.where(entry < live, v, jnp.zeros_like(v))


def _pad_rows(a, multiple):
    short = -a.shape[0] % multiple
    return jnp.pad(a, ((0, short), (0, 0))) if short else a


def _vmem(*nbytes):
    """A limit for the scoped VMEM: what the blocks take, the pipeline's
    second buffer of each included, and room for the body's values."""
    return int(min(2 * sum(nbytes) + (8 << 20), 100 << 20))


def moe_dispatch(x, token, n, scale=None, *, interpret=None):
    """``x`` (rows, w) to the sorted space: (len(token), w) with
    ``out[i] = x[token[i]]`` for ``i < n``, times ``scale[i]`` (float32,
    the product in float32) when given. ``token`` (space,) int32, ``n`` a
    traced int32 scalar."""
    space, (rows, width) = token.shape[0], x.shape
    packed = _packed(x.dtype)
    size = x.dtype.itemsize
    # 16-bit rows are read and written by the words that hold two
    align = 16 if packed else 1
    x = _pad_rows(x, align)
    padded = space + -space % align
    tile = space_tile(padded)
    tiles = _cdiv(padded, tile)
    lanes = _chunk(x.shape[0], width, size)
    chunks = _cdiv(width, lanes)
    _space_record("moe_dispatch", rows, space // rows, tile, tiles)
    scaled = scale is not None
    operands = [x] + ([_pad_rows(
        scale.astype(jnp.float32).reshape(space, 1), align)]
        if scaled else [])
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, tile=tile, space=space,
                          scaled=scaled, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunks, tiles),
            in_specs=[pl.BlockSpec((x.shape[0], lanes),
                                   lambda c, i, token_ref, n_ref: (0, c))]
            + ([pl.BlockSpec((tile, 1), _live_map(tile, False))]
               if scaled else []),
            out_specs=pl.BlockSpec((tile, lanes), _live_map(tile, True)),
            scratch_shapes=[pltpu.VMEM((tile // 2, lanes), jnp.uint32)]
            if packed else []),
        out_shape=jax.ShapeDtypeStruct((padded, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(x.shape[0] * lanes * size,
                                   tile * lanes * size)),
        interpret=resolve_interpret(interpret),
        name="moe_dispatch",
    )(token.astype(jnp.int32), jnp.reshape(n, (1,)).astype(jnp.int32),
      *operands)
    return out if padded == space else out[:space]


# -- combine ------------------------------------------------------------------

def _combine_kernel(token_ref, n_ref, *refs, tile, space, weighted, packed):
    weight_ref = refs[0] if weighted else None
    y_ref, out_ref, acc = refs[-3:]
    i = pl.program_id(1)
    base = i * tile
    n = n_ref[0]

    @pl.when(i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(base < n)
    def _():
        live = jnp.minimum(n - base, tile)

        def add(p, row):
            """``row`` (1, lanes) float32 of entry ``p`` onto its token; an
            entry behind the count adds zero to some row."""
            at = jnp.minimum(base + p, space - 1)
            if weighted:
                row = row * weight_ref[at]
            t = token_ref[at]
            acc[pl.ds(t, 1), :] = _row(acc, t) + jnp.where(p < live, row, 0.0)

        if packed:
            words = y_ref.bitcast(jnp.uint32)

            def pair(m):
                word = _row(words, jnp.minimum(m, tile // 2 - 1))
                add(2 * m, jax.lax.bitcast_convert_type(
                    jax.lax.shift_left(word, _u32(16)), jnp.float32))
                add(2 * m + 1, jax.lax.bitcast_convert_type(
                    word & _u32(0xFFFF0000), jnp.float32))

            _each(pair, (live + 1) >> 1)
        else:
            _each(lambda r: add(r, _row(y_ref, jnp.minimum(r, tile - 1))
                                .astype(jnp.float32)), live)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def moe_combine(y, token, n, rows, weight=None, *, interpret=None):
    """The sorted space ``y`` (space, w) back to ``rows`` rows: ``out[r]``
    the sum of ``weight[i] * y[i]`` (of ``y[i]`` without ``weight``) over
    the entries ``i < n`` with ``token[i] == r``, accumulated in float32
    in the order of the space, as ``y``'s type. ``weight`` (space,)
    float32 by sorted entry."""
    packed = _packed(y.dtype)
    size = y.dtype.itemsize
    y = _pad_rows(y, 16 if packed else 1)
    space, width = y.shape
    tile = space_tile(space)
    tiles = _cdiv(space, tile)
    lanes = _chunk(rows, width, 4)
    chunks = _cdiv(width, lanes)
    _space_record("moe_combine", rows, space // rows, tile, tiles)
    weighted = weight is not None
    scalars = [token.astype(jnp.int32),
               jnp.reshape(n, (1,)).astype(jnp.int32)] \
        + ([weight.astype(jnp.float32)] if weighted else [])

    return pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, space=space,
                          weighted=weighted, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(chunks, tiles),
            in_specs=[pl.BlockSpec((tile, lanes), _live_map(tile, True))],
            out_specs=pl.BlockSpec((rows, lanes),
                                   lambda c, i, *scalar_refs: (0, c)),
            scratch_shapes=[pltpu.VMEM((rows, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, width), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(rows * lanes * (2 + size),
                                   tile * lanes * size)),
        interpret=resolve_interpret(interpret),
        name="moe_combine",
    )(*scalars, y)


# -- cost models --------------------------------------------------------------
# The count is a value of the step, not of the shapes: the models price a
# step in which every pick is held here (the most a call can move), each
# entry's row read once and written once (so ``x``, whose rows are read
# top_k times then, counts as the output does).

def _nbytes(aval):
    return float(aval.size) * aval.dtype.itemsize


def _dispatch_cost(in_avals, out_avals):
    out = out_avals[0]
    return KernelCost(flops=float(out.size) * (len(in_avals) > 3),
                      bytes=2.0 * _nbytes(out)
                      + sum(_nbytes(a) for a in in_avals[:1] + in_avals[3:]))


def _combine_cost(in_avals, out_avals):
    y, out = in_avals[-1], out_avals[0]
    return KernelCost(flops=2.0 * y.size,
                      bytes=_nbytes(y) + _nbytes(out)
                      + sum(_nbytes(a) for a in in_avals[:1] + in_avals[2:-1]))


register_kernel(
    "moe_dispatch", _dispatch_cost, module=__name__,
    doc="rows to the expert-sorted space: a row a held pick, tiles behind "
        "the step's count skipped")
register_kernel(
    "moe_combine", _combine_cost, module=__name__,
    doc="the expert-sorted space back to rows: float32 sum over each row's "
        "held picks, nothing behind the step's count read")
