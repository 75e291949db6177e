"""Flash attention as Pallas TPU kernels (forward + backward).

Design (pallas_guide.md patterns): the softmax is computed online per
query-block with a running (max, sum) carried in VMEM scratch across the
key-block grid dimension — the full [seq, seq] score matrix never
materializes in HBM. Backward recomputes the probabilities from the saved
log-sum-exp (the flash-attention trick) once a tile, in ONE kernel,
``flash_bwd_dkv``: its grid walks a query head a row, the key blocks, and
within each the query blocks that can need it; dk/dv accumulate over the
query blocks (over the whole sequence where consecutive rows share a
key-value head), and dq over the key blocks in a float32 VMEM accumulator
that holds the row's whole sequence. Where what it must hold does not fit
its budget (``_fused_vmem``: a long sequence), dq is computed by a second
kernel, ``flash_bwd_dq``, that walks the key blocks of each query block
and computes the probabilities again; ``flash_bwd_dkv`` then walks a
key-value head a row. The shapes alone choose.

Replaces the dense ``attention_reference`` einsum path wherever attention is
the hot op (models/transformer.py); numerics are validated against the dense
path in tests/test_pallas.py on CPU via interpret mode.

On-chip rates, block and sub-tile shapes as swept on a TPU v5e: PERF.md
(sections 5 and 7). bf16 numerics vs dense f32: max abs err ~1e-3 fwd, rel
~0.5% on grads.

Grouped heads and windows: ``k``/``v`` may carry fewer heads than ``q``
(query head ``i`` reads key-value head ``i // (hq / hkv)``; the index maps
do the sharing, nothing is repeated in HBM), and ``window=W`` with
``causal=True`` lets query ``t`` see keys ``t - W + 1 .. t``. The band (the
causal diagonal, the window's far edge, the padding of the last key block)
sorts every (query block, key block) tile into one of three classes, from
the block indices alone:

- outside: no pair of the tile is in the band. Never visited: the key
  dimension of the grid only spans the blocks a query block can need
  (``_kv_steps``), and the index maps clamp to the last needed block so
  that a skipped step moves nothing.
- inside: every pair is in the band. Computed whole, with no mask work.
- crossing an edge: walked by sub-tiles of ``_SUB[kernel]`` a side
  (``_tile_classes``): a sub-tile outside the band is not computed, one
  inside runs unmasked, and only the ones an edge passes through build a
  mask, from the terms that edge needs. The offset of a tile's first query
  from its first key takes few values over a grid, so each pattern is
  static code under its own ``pl.when`` (``_band_keys``).

A staircase (``step=B`` with ``causal=True``) moves the causal edge from
the diagonal to blocks of ``B`` positions: query ``t`` sees the keys of its
own block and of every block before it, ``k // B <= t // B`` (``step=1`` is
the diagonal). ``halves=2`` is the mask of block-diffusion training
(Arriola et al., arXiv:2503.09573): queries and keys are a sequence's noisy
copy followed by its clean copy, ``2 * seq`` positions; a clean query sees
the clean keys under the staircase, a noisy query the clean keys of the
blocks BEFORE its own (``k // B < t // B``) and the noisy keys of its own
block, one softmax over both. A query block's own noisy block is one more
key step of the grid, whose tile is the block diagonal; every tile is still
outside, inside or walked by sub-tiles, its pattern told by one more term,
the ``kind`` of its quadrant.

The backward kernel does the same over query blocks; ``flash_bwd_dkv``
accumulates a key-value head's gradient over the query heads that share
it. Block shapes are chosen in the wrapper from the mask
(``_choose_blocks``) unless a caller passes them. ``band_tiles`` counts
what a call computes; every kernel's wrapper leaves the count as one
``flash.band`` record of ``telemetry.phase`` when it is traced.
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


# Block indices are counts, never negative, so the truncating division is
# the floor. On a traced integer ``//`` and ``%`` lower to a dozen
# instructions each (signs, compares, a select), and Mosaic's lowering of
# every one of them is traced anew in every index map of every kernel: it
# was a third of the time the train program takes to lower (PERF.md, PR 28).
def _div(a, b):
    return jax.lax.div(a, jnp.int32(b))


def _mod(a, b):
    return jax.lax.rem(a, jnp.int32(b))


def _kv_lo(qi, bq, bk, window):
    """First key block that query block ``qi`` can need."""
    if window is None:
        return 0
    return _div(jnp.maximum(qi * bq - (window - 1), 0), bk)


def _kv_hi(qi, bq, bk, nk, causal, before=0):
    """Last key block that query block ``qi`` can need; ``before`` (0 or a
    staircase's step, static or traced) where a query sees only the blocks
    before its own: the block's last query then sees ``before`` keys fewer
    (blocks are whole steps, and longer than one)."""
    if not causal:
        return nk - 1
    return jnp.minimum(_div(qi * bq + bq - 1 - before, bk), nk - 1)


def _kv_steps(bq, bk, nk, causal, window):
    """Static size of the grid's key dimension: the most key blocks one
    query block can need (a span of ``bq + window - 1`` keys)."""
    if not causal or window is None:
        return nk
    # a span that ends on a block boundary (bk divides bq) starts one
    # block later than one that may end anywhere
    return min(nk, _cdiv(bq + window - 1, bk) + (1 if bq % bk else 0))


def _q_lo(kj, bq, bk, causal, before=0):
    """First query block that can need key block ``kj`` (``before`` as in
    ``_kv_hi``: the block's first key is seen ``before`` queries later)."""
    if not causal:
        return 0
    return _div(kj * bk + before, bq)


def _q_hi(kj, bq, bk, nq, window):
    """Last query block that can need key block ``kj``."""
    if window is None:
        return nq - 1
    return jnp.minimum(_div(kj * bk + bk - 1 + window - 1, bq), nq - 1)


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
    return _div(b, hq) * hkv + _div(_mod(b, hq), hq // hkv)


def _q_index(row, qi, hq, packed):
    """Block index of a query-side array for row ``row`` of the flattened
    (batch x query heads): heads a leading axis (``(batch x heads, seq,
    d)``) or, ``packed``, side by side on the last one (``(batch, seq,
    heads x d)``, as a projection leaves them: no transpose on the way in
    or out)."""
    return (_div(row, hq), qi, _mod(row, hq)) if packed else (row, qi, 0)


def _kv_index(row, kj, hkv, packed):
    """The same for a key-value-side array and a row of the flattened
    (batch x key-value heads)."""
    return (_div(row, hkv), kj, _mod(row, hkv)) if packed else (row, kj, 0)


# --------------------------------------------------------------------------
# the band by sub-tiles
# --------------------------------------------------------------------------
# A (bq, bk) tile is cut into sub-tiles of (sub_q, sub_k). Over a sub-tile
# whose first query lies ``d`` positions after its first key, ``q - k``
# takes every value in d - (sub_k - 1) .. d + (sub_q - 1); the band is
# 0 <= q - k (causal), q - k < window, k < seq_k. A sub-tile's class is
# ``None`` (no pair in the band: not computed) or the terms of its mask,
# ``()`` where every pair is in the band:
#   ("ge", c)   row - col >= c      the causal edge
#   ("lt", c)   row - col < c       the window's far edge
#   ("col", c)  col < c             the padding of the last key block
#   ("eq", c)   row - col == c      a query's own block (``halves=2``)
# with row and col counted from the sub-tile's own corner, so that the
# sub-tiles an edge cuts alike share one mask. Under a staircase of ``step``
# positions "ge" and "eq" compare the FIRST positions of the blocks that
# row and col lie in (``_masks``); blocks, sub-tiles and sequences are whole
# steps, so a corner is a block's first position and ``q - k`` of the
# blocks' firsts takes the multiples of ``step`` in d - (sub_k - step) ..
# d + (sub_q - step). Step 1 is the diagonal.

# side of a sub-tile, a kernel (PERF.md section 7 has the sweep): the
# forward pass, bound by its vector work, gains most from computing least;
# the backward kernels, bound by their products, from products of 256 rows
_SUB = {"flash_fwd": 128, "flash_bwd_dq": 256, "flash_bwd_dkv": 256}
# a pair of latent attention's heads walks larger sub-tiles: each piece of
# a body is traced and lowered in every layer, and a pair's piece costs
# about twice a head's to lower (its columns are cut apart as they load).
# At 8,192 causal positions on a v5e (PERF.md section 6) the three kernels
# of a layer then traced to fewer equations than the heads-first kernels
# did (823 against 889; the forward and the one backward kernel 726), and
# the train step lowered 1.75 s sooner than with 256 in the backward
# kernels, for 0.43 ms a layer more device time
_PAIR_SUB = {"flash_fwd": 256, "flash_bwd_dq": 512, "flash_bwd_dkv": 512}

# a tile's quadrant under ``halves=2``: a clean query on clean keys (the
# one kind there is with one half), a noisy query on the clean keys before
# its block, a noisy query on its own block's noisy keys
_CLEAN, _BEFORE, _OWN = 0, 1, 2


def _sub_shape(bq, bk, sub):
    """Sides of a sub-tile: ``sub``, or the whole block where ``sub`` does
    not divide it."""
    return (sub if bq % sub == 0 else bq), (sub if bk % sub == 0 else bk)


def _tile_classes(off, keys, bq, bk, sub, causal, window, step=1,
                  kind=_CLEAN):
    """Class of every sub-tile of the tile whose first query lies ``off``
    positions after its first key and whose key block holds ``keys`` keys
    that exist: a tuple (query strips) of tuples (along the keys)."""
    sq, sk = _sub_shape(bq, bk, sub)
    # the least q - k (of the blocks' first positions) a pair in the band has
    least = step if kind == _BEFORE else 0
    rows = []
    for a in range(bq // sq):
        row = []
        for b in range(bk // sk):
            d = off + a * sq - b * sk
            lo, hi = d - (sk - step), d + (sq - step)  # the range of q - k
            left = keys - b * sk                     # keys that exist here
            if kind == _OWN:
                # off is 0 and the tile square: a block lies in one sub-tile
                # (what a padded query sees of the padding is nobody's)
                row.append(None if a != b or left <= 0 else
                           (("eq", 0),) if step < sq else ())
                continue
            if (causal and hi < least) or left <= 0 or (
                    window is not None and lo >= window):
                row.append(None)
                continue
            terms = []
            if causal and lo < least:
                terms.append(("ge", least - d))
            if window is not None and hi >= window:
                terms.append(("lt", window - d))
            if left < sk:
                terms.append(("col", left))
            row.append(tuple(terms))
        rows.append(tuple(row))
    return tuple(rows)


@functools.lru_cache(maxsize=256)
def _band_keys(nq, nk, bq, bk, seq_k, sub, causal, window, step=1, halves=1):
    """``{(off, last): (classes, tiles)}`` over the tiles of an (nq, nk)
    grid that touch the band. ``(off, last)`` is what a kernel tells a
    tile's pattern by: the offset of its first query from its first key (0
    without a causal mask, where no edge depends on it) and whether its
    key block is a padded last one. With ``halves=2`` the grid is a half's
    and the keys are ``(off, last, kind)``, a tile for each quadrant that
    holds one and a query block's own."""
    tail = seq_k - (nk - 1) * bk
    found = {}

    def add(key, kind):
        if key not in found:
            found[key] = [_tile_classes(key[0], tail if key[1] else bk, bq,
                                        bk, sub, causal, window, step, kind),
                          0]
        found[key][1] += 1

    for qi in range(nq):
        for kj in range(nk):
            last = tail < bk and kj == nk - 1
            key = (qi * bq - kj * bk if causal else 0, last)
            if halves == 1:
                add(key, _CLEAN)
                continue
            add(key + (_CLEAN,), _CLEAN)
            add(key + (_BEFORE,), _BEFORE)
        if halves == 2:
            add((0, tail < bk and qi == nk - 1, _OWN), _OWN)
    return {key: (classes, n) for key, (classes, n) in found.items()
            if any(c is not None for row in classes for c in row)}


def _all_inside(classes):
    return all(c == () for row in classes for c in row)


def band_tiles(seq_q, seq_k, bq, bk, sub, causal, window, step=1, halves=1):
    """What one head of a call computes, exact from the shapes: ``tiles``
    visited, their sub-tiles computed ``unmasked`` / ``masked`` and
    ``skipped``, and ``ratio``, the products computed over the products
    inside the band (1.0 would be no waste; padded query rows are
    computed and are no part of the band). ``seq_q`` and ``seq_k`` are a
    half's with ``halves=2``, the count both halves'."""
    import numpy as np

    keys = _band_keys(_cdiv(seq_q, bq), _cdiv(seq_k, bk), bq, bk, seq_k,
                      sub, bool(causal), window, step, halves)
    count = {"tiles": 0, "unmasked": 0, "masked": 0, "skipped": 0}
    for classes, n in keys.values():
        flat = [c for row in classes for c in row]
        count["tiles"] += n
        count["unmasked"] += n * sum(c == () for c in flat)
        count["masked"] += n * sum(bool(c) for c in flat)
        count["skipped"] += n * sum(c is None for c in flat)
    q = np.arange(seq_q)
    first = np.maximum(q - window + 1, 0) if window is not None else 0 * q
    # the last key of the block a query lies in (itself at step 1)
    edge = q - q % step + step - 1
    last = np.minimum(edge, seq_k - 1) if causal else 0 * q + seq_k - 1
    inside = int(np.maximum(last - first + 1, 0).sum())
    if halves == 2:     # a noisy query: the blocks before its own, and its own
        inside += int(np.minimum(edge + 1 - step, seq_k).sum()) + seq_q * step
    sq, sk = _sub_shape(bq, bk, sub)
    count["ratio"] = (count["unmasked"] + count["masked"]) * sq * sk / inside
    return count


def _band(kernel, bq, bk, seq_q, seq_k, causal, window, step=1, halves=1,
          rope_dim=0, **attrs):
    """What a kernel's body needs of the band: its ``sub``, whether the
    last key block is ``padded``, and ``groups``, the distinct patterns of
    its grid with the ``(off, last)`` (and ``kind``) of the tiles that have
    each. Leaves one zero-length ``flash.band`` record a traced call, with
    ``rope_dim`` the width of the shared rotary key (latent attention) and
    ``rope_shared`` 1 where there is one, 0 / 0 for every other call, and
    the kernel's own ``attrs`` (``flash_bwd_dkv``: ``dq`` and
    ``dq_vmem_bytes``, ``_fused_vmem``)."""
    from ... import telemetry

    sub = (_PAIR_SUB if rope_dim else _SUB)[kernel]
    if step > 1 or halves == 2:
        sides = (bq, bk, seq_q, seq_k, *_sub_shape(bq, bk, sub))
        if not causal or window is not None or any(n % step for n in sides) \
                or min(bq, bk) <= step or (halves == 2 and bq != bk):
            raise ValueError(
                f"flash_attention: a staircase of step {step} over "
                f"{halves} halves needs causal=True, no window, and "
                f"sequences, blocks {bq} x {bk} and sub-tiles of whole "
                "steps (equal blocks, longer than a step, over two halves)")
    with telemetry.phase("flash.band", kernel=kernel, seq=seq_q, bq=bq,
                         bk=bk, sub=sub, window=window or 0, step=step,
                         halves=halves, rope_dim=rope_dim,
                         rope_shared=int(rope_dim > 0), **attrs,
                         **band_tiles(seq_q, seq_k, bq, bk, sub, causal,
                                      window, step, halves)):
        pass
    groups = {}
    for key, (classes, _) in _band_keys(
            _cdiv(seq_q, bq), _cdiv(seq_k, bk), bq, bk, seq_k, sub, causal,
            window, step, halves).items():
        groups.setdefault(classes, []).append(key)
    return dict(sub=sub, padded=seq_k % bk != 0, groups=groups, step=step,
                halves=halves)


def _strips(classes, bq, bk, sub, by_keys=False):
    """The computed part of a tile, ``(extent, [(start, pieces)])``: strips
    of ``extent`` queries (keys with ``by_keys``) from ``start``, each with
    its ``pieces`` ``(start, extent, terms)`` along the other side, an
    edge sub-tile by itself and a run of unmasked ones as one piece. A
    tile inside the band is one strip of one piece."""
    if _all_inside(classes):
        return (bk, [(0, [(0, bq, ())])]) if by_keys else \
            (bq, [(0, [(0, bk, ())])])
    strip, step = _sub_shape(bq, bk, sub)
    if by_keys:
        classes, strip, step = tuple(zip(*classes)), step, strip
    strips = []
    for a, line in enumerate(classes):
        pieces = []
        for b, terms in enumerate(line):
            if terms is None:
                continue
            if terms == () and pieces and pieces[-1][2] == () \
                    and pieces[-1][0] + pieces[-1][1] == b * step:
                pieces[-1] = (pieces[-1][0], pieces[-1][1] + step, ())
            else:
                pieces.append((b * step, step, terms))
        if pieces:
            strips.append((a * strip, pieces))
    return strip, strips


def _block_first(x, step):
    """The first position of the block of ``step`` that ``x`` lies in."""
    if step == 1:
        return x
    if step & (step - 1) == 0:
        return x & jnp.int32(-step)
    return x - _mod(x, step)


def _masks(bq, bk, sub, by_keys=False, step=1):
    """``mask(terms)`` for one kernel body: the [sub_q, sub_k] bool array of
    an edge sub-tile's terms ([sub_k, sub_q] with ``by_keys``, the keys
    down the rows), built once a body; ``None`` for no terms."""
    shape = _sub_shape(bq, bk, sub)[::-1 if by_keys else 1]
    made = {}

    def mask(terms):
        if terms and terms not in made:
            row = jax.lax.broadcasted_iota(jnp.int32, shape, int(by_keys))
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - by_keys)
            stair = _block_first(row, step) - _block_first(col, step)
            parts = [stair >= c if kind == "ge" else
                     stair == c if kind == "eq" else
                     row - col < c if kind == "lt" else col < c
                     for kind, c in terms]
            made[terms] = functools.reduce(jnp.logical_and, parts)
        return made.get(terms)

    return mask


def _on_tile(groups, off, last, needed, causal, padded, body, kind=None):
    """Run ``body(classes)`` for the pattern of this step's tile: every
    pattern an edge passes through under a ``pl.when`` of the few
    ``(off, last)`` that have it, the tiles inside the band under what is
    left of ``needed``. ``kind``: the tile's quadrant over two halves."""
    def is_key(key):
        terms = ([off == key[0]] if causal else []) + (
            [last if key[1] else jnp.logical_not(last)] if padded else []) + (
            [kind == key[2]] if kind is not None else [])
        return functools.reduce(jnp.logical_and, terms)

    inside = needed
    for classes, keys in groups.items():
        if _all_inside(classes):
            continue
        hit = functools.reduce(jnp.logical_or, [is_key(k) for k in keys])
        pl.when(jnp.logical_and(needed, hit))(
            functools.partial(body, classes))
        inside = jnp.logical_and(inside, jnp.logical_not(hit))
    for classes in groups:
        if _all_inside(classes):
            pl.when(inside)(functools.partial(body, classes))


def _kv_step(i, j, bq, bk, nq, nk, causal, window, step, halves):
    """Step ``j`` of query block ``i`` on the forward / dq grid: ``(qi, kj,
    kind, needed)``, the query and key block within their halves, the
    tile's quadrant (``None`` with one half) and whether the step has a
    tile. Over two halves step 0 is a noisy query block's own noisy block
    (nothing for a clean one) and step ``j`` the clean key block ``j - 1``."""
    if halves == 1:
        kj = _kv_lo(i, bq, bk, window) + j
        return i, kj, None, kj <= _kv_hi(i, bq, bk, nk, causal)
    clean = (i >= nq).astype(jnp.int32)
    qi = i - clean * nq
    own = jnp.logical_and(clean == 0, j == 0)
    kj = jnp.where(own, qi, j - 1)
    needed = jnp.logical_or(own, jnp.logical_and(
        j >= 1, kj <= _kv_hi(qi, bq, bk, nk, True, (1 - clean) * step)))
    return qi, kj, jnp.where(own, _OWN, 1 - clean), needed


def _q_step(kjg, s, bq, bk, nq, nk, causal, window, step, halves):
    """Step ``s`` (of one query head) of key block ``kjg`` on the dkv grid:
    ``(query block in the array, qi, kj, kind, needed)``, clamped to the
    last step that has a tile. Over two halves a clean key block meets the
    noisy query blocks that see it, then the clean ones; a noisy key block
    its own query block alone."""
    if halves == 1:
        qi = _q_lo(kjg, bq, bk, causal) + s
        hi = _q_hi(kjg, bq, bk, nq, window)
        return jnp.minimum(qi, hi), qi, kjg, None, qi <= hi
    clean = (kjg >= nk).astype(jnp.int32)
    kj = kjg - clean * nk
    lo_noisy, lo_clean = _q_lo(kj, bq, bk, True, step), \
        _q_lo(kj, bq, bk, True)
    noisy, both = nq - lo_noisy, 2 * nq - lo_noisy - lo_clean
    at = jnp.minimum(s, both - 1)
    by_noisy = (at < noisy).astype(jnp.int32)
    qi = jnp.where(clean == 1, jnp.where(
        by_noisy == 1, lo_noisy + at, lo_clean + at - noisy), kj)
    kind = jnp.where(clean == 1, by_noisy, _OWN)   # _BEFORE is 1
    needed = jnp.where(clean == 1, s < both, s == 0)
    return qi + clean * (1 - by_noisy) * nq, qi, kj, kind, needed


# --------------------------------------------------------------------------
# rotary positions and the head gate, inside the kernels
# --------------------------------------------------------------------------
# A query block is (bq, d) in VMEM whatever the array's layout in HBM, so
# the rotation and the gate cost no pass over HBM and no relayout there:
#   rotated q = q * cos + (q @ rot) * sin      (``rot`` a signed permutation)
#   gated   o = o * gate                       (one factor a row)
# ``extras`` is the static triple (rotary, gated, shared); the refs they
# add follow the kernel's own inputs in the order cos, sin, rot, gate,
# shared key.
#
# ``shared`` is latent attention (DeepSeek-V3): a query head is ``[q_nope |
# q_pe]``, a key-value head ``[k_nope | v]``, both as the projections leave
# them, and ONE rotary key ``k_pe`` a position is read by every head (the
# shared key, (batch, 1, seq, rope)). A score is ``q_nope . k_nope +
# rot(q_pe) . k_pe``; the tables rotate ``q_pe`` alone. A head of ``nope +
# rope`` columns need not be whole lane tiles, two are: a grid step takes a
# PAIR of heads, its query, key-value and output blocks those of both, and
# the body runs on values with the pair as a leading axis of 2 (one batched
# product for both heads): the same traced body as one head's, whose loads,
# stores and products only gain that axis. The pair's columns are cut apart
# by ``_pair`` as they are loaded, whole lane tiles but for the second
# query head. A query or key is then the pair of its parts ``(own,
# rotary)``, and a score the sum of two products (``_score``), the rotary
# one both heads' rows against the one key: nothing is put together in
# VMEM, and the MXU passes are those of one product of ``nope + rope``.

def _split_refs(refs, n_in, extras):
    """``(inputs, (cos, sin, rot), gate, shared, rest)`` of a kernel's
    refs. With the shared key the inputs' second ref is the pair's
    key-value block and ``None`` stands in the value ref's place."""
    rotary, gated, shared = extras
    ins, k = refs[:n_in - shared], n_in - shared
    if shared:
        ins = (*ins[:2], None, *ins[2:])
    rope = refs[k:k + 3] if rotary else None
    k += 3 if rotary else 0
    gate = refs[k] if gated else None
    k += 1 if gated else 0
    key = refs[k] if shared else None
    k += 1 if shared else 0
    return ins, rope, gate, key, refs[k:]


def _pair(ref, rows, lo, width):
    """Columns ``lo:lo + width`` of either head of a pair block's ``rows``:
    ``(2, rows, width)``."""
    half = ref.shape[-1] // 2
    return jax.lax.concatenate([ref[:1, rows, lo:lo + width],
                                ref[:1, rows, half + lo:half + lo + width]], 0)


def _pair_keys(kv_ref, shared, rows, nope):
    """The pair's keys of ``rows``: each head's own ``k_nope`` ``(2, rows,
    nope)`` and the one rotary key ``(rows, rope)``, which both read."""
    return _pair(kv_ref, rows, 0, nope), shared[0, 0, rows, :]


def _pair_query(q_ref, rows, rope, nope, at=None):
    """The pair's queries of ``rows`` as ``(q_nope, q_pe)``, ``q_pe``
    rotated to its positions (the tables' rows ``at``, ``rows`` unless
    given)."""
    pe = _pair(q_ref, rows, nope, q_ref.shape[-1] // 2 - nope)
    return _pair(q_ref, rows, 0, nope), _rotated(
        pe, rope, rows if at is None else at)


def _parts(f, x):
    """``f`` of ``x``, or of each part of a query or key in parts."""
    return tuple(map(f, x)) if isinstance(x, tuple) else f(x)


def _score(a, b):
    """``a . b^T`` over the last axis, float32: the sum of the parts'
    products where ``a`` and ``b`` come in parts (latent attention)."""
    if isinstance(a, tuple):
        return jax.lax.add(_dot(a[0], b[0], (1, 1)), _dot(a[1], b[1], (1, 1)))
    return _dot(a, b, (1, 1))


def _weigh(w, x):
    """``w . x`` (``w``'s last axis against ``x``'s rows), float32, for
    ``x`` or each of its parts."""
    return _parts(lambda part: _dot(w, part, (1, 0)), x)


def _plus(a, b):
    return tuple(map(jax.lax.add, a, b)) if isinstance(a, tuple) else a + b


def _acc_rows(acc, rows):
    """The ``rows`` of an accumulator, or of each of its parts (``rows``
    a tuple: a part's own)."""
    if isinstance(acc, tuple):
        rows = rows if isinstance(rows, tuple) else (rows,) * len(acc)
        return tuple(ref[..., at, :] for ref, at in zip(acc, rows))
    return acc[..., rows, :]


def _acc_store(acc, rows, x):
    if isinstance(acc, tuple):
        rows = rows if isinstance(rows, tuple) else (rows,) * len(acc)
        for ref, at, part in zip(acc, rows, x):
            ref[..., at, :] = part
    else:
        acc[..., rows, :] = x


def _lanes_add(acc, cols, x):
    """Add ``x`` to the columns ``cols`` (the last axis) of an accumulator,
    or of each of its parts."""
    for ref, part in zip(*((acc, x) if isinstance(acc, tuple)
                           else ((acc,), (x,)))):
        at = (slice(None),) * (ref.ndim - 1) + (cols,)
        ref[at] = ref[at] + part


def _swapped(x):
    """``x`` with its last two axes swapped (a pair's heads stay first)."""
    return jnp.swapaxes(x, -1, -2)


def _each_block(n, size, body, align=8):
    """``body(at, i)`` for the ``n`` blocks ``i`` of ``size`` rows (or
    columns) of a whole sequence, ``at`` the block's slice: one traced
    body in a loop (a store of a whole sequence's accumulator at once
    would be unrolled over every vector register)."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * size, align), size), i)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _rotated(x, rope, rows=slice(None)):
    """``x``, the ``rows`` of a query block (rows, d) or of a pair's (2,
    rows, d), rotated to its positions, in ``x``'s dtype."""
    if rope is None:
        return x
    cos_ref, sin_ref, rot_ref = rope
    turned = _dot(x, rot_ref[...], (1, 0))
    return (x.astype(jnp.float32) * cos_ref[rows, :]
            + turned * sin_ref[rows, :]).astype(x.dtype)


def _unrotated(g, rope, dtype, rows=slice(None)):
    """Gradient by the unrotated ``x`` from the gradient ``g`` (float32) by
    the rotated one: ``g * cos + (g * sin) @ rot^T``, at the tables'
    ``rows``."""
    if rope is None:
        return g
    cos_ref, sin_ref, rot_ref = rope
    return g * cos_ref[rows, :] + _dot((g * sin_ref[rows, :]).astype(dtype),
                                       rot_ref[...], (1, 1))


def _gated(do, gate, rows=slice(None)):
    """The gradient by the ungated output: ``do``, the ``rows`` of a query
    block, times each row's gate."""
    if gate is None:
        return do
    return (do.astype(jnp.float32) * gate[0, rows, :]).astype(do.dtype)


def _extra_specs(extras, d, q_block_map, stat_map, bq, whole=0):
    """BlockSpecs of the rotary tables and the gate: cos and sin (``d``
    wide) by the query block, or all ``whole`` rows of them read once
    (one buffer: the index never moves), the whole ``rot``, the gate as
    the row statistics are. The shared key's follows them (the
    wrappers')."""
    rotary, gated = extras[:2]
    specs = []
    if rotary and whole:
        specs += [pl.BlockSpec((whole, d), lambda *_: (0, 0),
                               pipeline_mode=pl.Buffered(1))] * 2 + [
            pl.BlockSpec((d, d), lambda *_: (0, 0))]
    elif rotary:
        specs += [pl.BlockSpec((bq, d), q_block_map),
                  pl.BlockSpec((bq, d), q_block_map),
                  pl.BlockSpec((d, d), lambda *_: (0, 0))]
    if gated:
        specs.append(pl.BlockSpec((1, bq, 1), stat_map))
    return specs


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _dot(a, b, contract):
    """``a`` and ``b`` contracted over the axes ``contract``, float32; a
    pair of heads (a leading axis of 2 on both) a product each, or on
    ``a`` alone one product of both heads' rows against ``b``."""
    if a.ndim == 3 and b.ndim == 2:
        return jax.lax.dot_general(
            a, b, (((contract[0] + 1,), (contract[1],)), ((), ())),
            preferred_element_type=jnp.float32)
    if a.ndim == 3:
        return jax.lax.dot_general(
            a, b, (((contract[0] + 1,), (contract[1] + 1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _probs(a, b, lse, scale, mask):
    """The probabilities of a piece from the saved row statistics
    (backward), zero where ``mask`` is not set: queries ``a`` down the
    rows and ``lse`` a column, or keys ``a`` and ``lse`` a row."""
    p = jnp.exp(_score(a, b) * scale - lse)
    return p if mask is None else jnp.where(mask, p, 0.0)


def _fwd_kernel(*refs, scale, causal, window, bq, bk, nq, nk, steps, sub,
                groups, padded, extras, step, halves, nope=0):
    (q_ref, k_ref, v_ref), rope, gate, shared, rest = _split_refs(
        refs, 3, extras)
    o_ref, lse_ref, acc, m_scr, l_scr = rest[:5]
    q_scr = rest[5] if rope is not None else None
    j = pl.program_id(2)
    qi, kj, kind, needed = _kv_step(pl.program_id(1), j, bq, bk, nq, nk,
                                    causal, window, step, halves)
    if shared is None:
        def queries(rows):
            return q_ref[0, rows, :] if rope is None else q_scr[..., rows, :]

        def keys(rows):
            return k_ref[0, rows, :]

        def values(rows):
            return v_ref[0, rows, :]
    else:     # the pair's, read from its blocks as they are
        q_scr = rest[5:7]

        def queries(rows):
            return _acc_rows(q_scr, rows)

        def keys(rows):
            return _pair_keys(k_ref, shared, rows, nope)

        def values(rows):
            return _pair(k_ref, rows, nope, o_ref.shape[-1] // 2)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        if shared is not None:     # both heads, once a query block
            _acc_store(q_scr, slice(None),
                       _pair_query(q_ref, slice(None), rope, nope))
        elif rope is not None:     # once a query block, kept for its steps
            q_scr[...] = _rotated(q_ref[0], rope)

    def body(classes):
        # a strip of queries keeps one running maximum over its pieces;
        # matmul operands per the _mxu policy, products accumulate f32
        height, strips = _strips(classes, bq, bk, sub)
        mask = _masks(bq, bk, sub, step=step)
        for r0, pieces in strips:
            rows = slice(r0, r0 + height)
            q = _parts(_mxu, queries(rows))
            scores = []
            for k0, n, terms in pieces:
                s = _score(q, _parts(_mxu, keys(slice(k0, k0 + n)))) * scale
                scores.append(jnp.where(mask(terms), s, NEG_INF)
                              if terms else s)
            m_prev = m_scr[..., rows, :1]            # [height, 1]
            m_new = functools.reduce(jnp.maximum, [m_prev] + [
                jnp.max(s, axis=-1, keepdims=True) for s in scores])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[..., rows, :1] * alpha
            out = acc[..., rows, :] * alpha
            for (k0, n, terms), s in zip(pieces, scores):
                p = jnp.exp(s - m_new)               # [height, n] f32
                if terms:     # a row with no key yet has m_new = NEG_INF
                    p = jnp.where(mask(terms), p, 0.0)
                l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                v = _mxu(values(slice(k0, k0 + n)))
                out = out + _dot(p.astype(v.dtype), v, (1, 0))
            acc[..., rows, :] = out
            lanes = (*m_new.shape[:-1], _LANES)
            m_scr[..., rows, :] = jnp.broadcast_to(m_new, lanes)
            l_scr[..., rows, :] = jnp.broadcast_to(l_new, lanes)

    _on_tile(groups, qi * bq - kj * bk, kj == nk - 1, needed, causal,
             padded, body, kind)

    @pl.when(j == steps - 1)
    def _finalize():
        l = l_scr[..., :1]
        l_safe = jnp.maximum(l, 1e-30)
        out = acc[:] / l_safe
        if gate is not None:
            out = out * gate[0]
        if shared is None:
            o_ref[0] = out.astype(o_ref.dtype)
            lse_ref[0] = (m_scr[:, :1] + jnp.log(l_safe)).astype(jnp.float32)
            return
        # the pair's outputs side by side, its statistics a row each
        o_ref[0] = jnp.concatenate([out[0], out[1]], axis=-1).astype(
            o_ref.dtype)
        lse_ref[...] = m_scr[..., :1] + jnp.log(l_safe)


def _kv_block_map(hq, hkv, bq, bk, nq, nk, causal, window, packed, step=1,
                  halves=1):
    """Index map of a key/value block on a (row, query block, step) grid:
    the step's key block, clamped to the last one needed so that a skipped
    step fetches nothing new. Over two halves the clean keys follow the
    noisy ones in the array, and a clean query block's idle step 0 stays
    on the block of its step 1."""
    def index(b, i, j):
        if halves == 1:
            kj = jnp.minimum(_kv_lo(i, bq, bk, window) + j,
                             _kv_hi(i, bq, bk, nk, causal))
        else:
            qi, _, kind, _ = _kv_step(i, j, bq, bk, nq, nk, causal, window,
                                      step, halves)
            kj = jnp.where(kind == _OWN, qi, nk + jnp.minimum(
                jnp.maximum(j - 1, 0), _kv_hi(
                    qi, bq, bk, nk, True, jnp.where(kind == _CLEAN, 0, step))))
        return _kv_index(_kv_head(b, hq, hkv), kj, hkv, packed)

    return index


def _table_map(nq, halves, block):
    """Index map of the rotary tables' block: ``block(*grid indices)`` is
    the query block in the array, and both halves have the positions of
    one."""
    def index(*at):
        qi = block(*at)
        return (qi if halves == 1 else _mod(qi, nq), 0)

    return index


def _shared_map(kv_map):
    """Index map of the shared key's block: the key block that ``kv_map``
    gives, of the row's sequence."""
    def index(*at):
        row, kj, _ = kv_map(*at)
        return row, 0, kj, 0

    return index


def _pair_widths(q, k, shared, hq):
    """A call with the shared key as the grid sees it, a row a PAIR of
    heads: ``(rows a sequence, query width, key-value width, output
    width, nope)`` of a pair, ``nope`` a head's key columns without
    positions."""
    d = q.shape[2] // hq
    nope = d - shared.shape[3]
    v_dim = k.shape[2] // hq - nope
    return hq // 2, 2 * d, 2 * (nope + v_dim), 2 * v_dim, nope


def _pair_scratch(rows, widths, dtype):
    """VMEM of a pair's parts: ``(2, rows, width)`` for each width."""
    return [pltpu.VMEM((2, rows, w), dtype) for w in widths]


# A pair's blocks and accumulators are twice a head's, and its row
# statistics are a lane tile a row: latent attention's kernels take more
# VMEM than the default scoped limit of 16 MiB (v5e has 128 MiB).
_LATENT_VMEM = 48 * 2 ** 20


def _latent_params(shared):
    """The ``pallas_call`` arguments of a latent call (none otherwise)."""
    if shared is None:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_LATENT_VMEM)}


def _flash_fwd_padded(q, k, v, *, scale, causal, window, hq, hkv, bq, bk,
                      seq_q, seq_k, interpret, packed=False, rope=None,
                      gate=None, step=1, halves=1, shared=None):
    """``seq_q`` / ``seq_k`` and the blocks counted below are a half's;
    the arrays and the grid hold ``halves`` of them. With the ``shared``
    key (latent attention) ``k`` is the key-value rows, ``v`` is None and
    a row of the grid is a pair of heads (``_pair_widths``)."""
    rope_dim = 0 if shared is None else shared.shape[3]
    if shared is None:
        d = q.shape[2] // hq if packed else q.shape[2]
        v_dim = v.shape[2] // hkv if packed else v.shape[2]
    else:
        hq, d, kv_dim, v_dim, nope = _pair_widths(q, k, shared, hq)
        hkv = hq
    bh = q.shape[0] * hq if packed else q.shape[0]
    sq = q.shape[1]
    nq, nk = sq // bq // halves, k.shape[1] // bk // halves
    # over two halves one step more: a noisy query block's own block
    steps = _kv_steps(bq, bk, nk, causal, window) + halves - 1
    extras = (rope is not None, gate is not None, shared is not None)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        nq=nq, nk=nk, steps=steps, extras=extras,
        **_band("flash_fwd", bq, bk, seq_q, seq_k, causal, window, step,
                halves, rope_dim))
    kv_map = _kv_block_map(hq, hkv, bq, bk, nq, nk, causal, window, packed,
                           step, halves)

    def q_map(b, i, j):
        return _q_index(b, i, hq, packed)

    def stat_map(b, i, j):
        return (b, i, 0)

    if shared is None:
        pair, per, ins = (), 1, (q, k, v)
        own = [pl.BlockSpec((1, bq, d), q_map),
               pl.BlockSpec((1, bk, d), kv_map),
               pl.BlockSpec((1, bk, v_dim), kv_map)]
        last = []
    else:     # a pair's statistics and scratch a row a head
        kern = functools.partial(kern, nope=nope)
        pair, per, ins = (2,), 2, (q, k)
        own = [pl.BlockSpec((1, bq, d), q_map),
               pl.BlockSpec((1, bk, kv_dim), kv_map)]
        last = [pl.BlockSpec((1, 1, bk, rope_dim), _shared_map(kv_map))]

    o, lse = pl.pallas_call(
        kern,
        grid=(bh, halves * nq, steps),
        in_specs=own + _extra_specs(
            extras, rope_dim or d, _table_map(nq, halves, lambda b, i, j: i),
            stat_map, bq) + last,
        out_specs=[
            pl.BlockSpec((1, bq, v_dim), q_map),
            pl.BlockSpec((per, bq, 1), stat_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((*q.shape[:2], q.shape[2] // d * v_dim),
                                 q.dtype),
            jax.ShapeDtypeStruct((bh * per, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((*pair, bq, v_dim // per), jnp.float32),
            pltpu.VMEM((*pair, bq, _LANES), jnp.float32),
            pltpu.VMEM((*pair, bq, _LANES), jnp.float32),
        ] + (_pair_scratch(bq, (nope, rope_dim), q.dtype) if shared is not None
             else [pltpu.VMEM((bq, d), q.dtype)] if rope is not None else []),
        interpret=interpret,
        name="flash_fwd",
        **_latent_params(shared),
    )(*ins, *(rope or ()), *(() if gate is None else (gate,)),
      *(() if shared is None else (shared,)))
    return o, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, window, bq, bk, nq, nk, steps, sub,
                   groups, padded, extras, step, halves, nope=0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope, gate, shared, \
        rest = _split_refs(refs, 6, extras)
    dq_ref, dq_acc = rest[:2]
    q_scr = rest[2] if rope is not None else None
    j = pl.program_id(2)
    qi, kj, kind, needed = _kv_step(pl.program_id(1), j, bq, bk, nq, nk,
                                    causal, window, step, halves)
    if shared is None:
        def queries(rows):
            return q_ref[0, rows, :] if rope is None else q_scr[..., rows, :]

        def keys(rows):
            return k_ref[0, rows, :]

        def values(rows):
            return v_ref[0, rows, :]

        def grads(rows):
            return _gated(do_ref[0, rows, :], gate, rows)

        def stats(ref, rows):
            return ref[0, rows, :]
    else:     # the pair's, read from its blocks as they are; the gradient
        # by q_nope and by q_pe accumulated apart. The output rows stand in
        # delta's place, and delta is the second output, made at the first
        # step
        o_ref, delta_ref = delta_ref, rest[1]
        dq_acc, q_scr = rest[2:4], rest[4:6]

        def queries(rows):
            return _acc_rows(q_scr, rows)

        def keys(rows):
            return _pair_keys(k_ref, shared, rows, nope)

        def values(rows):
            return _pair(k_ref, rows, nope, do_ref.shape[-1] // 2)

        def grads(rows):
            return _pair(do_ref, rows, 0, do_ref.shape[-1] // 2)

        def stats(ref, rows):
            return ref[:, rows, :]

    @pl.when(j == 0)
    def _init():
        if shared is not None:
            for acc in dq_acc:
                acc[...] = jnp.zeros_like(acc)
            _acc_store(q_scr, slice(None),
                       _pair_query(q_ref, slice(None), rope, nope))
            o = _pair(o_ref, slice(None), 0, o_ref.shape[-1] // 2)
            delta_ref[...] = jnp.sum(
                grads(slice(None)).astype(jnp.float32)
                * o.astype(jnp.float32), axis=-1, keepdims=True)
            return
        dq_acc[:] = jnp.zeros_like(dq_acc)
        if rope is not None:
            q_scr[...] = _rotated(q_ref[0], rope)

    def body(classes):
        height, strips = _strips(classes, bq, bk, sub)
        mask = _masks(bq, bk, sub, step=step)
        for r0, pieces in strips:
            rows = slice(r0, r0 + height)
            q = _parts(_mxu, queries(rows))
            do = _mxu(grads(rows))
            lse = stats(lse_ref, rows)                # [height, 1]
            delta = stats(delta_ref, rows)
            dq = _acc_rows(dq_acc, rows)
            for k0, n, terms in pieces:
                k = _parts(_mxu, keys(slice(k0, k0 + n)))
                v = _mxu(values(slice(k0, k0 + n)))
                p = _probs(q, k, lse, scale, mask(terms))
                dp = _dot(do, v, (1, 1))
                ds = (p * (dp - delta) * scale).astype(v.dtype)
                dq = _plus(dq, _weigh(ds, k))
            _acc_store(dq_acc, rows, dq)

    _on_tile(groups, qi * bq - kj * bk, kj == nk - 1, needed, causal,
             padded, body, kind)

    @pl.when(j == steps - 1)
    def _finalize():
        if shared is None:
            dq_ref[0] = _unrotated(dq_acc[:], rope, dq_ref.dtype).astype(
                dq_ref.dtype)
            return
        # the gradient by q_pe unrotated, the pair's heads side by side
        own = dq_acc[0][...]
        pe = _unrotated(dq_acc[1][...], rope, dq_ref.dtype)
        dq_ref[0] = jnp.concatenate([own[0], pe[0], own[1], pe[1]],
                                    axis=-1).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, window, bq, bk, nq, nk, steps,
                    group, sub, groups, padded, extras, step, halves, nope=0,
                    shares=1, fused=False):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope, gate, shared, \
        rest = _split_refs(refs, 6, extras)
    # with the shared key: the gradient of the pair's key-value block and
    # the shared key's; ``fused``, the gradient by the queries too
    dk_ref, dv_ref = rest[:2]
    dq_ref = rest[2] if fused else None
    dk_acc, dv_acc, *more = rest[2 + fused:]
    # the inner grid dimension walks the ``group`` query heads (pairs)
    # that share this key-value head, and within each the query blocks
    # that can need this key block. Fused a row is ONE query head (pair)
    # and ``shares`` consecutive rows share the key-value head: its
    # accumulator then holds the whole sequence (``whole``), and dq's
    # holds the row's, the queries along the lanes (``dq^T += k^T . ds``:
    # the keys' strip is transposed, not ds)
    b, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    at = t if group == 1 else _mod(t, steps)
    blk, qi, kj, kind, needed = _q_step(j, at, bq, bk, nq, nk, causal,
                                        window, step, halves)
    whole = shares > 1

    def table(rows):     # the rotary tables' rows of a query block's rows
        return pl.ds(qi * bq + rows.start, rows.stop - rows.start) \
            if fused else rows

    if shared is None:
        dq_acc = more[0] if fused else None

        def keys(rows):
            return k_ref[0, rows, :]

        def values(rows):
            return v_ref[0, rows, :]

        def queries(rows):
            return _rotated(q_ref[0, rows, :], rope, table(rows))

        def grads(rows):
            return _gated(do_ref[0, rows, :], gate, rows)

        def stats(ref, rows):
            return ref[0, 0, :, rows]
    else:     # the step's pair's, read from its blocks as they are; the
        # gradient by k_nope a pair's, by the shared key summed over all
        dk_acc = (dk_acc, more[0])
        dq_acc = tuple(more[1:]) if fused else None

        def keys(rows):     # a strip's: the keys are the products' rows
            own, pe = _pair_keys(k_ref, shared, rows, nope)
            return own, jax.lax.broadcast(pe, (2,))

        def values(rows):
            return _pair(k_ref, rows, nope, do_ref.shape[-1] // 2)

        def queries(rows):
            return _pair_query(q_ref, rows, rope, nope, table(rows))

        def grads(rows):
            return _pair(do_ref, rows, 0, do_ref.shape[-1] // 2)

        def stats(ref, rows):
            return ref[:, 0, :, rows]

    def kv_rows(cols):
        """The accumulators' rows of a strip of keys: the block's, or the
        sequence's where the accumulator is whole (with the shared key its
        own part is always the block's)."""
        rows = pl.ds(j * bk + cols.start, cols.stop - cols.start) \
            if whole else cols
        return rows if shared is None else (cols, rows)

    if fused:
        last_row = jnp.logical_and(j == halves * nk - 1, t == steps - 1)

        @pl.when(jnp.logical_and(j == 0, t == 0))
        def _start_row():
            def clear(cols, i):
                for ref in dq_acc if shared is not None else (dq_acc,):
                    ref[..., cols] = jnp.zeros(
                        (*ref.shape[:-1], bq), jnp.float32)

            _each_block(halves * nq, bq, clear, _LANES)
            if whole:
                @pl.when(_mod(b, shares) == 0)
                def _start_head():
                    def clear_kv(rows, i):
                        for ref in (dk_acc[1],) if shared is not None \
                                else (dk_acc, dv_acc):
                            ref[..., rows, :] = jnp.zeros(
                                (*ref.shape[:-2], bk, ref.shape[-1]),
                                jnp.float32)

                    _each_block(halves * nk, bk, clear_kv)

    if shared is None:
        if not whole:
            @pl.when(t == 0)
            def _init():
                dk_acc[:] = jnp.zeros_like(dk_acc)
                dv_acc[:] = jnp.zeros_like(dv_acc)
    else:     # a pair's own accumulators anew, the shared key's once
        @pl.when(at == 0)
        def _next_pair():
            for acc in (dk_acc[0], dv_acc):
                acc[...] = jnp.zeros_like(acc)

        if not whole:
            @pl.when(t == 0)
            def _init():
                dk_acc[1][...] = jnp.zeros_like(dk_acc[1])

    def body(classes):
        # strips of keys, each over the pieces of queries it needs, the
        # keys down the rows of every product: nothing is transposed but,
        # fused, a strip's keys, once
        width, strips = _strips(classes, bq, bk, sub, by_keys=True)
        mask = _masks(bq, bk, sub, by_keys=True, step=step)
        # every step has another query block: what the strips need of it
        # is rotated as it comes, once
        lo = min(r0 for _, pieces in strips for r0, _, _ in pieces)
        hi = max(r0 + n for _, pieces in strips for r0, n, _ in pieces)
        q_all = _parts(_mxu, queries(slice(lo, hi)))
        do_all = _mxu(grads(slice(lo, hi)))
        for c0, pieces in strips:
            cols = slice(c0, c0 + width)
            k = _parts(_mxu, keys(cols))
            v = _mxu(values(cols))
            k_t = _parts(_swapped, k) if fused else None
            dk, dv = _acc_rows(dk_acc, kv_rows(cols)), \
                _acc_rows(dv_acc, cols if shared is not None
                          else kv_rows(cols))
            for r0, n, terms in pieces:
                q = _parts(lambda x: x[..., r0 - lo:r0 - lo + n, :], q_all)
                do = do_all[..., r0 - lo:r0 - lo + n, :]
                p = _probs(k, q, stats(lse_ref, slice(r0, r0 + n)), scale,
                           mask(terms))              # [width, n] f32
                dv = dv + _dot(p.astype(do.dtype), do, (1, 0))
                dp = _dot(v, do, (1, 1))
                ds = (p * (dp - stats(delta_ref, slice(r0, r0 + n)))
                      * scale).astype(do.dtype)
                dk = _plus(dk, _weigh(ds, q))
                if fused:
                    _lanes_add(dq_acc, pl.ds(pl.multiple_of(
                        blk * bq + r0, _LANES), n), _parts(
                            lambda kt: _dot(kt, ds, (1, 0)), k_t))
            _acc_store(dk_acc, kv_rows(cols), dk)
            _acc_store(dv_acc, cols if shared is not None else kv_rows(cols),
                       dv)

    _on_tile(groups, qi * bq - kj * bk, kj == nk - 1, needed, causal,
             padded, body, kind)

    if fused:
        @pl.when(last_row)
        def _dq_done():     # unrotated a block at a time, as q's rows lie
            def put(rows, i):
                cols = pl.ds(pl.multiple_of(i * bq, _LANES), bq)
                tab = rows if halves == 1 else pl.ds(
                    pl.multiple_of(_mod(i, nq) * bq, 8), bq)
                if shared is None:
                    dq_ref[0, rows, :] = _unrotated(
                        _swapped(dq_acc[:, cols]), rope, dq_ref.dtype,
                        tab).astype(dq_ref.dtype)
                    return
                own = _swapped(dq_acc[0][:, :, cols])
                pe = _unrotated(_swapped(dq_acc[1][:, :, cols]), rope,
                                dq_ref.dtype, tab)
                dq_ref[0, rows, :] = jnp.concatenate(
                    [own[0], pe[0], own[1], pe[1]], axis=-1).astype(
                        dq_ref.dtype)

            _each_block(halves * nq, bq, put)

    done = jnp.logical_and(last_row, _mod(b, shares) == shares - 1) \
        if whole else t == group * steps - 1
    if shared is None:
        @pl.when(done)
        def _finalize():
            if not whole:
                dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
                dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
                return

            def put(rows, i):
                dk_ref[0, rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
                dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)

            _each_block(halves * nk, bk, put)
        return

    @pl.when(at == steps - 1)
    def _pair_done():     # [k_nope | v] of either head, as the rows lie
        own, dv = dk_acc[0][...], dv_acc[...]
        dk_ref[0] = jnp.concatenate([own[0], dv[0], own[1], dv[1]],
                                    axis=-1).astype(dk_ref.dtype)

    @pl.when(done)
    def _finalize_shared():
        if not whole:
            dv_ref[0, 0] = (dk_acc[1][0] + dk_acc[1][1]).astype(dv_ref.dtype)
            return

        def put(rows, i):
            dv_ref[0, 0, rows, :] = (dk_acc[1][0, rows, :]
                                     + dk_acc[1][1, rows, :]).astype(
                                         dv_ref.dtype)

        _each_block(halves * nk, bk, put)


# What ``flash_bwd_dkv`` may hold across a grid row to make dq as well
# (``_fused_vmem``); a call whose shapes need more keeps ``flash_bwd_dq``.
# At 8,192 positions every attention layer of the decoder cells holds 23-29
# MB; a latent pair at 16,384 positions would hold 57.
_FUSED_VMEM = 40 * 2 ** 20
# what a fused call may take besides: its blocks in flight and the body's
# values (the latent kernels' own limit, ``_LATENT_VMEM``, for a pair)
_STREAM_VMEM = 32 * 2 ** 20


def _fused_vmem(sq, sk, d, v_dim, rope_dim, group, itemsize, rope):
    """Bytes that ``flash_bwd_dkv`` holds across a grid row when it makes
    dq too: dq's float32 accumulator and its output block over the whole
    (padded) sequence, ``d`` wide (a pair's with the shared key); where
    ``group`` rows share a key-value head, its accumulators and output
    blocks over the whole sequence (the shared key's: both heads' float32
    and one block); and the whole rotary tables, float32."""
    held = sq * d * (4 + itemsize)
    if group > 1:
        held += sk * (rope_dim * (2 * 4 + itemsize) if rope_dim
                      else (d + v_dim) * (4 + itemsize))
    if rope is not None:
        held += 2 * rope[0].size * 4
    return held


def _row_sums(do, o, heads, width, packed):
    """The row sums of ``do * o`` (delta), ``(batch x heads, seq)``
    float32, the positions along the lanes."""
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if not packed:
        return jnp.sum(prod, axis=-1)
    b, sq = do.shape[:2]
    return jnp.sum(prod.reshape(b, sq, heads, width), axis=-1).transpose(
        0, 2, 1).reshape(b * heads, sq)


def _flash_bwd_padded(q, k, v, o, lse, do, *, scale, causal, window, hq,
                      hkv, bq, bk, seq_q, seq_k, interpret, packed=False,
                      rope=None, gate=None, step=1, halves=1, shared=None):
    """``(dq, dk, dv, delta)``. With a gate ``o`` is the gated output and
    ``delta`` (the row sums of ``do * o``) serves both the kernels and the
    gate's own gradient. With the ``shared`` key (latent attention) ``k``
    is the key-value rows and ``v`` None: ``dk`` is their gradient and
    ``dv`` the shared key's, summed over every head in ``flash_bwd_dkv``,
    whose one key-value head is the shared key; a row of the grids is a
    pair of heads (``_pair_widths``).

    One kernel, ``flash_bwd_dkv``, makes all three gradients where what it
    must hold across a grid row fits ``_FUSED_VMEM`` (``_fused_vmem``) and
    the query blocks are whole lane tiles: each tile's probabilities and
    dS are computed once, its grid walks a query head (pair) a row, and dq
    is accumulated in VMEM over the whole sequence; ``delta`` is made by
    XLA. Otherwise dq is ``flash_bwd_dq``'s (which makes ``delta`` with
    the shared key) and ``flash_bwd_dkv``'s grid walks a key-value head a
    row."""
    extras = (rope is not None, gate is not None, shared is not None)
    more = (*(rope or ()), *(() if gate is None else (gate,)),
            *(() if shared is None else (shared,)))
    rope_dim = 0 if shared is None else shared.shape[3]
    if shared is None:
        d = q.shape[2] // hq if packed else q.shape[2]
        v_dim = v.shape[2] // hkv if packed else v.shape[2]
        per = 1
    else:
        hq, d, kv_dim, v_dim, nope = _pair_widths(q, k, shared, hq)
        hkv, per = hq, 2
    bh = q.shape[0] * hq if packed else q.shape[0]
    sq, sk = q.shape[1], k.shape[1]
    nq, nk = sq // bq // halves, sk // bk // halves      # a half's
    # the query heads (pairs) that read one key-value head: with the
    # shared key, every pair
    group = hq if shared is not None else hq // hkv
    held = _fused_vmem(sq, sk, d, v_dim, rope_dim, group, q.dtype.itemsize,
                       rope)
    fused = bq % _LANES == 0 and held <= _FUSED_VMEM
    band = (bq, bk, seq_q, seq_k, causal, window, step, halves, rope_dim)
    delta = None if shared is not None and not fused else _row_sums(
        do, o, hq * per, v_dim // per, packed)[..., None]
    if not fused:     # dq from flash_bwd_dq (and delta, with the shared key)
        kv_steps = _kv_steps(bq, bk, nk, causal, window) + halves - 1
        kv_map = _kv_block_map(hq, hkv, bq, bk, nq, nk, causal, window, packed,
                               step, halves)
        dq_kernel = functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window, bq=bq,
            bk=bk, nq=nq, nk=nk, steps=kv_steps, extras=extras,
            **_band("flash_bwd_dq", *band))

        def q_of_row(b, i, j):
            return _q_index(b, i, hq, packed)

        def stat_of_row(b, i, j):
            return (b, i, 0)

        outs = (pl.BlockSpec((1, bq, d), q_of_row),
                jax.ShapeDtypeStruct(q.shape, q.dtype))
        if shared is None:
            own, last, ins = [pl.BlockSpec((1, bk, d), kv_map),
                              pl.BlockSpec((1, bk, v_dim), kv_map)], [], (k, v)
            sums = (delta, pl.BlockSpec((per, bq, 1), stat_of_row))
        else:
            dq_kernel = functools.partial(dq_kernel, nope=nope)
            own, ins = [pl.BlockSpec((1, bk, kv_dim), kv_map)], (k,)
            last = [pl.BlockSpec((1, 1, bk, rope_dim), _shared_map(kv_map))]
            # the output rows in delta's place: the row sums of do * o are the
            # kernel's second output, made there from the blocks it reads
            sums = (o, pl.BlockSpec((1, bq, v_dim), q_of_row))
            outs = ([outs[0], pl.BlockSpec((per, bq, 1), stat_of_row)],
                    [outs[1], jax.ShapeDtypeStruct((bh * per, sq, 1),
                                                   jnp.float32)])

        dq = pl.pallas_call(
            dq_kernel,
            grid=(bh, halves * nq, kv_steps),
            in_specs=[pl.BlockSpec((1, bq, d), q_of_row)] + own + [
                pl.BlockSpec((1, bq, v_dim), q_of_row),
                pl.BlockSpec((per, bq, 1), stat_of_row),
                sums[1],
            ] + _extra_specs(extras, rope_dim or d, _table_map(
                nq, halves, lambda b, i, j: i), stat_of_row, bq) + last,
            out_specs=outs[0],
            out_shape=outs[1],
            scratch_shapes=(
                _pair_scratch(bq, (nope, rope_dim), jnp.float32)
                + _pair_scratch(bq, (nope, rope_dim), q.dtype)
                if shared is not None else
                [pltpu.VMEM((bq, d), jnp.float32)]
                + ([pltpu.VMEM((bq, d), q.dtype)] if rope is not None
                   else [])),
            interpret=interpret,
            name="flash_bwd_dq",
            **_latent_params(shared),
        )(q, *ins, do, lse, sums[0], *more)
        if shared is not None:
            dq, delta = dq

    # the one key-value head of latent attention is the shared key, which
    # every pair reads
    if shared is not None:
        hkv = 1
    shares = group if fused else 1
    # over two halves a clean key block meets both halves' query blocks
    q_steps = halves * _q_steps(bq, bk, nq, causal, window)

    def q_row(b, j, t):
        # row of the flattened (batch x query heads) and query block of
        # step t: fused the row's own; else this key-value head's
        # ``t // q_steps``-th query head; block clamped to the last one
        # needed
        if fused:
            head, s = b, t
        else:
            head = b if hq == hkv else \
                _div(b, hkv) * hq + _mod(b, hkv) * group + _div(t, q_steps)
            s = _mod(t, q_steps)
        return head, _q_step(j, s, bq, bk, nq, nk, causal, window, step,
                             halves)[0]

    def q_map(b, j, t):
        return _q_index(*q_row(b, j, t), hq, packed)

    def stat_map(b, j, t):
        return (*q_row(b, j, t), 0)

    def stat_row_map(b, j, t):
        return (*q_row(b, j, t), 0, 0)

    def stat_rows(x):
        # a query block's statistics along the lanes, as flash_bwd_dkv
        # reads them: the same bytes
        return x.reshape(bh * per, halves * nq, 1, bq)

    def kv_head(b):     # the key-value head a grid row reads
        return _kv_head(b, hq, hkv) if fused else b

    def kv_of_row(b, j, t):
        return _kv_index(kv_head(b), j, hkv, packed)

    def kv_whole(b, j, t):
        return _kv_index(kv_head(b), 0, hkv, packed)

    rows = sk if shares > 1 else bk     # of the key-value accumulators
    if shared is None:
        own = [pl.BlockSpec((1, bk, d), kv_of_row),
               pl.BlockSpec((1, bk, v_dim), kv_of_row)]
        last = []
        outs = own if shares == 1 else [
            pl.BlockSpec((1, sk, d), kv_whole, pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((1, sk, v_dim), kv_whole,
                         pipeline_mode=pl.Buffered(1))]
        shapes = (v.shape, v.dtype)
        scratch = [pltpu.VMEM((rows, d), jnp.float32),
                   pltpu.VMEM((rows, v_dim), jnp.float32)] + (
            [pltpu.VMEM((d, sq), jnp.float32)] if fused else [])
    else:     # the step's pair's key-value block, and the shared key's
        def kv_of_pair(b, j, t):
            return _kv_index(q_row(b, j, t)[0], j, hq, True)

        def shared_of_row(b, j, t):
            return (_div(b, hq) if fused else b, 0, j, 0)

        own = [pl.BlockSpec((1, bk, kv_dim), kv_of_pair)]
        last = [pl.BlockSpec((1, 1, bk, rope_dim), shared_of_row)]
        outs = own + (last if shares == 1 else [pl.BlockSpec(
            (1, 1, sk, rope_dim), lambda b, j, t: (_div(b, hq), 0, 0, 0),
            pipeline_mode=pl.Buffered(1))])
        shapes = (shared.shape, shared.dtype)
        scratch = _pair_scratch(bk, (nope, v_dim // 2), jnp.float32) + [
            pltpu.VMEM((2, rows, rope_dim), jnp.float32)] + (
            [pltpu.VMEM((2, nope, sq), jnp.float32),
             pltpu.VMEM((2, rope_dim, sq), jnp.float32)] if fused else [])
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(*shapes)]
    if fused:     # the row's dq, the whole sequence, written once
        outs = outs + [pl.BlockSpec(
            (1, sq, d), lambda b, j, t: _q_index(b, 0, hq, packed),
            pipeline_mode=pl.Buffered(1))]
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        params = {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=held + (_LATENT_VMEM if shared is not None
                                     else _STREAM_VMEM))}
        grid = (bh, halves * nk, q_steps)
    else:
        params = _latent_params(shared)
        grid = (k.shape[0] * hkv if packed else k.shape[0], halves * nk,
                group * q_steps)

    got = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nq=nq, nk=nk,
                          steps=q_steps, group=1 if fused else group,
                          extras=extras, shares=shares, fused=fused,
                          **_band("flash_bwd_dkv", *band, dq=int(fused),
                                  dq_vmem_bytes=held),
                          **({} if shared is None else {"nope": nope})),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, d), q_map)] + own + [
            pl.BlockSpec((1, bq, v_dim), q_map),
            pl.BlockSpec((per, 1, 1, bq), stat_row_map),
            pl.BlockSpec((per, 1, 1, bq), stat_row_map),
        ] + _extra_specs(extras, rope_dim or d, _table_map(
            nq, halves, lambda b, j, t: q_row(b, j, t)[1]), stat_map,
            bq, rope[0].shape[0] if fused and rope is not None else 0) + last,
        out_specs=outs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_dkv",
        **params,
    )(q, *((k,) if shared is not None else (k, v)), do, stat_rows(lse),
      stat_rows(delta), *more)
    if fused:
        dk, dv, dq = got
    else:
        dk, dv = got
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


def _pad_seq(x, mult, halves):
    """``x`` (batch, halves x seq, width) with every half padded to a
    multiple of ``mult``."""
    if halves == 1 or x.shape[1] // halves % mult == 0:
        return _pad_to(x, 1, mult)
    b, rows, w = x.shape
    return _pad_to(x.reshape(b * halves, rows // halves, w), 1,
                   mult).reshape(b, -1, w)


def _cut_seq(x, seq, width, halves):
    """The first ``seq`` positions of every half and ``width`` columns."""
    if halves == 1 or x.shape[1] == halves * seq:
        return x[:, :halves * seq, :width]
    b, rows, w = x.shape
    return x.reshape(b * halves, rows // halves, w)[:, :seq, :width].reshape(
        b, halves * seq, width)


# static configuration of one call: (causal, window, hq, hkv, bq, bk,
# interpret, packed, step, halves), hashable so that it rides as one
# non-differentiable argument. ``rope`` is ``None`` or ``(cos, sin, rot)`` (tables (seq, d)
# float32, ``rot`` (d, d)): the QUERY is rotated inside the kernels; ``gate``
# is ``None`` or the logits (batch x heads, seq, 1) of a sigmoid gate on the
# output, a factor a head and position. ``shared`` is ``None`` or latent
# attention's rotary key (batch, 1, seq, rope), already rotated: ``k`` is
# then the key-value rows, ``v`` is ``None``, the tables are ``rope`` wide
# and ``hkv`` is 1.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash(q, k, v, rope, gate, shared, cfg):
    return _flash_fwd(q, k, v, rope, gate, shared, cfg)[0]


def _flash_fwd(q, k, v, rope, gate, shared, cfg):
    causal, window, hq, hkv, bq, bk, interpret, packed, step, halves = cfg
    sq, sk = q.shape[1] // halves, k.shape[1] // halves      # a half's
    d = q.shape[2] // hq if packed else q.shape[2]
    # the scores are over the queries' and keys' width, whatever the
    # values' (latent attention: 192 and 128)
    scale = 1.0 / (d ** 0.5)
    # Blocks span the full head_dim, so any d equal to the array dim lowers
    # fine; Mosaic pads lanes in VMEM itself without extra HBM traffic.
    # Only round tiny/odd head dims up to a sublane multiple (packed heads
    # are whole lane tiles already).
    def dm(x):
        return 1 if packed else 8 if x.shape[2] >= 8 else x.shape[2]

    qp = _pad_seq(_pad_to(q, 2, dm(q)), bq, halves)
    kp = _pad_seq(_pad_to(k, 2, dm(k)), bk, halves)
    vp = None if v is None else _pad_seq(_pad_to(v, 2, dm(v)), bk, halves)
    sharedp = None if shared is None else _pad_to(shared, 2, bk)
    ropep = None if rope is None else (
        _pad_to(rope[0], 0, bq), _pad_to(rope[1], 0, bq),
        rope[2].astype(q.dtype))
    factor = None if gate is None else _pad_to(
        jax.nn.sigmoid(gate.astype(jnp.float32)), 1, bq)
    o, lse = _flash_fwd_padded(qp, kp, vp, scale=scale, causal=causal,
                               window=window, hq=hq, hkv=hkv, bq=bq, bk=bk,
                               seq_q=sq, seq_k=sk,
                               interpret=interpret, packed=packed,
                               rope=ropep, gate=factor, step=step,
                               halves=halves, shared=sharedp)
    # under a recomputation segment (executor._remat_segments) the output
    # and the row statistics are kept: recomputing them is this kernel again
    o = checkpoint_name(o, REMAT_KEEP)
    lse = checkpoint_name(lse, REMAT_KEEP)
    widths = (q.shape[2], k.shape[2], None if v is None else v.shape[2],
              o.shape[2] if packed else v.shape[2])
    return _cut_seq(o, sq, widths[3], halves), (
        qp, kp, vp, o, lse, ropep, factor, sharedp, rope, gate, scale, sq,
        sk, widths)


def _flash_bwd(cfg, res, g):
    causal, window, hq, hkv, bq, bk, interpret, packed, step, halves = cfg
    qp, kp, vp, o, lse, ropep, factor, sharedp, rope, gate, scale, sq, sk, \
        widths = res
    # match residual padding
    gp = _pad_seq(_pad_to(g, 2, o.shape[-1]), bq, halves)
    dq, dk, dv, delta = _flash_bwd_padded(
        qp, kp, vp, o, lse, gp, scale=scale, causal=causal, window=window,
        hq=hq, hkv=hkv, bq=bq, bk=bk, seq_q=sq, seq_k=sk,
        interpret=interpret, packed=packed, rope=ropep, gate=factor,
        step=step, halves=halves, shared=sharedp)
    d_rope = None if rope is None else tuple(jnp.zeros_like(a) for a in rope)
    # o is the gated output: sum(do * o) = s * sum(do * ungated), and the
    # sigmoid's slope is s (1 - s)
    d_gate = None if gate is None else \
        (delta * (1.0 - factor))[:, :sq].astype(gate.dtype)
    dq = _cut_seq(dq, sq, widths[0], halves)
    dk = _cut_seq(dk, sk, widths[1], halves)
    if sharedp is not None:    # dv is the shared key's
        return dq, dk, None, d_rope, d_gate, dv[:, :, :sk]
    return dq, dk, _cut_seq(dv, sk, widths[2], halves), d_rope, d_gate, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _choose_blocks(causal):
    """Block shapes where the caller names none. Without a mask the blocks
    that reached 64.5 % of the roofline on a v5e (PERF.md, PR 26). Under a
    causal mask square blocks of 1,024, so that the blocks above the
    diagonal are whole and skipped and the offset of a tile's first query
    from its first key takes few values. A window changes nothing: a tile
    the band crosses is walked by sub-tiles, so what is computed outside
    the band does not depend on the block's size, and the larger block
    spreads a query block's fixed cost (the rotation, the final division,
    the steps themselves) over more keys. PERF.md section 7 has the sweep
    (2,048 x 2,048 is 6 % better still for a window of 512 and does not
    fit VMEM at a head of 256)."""
    return (1024, 1024) if causal else (512, 2048)


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
                        v.reshape(b * h, v.shape[2], d), None, None, None,
                        (causal, None, h, h, bq, bk, interpret, False, 1, 1))
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
                                      hkv=h, bq=bq, bk=bk,
                                      seq_q=sq, seq_k=sk,
                                      interpret=interpret)
    return (dq[:, :sq, :d].reshape(b, h, sq, d),
            dk[:, :sk, :d].reshape(b, h, sk, d),
            dv[:, :sk, :d].reshape(b, h, sk, d))


def rotary_tables(seq, head_dim, inv_freq, attention_factor=1.0,
                  interleave=False, offset=0):
    """``(cos, sin, rot)`` for ``flash_attention(rotary=...)`` or
    ``rotate_heads``: ``2 * len(inv_freq)`` dimensions of a head from
    ``offset`` on rotate, the rest pass through (cos 1, sin 0). Pair ``i``
    (frequency ``inv_freq[i]``) is dimensions ``offset + i`` and ``offset
    + i + len(inv_freq)``, or with ``interleave`` the adjacent ``offset +
    2i`` and ``offset + 2i + 1``. ``rot`` is the signed permutation with
    ``x @ rot`` giving ``-x[second]`` at a pair's first dimension and
    ``x[first]`` at its second."""
    import numpy as np

    half = len(inv_freq)
    first = offset + (2 * np.arange(half) if interleave else np.arange(half))
    second = first + (1 if interleave else half)
    rot = np.zeros((head_dim, head_dim), np.float32)
    rot[second, first] = -1.0
    rot[first, second] = 1.0
    if not interleave and not offset:
        # the rotate-half tables, built as they always were: the programs
        # of the decoders that use them do not change
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv_freq, jnp.float32)[None, :]
        still = head_dim - 2 * half
        cos = jnp.cos(angle) * attention_factor
        sin = jnp.sin(angle) * attention_factor
        return (jnp.concatenate([cos, cos, jnp.ones((seq, still))], axis=1),
                jnp.concatenate([sin, sin, jnp.zeros((seq, still))], axis=1),
                jnp.asarray(rot))
    # a frequency a column, none where nothing turns: whole-width tables
    # that XLA computes inside the rotation's fusion
    freq = np.zeros(head_dim, np.float32)
    freq[first] = freq[second] = inv_freq
    turns = np.zeros(head_dim, bool)
    turns[first] = turns[second] = True
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq)[None, :]
    return (jnp.where(turns, jnp.cos(angle) * attention_factor, 1.0),
            jnp.where(turns, jnp.sin(angle) * attention_factor, 0.0),
            jnp.asarray(rot))


def latent_fits(heads, nope, rope, v_dim):
    """Whether ``flash_attention(key_rope=...)`` takes latent attention of
    these widths as projected: the pair of query heads and the key-value
    head are whole lane tiles, as are the keys' and the values' columns."""
    return heads % 2 == 0 and (2 * (nope + rope)) % _LANES == 0 \
        and nope % _LANES == 0 and v_dim % _LANES == 0


def _latent_attention(q, kv, key_rope, rotary, causal, block_q, block_k,
                      interpret):
    """``flash_attention(key_rope=...)``: ``_flash`` with the shared key."""
    b, sq, hq, d = q.shape
    sk, rope = kv.shape[1], key_rope.shape[-1]
    v_dim = kv.shape[3] - (d - rope)
    if rotary is None or rotary[0].shape[-1] != rope \
            or key_rope.shape != (b, sk, rope) or kv.shape[2] != hq \
            or not latent_fits(hq, d - rope, rope, v_dim):
        raise ValueError(
            f"flash_attention: latent attention of {hq} heads of {d} "
            f"(rotary {rope}) over keys and values {kv.shape[3]} wide needs "
            "rotary tables of the rotary key's width and heads that pair "
            f"into whole lane tiles of {_LANES}")
    chosen = _choose_blocks(causal)
    bq = min(chosen[0] if block_q is None else block_q, max(8, sq))
    bk = min(chosen[1] if block_k is None else block_k, max(8, sk))
    o = _flash(q.reshape(b, sq, hq * d), kv.reshape(b, sk, hq * kv.shape[3]),
               None, rotary, None, key_rope.reshape(b, 1, sk, rope),
               (causal, None, hq, 1, bq, bk, interpret, True, 1, 1))
    return o.reshape(b, sq, hq, v_dim)


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None, window=None, heads_last=False,
                    rotary=None, gate=None, step=1, halves=1, key_rope=None):
    """Blocked flash attention. q: [batch, heads, seq, head_dim]; k, v:
    [batch, kv_heads, seq, head_dim] with ``heads`` a multiple of
    ``kv_heads`` (query head ``i`` reads key-value head ``i // (heads /
    kv_heads)``). The values (and the output) may be of another width
    than the queries and keys, whose width alone scales the scores:
    latent attention's keys are 192 wide and its values 128, and nothing
    is padded to the wider of the two. ``window=W`` (with
    ``causal=True``) lets query ``t`` see keys ``t - W + 1 .. t``; key
    blocks outside the band are skipped.

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

    ``step=B`` (with ``causal=True``, no window) is the block-causal mask:
    query ``t`` sees the keys of its own block of ``B`` positions and of
    every block before it. ``halves=2`` is block-diffusion training: q, k
    and v hold a sequence's noisy copy and then its clean copy (``seq`` is
    twice a copy's length; the rotary tables are one copy's, both have its
    positions); a clean query sees the clean keys block-causally, a noisy
    query the clean keys of the blocks before its own and the noisy keys of
    its own block. No gate there. Sequences and blocks are whole steps.

    ``key_rope`` [batch, seq, rope] is latent attention (DeepSeek-V3)
    read as the projections leave its rows (``heads_last``, ``v`` None):
    a head of ``q`` [batch, seq, heads, nope + rope] is ``[q_nope | q_pe]``,
    a head of ``k`` [batch, seq, heads, nope + v_dim] is ``[k_nope | v]``,
    and ``key_rope`` is ONE rotary key a position, already rotated, that
    every head reads. The score is ``q_nope . k_nope + rot(q_pe) . k_pe``
    scaled by ``(nope + rope) ** -0.5``, bfloat16 products accumulated in
    float32; ``rotary`` (``rope`` wide) rotates ``q_pe`` on the query block
    in VMEM. A row of the kernels' grids is a PAIR of heads (``nope +
    rope`` need not be whole lane tiles, two heads are: 384 columns at
    192): their query block, their key-value block (``[k_nope | v]`` twice,
    whose columns split into the keys' and the values') and the block of
    the one rotary key, which every pair reads; the body runs on both heads
    at once, a leading axis of 2. Nothing is transposed, broadcast or
    rotated in HBM but that small key. The output is ``[batch, seq, heads,
    v_dim]`` in the rows' layout; the gradient by ``q`` comes in ``q``'s
    layout (written once a pair, ``dq_pe`` unrotated in the kernel), by
    ``k`` in ``k``'s, and by ``key_rope`` summed over every head inside
    ``flash_bwd_dkv``: its grid walks a pair a row, and the shared key's
    gradient accumulates over the whole sequence and every pair (where
    the two kernels run, ``flash_bwd_dq`` makes dq and the row sums of
    ``do * o`` and ``flash_bwd_dkv`` walks all the pairs of a key block).
    ``latent_fits`` says which widths it takes. Set-up rules its kernels
    keep, because each kernel body is traced and lowered once a layer and
    the train step's lowering is paid in ``setup_s``: heads run on the
    grid (no loop over heads in a body; a pair is one body); index maps
    divide through ``_div`` / ``_mod``; no table is a host constant
    (``rotary_tables`` builds them in the program); a pair walks larger
    sub-tiles (``_PAIR_SUB``), so that its bodies have few pieces; nothing is
    inserted between the caller's scope and ``flash_*/pallas_call`` in the
    kernels' names; and the lowered size of a layer is held by a test
    (``tests/test_tpu_lowering.py``).

    Exact (up to fp accumulation order) match of the dense masked softmax
    attention, with O(block) VMEM footprint forward. Differentiable via
    a Pallas backward kernel (two where a row's whole-sequence dq does not
    fit its VMEM budget: ``_flash_bwd_padded``). Block shapes are chosen
    here from the mask
    (``_choose_blocks``) unless given. On non-TPU backends defaults to
    interpret mode so the same kernel code runs in tests.
    """
    if interpret is None:
        interpret = _use_interpret()
    if key_rope is not None:
        if not heads_last or v is not None or gate is not None \
                or window is not None or step != 1 or halves != 1:
            raise ValueError("flash_attention: latent attention takes the "
                             "rows as projected (heads_last, v None) and "
                             "no gate, window or staircase")
        return _latent_attention(q, k, key_rope, rotary, causal, block_q,
                                 block_k, interpret)
    if (rotary is not None or gate is not None) and not (
            heads_last and q.shape[3] % _LANES == 0):
        raise ValueError("flash_attention: rotary and gate need heads_last "
                         f"and a head_dim that is a multiple of {_LANES}")
    if heads_last and (q.shape[3] % _LANES or v.shape[3] % _LANES):
        # heads of part of a lane tile cannot be cut out of the packed
        # last axis
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal, block_q,
                            block_k, interpret, window, step=step,
                            halves=halves)
        return o.transpose(0, 2, 1, 3)
    seq_axis, head_axis = (1, 2) if heads_last else (2, 1)
    b, d, dv = q.shape[0], q.shape[3], v.shape[3]
    hq, sq = q.shape[head_axis], q.shape[seq_axis]
    hkv, sk = k.shape[head_axis], k.shape[seq_axis]
    if hq % hkv or v.shape[head_axis] != hkv:
        raise ValueError(f"flash_attention: {hq} query heads over "
                         f"{hkv} / {v.shape[head_axis]} key / value heads")
    if k.shape[3] != d:
        raise ValueError(f"flash_attention: queries {d} and keys "
                         f"{k.shape[3]} wide")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_attention: a window needs causal=True "
                             "and at least one key")
        if window >= sk:
            window = None   # the causal mask alone
    if (step != 1 or halves != 1) and (
            step < 1 or halves not in (1, 2) or gate is not None
            or sq % halves or sk % halves):
        raise ValueError("flash_attention: a staircase has a step of at "
                         "least one position, one or two halves and no gate")
    chosen = _choose_blocks(causal)
    bq = min(chosen[0] if block_q is None else block_q,
             max(8, sq // halves))
    bk = min(chosen[1] if block_k is None else block_k,
             max(8, sk // halves))
    cfg = (causal, window, hq, hkv, bq, bk, interpret, heads_last, step,
           halves)
    # pad seq blocks up so bq | sq_padded handled inside _flash_fwd
    if heads_last:
        if gate is not None:     # as the row statistics lie: a row a head
            gate = gate.transpose(0, 2, 1).reshape(b * hq, sq, 1)
        o = _flash(q.reshape(b, sq, hq * d), k.reshape(b, sk, hkv * d),
                   v.reshape(b, sk, hkv * dv), rotary, gate, None, cfg)
        return o.reshape(b, sq, hq, dv)
    o = _flash(q.reshape(b * hq, sq, d), k.reshape(b * hkv, sk, d),
               v.reshape(b * hkv, sk, dv), None, None, None, cfg)
    return o.reshape(b, hq, sq, dv)


# --------------------------------------------------------------------------
# registry cost models (ops/pallas/registry.py contract)
# --------------------------------------------------------------------------
# Model FLOPs from the FULL (padded) avals — exact trace-time arithmetic,
# comparable across runs. Counts the matmul work (the softmax elementwise
# tail is <1% at any real head_dim); causal masking is NOT discounted so
# the number matches the dense attention it replaces (MFU convention:
# model FLOPs, not grid-cell recompute).

def _flash_dims(in_avals, stats=None):
    """``(bh, sq, sk, d, dv)``; the values' width by its ratio to the keys'
    (both have the key-value heads, in either layout). A latent call's
    operands end in the shared key (batch, 1, seq, rope) and its ``k`` is
    the key-value rows: the heads are counted from ``stats``, the row
    statistics (batch x heads, ...) (the forward's second result, the
    backward's fourth operand)."""
    q, k, v = in_avals[0], in_avals[1], in_avals[2]
    bh, sq, d = q.shape
    sk = k.shape[1]
    key = in_avals[-1]
    if len(key.shape) == 4 and key.shape[1] == 1 and key.shape[2] == sk:
        heads = int(stats.shape[0]) // int(bh)
        d = int(d) // heads
        nope = d - int(key.shape[3])
        return int(stats.shape[0]), int(sq), int(sk), d, \
            int(k.shape[2]) // heads - nope
    return int(bh), int(sq), int(sk), int(d), d * v.shape[2] / k.shape[2]


def _flash_fwd_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d, dv = _flash_dims(
        in_avals, out_avals[1] if len(out_avals) > 1 else None)
    # QK^T (d) and PV (dv), per batch*head slab
    return KernelCost(flops=2.0 * bh * sq * sk * (d + dv),
                      bytes=io_bytes(in_avals, out_avals))


def _flash_bwd_dq_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d, dv = _flash_dims(
        in_avals, in_avals[3] if len(in_avals) > 3 else None)
    # recomputed scores (d) + dp (dv) + dq accumulation (d)
    return KernelCost(flops=2.0 * bh * sq * sk * (2 * d + dv),
                      bytes=io_bytes(in_avals, out_avals))


def _flash_bwd_dkv_cost(in_avals, out_avals):
    from .registry import KernelCost

    bh, sq, sk, d, dv = _flash_dims(
        in_avals, in_avals[3] if len(in_avals) > 3 else None)
    # recomputed scores (d) + dp (dv) + dv (dv) + dk (d) accumulations,
    # and dq (d) where the call makes it (its third result)
    return KernelCost(
        flops=2.0 * bh * sq * sk * (2 * (d + dv) + d * (len(out_avals) > 2)),
        bytes=io_bytes(in_avals, out_avals))


register_kernel(
    "flash_fwd", _flash_fwd_cost, module=__name__,
    doc="blocked online-softmax attention forward (o, lse)")
register_kernel(
    "flash_bwd_dq", _flash_bwd_dq_cost, module=__name__,
    doc="flash attention backward: dq accumulated over key blocks")
register_kernel(
    "flash_bwd_dkv", _flash_bwd_dkv_cost, module=__name__,
    doc="flash attention backward: dk/dv accumulated over query blocks, "
        "and dq in VMEM where it fits (then the whole backward)")
