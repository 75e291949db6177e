"""Fused Adam/AdamW: the whole update as ONE blocked kernel pass.

``Optimizer.apply``'s per-leaf loop emits ~10 elementwise HLO ops per
parameter tensor — a tree of small fused loops XLA schedules one after
another. This kernel flattens the (param, grad, m, v) pytrees into one
padded slab and runs the complete Adam update — preprocess, moment
updates, bias correction, weight step, AdamW's decoupled decay — tile by
tile through VMEM: inside the kernel every element is read once and
written once (the registry's byte model prices that floor). Honest
accounting: the flatten/
unflatten concatenate+slice passes around the kernel cost HBM copies of
their own, so the net step-time win over a WELL-fused per-leaf tree is
workload- and backend-dependent — the kernel's durable wins are the
single program (one launch, no per-leaf scheduling gaps), the fixed
pass structure XLA can't unfuse, and the slab layout the sharded
optimizer work in ROADMAP item 4 builds on. The delta is not measured
on a chip (no benchmark cell runs the kernel).

Exact-parity contract: the kernel reproduces ``Adam._apply_one``'s f32
arithmetic op-for-op (same expressions, same evaluation order), so the
fused and per-leaf paths produce BITWISE-identical params and moments —
a run can flip the gate mid-training (or resume a per-leaf checkpoint
fused, and vice versa: the state pytree layout is unchanged,
``{name: (m, v, t)}``, no migration). Enforced by
tests/test_pallas_kernels.py.

Sharding: the update is pure per-element math, so it composes unchanged
with the P("dp") fused train step — inside the shard_map body the
replicated params update replicatedly, exactly like the per-leaf tree it
replaces. Gate: ``Adam(fused=True)`` / env ``MXNET_TPU_FUSED_ADAM``.

Per-leaf scalars (bias-correction factors from each leaf's step counter,
AdamW's decay-filtered weight decay) ride in SMEM as whole ``(T,)`` arrays
indexed by the grid position — leaves are padded to whole tiles so no tile
straddles two leaves. Each ``block``-element tile is presented to Mosaic
as an ``(8, block/8)`` slab (f32 sublane count), so ``block`` must be a
multiple of 8 and, to fill the lanes, of 1024.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import ENV_OFF_VALUES, ENV_ON_VALUES, MXNetError
from ._common import resolve_interpret
from .registry import KernelCost, io_bytes, register_kernel

__all__ = ["fused_adam_apply", "fused_resolve", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 8192  # f32 elements per tile (32 KB): VPU-bound either way
_SUBLANES = 8         # f32 rows of one TPU vreg tile


def fused_resolve(value) -> bool:
    """Normalize the ``fused=`` optimizer knob: None -> env gate
    ``MXNET_TPU_FUSED_ADAM`` (unrecognized values raise rather than
    silently picking a side); otherwise truthiness."""
    if value is None:
        raw = os.environ.get("MXNET_TPU_FUSED_ADAM", "").strip().lower()
        if raw in ("",) + ENV_OFF_VALUES:
            return False
        if raw in ENV_ON_VALUES:
            return True
        raise MXNetError(
            f"MXNET_TPU_FUSED_ADAM={raw!r} not understood (use 1/0)")
    return bool(value)


def _adam_kernel(w_ref, g_ref, m_ref, v_ref, c1_ref, c2_ref, wd_ref, lr_ref,
                 wn_ref, mn_ref, vn_ref, *, beta1, beta2, eps, rescale,
                 clip, wd_l2, decoupled):
    # op-for-op mirror of Adam._preprocess + _apply_one + _step_update:
    # any deviation (even reassociation) breaks the bitwise-parity
    # contract the tests pin
    w = w_ref[:]
    g = g_ref[:] * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    g = g + wd_l2 * w
    m = beta1 * m_ref[:] + (1 - beta1) * g
    v = beta2 * v_ref[:] + (1 - beta2) * jnp.square(g)
    i = pl.program_id(0)
    mhat = m / c1_ref[i]
    vhat = v / c2_ref[i]
    lr = lr_ref[0]
    new_w = w - lr * mhat / (jnp.sqrt(vhat) + eps)
    if decoupled:
        new_w = new_w - lr * wd_ref[i] * w
    wn_ref[:] = new_w
    mn_ref[:] = m
    vn_ref[:] = v


def _flatten_padded(leaves, block):
    """Concatenate f32-cast leaves, each padded up to a whole number of
    ``block``-sized tiles (tiles never straddle leaves, so per-leaf
    scalars are per-tile constants)."""
    parts = []
    for leaf in leaves:
        flat = leaf.astype(jnp.float32).ravel()
        pad = (-flat.shape[0]) % block
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        parts.append(flat)
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return out


def fused_adam_apply(opt, params, grads, states, lr, *, block=None,
                     interpret=None):
    """One fused kernel pass over the whole parameter set.

    ``opt`` is an Adam (or AdamW) instance — hyperparameters are read
    off it so the two paths cannot drift. ``states`` is the standard
    ``{name: (m, v, t)}`` pytree and comes back in the SAME layout.
    Returns ``(new_params, new_states)`` exactly like ``Optimizer.apply``.
    """
    interpret = resolve_interpret(interpret)
    block = int(block or DEFAULT_BLOCK)
    if block % _SUBLANES:
        raise MXNetError(f"fused_adam block={block} must be a multiple of "
                         f"{_SUBLANES} (one tile is an ({_SUBLANES}, "
                         f"block/{_SUBLANES}) slab)")
    names = list(params)
    if not names:
        return {}, {}
    decoupled = getattr(opt, "weight_decay", None) is not None
    decay_filter = getattr(opt, "decay_filter", None)

    leaves_w = [params[k] for k in names]
    sizes = [int(np.prod(np.shape(w))) or 1 for w in leaves_w]
    tiles = [-(-s // block) for s in sizes]
    T = sum(tiles)

    # tile i is rows [8i, 8i+8) of the (8T, block/8) slab
    slab = (T * _SUBLANES, block // _SUBLANES)
    flat_w = _flatten_padded(leaves_w, block).reshape(slab)
    flat_g = _flatten_padded([grads[k] for k in names],
                             block).reshape(slab)
    flat_m = _flatten_padded([states[k][0] for k in names],
                             block).reshape(slab)
    flat_v = _flatten_padded([states[k][1] for k in names],
                             block).reshape(slab)

    # per-leaf scalars, repeated per tile. The bias correction uses the
    # SAME expressions as _apply_one (t+1, 1-beta**t) so the divided-by
    # values are bitwise identical.
    t_new = {k: states[k][2] + 1.0 for k in names}
    c1_rows, c2_rows, wd_rows = [], [], []
    for k, nt in zip(names, tiles):
        c1 = jnp.reshape(1 - opt.beta1 ** t_new[k], (1,))
        c2 = jnp.reshape(1 - opt.beta2 ** t_new[k], (1,))
        c1_rows.append(jnp.broadcast_to(c1.astype(jnp.float32), (nt,)))
        c2_rows.append(jnp.broadcast_to(c2.astype(jnp.float32), (nt,)))
        if decoupled:
            wd = opt.weight_decay if (decay_filter is None
                                      or decay_filter(k)) else 0.0
            wd_rows.append(np.full((nt,), wd, np.float32))
    c1_t = jnp.concatenate(c1_rows)
    c2_t = jnp.concatenate(c2_rows)
    wd_t = jnp.asarray(np.concatenate(wd_rows)) if decoupled \
        else jnp.zeros((T,), jnp.float32)
    lr_s = jnp.asarray(lr, jnp.float32).reshape(1)

    kern = functools.partial(
        _adam_kernel, beta1=opt.beta1, beta2=opt.beta2, eps=opt.epsilon,
        rescale=opt.rescale_grad, clip=opt.clip_gradient,
        wd_l2=(0.0 if decoupled else opt.wd), decoupled=decoupled)
    big = pl.BlockSpec((_SUBLANES, slab[1]), lambda i: (i, 0))
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array
    new_w, new_m, new_v = pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[big, big, big, big, scalars, scalars, scalars, scalars],
        out_specs=[big, big, big],
        out_shape=[jax.ShapeDtypeStruct(slab, jnp.float32)] * 3,
        interpret=interpret,
        name="fused_adam",
    )(flat_w, flat_g, flat_m, flat_v, c1_t, c2_t, wd_t, lr_s)

    new_params, new_states = {}, {}
    off = 0
    new_w, new_m, new_v = (a.ravel() for a in (new_w, new_m, new_v))
    for k, size, nt in zip(names, sizes, tiles):
        span = nt * block
        shape = np.shape(params[k])
        new_params[k] = new_w[off:off + size].reshape(shape).astype(
            params[k].dtype)
        new_states[k] = (new_m[off:off + size].reshape(shape),
                         new_v[off:off + size].reshape(shape),
                         t_new[k])
        off += span
    return new_params, new_states


def _adam_cost(in_avals, out_avals):
    # ~14 elementwise ops per parameter element (preprocess, two moment
    # updates, bias correction, sqrt, update); slab size = first operand
    n = int(getattr(in_avals[0], "size", 0)) if in_avals else 0
    return KernelCost(flops=14.0 * n, bytes=io_bytes(in_avals, out_avals))


register_kernel(
    "fused_adam", _adam_cost, module=__name__,
    doc="whole-tree Adam/AdamW update (preprocess + moments + bias "
        "correction + weight step) in one blocked pass")
