"""The Mamba-2 mixer: a state-space layer of a hybrid decoder.

No reference counterpart (MXNet 0.5 predates it); it follows the OpProp
contract so that a hybrid decoder is a Symbol like any other model and
trains through ``FeedForward.fit`` (doc/developer-guide/decoder-ops.md).

  Mamba2Mixer   the part of a Mamba-2 layer between its input and its
                output projection (Dao & Gu, arXiv:2405.21060): a causal
                depthwise convolution over positions, the selective scan in
                its chunked ("SSD") form, a gated RMS norm by groups.

Rows are positions: activations are ``(batch * seq_len, width)`` as
``FullyConnected`` makes them, and the operator is told ``seq_len``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpProp, Range, REQUIRED, register_op


def causal_conv(x, weight, bias):
    """Depthwise causal convolution over positions: ``x`` (batch, T, C),
    ``weight`` (C, K), ``bias`` (C,); output ``t`` is ``sum_k weight[:, k]
    * x[t - (K - 1) + k] + bias``, positions before the first read as
    zero. In float32."""
    k = weight.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for i in range(k):
        out = out + padded[:, i:i + t] * w[:, i]
    return out


def _product(spec, a, b, dtype):
    """One product on the MXU: operands in ``dtype``, float32 out."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def chunked_scan(x, dt, a, b, c, chunk, dtype):
    """The selective scan ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t`` of every head, in its chunked dual form.

    ``x`` (batch, T, H, P); ``dt`` (batch, T, H) float32, already through
    its softplus; ``a`` (H,) float32, negative; ``b``, ``c`` (batch, T, G,
    N), head ``h`` reading group ``h // (H / G)``. Within a chunk of
    ``chunk`` positions the causal products ``(L o C B^T) (dt x)``, ``L``
    the decays between two positions; each chunk's own state ``B^T (decay
    dt x)``; the states carried from chunk to chunk by a scan in float32;
    their part of the output ``C S`` decayed to each position. Products
    take their operands in ``dtype`` and accumulate in float32; the
    segment sums, the decays and the carried state are float32. Returns
    ``(y, chunk_decay)``: ``y`` (batch, T, H, P) float32 and ``exp(a sum
    of a chunk's dt)`` (batch, T / chunk, H), the share of the carried
    state that crosses a chunk."""
    batch, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q, r = chunk, h // g
    nc = t // q
    x = x.reshape(batch, nc, q, g, r, p)
    b = b.reshape(batch, nc, q, g, n)
    c = c.reshape(batch, nc, q, g, n)
    # positions last, the decays' (i, j) planes as the products read them
    dt = jnp.moveaxis(dt.reshape(batch, nc, q, g, r), 2, -1)
    cs = jnp.cumsum(dt * a.reshape(g, r, 1), axis=-1)   # (b, nc, g, r, q)
    # within a chunk: position i reads j <= i decayed by exp(cs_i - cs_j)
    gap = cs[..., :, None] - cs[..., None, :]           # (b, nc, g, r, i, j)
    decay = jnp.exp(jnp.where(np.tril(np.ones((q, q), bool)), gap, -jnp.inf))
    scores = _product("bcign,bcjgn->bcgij", c, b, dtype)
    weights = scores[:, :, :, None] * decay * dt[..., None, :]
    y = _product("bcgrij,bcjgrp->bcigrp", weights, x, dtype)
    # each chunk's own state at its end, then the states carried over
    to_end = jnp.moveaxis(jnp.exp(cs[..., -1:] - cs) * dt, -1, 2)
    own = _product("bcjgn,bcjgrp->bcgrpn", b, x * to_end[..., None], dtype)
    crossing = jnp.exp(cs[..., -1])                     # (b, nc, g, r)

    def carry(state, chunk_in):
        own_c, crossing_c = chunk_in
        return crossing_c[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros_like(own[:, 0]),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(crossing, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (b, nc, g, r, p, n)
    y = y + _product("bcign,bcgrpn->bcigrp", c, entering, dtype) \
        * jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None]
    return y.reshape(batch, t, h, p), crossing.reshape(batch, nc, h)


def gated_group_norm(y, z, gamma, groups, eps):
    """``RMSNorm_grouped(y * silu(z)) * gamma``, the mean square taken
    over each of ``groups`` equal groups of channels, in float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    rows, width = gated.shape
    parts = gated.reshape(rows, groups, width // groups)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
                        + eps)
    return (parts * inv).reshape(rows, width) * gamma.astype(jnp.float32)


@register_op("Mamba2Mixer")
class Mamba2MixerOp(OpProp):
    """The mixer of a Mamba-2 layer on projected rows, rows = batch *
    seq_len.

    ``xbc`` (rows, H P + 2 G N), ``z`` (rows, H P) and ``dt`` (rows, H) as
    one input projection leaves them, ``[z | xbc | dt]`` its columns.
    ``xbc <- silu(conv(xbc) + conv_bias)``, the causal depthwise
    convolution over positions of ``conv_weight`` (H P + 2 G N,
    conv_kernel); then ``[x | B | C] = xbc``, ``x`` H heads of P, ``B`` and
    ``C`` G groups of N. Step sizes ``dt <- softplus(dt + dt_bias)``,
    decays ``A = -exp(A_log)``; head ``h`` reads group ``h // (H / G)``:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D
    x_t`` (``chunked_scan``, chunks of ``chunk_size``). Output (rows, H P):
    ``RMSNorm(y * silu(z))`` over each of the G groups of channels, times
    ``norm_gamma``. The executor emits it under ``<node>/Mamba2Mixer``,
    its three parts under ``conv``, ``ssd`` and ``gate_norm``.

    Auxiliary state ``ssm_record`` (RING + 1, 4), float32: row 0 counts the
    training steps modulo ``STEP_WRAP``; the row of a step, ``1 + step %
    RING``, holds that step's mean and largest ``dt`` and the mean and
    least share of the carried state that crosses a chunk, ``exp(A sum of
    the chunk's dt)``. ``epoch_record`` makes ``fit``'s ``fit.epoch.ssm``
    line of the epoch's steps."""

    RING = 1024
    STEP_WRAP = 1 << 23

    params = {
        "seq_len": (Range(int, lo=1), REQUIRED, "positions a sequence"),
        "num_heads": (Range(int, lo=1), REQUIRED, "heads H of the scan"),
        "head_dim": (Range(int, lo=1), REQUIRED, "width P of a head"),
        "n_groups": (Range(int, lo=1), REQUIRED,
                     "groups G of B and C, and of the gated norm"),
        "state_size": (Range(int, lo=1), REQUIRED,
                       "width N of a group's B and C"),
        "conv_kernel": (Range(int, lo=1), 4, "positions the convolution reads"),
        "chunk_size": (Range(int, lo=1), 128, "positions a chunk of the scan"),
        "eps": (Range(float, lo=0.0), 1e-5, "added to the gated norm's mean "
                                            "square"),
    }

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if self.num_heads % self.n_groups \
                or (self.num_heads * self.head_dim) % self.n_groups:
            raise MXNetError(f"Mamba2Mixer: {self.num_heads} heads of "
                             f"{self.head_dim} over {self.n_groups} groups")
        if self.seq_len % self.chunk_size:
            raise MXNetError(f"Mamba2Mixer: chunks of {self.chunk_size} do "
                             f"not divide a sequence of {self.seq_len}")

    def list_arguments(self):
        return ["xbc", "z", "dt", "conv_weight", "conv_bias", "dt_bias",
                "A_log", "D", "norm_gamma"]

    def list_auxiliary_states(self):
        return ["ssm_record"]

    def infer_shape(self, in_shapes):
        rows = next((s[0] for s in in_shapes[:3] if s is not None), None)
        if rows is None:
            raise MXNetError("Mamba2Mixer: no input shape known")
        if rows % self.seq_len:
            raise MXNetError(f"Mamba2Mixer: {rows} rows are not whole "
                             f"sequences of {self.seq_len}")
        h, inner = self.num_heads, self.num_heads * self.head_dim
        conv = inner + 2 * self.n_groups * self.state_size
        return [(rows, conv), (rows, inner), (rows, h),
                (conv, self.conv_kernel), (conv,), (h,), (h,), (h,),
                (inner,)], [(rows, inner)], [(self.RING + 1, 4)]

    def fwd(self, ins, aux, is_train, rng):
        xbc, z, dt, conv_w, conv_b, dt_bias, a_log, d, gamma = ins
        t, h, p = self.seq_len, self.num_heads, self.head_dim
        g, n = self.n_groups, self.state_size
        batch = xbc.shape[0] // t
        dtype = xbc.dtype
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(
                xbc.reshape(batch, t, -1), conv_w, conv_b)).astype(dtype)
        with jax.named_scope("ssd"):
            x = xbc[..., :h * p].reshape(batch, t, h, p)
            b = xbc[..., h * p:h * p + g * n].reshape(batch, t, g, n)
            c = xbc[..., h * p + g * n:].reshape(batch, t, g, n)
            step = jax.nn.softplus(dt.reshape(batch, t, h).astype(jnp.float32)
                                   + dt_bias.astype(jnp.float32))
            decay = -jnp.exp(a_log.astype(jnp.float32))
            y, crossing = chunked_scan(x, step, decay, b, c, self.chunk_size,
                                       dtype)
            y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        with jax.named_scope("gate_norm"):
            out = gated_group_norm(y.reshape(batch * t, h * p), z, gamma, g,
                                   self.eps).astype(dtype)
        record = aux[0]
        if is_train:
            step, crossing = jax.lax.stop_gradient((step, crossing))
            seen = jnp.stack([jnp.mean(step), jnp.max(step),
                              jnp.mean(crossing), jnp.min(crossing)])
            count = record[0, 0]
            slot = 1 + (count % self.RING).astype(jnp.int32)
            record = jax.lax.dynamic_update_slice(
                record, seen[None].astype(record.dtype), (slot, 0))
            record = record.at[0, 0].set((count + 1) % self.STEP_WRAP)
        return [out], [record]

    def epoch_record(self, before, after):
        """This epoch's training steps (``OpProp.epoch_record``): ``steps``;
        over them (the last ``RING`` where there were more) ``dt_mean`` and
        ``dt_max`` of the step sizes, ``carry_decay_mean`` and
        ``carry_decay_min`` of the share of the carried state that crosses
        a chunk."""
        first, last = int(before[0][0, 0]), int(after[0][0, 0])
        steps = (last - first) % self.STEP_WRAP
        seen = min(steps, self.RING)
        attrs = {"steps": steps, "chunk_size": self.chunk_size}
        if not seen:
            return "fit.epoch.ssm", attrs
        rows = after[0][1 + (last - 1 - np.arange(seen)) % self.RING]
        return "fit.epoch.ssm", dict(
            attrs, dt_mean=float(rows[:, 0].mean()),
            dt_max=float(rows[:, 1].max()),
            carry_decay_mean=float(rows[:, 2].mean()),
            carry_decay_min=float(rows[:, 3].min()))
