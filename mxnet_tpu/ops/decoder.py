"""Operators of a sparse decoder: what a pre-norm transformer layer needs
beyond ``FullyConnected`` / ``Activation`` / ``_Mul`` / ``_Plus``.

No reference counterpart (MXNet 0.5 predates them); they follow the OpProp
contract so that a decoder is a Symbol like any other model and trains
through ``FeedForward.fit`` (doc/developer-guide/decoder-ops.md).

  RMSNorm           x / sqrt(mean(x^2) + eps) * gamma over the last axis,
                    computed in float32.
  RotaryAttention   causal grouped-query attention on projected rows:
                    rotary positions on the first ``rotary_dim`` dimensions
                    of every head (default or YaRN frequencies), an optional
                    sliding window, an optional sigmoid gate a head. The
                    products run in ops/pallas/flash_attention.py.
  BlockDiffusionAttention
                    the attention of block-diffusion training: rows are a
                    sequence's noisy copy followed by its clean copy, both
                    at positions ``0 .. seq_len - 1``; a clean query sees
                    the clean keys of its block and the blocks before it,
                    a noisy query the clean keys of the blocks before its
                    own and the noisy keys of its own block.
  LatentAttention   causal multi-head latent attention (DeepSeek-V3): keys
                    of ``qk_nope_head_dim + qk_rope_head_dim`` a head whose
                    rotary part is ONE key a position shared by every head,
                    values of ``v_head_dim``; rotary pairs adjacent.
  MixtureOfExperts  a router over ALL ``num_experts``, top-k, normalised and
                    scaled weights, the ``experts_held`` experts from
                    ``first_expert`` on computed here as grouped products
                    over the picks sorted by expert, plus a shared expert.
                    Picks on experts held elsewhere add nothing; no pick is
                    dropped. Keeps the count of picks per expert as the
                    auxiliary state ``expert_load``; with
                    ``selection="biased"`` the picks are by the scores plus
                    a bias kept as ``selection_bias`` and moved by the load.
  MixtureOfExpertsSquaredReLU
                    the same routing over ungated experts
                    ``down(relu(up x)^2)`` (Nemotron-H).
  RematBoundary     identity; closes a recomputation segment of the
                    executor (executor._remat_segments).

Rows are positions: activations are ``(batch * seq_len, width)`` as
``FullyConnected`` makes them, and ``RotaryAttention`` is told ``seq_len``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpProp, Range, REQUIRED, register_op


@register_op("RMSNorm")
class RMSNormOp(OpProp):
    """Root-mean-square normalisation over the last axis with a learnable
    scale, in float32 whatever the input's type."""

    params = {"eps": (Range(float, lo=0.0), 1e-6, "added to the mean square")}

    def list_arguments(self):
        return ["data", "gamma"]

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        return [d, (d[-1],)], [d], []

    def fwd(self, ins, aux, is_train, rng):
        x, gamma = ins
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                            + self.eps)
        return [(x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)], []


@register_op("RematBoundary")
class RematBoundaryOp(OpProp):
    """Identity. Marks the end of a recomputation segment: the executor
    runs the nodes since the previous boundary under ``jax.checkpoint``, so
    only what crosses the boundary is kept for the backward pass."""

    closes_remat_segment = True

    def fwd(self, ins, aux, is_train, rng):
        return [ins[0]], []


# -- rotary positions ---------------------------------------------------------

def rotary_inv_freq(rotary_dim, theta, rope_type="default", factor=1.0,
                    original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0):
    """The ``rotary_dim / 2`` inverse frequencies (numpy float64).

    ``default``: ``theta ** (-2i / rotary_dim)``. ``yarn``: dimensions that
    turn more than ``beta_fast`` times within ``original_max_position``
    keep their frequency, those that turn fewer than ``beta_slow`` times
    are divided by ``factor``, a linear ramp over the dimension index in
    between (Peng et al., arXiv:2309.00071, as transformers computes it)."""
    half = rotary_dim // 2
    base = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rotary_dim)
    if rope_type == "default":
        return base
    if rope_type != "yarn":
        raise MXNetError(f"rotary positions: unknown rope_type {rope_type!r}")

    def dim_of(turns):
        return rotary_dim * math.log(original_max_position
                                     / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim_of(beta_fast)), 0)
    hi = min(math.ceil(dim_of(beta_slow)), rotary_dim - 1)
    if lo == hi:
        hi += 0.001
    keep = 1.0 - np.clip((np.arange(half, dtype=np.float64) - lo) / (hi - lo),
                         0.0, 1.0)
    return base / factor * (1.0 - keep) + base * keep


def rotate_heads(x, rope):
    """``x`` (batch, seq, heads, head_dim) rotated to its positions by the
    tables ``rotary_tables`` makes: ``x * cos + (x @ rot) * sin``, in
    float32. What the flash kernels do to a query block, done here."""
    cos, sin, rot = rope
    x32 = x.astype(jnp.float32)
    return (x32 * cos[None, :, None, :]
            + jnp.einsum("bthd,de->bthe", x32, rot)
            * sin[None, :, None, :]).astype(x.dtype)


@register_op("RotaryAttention")
class RotaryAttentionOp(OpProp):
    """Causal grouped-query attention on projected rows.

    ``query`` (rows, num_heads * head_dim), ``key`` / ``value`` (rows,
    num_kv_heads * head_dim), with ``gated`` also ``gate`` (rows,
    num_heads); rows = batch * seq_len. Query head ``i`` reads key-value
    head ``i // (num_heads / num_kv_heads)``. Output (rows, num_heads *
    head_dim): ``softmax(q k^T / sqrt(head_dim)) v`` a head, times
    ``sigmoid(gate)`` a head and position when gated."""

    params = {
        "seq_len": (Range(int, lo=1), REQUIRED, "positions a sequence"),
        "num_heads": (Range(int, lo=1), REQUIRED, "query heads"),
        "num_kv_heads": (Range(int, lo=1), REQUIRED, "key-value heads"),
        "head_dim": (Range(int, lo=2), REQUIRED, "width of a head"),
        "window": (Range(int, lo=0), 0,
                   "keys a query sees, itself included; 0 = all before it"),
        "gated": (bool, False, "multiply each head by sigmoid(gate)"),
        "rotary_dim": (Range(int, lo=0), 0,
                       "leading dimensions of a head that rotate; 0 = none"),
        "rope_theta": (float, 10000.0, "rotary base"),
        "rope_type": (("default", "yarn"), "default", "frequency schedule"),
        "rope_factor": (float, 1.0, "yarn: context extension factor"),
        "rope_original_max_position": (Range(int, lo=1), 4096,
                                       "yarn: positions before extension"),
        "rope_beta_fast": (float, 32.0, "yarn: turns above which a "
                                        "dimension keeps its frequency"),
        "rope_beta_slow": (float, 1.0, "yarn: turns below which a "
                                       "dimension is interpolated"),
        "rope_attention_factor": (float, 1.0, "multiplies cos and sin"),
    }

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if self.num_heads % self.num_kv_heads:
            raise MXNetError(f"RotaryAttention: {self.num_heads} query heads "
                             f"over {self.num_kv_heads} key-value heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise MXNetError(f"RotaryAttention: rotary_dim {self.rotary_dim} "
                             f"of a head of {self.head_dim}")

    def list_arguments(self):
        return ["query", "key", "value"] + (["gate"] if self.gated else [])

    def infer_shape(self, in_shapes):
        rows = next((s[0] for s in in_shapes if s is not None), None)
        if rows is None:
            raise MXNetError("RotaryAttention: no input shape known")
        if rows % self.seq_len:
            raise MXNetError(f"RotaryAttention: {rows} rows are not whole "
                             f"sequences of {self.seq_len}")
        q = (rows, self.num_heads * self.head_dim)
        kv = (rows, self.num_kv_heads * self.head_dim)
        shapes = [q, kv, kv] + ([(rows, self.num_heads)] if self.gated else [])
        return shapes, [q], []

    def inv_freq(self):
        return rotary_inv_freq(
            self.rotary_dim, self.rope_theta, self.rope_type,
            self.rope_factor, self.rope_original_max_position,
            self.rope_beta_fast, self.rope_beta_slow)

    def fwd(self, ins, aux, is_train, rng):
        from .pallas import flash_attention
        from .pallas.flash_attention import rotary_tables

        t, d = self.seq_len, self.head_dim
        batch = ins[0].shape[0] // t
        q = ins[0].reshape(batch, t, self.num_heads, d)
        k = ins[1].reshape(batch, t, self.num_kv_heads, d)
        v = ins[2].reshape(batch, t, self.num_kv_heads, d)
        gate = ins[3].reshape(batch, t, self.num_heads) if self.gated \
            else None
        window = self.window or None
        rope = rotary_tables(t, d, self.inv_freq(),
                             self.rope_attention_factor) \
            if self.rotary_dim else None
        if rope is not None:
            k = rotate_heads(k, rope)       # the keys are few: rotated here
        if d % 128 == 0:
            # heads of whole lane tiles: the kernels read the projections'
            # rows as they lie, rotate the queries and apply the gate on
            # the block in VMEM
            o = flash_attention(q, k, v, causal=True, window=window,
                                heads_last=True, rotary=rope, gate=gate)
            return [o.reshape(batch * t, self.num_heads * d)], []
        if rope is not None:
            q = rotate_heads(q, rope)
        o = flash_attention(q, k, v, causal=True, window=window,
                            heads_last=True)         # (batch, t, heads, d)
        if self.gated:
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
                 ).astype(o.dtype)
        return [o.reshape(batch * t, self.num_heads * d)], []


@register_op("BlockDiffusionAttention")
class BlockDiffusionAttentionOp(RotaryAttentionOp):
    """Grouped-query attention under the mask of block-diffusion training
    (BD3-LM, Arriola et al., arXiv:2503.09573).

    A sequence of ``seq_len`` positions, cut into blocks of
    ``block_length``, comes as ``2 * seq_len`` rows: its noisy copy (some
    positions replaced by the mask token), then its clean copy; noisy row
    ``i`` and clean row ``i`` carry the SAME rotary position ``i``. With
    ``b(i) = i // block_length``, in one softmax a query:

    - a clean query ``i`` sees the clean keys ``j`` with ``b(j) <= b(i)``,
    - a noisy query ``i`` sees the clean keys with ``b(j) < b(i)`` and the
      noisy keys with ``b(j) == b(i)`` (its own block, both directions),

    and no query any other key. ``query`` (rows, num_heads * head_dim),
    ``key`` / ``value`` (rows, num_kv_heads * head_dim), rows = batch * 2 *
    seq_len; output as ``RotaryAttention``'s. The products run in
    ops/pallas/flash_attention.py (``step=block_length, halves=2``): no
    score matrix is ever held, and what the mask hides is not computed."""

    params = {**{k: v for k, v in RotaryAttentionOp.params.items()
                 if k not in ("window", "gated")},
              "block_length": (Range(int, lo=1), REQUIRED,
                               "positions a block; divides seq_len")}
    window, gated = 0, False

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if self.seq_len % self.block_length:
            raise MXNetError(
                f"BlockDiffusionAttention: blocks of {self.block_length} do "
                f"not divide a sequence of {self.seq_len}")

    def infer_shape(self, in_shapes):
        rows = next((s[0] for s in in_shapes if s is not None), None)
        if rows is None or rows % (2 * self.seq_len):
            raise MXNetError(
                f"BlockDiffusionAttention: {rows} rows are not whole pairs "
                f"of a noisy and a clean copy of {self.seq_len} positions")
        q = (rows, self.num_heads * self.head_dim)
        kv = (rows, self.num_kv_heads * self.head_dim)
        return [q, kv, kv], [q], []

    def fwd(self, ins, aux, is_train, rng):
        from .pallas import flash_attention
        from .pallas.flash_attention import rotary_tables

        t, d = self.seq_len, self.head_dim
        pairs = ins[0].shape[0] // (2 * t)
        q = ins[0].reshape(pairs, 2 * t, self.num_heads, d)
        k = ins[1].reshape(pairs, 2 * t, self.num_kv_heads, d)
        v = ins[2].reshape(pairs, 2 * t, self.num_kv_heads, d)
        rope = rotary_tables(t, d, self.inv_freq(),
                             self.rope_attention_factor) \
            if self.rotary_dim else None

        def rotated(x):     # both copies at the positions of one
            return rotate_heads(x.reshape(2 * pairs, t, -1, d),
                                rope).reshape(x.shape)

        if rope is not None:
            k = rotated(k)
            if d % 128:
                q, rope = rotated(q), None
        o = flash_attention(q, k, v, causal=True, heads_last=True,
                            rotary=rope, step=self.block_length, halves=2)
        return [o.reshape(2 * pairs * t, self.num_heads * d)], []


@register_op("LatentAttention")
class LatentAttentionOp(OpProp):
    """Causal multi-head latent attention (DeepSeek-V3, arXiv:2412.19437,
    section 2.1.1) on projected rows, rows = batch * seq_len.

    ``query`` (rows, num_heads * (qk_nope_head_dim + qk_rope_head_dim)), a
    head ``[q_nope | q_pe]`` as the query projection leaves it;
    ``key_value`` (rows, num_heads * (qk_nope_head_dim + v_head_dim)), a
    head ``[k_nope | v]`` as the up-projection of the latent leaves it;
    ``key_rope`` (rows, qk_rope_head_dim), ONE rotary key a position that
    every head reads. ``q_pe`` and ``key_rope`` rotate to their positions,
    dimensions ``2i`` and ``2i + 1`` paired at frequency ``i``. Head ``h``:
    ``softmax((q_nope . k_nope + q_pe . k_pe) / sqrt(qk_nope_head_dim +
    qk_rope_head_dim)) v`` over the keys up to its position. Output (rows,
    num_heads * v_head_dim).

    The products run in ops/pallas/flash_attention.py with keys of
    ``qk_nope + qk_rope`` and values of ``v_head_dim``: nothing is padded
    to the wider. The kernels read the three inputs as they are
    (``flash_attention(key_rope=...)``): a pair of query heads is whole
    lane tiles (two of 192 are 384 columns), ``q_pe`` turns on the query
    block in VMEM, the one rotary key (rotated here: it is 64 columns) is
    read by every head, the output and the gradients of ``query`` and
    ``key_value`` come in those rows' layout, and ``key_rope``'s is summed
    over the heads inside the kernel. Widths whose pair is no whole lane
    tiles (``latent_fits``) take the heads first: the rotation of the
    queries, the shared key's copy to every head and that change of layout
    are then one pass of XLA's over the rows."""

    params = {
        "seq_len": (Range(int, lo=1), REQUIRED, "positions a sequence"),
        "num_heads": (Range(int, lo=1), REQUIRED, "attention heads"),
        "qk_nope_head_dim": (Range(int, lo=1), REQUIRED,
                             "width of a head's query and key without "
                             "positions"),
        "qk_rope_head_dim": (Range(int, lo=2), REQUIRED,
                             "width of the rotary part of a query and key"),
        "v_head_dim": (Range(int, lo=1), REQUIRED, "width of a head's value"),
        "rope_theta": (float, 10000.0, "rotary base"),
    }

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if self.qk_rope_head_dim % 2:
            raise MXNetError(f"LatentAttention: a rotary part of "
                             f"{self.qk_rope_head_dim} is no whole pairs")

    def list_arguments(self):
        return ["query", "key_value", "key_rope"]

    def infer_shape(self, in_shapes):
        rows = next((s[0] for s in in_shapes if s is not None), None)
        if rows is None:
            raise MXNetError("LatentAttention: no input shape known")
        if rows % self.seq_len:
            raise MXNetError(f"LatentAttention: {rows} rows are not whole "
                             f"sequences of {self.seq_len}")
        h, n, r, v = self.num_heads, self.qk_nope_head_dim, \
            self.qk_rope_head_dim, self.v_head_dim
        return [(rows, h * (n + r)), (rows, h * (n + v)), (rows, r)], \
            [(rows, h * v)], []

    def fwd(self, ins, aux, is_train, rng):
        from .pallas import flash_attention
        from .pallas.flash_attention import latent_fits, rotary_tables

        t, h, n, r = self.seq_len, self.num_heads, self.qk_nope_head_dim, \
            self.qk_rope_head_dim
        batch = ins[0].shape[0] // t
        inv_freq = rotary_inv_freq(r, self.rope_theta)
        kv = ins[1].reshape(batch, t, h, n + self.v_head_dim)
        turn = rotary_tables(t, r, inv_freq, interleave=True)
        k_pe = rotate_heads(ins[2].reshape(batch, t, 1, r), turn)
        if latent_fits(h, n, r, self.v_head_dim):
            o = flash_attention(ins[0].reshape(batch, t, h, n + r), kv, None,
                                causal=True, heads_last=True, rotary=turn,
                                key_rope=k_pe.reshape(batch, t, r))
            return [o.reshape(batch * t, h * self.v_head_dim)], []
        q = rotate_heads(ins[0].reshape(batch, t, h, n + r), rotary_tables(
            t, n + r, inv_freq, interleave=True, offset=n))
        k = jnp.concatenate(
            [kv[..., :n], jnp.broadcast_to(k_pe, (batch, t, h, r))], axis=-1)
        o = flash_attention(q, k, kv[..., n:], causal=True, heads_last=True)
        return [o.reshape(batch * t, h * self.v_head_dim)], []


# -- experts ------------------------------------------------------------------
# The picks live in an expanded space of rows * top_k entries, sorted by
# expert, of which the first ``n`` hold a pick on an expert kept here. Going
# there and back is a permutation (each sorted entry is read exactly once on
# the way back), so each direction's gradient is the other direction: the
# two kernels of ops/pallas/moe.py, whose work follows ``n``. What lies
# behind ``n`` in the space is never written and never read.

def _kernels():
    from .pallas import moe     # the package imports this module's own

    return moe


@jax.custom_vjp
def _rows_to_sorted(x, order, slots, n):
    """``x[order // top_k]`` for the first ``n`` sorted entries: (rows, w)
    -> (rows * top_k, w). ``order`` (rows * top_k,) is the pick each sorted
    entry holds, ``slots`` (rows, top_k) its inverse: where each of a row's
    picks landed."""
    return _rows_to_sorted_fwd(x, order, slots, n)[0]


def _rows_to_sorted_fwd(x, order, slots, n):
    token = order // slots.shape[1]
    return _kernels().moe_dispatch(x, token, n), (token, slots, n)


def _rows_to_sorted_bwd(res, g):
    token, slots, n = res
    return _kernels().moe_combine(g, token, n, slots.shape[0]), \
        None, None, None


_rows_to_sorted.defvjp(_rows_to_sorted_fwd, _rows_to_sorted_bwd)


@jax.custom_vjp
def _sorted_to_rows(y, weight, order, slots, n):
    """``sum_j weight[r, j] * y[slots[r, j]]`` over the picks with
    ``slots[r, j] < n``: (rows * top_k, w) -> (rows, w), accumulated in
    float32."""
    return _sorted_to_rows_fwd(y, weight, order, slots, n)[0]


def _sorted_to_rows_fwd(y, weight, order, slots, n):
    rows, k = slots.shape
    token, by_entry = order // k, weight.reshape(-1)[order]
    return _kernels().moe_combine(y, token, n, rows, by_entry), \
        (y, token, by_entry, slots, n)


def _sorted_to_rows_bwd(res, g):
    y, token, by_entry, slots, n = res
    dispatch = _kernels().moe_dispatch
    dy = dispatch(g, token, n, by_entry)
    # the routing weights' gradient: a pass over the whole space, made only
    # where the weights are trained (unused, it is no part of the program)
    dot = jnp.sum(dispatch(g, token, n).astype(jnp.float32)
                  * y.astype(jnp.float32), axis=1)
    dweight = jnp.where(slots < n, dot[slots], 0.0).astype(by_entry.dtype)
    return dy, dweight, None, None, None


_sorted_to_rows.defvjp(_sorted_to_rows_fwd, _sorted_to_rows_bwd)


def _count(ids, length):
    """How often each of ``0 .. length - 1`` occurs in ``ids`` (a compare
    and a sum: a scatter-add of as many entries is slow on the chip)."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(length, dtype=ids.dtype),
                   axis=0, dtype=jnp.int32)


def _product(a, w):
    """``a w^T`` with an (out, in) weight, accumulated in float32."""
    return jax.lax.dot_general(
        a, w.astype(a.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(a.dtype)


def _gated_ffn(x, gate_w, up_w, down_w):
    """``down(silu(gate x) * (up x))`` with (out, in) weights."""
    return _product(jax.nn.silu(_product(x, gate_w)) * _product(x, up_w),
                    down_w)


def _relu2_ffn(x, up_w, down_w):
    """``down(relu(up x)^2)`` with (out, in) weights."""
    return _product(jnp.square(jax.nn.relu(_product(x, up_w))), down_w)


@register_op("MixtureOfExperts")
class MixtureOfExpertsOp(OpProp):
    """Sparse gated feed-forward layer, one rank's share of its experts.

    ``data`` (rows, hidden). ``router_weight`` (num_experts, hidden): scores
    ``sigmoid(x W_r^T)`` (or, ``score="softmax"``, the softmax of the
    logits over ALL experts, in float32) over ALL experts, the ``top_k``
    largest picked, weights ``scaling * s_e / sum of the picked s``. ``gate_weight`` /
    ``up_weight`` (experts_held, width, hidden) and ``down_weight``
    (experts_held, hidden, width) are experts ``first_expert`` ..
    ``first_expert + experts_held - 1``: the picks that fall on them are
    sorted by expert and run as grouped products (``jax.lax.ragged_dot``);
    a pick on an expert held elsewhere adds nothing here, and no pick is
    dropped whatever the load. With ``shared_width`` a shared expert
    (``shared_{gate,up,down}_weight``) sees every row. Output (rows,
    hidden): this rank's part of the routed sum, plus the shared expert.
    Rows go to the sorted space and come back through the two kernels of
    ops/pallas/moe.py, whose work is the picks held here in this step; the
    space behind them is unwritten memory that nothing reads.

    ``train_router=False`` cuts the routing weights out of the gradient
    (the router's weight then gets a zero gradient). It is for a rank that
    holds a share and trains ALONE: the loss reaches the router through
    every expert's output, the absent experts' terms are on other ranks,
    and this rank's part applied by itself is no gradient of anything: it
    teaches the router to route away from the experts held here (measured
    on a v5e, 32 of 256 held: at Adam 1e-3 0.015 picks a token on held
    experts after 80 steps where uniform routing gives 1.0; at 1e-5 two
    of four layers fall by 0.06 an epoch of 64 steps, PERF.md section 6).

    ``selection="biased"`` (DeepSeek-V3's auxiliary-loss-free balancing,
    arXiv:2412.19437, section 2.1.2) picks the ``top_k`` largest of
    ``s + b`` and weighs the picks by ``s`` alone, as above: the bias ``b``
    selects and does not weigh. ``b`` is the auxiliary state
    ``selection_bias`` (num_experts,), float32, no gradient's: after every
    training step ``b_e += BIAS_RATE * sign(mean load - load_e)`` over the
    step's picks on ALL experts, which the router of every rank sees.

    Auxiliary state ``expert_load`` (num_experts,): picks per expert,
    all experts, accumulated over the training steps modulo ``LOAD_WRAP``
    (whole numbers a float32 holds exactly, so an epoch's counts come out
    exact by differencing however long the training ran);
    ``epoch_record`` makes ``fit``'s ``fit.epoch.expert_load`` line of
    them."""

    LOAD_WRAP = 1 << 23
    # the selection bias's step after each training step (the rate the
    # DeepSeek-V3 report trains with; no published config has a key for it)
    BIAS_RATE = 0.001
    # an expert's matrices, the last its down projection: (experts_held,
    # width, hidden) each, the last (experts_held, hidden, width)
    MATRICES = ("gate_weight", "up_weight", "down_weight")

    params = {
        "num_experts": (Range(int, lo=1), REQUIRED, "width of the router"),
        "experts_held": (Range(int, lo=1), REQUIRED, "experts computed here"),
        "first_expert": (Range(int, lo=0), 0, "index of the first one held"),
        "top_k": (Range(int, lo=1), REQUIRED, "experts a row picks"),
        "expert_width": (Range(int, lo=1), REQUIRED, "width of an expert"),
        "scaling": (float, 1.0, "multiplies the normalised weights"),
        "score": (("sigmoid", "softmax"), "sigmoid",
                  "the router's score function, over all experts"),
        "selection": (("plain", "biased"), "plain",
                      "pick by the scores, or by the scores plus the "
                      "load-driven selection_bias"),
        "shared_width": (Range(int, lo=0), 0,
                         "width of the shared expert; 0 = none"),
        "train_router": (bool, True,
                         "let the loss reach the router through the "
                         "routing weights"),
    }

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        if self.first_expert + self.experts_held > self.num_experts \
                or self.top_k > self.num_experts:
            raise MXNetError(
                f"MixtureOfExperts: experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held - 1} and top "
                f"{self.top_k} of {self.num_experts}")

    def list_arguments(self):
        names = ["data", "router_weight", *self.MATRICES]
        if self.shared_width:
            names += [f"shared_{m}" for m in self.MATRICES]
        return names

    def list_auxiliary_states(self):
        return ["expert_load"] + (
            ["selection_bias"] if self.selection == "biased" else [])

    def argument_major_to_minor(self):
        # the grouped product and its weight gradient read ``gate_weight``
        # and ``up_weight`` with their last two axes swapped (``routed``).
        # Kept as declared, every stacked weight and both its Adam moments
        # were copied into that order at the top of each step and back at
        # its end (a v5e, laguna_xs2.seq8k: 72 float32 copies of 134 MB a
        # step, ``down_weight``'s among them, which follow the other two
        # and go with them; stored so too, ``down_weight`` costs the
        # block-diffusion cell 150 MB more of temporaries than it saves)
        return {m: (0, 2, 1) for m in self.MATRICES[:-1]}

    def infer_shape(self, in_shapes):
        d = self._known(in_shapes, 0)
        hidden, e, w, s = d[1], self.experts_held, self.expert_width, \
            self.shared_width
        ins = len(self.MATRICES) - 1
        shapes = [d, (self.num_experts, hidden), *[(e, w, hidden)] * ins,
                  (e, hidden, w)]
        if s:
            shapes += [*[(s, hidden)] * ins, (hidden, s)]
        return shapes, [d], [(self.num_experts,)] * len(
            self.list_auxiliary_states())

    def route(self, x, router_w, bias=None):
        """``(experts, weights)``, both (rows, top_k): the picks and their
        normalised, scaled weights (float32); with a ``bias`` the picks
        are by the scores plus it, the weights the scores'."""
        logits = jax.lax.dot_general(
            x, router_w.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits) if self.score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        if bias is None:
            picked, experts = jax.lax.top_k(scores, self.top_k)
        else:
            _, experts = jax.lax.top_k(scores + bias, self.top_k)
            picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = self.scaling * picked / jnp.sum(picked, axis=-1,
                                                  keepdims=True)
        if not self.train_router:
            weights = jax.lax.stop_gradient(weights)
        return experts, weights

    def routed(self, x, experts, weights, *matrices):
        """This rank's part of the routed sum.

        The picks live in a sorted space of rows x top_k entries, those on
        experts held here first, by expert. No pick is dropped, so the
        space is all of it whatever the load, and the work is the load's:
        the two kernels that take rows there and back move the ``n``
        entries that hold a pick of this step (``n = sum(sizes)``, read on
        the device: no control flow in the program), the grouped products
        do the rows of their groups. Behind ``n`` the space is unwritten
        memory that nothing reads; the elementwise product between the
        grouped products runs over it and its result there is as
        meaningless."""
        rows, k, held = x.shape[0], self.top_k, self.experts_held
        self._step_space = rows * k      # for the epoch's record
        local = experts - self.first_expert
        here = (local >= 0) & (local < held)
        # picks on experts held elsewhere sort behind every group
        key = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)         # sorted entry -> pick
        slots = jnp.zeros((rows * k,), jnp.int32).at[order].set(
            jnp.arange(rows * k, dtype=jnp.int32),
            unique_indices=True).reshape(rows, k)     # pick -> sorted entry
        sizes = _count(key, held)
        n = jnp.sum(sizes)
        xs = _rows_to_sorted(x, order, slots, n)

        def grouped(a, w):
            with jax.named_scope("grouped"):
                # the product leaves the kernel as the activations' type
                # (accumulated in float32 inside it: bit for bit what a
                # float32 result rounded afterwards is, PERF.md section 6,
                # PR 30), so no float32 copy of the space is written and
                # converted, forward or backward
                return jax.lax.ragged_dot(
                    a, jnp.swapaxes(w, 1, 2).astype(a.dtype), sizes,
                    preferred_element_type=a.dtype)

        h = self.hidden(lambda w: grouped(xs, w), *matrices[:-1])
        return _sorted_to_rows(grouped(h, matrices[-1]),
                               jnp.where(here, weights, 0.0), order, slots,
                               n)

    @staticmethod
    def hidden(up, gate_w, up_w):
        """An expert's activation between its products; ``up(w)`` is the
        product of the rows with ``w``."""
        return jax.nn.silu(up(gate_w)) * up(up_w)

    @staticmethod
    def shared(x, *matrices):
        return _gated_ffn(x, *matrices)

    def fwd(self, ins, aux, is_train, rng):
        x, router_w = ins[:2]
        k = len(self.MATRICES)
        load, *bias = aux
        experts, weights = self.route(x, router_w, *bias)
        y = self.routed(x, experts, weights, *ins[2:2 + k])
        if self.shared_width:
            y = y + self.shared(x, *ins[2 + k:2 + 2 * k])
        if is_train:
            counts = _count(jax.lax.stop_gradient(experts),
                            self.num_experts).astype(load.dtype)
            load = (load + counts) % self.LOAD_WRAP
            # an expert under the mean load gains, one over it loses
            bias = [b + self.BIAS_RATE * jnp.sign(
                experts.size / self.num_experts - counts) for b in bias]
        return [y], [load, *bias]

    def epoch_record(self, before, after):
        """This epoch's picks (``OpProp.epoch_record``): over all experts
        ``picks_all`` and ``tokens``, over those held here ``picks_held``,
        the busiest one's ``max_held`` and how many had a pick at all,
        ``experts_hit``; ``space``, the entries of the sorted space over
        the epoch's steps (rows x top_k a step), of which the kernels move
        ``picks_held``, by tiles of ``tile`` sorted entries (the step this
        operator was last traced for); with the selection bias its least
        and largest entry at the epoch's end, ``bias_min`` / ``bias_max``."""
        counts = (after[0].astype(np.int64) - before[0].astype(np.int64)) \
            % self.LOAD_WRAP
        held = counts[self.first_expert:self.first_expert + self.experts_held]
        # the bias as the epoch left it: read on the host, nothing in the step
        bias = {"bias_min": float(after[1].min()),
                "bias_max": float(after[1].max())} if len(after) > 1 else {}
        return "fit.epoch.expert_load", {
            **bias,
            "space": float(counts.sum()),
            "tile": _kernels().space_tile(
                getattr(self, "_step_space", 1 << 30)),
            "tokens": float(counts.sum()) / self.top_k,
            "picks_held": float(held.sum()),
            "picks_all": float(counts.sum()),
            "max_held": float(held.max()),
            "experts_hit": int(np.count_nonzero(held)),
            "experts_held": self.experts_held}


@register_op("MixtureOfExpertsSquaredReLU")
class MixtureOfExpertsSquaredReLUOp(MixtureOfExpertsOp):
    """``MixtureOfExperts`` with ungated experts (Nemotron-H's
    ``relu2``): each expert, and the shared one, is ``down(relu(up x)^2)``.
    ``up_weight`` (experts_held, width, hidden), ``down_weight``
    (experts_held, hidden, width), with ``shared_width``
    ``shared_up_weight`` / ``shared_down_weight``; routing, selection, the
    sorted space, its kernels and the ``expert_load`` record are the
    gated layer's."""

    MATRICES = ("up_weight", "down_weight")

    @staticmethod
    def hidden(up, up_w):
        return jnp.square(jax.nn.relu(up(up_w)))

    @staticmethod
    def shared(x, *matrices):
        return _relu2_ffn(x, *matrices)
