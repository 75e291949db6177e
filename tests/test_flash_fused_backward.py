"""The flash attention backward in one kernel: ``flash_bwd_dkv`` makes dq
too where what it holds across a grid row fits its VMEM budget, and the
two kernels do where it does not.

Interpret mode on the CPU (the same kernel code that compiles for the
chip) against dense float32 attention, at the tolerances of the other
flash tests: every gradient of every mask kind the decoders use, the
``flash.band`` record that says which path a call took, and the budget's
arithmetic at the decoder cells' shapes.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.pallas import flash_attention, flash_block_grads
from mxnet_tpu.ops.pallas.flash_attention import rotary_tables

fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _dense(q, k, v, seen, scale=None):
    """Softmax attention of heads-first ``q`` over ``k`` / ``v`` (fewer
    heads, each shared by consecutive query heads) under the bool mask
    ``seen`` (queries x keys)."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = scale or 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def _band(t, window=None, causal=True):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = i >= j if causal else np.ones((t, t), bool)
    return seen & (i - j < window) if window else seen


def _staircase(t, step):
    """Two halves (a noisy copy, then the clean one) under the staircase
    of ``step`` positions: a clean query sees the clean keys of its block
    and before, a noisy one the clean keys before its block and the noisy
    keys of its own."""
    b = np.arange(t) // step
    return np.block([[b[None, :] == b[:, None], b[None, :] < b[:, None]],
                     [np.zeros((t, t), bool), b[None, :] <= b[:, None]]])


def rows(x):      # heads last, as a projection leaves its rows
    return x.transpose(0, 2, 1, 3)


def _plain(rng, seq, heads, kv, d, causal, window=None, step=1, halves=1):
    q, k, v, w = (jnp.asarray(rng.randn(1, n, halves * seq, d), jnp.float32)
                  for n in (heads, kv, kv, heads))
    seen = _staircase(seq, step) if halves == 2 else _band(seq, window,
                                                           causal)

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               step=step, halves=halves, block_q=128,
                               block_k=128)

    return ours, lambda q, k, v: _dense(q, k, v, seen), (q, k, v), w


def _rotated_gated(rng, seq, heads, kv, window):
    """Heads of 128 as the projections leave them, the query rotated and
    the output gated inside the kernels (``laguna_xs2``'s layer kinds)."""
    d = 128
    q, k, v, w = (jnp.asarray(rng.randn(1, n, seq, d), jnp.float32)
                  for n in (heads, kv, kv, heads))
    g = jnp.asarray(rng.randn(1, seq, heads), jnp.float32)
    rope = rotary_tables(seq, d, 1.0 / 10000.0 ** (np.arange(32) / 32.0))

    def ours(q, k, v, g):
        return rows(flash_attention(
            rows(q), rows(k), rows(v), causal=True, window=window,
            block_q=128, block_k=128, heads_last=True, rotary=rope, gate=g))

    def dense(q, k, v, g):
        cos, sin, rot = rope
        o = _dense(q * cos + (q @ rot) * sin, k, v, _band(seq, window))
        return o * rows(jax.nn.sigmoid(g)[..., None])

    return ours, dense, (q, k, v, g), w


def _staircase_rotated(rng, seq, heads, kv, step):
    """Block-diffusion training's mask over a noisy and a clean copy of
    ``seq`` positions, heads of 128 as the projections leave them, the
    query of both copies rotated to its position inside the kernels
    (``sdar_30b_a3b``'s layer)."""
    d = 128
    q, k, v, w = (jnp.asarray(rng.randn(1, n, 2 * seq, d), jnp.float32)
                  for n in (heads, kv, kv, heads))
    rope = rotary_tables(seq, d, 1.0 / 10000.0 ** (np.arange(64) / 64.0))

    def ours(q, k, v):
        return rows(flash_attention(
            rows(q), rows(k), rows(v), causal=True, step=step, halves=2,
            block_q=128, block_k=128, heads_last=True, rotary=rope))

    def dense(q, k, v):
        cos, sin, rot = (jnp.concatenate([t, t]) if t.shape[0] == seq else t
                         for t in rope)
        return _dense(q * cos + (q @ rot) * sin, k, v,
                      _staircase(seq, step))

    return ours, dense, (q, k, v), w


def _latent(rng, seq, heads):
    """Latent attention at its published head widths: ``[q_nope 128 | q_pe
    64]``, ``[k_nope 128 | v 128]``, one rotary key of 64 for every head."""
    from mxnet_tpu.ops.decoder import rotary_inv_freq, rotate_heads

    b, n, r, dv = 1, 128, 64, 128
    q = jnp.asarray(rng.randn(b, seq, heads, n + r), jnp.float32)
    kv = jnp.asarray(rng.randn(b, seq, heads, n + dv), jnp.float32)
    k_pe = jnp.asarray(rng.randn(b, seq, r), jnp.float32)
    w = jnp.asarray(rng.randn(b, seq, heads, dv), jnp.float32)
    turn = rotary_tables(seq, r, rotary_inv_freq(r, 1e6), interleave=True)

    def ours(q, kv, k_pe):
        return flash_attention(q, kv, None, causal=True, heads_last=True,
                               rotary=turn, key_rope=k_pe, block_q=128,
                               block_k=128)

    def dense(q, kv, k_pe):
        qh = jnp.concatenate([q[..., :n], rotate_heads(q[..., n:], turn)],
                             -1)
        k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
            k_pe[:, :, None], (b, seq, heads, r))], -1)
        return rows(_dense(rows(qh), rows(k), rows(kv[..., n:]),
                           _band(seq)))

    return ours, dense, (q, kv, k_pe), w


def _ring_block(rng, seq, heads, d):
    """Ring attention's per-block call: the gradient of one key block given
    the whole sequence's output and row statistics, no mask."""
    q, k, v, do = (jnp.asarray(rng.randn(1, heads, seq, d), jnp.float32)
                   for _ in range(4))
    o, lse = fa.flash_attention_with_lse(q, k, v, block_q=128, block_k=128)

    def ours(q, k, v):
        return flash_block_grads(q, k, v, o, lse, do, block_q=128,
                                 block_k=128)

    def dense(q, k, v):
        return jax.vjp(lambda *a: _dense(*a, _band(seq, causal=False)),
                       q, k, v)[1](do)

    return ours, dense, (q, k, v), None


# (builder, its arguments, tolerance of the output and the gradients)
CASES = {
    "causal": (_plain, (256, 2, 2, 32, True), 1e-4),
    "window_grouped_rotated_gated": (_rotated_gated, (384, 4, 2, 200), 1e-4),
    "full_grouped_rotated_gated": (_rotated_gated, (256, 6, 2, None), 1e-4),
    "staircase_two_halves": (_plain, (256, 2, 1, 32, True, None, 4, 2),
                             1e-4),
    "staircase_two_halves_rotated": (_staircase_rotated, (256, 4, 1, 4),
                                     1e-4),
    "latent_pairs": (_latent, (256, 4), 1e-5),
    "padded_last_key_block": (_plain, (200, 4, 2, 32, True, 64), 1e-4),
    "latent_padded": (_latent, (200, 2), 1e-5),
    "not_causal_block_grads": (_ring_block, (256, 2, 32), 1e-4),
}


def _records(since):
    return [r["attrs"] for r in telemetry.span_records(since)
            if r["name"] == "flash.band"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_kernel_makes_every_gradient(case):
    """Every gradient (the shared key's and the gate's where there are
    such) against dense attention; the call's one backward kernel says it
    made dq, and no ``flash_bwd_dq`` ran."""
    build, args, tol = CASES[case]
    ours, dense, x, w = build(np.random.RandomState(11), *args)
    wrt = tuple(range(len(x)))
    since = time.perf_counter()
    if w is None:      # a backward call of its own
        got, want = ours(*x), dense(*x)
    else:
        assert rel(ours(*x), dense(*x)) < tol
        got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), wrt)(*x)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), wrt)(*x)
    for a, b in zip(got, want):
        assert a.shape == b.shape and rel(a, b) < tol
    records = _records(since)
    assert {r["kernel"] for r in records} == (
        {"flash_bwd_dkv"} if w is None else {"flash_bwd_dkv", "flash_fwd"})
    dkv, = (r for r in records if r["kernel"] == "flash_bwd_dkv")
    assert dkv["dq"] == 1 and 0 < dkv["dq_vmem_bytes"] <= fa._FUSED_VMEM


@pytest.mark.parametrize("case", ["latent_pairs",
                                  "window_grouped_rotated_gated"])
def test_over_the_budget_the_two_kernels_run(case, monkeypatch):
    """A call whose accumulators exceed the budget keeps ``flash_bwd_dq``:
    the same gradients, and the ``flash_bwd_dkv`` record says it did not
    make dq and what the one kernel would have held."""
    build, args, tol = CASES[case]
    ours, dense, x, w = build(np.random.RandomState(12), *args)
    since = time.perf_counter()
    jax.grad(lambda *a: jnp.sum(ours(*a) * w))(*x)
    held = next(r for r in _records(since)
                if r["kernel"] == "flash_bwd_dkv")["dq_vmem_bytes"]
    monkeypatch.setattr(fa, "_FUSED_VMEM", held - 1)
    wrt = tuple(range(len(x)))
    since = time.perf_counter()
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), wrt)(*x)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), wrt)(*x)
    for a, b in zip(got, want):
        assert rel(a, b) < tol
    records = _records(since)
    assert sorted(r["kernel"] for r in records) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    dkv, = (r for r in records if r["kernel"] == "flash_bwd_dkv")
    assert dkv["dq"] == 0 and dkv["dq_vmem_bytes"] == held


def test_query_blocks_of_part_of_a_lane_tile_keep_two_kernels():
    """dq's accumulator lies with the queries along the lanes: a query
    block that is not whole lane tiles keeps ``flash_bwd_dq``."""
    ours, dense, x, w = _plain(np.random.RandomState(13), 64, 2, 1, 16, True)
    since = time.perf_counter()
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, x[1], x[2], causal=True, block_q=32, block_k=32) * w))(x[0])
    records = _records(since)
    assert sorted(r["kernel"] for r in records) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert all(r["dq"] == 0 for r in records
               if r["kernel"] == "flash_bwd_dkv")


def test_the_budget_at_the_decoder_cells_shapes():
    """What the one kernel holds at the cells' shapes, from the shapes
    alone: every attention layer of the three decoders fits at 8,192
    positions (rows), a latent pair at 32,768 does not."""
    f32 = 4

    def tables(seq, width):
        return (np.zeros((seq, width), np.float32),) * 2

    # latent: a pair's dq (2 x 192 wide), the shared key's 64 over all pairs
    latent = fa._fused_vmem(8192, 8192, 384, 256, 64, 16, 2, tables(8192, 64))
    assert latent == 8192 * 384 * (f32 + 2) + 8192 * 64 * (2 * f32 + 2) \
        + 2 * 8192 * 64 * f32
    # laguna_xs2: 48 heads of 128 over 8, rotation on the whole head
    laguna = fa._fused_vmem(8192, 8192, 128, 128, 0, 6, 2, tables(8192, 128))
    # sdar_30b_a3b: two halves of 4,096, 32 heads over 4, tables of one half
    sdar = fa._fused_vmem(8192, 8192, 128, 128, 0, 8, 2, tables(4096, 128))
    assert max(latent, laguna, sdar) <= fa._FUSED_VMEM
    assert fa._fused_vmem(32768, 32768, 384, 256, 64, 16, 2,
                          tables(32768, 64)) > fa._FUSED_VMEM


def test_the_registry_prices_what_the_one_kernel_computes():
    """A latent call's fused ``flash_bwd_dkv`` is priced at the five
    products it computes, the scores once: ``2 (3 x 192 + 2 x 128)`` a
    pair of positions and head, where the two kernels are ``2 (2 x 192 +
    128)`` and ``4 (192 + 128)``."""
    from mxnet_tpu.ops.pallas.registry import attribute_eqn

    ours, _, x, w = _latent(np.random.RandomState(14), 256, 2)
    flops = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            hit = attribute_eqn(eqn)
            if hit:
                flops[hit[0]] = hit[1].flops
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else [p]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns") and not hit:
                        walk(sub)

    walk(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ours(*a) * w),
                                 (0, 1, 2)))(*x).jaxpr)
    unit = 2.0 * 2 * 256 * 256
    assert flops == {"flash_fwd": unit * (192 + 128),
                     "flash_bwd_dkv": unit * (3 * 192 + 2 * 128)}
