"""Block-diffusion training of a sparse decoder through the normal path:
``BlockDiffusionAttention`` and the flash kernels' staircase against a
dense boolean mask, ``MixtureOfExperts(score="softmax")`` and the loss head
``MaskedDiffusionOutput`` against the plain reference
(``benchmark/configs/sdar_30b_a3b.py``, which imports nothing from the
program), the tiny ``sdar`` through ``FeedForward.fit``, the share of an
expert-parallel deployment and the ``fit.epoch.diffusion_mask`` record.

Tiny sizes, seeded weights, float32 unless a test says bfloat16.
"""

import importlib.util
import inspect
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import OPS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference",
        os.path.join(ROOT, "benchmark", "configs", "sdar_30b_a3b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
T, B, V = 32, 4, 96
MASK = V - 1
# 3 layers, 16 experts top-4, 4 query heads over 2
TINY = dict(
    num_hidden_layers=3, vocab_size=V, num_experts=16, first_expert=0,
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, num_experts_per_tok=4, moe_intermediate_size=32,
    rms_norm_eps=1e-6, rope_theta=1000000, block_length=B, mask_id=MASK)


def tiny_model(experts_held=16, first_expert=0, **over):
    sizes = {k: v for k, v in dict(TINY, **over).items()
             if k not in ("num_hidden_layers", "vocab_size", "num_experts",
                          "first_expert", "mask_id")}
    return mx.models.sdar(seq_len=T, layers=3, vocab_rows=V,
                          experts_held=experts_held,
                          first_expert=first_expert, num_experts=16, **sizes)


def seeded_params(symbol, batch, seed=3, head_scale=1.5):
    """Drawn weights; the per-head norm scales on queries and keys at
    ``head_scale``, not the one they start at, so that they are held."""
    mx.random.seed(seed)
    model = mx.FeedForward(symbol, ctx=mx.cpu(),
                           initializer=mx.init.Xavier())
    model._init_params({"data": (batch, 2 * T),
                        "softmax_label": (batch, T)})
    for name, arr in model.arg_params.items():
        if head_scale and name.endswith(("q_norm_gamma", "k_norm_gamma")):
            arr[:] = head_scale
    return model


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def noisy_pairs(rng, rows, t=T, block=B, mask=MASK, counts=None):
    """``(data, labels)``: clean ids below the mask id, and in every block
    of the noisy copy ``counts`` (1 .. block, drawn) positions masked."""
    x0 = rng.randint(0, mask, (rows, t)).astype(np.int32)
    k = rng.randint(1, block + 1, (rows, t // block, 1)) if counts is None \
        else np.asarray(counts).reshape(rows, t // block, 1)
    rank = np.argsort(np.argsort(rng.rand(rows, t // block, block), -1), -1)
    xt = np.where((rank < k).reshape(rows, t), mask, x0).astype(np.int32)
    return np.concatenate([xt, x0], axis=1), x0


def seen(t, block, halves=2):
    """The mask itself, (halves x t, halves x t) bool: rows and columns
    the noisy copy, then the clean one."""
    b = np.arange(t) // block
    clean = b[None, :] <= b[:, None]
    if halves == 1:
        return clean
    return np.block([[b[None, :] == b[:, None], b[None, :] < b[:, None]],
                     [np.zeros_like(clean), clean]])


# -- the attention operator and the kernels' staircase --------------------------

def test_the_reference_builds_the_mask_the_issue_draws():
    """T = 8, B = 4, as doc/developer-guide/decoder-ops.md draws it."""
    drawn = """
        NNNN.... ........
        NNNN.... ........
        NNNN.... ........
        NNNN.... ........
        ....NNNN CCCC....
        ....NNNN CCCC....
        ....NNNN CCCC....
        ....NNNN CCCC....
        ........ CCCC....
        ........ CCCC....
        ........ CCCC....
        ........ CCCC....
        ........ CCCCCCCC
        ........ CCCCCCCC
        ........ CCCCCCCC
        ........ CCCCCCCC"""
    want = np.array([[c != "." for c in line.replace(" ", "")]
                     for line in drawn.split("\n") if line.strip()])
    assert np.array_equal(np.asarray(REF.seen(jnp.arange(16), 8, 4)), want)
    assert np.array_equal(seen(8, 4), want)
    # the mean keys a query attends, as the walker prices it
    assert want.sum() / 16 == (8 + 4) / 2


@pytest.mark.parametrize("block,d", [(1, 16), (4, 16), (4, 128), (8, 16)])
def test_attention_operator_matches_a_dense_boolean_mask(block, d):
    """Grouped heads (4 over 2), rotary positions shared by the two
    copies: output and every input's gradient against dense attention
    under the boolean mask. At a head of 128 the kernels rotate the
    queries themselves; at 16 the operator does, around them."""
    rng = np.random.RandomState(1)
    batch, heads, kv = 2, 4, 2
    q, k, v, w = (jnp.asarray(rng.randn(batch * 2 * T, n), jnp.float32)
                  for n in (heads * d, kv * d, kv * d, heads * d))
    op = OPS.create("BlockDiffusionAttention", seq_len=T, block_length=block,
                    num_heads=heads, num_kv_heads=kv, head_dim=d,
                    rotary_dim=d, rope_theta=1e6)
    assert op.list_arguments() == ["query", "key", "value"]
    assert op.infer_shape([(batch * 2 * T, heads * d), None, None]) == (
        [(batch * 2 * T, heads * d)] + [(batch * 2 * T, kv * d)] * 2,
        [(batch * 2 * T, heads * d)], [])
    with pytest.raises(mx.MXNetError, match="whole pairs"):
        op.infer_shape([(3 * T, heads * d), None, None])
    allowed = jnp.asarray(seen(T, block))
    positions = jnp.arange(2 * T) % T

    def ours(q, k, v):
        return op.fwd([q, k, v], [], True, None)[0][0]

    def dense(q, k, v):
        q, k, v = (x.reshape(batch, 2 * T, -1, d) for x in (q, k, v))
        q, k = (REF.rotate(x, positions, 1e6) for x in (q, k))
        k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(
            batch * 2 * T, heads * d)

    assert rel(ours(q, k, v), dense(q, k, v)) < 1e-5
    # the reference's own attention is that dense form, blocked
    assert rel(REF.attention(*(x.reshape(batch, 2 * T, -1, d)
                               for x in (q, k, v)), T, block),
               dense_unrotated(q, k, v, batch, heads, kv, d, allowed)) < 1e-5
    for a, b in zip(
            jax.grad(lambda *x: jnp.sum(ours(*x) * w), (0, 1, 2))(q, k, v),
            jax.grad(lambda *x: jnp.sum(dense(*x) * w), (0, 1, 2))(q, k, v)):
        assert rel(a, b) < 1e-4


def dense_unrotated(q, k, v, batch, heads, kv, d, allowed):
    q, k, v = (x.reshape(batch, 2 * T, -1, d) for x in (q, k, v))
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# sequence (a half's), step, halves, blocks, sub-tile
STAIR_CASES = {
    "two_halves_sub_tiles": (64, 4, 2, 32, 8),
    "two_halves_step_1": (64, 1, 2, 32, 8),
    "two_halves_whole_tiles": (32, 4, 2, 16, 128),
    "two_halves_a_block_a_sub_tile": (64, 8, 2, 32, 8),
    "two_halves_padded": (24, 4, 2, 16, 8),
    "one_half_block_causal": (64, 4, 1, 32, 8),
}


def _set_sub_tile(monkeypatch, fa, sub):
    monkeypatch.setattr(fa, "_SUB", dict.fromkeys(fa._SUB, sub))
    fa._band_keys.cache_clear()


@pytest.mark.parametrize("case", sorted(STAIR_CASES))
def test_flash_kernels_walk_the_staircase(case, monkeypatch):
    """The three kernels, heads leading and no rotation, against the dense
    mask: every tile class of the staircase, the quadrants of two halves,
    a padded last block."""
    from mxnet_tpu.ops.pallas import flash_attention

    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]
    seq, step, halves, block, sub = STAIR_CASES[case]
    _set_sub_tile(monkeypatch, fa, sub)
    rng = np.random.RandomState(5)
    q, k, v, w = (jnp.asarray(rng.randn(1, n, halves * seq, 8), jnp.float32)
                  for n in (4, 2, 2, 4))
    allowed = jnp.asarray(seen(seq, step, halves))

    def ours(q, k, v):
        return flash_attention(q, k, v, causal=True, step=step,
                               halves=halves, block_q=block, block_k=block)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, 2, axis=1) for x in (k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
        p = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    assert rel(ours(q, k, v), dense(q, k, v)) < 1e-5
    for a, b in zip(
            jax.grad(lambda *x: jnp.sum(ours(*x) * w), (0, 1, 2))(q, k, v),
            jax.grad(lambda *x: jnp.sum(dense(*x) * w), (0, 1, 2))(q, k, v)):
        assert rel(a, b) < 1e-4


@pytest.mark.parametrize("case", sorted(STAIR_CASES))
def test_band_tiles_counts_the_staircase_masks(case, monkeypatch):
    """``band_tiles`` against a count over the boolean mask itself, a
    quadrant at a time: no sub-tile that holds a pair of the mask is
    skipped, none that holds a pair outside it runs unmasked; and a traced
    call leaves the count as one ``flash.band`` record a kernel, with the
    step and the halves."""
    import time

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas import flash_attention

    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]
    seq, step, halves, block, _ = STAIR_CASES[case]
    n = -(-seq // block)
    padded = n * block
    # each half padded to whole blocks: a padded query is computed at its
    # position (and cut off), a padded key is seen by nobody
    mask = seen(seq, step, halves)
    allowed = seen(padded, step, halves)
    for b in range(halves):
        allowed[:, b * padded + seq:(b + 1) * padded] = False

    def count(sub):
        sq, sk = fa._sub_shape(block, block, sub)
        keys = fa._band_keys(n, n, block, block, seq, sub, True, None, step,
                             halves)
        want = {"tiles": 0, "unmasked": 0, "masked": 0, "skipped": 0}
        # the quadrants that hold a tile: (query half, key half, kind)
        quadrants = [(0, 0, None)] if halves == 1 else \
            [(0, 0, fa._OWN), (0, 1, fa._BEFORE), (1, 1, fa._CLEAN)]
        for qh, kh, kind in quadrants:
            for qi in range(n):
                for kj in range(n):
                    if kind == fa._OWN and qi != kj:
                        continue
                    tile = allowed[(qh * n + qi) * block:
                                   (qh * n + qi + 1) * block,
                                   (kh * n + kj) * block:
                                   (kh * n + kj + 1) * block]
                    key = (qi * block - kj * block,
                           seq % block != 0 and kj == n - 1)
                    key += () if kind is None else (kind,)
                    assert tile.any() == (key in keys), (qh, kh, qi, kj)
                    if not tile.any():
                        continue
                    want["tiles"] += 1
                    for a, line in enumerate(keys[key][0]):
                        for b, terms in enumerate(line):
                            part = tile[a * sq:(a + 1) * sq,
                                        b * sk:(b + 1) * sk]
                            where = (qh, kh, qi, kj, a, b)
                            assert (terms is None) == (not part.any()), where
                            assert (terms == ()) == part.all(), where
                            want["unmasked" if part.all() else
                                 "masked" if part.any() else "skipped"] += 1
        want["ratio"] = (want["unmasked"] + want["masked"]) * sq * sk \
            / mask.sum()
        return want

    subs = {8, 128} if block % 8 == 0 else {128}
    want = {sub: count(sub) for sub in subs}
    for sub, numbers in want.items():
        assert fa.band_tiles(seq, seq, block, block, sub, True, None, step,
                             halves) == pytest.approx(numbers)
        assert numbers["ratio"] >= 1.0

    _set_sub_tile(monkeypatch, fa, min(subs))
    x = jnp.zeros((1, 2, halves * seq, 8), jnp.float32)
    since = time.perf_counter()
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, x, x, causal=True, step=step, halves=halves, block_q=block,
        block_k=block)))(x)
    records = [r["attrs"] for r in telemetry.span_records(since)
               if r["name"] == "flash.band"]
    assert sorted(r["kernel"] for r in records) == sorted(fa._SUB)
    for r in records:
        assert {k: r[k] for k in want[min(subs)]} == \
            pytest.approx(want[min(subs)])
        assert (r["seq"], r["bq"], r["bk"], r["step"], r["halves"],
                r["window"]) == (seq, block, block, step, halves, 0)


def test_the_accepted_decoders_band_counts_are_what_they_were():
    """``laguna_xs2.seq8k``'s layers, a head (PERF.md section 3): the
    staircase changes no count of a diagonal, and a call without a step
    records step 1 over one half."""
    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]
    fa._band_keys.cache_clear()
    # read off the parent commit's band_tiles (PR 31's tree)
    parent = {
        (128, None): (36, 2016, 64, 224, 1.0155010374710118),
        (128, 512): (15, 186, 124, 650, 1.24992124992125),
        (256, None): (36, 496, 32, 48, 1.0311241303551812),
        (256, 512): (15, 31, 62, 147, 1.4999054999055)}
    for (sub, window), want in parent.items():
        got = fa.band_tiles(8192, 8192, 1024, 1024, sub, True, window)
        assert tuple(got[k] for k in ("tiles", "unmasked", "masked",
                                      "skipped", "ratio")) == want
        assert fa.band_tiles(8192, 8192, 1024, 1024, sub, True, window, 1,
                             1) == got


def test_flash_kernel_refuses_a_staircase_it_cannot_walk():
    from mxnet_tpu.ops.pallas import flash_attention

    q = jnp.zeros((1, 2, 32, 8))
    for bad in (dict(causal=False, step=4), dict(causal=True, step=3),
                dict(causal=True, step=4, window=8),
                dict(causal=True, step=4, halves=2, block_q=16, block_k=8),
                dict(causal=True, step=16, halves=2, block_q=16,
                     block_k=16)):
        with pytest.raises(ValueError, match="staircase"):
            flash_attention(q, q, q, **bad)
    with pytest.raises(ValueError, match="staircase"):
        flash_attention(q, q, q, causal=True, step=4, halves=3)


# -- the router's score function and the share -----------------------------------

def _moe(held, first, experts=16, top_k=4, width=32):
    return OPS.create("MixtureOfExperts", num_experts=experts,
                      experts_held=held, first_expert=first, top_k=top_k,
                      expert_width=width, score="softmax")


def _moe_weights(rng, experts=16, hidden=32, width=32):
    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)

    return [draw(experts, hidden), draw(experts, width, hidden),
            draw(experts, width, hidden), draw(experts, hidden, width)]


def _moe_reference(x, ws, held, first, top_k=4):
    names = ("router_weight", "gate_weight", "up_weight", "down_weight")
    p = dict(zip(names, ws))
    for n in names[1:]:
        p[n] = p[n][first:first + held]
    return REF.sparse_ffn(x, p, "", {"num_experts_per_tok": top_k,
                                     "num_experts": held,
                                     "first_expert": first})


def test_softmax_router_matches_the_reference():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    ws = _moe_weights(rng)
    op = _moe(16, 0)
    load0 = jnp.zeros((16,), jnp.float32)

    def ours(x, *ws):
        return op.fwd([x, *ws], [load0], True, None)[0][0]

    assert rel(ours(x, *ws), _moe_reference(x, ws, 16, 0)) < 1e-5
    w = jnp.asarray(rng.randn(40, 32), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w),
                   tuple(range(5)))(x, *ws)
    want = jax.grad(lambda x, *ws: jnp.sum(_moe_reference(x, ws, 16, 0) * w),
                    tuple(range(5)))(x, *ws)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4
    # softmax over ALL experts, the picked probabilities over their sum
    experts, weights = op.route(x, ws[0])
    probs = jax.nn.softmax(x @ ws[0].T, axis=-1)
    assert np.array_equal(np.sort(np.asarray(experts), 1),
                          np.sort(np.argsort(-np.asarray(probs), 1)[:, :4], 1))
    assert np.allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-5)
    picked = jnp.take_along_axis(probs, experts, axis=1)
    assert rel(weights, picked / picked.sum(1, keepdims=True)) < 1e-6
    # the sigmoid router of the other decoder is what it was
    sigmoid = OPS.create("MixtureOfExperts", num_experts=16, experts_held=16,
                         top_k=4, expert_width=32)
    assert sigmoid.score == "sigmoid"
    _, s_weights = sigmoid.route(x, ws[0])
    assert rel(weights, s_weights) > 1e-3


def test_the_eight_shares_add_up():
    """16 experts cut 8 ways, as the cell's 128 are: the eight shares'
    routed parts (there is no shared expert) add up to the uncut layer's
    output, and the reference given a share agrees with its rank."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(48, 32), jnp.float32)
    ws = _moe_weights(rng)
    load0 = jnp.zeros((16,), jnp.float32)
    whole = _moe(16, 0).fwd([x, *ws], [load0], True, None)[0][0]
    parts = []
    for rank in range(8):
        held = [w[2 * rank:2 * rank + 2] for w in ws[1:]]
        out = _moe(2, 2 * rank).fwd([x, ws[0], *held], [load0], True,
                                    None)[0][0]
        assert rel(out, _moe_reference(x, ws, 2, 2 * rank)) < 1e-5
        parts.append(out)
    assert rel(sum(parts), whole) < 1e-5
    assert rel(whole, _moe_reference(x, ws, 16, 0)) < 1e-5


# -- the loss head ---------------------------------------------------------------

def _head(block=B):
    return OPS.create("MaskedDiffusionOutput", mask_id=MASK,
                      block_length=block)


def test_loss_head_weights_and_gradient_match_the_reference():
    """A block with one mask weighs its row 4, a block with four each 1,
    an unmasked row nothing; the injected gradient is ``(p - onehot) * w``
    as the reference's; ``loss_value`` (the health stream has the label
    alone) is the unweighted cross-entropy of every row."""
    rng = np.random.RandomState(6)
    counts = [[1, 4, 2, 3, 1, 4, 2, 3], [4, 1, 3, 2, 4, 1, 3, 2]]
    data, x0 = noisy_pairs(rng, 2, counts=counts)
    xt = data[:, :T]
    op = _head()
    in_shapes, out_shapes, aux_shapes = op.infer_shape(
        [(2 * T, V), None, (2, T)])
    assert in_shapes == [(2 * T, V), (2, T), (2, T)]
    assert out_shapes == [(2 * T, V)] and aux_shapes == [(4,)]
    assert op.infer_shape([(2 * T, V), (2, T), None])[0] == in_shapes
    with pytest.raises(mx.MXNetError, match="rows of"):
        op.infer_shape([(2 * T + 1, V), (2, T), None])
    assert [str(t) for t in op.infer_dtype(["bfloat16", None, "int32"])[0]] \
        == ["bfloat16", "int32", "int32"]

    w, nonempty = op.weights(jnp.asarray(xt))
    want = np.asarray(REF.row_weights(jnp.asarray(xt), TINY))
    assert np.array_equal(np.asarray(w), want)
    blocks = np.asarray(w).reshape(2, T // B, B)
    assert sorted(set(blocks[0, 0])) == [0.0, 4.0]        # one mask
    assert np.array_equal(blocks[0, 1], np.ones(B))        # four masks
    assert int(nonempty) == 2 * T // B
    assert np.allclose(blocks.sum(-1), B)

    logits = jnp.asarray(rng.randn(2 * T, V), jnp.float32)
    count0 = jnp.zeros((4,), jnp.float32)

    def ours(z):
        out, _ = op.fwd([z, jnp.asarray(x0), jnp.asarray(xt)], [count0],
                        True, None)
        return jnp.sum(out[0])      # the cotangent is ignored: a loss head

    def theirs(z):
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.sum(want * jnp.take_along_axis(
            logp, jnp.asarray(x0).reshape(-1, 1), axis=1)[:, 0])

    out, (count,) = op.fwd([logits, jnp.asarray(x0), jnp.asarray(xt)],
                           [count0], True, None)
    assert rel(out[0], jax.nn.softmax(logits, axis=-1)) < 1e-6
    assert rel(jax.grad(ours)(logits), jax.grad(theirs)(logits)) < 1e-5
    every_row = -jnp.sum(jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1),
        jnp.asarray(x0).reshape(-1, 1), axis=1))
    assert float(op.loss_value(out[0], jnp.asarray(x0))) \
        == pytest.approx(float(every_row), rel=1e-5)
    # rows, masked rows, blocks, the weights' sum: in training only
    assert np.asarray(count).tolist() == [
        2 * T, float(np.sum(counts)), 2 * T // B, 2 * T]
    _, (same,) = op.fwd([logits, jnp.asarray(x0), jnp.asarray(xt)],
                        [count0], False, None)
    assert float(same.sum()) == 0
    # a block without a mask weighs nothing and is no division by zero
    bare = np.array(xt)
    bare[0, :B] = x0[0, :B]
    w, nonempty = op.weights(jnp.asarray(bare))
    assert np.all(np.isfinite(np.asarray(w))) and float(w[:B].sum()) == 0
    assert int(nonempty) == 2 * T // B - 1


def test_softmax_output_and_the_new_head_share_one_cross_entropy():
    from mxnet_tpu.ops import loss

    rng = np.random.RandomState(8)
    p = jax.nn.softmax(jnp.asarray(rng.randn(12, 7), jnp.float32), axis=-1)
    label = jnp.asarray(rng.randint(0, 7, (12,)))
    nll = -np.log(np.asarray(p)[np.arange(12), np.asarray(label)] + 1e-12)
    assert rel(loss._cross_entropy(p, label), nll) < 1e-6
    plain = OPS.create("SoftmaxOutput")
    assert float(plain.loss_value(p, label)) == pytest.approx(nll.sum(),
                                                              rel=1e-6)
    head = OPS.create("MaskedDiffusionOutput", mask_id=6, block_length=4)
    assert float(head.loss_value(p, label.reshape(3, 4))) \
        == pytest.approx(nll.sum(), rel=1e-6)
    for fn in (loss.SoftmaxOutputOp.loss_value,
               loss.MaskedDiffusionOutputOp.loss_value):
        assert "_cross_entropy(" in inspect.getsource(fn)
        assert "take_along_axis" not in inspect.getsource(fn)


@pytest.mark.parametrize("start", [0, (1 << 23) - 40])
def test_the_mask_count_is_exact_across_its_wrap(start):
    op = _head()
    rng = np.random.RandomState(17)
    before = np.full((4,), start, np.float32)
    count, masked = jnp.asarray(before), 0
    for _ in range(3):
        data, x0 = noisy_pairs(rng, 2)
        masked += int((data[:, :T] == MASK).sum())
        _, (count,) = op.fwd(
            [jnp.zeros((2 * T, V)), jnp.asarray(x0),
             jnp.asarray(data[:, :T])], [count], True, None)
    after = np.asarray(count)
    assert after.dtype == np.float32 and after.max() < op.LOAD_WRAP
    name, attrs = op.epoch_record([before], [after])
    assert name == "fit.epoch.diffusion_mask"
    assert attrs == {"rows": 3 * 2 * T, "masked": masked,
                     "blocks": 3 * 2 * T // B, "weight_sum": 3 * 2 * T,
                     "block_length": B}


# -- the whole tiny model through fit ---------------------------------------------

def test_the_builder_defaults_are_the_published_sizes():
    """https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json,
    as the catalog row has it."""
    defaults = {k: p.default for k, p in
                inspect.signature(mx.models.sdar).parameters.items()}
    assert defaults == {
        "seq_len": 4096, "block_length": 4, "mask_id": None, "layers": 48,
        "vocab_rows": 151936, "experts_held": 128, "first_expert": 0,
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "num_experts": 128,
        "num_experts_per_tok": 8, "moe_intermediate_size": 768,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-6, "remat": True,
        "train_router": None}
    symbol = tiny_model()
    shapes = dict(zip(symbol.list_arguments(),
                      symbol.infer_shape(data=(2, 2 * T))[0]))
    assert shapes["softmax_label"] == (2, T)
    assert shapes["layer0_q_norm_gamma"] == shapes["layer2_k_norm_gamma"] \
        == (16,)
    assert shapes["layer1_moe_router_weight"] == (16, 64)
    assert "shared_gate_weight" not in " ".join(shapes)
    assert symbol.list_auxiliary_states()[-1] == "softmax_mask_count"
    # the mask id defaults to the last row held; a cut rank keeps its router
    text = symbol.tojson()
    assert f'"mask_id": {MASK}' in text and '"score": "softmax"' in text
    assert '"rope_attention_factor": 1.0' in text     # no score scale
    assert '"train_router": true' in text
    assert '"train_router": false' in tiny_model(experts_held=2).tojson()


def test_initializer_rules_for_the_new_names():
    model = seeded_params(tiny_model(), 2, head_scale=None)
    assert np.array_equal(model.arg_params["layer0_q_norm_gamma"].asnumpy(),
                          np.ones(16, np.float32))
    assert np.array_equal(model.aux_params["softmax_mask_count"].asnumpy(),
                          np.zeros(4, np.float32))


def test_mixed_initializer_starts_the_head_norms_elsewhere():
    """``mx.init.Mixed`` from what a configuration file can hold: the first
    pattern that matches, a dict as a class of ``mx.init`` with its
    arguments, a bare number as a fill whatever the name's suffix."""
    import pickle

    spec = {"patterns": ["layer[0-1]_[qk]_norm_gamma$", ".*"],
            "initializers": [2.5, {"name": "Xavier", "magnitude": 3}]}
    symbol = tiny_model()
    shapes = {"data": (2, 2 * T), "softmax_label": (2, T)}

    def drawn(initializer):
        mx.random.seed(3)
        model = mx.FeedForward(symbol, ctx=mx.cpu(), initializer=initializer)
        model._init_params(shapes)
        return {k: v.asnumpy() for k, v in model.arg_params.items()}

    mixed, plain = drawn(mx.init.Mixed(**spec)), drawn(mx.init.Xavier())
    for name, arr in mixed.items():
        if re.search(spec["patterns"][0], name):
            assert np.array_equal(arr, np.full(16, 2.5, np.float32)), name
        else:       # layer 2's and every other array as Xavier alone draws
            assert np.array_equal(arr, plain[name]), name
    assert np.array_equal(mixed["layer2_q_norm_gamma"], np.ones(16))
    again = pickle.loads(pickle.dumps(mx.init.Mixed(**spec)))
    arr = mx.nd.zeros((4,))
    again("layer1_q_norm_gamma", arr)
    assert arr.asnumpy().tolist() == [2.5] * 4
    with pytest.raises(mx.MXNetError, match="no pattern matches"):
        mx.init.Mixed(["gamma$"], [1.0])("layer0_q_weight", arr)
    with pytest.raises(mx.MXNetError, match="2 patterns for 1"):
        mx.init.Mixed(["a", "b"], [1.0])


def test_tiny_model_logits_match_the_reference():
    symbol = tiny_model()
    model = seeded_params(symbol, 2)
    data, _ = noisy_pairs(np.random.RandomState(9), 2)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    want = np.asarray(REF.logits(params, None, data, TINY))
    assert want.shape == (2 * T, V)
    head = mx.symbol.Reshape(data=symbol.get_internals()["head_output"],
                             target_shape=(2, -1))
    served = mx.FeedForward(head, ctx=mx.cpu(), arg_params=model.arg_params,
                            aux_params=model.aux_params)
    # ``predict`` cuts an output to the batch's valid rows: here a sample is
    # 2 T positions of data and T rows of logits
    got = served.predict(data, batch_size=2)
    assert got.shape == (2, T * V)
    assert rel(got, want.reshape(2, -1)) < 1e-5


def test_valid_rows_keeps_every_noisy_position_of_a_padded_batch():
    """``_valid_rows`` for a head whose rows are T a sample while the data
    has 2 T positions: three samples through batches of two."""
    from mxnet_tpu.model import _valid_rows

    out = np.arange(2 * T * 3).reshape(2 * T, 3)
    assert np.array_equal(_valid_rows(out, 2, 1), out[:T])
    assert _valid_rows(out, 2, 2) is out
    symbol = tiny_model()
    model = seeded_params(symbol, 2)
    data, _ = noisy_pairs(np.random.RandomState(10), 3)
    served = mx.FeedForward(symbol.get_internals()["head_output"],
                            ctx=mx.cpu(), arg_params=model.arg_params,
                            aux_params=model.aux_params)
    got = served.predict(data, batch_size=2)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    want = np.asarray(REF.logits(params, None, data, TINY))
    assert got.shape == (3 * T, V) and rel(got, want) < 1e-5


def _fit(symbol, params, data, labels, batch, lr, compute_dtype=None,
         epochs=1):
    metric = mx.metric.CrossEntropy()
    model = mx.FeedForward(
        symbol, ctx=mx.cpu(), num_epoch=epochs, optimizer="sgd",
        learning_rate=lr, compute_dtype=compute_dtype,
        arg_params={k: mx.nd.array(v) for k, v in params.items()})
    model.fit(mx.io.NDArrayIter(data, labels, batch_size=batch),
              eval_metric=metric, batch_size=batch)
    return model, metric.get()[1]


@pytest.fixture(scope="module")
def three_steps():
    batch, lr = 2, 0.01
    symbol = tiny_model()
    start = {k: v.asnumpy() for k, v in
             seeded_params(symbol, batch).arg_params.items()}
    data, labels = noisy_pairs(np.random.RandomState(7), 3 * batch)
    p, first = dict(start), None
    for s in range(3):
        rows = slice(s * batch, (s + 1) * batch)
        _, grads = REF.loss_and_grads(p, data[rows], labels[rows], TINY)
        first = first or {k: np.asarray(g) / batch for k, g in grads.items()}
        p = {k: np.asarray(p[k] - lr * grads[k] / batch) for k in p}
    return dict(batch=batch, lr=lr, symbol=symbol, start=start, data=data,
                labels=labels, want=p, first=first)


def test_tiny_model_three_steps_of_fit_follow_the_reference(three_steps):
    """float32: the first step's gradient norm and every leaf's change
    over three steps against ``loss_and_grads``; the metric is the plain
    cross-entropy of every noisy row, as ``CrossEntropy`` reads the head."""
    s = three_steps
    model, loss = _fit(s["symbol"], s["start"], s["data"], s["labels"],
                       s["batch"], s["lr"])
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert set(got) == set(s["want"])
    worst = max(rel(got[k] - s["start"][k], s["want"][k] - s["start"][k])
                for k in got)
    assert worst < 1e-4, worst
    one, _ = _fit(s["symbol"], s["start"], s["data"][:s["batch"]],
                  s["labels"][:s["batch"]], s["batch"], s["lr"])
    grads = {k: (s["start"][k] - v.asnumpy()) / s["lr"]
             for k, v in one.arg_params.items()}
    norm = np.sqrt(sum(np.sum(np.square(g, dtype=np.float64))
                       for g in grads.values()))
    want = np.sqrt(sum(np.sum(np.square(g, dtype=np.float64))
                       for g in s["first"].values()))
    assert norm == pytest.approx(want, rel=1e-4)
    # the loss fit reports: every noisy row's cross-entropy, unweighted,
    # over the three steps' weights
    p, seen_loss = dict(s["start"]), []
    for i in range(3):
        rows = slice(i * s["batch"], (i + 1) * s["batch"])
        logp = jax.nn.log_softmax(REF.logits(p, None, s["data"][rows], TINY))
        seen_loss.append(-float(jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(s["labels"][rows]).reshape(-1, 1), axis=1))))
        _, g = REF.loss_and_grads(p, s["data"][rows], s["labels"][rows],
                                  TINY)
        p = {k: np.asarray(p[k] - s["lr"] * g[k] / s["batch"]) for k in p}
    assert loss == pytest.approx(np.mean(seen_loss), rel=1e-5)
    # the head counted on the way
    count = model.aux_params["softmax_mask_count"].asnumpy()
    assert count[0] == 3 * s["batch"] * T
    assert count[1] == (s["data"][:, :T] == MASK).sum()
    for l in range(3):
        load = model.aux_params[f"layer{l}_moe_expert_load"].asnumpy()
        assert load.sum() == 3 * s["batch"] * 2 * T * 4


def test_tiny_model_in_bfloat16_stays_inside_its_band(three_steps):
    """bfloat16 compute, float32 master weights: the three steps' loss
    agrees with float32 to 2 %, and with every row taking every expert
    (so that no pick flips under the rounding, tests/test_laguna.py) the
    first step's parameter change to 12 % of its norm."""
    s = three_steps
    _, want = _fit(s["symbol"], s["start"], s["data"], s["labels"],
                   s["batch"], s["lr"])
    _, loss = _fit(s["symbol"], s["start"], s["data"], s["labels"],
                   s["batch"], s["lr"], compute_dtype=jnp.bfloat16)
    assert loss == pytest.approx(want, rel=2e-2)
    rows = slice(0, s["batch"])
    _, g = REF.loss_and_grads(s["start"], s["data"][rows], s["labels"][rows],
                              dict(TINY, num_experts_per_tok=16))
    model, _ = _fit(tiny_model(num_experts_per_tok=16), s["start"],
                    s["data"][rows], s["labels"][rows], s["batch"], s["lr"],
                    compute_dtype=jnp.bfloat16)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert all(v.dtype == np.float32 for v in got.values())
    total = rel(
        np.concatenate([(got[k] - s["start"][k]).ravel()
                        for k in sorted(got)]),
        np.concatenate([-s["lr"] * np.asarray(g[k]).ravel() / s["batch"]
                        for k in sorted(got)]))
    assert 1e-4 < total < 0.12, total


def test_fit_emits_one_diffusion_mask_record_an_epoch():
    """After each epoch's write-back one ``fit.epoch.diffusion_mask``
    record with THAT epoch's counts, beside the expert nodes' records,
    through the hook ``fit`` offers every operator."""
    from mxnet_tpu import telemetry

    batch = 2
    data, labels = noisy_pairs(np.random.RandomState(11), 3 * batch)
    mx.random.seed(5)
    mark = len(telemetry.span_records())
    model = mx.FeedForward(tiny_model(experts_held=8, first_expert=4),
                           ctx=mx.cpu(), num_epoch=2, optimizer="sgd",
                           learning_rate=0.01, initializer=mx.init.Xavier())
    model.fit(mx.io.NDArrayIter(data, labels, batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    records = telemetry.span_records()[mark:]
    masks = [r for r in records if r["name"] == "fit.epoch.diffusion_mask"]
    assert [(r["epoch"], r["attrs"]["node"]) for r in masks] == [
        (0, "softmax"), (1, "softmax")]
    masked = int((data[:, :T] == MASK).sum())
    for r in masks:
        a = {k: v for k, v in r["attrs"].items() if k not in ("epoch",
                                                              "node")}
        assert a == {"rows": 3 * batch * T, "masked": masked,
                     "blocks": 3 * batch * T // B,
                     "weight_sum": 3 * batch * T, "block_length": B}
    loads = [r for r in records if r["name"] == "fit.epoch.expert_load"]
    assert len(loads) == 2 * 3
    assert all(r["attrs"]["tokens"] == 3 * batch * 2 * T for r in loads)
    total = model.aux_params["softmax_mask_count"].asnumpy()
    assert total.tolist() == [2 * 3 * batch * T, 2 * masked,
                              2 * 3 * batch * T // B, 2 * 3 * batch * T]
