"""The sparse decoder through the normal path: the operators of
``ops/decoder.py`` and the flash kernel's grouped heads and window against
the plain reference (``benchmark/configs/laguna_xs2.py``, which imports
nothing from the program), the tiny model through ``FeedForward.fit``, the
share of an expert-parallel deployment, and the repairs that came with it
(``model.py``'s cast of integer inputs, ``predict``'s cut of per-position
outputs, the initializer's rules, the Symbol-carried recomputation
boundary, the ``fit.epoch.expert_load`` record).

Tiny sizes, seeded weights, float32 unless a test says bfloat16.
"""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import OPS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL, SLIDING = "full_attention", "sliding_attention"


def _reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_reference",
        os.path.join(ROOT, "benchmark", "configs", "laguna_xs2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
T, V = 32, 96
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 16, "beta_slow": 1,
           "beta_fast": 4, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}
# 1 dense + 2 sliding + 1 full layer, 16 experts top-4, unequal head counts
TINY = dict(
    num_hidden_layers=4, vocab_size=V, num_experts=16, first_expert=0,
    hidden_size=64, intermediate_size=96, head_dim=16, num_key_value_heads=2,
    num_attention_heads_per_layer=[4, 6, 6, 4],
    layer_types=[FULL, SLIDING, SLIDING, FULL],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    sliding_window=8, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, moe_routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, gating=True, rope_parameters=ROPE)


def tiny_model(experts_held=16, first_expert=0, seq_len=T, **over):
    sizes = {k: tuple(v) if isinstance(v, list) else v
             for k, v in dict(TINY, **over).items()
             if k not in ("num_hidden_layers", "vocab_size", "num_experts",
                          "first_expert")}
    return mx.models.laguna(seq_len=seq_len, layers=4, vocab_rows=V,
                            experts_held=experts_held,
                            first_expert=first_expert, num_experts=16,
                            **sizes)


def seeded_params(symbol, batch, seed=3):
    mx.random.seed(seed)
    model = mx.FeedForward(symbol, ctx=mx.cpu(),
                           initializer=mx.init.Xavier())
    model._init_params({"data": (batch, T), "softmax_label": (batch, T)})
    return model


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# -- operators against the reference's pieces ---------------------------------

def test_rms_norm_and_silu_match_the_reference():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(10, 24), jnp.float32)
    g = jnp.asarray(rng.rand(24) + 0.5, jnp.float32)
    op = OPS.create("RMSNorm", eps=1e-6)
    assert op.infer_shape([(10, 24), None]) == ([(10, 24), (24,)],
                                                [(10, 24)], [])

    def ours(x, g):
        return jnp.sum(jnp.sin(op.fwd([x, g], [], True, None)[0][0]))

    def theirs(x, g):
        return jnp.sum(jnp.sin(REF.rms_norm(x, g, 1e-6)))

    assert rel(op.fwd([x, g], [], True, None)[0][0],
               REF.rms_norm(x, g, 1e-6)) < 1e-6
    for a, b in zip(jax.grad(ours, (0, 1))(x, g),
                    jax.grad(theirs, (0, 1))(x, g)):
        assert rel(a, b) < 1e-5
    # computed in float32 whatever the input's type
    y16 = op.fwd([x.astype(jnp.bfloat16), g], [], True, None)[0][0]
    assert y16.dtype == jnp.bfloat16
    assert rel(y16.astype(jnp.float32), REF.rms_norm(x, g, 1e-6)) < 1e-2
    silu = OPS.create("Activation", act_type="silu")
    assert rel(silu.fwd([x], [], True, None)[0][0],
               x * jax.nn.sigmoid(x)) < 1e-6


@pytest.mark.parametrize("kind,heads,window,d", [
    (FULL, 4, 0, 16), (SLIDING, 6, 8, 16), (SLIDING, 6, 0, 16),
    (FULL, 4, 0, 128), (SLIDING, 6, 8, 128)])
def test_attention_operator_matches_the_reference(kind, heads, window, d):
    """Rotary positions (plain and YaRN, partial), grouped heads, window,
    head gate: output and every input's gradient. At a head of 128 (whole
    lane tiles, the published size) the kernels rotate the queries and
    apply the gate themselves; at 16 the operator does, around them."""
    rng = np.random.RandomState(1)
    batch, kv = 2, 2
    rope = ROPE[kind]
    q, k, v, g = (jnp.asarray(rng.randn(batch * T, n), jnp.float32)
                  for n in (heads * d, kv * d, kv * d, heads))
    from mxnet_tpu.models.laguna import _rotary_kwargs

    op = OPS.create("RotaryAttention", seq_len=T, num_heads=heads,
                    num_kv_heads=kv, head_dim=d, window=window, gated=True,
                    **_rotary_kwargs(rope, d))
    shapes = op.infer_shape([(batch * T, heads * d), None, None, None])
    assert shapes[0] == [(batch * T, heads * d), (batch * T, kv * d),
                         (batch * T, kv * d), (batch * T, heads)]
    assert shapes[1] == [(batch * T, heads * d)]
    inv_freq, factor = REF.inverse_frequencies(rope, d)
    assert np.allclose(op.inv_freq(), inv_freq, rtol=1e-12)

    def ours(q, k, v, g):
        return op.fwd([q, k, v, g], [], True, None)[0][0]

    def theirs(q, k, v, g):
        a = REF.attention(
            REF.rotate(q.reshape(batch, T, heads, d), inv_freq, factor),
            REF.rotate(k.reshape(batch, T, kv, d), inv_freq, factor),
            v.reshape(batch, T, kv, d), window)
        a = a * jax.nn.sigmoid(g).reshape(batch, T, heads, 1)
        return a.reshape(batch * T, heads * d)

    assert rel(ours(q, k, v, g), theirs(q, k, v, g)) < 1e-5
    w = jnp.asarray(rng.randn(batch * T, heads * d), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2, 3))(q, k, v, g)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * w),
                    (0, 1, 2, 3))(q, k, v, g)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4


def test_yarn_frequencies_of_the_published_full_layers():
    """The published full-attention parameters (rotary_dim 64): the fast
    dimensions keep theta^(-2i/d), the slow ones are divided by 64."""
    from mxnet_tpu.ops.decoder import rotary_inv_freq

    inv = rotary_inv_freq(64, 500000.0, "yarn", 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-np.arange(32) * 2.0 / 64)
    assert inv.shape == (32,)
    # 64 turns within 4,096 positions at dimension 5.7, one turn at 15.8
    assert np.allclose(inv[:6], plain[:6]) and np.allclose(
        inv[16:], plain[16:] / 64)
    assert np.all(inv[6:16] < plain[6:16]) and np.all(
        inv[6:16] > plain[6:16] / 64)
    assert np.all(np.diff(inv) < 0)
    ref = REF.inverse_frequencies(
        {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
         "original_max_position_embeddings": 4096, "beta_slow": 1,
         "beta_fast": 64, "attention_factor": 1.4158883083359672,
         "partial_rotary_factor": 0.5}, 128)
    assert np.allclose(inv, ref[0], rtol=1e-12) and ref[1] > 1.4


def _moe(held, first, experts=16, top_k=4):
    return OPS.create("MixtureOfExperts", num_experts=experts,
                      experts_held=held, first_expert=first, top_k=top_k,
                      expert_width=24, scaling=2.5, shared_width=24)


def _moe_weights(rng, hidden=32, experts=16, width=24):
    shapes = [(experts, hidden), (experts, width, hidden),
              (experts, width, hidden), (experts, hidden, width),
              (width, hidden), (width, hidden), (hidden, width)]
    return [jnp.asarray(rng.randn(*s) / np.sqrt(s[-1]), jnp.float32)
            for s in shapes]


def _moe_reference(x, ws, held, first, top_k=4):
    names = ("router_weight", "gate_weight", "up_weight", "down_weight",
             "shared_gate_weight", "shared_up_weight", "shared_down_weight")
    p = dict(zip(names, ws))
    for n in names[1:4]:
        p[n] = p[n][first:first + held]
    return REF.sparse_ffn(x, p, "", {
        "num_experts_per_tok": top_k, "moe_routed_scaling_factor": 2.5,
        "num_experts": held, "first_expert": first})


def test_expert_operator_matches_the_reference():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    ws = _moe_weights(rng)
    op = _moe(16, 0)
    in_shapes, out_shapes, aux_shapes = op.infer_shape(
        [(40, 32)] + [None] * 7)
    assert in_shapes[1:] == [tuple(w.shape) for w in ws]
    assert out_shapes == [(40, 32)] and aux_shapes == [(16,)]
    load0 = jnp.zeros((16,), jnp.float32)

    def ours(x, *ws):
        return op.fwd([x, *ws], [load0], True, None)[0][0]

    assert rel(ours(x, *ws), _moe_reference(x, ws, 16, 0)) < 1e-5
    w = jnp.asarray(rng.randn(40, 32), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w),
                   tuple(range(8)))(x, *ws)
    want = jax.grad(lambda x, *ws: jnp.sum(_moe_reference(x, ws, 16, 0) * w),
                    tuple(range(8)))(x, *ws)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4
    # the auxiliary count: every pick of every row, all experts, in
    # training only
    _, (load,) = op.fwd([x, *ws], [load0], True, None)
    assert float(load.sum()) == 40 * 4 and load.shape == (16,)
    experts, weights = op.route(x, ws[0])
    assert np.array_equal(np.asarray(load),
                          np.bincount(np.asarray(experts).ravel(),
                                      minlength=16))
    assert np.allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-5)
    _, (same,) = op.fwd([x, *ws], [load0], False, None)
    assert float(same.sum()) == 0


def test_the_shares_add_up():
    """16 experts cut 4 ways: the four shares' routed parts plus the shared
    expert once equal the uncut layer's output."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(48, 32), jnp.float32)
    ws = _moe_weights(rng)
    load0 = jnp.zeros((16,), jnp.float32)
    whole = _moe(16, 0).fwd([x, *ws], [load0], True, None)[0][0]
    shared = REF.gated_ffn(x, *ws[4:])
    parts = []
    for rank in range(4):
        held = [w[4 * rank:4 * rank + 4] for w in ws[1:4]]
        out = _moe(4, 4 * rank).fwd([x, ws[0], *held, *ws[4:]], [load0],
                                    True, None)[0][0]
        # the reference, given the same share, agrees with each rank
        assert rel(out, _moe_reference(x, ws, 4, 4 * rank)) < 1e-5
        parts.append(out - shared)
    assert rel(sum(parts) + shared, whole) < 1e-5
    assert rel(whole, _moe_reference(x, ws, 16, 0)) < 1e-5


def test_no_pick_is_dropped_when_every_row_picks_the_same_experts():
    """A router that sends every row to the same top-k: the grouped
    products take all rows x top_k picks, and a rank that holds none of
    them adds the shared expert alone."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(np.abs(rng.randn(64, 32)) + 0.1, jnp.float32)
    ws = _moe_weights(rng)
    router = np.zeros((16, 32), np.float32)
    router[[1, 2, 3, 9]] = [[4.0], [3.0], [2.0], [1.0]]   # positive rows
    ws[0] = jnp.asarray(router)
    load0 = jnp.zeros((16,), jnp.float32)
    op = _moe(4, 0)        # holds 0..3: three of every row's four picks
    held = [w[:4] for w in ws[1:4]]
    out, (load,) = op.fwd([x, ws[0], *held, *ws[4:]], [load0], True, None)
    assert np.array_equal(np.asarray(load)[[1, 2, 3, 9]], [64.0] * 4)
    assert float(load.sum()) == 64 * 4
    assert rel(out[0], _moe_reference(x, ws, 4, 0)) < 1e-5
    away = _moe(4, 12)     # holds 12..15: none of them
    out = away.fwd([x, ws[0], *[w[12:] for w in ws[1:4]], *ws[4:]],
                   [load0], True, None)[0][0]
    assert rel(out, REF.gated_ffn(x, *ws[4:])) < 1e-5


# -- the kernels that move rows to the sorted space and back -------------------
# ``(rows, top_k, width, dtype, held experts, load)``: ``load`` says how many
# of the rows x top_k picks fall on an expert held here, and on which
EXPERT_SPACE_CASES = {
    "drawn": (300, 4, 32, "float32", 8, ("share", 0.5)),
    "drawn_bf16": (300, 4, 32, "bfloat16", 8, ("share", 0.5)),
    "an_eighth_bf16": (512, 8, 160, "bfloat16", 4, ("share", 0.125)),
    "none_held": (300, 4, 32, "float32", 8, ("count", 0)),
    "none_held_bf16": (64, 4, 32, "bfloat16", 8, ("count", 0)),
    "all_held": (300, 4, 32, "float32", 8, ("count", 1200)),
    "all_held_bf16": (300, 4, 32, "bfloat16", 8, ("count", 1200)),
    "one_expert_takes_all": (300, 4, 32, "float32", 8, ("one", 700)),
    "on_a_tile_boundary": (300, 4, 32, "float32", 8, ("count", 1024)),
    "one_off_a_tile_boundary": (300, 4, 32, "bfloat16", 8, ("count", 1025)),
    "an_odd_count_bf16": (300, 4, 32, "bfloat16", 8, ("count", 77)),
    "rows_no_multiple_of_anything": (37, 3, 24, "float32", 5, ("share", 0.4)),
    "rows_no_multiple_of_anything_bf16": (37, 3, 24, "bfloat16", 5,
                                          ("share", 0.4)),
    "all_of_an_odd_space": (37, 3, 24, "float32", 5, ("count", 111)),
    "wider_than_a_chunk": (4200, 2, 1100, "float32", 4, ("share", 0.1)),
    "clean_behind_the_count": (300, 4, 32, "float32", 8, ("share", 0.5)),
}


def _sorted_space(rng, rows, k, held, load):
    """``order``, ``slots``, ``sizes``, ``n`` as ``routed`` makes them, from
    a drawn key a pick: an expert held here, or ``held`` for elsewhere."""
    kind, amount = load
    space = rows * k
    n = int(round(amount * space)) if kind == "share" else amount
    key = np.full(space, held, np.int32)
    here = rng.permutation(space)[:n]
    key[here] = 2 if kind == "one" else rng.randint(0, held, n)
    order = np.argsort(key, kind="stable").astype(np.int32)
    slots = np.empty(space, np.int32)
    slots[order] = np.arange(space, dtype=np.int32)
    sizes = np.bincount(key, minlength=held + 1)[:held]
    assert sizes.sum() == n
    return jnp.asarray(order), jnp.asarray(slots.reshape(rows, k)), n


@pytest.mark.parametrize("case", sorted(EXPERT_SPACE_CASES))
def test_the_expert_kernels_match_the_plain_gathers(case):
    """Both custom VJPs of ``ops/decoder.py``, forward and gradients,
    against the gathers over the whole space that they replaced. Behind the
    count everything the kernels are handed is NaN (``clean_...`` excepted)
    and everything they leave unwritten is NaN under the interpreter: what
    comes out must be finite and the reference's, the routing weights'
    gradient included (what ``train_router`` true asks for; false leaves
    it unused)."""
    from mxnet_tpu.ops import decoder as dec

    rows, k, width, dtype, held, load = EXPERT_SPACE_CASES[case]
    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(len(case))
    order, slots, n = _sorted_space(rng, rows, k, held, load)
    space = rows * k
    inside = (jnp.arange(space) < n)[:, None]
    poison = 0.0 if case.startswith("clean") else jnp.nan
    exact = dict(rtol=0, atol=0)
    close = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=1e-3)     # one rounding of a float32 sum

    def same(got, want, **tol):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(want, np.float32), **tol)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    # rows -> sorted: a gather, bit for bit; its gradient a sum over a
    # row's held picks
    x, g = draw(rows, width), jnp.where(inside, draw(space, width), poison)
    out, vjp = jax.vjp(lambda x: dec._rows_to_sorted(x, order, slots, n), x)
    same(out[:n], x[order // k][:n], **exact)
    tile = min(1024, space + (-space % 16 if dtype.itemsize == 2 else 0))
    same(out[n:-(-max(n, 1) // tile) * tile], 0, **exact)
    same(vjp(g.astype(dtype))[0],
         jnp.where(inside, g, 0)[slots].astype(jnp.float32).sum(axis=1)
         .astype(dtype), **close)

    # sorted -> rows: the weighted sum in float32; its gradients the
    # scaled gather and a dot product a pick
    y = jnp.where(inside, draw(space, width), poison).astype(dtype)
    weight = jnp.where(slots < n, jnp.asarray(rng.rand(rows, k), jnp.float32),
                       0.0)

    def plain_sum(y, weight):
        return jnp.einsum("rk,rkw->rw", weight,
                          jnp.where(inside, y, 0)[slots].astype(jnp.float32)
                          ).astype(dtype)

    out, vjp = jax.vjp(
        lambda y, w: dec._sorted_to_rows(y, w, order, slots, n), y, weight)
    plain, plain_vjp = jax.vjp(plain_sum, y, weight)
    same(out, plain, **close)
    g = draw(rows, width)
    (dy, dweight), (plain_dy, plain_dweight) = vjp(g), plain_vjp(g)
    same(dy[:n], plain_dy[:n], **close)
    same(dweight, jnp.where(slots < n, plain_dweight, 0),
         **dict(close, atol=1e-4 * width))


def test_the_expert_operator_holds_no_control_flow():
    """No ``cond``, ``while`` or ``scan`` in the operator's forward or
    gradient outside a kernel's body: the benchmark sums device time by
    instruction and counts an instruction inside a conditional or a loop
    twice (PERF.md section 7), so the work follows the step's count inside
    the kernels only."""
    rng = np.random.RandomState(6)
    ws = _moe_weights(rng)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    load0 = jnp.zeros((16,), jnp.float32)

    def found(jaxpr, names):
        for eqn in jaxpr.eqns:
            names.add(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found(sub, names)
        return names

    for train_router in (True, False):
        op = OPS.create("MixtureOfExperts", num_experts=16, experts_held=8,
                        first_expert=4, top_k=4, expert_width=24,
                        scaling=2.5, shared_width=24,
                        train_router=train_router)
        held = [ws[0]] + [w[4:12] for w in ws[1:4]] + ws[4:]

        def out(x, *ws):
            return jnp.sum(op.fwd([x, *ws], [load0], True, None)[0][0])

        for fn in (out, jax.grad(out, tuple(range(8)))):
            names = found(jax.make_jaxpr(fn)(x, *held).jaxpr, set())
            assert "pallas_call" in names and "ragged_dot_general" in names
            assert not names & {"cond", "while", "scan"}, names


def test_a_traced_expert_kernel_leaves_its_space_record():
    """One zero-length ``moe.space`` record a traced call of each kernel:
    the step's rows and ``top_k``, the sorted entries a tile and the most
    tiles a call can run (the step's count decides how many it does)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas import moe

    rows, k, width = 700, 4, 32
    token = jnp.zeros((rows * k,), jnp.int32)
    mark = len(telemetry.span_records())
    jax.eval_shape(lambda x, n: moe.moe_dispatch(x, token, n),
                   jnp.zeros((rows, width)), jnp.int32(0))
    jax.eval_shape(lambda y, n: moe.moe_combine(y, token, n, rows),
                   jnp.zeros((rows * k, width)), jnp.int32(0))
    records = [r for r in telemetry.span_records()[mark:]
               if r["name"] == "moe.space"]
    assert [r["attrs"] for r in records] == [
        {"kernel": kernel, "rows": rows, "top_k": k,
         "tile": moe.space_tile(rows * k), "tiles_max": 3}
        for kernel in ("moe_dispatch", "moe_combine")]
    assert moe.space_tile(rows * k) == 1024 and moe.space_tile(40) == 40


# -- the flash kernel: grouped heads, window, skipped blocks -------------------

def _dense_attention(q, k, v, window, causal=True):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    seen = i >= j if causal else jnp.ones((q.shape[2], k.shape[2]), bool)
    if window:
        seen &= i - j < window
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("heads,window", [
    (48, None), (48, 512), (64, None), (64, 512)])
def test_flash_kernel_grouped_heads_and_window(heads, window):
    """Interpreter mode against a dense masked softmax at the published
    head counts over 8 key-value heads, the published window and none, the
    wrapper's own block shapes; gradients included."""
    from mxnet_tpu.ops.pallas import flash_attention
    from mxnet_tpu.ops.pallas.flash_attention import _choose_blocks, _kv_steps

    rng = np.random.RandomState(6)
    seq, d = 1536, 8
    q = jnp.asarray(rng.randn(1, heads, seq, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, 8, seq, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, 8, seq, d), jnp.float32)
    bq, bk = _choose_blocks(True)
    assert (bq, bk) == (1024, 1024)
    # with the window the grid's key dimension stays two blocks however
    # long the sequence: blocks outside the band are never visited
    assert _kv_steps(bq, bk, -(-seq // bk), True, window) == 2
    assert _kv_steps(bq, bk, 8, True, window) == (2 if window else 8)
    w = jnp.asarray(rng.randn(1, heads, seq, d), jnp.float32)

    def ours(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       window=window) * w)

    def dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, window) * w)

    assert rel(flash_attention(q, k, v, causal=True, window=window),
               _dense_attention(q, k, v, window)) < 1e-5
    for a, b in zip(jax.grad(ours, (0, 1, 2))(q, k, v),
                    jax.grad(dense, (0, 1, 2))(q, k, v)):
        assert rel(a, b) < 1e-4


# the tile classes of the band walk: (positions, heads, key-value heads,
# head size, causal, window, block_q, block_k, sub-tile, rotation and gate
# in the kernels). ``None`` blocks are the wrapper's own choice, a ``None``
# sub-tile the module's
BAND_CASES = {
    # tiles inside the band, and diagonal tiles walked by sub-tiles
    "causal": (1536, 2, 1, 8, True, None, None, None, None, False),
    # the published window at the blocks the wrapper chooses for it
    "window_512": (1536, 2, 1, 8, True, 512, None, None, None, False),
    # a window that is no multiple of the sub-tile, one narrower than it
    "window_200": (1000, 2, 2, 8, True, 200, 256, 256, 128, False),
    "window_50": (1000, 2, 2, 8, True, 50, 256, 256, 128, False),
    # a length that is no multiple of the block: a padded last key block
    "padded_causal": (1000, 2, 2, 8, True, None, 512, 512, 128, False),
    "padded_window": (900, 2, 1, 8, True, 300, 512, 512, 128, False),
    # blocks that are not square, either way
    "wide_keys": (1000, 4, 2, 8, True, None, 256, 512, 128, False),
    "tall_queries": (1000, 4, 2, 8, True, 300, 512, 256, 128, False),
    # no mask but the padding: every other tile is inside
    "not_causal": (1000, 2, 2, 8, False, None, 256, 512, 128, False),
    # blocks smaller than a sub-tile: a tile is its own sub-tile
    "small_blocks": (100, 2, 2, 8, True, 30, 64, 32, None, False),
    # the published head counts over 8, rotation and gate in the kernels
    "heads_48_of_8": (384, 48, 8, 128, True, None, 256, 256, 128, True),
    "heads_64_of_8": (384, 64, 8, 128, True, 200, 256, 256, 128, True),
}


def _set_sub_tile(monkeypatch, fa, sub):
    for kernel in fa._SUB if sub else ():
        monkeypatch.setitem(fa._SUB, kernel, sub)


@pytest.mark.parametrize("case", sorted(BAND_CASES))
def test_flash_kernels_walk_the_band_by_sub_tiles(case, monkeypatch):
    """Interpreter mode against the dense masked softmax, output and all
    three gradients (the gate's too where it is on), one case a tile
    class."""
    import sys

    from mxnet_tpu.ops.pallas import flash_attention
    from mxnet_tpu.ops.pallas.flash_attention import rotary_tables

    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]
    seq, heads, kv, d, causal, window, bq, bk, sub, in_kernel = \
        BAND_CASES[case]
    _set_sub_tile(monkeypatch, fa, sub)
    rng = np.random.RandomState(7)
    q, k, v, w = (jnp.asarray(rng.randn(1, n, seq, d), jnp.float32)
                  for n in (heads, kv, kv, heads))
    g = jnp.asarray(rng.randn(1, seq, heads), jnp.float32)
    rope = rotary_tables(seq, d, 1.0 / 10000.0 ** (np.arange(32) / 32.0)) \
        if in_kernel else None

    def rows(x):      # heads last, as a projection leaves its rows
        return x.transpose(0, 2, 1, 3)

    def ours(q, k, v, g):
        if not in_kernel:
            return flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=bq, block_k=bk)
        return rows(flash_attention(
            rows(q), rows(k), rows(v), causal=True, window=window,
            block_q=bq, block_k=bk, heads_last=True, rotary=rope, gate=g))

    def dense(q, k, v, g):
        if not in_kernel:
            return _dense_attention(q, k, v, window, causal)
        cos, sin, rot = rope
        o = _dense_attention(q * cos + (q @ rot) * sin, k, v, window)
        return o * rows(jax.nn.sigmoid(g)[..., None])

    assert rel(ours(q, k, v, g), dense(q, k, v, g)) < 1e-5
    wrt = (0, 1, 2, 3) if in_kernel else (0, 1, 2)
    for a, b in zip(
            jax.grad(lambda *x: jnp.sum(ours(*x) * w), wrt)(q, k, v, g),
            jax.grad(lambda *x: jnp.sum(dense(*x) * w), wrt)(q, k, v, g)):
        assert rel(a, b) < 1e-4


@pytest.mark.parametrize("case", sorted(BAND_CASES))
def test_band_tiles_counts_what_the_mask_says(case, monkeypatch):
    """``band_tiles`` against a count over the boolean mask itself; no
    sub-tile that holds a pair of the band is skipped, none that holds a
    pair outside it runs unmasked; and a traced call leaves the count as
    one ``flash.band`` record a kernel (two where ``flash_bwd_dkv`` makes
    dq too)."""
    import sys

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas import flash_attention

    fa = sys.modules["mxnet_tpu.ops.pallas.flash_attention"]
    seq, heads, kv, d, causal, window, *given, sub, _ = BAND_CASES[case]
    _set_sub_tile(monkeypatch, fa, sub)
    bq, bk = (min(b or chosen, seq)
              for b, chosen in zip(given, fa._choose_blocks(causal)))
    nq, nk = -(-seq // bq), -(-seq // bk)
    i = np.arange(nq * bq)[:, None]
    j = np.arange(nk * bk)[None, :]
    seen = (j < seq) & (i >= (j if causal else 0))
    if window:
        seen &= i - j < window

    def count(sub):
        sq, sk = fa._sub_shape(bq, bk, sub)
        keys = fa._band_keys(nq, nk, bq, bk, seq, sub, causal, window)
        want = {"tiles": 0, "unmasked": 0, "masked": 0, "skipped": 0}
        for qi in range(nq):
            for kj in range(nk):
                tile = seen[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
                key = (qi * bq - kj * bk if causal else 0,
                       seq % bk != 0 and kj == nk - 1)
                assert tile.any() == (key in keys), (qi, kj)
                if not tile.any():
                    continue
                want["tiles"] += 1
                for a, line in enumerate(keys[key][0]):
                    for b, terms in enumerate(line):
                        part = tile[a * sq:(a + 1) * sq,
                                    b * sk:(b + 1) * sk]
                        where = (qi, kj, a, b)
                        assert (terms is None) == (not part.any()), where
                        assert (terms == ()) == part.all(), where
                        want["unmasked" if part.all() else
                             "masked" if part.any() else "skipped"] += 1
        want["ratio"] = (want["unmasked"] + want["masked"]) * sq * sk \
            / seen[:seq].sum()
        return want

    want = {sub: count(sub) for sub in set(fa._SUB.values())}
    for sub, n in want.items():
        assert fa.band_tiles(seq, seq, bq, bk, sub, causal, window) \
            == pytest.approx(n)
        assert n["ratio"] >= 1.0

    if d != 8:
        return      # the record is the wrapper's, whatever the kernel's extras
    x = jnp.zeros((1, heads, seq, d), jnp.float32)
    kvx = jnp.zeros((1, kv, seq, d), jnp.float32)
    since = time.perf_counter()
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, kvx, kvx, causal=causal, window=window, block_q=given[0],
        block_k=given[1])))(x)
    records = [r["attrs"] for r in telemetry.span_records(since)
               if r["name"] == "flash.band"]
    # query blocks of whole lane tiles: one backward kernel makes dq too
    fused = bq % 128 == 0
    assert sorted(r["kernel"] for r in records) == sorted(
        k for k in fa._SUB if k != "flash_bwd_dq" or not fused)
    for r in records:
        if r["kernel"] == "flash_bwd_dkv":
            assert r["dq"] == fused
        sub = fa._SUB[r["kernel"]]
        assert {n: r[n] for n in want[sub]} == pytest.approx(want[sub])
        assert (r["seq"], r["bq"], r["bk"], r["sub"], r["window"]) == (
            seq, bq, bk, sub, window or 0)


def test_flash_kernel_refuses_what_it_cannot_mean():
    from mxnet_tpu.ops.pallas import flash_attention

    q = jnp.zeros((1, 6, 16, 8))
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, jnp.zeros((1, 4, 16, 8)), jnp.zeros((1, 4, 16, 8)))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4)


# -- the whole tiny model through fit ---------------------------------------------

def _batches(rng, batch, steps=3):
    ids = rng.randint(0, V, (steps * batch, T)).astype(np.int32)
    labels = rng.randint(0, V, (steps * batch, T)).astype(np.int32)
    return ids, labels


def _fit(symbol, params, ids, labels, batch, lr, compute_dtype=None):
    metric = mx.metric.CrossEntropy()
    model = mx.FeedForward(
        symbol, ctx=mx.cpu(), num_epoch=1, optimizer="sgd",
        learning_rate=lr, compute_dtype=compute_dtype,
        arg_params={k: mx.nd.array(v) for k, v in params.items()})
    model.fit(mx.io.NDArrayIter(ids, labels, batch_size=batch),
              eval_metric=metric, batch_size=batch)
    return model, metric.get()[1]


def _reference_steps(params, ids, labels, batch, lr):
    p, losses, first = dict(params), [], None
    for s in range(len(ids) // batch):
        rows = slice(s * batch, (s + 1) * batch)
        loss, grads = REF.loss_and_grads(p, ids[rows], labels[rows], TINY)
        losses.append(float(loss) / (batch * T))
        first = first or {k: np.asarray(g) / batch for k, g in grads.items()}
        p = {k: np.asarray(p[k] - lr * grads[k] / batch) for k in p}
    return p, losses, first


@pytest.fixture(scope="module")
def three_steps():
    batch, lr = 2, 0.01
    symbol = tiny_model()
    start = {k: v.asnumpy() for k, v in
             seeded_params(symbol, batch).arg_params.items()}
    ids, labels = _batches(np.random.RandomState(7), batch)
    want, losses, first = _reference_steps(start, ids, labels, batch, lr)
    return dict(batch=batch, lr=lr, symbol=symbol, start=start, ids=ids,
                labels=labels, want=want, losses=losses, first=first)


def test_tiny_model_three_steps_of_fit_follow_the_reference(three_steps):
    """float32: the epoch's loss, the first step's gradient norm and every
    leaf's change over three steps against ``loss_and_grads``."""
    s = three_steps
    model, loss = _fit(s["symbol"], s["start"], s["ids"], s["labels"],
                       s["batch"], s["lr"])
    assert loss == pytest.approx(np.mean(s["losses"]), rel=1e-5)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert set(got) == set(s["want"])
    worst = max(rel(got[k] - s["start"][k], s["want"][k] - s["start"][k])
                for k in got)
    assert worst < 1e-4, worst
    # one step alone: its parameter change over the rate is the gradient
    one, _ = _fit(s["symbol"], s["start"], s["ids"][:s["batch"]],
                  s["labels"][:s["batch"]], s["batch"], s["lr"])
    grads = {k: (s["start"][k] - v.asnumpy()) / s["lr"]
             for k, v in one.arg_params.items()}
    norm = np.sqrt(sum(np.sum(np.square(g, dtype=np.float64))
                       for g in grads.values()))
    want = np.sqrt(sum(np.sum(np.square(g, dtype=np.float64))
                       for g in s["first"].values()))
    assert norm == pytest.approx(want, rel=1e-4)
    # the experts' load was counted on the way: 3 steps of batch x T rows
    for l in (1, 2, 3):
        load = model.aux_params[f"layer{l}_moe_expert_load"].asnumpy()
        assert load.sum() == 3 * s["batch"] * T * 4


def test_tiny_model_in_bfloat16_stays_inside_its_band(three_steps):
    """bfloat16 compute, float32 master weights. The band: bf16 keeps 8
    bits (2^-9 an operand); through four layers the three steps' loss
    agrees with the float32 reference to 2 %, and the FIRST step's
    parameter change to 12 % of its norm where every row takes every expert
    (reading 5 %: the arithmetic's own band). Two things widen it here and
    not at the published widths, so they are kept out of the band: with
    top-4 of 16 at a hidden size of 64 a pick that flips at the boundary
    under bf16 rounding moves a whole expert in a batch of 64 rows (the
    first step then reads 21 % off; with the routing held fixed the expert
    layer alone agrees to 0.7 %), and steps of 0.01 on a summed loss make
    the tiny model's path sensitive (three steps read 43 % where one reads
    5 %). On the chip the logits of the trained weights agree with the
    float32 reference to 0.2-0.4 % (PERF.md)."""
    s = three_steps
    _, loss = _fit(s["symbol"], s["start"], s["ids"], s["labels"],
                   s["batch"], s["lr"], compute_dtype=jnp.bfloat16)
    assert loss == pytest.approx(np.mean(s["losses"]), rel=2e-2)

    every = dict(TINY, num_experts_per_tok=16)
    rows = slice(0, s["batch"])
    _, g = REF.loss_and_grads(s["start"], s["ids"][rows], s["labels"][rows],
                              every)
    model, _ = _fit(tiny_model(num_experts_per_tok=16), s["start"],
                    s["ids"][rows], s["labels"][rows], s["batch"], s["lr"],
                    compute_dtype=jnp.bfloat16)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert all(v.dtype == np.float32 for v in got.values())
    total = rel(
        np.concatenate([(got[k] - s["start"][k]).ravel()
                        for k in sorted(got)]),
        np.concatenate([-s["lr"] * np.asarray(g[k]).ravel() / s["batch"]
                        for k in sorted(got)]))
    assert 1e-4 < total < 0.12, total


def test_a_cut_rank_trains_everything_but_its_router():
    """8 of 16 experts held from the fifth on: ``laguna`` then builds the
    expert layers with ``train_router=False``. Three steps of ``fit``
    follow the reference given the same share and the same rule; the
    routers come back as they were drawn, every other leaf moves."""
    batch, lr = 2, 0.01
    symbol = tiny_model(experts_held=8, first_expert=4)
    assert '"train_router": false' in symbol.tojson()
    assert '"train_router": true' in tiny_model().tojson()
    start = {k: v.asnumpy() for k, v in
             seeded_params(symbol, batch).arg_params.items()}
    assert start["layer1_moe_gate_weight"].shape == (8, 32, 64)
    assert start["layer1_moe_router_weight"].shape == (16, 64)
    ids, labels = _batches(np.random.RandomState(12), batch)
    cut = dict(TINY, num_experts=8, first_expert=4, train_router=False)
    p = dict(start)
    for i in range(3):
        rows = slice(i * batch, (i + 1) * batch)
        _, g = REF.loss_and_grads(p, ids[rows], labels[rows], cut)
        p = {k: np.asarray(p[k] - lr * g[k] / batch) for k in p}
    model, _ = _fit(symbol, start, ids, labels, batch, lr)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    for k in got:
        if k.endswith("router_weight"):
            assert np.array_equal(got[k], start[k]), k
            assert np.array_equal(p[k], start[k]), k
        else:
            assert rel(got[k] - start[k], p[k] - start[k]) < 1e-4, k


def test_predict_returns_every_position_of_every_sequence():
    """``predict`` cuts an output to the batch's valid ROWS: logits of
    (rows x positions, classes) keep every position, also in a padded
    last batch."""
    batch = 2
    symbol = tiny_model()
    model = seeded_params(symbol, batch)
    ids = np.random.RandomState(8).randint(0, V, (5, T)).astype(np.int32)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    want = np.asarray(REF.logits(params, {}, ids, TINY))
    head = symbol.get_internals()["head_output"]
    served = mx.FeedForward(head, ctx=mx.cpu(), arg_params=model.arg_params,
                            aux_params=model.aux_params)
    got = served.predict(ids[:4], batch_size=batch)
    assert got.shape == (4 * T, V)
    assert rel(got, want[:4 * T]) < 1e-5
    # five sequences in batches of two: the last batch is padded by one
    # row, and its one valid sequence comes back whole
    got = served.predict(ids, batch_size=batch)
    assert got.shape == (5 * T, V)
    assert rel(got, want) < 1e-5


class _IdIter(mx.io.DataIter):
    """Batches of int32 ids as a token feeder hands them over
    (``NDArrayIter`` makes float32 of everything)."""

    def __init__(self, ids, labels, batch):
        super().__init__()
        self.ids, self.labels, self.batch_size = ids, labels, batch
        self.cursor = 0

    def reset(self):
        self.cursor = 0

    def next(self):
        if self.cursor >= len(self.ids):
            raise StopIteration
        rows = slice(self.cursor, self.cursor + self.batch_size)
        self.cursor += self.batch_size
        return mx.io.DataBatch(
            [mx.nd.NDArray(jnp.asarray(self.ids[rows], jnp.int32))],
            [mx.nd.NDArray(jnp.asarray(self.labels[rows], jnp.float32))])

    @property
    def provide_data(self):
        return [("data", (self.batch_size,))]

    @property
    def provide_label(self):
        return [("softmax_label", (self.batch_size,))]


def test_integer_inputs_are_not_rounded_to_the_compute_type():
    """Two ``fit`` steps of Embedding -> FullyConnected under bfloat16 on
    int32 ids above 4,096 give the float32 run's loss to bfloat16
    rounding. Cast to bfloat16, as the train step did with every data
    input, the ids are other ids (4,097 is 4,096): the loss then is far
    off."""
    vocab, width, batch = 8192, 8, 16
    sym = mx.symbol
    net = sym.SoftmaxOutput(
        data=sym.FullyConnected(
            data=sym.Embedding(data=sym.Variable("data"), input_dim=vocab,
                               output_dim=width, name="embed"),
            num_hidden=4, name="fc"), name="softmax")
    rng = np.random.RandomState(9)
    ids = (4097 + 2 * rng.randint(0, 2000, (2 * batch,))).astype(np.int32)
    rounded = np.asarray(jnp.asarray(ids).astype(jnp.bfloat16)
                         .astype(jnp.int32))
    assert (rounded != ids).all()
    # the class is the id's own, through a table only its row knows
    labels = ((ids // 2) % 4).astype(np.float32)
    table = rng.randn(vocab, width).astype(np.float32)
    start = {"embed_weight": table,
             "fc_weight": rng.randn(4, width).astype(np.float32),
             "fc_bias": np.zeros(4, np.float32)}

    def loss_of(data, compute_dtype):
        metric = mx.metric.CrossEntropy()
        model = mx.FeedForward(
            net, ctx=mx.cpu(), num_epoch=1, optimizer="sgd",
            learning_rate=0.05, compute_dtype=compute_dtype,
            arg_params={k: mx.nd.array(v) for k, v in start.items()})
        model.fit(_IdIter(data, labels, batch), eval_metric=metric,
                  batch_size=batch)
        return metric.get()[1]

    exact = loss_of(ids, None)
    assert loss_of(ids, jnp.bfloat16) == pytest.approx(exact, rel=2e-2)
    # what the train step computed before the repair: other rows of the
    # table, another loss
    assert abs(loss_of(rounded, None) - exact) > 0.04 * exact


# -- what the model needed of the rest of the program ----------------------------

def test_initializer_rules_for_the_decoder():
    init = mx.init.Xavier()
    mx.random.seed(1)
    stacked = mx.nd.zeros((32, 24, 200))
    init("layer1_moe_gate_weight", stacked)
    one = mx.nd.zeros((24, 200))
    init("layer1_q_weight", one)
    # each expert by its own fan: the bound of a (24, 200) matrix, not of
    # a (32, 4800) one
    bound = np.sqrt(3.0 / ((24 + 200) / 2))
    a = stacked.asnumpy()
    assert 0.9 * bound < np.abs(a).max() <= bound
    assert np.abs(one.asnumpy()).max() <= bound
    assert a.std() == pytest.approx(bound / np.sqrt(3), rel=0.05)
    gamma, load = mx.nd.zeros((8,)), mx.nd.array(np.ones(8, np.float32))
    init("layer0_attn_norm_gamma", gamma)
    init("layer1_moe_expert_load", load)
    assert (gamma.asnumpy() == 1).all() and (load.asnumpy() == 0).all()


def test_the_symbol_carries_its_recomputation_boundaries():
    """``RematBoundary`` closes a segment of the executor without any
    environment variable, survives the JSON round trip, and changes no
    result."""
    from mxnet_tpu.executor import _build_graph_fn, _remat_segments

    assert not os.environ.get("MXNET_TPU_REMAT")
    marked, plain = tiny_model(), tiny_model(remat=False)
    segments = _remat_segments(marked._topo())
    blocks = [s for s in segments if s[0] == "blk"]
    assert [b[1][-1][1].name for b in blocks] == [
        f"layer{l}_out" for l in range(4)]
    assert _remat_segments(plain._topo()) is None
    again = mx.symbol.load_json(marked.tojson())
    assert len([s for s in _remat_segments(again._topo())
                if s[0] == "blk"]) == 4
    assert marked.list_arguments() == plain.list_arguments()

    model = seeded_params(marked, 2)
    args = {k: v.data for k, v in model.arg_params.items()}
    aux = {k: v.data for k, v in model.aux_params.items()}
    rng = np.random.RandomState(10)
    batch = {"data": jnp.asarray(rng.randint(0, V, (2, T)), jnp.int32),
             "softmax_label": jnp.asarray(rng.randint(0, V, (2, T)),
                                          jnp.int32)}
    key = jnp.zeros((2,), jnp.uint32)

    def grads(symbol):
        fn = _build_graph_fn(symbol, is_train=True)

        def loss(p):
            outs, new_aux = fn({**p, **batch}, aux, key)
            return jnp.sum(outs[0]), new_aux

        return jax.grad(loss, has_aux=True)(args)

    (g1, aux1), (g2, aux2) = grads(marked), grads(plain)
    assert max(rel(g1[k], g2[k]) for k in g1) < 1e-5
    assert all(np.array_equal(aux1[k], aux2[k]) for k in aux1)


def test_fit_emits_one_expert_load_record_a_node_and_epoch():
    """Held 8 of 16 experts from the fifth on: after each epoch's
    write-back one ``fit.epoch.expert_load`` record a node with THAT
    epoch's counts; a model without an expert node emits none."""
    from mxnet_tpu import telemetry

    batch = 2
    symbol = tiny_model(experts_held=8, first_expert=4)
    ids, labels = _batches(np.random.RandomState(11), batch)
    mx.random.seed(5)
    mark = len(telemetry.span_records())
    model = mx.FeedForward(symbol, ctx=mx.cpu(), num_epoch=2,
                           optimizer="sgd", learning_rate=0.01,
                           initializer=mx.init.Xavier())
    model.fit(mx.io.NDArrayIter(ids, labels, batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    records = [r for r in telemetry.span_records()[mark:]
               if r["name"] == "fit.epoch.expert_load"]
    assert [(r["epoch"], r["attrs"]["node"]) for r in records] == [
        (e, f"layer{l}_moe") for e in (0, 1) for l in (1, 2, 3)]
    tokens = 3 * batch * T
    for r in records:
        a = r["attrs"]
        assert a["tokens"] == tokens and a["picks_all"] == 4 * tokens
        assert a["experts_held"] == 8
        # the sorted space of the epoch's steps, and the kernels' tile: a
        # step's whole space here, so one tile a step whatever the load
        assert a["space"] == 4 * tokens and a["tile"] == 4 * batch * T
        assert 0 < a["picks_held"] < a["picks_all"]
        assert a["picks_held"] / 8 <= a["max_held"] <= a["picks_held"]
        assert a["picks_held"] / a["max_held"] <= a["experts_hit"] <= 8
    total = model.aux_params["layer1_moe_expert_load"].asnumpy()
    assert total.sum() == 2 * 4 * tokens        # the state accumulates
    held = [r["attrs"]["picks_held"] for r in records
            if r["attrs"]["node"] == "layer1_moe"]
    assert sum(held) == total[4:12].sum()       # the records difference it

    mark = len(telemetry.span_records())
    plain = mx.FeedForward(mx.models.mlp(), ctx=mx.cpu(), num_epoch=1,
                           initializer=mx.init.Xavier())
    plain.fit(np.random.RandomState(0).randn(8, 784).astype(np.float32),
              np.zeros(8, np.float32), batch_size=4)
    assert not [r for r in telemetry.span_records()[mark:]
                if r["name"] == "fit.epoch.expert_load"]


# two epochs of Adam on the tiny model from one seed, as the tree before
# PR 34 computed them (sha256 over the written-back arg_params, names in
# order, each with its shape and bytes): pinned BEFORE the train step's
# state moved into the order its program reads it in, which is no rounding
ADAM_TWO_EPOCHS = \
    "964c964ddd83b870e77e61caa01d64b3f0704602bf6bcfc438ae09d4b8fc0c92"


def _adam_two_epochs(precompile=False):
    """``(model, digest, fit.start's attrs)`` of the pinned run."""
    import hashlib

    from mxnet_tpu import telemetry

    batch = 2
    symbol = tiny_model(experts_held=8, first_expert=4)
    ids, labels = _batches(np.random.RandomState(11), batch)
    mx.random.seed(5)
    model = mx.FeedForward(symbol, ctx=mx.cpu(), num_epoch=2,
                           optimizer="adam", learning_rate=1e-3,
                           initializer=mx.init.Xavier())
    metric = mx.metric.CrossEntropy()
    if precompile:          # NDArrayIter hands the ids over as float32
        model.precompile(data_shapes={"data": (batch, T)},
                         label_shapes={"softmax_label": (batch, T)},
                         eval_metric=metric)
    mark = len(telemetry.span_records())
    model.fit(mx.io.NDArrayIter(ids, labels, batch_size=batch),
              eval_metric=metric, batch_size=batch)
    start = [r for r in telemetry.span_records()[mark:]
             if r["name"] == "fit.start"]
    h = hashlib.sha256()
    for k in sorted(model.arg_params):
        a = model.arg_params[k].asnumpy()
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return model, h.hexdigest(), start[0]["attrs"]


@pytest.mark.parametrize("precompile", [False, True],
                         ids=["compiled_at_the_first_step", "precompiled"])
def test_two_epochs_of_adam_are_bit_equal_to_the_declared_order(precompile):
    """The order a leaf is stored in is no rounding: with the stacked
    expert weights and their moments kept on the device as the grouped
    product reads them, ``fit`` writes back what the tree before PR 34
    wrote, in the declared shapes, from one train program; ``fit.start``
    says how many leaves live in another order than declared, and their
    bytes."""
    model, digest, attrs = _adam_two_epochs(precompile)
    assert digest == ADAM_TWO_EPOCHS
    shapes, _, _ = model.symbol.infer_shape(data=(2, T),
                                            softmax_label=(2, T))
    for name, shape in zip(model.symbol.list_arguments(), shapes):
        if name in model.arg_params:
            a = model.arg_params[name].asnumpy()
            assert a.shape == tuple(shape) and a.flags.c_contiguous, name
    stored = sorted(k for k in model.arg_params
                    if k.endswith(("moe_gate_weight", "moe_up_weight")))
    assert model._state_order() == dict.fromkeys(stored, (0, 2, 1))
    assert len(stored) == 6             # three sparse layers, two each
    # each weight and its two moments, float32
    assert (attrs["state_leaves_relaid"], attrs["state_bytes_relaid"]) == (
        18, 3 * sum(model.arg_params[k].asnumpy().nbytes for k in stored))
    (run,) = model._train_fns.values()
    tracked = run._tracked
    assert tracked.aot_programs + tracked._cache_size() == 1


def test_a_state_leaf_in_its_declared_shape_is_refused_by_the_step():
    """``fit``'s step holds the stacked leaves in their stored order: one
    handed over as declared raises (also where the two shapes are the
    same length, which a program could not tell apart), and no second
    train program is compiled for it."""
    model, _, _ = _adam_two_epochs(precompile=True)
    (run,) = model._train_fns.values()
    order = model._state_order()
    names = [k for k in model.symbol.list_arguments()
             if k in model.arg_params]
    optimizer = model._resolve_optimizer(names, 2)
    ids, labels = _batches(np.random.RandomState(11), 2)

    def step(params):
        return run(params, optimizer.init_state_tree(params),
                   {k: v.asnumpy() for k, v in model.aux_params.items()},
                   {"data": ids[:2].astype(np.float32),
                    "softmax_label": labels[:2].astype(np.float32)},
                   mx.random.next_key(), 1e-3,
                   mx.metric.CrossEntropy().device_init())

    declared = {k: jnp.asarray(model.arg_params[k].asnumpy())
                for k in names}
    stored = {k: v.transpose(order[k]) if k in order else v
              for k, v in declared.items()}
    out = step(stored)
    assert all(out[0][k].shape == stored[k].shape for k in names)
    with pytest.raises(mx.MXNetError, match="stored order"):
        step(declared)
    assert run._tracked.aot_programs == 1
    assert run._tracked._cache_size() == 0


def test_stored_order_follows_the_operators_of_the_symbol():
    """``_stored_order`` reads what the operators declare: a model with no
    such operator stores nothing otherwise, a variable two nodes share is
    stored once, and one they want in different orders stays as
    declared."""
    from mxnet_tpu import model as model_mod

    assert model_mod._stored_order(mx.models.mlp()) == {}
    shared = mx.sym.Variable("shared")
    moe = dict(num_experts=4, experts_held=4, top_k=2, expert_width=8,
               gate_weight=shared)
    symbol = mx.sym.MixtureOfExperts(
        data=mx.sym.MixtureOfExperts(data=mx.sym.Variable("data"),
                                     name="a", **moe), name="b", **moe)
    stacked = {"shared", "a_up_weight", "b_up_weight"}
    assert model_mod._stored_order(symbol) == dict.fromkeys(stacked,
                                                            (0, 2, 1))
    last = [n for n in symbol._topo() if n.name == "b"][0]
    last.op.argument_major_to_minor = lambda: {
        "gate_weight": (1, 0, 2), "up_weight": (0, 2, 1)}
    assert model_mod._stored_order(symbol) == dict.fromkeys(
        stacked - {"shared"}, (0, 2, 1))


def test_fit_asks_any_operator_with_an_epoch_record(monkeypatch):
    """``fit`` knows no operator by name: whatever operator defines
    ``epoch_record`` gets its auxiliary states, before and after, once an
    epoch and a node, and its line goes out under the name it returns."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.decoder import RMSNormOp

    monkeypatch.setattr(
        RMSNormOp, "epoch_record",
        lambda self, before, after: ("test.epoch.norm", {
            "states": len(before) + len(after), "eps": self.eps}),
        raising=False)
    batch = 2
    ids, labels = _batches(np.random.RandomState(3), batch)
    mark = len(telemetry.span_records())
    model = mx.FeedForward(tiny_model(), ctx=mx.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.01,
                           initializer=mx.init.Xavier())
    model.fit(mx.io.NDArrayIter(ids, labels, batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    records = [r for r in telemetry.span_records()[mark:]
               if r["name"] == "test.epoch.norm"]
    norms = [n.name for n in model.symbol._topo()
             if not n.is_variable and n.op.name == "RMSNorm"]
    assert [r["attrs"]["node"] for r in records] == norms and norms
    assert all(r["attrs"]["states"] == 0 and r["epoch"] == 0
               for r in records)


@pytest.mark.parametrize("start", [0, (1 << 23) - 5])
def test_the_load_count_is_exact_across_its_wrap(start):
    """The count is kept modulo ``LOAD_WRAP`` in float32, so the picks of
    an epoch come out exact by differencing whatever was counted before."""
    op = _moe(held=4, first=2, experts=8, top_k=2)
    rng = np.random.RandomState(17)
    ins = [jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
           for shape in op.infer_shape([(6, 16)] + [None] * 7)[0]]
    before = np.full((8,), start, np.float32)
    load = jnp.asarray(before)
    for _ in range(3):
        _, (load,) = op.fwd(ins, [load], True, None)
    after = np.asarray(load)
    assert after.dtype == np.float32 and after.max() < op.LOAD_WRAP
    name, attrs = op.epoch_record([before], [after])
    experts, _ = op.route(ins[0], ins[1])
    counts = 3 * np.bincount(np.asarray(experts).reshape(-1), minlength=8)
    assert name == "fit.epoch.expert_load"
    assert attrs["picks_all"] == 3 * 6 * 2 == counts.sum()
    assert attrs["tokens"] == 3 * 6
    assert attrs["picks_held"] == counts[2:6].sum()
    assert attrs["max_held"] == counts[2:6].max()
    assert attrs["experts_hit"] == np.count_nonzero(counts[2:6])
    assert attrs["experts_held"] == 4
    assert attrs["space"] == 3 * 6 * 2 and attrs["tile"] == 6 * 2
