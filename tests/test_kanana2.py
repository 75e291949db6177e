"""Latent attention and a router that selects by a load-driven bias, through
the normal path: the flash kernels with keys and values of different
widths against dense attention, ``LatentAttention`` and
``MixtureOfExperts(selection="biased")`` against the plain reference
(``benchmark/configs/kanana2_30b_a3b.py``, which imports nothing from the
program), the bias's update and its write-back by ``fit``, the tiny
``kanana2`` through ``FeedForward.fit``, and the share of an
expert-parallel deployment.

Tiny sizes, seeded weights, float32.
"""

import importlib.util
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import OPS
from mxnet_tpu.ops.pallas import flash_attention
from mxnet_tpu.ops.pallas.flash_attention import rotary_tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kanana2_reference",
        os.path.join(ROOT, "benchmark", "configs", "kanana2_30b_a3b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
T, V = 32, 96
# 3 layers (one dense, two sparse), 16 experts top-4, 4 heads of 16 + 8
# (keys) and 16 (values) over a latent of 32
TINY = dict(
    num_hidden_layers=3, vocab_size=V, n_routed_experts=16, first_expert=0,
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, rope_theta=1e6,
    rms_norm_eps=1e-6, first_k_dense_replace=1, num_experts_per_tok=4,
    routed_scaling_factor=2.448)
RATE = 0.001


def tiny_model(experts_held=16, first_expert=0, **over):
    sizes = {k: v for k, v in dict(TINY, **over).items()
             if k not in ("num_hidden_layers", "vocab_size",
                          "n_routed_experts", "first_expert")}
    return mx.models.kanana2(
        seq_len=T, layers=3, vocab_rows=V, experts_held=experts_held,
        first_expert=first_expert, n_routed_experts=16,
        intermediate_size=96, moe_intermediate_size=32, n_shared_experts=2,
        **sizes)


def seeded_params(symbol, batch, seed=3):
    mx.random.seed(seed)
    model = mx.FeedForward(symbol, ctx=mx.cpu(),
                           initializer=mx.init.Xavier())
    model._init_params({"data": (batch, T), "softmax_label": (batch, T)})
    return model


def drawn_bias(symbol, seed=8):
    """Every selection bias drawn in [-0.05, 0.05]: held, not zero."""
    rng = np.random.RandomState(seed)
    return {n: (rng.rand(16) * 0.1 - 0.05).astype(np.float32)
            for n in symbol.list_auxiliary_states()
            if n.endswith("selection_bias")}


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# -- the flash kernels at keys and values of different widths --------------------

def _dense(q, k, v):
    """q, k (batch, heads, T, dq); v (batch, heads, T, dv): causal."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("dq,dv,heads_last", [
    (24, 16, False), (192, 128, True)])
def test_flash_kernels_take_keys_and_values_of_different_widths(
        dq, dv, heads_last):
    """Interpret mode: the output (``dv`` wide) and the three gradients
    against dense attention; the scores are scaled by the keys' width."""
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(1, 3, 48, dq), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 3, 48, dv), jnp.float32)
    w = jnp.asarray(rng.randn(1, 3, 48, dv), jnp.float32)

    def ours(q, k, v):
        def lay(x):
            return x.transpose(0, 2, 1, 3) if heads_last else x
        return lay(flash_attention(lay(q), lay(k), lay(v), causal=True,
                                   block_q=16, block_k=16,
                                   heads_last=heads_last))

    assert ours(q, k, v).shape == (1, 3, 48, dv)
    assert rel(ours(q, k, v), _dense(q, k, v)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, h in zip(got, want):
        assert g.shape == h.shape and rel(g, h) < 1e-5
    with pytest.raises(ValueError, match="keys"):
        flash_attention(q, k[..., :8], v, causal=True)


def test_equal_widths_are_what_they_were():
    """The path every accepted decoder takes: the default rotary tables
    are the rotate-half tables written out, and the kernels' registered
    costs at one width are the former ``4 / 6 / 8 x bh sq sk d``."""
    from mxnet_tpu.ops.pallas.registry import kernel_cost

    inv = 1e4 ** (-np.arange(4) / 4.0)
    cos, sin, rot = rotary_tables(5, 12, inv, 1.5)
    angle = np.arange(5)[:, None] * inv.astype(np.float32)[None, :]
    c, s = np.cos(angle) * 1.5, np.sin(angle) * 1.5
    assert np.allclose(cos, np.concatenate([c, c, np.ones((5, 4))], 1))
    assert np.allclose(sin, np.concatenate([s, s, np.zeros((5, 4))], 1))
    want = np.zeros((12, 12))
    want[np.arange(4) + 4, np.arange(4)] = -1
    want[np.arange(4), np.arange(4) + 4] = 1
    assert np.array_equal(rot, want)
    a = [jax.ShapeDtypeStruct((6, 256, 64), jnp.bfloat16)] * 3
    for name, factor in (("flash_fwd", 4), ("flash_bwd_dq", 6),
                         ("flash_bwd_dkv", 8)):
        assert kernel_cost(name, a, []).flops == factor * 6 * 256 * 256 * 64
    # flash_bwd_dkv making dq too (a third result): the scores once, 10
    # where the two kernels are 14
    assert kernel_cost("flash_bwd_dkv", a, a).flops \
        == 10 * 6 * 256 * 256 * 64
    wide = [a[0], a[0], jax.ShapeDtypeStruct((6, 256, 32), jnp.bfloat16)]
    assert kernel_cost("flash_fwd", wide, []).flops == 2 * 6 * 256 * 256 * 96


def test_interleaved_tables_rotate_the_last_part_in_adjacent_pairs():
    from mxnet_tpu.ops.decoder import rotary_inv_freq, rotate_heads

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 7, 3, 24), jnp.float32)
    inv = rotary_inv_freq(8, 1e6)
    got = rotate_heads(x, rotary_tables(7, 24, inv, interleave=True,
                                        offset=16))
    assert np.array_equal(got[..., :16], x[..., :16])
    want = REF.rotate_interleaved(x[..., 16:], 1e6)
    assert rel(got[..., 16:], want) < 1e-6
    # a pair's two entries turn together, and position 0 not at all
    assert rel(got[:, 0], x[:, 0]) < 1e-7


# -- LatentAttention ------------------------------------------------------------

def _latent_inputs(rng, rows=2 * T):
    """The projections the model computes, drawn: the layer's weights, its
    normalised input, and the three inputs of the operator made from them
    as the builder makes them."""
    h, n, r, v, c = 4, 16, 8, 16, 32
    p = {"L_q_weight": rng.randn(h * (n + r), 64) * 0.3,
         "L_kv_a_weight": rng.randn(c + r, 64) * 0.3,
         "L_kv_norm_gamma": 1 + 0.1 * rng.randn(c),
         "L_kv_b_weight": rng.randn(h * (n + v), c) * 0.3}
    p = {k: jnp.asarray(a, jnp.float32) for k, a in p.items()}
    x = jnp.asarray(rng.randn(rows, 64), jnp.float32)
    compressed = x @ p["L_kv_a_weight"].T
    latent = REF.rms_norm(compressed[:, :c], p["L_kv_norm_gamma"], 1e-6)
    return p, x, (x @ p["L_q_weight"].T, latent @ p["L_kv_b_weight"].T,
                  compressed[:, c:])


def test_latent_attention_matches_the_reference_layer():
    """Two sequences; the output and the gradients of all three inputs
    against the reference's layer, whose rotary key is ONE a position:
    its gradient is every head's, summed."""
    rng = np.random.RandomState(3)
    p, x, ins = _latent_inputs(rng)
    op = OPS.create("LatentAttention", seq_len=T, num_heads=4,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    rope_theta=1e6)
    assert op.infer_shape([(2 * T, 96), None, None]) == (
        [(2 * T, 96), (2 * T, 128), (2 * T, 8)], [(2 * T, 64)], [])

    def ours(q, kv, k_pe):
        return op.fwd([q, kv, k_pe], [], True, None)[0][0]

    def theirs(q, kv, k_pe):
        # the reference's layer past its projections, from the arrays
        # handed to the operator
        batch = q.shape[0] // T
        qh = q.reshape(batch, T, 4, 24)
        qh = jnp.concatenate([qh[..., :16], REF.rotate_interleaved(
            qh[..., 16:], 1e6)], -1)
        k_r = REF.rotate_interleaved(k_pe.reshape(batch, T, 1, 8), 1e6)
        kvh = kv.reshape(batch, T, 4, 32)
        k = jnp.concatenate([kvh[..., :16], jnp.broadcast_to(
            k_r, (batch, T, 4, 8))], -1)
        return REF.attention(qh, k, kvh[..., 16:]).reshape(batch * T, 64)

    # the reference's own layer, from the normalised input
    whole = REF.latent_attention(x, {k: v for k, v in p.items()}, "L_",
                                 TINY, 2, T)
    assert rel(ours(*ins), whole) < 1e-5
    assert rel(theirs(*ins), whole) < 1e-6
    w = jnp.asarray(rng.randn(2 * T, 64), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2))(*ins)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * w), (0, 1, 2))(*ins)
    for g, h in zip(got, want):
        assert rel(g, h) < 1e-5
    # every head reads the one rotary key: moving it moves every head
    moved = ours(ins[0], ins[1], ins[2] + 1.0).reshape(2 * T, 4, 16) \
        - ours(*ins).reshape(2 * T, 4, 16)
    assert np.all(np.abs(np.asarray(moved[T // 2:])).max(axis=(0, 2)) > 1e-3)


def test_latent_kernels_read_the_rows_as_projected():
    """Interpret mode, the published head widths (``[q_nope 128 | q_pe
    64]``, ``[k_nope 128 | v 128]``, one rotary key of 64) at a small
    length: 40 positions in blocks of 16 (the last key block padded), two
    sequences, four heads, so that a head starting on a lane tile (even)
    and one 64 columns into one (odd) are both read and written. The
    output, the gradient by ``query`` in its layout, by ``key_value`` in
    its layout and by the one rotary key, summed over the heads, against
    dense float32 attention; the ``flash.band`` record of every latent call
    says the shared rotary key is there, a ``RotaryAttention`` call's
    says it is not."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.decoder import rotary_inv_freq, rotate_heads

    b, t, h, n, r, dv = 2, 40, 4, 128, 64, 128
    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(b, t, h, n + r), jnp.float32)
    kv = jnp.asarray(rng.randn(b, t, h, n + dv), jnp.float32)
    k_pe = jnp.asarray(rng.randn(b, t, r), jnp.float32)
    w = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    turn = rotary_tables(t, r, rotary_inv_freq(r, 1e6), interleave=True)

    def ours(q, kv, k_pe):
        return flash_attention(q, kv, None, causal=True, heads_last=True,
                               rotary=turn, key_rope=k_pe, block_q=16,
                               block_k=16)

    def dense(q, kv, k_pe):
        qh = jnp.concatenate([q[..., :n], rotate_heads(q[..., n:], turn)],
                             -1).transpose(0, 2, 1, 3)
        k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
            k_pe[:, :, None], (b, t, h, r))], -1).transpose(0, 2, 1, 3)
        return _dense(qh, k, kv[..., n:].transpose(0, 2, 1, 3)).transpose(
            0, 2, 1, 3)

    mark = len(telemetry.span_records())
    assert ours(q, kv, k_pe).shape == (b, t, h, dv)
    assert rel(ours(q, kv, k_pe), dense(q, kv, k_pe)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), (0, 1, 2))(q, kv, k_pe)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(
        q, kv, k_pe)
    for g, e in zip(got, want):
        assert g.shape == e.shape and rel(g, e) < 1e-5
    # the odd heads' halves of the pair blocks, each head's own gradient
    for head in range(h):
        assert rel(got[0][:, :, head], want[0][:, :, head]) < 1e-5
        assert rel(got[1][:, :, head], want[1][:, :, head]) < 1e-5
    bands = [rec["attrs"] for rec in telemetry.span_records()[mark:]
             if rec["name"] == "flash.band"]
    # blocks of 16 are part of a lane tile: dq is flash_bwd_dq's
    assert {a["kernel"] for a in bands} == {"flash_fwd", "flash_bwd_dq",
                                            "flash_bwd_dkv"}
    assert all(a["dq"] == 0 for a in bands if a["kernel"] == "flash_bwd_dkv")
    assert all((a["rope_dim"], a["rope_shared"]) == (64, 1) for a in bands)
    # the registry prices the calls at keys of 192 and values of 128, the
    # padded 48 positions
    from mxnet_tpu.ops.pallas.registry import attribute_eqn

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
                                 (0, 1, 2)))(q, kv, k_pe).jaxpr)
    unit = 2.0 * b * h * 48 * 48
    assert flops == {"flash_fwd": unit * (192 + 128),
                     "flash_bwd_dq": unit * (2 * 192 + 128),
                     "flash_bwd_dkv": unit * 2 * (192 + 128)}
    # a call without the shared key: 0 / 0
    mark = len(telemetry.span_records())
    op = OPS.create("RotaryAttention", seq_len=t, num_heads=2,
                    num_kv_heads=1, head_dim=128, rotary_dim=128)
    x = jnp.asarray(rng.randn(t, 256), jnp.float32)
    op.fwd([x, x[:, :128], x[:, 128:]], [], True, None)
    bands = [rec["attrs"] for rec in telemetry.span_records()[mark:]
             if rec["name"] == "flash.band"]
    assert bands and all((a["rope_dim"], a["rope_shared"]) == (0, 0)
                         for a in bands)
    with pytest.raises(ValueError, match="latent attention"):
        flash_attention(q[:, :, :3], kv[:, :, :3], None, causal=True,
                        heads_last=True, rotary=turn, key_rope=k_pe)
    with pytest.raises(ValueError, match="latent attention"):
        flash_attention(q, kv, None, causal=True, heads_last=True,
                        rotary=turn, key_rope=k_pe, window=8)


# -- the selection bias ---------------------------------------------------------

def _moe(held=16, first=0, selection="biased"):
    return OPS.create("MixtureOfExperts", num_experts=16, experts_held=held,
                      first_expert=first, top_k=4, expert_width=32,
                      scaling=2.448, shared_width=64, selection=selection)


def _moe_weights(rng, hidden=32):
    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)

    return [draw(16, hidden), draw(16, 32, hidden), draw(16, 32, hidden),
            draw(16, hidden, 32), draw(64, hidden), draw(64, hidden),
            draw(hidden, 64)]


def _moe_reference(x, ws, bias, held=16, first=0):
    names = ("router_weight", "gate_weight", "up_weight", "down_weight",
             "shared_gate_weight", "shared_up_weight", "shared_down_weight")
    p = dict(zip(names, ws))
    for n in names[1:4]:
        p[n] = p[n][first:first + held]
    return REF.sparse_ffn(x, p, "", jnp.asarray(bias), dict(
        TINY, n_routed_experts=held, first_expert=first))


def test_the_bias_selects_and_does_not_weigh():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    ws = _moe_weights(rng)
    op = _moe()
    assert op.list_auxiliary_states() == ["expert_load", "selection_bias"]
    assert _moe(selection="plain").list_auxiliary_states() == ["expert_load"]
    scores = jax.nn.sigmoid(x @ ws[0].T)
    # a bias that forces expert 11 into every row's picks, whatever its
    # score: its weight is its SCORE's share of the picked scores
    bias = jnp.zeros(16).at[11].set(10.0)
    experts, weights = op.route(x, ws[0], bias)
    assert np.all(np.any(np.asarray(experts) == 11, axis=1))
    picked = jnp.take_along_axis(scores, experts, axis=1)
    assert rel(weights, 2.448 * picked / picked.sum(1, keepdims=True)) < 1e-6
    # without a bias the picks are the scores' top 4, as before
    plain, plain_w = _moe(selection="plain").route(x, ws[0])
    top = np.sort(np.argsort(-np.asarray(scores), 1)[:, :4], 1)
    assert np.array_equal(np.sort(np.asarray(plain), 1), top)
    assert np.any(np.sort(np.asarray(experts), 1) != top)
    # the layer against the reference, with a drawn bias, and gradients
    bias = jnp.asarray(rng.rand(16) * 0.2 - 0.1, jnp.float32)
    aux = [jnp.zeros(16), bias]

    def ours(x, *ws):
        return op.fwd([x, *ws], aux, True, None)[0][0]

    assert rel(ours(x, *ws), _moe_reference(x, ws, bias)) < 1e-5
    w = jnp.asarray(rng.randn(40, 32), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), tuple(range(8)))(x, *ws)
    want = jax.grad(lambda x, *ws: jnp.sum(_moe_reference(x, ws, bias) * w),
                    tuple(range(8)))(x, *ws)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4


def test_the_sign_update_of_a_training_step():
    """After a training step ``b_e + rate * sign(mean - load_e)`` over the
    step's picks on all experts; nothing moves outside training."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(40, 32), jnp.float32)
    ws = _moe_weights(rng)
    bias = jnp.asarray(rng.rand(16) * 0.02, jnp.float32)
    op = _moe(held=2, first=6)            # a rank sees every expert's load
    held = [w[6:8] for w in ws[1:4]]
    aux = [jnp.full(16, 5.0), bias]
    _, (load, new) = op.fwd([x, ws[0], *held, *ws[4:]], aux, True, None)
    experts, _ = op.route(x, ws[0], bias)
    counts = np.bincount(np.asarray(experts).ravel(), minlength=16)
    assert np.array_equal(load, 5.0 + counts)
    assert np.allclose(new, bias + 0.001 * np.sign(40 * 4 / 16 - counts))
    assert np.any(counts > 10) and np.any(counts < 10)
    _, still = op.fwd([x, ws[0], *held, *ws[4:]], aux, False, None)
    assert np.array_equal(still[1], bias) and np.array_equal(still[0], aux[0])
    # the epoch's record carries where the bias stands
    _, attrs = op.epoch_record([np.zeros(16), bias], [load, new])
    assert attrs["bias_min"] == pytest.approx(float(np.min(new)))
    assert attrs["bias_max"] == pytest.approx(float(np.max(new)))
    _, plain = _moe(selection="plain").epoch_record([np.zeros(16)], [load])
    assert "bias_min" not in plain


def test_the_eight_shares_add_up():
    """16 experts cut 8 ways, as the cell's 128 are: the eight shares'
    outputs, with the shared expert (which every rank computes alike)
    counted once, add up to the uncut layer's, the bias as drawn."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(48, 32), jnp.float32)
    ws = _moe_weights(rng)
    bias = jnp.asarray(rng.rand(16) * 0.2 - 0.1, jnp.float32)
    aux = [jnp.zeros(16), bias]
    whole = _moe().fwd([x, *ws], aux, True, None)[0][0]
    shared = REF.gated_ffn(x, *ws[4:])
    parts = []
    for rank in range(8):
        held = [w[2 * rank:2 * rank + 2] for w in ws[1:4]]
        out = _moe(2, 2 * rank).fwd([x, ws[0], *held, *ws[4:]], aux, True,
                                    None)[0][0]
        assert rel(out, _moe_reference(x, ws, bias, 2, 2 * rank)) < 1e-5
        parts.append(out - shared)
    assert rel(sum(parts) + shared, whole) < 1e-5
    assert rel(whole, _moe_reference(x, ws, bias)) < 1e-5


# -- the whole tiny model through fit -------------------------------------------

def test_the_builder_defaults_are_the_published_sizes():
    """https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/
    blob/main/config.json, as the catalog row has it."""
    defaults = {k: p.default for k, p in
                inspect.signature(mx.models.kanana2).parameters.items()}
    assert defaults == {
        "seq_len": 8192, "layers": 48, "vocab_rows": 128256,
        "experts_held": 128, "first_expert": 0, "hidden_size": 2048,
        "intermediate_size": 6144, "num_attention_heads": 32,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "kv_lora_rank": 512, "n_routed_experts": 128,
        "num_experts_per_tok": 6, "moe_intermediate_size": 768,
        "n_shared_experts": 2, "routed_scaling_factor": 2.448,
        "first_k_dense_replace": 1, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6, "remat": True,
        "train_router": None}
    symbol = tiny_model()
    shapes = dict(zip(symbol.list_arguments(), symbol.infer_shape(
        data=(2, T), softmax_label=(2, T))[0]))
    # one tensor a published one, in its layout
    assert shapes["layer1_kv_a_weight"] == (32 + 8, 64)
    assert shapes["layer1_kv_b_weight"] == (4 * 32, 32)
    assert shapes["layer1_q_weight"] == (4 * 24, 64)
    assert shapes["layer1_o_weight"] == (64, 64)
    assert shapes["layer1_moe_shared_gate_weight"] == (64, 64)
    assert "layer0_moe_router_weight" not in shapes
    assert shapes["layer0_ffn_gate_weight"] == (96, 64)
    assert symbol.list_auxiliary_states() == [
        f"layer{l}_moe_{a}" for l in (1, 2)
        for a in ("expert_load", "selection_bias")]
    text = symbol.tojson()
    assert '"selection": "biased"' in text and '"train_router": true' in text
    assert '"train_router": false' in tiny_model(experts_held=2).tojson()


def test_the_selection_bias_starts_at_zero():
    model = seeded_params(tiny_model(), 2)
    for name in ("layer1_moe_selection_bias", "layer2_moe_selection_bias"):
        assert np.array_equal(model.aux_params[name].asnumpy(),
                              np.zeros(16, np.float32))


def test_tiny_model_logits_match_the_reference():
    symbol = tiny_model()
    model = seeded_params(symbol, 2)
    aux = drawn_bias(symbol)
    for name, b in aux.items():
        model.aux_params[name] = mx.nd.array(b)
    ids = np.random.RandomState(9).randint(0, V, (2, T)).astype(np.int32)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    want = np.asarray(REF.logits(params, aux, ids, TINY))
    assert want.shape == (2 * T, V)
    head = mx.symbol.Reshape(data=symbol.get_internals()["head_output"],
                             target_shape=(2, -1))
    served = mx.FeedForward(head, ctx=mx.cpu(), arg_params=model.arg_params,
                            aux_params=model.aux_params)
    got = served.predict(ids, batch_size=2)
    assert rel(got, want.reshape(2, -1)) < 1e-5
    # the comparison sees the bias: without it the logits are another's
    unbiased = {k: np.zeros_like(b) for k, b in aux.items()}
    assert rel(got, np.asarray(REF.logits(params, unbiased, ids, TINY))
               .reshape(2, -1)) > 1e-3


def _fit(symbol, params, data, labels, batch, lr):
    metric = mx.metric.CrossEntropy()
    model = mx.FeedForward(
        symbol, ctx=mx.cpu(), num_epoch=1, optimizer="sgd", learning_rate=lr,
        arg_params={k: mx.nd.array(v) for k, v in params.items()})
    model.fit(mx.io.NDArrayIter(data, labels, batch_size=batch),
              eval_metric=metric, batch_size=batch)
    return model, metric.get()[1]


def test_tiny_model_three_steps_of_fit_follow_the_reference():
    """float32: every leaf's change over three steps of ``fit`` against
    ``loss_and_grads`` with the selection biases moved after each step
    by ``bias_step``, and the biases ``fit`` wrote back against the
    reference's."""
    batch, lr = 2, 0.01
    symbol = tiny_model()
    start = {k: v.asnumpy() for k, v in
             seeded_params(symbol, batch).arg_params.items()}
    rng = np.random.RandomState(7)
    ids = rng.randint(0, V, (3 * batch, T + 1)).astype(np.int32)
    data, labels = ids[:, :-1], ids[:, 1:]
    p = dict(start)
    aux = {n: np.zeros(16, np.float32) for n in drawn_bias(symbol)}
    for s in range(3):
        rows = slice(s * batch, (s + 1) * batch)
        _, grads = REF.loss_and_grads(p, aux, data[rows], labels[rows], TINY)
        aux = REF.bias_step(p, aux, data[rows], RATE, TINY)
        p = {k: np.asarray(p[k] - lr * grads[k] / batch) for k in p}
    model, _ = _fit(symbol, start, data, labels, batch, lr)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert set(got) == set(p)
    worst = max(rel(got[k] - start[k], p[k] - start[k]) for k in got)
    assert worst < 1e-4, worst
    for name, b in aux.items():
        written = model.aux_params[name].asnumpy()
        assert np.allclose(written, b, atol=1e-7), name
        assert np.abs(written).max() > 0        # it moved
    loads = [model.aux_params[f"layer{l}_moe_expert_load"].asnumpy()
             for l in (1, 2)]
    assert all(load.sum() == 3 * batch * T * 4 for load in loads)


def test_fit_records_where_the_bias_stands_each_epoch():
    from mxnet_tpu import telemetry

    batch = 2
    ids = np.random.RandomState(11).randint(0, V, (3 * batch, T + 1))
    mx.random.seed(5)
    mark = len(telemetry.span_records())
    model = mx.FeedForward(tiny_model(experts_held=8, first_expert=4),
                           ctx=mx.cpu(), num_epoch=2, optimizer="sgd",
                           learning_rate=0.01, initializer=mx.init.Xavier())
    model.fit(mx.io.NDArrayIter(ids[:, :-1].astype(np.int32),
                                ids[:, 1:].astype(np.int32),
                                batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    loads = [r for r in telemetry.span_records()[mark:]
             if r["name"] == "fit.epoch.expert_load"]
    assert [(r["epoch"], r["attrs"]["node"]) for r in loads] == [
        (0, "layer1_moe"), (0, "layer2_moe"), (1, "layer1_moe"),
        (1, "layer2_moe")]
    last = model.aux_params["layer2_moe_selection_bias"].asnumpy()
    assert loads[-1]["attrs"]["bias_min"] == pytest.approx(float(last.min()))
    assert loads[-1]["attrs"]["bias_max"] == pytest.approx(float(last.max()))
    # six steps of one thousandth at most
    assert -0.006 - 1e-7 <= last.min() < last.max() <= 0.006 + 1e-7
