"""Mamba-2 state-space layers and ungated squared-ReLU experts, through the
normal path: the chunked scan of ``Mamba2Mixer`` against the per-position
recurrence of the plain reference
(``benchmark/configs/nemotron3_nano_30b_a3b.py``, which imports nothing
from the program), forward and every gradient; the causal convolution;
the gated norm by groups; ``MixtureOfExpertsSquaredReLU``'s shares; the
tiny ``nemotron_h`` through ``FeedForward.fit`` and its ``fit.epoch.ssm``
record.

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
from mxnet_tpu.ops.ssm import causal_conv, chunked_scan, gated_group_norm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference",
        os.path.join(ROOT, "benchmark", "configs", "nemotron3_nano_30b_a3b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
T, V = 64, 96
# the published pattern's first period at tiny widths: 8 heads of 8 over 2
# groups of 16, chunks of 16 (four a sequence), 16 experts top-4
TINY = dict(
    num_hidden_layers=9, vocab_size=V, n_routed_experts=16, first_expert=0,
    hidden_size=48, hybrid_override_pattern="MEMEM*EME", mamba_num_heads=8,
    mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, num_experts_per_tok=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=32, routed_scaling_factor=2.5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_norm_epsilon=1e-5)
INIT = mx.init.Mixed(
    ["_A_log$", "_D$", "_dt_bias$", ".*"],
    [mx.init.SSMDecay(), 1.0, mx.init.SSMStepBias(), mx.init.Xavier()])


def tiny_model(experts_held=16, first_expert=0, **over):
    sizes = {k: v for k, v in dict(TINY, **over).items()
             if k not in ("num_hidden_layers", "vocab_size", "first_expert")}
    return mx.models.nemotron_h(
        seq_len=T, layers=9, vocab_rows=V, experts_held=experts_held,
        first_expert=first_expert, **sizes)


def seeded_params(symbol, batch, seed=3):
    mx.random.seed(seed)
    model = mx.FeedForward(symbol, ctx=mx.cpu(), initializer=INIT)
    model._init_params({"data": (batch, T), "softmax_label": (batch, T)})
    return model


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# -- the scan -------------------------------------------------------------------

def _scan_inputs(rng, batch, t, heads=8, dim=8, groups=2, state=16):
    x = jnp.asarray(rng.randn(batch, t, heads, dim), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (batch, t, heads))), jnp.float32)
    a = -jnp.asarray(np.arange(1, heads + 1) * 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(batch, t, groups, state), jnp.float32)
    c = jnp.asarray(rng.randn(batch, t, groups, state), jnp.float32)
    return x, dt, a, b, c


@pytest.mark.parametrize("t,chunk", [(64, 16), (96, 32), (32, 32)])
def test_the_chunked_scan_is_the_recurrence(t, chunk):
    """Forward and the gradient of every input against the reference's
    per-position recurrence; several chunks a sequence, so the state is
    carried, and one chunk the whole sequence."""
    rng = np.random.RandomState(t + chunk)
    ins = _scan_inputs(rng, 2, t)
    w = jnp.asarray(rng.randn(2, t, 8, 8), jnp.float32)

    def ours(*ins):
        return chunked_scan(*ins, chunk, jnp.float32)[0]

    assert rel(ours(*ins), REF.selective_scan(*ins)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), tuple(range(5)))(*ins)
    want = jax.grad(lambda *a: jnp.sum(REF.selective_scan(*a) * w),
                    tuple(range(5)))(*ins)
    for g, h in zip(got, want):
        assert g.shape == h.shape and rel(g, h) < 1e-5


def test_the_state_crosses_chunks():
    """What the second chunk reads of the first: with the carry cut, the
    scan's output past the first chunk moves; the share that crosses a
    chunk is ``exp(a sum of the chunk's dt)``."""
    rng = np.random.RandomState(2)
    x, dt, a, b, c = _scan_inputs(rng, 1, 32)
    y, crossing = chunked_scan(x, dt, a, b, c, 16, jnp.float32)
    cut = jnp.concatenate([chunked_scan(x[:, s:s + 16], dt[:, s:s + 16], a,
                                        b[:, s:s + 16], c[:, s:s + 16], 16,
                                        jnp.float32)[0]
                           for s in (0, 16)], axis=1)
    assert rel(y[:, :16], cut[:, :16]) < 1e-6
    assert rel(y[:, 16:], cut[:, 16:]) > 1e-3
    want = np.exp(np.asarray(a) * np.asarray(dt).reshape(1, 2, 16, 8).sum(2))
    assert rel(crossing, want) < 1e-6


def test_the_convolution_is_causal():
    """Changing position t moves no output before t, and moves t itself
    by the weight's last column; the reference's convolution agrees."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 20, 6), jnp.float32)
    w = jnp.asarray(rng.randn(6, 4), jnp.float32)
    bias = jnp.asarray(rng.randn(6), jnp.float32)
    base = causal_conv(x, w, bias)
    assert rel(base, REF.causal_conv(x, w, bias)) < 1e-6
    for t in (0, 7, 19):
        moved = causal_conv(x.at[:, t].add(1.0), w, bias) - base
        assert np.all(np.asarray(moved[:, :t]) == 0)
        assert np.allclose(moved[:, t], np.broadcast_to(w[:, 3], (2, 6)))
        assert np.any(np.asarray(moved[:, t + 1:t + 4]) != 0) or t == 19
        assert np.all(np.asarray(moved[:, t + 4:]) == 0)
    # each sequence of a batch on its own
    assert np.all(np.asarray(causal_conv(x.at[0].add(1.0), w, bias)
                             - base)[1] == 0)


def test_the_gated_norm_normalises_by_groups():
    rng = np.random.RandomState(5)
    y = jnp.asarray(rng.randn(6, 32), jnp.float32)
    z = jnp.asarray(rng.randn(6, 32), jnp.float32)
    gamma = jnp.asarray(1 + 0.1 * rng.randn(32), jnp.float32)
    out = gated_group_norm(y, z, gamma, 4, 1e-5)
    gated = np.asarray(y * jax.nn.silu(z)).reshape(6, 4, 8)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert rel(out, want.reshape(6, 32) * np.asarray(gamma)) < 1e-6
    # one group scaled moves no other group
    scaled = gated_group_norm(y.at[:, :8].multiply(5.0), z, gamma, 4, 1e-5)
    assert rel(scaled[:, 8:], out[:, 8:]) < 1e-6
    # over the whole width it would be another norm
    assert rel(gated_group_norm(y, z, gamma, 1, 1e-5), out) > 1e-2


def _mixer_inputs(rng, rows=2 * T):
    h, d, g, n = 8, 8, 2, 16
    conv = h * d + 2 * g * n
    p = {"in_weight": rng.randn(2 * h * d + 2 * g * n + h, 48) * 0.3,
         "conv_weight": rng.randn(conv, 4) * 0.5,
         "conv_bias": rng.randn(conv) * 0.1,
         "dt_bias": np.log(np.expm1(np.exp(rng.uniform(-6, -1, h)))),
         "A_log": np.log(np.arange(1, h + 1)),
         "D": 1 + 0.1 * rng.randn(h),
         "norm_gamma": 1 + 0.1 * rng.randn(h * d),
         "out_weight": rng.randn(48, h * d) * 0.3}
    p = {k: jnp.asarray(a, jnp.float32) for k, a in p.items()}
    x = jnp.asarray(rng.randn(rows, 48), jnp.float32)
    return p, x


def test_the_mixer_is_the_reference_layer():
    """The operator between the projections against the reference's whole
    layer (the recurrence, not the chunked form): output, the gradients
    of its three inputs and of its six parameters; the scopes its parts
    are traced under."""
    rng = np.random.RandomState(6)
    p, x = _mixer_inputs(rng)
    op = OPS.create("Mamba2Mixer", seq_len=T, num_heads=8, head_dim=8,
                    n_groups=2, state_size=16, conv_kernel=4, chunk_size=16)
    assert op.infer_shape([(2 * T, 128), None, None] + [None] * 6) == (
        [(2 * T, 128), (2 * T, 64), (2 * T, 8), (128, 4), (128,), (8,),
         (8,), (8,), (64,)], [(2 * T, 64)], [(1025, 4)])
    names = ("conv_weight", "conv_bias", "dt_bias", "A_log", "D",
             "norm_gamma")
    record = jnp.zeros((1025, 4))

    def ours(x, *ws):
        proj = x @ p["in_weight"].T
        z, xbc, dt = proj[:, :64], proj[:, 64:192], proj[:, 192:]
        y = op.fwd([xbc, z, dt, *ws], [record], True, None)[0][0]
        return y @ p["out_weight"].T

    def theirs(x, *ws):
        return REF.mamba_mixer(x, dict(p, **dict(zip(names, ws))), "",
                               TINY, 2, T)

    ws = [p[k] for k in names]
    assert rel(ours(x, *ws), theirs(x, *ws)) < 1e-5
    w = jnp.asarray(rng.randn(2 * T, 48), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), tuple(range(7)))(x, *ws)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * w), tuple(range(7)))(
        x, *ws)
    for name, g, h in zip(("x",) + names, got, want):
        assert rel(g, h) < 1e-5, name
    text = jax.jit(lambda x: op.fwd(
        [x[:, 64:192], x[:, :64], x[:, 192:], *ws], [record], True,
        None)[0][0]).lower(x @ p["in_weight"].T).as_text(
        debug_info=True)
    for part in ("conv", "ssd", "gate_norm"):
        assert f'"{part}/' in text or f"{part}/" in text, part


def test_the_record_of_an_epochs_steps():
    """Training steps write a row each; ``epoch_record`` reads the steps
    since the previous call, and nothing moves outside training."""
    op = OPS.create("Mamba2Mixer", seq_len=T, num_heads=8, head_dim=8,
                    n_groups=2, state_size=16, conv_kernel=4, chunk_size=16)
    rng = np.random.RandomState(7)
    p, x = _mixer_inputs(rng, T)
    proj = x @ p["in_weight"].T
    ins = [proj[:, 64:192], proj[:, :64], proj[:, 192:]] + [
        p[k] for k in ("conv_weight", "conv_bias", "dt_bias", "A_log", "D",
                       "norm_gamma")]
    record = np.zeros((1025, 4), np.float32)
    start = record.copy()
    for _ in range(3):
        record = np.asarray(op.fwd(ins, [record], True, None)[1][0])
    assert record[0, 0] == 3
    assert np.array_equal(op.fwd(ins, [record], False, None)[1][0], record)
    name, attrs = op.epoch_record([start], [record])
    assert name == "fit.epoch.ssm" and attrs["steps"] == 3
    dt = np.asarray(jax.nn.softplus(proj[:, 192:] + p["dt_bias"]))
    assert attrs["dt_mean"] == pytest.approx(dt.mean(), rel=1e-5)
    assert attrs["dt_max"] == pytest.approx(dt.max(), rel=1e-5)
    a = -np.exp(np.asarray(p["A_log"]))
    crossing = np.exp(a * dt.reshape(4, 16, 8).sum(1))
    assert attrs["carry_decay_mean"] == pytest.approx(crossing.mean(),
                                                      rel=1e-5)
    assert attrs["carry_decay_min"] == pytest.approx(crossing.min(),
                                                     rel=1e-5)
    assert op.epoch_record([record], [record])[1] == {
        "steps": 0, "chunk_size": 16}


# -- the ungated experts ----------------------------------------------------------

def _moe(held=16, first=0):
    return OPS.create("MixtureOfExpertsSquaredReLU", num_experts=16,
                      experts_held=held, first_expert=first, top_k=4,
                      expert_width=24, scaling=2.5, shared_width=32,
                      selection="biased")


def _moe_weights(rng, hidden=48):
    def draw(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)

    return [draw(16, hidden), draw(16, 24, hidden), draw(16, hidden, 24),
            draw(32, hidden), draw(hidden, 32)]


def _moe_reference(x, ws, bias, held=16, first=0):
    names = ("router_weight", "up_weight", "down_weight", "shared_up_weight",
             "shared_down_weight")
    p = dict(zip(names, ws))
    for n in names[1:3]:
        p[n] = p[n][first:first + held]
    return REF.sparse_ffn(x, p, "", jnp.asarray(bias), dict(
        TINY, n_routed_experts=held, first_expert=first))


def test_the_ungated_experts_are_the_reference_layer():
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(40, 48), jnp.float32)
    ws = _moe_weights(rng)
    op = _moe()
    assert op.list_arguments() == [
        "data", "router_weight", "up_weight", "down_weight",
        "shared_up_weight", "shared_down_weight"]
    assert op.infer_shape([(40, 48)] + [None] * 5)[0] == [
        (40, 48), (16, 48), (16, 24, 48), (16, 48, 24), (32, 48), (48, 32)]
    assert op.argument_major_to_minor() == {"up_weight": (0, 2, 1)}
    bias = jnp.asarray(rng.rand(16) * 0.2 - 0.1, jnp.float32)
    aux = [jnp.zeros(16), bias]

    def ours(x, *ws):
        return op.fwd([x, *ws], aux, True, None)[0][0]

    assert rel(ours(x, *ws), _moe_reference(x, ws, bias)) < 1e-5
    w = jnp.asarray(rng.randn(40, 48), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), tuple(range(6)))(x, *ws)
    want = jax.grad(lambda x, *ws: jnp.sum(_moe_reference(x, ws, bias) * w),
                    tuple(range(6)))(x, *ws)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-4


def test_the_sixteen_shares_add_up():
    """16 experts cut 16 ways, as the cell's 128 are: the shares' outputs,
    with the shared expert (which every rank computes alike) counted once,
    add up to the uncut reference layer's, the bias as drawn."""
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(48, 48), jnp.float32)
    ws = _moe_weights(rng)
    bias = jnp.asarray(rng.rand(16) * 0.2 - 0.1, jnp.float32)
    aux = [jnp.zeros(16), bias]
    shared = REF.relu2_ffn(x, *ws[3:])
    parts = []
    for rank in range(16):
        held = [w[rank:rank + 1] for w in ws[1:3]]
        out = _moe(1, rank).fwd([x, ws[0], *held, *ws[3:]], aux, True,
                                None)[0][0]
        assert rel(out, _moe_reference(x, ws, bias, 1, rank)) < 1e-5
        parts.append(out - shared)
    assert rel(sum(parts) + shared, _moe_reference(x, ws, bias)) < 1e-5


# -- the whole tiny model through fit -------------------------------------------

def test_the_builder_defaults_are_the_published_sizes():
    """https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/
    blob/main/config.json, as the catalog row has it."""
    defaults = {k: p.default for k, p in
                inspect.signature(mx.models.nemotron_h).parameters.items()}
    assert defaults == {
        "seq_len": 8192, "layers": 52, "vocab_rows": 131072,
        "experts_held": 128, "first_expert": 0, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
        "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
        "n_routed_experts": 128, "num_experts_per_tok": 6,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "layer_norm_epsilon": 1e-5, "remat": True, "train_router": None}
    symbol = tiny_model()
    shapes = dict(zip(symbol.list_arguments(), symbol.infer_shape(
        data=(2, T), softmax_label=(2, T))[0]))
    # one tensor a published one, in its layout
    assert shapes["layer0_mamba_in_weight"] == (2 * 64 + 2 * 32 + 8, 48)
    assert shapes["layer0_mamba_conv_weight"] == (128, 4)
    assert shapes["layer0_mamba_out_weight"] == (48, 64)
    assert shapes["layer1_moe_up_weight"] == (16, 24, 48)
    assert shapes["layer1_moe_shared_down_weight"] == (48, 32)
    assert shapes["layer5_q_weight"] == (64, 48)
    assert shapes["layer5_k_weight"] == (32, 48)
    assert "layer1_moe_gate_weight" not in shapes
    assert symbol.list_auxiliary_states() == [
        f"layer{l}_{a}" for l, kind in enumerate("MEMEM*EME")
        for a in {"M": ("mamba_ssm_record",), "*": (),
                  "E": ("moe_expert_load", "moe_selection_bias")}[kind]]
    text = symbol.tojson()
    assert '"rotary_dim": 0' in text and '"selection": "biased"' in text
    assert '"train_router": false' in tiny_model(experts_held=2).tojson()
    with pytest.raises(ValueError, match="letters"):
        tiny_model(hybrid_override_pattern="MEX")


def test_the_initial_values():
    model = seeded_params(tiny_model(), 2)
    arg = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert np.allclose(arg["layer0_mamba_A_log"], np.log(np.arange(1, 9)))
    assert np.array_equal(arg["layer2_mamba_D"], np.ones(8, np.float32))
    dt0 = np.log1p(np.exp(arg["layer0_mamba_dt_bias"]))
    assert np.all((dt0 >= 1e-3 - 1e-7) & (dt0 <= 0.1 + 1e-7))
    assert not np.allclose(arg["layer0_mamba_dt_bias"],
                           arg["layer2_mamba_dt_bias"])
    assert np.array_equal(arg["layer0_mamba_norm_gamma"], np.ones(64))
    assert np.array_equal(arg["layer0_mamba_conv_bias"], np.zeros(128))
    assert np.array_equal(model.aux_params["layer1_moe_selection_bias"]
                          .asnumpy(), np.zeros(16, np.float32))
    assert not np.any(model.aux_params["layer0_mamba_ssm_record"].asnumpy())


def test_tiny_model_logits_match_the_reference():
    symbol = tiny_model()
    model = seeded_params(symbol, 2)
    rng = np.random.RandomState(10)
    aux = {n: (rng.rand(16) * 0.1 - 0.05).astype(np.float32)
           for n in symbol.list_auxiliary_states()
           if n.endswith("selection_bias")}
    for name, b in aux.items():
        model.aux_params[name] = mx.nd.array(b)
    ids = rng.randint(0, V, (2, T)).astype(np.int32)
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    want = np.asarray(REF.logits(params, aux, ids, TINY))
    assert want.shape == (2 * T, V)
    head = mx.symbol.Reshape(data=symbol.get_internals()["head_output"],
                             target_shape=(2, -1))
    served = mx.FeedForward(head, ctx=mx.cpu(), arg_params=model.arg_params,
                            aux_params=model.aux_params)
    got = served.predict(ids, batch_size=2)
    assert rel(got, want.reshape(2, -1)) < 1e-5


def _fit(symbol, params, data, labels, batch, lr, num_epoch=1):
    model = mx.FeedForward(
        symbol, ctx=mx.cpu(), num_epoch=num_epoch, optimizer="sgd",
        learning_rate=lr,
        arg_params={k: mx.nd.array(v) for k, v in params.items()})
    model.fit(mx.io.NDArrayIter(data, labels, batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    return model


def test_tiny_model_three_steps_of_fit_follow_the_reference():
    """float32, every expert held (the router trained): every leaf's
    change over three steps of ``fit`` against ``loss_and_grads`` with
    the selection biases moved after each step by ``bias_step``, and the
    biases ``fit`` wrote back against the reference's."""
    batch, lr = 2, 0.01
    symbol = tiny_model()
    start = {k: v.asnumpy() for k, v in
             seeded_params(symbol, batch).arg_params.items()}
    rng = np.random.RandomState(11)
    ids = rng.randint(0, V, (3 * batch, T + 1)).astype(np.int32)
    data, labels = ids[:, :-1], ids[:, 1:]
    aux = {n: np.zeros(16, np.float32) for n in symbol.list_auxiliary_states()
           if n.endswith("selection_bias")}
    p = dict(start)
    for s in range(3):
        rows = slice(s * batch, (s + 1) * batch)
        _, grads = REF.loss_and_grads(p, aux, data[rows], labels[rows], TINY)
        aux = REF.bias_step(p, aux, data[rows], 0.001, TINY)
        p = {k: np.asarray(p[k] - lr * grads[k] / batch) for k in p}
    model = _fit(symbol, start, data, labels, batch, lr)
    got = {k: v.asnumpy() for k, v in model.arg_params.items()}
    assert set(got) == set(p)
    worst = max(rel(got[k] - start[k], p[k] - start[k]) for k in got)
    assert worst < 1e-4, worst
    for name, b in aux.items():
        written = model.aux_params[name].asnumpy()
        assert np.allclose(written, b, atol=1e-7), name
        assert np.abs(written).max() > 0        # it moved


def test_fit_writes_the_ssm_record_each_epoch():
    from mxnet_tpu import telemetry

    batch = 2
    ids = np.random.RandomState(12).randint(0, V, (3 * batch, T + 1))
    mx.random.seed(5)
    mark = len(telemetry.span_records())
    model = mx.FeedForward(tiny_model(experts_held=8, first_expert=4),
                           ctx=mx.cpu(), num_epoch=2, optimizer="sgd",
                           learning_rate=0.01, initializer=INIT)
    model.fit(mx.io.NDArrayIter(ids[:, :-1].astype(np.int32),
                                ids[:, 1:].astype(np.int32),
                                batch_size=batch),
              eval_metric=mx.metric.CrossEntropy(), batch_size=batch)
    records = [r for r in telemetry.span_records()[mark:]
               if r["name"] == "fit.epoch.ssm"]
    assert [(r["epoch"], r["attrs"]["node"]) for r in records] == [
        (e, f"layer{l}_mamba") for e in (0, 1) for l in (0, 2, 4, 7)]
    for r in records:
        a = r["attrs"]
        assert a["steps"] == 3
        assert 1e-3 * 0.5 < a["dt_mean"] <= a["dt_max"] < 1.0
        assert 0 < a["carry_decay_min"] <= a["carry_decay_mean"] < 1
    loads = [r for r in telemetry.span_records()[mark:]
             if r["name"] == "fit.epoch.expert_load"]
    assert len(loads) == 2 * 4
    assert model.aux_params["layer7_mamba_ssm_record"].asnumpy()[0, 0] == 6
