"""Trainer-stack tests (reference: tests/python/train/test_mlp.py — train a
real model and assert final accuracy; dataset synthesized since there is no
network). Also covers optimizer math, initializers, metrics, checkpointing."""

import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym


def _two_blob_dataset(n=400, dim=10, seed=0):
    """Linearly separable 2-class blobs — converges in a few epochs."""
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-2, 2, (2, dim))
    X, y = [], []
    for cls in range(2):
        X.append(centers[cls] + 0.3 * rng.randn(n // 2, dim))
        y.append(np.full(n // 2, cls))
    X = np.concatenate(X).astype(np.float32)
    y = np.concatenate(y).astype(np.float32)
    order = rng.permutation(n)
    return X[order], y[order]


def _mlp_sym(num_classes=2):
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=16)
    net = sym.Activation(data=net, name="relu1", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=num_classes)
    return sym.SoftmaxOutput(data=net, name="softmax")


def test_feedforward_fit_accuracy():
    X, y = _two_blob_dataset()
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=8,
                           learning_rate=0.5, optimizer="sgd", momentum=0.9)
    model.fit(X, y, batch_size=40)
    preds = model.predict(X, batch_size=40)
    acc = (preds.argmax(axis=1) == y).mean()
    assert acc > 0.95, f"accuracy {acc}"


def test_feedforward_eval_data_and_score():
    Xall, yall = _two_blob_dataset(n=600, seed=1)
    X, y = Xall[:400], yall[:400]
    Xv, yv = Xall[400:], yall[400:]
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=6,
                           learning_rate=0.5)
    val_iter = mx.io.NDArrayIter(Xv, yv, batch_size=40)
    model.fit(X, y, eval_data=val_iter, batch_size=40)
    score = model.score(mx.io.NDArrayIter(Xv, yv, batch_size=40))
    assert score > 0.9


def test_feedforward_checkpoint_roundtrip(tmp_path):
    X, y = _two_blob_dataset()
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=3,
                           learning_rate=0.5)
    model.fit(X, y, batch_size=40)
    p1 = model.predict(X, batch_size=40)
    prefix = str(tmp_path / "mlp")
    model.save(prefix, 3)
    loaded = mx.FeedForward.load(prefix, 3, ctx=mx.cpu())
    p2 = loaded.predict(X, batch_size=40)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-5)


def test_feedforward_multi_device_dp():
    """Data parallel over multiple virtual devices: same convergence."""
    X, y = _two_blob_dataset()
    model = mx.FeedForward(_mlp_sym(), ctx=[mx.cpu(i) for i in range(4)],
                           num_epoch=6, learning_rate=0.5)
    model.fit(X, y, batch_size=40, kvstore="device")
    preds = model.predict(X, batch_size=40)
    acc = (preds.argmax(axis=1) == y).mean()
    assert acc > 0.95, f"multi-device accuracy {acc}"


def test_feedforward_create():
    X, y = _two_blob_dataset()
    model = mx.FeedForward.create(_mlp_sym(), X, y, ctx=mx.cpu(), num_epoch=4,
                                  lr=0.5, batch_size=40)
    acc = (model.predict(X, batch_size=40).argmax(axis=1) == y).mean()
    assert acc > 0.9


def test_epoch_and_batch_callbacks():
    X, y = _two_blob_dataset()
    epochs, batches = [], []
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=2,
                           learning_rate=0.1)
    model.fit(
        X, y, batch_size=40,
        epoch_end_callback=lambda e, s, a, x: epochs.append(e),
        batch_end_callback=lambda p: batches.append(p.nbatch),
    )
    assert epochs == [0, 1]
    assert len(batches) == 20  # 10 batches x 2 epochs


def test_optimizer_sgd_momentum_math():
    opt = mx.optimizer.create("sgd", lr=0.1, momentum=0.9, rescale_grad=1.0)
    w = mx.nd.ones((3,))
    g = mx.nd.ones((3,))
    state = opt.create_state(0, w)
    opt.update(0, w, g, state)
    np.testing.assert_allclose(w.asnumpy(), np.ones(3) - 0.1, rtol=1e-6)
    opt.update(0, w, g, state)
    # momentum: m1=-0.1, m2=0.9*(-0.1)-0.1=-0.19
    np.testing.assert_allclose(w.asnumpy(), np.ones(3) - 0.1 - 0.19, rtol=1e-5)


def test_optimizer_clip_and_wd():
    opt = mx.optimizer.create("sgd", lr=1.0, wd=0.1, clip_gradient=0.5,
                              rescale_grad=1.0)
    w = mx.nd.ones((2,))
    g = mx.nd.array(np.array([10.0, -10.0]))
    opt.update(0, w, g, opt.create_state(0, w))
    # clipped grad ±0.5, +wd*w=0.1 -> steps 0.6, -0.4
    np.testing.assert_allclose(w.asnumpy(), [1 - 0.6, 1 + 0.4], rtol=1e-5)


def test_get_updater():
    opt = mx.optimizer.create("sgd", lr=0.1, rescale_grad=1.0)
    updater = mx.optimizer.get_updater(opt)
    w = mx.nd.ones((2,))
    updater(0, mx.nd.ones((2,)), w)
    np.testing.assert_allclose(w.asnumpy(), [0.9, 0.9], rtol=1e-6)


def test_initializers():
    for init, checker in [
        (mx.init.Uniform(0.5), lambda a: (np.abs(a) <= 0.5).all()),
        (mx.init.Normal(2.0), lambda a: 1.0 < a.std() < 3.0),
        (mx.init.Xavier(), lambda a: a.std() > 0),
    ]:
        arr = mx.nd.zeros((100, 100))
        init("fc1_weight", arr)
        assert checker(arr.asnumpy())
    arr = mx.nd.zeros((10,))
    mx.init.Uniform()("fc1_bias", arr)
    np.testing.assert_allclose(arr.asnumpy(), 0)
    mx.init.Uniform()("bn_gamma", arr)
    np.testing.assert_allclose(arr.asnumpy(), 1)
    mx.init.Uniform()("bn_moving_var", arr)
    np.testing.assert_allclose(arr.asnumpy(), 1)


def test_metrics():
    acc = mx.metric.create("accuracy")
    preds = mx.nd.array(np.array([[0.9, 0.1], [0.2, 0.8], [0.7, 0.3]]))
    labels = mx.nd.array(np.array([0, 1, 1]))
    acc.update([labels], [preds])
    assert abs(acc.get()[1] - 2.0 / 3) < 1e-6
    mse = mx.metric.create("mse")
    mse.update([mx.nd.array([1.0, 2.0])], [mx.nd.array([1.5, 2.5])])
    assert abs(mse.get()[1] - 0.25) < 1e-6
    custom = mx.metric.np_metric(lambda l, p: float(np.abs(l - p).sum()))
    custom.update([mx.nd.array([1.0])], [mx.nd.array([3.0])])
    assert abs(custom.get()[1] - 2.0) < 1e-6


def test_lr_scheduler():
    s = mx.lr_scheduler.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(5) == 1.0
    assert s(10) == 0.5
    assert s(25) == 0.25
    m = mx.lr_scheduler.MultiFactorScheduler(step=[5, 15], factor=0.1, base_lr=1.0)
    assert m(0) == 1.0 and abs(m(7) - 0.1) < 1e-9 and abs(m(20) - 0.01) < 1e-9


def test_monitor():
    X, y = _two_blob_dataset()
    net = _mlp_sym()
    exe = net.simple_bind(mx.cpu(), data=(4, 10), softmax_label=(4,))
    exe.arg_dict["data"][:] = X[:4]
    exe.arg_dict["fc1_weight"][:] = np.random.uniform(-1, 1, (16, 10))
    exe.arg_dict["fc2_weight"][:] = np.random.uniform(-1, 1, (2, 16))
    mon = mx.Monitor(interval=1, pattern=".*fc1.*")
    mon.install(exe)
    mon.tic()
    exe.forward()
    stats = mon.toc()
    assert stats, "monitor collected nothing"
    assert all("fc1" in name for _, name, _ in stats)


def test_monitor_sees_bn_output_under_fusion():
    """The executor fuses BatchNorm->relu, but Monitor's get_internals()
    graph makes every node a head — fusion is suppressed there and the
    observed BN output is the true pre-relu value."""
    from mxnet_tpu import symbol as S

    bn = S.BatchNorm(data=S.Variable("data"), name="bn")
    net = S.Activation(data=bn, act_type="relu", name="relu")
    exe = net.simple_bind(mx.cpu(), data=(4, 3, 5, 5))
    rng = np.random.RandomState(0)
    exe.arg_dict["data"][:] = rng.randn(4, 3, 5, 5).astype(np.float32)
    exe.arg_dict["bn_gamma"][:] = np.ones(3, np.float32)
    mon = mx.Monitor(interval=1, stat_func=lambda x: x.min(),
                     pattern=".*bn.*")
    mon.install(exe)
    mon.tic()
    exe.forward()
    stats = mon.toc()
    bn_stats = [v for _, name, v in stats if name == "bn_output"]
    assert bn_stats, f"no bn_output stat in {[s[1] for s in stats]}"
    # pre-relu BN output must go negative; post-relu would be >= 0
    assert float(bn_stats[0]) < 0


def test_visualization():
    net = _mlp_sym()
    dot = mx.viz.plot_network(net, title="mlp")
    assert "digraph" in dot and "fc1" in dot
    summary = mx.viz.print_summary(net, shape={"data": (4, 10), "softmax_label": (4,)})
    assert "Total params" in summary


def test_perplexity_and_topk_device_host_parity():
    """New metrics: device_update and host update agree numerically."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    logits = rng.rand(16, 10).astype(np.float32)
    probs = logits / logits.sum(axis=1, keepdims=True)
    labels = rng.randint(0, 10, (16,)).astype(np.float32)
    labels[:3] = 0  # some ignorable rows
    for make in (lambda: mx.metric.create("perplexity"),
                 lambda: mx.metric.Perplexity(ignore_label=0),
                 lambda: mx.metric.create("top_k_accuracy"),):
        host = make()
        host.update([mx.nd.array(labels)], [mx.nd.array(probs)])
        dev = make()
        state = dev.device_init()
        state = dev.device_update(state, [jnp.asarray(labels)],
                                  [jnp.asarray(probs)])
        dev.absorb_device_state(state)
        np.testing.assert_allclose(dev.get()[1], host.get()[1], rtol=1e-5)


def test_fit_dist_async_kvstore_single_process():
    """fit(kvstore='dist_async') runs the real update-on-kvstore path: the
    optimizer executes on the parameter host (loopback server in single
    process), workers push grads / pull weights each batch — and still
    converges (reference semantics: update-on-arrival, no BSP round)."""
    X, y = _two_blob_dataset()
    model = mx.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=8,
                           learning_rate=0.5, optimizer="sgd", momentum=0.9)
    model.fit(X, y, batch_size=40, kvstore="dist_async")
    preds = model.predict(X, batch_size=40)
    acc = (preds.argmax(axis=1) == y).mean()
    assert acc > 0.95, f"accuracy {acc}"


def test_train_step_runs_on_ctx_device_not_batch_device():
    """Regression (round 3): data iterators hand over host-committed
    arrays, and jit follows committed inputs — without explicit placement,
    a cpu:0-committed batch silently dragged the whole train step onto the
    wrong backend/device. The trainer must pin the step to the ctx
    device."""
    import jax

    if len(jax.devices()) < 3:
        pytest.skip("needs multi-device virtual mesh")
    X, y = _two_blob_dataset(n=64, dim=6)

    target = mx.cpu(2)
    data = sym.Variable("data")
    net = sym.SoftmaxOutput(
        data=sym.FullyConnected(data=data, num_hidden=2, name="fc"),
        name="softmax")
    model = mx.FeedForward(net, ctx=target, num_epoch=1, learning_rate=0.1,
                           initializer=mx.init.Xavier())

    placed_on = []
    orig_build = model._build_train_step

    def spy_build(*args, **kwargs):
        step = orig_build(*args, **kwargs)

        def wrapped(params, opt_state, aux, batch, rng, lr, mstate):
            out = step(params, opt_state, aux, batch, rng, lr, mstate)
            placed_on.append(next(iter(out[0].values())).devices())
            return out

        return wrapped

    model._build_train_step = spy_build
    # iterator batches are committed to cpu:0 (default device):
    model.fit(X, y, batch_size=32)
    assert placed_on, "train step never ran"
    assert placed_on[0] == {target.jax_device}, (
        f"step executed on {placed_on[0]}, expected {target.jax_device}")


def test_optimizer_adamw_decoupled_decay():
    """AdamW: decay applies to the WEIGHT (scaled by lr), not through the
    gradient — distinct from Adam with wd, and matching the closed form."""
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.999, 1e-8, 0.1
    opt = mx.optimizer.create("adamw", lr=lr, beta1=b1, beta2=b2,
                              epsilon=eps, weight_decay=wd, rescale_grad=1.0)
    w = mx.nd.array(np.array([1.0, -2.0, 3.0], np.float32))
    g = np.array([0.5, -0.25, 1.0], np.float32)
    state = opt.create_state(0, w)

    m = np.zeros(3)
    v = np.zeros(3)
    w_ref = np.array([1.0, -2.0, 3.0])
    for t in range(1, 4):
        state = opt.update(0, w, mx.nd.array(g), state) or state
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        w_ref = w_ref - lr * (mhat / (np.sqrt(vhat) + eps) + wd * w_ref)
    np.testing.assert_allclose(w.asnumpy(), w_ref, atol=1e-5)

    # decoupled vs L2-through-gradient: one step of adam(wd) differs
    w2 = mx.nd.array(np.array([1.0, -2.0, 3.0], np.float32))
    adam = mx.optimizer.create("adam", lr=lr, beta1=b1, beta2=b2,
                               epsilon=eps, wd=wd, rescale_grad=1.0)
    s2 = adam.create_state(0, w2)
    adam.update(0, w2, mx.nd.array(g), s2)
    w3 = mx.nd.array(np.array([1.0, -2.0, 3.0], np.float32))
    opt2 = mx.optimizer.create("adamw", lr=lr, beta1=b1, beta2=b2,
                               epsilon=eps, weight_decay=wd, rescale_grad=1.0)
    opt2.update(0, w3, mx.nd.array(g), opt2.create_state(0, w3))
    assert np.abs(w2.asnumpy() - w3.asnumpy()).max() > 1e-6


def test_transformer_train_step_with_registry_optimizer():
    """TransformerLM.make_train_step(optimizer=...) runs a registry
    optimizer's pure pytree path fused in the sharded step (state tree
    sharded leaf-wise: m/v follow the parameter, step counter replicates)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import (TransformerLM,
                                              transformer_lm_config)
    from mxnet_tpu.parallel import make_mesh

    n = min(8, len(jax.devices()))
    if n < 4:
        import pytest

        pytest.skip("needs 4+ devices")
    mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
    cfg = transformer_lm_config(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=1, max_len=16, dtype=jnp.float32,
                                attn_impl="dense")
    model = TransformerLM(cfg)
    opt = mx.optimizer.create("adamw", lr=1e-2, weight_decay=0.0,
                              rescale_grad=1.0)
    params, state = model.init_sharded(mesh, seed=0, optimizer=opt)
    # Adam-family state: (m, v, t) per parameter
    assert all(len(state[k]) == 3 for k in state)
    step = model.make_train_step(mesh, lr=1e-2, optimizer=opt)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 32, (4, 16)).astype(np.int32)
    losses = []
    for _ in range(10):
        params, state, loss = step(params, state, toks, toks)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # memorizing one batch must descend


def test_adamw_decay_filter_exempts_parameters():
    """decay_filter masks decoupled decay per parameter NAME (standard
    recipe: no decay on biases/LN) — exempted params match plain Adam's
    trajectory, decayed ones don't."""
    import jax.numpy as jnp

    lr = 0.1
    opt = mx.optimizer.create(
        "adamw", lr=lr, weight_decay=0.5, rescale_grad=1.0,
        decay_filter=lambda name: "bias" not in name)
    params = {"fc_weight": jnp.ones((3,)), "fc_bias": jnp.ones((3,))}
    grads = {"fc_weight": jnp.full((3,), 0.1),
             "fc_bias": jnp.full((3,), 0.1)}
    states = opt.init_state_tree(params)
    new_p, _ = opt.apply(params, grads, states, lr)

    ref = mx.optimizer.create("adam", lr=lr, rescale_grad=1.0)
    rp, _ = ref.apply(params, grads, ref.init_state_tree(params), lr)
    # bias exempt: identical to Adam; weight decayed: differs by lr*wd*w
    np.testing.assert_allclose(np.asarray(new_p["fc_bias"]),
                               np.asarray(rp["fc_bias"]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(new_p["fc_weight"]),
        np.asarray(rp["fc_weight"]) - lr * 0.5 * 1.0, atol=1e-6)


def test_adamw_decay_filter_imperative_path():
    """The filter must also mask on the update()/get_updater path (Module
    / kvstore training), via the optimizer's index->name mapping."""
    lr = 0.1
    opt = mx.optimizer.create(
        "adamw", lr=lr, weight_decay=0.5, rescale_grad=1.0,
        decay_filter=lambda name: "bias" not in name)
    opt.arg_names = ["fc_weight", "fc_bias"]
    ref = mx.optimizer.create("adam", lr=lr, rescale_grad=1.0)

    g = np.full(3, 0.1, np.float32)
    w_dec = mx.nd.array(np.ones(3, np.float32))   # index 0: decayed
    w_ex = mx.nd.array(np.ones(3, np.float32))    # index 1: exempt
    w_ref = mx.nd.array(np.ones(3, np.float32))
    opt.update(0, w_dec, mx.nd.array(g), opt.create_state(0, w_dec))
    opt.update(1, w_ex, mx.nd.array(g), opt.create_state(1, w_ex))
    ref.update(0, w_ref, mx.nd.array(g), ref.create_state(0, w_ref))

    np.testing.assert_allclose(w_ex.asnumpy(), w_ref.asnumpy(), atol=1e-6)
    np.testing.assert_allclose(w_dec.asnumpy(),
                               w_ref.asnumpy() - lr * 0.5 * 1.0, atol=1e-6)

    # without names the filter cannot be honored: loud, not silent
    opt2 = mx.optimizer.create("adamw", decay_filter=lambda n: True)
    try:
        opt2.update(0, w_ex, mx.nd.array(g), opt2.create_state(0, w_ex))
        raise AssertionError("expected MXNetError without arg_names")
    except mx.base.MXNetError:
        pass
