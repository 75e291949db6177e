"""Parallel-stack tests on the 8-device virtual CPU mesh: mesh construction,
data-parallel gradient equivalence, tensor-parallel numerics, ring attention
vs dense attention, and the multi-axis transformer train step (the same path
__graft_entry__.dryrun_multichip exercises)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.models.transformer import TransformerLM, transformer_lm_config


def test_make_mesh():
    mesh = par.make_mesh(dp=2, tp=2, sp=2)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2 and mesh.shape["sp"] == 2
    mesh2 = par.auto_mesh(tp=4)
    assert mesh2.shape["dp"] == 2 and mesh2.shape["tp"] == 4


def test_mesh_wrong_size():
    with pytest.raises(ValueError):
        par.make_mesh(dp=3, tp=2)


def test_allreduce_grads_shard_map():
    from mxnet_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = par.make_mesh(dp=8)
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(xs):
        return par.allreduce_grads({"g": xs}, "dp", average=True)["g"]

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), x.mean()))


def test_dp_training_equivalence():
    """Sharded-batch jit training step == single-device step (same math)."""
    cfg = transformer_lm_config(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, max_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, (8, 16)).astype(np.int32)
    targets = rng.randint(0, 64, (8, 16)).astype(np.int32)

    # single device
    params1, moms1 = model.init_sharded(None, seed=0)
    step1 = model.make_train_step(None, lr=0.1)
    p1, _, loss1 = step1(params1, moms1, tokens, targets)

    # dp=8 mesh
    mesh = par.make_mesh(dp=8)
    params2, moms2 = model.init_sharded(mesh, seed=0)
    step2 = model.make_train_step(mesh, lr=0.1)
    p2, _, loss2 = step2(params2, moms2, tokens, targets)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(p1["embed"]), np.asarray(p2["embed"]),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_matches_dense():
    from mxnet_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.sequence import attention_reference, ring_attention
    import functools

    mesh = par.make_mesh(sp=8)
    rng = np.random.RandomState(0)
    b, h, s, d = 2, 2, 32, 8
    q = rng.randn(b, h, s, d).astype(np.float32)
    k = rng.randn(b, h, s, d).astype(np.float32)
    v = rng.randn(b, h, s, d).astype(np.float32)

    for causal in (False, True):
        dense = attention_reference(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=causal)
        spec = P(None, None, "sp", None)
        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)


def test_ring_self_attention_wrapper():
    mesh = par.make_mesh(dp=2, tp=2, sp=2)
    rng = np.random.RandomState(1)
    q = rng.randn(2, 2, 16, 4).astype(np.float32)
    k = rng.randn(2, 2, 16, 4).astype(np.float32)
    v = rng.randn(2, 2, 16, 4).astype(np.float32)
    out = par.ring_self_attention(mesh, q, k, v, causal=True)
    dense = par.attention_reference(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_transformer_multi_axis_train_step():
    """Full train step over a dp=2, tp=2, sp=2 mesh — loss decreases and the
    result matches the unsharded step."""
    cfg = transformer_lm_config(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=1, max_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 32, (4, 16)).astype(np.int32)
    targets = rng.randint(0, 32, (4, 16)).astype(np.int32)

    params_ref, moms_ref = model.init_sharded(None, seed=0)
    step_ref = model.make_train_step(None, lr=0.1)
    _, _, loss_ref = step_ref(params_ref, moms_ref, tokens, targets)

    mesh = par.make_mesh(dp=2, tp=2, sp=2)
    params, moms = model.init_sharded(mesh, seed=0)
    step = model.make_train_step(mesh, lr=0.1)
    p, m, loss = step(params, moms, tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-3)

    # losses decrease across steps
    losses = [float(loss)]
    for _ in range(3):
        p, m, loss = step(p, m, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_explicit_flash_raises_on_uneven_mesh(caplog):
    """attn_impl='flash' under a mesh the batch/heads do not split over
    raises (it used to fall to the dense path without a word); 'auto'
    may choose dense, and says so once."""
    cfg = transformer_lm_config(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=2, max_len=16, dtype=jnp.float32,
                                attn_impl="flash")
    tokens = np.zeros((3, 16), np.int32)  # batch 3 over dp=2
    mesh = par.make_mesh(dp=2, tp=4)      # 2 heads over tp=4
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(mx.MXNetError, match="attn_impl='flash'"):
        jax.eval_shape(lambda p: model.forward(p, tokens, mesh=mesh), params)

    auto = TransformerLM(dict(cfg, attn_impl="auto"))
    auto._use_flash = lambda: True        # what 'auto' resolves to on a TPU
    with caplog.at_level("WARNING"):
        jax.eval_shape(lambda p: auto.forward(p, tokens, mesh=mesh), params)
    notices = [r for r in caplog.records if "dense attention" in r.message]
    assert len(notices) == 1              # once, not once per layer


def test_column_row_parallel_numerics():
    x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    w1 = np.random.RandomState(1).randn(8, 16).astype(np.float32)
    w2 = np.random.RandomState(2).randn(16, 8).astype(np.float32)
    u = par.column_parallel(jnp.asarray(x), jnp.asarray(w1))
    y = par.row_parallel(u, jnp.asarray(w2))
    np.testing.assert_allclose(np.asarray(y), x @ w1 @ w2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_dense(causal):
    """Flash-kernel ring attention == dense attention, forward and grads
    (the long-context fast path: pallas blocks merged by lse across the
    ring, backward through per-block flash kernels vs global lse)."""
    import functools as ft

    from mxnet_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.sequence import (attention_reference,
                                             ring_flash_attention)

    mesh = make_mesh(sp=8)
    rng = np.random.RandomState(0)
    b, h, s, d = 2, 2, 64, 16
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))

    spec = P(None, None, "sp", None)
    ring = shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, "sp", causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    dense = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)

    # gradient parity through the custom ring VJP
    w = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v).astype(jnp.float32) * w)

    def loss_dense(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal)
                       .astype(jnp.float32) * w)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-4, rtol=3e-4)


def test_ring_self_attention_flash_wrapper():
    from mxnet_tpu import parallel as par
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh(dp=2, tp=2, sp=2)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, 16, 8).astype(np.float32))
    from mxnet_tpu.parallel.sequence import attention_reference

    out = par.ring_self_attention(mesh, q, k, v, causal=True, use_flash=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grad_accum_matches_full_batch():
    """n_micro-accumulated gradients == full-batch gradients (mean loss)."""
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(6, 3).astype(np.float32))
    X = jnp.asarray(rng.randn(16, 6).astype(np.float32))
    Y = jnp.asarray(rng.randn(16, 3).astype(np.float32))

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params - y) ** 2)

    l_full, g_full = jax.value_and_grad(loss)(W, (X, Y))
    l_acc, g_acc = par.grad_accum(loss, W, (X, Y), n_micro=4)
    np.testing.assert_allclose(l_acc, l_full, rtol=1e-5)
    np.testing.assert_allclose(g_acc, g_full, rtol=1e-5, atol=1e-6)


def test_make_data_parallel_step_trains_and_matches_single_device():
    """The sharded jitted step over dp=8 computes the same update as a
    plain single-device step (partitioner-inserted allreduce)."""
    mesh = par.make_mesh(dp=8)
    rng = np.random.RandomState(1)
    W0 = rng.randn(4, 2).astype(np.float32)
    X = rng.randn(32, 4).astype(np.float32)
    Y = rng.randn(32, 2).astype(np.float32)
    lr = 0.1

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params - y) ** 2)

    def update(params, opt_state, grads):
        return params - lr * grads, opt_state

    step = par.make_data_parallel_step(loss, update, mesh, donate=False)
    params = par.replicate_params(jnp.asarray(W0), mesh)
    batch = par.shard_batch((X, Y), mesh)
    p1, _, l1 = step(params, jnp.zeros(()), batch)

    l_ref, g_ref = jax.value_and_grad(loss)(jnp.asarray(W0),
                                            (jnp.asarray(X), jnp.asarray(Y)))
    np.testing.assert_allclose(float(l1), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(W0) - lr * np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)

    # microbatched variant agrees too
    step2 = par.make_data_parallel_step(loss, update, mesh, donate=False,
                                        n_micro=2)
    p2, _, l2 = step2(params, jnp.zeros(()), batch)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p1), rtol=1e-4,
                               atol=1e-5)


def test_host_local_batch_to_global_single_process():
    mesh = par.make_mesh(dp=8)
    X = np.arange(16, dtype=np.float32).reshape(16, 1)
    g = par.host_local_batch_to_global(X, mesh)
    assert g.shape == (16, 1)
    np.testing.assert_allclose(np.asarray(g), X)
