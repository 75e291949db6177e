"""Comm/compute overlap scheduler tests (ISSUE 7).

Covers: the OverlapConfig knob, reverse-topological bucket scheduling
from the Symbol graph, per-bucket wire plans summing EXACTLY to the
fused plan, the overlapped in-jit sync (correctness + per-bucket error
feedback + independent HLO collective pairs), fit(overlap=...)
convergence parity vs the fused single bucket (int8 + twobit) with the
armed zero-recompile steady state, per-bucket EF-residual checkpoint/
resume round-trip + invalidation on a bucket-plan change, the
stale-sync AsyncKVStore pipeline (one-round staleness + flush), and the
satellites: axis_size==1 short-circuit (0-byte plan), symmetric
HostCodec wire accounting, GradBucketer.from_layout exact rebuild.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import comm
from mxnet_tpu import parallel as par
from mxnet_tpu import symbol as sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.compat import shard_map
from mxnet_tpu.utils import compile as cm


def _mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return par.make_mesh(dp=8, devices=jax.devices()[:8])


def _ctx8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return [mx.cpu(i) for i in range(8)]


def _mlp(hidden=64, num_classes=2):
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc1", num_hidden=hidden)
    net = sym.Activation(data=net, name="relu1", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=num_classes)
    return sym.SoftmaxOutput(data=net, name="softmax")


def _blobs(n=160, dim=10, seed=0):
    rng = np.random.RandomState(seed)
    X = np.concatenate([rng.randn(n // 2, dim) + 1,
                        rng.randn(n - n // 2, dim) - 1]).astype(np.float32)
    y = np.concatenate([np.zeros(n // 2), np.ones(n - n // 2)]).astype(
        np.float32)
    order = rng.permutation(n)
    return X[order], y[order]


# -- config + schedule planning ------------------------------------------------

def test_overlap_config_resolve(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_COMM_OVERLAP", raising=False)
    assert comm.OverlapConfig.resolve(None) is None
    assert comm.OverlapConfig.resolve(False) is None
    cfg = comm.OverlapConfig.resolve(True)
    assert cfg.bucket_bytes == comm.DEFAULT_BUCKET_BYTES
    assert comm.OverlapConfig.resolve(1 << 20).bucket_bytes == 1 << 20
    assert comm.OverlapConfig.resolve(cfg) is cfg
    monkeypatch.setenv("MXNET_TPU_COMM_OVERLAP", "1")
    assert comm.OverlapConfig.resolve(None).bucket_bytes == \
        comm.DEFAULT_BUCKET_BYTES
    monkeypatch.setenv("MXNET_TPU_COMM_OVERLAP", "65536")
    assert comm.OverlapConfig.resolve(None).bucket_bytes == 65536
    with pytest.raises(MXNetError):
        comm.OverlapConfig.resolve("garbage")
    with pytest.raises(MXNetError):
        comm.OverlapConfig(0)


def test_reverse_topo_param_order():
    """Last layers first: fc2's params (consumed latest in the forward
    graph) lead the schedule — backward produces their gradients first."""
    net = _mlp()
    names = ["fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"]
    order = comm.reverse_topo_param_order(net, names)
    assert set(order) == set(names)
    assert order.index("fc2_weight") < order.index("fc1_weight")
    assert order.index("fc2_bias") < order.index("fc1_bias")
    # ties (a layer's weight+bias feed the same op) keep caller order
    assert order.index("fc2_weight") < order.index("fc2_bias")
    # names the graph never consumes go last
    order2 = comm.reverse_topo_param_order(net, names + ["orphan"])
    assert order2[-1] == "orphan"


def test_plan_overlap_buckets_and_layout_key():
    shapes = {"a": (1000,), "b": (1000,), "c": (1000,)}
    spec = comm.CompressionSpec.resolve("int8")
    plan = comm.plan_overlap(shapes, spec, 8, max_bytes=4096)  # 1024 f32 cap
    assert plan.num_buckets == 3
    assert sorted(plan.param_keys()) == ["a", "b", "c"]
    # without a symbol: sorted names, reversed (canonical on both sides
    # of a traced boundary)
    assert plan.buckets[0]["keys"] == ["c"]
    one = comm.plan_overlap(shapes, spec, 8, max_bytes=1 << 30)
    assert one.num_buckets == 1
    assert plan.layout_key() != one.layout_key()
    assert plan.layout_key() == comm.plan_overlap(
        shapes, spec, 8, max_bytes=4096).layout_key()
    assert plan.layout_key() != comm.plan_overlap(
        shapes, comm.CompressionSpec.resolve("twobit"), 8,
        max_bytes=4096).layout_key()
    with pytest.raises(MXNetError):
        comm.plan_overlap(shapes, None, 8)  # overlap needs compression


def test_overlap_plan_sums_exactly_to_fused():
    """ACCEPTANCE: per-bucket closed-form plans sum EXACTLY (==, not
    approx) to the fused single-bucket plan over the same padded total."""
    for mode in ("bf16", "int8", "twobit"):
        for elems in ([("b0", 4096)], [("b0", 1000), ("b1", 517)],
                      [("b0", 100), ("b1", 33), ("b2", 7), ("b3", 70000)]):
            p = comm.overlap_plan(elems, 8, mode)
            assert p["matches_fused"], (mode, elems, p)
            assert p["wire_bytes"] == p["fused_wire_bytes"]
            assert p["num_buckets"] == len(elems)
            assert p["padded_elements"] >= p["num_elements"]
    # fp32 (no compression) merges to the plain psum arithmetic
    p = comm.overlap_plan([("b0", 256), ("b1", 256)], 4, None)
    assert p["wire_bytes"] == comm.allreduce_plan(512, 4, None)["wire_bytes"]


def test_axis_size_one_short_circuit():
    """SATELLITE: the degenerate single-device mesh is a no-op sync — no
    encode/all_to_all/all_gather, no quantization error — and the wire
    plan prices it at 0 bytes."""
    tree = {"w": jnp.arange(7, dtype=jnp.float32)}
    out = comm.compressed_allreduce(tree, "int8", axis_size=1)
    assert out is tree  # identical object: nothing ran
    resid = jnp.zeros((1, 8))
    out2, r2 = comm.error_feedback_allreduce(tree, resid, "int8",
                                             axis_size=1)
    assert out2 is tree and r2 is resid
    for mode in ("bf16", "int8", "twobit"):
        assert comm.allreduce_plan(4096, 1, mode)["wire_bytes"] == 0.0
        assert comm.overlap_plan([("b0", 4096)], 1, mode)["wire_bytes"] \
            == 0.0


# -- the overlapped in-jit sync ------------------------------------------------

def _overlap_sync(mesh, grads_by_dev, mode, cap):
    """Run overlap_allreduce inside shard_map over dp-8; returns the
    synced tree (average semantics) on host."""
    spec = comm.CompressionSpec.resolve(mode)
    shapes = {k: tuple(v.shape[1:]) for k, v in grads_by_dev.items()}
    plan = comm.plan_overlap(shapes, spec, 8, max_bytes=cap)
    resid = comm.init_overlap_residuals(plan)

    def body(tree, *res):
        local = {k: v[0] for k, v in tree.items()}
        synced, new_res = comm.overlap_allreduce(
            local, res[0] if res else None, plan, average=True)
        out = {k: v[None] for k, v in synced.items()}
        return (out, new_res) if res else out

    has_ef = resid is not None
    in_specs = (P("dp"),) + ((P("dp"),) if has_ef else ())
    out_specs = (P("dp"), P("dp")) if has_ef else P("dp")
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    dev = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
           for k, v in grads_by_dev.items()}
    if has_ef:
        rdev = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
                for k, v in resid.items()}
        out, _ = fn(dev, rdev)
    else:
        out = fn(dev)
    return {k: np.asarray(v[0]) for k, v in out.items()}


def test_overlap_allreduce_matches_mean():
    mesh = _mesh8()
    rng = np.random.RandomState(3)
    grads = {"a": rng.randn(8, 500).astype(np.float32),
             "b": rng.randn(8, 40, 10).astype(np.float32),
             "c": rng.randn(8, 90).astype(np.float32)}
    want = {k: v.mean(axis=0) for k, v in grads.items()}
    for mode, tol in (("bf16", 2e-2), ("int8", 2e-2)):
        got = _overlap_sync(mesh, grads, mode, cap=1200 * 4)
        for k in want:
            err = np.abs(got[k] - want[k]).max()
            scale = np.abs(want[k]).max()
            assert err <= tol * max(scale, 1.0), (mode, k, err)


def test_overlap_allreduce_rejects_key_mismatch():
    spec = comm.CompressionSpec.resolve("int8")
    plan = comm.plan_overlap({"a": (8,)}, spec, 8)
    with pytest.raises(MXNetError, match="do not match the plan"):
        comm.overlap_allreduce({"b": jnp.zeros((8,))}, None, plan)


def test_residuals_match_plan_and_invalidation():
    spec = comm.CompressionSpec.resolve("int8")
    shapes = {"a": (1000,), "b": (600,)}
    plan = comm.plan_overlap(shapes, spec, 8, max_bytes=4096)
    res = comm.init_overlap_residuals(plan)
    assert comm.residuals_match_plan(res, plan)
    assert set(res) == {b["name"] for b in plan.buckets}
    # a cap change re-slabs the params -> saved ledgers are meaningless
    plan2 = comm.plan_overlap(shapes, spec, 8, max_bytes=1 << 30)
    assert not comm.residuals_match_plan(res, plan2)
    assert not comm.residuals_match_plan(None, plan)
    assert not comm.residuals_match_plan({"bucket0": res["bucket0"]}, plan)
    # bf16 needs no feedback: None is the only valid state
    bplan = comm.plan_overlap(shapes, "bf16", 8)
    assert comm.init_overlap_residuals(bplan) is None
    assert comm.residuals_match_plan(None, bplan)
    # fused path key: layout identity for the single-bucket residual
    k1 = comm.fused_layout_key(1600, spec, 8)
    assert k1 == comm.fused_layout_key(1600, spec, 8)
    assert k1 != comm.fused_layout_key(1600, spec, 4)
    assert k1 != comm.fused_layout_key(1601, spec, 8)


def test_overlap_hlo_has_independent_collective_pairs():
    """ACCEPTANCE: the compiled overlapped step contains one independent
    reduce-scatter/all-gather pair group PER BUCKET (>= 2), not the one
    fused pair."""
    mesh = _mesh8()
    rng = np.random.RandomState(0)
    params0 = {f"w{i}": (rng.randn(256, 256) * 0.05).astype(np.float32)
               for i in range(3)}
    num = sum(v.size for v in params0.values())

    def loss_fn(params, data):
        h = data["x"]
        for k in sorted(params):
            h = jnp.tanh(h @ params[k])
        return jnp.mean((h - data["y"]) ** 2)

    def update(params, s, grads):
        return {k: params[k] - 0.01 * grads[k] for k in params}, s

    x = rng.randn(64, 256).astype(np.float32)
    data = par.shard_batch({"x": x, "y": x}, mesh)
    spec = comm.CompressionSpec.resolve("int8")
    params = par.replicate_params(
        {k: jnp.asarray(v) for k, v in params0.items()}, mesh)

    def hlo_counts(step, call):
        hlo = step.lower(*call).compile().as_text()
        table = comm.hlo_collective_table(hlo, default_group_size=8)
        a2a = sum(r["count"] for r in table if "all-to-all" in r["op"])
        ag = sum(r["count"] for r in table if "all-gather" in r["op"])
        wire = sum(r["wire_bytes"] for r in table)
        return a2a, ag, wire

    step_f = par.make_data_parallel_step(loss_fn, update, mesh,
                                         donate=False, compression="int8")
    resid_f = jax.device_put(comm.init_error_feedback(params, spec, 8),
                             NamedSharding(mesh, P("dp")))
    f_a2a, f_ag, _ = hlo_counts(step_f, (params, {}, data, resid_f))

    cap = num * 4 // 3 + 4  # 3 slabs
    step_o = par.make_data_parallel_step(loss_fn, update, mesh,
                                         donate=False, compression="int8",
                                         overlap=cap)
    plan = comm.plan_overlap({k: v.shape for k, v in params0.items()},
                             spec, 8, max_bytes=cap)
    assert plan.num_buckets == 3
    resid_o = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
               for k, v in comm.init_overlap_residuals(plan).items()}
    o_a2a, o_ag, o_wire = hlo_counts(step_o, (params, {}, data, resid_o))
    # one pair group per bucket: the compiled op counts are the fused
    # counts multiplied by the bucket count — nothing re-fused them
    assert o_a2a == plan.num_buckets * f_a2a, (o_a2a, f_a2a)
    assert o_ag == plan.num_buckets * f_ag, (o_ag, f_ag)
    assert plan.num_buckets >= 2
    # and the compiled wire agrees with the closed-form per-bucket plan
    # (int8 payloads survive CPU lowering faithfully; the bf16 stage-2
    # all-gather upcasts on CPU, so compare the int8 stage-1 rows only)
    plan_a2a = sum(r["payload_bytes"] for r in plan.wire_plan()["collectives"]
                   if r["op"] == "all-to-all")
    hlo = step_o.lower(params, {}, data, resid_o).compile().as_text()
    hlo_a2a_payload = sum(
        r["payload_bytes"] for r in
        comm.hlo_collective_table(hlo, default_group_size=8)
        if "all-to-all" in r["op"])
    assert hlo_a2a_payload == pytest.approx(plan_a2a, rel=0.05)

    # loss parity of the two schedules: the buckets change where the
    # quantization scales fall, not what is trained
    def loss_after(step, call, steps=4):
        for _ in range(steps):
            res = step(*call)
            jax.block_until_ready(res[2])
            call = (res[0], res[1], data) + tuple(res[3:])
        return float(np.asarray(res[2]))

    assert abs(loss_after(step_f, (params, {}, data, resid_f))
               - loss_after(step_o, (params, {}, data, resid_o))) < 1e-5


# -- fit(overlap=...) ----------------------------------------------------------

def test_fit_overlap_convergence_parity_int8_and_twobit():
    """SATELLITE: overlap-mode convergence parity vs the fused single
    bucket for both lossy modes (per-bucket EF residuals recover the
    quantization error exactly like the fused ledger does)."""
    X, y = _blobs(160)

    def train(compression, overlap):
        np.random.seed(0)
        mx.random.seed(0)
        model = mx.FeedForward(_mlp(), ctx=_ctx8(), num_epoch=5,
                               learning_rate=0.5,
                               initializer=mx.init.Xavier())
        model.fit(X, y, batch_size=32, compression=compression,
                  overlap=overlap)
        return (model.predict(X, batch_size=32).argmax(axis=1) == y).mean()

    comm.reset_comm_stats()
    for mode in ("int8", "twobit"):
        acc_fused = train(mode, None)
        acc_over = train(mode, 2048)  # small cap -> multiple buckets
        assert acc_fused > 0.9, (mode, acc_fused)
        assert abs(acc_over - acc_fused) < 0.05, (mode, acc_fused, acc_over)
    # the registered per-step plan is the per-bucket overlapped one and
    # its totals carry the exact fused arithmetic
    per = comm.comm_stats()["per_program"]
    over = [p for p in per.values() if p.get("num_buckets")]
    assert over and all(p["num_buckets"] >= 2 for p in over)
    assert all(p["matches_fused"] for p in over)


def test_fit_overlap_zero_recompiles_steady_state():
    """SATELLITE: a RecompileTracker-armed epoch with overlap= on stays
    at zero recompiles — per-bucket residual dicts thread through the
    donated carry without perturbing the program signature."""
    X, y = _blobs(160)
    model = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=3,
                           learning_rate=0.5)
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()

    cm.reset_compile_stats()
    try:
        model.fit(X, y, batch_size=32, compression="int8", overlap=8192,
                  epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    assert tracker.recompiles == []
    per = cm.compile_stats()["per_function"]
    train = [c for lbl, c in per.items() if lbl.startswith("train_step:")]
    assert train and train[0]["misses"] == 1


def test_precompile_overlap_then_fit_no_compiles():
    X, y = _blobs(120)
    model = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=2,
                           learning_rate=0.5)
    out = model.precompile(data_shapes={"data": (40, 10)},
                           label_shapes={"softmax_label": (40,)},
                           compression="int8", overlap=8192)
    assert out["programs"] == 1
    with cm.RecompileTracker(raise_on_recompile=True):
        model.fit(X, y, batch_size=40, compression="int8", overlap=8192)


def _capture_logger(name):
    logger = logging.getLogger(name)
    logger.setLevel(logging.INFO)
    records = []

    class _H(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logger.addHandler(_H())
    return logger, records


def test_overlap_residual_checkpoint_resume_and_invalidation(tmp_path):
    """SATELLITE: per-bucket EF residuals round-trip through the sharded
    checkpoint (layout-keyed), and a bucket-plan change on resume DROPS
    them instead of cross-injecting stale error."""
    X, y = _blobs(96)
    d = str(tmp_path / "ckpt")

    m1 = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=2,
                        learning_rate=0.5)
    m1.fit(X, y, batch_size=32, compression="int8", overlap=2048,
           sharded_checkpoint_dir=d)

    from mxnet_tpu.utils import checkpoint as ckpt
    step = ckpt.latest_step(d)
    assert step == 2
    *_, meta, _, comm_state = ckpt.load_sharded(d, step, with_comm=True)
    assert comm_state is not None and len(comm_state) >= 2  # >=2 ledgers
    assert meta["comm_layout"].startswith("overlap:")
    names = set(comm_state)
    assert all(n.startswith("bucket") for n in names)

    # same plan on resume -> ledgers adopted
    log1, rec1 = _capture_logger("test_overlap_resume1")
    m2 = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=3,
                        learning_rate=0.5)
    m2.fit(X, y, batch_size=32, compression="int8", overlap=2048,
           sharded_checkpoint_dir=d, logger=log1)
    assert any("resumed" in m and "ledger" in m for m in rec1), rec1

    # different bucket cap -> plan changed -> ledgers dropped, fresh start
    log2, rec2 = _capture_logger("test_overlap_resume2")
    m3 = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=4,
                        learning_rate=0.5)
    m3.fit(X, y, batch_size=32, compression="int8", overlap=32768,
           sharded_checkpoint_dir=d, logger=log2)
    assert any("dropped on resume" in m for m in rec2), rec2
    acc = (m3.predict(X, batch_size=32).argmax(axis=1) == y).mean()
    assert acc > 0.9


def test_fused_residual_checkpoint_resume(tmp_path):
    """The non-overlap EF residual gets the same layout-keyed round-trip
    (saved under the __fused__ slot)."""
    X, y = _blobs(96)
    d = str(tmp_path / "ckpt")
    m1 = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=2,
                        learning_rate=0.5)
    m1.fit(X, y, batch_size=32, compression="int8",
           sharded_checkpoint_dir=d)
    from mxnet_tpu.utils import checkpoint as ckpt
    *_, meta, _, comm_state = ckpt.load_sharded(d, 2, with_comm=True)
    assert set(comm_state) == {"__fused__"}
    assert meta["comm_layout"].startswith("fused:")
    log1, rec1 = _capture_logger("test_fused_resume")
    m2 = mx.FeedForward(_mlp(hidden=64), ctx=_ctx8(), num_epoch=3,
                        learning_rate=0.5)
    m2.fit(X, y, batch_size=32, compression="int8",
           sharded_checkpoint_dir=d, logger=log1)
    assert any("resumed fused EF residual" in m for m in rec1), rec1


# -- stale-sync kvstore pipeline -----------------------------------------------

def test_push_pull_stale_one_round_staleness_and_flush():
    """The pipelined push lags exactly one round behind compute: call k
    returns the weights as of push k-1; flush_stale drains and returns
    the truth."""
    from mxnet_tpu.kvstore_async import AsyncKVStore

    kv = AsyncKVStore()
    try:
        kv.init("w", mx.nd.zeros((4,)))
        kv.set_optimizer(mx.optimizer.create("test", rescale_grad=1.0))
        g = {"w": np.ones((4,), np.float32)}
        r1 = kv.push_pull_stale(g)   # pull-before-push: pre-push state
        np.testing.assert_allclose(r1["w"], 0.0)
        r2 = kv.push_pull_stale(g)   # result of push #1
        np.testing.assert_allclose(r2["w"], 1.0)
        r3 = kv.push_pull_stale(g)   # result of push #2
        np.testing.assert_allclose(r3["w"], 2.0)
        out = kv.flush_stale(["w"])  # drains push #3
        np.testing.assert_allclose(out["w"], 3.0)
        assert kv._stale_round is None
        # flush with nothing in flight is a plain pull
        out2 = kv.flush_stale(["w"])
        np.testing.assert_allclose(out2["w"], 3.0)
    finally:
        del kv


def test_fit_overlap_dist_async_stale_sync():
    """fit(kvstore='dist_async', overlap=True) arms the stale-sync
    pipeline and still converges (weights one round stale)."""
    X, y = _blobs(120)
    model = mx.FeedForward(_mlp(hidden=32), ctx=mx.cpu(), num_epoch=4,
                           learning_rate=0.5)
    log, rec = _capture_logger("test_stale_sync_fit")
    model.fit(X, y, batch_size=40, kvstore="dist_async", overlap=True,
              logger=log)
    assert any("stale-sync armed" in m for m in rec), rec
    acc = (model.predict(X, batch_size=40).argmax(axis=1) == y).mean()
    assert acc > 0.85, acc


# -- satellites ----------------------------------------------------------------

def test_host_codec_symmetric_wire_accounting():
    """SATELLITE: decode records RECEIVED bytes — comm_stats() sees both
    ends of the host transport, and they balance for a loopback pair."""
    comm.reset_comm_stats()
    spec = comm.CompressionSpec.resolve("int8")
    codec = comm.HostCodec(spec)
    rng = np.random.RandomState(0)
    flat = rng.randn(4096).astype(np.float32)
    payload = codec.encode("slab0", flat)
    assert codec.bytes_encoded > 0 and codec.bytes_decoded == 0
    out = codec.decode(payload)
    assert out.shape == flat.shape
    assert codec.bytes_decoded == codec.bytes_encoded
    host = comm.comm_stats()["host_bytes"]
    assert host["sent"] == host["received"] > 0
    # the stateless receiving end (decode_payload) also records
    comm.reset_comm_stats()
    comm.decode_payload(spec, payload)
    host = comm.comm_stats()["host_bytes"]
    assert host["received"] > 0 and host["sent"] == 0


def test_from_layout_exact_rebuild():
    """SATELLITE: from_layout reconstructs the producer's layout exactly
    — same bucket names, key->slab assignment, offsets, sizes — without
    the old discard-and-rebuild dance."""
    shapes = [("a", (100, 10)), ("b", (5000,)), ("c", (300, 300)),
              ("d", ()), ("e", (7,))]
    b1 = comm.GradBucketer(shapes, max_bytes=40_000)
    b2 = comm.GradBucketer.from_layout(b1.layout())
    assert [bk["name"] for bk in b2.buckets] == \
        [bk["name"] for bk in b1.buckets]
    for x, ycol in zip(b1.buckets, b2.buckets):
        assert x["keys"] == ycol["keys"]
        assert x["shapes"] == ycol["shapes"]
        assert x["offsets"] == ycol["offsets"]
        assert x["size"] == ycol["size"]
    # max_bytes reflects the actual largest reconstructed slab
    assert b2.max_bytes == max(4 * bk["size"] for bk in b2.buckets)
    # pack/unpack works through the rebuilt layout
    rng = np.random.RandomState(1)
    kvs = {k: rng.randn(*s).astype(np.float32) if s
           else np.float32(rng.randn()) for k, s in shapes}
    flats = b2.pack({k: np.asarray(v) for k, v in kvs.items()})
    back = b2.unpack(flats)
    for k, s in shapes:
        np.testing.assert_allclose(back[k], np.asarray(kvs[k]).reshape(s))
    with pytest.raises(MXNetError):
        comm.GradBucketer.from_layout([])
