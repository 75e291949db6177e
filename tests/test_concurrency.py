"""Concurrency tier (ISSUE 11): the runtime lock-order watchdog, the
thread-name contract, and hammer tests for the three scariest shared
structures — hub reset() racing emit(), memory-ledger GC callbacks racing
track_arrays() adds, and _GroupServer membership churn racing an open
accumulate round — all run under the watchdog with zero cycles asserted.

Acceptance (ISSUE 11): a seeded deliberate lock-order inversion is
detected both statically (MX702) and at runtime (a lockwatch incident in
a CRC-valid flight dump)."""

import gc
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.analysis import concurrency, lockwatch
from mxnet_tpu.ndarray import NDArray


@pytest.fixture(autouse=True)
def _restore_world_identity():
    """ElasticCoordinator.commit relabels the process (rank, world) —
    the heartbeat-monitor test commits resizes, which must not leak this
    module's world into later tests' metric labels."""
    prev = (telemetry.current_rank(), telemetry.world_size())
    yield
    telemetry.set_world(*prev)


@pytest.fixture
def watchdog():
    """A fresh enabled watcher for the test; disabled afterwards."""
    was = lockwatch.enabled()
    lockwatch.enable()
    lockwatch.reset()
    yield lockwatch.watcher()
    if not was:
        lockwatch.disable()


# -- the watchdog itself -------------------------------------------------------

def test_disabled_watchdog_is_passthrough():
    lockwatch.disable()
    lk = lockwatch.named_lock("t.passthrough")
    with lk:
        pass
    assert lk.acquire(blocking=False)
    lk.release()
    assert lockwatch.report() == {"enabled": False}


def test_seeded_inversion_detected_at_runtime(watchdog):
    a = lockwatch.named_lock("t.A")
    b = lockwatch.named_lock("t.B")
    with a:
        with b:
            pass
    with b:
        with a:      # closes the cycle: A->B and B->A both observed
            pass
    rep = lockwatch.report()
    assert rep["enabled"]
    assert len(rep["cycles"]) == 1
    assert sorted(rep["cycles"][0]["cycle"]) == ["t.A", "t.B"]
    # the same cycle re-observed is reported once
    with b:
        with a:
            pass
    assert len(lockwatch.report()["cycles"]) == 1


def test_inversion_incident_lands_in_crc_valid_flight_dump(
        tmp_path, watchdog):
    """ISSUE 11 acceptance: the deadlock risk shows up in the same
    post-mortem tooling as everything else — a lockwatch incident inside
    a CRC-validated flight dump, plus the hub gauges."""
    telemetry.reset()
    telemetry.flight.reset()
    a = lockwatch.named_lock("t.flight.A")
    b = lockwatch.named_lock("t.flight.B")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    path = str(tmp_path / "flight.json")
    telemetry.flight.dump(path, reason="lockwatch-test")
    ok, payload = telemetry.validate_flight(path)
    assert ok, payload
    incidents = [e for e in payload["incidents"]
                 if e.get("kind") == "lockwatch"]
    assert incidents, payload["incidents"]
    assert incidents[0]["what"] == "cycle"
    assert "t.flight.A" in incidents[0]["cycle"]
    gauges = telemetry.hub().snapshot()["gauges"]
    assert gauges.get("lockwatch_cycles_total", 0) >= 1
    assert "lockwatch_max_hold_ms" in gauges


def test_seeded_inversion_detected_statically():
    """The SAME inversion shape, caught by MX702 before any thread runs."""
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def g():\n"
        "    with B:\n"
        "        with A:\n"
        "            pass\n"
    )
    findings = concurrency.lint_source(src, "fx.py")
    assert [f.rule.id for f in findings] == ["MX702"]
    assert "fx.A" in findings[0].extra["cycle"]


def test_stall_detection(watchdog):
    lockwatch.reset(stall_ms=20)
    lk = lockwatch.named_lock("t.stall")
    with lk:
        time.sleep(0.05)
    rep = lockwatch.report()
    assert rep["stalls"] and rep["stalls"][0]["lock"] == "t.stall"
    assert rep["max_hold_ms"] >= 20


def test_named_condition_rejects_reentrant_lock():
    """Condition.wait must fully release its lock; the wrapper does not
    forward RLock's multi-level _release_save, so a cv over a
    named_rlock would sleep still holding the lock — rejected loudly at
    construction instead of wedging at the first wait."""
    with pytest.raises(TypeError, match="reentrant"):
        lockwatch.named_condition("t.bad_cv", lockwatch.named_rlock("t.rl"))
    # a plain watched lock stays Condition-compatible, armed or not
    lockwatch.disable()
    cv = lockwatch.named_condition("t.ok_cv")
    with cv:
        assert not cv.wait(timeout=0.01)  # no deadlock, normal timeout


def test_rlock_reentrancy_no_self_edge(watchdog):
    rl = lockwatch.named_rlock("t.rlock")
    with rl:
        with rl:       # reentrant re-acquire: no A->A edge, no cycle
            pass
    rep = lockwatch.report()
    assert rep["cycles"] == []
    assert all(e["from"] != e["to"] for e in rep["edges"])


def test_condition_over_watched_lock(watchdog):
    lk = lockwatch.named_lock("t.cv_lock")
    cv = lockwatch.named_condition("t.cv", lk)
    state = []

    def waiter():
        with cv:
            assert cv.wait_for(lambda: state, timeout=10)

    t = threading.Thread(target=waiter, daemon=True, name="t-waiter")
    t.start()
    time.sleep(0.05)
    with cv:
        state.append(1)
        cv.notify_all()
    t.join(timeout=10)
    assert not t.is_alive()
    assert lockwatch.report()["cycles"] == []


# -- hammer 1: hub reset() racing emit() ---------------------------------------

def test_hub_reset_racing_emit_zero_cycles(watchdog):
    telemetry.reset()
    stop = threading.Event()
    errors = []

    def writer(tid):
        try:
            i = 0
            while not stop.is_set():
                telemetry.emit("hammer", tid=tid, i=i)
                telemetry.counter("hammer_total")
                telemetry.observe("hammer_ms", 0.1, tid=tid)
                i += 1
        except Exception as e:  # noqa: BLE001 - the assertion surface
            errors.append(("writer", e))

    writers = [threading.Thread(target=writer, args=(t,), daemon=True)
               for t in range(4)]
    for t in writers:
        t.start()
    try:
        for _ in range(30):
            telemetry.reset()       # swaps the hub under the writers
            telemetry.hub().snapshot()
            time.sleep(0.002)
    finally:
        stop.set()
        for t in writers:
            t.join(timeout=30)
    assert not errors, errors
    assert lockwatch.report()["cycles"] == []


# -- hammer 2: ledger GC callbacks racing track_arrays() adds ------------------

def test_ledger_gc_callbacks_racing_adds_zero_cycles(watchdog):
    from mxnet_tpu.telemetry import memory as memory_mod

    prev = telemetry.track_arrays(True)
    stop = threading.Event()
    errors = []

    def churner(seed):
        try:
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                arrs = [NDArray(rng.randn(8, 8).astype(np.float32))
                        for _ in range(8)]
                del arrs           # GC callbacks fire under churn
        except Exception as e:  # noqa: BLE001
            errors.append(("churner", e))

    threads = [threading.Thread(target=churner, args=(s,), daemon=True)
               for s in range(4)]
    for t in threads:
        t.start()
    try:
        led = memory_mod.ledger()
        for _ in range(50):
            led.stats()
            led.top_arrays(4)
            gc.collect()           # force collector-driven callbacks too
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        telemetry.track_arrays(prev)
    assert not errors, errors
    gc.collect()
    stats = memory_mod.ledger().stats()
    assert stats["live_bytes"] >= 0 and stats["live_count"] >= 0
    assert lockwatch.report()["cycles"] == []


# -- hammer 3: _GroupServer membership churn vs an open accumulate round -------

def test_group_server_membership_churn_zero_cycles(watchdog):
    """Ranks 0-2 push 16 rounds; rank 3 pushes 6 then dies. The
    deregistration lands while the survivors are blocked inside the open
    round 7 — they must release and finish, the re-registration must be
    idempotent, and the watchdog must see zero lock-order cycles."""
    from mxnet_tpu import kvstore as kv_mod

    workers = kv_mod.create_group(4, op_timeout=60.0)
    server = workers[0]._server
    init = NDArray(np.zeros((4,), np.float32))
    rounds = {0: 16, 1: 16, 2: 16, 3: 6}
    errors = []

    def run(rank):
        try:
            w = workers[rank]
            for _ in range(rounds[rank]):
                w.push("k", NDArray(np.ones((4,), np.float32)))
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    server.init("k", init.asnumpy())   # direct: the group barrier would
    del init                           # wait for all 4 worker threads
    threads = [threading.Thread(target=run, args=(r,), daemon=True,
                                name=f"t-rank{r}") for r in range(4)]
    for t in threads:
        t.start()
    threads[3].join(timeout=60)        # rank 3 finishes its 6 rounds
    time.sleep(0.1)                    # survivors block in round 7
    epoch = server.deregister_worker(3)
    assert epoch >= 1
    for t in threads[:3]:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    # rejoin handshake between rounds: register is idempotent
    assert server.register_worker(3) == server.register_worker(3)
    assert server.num_workers == 4
    assert lockwatch.report()["cycles"] == []


def test_elastic_resize_fit_zero_cycles(watchdog, tmp_path):
    """A dp-4 fit that shrinks to 3 mid-epoch and regrows to 4, armed:
    the coordinator, the checkpoint plane, the hub and the feed take
    their locks across two resizes, the watchdog observes them (the
    acquire count moves) and sees zero lock-order cycles."""
    import jax

    from mxnet_tpu.resilience import ElasticCoordinator

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    data = mx.sym.Variable("data")
    net = mx.sym.Activation(mx.sym.FullyConnected(
        data, name="fc1", num_hidden=16), name="a1", act_type="tanh")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        net, name="fc2", num_hidden=2), name="softmax")
    rng = np.random.RandomState(0)
    X = rng.randn(192, 10).astype(np.float32)
    y = rng.randint(0, 2, (192,)).astype(np.float32)
    co = ElasticCoordinator(4)

    def drive(param):
        if param.epoch == 1 and param.nbatch == 1 and co.world_size == 4:
            co.kill()
        if param.epoch == 2 and param.nbatch == 1 and co.world_size == 3:
            co.join_all()

    model = mx.FeedForward(net, ctx=[mx.cpu(i) for i in range(4)],
                           num_epoch=4, optimizer="sgd", learning_rate=0.05)
    before = watchdog.acquires
    model.fit(X, y, batch_size=48, elastic=co,   # 48 % 12 == 0: 4 and 3
              sharded_checkpoint_dir=str(tmp_path / "ckpt"),
              batch_end_callback=drive, telemetry=True)
    assert co.resizes == 2
    assert [h["to"] for h in co.history] == [3, 4]
    assert watchdog.acquires > before
    assert lockwatch.report()["cycles"] == []


# -- thread-name contract ------------------------------------------------------

def _names():
    return {t.name for t in threading.enumerate()}


def test_kv_async_and_metrics_http_thread_names():
    from mxnet_tpu.kvstore_async import AsyncKVStore

    kv = AsyncKVStore()                # rank 0 spawns the server in-proc
    try:
        kv.init("w", NDArray(np.zeros((2,), np.float32)))
        names = _names()
        assert "mx-kv-accept" in names, names
        assert any(n.startswith("mx-kv-serve-") for n in names), names
    finally:
        del kv
    port = telemetry.serve_http(0)
    try:
        assert port > 0
        assert "mx-metrics-http" in _names()
    finally:
        telemetry.stop_http()


def test_prefetch_and_heartbeat_thread_names():
    from mxnet_tpu.model import _AsyncDeviceFeed
    from mxnet_tpu.resilience import ElasticCoordinator

    feed = _AsyncDeviceFeed(iter([{"x": 1}, {"x": 2}]),
                            extract=lambda b: b, place=lambda b: b)
    try:
        assert feed._thread.name == "mx-prefetch"
        assert feed._thread.daemon
    finally:
        feed.close()

    co = ElasticCoordinator(4, heartbeat_timeout=10.0)
    t = co.start_heartbeat_monitor(interval=0.05)
    try:
        assert t is not None and t.name == "mx-heartbeat" and t.daemon
        assert co.start_heartbeat_monitor() is t  # idempotent
    finally:
        co.stop_heartbeat_monitor()
    assert not t.is_alive()


def test_precompile_thread_names():
    """The parallel AOT warmup pool carries the mx-precompile role name
    (sampled concurrently: pool threads live only inside precompile)."""
    from mxnet_tpu.models import lstm_unroll

    sents = [[1, 2, 3], [2, 3, 4, 5, 6, 7], [3, 4], [1] * 7] * 4

    def sym_gen(seq_len):
        return lstm_unroll(num_layers=1, seq_len=seq_len, input_size=8,
                           num_hidden=8, num_embed=4, num_label=8)

    init_states = [("l0_init_c", (4, 8)), ("l0_init_h", (4, 8))]
    it = mx.BucketSentenceIter(sents, buckets=[4, 8], batch_size=4,
                               init_states=init_states, shuffle=False)
    model = mx.BucketingFeedForward(sym_gen, default_bucket_key=8,
                                    num_epoch=1, learning_rate=0.1,
                                    initializer=mx.init.Xavier())
    seen = set()
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            seen.update(_names())
            time.sleep(0.001)

    s = threading.Thread(target=sampler, daemon=True)
    s.start()
    try:
        out = model.precompile(data=it)
    finally:
        stop.set()
        s.join(timeout=10)
    assert out["programs"] == 2
    assert any(n.startswith("mx-precompile") for n in seen), sorted(seen)


# -- heartbeat monitor behavior ------------------------------------------------

def test_heartbeat_monitor_detects_silence():
    from mxnet_tpu.resilience import ElasticCoordinator

    co = ElasticCoordinator(4, heartbeat_timeout=0.1)
    for r in range(4):
        co.heartbeat(r)
    co.start_heartbeat_monitor(interval=0.02)
    try:
        deadline = time.monotonic() + 5.0
        # ranks 0-1 keep beating; 2-3 go silent and must be killed by
        # the monitor thread without any fit-loop poll
        while co.world_size > 2 and time.monotonic() < deadline:
            co.heartbeat(0)
            co.heartbeat(1)
            ev = co.poll()
            if ev is not None:
                co.commit(ev)
            time.sleep(0.02)
    finally:
        co.stop_heartbeat_monitor()
    assert co.world_size == 2
    assert sorted(co.alive) == [0, 1]
