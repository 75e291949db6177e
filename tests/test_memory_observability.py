"""Memory-observability acceptance (ISSUE 9).

Covers: static per-program memory plans (AOT registration, debug_str
reading the registry instead of re-compiling, Prometheus/table export),
the live-array ledger (weakref byte accounting, watermarks, the epoch
leak detector), OOM preflight (the fail-fast over-budget gate with its
ranked report), flight-recorder memory forensics, the memory CLI
(``mem`` table + ``diff`` peak-memory gate), the memory_stats
pass-through contract, and the zero-recompile armed epoch with tracking
enabled."""

import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import memory as mem_mod
from mxnet_tpu.utils import compile as cm
from mxnet_tpu.utils.memory import memory_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state():
    telemetry.reset()
    telemetry.track_arrays(False)
    mem_mod.detach_sampler()
    mem_mod.reset_leak_tracker()
    mem_mod.ledger().clear()
    yield
    telemetry.track_arrays(False)
    mem_mod.detach_sampler()
    mem_mod.ledger().clear()


def _mlp():
    data = mx.sym.Variable("data")
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        data, name="fc", num_hidden=4), name="softmax")
    return out


def _digits(n=128, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, dim).astype(np.float32),
            rng.randint(0, classes, (n,)).astype(np.float32))


# -- utils.memory_stats contract -----------------------------------------------

def test_memory_stats_passthrough_and_zero_contract():
    """Satellite: backend stats keys pass through instead of being
    dropped; the zeros-on-CPU contract holds when nothing is exposed."""

    class _Rich:
        def memory_stats(self):
            return {"bytes_in_use": 100, "peak_bytes_in_use": 200,
                    "bytes_limit": 1000, "largest_alloc_size": 64,
                    "num_allocs": 7, "pool_bytes": 4096}

        def __str__(self):
            return "FakeTPU:0"

    class _Bare:
        def memory_stats(self):
            return None

        def __str__(self):
            return "FakeCPU:0"

    rich = memory_stats(_Rich())["FakeTPU:0"]
    assert rich["largest_alloc_size"] == 64
    assert rich["num_allocs"] == 7
    assert rich["pool_bytes"] == 4096
    assert rich["bytes_in_use"] == 100
    bare = memory_stats(_Bare())["FakeCPU:0"]
    assert bare == {"bytes_in_use": 0, "peak_bytes_in_use": 0,
                    "bytes_limit": 0}
    # the real local backend honors the same always-present contract
    for row in memory_stats().values():
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            assert key in row


# -- static memory plans -------------------------------------------------------

def test_precompile_registers_plan_and_exports():
    """AOT warmup registers the program's memory_analysis breakdown in
    the compile registry, publishes labeled hub gauges, emits a
    memory_plan event, and the plan table renders it."""
    import jax
    import jax.numpy as jnp

    tj = cm.tracked_jit(lambda x: (x @ x).sum(), label="memtest:fwd")
    tj.precompile(jax.ShapeDtypeStruct((64, 64), jnp.float32))
    plan = cm.registry().memory_plan_for("memtest:fwd")
    assert plan is not None
    assert plan["argument_bytes"] == 64 * 64 * 4
    assert plan["total_bytes"] == plan["temp_bytes"] + plan["output_bytes"]
    events = telemetry.hub().events(kind="memory_plan")
    assert any(e["program"] == "memtest:fwd" for e in events)
    dump = telemetry.prom_dump()
    assert 'mxtpu_memory_plan_total_bytes{program="memtest:fwd"' in dump
    assert "memtest:fwd" in telemetry.plan_table()


def test_plans_republished_to_fresh_hub():
    """telemetry.reset() must not lose the plan gauges (on_hub_create
    re-publishes; the registry stays the owner)."""
    import jax
    import jax.numpy as jnp

    tj = cm.tracked_jit(lambda x: x * 2.0, label="memtest:republish")
    tj.precompile(jax.ShapeDtypeStruct((8,), jnp.float32))
    telemetry.reset()
    dump = telemetry.prom_dump()
    assert 'program="memtest:republish"' in dump


def test_debug_str_reads_plan_without_recompiling():
    """Satellite: a warmed executor's debug_str reads the registered plan
    (zero compiles); a never-compiled executor pays the fallback ONCE and
    registers the plan for the next call. Printed MB == plan MB."""
    out = _mlp()
    exe = out.simple_bind(mx.cpu(), data=(32, 8))
    exe.precompile(is_train=False)
    before = cm.registry().snapshot()["compiles"]
    s = exe.debug_str()
    assert cm.registry().snapshot()["compiles"] == before, \
        "debug_str re-compiled a warmed program"
    label = exe._fwd_fns[False].label  # THIS executor's warmed program
    plan = cm.registry().memory_plan_for(label)
    assert plan is not None
    assert f"Total {plan['total_bytes'] / (1 << 20):.4f} MB allocated" in s

    # fallback path: fresh executor, no plan -> one compile, then cached
    exe2 = _mlp().simple_bind(mx.cpu(), data=(16, 8))
    cm.reset_compile_stats()
    s2 = exe2.debug_str()
    assert "MB allocated" in s2
    mid = cm.registry().snapshot()["compiles"]
    assert mid >= 1
    s3 = exe2.debug_str()
    assert cm.registry().snapshot()["compiles"] == mid
    assert s3 == s2


# -- live-array ledger ---------------------------------------------------------

def test_ledger_tracks_live_bytes_and_watermark():
    prev = telemetry.track_arrays(True)
    led = mem_mod.ledger()
    base = led.live_bytes()
    a = mx.nd.zeros((128, 128))
    stats = led.stats()
    assert stats["live_bytes"] - base >= 128 * 128 * 4
    assert any(row["bytes"] >= 128 * 128 * 4 for row in led.top_arrays(3))
    peak = led.watermark_bytes
    del a
    gc.collect()
    assert led.live_bytes() < peak  # freed arrays leave the ledger
    assert led.watermark_bytes == peak  # ...but not the watermark
    telemetry.track_arrays(prev)


def test_ledger_dedups_wrappers_of_one_buffer():
    """NDArray(existing) and same-device as_in_context share one
    jax.Array — the ledger must count the BUFFER once, and free it only
    when the last wrapper dies."""
    prev = telemetry.track_arrays(True)
    led = mem_mod.ledger()
    try:
        base = led.live_bytes()
        a = mx.nd.zeros((64, 64))
        once = led.live_bytes() - base
        b = mx.nd.NDArray(a)      # shares a._data
        c = a.as_in_context(a.context)  # same-device: returns a itself
        assert led.live_bytes() - base == once, "wrapper double-counted"
        del a, c
        gc.collect()
        assert led.live_bytes() - base == once, "freed while b holds it"
        del b
        gc.collect()
        assert led.live_bytes() == base
    finally:
        telemetry.track_arrays(prev)


def test_debug_str_distinguishes_shapes_of_one_symbol():
    """Two executors of the SAME symbol at different shapes share a
    program label; each debug_str must print ITS OWN totals, not the
    other's registered plan."""
    sym = _mlp()
    small = sym.simple_bind(mx.cpu(), data=(2, 8))
    big = sym.simple_bind(mx.cpu(), data=(512, 8))
    s_small = small.debug_str()
    s_big = big.debug_str()
    total_small = next(l for l in s_small.splitlines() if "Total" in l)
    total_big = next(l for l in s_big.splitlines() if "Total" in l)
    assert total_small != total_big
    # and re-printing the small one is not poisoned by big's plan
    assert next(l for l in small.debug_str().splitlines()
                if "Total" in l) == total_small


def test_phase_sampler_publishes_gauges():
    prev = telemetry.track_arrays(True)
    mem_mod.attach_sampler()
    try:
        keep = mx.nd.zeros((64, 64))
        tl = telemetry.StepTimeline()
        with tl.begin_step(0, 0) as span:
            span.mark("device")
        snap = telemetry.hub().snapshot()["gauges"]
        assert snap.get("live_array_bytes", 0) >= 64 * 64 * 4
        assert snap.get("live_array_watermark_bytes", 0) >= \
            snap["live_array_bytes"]
        del keep
    finally:
        mem_mod.detach_sampler()
        telemetry.track_arrays(prev)


def test_epoch_leak_detector_emits_incident():
    """Three epochs of >threshold watermark growth -> memory_leak event,
    and the flight recorder catches it in the incident ring."""
    prev = telemetry.track_arrays(True)
    mem_mod.reset_leak_tracker()
    hoard = []
    try:
        leaks = []
        for epoch in range(3):
            hoard.append(mx.nd.zeros((256, 256)))  # +256KB per epoch
            leak = mem_mod.epoch_mark(epoch, drift_bytes=1024,
                                      consecutive=2)
            leaks.append(leak)
        assert leaks[0] is None  # first epoch: no baseline to drift from
        assert leaks[2] is not None
        events = telemetry.hub().events(kind="memory_leak")
        assert events and events[-1]["epoch"] == 2
        _, _, incidents = telemetry.flight.recorder().snapshot()
        assert any(e["kind"] == "memory_leak" for e in incidents)
        marks = telemetry.hub().events(kind="memory_watermark")
        assert len(marks) == 3
    finally:
        telemetry.track_arrays(prev)


def test_steady_state_does_not_flag_leak():
    prev = telemetry.track_arrays(True)
    mem_mod.reset_leak_tracker()
    try:
        for epoch in range(4):
            a = mx.nd.zeros((64, 64))  # same transient every epoch
            del a
            gc.collect()
            assert mem_mod.epoch_mark(epoch, drift_bytes=1024,
                                      consecutive=2) is None
        assert telemetry.hub().events(kind="memory_leak") == []
    finally:
        telemetry.track_arrays(prev)


# -- OOM preflight -------------------------------------------------------------

def test_preflight_report_ranking_and_pass():
    report = mem_mod.preflight(
        [("param:small", 10), ("param:big", 1000), ("opt:mid", 100)],
        budget=10_000, what="test")
    assert report["fits"] is True
    assert report["entries"][0] == ("param:big", 1000)
    assert telemetry.hub().events(kind="memory_preflight")


def test_preflight_rejects_over_budget_fit_before_any_step(monkeypatch):
    """Acceptance: a synthetic over-budget model is rejected BEFORE any
    step runs, with a ranked byte report naming arrays/programs."""
    monkeypatch.setenv("MXNET_TPU_HBM_BYTES", "64")
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    before = cm.registry().snapshot()
    with pytest.raises(telemetry.MemoryPreflightError) as ei:
        model.fit(X, y, batch_size=32)
    msg = str(ei.value)
    assert "exceeds" in msg and "param:" in msg and "MB" in msg
    # ranked: first listed allocation is the largest
    first = float(msg.splitlines()[1].split("MB")[0])
    for line in msg.splitlines()[2:]:
        assert float(line.split("MB")[0]) <= first
    after = cm.registry().snapshot()
    assert after["misses"] == before["misses"], "a step program compiled"


def test_preflight_rejects_over_budget_precompile(monkeypatch):
    """precompile's gate uses the EXACT warmed program plans."""
    monkeypatch.setenv("MXNET_TPU_HBM_BYTES", "64")
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    with pytest.raises(telemetry.MemoryPreflightError) as ei:
        model.precompile(data_shapes={"data": (32, 8)},
                         label_shapes={"softmax_label": (32,)})
    assert "program temp+output" in str(ei.value)


def test_generous_budget_trains_and_reports(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HBM_BYTES", str(1 << 30))
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    model.fit(X, y, batch_size=32)
    events = telemetry.hub().events(kind="memory_preflight")
    assert events and events[-1]["fits"] is True


# -- forensics -----------------------------------------------------------------

def test_flight_dump_carries_memory_snapshot(tmp_path):
    prev = telemetry.track_arrays(True)
    try:
        keep = mx.nd.zeros((64, 64))
        path = str(tmp_path / "flight.json")
        telemetry.flight.dump(path, reason="test")
        ok, payload = telemetry.validate_flight(path)
        assert ok, payload
        mem = payload["memory"]
        assert mem["tracking"] is True
        assert mem["ledger"]["live_bytes"] >= 64 * 64 * 4
        assert "allocator" in mem
        del keep
    finally:
        telemetry.track_arrays(prev)


def test_flight_show_renders_and_degrades_without_memory(tmp_path):
    """Satellite: `flight show` renders the memory section; a dump
    without one (pre-ISSUE-9, or a torn snapshot stripped by a tool)
    still validates and shows instead of failing."""
    import zlib

    from mxnet_tpu.telemetry.__main__ import main as cli

    prev = telemetry.track_arrays(True)
    try:
        mx.nd.zeros((32, 32)).wait_to_read()
        path = str(tmp_path / "flight.json")
        telemetry.flight.dump(path, reason="test")
    finally:
        telemetry.track_arrays(prev)
    assert cli(["flight", "show", path]) == 0

    # strip the memory section and re-seal the CRC: must still show clean
    blob = json.load(open(path))
    del blob["payload"]["memory"]
    body = json.dumps(blob["payload"], sort_keys=True, default=str)
    blob["crc32"] = zlib.crc32(body.encode()) & 0xFFFFFFFF
    bare = str(tmp_path / "bare.json")
    json.dump(blob, open(bare, "w"))
    assert cli(["flight", "validate", bare]) == 0
    assert cli(["flight", "show", bare]) == 0


# -- CLI -----------------------------------------------------------------------

def test_mem_cli_table_and_diff_gate(tmp_path):
    from mxnet_tpu.telemetry.__main__ import main as cli

    h = telemetry.hub()
    mem_mod.publish_plan("train_step:abc:bucket=16", {
        "argument_bytes": 1 << 20, "output_bytes": 1 << 18,
        "temp_bytes": 1 << 21, "generated_code_bytes": 0,
        "alias_bytes": 0, "total_bytes": (1 << 21) + (1 << 18)})
    h.emit("memory_watermark", epoch=0, watermark_bytes=1 << 20,
           live_bytes=1 << 19, live_count=12)
    a_path = str(tmp_path / "a.jsonl")
    telemetry.write_jsonl(a_path, h.events())
    assert cli(["mem", a_path]) == 0

    # diff: run B doubles the peak watermark -> peak_mem_mb regression
    telemetry.reset()
    h = telemetry.hub()
    h.emit("memory_watermark", epoch=0, watermark_bytes=2 << 20,
           live_bytes=1 << 19, live_count=12)
    b_path = str(tmp_path / "b.jsonl")
    telemetry.write_jsonl(b_path, h.events())
    assert cli(["diff", a_path, b_path, "--threshold", "50"]) == 3
    assert cli(["diff", a_path, a_path, "--threshold", "50"]) == 0


def test_mem_cli_no_events(tmp_path):
    from mxnet_tpu.telemetry.__main__ import main as cli

    path = str(tmp_path / "empty.jsonl")
    telemetry.write_jsonl(path, [{"kind": "span", "ts": 0.0}])
    assert cli(["mem", path]) == 1


# -- the zero-recompile invariant ----------------------------------------------

def test_zero_recompile_armed_epoch_with_memory_tracking():
    """Acceptance: the ledger + phase-boundary sampler are host-side
    bookkeeping — jit cache keys are untouched, the armed epoch stays
    green, and every epoch closes a watermark mark."""
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=3,
                           learning_rate=0.1)
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()

    try:
        model.fit(X, y, batch_size=32, telemetry=True,
                  epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    assert tracker.recompiles == []
    assert len(model.telemetry.steps("step")) == 12
    marks = telemetry.hub().events(kind="memory_watermark")
    assert [e["epoch"] for e in marks] == [0, 1, 2]
    # the ledger saw the tracked run: every epoch's window held arrays
    assert all(e["watermark_bytes"] > 0 for e in marks), marks
    assert not telemetry.memory.tracking_enabled()  # fit restored state


def test_warmed_fit_exports_plan_per_program(monkeypatch):
    """Acceptance: a precompile-warmed fit exposes the per-program plan
    through the CLI table and the Prometheus dump with rank/world
    labels."""
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    info = model.precompile(data_shapes={"data": (32, 8)},
                            label_shapes={"softmax_label": (32,)})
    plans = cm.registry().memory_plans()
    for label in info["labels"]:
        assert label in plans, f"no memory plan for warmed {label}"
        assert plans[label]["total_bytes"] > 0
    model.fit(X, y, batch_size=32)
    dump = telemetry.prom_dump()
    label = info["labels"][0]
    line = next(l for l in dump.splitlines()
                if "memory_plan_total_bytes" in l and label in l)
    assert 'rank="0"' in line and 'world_size="1"' in line
    assert label in telemetry.plan_table()
