"""Telemetry subsystem acceptance (ISSUE 5).

Covers: hub metric semantics, histogram percentile math (property-tested
against numpy), JSONL schema stability (golden keys per event kind),
Prometheus exposition incl. the compile/comm registry adapters, the
background HTTP endpoint, the Speedometer warm-up-skew fix, MFU/goodput
arithmetic, and the end-to-end contract — ``fit(telemetry=True)`` yields
exactly one span per step with non-overlapping phases, per-epoch MFU/
Goodput log lines, a loadable Chrome trace, and hub overhead under 2% of
step time.
"""

import json
import logging
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_hub():
    telemetry.reset()
    yield
    telemetry.stop_http()


# -- hub basics ----------------------------------------------------------------

def test_counter_gauge_observe_with_labels():
    h = telemetry.hub()
    h.counter("reqs_total")
    h.counter("reqs_total", 2)
    h.counter("reqs_total", 1, store="dist")
    h.gauge("depth", 7)
    h.gauge("depth", 3)          # gauges overwrite
    h.observe("lat_seconds", 0.5)
    h.observe("lat_seconds", 1.5)
    snap = h.snapshot()
    assert snap["counters"]["reqs_total"] == 3
    assert snap["counters"]["reqs_total{store=dist}"] == 1
    assert snap["gauges"]["depth"] == 3
    hist = snap["histograms"]["lat_seconds"]
    assert hist["count"] == 2 and hist["sum"] == 2.0
    assert hist["min"] == 0.5 and hist["max"] == 1.5


def test_default_counter_families_preregistered():
    """A fresh process exposes the full wired-subsystem schema at zero —
    'no traffic' and 'not instrumented' must look different to a scrape."""
    snap = telemetry.hub().snapshot()
    for name in telemetry.DEFAULT_COUNTERS:
        assert name in snap["counters"], name
    dump = telemetry.prom_dump()
    for family in ("resilience_step_retries_total", "io_prefetch_batches",
                   "kvstore_push_pull_total", "checkpoint_saves_total"):
        assert family in dump, family


def test_event_ring_and_sink(tmp_path):
    h = telemetry.hub()
    for i in range(5):
        h.emit("tick", i=i)
    assert len(h.events("tick")) == 5
    assert h.events("tick", limit=2)[-1]["i"] == 4
    sink = h.add_sink(telemetry.JsonlWriter(str(tmp_path / "s.jsonl")))
    h.emit("tock", x=1)
    h.remove_sink(sink)
    sink.close()
    h.emit("tock", x=2)  # after removal: not written
    rows = telemetry.read_jsonl(str(tmp_path / "s.jsonl"))
    assert len(rows) == 1 and rows[0]["kind"] == "tock" and rows[0]["x"] == 1
    assert rows[0]["v"] == telemetry.SCHEMA_VERSION


def test_histogram_percentile_matches_numpy():
    """Property test: for windows smaller than the reservoir the hub's
    percentile must equal numpy's linear-interpolation percentile."""
    rng = np.random.RandomState(7)
    for trial in range(20):
        n = int(rng.randint(1, 500))
        values = rng.randn(n) * rng.uniform(0.1, 100.0)
        hist = telemetry.Histogram()
        for v in values:
            hist.observe(v)
        for q in (0.0, 10.0, 50.0, 90.0, 99.0, 100.0):
            expect = np.percentile(values, q)  # default 'linear'
            got = hist.percentile(q)
            assert got == pytest.approx(expect, rel=1e-9, abs=1e-9), \
                (trial, n, q)


def test_histogram_reservoir_window():
    hist = telemetry.Histogram(maxlen=100)
    for v in range(1000):
        hist.observe(float(v))
    assert hist.count == 1000 and hist.max == 999.0
    # percentiles are over the most recent window only
    assert hist.percentile(0) == 900.0


# -- exporters -----------------------------------------------------------------

def test_jsonl_schema_golden_keys(tmp_path):
    """Schema-stability: every declared event kind carries its golden keys
    (v/kind/ts + the per-kind contract in EVENT_GOLDEN_KEYS)."""
    h = telemetry.hub()
    tl = telemetry.StepTimeline()
    span = tl.begin_step(0, 0)
    span.mark("dispatch")
    span.event("step_retry")
    span.end()                                   # -> span + step_event
    h.emit("badput", reason="compile", seconds=1.0, epoch=0)
    h.emit("epoch_summary", epoch=0, steps=4, seconds=2.0, goodput_pct=90.0)
    h.emit("checkpoint", step=3, seconds=0.5, tier="t2")
    h.emit("retry", op="push", attempt=1)
    h.emit("circuit_open", op="kvstore")
    h.emit("monitor", rows=7)
    # distributed-tracing kinds (schema v2)
    h.emit("server_span", op="push", dur_ms=0.2, origin_rank=1,
           start_ts=h.now(), parent_span="t-r1-e0-s0", dedup=False)
    h.emit("server_dedup", op="push", origin_rank=1)
    telemetry.record_clock_beacon("server", h.now(), h.now(), h.now())
    h.emit("server_stats", update_count=3)
    h.emit("flight_dump", reason="manual", path="/tmp/f.json")
    h.emit("watchdog", deadline=5.0)
    h.emit("chaos", site="kvstore.push")
    # elastic-training kind (ISSUE 10)
    h.emit("resize", from_world=8, to_world=6, reason="kill:7:chaos",
           membership_epoch=1, resize_kind="shrink")
    # memory-observability kinds (ISSUE 9)
    telemetry.memory.publish_plan("train_step:abc", {
        "argument_bytes": 1024, "output_bytes": 128, "temp_bytes": 2048,
        "generated_code_bytes": 0, "alias_bytes": 0, "total_bytes": 2176})
    h.emit("memory_watermark", epoch=0, watermark_bytes=4096,
           live_bytes=2048, live_count=7)
    h.emit("memory_leak", epoch=3, drift_bytes=1 << 20, epochs=2,
           watermark_bytes=8 << 20)
    h.emit("memory_preflight", what="fit", total_bytes=4096,
           budget_bytes=None, fits=True)
    # concurrency watchdog kind (ISSUE 11)
    h.emit("lockwatch", what="cycle", cycle="a->b", closing_edge="b->a",
           thread="mx-kv-serve-1")
    # fleet-controller kinds (ISSUE 12)
    h.emit("controller", lever="evict", action="evict rank 7",
           outcome="actuated", rank=7, votes=3, dry_run=False)
    h.emit("breaker", breaker="controller", state="open",
           from_state="closed", failures=2)
    # training-health kinds (ISSUE 14)
    h.emit("health", epoch=0, step=3, loss=1.25, finite=True,
           stats={"fc1": {"grad_norm": 0.5, "weight_norm": 1.0,
                          "update_ratio": 1e-3, "nonfinite": 0}})
    h.emit("health_anomaly", reason="grad_explosion", layer="fc1",
           epoch=0, step=3, value=1e7, threshold=1e6)
    # device-time profiler kind (ISSUE 15): capture lifecycle + summary
    h.emit("profile", phase="start", owner="fit", log_dir="/tmp/t",
           steps=0, device_ms=0.0, coverage_pct=None)
    h.emit("profile", phase="summary", owner="fit", steps=4,
           device_ms=12.5, coverage_pct=91.2, window_seconds=0.05,
           unattributed_ms=1.1,
           top=[{"layer": "fc1", "op": "dot_general", "us": 9000.0}])
    # cross-run ledger kind (ISSUE 20): append_record announces each
    # persisted RunRecord through the hub itself
    rec = telemetry.ledger.distill("fit", fingerprint="fp-golden",
                                   world_size=1)
    telemetry.ledger.append_record(rec, directory=str(tmp_path / "ledger"))
    path = str(tmp_path / "events.jsonl")
    telemetry.write_jsonl(path, h.events())
    rows = telemetry.read_jsonl(path)
    seen = set()
    for row in rows:
        assert row["v"] == telemetry.SCHEMA_VERSION
        assert "ts" in row and "kind" in row
        # the v2 envelope: every event carries its rank identity
        assert "rank" in row and "world_size" in row, row
        kind = row["kind"]
        for key in telemetry.EVENT_GOLDEN_KEYS.get(kind, ()):
            assert key in row, (kind, key, row)
        seen.add(kind)
    assert set(telemetry.EVENT_GOLDEN_KEYS) <= seen, \
        f"kinds never emitted: {set(telemetry.EVENT_GOLDEN_KEYS) - seen}"


def test_read_events_v1_backward_compat(tmp_path):
    """Schema v1 files (PR 5, pre-distributed-tracing) stay readable:
    read_events fills the v2 identity defaults (rank 0 of world 1)."""
    import json

    path = str(tmp_path / "v1.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": 1, "kind": "span", "ts": 1.0, "name":
                            "step", "epoch": 0, "step": 0, "dur_ms": 2.0,
                            "phases": []}) + "\n")
        f.write(json.dumps({"v": 1, "kind": "retry", "ts": 2.0,
                            "op": "push", "attempt": 0}) + "\n")
    rows = telemetry.read_events(path)
    assert all(r["rank"] == 0 and r["world_size"] == 1 for r in rows)
    # pre-ledger files (ISSUE 20): every row backfills run_id=None
    assert all(r["run_id"] is None for r in rows)
    span = rows[0]
    assert span["span_id"] is None and span["trace_id"] is None
    assert span["wall_ts"] == span["ts"]


def test_prom_dump_format_and_adapters():
    h = telemetry.hub()
    h.counter("widgets_total", 3, kind="a b")
    h.gauge("depth", 2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe("lat_seconds", v)
    dump = telemetry.prom_dump()
    assert "# TYPE mxtpu_widgets_total counter" in dump
    # every family carries the rank/world identity labels (ISSUE 6)
    assert ('mxtpu_widgets_total{kind="a b",rank="0",world_size="1"} 3'
            in dump)
    assert 'mxtpu_depth{rank="0",world_size="1"} 2.5' in dump
    assert "# TYPE mxtpu_lat_seconds summary" in dump
    assert 'mxtpu_lat_seconds_count{rank="0",world_size="1"} 4' in dump
    assert 'quantile="0.5"' in dump
    # registry adapters: compile + comm families present via collectors
    assert "mxtpu_compile_compiles_total" in dump
    assert "mxtpu_comm_sync_steps_total" in dump
    assert "mxtpu_comm_wire_bytes_total" in dump


def test_http_endpoint_serves_metrics():
    port = telemetry.serve_http(0)
    telemetry.counter("http_probe_total", 5)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert 'mxtpu_http_probe_total{rank="0",world_size="1"} 5' in body
    health = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10).read().decode()
    assert health == "ok\n"
    telemetry.stop_http()


def test_config_resolution(monkeypatch):
    assert telemetry.TelemetryConfig.resolve(False) is None
    monkeypatch.delenv("MXNET_TPU_TELEMETRY", raising=False)
    assert telemetry.TelemetryConfig.resolve(None) is None
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "1")
    cfg = telemetry.TelemetryConfig.resolve(None)
    assert cfg is not None and cfg.timeline and cfg.mfu
    cfg = telemetry.TelemetryConfig.resolve("/tmp/x.jsonl")
    assert cfg.jsonl == "/tmp/x.jsonl"
    assert telemetry.TelemetryConfig.resolve(cfg) is cfg


# -- timeline primitives -------------------------------------------------------

def test_phase_attaches_to_current_span_and_histogram():
    tl = telemetry.StepTimeline()
    span = tl.begin_step(0, 0)
    with telemetry.phase("kvstore_push_pull"):
        time.sleep(0.002)
    span.end()
    assert [s[0] for s in span.subs] == ["kvstore_push_pull"]
    assert span.subs[0][2] >= 0.002
    p = telemetry.hub().percentile("kvstore_push_pull_seconds", 50)
    assert p is not None and p >= 0.002
    # without a span: histogram only, no crash
    with telemetry.phase("kvstore_push_pull"):
        pass


def test_mfu_epoch_report_arithmetic(caplog):
    acct = telemetry.MFUAccountant(num_devices=2, peak_flops=1e9)
    acct.flops_per_step = 1e6
    with caplog.at_level(logging.INFO):
        rep = acct.epoch_report(3, steps=100, wall_seconds=2.0,
                                compile_seconds=0.5, data_wait_seconds=0.25,
                                skipped_steps=2, step_retries=3)
    # achieved = 1e6*100/2 = 5e7 -> 5% of 1e9
    assert rep["mfu_pct"] == pytest.approx(5.0)
    # wasted: 5 steps at 20ms mean = 0.1s; badput total 0.85 of 2.0
    assert rep["badput"]["wasted_steps"] == pytest.approx(0.1)
    assert rep["goodput_pct"] == pytest.approx(100.0 * (2.0 - 0.85) / 2.0)
    assert any("MFU:" in r.message for r in caplog.records)
    assert any("Goodput:" in r.message for r in caplog.records)
    gauges = telemetry.hub().snapshot()["gauges"]
    assert gauges["mfu_pct"] == pytest.approx(5.0)
    assert gauges["goodput_pct"] == pytest.approx(rep["goodput_pct"])


def test_peak_comes_from_the_device_kind_table_or_not_at_all(monkeypatch):
    """One table keyed by device_kind; an accelerator that is not in it
    has no peak and MFU is reported unavailable, never guessed. The
    measured probe is the CPU rigs' denominator only."""
    import types

    from mxnet_tpu.telemetry import mfu

    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    other = types.SimpleNamespace(device_kind="TPU v9 mega", platform="tpu")
    assert mfu.device_peak_flops(v5e) == 197e12
    assert mfu.device_peak_flops(other) is None

    monkeypatch.delenv("MXNET_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(mfu, "measured_peak_flops",
                        lambda: pytest.fail("probe used on an accelerator"))
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [v5e])
    assert mfu.resolve_peak_flops(4) == 4 * 197e12
    monkeypatch.setattr(jax, "devices", lambda *a: [other])
    assert mfu.resolve_peak_flops(4) is None
    acct = telemetry.MFUAccountant(num_devices=4)
    acct.flops_per_step = 1e6
    assert acct.epoch_report(0, steps=10, wall_seconds=1.0)["mfu_pct"] is None
    monkeypatch.setenv("MXNET_TPU_PEAK_FLOPS", "2e12")
    assert mfu.resolve_peak_flops(4) == 8e12


# -- Speedometer warm-up skew fix ---------------------------------------------

def test_speedometer_skips_compile_polluted_window(caplog):
    from mxnet_tpu.callback import BatchEndParam, Speedometer
    from mxnet_tpu.utils import compile as compile_mod

    metric = mx.metric.create("accuracy")
    speedo = Speedometer(batch_size=32, frequent=2)
    reg = compile_mod.registry()
    with caplog.at_level(logging.INFO):
        speedo(BatchEndParam(epoch=0, nbatch=1, eval_metric=metric))
        # a compile lands inside the first window (what warm-up looks like)
        with reg.attribute("fake_prog"):
            reg._on_duration("/jax/backend_compile_duration_sec", 0.75)
        speedo(BatchEndParam(epoch=0, nbatch=2, eval_metric=metric))
        # steady-state window: no compiles -> a real throughput line
        speedo(BatchEndParam(epoch=0, nbatch=3, eval_metric=metric))
        speedo(BatchEndParam(epoch=0, nbatch=4, eval_metric=metric))
    msgs = [r.getMessage() for r in caplog.records]
    assert any("window skipped" in m and "badput/compile" in m
               for m in msgs), msgs
    assert any("samples/sec" in m and "window skipped" not in m
               for m in msgs), msgs
    counters = telemetry.hub().snapshot()["counters"]
    assert counters["badput_compile_seconds_total"] >= 0.75
    badput = telemetry.hub().events("badput")
    assert badput and badput[-1]["reason"] == "compile"


# -- end to end ----------------------------------------------------------------

def _mlp(classes=4, hidden=64):
    data = mx.sym.Variable("data")
    h1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, name="fc1", num_hidden=hidden), name="a1", act_type="relu")
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h1, name="fc2", num_hidden=classes), name="softmax")
    return out


def test_fit_telemetry_end_to_end(tmp_path, caplog):
    rng = np.random.RandomState(0)
    n_rows, batch, epochs = 256, 64, 2
    X = rng.randn(n_rows, 16).astype(np.float32)
    y = rng.randint(0, 4, (n_rows,)).astype(np.float32)
    jsonl = str(tmp_path / "run.jsonl")
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=epochs,
                           optimizer="sgd", learning_rate=0.1)
    with caplog.at_level(logging.INFO):
        model.fit(X, y, eval_data=(X[:64], y[:64]), batch_size=batch,
                  telemetry=telemetry.TelemetryConfig(jsonl=jsonl))
    tl = model.telemetry
    steps_per_epoch = n_rows // batch

    # exactly one span per train step
    steps = tl.steps("step")
    assert len(steps) == epochs * steps_per_epoch
    for i, span in enumerate(steps):
        assert span.epoch == i // steps_per_epoch
        assert span.step == i % steps_per_epoch
        phases = span.phases()
        names = [n for n, _, _ in phases]
        assert "dispatch" in names and "device" in names \
            and "host" in names
        # non-overlapping and ordered: each phase ends where the next starts
        for (_, t0, d0), (_, t1, _) in zip(phases, phases[1:]):
            assert t0 + d0 == pytest.approx(t1, abs=1e-6)
        assert phases[-1][1] + phases[-1][2] <= span.end_ts + 1e-6
        assert span.duration > 0
    # eval ran under the same timeline
    assert len(tl.steps("eval_step")) == epochs * (64 // batch)

    # per-epoch MFU/Goodput lines
    msgs = [r.getMessage() for r in caplog.records]
    for epoch in range(epochs):
        assert any(m.startswith(f"Epoch[{epoch}] MFU:") for m in msgs), msgs
        assert any(m.startswith(f"Epoch[{epoch}] Goodput:") for m in msgs)
    assert any("MFU: n/a" not in m for m in msgs if "MFU" in m)

    # chrome trace: loads as JSON, complete events carry the required keys
    trace_path = str(tmp_path / "trace.json")
    tl.dump_chrome_trace(trace_path)
    with open(trace_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    complete = [e for e in events if e.get("ph") == "X"]
    assert complete, "no complete events"
    for e in complete:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e, e
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert sum(1 for e in complete
               if e["name"].startswith("step[")) == len(steps)

    # streamed JSONL: span events arrived as the run progressed
    rows = telemetry.read_jsonl(jsonl)
    kinds = {r["kind"] for r in rows}
    assert "span" in kinds and "epoch_summary" in kinds
    span_rows = [r for r in rows if r["kind"] == "span" and r["name"] == "step"]
    assert len(span_rows) == len(steps)
    for r in span_rows[:3]:
        for key in telemetry.EVENT_GOLDEN_KEYS["span"]:
            assert key in r

    # dump_jsonl round-trips the timeline itself
    tl_path = str(tmp_path / "tl.jsonl")
    tl.dump_jsonl(tl_path)
    assert len([r for r in telemetry.read_jsonl(tl_path)
                if r["name"] == "step"]) == len(steps)

    # prometheus exposition covers the four registries' families
    dump = telemetry.prom_dump()
    for family in ("mxtpu_compile_compiles_total", "mxtpu_comm_wire_bytes",
                   "mxtpu_resilience_step_retries_total",
                   "mxtpu_io_prefetch_batches_total",
                   "mxtpu_step_seconds", "mxtpu_mfu_pct"):
        assert family in dump, family

    # hub overhead: what a step costs the hub is what the budget is made
    # of, so it is held as a count and not as a ratio of host-clock times
    # (which failed now and then: 2.24 % against 2 % on a loaded box).
    # Per step, with the timeline, MFU and the memory sampler on: the 7
    # telemetry.phase() records every fit keeps (3 of them the feed
    # thread's) + 6 observes + 12 gauges + 1 emit
    records, hub_ops = _per_step_hub_traffic(telemetry=True)
    assert records == 7 and hub_ops <= 26, (records, hub_ops)
    # ... and with telemetry off the records are all there is
    assert _per_step_hub_traffic(telemetry=None) == (7, 7)


def _per_step_hub_traffic(**fit_kwargs):
    """(span records, hub operations) one more train step costs: the
    difference between two fits of 4 and of 8 steps an epoch, so that
    set-up and per-epoch traffic cancel."""
    totals = []
    for n_rows in (256, 512):
        rng = np.random.RandomState(0)
        X = rng.randn(n_rows, 16).astype(np.float32)
        y = rng.randint(0, 4, (n_rows,)).astype(np.float32)
        h = telemetry.reset()
        calls = []
        for name in ("emit", "observe", "counter", "gauge", "record_span"):
            def counted(*args, _fn=getattr(h, name), **kwargs):
                calls.append(1)
                return _fn(*args, **kwargs)
            setattr(h, name, counted)
        model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=2,
                               optimizer="sgd", learning_rate=0.1)
        model.fit(X, y, batch_size=64, **fit_kwargs)
        totals.append((len(telemetry.span_records()), len(calls)))
    telemetry.reset()
    more_steps = 2 * (512 - 256) // 64
    return tuple((b - a) / more_steps for a, b in zip(*totals))


def test_fit_telemetry_off_leaves_no_timeline():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 4, (64,)).astype(np.float32)
    model = mx.FeedForward(_mlp(hidden=16), ctx=mx.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.1)
    model.fit(X, y, batch_size=32)
    assert getattr(model, "telemetry", None) is None
    assert telemetry.hub().events("span") == []


def test_predict_telemetry_spans():
    rng = np.random.RandomState(0)
    X = rng.randn(96, 8).astype(np.float32)
    y = rng.randint(0, 4, (96,)).astype(np.float32)
    model = mx.FeedForward(_mlp(hidden=16), ctx=mx.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.1)
    model.fit(X, y, batch_size=32)
    model.predict(X, batch_size=32, telemetry=True)
    spans = model.telemetry.steps("predict_step")
    assert len(spans) == 3
    assert all(s.kind == "predict_step" for s in spans)


def test_fit_telemetry_with_guards_counts_retries():
    """Guard retries surface as hub counters + span instant events."""
    from mxnet_tpu.resilience import chaos as chaos_mod

    rng = np.random.RandomState(0)
    X = rng.randn(128, 8).astype(np.float32)
    y = rng.randint(0, 4, (128,)).astype(np.float32)
    model = mx.FeedForward(_mlp(hidden=16), ctx=mx.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.1)
    base = telemetry.hub().snapshot()["counters"][
        "resilience_step_retries_total"]
    with chaos_mod.chaos_scope(seed=3, rules={"step.raise": 0.5}):
        model.fit(X, y, batch_size=32, guards=True, telemetry=True)
    counters = telemetry.hub().snapshot()["counters"]
    retried = counters["resilience_step_retries_total"] - base
    assert retried == model.guard_stats["step_retries"]
    assert retried > 0  # p=0.5 over 4 steps: ~0.94 chance; seed-pinned
    retry_events = [e for s in model.telemetry.steps("step")
                    for e in s.events if e["name"] == "step_retry"]
    assert len(retry_events) == retried


# -- CLI -----------------------------------------------------------------------

def test_cli_tail_and_summarize(tmp_path):
    h = telemetry.hub()
    tl = telemetry.StepTimeline()
    for i in range(3):
        s = tl.begin_step(0, i)
        s.mark("dispatch")
        s.mark("device")
        s.end()
    h.emit("badput", reason="compile", seconds=1.25, epoch=0)
    h.emit("epoch_summary", epoch=0, steps=3, seconds=0.5,
           goodput_pct=88.0, mfu_pct=12.5)
    path = str(tmp_path / "run.jsonl")
    telemetry.write_jsonl(path, h.events())
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-m", "mxnet_tpu.telemetry",
                        "tail", path, "-n", "5"], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "epoch_summary" in r.stdout
    r = subprocess.run([sys.executable, "-m", "mxnet_tpu.telemetry",
                        "summarize", path], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "spans: 3" in r.stdout
    assert "goodput 88.0%" in r.stdout and "MFU 12.5%" in r.stdout
    assert "compile" in r.stdout  # badput bucket listed


def test_record_compile_badput_dedupes_overlapping_observers():
    """Speedometer (window) and MFU epoch accounting (epoch) see the same
    compile-registry delta; the watermark must count it exactly once."""
    total0 = 1000.0  # pretend cumulative registry seconds
    before = telemetry.hub().snapshot()["counters"].get(
        "badput_compile_seconds_total", 0.0)
    first = telemetry.record_compile_badput(total0, 2.0, epoch=0)
    again = telemetry.record_compile_badput(total0, 2.0, epoch=0)
    assert first == pytest.approx(2.0) and again == 0.0
    # a later, larger window overlapping the counted region only adds the
    # uncounted tail
    tail = telemetry.record_compile_badput(total0 + 0.5, 2.5, epoch=0)
    assert tail == pytest.approx(0.5)
    counters = telemetry.hub().snapshot()["counters"]
    assert counters["badput_compile_seconds_total"] - before == \
        pytest.approx(2.5)


def test_score_after_fit_does_not_extend_fit_timeline():
    """fit() must clear the active timeline on exit: a later score() is
    not part of the traced run and must not sync per batch or append
    spans to the finished timeline."""
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 4, (64,)).astype(np.float32)
    model = mx.FeedForward(_mlp(hidden=16), ctx=mx.cpu(), num_epoch=1,
                           optimizer="sgd", learning_rate=0.1)
    model.fit(X, y, batch_size=32, telemetry=True)
    n_before = len(model.telemetry.spans)
    model.score(X, y=y, batch_size=32)
    assert len(model.telemetry.spans) == n_before
    assert telemetry.current_span() is None
