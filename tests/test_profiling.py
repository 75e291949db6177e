"""Device-time profiler acceptance (ISSUE 15).

Covers: config resolution and the capture doorway (hub events, soft
failure on concurrent windows, finally-safe stop), named-scope provenance
landing in HLO op metadata (executor scopes AND user ``profile_scope``
annotations), trace parsing + attribution on a real capture, the e2e
contract — a profiled dp-8 ``fit`` window attributes >= 80% of in-window
device time to named layers/kernels with an explicit unattributed row,
produces ``source: "measured"`` roofline rows joined to the FLOP models,
reconciles measured vs modeled MFU, prices the window as ``profile``
badput, and stays green under the armed zero-recompile epoch stacked on
compression + overlap + fused-Adam + guards + health — plus
``predict(profile=...)``, the flight-recorder profile section (CRC-valid
with and without), the ``telemetry profile`` CLI, the per-op rows in the
``telemetry diff`` CI gate, schema back-fill, and the out-of-window
overhead bound (<0.5% of a step).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import profiling
from mxnet_tpu.utils import compile as cm
from mxnet_tpu.utils import profiler as profiler_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_hub():
    telemetry.reset()
    yield
    # a failing test must never leak a running process-global trace into
    # the rest of the suite
    profiling.stop_capture()


def _ctx8():
    return [mx.cpu(i) for i in range(8)]


def _mlp(hidden=64, classes=4, dim=10):
    data = mx.sym.Variable("data")
    h1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h1, name="fc2", num_hidden=classes), name="softmax")


def _blobs(n=160, dim=10, classes=4):
    rng = np.random.RandomState(0)
    X = rng.randn(n, dim).astype(np.float32)
    y = rng.randint(0, classes, (n,)).astype(np.float32)
    return X, y


# -- config + capture doorway --------------------------------------------------

def test_profile_config_resolution(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PROFILE", raising=False)
    assert profiling.ProfileConfig.resolve(None) is None
    assert profiling.ProfileConfig.resolve(False) is None
    cfg = profiling.ProfileConfig.resolve(True)
    assert cfg.steps == 6 and cfg.warmup == 2
    assert profiling.ProfileConfig.resolve(9).steps == 9
    assert profiling.ProfileConfig.resolve(cfg) is cfg
    monkeypatch.setenv("MXNET_TPU_PROFILE", "0")
    assert profiling.ProfileConfig.resolve(None) is None
    monkeypatch.setenv("MXNET_TPU_PROFILE", "1")
    assert profiling.ProfileConfig.resolve(None).steps == 6
    monkeypatch.setenv("MXNET_TPU_PROFILE", "12")
    assert profiling.ProfileConfig.resolve(None).steps == 12
    # 0 means off everywhere: a computed "no window" stays a no-op, like
    # the env gate's MXNET_TPU_PROFILE=0
    assert profiling.ProfileConfig.resolve(0) is None
    assert profiling.ProfileConfig.resolve(-3) is None
    with pytest.raises(ValueError):
        profiling.ProfileConfig.resolve(1.5)


def test_capture_emits_hub_events_and_fails_soft(tmp_path):
    """The capture doorway: start/stop are hub events (a JSONL sink sees
    every capture), a concurrent window raises for the CALLER to handle,
    and an unmatched stop is a safe no-op."""
    assert profiling.stop_capture() == (None, 0.0)  # finally-safe
    d = str(tmp_path / "trace")
    with profiling.capture(d, owner="test"):
        assert profiling.capture_active() == d
        with pytest.raises(RuntimeError):
            profiling.start_capture(str(tmp_path / "other"))
    assert profiling.capture_active() is None
    phases = [e["phase"] for e in telemetry.hub().events(kind="profile")]
    assert phases == ["start", "capture"]
    caps = [e for e in telemetry.hub().events(kind="profile")
            if e["phase"] == "capture"]
    assert caps[0]["seconds"] > 0 and caps[0]["owner"] == "test"


def test_profiler_module_routes_through_capture_path(tmp_path):
    """ISSUE 15 satellite: utils.profiler.start_trace/stop_trace and
    profile_step ride the shared capture path — hub events, one window
    at a time — instead of a second uninstrumented doorway."""
    d = str(tmp_path / "t")
    profiler_mod.start_trace(d)
    try:
        assert profiling.capture_active() == d
    finally:
        profiler_mod.stop_trace()
    assert profiling.capture_active() is None

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    stats, log_dir = profiler_mod.profile_step(
        f, x, iters=2, log_dir=str(tmp_path / "ps"))
    assert stats and stats[0].total_us > 0
    phases = [e["phase"] for e in telemetry.hub().events(kind="profile")]
    assert phases == ["start", "capture", "start", "capture"]


def test_profile_scope_lands_in_hlo_metadata():
    """ISSUE 15 satellite: a user ``profile_scope`` annotation doubles as
    a named_scope, so its ops carry the scope in HLO op metadata and the
    attribution tables can name them like a framework layer."""
    def f(x):
        with profiler_mod.profile_scope("userblock"):
            return jnp.tanh(x @ x)

    txt = jax.jit(f).lower(jnp.ones((32, 32))).compile().as_text()
    _, meta = profiling.hlo_op_metadata(txt)
    assert any("userblock" in v for v in meta.values()), meta
    layer, prim = profiling.attribute_op_name(
        next(v for v in meta.values() if "userblock" in v), {"userblock"})
    assert layer == "userblock"


# -- attribution machinery -----------------------------------------------------

def test_attribute_op_name_unwraps_transforms():
    layers = {"fc1", "a1"}
    cases = [
        ("jit(step)/jit(main)/jvp(fc1/FullyConnected)/dot_general",
         "fc1", "dot_general"),
        ("jit(step)/jit(main)/transpose(jvp(fc1/FullyConnected))/dot_general",
         "fc1", "dot_general"),
        ("jit(step)/jit(main)/shmap_body/a1/Activation/tanh", "a1", "tanh"),
        ("jit(step)/jit(main)/optimizer/update/sub", "optimizer", "sub"),
        ("jit(step)/jit(main)/comm/allreduce/psum", "comm", "psum"),
        ("jit(step)/jit(main)/convert_element_type", None,
         "convert_element_type"),
    ]
    for op_name, want_layer, want_prim in cases:
        layer, prim = profiling.attribute_op_name(op_name, layers)
        assert (layer, prim) == (want_layer, want_prim), op_name


def test_parse_and_build_report_on_real_capture(tmp_path):
    """Capture a scoped jitted fn, parse the trace, join through the HLO
    metadata map: the report attributes the layers, carries an explicit
    unattributed remainder, and its coverage is consistent."""
    def f(x, w1, w2):
        with jax.named_scope("l1"):
            h = jnp.tanh(x @ w1)
        with jax.named_scope("l2"):
            return jnp.sum(h @ w2)

    jf = jax.jit(f)
    x = jnp.ones((256, 256))
    w1 = jnp.ones((256, 256))
    w2 = jnp.ones((256, 64))
    jax.block_until_ready(jf(x, w1, w2))  # compile outside the window
    d = str(tmp_path / "trace")
    with profiling.capture(d):
        for _ in range(3):
            out = jf(x, w1, w2)
        jax.block_until_ready(out)
    rows = profiling.parse_trace_dir(d)
    assert rows and all(r["us"] >= 0 for r in rows.values())
    _, meta = profiling.hlo_op_metadata(
        jf.lower(x, w1, w2).compile().as_text())
    report = profiling.build_report(rows, [meta], {"l1", "l2"}, steps=3,
                                    window_seconds=0.1)
    assert report.total_us > 0
    assert {"l1", "l2"} <= set(report.layers)
    assert report.coverage_pct > 50.0
    assert abs(report.attributed_us + report.unattributed_us
               - report.total_us) < 1e-6
    top = report.to_dict(top_k=5)["top"]
    assert len(top) <= 5 and top[0]["us"] >= top[-1]["us"]


def test_parse_trace_dir_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        profiling.parse_trace_dir(str(tmp_path / "empty"))


def test_measured_peak_bandwidth_cached():
    bw = profiling.measured_peak_bandwidth()
    assert bw > 0
    assert profiling.measured_peak_bandwidth() == bw  # cached


# -- the e2e fit contract ------------------------------------------------------

def _profiled_fit(tmp_path, **fit_kwargs):
    X, y = _blobs(256)
    model = mx.FeedForward(_mlp(), ctx=_ctx8(), num_epoch=2,
                           optimizer="adam", fused=True,
                           learning_rate=0.01)
    jsonl = str(tmp_path / "run.jsonl")
    model.fit(X, y, batch_size=32,
              telemetry=telemetry.TelemetryConfig(jsonl=jsonl,
                                                  memory=False),
              profile=telemetry.ProfileConfig(steps=4, warmup=2),
              **fit_kwargs)
    return model, jsonl


def test_fit_profile_window_acceptance(tmp_path):
    """ACCEPTANCE: a profiled dp-8 fit window (guards + health + int8
    compression + overlap + fused-Adam stacked) attributes >= 80% of
    in-window device time to named layers/kernels, reports the coverage
    ratio and an explicit unattributed row, joins measured roofline rows
    to the registry FLOP models with source="measured", reconciles
    measured vs modeled MFU, and prices the window as profile badput."""
    model, jsonl = _profiled_fit(tmp_path, guards=True, health=True,
                                 compression="int8", overlap=True)
    rep = model.profile_report
    assert rep is not None and rep.steps == 4
    assert rep.coverage_pct >= 80.0, rep.table()
    assert rep.unattributed_us >= 0.0
    # real model layers attributed, not just the pseudo-categories
    assert {"fc1", "fc2"} <= set(rep.layers), rep.layers
    assert "comm" in rep.layers  # the int8 sync's device cost is named
    # the hotspot table is sorted and its head holds device time
    assert rep.ops and rep.ops[0]["us"] > 0
    # measured roofline: source stamped, models joined, bound classified
    assert rep.roofline, "no measured roofline rows"
    for row in rep.roofline:
        assert row["source"] == "measured"
        assert row["model_flops"] > 0 and row["measured_ms_per_step"] > 0
        assert row.get("bound") in ("compute", "bandwidth", None)
    prims = {r["op"] for r in rep.roofline}
    assert "dot_general" in prims
    # measured-vs-modeled MFU reconciliation resolved
    assert rep.mfu["measured_mfu_pct"] is not None
    assert rep.mfu["modeled_mfu_pct"] is not None
    assert rep.mfu["delta_pct"] == pytest.approx(
        rep.mfu["measured_mfu_pct"] - rep.mfu["modeled_mfu_pct"])

    # the window is priced as `profile` badput — observation, not
    # throughput — and the epoch summary carries the bucket
    h = telemetry.hub()
    bads = [e for e in h.events(kind="badput")
            if e.get("reason") == "profile"]
    assert bads and bads[0]["seconds"] > 0
    snap = h.snapshot()
    assert snap["counters"].get("badput_profile_seconds_total", 0) > 0
    epochs = [e for e in h.events(kind="epoch_summary")]
    assert any(e.get("badput_profile_seconds", 0) > 0 for e in epochs)

    # surface: summary event with golden keys + per-layer gauges
    summaries = [e for e in h.events(kind="profile")
                 if e.get("phase") == "summary"]
    assert len(summaries) == 1
    s = summaries[0]
    for key in telemetry.EVENT_GOLDEN_KEYS["profile"]:
        assert key in s, key
    assert s["steps"] == 4 and s["coverage_pct"] >= 80.0
    gauges = snap["gauges"]
    assert gauges.get("profile_coverage_pct", 0) >= 80.0
    assert any(k.startswith("profile_layer_device_ms") for k in gauges), \
        sorted(gauges)

    # the JSONL stream saw the capture lifecycle
    rows = telemetry.read_events(jsonl)
    phases = [e["phase"] for e in rows if e.get("kind") == "profile"]
    assert phases == ["start", "capture", "summary"]


def test_fit_profile_zero_recompile_full_stack():
    """ACCEPTANCE: the armed zero-recompile epoch stays green with
    named-scope annotations + a profiling window stacked on compression +
    overlap + fused-Adam + guards + health — scopes are trace-time
    metadata, and the window's HLO harvest precompiles (never a cache
    miss)."""
    X, y = _blobs(160)
    model = mx.FeedForward(_mlp(), ctx=_ctx8(), num_epoch=3,
                           optimizer="adam", fused=True,
                           learning_rate=0.01)
    tracker = cm.RecompileTracker(raise_on_recompile=True)

    def arm_after_first(epoch, *_):
        if epoch == 0:
            tracker.arm()

    cm.reset_compile_stats()
    try:
        # warmup=6 places the window in epoch 2 — inside the ARMED span,
        # so the capture machinery itself is proven recompile-free
        model.fit(X, y, batch_size=32, compression="int8", overlap=True,
                  guards=True, health=True,
                  profile=telemetry.ProfileConfig(steps=3, warmup=6),
                  epoch_end_callback=arm_after_first)
    finally:
        tracker.disarm()
    assert tracker.recompiles == []
    per = cm.compile_stats()["per_function"]
    train = [c for lbl, c in per.items() if lbl.startswith("train_step:")]
    assert train and train[0]["misses"] == 1  # compiled exactly once
    assert model.profile_report is not None
    assert model.profile_report.coverage_pct >= 80.0


def test_fit_profile_out_of_window_overhead():
    """ACCEPTANCE: once the window closes, the loop's per-step profiler
    cost is one state poll — priced per-poll against the session's own
    measured window, far under 0.5% of a step."""
    import time

    ses = profiling.ProfileSession(telemetry.ProfileConfig(), layers=())
    ses._state = "done"
    reps = 50000
    t0 = time.perf_counter()
    for _ in range(reps):
        _ = ses.pending
        _ = ses.open
    poll_s = (time.perf_counter() - t0) / reps
    # 0.5% of even a very fast 1 ms step is 5 us; the poll is ~100 ns
    assert poll_s < 5e-6, f"out-of-window poll {poll_s * 1e9:.0f} ns"
    # and a done session's hooks are no-ops
    assert ses.after_step(None) == 0.0
    assert ses.close() == 0.0


def test_predict_profile_emits_summary(tmp_path):
    X, _ = _blobs(256)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    model._init_params({"data": (32, 10), "softmax_label": (32,)})
    out = model.predict(X, batch_size=32,
                        profile=telemetry.ProfileConfig(steps=3, warmup=1))
    assert out.shape == (256, 4)
    rep = model.profile_report
    assert rep is not None and rep.steps == 3
    assert rep.coverage_pct > 0
    summaries = [e for e in telemetry.hub().events(kind="profile")
                 if e.get("phase") == "summary"]
    assert summaries and summaries[0]["owner"] == "predict"


def test_reused_log_dir_isolates_windows(tmp_path):
    """A ProfileConfig with an explicit log_dir can be reused: every
    window captures into its own subdirectory, so a second run's report
    never folds the first window's trace events into its totals."""
    cfg = telemetry.ProfileConfig(steps=3, warmup=1,
                                  log_dir=str(tmp_path / "prof"))
    X, _ = _blobs(256)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    model._init_params({"data": (32, 10), "softmax_label": (32,)})
    model.predict(X, batch_size=32, profile=cfg)
    first = model.profile_report
    model.predict(X, batch_size=32, profile=cfg)
    second = model.profile_report
    # the structural fix: sibling per-window directories under the
    # configured dir, so the second parse cannot see the first's files
    assert first.log_dir != second.log_dir
    assert os.path.dirname(first.log_dir) == str(tmp_path / "prof")
    assert os.path.dirname(second.log_dir) == str(tmp_path / "prof")
    assert first.steps == second.steps == 3

    # same program, same window length: each window's directory holds
    # every instruction of the predict program once a step, not a
    # two-window aggregate (the bug's reading, which the shared parent
    # directory still shows: twice the steps)
    def step_counts(log_dir):
        rows = profiling.parse_trace_dir(log_dir)
        return {instr: row["count"] for (module, instr), row in rows.items()
                if module == "jit_step"}

    one, two = step_counts(first.log_dir), step_counts(second.log_dir)
    assert one and set(one) == set(two)
    assert set(one.values()) == set(two.values()) == {3}, (one, two)
    both = step_counts(str(tmp_path / "prof"))
    assert set(both) == set(one) and set(both.values()) == {6}, both


def test_short_predict_closes_partial_window():
    """A dataset shorter than warmup+steps still closes cleanly: the
    partial window publishes what it captured and the process-global
    profiler is released."""
    X, _ = _blobs(96)
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    model._init_params({"data": (32, 10), "softmax_label": (32,)})
    model.predict(X, batch_size=32,
                  profile=telemetry.ProfileConfig(steps=50, warmup=1))
    assert profiling.capture_active() is None
    rep = model.profile_report
    assert rep is not None and 0 < rep.steps < 50


# -- flight-recorder section ---------------------------------------------------

def test_flight_dump_embeds_last_capture(tmp_path):
    """Flight dumps embed the last capture summary; dumps from
    un-profiled processes simply lack the section — both CRC-validate."""
    from mxnet_tpu.telemetry import flight

    # no capture yet in this hub epoch: absence is graceful
    profiling._set_last_summary(None)
    p0 = str(tmp_path / "no_profile.json")
    flight.dump(p0, reason="test")
    ok, payload = telemetry.validate_flight(p0)
    assert ok and "profile" not in payload

    model, _ = _profiled_fit(tmp_path)
    p1 = str(tmp_path / "with_profile.json")
    flight.dump(p1, reason="test")
    ok, payload = telemetry.validate_flight(p1)
    assert ok, payload
    prof = payload["profile"]
    assert prof["steps"] == 4 and prof["coverage_pct"] > 0
    assert prof["top"], prof


# -- CLI + diff gate -----------------------------------------------------------

def _cli(argv):
    from mxnet_tpu.telemetry.__main__ import main

    return main(argv)


def test_profile_cli_renders_hotspots(tmp_path, capsys):
    _, jsonl = _profiled_fit(tmp_path)
    rc = _cli(["profile", jsonl])
    out = capsys.readouterr().out
    assert rc == 0
    assert "device profile:" in out and "coverage" in out
    assert "dot_general" in out
    assert "measured roofline" in out and "MFU: measured" in out
    # flight show renders the embedded section too
    from mxnet_tpu.telemetry import flight

    dump = str(tmp_path / "f.json")
    flight.dump(dump, reason="test")
    rc = _cli(["flight", "show", dump])
    out = capsys.readouterr().out
    assert rc == 0 and "last device-profile capture:" in out


def test_profile_cli_without_summary(tmp_path, capsys):
    path = str(tmp_path / "empty.jsonl")
    telemetry.write_jsonl(path, [{"kind": "span", "ts": 1.0, "name": "step",
                                  "epoch": 0, "step": 0, "dur_ms": 1.0,
                                  "phases": [], "trace_id": None,
                                  "span_id": None, "rank": 0}])
    assert _cli(["profile", path]) == 1
    assert "no profile summary" in capsys.readouterr().out


def _summary_event(op_us):
    top = [{"layer": "fc1", "op": op, "us": us, "count": 4, "pct": 50.0,
            "program": "jit_step", "ms_per_step": us / 1e3 / 4}
           for op, us in op_us.items()]
    return {"kind": "profile", "phase": "summary", "steps": 4,
            "device_ms": sum(op_us.values()) / 1e3, "coverage_pct": 90.0,
            "window_seconds": 0.1, "unattributed_ms": 0.0,
            "layers": {"fc1": 1.0}, "top": top, "roofline": [], "mfu": {},
            "ts": 1.0}


def _span_events(n=8, dur=2.0):
    return [{"kind": "span", "ts": float(i), "name": "step", "epoch": 0,
             "step": i, "dur_ms": dur, "phases": [], "trace_id": None,
             "span_id": None, "rank": 0} for i in range(n)]


def test_diff_gates_hotspot_regression(tmp_path, capsys):
    """ISSUE 15: the last capture's per-op rows join the telemetry diff
    CI gate — a hotspot that regresses beyond the threshold exits 3."""
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    telemetry.write_jsonl(
        a, _span_events() + [_summary_event({"dot_general": 1000.0})])
    telemetry.write_jsonl(
        b, _span_events() + [_summary_event({"dot_general": 2000.0})])
    rc = _cli(["diff", a, b, "--threshold", "25"])
    out = capsys.readouterr().out
    assert rc == 3, out
    assert "op_ms[fc1/dot_general]" in out and "REGRESSION" in out
    # within threshold: clean exit
    telemetry.write_jsonl(
        b, _span_events() + [_summary_event({"dot_general": 1100.0})])
    assert _cli(["diff", a, b, "--threshold", "25"]) == 0
    capsys.readouterr()


def test_read_events_backfills_profile_defaults(tmp_path):
    """Old/hand-rolled profile rows gain the additive fields (schema
    satellite): phase/steps/device_ms/coverage_pct/top."""
    path = str(tmp_path / "old.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": 2, "kind": "profile", "ts": 1.0,
                            "rank": 0, "world_size": 1}) + "\n")
    rows = telemetry.read_events(path)
    assert rows[0]["phase"] == "summary"
    assert rows[0]["steps"] == 0
    assert rows[0]["device_ms"] == 0.0
    assert rows[0]["coverage_pct"] is None
    assert rows[0]["top"] == []
