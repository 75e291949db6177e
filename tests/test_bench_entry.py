"""Driver-entry guards: bench.py's host-only mode must stay runnable
(the TPU modes need a chip, but argument parsing, RecordIO synthesis,
the native pipeline, and the JSON contract are all exercisable on CPU —
if this breaks, the driver's end-of-round capture breaks with it)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_comm_smoke_json_contract():
    """--comm-bench --smoke is the CI guard on the comm bench entry (tiny
    shapes, CPU mesh, no file written): one JSON line with the contract
    keys, all four modes measured, and the int8 plan ratio sane."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--comm-bench",
         "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "modes"):
        assert key in blob, blob
    assert blob["value"] > 1.0  # int8 moves fewer bytes than fp32
    assert set(blob["modes"]) == {"none", "bf16", "int8", "twobit"}
    for mode, row in blob["modes"].items():
        assert row["hlo_wire_bytes_per_step"] > 0, mode
        assert row["step_ms"] > 0, mode
    # int8 is integer-typed on the wire, so CPU HLO shows it faithfully:
    # compiled reality must agree with the closed-form plan
    assert blob["modes"]["int8"]["hlo_wire_bytes_per_step"] == pytest.approx(
        blob["modes"]["int8"]["plan_wire_bytes_per_step"], rel=0.02)
    assert blob["smoke"] is True  # smoke runs never write BENCH_COMM_*.json


def test_bench_telemetry_smoke_json_contract():
    """--telemetry-bench --smoke is the CI guard on the telemetry bench
    entry: one JSON line with the contract keys, hub op costs measured,
    and the acceptance bound — hub overhead under 2% of the baseline step
    on the 8-virtual-device smoke run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--telemetry-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "emit_ns",
                "observe_ns", "counter_ns", "step_ms_baseline",
                "step_ms_telemetry", "timeline_overhead_pct"):
        assert key in blob, blob
    assert blob["metric"] == "telemetry_hub_overhead_pct_of_step"
    assert blob["emit_ns"] > 0 and blob["step_ms_baseline"] > 0
    # the acceptance bound: hub instrumentation costs <2% of a step
    assert 0 < blob["value"] < 2.0, blob
    assert blob["smoke"] is True  # smoke runs never write BENCH_TELEMETRY_*


def test_bench_trace_smoke_json_contract():
    """--trace-bench --smoke is the CI guard on the distributed-tracing
    bench entry: one JSON line with the contract keys, per-op tracing
    costs measured, and the ISSUE 6 acceptance bound — flight recorder +
    trace propagation under 2% of the dp-8 baseline step."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--trace-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "note_ns",
                "sink_ns", "ctx_ns", "mint_ns", "step_ms_baseline",
                "step_ms_traced", "traced_overhead_pct",
                "flight_steps_recorded"):
        assert key in blob, blob
    assert blob["metric"] == "trace_flight_overhead_pct_of_step"
    assert blob["note_ns"] > 0 and blob["step_ms_baseline"] > 0
    # the acceptance bound: always-on tracing costs <2% of a step
    assert 0 < blob["value"] < 2.0, blob
    assert blob["flight_steps_recorded"] > 0  # the black box was live
    assert blob["smoke"] is True  # smoke runs never write BENCH_TRACE_*


def test_bench_mem_smoke_json_contract():
    """--mem-bench --smoke is the CI guard on the memory-observability
    bench entry: one JSON line with the contract keys, ledger/sampler op
    costs measured, a live watermark recorded, at least one program plan
    registered, and the ISSUE 9 acceptance bound — ledger + sampler
    under 2% of the dp-8 baseline step."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mem-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "add_ns",
                "sample_ns", "step_ms_baseline", "step_ms_tracked",
                "tracked_overhead_pct", "watermark_mb",
                "memory_plans_registered"):
        assert key in blob, blob
    assert blob["metric"] == "memory_ledger_overhead_pct_of_step"
    assert blob["add_ns"] > 0 and blob["step_ms_baseline"] > 0
    # the acceptance bound: memory accounting costs <2% of a step
    assert 0 < blob["value"] < 2.0, blob
    assert blob["watermark_mb"] > 0  # the ledger saw the tracked run
    assert blob["memory_plans_registered"] >= 1  # AOT plan registered
    assert blob["smoke"] is True  # smoke runs never write BENCH_MEM_*


def test_bench_health_smoke_json_contract():
    """--health-bench --smoke is the CI guard on the training-health
    bench entry (ISSUE 14): one JSON line with the contract keys, the
    ISSUE 14 acceptance bound — on-device stats overhead < 2% of the
    dp-8 step's FLOPs — a per-layer table from the instrumented run, and
    the injected-anomaly detection latencies (nonfinite in 0 extra
    steps, explosion/spike within 1)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--health-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline",
                "flops_per_step_baseline", "flops_per_step_health",
                "step_ms_baseline", "step_ms_health", "wall_overhead_pct",
                "health_events", "layers", "detect_latency_steps"):
        assert key in blob, blob
    assert blob["metric"] == "health_stats_overhead_pct_of_step"
    # ACCEPTANCE: the in-graph stats cost < 2% of the step's FLOPs
    assert 0 < blob["value"] < 2.0, blob
    assert blob["flops_per_step_health"] > blob["flops_per_step_baseline"]
    # the instrumented run streamed per-layer stats
    assert blob["health_events"] > 0
    assert {row["layer"] for row in blob["layers"]} == {"fc1", "fc2"}
    for row in blob["layers"]:
        assert row["max_grad_norm"] > 0, row
    # ACCEPTANCE: detectors catch the injected anomalies promptly
    lat = blob["detect_latency_steps"]
    assert lat["nonfinite"] == 0
    assert lat["grad_explosion"] is not None and lat["grad_explosion"] <= 1
    assert lat["loss_spike"] is not None and lat["loss_spike"] <= 1
    assert blob["smoke"] is True  # smoke runs never write BENCH_HEALTH_*


def test_bench_overlap_smoke_json_contract():
    """--overlap-bench --smoke is the CI guard on the comm/compute
    overlap bench entry: one JSON line with the contract keys, the
    per-bucket schedule proven structurally (>= 2 independent HLO
    collective pairs, per-bucket plans summing exactly to the fused
    plan), the stale-sync pipeline strictly beating the serial
    schedule, a positive overlap-efficiency gauge, and the telemetry
    tax under the 2% invariant."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--overlap-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "mesh",
                "stale_sync", "overlap_efficiency",
                "telemetry_overhead_pct"):
        assert key in blob, blob
    assert blob["metric"] == "overlap_bench_stale_sync_speedup"
    # ACCEPTANCE: the overlapped schedule strictly beats the serial one
    assert blob["value"] > 1.0, blob
    assert blob["stale_sync"]["step_ms_pipelined"] < \
        blob["stale_sync"]["step_ms_serial"]
    # ACCEPTANCE: >= 2 independent per-bucket collective pair groups in
    # the compiled HLO, and the plan arithmetic is exact vs fused
    assert blob["mesh"]["hlo_independent_pairs"] >= 2, blob["mesh"]
    assert blob["mesh"]["num_buckets"] >= 2
    assert blob["mesh"]["plan_matches_fused"] is True
    assert blob["mesh"]["loss_parity"] is True
    # ACCEPTANCE: efficiency gauge exported and positive, telemetry tax
    # within the <2% invariant
    assert blob["overlap_efficiency"] > 0, blob
    assert 0 <= blob["telemetry_overhead_pct"] < 2.0, blob
    assert blob["smoke"] is True  # smoke runs never write BENCH_OVERLAP_*


def test_bench_elastic_smoke_json_contract():
    """--elastic-bench --smoke is the CI guard on the elastic-training
    bench entry (ISSUE 10): one JSON line with the contract keys, both
    resizes (8->6 shrink, 6->8 regrow) executed with measured downtime,
    per-world step times, and the resize badput priced into goodput."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--elastic-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline",
                "shrink_downtime_s", "grow_downtime_s", "resizes",
                "worlds", "step_ms_by_world", "goodput_pct_by_epoch",
                "resize_badput_s"):
        assert key in blob, blob
    assert blob["metric"] == "elastic_resize_downtime_seconds"
    # both resizes happened and were priced
    assert blob["resizes"] == 2
    assert blob["worlds"] == [6, 8]
    assert blob["shrink_downtime_s"] > 0
    assert blob["grow_downtime_s"] > 0
    assert blob["resize_badput_s"] > 0
    # training ran at every world size
    for world in ("8_pre", "6", "8_post"):
        assert blob["step_ms_by_world"].get(world, 0) > 0, blob
    assert blob["smoke"] is True  # smoke runs never write BENCH_ELASTIC_*


def test_bench_ckpt_smoke_json_contract():
    """--ckpt-bench --smoke is the CI guard on the async-checkpoint bench
    entry (ISSUE 17): one JSON line with the contract keys, the async
    step stall under the 10%-of-sync acceptance bound, both recovery
    tiers exercised (peer RAM restore + chaos-forced disk fallback), and
    checkpoint badput priced at all three cadences."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--ckpt-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline",
                "async_stall_ms", "sync_save_ms", "peer_recovery_s",
                "disk_recovery_s", "badput_by_cadence"):
        assert key in blob, blob
    assert blob["metric"] == "ckpt_async_stall_pct_of_sync"
    # ACCEPTANCE: the async save stalls the step loop <10% of a sync save
    assert 0 < blob["value"] < 10.0, blob
    assert blob["async_stall_ms"] < blob["sync_save_ms"]
    # both recovery paths ran: T1 with replication live, T2 under chaos
    assert blob["peer_recovery_tier"] == "t1"
    assert blob["disk_recovery_tier"] == "t2"
    assert blob["peer_recovery_s"] > 0 and blob["disk_recovery_s"] > 0
    # badput priced at every cadence, monotone non-increasing with cadence
    rows = blob["badput_by_cadence"]
    assert set(rows) == {"1", "4", "16"}
    assert all(r["badput_s_per_epoch"] >= 0 for r in rows.values())
    assert rows["16"]["badput_s_per_epoch"] <= rows["1"]["badput_s_per_epoch"]
    assert blob["smoke"] is True  # smoke runs never write BENCH_CKPT_*


def test_bench_controller_smoke_json_contract():
    """--controller-bench --smoke is the CI guard on the fleet-controller
    bench entry (ISSUE 12): one JSON line with the contract keys, the
    blamed straggler really evicted by the armed run, a compression tier
    auto-picked, the breaker never tripped, and the armed fleet's
    steady-state per-chip throughput recovering a positive fraction of
    what the straggler cost the static fleet."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--controller-bench", "--smoke"],
        capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "tpc_clean",
                "tpc_static", "tpc_controller", "final_step_ms",
                "evicted", "backfilled", "tier_chosen", "retier_actions",
                "worlds", "breaker_state", "decisions_total"):
        assert key in blob, blob
    assert blob["metric"] == "controller_goodput_recovered_frac"
    # the closed loop actually closed: blame -> evict -> recover
    assert blob["evicted"] == [7]
    assert blob["tier_chosen"] in ("bf16", "int8", "twobit")
    assert blob["breaker_state"] == "closed"
    # the straggler really cost the static fleet, and the armed fleet
    # bought a solid share back (generous margin: shared-box timing)
    assert blob["tpc_static"] < blob["tpc_clean"]
    assert blob["value"] is not None and blob["value"] > 0.2, blob
    assert blob["smoke"] is True  # smoke runs never write BENCH_CONTROLLER_*


def test_bench_lockwatch_smoke_json_contract():
    """--lockwatch-bench --smoke is the CI guard on the lock-order
    watchdog bench (ISSUE 11): one JSON line with the contract keys,
    ZERO lock-order cycles across both soaks (group-kvstore membership
    churn + elastic-resize fit), the kvstore soak finishing without a
    hang, and the acceptance bound — watchdog overhead under 2% of a
    dp-4 step (priced per-pair x acquisitions/step, robust to
    shared-box noise)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--lockwatch-bench", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "pair_ns_off",
                "pair_ns_on", "pair_delta_ns", "acquires_per_step",
                "step_ms", "cycles", "max_hold_ms", "kv_soak",
                "resizes", "worlds"):
        assert key in blob, blob
    assert blob["metric"] == "lockwatch_overhead_pct_of_step"
    # ACCEPTANCE: zero lock-order cycles in both soaks, no kv hang
    assert blob["cycles"] == 0, blob
    assert blob["kv_soak"]["cycles"] == 0, blob
    assert blob["kv_soak"]["hung"] is False, blob
    # ACCEPTANCE: the armed watchdog costs <2% of a step
    assert 0 <= blob["value"] < 2.0, blob
    assert blob["pair_ns_on"] > blob["pair_ns_off"] > 0
    assert blob["acquires_per_step"] > 0 and blob["step_ms"] > 0
    # both elastic resizes committed under the watchdog
    assert blob["resizes"] == 2 and blob["worlds"] == [3, 4], blob
    assert blob["smoke"] is True  # smoke runs never write BENCH_LOCKWATCH_*


def test_bench_kernel_smoke_json_contract():
    """--kernel-bench --smoke is the CI guard on the Pallas kernel-layer
    bench (ISSUE 13): one JSON line with the contract keys, a roofline
    row per kernel (registry FLOP/byte model + measured interpret-mode
    time), the fused-vs-unfused HLO acceptance — the kernel path removes
    EVERY full-slab quantize pass while moving byte-identical
    collectives — and fused-Adam bitwise parity."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--kernel-bench",
         "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "kernels",
                "hlo_fused_vs_unfused", "wire_bytes_identical",
                "fused_adam", "int8_matmul_rel_error", "catalog"):
        assert key in blob, blob
    assert blob["metric"] == "kernel_bench_full_slab_quantize_passes_removed"
    # ACCEPTANCE: the codec path runs full-slab quantize passes, the
    # kernel path runs none, and the wire bytes are identical
    hlo = blob["hlo_fused_vs_unfused"]
    assert hlo["codec"]["full_slab_quantize_passes"] > 0, blob
    assert hlo["kernels"]["full_slab_quantize_passes"] == 0, blob
    assert blob["value"] == hlo["codec"]["full_slab_quantize_passes"]
    assert blob["wire_bytes_identical"] is True, blob
    # a roofline row per kernel family, each priced by the registry
    row_names = {k["kernel"] for k in blob["kernels"]}
    assert {"flash_attention_fwd", "flash_attention_fwd_bwd", "quant_int8",
            "quant_twobit", "dequant_sum_int8", "fused_adam",
            "int8_matmul"} <= row_names
    for row in blob["kernels"]:
        assert row["model_flops"] > 0 and row["model_bytes"] > 0, row
        assert row["ms"] > 0 and row["achieved_gflops_s"] > 0, row
        assert row["kernels_in_program"], row
    # ACCEPTANCE: fused sharded-Adam step-time row + exact parity
    assert blob["fused_adam"]["bitwise_parity"] is True, blob
    assert blob["fused_adam"]["fused_ms"] > 0
    assert blob["fused_adam"]["per_leaf_ms"] > 0
    assert 0 < blob["int8_matmul_rel_error"] < 0.02, blob
    # the catalog covers every registered kernel
    assert {c["kernel"] for c in blob["catalog"]} >= {
        "flash_fwd", "fused_adam", "quant_int8", "int8_matmul"}
    assert blob["smoke"] is True  # smoke runs never write BENCH_KERNELS_*


def test_bench_profile_smoke_json_contract():
    """--profile-bench --smoke is the CI guard on the device-time
    profiler bench (ISSUE 15): one JSON line with the contract keys, the
    acceptance bounds — >= 80% of in-window device time attributed to
    named layers/kernels, out-of-window overhead < 0.5% of a step — a
    top-K hotspot table, measured roofline rows stamped
    source="measured", a measured-vs-modeled MFU delta, and the capture
    window priced as profile badput."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--profile-bench",
         "--smoke"],
        capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "window_steps",
                "device_ms", "unattributed_ms", "layers_ms", "top",
                "roofline", "measured_mfu_pct", "mfu_delta_pct",
                "profile_badput_s", "out_of_window_poll_ns",
                "out_of_window_overhead_pct", "step_ms"):
        assert key in blob, blob
    assert blob["metric"] == "profile_attribution_coverage_pct"
    # ACCEPTANCE: >= 80% of in-window device time named, remainder
    # reported explicitly
    assert blob["value"] >= 80.0, blob
    assert blob["unattributed_ms"] >= 0.0
    # model layers really attributed (not just the pseudo-categories)
    assert {"fc1", "fc2"} <= set(blob["layers_ms"]), blob["layers_ms"]
    assert blob["top"] and blob["top"][0]["ms"] > 0
    # measured roofline rows: source=measured, joined FLOP models, a
    # bound classification per row
    assert blob["roofline"], blob
    for row in blob["roofline"]:
        assert row["source"] == "measured", row
        assert row["model_flops"] > 0 and row["measured_ms_per_step"] > 0
        assert row.get("bound") in ("compute", "bandwidth"), row
    # the measured-vs-modeled reconciliation resolved
    assert blob["measured_mfu_pct"] is not None
    assert blob["mfu_delta_pct"] is not None
    # ACCEPTANCE: out-of-window overhead < 0.5% of a step; the window
    # itself priced as profile badput
    assert 0 <= blob["out_of_window_overhead_pct"] < 0.5, blob
    assert blob["profile_badput_s"] > 0
    assert blob["smoke"] is True  # smoke runs never write BENCH_PROFILE_*


def test_kernel_bench_roofline_rows_carry_source():
    """ISSUE 15 satellite: every --kernel-bench roofline row is stamped
    with its provenance (interpret on the CPU rig) so an interpret-mode
    estimate can never be read as a device measurement. Asserted on the
    committed artifact so the full-run schema is pinned without re-running
    the bench."""
    path = os.path.join(REPO, "BENCH_KERNELS_r16.json")
    with open(path) as f:
        blob = json.load(f)
    assert blob["kernels"], blob
    for row in blob["kernels"]:
        assert row.get("source") in ("interpret", "measured"), row
        # the CPU artifact ran under the Pallas interpreter
        if blob.get("interpret_mode"):
            assert row["source"] == "interpret", row


@pytest.mark.slow
def test_bench_pipeline_mode_json_contract(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode",
         "pipeline", "--recordio", str(tmp_path / "b.rec"),
         "--num-images", "64"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    # the contract: ONE JSON line on stdout with the required keys
    lines = [l for l in r.stdout.strip().splitlines()
             if l.startswith("{")]
    assert len(lines) == 1, r.stdout
    blob = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in blob, blob
    assert blob["value"] > 0
