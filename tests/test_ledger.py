"""Cross-run telemetry ledger acceptance (ISSUE 20).

Covers: run_id identity (minted per hub, stamped on every event and
flight dump, fresh across reset), the RunRecord append/read roundtrip
through the atomic CRC'd store, concurrent multi-process appends,
corrupt-record skip-not-fatal reads, the trend gate (exit 3 on an
injected regression through the CLI), knob attribution across record
pairs differing in exactly one knob, the FleetController warm-start
sensor picking the historically best tier, and the e2e acceptance: two dp-8
fits differing only in compression tier land as two comparable records
while the armed zero-recompile epoch stays green with the ledger on.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import ledger
from mxnet_tpu.telemetry.__main__ import main as cli
from mxnet_tpu.utils import compile as cm


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    # the store must stay off unless a test points it somewhere; reset
    # gives each test its own hub (and so its own run_id)
    monkeypatch.delenv("MXNET_TPU_LEDGER_DIR", raising=False)
    telemetry.reset()
    yield


def _mlp():
    data = mx.sym.Variable("data")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        data, name="fc", num_hidden=4), name="softmax")


def _digits(n=64, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, dim).astype(np.float32),
            rng.randint(0, classes, (n,)).astype(np.float32))


def _mk_record(directory, fingerprint="fp-test", p50=10.0, world=8,
               knobs=None, completed=True, **outcomes):
    """Hand-build + append one record through the real writer (tests are
    MX316-exempt, but going through distill/append keeps the schema
    honest)."""
    rec = ledger.distill("fit", fingerprint=fingerprint, world_size=world,
                         knobs=knobs or {}, completed=completed,
                         since_ts=float("inf"))
    rec["outcomes"]["step_ms_p50"] = p50
    rec["outcomes"].update(outcomes)
    ledger.append_record(rec, directory=directory)
    return rec


# -- run identity --------------------------------------------------------------

def test_run_id_minted_stamped_and_reset():
    h = telemetry.hub()
    assert isinstance(h.run_id, str) and len(h.run_id) == 12
    h.emit("retry", op="push", attempt=1)
    ev = h.events(kind="retry")[-1]
    assert ev["run_id"] == h.run_id
    first = h.run_id
    telemetry.reset()
    assert telemetry.hub().run_id != first  # a new hub is a new run


def test_flight_dump_carries_run_id(tmp_path):
    path = str(tmp_path / "flight.json")
    telemetry.flight.dump(path, reason="test")
    ok, payload = telemetry.validate_flight(path)
    assert ok and payload["run_id"] == telemetry.hub().run_id


# -- store: append/read/corruption/concurrency ---------------------------------

def test_append_read_roundtrip(tmp_path):
    d = str(tmp_path / "ledger")
    h = telemetry.hub()
    t0 = h.now()
    for i in range(5):  # deterministic percentile fodder
        h.emit("span", name="step", epoch=0, step=i, dur_ms=10.0 + i)
    rec = ledger.distill("fit", fingerprint="fp-abc", world_size=8,
                         knobs={"compression": "int8"}, since_ts=t0)
    path = ledger.append_record(rec, directory=d)
    assert os.path.exists(path) and os.path.exists(path + ".crc32")
    # the append announced itself on the hub
    ann = h.events(kind="run_summary")[-1]
    assert ann["record_id"] == rec["record_id"]
    assert ann["fingerprint"] == "fp-abc"

    rows = ledger.read_ledger(d)
    assert len(rows) == 1
    r = rows[0]
    assert r["ledger_schema"] == ledger.LEDGER_SCHEMA
    assert r["run_id"] == h.run_id
    assert r["kind"] == "fit" and r["world_size"] == 8
    assert r["knobs"]["compression"] == "int8"
    # absent knobs read as None so compare() can pair across versions
    assert r["knobs"]["fused_adam"] is None
    assert r["outcomes"]["steps"] == 5
    assert r["outcomes"]["step_ms_p50"] == 12.0


def test_record_run_noop_without_dir(tmp_path):
    assert ledger.record_run("fit", fingerprint="fp") is None
    assert list(tmp_path.iterdir()) == []


def test_corrupt_record_skipped_not_fatal(tmp_path):
    d = str(tmp_path / "ledger")
    good = _mk_record(d, p50=10.0)
    bad = _mk_record(d, p50=11.0)
    # bit-flip the second record's body: CRC sidecar must fail it closed
    path = ledger.read_ledger(d)[1]["_path"]
    with open(path, "r+") as f:
        body = f.read()
        f.seek(0)
        f.write(body.replace("11.0", "99.0", 1))
        f.truncate()
    rows = ledger.read_ledger(d)
    assert [r["record_id"] for r in rows] == [good["record_id"]]
    # a torn (half-written) file without a parsable body skips too
    with open(os.path.join(d, "run-0000000000000-1-torn-001.json"),
              "w") as f:
        f.write('{"ledger_schema": 1, "record_')
    assert [r["record_id"] for r in ledger.read_ledger(d)] == \
        [good["record_id"]]
    del bad


def test_concurrent_multiprocess_appends(tmp_path):
    """One file per record through atomic_write: N processes appending
    at once never tear or drop a record."""
    d = str(tmp_path / "ledger")
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from mxnet_tpu.telemetry import ledger\n"
        "for i in range(4):\n"
        "    rec = ledger.distill('fit', fingerprint='fp-mp',\n"
        "                         world_size=8, since_ts=float('inf'))\n"
        "    rec['outcomes']['step_ms_p50'] = float(i)\n"
        f"    ledger.append_record(rec, directory={d!r})\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for _ in range(3)]
    for p in procs:
        _, err = p.communicate(timeout=180)
        assert p.returncode == 0, err.decode()
    rows = ledger.read_ledger(d)
    assert len(rows) == 12  # 3 processes x 4 records, none torn
    assert len({r["record_id"] for r in rows}) == 12
    assert len({r["pid"] for r in rows}) == 3


# -- gates + attribution -------------------------------------------------------

def test_trend_gate_catches_injected_regression(tmp_path):
    d = str(tmp_path / "ledger")
    for p50 in (10.0, 10.2, 9.8, 10.1):
        _mk_record(d, p50=p50)
    _mk_record(d, p50=20.0)  # the injected regression
    report = ledger.trend_gate(ledger.read_ledger(d),
                               metric="step_ms_p50", n=8, threshold=10.0)
    assert report["regressed"] is True
    assert report["baseline"] == 10.05  # median of the 4 predecessors
    assert report["latest"] == 20.0

    # CLI: trend exits 3 on the breach, 0 once the latest run recovers
    argv = ["ledger", "trend", "--dir", d, "--fingerprint", "fp-test",
            "--threshold", "10"]
    assert cli(argv) == 3
    _mk_record(d, p50=10.0)
    assert cli(argv) == 0
    # higher-is-better metrics gate in the other direction
    for mfu in (50.0, 50.0, 30.0):
        _mk_record(d, fingerprint="fp-mfu", p50=1.0, mfu_pct=mfu)
    assert cli(["ledger", "trend", "--dir", d, "--fingerprint", "fp-mfu",
                "--metric", "mfu_pct", "--threshold", "10"]) == 3


def test_trend_gate_needs_history(tmp_path):
    d = str(tmp_path / "ledger")
    _mk_record(d, p50=10.0)
    report = ledger.trend_gate(ledger.read_ledger(d))
    assert report["regressed"] is False and "reason" in report
    assert cli(["ledger", "trend", "--dir", d]) == 0


def test_compare_attributes_single_knob_delta(tmp_path):
    d = str(tmp_path / "ledger")
    base = {"compression": "fp32", "comm_kernels": False}
    _mk_record(d, p50=20.0, knobs=base, wire_bytes=1000.0)
    _mk_record(d, p50=8.0, knobs={**base, "compression": "int8"},
               wire_bytes=250.0)
    # two knobs differ -> NOT a comparable pair
    _mk_record(d, p50=7.0, knobs={"compression": "int8",
                                  "comm_kernels": True,
                                  "overlap_bytes": 1 << 20})
    rows = ledger.knob_attribution(ledger.read_ledger(d),
                                   metrics=("step_ms_p50", "wire_bytes"))
    assert len(rows) == 1
    row = rows[0]
    assert row["knob"] == "compression"
    assert (row["a_value"], row["b_value"]) == ("fp32", "int8")
    assert row["deltas"]["step_ms_p50"]["delta_pct"] == -60.0
    assert row["deltas"]["wire_bytes"]["delta_pct"] == -75.0
    assert cli(["ledger", "compare", "--dir", d]) == 0


def test_cli_list_show_and_errors(tmp_path):
    d = str(tmp_path / "ledger")
    assert cli(["ledger", "list", "--dir", d]) == 1          # empty store
    assert cli(["ledger", "list"]) == 2                      # no dir at all
    rec = _mk_record(d, p50=10.0)
    assert cli(["ledger", "list", "--dir", d]) == 0
    assert cli(["ledger", "show", rec["record_id"], "--dir", d]) == 0
    # prefix match on the shared run_id resolves too
    assert cli(["ledger", "show", rec["run_id"][:6], "--dir", d]) == 0
    assert cli(["ledger", "show", "nope", "--dir", d]) == 1
    assert cli(["ledger", "show", "--dir", d]) == 2          # missing arg


# -- controller warm start -----------------------------------------------------

def test_warm_start_picks_historically_best_tier(tmp_path, monkeypatch):
    from mxnet_tpu.resilience.controller import FleetController

    d = str(tmp_path / "ledger")
    for mode, p50 in (("fp32", 20.0), ("int8", 8.0), ("bf16", 12.0)):
        _mk_record(d, fingerprint="model-a", p50=p50,
                   knobs={"compression": mode,
                          "overlap_bytes": 4 << 20 if mode == "int8"
                          else None})
    # an incomplete (crashed) run with a better number must NOT win
    _mk_record(d, fingerprint="model-a", p50=1.0, completed=False,
               knobs={"compression": "twobit"})
    monkeypatch.setenv("MXNET_TPU_LEDGER_DIR", d)

    hist = ledger.warm_start_tier("model-a", 8)
    assert hist["mode"] == "int8"
    assert hist["bucket_bytes"] == 4 << 20
    assert hist["runs"] == 3  # completed runs only

    ctl = FleetController(dry_run=True)
    ctl.bind(model_key="model-a", world_size=8, comm_mode="none",
             can_retier=True)
    try:
        assert ctl._tier_cache[("model-a", 8)] == "int8"
        warm = [dec for dec in ctl.decisions
                if dec["outcome"] == "warm_start"]
        assert len(warm) == 1 and warm[0]["mode"] == "int8"
    finally:
        ctl.unbind()
    # no history for this shape -> no seed, no decision
    ctl2 = FleetController(dry_run=True)
    ctl2.bind(model_key="model-b", world_size=8, comm_mode="none",
              can_retier=True)
    try:
        assert ("model-b", 8) not in ctl2._tier_cache
        assert not [dec for dec in ctl2.decisions
                    if dec["outcome"] == "warm_start"]
    finally:
        ctl2.unbind()


# -- e2e acceptance ------------------------------------------------------------

def test_e2e_two_fits_differing_only_in_tier(tmp_path, monkeypatch):
    """Two dp-8 fits, identical but for the compression tier, with the
    ledger armed: two complete records land, compare() attributes the
    wire-byte delta to the tier knob, and the armed zero-recompile epoch
    stays green — the ledger distills at run END, off the step path."""
    d = str(tmp_path / "ledger")
    monkeypatch.setenv("MXNET_TPU_LEDGER_DIR", d)
    X, y = _digits()
    ctx = [mx.cpu(i) for i in range(8)]
    for tier in ("int8", "fp16"):
        # the invariant is per-fit: each tier is its own program, so the
        # tracker arms after the fit's first epoch and disarms at its end
        tracker = cm.RecompileTracker(raise_on_recompile=True)

        def arm_after_first(epoch, *_):
            if epoch == 0:
                tracker.arm()

        try:
            model = mx.FeedForward(_mlp(), ctx=ctx, num_epoch=2,
                                   learning_rate=0.1)
            model.fit(X, y, batch_size=16, compression=tier,
                      telemetry=True, epoch_end_callback=arm_after_first)
        finally:
            tracker.disarm()
        assert tracker.recompiles == []

    rows = [r for r in ledger.read_ledger(d) if r["kind"] == "fit"]
    assert len(rows) == 2
    assert all(r["completed"] and r["world_size"] == 8 for r in rows)
    assert rows[0]["fingerprint"] == rows[1]["fingerprint"]
    assert {r["knobs"]["compression"] for r in rows} == {"int8", "bf16"}
    assert all(r["outcomes"]["steps"] == 8 for r in rows)
    assert all((r["outcomes"]["wire_bytes"] or 0) > 0 for r in rows)
    # each tier's bytes are ITS plan's — a second fit must not retro-
    # price the first (the registry plan-overwrite hazard distill dodges
    # by pricing per-label step deltas at run end)
    by_tier = {r["knobs"]["compression"]: r for r in rows}
    assert by_tier["int8"]["outcomes"]["wire_bytes"] != \
        by_tier["bf16"]["outcomes"]["wire_bytes"]

    pairs = ledger.knob_attribution(rows)
    assert [p["knob"] for p in pairs] == ["compression"]
    assert pairs[0]["deltas"]["wire_bytes"]["delta_pct"] != 0

    assert cli(["ledger", "list", "--dir", d]) == 0
    assert cli(["ledger", "compare", "--dir", d]) == 0


def test_predict_lands_a_record(tmp_path, monkeypatch):
    d = str(tmp_path / "ledger")
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=1,
                           learning_rate=0.1)
    model.fit(X, y, batch_size=16)
    monkeypatch.setenv("MXNET_TPU_LEDGER_DIR", d)
    model.predict(X, batch_size=16, telemetry=True)
    rows = ledger.read_ledger(d)
    assert [r["kind"] for r in rows] == ["predict"]
    assert rows[0]["completed"] is True
    assert rows[0]["outcomes"]["steps"] == 4
    assert rows[0]["outcomes"]["step_ms_p50"] > 0


def test_failed_fit_records_incomplete(tmp_path, monkeypatch):
    d = str(tmp_path / "ledger")
    monkeypatch.setenv("MXNET_TPU_LEDGER_DIR", d)
    X, y = _digits()
    model = mx.FeedForward(_mlp(), ctx=mx.cpu(), num_epoch=2,
                           learning_rate=0.1)

    def boom(*_):
        raise RuntimeError("injected epoch-end failure")

    with pytest.raises(RuntimeError, match="injected"):
        model.fit(X, y, batch_size=16, epoch_end_callback=boom)
    rows = ledger.read_ledger(d)
    assert len(rows) == 1 and rows[0]["completed"] is False
