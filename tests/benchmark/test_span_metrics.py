"""Tests of the ``program_span`` per-layer metrics: the reduction of
``telemetry.span_records()`` (``benchmark/span_reduce.py``) and its seven
readers, on hand-written records with known answers, and one rehearsal of
the runner on the CPU in which all seven report. The harness's own test
file is imported for its overlay of added files; nothing of it is changed.
"""

import importlib.util
import math
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


harness = _module(os.path.join(HERE, "test_benchmark_harness.py"),
                  "bench_harness_tests")
span_reduce = _module(os.path.join(BENCH, "span_reduce.py"),
                      "bench_span_reduce")
overlay, bench = harness.overlay, harness.bench     # the harness's fixtures

# name -> the answer on RECORDS below, worked by hand
ANSWERS = {
    "write_back_ms": 250.0,             # median of 200 and 300
    "epoch_tail_host_ms": 311.0,        # median of 36 + 225 and 36 + 325
    "epoch_tail_unnamed_ms": 27.0,      # 12 of the head, 15 of the foot
    "host_step_ms_p10": 8.0,            # steps of 15, 8, 15, 8 ms
    "feed_wait_ms_per_step": 3.5,       # (4 + 2 + 1) ms over two steps
    "init_params_s": 7.05,
    "fit_start_s": 1.5,
}


def rec(name, start, end, epoch=None, step=None, thread="MainThread",
        parent=None, **attrs):
    return {"name": name, "thread": thread, "start": start, "end": end,
            "parent": parent, "epoch": epoch, "step": step, "attrs": attrs}


def fit_epoch(t, epoch, write_back):
    """The records of one ``fit`` epoch of two steps whose previous callback
    ended at ``t + 0.01``: a head of 36 ms to the end of the first dispatch
    (24 ms of it named) and a foot of ``0.025 + write_back`` seconds after
    the drain (15 ms of it not named)."""
    cb = t + 9.02 + write_back + 0.005
    main = [
        rec("fit.epoch.feed_start", t + 0.020, t + 0.030),
        rec("fit.feed_wait", t + 0.030, t + 0.034, step=0),
        rec("fit.step", t + 0.035, t + 0.050, step=0),
        rec("fit.dispatch", t + 0.036, t + 0.046, step=0,
            parent="fit.step"),
        rec("fit.step_host", t + 0.046, t + 0.050, step=0,
            parent="fit.step"),
        rec("fit.feed_wait", t + 0.050, t + 0.052, step=1),
        rec("fit.step", t + 0.052, t + 0.060, step=1),
        rec("fit.dispatch", t + 0.053, t + 0.058, step=1,
            parent="fit.step"),
        rec("fit.step_host", t + 0.058, t + 0.060, step=1,
            parent="fit.step"),
        rec("fit.feed_wait", t + 0.060, t + 0.061, step=2),
        rec("fit.epoch.feed_close", t + 0.061, t + 0.062),
        rec("fit.epoch.drain", t + 0.062, t + 9.0),
        rec("fit.epoch.metric_pull", t + 9.0, t + 9.01),
        rec("fit.epoch.write_back", t + 9.02, t + 9.02 + write_back,
            arrays=3, bytes=12),
        rec("fit.epoch.callback", cb, cb + 0.12),
        rec("fit.epoch", t + 0.011, cb + 0.121),
    ]
    for r in main:
        r["epoch"] = epoch
        r["parent"] = r["parent"] or (
            None if r["name"] == "fit.epoch" else "fit.epoch")
    # the feed thread works through the head: not the main thread's cover
    return main + [rec("feed.produce", t + 0.010, t + 0.040, epoch=epoch,
                       step=0, thread="mx-prefetch")]


def written():
    """Set-up, a warm-up epoch's callback, two traced epochs, one more;
    the runner's rows lie inside the callbacks. Oldest first, by end."""
    records = [
        rec("setup.init_params", 1.0, 8.0, arrays=3),
        rec("setup.compile", 8.0, 8.5, label="train_step:x"),
        rec("setup.init_params", 9.0, 9.05, parent="fit.start", arrays=0),
        rec("fit.start", 8.9, 10.4),
        rec("fit.epoch.callback", 99.9, 100.01, epoch=4,
            parent="fit.epoch"),
    ]
    rows = [{"entry": 99.95, "exit": 100.0}]
    t = 100.0
    for epoch, write_back in ((5, 0.2), (6, 0.3), (7, 0.9)):
        records += fit_epoch(t, epoch, write_back)
        cb = t + 9.02 + write_back + 0.005
        rows.append({"entry": cb + 0.05, "exit": cb + 0.11})
        t = cb + 0.11
    records.sort(key=lambda r: r["end"])
    return records, rows


def test_reduction_on_hand_written_records():
    records, rows = written()
    got = span_reduce.reduce(records, 0, rows, [0, 1])
    assert set(got) == set(ANSWERS) == set(span_reduce.NAMES)
    for name, want in ANSWERS.items():
        assert got[name] == pytest.approx(want, abs=1e-6), name
    # one traced epoch: its own numbers, no median
    one = span_reduce.reduce(records, 0, rows, [1])
    assert one["write_back_ms"] == pytest.approx(300.0)
    assert one["epoch_tail_host_ms"] == pytest.approx(361.0)
    assert one["epoch_tail_unnamed_ms"] == pytest.approx(27.0)


def test_dropped_records_read_as_none():
    records, rows = written()
    # any drop may have taken the set-up spans; the epochs are whole
    got = span_reduce.reduce(records, 3, rows, [0, 1])
    assert got["init_params_s"] is None and got["fit_start_s"] is None
    assert got["write_back_ms"] == pytest.approx(250.0)
    # the ring has let go of the callback before the first traced epoch
    cut = [r for r in records if r["end"] > 100.02]
    got = span_reduce.reduce(cut, len(records) - len(cut), rows, [0, 1])
    assert got == dict.fromkeys(span_reduce.NAMES)
    # ... which leaves the second traced epoch readable
    got = span_reduce.reduce(cut, len(records) - len(cut), rows, [1])
    assert got["write_back_ms"] == pytest.approx(300.0)
    assert got["feed_wait_ms_per_step"] == pytest.approx(3.5)
    # an epoch without its drain or its steps: no tail, no step time
    bare = [r for r in records
            if r["name"] not in ("fit.epoch.drain", "fit.step")]
    got = span_reduce.reduce(bare, 0, rows, [0, 1])
    assert got["epoch_tail_host_ms"] is None
    assert got["host_step_ms_p10"] is None
    assert got["feed_wait_ms_per_step"] is None
    assert got["write_back_ms"] == pytest.approx(250.0)


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_reads_the_programs_records(name, bench, monkeypatch):
    from mxnet_tpu import telemetry

    reader = harness.catalog.load_metric("layer_metrics", name)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert reader.METRIC == entry and "workloads" not in entry
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    records, rows = written()
    monkeypatch.setattr(telemetry, "span_records", lambda: records)
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    run = {"rows": rows, "traced_epochs": [0, 1]}
    assert reader.read(run) == pytest.approx(ANSWERS[name], abs=1e-6)
    # reduced once a run, whichever reader comes first
    monkeypatch.setattr(telemetry, "span_records", lambda: [])
    assert reader.read(run) == pytest.approx(ANSWERS[name], abs=1e-6)
    # a program that keeps no span records (this metric's parent commit)
    monkeypatch.delattr(telemetry, "span_records")
    assert reader.read({"rows": rows, "traced_epochs": [0, 1]}) is None


@pytest.mark.parametrize("cell", ["tiny.device", "tiny.dp4"])
def test_runner_reports_all_seven_in_a_traced_rehearsal(overlay, cell):
    proc = harness._run(overlay, "--workload", cell, "--seed",
                        str(2 ** 31 + 24), "--seconds", "0.5", "--trace",
                        "1", "--rehearse-on-cpu")
    result, earlier = harness._result(proc)
    assert result["correct"] is True, earlier
    metrics = result["metrics"]
    assert set(ANSWERS) <= set(metrics)
    for name in ANSWERS:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    # parts of one tail, cut from the same records: never more than it
    assert metrics["epoch_tail_unnamed_ms"]["value"] <= \
        metrics["epoch_tail_host_ms"]["value"]
    assert metrics["write_back_ms"]["value"] <= \
        metrics["epoch_tail_host_ms"]["value"]
