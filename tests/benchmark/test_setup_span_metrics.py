"""Tests of the five set-up metrics that read the program's compile spans
and records (``benchmark/setup_spans.py`` and its readers): on hand-written
records with known answers, against their entries on every tree the
contract is held on, and in one traced rehearsal of the runner on the CPU.
The harness's own test file is imported for its fixtures; nothing of it is
changed.
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
                  "bench_harness_tests_of_setup_spans")
setup_spans = _module(os.path.join(BENCH, "setup_spans.py"),
                      "bench_setup_spans")
bench, overlay, tree = harness.bench, harness.overlay, harness.tree

# the cells that read them: the two decoder cells whose entry tests pin the
# whole list of what they report are not among them
CELLS = ["resnet50.device", "inception_bn.device", "resnet50.dp4",
         "kanana2_30b_a3b.seq8k"]
LAYERS = {
    "train_step_trace_s": "step builder + compile management "
                          "(_build_train_step, utils/compile.py)",
    "train_step_lower_s": "step builder + compile management "
                          "(_build_train_step, utils/compile.py)",
    "train_step_backend_s": "step builder + compile management "
                            "(_build_train_step, utils/compile.py)",
    "setup_other_compile_s": "set-up (model.py _init_params, precompile, "
                             "fit start)",
    "precompile_unnamed_s": "set-up (model.py _init_params, precompile, "
                            "fit start)",
}
# name -> the answer on written() below, worked by hand
ANSWERS = {
    "train_step_trace_s": 5.4,          # 8.6 to 14.0
    "train_step_lower_s": 3.0,
    "train_step_backend_s": 2.0,
    "setup_other_compile_s": 1.1,       # feeder 0.4, init 0.4, metric 0.2,
                                        # placement 0.1
    "precompile_unnamed_s": 1.8,        # 19 s less 6.5 + 0.2 + 0.1 + 10.4
}
SETUP_END = 30.0
TRAIN = "train_step:3f2a"


def rec(name, start, end, parent=None, thread="MainThread", epoch=None,
        step=None, **attrs):
    return {"name": name, "thread": thread, "start": start, "end": end,
            "parent": parent, "epoch": epoch, "step": step, "attrs": attrs}


def compiled(start, end, parent=None, label="<untracked>", cache="hit",
             **kw):
    return rec("compile.backend", start, end, parent=parent, label=label,
               fun="jit(f)", cache=cache, **kw)


def stage(which, start, end, label=TRAIN, **attrs):
    return rec("setup.compile." + which, start, end, parent="setup.compile",
               label=label, **attrs)


def written():
    """The feeder's program, ``precompile`` with its children and the
    compiles under them, ``fit``'s start, a warm-up callback returning at
    ``SETUP_END``, then what comes after set-up: a compile in the window,
    the reference's program, a train program compiled again. Oldest first,
    by end, as the ring holds them."""
    records = [
        compiled(0.2, 0.6, cache="miss"),
        rec("setup.precompile", 1.0, 20.0, programs=1),
        rec("setup.init_params", 1.5, 8.0, parent="setup.precompile",
            arrays=3),
        compiled(2.0, 2.2, parent="setup.init_params"),
        compiled(3.0, 3.2, parent="setup.init_params"),
        compiled(8.1, 8.3, parent="setup.precompile"),
        rec("setup.build_step", 8.5, 8.6, parent="setup.precompile",
            label=TRAIN),
        rec("setup.compile", 8.6, 19.0, parent="setup.precompile",
            label=TRAIN),
        stage("trace", 8.6, 14.0),
        # a program the trace runs eagerly: the trace's, by its label
        compiled(9.0, 9.1, parent="setup.compile.trace", label=TRAIN),
        stage("lower", 14.0, 17.0),
        stage("backend", 17.0, 19.0, cache="hit"),
        rec("fit.start", 20.5, 21.0),
        compiled(20.6, 20.7, parent="fit.start"),
        rec("fit.epoch.callback", 29.0, 30.1, epoch=0, parent="fit.epoch"),
        compiled(40.0, 41.0, parent="fit.dispatch", epoch=1, step=3),
        compiled(100.0, 102.0),
        rec("setup.precompile", 103.0, 111.0, programs=1),
        stage("trace", 104.0, 108.0),
        stage("lower", 108.0, 110.0),
        stage("backend", 110.0, 111.0, cache="hit"),
    ]
    return sorted(records, key=lambda r: r["end"])


def test_reduction_on_hand_written_records():
    got = setup_spans.reduce(written(), 0, SETUP_END)
    assert set(got) == set(ANSWERS) == set(setup_spans.NAMES)
    for name, want in ANSWERS.items():
        assert got[name] == pytest.approx(want, abs=1e-9), name


def test_parallel_precompile_threads_cover_their_container():
    """Two programs compiled on threads of their own: their
    ``setup.compile`` spans have no parent there and overlap."""
    labels = [TRAIN + ":bucket=8", TRAIN + ":bucket=16"]
    records = [
        rec("setup.precompile", 1.0, 10.0, programs=2),
        rec("setup.init_params", 1.0, 4.0, parent="setup.precompile"),
        rec("setup.build_step", 4.0, 4.1, parent="setup.precompile",
            label=labels[0]),
        rec("setup.build_step", 4.1, 4.2, parent="setup.precompile",
            label=labels[1]),
    ]
    for k, (lo, label) in enumerate(zip((4.5, 5.0), labels)):
        thread = f"mx-precompile_{k}"
        records += [
            rec("setup.compile", lo, lo + 4.5, thread=thread, label=label),
            dict(stage("trace", lo, lo + 2.0, label=label), thread=thread),
            dict(stage("lower", lo + 2.0, lo + 3.0, label=label),
                 thread=thread),
            dict(stage("backend", lo + 3.0, lo + 4.5, label=label,
                       cache="miss"), thread=thread)]
    got = setup_spans.reduce(records, 0, SETUP_END)
    assert got["train_step_trace_s"] == pytest.approx(4.0)
    assert got["train_step_lower_s"] == pytest.approx(2.0)
    assert got["train_step_backend_s"] == pytest.approx(3.0)
    assert got["setup_other_compile_s"] == 0.0      # none: a reading
    # 9 s less 3 + 0.2 and the two compiles' union, 4.5 to 9.5
    assert got["precompile_unnamed_s"] == pytest.approx(0.8)


def test_nothing_to_read_is_none_not_zero():
    records = written()
    nothing = dict.fromkeys(setup_spans.NAMES)
    assert setup_spans.reduce([], 0, SETUP_END) == nothing
    # any drop may have taken set-up's records
    assert setup_spans.reduce(records, 2, SETUP_END) == nothing
    # a program from before these spans: no setup.precompile, whatever else
    parent = [r for r in records if r["name"] != "setup.precompile"
              and not r["name"].startswith("setup.compile.")
              and r["name"] != "compile.backend"]
    assert setup_spans.reduce(parent, 0, SETUP_END) == nothing
    # a precompile that ends after set-up is not set-up's
    late = [r for r in records if r["end"] > SETUP_END]
    assert setup_spans.reduce(late, 0, SETUP_END) == nothing
    # the spans there, but no train program's stages: those three read
    # nothing, the other two read
    other = [r for r in records if not r["name"].startswith("setup.compile.")]
    got = setup_spans.reduce(other, 0, SETUP_END)
    assert [got[n] for n in setup_spans.STAGES] == [None] * 3
    assert got["setup_other_compile_s"] == pytest.approx(1.1)
    assert got["precompile_unnamed_s"] == pytest.approx(1.8)


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_reader_reads_the_programs_records(name, monkeypatch):
    from mxnet_tpu import telemetry

    reader = harness.catalog.load_metric("layer_metrics", name)
    assert reader.METRIC == {"name": name, "unit": "s", "better": "lower",
                             "source": "program_span", "layer": LAYERS[name],
                             "moves": "setup_s"}
    records = written()
    monkeypatch.setattr(telemetry, "span_records", lambda: records)
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    run = {"rows": [{"entry": 29.5, "exit": SETUP_END}]}
    assert reader.read(run) == pytest.approx(ANSWERS[name], abs=1e-9)
    # reduced once a run, whichever reader comes first
    monkeypatch.setattr(telemetry, "span_records", lambda: [])
    assert reader.read(run) == pytest.approx(ANSWERS[name], abs=1e-9)
    # the ring let a record go
    monkeypatch.setattr(telemetry, "span_records", lambda: records)
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 1)
    assert reader.read({"rows": run["rows"]}) is None
    # a program that keeps no span records at all
    monkeypatch.delattr(telemetry, "span_records")
    assert reader.read({"rows": run["rows"]}) is None


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_entries_list_the_cells_that_read_them(tree, name):
    here = os.path.join(tree.root, "benchmark")
    (entry,) = [m for m in tree.bench["per_layer"] if m["name"] == name]
    reader = harness.catalog.load_metric("layer_metrics", name, here=here)
    assert entry == dict(reader.METRIC, workloads=entry["workloads"])
    # a later cell is appended after these
    assert entry["workloads"][:len(CELLS)] == CELLS
    layers = {m["layer"] for m in tree.bench["per_layer"]
              if m["name"] in harness.ACCEPTED_METRICS}
    assert entry["layer"] in layers


def test_runner_reports_the_five_in_a_traced_rehearsal(overlay):
    proc = harness._run(overlay, "--workload", "tiny.dp4", "--seed",
                        str(2 ** 31 + 39), "--seconds", "0.5", "--trace",
                        "1", "--rehearse-on-cpu")
    result, earlier = harness._result(proc)
    assert result["correct"] is True, earlier
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(ANSWERS) <= set(metrics)
    for name in ANSWERS:
        assert math.isfinite(metrics[name]) and metrics[name] >= 0, name
    assert metrics["train_step_trace_s"] > 0
    assert metrics["train_step_backend_s"] > 0
    # parts of precompile, cut from the same records: never more than it
    parts = sum(metrics[n] for n in (
        "init_params_s", "train_step_trace_s", "train_step_lower_s",
        "train_step_backend_s", "precompile_unnamed_s"))
    assert parts <= metrics["precompile_s"]
