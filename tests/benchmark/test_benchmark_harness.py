"""Tests of the benchmark harness (``benchmark/``), on the CPU at a tiny
preset. Everything that runs the runner does so in a child process with an
environment of its own, in a copy of ``benchmark/`` to which the test ADDS
files (a configuration, traffic mixes, a per-layer metric, cells) and
edits none: that a later PR can do the same is what is being tested.
No TPU topology is described anywhere in this file.
"""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")   # the contract's
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _module(path, name):
    """Loaded under a name of its own and without touching ``sys.path``:
    other test files share this worker process."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


catalog = _module(os.path.join(BENCH, "catalog.py"), "bench_catalog")
epochs = _module(os.path.join(BENCH, "epochs.py"), "bench_epochs")
flops = _module(os.path.join(BENCH, "flops.py"), "bench_flops")
trace_reduce = _module(os.path.join(BENCH, "trace_reduce.py"),
                       "bench_trace_reduce")


def _spawn(cmd, cwd, devices, timeout=600):
    """A child with an environment of its own, at the lowest priority and
    with single-threaded kernels: the other workers of the test run time
    things on the host clock, and this file must not slow them."""
    env = {k: v for k, v in os.environ.items()
           if k in ("PATH", "HOME", "TMPDIR", "LANG", "VIRTUAL_ENV",
                    "LD_LIBRARY_PATH")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               MXNET_TPU_COMPILE_CACHE="0", OMP_NUM_THREADS="1",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}"
                         " --xla_cpu_multi_thread_eigen=false")
    if shutil.which("nice"):    # not preexec_fn: this process has threads
        cmd = ["nice", "-n", "19", *cmd]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.fixture(scope="module")
def bench():
    return catalog.load_benchmark(ROOT)


# -- BENCHMARK.json against its contract and against the files ---------------

def test_benchmark_json_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in bench[g]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for group in ("end_to_end", "per_layer"):
        metric_names = [m["name"] for m in bench[group]]
        assert len(metric_names) == len(set(metric_names))
        for m in bench[group]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for text in [w["why"] for w in bench["workloads"]] + \
            [c["why"] for c in bench["configs"]] + \
            [c["source"] for c in bench["configs"]] + \
            [m["layer"] for m in bench["per_layer"]] + bench["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for folder in bench["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, folder)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_every_entry_has_its_file_and_they_agree(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        meta = catalog.load_metric("end_to_end", m["name"]).METRIC
        assert {k: m[k] for k in meta} == meta
    for m in bench["per_layer"]:
        meta = catalog.load_metric("layer_metrics", m["name"]).METRIC
        assert {k: m[k] for k in meta} == meta
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        found = catalog.find_cell(bench, w["name"])
        config = found["config"]
        assert found["config_entry"]["file"].startswith(
            tuple(p + "/" for p in bench["paths"]))
        assert config["reduced"] == found["config_entry"]["reduced"]
        assert config["source"] == found["config_entry"]["source"]
        assert os.path.isfile(os.path.join(
            os.path.dirname(found["config_path"]), config["reference"]))
        assert os.path.isfile(os.path.join(
            BENCH, "feeds", found["traffic"]["kind"] + ".py"))
    with pytest.raises(catalog.BenchmarkError):
        catalog.find_cell(bench, "no.such.cell")
    with pytest.raises(catalog.BenchmarkError):
        catalog.peak_for("cpu")
    assert catalog.peak_for("TPU v5 lite")["bf16_flops"] == 197e12


# -- the rate: all the window's samples over all its seconds -------------------

def _rows(stamps):
    """(entry, exit) pairs -> callback rows, the first the warm-up."""
    return [{"entry": a, "exit": b} for a, b in stamps]


def test_a_stall_costs_the_rate_what_it_cost_and_leaves_the_median():
    rate_of = catalog.load_metric("end_to_end", "samples_per_s_per_chip").read
    median_of = catalog.load_metric("layer_metrics", "epoch_rate_median").read
    worst_of = catalog.load_metric("layer_metrics",
                                   "epoch_rate_min_over_median").read

    def run(rows, chips=1):
        return {"epoch_seconds": epochs.epoch_seconds(rows),
                "epoch_rates": epochs.epoch_rates(rows, 16384, chips),
                "window_seconds": epochs.window_seconds(rows),
                "samples_per_epoch": 16384, "chips": chips}

    steady = _rows([(10.0, 10.1)] + [(10.1 + 5 * i + 5, 10.1 + 5 * i + 5)
                                     for i in range(7)])
    assert epochs.epoch_seconds(steady)[0] == pytest.approx(5.0)
    assert epochs.window_seconds(steady) == pytest.approx(35.0)
    rate = rate_of(run(steady))
    assert rate == pytest.approx(16384 / 5.0)
    assert median_of(run(steady)) == pytest.approx(rate)
    # the fourth epoch stalls for 3 s; the later ones are pushed back whole
    stalled = [dict(r) for r in steady]
    for r in stalled[4:]:
        r["entry"] += 3.0
        r["exit"] += 3.0
    assert epochs.epoch_seconds(stalled)[3] == pytest.approx(8.0)
    # all the work over all the time: the rate loses the 3 s in 38 ...
    assert rate_of(run(stalled)) == pytest.approx(7 * 16384 / 38.0)
    assert rate_of(run(stalled)) < 0.93 * rate
    # ... and the per-layer pair says it was one epoch, not all of them
    assert median_of(run(stalled)) == pytest.approx(rate)
    assert worst_of(run(stalled)) == pytest.approx(100 * 5.0 / 8.0)
    # time the benchmark spends inside its own callback between two
    # measured epochs is no epoch's, but it is the window's
    slow_callback = _rows([(0.0, 1.0), (6.0, 9.0), (14.0, 14.0)])
    assert epochs.epoch_seconds(slow_callback) == [5.0, 5.0]
    assert epochs.window_seconds(slow_callback) == 13.0
    assert rate_of(run(steady, chips=4)) == pytest.approx(rate / 4)


def test_epoch_clock_counts_whole_epochs_and_stops_at_a_boundary():
    now = [0.0]
    fired = []
    clock = epochs.EpochClock(lambda: now[0], seconds=12.0, min_epochs=1,
                              probe=lambda: {"loss": 1.0},
                              hooks={1: lambda: fired.append(now[0])})
    now[0] = 100.0
    clock(0)                                   # warm-up: the window opens
    for _ in range(2):
        now[0] += 5.0
        clock(0)                               # 5 s, 10 s: not yet 12
    now[0] += 5.0
    with pytest.raises(epochs.StopFit):
        clock(0)                               # 15 s >= 12: stop, whole epochs
    assert len(clock.rows) == 4 and fired == [105.0]
    assert epochs.epoch_seconds(clock.rows) == [5.0, 5.0, 5.0]
    # a traced run keeps going until its traced epochs are in
    now[0] = 0.0
    clock = epochs.EpochClock(lambda: now[0], seconds=1.0, min_epochs=3,
                              probe=dict)
    clock(0)
    for _ in range(2):
        now[0] += 5.0
        clock(0)
    now[0] += 5.0
    with pytest.raises(epochs.StopFit):
        clock(0)
    assert len(clock.rows) == 4


# -- the FLOP count --------------------------------------------------------------

def test_flops_hand_worked_layers_and_totals(bench):
    # ResNet-50's stem by hand: 7x7 kernel, 3 -> 64 channels, 112x112
    # outputs: 7*7*3*64 = 9,408 multiply-adds an output pixel, x 12,544
    # pixels = 118,013,952 multiply-adds = 236,027,904 FLOP forward
    stem = {"op": "conv", "name": "stem", "kernel": [7, 7], "cin": 3,
            "cout": 64, "out": [112, 112]}
    assert flops.layer_forward_flops(stem) == 236_027_904
    head = {"op": "fc", "name": "fc1", "cin": 2048, "cout": 1000}
    assert flops.layer_forward_flops(head) == 4_096_000
    assert flops.train_flops_per_sample([stem, head]) == 3 * 240_123_904
    with pytest.raises(ValueError):
        flops.layer_forward_flops({"op": "pool"})
    totals = {}
    for c in bench["configs"]:
        layers = catalog.read_json(os.path.join(ROOT, c["file"]))[
            "flops_per_sample"]["layers"]
        totals[c["name"]] = flops.train_flops_per_sample(layers) / 1e9
    # 4.09 G multiply-adds forward (v1.5) -> 8.18 GFLOP, 24.5 with backward
    assert totals["resnet50"] == pytest.approx(24.535, abs=0.001)
    if "inception_bn" in totals:
        # the published network: 2.03 G multiply-adds forward
        assert totals["inception_bn"] == pytest.approx(12.196, abs=0.001)


def test_layer_lists_match_the_models_the_builders_make(bench):
    """The recipe in a configuration's file is data; the model is code.
    Walk the built symbol's shapes and require the same layers."""
    for c in bench["configs"]:
        config = catalog.read_json(os.path.join(ROOT, c["file"]))
        internals = catalog.build_symbol(
            config["builder"],
            os.path.dirname(os.path.join(ROOT, c["file"]))).get_internals()
        arg_shapes, out_shapes, _ = internals.infer_shape(
            data=(1, *config["image"]))
        args = dict(zip(internals.list_arguments(), arg_shapes))
        outs = dict(zip(internals.list_outputs(), out_shapes))
        walked = []
        for name, shape in args.items():
            if not name.endswith("_weight"):
                continue
            base = name[:-len("_weight")]
            if len(shape) == 4:
                out = outs[base + "_output"]          # NHWC
                walked.append({"op": "conv", "name": base,
                               "kernel": [shape[2], shape[3]],
                               "cin": shape[1], "cout": shape[0],
                               "out": [out[1], out[2]]})
            else:
                walked.append({"op": "fc", "name": base, "cin": shape[1],
                               "cout": shape[0]})
        assert walked == config["flops_per_sample"]["layers"], c["name"]
        if "block_channels" in config:     # the source's own table
            blocks = [shape[-1] for name, shape in outs.items()
                      if name.endswith("_chconcat_output")]
            assert blocks == config["block_channels"], c["name"]


# -- the plain references --------------------------------------------------------

REFERENCE_SCRIPT = '''
import os, sys
import numpy as np
bench, name = sys.argv[1], sys.argv[2]
sys.path.insert(0, bench)
import catalog
import mxnet_tpu as mx

config = catalog.read_json(os.path.join(bench, "configs", name + ".json"))
builder = dict(config["builder"])
builder["kwargs"] = dict(builder["kwargs"], num_classes=10)
head = catalog.build_symbol(builder, os.path.join(bench, "configs")) \
    .get_internals()[config["logits"]]
rng = np.random.default_rng(0)
x = rng.standard_normal((2, 64, 64, 3)).astype(np.float32)
mx.random.seed(0)
model = mx.FeedForward(head, ctx=mx.cpu(), initializer=mx.init.Xavier())
model._init_params({"data": x.shape})
for k, v in model.aux_params.items():   # moving statistics off identity
    a = v.asnumpy()
    spread = 0.3 if k.endswith("mean") else 0.5
    model.aux_params[k] = mx.nd.array(
        (a + rng.uniform(-spread, spread, a.shape)).astype(np.float32))
got = model.predict(x, batch_size=2)
ref = catalog.load_file_module(
    os.path.join(bench, "configs", config["reference"]), "bench_ref")
want = np.asarray(ref.logits(
    {k: v.asnumpy() for k, v in model.arg_params.items()},
    {k: v.asnumpy() for k, v in model.aux_params.items()}, x))
assert got.shape == want.shape == (2, 10), (got.shape, want.shape)
assert np.abs(want).max() > 0.1, np.abs(want).max()
err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
assert err < 1e-5, err
print("relative error", err)
'''


@pytest.mark.parametrize("name", ["resnet50", "inception_bn"])
def test_plain_reference_agrees_with_the_system_in_float32(name):
    """Full depth and widths, small images, float32 on the CPU: the
    system's logits and the plain reference's agree to rounding. In a
    child process at low priority, like every test here that compiles."""
    proc = _spawn([sys.executable, "-c", REFERENCE_SCRIPT, BENCH, name],
                  cwd=ROOT, devices=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "relative error" in proc.stdout


# -- the trace reduction ---------------------------------------------------------

def _synthetic_trace():
    """Two chips' worth of two 4-step epochs: 100 us steps 10 us apart, a
    300 us epoch tail; every step holds a 60 us fusion, a 30 us all-reduce
    of which 10 us overlap the fusion, and 10 us of nothing."""
    def line(name, events):
        body = "".join(
            f"events {{ metadata_id: {mid} offset_ps: {int(start * 1e6)} "
            f"duration_ps: {int(dur * 1e6)} }}\n" for mid, start, dur in events)
        return f'lines {{ name: "{name}" timestamp_ns: 1000 {body} }}\n'

    starts = [i * 110.0 for i in range(4)]
    starts += [starts[-1] + 100.0 + 300.0 + i * 110.0 for i in range(4)]
    modules = [(1, s, 100.0) for s in starts] + [(4, starts[3] + 150.0, 5.0)]
    ops = []
    for s in starts:
        ops += [(2, s, 60.0), (3, s + 50.0, 30.0), (2, s + 90.0, 10.0)]
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
        for k, n in ((1, "jit_step(7)"), (2, "fusion.1"),
                     (3, "all-reduce.2"), (4, "jit_pull(9)"),
                     (5, "bench.fit.epoch"), (6, "bench.feed.next"),
                     (7, "other")))
    device = line("XLA Modules", modules) + line("XLA Ops", ops) + meta
    host = line("main", [(5, -5.0, 745.0), (7, 0.0, 10.0)]) \
        + line("feed", [(6, 10.0, 1.0)]) + meta
    return ('planes { name: "/device:TPU:0" ' + device + "}\n"
            'planes { name: "/device:TPU:1" ' + device + "}\n"
            'planes { name: "/host:CPU" ' + host + "}\n"
            'planes { name: "/host:metadata" }\n')


def test_trace_reduction_on_a_synthetic_trace():
    from jax.profiler import ProfileData

    events = trace_reduce.events_of(
        ProfileData.from_text_proto(_synthetic_trace()))
    assert sorted(events["devices"]) == [0, 1]
    assert [s[0] for s in events["spans"]] == ["bench.fit.epoch",
                                               "bench.feed.next"]
    r = trace_reduce.reduce(events, steps_per_epoch=4)
    assert r["chips"] == 2 and r["program"] == "jit_step" and r["steps"] == 8
    assert r["device_step_ms_p50"] == pytest.approx(0.1)
    assert r["step_gap_ms_p50"] == pytest.approx(0.01)
    assert r["epoch_device_span_s"] == pytest.approx([430e-6, 430e-6])
    # one epoch period: first step of epoch 1 to first step of epoch 2
    assert r["window_s"] == pytest.approx(730e-6)
    assert r["busy_s"] == pytest.approx(4 * 90e-6)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(4 * 70e-6)]
    assert r["top_ops"][1] == ["all-reduce.2", pytest.approx(4 * 30e-6)]
    assert r["collective_ms_per_step"] == pytest.approx(0.03)
    assert r["collective_exposed_ms_per_step"] == pytest.approx(0.02)
    name, seconds = r["idle_gaps"][0]
    assert name == "fit.epoch/epoch_tail" and seconds == pytest.approx(300e-6)
    assert r["idle_gaps"][1][0] == "fit.epoch/between_steps"
    assert trace_reduce.reduce({"devices": {}, "spans": []}, 4) is None
    assert trace_reduce.union_ns([(0, 2), (1, 3), (5, 6)]) == 4


RECORDED = os.path.join(BENCH, "testdata", "resnet50_device.xplane.pb.gz")
PINNED = os.path.join(BENCH, "testdata", "resnet50_device.reduced.json")


def test_trace_reduction_on_the_recorded_chip_trace():
    """A trace recorded on one v5e by this benchmark (PR 23), cut to its
    first five train steps; the reduction's output on it is pinned."""
    pinned = catalog.read_json(PINNED)
    r = trace_reduce.reduce(trace_reduce.load(RECORDED),
                            pinned["steps_per_epoch"])
    assert r["program"] == pinned["program"] == "jit_step"
    assert r["steps"] == pinned["steps"] == 5
    for key in ("device_step_ms_p50", "step_gap_ms_p50", "window_s",
                "busy_s"):
        assert r[key] == pytest.approx(pinned[key], rel=1e-9), key
    assert r["epoch_device_span_s"] == pytest.approx(
        pinned["epoch_device_span_s"])
    assert r["top_ops"][0][0] == "convert_reduce_fusion.7"
    for got, want in zip(r["top_ops"], pinned["top_ops"]):
        assert got[0] == want[0] and got[1] == pytest.approx(want[1])
    assert [g[0] for g in r["idle_gaps"]] == [g[0] for g in
                                              pinned["idle_gaps"]]
    assert r["collective_ops"] == 0


# -- the runner, in a child process, on added files only -------------------------

TINY_CONFIG = {
    "name": "tiny_resnet",
    "source": "test preset: one bottleneck unit a stage, narrow, 32x32",
    "sample": "one 32x32x3 image",
    "builder": {"import": "mxnet_tpu.models:resnet",
                "kwargs": {"units": [1, 1, 1, 1], "num_classes": 10,
                           "filter_list": [16, 32, 64, 128],
                           "layout": "NHWC"}},
    "image": [32, 32, 3],
    "per_chip_batch": 8,
    "compute_dtype": "bfloat16",
    "optimizer": {"name": "sgd", "learning_rate": 0.01, "momentum": 0.9},
    "initializer": {"name": "Xavier"},
    "logits": "fc1_output",
    "reference": "tiny_resnet.py",
    "reference_rows": 8,
    "reference_tolerance": 0.1,
    "reduced": [],
    "flops_per_sample": {"layers": [
        {"op": "fc", "name": "fc1", "cin": 128, "cout": 10}]},
}
STEPS_METRIC = '''
METRIC = {"name": "steps_in_window", "unit": "count", "better": "higher",
          "source": "program_counter", "layer": "test layer",
          "moves": "samples_per_s_per_chip"}


def read(run):
    return run["rows"][-1]["steps"] - run["rows"][0]["steps"]
'''


@pytest.fixture(scope="module")
def overlay(tmp_path_factory, bench):
    """A checkout-shaped directory: ``benchmark/`` copied as it is, plus
    ADDED files and a ``BENCHMARK.json`` with added entries."""
    root = tmp_path_factory.mktemp("bench_overlay")
    here = root / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "tiny_resnet.json").write_text(json.dumps(TINY_CONFIG))
    reference = (here / "configs" / "resnet50.py").read_text()
    assert "UNITS = (3, 4, 6, 3)" in reference
    (here / "configs" / "tiny_resnet.py").write_text(
        reference.replace("UNITS = (3, 4, 6, 3)", "UNITS = (1, 1, 1, 1)"))
    (here / "traffic" / "tiny_ring.json").write_text(json.dumps(
        {"kind": "device_ring", "ring": 3, "steps_per_epoch": 4,
         "warmup_steps": 3, "dtype": "float32", "class_shift": 0.5}))
    (here / "traffic" / "tiny_ring_dp.json").write_text(json.dumps(
        {"kind": "device_ring", "ring": 3, "steps_per_epoch": 4,
         "dtype": "float32", "class_shift": 0.5}))
    (here / "layer_metrics" / "steps_in_window.py").write_text(STEPS_METRIC)
    added = dict(bench)
    added["configs"] = bench["configs"] + [
        {"name": "tiny_resnet", "source": TINY_CONFIG["source"],
         "file": "benchmark/configs/tiny_resnet.json", "reduced": [],
         "why": "test preset"}]
    added["workloads"] = bench["workloads"] + [
        {"name": "tiny.device", "config": "tiny_resnet",
         "traffic": "tiny_ring", "chips": 1, "why": "test"},
        {"name": "tiny.dp4", "config": "tiny_resnet",
         "traffic": "tiny_ring_dp", "chips": 4, "why": "test"}]
    added["per_layer"] = [
        dict(m, workloads=m["workloads"] + ["tiny.dp4"])
        if "workloads" in m else m for m in bench["per_layer"]] + [
        dict(catalog.load_file_module(
            str(here / "layer_metrics" / "steps_in_window.py"),
            "steps_in_window").METRIC,
             workloads=["tiny.device"])]
    (root / "BENCHMARK.json").write_text(json.dumps(added))
    yield root
    for p, content in before.items():     # nothing that was there changed
        assert p.read_bytes() == content, p


def _run(root, *args, devices=4):
    return _spawn([sys.executable, os.path.join("benchmark", "run.py"),
                   *args], cwd=root, devices=devices)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    earlier = [json.loads(ln) for ln in lines[:-1] if ln.startswith("{")]
    return result, earlier


@pytest.mark.parametrize("cell,trace", [
    ("tiny.device", 0), ("tiny.device", 1), ("tiny.dp4", 0),
    ("tiny.dp4", 1)])
def test_runner_on_added_files_at_the_tiny_preset(overlay, bench, cell,
                                                   trace):
    """The runner resolves a configuration, traffic mixes, a layer metric
    and cells that exist only as added files; ``tiny.dp4`` is the
    four-virtual-device rehearsal of the four-chip cell's path."""
    proc = _run(overlay, "--workload", cell, "--seed", str(2 ** 31 + 11),
                "--seconds", "0.5", "--trace", str(trace),
                "--rehearse-on-cpu")
    result, earlier = _result(proc)
    assert set(result) == RESULT_KEYS | ({"breakdown"} & set(result))
    assert result["correct"] is True, earlier
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["attempted"] % 4 == 0          # whole epochs only
    chips = 4 if cell.endswith("dp4") else 1
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert "memory_peak_bytes" in result["device"]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    got = set(result["metrics"])
    if trace == 0:
        assert got == {m["name"] for m in bench["end_to_end"]} - {
            "peak_hbm_mb"}          # the CPU backend reports no memory
        assert result["metrics"]["samples_per_s_per_chip"]["value"] > 0
    else:
        # no TPU plane in a CPU trace: the device-trace readers find
        # nothing and are left out; the others report
        assert {"epoch_rate_median", "epoch_rate_min_over_median",
                "precompile_s", "compiles_in_window"} <= got
        assert "device_step_ms" not in got and "mfu_device" not in got
        assert result["metrics"]["compiles_in_window"]["value"] == 0.0
        assert ("steps_in_window" in got) == (cell == "tiny.device")
        if cell == "tiny.device":
            assert result["metrics"]["steps_in_window"]["value"] == \
                result["attempted"]
    # rule 4: every epoch's reading and its two stamps on an earlier line
    epoch_lines = [e for e in earlier if "epochs" in e]
    assert len(epoch_lines) == 1
    rows = epoch_lines[0]["epochs"]
    assert len(rows) * 4 == result["attempted"]
    # the warm-up epoch is as long as the traffic mix says, else whole
    assert epoch_lines[0]["warmup_steps"] == (3 if chips == 1 else 4)
    assert epoch_lines[0]["window_seconds"] >= sum(r["seconds"] for r in rows)
    if trace == 0:
        assert result["metrics"]["samples_per_s_per_chip"]["value"] == \
            pytest.approx(epoch_lines[0]["window_samples"]
                          / epoch_lines[0]["window_seconds"] / chips)
    for row in rows:
        assert row["end"] > row["start"]
        assert row["seconds"] == pytest.approx(row["end"] - row["start"])
    assert any("setup_items" in e for e in earlier)


def test_runner_exits_nonzero_without_a_tpu(overlay):
    proc = _run(overlay, "--workload", "tiny.device", "--seed", "1",
                "--seconds", "0.5", "--trace", "0")
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    # fewer devices than the cell's chips: the same, even when rehearsing
    proc = _run(overlay, "--workload", "tiny.dp4", "--seed", "1",
                "--seconds", "0.5", "--trace", "0", "--rehearse-on-cpu",
                devices=2)
    assert proc.returncode != 0 and "asks for 4" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
