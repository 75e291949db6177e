"""Tests of the benchmark harness (``benchmark/``), on the CPU at a tiny
preset. Everything that runs the runner does so in a child process with an
environment of its own, in a copy of ``benchmark/`` to which the test ADDS
files (a configuration, traffic mixes, a per-layer metric, cells) and
edits none: that a later PR can do the same is what is being tested.
No TPU topology is described anywhere in this file.
"""

import ast
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
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
scopes = _module(os.path.join(BENCH, "scopes.py"), "bench_scopes")
walk = _module(os.path.join(BENCH, "walk.py"), "bench_walk")


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


# -- the accepted record ----------------------------------------------------------

# What the benchmark held when each of its cells was accepted, by NAME (the
# convnets PR 23, ``laguna_xs2.seq8k`` PR 27, ``sdar_30b_a3b.blockdiff4k``
# PR 33). A later PR appends cells, metrics and names to the lists and edits
# none; only a ``benchmark`` PR extends this record, and a configuration's
# own test file imports it and keeps what is its own.
ACCEPTED_CELLS = {
    "resnet50.device": ("resnet50", "device_ring", 1),
    "inception_bn.device": ("inception_bn", "device_ring", 1),
    "resnet50.dp4": ("resnet50", "device_ring_dp", 4),
    "laguna_xs2.seq8k": ("laguna_xs2", "token_ring_8k", 1),
    "sdar_30b_a3b.blockdiff4k": ("sdar_30b_a3b", "token_ring_blockdiff_4k",
                                 1)}
COMMON_METRICS = [
    "epoch_tail_ms", "epoch_rate_median", "epoch_rate_min_over_median",
    "step_gap_ms_p50", "precompile_s", "compiles_in_window",
    "device_step_ms", "mfu_device", "collective_ms_per_step",
    "collective_exposed_ms_per_step", "plan_mb", "device_idle_pct",
    "write_back_ms", "epoch_tail_host_ms", "epoch_tail_unnamed_ms",
    "host_step_ms_p10", "feed_wait_ms_per_step", "init_params_s",
    "fit_start_s", "forward_ms_per_step", "backward_ms_per_step",
    "optimizer_unfused_ms_per_step", "unscoped_ms_per_step"]
ATTENTION_METRICS = [
    "attention_window_ms_per_step", "attention_full_ms_per_step",
    "attention_window_roofline_pct", "attention_full_roofline_pct"]
MOE_METRICS = [
    "moe_ms_per_step", "moe_grouped_roofline_pct", "moe_load_max_over_mean",
    "moe_picks_held_per_token"]
DECODER_METRICS = ATTENTION_METRICS + MOE_METRICS      # laguna_xs2's eight
BLOCKDIFF_METRICS = [                                  # sdar_30b_a3b's three
    "attention_blockdiff_ms_per_step", "attention_blockdiff_roofline_pct",
    "blockdiff_masked_share"]
ACCEPTED_METRICS = COMMON_METRICS + DECODER_METRICS + BLOCKDIFF_METRICS
# the cells each accepted metric is read in, in the order they joined; a
# metric that is not here has no ``workloads`` key: every cell reports it
ACCEPTED_WORKLOADS = {
    "collective_ms_per_step": ["resnet50.dp4"],
    "collective_exposed_ms_per_step": ["resnet50.dp4"],
    **{name: ["laguna_xs2.seq8k"] for name in ATTENTION_METRICS},
    **{name: ["laguna_xs2.seq8k", "sdar_30b_a3b.blockdiff4k"]
       for name in MOE_METRICS},
    **{name: ["sdar_30b_a3b.blockdiff4k"] for name in BLOCKDIFF_METRICS}}
ACCEPTED_BOUNDS = {"samples_per_s_per_chip": 0.01, "peak_hbm_mb": 0.01,
                   "setup_s": 0.1}


# -- BENCHMARK.json against its contract and against the files ---------------

@pytest.fixture(scope="module",
                params=["repository", "later_pr", "second_blockdiff"])
def tree(request, bench, tmp_path_factory):
    """A checkout whose ``BENCHMARK.json`` and files the contract tests
    read: the repository's own, and two trees a later PR makes of it by
    adding files and entries: ``overlay``'s (three configurations and five
    cells, two of them four-chip rehearsals) and the one the next
    ``model_config`` PR makes (``_second_blockdiff``: ONE configuration,
    one one-chip cell). ``quota``: the tree keeps to the contract's share
    of four-chip cells."""
    if request.param == "repository":
        yield types.SimpleNamespace(root=ROOT, bench=bench, quota=True)
    elif request.param == "later_pr":
        root = str(request.getfixturevalue("overlay"))
        yield types.SimpleNamespace(root=root, quota=False,
                                    bench=catalog.load_benchmark(root))
    else:
        root = tmp_path_factory.mktemp("bench_blockdiff")
        before = _second_blockdiff(root, bench)
        yield types.SimpleNamespace(root=str(root), quota=True,
                                    bench=catalog.load_benchmark(str(root)))
        _nothing_that_was_there_changed(before)


def test_benchmark_json_keys_names_and_units(tree):
    bench = tree.bench
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
    if tree.quota:          # ``overlay`` rehearses the four-chip path twice
        assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for folder in bench["paths"]:
        for base, _, files in os.walk(os.path.join(tree.root, folder)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), tree.root)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_every_entry_has_its_file_and_they_agree(tree):
    bench, here = tree.bench, os.path.join(tree.root, "benchmark")
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        meta = catalog.load_metric("end_to_end", m["name"], here=here).METRIC
        assert {k: m[k] for k in meta} == meta
    for m in bench["per_layer"]:
        meta = catalog.load_metric("layer_metrics", m["name"],
                                   here=here).METRIC
        assert {k: m[k] for k in meta} == meta
        # the cells a metric is read in are the entry's alone: a later PR
        # appends its cell there without touching the reader's file
        assert "workloads" not in meta, m["name"]
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        found = catalog.find_cell(bench, w["name"], root=tree.root, here=here)
        config = found["config"]
        assert found["config_entry"]["file"].startswith(
            tuple(p + "/" for p in bench["paths"]))
        assert config["reduced"] == found["config_entry"]["reduced"]
        assert config["source"] == found["config_entry"]["source"]
        assert os.path.isfile(os.path.join(
            os.path.dirname(found["config_path"]), config["reference"]))
        assert os.path.isfile(os.path.join(
            here, "feeds", found["traffic"]["kind"] + ".py"))
    with pytest.raises(catalog.BenchmarkError):
        catalog.find_cell(bench, "no.such.cell", root=tree.root, here=here)
    with pytest.raises(catalog.BenchmarkError):
        catalog.peak_for("cpu", here=here)
    assert catalog.peak_for("TPU v5 lite", here=here)["bf16_flops"] == 197e12


def test_the_accepted_cells_and_metrics_are_as_they_were(tree):
    """Whatever came after: every accepted cell, metric and bound is found
    by its name and is what it was accepted as; the accepted metrics head
    ``per_layer`` in their order and the accepted names head each
    ``workloads`` list in theirs, because a later PR appends."""
    bench = tree.bench
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(cells) == len(bench["workloads"])
    for name, (of, mix, chips) in ACCEPTED_CELLS.items():
        assert (cells[name]["config"], cells[name]["traffic"],
                cells[name]["chips"]) == (of, mix, chips), name
    assert {c["name"] for c in bench["configs"]} >= {
        of for of, _, _ in ACCEPTED_CELLS.values()}
    assert len(ACCEPTED_METRICS) == len(set(ACCEPTED_METRICS)) == 23 + 8 + 3
    assert [m["name"] for m in bench["per_layer"]][
        :len(ACCEPTED_METRICS)] == ACCEPTED_METRICS
    assert set(ACCEPTED_WORKLOADS) <= set(ACCEPTED_METRICS)
    for m in bench["per_layer"][:len(ACCEPTED_METRICS)]:
        accepted = ACCEPTED_WORKLOADS.get(m["name"])
        if accepted is None:        # every cell reports it, a later one too
            assert "workloads" not in m, m["name"]
        else:
            assert m["workloads"][:len(accepted)] == accepted, m["name"]
    assert {m["name"]: m["bound"] for m in bench["end_to_end"]
            if m["name"] in ACCEPTED_BOUNDS} == ACCEPTED_BOUNDS
    assert bench["run_seconds"] == 24


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

def test_flops_hand_worked_layers_and_totals(tree):
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
    for c in tree.bench["configs"]:
        layers = catalog.read_json(os.path.join(tree.root, c["file"]))[
            "flops_per_sample"]["layers"]
        totals[c["name"]] = flops.train_flops_per_sample(layers) / 1e9
    # 4.09 G multiply-adds forward (v1.5) -> 8.18 GFLOP, 24.5 with backward
    assert totals["resnet50"] == pytest.approx(24.535, abs=0.001)
    if "inception_bn" in totals:
        # the published network: 2.03 G multiply-adds forward
        assert totals["inception_bn"] == pytest.approx(12.196, abs=0.001)


def test_flops_matmul_and_attention_by_hand():
    # a product at every one of 8,192 positions of a sample: 2,048 x 4,096
    # = 8,388,608 multiply-adds a position, x 8,192 = 68,719,476,736,
    # 137,438,953,472 FLOP forward
    proj = {"op": "matmul", "name": "q_proj", "cin": 2048, "cout": 4096,
            "rows": 8192}
    assert flops.layer_forward_flops(proj) == 137_438_953_472
    # rows may be a fraction: the picks one chip's experts are expected to
    # serve, 8,192 positions x 8 picks x 16 of 64 experts = 16,384 ... and
    # 8,192 x 8 x 1/3 is no whole number
    routed = dict(proj, rows=8192 * 8 / 3)
    assert flops.layer_forward_flops(routed) == pytest.approx(
        2 * 2048 * 4096 * 8192 * 8 / 3)
    # causal attention over T = 8: query i sees i + 1 keys, 1 + ... + 8 =
    # 36 pairs, 4.5 a query = (T + 1) / 2. 2 heads, 16 a key for the
    # scores and 16 for the values: 2 x 36 x 32 = 2,304 multiply-adds
    full = {"op": "attention", "name": "attn", "heads": 2, "qk_dim": 16,
            "v_dim": 16, "q_len": 8, "kv_mean": (8 + 1) / 2}
    assert flops.layer_forward_flops(full) == 2 * 2304
    # window W = 3 over T = 8: 1 + 2 + 3 + 3 x 5 = 21 pairs, 2.625 a query
    # = W - W (W - 1) / (2 T) = 3 - 6 / 16; 2 x 21 x 32 = 1,344
    assert 3 - 3 * 2 / (2 * 8) == 21 / 8
    windowed = dict(full, kv_mean=3 - 3 * (3 - 1) / (2 * 8))
    assert flops.layer_forward_flops(windowed) == 2 * 1344
    # the window as long as the sequence is the full causal count
    assert 8 - 8 * 7 / (2 * 8) == (8 + 1) / 2
    # latent attention: the keys' width and the values' differ
    assert flops.layer_forward_flops(
        dict(full, qk_dim=24, v_dim=16)) == 2 * 2 * 36 * 40
    assert flops.train_flops_per_sample([proj, full]) == \
        3 * (137_438_953_472 + 4608)


def test_layer_lists_match_the_models_the_builders_make(bench):
    """The recipe in a configuration's file is data; the model is code.
    Walk the built symbol's nodes, each by its operator's walker
    (``benchmark/walkers/``), and require the same layers."""
    for c in bench["configs"]:
        config = catalog.read_json(os.path.join(ROOT, c["file"]))
        symbol = catalog.build_symbol(
            config["builder"], os.path.dirname(os.path.join(ROOT, c["file"])))
        walked = walk.layers_of(symbol, config)
        assert walked == config["flops_per_sample"]["layers"], c["name"]
        if "block_channels" in config:     # the source's own table
            internals = symbol.get_internals()
            _, out_shapes, _ = internals.infer_shape(
                data=(1, *walk.sample_shape(config)))
            blocks = [shape[-1] for name, shape in
                      zip(internals.list_outputs(), out_shapes)
                      if name.endswith("_chconcat_output")]
            assert blocks == config["block_channels"], c["name"]


def test_the_walk_goes_by_operator_and_a_walkerless_one_fails_by_name(
        tmp_path):
    import mxnet_tpu as mx

    sym = mx.symbol
    data = sym.Variable("data")
    # per-position products: FullyConnected on (positions, width) rows is
    # a matmul with the rows a sample has; Embedding and BatchNorm have
    # learnable arguments and no product
    ids_net = sym.FullyConnected(
        data=sym.Reshape(data=sym.Embedding(
            data=data, input_dim=50, output_dim=12, name="embed"),
            target_shape=(-1, 12), name="rows"),
        num_hidden=20, name="proj")
    assert walk.layers_of(ids_net, {"input_shape": [6]}) == [
        {"op": "matmul", "name": "proj", "cin": 12, "cout": 20, "rows": 6}]
    image_net = sym.FullyConnected(
        data=sym.Flatten(data=sym.BatchNorm(data=sym.Convolution(
            data=data, kernel=(3, 3), num_filter=4, pad=(1, 1),
            name="c1"), name="bn")), num_hidden=5, name="head")
    assert walk.layers_of(image_net, {"image": [3, 8, 8]}) == [
        {"op": "conv", "name": "c1", "kernel": [3, 3], "cin": 3, "cout": 4,
         "out": [8, 8]},
        {"op": "fc", "name": "head", "cin": 256, "cout": 5}]
    # a learnable argument under an operator no file accounts for
    up = sym.Deconvolution(data=data, kernel=(2, 2), num_filter=4,
                           name="up")
    with pytest.raises(walk.WalkError) as err:
        walk.layers_of(up, {"image": [3, 8, 8]})
    assert "'Deconvolution'" in str(err.value)
    assert "walkers/Deconvolution.py" in str(err.value)
    # ... until a later PR adds the file, beside the others, editing none
    here = tmp_path / "benchmark"
    shutil.copytree(os.path.join(BENCH, "walkers"), here / "walkers")
    (here / "walkers" / "Deconvolution.py").write_text(
        "def layers(node, in_shapes, out_shapes):\n"
        "    w = in_shapes[node['args'].index('weight')]\n"
        "    return [{'op': 'conv', 'name': node['name'], 'kernel': "
        "[w[2], w[3]], 'cin': w[0], 'cout': w[1], "
        "'out': list(in_shapes[0][2:])}]\n")
    assert walk.layers_of(up, {"image": [3, 8, 8]}, here=str(here)) == [
        {"op": "conv", "name": "up", "kernel": [2, 2], "cin": 3, "cout": 4,
         "out": [8, 8]}]


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

def _synthetic_trace(pull_op_us=0.0):
    """Two chips' worth of two 4-step epochs: 100 us steps 10 us apart, a
    300 us epoch tail; every step holds a 60 us fusion, a 30 us all-reduce
    of which 10 us overlap the fusion, and 10 us of nothing. With
    ``pull_op_us`` the other program, ``jit_pull``, runs an instruction
    that has the NAME of the train program's fusion."""
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
    if pull_op_us:
        ops.append((2, starts[3] + 150.0, pull_op_us))
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
        for k, n in ((1, "jit_step(7)"), (2, "fusion.1"),
                     (3, "all-reduce.2"), (4, "jit_pull(9)"),
                     (5, "bench.fit.epoch"), (6, "bench.feed.next"),
                     (7, "other"), (8, "mx.fit.epoch.write_back")))
    device = line("XLA Modules", modules) + line("XLA Ops", ops) + meta
    # the program's own span covers 200 us of the 300 us epoch tail
    host = line("main", [(5, -5.0, 745.0), (7, 0.0, 10.0),
                         (8, 480.0, 200.0)]) \
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
    assert [s[0] for s in events["spans"]] == [
        "bench.fit.epoch", "bench.feed.next", "mx.fit.epoch.write_back"]
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
    # every instruction's seconds in the traced span, of which top_ops
    # is the head, and the steps the span holds
    assert r["op_seconds"] == {"fusion.1": pytest.approx(4 * 70e-6),
                               "all-reduce.2": pytest.approx(4 * 30e-6)}
    assert [list(kv) for kv in r["op_seconds"].items()] == r["top_ops"]
    assert r["program_op_seconds"] == r["op_seconds"]   # one program's only
    assert r["span_steps"] == 4
    # a gap is named by the shortest span over its middle: the program's
    # own (``mx.``) inside the harness's wrapper, each without its prefix
    name, seconds = r["idle_gaps"][0]
    assert name == "fit.epoch.write_back/epoch_tail"
    assert seconds == pytest.approx(300e-6)
    assert r["idle_gaps"][1][0] == "fit.epoch/between_steps"
    assert trace_reduce.reduce({"devices": {}, "spans": []}, 4) is None
    assert trace_reduce.union_ns([(0, 2), (1, 3), (5, 6)]) == 4


def test_an_instruction_of_another_program_stays_out_of_the_train_programs():
    """An instruction's name is unique in its program only: what ran
    outside the train program's executions is in ``op_seconds`` and not in
    ``program_op_seconds``, which the join with the HLO text reads."""
    from jax.profiler import ProfileData

    r = trace_reduce.reduce(trace_reduce.events_of(
        ProfileData.from_text_proto(_synthetic_trace(pull_op_us=4.0))), 4)
    assert r["busy_s"] == pytest.approx(4 * 90e-6 + 4e-6)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(4 * 70e-6 + 4e-6)
    assert r["program_op_seconds"] == {
        "fusion.1": pytest.approx(4 * 70e-6),
        "all-reduce.2": pytest.approx(4 * 30e-6)}
    # the scope join gives the stray 4 us to ``unscoped``, not to the
    # scope the train program's ``fusion.1`` has
    run = {"trace": r, "hlo_scopes": {
        "fusion.1": "jit(step)/jvp(c1/Convolution)/conv_general_dilated",
        "all-reduce.2": "jit(step)/transpose(jvp(c1/Convolution))/psum"}}
    assert scopes.bucket_ms_per_step(run, "forward") == pytest.approx(0.07)
    assert scopes.bucket_ms_per_step(run, "backward") == pytest.approx(0.03)
    assert scopes.bucket_ms_per_step(run, "unscoped") == pytest.approx(0.001)
    assert scopes.ms_per_step(run, "c1/Convolution") == pytest.approx(0.1)


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
    # since PR 26: every instruction of the span, not the ten longest. On
    # one chip's line no two run at once, so they add up to the busy time
    ops = r["op_seconds"]
    assert len(ops) == 4150 and r["span_steps"] == 4
    assert [list(kv) for kv in list(ops.items())[:10]] == r["top_ops"]
    assert list(ops.values()) == sorted(ops.values(), reverse=True)
    assert sum(ops.values()) == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert ops["custom-call.58"] == pytest.approx(1e-9)
    # the train program's own: the five instructions of the tiny programs
    # fit runs a step fall out (a ``fusion.16`` among them, a name that
    # any program may have)
    own = r["program_op_seconds"]
    assert len(own) == 4145 and set(own) <= set(ops)
    assert sum(own.values()) == pytest.approx(0.4126494, rel=1e-7)
    assert sorted(set(ops) - set(own)) == [
        "add_add_fusion", "broadcast_add_fusion", "fusion.16",
        "pad_add_fusion", "slice_bitcast_fusion"]
    assert all(own[n] == ops[n] for n in own)


# -- device time by scope ----------------------------------------------------------

HLO_TEXT = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %convert.5 = bf16[8]{0} convert(%param_0), metadata={op_name="jit(step)/jvp()/convert_element_type" stack_frame_id=3}
  %convert.6 = f32[8]{0} convert(%convert.5)
  ROOT %bitcast.2 = f32[8]{0} bitcast(%convert.6)
}

%fused_computation.2 (param_0.1: f32[8], param_1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %mul.3 = f32[8]{0} multiply(%param_0.1, %param_1), metadata={op_name="jit(step)/optimizer/update/mul"}
  %add.4 = f32[8]{0} add(%mul.3, %param_1), metadata={op_name="jit(step)/optimizer/update/add"}
  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0}) tuple(%add.4, %mul.3)
}

ENTRY %main.10 (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %p1 = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %fusion.7 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(fc1/FullyConnected))/dot_general"}
  %custom-call.3 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn0/FlashAttention)/pallas_call[name=flash_fwd]"}
  %copy.4 = f32[8]{0} copy(%custom-call.3)
  ROOT %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%copy.4, %p1), kind=kLoop, calls=%fused_computation.2
}
'''


def test_hlo_scopes_reads_every_instructions_scope_from_the_text():
    got = scopes.hlo_scopes(HLO_TEXT)
    # its own op_name first; a fusion without one takes its root's, and a
    # root that is a bitcast or a tuple the nearest above it
    assert got["fusion.7"] == \
        "jit(step)/transpose(jvp(fc1/FullyConnected))/dot_general"
    assert got["fusion.1"] == "jit(step)/jvp()/convert_element_type"
    assert got["fusion.2"] == "jit(step)/optimizer/update/add"
    assert got["custom-call.3"].endswith("pallas_call[name=flash_fwd]")
    # where the program cannot say: None, not a guess from a neighbour
    assert got["copy.4"] is None and got["p1"] is None
    assert got["p0"] == "params[\\'w\\']"
    assert got["mul.3"] == "jit(step)/optimizer/update/mul"   # nested too
    assert set(got) >= {"param_0", "convert.5", "tuple.9", "fusion.2"}
    assert [scopes.bucket(got[n]) for n in
            ("fusion.1", "fusion.7", "fusion.2", "copy.4", "p0",
             "custom-call.3")] == ["forward", "backward", "optimizer_unfused",
                                   "unscoped", "unscoped", "forward"]


def test_the_train_programs_text_or_the_reason_it_was_not_read(monkeypatch):
    import types

    import jax

    monkeypatch.setitem(sys.modules, "catalog", catalog)   # checks imports it
    checks = _module(os.path.join(BENCH, "checks.py"), "bench_checks")

    class Warm:
        def __init__(self, answer):
            self.answer = answer

        def as_text(self):
            if isinstance(self.answer, Exception):
                raise self.answer
            return self.answer

    def spy(*programs):
        return types.SimpleNamespace(tracked=types.SimpleNamespace(
            _aot=dict(enumerate(programs))))

    assert checks.train_program_text(spy(Warm(HLO_TEXT))) == (HLO_TEXT, None)
    for broken, why in (
            (types.SimpleNamespace(tracked=None), "no _tracked handle"),
            (spy(), "holds 0 warmed"),
            (spy(Warm("a"), Warm("b")), "holds 2 warmed"),
            (spy(Warm(None)), "gives no text"),
            (spy(Warm(jax.errors.JaxRuntimeError("UNIMPLEMENTED: hidden"))),
             "UNIMPLEMENTED: hidden")):
        text, reason = checks.train_program_text(broken)
        assert text is None and why in reason, reason
    # anything else is a fault of the harness and is not swallowed
    with pytest.raises(ZeroDivisionError):
        checks.train_program_text(spy(Warm(ZeroDivisionError())))


def test_ms_per_step_by_scope_and_the_four_buckets_on_hand_written_maps():
    own = {"fusion.1": 0.120, "fusion.7": 0.200, "custom-call.3": 0.040,
           "fusion.2": 0.020, "copy.4": 0.012, "all-reduce.5": 0.006}
    run = {
        # another program ran a ``convert.9`` and a ``fusion.7`` of its own
        "trace": {"span_steps": 4, "busy_s": 0.4, "program_op_seconds": own,
                  "op_seconds": dict(own, **{"fusion.7": 0.2015,
                                             "convert.9": 0.0005})},
        "hlo_scopes": {
            "fusion.1": "jit(step)/jvp(c1/Convolution)/conv_general_dilated",
            "fusion.7": "jit(step)/transpose(jvp(c1/Convolution))/"
                        "conv_general_dilated",
            "custom-call.3": "jit(step)/jvp(attn0/FlashAttention)/"
                             "pallas_call[name=flash_fwd]",
            "fusion.2": "jit(step)/optimizer/update/add",
            "copy.4": None,
            "all-reduce.5": "jit(step)/comm/allreduce/psum",
            "never_ran.1": "jit(step)/jvp(c2/Convolution)/mul"},
    }
    by = {b: scopes.bucket_ms_per_step(run, b) for b in scopes.BUCKETS}
    assert by == {"forward": pytest.approx(40.0),     # 120 + 40 over 4
                  "backward": pytest.approx(50.0),
                  "optimizer_unfused": pytest.approx(5.0),
                  "unscoped": pytest.approx(5.0)}     # 12 + 6 + 2 over 4
    # disjoint, and together every instruction that ran: the busy time
    assert sum(by.values()) == pytest.approx(1e3 * 0.4 / 4)
    # one kernel, one layer or one phase, each instruction counted once
    assert scopes.ms_per_step(run, r"attn0/FlashAttention") == \
        pytest.approx(10.0)
    assert scopes.ms_per_step(run, r"pallas_call\[name=flash_fwd\]") == \
        pytest.approx(10.0)
    assert scopes.ms_per_step(run, r"c1/Convolution") == pytest.approx(80.0)
    assert scopes.ms_per_step(run, r"Convolution|c1/") == \
        pytest.approx(80.0)
    # nothing to read is nothing, never 0
    assert scopes.ms_per_step(run, r"c2/Convolution") is None
    assert scopes.ms_per_step({"trace": None, "hlo_scopes": {}}, "x") is None
    assert scopes.bucket_ms_per_step(dict(run, hlo_scopes=None),
                                     "forward") is None
    lone = {"trace": {"span_steps": 1, "op_seconds": {"copy.1": 0.5},
                      "program_op_seconds": {"copy.1": 0.5}},
            "hlo_scopes": {"copy.1": None}}
    assert scopes.bucket_ms_per_step(lone, "optimizer_unfused") is None
    assert scopes.bucket_ms_per_step(lone, "unscoped") == pytest.approx(500.0)
    # the four metric files read the four buckets
    for b in scopes.BUCKETS:
        metric = catalog.load_metric("layer_metrics", b + "_ms_per_step")
        assert metric.read(run) == by[b]
        assert metric.read({"trace": None, "hlo_scopes": None}) is None


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


# a configuration whose sample is a sequence of token ids, made of added
# files only: ids -> Embedding 64 x 16 -> (rows x positions, 16) -> two
# products a position -> softmax over the 64 ids on (rows x positions,)
# labels, built from operators the program has
TOKENS_CONFIG = {
    "name": "tiny_tokens",
    "source": "test preset: ids, an embedding and two products a position",
    "sample": "one sequence of 8 token ids",
    "builder": {"file": "tiny_tokens_model.py", "call": "build",
                "kwargs": {"vocab": 64, "width": 16, "hidden": 32}},
    "input_shape": [8],
    "vocab_rows": 64,
    "per_chip_batch": 8,
    "compute_dtype": "bfloat16",
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9},
    "initializer": {"name": "Xavier"},
    "logits": "head_output",
    "reference": "tiny_tokens.py",
    "reference_rows": 8,
    "reference_tolerance": 0.1,
    "reduced": [],
    "flops_per_sample": {"layers": [
        {"op": "matmul", "name": "hidden", "cin": 16, "cout": 32, "rows": 8},
        {"op": "matmul", "name": "head", "cin": 32, "cout": 64, "rows": 8}]},
}
TOKENS_BUILDER = '''
import mxnet_tpu as mx


def build(vocab, width, hidden):
    sym = mx.symbol
    rows = sym.Reshape(
        data=sym.Embedding(data=sym.Variable("data"), input_dim=vocab,
                           output_dim=width, name="embed"),
        target_shape=(-1, width), name="rows")
    hidden = sym.Activation(
        data=sym.FullyConnected(data=rows, num_hidden=hidden, name="hidden"),
        act_type="relu", name="hidden_relu")
    head = sym.FullyConnected(data=hidden, num_hidden=vocab, name="head")
    return sym.SoftmaxOutput(
        data=head, name="softmax",
        label=sym.Reshape(data=sym.Variable("softmax_label"),
                          target_shape=(-1,), name="label_rows"))
'''
TOKENS_REFERENCE = '''
import jax.numpy as jnp


def logits(params, aux, ids):
    table = params["embed_weight"]
    x = table[ids].reshape(-1, table.shape[1])
    h = jnp.maximum(x @ params["hidden_weight"].T + params["hidden_bias"], 0)
    return h @ params["head_weight"].T + params["head_bias"]
'''
TOKEN_RING = {"kind": "token_ring", "ring": 3, "steps_per_epoch": 4,
              "warmup_steps": 3, "follow_p": 0.5}

# what the next ``model_config`` PR brings, played here: a SECOND decoder
# configuration beside the one the benchmark has, of added files and
# entries alone. Another pattern than that one's cut (sliding, full,
# sliding, full; every layer sparse), no head gate, no shared expert,
# widths that are no power of two, and its keys as another family's
# ``config.json`` writes them: one ``num_attention_heads``, no list a layer
FULL, SLIDING = "full_attention", "sliding_attention"
DECODER_SIZES = {
    "hidden_size": 48, "head_dim": 16, "num_key_value_heads": 2,
    "layer_types": [SLIDING, FULL, SLIDING, FULL],
    "mlp_layer_types": ["sparse"] * 4, "sliding_window": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 0, "moe_routed_scaling_factor": 1.0,
    "rms_norm_eps": 1e-6, "gating": False,
    "rope_parameters": {
        FULL: {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 100,
                  "partial_rotary_factor": 1}},
}
DECODER_CONFIG = dict(
    DECODER_SIZES,
    name="tiny_decoder",
    source="test preset: a second decoder, sliding/full by turns, top-2 "
           "of 8 experts (4 held), no gate, no shared expert",
    sample="one sequence of 32 token ids",
    builder={"import": "mxnet_tpu.models:laguna", "kwargs": dict(
        DECODER_SIZES, seq_len=32, layers=4, vocab_rows=80, num_experts=8,
        experts_held=4, first_expert=0, train_router=False,
        num_attention_heads_per_layer=[6] * 4)},
    input_shape=[32], vocab_rows=80, vocab_size=80, per_chip_batch=2,
    num_hidden_layers=4, num_attention_heads=6, num_experts=4,
    first_expert=0, train_router=False,
    compute_dtype=None, optimizer={"name": "adam", "learning_rate": 0.002},
    initializer={"name": "Xavier"}, logits="head_output",
    reference="tiny_decoder.py", reference_rows=2, reference_tolerance=1e-4,
    reduced=[])
# its reference: the decoder's plain reference that is there, reading this
# configuration's file, one head count and no shared expert
DECODER_REFERENCE_EDITS = [
    ('"laguna_xs2.json"', '"tiny_decoder.json"'),
    ('cfg["num_attention_heads_per_layer"][l]', 'cfg["num_attention_heads"]'),
    ('out = gated_ffn(x, p[prefix + "shared_gate_weight"],\n'
     '                    p[prefix + "shared_up_weight"],\n'
     '                    p[prefix + "shared_down_weight"])',
     "out = jnp.zeros_like(x)")]
HIT_METRIC = '''
import os
import runpy

DECODER = runpy.run_path(os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "decoder_metrics.py"))

METRIC = {"name": "moe_experts_hit_pct", "unit": "%", "better": "higher",
          "source": "program_span",
          "layer": "graph to XLA (symbol.py, executor.py, ops/)",
          "moves": "samples_per_s_per_chip"}


def read(run):
    loads = DECODER["expert_load"](run)
    if not loads:
        return None
    return min(100.0 * a["experts_hit"] / a["experts_held"] for a in loads)
'''


def _copy_of_the_benchmark(root):
    """``benchmark/`` as it is under ``root``, and every byte of it."""
    here = root / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    return here, {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}


def _nothing_that_was_there_changed(before):
    for p, content in before.items():
        assert p.read_bytes() == content, p


@pytest.fixture(scope="module")
def overlay(tmp_path_factory, bench):
    """A checkout-shaped directory: ``benchmark/`` copied as it is, plus
    ADDED files and a ``BENCHMARK.json`` with added entries."""
    root = tmp_path_factory.mktemp("bench_overlay")
    here, before = _copy_of_the_benchmark(root)
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
    (here / "configs" / "tiny_tokens.json").write_text(
        json.dumps(TOKENS_CONFIG))
    (here / "configs" / "tiny_tokens_model.py").write_text(TOKENS_BUILDER)
    (here / "configs" / "tiny_tokens.py").write_text(TOKENS_REFERENCE)
    (here / "traffic" / "tiny_token_ring.json").write_text(
        json.dumps(TOKEN_RING))
    (here / "traffic" / "tiny_token_ring_dp.json").write_text(
        json.dumps(dict(TOKEN_RING, ring=2)))
    decoder = dict(DECODER_CONFIG)
    decoder["flops_per_sample"] = {"layers": walk.layers_of(
        catalog.build_symbol(decoder["builder"], str(here / "configs")),
        decoder)}
    (here / "configs" / "tiny_decoder.json").write_text(json.dumps(decoder))
    reference = (here / "configs" / "laguna_xs2.py").read_text()
    for was, becomes in DECODER_REFERENCE_EDITS:
        assert reference.count(was) == 1, was
        reference = reference.replace(was, becomes)
    (here / "configs" / "tiny_decoder.py").write_text(reference)
    (here / "traffic" / "tiny_decoder_ring.json").write_text(
        json.dumps(TOKEN_RING))
    (here / "layer_metrics" / "moe_experts_hit_pct.py").write_text(HIT_METRIC)
    added = dict(bench)
    added["configs"] = bench["configs"] + [
        {"name": "tiny_resnet", "source": TINY_CONFIG["source"],
         "file": "benchmark/configs/tiny_resnet.json", "reduced": [],
         "why": "test preset"},
        {"name": "tiny_tokens", "source": TOKENS_CONFIG["source"],
         "file": "benchmark/configs/tiny_tokens.json", "reduced": [],
         "why": "test preset"},
        {"name": "tiny_decoder", "source": DECODER_CONFIG["source"],
         "file": "benchmark/configs/tiny_decoder.json", "reduced": [],
         "why": "test preset"}]
    added["workloads"] = bench["workloads"] + [
        {"name": "tiny.device", "config": "tiny_resnet",
         "traffic": "tiny_ring", "chips": 1, "why": "test"},
        {"name": "tiny.dp4", "config": "tiny_resnet",
         "traffic": "tiny_ring_dp", "chips": 4, "why": "test"},
        {"name": "tiny_tokens.device", "config": "tiny_tokens",
         "traffic": "tiny_token_ring", "chips": 1, "why": "test"},
        {"name": "tiny_tokens.dp4", "config": "tiny_tokens",
         "traffic": "tiny_token_ring_dp", "chips": 4, "why": "test"},
        {"name": "tiny_decoder.device", "config": "tiny_decoder",
         "traffic": "tiny_decoder_ring", "chips": 1, "why": "test"}]
    # a cell's name appended to the lists that are there (the decoder
    # metrics' too: a cell that is no decoder's reads nothing there), the
    # new decoder cell's to the eight decoder lists, new metrics at the end
    added["per_layer"] = [
        dict(m, workloads=m["workloads"] + ["tiny.dp4", "tiny_tokens.dp4"]
             + ["tiny_decoder.device"] * (m["name"] in DECODER_METRICS))
        if "workloads" in m else m for m in bench["per_layer"]] + [
        dict(catalog.load_file_module(
            str(here / "layer_metrics" / (name + ".py")), name).METRIC,
             workloads=[cell])
        for name, cell in (("steps_in_window", "tiny.device"),
                           ("moe_experts_hit_pct", "tiny_decoder.device"))]
    (root / "BENCHMARK.json").write_text(json.dumps(added))
    yield root
    _nothing_that_was_there_changed(before)


def _second_blockdiff(root, bench):
    """What the next ``model_config`` PR does, as ISSUE 35 tried it by hand
    on a copy of the repository: a SIXTH configuration by files and entries
    alone, here a second block-diffusion decoder (``sdar_30b_a3b``'s file
    under another name with its reference), ONE one-chip cell on the mix
    that is there, the cell's name appended to the four ``moe_*`` lists
    and to none of the ``attention_*`` ones, one metric of its own. At its
    full size, so nothing runs it: the tests held on ``tree`` read it."""
    twin, cell = "blockdiff_twin", "blockdiff_twin.blockdiff4k"
    here, before = _copy_of_the_benchmark(root)
    (entry,) = [c for c in bench["configs"] if c["name"] == "sdar_30b_a3b"]
    config = catalog.read_json(os.path.join(ROOT, entry["file"]))
    reference = (here / "configs" / config["reference"]).read_text()
    assert reference.count('"sdar_30b_a3b.json"') == 1
    (here / "configs" / (twin + ".py")).write_text(
        reference.replace('"sdar_30b_a3b.json"', f'"{twin}.json"'))
    (here / "configs" / (twin + ".json")).write_text(json.dumps(
        dict(config, name=twin, reference=twin + ".py")))
    (here / "layer_metrics" / "moe_experts_hit_pct.py").write_text(HIT_METRIC)
    added = dict(bench)
    added["configs"] = bench["configs"] + [
        dict(entry, name=twin, file=f"benchmark/configs/{twin}.json")]
    added["workloads"] = bench["workloads"] + [
        {"name": cell, "config": twin,
         "traffic": "token_ring_blockdiff_4k", "chips": 1, "why": "test"}]
    added["per_layer"] = [
        dict(m, workloads=m["workloads"] + [cell])
        if m["name"] in MOE_METRICS else m for m in bench["per_layer"]] + [
        dict(catalog.load_file_module(
            str(here / "layer_metrics" / "moe_experts_hit_pct.py"),
            "moe_experts_hit_pct").METRIC, workloads=[cell])]
    (root / "BENCHMARK.json").write_text(json.dumps(added))
    return before


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


# -- a sequence model by files and entries alone ---------------------------------

@pytest.mark.parametrize("cell,trace", [
    ("tiny_tokens.device", 0), ("tiny_tokens.device", 1),
    ("tiny_tokens.dp4", 0), ("tiny_tokens.dp4", 1)])
def test_runner_on_a_token_configuration_made_of_added_files(overlay, cell,
                                                             trace):
    """Ids in, ``input_shape``, ``matmul`` layers, a ``token_ring`` mix:
    files and entries only, and no line of the runner names them."""
    proc = _run(overlay, "--workload", cell, "--seed", str(2 ** 31 + 26),
                "--seconds", "0.5", "--trace", str(trace),
                "--rehearse-on-cpu")
    result, earlier = _result(proc)
    assert result["correct"] is True, (earlier, proc.stderr[-2000:])
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["attempted"] % 4 == 0
    assert result["device"]["count"] == (4 if cell.endswith("dp4") else 1)
    # every number compared, beside its limit: last in the line and last
    # on standard error
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert all(set(pair) == {"value", "limit"} for pair in compared.values())
    assert compared["last_loss_over_warmup_loss"]["value"] < 1.0   # it fell
    assert compared["reference_relative_error"]["value"] < 0.1     # bf16
    assert compared["train_programs"] == {"value": 1, "limit": 1}
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    tail = proc.stderr.strip().splitlines()[-len(compared):]
    assert [ln.split()[2] for ln in tail] == list(compared), tail
    losses = [e for e in earlier if "epochs" in e][0]
    assert losses["warmup_loss"] > losses["epochs"][-1]["loss"]
    # untrained, the loss is ln 64 = 4.16; the chain is learnable
    assert losses["warmup_loss"] == pytest.approx(4.16, abs=0.15)
    got = set(result["metrics"])
    if trace == 0:
        assert got == {"samples_per_s_per_chip", "setup_s"}
    else:
        assert {"epoch_rate_median", "precompile_s", "compiles_in_window",
                "write_back_ms", "host_step_ms_p10"} <= got
        # a CPU trace has no device plane: nothing to read is nothing
        assert not got & {"forward_ms_per_step", "backward_ms_per_step",
                          "optimizer_unfused_ms_per_step",
                          "unscoped_ms_per_step",
                          "device_step_ms", "mfu_device"}
        # ... but the train program's text was read, after the window
        checks = [e for e in earlier if "checks" in e][0]["checks"]
        assert checks["hlo_instructions"] > 50 and checks["hlo_text_s"] < 5
        assert checks["hlo_text_not_read"] is None


# -- a second decoder by files and entries alone ---------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_runner_on_a_second_decoder_made_of_added_files(overlay, bench,
                                                        trace):
    """What the next ``model_config`` PR does: a decoder configuration
    beside the one that is there, its reference, a cell on a ``token_ring``
    mix, the cell's name appended to the eight decoder metrics' lists and
    one metric of its own. No file that was there is edited (the fixture
    checks every byte), and the cell runs to ``correct``."""
    later = catalog.load_benchmark(str(overlay))
    listed = {m["name"] for m in catalog.metrics_for(
        later, "per_layer", "tiny_decoder.device")}
    assert set(DECODER_METRICS) | {"moe_experts_hit_pct"} <= listed
    assert "steps_in_window" not in listed
    # a cell that was there reports what it reported, under the same names
    for w in bench["workloads"]:
        assert [m["name"] for m in catalog.metrics_for(
            later, "per_layer", w["name"])] == [m["name"] for m in
            catalog.metrics_for(bench, "per_layer", w["name"])], w["name"]
    proc = _run(overlay, "--workload", "tiny_decoder.device", "--seed",
                str(2 ** 31 + 31), "--seconds", "0.5", "--trace", str(trace),
                "--rehearse-on-cpu", devices=1)
    result, earlier = _result(proc)
    assert result["correct"] is True, (earlier, proc.stderr[-2000:])
    assert result["failed"] == 0 and result["attempted"] >= 4
    compared = result["compared"]
    assert compared["reference_relative_error"]["value"] < 1e-4   # float32
    assert compared["last_loss_over_warmup_loss"]["value"] < 1.0
    assert compared["train_programs"] == {"value": 1, "limit": 1}
    got = set(result["metrics"])
    if trace == 0:
        assert got == {"samples_per_s_per_chip", "setup_s"}
        return
    # the program's records are read on the CPU as on the chip; a CPU trace
    # has no device plane, so the device-trace readers read nothing
    assert {"moe_load_max_over_mean", "moe_picks_held_per_token",
            "moe_experts_hit_pct", "write_back_ms"} <= got
    assert not got & {"attention_window_ms_per_step", "moe_ms_per_step",
                      "attention_full_roofline_pct",
                      "moe_grouped_roofline_pct", "steps_in_window"}
    picks = result["metrics"]["moe_picks_held_per_token"]["value"]
    assert 0.2 < picks < 1.8          # 2 picks x 4 of 8 held: 1 expected
    assert 0 < result["metrics"]["moe_experts_hit_pct"]["value"] <= 100


TOKENS_SCRIPT = '''
import os, sys
import numpy as np
bench = sys.argv[1]
sys.path.insert(0, bench)
import catalog, flops, scopes, walk
import jax
import mxnet_tpu as mx

config = catalog.read_json(os.path.join(bench, "configs", "tiny_tokens.json"))
symbol = catalog.build_symbol(config["builder"],
                              os.path.join(bench, "configs"))
# the walk, by the operators' walkers, gives the file's list
assert walk.layers_of(symbol, config) == config["flops_per_sample"]["layers"]
assert flops.train_flops_per_sample(
    config["flops_per_sample"]["layers"]) == 3 * 2 * 8 * (16 * 32 + 32 * 64)
# the feeder: seeded, ids and next-token labels, int32, in range
traffic = catalog.read_json(os.path.join(bench, "traffic",
                                         "tiny_token_ring.json"))
feeder = catalog.load_feeder(traffic["kind"])
feed = feeder.make(traffic, config, jax.devices()[:1], 2 ** 31 + 5, "data",
                   "softmax_label")
again = feeder.make(traffic, config, jax.devices()[:1], 2 ** 31 + 5, "data",
                    "softmax_label")
other = feeder.make(traffic, config, jax.devices()[:1], 7, "data",
                    "softmax_label")
assert feed.batch_rows == 8 and feed.steps_per_epoch == 4
ring = [(np.asarray(x), np.asarray(y)) for x, y in feed.iter.ring]
assert len(ring) == 3
for (x, y), (x2, y2) in zip(ring, again.iter.ring):
    assert x.dtype == y.dtype == np.int32 and x.shape == y.shape == (8, 8)
    assert x.min() >= 0 and x.max() < 64 and y.min() >= 0 and y.max() < 64
    assert (x[:, 1:] == y[:, :-1]).all()          # the label is the next id
    assert (x == np.asarray(x2)).all() and (y == np.asarray(y2)).all()
assert not (ring[0][0] == ring[1][0]).all()       # distinct batches
assert not (ring[0][0] == np.asarray(other.iter.ring[0][0])).all()
assert (feed.check_rows(5) == ring[0][0][:5]).all()
assert feed.iter.provide_data == [("data", (8, 8))]
assert feed.iter.provide_label == [("softmax_label", (8, 8))]
# about half the steps follow the seeded successor: one successor an id
pairs = np.concatenate([np.stack([x.ravel(), y.ravel()], 1) for x, y in ring])
best = {}
for a, b in pairs:
    best.setdefault(int(a), []).append(int(b))
followed = sum(max(np.bincount(v)) for v in best.values()) / len(pairs)
assert 0.35 < followed < 0.75, followed
# float32 on the CPU, through the comparison that decides ``correct``: the
# system and the plain reference agree to rounding over ALL 64 positions
# (8 sequences of 8), and one wrong position is seen wherever it is
import checks
mx.random.seed(0)
model = mx.FeedForward(symbol, ctx=mx.cpu(), initializer=mx.init.Xavier())
ids = feed.check_rows(8)
model._init_params({"data": ids.shape, "softmax_label": ids.shape})
config_path = os.path.join(bench, "configs", "tiny_tokens.json")
device = jax.devices()[0]
err = checks.reference_error(mx, model, symbol, config, config_path, ids,
                             device, None)
assert err < 1e-5, err
reference = open(os.path.join(bench, "configs", config["reference"])).read()
wrong_dir = sys.argv[2]
for position in (0, 7, 8, 37, 63):        # 63: last of the last sequence
    with open(os.path.join(wrong_dir, f"wrong_{position}.py"), "w") as f:
        f.write(reference.replace(
            "return h @", f"return jnp.zeros((64, 64)).at[{position}]"
            ".set(1.0) + h @"))
    planted = checks.reference_error(
        mx, model, symbol, dict(config, reference=f"wrong_{position}.py"),
        os.path.join(wrong_dir, "x.json"), ids, device, None)
    assert planted > 0.05, (position, planted)
# fewer rows than were sent is a mismatch, not a shorter comparison
short = checks.reference_error(mx, model, symbol, config, config_path,
                               ids[:4], device, None)
assert short < 1e-5, short                # 4 sequences: 32 positions, all
with open(os.path.join(wrong_dir, "half.py"), "w") as f:
    f.write(reference.replace("return h @", "return (h @")
            .rstrip() + ")[:32]")
assert checks.reference_error(
    mx, model, symbol, dict(config, reference="half.py"),
    os.path.join(wrong_dir, "x.json"), ids, device, None) == float("inf")
print("relative error", err, "positions", 64)
'''


def test_token_preset_walk_feeder_and_float32_reference(overlay, tmp_path):
    proc = _spawn([sys.executable, "-c", TOKENS_SCRIPT,
                   str(overlay / "benchmark"), str(tmp_path)],
                  cwd=str(overlay), devices=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "relative error" in proc.stdout


def test_no_line_of_the_harness_names_the_token_preset(tree):
    here = os.path.join(tree.root, "benchmark")
    for name in ("run.py", "checks.py", "catalog.py", "walk.py", "scopes.py",
                 "flops.py", "trace_reduce.py",
                 os.path.join("feeds", "token_ring.py")):
        with open(os.path.join(here, name), encoding="utf-8") as f:
            text = f.read()
        assert "tiny_tokens" not in text and "tiny_resnet" not in text, name
    # nor a configuration of the benchmark: only ``configs/`` and the tests
    # name one, so a reader serves whichever cell lists it
    names = [c["name"] for c in tree.bench["configs"]]
    for folder in ("", "layer_metrics", "end_to_end", "feeds", "walkers"):
        for name in sorted(os.listdir(os.path.join(here, folder))):
            if name.endswith(".py"):
                with open(os.path.join(here, folder, name),
                          encoding="utf-8") as f:
                    text = f.read()
                assert not [n for n in names + ["laguna"] if n in text], name


# -- the tests themselves: held on the tree a later PR makes ------------------------

# test functions that read the repository's ``BENCHMARK.json`` alone, and why
# each may: everything else that reads its ``configs``, ``workloads`` or
# ``per_layer`` takes ``tree``
READS_THE_REPOSITORY_ALONE = {
    ("test_benchmark_harness.py",
     "test_layer_lists_match_the_models_the_builders_make"):
        "walks the model of every configuration the repository has; "
        "``overlay``'s ``tiny_resnet`` states a one-layer recipe that is not "
        "its model's, and a later PR's configuration is walked in that PR, "
        "where it is the repository's",
    ("test_benchmark_harness.py",
     "test_runner_on_a_second_decoder_made_of_added_files"):
        "holds BOTH trees against each other: a cell that was there reports "
        "in the later tree what it reports in the repository's",
    ("test_span_metrics.py", "test_reader_reads_the_programs_records"):
        "finds each of its seven entries by name and compares it with its "
        "reader's ``METRIC``: PR 24's file, as ISSUE 35 leaves it",
}
LISTS = {"configs", "workloads", "per_layer"}
LIST_READERS = {"metrics_for", "find_cell"}


def _unheld_readers(folder):
    """``(file, function)`` of every test function in ``folder``'s
    ``test_*.py`` that has the repository's ``BENCHMARK.json`` in hand (a
    ``bench`` parameter, a ``load_benchmark`` call or the file's name) and
    reads its ``configs``, ``workloads`` or ``per_layer``: what a pin on a
    list's end or length is written with, and what only a test held on
    ``tree`` may do, because the later trees append after every end.
    Fixtures and helpers are not meant: they assert nothing."""
    found = []
    for name in sorted(os.listdir(folder)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        with open(os.path.join(folder, name), encoding="utf-8") as f:
            module = ast.parse(f.read(), name)
        for node in ast.walk(module):
            if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name.startswith("test_")):
                continue
            params = {a.arg for a in node.args.posonlyargs + node.args.args
                      + node.args.kwonlyargs}
            inside = list(ast.walk(node))
            words = {n.value for n in inside if isinstance(n, ast.Constant)
                     and isinstance(n.value, str)}
            called = {n.func.attr if isinstance(n.func, ast.Attribute)
                      else getattr(n.func, "id", None)
                      for n in inside if isinstance(n, ast.Call)}
            in_hand = "bench" in params or "load_benchmark" in called \
                or "BENCHMARK.json" in words
            if in_hand and (words & LISTS or called & LIST_READERS):
                found.append((name, node.name))
    return sorted(found)


def test_every_test_that_reads_the_benchmarks_lists_is_held_on_tree():
    """PR 33's entry test pinned the LAST configuration, cell and metrics
    through the plain ``bench`` fixture, and nothing saw it until a sixth
    configuration was tried (ISSUE 35). A test file a later PR adds in that
    style fails here, in that PR, by file and function."""
    readers = _unheld_readers(os.path.dirname(os.path.abspath(__file__)))
    unheld = [r for r in readers if r not in READS_THE_REPOSITORY_ALONE]
    assert not unheld, (
        f"{unheld}: each reads configs, workloads or per_layer of the "
        "repository's BENCHMARK.json without `tree`. Take `tree` from "
        "test_benchmark_harness.py (`overlay, tree = harness.overlay, "
        "harness.tree`), read `tree.bench` and `tree.root`, find entries by "
        "name and assert prefixes of lists, never their ends or lengths")
    assert set(READS_THE_REPOSITORY_ALONE) <= set(readers)  # no stale excuse


def test_the_guard_names_a_test_written_in_the_old_style(tmp_path):
    (tmp_path / "test_scratch_config.py").write_text('''
import pytest


@pytest.fixture(scope="module")
def tiny_checkout(bench):                      # a fixture: not meant
    return [w["name"] for w in bench["workloads"]]


def _cells(bench):                             # a helper: not meant
    return bench["workloads"]


def test_entries_of_the_benchmark(bench, config):
    assert bench["workloads"][-1]["config"] == config["name"]


class TestReaders:
    def test_listed(self, bench):
        assert catalog.metrics_for(bench, "end_to_end", "a.cell")


def test_loaded_by_hand():
    assert len(catalog.load_benchmark()["per_layer"]) == 34


def test_held(tree, config):
    assert tree.bench["workloads"][0]["chips"] == 1


def test_reads_no_list(bench):
    assert bench["run_seconds"] == 24
''')
    (tmp_path / "helpers.py").write_text(
        'def test_not_a_test_file(bench):\n    bench["configs"]\n')
    assert _unheld_readers(str(tmp_path)) == [
        ("test_scratch_config.py", "test_entries_of_the_benchmark"),
        ("test_scratch_config.py", "test_listed"),
        ("test_scratch_config.py", "test_loaded_by_hand")]
