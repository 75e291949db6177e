"""The configuration ``laguna_xs2`` and the cell ``laguna_xs2.seq8k``: the
file against the public config's values (pinned here), the FLOP recipe
against the walk of the built model, the kernels' cost functions, the
readers of the eight per-layer metrics on hand-written runs, and the
runner on the cell at a tiny preset on the CPU.
"""

import importlib.util
import inspect
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import catalog  # noqa: E402
import flops  # noqa: E402
import walk  # noqa: E402

FULL, SLIDING = "full_attention", "sliding_attention"
# https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json, as the
# catalog row has it: every number a builder needs
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 10,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
CUT = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544}


@pytest.fixture(scope="module")
def bench():
    return catalog.load_benchmark()


@pytest.fixture(scope="module")
def config():
    return catalog.read_json(os.path.join(BENCH, "configs",
                                          "laguna_xs2.json"))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the harness's own test file, for the tree a later PR makes of this one by
# additions (``overlay``), the trees the contract is held on (``tree``) and
# the record of what the benchmark was accepted with
harness = _load(os.path.join(HERE, "test_benchmark_harness.py"),
                "bench_harness_tests_of_laguna")
overlay, tree = harness.overlay, harness.tree
NEW_METRICS = harness.DECODER_METRICS       # the eight this cell brought


def test_the_file_holds_the_published_values(config):
    """Every key of the public config under its own name and unchanged,
    but the three that the cut names, each with the published count and
    the deployment beside it."""
    for key, value in PUBLISHED.items():
        assert config[key] == CUT.get(key, value), key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "epoch_samples"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 100352
    assert "eight chips share each layer" in config["deployment"]
    for form in ("gate", "router", "absent", "train_router"):
        assert len(config["assumed"][form]) > 80
    kwargs = config["builder"]["kwargs"]
    assert config["builder"]["import"] == "mxnet_tpu.models:laguna"
    assert kwargs == {"seq_len": 8192, "layers": 5, "vocab_rows": 12544,
                      "experts_held": 32, "first_expert": 0,
                      "train_router": False}
    assert config["input_shape"] == [8192] and config["vocab_rows"] == 12544
    assert config["per_chip_batch"] == 1 and config["reference_rows"] == 1
    assert config["compute_dtype"] == "bfloat16"
    assert config["optimizer"]["name"] == "adam"
    assert config["logits"] == "head_output"
    assert len(config["reference_tolerance_why"]) > 80


def test_the_builder_defaults_are_the_published_sizes():
    """``laguna()`` with no argument is the published model: the cut's
    arguments are the only ones the configuration passes."""
    from mxnet_tpu.models import laguna
    from mxnet_tpu.models.laguna import ROPE_PARAMETERS

    defaults = {k: p.default
                for k, p in inspect.signature(laguna).parameters.items()}
    assert defaults["layers"] == 40 and defaults["vocab_rows"] == 100352
    assert defaults["experts_held"] == defaults["num_experts"] == 256
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_key_value_heads", "sliding_window",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size",
                "moe_routed_scaling_factor", "rms_norm_eps", "gating"):
        assert defaults[key] == PUBLISHED[key], key
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert list(defaults[key]) == PUBLISHED[key], key
    for kind in (FULL, SLIDING):
        assert ROPE_PARAMETERS[kind] == PUBLISHED["rope_parameters"][kind]


def test_entries_of_the_benchmark(tree, config):
    """Laguna's own entries, each found by its NAME: in the repository's
    tree, and in the trees a later PR makes by appending cells, metrics and
    names to the lists (the accepted record is the harness's to hold)."""
    bench = tree.bench
    (entry,) = [c for c in bench["configs"] if c["name"] == "laguna_xs2"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert entry["file"] == "benchmark/configs/laguna_xs2.json"
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "laguna_xs2.seq8k"]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    traffic = catalog.read_json(os.path.join(
        tree.root, "benchmark", "traffic", cell["traffic"] + ".json"))
    assert {k: traffic[k] for k in ("kind", "ring", "steps_per_epoch",
                                    "warmup_steps", "follow_p")} == {
        "kind": "token_ring", "ring": 8, "steps_per_epoch": 64,
        "warmup_steps": 16, "follow_p": 0.5}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = metrics[name]
        assert m["workloads"][0] == "laguna_xs2.seq8k"
        assert m["moves"] == "samples_per_s_per_chip"
        assert m["layer"] == "graph to XLA (symbol.py, executor.py, ops/)"
    # the cell reports its 29 metrics, and none that a later cell brought
    reported = [m["name"] for m in catalog.metrics_for(
        bench, "per_layer", "laguna_xs2.seq8k")]
    assert reported == [n for n in harness.COMMON_METRICS + NEW_METRICS
                        if not n.startswith("collective_")]
    assert len(reported) == 21 + 8


def test_the_walk_gives_the_files_recipe_and_the_issues_count(config):
    symbol = catalog.build_symbol(config["builder"],
                                  os.path.join(BENCH, "configs"))
    layers = walk.layers_of(symbol, config)
    assert layers == config["flops_per_sample"]["layers"]
    forward = flops.forward_flops_per_sample(layers)
    tokens = config["input_shape"][0]
    assert forward / 2 / tokens == pytest.approx(400.9e6, rel=1e-4)
    assert flops.train_flops_per_sample(layers) == pytest.approx(
        19.70e12, rel=1e-3)
    by_op = {}
    for layer in layers:
        by_op.setdefault(layer["op"], []).append(layer)
    assert len(by_op["attention"]) == 5
    assert [a["kv_mean"] for a in by_op["attention"]] == [
        4096.5, 496.03125, 496.03125, 496.03125, 4096.5]
    assert [a["heads"] for a in by_op["attention"]] == [48, 64, 64, 64, 48]
    routed = [m for m in by_op["matmul"]
              if m["name"].startswith("layer1_moe_") and "shared" not in
              m["name"] and "router" not in m["name"]]
    # the EXPECTED rows: 8,192 x 8 x 32 / 256
    assert [m["rows"] for m in routed] == [8192.0] * 3
    # what the cut holds: 691.6 M parameters, about 70 leaves
    args, _, aux = symbol.infer_shape(data=(1, tokens),
                                      softmax_label=(1, tokens))
    sizes = [s for n, s in zip(symbol.list_arguments(), args)
             if n not in ("data", "softmax_label")]
    count = 0
    for shape in sizes:
        n = 1
        for d in shape:
            n *= d
        count += n
    assert count == 691_623_936 and len(sizes) == 69
    assert aux == [(256,)] * 4


def test_the_reference_is_independent_of_the_program():
    text = open(os.path.join(BENCH, "configs", "laguna_xs2.py")).read()
    assert "mxnet_tpu" not in text.replace("``mxnet_tpu``", "")
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["import jax", "import jax.numpy as jnp",
                               "import json", "import numpy as np",
                               "import os"]
    assert text.count("DEPARTURE") >= 5


def test_kernel_cost_functions_count_as_flops_py_counts():
    costs = _load(os.path.join(BENCH, "kernel_costs.py"), "kernel_costs")
    assert costs.kv_mean(8192, 0) == 4096.5
    assert costs.kv_mean(8192, 512) == 496.03125
    forward, backward = costs.flash_attention(64, 8, 8192, 128, 128, 512)
    entry = {"op": "attention", "heads": 64, "qk_dim": 128, "v_dim": 128,
             "q_len": 8192, "kv_mean": 496.03125}
    assert forward["flops"] == flops.layer_forward_flops(entry)
    assert backward["flops"] == 2 * forward["flops"]
    q, kv = 64 * 8192 * 128 * 2, 8 * 8192 * 128 * 2
    assert forward["bytes"] == 2 * q + 2 * kv + 64 * 8192 * 4
    # a windowed kernel is credited with a sixteenth of the dense products
    dense = costs.flash_attention(64, 8, 8192, 128, 128, 0)[0]["flops"]
    assert 8.2 < dense / forward["flops"] < 8.3
    product = costs.grouped_product(8192, 2048, 512, 32)
    assert product["flops"] == 2 * 8192 * 2048 * 512
    assert product["bytes"] == 8192 * 2048 * 2 + 32 * 2048 * 512 * 2 \
        + 8192 * 512 * 4
    fwd, bwd = costs.gated_experts(8192, 2048, 512, 32)
    assert len(fwd) == 3 and len(bwd) == 6
    assert sum(c["flops"] for c in bwd) == 2 * sum(c["flops"] for c in fwd)
    peak = catalog.peak_for("TPU v5 lite")
    # 256 rows an expert: the weights' bytes bound the product
    assert costs.roofline_seconds(product, peak) == \
        product["bytes"] / 819e9 > product["flops"] / 197e12


def _records(loads):
    """Span records as ``fit`` leaves them: a callback span around each of
    the runner's epoch stamps, and the loads of the epoch it ends."""
    records = []
    for epoch in range(3):
        records.append({"name": "fit.epoch.callback", "thread": "main",
                        "start": 10.0 * epoch + 9.0,
                        "end": 10.0 * epoch + 9.9, "epoch": epoch,
                        "attrs": {"epoch": epoch}})
        for node, attrs in loads.get(epoch, {}).items():
            records.append({"name": "fit.epoch.expert_load",
                            "thread": "main", "start": 10.0 * epoch + 8.5,
                            "end": 10.0 * epoch + 8.5, "epoch": epoch,
                            "attrs": dict(attrs, node=node, epoch=epoch)})
    return records


def _hand_written_run(config, per_chip_batch):
    """A traced run as ``run.py`` hands it to the readers, written by
    hand: flash kernels and other instructions under the attention scopes
    of layers 0, 1, 2 and 10, an expert layer's gather, a grouped product
    and a projection, three epoch stamps."""
    fwd = "jit(step)/jvp(layer{}_attn/RotaryAttention)/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/layer{}_attn/" \
          "RotaryAttention/"
    scopes = {
        "flash_fwd.1": fwd.format(1) + "flash_fwd/pallas_call",
        "flash_bwd_dq.1": bwd.format(1) + "flash_bwd_dq/pallas_call",
        "fusion.1": fwd.format(2) + "mul",
        "flash_fwd.0": fwd.format(0) + "flash_fwd/pallas_call",
        "flash_fwd.10": fwd.format(10) + "flash_fwd/pallas_call",
        "fusion.2": "jit(step)/jvp(layer1_moe/MixtureOfExperts)/gather",
        "ragged-dot-none.3": "ragged-dot-none",
        "fusion.3": "jit(step)/jvp(layer1_q/FullyConnected)/dot_general",
    }
    seconds = {"flash_fwd.1": 0.128, "flash_bwd_dq.1": 0.256,
               "fusion.1": 0.064, "flash_fwd.0": 0.640,
               "flash_fwd.10": 9.0, "fusion.2": 0.192,
               "ragged-dot-none.3": 0.064, "fusion.3": 5.0}
    return {"trace": {"program_op_seconds": seconds, "op_seconds": seconds,
                      "span_steps": 64},
            "hlo_scopes": scopes, "peak": catalog.peak_for("TPU v5 lite"),
            "config": config, "per_chip_batch": per_chip_batch,
            "steps_per_epoch": 64, "traced_epochs": [0, 1],
            "rows": [{"entry": 9.1, "exit": 9.2}, {"entry": 19.1,
                                                   "exit": 19.2},
                     {"entry": 29.1, "exit": 29.2}]}


def _two_epochs(balanced, skewed):
    """Two traced epochs of two expert nodes, one node of the first
    skewed."""
    return {1: {"layer1_moe": balanced, "layer2_moe": skewed},
            2: {"layer1_moe": balanced, "layer2_moe": balanced}}


LAGUNA_BALANCED = {"tokens": 64 * 8192.0, "picks_held": 64 * 8192.0,
                   "picks_all": 64 * 65536.0, "max_held": 64 * 320.0,
                   "experts_hit": 32, "experts_held": 32}
LAGUNA_LOADS = _two_epochs(LAGUNA_BALANCED, dict(
    LAGUNA_BALANCED, picks_held=64 * 4096.0, max_held=64 * 2048.0,
    experts_hit=2))


def test_readers_of_the_new_metrics_on_a_hand_written_run(config):
    readers = _load(os.path.join(BENCH, "decoder_metrics.py"),
                    "decoder_metrics")
    run = _hand_written_run(config, 1)
    peak = run["peak"]
    # layers 1-3 are the sliding ones, 0 and 4 the full ones; layer 10 is
    # no layer of this cut
    assert readers.attention_ms(run, SLIDING) == \
        pytest.approx(1e3 * (0.128 + 0.256 + 0.064) / 64)
    assert readers.attention_ms(run, FULL) == pytest.approx(10.0)
    assert readers.moe_ms(run) == pytest.approx(1e3 * 0.256 / 64)
    costs = readers.COSTS
    least = 3 * sum(costs["roofline_seconds"](c, peak) for c in
                    costs["flash_attention"](64, 8, 8192, 128, 128, 512))
    assert readers.attention_roofline_pct(run, SLIDING) == \
        pytest.approx(100 * least / ((0.128 + 0.256) / 64))

    readers.SPANS["program_records"] = lambda: (_records(LAGUNA_LOADS), 0)
    assert readers.moe_picks_held_per_token(run) == pytest.approx(
        (3 * 8192 + 4096) / (4 * 8192))
    assert readers.moe_load_max_over_mean(run) == pytest.approx(
        2048 * 32 / 4096)
    # the grouped products at the rows the traced steps really routed and
    # the weights of the experts that had a pick (the record's count): all
    # 32 where the load is balanced, two at the skewed node
    def least_of(rows, experts):
        return sum(costs["roofline_seconds"](c, peak) for part in
                   costs["gated_experts"](rows, 2048, 512, experts)
                   for c in part)

    want = (3 * least_of(8192, 32) + least_of(4096, 2)) / 2
    assert readers.moe_grouped_roofline_pct(run) == \
        pytest.approx(100 * want / (0.064 / 64))
    # nothing to read is nothing, never 0
    readers.SPANS["program_records"] = lambda: None
    assert readers.moe_picks_held_per_token(run) is None
    assert readers.moe_grouped_roofline_pct(run) is None
    empty = dict(run, trace=None)
    assert readers.attention_ms(empty, FULL) is None
    assert readers.moe_ms(dict(run, hlo_scopes=None)) is None
    for name in NEW_METRICS:
        module = catalog.load_metric("layer_metrics", name)
        assert module.read(empty) is None, name
    # keys a published config.json may lack, read as its family writes
    # them: no list of layer types is full attention in every layer, a
    # null window is none, one head count stands for every layer
    plain = {k: v for k, v in config.items() if k != "layer_types"}
    assert readers.attention_layers(plain, FULL) == [0, 1, 2, 3, 4]
    assert readers.attention_ms(dict(run, config=plain), SLIDING) is None
    assert readers.attention_ms(dict(run, config=plain), FULL) == \
        pytest.approx(1e3 * (0.128 + 0.256 + 0.064 + 0.640) / 64)
    one_count = {k: v for k, v in config.items()
                 if k != "num_attention_heads_per_layer"}
    least = 3 * sum(costs["roofline_seconds"](c, peak) for c in
                    costs["flash_attention"](48, 8, 8192, 128, 128, 512))
    assert readers.attention_roofline_pct(
        dict(run, config=one_count), SLIDING) == \
        pytest.approx(100 * least / ((0.128 + 0.256) / 64))
    least = 3 * sum(costs["roofline_seconds"](c, peak) for c in
                    costs["flash_attention"](64, 8, 8192, 128, 128, 0))
    assert readers.attention_roofline_pct(
        dict(run, config=dict(config, sliding_window=None)), SLIDING) == \
        pytest.approx(100 * least / ((0.128 + 0.256) / 64))


# what the eight metric FILES must give on the run above, read in a cell
# of each configuration of the later PR's tree. ``laguna_xs2``: the numbers
# of the test above. ``tiny_decoder``: layers 0 and 2 are the sliding ones,
# 1 and 3 the full ones (Laguna's pattern would read 7 and 10 ms), 6 heads
# over 2 of 16, 32 positions, a window of 4, 2 sequences a step, experts
# of 48 x 24, 4 held
LATER_CELLS = {
    "laguna_xs2": {
        "batch": 1, "loads": LAGUNA_LOADS, "experts": (2048, 512),
        "attention_window_ms_per_step": 1e3 * (0.128 + 0.256 + 0.064) / 64,
        "attention_full_ms_per_step": 10.0,
        "window": (3, (64, 8, 8192, 128, 128, 512), 0.128 + 0.256),
        "full": (2, (48, 8, 8192, 128, 128, 0), 0.640),
        "grouped": ((8192, 32), (4096, 2)),
        "moe_load_max_over_mean": 2048 * 32 / 4096,
        "moe_picks_held_per_token": (3 * 8192 + 4096) / (4 * 8192)},
    "tiny_decoder": {
        "batch": 2, "experts": (48, 24),
        "loads": _two_epochs(
            {"tokens": 64 * 64.0, "picks_held": 64 * 64.0,
             "picks_all": 64 * 128.0, "max_held": 64 * 20.0,
             "experts_hit": 4, "experts_held": 4},
            {"tokens": 64 * 64.0, "picks_held": 64 * 32.0,
             "picks_all": 64 * 128.0, "max_held": 64 * 16.0,
             "experts_hit": 2, "experts_held": 4}),
        "attention_window_ms_per_step": 1e3 * (0.640 + 0.064) / 64,
        "attention_full_ms_per_step": 1e3 * (0.128 + 0.256) / 64,
        "window": (2, (6, 2, 32, 16, 16, 4), 0.640),
        "full": (2, (6, 2, 32, 16, 16, 0), 0.128 + 0.256),
        "grouped": ((64, 4), (32, 2)),
        "moe_load_max_over_mean": 16 * 4 / 32,
        "moe_picks_held_per_token": (3 * 64 + 32) / (4 * 64)},
}


@pytest.mark.parametrize("name", sorted(LATER_CELLS))
def test_the_metric_files_price_the_cell_they_are_read_in(name, overlay):
    """In the tree a later PR makes, the eight metric files are listed for
    two decoder cells under one name each, and each reads the sizes of
    the cell it is read in from ``run["config"]``: given another cell's
    configuration this test fails."""
    here = str(overlay / "benchmark")
    case = LATER_CELLS[name]
    cfg = catalog.read_json(os.path.join(here, "configs", name + ".json"))
    run = _hand_written_run(cfg, case["batch"])
    costs = _load(os.path.join(here, "kernel_costs.py"), "kernel_costs")
    peak = run["peak"]

    def attention_pct(layers, flash, kernels_s):
        least = layers * sum(costs.roofline_seconds(c, peak)
                             for c in costs.flash_attention(*flash))
        return 100 * least * case["batch"] / (kernels_s / 64)

    def least_of(rows, experts):
        return sum(costs.roofline_seconds(c, peak) for part in
                   costs.gated_experts(rows, *case["experts"], experts)
                   for c in part)

    balanced, skewed = case["grouped"]
    want = dict(
        {k: case[k] for k in NEW_METRICS if k in case},
        attention_window_roofline_pct=attention_pct(*case["window"]),
        attention_full_roofline_pct=attention_pct(*case["full"]),
        moe_ms_per_step=1e3 * 0.256 / 64,
        moe_grouped_roofline_pct=100 * (
            3 * least_of(*balanced) + least_of(*skewed)) / 2 / (0.064 / 64))
    assert sorted(want) == sorted(NEW_METRICS)
    convnet = dict(run, config={"image": [224, 224, 3],
                                "per_chip_batch": 256})
    got = {}
    for metric in NEW_METRICS:
        module = catalog.load_metric("layer_metrics", metric, here=here)
        assert "workloads" not in module.METRIC
        spans = module.DECODER["SPANS"]
        spans["program_records"] = lambda: (_records(case["loads"]), 0)
        got[metric] = module.read(run)
        # a cell whose configuration is no decoder's: nothing, never 0
        # (the three that read no size read the run's records and scopes)
        if metric not in ("moe_ms_per_step", "moe_load_max_over_mean",
                          "moe_picks_held_per_token"):
            assert module.read(convnet) is None, metric
        spans["program_records"] = lambda: None
        assert module.read(dict(run, trace=None)) is None, metric
    assert got == pytest.approx(want)
    # the two cells' numbers differ wherever a size is read
    other = LATER_CELLS[sorted(set(LATER_CELLS) - {name})[0]]
    for metric in ("attention_window_ms_per_step",
                   "attention_full_ms_per_step", "moe_load_max_over_mean"):
        assert want[metric] != pytest.approx(other[metric]), metric


# -- the runner on the cell, at a tiny preset on the CPU -------------------------

TINY = {
    "input_shape": [32], "vocab_rows": 96, "vocab_size": 96,
    "per_chip_batch": 2, "num_hidden_layers": 4, "num_experts": 8,
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "num_key_value_heads": 2, "num_attention_heads_per_layer": [4, 6, 6, 4],
    "layer_types": [FULL, SLIDING, SLIDING, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "sliding_window": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "optimizer": {"name": "adam", "learning_rate": 0.002},
    # float32: at these sizes a bfloat16 run differs from the reference by
    # which expert a row takes (0.05-0.15 by the seed), not by arithmetic
    "compute_dtype": None, "reference_rows": 2, "reference_tolerance": 1e-4,
}


@pytest.fixture(scope="module")
def tiny_checkout(tmp_path_factory, bench, config):
    """A checkout-shaped directory: ``benchmark/`` as it is, the
    configuration's file with tiny sizes under its own name (the reference
    and the metric files read it by that name), a short traffic mix."""
    root = tmp_path_factory.mktemp("laguna_overlay")
    here = root / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    tiny = dict(config, **TINY)
    sizes = {k: TINY[k] for k in (
        "hidden_size", "intermediate_size", "head_dim",
        "num_key_value_heads", "num_attention_heads_per_layer",
        "layer_types", "mlp_layer_types", "sliding_window",
        "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size")}
    tiny["builder"] = {"import": "mxnet_tpu.models:laguna", "kwargs": dict(
        sizes, seq_len=32, layers=4, vocab_rows=96, experts_held=8,
        first_expert=0, num_experts=16, train_router=False)}
    from mxnet_tpu.models import laguna

    symbol = laguna(**{k: tuple(v) if isinstance(v, list) else v
                       for k, v in tiny["builder"]["kwargs"].items()})
    tiny["flops_per_sample"] = {"layers": walk.layers_of(symbol, tiny)}
    (here / "configs" / "laguna_xs2.json").write_text(json.dumps(tiny))
    (here / "traffic" / "token_ring_8k.json").write_text(json.dumps(
        {"kind": "token_ring", "ring": 3, "steps_per_epoch": 4,
         "warmup_steps": 3, "follow_p": 0.5}))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_runner_on_the_cell_at_a_tiny_preset(tiny_checkout, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "laguna_xs2.seq8k", "--seed", str(2 ** 31 + 27), "--seconds", "0.5",
         "--trace", str(trace), "--rehearse-on-cpu"],
        cwd=tiny_checkout, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True, (lines, proc.stderr[-2000:])
    assert result["failed"] == 0 and result["attempted"] >= 4
    compared = result["compared"]
    assert compared["reference_relative_error"]["value"] < 1e-4
    assert compared["last_loss_over_warmup_loss"]["value"] < 1.0
    assert compared["train_programs"] == {"value": 1, "limit": 1}
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    got = set(result["metrics"])
    if trace == 0:
        assert got == {"samples_per_s_per_chip", "setup_s"}
        return
    # the program's records are read on the CPU as on the chip; a CPU
    # trace has no device plane, so the device-trace readers read nothing
    assert {"moe_load_max_over_mean", "moe_picks_held_per_token",
            "write_back_ms", "compiles_in_window"} <= got
    assert not got & {"attention_window_ms_per_step", "moe_ms_per_step",
                      "attention_full_roofline_pct",
                      "moe_grouped_roofline_pct", "device_step_ms"}
    picks = result["metrics"]["moe_picks_held_per_token"]["value"]
    assert 0.5 < picks < 3.5          # 4 picks x 8 of 16 held: 2 expected
    assert 1.0 <= result["metrics"]["moe_load_max_over_mean"]["value"] <= 8
