"""The configuration ``sdar_30b_a3b`` and the cell
``sdar_30b_a3b.blockdiff4k``: the file against the public config's values
(pinned here), the FLOP recipe against the walk of the built model, the
attention kernels' cost function, the feeder's pairs, the readers of the
three new per-layer metrics on a hand-written run, the entries of
``BENCHMARK.json``, and the runner on the cell at a tiny preset on the CPU.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import catalog  # noqa: E402
import flops  # noqa: E402
import walk  # noqa: E402

CELL = "sdar_30b_a3b.blockdiff4k"
# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json, as
# the catalog row has it: every key of its ``config``
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
HEAD_NORMS_SHARP = "^layer[0-1]_[qk]_norm_gamma$"
CUT = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}


@pytest.fixture(scope="module")
def bench():
    return catalog.load_benchmark()


@pytest.fixture(scope="module")
def config():
    return catalog.read_json(os.path.join(BENCH, "configs",
                                          "sdar_30b_a3b.json"))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the harness's own test file, for the tree a later PR makes of this one by
# additions (``overlay``), the trees the contract is held on (``tree``) and
# the record of what the benchmark was accepted with
harness = _load(os.path.join(HERE, "test_benchmark_harness.py"),
                "bench_harness_tests_of_sdar")
overlay, tree = harness.overlay, harness.tree
MOE_METRICS = harness.MOE_METRICS           # the four lists this cell joined
NEW_METRICS = harness.BLOCKDIFF_METRICS     # the three this cell brought


def test_the_file_holds_the_published_values(config):
    """Every key of the public config under its own name and unchanged,
    but the three that the cut names, each with the published count and
    the deployment beside it; every assumption the config forces, named."""
    for key, value in PUBLISHED.items():
        assert key in config and config[key] == CUT.get(key, value), key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "epoch_samples"]
    assert {k: config["published"][k] for k in CUT} == {
        k: PUBLISHED[k] for k in CUT}
    assert "eight chips share each layer" in config["deployment"]
    assert "645.6 M parameters" in config["deployment"]
    for form in ("block_length", "schedule", "mask_id", "qk_norm", "router",
                 "train_router", "optimizer", "initializer",
                 "compute_dtype"):
        assert len(config["assumed"][form]) > 40, form
    assert config["builder"] == {
        "import": "mxnet_tpu.models:sdar",
        "kwargs": {"seq_len": 4096, "block_length": 4, "mask_id": 18991,
                   "layers": 6, "vocab_rows": 18992, "experts_held": 16,
                   "first_expert": 0, "train_router": False}}
    # the feeder and the reference read these beside the builder's
    assert (config["block_length"], config["mask_id"]) == (4, 18991)
    # the drawn weights: Xavier, but the per-head norm scales of the first
    # layers start at 3 (with the reason, and what it costs the comparison)
    assert config["initializer"] == {
        "name": "Mixed", "patterns": [HEAD_NORMS_SHARP, ".*"],
        "initializers": [3.0, {"name": "Xavier"}]}
    assert "attention_factor" not in json.dumps(config)
    assert "one token" in config["assumed"]["initializer"]
    assert config["vocab_rows"] == 151936 // 8 == config["mask_id"] + 1
    assert config["input_shape"] == [2 * 4096]
    assert config["per_chip_batch"] == 1 and config["reference_rows"] == 1
    assert config["compute_dtype"] == "bfloat16"
    assert config["optimizer"] == {"name": "adam", "learning_rate": 1e-05}
    assert config["logits"] == "head_output"
    assert config["reference"] == "sdar_30b_a3b.py"
    assert len(config["reference_tolerance_why"]) > 80
    assert 0 < config["reference_tolerance"] < 1


def test_the_walk_gives_the_files_recipe_and_the_issues_count(config):
    symbol = catalog.build_symbol(config["builder"],
                                  os.path.join(BENCH, "configs"))
    layers = walk.layers_of(symbol, config)
    assert layers == config["flops_per_sample"]["layers"]
    assert flops.train_flops_per_sample(layers) == pytest.approx(
        12.94e12, rel=1e-3)
    attention = [a for a in layers if a["op"] == "attention"]
    assert len(attention) == 6
    # 8,192 rows of queries, (T + B) / 2 keys each: not the causal 4,096.5
    assert {(a["q_len"], a["kv_mean"], a["heads"]) for a in attention} == {
        (8192, 2050.0, 32)}
    share = sum(map(flops.layer_forward_flops, attention)) \
        / flops.forward_flops_per_sample(layers)
    assert share == pytest.approx(0.383, abs=2e-3)
    by_name = {m["name"]: m for m in layers}
    # the head on the 4,096 noisy rows; the experts at the EXPECTED rows,
    # 8,192 x 8 x 16 / 128
    assert by_name["head"]["rows"] == 4096
    assert by_name["layer0_moe_gate"]["rows"] == 8192.0
    assert by_name["layer0_moe_router"]["cout"] == 128
    # what the cut holds: 645.6 M parameters
    args, _, aux = symbol.infer_shape(data=(1, 8192))
    count = sum(int(np.prod(s)) for n, s in zip(symbol.list_arguments(), args)
                if n not in ("data", "softmax_label"))
    assert count == 645_623_296
    assert count * 16 / 1e9 == pytest.approx(10.33, abs=5e-3)
    assert aux == [(128,)] * 6 + [(4,)]


def test_the_reference_is_independent_of_the_program():
    text = open(os.path.join(BENCH, "configs", "sdar_30b_a3b.py")).read()
    assert "mxnet_tpu" not in text.replace("``mxnet_tpu``", "")
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["import jax", "import jax.numpy as jnp",
                               "import json", "import numpy as np",
                               "import os"]
    assert text.count("DEPARTURE") >= 4


@pytest.mark.parametrize("control", ["e4m3", "causal", "bf16"])
def test_the_controls_are_the_reference_with_one_thing_wrong(control):
    """The files that set ``reference_tolerance``, loaded as the runner
    loads a ``reference``: at a tiny size the two controls read far from
    the plain reference; the bfloat16 witness is the reference itself
    wherever a float32 product keeps its operands (the CPU)."""
    import mxnet_tpu as mx

    tiny = {"num_hidden_layers": 2, "num_experts": 8, "first_expert": 0,
            "head_dim": 16, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_experts_per_tok": 4,
            "rms_norm_eps": 1e-6, "rope_theta": 1000000, "block_length": 4}
    symbol = mx.models.sdar(
        seq_len=32, layers=2, vocab_rows=96, experts_held=8, num_experts=16,
        hidden_size=64, moe_intermediate_size=32, **{
            k: tiny[k] for k in ("head_dim", "num_attention_heads",
                                 "num_key_value_heads", "block_length",
                                 "num_experts_per_tok")})
    mx.random.seed(5)
    model = mx.FeedForward(symbol, ctx=mx.cpu(),
                           initializer=mx.init.Xavier())
    model._init_params({"data": (2, 64), "softmax_label": (2, 32)})
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    ids = np.random.RandomState(5).randint(0, 95, (2, 64)).astype(np.int32)
    ids[:, :32][:, ::3] = 95
    configs = os.path.join(BENCH, "configs")
    plain = catalog.load_file_module(
        os.path.join(configs, "sdar_30b_a3b.py"), "plain_for_controls")
    module = catalog.load_file_module(
        os.path.join(configs, f"sdar_30b_a3b_control_{control}.py"),
        "control_" + control)
    assert "mxnet_tpu" not in open(module.__file__).read()
    want = np.asarray(plain.logits(params, None, ids, tiny))
    got = np.asarray(module.logits(params, None, ids, tiny))
    error = np.linalg.norm(got - want) / np.linalg.norm(want)
    if control == "bf16":
        assert error < 1e-6
    else:
        assert error > 0.05, error
    # the plain reference is untouched by a control loaded beside it
    assert np.array_equal(
        np.asarray(plain.logits(params, None, ids, tiny)), want)


def test_the_cost_function_counts_as_flops_py_counts(config):
    costs = _load(os.path.join(BENCH, "blockdiff_costs.py"),
                  "blockdiff_costs")
    assert costs.kv_mean(4096, 4) == 2050.0
    forward, backward = costs.flash_attention(32, 4, 4096, 4, 128, 128)
    entry = next(a for a in config["flops_per_sample"]["layers"]
                 if a["op"] == "attention")
    assert forward["flops"] == flops.layer_forward_flops(entry)
    assert backward["flops"] == 2 * forward["flops"]
    q, kv = 32 * 8192 * 128 * 2, 4 * 8192 * 128 * 2
    assert forward["bytes"] == 2 * q + 2 * kv + 32 * 8192 * 4
    assert backward["bytes"] == 2 * (2 * q + 2 * kv) + 2 * 32 * 8192 * 4
    # half of what a plain causal mask over the 8,192 rows would be credited
    plain = _load(os.path.join(BENCH, "kernel_costs.py"), "kernel_costs")
    causal = plain.flash_attention(32, 4, 8192, 128, 128, 0)[0]
    assert causal["flops"] / forward["flops"] == pytest.approx(1.998,
                                                               abs=1e-3)
    assert causal["bytes"] == forward["bytes"]
    # compute-bound on a v5e, forward and backward
    peak = catalog.peak_for("TPU v5 lite")
    for cost in (forward, backward):
        assert plain.roofline_seconds(cost, peak) == \
            cost["flops"] / 197e12 > cost["bytes"] / 819e9
    import re
    assert re.search(costs.SCOPE, "jit(step)/jvp(layer12_attn/"
                     "BlockDiffusionAttention)/flash_fwd/pallas_call")
    assert not re.search(costs.SCOPE, "jit(step)/jvp(layer1_attn/"
                         "RotaryAttention)/flash_fwd/pallas_call")


def test_entries_of_the_benchmark(tree, config):
    """This configuration's own entries, each found by its NAME, in the
    repository's tree and in the trees a later PR makes by appending: the
    configuration, the one cell, the cell's name after ``laguna_xs2``'s in
    the four expert metrics' lists and in none of the accepted attention
    ones, and the three new metrics, each listing the cell first."""
    bench, here = tree.bench, os.path.join(tree.root, "benchmark")
    (entry,) = [c for c in bench["configs"] if c["name"] == "sdar_30b_a3b"]
    assert config["name"] == "sdar_30b_a3b"
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/sdar_30b_a3b.json"
    assert entry["reduced"] == config["reduced"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "sdar_30b_a3b",
                    "traffic": "token_ring_blockdiff_4k", "chips": 1,
                    "why": cell["why"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MOE_METRICS:        # a later cell comes after the two
        assert by_name[name]["workloads"][:2] == ["laguna_xs2.seq8k", CELL]
    for name in harness.ATTENTION_METRICS:
        assert CELL not in by_name[name]["workloads"]
    for name in NEW_METRICS:
        reader = catalog.load_metric("layer_metrics", name, here=here)
        assert "workloads" not in reader.METRIC
        assert by_name[name] == dict(reader.METRIC,
                                     workloads=by_name[name]["workloads"])
        assert by_name[name]["workloads"][0] == CELL
        assert by_name[name]["layer"] == \
            "graph to XLA (symbol.py, executor.py, ops/)"
        assert by_name[name]["moves"] == "samples_per_s_per_chip"
    # the cell reports its 28 metrics, and none that a later cell brought:
    # of the attention readers its own three alone
    reported = [m["name"] for m in catalog.metrics_for(bench, "per_layer",
                                                       CELL)]
    assert reported == [n for n in harness.ACCEPTED_METRICS
                        if not n.startswith("collective_")
                        and n not in harness.ATTENTION_METRICS]
    assert len(reported) == 21 + 4 + 3
    traffic = catalog.read_json(os.path.join(
        here, "traffic", cell["traffic"] + ".json"))
    assert {k: v for k, v in traffic.items() if k != "why"} == {
        "kind": "token_ring_blockdiff", "ring": 8, "steps_per_epoch": 64,
        "warmup_steps": 16, "follow_p": 0.5}


def test_the_feeder_makes_a_noisy_copy_beside_the_clean_one():
    import jax

    feeder = catalog.load_feeder("token_ring_blockdiff")
    config = {"per_chip_batch": 3, "input_shape": [2 * 64], "vocab_rows": 50,
              "block_length": 4, "mask_id": 49}
    traffic = {"ring": 4, "steps_per_epoch": 5, "warmup_steps": 2,
               "follow_p": 0.5}

    def make(seed):
        return feeder.make(traffic, config, jax.devices()[:1], seed, "data",
                           "softmax_label")

    feed = make(2 ** 31 + 5)
    assert (feed.steps_per_epoch, feed.batch_rows, len(feed.iter.ring)) \
        == (5, 3, 4)
    counts = []
    for data, label in feed.iter.ring:
        data, label = np.asarray(data), np.asarray(label)
        assert data.dtype == label.dtype == np.int32
        assert data.shape == (3, 128) and label.shape == (3, 64)
        xt, x0 = data[:, :64], data[:, 64:]
        assert np.array_equal(x0, label)
        assert x0.min() >= 0 and x0.max() < 49      # below the mask id
        masked = xt == 49
        assert np.array_equal(xt[~masked], x0[~masked])
        counts.append(masked.reshape(3, 16, 4).sum(-1))
    counts = np.concatenate(counts).ravel()
    # 1 to 4 masks a block, every count drawn
    assert set(counts) == {1, 2, 3, 4}
    assert 0.5 < counts.mean() / 4 < 0.75              # (B + 1) / (2 B)
    rows = feed.check_rows(2)
    assert rows.shape == (2, 128) and rows.dtype == np.int32
    assert np.array_equal(rows, np.asarray(feed.iter.ring[0][0])[:2])
    # the same seed gives the same pairs, another seed others
    again, other = make(2 ** 31 + 5), make(7)
    assert np.array_equal(np.asarray(again.iter.ring[1][0]),
                          np.asarray(feed.iter.ring[1][0]))
    assert not np.array_equal(np.asarray(other.iter.ring[1][0]),
                              np.asarray(feed.iter.ring[1][0]))
    with pytest.raises(catalog.BenchmarkError, match="mask id"):
        feeder.make(traffic, dict(config, mask_id=48), jax.devices()[:1], 1,
                    "data", "softmax_label")


def test_the_feeders_clean_copy_is_token_rings_chain():
    """The clean copy is ``token_ring``'s chain, position by position, from
    the feeder's own draws: ``x[t]`` the successor of ``x[t - 1]`` where
    ``t`` follows, a drawn id where it does not."""
    import jax
    import jax.numpy as jnp

    feeder = catalog.load_feeder("token_ring_blockdiff")
    rows, length, vocab, seed = 2, 512, 95, 2 ** 31 + 5
    config = {"per_chip_batch": rows, "input_shape": [2 * length],
              "vocab_rows": vocab + 1, "block_length": 4, "mask_id": vocab}
    traffic = {"ring": 2, "steps_per_epoch": 2, "follow_p": 0.5}
    feed = feeder.make(traffic, config, jax.devices()[:1], seed, "data",
                       "softmax_label")
    root = jax.random.PRNGKey(seed)
    successor = np.asarray(
        jax.random.permutation(jax.random.fold_in(root, 1), vocab))
    longest = 0
    for key, (_, label) in zip(
            jax.random.split(jax.random.fold_in(root, 2), 2), feed.iter.ring):
        k_first, k_follow, k_other, _, _ = jax.random.split(key, 5)
        follow = np.asarray(
            jax.random.bernoulli(k_follow, 0.5, (length - 1, rows)))
        other = np.asarray(jax.random.randint(
            k_other, (length - 1, rows), 0, vocab, jnp.int32))
        x = np.empty((length, rows), np.int32)
        x[0] = np.asarray(jax.random.randint(k_first, (rows,), 0, vocab,
                                             jnp.int32))
        run = np.zeros(rows, int)
        for t in range(1, length):
            x[t] = np.where(follow[t - 1], successor[x[t - 1]], other[t - 1])
            run = np.where(follow[t - 1], run + 1, 0)
            longest = max(longest, run.max())
        assert np.array_equal(np.asarray(label), x.T)
    assert longest >= 5


def _hand_written_run(config):
    """A traced run as ``run.py`` hands it to the readers, written by
    hand: the flash kernels and another instruction under the new
    operator's scope in layers 0 and 5, a kernel of the OTHER attention
    operator, an expert layer's gather, three epoch stamps."""
    fwd = "jit(step)/jvp(layer{}_attn/BlockDiffusionAttention)/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/layer{}_attn/" \
          "BlockDiffusionAttention/"
    scopes = {
        "flash_fwd.1": fwd.format(0) + "flash_fwd/pallas_call",
        "flash_fwd.2": bwd.format(0) + "flash_fwd/pallas_call",
        "flash_bwd_dq.1": bwd.format(5) + "flash_bwd_dq/pallas_call",
        "flash_bwd_dkv.1": bwd.format(5) + "flash_bwd_dkv/pallas_call",
        "fusion.1": fwd.format(5) + "mul",
        "flash_fwd.9": "jit(step)/jvp(layer1_attn/RotaryAttention)/"
                       "flash_fwd/pallas_call",
        "fusion.2": "jit(step)/jvp(layer1_moe/MixtureOfExperts)/gather",
    }
    seconds = {"flash_fwd.1": 0.128, "flash_fwd.2": 0.128,
               "flash_bwd_dq.1": 0.256, "flash_bwd_dkv.1": 0.128,
               "fusion.1": 0.064, "flash_fwd.9": 7.0, "fusion.2": 0.192}
    return {"trace": {"program_op_seconds": seconds, "op_seconds": seconds,
                      "span_steps": 64},
            "hlo_scopes": scopes, "peak": catalog.peak_for("TPU v5 lite"),
            "config": config, "per_chip_batch": 1, "steps_per_epoch": 64,
            "traced_epochs": [0, 1],
            "rows": [{"entry": 9.1, "exit": 9.2},
                     {"entry": 19.1, "exit": 19.2},
                     {"entry": 29.1, "exit": 29.2}]}


def _records(masks):
    records = []
    for epoch in range(3):
        records.append({"name": "fit.epoch.callback", "thread": "main",
                        "start": 10.0 * epoch + 9.0,
                        "end": 10.0 * epoch + 9.9, "epoch": epoch,
                        "attrs": {"epoch": epoch}})
        if epoch in masks:
            records.append({"name": "fit.epoch.diffusion_mask",
                            "thread": "main", "start": 10.0 * epoch + 8.5,
                            "end": 10.0 * epoch + 8.5, "epoch": epoch,
                            "attrs": dict(masks[epoch], node="softmax",
                                          epoch=epoch)})
    return records


def test_readers_of_the_new_metrics_on_a_hand_written_run(config,
                                                          monkeypatch):
    run = _hand_written_run(config)
    ms = catalog.load_metric("layer_metrics",
                             "attention_blockdiff_ms_per_step")
    # everything under the operator's scope, every layer, and nothing of
    # the other attention operator: 0.704 s over 64 steps
    assert ms.read(run) == pytest.approx(1e3 * 0.704 / 64)
    roofline = catalog.load_metric("layer_metrics",
                                   "attention_blockdiff_roofline_pct")
    costs = _load(os.path.join(BENCH, "blockdiff_costs.py"), "costs")
    forward, backward = costs.flash_attention(32, 4, 4096, 4, 128, 128)
    least = 6 * (forward["flops"] + backward["flops"]) / 197e12
    # the kernels' time alone (the recomputed forward in it): 0.640 s
    assert roofline.read(run) == pytest.approx(
        100 * least / (0.640 / 64), rel=1e-9)
    assert least * 1e3 == pytest.approx(25.14, abs=0.01)   # ms a step
    share = catalog.load_metric("layer_metrics", "blockdiff_masked_share")
    from mxnet_tpu import telemetry
    masks = {0: {"rows": 16 * 4096, "masked": 1},           # the warm-up's
             1: {"rows": 64 * 4096, "masked": 64 * 2560},
             2: {"rows": 64 * 4096, "masked": 64 * 2568}}
    monkeypatch.setattr(telemetry, "span_records",
                        lambda *a, **k: _records(masks))
    assert share.read(run) == pytest.approx(2564 / 4096)
    # nothing to read is nothing, never 0: no trace, no HLO text, a program
    # without the operator or without the record, another configuration
    monkeypatch.setattr(telemetry, "span_records",
                        lambda *a, **k: _records({}))
    assert share.read(run) is None
    bare = dict(run, trace=None, hlo_scopes=None)
    assert ms.read(bare) is None and roofline.read(bare) is None
    other = dict(run, hlo_scopes={k: v.replace("BlockDiffusionAttention",
                                               "RotaryAttention")
                                  for k, v in run["hlo_scopes"].items()})
    assert ms.read(other) is None and roofline.read(other) is None
    laguna = catalog.read_json(os.path.join(BENCH, "configs",
                                            "laguna_xs2.json"))
    assert roofline.read(dict(run, config=laguna)) is None
    # the four expert readers take this cell's sizes from its configuration
    decoder = _load(os.path.join(BENCH, "decoder_metrics.py"), "decoder")
    assert decoder.moe_ms(run) == pytest.approx(1e3 * 0.192 / 64)


# -- the runner on the cell, at a tiny preset on the CPU -------------------------

TINY = {
    "input_shape": [64], "vocab_rows": 96, "vocab_size": 96,
    "mask_id": 95, "block_length": 4, "per_chip_batch": 2,
    "num_hidden_layers": 3, "num_experts": 8, "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "optimizer": {"name": "adam", "learning_rate": 0.002},
    # float32: at these sizes a bfloat16 run differs from the reference by
    # which expert a row takes, not by arithmetic
    "compute_dtype": None, "reference_rows": 2, "reference_tolerance": 1e-4,
}


@pytest.fixture(scope="module")
def tiny_checkout(tmp_path_factory, config):
    """A checkout-shaped directory: ``benchmark/`` as it is, the
    configuration's file with tiny sizes under its own name (the reference
    and the metric files read it by that name), a short traffic mix."""
    root = tmp_path_factory.mktemp("sdar_overlay")
    here = root / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    tiny = dict(config, **TINY)
    sizes = {k: TINY[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "num_experts_per_tok",
        "moe_intermediate_size", "block_length", "mask_id")}
    tiny["builder"] = {"import": "mxnet_tpu.models:sdar", "kwargs": dict(
        sizes, seq_len=32, layers=3, vocab_rows=96, experts_held=8,
        first_expert=0, num_experts=16, train_router=False)}
    from mxnet_tpu.models import sdar

    tiny["flops_per_sample"] = {"layers": walk.layers_of(
        sdar(**tiny["builder"]["kwargs"]), tiny)}
    (here / "configs" / "sdar_30b_a3b.json").write_text(json.dumps(tiny))
    (here / "traffic" / "token_ring_blockdiff_4k.json").write_text(
        json.dumps({"kind": "token_ring_blockdiff", "ring": 3,
                    "steps_per_epoch": 4, "warmup_steps": 3,
                    "follow_p": 0.5}))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_runner_on_the_cell_at_a_tiny_preset(tiny_checkout, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 33), "--seconds", "0.5", "--trace",
         str(trace), "--rehearse-on-cpu"],
        cwd=tiny_checkout, env=env, capture_output=True, text=True,
        timeout=900)
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
    assert {"blockdiff_masked_share", "moe_load_max_over_mean",
            "moe_picks_held_per_token", "write_back_ms"} <= got
    assert not got & {"attention_blockdiff_ms_per_step",
                      "attention_blockdiff_roofline_pct", "moe_ms_per_step",
                      "attention_full_ms_per_step", "device_step_ms"}
    # 1 to 4 of a block's 4 positions: 0.625 in expectation
    assert 0.5 < result["metrics"]["blockdiff_masked_share"]["value"] < 0.75
    picks = result["metrics"]["moe_picks_held_per_token"]["value"]
    assert 0.5 < picks < 3.5          # 4 picks x 8 of 16 held: 2 expected


def test_the_sweep_that_chose_the_drawn_weights_runs(tiny_checkout):
    """``sdar_30b_a3b_sweep.py`` at the tiny preset: a line a seed and
    fill: the witness and the controls against the reference, this rank's
    load a layer."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    fills = {"as_drawn": {}, "first_sharp": {"layer0_[qk]_norm_gamma$": 3}}
    proc = subprocess.run(
        [sys.executable,
         os.path.join("benchmark", "configs", "sdar_30b_a3b_sweep.py"),
         "--seeds", str(2 ** 31 + 34), "--fills", json.dumps(fills),
         "--rehearse-on-cpu"],
        cwd=tiny_checkout, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln["fill"] for ln in lines] == list(fills)
    for line in lines:
        assert line["reference_bf16"] < 1e-6        # the CPU keeps operands
        assert line["e4m3"] > 0.05 and line["causal"] > 0.05
        assert len(line["picks_per_token"]) == 3    # 4 picks, 8 of 16 held
        assert 1.0 < sum(line["picks_per_token"]) / 3 < 3.0
    assert lines[0]["picks_per_token"] != lines[1]["picks_per_token"]
