"""The configuration ``nemotron3_nano_30b_a3b`` and the cell
``nemotron3_nano_30b_a3b.seq8k``: the file against the public config's
values (pinned here), the cut, the FLOP recipe against the walk of the
built model, the reference's independence and its controls, the scan's
cost function, the readers of the two new per-layer metrics on a
hand-written run, the entries of ``BENCHMARK.json``, and the runner on the
cell at a tiny preset on the CPU.
"""

import importlib.util
import json
import os
import re
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

NAME, CELL = "nemotron3_nano_30b_a3b", "nemotron3_nano_30b_a3b.seq8k"
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/
# config.json, as the catalog row has it: every key of its ``config``
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    return catalog.read_json(os.path.join(BENCH, "configs", NAME + ".json"))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the harness's own test file, for the trees the contract is held on
# (``tree``) and the record of what the benchmark was accepted with
harness = _load(os.path.join(HERE, "test_benchmark_harness.py"),
                "bench_harness_tests_of_nemotron3")
bench, overlay, tree = harness.bench, harness.overlay, harness.tree
NEW_METRICS = ["ssm_ms_per_step", "ssm_scan_roofline_pct"]
JOINED = ["attention_full_ms_per_step", "attention_full_roofline_pct",
          "moe_ms_per_step", "moe_load_max_over_mean",
          "moe_picks_held_per_token"]
SETUP_METRICS = ["train_step_trace_s", "train_step_lower_s",
                 "train_step_backend_s", "setup_other_compile_s",
                 "precompile_unnamed_s"]


def test_the_file_holds_the_published_values(config):
    """Every key of the public config under its own name and unchanged,
    but the three that the cut names, each with the published count and
    the deployment beside it; every assumption the config forces, named;
    no width among the cut's keys."""
    for key, value in PUBLISHED.items():
        assert key in config and config[key] == CUT.get(key, value), key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "epoch_samples"]
    assert {k: config["published"][k] for k in CUT} == {
        k: PUBLISHED[k] for k in CUT}
    assert "31,577,937,344" in config["published"]["parameters"]
    assert "sixteen chips share each layer" in config["deployment"]
    assert "666,962,944 parameters" in config["deployment"]
    assert "MEMEM*EME" in config["deployment"]
    for form in ("mamba_widths", "in_proj_split", "gated_norm", "time_step",
                 "rotary", "scan_form", "bias_rate", "selection_bias",
                 "train_router", "routing", "optimizer", "initializer",
                 "data", "compute_dtype"):
        assert len(config["assumed"][form]) > 40, form
    assert "group_size" in config["assumed"]["gated_norm"]
    assert "unused" in config["assumed"]["rotary"]
    assert config["layer_types"] == [
        "mamba", "moe", "mamba", "moe", "mamba", "full_attention", "moe",
        "mamba", "moe"]
    assert config["builder"] == {
        "import": "mxnet_tpu.models:nemotron_h",
        "kwargs": {"seq_len": 8192, "layers": 9, "vocab_rows": 16384,
                   "experts_held": 8, "first_expert": 0,
                   "train_router": False}}
    assert config["vocab_rows"] == 131072 // 8 == config["vocab_size"]
    assert config["input_shape"] == [8192]
    assert config["per_chip_batch"] == 1 and config["reference_rows"] == 1
    assert config["compute_dtype"] == "bfloat16"
    assert config["optimizer"] == {"name": "adam", "learning_rate": 1e-05}
    init = config["initializer"]
    assert init["name"] == "Mixed" and init["patterns"][-1] == ".*"
    assert init["initializers"][-1] == {"name": "Xavier"}
    assert {"name": "SSMStepBias", "time_step_min": 0.001,
            "time_step_max": 0.1, "time_step_floor": 0.0001} \
        in init["initializers"]
    assert config["logits"] == "head_output"
    assert config["reference"] == NAME + ".py"
    assert len(config["reference_tolerance_why"]) > 80
    assert 0 < config["reference_tolerance"] < 1


def test_the_initializer_draws_what_the_file_says(config):
    import mxnet_tpu as mx

    spec = dict(config["initializer"])
    init = getattr(mx.init, spec.pop("name"))(**spec)
    arrays = {n: mx.nd.zeros(s) for n, s in (
        ("layer0_mamba_A_log", (64,)), ("layer0_mamba_D", (64,)),
        ("layer0_mamba_dt_bias", (64,)), ("layer0_mamba_conv_weight",
                                          (6144, 4)),
        ("layer0_mamba_conv_bias", (6144,)),
        ("layer0_mamba_norm_gamma", (4096,)),
        ("layer1_moe_selection_bias", (128,)),
        ("layer0_mamba_ssm_record", (1025, 4)))}
    mx.random.seed(41)
    for name, arr in arrays.items():
        init(name, arr)
    got = {n: a.asnumpy() for n, a in arrays.items()}
    assert np.allclose(got["layer0_mamba_A_log"], np.log(np.arange(1, 65)))
    assert np.all(got["layer0_mamba_D"] == 1)
    dt0 = np.log1p(np.exp(got["layer0_mamba_dt_bias"].astype(np.float64)))
    assert 0.001 * (1 - 1e-5) <= dt0.min() < 0.003
    assert 0.05 < dt0.max() <= 0.1 * (1 + 1e-5)
    conv = got["layer0_mamba_conv_weight"]
    assert 1.4 < np.abs(conv).max() <= 1.5
    for zero in ("layer0_mamba_conv_bias", "layer1_moe_selection_bias",
                 "layer0_mamba_ssm_record"):
        assert not np.any(got[zero]), zero
    assert np.all(got["layer0_mamba_norm_gamma"] == 1)


def test_the_walk_gives_the_files_recipe_and_the_issues_count(config):
    symbol = catalog.build_symbol(config["builder"],
                                  os.path.join(BENCH, "configs"))
    layers = walk.layers_of(symbol, config)
    assert layers == config["flops_per_sample"]["layers"]
    # 357.6 M multiply-adds a token forward (358.9 M with the two causal
    # products of a chunk credited at all 128 positions), 17.58 TFLOP a
    # sequence forward and backward
    forward = flops.forward_flops_per_sample(layers)
    assert forward / 2 / 8192 == 357_603_328
    assert flops.train_flops_per_sample(layers) == pytest.approx(
        17.577e12, rel=1e-4)
    by_name = {m["name"]: m for m in layers}

    def share(prefixes):
        return sum(flops.layer_forward_flops(m) for m in layers
                   if m["name"].startswith(prefixes)) / forward

    mamba = tuple(f"layer{l}_mamba" for l in (0, 2, 4, 7))
    moe = tuple(f"layer{l}_moe" for l in (1, 3, 6, 8))
    assert share(mamba) == pytest.approx(0.4487, abs=1e-3)
    assert share(moe) == pytest.approx(0.2689, abs=1e-3)
    assert share(("layer5_",)) == pytest.approx(0.1593, abs=1e-3)
    assert share(("head",)) == pytest.approx(0.1232, abs=1e-3)
    scan = tuple(f"{m}_ssd" for m in mamba)
    assert share(scan) == pytest.approx(0.0154, abs=1e-3)
    # a Mamba-2 layer: both projections, the convolution, the scan
    assert [(by_name[f"layer0_mamba_{p}"]["cin"],
             by_name[f"layer0_mamba_{p}"]["cout"],
             by_name[f"layer0_mamba_{p}"]["rows"])
            for p in ("in", "conv", "ssd_scores", "ssd_diagonal",
                      "ssd_states", "ssd_carried", "out")] == [
        (2688, 10304, 8192), (4, 1, 8192 * 6144), (128, 64.5, 8192 * 8),
        (64.5, 64, 8192 * 64), (64, 128, 8192 * 64), (128, 64, 8192 * 64),
        (4096, 2688, 8192)]
    # ungated experts at the EXPECTED rows 8,192 x 6 x 8 / 128, no gate
    assert by_name["layer1_moe_up"]["rows"] == 384 * 8
    assert "layer1_moe_gate" not in by_name
    assert (by_name["layer1_moe_shared_up"]["cout"],
            by_name["layer1_moe_router"]["cout"]) == (3712, 128)
    attention = [a for a in layers if a["op"] == "attention"]
    assert [(a["name"], a["heads"], a["qk_dim"], a["kv_mean"])
            for a in attention] == [("layer5_attn", 32, 128, 4096.5)]
    # what the cut holds: 666,962,944 parameters, 10.67 GB at 16 B
    args, _, aux = symbol.infer_shape(data=(1, 8192),
                                      softmax_label=(1, 8192))
    count = sum(int(np.prod(s)) for n, s in zip(symbol.list_arguments(), args)
                if n not in ("data", "softmax_label"))
    assert count == 666_962_944
    assert count * 16 / 1e9 == pytest.approx(10.67, abs=5e-3)
    assert symbol.list_auxiliary_states()[:3] == [
        "layer0_mamba_ssm_record", "layer1_moe_expert_load",
        "layer1_moe_selection_bias"]


def test_the_reference_is_independent_of_the_program():
    text = open(os.path.join(BENCH, "configs", NAME + ".py")).read()
    assert "mxnet_tpu" not in text.replace("``mxnet_tpu``", "")
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["import jax", "import jax.numpy as jnp",
                               "import json", "import numpy as np",
                               "import os"]
    assert text.count("DEPARTURE") >= 3
    # the recurrence, one position at a time: no chunk in the reference
    assert "lax.scan" in text and "chunk_size" not in text


TINY_REF = {
    "num_hidden_layers": 9, "n_routed_experts": 16, "first_expert": 0,
    "hidden_size": 48, "hybrid_override_pattern": "MEMEM*EME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_norm_epsilon": 1e-5}


@pytest.mark.parametrize("control", ["e4m3", "nocarry", "bf16"])
def test_the_controls_are_the_reference_with_one_thing_wrong(control,
                                                             config):
    """The files that set ``reference_tolerance``, loaded as the runner
    loads a ``reference``: at a tiny size the two controls read far from
    the plain reference; the bfloat16 witness is the reference itself
    wherever a float32 product keeps its operands (the CPU)."""
    import mxnet_tpu as mx

    symbol = mx.models.nemotron_h(
        seq_len=64, layers=9, vocab_rows=96, experts_held=16,
        **{k: v for k, v in TINY_REF.items()
           if k not in ("num_hidden_layers", "first_expert")})
    spec = dict(config["initializer"])
    mx.random.seed(5)
    model = mx.FeedForward(symbol, ctx=mx.cpu(),
                           initializer=getattr(mx.init, spec.pop("name"))(
                               **spec))
    model._init_params({"data": (2, 64), "softmax_label": (2, 64)})
    params = {k: v.asnumpy() for k, v in model.arg_params.items()}
    rng = np.random.RandomState(5)
    aux = {n: (rng.rand(16) * 0.4 - 0.2).astype(np.float32)
           for n in symbol.list_auxiliary_states()
           if n.endswith("selection_bias")}
    ids = rng.randint(0, 96, (2, 64)).astype(np.int32)
    configs = os.path.join(BENCH, "configs")
    plain = catalog.load_file_module(os.path.join(configs, NAME + ".py"),
                                     "plain_for_controls")
    module = catalog.load_file_module(
        os.path.join(configs, f"{NAME}_control_{control}.py"),
        "control_" + control)
    assert "mxnet_tpu" not in open(module.__file__).read()
    tiny = dict(TINY_REF, vocab_size=96)
    want = np.asarray(plain.logits(params, aux, ids, tiny))
    got = np.asarray(module.logits(params, aux, ids, tiny))
    error = np.linalg.norm(got - want) / np.linalg.norm(want)
    if control == "bf16":
        assert error < 1e-6
    else:
        assert error > 0.05, error
    # the plain reference is untouched by a control loaded beside it, and
    # the no-carry control leaves its own copy of the reference as it was
    assert np.array_equal(
        np.asarray(plain.logits(params, aux, ids, tiny)), want)
    if control == "nocarry":
        assert module.plain.selective_scan is module.recurrence


def test_the_nocarry_control_restarts_the_state_at_every_chunk():
    configs = os.path.join(BENCH, "configs")
    module = catalog.load_file_module(
        os.path.join(configs, f"{NAME}_control_nocarry.py"), "nocarry_scan")
    plain = module.plain
    rng = np.random.RandomState(3)
    x = rng.randn(1, 32, 4, 2).astype(np.float32)
    dt = np.full((1, 32, 4), 0.05, np.float32)
    a = -np.ones(4, np.float32)
    b = rng.randn(1, 32, 2, 3).astype(np.float32)
    c = rng.randn(1, 32, 2, 3).astype(np.float32)
    whole = np.asarray(plain.selective_scan(x, dt, a, b, c))
    halves = np.concatenate([np.asarray(plain.selective_scan(
        x[:, s:s + 16], dt[:, s:s + 16], a, b[:, s:s + 16], c[:, s:s + 16]))
        for s in (0, 16)], axis=1)
    seen = {}

    def spy(params, aux, ids, config):
        seen["y"] = np.asarray(plain.selective_scan(x, dt, a, b, c))
        return np.zeros(1)

    real, plain.logits = plain.logits, spy
    try:
        module.logits({}, {}, None, {"chunk_size": 16})
    finally:
        plain.logits = real
    assert np.allclose(seen["y"], halves, atol=1e-6)
    assert not np.allclose(seen["y"][:, 16:], whole[:, 16:], atol=1e-3)


def test_the_scan_cost_function(config):
    costs = _load(os.path.join(BENCH, "ssm_costs.py"), "ssm_costs")
    forward, backward = costs.ssd(8192, 64, 64, 8, 128, 128)
    entries = [m for m in config["flops_per_sample"]["layers"]
               if m["name"].startswith("layer0_mamba_ssd")]
    assert forward["flops"] == sum(map(flops.layer_forward_flops, entries))
    # by hand: C B^T at 64.5 positions a position over 128, a group; the
    # diagonal's 64.5 x 64 a head; states and carry 64 x 128 a head
    assert forward["flops"] == 2 * 8192 * (8 * 64.5 * 128 + 64 * 64.5 * 64
                                           + 2 * 64 * 64 * 128)
    assert backward["flops"] == 2 * forward["flops"]
    x = 8192 * 64 * 64 * 2
    bc = 2 * 8192 * 8 * 128 * 2
    dt = 8192 * 64 * 4
    assert forward["bytes"] == 2 * x + bc + dt
    assert backward["bytes"] == 3 * x + 2 * (bc + dt)
    # bound by the memory on a v5e, forward and backward
    kernels = _load(os.path.join(BENCH, "kernel_costs.py"), "kernel_costs")
    peak = catalog.peak_for("TPU v5 lite")
    for cost in (forward, backward):
        assert kernels.roofline_seconds(cost, peak) == \
            cost["bytes"] / 819e9 > cost["flops"] / 197e12
    for scope in ("jit(step)/jvp(layer0_mamba/Mamba2Mixer)/ssd/exp",
                  "jit(step)/transpose(jvp(jvp()))/checkpoint/layer7_mamba/"
                  "Mamba2Mixer/ssd/while/body/add"):
        assert re.search(costs.SCAN, scope) and re.search(costs.SCOPE, scope)
    for scope in ("jit(step)/jvp(layer0_mamba/Mamba2Mixer)/conv/pad",
                  "jit(step)/jvp(layer0_mamba_in/FullyConnected)/dot"):
        assert not re.search(costs.SCAN, scope)
        assert re.search(costs.SCOPE, scope)
    assert not re.search(costs.SCOPE, "jit(step)/jvp(layer1_moe/"
                         "MixtureOfExpertsSquaredReLU)/gather")


def test_entries_of_the_benchmark(tree, config):
    """This configuration's own entries, each found by its NAME, in the
    repository's tree and in the trees a later PR makes by appending: the
    configuration, the one cell, the cell's name after the accepted cells'
    in the lists it joined and in none of the others, and the two new
    metrics, each listing the cell first. A tree without the cell (the
    harness's own copies are made of the accepted benchmark) has nothing
    to hold."""
    bench, here = tree.bench, os.path.join(tree.root, "benchmark")
    found = [c for c in bench["configs"] if c["name"] == NAME]
    if not found:
        pytest.skip("a tree made without this configuration")
    (entry,) = found
    assert config["name"] == NAME
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == config["reduced"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": NAME, "traffic": "token_ring_8k",
                    "chips": 1, "why": cell["why"]}
    assert "384 rows an expert" in cell["why"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    accepted = harness.ACCEPTED_WORKLOADS
    for name in JOINED:
        lists = by_name[name]["workloads"]
        before = lists[:lists.index(CELL)]
        assert before[:len(accepted[name])] == accepted[name]
    for name in SETUP_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["workloads"].index("kanana2_30b_a3b.seq8k") \
            < by_name[name]["workloads"].index(CELL)
    # the ungated experts' share of their roofline waits for a reader that
    # knows the expert's form
    for name in ["moe_grouped_roofline_pct"] + [
            n for n in harness.ATTENTION_METRICS + harness.BLOCKDIFF_METRICS
            if "_full_" not in n]:
        assert CELL not in by_name[name]["workloads"]
    for name in NEW_METRICS:
        reader = catalog.load_metric("layer_metrics", name, here=here)
        assert "workloads" not in reader.METRIC
        assert by_name[name] == dict(reader.METRIC,
                                     workloads=by_name[name]["workloads"])
        assert by_name[name]["workloads"][0] == CELL
    reported = [m["name"] for m in catalog.metrics_for(bench, "per_layer",
                                                       CELL)]
    assert set(NEW_METRICS + JOINED + SETUP_METRICS) <= set(reported)
    traffic = catalog.read_json(os.path.join(
        here, "traffic", cell["traffic"] + ".json"))
    assert {k: v for k, v in traffic.items() if k != "why"} == {
        "kind": "token_ring", "ring": 8, "steps_per_epoch": 64,
        "warmup_steps": 16, "follow_p": 0.5}


def _hand_written_run(config):
    """A traced run as ``run.py`` hands it to the readers, written by
    hand: the scan, the convolution and the projections of layers 0 and 7,
    the expert layer, the attention kernel, three epoch stamps."""
    fwd = "jit(step)/jvp(layer{}_mamba/Mamba2Mixer)/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/layer{}_mamba/" \
          "Mamba2Mixer/"
    scopes = {
        "fusion.1": fwd.format(0) + "ssd/exp",
        "dot.2": bwd.format(7) + "ssd/bcgrij,bcjgrp->bcigrp/dot_general",
        "while.3": bwd.format(7) + "ssd/while/body/add",
        "fusion.4": fwd.format(0) + "conv/pad",
        "gate.5": fwd.format(7) + "gate_norm/mul",
        "dot.6": "jit(step)/jvp(layer0_mamba_in/FullyConnected)/dot",
        "dot.7": "jit(step)/transpose(jvp(layer2_mamba_out/"
                 "FullyConnected))/dot",
        "fusion.8": "jit(step)/jvp(layer1_moe/MixtureOfExpertsSquaredReLU)/"
                    "gather",
        "flash_fwd.9": "jit(step)/jvp(layer5_attn/RotaryAttention)/"
                       "flash_fwd/pallas_call",
        "fusion.10": "jit(step)/jvp(layer0_norm/RMSNorm)/mul",
    }
    seconds = {"fusion.1": 0.256, "dot.2": 0.512, "while.3": 0.128,
               "fusion.4": 0.064, "gate.5": 0.032, "dot.6": 1.024,
               "dot.7": 0.512, "fusion.8": 0.192, "flash_fwd.9": 0.640,
               "fusion.10": 7.0}
    return {"trace": {"program_op_seconds": seconds, "op_seconds": seconds,
                      "span_steps": 64},
            "hlo_scopes": scopes, "peak": catalog.peak_for("TPU v5 lite"),
            "config": config, "per_chip_batch": 1, "steps_per_epoch": 64,
            "traced_epochs": [0, 1],
            "rows": [{"entry": 9.1, "exit": 9.2},
                     {"entry": 19.1, "exit": 19.2},
                     {"entry": 29.1, "exit": 29.2}]}


def test_readers_of_the_new_metrics_on_a_hand_written_run(config):
    run = _hand_written_run(config)
    ms = catalog.load_metric("layer_metrics", "ssm_ms_per_step")
    # the scan, the convolution, the gated norm and both projections of
    # every Mamba-2 layer, and nothing of the other layers: 2.528 s
    assert ms.read(run) == pytest.approx(1e3 * 2.528 / 64)
    roofline = catalog.load_metric("layer_metrics", "ssm_scan_roofline_pct")
    costs = _load(os.path.join(BENCH, "ssm_costs.py"), "costs")
    forward, backward = costs.ssd(8192, 64, 64, 8, 128, 128)
    least = 4 * (forward["bytes"] + backward["bytes"]) / 819e9
    # the scan's time alone: 0.896 s over 64 steps
    assert roofline.read(run) == pytest.approx(
        100 * least / (0.896 / 64), rel=1e-9)
    assert least * 1e3 == pytest.approx(2.1612, abs=1e-3)   # ms a step
    # nothing to read is nothing, never 0: no trace, no HLO text, a program
    # without the mixer, a configuration without a Mamba-2 layer
    bare = dict(run, trace=None, hlo_scopes=None)
    assert ms.read(bare) is None and roofline.read(bare) is None
    other = dict(run, hlo_scopes={k: v.replace("mamba", "attn")
                                  for k, v in run["hlo_scopes"].items()})
    assert ms.read(other) is None and roofline.read(other) is None
    kanana = catalog.read_json(os.path.join(BENCH, "configs",
                                            "kanana2_30b_a3b.json"))
    assert roofline.read(dict(run, config=kanana)) is None
    # the accepted readers this cell joined take its sizes from its file
    decoder = _load(os.path.join(BENCH, "decoder_metrics.py"), "decoder")
    assert decoder.moe_ms(run) == pytest.approx(1e3 * 0.192 / 64)
    assert decoder.attention_layers(config, "full_attention") == [5]
    assert decoder.attention_ms(run, "full_attention") == pytest.approx(
        1e3 * 0.640 / 64)


# -- the runner on the cell, at a tiny preset on the CPU ---------------------

TINY = {
    "input_shape": [64], "vocab_rows": 96, "vocab_size": 96,
    "per_chip_batch": 2, "num_hidden_layers": 9, "n_routed_experts": 8,
    "hidden_size": 48, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
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
    root = tmp_path_factory.mktemp("nemotron3_overlay")
    here = root / "benchmark"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__"))
    tiny = dict(config, **TINY)
    sizes = {k: TINY[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "chunk_size", "num_experts_per_tok",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim")}
    tiny["builder"] = {"import": "mxnet_tpu.models:nemotron_h", "kwargs": dict(
        sizes, seq_len=64, layers=9, vocab_rows=96, experts_held=8,
        first_expert=0, n_routed_experts=16, train_router=False)}
    from mxnet_tpu.models import nemotron_h

    tiny["flops_per_sample"] = {"layers": walk.layers_of(
        nemotron_h(**tiny["builder"]["kwargs"]), tiny)}
    (here / "configs" / (NAME + ".json")).write_text(json.dumps(tiny))
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
         CELL, "--seed", str(2 ** 31 + 41), "--seconds", "0.5", "--trace",
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
    assert {"moe_load_max_over_mean", "moe_picks_held_per_token",
            "write_back_ms"} <= got
    assert not got & {"ssm_ms_per_step", "ssm_scan_roofline_pct",
                      "moe_ms_per_step", "attention_full_ms_per_step",
                      "device_step_ms"}
    picks = result["metrics"]["moe_picks_held_per_token"]["value"]
    assert 0.5 < picks < 3.5          # 4 picks x 8 of 16 held: 2 expected
