#!/usr/bin/env python3
"""One run of one benchmark cell: ``FeedForward.fit`` on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine whose JAX finds the TPU chips
the cell asks for. One process, no children. The cell's configuration,
traffic mix, feeder and metrics are found by the names ``BENCHMARK.json``
gives (``catalog.py``); nothing here names a cell or a model.

A run: build the model and its inputs from ``--seed``, ``precompile``,
then ONE ``fit`` call. Its first epoch is the untimed warm-up (shorter
than a measured one if the traffic mix says so, but with its tail, so the
tail's programs and the feed thread are hot); the WHOLE epochs after it
are the window, stamped in ``epoch_end_callback`` and ended at the first
epoch boundary at or after ``--seconds`` (``epochs.py``). The rate is all
the window's samples over all its seconds. The checks that decide
``correct`` run after the window (``checks.py``).

stdout, in order: a line of itemised set-up seconds, a line with every
epoch's two stamps, seconds, rate and loss, and LAST one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``compared``: every number that
decided ``correct`` beside its limit, which are also the last lines on
stderr). ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of the window's first two
whole epochs and from the train program's HLO text (``scopes.py``).

Exit code non-zero, and no result line, without a TPU or with fewer chips
than the cell asks for, outside a checkout, or when anything raises.
``--rehearse-on-cpu`` lets the CPU stand in at a tiny preset for the
harness's own tests; the device it reports is then the CPU.
"""

import time

T0 = time.perf_counter()   # set-up is counted from the runner's first line

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import catalog  # noqa: E402
import checks  # noqa: E402
import epochs  # noqa: E402
import flops  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

# traced: measured epochs 1 and 2, whole. The profiler starts inside the
# warm-up epoch's callback and stops inside the second measured epoch's
TRACE_FROM, TRACE_TO = 0, 2


def say(obj):
    print(json.dumps(obj), flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-on-cpu", action="store_true",
                   help="harness tests only: accept the CPU backend")
    return p.parse_args(argv)


def take_devices(chips, platform):
    """The cell's chips, or exit non-zero: never another backend."""
    try:
        import jax
    except ImportError as e:
        sys.exit(f"benchmark: cannot import jax: {e}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"benchmark: JAX found no usable backend: "
                 f"{str(e).splitlines()[0]}")
    if devices[0].platform != platform:
        sys.exit(f"benchmark: JAX found no accelerator (first device is "
                 f"{devices[0]}, platform {devices[0].platform!r}); a cell "
                 f"runs only on a {platform}")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s) and JAX "
                 f"sees {len(devices)}")
    return devices[:chips]


class Tracer:
    """The profiler over whole epochs, started and stopped from the epoch
    callback; the trace goes to a directory under TMPDIR, removed after
    the reduction."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0
        self.active = False

    def start(self):
        self.jax.profiler.start_trace(self.dir, profiler_options=self.options)
        self.active = True

    def stop(self):
        self.jax.profiler.stop_trace()
        self.active = False


def train_program_rows(stats):
    return {label: row for label, row in stats["per_function"].items()
            if label.startswith("train_step:")
            and any(v for v in row.values() if isinstance(v, (int, float)))}


def main(argv=None):
    args = parse_args(argv)
    setup = {}
    last = [T0]

    def item(name):
        now = time.perf_counter()
        setup[name] = setup.get(name, 0.0) + now - last[0]
        last[0] = now

    bench = catalog.load_benchmark()
    found = catalog.find_cell(bench, args.workload)
    cell, config, traffic = found["cell"], found["config"], found["traffic"]
    chips = int(cell["chips"])
    platform = "cpu" if args.rehearse_on_cpu else "tpu"
    devices = take_devices(chips, platform)
    import jax
    import jax.numpy as jnp
    import numpy as np
    try:
        import mxnet_tpu as mx
    except ImportError as e:
        sys.exit(f"benchmark: cannot import mxnet_tpu ({e}); run it from "
                 "the root of a checkout")
    from mxnet_tpu.telemetry import memory
    # the small programs (initializers, casts, the metric pull) cost more
    # to compile than to read back; the program's default keeps them out
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peak = None if args.rehearse_on_cpu \
        else catalog.peak_for(devices[0].device_kind)
    item("imports_backend")

    seed = args.seed % (2 ** 31 - 1)
    mx.random.seed(seed)
    np.random.seed(seed)
    symbol = catalog.build_symbol(config["builder"],
                                  os.path.dirname(found["config_path"]))
    inputs = [n for n in symbol.list_arguments()
              if n == "data" or n.endswith("label")]
    data_name, label_name = inputs[0], inputs[-1]
    compute_dtype = jnp.dtype(config["compute_dtype"]) \
        if config.get("compute_dtype") else None
    opt = dict(config["optimizer"])
    init = dict(config["initializer"])
    model = mx.FeedForward(
        symbol, ctx=[mx.Context(platform, d.id) for d in devices],
        num_epoch=10 ** 9, compute_dtype=compute_dtype,
        initializer=getattr(mx.init, init.pop("name"))(**init),
        optimizer=opt.pop("name"), **opt)
    spy = checks.StepSpy(model)
    metric = mx.metric.CrossEntropy()
    kvstore = "device" if chips > 1 else "local"
    item("model")

    feed = catalog.load_feeder(traffic["kind"]).make(
        traffic, config, devices, seed, data_name, label_name)
    samples_per_epoch = feed.steps_per_epoch * feed.batch_rows
    item("data")

    t_pre = time.perf_counter()
    # shapes AND types of a batch as the feeder hands it over, read off its
    # ring: ``precompile(data=<DataIter>)`` would take token ids for float32
    spec = [{name: (tuple(a.shape), np.dtype(a.dtype))}
            for name, a in zip((data_name, label_name), feed.iter.ring[0])]
    warm = model.precompile(data_shapes=spec[0], label_shapes=spec[1],
                            eval_metric=metric, kvstore=kvstore)
    precompile_s = time.perf_counter() - t_pre
    _, plan = memory.largest_plan(labels=warm["labels"])
    plan_bytes = memory.program_step_bytes(plan) if plan else None
    item("weights_and_precompile")

    def probe():
        stats = mx.utils.compile_stats()
        return {"loss": float(metric.get()[1]), "compiles": stats["compiles"],
                "misses": stats["misses"], "steps": spy.calls}

    tracer = Tracer(jax) if args.trace else None
    clock = epochs.EpochClock(
        time.perf_counter, args.seconds,
        min_epochs=TRACE_TO if tracer else 1, probe=probe,
        hooks={TRACE_FROM: tracer.start, TRACE_TO: tracer.stop}
        if tracer else None,
        span=lambda name: jax.profiler.TraceAnnotation("bench." + name))
    try:
        try:
            model.fit(feed.iter, eval_metric=metric, kvstore=kvstore,
                      batch_size=feed.batch_rows, epoch_end_callback=clock)
        except epochs.StopFit:
            pass
        finally:
            clock.close()
            if tracer and tracer.active:   # fit raised in a traced epoch
                tracer.stop()
        t_end = time.perf_counter()
        rows = clock.rows
        setup["fit_start_and_warmup_epoch"] = rows[0]["exit"] - last[0]
        setup_s = rows[0]["exit"] - T0
        say({"setup_s": setup_s, "setup_items": setup})
        seconds = epochs.epoch_seconds(rows)
        rates = epochs.epoch_rates(rows, samples_per_epoch, chips)
        window_s = epochs.window_seconds(rows)
        say({"warmup_loss": rows[0]["loss"], "warmup_steps": rows[0]["steps"],
             "window_seconds": window_s,
             "window_samples": samples_per_epoch * len(seconds), "epochs": [
            {"epoch": i + 1, "start": rows[i]["exit"] - T0,
             "end": rows[i + 1]["entry"] - T0, "seconds": seconds[i],
             "samples_per_s_per_chip": rates[i], "loss": rows[i + 1]["loss"]}
            for i in range(len(seconds))]})

        # this runtime keeps a program's temporaries in a reservation
        # apart from the buffers (PERF.md section 2): both are on the chip
        # at once, so the chip's peak is their sum
        stats = [d.memory_stats() or {} for d in devices]
        peak_bytes = max(int(s.get("peak_bytes_in_use", 0))
                         + int(s.get("peak_bytes_reserved", 0))
                         for s in stats)
        reduced = None
        if tracer:
            found_pb = glob.glob(os.path.join(
                tracer.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if found_pb:
                reduced = trace_reduce.reduce(trace_reduce.load(found_pb[0]),
                                              feed.steps_per_epoch)
    finally:
        if tracer:
            shutil.rmtree(tracer.dir, ignore_errors=True)

    # -- correct / attempted / failed: all outside the window ---------------
    faults = []
    losses = [r["loss"] for r in rows]
    if not all(math.isfinite(v) for v in losses):
        faults.append(f"non-finite epoch loss: {losses}")
    elif not losses[-1] < losses[0]:
        faults.append(f"loss did not fall: warm-up {losses[0]}, last "
                      f"measured epoch {losses[-1]}")
    compiles_in_window = (rows[-1]["compiles"] - rows[0]["compiles"]) \
        + (rows[-1]["misses"] - rows[0]["misses"])
    if compiles_in_window:
        faults.append(f"{compiles_in_window} compile(s) or jit miss(es) "
                      "inside the window")
    programs = train_program_rows(mx.utils.compile_stats())
    if len(programs) != 1 or \
            next(iter(programs.values()))["programs"] != 1:
        faults.append(f"not exactly one train program: {programs}")
    faults += checks.placement_faults(spy, devices, platform)
    if chips > 1:
        faults += checks.replica_faults(spy, devices)
    sample_rows = feed.check_rows(int(config["reference_rows"]))
    ref_err = checks.reference_error(
        mx, model, symbol, config, found["config_path"], sample_rows,
        devices[0], compute_dtype)
    if not ref_err <= float(config["reference_tolerance"]):
        faults.append(f"logits differ from the float32 reference by "
                      f"{ref_err} (relative L2), tolerance "
                      f"{config['reference_tolerance']}")
    attempted = rows[-1]["steps"] - rows[0]["steps"]
    bad_epochs = sum(not math.isfinite(v) for v in losses[1:])
    failed = spy.raised + bad_epochs * feed.steps_per_epoch
    # the scope of every instruction of the train program, for the metric
    # files that read device time by scope: from the executable that is
    # already warm, after the window and after the count of train programs
    # above, so that it adds nothing to what decides ``correct``
    t_hlo = time.perf_counter()
    text, no_text = checks.train_program_text(spy) if args.trace \
        else (None, "read with --trace 1 only")
    hlo_scopes = scopes.hlo_scopes(text) if text else None
    hlo_text_s = time.perf_counter() - t_hlo
    say({"memory_stats_chip0": stats[0]})
    say({"checks": {"reference_relative_error": ref_err,
                    "compiles_in_window": compiles_in_window,
                    "train_programs": sorted(programs), "faults": faults,
                    "hlo_text_s": hlo_text_s,
                    "hlo_instructions": len(hlo_scopes or ()),
                    "hlo_text_not_read": no_text,
                    "after_window_s": time.perf_counter() - t_end}})

    run = {
        "rows": rows, "epoch_seconds": seconds, "epoch_rates": rates,
        "window_seconds": window_s,
        "samples_per_epoch": samples_per_epoch, "chips": chips,
        "config": config, "per_chip_batch": int(config["per_chip_batch"]),
        "steps_per_epoch": feed.steps_per_epoch,
        "setup_s": setup_s, "peak_bytes": peak_bytes,
        "precompile_s": precompile_s, "plan_bytes": plan_bytes,
        "compiles_in_window": compiles_in_window,
        "flops_per_sample": flops.train_flops_per_sample(
            config["flops_per_sample"]["layers"]),
        "peak": peak, "trace": reduced, "hlo_scopes": hlo_scopes,
        "traced_epochs": list(range(TRACE_FROM, TRACE_TO)),   # 0-based
    }
    group, folder = ("per_layer", "layer_metrics") if args.trace \
        else ("end_to_end", "end_to_end")
    metrics = {}
    for entry in catalog.metrics_for(bench, group, cell["name"]):
        value = catalog.load_metric(folder, entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": peak_bytes}
    result = {"correct": not faults, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    # every number compared beside its limit: last in the result's line
    # and the last lines on standard error
    result["compared"] = {
        "reference_relative_error": {
            "value": ref_err, "limit": float(config["reference_tolerance"])},
        "last_loss_over_warmup_loss": {
            "value": losses[-1] / losses[0] if losses[0] else float("nan"),
            "limit": 1.0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "train_programs": {"value": len(programs), "limit": 1},
        "steps_failed": {"value": failed, "limit": 0},
        "other_faults": {"value": len(faults), "limit": 0},
    }
    for fault in faults:
        print(f"benchmark: fault: {fault}", file=sys.stderr)
    for name, pair in result["compared"].items():
        print(f"benchmark: compared {name} = {pair['value']} "
              f"(limit {pair['limit']})", file=sys.stderr)
    sys.stderr.flush()
    say(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
