"""Headline benchmark: ResNet-50 ImageNet training throughput per chip.

Prints ONE JSON line:
  {"metric": "resnet50_imagenet_train_images_per_sec_per_chip",
   "value": N, "unit": "images/sec", "vs_baseline": N}

Baseline: the reference (dawdle/mxnet v0.5) publishes no ResNet-50 number
(the model postdates it). The closest published anchor in the same
FLOP class (~4 GFLOPs/image) is Inception-BN at 97 img/s on 1x GTX 980 with
cuDNN v3 (reference example/imagenet/README.md:40, mirrored in BASELINE.md),
so vs_baseline = value / 97.0 — "how much faster than the reference's best
same-class single-device training throughput".

Method: fused train step (forward + backward + SGD-momentum update in one
donated XLA program), NHWC activations (channels on the MXU lane dimension;
weights stay OIHW for checkpoint parity), bf16 compute / f32 master params,
custom-VJP fused BatchNorm(+add)+ReLU kernels (executor fusion passes),
1x1 convs as channel matmuls, synthetic on-device data (the input pipeline
is benchmarked separately; the reference's numbers are likewise decode-bound
only beyond 3000 img/s, README:5). The timed region is an in-device
fori_loop; the per-step cost is the slope between a short and a long run,
each closed by a scalar readback.

The default mode needs an accelerator whose device_kind is in
mxnet_tpu.telemetry.mfu.DEVICE_PEAKS, and exits non-zero on an unknown
device, a failed measurement, or a non-positive or non-finite step time.
It does not run the system (the step is hand-built over _build_graph_fn,
not FeedForward.fit): ROADMAP A1 replaces it; chip_smoke.py is the proof
that fit runs on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _data_shape(batch_size, layout):
    return (batch_size, 224, 224, 3) if layout == "NHWC" else \
        (batch_size, 3, 224, 224)


def _publish(result, filename, smoke=False):
    """Single exit for every bench headline (ISSUE 20): the per-bench
    JSON artifact (full runs), a kind="bench" RunRecord in the cross-run
    ledger when MXNET_TPU_LEDGER_DIR is set, and the combined
    BENCH_LEDGER_r20.json trajectory. telemetry.ledger.publish_bench is
    the one writer — no hand-rolled per-bench dumps (mxlint MX316)."""
    from mxnet_tpu.telemetry import ledger

    out = ledger.publish_bench(
        result, filename=filename,
        bench_dir=os.path.dirname(os.path.abspath(__file__)), smoke=smoke)
    if out["bench_path"]:
        print(f"wrote {out['bench_path']}", file=sys.stderr)
    return out


def _checked_slope(t0, t1, t2, k1, k2, what):
    """Per-iteration seconds from a k1-iteration run (t0..t1) and a
    k2-iteration run (t1..t2). The estimator is unguarded arithmetic: a
    stall in the short run makes it zero or negative, which once reached
    the record as -129 img/s with rc=0. That is a failed measurement."""
    per_iter = ((t2 - t1) - (t1 - t0)) / (k2 - k1)
    if not (per_iter > 0 and np.isfinite(per_iter)):
        raise RuntimeError(
            f"{what}: slope timing gave {per_iter * 1e3:.3f} ms/iter "
            f"(short run {t1 - t0:.3f}s for {k1} iters, long run "
            f"{t2 - t1:.3f}s for {k2}); measurement failed")
    return per_iter


def measured_matmul_peak_tflops(n=8192, iters=16, samples=3):
    """This chip's achievable bf16 matmul rate, measured through the same
    timing path as the headline number. Slope method: the loop runs
    in-device via fori_loop and the per-iter cost is the slope between a
    short and a long run, cancelling constant dispatch+fence overhead.
    n=8192 (1.1 TFLOP/iter) keeps the timed region hundreds of ms; the
    median of several slope samples is reported. A non-positive slope (a
    stall in the short run) raises rather than print a negative rate."""
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)

    @jax.jit
    def run(a, k):
        def body(i, x):
            return (jax.lax.dot_general(
                x, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * 1e-3).astype(jnp.bfloat16)
        return jax.lax.fori_loop(0, k, body, a)

    k1, k2 = iters, iters * 4
    a = run(a, k1)  # compile + warm
    float(jnp.sum(a))
    rates = []
    for _ in range(samples):
        t0 = time.perf_counter()
        a = run(a, k1)
        float(jnp.sum(a))
        t1 = time.perf_counter()
        a = run(a, k2)
        float(jnp.sum(a))
        t2 = time.perf_counter()
        per_iter = _checked_slope(t0, t1, t2, k1, k2, "matmul peak")
        rates.append(2 * n ** 3 / per_iter / 1e12)
    rates.sort()
    return rates[len(rates) // 2]


def build_train_step(batch_size, lr=0.1, momentum=0.9, layout="NHWC",
                     model="resnet50"):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.executor import _build_graph_fn
    from mxnet_tpu.models import resnet50
    from mxnet_tpu.models.inception import inception_bn

    if model == "inception_bn":
        # the BASELINE anchor architecture itself (97 img/s, 1x GTX 980,
        # example/imagenet/README.md:40) — same net, our chip
        sym = inception_bn(num_classes=1000, layout=layout)
    else:
        sym = resnet50(num_classes=1000, layout=layout)
    input_shapes = {"data": _data_shape(batch_size, layout),
                    "softmax_label": (batch_size,)}
    arg_shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()

    rng = np.random.RandomState(0)
    params = {}
    for name, shape in zip(arg_names, arg_shapes):
        if name in input_shapes:
            continue
        scale = 0.1 if name.endswith(("gamma", "bias", "beta")) else \
            float(np.sqrt(2.0 / max(1, int(np.prod(shape[1:])))))
        if name.endswith("gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("beta", "bias")):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jnp.asarray((rng.randn(*shape) * scale).astype(np.float32))
    aux = {name: (jnp.ones(s, jnp.float32) if name.endswith("var")
                  else jnp.zeros(s, jnp.float32))
           for name, s in zip(aux_names, aux_shapes)}
    moms = {k: jnp.zeros_like(v) for k, v in params.items()}

    graph_fn = _build_graph_fn(sym, is_train=True)
    zero_key = jnp.zeros((2,), jnp.uint32)
    rescale = 1.0 / batch_size

    def step(params, moms, aux, data, label):
        def loss_fn(p):
            p_c = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
            outs, new_aux = graph_fn(
                {**p_c, "data": data.astype(jnp.bfloat16), "softmax_label": label},
                aux, zero_key)
            return jnp.sum(outs[0].astype(jnp.float32)), new_aux

        grads, new_aux = jax.grad(loss_fn, has_aux=True)(params)
        new_moms = {k: momentum * moms[k] + grads[k] * rescale for k in params}
        new_params = {k: params[k] - lr * new_moms[k] for k in params}
        return new_params, new_moms, new_aux

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    return jitted, params, moms, aux


def build_resnet50_train_step(batch_size, lr=0.1, momentum=0.9,
                              layout="NHWC"):
    """Back-compat alias (tools/bench_roofline.py imports this name)."""
    return build_train_step(batch_size, lr=lr, momentum=momentum,
                            layout=layout, model="resnet50")


def ensure_recordio(path, n=1024, size=256, seed=0):
    """Synthetic ImageNet-like RecordIO shard: n JPEG records of size²
    smooth-gradient images (JPEG-compressible, like the reference's test
    data), cached across runs."""
    import os

    if os.path.exists(path):
        return path
    from mxnet_tpu import recordio as rio

    rng = np.random.RandomState(seed)
    w = rio.MXRecordIO(path + ".tmp", "w")
    yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    for i in range(n):
        f = rng.uniform(0.5, 4.0, 3)
        ph = rng.uniform(0, np.pi, 3)
        img = np.stack([
            127 + 120 * np.sin(2 * np.pi * f[c] * (yy + xx) / size + ph[c])
            for c in range(3)], axis=-1).astype(np.uint8)
        w.write(rio.pack_img(rio.IRHeader(0, float(i % 1000), i, 0), img,
                             quality=90, img_fmt=".jpg"))
    w.close()
    os.rename(path + ".tmp", path)
    return path


def _make_iter(args, layout, output_dtype="float32"):
    from mxnet_tpu import io as mio

    path = ensure_recordio(args.recordio, n=args.num_images)
    return mio.ImageRecordIter(
        path_imgrec=path, data_shape=(3, 224, 224),
        batch_size=args.batch_size, shuffle=True, rand_crop=True,
        rand_mirror=True, resize=256, layout=layout,
        prefetch_buffer=8, seed=7, output_dtype=output_dtype)


def run_pipeline_bench(args):
    """Input-pipeline-only throughput (no device in the loop): RecordIO read
    -> JPEG decode -> resize-short 256 -> rand-crop 224 -> mirror -> batch.
    Reference anchor: 3000 img/s from HDD on a multicore Xeon
    (example/imagenet/README.md:5); this host has os.cpu_count() cores and
    the native pipeline scales per-core."""
    import os

    it = _make_iter(args, args.layout)
    n_batches = 0
    for _ in it:  # epoch 1: warm page cache / thread spin-up
        n_batches += 1
    t0 = time.perf_counter()
    it.reset()
    for _ in it:
        pass
    dt = time.perf_counter() - t0
    ips = n_batches * args.batch_size / dt
    print(json.dumps({
        "metric": "imagerecorditer_pipeline_images_per_sec",
        "value": round(ips, 2), "unit": "images/sec",
        "host_cores": os.cpu_count(),
        "native": it._native is not None,
        "vs_baseline": round(ips / 3000.0, 3),
    }))


def run_io_bench(args):
    """End-to-end FeedForward.fit fed by ImageRecordIter on the real chip.
    Reports the steady-state epoch throughput (epochs after the first, so
    compile time is excluded). With prefetch overlap this should approach
    min(pipeline img/s, transfer img/s, synthetic train img/s).

    Batches cross to the device as raw uint8 (output_dtype='uint8', the
    standard TPU input path — 4x less wire traffic); FeedForward's
    compute_dtype casts them to bf16 in-graph. The JSON carries the host's
    core count and the feed-only rate so the result is interpretable."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet50

    # feed-only throughput first (drain one pass, no training): the
    # overlap arithmetic needs max(feed, compute) measured in the same
    # process — an overlapped epoch should cost ~max of the two,
    # a serial one their sum (see _AsyncDeviceFeed / tests/test_overlap.py).
    # Iterator construction stays OUTSIDE the clock: _make_iter may
    # synthesize the RecordIO shard on a fresh host (ensure_recordio), and
    # timing that would understate the decode rate by an order of magnitude.
    feed_iter = _make_iter(args, args.layout, output_dtype="uint8")
    t0 = time.perf_counter()
    n_feed = sum(b.data[0].shape[0] for b in feed_iter)
    feed_ips = n_feed / (time.perf_counter() - t0)
    print(f"feed-only: {feed_ips:.0f} img/s", file=sys.stderr)

    it = _make_iter(args, args.layout, output_dtype="uint8")
    model = mx.model.FeedForward(
        resnet50(num_classes=1000, layout=args.layout), ctx=mx.tpu(),
        num_epoch=args.epochs, learning_rate=0.01, momentum=0.9,
        initializer=mx.init.Xavier(), compute_dtype=jnp.bfloat16)
    marks = [time.perf_counter()]

    def at_epoch_end(epoch, symbol, arg_params, aux_params):
        marks.append(time.perf_counter())

    model.fit(it, epoch_end_callback=at_epoch_end,
              batch_size=args.batch_size)
    import os

    n_batches = (args.num_images + args.batch_size - 1) // args.batch_size
    steady = marks[2:]  # skip epoch 1 (compile) boundary
    dt = (steady[-1] - marks[1]) / (len(steady)) if steady else float("nan")
    ips = n_batches * args.batch_size / dt
    print(json.dumps({
        "metric": "resnet50_io_fed_fit_images_per_sec_per_chip",
        "value": round(ips, 2), "unit": "images/sec",
        "epochs_timed": len(steady),
        "host_cores": os.cpu_count(),
        "transfer": "uint8",
        "feed_only_img_s": round(feed_ips, 1),
        "overlap_explained": (
            "overlapped epoch ~= max(feed, compute): io-fed value should "
            "approach min(feed_only_img_s, synthetic train img/s); a "
            "serial loop would sit near their harmonic combination "
            "1/(1/feed + 1/compute)"),
        "vs_baseline": round(ips / 97.0, 3),
    }))


def _compile_bench_symbol():
    """A conv+BN net with a nontrivial XLA compile (the persistent cache's
    win scales with compile time; a bare MLP compiles too fast to measure)."""
    from mxnet_tpu import symbol as sym

    net = sym.Variable("data")
    for i, ch in enumerate((32, 64, 64)):
        net = sym.Convolution(data=net, name=f"conv{i}", num_filter=ch,
                              kernel=(3, 3), pad=(1, 1))
        net = sym.BatchNorm(data=net, name=f"bn{i}")
        net = sym.Activation(data=net, name=f"relu{i}", act_type="relu")
        if i < 2:
            net = sym.Pooling(data=net, name=f"pool{i}", kernel=(2, 2),
                              stride=(2, 2), pool_type="max")
    net = sym.Flatten(data=net, name="flat")
    net = sym.FullyConnected(data=net, name="fc1", num_hidden=64)
    net = sym.Activation(data=net, name="fcrelu", act_type="relu")
    net = sym.FullyConnected(data=net, name="fc2", num_hidden=10)
    return sym.SoftmaxOutput(data=net, name="softmax")


def run_compile_bench_child(args):
    """One measured process start: import -> build -> (optional AOT
    precompile) -> first train step. Prints one JSON line; the parent
    (run_compile_bench) aggregates cold/warm/AOT runs. The persistent
    cache dir arrives via JAX_COMPILATION_CACHE_DIR, which JAX reads
    itself (utils/compile.py sets nothing over it)."""
    t0 = time.perf_counter()
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.utils import compile as compile_mod

    import_s = time.perf_counter() - t0
    bs = args.batch_size
    rng = np.random.RandomState(0)
    X = rng.randn(bs, 3, 32, 32).astype(np.float32)
    y = rng.randint(0, 10, (bs,)).astype(np.float32)
    model = mx.FeedForward(_compile_bench_symbol(),
                           ctx=mx.cpu() if jax.default_backend() == "cpu"
                           else mx.tpu(),
                           num_epoch=1, learning_rate=0.1)
    marks = []
    first_step_cb = lambda p: marks.append(time.perf_counter())  # noqa: E731
    precompile_s = None
    if args.compile_bench_child == "aot":
        t_pre = time.perf_counter()
        # batch_end_callback must match fit()'s (it un-fuses the device
        # metric, changing the compiled program — a mismatch orphans the
        # whole warmup; fit warns when that happens)
        model.precompile(
            data_shapes={"data": (bs, 3, 32, 32)},
            label_shapes={"softmax_label": (bs,)},
            batch_end_callback=first_step_cb)
        precompile_s = time.perf_counter() - t_pre
    model.fit(X, y, batch_size=bs, batch_end_callback=first_step_cb)
    stats = compile_mod.compile_stats()
    print(json.dumps({
        "import_s": round(import_s, 3),
        "time_to_first_step_s": round(marks[0] - t0, 3),
        "first_step_after_setup_s": round(
            marks[0] - t0 - import_s - (precompile_s or 0.0), 3),
        "precompile_s": (round(precompile_s, 3)
                         if precompile_s is not None else None),
        "compiles": stats["compiles"],
        "compile_seconds": round(stats["compile_seconds"], 3),
        "persistent_cache_hits": stats["persistent_cache_hits"],
        "persistent_cache_saved_s": round(
            stats["persistent_cache_saved_seconds"], 3),
    }))


def run_compile_bench(args):
    """Cold-start vs warm-start (persistent compilation cache) time-to-
    first-step, plus AOT-warmup wall time — each in a fresh subprocess so
    every run pays real process start. Emits BENCH_COMPILE_r07.json."""
    import shutil
    import subprocess
    import tempfile

    base = tempfile.mkdtemp(prefix="mxtpu_compile_bench_")

    def child(mode, cache_dir):
        env = {**os.environ,
               "JAX_COMPILATION_CACHE_DIR": cache_dir,
               "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
        cmd = [sys.executable, os.path.abspath(__file__),
               "--compile-bench-child", mode,
               "--batch-size", str(args.batch_size)]
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=1200)
        if r.returncode != 0:
            print(r.stdout + r.stderr, file=sys.stderr)
            raise RuntimeError(f"compile-bench child ({mode}) failed")
        return json.loads(r.stdout.strip().splitlines()[-1])

    cache = os.path.join(base, "cache")
    cold = child("plain", cache)          # empty cache: full XLA compiles
    warm = child("plain", cache)          # same cache: deserialize from disk
    aot_cache = os.path.join(base, "aot_cache")
    aot = child("aot", aot_cache)         # fresh cache + AOT warmup up front
    aot_warm = child("aot", aot_cache)    # warm cache + AOT: best case
    entries = len([f for f in os.listdir(cache) if f.endswith("-cache")]) \
        if os.path.isdir(cache) else 0
    result = {
        "metric": "compile_bench_time_to_first_step_sec",
        "unit": "seconds",
        "batch_size": args.batch_size,
        "cold_start_s": cold["time_to_first_step_s"],
        "warm_start_s": warm["time_to_first_step_s"],
        "warm_speedup": round(cold["time_to_first_step_s"]
                              / max(warm["time_to_first_step_s"], 1e-9), 2),
        "warm_persistent_cache_hits": warm["persistent_cache_hits"],
        "warm_compile_saved_s": warm["persistent_cache_saved_s"],
        "aot_precompile_s": aot["precompile_s"],
        "aot_first_step_after_setup_s": aot["first_step_after_setup_s"],
        "aot_warm_precompile_s": aot_warm["precompile_s"],
        "aot_warm_first_step_after_setup_s":
            aot_warm["first_step_after_setup_s"],
        "cold_first_step_after_setup_s": cold["first_step_after_setup_s"],
        "cache_entries": entries,
        "detail": {"cold": cold, "warm": warm, "aot": aot,
                   "aot_warm": aot_warm},
    }
    print(json.dumps(result))
    _publish(result, "BENCH_COMPILE_r07.json")
    shutil.rmtree(base, ignore_errors=True)


def run_comm_bench(args):
    """Gradient-sync wire bytes + step time per compression mode on the
    8-virtual-device CPU mesh (the comm subsystem's acceptance rig: real
    chips aren't needed to measure the collective plan — the compiled
    HLO's collective instructions ARE the wire). For each mode the same
    dp-8 MLP train step is built via parallel.make_data_parallel_step,
    its HLO collective-byte table extracted (comm.hlo_collective_table),
    cross-checked against the closed-form plan (comm.allreduce_plan), and
    timed. Emits one JSON line; full runs write BENCH_COMM_r08.json."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import comm
    from mxnet_tpu import parallel as par

    ndev = 8
    devs = jax.devices()
    if len(devs) < ndev:
        print(json.dumps({"metric": "comm_bench_int8_wire_reduction_vs_fp32",
                          "value": 0, "unit": "x", "vs_baseline": 0,
                          "error": f"need {ndev} devices, have {len(devs)}"}))
        return
    mesh = par.make_mesh(dp=ndev, devices=devs[:ndev])
    smoke = args.smoke
    dim, hidden, classes = (64, 64, 8) if smoke else (512, 1024, 64)
    batch = 64 if smoke else 256
    steps = 3 if smoke else 30
    rng = np.random.RandomState(0)
    params0 = {
        "w1": (rng.randn(dim, hidden) * 0.05).astype(np.float32),
        "b1": np.zeros(hidden, np.float32),
        "w2": (rng.randn(hidden, classes) * 0.05).astype(np.float32),
        "b2": np.zeros(classes, np.float32),
    }
    num_elems = sum(v.size for v in params0.values())

    def loss_fn(params, data):
        h = jnp.tanh(data["x"] @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            logp, data["y"][:, None], axis=1))

    lr = 0.1

    def update_fn(params, opt_state, grads):
        return {k: params[k] - lr * grads[k] for k in params}, opt_state

    x = rng.randn(batch, dim).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.int32)
    data = par.shard_batch({"x": x, "y": y}, mesh)

    modes = {}
    for mode in (None, "bf16", "int8", "twobit"):
        spec = comm.CompressionSpec.resolve(mode)
        step = par.make_data_parallel_step(loss_fn, update_fn, mesh,
                                           donate=False, compression=mode)
        params = par.replicate_params(
            {k: jnp.asarray(v) for k, v in params0.items()}, mesh)
        call = (params, {}, data)
        if spec is not None and spec.error_feedback:
            resid = jax.device_put(
                comm.init_error_feedback(params, spec, ndev),
                NamedSharding(mesh, P("dp")))
            call += (resid,)
        hlo = step.lower(*call).compile().as_text()
        table = comm.hlo_collective_table(hlo, default_group_size=ndev)
        hlo_wire = sum(r["wire_bytes"] for r in table)
        plan = comm.allreduce_plan(num_elems, ndev, mode)
        res = step(*call)  # warm the dispatch path
        jax.block_until_ready(res[0])
        state = call
        t0 = _time.perf_counter()
        for _ in range(steps):
            res = step(state[0], state[1], data, *state[3:])
            state = (res[0], res[1], data) + tuple(res[3:])
        jax.block_until_ready(res[0])
        dt = (_time.perf_counter() - t0) / steps
        modes[mode or "none"] = {
            "hlo_wire_bytes_per_step": round(hlo_wire, 1),
            "hlo_collectives": table,
            "plan_wire_bytes_per_step": round(plan["wire_bytes"], 1),
            "plan_ratio_vs_fp32": round(plan["ratio"], 2),
            "step_ms": round(dt * 1e3, 3),
            "final_loss": round(float(np.asarray(res[2])), 5),
        }
    fp32_wire = modes["none"]["hlo_wire_bytes_per_step"]
    for m in modes.values():
        m["hlo_ratio_vs_fp32"] = round(
            fp32_wire / m["hlo_wire_bytes_per_step"], 2) \
            if m["hlo_wire_bytes_per_step"] else None
    ratio = modes["int8"]["hlo_ratio_vs_fp32"] or 0.0
    result = {
        "metric": "comm_bench_int8_wire_reduction_vs_fp32",
        "value": ratio,
        "unit": "x",
        # fp32 IS the baseline: vs_baseline == the reduction factor
        "vs_baseline": ratio,
        "axis_size": ndev,
        "param_elements": num_elems,
        "smoke": bool(smoke),
        "modes": modes,
        "notes": (
            "hlo_* numbers are from the compiled CPU-mesh HLO: int8/uint8 "
            "payloads are faithful, but the CPU backend's float "
            "normalization upcasts bf16 collectives to f32, so bf16 (and "
            "twobit's bf16 all-gather stage) read high here — plan_* is "
            "authoritative for those; on TPU bf16 stays bf16. step_ms is "
            "CPU compute-bound (quantization arithmetic costs more than "
            "the loopback 'wire' saves); the wire-byte cut is the number "
            "that transfers to bandwidth-bound pods."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_COMM_r08.json", smoke=smoke)


def run_overlap_bench(args):
    """Comm/compute overlap: fused single-bucket sync vs the overlapped
    per-bucket schedule (comm/overlap.py), measured two ways.

    **Mesh part** (dp-8 CPU mesh, int8): builds the same MLP train step
    with the fused allreduce and with ``overlap=`` bucketing, and proves
    the SCHEDULE — the compiled HLO must contain one independent
    reduce-scatter/all-gather pair per bucket (≥2, not one fused pair)
    and the per-bucket closed-form plans must sum exactly to the fused
    plan. Loopback step times are reported but are NOT the overlap
    headline: the CPU backend lowers collectives as synchronous thunks
    and its 'wire' is memcpy (CPU work), so there is no idle wire
    latency for XLA to hide here — that schedule benefit needs real
    interconnect (same caveat class as BENCH_COMM's bf16 note).

    **Stale-sync part** (the timed headline): single-process dist_async
    with an EMULATED cross-host RTT (an idle sleep on the push_pull
    round trip — loopback TCP has none; real parameter hosts do).
    Serial baseline: compute + push_pull every step. Overlapped:
    ``push_pull_stale`` pipelines the round trip one step behind
    compute. The headline speedup is serial/pipelined step time, and the
    ``comm_overlap_efficiency`` gauge (comm.overlap_efficiency) is
    computed from measured compute / comm / pipelined-step times and
    exported through the telemetry hub. Emits one JSON line; full runs
    write BENCH_OVERLAP_r11.json."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import comm, telemetry
    from mxnet_tpu import parallel as par
    from mxnet_tpu.kvstore_async import AsyncKVStore

    smoke = args.smoke
    ndev = 8
    devs = jax.devices()
    if len(devs) < ndev:
        print(json.dumps({"metric": "overlap_bench_stale_sync_speedup",
                          "value": 0, "unit": "x", "vs_baseline": 0,
                          "error": f"need {ndev} devices, have {len(devs)}"}))
        return

    # -- mesh part: schedule structure + exact plan arithmetic -----------------
    mesh = par.make_mesh(dp=ndev, devices=devs[:ndev])
    layers, dim = (3, 128) if smoke else (4, 512)
    batch = 64 if smoke else 128
    steps = 3 if smoke else 20
    rng = np.random.RandomState(0)
    params0 = {}
    for i in range(layers):
        params0[f"w{i:02d}"] = (rng.randn(dim, dim) * 0.05).astype(np.float32)
        params0[f"b{i:02d}"] = np.zeros(dim, np.float32)
    num_elems = sum(v.size for v in params0.values())
    # cap at ~1/3 of the f32 bytes -> >=3 slabs, >=3 independent pairs
    cap = max(num_elems * 4 // 3, 1 << 14)

    def loss_fn(params, data):
        h = data["x"]
        for i in range(layers):
            h = jnp.tanh(h @ params[f"w{i:02d}"] + params[f"b{i:02d}"])
        return jnp.mean((h - data["y"]) ** 2)

    def update_fn(params, opt_state, grads):
        return {k: params[k] - 0.01 * grads[k] for k in params}, opt_state

    x = rng.randn(batch, dim).astype(np.float32)
    y = rng.randn(batch, dim).astype(np.float32)
    data = par.shard_batch({"x": x, "y": y}, mesh)
    spec = comm.CompressionSpec.resolve("int8")
    params = par.replicate_params(
        {k: jnp.asarray(v) for k, v in params0.items()}, mesh)

    def timed_steps(step, call):
        res = step(*call)
        jax.block_until_ready(res[0])
        state = call
        t0 = _time.perf_counter()
        for _ in range(steps):
            res = step(state[0], state[1], data, *state[3:])
            state = (res[0], res[1], data) + tuple(res[3:])
        jax.block_until_ready(res[0])
        return (_time.perf_counter() - t0) / steps, res

    step_f = par.make_data_parallel_step(loss_fn, update_fn, mesh,
                                         donate=False, compression="int8")
    resid_f = jax.device_put(comm.init_error_feedback(params, spec, ndev),
                             NamedSharding(mesh, P("dp")))
    t_fused, res_f = timed_steps(step_f, (params, {}, data, resid_f))

    hlo_f = step_f.lower(params, {}, data, resid_f).compile().as_text()
    table_f = comm.hlo_collective_table(hlo_f, default_group_size=ndev)

    def _op_counts(table):
        a2a = sum(r["count"] for r in table if "all-to-all" in r["op"])
        ag = sum(r["count"] for r in table if "all-gather" in r["op"])
        return a2a, ag

    f_a2a, f_ag = _op_counts(table_f)

    step_o = par.make_data_parallel_step(loss_fn, update_fn, mesh,
                                         donate=False, compression="int8",
                                         overlap=cap)
    oplan = comm.plan_overlap({k: v.shape for k, v in params0.items()},
                              spec, ndev, max_bytes=cap)
    resid_o = {k: jax.device_put(v, NamedSharding(mesh, P("dp")))
               for k, v in comm.init_overlap_residuals(oplan).items()}
    call_o = (params, {}, data, resid_o)
    hlo = step_o.lower(*call_o).compile().as_text()
    table = comm.hlo_collective_table(hlo, default_group_size=ndev)
    n_a2a, n_ag = _op_counts(table)
    t_over, res_o = timed_steps(step_o, call_o)
    wplan = oplan.wire_plan()

    mesh_part = {
        "num_buckets": oplan.num_buckets,
        # int8 payloads are (values, scales) dicts: 2 wire arrays per
        # collective pair — the split is proven by per-bucket multiplicity
        # over the fused counts, 1 independent pair group per bucket
        "hlo_reduce_scatter_ops": n_a2a,
        "hlo_all_gather_ops": n_ag,
        "hlo_reduce_scatter_ops_fused": f_a2a,
        "hlo_all_gather_ops_fused": f_ag,
        "hlo_independent_pairs": min(n_a2a // max(f_a2a, 1),
                                     n_ag // max(f_ag, 1)),
        "plan_wire_bytes": round(wplan["wire_bytes"], 1),
        "plan_matches_fused": wplan["matches_fused"],
        "fused_wire_bytes": round(wplan["fused_wire_bytes"], 1),
        "step_ms_fused": round(t_fused * 1e3, 3),
        "step_ms_overlapped": round(t_over * 1e3, 3),
        "loss_parity": abs(float(np.asarray(res_f[2]))
                           - float(np.asarray(res_o[2]))) < 1e-5,
    }

    # -- stale-sync part: the timed fused-vs-overlapped headline ---------------
    rtt = 0.040

    class _WireDelayed(AsyncKVStore):
        # emulated cross-host RTT: idle latency on the batch round trip
        # (time.sleep releases the GIL — genuinely hideable, like a NIC)
        def _call(self, *msg, **kw):
            if msg[0] in ("push_pull", "push_pull_enc"):
                _time.sleep(rtt)
            return super()._call(*msg, **kw)

    # sized so compute ~ comm (the regime where pipelining pays most:
    # serial = c + m, pipelined -> max(c, m))
    sdim = 256 if smoke else 512
    sbatch = 2048
    ssteps = 12 if smoke else 30
    W = {f"w{i}": (rng.randn(sdim, sdim) * 0.01).astype(np.float32)
         for i in range(2)}
    kv = _WireDelayed()
    try:
        for k, v in W.items():
            kv.init(k, mx.nd.NDArray(v))
        kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.01,
                                             rescale_grad=1.0))
        kv.set_gradient_compression("int8")

        @jax.jit
        def sstep(p, xb):
            def lf(q):
                h = xb
                for k in sorted(q):
                    h = jnp.tanh(h @ q[k])
                return jnp.mean(h ** 2)
            return jax.value_and_grad(lf)(p)

        xb = jnp.asarray(rng.randn(sbatch, sdim).astype(np.float32))
        ps = {k: jnp.asarray(v) for k, v in W.items()}
        loss, g = sstep(ps, xb)
        jax.block_until_ready(loss)
        t0 = _time.perf_counter()
        for _ in range(ssteps):
            loss, g = sstep(ps, xb)
            jax.block_until_ready(loss)
        t_compute = (_time.perf_counter() - t0) / ssteps
        gh = {k: np.asarray(v) for k, v in g.items()}
        pulled = kv.push_pull(gh)
        t0 = _time.perf_counter()
        for _ in range(ssteps):
            pulled = kv.push_pull(gh)
        t_comm = (_time.perf_counter() - t0) / ssteps

        def loop(pipelined):
            p = {k: jnp.asarray(pulled[k]) for k in W}
            t0 = _time.perf_counter()
            for _ in range(ssteps):
                loss, g = sstep(p, xb)
                jax.block_until_ready(loss)
                gh = {k: np.asarray(v) for k, v in g.items()}
                out = kv.push_pull_stale(gh) if pipelined \
                    else kv.push_pull(gh)
                p = {k: jnp.asarray(out[k]) for k in W}
            if pipelined:
                # drain INSIDE the clock: the last round's tail is part of
                # the pipelined schedule's honest cost
                kv.flush_stale(list(W))
            return (_time.perf_counter() - t0) / ssteps

        t_serial = loop(False)
        t_pipe = loop(True)
    finally:
        del kv
    speedup = t_serial / t_pipe
    # efficiency from self-consistent in-loop numbers: the serial loop IS
    # compute + comm by construction, so its excess over the measured
    # compute step prices the per-step comm the pipeline had to hide
    # (the standalone round_trip_ms microbench is reported for context;
    # back-to-back round trips contend differently than in-loop ones)
    t_comm_inloop = max(t_serial - t_compute, 0.0)
    eff = comm.overlap_efficiency(t_pipe, t_compute, t_comm_inloop)
    telemetry.gauge("comm_overlap_efficiency", eff)

    # telemetry tax of the overlap accounting: push_pull_stale adds two
    # histogram observes + one span sub-record per step
    hub = telemetry.hub()
    reps = 10000
    t0 = _time.perf_counter()
    for _ in range(reps):
        hub.observe("bench_overlap_seconds", 0.001)
    observe_s = (_time.perf_counter() - t0) / reps
    overhead_pct = 3 * observe_s / t_pipe * 100.0

    result = {
        "metric": "overlap_bench_stale_sync_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        # the serial (fused, un-overlapped) schedule IS the baseline
        "vs_baseline": round(speedup, 3),
        "axis_size": ndev,
        "smoke": bool(smoke),
        "mesh": mesh_part,
        "stale_sync": {
            "emulated_rtt_ms": rtt * 1e3,
            "step_ms_compute": round(t_compute * 1e3, 3),
            "round_trip_ms": round(t_comm * 1e3, 3),
            "comm_ms_in_loop": round(t_comm_inloop * 1e3, 3),
            "step_ms_serial": round(t_serial * 1e3, 3),
            "step_ms_pipelined": round(t_pipe * 1e3, 3),
        },
        "overlap_efficiency": round(eff, 4),
        "telemetry_overhead_pct": round(overhead_pct, 4),
        "notes": (
            "stale_sync is the timed headline: push_pull_stale pipelines "
            "the parameter-host round trip (emulated cross-host RTT — "
            "loopback TCP has no idle wire latency; real pods do) one "
            "step behind compute, so the pipelined step approaches "
            "max(compute, comm) instead of their sum. overlap_efficiency "
            "= 1 - (step - max(compute, comm)) / min(compute, comm), "
            "exported as the comm_overlap_efficiency hub gauge. The mesh "
            "part proves the per-bucket schedule structurally (>=2 "
            "independent HLO pairs, per-bucket plans summing exactly to "
            "the fused plan, loss parity); its loopback step times carry "
            "no hideable wire latency (synchronous CPU collectives) and "
            "are reported for completeness only."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_OVERLAP_r11.json", smoke=smoke)


def run_telemetry_bench(args):
    """Telemetry-hub overhead on the 8-virtual-device CPU mesh.

    Three measurements: (1) microbenched hub op cost (emit / observe /
    counter — the operations the train loop performs per step); (2) a
    small dp-8 MLP ``fit()`` WITHOUT telemetry (baseline steps/s); (3) the
    same fit WITH ``telemetry=True`` (timeline + MFU, per-step output
    sync). The headline number is hub overhead as a percentage of the
    baseline step: (hub ops per step) x (measured op cost) / step time —
    the always-on cost of the instrumentation layer. The timeline's
    sync-per-step cost (opt-in, trades pipelining for attribution) is
    reported separately as ``timeline_overhead_pct``. Emits one JSON
    line; full runs write BENCH_TELEMETRY_r09.json."""
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    ndev = 8
    import jax

    if len(jax.devices()) < ndev:
        print(json.dumps({"metric": "telemetry_hub_overhead_pct_of_step",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {ndev} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (128, 256, 8) if smoke else (256, 1024, 32)
    batch, n_rows = (128, 1024) if smoke else (256, 4096)
    epochs = 3 if smoke else 6

    # -- (1) hub op microbench -------------------------------------------------
    hub = telemetry.reset()
    reps = 20000
    t0 = _time.perf_counter()
    for i in range(reps):
        hub.emit("bench", i=i)
    emit_ns = (_time.perf_counter() - t0) / reps * 1e9
    t0 = _time.perf_counter()
    for i in range(reps):
        hub.observe("bench_seconds", 0.001)
    observe_ns = (_time.perf_counter() - t0) / reps * 1e9
    t0 = _time.perf_counter()
    for i in range(reps):
        hub.counter("bench_total")
    counter_ns = (_time.perf_counter() - t0) / reps * 1e9

    # -- (2)/(3) fit with and without the timeline -----------------------------
    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(ndev)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.measured_peak_flops()  # cache the peak probe outside timing

    def timed_fit(tel):
        model = build()
        model.fit(X, y, batch_size=batch, telemetry=tel)  # warm programs
        t0 = _time.perf_counter()
        model.fit(X, y, batch_size=batch, telemetry=tel)
        return _time.perf_counter() - t0

    wall_off = timed_fit(None)
    wall_on = timed_fit(True)
    step_s_off = wall_off / (epochs * steps_per_epoch)
    step_s_on = wall_on / (epochs * steps_per_epoch)

    # per-step hub traffic in the instrumented loop: 1 span emit + ~6
    # histogram observes (phases, step, data-wait) + ~3 counters
    hub_ops_per_step = 10
    op_ns = (emit_ns + observe_ns + counter_ns) / 3.0
    hub_overhead_pct = hub_ops_per_step * op_ns / (step_s_off * 1e9) * 100.0
    timeline_overhead_pct = (wall_on - wall_off) / wall_off * 100.0

    result = {
        "metric": "telemetry_hub_overhead_pct_of_step",
        "value": round(hub_overhead_pct, 4),
        "unit": "%",
        "vs_baseline": round(hub_overhead_pct, 4),
        "emit_ns": round(emit_ns, 1),
        "observe_ns": round(observe_ns, 1),
        "counter_ns": round(counter_ns, 1),
        "hub_ops_per_step": hub_ops_per_step,
        "step_ms_baseline": round(step_s_off * 1e3, 3),
        "step_ms_telemetry": round(step_s_on * 1e3, 3),
        "timeline_overhead_pct": round(timeline_overhead_pct, 2),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "axis_size": ndev,
        "smoke": bool(smoke),
        "notes": (
            "hub overhead = measured per-op hub cost x ops/step vs the "
            "un-instrumented step (the always-on tax); "
            "timeline_overhead_pct additionally includes the OPT-IN "
            "per-step output sync (exact device-phase attribution trades "
            "feed/compute overlap) and one jaxpr FLOP trace per fit — on "
            "a CPU rig with ~ms steps that sync dominates; on a real pod "
            "with 100ms+ steps it vanishes."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_TELEMETRY_r09.json", smoke=smoke)


def run_trace_bench(args):
    """Flight-recorder + trace-propagation overhead on the dp-8 fused step.

    ISSUE 6 acceptance: the always-on black box (flight ring writes) plus
    the distributed-tracing identity work (rank/world stamping on every
    emit, span-id minting, trace-context capture for kvstore envelopes)
    must cost <2%% of a dp-8 step. Three measurements: (1) microbenched
    per-op costs for the operations tracing adds per step — one
    flight ``note_step`` ring append, one stamped ``emit`` through the
    recorder sink, one ``trace_ctx()`` capture, one span-id mint; (2) a
    dp-8 MLP ``fit()`` without telemetry (baseline steps/s); (3) the same
    fit with the timeline + flight recording on, reported separately
    (includes the opt-in per-step output sync). The headline number is
    (tracing ops per step) x (measured op cost) / baseline step time.
    Emits one JSON line; full runs write BENCH_TRACE_r10.json."""
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    ndev = 8
    import jax

    if len(jax.devices()) < ndev:
        print(json.dumps({"metric": "trace_flight_overhead_pct_of_step",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {ndev} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (128, 256, 8) if smoke else (256, 1024, 32)
    batch, n_rows = (128, 1024) if smoke else (256, 4096)
    epochs = 3 if smoke else 6

    # -- (1) tracing-op microbench --------------------------------------------
    telemetry.reset()
    telemetry.flight.reset()
    rec = telemetry.flight.recorder()
    reps = 20000
    t0 = _time.perf_counter()
    for i in range(reps):
        rec.note_step(0, i)
    note_ns = (_time.perf_counter() - t0) / reps * 1e9
    span_event = {"kind": "span", "name": "step", "epoch": 0, "step": 0,
                  "dur_ms": 1.0, "phases": [], "rank": 0}
    t0 = _time.perf_counter()
    for i in range(reps):
        rec.write_event(span_event)
    sink_ns = (_time.perf_counter() - t0) / reps * 1e9
    t0 = _time.perf_counter()
    for i in range(reps):
        telemetry.trace_ctx()
    ctx_ns = (_time.perf_counter() - t0) / reps * 1e9
    t0 = _time.perf_counter()
    for i in range(reps):
        telemetry.mint_span_id(0, 0, i)
    mint_ns = (_time.perf_counter() - t0) / reps * 1e9

    # -- (2)/(3) fit with and without tracing ---------------------------------
    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(ndev)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.measured_peak_flops()  # cache the peak probe outside timing

    def timed_fit(tel):
        model = build()
        model.fit(X, y, batch_size=batch, telemetry=tel)  # warm programs
        t0 = _time.perf_counter()
        model.fit(X, y, batch_size=batch, telemetry=tel)
        return _time.perf_counter() - t0

    wall_off = timed_fit(None)
    wall_on = timed_fit(True)
    step_s_off = wall_off / (epochs * steps_per_epoch)
    step_s_on = wall_on / (epochs * steps_per_epoch)

    # tracing ops per step: 1 flight ring append (lite mark or span
    # routing) + 1 stamped emit through the recorder sink + 1 trace-ctx
    # capture (kvstore envelope) + 1 span-id mint
    op_ns = note_ns + sink_ns + ctx_ns + mint_ns
    overhead_pct = op_ns / (step_s_off * 1e9) * 100.0
    traced_overhead_pct = (wall_on - wall_off) / wall_off * 100.0

    result = {
        "metric": "trace_flight_overhead_pct_of_step",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "vs_baseline": round(overhead_pct, 4),
        "note_ns": round(note_ns, 1),
        "sink_ns": round(sink_ns, 1),
        "ctx_ns": round(ctx_ns, 1),
        "mint_ns": round(mint_ns, 1),
        "step_ms_baseline": round(step_s_off * 1e3, 3),
        "step_ms_traced": round(step_s_on * 1e3, 3),
        "traced_overhead_pct": round(traced_overhead_pct, 2),
        "flight_steps_recorded": len(rec.snapshot()[0]),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "axis_size": ndev,
        "smoke": bool(smoke),
        "notes": (
            "headline = measured per-op cost of the tracing additions "
            "(flight ring append + rank-stamped emit through the recorder "
            "sink + trace-context capture + span-id mint) vs the "
            "un-instrumented dp-8 step — the always-on tax of ISSUE 6; "
            "step_ms_traced additionally includes the OPT-IN timeline "
            "with its per-step output sync (PR 5's attribution trade), "
            "dominated by sync on a CPU rig with ~ms steps."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_TRACE_r10.json", smoke=smoke)


def run_mem_bench(args):
    """Memory-observability overhead on the dp-8 fused step (ISSUE 9).

    The acceptance bound: the live-array ledger + phase-boundary sampler
    must cost <2%% of a dp-8 step. Three measurements: (1) microbenched
    per-op costs — one ledger add (weakref + locked dict insert, the
    NDArray-creation hook) and one phase-boundary sample (three gauge
    writes); (2) a dp-8 MLP ``fit()`` with telemetry but memory tracking
    OFF (baseline); (3) the same fit with tracking ON. The headline is
    (ledger+sampler ops per step) x (measured op cost) / baseline step —
    the deterministic always-on tax; the measured wall delta is reported
    separately (``tracked_overhead_pct``, noisy on ~ms CPU steps). Also
    reports the run's watermark and the number of registered program
    plans. Emits one JSON line; full runs write BENCH_MEM_r12.json."""
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import memory as mem_mod

    ndev = 8
    import jax

    if len(jax.devices()) < ndev:
        print(json.dumps({"metric": "memory_ledger_overhead_pct_of_step",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {ndev} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (128, 256, 8) if smoke else (256, 1024, 32)
    batch, n_rows = (128, 1024) if smoke else (256, 4096)
    epochs = 2 if smoke else 6

    # -- (1) ledger/sampler op microbench (smoke stays light: this runs
    # inside tier-1 as a CI guard, and suite-cumulative CPU load skews
    # later timing tests) ------------------------------------------------------
    telemetry.reset()
    led = mem_mod.ledger()
    led.clear()
    reps = 5000 if smoke else 20000
    # the least of five batches each: an operation's cost is what it takes
    # when nothing else holds the cores (inside tier-1 five other workers
    # do, and one batch's mean read 2.5 times the quiet one)
    add_ns = sample_ns = float("inf")
    for _ in range(5):
        # distinct buffers: the ledger dedups wrappers of one buffer onto
        # a refcount fast path, so measuring the full insert needs fresh
        # arrays
        probes = [mx.nd.zeros((8, 8)) for _ in range(reps // 5)]
        t0 = _time.perf_counter()
        for p in probes:
            led.add(p)
        add_ns = min(add_ns, (_time.perf_counter() - t0) / len(probes) * 1e9)
        del probes
        led.clear()
        t0 = _time.perf_counter()
        for _ in range(reps // 5):
            mem_mod.sample()
        sample_ns = min(sample_ns,
                        (_time.perf_counter() - t0) / (reps // 5) * 1e9)

    # -- (2)/(3) fit with tracking off vs on ----------------------------------
    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(ndev)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.measured_peak_flops()  # cache the peak probe outside timing

    def timed_fit(mem):
        tel = telemetry.TelemetryConfig(memory=mem)
        model = build()
        model.fit(X, y, batch_size=batch, telemetry=tel)  # warm programs
        t0 = _time.perf_counter()
        model.fit(X, y, batch_size=batch, telemetry=tel)
        return _time.perf_counter() - t0

    wall_off = timed_fit(False)
    wall_on = timed_fit(True)
    step_s_off = wall_off / (epochs * steps_per_epoch)
    step_s_on = wall_on / (epochs * steps_per_epoch)
    watermark = led.watermark_bytes

    # register the AOT program's static memory plan so the JSON also
    # reports the plans side of ISSUE 9 (precompile -> memory_analysis)
    build().precompile(data_shapes={"data": (batch, dim)},
                       label_shapes={"softmax_label": (batch,)})

    # ledger traffic per instrumented step: a handful of NDArray creations
    # (device-metric path creates ~2; host-metric paths more) + ~6 phase-
    # boundary samples (one per mark + span finish)
    ledger_ops_per_step = 4
    samples_per_step = 6
    overhead_pct = (ledger_ops_per_step * add_ns
                    + samples_per_step * sample_ns) \
        / (step_s_off * 1e9) * 100.0
    tracked_overhead_pct = (wall_on - wall_off) / wall_off * 100.0

    result = {
        "metric": "memory_ledger_overhead_pct_of_step",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "vs_baseline": round(overhead_pct, 4),
        "add_ns": round(add_ns, 1),
        "sample_ns": round(sample_ns, 1),
        "ledger_ops_per_step": ledger_ops_per_step,
        "samples_per_step": samples_per_step,
        "step_ms_baseline": round(step_s_off * 1e3, 3),
        "step_ms_tracked": round(step_s_on * 1e3, 3),
        "tracked_overhead_pct": round(tracked_overhead_pct, 2),
        "watermark_mb": round(watermark / (1 << 20), 3),
        "memory_plans_registered": len(mem_mod.plans()),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "axis_size": ndev,
        "smoke": bool(smoke),
        "notes": (
            "headline = measured per-op ledger/sampler cost x ops/step vs "
            "the tracking-off step (the always-on tax of ISSUE 9's "
            "live-array ledger); tracked_overhead_pct is the raw wall "
            "delta of the same fit with tracking on — noisy on a CPU rig "
            "with ~ms steps, representative only on real 100ms+ pod "
            "steps."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_MEM_r12.json", smoke=smoke)


def run_health_bench(args):
    """--health-bench: price the in-graph training-health stats engine
    (ISSUE 14) and measure its detectors.

    Three measurements on the 8-virtual-device CPU mesh:

      (1) **stats overhead** — the headline. Two identical dp-8 MLP fits,
          health off vs on; the deterministic cost model is the jaxpr
          FLOP delta of the two fused-step programs (the stats live in
          the same XLA program, so ``model_flops_per_step`` prices them
          exactly), reported as %% of the baseline step's FLOPs. The raw
          wall delta is reported separately (noisy on ~ms CPU steps).
      (2) **per-layer table** — the health events of the instrumented run
          (what ``telemetry health`` renders), proving the stream.
      (3) **detection latency** — synthetic anomaly streams through the
          EXACT HealthMonitor detectors: a layer's grad norm exploding
          10x over a healthy baseline, a 20x loss spike, and a NaN step;
          reported as steps from injection to the ``health_anomaly``
          event. Acceptance: nonfinite detects in 0 extra steps,
          explosion/spike within 1.

    Emits one JSON line; full runs write BENCH_HEALTH_r17.json."""
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    ndev = 8
    import jax

    if len(jax.devices()) < ndev:
        print(json.dumps({"metric": "health_stats_overhead_pct_of_step",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {ndev} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (128, 256, 8) if smoke else (256, 1024, 32)
    batch, n_rows = (128, 1024) if smoke else (256, 4096)
    epochs = 2 if smoke else 6

    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(ndev)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.measured_peak_flops()  # cache the peak probe outside timing

    def timed_fit(health, jsonl=None):
        telemetry.reset()
        model = build()
        # warm-up fit WITHOUT the jsonl sink: the published event counts
        # and per-layer table must describe the instrumented run only
        model.fit(X, y, batch_size=batch,
                  telemetry=telemetry.TelemetryConfig(memory=False),
                  health=health)
        tel = telemetry.TelemetryConfig(jsonl=jsonl, memory=False)
        t0 = _time.perf_counter()
        model.fit(X, y, batch_size=batch, telemetry=tel, health=health)
        wall = _time.perf_counter() - t0
        flops = telemetry.hub().snapshot()["gauges"].get(
            "model_flops_per_step", 0.0)
        return wall, flops

    import tempfile

    jsonl = os.path.join(tempfile.mkdtemp(prefix="mxtpu_health_bench_"),
                         "run.jsonl")
    wall_off, flops_off = timed_fit(False)
    wall_on, flops_on = timed_fit(True, jsonl=jsonl)
    step_s_off = wall_off / (epochs * steps_per_epoch)
    step_s_on = wall_on / (epochs * steps_per_epoch)
    flop_overhead_pct = (flops_on - flops_off) / flops_off * 100.0 \
        if flops_off else 0.0
    wall_overhead_pct = (wall_on - wall_off) / wall_off * 100.0

    # -- (2) the per-layer table from the instrumented run --------------------
    from mxnet_tpu.telemetry.health import aggregate_events

    rows = telemetry.read_events(jsonl)
    health_events = [e for e in rows if e.get("kind") == "health"]
    run_anomalies = [e for e in rows if e.get("kind") == "health_anomaly"]
    layer_table = [{"layer": k, **v}
                   for k, v in sorted(aggregate_events(rows).items())]

    # -- (3) detection latency on synthetic streams ---------------------------
    def synth(kind):
        """Healthy baseline then one injected anomaly; returns steps from
        injection to detection (None = missed within the horizon)."""
        telemetry.reset()
        mon = telemetry.HealthMonitor(telemetry.HealthConfig())
        srng = np.random.RandomState(7)
        base = 40
        for i in range(base):
            stats = {"fc1": {"grad_norm": 1.0 + 0.05 * srng.randn(),
                             "weight_norm": 1.0, "update_ratio": 1e-3,
                             "nonfinite": 0},
                     "fc2": {"grad_norm": 2.0 + 0.1 * srng.randn(),
                             "weight_norm": 1.0, "update_ratio": 1e-3,
                             "nonfinite": 0}}
            mon.observe({"kind": "health", "epoch": 0, "step": i,
                         "loss": 1.0 + 0.01 * srng.randn(), "finite": True,
                         "stats": stats})
        for k in range(8):
            stats = {"fc1": {"grad_norm": 1.0, "weight_norm": 1.0,
                             "update_ratio": 1e-3, "nonfinite": 0},
                     "fc2": {"grad_norm": 2.0, "weight_norm": 1.0,
                             "update_ratio": 1e-3, "nonfinite": 0}}
            loss = 1.0
            if kind == "grad_explosion":
                stats["fc2"]["grad_norm"] = 20.0 * (k + 1)
            elif kind == "loss_spike":
                loss = 20.0
            elif kind == "nonfinite":
                stats["fc2"]["nonfinite"] = 17
            found = mon.observe({"kind": "health", "epoch": 0,
                                 "step": base + k, "loss": loss,
                                 "finite": kind != "nonfinite",
                                 "stats": stats})
            if any(r[0] == kind for r in found):
                return k
        return None

    latency = {kind: synth(kind)
               for kind in ("nonfinite", "grad_explosion", "loss_spike")}

    result = {
        "metric": "health_stats_overhead_pct_of_step",
        "value": round(flop_overhead_pct, 4),
        "unit": "%",
        "vs_baseline": round(flop_overhead_pct, 4),
        "flops_per_step_baseline": flops_off,
        "flops_per_step_health": flops_on,
        "step_ms_baseline": round(step_s_off * 1e3, 3),
        "step_ms_health": round(step_s_on * 1e3, 3),
        "wall_overhead_pct": round(wall_overhead_pct, 2),
        "health_events": len(health_events),
        "anomalies_in_run": len(run_anomalies),
        "layers": layer_table,
        "detect_latency_steps": latency,
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "axis_size": ndev,
        "smoke": bool(smoke),
        "notes": (
            "headline = jaxpr-audit FLOP delta of the health-instrumented "
            "fused step vs the bare one, as % of baseline FLOPs — the "
            "deterministic on-device cost of the in-graph stats engine "
            "(ISSUE 14); wall_overhead_pct is the raw dp-8 wall delta "
            "(includes the per-step host pull + detector pass; noisy on "
            "~ms CPU steps). detect_latency_steps: steps from synthetic "
            "injection to the health_anomaly event through the exact "
            "HealthMonitor detectors."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_HEALTH_r17.json", smoke=smoke)


def run_profile_bench(args):
    """--profile-bench: the device-time profiler's acceptance numbers
    (ISSUE 15). Three measurements on the 8-virtual-device CPU mesh:

      (1) **attribution coverage** — the headline. A dp-8 MLP fit with a
          bounded capture window (guards + health stacked, the production
          shape): the profiler must attribute >= 80%% of in-window device
          time to named layers/kernels, with the remainder reported as an
          explicit ``unattributed`` row. The top-K hotspot table, the
          per-layer split, and the measured roofline rows
          (``source: "measured"``, joined to the jaxpr-audit FLOP/byte
          models) are published alongside.
      (2) **measured-vs-modeled MFU** — the reconciliation delta between
          the device-clock MFU (measured numerator) and the wall-clock
          MFU the epoch report logs.
      (3) **out-of-window overhead** — once the window closes, the fit
          loop's only profiler cost is one state poll per step; priced
          per-poll (ns, microbenched) against the measured step time —
          acceptance < 0.5%% of a step. The window itself is priced as
          ``profile`` badput (reported, not hidden in throughput).

    Emits one JSON line; full runs write BENCH_PROFILE_r18.json."""
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import profiling

    ndev = 8
    import jax

    if len(jax.devices()) < ndev:
        print(json.dumps({"metric": "profile_attribution_coverage_pct",
                          "value": 0, "unit": "%", "vs_baseline": 80,
                          "error": f"need {ndev} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (64, 128, 8) if smoke else (256, 1024, 32)
    batch, n_rows = (128, 1024) if smoke else (256, 4096)
    epochs = 2 if smoke else 4
    window = 4 if smoke else 8

    def build(ndev=ndev):
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1",
            act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(ndev)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.measured_peak_flops()  # cache the probes outside timing
    profiling.measured_peak_bandwidth()

    telemetry.reset()
    model = build()
    t0 = _time.perf_counter()
    model.fit(X, y, batch_size=batch, guards=True, health=True,
              telemetry=telemetry.TelemetryConfig(memory=False),
              profile=telemetry.ProfileConfig(steps=window, warmup=2))
    wall = _time.perf_counter() - t0
    rep = model.profile_report
    assert rep is not None, "profiled fit produced no report"
    summary = rep.to_dict(top_k=10)
    step_ms = wall / (epochs * steps_per_epoch) * 1e3

    # -- (3) out-of-window overhead: the per-step poll of a closed session
    ses = profiling.ProfileSession(telemetry.ProfileConfig(), layers=())
    ses._state = "done"
    reps = 20000 if smoke else 200000
    t0 = _time.perf_counter()
    for _ in range(reps):
        _ = ses.pending
        _ = ses.open
    poll_ns = (_time.perf_counter() - t0) / reps * 1e9
    overhead_pct = poll_ns / (step_ms * 1e6) * 100.0

    badput = sum(float(e.get("seconds", 0.0))
                 for e in telemetry.hub().events(kind="badput")
                 if e.get("reason") == "profile")

    mfu = summary.get("mfu", {})
    result = {
        "metric": "profile_attribution_coverage_pct",
        "value": round(summary["coverage_pct"], 2),
        "unit": "%",
        "vs_baseline": 80.0,
        "window_steps": summary["steps"],
        "device_ms": round(summary["device_ms"], 3),
        "unattributed_ms": round(summary["unattributed_ms"], 3),
        "layers_ms": {k: round(v, 3)
                      for k, v in summary["layers"].items()},
        "top": [{"layer": r.get("layer"), "op": r.get("op"),
                 "ms": round(r.get("us", 0.0) / 1e3, 4),
                 "pct": round(r.get("pct", 0.0), 2)}
                for r in summary["top"]],
        "roofline": summary["roofline"][:10],
        "measured_mfu_pct": mfu.get("measured_mfu_pct"),
        "modeled_mfu_pct": mfu.get("modeled_mfu_pct"),
        "mfu_delta_pct": mfu.get("delta_pct"),
        "profile_badput_s": round(badput, 4),
        "out_of_window_poll_ns": round(poll_ns, 1),
        "out_of_window_overhead_pct": round(overhead_pct, 6),
        "step_ms": round(step_ms, 3),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "axis_size": ndev,
        "smoke": bool(smoke),
        "notes": (
            "headline = share of in-window device time attributed to "
            "named layers/kernels through the named-scope HLO metadata "
            "join (>= 80% acceptance; the remainder is the explicit "
            "unattributed row). roofline rows are source=measured: "
            "measured per-op seconds against the jaxpr-audit/kernel-"
            "registry models — on this CPU rig the rates are rig-"
            "relative (measured matmul peak), the row schema is the TPU "
            "contract. out_of_window = the closed session's per-step "
            "state poll, priced per-poll x 1 poll/step against the "
            "measured step (<0.5% acceptance); the window itself is "
            "priced as `profile` badput, never as throughput."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_PROFILE_r18.json", smoke=smoke)


def run_elastic_bench(args):
    """--elastic-bench: price a mid-run world resize (ISSUE 10).

    On the 8-virtual-device CPU mesh, an elastic fit loses 2 of 8 workers
    mid-epoch, continues on 6, and regrows to 8 — the bench measures the
    quiesce->reshard->replan->rewarm downtime of each resize, the per-step
    time at every world size, and the post-resize goodput (the `resize`
    badput bucket priced by the epoch report). Emits one JSON line; full
    runs write BENCH_ELASTIC_r13.json."""
    import tempfile
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.resilience import ElasticCoordinator

    import jax

    world = 8
    if len(jax.devices()) < world:
        print(json.dumps({"metric": "elastic_resize_downtime_seconds",
                          "value": 0, "unit": "s", "vs_baseline": 0,
                          "error": f"need {world} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (32, 64, 4) if smoke else (256, 1024, 32)
    batch, n_rows = (48, 480) if smoke else (192, 3840)  # 48,192 % 6 == 0
    epochs = 4 if smoke else 6

    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1",
            act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(world)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.reset()
    telemetry.measured_peak_flops()  # cache the peak probe outside timing

    co = ElasticCoordinator(world)

    def drive(param):
        # kill 2 of 8 mid-epoch-1; regrow mid-epoch-2 — both resizes land
        # mid-epoch so the redo + downtime are fully priced
        if param.epoch == 1 and param.nbatch == 2 and co.world_size == 8:
            co.kill()
            co.kill()
        if param.epoch == 2 and param.nbatch == 2 and co.world_size == 6:
            co.join_all()

    tmp = tempfile.mkdtemp(prefix="mxtpu_elastic_bench_")
    jsonl = os.path.join(tmp, "events.jsonl")
    model = build()
    t0 = _time.perf_counter()
    model.fit(X, y, batch_size=batch, elastic=co,
              sharded_checkpoint_dir=os.path.join(tmp, "ckpt"),
              batch_end_callback=drive,
              telemetry=telemetry.TelemetryConfig(jsonl=jsonl))
    wall = _time.perf_counter() - t0

    downs = [h["downtime_s"] for h in co.history]
    # per-world step times from the timeline: an epoch interrupted by a
    # resize leaves the ABORTED attempt's old-world spans under the same
    # epoch number, so take only the trailing steps_per_epoch spans of
    # each epoch — the completed attempt at that epoch's final world size
    spans = model.telemetry.steps()
    step_ms = {}
    for world_size, epoch in (("8_pre", 0), ("6", 1), ("8_post", 3)):
        tail = [s.duration for s in spans
                if s.epoch == epoch][-steps_per_epoch:]
        if tail:
            tail.sort()
            step_ms[world_size] = tail[len(tail) // 2] * 1e3
    events = telemetry.read_events(jsonl)
    goodput = {int(e["epoch"]): e.get("goodput_pct")
               for e in events if e.get("kind") == "epoch_summary"}
    resize_badput = sum(float(e.get("seconds", 0.0)) for e in events
                        if e.get("kind") == "badput"
                        and e.get("reason") == "resize")
    resizes = [e for e in events if e.get("kind") == "resize"]

    result = {
        "metric": "elastic_resize_downtime_seconds",
        "value": round(downs[0], 4) if downs else None,
        "unit": "s",
        "vs_baseline": round(downs[0], 4) if downs else None,
        "shrink_downtime_s": round(downs[0], 4) if downs else None,
        "grow_downtime_s": round(downs[1], 4) if len(downs) > 1 else None,
        "resizes": co.resizes,
        "resize_events": len(resizes),
        "worlds": [h["to"] for h in co.history],
        "step_ms_by_world": {k: round(v, 3) for k, v in step_ms.items()},
        "goodput_pct_by_epoch": {k: round(v, 2)
                                 for k, v in sorted(goodput.items())
                                 if v is not None},
        "resize_badput_s": round(resize_badput, 4),
        "wall_s": round(wall, 3),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "batch": batch, "full_world": world,
        "smoke": bool(smoke),
        "notes": (
            "headline = shrink (8->6) downtime: quiesce + checkpoint "
            "reshard + plan re-derivation + AOT re-warmup for the new "
            "axis, measured on the CPU rig (pod-scale compiles dominate "
            "on real hardware; the persistent compile cache and warm-"
            "program reuse on regrow are what bound it). resize badput "
            "additionally prices the redone partial epoch."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_ELASTIC_r13.json", smoke=smoke)


def run_controller_bench(args):
    """--controller-bench: goodput recovered by the fleet controller
    under an injected persistent straggler + a flaky rank (ISSUE 12).

    Three dp-8 fits on the CPU mesh, same model/data/steps:

      clean    no fault injected, no controller — the ceiling;
      static   rank 7 drags every collective by a fixed stall (injected
               as a real per-step sleep + per-rank telemetry spans that
               blame it) and rank 6 goes heartbeat-silent mid-run; no
               controller, so the fleet pays the stall forever;
      armed    same faults, fit(controller=...): the controller blames
               rank 7 over K-of-N windows, evicts it, backfills the
               flaky rank when it beats again, and auto-picks a
               compression tier from the (bandwidth-scaled) comm:compute
               ratio.

    Headline: goodput_recovered_frac = (tpc_armed - tpc_static) /
    (tpc_clean - tpc_static) on per-chip throughput over the post-
    warmup epochs — 1.0 means the autopilot bought back everything the
    straggler cost. Emits one JSON line; full runs write
    BENCH_CONTROLLER_r15.json."""
    import tempfile
    import threading
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.resilience import ElasticCoordinator, FleetController

    import jax

    world = 8
    if len(jax.devices()) < world:
        print(json.dumps({"metric": "controller_goodput_recovered_frac",
                          "value": 0, "unit": "frac", "vs_baseline": 0,
                          "error": f"need {world} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (32, 64, 4) if smoke else (128, 512, 16)
    # batch % 6, 7, 8 == 0: every world this fleet can pass through
    # (evict the straggler -> 7, flaky death -> 6, backfill -> 7/8)
    batch, n_rows = (168, 840) if smoke else (168, 3360)
    epochs = 3 if smoke else 5
    stall_s = 0.03 if smoke else 0.05
    straggler, flaky = 7, 6
    steps_per_epoch = n_rows // batch

    def build():
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1",
            act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(world)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    telemetry.measured_peak_flops()  # cache the probe outside timing

    class FaultHarness:
        """The injected fleet pathology: a persistent straggler (real
        per-step sleep, charged to whoever keeps rank 7 in the world)
        plus per-rank telemetry spans blaming it, and a flaky rank whose
        out-of-band heartbeats stop for a while mid-run and resume.
        Heartbeats come from their own thread (like a real fleet's —
        and so a long AOT re-warm gap can never read as a mass death).
        With ``inject=False`` it is the clean harness: same bookkeeping
        (per-step wall clocks), no faults."""

        def __init__(self, co=None, inject=True):
            self.co = co
            self.inject = inject
            self.step = 0
            self.times = []  # monotonic at every batch callback
            self._stop = threading.Event()
            self._silent_at = None  # wall start of the flaky outage
            if co is not None and co.heartbeat_timeout:
                self._silence = 6.0 * co.heartbeat_timeout
                threading.Thread(target=self._beat, daemon=True,
                                 name="mx-bench-beater").start()

        def alive(self):
            return self.co.alive if self.co is not None \
                else tuple(range(world))

        def _beat(self):
            # every rank beats (departed ones too: a recovered host
            # heartbeats before readmission) except the flaky one
            # during its outage window
            while not self._stop.wait(0.05):
                now = _time.monotonic()
                out = self._silent_at is not None and \
                    now - self._silent_at < self._silence
                for r in range(world):
                    if r == flaky and out:
                        continue
                    self.co.heartbeat(r)

        def close(self):
            self._stop.set()

        def final_epoch_step_s(self):
            """Median wall per step over the run's final epoch — the
            steady state each fleet settled into (run-order XLA-cache
            effects and mid-run re-warms excluded by construction)."""
            tail = self.times[-(steps_per_epoch + 1):]
            diffs = sorted(b - a for a, b in zip(tail, tail[1:]))
            return diffs[len(diffs) // 2] if diffs else None

        def __call__(self, param):
            del param
            self.times.append(_time.monotonic())
            s = self.step
            self.step += 1
            if not self.inject:
                return
            if self.co is not None and self._silent_at is None and \
                    s >= steps_per_epoch:
                self._silent_at = _time.monotonic()  # outage starts
            alive = self.alive()
            if straggler in alive:
                _time.sleep(stall_s)  # the whole collective waits
            for r in alive:
                dur_ms = (stall_s * 1e3 + 2.0) if r == straggler else 2.0
                telemetry.emit(
                    "span", rank=r, name="step", epoch=0, step=s,
                    dur_ms=dur_ms,
                    phases=[{"name": "device", "dur_ms": dur_ms}])

    def run_fit(name, faults, controller=None, co=None):
        telemetry.reset()
        model = build()
        tmp = tempfile.mkdtemp(prefix=f"mxtpu_ctl_bench_{name}_")
        t0 = _time.perf_counter()
        try:
            model.fit(X, y, batch_size=batch,
                      # False, not None: a user's MXNET_TPU_ELASTIC /
                      # MXNET_TPU_CONTROLLER env gates must not arm the
                      # clean/static baselines
                      elastic=co if co is not None else False,
                      controller=controller if controller is not None
                      else False,
                      sharded_checkpoint_dir=os.path.join(tmp, "ckpt")
                      if co is not None else None,
                      batch_end_callback=faults,
                      telemetry=telemetry.TelemetryConfig(
                          timeline=False, memory=False))
        finally:
            if hasattr(faults, "close"):
                faults.close()
        wall = _time.perf_counter() - t0
        return model, wall

    clean = FaultHarness(inject=False)   # the no-fault ceiling
    _, wall_clean = run_fit("clean", clean)
    static = FaultHarness()
    _, wall_static = run_fit("static", static)

    co = ElasticCoordinator(world, heartbeat_timeout=0.5)
    ctl = FleetController(
        interval=0.0, window=24, min_report_steps=24, evict_k=3,
        evict_n=5, max_evictions=1, rejoin_after=1.0, evaluate_after=1.0,
        cooldowns={"evict": 0.5, "backfill": 0.2, "retier": 0.5},
        wire_gbps=0.01)  # scaled bandwidth: the tiny CPU model reads as
    #                      comm-bound, so the tier policy has a real
    #                      choice to make on this rig
    harness = FaultHarness(co)
    model, wall_ctl = run_fit("armed", harness, controller=ctl, co=co)

    # per-chip throughput in each run's FINAL-epoch steady state
    # (steps/sec/chip, global batch fixed): the static fleet is still
    # paying the straggler there; the armed fleet has evicted it and
    # settled on its chosen world/tier. Whole-run walls are reported
    # too, but run-order XLA-executable-cache effects make them
    # incomparable as the headline.
    worlds = [h["to"] for h in co.history]
    step_clean = clean.final_epoch_step_s()
    step_static = static.final_epoch_step_s()
    step_ctl = harness.final_epoch_step_s()
    tpc_clean = 1.0 / (step_clean * world) if step_clean else None
    tpc_static = 1.0 / (step_static * world) if step_static else None
    tpc_ctl = 1.0 / (step_ctl * co.world_size) if step_ctl else None
    recovered = None
    if None not in (tpc_clean, tpc_static, tpc_ctl) and \
            tpc_clean > tpc_static:
        recovered = (tpc_ctl - tpc_static) / (tpc_clean - tpc_static)

    evicts = [d for d in ctl.decisions
              if d["lever"] == "evict" and d["outcome"] == "actuated"]
    backfills = [d for d in ctl.decisions
                 if d["lever"] == "backfill" and d["outcome"] == "actuated"]
    retiers = [d for d in ctl.decisions
               if d["lever"] == "retier" and d["outcome"] == "actuated"]

    result = {
        "metric": "controller_goodput_recovered_frac",
        "value": round(recovered, 4) if recovered is not None else None,
        "unit": "frac",
        "vs_baseline": round(tpc_ctl / tpc_static, 4)
        if tpc_ctl and tpc_static else None,
        "tpc_clean": round(tpc_clean, 4) if tpc_clean else None,
        "tpc_static": round(tpc_static, 4) if tpc_static else None,
        "tpc_controller": round(tpc_ctl, 4) if tpc_ctl else None,
        "final_step_ms": {
            "clean": round(step_clean * 1e3, 3) if step_clean else None,
            "static": round(step_static * 1e3, 3) if step_static else None,
            "controller": round(step_ctl * 1e3, 3) if step_ctl else None},
        "wall_clean_s": round(wall_clean, 3),
        "wall_static_s": round(wall_static, 3),
        "wall_controller_s": round(wall_ctl, 3),
        "stall_ms": stall_s * 1e3,
        "evicted": [d.get("rank") for d in evicts],
        "backfilled": [d.get("rank") for d in backfills],
        "tier_chosen": ctl._comm_mode,
        "retier_actions": [d["action"] for d in retiers],
        "resizes": co.resizes,
        "worlds": worlds,
        "breaker_state": ctl.breaker.state,
        "decisions_total": len(ctl.decisions),
        "epochs": epochs, "steps_per_epoch": steps_per_epoch,
        "batch": batch, "full_world": world, "smoke": bool(smoke),
        "notes": (
            "headline = fraction of straggler-lost per-chip throughput "
            "the armed controller bought back, measured in each run's "
            "final-epoch steady state (the static fleet still pays the "
            "stall there; the armed fleet has evicted the straggler and "
            "settled on its chosen world/tier). Whole-run walls carry "
            "the autopilot's own costs (resize + retier re-warms) and "
            "run-order XLA-cache effects — reported, not the headline. "
            "CPU-rig caveat: stall_ms dominates the tiny step, so "
            "fractions exaggerate what a pod would see; the shape of "
            "the loop (blame -> evict -> backfill -> retier) is the "
            "measured artifact."),
    }
    print(json.dumps(result))
    if not smoke:
        assert recovered is not None and recovered >= 0.3, result
        assert [d.get("rank") for d in evicts] == [straggler], result
    _publish(result, "BENCH_CONTROLLER_r15.json", smoke=smoke)


def run_kernel_bench(args):
    """--kernel-bench: the Pallas kernel layer's roofline accounting
    (ISSUE 13). Three measurements, one JSON line (full runs write
    BENCH_KERNELS_r16.json):

    (a) a roofline row per registered kernel — registry FLOP/byte model
        vs measured interpret-mode wall time on this rig (CPU numbers:
        the interpreter prices correctness, not Mosaic speed; the row
        SCHEMA is the TPU contract, and flash's on-chip numbers live in
        FLASH_r05.json / the kernel catalog);
    (b) the fused-vs-unfused HLO delta on the dp-8 compressed allreduce:
        full-slab quantize-shaped elementwise passes (the encode/decode
        cost the comm kernels remove) and collective wire bytes (which
        must NOT change — same bits on the wire);
    (c) the fused-Adam step-time delta vs the per-leaf optimizer tree,
        parity-checked bitwise on the same inputs.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import mxnet_tpu.optimizer as opt_mod
    from mxnet_tpu import comm
    from mxnet_tpu import parallel as par
    from mxnet_tpu.analysis import jaxpr_audit
    from mxnet_tpu.compat import shard_map
    from mxnet_tpu.ops import pallas as pk
    from mxnet_tpu.telemetry.mfu import measured_peak_flops

    smoke = args.smoke
    rng = np.random.RandomState(0)
    peak = measured_peak_flops()

    def time_fn(fn, *a, iters=None, warmup=2):
        iters = iters or (3 if smoke else 20)
        for _ in range(warmup):
            out = fn(*a)
        jax.block_until_ready(out)
        t0 = _time.perf_counter()
        for _ in range(iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (_time.perf_counter() - t0) / iters

    def roofline_row(label, fn, *a):
        """One kernel invocation: registry-priced cost + measured time.

        Every roofline row carries ``source`` (ISSUE 15 satellite):
        ``interpret`` when the Pallas interpreter ran (CPU rig — prices
        the interpreter, not Mosaic), ``measured`` on real hardware;
        device-profiler rows (telemetry/profiling.py) are always
        ``measured``, and rows priced purely from cost models say
        ``model`` — so a CPU estimate can never be read as a device
        measurement."""
        jitted = jax.jit(fn)
        rows, totals = jaxpr_audit.cost_rows(fn, *a)
        krows = [r for r in rows if r["primitive"].startswith("pallas::")]
        flops = sum(r["flops"] for r in krows)
        bytes_ = sum(r["bytes"] for r in krows)
        dt = time_fn(jitted, *a)
        return {
            "kernel": label,
            "source": "interpret" if pk.use_interpret() else "measured",
            "kernels_in_program": [r["primitive"] for r in krows],
            "model_flops": flops,
            "model_bytes": bytes_,
            "intensity_flops_per_byte": round(flops / bytes_, 3)
            if bytes_ else None,
            "ms": round(dt * 1e3, 4),
            "achieved_gflops_s": round(flops / dt / 1e9, 3),
            "achieved_gbytes_s": round(bytes_ / dt / 1e9, 3),
            "pct_of_measured_peak": round(100.0 * flops / dt / peak, 3),
        }

    # -- (a) per-kernel roofline rows (interpret mode on this rig) ---------
    b, h, s, d = (1, 2, 128, 32) if smoke else (2, 4, 512, 64)
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
    slab_r, slab_l = (8, 4096) if smoke else (8, 65536)
    rows_in = jnp.asarray(rng.randn(slab_r, slab_l).astype(np.float32))
    spec8 = comm.CompressionSpec("int8", chunk=256)
    spec2 = comm.CompressionSpec("twobit", threshold=0.5, chunk=256)
    m_mm, k_mm, n_mm = (64, 128, 64) if smoke else (512, 1024, 512)
    x_mm = jnp.asarray(rng.randn(m_mm, k_mm).astype(np.float32))
    w_mm = jnp.asarray(rng.randn(n_mm, k_mm).astype(np.float32))

    names = ["p0", "p1", "p2"]
    shapes = [(256, 64), (64,), (64, 32)] if smoke else \
        [(1024, 512), (512,), (512, 256)]
    params = {n: jnp.asarray(rng.randn(*sh).astype(np.float32))
              for n, sh in zip(names, shapes)}
    grads = {n: jnp.asarray(rng.randn(*sh).astype(np.float32))
             for n, sh in zip(names, shapes)}
    adam_f = opt_mod.Adam(lr=1e-3, fused=True)
    adam_u = opt_mod.Adam(lr=1e-3, fused=False)
    states = adam_f.init_state_tree(params)
    lr = jnp.float32(1e-3)

    kernels = [
        roofline_row("flash_attention_fwd",
                     lambda x: pk.flash_attention(x, x, x, causal=True), q),
        roofline_row(
            "flash_attention_fwd_bwd",
            lambda x: jax.grad(lambda y: jnp.sum(
                pk.flash_attention(y, y, y, causal=True)))(x), q),
        roofline_row(
            "quant_int8",
            lambda r: pk.fused_quantize(spec8, r, want_dequant=True)[0]["q"],
            rows_in),
        roofline_row(
            "quant_twobit",
            lambda r: pk.fused_quantize(spec2, r, want_dequant=True)[0]["q"],
            rows_in),
        # payload built OUTSIDE the measured fn: the row prices the
        # dequant-sum kernel alone, not a quantize+dequant pair
        roofline_row(
            "dequant_sum_int8",
            lambda p: pk.fused_dequant_sum(spec8, p),
            jax.jit(lambda r: pk.fused_quantize(spec8, r)[0])(rows_in)),
        roofline_row("fused_adam",
                     lambda p, g, st: pk.fused_adam_apply(
                         adam_f, p, g, st, lr)[0]["p0"],
                     params, grads, states),
        roofline_row("int8_matmul",
                     lambda a, w: pk.int8_matmul(a, w), x_mm, w_mm),
    ]

    # -- (b) fused-vs-unfused HLO delta on the dp-8 exchange ---------------
    ndev = 8
    mesh = par.make_mesh(dp=ndev, devices=jax.devices()[:ndev])
    L = ndev * (2048 if smoke else 16384)
    tree = {"g": jnp.asarray(rng.randn(L).astype(np.float32))}
    resid = jnp.zeros((ndev, L), jnp.float32)

    def build_exchange(kern_cfg):
        def body(t, r):
            return comm.error_feedback_allreduce(
                t, r, spec8, axis_name="dp", axis_size=ndev,
                kernels=kern_cfg)
        return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("dp")),
                                 out_specs=(P(), P("dp")), check_vma=False))

    hlo_delta = {}
    # block cap below the slab: the pass count tells kernel-body (per
    # block) instructions from full-slab HLO passes by element count, so
    # the slab must span more than one block
    for label, cfg in (("codec", False),
                       ("kernels", comm.CommKernelConfig(block_elems=8192))):
        f = build_exchange(cfg)
        hlo = f.lower(tree, resid).compile().as_text()
        hlo_delta[label] = {
            "full_slab_quantize_passes":
                comm.hlo_quantize_pass_count(hlo, min_elements=L),
            "collective_wire_bytes": round(sum(
                r["wire_bytes"] for r in comm.hlo_collective_table(
                    hlo, default_group_size=ndev)), 1),
            "step_ms": round(time_fn(f, tree, resid) * 1e3, 3),
        }
    passes_cut = (hlo_delta["codec"]["full_slab_quantize_passes"]
                  - hlo_delta["kernels"]["full_slab_quantize_passes"])

    # -- (c) fused-Adam step-time delta + parity ---------------------------
    apply_f = jax.jit(lambda p, g, st: adam_f.apply(p, g, st, lr))
    apply_u = jax.jit(lambda p, g, st: adam_u.apply(p, g, st, lr))
    pf, sf = apply_f(params, grads, states)
    pu, su = apply_u(params, grads, states)
    adam_parity = all(
        bool(jnp.all(pf[n] == pu[n])) for n in names) and all(
        bool(jnp.all(sf[n][i] == su[n][i]))
        for n in names for i in range(3))
    adam_row = {
        "fused_ms": round(time_fn(apply_f, params, grads, states) * 1e3, 4),
        "per_leaf_ms": round(
            time_fn(apply_u, params, grads, states) * 1e3, 4),
        "bitwise_parity": bool(adam_parity),
        "param_elements": int(sum(int(np.prod(sh)) for sh in shapes)),
    }

    y_ref = x_mm @ w_mm.T
    y_q = pk.int8_matmul(x_mm, w_mm)
    mm_err = float(jnp.linalg.norm(y_q - y_ref) / jnp.linalg.norm(y_ref))

    result = {
        "metric": "kernel_bench_full_slab_quantize_passes_removed",
        "value": passes_cut,
        "unit": "hlo_passes",
        "vs_baseline": hlo_delta["codec"]["full_slab_quantize_passes"],
        "smoke": bool(smoke),
        "interpret_mode": bool(pk.use_interpret()),
        "measured_peak_gflops_s": round(peak / 1e9, 2),
        "kernels": kernels,
        "hlo_fused_vs_unfused": hlo_delta,
        "wire_bytes_identical": (
            hlo_delta["codec"]["collective_wire_bytes"]
            == hlo_delta["kernels"]["collective_wire_bytes"]),
        "fused_adam": adam_row,
        "int8_matmul_rel_error": round(mm_err, 6),
        "catalog": pk.catalog(),
        "notes": (
            "CPU rig: kernels run under the Pallas interpreter, so ms/"
            "achieved-rate columns price the interpreter, not Mosaic — "
            "the registry flops/bytes and the HLO pass/wire deltas are "
            "the numbers that transfer to TPU (schema ready; flash's "
            "on-chip rates are in FLASH_r05.json). wire bytes must be "
            "identical between codec and kernel paths: same bits, fewer "
            "passes."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_KERNELS_r16.json", smoke=smoke)


def run_lockwatch_bench(args):
    """--lockwatch-bench: price the runtime lock-order watchdog (ISSUE 11).

    Two soaks under MXNET_TPU_LOCKWATCH semantics (watchdog armed
    in-process): (a) a 4-rank group-kvstore push/pull/barrier soak with a
    mid-soak membership churn (deregister a rank inside an open
    accumulate round, then re-register it), and (b) an elastic fit on a
    dp-4 CPU mesh that shrinks to 3 mid-epoch and regrows — the two most
    lock-entangled paths in the stack. Acceptance: ZERO lock-order cycles
    across both, and watchdog overhead <2% of a step (priced robustly:
    per acquire/release-pair microbench delta x measured acquisitions per
    step / measured step time — two full timed runs would drown the
    number in shared-box noise). Emits one JSON line; full runs write
    BENCH_LOCKWATCH_r14.json."""
    import tempfile
    import threading
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.analysis import lockwatch
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.resilience import ElasticCoordinator

    import jax

    world = 4
    if len(jax.devices()) < world:
        print(json.dumps({"metric": "lockwatch_overhead_pct_of_step",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {world} devices"}))
        return
    smoke = args.smoke

    # -- (1) per-pair microbench: watched lock, watchdog off vs on ------------
    reps = 20000 if smoke else 200000
    lk = lockwatch.named_lock("bench.probe")

    def pairs_ns(n):
        t0 = _time.perf_counter()
        for _ in range(n):
            lk.acquire()
            lk.release()
        return (_time.perf_counter() - t0) / n * 1e9

    lockwatch.disable()
    pairs_ns(reps // 10)  # warm
    pair_ns_off = min(pairs_ns(reps) for _ in range(3))
    lockwatch.enable()
    lockwatch.reset()
    pairs_ns(reps // 10)
    pair_ns_on = min(pairs_ns(reps) for _ in range(3))
    pair_delta_ns = max(pair_ns_on - pair_ns_off, 0.0)

    # -- (2) group-kvstore soak with membership churn -------------------------
    from mxnet_tpu import kvstore as kv_mod

    lockwatch.reset()
    rounds = 30 if smoke else 200
    churn_at = rounds // 3
    workers = kv_mod.create_group(4, op_timeout=120.0)
    server = workers[0]._server
    server.init("k", np.zeros((256,), np.float32))
    soak_rounds = {0: rounds, 1: rounds, 2: rounds, 3: churn_at}

    def run_worker(rank):
        w = workers[rank]
        for _ in range(soak_rounds[rank]):
            w.push("k", NDArray(np.ones((256,), np.float32)))

    ts = [threading.Thread(target=run_worker, args=(r,), daemon=True)
          for r in range(4)]
    for t in ts:
        t.start()
    ts[3].join(timeout=300)           # rank 3 dies after churn_at rounds
    _time.sleep(0.05)                 # survivors block in the open round
    server.deregister_worker(3)       # churn inside the open round
    for t in ts[:3]:
        t.join(timeout=300)
    server.register_worker(3)         # rejoin between rounds (idempotent)
    kv_hung = any(t.is_alive() for t in ts)
    kv_cycles = len(lockwatch.report()["cycles"])

    # -- (3) elastic fit soak: dp-4 -> 3 -> 4 under the watchdog --------------
    # full-size layer dims in BOTH modes: the overhead ratio's denominator
    # must be a realistic step, not a toy one (smoke only trims rows/epochs)
    lockwatch.reset()
    dim, hidden, classes = 256, 1024, 16
    batch, n_rows = 96, 960 if smoke else 3840   # 96 % 12 == 0: 4 and 3
    epochs = 4 if smoke else 6

    data = mx.sym.Variable("data")
    h1 = mx.sym.Activation(mx.sym.FullyConnected(
        data, name="fc1", num_hidden=hidden), name="a1", act_type="tanh")
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h1, name="fc2", num_hidden=classes), name="softmax")
    model = mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(world)],
                           num_epoch=epochs, optimizer="sgd",
                           learning_rate=0.05)
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    telemetry.reset()
    telemetry.measured_peak_flops()

    co = ElasticCoordinator(world)

    def drive(param):
        if param.epoch == 1 and param.nbatch == 2 and co.world_size == 4:
            co.kill()
        if param.epoch == 2 and param.nbatch == 2 and co.world_size == 3:
            co.join_all()

    tmp = tempfile.mkdtemp(prefix="mxtpu_lockwatch_bench_")
    acq0 = lockwatch.watcher().acquires
    model.fit(X, y, batch_size=batch, elastic=co,
              sharded_checkpoint_dir=os.path.join(tmp, "ckpt"),
              batch_end_callback=drive, telemetry=True)
    acq1 = lockwatch.watcher().acquires
    rep = lockwatch.report()
    fit_cycles = len(rep["cycles"])
    lockwatch.publish()

    spans = model.telemetry.steps()
    durs = sorted(s.duration for s in spans)
    step_ms = durs[len(durs) // 2] * 1e3 if durs else 0.0
    total_steps = max(len(spans), 1)
    acquires_per_step = (acq1 - acq0) / total_steps
    overhead_pct = (acquires_per_step * pair_delta_ns) / (step_ms * 1e6) \
        * 100.0 if step_ms else 0.0
    lockwatch.disable()

    result = {
        "metric": "lockwatch_overhead_pct_of_step",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "vs_baseline": round(overhead_pct, 4),
        "pair_ns_off": round(pair_ns_off, 1),
        "pair_ns_on": round(pair_ns_on, 1),
        "pair_delta_ns": round(pair_delta_ns, 1),
        "acquires_per_step": round(acquires_per_step, 1),
        "step_ms": round(step_ms, 3),
        "steps": total_steps,
        "cycles": fit_cycles,
        "max_hold_ms": rep["max_hold_ms"],
        "stalls": len(rep["stalls"]),
        "kv_soak": {"workers": 4, "rounds": rounds,
                    "churn_resizes": 2, "cycles": kv_cycles,
                    "hung": bool(kv_hung)},
        "resizes": co.resizes,
        "worlds": [h["to"] for h in co.history],
        "smoke": bool(smoke),
        "notes": (
            "overhead priced as pair-microbench delta x acquisitions/"
            "step / step time (robust to shared-box noise; two timed "
            "full runs swing +-17% for identical binaries). "
            "acceptance: zero lock-order cycles "
            "across the group-kvstore churn soak AND the elastic "
            "resize fit, overhead <2% of a dp-4 step."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_LOCKWATCH_r14.json", smoke=smoke)


def run_ckpt_bench(args):
    """--ckpt-bench: price the async multi-tier checkpoint plane
    (ISSUE 17) on the dp-8 CPU mesh. Three measurements:

      1. the step-loop stall per checkpoint — the T0 capture+submit wall
         (one blocking device->host copy, writer thread owns the rest)
         vs the synchronous durable save wall on the same training state.
         Acceptance: async stall < 10% of the sync wall.
      2. the recovery wall on an 8 -> 6 elastic resize: peer (T1, RAM)
         restore vs a chaos-forced disk (T2) restore of the same run.
      3. checkpoint badput per epoch at three cadences (every 1/4/16
         steps), as priced by the epoch goodput report.

    Emits one JSON line; full runs write BENCH_CKPT_r19.json."""
    import statistics
    import tempfile
    import time as _time

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.resilience import (ElasticCoordinator, chaos_scope,
                                      ckpt_async)
    from mxnet_tpu.utils import checkpoint as ckpt_mod

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    world = 8
    if len(jax.devices()) < world:
        print(json.dumps({"metric": "ckpt_async_stall_pct_of_sync",
                          "value": 0, "unit": "%", "vs_baseline": 0,
                          "error": f"need {world} devices"}))
        return
    smoke = args.smoke
    dim, hidden, classes = (32, 64, 4) if smoke else (256, 1024, 32)
    batch, n_rows = (48, 480) if smoke else (192, 3840)
    reps = 5 if smoke else 20

    def build(epochs):
        data = mx.sym.Variable("data")
        h1 = mx.sym.Activation(mx.sym.FullyConnected(
            data, name="fc1", num_hidden=hidden), name="a1",
            act_type="tanh")
        out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h1, name="fc2", num_hidden=classes), name="softmax")
        return mx.FeedForward(out, ctx=[mx.cpu(i) for i in range(world)],
                              num_epoch=epochs, optimizer="sgd",
                              learning_rate=0.05)

    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, dim).astype(np.float32)
    y = rng.randint(0, classes, (n_rows,)).astype(np.float32)
    steps_per_epoch = n_rows // batch
    os.environ.setdefault("MXNET_TPU_CKPT_KEEP", "0")  # GC out of the timing

    # -- 1. per-checkpoint step stall: T0 capture+submit vs sync save ------
    tmp = tempfile.mkdtemp(prefix="mxtpu_ckpt_bench_")
    d_state = os.path.join(tmp, "state")
    model = build(1)
    model.fit(X, y, batch_size=batch, sharded_checkpoint_dir=d_state)
    loaded, laux, _, _, opt_leaves = ckpt_mod.load_sharded(d_state)
    mesh = make_mesh(dp=world)
    repl = NamedSharding(mesh, P())
    params = {k: jax.device_put(np.asarray(v), repl)
              for k, v in loaded.items()}
    opt = None if opt_leaves is None else \
        [jax.device_put(np.asarray(l), repl) for l in opt_leaves]

    d_async = os.path.join(tmp, "async")
    writer = ckpt_async.AsyncCheckpointWriter(d_async, queue_depth=2,
                                              keep_last_k=0)
    async_ms, step_id = [], 0
    try:
        for _ in range(reps):
            step_id += 1
            t0 = _time.perf_counter()
            snap = ckpt_async.capture_snapshot(
                step_id, params, opt_state=opt,
                meta={"num_update": step_id})
            writer.submit(snap)
            async_ms.append((_time.perf_counter() - t0) * 1e3)
            writer.flush(timeout=120)  # drain OUTSIDE the stall timer
    finally:
        writer.close()
    d_sync = os.path.join(tmp, "sync")
    sync_ms = []
    for _ in range(reps):
        step_id += 1
        t0 = _time.perf_counter()
        ckpt_async.save_now(d_sync, step_id, params, opt_state=opt,
                            extra_meta={"num_update": step_id})
        sync_ms.append((_time.perf_counter() - t0) * 1e3)
    async_stall = statistics.median(async_ms)
    sync_wall = statistics.median(sync_ms)
    stall_pct = 100.0 * async_stall / sync_wall if sync_wall else None

    # -- 2. resize recovery wall: peer (T1) vs chaos-forced disk (T2) ------
    def resize_run(chaos_rules=None):
        telemetry.reset()
        co = ElasticCoordinator(world)

        def drive(param):
            if param.epoch == 1 and param.nbatch == 2 and \
                    co.world_size == world:
                co.kill()
                co.kill()

        m = build(3)
        d = tempfile.mkdtemp(prefix="mxtpu_ckpt_bench_el_")
        kw = dict(batch_size=batch, elastic=co, sharded_checkpoint_dir=d,
                  checkpoint_every_n_steps=2, batch_end_callback=drive)
        it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False)
        if chaos_rules:
            with chaos_scope(seed=0, rules=chaos_rules):
                m.fit(it, **kw)
        else:
            m.fit(it, **kw)
        assert co.resizes == 1
        events = telemetry.hub().events("checkpoint")
        tier = "t1" if any(e.get("tier") == "t1" for e in events) else "t2"
        return co.history[0]["downtime_s"], tier

    peer_recovery_s, peer_tier = resize_run()
    disk_recovery_s, disk_tier = resize_run({"ckpt.replica": 1.0})

    # -- 3. checkpoint badput per epoch at three cadences ------------------
    badput_by_cadence = {}
    for every in (1, 4, 16):
        telemetry.reset()
        jsonl = os.path.join(tmp, f"events_{every}.jsonl")
        m = build(2)
        m.fit(mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False),
              batch_size=batch,
              sharded_checkpoint_dir=os.path.join(tmp, f"cad{every}"),
              checkpoint_every_n_steps=every,
              telemetry=telemetry.TelemetryConfig(jsonl=jsonl))
        events = telemetry.read_events(jsonl)
        ckpt_s = [float(e.get("seconds", 0.0)) for e in events
                  if e.get("kind") == "badput"
                  and e.get("reason") == "checkpoint"]
        walls = [float(e.get("seconds", 0.0)) for e in events
                 if e.get("kind") == "epoch_summary"]
        per_epoch = sum(ckpt_s) / max(1, len(walls))
        badput_by_cadence[str(every)] = {
            "badput_s_per_epoch": round(per_epoch, 4),
            "badput_pct_of_wall": round(
                100.0 * sum(ckpt_s) / sum(walls), 2) if sum(walls) else None,
        }

    result = {
        "metric": "ckpt_async_stall_pct_of_sync",
        "value": round(stall_pct, 2) if stall_pct is not None else None,
        "unit": "%",
        "vs_baseline": round(sync_wall, 3),
        "async_stall_ms": round(async_stall, 3),
        "sync_save_ms": round(sync_wall, 3),
        "peer_recovery_s": round(peer_recovery_s, 4),
        "disk_recovery_s": round(disk_recovery_s, 4),
        "peer_recovery_tier": peer_tier,
        "disk_recovery_tier": disk_tier,
        "badput_by_cadence": badput_by_cadence,
        "reps": reps, "steps_per_epoch": steps_per_epoch,
        "batch": batch, "world": world,
        "smoke": bool(smoke),
        "notes": (
            "headline = the step-loop stall per checkpoint (T0 capture+"
            "submit) as % of the synchronous durable save wall on the "
            "same state; acceptance <10%. peer vs disk recovery is the "
            "8->6 resize downtime with the T1 RAM tier live vs chaos-"
            "killed (ckpt.replica) forcing the T2 disk read. badput rows "
            "are the epoch goodput report's `checkpoint` bucket."),
    }
    print(json.dumps(result))
    _publish(result, "BENCH_CKPT_r19.json", smoke=smoke)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--layout", choices=("NCHW", "NHWC"), default="NHWC")
    ap.add_argument("--mode", choices=("train", "pipeline", "io"),
                    default="train",
                    help="train: synthetic-fed fused step (headline); "
                         "pipeline: input pipeline only; io: fit() fed by "
                         "ImageRecordIter end-to-end")
    ap.add_argument("--recordio", default="/tmp/mxtpu_bench_imagenet.rec")
    ap.add_argument("--num-images", type=int, default=1024)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--model", choices=("resnet50", "inception_bn"),
                    default="resnet50",
                    help="resnet50: headline; inception_bn: the BASELINE "
                         "anchor architecture itself (97 img/s on GTX 980) "
                         "for a same-architecture comparison")
    ap.add_argument("--comm-bench", action="store_true",
                    help="gradient-sync wire bytes + step time per "
                         "compression mode (none/bf16/int8/twobit) on the "
                         "8-virtual-device CPU mesh; emits "
                         "BENCH_COMM_r08.json (full run)")
    ap.add_argument("--overlap-bench", action="store_true",
                    help="comm/compute overlap: per-bucket schedule "
                         "structure on the dp-8 mesh (HLO pair count, "
                         "exact plan sums) + stale-sync pipelined vs "
                         "serial kvstore step time; emits "
                         "BENCH_OVERLAP_r11.json (full run)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --comm-bench/--telemetry-bench/"
                         "--overlap-bench: tiny shapes, no file written "
                         "(the CI guards in tests/test_bench_entry.py)")
    ap.add_argument("--telemetry-bench", action="store_true",
                    help="telemetry-hub overhead (emit/observe/counter "
                         "cost, fit with vs without the step timeline) on "
                         "the 8-virtual-device CPU mesh; emits "
                         "BENCH_TELEMETRY_r09.json (full run)")
    ap.add_argument("--elastic-bench", action="store_true",
                    help="measure elastic-resize downtime (kill 2 of 8 "
                         "virtual workers mid-epoch, continue on 6, regrow "
                         "to 8) and post-resize goodput on the CPU mesh; "
                         "emits one JSON line, full runs write "
                         "BENCH_ELASTIC_r13.json")
    ap.add_argument("--ckpt-bench", action="store_true",
                    help="async multi-tier checkpoint plane (ISSUE 17): "
                         "T0 capture+submit stall vs sync save wall "
                         "(acceptance <10%%), peer (RAM) vs disk recovery "
                         "on a dp-8 resize, checkpoint badput at 3 "
                         "cadences -> BENCH_CKPT_r19.json (one JSON line "
                         "with --smoke)")
    ap.add_argument("--controller-bench", action="store_true",
                    help="fleet-controller acceptance (ISSUE 12): inject "
                         "a persistent straggler + flaky rank into dp-8 "
                         "fits with and without the armed controller; "
                         "headline = fraction of per-chip goodput "
                         "recovered -> BENCH_CONTROLLER_r15.json (one "
                         "JSON line with --smoke)")
    ap.add_argument("--kernel-bench", action="store_true",
                    help="Pallas kernel layer (ISSUE 13): per-kernel "
                         "roofline rows (registry FLOP/byte models vs "
                         "measured time), fused-vs-unfused quantize HLO "
                         "pass counts on the dp-8 exchange, fused-Adam "
                         "step-time delta -> BENCH_KERNELS_r16.json (one "
                         "JSON line with --smoke)")
    ap.add_argument("--lockwatch-bench", action="store_true",
                    help="price the runtime lock-order watchdog (ISSUE "
                         "11): group-kvstore churn + elastic-resize fit "
                         "soaks under the watchdog, zero-cycle + <2%% "
                         "overhead acceptance -> BENCH_LOCKWATCH_r14."
                         "json (one JSON line with --smoke)")
    ap.add_argument("--mem-bench", action="store_true",
                    help="measure memory-observability overhead (live-"
                         "array ledger + phase-boundary sampler) on the "
                         "8-virtual-device CPU mesh; emits one JSON line, "
                         "full runs write BENCH_MEM_r12.json")
    ap.add_argument("--profile-bench", action="store_true",
                    help="device-time profiler acceptance (ISSUE 15): "
                         "attribution coverage of a profiled dp-8 fit "
                         "window (>=80%%), top-K hotspot table, measured "
                         "roofline rows, measured-vs-modeled MFU delta, "
                         "out-of-window overhead (<0.5%%) -> "
                         "BENCH_PROFILE_r18.json (one JSON line with "
                         "--smoke)")
    ap.add_argument("--health-bench", action="store_true",
                    help="price the in-graph training-health stats engine "
                         "on the dp-8 CPU mesh (FLOP-model overhead, "
                         "per-layer table, injected-anomaly detection "
                         "latency) -> BENCH_HEALTH_r17.json (full run)")
    ap.add_argument("--trace-bench", action="store_true",
                    help="flight-recorder + distributed-trace propagation "
                         "overhead on the dp-8 fused step (the ISSUE 6 "
                         "<2%% acceptance bound); emits "
                         "BENCH_TRACE_r10.json (full run)")
    ap.add_argument("--compile-bench", action="store_true",
                    help="cold vs warm (persistent compilation cache) "
                         "time-to-first-step + AOT warmup wall time; "
                         "emits BENCH_COMPILE_r07.json")
    ap.add_argument("--compile-bench-child",
                    choices=("plain", "aot"), default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--remat", nargs="?", const=r"unit\d+_out$", default="",
                    help="rematerialize activations per residual unit "
                         "(MXNET_TPU_REMAT boundary regex; bare --remat "
                         "uses the ResNet unit boundaries) — trades MXU "
                         "recompute for HBM traffic on the bandwidth-bound "
                         "step")
    args = ap.parse_args()
    if args.remat:
        os.environ["MXNET_TPU_REMAT"] = args.remat

    if args.comm_bench:
        # CPU-mesh bench by design (see run_comm_bench): force the cpu
        # platform + 8 virtual devices BEFORE the first jax import so the
        # collective plan is inspectable without hardware
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_comm_bench(args)
        return

    if args.overlap_bench:
        # same CPU-mesh rig as --comm-bench: schedule structure and the
        # stale-sync pipeline are measurable without hardware
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_overlap_bench(args)
        return

    if args.kernel_bench:
        # same CPU-mesh rig: interpret-mode kernels + HLO structure are
        # measurable without hardware (the roofline row schema is the
        # TPU contract; no on-chip kernel rates are on record)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_kernel_bench(args)
        return

    if args.telemetry_bench:
        # same CPU-mesh rig as --comm-bench: the hub/timeline tax is a
        # host-side number, measurable without hardware
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_telemetry_bench(args)
        return

    if args.trace_bench:
        # same CPU-mesh rig: the flight/trace tax is host-side
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_trace_bench(args)
        return

    if args.mem_bench:
        # same CPU-mesh rig: ledger/sampler tax is host-side bookkeeping
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_mem_bench(args)
        return

    if args.profile_bench:
        # same CPU-mesh rig: the capture/attribution machinery is
        # backend-agnostic (the trace parser reads the CPU backend's
        # instruction lanes; a TPU xplane dump feeds the same tables)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_profile_bench(args)
        return

    if args.health_bench:
        # same CPU-mesh rig: the stats live inside the fused step, so the
        # FLOP-model overhead and the detector latency are measurable
        # without hardware
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_health_bench(args)
        return

    if args.lockwatch_bench:
        # same CPU-mesh rig: lock bookkeeping is host-side, and the two
        # soaked paths (group kvstore, elastic resize) run without hardware
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_lockwatch_bench(args)
        return

    if args.ckpt_bench:
        # same CPU-mesh rig: the snapshot stall, writer drain and both
        # recovery tiers are host+virtual-world paths, no hardware needed
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_ckpt_bench(args)
        return

    if args.elastic_bench:
        # same CPU-mesh rig: the resize protocol (quiesce/reshard/replan/
        # rewarm) is fully exercisable on the 8-virtual-device world
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_elastic_bench(args)
        return

    if args.controller_bench:
        # same CPU-mesh rig: the sense->decide->actuate loop (blame,
        # evict, backfill, retier) runs end-to-end on the virtual world
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        run_controller_bench(args)
        return

    if args.compile_bench_child:
        # measured subprocess of --compile-bench: no watchdog/probe — the
        # parent bounds each child's runtime
        if args.batch_size > 64:
            args.batch_size = 64
        run_compile_bench_child(args)
        return
    if args.compile_bench:
        if args.batch_size > 64:
            args.batch_size = 64  # compile cost, not throughput, is measured
        run_compile_bench(args)
        return

    if args.mode == "pipeline":
        run_pipeline_bench(args)
        return

    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit(f"bench.py --mode {args.mode}: JAX found no accelerator "
                 f"({dev}); this mode times the chip and has no CPU form")

    if args.mode == "io":
        run_io_bench(args)
        return

    from mxnet_tpu.telemetry.mfu import device_peak_flops

    peak_bf16 = device_peak_flops(dev)
    if peak_bf16 is None:
        sys.exit(f"bench.py: no published peak for device kind "
                 f"{dev.device_kind!r} in telemetry.mfu.DEVICE_PEAKS")
    print(f"bench device: {dev} ({dev.device_kind})", file=sys.stderr)

    step, params, moms, aux = build_train_step(
        args.batch_size, layout=args.layout, model=args.model)
    rng = np.random.RandomState(0)
    data = jax.device_put(
        rng.randn(*_data_shape(args.batch_size, args.layout)).astype(np.float32))
    label = jax.device_put(
        rng.randint(0, 1000, (args.batch_size,)).astype(np.float32))

    import jax.numpy as jnp

    # Self-accounting FLOPs: XLA's cost analysis of the exact compiled step.
    compiled = step.lower(params, moms, aux, data, label).compile()
    step_gflops = float(compiled.cost_analysis()["flops"]) / 1e9

    # Timed region runs ON DEVICE (fori_loop, dynamic trip count) and the
    # per-step cost is the slope between a short and a long run, so the
    # constant dispatch + readback cost of a run cancels.
    def loop_step(s):
        p, m, a = step(s[0], s[1], s[2], data, label)
        return (p, m, a)

    @jax.jit
    def run(s, k):
        return jax.lax.fori_loop(0, k, lambda i, t: loop_step(t), s)

    if args.steps < 8:
        print(f"--steps {args.steps} too small for slope timing "
              "(need >=8); raising to 8", file=sys.stderr)
        args.steps = 8
    k1 = max(2, args.steps // 4)
    k2 = args.steps

    state = run((params, moms, aux), k1)  # compile + warm
    float(jnp.sum(state[0]["fc1_bias"]))
    t0 = time.perf_counter()
    state = run(state, k1)
    float(jnp.sum(state[0]["fc1_bias"]))
    t1 = time.perf_counter()
    state = run(state, k2)
    float(jnp.sum(state[0]["fc1_bias"]))
    t2 = time.perf_counter()
    step_time = _checked_slope(t0, t1, t2, k1, k2, "train step")

    images_per_sec = args.batch_size / step_time

    # MFU uses the STANDARD model-FLOP count (ResNet-50/224 fwd = 4.09
    # GFLOP at 2 FLOP/MAC, train = 3x -> 12.27) so the figure is comparable
    # across frameworks; XLA's cost-analysis count of the actual compiled
    # step (which includes BN stats, recompute, optimizer arithmetic) is
    # reported alongside. Inception-BN has no standard published count at
    # this input config, so its achieved-TFLOPs derive from the XLA count.
    gflop_xla = step_gflops / args.batch_size
    gflop_analytic = gflop_xla if args.model == "inception_bn" else 12.27
    achieved_tflops = images_per_sec * gflop_analytic / 1e3
    peak = measured_matmul_peak_tflops()

    baseline = 97.0  # Inception-BN img/s, 1x GTX 980 cuDNN v3 (BASELINE.md)
    # resnet50: same-FLOP-class comparison; inception_bn: SAME ARCHITECTURE
    # as the anchor — the apples-to-apples number
    print(json.dumps({
        "metric": f"{args.model}_imagenet_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "vs_baseline": round(images_per_sec / baseline, 3),
        "baseline_comparison": ("same_architecture"
                                if args.model == "inception_bn"
                                else "same_flop_class"),
        "step_ms": round(step_time * 1e3, 2),
        "batch_size": args.batch_size,
        "gflop_per_image": gflop_analytic,
        "gflop_per_image_xla_cost_model": round(gflop_xla, 2),
        "achieved_model_tflops": round(achieved_tflops, 1),
        "measured_matmul_peak_tflops": round(peak, 1),
        "mfu_vs_measured_peak": round(achieved_tflops / peak, 3),
        "mfu_vs_published_peak": round(
            achieved_tflops / (peak_bf16 / 1e12), 3),
        "timing": "device_loop_slope",
    }))


if __name__ == "__main__":
    main()
