#!/usr/bin/env python3
"""Proof of life on the chip: FeedForward.fit at full width on a TPU v5e.

Run from the root of a checkout, on a machine whose JAX finds a TPU:

    python3 chip_smoke.py

One process, no children. It drives the main path once through the entry
points a user calls, checks what comes out, and prints per-phase wall
time and compile counts. The phases:

  fit        ResNet-50 (1000 classes, NHWC, bf16 compute, 224x224, batch
             256, no width or resolution cut): precompile, fit over 4
             synthetic batches for 2 epochs, predict one batch. All
             state on a TPU device; loss finite and falling; the train
             program compiled once and epoch 2 compiled nothing; the OOM
             preflight ran against the chip's real HBM budget; predict
             agrees with a float32 run of the same weights on the host.
  kernels    every kernel in ops/pallas/registry.py, compiled by Mosaic,
             once, against its pure-jnp reference.
  four-chip  (only where >= 4 TPU devices are visible) the same fit on a
             dp=4 mesh — 64-row batch shards, replicas bitwise equal and
             moved — and one TransformerLM train step on dp2 x tp2 and
             tp2 x sp2 against the one-chip step.

Any failed check raises: the exit code is non-zero and no result line is
printed. Without a TPU, with MXNET_TPU_PALLAS_INTERPRET set, or outside
a checkout it refuses to run with a one-line reason. On success the last
line of stdout is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time

PLATFORM = "tpu"

# Full width; a debugging driver may import this module and shrink these.
SIZES = {
    "image": 224, "batch": 256, "batches": 4, "epochs": 2, "classes": 1000,
    "lr": 0.001,
    "ref_rows": 8,           # rows of the float32 host reference for predict
    "flash_seq": 2048, "flash_heads": 4,
    "slab": (4, 1 << 18),    # (rows, elements) of the comm-kernel slab
    "lm": {"d_model": 512, "n_layers": 4, "n_heads": 8, "max_len": 2048,
           "vocab_size": 32000},
    "lm_batch": 4,
}


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


class Phase:
    """Times one phase and reports the compile-registry delta over it."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        import mxnet_tpu as mx

        self.t0 = time.perf_counter()
        self.c0 = mx.utils.compile_stats()
        log(f"phase {self.name}: start")
        return self

    def __exit__(self, exc_type, exc, tb):
        import mxnet_tpu as mx

        if exc_type is not None:
            log(f"phase {self.name}: FAILED after "
                f"{time.perf_counter() - self.t0:.1f}s: {exc!r}")
            return False
        c1 = mx.utils.compile_stats()
        log(f"phase {self.name}: ok in {time.perf_counter() - self.t0:.1f}s "
            f"(XLA compiles {c1['compiles'] - self.c0['compiles']}, "
            f"{c1['compile_seconds'] - self.c0['compile_seconds']:.1f}s; "
            f"persistent-cache hits "
            f"{c1['persistent_cache_hits'] - self.c0['persistent_cache_hits']})")
        return False


def on_platform(tree):
    """Every array leaf of ``tree`` lives only on PLATFORM devices."""
    import jax

    return all(d.platform == PLATFORM
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array) for d in leaf.devices())


# -- the main path: FeedForward.fit ---------------------------------------------

def synthetic_images(sizes, seed=0):
    """Two separable classes labelled 0/1 on the 1000-way head, so the loss
    can fall within a few steps."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = sizes["batch"] * sizes["batches"]
    y = (np.arange(n) % 2).astype(np.float32)
    x = rng.standard_normal((n, sizes["image"], sizes["image"], 3),
                            dtype=np.float32)
    x += (y * 2 - 1)[:, None, None, None] * 0.5
    return x, y


class StepSpy:
    """Wraps the train-step callable fit builds, without changing what it
    dispatches: keeps the first call's parameters (host copies), and the
    last call's placed batch and outputs. Outputs of earlier steps are
    donated to the next step, so only the last call's are still alive
    after fit returns."""

    def __init__(self, model):
        self.calls = 0
        self.initial = None
        self.batch = None
        self.outputs = None
        build = model._build_train_step

        def spy_build(*args, **kwargs):
            run = build(*args, **kwargs)

            def spied(params, opt_state, aux, batch, *rest):
                import numpy as np

                if self.initial is None:
                    self.initial = {k: np.array(v) for k, v in params.items()}
                out = run(params, opt_state, aux, batch, *rest)
                self.calls += 1
                self.batch, self.outputs = batch, out
                return out

            spied.__dict__.update(run.__dict__)  # keeps run._tracked
            return spied

        model._build_train_step = spy_build


def fit_resnet50(sizes, ctx, kvstore):
    """precompile + fit through the public API. Returns (model, spy,
    images) after checking loss, compile counts and the OOM preflight."""
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet50
    from mxnet_tpu.telemetry import memory

    x, y = synthetic_images(sizes)
    train = mx.io.NDArrayIter(x, y, batch_size=sizes["batch"], shuffle=False)
    model = mx.FeedForward(
        resnet50(num_classes=sizes["classes"], layout="NHWC"), ctx=ctx,
        num_epoch=sizes["epochs"], compute_dtype=jnp.bfloat16,
        initializer=mx.init.Xavier(), learning_rate=sizes["lr"],
        momentum=0.9)
    spy = StepSpy(model)
    metric = mx.metric.CrossEntropy()

    stats0 = mx.utils.compile_stats()
    budget = memory.hbm_budget()
    check(budget and budget > 0,
          f"the backend reports an HBM budget (got {budget!r}); without "
          "one the OOM preflight is inert")
    t0 = time.perf_counter()
    warm = model.precompile(data=train, eval_metric=metric, kvstore=kvstore)
    _, plan = memory.largest_plan(labels=warm["labels"])
    check(plan is not None, "precompile registered the train program's "
          "memory plan for the preflight")
    log(f"precompile: {warm['programs']} program(s) in "
        f"{time.perf_counter() - t0:.1f}s; plan temp+output "
        f"{memory.program_step_bytes(plan) / 2**30:.2f} GiB of a "
        f"{budget / 2**30:.2f} GiB budget (preflight passed)")

    epoch_loss, epoch_stats = [], []

    def at_epoch_end(epoch, symbol, arg_params, aux_params):
        epoch_loss.append(float(metric.get()[1]))
        epoch_stats.append(mx.utils.compile_stats())

    t0 = time.perf_counter()
    model.fit(train, eval_metric=metric, kvstore=kvstore,
              batch_size=sizes["batch"], epoch_end_callback=at_epoch_end)
    log(f"fit: {sizes['epochs']} epochs x {sizes['batches']} batches of "
        f"{sizes['batch']} in {time.perf_counter() - t0:.1f}s; train "
        f"cross-entropy per epoch {[round(v, 4) for v in epoch_loss]}")

    check(spy.calls == sizes["epochs"] * sizes["batches"],
          f"{spy.calls} train steps ran")
    check(len(epoch_loss) == sizes["epochs"] and np.all(np.isfinite(epoch_loss)),
          f"finite loss every epoch: {epoch_loss}")
    check(epoch_loss[-1] < epoch_loss[0],
          f"loss falls: {epoch_loss[0]:.4f} -> {epoch_loss[-1]:.4f}")

    # this fit's share of the registry: an earlier phase may have trained
    # the same symbol under the same label
    train_rows = {}
    for label, after in mx.utils.compile_stats()["per_function"].items():
        before = stats0["per_function"].get(label, {})
        delta = {k: v - before.get(k, 0) for k, v in after.items()}
        if label.startswith("train_step:") and any(delta.values()):
            train_rows[label] = delta
    check(len(train_rows) == 1, f"one train program: {sorted(train_rows)}")
    row = next(iter(train_rows.values()))
    log(f"train program: {row}")
    check(row["programs"] == 1 and row["precompiles"] == 1
          and row["misses"] == 0 and row["compiles"] <= 1,
          f"the train program was built exactly once: {row}")
    check(row["aot_hits"] == spy.calls,
          f"every step dispatched the precompiled executable: {row}")
    first, last = epoch_stats[0], epoch_stats[-1]
    check(last["compiles"] == first["compiles"]
          and last["misses"] == first["misses"],
          f"no compile after epoch 1: compiles {first['compiles']} -> "
          f"{last['compiles']}, jit misses {first['misses']} -> "
          f"{last['misses']}")
    return model, spy, x


def phase_fit_one_chip(sizes):
    import jax
    import numpy as np

    import mxnet_tpu as mx

    model, spy, x = fit_resnet50(sizes, mx.Context(PLATFORM, 0), "local")
    dev = mx.Context(PLATFORM, 0).jax_device
    check(on_platform(spy.outputs) and on_platform(spy.batch),
          f"params, optimizer state, batch and step outputs on {PLATFORM}")
    leaves = jax.tree_util.tree_leaves(spy.outputs)
    check(all(leaf.devices() == {dev} for leaf in leaves),
          f"all {len(leaves)} state leaves on {dev}")
    log(f"{len(leaves)} state/output leaves, all on {dev}")

    rows = sizes["batch"]
    t0 = time.perf_counter()
    prob = model.predict(x[:rows])
    log(f"predict: {prob.shape} in {time.perf_counter() - t0:.1f}s")
    check(prob.shape == (rows, sizes["classes"]), f"predict shape {prob.shape}")
    check(np.all(np.isfinite(prob)), "predict is finite")
    check(np.allclose(prob.sum(axis=1), 1.0, atol=1e-2),
          "predicted rows are distributions")

    # the same weights, float32, on the host CPU backend: a gross error
    # (layout, placement, a kernel computing something else) moves whole
    # probabilities; bf16 rounding through 50 layers does not
    n = sizes["ref_rows"]
    ref_model = mx.FeedForward(
        model.symbol, ctx=mx.cpu(), arg_params=model.arg_params,
        aux_params=model.aux_params)
    t0 = time.perf_counter()
    ref = ref_model.predict(x[:n])
    diff = float(np.abs(prob[:n] - ref).max())
    agree = float((prob[:n].argmax(1) == ref.argmax(1)).mean())
    log(f"predict vs float32 host reference on {n} rows in "
        f"{time.perf_counter() - t0:.1f}s: max |dp| = {diff:.4f}, "
        f"argmax agreement {agree:.2f}")
    check(diff <= 0.1, f"bf16 chip predict agrees with the f32 host "
          f"reference (max |dp| {diff:.4f})")


# -- Pallas kernels against their references ------------------------------------

def compiled_call(fn, *args):
    """Compile ``fn`` for ``args``, require a Mosaic custom call in the
    executable (nothing interpreted, nothing left to XLA), and run it."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    if PLATFORM == "tpu":
        check("tpu_custom_call" in exe.as_text(),
              f"{getattr(fn, '__name__', fn)} lowered to a Mosaic kernel")
    return exe(*args)


def exactly_equal(got, want):
    """Same shape, every element ``==`` (the repo's parity tests' notion:
    -0.0 and +0.0, which the fused round-trip can differ in, compare
    equal)."""
    import numpy as np

    return np.array_equal(np.asarray(got), np.asarray(want))


def rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def kernels_flash(sizes, covered):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas import flash_attention
    from mxnet_tpu.parallel.sequence import attention_reference

    s, h = sizes["flash_seq"], sizes["flash_heads"]

    def loss_flash(q, k, v, w):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def loss_ref(q, k, v, w):
        o = attention_reference(q, k, v, causal=True)
        return jnp.sum(o * w), o

    for d in (64, 128):
        keys = jax.random.split(jax.random.PRNGKey(d), 4)
        q, k, v = (jax.random.normal(kk, (1, h, s, d), jnp.float32)
                   .astype(jnp.bfloat16) for kk in keys[:3])
        w = jax.random.normal(keys[3], (1, h, s, d), jnp.float32)
        (_, o), grads = compiled_call(
            jax.value_and_grad(loss_flash, argnums=(0, 1, 2), has_aux=True),
            q, k, v, w)
        # reference: same bf16 inputs widened to f32, dense softmax at
        # full matmul precision (the TPU default rounds operands to bf16)
        with jax.default_matmul_precision("highest"):
            (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
                loss_ref, argnums=(0, 1, 2), has_aux=True))(
                    *(a.astype(jnp.float32) for a in (q, k, v)), w)
        fwd = float(np.abs(np.asarray(o, np.float32)
                           - np.asarray(o_ref)).max())
        errs = [rel_err(g, r) for g, r in zip(grads, g_ref)]
        log(f"flash fwd+bwd causal bf16 s={s} d={d}: max |do| = {fwd:.2e}, "
            f"rel err dq/dk/dv = {', '.join(f'{e:.2e}' for e in errs)}")
        check(fwd <= 5e-2, f"flash forward d={d} within bf16 tolerance")
        check(max(errs) <= 3e-2, f"flash backward d={d} within bf16 tolerance")
    covered |= {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}


def kernels_comm(sizes, covered):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import comm
    from mxnet_tpu.ops.pallas import comm_kernels as ck

    rows = jax.random.normal(jax.random.PRNGKey(1), sizes["slab"],
                             jnp.float32)
    for mode in ("int8", "twobit"):
        spec = comm.CompressionSpec(mode)
        pay, dq = compiled_call(
            lambda x: ck.fused_quantize(spec, x, want_dequant=True), rows)
        ref = jax.jit(lambda x: comm.encode(spec, x))(rows)
        ref_dq = jax.jit(lambda p: comm.decode(spec, p))(ref)
        # decode side: fed the REFERENCE payload, so a quantize mismatch
        # cannot hide a decode one
        dec = compiled_call(lambda p: ck.fused_dequant(spec, p), ref)
        tot = compiled_call(lambda p: ck.fused_dequant_sum(spec, p), ref)
        # the kernels' contract: the codec's wire, value for value
        check(set(pay) == set(ref), f"{mode} payload keys match the codec")
        for k in ref:
            check(pay[k].dtype == ref[k].dtype and exactly_equal(pay[k], ref[k]),
                  f"{mode} payload[{k!r}] equals the codec's")
        check(exactly_equal(dq, ref_dq), f"{mode} fused round-trip == decode")
        check(exactly_equal(dec, ref_dq), f"{mode} dequant == decode")
        log(f"comm kernels {mode}: payload {sorted(ref)}, fused round-trip "
            "and dequant exactly equal to the codec")
        check(np.allclose(np.asarray(tot),
                          np.asarray(ref_dq).sum(axis=0),
                          rtol=1e-5, atol=1e-5),
              f"{mode} dequant+sum matches sum(decode)")
        covered |= {f"quant_{mode}", f"dequant_{mode}", f"dequant_sum_{mode}"}


def kernels_adam(covered):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.ops.pallas import fused_adam_apply

    opt = mx.optimizer.create("adam", learning_rate=1e-3, wd=1e-4,
                              rescale_grad=0.5, clip_gradient=1.0,
                              fused=False)
    shapes = {"w1": (1024, 1000), "b1": (1000,), "w2": (3, 3, 64, 64),
              "s": ()}
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 4 * len(shapes)))
    params = {k: jax.random.normal(next(keys), s) for k, s in shapes.items()}
    grads = {k: jax.random.normal(next(keys), s) for k, s in shapes.items()}
    states = {k: (0.1 * jax.random.normal(next(keys), s),
                  jnp.square(jax.random.normal(next(keys), s)),
                  jnp.float32(3.0)) for k, s in shapes.items()}
    got = compiled_call(
        lambda p, g, s: fused_adam_apply(opt, p, g, s, 1e-3),
        params, grads, states)
    want = jax.jit(lambda p, g, s: opt.apply(p, g, s, 1e-3))(
        params, grads, states)
    flat_g = jax.tree_util.tree_leaves(got)
    flat_w = jax.tree_util.tree_leaves(want)
    check(len(flat_g) == len(flat_w)
          and all(exactly_equal(a, b) for a, b in zip(flat_g, flat_w)),
          "fused Adam equals the per-leaf optimizer exactly")
    log(f"fused_adam vs per-leaf Adam over {len(flat_g)} leaves: exactly "
        "equal")
    covered |= {"fused_adam"}


def kernels_int8_matmul(covered):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas import int8_matmul

    x = jax.random.normal(jax.random.PRNGKey(3), (256, 2048), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (1000, 2048), jnp.float32)
    got = compiled_call(lambda a, b: int8_matmul(a, b), x, w)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda a, b: a @ b.T)(x, w)
    err = rel_err(got, want)
    log(f"int8_matmul (256x2048 @ 2048x1000) vs f32: rel err {err:.2e}")
    check(got.shape == want.shape and err <= 2e-2,
          "int8 matmul within its quantization error of the f32 product")
    covered |= {"int8_matmul"}


def kernels_moe(covered):
    """The expert layer's two kernels at the widths of ``laguna_xs2``
    (8,192 rows, top-8, hidden 2,048, bf16) with an eighth of the picks
    held: the gather bit for bit, the float32 sum to one bf16 rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas.moe import moe_combine, moe_dispatch

    rows, k, width = 8192, 8, 2048
    rng = np.random.default_rng(5)
    key = np.where(rng.random(rows * k) < 0.125,
                   rng.integers(0, 32, rows * k), 32)
    order = jnp.asarray(np.argsort(key, kind="stable"), jnp.int32)
    n = int((key < 32).sum())
    token, count = order // k, jnp.int32(n)
    x = jnp.asarray(rng.standard_normal((rows, width)), jnp.bfloat16)
    scale = jnp.asarray(rng.random(rows * k), jnp.float32)
    xs = compiled_call(moe_dispatch, x, token, count, scale)
    want = (x[token].astype(jnp.float32) * scale[:, None]) \
        .astype(jnp.bfloat16)
    check(exactly_equal(xs[:n], want[:n]),
          "expert dispatch equals the scaled gather on the held entries")
    held = (jnp.arange(rows * k) < n)[:, None]
    y = jnp.where(held, xs, jnp.nan)      # nothing behind the count is read
    back = compiled_call(lambda y, t, c, w: moe_combine(y, t, c, rows, w),
                         y, token, count, scale)
    want = jnp.zeros((rows, width), jnp.float32).at[token].add(
        jnp.where(held, y.astype(jnp.float32) * scale[:, None], 0))
    err = rel_err(back, want)
    log(f"expert dispatch + combine, {n} of {rows * k} entries held: "
        f"rel err of the sum {err:.2e}")
    check(err <= 4e-3, "expert combine within one bf16 rounding of the "
          "float32 sum")
    covered |= {"moe_dispatch", "moe_combine"}


def phase_kernels(sizes):
    from mxnet_tpu.ops.pallas import kernel_names

    covered = set()
    kernels_flash(sizes, covered)
    kernels_comm(sizes, covered)
    kernels_adam(covered)
    kernels_int8_matmul(covered)
    kernels_moe(covered)
    check(covered == set(kernel_names()),
          f"every registered kernel ran: missing "
          f"{sorted(set(kernel_names()) - covered)}, unknown "
          f"{sorted(covered - set(kernel_names()))}")
    log(f"{len(covered)} registered kernels compiled and matched: "
        f"{', '.join(sorted(covered))}")


# -- four chips -----------------------------------------------------------------

def phase_fit_four_chips(sizes):
    import jax
    import numpy as np

    import mxnet_tpu as mx

    ctx = [mx.Context(PLATFORM, i) for i in range(4)]
    _, spy, _ = fit_resnet50(sizes, ctx, "device")
    devs = [c.jax_device for c in ctx]
    check(len({d.id for d in devs}) == 4
          and all(d.platform == PLATFORM for d in devs),
          f"four distinct {PLATFORM} devices: {devs}")

    data = spy.batch["data"]
    shards = sorted(data.addressable_shards, key=lambda s: s.device.id)
    per = sizes["batch"] // 4
    check([s.device for s in shards] == sorted(devs, key=lambda d: d.id)
          and all(s.data.shape[0] == per for s in shards),
          f"each chip holds a {per}-row shard of the batch: "
          f"{[(str(s.device), s.data.shape) for s in shards]}")

    params = spy.outputs[0]
    check(on_platform(spy.outputs), "all dp-4 state on the chips")
    moved = 0
    for name, arr in params.items():
        check({s.device for s in arr.addressable_shards} == set(devs),
              f"{name} has a replica on every chip")
        replicas = [np.asarray(s.data) for s in arr.addressable_shards]
        check(all(r.tobytes() == replicas[0].tobytes()
                  for r in replicas[1:]),
              f"{name}: four replicas bitwise equal")
        moved += not np.array_equal(replicas[0], spy.initial[name])
    log(f"dp=4 over {[str(d) for d in devs]}: batch shards of {per} rows; "
        f"{len(params)} parameters bitwise equal across replicas, "
        f"{moved} moved from their initial values")
    check(moved == len(params), f"every parameter moved ({moved} of "
          f"{len(params)}): the all-reduced gradient reached every chip")


def phase_transformer_meshes(sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models.transformer import (TransformerLM,
                                              transformer_lm_config)
    from mxnet_tpu.parallel import make_mesh

    cfg = transformer_lm_config(dtype=jnp.bfloat16, attn_impl="flash",
                                **sizes["lm"])
    seq, batch = cfg["max_len"], sizes["lm_batch"]
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], (batch, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, axis=1)
    devices = [d for d in jax.devices() if d.platform == PLATFORM][:4]

    def one_step(mesh):
        model = TransformerLM(cfg)
        params, moms = model.init_sharded(mesh, seed=0)
        step = model.make_train_step(mesh, lr=1e-3)
        new_params, _, loss = step(params, moms, tokens, targets)
        jax.block_until_ready(new_params)
        check(on_platform(new_params), "transformer state on the chips")
        return float(loss)

    base = one_step(None)
    check(np.isfinite(base), f"one-chip loss finite: {base}")
    for name, axes in (("dp2 x tp2", {"dp": 2, "tp": 2}),
                       ("tp2 x sp2", {"tp": 2, "sp": 2})):
        loss = one_step(make_mesh(devices=devices, **axes))
        log(f"TransformerLM d={cfg['d_model']} L={cfg['n_layers']} s={seq} "
            f"bf16 flash, {name}: loss {loss:.4f} vs one chip {base:.4f}")
        check(abs(loss - base) <= 5e-2,
              f"{name} loss {loss:.4f} within bf16 tolerance of {base:.4f}")


# -- entry ----------------------------------------------------------------------

def main():
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET"):
        sys.exit("chip_smoke: MXNET_TPU_PALLAS_INTERPRET is set; this run "
                 "must execute compiled kernels, unset it")
    try:
        import jax
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import jax: {e}")
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        sys.exit(f"chip_smoke: JAX found no usable backend: "
                 f"{str(e).splitlines()[0]}")
    if dev.platform != PLATFORM:
        sys.exit(f"chip_smoke: JAX found no accelerator (first device is "
                 f"{dev}, platform {dev.platform!r}); this script only runs "
                 f"on a {PLATFORM}")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import mxnet_tpu as mx
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import mxnet_tpu ({e}); run it from "
                 "the root of a checkout")

    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="%(message)s")
    count = len(jax.devices())
    log(f"jax {jax.__version__}, {count} x {dev.device_kind} "
        f"({dev.platform}); compile cache: "
        f"{mx.utils.compile.persistent_cache_dir()}")
    t0 = time.perf_counter()
    with Phase("fit (ResNet-50 b256 bf16 NHWC, one chip)"):
        phase_fit_one_chip(SIZES)
    with Phase("kernels (ops/pallas registry vs references)"):
        phase_kernels(SIZES)
    if sum(d.platform == PLATFORM for d in jax.devices()) >= 4:
        with Phase("four-chip fit (dp=4, kvstore=device)"):
            phase_fit_four_chips(SIZES)
        with Phase("four-chip TransformerLM (dp2 x tp2, tp2 x sp2)"):
            phase_transformer_meshes(SIZES)
    else:
        log(f"four-chip phases did not run: {count} {PLATFORM} device(s) "
            "visible, 4 needed")
    stats = mx.utils.compile_stats()
    log(f"total {time.perf_counter() - t0:.1f}s; XLA compiles "
        f"{stats['compiles']} ({stats['compile_seconds']:.1f}s); "
        f"persistent-cache hits {stats['persistent_cache_hits']} "
        f"(saved {stats['persistent_cache_saved_seconds']:.1f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()
